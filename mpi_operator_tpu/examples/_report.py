"""What every benchmark record says about where and how it ran.

A number from a CPU run and a number from a chip run must never be told
apart by their surroundings alone, and a run that asked for a kernel must
say whether it got one. `with_run_report` wraps a `run_*_benchmark`
function so the metrics dict it returns names the device as jax reports
it and the attention implementations that were actually traced
(ops/attention.record_traced) — set by the dispatch sites, not copied
from a flag.
"""
from __future__ import annotations

import functools
from typing import Dict


def device_record() -> Dict[str, object]:
    """The device a record was measured on, as jax reports it."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def device_ids(tree) -> list:
    """Sorted ids of every device holding a shard of a leaf of `tree`."""
    import jax

    return sorted({d.id for leaf in jax.tree.leaves(tree)
                   for d in leaf.devices()})


def with_run_report(fn):
    """Run `fn` under `record_traced` and add `device_record()` plus
    `attention_impl` / `decode_impl` / `prefill_impl` (None when nothing
    of that kind was traced) to the metrics dict it returns — the dict
    itself, or the second element of a `(state, metrics)` pair."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from ..ops.attention import record_traced, traced_name

        with record_traced() as traced:
            out = fn(*args, **kwargs)
        metrics = out[1] if isinstance(out, tuple) else out
        metrics.update(device_record())
        metrics.update({f"{kind}_impl": traced_name(impls)
                        for kind, impls in traced.items()})
        return out
    return wrapper


__all__ = ["device_record", "device_ids", "with_run_report"]
