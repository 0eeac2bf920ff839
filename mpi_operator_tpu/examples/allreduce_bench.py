"""Allreduce scaling-efficiency harness.

BASELINE.md's north-star for the reference's data plane is Horovod/NCCL
allreduce scaling efficiency — ≥90% going 4→32 chips. The TPU-native
equivalent op is the explicit shard_map allreduce
(parallel/collectives.sharded_allreduce_fn); this harness times it across
growing device counts and payload sizes and emits the efficiency curve as
JSON, so the day a multi-chip slice is attached the same entrypoint
produces the BASELINE-comparable number (ref README.md:113-131 publishes
only training throughput; Horovod's own benchmarks report the allreduce
bus bandwidth this harness computes).

Metrics per (devices n, payload):
  time_ms   — mean wall time of one allreduce (chained dispatch, closed
              by one host read of the last result, which waits for the
              whole chain)
  algbw_gbs — payload_bytes / time (the application-visible rate)
  busbw_gbs — algbw × 2(n-1)/n, the link-level rate of a ring allreduce;
              flat-over-n busbw = perfect scaling
  efficiency — busbw(n) / busbw(n₀), n₀ = smallest multi-device count
              (matches the BASELINE "4→32 ≥ 90%" definition: time per
              allreduce should not grow as the ring grows)

On one real chip the harness degenerates to the n=1 floor (reduction is a
local copy); the CPU-virtual 8-device mesh of the tests exercises the
full curve shape today.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence


def run_allreduce_benchmark(
    payload_mb: Sequence[float] = (1.0, 16.0, 64.0),
    device_counts: Optional[Sequence[int]] = None,
    iters: int = 10,
    log: Callable[[str], None] = print,
) -> Dict[str, object]:
    """Time sharded allreduce-mean across device counts; return the curve.

    Returns {"points": [{devices, payload_mb, time_ms, algbw_gbs,
    busbw_gbs, efficiency}...], "efficiency_curve": {n: eff}} where
    efficiency is relative to the smallest multi-device count at the
    LARGEST payload (the bandwidth-bound regime the BASELINE number is
    about)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel import MeshConfig, make_mesh
    from ..parallel.collectives import sharded_allreduce_fn

    devices = jax.devices()
    if device_counts is None:
        device_counts = [n for n in (1, 2, 4, 8, 16, 32, 64, 128)
                         if n <= len(devices)]
    points: List[Dict[str, float]] = []
    for n in device_counts:
        mesh = make_mesh(MeshConfig(dp=n), devices=devices[:n])
        fn = sharded_allreduce_fn(mesh, ("dp",))
        for mb in payload_mb:
            nelem = int(mb * (1 << 20) / 4)
            nelem -= nelem % max(n, 1)          # divisible over dp
            x = jax.device_put(
                jnp.arange(nelem, dtype=jnp.float32) / nelem,
                NamedSharding(mesh, P("dp")))
            float(fn(x)[0])                     # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x)
            float(out[0])                       # waits for the whole chain
            dt = (time.perf_counter() - t0) / iters
            nbytes = nelem * 4
            algbw = nbytes / dt / 1e9
            busbw = algbw * (2 * (n - 1) / n if n > 1 else 1.0)
            points.append({"devices": n, "payload_mb": round(mb, 3),
                           "time_ms": round(dt * 1e3, 4),
                           "algbw_gbs": round(algbw, 3),
                           "busbw_gbs": round(busbw, 3)})
            log(f"allreduce n={n:<3d} {mb:6.1f} MB: {dt*1e3:8.3f} ms  "
                f"algbw {algbw:7.2f} GB/s  busbw {busbw:7.2f} GB/s")

    # efficiency at the largest payload, relative to the smallest ring
    big = max(payload_mb)
    multi = [p for p in points
             if p["payload_mb"] == round(big, 3) and p["devices"] > 1]
    curve: Dict[str, float] = {}
    if multi:
        base = multi[0]["busbw_gbs"] or 1e-9
        for p in multi:
            eff = p["busbw_gbs"] / base
            curve[str(p["devices"])] = round(eff, 4)
            p["efficiency"] = round(eff, 4)
    return {"points": points, "efficiency_curve": curve}


def main(argv=None) -> int:
    argparse.ArgumentParser(
        prog="allreduce-bench",
        description="time the sharded allreduce at 1, 16 and 64 MB over "
                    "every power-of-two count of the visible devices; "
                    "one JSON line").parse_args(argv)
    from ..utils.compile_cache import enable_compile_cache
    from ._report import device_record
    enable_compile_cache()
    result = run_allreduce_benchmark(
        log=lambda s: print(s, file=sys.stderr))
    print(json.dumps({**result, **device_record()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
