#!/usr/bin/env python3
"""The tied head and its loss alone, value and both gradients, at the
training cells' shape (8 x 1024 tokens of 1024 against GPT-2's 50 304
rows, bfloat16 operands):

  logits      `lm_loss(tied_logits(h, wte), targets)` and the arg-max of
              the step's accuracy: what `LMTrainer` ran before PR 43;
  fused_xent  `train/lm_trainer.py::fused_lm_loss`, the path
              `LMTrainerConfig.fused_xent` selects for float32 compute
              (four products under `jax.checkpoint`, no accuracy);
  kernel      `ops/xent.py::tied_head_xent` as the trainer takes it on the
              TPU, by the tokens a block keeps and the table rows a grid
              step scores;
  scan        the same `custom_vjp` as a scan in plain `jax.numpy`, by
              its chunks (what runs off the TPU).

(Two more forms of the kernel were measured here in PR 43 and dropped with
their code: a block's two sweeps over the table one after the other, and
four products with nothing kept between a statistics kernel and a
backward kernel that scores each tile again: PERF.md section 6.)

    chiprun -- python scripts/head_loss_microbench.py

Each form runs `--calls` calls back to back, three times; a line a form in
`chiprun_out/head_loss_microbench.jsonl` with the best run's ms a call
beside what its products cost the MXU at its peak (3 x 0.844 TFLOP at
197 TFLOP/s: 12.86 ms), its loss, accuracy and gradients against the
`logits` form's. Without a TPU it exits; `--tiny` rehearses on the CPU
(the kernel interpreted) into `...microbench.tiny.jsonl` with `wall_ms`
alone, which means nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS = 197e12


def forms(interpret, rows, tiles, chunks):
    """{name: (h, table, y) -> (loss, accuracy)}; `table` float32 as the
    trainer's masters are, every form casts it."""
    import jax.numpy as jnp

    from mpi_operator_tpu.models.transformer import _head_matmul
    from mpi_operator_tpu.ops.xent import tied_head_xent
    from mpi_operator_tpu.train.lm_trainer import fused_lm_loss, lm_loss

    def logits(h, table, y):
        z = _head_matmul(h, table.astype(h.dtype))
        return lm_loss(z, y), jnp.mean(jnp.argmax(z, -1) == y)

    def fused_xent(h, table, y):
        return fused_lm_loss(h, table, y), jnp.full((), jnp.nan)

    out = {"logits": logits, "fused_xent": fused_xent}
    for t in tiles:
        for r in rows:
            out[f"kernel[rows={r},tile={t}]"] = \
                lambda h, table, y, r=r, t=t: tied_head_xent(
                    h, table, y, scan=False, rows=r, tile=t,
                    interpret=interpret)
    for c in chunks:
        out[f"scan[chunks={c}]"] = lambda h, table, y, c=c: tied_head_xent(
            h, table, y, scan=True, rows=c)
    return out


def operands(B, S, E, V):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(43), 3)
    h = jax.random.normal(ks[0], (B, S, E), jnp.bfloat16)
    table = 0.02 * jax.random.normal(ks[1], (V, E), jnp.float32)
    return h, table, jax.random.randint(ks[2], (B, S), 0, V - 47)


def _gap(a, b):
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def measure(name, fn, args, calls, want, on_tpu):
    import jax
    step = jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True))
    t0 = time.perf_counter()
    (loss, acc), (dh, dtable) = jax.block_until_ready(step(*args))
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = step(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    B, S, E = args[0].shape
    line = {"form": name, "tokens": B * S, "embed": E,
            "vocab": args[1].shape[0], "calls": calls,
            "loss": float(loss), "accuracy": float(acc),
            "compile_and_first_s": round(first, 3)}
    if want is not None:
        (wl, _), (wdh, wdt) = want
        line.update(loss_gap=abs(float(loss) - float(wl)),
                    dh_gap=_gap(dh, wdh), dtable_gap=_gap(dtable, wdt))
    if on_tpu:
        ms = 1e3 * best / calls
        bound = 1e3 * 3 * 2 * B * S * E * args[1].shape[0] / PEAK_FLOPS
        line.update(ms_per_call=ms, three_products_at_peak_ms=bound,
                    peak_bytes=jax.devices()[0].memory_stats().get(
                        "peak_bytes_in_use"))
    else:
        line["wall_ms"] = 1e3 * best / calls
    return line, ((loss, acc), (dh, dtable))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--rows", type=int, nargs="*", default=[256],
                    help="tokens a block of the kernel keeps (a cut last "
                    "block has hung the chip: take divisors of 8192)")
    ap.add_argument("--tiles", type=int, nargs="*", default=[2048])
    ap.add_argument("--chunks", type=int, nargs="*", default=[8])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.tiny:
        print("head_loss_microbench: needs a TPU (or --tiny to rehearse)",
              file=sys.stderr)
        return 2
    shape, calls = (8, 1024, 1024, 50304), args.calls
    if args.tiny:
        shape, calls = (2, 64, 128, 1000), 1
    out = os.path.join("chiprun_out", "head_loss_microbench"
                       + (".tiny" if args.tiny else "") + ".jsonl")
    os.makedirs("chiprun_out", exist_ok=True)
    ops = operands(*shape)
    device = jax.devices()[0].device_kind
    want = None
    with open(out, "a") as f:
        for name, fn in forms(not on_tpu, args.rows, args.tiles,
                              args.chunks).items():
            if args.only and name != "logits" and not any(
                    name.startswith(o) for o in args.only):
                continue
            try:
                line, got = measure(name, fn, ops, calls, want, on_tpu)
                if name == "logits":
                    want = got
            except Exception as e:     # a form the compiler refuses: a result
                line = {"form": name,
                        "refused": f"{type(e).__name__}: {str(e)[:600]}"}
            line["device"] = device
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
