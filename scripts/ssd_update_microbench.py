#!/usr/bin/env python3
"""The Mamba-2 state update alone, at Granite-4.0-H-Small's head shape (128
heads of 64 channels over 128 states, one group, 48 rows), in the kept
form and beside the layout it replaced:

  packed   `ops/ssm.py::ssd_state_update` as the served model calls it:
           the state held [H / 2, N, 2 P], two heads side by side on a
           tile's 128 lanes (the KEPT form);
  padded   the same kernel over a state held [H, N, P] with P = 64 minor,
           half of each lane tile empty: what the parent's layout gives.

(A third form, the published layout [H, P, N] with the states minor, was
measured here in PR 42 and dropped with its kernel: 25.27 us a row against
the packed form's 13.09 and the padded one's 25.94, PERF.md section 6.)

and, as a yardstick that did not move, the kernel at Falcon-H1-34B's shape
(32 heads of 128 over 256 states, 2 groups, 96 rows).

    chiprun -- python scripts/ssd_update_microbench.py

Each form runs `--steps` updates in one program with the state carried
(donated), twice; a line a form in `chiprun_out/ssd_update_microbench.jsonl`
with the better run's us a call and us a row, beside the bytes' bound
(2 x 4 194 304 B a row at the HBM peak: 10.24 us). Every form's y and state
are compared with plain `jax.numpy` first. Without a TPU it exits; `--tiny`
rehearses on the CPU (kernels interpreted) into `...microbench.tiny.jsonl`
with `wall_us` alone, which means nothing.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9


def forms(interpret):
    """{name: (([G, H, N, P], groups) -> held, step(x, dt, A, B, C, D,
    held, fresh), (held, H) -> [G, H, N, P])}."""
    from mpi_operator_tpu.ops import ssm

    def packed_in(s, groups):
        H, P = s.shape[1], s.shape[3]
        return ssm._tiles_of(s, ssm.ssd_state_shape(1, H, P, groups, 1)[1])

    def packed(x, dt, A, B, C, D, held, fresh):
        return ssm.ssd_state_update(x, dt, A, B, C, D, held, fresh=fresh,
                                    interpret=interpret or None)

    def padded(x, dt, A, B, C, D, held, fresh):
        return ssm._ssd_update_call(x, dt, A, B, C, D, held, fresh,
                                    bool(interpret))

    return {"packed": (packed_in, packed, ssm._heads_of),
            "padded": (lambda s, _: s, padded, lambda held, _: held)}


def plain(x, dt, A, B, C, D, state, fresh):
    import jax.numpy as jnp
    G, H, P = x.shape
    K = B.shape[1]
    s = jnp.where(fresh[:, None, None, None], 0.0, state)
    Bh, Ch = (jnp.repeat(a, H // K, axis=1) for a in (B, C))
    s = jnp.exp(dt * A)[..., None, None] * s \
        + Bh[..., None] * (dt[..., None] * x)[:, :, None, :]
    return jnp.sum(s * Ch[..., None], axis=2) + D[:, None] * x, s


def operands(rows, heads, head_dim, groups, states):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (rows, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, heads)) - 4.0)
    A = -(1.0 + 15.0 * jax.random.uniform(ks[2], (heads,)))
    B, C = (jax.random.normal(k, (rows, groups, states)) for k in ks[3:5])
    state = jax.random.normal(ks[5], (rows, heads, states, head_dim))
    fresh = jnp.zeros((rows,), bool).at[1].set(True)
    return x, dt, A, B, C, jnp.ones((heads,)), state, fresh


def measure(name, form, shape, steps, on_tpu):
    import jax
    import jax.numpy as jnp
    to_held, step, to_plain = form
    x, dt, A, B, C, D, state, fresh = operands(**shape)
    held = to_held(state, shape["groups"])
    want_y, want_s = plain(x, dt, A, B, C, D, state, fresh)
    y, s = jax.jit(step)(x, dt, A, B, C, D, held, fresh)
    err = max(float(jnp.abs(y - want_y).max()),
              float(jnp.abs(to_plain(s, shape["heads"]) - want_s).max()))
    none = jnp.zeros_like(fresh)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(held):
        def body(_, carry):
            held, acc = carry
            y, held = step(x, dt, A, B, C, D, held, none)
            return held, acc + y[0, 0, 0]
        return jax.lax.fori_loop(0, steps, body, (held, jnp.float32(0)))

    t0 = time.perf_counter()
    held, _ = jax.block_until_ready(run(held))
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        held, _ = jax.block_until_ready(run(held))
        best = min(best, time.perf_counter() - t0)
    rows = shape["rows"]
    moved = 2 * 4 * shape["heads"] * shape["head_dim"] * shape["states"]
    line = {"form": name, **shape, "steps": steps, "max_abs_err": err,
            "held_shape": list(held.shape),
            "compile_and_first_s": round(first, 3)}
    if on_tpu:
        us = 1e6 * best / steps
        line.update(us_per_call=us, us_per_row=us / rows,
                    bound_us_per_row=1e6 * moved / HBM_BYTES_PER_S,
                    roofline_pct=100 * (moved / HBM_BYTES_PER_S)
                    / (best / steps / rows))
    else:
        line["wall_us"] = 1e6 * best / steps
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.tiny:
        print("ssd_update_microbench: needs a TPU (or --tiny to rehearse)",
              file=sys.stderr)
        return 2
    granite = dict(rows=48, heads=128, head_dim=64, groups=1, states=128)
    falcon = dict(rows=96, heads=32, head_dim=128, groups=2, states=256)
    steps = args.steps
    if args.tiny:
        granite.update(rows=2, heads=16)
        falcon.update(rows=2, heads=16)
        steps = 2
    out = os.path.join("chiprun_out", "ssd_update_microbench"
                       + (".tiny" if args.tiny else "") + ".jsonl")
    os.makedirs("chiprun_out", exist_ok=True)
    fs = forms(interpret=not on_tpu)
    plan = [(n, fs[n], granite) for n in ("packed", "padded")]
    plan.append(("packed@falcon_h1", fs["packed"], falcon))
    device = jax.devices()[0].device_kind
    with open(out, "a") as f:
        for name, form, shape in plan:
            if args.only and name not in args.only:
                continue
            try:
                line = measure(name, form, shape, steps, on_tpu)
            except Exception as e:      # a form the compiler refuses is a result
                line = {"form": name, **shape,
                        "refused": f"{type(e).__name__}: {str(e)[:600]}"}
            line["device"] = device
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
