#!/usr/bin/env python3
"""What Qwen3-Next brought, alone, at its cell's shapes (96 rows):

  gdn_update         `ops/gated_delta.py::gated_delta_state_update` at 32
                     value heads on 16 key heads of [128, 128], as the
                     served model calls it (16 value heads a grid step; all
                     32 a step was measured here in PR 46 and read the
                     same, 6.59 us a row against 6.57); the bytes' bound is
                     2 x 2 097 152 B a row at the HBM peak, 5.12 us;
  gdn_chunk          `gated_delta_chunk_scan` over [16 rows, 128 positions]
                     against the position-by-position scan ON THE CHIP: the
                     triangular solve's and the products' precision there;
  experts            `parallel/held_experts.py::masked_experts` at [96
                     rows, 128 held experts of 2048 x 512] bfloat16: every
                     expert's float32 product [128, 96, 2048] and then the
                     gates; the bytes' bound is the weights' 805 MB,
                     0.983 ms;
  paged_decode       `paged_decode_attention` at 16 query heads on 2 key
                     heads of 256 over contexts of 6 900, a table of 256
                     pages: 2 048 B a position.

    chiprun -- python scripts/gdn_update_microbench.py

A line a form in `chiprun_out/gdn_update_microbench.jsonl`, each compared
with plain `jax.numpy` first. Without a TPU it exits; `--tiny` rehearses on
the CPU (kernels interpreted) into `...microbench.tiny.jsonl` with
`wall_us` alone, which means nothing.
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


def timed(run, carry, steps):
    """`run(carry) -> carry`, a program of `steps` calls: (the first
    call's seconds with its compile, the better of two more a step)."""
    import jax
    t0 = time.perf_counter()
    carry = jax.block_until_ready(run(carry))
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        carry = jax.block_until_ready(run(carry))
        best = min(best, time.perf_counter() - t0)
    return first, best / steps


def gdn_operands(rows, T, Hk, Hv, Dk, Dv):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, T, Hk, Dk))) * Dk ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, T, Hk, Dk)))
    v = jax.random.normal(ks[2], (rows, T, Hv, Dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (rows, T, Hv), minval=-7,
                                    maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, T, Hv)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (rows, Hv, Dk, Dv))


def gdn_update(shape, steps, interpret):
    import jax
    import jax.numpy as jnp
    from mpi_operator_tpu.ops import gated_delta as gd
    q, k, v, g, beta, state = gdn_operands(T=1, **shape)
    step = tuple(a[:, 0] for a in (q, k, v, g, beta))
    fresh = jnp.zeros((shape["rows"],), bool).at[1].set(True)
    want_o, want_s = gd.gated_delta_scan(
        q, k, v, g, beta, jnp.where(fresh[:, None, None, None], 0.0, state))
    call = lambda s, f: gd.gated_delta_state_update(        # noqa: E731
        *step, s, fresh=f, interpret=interpret or None)
    o, s = jax.jit(call)(state, fresh)
    err = max(float(jnp.abs(o - want_o[:, 0]).max()),
              float(jnp.abs(s - want_s).max()))
    none = jnp.zeros_like(fresh)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state):
        def body(_, carry):
            state, acc = carry
            o, state = call(state, none)
            return state, acc + o[0, 0, 0]
        return jax.lax.fori_loop(0, steps, body, (state, jnp.float32(0)))[0]
    first, each = timed(run, state, steps)
    moved = 2 * 4 * shape["Hv"] * shape["Dk"] * shape["Dv"]
    return {"max_abs_err": err, "compile_and_first_s": round(first, 3)}, \
        each / shape["rows"], moved / HBM_BYTES_PER_S


def gdn_chunk(shape, T):
    import jax
    import jax.numpy as jnp
    from mpi_operator_tpu.ops import gated_delta as gd
    ops = gdn_operands(T=T, **shape)
    t0 = time.perf_counter()
    o, s = jax.block_until_ready(jax.jit(gd.gated_delta_chunk_scan)(*ops))
    first = time.perf_counter() - t0
    want_o, want_s = jax.jit(gd.gated_delta_scan)(*ops)
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(gd.gated_delta_chunk_scan)(*ops))
    return {"max_abs_err_o": float(jnp.abs(o - want_o).max()),
            "max_abs_err_state": float(jnp.abs(s - want_s).max()),
            "max_abs_o": float(jnp.abs(want_o).max()),
            "max_abs_state": float(jnp.abs(want_s).max()),
            "compile_and_first_s": round(first, 3),
            "call_s": time.perf_counter() - t0, "positions": T}


def experts(T, E, H, F, steps):
    import jax
    import jax.numpy as jnp
    from mpi_operator_tpu.parallel import held_experts as he
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    bf = jnp.bfloat16
    y = jax.random.normal(ks[0], (T, H)).astype(bf)
    gate, up = (0.02 * jax.random.normal(k, (E, H, F)) for k in ks[1:3])
    down = 0.02 * jax.random.normal(ks[3], (E, F, H))
    gate, up, down = gate.astype(bf), up.astype(bf), down.astype(bf)
    idx, w = he.route(jax.random.normal(ks[4], (T, 4 * E)), None, 10, 1.0,
                      over="picks")
    gates = he.held_gates(idx, w, 0, E)
    call = lambda y: he.masked_experts(y, gates, gate, up, down)  # noqa: E731
    f32 = lambda a: a.astype(jnp.float32)                     # noqa: E731
    with jax.default_matmul_precision("highest"):
        h = jax.nn.silu(jnp.einsum("th,ehf->etf", f32(y), f32(gate))) \
            * jnp.einsum("th,ehf->etf", f32(y), f32(up))
        want = jnp.einsum("etf,efh,te->th", h, f32(down), gates)
    got = jax.jit(call)(y)
    err = float(jnp.abs(got - want).max())

    @jax.jit
    def run(y):
        def body(_, y):
            return (y + 1e-3 * call(y).astype(bf)).astype(bf)
        return jax.lax.fori_loop(0, steps, body, y)
    first, each = timed(run, y, steps)
    moved = 3 * E * H * F * 2
    return {"max_abs_err": err, "max_abs": float(jnp.abs(want).max()),
            "compile_and_first_s": round(first, 3)}, each, \
        moved / HBM_BYTES_PER_S


def paged_decode(rows, H, KV, D, context, nblk, ps, steps, interpret):
    import jax
    import jax.numpy as jnp
    from mpi_operator_tpu.ops.attention import (paged_attend,
                                                paged_decode_attention)
    per = -(-context // ps)
    NP = rows * per + 1
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    pool = jax.random.normal(ks[0], (NP, ps, KV * 2 * D)).astype(jnp.bfloat16)
    q = jax.random.normal(ks[1], (rows, H, D)).astype(jnp.bfloat16)
    cur = jnp.full((rows,), context - 1, jnp.int32)
    pt = jnp.zeros((rows, nblk), jnp.int32).at[:, :per].set(
        1 + jnp.arange(rows * per, dtype=jnp.int32).reshape(rows, per))
    call = lambda q: paged_decode_attention(               # noqa: E731
        q, pool, cur, pt, interpret=interpret or None)
    got = jax.jit(call)(q)
    want = paged_attend(q[:, None], pool, cur[:, None], pt)[:, 0]
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())

    @jax.jit
    def run(q):
        def body(_, q):
            return (q + 1e-3 * call(q)).astype(q.dtype)
        return jax.lax.fori_loop(0, steps, body, q)
    first, each = timed(run, q, steps)
    moved = rows * (per * ps * KV * 2 * D * 2 + 2 * H * D * 2)
    return {"max_abs_err": err, "compile_and_first_s": round(first, 3),
            "context": context}, each, moved / HBM_BYTES_PER_S


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.tiny:
        print("gdn_update_microbench: needs a TPU (or --tiny to rehearse)",
              file=sys.stderr)
        return 2
    gdn = dict(rows=96, Hk=16, Hv=32, Dk=128, Dv=128)
    moe = dict(T=96, E=128, H=2048, F=512)
    walk = dict(rows=96, H=16, KV=2, D=256, context=6900, nblk=256, ps=64)
    steps, chunk_rows, chunk_T = args.steps, 16, 128
    if args.tiny:
        gdn.update(rows=2)
        moe.update(T=4, E=6, H=64, F=32)
        walk.update(rows=2, context=200, nblk=8)
        steps, chunk_rows, chunk_T = 2, 1, 70
    interpret = not on_tpu
    plan = [("gdn_update",
             functools.partial(gdn_update, gdn, steps, interpret))]
    plan.append(("gdn_chunk", lambda: (gdn_chunk(
        {**gdn, "rows": chunk_rows}, chunk_T), None, None)))
    plan.append(("experts", functools.partial(experts, steps=steps, **moe)))
    plan.append(("paged_decode", functools.partial(
        paged_decode, steps=steps, interpret=interpret, **walk)))
    out = os.path.join("chiprun_out", "gdn_update_microbench"
                       + (".tiny" if args.tiny else "") + ".jsonl")
    os.makedirs("chiprun_out", exist_ok=True)
    device = jax.devices()[0].device_kind
    with open(out, "a") as f:
        for name, measure in plan:
            if args.only and name not in args.only:
                continue
            try:
                line, each, bound = measure()
                line = {"form": name, **line}
                if each is not None and on_tpu:
                    line.update(us=1e6 * each, bound_us=1e6 * bound,
                                roofline_pct=100 * bound / each)
                elif each is not None:
                    line["wall_us"] = 1e6 * each
            except Exception as e:      # a form the compiler refuses is a result
                line = {"form": name,
                        "refused": f"{type(e).__name__}: {str(e)[:600]}"}
            line["device"] = device
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
