"""The latent (MLA) decode kernel alone, on the chip (PERF.md, PR 39).

Times `ops.attention.mla_paged_decode_attention` — one grid step a row,
the row's live pages walked `pages` a turn into two VMEM slots — at the
two shapes the benchmark's cells run:

  dsv2     64 rows, 128 heads (DeepSeek-V2), a table of 256 pages, every
           row at a context of 2 048, 8 192 and 16 320
  longcat  64 rows, 64 heads (LongCat-Flash), a table of 100 pages, at
           2 048 and 6 016

for the pages a turn that `--pages` lists (the kernel's own choice,
`mla_pages_per_turn`, is 16 under the long table and 8 under LongCat's: it
is sized by `_MLA_PAGES_VMEM_BUDGET` and `_MLA_LONG_TABLE`, which this
script sets to force the listed values). A call's time is the median
wall time of a jitted loop of `--calls` dependent kernel calls, divided by
the calls; beside it the least time its products (2 x H x (576 + 512) a
cached token) and its bytes (576-wide rows of whole pages, q and u) need
at the chip's peaks, `perfbench/ops/mla_paged_decode.py`'s count.

    python scripts/mla_decode_microbench.py            # on the chip
    JAX_PLATFORMS=cpu python scripts/mla_decode_microbench.py --tiny

`--tiny` rehearses the control flow on the CPU in interpret mode; its
times mean nothing and are labelled with the platform they came from.
Lines go to stdout and to `chiprun_out/mla_decode_microbench.jsonl`.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from mpi_operator_tpu.ops import attention                    # noqa: E402
from perfbench.ops import mla_paged_decode                    # noqa: E402

PS, RANK, ROPE, W = 64, 512, 64, 640
SHAPES = {"dsv2": dict(B=64, H=128, nblk=256, contexts=(2048, 8192, 16320),
                       sm_scale=0.11472),
          "longcat": dict(B=64, H=64, nblk=100, contexts=(2048, 6016),
                          sm_scale=192 ** -0.5)}
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def make(B, H, nblk, context, seed=0):
    """Queries, a pool that holds every row's live pages apart, cursors
    and a table whose dead entries point at page 0."""
    live = min((context - 1) // PS, nblk - 1) + 1
    pt = np.zeros((B, nblk), np.int32)
    pt[:, :live] = 1 + np.arange(B * live).reshape(B, live)
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (B, H, W), jnp.bfloat16)
    pool = jax.random.normal(kp, (B * live + 1, PS, W), jnp.bfloat16)
    return (q, pool, jnp.full((B,), context - 1, jnp.int32),
            jnp.asarray(pt), live)


def time_call(q, pool, cur, pt, sm_scale, interpret, calls, reps):
    """Seconds a kernel call: `calls` dependent calls in one program."""
    @jax.jit
    def many(q, pool, cur, pt):
        def body(_, q):
            u = attention.mla_paged_decode_attention(
                q, pool, cur, pt, RANK, sm_scale, interpret=interpret)
            return q.at[..., :RANK].add(u * jnp.asarray(1e-3, q.dtype))
        return jax.lax.fori_loop(0, calls, body, q)
    many(q, pool, cur, pt).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        many(q, pool, cur, pt).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / calls


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal in interpret mode: no time means "
                         "anything")
    ap.add_argument("--pages", type=int, nargs="+", default=[4, 8, 16])
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/mla_decode_microbench.jsonl")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit(f"no TPU here ({dev.platform}): a kernel's time comes from "
                 f"the chip; --tiny rehearses the control flow")
    interpret = dev.platform != "tpu"
    shapes = SHAPES
    if args.tiny:
        shapes = {"dsv2": dict(B=2, H=8, nblk=6, contexts=(100, 380),
                               sm_scale=0.11472)}
        args.calls, args.reps, args.pages = 2, 1, [2, 8]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    own = (attention._MLA_PAGES_VMEM_BUDGET, attention._MLA_LONG_TABLE)
    with open(args.out, "w") as f:
        for name, shape in shapes.items():
            B, H, nblk = shape["B"], shape["H"], shape["nblk"]
            for context in shape["contexts"]:
                q, pool, cur, pt, live = make(B, H, nblk, context)
                ops, moved = mla_paged_decode.ops_and_bytes(
                    tokens_in_pages=B * live * PS, rows=B, heads=H,
                    kv_rank=RANK, rope=ROPE, sublayers=1)
                least = max(ops / PEAK_FLOPS, moved / PEAK_BYTES)
                (attention._MLA_PAGES_VMEM_BUDGET,
                 attention._MLA_LONG_TABLE) = own
                chosen = attention.mla_pages_per_turn(nblk, PS * W * 2)
                attention._MLA_LONG_TABLE = 1 << 30     # no doubling below
                for pages in args.pages:
                    attention._MLA_PAGES_VMEM_BUDGET = 2 * pages * PS * W * 2
                    took = attention.mla_pages_per_turn(nblk, PS * W * 2)
                    try:
                        s = time_call(q, pool, cur, pt, shape["sm_scale"],
                                      interpret, args.calls, args.reps)
                    except Exception as e:      # Mosaic refused this size
                        print(json.dumps({"shape": name, "context": context,
                                          "pages_a_turn": took,
                                          "refused": str(e)[:300]}),
                              flush=True)
                        continue
                    line = {"shape": name, "rows": B, "heads": H,
                            "table": nblk, "context": context,
                            "live_pages": live, "pages_a_turn": took,
                            "own_choice": took == chosen,
                            ("wall_us" if interpret else "call_us"):
                                round(1e6 * s, 2),
                            "ns_a_cached_token": round(
                                1e9 * s / (B * live * PS), 4),
                            "least_us_flops": round(1e6 * ops / PEAK_FLOPS, 2),
                            "least_us_bytes": round(1e6 * moved / PEAK_BYTES,
                                                    2),
                            "platform": dev.platform,
                            "device_kind": dev.device_kind}
                    if not interpret:
                        line["roofline_pct"] = round(100 * least / s, 2)
                    print(json.dumps(line), flush=True)
                    f.write(json.dumps(line) + "\n")
    attention._MLA_PAGES_VMEM_BUDGET, attention._MLA_LONG_TABLE = own


if __name__ == "__main__":
    main()
