"""The per-head paged decode kernels alone, on the chip (PERF.md, PR 32).

Times `ops.attention`'s two forms over an unquantised page pool — the
grid over the page table (`_paged_grid_call`: what every cell ran up to
PR 31, and what an int8 pool still runs) and the walk of a row's live
pages (`_paged_walk_call`) — at the three shapes the benchmark's cells
run, and splits a call into a row's fixed cost, a dead table entry and
a live page:

  gpt2xl  64 rows, 25 heads of 64 (rows of 3200), a table of 16 pages
  phi     64 rows, 40 query heads over 10 pairs of 128 (rows of 2560), a
          table of 256 pages: layer 17's pool, read eight times a step
  ring    the same rows as 64 x 9 ring pages under window=512

A call's time is the median wall time of a jitted loop of `--calls`
dependent kernel calls, divided by the calls. With every row at one
context, time a row = fixed + live x (a live page) + dead x (a dead
entry); the table's length is varied (phi: 256 and 128 pages) to tell a
dead entry from the fixed cost, the context to price a live page.

    python scripts/paged_decode_microbench.py            # on the chip
    JAX_PLATFORMS=cpu python scripts/paged_decode_microbench.py --tiny

`--tiny` rehearses the control flow on the CPU in interpret mode; its
times mean nothing and are labelled with the platform they came from.
Lines go to stdout and to `chiprun_out/paged_decode_microbench.jsonl`.
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

SHAPES = {
    # name: rows, query heads, kv heads, head dim, table pages, window
    "gpt2xl": dict(B=64, H=25, KV=25, D=64, nblk=16, window=None),
    "phi": dict(B=64, H=40, KV=10, D=128, nblk=256, window=None),
    "phi-table128": dict(B=64, H=40, KV=10, D=128, nblk=128, window=None),
    "ring": dict(B=64, H=40, KV=10, D=128, nblk=9, window=512),
}
PS = 64


def make(shape, contexts, seed=0):
    """Queries, a pool that holds every row's live pages apart, cursors
    and a table whose dead entries point at page 0."""
    B, H, KV, D, nblk = (shape[k] for k in ("B", "H", "KV", "D", "nblk"))
    cur = np.asarray(contexts, np.int32) - 1
    live = np.minimum(cur // PS, nblk - 1) + 1
    NP = int(live.sum()) + 1
    pt = np.zeros((B, nblk), np.int32)
    at = 1
    for b in range(B):
        pt[b, :live[b]] = np.arange(at, at + live[b])
        at += live[b]
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    pool = jax.random.normal(kp, (NP, PS, KV * 2 * D), jnp.bfloat16)
    return q, pool, jnp.asarray(cur), jnp.asarray(pt), live


def time_call(kernel, q, pool, cur, pt, calls, reps):
    """Seconds a kernel call: `calls` dependent calls in one program."""
    @jax.jit
    def many(q, pool, cur, pt):
        def body(_, q):
            return q + kernel(q, pool, cur, pt) * jnp.asarray(1e-3, q.dtype)
        return jax.lax.fori_loop(0, calls, body, q)
    many(q, pool, cur, pt).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        many(q, pool, cur, pt).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / calls


def kernels(shape, interpret, pages_list, forms):
    """(label, pages a turn, fn) of every kernel form to time."""
    B, H, KV, D = (shape[k] for k in ("B", "H", "KV", "D"))
    window = shape["window"]
    scale = 1.0 / D ** 0.5

    def grid(q, pool, cur, pt):
        return attention._paged_grid_call(
            q.reshape(B, KV, H // KV, D), pool, cur, pt, None, None, scale,
            window, interpret)

    def walk(pages, k_lanes):
        def fn(q, pool, cur, pt):
            return attention._paged_walk_call(
                q.reshape(B, KV, H // KV, D), pool, cur, pt, scale, window,
                interpret, pages=pages, k_lanes=k_lanes)
        return fn

    out = [("grid", 1, grid)]
    for pages in pages_list:
        out.append(("walk", pages, walk(pages, None)))
        if D % 128 == 0 and "padded" in forms:
            # the padded-query form on a shape that need not pad: prices
            # the MXU tiles the K-lane form saves
            out.append(("walk-padded-q", pages, walk(pages, False)))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal in interpret mode: no time means "
                         "anything")
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out",
                    default="chiprun_out/paged_decode_microbench.jsonl")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit(f"no TPU here ({dev.platform}): a kernel's time comes from "
                 f"the chip; --tiny rehearses the control flow")
    interpret = dev.platform != "tpu"
    plan = [
        # shape, contexts (every row at one), pages a turn, extra forms
        # (16 pages of gpt2-xl's a turn are refused: 13 MB of slots)
        ("gpt2xl", [64, 256, 448, 1024], [1, 2, 4, 8], ()),
        ("phi", [2048, 7000, 14000], [1, 2, 4, 8, 12], ("padded",)),
        ("phi-table128", [2048, 7000], [8], ()),
        ("ring", [575], [1, 2, 3, 5, 9], ()),
    ]
    if args.tiny:
        for s in SHAPES.values():
            s.update(B=2, nblk=min(s["nblk"], 9))
        plan = [("gpt2xl", [64, 300], [1, 8], ()),
                ("phi", [100, 500], [2, 8], ("padded",)),
                ("ring", [575], [5], ())]
        args.calls, args.reps = 2, 1
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    lines = []
    with open(args.out, "w") as f:
        def emit(rec):
            rec.update(platform=dev.platform, device_kind=dev.device_kind)
            lines.append(rec)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(json.dumps(rec), flush=True)

        for name, contexts, pages_list, forms in plan:
            shape = SHAPES[name]
            B, nblk = shape["B"], shape["nblk"]
            for ctx in contexts:
                q, pool, cur, pt, live = make(shape, [ctx] * B)
                for label, pages, fn in kernels(shape, interpret, pages_list,
                                                forms):
                    if pages > nblk:
                        continue
                    try:
                        t = time_call(fn, q, pool, cur, pt, args.calls,
                                      args.reps)
                    except Exception as e:    # Mosaic refused the size
                        emit(dict(shape=name, context=ctx, kernel=label,
                                  pages_a_turn=pages, refused=str(e)[-300:]))
                        continue
                    emit(dict(shape=name, context=ctx, kernel=label,
                              pages_a_turn=pages, live_pages=int(live[0]),
                              table_pages=nblk, call_us=t * 1e6,
                              row_us=t * 1e6 / B, page_bytes=(
                                  PS * shape["KV"] * 2 * shape["D"] * 2)))
        # rows of one call at unrelated depths, as the cells have them:
        # the forms as `paged_decode_attention` would choose them
        mixes = [("gpt2xl", np.linspace(64, 1024, 64).astype(int)),
                 ("gpt2xl", np.linspace(200, 560, 64).astype(int)),
                 ("phi", np.linspace(2048, 14336, 64).astype(int))]
        for name, contexts in ([] if args.tiny else mixes):
            shape = SHAPES[name]
            rs = np.random.RandomState(1)
            contexts = rs.permutation(contexts)
            q, pool, cur, pt, live = make(shape, contexts)
            for label, pages, fn in kernels(shape, interpret, [None], ()):
                t = time_call(fn, q, pool, cur, pt, args.calls, args.reps)
                emit(dict(shape=name, context="mixed", kernel=label,
                          pages_a_turn=pages,
                          live_pages=float(live.mean()),
                          table_pages=shape["nblk"], call_us=t * 1e6,
                          row_us=t * 1e6 / shape["B"]))

    # the split: a row's fixed cost and a live page from the contexts of
    # one (shape, kernel, pages); a dead entry from the two table lengths
    timed = [r for r in lines
             if r["context"] != "mixed" and "refused" not in r]
    print("\nshape kernel pages | us a row fixed | us a live page | "
          "(HBM time of a page at 819 GB/s)")
    for key in sorted({(r["shape"], r["kernel"], r["pages_a_turn"])
                       for r in timed}):
        of_key = [r for r in timed
                  if (r["shape"], r["kernel"], r["pages_a_turn"]) == key]
        if len(of_key) < 2:
            continue
        slope, fixed = np.polyfit([r["live_pages"] for r in of_key],
                                  [r["row_us"] for r in of_key], 1)
        hbm = of_key[0]["page_bytes"] / 819e9 * 1e6
        print(f"{key[0]} {key[1]} {key[2]} | {fixed:.2f} | {slope:.3f} | "
              f"{hbm:.3f}")
    for ctx in (2048, 7000):
        t = {r["table_pages"]: r["row_us"] for r in timed
             if r["kernel"] == "grid" and r["context"] == ctx
             and r["shape"] in ("phi", "phi-table128")}
        if len(t) == 2:
            print(f"grid, phi, context {ctx}: a dead table entry "
                  f"{(t[256] - t[128]) / 128:.3f} us")


if __name__ == "__main__":
    main()
