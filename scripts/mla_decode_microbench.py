"""The latent (MLA) decode kernel alone, on the chip (PERF.md, PR 39, PR 40).

Times `ops.attention.mla_paged_decode_attention` — one grid step a row,
the row's live pages walked `pages` a turn into two VMEM slots, a turn cut
into `chains` chains of score product -> softmax -> p.v product — at the
two shapes the benchmark's cells run:

  dsv2     64 rows, 128 heads (DeepSeek-V2), a table of 256 pages, every
           row at a context of 2 048, 5 960 (the cell's mean in a
           capture) and 16 000
  longcat  64 rows, 64 heads (LongCat-Flash), a table of 100 pages, at
           2 048, 5 960 and 6 400

for the pages a turn that `--pages` lists and the chains that `--chains`
lists (the kernel's own choice, `mla_pages_per_turn` and `mla_chains`, is
16 pages in 2 chains at DeepSeek-V2's shape and 8 in one at LongCat's: the
first is sized by `_MLA_PAGES_VMEM_BUDGET` and `_MLA_LONG_TABLE`, the
second by `_MLA_CHAINS` and `_MLA_CHAINS_HEADS`, which this script sets to
force the listed values; a turn that the chains do not divide is left
out). A call's time is the median wall time of a jitted loop of `--calls`
dependent kernel calls, divided by the calls; beside it the least time
its products (2 x H x (576 + 512) a cached token) and its bytes (576-wide
rows of whole pages, q and u) need at the chip's peaks,
`perfbench/ops/mla_paged_decode.py`'s count. After a form's contexts, one
`fit` line: the call's time over the pages its turns FETCH (turns x pages
a row, the dead ones of a last turn among them), least squares: us a
fetched page and us a row.

    python scripts/mla_decode_microbench.py            # on the chip
    python scripts/mla_decode_microbench.py --parent DIR
    JAX_PLATFORMS=cpu python scripts/mla_decode_microbench.py --tiny

`--parent DIR` also times another checkout's kernel (the commit before a
change, unpacked with `git archive`) at its own choice of turn and at the
listed pages a turn, on the same inputs: lines with `"tree": "parent"`.

`--tiny` rehearses the control flow on the CPU in interpret mode into
`chiprun_out/mla_decode_microbench.tiny.jsonl`; its times mean nothing
and are labelled with the platform they came from. The chip's lines go to
stdout and to `chiprun_out/mla_decode_microbench.jsonl`.
"""
import argparse
import importlib.util
import itertools
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
SHAPES = {"dsv2": dict(B=64, H=128, nblk=256, contexts=(2048, 5960, 16000),
                       sm_scale=0.11472),
          "longcat": dict(B=64, H=64, nblk=100, contexts=(2048, 5960, 6400),
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


def load_tree(path):
    """`ops/attention.py` of another checkout as a module of its own."""
    file = os.path.join(path, "mpi_operator_tpu", "ops", "attention.py")
    spec = importlib.util.spec_from_loader(
        "mpi_operator_tpu.ops._microbench_parent", loader=None)
    mod = importlib.util.module_from_spec(spec)
    mod.__package__ = "mpi_operator_tpu.ops"
    mod.__file__ = file
    exec(compile(open(file).read(), file, "exec"), mod.__dict__)
    return mod


def time_call(tree, q, pool, cur, pt, sm_scale, interpret, calls, reps):
    """Seconds a call of `tree`'s kernel: `calls` dependent calls in one
    program."""
    @jax.jit
    def many(q, pool, cur, pt):
        def body(_, q):
            u = tree.mla_paged_decode_attention(
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


def force(tree, pages, chains):
    """Have `tree`'s kernel take `pages` bfloat16 pages a turn in `chains`
    chains (where the tree has chains), whatever the table and the head
    count."""
    tree._MLA_LONG_TABLE = 1 << 30          # no doubling
    tree._MLA_PAGES_VMEM_BUDGET = 2 * pages * PS * W * 2
    if hasattr(tree, "mla_chains"):
        tree._MLA_CHAINS, tree._MLA_CHAINS_HEADS = chains, 1


def chains_of(tree, H, pages):
    return tree.mla_chains(H, pages) if hasattr(tree, "mla_chains") else 1


def fit(points):
    """Least squares of a call's us over the pages a row fetches:
    (us a fetched page, us a row); the rows share one context."""
    xs, ys = zip(*points)
    n, sx, sy = len(xs), sum(xs), sum(ys)
    sxx, sxy = sum(x * x for x in xs), sum(x * y for x, y in points)
    a = (n * sxy - sx * sy) / (n * sxx - sx * sx)
    return a, (sy - a * sx) / n


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal in interpret mode: no time means "
                         "anything")
    ap.add_argument("--pages", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--chains", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--parent", help="another checkout to time beside this")
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit(f"no TPU here ({dev.platform}): a kernel's time comes from "
                 f"the chip; --tiny rehearses the control flow")
    interpret = dev.platform != "tpu"
    shapes = {name: SHAPES[name] for name in args.shapes}
    if args.tiny:
        shapes = {"dsv2": dict(B=2, H=8, nblk=6, contexts=(100, 380),
                               sm_scale=0.11472)}
        args.calls, args.reps, args.pages, args.chains = 2, 1, [2, 6], [1, 2]
    out = args.out or ("chiprun_out/mla_decode_microbench.tiny.jsonl"
                       if interpret else
                       "chiprun_out/mla_decode_microbench.jsonl")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    names = ("_MLA_PAGES_VMEM_BUDGET", "_MLA_LONG_TABLE", "_MLA_CHAINS",
             "_MLA_CHAINS_HEADS")
    trees = [("change", attention)]
    if args.parent:
        trees.append(("parent", load_tree(args.parent)))
    unit = "wall_us" if interpret else "call_us"

    def emit(f, line):
        print(json.dumps(line), flush=True)
        f.write(json.dumps(line) + "\n")

    with open(out, "w") as f:
        for (name, shape), (label, tree) in itertools.product(shapes.items(),
                                                              trees):
            B, H, nblk = shape["B"], shape["H"], shape["nblk"]
            own = {n: getattr(tree, n) for n in names if hasattr(tree, n)}
            chosen = tree.mla_pages_per_turn(nblk, PS * W * 2)
            chosen = (chosen, chains_of(tree, H, chosen))
            for pages, chains in itertools.product(args.pages, args.chains):
                for n, v in own.items():
                    setattr(tree, n, v)
                force(tree, pages, chains)
                took = tree.mla_pages_per_turn(nblk, PS * W * 2)
                if chains_of(tree, H, took) != chains:
                    continue    # the turn does not divide so, or no chains
                points = []
                for context in shape["contexts"]:
                    context = min(context, nblk * PS)
                    q, pool, cur, pt, live = make(B, H, nblk, context)
                    ops, moved = mla_paged_decode.ops_and_bytes(
                        tokens_in_pages=B * live * PS, rows=B, heads=H,
                        kv_rank=RANK, rope=ROPE, sublayers=1)
                    least = max(ops / PEAK_FLOPS, moved / PEAK_BYTES)
                    form = {"tree": label, "shape": name, "rows": B,
                            "heads": H, "table": nblk, "context": context,
                            "live_pages": live, "pages_a_turn": took,
                            "chains": chains,
                            "own_choice": (took, chains) == chosen}
                    try:
                        s = time_call(tree, q, pool, cur, pt,
                                      shape["sm_scale"], interpret,
                                      args.calls, args.reps)
                    except Exception as e:      # Mosaic refused this size
                        emit(f, {**form, "refused": str(e)[:300]})
                        continue
                    points.append((-(-live // took) * took, 1e6 * s / B))
                    line = {**form, unit: round(1e6 * s, 2),
                            "ns_a_cached_token": round(
                                1e9 * s / (B * live * PS), 4),
                            "us_a_live_page": round(1e6 * s / (B * live), 4),
                            "least_us_flops": round(1e6 * ops / PEAK_FLOPS, 2),
                            "least_us_bytes": round(1e6 * moved / PEAK_BYTES,
                                                    2),
                            "platform": dev.platform,
                            "device_kind": dev.device_kind}
                    if not interpret:
                        line["roofline_pct"] = round(100 * least / s, 2)
                    emit(f, line)
                if len({x for x, _ in points}) > 1:
                    a, c = fit(points)
                    emit(f, {"fit": name, "tree": label, "heads": H,
                             "pages_a_turn": took, "chains": chains,
                             "own_choice": (took, chains) == chosen,
                             "us_a_fetched_page": round(a, 4),
                             "us_a_row": round(c, 3), "of": unit,
                             "platform": dev.platform})
            for n, v in own.items():
                setattr(tree, n, v)


if __name__ == "__main__":
    main()
