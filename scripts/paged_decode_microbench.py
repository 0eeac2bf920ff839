"""The per-head paged decode kernels alone, on the chip (PERF.md, PR 32,
PR 49).

Times `ops.attention`'s two forms over an unquantised page pool — the
grid over the page table (`_paged_grid_call`: what every cell ran up to
PR 31, and what an int8 pool still runs) and the walk of a row's live
pages (`_paged_walk_call`) — at the shapes the benchmark's cells run,
and splits a call into a row's fixed cost, a dead table entry and a
live page; and the walk's row into its fixed cost, a full turn and a
short turn by the pages live in it:

  gpt2xl     64 rows, 25 heads of 64 (rows of 3200), a table of 16 pages
  phi        64 rows, 40 query heads over 10 pairs of 128 (rows of 2560),
             a table of 256 pages: layer 17's pool, read eight times a step
  ring       the same rows as 64 x 9 ring pages under window=512
  falcon     96 rows, 20 query heads over 4 heads of 128 (rows of 1024), a
             table of 64 pages: Falcon-H1's four pools
  qwen3next  96 rows, 16 query heads over 2 heads of 256 (rows of 1024), a
             table of 256 pages: Qwen3-Next's two pools

A call's time is the median wall time of a jitted loop of `--calls`
dependent kernel calls, divided by the calls. With every row at one
context, time a row = fixed + live x (a live page) + dead x (a dead
entry); the table's length is varied (phi: 256 and 128 pages) to tell a
dead entry from the fixed cost, the context to price a live page. The
mixed batches hold rows at unrelated depths as the cells have them;
`gpt2xl-cell` is `serve-gpt2xl-decode-heavy`'s: two thirds of the rows
at 2-13 live pages, a third free at cursor 0 on the trash page.

    python scripts/paged_decode_microbench.py            # on the chip
    python scripts/paged_decode_microbench.py --tree parent=DIR
    JAX_PLATFORMS=cpu python scripts/paged_decode_microbench.py --tiny

`--tree LABEL=DIR` (repeatable) also times the walk of another
checkout's `ops/attention.py` beside this one's (the commit before a
change to the kernel): the last table prints the trees side by side.

`--tiny` rehearses the control flow on the CPU in interpret mode; its
times mean nothing and are labelled with the platform they came from.
Lines go to stdout and to `chiprun_out/paged_decode_microbench.jsonl`.
"""
import argparse
import importlib.util
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
    "falcon": dict(B=96, H=20, KV=4, D=128, nblk=64, window=None),
    "qwen3next": dict(B=96, H=16, KV=2, D=256, nblk=256, window=None),
}
PS = 64
# pages a turn the mixed batches are also timed at, beside the count
# `paged_pages_per_turn` gives (4, 4, 3, 16, 16)
OTHER_PAGES = {"gpt2xl": [2, 5, 8], "falcon": [8], "qwen3next": [8]}


def load_tree(path):
    """`ops/attention.py` of another checkout as a module of its own."""
    file = os.path.join(path, "mpi_operator_tpu", "ops", "attention.py")
    name = "mpi_operator_tpu.ops._microbench_" + str(abs(hash(file)))
    spec = importlib.util.spec_from_loader(name, loader=None)
    mod = importlib.util.module_from_spec(spec)
    mod.__package__ = "mpi_operator_tpu.ops"
    mod.__file__ = file
    exec(compile(open(file).read(), file, "exec"), mod.__dict__)
    return mod


def make(shape, contexts, seed=0):
    """Queries, a pool that holds every row's live pages apart, cursors
    and a table whose dead entries point at page 0. A row of context 1
    is a FREE row as the engine holds one: cursor 0, every entry on page
    0, the trash page."""
    B, H, KV, D, nblk = (shape[k] for k in ("B", "H", "KV", "D", "nblk"))
    cur = np.asarray(contexts, np.int32) - 1
    live = np.minimum(cur // PS, nblk - 1) + 1
    NP = int(live.sum()) + 1
    pt = np.zeros((B, nblk), np.int32)
    at = 1
    for b in range(B):
        if cur[b] == 0:
            continue
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


def kernels(shape, interpret, pages_list, forms, attention=attention):
    """(label, pages a turn, fn) of every kernel form of a tree's
    `ops/attention.py` to time."""
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


def pages_of(shape):
    """Pages a turn `paged_decode_attention` takes at this shape."""
    hb = attention.decode_head_block(shape["KV"], PS, shape["D"],
                                     jnp.bfloat16, attention._KV_VMEM_BUDGET,
                                     paged=True)
    return attention.paged_pages_per_turn(
        shape["nblk"], PS * hb * 2 * shape["D"] * 2, PS, shape["window"])


def short_turns(pages):
    """The live pages a short turn is probed at."""
    return sorted(n for n in {1, 2, 3, pages // 4, pages // 2,
                              3 * pages // 4} if 0 < n < pages)


def turn_probes(shape):
    """Contexts of 1 .. 2 turns by the live pages of the short one, then
    of 3 and 4 turns and of half the table (as far as the table goes): a
    row's fixed cost, a full turn and a short turn by its live pages come
    from their differences. Under a window a context one short of a
    page's end keeps every page it names inside the window."""
    pages, nblk = pages_of(shape), shape["nblk"]
    short = short_turns(pages)
    live = short + [pages] + [pages + n for n in short] + [
        k * pages for k in (2, 3, 4, nblk // 2 // pages)]
    return [n * PS - (shape["window"] is not None)
            for n in sorted(set(live)) if n <= nblk]


def cell_mixes(tiny):
    """(name, shape, contexts): rows of one call at unrelated depths, as
    the cells have them."""
    rs = np.random.RandomState(1)
    if tiny:
        return [("gpt2xl-cell", "gpt2xl", [1, 200])]
    decoding = rs.randint(1 * PS + 1, 13 * PS + 1, size=43)   # 2-13 pages
    return [
        ("gpt2xl-full-range", "gpt2xl", np.linspace(64, 1024, 64)),
        ("gpt2xl-mid", "gpt2xl", np.linspace(200, 560, 64)),
        ("gpt2xl-cell", "gpt2xl", np.concatenate([decoding, np.ones(21)])),
        ("phi-cell", "phi", np.linspace(2048, 14336, 64)),
        ("falcon-cell", "falcon", np.linspace(300, 3600, 96)),
        ("qwen3next-cell", "qwen3next", np.linspace(2048, 10240, 96)),
    ]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal in interpret mode: no time means "
                         "anything")
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tree", action="append", default=[],
                    metavar="LABEL=DIR",
                    help="another checkout whose walk to time beside this")
    ap.add_argument("--out",
                    default="chiprun_out/paged_decode_microbench.jsonl")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit(f"no TPU here ({dev.platform}): a kernel's time comes from "
                 f"the chip; --tiny rehearses the control flow")
    interpret = dev.platform != "tpu"
    trees = [("change" if args.tree else "this", attention)]
    trees += [(label, load_tree(path)) for label, path in
              (t.split("=", 1) for t in args.tree)]
    plan = [
        # shape, contexts (every row at one), pages a turn, extra forms
        # (16 pages of gpt2-xl's a turn are refused: 13 MB of slots)
        ("gpt2xl", [64, 256, 448, 1024], [1, 2, 4, 5, 8], ()),
        ("phi", [2048, 7000, 14000], [1, 2, 4, 8, 12], ("padded",)),
        ("phi-table128", [2048, 7000], [8], ()),
        ("ring", [575], [1, 2, 3, 5, 9], ()),
        ("falcon", [1024, 4096], [4, 8, 16], ()),
        ("qwen3next", [2048, 10240], [4, 8, 16], ()),
    ]
    probed = ["gpt2xl", "phi", "ring", "falcon", "qwen3next"]
    if args.tiny:
        for s in SHAPES.values():
            s.update(B=2, nblk=min(s["nblk"], 9))
        plan = [("gpt2xl", [64, 300], [1, 8], ()),
                ("phi", [100, 500], [2, 8], ("padded",)),
                ("ring", [575], [5], ())]
        probed = ["gpt2xl"]
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

        def timed(rec, fn, operands):
            try:
                t = time_call(fn, *operands, args.calls, args.reps)
            except Exception as e:            # Mosaic refused the size
                emit(dict(rec, refused=str(e)[-300:]))
                return
            emit(dict(rec, call_us=t * 1e6, row_us=t * 1e6 / rec["rows"]))

        # every row at one context, every form and count of pages a
        # turn: this tree alone
        for name, contexts, pages_list, forms in plan:
            shape = SHAPES[name]
            for ctx in contexts:
                *operands, live = make(shape, [ctx] * shape["B"])
                for label, pages, fn in kernels(shape, interpret, pages_list,
                                                forms):
                    if pages > shape["nblk"]:
                        continue
                    timed(dict(tree=trees[0][0], shape=name, context=ctx,
                               kernel=label, pages_a_turn=pages,
                               live_pages=int(live[0]), rows=shape["B"],
                               table_pages=shape["nblk"], page_bytes=(
                                   PS * shape["KV"] * 2 * shape["D"] * 2)),
                          fn, operands)
        # the walk as `paged_decode_attention` takes it, every tree: a
        # row's turns one live page at a time, then the cells' mixes
        for tree, mod in trees:
            for name in probed:
                shape = SHAPES[name]
                walk = kernels(shape, interpret, [None], (), mod)[1][2]
                for ctx in turn_probes(shape):
                    *operands, live = make(shape, [ctx] * shape["B"])
                    timed(dict(tree=tree, shape=name, context=ctx,
                               kernel="walk", pages_a_turn=pages_of(shape),
                               live_pages=int(live[0]), rows=shape["B"],
                               probe=True), walk, operands)
            for mix, name, contexts in cell_mixes(args.tiny):
                shape = SHAPES[name]
                contexts = np.random.RandomState(1).permutation(
                    np.asarray(contexts).astype(int))
                *operands, live = make(shape, contexts)
                # other counts of pages a turn on this tree alone: is
                # `paged_pages_per_turn`'s the one to take?
                others = OTHER_PAGES.get(name, []) if mod is attention else []
                for label, pages, fn in kernels(shape, interpret,
                                                [None] + others, (), mod):
                    if label == "grid" and mod is not attention:
                        continue
                    timed(dict(tree=tree, shape=name, context=mix,
                               kernel=label, pages_a_turn=pages,
                               live_pages=float(live.mean()),
                               rows=shape["B"], table_pages=shape["nblk"]),
                          fn, operands)

    # the split: a row's fixed cost and a live page from the contexts of
    # one (shape, kernel, pages); a dead entry from the two table lengths
    ok = [r for r in lines if "refused" not in r]
    uniform = [r for r in ok
               if isinstance(r["context"], int) and "probe" not in r]
    print("\nshape kernel pages | us a row fixed | us a live page | "
          "(HBM time of a page at 819 GB/s)")
    for key in sorted({(r["shape"], r["kernel"], r["pages_a_turn"])
                       for r in uniform}):
        of_key = [r for r in uniform
                  if (r["shape"], r["kernel"], r["pages_a_turn"]) == key]
        if len(of_key) < 2:
            continue
        slope, fixed = np.polyfit([r["live_pages"] for r in of_key],
                                  [r["row_us"] for r in of_key], 1)
        hbm = of_key[0]["page_bytes"] / 819e9 * 1e6
        print(f"{key[0]} {key[1]} {key[2]} | {fixed:.2f} | {slope:.3f} | "
              f"{hbm:.3f}")
    for ctx in (2048, 7000):
        t = {r["table_pages"]: r["row_us"] for r in uniform
             if r["kernel"] == "grid" and r["context"] == ctx
             and r["shape"] in ("phi", "phi-table128")}
        if len(t) == 2:
            print(f"grid, phi, context {ctx}: a dead table entry "
                  f"{(t[256] - t[128]) / 128:.3f} us")

    # the walk's row by its turns, the trees side by side: with P pages a
    # turn, a full turn = (row(kP) - row(P)) / (k - 1) at the largest k
    # probed, fixed = row(P) - a full turn, a short FIRST turn of n live
    # pages = row(n) - fixed, a short SECOND turn = row(P + n) - row(P)
    names = [t for t, _ in trees]
    print("\nthe walk, us a row: " + " | ".join(names))
    for name in probed:
        P = pages_of(SHAPES[name])
        row = {t: {r["live_pages"]: r["row_us"] for r in ok
                   if r.get("probe") and r["shape"] == name
                   and r["tree"] == t} for t in names}
        if any(P not in row[t] or max(row[t]) == P for t in names):
            continue
        k = max(row[names[0]]) // P
        full = {t: (row[t][k * P] - row[t][P]) / (k - 1) for t in names}
        fixed = {t: row[t][P] - full[t] for t in names}

        def show(what, of):
            print(f"{name} pages={P} {what}: "
                  + " | ".join(f"{of(t):.3f}" for t in names))
        show("a row's fixed cost", lambda t: fixed[t])
        show("a full turn", lambda t: full[t])
        for n in short_turns(P):
            show(f"a first turn of {n} live", lambda t: row[t][n] - fixed[t])
        for n in short_turns(P):
            show(f"a second turn of {n} live",
                 lambda t: row[t][P + n] - row[t][P])
    print("\nmixed batches, us a call: " + " | ".join(names))
    for mix in sorted({r["context"] for r in ok
                       if not isinstance(r["context"], int)}):
        walks = [r for r in ok
                 if r["context"] == mix and r["kernel"] == "walk"]
        of = {r["tree"]: r["call_us"] for r in walks
              if r["pages_a_turn"] is None}
        print(f"{mix}: " + " | ".join(f"{of[t]:.1f}" for t in names)
              + "".join(f"; {names[0]} at {r['pages_a_turn']} a turn "
                        f"{r['call_us']:.1f}" for r in walks
                        if r["pages_a_turn"] is not None))


if __name__ == "__main__":
    main()
