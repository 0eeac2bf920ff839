"""The flash-attention kernels of the training step alone, on the chip
(PERF.md section 6, PR 36).

Times `ops.attention`'s two forms at the shape `train-gpt2m-1chip` runs
in each of its 24 layers — q, k, v `[8, 1024, 16, 64]` bfloat16, causal,
no key mask — kernel by kernel and as `jax.value_and_grad` of
`flash_attention` with what XLA puts beside the kernels:

  resident  one grid step a lane block of heads, loops inside
            (`_resident_fwd`, `_resident_bwd`), over the steps of
            `--steps`
  streamed  a grid over (head, q block, k block) (`_flash_fwd`,
            `_dq_call`, `_dkv_call`), over the blocks of `--blocks`
  xla       the transposes and lane broadcasts the streamed form needs
            beside its kernels, and `delta`, as XLA runs them alone

`--parent DIR` also times another checkout's kernels (the commit before
PR 36: `git archive <commit> | tar -x -C DIR`), as they are and with the
float32 casts of `do` and `v` taken out of their source, which splits
that cost from the rest.

A time is DEVICE time from a profiler trace of one program that makes
`--calls` dependent calls: the sum of the events whose name starts with
the variant's scope, over the calls (`kernel_us`), and of every event of
the program (`all_us`: the kernels and what stands beside them). `compile_s`
is the HOST's: the seconds XLA and Mosaic took over that program, which
holds one instance of the variant's kernels (a 24-layer step holds 24 of
each, compiled one by one), with the persistent cache off.

    python scripts/flash_attention_microbench.py            # on the chip
    JAX_PLATFORMS=cpu python scripts/flash_attention_microbench.py --tiny

Without a TPU the script exits: a kernel's time comes from the chip.
`--tiny` rehearses the control flow on the CPU in interpret mode: no
device trace exists there, and its lines carry the interpreter's wall
time as `wall_us` and never a `kernel_us` or an `all_us`. Lines go to
stdout and to `chiprun_out/flash_attention_microbench.jsonl` (a
rehearsal's to `...microbench.tiny.jsonl`, apart from the chip's).
"""
import argparse
import collections
import glob
import importlib.util
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from mpi_operator_tpu.ops import attention                    # noqa: E402

SHAPES = {
    # name: rows, sequence, heads, head dim
    "gpt2m": (8, 1024, 16, 64),
    "heads128": (8, 1024, 8, 128),
    "seq2048": (4, 2048, 16, 64),
    "tiny": (2, 256, 2, 64),
}
_SUFFIX = re.compile(r"(\.\d+)+$")


def load_tree(path, patch=None):
    """`ops/attention.py` of another checkout as a module of its own,
    its source passed through `patch` first."""
    file = os.path.join(path, "mpi_operator_tpu", "ops", "attention.py")
    src = open(file).read()
    if patch:
        src = patch(src)
    name = "mpi_operator_tpu.ops._microbench_" + str(abs(hash(src)))
    spec = importlib.util.spec_from_loader(name, loader=None)
    mod = importlib.util.module_from_spec(spec)
    mod.__package__ = "mpi_operator_tpu.ops"
    mod.__file__ = file
    exec(compile(src, file, "exec"), mod.__dict__)
    return mod


def bfloat16_products(src):
    """The parent's backward with `do` and `v` left in their own type."""
    out = src.replace("do = do_ref[0].astype(jnp.float32)", "do = do_ref[0]")
    out = out.replace("v.astype(jnp.float32)", "v")
    if out == src:
        raise SystemExit("--parent: no float32 casts found to take out")
    return out


def device_time(fn, args, scope, calls, tiny):
    """The times of a call of `fn(*args)`, which returns arrays of which
    some have the first argument's shape: `kernel_us`, `all_us` and the
    largest `events` by name off a device trace, and the host's
    `compile_s`; under `tiny` the interpreter's `wall_us` alone."""
    @jax.jit
    def many(*args):
        def body(_, args):
            with jax.named_scope(scope):
                out = fn(*args)
            # every result feeds the next call: none is dead code
            first = args[0] + sum(o for o in out if o.shape == args[0].shape
                                  ).astype(args[0].dtype) * 1e-3
            return (first,) + tuple(args[1:])
        return jax.lax.fori_loop(0, calls, body, args)

    lowered = many.lower(*args)
    t = time.perf_counter()
    many = lowered.compile()
    compile_s = round(time.perf_counter() - t, 2)
    jax.block_until_ready(many(*args))
    if tiny:
        t = time.perf_counter()
        jax.block_until_ready(many(*args))
        return {"wall_us": round((time.perf_counter() - t) / calls * 1e6, 1)}
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            jax.block_until_ready(many(*args))
        path, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        profile = jax.profiler.ProfileData.from_file(path)
    by_name = collections.Counter()
    for plane in profile.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = _SUFFIX.sub("", ev.name.split(" = ", 1)[0]
                                   .lstrip("%").strip())
                by_name[name] += ev.duration_ns / 1e3 / calls
    # the loop itself is an event that spans its body's
    by_name = {n: t for n, t in by_name.items() if not n.startswith("while")}
    kernel = sum(t for n, t in by_name.items() if scope in n)
    return {"kernel_us": round(kernel, 1),
            "all_us": round(sum(by_name.values()), 1),
            "compile_s": compile_s,
            "events": {n: round(t, 1) for n, t in sorted(
                by_name.items(), key=lambda x: -x[1])[:6]}}


def operands(shape, dtype, seed=0):
    B, S, H, D = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (B, S, H, D), dtype) for k in keys]


def streamed_variants(mod, shape, dtype, bq, bk, interpret):
    """fwd, dq, dkv of a tree's streamed kernels alone."""
    B, S, H, D = shape
    q, k, v, do = (x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
                   for x in operands(shape, dtype))
    scale = D ** -0.5
    tail = (None, scale, True, bq, bk, H, interpret)
    out, lse = jax.jit(lambda q, k, v: mod._flash_fwd(q, k, v, *tail))(
        q, k, v)
    delta = jnp.broadcast_to(jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), -1)[..., None],
        lse.shape)
    yield "fwd", lambda q, k, v: mod._flash_fwd(q, k, v, *tail), (q, k, v)
    yield "dq", lambda do, q, k, v, lse, delta: (mod._dq_call(
        q, k, v, do, lse, delta, *tail),), (do, q, k, v, lse, delta)
    yield "dkv", lambda do, q, k, v, lse, delta: mod._dkv_call(
        q, k, v, do, lse, delta, *tail), (do, q, k, v, lse, delta)


def resident_variants(shape, dtype, bq, bk, interpret):
    """fwd and bwd of the resident kernels alone."""
    B, S, H, D = shape
    q, k, v, do = (x.reshape(B, S, H * D) for x in operands(shape, dtype))
    tail = (D ** -0.5, True, bq, bk, D, interpret)
    out, lse = attention._resident_fwd(q, k, v, *tail)
    yield "fwd", lambda q, k, v: attention._resident_fwd(q, k, v, *tail), \
        (q, k, v)
    yield "bwd", lambda do, q, k, v, out, lse: attention._resident_bwd(
        q, k, v, out, do, lse, *tail), (do, q, k, v, out, lse)


def xla_variants(shape, dtype):
    """What stands beside the streamed kernels, as XLA runs it alone."""
    B, S, H, D = shape
    q, _, _, do = operands(shape, dtype)
    rows = jnp.zeros((B * H, S), jnp.float32)
    alone = jax.lax.optimization_barrier     # nothing fuses across it
    yield "transpose", lambda x: (alone(
        x.transpose(0, 2, 1, 3).reshape(B * H, S, D))
        .reshape(B, S, H, D),), (q,)
    yield "lane_broadcast", lambda r: (alone(jnp.broadcast_to(
        r[..., None], (B * H, S, attention.LANES)))[..., 0],), (rows,)
    yield "delta", lambda do, out: (do + alone(jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), -1,
        keepdims=True)).astype(do.dtype),), (do, q)


def whole(mod, shape, dtype, interpret, **blocks):
    """`jax.value_and_grad` of a tree's `flash_attention`: the kernels
    and what XLA puts beside them, q, k, v lying [B, S, H, D]."""
    q, k, v, w = operands(shape, dtype)

    def loss(q, k, v):
        return jnp.sum(mod.flash_attention(
            q, k, v, causal=True, interpret=interpret, **blocks)
            .astype(jnp.float32) * w.astype(jnp.float32))

    return lambda q, k, v: jax.grad(loss, (0, 1, 2))(q, k, v), (q, k, v)


def pairs(text):
    return [tuple(int(n) for n in p.split("x")) for p in text.split(",")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="gpt2m", choices=sorted(SHAPES))
    ap.add_argument("--steps", default="128x128,256x256,512x512,256x128,"
                    "512x128,512x256,128x256,256x512",
                    help="resident steps, block_q x block_k")
    ap.add_argument("--blocks", default="512x512,256x256,128x128",
                    help="streamed blocks, block_q x block_k")
    ap.add_argument("--parent", help="another checkout to time beside this")
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    if args.tiny:
        args.shape, args.calls = "tiny", 2
        args.steps, args.blocks = "128x128", "128x128"
    shape, dtype = SHAPES[args.shape], jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit(f"no TPU here ({dev.platform}): a kernel's time comes from "
                 f"the chip; --tiny rehearses the control flow")
    interpret = dev.platform != "tpu"
    jax.config.update("jax_enable_compilation_cache", False)   # compile_s
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(
        out_dir, "flash_attention_microbench"
        + (".tiny" if args.tiny else "") + ".jsonl"), "a")

    def emit(variant, fn, operands_, shape=shape):
        scope = "mb_" + re.sub(r"\W", "_", variant)
        try:
            rec = device_time(fn, operands_, scope, args.calls, args.tiny)
        except Exception as e:                  # a variant Mosaic refuses
            rec = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        line = json.dumps({
            "variant": variant, "shape": list(shape), "dtype": str(dtype),
            "calls": args.calls, "platform": dev.platform,
            "device_kind": dev.device_kind, **rec})
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    trees = [("this", attention)]
    if args.parent:
        trees += [("parent", load_tree(args.parent)),
                  ("parent+bf16", load_tree(args.parent, bfloat16_products))]
    for bq, bk in pairs(args.steps):
        for name, fn, ops in resident_variants(shape, dtype, bq, bk,
                                               interpret):
            emit(f"resident.{name}[{bq}x{bk}]", fn, ops)
    for tree, mod in trees:
        for bq, bk in pairs(args.blocks):
            for name, fn, ops in streamed_variants(mod, shape, dtype, bq,
                                                   bk, interpret):
                emit(f"{tree}.streamed.{name}[{bq}x{bk}]", fn, ops)
    for name, fn, ops in xla_variants(shape, dtype):
        emit(f"xla.{name}", fn, ops)
    for tree, mod in trees[:2]:
        emit(f"{tree}.value_and_grad", *whole(mod, shape, dtype, interpret))
    bq, bk = pairs(args.blocks)[0]
    odd = (shape[0], shape[1], shape[2] + 1, shape[3])   # no pairs: streams
    emit("this.value_and_grad.streamed", *whole(
        attention, odd, dtype, interpret, block_q=bq, block_k=bk), shape=odd)


if __name__ == "__main__":
    main()
