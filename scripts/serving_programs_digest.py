#!/usr/bin/env python3
"""A digest of every serving cell's programs as they lower for a TPU,
without one: for each serving cell of `BENCHMARK.json` the engine's own
`step_paged` and `prefill_paged` (each bucket, at a call's width: since
PR 48 `programs.NARROW_ROWS` rows, `slots` in a checkout from before)
over the cell's model at the cell's sizes, lowered for a described v5e
(nothing compiles, nothing runs), the text hashed. Two checkouts whose lines agree hand the compiler
the same programs:

    JAX_PLATFORMS=cpu python scripts/serving_programs_digest.py > a.jsonl
    (cd <other checkout> && JAX_PLATFORMS=cpu python \
        <this file> --root . > b.jsonl);  diff a.jsonl b.jsonl

A cell whose kind or model a checkout does not have is a line that says
so. Source locations are not part of the text hashed: a Mosaic kernel's
serialised module, which carries its call stack's file names and line
numbers, is put back as its assembly without them.
"""
from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys


KERNEL = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def without_locations(text: str) -> str:
    """`text` with every Mosaic kernel's bytecode replaced by the module's
    assembly, debug information left out."""
    from jax._src.lib.mlir import ir

    def plain(match):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            return ir.Module.parse(base64.b64decode(match.group(1))) \
                .operation.get_asm(enable_debug_info=False)
    return KERNEL.sub(plain, text)


def programs_of(root, cell, one_chip, want=lambda kind: True):
    """(name, the lowered program) of `cell`'s `step` and `prefill[bucket]`,
    those whose kind (`step`, `prefill`) `want` takes."""
    import jax
    import jax.numpy as jnp
    from mpi_operator_tpu.models.generate import decode_model
    from mpi_operator_tpu.serve import EngineConfig
    from mpi_operator_tpu.serve.engine import sample_slots
    from mpi_operator_tpu.serve.programs import build_programs
    from perfbench.manifest import Manifest
    m = Manifest(root)
    config, t = m.config(cell["config"]), m.traffic(cell["traffic"])
    e = t["engine"]
    dtype = jnp.dtype(e["weights_dtype"])
    if t["kind"] == "serve_closed":
        from mpi_operator_tpu.models.transformer import (CausalLM,
                                                         TransformerConfig)
        from perfbench import weights
        d = weights.Dims.from_config(config)
        model = CausalLM(TransformerConfig(
            vocab_size=d.vocab, max_len=d.positions, num_layers=d.layers,
            num_heads=d.heads, embed_dim=d.embed, mlp_dim=d.mlp, causal=True,
            dtype=dtype, decode_kernel=bool(e["decode_kernel"])))
    else:
        serve = m.module("kinds", "_" + t["kind"].replace("_closed", ""))
        weights = serve.weights
        d = weights.Dims.from_config(config)
        model = serve.model_of(d, dtype, int(t["max_total"]),
                               bool(e["decode_kernel"]))
    S, ps = int(e["slots"]), int(e["page_size"])
    dmodel = decode_model(model, bool(e["decode_kernel"]), page_size=ps,
                          num_pages=int(e["num_pages"]))
    nblk = dmodel.config.max_len // ps
    on_chip = lambda tree: jax.tree.map(                        # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: weights.make_params(jax.random.PRNGKey(0), d, dtype)))
    z = jnp.zeros((S, 1), jnp.int32)
    cache = on_chip(jax.eval_shape(
        lambda p: dmodel.apply({"params": p}, z, positions=z, with_head=False,
                               mutable=["cache"],
                               pages=jnp.zeros((S, nblk), jnp.int32)
                               )[1]["cache"], params))
    progs = build_programs(dmodel, EngineConfig(slots=S, page_size=ps), None,
                           sample_slots)
    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt,           # noqa: E731
                                              sharding=one_chip)
    i32, f32 = arg(jnp.int32, S), arg(jnp.float32, S)
    if want("step"):
        yield "step", progs.step.lower(
            params, cache, i32, i32, arg(jnp.bool_, S), i32,
            arg(jnp.uint32, 2), f32, i32, f32, arg(jnp.int32, S, nblk),
            "greedy")
    if not want("prefill"):
        return
    from mpi_operator_tpu.serve import programs
    # a call's rows, and before them the slots they belong to: since PR 48
    R = getattr(programs, "NARROW_ROWS", None)
    rows, slots_of = (S, ()) if R is None else (R, (arg(jnp.int32, R),))
    lengths = (arg(jnp.int32, rows),) if progs.slot_state else ()
    for bucket in e["chunk_buckets"]:
        yield f"prefill[{bucket}]", progs.prefill.lower(
            params, cache, *slots_of, arg(jnp.int32, rows, int(bucket)),
            arg(jnp.int32, rows), arg(jnp.int32, rows, nblk), *lengths)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    # the dispatch sites ask the backend which kernels to trace
    jax.default_backend = lambda: "tpu"
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = [c for c in json.load(f)["workloads"]
                 if c["name"].startswith("serve")]
    for cell in cells:
        if args.only and cell["name"] not in args.only:
            continue
        try:
            for name, lowered in programs_of(root, cell, one_chip):
                raw = lowered.as_text()
                text = without_locations(raw)
                print(json.dumps({
                    "cell": cell["name"], "program": name,
                    "lines": text.count("\n"),
                    "kernels": len(KERNEL.findall(raw)),
                    "sha256": hashlib.sha256(text.encode()).hexdigest()}),
                    flush=True)
        except (ImportError, FileNotFoundError, ValueError) as e:
            print(json.dumps({"cell": cell["name"],
                              "absent": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
