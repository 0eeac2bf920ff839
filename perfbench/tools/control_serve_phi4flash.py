"""Readings the limits of the Phi-4-mini-flash serving cell are set from, in
one process — `control_serve_longcat.py` over `_serve_phi4flash`:

    python3 -m perfbench.tools.control_serve_phi4flash --workload <cell> \
        --seeds 1 2 3 ... --window-s 15

For each seed the engine is given that seed's weights, serves a short
window of the cell's own traffic (the first wave prefilled as a run
prefills it), and a sample of its requests (finished, or cut where the
window closed) is compared as a run compares it, beside the control's:
the gaps of the token that the reference computed in the precision below
(`--control fp8`: products and the recurrent state rounded) puts first.
Not a cell; needs the cell's chip. Like `control_serve`, it reaches into
the engine (`params`, `reset`) to change seeds without a second set-up; a
run never does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from perfbench import harness
from perfbench import weights_phi4flash as weights
from perfbench.tools._common import ROOT, context


def serve_window(ctx, serve, eng, seconds):
    """A short window of the cell's closed loop; returns (results,
    prompts), what the rows still in their slots were served so far
    among the results (`control_serve.serve_window`'s closed branch)."""
    import types

    from mpi_operator_tpu.serve import Request
    engine = eng.engine
    base = time.perf_counter()
    now = lambda: time.perf_counter() - base  # noqa: E731
    engine.start(now_fn=now)
    first, backlog = serve.deep_closed_loop(ctx.traffic, ctx.seed,
                                            eng.dims.vocab_real)
    reqs = first + backlog
    for r in first:
        engine.submit(Request(id=r.id, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens))
    answered = 0
    while now() < seconds:
        eng.tick()
        done = len(engine.session_results())
        while answered < done:
            r = backlog.pop(0)
            engine.submit(Request(id=r.id, prompt=r.prompt,
                                  max_new_tokens=r.max_new_tokens,
                                  arrival=now()))
            answered += 1
    results = dict(engine.session_results())
    for st in engine.scheduler.active:
        if st.generated and st.req.id not in results:
            results[st.req.id] = types.SimpleNamespace(
                id=st.req.id, tokens=list(st.generated),
                logprobs=list(st.logprobs), finish_reason="length")
    engine.finish()
    return results, {r.id: r.prompt for r in reqs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--window-s", type=float, default=20.0)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    ctx = context(args.root, args.workload, args.cpu, seed=args.seeds[0])
    serve = ctx.manifest.module("kinds", "_serve_phi4flash")
    eng = serve.Engine(ctx)
    engine = eng.engine
    t = ctx.traffic
    eng.warm([int(t["prompt"]["min"]), int(t["prompt"]["max"])],
             eng.dims.vocab_real)
    make = jax.jit(lambda k: weights.make_params(k, eng.dims, eng.dtype))
    sound, control = [], []
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        ctx.seed = seed
        eng.key = weights.seed_key(seed)
        harness.delete_arrays((engine.params, engine.cache))
        engine.params = make(eng.key)
        engine.reset()
        results, prompts = serve_window(ctx, serve, eng, args.window_s)
        sample = serve._serve.pick_sample(results, prompts, seed,
                                          int(t["check_requests"]))
        # the reference needs the chip's memory; the next seed remakes both
        harness.delete_arrays((engine.params, engine.cache))
        ctrl = args.control if i < args.control_seeds else None
        g = serve.served_gaps(eng.dims, eng.dtype, eng.key, sample, prompts,
                              ctrl)
        sound.append(g)
        if ctrl:
            control.append(g)
        print(json.dumps({
            "seed": seed, "finished": len(results), "compared": len(sample),
            **g, "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
    for name in ("logit_gap", "logprob_gap"):
        print(f"served_{name}_widest: sound max "
              f"{max(g['served_' + name] for g in sound):.6g}"
              + (f", control min "
                 f"{min(g['control_' + name] for g in control):.6g}"
                 if control else ""), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
