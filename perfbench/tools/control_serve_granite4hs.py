"""Readings the limits of the Granite-4.0-H serving cell are set from, in one
process — `control_serve_deepseekv2.py` over `_serve_granite4hs`:

    python3 -m perfbench.tools.control_serve_granite4hs --workload <cell> \
        --seeds 1 2 3 ... --window-s 15

For each seed the engine is given that seed's weights, serves a short
window of the cell's own traffic (the first wave prefilled as a run
prefills it), and a sample of its requests (finished, or cut where the
window closed) is compared as a run compares it, beside the control's:
the gaps of the token that the reference computed in the precision below
(`--control fp8`: the products' operands rounded) puts first.
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
from perfbench import weights_granite4hs as weights
from perfbench.tools._common import ROOT, context
from perfbench.tools.control_serve_phi4flash import serve_window


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", nargs="+", default=["fp8"],
                    help="the precisions below: each is one more pass of "
                         "the reference over a control seed's sample")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--window-s", type=float, default=20.0)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    ctx = context(args.root, args.workload, args.cpu, seed=args.seeds[0])
    serve = ctx.manifest.module("kinds", "_serve_granite4hs")
    eng = serve.Engine(ctx)
    engine = eng.engine
    t = ctx.traffic
    eng.warm([int(t["prompt"]["min"]), int(t["prompt"]["max"])],
             eng.dims.vocab_real)
    make = jax.jit(lambda k: weights.make_params(k, eng.dims, eng.dtype))
    sound, control = [], {}
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
        ctrls = args.control if i < args.control_seeds else [None]
        for ctrl in ctrls:
            g = serve.served_gaps(eng.dims, eng.dtype, eng.key, sample,
                                  prompts, ctrl)
            if ctrl:
                control.setdefault(ctrl, []).append(g)
            print(json.dumps({
                "seed": seed, "finished": len(results),
                "compared": len(sample),
                "lengths": [len(prompts[r.id]) + len(r.tokens)
                            for r in sample],
                **({"control": ctrl} if ctrl else {}), **g,
                "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
        sound.append(g)
    for name, tail in (("logit_gap", "_widest"), ("logprob_gap", "_widest"),
                       ("logprob_gap_median", ""), ("logprob_gap_p99", ""),
                       ("logit_gap_p99", "")):
        print(f"served_{name}{tail}: sound max "
              f"{max(g['served_' + name] for g in sound):.6g}"
              + "".join(f", {ctrl} control min "
                        f"{min(g['control_' + name] for g in gs):.6g}"
                        for ctrl, gs in control.items()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
