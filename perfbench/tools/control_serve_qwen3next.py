"""Readings the limits of the Qwen3-Next serving cell are set from, in one
process — `control_serve_deepseekv2.py` over `_serve_qwen3next`:

    python3 -m perfbench.tools.control_serve_qwen3next --workload <cell> \
        --seeds 1 2 3 ... --window-s 80

For each seed the engine is given that seed's weights, serves a short
window of the cell's own traffic (the first wave prefilled as a run
prefills it, INSIDE the window: 49 s of it on the chip, so 80 s decode
for 31 and a row is served some 1 700 tokens), and a sample of its
requests (finished, or cut where the window closed) is compared as a run
compares it, beside the control's:
the gaps of the token that the reference computed in the precision below
(`--control fp8`: the products' operands rounded) puts first.
Every reading then goes through the harness's `Check` against the cell's
own `limits`, as a run's goes: the sound program has to come out correct on
every seed and the fp8 control NOT CORRECT by EACH limit on every control
seed (bfloat16 is what the program computes in, so its control reads beside
the program and is judged by neither rule). The exit code is 1 where either
fails: limits that the precision below passes decide nothing.
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
from perfbench import weights_qwen3next as weights
from perfbench.tools._common import ROOT, context
from perfbench.tools.control_serve_phi4flash import serve_window


def judge(limits, sound, control) -> int:
    """The readings through the run's own `Check`, a line each: 0 where the
    sound program is correct on every seed and the fp8 control is NOT
    correct by each limit on every control seed, else 1."""
    def checks(g, side):
        # limit `served_<gap>_widest` judges reading `<side>_<gap>`
        return [harness.Check(name, g[side + name[len("served"):-len(
            "_widest")]], limit) for name, limit in sorted(limits.items())]
    rc = 0
    for i, g in enumerate(sound):
        for c in checks(g, "served"):
            print(f"sound #{i} {c.line()}", flush=True)
            rc |= not c.ok
    for ctrl, gs in control.items():
        for i, g in enumerate(gs):
            for c in checks(g, "control"):
                print(f"{ctrl} control #{i} {c.line()}", flush=True)
                rc |= ctrl == "fp8" and c.ok
    print("verdict: " + ("the sound program is correct on every seed"
                         + (" and the fp8 control is NOT correct by each "
                            "limit" if "fp8" in control else "")
                         if not rc else
                         "the limits do NOT part the sound program from "
                         "the fp8 control"), flush=True)
    return int(rc)


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
    serve = ctx.manifest.module("kinds", "_serve_qwen3next")
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
    rc = judge(t["limits"], sound, control)
    for name, tail in (("logit_gap", "_widest"), ("logprob_gap", "_widest"),
                       ("logprob_gap_median", ""), ("logprob_gap_p99", ""),
                       ("logit_gap_p99", "")):
        print(f"served_{name}{tail}: sound max "
              f"{max(g['served_' + name] for g in sound):.6g}"
              + "".join(f", {ctrl} control min "
                        f"{min(g['control_' + name] for g in gs):.6g}"
                        for ctrl, gs in control.items()), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
