"""Readings the limits of a training cell are set from, in one process:

    python3 -m perfbench.tools.control_train --workload <cell> --seeds 1 2 3 ...

For each seed: the program's numbers over its first `check_steps` steps
(the kind's own `first_steps`, at the cell's own size), the plain
reference's, and the control's — the reference computed in the precision
below the configuration's (`--control fp8` under a bfloat16 cell). Prints
each gap of the program and of the control against the reference, then
the largest sound reading and the smallest control reading of each
number. Not a cell; needs the cell's chips.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from perfbench import harness, weights
from perfbench.tools._common import ROOT, context


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also run the control")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal off the chip (tiny sizes only)")
    args = ap.parse_args(argv)

    import jax
    ctx = context(args.root, args.workload, args.cpu)
    traffic, devices = ctx.traffic, ctx.devices
    train = ctx.manifest.module("kinds", traffic["kind"])
    trainer, new_state, dims = train.build(ctx)
    rows = traffic["rows_per_chip"] * len(devices)
    readers = train.program_readers(dims, traffic["optimizer"]["b1"])
    sound, control = [], []
    for i, seed in enumerate(args.seeds):
        ctx.seed = seed
        key = weights.seed_key(seed)
        t0 = time.perf_counter()
        stream = train.make_stream(seed, rows, traffic["seq_len"],
                                   dims.vocab_real, trainer.batch_sharding)
        try:
            state, program = train.first_steps(
                trainer, new_state(key), stream, readers, key,
                int(traffic["check_steps"]))
            program = train.by_name(jax.device_get(program), dims.layers)
        finally:
            stream.close()
        harness.delete_arrays(state)
        del state
        reference = train.reference_readings(ctx, dims, key)
        g = train.gaps(program, reference)
        sound.append(g)
        line = {"seed": seed, "program": g}
        if i < args.control_seeds:
            ctrl = train.reference_readings(ctx, dims, key, args.control)
            line["control"] = train.gaps(ctrl, reference)
            control.append(line["control"])
        line["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(line), flush=True)
    for name in sound[0]:
        print(f"{name}: sound max {max(g[name] for g in sound):.6g}"
              + (f", control min {min(g[name] for g in control):.6g}"
                 if control else ""), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
