"""One run of one cell:

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. A new process each time: it builds the cell
from `BENCHMARK.json` (configuration, traffic, kind of traffic, per-layer
readers — each found by name, see `manifest.py`), refuses to measure
anywhere but on the chips the cell asks for, warms the cell's own shapes,
measures for `--seconds`, compares the timed path's output with the plain
reference, and prints one JSON object as its last line. `--trace 0`
reports the cell's end-to-end metrics; `--trace 1` profiles a short
sub-window and reports its per-layer metrics and the breakdown.
"""
from __future__ import annotations

from perfbench import harness  # first: set-up is counted from its import

import argparse
import json
import os
import sys

from perfbench.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_layer_metrics(manifest, cell_name, evidence) -> dict:
    """Each per-layer metric of the cell, by its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in manifest.metrics_for("per_layer", cell_name):
        spec = manifest.layer_metric(metric["name"])
        reader = manifest.module("readers", spec["reader"])
        value = reader.read(spec, evidence)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             keep_trace: str | None = None) -> dict:
    """Drive one run and return the object of its last line.
    `require_tpu=False` is for the tests alone: they drive everything but
    the look for the chip, at sizes a CPU can hold."""
    manifest = Manifest(root)
    cell = manifest.cell(workload)
    import jax
    if require_tpu:
        devices = harness.require_chips(int(cell["chips"]))
        cache = harness.enable_cache()
        harness.log(f"compile cache: {cache}")
    else:
        devices = jax.devices()[:int(cell["chips"])]
    traffic = manifest.traffic(cell["traffic"])
    ctx = harness.Context(
        manifest=manifest, cell=cell, config=manifest.config(cell["config"]),
        traffic=traffic, seed=int(seed), seconds=float(seconds),
        trace=bool(trace), devices=devices, keep_trace=keep_trace)
    outcome = manifest.module("kinds", traffic["kind"]).run(ctx)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(outcome.memory_peak_bytes)}
    result = {"correct": bool(outcome.correct),
              "attempted": int(outcome.attempted),
              "failed": int(outcome.failed), "device": device}
    if trace:
        summary = outcome.evidence.trace
        result["metrics"] = read_layer_metrics(manifest, workload,
                                               outcome.evidence)
        if summary is not None:
            device["busy_s"] = summary.busy_s()
            device["window_s"] = summary.window_s
            result["breakdown"] = {"device_ops": summary.device_ops(),
                                   "idle_gaps": summary.idle_gaps()}
    else:
        units = {m["name"]: m["unit"]
                 for m in manifest.metrics_for("end_to_end", workload)}
        missing = sorted(set(units) - set(outcome.end_to_end))
        if missing:
            raise RuntimeError(f"cell {workload!r} did not report {missing}")
        result["metrics"] = {n: {"value": float(outcome.end_to_end[n]),
                                 "unit": u} for n, u in units.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the raw .xplane.pb in")
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
