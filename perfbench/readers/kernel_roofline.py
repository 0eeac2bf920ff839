"""A kernel's share of its roofline, from the device trace.

Over the whole executions of `module_pattern` in the traced window: the
least time the chip could take for the kernel's work — the larger of
operations over peak FLOP/s and bytes over peak bytes/s, both from
`perfbench/ops/<ops_module>.py` and the shapes of the run — divided by
the device time of the operations matching `op_pattern`. Averaged over
the chips.
"""
import importlib

import numpy as np

from perfbench.readers._args import resolve_all
from perfbench.readers._trace import op_time_inside, whole_modules


def read(spec, evidence):
    trace = evidence.trace
    if trace is None or not evidence.peaks:
        return None
    ops_mod = importlib.import_module("perfbench.ops." + spec["ops_module"])
    ops, moved = ops_mod.ops_and_bytes(**resolve_all(spec["args"], evidence))
    least = max(ops / evidence.peaks["bf16_flops_per_s"],
                moved / evidence.peaks["hbm_bytes_per_s"])
    shares = []
    for dev in trace.devices:
        runs = whole_modules(dev, spec["module_pattern"], trace.window)
        spent = op_time_inside(dev, spec["op_pattern"], runs) / 1e9
        if runs and spent > 0:
            shares.append(100.0 * least * len(runs) / spent)
    return float(np.mean(shares)) if shares else None
