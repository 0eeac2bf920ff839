"""Time a train step's chips wait in collective operations with no
compute beside them, in milliseconds: per chip the median over the whole
executions of `module_pattern` in the traced window, then the median over
the chips.

A chip's "XLA Ops" line holds the operations its core ran, collectives
among them (`all-reduce`, or `all-reduce-start` / `all-reduce-done` where
the compiler made it asynchronous: the start is an issue, the done is the
wait). Exposed time is the part of the intervals of operations matching
`op_pattern` that no other operation's interval covers. On one chip a
data-parallel step has no such operation and the reading is 0.
"""
import re

import numpy as np

from perfbench.harness import log, median
from perfbench.readers._trace import whole_modules
from perfbench.trace_reduce import union


def _minus(a, b):
    """Total length of the union `a` outside the union `b` (both sorted,
    disjoint (start, end) lists)."""
    total, j = 0.0, 0
    for s, e in a:
        cursor = s
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cursor:
                total += b[k][0] - cursor
            cursor = max(cursor, b[k][1])
            k += 1
        if e > cursor:
            total += e - cursor
    return total


def exposed_per_step(device, op_pattern, runs):
    """[ns] for each of `runs`: collective time not covered by any other
    operation of the chip."""
    rx = re.compile(op_pattern)
    ops = device.ops
    hit = np.array([bool(rx.search(n)) for n in ops.names], bool)
    out = []
    for a, b in runs:
        inside = (ops.start >= a) & (ops.start < b)
        coll = union(ops.start[inside & hit], ops.dur[inside & hit])
        rest = union(ops.start[inside & ~hit], ops.dur[inside & ~hit])
        out.append(_minus(coll, rest))
    return out


def read(spec, evidence):
    trace = evidence.trace
    if trace is None or not trace.devices:
        return None
    per_chip = []
    for dev in trace.devices:
        runs = whole_modules(dev, spec["module_pattern"], trace.window)
        if runs:
            per_chip.append(median(exposed_per_step(
                dev, spec["op_pattern"], runs)) / 1e6)
    if not per_chip:
        return None
    rx = re.compile(spec["op_pattern"])
    log("collectives by chip, exposed ms a step: "
        f"{[round(x, 4) for x in per_chip]}; operations matched: "
        f"{sorted({n for n in trace.devices[0].ops.names if rx.search(n)})}")
    return float(median(per_chip))
