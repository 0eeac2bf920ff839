"""Whole executions of a program inside the traced window, and the
operations that ran inside them."""
import re

import numpy as np


def whole_modules(device, pattern, window):
    """(start, end) of each execution matching `pattern` that lies wholly
    inside the window (the trace cuts the first and the last)."""
    rx = re.compile(pattern)
    t0, t1 = window
    out = []
    m = device.modules
    for n, s, d in zip(m.names, m.start, m.dur):
        if rx.search(n) and s > t0 and s + d < t1:
            out.append((s, s + d))
    return out


def op_time_inside(device, pattern, intervals):
    """Nanoseconds of operations matching `pattern` that start inside one
    of `intervals` (sorted, disjoint)."""
    if not intervals:
        return 0.0
    rx = re.compile(pattern)
    starts = np.array([a for a, _ in intervals])
    ends = np.array([b for _, b in intervals])
    total = 0.0
    ops = device.ops
    hit = np.array([bool(rx.search(n)) for n in ops.names], bool)
    for s, d in zip(ops.start[hit], ops.dur[hit]):
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i >= 0 and s < ends[i]:
            total += d
    return total
