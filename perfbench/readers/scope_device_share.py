"""Share of a program's device time spent in operations of one named
scope, first chip, in percent.

Over the whole executions of `module_pattern` in the traced window: the
device time of the operations whose scope matches `scope_pattern`, over
the device time of the executions. An operation's scope is the
`jax.named_scope` path its instruction was traced under, which the
program's `ServingEngine.decode_step_scopes()` reads from the compiled
step (`shapes["op_scopes"]`); a Pallas kernel's custom call carries its
scope in its own name and is matched by that. The run keeps the
operations of the first chip under their full instruction names
(`shapes["device_ops_raw"]`: names, starts, durations), which the reduced
trace folds together (`fusion.12` and `fusion.7` under `fusion`).

Where the program offers no such map (a commit before it did), or no
trace was taken, there is nothing to read.
"""
import re

import numpy as np

from perfbench.readers._trace import whole_modules


def read(spec, evidence):
    trace = evidence.trace
    scopes = evidence.shapes.get("op_scopes")
    raw = evidence.shapes.get("device_ops_raw")
    if trace is None or not trace.devices or not scopes or raw is None:
        return None
    runs = whole_modules(trace.devices[0], spec["module_pattern"],
                         trace.window)
    if not runs:
        return None
    names, start, dur = raw
    rx = re.compile(spec["scope_pattern"])
    # a step's few thousand instructions recur in every execution
    matches = {n: bool(rx.search(scopes.get(n, "")) or rx.search(n))
               for n in set(names)}
    hit = np.array([matches[n] for n in names], bool)
    starts = np.array([a for a, _ in runs])
    ends = np.array([b for _, b in runs])
    i = np.searchsorted(starts, start, side="right") - 1
    inside = (i >= 0) & (start < ends[np.maximum(i, 0)])
    total = float(np.sum(ends - starts))
    return 100.0 * float(np.sum(dur[hit & inside])) / total


def raw_device_ops(trace_dir):
    """(names, starts, durations) of the first chip's operations in the
    profiler's trace under `trace_dir`, names as the HLO has them (no `%`,
    suffix kept), times in ns on the trace's clock; None where there is no
    trace or no chip in it. Called by a kind before the trace is reduced
    and removed."""
    import glob
    import os

    import jax

    from perfbench import trace_reduce
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    profile = jax.profiler.ProfileData.from_file(files[0])
    planes = {int(m.group(1)): plane for plane in profile.planes
              if (m := trace_reduce.DEVICE_PLANE.match(plane.name))}
    if not planes:
        return None
    for line in planes[min(planes)].lines:
        if line.name == trace_reduce.OPS_LINE:
            rows = [(e.name.split(" = ", 1)[0].lstrip("%").strip(),
                     float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
            return ([r[0] for r in rows], np.array([r[1] for r in rows]),
                    np.array([r[2] for r in rows]))
    return None
