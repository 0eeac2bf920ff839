"""A percentile of the device time of one program's whole executions in
the traced window (`module_pattern`, `q`), in milliseconds, first chip."""
from perfbench.harness import percentile
from perfbench.readers._trace import whole_modules


def read(spec, evidence):
    trace = evidence.trace
    if trace is None or not trace.devices:
        return None
    runs = whole_modules(trace.devices[0], spec["module_pattern"],
                         trace.window)
    if not runs:
        return None
    return percentile([(b - a) / 1e6 for a, b in runs], spec["q"])
