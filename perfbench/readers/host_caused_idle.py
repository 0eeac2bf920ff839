"""Idle time of the first chip that the host caused, as a share of the
traced sub-window: the gaps between device operations that fall OUTSIDE
every `serve.sync` of the program. Inside a sync the host is waiting for
the device, so a gap there is the device's own; outside, the device had
nothing queued while the host scheduled, built arrays, dispatched or
streamed tokens.

The program's spans are on `time.perf_counter_ns()`, the device's
operations on the trace's clock; `_spans.tick_clock` maps one onto the
other and raises where they do not fit. It logs the offset with its worst
residual, and the idle time by innermost program span.
"""
from perfbench.harness import log
from perfbench.readers import _spans
from perfbench.trace_reduce import union

PROGRAM_PREFIX = "serve."
WAITING = "serve.sync"      # inside it the host waits for the device


def read(spec, evidence):
    records = _spans.program_log()
    trace = evidence.trace
    if records is None or trace is None:
        return None
    clock = _spans.tick_clock(trace, records)
    if clock is None:
        return None
    offset, residual = clock
    log(f"clock: program + {offset:.0f} ns = trace, over "
        f"{len(_spans.captured(records, _spans.PROGRAM_TICK))} ticks; worst "
        f"residual {residual / 1e3:.3f} us (tolerance "
        f"{_spans.CLOCK_TOLERANCE_NS / 1e3:.0f} us)")
    if not trace.devices or trace.window_s <= 0:
        return None
    ops = trace.devices[0].ops
    idle = _spans.IdleTime(union(ops.start, ops.dur), trace.window)
    spans = [(r.start_ns + offset, r.end_ns + offset, r.name)
             for r in records
             if r.in_capture and r.name.startswith(PROGRAM_PREFIX)]
    by_name = {}
    for a, b, name in _spans.innermost_pieces(spans):
        by_name[name] = by_name.get(name, 0.0) + idle.inside(a, b)
    by_name["(no program span)"] = idle.total - sum(by_name.values())
    for line in _spans.table(
            f"idle time of chip 0 by innermost program span "
            f"({idle.total / 1e6:.3f} ms idle of "
            f"{trace.window_s * 1e3:.1f} ms):",
            [(n, v / 1e6) for n, v in by_name.items()], "ms"):
        log(line)
    waiting = by_name.get(WAITING, 0.0)
    return 100.0 * (idle.total - waiting) / (trace.window_s * 1e9)
