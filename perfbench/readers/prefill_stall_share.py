"""Decode time lost to prefill calls, as a share of the traced sub-window,
from the program's own spans.

The engine's loop has one blocking read, `serve.sync`, and each names the
dispatch it waited on (`caused_by`); that dispatch's span says whether a
prefill call was queued ahead of it on the device (`prefill_rows` > 0).
From one sync's end to the next is one step of the device. A step behind
a prefill call is longer than a step alone by the call's time, during
which no row decodes: the stall is the sum, over the steps behind a call,
of the step less the median step alone, over the sub-window. 0 with no
call in it. Steps whose earlier sync lies outside the capture are left
out (the profiler's start holds the host for seconds). Where every step
of the sub-window stood behind a call, the shortest step stands for the
step alone.

It logs the steps it compared and, from the device trace, the device time
of the prefill program's executions in the sub-window (whole or cut): the
two shares should agree.

What it is not: a rate. The sub-window is 8 s of a fixed schedule and a
call is 0.8 to 2.2 s, so the value is set by how many calls, of which
buckets, the 8 s happen to hold, and only then by what a call costs. Read
it against another run's only where the logged call count and buckets are
the same; a change that moves one retirement moves a call across the
sub-window's edge and this number by the call's whole time. The cure is
the window's bounds on the program's clock in `Evidence`, for the next
benchmark PR (PERF.md section 7): the log already holds every sync.
"""
import re

from perfbench.harness import log, median
from perfbench.readers import _spans


PREFILL_MODULE = re.compile("prefill")


def read(spec, evidence):
    records = _spans.program_log()
    trace = evidence.trace
    if records is None or trace is None or trace.window_s <= 0:
        return None
    by_id = {r.id: r for r in records}
    syncs = sorted(_spans.captured(records, "serve.sync"),
                   key=lambda r: r.end_ns)
    alone, behind = [], {}            # ns of each step; behind: by bucket
    for prev, cur in zip(syncs, syncs[1:]):
        attrs = getattr(by_id.get(cur.caused_by), "attrs", {})
        if attrs.get("prefill_rows", 0) > 0:
            behind.setdefault(attrs.get("prefill_bucket", 0), []).append(
                cur.end_ns - prev.end_ns)
        else:
            alone.append(cur.end_ns - prev.end_ns)
    stalled = [ns for v in behind.values() for ns in v]
    base = median(alone) if alone else min(stalled, default=0.0)
    lost = sum(ns - base for ns in stalled)
    window_ns = trace.window_s * 1e9
    calls = "; ".join(
        f"bucket {b}: {len(v)} of median {_spans.ms(median(v)):.1f} ms"
        for b, v in sorted(behind.items())) or "none"
    log(f"prefill stall: {len(alone) + len(stalled)} steps between syncs in "
        f"the traced sub-window, {len(stalled)} behind a prefill call "
        f"({calls}); a step alone {_spans.ms(base):.3f} ms; lost "
        f"{_spans.ms(lost):.1f} ms of {_spans.ms(window_ns):.1f}")
    if trace.devices:
        m = trace.devices[0].modules
        on_device = sum(d for n, d in zip(m.names, m.dur)
                        if PREFILL_MODULE.search(n))
        log(f"  device time of the prefill program's executions in the "
            f"sub-window: {_spans.ms(on_device):.1f} ms = "
            f"{100.0 * on_device / window_ns:.3f}% of it")
    return 100.0 * lost / window_ns
