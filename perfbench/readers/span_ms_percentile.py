"""A percentile, in milliseconds, of the durations of one of the program's
own spans (`spec["span"]`, `spec["q"]`) over those recorded in the traced
sub-window — less, with `minus_children`, the time of its children of
those names: `serve.tick` minus its `serve.sync` children is what the host
did in a tick besides waiting for the device.

With `"table": "tick"` it logs what that number is the total of: the mean
self time of every span of a tick, by name; the program's median tick and
median sync beside the harness's own median `perfbench.tick`; and the
same two medians over the ticks recorded after the capture stopped, where
no profiler slows the host.
"""
from perfbench.harness import log, median, percentile
from perfbench.readers import _spans


def children_ns(records, names):
    """Nanoseconds of each span's children named in `names`, by parent."""
    out = {}
    for r in records:
        if r.name in names and r.parent is not None:
            out[r.parent] = out.get(r.parent, 0) + r.duration_ns
    return out


def tick_table(records, ticks, evidence):
    """Lines: self time by span name a tick, the medians that must agree,
    every tick, and the ticks after the capture."""
    t0, t1 = ticks[0].start_ns, ticks[-1].end_ns
    inside = [r for r in records if r.thread == ticks[0].thread
              and r.start_ns >= t0 and r.end_ns <= t1]
    own, _ = _spans.self_times(inside)
    by_name = {}
    for r in inside:
        by_name[r.name] = by_name.get(r.name, 0.0) + own[r.id]
    n = len(ticks)
    lines = _spans.table(
        f"self time by span, mean of {n} ticks of the traced sub-window:",
        [(name, _spans.ms(v) / n) for name, v in by_name.items()], "ms")
    sync = children_ns(records, {"serve.sync"})
    dispatch = children_ns(records, {"serve.decode_step"})

    def med(ticks_, ns):
        return median([_spans.ms(ns(t)) for t in ticks_])
    line = (f"  median serve.tick {med(ticks, lambda t: t.duration_ns):.4f} "
            f"ms, median serve.sync "
            f"{med(ticks, lambda t: sync.get(t.id, 0)):.4f} ms")
    spans = evidence.trace.spans
    harness = [_spans.ms(d) for name, d in zip(spans.names, spans.dur)
               if name == _spans.HARNESS_TICK]
    if harness:
        line += (f"; the harness's median perfbench.tick "
                 f"{median(harness):.4f} ms over {len(harness)} ticks")
    lines.append(line)
    lines.append("  each tick (tick/sync/dispatch ms): " + " ".join(
        f"{_spans.ms(t.duration_ns):.1f}/{_spans.ms(sync.get(t.id, 0)):.1f}/"
        f"{_spans.ms(dispatch.get(t.id, 0)):.1f}" for t in ticks[:40]))
    # the same ticks' later kin, with no profiler running: what the
    # capture itself adds to the host's work shows in the difference
    after = [r for r in records if r.name == _spans.PROGRAM_TICK
             and not r.in_capture and r.start_ns > t1]
    if after:
        lines.append(
            f"  after the capture, no profiler, {len(after)} ticks: median "
            f"tick less its syncs "
            f"{med(after, lambda t: t.duration_ns - sync.get(t.id, 0)):.4f} "
            f"ms, median serve.decode_step "
            f"{med(after, lambda t: dispatch.get(t.id, 0)):.4f} ms")
    return lines


def read(spec, evidence):
    records = _spans.program_log()
    if records is None or evidence.trace is None:
        return None
    spans = _spans.captured(records, spec["span"])
    if not spans:
        return None
    children = children_ns(records, set(spec.get("minus_children", ())))
    values = [_spans.ms(r.duration_ns - children.get(r.id, 0))
              for r in spans]
    if spec.get("table") == "tick":
        for line in tick_table(records, spans, evidence):
            log(line)
    return percentile(values, spec["q"])
