"""Milliseconds of the WHOLE measured window (`_window.py`) in which the
engine's loop stood still for no reason its schedule gives, summed:

  host work  a tick less its `serve.sync` children is what the host did
             besides waiting. A tick whose host work is over `HOST_TIMES` x
             the window's median counts its excess over that median: a
             collector pause, a compile, a thread that was not scheduled.
  waits      a `serve.sync` behind NO prefill call (its dispatch has
             `prefill_rows` 0) waits one step of the device. One over
             `SYNC_TIMES` x the median such sync counts its excess: the
             main thread asleep on a transfer that had finished, a device
             that stalled.

Ticks that touch a profiler gap are left out. Stalls are rare (0-3 a window)
and an 8 s capture mostly misses them; the log holds every tick. A sound
run reads 0-300; a run whose `serve_tokens_per_s` read low for no reason
shows its seconds here, and which of the two kinds they were.

It logs every collection of a millisecond or more that fell in the window
(`py.gc`: when, how long, which generation, how many objects: a full
collection of a heap that grows is the pause a kind may rest) and every
tick it counted: when (seconds into the window), how long,
its host work and syncs, the self time of each span inside it by innermost
span (`py.gc`, JAX's phases and the program's own), whether the tick held a
prefill call, and, where the tick lies in the capture and the trace has the
first chip, how long that chip was idle inside it.
"""
from perfbench.harness import log, median
from perfbench.readers import _spans, _window
from perfbench.trace_reduce import union

HOST_TIMES = 5.0
SYNC_TIMES = 2.0
LOGGED = 12


def chip_idle(evidence, records):
    """f(tick) -> ' chip 0 idle x ms of it' for a captured tick, from the
    device trace on the checked clock; '' where there is none."""
    trace = evidence.trace
    if trace is None or not trace.devices or trace.window_s <= 0:
        return lambda tick: ""
    try:
        clock = _spans.tick_clock(trace, records)
    except _spans.ClockMismatch as e:
        # `host_caused_idle` fails the run for it; here it costs a remark
        log(f"  no chip time beside the ticks: {e}")
        clock = None
    if clock is None:
        return lambda tick: ""
    ops = trace.devices[0].ops
    idle = _spans.IdleTime(union(ops.start, ops.dur), trace.window)

    def of(tick):
        if not tick.in_capture:
            return "; outside the capture"
        ns = idle.inside(tick.start_ns + clock[0], tick.end_ns + clock[0])
        return f"; chip 0 idle {_spans.ms(ns):.3f} ms of it"
    return of


def tick_lines(window, tick, host_ns, syncs, idle_of):
    inside = [r for r in window.records if r.thread == tick.thread
              and r.start_ns >= tick.start_ns and r.end_ns <= tick.end_ns]
    own, _ = _spans.self_times(inside)
    by_name = {}
    for r in inside:
        by_name[r.name] = by_name.get(r.name, 0.0) + own[r.id]
    prefill = any(r.name == "serve.prefill" for r in inside)
    parts = ", ".join(f"{n} {_spans.ms(v):.3f}" for n, v in sorted(
        by_name.items(), key=lambda kv: -kv[1]))
    return [f"  at {(tick.start_ns - window.start_ns) / 1e9:.3f} s: tick "
            f"{_spans.ms(tick.duration_ns):.3f} ms, host work "
            f"{_spans.ms(host_ns):.3f}, syncs "
            f"{[round(_spans.ms(s.duration_ns), 3) for s in syncs]}; "
            f"{'held' if prefill else 'no'} prefill call"
            f"{idle_of(tick)}",
            f"    self ms by innermost span: {parts}"]


def read(spec, evidence):
    window = _window.find(evidence)
    if window is None:
        return None
    syncs = window.children("serve.sync")
    ticks = [t for t in window.ticks
             if not window.straddles(t.start_ns, t.end_ns)]
    if not ticks:
        return None
    host = {t.id: t.duration_ns - sum(s.duration_ns
                                      for s in syncs.get(t.id, ()))
            for t in ticks}
    host_med = median(list(host.values()))
    plain = [s for t in ticks for s in syncs.get(t.id, ())
             if _window.dispatch_attrs(window, s).get("prefill_rows", 0) == 0]
    sync_med = median([s.duration_ns for s in plain]) if plain else 0.0
    long_sync = {s.id for s in plain
                 if s.duration_ns > SYNC_TIMES * sync_med}
    counted = []                       # (stall ns, tick, of host, of waits)
    for t in ticks:
        of_host = (host[t.id] - host_med
                   if host[t.id] > HOST_TIMES * host_med else 0.0)
        of_waits = sum(s.duration_ns - sync_med
                       for s in syncs.get(t.id, ()) if s.id in long_sync)
        if of_host or of_waits:
            counted.append((of_host + of_waits, t, of_host, of_waits))
    total = sum(c[0] for c in counted)
    gc_recs = [r for r in window.records if r.name == "py.gc"
               and window.start_ns <= r.start_ns <= window.end_ns]
    log(window.describe())
    log(f"engine stalls: {len(ticks)} ticks of the window outside the "
        f"profiler gaps; median host work {_spans.ms(host_med):.4f} ms "
        f"(counted over {HOST_TIMES:g} x), median sync behind no prefill "
        f"call {_spans.ms(sync_med):.4f} ms over {len(plain)} (counted over "
        f"{SYNC_TIMES:g} x); {len(counted)} ticks counted, "
        f"{_spans.ms(total):.3f} ms: "
        f"{_spans.ms(sum(c[2] for c in counted)):.3f} of host work, "
        f"{_spans.ms(sum(c[3] for c in counted)):.3f} of waits; "
        f"{len(gc_recs)} py.gc records in the window, "
        f"{_spans.ms(sum(r.duration_ns for r in gc_recs)):.3f} ms")
    for r in sorted(gc_recs, key=lambda r: -r.duration_ns)[:LOGGED]:
        log(f"  py.gc at {(r.start_ns - window.start_ns) / 1e9:.3f} s: "
            f"{_spans.ms(r.duration_ns):.3f} ms, generation "
            f"{r.attrs.get('generation')}, {r.attrs.get('collected')} "
            f"objects collected")
    idle_of = chip_idle(evidence, window.records)
    for _, t, _, _ in sorted(counted, key=lambda c: -c[0])[:LOGGED]:
        for line in tick_lines(window, t, host[t.id], syncs.get(t.id, ()),
                               idle_of):
            log(line)
    if len(counted) > LOGGED:
        log(f"  ({len(counted) - LOGGED} shorter ones not listed)")
    return _spans.ms(total)
