"""The measured window, found in the program's own span log.

The harness counts the worked ticks of its window (`counters["serve.ticks"]`),
every worked tick leaves one `serve.tick` record (a tick that found nothing
to do is dropped), and no kind of traffic ticks the engine after its window
closes: the window's ticks are the LAST `serve.ticks` records of that name,
on the program's own clock (`time.perf_counter_ns`, which is the harness's
clock too). So what happens a handful of times a window (a replacement
prefill call, a stall, a request's admission) is read over all 51 s of it
and not over the 8 s the profiler happened to capture.

`find` checks what it can: it raises `WindowNotFound` where the log holds
fewer ticks than the harness counted, or where a tick recorded during the
capture lies outside the ticks it picked (the capture is a sub-window, so
that would be another run's log or a miscount); `_spans.program_log` raises
`LogWrapped` where the log is full and its oldest records are gone.

The profiler's start and its stop each hold the host between two ticks, the
stop for 10-18 s of a traced window (measured, PR 37: it reduces 8 s of
device events there). `Window.gaps` names both: from the end of the last
tick on one side of a flip of `in_capture` to the end of the first tick on
the other (that tick finds a device that has long been idle, and the
profiler's after-effects in its own work). A reader of short things (a step,
a tick) leaves out what `straddles` a gap: it measures the profiler, not the
program. A reader of long things (a request's wait, which in a window with a
16 s hold nearly always reaches into it) takes `held_ns` off instead: the
part of the interval in which the engine stood still, from the end of the
tick before the hold to the start of the tick after it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

from perfbench.readers import _spans

TICK = _spans.PROGRAM_TICK
COUNTER = "serve.ticks"


class WindowNotFound(RuntimeError):
    """The log's ticks are not the window's."""


@dataclasses.dataclass
class Window:
    records: list                      # the whole log
    ticks: list                        # the window's `serve.tick`s, by start
    gaps: List[Tuple[int, int]]        # the profiler's start and stop
    holds: List[Tuple[int, int]]       # of each gap, the part between ticks

    @functools.cached_property
    def by_id(self) -> dict:
        return {r.id: r for r in self.records}

    @property
    def start_ns(self) -> int:
        return self.ticks[0].start_ns

    @property
    def end_ns(self) -> int:
        return self.ticks[-1].end_ns

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def straddles(self, start_ns: float, end_ns: float) -> bool:
        """Whether (start_ns, end_ns) reaches into a profiler gap."""
        return any(a < end_ns and start_ns < b for a, b in self.gaps)

    def held_ns(self, start_ns: float, end_ns: float) -> float:
        """The nanoseconds of (start_ns, end_ns) in which the profiler held
        the host between two ticks (`holds`)."""
        return sum(max(0, min(end_ns, b) - max(start_ns, a))
                   for a, b in self.holds)

    def children(self, name: str) -> Dict[int, list]:
        """The window's spans of `name`, by the id of their tick."""
        ids = {t.id for t in self.ticks}
        out: Dict[int, list] = {}
        for r in self.records:
            if r.name == name and r.parent in ids:
                out.setdefault(r.parent, []).append(r)
        return out

    def closed_inside(self, name: str) -> list:
        """Records of `name` that ENDED inside the window, by end: how a
        span that lives across ticks (a request's phase) is placed."""
        return sorted((r for r in self.records if r.name == name
                       and self.start_ns <= r.end_ns <= self.end_ns),
                      key=lambda r: r.end_ns)

    def describe(self) -> str:
        gaps = ", ".join(
            f"{(a - self.start_ns) / 1e9:.3f}-{(b - self.start_ns) / 1e9:.3f}"
            for a, b in self.gaps) or "none"
        return (f"window: the last {len(self.ticks)} serve.tick records, "
                f"{self.seconds:.3f} s on the program's clock; profiler "
                f"gaps at {gaps} s, in which it held the host "
                f"{self.held_ns(self.start_ns, self.end_ns) / 1e9:.3f} s")


def find(evidence) -> Optional[Window]:
    """The window of this run, or None where the program keeps no log or
    the harness counted no tick. Raises `WindowNotFound`, `LogWrapped`."""
    records = _spans.program_log()
    n = int(evidence.counters.get(COUNTER, 0))
    if records is None or n <= 0:
        return None
    ticks = sorted((r for r in records if r.name == TICK),
                   key=lambda r: r.start_ns)
    if len(ticks) < n:
        raise WindowNotFound(
            f"the harness counted {n} worked ticks in its window and the "
            f"program's log holds {len(ticks)} {TICK} records")
    window = ticks[-n:]
    stray = [t for t in ticks[:-n] if t.in_capture]
    if stray:
        raise WindowNotFound(
            f"{len(stray)} {TICK} records of the capture lie before the "
            f"last {n}: the capture is a part of the window, so these are "
            f"not the window's ticks")
    flips = [(a, b) for a, b in zip(window, window[1:])
             if a.in_capture != b.in_capture]
    return Window(records, window,
                  gaps=[(a.end_ns, b.end_ns) for a, b in flips],
                  holds=[(a.end_ns, b.start_ns) for a, b in flips])


def dispatch_attrs(window: Window, sync) -> dict:
    """The attributes of the dispatch a `serve.sync` waited on."""
    return getattr(window.by_id.get(sync.caused_by), "attrs", {})


def steps(window: Window) -> Tuple[List[float], Dict[int, List[float]], float]:
    """The window's device steps, sync end to sync end, as
    `prefill_stall_share` reckons them: (ns of each step alone; ns of each
    step behind a prefill call, by bucket; ns left out astride a gap)."""
    syncs = sorted((s for v in window.children("serve.sync").values()
                    for s in v), key=lambda r: r.end_ns)
    alone: List[float] = []
    behind: Dict[int, List[float]] = {}
    left_out = 0.0
    for prev, cur in zip(syncs, syncs[1:]):
        ns = cur.end_ns - prev.end_ns
        if window.straddles(prev.end_ns, cur.end_ns):
            left_out += ns
            continue
        attrs = dispatch_attrs(window, cur)
        if attrs.get("prefill_rows", 0) > 0:
            behind.setdefault(attrs.get("prefill_bucket", 0), []).append(ns)
        else:
            alone.append(ns)
    return alone, behind, left_out
