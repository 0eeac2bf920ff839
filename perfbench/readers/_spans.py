"""The program's own span log (`mpi_operator_tpu/telemetry/spans.py`),
for the readers that reduce it.

The program records every `span(...)` it closes, and JAX's trace, lower,
compile and cache-load phases, in a bounded log in memory, on
`time.perf_counter_ns()`. A record has `id`, `parent` (the enclosing span
of its thread), `caused_by` (a `serve.sync` names the dispatch it waited
on), `name`, `attrs`, `start_ns`, `end_ns`, `thread`, and `in_capture`:
whether a profiler capture was running when it opened, which is how a
reader picks the records of the traced sub-window without a clock.

A program without the log (an older commit) gives `program_log()` None,
and each reader then finds nothing to read. A log that is full has lost
its oldest records, set-up's first: `program_log()` raises `LogWrapped`
instead of letting a sum come out short. A traced run of either cell holds
a third of the bound, the reference's own JAX spans included (PERF.md
section 3 has the counts).

The device trace has another clock: nanoseconds from the start of the
capture. `tick_clock` maps the one onto the other. The harness's
`perfbench.tick` annotation wraps `engine.tick()` and nothing else, so
every `serve.tick` recorded during the capture lies inside one
`perfbench.tick` of `evidence.trace.spans`; the offsets that allow that,
for every pair at once, are an interval a few microseconds wide, and its
middle is the offset. The mapping is checked, not trusted: a tick that
still pokes more than `CLOCK_TOLERANCE_NS` out of its `perfbench.tick`
raises `ClockMismatch` with the worst residual, so a wrong clock fails
the run instead of shifting a metric.
"""
from __future__ import annotations

import bisect
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

HARNESS_TICK = "perfbench.tick"
PROGRAM_TICK = "serve.tick"
CLOCK_TOLERANCE_NS = 100_000
#: the program spans whose first record inside the capture ends set-up
WINDOW_SPANS = ("serve.tick", "data.next")
JAX_SPANS = ("jax.trace", "jax.lower", "jax.compile", "jax.cache_load")


class ClockMismatch(RuntimeError):
    """The program's ticks do not fit the harness's on any one offset."""


class LogWrapped(RuntimeError):
    """The program's log reached its bound: the oldest records are gone."""


def program_log() -> Optional[list]:
    """A snapshot of the program's span log; None where the program keeps
    none. Raises `LogWrapped` where the log is full."""
    try:
        from mpi_operator_tpu.telemetry import spans
    except ImportError:
        return None
    if not hasattr(spans, "records"):
        return None
    records = spans.records()
    if len(records) >= spans.LOG_BOUND:
        raise LogWrapped(
            f"the program's span log holds {len(records)} records, its "
            f"bound: the oldest (set-up's) have fallen out, and every "
            f"number read from it would come out short")
    return records


def captured(records: Iterable, name: str) -> list:
    """Records of `name` that opened during the capture, by start."""
    return sorted((r for r in records if r.in_capture and r.name == name),
                  key=lambda r: r.start_ns)


def ms(ns: float) -> float:
    return ns / 1e6


# -- the clock ---------------------------------------------------------------

def _fit(h_start, h_end, p_start, p_end, first: int):
    """Pair program tick i with the harness tick that holds it when
    program tick 0 sits in harness tick `first`. Returns (offset, worst
    residual), or None where some program tick finds no harness tick."""
    rough = h_start[first] - p_start[0]
    # the harness tick in which each program tick ends: `rough` is short
    # of the offset by the few microseconds between the two openings, far
    # less than a worked tick lasts
    idx = np.searchsorted(h_start, p_end + rough, side="right") - 1
    if idx.min() < 0 or len(set(idx.tolist())) != len(idx):
        return None
    lo = float(np.max(h_start[idx] - p_start))     # offset >= lo
    hi = float(np.min(h_end[idx] - p_end))         # offset <= hi
    offset = (lo + hi) / 2.0
    residual = max(0.0, float(np.max(h_start[idx] - (p_start + offset))),
                   float(np.max((p_end + offset) - h_end[idx])))
    return offset, residual


def tick_clock(trace, records) -> Optional[Tuple[float, float]]:
    """(offset_ns, worst_residual_ns): add the offset to a time of the
    program's log to get the time of the same instant in the trace. None
    where the capture holds no program tick. Raises `ClockMismatch`."""
    ticks = captured(records, PROGRAM_TICK)
    harness = [i for i, n in enumerate(trace.spans.names)
               if n == HARNESS_TICK]
    if not ticks:
        return None
    if len(harness) < len(ticks):
        raise ClockMismatch(
            f"{len(ticks)} {PROGRAM_TICK} spans were recorded during the "
            f"capture and the trace holds {len(harness)} {HARNESS_TICK}")
    h_start = trace.spans.start[harness]
    h_end = h_start + trace.spans.dur[harness]
    p_start = np.array([r.start_ns for r in ticks], np.float64)
    p_end = np.array([r.end_ns for r in ticks], np.float64)
    # a harness tick that found nothing to do holds no program tick, so
    # the first program tick may sit in any of the first few
    best = None
    for first in range(len(harness) - len(ticks) + 1):
        fit = _fit(h_start, h_end, p_start, p_end, first)
        if fit is not None and (best is None or fit[1] < best[1]):
            best = fit
            if fit[1] == 0.0:
                break
    if best is None or best[1] > CLOCK_TOLERANCE_NS:
        worst = "no pairing" if best is None else f"{best[1] / 1e3:.1f} us"
        raise ClockMismatch(
            f"the program's {len(ticks)} {PROGRAM_TICK} spans do not lie "
            f"inside the trace's {HARNESS_TICK} spans on one offset: worst "
            f"residual {worst} (tolerance {CLOCK_TOLERANCE_NS / 1e3:.0f} us)")
    return best


# -- nesting -----------------------------------------------------------------

def self_times(spans: Sequence) -> Tuple[Dict[int, float], Dict[int, int]]:
    """For spans that nest by time on each thread: the nanoseconds of each
    that no span inside it covers, and the id of the outermost span it
    lies in (its own where it lies in none). JAX reports a jitted function
    traced inside another's trace, and an eager compile inside a trace, as
    spans of their own: summing durations would count that time twice."""
    own: Dict[int, float] = {}
    root: Dict[int, int] = {}
    by_thread: Dict[int, list] = {}
    for r in spans:
        by_thread.setdefault(r.thread, []).append(r)
    for rows in by_thread.values():
        rows.sort(key=lambda r: (r.start_ns, -r.end_ns))
        stack: list = []
        for r in rows:
            while stack and stack[-1].end_ns <= r.start_ns:
                stack.pop()
            own[r.id] = float(r.end_ns - r.start_ns)
            if stack:
                top = stack[-1]
                own[top.id] -= max(0.0, min(r.end_ns, top.end_ns)
                                   - max(r.start_ns, top.start_ns))
                root[r.id] = root[top.id]
            else:
                root[r.id] = r.id
            stack.append(r)
    return own, root


def path(record, by_id: Dict[int, object]) -> str:
    """`serve.engine_init>serve.init_cache` for a record whose parent is
    `serve.init_cache`. One that has none was built while no program
    span was open: the harness's own programs (its seeded weights, its
    readers of the state), and a program's step that compiles on the
    harness's first call of it; the `fun_name` tells them apart."""
    names: List[str] = []
    parent = record.parent
    while parent in by_id:
        names.append(by_id[parent].name)
        parent = by_id[parent].parent
    return ">".join(reversed(names)) or "(no program span)"


# -- set-up ------------------------------------------------------------------

def setup_spans(records) -> Optional[list]:
    """JAX's spans that ended before the first program span recorded
    during the capture began: the capture starts seconds into the window,
    nothing may compile inside the window, and the reference runs after
    it, so these are set-up and warm-up exactly. None without a capture."""
    first = [r.start_ns for r in records
             if r.in_capture and r.name in WINDOW_SPANS]
    if not first:
        return None
    cutoff = min(first)
    return [r for r in records if r.name in JAX_SPANS and r.end_ns <= cutoff]


# -- idle time ---------------------------------------------------------------

class IdleTime:
    """The idle gaps of a chip inside a window, as a function of time:
    `before(t)` is the idle nanoseconds up to `t`."""

    def __init__(self, busy: List[Tuple[float, float]],
                 window: Tuple[float, float]):
        t0, t1 = window
        gaps, cursor = [], t0
        for s, e in busy:
            if s > cursor:
                gaps.append((cursor, min(s, t1)))
            cursor = max(cursor, e)
            if cursor >= t1:
                break
        if t1 > cursor:
            gaps.append((cursor, t1))
        self.start = [g[0] for g in gaps]
        self.end = [g[1] for g in gaps]
        self.cum = np.concatenate([[0.0], np.cumsum(
            [b - a for a, b in gaps])]) if gaps else np.zeros(1)

    @property
    def total(self) -> float:
        return float(self.cum[-1])

    def before(self, t: float) -> float:
        k = bisect.bisect_right(self.start, t)      # gaps begun by t
        if k == 0:
            return 0.0
        return float(self.cum[k - 1]) + min(t, self.end[k - 1]) \
            - self.start[k - 1]

    def inside(self, a: float, b: float) -> float:
        return self.before(b) - self.before(a)


def innermost_pieces(intervals: Sequence[Tuple[float, float, str]]
                     ) -> List[Tuple[float, float, str]]:
    """Cut named intervals that nest (one thread's spans) into pieces,
    each named by the innermost interval that covers it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            _, end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for iv in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        close_until(iv[0])
        if stack and iv[0] > cursor:
            out.append((cursor, iv[0], stack[-1][2]))
        cursor = iv[0]
        stack.append(iv)
    close_until(float("inf"))
    return out


def table(title: str, rows: Sequence[Tuple[str, float]], unit: str,
          top: int = 10) -> List[str]:
    """Lines of a table of (label, value), largest first."""
    rows = sorted(rows, key=lambda kv: -kv[1])
    lines = [title]
    for label, value in rows[:top]:
        lines.append(f"  {value:12.6f} {unit}  {label}")
    if len(rows) > top:
        rest = sum(v for _, v in rows[top:])
        lines.append(f"  {rest:12.6f} {unit}  ({len(rows) - top} more)")
    return lines
