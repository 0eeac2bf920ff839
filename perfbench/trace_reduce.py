"""From a profiler trace (`.xplane.pb`) to numbers.

Reads the trace with `jax.profiler.ProfileData` and nothing else. What
it takes from a TPU trace:

  device planes   `/device:TPU:<n>`; on each, the line "XLA Ops" holds one
                  event per executed operation and "XLA Modules" one per
                  executed program (a jitted step, a prefill call);
  host plane      `/host:CPU`; the events whose names start with the
                  harness's prefix are its own `TraceAnnotation` spans.

Busy time of a chip is the union of its operation intervals inside the
window; idle gaps are the rest, each attributed to the harness span that
covers most of it. Names drop the `%` and the `.<n>` suffixes the
compiler appends, so `fusion.12` and `fusion.7` add up under `fusion`.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.trace_window"

_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def op_name(raw: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion`: the trace names an
    operation by its whole HLO text; the name is what stands before the
    ` = `, without the `%` and the `.<n>` suffixes."""
    return _SUFFIX.sub("", raw.split(" = ", 1)[0].lstrip("%").strip())


def module_name(raw: str) -> str:
    return _MODULE_ID.sub("", raw.strip())


@dataclasses.dataclass
class Events:
    """Named intervals in nanoseconds, sorted by start."""
    names: List[str]
    start: np.ndarray
    dur: np.ndarray

    @classmethod
    def build(cls, rows):
        rows = sorted(rows, key=lambda r: r[1])
        return cls([r[0] for r in rows],
                   np.array([r[1] for r in rows], np.float64),
                   np.array([r[2] for r in rows], np.float64))

    def clip(self, t0, t1) -> "Events":
        """Events that overlap [t0, t1], cut to it."""
        end = self.start + self.dur
        keep = (end > t0) & (self.start < t1)
        s = np.maximum(self.start[keep], t0)
        e = np.minimum(end[keep], t1)
        return Events([n for n, k in zip(self.names, keep) if k], s, e - s)

    def sums(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n, d in zip(self.names, self.dur):
            out[n] = out.get(n, 0.0) + float(d)
        return out


def union(start: np.ndarray, dur: np.ndarray) -> List[Tuple[float, float]]:
    """Merged intervals of sorted (start, dur)."""
    merged: List[Tuple[float, float]] = []
    for s, d in zip(start, dur):
        e = s + d
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


@dataclasses.dataclass
class DeviceTrace:
    index: int
    ops: Events
    modules: Events


@dataclasses.dataclass
class TraceSummary:
    devices: List[DeviceTrace]
    spans: Events                     # the harness's host spans
    window: Tuple[float, float]       # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = [sum(e - s for s, e in union(d.ops.start, d.ops.dur))
               for d in self.devices]
        return float(np.mean(per)) / 1e9 if per else 0.0

    def device_ops(self, top: int = 10) -> List[List]:
        """Operations by summed seconds, averaged over the chips."""
        total: Dict[str, float] = {}
        for d in self.devices:
            for n, v in d.ops.sums().items():
                total[n] = total.get(n, 0.0) + v
        k = max(1, len(self.devices))
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[n, v / k / 1e9] for n, v in rows]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """Idle seconds of the first chip by the harness span that covers
        most of each gap ("(no span)" where none does)."""
        if not self.devices:
            return []
        d = self.devices[0]
        t0, t1 = self.window
        gaps, cursor = [], t0
        for s, e in union(d.ops.start, d.ops.dur):
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if t1 > cursor:
            gaps.append((cursor, t1))
        sp_end = self.spans.start + self.spans.dur
        total: Dict[str, float] = {}
        for g0, g1 in gaps:
            overlap = np.minimum(sp_end, g1) - np.maximum(self.spans.start,
                                                          g0)
            best, best_dur, name = 0.0, np.inf, "(no span)"
            for i in np.nonzero(overlap > 0)[0]:
                # most overlap wins; of equals, the inner (shorter) span
                if overlap[i] > best or (overlap[i] == best and
                                         self.spans.dur[i] < best_dur):
                    best, name, best_dur = (overlap[i], self.spans.names[i],
                                            self.spans.dur[i])
            total[name] = total.get(name, 0.0) + (g1 - g0)
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[n, float(v) / 1e9] for n, v in rows]


def _line_events(line, normalise) -> Events:
    return Events.build([(normalise(e.name), float(e.start_ns),
                          float(e.duration_ns)) for e in line.events])


def reduce_trace(profile, window: Optional[Tuple[float, float]] = None
                 ) -> TraceSummary:
    """`profile`: a path to an `.xplane.pb` or a `ProfileData`. The window
    is the harness's `perfbench.trace_window` span when the trace has one,
    else the extent of the device events."""
    if isinstance(profile, (str, bytes)) or hasattr(profile, "__fspath__"):
        import jax
        profile = jax.profiler.ProfileData.from_file(str(profile))
    devices, span_rows = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = modules = Events.build([])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _line_events(line, op_name)
                elif line.name == MODULES_LINE:
                    modules = _line_events(line, module_name)
            devices.append(DeviceTrace(int(m.group(1)), ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        span_rows.append((e.name, float(e.start_ns),
                                          float(e.duration_ns)))
    devices.sort(key=lambda d: d.index)
    spans = Events.build(span_rows)
    if window is None:
        marks = [i for i, n in enumerate(spans.names) if n == WINDOW_SPAN]
        if marks:
            i = marks[0]
            window = (spans.start[i], spans.start[i] + spans.dur[i])
        else:
            starts = [d.ops.start.min() for d in devices if len(d.ops.start)]
            ends = [(d.ops.start + d.ops.dur).max() for d in devices
                    if len(d.ops.start)]
            window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    t0, t1 = window
    for d in devices:
        d.ops = d.ops.clip(t0, t1)
        d.modules = d.modules.clip(t0, t1)
    keep = [i for i, n in enumerate(spans.names) if n != WINDOW_SPAN]
    spans = Events([spans.names[i] for i in keep], spans.start[keep],
                   spans.dur[keep]).clip(t0, t1)
    return TraceSummary(devices, spans, (t0, t1))
