"""What every kind of traffic shares: the clock that set-up is counted
from, the look for the chip, the compile cache, the count of compiles
inside the window, the traced sub-window, and the evidence that the
per-layer readers read."""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import statistics
import tempfile
import time
from typing import Any, Dict, List, Optional

#: set by run.py before it imports anything heavy: set-up is counted from
#: here, about 50 ms after the process began
PROCESS_START = time.perf_counter()

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    """An earlier line of the run's output (never the last one)."""
    print(msg, flush=True)


class Phases:
    """The split of set-up: `mark(label)` closes the phase that began at
    the previous mark (the first began with the process)."""

    def __init__(self):
        self.last = PROCESS_START
        self.rows: List[tuple] = []

    def mark(self, label: str) -> None:
        now = time.perf_counter()
        self.rows.append((label, now - self.last))
        self.last = now

    def line(self, total: float) -> str:
        return (f"setup_s {total:.3f} = "
                + " + ".join(f"{label} {s:.3f}" for label, s in self.rows))


class NoChip(RuntimeError):
    """JAX found no accelerator, or not the chips the cell asks for."""


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        raise NoChip(
            f"this cell needs {chips} TPU chip(s); jax reports "
            f"{len(devices)} device(s) of platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}). The "
            f"benchmark measures on the chip and nowhere else.")
    return devices


def enable_cache() -> Optional[str]:
    """The program's own placement of the persistent compile cache
    (`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_compile_cache`),
    with every program kept, however quick its compile."""
    import jax
    from mpi_operator_tpu.utils.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compiles while `active` (a cache hit is no compile)."""

    def __init__(self):
        import jax
        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == COMPILE_EVENT:
            self.count += 1

    @contextlib.contextmanager
    def window(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def peaks_of(devices) -> Dict[str, float]:
    """The chip's peaks for the roofline and MFU readers; nothing off-TPU
    (the tests), where those readers then find nothing to read."""
    from perfbench.peaks import device_peaks
    if devices[0].platform != "tpu":
        return {}
    return device_peaks(devices[0].device_kind)


def delete_arrays(tree) -> None:
    """Free device memory now, not when the collector gets to it."""
    import jax
    for x in jax.tree.leaves(tree):
        if hasattr(x, "delete") and not x.is_deleted():
            x.delete()


def span(name: str):
    """A host span on the profiler's clock; near free outside a capture."""
    import jax
    return jax.profiler.TraceAnnotation("perfbench." + name)


class SubWindowTracer:
    """Profiles `[start_after, start_after + length)` seconds of the
    measured window: a short trace in a run of its own, as the
    measurement guide asks. `poll(now)` is called by the kind's loop
    between units of work; the trace is reduced after the window."""

    def __init__(self, enabled: bool, start_after: float, length: float):
        self.start_after = start_after
        self.length = length
        self.dir: Optional[str] = None
        self._state = "idle" if enabled else "off"
        self._window = None
        #: (begin, end) on `time.perf_counter` of the profiler's own start
        #: and stop, each of which holds the host for seconds: samples
        #: that overlap them measure the profiler, and the kinds drop them
        self.disturbed: List[tuple] = []

    def poll(self, elapsed: float) -> None:
        if self._state == "idle" and elapsed >= self.start_after:
            import jax
            self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            t = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.disturbed.append((t, time.perf_counter()))
            self._window = span("trace_window")
            self._window.__enter__()
            self._state = "on"
            self._t0 = elapsed + self.disturbed[-1][1] - t
        elif self._state == "on" and elapsed >= self._t0 + self.length:
            self.stop()

    def stop(self) -> None:
        if self._state == "on":
            import jax
            self._window.__exit__(None, None, None)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.disturbed.append((t, time.perf_counter()))
            self._state = "done"

    def overlaps(self, t0: float, t1: float) -> bool:
        """Whether [t0, t1] on `time.perf_counter` touches the profiler's
        own start or stop."""
        return any(a <= t1 and t0 <= b for a, b in self.disturbed)

    def summary(self, keep_copy: Optional[str] = None):
        """The reduced trace, or None when tracing was off. The raw file
        is removed (or moved to `keep_copy`, a directory)."""
        self.stop()
        if self._state != "done":
            return None
        from perfbench.trace_reduce import reduce_trace
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        try:
            if not files:
                raise RuntimeError(f"the profiler wrote no trace under "
                                   f"{self.dir}")
            if keep_copy:
                os.makedirs(keep_copy, exist_ok=True)
                shutil.copy(files[0], keep_copy)
            return reduce_trace(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by linear interpolation (q in 0..100)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Evidence:
    """What a run hands the per-layer readers."""
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    shapes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None                 # trace_reduce.TraceSummary or None
    peaks: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Outcome:
    """What a kind returns to run.py."""
    end_to_end: Dict[str, float]
    evidence: Evidence
    correct: bool
    attempted: int
    failed: int
    memory_peak_bytes: int


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit; printed in every run."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit

    def line(self) -> str:
        return (f"check {self.name}: {self.value:.6g} (limit "
                f"{self.limit:.6g}) {'ok' if self.ok else 'NOT CORRECT'}")


def judge(checks: List[Check]) -> bool:
    for c in checks:
        log(c.line())
    return all(c.ok for c in checks)


@dataclasses.dataclass
class Context:
    manifest: Any
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    keep_trace: Optional[str] = None


def median(values):
    return statistics.median(values)
