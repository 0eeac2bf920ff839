"""Host spans of the program: one call, two places they land.

`span(name, **attrs)` names a host-thread region. It opens the
`jax.profiler.TraceAnnotation` it has always opened, so an XProf capture
shows `serve.tick`, `serve.prefill`, `serve.sync`, `train.init_state`,
`checkpoint.save` NEXT TO the device operations they caused — and when the
region closes it appends one record to a bounded in-memory log of this
process, so that the same names can be read without a capture: which sync
waited on which dispatch, what the host did in a tick besides waiting, which
program's trace, lowering, compile or cache load set-up was spent on.

The log has no switch. It is a `deque` of `LOG_BOUND` records (30-50 MB
when full, measured): a server that runs for a month keeps the last hours,
a benchmark run keeps everything (set-up alone is 20-30 thousand records,
nearly all of them JAX's nested traces). A span costs under 2 us; outside
a capture the annotation itself is a disabled-flag check. Nothing is
written to disk here — writing out is the reader's business (`records()`).

A record (`Span`):

  id          unique in the process, in order of opening
  parent      id of the innermost span open on the same thread, or None
  caused_by   id of the span named as the cause, or None — how a
              `serve.sync` names the `serve.decode_step` whose output it
              fetches, a tick later
  name, attrs attributes may be set until the span closes (`set`)
  start_ns, end_ns   on `time.perf_counter_ns()`
  in_capture  whether a profiler capture was running when it opened
  thread      `threading.get_ident()` of the thread that opened it; 0 for
              a span that lives across calls (`begin` ... `Span.end`): it
              belongs to no thread's stack, nests under no tick, and is
              in_capture by the moment it ENDED, as JAX's records are

The trees the program records:

  a serving tick (serve/engine.py `tick()`; one `serve.tick` a worked tick)
    serve.schedule     timeouts, admission    blocked: why admission
                       stopped (`none`: nobody waits; `slot`; `pages`: a
                       free slot and no span of pages for any request
                       within the lookahead; `gate`), waiting: the
                       arrived requests it left queued, pages_reserved:
                       the pages that admitted and staged requests hold,
                       pages_filled: those of them with a written position
    serve.prefill      one prefill call's dispatch, arrays built
                       width: the rows of the program it ran
                       (programs.NARROW_ROWS: a call a member, not
                       `slots` rows); state_rows (a model that keeps
                       state a slot): the rows whose chunk started at 0,
                       and so from zeros
    serve.decode_step  one decode dispatch (serve.verify_step when
                       speculating)      prefill_rows, prefill_bucket: the
                       prefill calls queued ahead of it on the device
    serve.sync         the blocking token fetch   caused_by = the dispatch
    serve.retire       tokens streamed, requests retired

  a request (serve/engine.py; begun in one tick and ended in another, so
  thread 0; every record carries request=<id>; a phase is recorded when
  it closes, and the three are contiguous and sum to the root)
    request            `submit` (its arrival, if later) -> retirement
                       prompt_len, tokens, finish_reason, pages_reserved
    request.queued     the same start -> admission     blocked_on: what
                       held it at its last failed attempt (`slot`,
                       `pages`, `gate`; `none` where none failed)
    request.prefill    admission -> its first token on the host
                       calls: the prefill calls its prompt was planned
                       into, cached_tokens
    request.decode     first token -> retirement
  A request that times out before its first token leaves no
  `request.decode`; one the router's drain takes back out of the queue
  (`ServingEngine.withdraw`) closes as `withdrawn`; one still open when
  the session closes (`finish`) leaves nothing. The disaggregated
  facade's requests never pass `ServingEngine.submit` and leave none of
  these.

  anywhere
    py.gc              a collection of Python's cyclic collector that
                       took `GC_MIN_NS` or longer, on the thread it ran
                       on, under whatever span was open    generation,
                       collected. A shorter one leaves nothing.

  An attribute is kept only where something reads it (PERF.md section 3
  names the reader of each: a request's and a phase's are the log lines of
  `perfbench/readers/request_phase_ms_percentile.py`, a collection's
  those of `engine_stall_ms.py`); the counts a tick could carry are
  `ServeTelemetry`'s gauges already — and so are the counts a decode step
  of the served model makes for itself: an expert layer's
  `moe_held_picks`, `moe_identity_picks`, `moe_load_max`, summed over the
  layers inside the step, fetched with its tokens under the same
  `serve.sync` and observed on `ServeTelemetry.step_counters`, not set as
  span attributes. What a slot holds beside its pages is a gauge
  (`slot_state_bytes`), the rows started over a counter
  (`slot_state_starts`).

  on the device (`jax.named_scope`, in the compiled programs; a device
  trace shows a Pallas kernel under its scope's name, and
  `ServingEngine.decode_step_scopes()` names every other instruction of
  the decode step by the scope it was traced under)
    mla.project      a latent-attention sublayer's q and kv projections
    mla.cache_write  its rows into the latent page pool
    mla.attend       the absorbed attention (the kernel, or the dense form)
    mla.out          the output projection
    moe.route        router logits, picks, weights, the step's counters
    moe.experts      the held experts' part (masked or grouped)
    moe.identity     the identity experts' part
    ssm.project      a state-space layer's in-, x- and dt-projections
    ssm.conv         its causal conv over the slot's tail, and the silu
    ssm.scan         the recurrence over the slot's state (one step, or a
                     chunk with the state carried in)
    ssm.out          the gate and the output projection
    swa.project      a window layer's q, k, v;  swa.cache_write  its row
                     into the slot's ring;  swa.attend  the window (the
                     paged kernel with its lower bound, or dense);
                     swa.out  the pairs' subtraction, norm and projection
    yoco.project, yoco.cache_write (the one layer that owns the pool),
    yoco.attend, yoco.out   the same for the layers that read the ONE
                     pool of keys and values
    gmu              a gated memory unit
    mlp              the gated MLP of a phi4flash layer
    gdn.project      a delta-rule mixer's [q | k | v | z] and [b | a]
                     projections, beta and the log decay
    gdn.conv         its causal conv over the slot's tail, the silu and
                     the L2 norm of q and k
    gdn.update       one position of the gated delta rule over the slot's
                     state (the kernel, or plain jax.numpy off the TPU);
                     gdn.chunk in a multi-token call, the WY form
    gdn.norm         the per-head norm and the silu(z) gate
    gdn.out          the output projection
    q3attn.project   a gated attention layer's q & gate, k, v, the q and k
                     norms and the partial rotary
    q3attn.cache_write  its rows into the layer's own page pool
    q3attn.attend    the walk of a row's pages (heads of 256)
    q3attn.out       the sigmoid gate on the heads' output, W_o
    moe.shared       a shared expert beside the held ones (and, where the
                     model has one, its own sigmoid gate)

  set-up
    serve.engine_init > serve.cast_params, serve.init_cache
    train.trainer_init; train.init_state > train.shard_init,
                                           train.optimizer_init
    each with JAX's own work under it, by `fun_name`:
    jax.trace, jax.lower, jax.compile (backend compile) or jax.cache_load
    (the same program found in the persistent cache). One of these under
    a `serve.tick` after warm-up IS the recompile, by name. `LMTrainer`'s
    step compiles at its first call, under no span of the program's; its
    jax.compile (or jax.cache_load) of `jit(_step_fn)` carries
                       grad_reductions, grad_reductions_async: the
                       reductions across chips the program holds and how
                       many of them the compiler made asynchronous (0 and
                       0 on one chip; `lm_benchmark`'s record prints them)

  input (data/prefetch.py)
    data.next          the consumer's wait on the queue   depth on entry
"""
from __future__ import annotations

import collections
import gc
import itertools
import sys
import threading
import time
from typing import Any, Dict, List, Optional

#: records kept; the oldest fall out
LOG_BOUND = 100_000

#: a collection of the cyclic collector shorter than this leaves no record
GC_MIN_NS = 1_000_000

_LOG: "collections.deque[Span]" = collections.deque(maxlen=LOG_BOUND)
_ids = itertools.count(1)
_open = threading.local()          # .stack: the thread's open spans

# resolved on the first span() — or at import when jax is already loaded —
# and not by importing jax here: the telemetry package is shared with the
# CONTROL plane (controller/metrics.py reuses the histogram/text-format
# code), which must stay importable without jax
_TraceAnnotation = None
_resolve_lock = threading.Lock()

#: JAX's own phases, reported through jax.monitoring with their duration
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


class Span:
    """One named region: a context manager while open, a record after."""

    __slots__ = ("id", "parent", "caused_by", "name", "attrs", "start_ns",
                 "end_ns", "in_capture", "thread", "_annotation", "_dropped")

    def __init__(self, name: str, caused_by: Optional[int],
                 attrs: Dict[str, Any]):
        self.id = next(_ids)
        self.parent: Optional[int] = None
        self.caused_by = caused_by
        self.name = name
        self.attrs = attrs
        self.start_ns = self.end_ns = 0
        self.in_capture = False
        self.thread = 0
        self._annotation = None
        self._dropped = False

    def set(self, **attrs) -> None:
        """Attributes known only later (a tick's counts at its end)."""
        self.attrs.update(attrs)

    def drop(self) -> None:
        """Keep no record of this span nor of what closed under it: the
        region turned out to be no work (a tick that found nothing to do,
        whose `serve.schedule` would otherwise stay behind without a
        parent and, from a server that polls, fill the log)."""
        self._dropped = True

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    def end(self, end_ns: Optional[int] = None, **attrs) -> None:
        """Close a span that `begin` opened, in whatever call and on
        whatever thread: the record goes into the log now."""
        self.attrs.update(attrs)
        self.end_ns = time.perf_counter_ns() if end_ns is None else end_ns
        self.in_capture = _capturing()
        _LOG.append(self)

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.thread = threading.get_ident()
        self.in_capture = _TraceAnnotation.is_enabled()
        self._annotation = _TraceAnnotation(self.name)
        stack.append(self)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _stack().pop()
        if not self._dropped:
            _LOG.append(self)
            return
        # what closed on this thread since this span opened lies under it,
        # at the log's end; another thread's records go back as they were
        others = []
        while _LOG and _LOG[-1].end_ns >= self.start_ns:
            rec = _LOG.pop()
            if rec.thread != self.thread:
                others.append(rec)
        _LOG.extend(reversed(others))

    def __repr__(self) -> str:
        return (f"Span({self.id} {self.name!r} parent={self.parent} "
                f"caused_by={self.caused_by} "
                f"{self.duration_ns / 1e6:.3f} ms {self.attrs})")


def _capturing() -> bool:
    return _TraceAnnotation is not None and _TraceAnnotation.is_enabled()


def _closed(name: str, attrs: Dict[str, Any], duration_ns: int) -> None:
    """A region of this thread that ended just now and was not a `with`
    block (JAX reports a phase when it is over, the collector calls back
    at both ends): a closed record under whatever span is open here."""
    rec = Span(name, None, attrs)
    stack = _stack()
    rec.parent = stack[-1].id if stack else None
    rec.thread = threading.get_ident()
    rec.in_capture = _capturing()
    rec.end_ns = time.perf_counter_ns()
    rec.start_ns = rec.end_ns - duration_ns
    _LOG.append(rec)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """`gc.callbacks` hook: a collection that took `GC_MIN_NS` or longer
    becomes a `py.gc` record. The young collections, many a tick, cost
    two clock reads each and leave nothing."""
    if phase == "start":
        _open.gc_start_ns = time.perf_counter_ns()
        return
    took = time.perf_counter_ns() - getattr(_open, "gc_start_ns", 1 << 62)
    if took >= GC_MIN_NS:
        _closed("py.gc", {"generation": info["generation"],
                          "collected": info["collected"]}, took)


gc.callbacks.append(_on_gc)


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    """jax.monitoring listener: a phase of JAX's that just ended becomes a
    closed span under whatever program span is open on this thread. A
    persistent-cache hit reports its retrieval and THEN the backend
    compile that enclosed it; the two become one `jax.cache_load` that
    carries the program's name."""
    if event == _CACHE_LOAD_EVENT:
        _open.cache_hit = True
        return
    name = _JAX_EVENTS.get(event)
    if name is None:
        return
    attrs = {k: v for k, v in kw.items() if k == "fun_name"}
    if name == "jax.compile" and getattr(_open, "cache_hit", False):
        _open.cache_hit = False
        name = "jax.cache_load"
    _closed(name, attrs, int(duration * 1e9))


def _resolve() -> None:
    """Meet JAX: take its TraceAnnotation and listen, once, to the
    durations it reports."""
    global _TraceAnnotation
    import jax.monitoring
    from jax.profiler import TraceAnnotation
    with _resolve_lock:
        if _TraceAnnotation is None:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _TraceAnnotation = TraceAnnotation


def span(name: str, caused_by: Optional[int] = None, **attrs) -> Span:
    """Context manager naming a host region: in XProf captures, and in
    this process's span log when it closes. `caused_by` names another
    span's id as the cause where that is not the enclosing span."""
    if _TraceAnnotation is None:
        _resolve()
    return Span(name, caused_by, attrs)


def begin(name: str, parent: Optional[int] = None,
          caused_by: Optional[int] = None, start_ns: Optional[int] = None,
          **attrs) -> Span:
    """Open a span that outlives the call: a request that waits through
    many ticks. It is on no thread's stack (thread 0, no annotation), so
    nothing nests under it by itself: name its `parent` by id. Nothing is
    recorded until `Span.end`; dropping a span it closed under does not
    take it along."""
    rec = Span(name, caused_by, attrs)
    rec.parent = parent
    rec.start_ns = time.perf_counter_ns() if start_ns is None else start_ns
    return rec


def records() -> List[Span]:
    """A snapshot of the closed spans, oldest first."""
    return list(_LOG.copy())      # copy() is atomic; iterating _LOG is not


def last(*names: str, **attrs) -> Optional[Span]:
    """The newest closed record with one of `names` and these attributes:
    the way to a region that was recorded when it was over (a jax.compile),
    for what is known about it only after."""
    for rec in reversed(records()):
        if rec.name in names and attrs.items() <= rec.attrs.items():
            return rec
    return None


def clear() -> None:
    """Forget every record (for the tests)."""
    _LOG.clear()


if sys.modules.get("jax") is not None:
    # jax's own phases count from the first program that is built, which
    # may be before the program's first span
    _resolve()

__all__ = ["GC_MIN_NS", "LOG_BOUND", "Span", "begin", "clear", "last",
           "records", "span"]
