"""The measured window found in the program's span log (`readers/_window.py`)
and the six metrics read over it: hand-made logs with known answers; what
straddles the profiler's start or stop left out; a program that records no
requests (the parent commit), for which the readers of the new records find
nothing and none raises; the five older span readers unmoved by the new
records; a traced CPU run of the toy closed loop that reports all six."""
import dataclasses
import itertools

import pytest

from _perfbench_tiny import make_root
from perfbench import run
from perfbench.harness import Evidence
from perfbench.readers import (_spans, _window, engine_stall_ms,
                               host_caused_idle, pages_unfilled,
                               prefill_stall_share,
                               prefill_stall_window_share,
                               request_phase_ms_percentile,
                               setup_jax_seconds, span_attr_share,
                               span_ms_percentile, window_span_attr_share)
from perfbench.trace_reduce import DeviceTrace, Events, TraceSummary

MS = 1_000_000  # nanoseconds
S = 1000 * MS
MAIN = 11       # the thread the engine's loop runs on
_ids = itertools.count(1)

QUEUED = {"span": "request.queued", "q": 50, "account": True}
PREFILL = {"span": "request.prefill", "q": 50, "begun_inside": True}
BLOCKED = {"span": "serve.schedule", "attr": "blocked", "equals": "pages"}


@dataclasses.dataclass
class Rec:
    """A record as `mpi_operator_tpu.telemetry.spans` keeps them."""
    name: str
    start_ns: int
    end_ns: int
    parent: int = None
    caused_by: int = None
    attrs: dict = dataclasses.field(default_factory=dict)
    in_capture: bool = False
    thread: int = MAIN
    id: int = dataclasses.field(default_factory=lambda: next(_ids))

    @property
    def duration_ns(self):
        return self.end_ns - self.start_ns


def _evidence(ticks, occupancy_pct=None, slots=None, trace=None):
    counters = {"serve.ticks": float(ticks)}
    if occupancy_pct is not None:
        counters["serve.slot_occupancy_pct"] = occupancy_pct
    return Evidence(counters=counters, trace=trace,
                    shapes={"slots": slots} if slots else {})


@pytest.fixture
def log(monkeypatch):
    """Hand the readers a made-up log."""
    def give(records):
        monkeypatch.setattr(_spans, "program_log", lambda: list(records))
    return give


def _tick(t0, host_ms=5.0, sync_ms=9.0, prefill_rows=0, bucket=0,
          in_capture=False, blocked=None, pages=None, prefill_call=False,
          cause=None, waiting=None):
    """One worked tick at `t0`: schedule, (prefill,) dispatch, the sync of
    the LAST tick's dispatch (`cause`), retire. Host work is `host_ms` in
    all. Returns (records, this tick's dispatch)."""
    t = t0
    tick = Rec("serve.tick", t0, t0 + int((host_ms + sync_ms) * MS),
               in_capture=in_capture)
    attrs = {}
    if blocked is not None:
        attrs = {"blocked": blocked, "pages_reserved": pages[0],
                 "pages_filled": pages[1]}
    if waiting is not None:
        attrs["waiting"] = waiting
    recs = [tick, Rec("serve.schedule", t, t + int(0.2 * host_ms * MS),
                      parent=tick.id, attrs=attrs, in_capture=in_capture)]
    t += int(0.2 * host_ms * MS)
    if prefill_call:
        recs.append(Rec("serve.prefill", t, t + int(0.2 * host_ms * MS),
                        parent=tick.id, in_capture=in_capture))
        t += int(0.2 * host_ms * MS)
    dispatch = Rec("serve.decode_step", t, t0 + int(0.6 * host_ms * MS),
                   parent=tick.id, in_capture=in_capture,
                   attrs={"prefill_rows": prefill_rows,
                          "prefill_bucket": bucket})
    t = dispatch.end_ns
    sync = Rec("serve.sync", t, t + int(sync_ms * MS), parent=tick.id,
               caused_by=cause.id if cause else None, in_capture=in_capture)
    recs += [dispatch, sync,
             Rec("serve.retire", sync.end_ns, tick.end_ns, parent=tick.id,
                 in_capture=in_capture)]
    return recs, dispatch


def _run_of_ticks(specs, t0=100 * S, gap_ns=0):
    """Ticks back to back from `t0`, each a dict for `_tick`; `hold_ms` in
    a spec puts that much of nothing before the tick (the profiler)."""
    recs, ticks, cause, t = [], [], None, t0
    for spec in specs:
        spec = dict(spec)
        t += int(spec.pop("hold_ms", 0) * MS)
        made, dispatch = _tick(t, cause=cause, **spec)
        recs += made
        ticks.append(made[0])
        # the step dispatched here runs behind whatever this tick queued
        cause = dispatch
        t = made[0].end_ns + gap_ns
    return recs, ticks


def _warm_up():
    """What lies before a window in every log: set-up, the warm-up's and
    the first wave's ticks."""
    recs = [Rec("serve.engine_init", 0, 20 * S)]
    early, _ = _run_of_ticks([{}] * 7, t0=30 * S)
    return recs + early


# -- the window --------------------------------------------------------------

def test_the_window_is_exactly_the_last_n_ticks(log):
    recs, ticks = _run_of_ticks([{}] * 12)
    log(_warm_up() + recs)
    w = _window.find(_evidence(12))
    assert [t.id for t in w.ticks] == [t.id for t in ticks]
    assert (w.start_ns, w.end_ns) == (ticks[0].start_ns, ticks[-1].end_ns)
    assert w.seconds == pytest.approx(12 * 0.014)
    assert w.gaps == []
    # fewer counted: the LAST ones, whatever order the log holds them in
    log(list(reversed(_warm_up() + recs)))
    w = _window.find(_evidence(5))
    assert [t.id for t in w.ticks] == [t.id for t in ticks[-5:]]
    # its children are found by their tick
    assert sorted(w.children("serve.sync")) == sorted(
        t.id for t in ticks[-5:])


def test_too_few_ticks_in_the_log_raise(log):
    recs, _ = _run_of_ticks([{}] * 12)
    log(recs)
    with pytest.raises(_window.WindowNotFound, match="counted 13 .* holds 12"):
        _window.find(_evidence(13))


def test_a_wrapped_log_raises(monkeypatch):
    from mpi_operator_tpu.telemetry import spans
    recs, _ = _run_of_ticks([{}] * 12)
    monkeypatch.setattr(spans, "LOG_BOUND", len(recs))
    monkeypatch.setattr(spans, "records", lambda: list(recs))
    with pytest.raises(_spans.LogWrapped):
        _window.find(_evidence(4))
    with pytest.raises(_spans.LogWrapped):
        engine_stall_ms.read({}, _evidence(4))


def test_a_tick_of_the_capture_outside_the_last_n_raises(log):
    specs = [{}] * 3 + [{"in_capture": True}] * 4 + [{}] * 5
    recs, _ = _run_of_ticks(specs)
    log(recs)
    assert len(_window.find(_evidence(12)).gaps) == 2
    # all four inside, the profiler's start before the window's first tick
    assert len(_window.find(_evidence(9)).gaps) == 1
    with pytest.raises(_window.WindowNotFound, match="2 serve.tick records "
                       "of the capture lie before the last 7"):
        _window.find(_evidence(7))


def test_no_log_or_no_count_is_no_window(monkeypatch, log):
    recs, _ = _run_of_ticks([{}] * 3)
    log(recs)
    assert _window.find(Evidence()) is None
    monkeypatch.setattr(_spans, "program_log", lambda: None)
    assert _window.find(_evidence(3)) is None


def test_the_two_gaps_are_where_in_capture_flips(log):
    specs = ([{}] * 3 + [{"in_capture": True, "hold_ms": 2500}]
             + [{"in_capture": True}] * 3 + [{"hold_ms": 1800}] + [{}] * 2)
    recs, ticks = _run_of_ticks(specs)
    log(recs)
    w = _window.find(_evidence(10))
    # from the end of the last tick before the flip to the END of the
    # first after it
    assert w.gaps == [(ticks[2].end_ns, ticks[3].end_ns),
                      (ticks[6].end_ns, ticks[7].end_ns)]
    assert w.gaps[0][1] - w.gaps[0][0] == (2500 + 14) * MS
    assert w.straddles(ticks[3].start_ns, ticks[3].end_ns)
    assert w.straddles(ticks[1].start_ns, ticks[4].start_ns)
    assert not w.straddles(ticks[4].start_ns + 1, ticks[6].end_ns - 1)
    assert not w.straddles(ticks[0].start_ns, ticks[2].end_ns - 1)
    # the part of each in which the host was held: up to the next tick
    assert w.holds == [(ticks[2].end_ns, ticks[3].start_ns),
                       (ticks[6].end_ns, ticks[7].start_ns)]
    assert w.held_ns(w.start_ns, w.end_ns) == 4300 * MS
    assert w.held_ns(ticks[2].end_ns + 500 * MS, ticks[5].end_ns) == 2000 * MS
    assert w.held_ns(ticks[3].start_ns, ticks[6].end_ns) == 0
    assert ("profiler gaps at 0.042-2.556, 2.598-4.412 s, in which it held "
            "the host 4.300 s") in w.describe()


# -- a request's phases ------------------------------------------------------

def _requests_case():
    """A window of 40 ticks of 14 ms from 100 s, a profiler gap of 2 s
    before the eleventh and of 1 s before the twenty-first (the gaps reach
    to those ticks' ends: 0.140-2.154 and 2.280-3.294 s), and requests
    whose phases closed here and there."""
    specs = ([{}] * 10 + [{"in_capture": True, "hold_ms": 2000}]
             + [{"in_capture": True}] * 9 + [{"hold_ms": 1000}] + [{}] * 19)
    recs, ticks = _run_of_ticks(specs)
    t0 = ticks[0].start_ns

    def phase(name, rid, a_ms, b_ms, **attrs):
        return Rec(name, t0 + int(a_ms * MS), t0 + int(b_ms * MS), thread=0,
                   in_capture=False, attrs={"request": rid, **attrs})
    waits = [  # (id, queued from, to): durations 30, 50, 70, 90, 110, 10 ms
        (1, -20, 10), (2, 20, 70), (3, 2160, 2230), (4, 2170, 2260),
        (5, 3300, 3410), (6, 3420, 3430)]
    reqs = [phase("request.queued", rid, a, b,
                  blocked_on="pages" if rid % 2 else "none")
            for rid, a, b in waits]
    # one astride the first gap (it waited for the profiler: 2 s), one that
    # closed before the window, one after it
    reqs += [phase("request.queued", 7, 100, 2200, blocked_on="pages"),
             phase("request.queued", 8, -900, -5, blocked_on="slot"),
             phase("request.queued", 9, 3500, 99_000, blocked_on="slot")]
    # one of the first wave's among them: admitted before the window
    reqs += [phase("request.prefill", rid, a, b, calls=1, cached_tokens=0)
             for rid, a, b in ((0, -500, 5), (1, 10, 50), (2, 2160, 2240),
                               (3, 2250, 2400), (4, 3300, 3460))]
    return recs + reqs, ticks


def test_queue_wait_is_the_median_of_the_phases_closed_in_the_window(
        log, capsys):
    recs, _ = _requests_case()
    log(_warm_up() + recs)
    got = request_phase_ms_percentile.read(QUEUED,
                                           _evidence(40, 75.0, slots=8))
    # 10, 30, 50, 70, 90, 110 and the wait of 2 100 through the profiler's
    # first hold, which is 100 of the engine's time
    assert got == pytest.approx(70.0)
    out = capsys.readouterr().out
    assert "window: the last 40 serve.tick records, 3.560 s" in out
    assert ("request.queued: 7 closed inside the window; 1 reached into a "
            "profiler hold and are read less 2000.0 ms of it in all") in out
    assert "their blocked_on: {'pages': 4, 'none': 3}" in out
    assert ("7 read: min 10.000, p50 70.000, p90 104.000, max 110.000 ms; in "
            "the order they closed: 30 50 100 70 90 110 10") in out
    assert "WARNING" not in out
    # Little's law on the engine's time: 7 admissions in 0.56 s, 460 ms of
    # waiting in all; against 8 slots a quarter empty
    assert ("7 admissions in 0.560 s of the engine's time = 12.5000 a second "
            f"x mean wait {0.46 / 7:.4f} s = {0.46 / 0.56:.3f} requests"
            ) in out
    assert (f"the harness saw 2.000 of 8 slots empty, mean over ticks "
            f"(Little's law over it: {0.46 / 0.56 / 2:.3f})") in out
    # this log's ticks carry no `waiting` and it holds no whole request
    assert "the queue itself" not in out
    assert "no request finished inside the window" in out


def test_the_queues_account_counts_the_waits_the_close_cut_off(log, capsys):
    """A queue that grows from 2 to 6 through ten ticks: four waits of 50
    ms ended, six were cut off; and two whole requests, one of 7 tokens
    whose decode took 60 ms."""
    specs = [{"blocked": "pages", "pages": (8, 4), "waiting": w}
             for w in (2, 2, 3, 3, 4, 4, 5, 5, 6, 6)]
    recs, ticks = _run_of_ticks(specs)
    t0 = ticks[0].start_ns

    def rec(name, rid, a_ms, b_ms, parent=None, **attrs):
        return Rec(name, t0 + int(a_ms * MS), t0 + int(b_ms * MS), thread=0,
                   parent=parent, attrs={"request": rid, **attrs})
    recs += [rec("request.queued", rid, a, a + 50, blocked_on="pages")
             for rid, a in ((11, -20), (12, 0), (13, 30), (14, 80))]
    for rid, tokens, end in ((1, 7, 120), (2, 13, 130)):
        root = rec("request", rid, -100, end, prompt_len=40 + rid,
                   tokens=tokens, finish_reason="length", pages_reserved=3)
        recs += [root, rec("request.queued", rid, -100, -90, root.id,
                           blocked_on="none"),
                 rec("request.prefill", rid, -90, 60, root.id, calls=1,
                     cached_tokens=0),
                 rec("request.decode", rid, 60, end, root.id)]
    log(recs)
    got = request_phase_ms_percentile.read(QUEUED,
                                           _evidence(10, 50.0, slots=8))
    assert got == pytest.approx(50.0)
    out = capsys.readouterr().out
    rate = 4 / 0.14
    assert ("after each of 10 ticks' admissions): 2 when the window opens, "
            "mean 4.000, 6 when it closes: 6 waits cut off by the close have "
            "left no record, beside the 4 read") in out
    assert (f"is a wait of {4 / rate:.3f} s (Little's law reads "
            f"{rate * 0.05 / 4:.3f} of it") in out
    assert (f"4.000 of 8 slots empty, mean over ticks (Little's law over "
            f"it: {rate * 0.05 / 4:.3f}, the queue's mean over it: 1.000)"
            ) in out
    assert ("2 requests finished inside the window; the phases of 2 sum to "
            "their root to the nanosecond (largest difference 0 ns)") in out
    assert "their finish_reason: {'length': 2}" in out
    assert "their tokens: {7: 1, 13: 1}" in out
    assert "their prompt_len: {41: 1, 42: 1}" in out
    assert "their pages_reserved: {3: 2}" in out
    # 60 ms for the six tokens after the first, 70 for twelve
    assert (f"a token took min {70 / 12:.3f}, median "
            f"{(10 + 70 / 12) / 2:.3f}, max 10.000 ms over 2 requests") in out


def test_admission_to_first_token_leaves_out_the_first_wave(log, capsys):
    recs, _ = _requests_case()
    log(recs)
    got = request_phase_ms_percentile.read(PREFILL, _evidence(40))
    # 40, 80, 160 and one of 150 that spent 120 in the second hold; the
    # first wave's, admitted half a second before the window, is not one
    assert got == pytest.approx(60.0)
    out = capsys.readouterr().out
    assert ("request.prefill: 5 closed inside the window, 4 of them begun "
            "inside it too; 1 reached into a profiler hold and are read less "
            "120.0 ms") in out
    assert "in the order they closed: 40 80 30 160" in out
    assert "their calls: {1: 4}" in out
    assert "their cached_tokens: {0: 4}" in out
    assert "WARNING: a percentile of 4 requests" in out
    assert "Little" not in out
    # without the flag the first wave's 505 ms counts
    every = request_phase_ms_percentile.read(
        {"span": "request.prefill", "q": 50}, _evidence(40))
    assert every == pytest.approx(80.0)


def test_a_phase_that_began_before_the_window_gives_nothing_begun_inside(log):
    recs, _ = _requests_case()
    keep = [r for r in recs if r.name != "request.prefill"
            or r.attrs["request"] == 0]
    log(keep)
    assert request_phase_ms_percentile.read(PREFILL, _evidence(40)) is None


# -- admission and pages -----------------------------------------------------

def test_blocked_share_and_unfilled_pages_over_the_whole_window(log, capsys):
    specs = ([{"blocked": "pages", "pages": (380, 190)}] * 6
             + [{"blocked": "none", "pages": (300, 240)}] * 3
             + [{"blocked": "slot", "pages": (100, 100)}] * 1)
    recs, _ = _run_of_ticks(specs)
    # the first wave's ticks, before the window, say something else
    early, _ = _run_of_ticks([{"blocked": "gate", "pages": (10, 0)}] * 5,
                             t0=10 * S)
    log(early + recs)
    ev = _evidence(10)
    assert window_span_attr_share.read(BLOCKED, ev) == pytest.approx(60.0)
    # (6 x 50% + 3 x 20% + 0) / 10
    assert pages_unfilled.read({}, ev) == pytest.approx(36.0)
    out = capsys.readouterr().out
    assert "6 of 10 spans of the window had blocked == 'pages'" in out
    assert "{'pages': 6, 'none': 3, 'slot': 1}" in out
    assert "a mean of 328.00 reserved (range 100-380), 196.00 of them " \
           "filled" in out


def test_a_tick_with_nothing_reserved_is_left_out_of_the_unfilled_mean(log):
    recs, _ = _run_of_ticks([{"blocked": "none", "pages": (0, 0)}] * 2
                            + [{"blocked": "none", "pages": (8, 6)}] * 2)
    log(recs)
    assert pages_unfilled.read({}, _evidence(4)) == pytest.approx(25.0)


# -- prefill over the window -------------------------------------------------

def _stall_specs(behind):
    """Steps of 14 ms; the step behind a prefill call is `behind` ms
    longer. A tick's dispatch carries the call's rows, and the NEXT
    tick's sync waits for it."""
    return ([{}] * 4 + [{"prefill_rows": 1, "bucket": 128,
                         "prefill_call": True}]
            + [{"sync_ms": 9.0 + behind}] + [{}] * 4)


@pytest.mark.parametrize("behind,n,share", [
    # 9 steps between 10 syncs: 8 of 14 ms alone, one of 14 + 700
    (700.0, 10, 100.0 * 700 / (9 * 14 + 700)),
    (0.0, 10, 0.0),
    # a window cut to the ticks after the call holds no call
    (700.0, 4, 0.0)])
def test_prefill_stall_over_the_window_on_hand_made_steps(log, behind, n,
                                                         share):
    recs, _ = _run_of_ticks(_stall_specs(behind))
    log(_warm_up() + recs)
    got = prefill_stall_window_share.read({}, _evidence(n))
    assert got == pytest.approx(share)


def test_prefill_stall_over_the_window_reads_what_the_capture_misses(log):
    """A capture of four ticks that holds no call reads 0; the window's
    two calls are read whole, and the steps astride the profiler's start
    and stop are in neither the sum nor the time."""
    call = [{"prefill_rows": 1, "bucket": 512, "prefill_call": True},
            {"sync_ms": 9.0 + 2000.0}]
    specs = ([{}] * 3 + call + [{}] * 3
             + [{"in_capture": True, "hold_ms": 2500}]
             + [{"in_capture": True}] * 3 + [{"hold_ms": 1500}]
             + [{}] * 2 + call + [{}] * 3)
    recs, ticks = _run_of_ticks(specs)
    log(recs)
    n = len(ticks)
    ev = _evidence(n, trace=TraceSummary([], Events.build([]), (0, 8 * S)))
    assert prefill_stall_share.read({}, ev) == 0.0
    # 19 steps between 20 syncs; four touch a gap (into and out of the
    # first tick on its far side, twice); 13 alone, 2 behind
    got = prefill_stall_window_share.read({}, ev)
    assert got == pytest.approx(100.0 * 4000 / (15 * 14 + 4000))


# -- stalls ------------------------------------------------------------------

def _stall_case(captured_sync_ms=9.0):
    """30 ticks of 5 ms host work and 9 ms sync. One has 120 ms of host
    work, 40 of them a collection; one sleeps 130 ms on a sync behind no
    prefill call; one waits 700 ms behind a prefill call (no stall); the
    first tick after the profiler's start has 60 ms of host work (left
    out). `captured_sync_ms`: the sync of a tick inside the capture."""
    specs = [{} for _ in range(30)]
    specs[4] = {"host_ms": 120.0}
    specs[9] = {"sync_ms": 130.0}
    specs[14] = {"prefill_rows": 1, "bucket": 128, "prefill_call": True}
    specs[15] = {"sync_ms": 709.0}
    specs[20] = {"host_ms": 60.0, "in_capture": True, "hold_ms": 2000}
    for i in range(21, 25):
        specs[i] = {"in_capture": True}
    specs[22] = {"in_capture": True, "sync_ms": captured_sync_ms}
    specs[25] = {"hold_ms": 1500}
    recs, ticks = _run_of_ticks(specs, gap_ns=20_000)
    slow = ticks[4]
    # inside its dispatch, which is [24, 72) ms of the tick
    recs.append(Rec("py.gc", slow.start_ns + 26 * MS, slow.start_ns + 66 * MS,
                    parent=slow.id, attrs={"generation": 2,
                                           "collected": 12345}))
    return recs, ticks


def test_engine_stalls_count_long_host_work_and_long_plain_syncs(log, capsys):
    recs, ticks = _stall_case()
    log(_warm_up() + recs)
    got = engine_stall_ms.read({}, _evidence(30))
    # host work 120 against a median of 5: 115; a sync of 130 against 9: 121
    assert got == pytest.approx(115.0 + 121.0)
    out = capsys.readouterr().out
    assert "28 ticks of the window outside the profiler gaps" in out
    assert "2 ticks counted, 236.000 ms: 115.000 of host work, 121.000 of " \
           "waits" in out
    assert "1 py.gc records in the window, 40.000 ms" in out
    at_s = (ticks[4].start_ns + 26 * MS - ticks[0].start_ns) / 1e9
    assert (f"py.gc at {at_s:.3f} s: 40.000 ms, generation 2, 12345 objects "
            f"collected") in out
    lines = out.splitlines()
    (at,) = [i for i, ln in enumerate(lines)
             if "host work 120.000" in ln]
    assert lines[at].startswith(
        f"  at {(ticks[4].start_ns - ticks[0].start_ns) / 1e9:.3f} s: tick "
        f"129.000 ms")
    assert "no prefill call" in lines[at]
    assert "py.gc 40.000, " in lines[at + 1]
    assert "serve.decode_step 8.000" in lines[at + 1]
    # the wait behind the prefill call is the schedule's, not a stall
    assert "709.0" not in out and "held prefill call" not in out


def test_a_tick_in_the_capture_says_how_long_the_chip_was_idle(log, capsys):
    """A long plain sync inside the capture, with a device trace whose
    clock is the program's less 90 s: busy but for 100 ms of that tick."""
    recs, ticks = _stall_case(captured_sync_ms=130.0)
    log(recs)
    off = -90 * S
    cap = ticks[20:25]
    harness = [("perfbench.tick", t.start_ns + off - 3000,
                t.duration_ns + 6000) for t in cap]
    a, b = cap[0].start_ns + off, cap[-1].end_ns + off
    hole = ticks[22].start_ns + off + 20 * MS
    ops = [("fusion", a, hole - a), ("fusion", hole + 100 * MS,
                                     b - hole - 100 * MS)]
    trace = TraceSummary([DeviceTrace(0, Events.build(ops),
                                      Events.build([]))],
                         Events.build(harness), (a, b))
    got = engine_stall_ms.read({}, _evidence(30, trace=trace))
    assert got == pytest.approx(115.0 + 121.0 + 121.0)
    out = capsys.readouterr().out
    assert "chip 0 idle 100.000 ms of it" in out
    assert out.count("outside the capture") == 2


# -- an older program, and the older readers ---------------------------------

def _old_program(recs):
    """The log as the parent commit leaves it: no request, no py.gc, no
    attribute on `serve.schedule`."""
    out = []
    for r in recs:
        if r.name.startswith("request") or r.name == "py.gc":
            continue
        if r.name == "serve.schedule":
            r = dataclasses.replace(r, attrs={})
        out.append(r)
    return out


@pytest.mark.parametrize("reader,spec", [
    (request_phase_ms_percentile, QUEUED),
    (request_phase_ms_percentile, PREFILL),
    (window_span_attr_share, BLOCKED), (pages_unfilled, {})])
def test_on_the_parents_log_the_new_records_readers_find_nothing(
        log, reader, spec):
    recs, _ = _requests_case()
    log(_old_program(_warm_up() + recs))
    assert reader.read(spec, _evidence(40)) is None


@pytest.mark.parametrize("reader", [prefill_stall_window_share,
                                    engine_stall_ms])
def test_on_the_parents_log_the_two_readers_of_old_spans_read_the_same(
        log, reader):
    recs, _ = _stall_case()
    log(recs)
    new = reader.read({}, _evidence(30))
    log(_old_program(recs))
    assert reader.read({}, _evidence(30)) == new is not None


@pytest.mark.parametrize("reader,spec", [
    (request_phase_ms_percentile, QUEUED), (window_span_attr_share, BLOCKED),
    (pages_unfilled, {}), (prefill_stall_window_share, {}),
    (engine_stall_ms, {})])
def test_on_a_program_without_the_log_a_window_reader_finds_nothing(
        monkeypatch, reader, spec):
    from mpi_operator_tpu.telemetry import spans
    monkeypatch.delattr(spans, "records")
    assert reader.read(spec, _evidence(40)) is None


def _captured_case():
    """A capture of six ticks (a prefill call among them) on a device
    trace, set-up's JAX spans before it, `data.next` spans; and the same
    with this PR's records in the pile: requests on thread 0 across the
    ticks, a collection inside one, counts on every schedule."""
    S_ = 1_000_000_000
    setup = [Rec("serve.engine_init", 0, 20 * S_),
             Rec("jax.trace", 1 * S_, 4 * S_, attrs={"fun_name": "step"}),
             Rec("jax.compile", 4 * S_, 9 * S_, attrs={"fun_name": "jit(f)"})]
    setup[1].parent = setup[2].parent = setup[0].id
    specs = ([{"in_capture": True}] * 2
             + [{"in_capture": True, "prefill_rows": 1, "bucket": 128,
                 "prefill_call": True},
                {"in_capture": True, "sync_ms": 409.0}]
             + [{"in_capture": True}] * 2)
    old, ticks = _run_of_ticks(specs, gap_ns=20_000)
    feed = [Rec("data.next", ticks[i].start_ns - 9_000,
                ticks[i].start_ns - 1_000, in_capture=True,
                attrs={"depth": i % 2}) for i in range(6)]
    off = -95 * S_
    harness = [("perfbench.tick", t.start_ns + off - 4_000,
                t.duration_ns + 7_000) for t in ticks]
    a, b = ticks[0].start_ns + off, ticks[-1].end_ns + off
    ops = [("fusion", a, 10 * MS), ("fusion", a + 12 * MS, b - a - 30 * MS)]
    ev = Evidence(counters={"serve.ticks": 6.0}, trace=TraceSummary(
        [DeviceTrace(0, Events.build(ops), Events.build([]))],
        Events.build(harness), (a, b)))
    new = []
    for r in old:
        if r.name == "serve.schedule":
            r = dataclasses.replace(r, attrs={
                "blocked": "pages", "pages_reserved": 9, "pages_filled": 4})
        new.append(r)
    t0 = ticks[0].start_ns
    root = Rec("request", t0 - 50 * MS, ticks[4].end_ns, thread=0,
               in_capture=True, attrs={"request": 1})
    new += [root,
            Rec("request.queued", root.start_ns, ticks[2].start_ns + MS,
                parent=root.id, thread=0, in_capture=True,
                attrs={"request": 1, "blocked_on": "pages"}),
            Rec("request.prefill", ticks[2].start_ns + MS,
                ticks[3].end_ns - MS, parent=root.id, thread=0,
                in_capture=True, attrs={"request": 1, "calls": 1}),
            Rec("request.decode", ticks[3].end_ns - MS, ticks[4].end_ns,
                parent=root.id, thread=0, in_capture=True,
                attrs={"request": 1, "tokens": 2}),
            # a warm-up request, before the window: set-up's tables may
            # list it, its sums may not count it
            Rec("request", 21 * S_, 22 * S_, thread=0,
                attrs={"request": -1}),
            Rec("py.gc", ticks[1].start_ns + 1 * MS,
                ticks[1].start_ns + 3 * MS, parent=ticks[1].id,
                in_capture=True, attrs={"generation": 2, "collected": 7})]
    return setup + feed, old, new, ev


@pytest.mark.parametrize("reader,spec", [
    (span_ms_percentile, {"span": "serve.tick",
                          "minus_children": ["serve.sync"], "q": 50,
                          "table": "tick"}),
    (span_ms_percentile, {"span": "serve.decode_step", "q": 50}),
    (host_caused_idle, {}), (prefill_stall_share, {}),
    (setup_jax_seconds, {"spans": ["jax.trace", "jax.lower"],
                         "program_spans": True}),
    (setup_jax_seconds, {"spans": ["jax.compile", "jax.cache_load"]}),
    (span_attr_share, {"span": "data.next", "attr": "depth", "equals": 0})])
def test_the_older_span_readers_read_the_same_with_the_new_records(
        log, reader, spec):
    """The seven metrics that read the log before this PR (five readers):
    requests on thread 0, `py.gc` inside a tick and the counts on
    `serve.schedule` move none of them."""
    base, old, new, ev = _captured_case()
    log(base + old)
    before = reader.read(spec, ev)
    log(base + new)
    after = reader.read(spec, ev)
    assert before == after and before is not None
    if reader is prefill_stall_share:
        assert before > 0
    if reader is host_caused_idle:
        assert before > 0


# -- a traced run of the toy closed loop on the CPU --------------------------

NEW = {"queue_wait_ms_p50", "admit_to_first_token_ms_p50",
       "admission_blocked_on_pages_pct", "pages_reserved_unfilled_pct",
       "prefill_stall_window_share_pct", "engine_stall_ms_per_window"}


def test_a_traced_run_of_the_toy_closed_loop_reports_all_six(tmp_path,
                                                             capsys):
    from mpi_operator_tpu.telemetry import spans
    from perfbench.manifest import Manifest
    root = make_root(tmp_path)
    # the six are entries of the repository's own BENCHMARK.json, listed
    # for three serving cells and read from the program's spans
    for m in Manifest(root).data["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == ["tiny-closed"]
            assert (m["source"], m["moves"]) == ("program_span",
                                                 "serve_tokens_per_s")
    spans.clear()           # one process, one run: as the benchmark has it
    result = run.run_cell(root, "tiny-closed", 2**31 + 9, 1.0, True,
                          require_tpu=False)
    got = result["metrics"]
    assert result["correct"] is True and NEW <= set(got)
    assert 0 < got["queue_wait_ms_p50"]["value"] < \
        got["admit_to_first_token_ms_p50"]["value"]
    for name in ("admission_blocked_on_pages_pct",
                 "pages_reserved_unfilled_pct",
                 "prefill_stall_window_share_pct"):
        assert 0 <= got[name]["value"] <= 100
    assert 0 < got["pages_reserved_unfilled_pct"]["value"]
    assert got["engine_stall_ms_per_window"]["value"] >= 0
    out = capsys.readouterr().out
    ticks = [ln for ln in out.splitlines() if ln.startswith("window: the ")]
    assert len(ticks) == 6 and len(set(ticks)) == 1     # one window for all
    assert "Little's law:" in out and "their blocked_on:" in out
    assert "the queue itself (serve.schedule's waiting" in out
    assert "requests finished inside the window; the phases of" in out
    assert "their finish_reason: {'length':" in out and "their calls:" in out
    assert "request.decode: a token took" in out
    # every finished request of the run: a root and three phases that sum
    recs = spans.records()
    roots = [r for r in recs if r.name == "request"
             and r.attrs.get("finish_reason") == "length"]
    assert len(roots) > 20
    for root_ in roots:
        kids = [r for r in recs if r.parent == root_.id]
        assert sorted(k.name for k in kids) == [
            "request.decode", "request.prefill", "request.queued"]
        assert sum(k.duration_ns for k in kids) == root_.duration_ns
