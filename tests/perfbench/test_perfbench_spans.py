"""The readers of the program's own span log: the mapping of its clock onto
the trace's, checked; the reductions on hand-made logs with known answers;
a program that keeps no log (an older commit), for which every reader finds
nothing and none raises; and traced CPU runs of toy cells that report every
new metric a CPU can."""
import dataclasses
import itertools

import numpy as np
import pytest

from _perfbench_tiny import make_root
from perfbench import run
from perfbench.harness import Evidence
from perfbench.readers import (_spans, host_caused_idle,
                               prefill_stall_share, setup_jax_seconds,
                               span_attr_share, span_ms_percentile)
from perfbench.trace_reduce import DeviceTrace, Events, TraceSummary

MS = 1_000_000  # nanoseconds
_ids = itertools.count(1)


@dataclasses.dataclass
class Rec:
    """A record as `mpi_operator_tpu.telemetry.spans` keeps them."""
    name: str
    start_ns: int
    end_ns: int
    parent: int = None
    caused_by: int = None
    attrs: dict = dataclasses.field(default_factory=dict)
    in_capture: bool = True
    thread: int = 1
    id: int = dataclasses.field(default_factory=lambda: next(_ids))

    @property
    def duration_ns(self):
        return self.end_ns - self.start_ns


def _evidence(spans=(), ops=(), modules=(), window=(0, 100 * MS),
              devices=True):
    devs = [DeviceTrace(0, Events.build(list(ops)),
                        Events.build(list(modules)))] if devices else []
    return Evidence(trace=TraceSummary(devs, Events.build(list(spans)),
                                       window))


@pytest.fixture
def log(monkeypatch):
    """Hand the readers a made-up log."""
    def give(records):
        monkeypatch.setattr(_spans, "program_log", lambda: list(records))
    return give


# -- the clock ---------------------------------------------------------------

OFFSET = -7_000_000_123      # program + OFFSET = trace


def _ticks(n=6, tick=40 * MS, gap=2 * MS, lead=5_000, lag=3_000, idle_at=2):
    """`n` program ticks on the program's clock, each inside a harness tick
    on the trace's clock (opened `lead` ns earlier, closed `lag` ns later),
    with one harness tick that found nothing to do in between."""
    program, harness = [], []
    t = 9_000_000_000
    for i in range(n):
        if i == idle_at:
            harness.append(("perfbench.tick", t + OFFSET, 4_000))
            t += 10_000
        program.append(Rec("serve.tick", t, t + tick))
        harness.append(("perfbench.tick", t - lead + OFFSET,
                        tick + lead + lag))
        t += tick + gap
    return program, harness


def test_the_offset_is_recovered_from_the_pairs_of_ticks():
    program, harness = _ticks()
    ev = _evidence(harness, window=(harness[0][1], harness[-1][1] + 50 * MS))
    offset, residual = _spans.tick_clock(ev.trace, program)
    # the offsets that keep every tick inside its pair: [-lag.., +lead]
    assert OFFSET - 5_000 <= offset <= OFFSET + 3_000
    assert residual == 0.0


@pytest.mark.parametrize("shift_ns", [0, 1 * MS, -250 * MS])
def test_a_log_shifted_as_a_whole_is_another_offset_not_an_error(shift_ns):
    program, harness = _ticks()
    for r in program:
        r.start_ns += shift_ns
        r.end_ns += shift_ns
    offset, residual = _spans.tick_clock(_evidence(harness).trace, program)
    assert abs(offset - (OFFSET - shift_ns)) <= 5_000 and residual == 0.0


@pytest.mark.parametrize("which", ["later_half", "one_tick", "stretched"])
def test_a_log_shifted_by_1_ms_against_the_trace_raises(which):
    """A clock that stepped by 1 ms inside the capture, one tick recorded
    1 ms late, or ticks that outlast the harness's by 1 ms: no one offset
    fits, and the worst residual is in the message."""
    program, harness = _ticks()
    if which == "later_half":
        for r in program[3:]:
            r.start_ns += MS
            r.end_ns += MS
    elif which == "one_tick":
        program[4].start_ns += MS
        program[4].end_ns += MS
    else:
        for r in program:
            r.end_ns += MS
    with pytest.raises(_spans.ClockMismatch, match=r"worst residual "
                       r"(4\d\d|5\d\d)\.\d us"):
        _spans.tick_clock(_evidence(harness).trace, program)


def test_more_program_ticks_than_harness_ticks_raises():
    program, harness = _ticks()
    with pytest.raises(_spans.ClockMismatch, match="holds 3"):
        _spans.tick_clock(_evidence(harness[:3]).trace, program)


def test_no_program_tick_in_the_capture_gives_no_clock():
    _, harness = _ticks()
    outside = [Rec("serve.tick", 0, MS, in_capture=False)]
    assert _spans.tick_clock(_evidence(harness).trace, outside) is None


# -- the reductions ----------------------------------------------------------

def _steps(lengths_ms, prefill_rows, in_capture=None):
    """Dispatches and the syncs that name them; sync k ends `lengths_ms[k]`
    after sync k-1 (the first at 0)."""
    recs, t = [], 0
    in_capture = in_capture or [True] * len(lengths_ms)
    for ms, rows, cap in zip(lengths_ms, prefill_rows, in_capture):
        d = Rec("serve.decode_step", t, t + MS,
                attrs={"prefill_rows": rows,
                       "prefill_bucket": 512 if rows else 0})
        t += int(ms * MS)
        recs += [d, Rec("serve.sync", t - 2 * MS, t, caused_by=d.id,
                        in_capture=cap)]
    return recs


@pytest.mark.parametrize("lengths,rows,share", [
    # 8 s sub-window; steps alone 400/410/420 ms (median 410); one step
    # behind a 512-bucket call takes 2570: 2160 lost of 8000 = 27%
    ([400, 400, 2570, 410, 420], [0, 0, 1, 0, 0], 27.0),
    # no call in the sub-window
    ([400, 400, 410, 420, 430], [0, 0, 0, 0, 0], 0.0),
    # two calls: (910 - 410) + (2570 - 410) = 2660 of 8000
    ([400, 910, 400, 2570, 410, 420], [0, 2, 0, 1, 0, 0], 33.25),
    # every step behind a call: the shortest stands for a step alone
    ([400, 900, 1000], [1, 1, 1], 1.25),
    # a single sync: no step to measure
    ([400], [0], 0.0)])
def test_prefill_stall_share_on_hand_made_steps(log, lengths, rows, share):
    log(_steps(lengths, rows))
    ev = _evidence(window=(0, 8000 * MS))
    got = prefill_stall_share.read({}, ev)
    assert got == pytest.approx(share)


def test_prefill_stall_leaves_out_steps_that_began_before_the_capture(log):
    # the step that spans the profiler's start is seconds long and is no
    # prefill stall; only steps between two captured syncs count
    log(_steps([400, 3000, 410, 2570, 420], [0, 1, 0, 1, 0],
               in_capture=[False, True, True, True, True]))
    ev = _evidence(window=(0, 8000 * MS))
    got = prefill_stall_share.read({}, ev)
    assert got == pytest.approx(100.0 * (2570 - 415) / 8000)


def _idle_case():
    """Ops busy [0,30) [40,70) [80,100) ms on the trace's clock: idle
    [30,40) and [70,80). Two ticks; the first's sync covers [25,38), the
    second's [60,72): idle outside any sync = [38,40) + [72,80) = 10 ms."""
    ops = [("fusion", 0, 30 * MS), ("attn", 40 * MS, 30 * MS),
           ("copy", 80 * MS, 20 * MS)]
    base = 5_000_000_000              # the program's clock, far away
    harness, recs = [], []
    for t0, t1, s0, s1 in ((1, 49, 25, 38), (51, 99, 60, 72)):
        harness.append(("perfbench.tick", t0 * MS - 4_000,
                        (t1 - t0) * MS + 8_000))
        tick = Rec("serve.tick", base + t0 * MS, base + t1 * MS)
        recs += [tick,
                 Rec("serve.decode_step", base + (t0 + 1) * MS,
                     base + (t0 + 9) * MS, parent=tick.id),
                 Rec("serve.sync", base + s0 * MS, base + s1 * MS,
                     parent=tick.id),
                 Rec("serve.retire", base + s1 * MS, base + (s1 + 3) * MS,
                     parent=tick.id)]
    return ops, harness, recs


def test_host_caused_idle_is_the_idle_time_outside_every_sync(log, capsys):
    ops, harness, recs = _idle_case()
    log(recs)
    ev = _evidence(harness, ops)
    got = host_caused_idle.read({}, ev)
    assert got == pytest.approx(10.0, abs=0.01)      # 10 ms of 100 ms
    out = capsys.readouterr().out
    assert "worst residual 0.000 us" in out
    # [30,40): 8 in sync, 2 in retire (38-41); [70,80): 2 in sync, 3 in
    # retire (72-75), 5 in the tick itself
    rows = {ln.split()[-1]: float(ln.split()[0]) for ln in out.splitlines()
            if ln.startswith("  ") and " ms " in ln}
    assert rows["serve.sync"] == pytest.approx(10.0, abs=0.01)
    assert rows["serve.retire"] == pytest.approx(5.0, abs=0.01)
    assert rows["serve.tick"] == pytest.approx(5.0, abs=0.01)


def test_host_caused_idle_is_0_when_the_device_idles_only_under_a_sync(log):
    ops, harness, recs = _idle_case()
    for r in recs:
        if r.name == "serve.sync":          # the syncs cover both gaps
            r.end_ns += 8 * MS
        if r.name == "serve.retire":
            r.start_ns += 8 * MS
            r.end_ns += 8 * MS
    log(recs)
    assert host_caused_idle.read({}, _evidence(harness, ops)) == \
        pytest.approx(0.0, abs=1e-9)


def test_host_caused_idle_raises_on_a_clock_that_does_not_fit(log):
    ops, harness, recs = _idle_case()
    for r in recs[4:]:
        r.start_ns += MS
        r.end_ns += MS
    log(recs)
    with pytest.raises(_spans.ClockMismatch):
        host_caused_idle.read({}, _evidence(harness, ops))


def test_idle_time_as_a_function_of_time():
    idle = _spans.IdleTime([(0, 30), (40, 70), (80, 100)], (0, 100))
    assert idle.total == 20
    assert [idle.before(t) for t in (0, 30, 35, 40, 75, 100)] == [
        0, 0, 5, 10, 15, 20]
    assert idle.inside(38, 72) == 4
    # the window's own edges are gaps too
    assert _spans.IdleTime([(10, 20)], (0, 50)).total == 40
    assert _spans.IdleTime([], (0, 50)).inside(10, 20) == 10


def _setup_case():
    """Set-up: a 10 s trace that holds a nested 2 s trace and a 1 s eager
    compile; a 3 s lowering; a 20 s compile; a 4 s cache load. Then the
    first captured tick, and a reference compiled after it."""
    S = 1_000_000_000
    init = Rec("train.init_state", 0, 40 * S, in_capture=False)
    shard = Rec("train.shard_init", 0, 39 * S, parent=init.id,
                in_capture=False)
    j = lambda name, a, b, fun, parent: Rec(  # noqa: E731
        name, int(a * S), int(b * S), parent=parent,
        attrs={"fun_name": fun}, in_capture=False)
    recs = [init, shard,
            j("jax.trace", 0, 10, "unboxed_init", shard.id),
            j("jax.trace", 2, 4, "dot_general", shard.id),
            j("jax.compile", 5, 6, "jit(zeros)", shard.id),
            j("jax.lower", 10, 13, "jit(unboxed_init)", shard.id),
            j("jax.compile", 13, 33, "jit(unboxed_init)", shard.id),
            j("jax.cache_load", 41, 45, "jit(<lambda>)", None),
            Rec("serve.tick", 50 * S, 51 * S, in_capture=False),  # warm-up
            Rec("data.next", 60 * S, 60 * S + 1000),
            j("jax.compile", 70, 90, "jit(reference)", None)]
    return recs


@pytest.mark.parametrize("names,seconds,first_row", [
    # the outer trace less the nested compile (the nested trace counts
    # once), plus the lowering: 9 + 3
    (["jax.trace", "jax.lower"], 12.0,
     "train.init_state>train.shard_init: unboxed_init [jax.trace]"),
    # 1 + 20 + 4; the reference's 20 s came after the window opened
    (["jax.compile", "jax.cache_load"], 25.0,
     "train.init_state>train.shard_init: jit(unboxed_init) [jax.compile]")])
def test_set_up_sums_count_each_instant_once_and_stop_at_the_window(
        log, capsys, names, seconds, first_row):
    log(_setup_case())
    got = setup_jax_seconds.read({"spans": names}, _evidence())
    assert got == pytest.approx(seconds)
    table = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("  ")]
    assert table[0].endswith(first_row)
    assert sum(float(ln.split()[0]) for ln in table) == pytest.approx(
        seconds)


def test_the_programs_set_up_spans_are_listed_with_jaxs_share(log, capsys):
    log(_setup_case())
    setup_jax_seconds.read({"spans": ["jax.trace"], "program_spans": True},
                           _evidence())
    out = capsys.readouterr().out.splitlines()
    at = out.index("the program's set-up spans:")
    # 33 s of shard_init's 39 are JAX's: 7 + 2 trace, 1 + 20 compile, 3
    # lowering; the rest it does not report. The warm-up tick is no
    # set-up span.
    assert [ln.split(None, 2) for ln in out[at + 1:at + 3]] == [
        ["40.000000", "s", "train.init_state: of which 33.000 s in jax.*"],
        ["39.000000", "s", "train.init_state>train.shard_init: of which "
                           "33.000 s in jax.*"]]
    assert not out[at + 3].startswith("  ")


def test_a_program_built_under_no_program_span_is_named_so(log, capsys):
    log(_setup_case())
    setup_jax_seconds.read({"spans": ["jax.cache_load"]}, _evidence())
    # a row says whether the program was compiled or found in the cache
    assert ("(no program span): jit(<lambda>) [jax.cache_load]"
            in capsys.readouterr().out)


def test_a_full_log_raises_instead_of_summing_what_is_left(monkeypatch):
    """Set-up's records are the oldest: a log at its bound has lost them,
    and every reader goes through `program_log`."""
    from mpi_operator_tpu.telemetry import spans
    monkeypatch.setattr(spans, "LOG_BOUND", 3)
    monkeypatch.setattr(spans, "records", lambda: [1, 2])
    assert _spans.program_log() == [1, 2]
    monkeypatch.setattr(spans, "records", lambda: [1, 2, 3])
    with pytest.raises(_spans.LogWrapped, match="3 records"):
        _spans.program_log()
    with pytest.raises(_spans.LogWrapped):
        setup_jax_seconds.read({"spans": ["jax.trace"]}, _evidence())


def test_the_set_up_reader_says_how_full_the_log_is(log, capsys):
    recs = _setup_case()
    log(recs)
    setup_jax_seconds.read({"spans": ["jax.trace"], "program_spans": True},
                           _evidence())
    assert (f"span log: {len(recs)} records, 6 of them JAX's"
            in capsys.readouterr().out)


@pytest.mark.parametrize("names", [["jax.trace", "jax.lower"],
                                   ["jax.compile", "jax.cache_load"]])
def test_set_up_sums_are_0_where_nothing_was_built(log, names):
    log([Rec("serve.tick", 5 * MS, 9 * MS)])
    assert setup_jax_seconds.read({"spans": names}, _evidence()) == 0.0


def test_self_times_charge_each_instant_to_the_innermost_span():
    a = Rec("a", 0, 100)
    b = Rec("b", 10, 60)
    c = Rec("c", 20, 30)
    d = Rec("d", 70, 80)
    e = Rec("e", 0, 50, thread=2)          # another thread: its own nest
    own, root = _spans.self_times([c, a, e, d, b])
    assert own == {a.id: 40, b.id: 40, c.id: 10, d.id: 10, e.id: 50}
    assert root == {a.id: a.id, b.id: a.id, c.id: a.id, d.id: a.id,
                    e.id: e.id}


def test_tick_work_is_the_tick_less_its_syncs_and_dispatch_is_a_span(log):
    recs = []
    for i, (tick_ms, sync_ms, disp_ms) in enumerate(
            [(430, 427, 1.0), (432, 427, 2.0), (440, 430, 9.0),
             (431, 427, 2.5), (2600, 2590, 3.0)]):
        t0 = i * 3000 * MS
        tick = Rec("serve.tick", t0, t0 + int(tick_ms * MS))
        recs += [tick,
                 Rec("serve.decode_step", t0, t0 + int(disp_ms * MS),
                     parent=tick.id),
                 Rec("serve.sync", t0 + 10 * MS,
                     t0 + 10 * MS + int(sync_ms * MS), parent=tick.id)]
    # a tick before the capture does not count
    recs.append(Rec("serve.tick", -50 * MS, -1 * MS, in_capture=False))
    log(recs)
    work = span_ms_percentile.read(
        {"span": "serve.tick", "minus_children": ["serve.sync"], "q": 50,
         "table": "tick"}, _evidence())
    assert work == pytest.approx(5.0)           # of 3, 5, 10, 4, 10
    dispatch = span_ms_percentile.read(
        {"span": "serve.decode_step", "q": 50}, _evidence())
    assert dispatch == pytest.approx(2.5)


@pytest.mark.parametrize("depths,share", [([2, 2, 1, 2], 0.0),
                                          ([0, 2, 0, 1], 50.0)])
def test_input_starved_share_counts_the_steps_that_found_depth_0(
        log, depths, share):
    log([Rec("data.next", i * MS, i * MS + 1000, attrs={"depth": d})
         for i, d in enumerate(depths)]
        + [Rec("data.next", -MS, -MS + 9, attrs={"depth": 0},
               in_capture=False)])
    spec = {"span": "data.next", "attr": "depth", "equals": 0}
    assert span_attr_share.read(spec, _evidence()) == pytest.approx(share)


# -- a program that keeps no log ---------------------------------------------

@pytest.mark.parametrize("reader,spec", [
    (span_ms_percentile, {"span": "serve.tick", "q": 50, "table": "tick"}),
    (prefill_stall_share, {}), (host_caused_idle, {}),
    (span_attr_share, {"span": "data.next", "attr": "depth", "equals": 0}),
    (setup_jax_seconds, {"spans": ["jax.trace"]})])
def test_on_a_program_without_the_log_a_reader_finds_nothing(
        monkeypatch, reader, spec):
    """The parent commit's `telemetry/spans.py` has `span` and no
    `records`: the driver runs these readers over it too."""
    from mpi_operator_tpu.telemetry import spans
    monkeypatch.delattr(spans, "records")
    _, harness = _ticks()
    assert reader.read(spec, _evidence(harness)) is None


@pytest.mark.parametrize("reader,spec", [
    (span_ms_percentile, {"span": "serve.tick", "q": 50}),
    (span_attr_share, {"span": "data.next", "attr": "depth", "equals": 0}),
    (setup_jax_seconds, {"spans": ["jax.trace"]}),
    (host_caused_idle, {})])
def test_a_log_with_nothing_of_the_capture_gives_nothing(log, reader, spec):
    log([Rec("serve.engine_init", 0, MS, in_capture=False)])
    assert reader.read(spec, _evidence()) is None


# -- traced runs of toy cells on the CPU -------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("perfbench_spans"))


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-closed", {"engine_host_work_ms_p50", "engine_dispatch_ms_p50",
                     "prefill_stall_share_pct", "setup_trace_lower_s",
                     "setup_compile_or_load_s"}),
    ("tiny-train", {"train_input_starved_pct", "setup_trace_lower_s",
                    "setup_compile_or_load_s"})])
def test_a_traced_run_of_a_toy_cell_reports_the_new_metrics(
        root, capsys, cell, metrics):
    """A toy cell built in a temporary root beside a link to the
    repository's own perfbench/ (as `_perfbench_tiny.py` builds it), run
    traced on the CPU through run.py: every new `program_span` metric of
    the cell is in the line, from the spans the program itself recorded;
    the clock check ran and passed; the one that reads the device trace
    found no device here and is left out."""
    from mpi_operator_tpu.telemetry import spans
    spans.clear()           # one process, one run: as the benchmark has it
    result = run.run_cell(root, cell, 2**31 + 5, 0.8, True,
                          require_tpu=False)
    assert result["correct"] is True
    got = result["metrics"]
    assert metrics <= set(got)
    assert "host_caused_idle_pct" not in got
    for name in metrics:
        assert np.isfinite(got[name]["value"]) and got[name]["value"] >= 0
    assert got["setup_trace_lower_s"]["value"] > 0.05
    assert got["setup_compile_or_load_s"]["value"] > 0.05
    out = capsys.readouterr().out
    if cell == "tiny-closed":
        assert 0 < got["engine_dispatch_ms_p50"]["value"] \
            <= got["engine_host_work_ms_p50"]["value"] \
            < got["tiny_tick_ms_p90"]["value"] + 1.0
        assert got["prefill_stall_share_pct"]["value"] <= 100.0
        assert "worst residual" in out and "self time by span" in out
        # the set-up table names the engine's own programs under its spans
        assert "serve.engine_init>serve.init_cache: jit(init_cache)" in out
        assert "serve.tick>serve.decode_step: jit(step_paged)" in out
    else:
        assert got["train_input_starved_pct"]["value"] <= 100.0
        assert "train.init_state>train.shard_init: jit(unboxed_init)" in out
        # the step compiles on the harness's first call of train_step
        assert "(no program span): jit(_step_fn)" in out
