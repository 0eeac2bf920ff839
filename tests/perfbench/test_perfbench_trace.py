"""The reduction from a trace to numbers: on intervals built by hand, and
on a small trace recorded on the chip."""
import os

import pytest

from perfbench import trace_reduce as tr
from perfbench.trace_reduce import DeviceTrace, Events, TraceSummary

MS = 1e6  # nanoseconds


def _summary(ops, spans, window=(0.0, 100 * MS), modules=()):
    return TraceSummary(
        [DeviceTrace(0, Events.build(ops), Events.build(list(modules)))],
        Events.build(spans), window)


@pytest.mark.parametrize("raw,clean", [
    ("%fusion.123", "fusion"), ("fusion.12.remat", "fusion.12.remat"),
    ("copy-done.4", "copy-done"), ("attn._decode_attend", "attn._decode_attend"),
    ("all-reduce.1.2", "all-reduce")])
def test_op_names_drop_compiler_suffixes(raw, clean):
    assert tr.op_name(raw) == clean


def test_module_names_drop_the_run_id():
    assert tr.module_name("jit_step_paged(1234567)") == "jit_step_paged"


@pytest.mark.parametrize("ops,busy_ms", [
    ([("a", 0, 10 * MS), ("b", 20 * MS, 10 * MS)], 20.0),
    ([("a", 0, 10 * MS), ("b", 5 * MS, 10 * MS)], 15.0),       # overlap
    ([("while", 0, 50 * MS), ("body", 10 * MS, 5 * MS)], 50.0),  # nested
    ([], 0.0)])
def test_busy_is_the_union_of_operation_intervals(ops, busy_ms):
    assert _summary(ops, []).busy_s() == pytest.approx(busy_ms / 1e3)


def test_operation_sums_are_by_name_largest_first():
    s = _summary([("fusion", 0, 10 * MS), ("copy", 10 * MS, 30 * MS),
                  ("fusion", 50 * MS, 15 * MS)], [])
    assert s.device_ops() == [["copy", pytest.approx(0.030)],
                              ["fusion", pytest.approx(0.025)]]


def test_idle_gaps_go_to_the_span_that_covers_most_of_each():
    ops = [("a", 10 * MS, 10 * MS), ("b", 60 * MS, 10 * MS)]
    spans = [("perfbench.tick", 0, 45 * MS),
             ("perfbench.sync", 45 * MS, 30 * MS)]
    gaps = dict(_summary(ops, spans).idle_gaps())
    # [0,10) tick; [20,60): tick covers 25 ms, sync 15 ms -> tick;
    # [70,100): sync covers 5 ms, nothing covers the rest -> sync
    assert gaps == {"perfbench.tick": pytest.approx(0.050),
                    "perfbench.sync": pytest.approx(0.030)}


def test_idle_gap_with_no_span_is_named_so():
    gaps = dict(_summary([("a", 0, 10 * MS)], []).idle_gaps())
    assert gaps == {"(no span)": pytest.approx(0.090)}


def test_events_are_cut_to_the_window():
    e = Events.build([("a", -5 * MS, 10 * MS), ("b", 95 * MS, 10 * MS),
                      ("c", 200 * MS, 1 * MS)]).clip(0.0, 100 * MS)
    assert e.names == ["a", "b"]
    assert list(e.dur) == [5 * MS, 5 * MS]


def test_readers_over_a_trace():
    from perfbench.harness import Evidence
    from perfbench.readers import device_module_percentile, kernel_roofline
    modules = [("jit_step", 0, 9 * MS), ("jit_step", 10 * MS, 20 * MS),
               ("jit_step", 30 * MS, 20 * MS), ("jit_step", 95 * MS, 20 * MS)]
    ops = [("attn", 12 * MS, 4 * MS), ("attn", 32 * MS, 4 * MS),
           ("attn", 96 * MS, 4 * MS), ("all-reduce", 25 * MS, 5 * MS)]
    ev = Evidence(trace=_summary(ops, [], modules=modules),
                  shapes={"rows_per_chip": 1, "heads": 1, "seq_len": 1024,
                          "head_dim": 64, "layers": 1},
                  peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12})
    # whole executions only: the first starts at the window's edge and
    # the last is cut by its end
    assert device_module_percentile.read(
        {"module_pattern": "step", "q": 50}, ev) == pytest.approx(20.0)
    spec = {"ops_module": "flash_attention", "op_pattern": "^attn$",
            "module_pattern": "step",
            "args": {"rows": "shape:rows_per_chip", "heads": "shape:heads",
                     "seq_len": "shape:seq_len",
                     "head_dim": "shape:head_dim", "layers": "shape:layers"}}
    flops = 7 * 2 * 1024 * 1024 * 64 / 2
    assert kernel_roofline.read(spec, ev) == pytest.approx(
        100 * (flops / 1e12) / 0.004)
    assert kernel_roofline.read(spec, Evidence()) is None


# -- a small trace recorded on a TPU v5e (perfbench/tools/record_fixture.py:
# five executions of one small program under perfbench.step spans, a 2 ms
# host sleep under perfbench.idle after each) -------------------------------

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "small_tpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_trace(FIXTURE)


def test_recorded_trace_has_one_chip_and_the_harness_window(recorded):
    assert [d.index for d in recorded.devices] == [0]
    assert recorded.window_s == pytest.approx(0.0165, rel=0.05)
    assert set(recorded.spans.names) == {"perfbench.step", "perfbench.idle"}
    assert tr.WINDOW_SPAN not in recorded.spans.names


def test_recorded_trace_busy_idle_and_operation_sums(recorded):
    busy = recorded.busy_s()
    assert 2e-5 < busy < 2e-4           # a few 12 us programs
    ops = recorded.device_ops()
    assert ops[0][0] == "fusion"        # named without % and .<n>
    assert sum(v for _, v in ops) == pytest.approx(busy, rel=0.01)
    gaps = recorded.idle_gaps()
    # the chip waits while the host sleeps: the gaps go to that span, and
    # busy and idle make up the window
    assert gaps[0][0] == "perfbench.idle"
    assert busy + sum(v for _, v in gaps) == pytest.approx(
        recorded.window_s, rel=1e-6)


def test_recorded_trace_program_executions(recorded):
    from perfbench.harness import Evidence
    from perfbench.readers import device_module_percentile
    names = set(recorded.devices[0].modules.names)
    assert names == {"jit_fixture_step"}
    ms = device_module_percentile.read(
        {"module_pattern": "fixture_step", "q": 50},
        Evidence(trace=recorded))
    assert ms == pytest.approx(0.0118, rel=0.1)
    assert device_module_percentile.read(
        {"module_pattern": "no_such_program", "q": 50},
        Evidence(trace=recorded)) is None
