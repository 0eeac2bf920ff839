"""Decode time lost to prefill calls, as a share of the WHOLE measured
window: `prefill_stall_share`'s reckoning (a step is sync end to sync end;
one behind a prefill call, by its dispatch's `prefill_rows`, is longer than
the median step alone by the call's time, in which no row decodes) over
every sync of the window's ticks (`_window.py`), not over the 8 s the
profiler captured. What it buys is the right LEVEL where calls are few: a
capture holds one call of Phi's thirteen and reads 5, the window 16.5.

It is a share over the ticks a TRACED window keeps, not a constant of the
cell: the profiler's stop holds the host for 10-18 s of the 51, so a traced
window keeps 285 of gpt2-xl's 380 ticks and 16 of Falcon-H1's 28 calls, and
how long the hold lasts decides which (a machine's first run read 88.69
where the next read 88.05: measured, PR 37). Two seeds differ in it no less
than they do in the capture's share.

Left out: the steps astride the profiler's start and stop, from the sum and
from the time it is a share of. Where every step stood behind a call, the
shortest stands for a step alone. It logs the calls by bucket and the step
alone; `prefill_stall_share` logs the capture's own beside it.
"""
from perfbench.harness import log, median
from perfbench.readers import _spans, _window


def read(spec, evidence):
    window = _window.find(evidence)
    if window is None:
        return None
    alone, behind, left_out = _window.steps(window)
    stalled = [ns for v in behind.values() for ns in v]
    counted = sum(alone) + sum(stalled)
    if counted <= 0:
        return None
    base = median(alone) if alone else min(stalled)
    lost = sum(ns - base for ns in stalled)
    calls = "; ".join(
        f"bucket {b}: {len(v)} of median {_spans.ms(median(v)):.1f} ms"
        for b, v in sorted(behind.items())) or "none"
    log(window.describe())
    log(f"prefill stall over the window: {len(alone) + len(stalled)} steps "
        f"between syncs ({_spans.ms(left_out):.1f} ms astride a profiler gap "
        f"left out), {len(stalled)} behind a prefill call ({calls}); a step "
        f"alone {_spans.ms(base):.3f} ms; lost {_spans.ms(lost):.1f} ms of "
        f"{_spans.ms(counted):.1f}")
    return 100.0 * lost / counted
