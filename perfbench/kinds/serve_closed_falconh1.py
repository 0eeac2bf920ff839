"""Closed-loop serving of Falcon-H1: the loop of `serve_closed_phi4flash.py`,
line for line (that file cannot take another engine without an edit, and a
benchmark file that is there is not this PR's to edit), around
`_serve_falconh1.Engine`, the same generator of a loop seen in its steady
state (`deep_closed_loop`: the first wave's prompts are instruction plus
the answer so far, built by prefill inside set-up) and the Falcon-H1
reference.

Under `--trace 1` it keeps, as that loop does, the first chip's operations
by their full instruction names beside the program's map from instruction
to named scope, for the readers that split a decode step's device time by
scope (`readers/scope_device_share.py`). What it adds: the counters the
two rooflines take are the means over the captured ticks
(`_serve_falconh1.Engine.traced_counters`), not the window's; and Python's
cyclic collector stands still from the first wave's end to the window's
(`_serve_falconh1.collector_at_rest`: its pauses land on ticks with 8 ms
of slack, and two of them are half the bound on `serve_tokens_per_s`).
"""
from __future__ import annotations

import time

from perfbench import harness
from perfbench.harness import log
from perfbench.kinds import _serve, _serve_falconh1
from perfbench.readers import scope_device_share


def run(ctx) -> harness.Outcome:
    t = ctx.traffic
    compiles = harness.CompileCounter()
    phases = harness.Phases()
    phases.mark("reach the chip")
    eng = _serve_falconh1.Engine(ctx)
    engine = eng.engine
    phases.mark("weights and engine")
    first, backlog = _serve_falconh1.deep_closed_loop(
        t, ctx.seed, eng.dims.vocab_real)
    # the backlog's prompts take every bucket the window can see; the first
    # wave's own are prefilled below, inside set-up
    counts = eng.warm([len(r.prompt) for r in backlog], eng.dims.vocab_real)
    phases.mark(f"compile or load of {counts}")

    clients = _serve.Clients(eng, first, backlog, ctx.seed)
    clients.first_wave(float(t["first_wave_limit_s"]))
    with _serve_falconh1.collector_at_rest():
        phases.mark("first wave")
        t_open = time.perf_counter()
        setup_s = t_open - harness.PROCESS_START
        log(phases.line(setup_s) + " (reference: after the window, not "
            f"counted); {engine.slots.occupied} of {engine.config.slots} "
            f"slots hold a request, {len(engine.scheduler.queue)} wait for "
            f"pages; a page holds {engine.page_bytes()} bytes, a slot "
            f"{engine.slot_state_bytes()} beside its pages")

        tracer = harness.SubWindowTracer(ctx.trace, t["trace_start_s"],
                                         t["trace_seconds"])
        with compiles.window():
            while time.perf_counter() - t_open < ctx.seconds:
                tracer.poll(time.perf_counter() - t_open)
                eng.tick()
                clients.answer_completions()
            t_close = time.perf_counter()
            tracer.stop()
    if compiles.count:
        raise RuntimeError(f"{compiles.count} program(s) compiled inside "
                           f"the measured window")
    window = t_close - t_open
    tokens, results, failed, served = clients.close(t_open, t_close)
    peak = harness.memory_peak_bytes(ctx.devices)
    counters = eng.window_counters(t_open, t_close)
    counters.update(eng.traced_counters(tracer, counters))
    log(f"window {window:.3f} s: {tokens} tokens fetched in "
        f"{counters.get('serve.ticks', 0):.0f} ticks, {served}; peak {peak} "
        f"bytes")

    shapes = eng.shapes()
    if tracer.dir is not None:
        # before the trace is reduced and removed
        shapes["device_ops_raw"] = scope_device_share.raw_device_ops(
            tracer.dir)
        shapes["op_scopes"] = eng.op_scopes()
    ev = harness.Evidence(
        samples=eng.samples(t_open, t_close, tracer),
        counters=counters,
        shapes=shapes, trace=tracer.summary(ctx.keep_trace),
        peaks=harness.peaks_of(ctx.devices))
    eng.free()
    checks = _serve_falconh1.check_served(ctx, eng, results, clients.prompts)
    return harness.Outcome(
        end_to_end={"serve_tokens_per_s": tokens / window,
                    "setup_s": setup_s},
        evidence=ev, correct=harness.judge(checks) and failed == 0,
        attempted=clients.sent, failed=failed, memory_peak_bytes=peak)
