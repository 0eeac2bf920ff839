"""Closed-loop serving of LongCat-Flash: the loop of `serve_closed.py`,
line for line, around `_serve_longcat.Engine` and the LongCat reference.
(`serve_closed.run` names its engine wrapper and its comparison itself, so
another model's closed loop is another kind file, not a parameter.)

What it adds to that loop: the expert layer's routing counters of the
window (`_serve_longcat.Engine.window_counters`), and under `--trace 1` the
first chip's operations by their full instruction names beside the
program's map from instruction to named scope, for the readers that split
a decode step's device time by scope (`readers/scope_device_share.py`).
"""
from __future__ import annotations

import time

from perfbench import generators, harness
from perfbench.harness import log
from perfbench.kinds import _serve, _serve_longcat
from perfbench.readers import scope_device_share


def run(ctx) -> harness.Outcome:
    t = ctx.traffic
    compiles = harness.CompileCounter()
    phases = harness.Phases()
    phases.mark("reach the chip")
    eng = _serve_longcat.Engine(ctx)
    engine = eng.engine
    phases.mark("weights and engine")
    first, backlog = generators.closed_loop(t, ctx.seed, eng.dims.vocab_real)
    counts = eng.warm([len(r.prompt) for r in first + backlog],
                      eng.dims.vocab_real)
    phases.mark(f"compile or load of {counts}")

    clients = _serve.Clients(eng, first, backlog, ctx.seed)
    clients.first_wave(float(t["first_wave_limit_s"]))
    phases.mark("first wave")
    t_open = time.perf_counter()
    setup_s = t_open - harness.PROCESS_START
    log(phases.line(setup_s) + " (reference: after the window, not "
        f"counted); {engine.slots.occupied} of {engine.config.slots} slots "
        f"hold a request, {len(engine.scheduler.queue)} wait for pages; a "
        f"page holds {engine.page_bytes()} bytes")

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
    log(f"window {window:.3f} s: {tokens} tokens fetched, {served}; peak "
        f"{peak} bytes")

    shapes = eng.shapes()
    if tracer.dir is not None:
        # before the trace is reduced and removed
        shapes["device_ops_raw"] = scope_device_share.raw_device_ops(
            tracer.dir)
        shapes["op_scopes"] = eng.op_scopes()
    ev = harness.Evidence(
        samples=eng.samples(t_open, t_close, tracer),
        counters=eng.window_counters(t_open, t_close),
        shapes=shapes, trace=tracer.summary(ctx.keep_trace),
        peaks=harness.peaks_of(ctx.devices))
    eng.free()
    checks = _serve_longcat.check_served(ctx, eng, results, clients.prompts)
    return harness.Outcome(
        end_to_end={"serve_tokens_per_s": tokens / window,
                    "setup_s": setup_s},
        evidence=ev, correct=harness.judge(checks) and failed == 0,
        attempted=clients.sent, failed=failed, memory_peak_bytes=peak)
