"""Closed-loop serving: `clients` callers (`_serve.Clients`), each sending
its next request when its last completes, so the engine is kept as full as
admission allows. The end-to-end metric is the tokens fetched to the host
inside the window over the window, requests in flight at both ends
included.

Set-up admits and prefills the first wave (its outputs cut short by a
stratified share, so retirements and the replacement prefills are spread
over the window); the window opens when admission has come to rest and
every admitted row decodes.
"""
from __future__ import annotations

import time

from perfbench import generators, harness
from perfbench.harness import log
from perfbench.kinds import _serve


def run(ctx) -> harness.Outcome:
    t = ctx.traffic
    compiles = harness.CompileCounter()
    phases = harness.Phases()
    phases.mark("reach the chip")
    eng = _serve.Engine(ctx)
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
        f"hold a request, {len(engine.scheduler.queue)} wait for pages")

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

    ev = harness.Evidence(
        samples=eng.samples(t_open, t_close, tracer),
        counters=eng.window_counters(t_open, t_close),
        shapes=eng.shapes(), trace=tracer.summary(ctx.keep_trace),
        peaks=harness.peaks_of(ctx.devices))
    eng.free()
    checks = _serve.check_served(ctx, eng, results, clients.prompts)
    return harness.Outcome(
        end_to_end={"serve_tokens_per_s": tokens / window,
                    "setup_s": setup_s},
        evidence=ev, correct=harness.judge(checks) and failed == 0,
        attempted=clients.sent, failed=failed, memory_peak_bytes=peak)
