"""Open-loop serving: arrivals on a schedule, whatever the engine does.

All requests are submitted up front with their due times
(`Request.arrival`), so no generator thread competes for the host.
Arrivals start `ramp_s` before the window opens, at the window's rate, so
the window opens on a queue in steady state; the window is `--seconds` of
due times; arrivals go on for `tail_s` after it at the same rate, so that
the last requests of the window are served under the load the first ones
met; the run ends when the last request due in the window has completed,
or at `drain_limit_s`. Each request is timed from its due time, whenever
it completes; one that does not complete counts as failed and as waiting
until the run's end.
"""
from __future__ import annotations

import time

from perfbench import generators, harness
from perfbench.harness import log, percentile
from perfbench.kinds import _serve


def run(ctx) -> harness.Outcome:
    from mpi_operator_tpu.serve import Request

    t = ctx.traffic
    compiles = harness.CompileCounter()
    phases = harness.Phases()
    phases.mark("reach the chip")
    eng = _serve.Engine(ctx)
    engine = eng.engine
    phases.mark("weights and engine")
    ramp, tail = float(t["ramp_s"]), float(t["tail_s"])
    reqs = generators.open_loop(t, ctx.seed, ctx.seconds,
                                eng.dims.vocab_real, ramp, tail)
    counts = eng.warm([len(r.prompt) for r in reqs], eng.dims.vocab_real)
    phases.mark(f"compile or load of {counts}")

    prompts = {r.id: r.prompt for r in reqs}
    due = {r.id: r.arrival for r in reqs}              # window clock
    in_window = [r.id for r in reqs if 0.0 <= r.arrival < ctx.seconds]
    # the session clock reads 0 when the window opens
    t_open = time.perf_counter() + ramp
    now = lambda: time.perf_counter() - t_open  # noqa: E731
    engine.start(now_fn=now)
    for r in reqs:
        engine.submit(Request(id=r.id, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens,
                              arrival=r.arrival))

    def step():
        if not eng.tick():
            nxt = engine.scheduler.next_arrival()
            if nxt is not None and nxt > now():
                time.sleep(min(nxt - now(), 0.02))

    with compiles.window():
        while now() < 0.0:                              # the ramp
            step()
        phases.mark("ramp")
        setup_s = t_open - harness.PROCESS_START
        log(phases.line(setup_s) + " (reference: after the run, not "
            f"counted); at the opening {engine.slots.occupied} slots hold a "
            f"request and {len(engine.scheduler.queue)} requests are queued "
            f"or not yet due")
        tracer = harness.SubWindowTracer(ctx.trace, t["trace_start_s"],
                                         t["trace_seconds"])
        limit = ctx.seconds + float(t["drain_limit_s"])
        while now() < limit:
            tracer.poll(now())
            step()
            if now() >= ctx.seconds:
                tracer.stop()
                done = engine.session_results()
                if all(i in done for i in in_window):
                    break
        t_end = now()
        tracer.stop()
    if compiles.count:
        raise RuntimeError(f"{compiles.count} program(s) compiled during "
                           f"the ramp, the window or the drain")
    results = dict(engine.session_results())
    peak = harness.memory_peak_bytes(ctx.devices)

    ttft, tpot, wait, failed = [], [], [], 0
    for i in in_window:
        r = results.get(i)
        if r is None or r.finish_reason != "length" or not r.token_times:
            failed += 1
            ttft.append(1e3 * (t_end - due[i]))
            continue
        ttft.append(1e3 * (r.token_times[0] - due[i]))
        wait.append(1e3 * (r.admitted_at - due[i]))
        if len(r.token_times) > 1:
            tpot.append(1e3 * (r.token_times[-1] - r.token_times[0])
                        / (len(r.token_times) - 1))
    tpot += [max(tpot)] * failed if tpot else []
    log(f"window {ctx.seconds:.3f} s of due times: {len(in_window)} requests "
        f"due, {failed} failed; run ended {t_end - ctx.seconds:.3f} s after "
        f"the window; ttft ms p50 {percentile(ttft, 50):.1f} p90 "
        f"{percentile(ttft, 90):.1f}; tpot ms p50 {percentile(tpot, 50):.1f} "
        f"p90 {percentile(tpot, 90):.1f}; due-to-admission ms p50 "
        f"{percentile(wait, 50):.1f} p90 {percentile(wait, 90):.1f}; peak "
        f"{peak} bytes")

    w0, w1 = t_open, t_open + ctx.seconds
    counters = eng.window_counters(w0, w1)
    ev = harness.Evidence(
        samples={**eng.samples(w0, w1, tracer),
                 "serve.queue_wait_ms": wait},
        counters=counters, shapes=eng.shapes(),
        trace=tracer.summary(ctx.keep_trace),
        peaks=harness.peaks_of(ctx.devices))
    eng.free()
    window_results = {i: results[i] for i in in_window if i in results}
    checks = _serve.check_served(ctx, eng, window_results, prompts)
    return harness.Outcome(
        end_to_end={"ttft_p90_ms": percentile(ttft, 90),
                    "tpot_p90_ms": percentile(tpot, 90),
                    "setup_s": setup_s},
        evidence=ev, correct=harness.judge(checks) and failed == 0,
        attempted=len(in_window), failed=failed, memory_peak_bytes=peak)
