"""Closed-loop serving of DeepSeek-V2: the loop of
`serve_closed_falconh1.py`, line for line (that file names its engine
wrapper itself, and a benchmark file that is there is not this PR's to
edit), around `_serve_deepseekv2.Engine`, the same generator of a loop
seen in its steady state (`deep_closed_loop`: the first wave's prompts are
files, history and the answer so far, built by prefill inside set-up) and
the DeepSeek-V2 reference. Here the wrapper IS a parameter (`run_loop`):
the next model's closed loop is its wrapper and three lines.

Under `--trace 1` it keeps the first chip's operations by their full
instruction names beside the program's map from instruction to named
scope, for the readers that split a decode step's device time by scope
(`readers/scope_device_share.py`); the counters the latent kernel's
roofline takes are the means over the captured ticks
(`Engine.traced_counters`), not the window's; and Python's cyclic
collector stands still from the first wave's end to the window's
(`collector_at_rest`: a tick here leaves the host as little slack as
Falcon-H1's, and two pauses are half the bound on `serve_tokens_per_s`).
"""
from __future__ import annotations

import time

from perfbench import harness
from perfbench.harness import log
from perfbench.kinds import _serve, _serve_deepseekv2
from perfbench.readers import scope_device_share


def run(ctx) -> harness.Outcome:
    return run_loop(ctx, _serve_deepseekv2)


def run_loop(ctx, serve) -> harness.Outcome:
    """The closed loop around `serve.Engine`, `serve.deep_closed_loop`,
    `serve.collector_at_rest` and `serve.check_served`."""
    t = ctx.traffic
    compiles = harness.CompileCounter()
    phases = harness.Phases()
    phases.mark("reach the chip")
    eng = serve.Engine(ctx)
    engine = eng.engine
    phases.mark("weights and engine")
    first, backlog = serve.deep_closed_loop(t, ctx.seed, eng.dims.vocab_real)
    # the backlog's prompts take every bucket the window can see; the first
    # wave's own are prefilled below, inside set-up
    counts = eng.warm([len(r.prompt) for r in backlog], eng.dims.vocab_real)
    phases.mark(f"compile or load of {counts}")

    clients = _serve.Clients(eng, first, backlog, ctx.seed)
    clients.first_wave(float(t["first_wave_limit_s"]))
    with serve.collector_at_rest():
        phases.mark("first wave")
        opened_at = len(eng.tick_at)
        t_open = time.perf_counter()
        setup_s = t_open - harness.PROCESS_START
        log(phases.line(setup_s) + " (reference: after the window, not "
            f"counted); {engine.slots.occupied} of {engine.config.slots} "
            f"slots hold a request, {len(engine.scheduler.queue)} wait for "
            f"pages; a page holds {engine.page_bytes()} bytes")

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
    # the host's standstills (PERF.md §7): ticks of the window, no prefill
    # call among them, that took over three times the median
    quiet = [s for at, s, rows in zip(eng.tick_at, eng.tick_s,
                                      eng.tick_prefilled_rows)
             if t_open <= at < t_close and rows == 0]
    median = sorted(quiet)[len(quiet) // 2] if quiet else 0.0
    slow = [s for s in quiet if s > 3 * median]
    log(f"slow ticks behind no prefill call: {len(slow)}, "
        f"{1e3 * sum(slow):.1f} ms together "
        f"({[round(1e3 * s) for s in slow][:12]})")
    log(f"window {window:.3f} s: {tokens} tokens fetched in "
        f"{counters.get('serve.ticks', 0):.0f} ticks, {served}; peak {peak} "
        f"bytes; completions at ticks "
        f"{[n - opened_at for n in clients.answered_at_tick if n > opened_at]}"
        f" of the window; counters "
        f"{ {k: round(v, 3) for k, v in counters.items()} }")

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
    checks = serve.check_served(ctx, eng, results, clients.prompts)
    return harness.Outcome(
        end_to_end={"serve_tokens_per_s": tokens / window,
                    "setup_s": setup_s},
        evidence=ev, correct=harness.judge(checks) and failed == 0,
        attempted=clients.sent, failed=failed, memory_peak_bytes=peak)
