"""Serving benchmark: continuous batching vs sequential generate().

Replays a seeded mixed-length request trace through the serving engine
(serve/engine.py) and reports what a serving frontend cares about:

- aggregate NEW-tokens/sec across the whole trace,
- time-to-first-token (TTFT) p50/p99 — arrival → first sampled token,
  queueing delay included (a burst trace IS a loaded server),
- time-per-output-token (TPOT) p50/p99 — inter-token gaps per request,
- the no-recompile contract: compile counts of the engine's programs
  after the measured trace (step ≤ the 3 sample_slots modes, prefill
  ≤ the bucket count).

The baseline is the fixed-batch `generate()` oracle run TRACE-
SEQUENTIALLY (batch 1, each request to completion before the next
starts) — the naive way to serve ragged traffic with a lockstep
decoder, and the number continuous batching has to beat. The prompt and
new-token lengths are drawn from small grids so the baseline compiles
one program per (P, N) pair, all warmed before timing; the engine is
shape-oblivious by construction.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

from ._report import device_ids, with_run_report


def _percentiles(xs, ps=(50, 99)):
    import numpy as np
    if not xs:
        return {p: None for p in ps}
    return {p: float(np.percentile(np.asarray(xs), p)) for p in ps}


def _latency_fields(results, prefix="serving"):
    """TTFT/TPOT p50/p99 fields in ms over an iterable of Results.
    ttft == -1.0 is the "no token ever produced" sentinel (the request
    expired before its first sample) — excluded here, never folded into
    the percentiles as a negative latency. An all-timeout trace yields
    all-None fields instead of crashing."""
    import numpy as np
    ttft = _percentiles([r.ttft for r in results if r.ttft >= 0.0])
    tpot = _percentiles([dt for r in results
                         for dt in np.diff(r.token_times)])
    ms = lambda v, nd: round(v * 1e3, nd) if v is not None else None  # noqa: E731
    return {f"{prefix}_ttft_p50_ms": ms(ttft[50], 2),
            f"{prefix}_ttft_p99_ms": ms(ttft[99], 2),
            f"{prefix}_tpot_p50_ms": ms(tpot[50], 3),
            f"{prefix}_tpot_p99_ms": ms(tpot[99], 3)}


@with_run_report
def run_serving_benchmark(
    size: Optional[str] = None,
    family: str = "gpt2",
    slots: int = 8,
    num_requests: int = 32,
    prompt_grid: Sequence[int] = (32, 64, 128),
    new_grid: Sequence[int] = (32, 64),
    chunk_buckets: Tuple[int, ...] = (32, 128),
    dtype_name: str = "bfloat16",
    temperature: float = 0.0,
    kv_cache_dtype: Optional[str] = None,
    decode_kernel: Optional[bool] = None,
    page_size: int = 64,
    num_pages: Optional[int] = None,
    shared_prefix_len: int = 0,
    speculative: Optional[str] = None,
    draft_k: int = 4,
    baseline: bool = True,
    compare_sync: bool = False,
    compare_spec: bool = False,
    seed: int = 0,
    profile_dir: Optional[str] = None,
    metrics_port: Optional[int] = None,
    log: Callable[[str], None] = print,
) -> Dict[str, object]:
    """Returns a flat dict of serving metrics (see module docstring).
    `temperature` > 0 makes every other request sample at that
    temperature with top_k=40 (the rest stay greedy) — per-request
    sampling params exercising ONE compiled step; the sequential
    baseline runs each request at its own matching params.

    `compare_sync` re-runs the identical trace through the SAME engine
    with the double-buffered dispatch disabled (EngineConfig.async_decode
    = False, reset between — zero extra compiles) and reports the sync
    throughput, the async speedup (best-of-2 walls per mode, runs
    alternated — see the inline comment), and a token-identity check
    over the greedy requests (sampled requests legitimately differ across modes:
    an EOS retirement costs the async loop one extra dispatched step, so
    the per-step rng stream shifts).

    The engine's KV cache is a pool of `page_size`-token pages,
    `num_pages` of them (None = every slot's worst case).
    `shared_prefix_len` > 0 prepends ONE seeded system prompt of that
    many tokens to every request — the prefix-cache trace: the first
    wave prefills it cold and publishes, later waves pin the shared
    pages and skip that prefill. The report carries prefix_hit_rate,
    cold-vs-hit TTFT (admission-relative — a hit skips prefill, not the
    queue), and page-occupancy peaks.

    `speculative` ("ngram") turns on speculative decoding with
    `draft_k` drafted tokens per greedy row; the report adds the
    engine's acceptance rate and effective tokens per row-step.
    `compare_spec` re-runs the identical trace through the SAME engine
    with speculation disabled (reset between — zero extra compiles) and
    reports the non-spec throughput/TPOT, the spec speedup, and a
    token-identity check over the greedy requests (speculation changes
    WHEN tokens compute, never WHICH — sampled requests legitimately
    differ because the per-step rng stream shifts with step count).

    `profile_dir` captures an XProf trace of the MEASURED trace only
    (warmup excluded, trace serialization after the closing timestamp —
    same discipline as the train benchmarks' WindowProfiler).
    `metrics_port` starts a worker /metrics endpoint over the engine's
    live telemetry (0 = any free port) so the TTFT/TPOT/occupancy series
    are scrapeable while the trace replays."""
    import time

    from ..telemetry import WorkerTelemetry
    from ..utils.profiling import WindowProfiler

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import create_lm, generate
    from ..parallel import MeshConfig, make_mesh
    from ..parallel.sharding import shard_init
    from ..serve import EngineConfig, Request, ServingEngine

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    if decode_kernel is None:
        # same auto policy as run_generate_benchmark: Pallas fast path on
        # TPU, dense oracle elsewhere (interpret-mode pallas inside the
        # step would simulate, not measure). The record's `decode_impl`
        # is what the step traced, whatever was asked for here.
        decode_kernel = jax.default_backend() == "tpu"
    # cache length: fits the longest request, rounded up so the decode
    # kernel's k-tile divides it (decode_block_k caps at max_len, so any
    # multiple of 128 — or anything <= 128 that the tile equals — works)
    need = shared_prefix_len + max(prompt_grid) + max(new_grid)
    max_len = need if need <= 128 else -(-need // 128) * 128
    if max_len % page_size:
        max_len = -(-max_len // page_size) * page_size
    name = f"{family}-{size}" if size else family
    model = create_lm(name, dtype=dtype, kv_cache_dtype=kv_cache_dtype,
                      decode_kernel=decode_kernel, max_len=max_len)
    mesh = make_mesh(MeshConfig(dp=jax.device_count()))
    variables, _ = shard_init(
        model, mesh, jax.random.PRNGKey(0),
        jnp.zeros((1, min(prompt_grid)), jnp.int32))
    params = variables["params"]

    vocab = model.config.vocab_size
    rs = np.random.RandomState(seed)
    system_prompt = rs.randint(0, vocab, (shared_prefix_len,)).tolist()

    def make_request(i, p, n):
        temp = (temperature if temperature > 0 and i % 2 == 1 else 0.0)
        return Request(
            id=i, prompt=system_prompt + rs.randint(0, vocab, (p,)).tolist(),
            max_new_tokens=n, temperature=temp,
            top_k=40 if temp > 0 else 0)

    trace = [make_request(i, int(rs.choice(prompt_grid)),
                          int(rs.choice(new_grid)))
             for i in range(num_requests)]

    from ..telemetry.trace import (Tracer, build_trees, hop_percentiles,
                                   trace_sum_gap)

    wtel = WorkerTelemetry()
    # in-memory ring only (no sink file): the per-hop breakdown and the
    # completeness gate read the ring after the measured run
    tracer = Tracer(sample=1.0)
    warm_t0 = time.perf_counter()
    engine = ServingEngine(model, params, EngineConfig(
        slots=slots, chunk_buckets=tuple(chunk_buckets),
        decode_kernel=decode_kernel, rng_seed=seed,
        page_size=page_size, num_pages=num_pages,
        speculative=speculative, draft_k=draft_k),
        telemetry=wtel.serving, tracer=tracer)
    if metrics_port is not None:
        log(f"worker /metrics listening on port "
            f"{wtel.serve(port=metrics_port).port}")

    # warmup: one request per distinct prompt length (covers every
    # prefill bucket the trace can hit) + the step program; then reset —
    # the measured trace must be all steady-state
    warm = [make_request(10_000 + j, p, 2)
            for j, p in enumerate(sorted(set(int(r) for r in prompt_grid)))]
    engine.run(warm)
    engine.reset()
    # engine construction + warm-up: every program the measured trace
    # uses compiles in here, none after (serving_no_recompile)
    warmup_seconds = time.perf_counter() - warm_t0
    warm_counts = engine.compile_counts()
    param_ids = device_ids(engine.params)
    cache_ids = device_ids(engine.cache)
    log(f"serving placement: params on devices {param_ids}, KV cache on "
        f"devices {cache_ids} of {jax.device_count()} visible")

    profiler = WindowProfiler(profile_dir, log)
    profiler.start()
    try:
        t0 = time.perf_counter()
        results = engine.run(trace)
        wall = time.perf_counter() - t0
    finally:
        # stop AFTER the closing timestamp: xplane serialization is real
        # I/O and must never be charged to serving throughput
        profiler.stop_if_active()
        wtel.close()
    total_new = sum(len(r.tokens) for r in results.values())
    tps = total_new / wall
    lat = _latency_fields(results.values())
    counts = engine.compile_counts()
    # step has at most 3 variants (the sample_slots modes), prefill one
    # program per bucket; anything beyond that is a recompile leak
    no_recompile = (counts["step"] <= 3
                    and counts["prefill"] <= len(chunk_buckets))
    # every request came back with the token count it asked for (the
    # trace sets no eos_id, so "length" is the only way to finish)
    complete = all(r.id in results
                   and len(results[r.id].tokens) == r.max_new_tokens
                   for r in trace)
    # host_gap percentiles BEFORE any sync rerun below touches the same
    # histogram: these must describe the measured (async) trace only
    gap50_ms, gap99_ms = None, None
    gap = wtel.serving.host_gap_seconds
    if gap.count:
        gap50_ms = round(gap.percentile(50) * 1e3, 3)
        gap99_ms = round(gap.percentile(99) * 1e3, 3)
    # per-hop latency breakdown + completeness gate, snapshotted BEFORE
    # any compare_* rerun replays the same request ids through the
    # tracer: every measured request must have one root span whose hop
    # durations tile its end-to-end latency
    trace_spans = list(tracer.ring)
    trees = build_trees(trace_spans)
    req_trees = {r.id: trees.get(r.id) for r in trace}
    trace_complete = all(
        t is not None and t["root"] is not None
        and t["root"]["status"] == "ok" for t in req_trees.values())
    gaps = [trace_sum_gap(t) for t in req_trees.values()
            if t is not None and t["root"] is not None]
    gaps = [g for g in gaps if g is not None]
    hop_fields = {f"serving_hop_{k}": round(v, 3)
                  for k, v in hop_percentiles(trace_spans).items()}

    out: Dict[str, object] = {
        "serving_tokens_per_sec": round(tps, 1),
        "serving_requests": num_requests,
        "serving_requests_complete": bool(complete),
        "serving_slots": slots,
        "serving_total_new_tokens": total_new,
        "serving_wall_seconds": round(wall, 3),
        **lat,
        "serving_host_gap_p50_ms": gap50_ms,
        "serving_host_gap_p99_ms": gap99_ms,
        **hop_fields,
        "serving_trace_complete": bool(trace_complete),
        "serving_trace_max_gap_ms": (round(max(gaps) * 1e3, 3)
                                     if gaps else None),
        "serving_step_compiles": counts["step"],
        "serving_prefill_compiles": counts["prefill"],
        "serving_no_recompile": bool(no_recompile),
        "serving_compiles_after_warmup": (
            sum(counts.values()) - sum(warm_counts.values())),
        "serving_warmup_seconds": round(warmup_seconds, 3),
        "serving_param_device_ids": param_ids,
        "serving_cache_device_ids": cache_ids,
        "serving_decode_kernel": bool(decode_kernel),
        "serving_async_decode": bool(engine.config.async_decode),
        "serving_cache_donated": engine.donates_cache,
    }
    if speculative is not None:
        # snapshot spec counters BEFORE any compare_* rerun resets them
        spec = engine.spec_stats()
        # verify pins like step does: <= 2 bucketed widths per
        # sample_slots mode, and a trace touches at most 3 modes
        out["serving_no_recompile"] = bool(
            no_recompile and counts["verify"] <= 2 * 3)
        out.update({
            "serving_speculative": speculative,
            "serving_spec_draft_k": draft_k,
            "serving_spec_proposed": int(spec["proposed"]),
            "serving_spec_accepted": int(spec["accepted"]),
            "serving_spec_acceptance_rate":
                round(spec["acceptance_rate"], 4),
            "serving_spec_effective_tokens_per_step":
                round(spec["effective_tokens_per_step"], 3),
            "serving_verify_compiles": counts["verify"],
        })
        log(f"speculative ({speculative}, k={draft_k}): acceptance "
            f"{out['serving_spec_acceptance_rate']} "
            f"({spec['accepted']}/{spec['proposed']} drafts), "
            f"{out['serving_spec_effective_tokens_per_step']} effective "
            f"tokens/row-step over {spec['verify_steps']} verify steps, "
            f"{counts['verify']} verify compiles")
    # snapshot the allocator BEFORE any compare_sync rerun resets it
    alloc = engine.page_allocator
    lookups = alloc.hits + alloc.misses
    ms = lambda v: round(v * 1e3, 3) if v is not None else None  # noqa: E731
    # admission-relative TTFT: a prefix hit skips prefill work, not
    # queueing delay, so the cold/hit split excludes the queue
    adm = lambda r: r.token_times[0] - r.admitted_at  # noqa: E731
    cold = _percentiles([adm(r) for r in results.values()
                         if r.cached_tokens == 0 and r.token_times])
    hit = _percentiles([adm(r) for r in results.values()
                        if r.cached_tokens > 0 and r.token_times])
    hit_reqs = sum(1 for r in results.values() if r.cached_tokens > 0)
    out.update({
        "serving_page_size": page_size,
        "serving_pages_total": alloc.usable,
        "serving_pages_in_use_peak": engine.pages_in_use_peak,
        "serving_occupancy_peak": engine.occupancy_peak,
        "serving_prefix_hit_rate": (round(alloc.hits / lookups, 4)
                                    if lookups else 0.0),
        "serving_prefix_hit_pages": alloc.hits,
        "serving_prefix_miss_pages": alloc.misses,
        "serving_prefix_hit_requests": hit_reqs,
        "serving_ttft_cold_p50_ms": ms(cold[50]),
        "serving_ttft_cold_p99_ms": ms(cold[99]),
        "serving_ttft_hit_p50_ms": ms(hit[50]),
        "serving_ttft_hit_p99_ms": ms(hit[99]),
    })
    log(f"paged KV: {alloc.usable} pages x {page_size} tokens, "
        f"peak {engine.pages_in_use_peak} pages / "
        f"{engine.occupancy_peak} slots in use; prefix hit rate "
        f"{out['serving_prefix_hit_rate']} ({hit_reqs} hit reqs), "
        f"TTFT-from-admission cold p50 "
        f"{out['serving_ttft_cold_p50_ms']} ms vs hit p50 "
        f"{out['serving_ttft_hit_p50_ms']} ms")
    log(f"serving {name}: {num_requests} reqs over {slots} slots: "
        f"{tps:.0f} new tokens/sec, TTFT p50/p99 "
        f"{out['serving_ttft_p50_ms']}/{out['serving_ttft_p99_ms']} ms, "
        f"TPOT p50/p99 {out['serving_tpot_p50_ms']}/"
        f"{out['serving_tpot_p99_ms']} ms, recompile-free="
        f"{no_recompile}")

    if compare_spec:
        # spec vs no-spec on the IDENTICAL seeded trace through the
        # same engine (reset between — same compiled step/prefill
        # programs, the verify program simply sits unused). Greedy
        # token identity is the exactness gate; sampled requests may
        # differ (per-step rng stream shifts with the step count).
        if speculative is None:
            raise ValueError("compare_spec requires speculative")
        engine.config.speculative = None
        engine.reset()
        t0 = time.perf_counter()
        base_results = engine.run(trace)
        base_wall = time.perf_counter() - t0
        engine.config.speculative = speculative
        base_total = sum(len(r.tokens) for r in base_results.values())
        base_tps = base_total / base_wall
        base_tpot = _percentiles([dt for r in base_results.values()
                                  for dt in np.diff(r.token_times)])
        spec_identical = all(
            results[r.id].tokens == base_results[r.id].tokens
            for r in trace if r.temperature == 0.0)
        out.update({
            "serving_nospec_tokens_per_sec": round(base_tps, 1),
            "serving_nospec_wall_seconds": round(base_wall, 3),
            "serving_nospec_tpot_p50_ms": (round(base_tpot[50] * 1e3, 3)
                                           if base_tpot[50] is not None
                                           else None),
            "serving_nospec_tpot_p99_ms": (round(base_tpot[99] * 1e3, 3)
                                           if base_tpot[99] is not None
                                           else None),
            "serving_spec_speedup": (round(tps / base_tps, 3)
                                     if base_tps else None),
            "serving_spec_greedy_identical": bool(spec_identical),
        })
        log(f"spec A/B: {tps:.0f} spec vs {base_tps:.0f} no-spec new "
            f"tokens/sec -> {out['serving_spec_speedup']}x, greedy "
            f"token-identical={spec_identical}")

    if compare_sync:
        # the A/B the double-buffered loop has to win: same engine, same
        # compiled programs, dispatch-then-drain instead of overlap.
        # Best-of-2 per mode, runs ALTERNATED (sync, async, sync): the
        # structural win is per-decode-step host time hidden under the
        # device, a few percent of wall — smaller than single-run noise
        # on a shared host, and a monotone drift (thermal, competing
        # load) would otherwise charge one mode for running later. The
        # measured (telemetry-backed) async wall above is async's first
        # sample.
        def timed_run(mode):
            engine.config.async_decode = mode
            engine.reset()
            t0 = time.perf_counter()
            r = engine.run(trace)
            return r, time.perf_counter() - t0

        sync_results, sync_wall = timed_run(False)
        _, async_wall2 = timed_run(True)
        _, sync_wall2 = timed_run(False)
        engine.config.async_decode = True
        sync_total = sum(len(r.tokens) for r in sync_results.values())
        best_async = min(wall, async_wall2)
        best_sync = min(sync_wall, sync_wall2)
        sync_tps = sync_total / best_sync
        async_tps = total_new / best_async
        greedy_identical = all(
            results[r.id].tokens == sync_results[r.id].tokens
            for r in trace if r.temperature == 0.0)
        out.update({
            "serving_sync_tokens_per_sec": round(sync_tps, 1),
            "serving_sync_wall_seconds": round(best_sync, 3),
            "serving_async_speedup": (round(async_tps / sync_tps, 3)
                                      if sync_tps else None),
            "serving_async_greedy_identical": bool(greedy_identical),
        })
        log(f"sync-decode A/B (best-of-2 each): {sync_tps:.0f} sync vs "
            f"{async_tps:.0f} async new tokens/sec -> "
            f"{out['serving_async_speedup']}x, greedy token-identical="
            f"{greedy_identical}")

    if baseline:
        # trace-sequential generate(): warm one compile per (P, N, temp)
        # shape class, then replay the identical trace one request at a
        # time. Same params, same sampling config per request.
        def run_one(req):
            return generate(
                model, params, jnp.asarray([list(req.prompt)]),
                req.max_new_tokens, temperature=req.temperature,
                top_k=req.top_k or None,
                rng=(jax.random.PRNGKey(req.id)
                     if req.temperature > 0 else None))

        shapes = {}
        for r in trace:
            shapes[(len(r.prompt), r.max_new_tokens,
                    r.temperature > 0)] = r
        for r in shapes.values():
            int(run_one(r).tokens[0, -1])       # compile + wait
        t0 = time.perf_counter()
        for r in trace:
            o = run_one(r)
        int(o.tokens[0, -1])                    # waits for the last call
        base_wall = time.perf_counter() - t0
        base_total = sum(r.max_new_tokens for r in trace)
        base_tps = base_total / base_wall
        speedup = tps / base_tps if base_tps else None
        out.update({
            "sequential_tokens_per_sec": round(base_tps, 1),
            "sequential_wall_seconds": round(base_wall, 3),
            "serving_vs_sequential": (round(speedup, 2)
                                      if speedup else None),
        })
        log(f"sequential generate() baseline: {base_tps:.0f} new "
            f"tokens/sec -> continuous batching {speedup:.2f}x")
    return out


@with_run_report
def run_disagg_benchmark(
    size: Optional[str] = None,
    family: str = "gpt2",
    slots: int = 8,
    num_requests: int = 24,
    prompt_grid: Sequence[int] = (64, 256, 384),
    new_grid: Sequence[int] = (16, 32),
    chunk_buckets: Tuple[int, ...] = (64, 128),
    dtype_name: str = "bfloat16",
    kv_cache_dtype: Optional[str] = None,
    decode_kernel: Optional[bool] = None,
    page_size: int = 64,
    num_pages: Optional[int] = None,
    seed: int = 0,
    log: Callable[[str], None] = print,
) -> Dict[str, object]:
    """Disaggregated prefill/decode A/B vs the colocated engine at equal
    chip count: the same long-prompt-heavy greedy trace (the grid skews
    long — long prompts are exactly the TTFT/TPOT interference the
    split removes) replays through a colocated ServingEngine and
    a DisaggEngine built from the SAME params and config, reporting
    TTFT/TPOT p50/p99 for both, kv_handoff p50/p99, and the per-pool
    compile pins (prefill pool never compiles step, decode pool never
    compiles prefill). Greedy-only: temperature 0 is the token-exact
    parity regime, so the A/B also asserts token identity.

    On CPU smoke the two pools are host devices and the latency split is
    structural only — token identity + pins are the gate there; the
    TTFT/TPOT win is measured on real hardware (ROADMAP follow-up)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import create_lm
    from ..parallel import MeshConfig, make_mesh
    from ..parallel.sharding import shard_init
    from ..serve import DisaggEngine, EngineConfig, Request, ServingEngine

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    if decode_kernel is None:
        decode_kernel = jax.default_backend() == "tpu"
    need = max(prompt_grid) + max(new_grid)
    max_len = need if need <= 128 else -(-need // 128) * 128
    if max_len % page_size:
        max_len = -(-max_len // page_size) * page_size
    name = f"{family}-{size}" if size else family
    model = create_lm(name, dtype=dtype, kv_cache_dtype=kv_cache_dtype,
                      decode_kernel=decode_kernel, max_len=max_len)
    mesh = make_mesh(MeshConfig(dp=jax.device_count()))
    variables, _ = shard_init(
        model, mesh, jax.random.PRNGKey(0),
        jnp.zeros((1, min(prompt_grid)), jnp.int32))
    params = variables["params"]

    vocab = model.config.vocab_size
    rs = np.random.RandomState(seed)

    def make_request(i, p, n):
        return Request(id=i, prompt=rs.randint(0, vocab, (p,)).tolist(),
                       max_new_tokens=n)

    trace = [make_request(i, int(rs.choice(prompt_grid)),
                          int(rs.choice(new_grid)))
             for i in range(num_requests)]

    from ..telemetry.trace import (Tracer, build_trees, hop_name,
                                   hop_percentiles)

    cfg = EngineConfig(
        slots=slots, chunk_buckets=tuple(chunk_buckets),
        decode_kernel=decode_kernel, rng_seed=seed,
        page_size=page_size, num_pages=num_pages)
    coloc = ServingEngine(model, params, cfg)
    tracer = Tracer(sample=1.0)
    disagg = DisaggEngine(model, params, cfg, tracer=tracer)

    warm = [make_request(10_000 + j, p, 2)
            for j, p in enumerate(sorted(set(int(r) for r in prompt_grid)))]

    def timed(engine):
        engine.run(warm)
        engine.reset()
        t0 = time.perf_counter()
        results = engine.run(trace)
        return results, time.perf_counter() - t0

    coloc_results, coloc_wall = timed(coloc)
    disagg_results, disagg_wall = timed(disagg)

    def latency(results):
        # drop the ttft == -1.0 "no token produced" sentinel
        ttft = _percentiles([r.ttft for r in results.values()
                             if r.ttft >= 0.0])
        tpot = _percentiles([dt for r in results.values()
                             for dt in np.diff(r.token_times)])
        return ttft, tpot

    ms = lambda v: round(v * 1e3, 3) if v is not None else None  # noqa: E731
    c_ttft, c_tpot = latency(coloc_results)
    d_ttft, d_tpot = latency(disagg_results)
    total_new = sum(len(r.tokens) for r in disagg_results.values())

    identical = all(coloc_results[r.id].tokens == disagg_results[r.id].tokens
                    for r in trace)
    counts = disagg.compile_counts()
    pre, dec = counts["prefill_pool"], counts["decode_pool"]
    pins = (pre["step"] == 0 and pre["prefill"] <= len(chunk_buckets)
            and dec["prefill"] == 0 and dec["step"] <= 3)
    handoff = _percentiles([dt for dt, _, _ in disagg.handoff_log])
    # request traces: every measured request must show the full
    # prefill -> kv_handoff -> decode hop chain with the page counts the
    # handoff actually moved riding as hop attrs (warm-batch ids are
    # excluded so the percentiles describe the measured trace only)
    idset = {r.id for r in trace}
    spans = [s for s in tracer.ring if s["trace"] in idset]
    trees = build_trees(spans)
    trace_handoff_pages = 0
    trace_complete = True
    for r in trace:
        t = trees.get(r.id)
        if t is None or t["root"] is None or t["root"]["status"] != "ok":
            trace_complete = False
            continue
        hops = [hop_name(s) for s in t["spans"]
                if s.get("parent") is not None]
        if not ("prefill" in hops and "kv_handoff" in hops
                and "decode" in hops):
            trace_complete = False
        for s in t["spans"]:
            if s.get("parent") is not None and hop_name(s) == "kv_handoff":
                trace_handoff_pages += int(
                    (s.get("attrs") or {}).get("pages", 0))
    hop_fields = {f"disagg_hop_{k}": round(v, 3)
                  for k, v in hop_percentiles(spans).items()}

    out: Dict[str, object] = {
        "disagg_tokens_per_sec": round(total_new / disagg_wall, 1),
        "disagg_wall_seconds": round(disagg_wall, 3),
        "disagg_ttft_p50_ms": ms(d_ttft[50]),
        "disagg_ttft_p99_ms": ms(d_ttft[99]),
        "disagg_tpot_p50_ms": ms(d_tpot[50]),
        "disagg_tpot_p99_ms": ms(d_tpot[99]),
        "coloc_tokens_per_sec": round(
            sum(len(r.tokens) for r in coloc_results.values())
            / coloc_wall, 1),
        "coloc_wall_seconds": round(coloc_wall, 3),
        "coloc_ttft_p50_ms": ms(c_ttft[50]),
        "coloc_ttft_p99_ms": ms(c_ttft[99]),
        "coloc_tpot_p50_ms": ms(c_tpot[50]),
        "coloc_tpot_p99_ms": ms(c_tpot[99]),
        "disagg_kv_handoff_p50_ms": ms(handoff[50]),
        "disagg_kv_handoff_p99_ms": ms(handoff[99]),
        "disagg_kv_handoff_pages_total": disagg.transfer.pages_moved,
        "disagg_handoffs": len(disagg.handoff_log),
        **hop_fields,
        "disagg_trace_complete": bool(trace_complete),
        "disagg_trace_handoff_pages": trace_handoff_pages,
        "disagg_token_identical": bool(identical),
        "disagg_pool_pins_held": bool(pins),
        "disagg_prefill_pool_prefill_compiles": pre["prefill"],
        "disagg_prefill_pool_step_compiles": pre["step"],
        "disagg_decode_pool_step_compiles": dec["step"],
        "disagg_decode_pool_prefill_compiles": dec["prefill"],
        "disagg_requests": num_requests,
        "disagg_slots": slots,
        "disagg_page_size": page_size,
        "disagg_two_devices": disagg.devices[0] != disagg.devices[1],
    }
    log(f"disagg {name}: {num_requests} reqs, TTFT p50/p99 "
        f"{out['disagg_ttft_p50_ms']}/{out['disagg_ttft_p99_ms']} ms vs "
        f"coloc {out['coloc_ttft_p50_ms']}/{out['coloc_ttft_p99_ms']} ms; "
        f"TPOT p99 {out['disagg_tpot_p99_ms']} vs "
        f"{out['coloc_tpot_p99_ms']} ms; kv_handoff p50/p99 "
        f"{out['disagg_kv_handoff_p50_ms']}/"
        f"{out['disagg_kv_handoff_p99_ms']} ms over "
        f"{out['disagg_handoffs']} handoffs "
        f"({out['disagg_kv_handoff_pages_total']} pages); "
        f"token-identical={identical}, pool-pins={pins}")
    return out


@with_run_report
def run_router_benchmark(
    size: Optional[str] = None,
    family: str = "gpt2",
    replicas: int = 2,
    slots: int = 4,
    num_requests: int = 24,
    prompt_grid: Sequence[int] = (16, 32),
    new_grid: Sequence[int] = (8, 16),
    chunk_buckets: Tuple[int, ...] = (16, 64),
    dtype_name: str = "bfloat16",
    decode_kernel: Optional[bool] = None,
    page_size: int = 16,
    num_pages: Optional[int] = None,
    shared_prefix_len: int = 32,
    num_tenants: int = 4,
    max_inflight: int = 8,
    arrival_gap: float = 0.15,
    seed: int = 0,
    log: Callable[[str], None] = print,
) -> Dict[str, object]:
    """Front-door A/B: the same seeded multi-tenant shared-system-prompt
    trace through `replicas` engine replicas behind the Router,
    affinity ON vs OFF (pure load-aware), plus an overload burst.

    The trace draws each request's prompt as one of `num_tenants` seeded
    system prefixes plus a per-request tail, arrivals `arrival_gap`
    apart — affinity ON concentrates each tenant's chain on one replica,
    OFF scatters it, and the replica-side PageAllocator hit counters
    (ground truth, not the router's own prediction) decide the A/B.

    Gates folded into the JSON record (the tier1 --router greps):
    per-request tokens bitwise-identical to a single-engine greedy
    oracle in BOTH modes, replica-measured hit rate strictly higher with
    affinity ON, zero sheds at this low offered load, >= 1 shed and a
    clean late-arrival recovery in the overload burst, and the compile
    pins (step <= 3, prefill <= buckets) unchanged on EVERY replica of
    every fleet."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import create_lm
    from ..parallel import MeshConfig, make_mesh
    from ..parallel.sharding import shard_init
    from ..serve import EngineConfig, Request, Router, RouterConfig, \
        ServingEngine
    from ..telemetry.trace import (Tracer, build_trees, hop_percentiles,
                                   orphan_spans, trace_sum_gap)

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    if decode_kernel is None:
        decode_kernel = jax.default_backend() == "tpu"
    need = shared_prefix_len + max(prompt_grid) + max(new_grid)
    max_len = need if need <= 128 else -(-need // 128) * 128
    if max_len % page_size:
        max_len = -(-max_len // page_size) * page_size
    name = f"{family}-{size}" if size else family
    model = create_lm(name, dtype=dtype, decode_kernel=decode_kernel,
                      max_len=max_len)
    mesh = make_mesh(MeshConfig(dp=jax.device_count()))
    variables, _ = shard_init(
        model, mesh, jax.random.PRNGKey(0),
        jnp.zeros((1, min(prompt_grid)), jnp.int32))
    params = variables["params"]

    vocab = model.config.vocab_size
    rs = np.random.RandomState(seed)
    tenants = [rs.randint(0, vocab, (shared_prefix_len,)).tolist()
               for _ in range(num_tenants)]

    def make_request(i, arrival):
        # tenants cycle round-robin, so consecutive same-tenant arrivals
        # sit num_tenants * arrival_gap apart — the first tenant request
        # has time to prefill and PUBLISH its prefix pages before the
        # second one's dispatch probes for them
        p, n = int(rs.choice(prompt_grid)), int(rs.choice(new_grid))
        prefix = tenants[i % num_tenants]
        return Request(
            id=i, prompt=prefix + rs.randint(0, vocab, (p,)).tolist(),
            max_new_tokens=n, arrival=arrival)

    trace = [make_request(i, i * arrival_gap) for i in range(num_requests)]
    # greedy only: token exactness across engines/replays is the gate
    assert all(r.temperature == 0.0 for r in trace)

    # warm one request per prompt length (covers every prefill bucket)
    # through each fresh replica, then reset — measured traffic is
    # steady-state and the TTFT A/B never charges a compile to a mode
    warm = [Request(10_000 + j,
                    rs.randint(0, vocab, (shared_prefix_len + p,)).tolist(),
                    2)
            for j, p in enumerate(sorted(set(int(v) for v in prompt_grid)))]

    def mk_engine():
        e = ServingEngine(model, params, EngineConfig(
            slots=slots, chunk_buckets=tuple(chunk_buckets),
            decode_kernel=decode_kernel, rng_seed=seed,
            page_size=page_size, num_pages=num_pages))
        e.run([Request(w.id, list(w.prompt), w.max_new_tokens)
               for w in warm])
        e.reset()
        return e

    def fresh_trace(reqs):
        return [Request(r.id, list(r.prompt), r.max_new_tokens,
                        arrival=r.arrival) for r in reqs]

    # single-engine greedy oracle: continuous batching is token-exact
    # regardless of batch composition, so ONE engine over the whole
    # trace defines the authoritative tokens for every fleet shape
    oracle_engine = mk_engine()
    oracle = {rid: res.tokens for rid, res in oracle_engine.run(
        [Request(r.id, list(r.prompt), r.max_new_tokens)
         for r in trace]).items()}

    def pins_held(router):
        return all(
            rep.engine.compile_counts()["step"] <= 3
            and rep.engine.compile_counts()["prefill"] <= len(chunk_buckets)
            for rep in router.replicas)

    def replica_hit_rate(router):
        hits = sum(rep.engine.page_allocator.hits for rep in router.replicas)
        miss = sum(rep.engine.page_allocator.misses
                   for rep in router.replicas)
        return hits / (hits + miss) if hits + miss else 0.0, hits

    def fleet_run(affinity, tracer=None):
        router = Router([mk_engine() for _ in range(replicas)],
                        RouterConfig(max_inflight=max_inflight,
                                     affinity=affinity),
                        tracer=tracer)
        t0 = time.perf_counter()
        results = router.run(fresh_trace(trace))
        return router, results, time.perf_counter() - t0

    # trace the measured (affinity-ON) arm at sample=1.0: every request
    # must reconstruct into a queue_wait -> admission -> prefill ->
    # decode span tree whose hop durations sum to the root e2e within
    # tolerance — the front-door-to-final-token completeness gate
    on_tracer = Tracer(sample=1.0)
    on_router, on_results, on_wall = fleet_run(True, on_tracer)
    off_router, off_results, off_wall = fleet_run(False)

    trace_ids = {r.id for r in trace}
    trace_spans = [s for s in on_tracer.ring if s["trace"] in trace_ids
                   or s["trace"] < 0]
    trees = build_trees(trace_spans)
    trace_gaps = []
    trace_complete = len(orphan_spans(trace_spans)) == 0
    for r in trace:
        t = trees.get(r.id)
        if t is None or t["root"] is None or t["root"]["status"] != "ok":
            trace_complete = False
            continue
        gap = trace_sum_gap(t)
        if gap is None:
            trace_complete = False
            continue
        trace_gaps.append(gap)
        if gap > max(0.005, 0.02 * t["root"]["seconds"]):
            trace_complete = False
    trace_hops = {f"router_hop_{k}": round(v, 3)
                  for k, v in hop_percentiles(trace_spans).items()}

    ms = lambda v: round(v * 1e3, 3) if v is not None else None  # noqa: E731
    adm = lambda r: r.token_times[0] - r.admitted_at  # noqa: E731

    def adm_ttft_p50(results):
        return _percentiles([adm(r) for r in results.values()
                             if r.token_times])[50]

    identical = all(
        on_results[r.id].tokens == oracle[r.id]
        and off_results[r.id].tokens == oracle[r.id] for r in trace)
    on_rate, on_hits = replica_hit_rate(on_router)
    off_rate, off_hits = replica_hit_rate(off_router)
    on_p50, off_p50 = adm_ttft_p50(on_results), adm_ttft_p50(off_results)
    # "no worse" with 20% headroom: the structural win is skipped prefill
    # work; single-run CPU noise must not flip a smoke verdict
    ttft_ok = (on_p50 is not None and off_p50 is not None
               and on_p50 <= off_p50 * 1.2)
    total_new = sum(len(r.tokens) for r in on_results.values())
    lat = _latency_fields(on_results.values(), prefix="router")

    # overload burst on a fresh fleet with a tight in-flight cap: every
    # burst request is due at once, so dispatch fills replicas*cap slots
    # and front-door-sheds the rest BEFORE any replica queues them; the
    # late recovery wave must then land entirely on drained replicas
    burst_cap = 2
    burst_n = replicas * burst_cap + 4
    burst = [make_request(1_000 + i, 0.0) for i in range(burst_n)]
    recovery = [make_request(2_000 + i, 2.5) for i in range(replicas)]
    burst_router = Router([mk_engine() for _ in range(replicas)],
                          RouterConfig(max_inflight=burst_cap))
    burst_results = burst_router.run(fresh_trace(burst + recovery))
    burst_sheds = sum(1 for r in burst
                      if burst_results[r.id].finish_reason == "shed")
    recovered = [burst_results[r.id] for r in recovery]
    recovery_clean = all(r.finish_reason in ("eos", "length")
                         for r in recovered)

    out: Dict[str, object] = {
        "router_replicas": replicas,
        "router_requests": num_requests,
        "router_slots": slots,
        "router_max_inflight": max_inflight,
        "router_page_size": page_size,
        "router_shared_prefix_len": shared_prefix_len,
        "router_num_tenants": num_tenants,
        "router_tokens_per_sec": round(total_new / on_wall, 1),
        "router_wall_seconds": round(on_wall, 3),
        "router_offered_rps": round(1.0 / arrival_gap, 2),
        **lat,
        "router_token_identical": bool(identical),
        "router_dispatch_counts": on_router.dispatch_counts(),
        "router_shed_low_load": on_router.shed_count()
                                + off_router.shed_count(),
        "router_affinity_hit_rate": round(on_rate, 4),
        "router_noaffinity_hit_rate": round(off_rate, 4),
        "router_affinity_nonzero": bool(on_rate > 0.0),
        "router_affinity_hit_gain": bool(on_rate > off_rate),
        "router_replica_prefix_hit_pages": on_hits,
        "router_predicted_hit_pages": on_router.affinity_hit_pages,
        "router_affinity_adm_ttft_p50_ms": ms(on_p50),
        "router_noaffinity_adm_ttft_p50_ms": ms(off_p50),
        "router_affinity_ttft_ok": bool(ttft_ok),
        "router_noaffinity_wall_seconds": round(off_wall, 3),
        "router_burst_requests": burst_n,
        "router_burst_sheds": burst_sheds,
        "router_burst_recovered": len(recovered),
        "router_burst_recovery_clean": bool(recovery_clean),
        "router_compile_pins_held": bool(
            pins_held(on_router) and pins_held(off_router)
            and pins_held(burst_router)),
        **trace_hops,
        "router_trace_complete": bool(trace_complete),
        "router_trace_max_gap_ms": (round(max(trace_gaps) * 1e3, 3)
                                    if trace_gaps else None),
    }
    log(f"router {name}: {num_requests} reqs over {replicas}x{slots} "
        f"slots at {out['router_offered_rps']} req/s offered: "
        f"{out['router_tokens_per_sec']} new tokens/sec, TTFT p99 "
        f"{out['router_ttft_p99_ms']} ms; hit rate "
        f"{out['router_affinity_hit_rate']} (affinity) vs "
        f"{out['router_noaffinity_hit_rate']} (load-only), adm-TTFT p50 "
        f"{out['router_affinity_adm_ttft_p50_ms']} vs "
        f"{out['router_noaffinity_adm_ttft_p50_ms']} ms; dispatch "
        f"{out['router_dispatch_counts']}, {out['router_shed_low_load']} "
        f"low-load sheds; burst {burst_n} -> {burst_sheds} sheds, "
        f"recovery clean={recovery_clean}; token-identical={identical}, "
        f"pins={out['router_compile_pins_held']}")
    return out


@with_run_report
def run_livescale_benchmark(
    size: Optional[str] = None,
    family: str = "gpt2",
    replicas: int = 2,
    slots: int = 4,
    num_requests: int = 12,
    prompt_grid: Sequence[int] = (16, 32),
    new_grid: Sequence[int] = (8, 16),
    chunk_buckets: Tuple[int, ...] = (16, 64),
    dtype_name: str = "bfloat16",
    decode_kernel: Optional[bool] = None,
    page_size: int = 16,
    num_pages: Optional[int] = None,
    shared_prefix_len: int = 32,
    num_tenants: int = 4,
    max_inflight: int = 8,
    arrival_gap: float = 0.15,
    scale_up_at: float = 0.3,
    scale_down_at: float = 0.8,
    seed: int = 0,
    log: Callable[[str], None] = print,
) -> Dict[str, object]:
    """Live decode-pool scaling vs gang restart: the SAME seeded trace
    through a ±1 replica cycle both ways.

    LIVE arm: a `replicas`-wide fleet takes one +1 step (a pre-warmed
    engine attaches at `scale_up_at`; build + warmup happen OUT of the
    trace clock — production prewarns out of band, which is live
    scaling's whole point) and one -1 step (replica 0 gracefully drains
    at `scale_down_at`: queued requests fail over to survivors,
    residents finish in place, pages/slots verified reclaimed). No
    survivor pauses, nothing recompiles.

    GANG arm: the same decision at `scale_up_at` materialized the old
    way — admission closes, in-flight work drains, then the WHOLE fleet
    is torn down and rebuilt one replica wider with construction,
    compile, and warmup all in-band; arrivals during the outage queue at
    a dead front door.

    Gates folded into the JSON record (the tier1 --router greps): zero
    dropped/shed requests in the live arm, every request's tokens
    bitwise-identical to the single-engine greedy oracle in BOTH arms
    (drained-replica failovers included — greedy replay is
    engine-independent), survivor compile pins untouched, and the
    measured live_scale ledger totals (through the REAL resize_ledger
    reader) strictly below the same trace's gang-restart total — the
    number the autoscaler's cooldown prices."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import create_lm
    from ..parallel import MeshConfig, make_mesh
    from ..parallel.sharding import shard_init
    from ..serve import EngineConfig, Request, Router, RouterConfig, \
        ServingEngine
    from ..telemetry.collector import resize_ledger
    from ..telemetry.events import LIVE_SCALE
    from ..telemetry.trace import (Tracer, build_trees, hop_percentiles,
                                   orphan_spans, trace_sum_gap)

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    if decode_kernel is None:
        decode_kernel = jax.default_backend() == "tpu"
    need = shared_prefix_len + max(prompt_grid) + max(new_grid)
    max_len = need if need <= 128 else -(-need // 128) * 128
    if max_len % page_size:
        max_len = -(-max_len // page_size) * page_size
    name = f"{family}-{size}" if size else family
    model = create_lm(name, dtype=dtype, decode_kernel=decode_kernel,
                      max_len=max_len)
    mesh = make_mesh(MeshConfig(dp=jax.device_count()))
    variables, _ = shard_init(
        model, mesh, jax.random.PRNGKey(0),
        jnp.zeros((1, min(prompt_grid)), jnp.int32))
    params = variables["params"]

    vocab = model.config.vocab_size
    rs = np.random.RandomState(seed)
    tenants = [rs.randint(0, vocab, (shared_prefix_len,)).tolist()
               for _ in range(num_tenants)]

    def make_request(i, arrival):
        p, n = int(rs.choice(prompt_grid)), int(rs.choice(new_grid))
        prefix = tenants[i % num_tenants]
        return Request(
            id=i, prompt=prefix + rs.randint(0, vocab, (p,)).tolist(),
            max_new_tokens=n, arrival=arrival)

    trace = [make_request(i, i * arrival_gap) for i in range(num_requests)]
    assert all(r.temperature == 0.0 for r in trace)

    warm = [Request(10_000 + j,
                    rs.randint(0, vocab, (shared_prefix_len + p,)).tolist(),
                    2)
            for j, p in enumerate(sorted(set(int(v) for v in prompt_grid)))]

    def mk_engine():
        e = ServingEngine(model, params, EngineConfig(
            slots=slots, chunk_buckets=tuple(chunk_buckets),
            decode_kernel=decode_kernel, rng_seed=seed,
            page_size=page_size, num_pages=num_pages))
        e.run([Request(w.id, list(w.prompt), w.max_new_tokens)
               for w in warm])
        e.reset()
        return e

    def fresh_trace(reqs):
        return [Request(r.id, list(r.prompt), r.max_new_tokens,
                        arrival=r.arrival) for r in reqs]

    oracle_engine = mk_engine()
    oracle = {rid: res.tokens for rid, res in oracle_engine.run(
        [Request(r.id, list(r.prompt), r.max_new_tokens)
         for r in trace]).items()}

    def pins_held(router):
        return all(
            rep.engine.compile_counts()["step"] <= 3
            and rep.engine.compile_counts()["prefill"] <= len(chunk_buckets)
            for rep in router.replicas)

    cfg = RouterConfig(max_inflight=max_inflight)

    # -- LIVE arm: ±1 mid-trace, fleet never pauses -----------------------
    # the +1 engine is built and warmed OUT of the trace clock; only the
    # measured cost rides into the ledger as the step's warmup phase
    warm_t0 = time.perf_counter()
    newcomer = mk_engine()
    attach_warmup = time.perf_counter() - warm_t0
    # trace the live arm end to end: requests that fail over off the
    # draining replica must still reconstruct as ONE root whose hop
    # chain stays contiguous across the replay
    live_tracer = Tracer(sample=1.0)
    live_router = Router([mk_engine() for _ in range(replicas)], cfg,
                         tracer=live_tracer)
    live_router.schedule_attach(scale_up_at, newcomer,
                                warmup_seconds=attach_warmup)
    live_router.schedule_detach(scale_down_at, 0)
    t0 = time.perf_counter()
    live_results = live_router.run(fresh_trace(trace))
    live_wall = time.perf_counter() - t0

    live_dropped = [r.id for r in trace if r.id not in live_results
                    or live_results[r.id].finish_reason == "shed"]
    live_identical = not live_dropped and all(
        live_results[r.id].tokens == oracle[r.id] for r in trace)
    live_ttfts = [res.ttft for res in live_results.values()
                  if res.ttft >= 0.0]
    live_tokens = sum(len(r.tokens) for r in live_results.values())

    live_ids = {r.id for r in trace}
    live_spans = [s for s in live_tracer.ring if s["trace"] in live_ids
                  or s["trace"] < 0]
    live_trees = build_trees(live_spans)
    live_gaps = []
    live_trace_complete = len(orphan_spans(live_spans)) == 0
    for r in trace:
        t = live_trees.get(r.id)
        if t is None or t["root"] is None or t["root"]["status"] != "ok":
            live_trace_complete = False
            continue
        gap = trace_sum_gap(t)
        if gap is None or gap > max(0.005, 0.02 * t["root"]["seconds"]):
            live_trace_complete = False
        if gap is not None:
            live_gaps.append(gap)
    live_hops = {f"livescale_hop_{k}": round(v, 3)
                 for k, v in hop_percentiles(live_spans).items()}

    # the live steps through the REAL ledger reader (collector.py):
    # each live_scale record is self-contained, total = drain + warmup
    live_entries = resize_ledger(
        [{"event": LIVE_SCALE, "ts": e["ts"], "action": e["action"],
          "drain_seconds": e["drain_seconds"],
          "warmup_seconds": e["warmup_seconds"]}
         for e in live_router.live_scale_log])
    live_totals = [e["total_seconds"] for e in live_entries]

    # -- GANG arm: the same +1 decision, materialized as a restart --------
    gang_results: Dict[int, object] = {}
    pre = [r for r in trace if r.arrival <= scale_up_at]
    post = [r for r in trace if r.arrival > scale_up_at]
    gang_a = Router([mk_engine() for _ in range(replicas)], cfg)
    g0 = time.perf_counter()
    gang_results.update(gang_a.run(fresh_trace(pre)))
    drain_done = time.perf_counter()
    # the restart window: every engine rebuilt from scratch IN-BAND —
    # this is the outage the live arm exists to delete
    gang_b_engines = [mk_engine() for _ in range(replicas + 1)]
    restart_done = time.perf_counter()
    gang_shift = restart_done - g0
    gang_b = Router(gang_b_engines, cfg)
    gang_results.update(gang_b.run(
        [Request(r.id, list(r.prompt), r.max_new_tokens,
                 arrival=max(0.0, r.arrival - gang_shift))
         for r in post]))
    gang_wall = time.perf_counter() - g0
    gang_drain = max(0.0, (drain_done - g0) - scale_up_at)
    gang_restore = restart_done - drain_done
    gang_total = gang_drain + gang_restore

    gang_dropped = [r.id for r in trace if r.id not in gang_results
                    or gang_results[r.id].finish_reason == "shed"]
    gang_identical = not gang_dropped and all(
        gang_results[r.id].tokens == oracle[r.id] for r in trace)
    # phase-2 TTFTs re-anchored to the ORIGINAL arrival timeline: the
    # queueing a request did at the dead front door is real latency
    gang_ttfts = [gang_results[r.id].ttft for r in pre
                  if gang_results[r.id].ttft >= 0.0]
    for r in post:
        res = gang_results[r.id]
        if res.token_times:
            gang_ttfts.append(
                (gang_shift + res.token_times[0]) - r.arrival)
    gang_tokens = sum(len(r.tokens) for r in gang_results.values())

    ledger_ok = bool(live_totals) and max(live_totals) < gang_total
    ms = lambda v: round(v * 1e3, 3) if v is not None else None  # noqa: E731

    out: Dict[str, object] = {
        "livescale_replicas_start": replicas,
        "livescale_requests": num_requests,
        "livescale_slots": slots,
        "livescale_page_size": page_size,
        "livescale_scale_up_at": scale_up_at,
        "livescale_scale_down_at": scale_down_at,
        "livescale_attaches": sum(1 for e in live_router.live_scale_log
                                  if e["action"] == "attach"),
        "livescale_detaches": sum(1 for e in live_router.live_scale_log
                                  if e["action"] == "detach"),
        "livescale_detached_replicas": live_router.detached_replicas(),
        "livescale_dropped": len(live_dropped),
        "livescale_sheds": live_router.shed_count(),
        "livescale_token_identical": bool(live_identical),
        "livescale_tokens_per_sec": round(live_tokens / live_wall, 1),
        "livescale_wall_seconds": round(live_wall, 3),
        "livescale_ttft_p99_ms": ms(_percentiles(live_ttfts)[99]),
        "livescale_attach_warmup_seconds": round(attach_warmup, 3),
        "livescale_detach_drain_seconds": round(
            next((e["drain_seconds"] for e in live_router.live_scale_log
                  if e["action"] == "detach"), 0.0), 3),
        "livescale_ledger_total_seconds": round(max(live_totals), 3)
                                          if live_totals else None,
        "livescale_compile_pins_held": bool(pins_held(live_router)),
        "livescale_gang_dropped": len(gang_dropped),
        "livescale_gang_token_identical": bool(gang_identical),
        "livescale_gang_tokens_per_sec": round(gang_tokens / gang_wall, 1),
        "livescale_gang_wall_seconds": round(gang_wall, 3),
        "livescale_gang_ttft_p99_ms": ms(_percentiles(gang_ttfts)[99]),
        "livescale_gang_stall_seconds": round(gang_restore, 3),
        "livescale_gang_total_seconds": round(gang_total, 3),
        "livescale_ledger_vs_gang_ok": ledger_ok,
        "livescale_lost_throughput_pct": round(
            100.0 * (1.0 - (live_wall / gang_wall)), 1)
            if gang_wall else None,
        **live_hops,
        "livescale_trace_complete": bool(live_trace_complete),
        "livescale_trace_max_gap_ms": (round(max(live_gaps) * 1e3, 3)
                                       if live_gaps else None),
    }
    log(f"livescale {name}: {num_requests} reqs, +1@{scale_up_at}s / "
        f"-1@{scale_down_at}s: live TTFT p99 "
        f"{out['livescale_ttft_p99_ms']} ms vs gang "
        f"{out['livescale_gang_ttft_p99_ms']} ms; "
        f"{out['livescale_tokens_per_sec']} vs "
        f"{out['livescale_gang_tokens_per_sec']} tokens/sec; ledger "
        f"{out['livescale_ledger_total_seconds']}s live vs "
        f"{out['livescale_gang_total_seconds']}s gang (ok={ledger_ok}); "
        f"dropped={out['livescale_dropped']}, "
        f"sheds={out['livescale_sheds']}, "
        f"token-identical={live_identical}/{gang_identical}, "
        f"pins={out['livescale_compile_pins_held']}")
    return out


def main(argv=None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(prog="tpu-serving-benchmark")
    parser.add_argument("--size", default=None)
    parser.add_argument("--family", default="gpt2",
                        choices=["gpt2", "llama"])
    parser.add_argument("--slots", type=int, default=8)
    parser.add_argument("--num-requests", type=int, default=32)
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--kv-cache-dtype", default=None,
                        choices=[None, "int8"])
    parser.add_argument("--page-size", type=int, default=64)
    parser.add_argument("--num-pages", type=int, default=None,
                        help="physical KV pages (default: every slot's "
                             "worst case)")
    parser.add_argument("--shared-prefix-len", type=int, default=0,
                        help="prepend one seeded system prompt of this "
                             "many tokens to every request (the "
                             "prefix-cache trace)")
    parser.add_argument("--router", action="store_true",
                        help="front-door A/B: the same multi-tenant "
                             "shared-prefix trace through N replicas "
                             "behind the prefix-affinity router with "
                             "affinity ON vs OFF, plus an overload-"
                             "burst shed/recovery leg; gates token "
                             "identity vs the single-engine oracle, "
                             "hit-rate gain, and per-replica compile "
                             "pins")
    parser.add_argument("--livescale", action="store_true",
                        help="live decode-pool scaling A/B: the same "
                             "trace through a ±1 replica cycle done "
                             "live (attach pre-warmed / graceful drain, "
                             "no survivor pause) vs as a gang restart "
                             "(drain, rebuild the whole fleet in-band); "
                             "gates zero drops, token identity both "
                             "arms, and live ledger total < gang total")
    parser.add_argument("--scale-up-at", type=float, default=0.3,
                        help="trace time of the +1 attach step "
                             "(--livescale)")
    parser.add_argument("--scale-down-at", type=float, default=0.8,
                        help="trace time of the -1 drain step "
                             "(--livescale)")
    parser.add_argument("--replicas", type=int, default=2,
                        help="engine replicas behind the router")
    parser.add_argument("--max-inflight", type=int, default=8,
                        help="per-replica in-flight cap (the router's "
                             "admission/shed threshold)")
    parser.add_argument("--disagg", action="store_true",
                        help="disaggregated prefill/decode A/B vs the "
                             "colocated engine: same greedy trace "
                             "through both, TTFT/TPOT p50/p99 each, "
                             "kv_handoff p50/p99, token-identity + "
                             "per-pool compile pins")
    parser.add_argument("--speculative", default=None,
                        choices=[None, "ngram"],
                        help="speculative decoding mode (prompt-lookup "
                             "self-drafting); greedy rows draft, verify "
                             "scores k drafts + bonus token per pass")
    parser.add_argument("--draft-k", type=int, default=4,
                        help="drafted tokens per speculative step")
    parser.add_argument("--compare-spec", action="store_true",
                        help="re-run the trace with speculation "
                             "disabled through the same engine and "
                             "report the no-spec throughput + spec "
                             "speedup + greedy token-identity check")
    parser.add_argument("--no-baseline", action="store_true")
    parser.add_argument("--compare-sync", action="store_true",
                        help="re-run the trace with async_decode=False "
                             "through the same engine and report the "
                             "sync throughput + async speedup + greedy "
                             "token-identity check")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--profile-dir", default=None,
                        help="write an XProf trace of the measured trace "
                             "(warmup excluded) under this directory")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="serve live engine telemetry at "
                             "/metrics on this port (0 = any free port)")
    args = parser.parse_args(argv)
    from ..utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    def headline(name, metrics) -> int:
        # the run_* functions' metrics already name the device and the
        # traced implementations (with_run_report)
        print(json.dumps({"metric": f"{name}_tokens_per_sec",
                          "value": metrics[f"{name}_tokens_per_sec"],
                          "unit": "tokens/sec", **metrics,
                          "compile_cache_dir": cache_dir}))
        return 0

    if args.livescale:
        metrics = run_livescale_benchmark(
            size=args.size, family=args.family, replicas=args.replicas,
            slots=args.slots, num_requests=args.num_requests,
            dtype_name=args.dtype, page_size=args.page_size,
            num_pages=args.num_pages,
            shared_prefix_len=args.shared_prefix_len or 32,
            max_inflight=args.max_inflight,
            scale_up_at=args.scale_up_at,
            scale_down_at=args.scale_down_at, seed=args.seed)
        return headline("livescale", metrics)
    if args.router:
        metrics = run_router_benchmark(
            size=args.size, family=args.family, replicas=args.replicas,
            slots=args.slots, num_requests=args.num_requests,
            dtype_name=args.dtype, page_size=args.page_size,
            num_pages=args.num_pages,
            shared_prefix_len=args.shared_prefix_len or 32,
            max_inflight=args.max_inflight, seed=args.seed)
        return headline("router", metrics)
    if args.disagg:
        metrics = run_disagg_benchmark(
            size=args.size, family=args.family, slots=args.slots,
            num_requests=args.num_requests, dtype_name=args.dtype,
            kv_cache_dtype=args.kv_cache_dtype,
            page_size=args.page_size, num_pages=args.num_pages,
            seed=args.seed)
        return headline("disagg", metrics)
    metrics = run_serving_benchmark(
        size=args.size, family=args.family, slots=args.slots,
        num_requests=args.num_requests, dtype_name=args.dtype,
        temperature=args.temperature, kv_cache_dtype=args.kv_cache_dtype,
        page_size=args.page_size, num_pages=args.num_pages,
        shared_prefix_len=args.shared_prefix_len,
        speculative=args.speculative, draft_k=args.draft_k,
        baseline=not args.no_baseline, compare_sync=args.compare_sync,
        compare_spec=args.compare_spec, seed=args.seed,
        profile_dir=args.profile_dir, metrics_port=args.metrics_port)
    return headline("serving", metrics)


if __name__ == "__main__":
    import sys
    sys.exit(main())
