"""Serving benchmark: continuous batching vs sequential generate().

Replays a seeded mixed-length greedy request trace through the serving
engine (serve/engine.py) and reports what a serving frontend cares about:

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

One set-up (`_Workload`: the model, its params, the seeded trace and its
warm-up requests, warmed engines) serves four topologies: one engine
(`run_serving_benchmark`), a prefill pool beside a decode pool
(`--disagg`), replicas behind the router (`--router`) and a fleet that
changes size mid-trace (`--livescale`).
"""
from __future__ import annotations

import time
from typing import Dict, NamedTuple, Optional, Tuple

from ._report import device_ids, with_run_report


class _Shape(NamedTuple):
    """What a topology replays: prompt lengths and new-token counts a
    request draws from, the engine's prefill chunk buckets, and the
    tokens of tenant prefix before every prompt (0 = none)."""
    prompt_grid: Tuple[int, ...]
    new_grid: Tuple[int, ...]
    chunk_buckets: Tuple[int, ...]
    prefix_len: int = 0


_SERVING = _Shape((32, 64, 128), (32, 64), (32, 128))
# skewed long: long prompts are the TTFT/TPOT interference the split removes
_DISAGG = _Shape((64, 256, 384), (16, 32), (64, 128))
# every request opens with one of NUM_TENANTS seeded system prompts
_FLEET = _Shape((16, 32), (8, 16), (16, 64), prefix_len=32)
NUM_TENANTS = 4
SEED = 0                    # of the request stream; weights are PRNGKey(0)
REPLICAS = 2
MAX_INFLIGHT = 8            # per replica: the router's shed threshold
ARRIVAL_GAP = 0.15          # seconds between a fleet trace's arrivals
SCALE_UP_AT, SCALE_DOWN_AT = 0.3, 0.8      # trace time of the +1 / -1


def _percentiles(xs, ps=(50, 99)):
    import numpy as np
    if not xs:
        return {p: None for p in ps}
    return {p: float(np.percentile(np.asarray(xs), p)) for p in ps}


def _ms(v, nd=3):
    return round(v * 1e3, nd) if v is not None else None


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
    return {f"{prefix}_ttft_p50_ms": _ms(ttft[50], 2),
            f"{prefix}_ttft_p99_ms": _ms(ttft[99], 2),
            f"{prefix}_tpot_p50_ms": _ms(tpot[50]),
            f"{prefix}_tpot_p99_ms": _ms(tpot[99])}


def _fresh(reqs, shift: float = 0.0):
    """Copies of `reqs` for one more engine, each due `shift` seconds
    earlier than it was (never before 0: inf makes all due at once)."""
    from ..serve import Request
    return [Request(r.id, list(r.prompt), r.max_new_tokens,
                    arrival=max(0.0, r.arrival - shift)) for r in reqs]


def _timed(engine, reqs):
    t0 = time.perf_counter()
    results = engine.run(reqs)
    return results, time.perf_counter() - t0


def _new_tokens(results) -> int:
    return sum(len(r.tokens) for r in results.values())


def _same_tokens(trace, results, reference) -> bool:
    """Every request of `trace` came back from `results` (not shed) with
    `reference`'s tokens — a {id: Result} or the oracle's {id: tokens}."""
    def tokens(r):
        return getattr(r, "tokens", r)
    return all(r.id in results and results[r.id].finish_reason != "shed"
               and results[r.id].tokens == tokens(reference[r.id])
               for r in trace)


def _span_gate(tracer, trace, prefix) -> Dict[str, object]:
    """A fleet's tracing gate: every request of `trace` reconstructs as
    ONE root span whose hop durations sum to its end-to-end latency
    within tolerance (failovers included), and no span is an orphan."""
    from ..telemetry.trace import (build_trees, hop_percentiles,
                                   orphan_spans, trace_sum_gap)
    ids = {r.id for r in trace}
    spans = [s for s in tracer.ring if s["trace"] in ids or s["trace"] < 0]
    trees = build_trees(spans)
    gaps = []
    complete = not orphan_spans(spans)
    for r in trace:
        t = trees.get(r.id)
        if t is None or t["root"] is None or t["root"]["status"] != "ok":
            complete = False
            continue
        gap = trace_sum_gap(t)
        if gap is None or gap > max(0.005, 0.02 * t["root"]["seconds"]):
            complete = False
        if gap is not None:
            gaps.append(gap)
    return {**{f"{prefix}_hop_{k}": round(v, 3)
               for k, v in hop_percentiles(spans).items()},
            f"{prefix}_trace_complete": bool(complete),
            f"{prefix}_trace_max_gap_ms": _ms(max(gaps)) if gaps else None}


class _Workload:
    """What the four topologies share: the model and its params, the
    seeded trace, the warm-up requests, and engines built and warmed
    from them. SEED draws the same requests in the same order every
    run: the trace first, then one warm-up request per prompt length,
    then whatever `request()` is asked for afterwards."""

    def __init__(self, family: str, size: Optional[str], slots: int,
                 num_requests: int, page_size: int, shape: _Shape,
                 arrival_gap: float = 0.0):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..models import create_lm
        from ..parallel import MeshConfig, make_mesh
        from ..parallel.sharding import shard_init
        from ..serve import Request

        self.slots, self.page_size, self.shape = slots, page_size, shape
        # the Pallas fast path on TPU, the dense oracle elsewhere
        # (interpret-mode pallas inside the step would simulate, not
        # measure). The record's `decode_impl` is what the step traced.
        self.decode_kernel = jax.default_backend() == "tpu"
        # cache length: fits the longest request, rounded up so the decode
        # kernel's k-tile divides it (decode_block_k caps at max_len, so any
        # multiple of 128 — or anything <= 128 that the tile equals — works)
        need = (shape.prefix_len + max(shape.prompt_grid)
                + max(shape.new_grid))
        max_len = need if need <= 128 else -(-need // 128) * 128
        if max_len % page_size:
            max_len = -(-max_len // page_size) * page_size
        self.name = f"{family}-{size}" if size else family
        self.model = create_lm(self.name, dtype=jnp.bfloat16,
                               decode_kernel=self.decode_kernel,
                               max_len=max_len)
        mesh = make_mesh(MeshConfig(dp=jax.device_count()))
        variables, _ = shard_init(
            self.model, mesh, jax.random.PRNGKey(0),
            jnp.zeros((1, min(shape.prompt_grid)), jnp.int32))
        self.params = variables["params"]

        self._vocab = self.model.config.vocab_size
        self._rs = np.random.RandomState(SEED)
        self._prefixes = [
            self._rs.randint(0, self._vocab, (shape.prefix_len,)).tolist()
            for _ in range(NUM_TENANTS if shape.prefix_len else 1)]
        self.trace = [self.request(i, i * arrival_gap)
                      for i in range(num_requests)]
        # one request per distinct prompt length covers every prefill
        # bucket the trace can hit, and the step program
        self.warm = [
            Request(10_000 + j, self._rs.randint(
                0, self._vocab, (shape.prefix_len + p,)).tolist(), 2)
            for j, p in enumerate(sorted(set(shape.prompt_grid)))]

    def request(self, i: int, arrival: float = 0.0):
        """The stream's next greedy request. Tenants cycle round-robin,
        so consecutive same-tenant arrivals sit NUM_TENANTS *
        ARRIVAL_GAP apart: the first has time to prefill and PUBLISH
        its prefix pages before the second one's dispatch probes."""
        from ..serve import Request
        rs = self._rs
        p = int(rs.choice(self.shape.prompt_grid))
        n = int(rs.choice(self.shape.new_grid))
        prefix = self._prefixes[i % len(self._prefixes)]
        return Request(
            id=i, prompt=prefix + rs.randint(0, self._vocab, (p,)).tolist(),
            max_new_tokens=n, arrival=arrival)

    def engine(self, cls=None, speculative=None, **kw):
        """A `cls` (ServingEngine) over the shared params, every program
        the trace uses compiled by the warm-up requests, then reset: a
        measured trace is all steady state."""
        from ..serve import EngineConfig, ServingEngine
        e = (cls or ServingEngine)(self.model, self.params, EngineConfig(
            slots=self.slots, chunk_buckets=self.shape.chunk_buckets,
            decode_kernel=self.decode_kernel, page_size=self.page_size,
            speculative=speculative), **kw)
        e.run(_fresh(self.warm))
        e.reset()
        return e

    def oracle(self) -> Dict[int, list]:
        """Single-engine greedy tokens: continuous batching is token-exact
        whatever the batch, so ONE engine over the whole trace defines
        the tokens of every fleet shape."""
        return {rid: res.tokens for rid, res in
                self.engine().run(
                    _fresh(self.trace, shift=float("inf"))).items()}

    def pins_held(self, *counts) -> bool:
        """step <= the 3 sample_slots modes, prefill <= the buckets."""
        return all(c["step"] <= 3
                   and c["prefill"] <= len(self.shape.chunk_buckets)
                   for c in counts)

    def fleet_pins_held(self, *routers) -> bool:
        return self.pins_held(*(rep.engine.compile_counts()
                                for router in routers
                                for rep in router.replicas))


@with_run_report
def run_serving_benchmark(
    size: Optional[str] = None,
    family: str = "gpt2",
    slots: int = 8,
    num_requests: int = 32,
    page_size: int = 64,
    speculative: Optional[str] = None,
    baseline: bool = True,
    compare_sync: bool = False,
    compare_spec: bool = False,
    profile_dir: Optional[str] = None,
    metrics_port: Optional[int] = None,
) -> Dict[str, object]:
    """Returns a flat dict of serving metrics (see module docstring).
    The engine's KV cache is a pool of `page_size`-token pages, every
    slot's worst case of them.

    `compare_sync` re-runs the identical trace through the SAME engine
    with the double-buffered dispatch disabled (EngineConfig.async_decode
    = False, reset between — zero extra compiles) and reports the sync
    throughput, the async speedup (best-of-2 walls per mode, runs
    alternated — see the inline comment), and a token-identity check.

    `speculative` ("ngram") turns on speculative decoding; the report
    adds the engine's acceptance rate and effective tokens per row-step.
    `compare_spec` re-runs the identical trace through the SAME engine
    with speculation disabled (reset between — zero extra compiles) and
    reports the non-spec throughput/TPOT, the spec speedup, and a
    token-identity check (speculation changes WHEN tokens compute,
    never WHICH).

    `profile_dir` captures an XProf trace of the MEASURED trace only
    (warmup excluded, trace serialization after the closing timestamp —
    same discipline as the train benchmarks' WindowProfiler).
    `metrics_port` starts a worker /metrics endpoint over the engine's
    live telemetry (0 = any free port) so the TTFT/TPOT/occupancy series
    are scrapeable while the trace replays."""
    import jax
    import jax.numpy as jnp

    from ..models import generate
    from ..telemetry import WorkerTelemetry
    from ..telemetry.trace import (Tracer, build_trees, hop_percentiles,
                                   trace_sum_gap)
    from ..utils.profiling import WindowProfiler

    w = _Workload(family, size, slots, num_requests, page_size, _SERVING)
    trace = w.trace
    wtel = WorkerTelemetry()
    if metrics_port is not None:
        print(f"worker /metrics listening on port "
              f"{wtel.serve(port=metrics_port).port}")
    # in-memory ring only (no sink file): the per-hop breakdown and the
    # completeness gate read the ring after the measured run
    tracer = Tracer(sample=1.0)
    # engine construction + warm-up: every program the measured trace
    # uses compiles in here, none after (serving_no_recompile)
    warm_t0 = time.perf_counter()
    engine = w.engine(speculative=speculative, telemetry=wtel.serving,
                      tracer=tracer)
    warmup_seconds = time.perf_counter() - warm_t0
    warm_counts = engine.compile_counts()
    param_ids = device_ids(engine.params)
    cache_ids = device_ids(engine.cache)
    print(f"serving placement: params on devices {param_ids}, KV cache on "
          f"devices {cache_ids} of {jax.device_count()} visible")

    profiler = WindowProfiler(profile_dir, print)
    profiler.start()
    try:
        results, wall = _timed(engine, trace)
    finally:
        # stop AFTER the closing timestamp: xplane serialization is real
        # I/O and must never be charged to serving throughput
        profiler.stop_if_active()
        wtel.close()
    total_new = _new_tokens(results)
    tps = total_new / wall
    counts = engine.compile_counts()
    no_recompile = w.pins_held(counts)
    # every request came back with the token count it asked for (the
    # trace sets no eos_id, so "length" is the only way to finish)
    complete = all(r.id in results
                   and len(results[r.id].tokens) == r.max_new_tokens
                   for r in trace)
    # host_gap percentiles BEFORE any sync rerun below touches the same
    # histogram: these must describe the measured (async) trace only
    gap = wtel.serving.host_gap_seconds
    gap50_ms = _ms(gap.percentile(50)) if gap.count else None
    gap99_ms = _ms(gap.percentile(99)) if gap.count else None
    # per-hop latency breakdown + completeness gate, snapshotted BEFORE
    # any compare_* rerun replays the same request ids through the
    # tracer: every measured request must have one root span whose hop
    # durations tile its end-to-end latency
    trace_spans = list(tracer.ring)
    trees = build_trees(trace_spans)
    roots = [trees[r.id] for r in trace
             if trees.get(r.id) is not None
             and trees[r.id]["root"] is not None]
    trace_complete = (len(roots) == len(trace) and all(
        t["root"]["status"] == "ok" for t in roots))
    gaps = [g for g in map(trace_sum_gap, roots) if g is not None]
    alloc = engine.page_allocator

    out: Dict[str, object] = {
        "serving_tokens_per_sec": round(tps, 1),
        "serving_requests": num_requests,
        "serving_requests_complete": bool(complete),
        "serving_slots": slots,
        "serving_total_new_tokens": total_new,
        "serving_wall_seconds": round(wall, 3),
        **_latency_fields(results.values()),
        "serving_host_gap_p50_ms": gap50_ms,
        "serving_host_gap_p99_ms": gap99_ms,
        **{f"serving_hop_{k}": round(v, 3)
           for k, v in hop_percentiles(trace_spans).items()},
        "serving_trace_complete": bool(trace_complete),
        "serving_trace_max_gap_ms": _ms(max(gaps)) if gaps else None,
        "serving_step_compiles": counts["step"],
        "serving_prefill_compiles": counts["prefill"],
        "serving_no_recompile": bool(no_recompile),
        "serving_compiles_after_warmup": (
            sum(counts.values()) - sum(warm_counts.values())),
        "serving_warmup_seconds": round(warmup_seconds, 3),
        "serving_param_device_ids": param_ids,
        "serving_cache_device_ids": cache_ids,
        "serving_decode_kernel": bool(w.decode_kernel),
        "serving_async_decode": bool(engine.config.async_decode),
        "serving_cache_donated": engine.donates_cache,
        # the allocator, read BEFORE any compare_* rerun resets it
        "serving_page_size": page_size,
        "serving_pages_total": alloc.usable,
        "serving_pages_in_use_peak": engine.pages_in_use_peak,
        "serving_occupancy_peak": engine.occupancy_peak,
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
            "serving_spec_draft_k": engine.config.draft_k,
            "serving_spec_proposed": int(spec["proposed"]),
            "serving_spec_accepted": int(spec["accepted"]),
            "serving_spec_acceptance_rate":
                round(spec["acceptance_rate"], 4),
            "serving_spec_effective_tokens_per_step":
                round(spec["effective_tokens_per_step"], 3),
            "serving_verify_compiles": counts["verify"],
        })
        print(f"speculative ({speculative}, k={engine.config.draft_k}): "
              f"acceptance {out['serving_spec_acceptance_rate']} "
              f"({spec['accepted']}/{spec['proposed']} drafts), "
              f"{out['serving_spec_effective_tokens_per_step']} effective "
              f"tokens/row-step over {spec['verify_steps']} verify steps, "
              f"{counts['verify']} verify compiles")
    print(f"serving {w.name}: {num_requests} reqs over {slots} slots, "
          f"{alloc.usable} pages x {page_size} tokens (peak "
          f"{engine.pages_in_use_peak} pages / {engine.occupancy_peak} "
          f"slots in use): {tps:.0f} new tokens/sec, TTFT p50/p99 "
          f"{out['serving_ttft_p50_ms']}/{out['serving_ttft_p99_ms']} ms, "
          f"TPOT p50/p99 {out['serving_tpot_p50_ms']}/"
          f"{out['serving_tpot_p99_ms']} ms, recompile-free="
          f"{no_recompile}")

    if compare_spec:
        # spec vs no-spec on the IDENTICAL seeded trace through the
        # same engine (reset between — same compiled step/prefill
        # programs, the verify program simply sits unused). Token
        # identity is the exactness gate.
        if speculative is None:
            raise ValueError("compare_spec requires speculative")
        engine.config.speculative = None
        engine.reset()
        base_results, base_wall = _timed(engine, trace)
        engine.config.speculative = speculative
        base_tps = _new_tokens(base_results) / base_wall
        base_tpot = {k: v for k, v in _latency_fields(
            base_results.values(), "serving_nospec").items() if "tpot" in k}
        spec_identical = _same_tokens(trace, results, base_results)
        out.update({
            "serving_nospec_tokens_per_sec": round(base_tps, 1),
            "serving_nospec_wall_seconds": round(base_wall, 3),
            **base_tpot,
            "serving_spec_speedup": (round(tps / base_tps, 3)
                                     if base_tps else None),
            "serving_spec_greedy_identical": bool(spec_identical),
        })
        print(f"spec A/B: {tps:.0f} spec vs {base_tps:.0f} no-spec new "
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
            return _timed(engine, trace)

        sync_results, sync_wall = timed_run(False)
        _, async_wall2 = timed_run(True)
        _, sync_wall2 = timed_run(False)
        engine.config.async_decode = True
        best_sync = min(sync_wall, sync_wall2)
        sync_tps = _new_tokens(sync_results) / best_sync
        async_tps = total_new / min(wall, async_wall2)
        greedy_identical = _same_tokens(trace, results, sync_results)
        out.update({
            "serving_sync_tokens_per_sec": round(sync_tps, 1),
            "serving_sync_wall_seconds": round(best_sync, 3),
            "serving_async_speedup": (round(async_tps / sync_tps, 3)
                                      if sync_tps else None),
            "serving_async_greedy_identical": bool(greedy_identical),
        })
        print(f"sync-decode A/B (best-of-2 each): {sync_tps:.0f} sync vs "
              f"{async_tps:.0f} async new tokens/sec -> "
              f"{out['serving_async_speedup']}x, greedy token-identical="
              f"{greedy_identical}")

    if baseline:
        # trace-sequential generate(): warm one compile per (P, N) shape
        # class, then replay the identical trace one request at a time.
        # Same params, greedy like the trace.
        def run_one(req):
            return generate(w.model, w.params,
                            jnp.asarray([list(req.prompt)]),
                            req.max_new_tokens)

        shapes = {(len(r.prompt), r.max_new_tokens): r for r in trace}
        for r in shapes.values():
            int(run_one(r).tokens[0, -1])       # compile + wait
        t0 = time.perf_counter()
        for r in trace:
            o = run_one(r)
        int(o.tokens[0, -1])                    # waits for the last call
        base_wall = time.perf_counter() - t0
        base_tps = sum(r.max_new_tokens for r in trace) / base_wall
        speedup = tps / base_tps if base_tps else None
        out.update({
            "sequential_tokens_per_sec": round(base_tps, 1),
            "sequential_wall_seconds": round(base_wall, 3),
            "serving_vs_sequential": (round(speedup, 2)
                                      if speedup else None),
        })
        print(f"sequential generate() baseline: {base_tps:.0f} new "
              f"tokens/sec -> continuous batching {speedup:.2f}x")
    return out


@with_run_report
def run_disagg_benchmark(
    size: Optional[str] = None,
    family: str = "gpt2",
    slots: int = 8,
    num_requests: int = 24,
    page_size: int = 64,
) -> Dict[str, object]:
    """Disaggregated prefill/decode A/B vs the colocated engine at equal
    chip count: the same long-prompt-heavy greedy trace replays through
    a colocated ServingEngine and a DisaggEngine built from the SAME
    params and config, reporting TTFT/TPOT p50/p99 for both, kv_handoff
    p50/p99, and the per-pool compile pins (prefill pool never compiles
    step, decode pool never compiles prefill). Greedy: temperature 0 is
    the token-exact parity regime, so the A/B also asserts token
    identity.

    On CPU smoke the two pools are host devices and the latency split is
    structural only — token identity + pins are the gate there; the
    TTFT/TPOT win is measured on real hardware (ROADMAP follow-up)."""
    from ..serve import DisaggEngine
    from ..telemetry.trace import (Tracer, build_trees, hop_name,
                                   hop_percentiles)

    w = _Workload(family, size, slots, num_requests, page_size, _DISAGG)
    trace = w.trace
    tracer = Tracer(sample=1.0)
    coloc_results, coloc_wall = _timed(w.engine(), trace)
    disagg = w.engine(DisaggEngine, tracer=tracer)
    disagg_results, disagg_wall = _timed(disagg, trace)

    identical = _same_tokens(trace, disagg_results, coloc_results)
    counts = disagg.compile_counts()
    pre, dec = counts["prefill_pool"], counts["decode_pool"]
    pins = (pre["step"] == 0 and dec["prefill"] == 0 and w.pins_held(
        {"prefill": pre["prefill"], "step": dec["step"]}))
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
        hops = [s for s in t["spans"] if s.get("parent") is not None]
        if not {"prefill", "kv_handoff", "decode"} <= set(
                map(hop_name, hops)):
            trace_complete = False
        trace_handoff_pages += sum(
            int((s.get("attrs") or {}).get("pages", 0))
            for s in hops if hop_name(s) == "kv_handoff")

    out: Dict[str, object] = {
        "disagg_tokens_per_sec": round(
            _new_tokens(disagg_results) / disagg_wall, 1),
        "disagg_wall_seconds": round(disagg_wall, 3),
        **_latency_fields(disagg_results.values(), "disagg"),
        "coloc_tokens_per_sec": round(
            _new_tokens(coloc_results) / coloc_wall, 1),
        "coloc_wall_seconds": round(coloc_wall, 3),
        **_latency_fields(coloc_results.values(), "coloc"),
        "disagg_kv_handoff_p50_ms": _ms(handoff[50]),
        "disagg_kv_handoff_p99_ms": _ms(handoff[99]),
        "disagg_kv_handoff_pages_total": disagg.transfer.pages_moved,
        "disagg_handoffs": len(disagg.handoff_log),
        **{f"disagg_hop_{k}": round(v, 3)
           for k, v in hop_percentiles(spans).items()},
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
    print(f"disagg {w.name}: {num_requests} reqs, TTFT p50/p99 "
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
    slots: int = 4,
    num_requests: int = 24,
    page_size: int = 16,
) -> Dict[str, object]:
    """Front-door A/B: the same seeded multi-tenant shared-system-prompt
    trace through REPLICAS engine replicas behind the Router, affinity
    ON vs OFF (pure load-aware), plus an overload burst.

    The trace draws each request's prompt as one of NUM_TENANTS seeded
    system prefixes plus a per-request tail, arrivals ARRIVAL_GAP
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
    from ..serve import Router, RouterConfig
    from ..telemetry.trace import Tracer

    w = _Workload(family, size, slots, num_requests, page_size, _FLEET,
                  arrival_gap=ARRIVAL_GAP)
    trace = w.trace
    oracle = w.oracle()

    def replica_hit_rate(router):
        hits = sum(rep.engine.page_allocator.hits for rep in router.replicas)
        miss = sum(rep.engine.page_allocator.misses
                   for rep in router.replicas)
        return hits / (hits + miss) if hits + miss else 0.0, hits

    def fleet_run(affinity, tracer=None):
        router = Router([w.engine() for _ in range(REPLICAS)],
                        RouterConfig(max_inflight=MAX_INFLIGHT,
                                     affinity=affinity),
                        tracer=tracer)
        return (router, *_timed(router, _fresh(trace)))

    # trace the measured (affinity-ON) arm at sample=1.0: every request
    # must reconstruct into a queue_wait -> admission -> prefill ->
    # decode span tree whose hop durations sum to the root e2e within
    # tolerance — the front-door-to-final-token completeness gate
    on_tracer = Tracer(sample=1.0)
    on_router, on_results, on_wall = fleet_run(True, on_tracer)
    off_router, off_results, off_wall = fleet_run(False)

    def adm_ttft_p50(results):
        # admission-relative: a prefix hit skips prefill work, not the
        # queue
        return _percentiles([r.token_times[0] - r.admitted_at
                             for r in results.values()
                             if r.token_times])[50]

    identical = (_same_tokens(trace, on_results, oracle)
                 and _same_tokens(trace, off_results, oracle))
    on_rate, on_hits = replica_hit_rate(on_router)
    off_rate, _ = replica_hit_rate(off_router)
    on_p50, off_p50 = adm_ttft_p50(on_results), adm_ttft_p50(off_results)
    # "no worse" with 20% headroom: the structural win is skipped prefill
    # work; single-run CPU noise must not flip a smoke verdict
    ttft_ok = (on_p50 is not None and off_p50 is not None
               and on_p50 <= off_p50 * 1.2)

    # overload burst on a fresh fleet with a tight in-flight cap: every
    # burst request is due at once, so dispatch fills replicas*cap slots
    # and front-door-sheds the rest BEFORE any replica queues them; the
    # late recovery wave must then land entirely on drained replicas
    burst_cap = 2
    burst_n = REPLICAS * burst_cap + 4
    burst = [w.request(1_000 + i, 0.0) for i in range(burst_n)]
    recovery = [w.request(2_000 + i, 2.5) for i in range(REPLICAS)]
    burst_router = Router([w.engine() for _ in range(REPLICAS)],
                          RouterConfig(max_inflight=burst_cap))
    burst_results = burst_router.run(_fresh(burst + recovery))
    burst_sheds = sum(1 for r in burst
                      if burst_results[r.id].finish_reason == "shed")
    recovery_clean = all(
        burst_results[r.id].finish_reason in ("eos", "length")
        for r in recovery)

    out: Dict[str, object] = {
        "router_replicas": REPLICAS,
        "router_requests": num_requests,
        "router_slots": slots,
        "router_max_inflight": MAX_INFLIGHT,
        "router_page_size": page_size,
        "router_shared_prefix_len": _FLEET.prefix_len,
        "router_num_tenants": NUM_TENANTS,
        "router_tokens_per_sec": round(_new_tokens(on_results) / on_wall, 1),
        "router_wall_seconds": round(on_wall, 3),
        "router_offered_rps": round(1.0 / ARRIVAL_GAP, 2),
        **_latency_fields(on_results.values(), prefix="router"),
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
        "router_affinity_adm_ttft_p50_ms": _ms(on_p50),
        "router_noaffinity_adm_ttft_p50_ms": _ms(off_p50),
        "router_affinity_ttft_ok": bool(ttft_ok),
        "router_noaffinity_wall_seconds": round(off_wall, 3),
        "router_burst_requests": burst_n,
        "router_burst_sheds": burst_sheds,
        "router_burst_recovered": len(recovery),
        "router_burst_recovery_clean": bool(recovery_clean),
        "router_compile_pins_held": w.fleet_pins_held(
            on_router, off_router, burst_router),
        **_span_gate(on_tracer, trace, "router"),
    }
    print(f"router {w.name}: {num_requests} reqs over {REPLICAS}x{slots} "
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
    slots: int = 4,
    num_requests: int = 12,
    page_size: int = 16,
) -> Dict[str, object]:
    """Live decode-pool scaling vs gang restart: the SAME seeded trace
    through a ±1 replica cycle both ways.

    LIVE arm: a REPLICAS-wide fleet takes one +1 step (a pre-warmed
    engine attaches at SCALE_UP_AT; build + warmup happen OUT of the
    trace clock — production prewarns out of band, which is live
    scaling's whole point) and one -1 step (replica 0 gracefully drains
    at SCALE_DOWN_AT: queued requests fail over to survivors,
    residents finish in place, pages/slots verified reclaimed). No
    survivor pauses, nothing recompiles.

    GANG arm: the same decision at SCALE_UP_AT materialized the old
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
    from ..serve import Router, RouterConfig
    from ..telemetry.collector import resize_ledger
    from ..telemetry.events import LIVE_SCALE
    from ..telemetry.trace import Tracer

    w = _Workload(family, size, slots, num_requests, page_size, _FLEET,
                  arrival_gap=ARRIVAL_GAP)
    trace = w.trace
    oracle = w.oracle()
    cfg = RouterConfig(max_inflight=MAX_INFLIGHT)

    def dropped(results):
        return sum(1 for r in trace if r.id not in results
                   or results[r.id].finish_reason == "shed")

    # -- LIVE arm: ±1 mid-trace, fleet never pauses -----------------------
    # the +1 engine is built and warmed OUT of the trace clock; only the
    # measured cost rides into the ledger as the step's warmup phase
    warm_t0 = time.perf_counter()
    newcomer = w.engine()
    attach_warmup = time.perf_counter() - warm_t0
    # trace the live arm end to end: requests that fail over off the
    # draining replica must still reconstruct as ONE root whose hop
    # chain stays contiguous across the replay
    live_tracer = Tracer(sample=1.0)
    live_router = Router([w.engine() for _ in range(REPLICAS)], cfg,
                         tracer=live_tracer)
    live_router.schedule_attach(SCALE_UP_AT, newcomer,
                                warmup_seconds=attach_warmup)
    live_router.schedule_detach(SCALE_DOWN_AT, 0)
    live_results, live_wall = _timed(live_router, _fresh(trace))
    live_ttfts = [res.ttft for res in live_results.values()
                  if res.ttft >= 0.0]
    scale_log = live_router.live_scale_log

    # the live steps through the REAL ledger reader (collector.py):
    # each live_scale record is self-contained, total = drain + warmup
    live_totals = [e["total_seconds"] for e in resize_ledger(
        [{"event": LIVE_SCALE, "ts": e["ts"], "action": e["action"],
          "drain_seconds": e["drain_seconds"],
          "warmup_seconds": e["warmup_seconds"]} for e in scale_log])]

    # -- GANG arm: the same +1 decision, materialized as a restart --------
    pre = [r for r in trace if r.arrival <= SCALE_UP_AT]
    post = [r for r in trace if r.arrival > SCALE_UP_AT]
    gang_a = Router([w.engine() for _ in range(REPLICAS)], cfg)
    g0 = time.perf_counter()
    gang_results = dict(gang_a.run(_fresh(pre)))
    drain_done = time.perf_counter()
    # the restart window: every engine rebuilt from scratch IN-BAND —
    # this is the outage the live arm exists to delete
    gang_b = Router([w.engine() for _ in range(REPLICAS + 1)], cfg)
    restart_done = time.perf_counter()
    gang_shift = restart_done - g0
    gang_results.update(gang_b.run(_fresh(post, shift=gang_shift)))
    gang_wall = time.perf_counter() - g0
    gang_drain = max(0.0, (drain_done - g0) - SCALE_UP_AT)
    gang_restore = restart_done - drain_done
    gang_total = gang_drain + gang_restore
    # phase-2 TTFTs re-anchored to the ORIGINAL arrival timeline: the
    # queueing a request did at the dead front door is real latency
    gang_ttfts = [gang_results[r.id].ttft for r in pre
                  if gang_results[r.id].ttft >= 0.0]
    gang_ttfts += [(gang_shift + gang_results[r.id].token_times[0])
                   - r.arrival for r in post
                   if gang_results[r.id].token_times]

    ledger_ok = bool(live_totals) and max(live_totals) < gang_total
    live_identical = _same_tokens(trace, live_results, oracle)
    gang_identical = _same_tokens(trace, gang_results, oracle)

    out: Dict[str, object] = {
        "livescale_replicas_start": REPLICAS,
        "livescale_requests": num_requests,
        "livescale_slots": slots,
        "livescale_page_size": page_size,
        "livescale_scale_up_at": SCALE_UP_AT,
        "livescale_scale_down_at": SCALE_DOWN_AT,
        "livescale_attaches": sum(1 for e in scale_log
                                  if e["action"] == "attach"),
        "livescale_detaches": sum(1 for e in scale_log
                                  if e["action"] == "detach"),
        "livescale_detached_replicas": live_router.detached_replicas(),
        "livescale_dropped": dropped(live_results),
        "livescale_sheds": live_router.shed_count(),
        "livescale_token_identical": bool(live_identical),
        "livescale_tokens_per_sec": round(
            _new_tokens(live_results) / live_wall, 1),
        "livescale_wall_seconds": round(live_wall, 3),
        "livescale_ttft_p99_ms": _ms(_percentiles(live_ttfts)[99]),
        "livescale_attach_warmup_seconds": round(attach_warmup, 3),
        "livescale_detach_drain_seconds": round(
            next((e["drain_seconds"] for e in scale_log
                  if e["action"] == "detach"), 0.0), 3),
        "livescale_ledger_total_seconds": round(max(live_totals), 3)
                                          if live_totals else None,
        "livescale_compile_pins_held": w.fleet_pins_held(live_router),
        "livescale_gang_dropped": dropped(gang_results),
        "livescale_gang_token_identical": bool(gang_identical),
        "livescale_gang_tokens_per_sec": round(
            _new_tokens(gang_results) / gang_wall, 1),
        "livescale_gang_wall_seconds": round(gang_wall, 3),
        "livescale_gang_ttft_p99_ms": _ms(_percentiles(gang_ttfts)[99]),
        "livescale_gang_stall_seconds": round(gang_restore, 3),
        "livescale_gang_total_seconds": round(gang_total, 3),
        "livescale_ledger_vs_gang_ok": ledger_ok,
        "livescale_lost_throughput_pct": round(
            100.0 * (1.0 - (live_wall / gang_wall)), 1)
            if gang_wall else None,
        **_span_gate(live_tracer, trace, "livescale"),
    }
    print(f"livescale {w.name}: {num_requests} reqs, +1@{SCALE_UP_AT}s / "
          f"-1@{SCALE_DOWN_AT}s: live TTFT p99 "
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
    parser.add_argument("--page-size", type=int, default=64)
    parser.add_argument("--router", action="store_true",
                        help="front-door A/B: the same multi-tenant "
                             "shared-prefix trace through 2 replicas "
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
    parser.add_argument("--profile-dir", default=None,
                        help="write an XProf trace of the measured trace "
                             "(warmup excluded) under this directory")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="serve live engine telemetry at "
                             "/metrics on this port (0 = any free port)")
    args = parser.parse_args(argv)
    from ..utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    shared = dict(size=args.size, family=args.family, slots=args.slots,
                  num_requests=args.num_requests, page_size=args.page_size)
    if args.livescale:
        name, metrics = "livescale", run_livescale_benchmark(**shared)
    elif args.router:
        name, metrics = "router", run_router_benchmark(**shared)
    elif args.disagg:
        name, metrics = "disagg", run_disagg_benchmark(**shared)
    else:
        name, metrics = "serving", run_serving_benchmark(
            **shared, speculative=args.speculative,
            baseline=not args.no_baseline, compare_sync=args.compare_sync,
            compare_spec=args.compare_spec, profile_dir=args.profile_dir,
            metrics_port=args.metrics_port)
    # the run_* functions' metrics already name the device and the
    # traced implementations (with_run_report)
    print(json.dumps({"metric": f"{name}_tokens_per_sec",
                      "value": metrics[f"{name}_tokens_per_sec"],
                      "unit": "tokens/sec", **metrics,
                      "compile_cache_dir": cache_dir}))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
