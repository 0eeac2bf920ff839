"""Speculative decoding tests (serve/engine.py multi-token verify).

The contract under test: speculation changes WHEN tokens are computed,
never WHICH. A greedy request served with speculative drafting +
batched verify must be BITWISE token-exact against the same request
through the non-speculative engine — in dense and kernel attention, in
contiguous and paged KV (int8 included), across retire/reuse and
reset(). On top of that: the no-recompile contract (at most the two
bucketed verify widths, held across reset + replay), the adversarial
drafter bound (a garbage drafter can waste proposals but never tokens
or extra sweeps), the `rewind` slot primitive, and the benchmark's
ttft == -1.0 timeout sentinel staying out of the latency percentiles.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from mpi_operator_tpu.models import CausalLM, generate, gpt2_config
from mpi_operator_tpu.serve import (
    EngineConfig, PageAllocator, Request, Scheduler, ServingEngine,
    SlotManager, propose_ngram,
)

pytestmark = [pytest.mark.serving, pytest.mark.spec]


# ---------------------------------------------------------------------------
# propose_ngram: host-side prompt-lookup drafting (no jax)
# ---------------------------------------------------------------------------

def test_propose_ngram_copies_after_the_match():
    # suffix [1,2,3] matched at the start; the k tokens after it follow
    assert propose_ngram([1, 2, 3, 4, 1, 2, 3], k=3) == [4, 1, 2]


def test_propose_ngram_prefers_the_most_recent_occurrence():
    # suffix [5,6] occurs at s=1 (followed by 9) and s=4 (followed by
    # 8): recency wins — the latest occurrence predicts a repeating tail
    assert propose_ngram([7, 5, 6, 9, 5, 6, 8, 5, 6], k=2) == [8, 5]


def test_propose_ngram_clamps_at_history_end():
    assert propose_ngram([1, 2, 1, 2], k=5) == [1, 2]


def test_propose_ngram_novel_text_returns_empty():
    assert propose_ngram([1, 2, 3, 4], k=4) == []
    assert propose_ngram([1], k=4) == []
    assert propose_ngram([1, 1, 1], k=0) == []


# ---------------------------------------------------------------------------
# rewind: the cursor-rollback slot primitive (no jax)
# ---------------------------------------------------------------------------

def _bound_state():
    m = SlotManager(2)
    s = Scheduler((4,), max_len=64)
    s.submit(Request(0, list(range(1, 7)), 8))
    st, = s.admit(m.free, 0.0, PageAllocator(9, 8))
    m.bind(st)
    return m, st


def test_rewind_moves_the_cursor_back():
    m, st = _bound_state()
    st.pos = 10
    m.rewind(st.slot, 3, 8)
    assert st.pos == 7
    m.rewind(st.slot, 0, 8)                     # no-op rewind is legal
    assert st.pos == 7


def test_rewind_validates_slot_and_bounds():
    m, st = _bound_state()
    st.pos = 2
    with pytest.raises(ValueError, match="negative"):
        m.rewind(st.slot, -1, 8)
    with pytest.raises(ValueError, match="< 0"):
        m.rewind(st.slot, 3, 8)              # underflow past 0
    free = next(i for i in range(len(m.states)) if m.states[i] is None)
    with pytest.raises(ValueError, match="free slot"):
        m.rewind(free, 1, 8)


def test_rewind_crosses_unpublished_page_boundaries():
    # a rejected span that crossed into a fresh page rolls back across
    # the boundary; the page stays allocated (inside the reserved span)
    m, st = _bound_state()
    st.pos = 20
    st.published_pages = 1
    m.rewind(st.slot, 11, page_size=8)       # 20 -> 9, across 16
    assert st.pos == 9


def test_rewind_refuses_to_unpublish_pages():
    # published pages are immutable prefix-cache entries other requests
    # may share: the cursor may land ON the frontier, never below it
    m, st = _bound_state()
    st.pos = 20
    st.published_pages = 2                   # frontier = 16 at page 8
    m.rewind(st.slot, 4, page_size=8)
    assert st.pos == 16
    with pytest.raises(ValueError, match="un-publish"):
        m.rewind(st.slot, 1, page_size=8)


# ---------------------------------------------------------------------------
# engine: greedy exactness across attention/KV modes
# ---------------------------------------------------------------------------

def _setup(decode_kernel=False, vocab=64, max_len=64, kv_cache_dtype=None,
           drafter=None, **cfg_kw):
    cfg_kw.setdefault("page_size", 8)
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=vocab, max_len=max_len,
                      kv_cache_dtype=kv_cache_dtype)
    model = CausalLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), probe))["params"]
    engine = ServingEngine(model, params, EngineConfig(
        slots=4, chunk_buckets=(4, 8), decode_kernel=decode_kernel,
        **cfg_kw), drafter=drafter)
    return model, params, engine


def _trace(seed=11, n=8, sampled=False):
    # 8 requests over 4 slots: the second wave reuses retired slots, so
    # exactness covers retire/reuse, not just a single resident batch
    rs = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        temp = 0.9 if (sampled and i % 2) else 0.0
        p = int(rs.choice([2, 5, 9, 13]))
        reqs.append(Request(i, list(rs.randint(0, 64, (p,))),
                            max_new_tokens=int(rs.choice([5, 8, 12])),
                            temperature=temp, top_k=4 if temp else 0))
    return reqs


def _nospec_rerun(engine, reqs):
    """Replay `reqs` through the SAME engine with speculation off
    (same compiled step/prefill programs — the A/B is pure policy)."""
    mode = engine.config.speculative
    engine.config.speculative = None
    engine.reset()
    base = engine.run(reqs)
    engine.config.speculative = mode
    return base


def _oracle(model, params, req):
    out = generate(model, params,
                   jnp.asarray([list(req.prompt)], jnp.int32),
                   req.max_new_tokens, eos_id=req.eos_id)
    return list(np.asarray(out.tokens[0, len(req.prompt):]))


@pytest.mark.parametrize("decode_kernel", [False, True],
                         ids=["paged", "paged-kernel"])
def test_spec_greedy_token_exact_across_modes(decode_kernel):
    _, _, engine = _setup(decode_kernel, speculative="ngram")
    reqs = _trace()
    spec = engine.run(reqs)
    stats = engine.spec_stats()
    assert stats["proposed"] > 0             # speculation actually ran
    base = _nospec_rerun(engine, reqs)
    for r in reqs:
        assert spec[r.id].tokens == base[r.id].tokens, f"request {r.id}"
        assert spec[r.id].finish_reason == base[r.id].finish_reason
        assert np.allclose(spec[r.id].logprobs, base[r.id].logprobs,
                           atol=1e-5)


def test_spec_single_request_matches_generate_oracle():
    model, params, engine = _setup(speculative="ngram")
    prompt = list(np.random.RandomState(3).randint(0, 64, (13,)))
    req = Request(0, prompt, max_new_tokens=10)
    res = engine.run([req])
    assert res[0].tokens == _oracle(model, params, req)
    assert len(res[0].logprobs) == 10
    assert all(lp <= 0 for lp in res[0].logprobs)
    assert res[0].ttft >= 0 and len(res[0].token_times) == 10


def test_spec_int8_kv_cache_token_exact():
    _, _, engine = _setup(kv_cache_dtype="int8", speculative="ngram")
    reqs = _trace(seed=17)
    spec = engine.run(reqs)
    assert engine.spec_stats()["proposed"] > 0
    base = _nospec_rerun(engine, reqs)
    for r in reqs:
        assert spec[r.id].tokens == base[r.id].tokens, f"request {r.id}"


def test_spec_mixed_sampling_rows_ride_along():
    # every other request samples — sampled rows never draft but share
    # the verify batch; greedy rows stay exact vs the non-spec engine,
    # and the whole mixed trace replays exactly across reset (the
    # per-step rng counter rewinds with it)
    _, _, engine = _setup(speculative="ngram")
    reqs = _trace(seed=31, sampled=True)
    a = engine.run(reqs)
    assert engine.spec_stats()["proposed"] > 0
    first = engine.compile_counts()
    engine.reset()
    b = engine.run(reqs)
    # the mixed batch holds the same pins: no recompile on replay
    assert engine.compile_counts() == first
    for r in reqs:
        assert a[r.id].tokens == b[r.id].tokens
    base = _nospec_rerun(engine, reqs)
    for r in reqs:
        if r.temperature == 0.0:
            assert a[r.id].tokens == base[r.id].tokens, f"request {r.id}"


# ---------------------------------------------------------------------------
# the no-recompile contract: <= 2 bucketed verify widths
# ---------------------------------------------------------------------------

def test_spec_reset_replay_holds_the_verify_compile_pins():
    _, _, engine = _setup(speculative="ngram")
    # draft_k=4 buckets: a narrow width-2 program + the full k+1
    assert engine._verify_buckets == (2, 5)
    reqs = _trace(seed=23)
    a = engine.run(reqs)
    first = engine.compile_counts()
    assert 1 <= first["verify"] <= len(engine._verify_buckets)
    engine.reset()
    b = engine.run(reqs)
    assert engine.compile_counts() == first  # replay: zero new compiles
    for r in reqs:
        assert a[r.id].tokens == b[r.id].tokens


def test_spec_verify_widths_bucket_a_mixed_budget_trace():
    # the draft budget clamps k: wave 1 (max_new=2, budget 1) drafts
    # exactly one token — the narrow width-2 program; wave 2 drafts the
    # full draft_k — width 5. Two widths ran, exactly the two bucketed
    # programs compiled, and the trace stays token-exact. (A drafter
    # that always fills its budget makes the width choice
    # deterministic; ngram proposal lengths are trace-dependent.)
    _, _, engine = _setup(speculative="draft",
                          drafter=lambda hist, k: [int(hist[-1])] * k)
    rs = np.random.RandomState(5)
    reqs = [Request(i, [1 + i, 2, 3], max_new_tokens=2)
            for i in range(4)]
    reqs += [Request(4 + i, list(rs.randint(0, 64, (6,))),
                     max_new_tokens=12) for i in range(4)]
    spec = engine.run(reqs)
    assert engine.compile_counts()["verify"] == 2
    base = _nospec_rerun(engine, reqs)
    for r in reqs:
        assert spec[r.id].tokens == base[r.id].tokens, f"request {r.id}"


def test_spec_composes_with_disagg_decode_pool():
    # speculation lives in the decode pool: the prefill pool strips the
    # knob (it never decodes, so it never drafts or verifies), the
    # decode pool drafts/verifies under its own compile pins, and the
    # disaggregated output stays token-identical to the colocated
    # speculative engine
    from mpi_operator_tpu.serve import DisaggEngine

    model, params, coloc = _setup(speculative="ngram", page_size=8)
    disagg = DisaggEngine(model, params, EngineConfig(
        slots=4, chunk_buckets=(4, 8), page_size=8,
        speculative="ngram"))
    reqs = _trace(seed=53, n=6)
    a = coloc.run(reqs)
    b = disagg.run(reqs)
    assert disagg.decode.spec_stats()["proposed"] > 0
    counts = disagg.compile_counts()
    assert counts["prefill_pool"]["verify"] == 0
    assert counts["prefill_pool"]["step"] == 0
    assert counts["decode_pool"]["prefill"] == 0
    assert 1 <= counts["decode_pool"]["verify"] <= 2
    for r in reqs:
        assert a[r.id].tokens == b[r.id].tokens, f"request {r.id}"


# ---------------------------------------------------------------------------
# drafter plug-in mode + adversarial drafters
# ---------------------------------------------------------------------------

def test_spec_draft_mode_shares_the_verify_path():
    # a pluggable drafter (here: the ngram proposer as a callable) rides
    # the exact same verify/accept path as the built-in mode
    _, _, engine = _setup(speculative="draft",
                          drafter=lambda hist, k: propose_ngram(hist, k))
    reqs = _trace(seed=47)
    spec = engine.run(reqs)
    stats = engine.spec_stats()
    assert stats["proposed"] > 0
    assert stats["acceptance_rate"] > 0
    base = _nospec_rerun(engine, reqs)
    for r in reqs:
        assert spec[r.id].tokens == base[r.id].tokens, f"request {r.id}"


def test_spec_adversarial_drafter_exact_and_never_more_sweeps():
    # a drafter that only proposes one constant token: it can waste
    # proposals but never tokens — output stays exact, and the verify
    # loop never takes MORE sequential sweeps than plain sync decode
    # takes steps (every verify banks at least its bonus token)
    _, _, engine = _setup(speculative="draft",
                          drafter=lambda hist, k: [63] * k)
    reqs = _trace(seed=41)
    adv = engine.run(reqs)
    assert engine.spec_stats()["proposed"] > 0
    adv_steps = engine._steps_dispatched
    engine.config.speculative = None
    engine.config.async_decode = False
    engine.reset()
    base = engine.run(reqs)
    for r in reqs:
        assert adv[r.id].tokens == base[r.id].tokens, f"request {r.id}"
    assert adv_steps <= engine._steps_dispatched
    engine.config.async_decode = True
    engine.config.speculative = "draft"


def test_spec_out_of_vocab_drafter_ids_are_truncated():
    # garbage ids out of [0, vocab) truncate at the first bad token —
    # nothing out-of-range ever reaches the device gather
    _, _, engine = _setup(speculative="draft",
                          drafter=lambda hist, k: [10 ** 9, -1, 3])
    reqs = _trace(seed=43, n=4)
    res = engine.run(reqs)
    base = _nospec_rerun(engine, reqs)
    for r in reqs:
        assert res[r.id].tokens == base[r.id].tokens, f"request {r.id}"


def test_spec_telemetry_federates_into_job_series():
    # engine-side spec counters/histograms export as tpu_worker_* and
    # federate into the tpu_job_* aggregate like every other series
    from mpi_operator_tpu.telemetry import WorkerTelemetry
    from mpi_operator_tpu.telemetry.collector import MetricsFederation
    from mpi_operator_tpu.telemetry.prometheus import render_registry

    wtel = WorkerTelemetry()
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=64)
    model = CausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 4), jnp.int32)))["params"]
    engine = ServingEngine(model, params, EngineConfig(
        slots=2, chunk_buckets=(4, 8), speculative="ngram"),
        telemetry=wtel.serving)
    engine.run([Request(0, [1, 2, 3, 1, 2, 3], max_new_tokens=8)])
    stats = engine.spec_stats()
    assert stats["proposed"] > 0
    fed = MetricsFederation("sjob", clock=lambda: 0.0)
    fed.ingest(0, render_registry(wtel.registry))
    text = "\n".join(fed.render_lines())
    for series, expect in [("tpu_job_spec_proposed_total",
                            float(stats["proposed"])),
                           ("tpu_job_spec_accepted_total",
                            float(stats["accepted"]))]:
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(series))
        assert float(line.rsplit(" ", 1)[1]) == expect, line
    assert "tpu_job_spec_tokens_per_step_bucket" in text
    assert "tpu_job_spec_acceptance_ratio_bucket" in text


def test_spec_config_validation():
    with pytest.raises(ValueError, match="speculative"):
        _setup(speculative="turbo")
    with pytest.raises(ValueError, match="draft_k"):
        _setup(speculative="ngram", draft_k=0)
    with pytest.raises(ValueError, match="drafter"):
        _setup(speculative="draft")


# ---------------------------------------------------------------------------
# benchmark: the ttft == -1.0 timeout sentinel stays out of percentiles
# ---------------------------------------------------------------------------

class _FakeResult:
    def __init__(self, ttft, token_times):
        self.ttft = ttft
        self.token_times = token_times


def test_ttft_sentinel_never_pollutes_latency_percentiles():
    from mpi_operator_tpu.examples.serve_benchmark import (
        _latency_fields, _percentiles)
    # pure all-timeout trace: every request expired before its first
    # token — all-None fields, no crash, no -1 folded in as a latency
    pure = _latency_fields([_FakeResult(-1.0, [])] * 4)
    assert pure == {"serving_ttft_p50_ms": None,
                    "serving_ttft_p99_ms": None,
                    "serving_tpot_p50_ms": None,
                    "serving_tpot_p99_ms": None}
    assert _percentiles([]) == {50: None, 99: None}
    # mixed trace: the sentinel is EXCLUDED, not clamped — percentiles
    # reflect only requests that produced a first token
    mixed = _latency_fields(
        [_FakeResult(-1.0, []), _FakeResult(0.5, [0.5, 0.6])])
    assert mixed["serving_ttft_p50_ms"] == mixed["serving_ttft_p99_ms"] \
        == 500.0
    assert mixed["serving_tpot_p50_ms"] == 100.0


def test_all_timeout_engine_trace_reports_without_crashing():
    from mpi_operator_tpu.examples.serve_benchmark import _latency_fields
    # integration: a real engine run under request_timeout=0 retires
    # everything with finish_reason "timeout"; the benchmark's latency
    # assembly must survive it with no negative field
    _, _, engine = _setup(speculative=None, request_timeout=0.0,
                          page_size=8)
    reqs = [Request(i, [1 + i, 2, 3, 4, 5, 6], 8) for i in range(3)]
    results = engine.run(reqs)
    assert all(r.finish_reason == "timeout" for r in results.values())
    # the sentinel fires exactly when no token was emitted
    assert all((r.ttft == -1.0) == (not r.token_times)
               for r in results.values())
    fields = _latency_fields(results.values())
    for key, val in fields.items():
        assert val is None or val >= 0.0, (key, val)
