"""Continuous-batching serving engine tests (serve/).

`generate()` is the oracle: a greedy request served through the slot
engine — chunked prefill, per-slot cursors, shared decode step — must be
TOKEN-EXACT against the same request run through the fixed-batch decode
path, on both the dense and Pallas-kernel attention paths. On top of
that: the host-side scheduling policy (chunk planning, FCFS admission,
EOS/length retirement, slot reuse) and the no-recompile contract
(compile counts pinned across traces).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from mpi_operator_tpu.models import CausalLM, generate, gpt2_config
from mpi_operator_tpu.models.generate import _sample
from mpi_operator_tpu.serve import (
    EngineConfig, PageAllocator, Request, Scheduler, ServingEngine,
    SlotManager, plan_chunks, sample_slots,
)

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# host-side policy (no jax)
# ---------------------------------------------------------------------------

def test_plan_chunks_walks_buckets():
    assert plan_chunks(0, (4, 16)) == []
    assert plan_chunks(16, (4, 16)) == [(0, 16)]
    # full big windows left->right, ragged tail RIGHT-ALIGNED
    assert plan_chunks(36, (4, 16)) == [(0, 16), (16, 16), (32, 4)]
    # tail that fits no small bucket takes the next size up, right-aligned
    assert plan_chunks(37, (4, 16)) == [(0, 16), (16, 16), (21, 16)]
    assert plan_chunks(23, (4, 16)) == [(0, 16), (7, 16)]
    # prompt shorter than every bucket: one window at 0 (engine pads)
    assert plan_chunks(3, (4, 16)) == [(0, 4)]


def test_plan_chunks_covers_exactly():
    # every position < n is written by >= 1 window; a window overruns n
    # ONLY in the pad case (n smaller than the chosen bucket, start 0)
    for n in range(0, 70):
        for buckets in [(8,), (4, 16), (2, 8, 32)]:
            covered = set()
            for start, size in plan_chunks(n, buckets):
                assert size in buckets
                if start + size > n:
                    assert start == 0 and n < size
                covered.update(range(start, start + size))
            assert covered.issuperset(range(n))


def test_scheduler_validates():
    with pytest.raises(ValueError, match="1-3"):
        Scheduler((1, 2, 4, 8), max_len=64)
    with pytest.raises(ValueError, match="ascending"):
        Scheduler((16, 4), max_len=64)
    with pytest.raises(ValueError, match="max_len"):
        Scheduler((128,), max_len=64)
    s = Scheduler((4, 16), max_len=32)
    with pytest.raises(ValueError, match="empty prompt"):
        s.submit(Request(0, [], 4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        s.submit(Request(0, [1], 0))
    with pytest.raises(ValueError, match="max_len"):
        s.submit(Request(0, [1] * 30, 8))


def test_scheduler_fcfs_admission_and_retire():
    s = Scheduler((4,), max_len=32)
    a = PageAllocator(33, 4)                   # pages never bind here
    for i in range(3):
        s.submit(Request(i, [1, 2, 3, 4, 5], 4, arrival=float(i)))
    free = [0, 1]
    admitted = s.admit(free, 10.0, a)
    assert [st.req.id for st in admitted] == [0, 1] and free == []
    # bonus token: prompt[:-1] prefills, last token is the first input
    assert admitted[0].next_input == 5
    assert admitted[0].chunks == [(0, 4)]
    assert s.admit([], 10.0, a) == []          # no slot, no admission
    s.retire(admitted[0])
    third, = s.admit([admitted[0].slot], 10.0, a)
    assert third.req.id == 2
    # future arrivals stay queued
    s.submit(Request(9, [1, 2], 2, arrival=99.0))
    assert s.admit([5], 10.0, a) == []
    assert s.next_arrival() == 99.0


def test_slot_manager_reuse_and_step_arrays():
    m = SlotManager(2)
    s = Scheduler((4,), max_len=32)
    s.submit(Request(0, list(range(1, 7)), 4))        # needs prefill
    # single-token prompt: no prefill (the bonus token IS the prompt)
    s.submit(Request(1, [8], 4, temperature=0.5, top_k=3, top_p=0.9))
    for st in s.admit(m.free, 0.0, PageAllocator(17, 4)):
        m.bind(st)
    toks, pos, use_prev, temps, top_ks, top_ps, consumers = m.step_arrays()
    # slot 0 is mid-prefill: present in pos, absent from consumers
    assert [st.req.id for st in consumers] == [1]
    assert toks[1] == 8 and temps[1] == np.float32(0.5)
    assert top_ks[1] == 3 and top_ps[1] == np.float32(0.9)
    # first decode step reads the host bonus token, not the device chain
    assert not use_prev[1]
    consumers[0].dispatched = 1
    arrs = m.step_arrays()
    assert arrs[2][1]                 # chained now
    # drained request: stops consuming, awaits its final sync
    consumers[0].dispatched = consumers[0].req.max_new_tokens
    arrs = m.step_arrays()
    assert arrs[-1] == []
    st0, st1 = m.states
    m.release(st0)
    assert m.free == [0] and m.occupied == 1
    with pytest.raises(RuntimeError, match="occupied"):
        m.bind(st1)


# ---------------------------------------------------------------------------
# sample_slots vs generate._sample
# ---------------------------------------------------------------------------

def test_sample_slots_matches_sample_reference():
    """Per-row traced filters == _sample's static filters at the same
    (temperature, top_k, top_p) and the same rng, token for token —
    in both the full-vocab and bounded-pool variants."""
    rng = jax.random.PRNGKey(5)
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 3.0
    B = logits.shape[0]

    def rows(v, dt=jnp.float32):
        return jnp.full((B,), v, dt)

    for t, k, p in [(0.7, 7, 0.9), (1.3, 3, 1.0), (0.5, 64, 0.85)]:
        ref_tok, ref_lp = _sample(logits, False, jnp.float32(t), rng,
                                  k, p < 1.0, jnp.float32(p))
        for mode in ("full", "bounded"):
            tok, lp = sample_slots(logits, rng, rows(t),
                                   rows(k, jnp.int32), rows(p), mode=mode)
            assert np.array_equal(np.asarray(ref_tok), np.asarray(tok)), \
                (t, k, p, mode)
            np.testing.assert_allclose(np.asarray(ref_lp), np.asarray(lp),
                                       atol=1e-5)
    # greedy rows pick argmax in every mode, logprob from the raw dist
    g_tok, g_lp = _sample(logits, True, jnp.float32(0.0), None, None,
                          False, jnp.float32(1.0))
    for mode in ("greedy", "bounded", "full"):
        tok, lp = sample_slots(logits, rng, rows(0.0),
                               rows(0, jnp.int32), rows(1.0), mode=mode)
        assert np.array_equal(np.asarray(g_tok), np.asarray(tok))
        np.testing.assert_allclose(np.asarray(g_lp), np.asarray(lp),
                                   atol=1e-5)


def test_sample_slots_mixed_rows_independent():
    """Greedy and sampling rows coexist in one call: the greedy row is
    exact argmax, the top_k=1 row degenerates to argmax too."""
    logits = jax.random.normal(jax.random.PRNGKey(2), (3, 32))
    tok, _ = sample_slots(
        logits, jax.random.PRNGKey(0),
        jnp.asarray([0.0, 1.5, 0.8]), jnp.asarray([0, 1, 4], jnp.int32),
        jnp.asarray([1.0, 1.0, 1.0]), mode="bounded")
    am = np.argmax(np.asarray(logits), -1)
    assert int(tok[0]) == am[0]
    assert int(tok[1]) == am[1]          # top_k=1 == greedy
    assert 0 <= int(tok[2]) < 32


# ---------------------------------------------------------------------------
# engine vs generate() (the oracle)
# ---------------------------------------------------------------------------

def _setup(decode_kernel=False, vocab=64, max_len=64, **cfg_kw):
    cfg_kw.setdefault("page_size", 8)
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=vocab, max_len=max_len)
    model = CausalLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), probe))["params"]
    engine = ServingEngine(model, params, EngineConfig(
        slots=4, chunk_buckets=(4, 8), decode_kernel=decode_kernel,
        **cfg_kw))
    return model, params, engine


def _oracle(model, params, req):
    out = generate(model, params,
                   jnp.asarray([list(req.prompt)], jnp.int32),
                   req.max_new_tokens, eos_id=req.eos_id)
    toks = list(np.asarray(out.tokens[0, len(req.prompt):]))
    if req.eos_id is not None and req.eos_id in toks:
        toks = toks[:toks.index(req.eos_id) + 1]   # engine stops at eos
    return toks


@pytest.mark.parametrize("decode_kernel", [False, True])
def test_engine_single_request_token_exact(decode_kernel):
    model, params, engine = _setup(decode_kernel)
    prompt = list(np.random.RandomState(3).randint(0, 64, (13,)))
    req = Request(0, prompt, max_new_tokens=10)
    res = engine.run([req])
    assert res[0].finish_reason == "length"
    assert res[0].tokens == _oracle(model, params, req)
    assert len(res[0].logprobs) == 10
    assert all(lp <= 0 for lp in res[0].logprobs)
    assert res[0].ttft >= 0 and len(res[0].token_times) == 10


@pytest.mark.parametrize("decode_kernel", [False, True])
def test_one_page_a_slot_is_token_exact(decode_kernel):
    """`page_size == max_len`: every slot's table is one page, which is
    the contiguous-slot layout, through the one path — mixed lengths,
    retirement and slot reuse included."""
    model, params, engine = _setup(decode_kernel, page_size=64)
    assert engine.page_allocator.usable == engine.config.slots
    rs = np.random.RandomState(5)
    reqs = [Request(i, list(rs.randint(0, 64, (p,))), max_new_tokens=n)
            for i, (p, n) in enumerate([(13, 10), (1, 6), (9, 4), (20, 7),
                                        (5, 5), (7, 8)])]
    results = engine.run(reqs)
    for req in reqs:
        assert results[req.id].tokens == _oracle(model, params, req), \
            f"request {req.id} diverged"


def test_the_contiguous_slot_option_is_refused_and_says_where_it_went():
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=64)
    with pytest.raises(ValueError, match="page_size=max_len"):
        ServingEngine(CausalLM(cfg), {}, EngineConfig(paged=False))
    assert EngineConfig().paged is True


def test_engine_mixed_lengths_match_oracle_per_request():
    """Six greedy requests at six prompt lengths share slots and the
    compiled step; each must still match its own batch-1 generate()."""
    model, params, engine = _setup()
    rs = np.random.RandomState(7)
    reqs = [Request(i, list(rs.randint(0, 64, (p,))), max_new_tokens=n)
            for i, (p, n) in enumerate([(1, 6), (3, 9), (9, 4), (14, 7),
                                        (5, 5), (7, 8)])]
    results = engine.run(reqs)
    assert set(results) == set(range(6))
    for req in reqs:
        assert results[req.id].tokens == _oracle(model, params, req), \
            f"request {req.id} diverged"


def test_engine_eos_retirement_and_slot_reuse():
    """More requests than slots + an eos_id that actually fires: finished
    rows retire, their slots serve later arrivals, every result matches
    the oracle (including the eos cut)."""
    model, params, engine = _setup()
    rs = np.random.RandomState(11)
    probe = Request(99, list(rs.randint(0, 64, (6,))), max_new_tokens=8)
    eos = _oracle(model, params, probe)[2]     # a token greedy WILL emit
    engine.reset()
    reqs = [Request(i, list(rs.randint(0, 64, (3 + i,))),
                    max_new_tokens=8, eos_id=eos)
            for i in range(6)]                 # 6 requests, 4 slots
    results = engine.run(reqs)
    assert len(results) == 6
    assert any(r.finish_reason == "eos" for r in results.values())
    for req in reqs:
        assert results[req.id].tokens == _oracle(model, params, req)
        if results[req.id].finish_reason == "eos":
            assert results[req.id].tokens[-1] == eos


def test_engine_compile_counts_stay_fixed():
    """The no-recompile contract: after a mixed greedy+sampling trace, a
    reset, and a second different-shape trace, the step has at most one
    program per sample_slots mode and prefill one per bucket. The reset
    must ALSO rewind the page allocator and prefix cache — a replay of
    the same trace admits with zero carried-over state (and identical
    tokens), still without recompiling."""
    _, _, engine = _setup()
    rs = np.random.RandomState(13)

    def trace(base):
        return [Request(base + i, list(rs.randint(0, 64, (p,))),
                        max_new_tokens=4,
                        temperature=0.9 if i % 2 else 0.0,
                        top_k=5 if i % 2 else 0)
                for i, p in enumerate([2, 6, 9, 13, 4])]

    t0 = trace(0)
    a = engine.run(t0)
    first = engine.compile_counts()
    engine.reset()
    # the allocator rewound with the rest of the serving state: every
    # page free, no refcounts, no cached prefixes (stale K/V must not
    # survive into the zeroed cache)
    alloc = engine.page_allocator
    assert alloc.in_use == 0 and alloc.cached_pages == 0
    assert alloc.available == alloc.usable
    assert alloc.hits == alloc.misses == 0
    alloc.check()
    engine.run(trace(100))
    second = engine.compile_counts()
    assert first == second                    # reset must not recompile
    assert second["step"] <= 3
    assert second["prefill"] <= len(engine.config.chunk_buckets)
    assert second["init_cache"] == 1 and second["cast"] == 1
    engine.reset()
    b = engine.run(t0)                        # identical replay post-reset
    assert engine.compile_counts() == second
    for r in t0:
        assert b[r.id].tokens == a[r.id].tokens


def test_engine_streams_tokens_in_order():
    model, params, engine = _setup()
    req = Request(0, [5, 9, 2], max_new_tokens=6)
    seen = []
    engine.run([req], on_token=lambda r, t: seen.append((r.id, t)))
    assert seen == [(0, t) for t in _oracle(model, params, req)]


def test_engine_rejects_oversized_request():
    _, _, engine = _setup(max_len=64)
    with pytest.raises(ValueError, match="max_len"):
        engine.run([Request(0, [1] * 60, max_new_tokens=10)])


def test_engine_sampling_reproducible_and_in_support():
    """Sampled requests: same seed → same tokens; different engine seed
    diverges; every sampled token is one of the top_k at its position."""
    model, params, engine = _setup()
    prompt = [3, 1, 4, 1, 5]
    req = Request(0, prompt, max_new_tokens=6, temperature=1.2, top_k=3)
    a = engine.run([req])[0].tokens
    engine.reset()
    assert engine.run([req])[0].tokens == a
    other = ServingEngine(model, params, EngineConfig(
        slots=4, chunk_buckets=(4, 8), page_size=8, rng_seed=1))
    b = other.run([req])[0].tokens
    assert len(a) == len(b) == 6
    ctx = list(prompt)
    for t in a:
        logits = np.asarray(model.apply(
            {"params": params}, jnp.asarray([ctx], jnp.int32)))[0, -1]
        assert t in np.argsort(logits)[-3:], "token outside top_k support"
        ctx.append(t)


@pytest.mark.parametrize("decode_kernel", [False, True])
def test_engine_async_matches_sync_token_exact(decode_kernel):
    """The double-buffered loop vs the drain-every-step loop: identical
    greedy tokens (including EOS cuts mid-flight, which cost the async
    loop one discarded junk step), identical finish reasons, and ZERO
    extra compiles — async/sync share the same compiled step."""
    model, params, engine = _setup(decode_kernel)
    rs = np.random.RandomState(11)
    probe = Request(99, list(rs.randint(0, 64, (6,))), max_new_tokens=8)
    eos = _oracle(model, params, probe)[2]     # a token greedy WILL emit
    engine.reset()
    reqs = [Request(i, list(rs.randint(0, 64, (3 + i,))),
                    max_new_tokens=8, eos_id=eos)
            for i in range(6)]                 # 6 requests, 4 slots
    assert engine.config.async_decode          # the default
    a = engine.run(reqs)
    counts_async = engine.compile_counts()
    engine.config.async_decode = False
    engine.reset()
    b = engine.run(reqs)
    assert engine.compile_counts() == counts_async
    assert any(r.finish_reason == "eos" for r in a.values())
    for req in reqs:
        assert a[req.id].tokens == b[req.id].tokens == \
            _oracle(model, params, req), f"request {req.id} diverged"
        assert a[req.id].finish_reason == b[req.id].finish_reason


@pytest.mark.parametrize("depth", [2, 4, 8])
def test_engine_async_depth_is_token_exact(depth):
    """`async_depth` steps dispatched and unfetched: the same greedy
    tokens, EOS cuts (which now cost up to `depth` junk steps, all
    discarded) and finish reasons as the double-buffered loop, through the
    same compiled programs; six requests over four slots, so rows are
    admitted onto slots whose last occupant still has junk steps out. (A
    sampled row draws by the step's number, which admission order moves:
    its tokens are another fair draw, not the same one.)"""
    model, params, engine = _setup()
    rs = np.random.RandomState(11)
    probe = Request(99, list(rs.randint(0, 64, (6,))), max_new_tokens=8)
    eos = _oracle(model, params, probe)[2]     # a token greedy WILL emit
    engine.reset()
    reqs = [Request(i, list(rs.randint(0, 64, (3 + i,))), max_new_tokens=8,
                    eos_id=eos)
            for i in range(6)]
    assert engine.config.async_depth == 1      # the default
    a = engine.run(reqs)
    counts = engine.compile_counts()
    engine.config.async_depth = depth
    engine.reset()
    b = engine.run(reqs)
    assert engine.compile_counts() == counts
    assert any(r.finish_reason == "eos" for r in a.values())
    for req in reqs:
        assert a[req.id].tokens == b[req.id].tokens == \
            _oracle(model, params, req), f"request {req.id} diverged"
        assert a[req.id].finish_reason == b[req.id].finish_reason
    assert engine.slots.occupied == 0


def test_engine_drain_fetches_what_is_out_and_changes_no_token():
    """`drain()` mid-session: nothing dispatched stays unfetched, the
    session goes on, and every token is what the undrained loop serves."""
    model, params, engine = _setup(async_depth=4)
    rs = np.random.RandomState(17)
    reqs = [Request(i, list(rs.randint(0, 64, (3 + i,))), max_new_tokens=9)
            for i in range(5)]
    want = engine.run(reqs)
    engine.reset()
    with pytest.raises(RuntimeError, match="outside a session"):
        engine.drain()
    engine.start()
    for r in reqs:
        engine.submit(r)
    for _ in range(6):
        engine.tick()
    assert engine._session["pending"]
    engine.drain()
    assert not engine._session["pending"]
    while engine.active:
        engine.tick()
    got = engine.finish()
    for r in reqs:
        assert got[r.id].tokens == want[r.id].tokens


def test_engine_refuses_a_depth_under_one():
    model, params, _ = _setup()
    with pytest.raises(ValueError, match="async_depth"):
        ServingEngine(model, params, EngineConfig(
            slots=4, chunk_buckets=(4, 8), page_size=8, async_depth=0))


def test_engine_async_compile_pins_and_sampled_replay():
    """Async mode holds the same no-recompile contract as sync, across
    run -> reset -> run with mixed greedy+sampled traffic; and a reset
    async engine replays its sampled draws exactly (the per-step rng
    counter rewinds with it)."""
    _, _, engine = _setup()
    rs = np.random.RandomState(29)
    reqs = [Request(i, list(rs.randint(0, 64, (p,))),
                    max_new_tokens=5,
                    temperature=1.1 if i % 2 else 0.0,
                    top_k=4 if i % 2 else 0)
            for i, p in enumerate([2, 7, 10, 3, 12])]
    a = engine.run(reqs)
    first = engine.compile_counts()
    engine.reset()
    b = engine.run(reqs)
    second = engine.compile_counts()
    assert first == second                    # reset must not recompile
    assert second["step"] <= 3
    assert second["prefill"] <= len(engine.config.chunk_buckets)
    for req in reqs:                          # sampled draws replay too
        assert a[req.id].tokens == b[req.id].tokens


@pytest.mark.multichip
def test_engine_with_sharded_params_matches_oracle():
    """Serving over dp-sharded params (the bench's deployment shape):
    GSPMD partitions the engine's programs; tokens stay oracle-exact."""
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh
    from mpi_operator_tpu.parallel.sharding import shard_init

    if jax.device_count() < 2:
        pytest.skip("needs >1 device")
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=64)
    model = CausalLM(cfg)
    mesh = make_mesh(MeshConfig(dp=jax.device_count()))
    variables, _ = shard_init(model, mesh, jax.random.PRNGKey(0),
                              jnp.zeros((1, 4), jnp.int32))
    params = variables["params"]
    engine = ServingEngine(model, params, EngineConfig(
        slots=2, chunk_buckets=(4, 8), page_size=8))
    rs = np.random.RandomState(17)
    reqs = [Request(i, list(rs.randint(0, 64, (p,))), max_new_tokens=5)
            for i, p in enumerate([4, 9, 6])]
    results = engine.run(reqs)
    for req in reqs:
        assert results[req.id].tokens == _oracle(model, params, req)


def test_paged_admission_stages_reservations_when_no_slot_free():
    """Slot-aware reserve-ahead: when pages fit but no SLOT is free,
    queued requests park their page reservations in `staged` — the pins
    land before decode churn can evict their prefixes, and when a slot
    frees the head admits off its parked reservation instead of paying
    reservation work on the critical path."""
    s = Scheduler((4,), max_len=16)
    a = PageAllocator(20, 4)                  # 19 usable pages
    for i in range(3):
        s.submit(Request(i, [1, 2, 3, 4, 5], 8, arrival=0.0))
    need = Scheduler.pages_needed(s.queue[0], a.page_size)

    avail0 = a.available
    st0, = s.admit([0], 1.0, a)
    assert st0.req.id == 0
    # the head consumed the only slot — the SAME admit call already
    # stages the two queued spans behind it
    assert set(s.staged) == {1, 2}
    assert a.available == avail0 - 3 * need
    # idempotent: a slotless pass admits nothing and stages nothing twice
    assert s.admit([], 1.0, a) == []
    assert set(s.staged) == {1, 2}
    assert a.available == avail0 - 3 * need

    # a slot frees: the staged head admits, CONSUMING its reservation
    st1, = s.admit([1], 1.0, a)
    assert st1.req.id == 1 and 1 not in s.staged
    assert a.available == avail0 - 3 * need       # no double reserve
    assert st1.page_table is not None
    a.check()


def test_reserve_ahead_respects_future_arrivals_and_pool_limits():
    """Staging follows the same gates as admission: requests that have
    not arrived yet are never staged, and a span the pool can't cover
    stays unstaged (no partial pins left behind)."""
    s = Scheduler((4,), max_len=16)
    a = PageAllocator(5, 4)                   # 4 usable pages
    s.submit(Request(0, [1, 2, 3, 4, 5], 8, arrival=0.0))   # needs 3 pages
    s.submit(Request(1, [1, 2, 3, 4, 5], 8, arrival=0.0))   # won't fit too
    s.submit(Request(2, [1, 2], 2, arrival=99.0))           # future
    assert s.admit([], 1.0, a) == []
    assert set(s.staged) == {0}               # 1 doesn't fit, 2 not arrived
    free_before = a.available
    assert s.admit([], 1.0, a) == []
    assert a.available == free_before         # failed fits leak nothing
    a.check()
