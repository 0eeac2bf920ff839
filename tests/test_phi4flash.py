"""Phi-4-mini-flash-reasoning through the serving engine at a tiny size
with every kind of layer present (8 layers split 4 / 4 by the published
rule: Mamba, window, Mamba, window, Mamba, full, gated memory, cross),
against the plain reference of `perfbench/reference/phi4_flash.py` on
seeded random weights.

Everything here is float32 on the CPU, program and reference alike, so a
tolerance is what summation order costs: 2e-5 on log-probabilities and on
the distance of a served token's logit from the reference's best (logits
here are of order 0.5). What is compared is logits, not tokens: the
engine's reported log-probability of each served token, and that the
served token IS the reference's best up to that tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models import phi4flash
from mpi_operator_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashLM
from mpi_operator_tpu.ops import attention, ssm
from mpi_operator_tpu.ops.attention import (pack_kv_rows, paged_attend,
                                            paged_decode_attention)
from mpi_operator_tpu.serve import (DecodeEngine, EngineConfig, PrefillEngine,
                                    Request, ServingEngine)
from mpi_operator_tpu.serve.scheduler import plan_chunks
from mpi_operator_tpu.serve.slots import SlotManager
from mpi_operator_tpu.serve.transfer import PageTransfer
from perfbench import weights_phi4flash as W
from perfbench.reference import phi4_flash as ref
from prefill_forms import member_rows_alone_leave_what_all_rows_leave

TOL = 2e-5
CONFIG = {"num_hidden_layers": 8, "hidden_size": 64, "num_attention_heads": 8,
          "num_key_value_heads": 4, "intermediate_size": 128,
          "sliding_window": 8, "vocab_size": 97, "layer_norm_eps": 1e-5,
          "mb_per_layer": 2,
          "assumed": {"mamba_d_state": 16, "mamba_d_conv": 4,
                      "mamba_expand": 2, "mamba_dt_rank": 4,
                      "initializer_range": 0.02, "lambda_std": 0.1,
                      "dt_min": 1e-3, "dt_max": 1e-1}}
DIMS = W.Dims.from_config(CONFIG)


def model(max_len=64, **kw):
    return Phi4FlashLM(Phi4FlashConfig(
        vocab_size=97, max_len=max_len, num_layers=8, hidden_size=64,
        num_heads=8, num_kv_heads=4, intermediate_size=128, sliding_window=8,
        mamba_dt_rank=4, dtype=jnp.float32, **kw))


@pytest.fixture(scope="module")
def params():
    return W.make_params(W.seed_key(3), DIMS, jnp.float32)


def engine(params, slots=3, page_size=4, kernel=False, max_len=64, **kw):
    cfg = dict(slots=slots, chunk_buckets=(4, 8), page_size=page_size,
               prefix_cache=False, decode_kernel=kernel)
    cfg.update(kw)
    return ServingEngine(model(max_len), params, EngineConfig(**cfg))


def requests(shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(id=i, prompt=rng.integers(0, 97, n).tolist(),
                    max_new_tokens=k) for i, (n, k) in enumerate(shapes)]


def gaps(params, reqs, results):
    """Widest distance of a served token's reported log-probability from
    the reference's, and of its reference logit from the reference's
    best, over every served token."""
    worst = 0.0
    for r in reqs:
        toks = results[r.id].tokens
        assert len(toks) == r.max_new_tokens
        logits = ref.forward(params, jnp.asarray([list(r.prompt) + toks]),
                             DIMS)[0]
        at = len(r.prompt) - 1 + np.arange(len(toks))
        logp = np.asarray(jax.nn.log_softmax(logits, -1))[at, toks]
        best = np.asarray(logits.max(-1))[at] - np.asarray(logits)[at, toks]
        worst = max(worst, float(best.max()), float(np.abs(
            logp - np.asarray(results[r.id].logprobs)).max()))
    return worst


def test_layer_pattern_has_every_kind_and_the_published_split():
    cfg = model().config
    assert [cfg.layer_kind(l) for l in range(8)] == [
        "mamba", "swa", "mamba", "swa", "mamba", "full", "gmu", "cross"]
    full = Phi4FlashConfig()
    kinds = [full.layer_kind(l) for l in range(32)]
    assert [kinds.count(k) for k in ("mamba", "swa", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert full.shared_kv_layer == 17 and full.ring_pages(64) == 9
    assert kinds == [W.Dims.from_config({
        **CONFIG, "num_hidden_layers": 32}).kind(l) for l in range(32)]


def test_whole_sequence_forward_matches_the_reference(params):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 97)
    got = model().apply({"params": params}, toks)
    assert float(jnp.abs(got - ref.forward(params, toks, DIMS)).max()) < TOL


@pytest.mark.parametrize("kernel,page_size", [(False, 4), (True, 8)])
def test_chunked_prefill_then_decode_matches_the_reference(params, kernel,
                                                           page_size):
    """(i), (ii), (iv): prompts through both buckets and a padded tail
    (29 = 3 x 8 + 5, 18 = 2 x 8 + 2, 22 = 2 x 8 + 6, none a multiple of a
    bucket), contexts of 50 past the window (8), the ring's slack (a
    page) and a dozen pages, five requests over three slots so that rows
    prefill while others decode and slots are used again. With the
    kernel (interpreted) the window layers read their ring through
    `paged_decode_attention`'s lower bound."""
    eng = engine(params, kernel=kernel, page_size=page_size)
    reqs = requests([(30, 20), (7, 30), (19, 12), (1, 9), (23, 25)])
    results = eng.run(reqs)
    assert gaps(params, reqs, results) < TOL
    assert eng.compile_counts()["prefill"] == 2
    assert eng.compile_counts()["step"] == 1


def test_row_groups_give_what_the_whole_call_gives(params, monkeypatch):
    """At the real size a chunk's rows go through in groups (`_by_rows`,
    `paged_attend`'s own); here the budgets are cut until they do."""
    monkeypatch.setattr(phi4flash, "_CHUNK_TOKENS", 8)
    monkeypatch.setattr(phi4flash, "_CHUNK_SCORES", 8 * 8 * 20)
    monkeypatch.setattr(attention, "_PAGED_SCORES", 8 * 8 * 8)
    monkeypatch.setattr(attention, "_PAGED_TURN", 8)
    eng = engine(params, slots=4)
    reqs = requests([(30, 6), (7, 9), (19, 5), (27, 4)], seed=5)
    assert gaps(params, reqs, eng.run(reqs)) < TOL


def test_two_requests_in_turn_through_one_slot_start_from_zeros(params):
    """(iii): no reset program runs between them; the second's first
    chunk starts at 0, and its one-token sibling decodes at 0."""
    eng = engine(params, slots=1)
    reqs = requests([(21, 10), (13, 10), (1, 6)], seed=2)
    assert gaps(params, reqs, eng.run(reqs)) < TOL


def _slot_leaves(cache):
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat
            if p[-1].key in Phi4FlashLM.SLOT_STATE}


def test_junk_rows_and_pad_tokens_leave_slot_state_exactly_as_it_was(params):
    """(iv): a decode step over a row at `max_len`, a prefill call the row
    is no member of, and the pads after a member's real tokens."""
    eng = engine(params)
    eng.run(requests([(20, 4), (9, 4), (15, 4)], seed=7))   # state != 0
    before = _slot_leaves(eng.cache)
    S, L, nblk = 3, 64, 16
    i32 = lambda *a: jnp.asarray(a, jnp.int32)               # noqa: E731
    pages = jnp.tile(jnp.arange(1, nblk + 1, dtype=jnp.int32)[None], (S, 1))
    cache, *_ = eng._step(
        eng.params, eng.cache, i32(1, 2, 3), i32(4, 5, 6),
        jnp.zeros((S,), bool), i32(11, L, L), eng._base_rng,
        jnp.zeros((S,)), i32(0, 0, 0), jnp.ones((S,)), pages, "greedy")
    after = _slot_leaves(cache)
    for name in before:
        assert np.array_equal(before[name][1:], after[name][1:]), name
        assert not np.array_equal(before[name][0], after[name][0]), name
    toks = jnp.ones((S, 8), jnp.int32)
    padded = eng._prefill(eng.params, cache, i32(0, 1, 2), toks,
                          i32(12, L, L), pages, i32(3, 0, 0))
    exact = eng._prefill(eng.params, cache, i32(0, 1, 2),
                         toks.at[0, 3:].set(77), i32(12, L, L), pages,
                         i32(3, 0, 0))
    padded, exact = _slot_leaves(padded), _slot_leaves(exact)
    for name in after:
        assert np.array_equal(after[name][1:], padded[name][1:]), name
        # whatever the pad tokens are, they change nothing
        assert np.array_equal(padded[name], exact[name]), name
    # and a call of the member row alone (beside a pad row that names no
    # slot) leaves what a call of every slot's row leaves, leaf for leaf
    assert member_rows_alone_leave_what_all_rows_leave(
        eng, cache, pages) == len(before)


def test_cache_holds_one_pool_and_a_slots_bytes_do_not_grow_with_max_len(
        params):
    """(v)"""
    small, large = engine(params, max_len=64), engine(params, max_len=256)
    for eng in (small, large):
        NP = eng.page_allocator.num_pages
        pooled = [x for x in jax.tree.leaves(eng.cache) if x.shape[0] == NP]
        assert len(pooled) == 1 and pooled[0].shape == (NP, 4, 2 * 2 * 16)
        assert eng.page_bytes() == 4 * 64 * 4
    # two rings of (8 + 4) positions x 64 values (two pairs of K and V of
    # 16), three layers' state of 16 x 128 and conv tail of 3 x 128, all
    # float32 here
    want = 2 * 12 * 64 * 4 + 3 * (16 * 128 + 3 * 128) * 4
    assert small.slot_state_bytes() == large.slot_state_bytes() == want


def _prefill_text(eng):
    S, nblk = eng.config.slots, eng._nblk
    z = lambda *s: jnp.zeros(s, jnp.int32)                   # noqa: E731
    return eng._prefill.lower(eng.params, eng.cache, z(S), z(S, 8), z(S),
                              z(S, nblk), z(S)).as_text(debug_info=True)


def test_prefill_stops_after_the_last_layer_that_keeps_anything(params):
    """(vi): layers 6 (gated memory) and 7 (cross) write no cache, and
    the lowered prefill program holds no operation of theirs; the decode
    step holds them."""
    eng = engine(params)
    text = _prefill_text(eng)
    assert "layer_5" in text and "yoco.cache_write" in text
    for gone in ("layer_6", "layer_7", "gmu", "final_layernorm"):
        assert gone not in text, gone
    scopes = set(eng.decode_step_scopes().values())
    for name in ("ssm.project", "ssm.conv", "ssm.scan", "ssm.out",
                 "swa.project", "swa.cache_write", "swa.attend", "swa.out",
                 "yoco.project", "yoco.cache_write", "yoco.attend",
                 "yoco.out", "gmu"):
        assert any(name in s for s in scopes), name
    assert any("layer_7" in s and "yoco.attend" in s for s in scopes)
    assert not any("layer_7" in s and "yoco.cache_write" in s for s in scopes)


@pytest.mark.parametrize("kwargs,piece", [
    (dict(prefix_cache=True), "snapshot"),
    (dict(speculative="ngram"), "rewound"),
])
def test_engine_refuses_what_needs_state_snapshots(params, kwargs, piece):
    """(vii)"""
    with pytest.raises(ValueError, match=piece):
        engine(params, **kwargs)


@pytest.mark.parametrize("cls", [PrefillEngine, DecodeEngine])
def test_disaggregated_pools_refuse_a_model_with_slot_state(params, cls):
    """(vii)"""
    with pytest.raises(ValueError, match="transfer of its slot's state"):
        cls(model(), params, EngineConfig(
            slots=2, chunk_buckets=(4, 8), page_size=4, prefix_cache=False))


def test_page_transfer_refuses_a_cache_with_slot_leaves(params):
    eng = engine(params)
    NP = eng.page_allocator.num_pages
    with pytest.raises(ValueError, match="moves pages only"):
        PageTransfer(NP, NP).move(eng.cache, eng.cache, [1], [2])


def test_lockstep_generate_is_refused_with_the_reason(params):
    from mpi_operator_tpu.models.generate import decode_model
    with pytest.raises(ValueError, match="driven by the serving engine"):
        decode_model(model()).apply({"params": params},
                                    jnp.zeros((1, 4), jnp.int32),
                                    mutable=["cache"])


# -- the kernel's lower bound -----------------------------------------------

def _dense_window(q, pool, cur, table, window, scale):
    """Attention of q [B, H, D] over the paged rows, positions
    cur - window < p <= cur, gathered and dense."""
    B, H, D = q.shape
    NP, ps, Wd = pool.shape
    KV = Wd // (2 * D)
    rows = pool[table].reshape(B, -1, KV, 2, D).astype(jnp.float32)
    k = jnp.repeat(rows[:, :, :, 0], H // KV, axis=2)
    v = jnp.repeat(rows[:, :, :, 1], H // KV, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32), k) * scale
    p = jnp.arange(rows.shape[1])[None, None]
    seen = (p <= cur[:, None, None]) & (p > cur[:, None, None] - window)
    return jnp.einsum("bht,bthd->bhd",
                      jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v)


@pytest.mark.parametrize("cursors", [
    (0, 7, 8), (15, 16, 17), (23, 24, 25), (31, 32, 33), (63, 40, 5)])
def test_kernel_window_matches_the_dense_form_around_page_and_window_edges(
        cursors):
    """(viii): pages of 8, a window of 24 (three pages): cursors on both
    sides of a page's edge (7|8, 15|16, 31|32) and of the window's (23|24:
    the first cursor that leaves position 0 behind), interpreted."""
    B, H, KV, D, ps, nblk, window = 3, 4, 2, 16, 8, 8, 24
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (B, H, D), jnp.float32)
    k, v = (jax.random.normal(kk, (1 + B * nblk, ps, KV, D), jnp.float32)
            for kk in keys[1:])
    pool = pack_kv_rows(k, v)
    table = 1 + jnp.arange(B * nblk, dtype=jnp.int32).reshape(B, nblk)[::-1]
    cur = jnp.asarray(cursors, jnp.int32)
    got = paged_decode_attention(q, pool, cur, table, window=window,
                                 sm_scale=0.3)
    want = _dense_window(q, pool, cur, table, window, 0.3)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # pages behind the window are not read: poison them
    behind = jnp.arange(nblk)[None] < (
        jnp.maximum(cur - window + 1, 0) // ps)[:, None]
    dead = jnp.zeros((pool.shape[0],), bool).at[
        jnp.where(behind, table, 0).reshape(-1)].set(True).at[0].set(False)
    poisoned = jnp.where(dead[:, None, None], jnp.nan, pool)
    again = paged_decode_attention(q, poisoned, cur, table, window=window,
                                   sm_scale=0.3)
    assert float(jnp.abs(again - want).max()) < 1e-5


@pytest.mark.parametrize("cursors", [
    (0, 5, 22),         # contexts shorter than the window: nothing wraps yet
    (23, 24, 31, 32),   # the window's edge, and the ring's first wrap
    (100, 77, 250, 39),  # rings that have wrapped many times, unrelated rows
    (41, 44, 47, 48),   # windows that start mid-page and on a page's edge
], ids=["short", "first-wrap", "wrapped", "mid-page"])
@pytest.mark.parametrize("H,KV,D", [(8, 2, 128), (4, 2, 16)],
                         ids=["pairs128", "gqa16"])
def test_kernel_walks_a_ring_whose_pages_wrap(H, KV, D, cursors):
    """A window layer's decode step as `_ring` makes it: the ring
    [B, 4 pages of 8, width] holds position p at p % 32, the table is the
    ring's pages counted from the window's first, the cursor counted from
    that page. Against dense attention over the absolute positions
    cur - 24 < p <= cur; what the ring holds of older positions, and its
    rows past the cursor, are inf or huge where the kernel may not look."""
    ps, nr, window = 8, 4, 24
    R, B = nr * ps, len(cursors)
    cur = jnp.asarray(cursors, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (B, H, D), jnp.float32)
    T = int(cur.max()) + 1
    k, v = (jax.random.normal(kk, (B, T, KV, D), jnp.float32)
            for kk in keys[1:])
    rows = pack_kv_rows(k, v)                               # [B, T, width]
    # the ring after position cur was written: slot i holds the newest
    # position <= cur that is i modulo R, if there is one
    slots = jnp.arange(R)[None]
    held = cur[:, None] - (cur[:, None] - slots) % R              # [B, R]
    ring = jnp.take_along_axis(rows, jnp.maximum(held, 0)[..., None], 1)
    first = jnp.maximum(cur - window + 1, 0) // ps
    # a page wholly outside the window holds inf; a row past the cursor
    # in the cursor's page something huge and finite (it is masked)
    page_of = held // ps
    outside = (held < 0) | (page_of < first[:, None])
    in_cursors_page = slots // ps == (cur // ps % nr)[:, None]
    ring = jnp.where(outside[..., None],
                     jnp.where(in_cursors_page[..., None], 1e4, jnp.inf),
                     ring)
    table = (jnp.arange(B)[:, None] * nr
             + (first[:, None] + jnp.arange(nr)[None]) % nr)
    got = paged_decode_attention(
        q, ring.reshape(B * nr, ps, -1), cur - first * ps, table,
        window=window, sm_scale=0.3)
    kk = jnp.repeat(k, H // KV, axis=2)
    vv = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q, kk) * 0.3
    p = jnp.arange(T)[None, None]
    seen = (p <= cur[:, None, None]) & (p > cur[:, None, None] - window)
    want = jnp.einsum("bht,bthd->bhd",
                      jax.nn.softmax(jnp.where(seen, s, -1e30), -1), vv)
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_without_a_window_the_kernel_lowers_to_the_program_it_was():
    """The lower bound is static: `window=None` (and the default scale)
    traces the body it always did, with no comparison against a bound."""
    q = jnp.zeros((2, 4, 16)); pool = jnp.zeros((9, 8, 64))
    cur = jnp.zeros((2,), jnp.int32); table = jnp.zeros((2, 4), jnp.int32)
    plain = jax.make_jaxpr(lambda *a: paged_decode_attention(*a))(
        q, pool, cur, table)
    named = jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, window=None, sm_scale=None))(q, pool, cur, table)
    bound = jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, window=16))(q, pool, cur, table)
    assert str(plain) == str(named) != str(bound)


def test_paged_attend_matches_dense_attention_over_the_gathered_table(
        monkeypatch):
    B, S, H, KV, D, ps, nblk = 4, 6, 4, 2, 16, 4, 12
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (B, S, H, D), jnp.float32)
    k, v = (jax.random.normal(kk, (1 + B * nblk, ps, KV, D), jnp.float32)
            for kk in keys[1:])
    pool = pack_kv_rows(k, v)
    table = 1 + jnp.arange(B * nblk, dtype=jnp.int32).reshape(B, nblk)
    start = jnp.asarray([0, 9, 30, nblk * ps], jnp.int32)   # the last: junk
    pos = jnp.minimum(start[:, None] + jnp.arange(S)[None], nblk * ps)
    want = jnp.stack([_dense_window(q[:, s], pool, pos[:, s], table,
                                    10 ** 6, 0.25) for s in range(S)], 1)
    for scores, turn in ((1 << 26, 512), (2 * H * S * 8, 8)):
        monkeypatch.setattr(attention, "_PAGED_SCORES", scores)
        monkeypatch.setattr(attention, "_PAGED_TURN", turn)
        got = paged_attend(q, pool, pos, table, 0.25)
        assert float(jnp.abs(got - want)[:3].max()) < 1e-5


# -- the recurrence ---------------------------------------------------------

def _scan_inputs(G=2, T=9, Din=12, N=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (G, T, Din))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (G, T, Din)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (N, Din)) * 0.3)
    Bm, Cm = (jax.random.normal(k, (G, T, N)) for k in ks[3:5])
    D = jnp.ones((Din,))
    s0 = jax.random.normal(ks[5], (G, N, Din))
    return x, delta, A, Bm, Cm, D, s0


def test_selective_scan_over_a_chunk_is_the_steps_one_by_one():
    x, delta, A, Bm, Cm, D, s = _scan_inputs()
    y, last = ssm.selective_scan(x, delta, A, Bm, Cm, D, s)
    ys = []
    for t in range(x.shape[1]):
        y_t, s = ssm.selective_scan(x[:, t:t + 1], delta[:, t:t + 1], A,
                                    Bm[:, t:t + 1], Cm[:, t:t + 1], D, s)
        ys.append(y_t)
    assert float(jnp.abs(y - jnp.concatenate(ys, 1)).max()) < 1e-5
    assert float(jnp.abs(last - s).max()) < 1e-5


def test_a_step_of_zero_holds_the_state_to_the_bit():
    x, delta, A, Bm, Cm, D, s = _scan_inputs(seed=1)
    _, held = ssm.selective_scan(x, jnp.zeros_like(delta), A, Bm, Cm, D, s)
    assert np.array_equal(np.asarray(held), np.asarray(s))


def test_causal_conv_carries_its_tail_from_chunk_to_chunk():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (2, 10, 6))
    w, b = jax.random.normal(ks[1], (4, 6)), jax.random.normal(ks[2], (6,))
    zeros = jnp.zeros((2, 3, 6))
    whole, _ = ssm.causal_conv(x, zeros, w, b, jnp.asarray([10, 10]))
    first, tail = ssm.causal_conv(x[:, :6], zeros, w, b, jnp.asarray([6, 4]))
    assert float(jnp.abs(first - whole[:, :6]).max()) < 1e-6
    # row 0 took 6 real inputs, row 1 only 4: each tail ends where its
    # real inputs did
    assert np.array_equal(np.asarray(tail[0]), np.asarray(x[0, 3:6]))
    assert np.array_equal(np.asarray(tail[1]), np.asarray(x[1, 1:4]))
    rest, _ = ssm.causal_conv(x[:, 6:], tail, w, b, jnp.asarray([4, 4]))
    assert float(jnp.abs(rest[0] - whole[0, 6:]).max()) < 1e-6
    _, kept = ssm.causal_conv(x[:, :6], tail, w, b, jnp.asarray([0, 0]))
    assert np.array_equal(np.asarray(kept), np.asarray(tail))


# -- the host's side of the contract ----------------------------------------

#: plans as the parent commit made them, for buckets (32, 128, 512) and
#: (8,): a model without state keeps them byte for byte
PLANS = {
    (0, (32, 128, 512), 0): [],
    (5, (32, 128, 512), 0): [(0, 32)],
    (100, (32, 128, 512), 0): [(0, 128)],
    (600, (32, 128, 512), 0): [(0, 512), (472, 128)],
    (1030, (32, 128, 512), 0): [(0, 512), (512, 512), (998, 32)],
    (300, (32, 128, 512), 64): [(64, 512)],
    (20, (8,), 0): [(0, 8), (8, 8), (12, 8)],
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_plan_chunks_is_unchanged_for_a_model_without_state(case):
    """(ix)"""
    n, buckets, start = case
    assert plan_chunks(n, buckets, start=start) == PLANS[case]
    assert plan_chunks(n, buckets, start, True) == PLANS[case]


@pytest.mark.parametrize("n,buckets,want", [
    (600, (32, 128, 512), [(0, 512), (512, 128)]),
    (1030, (32, 128, 512), [(0, 512), (512, 512), (1024, 32)]),
    (20, (8,), [(0, 8), (8, 8), (16, 8)]),
    (5, (32, 128), [(0, 32)]),
])
def test_plan_chunks_without_overlap_walks_left_to_right(n, buckets, want):
    plan = plan_chunks(n, buckets, overlap=False)
    assert plan == want
    covered = [p for w, size in plan for p in range(w, min(w + size, n))]
    assert covered == list(range(n))          # each position once


def test_idle_rows_of_a_decode_step_sit_at_the_junk_position():
    from mpi_operator_tpu.serve.scheduler import RequestState
    slots = SlotManager(4)
    deco = RequestState(req=Request(0, [1, 2, 3], 5), slot=0, pos=2,
                        next_input=3)
    pre = RequestState(req=Request(1, [1] * 9, 5), slot=2, pos=4,
                       chunks=[(4, 4)])
    slots.bind(deco), slots.bind(pre)
    assert list(slots.step_arrays()[1]) == [2, 0, 4, 0]
    assert list(slots.step_arrays(idle_pos=64)[1]) == [2, 64, 64, 64]
    assert slots.step_arrays(idle_pos=64)[-1] == [deco]


def test_spans_and_telemetry_name_the_slot_state(params):
    from mpi_operator_tpu.telemetry import spans
    from mpi_operator_tpu.telemetry.worker import ServeTelemetry
    tel = ServeTelemetry()
    eng = ServingEngine(model(), params, EngineConfig(
        slots=2, chunk_buckets=(4, 8), page_size=4, prefix_cache=False),
        telemetry=tel)
    assert tel.slot_state_bytes.value == eng.slot_state_bytes() > 0
    spans.clear()
    eng.run(requests([(14, 3), (6, 3), (11, 3)], seed=4))
    assert tel.slot_state_starts.value == 3
    rows = [s.attrs["state_rows"] for s in spans.records()
            if s.name == "serve.prefill"]
    assert sum(rows) == 3 and 0 in rows
