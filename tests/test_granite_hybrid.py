"""Granite-4.0-H through the serving engine at toy widths (three layers:
mamba, attention, mamba; two state-space heads of 64 channels, which share
one lane tile; 4 query heads on 2 key heads; 8 router outputs of which 4
are held, 3 picks), against the plain reference of
`perfbench/reference/granite_hybrid.py` on seeded random weights.

Everything here is float32 on the CPU, program and reference alike, so a
tolerance is what summation order costs: 2e-5 on log-probabilities and on
the distance of a served token's logit from the reference's best (logits
here are of order 0.2). What is compared is logits, not tokens.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models.granite_hybrid import (
    PUBLISHED_LAYER_TYPES, GraniteHybridConfig, GraniteHybridLayer,
    GraniteHybridLM)
from mpi_operator_tpu.ops import ssm
from mpi_operator_tpu.parallel import held_experts
from mpi_operator_tpu.serve import (DecodeEngine, EngineConfig, PrefillEngine,
                                    Request, ServingEngine)
from perfbench import weights_granite4hs as W
from perfbench.reference import granite_hybrid as ref
from prefill_forms import member_rows_alone_leave_what_all_rows_leave

TOL = 2e-5
PUBLISHED = GraniteHybridConfig()
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")
TYPES = ("mamba", "attention", "mamba")
CONFIG = {
    "position_embedding_type": "nope", "normalization_function": "rmsnorm",
    "hidden_act": "silu", "tie_word_embeddings": True,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "rope_scaling": None,
    "layer_types": list(TYPES), "num_hidden_layers": 3, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 32, "shared_intermediate_size": 48,
    "num_local_experts": 4, "num_experts_per_tok": 3, "vocab_size": 97,
    "rms_norm_eps": 1e-5, "mamba_n_heads": 2, "mamba_d_head": 64,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_expand": 2,
    **{m: getattr(PUBLISHED, m) for m in MULTIPLIERS},
    # steps of 0.03 to 0.5, as tests/test_falcon_h1.py: with 16 states a
    # head the state's share of y would otherwise be hard to see; weights
    # of 0.16, so that sqrt(hidden) x std is the published widths' 1.28
    # and attention scores are of order 1 here too
    "assumed": {"head_dim": 16, "num_local_experts_published": 8,
                "held_first_expert": 0, "initializer_range": 0.16,
                "conv_std": 0.3, "dt_min": 0.03, "dt_max": 0.5}}
DIMS = W.Dims.from_config(CONFIG)


def config(max_len=64, held=(0, 4), **kw):
    return GraniteHybridConfig(
        vocab_size=97, max_len=max_len, layer_types=TYPES, hidden_size=64,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=32,
        shared_intermediate_size=48, num_local_experts=8,
        num_experts_per_tok=3, mamba_n_heads=2, mamba_d_head=64,
        mamba_d_state=16, mamba_chunk_size=8, held=held, dtype=jnp.float32,
        **kw)


def model(max_len=64, **kw):
    return GraniteHybridLM(config(max_len, **kw))


@pytest.fixture(scope="module")
def params():
    return W.make_params(W.seed_key(3), DIMS, jnp.float32)


def engine(params, slots=3, page_size=4, kernel=False, max_len=64,
           served=None, **kw):
    cfg = dict(slots=slots, chunk_buckets=(8,), page_size=page_size,
               prefix_cache=False, decode_kernel=kernel)
    cfg.update(kw)
    return ServingEngine(served or model(max_len), params,
                         EngineConfig(**cfg))


def requests(shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(id=i, prompt=rng.integers(0, 97, n).tolist(),
                    max_new_tokens=k) for i, (n, k) in enumerate(shapes)]


def gaps(params, reqs, results, **kw):
    """Widest distance of a served token's reported log-probability from
    the reference's, and of its reference logit from the reference's
    best, over every served token."""
    worst = 0.0
    for r in reqs:
        toks = results[r.id].tokens
        assert len(toks) == r.max_new_tokens
        logits = ref.forward(params, jnp.asarray([list(r.prompt) + toks]),
                             DIMS, **kw)[0]
        at = len(r.prompt) - 1 + np.arange(len(toks))
        logp = np.asarray(jax.nn.log_softmax(logits, -1))[at, toks]
        best = np.asarray(logits.max(-1))[at] - np.asarray(logits)[at, toks]
        worst = max(worst, float(best.max()), float(np.abs(
            logp - np.asarray(results[r.id].logprobs)).max()))
    return worst


def test_the_defaults_are_the_published_configuration():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(json.loads(ln) for ln in f if json.loads(ln)["name"]
                         == "granite-4.0-h-small")["config"]
    names = {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
             "num_heads": "num_attention_heads",
             "num_kv_heads": "num_key_value_heads",
             "intermediate_size": "intermediate_size",
             "shared_intermediate_size": "shared_intermediate_size",
             "num_local_experts": "num_local_experts",
             "num_experts_per_tok": "num_experts_per_tok",
             "rms_norm_eps": "rms_norm_eps",
             "mamba_n_heads": "mamba_n_heads", "mamba_d_head": "mamba_d_head",
             "mamba_d_state": "mamba_d_state",
             "mamba_n_groups": "mamba_n_groups",
             "mamba_d_conv": "mamba_d_conv",
             "mamba_chunk_size": "mamba_chunk_size",
             **{m: m for m in MULTIPLIERS}}
    for ours, theirs in names.items():
        assert getattr(PUBLISHED, ours) == published[theirs], ours
    assert list(PUBLISHED_LAYER_TYPES) == published["layer_types"]
    assert PUBLISHED.num_layers == published["num_hidden_layers"] == 40
    assert PUBLISHED.head_dim * PUBLISHED.num_heads == PUBLISHED.hidden_size
    assert PUBLISHED.mamba_d_ssm == published["mamba_expand"] * 4096
    assert PUBLISHED.conv_dim == 8448


def test_whole_sequence_forward_matches_the_reference(params):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 97)
    got = model().apply({"params": params}, toks)
    want = ref.forward(params, toks, DIMS)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(want.std()) > 0.05        # logits that tell tokens apart


@pytest.mark.parametrize("kernel,page_size", [(False, 4), (True, 8)])
def test_chunked_prefill_then_decode_matches_the_reference(params, kernel,
                                                           page_size):
    """Prompts through one to four chunks of 8 with padded tails, a prompt
    of one token that decodes at position 0, contexts of 50 over a dozen
    pages, five requests over three slots so that rows prefill while
    others decode and slots are used again: the attention layer's pool,
    the mamba layers' state and conv tail all carried. With the kernel
    (interpreted) a decode step reads its pool through
    `paged_decode_attention` and updates its state through the Pallas
    kernel over tiles of two heads."""
    eng = engine(params, kernel=kernel, page_size=page_size)
    reqs = requests([(30, 20), (7, 30), (19, 12), (1, 9), (23, 25)])
    results = eng.run(reqs)
    assert gaps(params, reqs, results) < TOL
    assert eng.compile_counts()["prefill"] == 1
    assert eng.compile_counts()["step"] == 1


@pytest.mark.parametrize("depth", [3, 8])
def test_steps_dispatched_ahead_serve_the_same_tokens(params, depth):
    """`EngineConfig.async_depth`: the benchmark's cell keeps eight steps
    dispatched and unfetched. Five requests over two slots: a row is
    admitted onto a slot whose last occupant's final tokens are still
    unfetched, its state starts from zeros, and every served token is the
    double-buffered loop's and the reference's."""
    reqs = requests([(30, 20), (7, 30), (19, 12), (1, 9), (23, 25)])
    want = engine(params, slots=2).run(reqs)
    got = engine(params, slots=2, async_depth=depth).run(reqs)
    assert all(got[r.id].tokens == want[r.id].tokens for r in reqs)
    assert gaps(params, reqs, got) < TOL


def test_two_requests_in_turn_through_one_slot_start_from_zeros(params):
    """Admission onto a used slot: no reset program runs between them; the
    second's first chunk starts at 0, and its one-token sibling decodes
    at 0 over a state and a tail that are not zeros."""
    eng = engine(params, slots=1)
    reqs = requests([(21, 10), (13, 10), (1, 6)], seed=2)
    assert gaps(params, reqs, eng.run(reqs)) < TOL


def _slot_leaves(cache):
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat
            if p[-1].key in GraniteHybridLM.SLOT_STATE}


def test_junk_rows_and_pad_tokens_leave_slot_state_exactly_as_it_was(params):
    """A decode step over a row at `max_len`, a prefill call the row is no
    member of, and the pads after a member's real tokens."""
    eng = engine(params)
    eng.run(requests([(20, 4), (9, 4), (15, 4)], seed=7))   # state != 0
    before = _slot_leaves(eng.cache)
    assert len(before) == 4            # state and tail, two mamba layers
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


def test_a_layer_holds_what_its_kind_caches(params):
    """Mamba layers: two slot leaves and no pages, the state two heads to
    a lane tile; the attention layer: pages and no slot state."""
    small, large = engine(params, max_len=64), engine(params, max_len=256)
    for eng in (small, large):
        NP = eng.page_allocator.num_pages
        pooled = [x for x in jax.tree.leaves(eng.cache) if x.shape[0] == NP]
        assert [x.shape for x in pooled] == [(NP, 4, 2 * 2 * 16)]
        assert eng.page_bytes() == 4 * 64 * 4
        for l in (0, 2):
            layer = eng.cache[f"layer_{l}"]
            assert set(layer) == {"mamba"}
            assert set(layer["mamba"]) == {"ssm", "conv"}
            # [slots, H / 2, N, 2 P]: two heads of 64 fill 128 lanes
            assert layer["mamba"]["ssm"].shape == (3, 1, 16, 128)
            assert layer["mamba"]["ssm"].dtype == jnp.float32
            assert layer["mamba"]["conv"].shape == (3, 3, 128 + 2 * 16)
        assert set(eng.cache["layer_1"]) == {"attn"}
        assert set(eng.cache["layer_1"]["attn"]) == {"cached_kv"}
    # two layers' state of 2 x 64 x 16 and conv tail of 3 x 160, float32
    # here; what a slot holds does not grow with the context it may reach
    want = 2 * (2 * 64 * 16 + 3 * 160) * 4
    assert small.slot_state_bytes() == large.slot_state_bytes() == want


def test_the_published_sizes_give_the_issues_bytes_a_slot():
    c = PUBLISHED
    shape = ssm.ssd_state_shape(1, c.mamba_n_heads, c.mamba_d_head,
                                c.mamba_n_groups, c.mamba_d_state)
    assert shape == (1, 64, 128, 128)           # no lane of a tile empty
    state = int(np.prod(shape)) * 4
    tail = (c.mamba_d_conv - 1) * c.conv_dim * 2
    assert (state, tail, 9 * (state + tail)) == (4194304, 50688, 38204928)
    assert c.num_kv_heads * 2 * c.head_dim * 2 == 4096     # a cached token
    # Falcon-H1's head fills a tile alone and keeps its layout
    assert ssm.ssd_state_shape(1, 32, 128, 2, 256) == (1, 32, 256, 128)


def _prefill_text(eng):
    S, nblk = eng.config.slots, eng._nblk
    z = lambda *s: jnp.zeros(s, jnp.int32)                   # noqa: E731
    return eng._prefill.lower(eng.params, eng.cache, z(S), z(S, 8), z(S),
                              z(S, nblk), z(S)).as_text(debug_info=True)


def test_the_programs_carry_the_scopes_the_trace_is_split_by(params):
    eng = engine(params)
    scopes = set(eng.decode_step_scopes().values())
    for name in ("ssd.project", "ssd.conv", "ssd.update", "ssd.norm",
                 "ssd.out", "g4attn.qkv", "g4attn.cache_write",
                 "g4attn.attend", "g4attn.out", "moe.route", "moe.experts",
                 "moe.shared", "/head/"):
        assert any(name in s for s in scopes), name
    assert not any("ssd.chunk" in s for s in scopes)
    assert any("layer_2" in s and "ssd.update" in s for s in scopes)
    assert any("layer_1" in s and "g4attn.attend" in s for s in scopes)
    assert not any("layer_1" in s and "ssd." in s for s in scopes)
    # prefill: the chunked scan, and nothing after the last layer's mixer
    text = _prefill_text(eng)
    assert "ssd.chunk" in text and "ssd.update" not in text
    assert "layer_1/moe" in text and "layer_2/mamba" in text
    for gone in ("layer_2/moe", "final_layernorm", "/head"):
        assert gone not in text, gone


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_matters(params, name):
    """Set to 1 in the program (the reference keeps the published value),
    the comparison fails, by hundreds of tolerances: none is folded away
    by a norm, and the seeded weights make each load-bearing."""
    served = GraniteHybridLM(dataclasses.replace(config(), **{name: 1.0}))
    eng = engine(params, served=served)
    reqs = requests([(19, 6), (9, 6)], seed=4)
    assert gaps(params, reqs, eng.run(reqs)) > 100 * TOL


def test_a_position_term_on_the_attention_layer_fails_the_comparison(params):
    """`position_embedding_type` nope: a reference that rotates q and k
    (rotate-half, theta 1e4) is another model, by hundreds of
    tolerances."""
    def rotary(x, positions, theta=1e4):
        half = x.shape[-1] // 2
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = positions[:, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               -1)
    eng = engine(params)
    reqs = requests([(19, 6), (9, 6)], seed=4)
    results = eng.run(reqs)
    assert gaps(params, reqs, results) < TOL
    assert gaps(params, reqs, results, rotary=rotary) > 100 * TOL


def test_the_two_shares_add_up_to_the_uncut_layer():
    """The guide's share test. The uncut layer holds all 8 experts; the
    program's two shares hold experts 0..3 and 4..7 of the SAME weights.
    Each computes x' + 0.22 (its routed part + Shared): the two routed
    parts and the shared expert counted ONCE give the uncut reference's
    layer, for a mamba layer and for the attention layer."""
    whole = dataclasses.replace(DIMS, held=(0, 8))
    key = W.seed_key(11)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    for index, kind in ((0, "mamba"), (1, "attention")):
        p = W.layer_params(key, whole, index, jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = ref.layer(p, x[0], whole)
            alone = ref.layer(p, x[0], whole, held=(0, 0))  # x' + 0.22 Shared
        parts = []
        for first in (0, 4):
            mine = jax.tree.map(lambda a: a, p)
            for name in ("gate", "up", "down"):
                mine["moe"][name] = p["moe"][name][first:first + 4]
            got = GraniteHybridLayer(config(held=(first, 4)), kind).apply(
                {"params": mine}, x)[0]
            parts.append(got - alone)
            # a share alone is NOT the layer: the other half is missing
            assert float(jnp.abs(got - want).max()) > 100 * TOL
        assert float(jnp.abs(alone + parts[0] + parts[1] - want).max()) < TOL


def _by_hand(logits, k):
    """top-k-then-softmax written out: the k largest logits of a row,
    ties to the lower index, softmax over those alone."""
    idx, w = [], []
    for row in np.asarray(logits, np.float64):
        order = sorted(range(len(row)), key=lambda i: (-row[i], i))[:k]
        e = np.exp(row[order] - row[order].max())
        idx.append(order)
        w.append(e / e.sum())
    return np.asarray(idx), np.asarray(w)


@pytest.mark.parametrize("case", ["random", "ties", "published_width"])
def test_the_gate_is_top_k_of_the_logits_then_a_softmax_over_those(case):
    rng = np.random.default_rng(9)
    if case == "random":
        logits, k = rng.normal(size=(16, 8)), 3
    elif case == "ties":
        # whole rows of equal logits, and ties across the k-th place
        logits = np.round(rng.normal(size=(16, 8))) + 0.0     # no -0.0
        logits[0] = 0.0
        logits[1, :] = [1, 1, 1, 1, 0, 0, 2, 2]
        k = 3
    else:
        logits, k = 1.28 * rng.normal(size=(48, 72)), 10
    idx, w = held_experts.route(jnp.asarray(logits, jnp.float32), None, k,
                                1.0, over="picks")
    want_idx, want_w = _by_hand(logits, k)
    assert np.array_equal(np.asarray(idx), want_idx)
    assert np.abs(np.asarray(w) - want_w).max() < 1e-6
    assert np.abs(np.asarray(w).sum(-1) - 1.0).max() < 1e-6
    # the other rule, softmax over EVERY output, gives other weights
    _, w_all = held_experts.route(jnp.asarray(logits, jnp.float32),
                                  jnp.zeros(logits.shape[1]), k, 1.0)
    assert np.asarray(w_all).sum(-1).max() < 0.999
    with pytest.raises(ValueError, match="flat and has no bias"):
        held_experts.route(jnp.zeros((2, 8)), jnp.zeros(8), 3, 1.0,
                           over="picks")


def _plain_update(x, dt, A, B, C, D, state, fresh):
    """The recurrence in plain jax.numpy over the state [G, H, N, P]."""
    G, H, P = x.shape
    K, N = B.shape[1:]
    s = jnp.where(fresh[:, None, None, None], 0.0, state)
    Bh, Ch = (jnp.repeat(a, H // K, axis=1) for a in (B, C))   # [G, H, N]
    s = jnp.exp(dt * A)[..., None, None] * s \
        + Bh[..., None] * (dt[..., None] * x)[:, :, None, :]
    return jnp.sum(s * Ch[..., None], axis=2) + D[:, None] * x, s


@pytest.mark.parametrize("name,heads,head_dim,groups,states,form", [
    ("granite", 128, 64, 1, 128, "pallas_ssd_update[P-minor,heads=2]"),
    ("falcon_h1", 32, 128, 2, 256, "pallas_ssd_update[P-minor]"),
])
def test_the_state_update_kernel_at_each_models_shape(
        name, heads, head_dim, groups, states, form):
    """`ssd_state_update`, the kernel interpreted, against plain
    `jax.numpy`: a fresh row among carried ones, a row whose dt is 0 (its
    state held exactly), at Granite's [64 channels, 128 states] x 128
    heads x 1 group (two heads a tile) and at Falcon-H1's, unchanged."""
    from mpi_operator_tpu.ops.attention import record_traced
    G = 3
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    x = jax.random.normal(ks[0], (G, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (G, heads)) - 2.0)
    dt = dt.at[2].set(0.0)
    A = -(1.0 + 15.0 * jax.random.uniform(ks[2], (heads,)))
    B, C = (jax.random.normal(k, (G, groups, states)) for k in ks[3:5])
    D = jnp.linspace(0.5, 1.5, heads)
    plain = jax.random.normal(ks[5], (G, heads, states, head_dim))
    fresh = jnp.asarray([False, True, False])
    held_shape = ssm.ssd_state_shape(G, heads, head_dim, groups, states)
    held = ssm._tiles_of(plain, held_shape[1])
    assert held.shape == held_shape and held_shape[-1] == 128
    assert np.array_equal(ssm._heads_of(held, heads), plain)
    want_y, want_s = _plain_update(x, dt, A, B, C, D, plain, fresh)
    with record_traced() as traced:
        y, s = ssm.ssd_state_update(x, dt, A, B, C, D, held, fresh=fresh,
                                    interpret=True)
    assert traced["ssd"] == {form}
    assert s.shape == held_shape
    assert float(jnp.abs(y - want_y).max()) < 1e-4
    assert float(jnp.abs(ssm._heads_of(s, heads) - want_s).max()) < 1e-5
    assert np.array_equal(np.asarray(s[2]), np.asarray(held[2]))  # dt 0
    # off the chip and not interpreted: plain jax.numpy, the same layout
    with record_traced() as traced:
        y2, s2 = ssm.ssd_state_update(x, dt, A, B, C, D, held, fresh=fresh)
    assert traced["ssd"] == {"dense"}
    assert float(jnp.abs(y2 - want_y).max()) < 1e-4
    assert float(jnp.abs(s2 - s).max()) < 1e-5


def test_the_chunk_scan_carries_the_packed_state_as_the_steps_do():
    """`ssd_chunk_scan` over 20 positions in chunks of 8 from a carried
    state held two heads a tile, against twenty steps of the update."""
    G, H, P, N = 2, 4, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(ks[0], (G, 20, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (G, 20, H)) - 2.0)
    A = -(1.0 + 15.0 * jax.random.uniform(ks[2], (H,)))
    B, C = (jax.random.normal(k, (G, 20, 1, N)) for k in ks[3:5])
    D = jnp.ones((H,))
    s0 = jax.random.normal(ks[5], ssm.ssd_state_shape(G, H, P, 1, N))
    assert s0.shape == (G, 2, N, 128)
    y, last = ssm.ssd_chunk_scan(x, dt, A, B, C, D, s0, chunk=8)
    s, ys = s0, []
    for t in range(20):
        y_t, s = ssm.ssd_state_update(x[:, t], dt[:, t], A, B[:, t],
                                      C[:, t], D, s)
        ys.append(y_t)
    assert last.shape == s0.shape
    assert float(jnp.abs(y - jnp.stack(ys, 1)).max()) < 1e-4
    assert float(jnp.abs(last - s).max()) < 1e-4


@pytest.mark.parametrize("kwargs,piece", [
    (dict(prefix_cache=True), "snapshot"),
    (dict(speculative="ngram"), "rewound"),
    (dict(cls=PrefillEngine), "transfer of its slot's state"),
    (dict(cls=DecodeEngine), "transfer of its slot's state"),
])
def test_engine_refuses_what_needs_state_snapshots(params, kwargs, piece):
    """`_refuse_for_slot_state` for this model as for Falcon-H1: the
    prefix cache, speculation, and the two disaggregated pools; preemption
    has no switch of its own (a row's state is never dropped)."""
    cls = kwargs.pop("cls", ServingEngine)
    cfg = dict(slots=2, chunk_buckets=(8,), page_size=4, prefix_cache=False)
    cfg.update(kwargs)
    with pytest.raises(ValueError, match=piece):
        cls(model(), params, EngineConfig(**cfg))


def test_the_step_counters_count_picks_on_the_held_experts(params):
    """`STEP_COUNTERS`: over the three layers, picks that fell on the four
    held experts of eight (3 picks a row) and the layers' largest loads."""
    from mpi_operator_tpu.telemetry.worker import ServeTelemetry
    seen = {"moe_held_picks": [], "moe_load_max": []}

    class Rec:
        def __init__(self, name):
            self.name = name

        def observe(self, v):
            seen[self.name].append(float(v))
    tel = ServeTelemetry()
    tel.step_counters.update({n: Rec(n) for n in seen})
    eng = ServingEngine(model(), params, EngineConfig(
        slots=3, chunk_buckets=(8,), page_size=4, prefix_cache=False),
        telemetry=tel)
    eng.run(requests([(9, 8), (12, 8), (5, 8)], seed=1))
    assert GraniteHybridLM.STEP_COUNTERS == tuple(seen)
    assert seen["moe_held_picks"] and seen["moe_load_max"]
    # 3 rows x 3 picks x 3 layers at most, about half of them held
    assert 0 < max(seen["moe_held_picks"]) <= 27
    assert all(m <= p for m, p in zip(seen["moe_load_max"],
                                      seen["moe_held_picks"]))
    assert tel.slot_state_bytes.value == eng.slot_state_bytes() > 0
