"""Qwen3-Next through the serving engine at toy widths (four layers: three
delta-rule layers and one gated attention layer; 2 key heads on 4 value
heads of 16 x 24; 4 query heads on 2 key heads of 32, rotary over 8 of
them; 8 router outputs of which 4 are held, 3 picks; a gated shared
expert), against the plain reference of `perfbench/reference/qwen3_next.py`
on seeded random weights.

Everything here is float32 on the CPU, program and reference alike, so a
tolerance is what summation order costs through four layers: 2e-4 on
log-probabilities and on the distance of a served token's logit from the
reference's best (logits here are of order 1.3). What is compared is
logits, not tokens.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                Qwen3NextLayer, Qwen3NextLM)
from mpi_operator_tpu.parallel import held_experts
from mpi_operator_tpu.serve import (DecodeEngine, EngineConfig, PrefillEngine,
                                    Request, ServingEngine)
from perfbench import weights_qwen3next as W
from perfbench.reference import qwen3_next as ref
from prefill_forms import member_rows_alone_leave_what_all_rows_leave

TOL = 2e-4
PUBLISHED = Qwen3NextConfig()
CONFIG = {
    "hidden_act": "silu", "tie_word_embeddings": False,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "use_sliding_window": False, "rope_scaling": None,
    "num_hidden_layers": 4, "full_attention_interval": 4, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 24,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 48, "num_experts": 4,
    "num_experts_per_tok": 3, "vocab_size": 97, "rms_norm_eps": 1e-6,
    # steps of 0.03 to 0.5, as tests/test_granite_hybrid.py, so that a
    # head forgets inside a test's few dozen positions; weights of 0.16, so
    # that sqrt(hidden) x std is the published widths' 0.9 and more
    "assumed": {"num_experts_published": 8, "held_first_expert": 0,
                "initializer_range": 0.16, "conv_std": 0.3, "dt_min": 0.03,
                "dt_max": 0.5}}
DIMS = W.Dims.from_config(CONFIG)


def config(max_len=64, held=(0, 4), **kw):
    return Qwen3NextConfig(
        vocab_size=97, max_len=max_len, num_layers=4, hidden_size=64,
        num_heads=4, num_kv_heads=2, head_dim=32, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=24, moe_intermediate_size=32,
        shared_expert_intermediate_size=48, num_experts=8,
        num_experts_per_tok=3, held=held, dtype=jnp.float32, **kw)


def model(max_len=64, **kw):
    return Qwen3NextLM(config(max_len, **kw))


@pytest.fixture(scope="module")
def params():
    return W.make_params(W.seed_key(3), DIMS, jnp.float32)


def engine(params, slots=3, page_size=4, kernel=False, max_len=64, **kw):
    cfg = dict(slots=slots, chunk_buckets=(8,), page_size=page_size,
               prefix_cache=False, decode_kernel=kernel)
    cfg.update(kw)
    return ServingEngine(model(max_len), params, EngineConfig(**cfg))


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
                         == "Qwen3-Next-80B-A3B-Instruct")["config"]
    names = {"num_layers": "num_hidden_layers", "num_heads":
             "num_attention_heads", "num_kv_heads": "num_key_value_heads",
             **{k: k for k in (
                 "vocab_size", "hidden_size", "head_dim",
                 "full_attention_interval", "partial_rotary_factor",
                 "rope_theta", "linear_num_key_heads",
                 "linear_num_value_heads", "linear_key_head_dim",
                 "linear_value_head_dim", "linear_conv_kernel_dim",
                 "moe_intermediate_size", "shared_expert_intermediate_size",
                 "num_experts", "num_experts_per_tok", "rms_norm_eps")}}
    for ours, theirs in names.items():
        assert getattr(PUBLISHED, ours) == published[theirs], ours
    assert PUBLISHED.held == (0, 512) and PUBLISHED.rotary_dim == 64
    assert PUBLISHED.layer_types[:8] == ("delta",) * 3 + ("attention",) \
        + ("delta",) * 3 + ("attention",)
    assert PUBLISHED.layer_types.count("attention") == 12
    assert (PUBLISHED.key_dim, PUBLISHED.value_dim, PUBLISHED.conv_dim) == (
        2048, 4096, 8192)


def test_whole_sequence_forward_matches_the_reference(params):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 97)
    got = model().apply({"params": params}, toks)
    want = ref.forward(params, toks, DIMS)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(want.std()) > 0.5         # logits that tell tokens apart


@pytest.mark.parametrize("kernel,page_size,depth", [(False, 4, 1),
                                                    (True, 8, 1),
                                                    (False, 4, 8)])
def test_chunked_prefill_then_decode_matches_the_reference(params, kernel,
                                                           page_size, depth):
    """Prompts through one to four chunks of 8 with padded tails, a prompt
    of one token that decodes at position 0, contexts of 50 over a dozen
    pages, five requests over three slots so that rows prefill while
    others decode and slots are used again: the attention layer's pool,
    the delta-rule layers' state and conv tail all carried; with
    `async_depth` 1 (the double-buffered loop) and 8 (the cell's). With
    the kernel a decode step reads its pool through
    `paged_decode_attention` (interpreted)."""
    eng = engine(params, kernel=kernel, page_size=page_size,
                 async_depth=depth)
    reqs = requests([(30, 20), (7, 30), (19, 12), (1, 9), (23, 25)])
    results = eng.run(reqs)
    assert gaps(params, reqs, results) < TOL
    assert eng.compile_counts()["prefill"] == 1
    assert eng.compile_counts()["step"] == 1


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
            if p[-1].key in Qwen3NextLM.SLOT_STATE}


def test_junk_rows_and_pad_tokens_leave_slot_state_exactly_as_it_was(params):
    """A decode step over a row at `max_len`, a prefill call the row is no
    member of, and the pads after a member's real tokens."""
    eng = engine(params)
    eng.run(requests([(20, 4), (9, 4), (15, 4)], seed=7))   # state != 0
    before = _slot_leaves(eng.cache)
    assert len(before) == 6            # state and tail, three delta layers
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
    """Delta-rule layers: two slot leaves and no pages; the attention
    layer: pages and no slot state; `serve/programs.py`'s contract as it
    stands."""
    small, large = engine(params, max_len=64), engine(params, max_len=256)
    for eng in (small, large):
        NP = eng.page_allocator.num_pages
        pooled = [x for x in jax.tree.leaves(eng.cache) if x.shape[0] == NP]
        assert [x.shape for x in pooled] == [(NP, 4, 2 * 2 * 32)]
        assert eng.page_bytes() == 4 * 128 * 4
        for l in (0, 1, 2):
            layer = eng.cache[f"layer_{l}"]
            assert set(layer) == {"delta"}
            assert set(layer["delta"]) == {"delta", "conv"}
            assert layer["delta"]["delta"].shape == (3, 4, 16, 24)
            assert layer["delta"]["delta"].dtype == jnp.float32
            assert layer["delta"]["conv"].shape == (3, 3, 2 * 32 + 96)
        assert set(eng.cache["layer_3"]) == {"attn"}
        assert set(eng.cache["layer_3"]["attn"]) == {"cached_kv"}
    # three layers' state of 4 x 16 x 24 and conv tail of 3 x 160, float32
    # here; what a slot holds does not grow with the context it may reach
    want = 3 * (4 * 16 * 24 + 3 * 160) * 4
    assert small.slot_state_bytes() == large.slot_state_bytes() == want


def _prefill_text(eng):
    S, nblk = eng.config.slots, eng._nblk
    z = lambda *s: jnp.zeros(s, jnp.int32)                   # noqa: E731
    return eng._prefill.lower(eng.params, eng.cache, z(S), z(S, 8), z(S),
                              z(S, nblk), z(S)).as_text(debug_info=True)


def test_the_programs_carry_the_scopes_the_trace_is_split_by(params):
    eng = engine(params)
    scopes = set(eng.decode_step_scopes().values())
    for name in ("gdn.project", "gdn.conv", "gdn.update", "gdn.norm",
                 "gdn.out", "q3attn.project", "q3attn.cache_write",
                 "q3attn.attend", "q3attn.out", "moe.route", "moe.experts",
                 "moe.shared", "/head/"):
        assert any(name in s for s in scopes), name
    assert not any("gdn.chunk" in s for s in scopes)
    assert any("layer_2" in s and "gdn.update" in s for s in scopes)
    assert any("layer_3" in s and "q3attn.attend" in s for s in scopes)
    assert not any("layer_3" in s and "gdn." in s for s in scopes)
    # prefill: the chunk form, and nothing after the last layer's mixer
    text = _prefill_text(eng)
    assert "gdn.chunk" in text and "gdn.update" not in text
    assert "layer_2/moe" in text and "layer_3/attn" in text
    for gone in ("layer_3/moe", "final_layernorm", "/head"):
        assert gone not in text, gone


@pytest.fixture(scope="module")
def served(params):
    reqs = requests([(19, 6), (9, 6)], seed=4)
    results = engine(params).run(reqs)
    assert gaps(params, reqs, results) < TOL
    return reqs, results


@pytest.mark.parametrize("piece", ref.PIECES)
def test_each_new_piece_is_load_bearing(params, served, piece):
    """A reference WITHOUT the attention's output gate, its q and k norms,
    with rotary over all of a head, without the delta rule's L2 norm,
    without the shared expert's gate, or with the ten's weights left
    undivided, is another model: the sound program fails against each by
    hundreds of tolerances."""
    reqs, results = served
    assert gaps(params, reqs, results, without=(piece,)) > 100 * TOL


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test. The uncut layer holds all 8 experts; the
    program's four shares hold experts 0..1, 2..3, 4..5 and 6..7 of the
    SAME weights. Each computes x' + its routed part + g_s Shared: the
    four routed parts and the gated shared expert counted ONCE give the
    uncut reference's layer, for a delta-rule layer and for the attention
    layer."""
    whole = dataclasses.replace(DIMS, held=(0, 8))
    key = W.seed_key(11)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    for index, kind in ((0, "delta"), (3, "attention")):
        p = W.layer_params(key, whole, index, jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = ref.layer(p, x[0], whole)
            alone = ref.layer(p, x[0], whole, held=(0, 0))  # x' + g_s Shared
        parts = []
        for first in (0, 2, 4, 6):
            mine = jax.tree.map(lambda a: a, p)
            for name in ("gate", "up", "down"):
                mine["moe"][name] = p["moe"][name][first:first + 2]
            got = Qwen3NextLayer(config(held=(first, 2)), kind).apply(
                {"params": mine}, x)[0]
            parts.append(got - alone)
            # a share alone is NOT the layer: the other three are missing
            assert float(jnp.abs(got - want).max()) > 100 * TOL
        assert float(jnp.abs(alone + sum(parts) - want).max()) < TOL


@pytest.mark.parametrize("case", ["random", "ties", "published_width"])
def test_the_published_gate_is_the_gate_normalised_over_its_picks(case):
    """Softmax over ALL outputs, the ten largest, each over their sum (the
    published order, which the reference follows) against
    `route(over="picks")` (the ten largest logits, a softmax over those
    alone, which the program runs): the same picks, the same weights to
    float32 rounding; ties go to the lower index in both."""
    rng = np.random.default_rng(9)
    if case == "random":
        logits, k = rng.normal(size=(16, 8)), 3
    elif case == "ties":
        logits = np.round(rng.normal(size=(16, 8))) + 0.0     # no -0.0
        logits[0] = 0.0
        logits[1, :] = [1, 1, 1, 1, 0, 0, 2, 2]
        k = 3
    else:
        logits, k = 0.9 * rng.normal(size=(96, 512)), 10
    logits = jnp.asarray(logits, jnp.float32)
    idx, w = held_experts.route(logits, None, k, 1.0, over="picks")
    want_idx, want_w = ref.gate(logits, dataclasses.replace(DIMS, top_k=k))
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert float(jnp.abs(w - want_w).max()) < 1e-6
    assert float(jnp.abs(w.sum(-1) - 1.0).max()) < 1e-6
    # undivided, the ten's weights are their share of all 512
    _, raw = ref.gate(logits, dataclasses.replace(DIMS, top_k=k),
                      without=("norm_topk",))
    assert float(raw.sum(-1).max()) < 0.999


def test_held_experts_that_outnumber_the_rows(params):
    """The cell's regime at a toy size: 6 held experts and 4 rows. The
    held experts' part is the reference's whatever form computes it."""
    d = dataclasses.replace(DIMS, held=(1, 6))
    p = W.layer_params(W.seed_key(5), d, 0, jnp.float32)["moe"]
    v = jax.random.normal(jax.random.PRNGKey(2), (4, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(jax.tree.map(lambda a: a, p), v, d, "f32",
                           shared=False)
    got, (picks, load) = held_experts.flat_experts(
        v, p["router"], p["gate"], p["up"], p["down"], held=(1, 6), top_k=3)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert 0 < int(load) <= int(picks) <= 12


@pytest.mark.parametrize("kwargs,piece", [
    (dict(prefix_cache=True), "snapshot"),
    (dict(speculative="ngram"), "rewound"),
    (dict(cls=PrefillEngine), "transfer of its slot's state"),
    (dict(cls=DecodeEngine), "transfer of its slot's state"),
])
def test_engine_refuses_what_needs_state_snapshots(params, kwargs, piece):
    """`_refuse_for_slot_state` for this model as for Granite-4.0-H: the
    prefix cache, speculation, and the two disaggregated pools."""
    cls = kwargs.pop("cls", ServingEngine)
    cfg = dict(slots=2, chunk_buckets=(8,), page_size=4, prefix_cache=False)
    cfg.update(kwargs)
    with pytest.raises(ValueError, match=piece):
        cls(model(), params, EngineConfig(**cfg))


def test_the_step_counters_count_picks_on_the_held_experts(params):
    """`STEP_COUNTERS`: over the four layers, picks that fell on the four
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
    assert Qwen3NextLM.STEP_COUNTERS == tuple(seen)
    assert seen["moe_held_picks"] and seen["moe_load_max"]
    # 3 rows x 3 picks x 4 layers at most, about half of them held
    assert 0 < max(seen["moe_held_picks"]) <= 36
    assert all(m <= p for m, p in zip(seen["moe_load_max"],
                                      seen["moe_held_picks"]))
    assert tel.slot_state_bytes.value == eng.slot_state_bytes() > 0


def test_a_config_that_cannot_be_served_is_refused():
    for kw, what in ((dict(num_kv_heads=3), "num_kv_heads"),
                     (dict(linear_num_key_heads=3), "linear_num_key_heads"),
                     (dict(partial_rotary_factor=0.1), "partial_rotary"),
                     (dict(held=(6, 4)), "held")):
        with pytest.raises(ValueError, match=what):
            dataclasses.replace(config(), **kw)
