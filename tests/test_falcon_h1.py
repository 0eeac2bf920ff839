"""Falcon-H1 through the serving engine at toy widths (2 layers, each with
both mixers; 4 state-space heads over 2 groups; 4 query heads on 2 key
heads), against the plain reference of `perfbench/reference/falcon_h1.py`
on seeded random weights.

Everything here is float32 on the CPU, program and reference alike, so a
tolerance is what summation order costs: 2e-5 on log-probabilities and on
the distance of a served token's logit from the reference's best (logits
here are of order 0.2). What is compared is logits, not tokens: the
engine's reported log-probability of each served token, and that the
served token IS the reference's best up to that tolerance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models import falcon_h1
from mpi_operator_tpu.models.falcon_h1 import FalconH1Config, FalconH1LM
from mpi_operator_tpu.serve import (DecodeEngine, EngineConfig, PrefillEngine,
                                    Request, ServingEngine)
from perfbench import weights_falconh1 as W
from perfbench.reference import falcon_h1 as ref
from prefill_forms import member_rows_alone_leave_what_all_rows_leave

TOL = 2e-5
PUBLISHED = FalconH1Config()
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "attention_out_multiplier", "key_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
               "mlp_multipliers")
CONFIG = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 97, "rms_norm_eps": 1e-5, "rope_theta": 1e11,
    "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "attention_in_multiplier": 1,
    **{m: getattr(PUBLISHED, m) for m in MULTIPLIERS},
    # steps of 0.03 to 0.5, larger than the published initialisation's:
    # with 16 states a head and not 256, the state's share of y would
    # otherwise be a hundredth of D x, and a wrong recurrence hard to see
    "assumed": {"initializer_range": 0.02, "conv_std": 0.3, "dt_min": 0.03,
                "dt_max": 0.5}}
DIMS = W.Dims.from_config(CONFIG)


def model(max_len=64, **kw):
    return FalconH1LM(FalconH1Config(
        vocab_size=97, max_len=max_len, num_layers=2, hidden_size=64,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
        mamba_d_ssm=64, mamba_n_heads=4, mamba_d_state=16, mamba_n_groups=2,
        mamba_chunk_size=8, dtype=jnp.float32, **kw))


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


def test_the_defaults_are_the_published_configuration():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        import json
        published = next(json.loads(ln) for ln in f if json.loads(ln)["name"]
                         == "Falcon-H1-34B-Instruct")["config"]
    names = {"vocab_size": "vocab_size", "num_layers": "num_hidden_layers",
             "hidden_size": "hidden_size", "num_heads": "num_attention_heads",
             "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
             "intermediate_size": "intermediate_size",
             "rms_norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
             "mamba_d_ssm": "mamba_d_ssm", "mamba_n_heads": "mamba_n_heads",
             "mamba_d_head": "mamba_d_head",
             "mamba_d_state": "mamba_d_state",
             "mamba_n_groups": "mamba_n_groups",
             "mamba_d_conv": "mamba_d_conv",
             "mamba_chunk_size": "mamba_chunk_size",
             "attention_in_multiplier": "attention_in_multiplier",
             **{m: m for m in MULTIPLIERS}}
    for ours, theirs in names.items():
        got = getattr(PUBLISHED, ours)
        want = published[theirs]
        assert (list(got) if isinstance(got, tuple) else got) == want, ours
    assert PUBLISHED.conv_dim == 5120


def test_whole_sequence_forward_matches_the_reference(params):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 97)
    got = model().apply({"params": params}, toks)
    want = ref.forward(params, toks, DIMS)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(want.std()) > 0.05        # logits that tell tokens apart


@pytest.mark.parametrize("kernel,page_size", [(False, 4), (True, 8)])
def test_chunked_prefill_then_decode_matches_the_reference(params, kernel,
                                                           page_size):
    """Prompts through one to four chunks of 8 with padded tails (29 = 3 x
    8 + 5, 18 = 2 x 8 + 2, 22 = 2 x 8 + 6), a prompt of one token that
    decodes at position 0, contexts of 50 over a dozen pages, five
    requests over three slots so that rows prefill while others decode
    and slots are used again: pool, state and conv tail all carried. With
    the kernel (interpreted) a decode step reads its pool through
    `paged_decode_attention`."""
    eng = engine(params, kernel=kernel, page_size=page_size)
    reqs = requests([(30, 20), (7, 30), (19, 12), (1, 9), (23, 25)])
    results = eng.run(reqs)
    assert gaps(params, reqs, results) < TOL
    assert eng.compile_counts()["prefill"] == 1
    assert eng.compile_counts()["step"] == 1


def test_row_groups_give_what_the_whole_call_gives(params, monkeypatch):
    """At the real size a chunk's rows go through in groups (`_by_rows`);
    here the budget is cut until they do."""
    monkeypatch.setattr(falcon_h1, "_CHUNK_TOKENS", 16)
    eng = engine(params, slots=4)
    reqs = requests([(30, 6), (7, 9), (19, 5), (27, 4)], seed=5)
    assert gaps(params, reqs, eng.run(reqs)) < TOL


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
            if p[-1].key in FalconH1LM.SLOT_STATE}


def test_junk_rows_and_pad_tokens_leave_slot_state_exactly_as_it_was(params):
    """A decode step over a row at `max_len`, a prefill call the row is no
    member of, and the pads after a member's real tokens."""
    eng = engine(params)
    eng.run(requests([(20, 4), (9, 4), (15, 4)], seed=7))   # state != 0
    before = _slot_leaves(eng.cache)
    assert len(before) == 4                 # state and tail, two layers
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


def test_every_layer_holds_a_pool_a_state_and_a_tail(params):
    small, large = engine(params, max_len=64), engine(params, max_len=256)
    for eng in (small, large):
        NP = eng.page_allocator.num_pages
        pooled = [x for x in jax.tree.leaves(eng.cache) if x.shape[0] == NP]
        assert [x.shape for x in pooled] == [(NP, 4, 2 * 2 * 16)] * 2
        assert eng.page_bytes() == 2 * 4 * 64 * 4
        layer = eng.cache["layer_1"]
        assert set(layer["mamba"]) == {"ssm", "conv"}
        assert layer["mamba"]["ssm"].shape == (3, 4, 16, 16)
        assert layer["mamba"]["conv"].shape == (3, 3, 64 + 2 * 2 * 16)
        assert set(layer["attn"]) == {"cached_kv"}
    # two layers' state of 4 x 16 x 16 and conv tail of 3 x 128, float32
    # here; what a slot holds does not grow with the context it may reach
    want = 2 * (4 * 16 * 16 + 3 * 128) * 4
    assert small.slot_state_bytes() == large.slot_state_bytes() == want


def test_the_published_sizes_give_the_issues_bytes_a_slot():
    c = PUBLISHED
    state = c.mamba_n_heads * c.mamba_d_state * c.mamba_d_head * 4
    tail = (c.mamba_d_conv - 1) * c.conv_dim * 2
    assert (state, tail, 4 * (state + tail)) == (4194304, 30720, 16900096)
    assert c.num_kv_heads * 2 * c.head_dim * 2 == 2048     # a cached token


def _prefill_text(eng):
    S, nblk = eng.config.slots, eng._nblk
    z = lambda *s: jnp.zeros(s, jnp.int32)                   # noqa: E731
    return eng._prefill.lower(eng.params, eng.cache, z(S), z(S, 8), z(S),
                              z(S, nblk), z(S)).as_text(debug_info=True)


def test_the_programs_carry_the_scopes_the_trace_is_split_by(params):
    eng = engine(params)
    scopes = set(eng.decode_step_scopes().values())
    for name in ("ssd.project", "ssd.conv", "ssd.update", "ssd.norm",
                 "ssd.out", "h1attn.project", "h1attn.cache_write",
                 "h1attn.attend", "h1attn.out", "mlp", "/head/"):
        assert any(name in s for s in scopes), name
    assert not any("ssd.chunk" in s for s in scopes)
    assert any("layer_1" in s and "ssd.update" in s for s in scopes)
    # prefill: the chunked scan, and nothing after the last layer's mixers
    text = _prefill_text(eng)
    assert "ssd.chunk" in text and "ssd.update" not in text
    assert "layer_0/mlp" in text and "layer_1/attn" in text
    for gone in ("layer_1/mlp", "final_layernorm", "head"):
        assert gone not in text, gone


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_matters(params, name):
    """Set to 1 in the program (the reference keeps the published value),
    the comparison fails, by hundreds of tolerances: none is folded away
    by a norm, and the seeded weights make each load-bearing."""
    value = getattr(PUBLISHED, name)
    ones = tuple(1.0 for _ in value) if isinstance(value, tuple) else 1.0
    served = FalconH1LM(dataclasses.replace(model().config, **{name: ones}))
    eng = engine(params, served=served)
    reqs = requests([(19, 6), (9, 6)], seed=4)
    assert gaps(params, reqs, eng.run(reqs)) > 100 * TOL


@pytest.mark.parametrize("part", range(5))
def test_each_ssm_multiplier_matters(params, part):
    """[z | x | B | C | dt], one at a time. The grouped norm after the
    gate takes back most of what a scale on B or C does to y (what is
    left is the silu after the conv, and D x beside the state's part), so
    the bar is several tolerances here, not hundreds (dt's reads 8)."""
    value = list(PUBLISHED.ssm_multipliers)
    value[part] = 1.0
    served = FalconH1LM(dataclasses.replace(
        model().config, ssm_multipliers=tuple(value)))
    eng = engine(params, served=served)
    reqs = requests([(19, 6), (9, 6)], seed=4)
    assert gaps(params, reqs, eng.run(reqs)) > 5 * TOL


@pytest.mark.parametrize("kwargs,piece", [
    (dict(prefix_cache=True), "snapshot"),
    (dict(speculative="ngram"), "rewound"),
])
def test_engine_refuses_what_needs_state_snapshots(params, kwargs, piece):
    with pytest.raises(ValueError, match=piece):
        engine(params, **kwargs)


@pytest.mark.parametrize("cls", [PrefillEngine, DecodeEngine])
def test_disaggregated_pools_refuse_a_model_with_slot_state(params, cls):
    with pytest.raises(ValueError, match="transfer of its slot's state"):
        cls(model(), params, EngineConfig(
            slots=2, chunk_buckets=(8,), page_size=4, prefix_cache=False))


def test_lockstep_generate_is_refused_with_the_reason(params):
    from mpi_operator_tpu.models.generate import decode_model
    with pytest.raises(ValueError, match="driven by the serving engine"):
        decode_model(model()).apply({"params": params},
                                    jnp.zeros((1, 4), jnp.int32),
                                    mutable=["cache"])


def test_the_recurrence_is_a_visible_share_of_the_mixers_output(params):
    """What the seeded weights are drawn for: with the conv's weights at
    0.02 the state's share of y vanishes beside D x, and a wrong
    recurrence would pass. A model that forgets at once differs from the
    model by 77 tolerances here."""
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, 97)
    whole = ref.forward(params, toks, DIMS)
    no_state = jax.tree.map(lambda x: x, params)
    for l in range(DIMS.layers):
        # A of -inf-like size forgets everything at once: y = (dt x B.C +
        # D) x, the state's memory gone
        no_state[f"layer_{l}"]["mamba"]["A_log"] = jnp.full((4,), 20.0)
    forgot = ref.forward(no_state, toks, DIMS)
    assert float(jnp.abs(whole - forgot).max()) > 50 * TOL
