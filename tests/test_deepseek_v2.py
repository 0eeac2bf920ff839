"""DeepSeek-V2 at toy widths on the CPU (every ratio kept: a leading dense
layer, 8 routing groups of which 3 stay and one is held, a shared expert
two experts wide, YaRN past its original context): the program through the
serving engine's paged latent cache against the plain reference
(`perfbench/reference/deepseek_v2.py`, which imports nothing of the
program), and the pieces — the group-limited gate against a NumPy gate,
YaRN against values worked by hand, the shares, the chunk path in row
groups, and LongCat-Flash's side of what the two models now share."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models import longcat
from mpi_operator_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                 DeepseekV2LM,
                                                 yarn_correction_range,
                                                 yarn_frequencies,
                                                 yarn_mscale)
from mpi_operator_tpu.ops import attention
from mpi_operator_tpu.parallel import held_experts as he
from mpi_operator_tpu.serve import EngineConfig, Request, ServingEngine
from mpi_operator_tpu.telemetry.worker import ServeTelemetry
from perfbench import weights_deepseekv2 as wd
from perfbench.kinds import _serve_deepseekv2
from perfbench.reference import deepseek_v2 as ref

DIMS = wd.Dims(layers=3, dense_layers=1, hidden=96, heads=4, q_rank=24,
               kv_rank=8, nope=8, rope=8, v_dim=8, ffn=192, expert_ffn=32,
               shared_experts=2, experts_published=32, n_group=8,
               topk_group=3, top_k=6, route_scale=16.0, rope_theta=1e4,
               rope_factor=40.0, rope_original=16, beta_fast=32.0,
               beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707, eps=1e-6,
               held=(4, 4), vocab=128, std=0.02)
F32 = jnp.float32


def _model(dims=DIMS, max_len=64, **kw):
    model = _serve_deepseekv2.model_of(dims, F32, max_len, False)
    return DeepseekV2LM(dataclasses.replace(model.config, **kw))


def _params(seed=3, dims=DIMS):
    return jax.jit(lambda k: wd.make_params(k, dims, F32))(wd.seed_key(seed))


# -- the tree ------------------------------------------------------------

def test_the_benchmarks_tree_is_the_programs_tree_leaf_for_leaf():
    _serve_deepseekv2.check_tree(_model(), DIMS, F32)
    wrong = dataclasses.replace(DIMS, shared_experts=1)
    with pytest.raises(RuntimeError, match="does not serve the tree"):
        _serve_deepseekv2.check_tree(_model(), wrong, F32)
    # layer 0 is dense, the others hold experts and a shared one
    tree = jax.eval_shape(lambda: wd.make_params(jax.random.PRNGKey(0), DIMS,
                                                 F32))
    assert "ffn" in tree["layer_0"] and "moe" not in tree["layer_0"]
    assert tree["layer_1"]["moe"]["shared"]["gate"].shape == (96, 64)
    assert tree["layer_2"]["moe"]["gate"].shape == (4, 96, 32)
    assert tree["layer_2"]["moe"]["router"].shape == (96, 32)


# -- YaRN, by hand ---------------------------------------------------------

def test_yarn_frequencies_ramp_and_scale_are_the_values_worked_by_hand():
    """The published numbers: 64 rotary dims, theta 1e4, factor 40, original
    4096, beta 32 / 1, mscale 0.707 both."""
    assert yarn_correction_range(64, 1e4, 4096, 32, 1) == (10, 23)
    # d(r) = 64 ln(4096 / (2 pi r)) / (2 ln 1e4): 10.47 and 22.51
    assert abs(64 * math.log(4096 / (2 * math.pi * 32))
               / (2 * math.log(1e4)) - 10.47) < 0.01
    assert abs(64 * math.log(4096 / (2 * math.pi))
               / (2 * math.log(1e4)) - 22.51) < 0.01
    f = yarn_frequencies(64, 1e4, 40, 4096, 32, 1)
    assert f.shape == (32,) and f.dtype == np.float32
    plain = 1e4 ** (-np.arange(32) / 32.0)
    # the fast pairs are kept, the slow ones divided by 40, a ramp between
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    for i, ramp in ((11, 1 / 13), (17, 7 / 13), (22, 12 / 13)):
        np.testing.assert_allclose(
            f[i], plain[i] * (1 - ramp) + plain[i] / 40 * ramp, rtol=1e-5)
    # worked by hand: f_11 = 1e4^(-11/32) = 0.042170; ramp 1/13
    assert abs(f[11] - 0.042170 * (12 / 13 + 1 / 520)) < 2e-6
    assert abs(f[31] - 1e4 ** (-31 / 32) / 40) < 1e-9
    assert np.all(np.diff(f) < 0)
    # m(0.707) = 0.0707 ln 40 + 1 = 1.26080; s = 192^-0.5 m^2 = 0.11472
    assert abs(yarn_mscale(40, 0.707) - 1.26080) < 1e-5
    cfg = DeepseekV2Config()
    assert abs(cfg.sm_scale - 0.11472) < 1e-5
    np.testing.assert_array_equal(cfg.rope_freqs, f)
    assert cfg.mla_scale_q_lora is False and cfg.mla_scale_kv_lora is False
    # the reference works them out on its own, and agrees
    real = dataclasses.replace(DIMS, rope=64, nope=128, rope_original=4096)
    np.testing.assert_allclose(ref.yarn_frequencies(real), f, rtol=1e-6)
    assert abs(ref.softmax_scale(real) - 0.11472) < 1e-5
    # cos and sin carry m(mscale) / m(mscale_all_dim): only 1 is built
    with pytest.raises(ValueError, match="published ratio of 1"):
        DeepseekV2Config(rope_mscale=1.0)


# -- the full forward pass -------------------------------------------------

def test_full_forward_matches_the_plain_reference_on_logits():
    params = _params()
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 40)))
    got = _model().apply({"params": params}, tokens)
    want = ref.forward(params, tokens, DIMS)
    assert float(jnp.abs(got - want).max()) < 2e-4
    assert float(jnp.abs(want).max()) > 0.3


@pytest.mark.parametrize("what,change", [
    ("the mscale dropped", dict(rope_mscale=0.0, rope_mscale_all_dim=0.0)),
    ("every frequency interpolated", dict(rope_beta_fast=1e9,
                                          rope_beta_slow=1e8)),
    ("the rank factors put in", None),
    ("the picks renormalised", dict(routed_scaling_factor=1.0))])
def test_a_program_that_departs_from_the_equations_fails_the_comparison(
        what, change):
    """At weights wide enough for attention and the gate to matter, each
    departure the issue names moves the logits by far more than the
    tolerance of the test above."""
    # original 4096: of the four toy frequencies the first two are kept
    dims = dataclasses.replace(DIMS, std=0.15, rope_original=4096)
    assert yarn_correction_range(8, 1e4, 4096, 32, 1) == (1, 3)
    params = _params(dims=dims)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 128, (1, 48)))
    want = ref.forward(params, tokens, dims)
    sound = _model(dims).apply({"params": params}, tokens)
    assert float(jnp.abs(sound - want).max()) < 5e-3
    if change is None:
        class Scaled(DeepseekV2Config):
            mla_scale_q_lora = True
            mla_scale_kv_lora = True
        cfg = Scaled(**dataclasses.asdict(_model(dims).config))
    else:
        cfg = dataclasses.replace(_model(dims).config, **change)
    got = DeepseekV2LM(cfg).apply({"params": params}, tokens)
    assert float(jnp.abs(got - want).max()) > 0.1, what


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_prefill_then_decode_through_the_latent_pages_matches_reference(
        kernel):
    """Prompts prefilled in chunks, then decoded a token at a time through
    the paged latent cache (absorbed attention; the kernel interpreted),
    against the reference's full forward pass over prompt and served
    tokens, on logits: the served token is the reference's best to within
    rounding, and its reported log-probability is the reference's. The
    contexts pass YaRN's original length (16 here)."""
    params = _params()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, n).tolist() for n in (7, 29, 12)]
    tel = ServeTelemetry()
    eng = ServingEngine(_model(), params, EngineConfig(
        slots=2, chunk_buckets=(4, 8), page_size=8, num_pages=24,
        decode_kernel=kernel), telemetry=tel)
    res = eng.run([Request(id=i, prompt=p, max_new_tokens=6 + i)
                   for i, p in enumerate(prompts)])
    assert eng.compile_counts()["step"] == 1
    assert eng.compile_counts()["prefill"] <= 2
    for i, p in enumerate(prompts):
        seq = p + res[i].tokens
        logits = ref.forward(params, jnp.asarray([seq]), DIMS)[0]
        logp = jax.nn.log_softmax(logits, -1)
        for j, tok in enumerate(res[i].tokens):
            at = len(p) - 1 + j
            assert float(logits[at].max() - logits[at, tok]) < 1e-4
            assert abs(float(logp[at, tok]) - res[i].logprobs[j]) < 1e-4
    # the routing counters came with the tokens, one observation a step
    steps = tel.decode_step_seconds.count
    assert {n: h.count for n, h in tel.step_counters.items()} == {
        n: steps for n in DeepseekV2LM.STEP_COUNTERS}
    # one pooled latent leaf a layer, nothing a slot
    assert eng.page_bytes() == DIMS.layers * 128 * 8 * 4
    assert eng.slot_state_bytes() == 0


def test_step_counters_are_the_steps_own_routing():
    """What the engine fetches with a step's tokens is what the gate
    picked in that step, summed over the EXPERT layers (the dense layer
    sows nothing): picks on held experts, the largest load, and the rows
    that kept the held group."""
    params = _params()
    dmodel = _model(decode=True, decode_page_size=8, decode_num_pages=9)
    tokens = jnp.asarray([[5], [9], [77]])
    pages = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]] * 3, jnp.int32)
    _, vars_ = dmodel.apply({"params": params}, tokens,
                            positions=jnp.zeros((3, 1), jnp.int32),
                            with_head=False, pages=pages,
                            mutable=["cache", "counters"])
    leaves = jax.tree.leaves(vars_["counters"])
    assert len(leaves) == DIMS.layers - DIMS.dense_layers
    held, load, hits = (int(x) for x in sum(leaves))
    assert 0 <= held <= 3 * 4 * 2 and load <= held
    assert (load > 0) == (held > 0)
    # a row can only pick a held expert if it kept the held group
    assert 0 <= hits <= 3 * 2 and (held == 0 or hits > 0)


def test_scopes_of_the_decode_step_name_every_part_of_a_layer():
    eng = ServingEngine(_model(), _params(), EngineConfig(
        slots=2, chunk_buckets=(8,), page_size=8, num_pages=9))
    joined = " ".join(eng.decode_step_scopes().values())
    for scope in ("mla.project", "mla.cache_write", "mla.attend", "mla.out",
                  "moe.route", "moe.experts", "moe.shared", "/ffn/",
                  "/head/"):
        assert scope in joined, scope
    assert "moe.identity" not in joined


def test_the_latent_cache_is_paged_or_says_why_not():
    with pytest.raises(ValueError, match="latent cache is a page pool"):
        _model(decode=True).apply({"params": _params()},
                                  jnp.zeros((1, 4), jnp.int32),
                                  positions=jnp.arange(4)[None])
    with pytest.raises(ValueError, match="not a range"):
        _model(held=(30, 4)).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 4), jnp.int32))


# -- the gate ----------------------------------------------------------------

def _numpy_gate(logits, n_group, topk_group, top_k, scale):
    """The published gate, plainly: softmax, a group's score its maximum,
    the best groups kept (ties to the lower index), the rest set to 0,
    the top k of what is left (ties to the lower index)."""
    logits = np.asarray(logits, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    T, n = p.shape
    size = n // n_group
    idx = np.zeros((T, top_k), np.int64)
    for t in range(T):
        best = p[t].reshape(n_group, size).max(-1)
        groups = np.argsort(-best, kind="stable")[:topk_group]
        left = np.zeros(n)
        for g in groups:
            left[g * size:(g + 1) * size] = p[t, g * size:(g + 1) * size]
        idx[t] = np.argsort(-left, kind="stable")[:top_k]
    return idx, scale * np.take_along_axis(p, idx, -1)


def test_group_limited_gate_is_the_plain_numpy_gate():
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(0), (200, 160))
    idx, w = he.route(logits, None, 6, 16.0, n_group=8, topk_group=3)
    want_idx, want_w = _numpy_gate(logits, 8, 3, 6, 16.0)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    # every pick lies in one of three groups; weights are not renormalised
    assert all(len(set(row // 20)) <= 3 for row in np.asarray(idx))
    assert float(jnp.abs(w.sum(-1) - 16.0).min()) > 1e-3
    # a flat top-6 would have left the three groups for some rows
    flat, _ = he.route(logits, jnp.zeros(160), 6, 16.0)
    assert not np.array_equal(np.sort(flat, -1), np.sort(idx, -1))
    # and the reference's own gate agrees
    d = dataclasses.replace(DIMS, experts_published=160)
    ridx, rw = ref.gate(logits, d)
    np.testing.assert_array_equal(np.asarray(ridx), want_idx)
    np.testing.assert_allclose(np.asarray(rw), want_w, rtol=1e-5)


def test_ties_among_groups_and_picks_go_to_the_lower_index():
    # four groups of 4 tie in their best score; two rows
    logits = jnp.asarray([[3.0, 0, 0, 0] * 4 + [0.0] * 16,
                          [0.0] * 32], F32)
    idx, w = he.route(logits, None, 6, 1.0, n_group=8, topk_group=3)
    want_idx, _ = _numpy_gate(logits, 8, 3, 6, 1.0)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    # row 0: groups 0, 1, 2 stay; their best three, then the lowest zeros
    assert sorted(np.asarray(idx)[0][:3].tolist()) == [0, 4, 8]
    assert np.asarray(idx)[0][3:].tolist() == [1, 2, 3]
    # row 1, all equal: groups 0-2, picks 0..5
    assert np.asarray(idx)[1].tolist() == [0, 1, 2, 3, 4, 5]
    keep = he.kept_groups(jax.nn.softmax(logits, -1), 8, 3)
    assert np.asarray(keep).tolist() == [[True] * 3 + [False] * 5] * 2
    ridx, _ = ref.gate(logits, dataclasses.replace(DIMS, top_k=6))
    np.testing.assert_array_equal(np.asarray(ridx), want_idx)


def test_longcats_flat_route_is_bit_equal_to_what_it_was():
    """`route` with `n_group` 1 is the code LongCat-Flash compiled before
    this model shared it: the same values to the bit, and the same
    program."""
    def before(logits, bias, top_k, scale):
        p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        _, idx = jax.lax.top_k(p + bias.astype(jnp.float32), top_k)
        return idx, scale * jnp.take_along_axis(p, idx, axis=-1)
    logits = jax.random.normal(jax.random.PRNGKey(1), (64, 768),
                               jnp.bfloat16)
    bias = 5e-4 * jax.random.normal(jax.random.PRNGKey(2), (768,))
    idx, w = he.route(logits, bias, 12, 6.0)
    idx0, w0 = before(logits, bias, 12, 6.0)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx0))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w0))
    assert str(jax.make_jaxpr(lambda a, b: he.route(a, b, 12, 6.0))(
        logits, bias)) == str(jax.make_jaxpr(
            lambda a, b: before(a, b, 12, 6.0))(logits, bias))


# -- the shares ----------------------------------------------------------------

def _layer_inputs(seed=0, tokens=40, dims=DIMS):
    """A whole (uncut) expert layer's weights and some inputs."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    n, H, F = dims.experts_published, dims.hidden, dims.expert_ffn
    y = jax.random.normal(k[0], (tokens, H))
    w = lambda key, *shape: 0.1 * jax.random.normal(key, shape)  # noqa: E731
    return y, {"router": 0.3 * jax.random.normal(k[1], (H, n)),
               "gate": w(k[2], n, H, F), "up": w(k[3], n, H, F),
               "down": w(k[4], n, F, H),
               "shared": {"gate": w(k[5], H, 2 * F), "up": w(k[6], H, 2 * F),
                          "down": w(k[7], 2 * F, H)}}


def _program_share(y, p, held, dims=DIMS):
    lo, n = held
    return he.group_limited_experts(
        y, p["router"], p["gate"][lo:lo + n], p["up"][lo:lo + n],
        p["down"][lo:lo + n], held=held, top_k=dims.top_k,
        scale=dims.route_scale, n_group=dims.n_group,
        topk_group=dims.topk_group)


def test_the_8_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the eight chips give, each
    holding one routing group, with the shared expert (which every chip
    that serves a row computes alike) counted ONCE, are the uncut
    reference layer's result; and a row is counted as reaching a chip
    exactly when that chip's group is one of its three."""
    y, p = _layer_inputs()
    whole = dataclasses.replace(DIMS, held=(0, DIMS.experts_published))
    shared = longcat.SwiGLU(_model().config, width=64,
                            traced_as="moe.shared")
    with jax.default_matmul_precision("highest"):
        want = ref.experts(p, y, whole, "f32")
        total = jnp.zeros_like(y)
        hits = 0
        for share in range(8):
            part, (picks, load, reached) = _program_share(y, p,
                                                          (4 * share, 4))
            total = total + part
            hits += int(reached)
        got = total + shared.apply({"params": p["shared"]}, y)
        # and the reference given one share is that share
        one_p = {**p, **{k: p[k][12:16] for k in ("gate", "up", "down")}}
        one = ref.experts(one_p, y, dataclasses.replace(DIMS, held=(12, 4)),
                          "f32", shared=False)
        part, _ = _program_share(y, p, (12, 4))
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-4 * scale
    assert float(jnp.abs(part - one).max()) < 1e-4 * scale
    assert float(jnp.abs(part).max()) > 0.01 * scale
    assert hits == 3 * y.shape[0]          # every row reaches three chips


def test_a_chip_that_holds_two_groups_counts_a_row_once():
    y, p = _layer_inputs(tokens=64)
    _, (_, _, one) = _program_share(y, p, (4, 4))
    _, (_, _, other) = _program_share(y, p, (8, 4))
    _, (picks, load, both) = _program_share(y, p, (4, 8))
    assert max(int(one), int(other)) <= int(both) <= int(one) + int(other)
    assert int(both) <= 64 and 0 < int(load) <= int(picks)


@pytest.mark.parametrize("tokens", [40, he.MASKED_MAX_TOKENS + 44])
def test_both_forms_of_the_held_part_serve_the_grouped_gate(tokens):
    """Under the masked form's limit and over it (a prefill chunk): the
    same routed part, no assignment dropped."""
    y, p = _layer_inputs(seed=tokens, tokens=tokens)
    part, (picks, _, _) = _program_share(y, p, (4, 4))
    idx, w = he.route(y @ p["router"], None, DIMS.top_k, DIMS.route_scale,
                      DIMS.n_group, DIMS.topk_group)
    g, u, d = (p[m][4:8] for m in ("gate", "up", "down"))
    masked = he.masked_experts(y, he.held_gates(idx, w, 4, 4), g, u, d)
    scale = float(jnp.abs(masked).max())
    assert scale > 0 and int(picks) == int(((idx >= 4) & (idx < 8)).sum())
    assert float(jnp.abs(part - masked).max()) < 1e-5 * scale


# -- the chunk path ------------------------------------------------------------

def test_which_chunks_build_their_queries_in_row_groups():
    bf16 = jnp.bfloat16
    # LongCat-Flash's [64, 128] and [64, 32] chunks of 64 heads: whole
    assert attention.mla_query_rows(64, 128, 64, 640, bf16) == 64
    assert attention.mla_query_rows(64, 32, 64, 640, bf16) == 64
    # DeepSeek-V2's [64, 128] chunk of 128 heads: 1.34 GB of absorbed
    # queries; four rows at a time are 84 MB
    assert attention.mla_query_rows(64, 128, 128, 640, bf16) == 4
    assert attention.mla_query_rows(64, 64, 128, 640, bf16) == 64
    # a decode step without the kernel: whole
    assert attention.mla_query_rows(64, 1, 128, 640, bf16) == 64


def test_row_groups_give_the_whole_calls_attention_and_skip_idle_groups(
        monkeypatch):
    """The chunk path a group of rows at a time equals building every
    row's absorbed queries at once; a group none of whose rows is a
    member of the call walks no page, whatever its table points at."""
    B, S, H, dn, R, dr, ps, nblk = 8, 3, 2, 4, 8, 4, 4, 6
    W = attention.mla_row_width(R, dr)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    q_nope = jax.random.normal(k[0], (B, S, H, dn), F32)
    q_pe = jax.random.normal(k[1], (B, S, H, dr), F32)
    w_k = jax.random.normal(k[2], (R, H, dn), F32)
    w_v = jax.random.normal(k[3], (R, H, 5), F32)
    pool = jax.random.normal(k[4], (B * nblk + 1, ps, W), F32)
    pool = pool.at[..., R + dr:].set(0.0)
    pt = jnp.arange(B * nblk).reshape(B, nblk) + 1
    pos = jnp.asarray([[0, 1, 2], [9, 10, 11], [20, 21, 22], [4, 5, 6],
                       [24, 24, 24], [24, 24, 24], [1, 2, 3], [13, 14, 15]])
    q = jnp.concatenate([jnp.einsum("bshd,rhd->bshr", q_nope, w_k), q_pe,
                         jnp.zeros((B, S, H, W - R - dr))], -1)
    want = jnp.einsum("bshr,rhd->bshd", attention.mla_paged_attend(
        q, pool, pos, pt, R, 0.3), w_v)
    monkeypatch.setattr(attention, "_MLA_BUILT_QUERY_BYTES", 0)
    monkeypatch.setattr(attention, "_MLA_QUERY_ROWS", 2 * S * H)
    assert attention.mla_query_rows(B, S, H, W, F32) == 2
    got = attention.mla_paged_attend_rows(q_nope, q_pe, w_k, w_v, pool, pos,
                                          pt, R, 0.3)
    live = np.asarray([0, 1, 2, 3, 6, 7])
    assert float(jnp.abs(got - want)[live].max()) < 1e-5
    # rows 4 and 5 (one group) are no members: their pages may hold NaN
    poisoned = pool.at[pt[4:6].reshape(-1)].set(jnp.nan)
    again = attention.mla_paged_attend_rows(q_nope, q_pe, w_k, w_v, poisoned,
                                            pos, pt, R, 0.3)
    assert bool(jnp.isfinite(again).all())
    assert float(jnp.abs(again - want)[live].max()) < 1e-5


def test_absorbed_attention_in_row_groups_equals_the_non_absorbed_form(
        monkeypatch):
    """One MLA sublayer of this model (YaRN, no rank factors), the same
    weights and input: attention inside the window with K and V expanded,
    against one multi-token call through the latent pages that takes the
    row-group path."""
    monkeypatch.setattr(attention, "_MLA_BUILT_QUERY_BYTES", 0)
    monkeypatch.setattr(attention, "_MLA_QUERY_ROWS", 24 * DIMS.heads)
    cfg = _model().config
    dcfg = dataclasses.replace(cfg, decode=True, decode_page_size=8,
                               decode_num_pages=17)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, DIMS.hidden))
    plain = longcat.LatentAttention(cfg)
    p = plain.init(jax.random.PRNGKey(1), x)["params"]
    pages = jnp.asarray(np.random.RandomState(0).permutation(16)
                        .reshape(2, 8) + 1, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    with attention.record_traced() as seen:
        got, cache = longcat.LatentAttention(dcfg).apply(
            {"params": p}, x, positions=pos, pages=pages, mutable=["cache"])
    assert seen["prefill"] == {"dense"}
    want = plain.apply({"params": p}, x)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max()) + 1e-6
    pool = cache["cache"]["latent"]
    assert pool.shape == (17, 8, 128)
    assert float(jnp.abs(pool[..., DIMS.kv_rank + DIMS.rope:]).max()) == 0


# -- what the two models share, from LongCat-Flash's side ---------------------

def test_longcats_attention_keeps_its_factors_scale_and_frequencies():
    cfg = longcat.LongcatConfig()
    assert cfg.mla_scale_q_lora and cfg.mla_scale_kv_lora
    assert cfg.rope_freqs is None and cfg.sm_scale == 1 / math.sqrt(192)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 3, 8))
    pos = jnp.arange(5)[None]
    plain = longcat.rope_interleaved(x, pos, 1e4)
    given = longcat.rope_interleaved(
        x, pos, 1e4, freqs=1e4 ** (-np.arange(0, 8, 2, dtype=np.float32) / 8))
    assert float(jnp.abs(plain - given).max()) < 1e-6
    slow = longcat.rope_interleaved(x, pos, 1e4, freqs=np.zeros(4, np.float32))
    assert float(jnp.abs(slow - x).max()) == 0
