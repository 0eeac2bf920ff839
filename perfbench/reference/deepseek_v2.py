"""Plain DeepSeek-V2: the forward pass in `jax.numpy`.

Float32 under `jax.default_matmul_precision("highest")`, no kernel, no
cache, attention NOT absorbed (K and V expanded through `W_kvb`), a full
causal softmax: the published equations, of one chip's share of a stated
deployment, one sequence at a time. It imports nothing of the program
under test and is handed weights that `perfbench.weights_deepseekv2` made
from the seed.

Sizes (`config.json` of deepseek-ai/DeepSeek-V2): hidden 5120; 128 heads;
q_lora_rank 1536; kv_lora_rank 512; qk_nope_head_dim 128; qk_rope_head_dim
64; v_head_dim 128; intermediate_size 12288 (layer 0,
`first_k_dense_replace` 1); moe_intermediate_size 1536; n_routed_experts
160; n_shared_experts 2; num_experts_per_tok 6; n_group 8; topk_group 3;
`group_limited_greedy`, softmax scores, `norm_topk_prob` false;
routed_scaling_factor 16; rope_theta 1e4 under YaRN (factor 40, original
4096, beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707);
rms_norm_eps 1e-6; no attention bias; vocabulary 102400, head untied; 60
layers.

    h = Emb[tok];  x' = x + MLA(RMSNorm(x));  out = x' + F_l(RMSNorm(x'))
    logits = RMSNorm(h) W_head

MLA on x [T, 5120]:
  c_q = RMSNorm(x W_qa) (1536);  q = c_q W_qb, 128 heads of 192 =
  q_nope (128) ‖ q_pe (64);  [c_kv ‖ k_pe] = x W_kva (512 + 64);
  c_kv = RMSNorm(c_kv);  NO sqrt(hidden / rank) factors;  k_pe is shared
  by all heads;  [k_nope ‖ v] = c_kv W_kvb, per head 128 + 128;  RoPE on
  q_pe and k_pe;  scores (q_nope.k_nope + q_pe.k_pe) * s, causal softmax,
  o = concat_h(P v) W_o (16384 -> 5120).
YaRN (`yarn_frequencies`): f_i = theta^(-2i/64), i = 0..31;
  d(r) = 64 ln(4096 / (2 pi r)) / (2 ln theta); low = floor(d(32)) = 10,
  high = ceil(d(1)) = 23; ramp_i = clip((i - low) / (high - low), 0, 1);
  the frequency used is f_i (1 - ramp_i) + (f_i / 40) ramp_i; cos and sin
  carry m(mscale) / m(mscale_all_dim) = 1, m(a) = 0.1 a ln 40 + 1; and
  s = 192^-0.5 m(0.707)^2 = 0.11472.
F_0 is a SwiGLU `down(silu(gate x) * up x)` of 12288. For l >= 1
  F_l(y) = sum_{i in picks} w_i E_i(y) + S(y): p = softmax_160(y W_g) in
  float32; the 160 outputs are 8 groups of 20 consecutive; a group's score
  is the MAX of its p; the 3 best groups stay and p outside them is set to
  0; the picks are the top 6 of what is left; w_i = 16 p_i, not
  renormalised; no bias. E_i a SwiGLU of 1536, S ONE SwiGLU of 3072.

Departures, each also in the configuration's file:
  - RoPE rotates interleaved pairs (x[2i], x[2i+1]); the published code
    first de-interleaves each 64-wide rotary part and then rotates halves:
    the same fixed permutation of q_pe and of k_pe, so every score is the
    same;
  - ties, among groups' scores and among the picks, go to the lower index
    (`jax.lax.top_k`'s order; the published `torch.topk` leaves it open);
  - the chip's share: of the 160 routed experts only `held = (first,
    count)` are here (one routing group), and the parts of F_l(y) that the
    other experts would give are left out, here as in the program; the
    gate, the shared expert, the dense layer and attention are whole;
  - layers and vocabulary are cut as the configuration says: the
    embedding and the head hold a slice of the rows, and the softmax over
    the logits runs over the slice;
  - weights are held in the type they are served in (bfloat16 values,
    computed with in float32).

Long sequences go through a layer in blocks of `BLOCK` positions (the
queries of attention, the FFNs), so that no array of scores over a whole
context exists beside K and V of one; the head runs at served positions
alone.

`precision` selects what the products are computed in ("f32" the
reference proper, "bf16" and "fp8" the controls, as in `gpt2.py`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights_deepseekv2 as weights

HIGHEST = jax.lax.Precision.HIGHEST
#: positions whose queries, or whose FFN rows, are taken at once; a longer
#: sequence is padded to a multiple of it by its caller
BLOCK = 128


def _round(x, precision):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"precision {precision!r}")


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _blocks(fn, *xs):
    """`fn` over blocks of `BLOCK` leading positions of each of `xs`
    (whole where they are no longer than one block)."""
    T = xs[0].shape[0]
    if T <= BLOCK:
        return fn(*xs)
    if T % BLOCK:
        raise ValueError(f"{T} positions are no multiple of {BLOCK}")
    out = jax.lax.map(lambda a: fn(*a), tuple(
        x.reshape((T // BLOCK, BLOCK) + x.shape[1:]) for x in xs))
    return out.reshape((T,) + out.shape[2:])


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_mscale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(d):
    """The rope / 2 frequencies of the rotary part, float32."""
    dim = d.rope
    i = np.arange(dim // 2, dtype=np.float32)
    f = (1.0 / np.float32(d.rope_theta) ** (2 * i / np.float32(dim))
         ).astype(np.float32)
    def turns_at(r):
        return dim * math.log(d.rope_original / (2 * math.pi * r)) \
            / (2 * math.log(d.rope_theta))
    low = max(math.floor(turns_at(d.beta_fast)), 0)
    high = min(math.ceil(turns_at(d.beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0, 1).astype(
        np.float32)
    return f * (1 - ramp) + (f / np.float32(d.rope_factor)) * ramp


def softmax_scale(d):
    return (d.nope + d.rope) ** -0.5 * yarn_mscale(
        d.rope_factor, d.mscale_all_dim) ** 2


def rope(x, positions, d):
    """x [T, ..., rope] at `positions` [T], interleaved pairs, YaRN's
    frequencies; cos and sin times m(mscale) / m(mscale_all_dim)."""
    D = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * yarn_frequencies(d)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (D // 2,))
    m = yarn_mscale(d.rope_factor, d.mscale) / yarn_mscale(
        d.rope_factor, d.mscale_all_dim)
    cos, sin = m * jnp.cos(ang), m * jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (D // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def mla(p, x, d, precision):
    """x [T, hidden] of one sequence -> [T, hidden]."""
    T = x.shape[0]
    at = jnp.arange(T)
    kv = _einsum("se,er->sr", x, p["kv_a"], precision)
    c_kv = rms_norm(kv[:, :d.kv_rank], p["kv_a_norm"]["scale"], d.eps)
    k_pe = rope(kv[:, d.kv_rank:], at, d)                        # [T, rope]
    kvb = _einsum("sr,rhd->shd", c_kv, p["kv_b"], precision)
    k_nope, v = kvb[..., :d.nope], kvb[..., d.nope:]

    def one(x, qpos):
        c_q = rms_norm(_einsum("se,er->sr", x, p["q_a"], precision),
                       p["q_a_norm"]["scale"], d.eps)
        q = _einsum("sr,rhd->shd", c_q, p["q_b"], precision)
        q_nope, q_pe = q[..., :d.nope], rope(q[..., d.nope:], qpos, d)
        s = (_einsum("qhd,khd->hqk", q_nope, k_nope, precision)
             + _einsum("qhd,kd->hqk", q_pe, k_pe, precision)) \
            * softmax_scale(d)
        prob = jax.nn.softmax(
            jnp.where(at[None, None, :] <= qpos[None, :, None], s, -1e30),
            axis=-1)
        o = _einsum("hqk,khd->qhd", prob, v, precision)
        return _einsum("qhd,hde->qe", o, p["o"], precision)

    return _blocks(one, x, at)


def swiglu(p, x, precision):
    g = _einsum("se,ef->sf", x, p["gate"], precision)
    u = _einsum("se,ef->sf", x, p["up"], precision)
    return _einsum("sf,fe->se", jax.nn.silu(g) * u, p["down"], precision)


def gate(logits, d):
    """([S, k] picks, [S, k] weights) of [S, n_out] router logits: the
    group-limited greedy choice, ties to the lower index."""
    S, n = logits.shape
    prob = jax.nn.softmax(logits, axis=-1)
    best = prob.reshape(S, d.n_group, n // d.n_group).max(-1)
    _, groups = jax.lax.top_k(best, d.topk_group)                # [S, g]
    keep = (groups[:, :, None] == jnp.arange(d.n_group)).any(1)  # [S, G]
    left = jnp.where(jnp.repeat(keep, n // d.n_group, axis=1), prob, 0.0)
    _, idx = jax.lax.top_k(left, d.top_k)
    return idx, d.route_scale * jnp.take_along_axis(prob, idx, axis=-1)


def experts(p, y, d, precision, held=None, shared=True):
    """F_l(y) for y [S, hidden] on an expert layer: the part of `held =
    (first, count)` routed experts (default: the share the weights were
    made for) plus, unless `shared` is False, the shared expert's. `p`
    holds the held experts' stacked weights."""
    first, count = d.held if held is None else held
    logits = jnp.einsum("se,en->sn", y, p["router"], precision=HIGHEST)
    idx, w = gate(logits, d)
    out = swiglu(p["shared"], y, precision) if shared else jnp.zeros_like(y)
    for e in range(count):
        g = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1, keepdims=True)
        one = {k: p[k][e] for k in ("gate", "up", "down")}
        out = out + g * swiglu(one, y, precision)
    return out


def layer(p, x, d, precision="f32"):
    """One layer over one sequence x [T, hidden]; a dense layer's tree has
    `ffn`, an expert layer's `moe`."""
    p = _f32(p)
    h = x + mla(p["attn"], rms_norm(x, p["norm_attn"]["scale"], d.eps), d,
                precision)
    y = rms_norm(h, p["norm_ffn"]["scale"], d.eps)
    if "ffn" in p:
        return h + _blocks(lambda a: swiglu(p["ffn"], a, precision), y)
    return h + _blocks(lambda a: experts(p["moe"], a, d, precision), y)


def head(top, h, d, precision="f32"):
    top = _f32(top)
    return _einsum("se,ev->sv", rms_norm(h, top["norm"]["scale"], d.eps),
                   top["lm_head"], precision)


def forward(params, tokens, d, precision="f32"):
    """Logits [n, S, vocab] of [n, S] token ids from the program's tree
    (`weights_deepseekv2.make_params`): the whole model at once, for the
    tests' sizes."""
    def one(toks):
        h = params["embedding"].astype(jnp.float32)[toks]
        for i in range(d.layers):
            h = layer(params[f"layer_{i}"], h, d, precision)
        return head(params, h, d, precision)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, tokens)


# -- serving: the gap of each served token, layer by layer ---------------

@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _embed_from_seed(key, tokens, d, dtype):
    return weights.top_params(key, d, dtype)["embedding"].astype(
        jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision",
                                             "dense"),
                   donate_argnums=(2,))
def _layer_from_seed(key, index, h, d, dtype, precision, dense):
    p = weights.layer_params(key, d, index, dtype, dense)
    with jax.default_matmul_precision("highest"):
        # one sequence at a time: K and V of 128 heads over sixteen
        # thousand positions are two gigabytes a sequence in float32
        return jax.lax.map(lambda x: layer(p, x, d, precision), h)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision"))
def _head_from_seed(key, h, d, dtype, precision):
    with jax.default_matmul_precision("highest"):
        return head(weights.top_params(key, d, dtype), h, d, precision)


def hidden_from_seed(key, tokens, d, dtype, precision="f32"):
    """The hidden states [n, S, hidden] before the final norm of [n, S]
    sequences (S at most `BLOCK`, or a multiple of it), the weights
    remade from the seed one layer at a time in the type they are served
    in: a 2.7 GB float32 layer is all that is held at once."""
    h = _embed_from_seed(key, tokens, d, dtype)
    for index in range(d.layers):
        h = _layer_from_seed(key, jnp.int32(index), h, d, dtype, precision,
                             index < d.dense_layers)
    return h


@jax.jit
def _gaps(ref_logits, nxt, other_logits):
    """As `gpt2._gaps`, of logits [m, vocab] at picked positions and the
    tokens `nxt` [m] that followed them (`served`), and of the token that
    `other_logits` puts first (`other`): how far the reference's logit of
    it lies under the reference's best, the reference's log-probability
    of it, and the log-probability `other_logits` gives its own first
    token."""
    best = ref_logits.max(-1)
    ref_logp = jax.nn.log_softmax(ref_logits, axis=-1)
    def pick(a, i):
        return jnp.take_along_axis(a, i[..., None], -1)[..., 0]
    first = jnp.argmax(other_logits, -1)
    return {"served_gap": best - pick(ref_logits, nxt),
            "served_ref_logp": pick(ref_logp, nxt),
            "other_gap": best - pick(ref_logits, first),
            "other_ref_logp": pick(ref_logp, first),
            "other_own_logp": jax.nn.log_softmax(other_logits, -1).max(-1)}


def served_token_gaps(key, tokens, at, d, dtype, control=None,
                      positions: int = 1024):
    """`_gaps` of [n, S] sequences at positions `at` [n, m] (m a multiple
    of `positions`, or under it), each value [n, m]; position p speaks of
    the token at p + 1. The head runs over `positions` of one sequence at
    a time, and nowhere else. Without `control` the `other_*` entries are
    the reference's own first choice."""
    n, m = at.shape
    step = min(m, positions)
    if m % step:
        raise ValueError(f"{m} served positions are no multiple of {step}")
    ref_h = hidden_from_seed(key, tokens, d, dtype)
    other_h = ref_h if control is None else hidden_from_seed(
        key, tokens, d, dtype, control)
    nxt = jnp.take_along_axis(tokens, jnp.minimum(at + 1,
                                                  tokens.shape[1] - 1), 1)
    rows = []
    for i in range(n):
        parts = []
        for lo in range(0, m, step):
            pick = at[i, lo:lo + step]
            ref = _head_from_seed(key, ref_h[i][pick], d, dtype, "f32")
            other = ref if control is None else _head_from_seed(
                key, other_h[i][pick], d, dtype, control)
            parts.append(_gaps(ref, nxt[i, lo:lo + step], other))
        rows.append({k: jnp.concatenate([p[k] for p in parts])
                     for k in parts[0]})
    return {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}
