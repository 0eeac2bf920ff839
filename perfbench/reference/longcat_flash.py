"""Plain LongCat-Flash: the forward pass in `jax.numpy`.

Float32 under `jax.default_matmul_precision("highest")`, no kernel, no
cache, attention NOT absorbed: the published equations, of one chip's share
of a stated deployment. It imports nothing of the program under test and
is handed weights that `perfbench.weights_longcat` made from the seed.

Sizes (`config.json` of meituan-longcat/LongCat-Flash-Chat): hidden 6144;
64 heads; q_lora_rank 1536; kv_lora_rank 512; qk_nope_head_dim 128;
qk_rope_head_dim 64; v_head_dim 128; ffn_hidden_size 12288;
expert_ffn_hidden_size 2048; n_routed_experts 512; zero_expert_num 256
(identity); moe_topk 12; routed_scaling_factor 6; rope_theta 1e7, no
scaling; rms_norm_eps 1e-5; no attention bias; vocabulary 131072; 28
layers.

MLA sublayer on x [T, 6144]:
  c_q = RMSNorm(x W_qa) (1536);  q = c_q W_qb, 64 heads of 192 =
  q_nope (128) ‖ q_pe (64), both times s_q = sqrt(6144 / 1536) = 2
  (mla_scale_q_lora);
  [c_kv ‖ k_pe] = x W_kva (512 + 64);  c_kv = RMSNorm(c_kv) s_kv,
  s_kv = sqrt(6144 / 512) (mla_scale_kv_lora);  k_pe is not scaled, is
  shared by all heads and takes RoPE;  q_pe takes RoPE;  RoPE rotates
  interleaved pairs (x[2i], x[2i+1]) by pos * theta^(-2i/64);
  [k_nope ‖ v] = c_kv W_kvb, per head 128 + 128;
  scores (q_nope.k_nope + q_pe.k_pe) / sqrt(192), causal softmax,
  o = concat_h(P v) W_o (8192 -> 6144).

One layer (ScMoE), A0/A1 MLA sublayers, F0/F1 SwiGLU FFNs
`down(silu(gate x) * up x)` of 12288, M the expert layer, four RMSNorms:
  h1 = x + A0(norm_a0(x));  y = norm_f0(h1);  s = M(y);  h2 = h1 + F0(y)
  h3 = h2 + A1(norm_a1(h2));  out = h3 + F1(norm_f1(h3)) + s

Expert layer M(y): router logits y W_r in float32, 768 wide; p =
softmax(logits); the 12 picks are the top 12 of p + b (b the
score-correction bias, used for the choice only); weights w_i = 6 p_i of
the picked, not renormalised; M(y) = sum_i w_i E_i(y), E_i a SwiGLU expert
of 2048 for i < 512 and E_i(y) = y for the 256 identity experts. Then a
final RMSNorm and an untied head.

Departures, each also in the configuration's file:
  - `config.json` does not say whether the picked weights are
    renormalised, the head tied, RoPE interleaved, or what `b` is: assumed
    not renormalised, untied, interleaved, a buffer drawn from the seed;
  - the chip's share: of the 512 real experts only `held = (first, count)`
    are here, and the parts of M(y) that the other experts would give are
    left out (here as in the program); identity experts and everything
    outside the experts are computed in full;
  - layers and vocabulary are cut as the configuration says: the
    embedding and the head hold a slice of the rows, and the softmax over
    the logits runs over the slice;
  - weights are held in the type they are served in (bfloat16 values,
    computed with in float32).

`precision` selects what the products are computed in ("f32" the
reference proper, "bf16" and "fp8" the controls, as in `gpt2.py`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench import weights_longcat as weights

HIGHEST = jax.lax.Precision.HIGHEST


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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [S, ..., D] at positions 0..S-1, interleaved pairs."""
    S, D = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # [S, D/2]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    pairs = x.reshape(x.shape[:-1] + (D // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def mla(p, x, d, precision):
    """x [S, hidden] of one sequence -> [S, hidden]."""
    S = x.shape[0]
    c_q = rms_norm(_einsum("se,er->sr", x, p["q_a"], precision),
                   p["q_a_norm"]["scale"], d.eps)
    q = _einsum("sr,rhd->shd", c_q, p["q_b"], precision) \
        * math.sqrt(d.hidden / d.q_rank)
    q_nope, q_pe = q[..., :d.nope], rope(q[..., d.nope:], d.rope_theta)
    kv = _einsum("se,er->sr", x, p["kv_a"], precision)
    c_kv = rms_norm(kv[:, :d.kv_rank], p["kv_a_norm"]["scale"], d.eps) \
        * math.sqrt(d.hidden / d.kv_rank)
    k_pe = rope(kv[:, d.kv_rank:], d.rope_theta)                 # [S, rope]
    kvb = _einsum("sr,rhd->shd", c_kv, p["kv_b"], precision)
    k_nope, v = kvb[..., :d.nope], kvb[..., d.nope:]
    s = (_einsum("qhd,khd->hqk", q_nope, k_nope, precision)
         + _einsum("qhd,kd->hqk", q_pe, k_pe, precision)) \
        / math.sqrt(d.nope + d.rope)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -1e30)
    o = _einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision)
    return _einsum("qhd,hde->qe", o, p["o"], precision)


def swiglu(p, x, precision):
    g = _einsum("se,ef->sf", x, p["gate"], precision)
    u = _einsum("se,ef->sf", x, p["up"], precision)
    return _einsum("sf,fe->se", jax.nn.silu(g) * u, p["down"], precision)


def experts(p, y, d, precision, held=None):
    """M(y) for y [S, hidden]: the part of `held = (first, count)` real
    experts (default: the share the weights were made for) plus the
    identity experts'. `p` holds the held experts' stacked weights."""
    first, count = d.held if held is None else held
    logits = jnp.einsum("se,en->sn", y, p["router"], precision=HIGHEST)
    prob = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(prob + p["bias"], d.top_k)             # [S, k]
    w = d.route_scale * jnp.take_along_axis(prob, idx, axis=-1)
    out = jnp.sum(jnp.where(idx >= d.experts_published, w, 0.0), -1,
                  keepdims=True) * y
    for e in range(count):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1,
                       keepdims=True)                            # [S, 1]
        one = {k: p[k][e] for k in ("gate", "up", "down")}
        out = out + gate * swiglu(one, y, precision)
    return out


def layer(p, x, d, precision="f32"):
    """One ScMoE layer over one sequence x [S, hidden]."""
    p = _f32(p)
    norm = lambda n, v: rms_norm(v, p[n]["scale"], d.eps)        # noqa: E731
    h1 = x + mla(p["attn_0"], norm("norm_a0", x), d, precision)
    y = norm("norm_f0", h1)
    s = experts(p["moe"], y, d, precision)
    h2 = h1 + swiglu(p["ffn_0"], y, precision)
    h3 = h2 + mla(p["attn_1"], norm("norm_a1", h2), d, precision)
    return h3 + swiglu(p["ffn_1"], norm("norm_f1", h3), precision) + s


def head(top, h, d, precision="f32"):
    top = _f32(top)
    return _einsum("se,ev->sv", rms_norm(h, top["norm"]["scale"], d.eps),
                   top["lm_head"], precision)


def forward(params, tokens, d, precision="f32"):
    """Logits [n, S, vocab] of [n, S] token ids from the program's tree
    (`weights_longcat.make_params`): the whole model at once, for the
    tests' sizes."""
    with jax.default_matmul_precision("highest"):
        h = params["embedding"].astype(jnp.float32)[tokens]
        for i in range(d.layers):
            h = jax.vmap(lambda x, i=i: layer(params[f"layer_{i}"], x, d,
                                              precision))(h)
        return jax.vmap(lambda x: head(params, x, d, precision))(h)


# -- serving: the gap of each served token, layer by layer ---------------

@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _embed_from_seed(key, tokens, d, dtype):
    return weights.top_params(key, d, dtype)["embedding"].astype(
        jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision"),
                   donate_argnums=(2,))
def _layer_from_seed(key, index, h, d, dtype, precision):
    p = weights.layer_params(key, d, index, dtype)
    with jax.default_matmul_precision("highest"):
        # one sequence at a time: float32 scores of 64 heads over a few
        # thousand positions are a gigabyte a sequence
        return jax.lax.map(lambda x: layer(p, x, d, precision), h)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision"))
def _head_from_seed(key, h, d, dtype, precision):
    top = weights.top_params(key, d, dtype)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda x: head(top, x, d, precision), h)


def logits_from_seed(key, tokens, d, dtype, precision="f32"):
    """Logits of [n, S] sequences, the weights remade from the seed one
    layer at a time in the type they are served in: a 5 GB float32 layer
    is all that is held at once."""
    h = _embed_from_seed(key, tokens, d, dtype)
    for index in range(d.layers):
        h = _layer_from_seed(key, jnp.int32(index), h, d, dtype, precision)
    return _head_from_seed(key, h, d, dtype, precision)


@jax.jit
def _gaps(ref_logits, tokens, other_logits):
    """As `gpt2._gaps`: at each position p, of the token at p+1
    (`served`) and of the token that `other_logits` puts first
    (`other`): how far the reference's logit of it lies under the
    reference's best, the reference's log-probability of it, and the
    log-probability `other_logits` gives its own first token."""
    best = ref_logits.max(-1)
    ref_logp = jax.nn.log_softmax(ref_logits, axis=-1)
    def pick(a, i):
        return jnp.take_along_axis(a, i[..., None], -1)[..., 0]
    nxt = jnp.roll(tokens, -1, axis=1)
    first = jnp.argmax(other_logits, -1)
    return {"served_gap": best - pick(ref_logits, nxt),
            "served_ref_logp": pick(ref_logp, nxt),
            "other_gap": best - pick(ref_logits, first),
            "other_ref_logp": pick(ref_logp, first),
            "other_own_logp": jax.nn.log_softmax(other_logits, -1).max(-1)}


def served_token_gaps(key, tokens, d, dtype, control=None):
    """`_gaps` of [n, S] sequences, each value [n, S]; position p speaks
    of the token at p + 1. Without `control` the `other_*` entries are the
    reference's own first choice."""
    ref = logits_from_seed(key, tokens, d, dtype)
    other = ref if control is None else logits_from_seed(
        key, tokens, d, dtype, control)
    return _gaps(ref, tokens, other)
