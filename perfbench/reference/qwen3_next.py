"""Plain Qwen3-Next: the forward pass in `jax.numpy`.

Float32 under `jax.default_matmul_precision("highest")`, no kernel, no
cache, no chunks, no batching: the gated delta rule a `lax.scan` over
positions with the state held `[value heads, key dim, value dim]` as the
published `torch_recurrent_gated_delta_rule` holds it (NOT the chunked WY
form of the program), attention a full causal softmax, every held expert
computed for every token and weighted by its gate: the equations of
`perfbench/README-qwen3next.md`, of one chip's share of a stated
deployment, one sequence at a time. It imports nothing of the program
under test and is handed weights that `perfbench.weights_qwen3next` made
from the seed.

Sizes (`config.json` of Qwen/Qwen3-Next-80B-A3B-Instruct): hidden 2048; 48
layers, layer l full attention when (l + 1) % 4 == 0 and the delta rule
elsewhere; attention with 16 query heads on 2 key heads of 256, no bias,
`partial_rotary_factor` 0.25 (rotary over dims 0..63), `rope_theta` 1e7;
the delta rule with 16 key heads and 32 value heads of 128, conv 4 without
bias; 512 experts of 512, 10 picks, `norm_topk_prob` true, a shared expert
of 512; RMSNorm eps 1e-6; vocabulary 151936, head untied.

    h0 = E[tok]
    x' = x + Mixer_l(RMSNorm(x))
    y  = x' + Routed(v) + sigmoid(w_s^T v) Shared(v),  v = RMSNorm'(x')
    logits = W_head RMSNorm(h)
  Mixer_l, delta rule: [q | k | v | z] = W_qkvz u (2048 + 2048 + 4096 +
      4096), [b | a] = W_ba u (32 + 32); [q | k | v] = silu(conv1d([q | k
      | v])); q, k L2-normalised over each key head's 128 dims (x *
      rsqrt(sum x^2 + 1e-6)), q times 128^-0.5; key head j serves value
      heads 2j, 2j + 1; beta = sigmoid(b); g = -exp(A_log) softplus(a +
      dt_bias);
      S = exp(g_t) S;  r = S^T k_t;  S = S + k_t (beta_t (v_t - r))^T;
      o_t = S^T q_t                                           [128 x 128]
      out = W_o [w * RMSNorm_head(o) * silu(z)], the norm over a head's 128
      dims, one scale of 128 shared by the heads.
  Mixer_l, attention: [q | gate] a head = W_q u (16 x 512), k, v = W_k u,
      W_v u (512 each); q, k RMSNormed over a head's 256 dims with a
      learned scale, rotate-half RoPE on dims 0..63; softmax(q k^T
      256^-0.5) v, causal, query head i on key head i // 8; out = W_o [a *
      sigmoid(gate)].
  Routed: p = softmax(W_r v) over 512 in float32; the ten largest; weights
      p_i / (their sum); expert e: W2_e (silu(W1g_e v) * W1u_e v). Shared:
      the same at 512, times sigmoid(w_s^T v).

Departures, each also in the configuration's file:
  - a norm's `scale` is the multiplier itself: the published code holds w
    and multiplies by (1 + w), except in the delta rule's gated norm, which
    multiplies by w; the weights module draws every scale round 1;
  - `W_qkvz`'s columns are [q | k | v | z] and `W_ba`'s [b | a], each part
    whole; the published code interleaves them by key head: a layout of
    the same products;
  - `q & gate`, `k`, `v` of the attention are one fused matrix, an expert's
    `W1` is held as its two halves `gate` and `up`;
  - ties among the router's probabilities go to the lower index
    (`jax.lax.top_k`'s order; the published `torch.topk` leaves it open);
  - the chip's share: of the 512 routed experts only `held = (first,
    count)` are here, and the parts of Routed(v) that the other experts
    would give are left out, here as in the program; the gate, the shared
    expert and both mixers are whole;
  - layers and vocabulary are cut as the configuration says: the table and
    the head hold a slice of the rows, and the softmax over the logits
    runs over the slice;
  - the multi-token-prediction module is left out;
  - weights are held in the type they are served in (bfloat16 values,
    computed with in float32).

Long sequences go through the experts and the attention's queries in
blocks of positions (`BLOCK`), so that no array of scores over a whole
context exists; a layer's weights are remade from the seed when the layer
runs.

`precision` selects what the products are computed in and the recurrent
state kept in ("f32" the reference proper, "bf16" and "fp8" the controls,
as in `gpt2.py`). `without` is for the tests alone: it names pieces of the
mathematics to leave out or change, each of which a sound program then
fails against (`PIECES`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import weights_qwen3next as weights

HIGHEST = jax.lax.Precision.HIGHEST
#: positions the experts or an attention's queries take at once; a
#: sequence is padded to a multiple of it by its caller when longer
BLOCK = 256
#: what `without` may name: the attention's output gate, its q and k norms,
#: rotary over PART of a head (without: over all of it), the delta rule's
#: L2 norm of q and k, the shared expert's own gate, the division by the
#: ten's sum (without: the ten's probabilities among all 512, undivided)
PIECES = ("out_gate", "qk_norm", "partial_rotary", "l2norm", "shared_gate",
          "norm_topk")


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


def _blocks(fn, x, *rest):
    """`fn` over blocks of `BLOCK` leading positions of x (whole where it
    is no longer than one block)."""
    T = x.shape[0]
    if T <= BLOCK:
        return fn(x, *rest)
    if T % BLOCK:
        raise ValueError(f"{T} positions are no multiple of {BLOCK}")
    out = jax.lax.map(lambda a: fn(a, *rest),
                      x.reshape((T // BLOCK, BLOCK) + x.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def swiglu(p, x, precision):
    g = _einsum("se,ef->sf", x, p["gate"], precision)
    u = _einsum("se,ef->sf", x, p["up"], precision)
    return _einsum("sf,fe->se", jax.nn.silu(g) * u, p["down"], precision)


def gate(logits, d, without=()):
    """([S, k] picks, [S, k] weights) of [S, n_out] router logits, in the
    published order: a softmax over every output, the `top_k` largest,
    each over their sum; ties to the lower index."""
    p = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(p, d.top_k)
    if "norm_topk" in without:
        return idx, top
    return idx, top / jnp.sum(top, -1, keepdims=True)


def experts(p, v, d, precision, held=None, shared=True, without=()):
    """Routed(v) + g_s(v) Shared(v) for v [S, hidden]: the part of `held =
    (first, count)` routed experts (default: the share the weights were
    made for) plus, unless `shared` is False, the gated shared expert's.
    `p` holds the held experts' stacked weights."""
    first, count = d.held if held is None else held
    logits = jnp.einsum("se,en->sn", v, p["router"], precision=HIGHEST)
    idx, w = gate(logits, d, without)
    out = jnp.zeros_like(v)
    if shared:
        out = swiglu(p["shared"], v, precision)
        if "shared_gate" not in without:
            out = out * jax.nn.sigmoid(_einsum(
                "se,e->s", v, p["shared_gate"], precision))[:, None]
    for e in range(count):
        g = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1, keepdims=True)
        one = {k: p[k][e] for k in ("gate", "up", "down")}
        out = out + g * swiglu(one, v, precision)
    return out


def rotate_half(x, at, dims, theta):
    """x [T, heads, D] rotated over its first `dims` dims by position."""
    half = dims // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = at[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def attention(p, u, d, precision, without=()):
    """u [T, hidden] -> [T, hidden]."""
    T = u.shape[0]
    H, KV, D = d.heads, d.kv_heads, d.head_dim
    qkv = _einsum("se,ec->sc", u, p["Wqkv"], precision)
    at = jnp.arange(T)
    qg = qkv[:, :2 * H * D].reshape(T, H, 2 * D)
    q, out_gate = qg[..., :D], qg[..., D:].reshape(T, H * D)
    k = qkv[:, 2 * H * D:(2 * H + KV) * D].reshape(T, KV, D)
    v = qkv[:, (2 * H + KV) * D:].reshape(T, KV, D)
    if "qk_norm" not in without:
        q = rms_norm(q, p["q_norm"]["scale"], d.eps)
        k = rms_norm(k, p["k_norm"]["scale"], d.eps)
    dims = D if "partial_rotary" in without else d.rotary_dim
    q = rotate_half(q, at, dims, d.rope_theta)
    k = rotate_half(k, at, dims, d.rope_theta)
    q = q.reshape(T, KV, H // KV, D)

    def one(q, qpos):
        s = _einsum("qjrd,kjd->jrqk", q, k, precision) * D ** -0.5
        prob = jax.nn.softmax(
            jnp.where(at[None, :] <= qpos[:, None], s, -1e30), axis=-1)
        return _einsum("jrqk,kjd->qjrd", prob, v, precision)

    if T <= BLOCK:
        a = one(q, at)
    else:
        a = jax.lax.map(lambda qa: one(*qa),
                        (q.reshape((T // BLOCK, BLOCK) + q.shape[1:]),
                         at.reshape(T // BLOCK, BLOCK)))
    a = a.reshape(T, H * D)
    if "out_gate" not in without:
        a = a * jax.nn.sigmoid(out_gate)
    return _einsum("sc,ce->se", a, p["out_proj"], precision)


def delta_rule(p, u, d, precision, without=()):
    """u [T, hidden] -> [T, hidden]."""
    T = u.shape[0]
    Hk, Hv, Dk, Dv = d.key_heads, d.value_heads, d.key_head_dim, \
        d.value_head_dim
    Kd, Vd, Dc, W = d.key_dim, d.value_dim, d.conv_dim, d.d_conv
    proj = _einsum("se,ec->sc", u, p["in_proj_qkvz"], precision)
    ba = _einsum("se,ec->sc", u, p["in_proj_ba"], precision)
    qkv, z = proj[:, :Dc], proj[:, Dc:].reshape(T, Hv, Dv)
    padded = jnp.concatenate([jnp.zeros((W - 1, Dc)), qkv], 0)
    qkv = jax.nn.silu(sum(padded[i:i + T] * p["conv_w"][i]
                          for i in range(W)))
    q = qkv[:, :Kd].reshape(T, Hk, Dk)
    k = qkv[:, Kd:2 * Kd].reshape(T, Hk, Dk)
    v = qkv[:, 2 * Kd:].reshape(T, Hv, Dv)
    if "l2norm" not in without:
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(q * Dk ** -0.5, Hv // Hk, axis=1)          # [T, Hv, Dk]
    k = jnp.repeat(k, Hv // Hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, Hv:] + p["dt_bias"])

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at       # [Hv, Dk] x2, [Hv, Dv], [Hv] x2
        s = s * jnp.exp(g_t)[:, None, None]
        r = jnp.sum(s * k_t[:, :, None], axis=1)               # [Hv, Dv]
        s = s + k_t[:, :, None] * ((v_t - r) * b_t[:, None])[:, None, :]
        s = _round(s, precision)
        return s, jnp.sum(s * q_t[:, :, None], axis=1)
    _, o = jax.lax.scan(step, jnp.zeros((Hv, Dk, Dv)), (q, k, v, g, beta))
    o = rms_norm(o, p["norm"], d.eps) * jax.nn.silu(z)
    return _einsum("sd,de->se", o.reshape(T, Vd), p["out_proj"], precision)


def layer(p, x, d, precision="f32", held=None, shared=True, without=()):
    """One layer over one sequence x [T, hidden]; a delta-rule layer's
    tree has `delta`, an attention layer's `attn`."""
    p = _f32(p)
    u = rms_norm(x, p["input_layernorm"]["scale"], d.eps)
    mixed = delta_rule(p["delta"], u, d, precision, without) \
        if "delta" in p else attention(p["attn"], u, d, precision, without)
    h = x + mixed
    v = rms_norm(h, p["post_attention_layernorm"]["scale"], d.eps)
    return h + _blocks(
        lambda a: experts(p["moe"], a, d, precision, held, shared, without),
        v)


def head(top, h, d, precision="f32"):
    """[n, hidden] hidden states -> their logits [n, vocab] on the untied
    head."""
    top = _f32(top)
    return _einsum("se,ve->sv",
                   rms_norm(h, top["final_layernorm"]["scale"], d.eps),
                   top["lm_head"], precision)


def forward(params, tokens, d, precision="f32", without=()):
    """Logits [n, S, vocab] of [n, S] token ids from the program's tree
    (`weights_qwen3next.make_params`): the whole model at once, for the
    tests' sizes."""
    odd = set(without) - set(PIECES)
    if odd:
        raise ValueError(f"without={sorted(odd)}: the pieces are {PIECES}")

    def one(toks):
        h = params["embedding"].astype(jnp.float32)[toks]
        for l in range(d.layers):
            h = layer(params[f"layer_{l}"], h, d, precision, without=without)
        return head(params, h, d, precision)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, tokens)


# -- serving: the gap of each served token, layer by layer ---------------

@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _embed_from_seed(key, tokens, d, dtype):
    return weights.top_params(key, d, dtype)["embedding"].astype(
        jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision",
                                             "kind"),
                   donate_argnums=(2,))
def _layer_from_seed(key, index, h, d, dtype, precision, kind):
    p = weights.layer_params(key, d, index, dtype, kind)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda a: layer(p, a, d, precision), h)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision"))
def _head_from_seed(key, h, d, dtype, precision):
    with jax.default_matmul_precision("highest"):
        return head(weights.top_params(key, d, dtype), h, d, precision)


def hidden_from_seed(key, tokens, d, dtype, precision="f32"):
    """The hidden states [n, S, hidden] before the final norm of [n, S]
    sequences (S at most `BLOCK`, or a multiple of it), the weights
    remade from the seed one layer at a time in the type they are served
    in: a 1.7 GB float32 layer is all that is held at once."""
    h = _embed_from_seed(key, tokens, d, dtype)
    for index, kind in enumerate(d.layer_types):
        h = _layer_from_seed(key, jnp.int32(index), h, d, dtype, precision,
                             kind)
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
