"""Plain Granite-4.0-H: the forward pass in `jax.numpy`.

Float32 under `jax.default_matmul_precision("highest")`, no kernel, no
cache, no chunks, no batching: the state-space recurrence a `lax.scan`
over positions with the state held `[heads, channels, states]` as the
published code holds it (NOT the chunked form, nor the layout, of the
program), attention a full causal softmax, every held expert computed for
every token and weighted by its gate: the equations of
`perfbench/README-granite4hs.md`, of one chip's share of a stated
deployment, one sequence at a time. It imports nothing of the program
under test and is handed weights that `perfbench.weights_granite4hs` made
from the seed.

Sizes (`config.json` of ibm-granite/granite-4.0-h-small): hidden 4096; 40
layers, attention at 5, 15, 25, 35 and Mamba-2 elsewhere; 32 query heads
on 8 key heads of 128, no bias, `position_embedding_type` nope; Mamba-2
with 128 heads of 64 channels over 128 states in ONE group, conv 4 with
bias, expand 2; 72 experts of 768 (`intermediate_size`), 10 picks, a
shared expert of 1536; RMSNorm eps 1e-5; vocabulary 100352, head tied;
`embedding_multiplier` 12, `residual_multiplier` 0.22,
`attention_multiplier` 0.0078125, `logits_scaling` 16.

    h0 = 12 * E[tok]
    x' = x + 0.22 * Mixer_l(RMSNorm(x))
    y  = x' + 0.22 * (Routed(v) + Shared(v)),  v = RMSNorm'(x')
    logits = (RMSNorm(h) E^T) / 16
  Mixer_l, mamba: p = W_in u, its columns [z | x | B | C | dt] = 8192 +
      8192 + 128 + 128 + 128; [x | B | C] = silu(conv1d([x | B | C]));
      dt = softplus(dt + dt_bias); A = -exp(A_log); every head h reads the
      one group's B and C:
      S_h[t] = exp(dt_h A_h) S_h[t-1] + dt_h x_h[t] B[t]^T     [64 x 128]
      y_h = S_h C + D_h x_h;  RMSNorm over the 8192 channels of y *
      silu(z), with a learned scale; W_out.
  Mixer_l, attention: q, k, v = W_qkv u; NO position term; softmax(q k^T
      * 0.0078125) v, causal, query head i on key head i // 4; W_o.
  Routed: logits = v W_r (72, float32); the ten largest logits; gates =
      softmax over those ten; expert e: W2_e (silu(a) * b), [a | b] =
      W1_e v (held as `gate`, `up`, `down`). Shared: the same at 1536.

Departures, each also in the configuration's file:
  - ties among the router's logits go to the lower index
    (`jax.lax.top_k`'s order; the published `torch.topk` leaves it open);
  - the chip's share: of the 72 routed experts only `held = (first,
    count)` are here, and the parts of Routed(v) that the other experts
    would give are left out, here as in the program; the gate, the shared
    expert and both mixers are whole;
  - layers and vocabulary are cut as the configuration says: the tied
    table holds a slice of the rows, and the softmax over the logits runs
    over the slice;
  - `q`, `k`, `v` are one fused matrix, an expert's `W1` is held as its
    two halves `gate` and `up`: the same products;
  - dt is not clamped (`time_step_limit` (0, inf), the published default);
  - weights are held in the type they are served in (bfloat16 values,
    computed with in float32).

Long sequences go through the experts and the attention's queries in
blocks of positions (`BLOCK`), so that no array of scores over a whole
context exists; a layer's weights are remade from the seed when the layer
runs.

`precision` selects what the products are computed in and the recurrent
state kept in ("f32" the reference proper, "bf16" and "fp8" the controls,
as in `gpt2.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import weights_granite4hs as weights

HIGHEST = jax.lax.Precision.HIGHEST
#: positions the experts or an attention's queries take at once; a
#: sequence is padded to a multiple of it by its caller when longer
BLOCK = 256


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


def gate(logits, d):
    """([S, k] picks, [S, k] weights) of [S, n_out] router logits: the
    `top_k` largest logits, a softmax over those alone; ties to the lower
    index."""
    top, idx = jax.lax.top_k(logits, d.top_k)
    return idx, jax.nn.softmax(top, axis=-1)


def experts(p, v, d, precision, held=None, shared=True):
    """Routed(v) + Shared(v) for v [S, hidden]: the part of `held =
    (first, count)` routed experts (default: the share the weights were
    made for) plus, unless `shared` is False, the shared expert's. `p`
    holds the held experts' stacked weights."""
    first, count = d.held if held is None else held
    logits = jnp.einsum("se,en->sn", v, p["router"], precision=HIGHEST)
    idx, w = gate(logits, d)
    out = swiglu(p["shared"], v, precision) if shared else jnp.zeros_like(v)
    for e in range(count):
        g = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1, keepdims=True)
        one = {k: p[k][e] for k in ("gate", "up", "down")}
        out = out + g * swiglu(one, v, precision)
    return out


def attention(p, u, d, precision, rotary=None):
    """u [T, hidden] -> [T, hidden]. `rotary` is for the tests alone: a
    function of (x [T, heads, D], positions) put on q and k shows that the
    absence of a position term is load-bearing."""
    T = u.shape[0]
    H, KV, D = d.heads, d.kv_heads, d.head_dim
    qkv = _einsum("se,ec->sc", u, p["Wqkv"], precision)
    at = jnp.arange(T)
    q = qkv[:, :H * D].reshape(T, H, D)
    k = qkv[:, H * D:(H + KV) * D].reshape(T, KV, D)
    v = qkv[:, (H + KV) * D:].reshape(T, KV, D)
    if rotary is not None:
        q, k = rotary(q, at), rotary(k, at)
    q = q.reshape(T, KV, H // KV, D)

    def one(q, qpos):
        s = _einsum("qjrd,kjd->jrqk", q, k, precision) \
            * d.attention_multiplier
        prob = jax.nn.softmax(
            jnp.where(at[None, :] <= qpos[:, None], s, -1e30), axis=-1)
        return _einsum("jrqk,kjd->qjrd", prob, v, precision)

    if T <= BLOCK:
        a = one(q, at)
    else:
        a = jax.lax.map(lambda qa: one(*qa),
                        (q.reshape((T // BLOCK, BLOCK) + q.shape[1:]),
                         at.reshape(T // BLOCK, BLOCK)))
    return _einsum("sc,ce->se", a.reshape(T, H * D), p["out_proj"],
                   precision)


def mamba2(p, u, d, precision):
    """u [T, hidden] -> [T, hidden]."""
    T = u.shape[0]
    Dm, Hm, P, N, K = d.d_ssm, d.ssm_heads, d.ssm_head_dim, d.d_state, \
        d.groups
    W, Dc = d.d_conv, d.conv_dim
    proj = _einsum("se,ec->sc", u, p["in_proj"], precision)
    z, xbc, dt = proj[:, :Dm], proj[:, Dm:Dm + Dc], proj[:, Dm + Dc:]
    padded = jnp.concatenate([jnp.zeros((W - 1, Dc)), xbc], 0)
    xbc = jax.nn.silu(p["conv_b"] + sum(padded[k:k + T] * p["conv_w"][k]
                                        for k in range(W)))
    x = xbc[:, :Dm].reshape(T, Hm, P)
    B = jnp.repeat(xbc[:, Dm:Dm + K * N].reshape(T, K, N), Hm // K, axis=1)
    C = jnp.repeat(xbc[:, Dm + K * N:].reshape(T, K, N), Hm // K, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # [T, Hm]
    A = -jnp.exp(p["A_log"])

    def step(s, at):
        x_t, dt_t, b_t, c_t = at                  # [Hm, P], [Hm], [Hm, N] x2
        s = jnp.exp(dt_t * A)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        s = _round(s, precision)
        return s, jnp.sum(s * c_t[:, None, :], -1) + p["D"][:, None] * x_t
    _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N)), (x, dt, B, C))
    y = (y.reshape(T, Dm) * jax.nn.silu(z)).reshape(T, K, Dm // K)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + d.eps)
    return _einsum("sd,de->se", y.reshape(T, Dm) * p["norm"], p["out_proj"],
                   precision)


def layer(p, x, d, precision="f32", held=None, shared=True, rotary=None):
    """One layer over one sequence x [T, hidden]; a mamba layer's tree has
    `mamba`, an attention layer's `attn`."""
    p = _f32(p)
    u = rms_norm(x, p["input_layernorm"]["scale"], d.eps)
    mixed = mamba2(p["mamba"], u, d, precision) if "mamba" in p \
        else attention(p["attn"], u, d, precision, rotary)
    h = x + d.residual_multiplier * mixed
    v = rms_norm(h, p["post_attention_layernorm"]["scale"], d.eps)
    return h + d.residual_multiplier * _blocks(
        lambda a: experts(p["moe"], a, d, precision, held, shared), v)


def head(top, h, d, precision="f32"):
    """[n, hidden] hidden states -> their logits [n, vocab] on the tied
    table."""
    top = _f32(top)
    return _einsum("se,ve->sv",
                   rms_norm(h, top["final_layernorm"]["scale"], d.eps),
                   top["embedding"], precision) / d.logits_scaling


def forward(params, tokens, d, precision="f32", rotary=None):
    """Logits [n, S, vocab] of [n, S] token ids from the program's tree
    (`weights_granite4hs.make_params`): the whole model at once, for the
    tests' sizes."""
    def one(toks):
        h = d.embedding_multiplier \
            * params["embedding"].astype(jnp.float32)[toks]
        for l in range(d.layers):
            h = layer(params[f"layer_{l}"], h, d, precision, rotary=rotary)
        return head(params, h, d, precision)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, tokens)


# -- serving: the gap of each served token, layer by layer ---------------

@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _embed_from_seed(key, tokens, d, dtype):
    return d.embedding_multiplier * weights.top_params(
        key, d, dtype)["embedding"].astype(jnp.float32)[tokens]


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
    in: a 1.9 GB float32 layer is all that is held at once."""
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
