"""Plain Falcon-H1: the forward pass in `jax.numpy`.

Float32 under `jax.default_matmul_precision("highest")`, no kernel, no
cache, no chunks, the state-space recurrence a `lax.scan` over time (NOT
the chunked form the program uses), attention a full causal softmax: the
equations of `perfbench/README-falconh1.md`, one sequence at a time. It
imports nothing of the program under test and is handed weights that
`perfbench.weights_falconh1` made from the seed.

Sizes (`config.json` of tiiuae/Falcon-H1-34B-Instruct): hidden 5120; 20
query heads and 4 key/value heads of 128, no bias, rotate-half RoPE with
theta 1e11; FFN 21504 (SiLU, gated, no bias); Mamba-2 with `d_ssm` 4096 =
32 heads of 128, 256 states, 2 groups, conv 4 with bias; RMSNorm eps 1e-5;
vocabulary 261120, head untied; the muP multipliers as published.

    h0 = embedding_multiplier * E[tok]
    u  = RMSNorm(x)
    x' = x + ssm_out_multiplier * Mamba2(u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    y  = x' + mlp_multipliers[1] * W_down(up * silu(mlp_multipliers[0] *
         gate)), [gate, up] = W_gu RMSNorm'(x')
    logits = lm_head_multiplier * W_head RMSNorm(h)
  Attn: q, k, v = W_qkv u; k = key_multiplier * k; RoPE on q and k;
      softmax(q k^T / sqrt(128)) v, causal, query head i on key head
      i // 5; W_o.
  Mamba2: p = W_in(ssm_in_multiplier * u), its columns [z | x | B | C |
      dt] = 4096 + 4096 + 512 + 512 + 32 scaled by ssm_multipliers[0..4];
      [x | B | C] = silu(conv1d([x | B | C])); dt = softplus(dt +
      dt_bias); A = -exp(A_log); head h, group g = h // 16:
      S_h[t] = exp(dt_h A_h) S_h[t-1] + dt_h x_h[t] B_g[t]^T  [128 x 256]
      y_h = S_h C_g + D_h x_h;  RMSNorm over each group's 2048 channels
      of y * silu(z), with a learned scale; W_out.

Weights are held in the type they are served in (bfloat16 values,
computed with in float32). Long sequences go through the MLP and the
attention's queries in blocks of positions (`BLOCK`), so that no array of
scores over a whole context exists; a layer's weights are remade from the
seed when the layer runs, and the head a block of the vocabulary at a
time, so the 17.6 GB of float32 weights never exist.

`precision` selects what the products are computed in and the recurrent
state kept in ("f32" the reference proper, "bf16" and "fp8" the controls,
as in `gpt2.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import weights_falconh1 as weights

HIGHEST = jax.lax.Precision.HIGHEST
#: positions an MLP or an attention's queries take at once; a sequence is
#: padded to a multiple of it by its caller when longer
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


def mlp(p, x, d, precision):
    F = d.ffn
    m_gate, m_down = d.mlp_multipliers
    def one(x):
        gu = _einsum("se,ef->sf", x, p["gate_up"], precision)
        return m_down * _einsum(
            "sf,fe->se", gu[:, F:] * jax.nn.silu(m_gate * gu[:, :F]),
            p["down"], precision)
    return _blocks(one, x)


def rotate(x, positions, theta):
    """Rotate-half RoPE of x [T, heads, D] at `positions` [T]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None].astype(jnp.float32) * freqs     # [T, half]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, u, d, precision):
    """u [T, hidden] (already times attention_in_multiplier) -> [T,
    hidden]."""
    T = u.shape[0]
    H, KV, D = d.heads, d.kv_heads, d.head_dim
    qkv = _einsum("se,ec->sc", u, p["Wqkv"], precision)
    at = jnp.arange(T)
    q = rotate(qkv[:, :H * D].reshape(T, H, D), at, d.rope_theta)
    k = rotate((d.key_multiplier * qkv[:, H * D:(H + KV) * D]).reshape(
        T, KV, D), at, d.rope_theta)
    v = qkv[:, (H + KV) * D:].reshape(T, KV, D)
    q = q.reshape(T, KV, H // KV, D)

    def one(q, qpos):
        s = _einsum("qjrd,kjd->jrqk", q, k, precision) / (D ** 0.5)
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
    """u [T, hidden] (the normed input itself) -> [T, hidden]."""
    T = u.shape[0]
    Dm, Hm, P, N, K = d.d_ssm, d.ssm_heads, d.ssm_head_dim, d.d_state, \
        d.groups
    W, Dc = d.d_conv, d.conv_dim
    proj = _einsum("se,ec->sc", d.ssm_in_multiplier * u, p["in_proj"],
                   precision)
    proj = proj * jnp.concatenate([
        jnp.full((n,), m) for n, m in zip(d.in_proj_segments,
                                          d.ssm_multipliers)])
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


def layer(p, x, d, precision="f32"):
    """One layer over one sequence x [T, hidden]."""
    p = _f32(p)
    u = rms_norm(x, p["input_layernorm"]["scale"], d.eps)
    h = x + d.ssm_out_multiplier * mamba2(p["mamba"], u, d, precision) \
        + d.attention_out_multiplier * attention(
            p["attn"], d.attention_in_multiplier * u, d, precision)
    return h + mlp(p["mlp"], rms_norm(h, p["pre_ff_layernorm"]["scale"],
                                      d.eps), d, precision)


def head(norm, rows, h, d, precision="f32"):
    """[n, hidden] hidden states -> their logits [n, len(rows)] on the
    head's `rows` [., hidden]."""
    return d.lm_head_multiplier * _einsum(
        "se,ve->sv", rms_norm(h, norm["scale"].astype(jnp.float32), d.eps),
        rows.astype(jnp.float32), precision)


def forward(params, tokens, d, precision="f32"):
    """Logits [n, S, vocab] of [n, S] token ids from the program's tree
    (`weights_falconh1.make_params`): the whole model at once, for the
    tests' sizes."""
    def one(toks):
        h = d.embedding_multiplier \
            * params["embedding"].astype(jnp.float32)[toks]
        for l in range(d.layers):
            h = layer(params[f"layer_{l}"], h, d, precision)
        return head(params["final_layernorm"], params["lm_head"], h, d,
                    precision)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, tokens)


# -- serving: the gap of each served token, layer by layer ---------------

@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _embed_from_seed(key, tokens, d, dtype):
    """[n, S] ids -> [n, S, hidden], a block of the table's rows at a
    time."""
    rows = d.row_blocks

    def part(h, block):
        table = weights.table_rows(key, d, dtype, block).astype(jnp.float32)
        local = tokens - block * rows
        here = (local >= 0) & (local < rows)
        return h + jnp.where(here[..., None],
                             table[jnp.clip(local, 0, rows - 1)], 0.0), None
    h, _ = jax.lax.scan(part, jnp.zeros(tokens.shape + (d.hidden,)),
                        jnp.arange(d.vocab // rows))
    return d.embedding_multiplier * h


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision"),
                   donate_argnums=(2,))
def _layer_from_seed(key, index, h, d, dtype, precision):
    p = weights.layer_params(key, d, index, dtype)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda a: layer(p, a, d, precision), h)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision"))
def _head_from_seed(key, h, d, dtype, precision):
    """h [n, hidden] -> [n, vocab], a block of the head's rows at a
    time."""
    norm = weights.final_norm(key, d, dtype)
    with jax.default_matmul_precision("highest"):
        parts = jax.lax.map(
            lambda b: head(norm, weights.head_rows(key, d, dtype, b), h, d,
                           precision),
            jnp.arange(d.vocab // d.row_blocks))
    return jnp.moveaxis(parts, 0, 1).reshape(h.shape[0], d.vocab)


def hidden_from_seed(key, tokens, d, dtype, precision="f32"):
    """The hidden states [n, S, hidden] before the final norm of [n, S]
    sequences (S at most `BLOCK`, or a multiple of it), the weights
    remade from the seed one layer at a time in the type they are served
    in."""
    h = _embed_from_seed(key, tokens, d, dtype)
    for index in range(d.layers):
        h = _layer_from_seed(key, jnp.int32(index), h, d, dtype, precision)
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
                      positions: int = 512):
    """`_gaps` of [n, S] sequences at positions `at` [n, m] (m a multiple
    of `positions`, or under it), each value [n, m]; position p speaks of
    the token at p + 1. The head runs over `positions` of one sequence at
    a time: logits over the whole vocabulary exist for that many and no
    more. Without `control` the `other_*` entries are the reference's own
    first choice."""
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
