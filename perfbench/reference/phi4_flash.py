"""Plain Phi-4-mini-flash-reasoning: the forward pass in `jax.numpy`.

Float32 under `jax.default_matmul_precision("highest")`, no kernel, no
cache, no chunks, the state-space recurrence a `lax.scan` over time: the
equations of `perfbench/README-phi4flash.md`, one sequence at a time. It
imports nothing of the program under test and is handed weights that
`perfbench.weights_phi4flash` made from the seed.

Sizes (`config.json` of microsoft/Phi-4-mini-flash-reasoning): hidden
2560; 40 query heads and 20 key/value heads of 64; FFN 10240 (SiLU, gated,
no bias); 32 layers; `mb_per_layer` 2; `sliding_window` 512;
`layer_norm_eps` 1e-5; vocabulary 200064, head tied, no head bias. What
the config does not say (the state-space layer's sizes, the differential
attention's pairing, lambdas and norm, which layers are which) is the
family's convention and listed under `assumed` in the configuration's file.

Layer l (0-based, `half` = 16), LayerNorm with scale and bias, no
positional encoding:
  h = x + Mixer_l(LN(x));  y = h + W_down(up * silu(gate)), [gate, up] =
  W_gu LN'(h).
  l = 0, 2, .., 16   Mamba-1: [x, z] = W_in u; x = silu(conv1d(x)) (causal,
      depthwise, width 4, bias); [dr, B, C] = W_x x (160 + 16 + 16);
      delta = softplus(W_dt dr + b_dt); A = -exp(A_log);
      s_t = exp(delta_t A) s_{t-1} + (delta_t x_t) B_t^T;
      y_t = s_t C_t + D x_t; out = W_out(y_t silu(z_t)). Layer 16's y is
      m, the memory the upper layers gate.
  l = 1, 3, .., 15   differential attention, token t sees t-511 .. t.
  l = 17             differential attention over the whole context.
  l = 18, 20, .., 30 gated memory unit: W_out(silu(W_in u) * m).
  l = 19, 21, .., 31 differential attention with queries of its own and
      layer 17's keys and values.
Differential attention of layer l: pair i < 20 has queries q1_i, q2_i
(query heads 2i, 2i+1), pair j = i // 2 < 10 keys k1_j, k2_j and values
v_j = [v1_j | v2_j] (key/value heads 2j, 2j+1);
a1 = softmax(q1 k1^T / 8) v, a2 = softmax(q2 k2^T / 8) v,
lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0, lam0 = 0.8 - 0.6 exp(-0.3 l),
o_i = (1 - lam0) RMSNorm_128(a1 - lam a2) (learned scale); W_o with bias.

Weights are held in the type they are served in (bfloat16 values,
computed with in float32). Long sequences go through the MLP and the
attentions in blocks of positions (`BLOCK`), so that no array of scores
over a whole context exists; a layer's weights are remade from the seed
when the layer runs, so the 15.4 GB of float32 weights never exist.

`precision` selects what the products are computed in and the recurrent
state kept in ("f32" the reference proper, "bf16" and "fp8" the controls,
as in `gpt2.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import weights_phi4flash as weights

HIGHEST = jax.lax.Precision.HIGHEST
#: positions an MLP or an attention takes at once; a sequence is padded to
#: a multiple of it by its caller when longer
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


def layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def mlp(p, x, precision):
    F = p["down"].shape[0]
    def one(x):
        gu = _einsum("se,ef->sf", x, p["gate_up"], precision)
        return _einsum("sf,fe->se", gu[:, F:] * jax.nn.silu(gu[:, :F]),
                       p["down"], precision)
    return _blocks(one, x)


def mamba(p, u, d, precision):
    """u [T, hidden] of one sequence -> (out [T, hidden], y [T, Din])."""
    T = u.shape[0]
    Din, N, R, K = d.d_inner, d.d_state, d.dt_rank, d.d_conv
    xz = _einsum("se,ed->sd", u, p["in_proj"], precision)
    x, z = xz[:, :Din], xz[:, Din:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Din)), x], 0)
    x = jax.nn.silu(p["conv_b"] + sum(padded[k:k + T] * p["conv_w"][k]
                                      for k in range(K)))
    dbc = _einsum("sd,dr->sr", x, p["x_proj"], precision)
    delta = jax.nn.softplus(
        _einsum("sr,rd->sd", dbc[:, :R], p["dt_proj"], precision)
        + p["dt_bias"])
    A = -jnp.exp(p["A_log"])                                  # [Din, N]
    B, C = dbc[:, R:R + N], dbc[:, R + N:]

    def step(s, at):
        x_t, d_t, b_t, c_t = at
        s = jnp.exp(d_t[:, None] * A) * s + (d_t * x_t)[:, None] * b_t[None]
        s = _round(s, precision)
        return s, s @ c_t + p["D"] * x_t
    _, y = jax.lax.scan(step, jnp.zeros((Din, N)), (x, delta, B, C))
    return _einsum("sd,de->se", y * jax.nn.silu(z), p["out_proj"],
                   precision), y


def diff_attention(p, u, kv, d, lam0, precision, window=None):
    """u [T, hidden] -> (out [T, hidden], (k, v)). `kv` None: the layer's
    own keys and values, from `Wqkv`; else the (k, v) it is handed, and
    `Wq` alone."""
    T = u.shape[0]
    H, KV, D = d.heads, d.kv_heads, d.head_dim
    if kv is None:
        qkv = _einsum("se,ec->sc", u, p["Wqkv"], precision) + p["bqkv"]
        q = qkv[:, :H * D]
        kv = (qkv[:, H * D:(H + KV) * D].reshape(T, KV // 2, 2, D),
              qkv[:, (H + KV) * D:].reshape(T, KV // 2, 2 * D))
    else:
        q = _einsum("se,ec->sc", u, p["Wq"], precision) + p["bq"]
    k, v = kv
    # query head 4j + 2r + c: pair i = 2j + r of key/value pair j, c its
    # first or second query
    q = q.reshape(T, KV // 2, H // KV, 2, D)
    kpos = jnp.arange(T)

    def one(q, qpos):
        s = _einsum("qjrcd,kjcd->jrcqk", q, k, precision) / (D ** 0.5)
        seen = kpos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= kpos[None, :] > qpos[:, None] - window
        prob = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return _einsum("jrcqk,kjd->qjrcd", prob, v, precision)

    if T <= BLOCK:
        a = one(q, kpos)
    else:
        a = jax.lax.map(lambda qa: one(*qa),
                        (q.reshape((T // BLOCK, BLOCK) + q.shape[1:]),
                         kpos.reshape(T // BLOCK, BLOCK)))
        a = a.reshape((T,) + a.shape[2:])
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    diff = a[:, :, :, 0] - lam * a[:, :, :, 1]              # [T, 10, 2, 2D]
    o = diff * jax.lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True)
                             + d.eps) * p["subln"] * (1.0 - lam0)
    return _einsum("sc,ce->se", o.reshape(T, H * D), p["out_proj"],
                   precision) + p["out_bias"], kv


def layer(p, x, carry, d, kind, index, precision="f32"):
    """Layer `index` (of `kind`) over one sequence x [T, hidden]; `carry`
    is (m, (k, v)): layer 16's memory and layer 17's keys and values,
    zeros until those layers have run."""
    p = _f32(p)
    m, kv = carry
    u = layer_norm(x, p["input_layernorm"], d.eps)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(index, jnp.float32))
    if kind == "mamba":
        out, y = mamba(p["mamba"], u, d, precision)
        m = jnp.where(index == d.half, y, m)
    elif kind == "gmu":
        out = _einsum(
            "sd,de->se",
            jax.nn.silu(_einsum("se,ed->sd", u, p["gmu"]["in_proj"],
                                precision)) * m,
            p["gmu"]["out_proj"], precision)
    elif kind == "cross":
        out, _ = diff_attention(p["attn"], u, kv, d, lam0, precision)
    else:
        out, own = diff_attention(
            p["attn"], u, None, d, lam0, precision,
            window=d.window if kind == "swa" else None)
        if kind == "full":
            kv = own
    h = x + out
    return h + mlp(p["mlp"], layer_norm(h, p["post_attention_layernorm"],
                                        d.eps), precision), (m, kv)


def empty_carry(T, d):
    D = d.head_dim
    return (jnp.zeros((T, d.d_inner)),
            (jnp.zeros((T, d.kv_heads // 2, 2, D)),
             jnp.zeros((T, d.kv_heads // 2, 2 * D))))


def head(top, h, d, precision="f32"):
    """[n, hidden] hidden states -> [n, vocab] logits."""
    top = _f32(top)
    return _einsum("se,ve->sv", layer_norm(h, top["final_layernorm"], d.eps),
                   top["wte"]["embedding"], precision)


def forward(params, tokens, d, precision="f32"):
    """Logits [n, S, vocab] of [n, S] token ids from the program's tree
    (`weights_phi4flash.make_params`): the whole model at once, for the
    tests' sizes."""
    def one(toks):
        h = params["wte"]["embedding"].astype(jnp.float32)[toks]
        carry = empty_carry(toks.shape[0], d)
        for l in range(d.layers):
            h, carry = layer(params[f"layer_{l}"], h, carry, d, d.kind(l),
                             l, precision)
        return head(params, h, d, precision)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, tokens)


# -- serving: the gap of each served token, layer by layer ---------------

@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _embed_from_seed(key, tokens, d, dtype):
    return weights.top_params(key, d, dtype)["wte"]["embedding"].astype(
        jnp.float32)[tokens]


@functools.partial(jax.jit,
                   static_argnames=("d", "dtype", "kind", "precision"),
                   donate_argnums=(2, 3))
def _layer_from_seed(key, index, h, carry, d, dtype, kind, precision):
    p = weights.layer_params(key, d, kind, index, dtype)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: layer(p, a[0], a[1], d, kind, index, precision),
            (h, carry))


@functools.partial(jax.jit, static_argnames=("d", "dtype", "precision",
                                             "vocab_blocks"))
def _head_from_seed(key, h, d, dtype, precision, vocab_blocks):
    """h [n, hidden] -> [n, vocab], a block of the vocabulary at a time."""
    top = weights.top_params(key, d, dtype)
    table = top["wte"]["embedding"]
    rows = d.vocab // vocab_blocks
    with jax.default_matmul_precision("highest"):
        parts = jax.lax.map(
            lambda t: head({**top, "wte": {"embedding": t}}, h, d,
                           precision),
            table.reshape(vocab_blocks, rows, d.hidden))
    return jnp.moveaxis(parts, 0, 1).reshape(h.shape[0], d.vocab)


def _vocab_blocks(d) -> int:
    """Blocks of about 16 thousand rows that divide the vocabulary."""
    return max(b for b in range(1, max(1, d.vocab // 16384) + 1)
               if d.vocab % b == 0)


def hidden_from_seed(key, tokens, d, dtype, precision="f32"):
    """The hidden states [n, S, hidden] before the final norm of [n, S]
    sequences (S at most `BLOCK`, or a multiple of it), the weights
    remade from the seed one layer at a time in the type they are served
    in."""
    n, S = tokens.shape
    h = _embed_from_seed(key, tokens, d, dtype)
    carry = jax.vmap(lambda _: empty_carry(S, d))(jnp.arange(n))
    for index in range(d.layers):
        h, carry = _layer_from_seed(key, jnp.int32(index), h, carry, d,
                                    dtype, d.kind(index), precision)
    return h


def logits_at(key, tokens, at, d, dtype, precision="f32"):
    """Logits [n, m, vocab] of [n, S] sequences at the positions `at`
    [n, m] alone."""
    h = hidden_from_seed(key, tokens, d, dtype, precision)
    picked = jnp.take_along_axis(h, at[..., None], axis=1)     # [n, m, E]
    blocks = _vocab_blocks(d)
    return jnp.stack([_head_from_seed(key, x, d, dtype, precision, blocks)
                      for x in picked])


@jax.jit
def _gaps(ref_logits, nxt, other_logits):
    """As `gpt2._gaps`, of logits [n, m, vocab] at picked positions and
    the tokens `nxt` [n, m] that followed them (`served`), and of the
    token that `other_logits` puts first (`other`): how far the
    reference's logit of it lies under the reference's best, the
    reference's log-probability of it, and the log-probability
    `other_logits` gives its own first token."""
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


def served_token_gaps(key, tokens, at, d, dtype, control=None):
    """`_gaps` of [n, S] sequences at positions `at` [n, m], each value
    [n, m]; position p speaks of the token at p + 1. Without `control`
    the `other_*` entries are the reference's own first choice."""
    ref = logits_at(key, tokens, at, d, dtype)
    other = ref if control is None else logits_at(key, tokens, at, d, dtype,
                                                  control)
    nxt = jnp.take_along_axis(tokens, jnp.minimum(at + 1,
                                                  tokens.shape[1] - 1), 1)
    return _gaps(ref, nxt, other)
