"""Plain GPT-2: forward, loss, gradients and AdamW in `jax.numpy`.

Float32 with `precision="highest"` on every product, no kernels, no
cache, no batching tricks: the published equations (Radford et al. 2019;
pre-LN blocks, learned positions, tanh GELU, tied head). It imports
nothing of the program under test and is handed weights that
`perfbench.weights` made from the seed.

Departures from the published model, all the program's: the table is
padded to `Dims.vocab` rows (the pad rows are ordinary weights that no
token id selects, and the softmax runs over all of them, as the
program's does); dropout is not applied.

`precision` selects what the products are computed in:
  "f32"  — the reference proper;
  "bf16" — operands rounded to bfloat16 (the control of a float32 test);
  "fp8"  — operands scaled per tensor and rounded to float8_e4m3fn with a
           straight-through gradient: the control of a bfloat16 cell, the
           step below bfloat16 that a later PR would be tempted by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def _round(x, precision):
    if precision == "f32":
        return x
    if precision == "bf16":
        y = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
        y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"precision {precision!r}")
    return x + jax.lax.stop_gradient(y - x)


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(p, h, precision="f32"):
    """One pre-LN block over h [B, S, E]."""
    p = _f32(p)
    a = p["attn"]
    y = layer_norm(h, p["ln_1"])
    q = _einsum("bse,ehd->bshd", y, a["query"]["kernel"], precision) \
        + a["query"]["bias"]
    k = _einsum("bse,ehd->bshd", y, a["key"]["kernel"], precision) \
        + a["key"]["bias"]
    v = _einsum("bse,ehd->bshd", y, a["value"]["kernel"], precision) \
        + a["value"]["bias"]
    s = _einsum("bqhd,bkhd->bhqk", q, k, precision) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    n = h.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None, None], s, -1e30)
    o = _einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision)
    h = h + _einsum("bqhd,hde->bqe", o, a["out"]["kernel"], precision) \
        + a["out"]["bias"]
    y = layer_norm(h, p["ln_2"])
    m = p["mlp"]
    y = gelu_tanh(_einsum("bse,em->bsm", y, m["fc_in"]["kernel"], precision)
                  + m["fc_in"]["bias"])
    return h + _einsum("bsm,me->bse", y, m["fc_out"]["kernel"], precision) \
        + m["fc_out"]["bias"]


def embed(p, tokens):
    p = _f32(p)
    return p["wte"]["embedding"][tokens] \
        + p["wpe"]["embedding"][:tokens.shape[1]][None]


def head(ln_f, table, h, precision="f32"):
    y = layer_norm(h, _f32(ln_f))
    return _einsum("bse,ve->bsv", y, table.astype(jnp.float32), precision)


def forward(params, tokens, precision="f32", remat=False):
    """Logits [B, S, V] of the whole model from the stacked tree of
    `weights.make_stacked` (one loop over the layers)."""
    def step(h, p):
        return block(p, h, precision), None
    if remat:
        step = jax.checkpoint(step)
    h, _ = jax.lax.scan(step, embed(params, tokens), params["blocks"])
    return head(params["ln_f"], params["wte"]["embedding"], h, precision)


def xent_sum(params, tokens, targets, precision="f32"):
    """Summed next-token cross-entropy of a block of rows."""
    logits = forward(params, tokens, precision, remat=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return (logz - picked).sum()


# -- training: loss, gradient and AdamW over row blocks -----------------

@functools.partial(jax.jit, static_argnames=("precision",))
def _block_grad(params, tokens, targets, precision):
    return jax.value_and_grad(xent_sum)(params, tokens, targets, precision)


def loss_and_grad(params, tokens, targets, precision="f32", rows=2):
    """Mean loss and its gradient over a [B, S] batch, `rows` rows at a
    time so the float32 activations of the whole batch are never held."""
    total, grads = None, None
    for lo in range(0, tokens.shape[0], rows):
        l, g = _block_grad(params, tokens[lo:lo + rows],
                           targets[lo:lo + rows], precision)
        total, grads = (l, g) if total is None else _add((total, grads),
                                                         (l, g))
    return _scale((total, grads), 1.0 / (tokens.shape[0] * tokens.shape[1]))


# whole-tree helpers under jit: one small program each, not one a leaf
_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_scale = jax.jit(lambda a, c: jax.tree.map(lambda x: x * c, a),
                 donate_argnums=0)
_fresh = jax.jit(lambda p: (jax.tree.map(jnp.copy, p),
                            jax.tree.map(jnp.zeros_like, p),
                            jax.tree.map(jnp.zeros_like, p)))


def leaf_names(tree):
    """[(name, leaf)] of a stacked tree: "blocks/attn/key/bias", ..."""
    return [("/".join(str(k.key) for k in path), x)
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _lead(name, x):
    """A stacked leaf as [layers, n]; any other as [1, n]."""
    x = x.astype(jnp.float32)
    return x.reshape(x.shape[0], -1) if name.startswith("blocks/") \
        else x.reshape(1, -1)


@jax.jit
def leaf_norms(tree):
    """{leaf name: [layers or 1] norms} of a stacked tree."""
    return {n: jnp.sqrt(jnp.sum(_lead(n, x) ** 2, -1))
            for n, x in leaf_names(tree)}


@jax.jit
def _change_norms(after, before):
    return leaf_norms(jax.tree.map(jnp.subtract, after, before))


def salts(key, name, lead, k):
    """[lead, k] seeds of the sign vectors of one named leaf."""
    import zlib
    return jax.random.bits(jax.random.fold_in(
        key, zlib.crc32(name.encode()) & 0x7FFFFFFF), (lead, k), jnp.uint32)


def project(flat, salt):
    """Inner products of a flat leaf with the sign vectors of `salt` [k]:
    the signs come from an integer hash of the index, so there is no
    random-number program to compile and nothing to hold, and rounding
    noise knows nothing of the pattern."""
    h = jnp.arange(flat.size, dtype=jnp.uint32)[None] \
        * jnp.uint32(2654435761) + salt[:, None]
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    h = h ^ (h >> 16)
    signs = ((h >> 7) & 1).astype(jnp.float32) * 2.0 - 1.0
    return jnp.sum(signs * flat.astype(jnp.float32)[None], -1)


@functools.partial(jax.jit, static_argnames=("k",))
def leaf_projections(tree, key, k=8):
    """{leaf name: [layers or 1, k]}: each leaf's inner products with `k`
    seeded sign vectors. Rounding noise that leaves a norm where it was
    moves these in the first order: the root mean square of the gaps
    between two gradients' projections estimates the norm of their
    difference without holding both."""
    out = {}
    for n, x in leaf_names(tree):
        x = _lead(n, x)
        out[n] = jax.vmap(project)(x, salts(key, n, x.shape[0], k))
    return out


@jax.jit
def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * scale, grads)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def adamw_update(params, grads, m, v, count, lr, b1, b2, eps, wd):
    """AdamW as Loshchilov & Hutter state it, bias-corrected; `count` is
    the number of updates already made."""
    t = count + 1
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def new(p, mm, vv):
        mh = mm / (1 - b1 ** t)
        vh = vv / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    return jax.tree.map(new, params, m, v), m, v


def warmup_lr(count, peak, warmup_steps):
    """Linear warm-up from 0, constant after (the trainer's "linear")."""
    return peak * min(1.0, count / max(1, warmup_steps))


def train_steps(params, batches, hp, key, precision="f32", rows=2):
    """Follow `batches` ([(tokens, targets)]) from `params` with AdamW.
    Returns per-step losses, the leaf norms and seeded projections of the
    first gradient as the optimizer gets it (after clipping) and the leaf
    norms of the parameters' change over all the steps."""
    start = params
    params, m, v = _fresh(params)
    losses, first, proj = [], None, None
    for count, (tokens, targets) in enumerate(batches):
        loss, grads = loss_and_grad(params, tokens, targets, precision,
                                    rows)
        grads = clip_by_global_norm(grads, hp["grad_clip"])
        if first is None:
            first, proj = leaf_norms(grads), leaf_projections(grads, key)
        lr = warmup_lr(count, hp["learning_rate"], hp["warmup_steps"])
        params, m, v = adamw_update(params, grads, m, v, count, lr,
                                    hp["b1"], hp["b2"], 1e-8,
                                    hp["weight_decay"])
        losses.append(loss)
    delta = _change_norms(params, start)
    return [float(x) for x in losses], first, proj, delta


# -- serving: the gap of each served token, layer by layer ---------------

@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _embed_from_seed(key, tokens, dims, dtype):
    return embed(weights.embed_params(key, dims, dtype), tokens)


@functools.partial(jax.jit, static_argnames=("dims", "dtype", "precision"))
def _block_from_seed(key, layer, h, dims, dtype, precision):
    return block(weights.layer_params(key, dims, layer, dtype), h,
                 precision)


@functools.partial(jax.jit, static_argnames=("dims", "dtype", "precision"))
def _head_from_seed(key, h, dims, dtype, precision):
    table = weights.embed_params(key, dims, dtype)["wte"]["embedding"]
    return head(weights.final_norm_params(key, dims, dtype), table, h,
                precision)


def logits_from_seed(key, tokens, dims, dtype, precision="f32"):
    """Logits of [n, S] sequences, the weights remade from the seed one
    layer at a time in the type they are served in."""
    h = _embed_from_seed(key, tokens, dims, dtype)
    for layer in range(dims.layers):
        h = _block_from_seed(key, jnp.int32(layer), h, dims, dtype,
                             precision)
    return _head_from_seed(key, h, dims, dtype, precision)


@jax.jit
def _gaps(ref_logits, tokens, other_logits):
    """At each position p, of the token at p+1 (`served`) and of the token
    that `other_logits` puts first (`other`): how far the reference's
    logit of it lies under the reference's best, and the reference's
    log-probability of it; and the log-probability `other_logits` gives
    its own first token."""
    best = ref_logits.max(-1)
    ref_logp = jax.nn.log_softmax(ref_logits, axis=-1)
    pick = lambda a, i: jnp.take_along_axis(a, i[..., None], -1)[..., 0]  # noqa: E731
    nxt = jnp.roll(tokens, -1, axis=1)
    first = jnp.argmax(other_logits, -1)
    return {"served_gap": best - pick(ref_logits, nxt),
            "served_ref_logp": pick(ref_logp, nxt),
            "other_gap": best - pick(ref_logits, first),
            "other_ref_logp": pick(ref_logp, first),
            "other_own_logp": jax.nn.log_softmax(other_logits, -1).max(-1)}


def served_token_gaps(key, tokens, dims, dtype, control=None):
    """`_gaps` of [n, S] sequences, each value [n, S]; position p speaks
    of the token at p + 1. Without `control` the `other_*` entries are the
    reference's own first choice."""
    ref = logits_from_seed(key, tokens, dims, dtype)
    other = ref if control is None else logits_from_seed(
        key, tokens, dims, dtype, control)
    return _gaps(ref, tokens, other)
