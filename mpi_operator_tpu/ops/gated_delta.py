"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) with the state
carried in and handed back, as `ops/ssm.py` has the two state-space
recurrences: a SCALAR decay a head and position, heads of `Dk` key
channels over `Dv` value channels, and a write that READS the state first,

    S_t = exp(g_t) S_{t-1}                                     [Dk x Dv]
    r_t = S_t^T k_t                  what the decayed state holds along k_t
    S_t <- S_t + k_t (beta_t (v_t - r_t))^T                    the delta rule
    o_t = S_t^T q_t                                            [Dv]

so the state moves toward holding v_t along k_t, by beta_t of the way.
Neither `selective_scan` nor the SSD pair of `ops/ssm.py` can compute it:
their write, `B (dt x)^T`, does not depend on S, and their kernel passes a
tile once with ONE reduction over it; here the write needs a read-out of
the decayed tile before it and the output a second one after it.

The state is held `[rows, Hv, Dk, Dv]` float32, the value channels minor: a
head's v, its read-out r, its write and its output are rows of 128 lanes,
and k and q, which the two value heads of a key head share, are what has to
be turned into columns (`Hv` value heads on `Hk = Hv / n` key heads: key
head j serves value heads n j .. n j + n - 1). A state is 32 x 128 x 128
float32 = 2 MB a layer and row (Qwen3-Next), so a scan of positions would
pass it through HBM once a position:

  `gated_delta_chunk_scan`   a chunk of 64 positions in the WY / UT-transform
      form: inside a chunk the writes u_t = beta_t (v_t - r_t) solve the
      unit-lower-triangular system (I + A) U = diag(beta) (V - decay K S_0),
      A[t, s] = beta_t exp(G_t - G_s) k_t . k_s below the diagonal (G the
      running sum of g), then matmuls against the carried state. Plain
      `jax.numpy`, float32, products at the highest precision.
  `gated_delta_state_update` a decode step, ONE Pallas kernel on the TPU:
      grid (rows, blocks of value heads), the state aliased in and out,
      each `[Dk, Dv]` tile read once and written once with the two
      reductions over it while it is in VMEM.

A position whose `beta` and `g` are 0 leaves the state exactly as it was
(exp(0) = 1, and + k 0^T), which is how a caller holds the state over pad
tokens and over rows that are no member of a call, as `ops/ssm.py` promises
for `dt = 0`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import out_struct as _out_struct
from .attention import note_traced

#: positions a chunk of `gated_delta_chunk_scan` takes
GDN_CHUNK = 64
#: value heads a grid step of the state-update kernel takes: 16 tiles of
#: [128, 128] float32 are 1 MB in and 1 MB out, twice for the pipeline's
#: two buffers: what `ssm._SSD_HEAD_BLOCK` tiles of [256, 128] take (all
#: 32 a step measured the same: 6.59 us a row against 6.57, PERF.md PR 46)
_GDN_HEAD_BLOCK = 16
#: lanes of a float32 tile
_LANES = 128

_ein = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def _head_block(value_heads: int) -> int:
    return min(_GDN_HEAD_BLOCK, value_heads)


def gated_delta_scan(q, k, v, g, beta, state):
    """The recurrence position by position, as it is written above: q, k
    [G, T, Hk, Dk]; v [G, T, Hv, Dv]; g, beta [G, T, Hv]; state
    [G, Hv, Dk, Dv] -> (o [G, T, Hv, Dv], state after position T-1), all
    float32. What the chunk form and the kernel are tested against; a
    decode step off the TPU is its one position."""
    n = v.shape[2] // k.shape[2]

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        q_t, k_t = (jnp.repeat(a, n, axis=1) for a in (q_t, k_t))
        s = jnp.exp(g_t)[..., None, None] * s
        r = jnp.sum(s * k_t[..., None], axis=2)               # [G, Hv, Dv]
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - r))[:, :, None]
        return s, jnp.sum(s * q_t[..., None], axis=2)

    if q.shape[1] == 1:
        state, o = step(state, (q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0]))
        return o[:, None], state
    state, o = jax.lax.scan(
        step, state, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), state


def _gdn_chunk(state, q, k, v, g, beta):
    """One chunk of L positions in the WY form. state [G, Hk, n, Dk, Dv];
    q, k [G, L, Hk, Dk]; v [G, L, Hk, n, Dv]; g, beta [G, L, Hk, n] ->
    (o [G, L, Hk, n, Dv], the state after position L-1)."""
    L = q.shape[1]
    cs = jnp.cumsum(g, axis=1)                         # G_t, <= 0
    # position t sees s <= t through exp(g_{s+1} + .. + g_t), each sum made
    # of its own terms, as `ssm._ssd_chunk` makes them
    below = jnp.tril(jnp.ones((L, L), bool), -1)[None, :, :, None, None]
    seg = jnp.cumsum(jnp.where(below, g[:, :, None], 0.0), axis=1)
    seen = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None, None]
    decay = jnp.exp(jnp.where(seen, seg, -jnp.inf))    # [G, t, s, Hk, n]
    into = jnp.exp(cs)[..., None]                      # [G, L, Hk, n, 1]
    kk = _ein("gtjd,gsjd->gtsj", k, k)[..., None]      # [G, t, s, Hk, 1]
    # (I + A) U = diag(beta) (V - diag(exp G) K S_0), A strictly lower
    a = jnp.where(below, beta[:, :, None] * decay * kk, 0.0)
    rhs = beta[..., None] * (v - into * _ein("gtjd,gjndv->gtjnv", k, state))
    unit = a.transpose(0, 3, 4, 1, 2) + jnp.eye(L, dtype=a.dtype)
    u = jax.scipy.linalg.solve_triangular(
        unit, rhs.transpose(0, 2, 3, 1, 4), lower=True, unit_diagonal=True)
    u = u.transpose(0, 3, 1, 2, 4)                     # [G, L, Hk, n, Dv]
    # o_t = exp(G_t) S_0^T q_t + sum_{s <= t} exp(G_t - G_s) (k_s . q_t) u_s
    qk = _ein("gtjd,gsjd->gtsj", q, k)[..., None]
    o = into * _ein("gtjd,gjndv->gtjnv", q, state) \
        + _ein("gtsjn,gsjnv->gtjnv", decay * qk, u)
    # the state the chunk leaves: every g and beta 0 gives 1 * state + 0
    state = jnp.exp(cs[:, -1])[..., None, None] * state \
        + _ein("gsjd,gsjnv->gjndv", k, decay[:, -1][..., None] * u)
    return o, state


def gated_delta_chunk_scan(q, k, v, g, beta, state, chunk: int = GDN_CHUNK):
    """The gated delta rule over T positions, `chunk` at a time in the WY
    form (arXiv:2412.06464, section 3.3): q, k [G, T, Hk, Dk] (k
    L2-normalised by the caller, q scaled); v [G, T, Hv, Dv], Hv a
    multiple of Hk; g [G, T, Hv] the log decay (<= 0; 0 at a junk
    position); beta [G, T, Hv] (0 at a junk position); state
    [G, Hv, Dk, Dv] -> (o [G, T, Hv, Dv], the state after position T-1),
    all float32. A chunk costs the state one pass, not one a position. T
    past a chunk and no multiple of it is padded with junk positions."""
    G, T, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    n = Hv // Hk
    L = min(T, chunk)
    pad = -T % L
    xs = (q, k, v.reshape(G, T, Hk, n, Dv), g.reshape(G, T, Hk, n),
          beta.reshape(G, T, Hk, n))
    if pad:
        xs = tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                   for a in xs)
    state = state.reshape(G, Hk, n, Dk, Dv)
    m = (T + pad) // L
    if m == 1:
        o, state = _gdn_chunk(state, *xs)
    else:
        def step(s, at):
            o, s = _gdn_chunk(s, *at)
            return s, o
        state, o = jax.lax.scan(step, state, tuple(
            jnp.moveaxis(a.reshape((G, m, L) + a.shape[2:]), 1, 0)
            for a in xs))
        o = jnp.moveaxis(o, 0, 1).reshape((G, m * L) + o.shape[3:])[:, :T]
    return o.reshape(G, T, Hv, Dv), state.reshape(G, Hv, Dk, Dv)


def _gdn_update_kernel(fresh_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref,
                       o_ref, n_ref, *, hb, n):
    """One position for one (row, block of `hb` value heads): each
    [Dk, Dv] tile comes in once and goes out once. The block's hb / n key
    heads' k and q arrive as rows [1, Dk]; stacked, padded to a square and
    transposed ONCE a block they are columns, and a head's own is one of
    them spread along the lanes. A head's decay, beta, v, read-out, write
    and output are rows of Dv lanes. A row that starts a sequence
    (`fresh`) takes zeros for its tiles."""
    Dk, Dv = s_ref.shape[2:]
    kh = hb // n
    rows = jnp.concatenate(
        [k_ref[0], q_ref[0],
         jnp.zeros((Dk - 2 * kh, Dk), jnp.float32)], axis=0)  # [Dk, Dk]
    cols = rows.T                       # column j: k of key head j; kh + j: q
    decay = jnp.exp(g_ref[0])                                 # [hb, Dv]
    beta, v = b_ref[0], v_ref[0]

    def update(tile):
        for h in range(hb):
            j = h // n
            kcol = jnp.broadcast_to(cols[:, j:j + 1], (Dk, Dv))
            qcol = jnp.broadcast_to(cols[:, kh + j:kh + j + 1], (Dk, Dv))
            s = tile(h) * decay[h:h + 1]
            r = jnp.sum(s * kcol, axis=0, keepdims=True)      # [1, Dv]
            s = s + kcol * (beta[h:h + 1] * (v[h:h + 1] - r))
            n_ref[0, h] = s
            o_ref[0, h:h + 1] = jnp.sum(s * qcol, axis=0, keepdims=True)

    fresh = fresh_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_not(fresh))
    def _carried():
        update(lambda h: s_ref[0, h])

    @pl.when(fresh)
    def _from_zeros():
        update(lambda h: jnp.zeros((Dk, Dv), jnp.float32))


# jitted and inlined, as `ssm._ssd_update_call`: the layers of a model call
# it with the same shapes, so its body is traced once a program, and the
# call keeps its caller's named scope in its instruction's name
# (`gdn.update.3`), which the trace readers match.
@functools.partial(jax.jit, static_argnums=(7,), inline=True)
def _gdn_update_call(q, k, v, g, beta, state, fresh, interpret):
    G, Hv, Dk, Dv = state.shape
    Hk = q.shape[1]
    n = Hv // Hk
    hb = _head_block(Hv)
    kh = hb // n                        # key heads a block
    whole_blocks = Hv % hb == 0 and hb % n == 0
    key_tiles = kh % 8 == 0 or hb == Hv  # a block of k, q rows Mosaic takes
    if not (whole_blocks and key_tiles and 2 * kh <= Dk and Dv == _LANES):
        raise ValueError(
            f"{Hv} value heads on {Hk} key heads of [{Dk}, {Dv}] are not "
            f"whole blocks of {hb} value heads whose key heads fill "
            f"sublane tiles, value channels on the {_LANES} lanes")
    lanes = lambda a: jnp.broadcast_to(a[..., None], a.shape + (Dv,))  # noqa: E731
    key = pl.BlockSpec((1, kh, Dk), lambda r, j, *_: (r, j, 0))
    row = pl.BlockSpec((1, hb, Dv), lambda r, j, *_: (r, j, 0))
    tile = pl.BlockSpec((1, hb, Dk, Dv), lambda r, j, *_: (r, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_gdn_update_kernel, hb=hb, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(G, Hv // hb),
            in_specs=[key, key, row, row, row, tile],
            out_specs=[row, tile]),
        out_shape=[_out_struct((G, Hv, Dv), jnp.float32, v, state),
                   _out_struct(state.shape, jnp.float32, v, state)],
        # operand 6 (the prefetched flags are operand 0) is the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(fresh.astype(jnp.int32), q, k, v, lanes(g), lanes(beta), state)
    return o, state


def gdn_update_form(state) -> str:
    """The name the state-update kernel is traced under for a state
    [G, Hv, Dk, Dv]: `pallas_gdn_update[Dv-minor,heads=hb]`, hb the value
    heads a grid step takes."""
    return f"pallas_gdn_update[Dv-minor,heads={_head_block(state.shape[1])}]"


def gated_delta_state_update(q, k, v, g, beta, state, fresh=None,
                             interpret: Optional[bool] = None):
    """`gated_delta_chunk_scan` for a chunk of one, a decode step: q, k
    [G, Hk, Dk]; v [G, Hv, Dv]; g, beta [G, Hv]; state [G, Hv, Dk, Dv];
    `fresh` [G] bool, rows that start from zeros whatever their state
    holds -> (o [G, Hv, Dv], state), float32 throughout. On the TPU one
    Pallas kernel (`_gdn_update_kernel`) whose state operand is its state
    result, so a donated state is updated where it lies; plain `jax.numpy`
    elsewhere (`interpret=True`: the kernel, interpreted, for the tests).
    Which ran is noted for `attention.record_traced` under "gdn"
    (`gdn_update_form`, or "dense")."""
    if fresh is None:
        fresh = jnp.zeros(v.shape[:1], bool)
    if interpret is None and jax.default_backend() != "tpu":
        note_traced("gdn", "dense")
        o, state = gated_delta_scan(
            q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
            jnp.where(fresh[:, None, None, None], 0.0, state))
        return o[:, 0], state
    note_traced("gdn", gdn_update_form(state))
    return _gdn_update_call(q, k, v, g, beta, state, fresh, bool(interpret))


__all__ = ["gated_delta_scan", "gated_delta_chunk_scan",
           "gated_delta_state_update", "gdn_update_form", "GDN_CHUNK"]
