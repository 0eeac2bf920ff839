"""The recurrences of state-space layers with the state carried in and
handed back: the form a served model needs, where a prompt arrives in
chunks and a decode step is a chunk of one.

Mamba-1 (arXiv:2312.00752), `selective_scan`: a decay a channel and state,

    s_t = exp(delta_t A) * s_{t-1} + (delta_t x_t) B_t^T      [Din x N]
    y_t = s_t C_t + D * x_t                                    [Din]

the state held `[N, Din]`, channels minor: a float32 array whose minor
dimension is the 16 states would fill an eighth of each 128-lane tile,
on the chip eight times its bytes. Plain `jax.numpy`, float32: a
`lax.scan` over the chunk's positions (one position is the step itself,
no loop). There is no kernel; XLA fuses a position's few elementwise
passes over the state.

Mamba-2's SSD (arXiv:2405.21060), `ssd_chunk_scan` and
`ssd_state_update`: a SCALAR decay a head, heads of P channels over N
states, B and C shared by the heads of a group,

    S_t = exp(dt_t A_h) * S_{t-1} + B_t (dt_t x_t)^T          [N x P]
    y_t = S_t^T C_t + D_h * x_t                                [P]

the state held `[H, N, P]`, again the states major and the channels
minor: B and C, which every head of a group shares, are then what has to
be turned into columns, once a block of heads, and a head's own x, dt
and y stay rows of 128 lanes. A state is 32 x 256 x 128 float32 = 4 MB
a layer and row (Falcon-H1-34B), so a scan of positions would pass it
through HBM once a position: a chunk goes through the chunked matmul
form (`ssd_chunk_scan`: the decay matrix inside a chunk of 128, the
chunk's state, the carried state; float32, products at the highest
precision), and a decode step through ONE Pallas kernel on the TPU
(`ssd_state_update`: grid (rows, head blocks), the state aliased in and
out, each `[256, 128]` tile read once and written once).

A head of FEWER channels than the 128 lanes of a tile (Granite-4.0-H's
128 heads of 64 over 128 states, one group) would, held `[N, 64]`, fill
half of each lane tile: twice its bytes on the chip and every vector half
empty, the very thing the layout avoids for Mamba-1. There the state is
held `[H / n, N, n P]`, the `n = 128 / P` neighbouring heads of a group
side by side on a tile's lanes (`ssd_heads_per_tile`, from the shapes
alone): x, dt x, the decay and y of the n heads are still one row of 128
lanes, B and C the same columns, and the kernel below is the same kernel
over H / n tiles a row. The published layout (`[H, P, N]`, the states
minor, the C sum along the lanes) was measured beside it and lost
(PERF.md, PR 42). `ssd_state_shape` is the shape a caller holds;
`ssd_chunk_scan` and `ssd_state_update` read the layout off the state
they are handed.

In both, a position whose step (`delta`, `dt`) is 0 leaves the state
exactly as it was (exp(0) = 1, and + 0), which is how a caller holds the
state over pad tokens and over rows that are no member of a call.

A THIRD recurrence lives in the file beside this one, `ops/gated_delta.py`
(Gated DeltaNet, arXiv:2412.06464; Qwen3-Next), under the same conventions
(the state carried in and handed back, float32, the minor dimension the
one that fills the lanes, a step of zeros holds the state exactly):

    S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - exp(g_t) S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

Neither form here can serve it: the write above, `B (dt x)^T`, does not
depend on S, so a chunk is a sum of decayed outer products and the SSD
kernel passes a tile once with ONE reduction over it (the C sum); the
delta rule's write reads the decayed tile along k_t before it writes, so
a chunk needs a triangular solve for the writes, and a step needs the
read-out before the write and the output after it: two reductions while
the tile is in VMEM.
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

#: positions a chunk of `ssd_chunk_scan` takes (`mamba_chunk_size`)
SSD_CHUNK = 128
#: heads a grid step of the state-update kernel takes: 8 tiles of
#: [256, 128] float32 are 1 MB in and 1 MB out, twice for the pipeline's
#: two buffers, a quarter of what Mosaic lets a kernel take of VMEM
_SSD_HEAD_BLOCK = 8
#: lanes of a float32 tile
_LANES = 128


def ssd_heads_per_tile(heads: int, head_dim: int, groups: int) -> int:
    """Heads of one group that share a tile of the held state: 1 where a
    head's `head_dim` channels fill the 128 lanes (or do not divide
    them, or a group's heads the count), else 128 // head_dim."""
    n = _LANES // head_dim if head_dim < _LANES else 1
    if n < 2 or n * head_dim != _LANES or (heads // groups) % n:
        return 1
    return n


def ssd_state_shape(rows: int, heads: int, head_dim: int, groups: int,
                    states: int):
    """The shape the recurrent state of `rows` rows is held in:
    `[rows, H / n, N, n P]`, n = `ssd_heads_per_tile` (`[rows, H, N, P]`
    where a head fills a tile)."""
    n = ssd_heads_per_tile(heads, head_dim, groups)
    return (rows, heads // n, states, n * head_dim)


def _heads_of(state, H: int):
    """[G, H / n, N, n P] as held -> [G, H, N, P]."""
    G, T, N, W = state.shape
    if T == H:
        return state
    n = H // T
    return state.reshape(G, T, N, n, W // n).swapaxes(2, 3).reshape(
        G, H, N, W // n)


def _tiles_of(state, T: int):
    """[G, H, N, P] -> [G, T, N, (H / T) P] as held."""
    G, H, N, P = state.shape
    if T == H:
        return state
    n = H // T
    return state.reshape(G, T, n, N, P).swapaxes(2, 3).reshape(
        G, T, N, n * P)


def selective_scan(x, delta, A, B, C, D, state):
    """x, delta [G, T, Din]; A [N, Din]; B, C [G, T, N]; D [Din];
    state [G, N, Din] -> (y [G, T, Din], state after position T-1), all
    float32."""
    def step(s, at):
        x_t, d_t, b_t, c_t = at
        s = jnp.exp(d_t[:, None, :] * A) * s \
            + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + D * x_t

    if x.shape[1] == 1:
        state, y = step(state, (x[:, 0], delta[:, 0], B[:, 0], C[:, 0]))
        return y[:, None], state
    state, y = jax.lax.scan(
        step, state, tuple(jnp.swapaxes(a, 0, 1) for a in (x, delta, B, C)))
    return jnp.swapaxes(y, 0, 1), state


def causal_conv(x, tail, w, b, count):
    """The depthwise causal convolution before the scan, over a chunk that
    continues a sequence: x [G, T, Din] the chunk's inputs, tail
    [G, K-1, Din] the K-1 inputs before it, w [K, Din], b [Din], count [G]
    how many of the chunk's positions are real (they come first).
    Returns (y [G, T, Din] float32, the K-1 inputs before position `count`:
    the next chunk's tail, the old one where `count` is 0)."""
    K = w.shape[0]
    T = x.shape[1]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = b.astype(jnp.float32) + sum(
        seq[:, k:k + T].astype(jnp.float32) * w[k].astype(jnp.float32)
        for k in range(K))
    at = count[:, None] + jnp.arange(K - 1)[None]            # [G, K-1]
    return y, jnp.take_along_axis(seq, at[..., None], axis=1).astype(
        tail.dtype)


def _ssd_chunk(state, x, dt, A, B, C):
    """One chunk of L positions in the matmul form. state [G, H, N, P];
    x [G, L, H, P]; dt [G, L, H]; B, C [G, L, K, N], head h of group
    h // (H // K) -> (y [G, L, H, P] without the D term, the state after
    position L-1)."""
    G, L, H, P = x.shape
    K, N = B.shape[2:]
    J = H // K
    ein = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    a = dt * A                                         # [G, L, H], <= 0
    cs = jnp.cumsum(a, axis=1)
    x5 = x.reshape(G, L, K, J, P)
    s5 = state.reshape(G, K, J, N, P)
    # inside the chunk: position t sees s <= t through exp(a_{s+1} + .. +
    # a_t), each sum made of its own terms (a difference of two running
    # sums loses what the sums have in common: 1e-4 of a decay at the end
    # of a chunk of large steps)
    below = jnp.tril(jnp.ones((L, L), bool), -1)[None, :, :, None]
    seg = jnp.cumsum(jnp.where(below, a[:, :, None], 0.0), axis=1)
    seen = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(seen, seg, -jnp.inf))    # [G, t, s, H]
    w = (decay * dt[:, None]).reshape(G, L, L, K, J) \
        * ein("gtkn,gskn->gtsk", C, B)[..., None]
    y = ein("gtskj,gskjp->gtkjp", w, x5)
    # what the carried state gives position t
    y += ein("gtkn,gkjnp->gtkjp", C, s5) * jnp.exp(cs).reshape(G, L, K, J, 1)
    # the state the chunk leaves: all dt 0 gives 1 * state + 0
    left = (decay[:, -1] * dt).reshape(G, L, K, J, 1)
    s5 = jnp.exp(cs[:, -1]).reshape(G, K, J, 1, 1) * s5 \
        + ein("gskn,gskjp->gkjnp", B, left * x5)
    return y.reshape(G, L, H, P), s5.reshape(G, H, N, P)


def ssd_chunk_scan(x, dt, A, B, C, D, state, chunk: int = SSD_CHUNK):
    """The SSD recurrence over T positions, `chunk` at a time in the
    matmul form (arXiv:2405.21060, section 6): x [G, T, H, P]; dt
    [G, T, H] (after its softplus; 0 at a junk position); A, D [H]; B, C
    [G, T, K, N], K groups of H // K heads; state as `ssd_state_shape`
    has it -> (y [G, T, H, P], the state after position T-1, held as it
    came), all float32. A chunk costs the state one pass, not one a
    position. T past a chunk and no multiple of it is padded with
    positions of dt 0."""
    G, T, H, P = x.shape
    tiles = state.shape[1]
    state = _heads_of(state, H)
    L = min(T, chunk)
    pad = -T % L
    xs = (x, dt, B, C)
    if pad:
        xs = tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                   for a in xs)
    n = (T + pad) // L
    if n == 1:
        y, state = _ssd_chunk(state, *xs[:2], A, *xs[2:])
    else:
        def step(s, at):
            y, s = _ssd_chunk(s, at[0], at[1], A, at[2], at[3])
            return s, y
        state, y = jax.lax.scan(step, state, tuple(
            jnp.moveaxis(a.reshape((G, n, L) + a.shape[2:]), 1, 0)
            for a in xs))
        y = jnp.moveaxis(y, 0, 1).reshape(G, n * L, H, P)[:, :T]
    return y + D[:, None] * x, _tiles_of(state, tiles)


def _ssd_update_kernel(fresh_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
                       s_ref, y_ref, o_ref, *, hb):
    """One position for one (row, block of `hb` tiles of one group): each
    [N, P] tile (a head, or the heads that share its lanes: P is then
    their channels side by side) comes in once and goes out once. B and C
    arrive as rows [1, N] and are turned into columns constant along the
    lanes (a row broadcast down the sublanes, transposed), once for the
    block; a head's decay, dt x and y are rows of P lanes. A row that
    starts a sequence (`fresh`) takes zeros for its tiles."""
    N, P = s_ref.shape[2:]
    x, dt = x_ref[0], dt_ref[0]                              # [hb, P]
    da = jnp.exp(dt * a_ref[...])
    dtx = dt * x
    skip = d_ref[...] * x
    bcol = jnp.broadcast_to(b_ref[0, 0], (P, N)).T           # [N, P]
    ccol = jnp.broadcast_to(c_ref[0, 0], (P, N)).T

    def update(tile):
        for h in range(hb):
            s = tile(h) * da[h:h + 1] + bcol * dtx[h:h + 1]
            o_ref[0, h] = s
            y_ref[0, h:h + 1] = jnp.sum(s * ccol, axis=0, keepdims=True) \
                + skip[h:h + 1]

    fresh = fresh_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_not(fresh))
    def _carried():
        update(lambda h: s_ref[0, h])

    @pl.when(fresh)
    def _from_zeros():
        update(lambda h: jnp.zeros((N, P), jnp.float32))


# jitted and inlined, as `attention._paged_walk`: the layers of a model
# call it with the same shapes, so its body is traced once a program, and
# the call keeps its caller's named scope in its instruction's name
# (`ssd.update.3`), which the trace readers match.
@functools.partial(jax.jit, static_argnums=(8,), inline=True)
def _ssd_update_call(x, dt, A, B, C, D, state, fresh, interpret):
    G, H, P = x.shape
    K, N = B.shape[1:]
    # tiles a row and lanes a tile: a head each where P fills the lanes,
    # else H // T neighbouring heads side by side (`ssd_state_shape`)
    T, W = state.shape[1], state.shape[3]
    hb = min(_SSD_HEAD_BLOCK, T // K)
    if (T // K) % hb:
        raise ValueError(f"a group's {T // K} tiles are no multiple of the "
                         f"{hb} a grid step takes")
    lanes = lambda a: jnp.broadcast_to(  # noqa: E731
        a[..., None], a.shape + (P,)).reshape(a.shape[:-1] + (T, W))
    row = pl.BlockSpec((1, hb, W), lambda r, j, *_: (r, j, 0))
    head = pl.BlockSpec((hb, W), lambda r, j, *_: (j, 0))
    group = pl.BlockSpec((1, 1, 1, N),
                         lambda r, j, *_: (r, j * hb // (T // K), 0, 0))
    tile = pl.BlockSpec((1, hb, N, W), lambda r, j, *_: (r, j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_ssd_update_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(G, T // hb),
            in_specs=[row, row, head, head, group, group, tile],
            out_specs=[row, tile]),
        out_shape=[_out_struct((G, T, W), jnp.float32, x, state),
                   _out_struct(state.shape, jnp.float32, x, state)],
        # operand 7 (the prefetched flags are operand 0) is the state
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(fresh.astype(jnp.int32), x.reshape(G, T, W), lanes(dt), lanes(A),
      lanes(D), B[:, :, None], C[:, :, None], state)
    return y.reshape(G, H, P), state


def ssd_update_form(x, state) -> str:
    """The name the state-update kernel is traced under for x [G, H, P]
    and a state as held: `pallas_ssd_update[P-minor]` (a head a tile) or
    `pallas_ssd_update[P-minor,heads=n]` (n heads side by side on a
    tile's lanes)."""
    n = x.shape[1] // state.shape[1]
    return "pallas_ssd_update[P-minor" + (f",heads={n}]" if n > 1 else "]")


def ssd_state_update(x, dt, A, B, C, D, state, fresh=None,
                     interpret: Optional[bool] = None):
    """`ssd_chunk_scan` for a chunk of one, a decode step: x [G, H, P];
    dt [G, H]; A, D [H]; B, C [G, K, N]; state as `ssd_state_shape` has
    it; `fresh` [G] bool, rows that start from zeros whatever their state
    holds -> (y [G, H, P], state), float32 throughout. On the TPU one
    Pallas kernel (`_ssd_update_kernel`) whose state operand is its state
    result, so a donated state is updated where it lies; plain
    `jax.numpy` elsewhere (`interpret=True`: the kernel, interpreted, for
    the tests). Which ran is noted for `attention.record_traced` under
    "ssd" (`ssd_update_form`, or "dense")."""
    if fresh is None:
        fresh = jnp.zeros(x.shape[:1], bool)
    if interpret is None and jax.default_backend() != "tpu":
        note_traced("ssd", "dense")
        G, H, P = x.shape
        K, N = B.shape[1:]
        tiles = state.shape[1]
        s5 = jnp.where(fresh[:, None, None, None], 0.0,
                       _heads_of(state, H)).reshape(G, K, H // K, N, P)
        s5 = jnp.exp(dt * A).reshape(G, K, H // K, 1, 1) * s5 \
            + B[:, :, None, :, None] * (dt[..., None] * x).reshape(
                G, K, H // K, 1, P)
        y = jnp.sum(s5 * C[:, :, None, :, None], axis=3).reshape(G, H, P)
        return y + D[:, None] * x, _tiles_of(s5.reshape(G, H, N, P), tiles)
    note_traced("ssd", ssd_update_form(x, state))
    return _ssd_update_call(x, dt, A, B, C, D, state, fresh, bool(interpret))


__all__ = ["selective_scan", "causal_conv", "ssd_chunk_scan",
           "ssd_state_update", "ssd_state_shape", "ssd_heads_per_tile",
           "ssd_update_form", "SSD_CHUNK"]
