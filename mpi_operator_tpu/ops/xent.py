"""The tied head and its softmax cross-entropy in one pass over the
vocabulary.

`lm_loss(tied_logits(h, wte), targets, mask)` writes the float32 logits
`[B, S, V]` to HBM (1.65 GB at 8 x 1024 tokens against GPT-2's 50 304
rows), reads them for the softmax's statistics and again for the arg-max
of the step's `accuracy`, and both backward products read them once more
to form the cotangent. `tied_head_xent` is the same mathematics as one
`jax.custom_vjp` that walks the vocabulary in tiles and is done with a
tile while it is on the chip: bfloat16 operands with float32 accumulation
in every product (`models/transformer.py::_head_matmul`'s arithmetic),
float32 statistics over EVERY row of the table, the cotangent cast to the
table's type before the two backward products (`_head_matmul_bwd`'s
cast). What reaches HBM is three `[B, S]` rows of statistics and, on the
way back, the cotangent `d` once in the table's type for the plain
`dtable = d^T h` product, which needs the other loop order.

Forms, reported through `attention.note_traced("head_loss", ...)`:

  pallas_xent[rows=R,vocab_tile=T,products=3]  on the TPU (or
      `interpret=True`). ONE kernel a step (`_head_kernel`): a block of R
      tokens keeps its float32 logits `[R, V]` in VMEM. Pass i over the
      table scores block i tile by tile into the running maximum, the
      sum of exponentials and the label's logit, and in the same grid
      step, with the same table tile, turns the logits pass i - 1 kept
      into block i - 1's `d` (for a cotangent of one: the loss is linear
      in it, the backward pass scales) and adds `d . table_tile` into
      its `dh`: the table streams past once a block. Three products:
      scores, `dh`, `dtable`.
  xla_chunked[chunks=C,products=3]  everywhere else: the same
      `custom_vjp` in plain `jax.numpy`, a `lax.scan` over C chunks of
      the sequence whose body computes a chunk's cotangent in its forward
      pass. A chunk's logits still visit HBM, but twice.

A call that is not differentiated (an evaluation) runs the statistics
alone: `_stats_kernel`, or the scan without its gradient half.

On a multi-device mesh the kernels run under `shard_map` over the data
axes as the flash kernels do (`attention._per_device`: GSPMD cannot
partition a Mosaic call); `dtable` stays a plain product outside it, so
the compiler sums it across chips as it does every other leaf's.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import out_struct as _out_struct
from .attention import (LANES, NEG_INF, _NN, _NT, _dividing_axes, _dot,
                        _kernel_mesh, _per_device, note_traced)

#: tokens a block keeps in VMEM with their float32 logits: 256 x 50 304
#: x 4 B = 51.5 MB of a v5e's 128 MiB. The table streams past each block,
#: so the rows a block holds are the products' operations a byte of
#: table: 128 rows were bound by HBM (24.0 ms against 16.3 for the head
#: and its gradients: PERF.md section 6, PR 43), 512 do not fit
_KEPT_ROWS = 256
#: tokens a grid step of the statistics-only kernel holds (nothing kept,
#: so the block is as tall as the step's temporaries allow)
_STATS_ROWS = 1024
#: table rows a grid step scores. A step costs a third of a microsecond
#: whatever it holds, and 256 tokens against 384 rows (the widest tile
#: that divides GPT-2's 50 304) are one microsecond of products: the head
#: and its gradients took 20.57 ms at 384, 17.41 at 1024, 16.34 at 2048
#: (PERF.md section 6, PR 43), so the last tile is cut and masked
_VOCAB_TILE = 2048
#: chunks of the sequence the plain form scans (`fused_lm_loss`'s eight)
_XLA_CHUNKS = 8
#: what Mosaic may take beyond the blocks and scratch counted by
#: `kernel_vmem_bytes`: a step's temporaries (scores, exponentials and
#: the cotangent of a [rows, tile] block, a few copies each)
_VMEM_TEMPORARIES = 24 << 20
#: what a kernel may ask of a v5e's 128 MiB in all
VMEM_CEILING = 112 << 20


class Tiling(NamedTuple):
    """How a kernel cuts [N, E] tokens against a [V, E] table."""
    rows: int          # tokens a grid step
    vocab_tile: int    # table rows a grid step
    row_blocks: int
    vocab_tiles: int


def tiling(N: int, V: int, rows: int, tile: Optional[int] = None) -> Tiling:
    """Blocks of `rows` tokens (fewer tokens: one block) against tiles of
    `tile` table rows: `_VOCAB_TILE` where none is named, a small table
    whole; a last tile that V cuts has its columns past V masked."""
    rows = min(rows, N)
    tile = tile or min(V, _VOCAB_TILE)
    return Tiling(rows, tile, pl.cdiv(N, rows), pl.cdiv(V, tile))


def kernel_vmem_bytes(t: Tiling, E: int, itemsize: int, kept: bool) -> int:
    """Bytes of VMEM a kernel's blocks (two buffers each) and scratch
    take, `kept` for the one that keeps a block's logits and writes `d`
    and `dh` (`_head_kernel`; else `_stats_kernel`): what
    `vmem_limit_bytes` is reckoned from, and what
    `tests/test_tpu_compile.py` holds against the limit asked for."""
    column = 4 * LANES * t.rows         # a [rows, 1] float32 column, padded
    blocks = 2 * itemsize * E * (t.rows + t.vocab_tile) + 2 * 5 * column
    scratch = 3 * column
    if kept:
        blocks += 2 * itemsize * t.rows * (t.vocab_tile + E)    # d, dh
        scratch += 4 * t.rows * (t.vocab_tiles * t.vocab_tile + E)
    return blocks + scratch


def _vmem_limit(t: Tiling, E: int, itemsize: int, kept: bool) -> int:
    return min(kernel_vmem_bytes(t, E, itemsize, kept) + _VMEM_TEMPORARIES,
               VMEM_CEILING)


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _scores(h_ref, t_ref, j, V):
    """A block's float32 scores against table tile `j`, and the tile's
    column numbers; columns past the table's last row (a cut last tile)
    read NEG_INF, which no statistic sees."""
    s = _dot(h_ref[...], t_ref[...], _NT)              # [rows, tile]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    tile = s.shape[1]
    if V % tile:
        s = jnp.where(col < V - j * tile, s, NEG_INF)
    return s, col


def _table_rows(t_ref, j, V):
    """Table tile `j` for a product that sums over its rows: a cut last
    tile's rows past the table hold anything, and 0 x NaN is NaN."""
    t = t_ref[...]
    tile = t.shape[0]
    if V % tile:
        row = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
        t = jnp.where(row < V - j * tile, t, jnp.zeros_like(t))
    return t


def _fold(s, at_label, m_ref, l_ref, zy_ref):
    """One tile into the running row maximum, sum of exponentials and
    label's logit (float32 columns [rows, 1])."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=-1, keepdims=True)
    m_ref[...] = m_new
    zy_ref[...] += jnp.sum(jnp.where(at_label, s, 0.0), axis=-1,
                           keepdims=True)


def _start(m_ref, l_ref, zy_ref):
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    zy_ref[...] = jnp.zeros(zy_ref.shape, jnp.float32)


def _finish(m_ref, l_ref, zy_ref, lse_ref, hit_ref):
    """The log-normaliser, and whether the label's logit is the maximum."""
    lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])
    hit_ref[...] = (zy_ref[...] >= m_ref[...]).astype(jnp.float32)


def _stats_kernel(h_ref, t_ref, y_ref, lse_ref, zy_ref, hit_ref, m_ref,
                  l_ref, *, V):
    """Grid (row blocks, vocabulary tiles), the tiles innermost: the
    statistics of a block of tokens, nothing else."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        _start(m_ref, l_ref, zy_ref)

    s, col = _scores(h_ref, t_ref, j, V)
    _fold(s, col == y_ref[...] - j * s.shape[1], m_ref, l_ref, zy_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        _finish(m_ref, l_ref, zy_ref, lse_ref, hit_ref)


def _head_kernel(h_ref, t_ref, yf_ref, yb_ref, w_ref, lse_ref, zy_ref,
                 hit_ref, d_ref, dh_ref, s_ref, m_ref, l_ref, zyf_ref,
                 lseb_ref, acc_ref, *, V, blocks):
    """Grid (row blocks + 1, vocabulary tiles): pass i scores block i
    against tile j (the forward half: the scores kept in `s_ref[j]`, the
    statistics folded) and, in the same step and with the same table
    tile, turns the scores pass i - 1 kept there into block i - 1's
    cotangent and its `dh` (the backward half, which reads the slot
    before the forward half fills it again). The table streams past
    once a block, not twice; the first pass has no backward half, the
    last no forward one."""
    i, j = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1
    tile = t_ref.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (h_ref.shape[0], tile), 1)

    def step(forward, backward):
        if backward:
            @pl.when(j == 0)
            def _():
                acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        if forward:
            @pl.when(j == 0)
            def _():
                _start(m_ref, l_ref, zyf_ref)
        if backward:
            p = jnp.exp(s_ref[j] - lseb_ref[...])
            onehot = (col == yb_ref[...] - j * tile).astype(jnp.float32)
            d = ((p - onehot) * w_ref[...]).astype(d_ref.dtype)
            d_ref[...] = d
            acc_ref[...] += _dot(d, _table_rows(t_ref, j, V), _NN)
        if forward:
            s, _ = _scores(h_ref, t_ref, j, V)
            s_ref[j] = s
            _fold(s, col == yf_ref[...] - j * tile, m_ref, l_ref, zyf_ref)
        if backward:
            @pl.when(j == last)
            def _():
                dh_ref[...] = acc_ref[...].astype(dh_ref.dtype)
        if forward:
            @pl.when(j == last)
            def _():
                _finish(m_ref, l_ref, zyf_ref, lse_ref, hit_ref)
                lseb_ref[...] = lse_ref[...]
                zy_ref[...] = zyf_ref[...]

    pl.when(i == 0)(lambda: step(True, False))
    pl.when((i > 0) & (i < blocks))(lambda: step(True, True))
    pl.when(i == blocks)(lambda: step(False, True))


@functools.partial(jax.jit, static_argnums=(3, 4, 5), inline=True)
def _stats_call(h, table, y, rows, tile, interpret):
    """h [N, E], table [V, E], y [N, 1] -> (lse, zy, hit) [N, 1] float32."""
    (N, E), V = h.shape, table.shape[0]
    t = tiling(N, V, rows, tile)
    column = pl.BlockSpec((t.rows, 1), lambda i, j: (i, 0))
    stat = _out_struct((N, 1), jnp.float32, h, table)
    return pl.pallas_call(
        functools.partial(_stats_kernel, V=V),
        grid=(t.row_blocks, t.vocab_tiles),
        in_specs=[pl.BlockSpec((t.rows, E), lambda i, j: (i, 0)),
                  pl.BlockSpec((t.vocab_tile, E), lambda i, j: (j, 0)),
                  column],
        out_specs=[column, column, column],
        out_shape=[stat, stat, stat],
        scratch_shapes=[pltpu.VMEM((t.rows, 1), jnp.float32),
                        pltpu.VMEM((t.rows, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(t, E, h.dtype.itemsize, False)),
        interpret=interpret,
    )(h, table, y)


@functools.partial(jax.jit, static_argnums=(4, 5, 6), inline=True)
def _head_call(h, table, y, w, rows, tile, interpret):
    """h [N, E], table [V, E], y and w [N, 1] -> (lse, zy, hit [N, 1]
    float32, d [N, V] and dh [N, E] in the operands' type, for a loss
    cotangent of one)."""
    (N, E), V = h.shape, table.shape[0]
    t = tiling(N, V, rows, tile)
    nb = t.row_blocks
    ahead = lambda i, j: (jnp.minimum(i, nb - 1), 0)     # noqa: E731
    behind = lambda i, j: (jnp.maximum(i - 1, 0), 0)     # noqa: E731
    stat = _out_struct((N, 1), jnp.float32, h, table)
    return pl.pallas_call(
        functools.partial(_head_kernel, V=V, blocks=nb),
        grid=(nb + 1, t.vocab_tiles),
        in_specs=[pl.BlockSpec((t.rows, E), ahead),
                  pl.BlockSpec((t.vocab_tile, E), lambda i, j: (j, 0)),
                  pl.BlockSpec((t.rows, 1), ahead),
                  pl.BlockSpec((t.rows, 1), behind),
                  pl.BlockSpec((t.rows, 1), behind)],
        # d's block stands at (0, 0) all through the first pass, which
        # does not write it
        out_specs=[pl.BlockSpec((t.rows, 1), ahead)] * 3 + [
            pl.BlockSpec((t.rows, t.vocab_tile), lambda i, j: (
                jnp.maximum(i - 1, 0), j * jnp.minimum(i, 1))),
            pl.BlockSpec((t.rows, E), behind)],
        out_shape=[stat, stat, stat,
                   _out_struct((N, V), table.dtype, h, table),
                   _out_struct((N, E), h.dtype, h, table)],
        scratch_shapes=[
            pltpu.VMEM((t.vocab_tiles, t.rows, t.vocab_tile), jnp.float32),
            pltpu.VMEM((t.rows, 1), jnp.float32),
            pltpu.VMEM((t.rows, 1), jnp.float32),
            pltpu.VMEM((t.rows, 1), jnp.float32),
            pltpu.VMEM((t.rows, 1), jnp.float32),
            pltpu.VMEM((t.rows, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(t, E, h.dtype.itemsize, True)),
        interpret=interpret,
    )(h, table, y, y, w)


# ---------------------------------------------------------------------------
# The plain form
# ---------------------------------------------------------------------------

def _by_chunk(x, chunks):
    """[B, S, ...] -> [chunks, B, S / chunks, ...]: the batch axis stays
    whole, so a batch split over the data axes stays split in the scan."""
    B, S = x.shape[:2]
    return jnp.moveaxis(x.reshape(B, chunks, S // chunks, *x.shape[2:]),
                        1, 0)


def _from_chunks(x):
    """[chunks, B, C, ...] -> [B, chunks * C, ...]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _xla_rows(h, table, y, w, chunks, with_grad):
    """The statistics [B, S] of every token and, `with_grad`, the
    cotangent for a loss cotangent of one as chunks [chunks, B, C, V]
    with `dh` [B, S, E]: a scan over chunks of the sequence."""
    from ..models.transformer import _head_matmul

    def chunk(_, xs):
        h_c, y_c, w_c = xs
        s = _head_matmul(h_c, table)                   # [B, C, V] float32
        m = s.max(-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(s - m[..., None]), -1))
        zy = jnp.take_along_axis(s, y_c[..., None], axis=-1)[..., 0]
        out = (lse, zy, (zy >= m).astype(jnp.float32))
        if with_grad:
            onehot = jax.nn.one_hot(y_c, s.shape[-1], dtype=jnp.float32)
            d = ((jnp.exp(s - lse[..., None]) - onehot)
                 * w_c[..., None]).astype(table.dtype)
            dh = jax.lax.dot_general(
                d, table, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(h_c.dtype)
            out += (d, dh)
        return None, out

    _, out = jax.lax.scan(chunk, None, tuple(
        _by_chunk(x, chunks) for x in (h, y, w)))
    lse, zy, hit = (_from_chunks(x) for x in out[:3])
    if not with_grad:
        return lse, zy, hit
    return lse, zy, hit, out[3], _from_chunks(out[4])


# ---------------------------------------------------------------------------
# The custom_vjp
# ---------------------------------------------------------------------------

class Form(NamedTuple):
    """How a call runs (static): the kernel (`rows`, `tile` its grid
    steps) or, `scan`, the plain form (`rows` its chunks)."""
    scan: bool
    rows: int
    tile: Optional[int]
    interpret: bool

    def name(self, N: int, V: int) -> str:
        if self.scan:
            return f"xla_chunked[chunks={self.rows},products=3]"
        t = tiling(N, V, self.rows, self.tile)
        return (f"pallas_xent[rows={t.rows},vocab_tile={t.vocab_tile},"
                f"products=3]")


def _flat(x):
    """[B, S, ...] -> [B * S, ...], a [B, S] array to a column."""
    return x.reshape(-1, *(x.shape[2:] or (1,)))


def _device_stats(h, table, y, form):
    """(lse, zy, hit) [B, S] of one device's tokens."""
    with jax.named_scope("xent.fwd"):
        out = _stats_call(_flat(h), table, _flat(y), _STATS_ROWS, form.tile,
                          form.interpret)
    return tuple(x.reshape(y.shape) for x in out)


def _device_head(h, table, y, w, form):
    """(lse, zy, hit [B, S], d [B, S, V], dh [B, S, E]) of one device's
    tokens, the last two for a loss cotangent of one."""
    with jax.named_scope("xent.fwd"):
        *stats, d, dh = _head_call(_flat(h), table, _flat(y), _flat(w),
                                   form.rows, form.tile, form.interpret)
    return (*(x.reshape(y.shape) for x in stats),
            d.reshape(*y.shape, -1), dh.reshape(h.shape))


_ROWS2, _ROWS3, _WHOLE = ("rows", None), ("rows", None, None), (None, None)


def _on_devices(fn, h, *args, layouts):
    """`fn(h, *args)` on each device's rows of a multi-device mesh, or
    straight through (one device, or already inside a manual region)."""
    mesh = _kernel_mesh(h)
    if mesh is None:
        return fn(h, *args)
    return _per_device(fn, mesh, h.shape[0], 1, (h, *args),
                       (_ROWS3, *layouts), ("rows",))


def _loss(w, lse, zy):
    return jnp.sum(w * (lse - zy))


def _scaled(x, g):
    """`g x` in x's type: exact where the loss's cotangent g is one."""
    return (g * x.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _head_xent(h, table, y, w, form):
    """h [B, S, E], table [V, E] of h's type, labels y and weights w
    [B, S] -> (sum of w x the tokens' cross-entropy, hits [B, S]: 1.0
    where the label's logit is the row's maximum)."""
    if form.scan:
        lse, zy, hit = _xla_rows(h, table, y, w, form.rows, False)
    else:
        lse, zy, hit = _on_devices(
            functools.partial(_device_stats, form=form), h, table, y,
            layouts=(_WHOLE, _ROWS2))
    return _loss(w, lse, zy), hit


def _head_xent_fwd(h, table, y, w, form):
    if form.scan:
        lse, zy, hit, d, dh = _xla_rows(h, table, y, w, form.rows, True)
        h = _by_chunk(h, form.rows)          # as d lies: [chunks, B, C, .]
    else:
        lse, zy, hit, d, dh = _on_devices(
            functools.partial(_device_head, form=form), h, table, y, w,
            layouts=(_WHOLE, _ROWS2, _ROWS2))
    return (_loss(w, lse, zy), hit), (h, table, d, dh)


def _head_xent_bwd(form, res, cotangents):
    """`dh` and `dtable = d^T h` (float32 sums, the cast to the table's
    type straight behind the product, as `_head_matmul_bwd` has it: a
    data-parallel mesh then sums the leaf in that type), scaled by the
    loss's cotangent."""
    del form
    h, table, d, dh = res
    g = cotangents[0]
    lead = tuple(range(d.ndim - 1))
    with jax.named_scope("xent.bwd"):
        dtable = jax.lax.dot_general(
            d, h, ((lead, lead), ((), ())),
            preferred_element_type=jnp.float32).astype(table.dtype)
    return _scaled(dh, g), _scaled(dtable, g), None, None


_head_xent.defvjp(_head_xent_fwd, _head_xent_bwd)


# ---------------------------------------------------------------------------
# The public op
# ---------------------------------------------------------------------------

def covers(E: int, V: int, dtype) -> bool:
    """Whether `tied_head_xent` takes a head of embedding width E over V
    rows computed in `dtype`: bfloat16 (float32 keeps `lm_loss`'s plain
    path), an embedding width of whole lane tiles, and a block of
    `_KEPT_ROWS` tokens whose logits fit VMEM."""
    if jnp.dtype(dtype) != jnp.bfloat16 or E % LANES:
        return False
    t = tiling(_KEPT_ROWS, V, _KEPT_ROWS)
    return kernel_vmem_bytes(t, E, 2, True) + _VMEM_TEMPORARIES \
        <= VMEM_CEILING


def tied_head_xent(h, table, targets, mask=None, denom=None, *,
                   scan: Optional[bool] = None, rows: Optional[int] = None,
                   tile: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """`(lm_loss(tied_logits(h, table), targets, mask, denom), accuracy)`
    without the logits: h [B, S, E] the backbone's output, table [V, E]
    the tied embedding, targets [B, S]; `mask` selects the scored tokens
    and `denom` overrides the normaliser, as `lm_loss` takes them.
    `accuracy` is the masked share of tokens whose label's logit is the
    row's maximum (`argmax(logits) == targets` but for an exact tie).

    The form follows the backend: the kernel on the TPU, the scan
    elsewhere (`interpret=True`: the kernel, interpreted, for the
    tests). `scan`, `rows` and `tile` name a form and its steps, for
    measurements and tests."""
    B, S, _ = h.shape
    V = table.shape[0]
    from ..parallel.mesh import BATCH_AXES
    mesh = _kernel_mesh(h)
    ways = 1 if mesh is None else _dividing_axes(mesh, B, BATCH_AXES)[1]
    N = B * S // ways                       # tokens a device scores
    if scan is None:
        # the kernel where its blocks divide a device's tokens: a cut
        # last block of tokens ran into the chip tool's time limit
        # (PERF.md section 6, PR 43); such a shape takes the scan
        scan = (interpret is None and jax.default_backend() != "tpu") \
            or N % min(_KEPT_ROWS, N) != 0
    form = Form(True, math.gcd(rows or _XLA_CHUNKS, S), None, False) \
        if scan else Form(False, rows or _KEPT_ROWS, tile, bool(interpret))
    note_traced("head_loss", form.name(N, V))
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    mask = mask.astype(jnp.float32)
    count = jnp.maximum(mask.sum(), 1)
    w = mask / (count if denom is None else denom)
    loss, hit = _head_xent(h, table.astype(h.dtype), targets, w, form)
    return loss, jnp.sum(hit * mask) / count


__all__ = ["tied_head_xent", "covers", "kernel_vmem_bytes", "tiling",
           "Tiling", "VMEM_CEILING"]
