"""Flash attention — Pallas TPU kernels for the hot op, forward AND backward.

The reference delegates all device compute to out-of-repo CUDA libraries
(SURVEY.md §2.2); this is the TPU-native hot-path kernel built per
/opt/skills/guides/pallas_guide.md: the attention score matrix never
materializes in HBM, in either direction.

Two forms of the same algorithm, chosen from the operands alone
(`flash_attention`):

  resident  what the trainer's step runs (no key mask, heads that fill
    128-lane tiles, a sequence whose blocks fit VMEM). TWO kernels, grid
    (B, H·D / 128), one step a lane block of heads — a head of 128, or a
    PAIR of 64 — whole in VMEM:
      q/k/v/o and gradients  [B, S, H·D]   blocks (1, S, 128)
      lse                    [B, H/hp, hp, S]  blocks (1, 1, hp, S): rows
    fwd   loops over q blocks and, inside, the k blocks up to the
          diagonal: online softmax, output + lse written a q block.
    bwd   the same loops with the scores TRANSPOSED (sT = k qT), so that
          lse and delta are rows; s, p, dp, ds computed ONCE a block and
          added into dq, dk and dv; delta computed in the kernel.
  streamed  everything else that tiles, and ring attention's pairings.
    THREE kernels over [BH, S, D] blocks (1, block, D), lse / delta
    [BH, S, 128] blocks (1, block_q, 128) (the row statistic broadcast
    across a 128-lane minor dim: a bare [BH, S] row can't block legally),
    kv mask [B, 8, S] blocks (1, 8, block_k), indexed b = bh // H:
    fwd   grid (BH, nq, nk), k innermost: online softmax in VMEM scratch,
          output + lse written on the last k step.
    dq    grid (BH, nq, nk), k innermost: dq accumulates in VMEM scratch,
          ds = p * (dp - delta) recomputed blockwise from the lse residual.
    dkv   grid (BH, nk, nq), q innermost: dk/dv accumulate in VMEM scratch.

In both, every product takes its operands in the caller's type and sums
in float32; a causal block above the diagonal is neither visited nor
fetched, and only a block the diagonal cuts is masked.

Key-padding masks are first-class: `kv_mask` [B, S] (True = real token)
masks score columns in all three kernels, so padded BERT batches keep the
flash path instead of falling back to dense O(S²) (the round-1 gap).

On CPU (tests, simulation) the identical kernels run in interpret mode;
on TPU they are always compiled by Mosaic (`_resolve_interpret`).

Every dispatch site that chooses between a kernel and the dense path
(here, and `models/transformer.py` `_attend` / `_decode_attend`) reports
what it chose through `note_traced`, so an entry point can print the
implementation that was actually traced (`record_traced`) instead of the
flag it was asked for.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Dict, Iterator, Optional, Set

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30
LANES = 128        # minor-dim width for row-statistic tensors


from ..utils.compat import out_struct as _out_struct  # noqa: E402


# ---------------------------------------------------------------------------
# Which implementation was traced
# ---------------------------------------------------------------------------

_TRACED: contextvars.ContextVar = contextvars.ContextVar(
    "traced_attention", default=None)


@contextlib.contextmanager
def record_traced() -> Iterator[Dict[str, Set[str]]]:
    """Collect the implementations traced while the block runs: traced
    once a jit, so wrap the whole run, first call included.
    Yields a dict the dispatch sites fill at TRACE time:
      "attention" — full-sequence attention: "flash" | "dense" | "ring"
      "flash"     — the form of the flash kernels traced, with the heads
                    a lane block holds and the loops' steps:
                    "resident[heads=2,q=512,k=512]" (a lane block of
                    heads whole in VMEM, two kernels) |
                    "streamed[q=512,k=512]" (a grid over blocks, three)
      "decode"    — single-token KV-cache steps: "pallas[hb=N]" |
                    "pallas_paged[live,pages=N,hb=M]" (the paged kernel
                    that walks a row's live pages, N a turn, M kv heads
                    a grid step) | "pallas_paged[hb=N]" (an int8 pool:
                    the grid form; N: kv heads a grid step of the kernel
                    covers, `decode_head_block`) |
                    "pallas_mla_paged[live,pages=N(,chains=C)]" (the latent
                    walk, N pages a turn, C chains in flight) | "dense"
      "prefill"   — multi-token KV-cache calls (always "dense" today)
      "ssd"       — ops/ssm.py's state update: `ssd_update_form` | "dense"
      "gdn"       — ops/gated_delta.py's state update: `gdn_update_form`
                    | "dense"
      "head_loss" — ops/xent.py's head and loss in one pass: `Form.name`"""
    rec: Dict[str, Set[str]] = {k: set() for k in (
        "attention", "flash", "decode", "prefill", "ssd", "gdn", "head_loss")}
    token = _TRACED.set(rec)
    try:
        yield rec
    finally:
        _TRACED.reset(token)


def note_traced(kind: str, impl: str) -> None:
    """Called by a dispatch site when it traces `impl`; no-op outside
    `record_traced`."""
    rec = _TRACED.get()
    if rec is not None:
        rec[kind].add(impl)


def traced_name(impls: Set[str]) -> Optional[str]:
    """One printable name for a set of traced implementations: the name
    itself when there is one, "a+b" when a run traced several, None when
    nothing of that kind was traced."""
    return "+".join(sorted(impls)) if impls else None


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode is the CPU stand-in for Mosaic; on TPU a kernel is
    compiled or it is an error — never quietly interpreted."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: Pallas kernels "
                         "are compiled by Mosaic there")
    return interpret


# ---------------------------------------------------------------------------
# Kernels on a multi-device mesh
# ---------------------------------------------------------------------------
# GSPMD cannot partition a Mosaic kernel: inside a plain jit whose program
# spans more than one device, lowering a pallas_call raises "Mosaic kernels
# cannot be automatically partitioned. Please wrap the call in a
# shard_map" (first seen on the four-chip v5e host; one chip never hits
# it). Attention is independent per (row, head), so the public kernels
# below wrap themselves: rows split over the data axes, heads over tp —
# the layout the trainer's activations and the tp-sharded projections
# already have, so the wrap moves no data — and each device runs the
# kernel on its own block. A dim the axes do not divide stays whole
# (every device computes all of it, which is what GSPMD would have done
# with an opaque call on replicated operands).

def _kernel_mesh(x):
    """The mesh a kernel called on `x` must be shard_mapped over, or None
    when the call can go straight through: `x` is not typed on a mesh
    (plain single-device jit), the mesh is one device, or we are already
    inside a manual region (ring attention, pipeline stages — Mosaic wants
    every axis manual, and those callers own their layout)."""
    mesh = jax.typeof(x).sharding.mesh
    if mesh.empty or mesh.size == 1 or any(
            t == jax.sharding.AxisType.Manual for t in mesh.axis_types):
        return None
    return mesh


#: the mesh axis heads are split over
_HEAD_AXES = ("tp",)


def _dividing_axes(mesh, dim: int, axes):
    """(the names among `axes` that split a dim of `dim` over `mesh`, how
    many ways): (None, 1) where the mesh has none of them or they do not
    divide it, and the dim stays whole on every device."""
    names = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    n = math.prod(mesh.shape[a] for a in names)
    return (names, n) if names and dim % n == 0 else (None, 1)


def _per_device(fn, mesh, rows: int, heads: int, args, layouts, out_layout):
    """Run `fn(*args)` on each device's block of a multi-device mesh.

    A layout names, per dim of its array, what the dim holds: "rows"
    (batch rows / slots, `rows` of them — split over the data axes),
    "heads" (`heads` of them — split over tp) or None (kept whole). A
    split the axes do not divide is dropped. None args (absent mask /
    scales) pass through."""
    from ..parallel.mesh import BATCH_AXES
    from ..utils.compat import shard_map

    split = {"rows": _dividing_axes(mesh, rows, BATCH_AXES)[0],
             "heads": _dividing_axes(mesh, heads, _HEAD_AXES)[0], None: None}

    def spec(layout):
        return P(*(split[role] for role in layout))

    present = [i for i, a in enumerate(args) if a is not None]

    def body(*given):
        full = [None] * len(args)
        for i, g in zip(present, given):
            full[i] = g
        return fn(*full)

    return shard_map(
        body, mesh=mesh, in_specs=tuple(spec(layouts[i]) for i in present),
        out_specs=spec(out_layout),
        # the Pallas interpreter (CPU) trips the VMA checker; the specs
        # here name every split explicitly and claim no replication
        check_vma=False)(*(args[i] for i in present))


# ---------------------------------------------------------------------------
# What the two forms share
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a bT: contract the minor dim of both
_NN = (((1,), (0,)), ((), ()))      # a b
_TN = (((0,), (0,)), ((), ()))      # aT b: contract the major dim of both


def _dot(a, b, dims):
    """A product on the operands' own type, summed in float32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _folds_scale(sm_scale: float, dtype) -> bool:
    """Whether the scores' scale can ride on q ([block, D]) instead of on
    the scores ([block_q, block_k], eight times the elements): where the
    scaled q is the same number — float32 operands, or a power of two
    (heads of 64: 1/8) in any type. Elsewhere (bfloat16 heads of 128) the
    scores are scaled, as they always were."""
    return (jnp.dtype(dtype) == jnp.float32
            or math.frexp(sm_scale)[0] == 0.5)


def _scaled(x, sm_scale, fold, keep=None):
    """`x` (a block of q, or of do with nothing to fold) times the scale
    if it is folded, zero outside the lanes `keep` marks; float32
    arithmetic, the operand's type back."""
    if not fold and keep is None:
        return x
    y = x.astype(jnp.float32)
    if fold:
        y = y * sm_scale
    if keep is not None:
        y = jnp.where(keep, y, 0.0)
    return y.astype(x.dtype)


def _under_diagonal(q0, k0, shape, q_axis):
    """Where key position <= query position in a block of scores whose
    first query is `q0` and first key `k0`; queries along `q_axis`."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return kpos <= qpos


# ---------------------------------------------------------------------------
# Streamed form: a grid over (head, q block, k block)
# ---------------------------------------------------------------------------
# What a shape takes that the resident form below does not cover: a key
# mask, a head whose sequence does not fit VMEM, heads that do not fill
# whole lane tiles (gpt2-xl's 25 of 64); and what ring attention pairs
# q-spans with k-spans through (`_flash_fwd`, `_dq_call`, `_dkv_call`).
# Every product takes its operands as the caller passed them, a block the
# diagonal does not cut is not masked, and a block above it is neither
# visited nor fetched (its index map pins to the last block seen).

def _last_k_block(qi, block_q, block_k):
    """The last k block a causal q block sees."""
    return (qi * block_q + block_q - 1) // block_k


def _for_visited_block(body, causal, qi, ki, block_q, block_k):
    """`body(cut)` if the block (qi, ki) is visited: `cut` False where it
    lies clear of the diagonal (or nothing is causal), True where the
    diagonal cuts it — its last key past its first query — and it must
    be masked; a block above the diagonal runs nothing."""
    if not causal:
        body(False)
        return
    seen = ki <= _last_k_block(qi, block_q, block_k)
    cut = ki * block_k + block_k - 1 > qi * block_q
    pl.when(seen & jnp.logical_not(cut))(functools.partial(body, False))
    pl.when(seen & cut)(functools.partial(body, True))


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal,
                block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    fold = _folds_scale(sm_scale, q_ref.dtype)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def attend(cut):
        q = _scaled(q_ref[0], sm_scale, fold)     # [block_q, d]
        k = k_ref[0]                              # [block_k, d]
        v = v_ref[0]
        s = _dot(q, k, _NT)
        if not fold:
            s = s * sm_scale
        if cut:
            s = jnp.where(_under_diagonal(qi * block_q, ki * block_k,
                                          s.shape, 0), s, NEG_INF)
        if mask_ref is not None:
            s = jnp.where(mask_ref[0, :1] > 0, s, NEG_INF)  # [1, block_k]

        m_prev = m_ref[:, :1]                     # [block_q, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                    # [block_q, block_k]
        l_ref[:, :1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _dot(p.astype(v.dtype), v, _NN)
        m_ref[:, :1] = m_new

    _for_visited_block(attend, causal, qi, ki, block_q, block_k)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l),
                                      (block_q, LANES))


def _streamed_specs(D, block_q, block_k, causal, kv_mask, num_heads,
                    q_major=True):
    """Block specs of the streamed kernels: (a q-side block of `width`,
    a k-side block, the key mask's or None). The grid is (head, q block,
    k block), or with `q_major` False (head, k block, q block). Under a
    causal mask a block the kernel skips takes the index of the nearest
    one it visits, so nothing is fetched for it."""
    def qk(a, b):
        qi, ki = (a, b) if q_major else (b, a)
        if causal and q_major:
            ki = jnp.minimum(ki, _last_k_block(qi, block_q, block_k))
        elif causal:
            qi = jnp.maximum(qi, (ki * block_k) // block_q)
        return qi, ki

    def q_side(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, i, j: (b, qk(i, j)[0], 0))

    k_side = pl.BlockSpec((1, block_k, D),
                          lambda b, i, j: (b, qk(i, j)[1], 0))
    mask = None if kv_mask is None else pl.BlockSpec(
        (1, 8, block_k), lambda b, i, j: (b // num_heads, 0, qk(i, j)[1]))
    return q_side, k_side, mask


def _flash_fwd(q, k, v, kv_mask, sm_scale, causal, block_q, block_k,
               num_heads, interpret):
    """q/k/v: [BH, S, D]; kv_mask: [B, 8, S] f32 or None.
    Returns (out [BH, S, D], lse [BH, S, LANES])."""
    BH, S, D = q.shape
    q_side, k_side, mask_spec = _streamed_specs(
        D, block_q, block_k, causal, kv_mask, num_heads)
    in_specs, args = [q_side(D), k_side, k_side], [q, k, v]
    if kv_mask is not None:
        in_specs.append(mask_spec)
        args.append(kv_mask)

    def kern(*refs):
        mask_ref = refs[3] if kv_mask is not None else None
        _fwd_kernel(*refs[:3], mask_ref, *refs[len(args):],
                    sm_scale=sm_scale, causal=causal, block_q=block_q,
                    block_k=block_k)
    return pl.pallas_call(
        kern,
        grid=(BH, S // block_q, S // block_k),
        in_specs=in_specs,
        out_specs=[q_side(D), q_side(LANES)],
        out_shape=[
            _out_struct((BH, S, D), q.dtype, q, k, v),
            _out_struct((BH, S, LANES), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),      # acc
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum l
        ],
        interpret=interpret,
    )(*args)


def _block_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                 qi, ki, cut, *, sm_scale, block_q, block_k):
    """(q as the scores took it, do, p, ds) of one block of the backward:
    p = exp(s - lse) from the forward's row statistics and ds = p (dp -
    delta), the scores' gradient before their scale (which the caller
    puts on dq, or has on q already). p is ZEROED where the forward
    masked, not the scores masked again: a row with no key left has a
    degenerate lse, and a bare exp would resurrect its masked keys."""
    fold = _folds_scale(sm_scale, q_ref.dtype)
    q = _scaled(q_ref[0], sm_scale, fold)
    k = k_ref[0]
    do = do_ref[0]
    s = _dot(q, k, _NT)
    if not fold:
        s = s * sm_scale
    p = jnp.exp(s - lse_ref[0, :, :1])            # [block_q, block_k]
    keep = None
    if cut:
        keep = _under_diagonal(qi * block_q, ki * block_k, s.shape, 0)
    if mask_ref is not None:
        valid = mask_ref[0, :1] > 0
        keep = valid if keep is None else jnp.logical_and(keep, valid)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    dp = _dot(do, v_ref[0], _NT)
    ds = p * (dp - delta_ref[0, :, :1])
    if not fold:
        ds = ds * sm_scale
    return q, do, p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
               dq_ref, dq_acc, *, sm_scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def accumulate(cut):
        *_, ds = _block_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, qi,
            ki, cut, sm_scale=sm_scale, block_q=block_q, block_k=block_k)
        k = k_ref[0]
        dq_acc[:] += _dot(ds.astype(k.dtype), k, _NN)

    _for_visited_block(accumulate, causal, qi, ki, block_q, block_k)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq = dq_acc[:]
        if _folds_scale(sm_scale, q_ref.dtype):
            dq = dq * sm_scale
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                block_q, block_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def accumulate(cut):
        q, do, p, ds = _block_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, qi,
            ki, cut, sm_scale=sm_scale, block_q=block_q, block_k=block_k)
        dv_acc[:] += _dot(p.astype(do.dtype), do, _TN)     # pT do
        dk_acc[:] += _dot(ds.astype(q.dtype), q, _TN)      # dsT q

    _for_visited_block(accumulate, causal, qi, ki, block_q, block_k)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_call(kernel, q_major, outs, q, k, v, do, lse_lanes, delta_lanes,
              kv_mask, sm_scale, causal, block_q, block_k, num_heads,
              interpret):
    """One of the streamed backward kernels over q/k/v/do [BH, S, D] and
    lse/delta [BH, S, LANES]; `outs` names its outputs' sides ("q" or
    "k"), each with an accumulator of its block."""
    BH, S, D = q.shape
    q_side, k_side, mask_spec = _streamed_specs(
        D, block_q, block_k, causal, kv_mask, num_heads, q_major)
    in_specs = [q_side(D), k_side, k_side, q_side(D), q_side(LANES),
                q_side(LANES)]
    args = [q, k, v, do, lse_lanes, delta_lanes]
    if kv_mask is not None:
        in_specs.append(mask_spec)
        args.append(kv_mask)

    def kern(*refs):
        mask_ref = refs[6] if kv_mask is not None else None
        kernel(*refs[:6], mask_ref, *refs[len(args):], sm_scale=sm_scale,
               causal=causal, block_q=block_q, block_k=block_k)
    nq, nk = S // block_q, S // block_k
    side = {"q": (q_side(D), block_q), "k": (k_side, block_k)}
    out = pl.pallas_call(
        kern,
        grid=(BH, nq, nk) if q_major else (BH, nk, nq),
        in_specs=in_specs,
        out_specs=[side[o][0] for o in outs],
        out_shape=[_out_struct((BH, S, D), q.dtype, q, k, v, do)
                   for _ in outs],
        scratch_shapes=[pltpu.VMEM((side[o][1], D), jnp.float32)
                        for o in outs],
        interpret=interpret,
    )(*args)
    return out


def _dq_call(q, k, v, do, lse_lanes, delta_lanes, kv_mask, sm_scale,
             causal, block_q, block_k, num_heads, interpret):
    """dq for one (q-span × k-span) pairing: grid (BH, nq, nk), k
    innermost. lse/delta: [BH, S, LANES]. Reused by the ring-attention
    backward (parallel/ring_attention.py) with per-block lse/delta from
    the GLOBAL softmax statistics."""
    return _bwd_call(_dq_kernel, True, "q", q, k, v, do, lse_lanes,
                     delta_lanes, kv_mask, sm_scale, causal, block_q,
                     block_k, num_heads, interpret)[0]


def _dkv_call(q, k, v, do, lse_lanes, delta_lanes, kv_mask, sm_scale,
              causal, block_q, block_k, num_heads, interpret):
    """dk/dv for one pairing: grid (BH, nk, nq), q innermost; see
    `_dq_call`."""
    return _bwd_call(_dkv_kernel, False, "kk", q, k, v, do, lse_lanes,
                     delta_lanes, kv_mask, sm_scale, causal, block_q,
                     block_k, num_heads, interpret)


def _flash_bwd(sm_scale, causal, block_q, block_k, num_heads, interpret,
               res, do):
    q, k, v, out, lse, kv_mask = res
    BH, S, D = q.shape
    # the residual lse is stored [BH, S] (one scalar per row); re-broadcast
    # to the Mosaic-legal 128-lane layout only for the kernels' lifetime
    lse = jnp.broadcast_to(lse[..., None], (BH, S, LANES))
    # delta = rowsum(dO ∘ O), lane-broadcast like lse
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (BH, S, LANES))
    dq = _dq_call(q, k, v, do, lse, delta, kv_mask, sm_scale, causal,
                  block_q, block_k, num_heads, interpret)
    dk, dv = _dkv_call(q, k, v, do, lse, delta, kv_mask, sm_scale, causal,
                       block_q, block_k, num_heads, interpret)
    dmask = None if kv_mask is None else jnp.zeros_like(kv_mask)
    return dq, dk, dv, dmask


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, kv_mask, sm_scale, causal, block_q, block_k,
                num_heads, interpret):
    out, _ = _flash_fwd(q, k, v, kv_mask, sm_scale, causal, block_q,
                        block_k, num_heads, interpret)
    return out


def _flash_core_fwd(q, k, v, kv_mask, sm_scale, causal, block_q, block_k,
                    num_heads, interpret):
    out, lse = _flash_fwd(q, k, v, kv_mask, sm_scale, causal, block_q,
                          block_k, num_heads, interpret)
    # keep only one lane of the [BH, S, LANES] lse as the fwd→bwd residual
    # (the broadcast layout is a kernel-interface artifact; holding it in
    # HBM across the whole backward would cost 128× the needed bytes)
    return out, (q, k, v, out, lse[..., 0], kv_mask)


_flash_core.defvjp(_flash_core_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Resident form: a lane tile of heads whole in VMEM, one grid step each
# ---------------------------------------------------------------------------
# What the trainer's step runs (no key mask, a sequence of 1024). The
# kernels read q, k, v [B, S, H·D] where the projections wrote them, a
# block [S, 128] a grid step (B, H·D / 128): ONE head of 128, or TWO of
# 64 side by side in the lanes (`hp` heads of D = 128 / hp), and write
# out, dq, dk, dv the same way, so no transpose stands beside them. A
# head of the pair is told from its neighbour by ZEROS in the other's
# lanes of ONE operand of each product (q or do, masked once a q block):
# a product over all 128 lanes then sees that head alone, costs the MXU
# what a half-filled one of 64 does, and no lane tile is split. The q
# and k blocks are walked by loops INSIDE the grid step, bounded by the
# diagonal: a block above it is never visited, one wholly under it is not
# masked. The forward keeps the scores' rows on sublanes (s = q kT); the
# backward keeps them on LANES (sT = k qT), so that lse and delta are
# read as rows [1, block_q] of arrays [B, H, S] — nothing 128 wide in
# HBM — and computes s, p, dp and ds ONCE a block for dq, dk and dv.
# The q blocks, the heads of a pair and the cut block are Python's `for`,
# so at 1024 a kernel's six block bodies are ONE straight line in which
# the scheduler lays a body's exp and reductions under its neighbour's
# products: walked by `fori_loop`s (two bodies a kernel whatever S is,
# half the 1.1 s Mosaic takes to compile each of a step's 48 kernels)
# the same bodies wait for each other across the loops' edges, forward +
# backward 680 + 877 us a layer where this takes 354 + 697 (PERF.md
# section 6, PR 36). The price is that cold compile, and one that grows
# with S / block_q.

#: bytes of the resident kernels' blocks (q, k, v, out, do and three
#: outputs, two buffers each, and two float32 accumulators) a head's
#: sequence may take to stay whole in VMEM: bfloat16 at 2048 takes 10.5 MB
#: (measured resident 1.85 ms a layer where it streams in 3.06, and 7.0 s
#: of the chip's host to compile a layer's two kernels cold where the
#: streamed three take 4.7: PERF.md, PR 36), float32 at 1024 9.4; float32
#: at 2048 and bfloat16 at 4096 stream
_RESIDENT_VMEM_BUDGET = 12 << 20
#: what Mosaic is asked for: the blocks and the loops' temporaries (a
#: block of scores [512, 512] float32 is 1 MB, and a step holds several)
_RESIDENT_VMEM_LIMIT = 48 << 20
#: the resident loops' steps over q and k where the caller names none.
#: The largest measured: a step's products wait for each other, so its
#: cost has a fixed part that only a larger step spreads (forward +
#: backward a layer at [8, 1024, 16, 64]: 3.56 ms at 128 x 128, 1.63 at
#: 256 x 256, 1.04 at 512 x 512, where the backward runs at 95% of what
#: its five products cost the MXU; PERF.md, PR 36)
_RESIDENT_STEPS = (512, 512)


#: heads side by side in a lane block of the resident form, by head_dim:
#: the two the chip measured (PERF.md, PR 36). Narrower heads would pay
#: each product 128 // D times over the lanes, wider ones take blocks
#: that nothing has compiled: both stream
_RESIDENT_LANE_HEADS = {LANES: 1, LANES // 2: 2}


def _resident_heads(S: int, H: int, D: int, dtype) -> Optional[int]:
    """Heads a lane block of the resident form holds, or None where the
    form does not apply: a head_dim that is neither 128 nor 64, an odd
    count of 64, or a sequence past `_RESIDENT_VMEM_BUDGET`."""
    hp = _RESIDENT_LANE_HEADS.get(D)
    if hp is None or H % hp:
        return None
    per_pos = LANES * (16 * jnp.dtype(dtype).itemsize + 8)
    return hp if S * per_pos <= _RESIDENT_VMEM_BUDGET else None


def _walk_k_blocks(step, carry, causal, i, block_q, block_k, S):
    """`carry` through `step(k0, carry, cut)` for each k block q block
    `i` sees: a loop over the blocks wholly under the diagonal (all of
    them where nothing is causal), unmasked, then the ones it cuts, one
    by one."""
    clear = seen = S // block_k
    if causal:
        clear = (i * block_q + 1) // block_k
        seen = -(-(i + 1) * block_q // block_k)
    if clear:
        carry = jax.lax.fori_loop(
            0, clear, lambda j, c: step(
                pl.multiple_of(j * block_k, block_k), c, False), carry)
    for j in range(clear, seen):
        carry = step(j * block_k, carry, True)
    return carry


def _resident_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale,
                         causal, block_q, block_k, hp):
    S, W = q_ref.shape[1:]
    fold = _folds_scale(sm_scale, q_ref.dtype)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // (W // hp)
    for i in range(S // block_q):
        rows = slice(i * block_q, (i + 1) * block_q)
        out = None
        for h in range(hp):
            mine = None if hp == 1 else lane_head == h
            q = _scaled(q_ref[0, rows], sm_scale, fold, mine)

            def attend(k0, carry, cut, q=q, i=i):
                m_prev, l_prev, acc = carry
                k = k_ref[0, pl.ds(k0, block_k)]
                v = v_ref[0, pl.ds(k0, block_k)]
                s = _dot(q, k, _NT)               # [block_q, block_k]
                if not fold:
                    s = s * sm_scale
                if cut:
                    s = jnp.where(_under_diagonal(i * block_q, k0, s.shape,
                                                  0), s, NEG_INF)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                return (m_new,
                        l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                        acc * alpha + _dot(p.astype(v.dtype), v, _NN))

            m, l, acc = _walk_k_blocks(
                attend, (jnp.full((block_q, 1), NEG_INF, jnp.float32),
                         jnp.zeros((block_q, 1), jnp.float32),
                         jnp.zeros((block_q, W), jnp.float32)),
                causal, i, block_q, block_k, S)
            l = jnp.maximum(l, 1e-30)
            # acc holds p v of BOTH heads' values; this head's lanes kept
            out = acc / l if out is None else jnp.where(mine, acc / l, out)
            # the row statistic as a ROW: a transpose of its lane broadcast
            lse_ref[0, 0, h:h + 1, rows] = jnp.broadcast_to(
                m + jnp.log(l), (block_q, LANES)).T[:1]
        o_ref[0, rows] = out.astype(o_ref.dtype)


def _resident_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                         sm_scale, causal, block_q, block_k, hp):
    S, W = q_ref.shape[1:]
    fold = _folds_scale(sm_scale, q_ref.dtype)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // (W // hp)
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)
    D = W // hp
    for i in range(S // block_q):
        rows = slice(i * block_q, (i + 1) * block_q)
        # delta = rowsum(dO ∘ O) a head, wanted as ROWS: the product
        # turned once a q block, then a head's D sublanes summed
        do_o = (do_ref[0, rows].astype(jnp.float32)
                * o_ref[0, rows].astype(jnp.float32)).T   # [W, block_q]
        dq = None
        for h in range(hp):
            mine = None if hp == 1 else lane_head == h
            # zeros in the other head's lanes: sT and dpT see this head,
            # and dk, dv get nothing of it in the other's lanes
            q = _scaled(q_ref[0, rows], sm_scale, fold, mine)
            do = _scaled(do_ref[0, rows], 1.0, False, mine)
            lse = lse_ref[0, 0, h:h + 1, rows]            # [1, block_q]
            delta = jnp.sum(do_o[h * D:(h + 1) * D], axis=0, keepdims=True)

            def accumulate(k0, dq_h, cut, q=q, do=do, lse=lse, delta=delta,
                           i=i):
                at = pl.ds(k0, block_k)
                k = k_ref[0, at]
                sT = _dot(k, q, _NT)              # [block_k, block_q]
                if not fold:
                    sT = sT * sm_scale
                pT = jnp.exp(sT - lse)
                if cut:
                    pT = jnp.where(_under_diagonal(i * block_q, k0,
                                                   sT.shape, 1), pT, 0.0)
                dv_acc[at] += _dot(pT.astype(do.dtype), do, _NN)
                dpT = _dot(v_ref[0, at], do, _NT)
                dsT = pT * (dpT - delta)
                if not fold:
                    dsT = dsT * sm_scale
                dsT = dsT.astype(q.dtype)
                dk_acc[at] += _dot(dsT, q, _NN)
                return dq_h + _dot(dsT, k, _TN)   # [block_q, W]

            dq_h = _walk_k_blocks(
                accumulate, jnp.zeros((block_q, W), jnp.float32), causal,
                i, block_q, block_k, S)
            # dq_h holds dsT k of BOTH heads' keys; this head's lanes kept
            dq = dq_h if dq is None else jnp.where(mine, dq_h, dq)
        if fold:
            dq = dq * sm_scale
        dq_ref[0, rows] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _resident_specs(S, W, hp):
    """(a [S, W] block of q/k/v/out and their gradients [B, S, H·D], a
    block [hp, S] of the row statistics [B, H / hp, hp, S])."""
    return (pl.BlockSpec((1, S, W), lambda b, g: (b, 0, g)),
            pl.BlockSpec((1, 1, hp, S), lambda b, g: (b, g, 0, 0)))


_RESIDENT_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=_RESIDENT_VMEM_LIMIT)


# jitted and inlined, as `_paged_walk` is: the 24 layers of a program call
# the kernels with the same shapes, and each body is traced once a program
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8), inline=True)
def _resident_fwd(q, k, v, sm_scale, causal, block_q, block_k, D,
                  interpret):
    """q/k/v [B, S, H·D] -> (out [B, S, H·D], lse [B, H / hp, hp, S]):
    a lane block of `_RESIDENT_LANE_HEADS[D]` heads a grid step."""
    B, S, HD = q.shape
    hp, W = _RESIDENT_LANE_HEADS[D], LANES
    wide, rows = _resident_specs(S, W, hp)
    return pl.pallas_call(
        functools.partial(_resident_fwd_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          hp=hp),
        grid=(B, HD // W),
        in_specs=[wide, wide, wide],
        out_specs=[wide, rows],
        out_shape=[_out_struct((B, S, HD), q.dtype, q, k, v),
                   _out_struct((B, HD // W, hp, S), jnp.float32, q, k, v)],
        compiler_params=_RESIDENT_PARAMS, interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11),
                   inline=True)
def _resident_bwd(q, k, v, out, do, lse, sm_scale, causal, block_q,
                  block_k, D, interpret):
    """(dq, dk, dv) [B, S, H·D] from the forward's out and lse
    [B, H / hp, hp, S]."""
    B, S, HD = q.shape
    hp, W = _RESIDENT_LANE_HEADS[D], LANES
    wide, rows = _resident_specs(S, W, hp)
    return pl.pallas_call(
        functools.partial(_resident_bwd_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          hp=hp),
        grid=(B, HD // W),
        in_specs=[wide, wide, wide, wide, wide, rows],
        out_specs=[wide, wide, wide],
        out_shape=[_out_struct((B, S, HD), x.dtype, q, k, v, do)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((S, W), jnp.float32),    # dk
                        pltpu.VMEM((S, W), jnp.float32)],   # dv
        compiler_params=_RESIDENT_PARAMS, interpret=interpret,
    )(q, k, v, out, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _resident_core(q, k, v, sm_scale, causal, block_q, block_k, D,
                   interpret):
    return _resident_fwd(q, k, v, sm_scale, causal, block_q, block_k, D,
                         interpret)[0]


def _resident_core_fwd(q, k, v, sm_scale, causal, block_q, block_k, D,
                       interpret):
    out, lse = _resident_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                             D, interpret)
    return out, (q, k, v, out, lse)


def _resident_core_bwd(sm_scale, causal, block_q, block_k, D, interpret,
                       res, do):
    q, k, v, out, lse = res
    return _resident_bwd(q, k, v, out, do, lse, sm_scale, causal, block_q,
                         block_k, D, interpret)


_resident_core.defvjp(_resident_core_fwd, _resident_core_bwd)


# ---------------------------------------------------------------------------
# The public op
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = True,
                    mask=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention over [B, S, H, D] tensors (layout matches
    models.transformer). `mask`: optional [B, S] valid-key mask (True =
    attend), the BERT padding mask. Falls back to dense attention when S
    doesn't tile into Mosaic-legal blocks (ViT's S=197); either way the
    choice is reported through `note_traced("attention", ...)`.

    Two forms of the same algorithm, chosen from the operands alone and
    reported through `note_traced("flash", ...)`:
      resident[heads=N,q=..,k=..]  no key mask, heads that fill lane
        tiles (128 wide, or an even count of 64), a sequence whose blocks
        fit `_RESIDENT_VMEM_BUDGET` (2048 in bfloat16, 1024 in float32):
        the kernels read and write [B, S, H·D] rows, N heads a lane
        block, loops inside one grid step a block
      streamed[q=..,k=..]  everything else that tiles: [B·H, S, D]
        through a grid over (head, q block, k block)
    block_q/block_k are the steps of either form. Where the caller names
    none: resident 512 x 512, the largest measured (`_RESIDENT_STEPS`);
    streamed 512 tiles up to seq 1024 and 1024 tiles from seq 2048 up
    where they tile it. Measured on a v5e at the training cells' shape,
    [8, 1024, 16, 64] bfloat16 causal, forward + backward a layer
    (PERF.md section 6, PR 36): resident 0.35 + 0.70 ms; streamed 0.79 +
    0.70 + 0.71 (2.52 before its products took bfloat16 operands and
    its masks the cut blocks only); at [4, 2048, 16, 64] resident 1.85,
    streamed 1024-tiles 3.06, 512-tiles 3.62. 2048-wide streamed q
    tiles overflow VMEM; don't.
    """
    B, S, H, D = q.shape
    interpret = _resolve_interpret(interpret)
    mesh = _kernel_mesh(q)
    # the heads a device will hold decide the form and its steps
    ways = 1 if mesh is None else _dividing_axes(mesh, H, _HEAD_AXES)[1]
    hp = None if mask is not None else _resident_heads(
        S, H // ways, D, q.dtype)
    if hp is not None:
        auto_q, auto_k = _RESIDENT_STEPS
    else:
        # 1024 tiles only when they tile S exactly — a 512-multiple like
        # 2560 must keep 512 tiles (flash), never fall through to dense
        auto_q = auto_k = 1024 if S >= 2048 and S % 1024 == 0 else 512
    block_q = min(block_q or auto_q, S)
    block_k = min(block_k or auto_k, S)
    unaligned = (S % block_q or S % block_k
                 or (not interpret and (block_q % 8 or block_k % 8)))
    if unaligned:
        from ..models.transformer import dense_attention
        note_traced("attention", "dense")
        return dense_attention(q, k, v, mask=mask, causal=causal,
                               dtype=q.dtype)
    note_traced("attention", "flash")
    if mesh is not None:
        # each device re-enters with its own rows / heads (inside the
        # manual region _kernel_mesh is None and the kernel below runs)
        qkv = ("rows", None, "heads")   # [B, S, H·D]: 4-D is laid S-minor
        return _per_device(
            lambda q, k, v, mask: flash_attention(
                *(x.reshape(*x.shape[:2], -1, D) for x in (q, k, v)), causal,
                mask, block_q, block_k, interpret).reshape(q.shape),
            mesh, B, H, (*(x.reshape(B, S, H * D) for x in (q, k, v)), mask),
            (qkv, qkv, qkv, ("rows", None)), qkv).reshape(B, S, H, D)
    sm_scale = 1.0 / (D ** 0.5)
    if hp is not None and (interpret or not (block_q % LANES
                                             or block_k % 16)):
        # (Mosaic: the transposed scores have block_q on their lanes)
        note_traced("flash", f"resident[heads={hp},q={block_q},k={block_k}]")
        out = _resident_core(
            *(x.reshape(B, S, H * D) for x in (q, k, v)), sm_scale, causal,
            block_q, block_k, D, interpret)
        return out.reshape(B, S, H, D)
    note_traced("flash", f"streamed[q={block_q},k={block_k}]")

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    kv_mask = None
    if mask is not None:
        # sublane-broadcast [B, 8, S] f32 (Mosaic-legal 2D mask blocks)
        kv_mask = jnp.broadcast_to(
            mask.astype(jnp.float32)[:, None, :], (B, 8, S))

    out = _flash_core(to_bh(q), to_bh(k), to_bh(v), kv_mask, sm_scale,
                      causal, block_q, block_k, H, interpret)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Decode attention (single-query KV-cache step)
# ---------------------------------------------------------------------------

def _decode_kernel(cur_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, sm_scale, block_k, window=None):
    """One decode step for one (row, block of kv heads, k block): grid
    (B, KV // hb, nk), k innermost. A grid step covers `hb` kv heads at
    once — K/V blocks [hb, block_k, D], the q block [hb, G, D] with ALL
    query heads of each group (GQA runs natively — no repeated-KV
    transient anywhere) — because a step has a fixed cost of a few
    tenths of a microsecond whatever it moves: with one head a step,
    gpt2-xl's 25 heads x 16 pages x 64 rows were 25 600 steps a layer
    at 0.22 us each, all of the kernel's 5.6 ms (ledger, PR 24), for
    8 KB blocks. The heads of a block are independent: scores and p.v
    are batched matmuls over the head dim, each head's products summed
    in the order the one-head body summed them, statistics and
    accumulator float32 per head. Length-aware: k blocks past the cache
    cursor are skipped (their index_map pins to the boundary block, so
    the pipeline re-uses the already-resident block instead of
    streaming dead cache), and the boundary block masks columns beyond
    the cursor. int8 caches dequantize BLOCKWISE in VMEM (ks/vs are the
    per-position scales) — the bf16 cache transient the dense path
    materializes in HBM never exists here. The cursor vector is per-row
    ([B]): row b attends positions <= cur_ref[b], which is what lets
    the serving engine pack independent requests at unrelated
    generation depths into one compiled step.

    `v_ref` None is the page pool's block (`kv_row_width`): `k_ref` is
    one page's rows [block_k, hb * 2D], head j's K and V side by side in
    its own lane-aligned columns. Keys AND values of head j are then
    that whole column block: the query comes padded with zeros over the
    V lanes, so a score sees K alone, and p . block carries p . V in its
    V lanes (the wrapper reads those). A page is read once, nothing is
    shuffled across lanes, and the body below is the same.

    `window` (static; None: none) is a lower bound a row: row b attends
    positions cursor - window < p <= cursor. Blocks wholly behind it are
    skipped as blocks past the cursor are, and the block it cuts masks
    the columns behind it."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    cur = cur_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    live = ki * block_k <= cur
    if window is not None:
        live &= (ki + 1) * block_k > cur - window + 1

    @pl.when(live)
    def _attend():
        q = q_ref[0]                              # [hb, G, D]
        if v_ref is None:
            hb, _, W = q.shape                    # W = 2D
            # the heads' lane-aligned column blocks, stacked: whole vregs
            # under another index, [hb, block_k, 2D]. One split, because
            # a program traces this body once a layer (a slice of the ref
            # a head runs the same and traces three times the operations);
            # not reshape + swapaxes, which is a relayout in VMEM (18%
            # slower on the chip; PERF.md section 6, PR 28)
            k = jnp.stack(jnp.split(k_ref[0], hb, axis=1))
            if ks_ref is not None:
                is_k = jax.lax.broadcasted_iota(jnp.int32, k.shape, 2) \
                    < W // 2
                k = k.astype(q.dtype) * jnp.where(
                    is_k, ks_ref[0], vs_ref[0]).astype(q.dtype)
            v = k
        else:
            k = k_ref[0]                          # [hb, block_k, D]
            v = v_ref[0]
            if ks_ref is not None:
                # fused dequant: int8 cache block × per-position f32
                # scale, in the compute dtype (matches the dense oracle's
                # cast-then-scale arithmetic exactly)
                k = k.astype(q.dtype) * ks_ref[0].astype(q.dtype)
                v = v.astype(q.dtype) * vs_ref[0].astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale  # [hb, G, block_k]
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        seen = ki * block_k + cols <= cur
        if window is not None:
            seen &= ki * block_k + cols > cur - window
        s = jnp.where(seen, s, NEG_INF)

        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[:, :, :1] = m_new
        l_ref[:, :, :1] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


#: VMEM the double-buffered K and V blocks of one decode grid step may
#: take, or the walking paged kernel's two slots of pages. Mosaic's
#: scoped limit on a v5e is 16 MiB; a quarter of it for the streamed
#: blocks leaves the rest to the step's own temporaries (dequantized
#: blocks, a turn's stacked heads, scores), which are of the same order.
_KV_VMEM_BUDGET = 4 * 1024 * 1024


def decode_head_block(kv_heads: int, block_k: int, head_dim: int,
                      cache_dtype, vmem_budget: int,
                      paged: bool = False) -> int:
    """How many kv heads one grid step of the decode kernels covers: the
    largest divisor of `kv_heads` (the per-device count) whose cache
    blocks, double-buffered by the pipeline, fit `vmem_budget` bytes. A
    function of shapes and dtype alone — a block is counted as VMEM
    holds it. The contiguous cache brings a K and a V block
    [hb, block_k, head_dim], the minor dim padded to whole 128-lane
    tiles; the page pool (`paged`) ONE block [block_k, hb * 2 * head_dim]
    of a page's rows, K and V of a head side by side (`kv_row_width`),
    whose column block must be whole lane tiles unless it is the whole
    row. An int8 cache brings its two float32 scale blocks
    [hb, block_k, 1], which pad to a lane tile a position. gpt2-xl's
    page block (25 heads, 64 positions of 2 x 64 bfloat16) takes 0.8 MB:
    all 25 heads in one step."""
    dtype = jnp.dtype(cache_dtype)
    if paged:
        per_head = 2 * block_k * 2 * head_dim * dtype.itemsize
    else:                                   # K and V, two buffers each
        per_head = 4 * block_k * -(-head_dim // LANES) * LANES \
            * dtype.itemsize
    if dtype == jnp.int8:
        per_head += 4 * block_k * LANES * 4
    legal = [d for d in range(1, kv_heads + 1) if kv_heads % d == 0
             and (not paged or d == kv_heads
                  or d * 2 * head_dim % LANES == 0)]
    fit = [d for d in legal if d <= vmem_budget // per_head]
    return max(fit) if fit else min(legal)


def _decode_call(name, q4, k, v, k_scale, v_scale, prefetch, nk, kv_index,
                 block_k, interpret, sm_scale=None, window=None):
    """The pallas_call of the decode kernels that are a grid over k
    blocks: the contiguous cache's (`decode_attention`) and the int8 page
    pool's (`_paged_grid_call`; an unquantised pool is walked,
    `_paged_walk_call`, and shares nothing with this). The `v is None`
    branches here and in `_decode_kernel` live for that int8 pool alone,
    until its scale planes have a walked form (ROADMAP queue 3, item
    12f). q4 is [B, KV, G, D];
    `k`/`v` (and the int8 scales, given a trailing unit dim here) are
    blocked (1, hb, block_k, ·) at `kv_index(b, h, ki, *prefetch_refs)` —
    the contiguous cache and the page pool differ in that index map, in
    what they prefetch (`prefetch[0]` is the [B] cursor vector) and in
    the form of the cache. `v` None is the page pool: `k` holds rows
    [NP, block_k, KV * 2D], blocked (1, block_k, hb * 2D) at the page
    `kv_index` names and column block h; the query is padded with zeros
    over each head's V lanes and the output read from them
    (`_decode_kernel`).
    Reports `name[hb=..]` as the traced decode implementation, so a
    headline says how many heads a grid step took. `sm_scale` None is
    1 / sqrt(D); `window` is `_decode_kernel`'s."""
    B, KV, G, D = q4.shape
    paged = v is None
    hb = decode_head_block(KV, block_k, D, k.dtype, _KV_VMEM_BUDGET, paged)
    note_traced("decode", f"{name}[hb={hb}]")
    quantized = k_scale is not None
    n_pre = len(prefetch)
    W = 2 * D if paged else D           # columns of a head's q/out block

    def kv_spec(minor):
        return pl.BlockSpec((1, hb, block_k, minor), kv_index)

    qo_spec = pl.BlockSpec((1, hb, G, W),
                           lambda b, h, ki, *pre: (b, h, 0, 0))
    if paged:
        q4 = jnp.concatenate([q4, jnp.zeros_like(q4)], -1)
        in_specs = [qo_spec, pl.BlockSpec(
            (1, block_k, hb * W),
            lambda *idx: (kv_index(*idx)[0], 0, idx[1]))]
        args = [q4, k]
    else:
        in_specs = [qo_spec, kv_spec(D), kv_spec(D)]
        args = [q4, k, v]
    if quantized:
        # [.., block_k] → [.., block_k, 1]: a trailing unit lane dim makes
        # the scale block Mosaic-legal (last dim equal to the array dim)
        in_specs += [kv_spec(1), kv_spec(1)]
        args += [k_scale[..., None], v_scale[..., None]]

    def kern(*refs):
        # prefetch refs, q, the cache block(s), the scales when quantized,
        # out, scratch
        q_ref, k_ref, *rest = refs[n_pre:]
        v_ref = None if paged else rest.pop(0)
        ks_ref, vs_ref = ((rest.pop(0), rest.pop(0)) if quantized
                          else (None, None))
        _decode_kernel(refs[0], q_ref, k_ref, v_ref, ks_ref, vs_ref, *rest,
                       sm_scale=sm_scale or 1.0 / (D ** 0.5),
                       block_k=block_k, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(B, KV // hb, nk),
        in_specs=in_specs,
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((hb, G, W), jnp.float32),      # acc
            pltpu.VMEM((hb, G, LANES), jnp.float32),  # running max m
            pltpu.VMEM((hb, G, LANES), jnp.float32),  # running sum l
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=_out_struct((B, KV, G, W), q4.dtype, *args),
        interpret=interpret,
    )(*prefetch, *args)
    return out[..., W - D:].reshape(B, KV * G, D)


def decode_block_k(max_len: int, block_k: Optional[int] = None) -> int:
    """The k-tile the decode kernel will use for a cache of `max_len`
    positions (callers gate on `max_len % decode_block_k(...) == 0`).
    128 default: small tiles keep the length-aware skip granular — at
    prompt=128/new=128 the second 128-tile streams only after the cache
    actually grows past it, which is where the halved bytes/step comes
    from — while staying Mosaic-legal for bf16 (16, 128) AND int8
    (32, 128) cache tilings."""
    return min(block_k or 128, max_len)


def decode_attention(q, k_cache, v_cache, cache_index,
                     k_scale=None, v_scale=None,
                     block_k: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Single-step KV-cache attention — the decode fast path.

    q            [B, H, D]      this step's queries (RoPE already applied)
    k_cache/v_cache [B, KV, L, D]  the kv-head-major cache; bf16/f32, or
                 int8 when k_scale/v_scale are given
    cache_index  scalar int32, or int32 [B] of per-row cursors: absolute
                 position of this step's token; row b attends cache
                 positions <= cursor(b) and never streams the unfilled
                 suffix. The scalar form is the lockstep `generate()`
                 path; the vector form is the serving engine's slot
                 cursors, where every row sits at its own depth
    k_scale/v_scale [B, KV, L] f32  int8 per-(position, head) scales

    Returns [B, H, D]. GQA (H > KV) is native: each kv head serves its
    whole query group from one cache block — the [B, H, L, D] repeated
    transient of the dense path never materializes. The cache length L
    must tile by `decode_block_k(L, block_k)`; callers fall back to the
    dense oracle otherwise. Grid (B, KV // hb, L // block_k): a step
    takes `decode_head_block` kv heads of one row's k-tile, `hb` strided
    chunks of the cache in one block.
    """
    B, H, D = q.shape
    _, KV, L, _ = k_cache.shape
    if H % KV:
        raise ValueError(f"H={H} must be a multiple of KV={KV}")
    bk = decode_block_k(L, block_k)
    if L % bk:
        raise ValueError(f"cache len {L} does not tile by block_k={bk}; "
                         f"use the dense decode path")
    interpret = _resolve_interpret(interpret)
    nk = L // bk
    cur = jnp.asarray(cache_index, jnp.int32)
    if cur.ndim == 0:
        cur = jnp.broadcast_to(cur[None], (B,))
    elif cur.shape != (B,):
        raise ValueError(f"cache_index must be scalar or [B]={B}, "
                         f"got shape {cur.shape}")
    mesh = _kernel_mesh(q)
    if mesh is not None:
        # per device, as in flash_attention; H follows KV (query head h
        # belongs to kv head h // G, both split in contiguous chunks)
        cache, scale = ("rows", "heads", None, None), ("rows", "heads", None)
        return _per_device(
            functools.partial(decode_attention, block_k=block_k,
                              interpret=interpret),
            mesh, B, KV, (q, k_cache, v_cache, cur, k_scale, v_scale),
            (scale, cache, cache, ("rows",), scale, scale), scale)

    def kv_index(b, h, ki, cur_ref):
        # k-tiles past the cursor re-use the boundary tile
        return (b, h, jnp.minimum(ki, jnp.minimum(cur_ref[b] // bk, nk - 1)),
                0)

    # query head h ↔ kv head h // G, matching jnp.repeat(kv, G, axis)
    return _decode_call("pallas", q.reshape(B, KV, H // KV, D), k_cache,
                        v_cache, k_scale, v_scale, (cur,), nk, kv_index, bk,
                        interpret)


def kv_row_width(kv_heads: int, head_dim: int) -> int:
    """Columns of one position's row in the per-head page pool
    [NP, ps, kv_heads * 2 * head_dim]: head h owns the column block
    [2D*h, 2D*h + 2D), its K in the first D lanes and its V in the rest
    (`pack_kv_rows`). With D >= 64 every head's block is whole 128-lane
    tiles (gpt2-xl: 25 x 128 = 3200, no padding), so a row-major
    bfloat16 pool is what the chip keeps resident, what a flat row
    scatter writes and what the kernel's block reads: one layout, the
    donated pool aliased through a step. A pool [NP, KV, ps, 64] half
    fills its lane tiles; the compiler kept it pages-minor and copied
    all of it into the scatter's layout, the kernel's and back, for K
    and for V, in every layer of every step (87% of a gpt2-xl step;
    PERF.md section 6, PR 28)."""
    return kv_heads * 2 * head_dim


def pack_kv_rows(k, v):
    """[..., KV, D] keys and values of some positions -> their pool rows
    [..., KV * 2D] (`kv_row_width`)."""
    return jnp.concatenate([k, v], -1).reshape(k.shape[:-2] + (-1,))


def paged_pages_per_turn(nblk: int, page_bytes: int, ps: int,
                         window: Optional[int] = None) -> int:
    """Pages one turn of the walking paged kernel takes; its two slots
    of that many pages, one attended while the other fills, share
    `_KV_VMEM_BUDGET` (the grid form's two buffers of a block do). From
    half of what fits up to all of it, the count that leaves least of
    the last turn empty when a row has live all it can — the table's
    `nblk` pages, or the pages a `window` touches — and the larger on a
    tie: a ring of 9 pages walks 3 + 3 + 3 and gpt2-xl's table of 16
    walks 4 a turn. Until PR 49 the rule's reason was that a turn's
    empty tail was copied all the same; a turn now copies its live pages
    alone, and the counts stand because the chip still prefers them: rows
    drawn as the cells hold them take 272.9 us a call at gpt2-xl's 4 a turn
    against 283.5 / 275.0 / 287.6 at 2 / 5 / 8, 592.3 at Falcon-H1's 16
    against 607.6 at 8, 1 680.0 at Qwen3-Next's 16 against 1 695.2 at 8
    (PERF.md, PR 49). Wide turns pay the turn's own work (a fifth of a
    microsecond) once for several pages; from 4 a turn the copies are
    what a FULL turn costs, at nine tenths of the HBM peak. A function
    of shapes and dtype alone."""
    live = nblk if window is None else min(nblk, (window + ps - 2) // ps + 1)
    cap = max(1, min(live, _KV_VMEM_BUDGET // (2 * page_bytes)))
    return min(range(-(-cap // 2), cap + 1), key=lambda n: (-live % n, -n))


def _live_span(cursor, ps: int, nblk: int, window: Optional[int]):
    """The first and last LIVE logical page of a row at `cursor` on a
    table of `nblk` pages of `ps`: last = min(cursor // ps, nblk - 1), a
    cursor past the logical cache attending the whole table; first = 0,
    or with `window` the page that holds cursor - window + 1."""
    last = jnp.minimum(cursor // ps, nblk - 1)
    if window is None:
        return 0, last
    return jnp.minimum(jnp.maximum(cursor - window + 1, 0) // ps, last), last


def _turn_pages(first, last, turn, pages: int):
    """Turn `turn` of a walk of logical pages first .. last, `pages` a
    turn: its first page and how many of its pages are live, 1 .. `pages`
    in each of the walk's (last - first) // pages + 1 turns. What
    `_paged_walk_kernel` copies in a turn and what it waits for: the one
    place that says so, for both."""
    page0 = first + turn * pages
    return page0, jnp.minimum(pages, last - page0 + 1)


def _paged_walk_kernel(cur_ref, pt_ref, q_ref, pool_ref, o_ref, buf_ref,
                       sem_ref, slot_ref, acc_ref, m_ref, l_ref, *,
                       sm_scale, ps, nblk, pages, hb, k_lanes, window):
    """One decode step for one (row, block of `hb` kv heads) over the
    per-head page pool (`kv_row_width`): grid (B, KV // hb), in order.
    The form of `_mla_decode_kernel`: the pool stays in HBM; the row's
    LIVE pages — logical pages first .. last, last = min(cursor // ps,
    nblk - 1), first = 0 or with `window` the page that holds cursor -
    window + 1, and no others — are walked `pages` at a time, each turn's
    pages (their `hb` heads' columns) copied into one of two VMEM slots
    while the other slot's are attended. A grid step's last turn starts
    the next grid step's first copies, so only the call's first step
    waits for a page; `slot_ref` carries which slot they went to.

    A turn: scores [hb, G, pages * ps] of every query head of a group
    against the turn's rows, an online softmax across turns, statistics
    and accumulator float32 a head, the products in the pool's type
    summed in float32 — `_decode_kernel`'s mathematics. `k_lanes` (a
    head's K and V halves are each whole 128-lane tiles) contracts the
    scores over the K lanes and p . V over the V lanes alone: no padded
    query and half the MXU work of the other form (7% of a call at one
    page a turn, nothing measurable from 4, where the copies bound it;
    PERF.md, PR 32), in which K and V share a lane tile, the query comes
    padded with zeros over the V lanes and the output is read from them
    (splitting a lane tile is a relayout: 18% slower, PERF.md PR 28).

    A turn does work for its LIVE pages only (`_turn_pages`: the same
    count where its copies are started, by this grid step or by the one
    before it, and where they are waited for): nothing a dead table entry
    points at is read, and a row's last turn copies no page twice. A FULL
    turn is attended as one straight block `pages` pages wide; a short one
    at the narrowest of the widths 1, 2, 4, .. pages that holds its live
    ones, so a free row's one page costs one page's copy and one page's
    products (PERF.md, PR 49). What lies in a slot past a turn's live
    pages is whatever was there: the mask over positions keeps it out of
    the scores, and so that 0 x nan cannot reach p . V both slots are
    zeroed once a call, before its first copy; after that a slot holds
    zeros or rows some turn copied. A cursor past the logical cache (a
    retiring row's post-EOS step) attends the whole table (inside its
    `window`, if any), as the grid form's clamp does.

    A turn's copies are started, and waited for, in a loop over its
    pages and not one by one in Python (`_mla_decode_kernel`)."""
    b, h = pl.program_id(0), pl.program_id(1)
    nb, nh = pl.num_programs(0), pl.num_programs(1)
    cols = buf_ref.shape[-1]                    # hb * 2D
    # what a turn is attended at: 1, 2, 4, .. pages, and `pages`
    widths = sorted({min(1 << k, pages)
                     for k in range(pages.bit_length() + 1)})

    def span(row):
        return _live_span(cur_ref[row], ps, nblk, window)

    def copy(page, head_block, slot, k):
        src = pool_ref.at[page]
        if cols != pool_ref.shape[-1]:
            src = src.at[:, pl.ds(pl.multiple_of(head_block * cols, LANES),
                                  cols)]
        return pltpu.make_async_copy(src, buf_ref.at[slot, k],
                                     sem_ref.at[slot])

    def start(row, head_block, turn, slot):
        page0, n = _turn_pages(*span(row), turn, pages)

        def one(k, _):
            copy(pt_ref[row, page0 + k], head_block, slot, k).start()
        jax.lax.fori_loop(0, n, one, None)

    def wait(slot, n):
        def one(k, _):
            copy(0, 0, slot, k).wait()  # a wait reads the size, not the page
        jax.lax.fori_loop(0, n, one, None)

    first, last = span(b)
    turns = (last - first) // pages + 1
    cur_raw = cur_ref[b]
    cur = jnp.minimum(cur_raw, nblk * ps - 1)
    step = b * nh + h
    nxt = jnp.minimum(step + 1, nb * nh - 1)

    @pl.when(step == 0)
    def _first_step():
        # a slot's dead part is masked out of the scores, and 0 x nan is
        # nan in p . V: what no copy ever wrote must be finite
        buf_ref[:] = jnp.zeros_like(buf_ref)
        slot_ref[0] = 0
        start(b, h, 0, 0)

    first_slot = slot_ref[0]
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    def attend(rows, page0):
        """The online softmax's update over `rows` [n * ps, cols], the
        rows of logical pages page0, page0 + 1, .."""
        q = q_ref[0]                                      # [hb, G, D or 2D]
        # the heads' lane-aligned column blocks, stacked: whole vregs
        # under another index (`_decode_kernel`)
        if k_lanes:
            halves = jnp.split(rows, 2 * hb, axis=1)
            k, v = jnp.stack(halves[0::2]), jnp.stack(halves[1::2])
        else:
            k = v = jnp.stack(jnp.split(rows, hb, axis=1))
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale  # [hb, G, n * ps]
        pos = page0 * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        seen = pos <= cur
        if window is not None:
            seen &= pos > cur_raw - window
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, :, :1] = l_ref[:, :, :1] * alpha + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[:, :, :1] = m_new

    def turn(i, _):
        slot = (first_slot + i) % 2
        more = i + 1 < turns

        @pl.when(more | (step + 1 < nb * nh))
        def _next():    # this step's next turn, or the next step's first
            start(jnp.where(more, b, nxt // nh), jnp.where(more, h, nxt % nh),
                  jnp.where(more, i + 1, 0), 1 - slot)

        page0, n = _turn_pages(first, last, i, pages)
        wait(slot, n)

        for lo, w in zip([0] + widths, widths):   # the narrowest that holds n
            @pl.when((lo < n) & (n <= w))
            def _at(w=w):
                attend(buf_ref[slot, :w].reshape(w * ps, cols), page0)

    jax.lax.fori_loop(0, turns, turn, None)
    slot_ref[0] = (first_slot + turns) % 2
    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, :, :1], 1e-30)
                ).astype(o_ref.dtype)


def _paged_walk_call(q4, pool, cur, pt, sm_scale, window, interpret,
                     pages: Optional[int] = None,
                     k_lanes: Optional[bool] = None):
    """The walking kernel over q4 [B, KV, G, D] and the pool [NP, ps,
    KV * 2D], cursors [B] and page table [B, nblk]: settles the sizes and
    reports `pallas_paged[live,pages=N,hb=M]` as the traced decode
    implementation — the form, the pages a turn and the kv heads a grid
    step took. `pages` None is `paged_pages_per_turn` and `k_lanes` None
    the form the head size gives (the microbenchmark passes its own, to
    price each)."""
    KV, D = q4.shape[1], q4.shape[3]
    ps = pool.shape[1]
    # heads a grid step: all of them where one page, two slots deep, fits
    hb = decode_head_block(KV, ps, D, pool.dtype, _KV_VMEM_BUDGET, paged=True)
    if pages is None:
        pages = paged_pages_per_turn(
            pt.shape[1], ps * hb * 2 * D * pool.dtype.itemsize, ps, window)
    if k_lanes is None:
        k_lanes = D % LANES == 0
    note_traced("decode", f"pallas_paged[live,pages={pages},hb={hb}]")
    return _paged_walk(q4, pool, cur, pt, sm_scale or 1.0 / (D ** 0.5),
                       window, interpret, pages, hb, k_lanes)


# jitted, and inlined where it is called: a model's layers call the kernel
# with the same shapes, and the kernel's body is traced once a program and
# not once a layer (48 times for gpt2-xl). A body traced a layer cost the
# benchmark's process 0.3 s a call, +10 s of Phi-4-mini-flash's set-up
# (PERF.md, PR 32). Inlined, the call keeps the caller's named scope in
# its instruction's name (`yoco.attend.3`), which the trace readers match.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9), inline=True)
def _paged_walk(q4, pool, cur, pt, sm_scale, window, interpret, pages, hb,
                k_lanes):
    """`_paged_walk_kernel`'s pallas_call: cursors and page table as
    scalar prefetch, the pool an HBM operand, two slots of `pages` pages
    of `hb` heads' columns."""
    B, KV, G, D = q4.shape
    ps, nblk = pool.shape[1], pt.shape[1]
    if not k_lanes:
        q4 = jnp.concatenate([q4, jnp.zeros_like(q4)], -1)
    Wq = q4.shape[-1]

    qo_spec = pl.BlockSpec((1, hb, G, Wq), lambda b, h, *pre: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV // hb),
        in_specs=[qo_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pages, ps, hb * 2 * D), pool.dtype),  # in flight
            pltpu.SemaphoreType.DMA((2,)),                # one a slot
            pltpu.SMEM((1,), jnp.int32),            # the next step's slot
            pltpu.VMEM((hb, G, Wq), jnp.float32),     # acc
            pltpu.VMEM((hb, G, LANES), jnp.float32),  # running max m
            pltpu.VMEM((hb, G, LANES), jnp.float32),  # running sum l
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_walk_kernel, sm_scale=sm_scale, ps=ps,
                          nblk=nblk, pages=pages, hb=hb, k_lanes=k_lanes,
                          window=window),
        grid_spec=grid_spec,
        out_shape=_out_struct((B, KV, G, Wq), q4.dtype, q4, pool),
        # steps in order: a step's last turn fetches for the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(cur, pt, q4, pool)
    return out[..., Wq - D:].reshape(B, KV * G, D)


def paged_decode_attention(q, pages, cache_index, page_table,
                           k_scale=None, v_scale=None,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None,
                           sm_scale: Optional[float] = None):
    """`decode_attention` over a PAGED cache — the serving engine's
    block-table layout (transformer.py decode_page_size).

    q            [B, H, D]          this step's queries (RoPE applied)
    pages        [NP, ps, KV * 2D]  the global page POOL: NP fixed pages
                 of ps positions, one row a position with each head's K
                 and V side by side (`kv_row_width`); bf16/f32, or int8
                 with scales
    cache_index  int32 [B] per-row cursors (same contract as the
                 contiguous kernel: row b attends positions <= cursor(b))
    page_table   int32 [B, nblk]: row b's logical KV block j lives in
                 physical page page_table[b, j]. nblk * ps is the logical
                 cache length; unallocated entries point at the trash
                 page (their positions sit beyond the cursor, so the
                 column mask already excludes them)
    k_scale/v_scale [NP, KV, ps] f32  int8 per-(page-slot, head) scales
    window       static: row b attends cursor(b) - window < p <= cursor(b)
                 and pages wholly behind that are neither fetched nor
                 scored. None: no lower bound, and the program lowered is
                 the one without this argument
    sm_scale     static: the scores' scale; None is 1 / sqrt(D)

    An unquantised pool is WALKED (`_paged_walk_kernel`): grid
    (B, KV // hb), the pool an HBM operand, a row's live pages copied
    `paged_pages_per_turn` a turn into two VMEM slots, a row's last turn
    its live pages alone (PR 49). Nothing is spent on the table's dead
    entries nor on a turn's, so a call's time follows the contexts and
    not the table's length, and a turn's work is paid once for several
    pages: the grid form's step a (row, page) cost 0.105 us dead or live
    and a live page 0.61 us more, of which the copy is 0.40 (PERF.md,
    PR 32); a gpt2-xl row of one live page costs 1.8 us where a turn of
    four copies cost 3.0 (PERF.md, PR 49).

    An int8 pool (`k_scale` given) stays on the grid form, the
    contiguous kernel's body with another index map (`_decode_call`,
    `_decode_kernel` with `v_ref` None): grid (B, KV // hb, nblk), one
    step the columns of `hb` kv heads of one page, the page table
    resolving which PHYSICAL page streams for logical block ki, blocks
    past the cursor (or behind the window) pinned to the boundary
    block's page. No cell runs it; its scale planes have no walked form
    yet (ROADMAP queue 3, item 12f).
    """
    B, H, D = q.shape
    NP, ps, W = pages.shape
    KV = W // (2 * D)
    if W != kv_row_width(KV, D) or H % KV:
        raise ValueError(f"pool rows of {W} columns do not hold K and V "
                         f"of a divisor of H={H} heads of D={D}")
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"page_table must be [B={B}, nblk], got shape "
                         f"{page_table.shape}")
    nblk = page_table.shape[1]
    interpret = _resolve_interpret(interpret)
    cur = jnp.asarray(cache_index, jnp.int32)
    if cur.shape != (B,):
        raise ValueError(f"cache_index must be [B]={B} per-row cursors, "
                         f"got shape {cur.shape}")
    pt = jnp.asarray(page_table, jnp.int32)
    mesh = _kernel_mesh(q)
    if mesh is not None:
        # rows and kv heads split as in decode_attention; the page POOL is
        # global state every row may point into, so it is split over the
        # kv heads' columns only
        pool, scale = (None, None, "heads"), (None, "heads", None)
        out = ("rows", "heads", None)
        return _per_device(
            functools.partial(paged_decode_attention, interpret=interpret,
                              window=window, sm_scale=sm_scale),
            mesh, B, KV, (q, pages, cur, pt, k_scale, v_scale),
            (out, pool, ("rows",), ("rows", None), scale, scale), out)

    q4 = q.reshape(B, KV, H // KV, D)
    if k_scale is None:
        return _paged_walk_call(q4, pages, cur, pt, sm_scale, window,
                                interpret)
    return _paged_grid_call(q4, pages, cur, pt, k_scale, v_scale, sm_scale,
                            window, interpret)


def _paged_grid_call(q4, pages, cur, pt, k_scale, v_scale, sm_scale, window,
                     interpret):
    """The grid form over the page pool: `_decode_call` with the page
    table's index map. The int8 pool's path; the microbenchmark
    (`scripts/paged_decode_microbench.py`) also times it unquantised
    beside the walk."""
    ps, nblk = pages.shape[1], pt.shape[1]

    def kv_index(b, h, ki, cur_ref, pt_ref):
        # physical page for logical block ki, clamped to the row's
        # boundary block (blocks past the cursor re-use its page — the
        # kernel skips their compute anyway)
        last = jnp.minimum(cur_ref[b] // ps, nblk - 1)
        ki = jnp.minimum(ki, last)
        if window is not None:
            # and pages behind the window re-use its first page
            ki = jnp.maximum(ki, jnp.minimum(
                jnp.maximum(cur_ref[b] - window + 1, 0) // ps, last))
        return (pt_ref[b, ki], h, 0, 0)

    return _decode_call("pallas_paged", q4, pages, None, k_scale, v_scale,
                        (cur, pt), nblk, kv_index, ps, interpret, sm_scale,
                        window)


# ---------------------------------------------------------------------------
# Latent (MLA) paged attention: one cached row a position, shared by all heads
# ---------------------------------------------------------------------------
# A latent page holds, per position, `c_kv` (after its norm and scale,
# `rank` values) beside the rotated shared key part `k_pe`, the row padded
# with zeros to whole 128-lane tiles: [NP, ps, W], W = `mla_row_width`.
# With the key/value up-projection absorbed into the query and the
# output, every head attends the SAME rows: an MQA of group H whose keys
# are the whole row and whose values are its first `rank` columns — so a
# page is read once, for scores and for p.v alike.

def einsum_f32(spec: str, a, b):
    """The float32 product of two operands in their own (bfloat16) type:
    what the MXU gives with `preferred_element_type`. XLA's CPU backend
    has no bf16 x bf16 = f32 dot, so there the operands are widened first
    — the same products, summed in float32 either way."""
    if jax.default_backend() == "cpu":
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def mla_row_width(rank: int, rope: int) -> int:
    """Columns of a latent cache row: `rank + rope` rounded up to whole
    128-lane tiles (576 -> 640). A row-major bfloat16 array on the chip
    is tiled (8, 128)(2, 1): a 576-wide row occupies five lane tiles
    whether the shape says so or not, and the decode kernel's page
    copies move whole tiles: Mosaic refuses one out of a pool whose
    shape says 576 (`tests/test_tpu_compile.py`). The pad columns are
    zeros in the cache and in the query, and add nothing to a score."""
    return -(-(rank + rope) // LANES) * LANES


def mla_paged_attend(q, pool, positions, page_table, rank, sm_scale,
                     pages_a_turn: int = 1):
    """Absorbed latent attention over the page pool in plain jax: the
    dense form the kernel is compared with, the path of every
    multi-token call (prefill chunks) and of a decode step that asked
    for no kernel.

    q [B, S, H, W]: per head, q_nope through the absorbed key projection
    (`rank` columns), the rotated q_pe, zeros up to W; pool [NP, ps, W];
    positions [B, S] absolute (a position >= nblk * ps marks a query
    whose result nobody reads); page_table [B, nblk]. Returns
    u [B, S, H, rank] = softmax(q . row) . c_kv, before the absorbed
    value projection.

    Pages are walked in logical order with an online softmax, up to the
    furthest block any live query reaches — a chunk of a 200-token
    prompt walks four pages, not the table's hundred — so neither scores
    nor gathered rows over the whole logical length ever exist. A turn
    takes `pages_a_turn` pages (a divisor of the table's length): the
    float32 accumulator passes through memory once a turn, and a turn's
    scores are that many pages wide."""
    B, S, H, W = q.shape
    NP, ps, _ = pool.shape
    nblk = page_table.shape[1]
    if nblk % pages_a_turn:
        raise ValueError(f"a turn of {pages_a_turn} pages does not divide "
                         f"a table of {nblk}")
    width = pages_a_turn * ps
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B, S))
    blocks = jnp.max(jnp.where(pos < nblk * ps, pos // width + 1, 0))
    pt = jnp.asarray(page_table, jnp.int32)

    def attend(q, qpos, pt):
        """q [G, S*H, W], qpos [G, S*H], pt [G, nblk] -> [G, S*H, rank]."""
        def body(j, carry):
            m, l, acc = carry
            if pages_a_turn == 1:
                page = pool[pt[:, j]]                         # [G, ps, W]
            else:
                page = pool[jax.lax.dynamic_slice_in_dim(
                    pt, j * pages_a_turn, pages_a_turn, 1)].reshape(
                    pt.shape[0], width, W)
            s = einsum_f32("bqw,bkw->bqk", q, page) * sm_scale
            cols = j * width + jnp.arange(width, dtype=jnp.int32)
            s = jnp.where(cols[None, None, :] <= qpos[:, :, None], s,
                          NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(-1, keepdims=True)
            acc = acc * alpha + einsum_f32(
                "bqk,bkr->bqr", p.astype(pool.dtype), page[..., :rank])
            return m_new, l, acc

        G, Q = qpos.shape
        init = (jnp.full((G, Q, 1), NEG_INF, jnp.float32),
                jnp.zeros((G, Q, 1), jnp.float32),
                jnp.zeros((G, Q, rank), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    q = q.reshape(B, S * H, W)
    qpos = jnp.repeat(pos, H, axis=1)                         # [B, S*H]
    # the float32 accumulator is [rows, S*H, rank]: a 128-token chunk of 64
    # rows holds a gigabyte of it at 64 heads and two at 128, so rows go
    # through in groups of `_MLA_QUERY_ROWS` queries (8 rows there, 4 here)
    G = max(1, _MLA_QUERY_ROWS // (S * H))
    if B > G and B % G == 0:
        grouped = lambda x: x.reshape((B // G, G) + x.shape[1:])  # noqa: E731
        u = jax.lax.map(lambda a: attend(*a),
                        (grouped(q), grouped(qpos), grouped(pt)))
    else:
        u = attend(q, qpos, pt)
    return u.reshape(B, S, H, rank)


#: queries (rows x positions x heads) one pass of `mla_paged_attend` holds
_MLA_QUERY_ROWS = 65536


#: VMEM the latent decode kernel gives its pages in flight: two slots of
#: `mla_pages_per_turn` pages each, one attended while the other fills
_MLA_PAGES_VMEM_BUDGET = 1280 * 1024


#: a table of this many pages or more says contexts run to thousands of
#: positions: its turns take a slot twice as large
_MLA_LONG_TABLE = 128


def mla_pages_per_turn(nblk: int, page_bytes: int) -> int:
    """Pages one turn of the latent decode kernel's loop takes: what fits
    a slot of `_MLA_PAGES_VMEM_BUDGET`, at least one, at most the table.
    More pages a turn give the loop fewer turns, each with its fixed
    cost (the loop's edge, the MXU filling and draining); a turn's pages
    past the row's last live one are fetched again from that page and
    attended under the mask, so a wide turn wastes bytes and products on
    short contexts (PERF.md, PR 30: eight 80 KB pages at LongCat-Flash's
    widths, whose rows start at 64 positions). Under a long table
    (`_MLA_LONG_TABLE`) that tail is a small share of a row's pages and
    the slot is doubled (PERF.md, PR 39 and PR 40: sixteen pages a turn
    against eight and thirty-two, for one chain and for two)."""
    slot = _MLA_PAGES_VMEM_BUDGET * (2 if nblk >= _MLA_LONG_TABLE else 1)
    return max(1, min(nblk, slot // (2 * page_bytes)))


#: chains a turn is cut into from `_MLA_CHAINS_HEADS` heads on: there a
#: page's products cost the MXU what its copy costs HBM (a page of 64 rows
#: of 640 bfloat16 columns: 0.100 us of HBM; 2 x H x (640 + 512) x 64
#: products: 0.096 us of the MXU at 128 heads, 0.048 at 64)
_MLA_CHAINS, _MLA_CHAINS_HEADS = 2, 128


def mla_chains(H: int, pages: int) -> int:
    """Chains of score product -> softmax -> p.v product that one turn of
    the latent decode kernel keeps in flight, from the call's own shapes:
    `_MLA_CHAINS` of `pages / _MLA_CHAINS` pages each from
    `_MLA_CHAINS_HEADS` heads on (where the turn's pages divide so), else
    one. In one chain the MXU stands idle between the score product and
    p.v while the row maximum crosses the lanes and the first
    exponentials are taken: a fifth of a turn's instructions at 128
    heads, where the MXU's work a page is as long as the page's copy and
    nothing hides it (11% of the kernel's time on the chip: PERF.md, PR
    40). At 64 heads the copies bound the turn either way, and two chains
    measure what one does."""
    if H >= _MLA_CHAINS_HEADS and pages % _MLA_CHAINS == 0:
        return _MLA_CHAINS
    return 1


def _mla_decode_kernel(cur_ref, pt_ref, q_ref, pool_ref, o_ref, buf_ref,
                       sem_ref, slot_ref, acc_ref, m_ref, l_ref, *,
                       sm_scale, ps, rank, nblk, pages, chains):
    """One decode step for one row: grid (B,), rows in order. The pool
    stays in HBM; the row's LIVE pages — logical pages 0 .. min(cursor //
    ps, nblk - 1), and no others — are walked `pages` at a time through
    two VMEM slots: all H heads share a turn's pages, scores against
    whole rows, p.v against their first `rank` columns, an online softmax
    in float32 across chains and turns.

    A turn is ONE straight-line block, which the compiler schedules as a
    whole: a branch or a loop inside it would end the block, and the MXU
    would stand idle on either side (the loop that started a turn's
    copies page by page and the loop that waited for them were a quarter
    of the parent's turn: PERF.md, PR 40). So:

    - the turn waits ONCE, for its whole slot (a wait reads a size);
    - it is cut into `chains` chains of `pages / chains` pages, each an
      online-softmax update of its own, and chain c + 1's score product
      is issued BEFORE chain c's softmax: the MXU multiplies one chain's
      operands while the vector units take the other's maximum,
      exponentials and sums (`mla_chains`);
    - behind a chain's update, its share of the slot is filled again for
      the turn AFTER THE NEXT (the call's turns in order, across rows):
      a copy has a whole turn to land, the copy engine never runs dry,
      and the starts' address arithmetic runs on the scalar unit under
      the products. The starts are a loop unrolled where it is lowered,
      so the text holds one descriptor a chain (a descriptor a page cost
      a serving process 0.3 s of tracing a call: PERF.md, PR 30), and
      they are unconditional: the call's last two turns fetch the last
      row's first pages once more, and the last row waits for them
      before it leaves. Only the call's first row starts copies and then
      waits for them; `slot_ref` carries a row's first slot to it.

    A turn's pages past the last live one are copied from that page
    again and masked: nothing a dead table entry points at is read. A
    cursor past the logical cache (a retiring row's post-EOS step)
    attends the whole table, as the dense form's clamp does."""
    b, nb = pl.program_id(0), pl.num_programs(0)
    width = pages * ps
    share = pages // chains

    def last_live(row):
        return jnp.minimum(cur_ref[row] // ps, nblk - 1)

    def turns_of(row):
        return last_live(row) // pages + 1

    def after(row, turn):
        """The turn behind (row, turn) in the call's order: the last
        row's last turn is followed by that row's first."""
        more = turn + 1 < turns_of(row)
        return (jnp.where(more, row, jnp.minimum(row + 1, nb - 1)),
                jnp.where(more, turn + 1, 0))

    def start(row, turn, slot, first=0, count=pages, unroll=False):
        last = last_live(row)

        def one(k, _):
            pltpu.make_async_copy(
                pool_ref.at[pt_ref[row, jnp.minimum(turn * pages + k, last)]],
                buf_ref.at[slot, k], sem_ref.at[slot]).start()
        jax.lax.fori_loop(first, first + count, one, None, unroll=unroll)

    def wait(slot):         # the slot's bytes at once, whatever filled it
        pltpu.make_async_copy(buf_ref.at[slot], buf_ref.at[slot],
                              sem_ref.at[slot]).wait()

    @pl.when(b == 0)
    def _first_row():
        slot_ref[0] = 0
        start(b, 0, 0)
        start(*after(b, 0), 1)

    first_slot = slot_ref[0]
    turns = turns_of(b)
    cur = jnp.minimum(cur_ref[b], nblk * ps - 1)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    def turn(i, _):
        slot = (first_slot + i) & 1
        refill = after(*after(b, i))
        wait(slot)

        def scores(c):
            rows = buf_ref[slot, c * share:(c + 1) * share].reshape(
                share * ps, buf_ref.shape[-1])
            s = jax.lax.dot_general(
                q_ref[0], rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [H, columns]
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            first = i * width + c * share * ps
            return jnp.where(first + cols <= cur, s, NEG_INF), rows

        def update(c, s, rows):
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
                p.astype(rows.dtype), rows[:, :rank],
                preferred_element_type=jnp.float32)
            m_ref[:, :1] = m_new
            start(*refill, slot, c * share, share, unroll=True)

        held = scores(0)
        for c in range(1, chains):
            ahead = scores(c)
            update(c - 1, *held)
            held = ahead
        update(chains - 1, *held)

    jax.lax.fori_loop(0, turns, turn, None)
    slot_ref[0] = (first_slot + turns) & 1

    @pl.when(b == nb - 1)
    def _last_row():
        wait(0)
        wait(1)

    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
                ).astype(o_ref.dtype)


def mla_paged_decode_attention(q, pool, cache_index, page_table, rank: int,
                               sm_scale: float,
                               interpret: Optional[bool] = None):
    """Single-step absorbed latent attention over the page pool — the
    decode fast path of a latent cache.

    q [B, H, W] (see `mla_paged_attend`), pool [NP, ps, W], cache_index
    int32 [B] (row b attends positions <= cursor(b)), page_table int32
    [B, nblk]. Returns u [B, H, rank]. One grid step a row, which walks
    the row's live pages with its own copies (`_mla_decode_kernel`): the
    time follows the contexts, not the table's length. The pages a turn
    and the chains a turn keeps in flight follow from the shapes
    (`mla_pages_per_turn`, `mla_chains`) and are in the traced name."""
    B, H, W = q.shape
    NP, ps, _ = pool.shape
    if pool.shape[2] != W or rank > W:
        raise ValueError(f"q rows of {W} and rank {rank} do not fit the "
                         f"pool's rows of {pool.shape[2]}")
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"page_table must be [B={B}, nblk], got shape "
                         f"{page_table.shape}")
    nblk = page_table.shape[1]
    interpret = _resolve_interpret(interpret)
    cur = jnp.asarray(cache_index, jnp.int32)
    if cur.shape != (B,):
        raise ValueError(f"cache_index must be [B]={B} per-row cursors, "
                         f"got shape {cur.shape}")
    pt = jnp.asarray(page_table, jnp.int32)
    mesh = _kernel_mesh(q)
    if mesh is not None:
        rows = ("rows", None, None)
        return _per_device(
            functools.partial(mla_paged_decode_attention, rank=rank,
                              sm_scale=sm_scale, interpret=interpret),
            mesh, B, 1, (q, pool, cur, pt),
            (rows, (None, None, None), ("rows",), ("rows", None)), rows)
    pages = mla_pages_per_turn(nblk, ps * W * pool.dtype.itemsize)
    chains = mla_chains(H, pages)
    note_traced("decode", f"pallas_mla_paged[live,pages={pages}"
                          + (f",chains={chains}]" if chains > 1 else "]"))
    return _mla_walk(q, pool, cur, pt, rank, sm_scale, interpret, pages,
                     chains)


# jitted, and inlined where it is called, as `_paged_walk` is: a model's
# layers call the kernel with the same shapes, and its body is traced once
# a program and not once a layer (a body traced a layer, with a turn's
# starts unrolled in it, cost DeepSeek-V2's set-up 3.9 s: PERF.md, PR 40)
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8), inline=True)
def _mla_walk(q, pool, cur, pt, rank, sm_scale, interpret, pages, chains):
    """`_mla_decode_kernel`'s pallas_call: cursors and page table as scalar
    prefetch, the pool an HBM operand, two slots of `pages` pages."""
    B, H, W = q.shape
    ps, nblk = pool.shape[1], pt.shape[1]

    def row_spec(minor):
        return pl.BlockSpec((1, H, minor), lambda b, *pre: (b, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row_spec(W), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((2, pages, ps, W), pool.dtype),  # pages in flight
            pltpu.SemaphoreType.DMA((2,)),              # one a slot
            pltpu.SMEM((1,), jnp.int32),          # the next row's slot
            pltpu.VMEM((H, rank), jnp.float32),   # acc
            pltpu.VMEM((H, LANES), jnp.float32),  # running max m
            pltpu.VMEM((H, LANES), jnp.float32),  # running sum l
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, sm_scale=sm_scale, ps=ps,
                          rank=rank, nblk=nblk, pages=pages, chains=chains),
        grid_spec=grid_spec,
        out_shape=_out_struct((B, H, rank), q.dtype, q, pool),
        # rows in order: a turn's slot is filled by the turns before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(cur, pt, q, pool)


#: cached positions a turn of the row groups' walk scores at once
_MLA_ROWS_TURN = 512

#: bytes of absorbed queries `[B, S, H, W]` a chunk call may build for all
#: its rows at once; `q_lat` and `u`, four fifths of that each, stand
#: beside them. LongCat-Flash's `[64, 128]` chunk of 64 heads builds 671
#: MB; DeepSeek-V2's of 128 heads would build 1.34 GB + 2 x 1.07 GB
_MLA_BUILT_QUERY_BYTES = 1 << 30


def mla_query_rows(B: int, S: int, H: int, W: int, dtype) -> int:
    """Rows of a `[B, S]` call whose absorbed queries are built together:
    all B where `[B, S, H, W]` of them stay under
    `_MLA_BUILT_QUERY_BYTES`; else what one pass of `mla_paged_attend`
    takes (`_MLA_QUERY_ROWS` queries), a divisor of B."""
    if B * S * H * W * jnp.dtype(dtype).itemsize <= _MLA_BUILT_QUERY_BYTES:
        return B
    return max(d for d in range(1, B + 1)
               if B % d == 0 and d <= max(1, _MLA_QUERY_ROWS // (S * H)))


def mla_paged_attend_rows(q_nope, q_pe, w_k, w_v, pool, positions,
                          page_table, rank, sm_scale):
    """A chunk's latent attention from the queries as projected to the
    values as projected, a group of `mla_query_rows` rows at a time:
    absorb (`q_nope` [B, S, H, dn] through `w_k` [rank, H, dn]), pad to
    the pool's rows beside `q_pe`, `mla_paged_attend`, and the absorbed
    value projection (`w_v` [rank, H, dv]) — so that the absorbed queries
    and `u` exist for one group only. Each group walks to the furthest
    page ITS live queries reach, `_MLA_ROWS_TURN` positions a turn: rows
    that are no member of a prefill call walk none. Returns
    [B, S, H, dv]."""
    B, S, H, _ = q_nope.shape
    ps, W = pool.shape[1:]
    nblk = page_table.shape[1]
    G = mla_query_rows(B, S, H, W, q_nope.dtype)
    turn = max(d for d in range(1, nblk + 1)
               if nblk % d == 0 and d <= max(1, _MLA_ROWS_TURN // ps))
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B, S))

    def part(a):
        q_nope, q_pe, pos, pt = a
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_k)
        pad = jnp.zeros(q_pe.shape[:-1] + (W - rank - q_pe.shape[-1],),
                        q_pe.dtype)
        u = mla_paged_attend(jnp.concatenate([q_lat, q_pe, pad], -1), pool,
                             pos, pt, rank, sm_scale, pages_a_turn=turn)
        return jnp.einsum("bshr,rhd->bshd", u, w_v)

    grouped = lambda x: x.reshape((B // G, G) + x.shape[1:])      # noqa: E731
    out = jax.lax.map(part, (grouped(q_nope), grouped(q_pe), grouped(pos),
                             grouped(jnp.asarray(page_table, jnp.int32))))
    return out.reshape((B,) + out.shape[2:])


# ---------------------------------------------------------------------------
# A chunk of queries over the per-head page pool, in plain jax
# ---------------------------------------------------------------------------

#: float32 scores (rows x heads x queries x keys of a turn) one pass of
#: `paged_attend` holds: 256 MB of them, beside probabilities in the
#: cache's type and an accumulator an eighth of that
_PAGED_SCORES = 1 << 26
#: cached positions a turn of `paged_attend`'s walk scores at once
_PAGED_TURN = 512


def paged_attend(q, pool, positions, page_table,
                 sm_scale: Optional[float] = None):
    """Attention of a chunk of queries over the per-head page pool
    (`kv_row_width`) without the pool's rows ever being gathered for a
    whole table: what `mla_paged_attend` is for the latent pool. The
    multi-token path (prefill chunks) of a model whose contexts are too
    long for `[rows, max_len]` scores, and its decode step where no
    kernel was asked for.

    q [B, S, H, D]; pool [NP, ps, KV * 2D]; positions [B, S] absolute (a
    position >= nblk * ps marks a query whose result nobody reads);
    page_table [B, nblk]. Query head h reads kv head h // (H // KV).
    Returns softmax(q . K * sm_scale) . V, [B, S, H, D]; `sm_scale` None
    is 1 / sqrt(D).

    Pages are walked in logical order, `_PAGED_TURN` positions a turn,
    with an online softmax, up to the furthest page a live query of the
    rows in hand reaches; rows go through in groups so that a turn's
    float32 scores stay under `_PAGED_SCORES`."""
    B, S, H, D = q.shape
    NP, ps, W = pool.shape
    KV = W // (2 * D)
    if W != kv_row_width(KV, D) or H % KV:
        raise ValueError(f"pool rows of {W} columns do not hold K and V "
                         f"of a divisor of H={H} heads of D={D}")
    nblk = page_table.shape[1]
    scale = sm_scale or 1.0 / (D ** 0.5)
    pb = max(d for d in range(1, nblk + 1)
             if nblk % d == 0 and d <= max(1, _PAGED_TURN // ps))
    T = pb * ps
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B, S))
    pt = jnp.asarray(page_table, jnp.int32)

    def attend(q, qpos, pt):
        """q [G, S, H, D], qpos [G, S], pt [G, nblk] -> [G, S, H, D]."""
        G = q.shape[0]
        q5 = q.reshape(G, S, KV, H // KV, D)
        turns = jnp.max(jnp.where(qpos < nblk * ps, qpos // T + 1, 0))

        def body(j, carry):
            m, l, acc = carry
            pages = pool[jax.lax.dynamic_slice_in_dim(pt, j * pb, pb, 1)]
            kv = pages.reshape(G, T, KV, 2, D)
            s = einsum_f32("gskqd,gtkd->gkqst", q5, kv[:, :, :, 0]) * scale
            cols = j * T + jnp.arange(T, dtype=jnp.int32)
            s = jnp.where(cols <= qpos[:, None, None, :, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(-1, keepdims=True)
            acc = acc * alpha + einsum_f32(
                "gkqst,gtkd->gkqsd", p.astype(pool.dtype), kv[:, :, :, 1])
            return m_new, l, acc

        stat = (G, KV, H // KV, S, 1)
        init = (jnp.full(stat, NEG_INF, jnp.float32),
                jnp.zeros(stat, jnp.float32),
                jnp.zeros(stat[:-1] + (D,), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, turns, body, init)
        out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
        return out.transpose(0, 3, 1, 2, 4).reshape(G, S, H, D)

    G = max(d for d in range(1, B + 1)
            if B % d == 0 and d <= max(1, _PAGED_SCORES // (H * S * T)))
    if G == B:
        return attend(q, pos, pt)
    grouped = lambda x: x.reshape((B // G, G) + x.shape[1:])      # noqa: E731
    out = jax.lax.map(lambda a: attend(*a),
                      (grouped(q), grouped(pos), grouped(pt)))
    return out.reshape(B, S, H, D)


__all__ = ["flash_attention", "decode_attention", "decode_block_k",
           "decode_head_block", "paged_decode_attention",
           "paged_pages_per_turn", "kv_row_width",
           "pack_kv_rows", "paged_attend",
           "mla_paged_attend", "mla_paged_attend_rows", "mla_query_rows",
           "mla_paged_decode_attention",
           "mla_pages_per_turn", "mla_chains", "mla_row_width", "einsum_f32",
           "record_traced", "note_traced", "traced_name"]
