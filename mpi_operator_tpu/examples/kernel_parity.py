"""Pallas attention kernels against their dense references, on the chip.

The CPU tests run these kernels in interpret mode; whether Mosaic compiles
them, and whether the compiled kernels agree with the dense path, can only
be learnt on a TPU. This module is that check, at the shapes the two
GPT-2-medium legs of `chip_smoke.py` use, the decode kernels also with
GPT-2-XL's 25 heads (an odd count, the benchmark's serving shape):

  flash forward and gradients (dq, dk, dv)   vs `dense_attention`
  decode_attention (generate()'s contiguous   vs a dense masked softmax
  cache), per-row cursor vector                  over the same cache
  paged_decode_attention (the page pool as    vs the dense branch of
  rows [pages, page, KV * 2D])                   `Attention._decode_attend`
  the int8-cache variants of both decode kernels
  mla_paged_decode_attention (absorbed latent   vs `mla_paged_attend`, the
  attention, LongCat-Flash's published widths     dense form of
  and DeepSeek-V2's: 128 heads, YaRN, a table      `LatentAttention`
  of 256 pages, rows of three turns in two
  chains)
  mla_paged_attend_rows (a [64, 128] chunk of   vs `LatentAttention` with K
  128 heads, its absorbed queries built four       and V expanded
  rows at a time)
  paged_decode_attention with a lower bound     vs a dense masked softmax
  (a window), over a slot's ring of pages and     over the gathered rows
  over a long table, at Phi-4-mini-flash's
  widths (40 heads over 10 pairs of 128)
  selective_scan over a chunk (`ops/ssm.py`)    vs its own steps one by one
  ssd_chunk_scan over a chunk, the matmul form   vs the steps of
  (Falcon-H1-34B's 32 heads of 128 over 256        `ssd_state_update`, the
  states, a head a lane tile; Granite-4.0-H's      Pallas kernel, one by one;
  128 heads of 64 over 128, two heads a tile)      the record names the form
  tied_head_xent (`ops/xent.py`: the training    vs `lm_loss` over the whole
  step's head and loss in one pass, value, dh       logits of `_head_matmul`
  and dtable at the cells' 8 x 1024 tokens of
  1024 against 50 304 rows)

The paged decode cases go through the `Attention` module itself — one
set of weights, one prefilled pool, the single-token step run once with
`decode_kernel=True` and once with `False` — so the reference is the
repo's own dense branch, not a copy of it. The module drives the
contiguous cache in lockstep only (`generate()`), so that kernel is
called directly, every row at its own cursor.

Closeness is measured at the output level, `max|a-b| / max|b|`, against a
stated bf16 tolerance: the MXU does not sum in the interpreter's order,
and the two paths round p·v at different points, so bit or token equality
is not the claim.

    python -m mpi_operator_tpu.examples.kernel_parity

fails at once, before building anything, when the backend is not a TPU or
its `device_kind` is not in the peaks table (utils/flops.py).
"""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict, List, Optional

#: max|kernel - dense| / max|dense| allowed for bf16 operands. bf16 keeps
#: 8 significant bits (relative step 2^-8 = 0.0039); kernel and reference
#: each round scores, probabilities and the p·v product at different
#: points, so a few steps of disagreement on the largest element is the
#: expected floor. An indexing bug (a wrong page, a read past a cursor, a
#: mis-masked block) moves whole rows by O(1) and lands far above it.
BF16_TOL = 2e-2

#: GPT-2-medium attention geometry and the two legs' shapes; GPT-2-XL's
#: head count is one no power of two divides
GPT2_MEDIUM = dict(heads=16, head_dim=64)
GPT2_XL = dict(heads=25, head_dim=64)
TRAIN_SHAPE = dict(batch=16, seq=512)
SERVE_SHAPE = dict(slots=8, max_len=256, page_size=64, prefilled=200)
#: the latent decode kernel at LongCat-Flash's published widths (the
#: module's defaults): 64 heads share rows of 512 + 64, padded to 640
MLA_CASE = dict(slots=8, max_len=1280, page_size=64, prefilled=1000)
#: the same kernel and module at DeepSeek-V2's (`models/deepseek_v2.py`):
#: 128 heads, no rank factors, YaRN's frequencies and scale, the cell's
#: table of 256 pages; contexts of three turns of sixteen pages, so that
#: cursors stand either side of a turn's edge and a slot is filled twice
MLA_CASE_128 = dict(slots=8, max_len=16384, page_size=64, prefilled=2500,
                    family="deepseek_v2")
#: the chunk path at the cell's one prefill shape, which builds its
#: absorbed queries a group of rows at a time
MLA_CHUNK_CASE = dict(rows=64, chunk=128, page_size=64)
#: Phi-4-mini-flash's differential attention as the kernel sees it: 40
#: query heads over 10 key/value pairs of 128, scores / 8, a window of 512
#: over a ring of 9 pages a slot, and the same call without a window over
#: a table of 64 pages
WINDOW_CASE = dict(slots=8, heads=40, kv_heads=10, head_dim=128,
                   page_size=64, window=512, table=64)
#: a state-space layer's chunk at its published widths
SCAN_CASE = dict(rows=8, chunk=64, channels=5120, states=16)
#: Mamba-2's chunk and step at Falcon-H1-34B's widths
SSD_CASE = dict(rows=8, chunk=128, heads=32, head_dim=128, groups=2,
                states=256)
#: and at Granite-4.0-H-Small's: heads of 64 channels, two to a lane tile
SSD_CASE_64 = dict(rows=8, chunk=128, heads=128, head_dim=64, groups=1,
                   states=128)
#: the training cells' head: 8 x 1024 tokens a chip against GPT-2's table
HEAD_LOSS_CASE = dict(batch=8, seq=1024, embed=1024, vocab=50304)


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def _assert_mosaic(jitted, *args) -> None:
    """The lowered program must carry the kernel as a Mosaic custom call;
    an interpreted kernel lowers to plain HLO loops instead."""
    import jax

    if jax.default_backend() == "tpu" and \
            "tpu_custom_call" not in jitted.lower(*args).as_text():
        raise AssertionError("kernel did not lower to a tpu_custom_call")


def flash_cases(batch: int, seq: int, heads: int, head_dim: int
                ) -> List[Dict[str, object]]:
    """Flash forward + (dq, dk, dv) vs dense_attention at one shape."""
    import jax
    import jax.numpy as jnp

    from ..models.transformer import dense_attention
    from ..ops.attention import flash_attention

    shape = (batch, seq, heads, head_dim)
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in (kq, kk, kv, kw))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=True, dtype=q.dtype)

    def fwd_and_grads(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)
                           * w.astype(jnp.float32))
        return jax.jit(lambda q, k, v: (fn(q, k, v),
                                        *jax.grad(loss, (0, 1, 2))(q, k, v)))

    kernel = fwd_and_grads(flash)
    _assert_mosaic(kernel, q, k, v)
    got = kernel(q, k, v)
    ref = fwd_and_grads(dense)(q, k, v)
    return [{"kernel": f"flash_{name}", "shape": list(shape),
             "max_rel_err": _rel_err(g, r)}
            for name, g, r in zip(("fwd", "dq", "dk", "dv"), got, ref)]


def _decode_cursors(slots: int, page_size: int, prefilled: int):
    """Cursors on and around the block boundaries of both decode kernels
    (k-tile 128, page 64), first and last filled position included."""
    marks = [0, page_size - 1, page_size, 2 * page_size - 1,
             2 * page_size, prefilled - 1, 5, prefilled - 9]
    return [min(max(m, 0), prefilled - 1) for m in (marks * slots)[:slots]]


def contiguous_decode_case(int8: bool, slots: int, max_len: int,
                           page_size: int, prefilled: int, heads: int,
                           head_dim: int) -> Dict[str, object]:
    """`decode_attention` over a contiguous [slots, heads, max_len,
    head_dim] cache filled to `prefilled`, every row at its own cursor,
    against a float32 masked softmax over the same (dequantized) cache."""
    import jax
    import jax.numpy as jnp

    from ..ops.attention import decode_attention, record_traced, traced_name

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    shape = (slots, heads, max_len, head_dim)
    live = (jnp.arange(max_len) < prefilled)[None, None, :, None]
    q = jax.random.normal(kq, (slots, heads, head_dim), jnp.bfloat16)
    k = jnp.where(live, jax.random.normal(kk, shape, jnp.bfloat16), 0)
    v = jnp.where(live, jax.random.normal(kv, shape, jnp.bfloat16), 0)
    cur = jnp.asarray(_decode_cursors(slots, page_size, prefilled),
                      jnp.int32)
    scales = {}
    if int8:
        def quant(x):       # the model's: symmetric, one scale a vector
            x = x.astype(jnp.float32)
            sc = jnp.maximum(jnp.max(jnp.abs(x), -1) / 127.0, 1e-8)
            return (jnp.clip(jnp.round(x / sc[..., None]), -127, 127)
                    .astype(jnp.int8), sc)
        (k, ks), (v, vs) = quant(k), quant(v)
        scales = dict(k_scale=ks, v_scale=vs)

    kernel = jax.jit(lambda q, k, v: decode_attention(q, k, v, cur,
                                                      **scales))
    with record_traced() as traced:
        _assert_mosaic(kernel, q, k, v)
        got = kernel(q, k, v)
    name = traced_name(traced["decode"]) or ""
    if not name.startswith("pallas[hb=") or "+" in name:
        raise AssertionError(f"decode kernel traced {traced['decode']}, "
                             f"expected one pallas[hb=N]")

    def dense(q, k, v):
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        if int8:
            k, v = k * ks[..., None], v * vs[..., None]
        s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32), k)
        s = s / head_dim ** 0.5
        s = jnp.where(jnp.arange(max_len)[None, None] <= cur[:, None, None],
                      s, -1e30)
        return jnp.einsum("bhl,bhld->bhd", jax.nn.softmax(s, -1), v)

    return {"kernel": "decode_attention" + ("_int8" if int8 else ""),
            "traced": name,
            "shape": {"slots": slots, "heads": heads, "head_dim": head_dim,
                      "max_len": max_len},
            "cursors": [int(c) for c in cur],
            "max_rel_err": _rel_err(got, jax.jit(dense)(q, k, v))}


def decode_case(int8: bool, slots: int, max_len: int, page_size: int,
                prefilled: int, heads: int, head_dim: int
                ) -> Dict[str, object]:
    """One single-token step through `Attention` over a prefilled page
    pool, kernel vs dense branch, every row at its own cursor."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.transformer import Attention, TransformerConfig
    from ..ops.attention import record_traced, traced_name

    nblk = max_len // page_size
    cfg = TransformerConfig(
        num_heads=heads, embed_dim=heads * head_dim, max_len=max_len,
        dtype=jnp.bfloat16, decode=True,
        kv_cache_dtype="int8" if int8 else None,
        decode_page_size=page_size, decode_num_pages=slots * nblk + 1)
    dense = Attention(dataclasses.replace(cfg, decode_kernel=False))
    kernel = Attention(dataclasses.replace(cfg, decode_kernel=True))

    # every row's logical blocks scattered over the pool (page 0 is the
    # reserved trash page), so a kernel that ignored the table would
    # read another row's pages
    ids = np.random.RandomState(0).permutation(slots * nblk) + 1
    pages = jnp.asarray(ids.reshape(slots, nblk), jnp.int32)
    kp, kx, ks = jax.random.split(jax.random.PRNGKey(1), 3)
    E = cfg.embed_dim
    x_fill = jax.random.normal(kx, (slots, prefilled, E), jnp.bfloat16)
    x_step = jax.random.normal(ks, (slots, 1, E), jnp.bfloat16)
    fill_pos = jnp.broadcast_to(jnp.arange(prefilled)[None],
                                (slots, prefilled))
    cur = jnp.asarray(_decode_cursors(slots, page_size, prefilled),
                      jnp.int32)

    params = dense.init(kp, x_step, positions=cur[:, None],
                        pages=pages)["params"]
    # the multi-token call is the dense branch under either setting
    _, filled = jax.jit(lambda p: dense.apply(
        {"params": p}, x_fill, positions=fill_pos, pages=pages,
        mutable=["cache"]))(params)

    def step(module):
        return jax.jit(lambda p, c: module.apply(
            {"params": p, "cache": c}, x_step, positions=cur[:, None],
            pages=pages, mutable=["cache"])[0])

    with record_traced() as traced:
        _assert_mosaic(step(kernel), params, filled["cache"])
        got = step(kernel)(params, filled["cache"])
    # the kernel names itself: the walk of live pages with its pages a
    # turn, or (an int8 pool) the grid form; and the kv heads a grid step
    name = traced_name(traced["decode"]) or ""
    form = "pallas_paged[hb=" if int8 else "pallas_paged[live,pages="
    if not name.startswith(form) or "+" in name:
        raise AssertionError(f"decode step traced {traced['decode']}, "
                             f"expected one {form}N..]")
    ref = step(dense)(params, filled["cache"])
    return {"kernel": "paged_decode_attention" + ("_int8" if int8 else ""),
            "traced": name,
            "shape": {"slots": slots, "heads": heads, "head_dim": head_dim,
                      "max_len": max_len, "page_size": page_size},
            "cursors": [int(c) for c in cur],
            "max_rel_err": _rel_err(got, ref)}


def _mla_config(family: str, **fields):
    """The configuration `LatentAttention` runs under: LongCat-Flash's or
    DeepSeek-V2's, at its published widths."""
    if family == "deepseek_v2":
        from ..models.deepseek_v2 import DeepseekV2Config
        return DeepseekV2Config(**fields)
    from ..models.longcat import LongcatConfig
    return LongcatConfig(**fields)


def mla_decode_case(slots: int, max_len: int, page_size: int, prefilled: int,
                    family: str = "longcat", **widths) -> Dict[str, object]:
    """One single-token step through `LatentAttention` (models/longcat.py)
    over a prefilled latent page pool, every row at its own cursor: the
    absorbed Pallas kernel against the module's dense form
    (`ops.attention.mla_paged_attend`), same weights, same cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.longcat import LatentAttention
    from ..ops.attention import record_traced, traced_name

    nblk = max_len // page_size
    cfg = _mla_config(family, max_len=max_len, dtype=jnp.bfloat16,
                      decode=True, decode_page_size=page_size,
                      decode_num_pages=slots * (-(-prefilled // page_size))
                      + 1, **widths)
    dense = LatentAttention(dataclasses.replace(cfg, decode_kernel=False))
    kernel = LatentAttention(dataclasses.replace(cfg, decode_kernel=True))
    # a row's live pages, scattered over the pool; the table's tail is dead
    live = -(-prefilled // page_size)
    ids = np.random.RandomState(0).permutation(slots * live) + 1
    pages = jnp.asarray(np.pad(ids.reshape(slots, live),
                               ((0, 0), (0, nblk - live))), jnp.int32)
    kp, kx, ks = jax.random.split(jax.random.PRNGKey(2), 3)
    E = cfg.hidden_size
    x_fill = jax.random.normal(kx, (slots, prefilled, E), jnp.bfloat16)
    x_step = jax.random.normal(ks, (slots, 1, E), jnp.bfloat16)
    fill_pos = jnp.broadcast_to(jnp.arange(prefilled)[None],
                                (slots, prefilled))
    # cursors on and around the page boundaries, the kernel's turns of
    # several pages and its pair of slots, first and last filled
    # position included
    from ..ops.attention import mla_pages_per_turn, mla_row_width
    turn = page_size * mla_pages_per_turn(
        nblk, page_size * jnp.dtype(cfg.dtype).itemsize * mla_row_width(
            cfg.kv_lora_rank, cfg.qk_rope_head_dim))
    marks = [0, page_size - 1, page_size, turn - 1, turn, 2 * turn - 1,
             2 * turn, prefilled - 1, 5, prefilled - 9]
    cur = jnp.asarray([min(max(m, 0), prefilled - 1)
                       for m in (marks * slots)[:slots]], jnp.int32)
    params = dense.init(kp, x_step, positions=cur[:, None],
                        pages=pages)["params"]
    _, filled = jax.jit(lambda p: dense.apply(
        {"params": p}, x_fill, positions=fill_pos, pages=pages,
        mutable=["cache"]))(params)

    def step(module):
        return jax.jit(lambda p, c: module.apply(
            {"params": p, "cache": c}, x_step, positions=cur[:, None],
            pages=pages, mutable=["cache"])[0])

    with record_traced() as traced:
        _assert_mosaic(step(kernel), params, filled["cache"])
        got = step(kernel)(params, filled["cache"])
    name = traced_name(traced["decode"]) or ""
    if not name.startswith("pallas_mla_paged[live,pages=") or "+" in name:
        raise AssertionError(f"decode step traced {traced['decode']}, "
                             f"expected one pallas_mla_paged[live,pages=N] "
                             f"or [live,pages=N,chains=C]")
    ref = step(dense)(params, filled["cache"])
    return {"kernel": "mla_paged_decode_attention", "traced": name,
            "shape": {"slots": slots, "max_len": max_len,
                      "page_size": page_size, "family": family,
                      "heads": cfg.num_heads, **widths},
            "cursors": [int(c) for c in cur],
            "max_rel_err": _rel_err(got, ref)}


def mla_chunk_case(rows: int, chunk: int, page_size: int, **widths
                   ) -> Dict[str, object]:
    """One prefill call of `rows` x `chunk` tokens from position 0 through
    DeepSeek-V2's `LatentAttention` in decode mode — the latent pages
    written, the absorbed queries built, attended and projected a group
    of rows at a time (`ops.attention.mla_paged_attend_rows`) — against
    the same module outside decode mode, K and V expanded: same weights,
    same input."""
    import jax
    import jax.numpy as jnp

    from ..models.longcat import LatentAttention
    from ..ops.attention import mla_query_rows, mla_row_width

    nblk = chunk // page_size
    cfg = _mla_config("deepseek_v2", max_len=chunk, dtype=jnp.bfloat16,
                      **widths)
    dcfg = dataclasses.replace(cfg, decode=True, decode_page_size=page_size,
                               decode_num_pages=rows * nblk + 1)
    group = mla_query_rows(rows, chunk, cfg.num_heads, mla_row_width(
        cfg.kv_lora_rank, cfg.qk_rope_head_dim), cfg.dtype)
    if group >= rows:
        raise AssertionError(f"[{rows}, {chunk}] x {cfg.num_heads} heads "
                             f"builds its queries whole, not in row groups")
    kp, kx = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (rows, chunk, cfg.hidden_size), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(chunk)[None], (rows, chunk))
    pages = 1 + jnp.arange(rows * nblk, dtype=jnp.int32).reshape(rows, nblk)
    plain = LatentAttention(cfg)
    params = plain.init(kp, x[:1])["params"]
    got = jax.jit(lambda p: LatentAttention(dcfg).apply(
        {"params": p}, x, positions=pos, pages=pages,
        mutable=["cache"])[0])(params)
    ref = jax.jit(lambda p: plain.apply({"params": p}, x))(params)
    return {"kernel": "mla_paged_attend_rows",
            "shape": {"rows": rows, "chunk": chunk, "page_size": page_size,
                      "heads": cfg.num_heads, "rows_a_group": group},
            "max_rel_err": _rel_err(got, ref)}


def window_decode_cases(slots: int, heads: int, kv_heads: int, head_dim: int,
                        page_size: int, window: int, table: int
                        ) -> List[Dict[str, object]]:
    """`paged_decode_attention` with its lower bound, called as a window
    layer calls it — the slot's ring as `window / page + 1` pages, the
    table counted from the window's first page, cursors relative to it —
    and over a long table with and without the bound, each against a
    float32 masked softmax over the gathered rows. Cursors sit on both
    sides of page edges and of the window's edge."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.attention import (pack_kv_rows, paged_decode_attention,
                                 record_traced, traced_name)

    scale = 1.0 / (head_dim // 2) ** 0.5
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (slots, heads, head_dim), jnp.bfloat16)

    def pool_of(pages):
        k, v = (jax.random.normal(key, (pages, page_size, kv_heads,
                                        head_dim), jnp.bfloat16)
                for key in (kk, kv))
        return pack_kv_rows(k, v)

    def dense(pool, cur, tbl, bound):
        rows = pool[tbl].reshape(slots, -1, kv_heads, 2, head_dim).astype(
            jnp.float32)
        k = jnp.repeat(rows[:, :, :, 0], heads // kv_heads, axis=2)
        v = jnp.repeat(rows[:, :, :, 1], heads // kv_heads, axis=2)
        s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32), k) * scale
        p = jnp.arange(rows.shape[1])[None, None]
        seen = p <= cur[:, None, None]
        if bound:
            seen &= p > cur[:, None, None] - bound
        return jnp.einsum("bht,bthd->bhd", jax.nn.softmax(
            jnp.where(seen, s, -1e30), -1), v)

    records = []
    nr = window // page_size + 1
    long_marks = [0, page_size - 1, page_size, window - 1, window,
                  window + 1, table * page_size - 1, 2 * window + 7]
    ring_marks = [m % (nr * page_size) if m >= window else m
                  for m in long_marks]
    # a long table's pages are scattered over the pool (page 0 the trash)
    ids = np.random.RandomState(1).permutation(slots * table) + 1
    scattered = jnp.asarray(ids.reshape(slots, table), jnp.int32)
    long_pool = pool_of(slots * table + 1)
    cases = [
        ("ring", pool_of(slots * nr),
         jnp.arange(slots * nr, dtype=jnp.int32).reshape(slots, nr),
         ring_marks, window),
        ("table", long_pool, scattered, long_marks, window),
        ("table", long_pool, scattered, long_marks, None)]
    for form, pool, tbl, marks, bound in cases:
        cur = jnp.asarray((marks * slots)[:slots], jnp.int32)
        call = jax.jit(lambda q, pool, cur, tbl, bound=bound:
                       paged_decode_attention(q, pool, cur, tbl,
                                              window=bound, sm_scale=scale))
        with record_traced() as traced:
            _assert_mosaic(call, q, pool, cur, tbl)
            got = call(q, pool, cur, tbl)
        records.append({
            "kernel": "paged_decode_attention_"
                      + (f"window_{form}" if bound else "pairs"),
            "traced": traced_name(traced["decode"]),
            "shape": {"slots": slots, "heads": heads, "kv_heads": kv_heads,
                      "head_dim": head_dim, "page_size": page_size,
                      "window": bound, "table": int(tbl.shape[1])},
            "cursors": [int(c) for c in cur],
            "max_rel_err": _rel_err(got, dense(pool, cur, tbl, bound))})
    return records


def scan_case(rows: int, chunk: int, channels: int, states: int
              ) -> Dict[str, object]:
    """`selective_scan` over a chunk with a state carried in, against its
    own steps one position at a time (what a prefill chunk is to the
    decode steps that follow it)."""
    import jax
    import jax.numpy as jnp

    from ..ops.ssm import selective_scan

    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (rows, chunk, channels))
    delta = jax.nn.softplus(jax.random.normal(ks[1], x.shape) - 4.0)
    A = -jnp.broadcast_to(jnp.arange(1.0, states + 1)[:, None],
                          (states, channels))
    B, C = (jax.random.normal(k, (rows, chunk, states)) for k in ks[2:4])
    D = jnp.ones((channels,))
    s0 = jax.random.normal(ks[4], (rows, states, channels))
    y, last = jax.jit(selective_scan)(x, delta, A, B, C, D, s0)
    step = jax.jit(selective_scan)
    s, ys = s0, []
    for t in range(chunk):
        y_t, s = step(x[:, t:t + 1], delta[:, t:t + 1], A, B[:, t:t + 1],
                      C[:, t:t + 1], D, s)
        ys.append(y_t)
    return {"kernel": "selective_scan_chunk_vs_steps",
            "shape": {"rows": rows, "chunk": chunk, "channels": channels,
                      "states": states},
            "max_rel_err": max(_rel_err(y, jnp.concatenate(ys, 1)),
                               _rel_err(last, s))}


def ssd_case(rows: int, chunk: int, heads: int, head_dim: int, groups: int,
             states: int) -> Dict[str, object]:
    """`ssd_chunk_scan` over a chunk with a state carried in, against the
    steps of `ssd_state_update` (on the chip: the Pallas kernel, its state
    donated) one position at a time; the state held as `ssd_state_shape`
    has it, and the record's `ssd_traced` the form the update took."""
    import jax
    import jax.numpy as jnp

    from ..ops.attention import record_traced, traced_name
    from ..ops.ssm import ssd_chunk_scan, ssd_state_shape, ssd_state_update

    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    x = jax.random.normal(ks[0], (rows, chunk, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(ks[1], x.shape[:3]) - 4.0)
    A = -(1.0 + 15.0 * jax.random.uniform(ks[2], (heads,)))
    B, C = (jax.random.normal(k, (rows, chunk, groups, states))
            for k in ks[3:5])
    D = jnp.ones((heads,))
    s0 = jax.random.normal(ks[5], ssd_state_shape(rows, heads, head_dim,
                                                  groups, states))
    y, last = jax.jit(ssd_chunk_scan)(x, dt, A, B, C, D, s0)
    step = jax.jit(ssd_state_update, donate_argnums=(6,))
    with record_traced() as traced:
        _assert_mosaic(step, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D, s0)
        s, ys = s0 + 0.0, []
        for t in range(chunk):
            y_t, s = step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, s)
            ys.append(y_t)
    return {"kernel": "ssd_chunk_scan_vs_state_update_steps",
            "shape": {"rows": rows, "chunk": chunk, "heads": heads,
                      "head_dim": head_dim, "groups": groups,
                      "states": states},
            "ssd_traced": traced_name(traced["ssd"]),
            "max_rel_err": max(_rel_err(y, jnp.stack(ys, 1)),
                               _rel_err(last, s))}


def head_loss_case(batch: int, seq: int, embed: int, vocab: int
                   ) -> Dict[str, object]:
    """`tied_head_xent`'s loss, accuracy, dh and dtable against `lm_loss`
    and the arg-max over the whole float32 logits; the record's
    `head_loss_traced` names the form it took (on the chip the kernel)."""
    import jax
    import jax.numpy as jnp

    from ..models.transformer import _head_matmul
    from ..ops.attention import record_traced, traced_name
    from ..ops.xent import tied_head_xent
    from ..train.lm_trainer import lm_loss

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    h = jax.random.normal(ks[0], (batch, seq, embed), jnp.bfloat16)
    # steep enough that a share of the labels ARE their row's arg-max
    table = (4.0 / embed ** 0.5) * jax.random.normal(ks[1], (vocab, embed))
    y = jnp.argmax(_head_matmul(h, table.astype(h.dtype)), -1)
    y = jnp.where(jax.random.bernoulli(ks[2], 0.5, y.shape), y,
                  (y + 1) % vocab)

    def dense(h, table):
        z = _head_matmul(h, table.astype(h.dtype))
        return lm_loss(z, y), jnp.mean(jnp.argmax(z, -1) == y)

    def one_pass(h, table):
        return tied_head_xent(h, table, y)

    def both(fn):
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True))

    with record_traced() as traced:
        _assert_mosaic(both(one_pass), h, table)
        (loss, acc), grads = both(one_pass)(h, table)
    (ref_loss, ref_acc), ref_grads = both(dense)(h, table)
    errs = [abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
            abs(float(acc) - float(ref_acc))]
    errs += [_rel_err(g, r) for g, r in zip(grads, ref_grads)]
    return {"kernel": "tied_head_xent_vs_logits",
            "shape": {"batch": batch, "seq": seq, "embed": embed,
                      "vocab": vocab},
            "head_loss_traced": traced_name(traced["head_loss"]),
            "accuracy": float(acc), "max_rel_err": max(errs)}


def run_kernel_parity(train_shape: Optional[dict] = None,
                      serve_shape: Optional[dict] = None,
                      model: Optional[dict] = None,
                      decode_models: Optional[List[dict]] = None,
                      mla: Optional[List[dict]] = None,
                      mla_chunk: Optional[dict] = None,
                      window: Optional[dict] = None,
                      scan: Optional[dict] = None,
                      ssd: Optional[List[dict]] = None,
                      head_loss: Optional[dict] = None,
                      tol: float = BF16_TOL) -> List[Dict[str, object]]:
    """Every kernel the two legs use, at their shapes; one record each
    with its measured error and `ok`. The decode kernels run once per
    entry of `decode_models` (default: `model` alone); the latent decode
    kernel where `mla` gives its cases (and the latent chunk path where
    `mla_chunk` does), the windowed decode kernel and the
    scans where `window`, `scan` and `ssd` give theirs, the training
    step's head and loss where `head_loss` does. Off TPU the kernels
    interpret (the tier-1 test runs tiny shapes that way)."""
    train_shape = train_shape or TRAIN_SHAPE
    serve_shape = serve_shape or SERVE_SHAPE
    model = model or GPT2_MEDIUM
    records = flash_cases(train_shape["batch"], train_shape["seq"],
                          model["heads"], model["head_dim"])
    for geometry in decode_models or [model]:
        for case in (contiguous_decode_case, decode_case):
            for int8 in (False, True):
                records.append(case(int8, **serve_shape, **geometry))
    for case in mla or ():
        records.append(mla_decode_case(**case))
    if mla_chunk:
        records.append(mla_chunk_case(**mla_chunk))
    if window:
        records += window_decode_cases(**window)
    if scan:
        records.append(scan_case(**scan))
    for case in ssd or ():
        records.append(ssd_case(**case))
    if head_loss:
        records.append(head_loss_case(**head_loss))
    for rec in records:
        rec["tol"] = tol
        rec["ok"] = bool(rec["max_rel_err"] <= tol)
    return records


def main(argv=None) -> int:
    del argv
    import jax

    from ..utils import flops
    from ..utils.compile_cache import enable_compile_cache
    from ._report import device_record

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"kernel_parity: needs a TPU backend to compile the kernels "
              f"with Mosaic; jax found platform {backend!r}",
              file=sys.stderr)
        return 2
    flops.device_peaks()            # an unknown device_kind raises here
    cache_dir = enable_compile_cache()
    device = device_record()
    records = run_kernel_parity(decode_models=[GPT2_MEDIUM, GPT2_XL],
                                mla=[MLA_CASE, MLA_CASE_128],
                                mla_chunk=MLA_CHUNK_CASE, window=WINDOW_CASE,
                                scan=SCAN_CASE,
                                ssd=[SSD_CASE, SSD_CASE_64],
                                head_loss=HEAD_LOSS_CASE)
    for rec in records:
        print(json.dumps({**rec, **device}))
    ok = all(rec["ok"] for rec in records)
    print(json.dumps({"metric": "kernel_parity", "ok": ok,
                      "kernels": len(records),
                      "worst_max_rel_err": max(r["max_rel_err"]
                                               for r in records),
                      "tol": BF16_TOL,
                      "decode_traced": sorted({r["traced"] for r in records
                                               if r.get("traced")}),
                      "ssd_traced": sorted({r["ssd_traced"] for r in records
                                            if r.get("ssd_traced")}),
                      "head_loss_traced": next(
                          (r["head_loss_traced"] for r in records
                           if r.get("head_loss_traced")), None),
                      **device,
                      "compile_cache_dir": cache_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
