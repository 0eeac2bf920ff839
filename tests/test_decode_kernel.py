"""Pallas decode-attention kernel tests (interpret mode on CPU — the same
kernel code path that compiles to Mosaic on TPU).

The dense `_decode_attend` path in models/transformer.py is the
correctness oracle: the kernel must match it within dtype tolerance for
MHA, GQA, and int8-quantized caches, INCLUDING mid-generation cursors —
a partially filled cache whose unfilled suffix is poisoned, so any read
past the cursor shows up as a huge error, not a lucky zero.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from mpi_operator_tpu.models import CausalLM, generate, gpt2_config
from mpi_operator_tpu.models.transformer import llama_config
from mpi_operator_tpu.ops import attention
from mpi_operator_tpu.ops.attention import (
    decode_attention, decode_block_k, decode_head_block,
    paged_decode_attention, record_traced, traced_name,
)

POISON = 1e4          # beyond-cursor cache contents: loud if ever read


def _dense_ref(q, k, v, cur, k_scale=None, v_scale=None):
    """The dense decode oracle, mirroring transformer._decode_attend:
    dequant, GQA repeat on the kv-head axis, masked softmax over the
    filled prefix [0, cur]."""
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[..., None]
        v = v.astype(jnp.float32) * v_scale[..., None]
    B, KV, L, D = k.shape
    H = q.shape[1]
    k = jnp.repeat(k, H // KV, axis=1)            # [B, H, L, D]
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (D ** 0.5)
    s = jnp.where(jnp.arange(L)[None, None] <= cur, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,bhld->bhd", p, v.astype(jnp.float32))


def _cache(B, H, KV, L, D, cur, quantized=False, seed=0):
    """A cache filled up to `cur` (inclusive) and POISONed past it."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, H, D), jnp.float32)
    k = jax.random.normal(keys[1], (B, KV, L, D), jnp.float32)
    v = jax.random.normal(keys[2], (B, KV, L, D), jnp.float32)
    dead = jnp.arange(L)[None, None, :, None] > cur
    if not quantized:
        return q, jnp.where(dead, POISON, k), jnp.where(dead, POISON, v), \
            None, None
    scale = jnp.maximum(jnp.max(jnp.abs(k), -1) / 127.0, 1e-8)
    k8 = jnp.clip(jnp.round(k / scale[..., None]), -127, 127)
    vscale = jnp.maximum(jnp.max(jnp.abs(v), -1) / 127.0, 1e-8)
    v8 = jnp.clip(jnp.round(v / vscale[..., None]), -127, 127)
    k8 = jnp.where(dead, 127, k8).astype(jnp.int8)
    v8 = jnp.where(dead, 127, v8).astype(jnp.int8)
    dead3 = jnp.arange(L)[None, None] > cur
    return (q, k8, v8, jnp.where(dead3, POISON, scale),
            jnp.where(dead3, POISON, vscale))


# the last three: head counts no power of two divides (5, and gpt2-xl's
# 25 heads of 64) as MHA, and 5 kv heads as GQA (G = 2)
@pytest.mark.parametrize("H,KV,D", [(4, 4, 16), (4, 2, 16), (8, 1, 16),
                                    (5, 5, 64), (25, 25, 64), (10, 5, 16)])
@pytest.mark.parametrize("quantized", [False, True])
def test_decode_kernel_matches_dense(H, KV, D, quantized):
    """MHA (H==KV), GQA, and MQA (KV=1), each with and without the int8
    cache — cursor mid-block so both the block skip and the in-block
    column mask are exercised. One grid step takes all KV heads of a
    k-tile (the default budget holds them)."""
    B, L, cur = 2, 64, 37
    q, k, v, ks, vs = _cache(B, H, KV, L, D, cur, quantized)
    with record_traced() as traced:
        if quantized:
            ref = _dense_ref(q, k, v, cur, ks, vs)
            out = decode_attention(q, k, v, cur, k_scale=ks, v_scale=vs,
                                   block_k=16, interpret=True)
        else:
            ref = _dense_ref(q, k, v, cur)
            out = decode_attention(q, k, v, cur, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)
    assert traced["decode"] == {f"pallas[hb={KV}]"}


@pytest.mark.parametrize("cur", [0, 15, 16, 31, 63])
def test_decode_kernel_cursor_positions(cur):
    """Mid-generation cursors: the first position, both sides of a block
    boundary, and the full cache — the length-aware index_map and the
    boundary-block column mask must agree with the oracle at each."""
    B, H, KV, L, D = 2, 4, 2, 64, 16
    q, k, v, _, _ = _cache(B, H, KV, L, D, cur, seed=cur + 1)
    ref = _dense_ref(q, k, v, cur)
    out = decode_attention(q, k, v, cur, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def _head_bytes(block_k, quantized):
    """What `decode_head_block` counts for ONE kv head of a float32 (or
    int8) cache with D <= 128: K and V blocks padded to 128 lanes (int8:
    plus a [block_k, 1] f32 scale block each), two pipeline buffers."""
    return 4 * block_k * 128 * ((1 + 4) if quantized else 4)


# fit: how many of the 6 kv heads the VMEM budget holds; None: the default
@pytest.mark.parametrize("fit,hb", [(None, 6), (4, 3), (1, 1), (0, 1)])
@pytest.mark.parametrize("quantized", [False, True])
def test_decode_kernel_per_row_cursors(monkeypatch, fit, hb, quantized):
    """[B] cursor vector (the serving engine's slot mode): each row reads
    exactly its own filled prefix — per-row poison past each cursor makes
    any cross-row or beyond-cursor read loud. GQA over 6 kv heads, in
    6 // hb head blocks a row: all heads a step, three (a budget for 4
    takes the largest divisor under it), one."""
    B, H, KV, L, D = 4, 12, 6, 64, 16
    if fit is not None:
        monkeypatch.setattr(attention, "_KV_VMEM_BUDGET",
                            fit * _head_bytes(16, quantized))
    curs = np.array([0, 17, 31, 63], np.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (B, H, D), jnp.float32)
    k = jax.random.normal(keys[1], (B, KV, L, D), jnp.float32)
    v = jax.random.normal(keys[2], (B, KV, L, D), jnp.float32)
    dead = jnp.arange(L)[None, None, :, None] > curs[:, None, None, None]
    ks = vs = None
    if quantized:
        ks = jnp.maximum(jnp.max(jnp.abs(k), -1) / 127.0, 1e-8)
        vs = jnp.maximum(jnp.max(jnp.abs(v), -1) / 127.0, 1e-8)
        k = jnp.clip(jnp.round(k / ks[..., None]), -127, 127)
        v = jnp.clip(jnp.round(v / vs[..., None]), -127, 127)
        k = jnp.where(dead, 127, k).astype(jnp.int8)
        v = jnp.where(dead, 127, v).astype(jnp.int8)
        dead3 = dead[..., 0]
        ks = jnp.where(dead3, POISON, ks)
        vs = jnp.where(dead3, POISON, vs)
    else:
        k = jnp.where(dead, POISON, k)
        v = jnp.where(dead, POISON, v)
    ref = jnp.concatenate([
        _dense_ref(q[b:b + 1], k[b:b + 1], v[b:b + 1], int(curs[b]),
                   None if ks is None else ks[b:b + 1],
                   None if vs is None else vs[b:b + 1])
        for b in range(B)])
    with record_traced() as traced:
        out = decode_attention(q, k, v, jnp.asarray(curs), k_scale=ks,
                               v_scale=vs, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)
    assert traced["decode"] == {f"pallas[hb={hb}]"}


def test_decode_kernel_vector_cursor_matches_broadcast_scalar():
    """A uniform [B] cursor vector must agree exactly with the scalar
    cursor path (same program semantics, different operand rank), and a
    wrong-shaped cursor is rejected."""
    B, H, KV, L, D, cur = 2, 4, 2, 64, 16, 29
    q, k, v, _, _ = _cache(B, H, KV, L, D, cur, seed=9)
    scalar = decode_attention(q, k, v, cur, block_k=16, interpret=True)
    vector = decode_attention(q, k, v, jnp.full((B,), cur, jnp.int32),
                              block_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(scalar), np.asarray(vector))
    with pytest.raises(ValueError, match="cache_index"):
        decode_attention(q, k, v, jnp.zeros((B + 1,), jnp.int32),
                         block_k=16, interpret=True)


def test_decode_kernel_rejects_bad_shapes():
    q, k, v, _, _ = _cache(1, 4, 2, 64, 16, 10)
    with pytest.raises(ValueError, match="multiple of KV"):
        decode_attention(q[:, :3], k, v, 10, interpret=True)
    with pytest.raises(ValueError, match="tile"):
        decode_attention(q, k, v, 10, block_k=48, interpret=True)


def test_decode_block_k_policy():
    assert decode_block_k(1024) == 128          # default tile
    assert decode_block_k(32) == 32             # short caches shrink
    assert decode_block_k(1024, 256) == 256     # explicit override


GPT2_XL_PAGE = dict(kv_heads=25, block_k=64, head_dim=64,
                    cache_dtype=jnp.bfloat16)       # 64 KiB a head


GPT2_XL_ROWS = dict(GPT2_XL_PAGE, paged=True)       # 32 KiB a head


@pytest.mark.parametrize("shape,budget,hb", [
    (GPT2_XL_PAGE, 4 << 20, 25),         # the shipped budget: every head
    (GPT2_XL_PAGE, 25 << 16, 25),        # exactly fits
    (GPT2_XL_PAGE, (25 << 16) - 1, 5),   # one byte short: next divisor
    (GPT2_XL_PAGE, 24 << 16, 5),         # 24 fit, 25 = 5 x 5
    (GPT2_XL_PAGE, 4 << 16, 1),
    (GPT2_XL_PAGE, 0, 1),                # nothing fits: one head anyway
    # D = 128 fills its lanes; the 128-tile of the contiguous kernel
    (dict(kv_heads=32, block_k=128, head_dim=128,
          cache_dtype=jnp.bfloat16), 4 << 20, 32),
    # int8: one byte an element, plus a [bk, 1] f32 scale block for K and
    # for V that pads to a lane tile a position — 320 KiB a head, 12 fit
    (dict(kv_heads=32, block_k=128, head_dim=128,
          cache_dtype=jnp.int8), 4 << 20, 8),
    (dict(kv_heads=8, block_k=128, head_dim=128,
          cache_dtype="bfloat16"), 4 << 20, 8),
    (dict(kv_heads=1, block_k=128, head_dim=64,
          cache_dtype=np.float32), 4 << 20, 1),
    # the page pool: ONE block of a page's rows, a head's K and V side by
    # side in 2 x 64 columns — 32 KiB a head, half the padded pair's
    (GPT2_XL_ROWS, 4 << 20, 25),
    (GPT2_XL_ROWS, 25 << 15, 25),
    (GPT2_XL_ROWS, (25 << 15) - 1, 5),
    (GPT2_XL_ROWS, 0, 1),
    # int8 rows: 16 KiB of payload and two padded scale blocks a head
    (dict(GPT2_XL_ROWS, cache_dtype=jnp.int8), 5 * (1 << 14) + 5 * (1 << 17),
     5),
    # columns of a head block are whole lane tiles, or the whole row:
    # two heads of 2 x 16 fit, and one alone would be 32 lanes
    (dict(kv_heads=2, block_k=16, head_dim=16, cache_dtype=jnp.float32,
          paged=True), 0, 2),
    (dict(kv_heads=8, block_k=16, head_dim=16, cache_dtype=jnp.float32,
          paged=True), 0, 4),
])
def test_decode_head_block_is_a_function_of_shapes_and_dtype(shape, budget,
                                                             hb):
    """The largest divisor of the kv heads whose double-buffered cache
    blocks fit the budget (and, for a page's rows, whose columns Mosaic
    can block); nothing else goes in."""
    assert decode_head_block(vmem_budget=budget, **shape) == hb
    assert shape["kv_heads"] % hb == 0


def test_traced_decode_names_the_head_block(monkeypatch):
    """What a run's headline prints (`traced_name` of `record_traced`'s
    "decode") says which kernel ran and how many kv heads a grid step
    took, also when the budget made it fall back to one; the paged
    kernel over an unquantised pool says that it walks live pages, and
    how many a turn."""
    B, H, KV, L, D, cur = 2, 6, 3, 32, 64, 20
    q, k, v, _, _ = _cache(B, H, KV, L, D, cur)
    pool = jnp.zeros((5, 16, KV * 2 * D), jnp.float32)
    curs = jnp.full((B,), cur, jnp.int32)
    table = jnp.ones((B, 2), jnp.int32)
    with record_traced() as traced:
        decode_attention(q, k, v, cur, block_k=16, interpret=True)
        paged_decode_attention(q, pool, curs, table, interpret=True)
    assert traced_name(traced["decode"]) == \
        "pallas[hb=3]+pallas_paged[live,pages=2,hb=3]"
    monkeypatch.setattr(attention, "_KV_VMEM_BUDGET", 0)
    with record_traced() as traced:
        paged_decode_attention(q, pool, curs, table, interpret=True)
    assert traced_name(traced["decode"]) == "pallas_paged[live,pages=1,hb=1]"
    # an int8 pool stays on the grid form
    scale = jnp.ones((5, KV, 16), jnp.float32)
    with record_traced() as traced:
        paged_decode_attention(q, pool.astype(jnp.int8), curs, table,
                               k_scale=scale, v_scale=scale, interpret=True)
    assert traced_name(traced["decode"]) == "pallas_paged[hb=1]"


@pytest.mark.parametrize("nblk,page_bytes,window,pages", [
    (16, 64 * 3200 * 2, None, 4),     # gpt2-xl: 410 KB pages, a table of 16
    (256, 64 * 2560 * 2, None, 4),    # Phi-4-mini-flash's pool: 327 KB pages
    (9, 64 * 2560 * 2, 512, 3),       # its ring: 9 pages walk 3 + 3 + 3
    (256, 64 * 2560 * 2, 512, 3),     # a window over a long table: the same
    (17, 64 * 3200 * 2, None, 3),     # a prime table: 3 x 6, not 5 x 4
    (4, 1 << 30, None, 1),            # a page over the budget: one a turn
    (3, 1024, None, 3),               # a short table: all of it
])
def test_pages_a_turn_are_a_function_of_shapes_and_dtype(nblk, page_bytes,
                                                         window, pages):
    from mpi_operator_tpu.ops.attention import paged_pages_per_turn
    assert paged_pages_per_turn(nblk, page_bytes, 64, window) == pages


def _e2e(cfg, new_tokens=8, seed=1):
    """Token-exact agreement between the kernel decode path and the dense
    oracle on the SAME params — the end-to-end form of the parity above
    (cache writes, cursor plumbing, and output layout included)."""
    model = CausalLM(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(seed), (2, 5), 0,
                                cfg.vocab_size)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), prompt))["params"]
    ref = generate(model, params, prompt, max_new_tokens=new_tokens,
                   decode_kernel=False)
    out = generate(model, params, prompt, max_new_tokens=new_tokens,
                   decode_kernel=True)
    assert np.array_equal(np.array(ref.tokens), np.array(out.tokens))
    assert bool(jnp.isfinite(out.logprobs).all())


def test_generate_kernel_matches_dense_gpt2():
    _e2e(gpt2_config("test", attention="dense", dtype=jnp.float32,
                     vocab_size=64, max_len=32))


@pytest.mark.slow
def test_generate_kernel_matches_dense_llama_gqa():
    _e2e(llama_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=32))


@pytest.mark.slow
def test_generate_kernel_matches_dense_int8_kv():
    cfg = llama_config("test", attention="dense", dtype=jnp.float32,
                       vocab_size=64, max_len=32)
    _e2e(dataclasses.replace(cfg, kv_cache_dtype="int8"))


def test_decode_kernel_config_falls_back_on_odd_cache_len():
    """A cache length that doesn't tile must silently use the dense path
    (same tokens), not crash — the transformer-side gate."""
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=24)   # 24 % 24 == 0 tiles...
    cfg = dataclasses.replace(cfg, decode_block_k=7)   # ...but 7 doesn't
    _e2e(cfg, new_tokens=4)
