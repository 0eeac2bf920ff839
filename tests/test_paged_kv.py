"""Paged KV cache tests: the block-table serving layout + prefix cache.

Three layers, each pinned against the layer below it:

- `PageAllocator` (serve/slots.py): free-list accounting, refcounted
  prefix chains, LRU eviction with descendant cascade — unit tests plus
  a randomized admit/publish/retire fuzz with the invariant audit
  (`check()`) after every operation.
- `paged_decode_attention` (ops/attention.py): the Pallas kernel over a
  page pool must match the gathered dense oracle, with POISON in every
  page slot past each row's cursor so any stray read is loud.
- The `ServingEngine` over its page pool: token-exact against
  `generate()`, the fixed-batch oracle — same model, same requests, both
  attention paths — including prefix-cache hits, slot reuse, int8
  caches, and the capacity claim (more concurrent requests than
  slots x max_len positions of contiguous rows would hold).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from mpi_operator_tpu.models import CausalLM, gpt2_config
from mpi_operator_tpu.ops import attention
from mpi_operator_tpu.ops.attention import (pack_kv_rows,
                                            paged_decode_attention,
                                            record_traced, traced_name)
from mpi_operator_tpu.serve import (
    EngineConfig, PageAllocator, Request, Scheduler, ServingEngine,
    plan_chunks,
)
from test_serve import _oracle

pytestmark = pytest.mark.serving

POISON = 1e4


# ---------------------------------------------------------------------------
# PageAllocator (no jax)
# ---------------------------------------------------------------------------

def test_page_allocator_lifecycle_and_errors():
    with pytest.raises(ValueError, match="trash"):
        PageAllocator(1, 4)
    a = PageAllocator(5, 4)                  # pages 1..4 usable
    assert a.usable == 4 and a.available == 4 and a.in_use == 0
    p1, p2 = a.alloc(), a.alloc()
    assert p1 != p2 and a.in_use == 2
    a.release(p1)
    assert a.available == 3
    with pytest.raises(RuntimeError, match="double-free"):
        a.release(p1)
    with pytest.raises(ValueError, match="trash"):
        a.release(a.TRASH)
    a.alloc(), a.alloc(), a.alloc()
    with pytest.raises(RuntimeError, match="out of KV pages"):
        a.alloc()                            # 4 live, nothing evictable
    a.check()


def test_page_allocator_prefix_chain_and_eviction():
    a = PageAllocator(6, 2)                  # 5 usable pages
    # request A: prompt pages (1,2) and (3,4), published as a chain
    pa, pb = a.alloc(), a.alloc()
    assert a.publish(pa, -1, (1, 2))
    assert a.publish(pb, pa, (3, 4))
    # a second publisher of the same key loses and keeps its page private
    pc = a.alloc()
    assert not a.publish(pc, -1, (1, 2))
    a.release(pc)                            # unpublished -> free list
    # lookup pins the whole chain; a diverging prompt stops at the match
    chain = a.lookup([1, 2, 3, 4, 9, 9], 3)
    assert chain == [pa, pb] and a.ref[pa] == 2 and a.ref[pb] == 2
    assert a.lookup([1, 2, 9, 9], 2) == [pa]     # second page diverges
    assert a.lookup([7, 7], 1) == []
    assert a.hits == 3 and a.misses == 3
    for p in (pa, pa, pb):                   # drop the lookup pins
        a.release(p)
    # publishers retire: ref-0 published pages park in the evictable LRU
    a.release(pa), a.release(pb)
    assert a.in_use == 0 and a.cached_pages == 2
    a.check()
    # exhaust the free list; the next allocs evict pa oldest-first, and
    # evicting pa CASCADES over pb (a child is unreachable without its
    # parent, and a recycled parent id must not match stale child keys)
    got = {a.alloc() for _ in range(5)}
    assert got == {1, 2, 3, 4, 5} and a.evictions == 2
    assert a.lookup([1, 2, 3, 4], 2) == []   # cache fully gone
    a.check()


def test_page_allocator_pin_revives_from_lru():
    a = PageAllocator(4, 2)
    p = a.alloc()
    assert a.publish(p, -1, (5, 6))
    a.release(p)
    assert a.cached_pages == 1
    assert a.lookup([5, 6, 7], 1) == [p]     # pin: LRU -> ref 1
    assert a.ref[p] == 1 and a.cached_pages == 0
    a.release(p)
    assert a.cached_pages == 1               # still published
    a.check()


def test_page_allocator_reset_rewinds_everything():
    a = PageAllocator(6, 2)
    p = a.alloc()
    a.publish(p, -1, (1, 2))
    a.alloc()
    a.reset()
    assert a.available == a.usable == 5 and a.in_use == 0
    assert a.cached_pages == 0 and a.hits == a.misses == 0
    assert a.lookup([1, 2], 1) == []
    a.check()


def test_page_allocator_fuzz_no_leaks_no_aliasing():
    """Randomized admit / publish / retire against the invariant audit:
    after every operation the free/evictable/live sets must partition
    the pool, no private page may be held by two requests, and draining
    all requests must return every reference."""
    rs = np.random.RandomState(0)
    ps = 4
    for trial in range(3):
        a = PageAllocator(num_pages=13, page_size=ps)
        # a small prompt universe so prefix collisions actually happen
        prompts = [tuple(rs.randint(0, 3, (ps * rs.randint(1, 4),)))
                   for _ in range(8)]
        live = []          # (chain pages, private pages, prompt, pub state)
        for _ in range(400):
            op = rs.rand()
            if op < 0.45:                                # admit
                prompt = prompts[rs.randint(len(prompts))]
                full = len(prompt) // ps
                need = full + 1                          # one decode page
                chain = a.lookup(prompt, full)
                if a.available < need - len(chain):
                    for p in reversed(chain):
                        a.release(p)
                else:
                    priv = [a.alloc() for _ in range(need - len(chain))]
                    live.append({"chain": chain, "priv": priv,
                                 "prompt": prompt, "pub": len(chain),
                                 "parent": chain[-1] if chain else -1})
            elif op < 0.65 and live:                     # publish one page
                st = live[rs.randint(len(live))]
                full = len(st["prompt"]) // ps
                k = st["pub"]
                if k < full:
                    page = (st["chain"] + st["priv"])[k]
                    tok = st["prompt"][k * ps:(k + 1) * ps]
                    if a.publish(page, st["parent"], tok):
                        st["pub"] = k + 1
                        st["parent"] = page
                    else:
                        st["pub"] = full     # lost the race: stop
            elif live:                                   # retire
                st = live.pop(rs.randint(len(live)))
                for p in st["chain"] + st["priv"]:
                    a.release(p)
            a.check()
            # no private page aliased between two live requests
            privs = [p for st in live for p in st["priv"]]
            assert len(privs) == len(set(privs))
            held = sum(len(st["chain"]) + len(st["priv"]) for st in live)
            assert a.in_use <= held          # shared pages count once
        while live:
            st = live.pop()
            for p in st["chain"] + st["priv"]:
                a.release(p)
            a.check()
        assert a.in_use == 0                 # no leaks after full drain


# ---------------------------------------------------------------------------
# chunk planning from a cached span + packing admission (no jax)
# ---------------------------------------------------------------------------

def test_plan_chunks_start_left_aligned_tail():
    # start at a cached span: windows begin there, never reach backwards
    assert plan_chunks(20, (4, 16), start=16) == [(16, 4)]
    # ragged tail LEFT-aligned with padding (right-aligning would rewrite
    # shared pages another request may be attending)
    assert plan_chunks(21, (4, 16), start=16) == [(16, 16)]
    assert plan_chunks(50, (4, 16), start=16) == [(16, 16), (32, 16),
                                                  (48, 4)]
    assert plan_chunks(16, (4, 16), start=16) == []
    with pytest.raises(ValueError, match="outside"):
        plan_chunks(8, (4, 16), start=9)
    for n in range(1, 60):
        for start in range(0, n + 1, 4):
            covered = set()
            for w, size in plan_chunks(n, (4, 16), start=start):
                assert w >= start            # never rewrites cached pages
                covered.update(range(w, w + size))
            assert covered.issuperset(range(start, n))


def test_pages_needed_and_packing_admission():
    ps = 4
    # span = prompt-1 prefill positions + max_new decode writes
    assert Scheduler.pages_needed(Request(0, [1] * 5, 4), ps) == 2
    assert Scheduler.pages_needed(Request(0, [1] * 5, 6), ps) == 3
    assert Scheduler.pages_needed(Request(0, [1], 1), ps) == 1
    a = PageAllocator(6, ps)                 # 5 usable
    s = Scheduler((4,), max_len=32, admit_lookahead=4)
    s.submit(Request(0, [1] * 5, 6))         # 3 pages
    [st0] = s.admit([0, 1], now=0.0, allocator=a)
    assert st0.req.id == 0 and a.in_use == 3
    s.submit(Request(1, [1] * 9, 8))         # 4 pages: does NOT fit
    s.submit(Request(2, [2] * 3, 4))         # 2 pages: fits
    admitted = s.admit([1], now=0.0, allocator=a)
    # packing: the short request behind the too-big head rides along
    assert [st.req.id for st in admitted] == [2]
    assert s.queue[0].id == 1                # FCFS head preserved
    # head fits again once the first request's pages release
    for st in (st0, admitted[0]):
        s.retire(st)
        for p in st.owned_pages:
            a.release(p)
    assert [st.req.id for st in s.admit([0, 1], now=0.0, allocator=a)] \
        == [1]
    a.check()


def test_admission_reserves_worst_case_and_rejects_when_full():
    ps = 4
    a = PageAllocator(5, ps)                 # 4 usable
    s = Scheduler((4,), max_len=32, admit_lookahead=2)
    s.submit(Request(0, [1] * 5, 6))         # 3 pages -> fits
    s.submit(Request(1, [1] * 5, 6))         # 3 pages -> must wait
    admitted = s.admit([0, 1], now=0.0, allocator=a)
    assert [st.req.id for st in admitted] == [0]
    assert admitted[0].page_table[:3] != [0, 0, 0]
    assert len(s.queue) == 1                 # no partial reservation
    assert a.in_use == 3                     # nothing leaked by the miss
    a.check()


# ---------------------------------------------------------------------------
# the paged Pallas kernel vs the gathered dense oracle
# ---------------------------------------------------------------------------

def _dense_ref(q, k, v, curs, k_scale=None, v_scale=None):
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[..., None]
        v = v.astype(jnp.float32) * v_scale[..., None]
    B, KV, L, D = k.shape
    H = q.shape[1]
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (D ** 0.5)
    mask = jnp.arange(L)[None, None] <= curs[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,bhld->bhd", p, v.astype(jnp.float32))


def _scatter_pages(contig, pt, NP, ps):
    """[B, KV, L, *] logical rows -> [NP, KV, ps, *] pool via the page
    table, POISON in every pool slot no table entry maps (incl. trash)."""
    B, KV, L = contig.shape[:3]
    pool = np.full((NP, KV, ps) + contig.shape[3:], POISON,
                   contig.dtype if contig.dtype != np.int8 else np.float32)
    pool = pool.astype(contig.dtype)
    if contig.dtype == np.int8:
        pool[:] = 127
    for b in range(B):
        for j in range(L // ps):
            pool[pt[b, j]] = contig[b, :, j * ps:(j + 1) * ps]
    return pool


def _pool_rows(k_pages, v_pages):
    """Per-head pages [NP, KV, ps, D] of K and of V -> the pool's rows
    [NP, ps, KV * 2D] (`ops.attention.kv_row_width`)."""
    return pack_kv_rows(jnp.asarray(k_pages).transpose(0, 2, 1, 3),
                        jnp.asarray(v_pages).transpose(0, 2, 1, 3))


def _paged_vs_dense(H, KV, D, quantized, curs, shared=(), ps=16, nblk=4):
    """The paged kernel against the dense oracle on a shuffled page
    table; beyond-cursor pool content is poisoned so a wrong page
    resolution or missing mask shows up as a huge error. Rows in
    `shared` read their first page through ONE physical page (the
    prefix-cache layout); their cursors must sit past it."""
    curs = np.asarray(curs, np.int32)
    B, L = len(curs), ps * nblk
    NP = B * nblk + 2                        # trash + one never-mapped
    rs = np.random.RandomState(5)
    # distinct physical pages per logical block, shuffled across the pool
    perm = rs.permutation(np.arange(1, NP - 1)).reshape(B, nblk)
    pt = perm.astype(np.int32)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, D), jnp.float32)
    k = rs.randn(B, KV, L, D).astype(np.float32)
    v = rs.randn(B, KV, L, D).astype(np.float32)
    for b in shared[1:]:
        assert curs[b] >= ps - 1 and curs[shared[0]] >= ps - 1
        pt[b, 0] = pt[shared[0], 0]
        k[b, :, :ps] = k[shared[0], :, :ps]
        v[b, :, :ps] = v[shared[0], :, :ps]
    dead = np.arange(L)[None, None, :, None] > curs[:, None, None, None]
    ks = vs = ksp = vsp = None
    if quantized:
        ks = np.maximum(np.abs(k).max(-1) / 127.0, 1e-8).astype(np.float32)
        vs = np.maximum(np.abs(v).max(-1) / 127.0, 1e-8).astype(np.float32)
        k = np.clip(np.round(k / ks[..., None]), -127, 127)
        v = np.clip(np.round(v / vs[..., None]), -127, 127)
        k = np.where(dead, 127, k).astype(np.int8)
        v = np.where(dead, 127, v).astype(np.int8)
        ks = np.where(dead[..., 0], POISON, ks)
        vs = np.where(dead[..., 0], POISON, vs)
        ksp = jnp.asarray(_scatter_pages(ks[..., None], pt, NP, ps)[..., 0])
        vsp = jnp.asarray(_scatter_pages(vs[..., None], pt, NP, ps)[..., 0])
    else:
        k = np.where(dead, POISON, k)
        v = np.where(dead, POISON, v)
    pool = _pool_rows(_scatter_pages(k, pt, NP, ps),
                      _scatter_pages(v, pt, NP, ps))
    ref = _dense_ref(q, jnp.asarray(k), jnp.asarray(v),
                     jnp.asarray(curs),
                     None if ks is None else jnp.asarray(ks),
                     None if vs is None else jnp.asarray(vs))
    with record_traced() as traced:
        out = paged_decode_attention(q, pool, jnp.asarray(curs),
                                     jnp.asarray(pt), k_scale=ksp,
                                     v_scale=vsp, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)
    return traced["decode"]


# MHA and GQA at the old shapes; then head counts no power of two divides
# (5, and gpt2-xl's 25 heads of 64), as MHA and as GQA (G = 2 over 5)
@pytest.mark.parametrize("H,KV,D", [(4, 4, 16), (4, 2, 16), (5, 5, 64),
                                    (25, 25, 64), (10, 5, 16)])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_kernel_matches_dense(H, KV, D, quantized):
    """Per-row cursors at block starts/interiors/ends; every kv head of a
    page in one grid step (the default budget holds them all)."""
    traced = _paged_vs_dense(H, KV, D, quantized, [0, 17, 31, 63])
    # an int8 pool stays on the grid form; an unquantised one is walked,
    # the table's four pages in one turn
    assert traced == {f"pallas_paged[hb={KV}]" if quantized
                      else f"pallas_paged[live,pages=4,hb={KV}]"}


@pytest.mark.parametrize("fit,hb", [(6, 6), (4, 3), (1, 1), (0, 1)])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_kernel_head_blocks(monkeypatch, fit, hb, quantized):
    """A VMEM budget that holds `fit` of 6 kv heads (GQA, G = 2): the
    grid runs 6 // hb head blocks a row and the result does not change.
    Rows: cursor 0, a cursor on a page's last position, and two rows
    sharing their prefix page; dead pages stay poisoned and unread."""
    ps, D = 16, 64
    # what decode_head_block counts for one head: its 2D columns of a
    # page's rows, two buffers (int8: plus a [ps, 1] f32 scale block for
    # K and for V, padded to 128 lanes, two buffers each)
    per_head = (2 * ps * 2 * D + 4 * ps * 128 * 4 if quantized
                else 2 * ps * 2 * D * 4)
    monkeypatch.setattr(attention, "_KV_VMEM_BUDGET", fit * per_head)
    traced = _paged_vs_dense(12, 6, D, quantized, [0, ps - 1, 2 * ps + 3, 63],
                             shared=(1, 2), ps=ps)
    # the walk under the same budget: one page a turn, the head block's
    # columns of it copied by themselves
    assert traced == {f"pallas_paged[hb={hb}]" if quantized
                      else f"pallas_paged[live,pages=1,hb={hb}]"}


def test_paged_kernel_shared_pages_between_rows():
    """Two rows whose tables alias the SAME physical prefix page (the
    prefix-cache layout) read identical K/V through it."""
    B, H, KV, D, ps, nblk = 2, 4, 2, 16, 16, 2
    NP = 4
    pt = np.array([[1, 2], [1, 3]], np.int32)    # page 1 shared
    curs = np.array([ps + 3, ps + 7], np.int32)
    rs = np.random.RandomState(9)
    pool_k = rs.randn(NP, KV, ps, D).astype(np.float32)
    pool_v = rs.randn(NP, KV, ps, D).astype(np.float32)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, D), jnp.float32)
    # gather the logical view per row, then dense-reference it
    gk = np.stack([np.concatenate([pool_k[p] for p in pt[b]], axis=1)
                   for b in range(B)])
    gv = np.stack([np.concatenate([pool_v[p] for p in pt[b]], axis=1)
                   for b in range(B)])
    ref = _dense_ref(q, jnp.asarray(gk), jnp.asarray(gv),
                     jnp.asarray(curs))
    out = paged_decode_attention(q, _pool_rows(pool_k, pool_v),
                                 jnp.asarray(curs), jnp.asarray(pt),
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


# ---------------------------------------------------------------------------
# the walk of a row's live pages (`_paged_walk_kernel`)
# ---------------------------------------------------------------------------

def _walk_vs_gathered(H, KV, D, cursors, ps=8, nblk=12, window=None,
                      pages=None, dtype=jnp.float32, atol=2e-5):
    """The walking kernel against dense attention over the gathered
    table. A row's live span is logical pages first .. last (`window`
    moves first); EVERY table entry outside it points at page 0, which
    holds inf: a page the walk must not fetch — a dead entry, a page
    behind the window, a turn's pages past `last` — turns the result
    into nan. `pages` None: the public entry point and its own choice of
    pages a turn; else that many through `_paged_walk_call`."""
    cur = np.asarray(cursors, np.int32)
    B, L = len(cur), ps * nblk
    last = np.minimum(cur // ps, nblk - 1)
    first = np.zeros_like(last) if window is None else np.minimum(
        np.maximum(cur - window + 1, 0) // ps, last)
    rs = np.random.RandomState(3)
    blocks = np.arange(nblk)[None]
    live = (blocks >= first[:, None]) & (blocks <= last[:, None])
    table = np.where(live, 1 + rs.permutation(B * nblk).reshape(B, nblk), 0)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    q = jax.random.normal(keys[0], (B, H, D), dtype)
    finite = jax.random.normal(keys[1], (1 + B * nblk, ps, KV * 2 * D),
                               dtype)
    pool = finite.at[0].set(jnp.inf)
    # the reference reads zeros where the kernel must read nothing
    rows = finite.at[0].set(0)[table].reshape(B, L, KV, 2, D).astype(
        jnp.float32)
    k = jnp.repeat(rows[:, :, :, 0], H // KV, axis=2)
    v = jnp.repeat(rows[:, :, :, 1], H // KV, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32), k) / D ** 0.5
    p = np.arange(L)[None, None]
    seen = p <= np.minimum(cur, L - 1)[:, None, None]
    if window is not None:
        seen &= p > (cur - window)[:, None, None]
    want = jnp.einsum("bht,bthd->bhd",
                      jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v)
    args = (pool, jnp.asarray(cur), jnp.asarray(table, jnp.int32))
    with record_traced() as traced:
        if pages is None:
            got = paged_decode_attention(q, *args, window=window,
                                         interpret=True)
        else:
            got = attention._paged_walk_call(
                q.reshape(B, KV, H // KV, D), *args, None, window, True,
                pages=pages)
    np.testing.assert_allclose(np.asarray(want),
                               np.asarray(got, np.float32), atol=atol)
    return traced_name(traced["decode"])


# pages of 8, a table of 12, 4 pages a turn: the turns' edges lie at
# positions 32 and 64
_WALK_CURSORS = {
    "first-page": (0, 7, 8),
    "a-turns-edge": (23, 24, 31, 32, 39, 40),    # the edge -1, 0, +1 page
    "the-second-edge": (63, 64, 71, 72),
    "table-end-and-past": (94, 95, 96, 101, 200),
    # rows of one call at unrelated depths: a row's last turn fetches the
    # first pages of a row that is nowhere near it
    "unrelated-depths": (95, 0, 40, 8, 77, 3),
    # a turn copies its live pages alone (PR 49): what the dead part of a
    # slot holds is whatever was there. Rows of one live page straight
    # after rows that filled the table, three turns through both slots:
    # stale rows in the dead part of either
    "one-page-after-a-full-table": (95, 3, 200, 0, 95, 7),
    # free rows sit at cursor 0 on the trash page, between decoding rows
    "free-rows-between": (40, 0, 0, 77, 0, 20, 0, 0),
    # the call's first turns land in slots nobody wrote, and the interpreter
    # starts scratch memory as nan (`test_the_interpreter_...` below): one
    # live page, then three, which are attended four wide
    "first-rows-short-in-unwritten-slots": (5, 20, 95, 40),
    # turns of 1, 2 and 3 live pages, first and after a full one
    "short-turns-by-live-pages": (7, 15, 23, 39, 47, 55, 87),
}


def test_the_interpreter_starts_scratch_memory_as_nan():
    """What makes "first-rows-short-in-unwritten-slots" a test of the
    kernel's own zeroing: a slot's part that no copy wrote reads nan
    here, and 0 x nan in p . V would reach the output."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, scratch):
        o_ref[:] = x_ref[:] + scratch[:]
    out = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        interpret=True)(jnp.zeros((8, 128), jnp.float32))
    assert np.isnan(np.asarray(out)).all()


def _turns_by_hand(cur, ps, nblk, pages, window):
    """A row's turns as lists of live logical pages, by enumeration."""
    last = min(cur // ps, nblk - 1)
    first = 0 if window is None else min(max(cur - window + 1, 0) // ps, last)
    live = list(range(first, last + 1))
    return [live[i:i + pages] for i in range(0, len(live), pages)]


@pytest.mark.parametrize("window", [None, 20], ids=["whole", "window20"])
@pytest.mark.parametrize("cur", [
    0, 7, 8,                     # the first page and the second
    23, 24, 31, 32, 39, 40,      # a turn's edge -1, 0, +1 page
    63, 64, 71, 72,              # the second edge
    87, 88, 94, 95,              # the table's last page: a full last turn
    96, 101, 200,                # past the table
])
def test_turn_pages_counts_the_live_pages_of_a_turn(cur, window):
    """`_turn_pages` over `_live_span`, what a turn of the walk copies
    AND what it waits for: pages of 8, a table of 12, 4 a turn. Every
    turn of the walk starts where the last one ended, holds 1 .. 4 live
    pages, and the turns together are the live span exactly."""
    ps, nblk, pages = 8, 12, 4
    want = _turns_by_hand(cur, ps, nblk, pages, window)
    first, last = attention._live_span(cur, ps, nblk, window)
    assert (int(first), int(last)) == (want[0][0], want[-1][-1])
    assert (int(last) - int(first)) // pages + 1 == len(want)
    for turn, live in enumerate(want):
        page0, n = attention._turn_pages(first, last, turn, pages)
        assert (int(page0), int(n)) == (live[0], len(live)), (turn, live)
        assert 1 <= int(n) <= pages


@pytest.mark.parametrize("cursors", list(_WALK_CURSORS.values()),
                         ids=list(_WALK_CURSORS))
@pytest.mark.parametrize("H,KV,D", [
    (5, 5, 64),       # gpt2-xl's form: G 1, K and V halves of a lane tile
    (8, 2, 128),      # Phi-4-mini-flash's pairs: G 4, scores on the K lanes
    (6, 3, 64),       # GQA on the padded-query form
    (4, 2, 256),      # GQA, a head's K two lane tiles
], ids=["mha64", "pairs128", "gqa64", "gqa256"])
def test_walk_matches_dense_and_reads_nothing_dead(H, KV, D, cursors):
    name = _walk_vs_gathered(H, KV, D, cursors, pages=4)
    assert name == f"pallas_paged[live,pages=4,hb={KV}]"


@pytest.mark.parametrize("pages", [1, 2, 5, 12, 16])
@pytest.mark.parametrize("H,KV,D", [(5, 5, 64), (8, 2, 128)],
                         ids=["mha64", "pairs128"])
def test_walk_at_any_pages_a_turn(H, KV, D, pages):
    """One page a turn, a count that does not divide the table, the whole
    table and more than the table: the result does not change."""
    _walk_vs_gathered(H, KV, D, (0, 95, 33, 64, 300, 17), pages=pages)


@pytest.mark.parametrize("cursors", [
    (0, 5, 18),          # contexts shorter than the window
    (19, 20, 21),        # the window's own edge: position 0 leaves at 20
    (27, 28, 35, 36),    # a window that starts mid-page, and on a page's edge
    (95, 96, 110, 112),  # the table's end and past it, the window still on it
    (95, 2, 50, 21),     # unrelated depths
    # a window of three live pages (16-23 .. 32-39 under cursor 39): a
    # short LAST turn at 2 a turn, a short only turn at 4, after rows whose
    # window fills both slots
    (95, 39, 47, 0, 31),
], ids=["short", "window-edge", "mid-page", "past-table", "unrelated",
        "short-last-turn"])
@pytest.mark.parametrize("H,KV,D,pages", [
    (5, 5, 64, None), (8, 2, 128, None), (8, 2, 128, 1), (6, 3, 64, 2)],
    ids=["mha64", "pairs128", "pairs128-1", "gqa64-2"])
def test_walk_under_a_window_starts_at_the_windows_page(H, KV, D, pages,
                                                         cursors):
    """window 20 over pages of 8: at most four pages are live, the pages
    behind them point at the inf page like the dead ones past the
    cursor."""
    name = _walk_vs_gathered(H, KV, D, cursors, window=20, pages=pages)
    if pages is None:       # the window's four pages, not the table's 12
        assert name == f"pallas_paged[live,pages=4,hb={KV}]"


def test_walk_in_bfloat16_sums_in_float32():
    _walk_vs_gathered(8, 2, 128, (95, 0, 40, 8), dtype=jnp.bfloat16,
                      atol=3e-2)
    _walk_vs_gathered(5, 5, 64, (95, 0, 40, 8), dtype=jnp.bfloat16,
                      atol=3e-2)


@pytest.mark.parametrize("fit,hb", [(2, 2), (1, 1)])
def test_walk_by_head_blocks_of_whole_lane_tiles(monkeypatch, fit, hb):
    """A budget that holds `fit` of 4 pairs of 128 (G = 2): the grid runs
    4 // hb head blocks a row, each copying its own columns of a page,
    and a row's last block fetches the next row's first."""
    ps, D = 8, 128
    monkeypatch.setattr(attention, "_KV_VMEM_BUDGET",
                        fit * 2 * ps * 2 * D * 4)
    name = _walk_vs_gathered(8, 4, D, (0, 95, 33, 64, 300), ps=ps)
    assert name == f"pallas_paged[live,pages=1,hb={hb}]"


# ---------------------------------------------------------------------------
# the pool the model writes: rows [NP, ps, KV * 2D], one form for every cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_cache_dtype", [None, "int8"])
@pytest.mark.parametrize("preset", ["gpt2", "llama"])       # MHA, GQA + RoPE
def test_paged_cache_is_one_pool_of_rows_and_junk_writes_drop(preset,
                                                              kv_cache_dtype):
    """White box. A multi-token call writes each position's row at
    (pages[pos // ps], pos % ps) with head h's K in columns
    [2D*h, 2D*h + D) and its V in the next D — the values the lockstep
    model's cache holds at the same positions — and a row at junk positions
    (>= max_len: a padded tail, a non-member of a prefill call) writes
    nothing anywhere, the trash page included."""
    from mpi_operator_tpu.models.generate import decode_model
    from mpi_operator_tpu.models.transformer import llama_config
    make = gpt2_config if preset == "gpt2" else llama_config
    cfg = make("test", attention="dense", dtype=jnp.float32, vocab_size=64,
               max_len=32, kv_cache_dtype=kv_cache_dtype)
    model = CausalLM(cfg)
    KV, D, ps, NP = cfg.kv_heads, cfg.head_dim, 8, 9
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), tokens))["params"]
    positions = jnp.asarray([[6, 7, 8, 9], [32, 33, 34, 35]], jnp.int32)
    pages = jnp.asarray([[3, 5, 0, 0], [7, 8, 0, 0]], jnp.int32)

    def cache_of(dmodel, **kw):
        return dmodel.apply({"params": params}, tokens, positions=positions,
                            with_head=False, mutable=["cache"],
                            **kw)[1]["cache"]
    paged = cache_of(decode_model(model, False, page_size=ps,
                                  num_pages=NP), pages=pages)
    # the lockstep model (generate()'s) over row 0 alone, its cursor at
    # the row's first position over an empty cache
    lock = decode_model(model, False)

    def lockstep(cache):
        return lock.apply({"params": params, **cache}, tokens[:1],
                          positions=positions[:1], with_head=False,
                          mutable=["cache"])[1]["cache"]
    empty = jax.tree.map(
        lambda x: (jnp.full((), 6, x.dtype) if x.shape == ()
                   else jnp.zeros(x.shape, x.dtype)),
        jax.eval_shape(lambda: lockstep({})))
    contig = lockstep({"cache": empty})
    leaves = lambda tree, name: [                               # noqa: E731
        x for p_, x in jax.tree_util.tree_leaves_with_path(tree)
        if name in jax.tree_util.keystr(p_)]
    assert not leaves(paged, "cached_key") and not leaves(paged,
                                                          "cached_value")
    pools = leaves(paged, "cached_kv")
    assert len(pools) == cfg.num_layers
    for pool, ck, cv in zip(pools, leaves(contig, "cached_key"),
                            leaves(contig, "cached_value")):
        assert pool.shape == (NP, ps, KV * 2 * D)
        assert pool.dtype == (jnp.int8 if kv_cache_dtype else jnp.float32)
        rows = np.asarray(pool).reshape(NP, ps, KV, 2, D)
        want = np.zeros_like(rows)
        written = np.zeros((NP, ps), bool)
        for pos in (6, 7, 8, 9):                    # row 0; row 1 is junk
            at = (int(pages[0, pos // ps]), pos % ps)
            written[at] = True
            want[at][:, 0] = np.asarray(ck)[0, :, pos]
            want[at][:, 1] = np.asarray(cv)[0, :, pos]
        # the two paths sum attention in different orders: below the
        # first layer the cached values agree to rounding (one int8 step)
        np.testing.assert_allclose(rows, want,
                                   atol=1 if kv_cache_dtype else 1e-5)
        assert rows[written].any() and not rows[~written].any()
        assert not rows[0].any()                    # nothing in the trash
    for name in ("key_scale", "value_scale") if kv_cache_dtype else ():
        for plane, flat in zip(leaves(paged, name), leaves(contig, name)):
            assert plane.shape == (NP, KV, ps) and plane.dtype == jnp.float32
            want = np.zeros((NP, KV, ps), np.float32)
            want[3, :, 6:8] = np.asarray(flat)[0, :, 6:8]
            want[5, :, 0:2] = np.asarray(flat)[0, :, 8:10]
            np.testing.assert_allclose(np.asarray(plane), want, rtol=1e-4)
            assert not np.asarray(plane)[want == 0].any()


# ---------------------------------------------------------------------------
# the engine over its page pool vs generate(), the oracle
# ---------------------------------------------------------------------------

def _setup(decode_kernel=False, kv_cache_dtype=None, slots=4,
           page_size=8, num_pages=None, max_len=64):
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=max_len,
                      kv_cache_dtype=kv_cache_dtype)
    model = CausalLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), probe))["params"]
    paged = ServingEngine(model, params, EngineConfig(
        slots=slots, chunk_buckets=(4, 8), decode_kernel=decode_kernel,
        page_size=page_size, num_pages=num_pages))
    return (lambda req: _oracle(model, params, req)), paged


def _mixed_trace(n=8, seed=7, eos=None):
    rs = np.random.RandomState(seed)
    lens = [(1, 6), (3, 9), (9, 4), (14, 7), (5, 5), (7, 8), (12, 6),
            (2, 7)]
    return [Request(i, list(rs.randint(0, 64, (p,))), max_new_tokens=m,
                    eos_id=eos)
            for i, (p, m) in enumerate(lens[:n])]


@pytest.mark.parametrize("decode_kernel", [False, True])
def test_paged_engine_token_exact_vs_contiguous(decode_kernel):
    """The acceptance gate: greedy decode through the page pool is
    token-for-token identical to generate() on every request of a
    trace — mixed prompt lengths, more requests than slots (slot AND
    page reuse across retire/admit), dense and kernel paths."""
    oracle, paged = _setup(decode_kernel)
    trace = _mixed_trace()
    got = paged.run(trace)
    for r in trace:
        assert got[r.id].tokens == oracle(r), f"request {r.id} diverged"
        assert got[r.id].finish_reason == "length"
    alloc = paged.page_allocator
    alloc.check()
    assert alloc.in_use == 0                 # every page released
    counts = paged.compile_counts()
    assert counts["step"] == 1 and counts["prefill"] <= 2


def test_paged_engine_int8_cache_token_exact():
    """The quantized cache pages ([NP, KV, ps] scale planes) through the
    same oracle: generate()'s int8 rows vs the int8 pool, dense path."""
    oracle, paged = _setup(kv_cache_dtype="int8")
    trace = _mixed_trace(n=5)
    got = paged.run(trace)
    for r in trace:
        assert got[r.id].tokens == oracle(r), f"request {r.id} diverged"


def test_paged_engine_eos_retirement_reuses_pages():
    """EOS mid-flight: retired requests release pages that later
    arrivals re-allocate; tokens still match the oracle's."""
    oracle, paged = _setup()
    eos = oracle(_mixed_trace(n=1)[0])[2]
    trace = _mixed_trace(eos=eos)            # 8 requests over 4 slots
    got = paged.run(trace)
    assert any(r.finish_reason == "eos" for r in got.values())
    for r in trace:
        assert got[r.id].tokens == oracle(r)
    assert paged.page_allocator.in_use == 0


@pytest.mark.parametrize("decode_kernel", [False, True])
def test_prefix_hit_token_exact_and_skips_prefill(decode_kernel):
    """A request sharing a cached prompt prefix admits with
    cached_tokens > 0, runs FEWER prefill chunks, produces the exact
    oracle tokens, and reaches its first token faster from admission
    (the queue-independent TTFT the bench reports)."""
    oracle, paged = _setup(decode_kernel)
    rs = np.random.RandomState(3)
    shared = list(rs.randint(0, 64, (40,)))      # 5 full pages of 8
    cold = Request(0, shared + list(rs.randint(0, 64, (3,))), 6)
    hot = Request(1, shared + list(rs.randint(0, 64, (3,))), 6)
    got0 = paged.run([cold])                 # publishes the 5 pages
    got1 = paged.run([hot])                  # pins them
    assert got0[0].tokens == oracle(cold)
    assert got1[1].tokens == oracle(hot)
    assert got0[0].cached_tokens == 0
    assert got1[1].cached_tokens == 40
    # the hit skipped the shared prefill: first token comes faster from
    # admission (5 chunk programs of work it never ran)
    t_cold = got0[0].token_times[0] - got0[0].admitted_at
    t_hot = got1[1].token_times[0] - got1[1].admitted_at
    assert t_hot < t_cold
    alloc = paged.page_allocator
    assert alloc.hits == 5 and alloc.cached_pages == 5
    alloc.check()


def test_prefix_divergence_is_copy_on_write():
    """Two prompts equal through page 2 then diverging INSIDE page 3:
    the hit stops at the divergence page, which stays private — the
    original's cached page is untouched and both match the oracle."""
    oracle, paged = _setup()
    rs = np.random.RandomState(13)
    head = list(rs.randint(0, 64, (16,)))        # 2 full pages of 8
    a = Request(0, head + list(rs.randint(0, 64, (7,))), 5)
    b = Request(1, head + list(rs.randint(0, 64, (7,))), 5)
    got_a = paged.run([a])
    got_b = paged.run([b])
    assert got_a[0].tokens == oracle(a)
    assert got_b[1].tokens == oracle(b)
    assert got_b[1].cached_tokens == 16          # only the shared pages
    # replaying A must still hit ITS chain exactly (page 3 not clobbered)
    got_a2 = paged.run([a])
    assert got_a2[0].tokens == oracle(a)
    paged.page_allocator.check()


def test_paged_capacity_beats_contiguous_at_equal_bytes():
    """The capacity claim: a cache of 2 x max_len positions held as
    contiguous rows serves 2 requests at a time, whatever their length.
    The same positions as 16 (+1) pages of 8 sustain strictly more,
    because short requests reserve their actual worst case, not a whole
    row."""
    budget_rows, max_len, ps = 2, 64, 8
    oracle, paged_wide = _setup(
        slots=6, page_size=ps, max_len=max_len,
        num_pages=budget_rows * (max_len // ps) + 1)   # + trash
    assert paged_wide.page_allocator.usable * ps == budget_rows * max_len
    # 6 short requests: each needs (6-2+6)//8+1 = 2 pages — the pool
    # fits 6 concurrently (12 of 16 pages), contiguous rows cap at 2
    reqs = [Request(i, [int(t) for t in
                        np.random.RandomState(i).randint(0, 64, (6,))],
                    max_new_tokens=6) for i in range(6)]
    got = paged_wide.run(reqs)
    for r in reqs:
        assert got[r.id].tokens == oracle(r)
    assert paged_wide.occupancy_peak > budget_rows
    assert paged_wide.pages_in_use_peak <= budget_rows * (max_len // ps)


def test_paged_engine_rejects_unservable_request():
    """A request whose worst-case span exceeds the whole pool can never
    admit — run() rejects it up front instead of livelocking."""
    _, paged = _setup(num_pages=4, page_size=8)  # 3 usable pages
    with pytest.raises(ValueError, match="KV pages"):
        paged.run([Request(0, [1] * 20, max_new_tokens=20)])
