"""Slot and page bookkeeping for the fixed-shape serving cache.

The device cache is one global pool of fixed-size pages per layer
(transformer.py decode_page_size) and NEVER changes shape: requests come
and go by host-side bookkeeping only. A slot is a row of the decode
batch with a cursor and a page table; `PageAllocator` here owns the
physical pages: a free list, per-page refcounts, and the prefix cache
that lets requests sharing a prompt prefix resolve to the SAME physical
pages and skip prefilling them. A freed slot is just a row whose cursor
resets and whose pages go back, and the stale K/V they hold is
unreachable: every row attends only positions <= its own cursor through
its own table, and a page's new owner rewrites each position before its
cursor gets there. That is the whole trick that makes
admission/retirement free of recompiles.

This module owns which row belongs to which request and builds the
per-step cursor/token/sampling arrays the compiled decode step consumes.
"""
from __future__ import annotations

from bisect import insort
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .scheduler import RequestState


#: chained prefix-cache key: (parent physical page id, the page's token
#: window). Chaining matters because K/V at position j depends on the
#: WHOLE token prefix (layers > 0 attend backwards), so two pages holding
#: identical tokens are interchangeable only when everything before them
#: matched too — which the parent link encodes transitively. Exact tuple
#: equality (dict keys), never a lossy hash: a collision would silently
#: serve another prompt's K/V.
PrefixKey = Tuple[int, Tuple[int, ...]]


def prefix_chain_windows(prompt: Sequence[int], page_size: int,
                         full_pages: Optional[int] = None,
                         ) -> List[Tuple[int, ...]]:
    """The page-aligned token windows of `prompt`'s complete pages — the
    token half of each chained PrefixKey, in chain order. This is the
    SINGLE source of the keying both sides of the front door use: the
    allocator's lookup/probe walk these windows against its cache, and
    the serving router scores replica affinity over the same windows —
    so a change to the keying here moves router and replica together
    (no silent divergence)."""
    if full_pages is None:
        full_pages = max(0, (len(prompt) - 1) // page_size)
    return [tuple(int(t) for t in prompt[k * page_size:(k + 1) * page_size])
            for k in range(full_pages)]


class PageAllocator:
    """Physical KV pages for the serving cache: a free list,
    per-page refcounts, and the prompt-prefix cache.

    Page 0 is the reserved TRASH page — unallocated page-table entries
    point at it so the fixed-shape decode/prefill programs always have a
    legal write target for masked rows; it is never handed out.

    Lifecycle of a page:
      free list ──alloc()──▶ live (ref 1) ──release()──▶
        · uncached page: straight back to the free list;
        · cached page (published prompt prefix): into the EVICTABLE LRU —
          still matchable by future lookups (pin() revives it, ref 0→1),
          reclaimed oldest-first only when alloc() finds the free list
          empty. Evicting a cached page cascades over its descendants in
          the prefix chain (they are unreachable without it) — and the
          cascade is also what keeps a recycled page id from falsely
          matching stale child keys.

    Sharing: `lookup(prompt)` walks the chained keys and PINS every page
    it matches (ref +1 per sharing request); `publish()` registers a
    fully-prefilled prompt page under its chain key. Shared pages are
    immutable by construction — only FULL prompt pages are ever
    published, and the divergence/partial page of a new request is always
    a freshly allocated private page (copy-on-write at page granularity).
    """

    TRASH = 0

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError(f"num_pages={num_pages}: need >= 2 (page 0 "
                             f"is the reserved trash sink)")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.free: List[int] = list(range(1, num_pages))   # sorted
        self.ref: List[int] = [0] * num_pages
        self._cache: Dict[PrefixKey, int] = {}       # key → physical page
        self._key_of: Dict[int, PrefixKey] = {}      # published page → key
        self._children: Dict[int, set] = {}          # parent → child pages
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # ref-0 cached
        self.hits = 0          # prompt pages served from the prefix cache
        self.misses = 0        # prompt pages that had to prefill cold
        self.evictions = 0

    # -- capacity ---------------------------------------------------------

    @property
    def usable(self) -> int:
        """Pages a single request could ever hold (pool minus trash)."""
        return self.num_pages - 1

    @property
    def available(self) -> int:
        """Pages alloc() can currently produce: truly free + evictable."""
        return len(self.free) + len(self._lru)

    @property
    def in_use(self) -> int:
        """Pages referenced by live requests (pinned shared + private)."""
        return self.usable - self.available

    @property
    def cached_pages(self) -> int:
        """Ref-0 prefix-cache pages retained for future lookups."""
        return len(self._lru)

    # -- alloc / release --------------------------------------------------

    def alloc(self) -> int:
        """Hand out one private page (ref 1), evicting the oldest idle
        prefix-cache page if the free list is dry. Raises when nothing is
        free OR evictable — callers must check `available` first (the
        scheduler reserves a request's whole worst-case page span at
        admission, so allocation never fails mid-flight)."""
        if self.free:
            p = self.free.pop(0)        # lowest-first, like slot rows
        elif self._lru:
            p, _ = self._lru.popitem(last=False)
            self._evict(p)
        else:
            raise RuntimeError("out of KV pages (none free or evictable)")
        self.ref[p] = 1
        return p

    def release(self, p: int) -> None:
        """Drop one reference. At ref 0 a published page parks in the
        evictable LRU (still matchable); an unpublished one returns to
        the free list."""
        if p == self.TRASH:
            raise ValueError("released the trash page")
        if self.ref[p] <= 0:
            raise RuntimeError(f"double-free of page {p}")
        self.ref[p] -= 1
        if self.ref[p] == 0:
            if p in self._key_of:
                self._lru[p] = None     # most-recently-used end
            else:
                insort(self.free, p)

    def _evict(self, p: int) -> None:
        """Remove page p's prefix-cache entry and cascade over its
        descendants (all ref 0 — a pinned child implies a pinned parent,
        because lookups pin whole chains and publishers hold their own
        chain). Descendants go straight to the free list."""
        key = self._key_of.pop(p)
        del self._cache[key]
        self._children.get(key[0], set()).discard(p)
        self.evictions += 1
        self._cascade_children(p)

    def _cascade_children(self, p: int) -> None:
        for child in sorted(self._children.pop(p, ())):
            assert self.ref[child] == 0, \
                f"evicting page {p} with referenced child {child}"
            del self._lru[child]
            del self._cache[self._key_of.pop(child)]
            self.evictions += 1
            self._cascade_children(child)
            insort(self.free, child)

    # -- prefix cache -----------------------------------------------------

    def pin(self, p: int) -> None:
        """Take a reference on a page (reviving it from the evictable
        LRU when idle)."""
        if self.ref[p] == 0:
            del self._lru[p]
        self.ref[p] += 1

    def lookup(self, prompt: Sequence[int], full_pages: int) -> List[int]:
        """Walk the prefix chain for `prompt`'s first `full_pages`
        complete pages and PIN every match. Returns the matched chain
        (physical page ids, possibly empty); callers release() each page
        if they end up not admitting."""
        chain: List[int] = []
        parent = -1
        for window in prefix_chain_windows(prompt, self.page_size,
                                           full_pages):
            p = self._cache.get((parent, window))
            if p is None:
                break
            self.pin(p)
            chain.append(p)
            parent = p
        self.hits += len(chain)
        self.misses += full_pages - len(chain)
        return chain

    def probe(self, prompt: Sequence[int],
              full_pages: Optional[int] = None) -> int:
        """Depth of the warm prefix chain for `prompt` WITHOUT pinning
        pages or touching the hit/miss counters — the read-only variant
        of lookup() the serving router's affinity scoring uses. Walks
        the same prefix_chain_windows keying, so probe depth k promises
        a later lookup() of the same prompt at least k hit pages
        (barring eviction in between)."""
        depth = 0
        parent = -1
        for window in prefix_chain_windows(prompt, self.page_size,
                                           full_pages):
            p = self._cache.get((parent, window))
            if p is None:
                break
            depth += 1
            parent = p
        return depth

    def publish(self, page: int, parent: int,
                tokens: Sequence[int]) -> bool:
        """Register a fully-prefilled prompt page under its chain key.
        Returns False when the key is already cached (another request
        prefilled the identical prefix concurrently) — the caller's page
        stays private and the caller must stop publishing descendants
        (they would be unreachable through the cached chain)."""
        key: PrefixKey = (parent, tuple(int(t) for t in tokens))
        if key in self._cache:
            return False
        if page in self._key_of:
            raise RuntimeError(f"page {page} published twice")
        self._cache[key] = page
        self._key_of[page] = key
        self._children.setdefault(parent, set()).add(page)
        return True

    def reset(self) -> None:
        """Rewind to the freshly-constructed state: every page free, no
        refcounts, no cached prefixes (ServingEngine.reset)."""
        self.free = list(range(1, self.num_pages))
        self.ref = [0] * self.num_pages
        self._cache.clear()
        self._key_of.clear()
        self._children.clear()
        self._lru.clear()
        self.hits = self.misses = self.evictions = 0

    def check(self) -> None:
        """Invariant audit (tests): {free} ⊔ {evictable} ⊔ {ref>0}
        partitions pages 1..N-1; cache maps are mutually consistent."""
        free, lru = set(self.free), set(self._lru)
        live = {p for p in range(1, self.num_pages) if self.ref[p] > 0}
        assert not (free & lru) and not (free & live) and not (lru & live)
        assert free | lru | live == set(range(1, self.num_pages))
        assert self.ref[self.TRASH] == 0
        assert all(r >= 0 for r in self.ref)
        vals = list(self._cache.values())
        assert len(vals) == len(set(vals)), "one page under two keys"
        assert set(vals) == set(self._key_of)
        assert all(self._cache[self._key_of[p]] == p for p in self._key_of)
        assert lru <= set(self._key_of), "evictable page not published"
        for parent, kids in self._children.items():
            for c in kids:
                assert self._key_of[c][0] == parent


class SlotManager:
    """Fixed pool of `n` slots. Rows are handed out lowest-first (keeps
    small active sets contiguous — friendlier to batch-sharded caches)
    and returned on retirement."""

    def __init__(self, n: int):
        if n < 1:
            raise ValueError(f"need at least one slot, got {n}")
        self.n = n
        self.free: List[int] = list(range(n))
        self.states: List[Optional[RequestState]] = [None] * n

    def bind(self, st: RequestState) -> None:
        if self.states[st.slot] is not None:
            raise RuntimeError(f"slot {st.slot} is already occupied")
        self.states[st.slot] = st

    def release(self, st: RequestState) -> None:
        self.states[st.slot] = None
        self.free.append(st.slot)
        self.free.sort()

    def rewind(self, slot: int, n: int, page_size: int) -> None:
        """Roll slot's cursor back `n` positions after a speculative
        verify step rejected the tail of its writes. The rejected K/V
        stays in place as dead weight — every reader masks positions
        >= the cursor and the next write lands exactly there, so rewind
        is pure host bookkeeping (no cache mutation, no page traffic; a
        rejected span that crossed into a fresh page leaves that page
        allocated — it is still inside the request's reserved span).

        The cursor must not drop below the published-page frontier
        (`published_pages` pages of `page_size`): published pages are
        immutable prefix-cache entries other requests may already share,
        so un-publishing is refused loudly rather than corrupting shared
        state. The engine never trips this (decode tokens are never
        published), but the guard keeps a buggy caller from silently
        poisoning the cache."""
        st = self.states[slot]
        if st is None:
            raise ValueError(f"rewind on free slot {slot}")
        if n < 0:
            raise ValueError(f"rewind by negative n={n}")
        new = st.pos - n
        if new < 0:
            raise ValueError(
                f"rewind({slot}, {n}) would move the cursor to {new} < 0")
        floor = st.published_pages * page_size
        if new < floor:
            raise ValueError(
                f"rewind({slot}, {n}) would un-publish: cursor {new} "
                f"< published frontier {floor} "
                f"({st.published_pages} pages x {page_size})")
        st.pos = new

    @property
    def occupied(self) -> int:
        return self.n - len(self.free)

    def step_arrays(self, idle_pos: Optional[int] = None):
        """The decode step's host-built inputs: tokens, cursors, use_prev
        flags, and per-slot sampling params, plus which states actually
        consume this step's samples. Slots mid-prefill or free still get
        a row (the step is fixed-shape): their position is their own next
        write offset, so the one junk K/V they write lands exactly
        where the next real write (chunk or cursor) overwrites it, and
        their sampled token is simply discarded. With `idle_pos` (the
        model's `max_len`, for a model that keeps state a slot:
        programs.py) every row that consumes nothing sits at that junk
        position instead: a recurrent state has no later write to be
        overwritten by.

        use_prev marks rows whose input token is the PREVIOUS step's
        device output for the same slot (st.dispatched >= 1: a decoding
        slot consumes every subsequent step, so the previous step's row
        is guaranteed to be its token) — the device-side chain that lets
        the engine dispatch step N+1 before step N's tokens reach the
        host. Rows with use_prev False read the host token (the bonus
        token after prefill), as do rows whose last tokens came from a
        speculative verify step (host_next: the verify program returned
        its targets to the host, so the device-side chain token of the
        last PLAIN step is stale for this row). States that have
        dispatched all
        max_new_tokens steps stop consuming: the engine already returned
        their row to the free pool at dispatch time (slot_released), so
        a drained state still tracked here is skipped — only the final
        sync's bookkeeping remains for it."""
        toks = np.zeros((self.n,), np.int32)
        pos = np.full((self.n,), idle_pos or 0, np.int32)
        use_prev = np.zeros((self.n,), bool)
        temps = np.zeros((self.n,), np.float32)
        top_ks = np.zeros((self.n,), np.int32)
        top_ps = np.ones((self.n,), np.float32)
        consumers: List[RequestState] = []
        for st in self.states:
            if st is None:
                continue
            if not st.prefilling and st.dispatched >= st.req.max_new_tokens:
                continue                  # drained: awaiting final sync
            if st.prefilling:
                if idle_pos is None:
                    pos[st.slot] = st.pos
                continue
            pos[st.slot] = st.pos
            toks[st.slot] = st.next_input
            use_prev[st.slot] = st.dispatched >= 1 and not st.host_next
            temps[st.slot] = st.req.temperature
            top_ks[st.slot] = st.req.top_k
            top_ps[st.slot] = st.req.top_p
            consumers.append(st)
        return toks, pos, use_prev, temps, top_ks, top_ps, consumers


__all__ = ["PageAllocator", "SlotManager", "prefix_chain_windows"]
