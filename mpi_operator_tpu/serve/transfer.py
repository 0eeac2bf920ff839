"""Device-to-device paged-KV handoff between serving pools.

The disaggregated engine (serve/engine.py DisaggEngine) runs prefill
and decode on SEPARATE device pools; when a prompt finishes prefilling,
its KV lives in the prefill pool's page arrays and must move into the
decode pool's. Because the paged cache layout puts the page axis first
on EVERY leaf — a per-head cache's `cached_kv` is [num_pages, page_size,
KV * 2D] (one row a position, K and V of a head side by side), its int8
scale planes are [num_pages, KV, page_size], and a latent cache's pool
(models/longcat.py) is [num_pages, page_size, W], also one row a
position — one generic axis-0 gather/scatter over the cache pytree
moves a page list uniformly for every kind of cache and all dtypes:
int8 payloads travel WITH their scale rows, nothing is dequantized in
flight, and a latent page moves as a per-head one does
(tests/test_longcat.py).

Three dispatches per handoff, all async:

    payload = gather(src_cache, src_ids)     # jit on the source device
    payload = jax.device_put(payload, dst)   # the actual D2D copy
    dst_cache = scatter(dst_cache, dst_ids, payload)   # jit on dest

Only OCCUPIED pages move — the caller passes the physical ids of pages
holding written prompt positions, minus any the destination resolved
from its own prefix cache (those need no bytes at all). On real
hardware the device_put rides ICI/DCN; on the CPU smoke it is a
host-memory copy between two single-device "meshes" in one process —
same program structure, same token math.

Compile discipline: a traced id-vector length is a program shape, so a
naive per-request transfer would compile one gather+scatter pair per
distinct page count. Id lists are padded to the next power of two
instead — source padding re-reads page 0 (the allocator's reserved
trash page), destination padding re-writes it, and duplicate trash
scatters are harmless because nothing ever reads trash — pinning the
compile count at ≤ log2(pool size) + 1 per direction, independent of
the trace (tests/test_disagg.py holds the pin).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..telemetry import span


def _bucket(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    w = 1
    while w < n:
        w <<= 1
    return w


class PageTransfer:
    """Moves occupied KV pages from a source pool's cache into a
    destination pool's. Stateless apart from the two jitted programs
    and a moved-pages odometer; one instance serves every handoff of a
    DisaggEngine, so its compile caches ARE the transfer pins."""

    TRASH = 0     # PageAllocator's reserved junk page, the padding sink

    def __init__(self, src_num_pages: int, dst_num_pages: int):
        self.src_num_pages = src_num_pages
        self.dst_num_pages = dst_num_pages
        self.pages_moved = 0

        def gather(cache, ids):
            # page-pool leaves all carry the pool's page count on axis
            # 0 (`move` refuses a cache with leaves of another kind)
            return jax.tree.map(
                lambda x: x[ids] if x.shape[0] == src_num_pages else x,
                cache)

        def scatter(cache, ids, rows):
            return jax.tree.map(
                lambda x, r: (x.at[ids].set(r)
                              if x.shape[0] == dst_num_pages else x),
                cache, rows)

        # donating the destination cache keeps the scatter in-place on
        # real hardware; CPU jit ignores donation (and warns), so gate
        # it the same way the engine gates its decode-step donation
        donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
        self._gather = jax.jit(gather)
        self._scatter = jax.jit(scatter, donate_argnums=donate)

    def move(self, src_cache, dst_cache, src_ids: Sequence[int],
             dst_ids: Sequence[int]) -> Tuple[object, int]:
        """Copy src_cache pages src_ids[i] -> dst_cache pages dst_ids[i]
        and return (new dst_cache, pages moved). Dispatch-async like
        every engine program: the gather captures the source buffers at
        dispatch, so the caller may release the source page REFERENCES
        immediately after this returns."""
        if len(src_ids) != len(dst_ids):
            raise ValueError(f"src/dst page lists disagree: "
                             f"{len(src_ids)} vs {len(dst_ids)}")
        odd = [tuple(x.shape) for x in jax.tree.leaves(src_cache)
               if x.shape[0] != self.src_num_pages]
        if odd:
            raise ValueError(
                f"PageTransfer moves pages only, and this cache has leaves "
                f"that lead with something else than its {self.src_num_pages}"
                f" pages ({odd[:3]}): state a slot keeps (a window's ring, "
                f"a recurrent layer's state) needs a state transfer, which "
                f"does not exist")
        n = len(src_ids)
        if n == 0:
            return dst_cache, 0
        width = _bucket(n)
        pad = [self.TRASH] * (width - n)
        sids = jnp.asarray(list(src_ids) + pad, jnp.int32)
        dids = jnp.asarray(list(dst_ids) + pad, jnp.int32)
        # the same span name the request trace's kv_handoff hop uses
        # (telemetry/trace.py taxonomy), scoped to the actual page move
        # so an XProf capture attributes gather/copy/scatter separately
        # from the install bookkeeping around it
        with span("serve.kv_handoff.move"):
            payload = self._gather(src_cache, sids)
            dst_dev = self._device_of(dst_cache)
            if dst_dev is not None:
                payload = jax.device_put(payload, dst_dev)
            dst_cache = self._scatter(dst_cache, dids, payload)
        self.pages_moved += n
        return dst_cache, n

    @staticmethod
    def _device_of(cache):
        """The destination pool's (single) device, so the payload is
        committed there before the scatter — jit would otherwise refuse
        operands committed to two different devices."""
        for leaf in jax.tree.leaves(cache):
            devs = getattr(leaf, "devices", None)
            if devs is None:
                continue
            ds = devs()
            if len(ds) == 1:
                return next(iter(ds))
        return None

    def compile_counts(self) -> Dict[str, int]:
        """Compiled program variants per direction — one per distinct
        padded width, so ≤ log2(pool size) + 1 each (the test pin)."""
        return {"gather": self._gather._cache_size(),
                "scatter": self._scatter._cache_size()}


__all__ = ["PageTransfer"]
