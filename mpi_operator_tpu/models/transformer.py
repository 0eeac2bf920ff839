"""Sharding-annotated Transformer backbone + the BASELINE model ladder.

The reference's workload ladder (BASELINE.json configs) goes beyond its
in-repo ResNet example: BERT-large pretraining, GPT-2-medium LM, and
ViT-B/16 multi-slice. The reference would run these as opaque container
images under mpirun (SURVEY.md §2.2 — all model code out-of-repo); here they
are first-class JAX models built TPU-first:

- bfloat16 compute / float32 params, matmuls shaped for the MXU
  (head_dim and mlp dims multiples of 128),
- every parameter annotated with *logical* axes
  (`nn.with_logical_partitioning`) so tensor parallelism / FSDP are rule-table
  choices (parallel/sharding.py), not model rewrites — the Megatron recipe
  (column-parallel QKV+FFN-in, row-parallel proj+FFN-out) falls out of the
  "mlp"/"heads" → tp rules with XLA inserting the collectives,
- attention pluggable: dense, Pallas flash kernel (ops/attention.py), or
  ring attention over the sp axis (parallel/ring_attention.py) for
  long-context.

One backbone serves three families:
  CausalLM  — GPT-2 (learned positions, causal mask, tied LM head)
  MaskedLM  — BERT (bidirectional, token-type embeddings, MLM head)
  ViT       — patchify + [CLS] + encoder + classifier head
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

# importing compat applies the flax duplicate-logical-name patch that
# MaskedLM's ("embed", "embed") mlm_dense kernel needs
from ..utils.compat import axis_bound as _axis_bound, shard_map

Dtype = Any

kernel_init = nn.initializers.normal(stddev=0.02)   # GPT-2/BERT init


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    max_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_dim: int = 3072
    dropout_rate: float = 0.0
    causal: bool = True
    use_token_types: bool = False      # BERT segment embeddings
    # the modern-LM knobs (Llama-style family; defaults = GPT-2/BERT):
    #   pos_embedding: "learned" (wpe table) | "rope" (rotary, applied to
    #     q/k inside attention — no position parameters at all)
    #   norm: "layernorm" | "rmsnorm"
    #   activation: "gelu" (fc_in→gelu→fc_out) | "swiglu"
    #     (silu(gate)·up→fc_out, the Llama FFN)
    #   num_kv_heads: grouped-query attention — K/V projected to this many
    #     heads and shared across num_heads//num_kv_heads query groups
    #     (None = num_heads = standard MHA). Shrinks the decode KV cache
    #     and its per-step HBM reads by the group factor.
    pos_embedding: str = "learned"
    norm: str = "layernorm"
    activation: str = "gelu"
    num_kv_heads: Optional[int] = None
    dtype: Dtype = jnp.bfloat16
    attention: str = "auto"            # auto | dense | flash | ring
    # autoregressive decode mode (models/generate.py): attention reads and
    # appends to a [B, max_len, H, D] KV cache ("cache" collection) instead
    # of attending within the input window. Training configs leave this
    # False; generate() flips it on a config copy — no extra params either
    # way, so trained params load directly.
    decode: bool = False
    # flash-attention tile sizes (None = the kernel's default 512). Long
    # sequences want bigger k tiles (fewer grid steps re-reading q/lse);
    # sweep per seq-len on real hardware — see README long-context table.
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    # decode KV-cache storage: None = model dtype; "int8" = symmetric
    # per-vector quantization (one f32 scale per cached position×kv-head)
    # — halves cache HBM vs bf16, so the bandwidth-bound decode step reads
    # half the bytes. Dequantized transiently at attend time.
    kv_cache_dtype: Optional[str] = None
    # decode fast path: single-token decode steps run the Pallas decode
    # kernel (ops/attention.decode_attention) — GQA-native (no repeated-KV
    # transient), length-aware cache reads (only the filled prefix
    # streams), int8 dequant fused into the cache read. False keeps the
    # dense einsum path, the CPU/correctness oracle. Prefill always uses
    # the dense path; a cache shape the kernel cannot tile is an error on
    # TPU and takes the dense path elsewhere (Attention._decode_attend).
    decode_kernel: bool = False
    # decode-kernel k-tile (None = ops.attention.decode_block_k default)
    decode_block_k: Optional[int] = None
    # the serving cache (serve/): with a page size the decode cache is a
    # global POOL of fixed-size pages, `cached_kv` [decode_num_pages,
    # decode_page_size, KV * 2D], instead of generate()'s lockstep
    # [B, KV, max_len, D] rows, and every row of a call is an independent
    # request SLOT at its own depth. `positions` ([B, S]) carries each
    # row's absolute write/attend offsets and `pages` ([B, max_len //
    # page_size] int32) its page table, mapping logical KV blocks to
    # physical pages; the scalar `cache_index` variable is NOT created —
    # the serving engine owns cursors and tables host-side, so
    # admitting/retiring requests never touches compiled code. Writes
    # scatter to (table[pos // page_size], pos % page_size); reads gather
    # the table back into logical order (dense path) or index pages
    # directly per block (Pallas path). Page 0 is the reserved TRASH
    # page: unallocated table entries point at it, so fixed-shape junk
    # writes from free/masked rows land somewhere harmless. HBM is
    # budgeted in pages actually used, and prompt-prefix pages can be
    # SHARED between requests (refcounted by the serving engine's
    # PageAllocator); one page a slot (page_size == max_len) is the
    # contiguous layout. Requires decode=True.
    decode_page_size: Optional[int] = None
    decode_num_pages: int = 0
    # latency-hiding tensor parallelism: run the tp-sharded projections
    # (Attention qkv/out, Mlp in/out, and the fused-LM-loss logits matmul)
    # as explicit ring collective-matmuls
    # (parallel/collectives.allgather_matmul / matmul_reducescatter) under
    # shard_map, with the tp all-gather/reduce-scatter decomposed into
    # ppermute hops hidden behind the per-shard matmuls. False keeps the
    # GSPMD einsum path — the correctness oracle (identical params either
    # way, so checkpoints swap freely). Engages only when an ambient mesh
    # has tp>1 and shapes divide (seq, heads, kv_heads, mlp_dim by tp);
    # decode and pipeline-stage bodies always use the oracle path.
    tp_overlap: bool = False
    # ring schedule for the tp-overlap collective-matmuls: "uni" rotates
    # each shard whole in one direction (the oracle ring); "bidir" splits
    # every shard in half and rotates the halves in opposite directions —
    # half the bytes per hop per direction, both transferring concurrently
    # on full-duplex ICI links. Numerically identical layouts either way.
    tp_ring: str = "uni"
    remat: bool = False                # jax.checkpoint each block
    # what remat may KEEP: "none" recomputes everything (min memory, ~2×
    # block fwd recompute); "dots" saves matmul outputs with no batch dims
    # (the standard FSDP-friendly policy — recomputes only cheap
    # elementwise/norm ops, most of the memory win at a fraction of the
    # recompute cost)
    remat_policy: str = "none"
    # MoE: replace the FFN of every `moe_every`-th block with a mixture of
    # experts (0 = dense FFN everywhere)
    num_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    # dropless MoE: every expert runs every token (num_experts× FFN
    # FLOPs, zero dropped tokens); capacity dispatch is the at-scale
    # default — see parallel/moe.py
    moe_dropless: bool = False

    @property
    def head_dim(self) -> int:
        assert self.embed_dim % self.num_heads == 0
        return self.embed_dim // self.num_heads

    @property
    def kv_heads(self) -> int:
        kv = self.num_kv_heads or self.num_heads
        if self.num_heads % kv:
            raise ValueError(
                f"num_kv_heads={kv} must divide num_heads="
                f"{self.num_heads} (each query group shares one KV head)")
        return kv


def _dense(features, name, logical_axes, dtype):
    return nn.Dense(
        features, dtype=dtype, name=name,
        kernel_init=nn.with_logical_partitioning(kernel_init, logical_axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros, (logical_axes[-1],)),
    )


class _ProjParams(nn.Module):
    """Parameter container producing the SAME tree (names, shapes, init
    fns, logical axes) as the nn.Dense/DenseGeneral it stands in for,
    without running the matmul: Attention runs its own products on the
    kernels (`_rows_dense`; under tp_overlap the ring collective-matmuls
    of parallel/collectives.py inside shard_map), so parameters trained
    on either path, or before PR 44, load directly on the other."""
    kernel_shape: tuple
    bias_shape: tuple
    kernel_axes: tuple
    bias_axes: tuple

    @nn.compact
    def __call__(self):
        k = self.param(
            "kernel",
            nn.with_logical_partitioning(kernel_init, self.kernel_axes),
            self.kernel_shape, jnp.float32)
        b = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros,
                                         self.bias_axes),
            self.bias_shape, jnp.float32)
        return k, b


def _rows_dense(x, kernel, bias, dtype, kernel_dim=0):
    """`x [.., K]` times a 2-D `kernel` over the kernel's dim `kernel_dim`,
    plus `bias`, by `nn.Dense`'s dtype rule: operands and bias cast to
    `dtype`, the product in `dtype` (the MXU accumulates in float32)."""
    x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias, dtype=dtype)
    return jax.lax.dot_general(
        x, kernel, (((x.ndim - 1,), (kernel_dim,)), ((), ()))) + bias


def tp_overlap_ring(cfg: "TransformerConfig", mesh, seq_len: int) -> int:
    """Ring size for the tp-overlap path, or 0 for the oracle path.

    Engages when cfg.tp_overlap is set, an ambient mesh carries tp>1, and
    we're NOT decoding or already inside a manual region (pipeline-stage
    bodies run under shard_map over pp — nesting another manual region
    over tp there is the oracle path's job). Raises at trace time on
    layouts the ring can't express rather than letting GSPMD produce an
    opaque placement error: sp>1 (both would shard the sequence dim). A
    seq_len not divisible by tp is fine — the overlap bodies zero-pad the
    sequence up to the next multiple and slice the pad off their output."""
    if not cfg.tp_overlap or cfg.decode or mesh is None:
        return 0
    shape = dict(mesh.shape)
    tp = shape.get("tp", 1)
    if tp <= 1:
        return 0
    if _axis_bound("tp") or _axis_bound("pp"):
        return 0
    if shape.get("sp", 1) > 1:
        raise ValueError(
            f"tp_overlap=True does not compose with sp={shape['sp']}>1 — "
            f"both shard the sequence dim (the ring rotates seq-over-tp "
            f"shards); set sp=1 or tp_overlap=False")
    if cfg.tp_ring not in ("uni", "bidir"):
        raise ValueError(
            f"tp_ring={cfg.tp_ring!r}; expected 'uni' or 'bidir'")
    return tp


def _pad_seq(x, tp, axis=1):
    """Zero-pad `axis` (the sequence dim) up to the next multiple of tp so
    shard_map can tile it over the ring; callers slice the pad back off."""
    pad = (-x.shape[axis]) % tp
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def rope(x, positions, base: float = 10000.0):
    """Rotary position embedding (rotate-half convention): x [.., S, H, D]
    rotated by per-position angles; positions [S] or [B, S] absolute ids.
    Applied to q AND k, so attention scores depend only on relative
    offsets — no position table, and decode steps just pass the absolute
    position past the cached prefix."""
    D = x.shape[-1]
    half = D // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs   # [.., S, half]
    cos = jnp.cos(angles)[..., None, :]                         # [.., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


class Attention(nn.Module):
    """Multi-head self-attention, heads sharded over tp.

    QKV projections are column-parallel ("embed" → "heads"/"kv"), the output
    projection row-parallel ("heads" → "embed") — with params replicated this
    reduces to plain MHA; with tp rules active XLA emits the Megatron
    collective pair automatically. K/V project to cfg.kv_heads (GQA) and
    are repeated across query groups for the attention kernels; the decode
    cache stores the UNrepeated kv_heads (the GQA memory win).
    """
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None, positions=None, pages=None):
        cfg = self.config
        B, S, E = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        KV = cfg.kv_heads

        from ..parallel.sharding import current_mesh
        mesh = current_mesh()
        tp = dict(mesh.shape).get("tp", 1) if mesh is not None else 1
        if tp > 1 and H % tp == 0 and KV % tp:
            # fail with a clear message at trace time: when query heads
            # shard over tp but kv_heads can't (e.g. llama 64q/8kv on
            # tp=16), the mismatch otherwise surfaces as an opaque GSPMD
            # placement error. H % tp != 0 configs replicate everything
            # (small test meshes) and stay valid.
            raise ValueError(
                f"num_kv_heads={KV} must be divisible by the mesh's tp={tp}"
                f" when num_heads={H} is (K/V heads shard over tp); choose "
                f"tp from the divisors of num_kv_heads")

        ring = tp_overlap_ring(cfg, mesh, S)
        if ring and (H % ring or KV % ring):
            raise ValueError(
                f"tp_overlap=True needs num_heads={H} and kv_heads={KV} "
                f"divisible by tp={ring} (head groups are the ring's "
                f"stationary weight shards); choose tp from their common "
                f"divisors or disable tp_overlap")

        def proj(heads, name):
            # ONE product over the merged heads·D dim, so the activation
            # is written as [B, S, heads·D] rows, the form the flash
            # kernels read; [B, S, heads, D] below is a view of it. (A
            # DenseGeneral with two feature dims is lowered to a
            # convolution over the heads that writes the sequence minor,
            # and a relayout copy then stands before every kernel
            # operand.) The tree is DenseGeneral's: kernel
            # [E, heads, D], bias [heads, D]; B and S are not merged, an
            # sp mesh shards S.
            w, b = _ProjParams((E, heads, D), (heads, D),
                               ("embed", "heads", "kv"), ("heads", "kv"),
                               name=name)()
            # The kernel enters as [heads·D, E], the product contracting
            # its minor dim: the order a TPU keeps an [E, heads, 64]
            # master in (E minor), so the weight gradient is born, and on
            # a dp mesh reduced, as the master lies; as [E, heads·D] it
            # is relaid before the update on a mesh, and on one chip the
            # step measured 0.3 ms slower (PERF.md section 6, PR 44).
            return _rows_dense(
                x, w.transpose(1, 2, 0).reshape(heads * D, E),
                b.reshape(heads * D), cfg.dtype,
                kernel_dim=1).reshape(B, S, heads, D)
        if ring:
            q, k, v = self._overlap_qkv(x, mesh, ring)
        else:
            q, k, v = proj(H, "query"), proj(KV, "key"), proj(KV, "value")

        if cfg.pos_embedding == "rope" and not cfg.decode:
            pos = jnp.arange(S) if positions is None else positions
            q = rope(q, pos)
            k = rope(k, pos)
        if cfg.decode:
            out = self._decode_attend(q, k, v, positions=positions,
                                      pages=pages)
        else:
            if KV != H:
                # repeat K/V across query groups for the shared kernels
                # (flash/ring/dense all take matching head counts); the
                # repeat is a transient — parameters and the decode cache
                # stay at KV heads
                k = jnp.repeat(k, H // KV, axis=2)
                v = jnp.repeat(v, H // KV, axis=2)
            out = _attend(q, k, v, mask=mask, cfg=cfg)

        if ring:
            return self._overlap_out(out, mesh, ring)
        # the heads' outputs read as the rows the kernels wrote: one
        # contracted dim of H·D
        w, b = _ProjParams((H, D, E), (E,), ("heads", "kv", "embed"),
                           ("embed",), name="out")()
        return _rows_dense(out.reshape(B, S, H * D), w.reshape(H * D, E), b,
                           cfg.dtype)

    def _overlap_qkv(self, x, mesh, tp):
        """Fused qkv as ONE ring allgather_matmul: the three column-parallel
        kernels concatenate along their (tp-local) output columns, so a
        single rotation of the seq-over-tp x shards feeds all three
        projections — one ring's worth of hops for q, k, AND v."""
        from ..parallel.collectives import allgather_matmul
        from ..parallel.sharding import (tp_manual_spec,
                                         tp_overlap_activation_spec)
        cfg = self.config
        H, D, KV = cfg.num_heads, cfg.head_dim, cfg.kv_heads
        E = x.shape[-1]
        wq, bq = _ProjParams((E, H, D), (H, D), ("embed", "heads", "kv"),
                             ("heads", "kv"), name="query")()
        wk, bk = _ProjParams((E, KV, D), (KV, D), ("embed", "heads", "kv"),
                             ("heads", "kv"), name="key")()
        wv, bv = _ProjParams((E, KV, D), (KV, D), ("embed", "heads", "kv"),
                             ("heads", "kv"), name="value")()
        Hl, KVl = H // tp, KV // tp

        S = x.shape[1]
        x = _pad_seq(x, tp)

        def body(x_l, wq, bq, wk, bk, wv, bv):
            w_cat = jnp.concatenate(
                [wq.reshape(E, Hl * D), wk.reshape(E, KVl * D),
                 wv.reshape(E, KVl * D)], axis=-1).astype(cfg.dtype)
            y = allgather_matmul(x_l.astype(cfg.dtype), w_cat, "tp",
                                 ring=cfg.tp_ring)
            lead = y.shape[:-1]
            q = y[..., :Hl * D].reshape(lead + (Hl, D)) + bq.astype(cfg.dtype)
            k = (y[..., Hl * D:(Hl + KVl) * D].reshape(lead + (KVl, D))
                 + bk.astype(cfg.dtype))
            v = (y[..., (Hl + KVl) * D:].reshape(lead + (KVl, D))
                 + bv.astype(cfg.dtype))
            return q, k, v

        w_spec = tp_manual_spec(("embed", "heads", "kv"))
        b_spec = tp_manual_spec(("heads", "kv"))
        head_spec = jax.sharding.PartitionSpec(
            ("dcn", "dp", "fsdp"), None, "tp", None)
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(tp_overlap_activation_spec(3),
                      w_spec, b_spec, w_spec, b_spec, w_spec, b_spec),
            out_specs=(head_spec, head_spec, head_spec),
            check_vma=False)
        q, k, v = fn(x, wq, bq, wk, bk, wv, bv)
        if q.shape[1] != S:        # slice the seq pad off the projections
            q, k, v = q[:, :S], k[:, :S], v[:, :S]
        return q, k, v

    def _overlap_out(self, a, mesh, tp):
        """Row-parallel output projection as a ring matmul_reducescatter:
        each rank contracts its head group and the partial [B,S,E] sums
        rotate home one seq shard at a time, every hop hidden behind the
        next partial's matmul. Returns the seq-over-tp sharded [B, S, E]
        (the Block residual gathers it back via the activation rules)."""
        from ..parallel.collectives import matmul_reducescatter
        from ..parallel.sharding import (tp_manual_spec,
                                         tp_overlap_activation_spec)
        cfg = self.config
        H, D, E = cfg.num_heads, cfg.head_dim, cfg.embed_dim
        wo, bo = _ProjParams((H, D, E), (E,), ("heads", "kv", "embed"),
                             ("embed",), name="out")()
        Hl = H // tp

        def body(a_l, w_l, b):
            flat = a_l.reshape(a_l.shape[:-2] + (Hl * D,)).astype(cfg.dtype)
            # matmul_reducescatter zero-pads non-divisible rows internally;
            # the global output then carries the pad rows (sliced below)
            y = matmul_reducescatter(
                flat, w_l.reshape(Hl * D, E).astype(cfg.dtype), "tp",
                ring=cfg.tp_ring)
            return y + b.astype(cfg.dtype)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(
                          ("dcn", "dp", "fsdp"), None, "tp", None),
                      tp_manual_spec(("heads", "kv", "embed")),
                      tp_manual_spec(("embed",))),
            out_specs=tp_overlap_activation_spec(3),
            check_vma=False)
        y = fn(a, wo, bo)
        return y[:, :a.shape[1]] if y.shape[1] != a.shape[1] else y

    def _decode_attend(self, q, k, v, positions=None, pages=None):
        """KV-cache attention for autoregressive decoding: append this
        call's K/V to the cache, attend q against everything written so
        far. RoPE is applied HERE (absolute positions) so cached keys are
        pre-rotated. Two regimes, told apart by `cfg.decode_page_size`:

        LOCKSTEP (no page size; `generate()`, the oracle): every row at
        the same depth. One contiguous kv-head-MAJOR cache [B, KV, L, D]
        (scales [B, KV, L]) — the tiled form the Pallas decode kernel
        streams directly, and the layout whose head axis tp-shards
        cleanly (logical "heads" → tp, parallel/sharding.py "cache" rule
        for the length axis) — written at the scalar cursor
        `cache_index`, which advances by the call's length; handles the
        multi-token prefill call and the single-token steps.

        PAGED (`serve/`): the rows are independent request slots.
        `positions` [B, S] gives each row its OWN absolute offsets (row b
        writes its K/V at positions[b] and attends cache <=
        positions[b]) and no cache_index variable exists — the serving
        engine drives the cursors from the host, one compiled step for
        any mix of request depths. The cache is ONE pool of pages,
        `cached_kv` [num_pages, page_size, KV * 2D] — a row a position,
        head h's K and V side by side in the lane-aligned columns
        [2D*h, 2D*h + 2D) (ops.attention.kv_row_width: the form the chip
        keeps row-major where it lies, so the donated pool is aliased
        through a step and never copied) — and `pages`
        ([B, L // page_size]) maps each row's logical KV blocks to
        physical pages. A call writes its rows with one flat row scatter
        at pages[pos // ps] * ps + pos % ps; the dense oracle gathers
        the table back into the logical [B, L, KV, D] keys and values,
        and the Pallas path resolves pages per block inside the kernel's
        index maps (ops.attention.paged_decode_attention). An int8
        pool's float32 scale planes are [num_pages, KV, page_size]. Page
        0 is the trash sink for unallocated table entries; a tp mesh
        splits the pool over the heads' columns.

        GQA caches the unrepeated kv_heads; with cfg.decode_kernel the
        single-token steps run the regime's Pallas kernel, which is
        GQA-native AND length-aware (only the filled prefix streams, int8
        dequant fused into the read) — the dense path below stays the
        correctness oracle and handles multi-token calls (and, off TPU,
        cache shapes the kernel cannot tile)."""
        cfg = self.config
        B, S, H, D = q.shape
        KV = k.shape[2]
        L = cfg.max_len
        paged = cfg.decode_page_size is not None
        if paged:
            ps = cfg.decode_page_size
            NP = cfg.decode_num_pages
            if ps < 1 or L % ps:
                raise ValueError(f"max_len={L} must be a multiple of "
                                 f"decode_page_size={ps}")
            if NP < 2:
                raise ValueError(
                    f"decode_num_pages={NP}: need >= 2 (page 0 is the "
                    f"reserved trash sink)")
            if positions is None or pages is None:
                raise ValueError(
                    "paged decode needs explicit positions ([B, S] "
                    "absolute per-slot offsets) and the [B, max_len // "
                    "page_size] page table from the serving engine")
            pos = jnp.broadcast_to(
                jnp.asarray(positions, jnp.int32), (B, S))  # [B, S]
            cur = pos[:, 0]                       # [B] per-slot cursors
            nblk = L // ps
            pt = jnp.broadcast_to(jnp.asarray(pages, jnp.int32),
                                  (B, nblk))
            blk = jnp.minimum(pos // ps, nblk - 1)
            phys = jnp.take_along_axis(pt, blk, axis=1)   # [B, S]
            # junk positions past the logical cache (padded prefill
            # tails, a retiring row's one post-EOS step) get an
            # out-of-range index: scatters DROP out-of-bounds updates,
            # so they never land anywhere (a clamped write could land
            # inside a SHARED prefix page)
            phys = jnp.where(pos < L, phys, NP)
            off = pos % ps
            flat = (phys * ps + off).reshape(-1)

            def upd_rows(c, u):   # pool [NP, ps, W] ← rows [B, S, W]
                return c.reshape(NP * ps, -1).at[flat].set(
                    u.reshape(B * S, -1), mode="drop").reshape(c.shape)

            def upd3(c, u):   # pool [NP, KV, ps] ← [B, KV, S]
                return c.at[phys, :, off].set(u.transpose(0, 2, 1))

            def bump():
                pass          # the engine owns the cursors host-side
        else:
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((), jnp.int32))
            cur = ci.value
            pos = cur + jnp.arange(S)                 # query positions

            def upd4(c, u):
                return jax.lax.dynamic_update_slice(c, u, (0, 0, cur, 0))

            def upd3(c, u):
                return jax.lax.dynamic_update_slice(c, u, (0, 0, cur))

            def bump():
                ci.value = cur + S
        if cfg.pos_embedding == "rope":
            q = rope(q, pos)
            k = rope(k, pos)
        k_scale = v_scale = None
        quantized = cfg.kv_cache_dtype == "int8"
        if quantized:
            # symmetric per-vector int8: scale = max|x|/127 over the head
            # dim, stored alongside. The cache is the decode bandwidth
            # bottleneck (every step re-reads the filled prefix), so
            # halving its bytes beats the tiny dequant cost.
            def quant(x):
                scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) \
                    .astype(jnp.float32) / 127.0
                scale = jnp.maximum(scale, 1e-8)
                q8 = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                              -127, 127).astype(jnp.int8)
                return q8, scale[..., 0].transpose(0, 2, 1)   # [B, KV, S]

            k, k_sc = quant(k)
            v, v_sc = quant(v)
        if paged:
            from ..ops.attention import kv_row_width, pack_kv_rows
            # the page pool is GLOBAL state shared by all rows — there is
            # no batch axis to shard, so no per-row cache constraint:
            # GSPMD places (replicates) it
            ckv = self.variable("cache", "cached_kv", jnp.zeros,
                                (NP, ps, kv_row_width(KV, D)), k.dtype)
            ckv.value = upd_rows(ckv.value, pack_kv_rows(k, v))
            sc_shape = (NP, KV, ps)
        else:
            # incoming projections are [B, S, KV, D]; the lockstep
            # cache wants the kv-head-major [B, KV, S, D] slab
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (B, KV, L, D), k.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (B, KV, L, D), v.dtype)
            ck.value = _constrain_cache(
                upd4(ck.value, k.transpose(0, 2, 1, 3)))
            cv.value = _constrain_cache(
                upd4(cv.value, v.transpose(0, 2, 1, 3)))
            sc_shape = (B, KV, L)
        if quantized:
            ks = self.variable("cache", "key_scale", jnp.zeros,
                               sc_shape, jnp.float32)
            vs = self.variable("cache", "value_scale", jnp.zeros,
                               sc_shape, jnp.float32)
            ks.value = upd3(ks.value, k_sc)
            vs.value = upd3(vs.value, v_sc)
            k_scale, v_scale = ks.value, vs.value
        bump()

        from ..ops.attention import note_traced
        if cfg.decode_kernel and S == 1:
            # A requested kernel runs (and notes itself as traced, with
            # the heads a grid step took), or says why it cannot. On TPU
            # a shape the kernel cannot tile is an error naming the shape
            # (falling through would quietly stream the whole cache
            # where a kernel was asked for); off TPU the dense oracle
            # below takes it, which is what the CPU tests compare the
            # kernel against.
            if paged:
                from ..ops.attention import paged_decode_attention
                # Mosaic second-minor tiling for a page's block of rows:
                # int8 needs 32, bf16 16, f32 8
                need = (32 if ckv.value.dtype == jnp.int8
                        else 16 if ckv.value.dtype == jnp.bfloat16 else 8)
                if ps % need == 0:
                    out = paged_decode_attention(
                        q[:, 0], ckv.value, cur, pt,
                        k_scale=k_scale, v_scale=v_scale)
                    return out[:, None]
                untileable = (f"decode_page_size={ps} is not a multiple "
                              f"of {need}, the second-minor tile of a "
                              f"{ckv.value.dtype.name} page block "
                              f"[{ps}, {ckv.value.shape[-1]}]")
            else:
                from ..ops.attention import (decode_attention,
                                             decode_block_k)
                bk = decode_block_k(L, cfg.decode_block_k)
                if L % bk == 0:
                    out = decode_attention(
                        q[:, 0], ck.value, cv.value, cur,
                        k_scale=k_scale, v_scale=v_scale,
                        block_k=cfg.decode_block_k)
                    return out[:, None]
                untileable = (f"cache length max_len={L} does not tile "
                              f"by the decode k-tile {bk}")
            if jax.default_backend() == "tpu":
                raise ValueError(
                    f"decode_kernel=True but the Pallas decode kernel "
                    f"cannot take this shape: {untileable}. Resize the "
                    f"cache, or set decode_kernel=False to ask for the "
                    f"dense path.")
        note_traced("decode" if S == 1 else "prefill", "dense")
        # dense oracle path (prefill, CPU correctness, unaligned shapes).
        # Paged caches gather the page table back into the logical rows
        # first, [B, L, KV, 2D] — trash/junk entries land at positions
        # the visibility mask below excludes. The lockstep cache is
        # kv-head-major [B, KV, L, D].
        if paged:
            # keys AND values of head h are its whole 2D-wide column block
            # (the kernel's trick): the query padded with zeros over the V
            # lanes scores K alone, and probs . block carries probs . V in
            # its V lanes — the gathered rows are never split by lanes,
            # which on the chip would be two more passes over them
            keys = values = ckv.value[pt].reshape(B, L, KV, 2 * D)
            if quantized:
                def scales(x):      # [NP, KV, ps] → [B, L, KV, 1]
                    return x[pt].transpose(0, 1, 3, 2).reshape(
                        B, L, KV, 1).astype(cfg.dtype)
                is_k = jnp.arange(2 * D) < D
                keys = values = keys.astype(cfg.dtype) * jnp.where(
                    is_k, scales(k_scale), scales(v_scale))
            q = jnp.concatenate([q, jnp.zeros_like(q)], -1)
            kv_dims, head_axis = "bkhd", 2
        else:
            keys, values = ck.value, cv.value
            if quantized:
                keys = (keys.astype(cfg.dtype)
                        * k_scale[..., None].astype(cfg.dtype))
                values = (values.astype(cfg.dtype)
                          * v_scale[..., None].astype(cfg.dtype))
            kv_dims, head_axis = "bhkd", 1
        if KV != H:
            keys, values = (jnp.repeat(x, H // KV, axis=head_axis)
                            for x in (keys, values))
        logits = jnp.einsum(f"bqhd,{kv_dims}->bhqk", q, keys)
        logits = logits.astype(jnp.float32) / jnp.sqrt(D)
        # per-row visibility: [B, S, L] (pos broadcasts from [S] in
        # the lockstep regime, is genuinely per-row in the paged one)
        visible = (jnp.arange(L)[None, None, :]
                   <= jnp.broadcast_to(pos, (B, S))[:, :, None])
        logits = jnp.where(visible[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
        return jnp.einsum(f"bhqk,{kv_dims}->bqhd", probs, values)[..., -D:]


def _attend(q, k, v, mask, cfg: TransformerConfig):
    """Dispatch to the configured attention implementation.
    q/k/v: [B, S, H, D]; returns [B, S, H, D].

    A key-padding `mask` ([B, S] valid-token) is first-class in the flash
    kernel (ops/attention.py); the ring schedule doesn't implement it, so
    masked ring requests fall back to dense rather than silently attending
    to padding."""
    from ..ops.attention import flash_attention, note_traced
    impl = cfg.attention
    if impl == "auto":
        # flash kernel only on TPU; dense elsewhere (CPU tests/simulation)
        impl = "flash" if jax.default_backend() == "tpu" else "dense"
    if mask is not None and impl == "ring":
        impl = "dense"
    if impl == "flash":
        # flash_attention reports "flash" or, for an untileable S, "dense"
        kw = {}
        if cfg.flash_block_q:
            kw["block_q"] = cfg.flash_block_q
        if cfg.flash_block_k:
            kw["block_k"] = cfg.flash_block_k
        return flash_attention(q, k, v, causal=cfg.causal, mask=mask, **kw)
    if impl == "ring":
        from ..parallel.ring_attention import (ring_attention,
                                               ring_attention_inner)
        from ..parallel.sharding import current_mesh
        note_traced("attention", "ring")
        if _axis_bound("sp"):
            # already inside shard_map/pmap over sp: the seq dim is the
            # local shard, run the ring body directly
            return ring_attention_inner(q, k, v, axis_name="sp",
                                        causal=cfg.causal)
        mesh = current_mesh()
        if mesh is not None and dict(mesh.shape).get("sp", 1) > 1:
            # plain-jit caller (LMTrainer's step under
            # activation_rules_scope): nest the shard_map wrapper — the
            # seq-sharded residual stream ("seq"→"sp" activation rule)
            # feeds the ring without a resharding gather
            return ring_attention(q, k, v, mesh, causal=cfg.causal)
        raise ValueError(
            'attention="ring" needs either execution inside shard_map/pmap '
            'over an "sp" mesh axis, or an ambient mesh with sp > 1 '
            "(train under LMTrainer on a MeshConfig(sp=N) mesh; a "
            "degenerate 1-device ring would deliver no context parallelism"
            "); for direct use call parallel.ring_attention(q, k, v, mesh)")
    note_traced("attention", "dense")
    return dense_attention(q, k, v, mask=mask, causal=cfg.causal,
                           dtype=cfg.dtype)


@jax.custom_vjp
def _head_matmul(h, table):
    """Tied-LM-head matmul [B,S,E]@[V,E]ᵀ with every matmul (fwd, dh,
    dtable) running at the operands' dtype on the MXU and accumulating in
    f32. Without this, `h.astype(f32)` before `wte.attend` forces the
    largest matmul in the model (E×50k vocab) to run at the f32 MXU rate
    (~¼ of bf16 on v5e) in forward AND both backward products."""
    return jax.lax.dot_general(h, table, (((h.ndim - 1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _head_matmul_fwd(h, table):
    return _head_matmul(h, table), (h, table)


def _head_matmul_bwd(res, g):
    h, table = res
    gb = g.astype(table.dtype)       # bf16 cotangent, f32 accumulation
    dh = jax.lax.dot_general(
        gb, table, (((g.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(h.dtype)
    V = g.shape[-1]
    E = h.shape[-1]
    dtable = jax.lax.dot_general(
        gb.reshape(-1, V), h.reshape(-1, E), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(table.dtype)
    return dh, dtable


_head_matmul.defvjp(_head_matmul_fwd, _head_matmul_bwd)


def tied_logits(h, wte, cfg: TransformerConfig):
    """LM logits against the (tied) token-embedding table; f32 output for
    a stable softmax-xent."""
    return _head_matmul(h, wte.embedding.astype(cfg.dtype))


def dense_attention(q, k, v, mask=None, causal=True, dtype=jnp.float32):
    """Reference O(S²) attention. Softmax in f32 for stability."""
    D = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(D).astype(jnp.float32)
    if causal:
        S_q, S_k = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((S_q, S_k), bool))
        logits = jnp.where(causal_mask[None, None], logits, -1e30)
    if mask is not None:
        # mask: [B, S_k] valid-token mask
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class Mlp(nn.Module):
    """FFN, column-parallel in ("embed"→"mlp"), row-parallel out. Two
    bodies: "gelu" (fc_in→gelu→fc_out, GPT-2/BERT) or "swiglu"
    (silu(gate)·up→fc_out, the Llama FFN — one extra column-parallel
    matmul, same sharding recipe)."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if cfg.activation not in ("gelu", "swiglu"):
            raise ValueError(f"activation={cfg.activation!r}; expected "
                             f"'gelu' or 'swiglu'")
        from ..parallel.sharding import current_mesh
        ring = tp_overlap_ring(cfg, current_mesh(), x.shape[-2])
        if ring:
            return self._overlap_ffn(x, current_mesh(), ring)
        if cfg.activation == "swiglu":
            gate = _dense(cfg.mlp_dim, "fc_gate", ("embed", "mlp"),
                          cfg.dtype)(x)
            up = _dense(cfg.mlp_dim, "fc_in", ("embed", "mlp"),
                        cfg.dtype)(x)
            h = nn.silu(gate) * up
        else:
            h = nn.gelu(_dense(cfg.mlp_dim, "fc_in", ("embed", "mlp"),
                               cfg.dtype)(x))
        return _dense(cfg.embed_dim, "fc_out", ("mlp", "embed"), cfg.dtype)(h)

    def _overlap_ffn(self, x, mesh, tp):
        """The whole FFN as ONE manual region: allgather_matmul for the
        column-parallel in/gate matmuls (fused into a single ring by
        concatenating their tp-local columns), the activation on the
        tp-local hidden columns, matmul_reducescatter for the row-parallel
        out matmul. Entry slices the replicated residual into seq-over-tp
        shards for free; the exit reduce-scatter leaves the output
        seq-sharded and the Block residual gathers it."""
        from ..parallel.collectives import (allgather_matmul,
                                            matmul_reducescatter)
        from ..parallel.sharding import (tp_manual_spec,
                                         tp_overlap_activation_spec)
        cfg = self.config
        E, M = cfg.embed_dim, cfg.mlp_dim
        if M % tp:
            raise ValueError(
                f"tp_overlap=True needs mlp_dim={M} divisible by tp={tp} "
                f"(hidden columns are the ring's stationary weight shards)"
                f"; resize mlp_dim or disable tp_overlap")
        swiglu = cfg.activation == "swiglu"
        if swiglu:
            wg, bg = _ProjParams((E, M), (M,), ("embed", "mlp"), ("mlp",),
                                 name="fc_gate")()
        wi, bi = _ProjParams((E, M), (M,), ("embed", "mlp"), ("mlp",),
                             name="fc_in")()
        wo, bo = _ProjParams((M, E), (E,), ("mlp", "embed"), ("embed",),
                             name="fc_out")()
        Ml = M // tp

        S = x.shape[1]
        x = _pad_seq(x, tp)

        def body(x_l, *ws):
            if swiglu:
                wg_l, bg_l, wi_l, bi_l, wo_l, bo_l = ws
                w_cat = jnp.concatenate([wg_l, wi_l], -1).astype(cfg.dtype)
                y = allgather_matmul(x_l.astype(cfg.dtype), w_cat, "tp",
                                     ring=cfg.tp_ring)
                h = (nn.silu(y[..., :Ml] + bg_l.astype(cfg.dtype))
                     * (y[..., Ml:] + bi_l.astype(cfg.dtype)))
            else:
                wi_l, bi_l, wo_l, bo_l = ws
                h = nn.gelu(
                    allgather_matmul(x_l.astype(cfg.dtype),
                                     wi_l.astype(cfg.dtype), "tp",
                                     ring=cfg.tp_ring)
                    + bi_l.astype(cfg.dtype))
            y = matmul_reducescatter(h, wo_l.astype(cfg.dtype), "tp",
                                     ring=cfg.tp_ring)
            return y + bo_l.astype(cfg.dtype)

        col_specs = (tp_manual_spec(("embed", "mlp")),
                     tp_manual_spec(("mlp",)))
        in_specs = (tp_overlap_activation_spec(3),) \
            + (col_specs if swiglu else ()) + col_specs \
            + (tp_manual_spec(("mlp", "embed")), tp_manual_spec(("embed",)))
        fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=tp_overlap_activation_spec(3),
                       check_vma=False)
        args = (x, wg, bg, wi, bi, wo, bo) if swiglu else (x, wi, bi, wo, bo)
        y = fn(*args)
        return y[:, :S] if y.shape[1] != S else y


def _layer_norm(cfg, name):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(
            dtype=cfg.dtype, name=name, epsilon=1e-5,
            scale_init=nn.with_logical_partitioning(nn.initializers.ones,
                                                    ("norm",)))
    if cfg.norm != "layernorm":
        raise ValueError(f"norm={cfg.norm!r}; expected 'layernorm' or "
                         f"'rmsnorm'")
    return nn.LayerNorm(
        dtype=cfg.dtype, name=name, epsilon=1e-5,
        scale_init=nn.with_logical_partitioning(nn.initializers.ones,
                                                ("norm",)),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros,
                                               ("norm",)))


def _constrain(x):
    """Pin the residual stream to the activation layout (batch-sharded,
    embed replicated — parallel/sharding.py ACTIVATION_RULES). A no-op
    unless the trainer entered activation_rules_scope; without the pin,
    GSPMD infers clashing layouts around the layernorms and pays an
    involuntary full rematerialization in the backward."""
    return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


def _constrain_cache(x):
    """Pin the decode KV cache to its serving layout: batch-sharded rows,
    kv-head axis over tp ("heads" rule), length+head-dim replicated (the
    "cache" rule). A no-op outside activation_rules_scope — generate()'s
    plain-jit path lets GSPMD propagate the layout from the tp-sharded
    projection params instead."""
    return nn.with_logical_constraint(x, ("batch", "heads", "cache", "kv"))


class Block(nn.Module):
    """Pre-LN transformer block (GPT-2/ViT style)."""
    config: TransformerConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, mask=None, positions=None, pages=None):
        cfg = self.config
        x = _constrain(x)
        y = _layer_norm(cfg, "ln_1")(x)
        x = _constrain(x + Attention(cfg, name="attn")(y, mask=mask,
                                                       positions=positions,
                                                       pages=pages))
        y = _layer_norm(cfg, "ln_2")(x)
        if self.use_moe:
            from ..parallel.moe import MoeMlp
            if cfg.activation != "gelu":
                # MoeMlp's experts are gelu FFNs; silently building gelu
                # experts inside a swiglu-configured model would mislabel
                # every benchmark of it
                raise ValueError(
                    f"num_experts>0 requires activation='gelu' (MoeMlp "
                    f"experts are gelu FFNs); got {cfg.activation!r}")
            ff, aux = MoeMlp(
                num_experts=cfg.num_experts, top_k=cfg.moe_top_k,
                embed_dim=cfg.embed_dim, mlp_dim=cfg.mlp_dim,
                dropless=cfg.moe_dropless,
                dtype=cfg.dtype, name="moe")(y)
            self.sow("intermediates", "moe_aux_loss", aux)
        else:
            ff = Mlp(cfg, name="mlp")(y)
        return _constrain(x + ff)


class Backbone(nn.Module):
    """Stack of blocks over pre-embedded input."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, h, mask=None, positions=None, pages=None):
        cfg = self.config
        block = Block
        if cfg.remat:
            if cfg.remat_policy == "dots":
                policy = (jax.checkpoint_policies
                          .dots_with_no_batch_dims_saveable)
            elif cfg.remat_policy == "none":
                policy = None           # recompute everything
            else:
                raise ValueError(
                    f"remat_policy={cfg.remat_policy!r}; expected "
                    f"'none' or 'dots'")
            block = nn.remat(Block, static_argnums=(), policy=policy)
        h = _constrain(h)      # pin the embedding output / dh cotangent too
        for i in range(cfg.num_layers):
            use_moe = (cfg.num_experts > 0
                       and i % cfg.moe_every == cfg.moe_every - 1)
            h = block(cfg, use_moe=use_moe, name=f"block_{i}")(
                h, mask=mask, positions=positions, pages=pages)
        return _constrain(_layer_norm(cfg, "ln_f")(h))


def _embed(cfg, num, features, name, logical0, logical1="embed"):
    return nn.Embed(
        num, features, dtype=cfg.dtype, name=name,
        embedding_init=nn.with_logical_partitioning(
            kernel_init, (logical0, logical1)))


def _pos_embed(cfg, num, name="wpe"):
    """Position/type tables are tiny and fully REPLICATED ("pos" maps to no
    mesh axis): an fsdp-sharded embed dim here makes the scatter-add
    gradient reshard the batch-sharded cotangent to embed-sharded through a
    non-divisible reshape — the exact involuntary-full-remat GSPMD warns
    about. Megatron replicates position embeddings for the same reason."""
    return _embed(cfg, num, cfg.embed_dim, name, None, "pos")


class CausalLM(nn.Module):
    """GPT-2-style decoder LM: learned positions, tied LM head
    (reference capability: "GPT-2 medium JAX data-parallel MPIJob",
    BASELINE.json configs[3])."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens, with_head: bool = True, positions=None,
                 pages=None):
        """with_head=False returns the backbone output h [B, S, E] instead
        of logits — the chunked fused-xent path (train/lm_trainer.py)
        consumes h + the wte table directly so the full [B·S, vocab]
        logits never materialize in HBM. Both modes create identical
        params (the tied head adds none). `positions` overrides the
        default arange(S) position ids (decode steps pass the absolute
        position of each token past the cached prefix). `pages` is the
        paged-KV page table ([B, max_len // page_size] int32), required
        when cfg.decode_page_size is set (serve/engine.py)."""
        cfg = self.config
        B, S = tokens.shape
        wte = _embed(cfg, cfg.vocab_size, cfg.embed_dim, "wte", "vocab")
        if positions is None:
            positions = jnp.arange(S)[None]
        h = wte(tokens)
        if cfg.pos_embedding == "learned":
            h = h + _pos_embed(cfg, cfg.max_len)(positions)
        # rope: no position table — rotations happen inside attention;
        # positions pass through UNsliced (rope broadcasts [S] or [B, S],
        # so per-row ids — left-padded prompts — stay per-row)
        h = Backbone(cfg, name="backbone")(h, positions=positions,
                                           pages=pages)
        if not with_head:
            return h
        # tied LM head; bf16 MXU matmul, f32 accumulation (tied_logits)
        return tied_logits(h, wte, cfg)


class MaskedLM(nn.Module):
    """BERT-style bidirectional encoder + MLM head
    (reference capability: "BERT-large pretraining MPIJob",
    BASELINE.json configs[2])."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens, token_types=None, attention_mask=None):
        cfg = self.config
        assert not cfg.causal, "MaskedLM needs causal=False"
        B, S = tokens.shape
        wte = _embed(cfg, cfg.vocab_size, cfg.embed_dim, "wte", "vocab")
        h = wte(tokens) + _pos_embed(cfg, cfg.max_len)(jnp.arange(S)[None])
        if cfg.use_token_types:
            if token_types is None:
                token_types = jnp.zeros_like(tokens)
            h = h + _pos_embed(cfg, 2, "wtte")(token_types)
        h = _layer_norm(cfg, "ln_emb")(h)
        h = Backbone(cfg, name="backbone")(h, mask=attention_mask)
        # MLM transform head (dense + gelu + LN), then tied decoder
        h = _dense(cfg.embed_dim, "mlm_dense", ("embed", "embed"),
                   cfg.dtype)(h)
        h = nn.gelu(h)
        h = _layer_norm(cfg, "mlm_ln")(h)
        logits = tied_logits(h, wte, cfg)
        logits = logits + self.param(
            "mlm_bias",
            nn.with_logical_partitioning(nn.initializers.zeros, ("vocab",)),
            (cfg.vocab_size,), jnp.float32)
        return logits


class ViT(nn.Module):
    """ViT-B/16-style image classifier
    (reference capability: "ViT-B/16 multi-slice MPIJob",
    BASELINE.json configs[4])."""
    config: TransformerConfig
    num_classes: int = 1000
    patch_size: int = 16

    @nn.compact
    def __call__(self, images, train: bool = True):
        del train   # no dropout by default; signature-compatible w/ ResNet
        cfg = self.config
        p = self.patch_size
        B, H, W, C = images.shape
        x = nn.Conv(
            cfg.embed_dim, (p, p), strides=(p, p), dtype=cfg.dtype,
            name="patch_embed",
            kernel_init=nn.with_logical_partitioning(
                kernel_init, (None, None, None, "embed")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed",)),
        )(images.astype(cfg.dtype))
        x = x.reshape(B, -1, cfg.embed_dim)
        cls = self.param(
            "cls",
            nn.with_logical_partitioning(nn.initializers.zeros,
                                         (None, None, "embed")),
            (1, 1, cfg.embed_dim), jnp.float32)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls, (B, 1, cfg.embed_dim)).astype(cfg.dtype),
             x], axis=1)
        x = x + _pos_embed(cfg, x.shape[1], "pos")(jnp.arange(x.shape[1])[None])
        x = Backbone(cfg, name="backbone")(x)
        return _dense(self.num_classes, "head", ("embed", "vocab"),
                      jnp.float32)(x[:, 0].astype(jnp.float32))


# ---------------------------------------------------------------------------
# The BASELINE.json ladder presets
# ---------------------------------------------------------------------------

def gpt2_config(size: str = "medium", **overrides) -> TransformerConfig:
    dims = {
        "small": (12, 12, 768),
        "medium": (24, 16, 1024),        # the BASELINE config
        "large": (36, 20, 1280),
        "xl": (48, 25, 1600),
        "test": (2, 4, 128),
    }[size]
    L, H, E = dims
    # vocab padded 50257→50304 (a multiple of 128, Megatron-style): keeps
    # the tied LM-head matmul MXU-aligned and the table divisible over
    # tp×fsdp (sharding rule "vocab", parallel/sharding.py)
    base = dict(vocab_size=50304, max_len=1024, num_layers=L, num_heads=H,
                embed_dim=E, mlp_dim=4 * E, causal=True)
    base.update(overrides)
    return TransformerConfig(**base)


def llama_config(size: str = "1b", **overrides) -> TransformerConfig:
    """Llama-style decoder: RoPE + RMSNorm + SwiGLU + grouped-query
    attention — the modern-LM stack as config knobs over the same
    sharded backbone (no reference analogue; the reference ships no
    models at all, SURVEY.md §2.2)."""
    # (layers, q heads, kv heads, embed, mlp) — mlp ≈ 8/3·E rounded to a
    # multiple of 256 (MXU-aligned), the SwiGLU sizing convention
    dims = {
        "test": (2, 4, 2, 128, 256),
        "1b": (16, 32, 8, 2048, 5504),
        "7b": (32, 32, 8, 4096, 11008),
    }[size]
    L, H, KV, E, M = dims
    base = dict(vocab_size=32000, max_len=2048, num_layers=L, num_heads=H,
                num_kv_heads=KV, embed_dim=E, mlp_dim=M, causal=True,
                pos_embedding="rope", norm="rmsnorm", activation="swiglu")
    base.update(overrides)
    return TransformerConfig(**base)


def bert_config(size: str = "large", **overrides) -> TransformerConfig:
    dims = {
        "base": (12, 12, 768),
        "large": (24, 16, 1024),         # the BASELINE config
        "test": (2, 4, 128),
    }[size]
    L, H, E = dims
    # vocab padded 30522→30592 (multiple of 128; same rationale as GPT-2)
    base = dict(vocab_size=30592, max_len=512, num_layers=L, num_heads=H,
                embed_dim=E, mlp_dim=4 * E, causal=False,
                use_token_types=True)
    base.update(overrides)
    return TransformerConfig(**base)


def vit_config(size: str = "b16", **overrides) -> TransformerConfig:
    dims = {
        "b16": (12, 12, 768, 3072),      # the BASELINE config (ViT-B/16)
        "l16": (24, 16, 1024, 4096),
        "test": (2, 4, 128, 256),
    }[size]
    L, H, E, M = dims
    base = dict(vocab_size=1, max_len=2048, num_layers=L, num_heads=H,
                embed_dim=E, mlp_dim=M, causal=False)
    base.update(overrides)
    return TransformerConfig(**base)


def create_lm(name: str = "gpt2-medium", **overrides):
    """Factory mirroring models.resnet.create_model."""
    family, _, size = name.partition("-")
    size = size or None
    if family == "gpt2":
        return CausalLM(gpt2_config(size or "medium", **overrides))
    if family == "llama":
        return CausalLM(llama_config(size or "1b", **overrides))
    if family == "bert":
        return MaskedLM(bert_config(size or "large", **overrides))
    raise ValueError(f"unknown LM {name!r}")


def create_vit(name: str = "vit-b16", num_classes: int = 1000, **overrides):
    size = name.split("-", 1)[1] if "-" in name else "b16"
    return ViT(vit_config(size, **overrides), num_classes=num_classes)


__all__ = [
    "TransformerConfig", "Attention", "Mlp", "Block", "Backbone",
    "CausalLM", "MaskedLM", "ViT", "dense_attention", "rope",
    "tp_overlap_ring",
    "gpt2_config", "llama_config", "bert_config", "vit_config",
    "create_lm", "create_vit",
]
