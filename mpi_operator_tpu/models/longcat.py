"""LongCat-Flash: latent attention (MLA) and a shortcut-connected expert
layer with zero-compute experts, served through `serve/` like any CausalLM.

One layer (ScMoE) holds two MLA sublayers `A0, A1`, two SwiGLU FFNs
`F0, F1` and one expert layer `M` whose result joins the residual after
the second FFN — in a deployment the experts' exchange overlaps the dense
sublayers between:

    h1 = x  + A0(norm_a0(x))
    y  = norm_f0(h1);  s = M(y);  h2 = h1 + F0(y)
    h3 = h2 + A1(norm_a1(h2))
    out = h3 + F1(norm_f1(h3)) + s

MLA (`LatentAttention`, which `models/deepseek_v2.py` serves DeepSeek-V2
with as well: what differs between the two models it takes from the
configuration — `mla_scale_q_lora` / `mla_scale_kv_lora`, the factors
below, LongCat's alone; `sm_scale`; `rope_freqs`, None for plain
`theta^(-2i/d)` or the frequencies to rotate by, YaRN's there):
`c_q = RMSNorm(x W_qa)`, `q = c_q W_qb` in heads of `nope ‖ rope`
columns, times `sqrt(hidden / q_rank)`; `[c_kv ‖ k_pe] = x W_kva`,
`c_kv = RMSNorm(c_kv) sqrt(hidden / kv_rank)`, `k_pe` one head shared by
all, RoPE on interleaved pairs of `q_pe` and `k_pe`; `[k_nope ‖ v] =
c_kv W_kvb`; scores over `nope + rope` columns. Outside decode mode the
sublayer expands K and V and attends inside the window (the form the
decode path is tested against). In decode mode the cache holds ONE row a
position — `c_kv ‖ k_pe`, padded to whole lane tiles
(`ops.attention.mla_row_width`) — in a page pool `[pages, page, W]`, and
`W_kvb` is absorbed: `q~ = q_nope W_kvb[K]`, scores `q~.c_kv + q_pe.k_pe`,
`u = P c_kv`, `v = u W_kvb[V]` (`ops.attention.mla_paged_attend`, and the
Pallas kernel for single-token steps; a chunk whose absorbed queries
would pass a gigabyte builds them a group of rows at a time,
`ops.attention.mla_paged_attend_rows`). The latent cache exists paged
only: it is driven by the serving engine's slots and page tables.

The expert layer is `parallel/held_experts.py`: told which experts it
holds (`held = (first, count)`), it routes over all `n_routed_experts +
zero_expert_num` outputs and computes its own part and the identity
experts'. The vocabulary may be a slice (`vocab_size` rows of the
embedding and of the untied head).

In decode mode every expert layer sows its `pick_counts` into the
"counters" collection; the serving engine sums them over the layers and
fetches them with the step's tokens (`STEP_COUNTERS` names them).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.held_experts import shortcut_experts

Dtype = Any
init = nn.initializers.normal(stddev=0.02)


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    vocab_size: int = 131072
    max_len: int = 131072
    num_layers: int = 28
    hidden_size: int = 6144
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    n_routed_experts: int = 512     # the router's real outputs, as published
    zero_expert_num: int = 256      # identity experts, after the real ones
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    #: q and c_kv times sqrt(hidden / rank), as published
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-5
    #: (first, count): the real experts whose weights live here
    held: Tuple[int, int] = (0, 512)
    dtype: Dtype = jnp.bfloat16
    causal: bool = True
    # decode mode, as in TransformerConfig (models/generate.decode_model
    # flips these on a copy): the latent cache is paged, so decode needs
    # a page size
    decode: bool = False
    decode_page_size: Optional[int] = None
    decode_num_pages: int = 0
    decode_kernel: bool = False

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def sm_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    #: no rope scaling: `rope_interleaved` rotates by theta^(-2i/d)
    rope_freqs = None


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope_interleaved(x, positions, theta: float, freqs=None):
    """Rotary embedding on interleaved pairs (x[2i], x[2i+1]) of the last
    dim; x [B, S, ..., D], positions [B, S]. `freqs` [D/2] float32, where
    given, are the frequencies to rotate by (a scaled RoPE's) in place
    of theta^(-2i/D)."""
    D = x.shape[-1]
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # [B, S, D/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (D // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


class _Norm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.eps)


class LatentAttention(nn.Module):
    #: a LongcatConfig, or another model's with the same fields
    #: (`deepseek_v2.DeepseekV2Config`)
    config: Any

    @nn.compact
    def __call__(self, x, positions=None, pages=None):
        cfg = self.config
        B, S, E = x.shape
        H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        dt = cfg.dtype
        def p(name, shape):
            return self.param(name, init, shape).astype(dt)
        q_a, q_b = p("q_a", (E, rq)), p("q_b", (rq, H, dn + dr))
        kv_a, kv_b = p("kv_a", (E, rkv + dr)), p("kv_b", (rkv, H, dn + dv))
        w_o = p("o", (H, dv, E))
        pos = (jnp.broadcast_to(jnp.arange(S)[None], (B, S))
               if positions is None
               else jnp.broadcast_to(jnp.asarray(positions, jnp.int32),
                                     (B, S)))
        sm_scale = cfg.sm_scale
        rope = functools.partial(rope_interleaved, positions=pos,
                                 theta=cfg.rope_theta, freqs=cfg.rope_freqs)

        with jax.named_scope("mla.project"):
            c_q = _Norm(cfg.rms_norm_eps, name="q_a_norm")(x @ q_a)
            q = jnp.einsum("bsr,rhd->bshd", c_q, q_b)
            if cfg.mla_scale_q_lora:
                q = q * jnp.asarray(math.sqrt(E / rq), dt)
            q_nope, q_pe = q[..., :dn], q[..., dn:]
            kv = x @ kv_a
            c_kv = _Norm(cfg.rms_norm_eps, name="kv_a_norm")(kv[..., :rkv])
            if cfg.mla_scale_kv_lora:
                c_kv = c_kv * jnp.asarray(math.sqrt(E / rkv), dt)
            k_pe = rope(kv[..., rkv:])
            q_pe = rope(q_pe)

        if not cfg.decode:
            with jax.named_scope("mla.attend"):
                k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, kv_b[..., :dn])
                v = jnp.einsum("bsr,rhd->bshd", c_kv, kv_b[..., dn:])
                s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
                     + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe)
                     ).astype(jnp.float32) * sm_scale
                s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
                v = jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(s, -1).astype(dt), v)
        else:
            v = self._cached(q_nope, q_pe, c_kv, k_pe, kv_b, pos, pages,
                             sm_scale)
        with jax.named_scope("mla.out"):
            return jnp.einsum("bshd,hde->bse", v, w_o)

    def _cached(self, q_nope, q_pe, c_kv, k_pe, kv_b, pos, pages, sm_scale):
        """Write this call's latent rows at `pos` through the page
        tables, then attend with the up-projection absorbed."""
        from ..ops.attention import (mla_paged_attend,
                                     mla_paged_attend_rows,
                                     mla_paged_decode_attention,
                                     mla_query_rows, mla_row_width,
                                     note_traced)
        cfg = self.config
        B, S, H, dn = q_nope.shape
        rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        ps, NP = cfg.decode_page_size, cfg.decode_num_pages
        if ps is None or pages is None:
            raise ValueError(
                "the latent cache is a page pool driven by the serving "
                "engine: decode needs a decode_page_size and the "
                "[B, max_len // page_size] page table")
        L = cfg.max_len
        if ps < 1 or L % ps or NP < 2:
            raise ValueError(f"max_len={L} must be a multiple of "
                             f"decode_page_size={ps}, and the pool needs "
                             f">= 2 pages (page 0 is the trash sink); got "
                             f"decode_num_pages={NP}")
        nblk = L // ps
        W = mla_row_width(rkv, dr)
        pool = self.variable("cache", "latent", jnp.zeros, (NP, ps, W),
                             cfg.dtype)
        pt = jnp.broadcast_to(jnp.asarray(pages, jnp.int32), (B, nblk))
        with jax.named_scope("mla.cache_write"):
            pad = jnp.zeros((B, S, W - rkv - dr), cfg.dtype)
            rows = jnp.concatenate([c_kv, k_pe, pad], -1)
            phys = jnp.take_along_axis(pt, jnp.minimum(pos // ps, nblk - 1),
                                       axis=1)
            # a position past the logical cache (a padded tail, a row that
            # is no member of a prefill call) gets an index past the pool:
            # scatters drop out-of-bounds updates
            flat = jnp.where(pos < L, phys * ps + pos % ps, NP * ps)
            pool.value = pool.value.reshape(NP * ps, W).at[
                flat.reshape(-1)].set(rows.reshape(B * S, W), mode="drop"
                                      ).reshape(NP, ps, W)
        with jax.named_scope("mla.attend"):
            if mla_query_rows(B, S, H, W, cfg.dtype) < B:
                note_traced("decode" if S == 1 else "prefill", "dense")
                return mla_paged_attend_rows(
                    q_nope, q_pe, kv_b[..., :dn], kv_b[..., dn:], pool.value,
                    pos, pt, rkv, sm_scale)
            q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, kv_b[..., :dn])
            q = jnp.concatenate(
                [q_lat, q_pe, jnp.zeros((B, S, H, W - rkv - dr), cfg.dtype)],
                -1)
            if S == 1 and cfg.decode_kernel:
                u = mla_paged_decode_attention(
                    q[:, 0], pool.value, pos[:, 0], pt, rkv, sm_scale)[:, None]
            else:
                note_traced("decode" if S == 1 else "prefill", "dense")
                u = mla_paged_attend(q, pool.value, pos, pt, rkv, sm_scale)
            return jnp.einsum("bshr,rhd->bshd", u, kv_b[..., dn:])


class SwiGLU(nn.Module):
    """`down(silu(gate x) * up x)` of `width` (None: the configuration's
    `ffn_hidden_size`), traced under the scope `traced_as`."""
    config: Any
    width: Optional[int] = None
    traced_as: str = "ffn"

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        E, F = x.shape[-1], self.width or cfg.ffn_hidden_size
        def p(name, shape):
            return self.param(name, init, shape).astype(cfg.dtype)
        with jax.named_scope(self.traced_as):
            h = jax.nn.silu(x @ p("gate", (E, F))) * (x @ p("up", (E, F)))
            return h @ p("down", (F, E))


class ShortcutExperts(nn.Module):
    """`parallel.held_experts.shortcut_experts` with its parameters: the
    router over every output, the score-correction bias (a buffer of the
    published model; a parameter here so that it travels with the tree),
    and the held experts' stacked SwiGLU weights."""
    config: LongcatConfig

    @nn.compact
    def __call__(self, y):
        cfg = self.config
        B, S, E = y.shape
        first, count = cfg.held
        if first < 0 or first + count > cfg.n_routed_experts:
            raise ValueError(f"held={cfg.held} is not a range of the "
                             f"{cfg.n_routed_experts} real experts")
        F = cfg.expert_ffn_hidden_size
        router = self.param("router", init, (E, cfg.router_outputs))
        bias = self.param("bias", nn.initializers.zeros,
                          (cfg.router_outputs,))
        def p(name, shape):
            return self.param(name, init, shape).astype(cfg.dtype)
        out, counts = shortcut_experts(
            y.reshape(B * S, E), router, bias, p("gate", (count, E, F)),
            p("up", (count, E, F)), p("down", (count, F, E)),
            held=cfg.held, n_real=cfg.n_routed_experts, top_k=cfg.moe_topk,
            scale=cfg.routed_scaling_factor)
        if cfg.decode:
            self.sow("counters", "picks", jnp.stack(counts))
        return out.reshape(B, S, E)


class LongcatLayer(nn.Module):
    config: LongcatConfig

    @nn.compact
    def __call__(self, x, positions=None, pages=None):
        cfg = self.config
        norm = lambda name: _Norm(cfg.rms_norm_eps, name=name)  # noqa: E731
        attn = lambda name: LatentAttention(cfg, name=name)     # noqa: E731
        h1 = x + attn("attn_0")(norm("norm_a0")(x), positions, pages)
        y = norm("norm_f0")(h1)
        s = ShortcutExperts(cfg, name="moe")(y)
        h2 = h1 + SwiGLU(cfg, name="ffn_0")(y)
        h3 = h2 + attn("attn_1")(norm("norm_a1")(h2), positions, pages)
        return h3 + SwiGLU(cfg, name="ffn_1")(norm("norm_f1")(h3)) + s


def _untied_head(params, h):
    """[T, hidden] final hidden states -> [T, vocab] logits."""
    return h @ params["lm_head"].astype(h.dtype)


class LongcatLM(nn.Module):
    """Token ids [B, S] -> logits [B, S, vocab] (or, `with_head=False`,
    the final hidden states after the last norm), with the call
    signature of `CausalLM` so that `serve/` drives either."""
    config: LongcatConfig

    #: what the serving engine computes logits with (CausalLM's head is
    #: its tied table; this one is untied)
    head_logits = staticmethod(_untied_head)
    #: the "counters" a decode call sows, summed over the layers: picks
    #: on held experts, picks on identity experts, and the layers' largest
    #: held-expert loads added up
    STEP_COUNTERS = ("moe_held_picks", "moe_identity_picks", "moe_load_max")

    @nn.compact
    def __call__(self, tokens, with_head: bool = True, positions=None,
                 pages=None):
        cfg = self.config
        table = self.param("embedding", init,
                           (cfg.vocab_size, cfg.hidden_size))
        h = table.astype(cfg.dtype)[tokens]
        for i in range(cfg.num_layers):
            h = LongcatLayer(cfg, name=f"layer_{i}")(h, positions, pages)
        h = _Norm(cfg.rms_norm_eps, name="norm")(h)
        head = self.param("lm_head", init, (cfg.hidden_size, cfg.vocab_size))
        if not with_head:
            return h
        return h @ head.astype(cfg.dtype)


__all__ = ["LongcatConfig", "LongcatLM", "LatentAttention", "LongcatLayer",
           "ShortcutExperts", "rms_norm", "rope_interleaved"]
