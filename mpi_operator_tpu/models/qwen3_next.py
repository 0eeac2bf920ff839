"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type` qwen3_next):
three layers in four mix their tokens through a gated delta rule, a linear
attention whose state does not grow with the context, the fourth through
GATED grouped-query attention over wide heads, and EVERY layer ends in
routed experts beside a shared one that has a gate of its own; served
through `serve/` like any CausalLM.

    h0 = E[tok]
    layer l:  x' = x + Mixer_l(RMSNorm(x))
              v  = RMSNorm'(x')
              y  = x' + Routed(v) + sigmoid(w_s^T v) * Shared(v)
    logits = W_head RMSNorm(h)                                  (untied)

    Mixer_l  full attention when (l + 1) % `full_attention_interval` == 0,
             else the delta rule.
      delta rule (`GatedDeltaNet`, arXiv:2412.06464):
             [q | k | v | z] = W_qkvz u, [b | a] = W_ba u; [q | k | v] =
             silu(causal depthwise conv, width 4, no bias); q and k of
             each of the `linear_num_key_heads` L2-normalised over their
             `linear_key_head_dim`, q times that dim ^ -0.5; key head j
             serves value heads n j .. n j + n - 1; beta = sigmoid(b),
             g = -exp(A_log) softplus(a + dt_bias) a value head;
             `ops/gated_delta.py` has the recurrence; out = W_o [w *
             RMSNorm_head(o) * silu(z)], the norm over each value head's
             channels, one scale shared by the heads.
      attention (`GatedAttention`): [q | gate] a head from one projection
             twice as wide, k, v; RMSNorm over each query and key head;
             rotate-half RoPE over the first `partial_rotary_factor` of a
             head's dims; scores / sqrt(D), causal, query head i on key
             head i // (H / KV); out = W_o [a * sigmoid(gate)].
    Routed   `parallel/held_experts.py::flat_experts`: softmax over every
             router output, the `num_experts_per_tok` largest, renormalised
             over those (`norm_topk_prob`) = `route(over="picks")`. This
             chip is told which experts it holds (`held = (first, count)`),
             routes over every output and adds its own experts' parts;
             what the others would add is left out.
    Shared   the same SwiGLU at `shared_expert_intermediate_size`, whole on
             every chip, times a sigmoid gate of its own.

A norm's `scale` here is the multiplier itself (the published code holds
`w` and multiplies by `1 + w`, except in the delta rule's gated norm).

What decode mode keeps differs by the KIND of layer (`SLOT_STATE` names
the leaves that lead with the engine's slots; serve/programs.py has the
contract): a delta-rule layer holds `delta [slots, Hv, Dk, Dv]` float32
(2 MB a slot at the published widths) and `conv [slots, 3, 2 Hk Dk +
Hv Dv]` and NO pages; an attention layer holds `cached_kv [num_pages,
page, KV * 2D]`, its own page pool, and no slot state. A position at
`max_len` is junk: the pool drops its write and the mixer holds its state
exactly over it (beta 0, g 0; the tail re-read). A call whose first
position is 0 starts its row from zeros. `cache_only=True` (prefill) stops
after the last layer's mixer: nothing after it is kept.

In decode mode every layer sows (picks on held experts, the largest held
expert's load) into the "counters" collection (`STEP_COUNTERS`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import einsum_f32
from ..ops.gated_delta import (gated_delta_chunk_scan,
                               gated_delta_state_update)
from ..ops.ssm import causal_conv
from ..parallel.held_experts import flat_experts
from .falcon_h1 import grouped_query_attend
from .longcat import SwiGLU, _Norm as RMSNorm
from .phi4flash import _by_rows
from .transformer import _head_matmul, rope

Dtype = Any
init = nn.initializers.normal(stddev=0.02)

#: tokens (rows x positions) the delta-rule mixer of a multi-token call
#: takes at once: a 96 x 128 chunk goes through in six groups of 16 rows,
#: whose float32 q, k, v, writes and decays are a sixth of the call's
_CHUNK_TOKENS = 2048


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    #: positions a request is served over (the config declares 262144)
    max_len: int = 16384
    num_layers: int = 48
    full_attention_interval: int = 4
    hidden_size: int = 2048
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    #: an expert's width
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512              # the router's outputs, as published
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-6
    #: (first, count): the routed experts whose weights live here
    held: Tuple[int, int] = (0, 512)
    dtype: Dtype = jnp.bfloat16
    causal: bool = True
    # decode mode, as in TransformerConfig (models/generate.decode_model
    # flips these on a copy)
    decode: bool = False
    decode_page_size: Optional[int] = None
    decode_num_pages: int = 0
    decode_kernel: bool = False

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_kv_heads={self.num_kv_heads} must divide "
                             f"num_heads={self.num_heads}")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"linear_num_key_heads={self.linear_num_key_heads} must "
                f"divide linear_num_value_heads={self.linear_num_value_heads}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"partial_rotary_factor="
                             f"{self.partial_rotary_factor} of head_dim="
                             f"{self.head_dim} is no even number of dims")
        if self.num_layers < 1 or self.full_attention_interval < 1:
            raise ValueError("a model has layers, and every "
                             "full_attention_interval-th attends in full")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_experts} routed experts")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(
            "attention" if (l + 1) % self.full_attention_interval == 0
            else "delta" for l in range(self.num_layers))

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv sees: q, k and v."""
        return 2 * self.key_dim + self.value_dim


class GatedDeltaNet(nn.Module):
    """The delta-rule mixer of one layer."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, u, positions=None):
        cfg = self.config
        B, S, E = u.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        Dk, Dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        Kd, Vd, Dc = cfg.key_dim, cfg.value_dim, cfg.conv_dim
        W = cfg.linear_conv_kernel_dim
        dt = cfg.dtype
        f32 = jnp.float32
        def p(name, shape, init_fn=init):
            return self.param(name, init_fn, shape)
        w_in = p("in_proj_qkvz", (E, Dc + Vd)).astype(dt)
        w_ba = p("in_proj_ba", (E, 2 * Hv)).astype(dt)
        conv_w = p("conv_w", (W, Dc))
        b_dt = p("dt_bias", (Hv,)).astype(f32)
        A = -jnp.exp(p("A_log", (Hv,)).astype(f32))
        norm_scale = p("norm", (Dv,), nn.initializers.ones)
        w_out = p("out_proj", (Vd, E)).astype(dt)
        no_bias = jnp.zeros((Dc,), f32)

        if cfg.decode:
            pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B, S))
            held = self.variable("cache", "delta", jnp.zeros,
                                 (B, Hv, Dk, Dv), f32)
            conv = self.variable("cache", "conv", jnp.zeros,
                                 (B, W - 1, Dc), dt)
            state, tail = held.value, conv.value
        else:
            pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            state = jnp.zeros((B, Hv, Dk, Dv), f32)
            tail = jnp.zeros((B, W - 1, Dc), dt)

        def mix(u, pos, state, tail):
            G = u.shape[0]
            real = pos < cfg.max_len                              # [G, S]
            # a call whose first position is 0 opens a sequence
            fresh = pos[:, 0] == 0
            tail = jnp.where(fresh[:, None, None], jnp.zeros((), dt), tail)
            with jax.named_scope("gdn.project"):
                proj = u @ w_in
                qkv, z = proj[..., :Dc], proj[..., Dc:]
                ba = einsum_f32("gse,ec->gsc", u, w_ba)
                # beta 0 and g 0 hold the state over a junk position
                beta = jnp.where(real[..., None],
                                 jax.nn.sigmoid(ba[..., :Hv]), 0.0)
                g = jnp.where(real[..., None],
                              A * jax.nn.softplus(ba[..., Hv:] + b_dt), 0.0)
            with jax.named_scope("gdn.conv"):
                qkv, tail = causal_conv(qkv, tail, conv_w, no_bias,
                                        real.sum(-1))
                qkv = jax.nn.silu(qkv)
                q = _l2norm(qkv[..., :Kd].reshape(G, S, Hk, Dk)) * Dk ** -0.5
                k = _l2norm(qkv[..., Kd:2 * Kd].reshape(G, S, Hk, Dk))
                v = qkv[..., 2 * Kd:].reshape(G, S, Hv, Dv)
            if S == 1:
                with jax.named_scope("gdn.update"):
                    o, state = gated_delta_state_update(
                        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                        state, fresh=fresh)
                    o = o[:, None]
            else:
                with jax.named_scope("gdn.chunk"):
                    o, state = gated_delta_chunk_scan(
                        q, k, v, g, beta,
                        jnp.where(fresh[:, None, None, None], 0.0, state))
            with jax.named_scope("gdn.norm"):
                # the norm over each head's channels, then the gate
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
                o = o * norm_scale.astype(f32) * jax.nn.silu(
                    z.astype(f32).reshape(G, S, Hv, Dv))
                o = o.reshape(G, S, Vd).astype(dt)
            with jax.named_scope("gdn.out"):
                return o @ w_out, state, tail

        out, state, tail = _by_rows(mix, _CHUNK_TOKENS // S, u, pos, state,
                                    tail)
        if cfg.decode:
            held.value, conv.value = state, tail
        return out


def _l2norm(x, eps: float = 1e-6):
    """x [.., D] float32 over its last dim, as the published `l2norm`."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def partial_rope(x, pos, rotary_dim: int, theta: float):
    """Rotate-half RoPE over the first `rotary_dim` dims of x [B, S, H, D]
    at absolute positions `pos` [B, S]; the rest pass untouched."""
    return jnp.concatenate(
        [rope(x[..., :rotary_dim], pos, theta), x[..., rotary_dim:]], -1)


class GatedAttention(nn.Module):
    """Grouped-query attention whose query projection is twice as wide:
    one half of a head's columns is its query, the other a sigmoid gate on
    its output; q and k are normed a head and rotated over part of it."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, u, positions=None, pages=None):
        cfg = self.config
        B, S, E = u.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.dtype
        w_qkv = self.param("Wqkv", init, (E, (2 * H + 2 * KV) * D)).astype(dt)
        w_o = self.param("out_proj", init, (H * D, E)).astype(dt)
        q_norm = RMSNorm(cfg.rms_norm_eps, name="q_norm")
        k_norm = RMSNorm(cfg.rms_norm_eps, name="k_norm")
        pos = (jnp.broadcast_to(jnp.arange(S)[None], (B, S))
               if positions is None
               else jnp.broadcast_to(jnp.asarray(positions, jnp.int32),
                                     (B, S)))
        with jax.named_scope("q3attn.project"):
            qkv = u @ w_qkv
            qg = qkv[..., :2 * H * D].reshape(B, S, H, 2 * D)
            gate = qg[..., D:].reshape(B, S, H * D)
            k = qkv[..., 2 * H * D:(2 * H + KV) * D].reshape(B, S, KV, D)
            v = qkv[..., (2 * H + KV) * D:].reshape(B, S, KV, D)
            q = partial_rope(q_norm(qg[..., :D]).astype(dt), pos,
                             cfg.rotary_dim, cfg.rope_theta)
            k = partial_rope(k_norm(k).astype(dt), pos, cfg.rotary_dim,
                             cfg.rope_theta)
        a = grouped_query_attend(self, "q3attn", q, k, v, pos, pages,
                                 1.0 / math.sqrt(D))
        with jax.named_scope("q3attn.out"):
            a = a * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
            return a @ w_o


class Experts(nn.Module):
    """`parallel.held_experts.flat_experts` with its parameters (the
    router over every output, the held experts' stacked SwiGLU weights)
    plus the shared expert behind its own sigmoid gate."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, y):
        cfg = self.config
        B, S, E = y.shape
        count, F = cfg.held[1], cfg.moe_intermediate_size
        router = self.param("router", init, (E, cfg.num_experts))
        def p(name, shape):
            return self.param(name, init, shape).astype(cfg.dtype)
        out, counts = flat_experts(
            y.reshape(B * S, E), router, p("gate", (count, E, F)),
            p("up", (count, E, F)), p("down", (count, F, E)), held=cfg.held,
            top_k=cfg.num_experts_per_tok)
        if cfg.decode:
            self.sow("counters", "picks", jnp.stack(counts))
        shared = SwiGLU(cfg, width=cfg.shared_expert_intermediate_size,
                        traced_as="moe.shared", name="shared")(y)
        w_s = self.param("shared_gate", init, (E,))
        with jax.named_scope("moe.shared"):
            g_s = jax.nn.sigmoid(einsum_f32("bse,e->bs", y,
                                            w_s.astype(y.dtype)))[..., None]
            return (out.reshape(B, S, E)
                    + g_s * shared.astype(jnp.float32)).astype(y.dtype)


class Qwen3NextLayer(nn.Module):
    config: Qwen3NextConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions=None, pages=None, mixer_only=False):
        cfg = self.config
        u = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        if self.kind == "delta":
            mixed = GatedDeltaNet(cfg, name="delta")(u, positions)
        else:
            mixed = GatedAttention(cfg, name="attn")(u, positions, pages)
        h = x + mixed
        if mixer_only:
            return h
        v = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(h)
        return h + Experts(cfg, name="moe")(v)


class Qwen3NextLM(nn.Module):
    """Token ids [B, S] -> logits [B, S, vocab] (or, `with_head=False`,
    the final hidden states after the last norm), with the call
    signature of `CausalLM` so that `serve/` drives either."""
    config: Qwen3NextConfig

    #: cache leaves that lead with the engine's slots, not with the pool's
    #: pages: a delta-rule layer's state and the tail of its conv (an
    #: attention layer holds a pooled `cached_kv` and neither)
    SLOT_STATE = ("delta", "conv")
    #: `apply(..., cache_only=True)` stops after the last layer's mixer;
    #: what it returns besides the cache is not the model's output
    PREFILL_CACHE_ONLY = True
    #: the "counters" a decode call sows, over the layers: picks on held
    #: experts summed, the layers' largest held-expert loads added up
    STEP_COUNTERS = ("moe_held_picks", "moe_load_max")

    def head_logits(self, params, h):
        """[T, hidden] final hidden states -> [T, vocab] float32 logits on
        the untied head."""
        return _head_matmul(h, params["lm_head"].astype(h.dtype))

    @nn.compact
    def __call__(self, tokens, with_head: bool = True, positions=None,
                 pages=None, cache_only: bool = False):
        cfg = self.config
        if cfg.decode:
            ps, NP, L = (cfg.decode_page_size, cfg.decode_num_pages,
                         cfg.max_len)
            if ps is None or pages is None or positions is None:
                raise ValueError(
                    "the cache is a page pool in the attention layers and "
                    "recurrent state in the others, driven by the serving "
                    "engine: decode needs a decode_page_size, per-row "
                    "positions and the [B, max_len // page_size] page table")
            if ps < 1 or L % ps or NP < 2:
                raise ValueError(
                    f"max_len={L} must be a multiple of decode_page_size="
                    f"{ps}, and the pool needs >= 2 pages (page 0 is the "
                    f"trash sink); got decode_num_pages={NP}")
        table = self.param("embedding", init,
                           (cfg.vocab_size, cfg.hidden_size))
        head = self.param("lm_head", init, (cfg.vocab_size, cfg.hidden_size))
        h = table.astype(cfg.dtype)[tokens]
        last = cfg.num_layers - 1
        for l, kind in enumerate(cfg.layer_types):
            h = Qwen3NextLayer(cfg, kind, name=f"layer_{l}")(
                h, positions, pages, mixer_only=cache_only and l == last)
        if cache_only:
            return h
        h = RMSNorm(cfg.rms_norm_eps, name="final_layernorm")(h)
        if not with_head:
            return h
        return self.head_logits({"lm_head": head}, h)


__all__ = ["Qwen3NextConfig", "Qwen3NextLM", "Qwen3NextLayer",
           "GatedDeltaNet", "GatedAttention", "Experts", "partial_rope"]
