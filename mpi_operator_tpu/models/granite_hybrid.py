"""Granite-4.0-H (ibm-granite/granite-4.0-h-small, `model_type`
granitemoehybrid): most layers mix their tokens through a Mamba-2
state-space mixer, one in ten through grouped-query attention that has NO
positional encoding, and EVERY layer ends in routed experts beside a
shared one; served through `serve/` like any CausalLM.

    h0 = embedding_multiplier * E[tok]
    layer l:  x' = x + residual_multiplier * Mixer_l(RMSNorm(x))
              v  = RMSNorm'(x')
              y  = x' + residual_multiplier * (Routed(v) + Shared(v))
    logits = (RMSNorm(h) E^T) / logits_scaling                  (tied)

    Mixer_l  `layer_types[l]` "mamba": the Mamba-2 mixer of
             `models/falcon_h1.py` (arXiv:2405.21060) with no muP factors
             inside: in_proj to [z | x | B | C | dt], a causal depthwise
             conv with bias over [x | B | C] and silu, dt = softplus(dt +
             dt_bias), A = -exp(A_log) a head, heads of `mamba_d_head`
             channels over `mamba_d_state` states that share the B and C
             of their group, a gated RMSNorm over each group's channels
             of y silu(z), out_proj. "attention": H query heads on KV key
             heads of D, no bias, NO rotary or other position term
             (`position_embedding_type` nope); scores times
             `attention_multiplier` (a published constant, not D^-0.5);
             causal softmax; query head i reads key head i // (H / KV).
    Routed   logits = W_r v over `num_local_experts` outputs in float32;
             the `num_experts_per_tok` largest LOGITS; weights a softmax
             over those alone (`parallel/held_experts.py::route(over=
             "picks")`); expert e: W2_e (silu(a) * b), [a | b] = W1_e v,
             held here as `gate`, `up` and `down` stacked over the held
             experts. This chip is told which experts it holds (`held =
             (first, count)`), routes over every output and adds its own
             experts' parts; what the others would add is left out.
    Shared   the same SwiGLU at `shared_intermediate_size`, whole on
             every chip.

The four multipliers are constants of the configuration, not parameters.

What decode mode keeps differs by the KIND of layer (`SLOT_STATE` names
the leaves that lead with the engine's slots; serve/programs.py has the
contract): a mamba layer holds `ssm` (float32, `ops/ssm.py::
ssd_state_shape`: heads of 64 channels two to a lane tile) and `conv
[slots, 3, d_ssm + 2 groups N]` and NO pages; an attention layer holds
`cached_kv [num_pages, page, KV * 2D]`, its own page pool, and no slot
state. A position at `max_len` is junk: the pool drops its write and the
mixer holds its state exactly over it. A call whose first position is 0
starts its row from zeros. `cache_only=True` (prefill) stops after the
last layer's mixer: nothing after it is kept.

In decode mode every layer sows (picks on held experts, the largest held
expert's load) into the "counters" collection (`STEP_COUNTERS`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.held_experts import flat_experts
from .falcon_h1 import Mamba2, grouped_query_attend
from .longcat import SwiGLU, _Norm as RMSNorm
from .transformer import _head_matmul

Dtype = Any
init = nn.initializers.normal(stddev=0.02)

#: `layer_types` of granite-4.0-h-small: attention at layers 5, 15, 25, 35
PUBLISHED_LAYER_TYPES = tuple(
    "attention" if l % 10 == 5 else "mamba" for l in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    #: positions a request is served over (the config declares 131072)
    max_len: int = 16384
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    hidden_size: int = 4096
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    #: an expert's width (the config's `intermediate_size`)
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72         # the router's outputs, as published
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-5
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    #: (first, count): the routed experts whose weights live here
    held: Tuple[int, int] = (0, 72)
    dtype: Dtype = jnp.bfloat16
    causal: bool = True
    # decode mode, as in TransformerConfig (models/generate.decode_model
    # flips these on a copy)
    decode: bool = False
    decode_page_size: Optional[int] = None
    decode_num_pages: int = 0
    decode_kernel: bool = False

    # what `falcon_h1.Mamba2` asks of a configuration beside the fields:
    # this model has no muP factors inside its mixer
    ssm_in_multiplier = 1.0
    ssm_multipliers = (1.0,) * 5

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_kv_heads={self.num_kv_heads} must divide "
                             f"num_heads={self.num_heads}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                f"mamba_n_heads={self.mamba_n_heads} are split over "
                f"mamba_n_groups={self.mamba_n_groups}")
        odd = set(self.layer_types) - {"mamba", "attention"}
        if odd or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(odd)}: a layer is "
                             f"'mamba' or 'attention'")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_local_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_local_experts} routed experts")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def mamba_d_ssm(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels the conv sees: x and every group's B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state


class NopeAttention(nn.Module):
    """Grouped-query attention with no position term; its scores are
    scaled by `attention_multiplier`."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u, positions=None, pages=None):
        cfg = self.config
        B, S, E = u.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.dtype
        w_qkv = self.param("Wqkv", init, (E, (H + 2 * KV) * D)).astype(dt)
        w_o = self.param("out_proj", init, (H * D, E)).astype(dt)
        sm_scale = cfg.attention_multiplier
        pos = (jnp.broadcast_to(jnp.arange(S)[None], (B, S))
               if positions is None
               else jnp.broadcast_to(jnp.asarray(positions, jnp.int32),
                                     (B, S)))
        with jax.named_scope("g4attn.qkv"):
            qkv = u @ w_qkv
            q = qkv[..., :H * D].reshape(B, S, H, D)
            k = qkv[..., H * D:(H + KV) * D].reshape(B, S, KV, D)
            v = qkv[..., (H + KV) * D:].reshape(B, S, KV, D)
        a = grouped_query_attend(self, "g4attn", q, k, v, pos, pages,
                                 sm_scale)
        with jax.named_scope("g4attn.out"):
            return a @ w_o


class Experts(nn.Module):
    """`parallel.held_experts.flat_experts` with its parameters (the
    router over every output, the held experts' stacked SwiGLU weights)
    plus the shared expert."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, y):
        cfg = self.config
        B, S, E = y.shape
        count, F = cfg.held[1], cfg.intermediate_size
        router = self.param("router", init, (E, cfg.num_local_experts))
        def p(name, shape):
            return self.param(name, init, shape).astype(cfg.dtype)
        out, counts = flat_experts(
            y.reshape(B * S, E), router, p("gate", (count, E, F)),
            p("up", (count, E, F)), p("down", (count, F, E)), held=cfg.held,
            top_k=cfg.num_experts_per_tok)
        if cfg.decode:
            self.sow("counters", "picks", jnp.stack(counts))
        shared = SwiGLU(cfg, width=cfg.shared_intermediate_size,
                        traced_as="moe.shared", name="shared")(y)
        return (out.reshape(B, S, E) + shared.astype(jnp.float32)
                ).astype(y.dtype)


class GraniteHybridLayer(nn.Module):
    config: GraniteHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions=None, pages=None, mixer_only=False):
        cfg = self.config
        u = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        if self.kind == "mamba":
            mixed = Mamba2(cfg, name="mamba")(u, positions)
        else:
            mixed = NopeAttention(cfg, name="attn")(u, positions, pages)
        h = x + cfg.residual_multiplier * mixed
        if mixer_only:
            return h
        v = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(h)
        return h + cfg.residual_multiplier * Experts(cfg, name="moe")(v)


class GraniteHybridLM(nn.Module):
    """Token ids [B, S] -> logits [B, S, vocab] (or, `with_head=False`,
    the final hidden states after the last norm), with the call
    signature of `CausalLM` so that `serve/` drives either."""
    config: GraniteHybridConfig

    #: cache leaves that lead with the engine's slots, not with the pool's
    #: pages: a mamba layer's recurrent state and the tail of its conv
    #: (an attention layer holds a pooled `cached_kv` and neither)
    SLOT_STATE = ("ssm", "conv")
    #: `apply(..., cache_only=True)` stops after the last layer's mixer;
    #: what it returns besides the cache is not the model's output
    PREFILL_CACHE_ONLY = True
    #: the "counters" a decode call sows, over the layers: picks on held
    #: experts summed, the layers' largest held-expert loads added up
    STEP_COUNTERS = ("moe_held_picks", "moe_load_max")

    def head_logits(self, params, h):
        """[T, hidden] final hidden states -> [T, vocab] float32 logits:
        the tied table over `logits_scaling`."""
        return _head_matmul(h, params["embedding"].astype(h.dtype)) \
            / self.config.logits_scaling

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
        h = table.astype(cfg.dtype)[tokens] * cfg.embedding_multiplier
        last = cfg.num_layers - 1
        for l, kind in enumerate(cfg.layer_types):
            h = GraniteHybridLayer(cfg, kind, name=f"layer_{l}")(
                h, positions, pages, mixer_only=cache_only and l == last)
        if cache_only:
            return h
        h = RMSNorm(cfg.rms_norm_eps, name="final_layernorm")(h)
        if not with_head:
            return h
        return self.head_logits({"embedding": table}, h)


__all__ = ["GraniteHybridConfig", "GraniteHybridLM", "GraniteHybridLayer",
           "NopeAttention", "Experts", "PUBLISHED_LAYER_TYPES"]
