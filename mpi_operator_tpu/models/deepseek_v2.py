"""DeepSeek-V2: latent attention (MLA) at 128 heads under YaRN, a leading
dense layer, then expert layers with group-limited routing and a shared
expert, served through `serve/` like any CausalLM.

    h = Emb[tok];  x' = x + MLA(norm_attn(x));  out = x' + F_l(norm_ffn(x'))
    logits = RMSNorm(h) W_head                       (untied)

`F_l` is a SwiGLU of `intermediate_size` in the first
`first_k_dense_replace` layers and after them

    F_l(y) = sum_{i in picks} w_i E_i(y) + S(y)

over `n_routed_experts` SwiGLU experts of `moe_intermediate_size` and ONE
shared SwiGLU of `n_shared_experts` times that width which every token
passes. The gate (`group_limited_greedy`, softmax scores): the router's
outputs are `n_group` runs of consecutive experts, the `topk_group` groups
with the largest single score stay, the picks are the top
`num_experts_per_tok` inside them, `w_i = routed_scaling_factor * p_i`,
not renormalised, no bias (`parallel/held_experts.py::route`). The routed
part is that module's: this chip is told which experts it holds (`held =
(first, count)`; one routing group is what one chip of the designed
deployment holds), routes over every output and computes its own
experts' part; the shared expert, which every chip that serves a row
computes whole, is added here.

The attention IS `models/longcat.py::LatentAttention` (one latent row a
position in a page pool, the up-projection absorbed in decode mode); what
this model's differs in comes from the configuration: no `sqrt(hidden /
rank)` factors, and YaRN (`yarn_frequencies`): of the rotary part's
frequencies `f_i = theta^(-2i/d)` those that turn fewer than `beta_slow`
times in the original context are divided by `factor`, those that turn
more than `beta_fast` times are kept, a linear ramp between; the softmax
scale is `(nope + rope)^-0.5 * m(mscale_all_dim)^2` with `m(a) = 0.1 a
ln(factor) + 1`; cos and sin carry `m(mscale) / m(mscale_all_dim)`, which
is 1 as published and anything else is refused. The published code
de-interleaves the rotary pairs and rotates halves: one fixed permutation
of `q_pe` and `k_pe` alike, so the scores are those of rotating
interleaved pairs, which is what `rope_interleaved` does.

In decode mode every expert layer sows (picks on held experts, the
largest held expert's load, rows that kept a held group) into the
"counters" collection (`STEP_COUNTERS`); the dense layer sows nothing.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from ..parallel.held_experts import group_limited_experts
from .longcat import LatentAttention, SwiGLU, _Norm, _untied_head, init

Dtype = Any


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float):
    """(low, high): the pair indices between which YaRN's ramp runs."""
    def index(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    return (max(math.floor(index(beta_fast)), 0),
            min(math.ceil(index(beta_slow)), dim - 1))


def yarn_frequencies(dim: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """The `dim / 2` rotary frequencies under YaRN, float32, computed once
    on the host."""
    f = 1.0 / np.float32(theta) ** (np.arange(0, dim, 2, dtype=np.float32)
                                    / np.float32(dim))
    low, high = yarn_correction_range(dim, theta, original, beta_fast,
                                      beta_slow)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0, 1).astype(np.float32)
    return (f / np.float32(factor)) * ramp + f * (1 - ramp)


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    max_len: int = 163840
    num_layers: int = 60
    hidden_size: int = 5120
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288      # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1536
    first_k_dense_replace: int = 1
    n_routed_experts: int = 160         # the router's outputs, as published
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rope_theta: float = 1e4
    rope_factor: float = 40.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rms_norm_eps: float = 1e-6
    #: (first, count): the routed experts whose weights live here
    held: Tuple[int, int] = (0, 160)
    dtype: Dtype = jnp.bfloat16
    causal: bool = True
    # decode mode, as in LongcatConfig: the latent cache is paged
    decode: bool = False
    decode_page_size: Optional[int] = None
    decode_num_pages: int = 0
    decode_kernel: bool = False

    # what `LatentAttention` asks of a configuration beside the fields
    mla_scale_q_lora = False
    mla_scale_kv_lora = False

    def __post_init__(self):
        f = self.rope_factor
        if yarn_mscale(f, self.rope_mscale) != yarn_mscale(
                f, self.rope_mscale_all_dim):
            raise ValueError(
                "cos and sin would carry m(mscale) / m(mscale_all_dim) = "
                f"{yarn_mscale(f, self.rope_mscale)} / "
                f"{yarn_mscale(f, self.rope_mscale_all_dim)}: only their "
                "published ratio of 1 is built")

    @property
    def sm_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2

    @property
    def rope_freqs(self) -> np.ndarray:
        return yarn_frequencies(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max_len, self.rope_beta_fast,
            self.rope_beta_slow)


class GroupLimitedExperts(nn.Module):
    """`parallel.held_experts.group_limited_experts` with its parameters
    (the router over every output, the held experts' stacked SwiGLU
    weights) plus the shared expert."""
    config: DeepseekV2Config

    @nn.compact
    def __call__(self, y):
        cfg = self.config
        B, S, E = y.shape
        first, count = cfg.held
        if first < 0 or first + count > cfg.n_routed_experts:
            raise ValueError(f"held={cfg.held} is not a range of the "
                             f"{cfg.n_routed_experts} routed experts")
        F = cfg.moe_intermediate_size
        router = self.param("router", init, (E, cfg.n_routed_experts))
        def p(name, shape):
            return self.param(name, init, shape).astype(cfg.dtype)
        out, counts = group_limited_experts(
            y.reshape(B * S, E), router, p("gate", (count, E, F)),
            p("up", (count, E, F)), p("down", (count, F, E)), held=cfg.held,
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            n_group=cfg.n_group, topk_group=cfg.topk_group)
        if cfg.decode:
            self.sow("counters", "picks", jnp.stack(counts))
        shared = SwiGLU(cfg, width=cfg.n_shared_experts * F,
                        traced_as="moe.shared", name="shared")(y)
        return (out.reshape(B, S, E) + shared.astype(jnp.float32)
                ).astype(y.dtype)


class DeepseekV2Layer(nn.Module):
    config: DeepseekV2Config
    dense: bool

    @nn.compact
    def __call__(self, x, positions=None, pages=None):
        cfg = self.config
        h = x + LatentAttention(cfg, name="attn")(
            _Norm(cfg.rms_norm_eps, name="norm_attn")(x), positions, pages)
        y = _Norm(cfg.rms_norm_eps, name="norm_ffn")(h)
        if self.dense:
            return h + SwiGLU(cfg, width=cfg.intermediate_size,
                              name="ffn")(y)
        return h + GroupLimitedExperts(cfg, name="moe")(y)


class DeepseekV2LM(nn.Module):
    """Token ids [B, S] -> logits [B, S, vocab] (or, `with_head=False`,
    the final hidden states after the last norm), with the call
    signature of `CausalLM` so that `serve/` drives either."""
    config: DeepseekV2Config

    head_logits = staticmethod(_untied_head)
    #: the "counters" a decode call sows, summed over the expert layers:
    #: picks on held experts, the layers' largest held-expert loads added
    #: up, and rows whose kept groups include a held one (the rows a
    #: deployment's dispatch would send here)
    STEP_COUNTERS = ("moe_held_picks", "moe_load_max", "moe_group_hit_rows")

    @nn.compact
    def __call__(self, tokens, with_head: bool = True, positions=None,
                 pages=None):
        cfg = self.config
        table = self.param("embedding", init,
                           (cfg.vocab_size, cfg.hidden_size))
        h = table.astype(cfg.dtype)[tokens]
        for i in range(cfg.num_layers):
            h = DeepseekV2Layer(cfg, dense=i < cfg.first_k_dense_replace,
                                name=f"layer_{i}")(h, positions, pages)
        h = _Norm(cfg.rms_norm_eps, name="norm")(h)
        head = self.param("lm_head", init, (cfg.hidden_size, cfg.vocab_size))
        if not with_head:
            return h
        return h @ head.astype(cfg.dtype)


__all__ = ["DeepseekV2Config", "DeepseekV2LM", "DeepseekV2Layer",
           "GroupLimitedExperts", "yarn_frequencies",
           "yarn_correction_range", "yarn_mscale"]
