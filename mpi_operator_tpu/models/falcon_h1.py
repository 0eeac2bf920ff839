"""Falcon-H1 (tiiuae/Falcon-H1-34B-Instruct, `model_type` falcon_h1): every
layer mixes its tokens TWICE, through a Mamba-2 state-space mixer and
through attention heads that read the same normed input, and adds the two;
served through `serve/` like any CausalLM.

    h0 = embedding_multiplier * E[tok]
    layer:  u  = RMSNorm(x)
            x' = x + ssm_out_multiplier * Mamba2(u)
                   + attention_out_multiplier * Attn(attention_in_multiplier u)
            y  = x' + mlp_multipliers[1] * W_down(up * silu(mlp_multipliers[0]
                 * gate)),  [gate, up] = W_gu RMSNorm'(x')         (no bias)
    logits = lm_head_multiplier * W_head RMSNorm(h)               (untied)

    Attn    H query heads and KV key/value heads of D, no bias; k is
            multiplied by key_multiplier; rotate-half RoPE (rope_theta) on
            q and k; scores / sqrt(D); causal over the whole context.
    Mamba2  (arXiv:2405.21060) p = W_in(ssm_in_multiplier u), its columns
            [z | x | B | C | dt] (d_ssm, d_ssm, groups x N, groups x N,
            heads) scaled by ssm_multipliers[0..4];
            [x | B | C] = silu(conv1d([x | B | C])) (causal, depthwise,
            width 4, bias); dt = softplus(dt + dt_bias); A = -exp(A_log), a
            scalar a head; head h of group h // (heads / groups):
            S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T,
            y_t = S_t^T C_t + D x_t; RMSNorm of y silu(z) over each
            group's channels with a learned scale (the gate BEFORE the
            norm: mamba_norm_before_gate false); W_out.

The muP multipliers are constants of the configuration, not parameters.

What decode mode keeps, EVERY layer of it all three (`SLOT_STATE` names
the leaves that lead with the engine's slots; serve/programs.py has the
contract):

  - `cached_kv [num_pages, page, KV * 2D]`: the layer's own page pool,
    rows as `ops.attention.kv_row_width` has them, written by a flat row
    scatter, read by `paged_decode_attention` in a decode step and by
    `paged_attend` in a chunk;
  - `ssm [slots, heads, N, P]` float32, the recurrent state, states major
    and a head's channels minor (`ops/ssm.py::ssd_state_shape`, which puts
    heads of under 128 channels side by side): 4 MB a slot and layer at
    the 34B model's widths, what 2 048 cached tokens cost. A decode step
    updates it where it lies (`ssd_state_update`), a chunk passes it
    through `ssd_chunk_scan` once;
  - `conv [slots, 3, d_ssm + 2 groups N]`: the conv's last inputs.

A position at `max_len` is junk: the pool drops its write, and the mixer
holds `ssm` and `conv` EXACTLY over it (dt 0; the tail re-read), whether it
is a pad after a row's real tokens or a row that is no member of the call.
A call whose first position is 0 starts its row from zeros, so a slot's
next request needs no reset. `cache_only=True` (prefill) skips the last
layer's MLP and the final norm: nothing after the last mixer is kept.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import (einsum_f32, kv_row_width, note_traced,
                             pack_kv_rows, paged_attend,
                             paged_decode_attention)
from ..ops.ssm import (causal_conv, ssd_chunk_scan, ssd_state_shape,
                       ssd_state_update)
from .longcat import _Norm as RMSNorm
from .phi4flash import _by_rows
from .transformer import _head_matmul, rope

Dtype = Any
init = nn.initializers.normal(stddev=0.02)

#: tokens (rows x positions) a sublayer of a multi-token call takes at
#: once, as in phi4flash.py: a 96 x 128 chunk's widest temporaries (the
#: MLP's [tokens, 2F], the scan's [rows, 128, 128, heads] decay) are a
#: third of what the whole call's would be
_CHUNK_TOKENS = 4096


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    #: positions a request is served over (the config declares 262144)
    max_len: int = 4096
    num_layers: int = 72
    hidden_size: int = 5120
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, float] = (
        0.1767766952966369, 0.011160714285714284)
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
        if (self.mamba_d_ssm % self.mamba_n_heads
                or self.mamba_n_heads % self.mamba_n_groups):
            raise ValueError(
                f"mamba_d_ssm={self.mamba_d_ssm} is split over "
                f"mamba_n_heads={self.mamba_n_heads}, and those over "
                f"mamba_n_groups={self.mamba_n_groups}")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers scales [z | x | B | C | dt] "
                             "and mlp_multipliers (gate, down)")

    @property
    def mamba_d_head(self) -> int:
        return self.mamba_d_ssm // self.mamba_n_heads

    @property
    def conv_dim(self) -> int:
        """Channels the conv sees: x and every group's B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state


class MLP(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        E, F = cfg.hidden_size, cfg.intermediate_size
        m_gate, m_down = cfg.mlp_multipliers
        w_gu = self.param("gate_up", init, (E, 2 * F)).astype(cfg.dtype)
        w_down = self.param("down", init, (F, E)).astype(cfg.dtype)

        def mlp(x):
            with jax.named_scope("mlp"):
                gu = x @ w_gu
                return ((gu[..., F:] * jax.nn.silu(gu[..., :F] * m_gate))
                        @ w_down) * m_down
        return _by_rows(mlp, _CHUNK_TOKENS // x.shape[1], x)


class Mamba2(nn.Module):
    """The Mamba-2 mixer of one layer, without `ssm_out_multiplier`."""
    #: a FalconH1Config, or another model's with the same `mamba_*`
    #: fields, `ssm_in_multiplier` and `ssm_multipliers` (all 1 where it
    #: has none: `granite_hybrid.GraniteHybridConfig`)
    config: Any

    @nn.compact
    def __call__(self, u, positions=None):
        cfg = self.config
        B, S, E = u.shape
        Dm, Hm, P = cfg.mamba_d_ssm, cfg.mamba_n_heads, cfg.mamba_d_head
        K, N, W = cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_d_conv
        Dc = cfg.conv_dim
        dt = cfg.dtype
        f32 = jnp.float32
        def p(name, shape, init_fn=init):
            return self.param(name, init_fn, shape)
        w_in = p("in_proj", (E, 2 * Dm + 2 * K * N + Hm)).astype(dt)
        conv_w, conv_b = p("conv_w", (W, Dc)), p("conv_b", (Dc,))
        b_dt = p("dt_bias", (Hm,)).astype(f32)
        A = -jnp.exp(p("A_log", (Hm,)).astype(f32))
        D = p("D", (Hm,), nn.initializers.ones).astype(f32)
        norm_scale = p("norm", (Dm,), nn.initializers.ones)
        w_out = p("out_proj", (Dm, E)).astype(dt)
        # ssm_multipliers, a column of the projection each (None: all 1)
        mup = None if all(m == 1 for m in cfg.ssm_multipliers) else \
            jnp.concatenate([
                jnp.full((n,), m, f32) for n, m in zip(
                    (Dm, Dm, K * N, K * N, Hm), cfg.ssm_multipliers)])
        held = ssd_state_shape(B, Hm, P, K, N)

        if cfg.decode:
            pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B, S))
            ssm = self.variable("cache", "ssm", jnp.zeros, held, f32)
            conv = self.variable("cache", "conv", jnp.zeros,
                                 (B, W - 1, Dc), dt)
            state, tail = ssm.value, conv.value
        else:
            pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            state = jnp.zeros(held, f32)
            tail = jnp.zeros((B, W - 1, Dc), dt)

        def mix(u, pos, state, tail):
            G = u.shape[0]
            real = pos < cfg.max_len                              # [G, S]
            # a call whose first position is 0 opens a sequence
            fresh = pos[:, 0] == 0
            tail = jnp.where(fresh[:, None, None], jnp.zeros((), dt), tail)
            with jax.named_scope("ssd.project"):
                if cfg.ssm_in_multiplier != 1:
                    u = u * cfg.ssm_in_multiplier
                proj = einsum_f32("gse,ec->gsc", u, w_in)
                proj = (proj if mup is None else proj * mup).astype(dt)
                z, xbc = proj[..., :Dm], proj[..., Dm:Dm + Dc]
                step = jax.nn.softplus(proj[..., Dm + Dc:].astype(f32) + b_dt)
                # dt 0 holds the state over a junk position
                step = jnp.where(real[..., None], step, 0.0)
            with jax.named_scope("ssd.conv"):
                xbc, tail = causal_conv(xbc, tail, conv_w, conv_b,
                                        real.sum(-1))
                xbc = jax.nn.silu(xbc)
                x = xbc[..., :Dm].reshape(G, S, Hm, P)
                Bm = xbc[..., Dm:Dm + K * N].reshape(G, S, K, N)
                Cm = xbc[..., Dm + K * N:].reshape(G, S, K, N)
            if S == 1:
                with jax.named_scope("ssd.update"):
                    y, state = ssd_state_update(
                        x[:, 0], step[:, 0], A, Bm[:, 0], Cm[:, 0], D, state,
                        fresh=fresh)
                    y = y[:, None]
            else:
                with jax.named_scope("ssd.chunk"):
                    y, state = ssd_chunk_scan(
                        x, step, A, Bm, Cm, D,
                        jnp.where(fresh[:, None, None, None], 0.0, state),
                        chunk=cfg.mamba_chunk_size)
            with jax.named_scope("ssd.norm"):
                # the gate, then the norm over each group's channels
                y = y.reshape(G, S, Dm) * jax.nn.silu(z.astype(f32))
                y = y.reshape(G, S, K, Dm // K)
                y = y * jax.lax.rsqrt(
                    jnp.mean(y * y, -1, keepdims=True) + cfg.rms_norm_eps)
                y = (y.reshape(G, S, Dm) * norm_scale.astype(f32)).astype(dt)
            with jax.named_scope("ssd.out"):
                return y @ w_out, state, tail

        out, state, tail = _by_rows(mix, _CHUNK_TOKENS // S, u, pos, state,
                                    tail)
        if cfg.decode:
            ssm.value, conv.value = state, tail
        return out


def grouped_query_attend(mod, scope: str, q, k, v, pos, pages, sm_scale):
    """Causal grouped-query attention of q [B, S, H, D] over k, v
    [B, S, KV, D], for the attention module `mod` (its `config`, its
    cache): the whole sequence in one call with nothing kept, or, in a
    decode model, the keys and values written into the module's own pool
    `cached_kv` [pages, page, width] at `pos` through the page table
    `pages` and read back from it (the Pallas walk for one position a row,
    `paged_attend` for a chunk). `scope` names the device scopes
    (`<scope>.cache_write`, `<scope>.attend`). Returns [B, S, H * D].
    The walk has been run on the chip at heads of 128 in groups of 5
    queries on 4 key heads (Falcon-H1) and of 4 on 8 (Granite-4.0-H), and
    at heads of 256, a head's K two lane tiles, in groups of 8 on 2
    (Qwen3-Next); heads of 64 and groups of 1 through `CausalLM`'s own
    call (gpt2-xl). Other widths compile (tests/test_tpu_compile.py) and
    have not been timed."""
    cfg = mod.config
    B, S, H, D = q.shape
    KV, dt = k.shape[2], cfg.dtype
    if not cfg.decode:
        # the whole sequence in one call, nothing kept
        with jax.named_scope(scope + ".attend"):
            q5 = q.reshape(B, S, KV, H // KV, D)
            s = einsum_f32("bskqd,btkd->bkqst", q5, k) * sm_scale
            s = jnp.where(pos[:, None, None, None, :]
                          <= pos[:, None, None, :, None], s, -1e30)
            a = einsum_f32("bkqst,btkd->bskqd",
                           jax.nn.softmax(s, -1).astype(dt), v)
            return a.astype(dt).reshape(B, S, H * D)
    ps, NP, L = cfg.decode_page_size, cfg.decode_num_pages, cfg.max_len
    nblk = L // ps
    width = kv_row_width(KV, D)
    pt = jnp.broadcast_to(jnp.asarray(pages, jnp.int32), (B, nblk))
    ckv = mod.variable("cache", "cached_kv", jnp.zeros, (NP, ps, width), dt)
    with jax.named_scope(scope + ".cache_write"):
        phys = jnp.take_along_axis(
            pt, jnp.minimum(pos // ps, nblk - 1), axis=1)
        # a junk position gets an index past the pool: scatters drop
        # out-of-bounds updates
        flat = jnp.where(pos < L, phys * ps + pos % ps, NP * ps)
        ckv.value = ckv.value.reshape(NP * ps, width).at[
            flat.reshape(-1)].set(
                pack_kv_rows(k, v).reshape(B * S, width),
                mode="drop").reshape(NP, ps, width)
    with jax.named_scope(scope + ".attend"):
        if S == 1 and cfg.decode_kernel:
            a = paged_decode_attention(q[:, 0], ckv.value, pos[:, 0], pt,
                                       sm_scale=sm_scale)[:, None]
        else:
            note_traced("decode" if S == 1 else "prefill", "dense")
            a = paged_attend(q, ckv.value, pos, pt, sm_scale)
        return a.reshape(B, S, H * D)


class Attention(nn.Module):
    """The attention heads of one layer, without
    `attention_out_multiplier`; their input comes already multiplied by
    `attention_in_multiplier`."""
    config: FalconH1Config

    @nn.compact
    def __call__(self, u, positions=None, pages=None):
        cfg = self.config
        B, S, E = u.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.dtype
        w_qkv = self.param("Wqkv", init, (E, (H + 2 * KV) * D)).astype(dt)
        w_o = self.param("out_proj", init, (H * D, E)).astype(dt)
        sm_scale = 1.0 / math.sqrt(D)
        pos = (jnp.broadcast_to(jnp.arange(S)[None], (B, S))
               if positions is None
               else jnp.broadcast_to(jnp.asarray(positions, jnp.int32),
                                     (B, S)))
        with jax.named_scope("h1attn.project"):
            qkv = u @ w_qkv
            q = rope(qkv[..., :H * D].reshape(B, S, H, D), pos,
                     cfg.rope_theta)
            k = rope((qkv[..., H * D:(H + KV) * D] * cfg.key_multiplier)
                     .reshape(B, S, KV, D), pos, cfg.rope_theta)
            v = qkv[..., (H + KV) * D:].reshape(B, S, KV, D)

        a = grouped_query_attend(self, "h1attn", q, k, v, pos, pages,
                                 sm_scale)
        with jax.named_scope("h1attn.out"):
            return a @ w_o


class FalconH1Layer(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, x, positions=None, pages=None, mixers_only=False):
        cfg = self.config
        u = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        h = x + cfg.ssm_out_multiplier * Mamba2(cfg, name="mamba")(
            u, positions) \
            + cfg.attention_out_multiplier * Attention(cfg, name="attn")(
                u * cfg.attention_in_multiplier, positions, pages)
        if mixers_only:
            return h
        return h + MLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, name="pre_ff_layernorm")(h))


class FalconH1LM(nn.Module):
    """Token ids [B, S] -> logits [B, S, vocab] (or, `with_head=False`,
    the final hidden states after the last norm), with the call
    signature of `CausalLM` so that `serve/` drives either."""
    config: FalconH1Config

    #: cache leaves that lead with the engine's slots, not with the pool's
    #: pages: a layer's recurrent state and the tail of its conv, beside
    #: the same layer's pooled `cached_kv` (serve/programs.py)
    SLOT_STATE = ("ssm", "conv")
    #: `apply(..., cache_only=True)` stops after the last layer's mixers;
    #: what it returns besides the cache is not the model's output
    PREFILL_CACHE_ONLY = True

    def head_logits(self, params, h):
        """[T, hidden] final hidden states -> [T, vocab] float32 logits:
        the untied head and `lm_head_multiplier`."""
        return _head_matmul(h, params["lm_head"].astype(h.dtype)) \
            * self.config.lm_head_multiplier

    @nn.compact
    def __call__(self, tokens, with_head: bool = True, positions=None,
                 pages=None, cache_only: bool = False):
        cfg = self.config
        if cfg.decode:
            ps, NP, L = (cfg.decode_page_size, cfg.decode_num_pages,
                         cfg.max_len)
            if ps is None or pages is None or positions is None:
                raise ValueError(
                    "the cache is a page pool and recurrent state a layer, "
                    "driven by the serving engine: decode needs a "
                    "decode_page_size, per-row positions and the "
                    "[B, max_len // page_size] page table")
            if ps < 1 or L % ps or NP < 2:
                raise ValueError(
                    f"max_len={L} must be a multiple of decode_page_size="
                    f"{ps}, and the pool needs >= 2 pages (page 0 is the "
                    f"trash sink); got decode_num_pages={NP}")
        table = self.param("embedding", init,
                           (cfg.vocab_size, cfg.hidden_size))
        head = self.param("lm_head", init, (cfg.vocab_size, cfg.hidden_size))
        h = table.astype(cfg.dtype)[tokens] * cfg.embedding_multiplier
        for l in range(cfg.num_layers):
            h = FalconH1Layer(cfg, name=f"layer_{l}")(
                h, positions, pages,
                mixers_only=cache_only and l == cfg.num_layers - 1)
        if cache_only:
            return h
        h = RMSNorm(cfg.rms_norm_eps, name="final_layernorm")(h)
        if not with_head:
            return h
        return self.head_logits({"lm_head": head}, h)


__all__ = ["FalconH1Config", "FalconH1LM", "FalconH1Layer", "Mamba2",
           "Attention", "MLP"]
