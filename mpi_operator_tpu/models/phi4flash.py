"""Phi-4-mini-flash-reasoning: a decoder-hybrid-decoder (arXiv:2507.06607)
of five kinds of mixer in one layer pattern, served through `serve/` like
any CausalLM.

Layer l of L (0-based; `half = L // 2`), LayerNorm with scale and bias,
no positional encoding of any kind (the state-space layers carry order):

    h = x + Mixer_l(LN(x));   y = h + MLP(LN'(h))
    MLP(u) = W_down(up * silu(gate)),  [gate, up] = W_gu u   (no bias)

    l even, l <= half   Mamba-1 (arXiv:2312.00752): [x, z] = W_in u;
                        x = silu(conv1d(x)) (causal, depthwise, bias);
                        [dr, B, C] = W_x x; delta = softplus(W_dt dr + b);
                        A = -exp(A_log);
                        s_t = exp(delta_t A) s_{t-1} + (delta_t x_t) B_t^T;
                        y_t = s_t C_t + D x_t;  out = W_out(y_t silu(z_t)).
                        Layer `half` also hands m_t = y_t (before the
                        gate) up to the layers above it.
    l odd,  l < half    differential attention inside a window: token t
                        sees positions t - window < p <= t, own K and V.
    l = half + 1        differential attention over the whole context;
                        its K and V are the only ones kept for a context.
    l even, l > half    gated memory unit: out = W_out(silu(W_in u) * m_t).
    l odd,  l > half+1  cross differential attention: q = W_q u + b only;
                        keys and values are layer half+1's.

Differential attention (arXiv:2410.05258): q (H heads of D), k, v (KV
heads of D), all with bias. Heads pair up adjacently: q heads (2i, 2i+1) =
(q1_i, q2_i), kv heads (2j, 2j+1) = (k1_j, k2_j), (v1_j, v2_j); pair i
reads kv pair i // (H // KV).
`a1_i = softmax(q1_i k1_j^T / sqrt(D)) [v1_j | v2_j]`, `a2_i` the same
from q2_i, k2_j; `lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)`,
`lam0(l) = 0.8 - 0.6 exp(-0.3 l)`; `o_i = (1 - lam0) RMSNorm_2D(a1_i -
lam a2_i)`, and the H/2 x 2D values go through W_o (bias). That IS
ordinary attention with H query heads and KV/2 heads of 2D — query
[q1 | 0] or [0 | q2] against key [k1 | k2], value [v1 | v2], scale
1/sqrt(D) — followed by a subtraction and a norm a pair, so the per-head
page pool and its kernel (`ops.attention.kv_row_width`,
`paged_decode_attention`) take it as it is.

What decode mode keeps, a served row (`SLOT_STATE` names the leaves that
lead with the engine's slots; serve/programs.py has the contract):

  - layer half+1: ONE page pool `cached_kv [num_pages, page, KV * 2D]`,
    written once a token, read by that layer and every cross layer;
  - a window layer: a ring `ring [slots, R, KV * 2D]`, R = the window
    rounded up to whole pages plus one page; position p lives at p % R.
    A decode step writes its row and reads the ring through the paged
    kernel with its lower bound (the ring IS `R / page` pages a slot, and
    a window touches at most that many); a chunk attends the old ring
    and itself, then leaves its last R positions. What a slot holds does
    not grow with the context;
  - a Mamba layer: `ssm [slots, N, Din]` float32 (channels minor,
    `ops/ssm.py`) and `conv [slots, K-1, Din]`, the conv's last inputs.

A position at `max_len` is junk: pool and ring drop its write, and the
state-space layers hold `ssm` and `conv` EXACTLY over it (delta 0; the
tail re-read), whether it is a pad after a row's real tokens or a row
that is no member of the call. A call whose first position is 0 starts
its row from zeros, so a slot's next request needs no reset.
`cache_only=True` (prefill) stops after layer half+1: the layers above
keep nothing.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import (NEG_INF, einsum_f32, kv_row_width, note_traced,
                             pack_kv_rows, paged_attend,
                             paged_decode_attention)
from ..ops.ssm import causal_conv, selective_scan

Dtype = Any
init = nn.initializers.normal(stddev=0.02)

#: tokens (rows x positions) a sublayer of a multi-token call takes at
#: once: rows go through in groups, so a 64 x 512 chunk's widest
#: temporaries (the MLP's [tokens, 2F], the scan's float32 inputs) are an
#: eighth of what the whole call's would be
_CHUNK_TOKENS = 4096
#: float32 scores a window layer's chunk holds at once
_CHUNK_SCORES = 1 << 26


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    #: positions a request is served over (the config declares 262144)
    max_len: int = 16384
    num_layers: int = 32
    hidden_size: int = 2560
    num_heads: int = 40
    num_kv_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None     # None: hidden_size // 16
    dtype: Dtype = jnp.bfloat16
    causal: bool = True
    # decode mode, as in TransformerConfig (models/generate.decode_model
    # flips these on a copy)
    decode: bool = False
    decode_page_size: Optional[int] = None
    decode_num_pages: int = 0
    decode_kernel: bool = False

    def __post_init__(self):
        if self.mb_per_layer != 2 or self.num_layers % 4:
            raise ValueError(
                f"the layer pattern is written for mb_per_layer=2 and a "
                f"depth that is a multiple of 4; got mb_per_layer="
                f"{self.mb_per_layer}, num_layers={self.num_layers}")
        if self.num_heads % self.num_kv_heads or self.num_kv_heads % 2:
            raise ValueError(
                f"differential attention pairs adjacent heads: "
                f"num_kv_heads={self.num_kv_heads} must be even and "
                f"divide num_heads={self.num_heads}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or self.hidden_size // 16

    @property
    def shared_kv_layer(self) -> int:
        """The one layer whose keys and values are kept for a context;
        the last layer that keeps anything."""
        return self.num_layers // 2 + 1

    def layer_kind(self, l: int) -> str:
        half = self.num_layers // 2
        if l % 2 == 0:
            return "mamba" if l <= half else "gmu"
        if l < half:
            return "swa"
        return "full" if l == half + 1 else "cross"

    def lambda_init(self, l: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * l)

    def ring_pages(self, page_size: int) -> int:
        """Pages of a window layer's ring: what `sliding_window`
        consecutive positions can touch."""
        return -(-self.sliding_window // page_size) + 1


def _by_rows(fn, rows: int, *args):
    """`fn(*args)` over groups of at most `rows` leading rows at a time
    (`lax.map`; the whole call where that is all of them). Arguments and
    results lead with the rows."""
    B = args[0].shape[0]
    G = max(d for d in range(1, B + 1) if B % d == 0 and d <= max(1, rows))
    if G == B:
        return fn(*args)
    out = jax.lax.map(
        lambda a: fn(*a),
        tuple(a.reshape((B // G, G) + a.shape[1:]) for a in args))
    return jax.tree.map(lambda x: x.reshape((B,) + x.shape[2:]), out)


class LayerNorm(nn.Module):
    eps: float
    dtype: Dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(self.dtype)


class MLP(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        E, F = cfg.hidden_size, cfg.intermediate_size
        w_gu = self.param("gate_up", init, (E, 2 * F)).astype(cfg.dtype)
        w_down = self.param("down", init, (F, E)).astype(cfg.dtype)

        def mlp(x):
            with jax.named_scope("mlp"):
                gu = x @ w_gu
                return (gu[..., F:] * jax.nn.silu(gu[..., :F])) @ w_down
        return _by_rows(mlp, _CHUNK_TOKENS // x.shape[1], x)


class Mamba(nn.Module):
    """The Mamba-1 mixer. Returns (out, y): `y` is the scan's result
    before the gate, which layer `half` hands up."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, positions=None):
        cfg = self.config
        B, S, E = u.shape
        Din, N, R, K = (cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank,
                        cfg.mamba_d_conv)
        dt = cfg.dtype
        f32 = jnp.float32
        def p(name, shape, init_fn=init):
            return self.param(name, init_fn, shape)
        w_in = p("in_proj", (E, 2 * Din)).astype(dt)
        conv_w, conv_b = p("conv_w", (K, Din)), p("conv_b", (Din,))
        w_x = p("x_proj", (Din, R + 2 * N)).astype(dt)
        w_dt = p("dt_proj", (R, Din)).astype(dt)
        b_dt = p("dt_bias", (Din,)).astype(f32)
        A = -jnp.exp(p("A_log", (Din, N)).astype(f32)).T          # [N, Din]
        D = p("D", (Din,), nn.initializers.ones).astype(f32)
        w_out = p("out_proj", (Din, E)).astype(dt)

        if cfg.decode:
            pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B, S))
            ssm = self.variable("cache", "ssm", jnp.zeros, (B, N, Din), f32)
            conv = self.variable("cache", "conv", jnp.zeros,
                                 (B, K - 1, Din), dt)
            state, tail = ssm.value, conv.value
        else:
            pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            state = jnp.zeros((B, N, Din), f32)
            tail = jnp.zeros((B, K - 1, Din), dt)

        def mix(u, pos, state, tail):
            real = pos < cfg.max_len                              # [G, S]
            # a call whose first position is 0 opens a sequence
            fresh = (pos[:, 0] == 0)[:, None, None]
            state = jnp.where(fresh, 0.0, state)
            tail = jnp.where(fresh, jnp.zeros((), dt), tail)
            with jax.named_scope("ssm.project"):
                xz = u @ w_in
                x, z = xz[..., :Din], xz[..., Din:]
            with jax.named_scope("ssm.conv"):
                xc, tail = causal_conv(x, tail, conv_w, conv_b,
                                       real.sum(-1))
                xc = jax.nn.silu(xc)
            with jax.named_scope("ssm.project"):
                dbc = einsum_f32("gsd,dr->gsr", xc.astype(dt), w_x)
                delta = jax.nn.softplus(einsum_f32(
                    "gsr,rd->gsd", dbc[..., :R].astype(dt), w_dt) + b_dt)
                # delta 0 holds the state over a junk position
                delta = jnp.where(real[..., None], delta, 0.0)
            with jax.named_scope("ssm.scan"):
                y, state = selective_scan(
                    xc, delta, A, dbc[..., R:R + N], dbc[..., R + N:], D,
                    state)
            with jax.named_scope("ssm.out"):
                y = y.astype(dt)
                out = (y * jax.nn.silu(z)) @ w_out
            return out, y, state, tail

        out, y, state, tail = _by_rows(mix, _CHUNK_TOKENS // S, u, pos,
                                       state, tail)
        if cfg.decode:
            ssm.value, conv.value = state, tail
        return out, y


def _sub_norm(a, scale, eps):
    a32 = a.astype(jnp.float32)
    y = a32 * jax.lax.rsqrt(jnp.mean(a32 * a32, -1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


class DiffAttention(nn.Module):
    """Differential attention of one layer: `kind` "swa" (window, own
    ring), "full" (whole context, owns the page pool) or "cross" (queries
    only, reads the pool it is given). Returns (out, pool): the pool's
    value for the layers above, None from a window layer."""
    config: Phi4FlashConfig
    kind: str
    layer: int

    @nn.compact
    def __call__(self, u, positions=None, pages=None, shared=None):
        cfg = self.config
        B, S, E = u.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.dtype
        scope = "swa" if self.kind == "swa" else "yoco"
        own_kv = self.kind != "cross"
        lam0 = cfg.lambda_init(self.layer)
        def p(name, shape, init_fn=init):
            return self.param(name, init_fn, shape)
        cols = (H + 2 * KV) * D if own_kv else H * D
        w_qkv = p("Wqkv" if own_kv else "Wq", (E, cols)).astype(dt)
        b_qkv = p("bqkv" if own_kv else "bq", (cols,),
                  nn.initializers.zeros).astype(dt)
        w_o = p("out_proj", (H * D, E)).astype(dt)
        b_o = p("out_bias", (E,), nn.initializers.zeros).astype(dt)
        lam_init = nn.initializers.normal(stddev=0.1)
        lq1, lk1, lq2, lk2 = (p(n, (D,), lam_init).astype(jnp.float32)
                              for n in ("lambda_q1", "lambda_k1",
                                        "lambda_q2", "lambda_k2"))
        sub_scale = p("subln", (2 * D,), nn.initializers.ones)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
        sm_scale = 1.0 / math.sqrt(D)
        pos = (jnp.broadcast_to(jnp.arange(S)[None], (B, S))
               if positions is None
               else jnp.broadcast_to(jnp.asarray(positions, jnp.int32),
                                     (B, S)))

        def queries(u):
            """[G, S, E] -> the pairs' two queries as heads of 2D,
            [q1 | 0] and [0 | q2]: [G, S, H, 2D]."""
            with jax.named_scope(scope + ".project"):
                q = (u @ w_qkv[:, :H * D] + b_qkv[:H * D]).reshape(
                    u.shape[:2] + (H // 2, 2, D))
                zero = jnp.zeros_like(q[..., 0, :])
                return jnp.stack(
                    [jnp.concatenate([q[..., 0, :], zero], -1),
                     jnp.concatenate([zero, q[..., 1, :]], -1)],
                    3).reshape(u.shape[:2] + (H, 2 * D))

        def kv_rows(u):
            """[G, S, E] -> the positions' pool rows [G, S, KV/2 * 4D]."""
            with jax.named_scope(scope + ".project"):
                kv = u @ w_qkv[:, H * D:] + b_qkv[H * D:]
                k, v = (kv[..., i * KV * D:(i + 1) * KV * D].reshape(
                    u.shape[:2] + (KV // 2, 2 * D)) for i in (0, 1))
                return pack_kv_rows(k, v)

        def output(a):
            """[G, S, H, 2D] attention results -> [G, S, E]."""
            with jax.named_scope(scope + ".out"):
                a = a.reshape(a.shape[:2] + (H // 2, 2, 2 * D)).astype(
                    jnp.float32)
                o = (1.0 - lam0) * _sub_norm(
                    a[..., 0, :] - lam * a[..., 1, :], sub_scale,
                    cfg.layer_norm_eps)
                return o.astype(dt).reshape(a.shape[:2] + (H * D,)) @ w_o \
                    + b_o

        if not cfg.decode:
            # the whole sequence in one call, nothing kept: the upper
            # decoder is handed the full layer's rows themselves
            rows = kv_rows(u) if own_kv else shared
            with jax.named_scope(scope + ".attend"):
                a = _by_rows(
                    lambda q, rows, pos: self._chunk_attend(
                        q, rows[:, :0], rows, pos, sm_scale,
                        window=(cfg.sliding_window if self.kind == "swa"
                                else None)),
                    1, queries(u), rows, pos)
            return output(a), rows if self.kind != "swa" else None
        if self.kind == "swa":
            return self._ring(u, pos, queries, kv_rows, output,
                              sm_scale), None
        return self._pooled(u, pos, pages, shared, queries,
                            kv_rows if own_kv else None, output, sm_scale)

    def _chunk_attend(self, q, before, rows, pos, sm_scale, window=None,
                      before_pos=None):
        """A call's queries q [G, S, H, 2D] at `pos` over the cached rows
        `before` [G, R, W] (at `before_pos`; a negative one holds
        nothing) and the call's own `rows` [G, S, W], dense. A key is
        seen at positions <= the query's, inside `window` if given."""
        cfg = self.config
        G, S, H, D2 = q.shape
        KV2 = cfg.num_kv_heads // 2
        keys = jnp.concatenate([before, rows], 1)
        T = keys.shape[1]
        kpos = pos if before_pos is None else jnp.concatenate(
            [before_pos, pos], 1)                                # [G, T]
        kpos = jnp.where(kpos < 0, cfg.max_len, kpos)
        kv = keys.reshape(G, T, KV2, 2, D2)
        q5 = q.reshape(G, S, KV2, H // KV2, D2)
        s = einsum_f32("gskqd,gtkd->gkqst", q5, kv[:, :, :, 0]) * sm_scale
        seen = kpos[:, None, :] <= pos[:, :, None]                # [G, S, T]
        if window is not None:
            seen &= kpos[:, None, :] > pos[:, :, None] - window
        s = jnp.where(seen[:, None, None], s, NEG_INF)
        prob = jax.nn.softmax(s, axis=-1).astype(keys.dtype)
        a = einsum_f32("gkqst,gtkd->gskqd", prob, kv[:, :, :, 1])
        return a.astype(q.dtype).reshape(G, S, H, D2)

    def _ring(self, u, pos, queries, kv_rows, output, sm_scale):
        """A window layer in decode mode: this call's rows go into the
        slot's ring, and its queries attend the window."""
        cfg = self.config
        B, S, _ = u.shape
        H = cfg.num_heads
        ps, L, W = cfg.decode_page_size, cfg.max_len, cfg.sliding_window
        nr = cfg.ring_pages(ps)
        R = nr * ps
        width = kv_row_width(cfg.num_kv_heads // 2, 2 * cfg.head_dim)
        ring = self.variable("cache", "ring", jnp.zeros, (B, R, width),
                             cfg.dtype)

        def write(ring_value, rows):
            # the last R real positions of the call, each at p % R; a junk
            # position, or one the call itself overwrites, is dropped
            real = pos < L
            last = jnp.max(jnp.where(real, pos, -1), axis=1, keepdims=True)
            keep = real & (pos > last - R)
            flat = jnp.where(keep, jnp.arange(B)[:, None] * R + pos % R,
                             B * R)
            return ring_value.reshape(B * R, width).at[flat.reshape(-1)].set(
                rows.reshape(B * S, width), mode="drop").reshape(B, R, width)

        if S == 1 and cfg.decode_kernel and self._kernel_tiles(ring.value):
            q = queries(u)
            with jax.named_scope("swa.cache_write"):
                ring.value = write(ring.value, kv_rows(u))
            with jax.named_scope("swa.attend"):
                # the ring as `nr` pages a slot: logical page b of a row
                # lives in ring page b % nr, and the window's pages are
                # the table, counted from its first
                cur = pos[:, 0]
                first = jnp.maximum(cur - W + 1, 0) // ps
                table = (jnp.arange(B)[:, None] * nr
                         + (first[:, None] + jnp.arange(nr)[None]) % nr)
                a = paged_decode_attention(
                    q[:, 0], ring.value.reshape(B * nr, ps, width),
                    cur - first * ps, table, window=W,
                    sm_scale=sm_scale)[:, None]
            return output(a)
        note_traced("decode" if S == 1 else "prefill", "dense")
        # slot i of the ring holds the last position before the call that
        # is i modulo R
        before = pos[:, :1] - 1
        held = before - (before - jnp.arange(R)[None]) % R           # [B, R]

        rows = kv_rows(u)
        with jax.named_scope("swa.attend"):
            a = _by_rows(
                lambda q, ring, rows, pos, held: self._chunk_attend(
                    q, ring, rows, pos, sm_scale, window=W, before_pos=held),
                _CHUNK_SCORES // (H * S * (R + S)), queries(u), ring.value,
                rows, pos, held)
        with jax.named_scope("swa.cache_write"):
            ring.value = write(ring.value, rows)
        return output(a)

    def _kernel_tiles(self, cache) -> bool:
        """Whether the paged kernel can take this cache's page blocks
        (Mosaic's second-minor tiling); on TPU a shape it cannot take is
        an error, as in `transformer._decode_attend`."""
        ps = self.config.decode_page_size
        need = 16 if cache.dtype == jnp.bfloat16 else 8
        if ps % need == 0:
            return True
        if jax.default_backend() == "tpu":
            raise ValueError(
                f"decode_kernel=True but decode_page_size={ps} is not a "
                f"multiple of {need}, the second-minor tile of a "
                f"{cache.dtype.name} page block")
        return False

    def _pooled(self, u, pos, pages, shared, queries, kv_rows, output,
                sm_scale):
        """The layers over the ONE page pool: the layer that owns it
        (`kv_rows` given) writes this call's rows first; every one
        attends through the row's page table."""
        cfg = self.config
        B, S, _ = u.shape
        ps, NP, L = cfg.decode_page_size, cfg.decode_num_pages, cfg.max_len
        nblk = L // ps
        pt = jnp.broadcast_to(jnp.asarray(pages, jnp.int32), (B, nblk))
        if kv_rows is not None:
            width = kv_row_width(cfg.num_kv_heads // 2, 2 * cfg.head_dim)
            ckv = self.variable("cache", "cached_kv", jnp.zeros,
                                (NP, ps, width), cfg.dtype)
            rows = kv_rows(u)
            with jax.named_scope("yoco.cache_write"):
                phys = jnp.take_along_axis(
                    pt, jnp.minimum(pos // ps, nblk - 1), axis=1)
                # a junk position gets an index past the pool: scatters
                # drop out-of-bounds updates
                flat = jnp.where(pos < L, phys * ps + pos % ps, NP * ps)
                ckv.value = ckv.value.reshape(NP * ps, width).at[
                    flat.reshape(-1)].set(rows.reshape(B * S, width),
                                          mode="drop").reshape(NP, ps, width)
            pool = ckv.value
        else:
            pool = shared
        q = queries(u)
        with jax.named_scope("yoco.attend"):
            if S == 1 and cfg.decode_kernel and self._kernel_tiles(pool):
                a = paged_decode_attention(q[:, 0], pool, pos[:, 0], pt,
                                           sm_scale=sm_scale)[:, None]
            else:
                note_traced("decode" if S == 1 else "prefill", "dense")
                a = paged_attend(q, pool, pos, pt, sm_scale)
        return output(a), pool


class GatedMemory(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, m):
        cfg = self.config
        E, Din = cfg.hidden_size, cfg.d_inner
        w_in = self.param("in_proj", init, (E, Din)).astype(cfg.dtype)
        w_out = self.param("out_proj", init, (Din, E)).astype(cfg.dtype)
        with jax.named_scope("gmu"):
            return (jax.nn.silu(u @ w_in) * m) @ w_out


class Embedding(nn.Module):
    """The token table, under the name the engine's tied head reads
    (`wte/embedding`)."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        return self.param("embedding", init,
                          (cfg.vocab_size, cfg.hidden_size)).astype(cfg.dtype)


class Phi4FlashLayer(nn.Module):
    config: Phi4FlashConfig
    layer: int

    @nn.compact
    def __call__(self, x, carry, positions=None, pages=None):
        """`carry` is what the lower decoder hands the upper one:
        {"m": layer half's scan result, "kv": the page pool}."""
        cfg = self.config
        kind = cfg.layer_kind(self.layer)
        def norm(name):
            return LayerNorm(cfg.layer_norm_eps, cfg.dtype, name=name)
        u = norm("input_layernorm")(x)
        if kind == "mamba":
            out, y = Mamba(cfg, name="mamba")(u, positions)
            if self.layer == cfg.num_layers // 2:
                carry = {**carry, "m": y}
        elif kind == "gmu":
            out = GatedMemory(cfg, name="gmu")(u, carry["m"])
        else:
            out, pool = DiffAttention(cfg, kind, self.layer, name="attn")(
                u, positions, pages, carry.get("kv"))
            if kind == "full":
                carry = {**carry, "kv": pool}
        h = x + out
        return h + MLP(cfg, name="mlp")(
            norm("post_attention_layernorm")(h)), carry


class Phi4FlashLM(nn.Module):
    """Token ids [B, S] -> logits [B, S, vocab] (or, `with_head=False`,
    the final hidden states after the last norm), with the call
    signature of `CausalLM` so that `serve/` drives either. The head is
    the tied table `wte/embedding`, which is the engine's default."""
    config: Phi4FlashConfig

    #: cache leaves that lead with the engine's slots, not with the pool's
    #: pages: a window layer's ring, a state-space layer's state and the
    #: tail of its conv (serve/programs.py has what follows from them)
    SLOT_STATE = ("ring", "ssm", "conv")
    #: `apply(..., cache_only=True)` stops after the last layer that
    #: keeps anything; what it returns besides the cache is not the
    #: model's output
    PREFILL_CACHE_ONLY = True

    @nn.compact
    def __call__(self, tokens, with_head: bool = True, positions=None,
                 pages=None, cache_only: bool = False):
        cfg = self.config
        if cfg.decode:
            ps, NP, L = (cfg.decode_page_size, cfg.decode_num_pages,
                         cfg.max_len)
            if ps is None or pages is None or positions is None:
                raise ValueError(
                    "the cache is a page pool, rings and recurrent state "
                    "driven by the serving engine: decode needs a "
                    "decode_page_size, per-row positions and the "
                    "[B, max_len // page_size] page table")
            if ps < 1 or L % ps or NP < 2:
                raise ValueError(
                    f"max_len={L} must be a multiple of decode_page_size="
                    f"{ps}, and the pool needs >= 2 pages (page 0 is the "
                    f"trash sink); got decode_num_pages={NP}")
        table = Embedding(cfg, name="wte")()
        h = table[tokens]
        carry = {}
        last = cfg.shared_kv_layer if cache_only else cfg.num_layers - 1
        for l in range(last + 1):
            h, carry = Phi4FlashLayer(cfg, l, name=f"layer_{l}")(
                h, carry, positions, pages)
        if cache_only:
            return h
        h = LayerNorm(cfg.layer_norm_eps, cfg.dtype, name="final_layernorm")(h)
        if not with_head:
            return h
        return jax.lax.dot_general(
            h, table, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


__all__ = ["Phi4FlashConfig", "Phi4FlashLM", "Phi4FlashLayer",
           "DiffAttention", "Mamba", "GatedMemory", "MLP", "LayerNorm"]
