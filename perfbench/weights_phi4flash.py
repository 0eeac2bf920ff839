"""Seeded weights for Phi-4-mini-flash-reasoning, made on the device.

As `weights.py` for GPT-2: the benchmark makes the weights, and the system
under test and the plain reference are each handed what this module makes
from `--seed`. One layer's leaves depend only on (seed, layer index), each
leaf on its own fold of that key, so the reference remakes a layer at a
time and neither side ever holds a draw larger than its largest leaf (the
token table's 512 M values).

The tree uses the names the program's `Phi4FlashLM` uses: `wte/embedding`,
`final_layernorm/{scale,bias}`, and a layer `layer_<i>/` of
`{input_layernorm,post_attention_layernorm}/{scale,bias}`,
`mlp/{gate_up,down}` and, by the layer's kind, `mamba/{in_proj,conv_w,
conv_b,x_proj,dt_proj,dt_bias,A_log,D,out_proj}`, `attn/{Wqkv,bqkv,out_proj,
out_bias,lambda_q1,lambda_k1,lambda_q2,lambda_k2,subln}` (a cross layer:
`Wq`, `bq` for the first two) or `gmu/{in_proj,out_proj}`. `tree_shapes` is
checked against the program's own abstract parameters before anything is
timed.

Every leaf is normal with std 0.02 (`assumed.initializer_range`) — biases
too, so that a program that drops one fails the comparison — except: norm
scales (1 + that); the four lambda vectors of an attention layer, std
`assumed.lambda_std`; and the state-space layer's own initialisation
(arXiv:2312.00752), without which the recurrence is not tested — a random
`A` makes the state blow up or vanish: `A_log = log(1..N)` a channel, `D`
= 1, `dt_bias` the inverse softplus of a step log-uniform in
[`assumed.dt_min`, `assumed.dt_max`], so a channel forgets over tens to
tens of thousands of positions.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key, tree_shapes  # noqa: F401 (re-exported)

KINDS = ("mamba", "swa", "full", "gmu", "cross")


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of one Phi-4-mini-flash configuration as it is run."""
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    ffn: int
    window: int
    vocab: int
    eps: float
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int
    std: float
    lambda_std: float
    dt_min: float
    dt_max: float

    @property
    def vocab_real(self) -> int:
        return self.vocab

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden

    @property
    def half(self) -> int:
        return self.layers // 2

    def kind(self, l: int) -> str:
        """Layer l's mixer (`mb_per_layer` 2: even layers are of the
        state-space kind; `num_hidden_layers // 2` splits the decoders)."""
        if l % 2 == 0:
            return "mamba" if l <= self.half else "gmu"
        if l < self.half:
            return "swa"
        return "full" if l == self.half + 1 else "cross"

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        a = cfg["assumed"]
        if int(cfg["mb_per_layer"]) != 2 or int(cfg["num_hidden_layers"]) % 4:
            raise ValueError("the layer pattern is written for mb_per_layer "
                             "2 and a depth that is a multiple of 4")
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            hidden=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            ffn=int(cfg["intermediate_size"]),
            window=int(cfg["sliding_window"]), vocab=int(cfg["vocab_size"]),
            eps=float(cfg["layer_norm_eps"]),
            d_state=int(a["mamba_d_state"]), d_conv=int(a["mamba_d_conv"]),
            expand=int(a["mamba_expand"]), dt_rank=int(a["mamba_dt_rank"]),
            std=float(a["initializer_range"]),
            lambda_std=float(a["lambda_std"]), dt_min=float(a["dt_min"]),
            dt_max=float(a["dt_max"]))

    def param_count(self) -> int:
        def count(layout):
            return sum(math.prod(shape) for _, shape, _ in layout)
        return count(_top_layout(self)) + sum(
            count(_layer_layout(self, self.kind(l)))
            for l in range(self.layers))


def _norm(name, d: Dims):
    return [((name, "scale"), (d.hidden,), "scale"),
            ((name, "bias"), (d.hidden,), "w")]


def _layer_layout(d: Dims, kind: str):
    """[(path, shape, kind of draw)] of one layer's leaves; a leaf's place
    in the list is its fold of the layer's key."""
    E, F, Din, N, R, K = (d.hidden, d.ffn, d.d_inner, d.d_state, d.dt_rank,
                          d.d_conv)
    H, KV, D = d.heads, d.kv_heads, d.head_dim
    out = _norm("input_layernorm", d) + _norm("post_attention_layernorm", d)
    out += [(("mlp", "gate_up"), (E, 2 * F), "w"),
            (("mlp", "down"), (F, E), "w")]
    if kind == "mamba":
        out += [(("mamba", "in_proj"), (E, 2 * Din), "w"),
                (("mamba", "conv_w"), (K, Din), "w"),
                (("mamba", "conv_b"), (Din,), "w"),
                (("mamba", "x_proj"), (Din, R + 2 * N), "w"),
                (("mamba", "dt_proj"), (R, Din), "w"),
                (("mamba", "dt_bias"), (Din,), "dt_bias"),
                (("mamba", "A_log"), (Din, N), "A_log"),
                (("mamba", "D"), (Din,), "ones"),
                (("mamba", "out_proj"), (Din, E), "w")]
    elif kind == "gmu":
        out += [(("gmu", "in_proj"), (E, Din), "w"),
                (("gmu", "out_proj"), (Din, E), "w")]
    else:
        cols = H * D if kind == "cross" else (H + 2 * KV) * D
        w, b = ("Wq", "bq") if kind == "cross" else ("Wqkv", "bqkv")
        out += [(("attn", w), (E, cols), "w"), (("attn", b), (cols,), "w"),
                (("attn", "out_proj"), (H * D, E), "w"),
                (("attn", "out_bias"), (E,), "w")]
        out += [(("attn", n), (D,), "lambda")
                for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")]
        out += [(("attn", "subln"), (2 * D,), "scale")]
    return out


def _top_layout(d: Dims):
    return [(("wte", "embedding"), (d.vocab, d.hidden), "w")] \
        + _norm("final_layernorm", d)


def _draw(key, shape, how, d: Dims):
    f32 = jnp.float32
    if how == "ones":
        return jnp.ones(shape, f32)
    if how == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=f32)), shape)
    if how == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, f32)
                       * (math.log(d.dt_max) - math.log(d.dt_min))
                       + math.log(d.dt_min))
        return step + jnp.log(-jnp.expm1(-step))      # softplus^-1(step)
    std = d.lambda_std if how == "lambda" else d.std
    leaf = std * jax.random.normal(key, shape, f32)
    return leaf + 1.0 if how == "scale" else leaf


def _make(key, layout, d: Dims, dtype):
    tree = {}
    for i, (path, shape, how) in enumerate(layout):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = _draw(jax.random.fold_in(key, i), shape, how,
                               d).astype(dtype)
    return tree


def layer_params(key, d: Dims, kind: str, layer, dtype):
    """The leaves of layer `layer` (a traced or plain integer), which is of
    `kind`."""
    return _make(jax.random.fold_in(key, 1000 + layer),
                 _layer_layout(d, kind), d, dtype)


def top_params(key, d: Dims, dtype):
    """The token table (the head is tied to it) and the final norm."""
    return _make(jax.random.fold_in(key, 1), _top_layout(d), d, dtype)


def make_params(key, d: Dims, dtype):
    """The whole tree as the program holds it; call under one `jax.jit` so
    it is made on the device in one program."""
    out = top_params(key, d, dtype)
    for l in range(d.layers):
        out[f"layer_{l}"] = layer_params(key, d, d.kind(l), l, dtype)
    return out
