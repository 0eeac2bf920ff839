"""Seeded weights for Falcon-H1, made on the device.

As `weights.py` for GPT-2: the benchmark makes the weights, and the system
under test and the plain reference are each handed what this module makes
from `--seed`. One layer's leaves depend only on (seed, layer index), each
leaf on its own fold of that key, so the reference remakes a layer at a
time. The token table and the head (1 337 M values each) are drawn
`ROW_BLOCKS` rows at a time, a block from its own fold, so neither side
ever holds a float32 draw larger than one FFN matrix, and the reference
remakes the head a block of the vocabulary at a time (`head_rows`).

The tree uses the names the program's `FalconH1LM` uses: `embedding`,
`lm_head` (both [vocab, hidden]), `final_layernorm/scale`, and a layer
`layer_<i>/` of `{input_layernorm,pre_ff_layernorm}/scale`,
`mlp/{gate_up,down}`, `mamba/{in_proj,conv_w,conv_b,dt_bias,A_log,D,norm,
out_proj}` and `attn/{Wqkv,out_proj}`. `tree_shapes` is checked against the
program's own abstract parameters before anything is timed.

What is drawn (`assumed.weights` in the configuration's file). The
published multipliers are muP's: they stand before weights that are NOT
of the usual small scale, and N(0, 0.02) under them gives attention scores
of 0.02 (a uniform softmax, which no wrong key could move), logits of 0.01
and an MLP that adds a twentieth of what the mixers add. So a matrix is
drawn N(0, 0.02 / m), m the product of the multipliers that scale ITS
product (by column, where the columns differ: `in_proj`'s five segments,
`Wqkv`'s keys, `gate_up`'s gate half), which makes the effective model the
N(0, 0.02) model the other cells serve — scores, logits and every
sublayer's share of order 1 — and every multiplier load-bearing: a program
that drops one is off by 1 / m. Norm scales are 1 + N(0, 0.02). The
state-space mixer's own (arXiv:2405.21060; without them the recurrence is
not tested): `conv_w` N(0, 0.3) (a depthwise Conv1d's default, uniform in
+-1/2, has std 0.29; at 0.02 the conv's output is 0.06, x, B and C vanish
and y is D x), `conv_b` N(0, 0.02), `A_log` the log of a value uniform in
[1, 16] a head, `D` = 1, `dt_bias` the inverse softplus of a step
log-uniform in [`dt_min`, `dt_max`]: a head forgets over 0.6 to 1 000
positions.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key, tree_shapes  # noqa: F401 (re-exported)

#: rows of the token table or the head drawn at once
ROW_BLOCKS = 16320


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of one Falcon-H1 configuration as it is run."""
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    eps: float
    rope_theta: float
    d_ssm: int
    ssm_heads: int
    d_state: int
    groups: int
    d_conv: int
    chunk: int
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: Tuple[float, ...]
    mlp_multipliers: Tuple[float, float]
    std: float
    conv_std: float
    dt_min: float
    dt_max: float

    @property
    def vocab_real(self) -> int:
        return self.vocab

    @property
    def ssm_head_dim(self) -> int:
        return self.d_ssm // self.ssm_heads

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.groups * self.d_state

    @property
    def in_proj_segments(self) -> Tuple[int, ...]:
        """Columns of [z | x | B | C | dt]."""
        gn = self.groups * self.d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads)

    @property
    def row_blocks(self) -> int:
        """Rows of the table or head a draw takes: `ROW_BLOCKS`, or the
        whole of a vocabulary it does not divide (the tests' sizes)."""
        return ROW_BLOCKS if self.vocab % ROW_BLOCKS == 0 else self.vocab

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        a = cfg["assumed"]
        if (cfg.get("attn_layer_indices") is not None
                or cfg.get("rope_scaling") is not None
                or cfg.get("mamba_norm_before_gate")
                or not cfg.get("mamba_rms_norm", True)
                or cfg.get("tie_word_embeddings")):
            raise ValueError(
                "written for attention in every layer, plain RoPE, the "
                "gate before the grouped norm and an untied head")
        if int(cfg["mamba_d_ssm"]) != (int(cfg["mamba_n_heads"])
                                       * int(cfg["mamba_d_head"])):
            raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            hidden=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            ffn=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
            eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            d_ssm=int(cfg["mamba_d_ssm"]),
            ssm_heads=int(cfg["mamba_n_heads"]),
            d_state=int(cfg["mamba_d_state"]),
            groups=int(cfg["mamba_n_groups"]),
            d_conv=int(cfg["mamba_d_conv"]),
            chunk=int(cfg["mamba_chunk_size"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            lm_head_multiplier=float(cfg["lm_head_multiplier"]),
            attention_in_multiplier=float(cfg["attention_in_multiplier"]),
            attention_out_multiplier=float(cfg["attention_out_multiplier"]),
            key_multiplier=float(cfg["key_multiplier"]),
            ssm_in_multiplier=float(cfg["ssm_in_multiplier"]),
            ssm_out_multiplier=float(cfg["ssm_out_multiplier"]),
            ssm_multipliers=tuple(float(m) for m in cfg["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m) for m in cfg["mlp_multipliers"]),
            std=float(a["initializer_range"]),
            conv_std=float(a["conv_std"]), dt_min=float(a["dt_min"]),
            dt_max=float(a["dt_max"]))

    def param_count(self) -> int:
        def count(layout):
            return sum(math.prod(shape) for _, shape, _ in layout)
        return (count(_layer_layout(self)) * self.layers
                + 2 * self.vocab * self.hidden + self.hidden)


def _layer_layout(d: Dims):
    """[(path, shape, how it is drawn)] of one layer's leaves; a leaf's
    place in the list is its fold of the layer's key. A `("w", ...)` draw
    names the multipliers, a (columns, multiplier) pair each, that scale
    the leaf's product."""
    E, F, Dm, Hm = d.hidden, d.ffn, d.d_ssm, d.ssm_heads
    H, KV, D = d.heads, d.kv_heads, d.head_dim
    a_in = d.attention_in_multiplier
    return [
        (("input_layernorm", "scale"), (E,), "scale"),
        (("pre_ff_layernorm", "scale"), (E,), "scale"),
        (("mlp", "gate_up"), (E, 2 * F),
         ("w", ((F, d.mlp_multipliers[0]), (F, 1.0)))),
        (("mlp", "down"), (F, E), ("w", ((E, d.mlp_multipliers[1]),))),
        (("mamba", "in_proj"), (E, sum(d.in_proj_segments)),
         ("w", tuple((n, d.ssm_in_multiplier * m) for n, m in zip(
             d.in_proj_segments, d.ssm_multipliers)))),
        (("mamba", "conv_w"), (d.d_conv, d.conv_dim), "conv"),
        (("mamba", "conv_b"), (d.conv_dim,), ("w", ((d.conv_dim, 1.0),))),
        (("mamba", "dt_bias"), (Hm,), "dt_bias"),
        (("mamba", "A_log"), (Hm,), "A_log"),
        (("mamba", "D"), (Hm,), "ones"),
        (("mamba", "norm"), (Dm,), "scale"),
        (("mamba", "out_proj"), (Dm, E), ("w", ((E, d.ssm_out_multiplier),))),
        (("attn", "Wqkv"), (E, (H + 2 * KV) * D),
         ("w", ((H * D, a_in), (KV * D, a_in * d.key_multiplier),
                (KV * D, a_in)))),
        (("attn", "out_proj"), (H * D, E),
         ("w", ((E, d.attention_out_multiplier),))),
    ]


def _draw(key, shape, how, d: Dims):
    f32 = jnp.float32
    if how == "ones":
        return jnp.ones(shape, f32)
    if how == "A_log":
        return jnp.log(1.0 + 15.0 * jax.random.uniform(key, shape, f32))
    if how == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, f32)
                       * (math.log(d.dt_max) - math.log(d.dt_min))
                       + math.log(d.dt_min))
        return step + jnp.log(-jnp.expm1(-step))      # softplus^-1(step)
    if how == "conv":
        return d.conv_std * jax.random.normal(key, shape, f32)
    if how == "scale":
        return 1.0 + d.std * jax.random.normal(key, shape, f32)
    _, columns = how
    std = jnp.concatenate([jnp.full((n,), d.std / m, f32)
                           for n, m in columns])
    return jax.random.normal(key, shape, f32) * std


def _make(key, layout, d: Dims, dtype):
    tree = {}
    for i, (path, shape, how) in enumerate(layout):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = _draw(jax.random.fold_in(key, i), shape, how,
                               d).astype(dtype)
    return tree


def layer_params(key, d: Dims, layer, dtype):
    """The leaves of layer `layer` (a traced or plain integer)."""
    return _make(jax.random.fold_in(key, 1000 + layer), _layer_layout(d), d,
                 dtype)


def _rows(key, d: Dims, dtype, block, multiplier):
    return (jax.random.normal(jax.random.fold_in(key, block),
                              (d.row_blocks, d.hidden), jnp.float32)
            * (d.std / multiplier)).astype(dtype)


def table_rows(key, d: Dims, dtype, block):
    """Rows [block x row_blocks, (block + 1) x row_blocks) of the token
    table."""
    return _rows(jax.random.fold_in(key, 1), d, dtype, block,
                 d.embedding_multiplier)


def head_rows(key, d: Dims, dtype, block):
    """The same rows of the head."""
    return _rows(jax.random.fold_in(key, 2), d, dtype, block,
                 d.lm_head_multiplier)


def final_norm(key, d: Dims, dtype):
    return {"scale": _draw(jax.random.fold_in(key, 3), (d.hidden,), "scale",
                           d).astype(dtype)}


def top_params(key, d: Dims, dtype):
    """The token table, the head and the final norm."""
    blocks = jnp.arange(d.vocab // d.row_blocks)
    whole = lambda rows: jax.lax.map(                          # noqa: E731
        lambda b: rows(key, d, dtype, b), blocks).reshape(d.vocab, d.hidden)
    return {"embedding": whole(table_rows), "lm_head": whole(head_rows),
            "final_layernorm": final_norm(key, d, dtype)}


def make_params(key, d: Dims, dtype):
    """The whole tree as the program holds it; call under one `jax.jit` so
    it is made on the device in one program."""
    out = top_params(key, d, dtype)
    for l in range(d.layers):
        out[f"layer_{l}"] = layer_params(key, d, l, dtype)
    return out
