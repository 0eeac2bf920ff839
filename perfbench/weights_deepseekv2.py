"""Seeded weights for DeepSeek-V2 as one chip holds it, made on the device.

As `weights_longcat.py`: the benchmark makes the weights, and the system
under test and the plain reference are each handed what this module makes
from `--seed`. One layer's leaves depend only on (seed, layer index), each
leaf on its own fold of that key, so the reference remakes a layer at a
time and neither side ever holds a draw larger than its largest leaf (the
held experts' 157 M values a matrix).

The tree uses the names the program's `DeepseekV2LM` uses: `embedding`,
`lm_head`, `norm/scale`, `layer_<i>/{norm_attn,norm_ffn}/scale`,
`layer_<i>/attn/{q_a,q_a_norm/scale,q_b,kv_a,kv_a_norm/scale,kv_b,o}`,
`layer_<i>/ffn/{gate,up,down}` in the leading dense layers, and after them
`layer_<i>/moe/{router,gate,up,down}` with `layer_<i>/moe/shared/{gate,up,
down}`. `tree_shapes` is checked against the program's own abstract
parameters before anything is timed.

Every leaf is normal with std `assumed.initializer_range` (0.02), norm
scales 1 + that. The gate has no bias to draw.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key, tree_shapes  # noqa: F401 (re-exported)


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of one DeepSeek-V2 configuration as it is run."""
    layers: int
    dense_layers: int           # leading layers whose FFN is dense
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ffn: int                    # the dense layers' SwiGLU
    expert_ffn: int
    shared_experts: int         # the shared SwiGLU is this many experts wide
    experts_published: int      # the router's outputs
    n_group: int
    topk_group: int
    top_k: int
    route_scale: float
    rope_theta: float
    rope_factor: float
    rope_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    eps: float
    held: Tuple[int, int]       # (first, count) of the experts held here
    vocab: int                  # rows of the slice held (ids the traffic uses)
    std: float

    @property
    def vocab_real(self) -> int:
        return self.vocab

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        a, y = cfg["assumed"], cfg["rope_scaling"]
        if (cfg["topk_method"], cfg["scoring_func"], cfg["norm_topk_prob"],
                cfg["moe_layer_freq"], y["type"]) != (
                "group_limited_greedy", "softmax", False, 1, "yarn"):
            raise ValueError("a gate, a layer period or a rope scaling that "
                             "perfbench/reference/deepseek_v2.py does not "
                             "write down")
        held = (int(a["held_first_expert"]), int(cfg["n_routed_experts"]))
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            dense_layers=int(cfg["first_k_dense_replace"]),
            hidden=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
            nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), v_dim=int(cfg["v_head_dim"]),
            ffn=int(cfg["intermediate_size"]),
            expert_ffn=int(cfg["moe_intermediate_size"]),
            shared_experts=int(cfg["n_shared_experts"]),
            experts_published=int(a["n_routed_experts_published"]),
            n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]),
            top_k=int(cfg["num_experts_per_tok"]),
            route_scale=float(cfg["routed_scaling_factor"]),
            rope_theta=float(cfg["rope_theta"]),
            rope_factor=float(y["factor"]),
            rope_original=int(y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"]),
            eps=float(cfg["rms_norm_eps"]), held=held,
            vocab=int(cfg["vocab_size"]),
            std=float(a["initializer_range"]))

    def param_count(self) -> int:
        def count(layout):
            return sum(math.prod(shape) for _, shape, _ in layout)
        return count(_top_layout(self)) + sum(
            count(_layer_layout(self, i < self.dense_layers))
            for i in range(self.layers))


def _ffn_layout(path, d: Dims, width: int):
    return [(path + ("gate",), (d.hidden, width), "w"),
            (path + ("up",), (d.hidden, width), "w"),
            (path + ("down",), (width, d.hidden), "w")]


def _layer_layout(d: Dims, dense: bool):
    """[(path, shape, kind)] of one layer's leaves; a leaf's place in the
    list is its fold of the layer's key."""
    a = "attn"
    out = [(("norm_attn", "scale"), (d.hidden,), "scale"),
           (("norm_ffn", "scale"), (d.hidden,), "scale"),
           ((a, "q_a"), (d.hidden, d.q_rank), "w"),
           ((a, "q_a_norm", "scale"), (d.q_rank,), "scale"),
           ((a, "q_b"), (d.q_rank, d.heads, d.nope + d.rope), "w"),
           ((a, "kv_a"), (d.hidden, d.kv_rank + d.rope), "w"),
           ((a, "kv_a_norm", "scale"), (d.kv_rank,), "scale"),
           ((a, "kv_b"), (d.kv_rank, d.heads, d.nope + d.v_dim), "w"),
           ((a, "o"), (d.heads, d.v_dim, d.hidden), "w")]
    if dense:
        return out + _ffn_layout(("ffn",), d, d.ffn)
    count = d.held[1]
    return out + [
        (("moe", "router"), (d.hidden, d.experts_published), "w"),
        (("moe", "gate"), (count, d.hidden, d.expert_ffn), "w"),
        (("moe", "up"), (count, d.hidden, d.expert_ffn), "w"),
        (("moe", "down"), (count, d.expert_ffn, d.hidden), "w"),
    ] + _ffn_layout(("moe", "shared"), d, d.shared_experts * d.expert_ffn)


def _top_layout(d: Dims):
    return [(("embedding",), (d.vocab, d.hidden), "w"),
            (("lm_head",), (d.hidden, d.vocab), "w"),
            (("norm", "scale"), (d.hidden,), "scale")]


def _make(key, layout, d: Dims, dtype):
    tree = {}
    for i, (path, shape, kind) in enumerate(layout):
        leaf = d.std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
        if kind == "scale":
            leaf = leaf + 1.0
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf.astype(dtype)
    return tree


def layer_params(key, d: Dims, layer, dtype, dense=None):
    """The leaves of layer `layer`. The leading dense layers have another
    tree than the expert layers: `dense` says which to make where `layer`
    is traced (None: read off a plain integer)."""
    if dense is None:
        dense = layer < d.dense_layers
    return _make(jax.random.fold_in(key, 1000 + layer),
                 _layer_layout(d, dense), d, dtype)


def top_params(key, d: Dims, dtype):
    """Embedding, untied head and the final norm."""
    return _make(jax.random.fold_in(key, 1), _top_layout(d), d, dtype)


def make_params(key, d: Dims, dtype):
    """The whole tree as the program holds it; call under one `jax.jit` so
    it is made on the device in one program."""
    out = top_params(key, d, dtype)
    for i in range(d.layers):
        out[f"layer_{i}"] = layer_params(key, d, i, dtype)
    return out
