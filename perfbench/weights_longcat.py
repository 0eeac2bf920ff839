"""Seeded weights for LongCat-Flash as one chip holds it, made on the device.

As `weights.py` for GPT-2: the benchmark makes the weights, and the system
under test and the plain reference are each handed what this module makes
from `--seed`. One layer's leaves depend only on (seed, layer index), each
leaf on its own fold of that key, so the reference remakes a layer at a
time and neither side ever holds a draw larger than its largest leaf (the
held experts' 201 M values).

The tree uses the names the program's `LongcatLM` uses: `embedding`,
`lm_head`, `norm/scale`, `layer_<i>/{norm_a0,norm_f0,norm_a1,norm_f1}/scale`,
`layer_<i>/{attn_0,attn_1}/{q_a,q_a_norm/scale,q_b,kv_a,kv_a_norm/scale,kv_b,
o}`,
`layer_<i>/{ffn_0,ffn_1}/{gate,up,down}`,
`layer_<i>/moe/{router,bias,gate,up,down}`. `tree_shapes` is checked
against the program's own abstract parameters before anything is timed.

Every leaf is normal with std 0.02 (`assumed.initializer_range`), except
norm scales (1 + that) and the router's score-correction bias `b`, std
`assumed.router_bias_std`: against scores of about 1/768 it changes some
of the twelve picks, so a program that drops it, or weights by `p + b`,
fails the comparison.
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
    """Sizes of one LongCat-Flash configuration as it is run."""
    layers: int
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ffn: int
    expert_ffn: int
    experts_published: int      # the router's real outputs
    zero_experts: int           # identity outputs after them
    top_k: int
    route_scale: float
    rope_theta: float
    eps: float
    held: Tuple[int, int]       # (first, count) of the experts held here
    vocab: int                  # rows of the slice held (ids the traffic uses)
    std: float
    bias_std: float

    @property
    def vocab_real(self) -> int:
        return self.vocab

    @property
    def router_outputs(self) -> int:
        return self.experts_published + self.zero_experts

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        a = cfg["assumed"]
        held = (int(a["held_first_expert"]), int(cfg["n_routed_experts"]))
        return cls(
            layers=int(cfg["num_layers"]), hidden=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
            nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), v_dim=int(cfg["v_head_dim"]),
            ffn=int(cfg["ffn_hidden_size"]),
            expert_ffn=int(cfg["expert_ffn_hidden_size"]),
            experts_published=int(a["n_routed_experts_published"]),
            zero_experts=int(cfg["zero_expert_num"]),
            top_k=int(cfg["moe_topk"]),
            route_scale=float(cfg["routed_scaling_factor"]),
            rope_theta=float(cfg["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]), held=held,
            vocab=int(cfg["vocab_size"]),
            std=float(a["initializer_range"]),
            bias_std=float(a["router_bias_std"]))

    def param_count(self) -> int:
        def count(layout):
            return sum(math.prod(shape) for _, shape, _ in layout)
        return count(_top_layout(self)) + self.layers * count(
            _layer_layout(self))


def _attn_layout(name, d: Dims):
    return [((name, "q_a"), (d.hidden, d.q_rank), "w"),
            ((name, "q_a_norm", "scale"), (d.q_rank,), "scale"),
            ((name, "q_b"), (d.q_rank, d.heads, d.nope + d.rope), "w"),
            ((name, "kv_a"), (d.hidden, d.kv_rank + d.rope), "w"),
            ((name, "kv_a_norm", "scale"), (d.kv_rank,), "scale"),
            ((name, "kv_b"), (d.kv_rank, d.heads, d.nope + d.v_dim), "w"),
            ((name, "o"), (d.heads, d.v_dim, d.hidden), "w")]


def _ffn_layout(name, d: Dims):
    return [((name, "gate"), (d.hidden, d.ffn), "w"),
            ((name, "up"), (d.hidden, d.ffn), "w"),
            ((name, "down"), (d.ffn, d.hidden), "w")]


def _layer_layout(d: Dims):
    """[(path, shape, kind)] of one layer's leaves; a leaf's place in the
    list is its fold of the layer's key."""
    count = d.held[1]
    out = [((n, "scale"), (d.hidden,), "scale")
           for n in ("norm_a0", "norm_f0", "norm_a1", "norm_f1")]
    out += _attn_layout("attn_0", d) + _attn_layout("attn_1", d)
    out += _ffn_layout("ffn_0", d) + _ffn_layout("ffn_1", d)
    out += [(("moe", "router"), (d.hidden, d.router_outputs), "w"),
            (("moe", "bias"), (d.router_outputs,), "bias"),
            (("moe", "gate"), (count, d.hidden, d.expert_ffn), "w"),
            (("moe", "up"), (count, d.hidden, d.expert_ffn), "w"),
            (("moe", "down"), (count, d.expert_ffn, d.hidden), "w")]
    return out


def _top_layout(d: Dims):
    return [(("embedding",), (d.vocab, d.hidden), "w"),
            (("lm_head",), (d.hidden, d.vocab), "w"),
            (("norm", "scale"), (d.hidden,), "scale")]


def _make(key, layout, d: Dims, dtype):
    tree = {}
    for i, (path, shape, kind) in enumerate(layout):
        std = d.bias_std if kind == "bias" else d.std
        leaf = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)
        if kind == "scale":
            leaf = leaf + 1.0
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf.astype(dtype)
    return tree


def layer_params(key, d: Dims, layer, dtype):
    """The leaves of layer `layer` (a traced or plain integer)."""
    return _make(jax.random.fold_in(key, 1000 + layer), _layer_layout(d), d,
                 dtype)


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
