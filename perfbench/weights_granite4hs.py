"""Seeded weights for Granite-4.0-H as one chip holds it, made on the device.

As `weights_falconh1.py` and `weights_deepseekv2.py`: the benchmark makes
the weights, and the system under test and the plain reference are each
handed what this module makes from `--seed`. One layer's leaves depend
only on (seed, layer index), each leaf on its own fold of that key, so the
reference remakes a layer at a time; the largest draw is the tied table's
205 M values (a layer's held experts are three leaves of 113 M).

The tree uses the names the program's `GraniteHybridLM` uses: `embedding`
([vocab, hidden], also the head), `final_layernorm/scale`, and a layer
`layer_<i>/` of `{input_layernorm,post_attention_layernorm}/scale`,
`moe/{router,gate,up,down}`, `moe/shared/{gate,up,down}` and, by its kind,
`mamba/{in_proj,conv_w,conv_b,dt_bias,A_log,D,norm,out_proj}` or
`attn/{Wqkv,out_proj}`. `tree_shapes` is checked against the program's own
abstract parameters before anything is timed.

What is drawn (`assumed.weights` in the configuration's file). The four
published multipliers are muP's and stand before weights that are NOT of
the usual small scale: N(0, 0.02) under them gives attention scores of
0.15 (a softmax no wrong key could move), logits of 0.08 and branches a
fifth of what they should add. So a leaf is drawn N(0, 0.02 / m), m the
product of the multipliers that scale ITS product, by column where the
columns differ: `out_proj` of either mixer and every expert's `down` 0.02
/ `residual_multiplier`; the key columns of `Wqkv` 0.02 /
(`attention_multiplier` x sqrt(head_dim)): scores of order 1, as the
N(0, 0.02) model under head_dim^-0.5 has them; the final norm's scale,
whose only product is the logits, (1 + N(0, 0.02)) x `logits_scaling`:
logits of 1.3, the N(0, 0.02) model's. The TIED table keeps N(0, 0.02):
its two uses carry opposite multipliers (x 12 on the way in, / 16 on the
way out), and a larger table makes the model repeat its input: the logit
of the token just read is 768 sigma / rms(stream) times the others'
spread (2.6 at 0.02, where ten layers add 5.9 an element to the 0.24 that
`embedding_multiplier` starts the stream from; 37 at 0.32). Every
multiplier is load-bearing: a program that drops one is off by 1 / m
where it acts (tests/test_granite_hybrid.py shows each). Other norm
scales are 1 + N(0, 0.02). The state-space mixer's own, as Falcon-H1's
file (arXiv:2405.21060; without them the recurrence is not tested):
`conv_w` N(0, 0.3), `conv_b` N(0, 0.02), `A_log` the log of a value
uniform in [1, 16] a head, `D` = 1, `dt_bias` the inverse softplus of a
step log-uniform in [`dt_min`, `dt_max`].
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key, tree_shapes  # noqa: F401 (re-exported)
# the draws are Falcon-H1's file's, name for name: a `("w", columns)` leaf by
# its multipliers, "scale", "conv", "A_log", "dt_bias", "ones"
from perfbench.weights_falconh1 import _draw, _make


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of one Granite-4.0-H configuration as it is run."""
    layer_types: Tuple[str, ...]
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_ffn: int
    shared_ffn: int
    experts_published: int      # the router's outputs
    top_k: int
    held: Tuple[int, int]       # (first, count) of the experts held here
    vocab: int                  # rows of the slice held (ids the traffic uses)
    eps: float
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    d_conv: int
    chunk: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    std: float
    conv_std: float
    dt_min: float
    dt_max: float

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def mamba_layers(self) -> int:
        return sum(k == "mamba" for k in self.layer_types)

    @property
    def vocab_real(self) -> int:
        return self.vocab

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.groups * self.d_state

    @property
    def in_proj_segments(self) -> Tuple[int, ...]:
        """Columns of [z | x | B | C | dt]."""
        gn = self.groups * self.d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads)

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        a = cfg["assumed"]
        if (cfg["position_embedding_type"], cfg["normalization_function"],
                cfg["hidden_act"], cfg["tie_word_embeddings"],
                cfg["mamba_conv_bias"], cfg["mamba_proj_bias"],
                cfg["attention_bias"], cfg.get("rope_scaling")) != (
                "nope", "rmsnorm", "silu", True, True, False, False, None):
            raise ValueError("a position term, a norm, an activation, a "
                             "bias or a head that perfbench/reference/"
                             "granite_hybrid.py does not write down")
        types = tuple(cfg["layer_types"])
        if len(types) != int(cfg["num_hidden_layers"]):
            raise ValueError("layer_types names every layer's kind")
        if int(cfg["mamba_expand"]) * int(cfg["hidden_size"]) != (
                int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"])):
            raise ValueError("mamba_expand x hidden_size is mamba_n_heads x "
                             "mamba_d_head")
        return cls(
            layer_types=types, hidden=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(a["head_dim"]),
            expert_ffn=int(cfg["intermediate_size"]),
            shared_ffn=int(cfg["shared_intermediate_size"]),
            experts_published=int(a["num_local_experts_published"]),
            top_k=int(cfg["num_experts_per_tok"]),
            held=(int(a["held_first_expert"]),
                  int(cfg["num_local_experts"])),
            vocab=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
            ssm_heads=int(cfg["mamba_n_heads"]),
            ssm_head_dim=int(cfg["mamba_d_head"]),
            d_state=int(cfg["mamba_d_state"]),
            groups=int(cfg["mamba_n_groups"]),
            d_conv=int(cfg["mamba_d_conv"]),
            chunk=int(cfg["mamba_chunk_size"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            attention_multiplier=float(cfg["attention_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]),
            std=float(a["initializer_range"]),
            conv_std=float(a["conv_std"]), dt_min=float(a["dt_min"]),
            dt_max=float(a["dt_max"]))

    def param_count(self) -> int:
        def count(layout):
            return sum(math.prod(shape) for _, shape, _ in layout)
        return (sum(count(_layer_layout(self, k)) for k in self.layer_types)
                + self.vocab * self.hidden + self.hidden)


def _ffn_layout(path, d: Dims, width: int, lead=()):
    down = ("w", ((d.hidden, d.residual_multiplier),))
    plain = ("w", ((width, 1.0),))
    return [(path + ("gate",), lead + (d.hidden, width), plain),
            (path + ("up",), lead + (d.hidden, width), plain),
            (path + ("down",), lead + (width, d.hidden), down)]


def _layer_layout(d: Dims, kind: str):
    """[(path, shape, how it is drawn)] of one layer's leaves; a leaf's
    place in the list is its fold of the layer's key. A `("w", ...)` draw
    names the multipliers, a (columns, multiplier) pair each, that scale
    the leaf's product."""
    E, Dm, Hm = d.hidden, d.d_ssm, d.ssm_heads
    H, KV, D = d.heads, d.kv_heads, d.head_dim
    out = [(("input_layernorm", "scale"), (E,), "scale"),
           (("post_attention_layernorm", "scale"), (E,), "scale")]
    if kind == "mamba":
        out += [
            (("mamba", "in_proj"), (E, sum(d.in_proj_segments)),
             ("w", ((sum(d.in_proj_segments), 1.0),))),
            (("mamba", "conv_w"), (d.d_conv, d.conv_dim), "conv"),
            (("mamba", "conv_b"), (d.conv_dim,),
             ("w", ((d.conv_dim, 1.0),))),
            (("mamba", "dt_bias"), (Hm,), "dt_bias"),
            (("mamba", "A_log"), (Hm,), "A_log"),
            (("mamba", "D"), (Hm,), "ones"),
            (("mamba", "norm"), (Dm,), "scale"),
            (("mamba", "out_proj"), (Dm, E),
             ("w", ((E, d.residual_multiplier),)))]
    else:
        out += [
            (("attn", "Wqkv"), (E, (H + 2 * KV) * D),
             ("w", ((H * D, 1.0),
                    (KV * D, d.attention_multiplier * math.sqrt(D)),
                    (KV * D, 1.0)))),
            (("attn", "out_proj"), (H * D, E),
             ("w", ((E, d.residual_multiplier),)))]
    count = d.held[1]
    return out + [
        (("moe", "router"), (E, d.experts_published),
         ("w", ((d.experts_published, 1.0),))),
    ] + _ffn_layout(("moe",), d, d.expert_ffn, (count,)) \
        + _ffn_layout(("moe", "shared"), d, d.shared_ffn)


def layer_params(key, d: Dims, layer, dtype, kind=None):
    """The leaves of layer `layer`. A mamba layer has another tree than an
    attention layer: `kind` says which to make where `layer` is traced
    (None: read off a plain integer)."""
    if kind is None:
        kind = d.layer_types[layer]
    return _make(jax.random.fold_in(key, 1000 + layer),
                 _layer_layout(d, kind), d, dtype)


def top_params(key, d: Dims, dtype):
    """The tied token table and the final norm."""
    table = d.std * jax.random.normal(jax.random.fold_in(key, 1),
                                      (d.vocab, d.hidden), jnp.float32)
    scale = d.logits_scaling * _draw(jax.random.fold_in(key, 3),
                                     (d.hidden,), "scale", d)
    return {"embedding": table.astype(dtype),
            "final_layernorm": {"scale": scale.astype(dtype)}}


def make_params(key, d: Dims, dtype):
    """The whole tree as the program holds it; call under one `jax.jit` so
    it is made on the device in one program."""
    out = top_params(key, d, dtype)
    for l in range(d.layers):
        out[f"layer_{l}"] = layer_params(key, d, l, dtype)
    return out
