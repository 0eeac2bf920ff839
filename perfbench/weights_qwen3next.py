"""Seeded weights for Qwen3-Next as one chip holds it, made on the device.

As `weights_granite4hs.py`: the benchmark makes the weights, and the system
under test and the plain reference are each handed what this module makes
from `--seed`. One layer's leaves depend only on (seed, layer index), each
leaf on its own fold of that key, so the reference remakes a layer at a
time; the largest draw is a layer's held experts, three leaves of 134 M
values.

The tree uses the names the program's `Qwen3NextLM` uses: `embedding`,
`lm_head` (both [vocab, hidden]), `final_layernorm/scale`, and a layer
`layer_<i>/` of `{input_layernorm,post_attention_layernorm}/scale`,
`moe/{router,gate,up,down,shared_gate}`, `moe/shared/{gate,up,down}` and,
by its kind, `delta/{in_proj_qkvz,in_proj_ba,conv_w,dt_bias,A_log,norm,
out_proj}` or `attn/{Wqkv,out_proj}` with `attn/{q_norm,k_norm}/scale`.
`tree_shapes` is checked against the program's own abstract parameters
before anything is timed.

What is drawn (`assumed.weights` in the configuration's file): the model
has no multiplier, so every matrix is N(0, 0.02) (`initializer_range`):
projections of a normed stream are of order 1 (0.02 x sqrt(2048) = 0.9),
attention scores of normed heads of 256 are of order 1 under 256^-0.5, the
router's 512 logits spread by 0.9. Norm scales, which here ARE the
multiplier (the published code holds w and multiplies by 1 + w), are 1 +
N(0, 0.02), the delta rule's gated norm among them. The delta rule's own,
as the two Mamba-2 configurations draw theirs (without them the recurrence
is not tested): `conv_w` N(0, 0.3) (a depthwise Conv1d's default, uniform
in +-1/2, has std 0.29; at 0.02 v vanishes), `A_log` the log of a value
uniform in [1, 16] a value head, `dt_bias` the inverse softplus of a step
log-uniform in [`dt_min`, `dt_max`]: a head forgets over 0.6 to 1 000
positions. (The published code draws A in [0, 16] and sets `dt_bias` to
ones.)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key, tree_shapes  # noqa: F401 (re-exported)
# the draws are Falcon-H1's file's, name for name: a `("w", columns)` leaf,
# "scale", "conv", "A_log", "dt_bias"
from perfbench.weights_falconh1 import _draw, _make


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of one Qwen3-Next configuration as it is run."""
    layers: int
    interval: int               # every interval-th layer attends in full
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_factor: float
    rope_theta: float
    key_heads: int              # the delta rule's
    value_heads: int
    key_head_dim: int
    value_head_dim: int
    d_conv: int
    expert_ffn: int
    shared_ffn: int
    experts_published: int      # the router's outputs
    top_k: int
    held: Tuple[int, int]       # (first, count) of the experts held here
    vocab: int                  # rows of the slice held (ids the traffic uses)
    eps: float
    std: float
    conv_std: float
    dt_min: float
    dt_max: float

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple("attention" if (l + 1) % self.interval == 0 else "delta"
                     for l in range(self.layers))

    @property
    def delta_layers(self) -> int:
        return sum(k == "delta" for k in self.layer_types)

    @property
    def vocab_real(self) -> int:
        return self.vocab

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.key_heads * self.key_head_dim

    @property
    def value_dim(self) -> int:
        return self.value_heads * self.value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        a = cfg["assumed"]
        if (cfg["hidden_act"], cfg["tie_word_embeddings"],
                cfg["norm_topk_prob"], cfg["decoder_sparse_step"],
                cfg["mlp_only_layers"], cfg["use_sliding_window"],
                cfg.get("rope_scaling")) != (
                "silu", False, True, 1, [], False, None):
            raise ValueError("an activation, a head, a gate, a dense layer, "
                             "a window or a scaled RoPE that perfbench/"
                             "reference/qwen3_next.py does not write down")
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            interval=int(cfg["full_attention_interval"]),
            hidden=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            rotary_factor=float(cfg["partial_rotary_factor"]),
            rope_theta=float(cfg["rope_theta"]),
            key_heads=int(cfg["linear_num_key_heads"]),
            value_heads=int(cfg["linear_num_value_heads"]),
            key_head_dim=int(cfg["linear_key_head_dim"]),
            value_head_dim=int(cfg["linear_value_head_dim"]),
            d_conv=int(cfg["linear_conv_kernel_dim"]),
            expert_ffn=int(cfg["moe_intermediate_size"]),
            shared_ffn=int(cfg["shared_expert_intermediate_size"]),
            experts_published=int(a["num_experts_published"]),
            top_k=int(cfg["num_experts_per_tok"]),
            held=(int(a["held_first_expert"]), int(cfg["num_experts"])),
            vocab=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
            std=float(a["initializer_range"]),
            conv_std=float(a["conv_std"]), dt_min=float(a["dt_min"]),
            dt_max=float(a["dt_max"]))

    def param_count(self) -> int:
        def count(layout):
            return sum(math.prod(shape) for _, shape, _ in layout)
        return (sum(count(_layer_layout(self, k)) for k in self.layer_types)
                + 2 * self.vocab * self.hidden + self.hidden)

    def slot_state_bytes(self) -> int:
        """What one slot holds beside its pages: a float32 state and a
        bfloat16 conv tail in each delta-rule layer."""
        state = self.value_heads * self.key_head_dim * self.value_head_dim * 4
        return self.delta_layers * (state
                                    + (self.d_conv - 1) * self.conv_dim * 2)

    def position_bytes(self) -> int:
        """What one cached position costs on ONE attention layer."""
        return self.kv_heads * 2 * self.head_dim * 2


def _plain(columns: int):
    return ("w", ((columns, 1.0),))


def _ffn_layout(path, d: Dims, width: int, lead=()):
    return [(path + ("gate",), lead + (d.hidden, width), _plain(width)),
            (path + ("up",), lead + (d.hidden, width), _plain(width)),
            (path + ("down",), lead + (width, d.hidden), _plain(d.hidden))]


def _layer_layout(d: Dims, kind: str):
    """[(path, shape, how it is drawn)] of one layer's leaves; a leaf's
    place in the list is its fold of the layer's key."""
    E, Hv = d.hidden, d.value_heads
    H, KV, D = d.heads, d.kv_heads, d.head_dim
    out = [(("input_layernorm", "scale"), (E,), "scale"),
           (("post_attention_layernorm", "scale"), (E,), "scale")]
    if kind == "delta":
        out += [
            (("delta", "in_proj_qkvz"), (E, d.conv_dim + d.value_dim),
             _plain(d.conv_dim + d.value_dim)),
            (("delta", "in_proj_ba"), (E, 2 * Hv), _plain(2 * Hv)),
            (("delta", "conv_w"), (d.d_conv, d.conv_dim), "conv"),
            (("delta", "dt_bias"), (Hv,), "dt_bias"),
            (("delta", "A_log"), (Hv,), "A_log"),
            (("delta", "norm"), (d.value_head_dim,), "scale"),
            (("delta", "out_proj"), (d.value_dim, E), _plain(E))]
    else:
        out += [
            (("attn", "Wqkv"), (E, (2 * H + 2 * KV) * D),
             _plain((2 * H + 2 * KV) * D)),
            (("attn", "q_norm", "scale"), (D,), "scale"),
            (("attn", "k_norm", "scale"), (D,), "scale"),
            (("attn", "out_proj"), (H * D, E), _plain(E))]
    count = d.held[1]
    return out + [
        (("moe", "router"), (E, d.experts_published),
         _plain(d.experts_published)),
        (("moe", "shared_gate"), (E,), _plain(E)),
    ] + _ffn_layout(("moe",), d, d.expert_ffn, (count,)) \
        + _ffn_layout(("moe", "shared"), d, d.shared_ffn)


def layer_params(key, d: Dims, layer, dtype, kind=None):
    """The leaves of layer `layer`. A delta-rule layer has another tree
    than an attention layer: `kind` says which to make where `layer` is
    traced (None: read off a plain integer)."""
    if kind is None:
        kind = d.layer_types[layer]
    return _make(jax.random.fold_in(key, 1000 + layer),
                 _layer_layout(d, kind), d, dtype)


def top_params(key, d: Dims, dtype):
    """The token table, the untied head and the final norm."""
    def rows(fold):
        return (d.std * jax.random.normal(jax.random.fold_in(key, fold),
                                          (d.vocab, d.hidden), jnp.float32)
                ).astype(dtype)
    scale = _draw(jax.random.fold_in(key, 3), (d.hidden,), "scale", d)
    return {"embedding": rows(1), "lm_head": rows(2),
            "final_layernorm": {"scale": scale.astype(dtype)}}


def make_params(key, d: Dims, dtype):
    """The whole tree as the program holds it; call under one `jax.jit` so
    it is made on the device in one program."""
    out = top_params(key, d, dtype)
    for l in range(d.layers):
        out[f"layer_{l}"] = layer_params(key, d, l, dtype)
    return out
