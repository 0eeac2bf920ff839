"""An expert layer that holds a share of the experts — the serving half of
expert parallelism, on one chip.

`MoeMlp` (moe.py) is the trainer's layer: every expert lives where the
layer does (or GSPMD moves tokens over `ep`), and capacity dispatch may
drop. A served model with hundreds of experts is spread over the chips
that share a layer; each is told which experts it holds, routes over ALL
the router's outputs, and computes the part of the result that its own
experts give. This module is that part, with no stand-in for the other
chips or for the exchange between them: what the absent experts would add
is left out.

Routing (`route`), shared by the served models with experts
(`models/longcat.py`: flat, with a bias and identity experts;
`models/deepseek_v2.py`: group-limited, no bias, a shared expert beside;
`models/granite_hybrid.py`: flat, no bias, normalised over its picks, a
shared expert beside; `models/qwen3_next.py`: the same gate over 512
outputs, the shared expert behind a gate of its own). What the weights are normalised OVER is the
model's to state (`over`): "all", the first two models' rule, or "picks"
(Granite's `GraniteMoeHybridTopKGating`): the picks are the `top_k`
largest LOGITS and their weights `scale` times a softmax over those
`top_k` logits alone, so a row's weights sum to `scale`. Under "all":
softmax over every router output in float32; the picks are the top `top_k`
of `p + bias` (the score-correction bias moves the CHOICE only); a pick's
weight is `scale * p`, not renormalised over the picks. With `n_group` > 1
the outputs are `n_group` runs of consecutive experts, a group's score is
the largest `p` in it, the `topk_group` best groups stay and `p` outside
them counts as 0 in the choice (`kept_groups`; device-limited routing: an
expert group is what one chip holds, so a token's picks lie on at most
`topk_group` chips). Ties, among groups and among picks, go to the lower
index, as `lax.top_k` breaks them. Outputs at or past `n_real` are
identity ("zero-compute") experts: `E_i(y) = y`, so their whole
contribution is `y` times the sum of their weights (`identity_weight`).

Two forms of the held experts' part, equal in what they compute and
neither dropping a token (`held_experts`):

  masked   every held expert runs the call's whole row block and the gate
           (zero for rows not routed to it) is applied in the combine. At
           a decode step's rows an expert's three matmuls are bound by
           reading its weights, so this costs what a grouped
           form costs whenever the expert is hit — and its device time is
           a function of SHAPES, not of the routing: a step takes as long
           whatever the router picked.
  grouped  the assignments to held experts are sorted by expert, each
           expert's group padded to whole blocks of `block_rows`, and a
           loop over the blocks that exist gathers a block's rows,
           runs its expert and scatter-adds the weighted result. Static
           shapes, a trip count that follows the routing: for prefill
           chunks, where thousands of tokens give a held expert a small
           share each and the masked form would multiply the FLOPs by the
           experts held.

Which runs is decided by the tokens in the call (`MASKED_MAX_TOKENS`),
something the code can see, not by a flag.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import einsum_f32

#: up to this many tokens a call takes the masked form. Every held expert
#: runs every row, so the form computes held experts x rows products of 6
#: FLOP a weight, whatever the router picked, beside reading each held
#: expert's weights once: under about 240 rows (the chip's FLOP per byte)
#: the weights' bytes are the larger side HOWEVER many experts are held
#: (both grow with them), and the grouped form, which reads a hit expert's
#: weights all the same, saves nothing. Qwen3-Next's [96 rows, 128 held]:
#: 0.81 GB of weights a layer, 0.98 ms at the chip's bandwidth, against
#: 77 GFLOP, 0.39 ms at its peak.
MASKED_MAX_TOKENS = 256


def kept_groups(p, n_group: int, topk_group: int):
    """[T, n_group] bool: the `topk_group` groups of consecutive outputs
    whose largest score in `p` [T, n_out] is highest."""
    T, n_out = p.shape
    best = jnp.max(p.reshape(T, n_group, n_out // n_group), axis=-1)
    _, groups = jax.lax.top_k(best, topk_group)               # [T, g]
    return jnp.any(groups[..., None] == jnp.arange(n_group), axis=1)


def route_in_groups(logits, bias, top_k: int, scale: float, n_group: int,
                    topk_group: int):
    """`route`'s group-limited form, with the [T, n_group] bool of the
    groups each row kept."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    choice = p if bias is None else p + bias.astype(jnp.float32)
    keep = kept_groups(choice, n_group, topk_group)
    choice = jnp.where(jnp.repeat(keep, p.shape[-1] // n_group, axis=-1),
                       choice, 0.0)
    _, idx = jax.lax.top_k(choice, top_k)
    return idx, scale * jnp.take_along_axis(p, idx, axis=-1), keep


def route(logits, bias, top_k: int, scale: float, n_group: int = 1,
          topk_group: int = 1, over: str = "all"):
    """([T, k] picked output ids, [T, k] weights) from [T, n_out] router
    logits (any float type; the softmax runs in float32). `n_group` 1
    picks flat over all outputs; above 1 only inside the `topk_group`
    best groups (`bias` may then be None). `over` "picks": the `top_k`
    largest logits, weighted by a softmax over those alone (flat, no
    bias)."""
    if over == "picks":
        if n_group > 1 or bias is not None:
            raise ValueError("a gate normalised over its picks is flat and "
                             "has no bias")
        top, idx = jax.lax.top_k(logits.astype(jnp.float32), top_k)
        return idx, scale * jax.nn.softmax(top, axis=-1)
    if over != "all":
        raise ValueError(f"over={over!r}: weights are normalised over "
                         f"'all' outputs or over the 'picks'")
    if n_group > 1:
        return route_in_groups(logits, bias, top_k, scale, n_group,
                               topk_group)[:2]
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, idx = jax.lax.top_k(p + bias.astype(jnp.float32), top_k)
    return idx, scale * jnp.take_along_axis(p, idx, axis=-1)


def held_gates(idx, w, first: int, count: int):
    """[T, count] gate of each held expert for each token: the pick's
    weight where output `first + e` was picked, else zero."""
    hit = (idx - first)[..., None] == jnp.arange(count)       # [T, k, count]
    return jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)


def identity_weight(idx, w, n_real: int):
    """[T] summed weight of the picks that fell on identity experts."""
    return jnp.sum(jnp.where(idx >= n_real, w, 0.0), axis=-1)


def pick_counts(idx, first: int, count: int, n_real: int):
    """(picks on held experts, picks on identity experts, the largest
    held expert's load) of one call, int32 scalars."""
    load = jnp.sum(((idx - first)[..., None] == jnp.arange(count))
                   .astype(jnp.int32), axis=(0, 1))           # [count]
    return (jnp.sum(load), jnp.sum((idx >= n_real).astype(jnp.int32)),
            jnp.max(load))


def _swiglu(x, gate, up, down):
    h = jax.nn.silu(x @ gate) * (x @ up)
    return h @ down


def masked_experts(y, gates, gate, up, down):
    """The masked form: y [T, H], gates [T, E] float32, stacked weights
    gate/up [E, H, F], down [E, F, H]. Returns [T, H] float32."""
    g = jnp.einsum("th,ehf->etf", y, gate)
    u = jnp.einsum("th,ehf->etf", y, up)
    o = einsum_f32("etf,efh->eth", jax.nn.silu(g) * u, down)
    return jnp.einsum("eth,te->th", o, gates)


def grouped_experts(y, idx, w, first: int, gate, up, down,
                    block_rows: int = 256):
    """The grouped form over the same operands as `held_experts`."""
    T, H = y.shape
    E = gate.shape[0]
    k = idx.shape[1]
    A = T * k
    e = (idx - first).reshape(A)
    key = jnp.where((e >= 0) & (e < E), e, E)         # not held: sorts last
    order = jnp.argsort(key, stable=True)
    token = order // k
    weight = w.reshape(A)[order]
    sizes = jnp.sum((key[:, None] == jnp.arange(E)).astype(jnp.int32), 0)
    starts = jnp.cumsum(sizes) - sizes                # group starts, sorted
    blocks = -(-sizes // block_rows)
    block_ends = jnp.cumsum(blocks)                   # [E]
    lane = jnp.arange(block_rows)

    def body(b, out):
        ex = jnp.searchsorted(block_ends, b, side="right")
        within = b - (block_ends[ex] - blocks[ex])
        row = starts[ex] + within * block_rows + lane
        ok = row < starts[ex] + sizes[ex]
        row = jnp.minimum(row, A - 1)
        tok = token[row]
        o = _swiglu(y[tok], gate[ex], up[ex], down[ex]).astype(jnp.float32)
        o = o * jnp.where(ok, weight[row], 0.0)[:, None]
        return out.at[tok].add(o)

    return jax.lax.fori_loop(0, block_ends[-1], body,
                             jnp.zeros((T, H), jnp.float32))


def held_experts(y, idx, w, first: int, gate, up, down) -> jax.Array:
    """sum_i w_i E_i(y) over the picks that fell on the held experts
    `first .. first + E - 1`: [T, H] float32, dropless on either form."""
    if y.shape[0] <= MASKED_MAX_TOKENS:
        return masked_experts(y, held_gates(idx, w, first, gate.shape[0]),
                              gate, up, down)
    return grouped_experts(y, idx, w, first, gate, up, down)


def _router_logits(y, router):
    return jnp.einsum("th,hn->tn", y.astype(jnp.float32),
                      router.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _check_held(held, gate):
    if gate.shape[0] != held[1]:
        raise ValueError(f"holds {gate.shape[0]} experts' weights but was "
                         f"told held={held}")


def shortcut_experts(y, router, bias, gate, up, down, *, held: Tuple[int, int],
                     n_real: int, top_k: int, scale: float):
    """LongCat-Flash's whole layer on this chip for y [T, H]: the held
    experts' part plus the identity experts' (computed here in full; in
    the deployment the token's home chip does). Returns ([T, H] in y's
    type, the `pick_counts` of the call)."""
    first, count = held
    _check_held(held, gate)
    with jax.named_scope("moe.route"):
        logits = _router_logits(y, router)
        idx, w = route(logits, bias, top_k, scale)
        counts = pick_counts(idx, first, count, n_real)
    with jax.named_scope("moe.experts"):
        out = held_experts(y, idx, w, first, gate, up, down)
    with jax.named_scope("moe.identity"):
        out = out + identity_weight(idx, w, n_real)[:, None] \
            * y.astype(jnp.float32)
    return out.astype(y.dtype), counts


def group_limited_experts(y, router, gate, up, down, *,
                          held: Tuple[int, int], top_k: int, scale: float,
                          n_group: int, topk_group: int):
    """The routed part of a layer with group-limited routing, no bias and
    no identity experts (DeepSeek-V2; the shared expert that every token
    passes is the model's own and is added there), on this chip for
    y [T, H]: the same two forms of the held experts' part. Returns
    ([T, H] float32, (picks on held experts, the largest held expert's
    load, rows whose kept groups include one that holds a held expert —
    the rows the deployment's dispatch would send to this chip))."""
    first, count = held
    _check_held(held, gate)
    n_out = router.shape[-1]
    with jax.named_scope("moe.route"):
        logits = _router_logits(y, router)
        idx, w, keep = route_in_groups(logits, None, top_k, scale, n_group,
                                       topk_group)
        picks, _, load = pick_counts(idx, first, count, n_out)
        size = n_out // n_group
        mine = (jnp.arange(n_group) >= first // size) \
            & (jnp.arange(n_group) <= (first + count - 1) // size)
        hits = jnp.sum(jnp.any(keep & mine, axis=-1).astype(jnp.int32))
    with jax.named_scope("moe.experts"):
        out = held_experts(y, idx, w, first, gate, up, down)
    return out, (picks, load, hits)


def flat_experts(y, router, gate, up, down, *, held: Tuple[int, int],
                 top_k: int):
    """The routed part of a layer whose gate is flat and normalised over
    its picks (`route(over="picks")`), with no bias and no identity
    experts (Granite-4.0-H, Qwen3-Next: softmax over all, the `top_k`
    largest, divided by their sum, is the same gate; the shared expert
    that every token passes is the model's own and is added there), on
    this chip for y [T, H]: the
    same two forms of the held experts' part.
    Returns ([T, H] float32, (picks on held experts, the largest held
    expert's load))."""
    first, count = held
    _check_held(held, gate)
    with jax.named_scope("moe.route"):
        idx, w = route(_router_logits(y, router), None, top_k, 1.0,
                       over="picks")
        picks, _, load = pick_counts(idx, first, count, router.shape[-1])
    with jax.named_scope("moe.experts"):
        out = held_experts(y, idx, w, first, gate, up, down)
    return out, (picks, load)


__all__ = ["MASKED_MAX_TOKENS", "route", "route_in_groups", "kept_groups", "held_gates",
           "identity_weight", "pick_counts", "masked_experts",
           "grouped_experts", "held_experts", "shortcut_experts",
           "group_limited_experts", "flat_experts"]
