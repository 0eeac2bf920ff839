"""Logical-axis sharding rules — how model parameters map onto the mesh.

The reference has no model-sharding story at all (its one strategy is
replicated-params data parallelism via Horovod allreduce, SURVEY.md §2.3);
this module is the TPU-native extension that makes tensor parallelism and
FSDP first-class: models annotate parameters with *logical* axis names
(`"embed"`, `"mlp"`, `"heads"`, ...) via `flax.linen.with_logical_partitioning`,
and a single rule table maps logical names to physical mesh axes. Swapping a
parallelism strategy is then a rule-table edit, not a model edit — the
Megatron sharding recipe (column-parallel in, row-parallel out) expressed as
GSPMD annotations instead of hand-written collectives.

Rule semantics (scaling-book recipe): pick a mesh, annotate shardings, let
XLA insert the collectives.
  "embed"  — the model/hidden dimension; sharded over fsdp so parameter
             storage scales with the fsdp degree (ZeRO-3 style).
  "mlp"    — the FFN intermediate dimension; sharded over tp
             (column-parallel first matmul, row-parallel second — XLA emits
             the ReduceScatter/AllReduce pair Megatron hand-codes).
  "heads"  — attention heads; sharded over tp (one head group per tp rank).
  "kv"     — per-head dim; replicated.
  "vocab"  — embedding/output vocab; sharded over tp.
  "expert" — MoE expert dimension; sharded over ep.
  "layers" — scan-stacked layer dimension (pipeline stages shard it over pp).
"""
from __future__ import annotations

import contextvars
import re
from typing import Any, Optional, Sequence, Tuple

import jax
from flax import linen as nn
from flax.core import meta
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the rule table's duplicate-name stance (logical_to_spec below) needs
# flax's checker patched to match; importing compat applies it
from ..utils import compat as _compat  # noqa: F401

# logical name -> mesh axis (or None = replicate). A name absent from the
# table replicates. Tuple values shard one dim over several mesh axes.
DEFAULT_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", ("dcn", "dp", "fsdp")),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv", None),
    # vocab shards over tp AND fsdp: embedding-table storage scales with
    # both degrees while the embed dim stays replicated — an fsdp-sharded
    # embed on the table forces the batch-sharded backward cotangent to
    # reshard embed-wise (GSPMD involuntary full remat at the first block).
    # Needs vocab divisible by tp*fsdp: model configs pad vocab to a
    # multiple of 128 (Megatron-style), see models/transformer.py.
    ("vocab", ("tp", "fsdp")),
    ("expert", "ep"),
    ("expert_mlp", "tp"),
    ("layers", "pp"),
    ("norm", None),
    # decode KV-cache length axis (models/transformer._constrain_cache):
    # the cache is [batch, kv-heads, L, head_dim] — batch over the data
    # axes, kv-heads over tp (the "heads" rule), L replicated. Keeping the
    # length axis unsharded is what lets the decode kernel's length-aware
    # reads stream a contiguous filled prefix per (batch, head).
    ("cache", None),
)

# ACTIVATION rules (flax nn.with_logical_constraint at residual-stream
# boundaries, models/transformer.py): activations are batch-sharded over the
# data axes with embed REPLICATED — fsdp shards parameter *storage* (the
# "embed" param rule above), never the residual stream, and tp shards only
# the inner heads/mlp dims. Without these constraints GSPMD is free to infer
# a tp-sharded embed for some ops and a replicated embed for their
# neighbors, and resolves the clash with "involuntary full rematerialization"
# (a full allgather+reslice) in the layernorm backward.
ACTIVATION_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", ("dcn", "dp", "fsdp")),
    ("seq", "sp"),
    ("embed", None),
    ("heads", "tp"),
    ("kv", None),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("cache", None),       # decode KV-cache length axis, replicated
    # tp-overlap (ring collective-matmul) boundary layout: INSIDE the
    # overlapped projections (models/transformer.py behind
    # TransformerConfig.tp_overlap) the sequence dim is sharded over tp —
    # the all-gather half of the Megatron collective pair is decomposed
    # into ppermute hops hidden behind the per-shard matmuls
    # (parallel/collectives.allgather_matmul/matmul_reducescatter), and
    # the seq-over-tp shards are what rotates. "seq_tp" names that layout
    # so boundary activations can be pinned with with_logical_constraint
    # instead of a hand-built PartitionSpec.
    ("seq_tp", "tp"),
)


def tp_overlap_activation_spec(rank: int = 3) -> "P":
    """PartitionSpec of an activation at a ring collective-matmul boundary:
    [batch, seq, ...] with batch over the data axes and SEQ over tp (the
    "seq_tp" activation rule as a physical spec, for shard_map
    in/out_specs where logical constraints don't reach)."""
    return P(("dcn", "dp", "fsdp"), "tp", *([None] * (rank - 2)))


def tp_manual_spec(logical_axes: Sequence[Optional[str]],
                   rules=DEFAULT_RULES) -> "P":
    """Physical spec of a parameter INSIDE the tp-overlap manual region:
    dims whose logical rule involves tp stay manual-sharded over it
    (those are the ring's stationary shards — the weights never move);
    every other dim enters replicated. An fsdp-sharded storage dim is
    therefore gathered at region entry — the same per-layer parameter
    gather FSDP pays on the oracle path."""
    table = dict(rules)
    out = []
    for name in logical_axes:
        axis = table.get(name) if name is not None else None
        axis_tuple = axis if isinstance(axis, tuple) else (axis,)
        out.append("tp" if "tp" in axis_tuple else None)
    return P(*out)


# The mesh made ambient by activation_rules_scope. Model code that needs a
# concrete Mesh at trace time (the ring-attention shard_map dispatch in
# models/transformer._attend) reads it via current_mesh() instead of the
# deprecated jax.interpreters.pxla.thread_resources channel.
_ACTIVE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "mpi_operator_tpu_active_mesh", default=None)


def current_mesh() -> Optional[Mesh]:
    """The Mesh of the innermost activation_rules_scope, or None."""
    return _ACTIVE_MESH.get()


def activation_rules_scope(mesh: Mesh):
    """Context under which the model's nn.with_logical_constraint calls
    resolve: the mesh set as the ambient device context + ACTIVATION_RULES
    as the flax logical-axis table. Trainers enter this around jitted-step
    calls; outside it the constraints are no-ops (tests calling
    model.apply directly are unaffected)."""
    import contextlib

    stack = contextlib.ExitStack()
    # the legacy Mesh context (resource env): what flax's
    # with_logical_constraint needs to resolve bare PartitionSpecs
    stack.enter_context(mesh)
    stack.enter_context(nn.logical_axis_rules(ACTIVATION_RULES))
    token = _ACTIVE_MESH.set(mesh)
    stack.callback(_ACTIVE_MESH.reset, token)
    return stack


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules=DEFAULT_RULES) -> P:
    """Map a tuple of logical axis names to a PartitionSpec. A mesh axis may
    shard at most one dimension — when two logical names map to the same
    mesh axis (e.g. an ("embed", "embed") square kernel), later dims
    replicate."""
    table = dict(rules)
    used: set = set()
    out = []
    for name in logical_axes:
        axis = table.get(name) if name is not None else None
        axis_tuple = axis if isinstance(axis, tuple) else (axis,)
        if axis is not None and any(a in used for a in axis_tuple):
            axis = None
        if axis is not None:
            used.update(axis_tuple)
        out.append(axis)
    return P(*out)


def logical_sharding(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                     rules=DEFAULT_RULES) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules))


def _divisible_spec(mesh: Mesh, spec: P, shape) -> P:
    """Replicate any dim whose size doesn't divide evenly over its mapped
    mesh axes — e.g. 4 attention heads on tp=8 (small test configs, odd
    vocab sizes). GSPMD can pad inside jit, but explicit out_shardings for
    init/device_put require exact divisibility, and an uneven layout would
    waste chips anyway."""
    fixed = []
    for d, axes in enumerate(spec):
        if axes is None:
            fixed.append(None)
            continue
        axis_tuple = axes if isinstance(axes, tuple) else (axes,)
        n = 1
        for a in axis_tuple:
            n *= mesh.shape[a]
        fixed.append(axes if shape[d] % n == 0 else None)
    return P(*fixed)


def param_shardings(mesh: Mesh, abstract_variables, rules=DEFAULT_RULES):
    """Pytree of NamedShardings for a variables tree whose leaves are
    `nn.Partitioned` boxes (produced by `jax.eval_shape` over an `init` of a
    model annotated with `nn.with_logical_partitioning`). Unboxed leaves
    (plain arrays — e.g. batch_stats) replicate.
    """
    def to_sharding(leaf):
        if isinstance(leaf, meta.Partitioned):
            spec = logical_to_spec(leaf.names, rules)
            spec = _divisible_spec(mesh, spec, leaf.value.shape)
            return NamedSharding(mesh, spec)
        return NamedSharding(mesh, P())
    return jax.tree.map(to_sharding, abstract_variables,
                        is_leaf=lambda x: isinstance(x, meta.Partitioned))


def unbox(variables):
    """Strip `nn.Partitioned` metadata boxes, leaving plain arrays."""
    return meta.unbox(variables)


# ---------------------------------------------------------------------------
# Regex restore rules — PartitionSpecs keyed by checkpoint tree path
# ---------------------------------------------------------------------------
# The logical-axis rules above govern params the MODEL annotates. A
# resharding restore (train/checkpoint.restore_resharded) works on the
# CHECKPOINT's tree paths instead — e.g. ("params", "blocks_0", "attn",
# "kernel") — because a checkpoint written by someone else's run carries
# no logical axis metadata, only names. Restore rules are (patterns,
# PartitionSpec) pairs: `patterns` is a tuple of regexes matched as a
# contiguous window anywhere along the flattened path (the t5x/flaxformer
# idiom), first hit wins.

def path_match(qs: Sequence[str], ks: Sequence[str]) -> bool:
    """True when the regex window `qs` matches a contiguous run of path
    components `ks` (each pattern is anchored with a trailing ``$``)."""
    qts = tuple(re.compile(x + "$") for x in qs)
    for i in range(len(ks) - len(qts) + 1):
        window = [q.match(k) for q, k in zip(qts, ks[i:])]
        if window and all(window):
            return True
    return False


def spec_for_path(path: Sequence[str], rules, default=None) -> Optional[P]:
    """Resolve a checkpoint tree path against restore rules; `rules` is a
    sequence of ((pattern, ...), PartitionSpec-or-None) pairs. None in
    the spec slot means replicate. Falls through to `default` (usually
    the target state's own sharding, signalled by None)."""
    ks = tuple(str(k) for k in path)
    for qs, spec in rules or ():
        if path_match(tuple(qs), ks):
            return spec if spec is not None else P()
    return default


def sharding_for_path(mesh: Mesh, path: Sequence[str], rules, shape,
                      default: Optional[NamedSharding] = None
                      ) -> Optional[NamedSharding]:
    """NamedSharding for one checkpoint leaf: first matching restore rule
    wins (downgraded to replication on non-divisible dims, same policy as
    param_shardings); no rule hit returns `default`."""
    spec = spec_for_path(path, rules)
    if spec is None:
        return default
    return NamedSharding(mesh, _divisible_spec(mesh, spec, shape))


def shard_init(model: nn.Module, mesh: Mesh, rng, *init_args,
               rules=DEFAULT_RULES, **init_kwargs):
    """Initialize a logically-annotated model with every parameter created
    directly in its sharded layout (no host round-trip, no full-size
    materialization — required for models that don't fit one device).

    Returns (variables, shardings) — both unboxed pytrees.
    """
    def init_fn(rng):
        return model.init(rng, *init_args, **init_kwargs)

    abstract = jax.eval_shape(init_fn, rng)
    shardings = param_shardings(mesh, abstract, rules)

    def unboxed_init(rng):
        return meta.unbox(init_fn(rng))

    # re-shape the sharding tree to match the unboxed variables tree
    flat_sh = jax.tree.leaves(shardings)
    out_tree = jax.tree.structure(meta.unbox(abstract))
    out_shardings = jax.tree.unflatten(out_tree, flat_sh)
    variables = jax.jit(unboxed_init, out_shardings=out_shardings)(rng)
    return variables, out_shardings


__all__ = ["DEFAULT_RULES", "ACTIVATION_RULES", "activation_rules_scope",
           "current_mesh", "logical_to_spec", "logical_sharding",
           "param_shardings", "path_match", "shard_init",
           "sharding_for_path", "spec_for_path", "tp_manual_spec",
           "tp_overlap_activation_spec", "unbox"]
