"""The jax/flax surface the data plane is written against, in one place.

The repo targets the one installation `requirements.txt` pins (jax 0.9.0,
flax 0.12.3). What lives here is what callers import instead of spelling
out themselves, plus the one flax patch the models still need:

  shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
            check_vma=None)
      — `jax.shard_map` with None-valued keywords dropped, so callers can
      pass through "not specified" without knowing jax's defaults.
  out_struct(shape, dtype, *like)
      — jax.ShapeDtypeStruct carrying the union of the `like` operands'
      varying-manual-axes, so Pallas kernels type-check under shard_map's
      VMA checker (ring attention launches them inside a manual region).
  axis_size(name)
      — static size of a bound collective axis.
  axis_bound(name)
      — True when `name` is a live collective axis at trace time.

Importing this module applies `_patch_flax_duplicate_logical_names`. The
data-plane modules that build or shard models import it
(models/transformer.py, parallel/sharding.py); the package root does not,
so control-plane processes never load jax.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None):
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def out_struct(shape, dtype, *like):
    """Pallas out_shape carrying the varying-manual-axes of its inputs."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def axis_size(name) -> int:
    """Static size of a bound collective axis."""
    return jax.lax.axis_size(name)


def axis_bound(name: str) -> bool:
    """True when `name` is a live collective axis (tracing inside
    shard_map/pmap over it)."""
    try:
        jax.lax.axis_size(name)
        return True
    except NameError:
        return False


def _patch_flax_duplicate_logical_names() -> None:
    """flax hard-errors when a parameter's logical axis names repeat
    (`flax/linen/spmd.py:_logical_to_mesh_axes` raises "Dimensions (...)
    occur more than once"; still true in 0.12.3). The repo's rule table
    takes the opposite, well-defined stance
    (parallel/sharding.logical_to_spec): a mesh axis shards at most one
    dim, so later duplicates REPLICATE — an ("embed", "embed") square
    kernel (MaskedLM's mlm_dense) shards its first dim and replicates the
    second. Rewrite duplicates to None before flax's checker sees them;
    first occurrence keeps its rule, which is exactly the layout
    logical_to_spec computes for the same names."""
    from flax.linen import spmd as _spmd

    orig = _spmd._logical_to_mesh_axes
    if getattr(orig, "_dedup_wrapped", False):
        return

    def dedup(array_dim_names, rules=None):
        if array_dim_names is not None:
            seen = set()
            fixed = []
            for name in array_dim_names:
                fixed.append(None if name in seen else name)
                if isinstance(name, str):
                    seen.add(name)
            array_dim_names = tuple(fixed)
        return orig(array_dim_names, rules)

    dedup._dedup_wrapped = True
    _spmd._logical_to_mesh_axes = dedup


_patch_flax_duplicate_logical_names()


__all__ = ["shard_map", "out_struct", "axis_size", "axis_bound"]
