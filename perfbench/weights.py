"""Seeded weights for the GPT-2 family, made on the device.

The benchmark makes the weights, not the program: the system under test
and the plain reference are each handed what this module makes from
`--seed`, so the reference takes nothing the program produced. One layer's
leaves depend only on (seed, layer index), so the reference can remake
them layer by layer and never hold the model twice.

The tree uses the names the program's `CausalLM` uses (flax):
`wte/embedding`, `wpe/embedding`, `backbone/block_<i>/{ln_1,attn,ln_2,mlp}`,
`backbone/ln_f`. `tree_shapes` is checked against the program's own
abstract parameters before anything is timed.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

STD = 0.02  # GPT-2's initializer_range


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of one GPT-2 configuration as it is run."""
    layers: int
    heads: int
    embed: int
    mlp: int
    positions: int
    vocab: int          # rows of the table as held (padded)
    vocab_real: int     # ids the traffic may use

    @property
    def head_dim(self) -> int:
        return self.embed // self.heads

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        embed = int(cfg["n_embd"])
        return cls(layers=int(cfg["n_layer"]), heads=int(cfg["n_head"]),
                   embed=embed, mlp=int(cfg.get("n_inner") or 4 * embed),
                   positions=int(cfg["n_positions"]),
                   vocab=int(cfg["assumed"]["padded_vocab_size"]),
                   vocab_real=int(cfg["vocab_size"]))

    def param_count(self) -> int:
        e, m = self.embed, self.mlp
        per_layer = 4 * e * e + 4 * e + 2 * e * m + m + e + 4 * e
        return (self.vocab + self.positions) * e + self.layers * per_layer \
            + 2 * e


def seed_key(seed: int):
    """A key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _normal(key, shape, dtype):
    return (STD * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _layer_layout(d: Dims):
    """[(path, shape)] of one block's leaves, in the order they are cut
    from the block's one draw."""
    h, hd, e, m = d.heads, d.head_dim, d.embed, d.mlp
    out = [(("ln_1", "scale"), (e,)), (("ln_1", "bias"), (e,))]
    for name in ("query", "key", "value"):
        out += [(("attn", name, "kernel"), (e, h, hd)),
                (("attn", name, "bias"), (h, hd))]
    out += [(("attn", "out", "kernel"), (h, hd, e)),
            (("attn", "out", "bias"), (e,)),
            (("ln_2", "scale"), (e,)), (("ln_2", "bias"), (e,)),
            (("mlp", "fc_in", "kernel"), (e, m)),
            (("mlp", "fc_in", "bias"), (m,)),
            (("mlp", "fc_out", "kernel"), (m, e)),
            (("mlp", "fc_out", "bias"), (e,))]
    return out


def _cut(flat, layout, dtype):
    """Cut one flat draw into a nested dict of leaves; norm scales sit
    around 1, everything else around 0."""
    tree, at = {}, 0
    for path, shape in layout:
        n = math.prod(shape)
        leaf = flat[at:at + n].reshape(shape)
        if path[-1] == "scale":
            leaf = leaf + 1.0
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf.astype(dtype)
        at += n
    return tree


def layer_params(key, d: Dims, layer, dtype):
    """The leaves of block `layer` (a traced or plain integer): one draw
    of normals from (seed, layer), cut into the block's leaves. One draw a
    block, not one a leaf, keeps the program that makes a whole model
    small enough to compile in seconds."""
    layout = _layer_layout(d)
    total = sum(math.prod(shape) for _, shape in layout)
    flat = STD * jax.random.normal(jax.random.fold_in(key, 1000 + layer),
                                   (total,), jnp.float32)
    return _cut(flat, layout, dtype)


def embed_params(key, d: Dims, dtype):
    k = jax.random.split(jax.random.fold_in(key, 1), 2)
    return {"wte": {"embedding": _normal(k[0], (d.vocab, d.embed), dtype)},
            "wpe": {"embedding": _normal(k[1], (d.positions, d.embed),
                                         dtype)}}


def final_norm_params(key, d: Dims, dtype):
    flat = STD * jax.random.normal(jax.random.fold_in(key, 2),
                                   (2 * d.embed,), jnp.float32)
    return _cut(flat, [(("scale",), (d.embed,)), (("bias",), (d.embed,))],
                dtype)


def make_stacked(key, d: Dims, dtype):
    """The whole model with the blocks stacked: `blocks` holds each of a
    block's leaves once, with the layer as leading axis. The blocks are
    made by one loop over the layer index, so the program that makes a
    model holds one block's draw, not one for each."""
    out = embed_params(key, d, dtype)
    out["blocks"] = jax.lax.map(lambda i: layer_params(key, d, i, dtype),
                                jnp.arange(d.layers))
    out["ln_f"] = final_norm_params(key, d, dtype)
    return out


def unstack(stacked, layers: int):
    """The program's tree (`backbone/block_<i>/...`) from the stacked one."""
    backbone = {f"block_{i}": jax.tree.map(lambda x, i=i: x[i],
                                           stacked["blocks"])
                for i in range(layers)}
    backbone["ln_f"] = stacked["ln_f"]
    return {"wte": stacked["wte"], "wpe": stacked["wpe"],
            "backbone": backbone}


def make_params(key, d: Dims, dtype):
    """The whole tree as the program holds it; call under one `jax.jit` so
    it is made on the device in one program."""
    return unstack(make_stacked(key, d, dtype), d.layers)


def by_leaf_name(tree, layers: int) -> dict:
    """Regroup per-leaf values of the program's tree as the stacked tree
    has them: {"blocks/attn/key/bias": [layers, ...], "wte/embedding":
    [1, ...], ...}, so that the two sides compare leaf for leaf."""
    import numpy as np
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(v)
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    out = {}
    for name, v in flat.items():
        parts = name.split("/")
        if parts[0] == "backbone" and parts[1].startswith("block_"):
            out.setdefault("blocks/" + "/".join(parts[2:]), {})[
                int(parts[1][len("block_"):])] = v
        elif parts[0] == "backbone":
            out["/".join(parts[1:])] = v[None]
        else:
            out[name] = v[None]
    return {k: (np.stack([v[i] for i in range(layers)])
                if isinstance(v, dict) else v) for k, v in out.items()}


def tree_shapes(tree) -> dict:
    """{path: (shape, dtype)} for comparing two trees' layouts."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
            for p, x in flat}
