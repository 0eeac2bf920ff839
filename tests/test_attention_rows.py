"""`Attention`'s projections as ONE product each over a merged heads·D dim
(PR 44): the activations between them and the attention kernels are
`[B, S, H·D]` rows, the 4-D shape a view. What must not have moved: the
parameter tree (it is `nn.DenseGeneral`'s, leaf for leaf and value for
value, so a checkpoint written before restores), and the numbers (the
same products by the same dtype rule)."""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models.transformer import (Attention,
                                                 TransformerConfig,
                                                 dense_attention,
                                                 kernel_init)


class _DenseGeneralProjections(nn.Module):
    """The four projections as the parent built them."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        for name, heads in (("query", cfg.num_heads), ("key", cfg.kv_heads),
                            ("value", cfg.kv_heads)):
            nn.DenseGeneral(
                axis=-1, dtype=cfg.dtype, features=(heads, cfg.head_dim),
                name=name,
                kernel_init=nn.with_logical_partitioning(
                    kernel_init, ("embed", "heads", "kv")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, ("heads", "kv")))(x)
        a = jnp.zeros(x.shape[:2] + (cfg.num_heads, cfg.head_dim), x.dtype)
        return nn.DenseGeneral(
            features=cfg.embed_dim, axis=(-2, -1), dtype=cfg.dtype,
            name="out",
            kernel_init=nn.with_logical_partitioning(
                kernel_init, ("heads", "kv", "embed")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed",)))(a)


def _by_einsum(params, x, cfg):
    """Causal attention with every projection an explicit einsum over
    the 3-D kernels, by `nn.DenseGeneral`'s dtype rule."""
    dt = cfg.dtype
    p = jax.tree.map(lambda a: a.astype(dt), params)

    def proj(name):
        return (jnp.einsum("bse,ehd->bshd", x.astype(dt), p[name]["kernel"])
                + p[name]["bias"])
    q, k, v = proj("query"), proj("key"), proj("value")
    group = cfg.num_heads // cfg.kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    a = dense_attention(q, k, v, mask=None, causal=True, dtype=dt)
    return (jnp.einsum("bshd,hde->bse", a, p["out"]["kernel"])
            + p["out"]["bias"])


def _leaves(boxed):
    """{path: (shape, dtype, logical axes)} of a boxed parameter tree."""
    flat = jax.tree_util.tree_flatten_with_path(
        boxed, is_leaf=lambda x: isinstance(x, nn.LogicallyPartitioned))[0]
    return {jax.tree_util.keystr(path): (
        leaf.value.shape, leaf.value.dtype, leaf.names) for path, leaf in flat}


@pytest.mark.parametrize("heads,kv_heads,head_dim,dtype,decode", [
    (16, None, 64, jnp.bfloat16, False),     # the training cells' heads
    (16, None, 64, jnp.float32, False),
    (8, 2, 64, jnp.bfloat16, False),         # GQA: key and value narrower
    (8, 2, 64, jnp.float32, False),
    (25, None, 64, jnp.bfloat16, False),     # gpt2-xl: H·D = 1600
    (25, None, 64, jnp.float32, True),       # and as its server applies it
    (16, 4, 64, jnp.bfloat16, True),
], ids=["16x64-bf16", "16x64-f32", "gqa-bf16", "gqa-f32", "25x64-bf16",
        "25x64-f32-decode", "gqa-bf16-decode"])
def test_attention_keeps_dense_generals_tree_and_numbers(
        heads, kv_heads, head_dim, dtype, decode):
    B, S = 2, 8
    cfg = TransformerConfig(
        vocab_size=64, max_len=16, num_layers=1, num_heads=heads,
        num_kv_heads=kv_heads, embed_dim=heads * head_dim, mlp_dim=64,
        causal=True, dtype=dtype, attention="dense", decode=decode)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.embed_dim),
                          jnp.float32)
    boxed = Attention(cfg).init(key, x)
    want = _DenseGeneralProjections(cfg).init(key, x)["params"]
    assert _leaves(boxed["params"]) == _leaves(want)
    assert set(_leaves(want)) == {
        f"['{n}']['{leaf}']" for n in ("query", "key", "value", "out")
        for leaf in ("kernel", "bias")}
    params, want = nn.unbox(boxed["params"]), nn.unbox(want)
    for got, ref in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(got, ref)      # the same draw
    # biases start at zero: give every leaf a value that shows
    noise = jax.tree.map(
        lambda a, k: a + 0.02 * jax.random.normal(k, a.shape),
        params, jax.tree.unflatten(
            jax.tree.structure(params),
            list(jax.random.split(jax.random.PRNGKey(5), 8))))
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)
    ref = _by_einsum(noise, x, cfg)
    if decode:
        # a lockstep prefill of S tokens through the cache is the causal
        # forward pass
        got, _ = Attention(cfg).apply(
            {"params": noise, "cache": jax.tree.map(
                jnp.zeros_like, nn.unbox(boxed["cache"]))}, x,
            mutable=["cache"])
        assert got.dtype == ref.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), **tol)
        return

    def loss(fn):
        def f(p, x):
            y = fn(p, x)
            return jnp.sum(y.astype(jnp.float32) ** 2), y
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))
    (_, got), got_grads = loss(
        lambda p, x: Attention(cfg).apply({"params": p}, x))(noise, x)
    (_, ref), ref_grads = loss(lambda p, x: _by_einsum(p, x, cfg))(noise, x)
    assert got.dtype == ref.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **tol)
    top = max(float(jnp.max(jnp.abs(r))) for r in jax.tree.leaves(ref_grads))
    atol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    for (path, g), r in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        assert g.shape == r.shape and g.dtype == r.dtype == jnp.float32
        if name.endswith("['key']['bias']"):
            # it moves every score of a query alike: rounding alone
            assert max(float(jnp.max(jnp.abs(a))) for a in (g, r)) \
                < atol * top, name
            continue
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(r) / scale, atol=atol,
                                   err_msg=name)


def test_a_checkpoint_of_dense_generals_tree_restores_into_attention(
        tmp_path):
    """Bytes written from the parent's tree load into `Attention` leaf by
    leaf (`flax.serialization` names every leaf by its path)."""
    from flax import serialization
    cfg = TransformerConfig(vocab_size=64, max_len=16, num_layers=1,
                            num_heads=4, embed_dim=256, mlp_dim=64,
                            dtype=jnp.float32, attention="dense")
    x = jnp.ones((1, 4, 256), jnp.float32)
    theirs = nn.unbox(_DenseGeneralProjections(cfg).init(
        jax.random.PRNGKey(1), x))["params"]
    path = tmp_path / "attn.msgpack"
    path.write_bytes(serialization.to_bytes(theirs))
    mine = nn.unbox(Attention(cfg).init(jax.random.PRNGKey(2), x))["params"]
    restored = serialization.from_bytes(mine, path.read_bytes())
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(
        Attention(cfg).apply({"params": restored}, x),
        _by_einsum(theirs, x, dataclasses.replace(cfg)), atol=1e-5)


@pytest.mark.multichip
@pytest.mark.parametrize("dp,tp", [(4, 1), (2, 2)], ids=["dp4", "dp2xtp2"])
def test_flash_on_a_mesh_carries_rows_and_matches_one_device(dp, tp):
    """The resident form (pairs of 64-wide heads) on a mesh: what crosses
    the `shard_map` boundary is `[B, S, H·D]` rows, tp splits the merged
    dim into whole heads, and output and gradients are the single-device
    call's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_operator_tpu.ops.attention import flash_attention, record_traced
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh

    B, S, H, D = 4, 64, 4, 64
    q, k, v = (jax.random.normal(key, (B, S, H, D), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(0), 3))

    def loss(q, k, v):
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        return jnp.sum(out * v), out
    grad = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
    (_, want), want_grads = grad(q, k, v)
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp), devices=jax.devices()[:4])
    sh = NamedSharding(mesh, P(("dcn", "dp", "fsdp"), None, "tp", None))
    sharded = [jax.device_put(x, sh) for x in (q, k, v)]
    with record_traced() as traced:
        jaxpr = str(jax.make_jaxpr(lambda *a: loss(*a)[1])(*sharded))
    assert traced["flash"] == {"resident[heads=2,q=32,k=32]"}
    (boundary,) = [ln for ln in jaxpr.splitlines() if "shard_map[" in ln]
    assert f"f32[{B},{S},{H * D}]" in boundary
    assert f"f32[{B},{S},{H},{D}]" not in boundary
    (_, got), got_grads = jax.jit(grad)(*sharded)
    assert got.shape == (B, S, H, D)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-5)
