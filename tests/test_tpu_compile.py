"""Programs of the serving path compiled at their real widths for a TPU
that is described, not attached (the `on-chip-measurement` guide, section
2): what interpret mode cannot show — whether Mosaic takes the kernel,
whether the program fits the chip, and whether the compiler leaves the
latent page pool where it lies. Nothing runs, so nothing here is a time.

One file, the topology described inside a fixture: only the worker that
runs this file loads the TPU's library.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, PAGES, PAGE, MAX_LEN = 64, 4480, 64, 6400


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def quiet_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _pool_copies(text):
    return [ln.strip()[:160] for ln in text.splitlines()
            if re.search(r"= bf16\[(4480,64,640|286720,640)\][^ ]* copy\(",
                         ln)]


def test_latent_decode_kernel_and_its_cache_write_leave_the_pool_in_place(
        one_chip, quiet_cache):
    """LongCat-Flash's widths: 64 heads on rows of 640 (576 padded), 4480
    pages of 64, a table of 100. Mosaic takes the kernel; the scatter of
    the step's rows and the kernel agree on the pool's layout with the
    resident one, so the donated pool is aliased and never copied."""
    from mpi_operator_tpu.ops.attention import (mla_paged_decode_attention,
                                                mla_row_width)
    W = mla_row_width(512, 64)
    assert W == 640
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)

    def step(q, pool, cur, pt, rows, at):
        pool = pool.reshape(PAGES * PAGE, W).at[at].set(
            rows, mode="drop").reshape(PAGES, PAGE, W)
        return pool, mla_paged_decode_attention(q, pool, cur, pt, 512,
                                                192 ** -0.5, interpret=False)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        spec((SLOTS, 64, W), jnp.bfloat16),
        spec((PAGES, PAGE, W), jnp.bfloat16), spec((SLOTS,), jnp.int32),
        spec((SLOTS, MAX_LEN // PAGE), jnp.int32),
        spec((SLOTS, W), jnp.bfloat16), spec((SLOTS,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _pool_copies(text) == []
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= PAGES * PAGE * W * 2
    assert m.temp_size_in_bytes < 64 << 20


def test_a_576_wide_pool_would_be_copied_whole_into_the_kernel(
        one_chip, quiet_cache):
    """Why the row is 640: given rows of 576 the compiler keeps the pool
    pages-minor and copies all of it into the kernel's layout."""
    from mpi_operator_tpu.ops.attention import mla_paged_decode_attention
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)
    compiled = jax.jit(lambda q, pool, cur, pt: mla_paged_decode_attention(
        q, pool, cur, pt, 512, 192 ** -0.5, interpret=False)).lower(
        spec((SLOTS, 64, 576), jnp.bfloat16),
        spec((PAGES, PAGE, 576), jnp.bfloat16), spec((SLOTS,), jnp.int32),
        spec((SLOTS, MAX_LEN // PAGE), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > PAGES * PAGE \
        * 576 * 2


def test_longcat_decode_step_fits_the_chip_beside_its_weights_and_pool(
        one_chip, quiet_cache, monkeypatch):
    """The whole decode step of `longcat-flash-1of32` as the engine runs
    it: 10.35 GB of weights and a 2.94 GB pool resident, eight kernel
    calls, next to no temporaries and no pool-wide copy."""
    from mpi_operator_tpu.models.generate import decode_model
    from perfbench import weights_longcat as wl
    from perfbench.kinds import _serve_longcat
    with open(os.path.join(REPO, "perfbench", "configs",
                           "longcat-flash-1of32.json")) as f:
        dims = wl.Dims.from_config(json.load(f))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dmodel = decode_model(
        _serve_longcat.model_of(dims, jnp.bfloat16, MAX_LEN, True), True,
        slots=True, page_size=PAGE, num_pages=PAGES)
    on_chip = lambda tree: jax.tree.map(                        # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: wl.make_params(jax.random.PRNGKey(0), dims, jnp.bfloat16)))
    z = jnp.zeros((SLOTS, 1), jnp.int32)
    table = jnp.zeros((SLOTS, MAX_LEN // PAGE), jnp.int32)
    cache = on_chip(jax.eval_shape(
        lambda p: dmodel.apply({"params": p}, z, positions=z,
                               with_head=False, mutable=["cache"],
                               pages=table)[1]["cache"], params))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,        # noqa: E731
                                          sharding=one_chip)

    def step(params, cache, tokens, positions, pages):
        h, v = dmodel.apply({"params": params, "cache": cache},
                            tokens[:, None], positions=positions[:, None],
                            with_head=False, mutable=["cache", "counters"],
                            pages=pages)
        logits = dmodel.head_logits(params, h[:, 0])
        return v["cache"], jnp.argmax(logits, -1), sum(
            jax.tree.leaves(v["counters"]))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, i32(SLOTS), i32(SLOTS),
        i32(SLOTS, MAX_LEN // PAGE)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 2 * dims.layers
    assert _pool_copies(text) == []
    assert 13.0e9 < m.argument_size_in_bytes < 13.6e9
    assert m.temp_size_in_bytes < 0.5e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.5e9
