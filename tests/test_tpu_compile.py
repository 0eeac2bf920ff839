"""Programs of the serving path compiled at their real widths for a TPU
that is described, not attached (the `on-chip-measurement` guide, section
2): what interpret mode cannot show — whether Mosaic takes the kernel,
whether the program fits the chip, and whether the compiler leaves the
page pools (latent rows, and K and V a head) where they lie. Nothing runs,
so nothing here is a time.

One file, the topology described inside a fixture: only the worker that
runs this file loads the TPU's library.
"""
import json
import math
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


def _copies_of(text, *shapes):
    """Lines of the compiled program that copy an array of one of `shapes`."""
    dims = "|".join(",".join(map(str, sh)) for sh in shapes)
    return [ln.strip()[:160] for ln in text.splitlines()
            if re.search(rf"= \w+\[({dims})\][^ ]* copy\(", ln)]


def _pool_copies(text):
    return _copies_of(text, (PAGES, PAGE, 640), (PAGES * PAGE, 640))


#: what Mosaic lets a kernel take of a v5e's VMEM unless it is told
#: otherwise, and no kernel here tells it otherwise
SCOPED_VMEM = 16 << 20


def _walk_vmem(nblk, ps, KV, D, G, window=None, itemsize=2):
    """(pages a turn, bytes) of the walking paged kernel's VMEM as its
    shapes give them: two slots of `pages` pages; a turn's temporaries,
    counted as if nothing were reused — the heads' column blocks stacked
    (one slot again), float32 scores and probabilities [KV, G, pages *
    ps] with G padded to 8 sublanes, the probabilities once more in the
    pool's type; accumulator and statistics. That Mosaic compiles the
    call under its scoped limit is the proof; this is the number."""
    from mpi_operator_tpu.ops.attention import paged_pages_per_turn
    page = ps * KV * 2 * D * itemsize
    pages = paged_pages_per_turn(nblk, page, ps, window)
    sub = -(-G // 8) * 8
    scores = KV * sub * pages * ps * 4
    acc = KV * sub * (2 * D if D % 128 else D) * 4 + 2 * KV * sub * 128 * 4
    return pages, 3 * pages * page + 2 * scores + scores // 2 + acc


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


def test_a_576_wide_pool_is_refused_by_the_kernels_page_copies(
        one_chip, quiet_cache):
    """Why the row is 640: in HBM a 576-wide bfloat16 row occupies five
    lane tiles whatever its shape says, and Mosaic copies whole tiles:
    it refuses the kernel's page copy out of such a pool. (Before PR 30
    the pool went through a BlockSpec, and the compiler kept a 576-wide
    one pages-minor and copied all of it into the kernel's layout, every
    step.)"""
    from mpi_operator_tpu.ops.attention import mla_paged_decode_attention
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)
    with pytest.raises(Exception, match=r"aligned to tiling \(128\), but "
                                        r"is 576"):
        jax.jit(lambda q, pool, cur, pt: mla_paged_decode_attention(
            q, pool, cur, pt, 512, 192 ** -0.5, interpret=False)).lower(
            spec((SLOTS, 64, 576), jnp.bfloat16),
            spec((PAGES, PAGE, 576), jnp.bfloat16),
            spec((SLOTS,), jnp.int32),
            spec((SLOTS, MAX_LEN // PAGE), jnp.int32)).compile()


def _engine_programs(dmodel, slots, page):
    """The engine's own compiled programs over `dmodel`. Call with
    `jax.default_backend` patched to "tpu": the cache is donated there."""
    from mpi_operator_tpu.serve import EngineConfig
    from mpi_operator_tpu.serve.engine import sample_slots
    from mpi_operator_tpu.serve.programs import build_programs
    return build_programs(dmodel, EngineConfig(slots=slots, page_size=page),
                          None, sample_slots)


def _lower_greedy_step(progs, params, cache, slots, nblk, one_chip):
    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt,           # noqa: E731
                                              sharding=one_chip)
    i32, f32 = arg(jnp.int32, slots), arg(jnp.float32, slots)
    return progs.step.lower(
        params, cache, i32, i32, arg(jnp.bool_, slots), i32,
        arg(jnp.uint32, 2), f32, i32, f32, arg(jnp.int32, slots, nblk),
        "greedy")


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
        page_size=PAGE, num_pages=PAGES)
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
    compiled = _lower_greedy_step(
        _engine_programs(dmodel, SLOTS, PAGE), params, cache, SLOTS,
        MAX_LEN // PAGE, one_chip).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 2 * dims.layers
    assert _pool_copies(text) == []
    assert 13.0e9 < m.argument_size_in_bytes < 13.6e9
    assert m.temp_size_in_bytes < 0.5e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.5e9


# ---------------------------------------------------------------------------
# DeepSeek-V2: the same latent kernel and chunk walk at 128 heads
# ---------------------------------------------------------------------------
DSV2 = dict(slots=64, pages=11008, page=64, max_len=16384, heads=128)


def _latent_vmem(H, pages, chains, ps=64, W=640, rank=512, itemsize=2):
    """Bytes of the latent decode kernel's VMEM as its shapes give them,
    counted as if nothing were reused: two slots of `pages` pages; q and
    u, double-buffered by the grid; of every chain in flight float32
    scores and probabilities [H, pages / chains * ps] and the
    probabilities once more in the pool's type; accumulator and
    statistics. That Mosaic compiles the call under its scoped limit is
    the proof; this is the number."""
    slots = 2 * pages * ps * W * itemsize
    q_u = 2 * H * (W + rank) * itemsize
    scores = chains * H * (pages // chains) * ps * (4 + 4 + itemsize)
    acc = H * rank * 4 + 2 * H * 128 * 4
    return slots + q_u + scores + acc


@pytest.mark.parametrize("forced,traced_as", [
    (None, "pallas_mla_paged[live,pages=16,chains=2]"),
    ((16, 1), "pallas_mla_paged[live,pages=16]"),
    ((8, 2), "pallas_mla_paged[live,pages=8,chains=2]"),
    ((32, 2), "pallas_mla_paged[live,pages=32,chains=2]"),
    ((32, 4), "pallas_mla_paged[live,pages=32,chains=4]")])
def test_latent_decode_kernel_compiles_at_128_heads_over_a_table_of_256(
        one_chip, quiet_cache, monkeypatch, forced, traced_as):
    """DeepSeek-V2's widths: 128 heads on rows of 640, 11 008 pages of 64,
    a table of 256, under which a turn takes 16 pages, and at 128 heads
    takes them in two chains of eight (q [1, 128, 640], float32 scores and
    probabilities [128, 512] a chain, acc [128, 512], two slots of 1.3
    MB: 4.7 MB of the 16 Mosaic gives a kernel); the pool stays where it
    lies. LongCat-Flash's table of 100 keeps 8 pages a turn and its 64
    heads one chain. The forms the microbenchmark forces beside the
    kernel's own (`scripts/mla_decode_microbench.py`: the parent's one
    chain, other turns, four chains) compile too, the widest at 8.4 MB."""
    from mpi_operator_tpu.ops import attention
    from mpi_operator_tpu.ops.attention import (mla_chains,
                                                mla_pages_per_turn,
                                                mla_paged_decode_attention,
                                                record_traced)
    S, NP, ps, H = (DSV2[k] for k in ("slots", "pages", "page", "heads"))
    assert mla_pages_per_turn(DSV2["max_len"] // ps, ps * 640 * 2) == 16
    assert mla_pages_per_turn(MAX_LEN // PAGE, PAGE * 640 * 2) == 8
    assert mla_chains(H, 16) == 2 and mla_chains(64, 8) == 1
    pages, chains = forced or (16, 2)
    if forced:
        monkeypatch.setattr(attention, "_MLA_LONG_TABLE", 1 << 30)
        monkeypatch.setattr(attention, "_MLA_PAGES_VMEM_BUDGET",
                            2 * pages * ps * 640 * 2)
        monkeypatch.setattr(attention, "_MLA_CHAINS", chains)
        monkeypatch.setattr(attention, "_MLA_CHAINS_HEADS", 1)
    assert _latent_vmem(H, pages, chains) < SCOPED_VMEM
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)

    def step(q, pool, cur, pt, rows, at):
        pool = pool.reshape(NP * ps, 640).at[at].set(
            rows, mode="drop").reshape(NP, ps, 640)
        return pool, mla_paged_decode_attention(q, pool, cur, pt, 512,
                                                0.11472, interpret=False)
    with record_traced() as traced:
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            spec((S, H, 640), jnp.bfloat16),
            spec((NP, ps, 640), jnp.bfloat16), spec((S,), jnp.int32),
            spec((S, DSV2["max_len"] // ps), jnp.int32),
            spec((S, 640), jnp.bfloat16), spec((S,), jnp.int32)).compile()
    assert traced["decode"] == {traced_as}
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, (NP, ps, 640), (NP * ps, 640)) == []
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= NP * ps * 640 * 2
    assert m.temp_size_in_bytes < 64 << 20


@pytest.fixture(scope="module")
def deepseek_v2(one_chip):
    """(dims, decode-mode model, abstract params and cache on the chip) of
    `deepseek-v2-1of8` as `serve-deepseekv2-1of8-longdoc` serves it."""
    from mpi_operator_tpu.models.generate import decode_model
    from perfbench import weights_deepseekv2 as wd
    from perfbench.kinds import _serve_deepseekv2
    with open(os.path.join(REPO, "perfbench", "configs",
                           "deepseek-v2-1of8.json")) as f:
        dims = wd.Dims.from_config(json.load(f))
    S, NP, ps, L = (DSV2[k] for k in ("slots", "pages", "page", "max_len"))
    dmodel = decode_model(
        _serve_deepseekv2.model_of(dims, jnp.bfloat16, L, True), True,
        page_size=ps, num_pages=NP)
    on_chip = lambda tree: jax.tree.map(                        # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: wd.make_params(jax.random.PRNGKey(0), dims, jnp.bfloat16)))
    z = jnp.zeros((S, 1), jnp.int32)
    table = jnp.zeros((S, L // ps), jnp.int32)
    cache = on_chip(jax.eval_shape(
        lambda p: dmodel.apply({"params": p}, z, positions=z,
                               with_head=False, mutable=["cache"],
                               pages=table)[1]["cache"], params))
    return dims, dmodel, params, cache


def test_deepseek_v2_decode_step_fits_the_chip_beside_its_weights_and_pool(
        one_chip, quiet_cache, monkeypatch, deepseek_v2):
    """The engine's own `step_paged` over the dense layer and four expert
    layers: 6.29 GB of weights and a 4.51 GB pool resident, five kernel
    calls under `mla.attend`, next to no temporaries, no pool-wide copy."""
    dims, dmodel, params, cache = deepseek_v2
    S, NP, ps, L = (DSV2[k] for k in ("slots", "pages", "page", "max_len"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _lower_greedy_step(
        _engine_programs(dmodel, S, ps), params, cache, S, L // ps,
        one_chip).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    calls = re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert [c.rsplit(".", 1)[0] for c in calls] == ["mla.attend"] * 5
    assert _copies_of(text, (NP, ps, 640), (NP * ps, 640)) == []
    assert m.alias_size_in_bytes >= dims.layers * NP * ps * 640 * 2
    assert 10.7e9 < m.argument_size_in_bytes < 10.9e9
    assert m.temp_size_in_bytes < 0.5e9


def test_deepseek_v2_prefill_bucket_builds_its_queries_in_row_groups(
        one_chip, quiet_cache, monkeypatch, deepseek_v2):
    """The engine's own `prefill_paged` at the cell's one bucket, as wide
    as since PR 48 ([1, 128] tokens, 128 heads, where until then the 64
    rows' absorbed queries were built four rows at a time: they alone
    would have been 1.34 + 2 x 1.07 GB a layer): the program's
    temporaries beside the 10.8 GB the engine holds; no copy of a pool,
    the cache aliased."""
    dims, dmodel, params, cache = deepseek_v2
    S, NP, ps, L = (DSV2[k] for k in ("slots", "pages", "page", "max_len"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt,           # noqa: E731
                                              sharding=one_chip)
    compiled = _engine_programs(dmodel, S, ps).prefill.lower(
        params, cache, arg(jnp.int32, 1), arg(jnp.int32, 1, 128),
        arg(jnp.int32, 1), arg(jnp.int32, 1, L // ps)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((params, cache)))
    assert "tpu_custom_call" not in text
    assert _copies_of(text, (NP, ps, 640), (NP * ps, 640)) == []
    assert m.alias_size_in_bytes >= dims.layers * NP * ps * 640 * 2
    assert m.temp_size_in_bytes < 3.0e9
    assert held + m.temp_size_in_bytes < 15.6e9


# ---------------------------------------------------------------------------
# gpt2-xl: the per-head page pool as rows [pages, page, KV * 2D]
# ---------------------------------------------------------------------------
XL = dict(slots=64, pages=384, page=64, heads=25, head_dim=64, max_len=1024)


def test_per_head_rows_pool_is_written_and_read_where_it_lies(
        one_chip, quiet_cache):
    """One layer's cache write and kernel call at gpt2-xl's widths: rows
    of 25 x (64 + 64) = 3200 columns, 25 whole lane tiles. Mosaic takes
    the walking kernel (4 pages of 410 KB a turn, two slots, all 25
    heads a grid step) under its scoped VMEM limit; the flat row
    scatter, the kernel's page copies and the resident pool agree on one
    row-major layout, so the donated pool is aliased and never copied."""
    from mpi_operator_tpu.ops.attention import (kv_row_width,
                                                paged_decode_attention)
    S, NP, ps, KV, D = (XL[k] for k in ("slots", "pages", "page", "heads",
                                        "head_dim"))
    W = kv_row_width(KV, D)
    assert W == 3200 and W % 128 == 0
    pages, vmem = _walk_vmem(XL["max_len"] // ps, ps, KV, D, 1)
    assert pages == 4 and vmem < SCOPED_VMEM, (
        f"{pages} pages a turn take {vmem} bytes of VMEM, slots and a "
        f"turn's temporaries; Mosaic's scoped limit is {SCOPED_VMEM}")
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)

    def layer(q, pool, cur, pt, rows, at):
        pool = pool.reshape(NP * ps, W).at[at].set(
            rows, mode="drop").reshape(NP, ps, W)
        return pool, paged_decode_attention(q, pool, cur, pt,
                                            interpret=False)
    compiled = jax.jit(layer, donate_argnums=(1,)).lower(
        spec((S, KV, D), jnp.bfloat16), spec((NP, ps, W), jnp.bfloat16),
        spec((S,), jnp.int32), spec((S, XL["max_len"] // ps), jnp.int32),
        spec((S, W), jnp.bfloat16), spec((S,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, (NP, ps, W), (NP * ps, W)) == []
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= NP * ps * W * 2
    assert m.temp_size_in_bytes < 64 << 20


def test_a_pool_of_64_wide_head_rows_would_be_copied_between_three_layouts(
        one_chip, quiet_cache):
    """Why the pool is rows of whole lane tiles: K and V pools
    [384, 25, 64, 64] half fill their tiles, the compiler keeps them
    pages-minor `{0,3,2,1}`, and the page scatter wants `{3,1,2,0}`: four
    pool-wide copies and 200 MB of temporaries for this one layer (six
    and 674 MB with the Mosaic call, which wanted a third layout) — in
    every layer of every step, 87% of a gpt2-xl decode step on the chip
    (ledger, PR 27)."""
    S, NP, ps, KV, D = (XL[k] for k in ("slots", "pages", "page", "heads",
                                        "head_dim"))
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)

    def layer(kp, vp, phys, off, k, v, pt):
        kp = kp.at[phys, :, off, :].set(k)
        vp = vp.at[phys, :, off, :].set(v)
        read = jnp.einsum("bjhkd,bjhkd->bh", kp[pt], vp[pt],
                          preferred_element_type=jnp.float32)
        return kp, vp, read
    pool = spec((NP, KV, ps, D), jnp.bfloat16)
    compiled = jax.jit(layer, donate_argnums=(0, 1)).lower(
        pool, pool, spec((S, 1), jnp.int32), spec((S, 1), jnp.int32),
        spec((S, 1, KV, D), jnp.bfloat16), spec((S, 1, KV, D), jnp.bfloat16),
        spec((S, 2), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(_copies_of(text, (NP, KV, ps, D))) >= 2
    assert compiled.memory_analysis().temp_size_in_bytes > NP * KV * ps * D \
        * 2


@pytest.fixture(scope="module")
def gpt2_xl(one_chip):
    """The decode model, parameter shapes and cache shapes of
    `perfbench/configs/gpt2-xl.json` as the benchmark's engine builds
    them (64 slots, 384 pages of 64), on the described chip."""
    from mpi_operator_tpu.models.generate import decode_model
    from mpi_operator_tpu.models.transformer import (CausalLM,
                                                     TransformerConfig)
    from perfbench import weights
    with open(os.path.join(REPO, "perfbench", "configs", "gpt2-xl.json")) as f:
        dims = weights.Dims.from_config(json.load(f))
    assert (dims.heads, dims.embed // dims.heads) == (XL["heads"],
                                                      XL["head_dim"])
    model = CausalLM(TransformerConfig(
        vocab_size=dims.vocab, max_len=dims.positions,
        num_layers=dims.layers, num_heads=dims.heads, embed_dim=dims.embed,
        mlp_dim=dims.mlp, causal=True, dtype=jnp.bfloat16,
        decode_kernel=True))
    dmodel = decode_model(model, True, page_size=XL["page"],
                          num_pages=XL["pages"])
    on_chip = lambda tree: jax.tree.map(                        # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: weights.make_params(jax.random.PRNGKey(0), dims,
                                    jnp.bfloat16)))
    z = jnp.zeros((XL["slots"], 1), jnp.int32)
    table = jnp.zeros((XL["slots"], XL["max_len"] // XL["page"]), jnp.int32)
    cache = on_chip(jax.eval_shape(
        lambda p: dmodel.apply({"params": p}, z, positions=z,
                               with_head=False, mutable=["cache"],
                               pages=table)[1]["cache"], params))
    return dims, dmodel, params, cache


def _xl_pool_copies(text):
    NP, ps, W = XL["pages"], XL["page"], XL["heads"] * 2 * XL["head_dim"]
    return _copies_of(text, (NP, ps, W), (NP * ps, W))


def test_gpt2_xl_decode_step_keeps_its_pool_in_place(
        one_chip, quiet_cache, monkeypatch, gpt2_xl):
    """The engine's own `step_paged` over gpt2-xl (serve/programs.py:
    the model's decode call, the tied head, `sample_slots`): a Mosaic
    call in each of the 48 layers, no copy of a pool-sized array, the
    7.5 GB of pools aliased, and weights + pools + temporaries fit the
    chip."""
    dims, dmodel, params, cache = gpt2_xl
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _lower_greedy_step(
        _engine_programs(dmodel, XL["slots"], XL["page"]), params, cache,
        XL["slots"], XL["max_len"] // XL["page"], one_chip).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    pools = dims.layers * XL["pages"] * XL["page"] * 3200 * 2
    assert text.count("tpu_custom_call") == dims.layers == 48
    assert _xl_pool_copies(text) == []
    assert m.alias_size_in_bytes >= pools
    assert 10.5e9 < m.argument_size_in_bytes < 10.9e9
    assert m.temp_size_in_bytes < 0.5e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.5e9


def test_gpt2_xl_prefill_bucket_keeps_its_pool_in_place(
        one_chip, quiet_cache, monkeypatch, gpt2_xl):
    """The engine's own `prefill_paged` at one bucket ([1, 128] tokens, a
    call's width since PR 48):
    the chunk's rows go into the pool by the same flat scatter and the
    dense path gathers each row's pages from it — no copy of the pool,
    the pools aliased."""
    dims, dmodel, params, cache = gpt2_xl
    S, C = XL["slots"], 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,        # noqa: E731
                                          sharding=one_chip)
    compiled = _engine_programs(dmodel, S, XL["page"]).prefill.lower(
        params, cache, i32(1), i32(1, C), i32(1),
        i32(1, XL["max_len"] // XL["page"])).compile()
    m = compiled.memory_analysis()
    assert _xl_pool_copies(compiled.as_text()) == []
    assert m.alias_size_in_bytes >= dims.layers * XL["pages"] * XL["page"] \
        * 3200 * 2
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.5e9


# ---------------------------------------------------------------------------
# phi4-mini-flash: a window layer's ring a slot, read through the paged
# kernel's lower bound, and the ONE pool of keys and values
# ---------------------------------------------------------------------------
PHI = dict(slots=64, page=64, window=512, heads=40, pairs=10, pair_dim=128,
           pages=10752, max_len=16384)


def test_a_window_layers_ring_is_written_and_read_where_it_lies(
        one_chip, quiet_cache):
    """One window layer's step at Phi-4-mini-flash's widths: the ring
    [64, 576, 2560] (9 pages of 64 a slot; rows of 10 x 256 columns, 20
    lane tiles) takes the step's rows by one flat scatter and is read as
    [64 * 9, 64, 2560] pages by `paged_decode_attention(window=512)`.
    Mosaic takes the walking kernel with its lower bound (the window's 9
    pages in turns of 3) under its scoped VMEM limit; the reshape is no
    copy, so the donated ring is aliased through the step."""
    from mpi_operator_tpu.ops.attention import (kv_row_width,
                                                paged_decode_attention)
    S, ps, W, H, KV, D = (PHI[k] for k in ("slots", "page", "window", "heads",
                                           "pairs", "pair_dim"))
    nr = W // ps + 1
    R, width = nr * ps, kv_row_width(KV, D)
    assert (R, width) == (576, 2560)
    pages, vmem = _walk_vmem(nr, ps, KV, D, H // KV, window=W)
    assert pages == 3 and vmem < SCOPED_VMEM, (
        f"{pages} pages a turn take {vmem} bytes of VMEM, slots and a "
        f"turn's temporaries; Mosaic's scoped limit is {SCOPED_VMEM}")
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)

    def layer(q, ring, cur, rows):
        at = jnp.arange(S) * R + cur % R
        ring = ring.reshape(S * R, width).at[at].set(
            rows, mode="drop").reshape(S, R, width)
        first = jnp.maximum(cur - W + 1, 0) // ps
        table = (jnp.arange(S)[:, None] * nr
                 + (first[:, None] + jnp.arange(nr)[None]) % nr)
        return ring, paged_decode_attention(
            q, ring.reshape(S * nr, ps, width), cur - first * ps, table,
            interpret=False, window=W, sm_scale=0.125)
    compiled = jax.jit(layer, donate_argnums=(1,)).lower(
        spec((S, H, D), jnp.bfloat16), spec((S, R, width), jnp.bfloat16),
        spec((S,), jnp.int32), spec((S, width), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, (S, R, width), (S * R, width),
                      (S * nr, ps, width)) == []
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= S * R * width * 2
    assert m.temp_size_in_bytes < 64 << 20


def test_the_once_cached_pool_is_read_by_a_table_of_256_pages(
        one_chip, quiet_cache):
    """The eight reads of layer 17's keys and values: 40 query heads over
    10 pairs of 128 against [10752, 64, 2560], a table of 256 pages a
    row: the walking kernel, all ten pairs a grid step, 4 pages of 327
    KB a turn, scores on the K lanes, under Mosaic's scoped VMEM limit."""
    from mpi_operator_tpu.ops.attention import (decode_head_block,
                                                paged_decode_attention)
    S, ps, H, KV, D, NP, L = (PHI[k] for k in (
        "slots", "page", "heads", "pairs", "pair_dim", "pages", "max_len"))
    assert decode_head_block(KV, ps, D, jnp.bfloat16, 4 << 20, True) == KV
    pages, vmem = _walk_vmem(L // ps, ps, KV, D, H // KV)
    assert pages == 4 and vmem < SCOPED_VMEM, (
        f"{pages} pages a turn take {vmem} bytes of VMEM, slots and a "
        f"turn's temporaries; Mosaic's scoped limit is {SCOPED_VMEM}")
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)
    compiled = jax.jit(lambda q, pool, cur, pt: paged_decode_attention(
        q, pool, cur, pt, interpret=False, sm_scale=0.125)).lower(
        spec((S, H, D), jnp.bfloat16), spec((NP, ps, KV * 2 * D),
                                            jnp.bfloat16),
        spec((S,), jnp.int32), spec((S, L // ps), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("H,KV,D,ps,nblk,window", [
    (32, 8, 128, 64, 128, None),     # GQA, heads of 128: llama-like
    (32, 8, 128, 128, 64, 4096),     # the same under a window, pages of 128
    (16, 16, 256, 64, 64, None),     # a head's K two lane tiles
    (12, 6, 64, 64, 32, None),       # GQA on the padded-query form
], ids=["gqa128", "gqa128-window", "mha256", "gqa64"])
def test_walking_kernel_compiles_at_shapes_no_cell_runs(
        one_chip, quiet_cache, H, KV, D, ps, nblk, window):
    from mpi_operator_tpu.ops.attention import paged_decode_attention
    S, NP = 16, 512
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)
    pages, vmem = _walk_vmem(nblk, ps, KV, D, H // KV, window)
    assert vmem < SCOPED_VMEM, (pages, vmem)
    compiled = jax.jit(lambda q, pool, cur, pt: paged_decode_attention(
        q, pool, cur, pt, interpret=False, window=window)).lower(
        spec((S, H, D), jnp.bfloat16),
        spec((NP, ps, KV * 2 * D), jnp.bfloat16),
        spec((S,), jnp.int32), spec((S, nblk), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_walking_kernel_compiles_by_head_blocks(one_chip, quiet_cache):
    """A page too wide for two slots of all its heads (64 kv heads of
    128, pages of 128: 4 MB): the grid takes head blocks, each copying
    its own lane-aligned columns of a page."""
    from mpi_operator_tpu.ops.attention import (paged_decode_attention,
                                                record_traced, traced_name)
    S, NP, H, KV, D, ps, nblk = 8, 64, 64, 64, 128, 128, 16
    pages, vmem = _walk_vmem(nblk, ps, KV // 2, D, H // KV)  # 32 heads a step
    assert pages == 1 and vmem < SCOPED_VMEM, (
        f"{pages} page a turn of 32 heads takes {vmem} bytes of VMEM, slots "
        f"and a turn's temporaries; Mosaic's scoped limit is {SCOPED_VMEM}")
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)
    with record_traced() as traced:
        compiled = jax.jit(lambda q, pool, cur, pt: paged_decode_attention(
            q, pool, cur, pt, interpret=False)).lower(
            spec((S, H, D), jnp.bfloat16),
            spec((NP, ps, KV * 2 * D), jnp.bfloat16),
            spec((S,), jnp.int32), spec((S, nblk), jnp.int32)).compile()
    assert traced_name(traced["decode"]) == "pallas_paged[live,pages=1,hb=32]"
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def phi4_flash(one_chip):
    """The decode model, parameter shapes and cache shapes of
    `perfbench/configs/phi4-mini-flash.json` as the benchmark's engine
    builds them (64 slots, 10752 pages of 64, contexts to 16384)."""
    from mpi_operator_tpu.models.generate import decode_model
    from perfbench import weights_phi4flash as weights
    from perfbench.kinds import _serve_phi4flash
    with open(os.path.join(REPO, "perfbench", "configs",
                           "phi4-mini-flash.json")) as f:
        dims = weights.Dims.from_config(json.load(f))
    model = _serve_phi4flash.model_of(dims, jnp.bfloat16, PHI["max_len"],
                                      True)
    dmodel = decode_model(model, True, page_size=PHI["page"],
                          num_pages=PHI["pages"])
    on_chip = lambda tree: jax.tree.map(                        # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: weights.make_params(jax.random.PRNGKey(0), dims,
                                    jnp.bfloat16)))
    z = jnp.zeros((PHI["slots"], 1), jnp.int32)
    table = jnp.zeros((PHI["slots"], PHI["max_len"] // PHI["page"]),
                      jnp.int32)
    cache = on_chip(jax.eval_shape(
        lambda p: dmodel.apply({"params": p}, z, positions=z,
                               with_head=False, mutable=["cache"],
                               pages=table)[1]["cache"], params))
    return dims, dmodel, params, cache


def test_phi4_flash_decode_step_keeps_its_pool_and_rings_in_place(
        one_chip, quiet_cache, monkeypatch, phi4_flash):
    """The engine's own `step_paged` over Phi-4-mini-flash: sixteen
    Mosaic calls (eight walks of the one pool, eight of a ring), no copy
    of the pool [10752, 64, 2560] or of a ring [64, 576, 2560], both
    aliased through the step, and everything fits the chip."""
    dims, dmodel, params, cache = phi4_flash
    S, ps, NP = PHI["slots"], PHI["page"], PHI["pages"]
    width = PHI["pairs"] * 2 * PHI["pair_dim"]
    R = (PHI["window"] // ps + 1) * ps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _lower_greedy_step(
        _engine_programs(dmodel, S, ps), params, cache, S,
        PHI["max_len"] // ps, one_chip).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 16
    assert _copies_of(text, (NP, ps, width), (NP * ps, width), (S, R, width),
                      (S * R, width), (S * R // ps, ps, width)) == []
    assert m.alias_size_in_bytes >= (NP * ps + 8 * S * R) * width * 2
    assert m.temp_size_in_bytes < 0.5e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.5e9


# ---------------------------------------------------------------------------
# Falcon-H1-34B as 4 of its 72 layers: a pool, a state and a conv tail in
# every layer, 96 slots
# ---------------------------------------------------------------------------
H1 = dict(slots=96, pages=5312, page=64, max_len=4096, ssm_heads=32,
          d_state=256, ssm_head_dim=128, groups=2)


#: Granite-4.0-H-Small's mixer in its cell: heads of 64 channels, under a
#: lane tile, held two to a tile
G4 = dict(slots=48, ssm_heads=128, d_state=128, ssm_head_dim=64, groups=1)


@pytest.mark.parametrize("name,shape,held", [
    ("falcon_h1", H1, (96, 32, 256, 128)),
    ("granite_4_0_h", G4, (48, 64, 128, 128)),
])
def test_ssd_state_update_kernel_updates_the_donated_state_where_it_lies(
        one_chip, quiet_cache, name, shape, held):
    """One layer's state update at Falcon-H1-34B's widths (96 rows of 32
    tiles [256, 128] float32, a head a tile) and at Granite-4.0-H-Small's
    (48 rows of 64 tiles [128, 128], two heads of 64 channels a tile):
    Mosaic takes the kernel (8 tiles a grid step, the transposes that
    turn B and C into columns) under its scoped VMEM limit; a row's
    4 194 304 B of state are the call's operand and its result, aliased,
    no lane of a tile empty, and nothing of that size is copied or made
    beside them."""
    from mpi_operator_tpu.ops.ssm import ssd_state_shape, ssd_state_update
    S, H, N, P, K = (shape["slots"], shape["ssm_heads"], shape["d_state"],
                     shape["ssm_head_dim"], shape["groups"])
    assert ssd_state_shape(S, H, P, K, N) == held
    spec = lambda *shape: jax.ShapeDtypeStruct(             # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: ssd_state_update(*a[:-1], fresh=a[-1], interpret=False),
        donate_argnums=(6,)).lower(
            spec(S, H, P), spec(S, H), spec(H), spec(S, K, N), spec(S, K, N),
            spec(H), spec(*held),
            jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip)
        ).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    state = S * H * N * P * 4
    assert text.count("tpu_custom_call") == 1
    assert _copies_of(text, held) == []
    assert m.alias_size_in_bytes >= state == S * 4194304
    assert m.temp_size_in_bytes < 16 << 20


@pytest.fixture(scope="module")
def falcon_h1(one_chip):
    """The decode model, parameter shapes and cache shapes of
    `perfbench/configs/falcon-h1-34b-4of72.json` as the benchmark's
    engine builds them (96 slots, 5312 pages of 64, contexts to 4096)."""
    from mpi_operator_tpu.models.generate import decode_model
    from perfbench import weights_falconh1 as weights
    from perfbench.kinds import _serve_falconh1
    with open(os.path.join(REPO, "perfbench", "configs",
                           "falcon-h1-34b-4of72.json")) as f:
        dims = weights.Dims.from_config(json.load(f))
    model = _serve_falconh1.model_of(dims, jnp.bfloat16, H1["max_len"], True)
    dmodel = decode_model(model, True, page_size=H1["page"],
                          num_pages=H1["pages"])
    on_chip = lambda tree: jax.tree.map(                        # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: weights.make_params(jax.random.PRNGKey(0), dims,
                                    jnp.bfloat16)))
    z = jnp.zeros((H1["slots"], 1), jnp.int32)
    table = jnp.zeros((H1["slots"], H1["max_len"] // H1["page"]), jnp.int32)
    cache = on_chip(jax.eval_shape(
        lambda p: dmodel.apply({"params": p}, z, positions=z,
                               with_head=False, mutable=["cache"],
                               pages=table)[1]["cache"], params))
    return dims, dmodel, params, cache


def _h1_big_copies(text):
    S, NP, ps = H1["slots"], H1["pages"], H1["page"]
    return _copies_of(
        text, (NP, ps, 1024), (NP * ps, 1024),
        (S, H1["ssm_heads"], H1["d_state"], H1["ssm_head_dim"]))


def test_falcon_h1_decode_step_passes_each_state_and_pool_through_once(
        one_chip, quiet_cache, monkeypatch, falcon_h1):
    """The engine's own `step_paged` over the four layers and the whole
    vocabulary: eight Mosaic calls (a state update and a walk of the
    layer's pool a layer, each under its scope's name), no copy of a
    pool [5312, 64, 1024] or of a state [96, 32, 256, 128], all 4.3 GB
    of them aliased through the step, and 8.79 GB of weights, the cache
    and the temporaries (the [96, 261120] float32 logits among them) fit
    the chip."""
    dims, dmodel, params, cache = falcon_h1
    S, ps, NP = H1["slots"], H1["page"], H1["pages"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _lower_greedy_step(
        _engine_programs(dmodel, S, ps), params, cache, S,
        H1["max_len"] // ps, one_chip).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    calls = re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert sorted(c.rsplit(".", 1)[0] for c in calls) == (
        ["h1attn.attend"] * 4 + ["ssd.update"] * 4)
    assert _h1_big_copies(text) == []
    held = dims.layers * (NP * ps * 1024 * 2 + S * 4194304)
    assert m.alias_size_in_bytes >= held
    assert 13.0e9 < m.argument_size_in_bytes < 13.3e9
    assert m.temp_size_in_bytes < 0.5e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.5e9


def test_falcon_h1_prefill_bucket_fits_beside_what_the_chip_holds(
        one_chip, quiet_cache, monkeypatch, falcon_h1):
    """The engine's own `prefill_paged` at the cell's one bucket
    ([1, 128] tokens through the chunked scan, a call's width since PR
    48; the slot leaves gathered at the row and scattered back):
    no copy of a pool, the cache aliased, and the program's temporaries
    beside the 13.2 GB the engine holds (of which this program is not
    handed the head) stay under the chip's 16 GB."""
    dims, dmodel, params, cache = falcon_h1
    S, ps = H1["slots"], H1["page"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt,           # noqa: E731
                                              sharding=one_chip)
    compiled = _engine_programs(dmodel, S, ps).prefill.lower(
        params, cache, arg(jnp.int32, 1), arg(jnp.int32, 1, 128),
        arg(jnp.int32, 1), arg(jnp.int32, 1, H1["max_len"] // ps),
        arg(jnp.int32, 1)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((params, cache)))
    assert "tpu_custom_call" not in text
    assert _copies_of(text, (H1["pages"], ps, 1024),
                      (H1["pages"] * ps, 1024)) == []
    assert m.alias_size_in_bytes >= held - 8.8e9
    assert m.temp_size_in_bytes < 2.4e9
    assert held + m.temp_size_in_bytes < 15.6e9


@pytest.mark.parametrize("H,D,dtype,form,kernels", [
    # the training cells' shape: pairs of 64-wide heads, resident
    (16, 64, jnp.bfloat16, "resident[heads=2,q=512,k=512]", 2),
    # heads that are a lane tile each
    (8, 128, jnp.bfloat16, "resident[heads=1,q=512,k=512]", 2),
    # float32 operands: 9.4 MB of blocks, the most the resident form takes
    (16, 64, jnp.float32, "resident[heads=2,q=512,k=512]", 2),
    # gpt2-xl's 25 heads: pairs do not divide them, the streamed form
    (25, 64, jnp.bfloat16, "streamed[q=512,k=512]", 3),
])
def test_flash_attention_and_its_gradient_at_the_training_cells_shape(
        H, D, dtype, form, kernels, one_chip, quiet_cache):
    """Attention and its gradient (`jax.vjp`) over [8, 1024, H, D], causal:
    bfloat16 as `LMTrainer`'s step calls it, and float32. Mosaic takes
    the kernels. In the resident form they are TWO (forward; one
    backward for dq, dk and dv), they read q, k, v and write out and
    the gradients as [B, S, H·D] rows: no copy or transpose of an
    operand stands beside them where the operands are BORN that way
    (here: arguments [B, S, H·D], what a 2-D projection writes, seen
    through the 4-D interface), and no row statistic 128 lanes wide
    ([B·H, 1024, 128] float32, four times q) exists in the program. The
    streamed form, which other shapes keep, has its three kernels, its
    transposes and those arrays.

    (A 4-D array [8, 1024, 16, 64] does NOT lie as [B, S, H·D] on the
    chip: XLA tiles its two minor dims, (16, 64), and a projection with
    two feature dims is lowered to a convolution over the heads whose
    output has the sequence minor. Between either and ANY row-major
    kernel operand the compiler puts a relayout copy. The model's
    projections paid eight of those a layer until PR 44 made each ONE
    product over a merged H·D dim:
    `test_the_train_step_keeps_its_attention_activations_as_rows`.)"""
    from mpi_operator_tpu.ops.attention import flash_attention, record_traced
    B, S = 8, 1024
    x = jax.ShapeDtypeStruct((B, S, H * D), dtype, sharding=one_chip)

    def attend(q, k, v, do):
        # the vjp itself: a loss's reduction would bring a copy of its own
        out, vjp = jax.vjp(lambda *qkv: flash_attention(
            *(x.reshape(B, S, H, D) for x in qkv), causal=True,
            interpret=False).reshape(B, S, H * D), q, k, v)
        return (out,) + vjp(do)
    with record_traced() as traced:
        compiled = jax.jit(attend).lower(x, x, x, x).compile()
    assert traced["flash"] == {form} and traced["attention"] == {"flash"}
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    operand_copies = _copies_of(text, (B, S, H, D), (B, H, S, D),
                                (B * H, S, D), (B, S, H * D))
    wide_rows = re.findall(rf"f32\[{B * H},{S},128\]", text)
    if kernels == 2:
        assert operand_copies == [] and wide_rows == []
        # what the program holds beside its operands and results: the
        # row statistics, 4 bytes a head and position, and `delta`
        assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    else:
        assert operand_copies and wide_rows


def _described_train_step(topo, monkeypatch, chips, layers=2):
    """`LMTrainer`'s step at the training cells' widths compiled for
    `chips` of the described v5e:2x2 on a dp mesh, the lowering told it
    is for a TPU (`attention="auto"` and the kernels' `interpret` ask
    `jax.default_backend()`). Returns the compiled step and the
    `compiler_options` its jit was handed."""
    from mpi_operator_tpu.models.transformer import (CausalLM,
                                                     TransformerConfig)
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh
    from mpi_operator_tpu.parallel.sharding import activation_rules_scope
    from mpi_operator_tpu.train import lm_trainer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    handed = []
    jit = jax.jit

    def spy(fun, **kw):
        if getattr(fun, "__name__", "") == "_step_fn":
            handed.append(kw.get("compiler_options"))
        return jit(fun, **kw)
    monkeypatch.setattr(lm_trainer.jax, "jit", spy)
    model = CausalLM(TransformerConfig(
        vocab_size=50304, max_len=1024, num_layers=layers, num_heads=16,
        embed_dim=1024, mlp_dim=4096, causal=True, dtype=jnp.bfloat16,
        attention="auto", remat=False))
    mesh = make_mesh(MeshConfig(dp=chips), devices=topo.devices[:chips])
    trainer = lm_trainer.LMTrainer(model, mesh, lm_trainer.LMTrainerConfig(
        global_batch_size=8 * chips, seq_len=1024))
    # nothing can be put on a described chip: the state as shapes, which
    # also leaves the trainer its shardings
    state = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    batch = lambda dt: jax.ShapeDtypeStruct(          # noqa: E731
        (8 * chips, 1024), dt, sharding=trainer.batch_sharding)
    with activation_rules_scope(mesh):
        compiled = trainer.compile_step().lower(
            state, batch(jnp.int32), batch(jnp.int32),
            batch(jnp.float32)).compile()
    (options,) = handed
    return compiled, options


_PLAIN_STEPS = {}


def _plain_train_step(topo, chips):
    """`_described_train_step` of the trainer as it stands, compiled once a
    module for each `chips` (four tests read the same two programs):
    (compiled, compiler options, what `record_traced` heard)."""
    from mpi_operator_tpu.ops.attention import record_traced
    if chips not in _PLAIN_STEPS:
        with pytest.MonkeyPatch.context() as patch, \
                record_traced() as traced:
            _PLAIN_STEPS[chips] = _described_train_step(
                topo, patch, chips) + (traced,)
    return _PLAIN_STEPS[chips]


def _entry(text):
    """The scheduled entry computation of a compiled program: what stands
    there is an operation of its own, not part of a fusion."""
    lines = text.splitlines()
    return "\n".join(lines[next(n for n, ln in enumerate(lines)
                                if ln.startswith("ENTRY ")):])


def _writes(text, op, dtype, *shapes):
    """How many `op` instructions of `text` write a `dtype` array of one of
    `shapes`."""
    dims = "|".join(",".join(map(str, sh)) for sh in shapes)
    return len(re.findall(rf"= {dtype}\[({dims})\][^ ]* {op}\(", text))


@pytest.mark.parametrize("chips", [1, 4])
def test_the_train_step_keeps_its_attention_activations_as_rows(
        chips, topo, quiet_cache):
    """The training cells' step, two layers deep, on one chip and on the
    dp=4 mesh: between `Attention`'s projections and the flash kernels no
    activation is copied into another layout (the parent's program held
    16 `copy` of `bf16[8,1024,1024]`, eight a layer: q, k, v and `out`'s
    input, `do`, `dq`, `dk`, `dv`), anywhere in the program, inside a
    fusion or out; on the mesh the `shard_map` boundary carries the same
    rows. What came instead, pinned at the count the hand-in has: each
    projection's float32 master is cast to bfloat16 ONCE by an operation
    of its own (4 a layer; the parent's convolutions took the cast in,
    once forward and once for `dx`), and no weight or weight gradient is
    relaid: q, k and v enter their product as `[H·D, E]`, the order the
    chip keeps an `[E, 16, 64]` master in, so their gradients are born
    and, on the mesh, reduced as the master lies (`[E, H·D]` products
    brought 3 `copy` of `f32[1024,1024]` a layer there). On the mesh the
    reduced gradient of `out` is cast back to float32 alone, 1 a layer."""
    text = _plain_train_step(topo, chips)[0].as_text()
    assert _copies_of(text, (8, 1024, 1024), (8, 1024, 16, 64),
                      (8, 16, 1024, 64), (128, 1024, 64)) == []
    assert "bf16[8,1024,16,64]" not in text      # the 4-D shape is a view
    entry = _entry(text)
    qkv, out, flat = (1024, 16, 64), (16, 64, 1024), (1024, 1024)
    assert _copies_of(entry, qkv, out, flat) == []
    assert _writes(entry, "convert", "bf16", qkv) == 6
    assert _writes(entry, "convert", "bf16", out) == 2
    assert _writes(entry, "convert", "f32", qkv, out, flat) == (
        2 if chips == 4 else 0)
    if chips == 4:
        # the weight gradients cross the chips as the masters lie
        assert _writes(text, "all-reduce", "bf16", qkv) >= 1


_HLO_ARRAY = re.compile(r"\b(bf16|f32)\[([\d,]*)\]")


def _reductions(text):
    """The all-reduces of a compiled step, in the order of the scheduled
    entry computation: (asynchronous, [(type, dims) of each operand],
    line number in the entry) — an `async-collective-start` fusion stands
    for the all-reduce its computation holds."""
    bodies, lines, entry = {}, text.splitlines(), None
    for n, ln in enumerate(lines):
        if ln.startswith("ENTRY "):
            entry = n
        m = re.match(r"^%?([\w.-]+) \(", ln)
        if m:
            name = m.group(1)
        m = re.search(r" = (.*?) all-reduce\(", ln)
        if m and entry is None:
            bodies[name] = _HLO_ARRAY.findall(m.group(1))
    out = []
    for n, ln in enumerate(lines[entry:]):
        m = re.match(r"^\s*%?async-collective-start[\w.-]* = .* "
                     r"calls=%?([\w.-]+)", ln)
        if m and m.group(1) in bodies:
            out.append((True, bodies[m.group(1)], n))
            continue
        m = re.search(r" = (.*?) all-reduce\(", ln)
        if m:
            out.append((False, _HLO_ARRAY.findall(m.group(1)), n))
    return out, lines[entry:]


def test_a_dp4_train_steps_gradient_all_reduces_run_under_weight_gradients(
        topo, quiet_cache, monkeypatch):
    """The training cells' step on a dp=4 mesh of the described chips, two
    layers deep. With `DP_OVERLAP_OPTIONS` (which the trainer hands its
    jit because the mesh splits the batch over TPUs) every matrix but
    the last of each of the scheduler's groups is reduced ALONE and
    asynchronously: an `async-collective-start` fusion, a weight
    gradient's product that carries the transfer's steps
    (`async_collective_fusion`), an `async-collective-done`. Without
    them (the parent's program) the same leaves are reduced as tuples,
    synchronously, and nothing is asynchronous. What is reduced, and in
    what type, is the same leaf for leaf: matrices and biases in
    bfloat16, the type the backward products write, norm scales and
    biases in float32. No asynchronous pair was merged back (an
    `all-reduce` that carries `async_collective_name`) where it stands
    alone with a MiB or more."""
    from mpi_operator_tpu.train import lm_trainer
    compiled, options, _ = _plain_train_step(topo, 4)
    assert options == lm_trainer.DP_OVERLAP_OPTIONS
    text = compiled.as_text()
    reds, entry = _reductions(text)
    leaves = lambda rs: sorted(op for _, ops, _ in rs   # noqa: E731
                               for op in ops if op[1])
    is_async = [a for a, ops, _ in reds if any(d for _, d in ops)]
    total, n_async = lm_trainer.count_grad_reductions(text)
    assert (total, n_async) == (len(is_async), sum(is_async))
    # two layers: 12 block matrices, the position table and the tied
    # table twice (the head's part and the embedding's)
    assert n_async >= 10 and total - n_async <= 5
    for asynchronous, ops, at in reds:
        if asynchronous:
            assert len(ops) == 1
            done = next(n for n in range(at, len(entry)) if re.match(
                r"^\s*%?async-collective-done", entry[n]))
            assert any("calls=%async_collective_fusion" in ln
                       for ln in entry[at:done]), entry[at][:200]
        elif len(ops) > 1:
            # what is still combined is small: a MiB is the bound
            assert sum(math.prod(map(int, d.split(","))) * (
                2 if t == "bf16" else 4) for t, d in ops if d) <= 1 << 20
    for t, d in leaves(reds):
        # float32: norm scales and biases [1024] only; all else bfloat16
        assert t == "bf16" or d == "1024", (t, d)
    assert ("bf16", "50304,1024") in leaves(reds)

    monkeypatch.setattr(lm_trainer, "DP_OVERLAP_OPTIONS", None)
    parent, options = _described_train_step(topo, monkeypatch, 4)
    assert options is None
    parent_reds, _ = _reductions(parent.as_text())
    assert leaves(parent_reds) == leaves(reds)
    assert not any(a for a, _, _ in parent_reds)
    assert lm_trainer.count_grad_reductions(parent.as_text())[1] == 0
    assert max(len(ops) for _, ops, _ in parent_reds) > 12


def test_a_one_chip_train_step_holds_no_collective_and_takes_no_option(
        topo, quiet_cache):
    from mpi_operator_tpu.train import lm_trainer
    compiled, options, _ = _plain_train_step(topo, 1)
    assert options is None
    text = compiled.as_text()
    assert lm_trainer.count_grad_reductions(text) == (0, 0)
    assert not re.search(r" (all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)[\w-]*\(", text)
    assert "async-collective" not in text
    # two layers' flash kernels (forward, backward) and the head's one
    assert text.count('custom_call_target="tpu_custom_call"') == 5


def test_the_train_step_scores_its_head_and_its_loss_in_one_pass(
        topo, quiet_cache, monkeypatch):
    """The training cells' step (8 x 1024 tokens a chip, GPT-2-medium's
    widths, two layers deep) takes `ops/xent.py`'s one-kernel form, and
    says so; its program holds no array of float32 logits, whole or as
    rows, on one chip or on the dp=4 mesh (where the kernel runs under
    `shard_map` on a chip's rows), and what it keeps beside its operands
    falls by half of what those logits took (the other half is the
    bfloat16 cotangent, which the `dtable` product reads)."""
    from mpi_operator_tpu.ops.attention import record_traced
    from mpi_operator_tpu.train import lm_trainer
    logits = re.compile(r"f32\[(8,1024|8192|32,1024|32768),50304\]")
    temp = {}
    for chips in (1, 4):
        compiled, _, traced = _plain_train_step(topo, chips)
        assert traced["head_loss"] == {
            "pallas_xent[rows=256,vocab_tile=2048,products=3]"}
        assert not logits.search(compiled.as_text())
        temp[chips] = compiled.memory_analysis().temp_size_in_bytes
    monkeypatch.setattr(lm_trainer.LMTrainer, "_one_pass_head",
                        lambda self: False)
    with record_traced() as traced:
        parent, _ = _described_train_step(topo, monkeypatch, 1)
    assert traced["head_loss"] == set()
    assert logits.search(parent.as_text())
    assert parent.memory_analysis().temp_size_in_bytes - temp[1] \
        > 0.45 * 8 * 1024 * 50304 * 4


@pytest.mark.parametrize("tokens,vocab", [
    (8192, 50304),      # the cells' head
    (4096, 50257),      # GPT-2's unpadded table: another cut of the tile
])
def test_head_loss_kernel_fits_the_vmem_it_asks_for(tokens, vocab,
                                                    one_chip, quiet_cache):
    """`tied_head_xent` and both its gradients for `tokens` rows of 1024
    against `vocab`: Mosaic takes the kernel with the 51.5 MB of kept
    logits (it refuses one that passes the limit it was given), the
    blocks and scratch `kernel_vmem_bytes` counts are under that limit,
    and the limit under what a v5e has; the cotangent is the one large
    array between the kernel and the `dtable` product."""
    from mpi_operator_tpu.ops import xent
    t = xent.tiling(tokens, vocab, xent._KEPT_ROWS)
    need = xent.kernel_vmem_bytes(t, 1024, 2, True)
    limit = xent._vmem_limit(t, 1024, 2, True)
    assert need < limit <= xent.VMEM_CEILING < 128 << 20
    assert need > 4 * t.rows * vocab
    shape = lambda sh, dt: jax.ShapeDtypeStruct(   # noqa: E731
        sh, dt, sharding=one_chip)

    def grads(h, table, y):
        return jax.value_and_grad(
            lambda h, table: xent.tied_head_xent(
                h, table, y, scan=False, interpret=False),
            argnums=(0, 1), has_aux=True)(h, table)
    compiled = jax.jit(grads).lower(
        shape((1, tokens, 1024), jnp.bfloat16),
        shape((vocab, 1024), jnp.bfloat16),
        shape((1, tokens), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.search(rf"f32\[(1,)?{tokens},{vocab}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.25 * 2 * tokens * vocab


# ---------------------------------------------------------------------------
# Qwen3-Next as one chip of the 4 that share a layer: heads of 256 over two
# layers' own pools, six delta-rule states of 2 MB a slot, 96 slots
# ---------------------------------------------------------------------------
Q3 = dict(slots=96, pages=15360, page=64, max_len=16384, heads=16,
          kv_heads=2, head_dim=256, key_heads=16, value_heads=32)


def test_walking_kernel_and_chunk_walk_at_heads_of_256_eight_queries_a_group(
        one_chip, quiet_cache):
    """One attention layer's cache write and kernel call at Qwen3-Next's
    widths: rows of 2 x (256 + 256) = 1024 columns (2 048 B a position),
    a head's K two lane tiles, eight queries a group, a table of 256
    pages. Mosaic takes the walking kernel in the K-lane form (16 pages
    of 128 KB a turn, two slots, both heads a grid step: nothing here
    was refused where the 576-wide latent pool above is); the pool is
    aliased through write and read. `paged_attend`, the chunk path, takes
    the same shape at the cell's bucket."""
    from mpi_operator_tpu.ops.attention import (kv_row_width, paged_attend,
                                                paged_decode_attention,
                                                record_traced, traced_name)
    S, NP, ps, H, KV, D = (Q3[k] for k in ("slots", "pages", "page", "heads",
                                           "kv_heads", "head_dim"))
    nblk = Q3["max_len"] // ps
    W = kv_row_width(KV, D)
    assert (W, nblk, H // KV) == (1024, 256, 8)
    pages, vmem = _walk_vmem(nblk, ps, KV, D, H // KV)
    assert pages == 16 and vmem < SCOPED_VMEM, (pages, vmem)
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                  sharding=one_chip)

    def layer(q, pool, cur, pt, rows, at):
        pool = pool.reshape(NP * ps, W).at[at].set(
            rows, mode="drop").reshape(NP, ps, W)
        return pool, paged_decode_attention(q, pool, cur, pt,
                                            interpret=False)
    with record_traced() as traced:
        compiled = jax.jit(layer, donate_argnums=(1,)).lower(
            spec((S, H, D), jnp.bfloat16), spec((NP, ps, W), jnp.bfloat16),
            spec((S,), jnp.int32), spec((S, nblk), jnp.int32),
            spec((S, W), jnp.bfloat16), spec((S,), jnp.int32)).compile()
    assert traced_name(traced["decode"]) == "pallas_paged[live,pages=16,hb=2]"
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, (NP, ps, W), (NP * ps, W)) == []
    assert compiled.memory_analysis().alias_size_in_bytes >= NP * ps * W * 2
    chunk = jax.jit(paged_attend).lower(
        spec((S, 128, H, D), jnp.bfloat16), spec((NP, ps, W), jnp.bfloat16),
        spec((S, 128), jnp.int32), spec((S, nblk), jnp.int32)).compile()
    assert "tpu_custom_call" not in chunk.as_text()
    assert chunk.memory_analysis().temp_size_in_bytes < 1.5e9


def test_gdn_state_update_kernel_updates_the_donated_state_where_it_lies(
        one_chip, quiet_cache):
    """One layer's delta-rule step at Qwen3-Next's widths (96 rows of 32
    tiles [128, 128] float32 on 16 key heads): Mosaic takes the kernel (16
    value heads a grid step, ONE transpose a block that turns their eight
    key heads' k and q into columns, a column spread along the lanes a
    head) under its scoped VMEM limit; a row's 2 097 152 B of state are
    the call's operand and its result, aliased, and nothing of that size
    is copied or made beside them."""
    from mpi_operator_tpu.ops.gated_delta import gated_delta_state_update
    S, Hk, Hv = Q3["slots"], Q3["key_heads"], Q3["value_heads"]
    held = (S, Hv, 128, 128)
    spec = lambda *shape: jax.ShapeDtypeStruct(             # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: gated_delta_state_update(*a[:-1], fresh=a[-1],
                                            interpret=False),
        donate_argnums=(5,)).lower(
            spec(S, Hk, 128), spec(S, Hk, 128), spec(S, Hv, 128),
            spec(S, Hv), spec(S, Hv), spec(*held),
            jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip)
        ).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 1
    assert _copies_of(text, held) == []
    assert m.alias_size_in_bytes >= S * 2097152
    assert m.temp_size_in_bytes < 16 << 20


@pytest.fixture(scope="module")
def qwen3_next(one_chip):
    """The decode model, parameter shapes and cache shapes of
    `perfbench/configs/qwen3-next-80b-a3b-1of4.json` as the benchmark's
    engine builds them (96 slots, two pools of 15360 pages of 64,
    contexts to 16384)."""
    from mpi_operator_tpu.models.generate import decode_model
    from perfbench import weights_qwen3next as weights
    from perfbench.kinds import _serve_qwen3next
    with open(os.path.join(REPO, "perfbench", "configs",
                           "qwen3-next-80b-a3b-1of4.json")) as f:
        dims = weights.Dims.from_config(json.load(f))
    model = _serve_qwen3next.model_of(dims, jnp.bfloat16, Q3["max_len"], True)
    dmodel = decode_model(model, True, page_size=Q3["page"],
                          num_pages=Q3["pages"])
    on_chip = lambda tree: jax.tree.map(                        # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: weights.make_params(jax.random.PRNGKey(0), dims,
                                    jnp.bfloat16)))
    z = jnp.zeros((Q3["slots"], 1), jnp.int32)
    table = jnp.zeros((Q3["slots"], Q3["max_len"] // Q3["page"]), jnp.int32)
    cache = on_chip(jax.eval_shape(
        lambda p: dmodel.apply({"params": p}, z, positions=z,
                               with_head=False, mutable=["cache"],
                               pages=table)[1]["cache"], params))
    return dims, dmodel, params, cache


def _q3_big_copies(text):
    S, NP, ps = Q3["slots"], Q3["pages"], Q3["page"]
    return _copies_of(text, (NP, ps, 1024), (NP * ps, 1024),
                      (S, Q3["value_heads"], 128, 128))


def test_qwen3_next_decode_step_passes_each_state_and_pool_through_once(
        one_chip, quiet_cache, monkeypatch, qwen3_next):
    """The engine's own `step_paged` over the eight layers: eight Mosaic
    calls (a state update in each delta-rule layer, a walk of its own
    pool in each attention layer, each under its scope's name), no copy
    of a pool [15360, 64, 1024] or of a state [96, 32, 128, 128], all 5.2
    GB of them aliased through the step, and 7.33 GB of weights, the cache
    and 55 MB of temporaries fit the chip."""
    dims, dmodel, params, cache = qwen3_next
    S, ps, NP = Q3["slots"], Q3["page"], Q3["pages"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _lower_greedy_step(
        _engine_programs(dmodel, S, ps), params, cache, S,
        Q3["max_len"] // ps, one_chip).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    calls = re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert sorted(c.rsplit(".", 1)[0] for c in calls) == (
        ["gdn.update"] * 6 + ["q3attn.attend"] * 2)
    assert _q3_big_copies(text) == []
    held = 2 * NP * ps * 1024 * 2 + S * dims.slot_state_bytes()
    assert dims.slot_state_bytes() == 12877824
    assert m.alias_size_in_bytes >= held
    assert 12.5e9 < m.argument_size_in_bytes < 12.8e9
    assert m.temp_size_in_bytes < 0.25e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.5e9


def test_qwen3_next_prefill_bucket_fits_beside_what_the_chip_holds(
        one_chip, quiet_cache, monkeypatch, qwen3_next):
    """The engine's own `prefill_paged` at the cell's one bucket ([1, 128]
    tokens, a call's width since PR 48: the delta rule in two chunks of
    64; 128 tokens through the grouped experts): no kernel, no copy of a
    pool, the cache aliased, and the program's temporaries beside the
    12.6 GB the engine holds stay under the chip's 16 GB. (The compiler
    does turn each layer's state into another layout and back round the
    chunk form, 0.4 GB a layer: PERF.md section 7.)"""
    dims, dmodel, params, cache = qwen3_next
    S, ps = Q3["slots"], Q3["page"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt,           # noqa: E731
                                              sharding=one_chip)
    compiled = _engine_programs(dmodel, S, ps).prefill.lower(
        params, cache, arg(jnp.int32, 1), arg(jnp.int32, 1, 128),
        arg(jnp.int32, 1), arg(jnp.int32, 1, Q3["max_len"] // ps),
        arg(jnp.int32, 1)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((params, cache)))
    assert "tpu_custom_call" not in text
    assert _copies_of(text, (Q3["pages"], ps, 1024),
                      (Q3["pages"] * ps, 1024)) == []
    assert m.alias_size_in_bytes >= held - 7.4e9
    assert m.temp_size_in_bytes < 2.0e9
    assert held + m.temp_size_in_bytes < 15.0e9


# ---------------------------------------------------------------------------
# Every serving cell's prefill buckets at a call's width (PR 48): a prompt
# runs `programs.NARROW_ROWS` rows, not `slots`
# ---------------------------------------------------------------------------
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SERVING_CELLS = {c["name"]: c for c in json.load(_f)["workloads"]
                     if c["name"].startswith("serve")}

#: temporaries of the `[slots, bucket]` program each bucket ran until PR 48,
#: compiled for the same described chip by this file's tests at the parent
#: commit (the other buckets' were never compiled here)
WIDE_TEMP_UNTIL_PR48 = {
    ("serve-deepseekv2-1of8-longdoc", 128): 1108537856,
    ("serve-falconh1-4of72-longform", 128): 2073662976,
    ("serve-gpt2xl-decode-heavy", 128): 1861819904,
    ("serve-qwen3next-1of4-sessions-wide", 128): 1425910272,
}


@pytest.mark.parametrize("cell", sorted(SERVING_CELLS))
def test_each_prefill_bucket_compiles_at_one_row_at_the_cells_sizes(
        cell, one_chip, quiet_cache, monkeypatch):
    """`prefill_paged` over the cell's own model, pool and slot state
    (built as `scripts/serving_programs_digest.py` builds every cell's
    programs) compiles at `NARROW_ROWS` = 1 row for each of the cell's
    buckets: no chunk path needs a row block, the cache stays aliased
    through the slot leaves' gather and scatter, and the temporaries are a
    small part of what the `[slots, bucket]` program took, logged beside
    them."""
    import importlib.util
    from mpi_operator_tpu.serve.programs import NARROW_ROWS
    spec = importlib.util.spec_from_file_location(
        "serving_programs_digest",
        os.path.join(REPO, "scripts", "serving_programs_digest.py"))
    digest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(digest)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert NARROW_ROWS == 1
    seen = []
    for name, lowered in digest.programs_of(
            REPO, SERVING_CELLS[cell], one_chip,
            want=lambda kind: kind == "prefill"):
        bucket = int(name[name.index("[") + 1:-1])
        m = lowered.compile().memory_analysis()
        wide = WIDE_TEMP_UNTIL_PR48.get((cell, bucket))
        print(f"{cell} [{NARROW_ROWS}, {bucket}]: temporaries "
              f"{m.temp_size_in_bytes} B; at [slots, {bucket}] until PR 48 "
              f"{wide if wide is not None else 'not compiled here'}")
        # the pools and the slot leaves come back in the buffers they
        # came in: all that is not aliased is weights
        assert m.alias_size_in_bytes > 2.9e9
        assert m.temp_size_in_bytes < 0.3e9
        assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.5e9
        if wide is not None:
            assert m.temp_size_in_bytes < wide / 4
        seen.append(bucket)
    with open(os.path.join(REPO, "perfbench", "traffic",
                           SERVING_CELLS[cell]["traffic"] + ".json")) as f:
        assert seen == json.load(f)["engine"]["chunk_buckets"]
