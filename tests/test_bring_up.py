"""Bring-up contracts: what must hold for the system to start on a chip
and to fail loudly when it cannot (chip_smoke.py, the compile cache, the
peaks table, the dispatch reports, the jax-free control plane).

All CPU: what these tests pin is placement, refusal and reporting — never
a time or a rate.
"""
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, env=None, timeout=120):
    """Run `python -c code` from the checkout in a fresh process."""
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env or {})
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=timeout)


# -- (a) compile-cache placement --------------------------------------------

def test_compile_cache_env_wins_and_nothing_is_set(monkeypatch, tmp_path):
    from mpi_operator_tpu.utils import compile_cache

    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))

    def refuse(*a, **kw):
        raise AssertionError(f"jax.config.update called: {a} {kw}")
    monkeypatch.setattr(jax.config, "update", refuse)
    assert compile_cache.enable_compile_cache() == str(tmp_path)


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    code = ("from mpi_operator_tpu.utils.compile_cache import "
            "compile_cache_dir; print(compile_cache_dir())")
    paths = {_run(code).stdout.strip() for _ in range(2)}
    assert paths == {os.path.join(REPO, ".jax_compile_cache")}
    # fixed by the package's location: no pid, temp dir or time in it
    (path,) = paths
    assert "/tmp" not in path and not any(ch.isdigit() for ch in
                                          os.path.relpath(path, REPO))


def test_compile_cache_is_off_on_cpu_without_env(monkeypatch):
    from mpi_operator_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


# -- (b) chip_smoke.py without a chip ---------------------------------------

def test_chip_smoke_fails_on_cpu_and_its_parent_never_imports_jax():
    # jax is poisoned in the PARENT only: were chip_smoke.py to import it
    # (or mpi_operator_tpu, which would), this dies with ImportError
    # instead of the platform message. Children are fresh processes.
    code = ("import sys, runpy; sys.modules['jax'] = None; "
            "runpy.run_path('chip_smoke.py', run_name='__main__')")
    proc = _run(code, env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "found platform 'cpu'" in proc.stderr
    assert "chip_smoke: FAILED" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""


# -- (c) peaks table ---------------------------------------------------------

class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peaks_table_v5e_unknown_tpu_and_cpu():
    from mpi_operator_tpu.utils import flops

    v5e = _Dev("tpu", "TPU v5 lite")
    assert flops.device_peak_flops(v5e) == 197e12
    assert flops.device_peaks(v5e) == (197e12, 819e9)
    with pytest.raises(ValueError, match="TPU v9 mega"):
        flops.device_peaks(_Dev("tpu", "TPU v9 mega"))
    # a substring of a known kind is not that kind
    with pytest.raises(ValueError):
        flops.device_peaks(_Dev("tpu", "TPU v5 lite pod"))
    assert flops.device_peaks(_Dev("cpu", "cpu")) is None
    assert flops.mfu(1e12, 1.0, 1, _Dev("cpu", "cpu")) is None


# -- (d) a requested decode kernel that cannot tile --------------------------

def test_untileable_decode_kernel_raises_on_tpu_and_falls_back_on_cpu(
        monkeypatch):
    from mpi_operator_tpu.models.transformer import (Attention,
                                                     TransformerConfig)
    from mpi_operator_tpu.ops.attention import record_traced

    # bf16 pages need a multiple of 16 positions; 8 cannot tile
    cfg = TransformerConfig(
        num_heads=2, embed_dim=32, max_len=32, dtype=jnp.bfloat16,
        decode=True, decode_kernel=True,
        decode_page_size=8, decode_num_pages=9)
    x = jnp.zeros((2, 1, 32), jnp.bfloat16)
    kw = dict(positions=jnp.zeros((2, 1), jnp.int32),
              pages=jnp.ones((2, 4), jnp.int32))
    with record_traced() as traced:
        Attention(cfg).init(jax.random.PRNGKey(0), x, **kw)
    assert traced["decode"] == {"dense"}        # CPU: the oracle takes it

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="decode_page_size=8"):
        Attention(cfg).init(jax.random.PRNGKey(0), x, **kw)


def test_interpret_is_refused_on_tpu(monkeypatch):
    from mpi_operator_tpu.ops import attention

    assert attention._resolve_interpret(None) is True        # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._resolve_interpret(None) is False
    with pytest.raises(ValueError, match="interpret=True"):
        attention._resolve_interpret(True)


def test_flash_reports_the_dense_fallback_for_an_untileable_seq():
    from mpi_operator_tpu.ops.attention import (flash_attention,
                                                record_traced)

    q = jnp.zeros((1, 197, 2, 16), jnp.float32)         # ViT's S=197
    with record_traced() as traced:
        flash_attention(q, q, q, causal=False, block_q=64, block_k=64)
    assert traced["attention"] == {"dense"}
    q = jnp.zeros((1, 128, 2, 16), jnp.float32)
    with record_traced() as traced:
        flash_attention(q, q, q, causal=False, block_q=64, block_k=64)
    assert traced["attention"] == {"flash"}


# -- (e) headline JSON of the entry points ----------------------------------

DEVICE_KEYS = {"platform": "cpu", "device_kind": "cpu"}


def _last_json(text):
    return json.loads([ln for ln in text.splitlines()
                       if ln.startswith("{")][-1])


def test_lm_benchmark_headline_names_device_and_traced_attention(
        monkeypatch, capsys, status_port):
    from mpi_operator_tpu.bootstrap.bootstrap import poll_status
    from mpi_operator_tpu.examples import lm_benchmark

    monkeypatch.setenv("TPU_COORDINATOR_ADDRESS", "127.0.0.1:1")
    monkeypatch.setenv("TPU_NUM_PROCESSES", "1")
    monkeypatch.delenv("TPU_LAUNCHER", raising=False)
    # play the launcher: rank 0 holds "done" until someone has read it
    stop = threading.Event()

    def poll():
        while not stop.wait(0.2):
            if (poll_status("127.0.0.1") or "").startswith("done"):
                return
    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        rc = lm_benchmark.main([
            "--workload", "gpt2", "--size", "test", "--batch-per-device",
            "1", "--seq-len", "64", "--num-steps", "2", "--warmup-steps",
            "1", "--attention", "flash"])
    finally:
        stop.set()
        poller.join(timeout=10)
    assert rc == 0 and not poller.is_alive()
    head = _last_json(capsys.readouterr().out)
    assert DEVICE_KEYS.items() <= head.items()
    assert head["device_count"] == jax.device_count()
    # set by the dispatch site: the flash kernel (interpreted on CPU) was
    # traced because it was asked for and S=64 tiles
    assert head["attention_impl"] == "flash"
    assert head["compile_seconds"] > 0
    # the state the step returns matches the state it was first given:
    # one train-step program, not a second compile on the second call
    assert head["step_compiles"] == 1
    # ... which reduces the gradients over the host's devices, and on the
    # CPU none of it asynchronously
    assert head["grad_reductions"] >= 1
    assert head["grad_reductions_async"] == 0
    assert head["state_device_ids"] == head["batch_device_ids"] \
        == list(range(jax.device_count()))


@pytest.mark.serving
def test_serve_benchmark_headline_names_device_and_traced_decode(capsys):
    from mpi_operator_tpu.examples import serve_benchmark

    rc = serve_benchmark.main([
        "--size", "test", "--slots", "2", "--num-requests", "3",
        "--no-baseline"])
    assert rc == 0
    head = _last_json(capsys.readouterr().out)
    assert DEVICE_KEYS.items() <= head.items()
    assert head["device_count"] == jax.device_count()
    # off TPU the benchmark asks for the dense oracle, and says so
    assert head["decode_impl"] == "dense"
    assert head["prefill_impl"] == "dense"
    assert head["serving_requests_complete"] is True
    # a greedy trace: one step program, the first step included
    assert head["serving_step_compiles"] == 1
    assert head["serving_compiles_after_warmup"] == 0
    assert head["serving_cache_donated"] is False        # CPU has none
    assert head["compile_cache_dir"] is None


# -- the control plane stays jax-free ----------------------------------------

def test_control_plane_and_launcher_import_without_jax():
    """The operator image's own build check (Dockerfile): with jax
    poisoned, the control plane and the per-worker launcher import."""
    code = ("import sys; sys.modules['jax'] = None\n"
            "import mpi_operator_tpu\n"
            "import mpi_operator_tpu.__main__\n"
            "import mpi_operator_tpu.controller\n"
            "import mpi_operator_tpu.cluster.kubeclient\n"
            "import mpi_operator_tpu.bootstrap.launch\n"
            "assert not [m for m in sys.modules if m.startswith('jax.')]\n"
            "print('jax-free')")
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr
    assert "jax-free" in proc.stdout


# -- one process for each chip ------------------------------------------------

def test_launch_refuses_slots_on_a_tpu_host(monkeypatch):
    from mpi_operator_tpu.bootstrap import BootstrapError, launch

    monkeypatch.setattr(launch, "local_tpu_chips",
                        lambda: ["/dev/accel0", "/dev/accel1"])
    with pytest.raises(BootstrapError, match="one process at a time"):
        launch.launch([sys.executable, "-c", "pass"], slots=2)
    # slots=1 is the normal TPU case and needs no check
    assert launch.launch([sys.executable, "-c", "pass"], slots=1) == 0


def test_mesh_layout_failure_is_an_error_on_tpu(monkeypatch):
    from jax.experimental import mesh_utils

    from mpi_operator_tpu.parallel import MeshConfig, make_mesh

    def boom(*a, **kw):
        raise ValueError("no topology-aware assignment")
    monkeypatch.setattr(mesh_utils, "create_device_mesh", boom)
    devs = jax.devices()[:2]
    assert make_mesh(MeshConfig(dp=2), devs).shape["dp"] == 2    # CPU
    fake = [type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite",
                           "id": i})() for i in range(2)]
    with pytest.raises(ValueError, match="TPU v5 lite"):
        make_mesh(MeshConfig(dp=2), fake)


# -- built from what git would commit -----------------------------------------

def test_native_loader_rebuilds_when_not_built_from_this_source():
    from mpi_operator_tpu.native import loader

    if loader._build() is not None:
        pytest.skip("no g++ in this image")
    with open(loader._STAMP) as fh:
        good = fh.read()
    # a library left by another tree: same name, other source
    with open(loader._STAMP, "w") as fh:
        fh.write("0" * 64 + "\n")
    before = os.stat(loader._SO).st_mtime_ns
    assert loader._build() is None
    with open(loader._STAMP) as fh:
        assert fh.read() == good
    assert os.stat(loader._SO).st_mtime_ns > before
    # built from this source: left alone
    before = os.stat(loader._SO).st_mtime_ns
    assert loader._build() is None
    assert os.stat(loader._SO).st_mtime_ns == before


# -- the chip check's kernel harness, interpreted at a tiny size --------------

def test_kernel_parity_harness_runs_every_kernel_of_the_two_legs():
    from mpi_operator_tpu.examples.kernel_parity import run_kernel_parity

    records = run_kernel_parity(
        train_shape=dict(batch=1, seq=64),
        serve_shape=dict(slots=2, max_len=64, page_size=32, prefilled=40),
        model=dict(heads=2, head_dim=16))
    assert [r["kernel"] for r in records] == [
        "flash_fwd", "flash_dq", "flash_dk", "flash_dv",
        "decode_attention", "decode_attention_int8",
        "paged_decode_attention", "paged_decode_attention_int8"]
    assert all(r["ok"] for r in records), records


def test_kernel_parity_harness_runs_the_latent_decode_kernel_when_asked():
    from mpi_operator_tpu.examples.kernel_parity import (MLA_CASE,
                                                         mla_decode_case)

    rec = mla_decode_case(
        slots=3, max_len=64, page_size=8, prefilled=40, hidden_size=48,
        num_heads=4, q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8)
    assert rec["kernel"] == "mla_paged_decode_attention"
    assert rec["traced"] == "pallas_mla_paged[live,pages=8]"
    assert rec["max_rel_err"] <= 2e-2
    assert MLA_CASE["max_len"] % MLA_CASE["page_size"] == 0


def test_kernel_parity_harness_runs_deepseek_v2s_latent_paths_when_asked(
        monkeypatch):
    """The same kernel under the other model that shares `LatentAttention`
    (YaRN, no rank factors; a table longer than the rows' live pages),
    and its chunk path in row groups against K and V expanded."""
    from mpi_operator_tpu.examples.kernel_parity import (MLA_CASE_128,
                                                         MLA_CHUNK_CASE,
                                                         mla_chunk_case,
                                                         mla_decode_case)
    from mpi_operator_tpu.ops import attention

    toy = dict(hidden_size=48, num_heads=4, q_lora_rank=12, kv_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    rec = mla_decode_case(slots=3, max_len=128, page_size=8, prefilled=40,
                          family="deepseek_v2", **toy)
    assert rec["traced"] == "pallas_mla_paged[live,pages=16]"
    assert rec["shape"]["family"] == "deepseek_v2"
    assert rec["max_rel_err"] <= 2e-2
    # at the model's 128 heads a turn goes in two chains, and says so
    # (the toy's four heads stand in for them, and its pages of 2 KB are
    # held to sixteen a turn); three turns a row
    with monkeypatch.context() as mp:
        mp.setattr(attention, "_MLA_CHAINS_HEADS", 4)
        mp.setattr(attention, "_MLA_PAGES_VMEM_BUDGET", 16 * 8 * 128 * 2)
        rec = mla_decode_case(slots=7, max_len=1024, page_size=8,
                              prefilled=300, family="deepseek_v2", **toy)
    assert rec["traced"] == "pallas_mla_paged[live,pages=16,chains=2]"
    assert max(rec["cursors"]) == 2 * 16 * 8 and rec["max_rel_err"] <= 2e-2
    assert MLA_CASE_128["prefilled"] > 2 * 16 * MLA_CASE_128["page_size"]
    assert MLA_CASE_128["family"] == "deepseek_v2"
    assert MLA_CASE_128["max_len"] // MLA_CASE_128["page_size"] == 256
    # the real case takes the row-group path; so does the toy, told to
    assert attention.mla_query_rows(
        MLA_CHUNK_CASE["rows"], MLA_CHUNK_CASE["chunk"], 128, 640,
        "bfloat16") == 4
    with pytest.raises(AssertionError, match="builds its queries whole"):
        mla_chunk_case(rows=4, chunk=16, page_size=8, **toy)
    monkeypatch.setattr(attention, "_MLA_BUILT_QUERY_BYTES", 0)
    monkeypatch.setattr(attention, "_MLA_QUERY_ROWS", 2 * 16 * 4)
    rec = mla_chunk_case(rows=4, chunk=16, page_size=8, **toy)
    assert rec["kernel"] == "mla_paged_attend_rows"
    assert rec["shape"]["rows_a_group"] == 2 and rec["max_rel_err"] <= 2e-2


def test_kernel_parity_harness_runs_the_windowed_kernel_and_the_scan():
    from mpi_operator_tpu.examples.kernel_parity import (scan_case,
                                                         window_decode_cases)

    records = window_decode_cases(slots=3, heads=4, kv_heads=2, head_dim=16,
                                  page_size=8, window=24, table=6)
    assert [r["kernel"] for r in records] == [
        "paged_decode_attention_window_ring",
        "paged_decode_attention_window_table",
        "paged_decode_attention_pairs"]
    assert all(r["traced"].startswith("pallas_paged[live,pages=")
               for r in records)
    assert all(r["max_rel_err"] <= 2e-2 for r in records), records
    rec = scan_case(rows=2, chunk=6, channels=16, states=4)
    assert rec["max_rel_err"] <= 1e-5


@pytest.mark.parametrize("name,shape,form", [
    ("a_head_a_tile", dict(rows=2, chunk=12, heads=4, head_dim=8, groups=2,
                           states=16), "dense"),
    ("two_heads_a_tile", dict(rows=2, chunk=12, heads=4, head_dim=64,
                              groups=1, states=16), "dense"),
])
def test_kernel_parity_harness_runs_the_ssd_chunk_against_its_steps(
        name, shape, form):
    """The SSD case at a head that fills a lane tile alone (Falcon-H1's
    form) and at heads of 64 channels, two to a tile (Granite-4.0-H's);
    off the chip the steps are plain `jax.numpy` and say so."""
    from mpi_operator_tpu.examples.kernel_parity import (
        SSD_CASE, SSD_CASE_64, ssd_case)
    from mpi_operator_tpu.ops.ssm import ssd_state_shape

    rec = ssd_case(**shape)
    assert rec["kernel"] == "ssd_chunk_scan_vs_state_update_steps"
    assert rec["max_rel_err"] <= 1e-5
    assert rec["ssd_traced"] == form
    assert SSD_CASE["heads"] * SSD_CASE["head_dim"] == 4096
    assert SSD_CASE_64["heads"] * SSD_CASE_64["head_dim"] == 8192
    held = {k: v for k, v in shape.items() if k not in ("rows", "chunk")}
    tiles = ssd_state_shape(1, held["heads"], held["head_dim"],
                            held["groups"], held["states"])[1]
    assert tiles == (2 if name == "two_heads_a_tile" else 4)


def test_kernel_parity_harness_runs_the_head_and_its_loss_when_asked():
    """The training step's head and loss in one pass joins the registry:
    off the chip it runs as the scan and says so, at the cells' shape the
    kernel's steps divide the tokens."""
    from mpi_operator_tpu.examples.kernel_parity import (HEAD_LOSS_CASE,
                                                         head_loss_case)
    from mpi_operator_tpu.ops import xent

    rec = head_loss_case(batch=2, seq=16, embed=128, vocab=640)
    assert rec["kernel"] == "tied_head_xent_vs_logits"
    assert rec["head_loss_traced"] == "xla_chunked[chunks=8,products=3]"
    assert rec["max_rel_err"] <= 1e-2 and 0.2 < rec["accuracy"] < 0.8
    c = HEAD_LOSS_CASE
    assert xent.covers(c["embed"], c["vocab"], "bfloat16")
    assert c["batch"] * c["seq"] % xent._KEPT_ROWS == 0


def test_the_state_update_names_the_form_it_takes_at_each_models_shape():
    """What `chip_smoke.py`'s kernels leg requires of `ssd_traced`, from
    the shapes alone: Falcon-H1's head a tile, Granite's two heads."""
    import importlib.util
    import os

    import jax.numpy as jnp

    from mpi_operator_tpu.examples.kernel_parity import SSD_CASE, SSD_CASE_64
    from mpi_operator_tpu.ops.ssm import ssd_state_shape, ssd_update_form

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    forms = []
    for case in (SSD_CASE, SSD_CASE_64):
        shape = ssd_state_shape(1, case["heads"], case["head_dim"],
                                case["groups"], case["states"])
        assert shape[-1] == 128 and shape[1] * shape[3] == 128 * 64 \
            or case is SSD_CASE
        forms.append(ssd_update_form(
            jnp.zeros((1, case["heads"], case["head_dim"])),
            jnp.zeros(shape)))
    assert sorted(forms) == smoke.SSD_FORMS


# -- kernels on a multi-device mesh -------------------------------------------
# GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
# automatically partitioned", first seen on the four-chip host). jax.export
# runs the TPU lowering rules from here, so the programs the two legs
# compile are lowered for TPU on a virtual mesh; interpret mode would not
# reach the check, hence the mocked backend while tracing.

def _lower_for_tpu(monkeypatch, fn, *args):
    from jax import export

    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return export.export(jax.jit(fn), platforms=["tpu"])(
            *args).mlir_module()


@pytest.mark.multichip
def test_train_step_with_flash_lowers_for_tpu_on_dp2_tp2(monkeypatch):
    from mpi_operator_tpu.models.transformer import create_lm
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh
    from mpi_operator_tpu.parallel.sharding import activation_rules_scope
    from mpi_operator_tpu.train.lm_trainer import LMTrainer, LMTrainerConfig

    mesh = make_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
    model = create_lm("gpt2-test", dtype=jnp.bfloat16, attention="flash",
                      max_len=512)
    trainer = LMTrainer(model, mesh,
                        LMTrainerConfig(global_batch_size=4, seq_len=512))
    state = trainer.init_state(jax.random.PRNGKey(0))
    toks = jax.device_put(jnp.zeros((4, 512), jnp.int32),
                          trainer.batch_sharding)
    mask = jax.device_put(jnp.ones((4, 512), jnp.float32),
                          trainer.batch_sharding)
    with activation_rules_scope(mesh):
        text = _lower_for_tpu(monkeypatch, trainer._step_fn, state, toks,
                              toks, mask)
    # forward, dq and dk/dv kernels for each of the two layers
    assert text.count("tpu_custom_call") == 6


@pytest.mark.multichip
@pytest.mark.parametrize("paged", [False, True])
def test_decode_step_with_kernel_lowers_for_tpu_on_dp4(monkeypatch, paged):
    from mpi_operator_tpu.models.generate import decode_model
    from mpi_operator_tpu.models.transformer import create_lm
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh
    from mpi_operator_tpu.parallel.sharding import shard_init

    mesh = make_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
    model = create_lm("gpt2-test", dtype=jnp.bfloat16, max_len=128)
    variables, _ = shard_init(model, mesh, jax.random.PRNGKey(0),
                              jnp.zeros((1, 32), jnp.int32))
    dmodel = decode_model(model, True,
                          page_size=32 if paged else None,
                          num_pages=8 * 4 + 1 if paged else 0)

    # paged: what the engine's init_cache runs; not: generate()'s
    # lockstep step, the contiguous kernel's only caller
    def step(params):
        z = jnp.zeros((8, 1), jnp.int32)
        kw = {"pages": jnp.zeros((8, 4), jnp.int32)} if paged else {}
        return dmodel.apply({"params": params}, z, positions=z,
                            with_head=False, mutable=["cache"], **kw)

    text = _lower_for_tpu(monkeypatch, step, variables["params"])
    assert text.count("tpu_custom_call") == 2          # one per layer


@pytest.mark.multichip
def test_kernels_on_a_mesh_match_the_single_device_call():
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_operator_tpu.ops.attention import (flash_attention,
                                                paged_decode_attention)
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (4, 64, 4, 16)) for key in keys)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32) * v)
    ref = jax.grad(loss, (0, 1, 2))(q, k, v)
    sh = NamedSharding(mesh, P(("dcn", "dp", "fsdp"), None, "tp", None))
    sharded = [jax.device_put(x, sh) for x in (q, k, v)]
    assert "shard_map" in str(jax.make_jaxpr(loss)(*sharded))
    for got, want in zip(jax.jit(jax.grad(loss, (0, 1, 2)))(*sharded), ref):
        np.testing.assert_allclose(got, want, atol=1e-5)

    # the paged pool replicated on the mesh: rows split over dp, the
    # pool's rows over tp by the heads' columns (4 heads of 2 x 16)
    rep = NamedSharding(mesh, P())
    qd = jax.random.normal(keys[0], (8, 4, 16))
    pool = jax.random.normal(keys[1], (8 * 4 + 1, 16, 4 * 2 * 16))
    cur = jnp.asarray([0, 5, 15, 16, 31, 32, 63, 40], jnp.int32)
    table = jnp.asarray(np.random.RandomState(0).permutation(32)
                        .reshape(8, 4) + 1, jnp.int32)
    want = paged_decode_attention(qd, pool, cur, table)
    got = jax.jit(paged_decode_attention)(
        *(jax.device_put(x, rep) for x in (qd, pool, cur, table)))
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.multichip
@pytest.mark.serving
def test_engine_on_a_dp4_mesh_compiles_its_step_once_and_matches():
    """Params on a dp=4 mesh, 8 slots: the kernel splits rows over dp, and
    the token chain must still start and stay where the step leaves it —
    one step program — with the tokens a single-device engine produces."""
    from mpi_operator_tpu.models.transformer import create_lm
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh
    from mpi_operator_tpu.parallel.sharding import shard_init
    from mpi_operator_tpu.serve import EngineConfig, Request, ServingEngine

    model = create_lm("gpt2-test", dtype=jnp.float32, max_len=64)
    prompt = jnp.zeros((1, 8), jnp.int32)
    cfg = EngineConfig(slots=8, chunk_buckets=(8,), decode_kernel=True,
                       page_size=8)

    def serve(devices):
        mesh = make_mesh(MeshConfig(dp=len(devices)), devices=devices)
        variables, _ = shard_init(model, mesh, jax.random.PRNGKey(0), prompt)
        engine = ServingEngine(model, variables["params"], cfg)
        results = engine.run([
            Request(id=i, prompt=[(7 * i + j) % 50 for j in range(5 + i)],
                    max_new_tokens=6) for i in range(8)])
        return engine, {i: r.tokens for i, r in results.items()}

    engine, tokens = serve(jax.devices()[:4])
    assert engine.compile_counts()["step"] == 1
    _, want = serve(jax.devices()[:1])
    assert tokens == want
