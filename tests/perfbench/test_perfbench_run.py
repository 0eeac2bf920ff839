"""The harness end to end on the CPU, at toy sizes, through everything
but its look for a chip: cells, traffic, configurations and a per-layer
metric added as files alone; the control, which has to come out as not
correct; and runs whose timed path is broken underneath, which have to
come out as not correct too."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from _perfbench_tiny import REPO, make_root
from perfbench import harness, run, weights
from perfbench.manifest import Manifest


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("perfbench"))


def _last_line_keys(result, metrics):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["metrics"]) == set(metrics)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])


def test_run_refuses_to_measure_off_tpu():
    """The command itself, as the driver starts it, on a machine without
    a chip: another exit code than 0, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload",
         "train-gpt2m-1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "TPU" in out.stderr
    assert not [ln for ln in out.stdout.splitlines()
                if ln.startswith("{") and "correct" in ln]


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-train", {"train_tokens_per_s_per_chip", "setup_s"}),
    ("tiny-train4", {"train_tokens_per_s_per_chip", "setup_s"}),
    ("tiny-closed", {"serve_tokens_per_s", "setup_s"}),
    ("tiny-open", {"ttft_p90_ms", "tpot_p90_ms", "setup_s"})])
def test_cells_added_by_files_alone_run_and_are_correct(root, cell, metrics):
    """A configuration, a traffic mix and a cell that exist only as files
    in a temporary directory and entries in its BENCHMARK.json run through
    the repository's run.py, untouched."""
    result = run.run_cell(root, cell, 2**31 + 17, 0.6, False,
                          require_tpu=False)
    _last_line_keys(result, metrics)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["count"] == Manifest(root).cell(cell)["chips"]


def test_traced_run_reports_layer_metrics_the_added_one_among_them(root):
    result = run.run_cell(root, "tiny-closed", 5, 0.8, True,
                          require_tpu=False)
    assert {"slot_occupancy_pct", "host_blocked_ms_p50",
            "decode_step_ms_p50", "tiny_tick_ms_p90"} <= set(
                result["metrics"])
    # no device ran on this machine: the readers of the device trace find
    # nothing to read and their metrics are left out, not invented
    assert "decode_device_ms_p50" not in result["metrics"]
    assert "paged_decode_roofline" not in result["metrics"]
    assert result["device"]["window_s"] > 0
    assert result["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    from mpi_operator_tpu.train.lm_trainer import LMTrainer
    real = LMTrainer.train_step

    def stuck(self, state, tokens, targets, mask=None):
        _, metrics = real(self, jax.tree.map(jax.numpy.copy, state), tokens,
                          targets, mask)
        return state, metrics
    monkeypatch.setattr(LMTrainer, "train_step", stuck)
    result = run.run_cell(root, "tiny-train", 3, 0.3, False,
                          require_tpu=False)
    assert result["correct"] is False


def test_train_step_that_leaves_out_part_of_the_batch_is_not_correct(
        root, monkeypatch):
    from mpi_operator_tpu.train.lm_trainer import LMTrainer
    real = LMTrainer.train_step

    def partial(self, state, tokens, targets, mask=None):
        mask = jax.numpy.ones(targets.shape, jax.numpy.float32
                              ).at[tokens.shape[0] // 2:].set(0.0)
        return real(self, state, tokens, targets, mask)
    monkeypatch.setattr(LMTrainer, "train_step", partial)
    result = run.run_cell(root, "tiny-train", 3, 0.3, False,
                          require_tpu=False)
    assert result["correct"] is False


@pytest.mark.parametrize("cell", ["tiny-closed", "tiny-open"])
def test_served_token_altered_where_it_is_produced_is_not_correct(
        root, cell, monkeypatch):
    from mpi_operator_tpu.serve import engine as engine_mod
    real = engine_mod.sample_slots

    def second_best(logits, *a, **kw):
        best = jax.numpy.argmax(logits, -1)
        masked = logits.at[jax.numpy.arange(logits.shape[0]), best].set(-1e9)
        return real(masked, *a, **kw)
    monkeypatch.setattr(engine_mod, "sample_slots", second_best)
    result = run.run_cell(root, cell, 9, 0.6, False, require_tpu=False)
    assert result["correct"] is False


def test_control_of_the_training_cell_is_not_correct(root):
    """The plain reference in the precision below the cell's (fp8 under
    bfloat16), put in the program's place, misses a limit; the program
    itself keeps every limit (the sound run above)."""
    m = Manifest(root)
    cell = m.cell("tiny-train")
    traffic = m.traffic(cell["traffic"])
    train = m.module("kinds", "train")
    ctx = harness.Context(manifest=m, cell=cell,
                          config=m.config(cell["config"]), traffic=traffic,
                          seed=4, seconds=0.0, trace=False,
                          devices=jax.devices()[:1])
    dims = weights.Dims.from_config(ctx.config)
    key = weights.seed_key(ctx.seed)
    reference = train.reference_readings(ctx, dims, key)
    control = train.reference_readings(ctx, dims, key, "fp8")
    gaps = train.gaps(control, reference)
    assert any(gaps[k] > traffic["limits"][k] for k in gaps), gaps


def test_control_of_the_serving_cell_is_not_correct(root):
    from perfbench.tools import control_serve
    m = Manifest(root)
    cell = m.cell("tiny-closed")
    traffic = m.traffic(cell["traffic"])
    serve = m.module("kinds", "_serve")
    ctx = harness.Context(manifest=m, cell=cell,
                          config=m.config(cell["config"]), traffic=traffic,
                          seed=6, seconds=0.0, trace=False,
                          devices=jax.devices()[:1])
    # 1.5 s serve a hundred tokens and more on a free CPU; where the
    # machine is busy (six test workers, other sandboxes) and they do not,
    # the window is made again on a fresh engine, longer
    for seconds in (1.5, 6.0, 24.0):
        eng = serve.Engine(ctx)
        eng.warm([16], eng.dims.vocab_real)
        results, prompts = control_serve.serve_window(ctx, eng, seconds)
        sample = serve.pick_sample(results, prompts, ctx.seed, 4)
        g = serve.served_gaps(eng.dims, eng.dtype, eng.key, sample, prompts,
                              "fp8")
        if g["served_tokens"] > 40:
            break
    assert g["served_tokens"] > 40
    lim = traffic["limits"]
    assert g["served_logit_gap"] <= lim["served_logit_gap_widest"]
    assert g["served_logprob_gap"] <= lim["served_logprob_gap_widest"]
    assert (g["control_logit_gap"] > lim["served_logit_gap_widest"]
            or g["control_logprob_gap"] > lim["served_logprob_gap_widest"])
