"""`--help` on every argparse entry point under `examples/`."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("module", [
    "lm_benchmark", "serve_benchmark", "benchmark", "allreduce_bench",
    "elastic_benchmark", "sched_benchmark"])
def test_benchmark_cli_help_exits_zero(module):
    """`--help` on every benchmark entrypoint must exit 0 without
    touching jax device state — a flag typo in an argparse block
    otherwise surfaces only when a cluster run dies at parse time."""
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", f"mpi_operator_tpu.examples.{module}",
         "--help"], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stdout
    assert "usage" in proc.stdout.lower()
