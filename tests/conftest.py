"""Test configuration.

Data-plane tests simulate multi-worker collectives on 8 virtual CPU devices
(the reference tests multi-node declaratively with fake clientsets,
SURVEY.md §4; we additionally own a data plane, so we use
--xla_force_host_platform_device_count to exercise real XLA collectives
without TPUs). `force_host_platform` must run before any backend is
initialized, hence at import of this file.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_operator_tpu.utils.hostplatform import force_host_platform  # noqa: E402

force_host_platform(8)

# debug builds pay for the O(num_pages) PageAllocator.check() audit on
# every engine reset(); production resets skip it (serve/engine.py)
os.environ.setdefault("TPU_DEBUG_PAGES", "1")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_checkpoint_saved_state():
    """Clear checkpoint.py's per-directory last-saved records between
    tests: tmp_path reuse across back-to-back in-process runs would
    otherwise make maybe_save skip a save the second test legitimately
    needs. sys.modules.get, not an import — tests that never touch
    checkpoints must not pay the jax/orbax import."""
    yield
    mod = sys.modules.get("mpi_operator_tpu.train.checkpoint")
    if mod is not None:
        mod.reset_saved_state()


@pytest.fixture(autouse=True, scope="module")
def _empty_span_log():
    """Empty telemetry.spans' process-global log (bound: 100 000 records)
    when a test module starts: an xdist worker runs several files in one
    process, every engine tick appends, and a traced run that finds the
    log full raises LogWrapped. Module scope, so a file whose module
    fixture runs an engine once keeps its records for all its tests."""
    mod = sys.modules.get("mpi_operator_tpu.telemetry.spans")
    if mod is not None:
        mod.clear()
    yield


@pytest.fixture
def status_port():
    """One user at a time, across xdist workers, of the status port that
    rank 0 of `lm_benchmark` binds (`bootstrap.STATUS_PORT`, 8477): two
    files run such a process, `--dist loadfile` runs files side by side,
    and the second to bind dies with `Address already in use` (tier-1 of
    PR 46, twice, once more files had moved the schedule). A lock on a
    file under the run's temporary directory, released when it closes."""
    import fcntl
    import tempfile
    path = os.path.join(tempfile.gettempdir(),
                        "mpi_operator_tpu_status_port.lock")
    with open(path, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second compile variants, excluded from the tier-1 "
        "gate (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "multichip: needs >1 device to be meaningful (tp/ring/pp meshes); "
        "satisfied here by the 8 virtual CPU devices, but deselect with "
        "-m 'not multichip' on a single real chip without the virtual "
        "mesh")
    config.addinivalue_line(
        "markers",
        "serving: continuous-batching engine tests (serve/); select with "
        "-m serving to gate the serving surface alone")
    config.addinivalue_line(
        "markers",
        "hfta: horizontally fused trainer tests (train/hfta.py); select "
        "with -m hfta to gate the job-packing data plane alone")
    config.addinivalue_line(
        "markers",
        "spec: speculative-decoding tests (multi-token verify, drafting, "
        "rewind); select with -m spec to gate the speculation surface "
        "alone")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / crash-consistency soak tests "
        "(controller/chaos.py harness); select with -m chaos, or run the "
        "longer out-of-process soak via scripts/tier1.sh --chaos")
