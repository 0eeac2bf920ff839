"""Bootstrap-path tests: the env/hostname → jax.distributed resolution that
replaces the reference's hostfile + kubexec rsh agent (SURVEY §2.4)."""
import time

import pytest

from mpi_operator_tpu.bootstrap import (
    BootstrapError, initialize, process_info, resolve_worker_ordinal,
)
from mpi_operator_tpu.bootstrap.bootstrap import (
    ENV_COORDINATOR, ENV_LAUNCHER, ENV_NUM_PROCESSES, ENV_WORKER_ID,
)


def _env(**kw):
    base = {
        ENV_COORDINATOR: "job-worker-0.job-worker.default.svc:8476",
        ENV_NUM_PROCESSES: "4",
    }
    base.update(kw)
    return base


def test_ordinal_from_hostname():
    assert resolve_worker_ordinal("job-worker-3") == 3
    assert resolve_worker_ordinal("a-b-c-worker-12") == 12
    with pytest.raises(BootstrapError, match="ordinal"):
        resolve_worker_ordinal("launcher")


def test_process_info_from_worker_hostname():
    info = process_info(env=_env(), hostname="job-worker-2")
    assert info.process_id == 2
    assert info.num_processes == 4
    assert not info.is_launcher
    assert not info.is_coordinator
    assert process_info(env=_env(), hostname="job-worker-0").is_coordinator


def test_explicit_worker_id_overrides_hostname():
    info = process_info(env=_env(**{ENV_WORKER_ID: "1"}),
                        hostname="job-worker-3")
    assert info.process_id == 1


def test_launcher_gets_rank_zero_without_ordinal():
    info = process_info(env=_env(**{ENV_LAUNCHER: "1"}), hostname="job-launcher-xyz12")
    assert info.is_launcher and info.process_id == 0


def test_missing_coordinator_is_actionable_error():
    with pytest.raises(BootstrapError, match="TPU_COORDINATOR_ADDRESS"):
        process_info(env={}, hostname="job-worker-0")


def test_ordinal_out_of_range_rejected():
    with pytest.raises(BootstrapError, match=">= num_processes"):
        process_info(env=_env(), hostname="job-worker-9")


def test_initialize_single_process_skips_distributed():
    """num_processes == 1 must not call jax.distributed (dev flow)."""
    info = initialize(env={ENV_COORDINATOR: "localhost:8476",
                           ENV_NUM_PROCESSES: "1"},
                      hostname="job-worker-0")
    assert info.num_processes == 1


def test_slots_interleave_global_rank():
    """slots>1: global rank = ordinal*slots + local (hostfile `slots=` parity,
    ref mpi_job_controller.go:857-869)."""
    env = _env(**{"TPU_SLOTS_PER_WORKER": "4", "TPU_NUM_PROCESSES": "8",
                  "TPU_LOCAL_RANK": "2"})
    info = process_info(env=env, hostname="job-worker-1")
    assert info.process_id == 6
    with pytest.raises(BootstrapError, match="TPU_LOCAL_RANK"):
        process_info(env=_env(**{"TPU_SLOTS_PER_WORKER": "2",
                                 "TPU_LOCAL_RANK": "2"}),
                     hostname="job-worker-0")


def test_launcher_never_joins_process_group():
    """The launcher must not call jax.distributed.initialize — rank 0 lives
    on worker-0 (rank-collision regression)."""
    import types
    import unittest.mock as mock

    import mpi_operator_tpu.bootstrap.bootstrap as bs

    calls = []
    sentinel_jax = types.ModuleType("jax")
    sentinel_dist = types.ModuleType("jax.distributed")
    sentinel_dist.initialize = lambda *a, **kw: calls.append((a, kw))
    sentinel_jax.distributed = sentinel_dist

    # num_processes=4 would normally trigger distributed init
    env = _env(**{ENV_LAUNCHER: "1"})
    with mock.patch.dict("sys.modules", {"jax": sentinel_jax,
                                         "jax.distributed": sentinel_dist}):
        info = bs.initialize(env=env, hostname="anything")
    assert info.is_launcher and info.process_id == 0
    assert calls == [], "launcher must never call jax.distributed.initialize"


def test_status_channel_and_launcher_wait():
    """rank-0 StatusServer ←poll— launcher: running → done <code>."""
    import threading
    from mpi_operator_tpu.bootstrap.bootstrap import (
        ProcessInfo, StatusServer, launcher_wait, poll_status,
    )
    server = StatusServer(port=0)
    try:
        assert poll_status("localhost", server.port) == "running"
        info = ProcessInfo(coordinator_address=f"localhost:8476",
                           num_processes=2, process_id=0, is_launcher=True)
        result = {}
        t = threading.Thread(target=lambda: result.update(
            code=launcher_wait(info, port=server.port, poll_interval=0.05)))
        t.start()
        server.set_done(3, linger=5.0)
        t.join(timeout=5)
        assert result["code"] == 3
    finally:
        server.close()


def test_launcher_wait_startup_timeout():
    from mpi_operator_tpu.bootstrap.bootstrap import ProcessInfo, launcher_wait
    info = ProcessInfo(coordinator_address="localhost:1", num_processes=2,
                       process_id=0, is_launcher=True)
    with pytest.raises(BootstrapError, match="unreachable"):
        launcher_wait(info, port=1, poll_interval=0.05, startup_timeout=0.3)


def test_launcher_wait_loss_then_recovery():
    """LOST → re-contact resets all windows; completion still observed."""
    import threading
    from mpi_operator_tpu.bootstrap.bootstrap import (
        LAUNCHER_LOST_EXIT, ProcessInfo, StatusServer, launcher_wait,
    )
    # phase 1: server up, launcher sees "running"
    server = StatusServer(port=0)
    port = server.port
    info = ProcessInfo(coordinator_address="localhost:8476",
                       num_processes=2, process_id=0, is_launcher=True)
    result = {}
    t = threading.Thread(target=lambda: result.update(code=launcher_wait(
        info, port=port, poll_interval=0.05,
        startup_timeout=5.0, lost_timeout=0.4)), daemon=True)
    t.start()
    time.sleep(0.3)              # launcher has made contact (RUNNING)
    # phase 2: outage longer than lost_timeout → launcher goes LOST then
    # RESTARTING, but must NOT give up: a fresh startup window applies
    server.close()
    time.sleep(0.8)
    # phase 3: "pod restarted" — new server on the same port; done observed
    server2 = StatusServer(port=port)
    try:
        server2.set_done(0, linger=5.0)
        t.join(timeout=10)
        assert not t.is_alive()
        assert result["code"] == 0
        assert result["code"] != LAUNCHER_LOST_EXIT
    finally:
        server2.close()


def test_a_closed_status_server_frees_its_port_though_nobody_polls_it():
    """close() under a serving thread that sits in accept(): the port is
    free for the next server of the same process at once (the in-process
    `lm_benchmark.main` of tests/test_bring_up.py left 8477 bound for the
    rest of its xdist worker's life, and a gang of this file then died
    with `Address already in use`)."""
    from mpi_operator_tpu.bootstrap.bootstrap import StatusServer
    server = StatusServer(port=0)
    server.close()
    server._thread.join(timeout=5)
    assert not server._thread.is_alive()
    StatusServer(port=server.port).close()


def test_launcher_wait_loss_then_timeout_returns_lost_exit():
    """LOST → RESTARTING → fresh startup window expires → LAUNCHER_LOST_EXIT
    (not BootstrapError: contact was established, so this is infra loss)."""
    from mpi_operator_tpu.bootstrap.bootstrap import (
        LAUNCHER_LOST_EXIT, ProcessInfo, StatusServer, launcher_wait,
    )
    server = StatusServer(port=0)
    port = server.port
    info = ProcessInfo(coordinator_address="localhost:8476",
                       num_processes=2, process_id=0, is_launcher=True)
    import threading
    result = {}
    t = threading.Thread(target=lambda: result.update(code=launcher_wait(
        info, port=port, poll_interval=0.05,
        startup_timeout=0.3, lost_timeout=0.2)), daemon=True)
    t.start()
    time.sleep(0.2)              # contact made
    server.close()               # permanent loss
    t.join(timeout=10)
    assert not t.is_alive()
    assert result["code"] == LAUNCHER_LOST_EXIT


def test_status_channel_token_handshake():
    """A wrong-token poller is denied and cannot consume the done-linger;
    the real launcher (right token) still observes completion."""
    import threading
    from mpi_operator_tpu.bootstrap.bootstrap import (
        StatusServer, poll_status,
    )
    server = StatusServer(port=0, token="job-uid-42")
    try:
        assert poll_status("localhost", server.port,
                           token="wrong") == "denied"
        assert poll_status("localhost", server.port,
                           token="job-uid-42") == "running"
        done = threading.Event()
        t = threading.Thread(
            target=lambda: (server.set_done(7, linger=10.0), done.set()))
        t.start()
        time.sleep(0.1)
        # stray connections hammering the channel must not end the linger
        for _ in range(5):
            assert poll_status("localhost", server.port,
                               token="wrong") == "denied"
        assert not done.is_set()
        assert poll_status("localhost", server.port,
                           token="job-uid-42") == "done 7"
        t.join(timeout=5)
        assert done.is_set()
    finally:
        server.close()


def test_controller_injects_job_token():
    """The controller's discovery env carries TPU_JOB_TOKEN = job uid for
    the status-channel handshake."""
    from mpi_operator_tpu.api.types import new_tpu_job
    from mpi_operator_tpu.cluster.apiserver import InMemoryAPIServer
    from mpi_operator_tpu.controller import ControllerConfig, TPUJobController

    api_server = InMemoryAPIServer()
    controller = TPUJobController(api_server, config=ControllerConfig())
    job = new_tpu_job("tok", tpus=8)
    job.metadata.uid = "uid-abc"
    alloc = controller.allocate_processing_units(job, False)
    worker = controller.new_worker(job, alloc)
    launcher = controller.new_launcher(job, alloc)
    for obj in (worker.spec.template, launcher.spec.template):
        assert obj.main_container().env["TPU_JOB_TOKEN"] == "uid-abc"


def test_launch_forks_slots_and_propagates_failure(tmp_path):
    """The orted-replacement: forks slots processes with TPU_LOCAL_RANK and
    returns the first non-zero exit code."""
    import sys
    from mpi_operator_tpu.bootstrap.launch import launch
    out = tmp_path / "ranks"
    out.mkdir()
    code = launch([sys.executable, "-c",
                   "import os, pathlib; pathlib.Path("
                   f"'{out}', os.environ['TPU_LOCAL_RANK']).write_text('x')"],
                  slots=3)
    assert code == 0
    assert sorted(p.name for p in out.iterdir()) == ["0", "1", "2"]
    code = launch([sys.executable, "-c",
                   "import os, sys; sys.exit(5 if "
                   "os.environ['TPU_LOCAL_RANK']=='1' else 0)"], slots=2)
    assert code == 5


def test_config_dir_fallback(tmp_path):
    (tmp_path / "coordinator-address").write_text("cm-host:8476\n")
    (tmp_path / "num-processes").write_text("2\n")
    info = process_info(env={"TPU_CONFIG_PATH": str(tmp_path)},
                        hostname="job-worker-1")
    assert info.coordinator_address == "cm-host:8476"
    assert info.num_processes == 2 and info.process_id == 1


WORKER_SCRIPT = r'''
import os, sys
rank, port, repo = int(sys.argv[1]), sys.argv[2], sys.argv[3]
# fresh process: force the host platform (one local device) before any
# backend init, same channel as utils/hostplatform
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
# cross-process CPU collectives need the gloo transport (XLA CPU default
# cannot psum across processes)
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, repo)
from mpi_operator_tpu.bootstrap import initialize
env = dict(os.environ)
env["TPU_COORDINATOR_ADDRESS"] = "127.0.0.1:" + port
env["TPU_NUM_PROCESSES"] = "2"
info = initialize(env, hostname="e2e-worker-%d" % rank)
assert info.process_id == rank, (info.process_id, rank)
assert jax.process_count() == 2
import jax.numpy as jnp
out = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
    jnp.ones((jax.local_device_count(),)))
assert float(out[0]) == float(len(jax.devices())), float(out[0])
print("rank %d psum ok" % rank, flush=True)
'''


def _spawn_and_collect(cmds, markers):
    """Run the worker commands as real processes; assert each exits 0 and
    prints its marker. Shared by the single- and multi-slice rendezvous
    e2e tests so the harness (timeouts, cleanup, asserts) can't drift."""
    import os
    import subprocess

    base_env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [subprocess.Popen(c, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              env=base_env) for c in cmds]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    for i, (p, out, marker) in enumerate(zip(procs, outs, markers)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert marker in out, f"worker {i} missing {marker!r}:\n{out}"


def test_multiprocess_rendezvous_e2e(tmp_path):
    """The full distributed-bootstrap slice as two REAL processes: the
    controller's env contract (TPU_COORDINATOR_ADDRESS / TPU_NUM_PROCESSES)
    plus StatefulSet-hostname rank derivation feed jax.distributed, and a
    cross-process psum proves the collective fabric is live — the
    capability the reference assembles from hostfile + kubexec + mpirun +
    orted (ref mpi_job_controller.go:849-885, :1123-1131), with zero exec
    machinery."""
    import os
    import socket
    import subprocess
    import sys

    script = tmp_path / "worker.py"
    script.write_text(WORKER_SCRIPT)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:            # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    _spawn_and_collect(
        [[sys.executable, str(script), str(rank), str(port), repo]
         for rank in (0, 1)],
        [f"rank {rank} psum ok" for rank in (0, 1)])


GANG_SCRIPT = r'''
import os, sys
repo = sys.argv[1]
# fresh process: force the host platform BEFORE any backend init
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, repo)
from mpi_operator_tpu.examples import lm_benchmark
sys.exit(lm_benchmark.main(sys.argv[2:]))
'''


def test_resize_and_resume_e2e(tmp_path, status_port):
    """The resize contract end-to-end with REAL processes (the way the
    rendezvous e2e proves bootstrap): a 2-process gang boots from the
    controller-MATERIALIZED worker env, trains the shipped lm_benchmark
    CLI and checkpoints into a shared dir; the user resizes the spec
    (tpus 8→4); the controller gang-restarts onto the new template; the
    new 1-process gang boots from the NEW env and RESUMES from the
    checkpoint — global-step continuity, not a from-scratch restart."""
    import os
    import re
    import socket
    import subprocess
    import sys

    from mpi_operator_tpu.api import types as api
    from mpi_operator_tpu.api.types import (
        Container, ObjectMeta, PodTemplateSpec, TPUJob, TPUJobSpec)
    from mpi_operator_tpu.cluster.apiserver import InMemoryAPIServer
    from mpi_operator_tpu.controller import TPUJobController

    srv = InMemoryAPIServer()
    ctrl = TPUJobController(srv)
    srv.create(TPUJob(
        metadata=ObjectMeta(name="resize", namespace="default"),
        spec=TPUJobSpec(tpus=8, template=PodTemplateSpec(containers=[
            Container(name="train", image="bench:latest")]))))
    ctrl.sync_handler("default/resize")
    sts = srv.get("StatefulSet", "default", "resize-worker")
    env_2proc = dict(sts.spec.template.main_container().env)
    assert env_2proc["TPU_NUM_PROCESSES"] == "2"

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    train_dir = str(tmp_path / "ckpt")
    script = tmp_path / "gang.py"
    script.write_text(GANG_SCRIPT)
    with socket.socket() as s:               # free coordinator port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    def gang_env(materialized, rank):
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env.update(materialized)
        # the test machine is not a pod: rank comes from the explicit
        # override instead of the StatefulSet hostname, the coordinator
        # DNS name becomes loopback, and the chip gate is dropped (no
        # TPU on a 1-CPU-device world)
        env["TPU_WORKER_ID"] = str(rank)
        env["TPU_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        for k in ("TPU_READY_FILE", "TPU_EXPECTED_CHIPS",
                  "TPU_CONFIG_PATH"):
            env.pop(k, None)
        return env

    cli = ["--workload", "gpt2", "--size", "test", "--batch-per-device",
           "4", "--seq-len", "32", "--warmup-steps", "1", "--dtype",
           "float32", "--train-dir", train_dir, "--ckpt-every", "6",
           # full LR from step 1: the default 100-step warmup would keep
           # the LR ~0 for this whole short run and flatline the loss
           # signal the continuity assertion reads
           "--lr-warmup-steps", "1"]

    def run_gang(materialized, nprocs, num_steps):
        procs = [subprocess.Popen(
            [sys.executable, str(script), repo] + cli
            + ["--num-steps", str(num_steps)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=gang_env(materialized, rank)) for rank in range(nprocs)]
        outs = []
        try:
            for p in procs:
                outs.append(p.communicate(timeout=300)[0])
        finally:
            for p in procs:
                p.kill()
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"gang rank {i} failed:\n{out}"
        return outs[0]                       # rank 0 logs

    out1 = run_gang(env_2proc, nprocs=2, num_steps=12)
    losses1 = [float(x) for x in re.findall(r"loss: ([0-9.]+)", out1)]
    assert losses1, out1
    ckpts = sorted(os.listdir(train_dir))
    assert any(d.startswith("step_") for d in ckpts), ckpts

    # user resizes the job: 8 chips → 4 (2 workers → 1). The controller
    # reconciles it as a checkpointed gang restart onto the new topology.
    job = srv.get(api.KIND, "default", "resize")
    job.spec.tpus = 4
    srv.update(job)
    ctrl.sync_handler("default/resize")
    sts = srv.get("StatefulSet", "default", "resize-worker")
    assert sts.spec.replicas == 1
    env_1proc = dict(sts.spec.template.main_container().env)
    assert env_1proc["TPU_NUM_PROCESSES"] == "1"

    out2 = run_gang(env_1proc, nprocs=1, num_steps=4)
    m = re.search(r"resumed from \S*step_(\d+)", out2)
    assert m, f"no resume line in:\n{out2}"
    assert int(m.group(1)) == 13       # probe + warmup(1) + 12 steps
    losses2 = [float(x) for x in re.findall(r"loss: ([0-9.]+)", out2)]
    assert losses2, out2
    # continuity: the resumed gang restored step 13 (above) and its step
    # counter carries on — 13 + probe + 4 steps lands the final
    # checkpoint at GLOBAL step 18, where a from-scratch run would be at
    # 5. (Streams are step-keyed for token-identical resume, so phase 2
    # sees FRESH batches; the old memorization signal — resumed loss
    # below phase-1's start — no longer exists on uniform random tokens,
    # where every fresh-data loss sits at ~ln(vocab). Bitwise resume
    # identity is pinned in test_resilience.py.)
    assert "step_18" in os.listdir(train_dir), sorted(os.listdir(train_dir))
    assert losses2[0] < 11.0, (losses1, losses2)   # sane, not diverged


def test_elastic_shrink_and_resume_e2e(tmp_path, status_port):
    """The ELASTIC path end-to-end with REAL processes (VERDICT r04 next
    #6 — shrink was controller-tested only): a 2-process elastic gang
    boots from the controller-materialized env, trains the shipped CLI
    and checkpoints; the gang then goes not-Ready past the degraded
    window (no spec edit — capacity loss); the controller SHRINKS via
    status.elasticTpus to the next valid size; the 1-process degraded
    gang boots from the NEW env and resumes from the checkpoint with
    global-step continuity. Restore stays controller-tested
    (tests/test_controller.py::test_elastic_restores_after_recovery_window)."""
    import os
    import re
    import socket
    import subprocess
    import sys

    from mpi_operator_tpu.api import types as api
    from mpi_operator_tpu.api.types import (
        Container, ObjectMeta, PodTemplateSpec, TPUJob, TPUJobSpec)
    from mpi_operator_tpu.cluster.apiserver import InMemoryAPIServer
    from mpi_operator_tpu.cluster.resources import JobStatus, \
        StatefulSetStatus
    from mpi_operator_tpu.controller import TPUJobController, \
        ControllerConfig

    class Clock:
        t = 1000.0

        def __call__(self):
            return self.t

    clock = Clock()
    srv = InMemoryAPIServer()
    ctrl = TPUJobController(srv, config=ControllerConfig(
        elastic_degraded_seconds=60, elastic_recovery_seconds=120))
    ctrl.now = clock
    srv.create(TPUJob(
        metadata=ObjectMeta(name="el", namespace="default"),
        spec=TPUJobSpec(tpus=8, elastic=True, min_tpus=4,
                        template=PodTemplateSpec(containers=[
                            Container(name="train", image="bench:latest")]))))
    ctrl.sync_handler("default/el")
    sts = srv.get("StatefulSet", "default", "el-worker")
    env_2proc = dict(sts.spec.template.main_container().env)
    assert env_2proc["TPU_NUM_PROCESSES"] == "2"

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    train_dir = str(tmp_path / "ckpt")
    script = tmp_path / "gang.py"
    script.write_text(GANG_SCRIPT)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    def gang_env(materialized, rank):
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env.update(materialized)
        env["TPU_WORKER_ID"] = str(rank)
        env["TPU_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        for k in ("TPU_READY_FILE", "TPU_EXPECTED_CHIPS",
                  "TPU_CONFIG_PATH"):
            env.pop(k, None)
        return env

    cli = ["--workload", "gpt2", "--size", "test", "--batch-per-device",
           "4", "--seq-len", "32", "--warmup-steps", "1", "--dtype",
           "float32", "--train-dir", train_dir, "--ckpt-every", "6",
           "--lr-warmup-steps", "1"]

    def run_gang(materialized, nprocs, num_steps):
        procs = [subprocess.Popen(
            [sys.executable, str(script), repo] + cli
            + ["--num-steps", str(num_steps)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=gang_env(materialized, rank)) for rank in range(nprocs)]
        outs = []
        try:
            for p in procs:
                outs.append(p.communicate(timeout=300)[0])
        finally:
            for p in procs:
                p.kill()
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"gang rank {i} failed:\n{out}"
        return outs[0]

    # phase 1: the full-size gang trains and checkpoints (playing kubelet
    # around it: workers Ready, launcher active → Running lands, which is
    # what arms the elastic degraded timer)
    sts.status = StatefulSetStatus(ready_replicas=2, replicas=2)
    srv.update(sts)
    ctrl.sync_handler("default/el")           # readiness gate → launcher
    launcher = srv.get("Job", "default", "el-launcher")
    launcher.status = JobStatus(active=1, start_time=clock.t)
    srv.update(launcher)
    ctrl.sync_handler("default/el")           # Running condition persists
    job = srv.get(api.KIND, "default", "el")
    assert job.status.get_condition(api.COND_RUNNING) is not None

    out1 = run_gang(env_2proc, nprocs=2, num_steps=12)
    losses1 = [float(x) for x in re.findall(r"loss: ([0-9.]+)", out1)]
    assert losses1, out1
    assert any(d.startswith("step_") for d in os.listdir(train_dir))

    # capacity loss: workers stop being Ready and STAY down past the
    # degraded window — NO spec edit anywhere
    sts = srv.get("StatefulSet", "default", "el-worker")
    sts.status = StatefulSetStatus(ready_replicas=0, replicas=2)
    srv.update(sts)
    ctrl.sync_handler("default/el")           # not-Ready timer arms
    clock.t += 61
    ctrl.sync_handler("default/el")           # → ElasticShrink decision
    job = srv.get(api.KIND, "default", "el")
    assert job.spec.tpus == 8                 # spec untouched
    assert job.status.elastic_tpus == 4
    assert job.status.get_condition(api.COND_DEGRADED).status == "True"
    ctrl.sync_handler("default/el")           # materialize the 1-worker world
    sts = srv.get("StatefulSet", "default", "el-worker")
    assert sts.spec.replicas == 1
    env_1proc = dict(sts.spec.template.main_container().env)
    assert env_1proc["TPU_NUM_PROCESSES"] == "1"

    # the degraded gang resumes from the checkpoint — step continuity
    # (see the resize e2e above for why the old memorization-based loss
    # assertion can't survive step-keyed, token-identical streams)
    out2 = run_gang(env_1proc, nprocs=1, num_steps=4)
    m = re.search(r"resumed from \S*step_(\d+)", out2)
    assert m, f"no resume line in:\n{out2}"
    assert int(m.group(1)) == 13
    losses2 = [float(x) for x in re.findall(r"loss: ([0-9.]+)", out2)]
    assert losses2, out2
    assert "step_18" in os.listdir(train_dir), sorted(os.listdir(train_dir))
    assert losses2[0] < 11.0, (losses1, losses2)   # sane, not diverged


# ---------------------------------------------------------------------------
# TPU-health readiness gate (SURVEY §7 "Readiness vs ICI formation")
# ---------------------------------------------------------------------------

def test_device_check_counts_local_devices():
    from mpi_operator_tpu.bootstrap.bootstrap import device_check

    import jax
    n = len(jax.local_devices())
    assert device_check() == n
    assert device_check(expected_chips=n) == n


def test_device_check_chip_mismatch_is_actionable():
    from mpi_operator_tpu.bootstrap.bootstrap import device_check

    with pytest.raises(BootstrapError, match="allocated 99 chips"):
        device_check(expected_chips=99)


def test_mark_ready_atomic_and_gated(tmp_path):
    from mpi_operator_tpu.bootstrap.bootstrap import mark_ready

    marker = tmp_path / "tpu-ready"
    # no path configured (env unset) → no-op, no litter
    assert mark_ready(None) is None
    assert not marker.exists()
    out = mark_ready(str(marker))
    assert out == str(marker)
    assert marker.read_text() == "ok\n"
    # no torn temp file left behind (atomic os.replace)
    assert list(tmp_path.iterdir()) == [marker]


def test_initialize_writes_marker_after_device_check(tmp_path):
    """The full gate: initialize() under the controller-injected env must
    leave the readiness marker only after the runtime enumerated the
    expected devices — the exec probe's contract."""
    import jax
    from mpi_operator_tpu.bootstrap.bootstrap import (
        ENV_EXPECTED_CHIPS, ENV_READY_FILE)

    marker = tmp_path / "tpu-ready"
    n = len(jax.local_devices())
    info = initialize(env={ENV_COORDINATOR: "localhost:8476",
                           ENV_NUM_PROCESSES: "1",
                           ENV_READY_FILE: str(marker),
                           ENV_EXPECTED_CHIPS: str(n)},
                      hostname="job-worker-0")
    assert info.num_processes == 1
    assert marker.exists()                      # probe would now pass


def test_initialize_leaves_no_marker_on_sick_runtime(tmp_path):
    """A chip-count mismatch (sick TPU) must raise AND leave no marker —
    the pod stays NotReady and the launcher gate holds."""
    from mpi_operator_tpu.bootstrap.bootstrap import (
        ENV_EXPECTED_CHIPS, ENV_READY_FILE)

    marker = tmp_path / "tpu-ready"
    with pytest.raises(BootstrapError, match="allocated 99 chips"):
        initialize(env={ENV_COORDINATOR: "localhost:8476",
                        ENV_NUM_PROCESSES: "1",
                        ENV_READY_FILE: str(marker),
                        ENV_EXPECTED_CHIPS: "99"},
                   hostname="job-worker-0")
    assert not marker.exists()


# ---------------------------------------------------------------------------
# multi-slice rank derivation (SURVEY §7 "Multi-slice (DCN) bootstrap")
# ---------------------------------------------------------------------------

def test_multislice_global_rank_is_slice_major():
    """Pod `<job>-worker-s<k>-<i>` + TPU_SLICE_ID=k → global worker index
    k*workers_per_slice + i, matching the controller's rank-major
    worker-hostnames order (the hostfile-analogue topology truth)."""
    from mpi_operator_tpu.bootstrap.bootstrap import (
        ENV_SLICE_ID, ENV_WORKERS_PER_SLICE)

    env = {ENV_COORDINATOR: "ms-worker-s0-0.ms-worker.default.svc:8476",
           ENV_NUM_PROCESSES: "4", "TPU_NUM_SLICES": "2",
           ENV_SLICE_ID: "1", ENV_WORKERS_PER_SLICE: "2"}
    info = process_info(env=env, hostname="ms-worker-s1-0")
    assert info.process_id == 2            # slice 1 starts at rank 2
    assert info.slice_id == 1
    assert info.num_slices == 2
    assert info.workers_per_slice == 2
    info = process_info(env={**env, ENV_SLICE_ID: "0"},
                        hostname="ms-worker-s0-1")
    assert info.process_id == 1


def test_multislice_workers_per_slice_derivable():
    """workers-per-slice can be derived from num_processes/slots/slices
    when the env omits it (older ConfigMaps)."""
    env = {ENV_COORDINATOR: "c:1", ENV_NUM_PROCESSES: "8",
           "TPU_NUM_SLICES": "2", "TPU_SLICE_ID": "1"}
    info = process_info(env=env, hostname="j-worker-s1-3")
    assert info.workers_per_slice == 4
    assert info.process_id == 7


def test_multislice_slots_interleave_within_slice():
    """slots>1 × multi-slice: rank = (slice*wps + ordinal)*slots + local."""
    from mpi_operator_tpu.bootstrap.bootstrap import ENV_LOCAL_RANK

    env = {ENV_COORDINATOR: "c:1", ENV_NUM_PROCESSES: "8",
           "TPU_NUM_SLICES": "2", "TPU_SLICE_ID": "1",
           "TPU_WORKERS_PER_SLICE": "2", "TPU_SLOTS_PER_WORKER": "2",
           ENV_LOCAL_RANK: "1"}
    info = process_info(env=env, hostname="j-worker-s1-1")
    assert info.process_id == (1 * 2 + 1) * 2 + 1    # == 7


def test_slice_id_out_of_range_rejected():
    with pytest.raises(BootstrapError, match="TPU_SLICE_ID=3"):
        process_info(env={ENV_COORDINATOR: "c:1", ENV_NUM_PROCESSES: "4",
                          "TPU_NUM_SLICES": "2", "TPU_SLICE_ID": "3"},
                     hostname="j-worker-s3-0")


def test_hybrid_mesh_from_env_contract():
    """bootstrap.hybrid_mesh builds the dcn×dp mesh straight from the
    controller-injected env — the REAL env contract, no hand-built mesh."""
    from mpi_operator_tpu.bootstrap.bootstrap import hybrid_mesh

    import jax
    n = jax.device_count()
    info = process_info(
        env={ENV_COORDINATOR: "c:1", ENV_NUM_PROCESSES: "1",
             "TPU_NUM_SLICES": "2"},
        hostname="j-worker-s0-0")
    mesh = hybrid_mesh(info)
    assert dict(mesh.shape)["dcn"] == 2
    assert dict(mesh.shape)["dp"] == n // 2


def test_slice_id_from_hostname_fallback():
    """ConfigMap-fallback processes (no slice env) recover the slice id
    from the pod name's group token — defaulting to 0 would collide
    global ranks across slices."""
    env = {ENV_COORDINATOR: "c:1", ENV_NUM_PROCESSES: "4",
           "TPU_NUM_SLICES": "2", "TPU_WORKERS_PER_SLICE": "2"}
    info = process_info(env=env, hostname="job-worker-s1-0")
    assert info.slice_id == 1
    assert info.process_id == 2
    # a multi-slice worker with NO slice identity at all is a hard error
    with pytest.raises(BootstrapError, match="identifies this"):
        process_info(env=env, hostname="job-worker-0")
    # launchers have no slice hostname and must not trip the check
    info = process_info(env={**env, "TPU_LAUNCHER": "1"},
                        hostname="job-launcher-abc12")
    assert info.is_launcher and info.slice_id == 0


def test_empty_slice_id_env_treated_as_unset():
    """TPU_SLICE_ID: "" (a YAML templating artifact) must not crash with
    a raw int() ValueError — it falls back to the hostname token."""
    env = {ENV_COORDINATOR: "c:1", ENV_NUM_PROCESSES: "4",
           "TPU_NUM_SLICES": "2", "TPU_WORKERS_PER_SLICE": "2",
           "TPU_SLICE_ID": ""}
    info = process_info(env=env, hostname="job-worker-s1-1")
    assert info.slice_id == 1 and info.process_id == 3


MULTISLICE_WORKER_SCRIPT = r'''
import json, os, sys
env_file, hostname, port, repo = sys.argv[1:5]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, repo)
from mpi_operator_tpu.bootstrap import initialize
env = dict(os.environ)
env.update(json.load(open(env_file)))
# the pod DNS name is unreachable outside the cluster; the CONTRACT under
# test is the topology resolution, so only the address is overridden
env["TPU_COORDINATOR_ADDRESS"] = "127.0.0.1:" + port
info = initialize(env, hostname=hostname)
expect_slice = int(env["TPU_SLICE_ID"])
assert info.slice_id == expect_slice, (info.slice_id, expect_slice)
assert info.process_id == expect_slice, (info.process_id, expect_slice)
assert jax.process_count() == 2
import jax.numpy as jnp
out = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
    jnp.ones((jax.local_device_count(),)))
assert float(out[0]) == 2.0, float(out[0])
print("slice %d rank %d psum ok" % (info.slice_id, info.process_id),
      flush=True)
'''


def test_multislice_cross_slice_rendezvous_e2e(tmp_path):
    """Two REAL processes — slice-0 worker-0 and slice-1 worker-0 — form
    ONE jax.distributed world from the env the CONTROLLER materialized
    (per-slice StatefulSets, TPU_SLICE_ID, slice-major ranks) and run a
    cross-slice psum. This is the megascale bootstrap contract end to
    end: controller → env → rank derivation → collective fabric (SURVEY
    §7 "Multi-slice (DCN) bootstrap")."""
    import json
    import os
    import socket
    import subprocess
    import sys

    from mpi_operator_tpu.api import new_tpu_job
    from mpi_operator_tpu.cluster import InMemoryAPIServer
    from mpi_operator_tpu.controller import TPUJobController

    api_server = InMemoryAPIServer()
    ctrl = TPUJobController(api_server)
    ctrl.factory.start_all()
    job = new_tpu_job("mse2e", tpus=8, namespace="default")
    job.spec.num_slices = 2
    job.spec.slice_topology = "2x2"
    api_server.create(job)
    ctrl.sync_handler("default/mse2e")

    env_files = {}
    for k in (0, 1):
        sts = api_server.get("StatefulSet", "default", f"mse2e-worker-s{k}")
        env = dict(sts.spec.template.main_container().env)
        # the controller's topology env (TPU_NUM_PROCESSES=2,
        # TPU_WORKERS_PER_SLICE=1 for tpus=8 over 2 slices) is used
        # VERBATIM — only the chip-count gate is dropped (the CPU-sim
        # process sees 1 device, not the allocated 4 chips)
        env.pop("TPU_EXPECTED_CHIPS", None)
        env.pop("TPU_READY_FILE", None)
        p = tmp_path / f"env-s{k}.json"
        p.write_text(json.dumps(env))
        env_files[k] = str(p)

    script = tmp_path / "worker.py"
    script.write_text(MULTISLICE_WORKER_SCRIPT)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    _spawn_and_collect(
        [[sys.executable, str(script), env_files[k],
          f"mse2e-worker-s{k}-0", str(port), repo] for k in (0, 1)],
        [f"slice {k} rank {k} psum ok" for k in (0, 1)])


# ---------------------------------------------------------------------------
# Distributed-init retry (bootstrap._initialize_distributed)
# ---------------------------------------------------------------------------

def _init_info():
    from mpi_operator_tpu.bootstrap.bootstrap import ProcessInfo
    return ProcessInfo(coordinator_address="job-worker-0:8476",
                       num_processes=2, process_id=1)


def test_init_retry_backoff_then_success():
    from mpi_operator_tpu.bootstrap.bootstrap import _initialize_distributed

    calls, sleeps = [], []

    def init_fn():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("failed to connect to coordinator")

    _initialize_distributed(_init_info(), {}, log=lambda s: None,
                            init_fn=init_fn, sleep=sleeps.append)
    assert len(calls) == 3
    assert sleeps == [1.0, 2.0]        # exponential from the 1s default


def test_init_retry_non_retryable_raises_immediately():
    from mpi_operator_tpu.bootstrap.bootstrap import _initialize_distributed

    calls, sleeps = [], []

    def bad_rank():
        calls.append(1)
        raise RuntimeError("process id 3 does not match num_processes 2")

    with pytest.raises(RuntimeError, match="process id"):
        _initialize_distributed(_init_info(), {}, log=lambda s: None,
                                init_fn=bad_rank, sleep=sleeps.append)
    assert len(calls) == 1 and sleeps == []    # no retry on config bugs

    def bad_value():
        raise ValueError("coordinator_address must be host:port")

    with pytest.raises(ValueError):
        _initialize_distributed(_init_info(), {}, log=lambda s: None,
                                init_fn=bad_value, sleep=sleeps.append)
    assert sleeps == []


def test_init_retry_exhaustion_raises_bootstrap_error():
    from mpi_operator_tpu.bootstrap.bootstrap import (
        ENV_INIT_RETRIES, _initialize_distributed)

    calls, sleeps = [], []

    def always_down():
        calls.append(1)
        raise RuntimeError("DEADLINE_EXCEEDED: coordinator unreachable")

    with pytest.raises(BootstrapError, match="after 3 attempt"):
        _initialize_distributed(_init_info(), {ENV_INIT_RETRIES: "3"},
                                log=lambda s: None,
                                init_fn=always_down, sleep=sleeps.append)
    assert len(calls) == 3
    assert sleeps == [1.0, 2.0]        # no sleep after the final attempt


def test_init_retry_delay_coordinator_fault():
    """TPU_FAULT_INJECT=delay-coordinator:K makes the first K attempts
    fail before init_fn even runs — the injectable drill for coordinator-
    late startup."""
    from mpi_operator_tpu.bootstrap.bootstrap import _initialize_distributed

    calls, sleeps = [], []
    env = {"TPU_FAULT_INJECT": "delay-coordinator:2"}
    _initialize_distributed(_init_info(), env, log=lambda s: None,
                            init_fn=lambda: calls.append(1),
                            sleep=sleeps.append)
    assert len(calls) == 1             # attempts 1-2 injected, 3rd real
    assert sleeps == [1.0, 2.0]


# ---------------------------------------------------------------------------
# launcher_wait window-reset proofs (fake clock: LOST -> RESTARTING ->
# contact must FULLY reset both windows)
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def monotonic(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds


def _run_launcher_wait(monkeypatch, responses, default=None, **kw):
    """Drive launcher_wait against a scripted poll_status sequence on a
    fake clock; each poll consumes one response (then `default` forever).
    Returns (exit_code_or_exception, clock, contact_times)."""
    from mpi_operator_tpu.bootstrap import bootstrap as bs

    clock = _FakeClock()
    monkeypatch.setattr(time, "monotonic", clock.monotonic)
    monkeypatch.setattr(time, "sleep", clock.sleep)
    script = list(responses)
    contacts = []

    def fake_poll(host, port, timeout=2.0, token=None):
        status = script.pop(0) if script else default
        if status is not None:
            contacts.append(clock.t)
        return status

    monkeypatch.setattr(bs, "poll_status", fake_poll)
    info = _init_info()
    kw.setdefault("poll_interval", 1.0)
    try:
        return bs.launcher_wait(info, **kw), clock, contacts
    except BootstrapError as exc:
        return exc, clock, contacts


def test_launcher_wait_transient_outages_never_accumulate(monkeypatch):
    """Outages each SHORTER than lost_timeout, repeated well past it in
    total, must never reach RESTARTING/give-up: any contact fully resets
    the loss window."""
    responses = []
    for _ in range(10):                 # 10 x 9s outages = 90s total loss
        responses += ["running"] + [None] * 9
    responses += ["done 0"]
    code, clock, _ = _run_launcher_wait(
        monkeypatch, responses, lost_timeout=10.0, startup_timeout=50.0)
    assert code == 0                    # survived 9x the lost budget


def test_launcher_wait_restarting_contact_resets_windows(monkeypatch):
    """Contact during RESTARTING returns to RUNNING with BOTH windows
    reset: a second total outage must again take the full
    lost_timeout + startup_timeout before the give-up exit."""
    from mpi_operator_tpu.bootstrap.bootstrap import LAUNCHER_LOST_EXIT

    # contact -> outage long enough to reach RESTARTING -> recovery
    # contact -> permanent outage
    responses = ["running"] + [None] * 15 + ["running"]
    code, clock, contacts = _run_launcher_wait(
        monkeypatch, responses, default=None,
        lost_timeout=10.0, startup_timeout=30.0)
    assert code == LAUNCHER_LOST_EXIT
    recovery_t = contacts[-1]
    # after the recovery the launcher owed a FULL fresh budget: 10s to
    # re-enter RESTARTING plus 30s of restart window
    assert clock.t - recovery_t >= 10.0 + 30.0
