"""Worker/launcher process bootstrap — replaces the reference's entire
rsh-agent machinery with environment-driven `jax.distributed` initialization.

Reference flow (SURVEY §2.4): mpirun on the launcher reads a hostfile and
forks `kubexec.sh <pod> orted ...` per worker through the Kubernetes exec
API (reference pkg/controllers/mpi_job_controller.go:849-885, :1123-1131),
requiring a kubectl-delivery init container and per-job pods/exec RBAC.

TPU-native flow: every worker pod runs its own process from the pod command.
At startup the process calls `initialize()` below, which
  1. reads the env the controller injected (TPU_COORDINATOR_ADDRESS,
     TPU_NUM_PROCESSES, TPU_WORKER_HOSTNAMES — controller.py
     _discovery_env), falling back to the ConfigMap mount at /etc/tpu;
  2. derives its process id from the StatefulSet pod hostname's trailing
     ordinal (`<job>-worker-<i>`), the stable identity the controller
     guarantees (reference StatefulSet ServiceName, :1079);
  3. calls `jax.distributed.initialize(coordinator, num_processes, id)` —
     after which XLA owns all collective transport over ICI/DCN.

No kubectl, no exec, no rsh. The launcher (TPU_LAUNCHER=1) participates as
the coordinator host or runs launcher-only logic (monitoring, completion).
"""
from __future__ import annotations

import os
import re
import socket
from dataclasses import dataclass
from typing import Mapping, Optional

# env names match controller.py:_discovery_env
ENV_COORDINATOR = "TPU_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "TPU_NUM_PROCESSES"
ENV_WORKER_HOSTNAMES = "TPU_WORKER_HOSTNAMES"
ENV_WORKER_ID = "TPU_WORKER_ID"            # explicit override only
ENV_SLOTS = "TPU_SLOTS_PER_WORKER"
ENV_LOCAL_RANK = "TPU_LOCAL_RANK"          # set by bootstrap.launch for slots>1
ENV_CONFIG_PATH = "TPU_CONFIG_PATH"
ENV_LAUNCHER = "TPU_LAUNCHER"
ENV_NUM_SLICES = "TPU_NUM_SLICES"
# multi-slice (controller injects per worker GROUP, i.e. per StatefulSet):
# the pod hostname ordinal is slice-LOCAL; the global rank folds in the
# slice id — global worker index = slice_id * workers_per_slice + ordinal
ENV_SLICE_ID = "TPU_SLICE_ID"
ENV_WORKERS_PER_SLICE = "TPU_WORKERS_PER_SLICE"
# TPU-health readiness gate (SURVEY §7 "Readiness vs ICI formation"):
# when the controller injects TPU_READY_FILE, the worker writes the marker
# only after the accelerator runtime proved usable (device_check), and the
# injected readinessProbe checks the file — so the pod's Ready (and hence
# the launcher gate, ref mpi_job_controller.go:503-509) means "chips
# enumerate", not just "container started".
ENV_READY_FILE = "TPU_READY_FILE"
ENV_EXPECTED_CHIPS = "TPU_EXPECTED_CHIPS"
READY_FILE_DEFAULT = "/tmp/tpu-ready"

#: rank-0 serves job status here for the launcher's completion poll
STATUS_PORT = 8477
# launcher gave up on an unreachable rank-0 (infra loss, NOT a workload
# failure); chosen in the 128-255 "retryable" band of the reference's
# v1alpha2 exit-code policy (ref common_types.go:150-155)
LAUNCHER_LOST_EXIT = 213

# Bounded exponential-backoff retry around jax.distributed.initialize:
# the coordinator pod being seconds late is the COMMON case at gang
# start (StatefulSet pods come up in any order), and a single un-retried
# connect would turn that race into a crash-loop.
ENV_INIT_RETRIES = "TPU_INIT_RETRIES"      # attempts, default 5
ENV_INIT_BACKOFF = "TPU_INIT_BACKOFF"      # base delay seconds, default 1.0
_INIT_BACKOFF_CAP = 30.0

_ORDINAL_RE = re.compile(r"-(\d+)$")
_SLICE_RE = re.compile(r"-s(\d+)-\d+$")   # <job>-worker-s<k>-<i>


class BootstrapError(RuntimeError):
    pass


@dataclass(frozen=True)
class ProcessInfo:
    """Everything jax.distributed.initialize needs, plus topology context."""
    coordinator_address: str
    num_processes: int
    process_id: int
    slots_per_worker: int = 1
    num_slices: int = 1
    slice_id: int = 0
    workers_per_slice: int = 0     # 0 = single-slice (all workers)
    is_launcher: bool = False

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def resolve_worker_ordinal(hostname: str) -> int:
    """`<job>-worker-<i>` → i. The hostfile-analogue rank derivation."""
    m = _ORDINAL_RE.search(hostname)
    if m is None:
        raise BootstrapError(
            f"hostname {hostname!r} carries no trailing ordinal; expected a "
            f"StatefulSet pod name like 'job-worker-3'")
    return int(m.group(1))


def _read_config_dir(path: str) -> dict:
    """Fallback discovery from the ConfigMap mount (controller.new_config_map
    keys), for processes exec'd without the env (debug shells)."""
    data = {}
    if not os.path.isdir(path):
        return data
    for key in ("coordinator-address", "num-processes", "slots-per-worker",
                "num-slices", "workers-per-slice"):
        p = os.path.join(path, key)
        if os.path.exists(p):
            with open(p) as f:
                data[key] = f.read().strip()
    return data


def process_info(
    env: Optional[Mapping[str, str]] = None,
    hostname: Optional[str] = None,
) -> ProcessInfo:
    """Pure resolution (no jax import) — unit-testable."""
    env = dict(os.environ if env is None else env)
    cfg = _read_config_dir(env.get(ENV_CONFIG_PATH, "/etc/tpu"))

    coordinator = env.get(ENV_COORDINATOR) or cfg.get("coordinator-address")
    if not coordinator:
        raise BootstrapError(
            f"{ENV_COORDINATOR} not set and no ConfigMap fallback — was this "
            f"process started by the TPUJob controller?")
    num_processes = int(
        env.get(ENV_NUM_PROCESSES) or cfg.get("num-processes") or 1)
    slots = int(env.get(ENV_SLOTS) or cfg.get("slots-per-worker") or 1)
    num_slices = int(env.get(ENV_NUM_SLICES) or cfg.get("num-slices") or 1)
    is_launcher = env.get(ENV_LAUNCHER) == "1"
    if env.get(ENV_SLICE_ID):        # empty string = unset (YAML artifact)
        slice_id = int(env[ENV_SLICE_ID])
    elif (num_slices > 1 and not is_launcher
          and ENV_WORKER_ID not in env):
        # ConfigMap-fallback processes (debug shells) have no slice env;
        # the slice id is recoverable from the pod name's group token
        # (`<job>-worker-s<k>-<i>`). Defaulting to 0 would collide global
        # ranks across slices and hang the rendezvous. Launchers and
        # explicit-TPU_WORKER_ID processes don't derive from hostnames.
        m = _SLICE_RE.search(hostname or socket.gethostname())
        if m is None:
            raise BootstrapError(
                f"numSlices={num_slices} but neither {ENV_SLICE_ID} nor a "
                f"slice-group hostname (…-s<k>-<i>) identifies this "
                f"process's slice")
        slice_id = int(m.group(1))
    else:
        slice_id = 0
    workers_per_slice = int(
        env.get(ENV_WORKERS_PER_SLICE) or cfg.get("workers-per-slice") or 0)
    if num_slices > 1 and workers_per_slice == 0:
        # derivable: ranks divide evenly over slices (admission enforces it)
        workers_per_slice = num_processes // (slots * num_slices)
    if slice_id >= max(num_slices, 1):
        raise BootstrapError(
            f"{ENV_SLICE_ID}={slice_id} >= num_slices {num_slices}")

    if ENV_WORKER_ID in env:
        pid = int(env[ENV_WORKER_ID])
    elif is_launcher or num_processes == 1:
        # The launcher is NOT a rank (see initialize()); pid 0 here is only
        # its bookkeeping view. Single-process jobs are rank 0 by definition
        # — no ordinal-bearing hostname needed (dev boxes, notebooks).
        pid = 0
    else:
        # Multi-slice: the StatefulSet ordinal is slice-LOCAL (pod
        # `<job>-worker-s<k>-<i>` → i); fold in the slice id so global
        # worker indexes are slice-major — exactly the order the
        # controller publishes worker-hostnames in (the hostfile-analogue
        # topology truth, ref mpi_job_controller.go:857-869).
        ordinal = resolve_worker_ordinal(hostname or socket.gethostname())
        if num_slices > 1:
            ordinal = slice_id * workers_per_slice + ordinal
        # slots>1: bootstrap.launch forks `slots` local processes per worker
        # (the orted replacement) and tags each with TPU_LOCAL_RANK; the
        # global rank interleaves exactly like the reference hostfile's
        # `slots=` lines (ref mpi_job_controller.go:857-869).
        local_rank = int(env.get(ENV_LOCAL_RANK, 0))
        if local_rank >= slots:
            raise BootstrapError(
                f"{ENV_LOCAL_RANK}={local_rank} >= slots_per_worker {slots}")
        pid = ordinal * slots + local_rank
        if pid >= num_processes:
            raise BootstrapError(
                f"derived rank {pid} (worker {ordinal}, local {local_rank}) "
                f">= num_processes {num_processes}")
    return ProcessInfo(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=pid,
        slots_per_worker=slots,
        num_slices=num_slices,
        slice_id=slice_id,
        workers_per_slice=workers_per_slice,
        is_launcher=is_launcher,
    )


def hybrid_mesh(info: Optional[ProcessInfo] = None, **axes):
    """The job's device mesh straight from the bootstrap topology: the
    `dcn` axis gets num_slices (so cross-slice collectives ride DCN
    hierarchically, parallel/mesh.make_mesh), the remaining devices spread
    over the given axes — default pure data-parallel, the reference's sole
    strategy. This is the env-contract path: controller env → process_info
    → mesh, no hand-built topology."""
    import jax

    from ..parallel.mesh import MeshConfig, make_mesh

    info = info if info is not None else process_info()
    n = jax.device_count()
    if axes:
        cfg = MeshConfig(dcn=info.num_slices, **axes)
        if cfg.num_devices != n:
            raise BootstrapError(
                f"mesh axes {axes} x num_slices {info.num_slices} = "
                f"{cfg.num_devices} devices, but the job sees {n}")
    else:
        cfg = MeshConfig.data_parallel(n, num_slices=info.num_slices)
    return make_mesh(cfg)


def device_check(expected_chips: Optional[int] = None) -> int:
    """Prove the accelerator runtime is usable from THIS process: enumerate
    local devices and (optionally) verify the chip count matches what the
    controller allocated. Raises BootstrapError with an actionable message
    otherwise. Runs in the worker process — the one that rightfully owns
    the TPU — never in a probe sidecar (libtpu is single-owner; a probe
    that touched the runtime would steal the training process's lock)."""
    import jax

    try:
        devices = jax.local_devices()
    except Exception as exc:  # noqa: BLE001 — runtime init failures vary
        raise BootstrapError(
            f"accelerator runtime failed to initialize: {exc}") from exc
    n = len(devices)
    if n == 0:
        raise BootstrapError(
            "accelerator runtime reports ZERO local devices — the TPU "
            "runtime is sick or the pod is missing its google.com/tpu "
            "resource limit")
    if expected_chips and n != expected_chips:
        raise BootstrapError(
            f"accelerator runtime enumerates {n} local device(s) but the "
            f"controller allocated {expected_chips} chips to this worker "
            f"— partial slice, check node health")
    return n


def mark_ready(path: Optional[str] = None) -> Optional[str]:
    """Write the readiness marker the injected probe checks. No-op (None)
    when no path is configured — dev/test processes outside the operator
    don't leave marker litter."""
    path = path or os.environ.get(ENV_READY_FILE)
    if not path:
        return None
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write("ok\n")
    os.replace(tmp, path)      # atomic: the probe never sees a torn write
    return path


def _retryable_init_error(exc: BaseException) -> bool:
    """Classify a jax.distributed.initialize failure: coordinator-not-yet-
    listening (grpc connect/deadline errors) is retryable; an identity
    mismatch (wrong rank, wrong gang size, double init) is NOT — retrying
    a misconfiguration just hides the config bug behind a timeout."""
    if isinstance(exc, ValueError):
        return False
    msg = str(exc).lower()
    fatal = ("process id", "process_id", "num_processes", "mismatch",
             "already initialized", "duplicate", "invalid")
    return not any(marker in msg for marker in fatal)


def _initialize_distributed(info: ProcessInfo,
                            env: Mapping[str, str],
                            log=print,
                            init_fn=None,
                            sleep=None,
                            events=None) -> None:
    """jax.distributed.initialize with bounded exponential backoff.
    TPU_INIT_RETRIES attempts (default 5), TPU_INIT_BACKOFF base delay
    doubling per attempt (default 1s, capped at 30s). A non-retryable
    failure (see _retryable_init_error) raises immediately; exhausting
    the budget raises BootstrapError. `init_fn`/`sleep` are injectable
    for tests. Honors the delay-coordinator fault (TPU_FAULT_INJECT) so
    the retry machinery itself is testable end-to-end. `events` (a
    telemetry EventLog) records one init_retry record per failed
    attempt — each is fsync'd before the backoff sleep, so the log shows
    a flapping coordinator even when a later attempt succeeds."""
    import time as _time

    if init_fn is None:
        import jax

        def init_fn():
            jax.distributed.initialize(
                coordinator_address=info.coordinator_address,
                num_processes=info.num_processes,
                process_id=info.process_id,
            )
    sleep = sleep if sleep is not None else _time.sleep
    attempts = max(1, int(env.get(ENV_INIT_RETRIES) or 5))
    backoff = float(env.get(ENV_INIT_BACKOFF) or 1.0)
    faults = None
    if env.get("TPU_FAULT_INJECT"):
        # deferred import: resilience lives train-side and pulls jax; only
        # fault-injected runs (tests, drills) pay for it here
        from ..train.resilience import FaultInjector
        faults = FaultInjector.from_env(env)
    last: Optional[BaseException] = None
    for attempt in range(attempts):
        try:
            if faults is not None and faults.fail_init_attempt():
                raise RuntimeError(
                    "fault-inject: coordinator not yet listening "
                    "(delay-coordinator)")
            init_fn()
            return
        except Exception as exc:  # noqa: BLE001 — classified below
            last = exc
            if not _retryable_init_error(exc):
                raise
            if attempt == attempts - 1:
                break
            delay = min(backoff * (2 ** attempt), _INIT_BACKOFF_CAP)
            log(f"jax.distributed.initialize attempt "
                f"{attempt + 1}/{attempts} failed ({exc}); retrying in "
                f"{delay:.1f}s")
            if events is not None:
                from ..telemetry import events as ev
                events.emit(ev.INIT_RETRY, attempt=attempt + 1,
                            attempts=attempts, error=str(exc),
                            backoff_seconds=delay,
                            process_id=info.process_id)
            sleep(delay)
    raise BootstrapError(
        f"jax.distributed.initialize failed after {attempts} attempt(s): "
        f"{last}") from last


def initialize(env: Optional[Mapping[str, str]] = None,
               hostname: Optional[str] = None,
               events=None) -> ProcessInfo:
    """Resolve + `jax.distributed.initialize`.

    `events` (an optional telemetry EventLog) captures init_retry records
    from the distributed-init backoff loop — open it BEFORE calling so
    gang-start flapping is durable even if the process never gets past
    bootstrap.

    The LAUNCHER never joins the process group: it has no TPUs and rank 0
    lives on worker-0 (whose hostname the coordinator address points at).
    Like `mpirun` in the reference, the launcher is only the completion
    signal — it observes rank-0's status channel (`launcher_wait`) and exits
    with the job's code so the batch Job's success/failure semantics carry
    over unchanged (ref SURVEY §7 "launcher Job as thin coordinator").

    Single-process jobs (num_processes == 1) also skip distributed init —
    single-host JAX needs none, keeping dev/test flows zero-config.
    """
    info = process_info(env, hostname)
    resolved_env = dict(os.environ if env is None else env)
    if events is not None and not info.is_launcher:
        # clock anchor for the controller-side timeline merge: a fresh
        # boot_id marks a new process incarnation, so the collector
        # (re)pins this host's clock offset exactly once per boot —
        # emitted FIRST so even a bootstrap that never converges leaves
        # the anchor a postmortem needs to place its init_retry records
        import uuid
        from ..telemetry import events as ev
        events.emit(ev.CLOCK_ANCHOR, boot_id=uuid.uuid4().hex[:12],
                    process_id=info.process_id,
                    num_processes=info.num_processes)
    if not info.is_launcher and info.num_processes > 1:
        _initialize_distributed(info, resolved_env, events=events)
    gated = (ENV_READY_FILE in resolved_env
             or ENV_EXPECTED_CHIPS in resolved_env)
    if not info.is_launcher and (gated or info.num_processes > 1):
        # TPU-health readiness gate: only after the runtime proves its
        # chips enumerate does the pod's readinessProbe start passing —
        # a Ready worker set then implies ICI can form, so the gated
        # launcher (ref :503-509) never starts against sick chips and the
        # first collective can't hang until activeDeadlineSeconds.
        # (Single-process runs outside the operator skip it — they keep
        # their zero-config, zero-jax-import bootstrap.)
        expected = int(resolved_env.get(ENV_EXPECTED_CHIPS, 0) or 0)
        device_check(expected_chips=expected or None)
        # only the RESOLVED env decides the marker path — mark_ready's
        # os.environ fallback must not resurrect a gate this call's
        # explicit `env` deliberately omitted
        ready_path = resolved_env.get(ENV_READY_FILE)
        if ready_path:
            mark_ready(ready_path)
    return info


# ---------------------------------------------------------------------------
# Completion channel: rank-0 status server ←poll— launcher
# ---------------------------------------------------------------------------
# Replaces the completion semantics mpirun gave the reference for free (all
# ranks are mpirun's children; it exits when they do — SURVEY §3.3). Here
# ranks are independent pods, so rank-0 exposes a one-line TCP status
# ("running" | "done <exitcode>") and the launcher polls it.
#
# Handshake: the poller's first line is the job token (the TPUJob uid,
# injected by the controller as TPU_JOB_TOKEN into launcher AND workers).
# A mismatching or missing token gets "denied" and does NOT count as the
# launcher having observed completion — a stray cluster connection can't
# consume the done-linger and race the real launcher out of its exit code.

ENV_JOB_TOKEN = "TPU_JOB_TOKEN"


class StatusServer:
    """Tiny TCP status endpoint served by rank-0 next to training."""

    def __init__(self, port: int = STATUS_PORT, token: Optional[str] = None):
        import threading

        self.token = (token if token is not None
                      else os.environ.get(ENV_JOB_TOKEN, ""))
        self._state = "running"
        self._lock = threading.Lock()
        self._served_done = threading.Event()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("", port))
        self._sock.listen(8)
        self.port = self._sock.getsockname()[1]
        self._thread = threading.Thread(
            target=self._serve, name="tpu-status", daemon=True)
        self._thread.start()

    def _authorized(self, conn) -> bool:
        if not self.token:
            return True          # tokenless dev mode: accept everyone
        try:
            conn.settimeout(2.0)
            # errors="replace": binary garbage (TLS probes, port scanners)
            # must compare unequal, not blow up the serving thread
            line = conn.makefile("rb").readline().decode(
                errors="replace").strip()
            return line == self.token
        except OSError:
            return False

    def _serve(self) -> None:
        while True:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            # nothing a single connection does may kill the serving thread
            # or leak its fd — rank-0 going "unreachable" here triggers a
            # spurious gang restart
            try:
                authorized = self._authorized(conn)
                with self._lock:
                    state = self._state if authorized else "denied"
                conn.sendall(state.encode() + b"\n")
                if authorized and state.startswith("done"):
                    self._served_done.set()
            except Exception:  # noqa: BLE001 — stray-client hardening
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def set_done(self, exit_code: int, linger: float = 10.0) -> None:
        """Mark done and give the launcher a chance to observe it before the
        process exits: returns once a poller has read the done state or
        `linger` elapsed."""
        with self._lock:
            self._state = f"done {exit_code}"
        self._served_done.wait(timeout=linger)

    def close(self) -> None:
        # shutdown first: it wakes the serving thread out of accept(), and
        # a listening socket closed under a blocked accept() stays bound
        # for as long as the process lives
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass


def poll_status(host: str, port: int = STATUS_PORT,
                timeout: float = 2.0,
                token: Optional[str] = None) -> Optional[str]:
    """One status read; None if unreachable. Sends the job-token handshake
    line first (empty token line for tokenless dev servers)."""
    if token is None:
        token = os.environ.get(ENV_JOB_TOKEN, "")
    try:
        with socket.create_connection((host, port), timeout=timeout) as conn:
            conn.sendall(token.encode() + b"\n")
            return conn.makefile().readline().strip()
    except OSError:
        return None


def launcher_wait(info: ProcessInfo, port: int = STATUS_PORT,
                  poll_interval: float = 2.0,
                  startup_timeout: float = 600.0,
                  lost_timeout: float = 120.0,
                  token: Optional[str] = None) -> int:
    """Block until rank-0 reports completion; return its exit code.

    Explicit state machine:

      STARTING ──contact──▶ RUNNING ──outage──▶ LOST ──lost_timeout──▶
      RESTARTING ──fresh startup_timeout expires──▶ LAUNCHER_LOST_EXIT

    STARTING: before first contact, wait up to `startup_timeout` (workers
    are already Ready — the controller gates the launcher on that — so
    rank-0's server appears as soon as its process starts); expiry raises
    BootstrapError. RUNNING: normal polling. LOST: the server went
    unreachable — the worker pod restarted mid-run (kubelet restarts
    workers, ref RestartPolicy Always, mpi_job_controller.go:1021); brief
    outages under `lost_timeout` are tolerated. RESTARTING: the outage
    outlived `lost_timeout`, so treat it as a pod reschedule and allow a
    FRESH `startup_timeout` window for the new pod to come up. ANY
    successful contact returns to RUNNING and fully resets both windows —
    repeated transient outages never accumulate toward the give-up
    deadline. Give-up exit is LAUNCHER_LOST_EXIT (128-255 retryable band)
    so operators can tell infra loss from application failure; job-level
    activeDeadlineSeconds (ref :1221-1222) remains the global stop."""
    import time as _time

    host = info.coordinator_address.split(":")[0]
    state = "STARTING"
    window_expiry = _time.monotonic() + startup_timeout
    while True:
        status = poll_status(host, port, timeout=poll_interval, token=token)
        now = _time.monotonic()
        if status is not None and status.startswith("done"):
            parts = status.split()
            return int(parts[1]) if len(parts) > 1 else 0
        if status is not None:
            # contact (running/denied both prove liveness) → RUNNING, reset
            state = "RUNNING"
        elif state == "STARTING":
            if now > window_expiry:
                raise BootstrapError(
                    f"rank-0 status channel {host}:{port} unreachable for "
                    f"{startup_timeout}s")
        elif state == "RUNNING":
            state = "LOST"
            window_expiry = now + lost_timeout
        elif state == "LOST":
            if now > window_expiry:
                state = "RESTARTING"
                window_expiry = now + startup_timeout
        elif state == "RESTARTING":
            if now > window_expiry:
                return LAUNCHER_LOST_EXIT
        _time.sleep(poll_interval)


__all__ = [
    "BootstrapError", "ProcessInfo", "initialize", "process_info",
    "resolve_worker_ordinal", "device_check", "mark_ready", "hybrid_mesh",
    "ENV_COORDINATOR", "ENV_NUM_PROCESSES", "ENV_WORKER_HOSTNAMES",
    "ENV_WORKER_ID", "ENV_SLOTS", "ENV_CONFIG_PATH", "ENV_LAUNCHER",
    "ENV_NUM_SLICES", "ENV_JOB_TOKEN", "ENV_READY_FILE",
    "ENV_EXPECTED_CHIPS", "READY_FILE_DEFAULT",
    "ENV_SLICE_ID", "ENV_WORKERS_PER_SLICE",
    "StatusServer", "poll_status", "launcher_wait",
    "STATUS_PORT", "LAUNCHER_LOST_EXIT",
    "ENV_INIT_RETRIES", "ENV_INIT_BACKOFF",
]
