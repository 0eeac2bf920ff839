"""Real-cluster backend tests: KubeAPIServer against a wire-level fake.

The reference pins its controller's behavior by asserting recorded client
Actions (mpi_job_controller_test.go:271-311). These tests go one level
deeper for the real-cluster adapter: the full `TPUJobController` runs
against `KubeAPIServer`, which speaks actual HTTP/JSON to an in-process
fake API server — so the asserted bodies are byte-for-byte what a real
cluster would receive (the manifests the reference's Go structs marshal to,
e.g. newWorker mpi_job_controller.go:1004-1083).
"""
import textwrap
import threading
import time

import pytest

from mpi_operator_tpu.api.types import (
    Container,
    ObjectMeta,
    OwnerReference,
    PodTemplateSpec,
    TPUJob,
    TPUJobSpec,
    TPUJobStatus,
    JobCondition,
    ReplicaStatus,
    new_tpu_job,
)
from mpi_operator_tpu.cluster.apiserver import (
    AlreadyExistsError,
    NotFoundError,
)
from mpi_operator_tpu.cluster.kubeclient import (
    KubeAPIServer,
    KubeConfig,
    KubeConfigError,
)
from mpi_operator_tpu.cluster.serialize import (
    from_manifest,
    parse_time,
    rfc3339,
    to_manifest,
)
from mpi_operator_tpu.controller import ControllerConfig, TPUJobController

from fake_kube_apiserver import FakeKubeAPIServer


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture()
def fake_server():
    server = FakeKubeAPIServer().start()
    yield server
    server.stop()


@pytest.fixture()
def kube(fake_server):
    client = KubeAPIServer(KubeConfig(server=fake_server.url),
                           request_timeout=5.0, watch_timeout_seconds=2)
    yield client
    client.stop()


def wait_for(pred, desc: str, timeout: float = 10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(0.02)
    raise TimeoutError(f"timed out waiting for: {desc}")


def sample_job(name="trainjob", **kw) -> TPUJob:
    job = new_tpu_job(name, tpus=8, **kw)
    job.spec.template.main_container().image = "tpu-bench:latest"
    return job


# ---------------------------------------------------------------------------
# serialization round-trips
# ---------------------------------------------------------------------------

class TestSerializeRoundTrip:
    def test_tpujob_full(self):
        job = sample_job()
        job.metadata.labels = {"team": "ml"}
        job.spec.slice_topology = "4x2"
        job.spec.backoff_limit = 3
        job.spec.launcher_on_master = True
        job.spec.template.main_container().env = {"A": "1"}
        job.spec.template.main_container().limits = {"google.com/tpu": 4}
        job.status = TPUJobStatus(
            launcher_status="Active", worker_replicas=2,
            start_time=1700000000.0,
            replica_statuses={"worker": ReplicaStatus(active=2)},
        )
        job.status.set_condition(JobCondition(type="Created", status="True",
                                              reason="TPUJobCreated"))
        back = from_manifest(to_manifest(job))
        assert back.spec == job.spec
        assert back.metadata.labels == {"team": "ml"}
        assert back.status.launcher_status == "Active"
        assert back.status.worker_replicas == 2
        assert back.status.start_time == 1700000000.0
        assert back.status.replica_statuses["worker"].active == 2
        assert back.status.get_condition("Created").reason == "TPUJobCreated"

    def test_children_roundtrip(self):
        """Every child kind the reconciler materializes survives the wire."""
        cfg = ControllerConfig()
        ctl = TPUJobController.__new__(TPUJobController)  # constructors only
        ctl.config = cfg
        job = sample_job()
        job.metadata.uid = "uid-7"
        alloc = ctl.allocate_processing_units(job, False)
        for obj in (
            ctl.new_config_map(job, alloc),
            ctl.new_launcher_service_account(job),
            ctl.new_launcher_role(job, alloc),
            ctl.new_launcher_role_binding(job),
            ctl.new_worker_service(job),
            ctl.new_pdb(job, alloc.worker_replicas),
            ctl.new_worker(job, alloc),
            ctl.new_launcher(job, alloc),
        ):
            back = from_manifest(to_manifest(obj))
            assert back.metadata.name == obj.metadata.name
            assert back.metadata.owner_references == \
                obj.metadata.owner_references
            if hasattr(obj, "spec"):
                assert back.spec == obj.spec
            if obj.kind == "ConfigMap":
                assert back.data == obj.data
            if obj.kind == "Role":
                assert back.rules == obj.rules

    def test_event_and_pod_roundtrip(self):
        """The Event sink kind and the Pod read-path kind survive the wire
        (timestamps quantize to whole seconds — RFC3339 without fractions,
        same as every other kind)."""
        from mpi_operator_tpu.cluster.resources import (
            Event, ObjectReference, Pod, PodStatus)

        ev = Event(
            metadata=ObjectMeta(name="trainjob.1a2b3c", namespace="default"),
            involved_object=ObjectReference(
                kind="TPUJob", namespace="default", name="trainjob",
                uid="uid-7", api_version="tpu.kubeflow.org/v1alpha1"),
            reason="Synced", message="TPUJob synced successfully",
            type="Normal", count=3,
            first_timestamp=1700000000.0, last_timestamp=1700000600.0,
            source_component="tpu-operator")
        back = from_manifest(to_manifest(ev))
        assert back == ev

        pod = Pod(
            metadata=ObjectMeta(name="trainjob-worker-0",
                                namespace="default",
                                labels={"tpu_job_name": "trainjob",
                                        "tpu_job_role": "worker"}),
            status=PodStatus(phase="Running", restart_count=2, exit_code=137))
        back = from_manifest(to_manifest(pod))
        assert back == pod

    def test_time_format(self):
        assert rfc3339(0.0) == "1970-01-01T00:00:00Z"
        assert parse_time("1970-01-01T00:00:00Z") == 0.0
        assert parse_time(rfc3339(1700000000.0)) == 1700000000.0
        assert parse_time("2023-11-14T22:13:20.5Z") == 1700000000.0
        assert parse_time(None) is None


# ---------------------------------------------------------------------------
# kubeconfig loading
# ---------------------------------------------------------------------------

class TestKubeConfig:
    def test_from_kubeconfig_token(self, tmp_path):
        cfg_file = tmp_path / "config"
        cfg_file.write_text(textwrap.dedent("""\
            apiVersion: v1
            kind: Config
            current-context: dev
            contexts:
            - name: dev
              context: {cluster: c1, user: u1}
            clusters:
            - name: c1
              cluster:
                server: https://10.0.0.1:6443
                insecure-skip-tls-verify: true
            users:
            - name: u1
              user: {token: sekrit}
        """))
        cfg = KubeConfig.from_kubeconfig(str(cfg_file))
        assert cfg.server == "https://10.0.0.1:6443"
        assert cfg.token == "sekrit"
        assert cfg.insecure_skip_tls_verify

    def test_load_precedence_master_overrides(self, tmp_path):
        cfg_file = tmp_path / "config"
        cfg_file.write_text(textwrap.dedent("""\
            current-context: dev
            contexts:
            - name: dev
              context: {cluster: c1, user: u1}
            clusters:
            - name: c1
              cluster: {server: "https://a:6443"}
            users:
            - name: u1
              user: {token: t}
        """))
        cfg = KubeConfig.load(kubeconfig=str(cfg_file),
                              master="https://b:6443")
        assert cfg.server == "https://b:6443"
        assert cfg.token == "t"

    def test_in_cluster_outside_cluster_raises(self, monkeypatch):
        monkeypatch.delenv("KUBERNETES_SERVICE_HOST", raising=False)
        with pytest.raises(KubeConfigError):
            KubeConfig.load()


# ---------------------------------------------------------------------------
# CRUD against the wire
# ---------------------------------------------------------------------------

class TestKubeCRUD:
    def test_create_get_roundtrip(self, kube):
        created = kube.create(sample_job())
        assert created.metadata.uid.startswith("uid-")
        assert created.metadata.resource_version == "1"
        got = kube.get("TPUJob", "default", "trainjob")
        assert got.spec.tpus == 8
        assert got.spec.template.main_container().image == "tpu-bench:latest"

    def test_create_duplicate_is_already_exists(self, kube):
        kube.create(sample_job())
        with pytest.raises(AlreadyExistsError):
            kube.create(sample_job())

    def test_get_missing_is_not_found(self, kube):
        with pytest.raises(NotFoundError):
            kube.get("TPUJob", "default", "nope")
        assert kube.try_get("TPUJob", "default", "nope") is None

    def test_update_bumps_resource_version(self, kube):
        created = kube.create(sample_job())
        created.spec.tpus = 16
        updated = kube.update(created)
        assert updated.spec.tpus == 16
        assert updated.metadata.resource_version != \
            created.metadata.resource_version

    def test_update_status_leaves_spec(self, kube, fake_server):
        created = kube.create(sample_job())
        created.spec.tpus = 32          # must NOT be persisted via /status
        created.status.launcher_status = "Active"
        kube.update_status(created)
        got = kube.get("TPUJob", "default", "trainjob")
        assert got.spec.tpus == 8
        assert got.status.launcher_status == "Active"
        paths = [r.path for r in fake_server.requests_of("PUT", "tpujobs")]
        assert paths == [
            "/apis/tpu.kubeflow.org/v1alpha1/namespaces/default/tpujobs"
            "/trainjob/status"]

    def test_plain_update_cannot_change_status(self, kube, fake_server):
        """A real server with the status subresource enabled strips .status
        from plain PUTs — status writes must go through update_status."""
        created = kube.create(sample_job())
        created.status.launcher_status = "Succeeded"   # smuggled in a PUT
        kube.update(created)
        got = kube.get("TPUJob", "default", "trainjob")
        assert got.status.launcher_status is None

    def test_failed_job_enriched_with_pod_exit_code(self, kube, fake_server):
        """The ExitCode restart policy needs the container exit code, which
        batch/v1 JobStatus omits — the adapter reads it from the Job's pods
        (ref v1alpha2 common_types.go:150-155)."""
        from mpi_operator_tpu.cluster.resources import Job as BatchJob
        job = BatchJob(metadata=ObjectMeta(name="tj-launcher",
                                           namespace="default"))
        kube.create(job)
        # play kubelet: a pod of this Job died with exit code 17
        kube._request("POST", "/api/v1/namespaces/default/pods", body={
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "tj-launcher-abc12",
                         "labels": {"job-name": "tj-launcher"}},
            "status": {"containerStatuses": [
                {"name": "tpu", "state": {"terminated": {"exitCode": 17}}}]},
        })
        fake_server.set_status("jobs", "default", "tj-launcher",
                               {"failed": 1})
        got = kube.get("Job", "default", "tj-launcher")
        assert got.status.failed == 1
        assert got.status.exit_code == 17

    def test_delete(self, kube):
        kube.create(sample_job())
        kube.delete("TPUJob", "default", "trainjob")
        with pytest.raises(NotFoundError):
            kube.get("TPUJob", "default", "trainjob")
        with pytest.raises(NotFoundError):
            kube.delete("TPUJob", "default", "trainjob")

    def test_list_namespaced_and_cluster_wide(self, kube):
        kube.create(sample_job("a"))
        kube.create(sample_job("b", namespace="other"))
        assert [j.metadata.name for j in kube.list("TPUJob", "default")] \
            == ["a"]
        assert sorted(j.metadata.name for j in kube.list("TPUJob")) \
            == ["a", "b"]

    def test_admission_applies_client_side(self, kube):
        from mpi_operator_tpu.api.validation import validate_spec
        kube.register_admission_validator(
            "TPUJob", lambda o: validate_spec(o.spec))
        bad = new_tpu_job("bad")          # no sizing mode at all
        from mpi_operator_tpu.cluster.apiserver import ApiError
        with pytest.raises(ApiError):
            kube.create(bad)


# ---------------------------------------------------------------------------
# watch
# ---------------------------------------------------------------------------

class TestKubeWatch:
    def test_watch_sees_lifecycle(self, kube, fake_server):
        events = []
        seen = threading.Event()

        def handler(etype, obj, old):
            events.append((etype, obj.metadata.name,
                           old.metadata.name if old else None))
            seen.set()

        kube.watch("TPUJob", handler, namespace="default")
        kube.create(sample_job())
        wait_for(lambda: ("ADDED", "trainjob", None) in events,
                 "ADDED event")
        job = kube.get("TPUJob", "default", "trainjob")
        job.spec.tpus = 16
        kube.update(job)
        wait_for(lambda: any(e[0] == "MODIFIED" for e in events),
                 "MODIFIED event")
        modified = [e for e in events if e[0] == "MODIFIED"][0]
        assert modified[2] == "trainjob"      # old obj provided from cache
        kube.delete("TPUJob", "default", "trainjob")
        wait_for(lambda: any(e[0] == "DELETED" for e in events),
                 "DELETED event")


# ---------------------------------------------------------------------------
# wire-format pinning: what the operator actually sends a real cluster
# ---------------------------------------------------------------------------

class TestWireFormat:
    """Create one TPUJob through the real controller and pin the exact JSON
    bodies of every child resource (ref newWorker/newLauncher/newConfigMap,
    mpi_job_controller.go:849-1236)."""

    @pytest.fixture()
    def reconciled(self, kube, fake_server):
        controller = TPUJobController(kube, config=ControllerConfig())
        stop = threading.Event()
        controller.run(threadiness=1, stop_event=stop)
        job = sample_job()
        kube.create(job)
        wait_for(lambda: fake_server.get_object(
            "jobs", "default", "trainjob-launcher") is not None
            or fake_server.get_object(
                "statefulsets", "default", "trainjob-worker") is not None,
            "reconcile fan-out")
        wait_for(lambda: fake_server.get_object(
            "statefulsets", "default", "trainjob-worker"), "worker sts")
        yield fake_server
        stop.set()
        controller.queue.shut_down()

    def test_statefulset_manifest(self, reconciled):
        sts = reconciled.get_object("statefulsets", "default",
                                    "trainjob-worker")
        assert sts["apiVersion"] == "apps/v1"
        spec = sts["spec"]
        assert spec["replicas"] == 2                  # tpus=8 / 4 per worker
        assert spec["serviceName"] == "trainjob-worker"
        assert spec["podManagementPolicy"] == "Parallel"
        assert spec["selector"]["matchLabels"] == {
            "tpu_job_name": "trainjob", "tpu_job_role": "worker"}
        tmpl = spec["template"]
        assert tmpl["metadata"]["labels"] == {
            "tpu_job_name": "trainjob", "tpu_job_role": "worker"}
        pod = tmpl["spec"]
        assert pod["restartPolicy"] == "Always"       # ref :1021
        assert pod["nodeSelector"] == {
            "cloud.google.com/gke-tpu-accelerator": "v5litepod"}
        assert pod["volumes"] == [{
            "name": "tpu-job-config",
            "configMap": {"name": "trainjob-config"}}]
        c = pod["containers"][0]
        assert c["image"] == "tpu-bench:latest"
        assert c["resources"]["limits"] == {"google.com/tpu": "4"}
        # TPU-health readiness gate on the wire (SURVEY §7): kubelet must
        # see the exec probe so Ready == "chips enumerated"
        probe = c["readinessProbe"]
        assert probe["exec"]["command"] == [
            "/bin/sh", "-c", "test -f /tmp/tpu-ready"]
        assert probe["failureThreshold"] >= 30
        assert {"name": "tpu-job-config",
                "mountPath": "/etc/tpu"} in c["volumeMounts"]
        env = {e["name"]: e["value"] for e in c["env"]}
        assert env["TPU_WORKER_HOSTNAMES"] == \
            "trainjob-worker-0,trainjob-worker-1"
        assert env["TPU_NUM_PROCESSES"] == "2"
        # ownership: real GC needs a controller ownerReference (ref :876-878)
        owner = sts["metadata"]["ownerReferences"][0]
        assert owner["kind"] == "TPUJob"
        assert owner["controller"] is True
        assert owner["blockOwnerDeletion"] is True
        assert owner["uid"].startswith("uid-")

    def test_configmap_and_rbac_manifests(self, reconciled):
        cm = reconciled.get_object("configmaps", "default", "trainjob-config")
        assert cm["apiVersion"] == "v1"
        assert cm["data"]["worker-hostnames"] == (
            "trainjob-worker-0.trainjob-worker.default.svc\n"
            "trainjob-worker-1.trainjob-worker.default.svc\n")
        assert cm["data"]["coordinator-address"] == (
            "trainjob-worker-0.trainjob-worker.default.svc:8476")
        role = reconciled.get_object("roles", "default", "trainjob-launcher")
        assert role["apiVersion"] == "rbac.authorization.k8s.io/v1"
        names = [n for rule in role["rules"]
                 for n in rule.get("resourceNames", [])]
        assert "trainjob-worker-0" in names          # per-pod least privilege
        rb = reconciled.get_object("rolebindings", "default",
                                   "trainjob-launcher")
        assert rb["roleRef"] == {
            "apiGroup": "rbac.authorization.k8s.io", "kind": "Role",
            "name": "trainjob-launcher"}
        assert rb["subjects"] == [{
            "kind": "ServiceAccount", "name": "trainjob-launcher",
            "namespace": "default"}]

    def test_headless_service_manifest(self, reconciled):
        svc = reconciled.get_object("services", "default", "trainjob-worker")
        assert svc["spec"]["clusterIP"] == "None"
        assert svc["spec"]["selector"]["tpu_job_name"] == "trainjob"
        # pod A-records must exist BEFORE Readiness (the rendezvous and
        # the discovery init wait both run pre-Ready) — without this the
        # TPU-health gate deadlocks against Ready-gated DNS
        assert svc["spec"]["publishNotReadyAddresses"] is True

    def test_synced_event_posted_over_the_wire(self, reconciled):
        """The recorder reaches the real core-v1 Events sink (ref
        StartRecordingToSink, mpi_job_controller.go:165-172; Synced event
        :518): after a reconcile the scripted server must hold a POSTed
        Event manifest with the exact wire fields kubectl consumes."""
        # the fixture returns once the worker StatefulSet exists, but the
        # Synced event is the LAST write of that same sync — wait for it
        synced = wait_for(
            lambda: [e for e in reconciled.objects_of("events")
                     if e.get("reason") == "Synced"],
            "Synced event posted")
        ev = synced[0]
        assert ev["apiVersion"] == "v1"
        assert ev["kind"] == "Event"
        assert ev["type"] == "Normal"
        assert ev["message"] == "TPUJob synced successfully"
        assert ev["source"] == {"component": "tpu-operator"}
        io = ev["involvedObject"]
        assert io["kind"] == "TPUJob"
        assert io["name"] == "trainjob"
        assert io["apiVersion"] == "tpu.kubeflow.org/v1alpha1"
        assert io["uid"]                        # correlatable by kubectl
        assert ev["firstTimestamp"].endswith("Z")
        assert ev["count"] >= 1
        # the Event's name is "<involved>.<hex>" (client-go convention)
        assert ev["metadata"]["name"].startswith("trainjob.")


# ---------------------------------------------------------------------------
# full lifecycle over the wire (SURVEY §3.3 end-to-end)
# ---------------------------------------------------------------------------

class TestCLIRealClusterMode:
    def test_main_runs_controller_against_kubeconfig(self, fake_server,
                                                     tmp_path):
        """`python -m mpi_operator_tpu --kube-config X` constructs the real
        controller path (ref cmd/mpi-operator/main.go:42-96)."""
        from mpi_operator_tpu.__main__ import main
        cfg_file = tmp_path / "kubeconfig"
        cfg_file.write_text(textwrap.dedent(f"""\
            current-context: test
            contexts:
            - name: test
              context: {{cluster: fake, user: u}}
            clusters:
            - name: fake
              cluster: {{server: "{fake_server.url}"}}
            users:
            - name: u
              user: {{}}
        """))
        # seed a job; the controller must reconcile it after startup sync
        kube = KubeAPIServer(KubeConfig(server=fake_server.url))
        kube.create(sample_job())

        stop = threading.Event()
        result = {}
        t = threading.Thread(
            target=lambda: result.setdefault("rc", main(
                ["--kube-config", str(cfg_file)], stop_event=stop)),
            daemon=True)
        t.start()
        try:
            wait_for(lambda: fake_server.get_object(
                "statefulsets", "default", "trainjob-worker"),
                "reconcile from CLI-constructed controller")
        finally:
            stop.set()
            t.join(timeout=10)
        assert result.get("rc") == 0

    def test_main_bad_kubeconfig_errors(self, tmp_path, capsys):
        from mpi_operator_tpu.__main__ import main
        rc = main(["--kube-config", str(tmp_path / "missing")],
                  stop_event=threading.Event())
        assert rc == 2


class TestRealClusterLifecycle:
    def test_job_runs_to_completion(self, kube, fake_server):
        controller = TPUJobController(kube, config=ControllerConfig())
        stop = threading.Event()
        controller.run(threadiness=1, stop_event=stop)
        try:
            kube.create(sample_job())
            wait_for(lambda: fake_server.get_object(
                "statefulsets", "default", "trainjob-worker"), "worker sts")
            # play kubelet: all workers become ready
            fake_server.set_status("statefulsets", "default",
                                   "trainjob-worker",
                                   {"readyReplicas": 2, "replicas": 2})
            wait_for(lambda: fake_server.get_object(
                "jobs", "default", "trainjob-launcher"),
                "launcher gated on readiness")
            # play kubelet: launcher completes
            fake_server.set_status(
                "jobs", "default", "trainjob-launcher",
                {"succeeded": 1,
                 "completionTime": "2026-01-01T00:00:00Z"})
            done = wait_for(
                lambda: (kube.get("TPUJob", "default", "trainjob")
                         .status.is_done()) or None,
                "TPUJob Succeeded")
            assert done
            job = kube.get("TPUJob", "default", "trainjob")
            assert job.status.launcher_status == "Succeeded"
            wait_for(lambda: fake_server.get_object(
                "statefulsets", "default",
                "trainjob-worker")["spec"]["replicas"] == 0,
                "workers scaled down")
        finally:
            stop.set()
            controller.queue.shut_down()
