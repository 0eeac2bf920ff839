"""Per-request distributed tracing tests (telemetry/trace.py and its
serving/federation/postmortem integrations).

The contracts pinned here, in dependency order: the sampled-out path
allocates nothing (head sampling is one hash + compare, deterministic
across processes); hop chains are contiguous by construction so hop
durations sum to the root's end-to-end seconds; a failover replay joins
the ONE existing root instead of opening a second; the fsync'd sink
tolerates torn tails like every other event log; TraceFederation
re-ingests idempotently and feeds slowest-trace exemplars to the
autoscaler, whose breach decisions the postmortem pairs with rendered
hop trees ("exemplar pending" when the trace was sampled out).
"""
import json

import pytest

from mpi_operator_tpu.telemetry.trace import (
    REQUEST_ROOT, SESSION_ROOT, SPAN, TRACE_HOP_BUCKETS, Tracer,
    _mix64, build_trees, hop_name, hop_percentiles, hop_spans,
    orphan_spans, read_trace_spans, render_tree, trace_sum_gap,
)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_deterministic_across_tracers():
    a, b = Tracer(sample=0.5), Tracer(sample=0.5)
    kept = [i for i in range(200) if a.sampled(i)]
    assert kept == [i for i in range(200) if b.sampled(i)]
    # rate=0.5 keeps roughly half — the hash is uniform enough that a
    # 200-id draw can't collapse to nothing or everything
    assert 50 < len(kept) < 150


def test_sampled_out_allocates_nothing():
    t = Tracer(sample=0.0)
    assert t.begin_request(123, 0.0) is None
    # the off-path pin: no RequestTrace, no registry entry, no record
    assert t.open_requests() == []
    assert len(t.ring) == 0
    # sample=1.0 never consults the hash
    assert Tracer(sample=1.0).sampled(123)


def test_force_sample_overrides_rate():
    t = Tracer(sample=0.0)
    t.force_sample(7)
    rt = t.begin_request(7, 0.0)
    assert rt is not None
    rt.finish("ok", 1.0)
    assert len(t.ring) == 1 and t.ring[0]["trace"] == 7


def test_mix64_is_stable():
    # the splitmix64 finalizer must never drift: every pod keeps the
    # SAME id subset or cross-pod trees stop reconstructing
    assert _mix64(0) == 0
    assert _mix64(1) == _mix64(1)
    assert _mix64(1) != _mix64(2)


# ---------------------------------------------------------------------------
# hop chains
# ---------------------------------------------------------------------------

def test_hops_are_contiguous_and_sum_to_root():
    t = Tracer(sample=1.0)
    rt = t.begin_request(1, 10.0, replica=0)
    rt.begin_hop("router.queue_wait", 10.0)
    rt.begin_hop("serve.admission", 10.5)
    rt.begin_hop("serve.prefill", 10.6)
    rt.begin_hop("serve.decode", 11.0)
    rt.finish("ok", 12.0)
    tree = build_trees(t.ring)[1]
    assert tree["root"]["name"] == REQUEST_ROOT
    assert tree["root"]["status"] == "ok"
    assert tree["root"]["seconds"] == pytest.approx(2.0)
    hops = [s for s in tree["spans"] if s["parent"] is not None]
    assert [hop_name(s) for s in hops] == [
        "queue_wait", "admission", "prefill", "decode"]
    # contiguity: each hop starts where the previous ended
    for prev, nxt in zip(hops, hops[1:]):
        assert prev["t0"] + prev["seconds"] == pytest.approx(nxt["t0"])
    assert trace_sum_gap(tree) == pytest.approx(0.0, abs=1e-6)


def test_hop_attrs_land_on_open_hop():
    t = Tracer(sample=1.0)
    rt = t.begin_request(1, 0.0)
    rt.begin_hop("serve.kv_handoff", 0.0)
    rt.hop_attrs(pages=3, cached_pages=1)
    rt.begin_hop("serve.decode", 0.5)
    rt.finish("ok", 1.0)
    hop = next(s for s in t.ring if s["name"] == "serve.kv_handoff")
    assert hop["attrs"] == {"pages": 3, "cached_pages": 1}


def test_failover_replay_joins_the_one_root():
    t = Tracer(sample=1.0)
    rt = t.begin_request(5, 0.0)
    rt.begin_hop("serve.admission", 0.0)
    # replica dies: the open hop closes as a failover casualty, the
    # root stays open for the replay
    rt.abandon(0.4)
    rt.event("failover", replica=0)
    again = t.begin_request(5, 99.0)       # fresh Request, SAME id
    assert again is rt
    again.begin_hop("router.queue_wait", 0.4)
    again.begin_hop("serve.decode", 0.7)
    again.finish("ok", 1.0)
    tree = build_trees(t.ring)[5]
    roots = [s for s in tree["spans"] if s["parent"] is None]
    assert len(roots) == 1
    assert roots[0]["events"] == [{"name": "failover", "replica": 0}]
    statuses = [s["status"] for s in tree["spans"]
                if s["parent"] is not None]
    assert statuses.count("failover") == 1
    # the replay reopened at the abandon instant: still gap-free
    assert trace_sum_gap(tree) == pytest.approx(0.0, abs=1e-6)
    assert t.open_requests() == []


def test_finish_is_idempotent():
    t = Tracer(sample=1.0)
    rt = t.begin_request(1, 0.0)
    rt.finish("timeout", 2.0)
    rt.finish("ok", 3.0)                   # loses: first terminal wins
    roots = [s for s in t.ring if s["parent"] is None]
    assert len(roots) == 1 and roots[0]["status"] == "timeout"


def test_session_spans_parent_batch_children():
    t = Tracer(sample=1.0)
    ss = t.begin_session(0.0, replica=1)
    assert ss.trace < 0                    # never collides with request ids
    ss.child("serve.decode_step", 0.1, 0.05, batch=4)
    ss.end(1.0)
    tree = build_trees(t.ring)[ss.trace]
    assert tree["root"]["name"] == SESSION_ROOT
    kids = [s for s in tree["spans"] if s["parent"] is not None]
    assert kids[0]["name"] == "serve.decode_step"
    assert kids[0]["attrs"] == {"batch": 4}
    # session spans are NOT request hops
    assert hop_spans(t.ring) == []


# ---------------------------------------------------------------------------
# sink + analysis
# ---------------------------------------------------------------------------

def test_sink_survives_torn_tail(tmp_path):
    path = str(tmp_path / "traces.jsonl")
    with Tracer(path=path, sample=1.0) as t:
        rt = t.begin_request(1, 0.0)
        rt.begin_hop("serve.decode", 0.0)
        rt.finish("ok", 1.0)
    with open(path, "a") as f:
        f.write('{"event": "span", "trace": 9, "span"')   # torn write
    spans = read_trace_spans(path)
    assert [s["trace"] for s in spans] == [1, 1]
    assert all(s["event"] == SPAN for s in spans)


def test_build_trees_dedups_and_finds_orphans():
    root = {"trace": 1, "span": 1, "parent": None, "name": REQUEST_ROOT,
            "t0": 0.0, "seconds": 1.0, "status": "ok"}
    hop = {"trace": 1, "span": 2, "parent": 1, "name": "serve.decode",
           "t0": 0.0, "seconds": 1.0, "status": "ok"}
    stray = {"trace": 2, "span": 3, "parent": 99, "name": "serve.decode",
             "t0": 0.0, "seconds": 0.5, "status": "ok"}
    # the same records twice — a re-read / re-ingest — keeps one copy
    trees = build_trees([root, hop, stray, root, hop])
    assert len(trees[1]["spans"]) == 2
    assert orphan_spans([root, hop, stray]) == [stray]
    assert trace_sum_gap(trees[2]) is None    # rootless: no verdict


def test_hop_percentiles_shape():
    spans = []
    for i, secs in enumerate([0.001, 0.002, 0.004, 0.1]):
        spans.append({"trace": i, "span": 2 * i + 1, "parent": 2 * i,
                      "name": "serve.decode", "t0": 0.0,
                      "seconds": secs, "status": "ok"})
    out = hop_percentiles(spans)
    assert set(out) == {"decode_p50_ms", "decode_p99_ms"}
    assert out["decode_p50_ms"] <= out["decode_p99_ms"]
    assert out["decode_p99_ms"] == pytest.approx(100.0)


def test_render_tree_lines():
    t = Tracer(sample=1.0)
    rt = t.begin_request(1, 0.0)
    rt.event("shed", reason="no capacity")
    rt.begin_hop("serve.kv_handoff", 0.0)
    rt.hop_attrs(pages=2)
    rt.finish("timeout", 0.5)
    lines = render_tree(build_trees(t.ring)[1])
    assert lines[0].startswith("serve.request 500.0ms status=timeout")
    assert any(line.strip().startswith("@ shed") for line in lines)
    assert any("pages=2" in line for line in lines)


# ---------------------------------------------------------------------------
# engine integration (the real serving path)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from mpi_operator_tpu.models import CausalLM, gpt2_config
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=64)
    model = CausalLM(cfg)
    probe = jnp.zeros((1, 8), jnp.int32)
    params = meta.unbox(
        model.init(jax.random.PRNGKey(0), probe))["params"]
    return model, params


def _requests(n=3):
    from mpi_operator_tpu.serve import Request
    return [Request(i, [(7 * i + j) % 60 + 1 for j in range(6)], 3)
            for i in range(n)]


@pytest.mark.serving
def test_engine_traces_sum_to_e2e(small_model):
    from mpi_operator_tpu.serve import EngineConfig, ServingEngine
    model, params = small_model
    tracer = Tracer(sample=1.0)
    eng = ServingEngine(model, params,
                        EngineConfig(slots=2, chunk_buckets=(8,)),
                        tracer=tracer)
    results = eng.run(_requests())
    assert tracer.open_requests() == []
    assert orphan_spans(tracer.ring) == []
    trees = build_trees(tracer.ring)
    for rid in results:
        tree = trees[rid]
        assert tree["root"]["status"] == "ok"
        names = [hop_name(s) for s in tree["spans"]
                 if s["parent"] is not None]
        assert names[0] == "admission" and names[-1] == "decode"
        assert trace_sum_gap(tree) <= max(
            0.005, 0.02 * tree["root"]["seconds"])
    # the engine session root parents the batch-level decode steps
    sessions = [s for s in tracer.ring if s["trace"] < 0]
    assert any(s["name"] == "serve.decode_step" for s in sessions)
    assert any(s["name"] == SESSION_ROOT for s in sessions)


@pytest.mark.serving
def test_tracing_never_changes_tokens_or_pins(small_model):
    from mpi_operator_tpu.serve import EngineConfig, ServingEngine
    model, params = small_model
    cfg = EngineConfig(slots=2, chunk_buckets=(8,))
    plain = ServingEngine(model, params, cfg)
    traced = ServingEngine(model, params, cfg, tracer=Tracer(sample=1.0))
    want = {rid: r.tokens for rid, r in plain.run(_requests()).items()}
    got = {rid: r.tokens for rid, r in traced.run(_requests()).items()}
    assert got == want                      # greedy: bitwise identical
    assert traced.compile_counts() == plain.compile_counts()


@pytest.mark.serving
def test_disagg_handoff_hop_carries_pages(small_model):
    from mpi_operator_tpu.serve import DisaggEngine, EngineConfig
    model, params = small_model
    tracer = Tracer(sample=1.0)
    eng = DisaggEngine(
        model, params,
        EngineConfig(slots=2, chunk_buckets=(8,), page_size=8, num_pages=32),
        tracer=tracer)
    results = eng.run(_requests(2))
    trees = build_trees(tracer.ring)
    pages = 0
    for rid in results:
        names = [hop_name(s) for s in trees[rid]["spans"]
                 if s["parent"] is not None]
        assert "prefill" in names and "kv_handoff" in names \
            and "decode" in names
        for s in trees[rid]["spans"]:
            if s["parent"] is not None and hop_name(s) == "kv_handoff":
                pages += s["attrs"]["pages"]
    assert pages > 0                        # the handoff actually moved KV


# ---------------------------------------------------------------------------
# federation
# ---------------------------------------------------------------------------

def _span_batch(trace, seconds, ts=1000.0):
    return [
        {"event": SPAN, "ts": ts, "trace": trace, "span": 2 * trace,
         "parent": None, "name": REQUEST_ROOT, "t0": 0.0,
         "seconds": seconds, "status": "ok"},
        {"event": SPAN, "ts": ts, "trace": trace, "span": 2 * trace + 1,
         "parent": 2 * trace, "name": "serve.decode", "t0": 0.0,
         "seconds": seconds, "status": "ok"},
    ]


def test_federation_ingest_is_idempotent():
    from mpi_operator_tpu.telemetry.collector import TraceFederation
    fed = TraceFederation("j", clock=lambda: 1000.0)
    batch = _span_batch(1, 0.25)
    assert fed.ingest("pod-0", batch) == 2
    assert fed.ingest("pod-0", batch) == 0       # re-scrape: no-op
    assert fed.hops["decode"]["count"] == 1
    # the SAME span ids from another pod are distinct evidence (a
    # cross-pod tree's pieces arrive from different files)
    assert fed.ingest("pod-1", batch) == 2
    tree = fed.tree(1)
    assert tree["root"] is not None and len(tree["spans"]) == 2


def test_federation_offset_corrects_wall_ts_only():
    from mpi_operator_tpu.telemetry.collector import TraceFederation
    fed = TraceFederation("j", clock=lambda: 1000.0)
    fed.ingest("pod-0", _span_batch(1, 0.25, ts=990.0), offset=10.0)
    span = fed.spans[1][0]
    assert span["ts"] == pytest.approx(1000.0)
    assert span["ts_raw"] == pytest.approx(990.0)
    assert span["seconds"] == pytest.approx(0.25)   # durations untouched


def test_federation_exemplars_slowest_first():
    from mpi_operator_tpu.telemetry.collector import TraceFederation
    fed = TraceFederation("j", clock=lambda: 1000.0)
    for trace, secs in [(1, 0.1), (2, 0.9), (3, 0.4)]:
        fed.ingest("pod-0", _span_batch(trace, secs))
    assert fed.slowest_trace() == 2
    assert [t for _s, t in fed.exemplars()] == [2, 3, 1]
    # outside the window the pool drains
    late = TraceFederation("j", clock=lambda: 5000.0, window=600.0)
    late.ingest("pod-0", _span_batch(4, 1.0, ts=1000.0))
    assert late.slowest_trace() is None


def test_federation_histogram_lines():
    from mpi_operator_tpu.telemetry.collector import TraceFederation
    fed = TraceFederation("j", clock=lambda: 1000.0)
    fed.ingest("pod-0", _span_batch(1, 0.003))
    lines = fed.render_lines()
    text = "\n".join(lines)
    assert '# TYPE tpu_job_trace_hop_seconds histogram' in text
    assert 'tpu_job_trace_hop_seconds_count{job="j",hop="decode"} 1' \
        in text
    # cumulative buckets: every edge >= 0.005 counts the 3ms decode
    assert 'le="0.005"} 1' in text and 'le="0.001"} 0' in text
    assert len([ln for ln in lines if "_bucket" in ln]) \
        == len(TRACE_HOP_BUCKETS) + 1


def test_observatory_push_ingests_like_a_scrape():
    from mpi_operator_tpu.telemetry.collector import JobObservatory
    now = [100.0]
    obs = JobObservatory(clock=lambda: now[0])
    payload = {
        "now": 100.0,
        "metrics": ("# TYPE tpu_worker_tokens_total counter\n"
                    "tpu_worker_tokens_total 5\n"),
        "events": [{"ts": 99.0, "event": "serve_started"}],
        "traces": _span_batch(3, 0.7, ts=99.5),
    }
    assert obs.ingest_push("job", 0, payload, serving=True)
    view = obs.view("job")
    assert view["federation"].observed_tokens() == 5
    assert obs.slowest_trace("job") == 3
    assert view["worker_records"]["push-0"][0]["event"] == "serve_started"
    # the push advanced the serving progress lease exactly like a scrape
    assert view["progress_step"] == 5 and view["progress_ts"] == 100.0
    # federated render carries the trace histograms
    assert any("tpu_job_trace_hop_seconds" in ln
               for ln in view["traces"].render_lines())


def test_observatory_push_rides_the_fault_injector():
    from mpi_operator_tpu.telemetry.chaos import ScrapeFaultInjector
    from mpi_operator_tpu.telemetry.collector import JobObservatory
    obs = JobObservatory(clock=lambda: 100.0,
                         scrape_injector=ScrapeFaultInjector(["*/fail=1"]))
    ok = obs.ingest_push("job", 0, {"now": 100.0, "metrics": ""})
    assert not ok                            # the injected fault dropped it
    view = obs.view("job")
    assert view["federation"].pods[0]["failures"] == 1
    assert obs.scrape_injector.fault_count("fail") == 1


# ---------------------------------------------------------------------------
# autoscaler exemplar threading + postmortem pairing
# ---------------------------------------------------------------------------

def test_breach_decision_carries_exemplar():
    from mpi_operator_tpu.api.types import ServingSLO
    from mpi_operator_tpu.controller.autoscale import (
        DecodeAutoscaler, SLOObservation)
    slo = ServingSLO(ttft_p99_seconds=0.5, breach_seconds=10.0,
                     cooldown_floor_seconds=0.0)
    scaler = DecodeAutoscaler(slo)
    bad = SLOObservation(ttft_p99=2.0, exemplar_trace=42)
    assert scaler.decide(0.0, bad, 1, None, None).target is None
    d = scaler.decide(20.0, bad, 1, None, None)
    assert d.target == 2 and d.exemplar_trace == 42
    # a hold decision never exemplifies
    calm = SLOObservation(ttft_p99=0.1, exemplar_trace=42)
    assert scaler.decide(30.0, calm, 2, None, None).exemplar_trace is None


def test_postmortem_renders_exemplar_tree_or_pending(tmp_path):
    import io

    from mpi_operator_tpu.postmortem import render, summarize
    records = [
        {"ts": 0.0, "event": "job_created", "job": "j"},
        {"ts": 5.0, "event": "autoscale_breach", "job": "j", "target": 2,
         "reason": "ttft_p99 2 > 0.5", "exemplar_trace": 7},
        # sampled out: the breach recorded no trace id
        {"ts": 9.0, "event": "request_timeout", "job": "j", "request": 3},
    ]
    summary = summarize(records)
    assert [b["trace"] for b in summary["slo_breaches"]] == [7, None]

    tracer = Tracer(sample=1.0)
    rt = tracer.begin_request(7, 0.0)
    rt.begin_hop("serve.decode", 0.0)
    rt.finish("ok", 1.5)
    trees = build_trees(tracer.ring)

    out = io.StringIO()
    render(summary, out, trees=trees)
    text = out.getvalue()
    assert "slow traces:" in text
    assert "serve.request 1500.0ms" in text        # exemplar hop tree
    assert "exemplar pending (no trace id attached" in text
    # with no trace file at all, the breach with an id degrades to the
    # other pending message instead of crashing
    out2 = io.StringIO()
    render(summary, out2, trees={})
    assert "exemplar pending (trace 7 not in the trace file" \
        in out2.getvalue()


def test_postmortem_cli_reads_trace_file(tmp_path):
    import subprocess
    import sys

    timeline = tmp_path / "timeline.jsonl"
    with open(timeline, "w") as f:
        for rec in [
            {"ts": 0.0, "event": "job_created", "job": "j"},
            {"ts": 5.0, "event": "autoscale_breach", "job": "j",
             "reason": "ttft", "exemplar_trace": 7},
            {"ts": 9.0, "event": "job_succeeded", "job": "j"},
        ]:
            f.write(json.dumps(rec) + "\n")
    traces = tmp_path / "traces.jsonl"
    with Tracer(path=str(traces), sample=1.0) as t:
        rt = t.begin_request(7, 0.0)
        rt.begin_hop("serve.decode", 0.0)
        rt.finish("ok", 0.25)
    proc = subprocess.run(
        [sys.executable, "-m", "mpi_operator_tpu.postmortem",
         str(timeline), "--traces", str(traces)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "slow traces:" in proc.stdout
    assert "serve.decode" in proc.stdout
