"""The LongCat cell's kind, reference, readers and tool on the CPU, at toy
widths, through `run.py` untouched: a root in a temporary directory whose
files stand beside a link to the repository's `perfbench/`."""
import json

import numpy as np
import pytest

from _perfbench_tiny import REPO, _dump, _load, make_root
from perfbench import harness, run, trace_reduce
from perfbench.manifest import Manifest
from perfbench.readers import allreduce_exposed, scope_device_share

CELL = "tiny-longcat"
MOE = ("moe_held_assignments_per_step", "moe_identity_pick_share_pct",
       "moe_expert_load_max_over_mean_pct")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("perfbench_longcat"))
    cfg = _load(REPO, "perfbench", "configs", "longcat-flash-1of32.json")
    cfg.update(vocab_size=128, hidden_size=96, ffn_hidden_size=192,
               expert_ffn_hidden_size=32, num_layers=2,
               num_attention_heads=4, kv_lora_rank=8, q_lora_rank=24,
               qk_rope_head_dim=4, v_head_dim=8, qk_nope_head_dim=8,
               n_routed_experts=4, zero_expert_num=32, moe_topk=6)
    cfg["assumed"].update(n_routed_experts_published=64, held_first_expert=8)
    _dump(cfg, root, "extra", "configs", "longcat-tiny.json")
    t = _load(REPO, "perfbench", "traffic", "reason-long-closed.json")
    t["engine"].update(slots=8, page_size=16, num_pages=60,
                       chunk_buckets=[8, 32], decode_kernel=False)
    t.update(clients=8, backlog=600,
             prompt={"dist": "lognormal", "median": 16, "sigma": 0.4,
                     "min": 8, "max": 32},
             output={"dist": "lognormal", "median": 24, "sigma": 0.3,
                     "min": 12, "max": 48},
             max_total=96, first_wave_min_output=4, trace_start_s=0.1,
             trace_seconds=0.3, check_requests=6,
             limits={"served_logit_gap_widest": 0.03,
                     "served_logprob_gap_widest": 0.03})
    _dump(t, root, "extra", "traffic", "tiny-long-closed.json")
    bench = _load(root, "BENCHMARK.json")
    real = _load(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "longcat-tiny", "source": "none",
                             "file": "extra/configs/longcat-tiny.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "longcat-tiny",
                               "traffic": "tiny-long-closed", "chips": 1,
                               "why": "toy"})
    names = {m["name"] for m in bench["per_layer"]}
    for section in ("end_to_end", "per_layer"):
        for m in real[section]:
            if "serve-longcat-1of32-reason-long" not in m.get("workloads",
                                                              []):
                continue
            if m["name"] in names or section == "end_to_end":
                next(x for x in bench[section]
                     if x["name"] == m["name"])["workloads"].append(CELL)
            else:
                bench[section].append({**m, "workloads": [CELL]})
    _dump(bench, root, "BENCHMARK.json")
    return root


def test_the_cell_is_in_the_benchmark_with_the_issues_parameters():
    m = Manifest(REPO)
    cell = m.cell("serve-longcat-1of32-reason-long")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-1of32", "reason-long-closed", 1)
    t = m.traffic(cell["traffic"])
    e = t["engine"]
    assert (t["kind"], t["clients"], t["backlog"], t["max_total"]) == (
        "serve_closed_longcat", 64, 128, 6400)
    assert (e["slots"], e["page_size"], e["num_pages"], e["chunk_buckets"],
            e["async_decode"], e["decode_kernel"]) == (
        64, 64, 4480, [32, 128], True, True)
    assert t["prompt"] == {"dist": "lognormal", "median": 128, "sigma": 0.45,
                           "min": 64, "max": 256}
    assert t["output"] == {"dist": "lognormal", "median": 4096,
                           "sigma": 0.25, "min": 3072, "max": 6144}
    assert t["first_wave_min_output"] == 16 and t["check_requests"] == 16
    dp4 = m.cell("train-gpt2m-dp4")
    assert (dp4["config"], dp4["traffic"], dp4["chips"]) == (
        "gpt2-medium", "pretrain-seq1024-dp4", 4)
    # the four-chip cells stay within the quarter the contract allows,
    # however many cells later PRs have added
    cells = m.data["workloads"]
    assert 1 <= sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


def test_the_configuration_keeps_every_published_width():
    cfg = Manifest(REPO).config("longcat-flash-1of32")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(json.loads(ln) for ln in f
                     if json.loads(ln)["name"] == "LongCat-Flash-Chat")
    differs = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_layers",
                                              "n_routed_experts",
                                              "vocab_size"}
    assert cfg["reduced_from"] == {k: entry["config"][k] for k in differs}
    assert cfg["source"] == entry["source_url"]


def test_the_longcat_cell_runs_and_is_correct(root):
    result = run.run_cell(root, CELL, 2**31 + 5, 0.8, False,
                          require_tpu=False)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8


def test_a_traced_run_reports_the_routing_counters(root):
    result = run.run_cell(root, CELL, 7, 0.8, True, require_tpu=False)
    got = result["metrics"]
    assert set(MOE) <= set(got) and "slot_occupancy_pct" in got
    # 8 rows x 6 picks x 4 held of 96 outputs = 2 a layer where the router
    # is even; identity 32 of 96
    assert 0.3 < got["moe_held_assignments_per_step"]["value"] < 8
    assert 15 < got["moe_identity_pick_share_pct"]["value"] < 60
    assert got["moe_expert_load_max_over_mean_pct"]["value"] >= 100
    # no device ran here: the readers of the device trace find nothing
    for name in ("mla_decode_roofline", "mla_device_share_pct",
                 "moe_device_share_pct", "decode_device_ms_p50"):
        assert name not in got


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    from mpi_operator_tpu.serve import engine as engine_mod
    real = engine_mod.sample_slots

    def off_by_one(logits, *a, **kw):
        tok, logp = real(logits, *a, **kw)
        return (tok + 1) % logits.shape[-1], logp
    monkeypatch.setattr(engine_mod, "sample_slots", off_by_one)
    result = run.run_cell(root, CELL, 3, 0.6, False, require_tpu=False)
    assert result["correct"] is False


def test_an_expert_layer_that_drops_the_bias_is_not_correct(
        root, monkeypatch):
    """The comparison holds the program to the routing: picks by `p`
    alone (the score-correction bias dropped) move the logits past the
    limits."""
    import jax.numpy as jnp
    from mpi_operator_tpu.parallel import held_experts
    real = held_experts.route
    monkeypatch.setattr(
        held_experts, "route",
        lambda logits, bias, k, scale: real(logits, jnp.zeros_like(bias), k,
                                            scale))
    t = Manifest(root).traffic("tiny-long-closed")
    # a bias that decides picks at this toy's scores
    cfg = _load(root, "extra", "configs", "longcat-tiny.json")
    cfg["assumed"]["router_bias_std"] = 0.02
    _dump(cfg, root, "extra", "configs", "longcat-tiny.json")
    try:
        result = run.run_cell(root, CELL, 11, 0.6, False, require_tpu=False)
    finally:
        cfg["assumed"]["router_bias_std"] = 0.0005
        _dump(cfg, root, "extra", "configs", "longcat-tiny.json")
    assert t["limits"]["served_logit_gap_widest"] == 0.03
    assert result["correct"] is False


def test_the_control_tool_reads_sound_and_control_gaps(root, capsys):
    from perfbench.tools import control_serve_longcat
    rc = control_serve_longcat.main([
        "--workload", CELL, "--seeds", "1", "2", "--control", "fp8",
        "--control-seeds", "1", "--window-s", "0.5", "--root", root,
        "--cpu"])
    assert rc == 0
    out = capsys.readouterr().out.splitlines()
    rows = [json.loads(ln) for ln in out if ln.startswith("{")]
    assert [r["seed"] for r in rows] == [1, 2]
    assert rows[0]["control_logit_gap"] > 4 * rows[0]["served_logit_gap"]
    assert "control_logit_gap" not in rows[1]
    assert any(ln.startswith("served_logit_gap_widest: sound max")
               for ln in out)


# -- the readers, on traces made by hand ------------------------------------

def _device(index, ops, modules):
    return trace_reduce.DeviceTrace(index, trace_reduce.Events.build(ops),
                                    trace_reduce.Events.build(modules))


def _summary(devices, window):
    return trace_reduce.TraceSummary(devices, trace_reduce.Events.build([]),
                                     window)


def test_scope_device_share_adds_up_a_steps_time_by_scope():
    mods = [("jit_step_paged", 100.0, 100.0), ("jit_step_paged", 300.0, 100.0),
            ("jit_prefill_paged", 500.0, 50.0)]
    ops = [("fusion", 100.0, 40.0), ("mla.attend", 140.0, 20.0),
           ("fusion", 160.0, 40.0), ("fusion", 300.0, 40.0),
           ("mla.attend", 340.0, 20.0), ("fusion", 360.0, 40.0),
           ("fusion", 500.0, 50.0)]
    raw = (["fusion.1", "mla.attend.3", "fusion.2", "fusion.1",
            "mla.attend.3", "fusion.2", "fusion.9"],
           np.array([r[1] for r in ops]), np.array([r[2] for r in ops]))
    scopes = {"fusion.1": "jit(step_paged)/layer_0/moe/moe.experts/dot",
              "fusion.2": "jit(step_paged)/layer_0/attn_0/mla.project/dot",
              "fusion.9": "jit(prefill_paged)/layer_0/moe/moe.experts/dot"}
    ev = harness.Evidence(
        trace=_summary([_device(0, ops, mods)], (0.0, 1000.0)),
        shapes={"device_ops_raw": raw, "op_scopes": scopes})
    spec = {"module_pattern": "step_paged"}
    share = lambda pattern: scope_device_share.read(       # noqa: E731
        {**spec, "scope_pattern": pattern}, ev)
    assert share(r"moe\.") == pytest.approx(40.0)
    assert share(r"mla\.") == pytest.approx(60.0)   # the kernel by its name
    # a program that offers no map, or a run without a trace: nothing
    ev.shapes["op_scopes"] = {}
    assert share(r"moe\.") is None
    assert scope_device_share.read(
        {**spec, "scope_pattern": "x"}, harness.Evidence()) is None


def test_allreduce_exposed_counts_collective_time_nothing_else_covers():
    """Two chips, two whole steps each (a third is cut by the window): a
    synchronous all-reduce, an asynchronous pair whose wait is the done,
    and a collective that a fusion runs beside."""
    def chip(index, wait):
        mods = [("jit_step_fn", 0.0, 1000.0), ("jit_step_fn", 1000.0, 1000.0),
                ("jit_step_fn", 2000.0, 1000.0)]
        ops = []
        for base in (0.0, 1000.0, 2000.0):
            ops += [("fusion", base, 300.0),
                    ("all-reduce-start", base + 300.0, 10.0),
                    ("fusion", base + 310.0, 200.0),
                    ("all-reduce-done", base + 510.0, wait),
                    ("all-reduce", base + 700.0, 50.0),
                    ("all-gather", base + 800.0, 100.0),
                    ("fusion", base + 820.0, 60.0)]
        return _device(index, ops, mods)
    ev = harness.Evidence(trace=_summary([chip(0, 100.0), chip(1, 140.0)],
                                         (-1.0, 2500.0)))
    spec = _load(REPO, "perfbench", "layer_metrics",
                 "allreduce_exposed_ms_per_step.json")
    # a step of chip 0: 10 + 100 + 50 + (100 - 60) = 200 ns; chip 1: 240
    assert allreduce_exposed.read(spec, ev) == pytest.approx(220e-6)
    one = harness.Evidence(trace=_summary(
        [_device(0, [("fusion", 0.0, 900.0)],
                 [("jit_step_fn", 0.0, 1000.0)])], (-1.0, 2000.0)))
    assert allreduce_exposed.read(spec, one) == 0.0
    assert allreduce_exposed.read(spec, harness.Evidence()) is None
