"""The DeepSeek-V2 cell's kind, generator, reference, ops count and tool on
the CPU, at toy widths, through `run.py` untouched: a root in a temporary
directory whose files stand beside a link to the repository's
`perfbench/`. Nothing here counts the benchmark's cells."""
import json
import types

import numpy as np
import pytest

from _perfbench_tiny import GENERIC, REPO, _dump, _load, make_root
from perfbench import run
from perfbench import weights_deepseekv2 as weights
from perfbench.kinds import _serve_deepseekv2
from perfbench.manifest import Manifest
from perfbench.ops import mla_paged_decode

CELL = "tiny-deepseekv2"
REAL = "serve-deepseekv2-1of8-longdoc"
OWN = ("dsv2_mla_decode_roofline", "dsv2_mla_device_share_pct",
       "dsv2_moe_device_share_pct", "dsv2_shared_expert_device_share_pct",
       "dsv2_moe_held_assignments_per_step", "dsv2_moe_group_hit_share_pct")
TOY = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
           moe_intermediate_size=32, num_hidden_layers=3, n_layer=3,
           num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
           kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
           v_head_dim=8, n_routed_experts=4)


def toy_config():
    cfg = _load(REPO, "perfbench", "configs", "deepseek-v2-1of8.json")
    cfg.update(TOY)
    # 32 router outputs in 8 groups of 4; this chip holds group 1
    cfg["assumed"] = {**cfg["assumed"], "n_routed_experts_published": 32,
                      "held_first_expert": 4}
    cfg["rope_scaling"] = {**cfg["rope_scaling"],
                           "original_max_position_embeddings": 32}
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("perfbench_deepseekv2"))
    _dump(toy_config(), root, "extra", "configs", "deepseekv2-tiny.json")
    t = _load(REPO, "perfbench", "traffic", "longdoc-deep-closed.json")
    t["engine"].update(slots=8, page_size=4, num_pages=200,
                       chunk_buckets=[8], decode_kernel=False)
    length = lambda median, lo, hi: {"dist": "lognormal",     # noqa: E731
                                     "median": median, "sigma": 0.4,
                                     "min": lo, "max": hi}
    t.update(clients=8, backlog=400, max_total=96,
             first_wave={"context": length(20, 8, 40),
                         "remaining": length(16, 4, 40)},
             prompt=length(6, 3, 8), output=length(30, 16, 60),
             trace_start_s=0.1, trace_seconds=0.3, check_requests=6,
             # bfloat16 program against the float32 reference at toy
             # widths; the altered-token test below reads 1 and more
             limits={"served_logprob_gap_median": 0.01,
                     "served_logprob_gap_p99": 0.05,
                     "served_logit_gap_p99": 0.05})
    _dump(t, root, "extra", "traffic", "tiny-longdoc-closed.json")
    bench = _load(root, "BENCHMARK.json")
    real = _load(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "deepseekv2-tiny", "source": "none",
                             "file": "extra/configs/deepseekv2-tiny.json",
                             "reduced": ["num_hidden_layers"], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "deepseekv2-tiny",
                               "traffic": "tiny-longdoc-closed", "chips": 1,
                               "why": "toy"})
    for section in ("end_to_end", "per_layer"):
        for m in real[section]:
            if REAL in m.get("workloads", []):
                mine = [x for x in bench[section] if x["name"] == m["name"]]
                if mine:
                    mine[0]["workloads"].append(CELL)
                else:
                    bench[section].append({**m, "workloads": [CELL]})
    _dump(bench, root, "BENCHMARK.json")
    return root


def test_the_cell_is_in_the_benchmark_with_the_issues_parameters():
    m = Manifest(REPO)
    cell = m.cell(REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2-1of8", "longdoc-deep-closed", 1)
    assert len(cell["why"]) <= 200 and "2.4" in cell["why"] \
        and "19.2" in cell["why"] and "11%" in cell["why"]
    t = m.traffic(cell["traffic"])
    e = t["engine"]
    assert (t["kind"], t["clients"], t["backlog"], t["max_total"]) == (
        "serve_closed_deepseekv2", 64, 128, 16384)
    assert (e["slots"], e["page_size"], e["chunk_buckets"], e["prefix_cache"],
            e["async_decode"], e["decode_kernel"], e["weights_dtype"]) == (
        64, 64, [128], True, True, True, "bfloat16")
    first = t["first_wave"]
    assert first["context"] == {"dist": "lognormal", "median": 5120,
                                "sigma": 0.5, "min": 2048, "max": 9216}
    # the issue lets the builder move `remaining.median` alone
    assert {k: first["remaining"][k] for k in ("dist", "sigma", "min",
                                               "max")} == {
        "dist": "lognormal", "sigma": 0.4, "min": 1536, "max": 7168}
    assert t["prompt"] == {"dist": "lognormal", "median": 96, "sigma": 0.35,
                           "min": 32, "max": 128}
    assert t["output"] == {"dist": "lognormal", "median": 6144,
                           "sigma": 0.25, "min": 4096, "max": 8192}
    assert (t["check_requests"], t["first_wave_limit_s"]) == (6, 900)
    assert "placement" in t and "ticks" in t["placement_why"]
    assert "fp8" in t["limits_set_from"]
    own = [x for x in m.data["per_layer"] if x.get("workloads") == [REAL]]
    assert tuple(x["name"] for x in own) == OWN
    assert all(x["moves"] == "serve_tokens_per_s" for x in own)
    assert all(x["layer"] == m.layer_metric(x["name"])["layer"] for x in own)
    # the cell joins the twelve generic serving and set-up metrics; it
    # holds no state a slot, and the per-head roofline is not its kernel's
    lists = {x["name"] for x in m.data["per_layer"]
             if REAL in x.get("workloads", []) and x not in own}
    assert GENERIC <= lists
    assert not lists & {"slot_state_bytes_per_row", "paged_decode_roofline",
                        "mla_decode_roofline"}
    assert REAL in next(x for x in m.data["end_to_end"]
                        if x["name"] == "serve_tokens_per_s")["workloads"]
    # its entries are there, the six metrics together and in their
    # order, wherever later PRs' entries have come to stand
    assert [c["name"] for c in m.data["configs"]].count(
        "deepseek-v2-1of8") == 1
    names = [x["name"] for x in m.data["per_layer"]]
    at = names.index(OWN[0])
    assert names[at:at + len(OWN)] == list(OWN)


def test_the_configuration_holds_every_published_key_and_cuts_three():
    cfg = Manifest(REPO).config("deepseek-v2-1of8")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(json.loads(ln) for ln in f if json.loads(ln)["name"]
                     == "DeepSeek-V2")
    differs = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_layer"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 5, 20, 12800)
    assert cfg["reduced_from"] == {"num_hidden_layers": 60,
                                   "n_routed_experts": 160,
                                   "vocab_size": 102400}
    assert cfg["source"] == entry["source_url"]
    assert "10 pipeline stages" in cfg["deployment"] \
        and "2.4 assignments" in cfg["deployment"]
    assert cfg["assumed"]["serves_max_total"] == 16384
    d = weights.Dims.from_config(cfg)
    assert (d.held, d.experts_published, d.n_group, d.topk_group, d.top_k,
            d.dense_layers, d.heads) == ((0, 20), 160, 8, 3, 6, 1, 128)
    # the issue's count: 337.97 M the dense layer, 669.1 M an expert
    # layer, 131.07 M the table and the head: 3 145 M, 6.29 GB in bfloat16
    assert round(d.param_count() / 1e6) == 3145
    assert round(2 * d.param_count() / 1e7) == 629


def test_a_gate_the_reference_does_not_write_down_is_refused():
    cfg = toy_config()
    for key, other in (("topk_method", "noaux_tc"),
                       ("scoring_func", "sigmoid"),
                       ("norm_topk_prob", True)):
        with pytest.raises(ValueError, match="does not write down"):
            weights.Dims.from_config({**cfg, key: other})


def test_every_seed_serves_the_same_lengths_in_the_same_places():
    t = Manifest(REPO).traffic("longdoc-deep-closed")
    lengths = lambda reqs: [(len(r.prompt), r.max_new_tokens)  # noqa: E731
                            for r in reqs]
    a = _serve_deepseekv2.deep_closed_loop(t, 1, 1000)
    b = _serve_deepseekv2.deep_closed_loop(t, 2**31 + 7, 1000)
    assert lengths(a[0]) == lengths(b[0]) and lengths(a[1]) == lengths(b[1])
    assert a[0][0].prompt != b[0][0].prompt
    first, backlog = a
    assert (len(first), len(backlog)) == (64, 128)
    assert all(2048 <= p <= 9216 and 1536 <= n <= 7168 and p + n <= 16384
               for p, n in lengths(first))
    assert all(32 <= p <= 128 and 4096 <= n <= 8192
               for p, n in lengths(backlog))
    # a replacement is ONE [64, 128] call; the first wave 72 of them
    assert max(p for p, _ in lengths(backlog)) - 1 <= 128
    assert -(-(max(p for p, _ in lengths(first)) - 1) // 128) == 72
    # every reservation fits the pool at every moment of a loop that runs
    # a token a tick, however long it runs, with under 5% to spare
    need = lambda p, n: (p - 2 + n) // 64 + 1                 # noqa: E731
    live = sorted((n, need(p, n)) for p, n in lengths(first))
    held = peak = sum(pages for _, pages in live)
    for p, n in lengths(backlog):
        done, pages = live.pop(0)
        held += need(p, n) - pages
        peak = max(peak, held)
        live.append((done + 1 + n, need(p, n)))
        live.sort()
    pool = t["engine"]["num_pages"] - 1
    assert peak <= pool < 1.05 * peak
    # the pool beside the weights: over 60% of the chip's 16 GB
    assert 0.6 * 16e9 < 6.29e9 + t["engine"]["num_pages"] * 409600 < 0.75 * 16e9


def test_the_ops_count_at_128_heads_sits_on_the_chips_ridge():
    # a cached token and layer: 2 x 128 x (576 + 512) products for 1 152 B
    ops, moved = mla_paged_decode.ops_and_bytes(
        tokens_in_pages=1000, rows=0, heads=128, kv_rank=512, rope=64,
        sublayers=5)
    assert ops == 5 * 2 * 128 * (576 + 512) * 1000
    assert moved == 5 * 1000 * 576 * 2
    assert 241 < ops / moved < 243
    # against the chip's 197e12 / 819e9 = 240.5 the latent rows alone are
    # bound by compute, by half a percent; with q~, q_pe and u of 64 rows
    # counted the bytes win by 3% (LongCat's 64 heads: by a factor of two)
    assert 1.0 < (ops / 197e12) / (moved / 819e9) < 1.01
    ops, moved = mla_paged_decode.ops_and_bytes(
        tokens_in_pages=64 * 6500, rows=64, heads=128, kv_rank=512, rope=64,
        sublayers=5)
    assert 0.96 < (ops / 197e12) / (moved / 819e9) < 1.0


def test_the_deepseekv2_cell_runs_and_is_correct(root):
    result = run.run_cell(root, CELL, 2**31 + 5, 0.8, False,
                          require_tpu=False)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8


def test_a_traced_run_reports_the_routing_counters(root):
    result = run.run_cell(root, CELL, 7, 0.8, True, require_tpu=False)
    got = result["metrics"]
    assert "slot_occupancy_pct" in got and "prefill_rows_per_call" in got
    # 8 rows, 6 picks of 32 outputs in 8 groups of 4, 3 groups kept, one
    # group held: 6 picks a step where the router is even, 3/8 of the rows
    assert 0 < got["dsv2_moe_held_assignments_per_step"]["value"] <= 8 * 4
    assert 0 < got["dsv2_moe_group_hit_share_pct"]["value"] <= 100
    # no device ran here: the readers of the device trace find nothing
    for name in OWN[:4] + ("decode_device_ms_p50",):
        assert name not in got


def test_the_six_metrics_read_a_traced_run_on_a_recorded_device():
    """The four device readers over hand-made evidence: the kernel's
    events under `mla.attend`, instructions mapped to the model's scopes,
    the captured ticks' counters."""
    from perfbench import harness, trace_reduce
    from perfbench.readers import kernel_roofline, scope_device_share
    m = Manifest(REPO)
    ops = {"mla.attend.3": 4e6, "fusion.1": 2e6, "fusion.2": 3e6,
           "fusion.3": 1e6}
    scopes = {"fusion.1": "jit(step_paged)/layer_1/moe/moe.experts/dot",
              "fusion.2": "jit(step_paged)/layer_1/moe/shared/moe.shared/dot",
              "fusion.3": "jit(step_paged)/layer_0/attn/mla.project/dot",
              "mla.attend.3": "jit(step_paged)/layer_0/attn/mla.attend/x"}
    names = list(ops)
    starts = 1.0 + np.cumsum([0.0] + [ops[n] for n in names[:-1]])
    dev = trace_reduce.DeviceTrace(
        0, trace_reduce.Events.build(
            [(trace_reduce.op_name(n), s, ops[n])
             for n, s in zip(names, starts)]),
        trace_reduce.Events.build([("jit_step_paged", 1.0, 10e6)]))
    trace = trace_reduce.TraceSummary([dev], trace_reduce.Events.build([]),
                                      (0.0, 11e6))
    ev = harness.Evidence(
        samples={}, counters={"serve.traced_tokens_in_pages_mean": 416000.0,
                              "serve.traced_decoding_rows_mean": 64.0,
                              "dsv2.held_assignments_per_step": 48.0,
                              "dsv2.group_hit_share_pct": 37.5},
        shapes={"heads": 128, "kv_rank": 512, "rope": 64, "sublayers": 5,
                "op_scopes": scopes,
                "device_ops_raw": (names, starts, np.array(
                    [ops[n] for n in names]))},
        trace=trace, peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9})
    read = lambda name: m.module(                              # noqa: E731
        "readers", m.layer_metric(name)["reader"]).read(
        m.layer_metric(name), ev)
    # 5 x 416 000 x 278 528 FLOP = 2.94 ms at the peak; 5 x (416 000 x
    # 1 152 + 64 x 128 x 1 088 x 2) B = 3.03 ms at the HBM peak; over 4 ms
    assert abs(read("dsv2_mla_decode_roofline") - 100 * 3.0345 / 4) < 0.1
    assert read("dsv2_mla_device_share_pct") == 50.0
    assert read("dsv2_moe_device_share_pct") == 20.0
    assert read("dsv2_shared_expert_device_share_pct") == 30.0
    assert read("dsv2_moe_held_assignments_per_step") == 48.0
    assert read("dsv2_moe_group_hit_share_pct") == 37.5
    assert kernel_roofline and scope_device_share


def test_the_roofline_takes_the_counters_of_the_captured_ticks():
    eng = object.__new__(_serve_deepseekv2.Engine)
    eng.engine = types.SimpleNamespace(config=types.SimpleNamespace(slots=4))
    eng.step_counts = {}
    eng.tick_at = [0.0, 1.0, 2.0, 3.0, 4.0]
    eng.tick_prefilled_rows = [0] * 5
    eng.tick_occupied = [4] * 5
    eng.tick_tokens_in_pages = [100, 200, 300, 400, 500]
    eng.tick_decoding_rows = [4, 4, 4, 3, 4]
    window = _serve_deepseekv2._serve.Engine.window_counters(eng, 0.0, 5.0)
    tracer = types.SimpleNamespace(disturbed=[(2.1, 2.9), (4.5, 4.8)])
    assert eng.traced_counters(tracer, window) == {
        "serve.traced_tokens_in_pages_mean": 450.0,
        "serve.traced_decoding_rows_mean": 3.5}
    tracer.disturbed = []
    assert eng.traced_counters(tracer, window) == {}
    args = _load(REPO, "perfbench", "layer_metrics",
                 "dsv2_mla_decode_roofline.json")["args"]
    assert args["rows"] == "counter:serve.traced_decoding_rows_mean"
    assert args["tokens_in_pages"] == \
        "counter:serve.traced_tokens_in_pages_mean"


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


def test_the_control_tool_reads_sound_and_control_gaps(root, capsys):
    from perfbench.tools import control_serve_deepseekv2
    rc = control_serve_deepseekv2.main([
        "--workload", CELL, "--seeds", "1", "2", "--control", "fp8", "bf16",
        "--control-seeds", "1", "--window-s", "0.5", "--root", root,
        "--cpu"])
    assert rc == 0
    out = capsys.readouterr().out.splitlines()
    rows = [json.loads(ln) for ln in out if ln.startswith("{")]
    assert [(r["seed"], r.get("control")) for r in rows] == [
        (1, "fp8"), (1, "bf16"), (2, None)]
    assert rows[0]["control_logit_gap"] > 4 * rows[0]["served_logit_gap"]
    # what bites the precision: every token's gap, not the widest one's
    assert rows[0]["control_logprob_gap_median"] > \
        4 * rows[1]["control_logprob_gap_median"] > 0
    assert "control_logit_gap" not in rows[2]
    assert any(ln.startswith("served_logprob_gap_median: sound max")
               and "fp8 control min" in ln and "bf16 control min" in ln
               for ln in out)


def test_the_reference_takes_logits_at_served_positions_alone():
    """`served_token_gaps` at picked positions, the head over two of them
    at a time and a layer's weights remade from the seed, is the whole
    forward pass's logits at those positions."""
    import jax
    import jax.numpy as jnp
    from perfbench.reference import deepseek_v2
    dims = weights.Dims.from_config(toy_config())
    key = weights.seed_key(2**31 + 1)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 128)
    at = jnp.asarray([[3, 10, 22, 5], [0, 7, 23, 1]])
    whole = deepseek_v2.forward(
        weights.make_params(key, dims, jnp.bfloat16), toks, dims)
    g = deepseek_v2.served_token_gaps(key, toks, at, dims, jnp.bfloat16,
                                      positions=2)
    nxt = np.asarray(toks)[np.arange(2)[:, None], np.minimum(at + 1, 23)]
    picked = np.asarray(whole)[np.arange(2)[:, None], np.asarray(at)]
    want = picked.max(-1) - np.take_along_axis(picked, nxt[..., None],
                                               -1)[..., 0]
    assert np.abs(np.asarray(g["served_gap"]) - want).max() < 1e-4
