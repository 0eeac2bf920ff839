"""The Qwen3-Next cell's kind, generator, reference, ops counts and tool on
the CPU, at toy widths, through `run.py` untouched: a root in a temporary
directory whose files stand beside a link to the repository's
`perfbench/`. Nothing here pins where in `BENCHMARK.json`'s lists the cell
stands: a later cell comes after it."""
import json
import types

import numpy as np
import pytest

from _perfbench_tiny import REPO, WINDOW_SIX, _dump, _load, make_root
from perfbench import run
from perfbench import weights_qwen3next as weights
from perfbench.kinds import _serve_qwen3next
from perfbench.manifest import Manifest
from perfbench.ops import gdn_state_update, paged_decode

CELL = "tiny-qwen3next"
REAL = "serve-qwen3next-1of4-sessions-wide"
CONFIG = "qwen3-next-80b-a3b-1of4"
TRAFFIC = "sessions-wide-closed"
OWN = ("q3n_gdn_device_share_pct", "q3n_gdn_state_update_roofline",
       "q3n_attn_device_share_pct", "q3n_kv_decode_roofline",
       "q3n_moe_device_share_pct", "q3n_shared_expert_device_share_pct",
       "q3n_moe_held_assignments_per_step",
       "q3n_moe_expert_load_max_over_mean_pct", "q3n_head_device_share_pct")
GENERIC = {"slot_occupancy_pct", "host_blocked_ms_p50", "decode_step_ms_p50",
           "decode_device_ms_p50", "prefill_tick_share_pct",
           "engine_host_work_ms_p50", "engine_dispatch_ms_p50",
           "prefill_stall_share_pct", "host_caused_idle_pct",
           "setup_trace_lower_s", "setup_compile_or_load_s",
           "slot_state_bytes_per_row"}
TOY = dict(vocab_size=128, hidden_size=64, num_hidden_layers=4, n_layer=4,
           num_attention_heads=4, num_key_value_heads=2, head_dim=32,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=24,
           moe_intermediate_size=32, shared_expert_intermediate_size=48,
           num_experts=4, num_experts_per_tok=3)


@pytest.fixture(autouse=True, scope="module")
def _a_span_log_of_this_files_own():
    """The program's span log is the process's, bounded at 100 000
    records, and a reader refuses a log that is full: this file's toy
    runs neither inherit another file's records nor leave theirs."""
    from mpi_operator_tpu.telemetry import spans
    spans.clear()
    yield
    spans.clear()


def toy_config():
    cfg = _load(REPO, "perfbench", "configs", CONFIG + ".json")
    cfg.update(TOY)
    # 8 router outputs; this chip holds experts 4..7. Weights of 0.16:
    # sqrt(hidden) x std is then the published widths' 0.9 and more
    cfg["assumed"] = {**cfg["assumed"], "num_experts_published": 8,
                      "held_first_expert": 4, "initializer_range": 0.16,
                      "dt_min": 0.03, "dt_max": 0.5}
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("perfbench_qwen3next"))
    _dump(toy_config(), root, "extra", "configs", "qwen3next-tiny.json")
    t = _load(REPO, "perfbench", "traffic", TRAFFIC + ".json")
    # a float32 program: at toy widths one of three picks among eight
    # flips on a bfloat16 rounding in one request of seventy, by 3 to 4.4
    # on the logits, and WHICH requests a window of 0.8 s finishes, so
    # which six are compared, is the machine's load's to say
    t["engine"].update(slots=8, page_size=4, num_pages=200,
                       chunk_buckets=[8], decode_kernel=False,
                       weights_dtype="float32")
    length = lambda median, lo, hi: {"dist": "lognormal",     # noqa: E731
                                     "median": median, "sigma": 0.4,
                                     "min": lo, "max": hi}
    t.update(clients=8, backlog=400, max_total=96,
             first_wave={"context": length(20, 8, 40),
                         "remaining": length(16, 4, 40),
                         "remaining_clear_of": [18, 22]},
             prompt=length(6, 3, 8), output=length(30, 16, 60),
             trace_start_s=0.1, trace_seconds=0.3, check_requests=6,
             # float32 program against the float32 reference: widest
             # under 0.01; the altered-token test below 4 and more
             limits={"served_logit_gap_widest": 3.0,
                     "served_logprob_gap_widest": 3.0})
    _dump(t, root, "extra", "traffic", "tiny-wide-closed.json")
    bench = _load(root, "BENCHMARK.json")
    real = _load(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "qwen3next-tiny", "source": "none",
                             "file": "extra/configs/qwen3next-tiny.json",
                             "reduced": ["num_hidden_layers"], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "qwen3next-tiny",
                               "traffic": "tiny-wide-closed", "chips": 1,
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
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "1.9" in cell["why"] \
        and "7.5" in cell["why"] and "12.9 MB" in cell["why"]
    t = m.traffic(cell["traffic"])
    e = t["engine"]
    assert (t["kind"], t["clients"], t["backlog"], t["max_total"]) == (
        "serve_closed_qwen3next", 96, 192, 16384)
    assert (e["slots"], e["page_size"], e["chunk_buckets"], e["prefix_cache"],
            e["async_decode"], e["decode_kernel"], e["weights_dtype"],
            e["async_depth"]) == (
        96, 64, [128], False, True, True, "bfloat16", 8)
    first = t["first_wave"]
    assert first["context"] == {"dist": "lognormal", "median": 5120,
                                "sigma": 0.5, "min": 2048, "max": 10240}
    assert first["remaining"] == {"dist": "lognormal", "median": 4096,
                                  "sigma": 0.35, "min": 1792, "max": 6144}
    assert t["prompt"] == {"dist": "lognormal", "median": 96, "sigma": 0.35,
                           "min": 32, "max": 128}
    assert t["output"] == {"dist": "lognormal", "median": 5120, "sigma": 0.2,
                           "min": 4096, "max": 6144}
    assert (t["check_requests"], t["trace_start_s"], t["trace_seconds"],
            t["first_wave_limit_s"]) == (6, 30.0, 8.0, 900)
    assert "placement" in t and "tick" in t["placement_why"]
    assert set(t["limits"]) == {"served_logit_gap_widest",
                                "served_logprob_gap_widest"}
    assert "control" in t["limits_set_from"]
    own = [x for x in m.data["per_layer"] if x.get("workloads") == [REAL]]
    assert set(OWN) == {x["name"] for x in own}
    assert all(x["moves"] == "serve_tokens_per_s" for x in own)
    assert all(x["layer"] == m.layer_metric(x["name"])["layer"] for x in own)
    assert all(x["unit"] == "%" for x in own if "roofline" in x["name"])
    # the cell joins the generic serving and set-up metrics and what a
    # slot holds; NOT the metrics that read nothing without an admission
    # (PR 37's six and the rows a prefill call: a traced window's ticks end
    # at 38 s, 4 s after this mix's first row retires, and a listed metric
    # that reads nothing refuses the run), NOT the other kernels' rooflines
    # nor Falcon-H1's head share
    lists = {x["name"] for x in m.data["per_layer"]
             if REAL in x.get("workloads", []) and x not in own}
    assert GENERIC <= lists
    assert not lists & (WINDOW_SIX | {"prefill_rows_per_call",
                                      "paged_decode_roofline",
                                      "head_device_share_pct"})
    assert REAL in next(x for x in m.data["end_to_end"]
                        if x["name"] == "serve_tokens_per_s")["workloads"]
    # one cell in nine takes four chips: within the quarter
    cells = m.data["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


def test_the_configuration_holds_every_published_key_and_cuts_three():
    cfg = Manifest(REPO).config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(json.loads(ln) for ln in f if json.loads(ln)["name"]
                     == "Qwen3-Next-80B-A3B-Instruct")
    differs = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"} \
        == set(cfg["reduced"])
    assert set(Manifest(REPO)._by_name("configs", CONFIG)["reduced"]) \
        == differs
    assert (cfg["num_hidden_layers"], cfg["n_layer"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 8, 128, 37984)
    assert cfg["reduced_from"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert cfg["source"] == entry["source_url"]
    assert "24 chips" in cfg["deployment"] \
        and "6 pipeline stages of 8 layers" in cfg["deployment"] \
        and "4 chips sharing each layer" in cfg["deployment"]
    a = cfg["assumed"]
    assert a["serves_max_total"] == 16384
    assert a["padded_vocab_size"] == 37984
    for key in ("norm_scales", "column_order", "gate", "ties", "state_dtype",
                "weights", "chunk", "mtp", "delta_rule", "attention"):
        assert key in a, key
    d = weights.Dims.from_config(cfg)
    assert (d.held, d.experts_published, d.top_k, d.layers, d.delta_layers,
            d.key_heads, d.value_heads, d.key_head_dim, d.value_head_dim,
            d.heads, d.kv_heads, d.head_dim, d.rotary_dim) == (
        (0, 128), 512, 10, 8, 6, 16, 32, 128, 128, 16, 2, 256, 64)
    assert d.layer_types == ("delta",) * 3 + ("attention",) \
        + ("delta",) * 3 + ("attention",)
    # the issue's count: 6 x 440.6 M + 2 x 434.1 M + 155.6 M: 3 667 M
    # parameters, 7.33 GB
    assert round(d.param_count() / 1e6) == 3667
    assert round(2 * d.param_count() / 1e7) == 733
    # what a slot holds and a cached position costs, as the file states
    assert d.slot_state_bytes() == 12877824 and d.position_bytes() == 2048
    assert "12877824" in cfg["bytes"] and "2 048 B" in cfg["bytes"] \
        and "7.335 GB" in cfg["bytes"]


@pytest.mark.parametrize("key,other", [
    ("tie_word_embeddings", True), ("norm_topk_prob", False),
    ("mlp_only_layers", [0]), ("use_sliding_window", True)])
def test_what_the_reference_does_not_write_down_is_refused(key, other):
    with pytest.raises(ValueError, match="does not write down"):
        weights.Dims.from_config({**toy_config(), key: other})


def test_every_seed_serves_the_same_lengths_in_the_same_places():
    t = Manifest(REPO).traffic(TRAFFIC)
    lengths = lambda reqs: [(len(r.prompt), r.max_new_tokens)  # noqa: E731
                            for r in reqs]
    a = _serve_qwen3next.deep_closed_loop(t, 1, 1000)
    b = _serve_qwen3next.deep_closed_loop(t, 2**31 + 7, 1000)
    assert lengths(a[0]) == lengths(b[0]) and lengths(a[1]) == lengths(b[1])
    assert a[0][0].prompt != b[0][0].prompt
    first, backlog = a
    assert (len(first), len(backlog)) == (96, t["backlog"])
    # what a row decodes counts from the window's opening: the tokens it
    # decodes while the first wave's other calls run (a call a tick, 80 of
    # them, a row's own ceil((p - 1) / 128)) come on top
    early = lambda p: 80 - -(-(p - 1) // 128)                  # noqa: E731
    remaining = sorted(n - early(p) for p, n in lengths(first))
    assert all(2048 <= p <= 10240 and p + n <= 16384
               for p, n in lengths(first))
    assert all(1792 <= n <= 6144 for n in remaining)
    # the median, 4096, moved up by the band's 12.9% of mass taken from below it
    assert 4096 < remaining[48] < 4400
    # the issue's clearance: NO retirement within 150 ticks of where the
    # window closes, wherever between its sound runs' closes that is
    lo, hi = t["first_wave"]["remaining_clear_of"]
    inside = sum(n < lo for n in remaining)
    assert 3 <= inside <= 8
    assert not [n for n in remaining if lo <= n <= hi]
    assert hi - lo >= 300
    assert all(32 <= p <= 128 and 4096 <= n <= 6144
               for p, n in lengths(backlog))
    # a replacement is ONE [96, 128] call; the first wave 80 of them
    assert max(p for p, _ in lengths(backlog)) - 1 <= 128
    assert -(-(max(p for p, _ in lengths(first)) - 1) // 128) == 80
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
    # weights, 96 slots' state and the two pools: 70-86% of the chip
    held_bytes = 7.335e9 + 96 * 12877824 \
        + 2 * t["engine"]["num_pages"] * 131072
    assert 0.70 * 2**34 < held_bytes < 0.86 * 2**34


def test_the_ops_counts_at_qwen3_nexts_widths_by_hand():
    # the state update: a row and layer moves its 32 x 128 x 128 float32
    # state in and out, 2 097 152 B each way; beside it q and k (2048
    # each), v and o (4096 each), g and beta (32 each)
    ops, moved = gdn_state_update.ops_and_bytes(
        rows=96, layers=6, value_heads=32, key_heads=16, key_head_dim=128,
        value_head_dim=128)
    small = 2 * 2048 + 2 * 4096 + 2 * 32
    assert moved == 96 * 6 * (2 * 2097152 + 4 * small)
    assert ops == 8.0 * 96 * 6 * 32 * 128 * 128
    # bound by memory: 2.44 GB a step at 819 GB/s is 3.0 ms
    assert ops / 197e12 < 0.01 * moved / 819e9
    assert 2.9e-3 < moved / 819e9 < 3.1e-3
    # the two attention layers: 2 048 B a cached position and layer, q and
    # out of 16 heads of 256; 4 x 16 x 256 products a cached token
    ops, moved = paged_decode.ops_and_bytes(
        tokens_in_pages=96 * 6912, rows=96, heads=16, kv_heads=2,
        head_dim=256, layers=2)
    assert moved == 2 * (96 * 6912 * 2048 + 2 * 96 * 16 * 256 * 2)
    assert ops == 2 * 4.0 * 96 * 6912 * 16 * 256
    assert ops / 197e12 < 0.1 * moved / 819e9


def test_the_qwen3next_cell_runs_and_is_correct(root):
    result = run.run_cell(root, CELL, 2**31 + 5, 0.8, False,
                          require_tpu=False)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8


def test_a_traced_run_reports_the_counters_and_what_a_slot_holds(root):
    result = run.run_cell(root, CELL, 7, 0.8, True, require_tpu=False)
    got = result["metrics"]
    assert result["correct"] is True
    assert "slot_occupancy_pct" in got
    # 8 rows x 3 picks of 8 outputs, 4 held: 12 a step and layer where
    # the router is even; the fullest expert at or over the mean
    assert 0 < got["q3n_moe_held_assignments_per_step"]["value"] <= 8 * 3
    assert got["q3n_moe_expert_load_max_over_mean_pct"]["value"] >= 100.0
    assert not [name for name in got if name.startswith("g4hs_")]
    # the cell is on no list that an admission has to fill
    assert not (WINDOW_SIX | {"prefill_rows_per_call"}) & set(got)
    # three delta-rule layers' state (4 x 16 x 24 float32) and conv tail
    # (3 x 160 float32 in the toy) a slot
    assert got["slot_state_bytes_per_row"]["value"] == \
        3 * (4 * 16 * 24 * 4 + 3 * 160 * 4)
    # no device ran here: the readers of the device trace find nothing
    for name in OWN[:6] + OWN[8:] + ("decode_device_ms_p50",):
        assert name not in got


def test_a_window_in_which_nothing_finished_compares_what_was_served(
        root, monkeypatch):
    """A traced window's ticks end where the profiler stops, before this
    mix's first row retires: the rows still in their slots are compared,
    and a run whose tokens are wrong there is still NOT correct."""
    from perfbench import harness
    from perfbench.kinds import serve_closed_deepseekv2
    kept = {}
    real = _serve_qwen3next.check_served

    def check(ctx, eng, results, prompts):
        kept["finished"] = len(results)
        return real(ctx, eng, {}, prompts)
    monkeypatch.setattr(_serve_qwen3next, "check_served", check)
    ctx = harness.Context(
        manifest=Manifest(root), cell=Manifest(root).cell(CELL),
        config=Manifest(root).config("qwen3next-tiny"),
        traffic=Manifest(root).traffic("tiny-wide-closed"), seed=5,
        seconds=0.4, trace=False, devices=__import__("jax").devices()[:1])
    outcome = serve_closed_deepseekv2.run_loop(ctx, _serve_qwen3next)
    assert outcome.correct is True and "finished" in kept
    cut = _serve_qwen3next.served_so_far(
        types.SimpleNamespace(scheduler=types.SimpleNamespace(active=[
            types.SimpleNamespace(req=types.SimpleNamespace(id=3),
                                  generated=[1, 2], logprobs=[-1.0, -2.0]),
            types.SimpleNamespace(req=types.SimpleNamespace(id=4),
                                  generated=[], logprobs=[])])), {})
    assert list(cut) == [3] and cut[3].tokens == [1, 2]
    done = {9: types.SimpleNamespace(id=9, tokens=[5], logprobs=[-0.5],
                                     finish_reason="length")}
    assert _serve_qwen3next.served_so_far(None, done) is done


def test_the_cells_own_metrics_read_a_traced_run_on_a_recorded_device():
    """The device readers over hand-made evidence: the kernels' events
    under `gdn.update` and `q3attn.attend`, instructions mapped to the
    model's scopes, the captured ticks' counters."""
    from perfbench import harness, trace_reduce
    m = Manifest(REPO)
    ops = {"gdn.update.3": 4e6, "q3attn.attend.1": 4e6, "fusion.1": 8e6,
           "fusion.2": 1e6, "fusion.3": 1e6, "fusion.4": 1e6,
           "fusion.5": 1e6}
    scopes = {
        "gdn.update.3": "jit(step_paged)/layer_0/delta/gdn.update/x",
        "q3attn.attend.1": "jit(step_paged)/layer_3/attn/q3attn.attend/x",
        "fusion.1": "jit(step_paged)/layer_1/moe/moe.experts/dot",
        "fusion.2": "jit(step_paged)/layer_1/moe/shared/moe.shared/dot",
        "fusion.3": "jit(step_paged)/layer_0/delta/gdn.project/dot",
        "fusion.4": "jit(step_paged)/layer_3/attn/q3attn.project/dot",
        "fusion.5": "jit(step_paged)/head/dot"}
    names = list(ops)
    starts = 1.0 + np.cumsum([0.0] + [ops[n] for n in names[:-1]])
    dev = trace_reduce.DeviceTrace(
        0, trace_reduce.Events.build(
            [(trace_reduce.op_name(n), s, ops[n])
             for n, s in zip(names, starts)]),
        trace_reduce.Events.build([("jit_step_paged", 1.0, 20e6)]))
    trace = trace_reduce.TraceSummary([dev], trace_reduce.Events.build([]),
                                      (0.0, 21e6))
    ev = harness.Evidence(
        samples={}, counters={"serve.traced_tokens_in_pages_mean": 663552.0,
                              "serve.traced_decoding_rows_mean": 96.0,
                              "q3n.held_assignments_per_step": 240.0,
                              "q3n.expert_load_max_over_mean_pct": 350.0,
                              "serve.slot_state_bytes_per_row": 12877824.0},
        shapes={"heads": 16, "kv_heads": 2, "head_dim": 256, "layers": 8,
                "attn_layers": 2, "delta_layers": 6, "key_heads": 16,
                "value_heads": 32, "key_head_dim": 128,
                "value_head_dim": 128, "op_scopes": scopes,
                "device_ops_raw": (names, starts, np.array(
                    [ops[n] for n in names]))},
        trace=trace, peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9})
    read = lambda name: m.module(                              # noqa: E731
        "readers", m.layer_metric(name)["reader"]).read(
        m.layer_metric(name), ev)
    # 96 x 6 x (4 194 304 + 49 408) B = 2.444 GB: 2.985 ms at the HBM peak
    assert abs(read("q3n_gdn_state_update_roofline") - 100 * 2.985 / 4) < 0.1
    # 663 552 x 2 048 B x 2 layers + q and out: 2.721 GB: 3.322 ms
    assert abs(read("q3n_kv_decode_roofline") - 100 * 3.322 / 4) < 0.1
    assert read("q3n_gdn_device_share_pct") == 25.0
    assert read("q3n_attn_device_share_pct") == 25.0
    assert read("q3n_moe_device_share_pct") == 40.0
    assert read("q3n_shared_expert_device_share_pct") == 5.0
    assert read("q3n_head_device_share_pct") == 5.0
    assert read("q3n_moe_held_assignments_per_step") == 240.0
    assert read("q3n_moe_expert_load_max_over_mean_pct") == 350.0
    assert read("slot_state_bytes_per_row") == 12877824.0


def test_the_rooflines_take_the_counters_of_the_captured_ticks():
    eng = object.__new__(_serve_qwen3next.Engine)
    eng.engine = types.SimpleNamespace(config=types.SimpleNamespace(slots=4))
    eng.step_counts = {}
    eng.slot_state = 7.0
    eng.tick_at = [0.0, 1.0, 2.0, 3.0, 4.0]
    eng.tick_prefilled_rows = [0] * 5
    eng.tick_occupied = [4] * 5
    eng.tick_tokens_in_pages = [100, 200, 300, 400, 500]
    eng.tick_decoding_rows = [4, 4, 4, 3, 4]
    window = eng.window_counters(0.0, 5.0)
    assert window["serve.slot_state_bytes_per_row"] == 7.0
    tracer = types.SimpleNamespace(disturbed=[(2.1, 2.9), (4.5, 4.8)])
    assert eng.traced_counters(tracer, window) == {
        "serve.traced_tokens_in_pages_mean": 450.0,
        "serve.traced_decoding_rows_mean": 3.5}
    for name in ("q3n_gdn_state_update_roofline", "q3n_kv_decode_roofline"):
        args = _load(REPO, "perfbench", "layer_metrics", name + ".json")[
            "args"]
        assert args["rows"] == "counter:serve.traced_decoding_rows_mean"


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
    from perfbench.tools import control_serve_qwen3next
    rc = control_serve_qwen3next.main([
        "--workload", CELL, "--seeds", "1", "2", "--control", "fp8", "bf16",
        "--control-seeds", "1", "--window-s", "0.5", "--root", root,
        "--cpu"])
    assert rc == 0
    out = capsys.readouterr().out.splitlines()
    rows = [json.loads(ln) for ln in out if ln.startswith("{")]
    assert [(r["seed"], r.get("control")) for r in rows] == [
        (1, "fp8"), (1, "bf16"), (2, None)]
    # the precision below reads wider than the sound program, and fp8
    # wider than bfloat16, over every token
    assert rows[0]["control_logprob_gap_median"] > \
        2 * rows[0]["served_logprob_gap_median"]
    assert rows[0]["control_logprob_gap_median"] > \
        2 * rows[1]["control_logprob_gap_median"] > 0
    assert "control_logit_gap" not in rows[2]
    assert any(ln.startswith("served_logit_gap_widest: sound max")
               and "fp8 control min" in ln and "bf16 control min" in ln
               for ln in out)


@pytest.mark.parametrize("fp8, sound, rc", [
    # the control fails by EACH limit and the program by none
    ((1.8, 1.5), (0.3, 0.2), 0),
    # a control that one limit lets through decides nothing
    ((1.8, 0.5), (0.3, 0.2), 1),
    ((0.5, 1.5), (0.3, 0.2), 1),
    # a sound seed over a limit
    ((1.8, 1.5), (0.9, 0.2), 1),
])
def test_the_control_tool_judges_its_readings_as_a_run_does(
        fp8, sound, rc, capsys):
    from perfbench.tools import control_serve_qwen3next
    limits = {"served_logit_gap_widest": 0.8,
              "served_logprob_gap_widest": 0.7}
    reading = lambda side, g: {side + "_logit_gap": g[0],      # noqa: E731
                               side + "_logprob_gap": g[1]}
    got = control_serve_qwen3next.judge(
        limits, [reading("served", sound), reading("served", (0.1, 0.1))],
        {"fp8": [reading("control", fp8)],
         # bfloat16 is the program's own precision: judged by neither rule
         "bf16": [reading("control", (0.3, 0.2))]})
    out = capsys.readouterr().out.splitlines()
    assert got == rc and len(out) == 2 * 2 + 2 * 2 + 1
    assert sum("NOT CORRECT" in ln for ln in out if ln.startswith("fp8")) \
        == sum(g > lim for g, lim in zip(fp8, (0.8, 0.7)))
    assert out[-1].startswith("verdict: ") \
        and ("do NOT part" in out[-1]) == bool(rc)


def test_the_reference_takes_logits_at_served_positions_alone():
    """`served_token_gaps` at picked positions, the head over two of them
    at a time and a layer's weights remade from the seed, is the whole
    forward pass's logits at those positions."""
    import jax
    import jax.numpy as jnp
    from perfbench.reference import qwen3_next
    dims = weights.Dims.from_config(toy_config())
    key = weights.seed_key(2**31 + 1)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 128)
    at = jnp.asarray([[3, 10, 22, 5], [0, 7, 23, 1]])
    whole = qwen3_next.forward(
        weights.make_params(key, dims, jnp.bfloat16), toks, dims)
    g = qwen3_next.served_token_gaps(key, toks, at, dims, jnp.bfloat16,
                                     positions=2)
    nxt = np.asarray(toks)[np.arange(2)[:, None], np.minimum(at + 1, 23)]
    picked = np.asarray(whole)[np.arange(2)[:, None], np.asarray(at)]
    want = picked.max(-1) - np.take_along_axis(picked, nxt[..., None],
                                               -1)[..., 0]
    assert np.abs(np.asarray(g["served_gap"]) - want).max() < 1e-4
