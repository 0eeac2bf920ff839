"""The Granite-4.0-H cell's kind, generator, reference, ops counts and tool
on the CPU, at toy widths, through `run.py` untouched: a root in a
temporary directory whose files stand beside a link to the repository's
`perfbench/`. Nothing here pins where in `BENCHMARK.json`'s lists the
cell stands: a later cell comes after it."""
import json
import types

import numpy as np
import pytest

from _perfbench_tiny import REPO, WINDOW_SIX, _dump, _load, make_root
from perfbench import run
from perfbench import weights_granite4hs as weights
from perfbench.kinds import _serve_granite4hs
from perfbench.manifest import Manifest
from perfbench.ops import paged_decode, ssd_state_update

CELL = "tiny-granite4hs"
REAL = "serve-granite4hs-1of2-sessions-deep"
CONFIG = "granite-4.0-h-small-1of2"
OWN = ("g4hs_ssd_device_share_pct", "g4hs_ssd_state_update_roofline",
       "g4hs_moe_device_share_pct", "g4hs_shared_expert_device_share_pct",
       "g4hs_moe_held_assignments_per_step",
       "g4hs_moe_expert_load_max_over_mean_pct",
       "g4hs_attn_device_share_pct", "g4hs_kv_decode_roofline",
       "g4hs_head_device_share_pct")
GENERIC = {"slot_occupancy_pct", "host_blocked_ms_p50", "decode_step_ms_p50",
           "decode_device_ms_p50", "prefill_rows_per_call",
           "prefill_tick_share_pct", "engine_host_work_ms_p50",
           "engine_dispatch_ms_p50", "prefill_stall_share_pct",
           "host_caused_idle_pct", "setup_trace_lower_s",
           "setup_compile_or_load_s", "slot_state_bytes_per_row"}
TOY = dict(vocab_size=128, hidden_size=64, intermediate_size=32,
           shared_intermediate_size=48, num_hidden_layers=3, n_layer=3,
           layer_types=["mamba", "attention", "mamba"],
           num_attention_heads=4, num_key_value_heads=2,
           num_local_experts=4, num_experts_per_tok=3, mamba_n_heads=2,
           mamba_d_head=64, mamba_d_state=16, mamba_chunk_size=8,
           # the logit of the token just read is 12 sqrt(hidden) std /
           # rms(stream) of the others' spread: 2.6 among 50 176 at the
           # published widths, but 4 among 128 here, where the toy would
           # repeat its input and every gap read 0
           embedding_multiplier=3)


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
    # sqrt(hidden) x std is then the published widths' 1.28
    cfg["assumed"] = {**cfg["assumed"], "head_dim": 16,
                      "num_local_experts_published": 8,
                      "held_first_expert": 4, "initializer_range": 0.16,
                      "dt_min": 0.03, "dt_max": 0.5}
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("perfbench_granite4hs"))
    _dump(toy_config(), root, "extra", "configs", "granite4hs-tiny.json")
    t = _load(REPO, "perfbench", "traffic", "sessions-deep-closed.json")
    t["engine"].update(slots=8, page_size=4, num_pages=200,
                       chunk_buckets=[8], decode_kernel=False)
    length = lambda median, lo, hi: {"dist": "lognormal",     # noqa: E731
                                     "median": median, "sigma": 0.4,
                                     "min": lo, "max": hi}
    t.update(clients=8, backlog=400, max_total=96,
             first_wave={"context": length(20, 8, 40),
                         "remaining": length(16, 4, 40),
                         "remaining_clear_of": [18, 22]},
             prompt=length(6, 3, 8), output=length(30, 16, 60),
             trace_start_s=0.1, trace_seconds=0.3, check_requests=6,
             # bfloat16 program against the float32 reference at toy
             # widths reads 0.02 and 0.04-0.3; the altered-token test
             # below 1 and more
             limits={"served_logit_gap_widest": 0.8,
                     "served_logprob_gap_widest": 0.8})
    _dump(t, root, "extra", "traffic", "tiny-sessions-closed.json")
    bench = _load(root, "BENCHMARK.json")
    real = _load(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "granite4hs-tiny", "source": "none",
                             "file": "extra/configs/granite4hs-tiny.json",
                             "reduced": ["num_hidden_layers"], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "granite4hs-tiny",
                               "traffic": "tiny-sessions-closed", "chips": 1,
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
        CONFIG, "sessions-deep-closed", 1)
    assert len(cell["why"]) <= 200 and "6.7" in cell["why"] \
        and "13.3" in cell["why"] and "4%" in cell["why"]
    t = m.traffic(cell["traffic"])
    e = t["engine"]
    assert (t["kind"], t["clients"], t["max_total"]) == (
        "serve_closed_granite4hs", 48, 16384)
    assert (e["slots"], e["page_size"], e["chunk_buckets"], e["prefix_cache"],
            e["async_decode"], e["decode_kernel"], e["weights_dtype"]) == (
        48, 64, [128], False, True, True, "bfloat16")
    # steps dispatched ahead, so that the measuring machine's standstills
    # starve nothing: not one of the issue's parameters, and said so
    assert e["async_depth"] == 8 and "standstill" in t["async_depth_why"]
    first = t["first_wave"]
    assert first["context"] == {"dist": "lognormal", "median": 5120,
                                "sigma": 0.5, "min": 2048, "max": 12288}
    assert first["remaining"] == {"dist": "lognormal", "median": 3584,
                                  "sigma": 0.4, "min": 1536, "max": 6144}
    assert (t["prompt"]["min"], t["prompt"]["median"],
            t["prompt"]["max"]) == (32, 96, 128)
    assert (t["output"]["min"], t["output"]["max"]) == (4096, 6144)
    assert (t["check_requests"], t["trace_seconds"]) == (6, 8.0)
    assert t["trace_start_s"] >= 30.0
    assert "placement" in t and "ticks" in t["placement_why"]
    assert set(t["limits"]) == {"served_logit_gap_widest",
                                "served_logprob_gap_widest"}
    assert "control" in t["limits_set_from"]
    own = [x for x in m.data["per_layer"] if x.get("workloads") == [REAL]]
    assert set(OWN) <= {x["name"] for x in own}
    assert all(x["moves"] == "serve_tokens_per_s" for x in own)
    assert all(x["layer"] == m.layer_metric(x["name"])["layer"] for x in own)
    assert all(x["unit"] == "%" for x in own if "roofline" in x["name"])
    # the cell joins the generic serving and set-up metrics, what a slot
    # holds and PR 37's six window metrics (the fixed lengths put two
    # retirements, so two admissions, inside every traced sub-window). The
    # head's share is a metric of the cell's own: `head_device_share_pct`
    # lists Falcon-H1's cell alone and that cell's test pins it so
    lists = {x["name"] for x in m.data["per_layer"]
             if REAL in x.get("workloads", []) and x not in own}
    assert GENERIC | WINDOW_SIX <= lists
    assert REAL in next(x for x in m.data["end_to_end"]
                        if x["name"] == "serve_tokens_per_s")["workloads"]
    # one cell in eight takes four chips: within the quarter
    cells = m.data["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


def test_the_configuration_holds_every_published_key_and_cuts_three():
    cfg = Manifest(REPO).config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(json.loads(ln) for ln in f if json.loads(ln)["name"]
                     == "granite-4.0-h-small")
    differs = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    # layer_types is cut with num_hidden_layers, to its first period, and
    # `reduced` names the group beside the three numbers
    assert differs == {"num_hidden_layers", "num_local_experts",
                       "vocab_size", "layer_types"} == set(cfg["reduced"])
    assert set(Manifest(REPO)._by_name("configs", CONFIG)["reduced"]) \
        == differs
    assert cfg["layer_types"] == entry["config"]["layer_types"][:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (cfg["num_hidden_layers"], cfg["n_layer"],
            cfg["num_local_experts"], cfg["vocab_size"]) == (
        10, 10, 36, 50176)
    assert {k: cfg["reduced_from"][k] for k in (
        "num_hidden_layers", "num_local_experts", "vocab_size")} == {
        "num_hidden_layers": 40, "num_local_experts": 72,
        "vocab_size": 100352}
    assert cfg["source"] == entry["source_url"]
    assert "4 pipeline stages" in cfg["deployment"] \
        and "13.3" in cfg["deployment"]
    a = cfg["assumed"]
    assert a["serves_max_total"] == 16384 and a["head_dim"] == 128
    for key in ("expert_width", "gate", "ties", "state_dtype",
                "state_layout", "time_step_limit", "mamba_init", "weights",
                "chunk"):
        assert key in a, key
    d = weights.Dims.from_config(cfg)
    assert (d.held, d.experts_published, d.top_k, d.layers, d.mamba_layers,
            d.ssm_heads, d.ssm_head_dim, d.d_state, d.groups) == (
        (0, 36), 72, 10, 10, 9, 128, 64, 128, 1)
    # the issue's count: 121.5 M + 339.7 M a mamba layer, 61.1 M + 339.7 M
    # the attention layer, 205.5 M the tied table's half: 4 757 M, 9.51 GB
    assert round(d.param_count() / 1e6) == 4757
    assert round(2 * d.param_count() / 1e7) == 951
    # what a slot holds and a cached position costs, as the file states
    state = d.ssm_heads * d.ssm_head_dim * d.d_state * 4
    tail = (d.d_conv - 1) * d.conv_dim * 2
    assert d.mamba_layers * (state + tail) == 38204928
    assert "38 204 928" in cfg["bytes"] and "4 096 B" in cfg["bytes"]


@pytest.mark.parametrize("key,other", [
    ("position_embedding_type", "rope"), ("tie_word_embeddings", False),
    ("normalization_function", "layernorm"), ("mamba_proj_bias", True)])
def test_what_the_reference_does_not_write_down_is_refused(key, other):
    with pytest.raises(ValueError, match="does not write down"):
        weights.Dims.from_config({**toy_config(), key: other})


def test_every_seed_serves_the_same_lengths_in_the_same_places():
    t = Manifest(REPO).traffic("sessions-deep-closed")
    lengths = lambda reqs: [(len(r.prompt), r.max_new_tokens)  # noqa: E731
                            for r in reqs]
    a = _serve_granite4hs.deep_closed_loop(t, 1, 1000)
    b = _serve_granite4hs.deep_closed_loop(t, 2**31 + 7, 1000)
    assert lengths(a[0]) == lengths(b[0]) and lengths(a[1]) == lengths(b[1])
    assert a[0][0].prompt != b[0][0].prompt
    first, backlog = a
    assert (len(first), len(backlog)) == (48, t["backlog"])
    # what a row decodes counts from the window's opening: the tokens it
    # decodes while the first wave's other calls run (a call a tick, 96 of
    # them, a row's own ceil((p - 1) / 128)) come on top
    early = lambda p: 96 - -(-(p - 1) // 128)                  # noqa: E731
    remaining = sorted(n - early(p) for p, n in lengths(first))
    assert all(2048 <= p <= 12288 and p + n <= 16384
               for p, n in lengths(first))
    assert all(1536 <= n <= 6144 for n in remaining)
    assert 3400 < remaining[24] < 3900                 # the median, 3584
    # the issue's clearance: between 4 and 10 rows retire inside a window
    # and NO retirement lies within 150 ticks of where it closes. Measured
    # on the chip (PERF.md, PR 42): a tick 20.72 ms, a replacement's call
    # 445 ms, the first 8 ticks of a window 5 ms (the queue filling), so
    # n retirements give (51 s - n x 0.445) / 20.72 ms + n + 6 ticks, 2 366
    # at 5; the harness sees a retirement, and sends its replacement, 7
    # ticks after the slot is released (`async_depth` 8)
    lo, hi = t["first_wave"]["remaining_clear_of"]
    inside = sum(n < lo for n in remaining)
    close = (51.0 - 0.445 * inside) / 0.02072 + inside + 6
    assert 4 <= inside <= 10 and 2355 < close < 2375
    assert not [n for n in remaining if abs(n + 7 - close) < 150]
    assert not [n for n in remaining if lo <= n <= hi]
    assert all(32 <= p <= 128 and 4096 <= n <= 6144
               for p, n in lengths(backlog))
    # a replacement is ONE [48, 128] call; the first wave 96 of them
    assert max(p for p, _ in lengths(backlog)) - 1 <= 128
    assert -(-(max(p for p, _ in lengths(first)) - 1) // 128) == 96
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
    # weights, 48 slots' state and the pool: over 75% of the chip's 16 GB
    held_bytes = 9.514e9 + 48 * 38204928 + t["engine"]["num_pages"] * 262144
    assert 0.75 * 16e9 < held_bytes < 0.9 * 16e9


@pytest.mark.parametrize("clear", [(2165, 2505), (3000, 4000), (1, 1600),
                                   (6000, 9000)])
def test_a_band_taken_out_of_the_lengths_leaves_the_rest_in_shape(clear):
    spec = {"dist": "lognormal", "median": 3584, "sigma": 0.4, "min": 1536,
            "max": 6144}
    from perfbench import generators
    plain = generators.length_quantiles(spec, 48)
    got = _serve_granite4hs.clear_quantiles(spec, 48, clear)
    lo, hi = clear
    assert got == sorted(got) and len(got) == 48
    # clipped values may stand ON a bound that lies inside the band
    assert not [v for v in got if lo < v < hi
                and v not in (spec["min"], spec["max"])]
    assert all(spec["min"] <= v <= spec["max"] for v in got)
    # outside the band the distribution keeps its shape: as many values
    # below it as the plain quantiles have there, scaled by what is left
    left = 1 - (sum(lo <= v <= hi for v in plain) / 48)
    below = sum(v < lo for v in plain)
    assert abs(sum(v < lo for v in got) - below / left) <= 1.5
    # an empty band changes nothing
    assert _serve_granite4hs.clear_quantiles(spec, 48, (100, 100)) == plain


def test_the_ops_counts_at_granites_widths_by_hand():
    # the state update: a row and layer moves its 128 x 64 x 128 float32
    # state in and out, 4 194 304 B each way, whatever layout holds it;
    # beside it x and y (8192 each), dt (128), B and C (128 each)
    ops, moved = ssd_state_update.ops_and_bytes(
        rows=48, layers=9, ssm_heads=128, head_dim=64, d_state=128, groups=1)
    small = 2 * 8192 + 128 + 2 * 128
    assert moved == 48 * 9 * (2 * 4194304 + 4 * small)
    assert ops == 5.0 * 48 * 9 * 128 * 64 * 128
    # bound by memory: 3.63 GB a step at 819 GB/s is 4.4 ms
    assert ops / 197e12 < 0.01 * moved / 819e9
    assert 4.3e-3 < moved / 819e9 < 4.5e-3
    # the one attention layer: 4 096 B a cached position, q and out of
    # 32 heads of 128; 4 x 32 x 128 products a cached token
    ops, moved = paged_decode.ops_and_bytes(
        tokens_in_pages=48 * 7040, rows=48, heads=32, kv_heads=8,
        head_dim=128, layers=1)
    assert moved == 48 * 7040 * 4096 + 2 * 48 * 32 * 128 * 2
    assert ops == 4.0 * 48 * 7040 * 32 * 128
    assert ops / 197e12 < 0.1 * moved / 819e9


def test_the_granite_cell_runs_and_is_correct(root):
    result = run.run_cell(root, CELL, 2**31 + 5, 0.8, False,
                          require_tpu=False)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8


def test_the_first_wave_is_done_before_the_window_opens(root, monkeypatch):
    """Once in a run, in the tick that sends the first wave's last prefill
    call, every dispatched step is fetched: the replacement calls of the
    window (dozens at toy sizes) drain nothing."""
    from mpi_operator_tpu.serve import ServingEngine
    drained = []
    real = ServingEngine.drain

    def drain(self):
        drained.append(len(self._session["pending"]))
        real(self)
    monkeypatch.setattr(ServingEngine, "drain", drain)
    result = run.run_cell(root, CELL, 11, 0.5, False, require_tpu=False)
    assert result["correct"] is True and result["attempted"] > 8
    assert len(drained) == 1 and 1 <= drained[0] <= 8


def test_a_traced_run_reports_the_counters_and_what_a_slot_holds(root):
    result = run.run_cell(root, CELL, 7, 0.8, True, require_tpu=False)
    got = result["metrics"]
    assert "slot_occupancy_pct" in got and "prefill_rows_per_call" in got
    # 8 rows x 3 picks of 8 outputs, 4 held: 12 a step and layer where
    # the router is even; the fullest expert at or over the mean
    assert 0 < got["g4hs_moe_held_assignments_per_step"]["value"] <= 8 * 3
    assert got["g4hs_moe_expert_load_max_over_mean_pct"]["value"] >= 100.0
    # two mamba layers' state (2 x 64 x 16 float32) and conv tail (3 x 160
    # bfloat16) a slot
    assert got["slot_state_bytes_per_row"]["value"] == \
        2 * (2 * 64 * 16 * 4 + 3 * 160 * 2)
    # PR 37's six, from the program's span log over the whole window:
    # requests retire and are replaced all through it
    assert WINDOW_SIX <= set(got)
    assert got["admit_to_first_token_ms_p50"]["value"] > 0
    # no device ran here: the readers of the device trace find nothing
    for name in OWN[:4] + OWN[6:] + ("decode_device_ms_p50",):
        assert name not in got


def test_the_eight_metrics_read_a_traced_run_on_a_recorded_device():
    """The device readers over hand-made evidence: the kernels' events
    under `ssd.update` and `g4attn.attend`, instructions mapped to the
    model's scopes, the captured ticks' counters."""
    from perfbench import harness, trace_reduce
    m = Manifest(REPO)
    ops = {"ssd.update.3": 5e6, "g4attn.attend.1": 0.5e6, "fusion.1": 8e6,
           "fusion.2": 1e6, "fusion.3": 2e6, "fusion.4": 1.5e6,
           "fusion.5": 2e6}
    scopes = {
        "ssd.update.3": "jit(step_paged)/layer_0/mamba/ssd.update/x",
        "g4attn.attend.1": "jit(step_paged)/layer_5/attn/g4attn.attend/x",
        "fusion.1": "jit(step_paged)/layer_1/moe/moe.experts/dot",
        "fusion.2": "jit(step_paged)/layer_1/moe/shared/moe.shared/dot",
        "fusion.3": "jit(step_paged)/layer_0/mamba/ssd.project/dot",
        "fusion.4": "jit(step_paged)/layer_5/attn/g4attn.qkv/dot",
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
        samples={}, counters={"serve.traced_tokens_in_pages_mean": 337920.0,
                              "serve.traced_decoding_rows_mean": 48.0,
                              "g4hs.held_assignments_per_step": 240.0,
                              "g4hs.expert_load_max_over_mean_pct": 190.0,
                              "serve.slot_state_bytes_per_row": 38204928.0},
        shapes={"heads": 32, "kv_heads": 8, "head_dim": 128, "layers": 10,
                "attn_layers": 1, "mamba_layers": 9, "ssm_heads": 128,
                "ssm_head_dim": 64, "d_state": 128, "groups": 1,
                "op_scopes": scopes,
                "device_ops_raw": (names, starts, np.array(
                    [ops[n] for n in names]))},
        trace=trace, peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9})
    read = lambda name: m.module(                              # noqa: E731
        "readers", m.layer_metric(name)["reader"]).read(
        m.layer_metric(name), ev)
    # 48 x 9 x (8 388 608 + 66 560) B = 3.653 GB: 4.460 ms at the HBM peak
    assert abs(read("g4hs_ssd_state_update_roofline") - 100 * 4.460 / 5) < 0.1
    # 337 920 x 4 096 B + q and out: 1.385 GB... of which 1.384 the pages:
    # 1.691 ms at the HBM peak, over 0.5 ms: a count that passes 100 is
    # what the driver refuses; this evidence is hand-made to show it read
    assert abs(read("g4hs_kv_decode_roofline") - 100 * 1.6910 / 0.5) < 1.0
    assert read("g4hs_ssd_device_share_pct") == 35.0
    assert read("g4hs_moe_device_share_pct") == 40.0
    assert read("g4hs_shared_expert_device_share_pct") == 5.0
    assert read("g4hs_attn_device_share_pct") == 10.0
    assert read("head_device_share_pct") == 10.0
    assert read("g4hs_moe_held_assignments_per_step") == 240.0
    assert read("g4hs_moe_expert_load_max_over_mean_pct") == 190.0
    assert read("slot_state_bytes_per_row") == 38204928.0


def test_the_rooflines_take_the_counters_of_the_captured_ticks():
    eng = object.__new__(_serve_granite4hs.Engine)
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
    tracer.disturbed = []
    assert eng.traced_counters(tracer, window) == {}
    for name in ("g4hs_ssd_state_update_roofline", "g4hs_kv_decode_roofline"):
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
    from perfbench.tools import control_serve_granite4hs
    rc = control_serve_granite4hs.main([
        "--workload", CELL, "--seeds", "1", "2", "--control", "fp8", "bf16",
        "--control-seeds", "1", "--window-s", "0.5", "--root", root,
        "--cpu"])
    assert rc == 0
    out = capsys.readouterr().out.splitlines()
    rows = [json.loads(ln) for ln in out if ln.startswith("{")]
    assert [(r["seed"], r.get("control")) for r in rows] == [
        (1, "fp8"), (1, "bf16"), (2, None)]
    # the precision below reads wider than the sound program, and fp8
    # wider than bfloat16, over every token (the widest gap of a toy's
    # few hundred tokens is one token's luck)
    assert rows[0]["control_logprob_gap_median"] > \
        2 * rows[0]["served_logprob_gap_median"]
    assert rows[0]["control_logprob_gap_median"] > \
        2 * rows[1]["control_logprob_gap_median"] > 0
    assert "control_logit_gap" not in rows[2]
    assert any(ln.startswith("served_logit_gap_widest: sound max")
               and "fp8 control min" in ln and "bf16 control min" in ln
               for ln in out)


def test_the_reference_takes_logits_at_served_positions_alone():
    """`served_token_gaps` at picked positions, the head over two of them
    at a time and a layer's weights remade from the seed, is the whole
    forward pass's logits at those positions."""
    import jax
    import jax.numpy as jnp
    from perfbench.reference import granite_hybrid
    dims = weights.Dims.from_config(toy_config())
    key = weights.seed_key(2**31 + 1)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 128)
    at = jnp.asarray([[3, 10, 22, 5], [0, 7, 23, 1]])
    whole = granite_hybrid.forward(
        weights.make_params(key, dims, jnp.bfloat16), toks, dims)
    g = granite_hybrid.served_token_gaps(key, toks, at, dims, jnp.bfloat16,
                                         positions=2)
    nxt = np.asarray(toks)[np.arange(2)[:, None], np.minimum(at + 1, 23)]
    picked = np.asarray(whole)[np.arange(2)[:, None], np.asarray(at)]
    want = picked.max(-1) - np.take_along_axis(picked, nxt[..., None],
                                               -1)[..., 0]
    assert np.abs(np.asarray(g["served_gap"]) - want).max() < 1e-4

