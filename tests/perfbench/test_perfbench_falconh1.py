"""The Falcon-H1 cell's kind, generator, reference, ops count and tool on
the CPU, at toy widths, through `run.py` untouched: a root in a temporary
directory whose files stand beside a link to the repository's
`perfbench/`. Nothing here counts the benchmark's cells."""
import gc
import json
import types

import numpy as np
import pytest

from _perfbench_tiny import (GENERIC, REPO, WINDOW_SIX, _dump, _load,
                             make_root)
from perfbench import run
from perfbench import weights_falconh1 as weights
from perfbench.kinds import _serve_falconh1
from perfbench.manifest import Manifest
from perfbench.ops import paged_decode, ssd_state_update

CELL = "tiny-falconh1"
REAL = "serve-falconh1-4of72-longform"
OWN = ("ssd_device_share_pct", "ssd_state_update_roofline",
       "h1_attn_device_share_pct", "h1_kv_decode_roofline",
       "head_device_share_pct")
TOY = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
           num_hidden_layers=2, n_layer=2, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, mamba_d_ssm=64,
           mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
           mamba_chunk_size=8)


def toy_config():
    cfg = _load(REPO, "perfbench", "configs", "falcon-h1-34b-4of72.json")
    cfg.update(TOY)
    return cfg


def toy_length(median, lo, hi):
    return {"dist": "lognormal", "median": median, "sigma": 0.4, "min": lo,
            "max": hi}


def toy_traffic(**over):
    t = _load(REPO, "perfbench", "traffic", "longform-steady-closed.json")
    t["engine"].update(slots=8, page_size=4, num_pages=200,
                       chunk_buckets=[8], decode_kernel=False)
    t.update(clients=8, backlog=400, max_total=96,
             first_wave={"context": toy_length(20, 8, 40),
                         "remaining": toy_length(16, 4, 40)},
             prompt=toy_length(6, 3, 8), output=toy_length(30, 16, 60),
             trace_start_s=0.1, trace_seconds=0.3, check_requests=6,
             # bfloat16 program against the float32 reference at toy
             # widths; the altered-token test below reads 1 and more
             limits={"served_logit_gap_widest": 0.05,
                     "served_logprob_gap_widest": 0.05})
    t.update(over)
    return t


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("perfbench_falconh1"))
    _dump(toy_config(), root, "extra", "configs", "falconh1-tiny.json")
    _dump(toy_traffic(), root, "extra", "traffic",
          "tiny-longform-closed.json")
    bench = _load(root, "BENCHMARK.json")
    real = _load(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "falconh1-tiny", "source": "none",
                             "file": "extra/configs/falconh1-tiny.json",
                             "reduced": ["num_hidden_layers"], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "falconh1-tiny",
                               "traffic": "tiny-longform-closed", "chips": 1,
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
        "falcon-h1-34b-4of72", "longform-steady-closed", 1)
    t = m.traffic(cell["traffic"])
    e = t["engine"]
    assert (t["kind"], t["clients"], t["backlog"], t["max_total"]) == (
        "serve_closed_falconh1", 96, 384, 4096)
    assert (e["slots"], e["page_size"], e["chunk_buckets"], e["prefix_cache"],
            e["async_decode"], e["decode_kernel"]) == (
        96, 64, [128], False, True, True)
    first = t["first_wave"]
    assert first["context"] == {"dist": "lognormal", "median": 512,
                                "sigma": 0.6, "min": 128, "max": 1024}
    assert {k: first["remaining"][k] for k in ("dist", "sigma", "min",
                                               "max")} == {
        "dist": "lognormal", "sigma": 0.4, "min": 256, "max": 3072}
    assert t["prompt"] == {"dist": "lognormal", "median": 96, "sigma": 0.35,
                           "min": 32, "max": 128}
    assert t["output"] == {"dist": "lognormal", "median": 2560,
                           "sigma": 0.25, "min": 2048, "max": 3072}
    assert t["check_requests"] == 6 and "placement" in t
    own = [x for x in m.data["per_layer"] if x.get("workloads") == [REAL]]
    assert tuple(x["name"] for x in own) == OWN
    assert all(x["moves"] == "serve_tokens_per_s" for x in own)
    # the cell joins the generic serving and set-up metrics, the six that
    # read the whole window from the span log (PR 47: sixteen admissions a
    # traced window) and what a slot holds, and not the roofline whose
    # event pattern is gpt2-xl's
    lists = {x["name"] for x in m.data["per_layer"]
             if REAL in x.get("workloads", []) and x not in own}
    assert GENERIC | WINDOW_SIX | {"slot_state_bytes_per_row"} <= lists
    assert "paged_decode_roofline" not in lists
    assert REAL in next(x for x in m.data["end_to_end"]
                        if x["name"] == "serve_tokens_per_s")["workloads"]


def test_the_configuration_holds_every_published_key_and_cuts_depth_alone():
    cfg = Manifest(REPO).config("falcon-h1-34b-4of72")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(json.loads(ln) for ln in f if json.loads(ln)["name"]
                     == "Falcon-H1-34B-Instruct")
    differs = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_layer"]) == (4, 4)
    assert cfg["published"] == {"num_hidden_layers": 72}
    assert cfg["source"] == entry["source_url"]
    assert "18 pipeline stages of 4 layers" in cfg["deployment"]
    dims = weights.Dims.from_config(cfg)
    # the issue's count: 430.1 M a layer, 1 336.9 M the table and the head
    layer = (dims.param_count() - 2 * dims.vocab * dims.hidden
             - dims.hidden) / dims.layers
    assert round(layer / 1e5) == 4301
    assert round(dims.vocab * dims.hidden / 1e5) == 13369
    assert round(2 * dims.param_count() / 1e7) == 879          # 8.79 GB
    assert dims.in_proj_segments == (4096, 4096, 512, 512, 32)
    assert dims.vocab % dims.row_blocks == 0 and dims.row_blocks < dims.vocab


def test_every_seed_serves_the_same_lengths_in_the_same_places():
    t = Manifest(REPO).traffic("longform-steady-closed")
    lengths = lambda reqs: [(len(r.prompt), r.max_new_tokens)  # noqa: E731
                            for r in reqs]
    a = _serve_falconh1.deep_closed_loop(t, 1, 1000)
    b = _serve_falconh1.deep_closed_loop(t, 2**31 + 7, 1000)
    assert lengths(a[0]) == lengths(b[0]) and lengths(a[1]) == lengths(b[1])
    assert a[0][0].prompt != b[0][0].prompt
    first, backlog = a
    assert (len(first), len(backlog)) == (96, 384)
    assert all(128 <= p <= 1024 and 256 <= n <= 3072 and p + n <= 4096
               for p, n in lengths(first))
    assert all(32 <= p <= 128 and 2048 <= n <= 3072
               for p, n in lengths(backlog))
    # a replacement is ONE [96, 128] call
    assert max(p for p, _ in lengths(backlog)) - 1 <= 128
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


def test_the_ops_counts_are_the_hand_counts():
    # 90 rows over 4 layers: a state is 32 x 256 x 128 float32 = 4 194 304
    # B, in and out; beside it x and y (32 x 128 each), 32 steps, and B
    # and C of 2 x 256, float32; five operations a state element
    ops, moved = ssd_state_update.ops_and_bytes(
        rows=90, layers=4, ssm_heads=32, head_dim=128, d_state=256, groups=2)
    assert moved == 90 * 4 * (2 * 4194304 + (2 * 4096 + 32 + 1024) * 4)
    assert ops == 90 * 4 * 5 * 32 * 128 * 256
    # the accepted count, at this model's sizes: a cached position is 4
    # heads x (128 K + 128 V) x 2 B = 2 048 B a layer
    ops, moved = paged_decode.ops_and_bytes(
        tokens_in_pages=1000, rows=3, heads=20, kv_heads=4, head_dim=128,
        layers=4)
    assert moved == 4 * (1000 * 2048 + 2 * 3 * 20 * 128 * 2)
    assert ops == 4 * 4 * 20 * 128 * 1000


def test_the_falconh1_cell_runs_and_is_correct(root):
    result = run.run_cell(root, CELL, 2**31 + 5, 0.8, False,
                          require_tpu=False)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8


def test_the_collector_rests_through_the_window_and_no_longer(
        root, monkeypatch):
    """A collection's pause lands on a tick with a few milliseconds of
    slack: from the first wave's end to the window's the cyclic collector
    stands still, over a heap collected and frozen; set-up before and the
    reference after run with it."""
    seen = []
    real = _serve_falconh1.Engine.tick

    def tick(self):
        seen.append((gc.isenabled(), gc.get_freeze_count()))
        return real(self)
    monkeypatch.setattr(_serve_falconh1.Engine, "tick", tick)
    assert gc.isenabled()
    # what the window finishes on a loaded CPU is another test's business
    run.run_cell(root, CELL, 11, 0.5, False, require_tpu=False)
    on = [enabled for enabled, _ in seen]
    assert on[0] and not on[-1]
    # one change of regime: at the first wave's end
    assert sum(1 for a, b in zip(on, on[1:]) if a != b) == 1
    frozen = seen[-1][1]
    assert frozen > seen[0][1] + 10_000
    assert gc.isenabled() and gc.get_freeze_count() < frozen - 10_000
    with pytest.raises(ZeroDivisionError):
        with _serve_falconh1.collector_at_rest():
            1 / 0
    assert gc.isenabled() and gc.get_freeze_count() < frozen - 10_000


def test_a_traced_run_reports_what_a_slot_holds(root):
    result = run.run_cell(root, CELL, 7, 0.8, True, require_tpu=False)
    got = result["metrics"]
    assert "slot_occupancy_pct" in got and "prefill_rows_per_call" in got
    # two layers' state of 4 x 16 x 16 float32 and conv tail of 3 x 128
    # bfloat16
    assert got["slot_state_bytes_per_row"]["value"] == (
        2 * (4 * 16 * 16 * 4 + 3 * 128 * 2))
    # no device ran here: the readers of the device trace find nothing
    for name in OWN + ("decode_device_ms_p50",):
        assert name not in got


def test_the_rooflines_take_the_counters_of_the_captured_ticks():
    """Contexts grow all through the window: the kernels of the traced
    sub-window are set against the pages THEIR ticks held, not against
    the window's mean."""
    eng = object.__new__(_serve_falconh1.Engine)
    eng.engine = types.SimpleNamespace(config=types.SimpleNamespace(slots=4))
    eng.slot_state = 1.0
    eng.tick_at = [0.0, 1.0, 2.0, 3.0, 4.0]
    eng.tick_prefilled_rows = [0] * 5
    eng.tick_occupied = [4] * 5
    eng.tick_tokens_in_pages = [100, 200, 300, 400, 500]
    eng.tick_decoding_rows = [4, 4, 4, 3, 4]
    window = eng.window_counters(0.0, 5.0)
    assert window["serve.tokens_in_pages_mean"] == 300
    tracer = types.SimpleNamespace(disturbed=[(2.1, 2.9), (4.5, 4.8)])
    assert eng.traced_counters(tracer, window) == {
        "serve.traced_tokens_in_pages_mean": 450.0,
        "serve.traced_decoding_rows_mean": 3.5}
    # no tick began inside the capture: the window's means
    tracer.disturbed = [(2.1, 2.2), (2.3, 2.4)]
    assert eng.traced_counters(tracer, window)[
        "serve.traced_tokens_in_pages_mean"] == 300
    # not traced, or a capture that never closed: nothing
    tracer.disturbed = []
    assert eng.traced_counters(tracer, window) == {}
    for name in ("h1_kv_decode_roofline", "ssd_state_update_roofline"):
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
    from perfbench.tools import control_serve_falconh1
    rc = control_serve_falconh1.main([
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


def test_the_reference_takes_logits_at_served_positions_alone():
    """`served_token_gaps` at picked positions, the head over two of them
    at a time and the table and head a block of rows at a time, is the
    whole forward pass's logits at those positions."""
    import jax
    import jax.numpy as jnp
    from perfbench.reference import falcon_h1
    dims = weights.Dims.from_config(toy_config())
    key = weights.seed_key(2**31 + 1)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 128)
    at = jnp.asarray([[3, 10, 22, 5], [0, 7, 23, 1]])
    whole = falcon_h1.forward(
        weights.make_params(key, dims, jnp.bfloat16), toks, dims)
    g = falcon_h1.served_token_gaps(key, toks, at, dims, jnp.bfloat16,
                                    positions=2)
    nxt = np.asarray(toks)[np.arange(2)[:, None], np.minimum(at + 1, 23)]
    picked = np.asarray(whole)[np.arange(2)[:, None], np.asarray(at)]
    want = picked.max(-1) - np.take_along_axis(picked, nxt[..., None],
                                               -1)[..., 0]
    # float32 both ways, jitted a layer at a time against one program
    assert np.abs(np.asarray(g["served_gap"]) - want).max() < 1e-4


def test_the_table_and_the_head_are_drawn_a_block_of_rows_at_a_time(
        monkeypatch):
    """At the real size neither is ever drawn whole; here the block is cut
    to 32 rows of 128, and the rows are the tree's own."""
    import jax.numpy as jnp
    monkeypatch.setattr(weights, "ROW_BLOCKS", 32)
    dims = weights.Dims.from_config(toy_config())
    assert dims.row_blocks == 32
    key = weights.seed_key(11)
    tree = weights.make_params(key, dims, jnp.bfloat16)
    assert tree["embedding"].shape == tree["lm_head"].shape == (128, 64)
    for block in (0, 3):
        rows = slice(32 * block, 32 * block + 32)
        assert np.array_equal(
            np.asarray(tree["lm_head"][rows], np.float32),
            np.asarray(weights.head_rows(key, dims, jnp.bfloat16, block),
                       np.float32))
        assert np.array_equal(
            np.asarray(tree["embedding"][rows], np.float32),
            np.asarray(weights.table_rows(key, dims, jnp.bfloat16, block),
                       np.float32))
    # the scales the multipliers ask for: 0.02 / m
    assert abs(float(jnp.std(tree["lm_head"].astype(jnp.float32)))
               - 0.02 / dims.lm_head_multiplier) < 0.1
    assert abs(float(jnp.std(tree["embedding"].astype(jnp.float32)))
               - 0.02 / dims.embedding_multiplier) < 2e-4
