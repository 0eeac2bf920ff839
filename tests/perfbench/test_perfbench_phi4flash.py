"""The Phi-4-mini-flash cell's kind, generator, reference, ops count and
tool on the CPU, at toy widths with every kind of layer present, through
`run.py` untouched: a root in a temporary directory whose files stand
beside a link to the repository's `perfbench/`."""
import json

import numpy as np
import pytest

from _perfbench_tiny import GENERIC, REPO, _dump, _load, make_root
from perfbench import run
from perfbench import weights_phi4flash as weights
from perfbench.kinds import _serve_phi4flash
from perfbench.manifest import Manifest
from perfbench.ops import shared_kv_paged_decode

CELL = "tiny-phi4flash"
REAL = "serve-phi4flash-reason-deep"
OWN = ("shared_kv_device_share_pct", "window_attn_device_share_pct",
       "ssm_device_share_pct", "shared_kv_decode_roofline",
       "window_decode_roofline", "slot_state_bytes_per_row")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("perfbench_phi4flash"))
    cfg = _load(REPO, "perfbench", "configs", "phi4-mini-flash.json")
    cfg.update(vocab_size=128, hidden_size=64, intermediate_size=128,
               num_hidden_layers=8, n_layer=8, num_attention_heads=8,
               num_key_value_heads=4, sliding_window=8)
    cfg["assumed"].update(mamba_dt_rank=4)
    _dump(cfg, root, "extra", "configs", "phi4flash-tiny.json")
    t = _load(REPO, "perfbench", "traffic", "reason-deep-closed.json")
    t["engine"].update(slots=8, page_size=4, num_pages=200,
                       chunk_buckets=[4, 8], decode_kernel=False)
    length = lambda median, lo, hi: {"dist": "lognormal",     # noqa: E731
                                     "median": median, "sigma": 0.4,
                                     "min": lo, "max": hi}
    t.update(clients=8, backlog=400, max_total=96,
             first_wave={"context": length(30, 12, 60),
                         "remaining": length(16, 4, 40)},
             prompt=length(6, 3, 12), output=length(30, 16, 60),
             trace_start_s=0.1, trace_seconds=0.3, check_requests=6,
             # bfloat16 program against the float32 reference at toy
             # widths; the altered-token test below reads 1 and more
             limits={"served_logit_gap_widest": 0.05,
                     "served_logprob_gap_widest": 0.05})
    _dump(t, root, "extra", "traffic", "tiny-deep-closed.json")
    bench = _load(root, "BENCHMARK.json")
    real = _load(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "phi4flash-tiny", "source": "none",
                             "file": "extra/configs/phi4flash-tiny.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "phi4flash-tiny",
                               "traffic": "tiny-deep-closed", "chips": 1,
                               "why": "toy"})
    for section in ("end_to_end", "per_layer"):
        for m in real[section]:
            if REAL in m.get("workloads", []):
                next(x for x in bench[section]
                     if x["name"] == m["name"])["workloads"].append(CELL)
    _dump(bench, root, "BENCHMARK.json")
    return root


def test_the_cell_is_in_the_benchmark_with_the_issues_parameters():
    m = Manifest(REPO)
    cell = m.cell(REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4-mini-flash", "reason-deep-closed", 1)
    t = m.traffic(cell["traffic"])
    e = t["engine"]
    assert (t["kind"], t["clients"], t["backlog"], t["max_total"]) == (
        "serve_closed_phi4flash", 64, 64, 16384)
    assert (e["slots"], e["page_size"], e["chunk_buckets"], e["prefix_cache"],
            e["async_decode"], e["decode_kernel"]) == (
        64, 64, [128, 512], False, True, True)
    assert 10000 <= e["num_pages"] <= 11000
    assert t["first_wave"] == {
        "context": {"dist": "lognormal", "median": 6144, "sigma": 0.5,
                    "min": 2048, "max": 14336},
        "remaining": {"dist": "lognormal", "median": 2048, "sigma": 0.8,
                      "min": 256, "max": 8192}}
    assert t["prompt"] == {"dist": "lognormal", "median": 128, "sigma": 0.45,
                           "min": 64, "max": 256}
    assert t["output"] == {"dist": "lognormal", "median": 8192,
                           "sigma": 0.35, "min": 4096, "max": 14336}
    assert (t["check_requests"], t["trace_start_s"], t["trace_seconds"]) == (
        6, 5.0, 8.0)
    cells = m.data["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    by_name = {x["name"]: x for x in m.data["per_layer"]}
    own = [by_name[n] for n in OWN]
    assert all(REAL in x["workloads"] for x in own)
    assert all(x["moves"] == "serve_tokens_per_s" for x in own)
    # its five device metrics are its own; what a slot holds beside its
    # pages is read in every cell whose slots hold state, Phi's among them
    assert all(x["workloads"] == [REAL] for x in own
               if x["name"] != "slot_state_bytes_per_row")
    # the cell joins the generic serving and set-up metrics, and not the
    # roofline whose count (every layer reads every page) is not its own
    lists = {n for n, x in by_name.items()
             if REAL in x.get("workloads", []) and n not in OWN}
    assert GENERIC <= lists and "paged_decode_roofline" not in lists


def test_the_configuration_holds_every_published_key_and_cuts_nothing():
    cfg = Manifest(REPO).config("phi4-mini-flash")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(json.loads(ln) for ln in f if json.loads(ln)["name"]
                     == "Phi-4-mini-flash-reasoning")
    assert {k: cfg.get(k) for k in entry["config"]} == entry["config"]
    assert cfg["reduced"] == [] and cfg["source"] == entry["source_url"]
    dims = weights.Dims.from_config(cfg)
    # the issue's count: 3 853 M parameters, the published "3.8B"
    assert round(dims.param_count() / 1e6) == 3853
    kinds = [dims.kind(l) for l in range(dims.layers)]
    assert [kinds.count(k) for k in weights.KINDS] == [9, 8, 1, 7, 7]


def test_every_seed_serves_the_same_lengths_in_the_same_places():
    t = Manifest(REPO).traffic("reason-deep-closed")
    lengths = lambda reqs: [(len(r.prompt), r.max_new_tokens)  # noqa: E731
                            for r in reqs]
    a = _serve_phi4flash.deep_closed_loop(t, 1, 1000)
    b = _serve_phi4flash.deep_closed_loop(t, 2**31 + 7, 1000)
    assert lengths(a[0]) == lengths(b[0]) and lengths(a[1]) == lengths(b[1])
    assert a[0][0].prompt != b[0][0].prompt
    first, backlog = a
    assert len(first) == len(backlog) == 64
    assert all(2048 <= p <= 14336 and 256 <= n <= 8192 and p + n <= 16384
               for p, n in lengths(first))
    assert all(64 <= p <= 256 and 4096 <= n <= 14336
               for p, n in lengths(backlog))
    other = _serve_phi4flash.deep_closed_loop({**t, "placement": 5}, 1, 1000)
    assert sorted(lengths(other[0]))[0][0] == sorted(lengths(first))[0][0]
    assert lengths(other[0]) != lengths(first)
    # every reservation fits the pool, with the replacements' too
    need = lambda p, n: (p - 2 + n) // 64 + 1                 # noqa: E731
    held = sum(need(*x) for x in lengths(first))
    spare = t["engine"]["num_pages"] - 1 - held
    assert spare > 5 * max(need(*x) for x in lengths(backlog))


def test_the_ops_count_is_the_hand_count():
    # 1000 cached tokens in whole pages over 3 rows, 8 reads: a position
    # is 10 pairs x (128 K + 128 V) x 2 bytes = 5 120 bytes; q and out are
    # 40 x 128 x 2 bytes each a row; 4 x 40 x 128 products a token
    ops, moved = shared_kv_paged_decode.ops_and_bytes(
        tokens_in_pages=1000, rows=3, heads=40, kv_pairs=10, pair_dim=128,
        reads=8)
    assert moved == 8 * (1000 * 5120 + 2 * 3 * 40 * 128 * 2)
    assert ops == 8 * 4 * 40 * 128 * 1000


def test_the_phi4flash_cell_runs_and_is_correct(root):
    result = run.run_cell(root, CELL, 2**31 + 5, 0.8, False,
                          require_tpu=False)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8


def test_a_traced_run_reports_what_a_slot_holds(root):
    result = run.run_cell(root, CELL, 7, 0.8, True, require_tpu=False)
    got = result["metrics"]
    assert "slot_occupancy_pct" in got
    # two rings of 12 positions x 64 values and three layers' state of
    # 16 x 128 float32 + 3 x 128, in bfloat16 but the state
    assert got["slot_state_bytes_per_row"]["value"] == (
        2 * 12 * 64 * 2 + 3 * (16 * 128 * 4 + 3 * 128 * 2))
    # no device ran here: the readers of the device trace find nothing
    for name in OWN[:5] + ("decode_device_ms_p50",):
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


def test_the_control_tool_reads_sound_and_control_gaps(root, capsys):
    from perfbench.tools import control_serve_phi4flash
    rc = control_serve_phi4flash.main([
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
    """`served_token_gaps` at picked positions is the whole forward pass's
    logits at those positions."""
    import jax
    import jax.numpy as jnp
    from perfbench.reference import phi4_flash
    cfg = _load(REPO, "perfbench", "configs", "phi4-mini-flash.json")
    cfg.update(vocab_size=128, hidden_size=64, intermediate_size=128,
               num_hidden_layers=8, num_attention_heads=8,
               num_key_value_heads=4, sliding_window=8)
    cfg["assumed"].update(mamba_dt_rank=4)
    dims = weights.Dims.from_config(cfg)
    key = weights.seed_key(2**31 + 1)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 128)
    at = jnp.asarray([[3, 10, 22], [0, 7, 23]])
    whole = phi4_flash.forward(
        weights.make_params(key, dims, jnp.bfloat16), toks, dims)
    g = phi4_flash.served_token_gaps(key, toks, at, dims, jnp.bfloat16)
    nxt = np.asarray(toks)[np.arange(2)[:, None], np.minimum(at + 1, 23)]
    picked = np.asarray(whole)[np.arange(2)[:, None], np.asarray(at)]
    want = picked.max(-1) - np.take_along_axis(picked, nxt[..., None],
                                               -1)[..., 0]
    assert np.abs(np.asarray(g["served_gap"]) - want).max() < 1e-5
