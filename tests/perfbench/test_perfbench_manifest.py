"""BENCHMARK.json against the contract it is held to, and the files its
names lead to."""
import os
import re

import pytest

from perfbench.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(REPO)


@pytest.fixture(scope="module")
def bench(manifest):
    return manifest.data


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert len(bench["command"]) <= 32
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    cells = bench["workloads"]
    four = sum(1 for c in cells if c["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    assert all(c["chips"] in (1, 4) for c in cells)
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_unique_and_well_formed(bench, section):
    names = [e["name"] for e in bench[section]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("section,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"})])
def test_entries_have_just_the_keys_of_the_contract(bench, section, keys):
    for e in bench[section]:
        assert set(e) - {"workloads"} == keys, e["name"]
        for field in ("why", "layer", "source"):
            if field in e:
                assert 1 <= len(e[field]) <= 200 and "\n" not in e[field] \
                    and "\t" not in e[field]


def test_metrics_units_bounds_and_sources(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_cell_reports_set_up_another_metric_and_a_layer(manifest,
                                                              bench):
    for cell in bench["workloads"]:
        e2e = [m["name"] for m in manifest.metrics_for("end_to_end",
                                                       cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        assert manifest.metrics_for("per_layer", cell["name"]), cell["name"]


def test_each_layer_metric_moves_a_metric_its_cells_report(manifest, bench):
    cells = [c["name"] for c in bench["workloads"]]
    for m in bench["per_layer"]:
        moved = [e for e in bench["end_to_end"] if e["name"] == m["moves"]]
        assert len(moved) == 1, m["name"]
        reporting = set(moved[0].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reporting, m["name"]
        assert set(m.get("workloads", cells)) <= set(cells)


def test_roofline_names_follow_the_contract(bench):
    for m in bench["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_every_cells_files_exist_and_parse(manifest, bench):
    for cell in bench["workloads"]:
        config = manifest.config(cell["config"])
        assert config["n_layer"] > 0
        entry = [c for c in bench["configs"] if c["name"] == cell["config"]]
        assert entry[0]["file"].startswith(tuple(bench["paths"]))
        assert sorted(config["reduced"]) == sorted(entry[0]["reduced"])
        traffic = manifest.traffic(cell["traffic"])
        assert os.path.isfile(manifest.find("kinds",
                                            traffic["kind"] + ".py"))
        assert "limits" in traffic and len(traffic["why"]) > 20
    used = {c["config"] for c in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


def test_every_layer_metric_has_a_file_and_a_reader(manifest, bench):
    for m in bench["per_layer"]:
        spec = manifest.layer_metric(m["name"])
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert spec["source"] == m["source"]
        assert hasattr(manifest.module("readers", spec["reader"]), "read")


def test_files_under_paths_are_named_from_allowed_characters(bench):
    for p in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert PATH.match(rel), rel


#: a key that names a width; `num_hidden_layers` holds "hidden" and is the
#: published key for DEPTH, which every cut configuration rightly lists
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|n_embd|"
                   r"n_inner|head_dim|n_head$")


def test_reduced_names_no_width(bench):
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if WIDTH.search(k)]


@pytest.mark.parametrize("key,is_width", [
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("head_dim", True),
    ("qk_rope_head_dim", True), ("kv_lora_rank", True), ("n_embd", True),
    ("n_inner", True), ("n_head", True), ("num_hidden_layers", False),
    ("num_layers", False), ("n_layer", False), ("n_routed_experts", False),
    ("vocab_size", False), ("layer_types", False)])
def test_the_width_pattern_tells_a_width_from_depth(key, is_width):
    assert bool(WIDTH.search(key)) is is_width


def test_full_check_fits_the_time_allowed(bench):
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (bench["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200
