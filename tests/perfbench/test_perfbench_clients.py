"""The clients of a closed loop (`perfbench/kinds/_serve.py::Clients`, the
one place where a closed-loop kind sends its requests): while the mix's
backlog lasts they send what every PR before sent, byte for byte; past its
end they send its lengths again under fresh token ids, so a program that
serves faster than the backlog was sized for still finishes its window."""
import glob
import hashlib
import importlib
import json
import os

import pytest

from _perfbench_tiny import REPO, _dump, _load, make_root
from perfbench import generators, run
from perfbench.kinds import _serve
from perfbench.manifest import Manifest
from test_perfbench_falconh1 import toy_config, toy_length, toy_traffic

#: sha256 of `first + backlog` (ids, token ids, output lengths, arrivals)
#: of every closed-loop traffic file at SEED, as commit 7327ac6 (PR 46)
#: makes them: what the cells send while their backlogs last
SEED = 2**31 + 47
PINNED = {
    "reason-closed":
        "505bc14e3db51366adc5abc3fc0201358b1a4687cf86edc4efe0f5d5eb5265a2",
    "reason-long-closed":
        "2eecd0156442af9ca57f3f7f09045b29f46f7cd4f2207ae0c412c0e097887b14",
    "reason-deep-closed":
        "dbd46b3340ddfb634264164dc8105fb910f87292e03edbe1981b8feec12cf607",
    "longform-steady-closed":
        "c979cf04b94f11b3a703dabdaf0ff1e57c8118f7407ee33fc58c94dfcc38e641",
    "longdoc-deep-closed":
        "2b14d7e055a020de72c849dcd6627d5a755aa91866a9b1a76348273ffd2436df",
    "sessions-deep-closed":
        "2503efac497c712835bec953efe10a1917791c07e35de3e78ef31d3872788469",
    "sessions-wide-closed":
        "1d628a2b334fdb5bdb54b75fb4de8fc5e5f1bc2a4373999cc0aa697a3de98c2a"}
PLAIN, STATE = "tiny-closed-laps", "tiny-falconh1-laps"


def _generator(kind: str):
    if kind in ("serve_closed", "serve_closed_longcat"):
        return generators.closed_loop
    return importlib.import_module(
        "perfbench.kinds._serve_" + kind[len("serve_closed_"):]
    ).deep_closed_loop


@pytest.mark.parametrize("traffic", sorted(PINNED))
def test_first_wave_and_backlog_are_the_parents_byte_for_byte(traffic):
    m = Manifest(REPO)
    cell = next(c for c in m.data["workloads"] if c["traffic"] == traffic)
    t = m.traffic(traffic)
    first, backlog = _generator(t["kind"])(
        t, SEED, int(m.config(cell["config"])["vocab_size"]))
    assert (len(first), len(backlog)) == (t["clients"], t["backlog"])
    body = json.dumps([len(first)] + [
        [r.id, r.prompt, r.max_new_tokens, r.arrival]
        for r in first + backlog])
    assert hashlib.sha256(body.encode()).hexdigest() == PINNED[traffic]


def test_a_laps_request_has_the_backlogs_lengths_and_ids_of_its_own():
    t = Manifest(REPO).traffic("reason-closed")
    first, backlog = generators.closed_loop(t, 7, 50257)
    n0, seen = len(first) + len(backlog), set()
    for k in (0, 1, len(backlog) - 1, len(backlog), 3 * len(backlog) + 5):
        r = generators.lap_request(backlog, k, 7, 50257)
        src = backlog[k % len(backlog)]
        assert (r.id, len(r.prompt), r.max_new_tokens) == (
            n0 + k, len(src.prompt), src.max_new_tokens)
        assert r.prompt != src.prompt and 0 <= min(r.prompt) \
            and max(r.prompt) < 50257
        # the same (seed, k) draws the same ids, whatever was drawn before
        assert r.prompt == generators.lap_request(backlog, k, 7,
                                                  50257).prompt
        assert r.prompt != generators.lap_request(backlog, k, 8,
                                                  50257).prompt
        seen.add(tuple(r.prompt[:64]))
    assert len(seen) == 5
    # the draws of a lap leave the first wave's and the backlog's alone
    again = generators.closed_loop(t, 7, 50257)
    assert [r.prompt for r in again[0] + again[1]] == [
        r.prompt for r in first + backlog]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root with two closed loops whose backlogs a CPU run spends
    many times over: the plain kind with the prefix cache on and a
    backlog of 3, Falcon-H1's kind (state a slot) with a backlog of 2."""
    root = make_root(tmp_path_factory.mktemp("perfbench_clients"))
    plain = _load(root, "extra", "traffic", "tiny-closed.json")
    assert plain["engine"]["prefix_cache"] is True
    plain.update(backlog=3, check_requests=6)
    _dump(plain, root, "extra", "traffic", PLAIN + ".json")
    _dump(toy_config(), root, "extra", "configs", "falconh1-tiny.json")
    _dump(toy_traffic(backlog=2, output=toy_length(12, 8, 20),
                      first_wave={"context": toy_length(20, 8, 40),
                                  "remaining": toy_length(8, 4, 16)}),
          root, "extra", "traffic", STATE + ".json")
    bench = _load(root, "BENCHMARK.json")
    bench["configs"].append({"name": "falconh1-tiny", "source": "none",
                             "file": "extra/configs/falconh1-tiny.json",
                             "reduced": ["num_hidden_layers"], "why": "toy"})
    bench["workloads"] += [
        {"name": PLAIN, "config": "gpt2-tiny-serve", "traffic": PLAIN,
         "chips": 1, "why": "toy"},
        {"name": STATE, "config": "falconh1-tiny", "traffic": STATE,
         "chips": 1, "why": "toy"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-closed" in m.get("workloads", []):
            m["workloads"] += [PLAIN, STATE]
    _dump(bench, root, "BENCHMARK.json")
    return root


@pytest.mark.parametrize("cell", [PLAIN, STATE])
def test_a_closed_loop_laps_its_backlog_and_ends_correct(root, cell,
                                                         monkeypatch):
    made, compared = [], []
    init, pick = _serve.Clients.__init__, _serve.pick_sample

    def spy_init(self, *a, **kw):
        init(self, *a, **kw)
        made.append(self)

    def spy_pick(*a, **kw):
        compared.append(pick(*a, **kw))
        return compared[-1]
    monkeypatch.setattr(_serve.Clients, "__init__", spy_init)
    monkeypatch.setattr(_serve, "pick_sample", spy_pick)
    seed = 2**31 + 29
    result = run.run_cell(root, cell, seed, 1.0, False, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    clients, = made
    backlog, n0 = clients.backlog, clients.backlog[-1].id + 1
    laps = clients.lapped / len(backlog)
    assert laps >= 3, f"{clients.lapped} requests past a backlog of " \
                      f"{len(backlog)}: lengthen the run"
    assert result["attempted"] == clients.sent == 8 + clients.answered
    # every request sent past the end: the backlog's lengths, in its
    # order, under ids that go on counting and token ids of its own
    assert sorted(i for i in clients.prompts if i >= n0) == list(
        range(n0, n0 + clients.lapped))
    done = clients.engine.session_results()
    finished_laps = 0
    for k in range(clients.lapped):
        src, prompt = backlog[k % len(backlog)], clients.prompts[n0 + k]
        assert len(prompt) == len(src.prompt) and prompt != src.prompt
        if n0 + k in done:
            finished_laps += 1
            assert done[n0 + k].finish_reason == "length"
            assert len(done[n0 + k].tokens) == src.max_new_tokens
    assert finished_laps >= 2 * len(backlog)
    # no two prompts of the backlog and the laps begin alike: nothing for
    # a prefix cache to find, on the first page or before
    page = Manifest(root).traffic(cell)["engine"]["page_size"]
    later = [tuple(clients.prompts[i][:page]) for i in sorted(
        clients.prompts) if i >= 8]
    assert len(set(later)) == len(later)
    if cell == PLAIN:
        assert clients.eng.engine.config.prefix_cache is True
        assert clients.eng.telemetry.prefix_hit_pages.value == 0
    # requests from past the end are among those held to the reference
    sample, = compared
    assert [r.id for r in sample if r.id >= n0]


def test_clients_refuse_a_loop_without_a_backlog():
    with pytest.raises(ValueError, match="backlog"):
        _serve.Clients(None, [], [], 1)


def test_no_kind_keeps_a_client_loop_of_its_own():
    kinds = os.path.join(REPO, "perfbench", "kinds")
    closed = 0
    for path in sorted(glob.glob(os.path.join(kinds, "*.py"))):
        name = os.path.basename(path)
        with open(path) as f:
            src = f.read()
        assert "backlog ran out" not in src, name
        if name == "_serve.py":
            assert src.count("def answer_completions(") == 1
            continue
        assert "def answer_completions" not in src, name
        if name.startswith("serve_closed"):
            closed += 1
            assert ".submit(" not in src and "backlog.pop" not in src, name
            assert "_serve.Clients(" in src or "import run_loop" in src, name
    assert closed >= 7
