"""The traffic generator: seeds do not change the work. In an open loop
they permute it; in a closed loop the mix places it."""
import json
import os

import pytest

from perfbench import generators

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mix(name):
    with open(os.path.join(REPO, "perfbench", "traffic", name + ".json")) as f:
        return json.load(f)


def _open(seed, seconds=51.0):
    t = _mix("chat-open")
    return t, generators.open_loop(t, seed, seconds, 50257, t["ramp_s"],
                                   t["tail_s"])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_requests(seed):
    _, a = _open(seed)
    _, b = _open(seed)
    assert [(r.id, r.prompt, r.max_new_tokens, r.arrival) for r in a] == \
        [(r.id, r.prompt, r.max_new_tokens, r.arrival) for r in b]


@pytest.mark.parametrize("part", ["ramp", "window", "tail"])
def test_every_seed_offers_the_same_lengths_in_each_part(part):
    def lengths(seed):
        t, reqs = _open(seed)
        lo, hi = {"ramp": (-t["ramp_s"], 0.0), "window": (0.0, 51.0),
                  "tail": (51.0, 51.0 + t["tail_s"])}[part]
        pick = [r for r in reqs if lo <= r.arrival < hi]
        return (sorted(len(r.prompt) for r in pick),
                sorted(r.max_new_tokens for r in pick))
    first = lengths(1)
    assert first[0], "no request in this part"
    for seed in (2, 3, 2**31 + 5):
        assert lengths(seed) == first


def test_seeds_differ_in_order_and_tokens():
    t = _mix("chat-open")
    a = generators.open_loop(t, 1, 51.0, 50257, 20.0, 15.0)
    b = generators.open_loop(t, 2, 51.0, 50257, 20.0, 15.0)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.arrival for r in a] != [r.arrival for r in b]
    assert a[0].prompt != b[0].prompt


def test_due_times_lie_inside_ramp_window_and_tail():
    t, reqs = _open(3)
    rate = t["arrivals"]["rate_per_s"]
    arrivals = [r.arrival for r in reqs]
    assert arrivals == sorted(arrivals)
    assert min(arrivals) >= -t["ramp_s"]
    assert max(arrivals) < 51.0 + t["tail_s"]
    in_window = [a for a in arrivals if 0.0 <= a < 51.0]
    assert len(in_window) == round(rate * 51.0)


@pytest.mark.parametrize("mix", ["chat-open", "reason-closed"])
def test_lengths_keep_to_the_mix(mix):
    t = _mix(mix)
    for spec in (t["prompt"], t["output"]):
        q = generators.length_quantiles(spec, 101)
        assert q == sorted(q)
        assert spec["min"] <= q[0] and q[-1] <= spec["max"]
        assert abs(q[50] - spec["median"]) <= 1
    assert t["prompt"]["max"] + t["output"]["max"] <= t["max_total"]


@pytest.mark.parametrize("process,extra", [
    ("poisson", {}), ("gamma", {"cv": 3.0}), ("uniform", {})])
def test_gaps_span_the_same_time_for_every_process(process, extra):
    spec = {"process": process, "rate_per_s": 2.0, **extra}
    gaps = generators.gap_quantiles(spec, 100, 50.0)
    assert gaps.min() > 0
    assert abs(gaps.sum() - 50.0) < 1e-9


def test_closed_loop_first_wave_is_spread_and_seeded():
    t = _mix("reason-closed")
    first, backlog = generators.closed_loop(t, 5, 50257)
    again, _ = generators.closed_loop(t, 5, 50257)
    assert [r.prompt for r in first] == [r.prompt for r in again]
    assert len(first) == t["clients"] and len(backlog) == t["backlog"]
    outs = sorted(r.max_new_tokens for r in first)
    assert outs[0] < 64 and outs[-1] > 256     # spread, not bunched
    other, _ = generators.closed_loop(t, 6, 50257)
    assert sorted(len(r.prompt) for r in first + backlog) == sorted(
        len(r.prompt) for r in other + _)
    for r in first + backlog:
        assert len(r.prompt) + r.max_new_tokens <= t["max_total"]


def test_closed_loop_places_lengths_by_the_mix_not_the_seed():
    t = _mix("reason-closed")
    a, rest_a = generators.closed_loop(t, 1, 50257)
    b, rest_b = generators.closed_loop(t, 2**31 + 7, 50257)
    assert [(len(r.prompt), r.max_new_tokens) for r in a + rest_a] == \
        [(len(r.prompt), r.max_new_tokens) for r in b + rest_b]
    assert a[0].prompt != b[0].prompt        # the seed draws the tokens


def test_another_placement_is_the_same_lengths_in_other_places():
    t = _mix("reason-closed")
    a, rest_a = generators.closed_loop(t, 1, 50257)
    b, rest_b = generators.closed_loop(dict(t, placement=t["placement"] + 1),
                                       1, 50257)
    here = [len(r.prompt) for r in a + rest_a]
    there = [len(r.prompt) for r in b + rest_b]
    assert here != there and sorted(here) == sorted(there)
