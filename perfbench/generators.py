"""The one general generator of serving traffic.

A traffic mix is a file of parameters (`perfbench/traffic/<name>.json`);
this module turns it and `--seed` into requests. What makes two seeds
comparable: every seed gets the SAME multiset of prompt lengths, of output
lengths and of arrival gaps — the quantiles of their distributions at the
mix's request count. In an open loop the seed permutes each of them and
draws the token ids: the work offered in a window is the same for every
seed, in another order. In a closed loop the order decides which requests
retire inside the window and which prompts replace them, so another order
is other work: there the mix's `placement` — a number of the mix, like its
medians — places the lengths, the same for every seed, and the seed draws
the token ids (and, in the kinds, the weights).

Length distributions (`"dist"`): `lognormal` (median, sigma), `uniform`
(min, max), `constant` (value); each clipped to [min, max] and rounded.
Arrival processes: `poisson` (exponential gaps), `gamma` (gaps with a
coefficient of variation `cv`; cv 1 is Poisson), `uniform` (even gaps).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class GenRequest:
    id: int
    prompt: List[int]
    max_new_tokens: int
    arrival: float          # seconds from the window's opening (< 0: ramp)


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_quantiles(spec: Dict, n: int) -> List[int]:
    """The n quantiles of a length distribution, ascending."""
    u = _quantile_points(n)
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + (spec["max"] - spec["min"]) * u
    elif dist == "constant":
        vals = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", math.inf)
    return [int(round(float(min(max(v, lo), hi)))) for v in vals]


def gap_quantiles(spec: Dict, n: int, span: float) -> np.ndarray:
    """The n quantiles of the gap distribution, scaled to sum to `span`:
    the arrivals fill the same time for every seed."""
    u = _quantile_points(n)
    process = spec["process"]
    if process == "poisson":
        gaps = -np.log1p(-u)
    elif process == "gamma":
        shape = 1.0 / float(spec["cv"]) ** 2
        # quantiles by sorting a large fixed sample: no scipy here
        sample = np.sort(np.random.default_rng(12345).gamma(
            shape, 1.0, 200_000))
        gaps = sample[(u * len(sample)).astype(int)]
    elif process == "uniform":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return gaps * span / gaps.sum()


def _permuted(values, rng):
    values = list(values)
    order = rng.permutation(len(values))
    return [values[i] for i in order]


def _requests(rng, prompts, outputs, arrivals, vocab, max_total, first_id):
    out = []
    for i, (p, n, t) in enumerate(zip(prompts, outputs, arrivals)):
        n = min(n, max_total - p)
        if n < 1:
            raise ValueError(f"prompt of {p} leaves no room under "
                             f"max_total={max_total}")
        out.append(GenRequest(first_id + i,
                              rng.integers(0, vocab, p).tolist(), int(n),
                              float(t)))
    return out


def open_loop(spec: Dict, seed: int, seconds: float, vocab: int,
              ramp_s: float, tail_s: float = 0.0) -> List[GenRequest]:
    """Arrivals over [-ramp_s, seconds + tail_s): round(rate x seconds)
    requests due in the window, round(rate x ramp_s) before it and
    round(rate x tail_s) after it, each part with the stratified lengths
    and gaps of its own count."""
    rng = np.random.default_rng([int(seed), 11])
    order = np.random.default_rng([int(seed), 12])
    rate = float(spec["arrivals"]["rate_per_s"])
    out: List[GenRequest] = []
    for t0, span in ((-ramp_s, ramp_s), (0.0, seconds), (seconds, tail_s)):
        n = int(round(rate * span))
        if n == 0:
            continue
        gaps = np.array(_permuted(gap_quantiles(spec["arrivals"], n, span),
                                  order))
        # an arrival sits in the middle of its gap, so the part's first
        # and last arrivals keep clear of its ends
        arrivals = t0 + np.cumsum(gaps) - gaps / 2.0
        out += _requests(
            rng, _permuted(length_quantiles(spec["prompt"], n), order),
            _permuted(length_quantiles(spec["output"], n), order), arrivals,
            vocab, spec["max_total"], first_id=len(out))
    return out


def closed_loop(spec: Dict, seed: int, vocab: int):
    """(first wave, backlog) of a closed loop of `clients`: the first
    wave's outputs are cut to a stratified uniform share between
    `first_wave_min_output` and their full draw, so that retirements are
    spread and not bunched; the backlog is what clients send next, in
    order, as each completes. Lengths sit where the mix's `placement`
    puts them, whatever the seed."""
    rng = np.random.default_rng([int(seed), 13])
    order = np.random.default_rng([int(spec["placement"]), 14])
    clients = int(spec["clients"])
    n = clients + int(spec["backlog"])
    reqs = _requests(
        rng, _permuted(length_quantiles(spec["prompt"], n), order),
        _permuted(length_quantiles(spec["output"], n), order),
        np.zeros(n), vocab, spec["max_total"], first_id=0)
    lo = int(spec["first_wave_min_output"])
    share = _permuted(_quantile_points(clients), order)
    for r, u in zip(reqs[:clients], share):
        if r.max_new_tokens > lo:
            r.max_new_tokens = int(round(lo + u * (r.max_new_tokens - lo)))
    return reqs[:clients], reqs[clients:]


def lap_request(backlog: List[GenRequest], k: int, seed: int,
                vocab: int) -> GenRequest:
    """The k-th request (from 0) a closed loop sends once its backlog is
    spent: the LENGTHS of backlog entry `k mod len(backlog)`, so a lap
    sends the backlog's lengths again in the backlog's order, wherever the
    mix's `placement` put them; an id that goes on counting past the
    backlog's last; token ids drawn anew from a stream of (seed, k) alone,
    so no prompt is sent twice (a prefix cache would hit) and the ids of
    the first wave and the backlog do not move."""
    src = backlog[k % len(backlog)]
    rng = np.random.default_rng([int(seed), 15, int(k)])
    return GenRequest(backlog[-1].id + 1 + k,
                      rng.integers(0, vocab, len(src.prompt)).tolist(),
                      src.max_new_tokens, 0.0)
