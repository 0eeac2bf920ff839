"""Disaggregated prefill/decode serving tests (serve/engine.py
DisaggEngine + serve/transfer.py).

The colocated paged ServingEngine is the oracle: a greedy trace served
through the split pools — prompt-span admission on the prefill pool,
paged-KV handoff, decode on its own device — must be TOKEN-EXACT
against the same trace run colocated, across retire/slot-reuse, on the
dense and Pallas-kernel paths and with int8 KV (the scale planes ride
the handoff). On top of that, the per-pool compile pins that ARE the
perf story: the prefill pool never compiles a decode step, the decode
pool never compiles a prefill, and the transfer's gather/scatter stay
within the power-of-two width buckets — all held across reset().
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from mpi_operator_tpu.models import CausalLM, gpt2_config
from mpi_operator_tpu.serve import (
    DisaggEngine, EngineConfig, PageAllocator, PageTransfer, Request,
    Scheduler, ServingEngine,
)
from mpi_operator_tpu.telemetry import events as ev
from mpi_operator_tpu.telemetry.core import Registry
from mpi_operator_tpu.telemetry.events import EventLog, read_events

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# host-side policy (no jax)
# ---------------------------------------------------------------------------

def test_prompt_pages_needed():
    # prefill writes [0, P-1): the prompt span excludes the decode span
    ps = 8
    assert Scheduler.prompt_pages_needed(Request(0, [1], 64), ps) == 0
    assert Scheduler.prompt_pages_needed(Request(0, [1, 2], 64), ps) == 1
    assert Scheduler.prompt_pages_needed(Request(0, [1] * 9, 64), ps) == 1
    assert Scheduler.prompt_pages_needed(Request(0, [1] * 10, 64), ps) == 2
    assert Scheduler.prompt_pages_needed(Request(0, [1] * 17, 64), ps) == 2
    # always <= the full span, whatever max_new_tokens is
    for p in range(1, 40):
        r = Request(0, [1] * p, 1)
        assert (Scheduler.prompt_pages_needed(r, ps)
                <= Scheduler.pages_needed(r, ps))


def test_scheduler_reserve_mode_validates():
    with pytest.raises(ValueError, match="reserve"):
        Scheduler((4, 8), max_len=64, reserve="both")


def test_scheduler_gate_blocks_and_packs_past():
    """A gated head stays queued but the lookahead still admits a
    request behind it — the same packing rule as a failed page
    reservation."""
    s = Scheduler((4, 8), max_len=64)
    s.submit(Request(0, [1] * 8, 4))
    s.submit(Request(1, [2] * 4, 4))
    s.gate = lambda req: req.id != 0
    a = PageAllocator(9, 8)
    admitted = s.admit([0, 1], 0.0, a)
    assert [st.req.id for st in admitted] == [1]
    assert [r.id for r in s.queue] == [0]
    s.gate = None
    assert [st.req.id for st in s.admit([0], 0.0, a)] == [0]


def test_transfer_width_bucketing():
    assert PageTransfer.TRASH == 0
    from mpi_operator_tpu.serve.transfer import _bucket
    assert [_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]


# ---------------------------------------------------------------------------
# the disagg facade vs the colocated oracle
# ---------------------------------------------------------------------------

def _setup(decode_kernel=False, kv_cache_dtype=None, slots=4,
           page_size=8, num_pages=None, max_len=64, **disagg_kw):
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=max_len,
                      kv_cache_dtype=kv_cache_dtype)
    model = CausalLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), probe))["params"]
    ecfg = EngineConfig(slots=slots, chunk_buckets=(4, 8),
                        decode_kernel=decode_kernel, page_size=page_size,
                        num_pages=num_pages)
    colocated = ServingEngine(model, params, ecfg)
    disagg = DisaggEngine(model, params, ecfg, **disagg_kw)
    return colocated, disagg


def _mixed_trace(n=8, seed=7, eos=None):
    rs = np.random.RandomState(seed)
    lens = [(1, 6), (3, 9), (9, 4), (14, 7), (5, 5), (7, 8), (12, 6),
            (2, 7)]
    return [Request(i, list(rs.randint(0, 64, (p,))), max_new_tokens=m,
                    eos_id=eos)
            for i, (p, m) in enumerate(lens[:n])]


def _assert_pool_pins(disagg):
    counts = disagg.compile_counts()
    # neither pool ever compiles the other's programs — the per-pool
    # HBM program-footprint win of the split
    assert counts["prefill_pool"]["step"] == 0
    assert counts["prefill_pool"]["prefill"] <= 2
    assert counts["decode_pool"]["prefill"] == 0
    assert counts["decode_pool"]["step"] <= 3
    # transfer widths are power-of-two bucketed: ≤ log2(pool) + 1 each
    cap = int(np.log2(disagg.decode.page_allocator.num_pages)) + 1
    assert counts["transfer"]["gather"] <= cap
    assert counts["transfer"]["scatter"] <= cap
    return counts


@pytest.mark.parametrize("decode_kernel", [False, True])
def test_disagg_token_exact_vs_colocated(decode_kernel):
    """The acceptance gate: greedy decode through the split pools is
    token-for-token identical to the colocated paged engine on the same
    trace — mixed prompt lengths, more requests than slots (slot AND
    page reuse across retire/admit, pages crossing devices mid-request),
    dense and kernel paths."""
    colocated, disagg = _setup(decode_kernel)
    want = colocated.run(_mixed_trace())
    got = disagg.run(_mixed_trace())
    for rid, res in want.items():
        assert got[rid].tokens == res.tokens, f"request {rid} diverged"
        assert got[rid].finish_reason == res.finish_reason
    assert disagg.transfer.pages_moved > 0     # pages really crossed
    for alloc in (disagg.prefill.page_allocator,
                  disagg.decode.page_allocator):
        alloc.check()
        assert alloc.in_use == 0               # every page released
    counts = _assert_pool_pins(disagg)
    assert counts["decode_pool"]["step"] == 1  # pure-greedy trace


def test_disagg_int8_cache_token_exact():
    """int8 KV through the handoff: quantized pages move WITH their
    [NP, KV, ps] scale planes (one generic pytree gather/scatter), so
    the decode pool dequantizes the same bytes the colocated engine
    would."""
    colocated, disagg = _setup(kv_cache_dtype="int8")
    want = colocated.run(_mixed_trace(n=5))
    got = disagg.run(_mixed_trace(n=5))
    for rid, res in want.items():
        assert got[rid].tokens == res.tokens, f"request {rid} diverged"
    assert disagg.transfer.pages_moved > 0


def test_disagg_eos_retirement_and_pins_across_reset():
    """EOS mid-flight retires through the decode pool (pages park in
    its prefix cache); a reset() replays the trace token-identically
    WITHOUT growing any pool's compile counts — the warmup→measure
    contract the bench relies on."""
    colocated, disagg = _setup()
    probe = colocated.run(_mixed_trace(n=1))
    eos = probe[0].tokens[2]
    colocated.reset()
    want = colocated.run(_mixed_trace(eos=eos))
    got = disagg.run(_mixed_trace(eos=eos))
    assert any(r.finish_reason == "eos" for r in got.values())
    for rid, res in want.items():
        assert got[rid].tokens == res.tokens
    counts_before = _assert_pool_pins(disagg)
    disagg.reset()
    again = disagg.run(_mixed_trace(eos=eos))
    for rid, res in want.items():
        assert again[rid].tokens == res.tokens
    assert disagg.compile_counts() == counts_before


def test_prefix_hit_handoff_moves_only_noncached_pages():
    """The handoff reads the DECODE pool's prefix cache: a repeat
    prompt's full prompt pages are already resident there, so the
    second handoff moves zero pages (and a diverging prompt moves only
    its divergent tail)."""
    _, disagg = _setup()
    shared = list(np.random.RandomState(3).randint(0, 64, (33,)))
    # p1=32, page_size=8: 4 full prompt pages, all published at install
    disagg.run([Request(0, shared, max_new_tokens=4)])
    first = disagg.transfer.pages_moved
    assert first >= 4
    out = disagg.run([Request(1, shared, max_new_tokens=4)])
    assert disagg.transfer.pages_moved == first   # full hit: no bytes
    assert out[1].cached_tokens == 32             # prefill skipped too
    # divergence in the last full page: pages 0-2 hit, page 3 moves
    fork = list(shared)
    fork[30] = (fork[30] + 1) % 64
    disagg.run([Request(2, fork, max_new_tokens=4)])
    assert disagg.transfer.pages_moved == first + 1
    # decode-side hit/miss counters saw the savings
    assert disagg.decode.page_allocator.hits >= 7


def test_backpressure_bounds_prefill_admission():
    """A decode pool sized for ONE request forces serial service: the
    admission gate keeps prompts out of the prefill pool until the
    decode pool can absorb their full span — bounded handoff queue, no
    page deadlock, every request still completes exactly."""
    # each request: prompt 14, max_new 7 -> (14-2+7)//8+1 = 3 pages
    reqs = [Request(i, list(np.random.RandomState(i).randint(0, 64, (14,))),
                    max_new_tokens=7) for i in range(3)]
    colocated, disagg = _setup(num_pages=4)     # 3 usable decode pages
    want = colocated.run(reqs)
    got = disagg.run(reqs)
    for r in reqs:
        assert got[r.id].tokens == want[r.id].tokens
    assert disagg.prefill.occupancy_peak == 1   # gate held admissions
    assert disagg.decode.occupancy_peak == 1
    assert not disagg._handoff_q


def test_disagg_rejects_unservable_requests():
    _, disagg = _setup(num_pages=4)             # 3 usable decode pages
    with pytest.raises(ValueError, match="decode pool"):
        disagg.run([Request(0, [1] * 20, max_new_tokens=30)])


# ---------------------------------------------------------------------------
# telemetry + events
# ---------------------------------------------------------------------------

def test_disagg_per_pool_telemetry_and_handoff_events(tmp_path):
    """One registry, two labeled bundles: every serve series shows up
    per pool (the federation keeps the label), kv_handoff_* instruments
    fill on the decode side, and the event log carries kv_handoff
    records plus pool-stamped admissions."""
    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=64)
    model = CausalLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), probe))["params"]
    reg = Registry()
    log = EventLog(str(tmp_path / "events.jsonl"))
    disagg = DisaggEngine(
        model, params,
        EngineConfig(slots=4, chunk_buckets=(4, 8), page_size=8),
        registry=reg, events=log)
    disagg.run(_mixed_trace(n=4))
    log.close()
    pre_tel, dec_tel = disagg.prefill.telemetry, disagg.decode.telemetry
    assert pre_tel.labels == {"pool": "prefill"}
    assert dec_tel.labels == {"pool": "decode"}
    # the decode pool's queue is the handoff queue; its occupancy and
    # handoff instruments are distinct series from the prefill pool's
    assert dec_tel.queue_depth is not pre_tel.queue_depth
    assert dec_tel.kv_handoff_pages.value == disagg.transfer.pages_moved
    assert dec_tel.kv_handoff_seconds.count == len(disagg.handoff_log)
    assert dec_tel.requests_total.value == 4
    assert pre_tel.requests_total.value == 0    # retirement is decode-side
    handoffs = read_events(log.path, kind=ev.KV_HANDOFF)
    assert len(handoffs) == 4
    assert all(h["pages"] >= 0 and h["seconds"] >= 0 for h in handoffs)
    admits = read_events(log.path, kind=ev.SLOT_ADMIT)
    pools = {a.get("pool") for a in admits}
    assert pools == {"prefill", "decode"}


def test_debug_pages_env_gates_reset_audit(monkeypatch):
    """Satellite: the O(num_pages) PageAllocator.check() audit runs on
    reset() only under TPU_DEBUG_PAGES=1 (the conftest sets it for the
    suite) — the bench's hot warmup→measure reset skips it."""
    assert os.environ.get("TPU_DEBUG_PAGES") == "1"
    _, disagg = _setup()
    calls = []
    monkeypatch.setattr(disagg.decode.page_allocator, "check",
                        lambda: calls.append(True))
    disagg.reset()
    assert calls                                # debug build: audited
    calls.clear()
    monkeypatch.delenv("TPU_DEBUG_PAGES")
    disagg.reset()
    assert not calls                            # production reset: O(1)
