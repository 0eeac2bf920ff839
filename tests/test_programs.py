"""serve/programs.py: the engine's four compiled programs, and the
contract a served model meets.

One builder serves both kinds of model the repo has — `CausalLM` (tied
head, no step counters) and `LongcatLM` (its own `head_logits`, its
`STEP_COUNTERS`) — through the same four programs under the same traced
names; the configs carry no second cache regime.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from mpi_operator_tpu.models import CausalLM, gpt2_config
from mpi_operator_tpu.models.generate import decode_model
from mpi_operator_tpu.models.longcat import LongcatConfig, LongcatLM
from mpi_operator_tpu.models.transformer import TransformerConfig
from mpi_operator_tpu.serve import EngineConfig, Request, ServingEngine
from mpi_operator_tpu.serve.engine import sample_slots
from mpi_operator_tpu.serve import programs
from mpi_operator_tpu.serve.programs import build_programs
from mpi_operator_tpu.telemetry import spans
from mpi_operator_tpu.telemetry.worker import ServeTelemetry
from prefill_forms import member_rows_alone_leave_what_all_rows_leave

pytestmark = pytest.mark.serving


def _tied():
    return CausalLM(gpt2_config("test", attention="dense", dtype=jnp.float32,
                                vocab_size=64, max_len=64))


def _own_head_and_counters():
    return LongcatLM(LongcatConfig(
        vocab_size=64, max_len=64, num_layers=1, hidden_size=32,
        num_heads=2, q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, ffn_hidden_size=48,
        expert_ffn_hidden_size=16, n_routed_experts=8, zero_expert_num=4,
        moe_topk=2, held=(0, 4), dtype=jnp.float32))


@pytest.mark.parametrize("make,counters", [
    (_tied, ()), (_own_head_and_counters, LongcatLM.STEP_COUNTERS)],
    ids=["tied-head", "own-head-and-counters"])
def test_one_builder_serves_both_kinds_of_model(make, counters):
    """Same four programs, same traced names, same compile counts after
    the same trace, whichever optional parts of the contract the model
    brings; what it brings is read by the builder and nowhere else."""
    model = make()
    assert hasattr(model, "head_logits") == bool(counters)
    params = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    cfg = EngineConfig(slots=2, chunk_buckets=(4, 8), page_size=8,
                       speculative="ngram", draft_k=2)
    progs = build_programs(decode_model(model, False, page_size=8,
                                        num_pages=17), cfg, None,
                           sample_slots)
    assert progs.step_counters == tuple(counters)
    assert [f.__name__ for f in progs[:4]] == [
        "init_cache", "prefill_paged", "step_paged", "verify_paged"]

    engine = ServingEngine(model, params, cfg)
    assert engine._step_counters == tuple(counters)
    rs = np.random.RandomState(2)
    reqs = [Request(i, list(rs.randint(0, 64, (p,))), max_new_tokens=6)
            for i, p in enumerate([3, 9, 14])]
    reqs.append(Request(3, [7, 8] * 5, max_new_tokens=8))   # drafts hit
    results = engine.run(reqs)
    assert all(len(results[r.id].tokens) == r.max_new_tokens for r in reqs)
    counts = engine.compile_counts()
    assert counts["verify"] >= 1 and engine.spec_stats()["proposed"] > 0
    assert {k: v for k, v in counts.items() if k != "verify"} == {
        "step": 1, "prefill": 2, "init_cache": 1, "cast": 1}
    assert counts["verify"] <= 2


@pytest.mark.parametrize("config", [TransformerConfig, LongcatConfig])
def test_model_configs_carry_one_cache_regime(config):
    """"Slots" is what a page size means: no second field chooses a
    per-row-cursor cache without one."""
    names = {f.name for f in dataclasses.fields(config)}
    assert "decode_slots" not in names
    assert {"decode", "decode_page_size", "decode_num_pages"} <= names


# ---------------------------------------------------------------------------
# A prefill call is as wide as its members (programs.prefill_calls)
# ---------------------------------------------------------------------------

def _params(model):
    return meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]


def _requests(lengths, new=4, seed=3):
    rs = np.random.RandomState(seed)
    return [Request(i, list(rs.randint(0, 64, (p,))), max_new_tokens=new)
            for i, p in enumerate(lengths)]


@pytest.mark.parametrize("make,tol", [(_tied, 0.0),
                                      (_own_head_and_counters, 1e-6)],
                         ids=["per-head-pool", "latent-pool"])
def test_a_call_of_the_member_row_leaves_the_pool_as_a_call_of_every_row(
        make, tol):
    """A model whose cache is pages alone: one member row through a call
    of its own (beside a pad row) and through a call of every slot's row
    writes the same pages, bit for bit (the latent model's absorbed
    products round by their batch on the CPU: 5e-7 of values near 7)."""
    model = make()
    eng = ServingEngine(model, _params(model), EngineConfig(
        slots=3, chunk_buckets=(8,), page_size=8, num_pages=25))
    eng.run(_requests([20, 9, 15]))                  # pages hold something
    pages = jnp.tile(jnp.arange(1, 9, dtype=jnp.int32)[None], (3, 1))
    assert not eng._slot_state
    assert member_rows_alone_leave_what_all_rows_leave(
        eng, eng.cache, pages, tol=tol) == 0


@pytest.mark.parametrize("rows,members,calls", [
    (1, 1, [1]), (1, 3, [1, 1, 1]), (2, 3, [2, 2]), (2, 4, [2, 2]),
    (4, 3, [4])])
def test_prefill_calls_cut_a_ticks_members_into_calls_of_narrow_rows(
        monkeypatch, rows, members, calls):
    """`NARROW_ROWS` members a call in their order, a last call short of
    members padded with rows at `max_len` that name slot `slots`."""
    monkeypatch.setattr(programs, "NARROW_ROWS", rows)
    slots = [5, 2, 7, 0][:members]
    toks = np.arange(members * 8, dtype=np.int32).reshape(members, 8)
    starts = np.arange(members, dtype=np.int32) * 8
    pages = np.arange(members * 4, dtype=np.int32).reshape(members, 4)
    lengths = np.full((members,), 8, np.int32)
    got = list(programs.prefill_calls(slots, toks, starts, pages, lengths,
                                      n_slots=9, max_len=64))
    assert [len(c[0]) for c in got] == calls
    flat = [np.concatenate([np.asarray(c[i]) for c in got]) for i in range(5)]
    pad = len(flat[0]) - members
    assert flat[0].tolist() == slots + [9] * pad       # a slot that is not
    assert flat[2].tolist() == starts.tolist() + [64] * pad
    assert flat[4].tolist() == [8] * members + [0] * pad
    assert np.array_equal(flat[1][:members], toks)
    assert np.array_equal(flat[3][:members], pages)
    assert not flat[1][members:].any()
    assert all(x.dtype == np.int32 for x in flat)
    # a model without SLOT_STATE: its program takes no lengths
    assert {len(c) for c in programs.prefill_calls(
        slots, toks, starts, pages, None, n_slots=9, max_len=64)} == {4}


@pytest.fixture(scope="module")
def eight_slots():
    model = _tied()
    tel = ServeTelemetry()
    backend_compiles = []             # one listener for the module's life
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _, **kw: backend_compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return ServingEngine(model, _params(model), EngineConfig(
        slots=8, chunk_buckets=(8, 16), page_size=8), telemetry=tel
    ), tel, backend_compiles


@pytest.mark.parametrize("members", [1, 2, 5, 8])
def test_a_call_is_narrow_whatever_the_member_count_and_compiles_once(
        eight_slots, members):
    """One member or every slot at once: each goes in a call of
    `NARROW_ROWS` rows, `serve.prefill` says so (`width`),
    `ServeTelemetry.prefill_calls` counts a call a member, and a bucket
    compiles ONE program at its first use: a later tick of another member
    count compiles nothing."""
    eng, tel, backend_compiles = eight_slots
    eng.reset()
    eng.run(_requests([12] * 8, new=2, seed=9)[:1])      # bucket 16 in use
    counts = eng.compile_counts()
    assert counts["prefill"] == 1
    del backend_compiles[:]
    before = tel.prefill_calls.value
    eng.reset()
    spans.clear()
    eng.run(_requests([12] * members, new=2, seed=members))
    assert [r.attrs for r in spans.records() if r.name == "serve.prefill"
            ] == [{"width": programs.NARROW_ROWS}]
    assert tel.prefill_calls.value - before == members
    assert eng.compile_counts() == counts and not backend_compiles


@pytest.mark.parametrize("narrow_rows", [2, 16])
def test_calls_of_any_row_count_serve_the_one_row_calls_tokens(
        monkeypatch, narrow_rows):
    """Three members on sixteen slots at two rows a call (the second call
    padded with a row that scatters nowhere, should a kernel's row block
    ever force `NARROW_ROWS` up) and at sixteen (every slot's row in one
    call, what a prefill call was until PR 48): the tokens are those of
    the calls of one row."""
    model = _tied()
    params = _params(model)
    cfg = EngineConfig(slots=16, chunk_buckets=(8,), page_size=8)
    reqs = lambda: _requests([7, 12, 9], new=5, seed=4)     # noqa: E731
    want = ServingEngine(model, params, cfg).run(reqs())
    monkeypatch.setattr(programs, "NARROW_ROWS", narrow_rows)
    spans.clear()
    got = ServingEngine(model, params, cfg).run(reqs())
    assert [r.attrs for r in spans.records() if r.name == "serve.prefill"
            ][0] == {"width": narrow_rows}
    assert {i: r.tokens for i, r in got.items()} == {
        i: r.tokens for i, r in want.items()}
