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
from mpi_operator_tpu.serve.programs import build_programs

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
