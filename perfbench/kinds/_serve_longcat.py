"""The serving engine around LongCat-Flash, for the closed-loop kind: what
`_serve.Engine` is for GPT-2, over another model, another weights module
and another plain reference. Everything that is not the model — warming,
the instrumented tick, the window's counters and samples — is
`_serve.Engine`'s, inherited.

The weights are made on the device in one program and handed to the
engine as its own (`EngineConfig.own_params`): 10.35 GB of them are 60% of
the chip and cannot be on it twice, and the round through the host that
`_serve.Engine` makes (because the engine copies what it is given) would
move 10 GB down and up again inside `setup_s`.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from perfbench import weights_longcat as weights
from perfbench.harness import Check, log
from perfbench.kinds import _serve

#: the model's step counters (`LongcatLM.STEP_COUNTERS`), as this file
#: reads them from the engine's telemetry
HELD, IDENTITY, LOAD_MAX = ("moe_held_picks", "moe_identity_picks",
                            "moe_load_max")


def model_of(dims, dtype, max_len: int, decode_kernel: bool):
    from mpi_operator_tpu.models.longcat import LongcatConfig, LongcatLM
    return LongcatLM(LongcatConfig(
        vocab_size=dims.vocab, max_len=max_len, num_layers=dims.layers,
        hidden_size=dims.hidden, num_heads=dims.heads,
        q_lora_rank=dims.q_rank, kv_lora_rank=dims.kv_rank,
        qk_nope_head_dim=dims.nope, qk_rope_head_dim=dims.rope,
        v_head_dim=dims.v_dim, ffn_hidden_size=dims.ffn,
        expert_ffn_hidden_size=dims.expert_ffn,
        n_routed_experts=dims.experts_published,
        zero_expert_num=dims.zero_experts, moe_topk=dims.top_k,
        routed_scaling_factor=dims.route_scale, rope_theta=dims.rope_theta,
        rms_norm_eps=dims.eps, held=dims.held, dtype=dtype,
        decode_kernel=decode_kernel))


def check_tree(model, dims, dtype) -> None:
    """The program's abstract parameters against the tree this benchmark
    makes, leaf for leaf, before anything is timed."""
    import jax
    import jax.numpy as jnp
    tokens = jax.ShapeDtypeStruct((1, 2), jnp.int32)
    program = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros(tokens.shape, tokens.dtype)))["params"]
    made = jax.eval_shape(
        lambda: weights.make_params(jax.random.PRNGKey(0), dims, dtype))
    shape = lambda tree: {k: v[0] for k, v in                 # noqa: E731
                          weights.tree_shapes(tree).items()}
    if shape(program) != shape(made):
        odd = sorted(set(shape(program).items())
                     ^ set(shape(made).items()))[:6]
        raise RuntimeError("the program does not serve the tree "
                           f"perfbench.weights_longcat makes: {odd}")


class Engine(_serve.Engine):
    """The serving engine over LongCat-Flash with the recorders and
    counters of one run."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from mpi_operator_tpu.serve import EngineConfig, ServingEngine
        from mpi_operator_tpu.telemetry.worker import ServeTelemetry

        e = ctx.traffic["engine"]
        self.dims = dims = weights.Dims.from_config(ctx.config)
        self.dtype = jnp.dtype(e["weights_dtype"])
        self.key = weights.seed_key(ctx.seed)
        model = model_of(dims, self.dtype, int(ctx.traffic["max_total"]),
                         bool(e["decode_kernel"]))
        check_tree(model, dims, self.dtype)
        params = jax.jit(lambda k: weights.make_params(k, dims, self.dtype))(
            self.key)
        self.telemetry = ServeTelemetry()
        self.host_gap = self.telemetry.host_gap_seconds = _serve.Recorder()
        self.decode_step = self.telemetry.decode_step_seconds = \
            _serve.Recorder()
        self.prefill = self.telemetry.prefill_seconds = _serve.Recorder()
        self.step_counts = {name: _serve.Recorder()
                            for name in (HELD, IDENTITY, LOAD_MAX)}
        self.telemetry.step_counters.update(self.step_counts)
        self.engine = ServingEngine(model, params, EngineConfig(
            slots=int(e["slots"]), chunk_buckets=tuple(e["chunk_buckets"]),
            decode_kernel=bool(e["decode_kernel"]), rng_seed=0,
            async_decode=bool(e["async_decode"]), paged=True,
            page_size=int(e["page_size"]), num_pages=int(e["num_pages"]),
            prefix_cache=bool(e["prefix_cache"]), own_params=True,
            request_timeout=e.get("request_timeout_s")),
            telemetry=self.telemetry)
        del params
        self.tick_at: List[float] = []
        self.tick_s: List[float] = []
        self.tick_prefilled_rows: List[int] = []
        self.tick_occupied: List[int] = []
        self.tick_tokens_in_pages: List[int] = []
        self.tick_decoding_rows: List[int] = []

    def window_counters(self, t0: float, t1: float) -> Dict[str, float]:
        """`_serve.Engine`'s counts, and the expert layer's: means over
        the decode steps fetched in [t0, t1), a layer."""
        out = super().window_counters(t0, t1)
        mean = {n: float(np.mean([v for t, v in zip(r.at, r.values)
                                  if t0 <= t < t1] or [math.nan]))
                for n, r in self.step_counts.items()}
        if not all(np.isfinite(list(mean.values()))) or mean[HELD] <= 0:
            return out
        d = self.dims
        picks = self.engine.config.slots * d.top_k * d.layers
        out.update({
            "moe.held_assignments_per_step": mean[HELD] / d.layers,
            "moe.identity_pick_share_pct": 100.0 * mean[IDENTITY] / picks,
            "moe.expert_load_max_over_mean_pct":
                100.0 * mean[LOAD_MAX] * d.held[1] / mean[HELD],
        })
        return out

    def shapes(self) -> Dict[str, float]:
        d = self.dims
        return {"heads": d.heads, "kv_rank": d.kv_rank, "rope": d.rope,
                "sublayers": 2 * d.layers, "layers": d.layers,
                "slots": self.engine.config.slots,
                "page_size": self.engine.config.page_size}

    def op_scopes(self) -> Dict[str, str]:
        """The program's map from the decode step's instructions to the
        scopes they were traced under; nothing where it offers none."""
        scopes = getattr(self.engine, "decode_step_scopes", None)
        if scopes is None:
            return {}
        t0 = time.perf_counter()
        out = scopes()
        log(f"decode step scopes: {len(out)} instructions named in "
            f"{time.perf_counter() - t0:.3f} s")
        return out


def served_gaps(dims, dtype, key, sample, prompts, control=None,
                rows: int = 4) -> dict:
    """As `_serve.served_gaps`, over the LongCat reference: every sampled
    request's prompt and served tokens through the plain forward pass,
    `rows` sequences a call, all padded to one width (causal: the pad
    changes nothing before it)."""
    import jax.numpy as jnp
    from perfbench.reference import longcat_flash
    out = {"served_logit_gap": 0.0, "served_logprob_gap": 0.0,
           "served_tokens": 0}
    if control:
        out.update(control_logit_gap=0.0, control_logprob_gap=0.0)
    longest = max(len(prompts[r.id]) + len(r.tokens) for r in sample)
    width = -(-longest // 256) * 256
    for lo in range(0, len(sample), rows):
        part = sample[lo:lo + rows]
        padded = np.zeros((rows, width), np.int32)
        for i, r in enumerate(part):
            seq = list(prompts[r.id]) + list(r.tokens)
            padded[i, :len(seq)] = seq
        g = {k: np.asarray(v) for k, v in longcat_flash.served_token_gaps(
            key, jnp.asarray(padded), dims, dtype, control).items()}
        for i, r in enumerate(part):
            p, n = len(prompts[r.id]), len(r.tokens)
            at = slice(p - 1, p - 1 + n)        # p-1+j foretells token j
            out["served_logit_gap"] = max(
                out["served_logit_gap"], float(g["served_gap"][i, at].max()))
            out["served_logprob_gap"] = max(
                out["served_logprob_gap"],
                float(np.abs(np.asarray(r.logprobs)
                             - g["served_ref_logp"][i, at]).max()))
            if control:
                out["control_logit_gap"] = max(
                    out["control_logit_gap"],
                    float(g["other_gap"][i, at].max()))
                out["control_logprob_gap"] = max(
                    out["control_logprob_gap"],
                    float(np.abs(g["other_own_logp"][i, at]
                                 - g["other_ref_logp"][i, at]).max()))
            out["served_tokens"] += n
    return out


def check_served(ctx, eng: Engine, results, prompts) -> List[Check]:
    t = ctx.traffic
    sample = _serve.pick_sample(results, prompts, ctx.seed,
                                int(t["check_requests"]))
    if not sample:
        log("check: no finished request to compare")
        return [Check("served_requests_compared", math.nan, 0.0)]
    t0 = time.perf_counter()
    g = served_gaps(eng.dims, eng.dtype, eng.key, sample, prompts)
    log(f"reference {time.perf_counter() - t0:.3f} s over {len(sample)} "
        f"requests, {g['served_tokens']} served tokens (ids "
        f"{[r.id for r in sample]})")
    return [Check(name + "_widest", g[name], t["limits"][name + "_widest"])
            for name in ("served_logit_gap", "served_logprob_gap")]
