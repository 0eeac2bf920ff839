"""The serving engine around DeepSeek-V2, for the closed-loop kind: what
`_serve_longcat.Engine` is for LongCat-Flash, over another model, another
weights module and another plain reference. Everything that is not the
model — warming, the instrumented tick, the window's counters and samples
— is `_serve.Engine`'s, inherited; the loop seen in its steady state (a
first wave whose prompts are files, history and the answer so far, built
by prefill inside set-up) is `_serve_phi4flash.deep_closed_loop`.

What it adds: the expert layer's routing counters of the window
(`DeepseekV2LM.STEP_COUNTERS`: picks on the held group's experts, and the
rows whose three kept groups include it), and for the latent kernel's
roofline the pages and rows of the CAPTURED ticks (`traced_counters`, as
`_serve_falconh1.Engine` has them: contexts grow all through this window).
The comparison takes the reference's logits at served positions alone.

The weights are made on the device in one program and handed to the
engine as its own (`EngineConfig.own_params`): 6.29 GB of them beside a
4.7 GB pool cannot be on the chip twice.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from perfbench import weights_deepseekv2 as weights
from perfbench.harness import Check, log
from perfbench.kinds import _serve
from perfbench.kinds._serve_falconh1 import collector_at_rest  # noqa: F401
from perfbench.kinds._serve_phi4flash import deep_closed_loop  # noqa: F401

#: the model's step counters (`DeepseekV2LM.STEP_COUNTERS`), as this file
#: reads them from the engine's telemetry
HELD, LOAD_MAX, GROUP_HITS = ("moe_held_picks", "moe_load_max",
                              "moe_group_hit_rows")
#: served positions the reference's head takes at once
HEAD_POSITIONS = 1024
#: what a run is held to: the median and the 99th percentile of EVERY
#: compared token's gaps, not the widest one's. A pick's weight here is
#: 16 p (0.4-1.3, where LongCat-Flash's 6 p is 0.07-0.25), so ONE near-tie
#: of the gate or of the group choice that falls the other way in bfloat16
#: moves a token's logits by more than five layers of rounding do: the
#: widest gap of fifteen thousand tokens reads the worst such tie (1.6-3.5
#: over ten sound runs; 2.3-2.4 where the REFERENCE's own products are
#: rounded to bfloat16; 4.3-4.6 in fp8), and no limit separates those.
#: The widest gaps are logged beside the checks.
CHECKS = ("served_logprob_gap_median", "served_logprob_gap_p99",
          "served_logit_gap_p99")


def model_of(dims, dtype, max_len: int, decode_kernel: bool):
    from mpi_operator_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                     DeepseekV2LM)
    return DeepseekV2LM(DeepseekV2Config(
        vocab_size=dims.vocab, max_len=max_len, num_layers=dims.layers,
        hidden_size=dims.hidden, num_heads=dims.heads,
        q_lora_rank=dims.q_rank, kv_lora_rank=dims.kv_rank,
        qk_nope_head_dim=dims.nope, qk_rope_head_dim=dims.rope,
        v_head_dim=dims.v_dim, intermediate_size=dims.ffn,
        moe_intermediate_size=dims.expert_ffn,
        first_k_dense_replace=dims.dense_layers,
        n_routed_experts=dims.experts_published,
        n_shared_experts=dims.shared_experts,
        num_experts_per_tok=dims.top_k, n_group=dims.n_group,
        topk_group=dims.topk_group, routed_scaling_factor=dims.route_scale,
        rope_theta=dims.rope_theta, rope_factor=dims.rope_factor,
        rope_original_max_len=dims.rope_original,
        rope_beta_fast=dims.beta_fast, rope_beta_slow=dims.beta_slow,
        rope_mscale=dims.mscale, rope_mscale_all_dim=dims.mscale_all_dim,
        rms_norm_eps=dims.eps, held=dims.held, dtype=dtype,
        decode_kernel=decode_kernel))


def check_tree(model, dims, dtype) -> None:
    """The program's abstract parameters against the tree this benchmark
    makes, leaf for leaf, before anything is timed."""
    import jax
    import jax.numpy as jnp
    program = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 2), jnp.int32)))["params"]
    made = jax.eval_shape(
        lambda: weights.make_params(jax.random.PRNGKey(0), dims, dtype))
    shape = lambda tree: {k: v[0] for k, v in                 # noqa: E731
                          weights.tree_shapes(tree).items()}
    if shape(program) != shape(made):
        odd = sorted(set(shape(program).items())
                     ^ set(shape(made).items()))[:6]
        raise RuntimeError("the program does not serve the tree "
                           f"perfbench.weights_deepseekv2 makes: {odd}")


class Engine(_serve.Engine):
    """The serving engine over DeepSeek-V2 with the recorders and counters
    of one run."""

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
                            for name in (HELD, LOAD_MAX, GROUP_HITS)}
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
        """`_serve.Engine`'s counts, and the expert layers': means over
        the decode steps fetched in [t0, t1), an expert layer."""
        out = super().window_counters(t0, t1)
        mean = {n: float(np.mean([v for t, v in zip(r.at, r.values)
                                  if t0 <= t < t1] or [math.nan]))
                for n, r in self.step_counts.items()}
        if not all(np.isfinite(list(mean.values()))) or mean[HELD] <= 0:
            return out
        d = self.dims
        layers = d.layers - d.dense_layers
        out.update({
            "dsv2.held_assignments_per_step": mean[HELD] / layers,
            "dsv2.group_hit_share_pct":
                100.0 * mean[GROUP_HITS]
                / (layers * self.engine.config.slots),
            "dsv2.expert_load_max_over_mean_pct":
                100.0 * mean[LOAD_MAX] * d.held[1] / mean[HELD],
        })
        return out

    def traced_counters(self, tracer, window: Dict[str, float]
                        ) -> Dict[str, float]:
        """What the captured steps' kernel read, for its roofline: the
        means over the ticks of the traced sub-window (the window's own
        where no tick began inside the capture); nothing untraced."""
        if len(tracer.disturbed) != 2:
            return {}
        sub = super().window_counters(tracer.disturbed[0][1],
                                      tracer.disturbed[1][0]) or window
        return {"serve.traced_" + name: sub["serve." + name]
                for name in ("tokens_in_pages_mean", "decoding_rows_mean")
                if "serve." + name in sub}

    def shapes(self) -> Dict[str, float]:
        d = self.dims
        return {"heads": d.heads, "kv_rank": d.kv_rank, "rope": d.rope,
                "sublayers": d.layers, "layers": d.layers,
                "slots": self.engine.config.slots,
                "page_size": self.engine.config.page_size}

    def op_scopes(self) -> Dict[str, str]:
        """The program's map from the decode step's instructions to the
        scopes they were traced under."""
        t0 = time.perf_counter()
        out = self.engine.decode_step_scopes()
        log(f"decode step scopes: {len(out)} instructions named in "
            f"{time.perf_counter() - t0:.3f} s")
        return out


def served_gaps(dims, dtype, key, sample, prompts, control=None) -> dict:
    """As `_serve_falconh1.served_gaps`, over the DeepSeek-V2 reference:
    every sampled request's prompt and served tokens through the plain
    forward pass, ALL in one call (the reference remakes a layer's weights
    from the seed once a call, 670 M normal draws, and takes the sequences
    one at a time inside it), padded to one width (causal: the pad changes
    nothing before it), the logits taken at the positions that foretold
    served tokens and nowhere else. Beside the widest gaps, the median
    and the 99th percentile of every served token's logprob gap."""
    import jax.numpy as jnp
    from perfbench.reference import deepseek_v2
    longest = max(len(prompts[r.id]) + len(r.tokens) for r in sample)
    block = deepseek_v2.BLOCK
    width = longest if longest <= block else -(-longest // block) * block
    most = max(len(r.tokens) for r in sample)
    served = (most if most <= HEAD_POSITIONS
              else -(-most // HEAD_POSITIONS) * HEAD_POSITIONS)
    padded = np.zeros((len(sample), width), np.int32)
    at = np.zeros((len(sample), served), np.int32)
    for i, r in enumerate(sample):
        seq = list(prompts[r.id]) + list(r.tokens)
        padded[i, :len(seq)] = seq
        p = len(prompts[r.id])                 # p-1+j foretells token j
        at[i] = np.minimum(p - 1 + np.arange(served), width - 1)
    g = {k: np.asarray(v) for k, v in deepseek_v2.served_token_gaps(
        key, jnp.asarray(padded), jnp.asarray(at), dims, dtype, control,
        HEAD_POSITIONS).items()}
    own = lambda name: np.concatenate(                         # noqa: E731
        [g[name][i, :len(r.tokens)] for i, r in enumerate(sample)])
    gaps = {"served_logit": own("served_gap"),
            "served_logprob": np.abs(np.concatenate(
                [np.asarray(r.logprobs) for r in sample])
                - own("served_ref_logp"))}
    if control:
        gaps.update(control_logit=own("other_gap"),
                    control_logprob=np.abs(own("other_own_logp")
                                           - own("other_ref_logp")))
    out = {"served_tokens": int(gaps["served_logit"].size)}
    for name, each in gaps.items():
        out.update({name + "_gap": float(each.max()),
                    name + "_gap_median": float(np.median(each)),
                    name + "_gap_p99": float(np.percentile(each, 99))})
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
        f"{[r.id for r in sample]}, lengths "
        f"{[len(prompts[r.id]) + len(r.tokens) for r in sample]})")
    log("the widest gaps, which read ONE routing near-tie and decide "
        f"nothing: logit {g['served_logit_gap']:.5f}, logprob "
        f"{g['served_logprob_gap']:.5f}")
    return [Check(name, g[name], t["limits"][name]) for name in CHECKS]
