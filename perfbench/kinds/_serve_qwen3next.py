"""The serving engine around Qwen3-Next, for the closed-loop kind: what
`_serve_granite4hs.Engine` is for Granite-4.0-H, over another model,
another weights module and another plain reference. Everything that is not
the model is inherited: warming, the instrumented tick and the window's
counters and samples (`_serve.Engine`), what a slot holds beside its pages
and the captured ticks' counters for the kernels' rooflines
(`_serve_falconh1.Engine`), the first wave drained out of the window
(`_serve_granite4hs.Engine.tick`), and the loop seen in its steady state,
whose first wave decodes what remains from the window's opening with a
band of ticks round the close left clear
(`_serve_granite4hs.deep_closed_loop`).

It adds the expert layers' routing counters of the window under this
cell's names (`Qwen3NextLM.STEP_COUNTERS`) and the shapes the two kernels'
rooflines take. The comparison takes the reference's logits at served
positions alone.

The weights are made on the device in one program and handed to the
engine as its own (`EngineConfig.own_params`): 7.3 GB of them beside 5.2 GB
of state and pools cannot be on the chip twice.
"""
from __future__ import annotations

import math
import time
import types
from typing import Dict, List

import numpy as np

from perfbench import weights_qwen3next as weights
from perfbench.harness import Check, log
from perfbench.kinds import _serve, _serve_falconh1, _serve_granite4hs
from perfbench.kinds._serve_granite4hs import (  # noqa: F401
    HELD, LOAD_MAX, clear_quantiles, collector_at_rest, deep_closed_loop)

#: served positions the reference's head takes at once
HEAD_POSITIONS = 1024


def model_of(dims, dtype, max_len: int, decode_kernel: bool):
    from mpi_operator_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                    Qwen3NextLM)
    return Qwen3NextLM(Qwen3NextConfig(
        vocab_size=dims.vocab, max_len=max_len, num_layers=dims.layers,
        full_attention_interval=dims.interval, hidden_size=dims.hidden,
        num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_dim=dims.head_dim, partial_rotary_factor=dims.rotary_factor,
        rope_theta=dims.rope_theta, linear_num_key_heads=dims.key_heads,
        linear_num_value_heads=dims.value_heads,
        linear_key_head_dim=dims.key_head_dim,
        linear_value_head_dim=dims.value_head_dim,
        linear_conv_kernel_dim=dims.d_conv,
        moe_intermediate_size=dims.expert_ffn,
        shared_expert_intermediate_size=dims.shared_ffn,
        num_experts=dims.experts_published,
        num_experts_per_tok=dims.top_k, rms_norm_eps=dims.eps,
        held=dims.held, dtype=dtype, decode_kernel=decode_kernel))


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
                           f"perfbench.weights_qwen3next makes: {odd}")


class Engine(_serve_granite4hs.Engine):
    """The serving engine over Qwen3-Next with the recorders and counters
    of one run. The tick that keeps the first wave's calls inside set-up is
    `_serve_granite4hs.Engine`'s, inherited."""

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
                            for name in (HELD, LOAD_MAX)}
        self.telemetry.step_counters.update(self.step_counts)
        self.engine = ServingEngine(model, params, EngineConfig(
            slots=int(e["slots"]), chunk_buckets=tuple(e["chunk_buckets"]),
            decode_kernel=bool(e["decode_kernel"]), rng_seed=0,
            async_decode=bool(e["async_decode"]), paged=True,
            page_size=int(e["page_size"]), num_pages=int(e["num_pages"]),
            prefix_cache=bool(e["prefix_cache"]), own_params=True,
            request_timeout=e.get("request_timeout_s"),
            async_depth=int(e.get("async_depth", 1))),
            telemetry=self.telemetry)
        del params
        self.tick_at: List[float] = []
        self.tick_s: List[float] = []
        self.tick_prefilled_rows: List[int] = []
        self.tick_occupied: List[int] = []
        self.tick_tokens_in_pages: List[int] = []
        self.tick_decoding_rows: List[int] = []
        # what a slot holds beside its pages: a number of the engine, the
        # same on every tick
        self.slot_state = float(self.engine.slot_state_bytes())
        self.first_wave_out = False

    def window_counters(self, t0: float, t1: float) -> Dict[str, float]:
        """`_serve.Engine`'s counts, what a slot holds
        (`_serve_falconh1.Engine`'s; Granite's own names are passed over),
        and the expert layers' under this cell's: means over the decode
        steps fetched in [t0, t1), a layer."""
        out = _serve_falconh1.Engine.window_counters(self, t0, t1)
        mean = {n: float(np.mean([v for t, v in zip(r.at, r.values)
                                  if t0 <= t < t1] or [math.nan]))
                for n, r in self.step_counts.items()}
        if not all(np.isfinite(list(mean.values()))) \
                or mean.get(HELD, 0.0) <= 0:
            return out
        d = self.dims
        out.update({
            "q3n.held_assignments_per_step": mean[HELD] / d.layers,
            "q3n.expert_load_max_over_mean_pct":
                100.0 * mean[LOAD_MAX] * d.held[1] / mean[HELD],
        })
        return out

    def shapes(self) -> Dict[str, float]:
        d = self.dims
        return {"heads": d.heads, "kv_heads": d.kv_heads,
                "head_dim": d.head_dim, "layers": d.layers,
                "attn_layers": d.layers - d.delta_layers,
                "delta_layers": d.delta_layers, "key_heads": d.key_heads,
                "value_heads": d.value_heads,
                "key_head_dim": d.key_head_dim,
                "value_head_dim": d.value_head_dim,
                "slots": self.engine.config.slots,
                "page_size": self.engine.config.page_size}


def served_gaps(dims, dtype, key, sample, prompts, control=None) -> dict:
    """As `_serve_granite4hs.served_gaps`, over the Qwen3-Next reference:
    every sampled request's prompt and served tokens through the plain
    forward pass, ALL in one call (the reference remakes a layer's weights
    from the seed once a call and takes the sequences one at a time inside
    it), padded to one width (causal: the pad changes nothing before it),
    the logits taken at the positions that foretold served tokens and
    nowhere else. The widest gaps, and beside them the median and the
    99th percentile of every served token's."""
    import jax.numpy as jnp
    from perfbench.reference import qwen3_next
    longest = max(len(prompts[r.id]) + len(r.tokens) for r in sample)
    block = qwen3_next.BLOCK
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
    g = {k: np.asarray(v) for k, v in qwen3_next.served_token_gaps(
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


def served_so_far(engine, results) -> dict:
    """`results`, or where nothing finished what the rows still in their
    slots were served so far (`control_serve_phi4flash.serve_window`
    compares the same): a TRACED window's ticks end where the profiler
    stops (its stop holds the host through what is left of the 51 s), 5 s
    after this mix's first row retires at the chip's 18.1 ms a tick; a
    slower machine's traced window would hold no finished request."""
    if any(r.finish_reason in ("length", "eos") and r.tokens
           for r in results.values()):
        return results
    cut = {st.req.id: types.SimpleNamespace(
        id=st.req.id, tokens=list(st.generated), logprobs=list(st.logprobs),
        finish_reason="length")
        for st in engine.scheduler.active if st.generated}
    log(f"check: no request finished inside the window; comparing what "
        f"{len(cut)} rows were served so far")
    return {**results, **cut}


def check_served(ctx, eng: Engine, results, prompts) -> List[Check]:
    t = ctx.traffic
    sample = _serve.pick_sample(served_so_far(eng.engine, results), prompts,
                                ctx.seed, int(t["check_requests"]))
    if not sample:
        log("check: no finished request to compare")
        return [Check("served_requests_compared", math.nan, 0.0)]
    t0 = time.perf_counter()
    g = served_gaps(eng.dims, eng.dtype, eng.key, sample, prompts)
    log(f"reference {time.perf_counter() - t0:.3f} s over {len(sample)} "
        f"requests, {g['served_tokens']} served tokens (ids "
        f"{[r.id for r in sample]}, lengths "
        f"{[len(prompts[r.id]) + len(r.tokens) for r in sample]}); "
        f"median / p99 logprob gap {g['served_logprob_gap_median']:.5f} / "
        f"{g['served_logprob_gap_p99']:.5f}, p99 logit gap "
        f"{g['served_logit_gap_p99']:.5f}")
    return [Check(name + "_widest", g[name], t["limits"][name + "_widest"])
            for name in ("served_logit_gap", "served_logprob_gap")]
