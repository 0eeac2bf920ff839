"""The serving engine around Granite-4.0-H, for the closed-loop kind: what
`_serve_falconh1.Engine` is for Falcon-H1 and `_serve_deepseekv2.Engine`
for DeepSeek-V2, over another model, another weights module and another
plain reference. Everything that is not the model — warming, the
instrumented tick, the window's counters and samples — is
`_serve.Engine`'s, inherited; the loop seen in its steady state (a first
wave whose prompts are documents, history and the answer so far, built by
prefill inside set-up) is `_serve_phi4flash.deep_closed_loop`'s with a
first wave of this cell's own (`deep_closed_loop` below): what a session
has left to decode counts from the window's opening, and no session ends
near where the window closes.

From `_serve_falconh1.Engine` it inherits what a slot holds beside its
pages (nine layers' recurrent state and conv tails,
`ServingEngine.slot_state_bytes()`) and, for the two kernels' rooflines,
the pages and rows of the CAPTURED ticks (`traced_counters`: contexts
grow all through this window); it adds the expert layers' routing
counters of the window (`GraniteHybridLM.STEP_COUNTERS`). The comparison
takes the reference's logits at served positions alone.

The weights are made on the device in one program and handed to the
engine as its own (`EngineConfig.own_params`): 9.5 GB of them beside 3.7 GB
of state and pool cannot be on the chip twice.
"""
from __future__ import annotations

import math
import time
from statistics import NormalDist
from typing import Dict, List

import numpy as np

from perfbench import generators
from perfbench import weights_granite4hs as weights
from perfbench.harness import Check, log
from perfbench.kinds import _serve, _serve_falconh1
from perfbench.kinds._serve_falconh1 import collector_at_rest  # noqa: F401

#: the model's step counters (`GraniteHybridLM.STEP_COUNTERS`), as this
#: file reads them from the engine's telemetry
HELD, LOAD_MAX = "moe_held_picks", "moe_load_max"
#: served positions the reference's head takes at once
HEAD_POSITIONS = 1024


def clear_quantiles(spec: Dict, n: int, clear) -> List[int]:
    """The n quantiles of a log-normal length distribution, ascending, as
    `generators.length_quantiles` takes them, with the band `clear` =
    (lo, hi) taken out of it: the quantiles of what the distribution holds
    below `lo` and above `hi`, so no length falls inside the band and the
    shape outside it is the distribution's own."""
    lo, hi = (float(x) for x in clear)
    norm = NormalDist()
    cdf = lambda v: norm.cdf(                                  # noqa: E731
        math.log(v / spec["median"]) / spec["sigma"])
    below, band = cdf(lo), cdf(hi) - cdf(lo)
    out = []
    for i in range(n):
        p = (i + 0.5) / n * (1.0 - band)
        v = spec["median"] * math.exp(
            spec["sigma"] * norm.inv_cdf(p if p < below else p + band))
        out.append(int(round(min(max(v, spec["min"]), spec["max"]))))
    return out


def deep_closed_loop(spec: Dict, seed: int, vocab: int):
    """(first wave, backlog) of the closed loop of `clients`, as
    `_serve_phi4flash.deep_closed_loop` makes them (every seed the same
    lengths in the same places, `placement`; the seed draws the token
    ids), but for what the first wave decodes. A row whose context is
    short ends its prefill early and decodes a token a tick through the
    rest of the first wave's calls, so it gets those tokens ON TOP of
    `first_wave.remaining`: what remains counts from the window's opening,
    a row retires at tick `remaining` of the window wherever it was
    placed, and `first_wave.remaining_clear_of`, the band of ticks round
    the window's close, holds no retirement (`clear_quantiles`)."""
    rng = np.random.default_rng([int(seed), 13])
    order = np.random.default_rng([int(spec["placement"]), 14])
    clients, backlog = int(spec["clients"]), int(spec["backlog"])
    permuted = lambda values: [values[i] for i in              # noqa: E731
                               order.permutation(len(values))]
    wave = spec["first_wave"]
    bucket = int(spec["engine"]["chunk_buckets"][-1])
    calls = lambda p: -(-(p - 1) // bucket)                    # noqa: E731
    contexts = generators.length_quantiles(wave["context"], clients)
    lengths = [(p, n + calls(contexts[-1]) - calls(p)) for p, n in zip(
        permuted(contexts),
        permuted(clear_quantiles(wave["remaining"], clients,
                                 wave["remaining_clear_of"])))]
    lengths += list(zip(
        permuted(generators.length_quantiles(spec["prompt"], backlog)),
        permuted(generators.length_quantiles(spec["output"], backlog))))
    out = []
    for i, (p, n) in enumerate(lengths):
        n = min(n, int(spec["max_total"]) - p)
        out.append(generators.GenRequest(
            i, rng.integers(0, vocab, p).tolist(), int(n), 0.0))
    return out[:clients], out[clients:]


def model_of(dims, dtype, max_len: int, decode_kernel: bool):
    from mpi_operator_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                        GraniteHybridLM)
    return GraniteHybridLM(GraniteHybridConfig(
        vocab_size=dims.vocab, max_len=max_len,
        layer_types=dims.layer_types, hidden_size=dims.hidden,
        num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_dim=dims.head_dim, intermediate_size=dims.expert_ffn,
        shared_intermediate_size=dims.shared_ffn,
        num_local_experts=dims.experts_published,
        num_experts_per_tok=dims.top_k, rms_norm_eps=dims.eps,
        mamba_n_heads=dims.ssm_heads, mamba_d_head=dims.ssm_head_dim,
        mamba_d_state=dims.d_state, mamba_n_groups=dims.groups,
        mamba_d_conv=dims.d_conv, mamba_chunk_size=dims.chunk,
        embedding_multiplier=dims.embedding_multiplier,
        residual_multiplier=dims.residual_multiplier,
        attention_multiplier=dims.attention_multiplier,
        logits_scaling=dims.logits_scaling, held=dims.held, dtype=dtype,
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
                           f"perfbench.weights_granite4hs makes: {odd}")


class Engine(_serve_falconh1.Engine):
    """The serving engine over Granite-4.0-H with the recorders and
    counters of one run. What a slot holds, the captured ticks' counters
    (`traced_counters`) and the step's scopes (`op_scopes`) are
    `_serve_falconh1.Engine`'s, inherited."""

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

    def tick(self) -> bool:
        """`_serve.Engine.tick`, and set-up's work kept inside set-up: in
        the tick that sends the first wave's LAST prefill call, every
        dispatched step is fetched before the tick returns. At
        `async_depth` 8 the device is eight steps behind the host there,
        each with a `[48, 128]` call of half a second before it; a window
        opened on that queue would hold four seconds of the first wave
        (the twelve runs of PR 42's review did: PERF.md section 6). A
        replacement's call later on drains nothing."""
        worked = super().tick()
        if worked and not self.first_wave_out \
                and self.tick_prefilled_rows[-1] > 0 \
                and self.engine.scheduler.next_prefill() is None:
            self.first_wave_out = True
            self.engine.drain()
        return worked

    def window_counters(self, t0: float, t1: float) -> Dict[str, float]:
        """`_serve.Engine`'s counts, what a slot holds, and the expert
        layers': means over the decode steps fetched in [t0, t1), a
        layer."""
        out = super().window_counters(t0, t1)
        mean = {n: float(np.mean([v for t, v in zip(r.at, r.values)
                                  if t0 <= t < t1] or [math.nan]))
                for n, r in self.step_counts.items()}
        if not all(np.isfinite(list(mean.values()))) \
                or mean.get(HELD, 0.0) <= 0:
            return out
        d = self.dims
        out.update({
            "g4hs.held_assignments_per_step": mean[HELD] / d.layers,
            "g4hs.expert_load_max_over_mean_pct":
                100.0 * mean[LOAD_MAX] * d.held[1] / mean[HELD],
        })
        return out

    def shapes(self) -> Dict[str, float]:
        d = self.dims
        return {"heads": d.heads, "kv_heads": d.kv_heads,
                "head_dim": d.head_dim, "layers": d.layers,
                "attn_layers": d.layers - d.mamba_layers,
                "mamba_layers": d.mamba_layers, "ssm_heads": d.ssm_heads,
                "ssm_head_dim": d.ssm_head_dim, "d_state": d.d_state,
                "groups": d.groups, "slots": self.engine.config.slots,
                "page_size": self.engine.config.page_size}


def served_gaps(dims, dtype, key, sample, prompts, control=None) -> dict:
    """As `_serve_deepseekv2.served_gaps`, over the Granite reference:
    every sampled request's prompt and served tokens through the plain
    forward pass, ALL in one call (the reference remakes a layer's weights
    from the seed once a call and takes the sequences one at a time inside
    it), padded to one width (causal: the pad changes nothing before it),
    the logits taken at the positions that foretold served tokens and
    nowhere else. The widest gaps, and beside them the median and the
    99th percentile of every served token's."""
    import jax.numpy as jnp
    from perfbench.reference import granite_hybrid
    longest = max(len(prompts[r.id]) + len(r.tokens) for r in sample)
    block = granite_hybrid.BLOCK
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
    g = {k: np.asarray(v) for k, v in granite_hybrid.served_token_gaps(
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
        f"{[len(prompts[r.id]) + len(r.tokens) for r in sample]}); "
        f"median / p99 logprob gap {g['served_logprob_gap_median']:.5f} / "
        f"{g['served_logprob_gap_p99']:.5f}, p99 logit gap "
        f"{g['served_logit_gap_p99']:.5f}")
    return [Check(name + "_widest", g[name], t["limits"][name + "_widest"])
            for name in ("served_logit_gap", "served_logprob_gap")]
