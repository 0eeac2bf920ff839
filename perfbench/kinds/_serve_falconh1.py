"""The serving engine around Falcon-H1, for the closed-loop kind: what
`_serve_phi4flash.Engine` is for Phi-4-mini-flash, over another model,
another weights module and another plain reference. Everything that is not
the model — warming, the instrumented tick, the window's counters and
samples — is `_serve.Engine`'s, inherited; the loop seen in its steady
state (a first wave whose prompts are instruction plus the answer so far,
built by prefill inside set-up) is `_serve_phi4flash.deep_closed_loop`.

The comparison takes the reference's logits at served positions alone, the
head over 512 of them at a time: an answer of three thousand tokens over a
vocabulary of 261 120 would be 3 GB of logits at once.

The weights are made on the device in one program and handed to the engine
as its own (`EngineConfig.own_params`): 8.79 GB of them beside 4.3 GB of
state and pool cannot be on the chip twice.
"""
from __future__ import annotations

import contextlib
import gc
import math
import time
from typing import Dict, List

import numpy as np

from perfbench import weights_falconh1 as weights
from perfbench.harness import Check, log
from perfbench.kinds import _serve
from perfbench.kinds._serve_phi4flash import deep_closed_loop  # noqa: F401

#: served positions the reference's head takes at once
HEAD_POSITIONS = 512


@contextlib.contextmanager
def collector_at_rest():
    """Python's cyclic collector stands still inside: what set-up left is
    collected once and frozen, and nothing is collected until the block
    ends. Reference counting frees as before; only cycles wait.

    This cell's tick leaves the host 8 ms of slack under a 16-17 ms step,
    2 640 times a window, and allocates for 96 rows a tick. With the
    collector on, a window held 133 collections, each of which also calls
    into the runtime (`jax._src.lib._xla_gc_callback`): the full one, 39.9
    s into every window, took 89-105 ms, and now and then a young one
    spent 100 ms of the main thread inside `collect_garbage()`; each is
    0.2% of `serve_tokens_per_s`, and half the bound is 0.5%. What stays
    is not the collector's (PERF.md, PR 33): about one tick a window in
    which the main thread sleeps 110 ms on a transfer the device finished
    long before."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


def model_of(dims, dtype, max_len: int, decode_kernel: bool):
    from mpi_operator_tpu.models.falcon_h1 import FalconH1Config, FalconH1LM
    return FalconH1LM(FalconH1Config(
        vocab_size=dims.vocab, max_len=max_len, num_layers=dims.layers,
        hidden_size=dims.hidden, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        intermediate_size=dims.ffn, rms_norm_eps=dims.eps,
        rope_theta=dims.rope_theta, mamba_d_ssm=dims.d_ssm,
        mamba_n_heads=dims.ssm_heads, mamba_d_state=dims.d_state,
        mamba_n_groups=dims.groups, mamba_d_conv=dims.d_conv,
        mamba_chunk_size=dims.chunk,
        embedding_multiplier=dims.embedding_multiplier,
        lm_head_multiplier=dims.lm_head_multiplier,
        attention_in_multiplier=dims.attention_in_multiplier,
        attention_out_multiplier=dims.attention_out_multiplier,
        key_multiplier=dims.key_multiplier,
        ssm_in_multiplier=dims.ssm_in_multiplier,
        ssm_out_multiplier=dims.ssm_out_multiplier,
        ssm_multipliers=dims.ssm_multipliers,
        mlp_multipliers=dims.mlp_multipliers, dtype=dtype,
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
                           f"perfbench.weights_falconh1 makes: {odd}")


class Engine(_serve.Engine):
    """The serving engine over Falcon-H1 with the recorders and counters
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
        # what a slot holds beside its pages: a number of the engine, the
        # same on every tick
        self.slot_state = float(self.engine.slot_state_bytes())

    def window_counters(self, t0: float, t1: float) -> Dict[str, float]:
        out = super().window_counters(t0, t1)
        if out:
            out["serve.slot_state_bytes_per_row"] = self.slot_state
        return out

    def traced_counters(self, tracer, window: Dict[str, float]
                        ) -> Dict[str, float]:
        """What the captured steps' kernels read, for the rooflines: the
        means over the ticks of the traced sub-window. Contexts grow all
        through this cell's window, so the window's own mean is the
        context of its middle, and a roofline that sets it against the
        kernels' time at 30 s reads 53% where one at 5 s read 90%. Where
        no tick began inside the capture, the window's means."""
        if len(tracer.disturbed) != 2:
            return {}
        sub = super().window_counters(tracer.disturbed[0][1],
                                      tracer.disturbed[1][0]) or window
        return {"serve.traced_" + name: sub["serve." + name]
                for name in ("tokens_in_pages_mean", "decoding_rows_mean")
                if "serve." + name in sub}

    def shapes(self) -> Dict[str, float]:
        d = self.dims
        return {"heads": d.heads, "kv_heads": d.kv_heads,
                "head_dim": d.head_dim, "layers": d.layers,
                "ssm_heads": d.ssm_heads, "ssm_head_dim": d.ssm_head_dim,
                "d_state": d.d_state, "groups": d.groups,
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


def served_gaps(dims, dtype, key, sample, prompts, control=None,
                rows: int = 2) -> dict:
    """As `_serve_phi4flash.served_gaps`, over the Falcon-H1 reference:
    every sampled request's prompt and served tokens through the plain
    forward pass, `rows` sequences a call, all padded to one width
    (causal: the pad changes nothing before it), the logits taken at the
    positions that foretold served tokens and nowhere else."""
    import jax.numpy as jnp
    from perfbench.reference import falcon_h1
    out = {"served_logit_gap": 0.0, "served_logprob_gap": 0.0,
           "served_tokens": 0}
    if control:
        out.update(control_logit_gap=0.0, control_logprob_gap=0.0)
    longest = max(len(prompts[r.id]) + len(r.tokens) for r in sample)
    block = falcon_h1.BLOCK
    width = longest if longest <= block else -(-longest // block) * block
    most = max(len(r.tokens) for r in sample)
    served = (most if most <= HEAD_POSITIONS
              else -(-most // HEAD_POSITIONS) * HEAD_POSITIONS)
    for lo in range(0, len(sample), rows):
        part = sample[lo:lo + rows]
        padded = np.zeros((len(part), width), np.int32)
        at = np.zeros((len(part), served), np.int32)
        for i, r in enumerate(part):
            seq = list(prompts[r.id]) + list(r.tokens)
            padded[i, :len(seq)] = seq
            p = len(prompts[r.id])             # p-1+j foretells token j
            at[i] = np.minimum(p - 1 + np.arange(served), width - 1)
        g = {k: np.asarray(v) for k, v in falcon_h1.served_token_gaps(
            key, jnp.asarray(padded), jnp.asarray(at), dims, dtype,
            control, HEAD_POSITIONS).items()}
        for i, r in enumerate(part):
            n = len(r.tokens)
            out["served_logit_gap"] = max(
                out["served_logit_gap"], float(g["served_gap"][i, :n].max()))
            out["served_logprob_gap"] = max(
                out["served_logprob_gap"],
                float(np.abs(np.asarray(r.logprobs)
                             - g["served_ref_logp"][i, :n]).max()))
            if control:
                out["control_logit_gap"] = max(
                    out["control_logit_gap"],
                    float(g["other_gap"][i, :n].max()))
                out["control_logprob_gap"] = max(
                    out["control_logprob_gap"],
                    float(np.abs(g["other_own_logp"][i, :n]
                                 - g["other_ref_logp"][i, :n]).max()))
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
        f"{[r.id for r in sample]}, lengths "
        f"{[len(prompts[r.id]) + len(r.tokens) for r in sample]})")
    return [Check(name + "_widest", g[name], t["limits"][name + "_widest"])
            for name in ("served_logit_gap", "served_logprob_gap")]
