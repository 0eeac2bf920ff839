"""The serving engine around Phi-4-mini-flash-reasoning, for the closed-loop
kind: what `_serve.Engine` is for GPT-2, over another model, another
weights module and another plain reference. Everything that is not the
model — warming, the instrumented tick, the window's counters and samples
— is `_serve.Engine`'s, inherited.

Two things are this mix's own. The first wave of `reason-deep-closed` is
a loop seen in its steady state: a request's prompt IS its question plus
the trace decoded so far (`first_wave.context`), and what it may still
decode is `first_wave.remaining`; the backlog is what a client sends when
its trace ends (`prompt`, `output`). `deep_closed_loop` makes both with
the generator's own quantiles and placement. And the comparison takes the
reference's logits at served positions alone: a context of ten thousand
tokens foretells a few hundred served ones.

The weights are made on the device in one program and handed to the
engine as its own (`EngineConfig.own_params`): 7.71 GB of them beside 5.2
GB of cache cannot be on the chip twice.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from perfbench import generators
from perfbench import weights_phi4flash as weights
from perfbench.harness import Check, log
from perfbench.kinds import _serve


def deep_closed_loop(spec: Dict, seed: int, vocab: int):
    """(first wave, backlog) of the closed loop of `clients`, every seed
    with the same lengths in the same places (`placement`); the seed
    draws the token ids."""
    rng = np.random.default_rng([int(seed), 13])
    order = np.random.default_rng([int(spec["placement"]), 14])
    clients, backlog = int(spec["clients"]), int(spec["backlog"])
    permuted = lambda values: [values[i] for i in              # noqa: E731
                               order.permutation(len(values))]
    wave = spec["first_wave"]
    lengths = list(zip(
        permuted(generators.length_quantiles(wave["context"], clients)),
        permuted(generators.length_quantiles(wave["remaining"], clients))))
    lengths += list(zip(
        permuted(generators.length_quantiles(spec["prompt"], backlog)),
        permuted(generators.length_quantiles(spec["output"], backlog))))
    out = []
    for i, (p, n) in enumerate(lengths):
        n = min(n, int(spec["max_total"]) - p)
        if n < 1:
            raise ValueError(f"prompt of {p} leaves no room under "
                             f"max_total={spec['max_total']}")
        out.append(generators.GenRequest(
            i, rng.integers(0, vocab, p).tolist(), int(n), 0.0))
    return out[:clients], out[clients:]


def model_of(dims, dtype, max_len: int, decode_kernel: bool):
    from mpi_operator_tpu.models.phi4flash import (Phi4FlashConfig,
                                                   Phi4FlashLM)
    return Phi4FlashLM(Phi4FlashConfig(
        vocab_size=dims.vocab, max_len=max_len, num_layers=dims.layers,
        hidden_size=dims.hidden, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, intermediate_size=dims.ffn,
        sliding_window=dims.window, layer_norm_eps=dims.eps,
        mamba_d_state=dims.d_state, mamba_d_conv=dims.d_conv,
        mamba_expand=dims.expand, mamba_dt_rank=dims.dt_rank, dtype=dtype,
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
                           f"perfbench.weights_phi4flash makes: {odd}")


class Engine(_serve.Engine):
    """The serving engine over Phi-4-mini-flash with the recorders and
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
        # this model's own: what the window layers read, and what a slot
        # holds beside its pages (absent on a program without them)
        self.tick_window_tokens: List[int] = []
        self.tick_slot_state: List[float] = []
        self._slot_state = getattr(self.engine, "slot_state_bytes", None)

    def tick(self) -> bool:
        worked = super().tick()
        if worked:
            eng = self.engine
            ps, w = eng.config.page_size, self.dims.window
            self.tick_window_tokens.append(sum(
                (st.pos // ps - max(0, st.pos - w + 1) // ps + 1) * ps
                for st in eng.scheduler.active if not st.prefilling))
            if self._slot_state is not None:
                self.tick_slot_state.append(float(self._slot_state()))
        return worked

    def window_counters(self, t0: float, t1: float) -> Dict[str, float]:
        """`_serve.Engine`'s counts, and the two above over the same
        ticks."""
        out = super().window_counters(t0, t1)
        pick = [i for i, t in enumerate(self.tick_at) if t0 <= t < t1]
        if not pick:
            return out
        out["serve.window_tokens_in_pages_mean"] = float(np.mean(
            [self.tick_window_tokens[i] for i in pick]))
        if self.tick_slot_state:
            held = [self.tick_slot_state[i] for i in pick]
            out["serve.slot_state_bytes_per_row"] = float(np.mean(held))
            out["serve.slot_state_bytes_spread"] = max(held) - min(held)
        return out

    def shapes(self) -> Dict[str, float]:
        d = self.dims
        kinds = [d.kind(l) for l in range(d.layers)]
        return {"heads": d.heads, "pair_dim": 2 * d.head_dim,
                "kv_pairs": d.kv_heads // 2,
                "shared_kv_reads": sum(k in ("full", "cross") for k in kinds),
                "window_reads": kinds.count("swa"),
                "layers": d.layers, "slots": self.engine.config.slots,
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
                rows: int = 2) -> dict:
    """As `_serve.served_gaps`, over the Phi-4-mini-flash reference:
    every sampled request's prompt and served tokens through the plain
    forward pass, `rows` sequences a call, all padded to one width
    (causal: the pad changes nothing before it), the logits taken at the
    positions that foretold served tokens and nowhere else."""
    import jax.numpy as jnp
    from perfbench.reference import phi4_flash
    out = {"served_logit_gap": 0.0, "served_logprob_gap": 0.0,
           "served_tokens": 0}
    if control:
        out.update(control_logit_gap=0.0, control_logprob_gap=0.0)
    longest = max(len(prompts[r.id]) + len(r.tokens) for r in sample)
    block = phi4_flash.BLOCK
    width = longest if longest <= block else -(-longest // block) * block
    most = max(len(r.tokens) for r in sample)
    served = most if most <= 128 else -(-most // 128) * 128
    for lo in range(0, len(sample), rows):
        part = sample[lo:lo + rows]
        padded = np.zeros((rows, width), np.int32)
        at = np.zeros((rows, served), np.int32)
        for i, r in enumerate(part):
            seq = list(prompts[r.id]) + list(r.tokens)
            padded[i, :len(seq)] = seq
            p = len(prompts[r.id])             # p-1+j foretells token j
            at[i] = np.minimum(p - 1 + np.arange(served), width - 1)
        g = {k: np.asarray(v) for k, v in phi4_flash.served_token_gaps(
            key, jnp.asarray(padded), jnp.asarray(at), dims, dtype,
            control).items()}
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
