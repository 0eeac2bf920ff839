"""What the two kinds of serving traffic share: building the engine around
the benchmark's seeded weights, warming the shapes the mix can hit, the
instrumented tick, the clients of a closed loop (`Clients`, the one place
where a closed-loop kind's requests are sent), and the comparison of
served tokens with the plain reference.

The engine is driven through `ServingEngine.start/submit/tick/finish` and
read through what it offers to any caller: its `telemetry=` hook (the
harness hands it recorders that keep every sample), `session_results()`,
and the scheduler's and slot manager's public state.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from perfbench import generators, harness, weights
from perfbench.harness import Check, log, span


class Recorder:
    """Stands where the engine expects a telemetry histogram and keeps
    every observation with the time it was made."""

    def __init__(self):
        self.at: List[float] = []
        self.values: List[float] = []

    def observe(self, x: float) -> None:
        self.at.append(time.perf_counter())
        self.values.append(x)


class Engine:
    """The serving engine with the recorders and counters of one run."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from mpi_operator_tpu.models.transformer import (CausalLM,
                                                         TransformerConfig)
        from mpi_operator_tpu.serve import EngineConfig, ServingEngine
        from mpi_operator_tpu.telemetry.worker import ServeTelemetry

        e = ctx.traffic["engine"]
        self.dims = dims = weights.Dims.from_config(ctx.config)
        self.dtype = jnp.dtype(e["weights_dtype"])
        self.key = weights.seed_key(ctx.seed)
        model = CausalLM(TransformerConfig(
            vocab_size=dims.vocab, max_len=dims.positions,
            num_layers=dims.layers, num_heads=dims.heads,
            embed_dim=dims.embed, mlp_dim=dims.mlp, causal=True,
            dtype=self.dtype, decode_kernel=bool(e["decode_kernel"])))
        # made on the device in one program, then handed over through the
        # host: the engine copies what it is given (`cast_params`, even in
        # the served type) and builds its cache while the caller's copy is
        # still alive, and two copies of the weights, the pool and the 3.6 GB
        # of scratch that `init_cache` needs do not fit the chip
        made = jax.jit(lambda k: weights.make_params(k, dims, self.dtype))(
            self.key)
        params = jax.device_get(made)
        harness.delete_arrays(made)
        self.telemetry = ServeTelemetry()
        self.host_gap = self.telemetry.host_gap_seconds = Recorder()
        self.decode_step = self.telemetry.decode_step_seconds = Recorder()
        self.prefill = self.telemetry.prefill_seconds = Recorder()
        self.engine = ServingEngine(model, params, EngineConfig(
            slots=int(e["slots"]), chunk_buckets=tuple(e["chunk_buckets"]),
            decode_kernel=bool(e["decode_kernel"]), rng_seed=0,
            async_decode=bool(e["async_decode"]), paged=True,
            page_size=int(e["page_size"]), num_pages=int(e["num_pages"]),
            prefix_cache=bool(e["prefix_cache"]),
            request_timeout=e.get("request_timeout_s")),
            telemetry=self.telemetry)
        shapes = weights.tree_shapes(self.engine.params)
        if shapes != weights.tree_shapes(params):
            raise RuntimeError("the engine does not serve the tree "
                               "perfbench.weights makes")
        del params
        # per-tick evidence, each with the time its tick began
        self.tick_at: List[float] = []
        self.tick_s: List[float] = []
        self.tick_prefilled_rows: List[int] = []
        self.tick_occupied: List[int] = []
        self.tick_tokens_in_pages: List[int] = []
        self.tick_decoding_rows: List[int] = []

    def warm(self, prompt_lengths, vocab: int) -> Dict[str, int]:
        """Compile, or load from the cache, the decode step and each
        prefill bucket that prompts of `prompt_lengths` can take — and no
        other program: one short request a bucket, outside any window."""
        from mpi_operator_tpu.serve import Request
        from mpi_operator_tpu.serve.scheduler import plan_chunks
        buckets = self.engine.config.chunk_buckets
        by_bucket: Dict[int, int] = {}
        for p in sorted(set(prompt_lengths)):
            for _, size in plan_chunks(p - 1, buckets):
                by_bucket.setdefault(size, p)
        rng = np.random.default_rng(5)
        reqs = [Request(id=-1 - i, prompt=rng.integers(0, vocab, p).tolist(),
                        max_new_tokens=2)
                for i, p in enumerate(by_bucket.values())]
        self.engine.run(reqs)
        return self.engine.compile_counts()

    def tick(self) -> bool:
        """One `engine.tick()` under a span, with what it did counted."""
        eng = self.engine
        before = {id(st): len(st.chunks) for st in eng.scheduler.active}
        calls = len(self.prefill.values)
        t0 = time.perf_counter()
        with span("tick"):
            worked = eng.tick()
        if not worked:
            return False
        self.tick_at.append(t0)
        self.tick_s.append(time.perf_counter() - t0)
        rows = sum(1 for st in eng.scheduler.active
                   if len(st.chunks) < before.get(id(st), len(st.chunks)))
        # a row whose last chunk ran and that retired in the same tick is
        # no longer active; a call with no row left to see still counts one
        if len(self.prefill.values) > calls:
            rows = max(rows, 1)
        self.tick_prefilled_rows.append(rows)
        self.tick_occupied.append(eng.slots.occupied)
        ps = eng.config.page_size
        decoding = [st for st in eng.scheduler.active if not st.prefilling]
        self.tick_decoding_rows.append(len(decoding))
        self.tick_tokens_in_pages.append(
            sum(math.ceil(max(st.pos, 1) / ps) * ps for st in decoding))
        return True

    def window_counters(self, t0: float, t1: float) -> Dict[str, float]:
        """Counts over the ticks that began in [t0, t1)."""
        pick = [i for i, t in enumerate(self.tick_at) if t0 <= t < t1]
        if not pick:
            return {}
        slots = self.engine.config.slots
        rows = [self.tick_prefilled_rows[i] for i in pick]
        calls = sum(1 for r in rows if r > 0)
        out = {
            "serve.ticks": float(len(pick)),
            "serve.slot_occupancy_pct": 100.0 * float(np.mean(
                [self.tick_occupied[i] for i in pick])) / slots,
            "serve.prefill_tick_share_pct": 100.0 * calls / len(pick),
            "serve.tokens_in_pages_mean": float(np.mean(
                [self.tick_tokens_in_pages[i] for i in pick])),
            "serve.decoding_rows_mean": float(np.mean(
                [self.tick_decoding_rows[i] for i in pick])),
        }
        if calls:
            out["serve.prefill_rows_per_call"] = sum(rows) / calls
        return out

    def samples(self, t0: float, t1: float,
                tracer=None) -> Dict[str, List[float]]:
        """Host-timed samples of the window, without those that ran while
        the profiler started or stopped (they measure the profiler)."""
        def quiet(at, dur):
            return tracer is None or not tracer.overlaps(at - dur, at + dur)

        def of(rec):
            return [1e3 * v for t, v in zip(rec.at, rec.values)
                    if t0 <= t < t1 and quiet(t, v)]
        return {
            "serve.tick_ms": [1e3 * s for t, s in zip(self.tick_at,
                                                      self.tick_s)
                              if t0 <= t < t1 and quiet(t, s)],
            "serve.host_blocked_ms": of(self.host_gap),
            "serve.decode_step_ms": of(self.decode_step),
        }

    def shapes(self) -> Dict[str, float]:
        d = self.dims
        return {"heads": d.heads, "kv_heads": d.heads,
                "head_dim": d.head_dim, "layers": d.layers,
                "slots": self.engine.config.slots,
                "page_size": self.engine.config.page_size}

    def free(self) -> None:
        """Give the device back before the reference runs."""
        harness.delete_arrays((self.engine.params, self.engine.cache))


class Clients:
    """The callers of a closed loop, for every closed-loop kind: each sends
    its next request when its last completes, so the engine is kept as
    full as admission allows. They send the mix's first wave, then its
    backlog in order, and past the backlog's end the backlog's LENGTHS
    again under ids that continue the count and token ids drawn anew
    (`generators.lap_request`): the loop never runs dry, at whatever rate
    the program serves, and what it sends while the backlog lasts does not
    depend on that. A lap's lengths are lengths `Engine.warm` has seen.

    Creating it starts the engine's session and submits the first wave.
    `prompts` holds every prompt sent, by request id, for the comparison
    after the window; `token_at` the host's clock at each fetched token;
    `answered_at_tick` the count of ticks at each completion answered."""

    def __init__(self, eng: Engine, first, backlog, seed: int):
        from mpi_operator_tpu.serve import Request
        if not backlog:
            raise ValueError("a closed loop needs a backlog: past its end "
                             "the clients send its lengths again")
        self._request = Request
        self.eng, self.engine = eng, eng.engine
        self.backlog, self.seed = backlog, int(seed)
        self.vocab = eng.dims.vocab_real
        self.prompts = {r.id: r.prompt for r in first + backlog}
        self.token_at: List[float] = []
        self.answered_at_tick: List[int] = []
        self.sent = self.answered = 0
        self.base = time.perf_counter()
        self.engine.start(on_token=lambda req, tok: self.token_at.append(
            time.perf_counter()), now_fn=self.now)
        for r in first:
            self._submit(r, 0.0)

    def now(self) -> float:
        return time.perf_counter() - self.base

    @property
    def lapped(self) -> int:
        """How many requests came from past the backlog's end."""
        return max(0, self.answered - len(self.backlog))

    def _submit(self, r, arrival: float) -> None:
        self.engine.submit(self._request(
            id=r.id, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
            arrival=arrival))
        self.sent += 1

    def answer_completions(self) -> None:
        """Each client whose request completed sends its next one now."""
        done = len(self.engine.session_results())
        while self.answered < done:
            k = self.answered - len(self.backlog)
            if k < 0:
                r = self.backlog[self.answered]
            else:
                r = generators.lap_request(self.backlog, k, self.seed,
                                           self.vocab)
                self.prompts[r.id] = r.prompt
            self._submit(r, self.now())
            self.answered += 1
            self.answered_at_tick.append(len(self.eng.tick_at))

    def first_wave(self, limit_s: float) -> None:
        """Tick until nobody prefills and admission is at rest."""
        engine = self.engine
        while True:
            occupied = engine.slots.occupied
            self.eng.tick()
            self.answer_completions()
            if engine.scheduler.next_prefill() is None \
                    and engine.slots.occupied == occupied:
                return
            if time.perf_counter() - self.base > limit_s:
                raise RuntimeError("the first wave did not come to rest "
                                   f"within {limit_s} s")

    def close(self, t_open: float, t_close: float):
        """(tokens fetched in [t_open, t_close), the session's results,
        how many of them did not end by length, what to log of them)."""
        results = dict(self.engine.session_results())
        tokens = sum(1 for x in self.token_at if t_open <= x < t_close)
        failed = sum(1 for r in results.values()
                     if r.finish_reason != "length")
        return tokens, results, failed, (
            f"{len(results)} requests finished of {self.sent} sent "
            f"({failed} not by length; {self.lapped} from past the end of "
            f"the backlog's {len(self.backlog)})")


def pick_sample(results: Dict[int, object], prompts: Dict[int, List[int]],
                seed: int, n: int):
    """`n` of the finished requests, drawn from the seed, the longest
    (prompt plus served tokens) always among them."""
    done = [r for r in results.values()
            if r.id in prompts and r.finish_reason in ("length", "eos")
            and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.id)
    longest = max(done, key=lambda r: len(prompts[r.id]) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 17])
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in take]


def served_gaps(dims, dtype, key, sample, prompts, control=None) -> dict:
    """One pass of the plain reference over each sampled request's prompt
    and served tokens. Numbers, each the widest over all served tokens:
    `served_logit_gap` — how far a served token's logit lies under the
    reference's best; `served_logprob_gap` — how far the log-probability
    the engine reported for it lies from the reference's. With `control`,
    the same two for the token that the lower precision puts first at
    each position of the same prompts and tokens."""
    import jax.numpy as jnp
    from perfbench.reference import gpt2
    width = dims.positions
    out = {"served_logit_gap": 0.0, "served_logprob_gap": 0.0,
           "served_tokens": 0}
    if control:
        out.update(control_logit_gap=0.0, control_logprob_gap=0.0)
    for r in sample:
        seq = list(prompts[r.id]) + list(r.tokens)
        p, n = len(prompts[r.id]), len(r.tokens)
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(seq)] = seq
        g = {k: np.asarray(v)[0, p - 1:p - 1 + n]   # p-1+i foretells token i
             for k, v in gpt2.served_token_gaps(
                 key, jnp.asarray(padded), dims, dtype, control).items()}
        out["served_logit_gap"] = max(out["served_logit_gap"],
                                      float(g["served_gap"].max()))
        out["served_logprob_gap"] = max(
            out["served_logprob_gap"],
            float(np.abs(np.asarray(r.logprobs) - g["served_ref_logp"])
                  .max()))
        if control:
            out["control_logit_gap"] = max(out["control_logit_gap"],
                                           float(g["other_gap"].max()))
            out["control_logprob_gap"] = max(
                out["control_logprob_gap"],
                float(np.abs(g["other_own_logp"] - g["other_ref_logp"])
                      .max()))
        out["served_tokens"] += n
    return out


def check_served(ctx, eng: Engine, results, prompts) -> List[Check]:
    t = ctx.traffic
    sample = pick_sample(results, prompts, ctx.seed, int(t["check_requests"]))
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
