"""Autoregressive text generation for CausalLM — KV-cache decode.

The reference framework is training-only (its data plane is an opaque
Horovod image, SURVEY.md §2.2); this is the inference half a complete
framework needs, built TPU-first:

- ONE jitted program for the whole generation: prefill (the full prompt in
  a single call, filling the KV cache) followed by a `lax.scan` over the
  decode steps — static shapes and trip count, so XLA compiles it once and
  the MXU sees batched [B, 1, E] matmuls against the cached [B, L, H, D]
  K/V instead of recomputing the prefix every token.
- The cache lives in flax's "cache" collection (models/transformer.py
  Attention._decode_attend); `decode=True` adds no parameters, so trained
  LMTrainer params load directly.
- Sampling: greedy (temperature=0) or temperature sampling via
  jax.random.categorical; optional `eos_id` freezes finished rows (they
  keep emitting eos and their logits are ignored).

Usage:
    model = CausalLM(gpt2_config("medium"))
    out = generate(model, params, prompt_tokens, max_new_tokens=64)
    # out.tokens: [B, prompt_len + max_new_tokens]
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


class GenerateResult(NamedTuple):
    tokens: jax.Array          # [B, prompt_len + max_new_tokens]
    logprobs: jax.Array        # [B, max_new_tokens] logprob of each choice


def decode_model(model, decode_kernel: Optional[bool] = None,
                 page_size: Optional[int] = None, num_pages: int = 0):
    """The decode-mode twin of a trained CausalLM: same params (decode
    adds none, so checkpoints load directly), dense attention (the cache
    path does its own masking), no remat. `decode_kernel` None inherits
    the model config. generate() keeps the lockstep twin; `page_size`/
    `num_pages` give the serving engine's (serve/programs.py): the
    page-pool cache driven by per-row cursors and page tables
    (transformer.py decode_page_size)."""
    cfg = model.config
    changes = dict(
        decode=True, attention="dense", remat=False,
        decode_page_size=page_size, decode_num_pages=num_pages,
        decode_kernel=(cfg.decode_kernel if decode_kernel is None
                       else decode_kernel))
    # a config that has no such knob (another family's: no choice of
    # training attention, no remat) is not given it
    known = {f.name for f in dataclasses.fields(cfg)}
    return type(model)(dataclasses.replace(
        cfg, **{k: v for k, v in changes.items() if k in known}))


def cast_params(params, dtype):
    """Cast f32 master params to the decode compute dtype, fenced behind
    an optimization_barrier. Decode is HBM-bound — every step re-reads
    the whole parameter set — and without the barrier XLA sinks the
    convert INTO the decode while-loop (rematerializing it per step as
    sliced chunks), so every step re-reads the 2x-bigger f32 masters:
    measured on v5e via the op trace, 76k slice/convert ops inside the
    loop, 45% MBU. Call INSIDE the jitted program that loops (generate),
    or once up front in a dedicated jit whose output stays device-resident
    across many step calls (the serving engine)."""
    params = jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, params)
    return jax.lax.optimization_barrier(params)


def _sample(logits, greedy, temperature, rng, top_k, use_top_p, top_p):
    """[B, V] logits → ([B] token, [B] logprob of the chosen token).
    `greedy`/`top_k`/`use_top_p` are static (they change the program);
    `temperature` and the `top_p` threshold are traced operands so value
    sweeps share one compile (top_k stays static — it is a slice index).
    Reported logprobs are from the UNfiltered distribution (what the
    model assigned), not the renormalized sampling distribution."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    if greedy:
        tok = jnp.argmax(logits, axis=-1)
        return tok, jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    scaled = logp / temperature
    if top_k is not None:
        # keep the k highest-scoring tokens, mask the rest (lax.top_k,
        # not a full vocab sort — this runs every decode step)
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if use_top_p:
        # nucleus: smallest prefix of the sorted distribution with
        # cumulative probability >= top_p (the kept set always includes
        # the most likely token)
        sorted_p = jnp.sort(jax.nn.softmax(scaled), axis=-1)[:, ::-1]
        cum = jnp.cumsum(sorted_p, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)       # [B]
        cutoff = jnp.take_along_axis(sorted_p, cutoff_idx[:, None],
                                     axis=-1)            # prob threshold
        probs = jax.nn.softmax(scaled)
        scaled = jnp.where(probs < cutoff, -jnp.inf, scaled)
    tok = jax.random.categorical(rng, scaled)
    return tok, jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]


@partial(jax.jit, static_argnums=(0, 3, 6, 7, 8, 9))
def _generate_jit(dmodel, params, prompt, max_new_tokens, temperature,
                  rng, eos_id, greedy, top_k, use_top_p, top_p):
    from .transformer import _head_matmul

    B, P = prompt.shape
    # cast the f32 masters to the compute dtype once up front — see
    # cast_params for why the barrier is load-bearing. The cast is part
    # of this program so generate() stays one dispatch that takes a
    # trainer's f32 masters as they are: it reads them once per call,
    # against max_new_tokens decode steps that each re-read the cast
    # copy. A caller that decodes many times from one parameter set
    # casts once itself (run_generate_benchmark, ServingEngine) and this
    # is then a no-op.
    params = cast_params(params, dmodel.config.dtype)
    table = params["wte"]["embedding"]

    # prefill: one multi-token call fills the cache; only the LAST
    # position's logits are needed, so run the backbone head-free and pay
    # the vocab matmul on h[:, -1:] alone (not the full [B, P, V] tensor)
    h, vars_ = dmodel.apply(
        {"params": params}, prompt, with_head=False, mutable=["cache"])
    logits = _head_matmul(h[:, -1:], table)
    cache = vars_["cache"]
    rng, sub = jax.random.split(rng)
    tok, logp = _sample(logits[:, -1], greedy, temperature, sub,
                        top_k, use_top_p, top_p)
    done = jnp.zeros((B,), bool)
    if eos_id is not None:
        done = tok == eos_id

    def step(carry, i):
        cache, tok, rng, done = carry
        h, vars_ = dmodel.apply(
            {"params": params, "cache": cache}, tok[:, None],
            positions=(P + i)[None, None], with_head=False,
            mutable=["cache"])
        logits = _head_matmul(h, table)
        rng, sub = jax.random.split(rng)
        nxt, logp = _sample(logits[:, -1], greedy, temperature, sub,
                            top_k, use_top_p, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            logp = jnp.where(done, 0.0, logp)
            done = done | (nxt == eos_id)
        return (vars_["cache"], nxt, rng, done), (nxt, logp)

    (_, _, _, _), (toks, logps) = lax.scan(
        step, (cache, tok, rng, done), jnp.arange(max_new_tokens - 1))
    all_new = jnp.concatenate([tok[:, None], toks.T], axis=1)
    all_logp = jnp.concatenate([logp[:, None], logps.T], axis=1)
    return GenerateResult(jnp.concatenate([prompt, all_new], axis=1),
                          all_logp)


def generate(model, params, prompt, max_new_tokens: int,
             temperature: float = 0.0, rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             decode_kernel: Optional[bool] = None) -> GenerateResult:
    """Generate `max_new_tokens` continuations of `prompt` [B, P] int32.

    model — a trained CausalLM (training config; this fn builds the
    decode-mode twin). temperature=0 is greedy argmax; otherwise softmax
    sampling at the given temperature using `rng`, optionally filtered to
    the `top_k` most likely tokens and/or the `top_p` nucleus. `eos_id`
    freezes a row once it emits that token.

    decode_kernel — None inherits the model config; True routes the
    single-token decode steps through the Pallas decode-attention fast
    path (GQA-native, length-aware cache reads, fused int8 dequant);
    False pins the dense oracle. Prefill always runs dense.
    """
    cfg = model.config
    if not cfg.causal:
        raise ValueError("generate() needs a causal LM")
    B, P = prompt.shape
    if P + max_new_tokens > cfg.max_len:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_len={cfg.max_len} (the KV cache size)")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if temperature < 0.0:
        raise ValueError(f"temperature={temperature} must be >= 0 "
                         f"(0 = greedy)")
    if temperature != 0.0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if (top_k is not None or top_p is not None) and temperature == 0.0:
        raise ValueError("top_k/top_p filter the SAMPLING distribution; "
                         "set temperature > 0 (greedy ignores them)")
    if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
        raise ValueError(f"top_k={top_k} must be in [1, vocab_size="
                         f"{cfg.vocab_size}]")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p} must be in (0, 1]")
    dmodel = decode_model(model, decode_kernel)
    return _generate_jit(dmodel, params, prompt, int(max_new_tokens),
                         jnp.float32(temperature),
                         rng if rng is not None else jax.random.PRNGKey(0),
                         eos_id, temperature == 0.0, top_k,
                         top_p is not None,
                         jnp.float32(top_p if top_p is not None else 1.0))


__all__ = ["generate", "GenerateResult", "decode_model", "cast_params"]
