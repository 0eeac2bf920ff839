"""The serving engine's compiled programs, and what a served model owes
them.

`build_programs` is the one place where the engine meets the model: it
jits the four programs every tick dispatches (`init_cache`,
`prefill_paged`, `step_paged`, `verify_paged` — the names a device trace
shows them under) over a decode-mode model's `apply`. The host loop in
engine.py owns everything else: which rows a call carries, cursors, page
tables, retirement. How wide a prefill call is, or serving another model
family, is an edit here and nowhere in the loop.

A prefill call is as wide as its members, not as the slots:
`prefill_paged` takes the rows that HAVE a chunk and the slots they
belong to, `NARROW_ROWS` of them a call (`prefill_calls` cuts a tick's
members into such calls), so a prompt that replaces a retired request
runs one row's work.
"""
from __future__ import annotations

from typing import Callable, Iterator, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models.generate import cast_params


#: rows of a prefill call. One: every served model's chunk path takes a
#: single row (`mla_query_rows` groups only what passes a GiB,
#: `_ssd_chunk` and the delta rule's chunk form carry the rows as a batch
#: dim), and tests/test_tpu_compile.py compiles each cell's buckets at it.
#: A kernel whose row block forces more raises THIS, for every model:
#: `prefill_calls` pads a tick's last call with rows that scatter nowhere.
#: There is no `[slots, bucket]` form beside it: measured on the chip (PR
#: 48, PERF.md section 3), a first wave served a row a call builds as fast
#: as one served `slots` rows a call or faster in six cells of seven and
#: 8% slower in the seventh, and a second form is a second program to
#: trace, compile and keep for every bucket.
NARROW_ROWS = 1


class Programs(NamedTuple):
    init_cache: Callable      # (params) -> cache
    prefill: Callable         # (params, cache, rows, tokens, starts,
    #                            pages[, lengths]) -> cache
    step: Callable            # (params, cache, prev_tok, host_toks,
    #                            use_prev, positions, rng, temperature,
    #                            top_k, top_p, pages, mode)
    verify: Callable          # (params, cache, toks, positions, rng,
    #                            temperature, top_k, top_p, pages, mode)
    step_counters: Tuple[str, ...]   # the model's STEP_COUNTERS
    donates_cache: bool
    slot_state: Tuple[str, ...]      # the model's SLOT_STATE


def prefill_calls(slots: Sequence[int], tokens, starts, pages, lengths,
                  n_slots: int, max_len: int) -> Iterator[tuple]:
    """The operands of the prefill calls that carry a tick's members:
    `NARROW_ROWS` members a call from their `[m, ...]` operands (numpy),
    as `(rows, tokens, starts, pages[, lengths])` on the device, what
    `prefill_paged` takes after the weights and the cache (`lengths`
    None: a model without `SLOT_STATE`, whose program takes none). A last
    call short of members is padded with rows of zero tokens at `max_len`
    whose slot is `n_slots`: it does not exist, so the row's state
    scatters nowhere."""
    members = (np.asarray(slots, np.int32), tokens, starts, pages)
    if lengths is not None:
        members += (lengths,)
    for i in range(0, len(slots), NARROW_ROWS):
        part = [x[i:i + NARROW_ROWS] for x in members]
        short = NARROW_ROWS - len(part[0])
        if short:
            fill = (n_slots, 0, max_len, 0, 0)
            part = [np.concatenate([x, np.full((short,) + x.shape[1:], v,
                                               x.dtype)])
                    for x, v in zip(part, fill)]
        yield tuple(jnp.asarray(x) for x in part)


def cast_program(dtype):
    """The weights' one cast to the served dtype, its output resident
    across every step (decode is HBM-bound; see generate.cast_params for
    the barrier story)."""
    return jax.jit(lambda p: cast_params(p, dtype))


def build_programs(dmodel, cfg, tok_sharding, sample) -> Programs:
    """Jit the engine's programs over `dmodel` for `cfg` (an
    EngineConfig: `slots` rows, pages of `page_size`). `tok_sharding` is
    where the step's token output is pinned on a mesh (engine.py
    `_tok_sharding`); `sample` is the engine's `sample_slots`.

    The contract a served model meets (`CausalLM`, `LongcatLM`,
    `Phi4FlashLM` and `FalconH1LM` do):

    - `dmodel.config` has `max_len`, and was made by
      `generate.decode_model(model, kernel, page_size=, num_pages=)`: a
      flax module in decode mode whose cache is a page pool.
    - `dmodel.apply({"params": p, "cache": c}, tokens, positions=,
      pages=, with_head=False, mutable=[...])` takes `[B, S]` tokens at
      `[B, S]` absolute positions with the `[B, max_len // page_size]`
      page tables and returns the final hidden states `[B, S, E]` and
      the mutated collections: `cache` always; `counters` when asked. A
      position at `max_len` is junk: its write is dropped and nothing
      reads it. Applied WITHOUT a `cache` collection it creates one at
      the call's batch.
    - the cache's pooled leaves lead with `num_pages`
      (`ServingEngine.page_bytes()`, `transfer.PageTransfer`).
    - optional `SLOT_STATE`: names of cache leaves of another kind, which
      lead with `slots` and belong to a row whatever pages it holds: the
      ring of a layer that attends inside a window, the state of a
      recurrent layer (`models/phi4flash.py` has all three kinds of leaf
      in one model, each in layers of its own: ONE pooled leaf read by
      eight layers, eight rings, nine states; in `models/falcon_h1.py`
      EVERY layer has both kinds, its own pooled pages and beside them
      its recurrent state and conv tail, 4 MB a slot and layer).
      `ServingEngine.slot_state_bytes()` counts them. For a model that
      names any, the contract widens, and the engine keeps its side of
      it:
        * a row's real positions in a call are consecutive and come
          FIRST; after them, and in every row that is a pad of the call
          (prefill) or consumes no token (a decode step: rows
          mid-prefill, free rows), positions are `max_len`. Over such a
          position the model leaves the row's slot leaves EXACTLY as they
          were: a pad token never enters a recurrence, and a row
          mid-prefill survives the decode steps between its chunks.
          `prefill` takes the rows' real `lengths` for that, and hands
          the model the slot leaves of the call's rows alone (a model
          declares them at the call's batch, whatever it is);
        * no position is computed twice: chunk plans do not overlap
          (`scheduler.plan_chunks(overlap=False)`);
        * a call whose first position is 0 starts its row from zeros, so
          admission onto a used slot needs no reset program;
        * what cannot be kept right without snapshots of those leaves is
          refused at construction: the prefix cache, speculation's
          rewind, the handoff of pages between pools.
    - optional `PREFILL_CACHE_ONLY`: `apply(..., cache_only=True)` may
      stop after the last layer that keeps anything; prefill, which
      returns the cache alone, asks for it.
    - optional `head_logits(params, h)`: `[T, E]` hidden states to
      `[T, vocab]` logits, for an untied head. Without it the head is
      the tied table `params["wte"]["embedding"]`.
    - optional `STEP_COUNTERS`: names for what a decode call sows into
      `counters` (equal-shaped leaves, one entry a name), summed over
      the layers inside the step and fetched with its tokens.
    """
    S = cfg.slots
    nblk = dmodel.config.max_len // cfg.page_size

    def pin_tok(tok):
        if isinstance(tok_sharding, jax.sharding.NamedSharding):
            return lax.with_sharding_constraint(tok, tok_sharding)
        return tok

    head = getattr(dmodel, "head_logits", None)
    if head is None:
        from ..models.transformer import _head_matmul

        def head(params, h):
            return _head_matmul(h, params["wte"]["embedding"])
    names = tuple(getattr(dmodel, "STEP_COUNTERS", ()))
    slot_state = tuple(getattr(dmodel, "SLOT_STATE", ()))
    cache_only = ({"cache_only": True}
                  if getattr(dmodel, "PREFILL_CACHE_ONLY", False) else {})
    counted = ["cache", "counters"] if names else ["cache"]

    def step_counts(vars_):
        return sum(jax.tree.leaves(vars_["counters"])) if names else None

    def init_cache(params):
        # a zero-token step apply materializes the cache collection
        # at its serving shape; the hidden-state output is discarded
        z = jnp.zeros((S, 1), jnp.int32)
        _, vars_ = dmodel.apply({"params": params}, z, positions=z,
                                with_head=False, mutable=["cache"],
                                pages=jnp.zeros((S, nblk), jnp.int32))
        return vars_["cache"]

    def slot_leaves(fn, *caches):
        # `fn` over the leaves that lead with `slots`, the pooled leaves
        # (which have no row to pick) as the first cache has them
        return jax.tree_util.tree_map_with_path(
            lambda path, x, *more: fn(x, *more) if getattr(
                path[-1], "key", None) in slot_state else x, *caches)

    def prefill_paged(params, cache, rows, tokens, starts, pages,
                      lengths=None):
        # A chunk over the page pool for the [R, C] rows that HAVE one,
        # `rows` [R] naming the slot each belongs to (a prompt that
        # replaces a retired request is ONE row, not `slots`). Writes are
        # routed through the rows' page tables — the pool is shared, so
        # it goes in as it is; the SLOT_STATE leaves go in as the rows of
        # the slots named, and come back into those rows. A position at
        # max_len is past the logical cache: the page scatter drops its
        # write (transformer.py, longcat.py), and `lengths` (a model with
        # SLOT_STATE) puts a row's pads there. A pad ROW sits there whole
        # and names slot S, which does not exist: it reads the last
        # slot's leaves, leaves them as they were and is dropped by the
        # scatter.
        positions = starts[:, None] + jnp.arange(tokens.shape[1])[None]
        if lengths is not None:
            positions = jnp.where(
                jnp.arange(tokens.shape[1])[None] < lengths[:, None],
                positions, dmodel.config.max_len)
        sub = slot_leaves(lambda x: jnp.take(x, rows, axis=0, mode="clip"),
                          cache)
        _, vars_ = dmodel.apply(
            {"params": params, "cache": sub}, tokens,
            positions=positions, with_head=False, mutable=["cache"],
            pages=pages, **cache_only)
        return slot_leaves(
            lambda new, old: old.at[rows].set(new, mode="drop"),
            vars_["cache"], cache)

    def step_paged(params, cache, prev_tok, host_toks, use_prev,
                   positions, rng, temperature, top_k, top_p, pages,
                   mode):
        # ONE token for ALL slots: [S] tokens at [S] cursors. The
        # input token per row comes from the DEVICE-side chain
        # (prev_tok = last step's output, rows with use_prev) or from
        # the host (bonus token after prefill) — the chain is what
        # lets the host dispatch step N+1 without reading step N.
        # The per-slot page tables are one [S, nblk] operand — table
        # churn (admit/retire) never recompiles, exactly like cursor
        # churn
        tokens = jnp.where(use_prev, prev_tok, host_toks)
        h, vars_ = dmodel.apply(
            {"params": params, "cache": cache}, tokens[:, None],
            positions=positions[:, None], with_head=False,
            mutable=counted, pages=pages)
        # the scope a device trace splits the step by, whatever the model
        with jax.named_scope("head"):
            logits = head(params, h[:, 0])
            tok, logp = sample(logits, rng, temperature, top_k, top_p,
                               mode=mode)
        return vars_["cache"], pin_tok(tok), logp, step_counts(vars_)

    def verify_paged(params, cache, toks, positions, rng, temperature,
                     top_k, top_p, pages, mode):
        # ONE batched pass over [S, W] proposed tokens at explicit
        # per-position cursors — a chunked-prefill-shaped step with
        # right-aligned ragged rows. Row layout (host-built): column
        # 0 = the row's real next input, columns 1..k = drafts,
        # padded tail positions = max_len (past the logical cache, so
        # their K/V writes DROP). K/V for every column is written
        # BEFORE attention reads it, and each query position attends
        # only <= itself, so a row's rejected tail never contaminates
        # an accepted position; the cursor rewind makes it invisible
        # to every later step too.
        h, vars_ = dmodel.apply(
            {"params": params, "cache": cache}, toks,
            positions=positions, with_head=False, mutable=["cache"],
            pages=pages)
        # [S, W] hidden states → per-position target tokens +
        # logprobs. Column 0 is the plain decode step's sample (same
        # sampler, so sampling rows in a mixed batch still draw
        # correctly); columns 1.. are the greedy targets the drafts
        # are checked against — argmax in float32, bitwise the same
        # reduction the sampler runs for a temperature-0 row, which is
        # the token-exactness hinge.
        Sv, W, E = h.shape
        logits = head(params, h.reshape(Sv * W, E))
        logits = logits.reshape(Sv, W, -1)
        tok0, lp0 = sample(logits[:, 0], rng, temperature, top_k, top_p,
                           mode=mode)
        f32 = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(f32)
        greedy = jnp.argmax(f32, axis=-1)
        glp = jnp.take_along_axis(logp, greedy[..., None],
                                  axis=-1)[..., 0]
        return (vars_["cache"], greedy.at[:, 0].set(tok0),
                glp.at[:, 0].set(lp0))

    # cache buffers are donated — the engine holds the only live
    # reference, and the page pool is the biggest allocation here;
    # donation keeps it single-buffered — and the pool's row-major form
    # is the one every program reads and writes, so it is aliased, not
    # copied. (CPU has no donation support and would warn per program.)
    # prev_tok is NOT donated: the pending sync still reads its buffer
    # after the next step consumed it.
    donate = (1,) if jax.default_backend() in ("tpu", "gpu") else ()
    return Programs(
        init_cache=jax.jit(init_cache),
        prefill=jax.jit(prefill_paged, donate_argnums=donate),
        step=jax.jit(step_paged, donate_argnums=donate,
                     static_argnums=(11,)),
        verify=jax.jit(verify_paged, donate_argnums=donate,
                       static_argnums=(9,)),
        step_counters=names, donates_cache=bool(donate),
        slot_state=slot_state)


__all__ = ["NARROW_ROWS", "Programs", "build_programs", "cast_program",
           "prefill_calls"]
