"""Continuous-batching serving engine over the decode fast path.

`generate()` (models/generate.py) is the fixed-batch oracle: equal-length
prompts, lockstep to max_new_tokens, EOS rows burning full decode compute,
no admission until the whole batch drains. This engine serves the same
model the way a frontend needs it served:

- **Slots over one page pool.** The KV cache is ONE pool of fixed-size
  pages per layer ([num_pages, page_size, KV * 2D]: a row a position,
  each head's K and V side by side — transformer.py decode_page_size; a
  latent row for longcat.py) and each of the SLOTS decode rows carries a
  page TABLE. Every row is an independent request at its own depth,
  driven by per-row cursors and tables the host owns. Finishing a
  request frees its row and its pages immediately; the next queued
  request moves in. Nothing about admission/retirement touches compiled
  code. Slot count is decoupled from max_len, so the same cache bytes
  serve strictly more concurrent requests whenever typical spans run
  short of the worst case; `page_size == max_len` is one page a slot,
  the contiguous layout, through the same path. Admission reserves a
  request's whole worst-case page span up front (slots.PageAllocator;
  scheduler packing skips past a head that doesn't fit), so decode
  never allocates mid-flight.
- **The compiled programs live in programs.py**, with the contract a
  served model meets: `init_cache`, `prefill_paged`, `step_paged`,
  `verify_paged`. This module is the host loop around them.
- **One compiled decode step.** Every step advances ALL slots one token —
  cursors, page tables, input tokens, and per-slot sampling params
  (temperature / top-k / top-p, the traced-per-row generalization of
  generate's `_sample`) are plain array operands. Compiled once, reused
  for the lifetime of the engine (asserted via `compile_counts` in tests).
- **Chunked, batched prefill.** Prompts prefill in fixed windows
  bucketed to ≤3 compiled shapes (scheduler.plan_chunks), one call per
  engine loop iteration, interleaved with decode steps — a long prompt
  cannot stall in-flight decodes, and ragged prompt lengths stop forcing
  per-shape recompiles. Every waiting slot whose next chunk shares the
  bucket advances in the same tick, each in a call of its own row
  (programs.NARROW_ROWS, programs.prefill_calls): the prompt that
  replaces a retired request runs one row's work, not `slots` rows' with
  all but one of them empty, and one fixed-shape [1, C] program per
  bucket serves a first wave as well.
- **Prefix caching** (`EngineConfig.prefix_cache`). Fully-prefilled
  PROMPT pages are published into a refcounted prefix cache (chained
  keys — exact token equality back to position 0), so a request sharing
  a system prompt pins the existing pages and starts prefill at the
  first divergent page; at worst-case TTFT the whole prompt is already
  resident and the request goes straight to decode. Retired requests'
  published pages linger in an evictable LRU until the free list runs
  dry.
- **Double-buffered decode.** The step's input tokens chain ON DEVICE:
  a decoding row's next input is the previous step's output for its slot
  (`jnp.where(use_prev, prev_tok, host_toks)`), so the host never has to
  read a token to dispatch the next step. `run()` dispatches step N+1
  BEFORE syncing step N's tokens — host-side scheduling, stream
  callbacks, EOS/length retirement, and prefill planning all hide under
  the in-flight device step. Length-finished rows free at DISPATCH time
  (exhaustion is deterministic host state, no token read needed), so
  admission runs at full occupancy; only EOS — which the host can't see
  until the sync — is one step delayed, costing that request a single
  discarded junk step, and a freed row's junk write is overwritten by
  its next occupant exactly like a free slot's (slots.py).
  `EngineConfig.async_decode=False` drains each step before the next
  dispatch — same compiled program (compile_counts is mode-blind),
  token-identical at temperature 0, the A/B baseline the serving bench
  measures against. `EngineConfig.async_depth` keeps more than one step
  dispatched behind the sync (step N+depth goes out before step N's
  tokens come in): the same tokens, `depth` steps of work queued on the
  device against a host that stands still, an EOS seen `depth` steps
  late.

- **Speculative decoding** (`EngineConfig.speculative`). Decode is one
  memory-bound HBM sweep per token; speculation turns k sequential
  sweeps into ONE batched verify step. A host-side drafter proposes up
  to `draft_k` continuation tokens per row — "ngram" self-drafting
  matches the request's own prompt+output history (no second model),
  "draft" plugs in any callable (a small draft model) — and the verify
  program scores all proposals plus the bonus token in a single pass:
  the same right-aligned ragged-row shape as a chunked-prefill window,
  bucketed to ≤2 compiled widths. Greedy acceptance keeps the longest
  prefix where draft == previous position's argmax, then emits the
  model's own next token — so speculation changes WHEN tokens are
  computed, never WHICH (token-exact vs the plain engine at temperature
  0, pinned in tests/test_spec_decode.py). Rejection is a cursor
  rewind (slots.SlotManager.rewind): written-but-rejected K/V is dead
  weight the next write overwrites — never a copy — and prefix-cache
  publishing only ever covers prompt pages, so published boundaries
  advance on accepted tokens by construction. The decode pool of a
  DisaggEngine verifies the same way; drafting is host state, so the
  split gets speculation for free.

Parity: at temperature 0 a single request produces token-for-token the
same output as `generate()` — tests/test_serve.py and
tests/test_paged_kv.py pin this across the dense and Pallas
decode-kernel paths, async and sync.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import decode_model
from ..telemetry import span
from ..telemetry import events as ev
from ..telemetry import spans
from .programs import build_programs, cast_program, prefill_calls
from .scheduler import Request, RequestState, Scheduler
from .slots import PageAllocator, SlotManager
from .transfer import PageTransfer


@dataclasses.dataclass
class EngineConfig:
    """Serving knobs. `slots` is the decode batch (rows in the cache);
    `chunk_buckets` are the ≤3 compiled prefill widths — cover your
    common prompt lengths with the fewest windows (a prompt of length P
    prefills ceil((P-1)/largest) windows, ragged tail right-aligned).
    `decode_kernel` None inherits the model config. `async_decode`
    dispatches decode step N+1 before syncing step N's tokens (the
    double-buffered loop — see the module docstring); False drains every
    step before the next dispatch, through the same compiled program.

    The cache is a page pool: `page_size` tokens per page (64 default —
    big enough that the page-table indirection amortizes, small enough
    that a short request doesn't strand half a row; must divide max_len,
    and the Pallas path wants a multiple of 32 so every cache dtype
    tiles; `page_size == max_len` is one page a slot, the contiguous
    layout), `num_pages` physical pages plus the reserved trash page
    (None gives every slot its worst case: slots * max_len // page_size,
    + 1 — capacity wins come from a smaller pool and requests that
    DON'T use their worst case). `paged` has one legal value, True (the
    benchmark's harness still passes it; False is refused).
    `prefix_cache` publishes fully-prefilled prompt pages for
    cross-request sharing; False keeps pure paging. `admit_lookahead`
    bounds the packing scan past a head-of-queue that doesn't fit.

    `request_timeout` (seconds, None = off) stamps a deadline on every
    request at ADMISSION (RequestState.deadline); the run loop's sweep
    retires a past-deadline request with finish_reason "timeout" through
    the normal retire path — slot and KV pages reclaimed like any EOS,
    plus a request_timeout event. This is the engine-side half of the
    serving progress lease: one wedged request cannot pin a slot (and
    its pages) forever, so the retired-request/token frontier the
    controller watches keeps moving unless the whole engine is stuck.
    In the disaggregated facade each pool stamps its own window (prefill
    admission and decode install each start a fresh deadline).

    `speculative` (None = off) enables multi-token verify: "ngram"
    self-drafts via prompt lookup against each request's own history
    (`spec_ngram` caps the match length), "draft" uses the `drafter`
    callable handed to the engine (a small draft model, or anything
    else — correctness never depends on draft quality). `draft_k` caps
    proposed tokens per row per verify step; the verify program runs at
    ≤2 bucketed widths from {2, draft_k+1}. Greedy rows are token-exact
    vs the plain engine; sampling rows never speculate (their next
    token is a draw, not an argmax, so lookahead has nothing to verify
    against) and run plain decode in the same batch.

    `async_depth` (with `async_decode`; 1 = the double-buffered loop) is
    how many decode steps stay dispatched and unfetched behind the one
    being synced: step N+depth is dispatched before step N's tokens are
    fetched. Every step's inputs are on the device already (the token
    chain, the cursors the host advances at dispatch), so the tokens are
    the same at any depth; what it buys is work queued on the device for
    `depth` steps' time, through which a host that stands still (a paused
    VM, a long collection) starves nothing; what it costs is that a token
    reaches its client, and an EOS frees its row, `depth - 1` steps
    later. Speculation drains the queue before every verify step."""
    slots: int = 8
    chunk_buckets: Tuple[int, ...] = (32, 128, 512)
    decode_kernel: Optional[bool] = None
    rng_seed: int = 0
    async_decode: bool = True
    paged: bool = True
    page_size: int = 64
    num_pages: Optional[int] = None
    prefix_cache: bool = True
    admit_lookahead: int = 8
    request_timeout: Optional[float] = None
    speculative: Optional[str] = None     # None | "ngram" | "draft"
    draft_k: int = 4
    spec_ngram: int = 3
    # the engine takes the tree it is given as its own: a tree whose
    # floating leaves are all in the served type already is used where it
    # lies, not copied by the cast — weights that fill most of a chip
    # cannot be on it twice. The caller gives the tree up (the engine may
    # delete its buffers); anything not yet in the served type is cast as
    # always.
    own_params: bool = False
    async_depth: int = 1


@dataclasses.dataclass
class RequestResult:
    id: int
    tokens: List[int]                 # new tokens only (no prompt)
    logprobs: List[float]
    finish_reason: str                # "eos" | "length" | "timeout"
    #                                   ("shed" at the router front door:
    #                                   rejected before any replica)
    ttft: float                       # arrival → first new token, seconds
    #                                   (-1.0 when the request timed out
    #                                   before its first token)
    token_times: List[float]          # absolute (run-relative) per token
    cached_tokens: int = 0            # prompt span served from the prefix
    #                                   cache (0 = cold)
    admitted_at: float = 0.0          # run-relative admission time —
    #                                   token_times[0] - admitted_at is
    #                                   TTFT with queueing excluded (the
    #                                   prefix-cache comparison the bench
    #                                   makes: a hit skips prefill, not
    #                                   the queue)


#: bounded-mode candidate pool: exact for any request with an active
#: top_k <= this (the nucleus then lives inside the kept top-k set, so
#: the tail beyond the pool carries zero probability mass by
#: construction) — and a lax.top_k of 128 is far cheaper per step than
#: the full-vocab sort the unbounded filters need
SAMPLE_POOL = 128


def sample_slots(logits, rng, temperature, top_k, top_p,
                 mode: str = "full"):
    """[B, V] logits + per-row [B] sampling params (ALL traced) →
    ([B] token, [B] logprob of the choice, from the UNfiltered
    distribution — same reporting convention as generate._sample).

    generate's `_sample` makes greedy/top_k/use_top_p STATIC — right for
    a lockstep batch sharing one config, wrong here where every slot
    carries its own params and the step must stay one compiled program.
    So: temperature==0 rows select argmax via a where; top_k becomes a
    traced threshold (k-th largest off a descending-sorted candidate
    pool); top_p==1 rows keep the whole nucleus. The filter arithmetic
    mirrors _sample, so a slot at (t, k, p) samples from the same
    distribution a generate() batch at static (t, k, p) would.

    `mode` is the one STATIC knob — three compiled variants, chosen by
    the host which knows the active rows exactly:
      "greedy"  — every active row is temperature 0: pure argmax, no
                  filter work at all (the common serving case);
      "bounded" — every sampling row has 1 <= top_k <= SAMPLE_POOL: the
                  candidate pool is lax.top_k(SAMPLE_POOL), EXACT for
                  both filters (post-top-k, all probability mass lives
                  in the pool) at a fraction of the full sort;
      "full"    — anything else (top_k disabled or huge): the pool is
                  the whole vocab, one full descending sort."""
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    logp = jax.nn.log_softmax(logits)
    greedy_tok = jnp.argmax(logits, axis=-1)
    if mode == "greedy":
        return greedy_tok, jnp.take_along_axis(
            logp, greedy_tok[:, None], axis=-1)[:, 0]
    W = V if mode == "full" else min(SAMPLE_POOL, V)
    scaled = logp / jnp.maximum(temperature, 1e-6)[:, None]
    # ONE top-k/sort serves both filters: the top-k threshold reads
    # straight off the pool, and because softmax is permutation-
    # equivariant, masking in the SORTED domain gives the nucleus its
    # sorted post-top-k probabilities without a second sort.
    pool = jax.lax.top_k(scaled, W)[0]            # [B, W] descending
    # top-k: mask below the k-th largest; k<=0 disables (keeps the pool)
    k = jnp.where(top_k <= 0, W, jnp.clip(top_k, 1, W))
    kth = jnp.take_along_axis(pool, (k - 1)[:, None], axis=-1)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    cols = jnp.arange(W)[None, :]
    pool_masked = jnp.where(cols < k[:, None], pool, -jnp.inf)
    sorted_p = jax.nn.softmax(pool_masked)
    # nucleus: smallest prefix of the sorted distribution with cumulative
    # probability >= top_p (kept set always includes the argmax). The
    # threshold is applied in the LOGIT domain — pool entries are bitwise
    # copies of `scaled` entries, so the comparison is exact, whereas a
    # probability-domain cutoff recomputes a softmax whose 1-ulp
    # normalizer drift can strand the boundary token (softmax is
    # monotone, so the kept set is identical)
    cum = jnp.cumsum(sorted_p, axis=-1)
    cutoff_idx = jnp.minimum(jnp.sum(cum < top_p[:, None], axis=-1), W - 1)
    cutoff = jnp.take_along_axis(pool_masked, cutoff_idx[:, None], axis=-1)
    scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    sampled = jax.random.categorical(rng, scaled)
    tok = jnp.where(temperature <= 0.0, greedy_tok, sampled)
    return tok, jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]


def propose_ngram(history: Sequence[int], k: int,
                  max_n: int = 3) -> List[int]:
    """Prompt-lookup self-drafting: propose up to `k` tokens by matching
    the longest suffix n-gram (n = max_n down to 1) of `history` against
    its most recent EARLIER occurrence and copying what followed it.
    Pure host work, no second model — repetitive continuations (code,
    lists, quoted spans, the cyclic output of a greedy decode) hit
    constantly; novel text just returns [] and the engine falls back to
    plain decode. Wrong proposals cost a verify column, never a token
    (greedy acceptance discards them)."""
    L = len(history)
    out: List[int] = []
    if k < 1 or L < 2:
        return out
    for n in range(min(max_n, L - 1), 0, -1):
        pat = list(history[L - n:])
        # scan right-to-left: recency wins (the latest occurrence is the
        # best predictor of what the model is currently repeating)
        for s in range(L - n - 1, -1, -1):
            if list(history[s:s + n]) == pat:
                out = [int(t) for t in history[s + n:s + n + k]]
                break
        if out:
            break
    return out


def _sample_mode(consumers) -> str:
    """The cheapest step variant the rows of this step allow (the host
    knows the sampling params exactly; see sample_slots)."""
    sampling = [st.req for st in consumers if st.req.temperature > 0.0]
    if not sampling:
        return "greedy"
    if all(1 <= r.top_k <= SAMPLE_POOL for r in sampling):
        return "bounded"
    return "full"


class ServingEngine:
    """Continuous-batching inference over a trained CausalLM.

    Usage:
        engine = ServingEngine(model, params, EngineConfig(slots=8))
        results = engine.run([Request(0, prompt_ids, max_new_tokens=64)])
        results[0].tokens       # streamed order; or pass on_token=

    The engine is single-threaded and synchronous: `run` drives the
    admit → prefill-chunk → decode-step loop to completion and returns
    per-request results. Submit-with-future-`arrival` replays a trace.
    """

    #: page-reservation mode handed to the Scheduler — the
    #: disaggregated PrefillEngine overrides this to "prompt" (its pool
    #: never holds decode tokens, so it only reserves the prompt span)
    RESERVE = "full"

    #: the hop a request's trace enters when its prompt finishes
    #: prefilling — decode here; the disaggregated PrefillEngine hands
    #: off instead (telemetry/trace.py taxonomy)
    POST_PREFILL_HOP = "serve.decode"

    #: whether a request's pages leave or enter this pool through
    #: `transfer.PageTransfer` (the two halves of a disaggregated pair)
    HANDS_OFF_PAGES = False

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 telemetry=None, events=None, drafter=None, tracer=None):
        """telemetry: a telemetry.ServeTelemetry — live TTFT/TPOT/step
        histograms and queue/occupancy gauges (today these exist only as
        a post-hoc trace reduction in serve_benchmark); events: a
        telemetry.EventLog receiving slot_admit/slot_retire records.
        Both optional and None-cost when absent. drafter: the
        speculative="draft" proposal hook — callable(history, k) -> up
        to k candidate tokens (history = prompt + generated so far);
        correctness never depends on what it returns. tracer: a
        telemetry.Tracer — per-request span trees (admission / prefill
        / decode hops on the session clock, batch-level decode/verify
        spans under a per-session root). All tracing is host-side
        bookkeeping: no device operand, no rng fold, no compiled
        program changes — greedy tokens and compile pins are bitwise
        identical with tracing on or off."""
        with span("serve.engine_init"):
            cfg = config or EngineConfig()
            mcfg = model.config
            if not mcfg.causal:
                raise ValueError("serving needs a causal LM")
            if not cfg.paged:
                raise ValueError(
                    "EngineConfig(paged=False): the page pool is the only "
                    "cache regime. The contiguous-slot layout is a pool of "
                    "one page a slot: page_size=max_len")
            for b in cfg.chunk_buckets:
                if b > mcfg.max_len:
                    raise ValueError(f"chunk bucket {b} exceeds "
                                     f"max_len={mcfg.max_len}")
            if cfg.async_depth < 1:
                raise ValueError(f"async_depth={cfg.async_depth}: at least "
                                 f"one step stays dispatched (async_decode="
                                 f"False fetches every step at once)")
            if cfg.speculative not in (None, "ngram", "draft"):
                raise ValueError(f"speculative={cfg.speculative!r}: expected "
                                 f"None, 'ngram' or 'draft'")
            if cfg.speculative is not None and cfg.draft_k < 1:
                raise ValueError(f"draft_k={cfg.draft_k}: speculation needs "
                                 f"at least one proposed token")
            if cfg.speculative == "draft" and drafter is None:
                raise ValueError("speculative='draft' needs a drafter "
                                 "callable (history, k) -> tokens")
            self._drafter = drafter
            # ≤2 compiled verify widths: a narrow one for single-token
            # proposals plus the full draft_k+1 (compile_counts pins this)
            self._verify_buckets = tuple(sorted({min(2, cfg.draft_k + 1),
                                                 cfg.draft_k + 1}))
            self.config = cfg
            self.model_config = mcfg
            ps = cfg.page_size
            if ps < 1 or mcfg.max_len % ps:
                raise ValueError(f"page_size={ps} must be >= 1 and divide "
                                 f"max_len={mcfg.max_len}")
            NP = cfg.num_pages
            if NP is None:
                # every slot's worst case, slots x max_len positions,
                # plus the trash page. In positions, not bytes: a page's
                # bytes follow the kind of cache the model keeps (K and V
                # a head, or one latent row — `page_bytes()` counts them
                # from the cache itself)
                NP = cfg.slots * (mcfg.max_len // ps) + 1
            self.page_allocator = PageAllocator(NP, ps)
            self._nblk = mcfg.max_len // ps
            self.dmodel = decode_model(model, cfg.decode_kernel,
                                       page_size=ps, num_pages=NP)
            self._base_rng = jax.random.PRNGKey(cfg.rng_seed)
            self._steps_dispatched = 0
            self.telemetry = telemetry
            self.events = events
            self.tracer = tracer
            # session clock for trace hops — set while a session (or the
            # disaggregated run loop) is live; tracing is inert without it
            self._trace_now: Optional[Callable[[], float]] = None
            self._session_span = None
            if telemetry is not None:
                telemetry.slots.set(cfg.slots)
                telemetry.pages_total.set(self.page_allocator.usable)

            dt = self.dmodel.config.dtype
            S = cfg.slots

            # params cast once, device-resident across every step
            self._cast = cast_program(dt)
            # not synced: host-born weights may copy to the device while the
            # cache program below is traced; serve.init_cache waits for both
            with span("serve.cast_params"):
                served = all(
                    x.dtype == dt for x in jax.tree.leaves(params)
                    if jnp.issubdtype(x.dtype, jnp.floating))
                self.params = (params if cfg.own_params and served
                               else self._cast(params))
            # where the persistent host-born operand (_prev_tok) must live so
            # that the FIRST decode step keys the same compiled program as
            # every later one, whose prev_tok is the previous step's output:
            #   params on a mesh (benchmarks' shard_init) — step outputs
            #     carry that mesh in their abstract type (jax types name it),
            #     so the chain starts replicated on the same mesh;
            #   params committed to one device (a disaggregated pool) — every
            #     jit output is committed there too, so the chain starts there;
            #   params uncommitted (the colocated default) — None, jit places
            #     everything on the default device.
            # Get this wrong and the step program compiles twice. On a mesh
            # the step also PINS its token output there (programs.py
            # pin_tok): left to GSPMD, a kernel that splits rows over dp
            # hands back a dp-sharded token vector, and the second step
            # would again see an input unlike the first's.
            leaves = jax.tree.leaves(self.params)
            self._tok_sharding = None
            if leaves and isinstance(leaves[0].sharding,
                                     jax.sharding.NamedSharding):
                self._tok_sharding = jax.sharding.NamedSharding(
                    leaves[0].sharding.mesh, jax.sharding.PartitionSpec())
            elif leaves and getattr(leaves[0], "committed", False):
                devs = leaves[0].devices()
                if len(devs) == 1:
                    self._tok_sharding = next(iter(devs))

            # the compiled programs (programs.py holds the model's
            # contract); `sample_slots` is read off this module here, so
            # whoever replaces it before construction is served
            progs = build_programs(self.dmodel, cfg, self._tok_sharding,
                                   sample_slots)
            self._init_cache = progs.init_cache
            self._prefill = progs.prefill
            self._step = progs.step
            self._verify = progs.verify
            self._step_counters = progs.step_counters
            self.donates_cache = progs.donates_cache
            # cache leaves the model keeps a slot, not a page (programs.py)
            self._slot_state = progs.slot_state
            self._refuse_for_slot_state(cfg)

            self.scheduler = self._new_scheduler()
            self.slots = SlotManager(S)
            # closes on a device sync, so the engine's set-up span holds the
            # weights' copy and both programs' time; tick() never blocks for
            # a span
            with span("serve.init_cache"):
                self.cache = jax.block_until_ready(
                    self._init_cache(self.params))
            if telemetry is not None:
                telemetry.slot_state_bytes.set(self.slot_state_bytes())
            self._prev_tok = self._zeros_tok(S)
            # (rows, widest bucket) of the prefill calls dispatched since the
            # last decode dispatch: the device runs them BEFORE that step, so
            # the step's span carries them and its sync is the one that waits
            self._prefill_queued = (0, 0)
            self._session = None   # open steppable session (start()/finish())
            # the open spans of each request that came through submit():
            # [its root, the phase it is in] (telemetry/spans.py, "a request")
            self._request_spans: Dict[int, list] = {}
            # push-based load reporting (set_heartbeat): (hook, interval)
            self._heartbeat = None
            self._heartbeat_last: Optional[float] = None
            # high-water marks over a run(): the capacity story in one pair
            # of numbers (a pool sustains more slots than slots x max_len
            # positions would exactly when pages_in_use_peak stays under the
            # pool while occupancy_peak exceeds that layout's slot cap)
            self.occupancy_peak = 0
            self.pages_in_use_peak = 0
            # speculation run counters (host truth the bench reads;
            # spec_stats() derives acceptance_rate / effective tokens/step)
            self.spec_proposed = 0       # draft tokens sent to verify
            self.spec_accepted = 0       # draft tokens that matched argmax
            self.spec_steps = 0          # verify steps run
            self.spec_rows = 0           # consumer rows across verify steps
            self.spec_tokens = 0         # tokens emitted by verify steps

        # -- bookkeeping ------------------------------------------------------

    def _refuse_for_slot_state(self, cfg: "EngineConfig") -> None:
        """A model that keeps state a slot (programs.py, SLOT_STATE) is
        served without what would need snapshots of that state: each
        refusal names the piece that is missing."""
        if not self._slot_state:
            return
        kept = (f"{type(self.dmodel).__name__} keeps state a slot "
                f"({', '.join(self._slot_state)}), not only pages")
        if cfg.prefix_cache:
            raise ValueError(
                f"EngineConfig(prefix_cache=True): {kept}. A prefix hit "
                f"would need a snapshot of that state at the page "
                f"boundary the hit ends on, and none is taken: pass "
                f"prefix_cache=False")
        if cfg.speculative is not None:
            raise ValueError(
                f"EngineConfig(speculative={cfg.speculative!r}): {kept}. "
                f"A rejected draft rewinds the cursor, and a recurrent "
                f"state or a window's ring that has consumed the draft "
                f"cannot be rewound without a snapshot: there is none")
        if self.HANDS_OFF_PAGES:
            raise ValueError(
                f"{type(self).__name__}: {kept}. PageTransfer moves "
                f"pages only; handing a request to another pool needs a "
                f"transfer of its slot's state, which does not exist")

    def _new_scheduler(self) -> Scheduler:
        return Scheduler(self.config.chunk_buckets, self.model_config.max_len,
                         admit_lookahead=self.config.admit_lookahead,
                         reserve=self.RESERVE,
                         overlap_chunks=not self._slot_state)

    def _zeros_tok(self, n: int):
        """The device-side token chain's initial value, placed where the
        step's own outputs land (see __init__) so step 1 and step N hit
        the same compiled program."""
        z = jnp.zeros((n,), jnp.int32)
        if self._tok_sharding is None:
            return z
        return jax.device_put(z, self._tok_sharding)

    def reset(self) -> None:
        """Clear all serving state (queue, slots, cache contents, page
        allocator and prefix cache) but keep every compiled program —
        what the bench calls between the warmup trace and the measured
        trace. A reset engine replays a trace with identical tokens AND
        identical compile counts."""
        self.scheduler = self._new_scheduler()
        self.slots = SlotManager(self.config.slots)
        if os.environ.get("TPU_DEBUG_PAGES") == "1":
            # O(num_pages) invariant audit of the state the trace left
            # behind — debug builds only (the test suite sets
            # TPU_DEBUG_PAGES=1), so the bench's warmup→measure reset
            # stays O(slots)
            self.page_allocator.check()
        # rewind refcounts, free list, AND the prefix cache — cached
        # pages index into a cache whose contents init_cache is about
        # to zero, so carrying them over would serve stale K/V
        self.page_allocator.reset()
        self.cache = self._init_cache(self.params)
        self._prev_tok = self._zeros_tok(self.config.slots)
        self._prefill_queued = (0, 0)
        # the per-step rng folds in this counter — rewind it so a reset
        # engine replays a trace with identical draws
        self._steps_dispatched = 0
        self._session: Optional[Dict] = None
        self._session_span = None
        self._trace_now = None
        self._request_spans = {}
        self.occupancy_peak = 0
        self.pages_in_use_peak = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        self.spec_rows = 0
        self.spec_tokens = 0

    def compile_counts(self) -> Dict[str, int]:
        """Executable-cache sizes of the engine's jitted programs —
        the no-recompile contract is `step <= 3` (at most one program
        per sample_slots mode; a pure-greedy trace compiles 1),
        `prefill <= len(chunk_buckets)`, and `verify <=
        len(_verify_buckets)` per mode (a greedy speculative trace
        compiles at most 2) no matter what trace ran."""
        return {
            "step": self._step._cache_size(),
            "prefill": self._prefill._cache_size(),
            "verify": self._verify._cache_size(),
            "init_cache": self._init_cache._cache_size(),
            "cast": self._cast._cache_size(),
        }

    def decode_step_scopes(self) -> Dict[str, str]:
        """{instruction of the compiled greedy decode step: the
        `jax.named_scope` path it was traced under}, from the optimised
        HLO. A device trace names an operation by its instruction
        (`fusion.412`), not by the scope it came from; with this map a
        reader can add up a step's device time by scope (`mla.`, `moe.`).
        Lowers and compiles the step again (a load, where the persistent
        cache has it): for after a measured window, never inside one."""
        from ..telemetry.hlo_names import instruction_scopes
        S = self.config.slots
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)     # noqa: E731
        f32 = lambda *shape: jnp.zeros(shape, jnp.float32)   # noqa: E731
        lowered = self._step.lower(
            self.params, self.cache, self._prev_tok, i32(S),
            jnp.zeros((S,), bool), i32(S), self._base_rng, f32(S), i32(S),
            f32(S), i32(S, self._nblk), "greedy")
        return instruction_scopes(lowered.compile().as_text())

    def page_bytes(self) -> int:
        """Bytes one page holds over every layer, counted from the cache
        the model made: K and V a head (and int8 scales) for a per-head
        cache, one latent row a position for a latent one."""
        NP = self.page_allocator.num_pages
        return sum(x.nbytes for x in jax.tree.leaves(self.cache)
                   if x.shape[0] == NP) // NP

    def slot_state_bytes(self) -> int:
        """Bytes ONE slot holds beside its pages, whatever its context:
        the leaves the model names in `SLOT_STATE` (a window layer's
        ring, a recurrent layer's state), counted from the cache itself.
        0 for a model whose cache is pages alone."""
        flat = jax.tree_util.tree_flatten_with_path(self.cache)[0]
        return sum(x.nbytes for path, x in flat
                   if getattr(path[-1], "key", None) in self._slot_state
                   ) // self.config.slots

    def spec_stats(self) -> Dict[str, float]:
        """Speculation accounting since construction/reset().
        effective_tokens_per_step is tokens emitted PER ROW per verify
        step (so batch width cancels out): 1.0 means drafts never
        helped (each row's bonus token only — exactly plain decode in
        step count), > 1.0 is sequential HBM sweeps actually saved."""
        return {
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "verify_steps": self.spec_steps,
            "spec_tokens": self.spec_tokens,
            "acceptance_rate": (self.spec_accepted / self.spec_proposed
                                if self.spec_proposed else 0.0),
            "effective_tokens_per_step": (self.spec_tokens / self.spec_rows
                                          if self.spec_rows else 0.0),
        }

    # -- tracing ----------------------------------------------------------

    def _trace(self, rid: int):
        """The open RequestTrace for request `rid`, or None — tracer
        absent, id sampled out, or no session clock to stamp hops with.
        One dict probe on the traced path, zero work otherwise."""
        if self.tracer is None or self._trace_now is None:
            return None
        return self.tracer.active(rid)

    def trace_abandon(self, now: float) -> None:
        """This engine is being killed/dropped mid-session (router
        failover): close its per-session trace root so already-recorded
        batch spans keep a parent — the zero-orphans invariant. The
        router abandons each in-flight REQUEST trace itself; those
        roots stay open for the replay on a surviving replica."""
        if self._session_span is not None:
            self._session_span.abandon(now)
            self._session_span = None
        self._trace_now = None

    def _request_phase(self, rid: int, name: Optional[str], **attrs):
        """Close request `rid`'s open phase and, at the same instant, open
        `name` with `attrs` (None: close its root instead: the request
        is over). Returns the span that closed, for its last attributes;
        None for a request that did not come through `submit()`."""
        entry = self._request_spans.get(rid)
        if entry is None:
            return None
        root, phase = entry
        # (a session clock that is not the wall's, a test's or a replay's,
        # may have put an arrival, and so the start, ahead of now)
        now_ns = max(time.perf_counter_ns(), phase.start_ns)
        phase.end(now_ns)
        if name is None:
            del self._request_spans[rid]
            root.end(now_ns)
        else:
            entry[1] = spans.begin(name, parent=root.id, start_ns=now_ns,
                                   request=rid, **attrs)
        return phase

    # -- the loop ---------------------------------------------------------

    def _note_prefill_queued(self, rows: int, bucket: int) -> None:
        r, b = self._prefill_queued
        self._prefill_queued = (r + rows, max(b, bucket))

    def _page_table_array(self) -> np.ndarray:
        """[S, nblk] physical-page tables for every slot row; free rows
        are all trash-page entries (their masked writes sink there)."""
        pt = np.zeros((self.config.slots, self._nblk), np.int32)
        for st in self.slots.states:
            if st is not None:
                pt[st.slot] = st.page_table
        return pt

    def _run_prefill_batched(self, lead: RequestState) -> None:
        """Prefill: advance EVERY waiting slot whose next chunk shares
        the lead's bucket in this tick — deeper queues amortize the same
        ≤3 compiled widths instead of serializing one chunk per loop
        iteration. The operands are as wide as the tick's members,
        `[m, C]`; `programs.prefill_calls` cuts them into calls of
        `NARROW_ROWS` rows with the slots they belong to, and the span
        carries that `width`. No row that is no member is computed."""
        size = lead.chunks[0][1]
        with span("serve.prefill") as sp:
            batch = [st for st in self.scheduler.active
                     if st.prefilling and st.chunks[0][1] == size]
            toks = np.zeros((len(batch), size), np.int32)
            starts = np.zeros((len(batch),), np.int32)
            lengths = np.zeros((len(batch),), np.int32)
            done = []
            for i, st in enumerate(batch):
                w, _ = st.chunks.pop(0)
                p1 = len(st.req.prompt) - 1
                window = st.req.prompt[w:min(w + size, p1)]
                lengths[i] = len(window)
                toks[i, :len(window)] = window
                starts[i] = w
                done.append((st, w, p1))
            # a model that keeps state a slot is told where each row's
            # real tokens end (its pads then sit at a junk position), and
            # starts a row whose chunk begins at 0 from zeros
            if self._slot_state:
                fresh = sum(1 for _, w, _ in done if w == 0)
                sp.set(state_rows=fresh)
                if self.telemetry is not None:
                    self.telemetry.slot_state_starts.inc(fresh)
            t0 = time.perf_counter()
            for ops in prefill_calls(
                    [st.slot for st in batch], toks, starts,
                    np.asarray([st.page_table for st in batch], np.int32),
                    lengths if self._slot_state else None,
                    self.config.slots, self.model_config.max_len):
                sp.set(width=len(ops[0]))
                self.cache = self._prefill(self.params, self.cache, *ops)
                if self.telemetry is not None:
                    self.telemetry.prefill_calls.inc()
        self._note_prefill_queued(len(batch), size)
        if self.telemetry is not None:
            # async dispatch: host wall time, not device time — the next
            # decode step's sync absorbs any queued prefill work (that
            # step's serve.decode_step span carries prefill_rows, and its
            # serve.sync is the wait)
            self.telemetry.prefill_seconds.observe(time.perf_counter() - t0)
        for st, w, p1 in done:
            st.pos = max(st.pos, min(p1, w + size))
            if not st.chunks:
                rt = self._trace(st.req.id)
                if rt is not None:
                    rt.begin_hop(self.POST_PREFILL_HOP, self._trace_now())
            if self.config.prefix_cache:
                self._publish_prompt_pages(st)

    def _publish_prompt_pages(self, st: RequestState) -> None:
        """Register this request's newly COMPLETED prompt pages in the
        prefix cache (chained keys, slots.PageAllocator.publish). Only
        full pages of prompt positions [0, P-1) are ever published — the
        partial tail page also holds decode tokens and stays private.
        A False from publish() means another request registered the
        identical prefix concurrently; our copy stays private, and we
        stop publishing descendants (they would chain off a parent page
        nothing can reach through the cache)."""
        alloc = self.page_allocator
        ps = alloc.page_size
        p1 = len(st.req.prompt) - 1
        full = p1 // ps
        while (st.published_pages < full
               and (st.published_pages + 1) * ps <= st.pos):
            k = st.published_pages
            page = st.page_table[k]
            if not alloc.publish(page, st.publish_parent,
                                 st.req.prompt[k * ps:(k + 1) * ps]):
                st.published_pages = full
                break
            st.published_pages = k + 1
            st.publish_parent = page

    def _dispatch_decode_step(self):
        """Build the step arrays and dispatch ONE decode step without
        waiting for its result. Returns the pending sync handle
        (device token/logprob refs, the consumers at dispatch time, the
        dispatch time and the id of the dispatch's span, which the sync
        names as its cause), or None when no state is eligible to
        consume a step. Cursors and dispatch counts advance HERE — they
        are deterministic, so the host's view stays exact while the
        tokens are in flight."""
        with span("serve.decode_step") as sp:
            toks, pos, use_prev, temps, top_ks, top_ps, consumers = \
                self.slots.step_arrays(
                    self.model_config.max_len if self._slot_state else None)
            if not consumers:
                sp.drop()
                return None
            mode = _sample_mode(consumers)
            prefill_rows, prefill_bucket = self._prefill_queued
            self._prefill_queued = (0, 0)
            sp.set(prefill_rows=prefill_rows, prefill_bucket=prefill_bucket)
            rng = jax.random.fold_in(self._base_rng, self._steps_dispatched)
            self._steps_dispatched += 1
            step_t0 = time.perf_counter()
            self.cache, out_tok, out_logp, out_counts = self._step(
                self.params, self.cache, self._prev_tok,
                jnp.asarray(toks), jnp.asarray(use_prev), jnp.asarray(pos),
                rng, jnp.asarray(temps), jnp.asarray(top_ks),
                jnp.asarray(top_ps),
                jnp.asarray(self._page_table_array()), mode)
        self._prev_tok = out_tok                 # the device-side chain
        for st in consumers:
            st.pos += 1                          # the step wrote at pos
            st.dispatched += 1
            st.host_next = False                 # chain re-established
            if st.dispatched >= st.req.max_new_tokens:
                # length exhaustion is known NOW, not at sync: free the
                # row so the next iteration admits into it — the final
                # token arrives at this step's sync, which reads the
                # dispatched snapshot, not the row. A new occupant's
                # prefill is dispatched after this step, so its writes
                # land on top of (never under) this request's K/V.
                self.slots.release(st)
                st.slot_released = True
        return out_tok, out_logp, consumers, step_t0, sp.id, out_counts

    def _plan_drafts(self) -> Dict[int, List[int]]:
        """Host-side proposal pass: {slot: draft tokens} for every row
        that can speculate THIS step. Eligibility: decoding (not
        prefilling/drained/done), temperature 0 (greedy acceptance
        verifies argmax agreement — a sampling row's next token is a
        draw, so there is nothing to verify), and ≥2 tokens of budget
        left (a 1-token budget is exactly a plain step). The caller
        must have synced any in-flight step first: drafting reads the
        request's full host-known history. Draft length is clamped so
        the verify step's worst-case writes stay inside the budget the
        scheduler reserved pages for (pos never passes P-2+max_new)."""
        cfg = self.config
        planned: Dict[int, List[int]] = {}
        vocab = self.model_config.vocab_size
        for st in self.slots.states:
            if st is None or st.prefilling or st.done:
                continue
            if st.req.temperature > 0.0:
                continue
            budget = st.req.max_new_tokens - st.dispatched - 1
            if budget < 1:
                continue
            k = min(cfg.draft_k, budget)
            hist = list(st.req.prompt) + st.generated
            if cfg.speculative == "ngram":
                raw = propose_ngram(hist, k, cfg.spec_ngram)
            else:
                raw = self._drafter(hist, k)
            draft: List[int] = []
            for t in raw[:k]:
                t = int(t)
                if not 0 <= t < vocab:
                    break          # garbage id: stop, keep the prefix
                draft.append(t)
            if draft:
                planned[st.slot] = draft
        return planned

    def _spec_step(self, planned: Dict[int, List[int]], now_fn,
                   on_token, results) -> List[RequestState]:
        """Dispatch ONE verify step over every decoding row and sync it:
        drafting rows carry [next_input, draft...] at consecutive
        cursors, plain rows ride along in column 0 (mixed batches cost
        nothing — the program is fixed-shape), padded tail positions sit
        at max_len so their writes drop. Greedy acceptance per row: keep
        the longest draft prefix matching the previous column's argmax,
        then the model's own next token rides free — every verify step
        emits ≥1 token, so speculation is never behind plain decode in
        steps. The cursor advanced over ALL written columns; the
        rejected tail is rolled back via slots.rewind (pure host
        bookkeeping — the dead K/V is masked now and overwritten next
        write). Synchronous by design: acceptance decides the NEXT
        step's inputs, so there is nothing to overlap (host_next keeps
        the device-side chain honest for the next plain step)."""
        cfg = self.config
        Sn = cfg.slots
        L = self.model_config.max_len
        with span("serve.verify_step") as sp:
            max_k = max((len(d) for d in planned.values()), default=0)
            W = next(b for b in self._verify_buckets if b >= max_k + 1)
            toks = np.zeros((Sn, W), np.int32)
            posn = np.full((Sn, W), L, np.int32)   # max_len = dropped write
            temps = np.zeros((Sn,), np.float32)
            top_ks = np.zeros((Sn,), np.int32)
            top_ps = np.ones((Sn,), np.float32)
            consumers: List[RequestState] = []
            for st in self.slots.states:
                if st is None or st.prefilling or st.done:
                    continue
                if st.dispatched >= st.req.max_new_tokens:
                    continue                       # drained: final sync only
                toks[st.slot, 0] = st.next_input
                posn[st.slot, 0] = st.pos
                temps[st.slot] = st.req.temperature
                top_ks[st.slot] = st.req.top_k
                top_ps[st.slot] = st.req.top_p
                d = planned.get(st.slot, ())
                if d:
                    toks[st.slot, 1:1 + len(d)] = d
                    posn[st.slot, 1:1 + len(d)] = \
                        st.pos + 1 + np.arange(len(d))
                consumers.append(st)
            if not consumers:
                sp.drop()
                return []
            mode = _sample_mode(consumers)
            prefill_rows, prefill_bucket = self._prefill_queued
            self._prefill_queued = (0, 0)
            sp.set(prefill_rows=prefill_rows, prefill_bucket=prefill_bucket)
            rng = jax.random.fold_in(self._base_rng, self._steps_dispatched)
            self._steps_dispatched += 1
            step_t0 = time.perf_counter()
            self.cache, dev_tg, dev_lp = self._verify(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(posn), rng, jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps),
                jnp.asarray(self._page_table_array()), mode)
        tel = self.telemetry
        with span("serve.sync", caused_by=sp.id):
            gap_t0 = time.perf_counter()
            tg = np.asarray(dev_tg)
            lp = np.asarray(dev_lp)
            t_sync = time.perf_counter()
        if tel is not None:
            tel.host_gap_seconds.observe(t_sync - gap_t0)
            tel.decode_step_seconds.observe(t_sync - step_t0)
        now = now_fn()
        finished: List[RequestState] = []
        self.spec_steps += 1
        spec_p0, spec_a0 = self.spec_proposed, self.spec_accepted
        with span("serve.retire"):
            for st in consumers:
                d = planned.get(st.slot, [])
                row_t, row_l = tg[st.slot], lp[st.slot]
                accepted = 0
                while (accepted < len(d)
                       and d[accepted] == int(row_t[accepted])):
                    accepted += 1
                emit = accepted + 1          # the model's own token is free
                eos = st.req.eos_id
                if eos is not None:
                    for j in range(emit):    # nothing streams past an EOS
                        if int(row_t[j]) == eos:
                            emit = j + 1
                            break
                written = len(d) + 1         # columns this row really wrote
                st.pos += written
                if written > emit:
                    self.slots.rewind(st.slot, written - emit, cfg.page_size)
                st.dispatched += emit
                if d:
                    self.spec_proposed += len(d)
                    self.spec_accepted += accepted
                    if tel is not None:
                        tel.spec_proposed_total.inc(len(d))
                        tel.spec_accepted_total.inc(accepted)
                        tel.spec_acceptance_ratio.observe(accepted / len(d))
                self.spec_rows += 1
                self.spec_tokens += emit
                if tel is not None:
                    tel.spec_tokens_per_step.observe(emit)
                if not st.token_times:
                    self._request_phase(st.req.id, "request.decode")
                for j in range(emit):
                    t = int(row_t[j])
                    if tel is not None:
                        if st.token_times:
                            tel.tpot_seconds.observe(now - st.token_times[-1])
                        else:
                            tel.ttft_seconds.observe(now - st.req.arrival)
                        tel.tokens_total.inc()
                    st.generated.append(t)
                    st.logprobs.append(float(row_l[j]))
                    st.token_times.append(now)
                    if on_token is not None:
                        on_token(st.req, t)
                st.next_input = int(row_t[emit - 1])
                st.host_next = True          # device chain token is stale
                if (eos is not None and st.generated
                        and st.generated[-1] == eos):
                    st.finish_reason = "eos"
                elif len(st.generated) >= st.req.max_new_tokens:
                    st.finish_reason = "length"
                if st.done:
                    finished.append(st)
            for st in finished:
                self._retire_state(st, results)
        if self._session_span is not None:
            # batch-level verify span under the session root, stamped
            # at sync on the session clock; acceptance counts ride as
            # attributes (the per-request roots cannot own a span that
            # served the whole batch)
            dur = t_sync - step_t0
            self._session_span.child(
                "serve.verify_step", now - dur, dur,
                batch=len(consumers),
                proposed=self.spec_proposed - spec_p0,
                accepted=self.spec_accepted - spec_a0)
        return finished

    def _sync_decode_step(self, pending, now_fn, on_token, results) \
            -> List[RequestState]:
        """Host-sync a previously dispatched step: fetch its tokens
        (the only blocking device read in the loop — host_gap_seconds
        is exactly this wait, and the `serve.sync` span names the
        dispatch it waited on), stream them, and retire what finished
        by EOS or length into `results`. A consumer already done at
        sync time took its one post-EOS junk step; its junk token is
        discarded here."""
        dev_tok, dev_logp, consumers, step_t0, dispatch_id, dev_counts = \
            pending
        tel = self.telemetry
        with span("serve.sync", caused_by=dispatch_id):
            gap_t0 = time.perf_counter()
            out_tok = np.asarray(dev_tok)        # host sync: stream point
            out_logp = np.asarray(dev_logp)
            counts = None if dev_counts is None else np.asarray(dev_counts)
            t_sync = time.perf_counter()
        if tel is not None:
            if counts is not None:
                tel.observe_step_counters(
                    dict(zip(self._step_counters, counts.tolist())))
            # how long the host was BLOCKED on the device — near zero
            # when the dispatched work fully hides under host scheduling
            tel.host_gap_seconds.observe(t_sync - gap_t0)
            # dispatch → sync: the effective per-step latency (in async
            # mode this spans the loop iteration that hid under it)
            tel.decode_step_seconds.observe(t_sync - step_t0)
        now = now_fn()
        if self._session_span is not None:
            dur = t_sync - step_t0
            self._session_span.child("serve.decode_step", now - dur, dur,
                                     batch=len(consumers))
        finished = []
        with span("serve.retire"):
            for st in consumers:
                if st.done:
                    continue
                t = int(out_tok[st.slot])
                if not st.token_times:
                    self._request_phase(st.req.id, "request.decode")
                if tel is not None:
                    if st.token_times:
                        tel.tpot_seconds.observe(now - st.token_times[-1])
                    else:
                        tel.ttft_seconds.observe(now - st.req.arrival)
                    tel.tokens_total.inc()
                st.next_input = t
                st.generated.append(t)
                st.logprobs.append(float(out_logp[st.slot]))
                st.token_times.append(now)
                if on_token is not None:
                    on_token(st.req, t)
                if st.req.eos_id is not None and t == st.req.eos_id:
                    st.finish_reason = "eos"
                elif len(st.generated) >= st.req.max_new_tokens:
                    st.finish_reason = "length"
                if st.done:
                    finished.append(st)
            for st in finished:
                self._retire_state(st, results)
        return finished

    def _note_admissions(self, admitted: List[RequestState]) -> None:
        """Bind newly admitted states to their slot rows and record the
        admission (slot_admit event, prefix-cache page counters). Shared
        by run() and the disaggregated facade's prefill side."""
        alloc = self.page_allocator
        tel = self.telemetry
        timeout = self.config.request_timeout
        for st in admitted:
            if timeout is not None:
                st.deadline = st.admitted_at + timeout
            self.slots.bind(st)
            blocked_on = self.scheduler.blocked_on.pop(st.req.id, "none")
            queued = self._request_phase(
                st.req.id, "request.prefill", calls=len(st.chunks),
                cached_tokens=st.cached_tokens)
            if queued is not None:
                queued.attrs["blocked_on"] = blocked_on
                self._request_spans[st.req.id][0].set(
                    pages_reserved=len(st.owned_pages))
            rt = self._trace(st.req.id)
            if rt is not None:
                # admission hop ends where the scheduler stamped it; a
                # fully-cached prompt has no chunks and skips straight
                # to the post-prefill hop
                rt.begin_hop("serve.prefill" if st.chunks
                             else self.POST_PREFILL_HOP,
                             st.admitted_at,
                             cached_tokens=st.cached_tokens)
            if self.events is not None:
                self.events.emit(ev.SLOT_ADMIT, request=st.req.id,
                                 slot=st.slot,
                                 prompt_len=len(st.req.prompt),
                                 cached_tokens=st.cached_tokens)
            if tel is not None:
                ps_ = alloc.page_size
                full = (len(st.req.prompt) - 1) // ps_
                hit = st.cached_tokens // ps_
                tel.prefix_hit_pages.inc(hit)
                tel.prefix_miss_pages.inc(full - hit)

    def _retire_state(self, st: RequestState,
                      results: Dict[int, "RequestResult"]) -> None:
        """Retire ONE finished state: scheduler/slot/page bookkeeping,
        the slot_retire event, and the RequestResult record. Shared by
        run() and the disaggregated facade's decode side."""
        alloc = self.page_allocator
        self.scheduler.retire(st)
        if not st.slot_released:          # EOS path: freed here; the
            self.slots.release(st)        # length path freed its row
            st.slot_released = True       # at dispatch already
        # drop every reference this request held — pinned shared prefix
        # pages and private pages alike; its PUBLISHED pages park in the
        # evictable LRU where future lookups still find them
        for p in st.owned_pages:
            alloc.release(p)
        st.owned_pages = []
        entry = self._request_spans.get(st.req.id)
        if entry is not None:
            entry[0].set(tokens=len(st.generated),
                         finish_reason=st.finish_reason)
            self._request_phase(st.req.id, None)
        if self.events is not None:
            self.events.emit(
                ev.SLOT_RETIRE, request=st.req.id, slot=st.slot,
                finish_reason=st.finish_reason,
                new_tokens=len(st.generated))
        if self.telemetry is not None:
            self.telemetry.requests_total.inc()
        rt = self._trace(st.req.id)
        if rt is not None:
            rt.attrs.update(finish_reason=st.finish_reason,
                            new_tokens=len(st.generated),
                            cached_tokens=st.cached_tokens)
            rt.finish("timeout" if st.finish_reason == "timeout"
                      else "ok", self._trace_now())
        results[st.req.id] = RequestResult(
            id=st.req.id, tokens=list(st.generated),
            logprobs=list(st.logprobs),
            finish_reason=st.finish_reason,
            # a request timed out before its first token has no TTFT
            ttft=(st.token_times[0] - st.req.arrival
                  if st.token_times else -1.0),
            token_times=list(st.token_times),
            cached_tokens=st.cached_tokens,
            admitted_at=st.admitted_at)

    def _sweep_timeouts(self, now: float,
                        results: Dict[int, "RequestResult"]) -> None:
        """Retire every resident state past its deadline with
        finish_reason "timeout" — through _retire_state, so the slot and
        pages come back exactly like an EOS retirement. Marking the state
        done here also makes any in-flight decode step's sync skip it
        (same discipline as a length retirement): the junk token the
        dispatched step produces for its old slot is discarded, and the
        row's next occupant overwrites its K/V."""
        if self.config.request_timeout is None:
            return
        for st in list(self.scheduler.active):
            if st.done or st.deadline is None or now < st.deadline:
                continue
            st.finish_reason = "timeout"
            st.chunks = []        # a mid-prefill request stops consuming
            #                       windows; nothing re-plans a done state
            if self.events is not None:
                # trace= pairs the incident with its span tree — the
                # postmortem "slow traces:" exemplar link
                self.events.emit(ev.REQUEST_TIMEOUT, request=st.req.id,
                                 slot=st.slot,
                                 new_tokens=len(st.generated),
                                 deadline_seconds=self.config
                                 .request_timeout,
                                 trace=st.req.id)
            self._retire_state(st, results)

    # -- steppable session (the router drives replicas through these) -----

    def start(self, on_token: Optional[Callable[[Request, int], None]]
              = None, now_fn: Optional[Callable[[], float]] = None) -> None:
        """Open a streaming session: submit() feeds requests in, tick()
        advances the loop one iteration, finish() closes it and returns
        the results. `now_fn` is the session clock (seconds, arbitrary
        epoch) — the serving router passes ONE shared clock to every
        replica so arrivals and TTFTs are comparable fleet-wide; None
        starts a private clock at 0."""
        if self._session is not None:
            raise RuntimeError("session already open (call finish())")
        if now_fn is None:
            t0 = time.perf_counter()
            now_fn = lambda: time.perf_counter() - t0   # noqa: E731
        self._session = {"results": {}, "pending": collections.deque(),
                         "on_token": on_token, "now_fn": now_fn}
        self._trace_now = now_fn
        if self.tracer is not None:
            self._session_span = self.tracer.begin_session(
                now_fn(), slots=self.config.slots)

    def set_heartbeat(self, hook: Callable[..., None],
                      interval: float) -> None:
        """Install a push-based load reporter: at most once per
        `interval` seconds of session time, tick() calls
        ``hook(now=..., queue_depth=..., free_slots=..., free_pages=...)``
        with this replica's instantaneous load. The router wires the
        hook into RouterTelemetry so dispatch can score replicas off
        published reports instead of probing engine state in-process —
        the shape a cross-host router actually has to live with."""
        if interval <= 0:
            raise ValueError(f"heartbeat interval must be > 0, got "
                             f"{interval}")
        self._heartbeat = (hook, float(interval))
        self._heartbeat_last = None

    def _maybe_heartbeat(self, now: float) -> None:
        """Rate-limited publish (see set_heartbeat); no-op when no
        reporter is installed."""
        if self._heartbeat is None:
            return
        hook, interval = self._heartbeat
        last = self._heartbeat_last
        if last is not None and now - last < interval:
            return
        self._heartbeat_last = now
        hook(now=now,
             queue_depth=len(self.scheduler.queue),
             free_slots=len(self.slots.free),
             free_pages=self.page_allocator.available)

    def submit(self, req: Request) -> None:
        """Queue one request into the open session (front-door entry
        point). Raises ValueError for spans the engine can NEVER
        satisfy — the same up-front rejection run() applies."""
        if self._session is None:
            raise RuntimeError("submit() outside a session (call start())")
        alloc = self.page_allocator
        need = Scheduler.pages_needed(req, alloc.page_size)
        if need > alloc.usable:
            # a request the pool can NEVER satisfy would sit at the head
            # of the queue forever (admission livelock); reject it up
            # front like an over-max_len prompt
            raise ValueError(
                f"request {req.id}: worst-case span needs {need} KV "
                f"pages but the pool has {alloc.usable} usable "
                f"(raise num_pages or lower max_new_tokens)")
        self.scheduler.submit(req)
        # the request enters the span log: its root and its first phase
        # begin now, or at its arrival where a replayed trace puts that
        # in the future
        start_ns = time.perf_counter_ns() + max(0, int(
            1e9 * (req.arrival - self._session["now_fn"]())))
        root = spans.begin("request", start_ns=start_ns, request=req.id,
                           prompt_len=len(req.prompt))
        self._request_spans[req.id] = [root, spans.begin(
            "request.queued", parent=root.id, start_ns=start_ns,
            request=req.id)]
        if self.tracer is not None:
            # open (or, behind a router / on a failover replay, JOIN)
            # this request's trace — the router's queue-wait hop closes
            # where admission begins
            rt = self.tracer.begin_request(
                req.id, t0=req.arrival, prompt_len=len(req.prompt),
                max_new_tokens=req.max_new_tokens)
            if rt is not None:
                rt.begin_hop("serve.admission",
                             max(req.arrival, self._session["now_fn"]()))

    def withdraw(self, req: Request) -> None:
        """Take a request that still waits for admission back out of the
        session: the router's drain re-routes it to a survivor. Its
        records close here, `finish_reason` "withdrawn"."""
        blocked_on = self.scheduler.withdraw(req)
        entry = self._request_spans.get(req.id)
        if entry is not None:
            entry[1].set(blocked_on=blocked_on)
            entry[0].set(tokens=0, finish_reason="withdrawn")
            self._request_phase(req.id, None)

    @property
    def active(self) -> bool:
        """True while the open session still has work in flight."""
        return (self._session is not None
                and not (self.scheduler.idle
                         and not self._session["pending"]))

    def tick(self) -> bool:
        """One iteration of the admit → prefill → decode loop. Returns
        False when the engine had nothing to do this instant (idle, or
        every queued arrival is in the future) WITHOUT sleeping — the
        caller owns the wait policy (run() naps; the router services
        other replicas)."""
        sess = self._session
        if sess is None:
            raise RuntimeError("tick() outside a session (call start())")
        if not self.active:
            return False
        with span("serve.tick") as tick_span:
            alloc = self.page_allocator
            tel = self.telemetry
            now_fn = sess["now_fn"]
            on_token = sess["on_token"]
            results = sess["results"]
            now = now_fn()
            with span("serve.schedule") as sched:
                # deadline sweep FIRST: a wedged head-of-queue request frees
                # its slot before this iteration's admission fills the rows
                self._sweep_timeouts(now, results)
                self._note_admissions(
                    self.scheduler.admit(self.slots.free, now, alloc))
                reserved, filled = self.scheduler.page_counts(
                    alloc.page_size)
                sched.set(blocked=self.scheduler.blocked,
                          waiting=self.scheduler.waiting,
                          pages_reserved=reserved, pages_filled=filled)
            self.occupancy_peak = max(self.occupancy_peak,
                                      self.slots.occupied)
            self.pages_in_use_peak = max(self.pages_in_use_peak,
                                         alloc.in_use)
            if tel is not None:
                tel.queue_depth.set(len(self.scheduler.queue))
                tel.slot_occupancy.set(self.slots.occupied)
                tel.pages_in_use.set(alloc.in_use)
                tel.pages_cached.set(alloc.cached_pages)
            # heartbeat AFTER admission: the published queue depth is what
            # is still waiting behind the slots, not this instant's intake
            self._maybe_heartbeat(now)
            # dispatched steps whose tokens are not fetched yet, oldest
            # first
            pending = sess["pending"]
            sync = lambda: self._sync_decode_step(             # noqa: E731
                pending.popleft(), now_fn, on_token, results)
            # nothing resident yet and the next arrival is in the future:
            # nothing to advance — report it instead of spinning
            if self.slots.occupied == 0 and not pending:
                nxt = self.scheduler.next_arrival()
                if nxt is not None and nxt > now_fn():
                    tick_span.drop()
                    return False
            st = self.scheduler.next_prefill()
            if st is not None:
                self._run_prefill_batched(st)
            planned = {}
            if (self.config.speculative is not None
                    and self.scheduler.decoding()):
                # drafting reads host-known history, and acceptance
                # decides the next step's inputs — drain the in-flight
                # steps first (speculative steps are synchronous; the
                # multi-token payoff replaces the dispatch overlap)
                while pending:
                    sync()
                planned = self._plan_drafts()
            if planned:
                self._spec_step(planned, now_fn, on_token, results)
                dispatched = None
            else:
                # no row drafted this step (novel text, sampling rows,
                # exhausted budgets): plain decode, async overlap intact
                dispatched = (self._dispatch_decode_step()
                              if self.scheduler.decoding() else None)
            # fetch the oldest step AFTER the new one went out, once more
            # than `async_depth` are out (sync mode is depth 0: the same
            # compiled step, fetched immediately); a tick that dispatched
            # nothing drains the queue by a step
            if dispatched is not None:
                pending.append(dispatched)
            elif pending:
                sync()
            depth = self.config.async_depth if self.config.async_decode \
                else 0
            while len(pending) > depth:
                sync()
            return True

    def drain(self) -> None:
        """Fetch the tokens of every dispatched step now, oldest first.
        The session stays open; the next tick() dispatches onto a device
        with nothing queued. For a caller that wants the work it has
        submitted so far DONE before it goes on (a benchmark's set-up
        before its window opens: at `async_depth` n the device is up to
        n steps, and the prefill calls dispatched with them, behind)."""
        sess = self._session
        if sess is None:
            raise RuntimeError("drain() outside a session (call start())")
        while sess["pending"]:
            self._sync_decode_step(sess["pending"].popleft(), sess["now_fn"],
                                   sess["on_token"], sess["results"])

    def session_results(self) -> Dict[int, RequestResult]:
        """The open session's retired results so far (live view) — the
        router fans these in after each tick()."""
        if self._session is None:
            raise RuntimeError("session_results() outside a session")
        return self._session["results"]

    def finish(self) -> Dict[int, RequestResult]:
        """Close the session (final telemetry flush) and return
        {request.id: RequestResult} for everything retired in it."""
        sess = self._session
        if sess is None:
            raise RuntimeError("finish() outside a session")
        tel = self.telemetry
        if tel is not None:
            counts = self.compile_counts()
            tel.step_compiles.set(counts["step"])
            tel.prefill_compiles.set(counts["prefill"])
            tel.queue_depth.set(len(self.scheduler.queue))
            tel.slot_occupancy.set(self.slots.occupied)
        if self._session_span is not None:
            self._session_span.end(sess["now_fn"]())
            self._session_span = None
        self._trace_now = None
        self._session = None
        # a request the session leaves unfinished leaves no record
        self._request_spans = {}
        return sess["results"]

    def run(self, requests: Sequence[Request] = (),
            on_token: Optional[Callable[[Request, int], None]] = None,
            ) -> Dict[int, RequestResult]:
        """Drive the engine until every submitted request completes.
        `on_token(request, token)` streams tokens as they are fetched.
        Returns {request.id: RequestResult}.

        The body is exactly start → submit* → tick-until-idle → finish;
        the double buffer lives inside tick(): each iteration dispatches
        step N+1 FIRST, then syncs step N — admission/retirement/prefill
        planning all happen while the dispatched step runs, and a slot
        retired at step N stays masked until step N+1's dispatch already
        consumed the old occupancy (the one-step-lagged lifecycle)."""
        self.start(on_token)
        try:
            for r in requests:
                self.submit(r)
            while self.active:
                if not self.tick():
                    # queue non-empty but every arrival is in the
                    # future: sleep up to the next one instead of
                    # spinning
                    nxt = self.scheduler.next_arrival()
                    now = self._session["now_fn"]()
                    if nxt is not None and nxt > now:
                        time.sleep(min(nxt - now, 0.05))
        except Exception:
            if self._session is not None:
                self.trace_abandon(self._session["now_fn"]())
            self._session = None
            raise
        return self.finish()


class PrefillEngine(ServingEngine):
    """The prefill half of a disaggregated pair (DisaggEngine drives
    it): admits requests and runs batched chunked prefill, but never
    dispatches a decode step — so its compiled-program footprint is
    prefill-only (`prefill <= len(chunk_buckets)`, `step == 0`; the
    per-pool HBM program-cache win of the split). Page reservations
    cover the PROMPT span only (Scheduler reserve="prompt"): the decode
    span lives in the decode pool, so this pool's pages all do prefill
    work — at equal bytes it keeps strictly more prompts in flight than
    a colocated engine could."""

    RESERVE = "prompt"
    HANDS_OFF_PAGES = True

    #: a prefilled prompt's next hop in this pool is the page handoff,
    #: not decode — trace hop names follow the disaggregated flow
    POST_PREFILL_HOP = "serve.kv_handoff"

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 telemetry=None, events=None, tracer=None):
        cfg = config or EngineConfig()
        # the prefill pool never decodes, so it never drafts either —
        # strip the speculation knob rather than make it validate a
        # drafter it will not call
        if cfg.speculative is not None:
            cfg = dataclasses.replace(cfg, speculative=None)
        super().__init__(model, params, cfg, telemetry=telemetry,
                         events=events, tracer=tracer)

    def take_prefilled(self) -> List[RequestState]:
        """Pop every state whose prefill just completed: it leaves the
        scheduler and frees its slot row (the next prompt starts
        immediately) but KEEPS its page references — the handoff copy
        still reads those pages; DisaggEngine releases them once the
        copy is dispatched. Nothing can write the kept pages meanwhile:
        writes route through slot page tables, and the freed row's
        table is rebuilt from its next occupant's pages."""
        done = [st for st in self.scheduler.active if not st.prefilling]
        for st in done:
            self.scheduler.retire(st)
            self.slots.release(st)
            st.slot_released = True
        return done


class DecodeEngine(ServingEngine):
    """The decode half: requests arrive pre-filled via
    `install_handoff` and flow through the shared decode step; this
    pool never compiles a prefill program (`step <= 3`, `prefill ==
    0`). Its PageAllocator runs the same prefix cache as a colocated
    engine — a handed-off prompt whose prefix is already resident here
    needs NO bytes moved for those pages (DisaggEngine transfers only
    the misses)."""

    HANDS_OFF_PAGES = True

    def install_handoff(self, req: Request, reserved, now: float,
                        cached_tokens: int = 0,
                        ) -> Tuple[RequestState, List[Tuple[int, int]]]:
        """Bind a prefill-complete request into a slot of THIS pool.
        `reserved` is this pool's full-span page reservation (chain,
        private, table) from Scheduler._reserve_pages — the chain pages
        are decode-side prefix-cache hits whose KV is already resident.
        Returns (state, fill) where fill lists (prompt-page index,
        physical page here) for every page whose contents must still be
        copied in from the prefill pool; full prompt pages among them
        are published into this pool's prefix cache immediately, so the
        NEXT handoff sharing the prefix skips their copy too.

        The caller must have checked `self.slots.free` first."""
        chain, private, table = reserved
        alloc = self.page_allocator
        ps = alloc.page_size
        p1 = len(req.prompt) - 1
        full = p1 // ps                   # complete PROMPT pages
        # pages prefill actually wrote: positions [0, p1)
        written = 0 if p1 < 1 else (p1 - 1) // ps + 1
        slot = self.slots.free.pop(0)
        st = RequestState(req=req, slot=slot, pos=p1, chunks=[],
                          next_input=int(req.prompt[-1]), admitted_at=now)
        if self.config.request_timeout is not None:
            # the decode pool stamps its OWN window — the prefill-side
            # deadline was consumed getting the request this far
            st.deadline = now + self.config.request_timeout
        st.page_table = table
        st.owned_pages = chain + private
        st.cached_tokens = cached_tokens
        st.published_pages = full         # published below or inherited —
        st.publish_parent = -1            # the engine never re-publishes
        self.slots.bind(st)
        self.scheduler.active.append(st)
        fill = [(k, table[k]) for k in range(len(chain), written)]
        if self.config.prefix_cache:
            parent = chain[-1] if chain else -1
            for k in range(len(chain), full):
                if not alloc.publish(table[k], parent,
                                     req.prompt[k * ps:(k + 1) * ps]):
                    break
                parent = table[k]
        if self.telemetry is not None:
            # decode-side hit/miss = handoff pages saved/moved — the
            # same instruments a colocated engine feeds at admission
            self.telemetry.prefix_hit_pages.inc(len(chain))
            self.telemetry.prefix_miss_pages.inc(written - len(chain))
        if self.events is not None:
            self.events.emit(ev.SLOT_ADMIT, request=req.id, slot=slot,
                             prompt_len=len(req.prompt),
                             cached_tokens=len(chain) * ps)
        return st, fill


class DisaggEngine:
    """Disaggregated prefill/decode serving: a PrefillEngine and a
    DecodeEngine on SEPARATE devices, bridged by paged-KV handoff
    (serve/transfer.py). One long prompt saturates the prefill pool
    while in-flight decodes keep stepping on the decode pool — the
    TTFT/TPOT interference a colocated engine can't avoid is gone by
    construction, and each pool compiles only its own programs.

    Flow per request: admit → prefill pool (prompt-span-only page
    reservation, batched chunked prefill) → handoff (decode-side
    full-span reservation; device-to-device copy of exactly the prompt
    pages the decode pool's prefix cache does NOT already hold) →
    decode pool (shared double-buffered step) → retire (pages park in
    the decode pool's prefix cache). Admission is backpressured when
    the decode pool's free pages can't absorb the in-flight handoffs
    (Scheduler.gate), so a handoff can stall only on slots, never
    deadlock on pages.

    Token parity: at temperature 0 the facade is token-for-token
    identical to a colocated ServingEngine over the same trace
    (tests/test_disagg.py pins it, dense and Pallas-kernel, int8 KV
    included): per-slot prefill/step rows are computed independently,
    so batching composition doesn't change a row's KV; the handoff
    copies those exact bytes (int8 payloads move with their scale
    planes); and the decode step is the same compiled program. At
    temperature > 0 sampling matches distributionally but not bitwise —
    the per-step rng folds in each pool's own dispatch counter.

    On CPU smoke the two "pools" are two of the virtual host devices
    (same program structure, host-memory device_put); on real hardware
    point `devices=` at chips in different pools and the copy rides
    ICI/DCN. The controller stands up the two pools as distinct worker
    groups (TPU_SERVE_ROLE) — see controller/controller.py."""

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 *, prefill_config: Optional[EngineConfig] = None,
                 registry=None, events=None, devices=None, drafter=None,
                 tracer=None):
        cfg = config or EngineConfig()
        pcfg = prefill_config or cfg
        if pcfg.page_size != cfg.page_size:
            raise ValueError(
                f"prefill/decode page_size disagree "
                f"({pcfg.page_size} vs {cfg.page_size}) — the handoff "
                f"moves pages verbatim")
        if devices is None:
            local = jax.local_devices()
            devices = ((local[0], local[1]) if len(local) > 1
                       else (local[0], local[0]))
        self.devices = tuple(devices)
        pre_tel = dec_tel = None
        if registry is not None:
            from ..telemetry.worker import ServeTelemetry
            pre_tel = ServeTelemetry(registry, labels={"pool": "prefill"})
            dec_tel = ServeTelemetry(registry, labels={"pool": "decode"})
        self.events = events
        pre_ev = events.bind(pool="prefill") if events is not None else None
        dec_ev = events.bind(pool="decode") if events is not None else None
        # device_put COMMITS each pool's params to its device; every jit
        # downstream (cast, init_cache, prefill/step, transfer
        # gather/scatter) follows its committed operands, so the two
        # engines' programs land on the two devices with no mesh code
        self.tracer = tracer
        self.prefill = PrefillEngine(
            model, jax.device_put(params, self.devices[0]), pcfg,
            telemetry=pre_tel, events=pre_ev, tracer=tracer)
        self.decode = DecodeEngine(
            model, jax.device_put(params, self.devices[1]), cfg,
            telemetry=dec_tel, events=dec_ev, drafter=drafter,
            tracer=tracer)
        self.transfer = PageTransfer(self.prefill.page_allocator.num_pages,
                                     self.decode.page_allocator.num_pages)
        self.config = cfg
        self._handoff_q: List[RequestState] = []
        # handoff trace for the bench: (seconds, pages moved, pages
        # skipped via the decode-side prefix cache) per handoff
        self.handoff_log: List[Tuple[float, int, int]] = []
        self._install_gate()

    def _install_gate(self) -> None:
        """Decode-capacity backpressure on PREFILL admission: a request
        enters the prefill pool only while the decode pool's available
        pages cover every in-flight request's worst-case span plus this
        one — so prefill can't fill with prompts the decode pool cannot
        absorb, and handoffs drain as decode capacity frees (the
        scheduler's lookahead still packs smaller requests past a gated
        head)."""
        ps = self.config.page_size
        dec_alloc = self.decode.page_allocator

        def gate(req: Request) -> bool:
            inflight = sum(Scheduler.pages_needed(s.req, ps)
                           for s in self.prefill.scheduler.active)
            inflight += sum(Scheduler.pages_needed(s.req, ps)
                            for s in self._handoff_q)
            return (dec_alloc.available
                    >= inflight + Scheduler.pages_needed(req, ps))

        self.prefill.scheduler.gate = gate

    def reset(self) -> None:
        """Reset both pools (queues, caches, allocators) keeping every
        compiled program — including the transfer's gather/scatter,
        which live on this facade, so a warmed DisaggEngine replays a
        trace with identical tokens and identical compile counts."""
        self.prefill.reset()
        self.decode.reset()
        self._handoff_q = []
        self.handoff_log = []
        self.transfer.pages_moved = 0
        self._install_gate()              # reset() rebuilt the scheduler

    def compile_counts(self) -> Dict[str, Dict[str, int]]:
        """Per-pool program-cache sizes plus the transfer pair. The
        disaggregation pins: prefill pool `step == 0`, decode pool
        `prefill == 0` — neither pool ever compiles the other's
        programs."""
        return {"prefill_pool": self.prefill.compile_counts(),
                "decode_pool": self.decode.compile_counts(),
                "transfer": self.transfer.compile_counts()}

    def _handoff(self, st: RequestState, reserved, now: float) -> None:
        """Move one prefill-complete request into the decode pool:
        install it there (decode-side reservation already made), copy
        exactly the non-cached written prompt pages device-to-device,
        then drop the prefill pool's page references — its published
        prompt pages park in the prefill prefix cache (a repeat prompt
        skips the recompute), the private tail returns to its free
        list."""
        pre, dec = self.prefill, self.decode
        t0 = time.perf_counter()
        chain_hits = len(reserved[0])
        new_st, fill = dec.install_handoff(st.req, reserved, now,
                                           cached_tokens=st.cached_tokens)
        src_ids = [st.page_table[k] for k, _ in fill]
        dst_ids = [p for _, p in fill]
        with span("serve.kv_handoff"):
            dec.cache, moved = self.transfer.move(pre.cache, dec.cache,
                                                  src_ids, dst_ids)
        # the gather captured the source buffers at dispatch — the page
        # REFERENCES can drop now (see PageTransfer.move)
        for p in st.owned_pages:
            pre.page_allocator.release(p)
        st.owned_pages = []
        dt = time.perf_counter() - t0     # host wall, async-dispatch
        self.handoff_log.append((dt, moved, chain_hits))
        rt = dec._trace(st.req.id)
        if rt is not None:
            # page counts land on the kv_handoff hop (which spans
            # prefill-done → installed here, queue wait included), then
            # the decode hop opens
            rt.hop_attrs(pages=moved, cached_pages=chain_hits,
                         move_seconds=round(dt, 6))
            rt.begin_hop("serve.decode", now)
        if dec.telemetry is not None:
            dec.telemetry.kv_handoff_seconds.observe(dt)
            dec.telemetry.kv_handoff_pages.inc(moved)
        if self.events is not None:
            self.events.emit(ev.KV_HANDOFF, request=st.req.id,
                             pages=moved, cached_pages=chain_hits,
                             seconds=dt)

    def _sweep_handoff_timeouts(self, now: float,
                                results: Dict[int, RequestResult]) -> None:
        """Expire past-deadline requests parked in the handoff queue.
        These left the prefill scheduler already (take_prefilled) but
        still hold prefill-pool page references for the pending copy —
        the one resident claim _sweep_timeouts can't see — so the drop
        happens here, against the prefill allocator, before the decode
        pool ever reserves for them."""
        pre = self.prefill
        still: List[RequestState] = []
        for st in self._handoff_q:
            if st.deadline is None or now < st.deadline:
                still.append(st)
                continue
            st.finish_reason = "timeout"
            for p in st.owned_pages:
                pre.page_allocator.release(p)
            st.owned_pages = []
            if self.events is not None:
                self.events.emit(ev.REQUEST_TIMEOUT, request=st.req.id,
                                 slot=st.slot, new_tokens=0,
                                 deadline_seconds=pre.config
                                 .request_timeout,
                                 trace=st.req.id)
            if pre.telemetry is not None:
                pre.telemetry.requests_total.inc()
            rt = pre._trace(st.req.id)
            if rt is not None:
                rt.attrs.update(finish_reason="timeout", new_tokens=0)
                rt.finish("timeout", now)
            results[st.req.id] = RequestResult(
                id=st.req.id, tokens=[], logprobs=[],
                finish_reason="timeout", ttft=-1.0, token_times=[],
                cached_tokens=st.cached_tokens,
                admitted_at=st.admitted_at)
        self._handoff_q = still

    def _drain_handoffs(self, now_fn) -> None:
        """Install every queued handoff the decode pool can take right
        now (a free slot + a full-span page reservation); the rest stay
        queued — backpressure keeps this queue short, and decode-side
        retirements free the capacity that drains it."""
        dec = self.decode
        still: List[RequestState] = []
        for st in self._handoff_q:
            reserved = None
            if dec.slots.free:
                reserved = dec.scheduler._reserve_pages(
                    st.req, dec.page_allocator)
            if reserved is None:
                still.append(st)
                continue
            self._handoff(st, reserved, now_fn())
        self._handoff_q = still

    def run(self, requests: Sequence[Request] = (),
            on_token: Optional[Callable[[Request, int], None]] = None,
            ) -> Dict[int, RequestResult]:
        """Drive both pools to completion over `requests` — same
        contract as ServingEngine.run (trace replay via future
        arrivals, on_token streaming, {id: RequestResult})."""
        pre, dec = self.prefill, self.decode
        ps = self.config.page_size
        for r in requests:
            need = Scheduler.pages_needed(r, ps)
            if need > dec.page_allocator.usable:
                raise ValueError(
                    f"request {r.id}: worst-case span needs {need} KV "
                    f"pages but the decode pool has "
                    f"{dec.page_allocator.usable} usable")
            pneed = Scheduler.prompt_pages_needed(r, ps)
            if pneed > pre.page_allocator.usable:
                raise ValueError(
                    f"request {r.id}: prompt span needs {pneed} KV pages "
                    f"but the prefill pool has "
                    f"{pre.page_allocator.usable} usable")
            pre.scheduler.submit(r)
            if self.tracer is not None:
                rt = self.tracer.begin_request(
                    r.id, t0=r.arrival, prompt_len=len(r.prompt),
                    max_new_tokens=r.max_new_tokens, disagg=True)
                if rt is not None:
                    # the facade has no front door queue: admission
                    # starts at arrival (the run clock starts at 0)
                    rt.begin_hop("serve.admission", r.arrival)
        t0 = time.perf_counter()
        now_fn = lambda: time.perf_counter() - t0   # noqa: E731
        # both pools stamp trace hops on the SAME run clock, so a
        # request's prefill/handoff/decode hops stay contiguous across
        # the pool boundary
        pre._trace_now = dec._trace_now = now_fn
        if self.tracer is not None:
            dec._session_span = self.tracer.begin_session(
                now_fn(), slots=dec.config.slots, pool="decode")
        results: Dict[int, RequestResult] = {}
        pending = None
        while not (pre.scheduler.idle and not self._handoff_q
                   and dec.scheduler.idle and pending is None):
            now = now_fn()
            # per-pool deadline sweeps plus the handoff queue (a request
            # parked between pools holds prefill-side pages — it must
            # not outlive its deadline there either)
            pre._sweep_timeouts(now, results)
            dec._sweep_timeouts(now, results)
            self._sweep_handoff_timeouts(now, results)
            with span("serve.schedule"):
                pre._note_admissions(
                    pre.scheduler.admit(pre.slots.free, now,
                                        pre.page_allocator))
            for eng, qdepth in ((pre, len(pre.scheduler.queue)),
                                (dec, len(self._handoff_q))):
                eng.occupancy_peak = max(eng.occupancy_peak,
                                         eng.slots.occupied)
                eng.pages_in_use_peak = max(eng.pages_in_use_peak,
                                            eng.page_allocator.in_use)
                if eng.telemetry is not None:
                    # the decode pool's "queue" is the handoff queue —
                    # prompts prefilled but not yet installed
                    eng.telemetry.queue_depth.set(qdepth)
                    eng.telemetry.slot_occupancy.set(eng.slots.occupied)
                    eng.telemetry.pages_in_use.set(
                        eng.page_allocator.in_use)
                    eng.telemetry.pages_cached.set(
                        eng.page_allocator.cached_pages)
            if (pre.slots.occupied == 0 and not self._handoff_q
                    and dec.slots.occupied == 0 and pending is None):
                nxt = pre.scheduler.next_arrival()
                if nxt is not None and nxt > now_fn():
                    time.sleep(min(nxt - now_fn(), 0.05))
                continue
            lead = pre.scheduler.next_prefill()
            if lead is not None:
                pre._run_prefill_batched(lead)
            self._handoff_q.extend(pre.take_prefilled())
            self._drain_handoffs(now_fn)
            planned = {}
            if (dec.config.speculative is not None
                    and dec.scheduler.decoding()):
                # the decode pool verifies; drafting is host state, so
                # the disaggregated split composes with speculation with
                # no extra machinery (see ServingEngine.run)
                if pending is not None:
                    dec._sync_decode_step(pending, now_fn, on_token,
                                          results)
                    pending = None
                planned = dec._plan_drafts()
            if planned:
                dec._spec_step(planned, now_fn, on_token, results)
                new_pending = None
            else:
                new_pending = (dec._dispatch_decode_step()
                               if dec.scheduler.decoding() else None)
            if pending is not None:
                dec._sync_decode_step(pending, now_fn, on_token, results)
                pending = None
            if self.config.async_decode:
                pending = new_pending
            elif new_pending is not None:
                dec._sync_decode_step(new_pending, now_fn, on_token,
                                      results)
        for eng in (pre, dec):
            if eng.telemetry is not None:
                counts = eng.compile_counts()
                eng.telemetry.step_compiles.set(counts["step"])
                eng.telemetry.prefill_compiles.set(counts["prefill"])
                eng.telemetry.queue_depth.set(0)
                eng.telemetry.slot_occupancy.set(eng.slots.occupied)
        if dec._session_span is not None:
            dec._session_span.end(now_fn())
            dec._session_span = None
        pre._trace_now = dec._trace_now = None
        return results


__all__ = ["SAMPLE_POOL", "DecodeEngine", "DisaggEngine", "EngineConfig",
           "PrefillEngine", "RequestResult", "ServingEngine",
           "propose_ngram", "sample_slots"]
