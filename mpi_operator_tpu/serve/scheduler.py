"""Host-side request scheduling for the continuous-batching engine.

Everything here is plain Python over plain numbers — no jax — so the
policy (FCFS admission, chunk planning, retirement) is unit-testable
without tracing anything, and the engine's device code stays a fixed
set of compiled programs that this module merely feeds.

The prefill trick worth knowing: a request's prompt of length P is
prefilled as prompt[:P-1] only. The LAST prompt token becomes the first
decode-step input (the "bonus token"), so the first NEW token comes out
of the same compiled decode step as every later one — no separate
"prefill tail + sample" program, and time-to-first-token is exactly one
decode step after the last chunk lands.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class Request:
    """One generation request. `arrival` is seconds relative to the
    engine run's t0 (0.0 = already waiting when the run starts) — the
    bench replays traces by submitting requests with future arrivals.
    Sampling params mirror generate(): temperature 0 = greedy argmax
    (top_k/top_p ignored), top_k 0 = disabled, top_p 1.0 = disabled."""
    id: int
    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    arrival: float = 0.0


@dataclasses.dataclass
class RequestState:
    """A request's life inside a slot. `pos` counts cache positions
    WRITTEN so far — it is both the slot's decode cursor and the next
    write offset. `chunks` are the pending prefill windows (start,
    size); once drained, `next_input` (initially the bonus token) flows
    through the shared decode step."""
    req: Request
    slot: int
    pos: int = 0
    chunks: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    next_input: int = 0
    # decode steps DISPATCHED for this request (>= len(generated): with
    # the async engine the newest step's token is still on the device).
    # dispatched >= 1 means the next step chains its input from the
    # previous step's device output (slots.step_arrays use_prev); once
    # dispatched reaches max_new_tokens the request stops consuming
    # steps and retires at the next sync.
    dispatched: int = 0
    # slot row already returned to the free pool (length exhaustion is
    # known at DISPATCH time, so the engine frees the row before the
    # final sync delivers the last token — the guard keeps the sync-side
    # retirement from releasing a row that may already be re-bound)
    slot_released: bool = False
    # the last emitted token lives on the HOST (next_input), not in the
    # device-side _prev_tok chain — set after a speculative verify step
    # (its targets return to the host for acceptance), cleared when a
    # plain decode step re-establishes the device chain. step_arrays
    # keeps use_prev False while set.
    host_next: bool = False
    generated: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    token_times: List[float] = dataclasses.field(default_factory=list)
    admitted_at: float = 0.0
    finish_reason: Optional[str] = None   # "eos" | "length" | "timeout"
    # wall-clock (run-relative) deadline stamped at admission when
    # EngineConfig.request_timeout is set; None = no deadline. The
    # engine's timeout sweep retires a past-deadline request with
    # finish_reason "timeout" through the NORMAL retire path — slot and
    # KV pages reclaimed like any EOS, so one wedged request can neither
    # freeze the serving progress frontier nor leak pages.
    deadline: Optional[float] = None
    # `page_table` maps the slot's logical KV blocks to physical pages
    # (length max_len // page_size, unallocated entries = trash page 0);
    # `owned_pages` are the references this request holds — pinned shared
    # prefix pages plus its private pages — each release()d exactly once
    # at retirement.
    # The request's whole worst-case span is reserved at ADMISSION
    # (ceil((P-1 + max_new) / page_size) pages, minus prefix hits), so
    # decode never allocates mid-flight and can never deadlock.
    page_table: Optional[List[int]] = None
    owned_pages: List[int] = dataclasses.field(default_factory=list)
    # prompt positions [0, cached_tokens) resolved from the prefix cache:
    # prefill starts at the cached span (TTFT win of a hit)
    cached_tokens: int = 0
    # prefix-publishing cursor: this request's prompt pages [0,
    # published_pages) are already in the prefix cache (hits count —
    # they were published by their original prefiller); the engine
    # advances it as prefill completes pages. publish_parent is the
    # chain key's parent page for the NEXT page to publish.
    published_pages: int = 0
    publish_parent: int = -1

    @property
    def prefilling(self) -> bool:
        return bool(self.chunks)

    @property
    def done(self) -> bool:
        return self.finish_reason is not None


def plan_chunks(n: int, buckets: Sequence[int], start: int = 0,
                overlap: bool = True) -> List[Tuple[int, int]]:
    """Windows (start, size) covering prompt positions [start, n), sizes
    drawn from the ≤3 compiled `buckets` (ascending). Full largest-bucket
    windows walk left→right; the ragged tail takes the smallest bucket
    that fits, RIGHT-ALIGNED (start = n - size) so no window writes past
    n — the overlap recomputes a suffix of already-written positions,
    which writes back identical values (same params, tokens, positions)
    instead of writing junk into the decode region. Only a prompt
    shorter than every bucket pads (one window at 0; the engine
    right-pads the tokens, and those pad writes land past the prompt
    where the decode cursor overwrites them before they are ever
    attended).

    `start` > 0 is the prefix-cache span (positions already resolved to
    shared pages): windows begin there, and the ragged tail is LEFT-
    aligned with padding instead of right-aligned — reaching backwards
    would rewrite SHARED pages, which other requests may be attending
    concurrently. The pad writes land past n where the decode cursor
    overwrites them, same as the short-prompt case.

    `overlap=False` is for a model that keeps recurrent state
    (programs.py, SLOT_STATE): recomputing a suffix would feed tokens
    through its scan twice, so the tail is left-aligned and padded from
    position 0 on as well — and the engine puts those pads at a junk
    position, not at real ones."""
    if n < 0:
        raise ValueError(f"negative prefill length {n}")
    if not 0 <= start <= n:
        raise ValueError(f"prefill start {start} outside [0, {n}]")
    out: List[Tuple[int, int]] = []
    done = start
    big = buckets[-1]
    while n - done >= big:
        out.append((done, big))
        done += big
    if done < n:
        size = next(b for b in buckets if b >= n - done)
        if start > 0 or not overlap:
            out.append((done, size))            # left-aligned, padded
        else:
            out.append((max(0, n - size), size))
    return out


class Scheduler:
    """FCFS arrival queue + admission. The engine asks it two questions
    per loop: who newly fits into a free slot (`admit`), and which
    admitted request should run its next prefill chunk
    (`next_prefill`, oldest-admitted first so a burst of long prompts
    drains in arrival order while decode steps interleave).

    Admission also reserves KV pages (the binding resource): a
    request needs its worst-case page span free — minus
    whatever its prompt prefix resolves to in the cache — before it gets
    a slot. When the head of the queue doesn't fit, `admit` looks ahead
    up to `admit_lookahead` arrived requests for one whose page demand
    DOES fit (prompt-length packing): a burst of long prompts no longer
    head-of-line-blocks the short requests that would ride along in the
    pages left over. FCFS order is preserved whenever the head fits."""

    def __init__(self, chunk_buckets: Sequence[int], max_len: int,
                 admit_lookahead: int = 8, reserve: str = "full",
                 overlap_chunks: bool = True):
        buckets = tuple(chunk_buckets)
        if not 1 <= len(buckets) <= 3:
            raise ValueError(f"chunk_buckets must have 1-3 entries "
                             f"(compiled prefill shapes), got {buckets}")
        if list(buckets) != sorted(set(buckets)):
            raise ValueError(f"chunk_buckets must be strictly ascending, "
                             f"got {buckets}")
        if buckets[-1] > max_len:
            raise ValueError(f"largest chunk bucket {buckets[-1]} exceeds "
                             f"max_len={max_len}")
        if admit_lookahead < 1:
            raise ValueError(f"admit_lookahead must be >= 1, "
                             f"got {admit_lookahead}")
        if reserve not in ("full", "prompt"):
            raise ValueError(f"reserve must be 'full' or 'prompt', "
                             f"got {reserve!r}")
        self.chunk_buckets = buckets
        self.max_len = max_len
        self.admit_lookahead = admit_lookahead
        # "full" reserves a request's whole worst-case span at admission
        # (colocated serving: decode must never allocate mid-flight);
        # "prompt" reserves only the pages prefill will write — the
        # disaggregated PREFILL pool's mode, where the decode span is
        # the decode pool's problem (serve/engine.py PrefillEngine).
        self.reserve = reserve
        # False for a model with recurrent state (plan_chunks)
        self.overlap_chunks = overlap_chunks
        # optional admission gate: a predicate over the candidate
        # request checked before any reservation work. The
        # disaggregated facade installs the decode-pool backpressure
        # here — when the decode pool's free pages cannot absorb the
        # in-flight handoffs plus this request, the candidate stays
        # queued (lookahead still lets a smaller request behind it try,
        # the same packing rule as a failed page reservation).
        self.gate = None
        self.queue: deque[Request] = deque()
        self.active: List[RequestState] = []
        # slot-aware reserve-ahead: page reservations made
        # while NO slot was free, keyed by request id — see admit().
        # Dies with the scheduler (engine reset() also resets the
        # allocator, so no pins leak).
        self.staged: Dict[int, Tuple[List[int], List[int], List[int]]] = {}
        # why the last `admit` stopped with arrived requests still queued
        # ("slot", "pages", "gate"; "none" where nobody waits), how many
        # it left waiting, and per queued request what its last failed
        # attempt lacked: the engine writes them into the span log
        # (`serve.schedule`'s `blocked` and `waiting`, `request.queued`'s
        # `blocked_on`)
        self.blocked = "none"
        self.waiting = 0
        self.blocked_on: Dict[int, str] = {}

    def submit(self, req: Request) -> None:
        p = len(req.prompt)
        if p < 1:
            raise ValueError(f"request {req.id}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.id}: max_new_tokens must be "
                             f">= 1")
        if p + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.id}: prompt ({p}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_len={self.max_len} "
                f"(the per-slot KV budget)")
        # keep the queue sorted by arrival (traces submit in order; the
        # insort tolerates out-of-order submission)
        if self.queue and req.arrival < self.queue[-1].arrival:
            items = sorted([*self.queue, req], key=lambda r: r.arrival)
            self.queue = deque(items)
        else:
            self.queue.append(req)

    def withdraw(self, req: Request) -> str:
        """Take a queued request back out; returns what its last failed
        attempt at admission lacked ("none" where none failed)."""
        self.queue.remove(req)
        return self.blocked_on.pop(req.id, "none")

    def next_arrival(self) -> Optional[float]:
        return self.queue[0].arrival if self.queue else None

    @staticmethod
    def pages_needed(req: Request, page_size: int) -> int:
        """Worst-case page span of a request: prefill writes positions
        [0, P-1) and decode writes [P-1, P-1 + max_new) — the last
        written position is P-2+max_new, so the span is its page + 1."""
        return (len(req.prompt) - 2 + req.max_new_tokens) // page_size + 1

    @staticmethod
    def prompt_pages_needed(req: Request, page_size: int) -> int:
        """Prompt-only page span: prefill writes positions [0, P-1), so
        the last written position is P-2. This is what a disaggregated
        PREFILL pool reserves (reserve="prompt") — the decode span never
        touches its pages, which is exactly the capacity win of the
        split (serve/engine.py PrefillEngine)."""
        p1 = len(req.prompt) - 1
        return 0 if p1 < 1 else (p1 - 1) // page_size + 1

    def _reserve_pages(self, req: Request, allocator):
        """Try to reserve `req`'s page span: pin its cached prefix
        chain, then allocate the rest — or undo the pins and return None
        when the pool (free + evictable) can't cover it. The span is the
        worst case for this scheduler's reserve mode (full request or
        prompt only); reserving up-front is what makes the steady state
        allocation-free: a request that gets a slot can always finish
        its phase here."""
        ps = allocator.page_size
        p1 = len(req.prompt) - 1              # bonus token excluded
        full = p1 // ps                       # complete PROMPT pages
        total = (self.pages_needed(req, ps) if self.reserve == "full"
                 else self.prompt_pages_needed(req, ps))
        chain = allocator.lookup(req.prompt, full)
        if allocator.available < total - len(chain):
            for p in reversed(chain):
                allocator.release(p)
            return None
        private = [allocator.alloc() for _ in range(total - len(chain))]
        table = [allocator.TRASH] * (self.max_len // ps)
        table[:len(chain)] = chain
        table[len(chain):total] = private
        return chain, private, table

    def admit(self, free_slots: List[int], now: float,
              allocator) -> List[RequestState]:
        """Move arrived requests into free slots, FCFS. A request is
        admitted only when its page span reserves from `allocator` (a
        slots.PageAllocator; see `_reserve_pages`); a head that doesn't
        fit lets up to `admit_lookahead` arrived requests behind it try
        (packing). Returns the new RequestStates (also tracked in
        self.active).

        Slot-aware reserve-ahead (the dual of the lookahead above): when
        pages FIT but no slot is free, up to `admit_lookahead` arrived
        requests reserve their page spans NOW and park them in
        `self.staged`. Two wins: the reservation pins their cached
        prefix chains before decode-side allocations can evict them, and
        the moment a slot frees the head admits instantly — no
        reservation work on that step's critical path.

        Why it stopped is left in `self.blocked`, what each request it
        tried and could not place lacked in `self.blocked_on`, and the
        arrived requests it leaves queued in `self.waiting`."""
        out = []
        self.blocked = "none"
        while free_slots and self.queue and self.queue[0].arrival <= now:
            picked = None
            lacked = "gate"               # unless some request lacked pages
            for idx, req in enumerate(self.queue):
                if idx >= self.admit_lookahead or req.arrival > now:
                    break
                if self.gate is not None and not self.gate(req):
                    self.blocked_on[req.id] = "gate"
                    continue              # backpressured; let others try
                reserved = self.staged.pop(req.id, None)
                if reserved is None:
                    reserved = self._reserve_pages(req, allocator)
                if reserved is not None:
                    picked = (idx, req, reserved)
                    break
                self.blocked_on[req.id] = lacked = "pages"
            if picked is None:
                self.blocked = lacked
                break
            idx, req, reserved = picked
            del self.queue[idx]
            slot = free_slots.pop(0)
            p1 = len(req.prompt) - 1          # bonus token excluded
            chain, private, table = reserved
            span = len(chain) * allocator.page_size   # prefix-cache hits
            st = RequestState(
                req=req, slot=slot,
                pos=span,                     # prefill starts past the hits
                chunks=plan_chunks(p1, self.chunk_buckets, start=span,
                                   overlap=self.overlap_chunks),
                next_input=int(req.prompt[-1]), admitted_at=now,
                page_table=table, owned_pages=chain + private,
                cached_tokens=span, published_pages=len(chain),
                publish_parent=chain[-1] if chain else -1)
            self.active.append(st)
            out.append(st)
        if not free_slots:
            for idx, req in enumerate(self.queue):
                if idx >= self.admit_lookahead or req.arrival > now:
                    break
                self.blocked = self.blocked_on[req.id] = "slot"
                if req.id in self.staged:
                    continue
                if self.gate is not None and not self.gate(req):
                    continue
                reserved = self._reserve_pages(req, allocator)
                if reserved is not None:
                    self.staged[req.id] = reserved
        self.waiting = 0
        for req in self.queue:            # sorted by arrival
            if req.arrival > now:
                break
            self.waiting += 1
        return out

    def next_prefill(self) -> Optional[RequestState]:
        for st in self.active:            # admission order = FCFS
            if st.prefilling:
                return st
        return None

    def decoding(self) -> List[RequestState]:
        return [st for st in self.active if not st.prefilling]

    def retire(self, st: RequestState) -> None:
        self.active.remove(st)

    def page_counts(self, page_size: int) -> Tuple[int, int]:
        """(reserved, filled): the pages that admitted and staged requests
        hold, and those of them with at least one written position (a
        request's first ceil(pos / page_size); a staged request's cached
        chain). One pass over the active rows; a page that two requests
        share through the prefix cache counts once for each."""
        reserved = filled = 0
        for st in self.active:
            n = len(st.owned_pages)
            written = -(-st.pos // page_size)
            reserved += n
            filled += written if written < n else n
        for chain, private, _ in self.staged.values():
            reserved += len(chain) + len(private)
            filled += len(chain)
        return reserved, filled

    @property
    def idle(self) -> bool:
        return not self.queue and not self.active


__all__ = ["Request", "RequestState", "Scheduler", "plan_chunks"]
