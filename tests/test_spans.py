"""The program's span log (telemetry/spans.py) and the call sites that feed
it: the record itself (ids, parent, cause, attributes, threads, the bound),
JAX's own phases as spans under the open program span, the input wait with
the queue's depth, the set-up trees of the engine and the trainer, and the
serving tick as a tree whose every sync names the dispatch it waited on —
with the observations on the `telemetry=` hook where they always were."""
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from mpi_operator_tpu.data.prefetch import PrefetchDataset
from mpi_operator_tpu.telemetry import spans
from mpi_operator_tpu.telemetry.spans import span

TICK_ORDER = ["serve.schedule", "serve.prefill", "serve.decode_step",
              "serve.sync", "serve.retire"]


@pytest.fixture(autouse=True)
def fresh_log():
    spans.clear()
    yield
    spans.clear()


def by_name(name):
    return [r for r in spans.records() if r.name == name]


# -- the record --------------------------------------------------------------

def test_parent_is_the_enclosing_span_and_a_cause_may_be_named():
    with span("outer") as outer:
        with span("dispatch") as dispatch:
            pass
        with span("inner", caused_by=dispatch.id) as inner:
            pass
    with span("later", caused_by=dispatch.id) as later:
        pass
    recs = {r.name: r for r in spans.records()}
    assert [r.name for r in spans.records()] == ["dispatch", "inner",
                                                 "outer", "later"]
    assert recs["outer"].parent is None and recs["outer"].caused_by is None
    assert recs["dispatch"].parent == outer.id
    assert recs["inner"].parent == outer.id
    assert recs["inner"].caused_by == dispatch.id
    assert recs["later"].parent is None
    assert recs["later"].caused_by == dispatch.id
    assert outer.id < dispatch.id < inner.id < later.id
    for r in recs.values():
        assert r.start_ns <= r.end_ns and r.thread == threading.get_ident()
        assert r.in_capture is False
    assert recs["outer"].start_ns <= recs["dispatch"].start_ns
    assert recs["inner"].end_ns <= recs["outer"].end_ns


def test_attributes_may_be_set_until_the_span_closes():
    with span("tick", slots=4) as s:
        s.set(occupied=3)
        s.set(occupied=2, queue_depth=0)
    (rec,) = spans.records()
    assert rec.attrs == {"slots": 4, "occupied": 2, "queue_depth": 0}


def test_a_dropped_span_leaves_no_record_and_no_open_parent():
    with span("idle") as s:
        s.drop()
    with span("next"):
        pass
    assert [(r.name, r.parent) for r in spans.records()] == [("next", None)]


def test_a_dropped_span_takes_what_closed_under_it_and_nothing_else():
    """No orphan: a dropped tick's `serve.schedule` goes with it, JAX's
    reports under it too; earlier records and another thread's stay."""
    with span("before"):
        pass
    opened, go = threading.Event(), threading.Event()

    def other():
        with span("other.thread"):
            opened.set()
            go.wait(5)
    t = threading.Thread(target=other)
    with span("idle.tick") as tick:
        with span("child"):
            with span("grandchild"):
                pass
        t.start()
        assert opened.wait(5)
        spans._on_jax_duration("/jax/core/compile/jaxpr_trace_duration",
                               1e-6, fun_name="f")
        go.set()
        t.join()                    # closes between the tick's children
        with span("child"):
            pass
        tick.drop()
    with span("after"):
        pass
    recs = spans.records()
    assert [r.name for r in recs] == ["before", "other.thread", "after"]
    assert all(r.parent is None for r in recs)


def test_a_span_that_raises_is_recorded_and_closed():
    with pytest.raises(ValueError):
        with span("outer"):
            with span("fails"):
                raise ValueError("boom")
    with span("after"):
        pass
    assert [(r.name, r.parent is None) for r in spans.records()] == [
        ("fails", False), ("outer", True), ("after", True)]


def test_two_threads_do_not_share_a_stack():
    inside = threading.Event()
    release = threading.Event()

    def other():
        with span("other.outer"):
            inside.set()
            assert release.wait(10)
            with span("other.inner"):
                pass

    t = threading.Thread(target=other)
    t.start()
    assert inside.wait(10)
    with span("main.outer"):           # opens while other.outer is open
        with span("main.inner"):
            pass
    release.set()
    t.join(10)
    assert not t.is_alive()
    recs = {r.name: r for r in spans.records()}
    assert recs["main.outer"].parent is None
    assert recs["main.inner"].parent == recs["main.outer"].id
    assert recs["other.outer"].parent is None
    assert recs["other.inner"].parent == recs["other.outer"].id
    assert recs["other.outer"].thread != recs["main.outer"].thread


@pytest.fixture
def no_gc_records(monkeypatch):
    """For the tests that count the log: a collection that happened to
    take a millisecond would be one record more."""
    monkeypatch.setattr(spans, "GC_MIN_NS", 10**15)


def test_many_threads_lose_no_record_and_share_no_id(no_gc_records):
    """More threads than cores, a short switch interval: every span of
    every thread is in the log once, and nests under its own thread's."""
    workers, each = 24, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(k):
            for i in range(each):
                with span("w.outer", worker=k):
                    with span("w.inner", worker=k, i=i):
                        pass
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    recs = spans.records()
    assert len(recs) == 2 * workers * each
    assert len({r.id for r in recs}) == len(recs)
    by_id = {r.id: r for r in recs}
    for r in recs:
        if r.name == "w.inner":
            assert by_id[r.parent].attrs["worker"] == r.attrs["worker"]
            assert by_id[r.parent].thread == r.thread


def test_the_log_is_bounded_and_keeps_the_newest(no_gc_records):
    extra = 50
    for i in range(spans.LOG_BOUND + extra):
        with span("s", i=i):
            pass
    recs = spans.records()
    assert len(recs) == spans.LOG_BOUND
    assert recs[0].attrs["i"] == extra
    assert recs[-1].attrs["i"] == spans.LOG_BOUND + extra - 1


def test_records_is_a_snapshot():
    with span("a"):
        pass
    snap = spans.records()
    with span("b"):
        pass
    assert [r.name for r in snap] == ["a"]
    assert [r.name for r in spans.records()] == ["a", "b"]


def test_last_is_the_newest_record_of_a_name_with_the_attributes():
    """How what is known only after a region closed gets onto its record
    (the train step's counts onto its jax.compile)."""
    for name, fun in (("jax.compile", "jit(f)"), ("jax.compile", "jit(g)"),
                      ("jax.trace", "g"), ("jax.cache_load", "jit(f)")):
        with span(name, fun_name=fun):
            pass
    a, b, _, d = spans.records()
    assert spans.last("jax.compile") is b
    assert spans.last("jax.compile", fun_name="jit(f)") is a
    assert spans.last("jax.compile", "jax.cache_load", fun_name="jit(f)") is d
    assert spans.last("jax.lower") is None
    assert spans.last("jax.trace", fun_name="jit(g)") is None
    spans.last("jax.trace").set(seen=1)
    assert spans.records()[2].attrs == {"fun_name": "g", "seen": 1}


# -- spans that live across calls --------------------------------------------

def test_a_begun_and_ended_record_is_on_no_thread_and_keeps_its_links():
    with span("dispatch") as dispatch:
        pass
    root = spans.begin("request", request=7, prompt_len=12)
    with span("tick.one"):
        phase = spans.begin("request.queued", parent=root.id,
                            caused_by=dispatch.id, request=7)
    assert [r.name for r in spans.records()] == ["dispatch", "tick.one"]
    with span("tick.two") as two:
        phase.end(blocked_on="pages")
        root.set(tokens=3)
        root.end()
    recs = {r.name: r for r in spans.records()}
    assert [r.name for r in spans.records()] == [
        "dispatch", "tick.one", "request.queued", "request", "tick.two"]
    queued, req = recs["request.queued"], recs["request"]
    assert queued.thread == 0 and req.thread == 0
    assert queued.parent == req.id and req.parent is None
    assert queued.caused_by == dispatch.id
    assert queued.attrs == {"request": 7, "blocked_on": "pages"}
    assert req.attrs == {"request": 7, "prompt_len": 12, "tokens": 3}
    assert req.start_ns <= queued.start_ns <= queued.end_ns <= req.end_ns
    assert two.start_ns <= queued.end_ns <= two.end_ns
    assert queued.in_capture is False and req.id < queued.id < two.id


def test_begin_and_end_take_the_callers_instants():
    rec = spans.begin("request", start_ns=1_000, request=1)
    rec.end(end_ns=4_500)
    (got,) = spans.records()
    assert (got.start_ns, got.end_ns, got.duration_ns) == (1_000, 4_500, 3_500)


def test_a_record_that_ended_under_a_dropped_span_stays():
    """A request may be retired (a timeout) in a tick that then finds
    nothing to do and is dropped: the tick's own children go, the
    request's records stay, in order."""
    req = spans.begin("request", request=1)
    with span("before"):
        pass
    with span("idle.tick") as tick:
        with span("child"):
            pass
        req.end(finish_reason="timeout")
        with span("child"):
            pass
        tick.drop()
    assert [(r.name, r.thread == 0) for r in spans.records()] == [
        ("before", False), ("request", True)]


def test_a_record_on_no_thread_is_invisible_to_a_threads_nesting():
    """`self_times` nests one thread's spans by time; a request that
    lives across the ticks it overlaps takes nothing from them."""
    from perfbench.readers import _spans
    req = spans.begin("request", request=1)
    with span("serve.tick") as tick:
        with span("serve.sync") as sync:
            pass
    req.end()
    mine = [r for r in spans.records() if r.thread == tick.thread]
    own, root = _spans.self_times(mine)
    assert set(own) == {tick.id, sync.id} and root[sync.id] == tick.id
    assert own[tick.id] == tick.duration_ns - sync.duration_ns
    # and with it in the pile it is a thread of its own, not a parent
    own_all, root_all = _spans.self_times(spans.records())
    assert own_all[tick.id] == own[tick.id] and root_all[tick.id] == tick.id
    assert own_all[req.id] == req.duration_ns


# -- the collector -----------------------------------------------------------

def _collect(monkeypatch, floor_ns):
    import gc
    monkeypatch.setattr(spans, "GC_MIN_NS", floor_ns)
    junk = []
    for _ in range(2000):
        a, b = [], []
        a.append(b)
        b.append(a)
        junk.append(a)
    del junk, a, b
    with span("serve.tick") as tick:
        gc.collect()
    return tick


def test_a_long_collection_leaves_py_gc_under_the_open_span(monkeypatch):
    tick = _collect(monkeypatch, 1)           # every collection is "long"
    (rec,) = [r for r in by_name("py.gc") if r.parent == tick.id]
    assert rec.thread == threading.get_ident()
    assert rec.attrs["generation"] == 2 and rec.attrs["collected"] >= 4000
    assert tick.start_ns <= rec.start_ns < rec.end_ns <= tick.end_ns


def test_a_short_collection_leaves_nothing(monkeypatch):
    _collect(monkeypatch, 10**12)             # none takes a quarter hour
    assert by_name("py.gc") == []
    assert spans.GC_MIN_NS == 10**12 and spans._on_gc in __import__(
        "gc").callbacks


# -- JAX's own phases --------------------------------------------------------

def test_jax_trace_lower_and_compile_land_under_the_open_program_span():
    def a_program_of_this_test(x):
        return jnp.tanh(x) * 3.0 + 1.0
    program = jax.jit(a_program_of_this_test)
    with span("test.program") as prog:
        program(jnp.ones((5,))).block_until_ready()
    mine = [r for r in spans.records() if "a_program_of_this_test"
            in str(r.attrs.get("fun_name"))]
    assert sorted(r.name for r in mine) == ["jax.compile", "jax.lower",
                                            "jax.trace"]
    for r in mine:
        assert r.parent == prog.id
        assert 0 < r.duration_ns < prog.duration_ns
        assert prog.start_ns - 5e6 <= r.start_ns and r.end_ns <= prog.end_ns
    # a second call compiles nothing: no new span
    n = len(spans.records())
    program(jnp.ones((5,))).block_until_ready()
    assert len(spans.records()) == n


def test_a_persistent_cache_hit_is_one_cache_load_with_the_programs_name():
    """JAX reports a hit's retrieval (no name) and then the backend
    compile that enclosed it (with the name): one span, `jax.cache_load`."""
    with span("test.warm") as warm:
        spans._on_jax_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        spans._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 0.5,
            fun_name="jit(step_paged)")
        spans._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 2.0,
            fun_name="jit(prefill_paged)")
        spans._on_jax_duration("/jax/some/other/event", 1.0)
    load, compiled, _ = spans.records()
    assert (load.name, load.attrs) == (
        "jax.cache_load", {"fun_name": "jit(step_paged)"})
    assert load.duration_ns == 500_000_000 and load.parent == warm.id
    assert (compiled.name, compiled.attrs) == (
        "jax.compile", {"fun_name": "jit(prefill_paged)"})


# -- input -------------------------------------------------------------------

class _Gated(PrefetchDataset):
    def __init__(self, n, gate):
        self.n, self.gate = n, gate
        self._start_feeder(prefetch=2)

    def _produce(self):
        for i in range(self.n):
            self.gate.wait()
            yield i


def test_data_next_carries_the_queues_depth_and_a_starved_feeder_reads_0():
    gate = threading.Event()
    ds = _Gated(4, gate)
    try:
        # the feeder is held back: the consumer finds the queue empty
        timer = threading.Timer(0.2, gate.set)
        timer.start()
        assert next(ds) == 0
        timer.join(10)
        starved = by_name("data.next")[-1]
        assert starved.attrs == {"depth": 0}
        assert starved.duration_ns > 100e6          # it waited for the gate
        # the feeder runs ahead: the queue fills to its bound
        deadline = threading.Event()
        for _ in range(200):
            if ds._queue.qsize() == 2:
                break
            deadline.wait(0.01)
        assert next(ds) == 1
        ahead = by_name("data.next")[-1]
        assert ahead.attrs == {"depth": 2}
        assert ahead.duration_ns < starved.duration_ns
    finally:
        ds.close()


# -- the serving engine ------------------------------------------------------

@pytest.fixture(scope="module")
def toy():
    from mpi_operator_tpu.models.transformer import (CausalLM,
                                                     TransformerConfig)
    cfg = TransformerConfig(vocab_size=256, max_len=128, num_layers=2,
                            num_heads=4, embed_dim=64, mlp_dim=256,
                            causal=True, dtype=jnp.float32)
    model = CausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((2, 8), jnp.int32)))["params"]
    return model, params


class _Recorder:
    def __init__(self):
        self.values = []

    def observe(self, x):
        self.values.append(x)


def _paged_engine(toy, **kw):
    from mpi_operator_tpu.serve import EngineConfig, ServingEngine
    from mpi_operator_tpu.telemetry.worker import ServeTelemetry
    model, params = toy
    tel = ServeTelemetry()
    tel.host_gap_seconds = _Recorder()
    tel.decode_step_seconds = _Recorder()
    tel.prefill_seconds = _Recorder()
    eng = ServingEngine(model, params, EngineConfig(
        slots=4, chunk_buckets=(8, 16), page_size=16,
        num_pages=20, **kw), telemetry=tel)
    return eng, tel


def _requests(n=6):
    from mpi_operator_tpu.serve import Request
    rng = np.random.default_rng(0)
    return [Request(id=i, prompt=rng.integers(0, 250, 10 + 3 * i).tolist(),
                    max_new_tokens=6 + i) for i in range(n)]


def test_engine_set_up_is_a_tree_with_jaxs_work_under_each_program(toy):
    _paged_engine(toy)
    (init,) = by_name("serve.engine_init")
    (cast,) = by_name("serve.cast_params")
    (cache,) = by_name("serve.init_cache")
    assert init.parent is None
    assert cast.parent == init.id and cache.parent == init.id
    assert cast.end_ns <= cache.start_ns
    compiled = {r.attrs["fun_name"]: r.parent
                for r in by_name("jax.compile") + by_name("jax.cache_load")}
    assert compiled.get("jit(init_cache)") == cache.id
    traced = {r.attrs["fun_name"]: r.parent for r in by_name("jax.trace")}
    assert traced.get("init_cache") == cache.id


@pytest.mark.parametrize("async_decode", [True, False])
def test_every_worked_tick_is_a_tree_in_order_and_syncs_name_dispatches(
        toy, async_decode):
    from mpi_operator_tpu.serve.scheduler import plan_chunks
    eng, tel = _paged_engine(toy, async_decode=async_decode)
    spans.clear()
    worked = 0
    eng.start()
    for r in _requests():
        eng.submit(r)
    while eng.active:
        worked += bool(eng.tick())
    results = eng.finish()
    assert {r.finish_reason for r in results.values()} == {"length"}

    recs = spans.records()
    by_id = {r.id: r for r in recs}
    ticks = by_name("serve.tick")
    assert len(ticks) == worked > 10
    order = {n: i for i, n in enumerate(TICK_ORDER)}
    for tick in ticks:
        assert tick.parent is None
        kids = sorted((r for r in recs if r.parent == tick.id
                       and r.name.startswith("serve.")),
                      key=lambda r: r.start_ns)
        names = [k.name for k in kids]
        assert names[0] == "serve.schedule" and names.count(
            "serve.schedule") == 1
        # the table's order, each at most once a tick: dispatch before
        # sync, whether the sync is of this tick's step or the last's
        assert names == sorted(set(names), key=order.get), names
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns
            assert tick.start_ns <= a.start_ns and b.end_ns <= tick.end_ns
    # every child of the loop belongs to a tick
    for name in TICK_ORDER:
        for r in by_name(name):
            assert by_id[r.parent].name == "serve.tick", name

    dispatches = by_name("serve.decode_step")
    syncs = by_name("serve.sync")
    assert len(syncs) == len(dispatches) > 10
    assert [s.caused_by for s in syncs] == [d.id for d in dispatches]
    for s in syncs:
        d = by_id[s.caused_by]
        assert d.end_ns <= s.start_ns
        # double-buffered: the sync is a tick after its dispatch
        assert (s.parent != d.parent) == async_decode
    # only what a reader consumes is carried (PERF.md section 3)
    assert all(set(d.attrs) == {"prefill_rows", "prefill_bucket"}
               for d in dispatches)
    assert all(set(r.attrs) == {"blocked", "waiting", "pages_reserved",
                                "pages_filled"}
               for r in by_name("serve.schedule"))
    # (`width`, the rows of the program a prefill call ran, waits for its
    # reader: PR 48 put it there for the next benchmark PR)
    assert all(r.attrs == {"width": 1} for r in by_name("serve.prefill"))
    assert not any(r.attrs for name in TICK_ORDER + ["serve.tick"]
                   if name not in ("serve.decode_step", "serve.schedule",
                                   "serve.prefill")
                   for r in by_name(name))

    # a prefill call is queued on the device ahead of the next dispatch,
    # and that dispatch's span says so: how many rows' chunks, how wide
    prefills = by_name("serve.prefill")
    calls_ahead = 0
    for r in sorted(prefills + dispatches, key=lambda r: r.start_ns):
        if r.name == "serve.prefill":
            calls_ahead += 1
            continue
        rows, bucket = r.attrs["prefill_rows"], r.attrs["prefill_bucket"]
        if calls_ahead:
            assert rows >= calls_ahead and bucket in (8, 16)
        else:
            assert (rows, bucket) == (0, 0)
        calls_ahead = 0
    # every chunk of every prompt rode ahead of exactly one dispatch
    assert sum(d.attrs["prefill_rows"] for d in dispatches) == sum(
        len(plan_chunks(len(r.prompt) - 1, (8, 16))) for r in _requests())
    assert any(d.attrs["prefill_rows"] == 0 for d in dispatches)

    # the hook is unmoved: one observation where there was one
    assert len(tel.host_gap_seconds.values) == len(syncs)
    assert len(tel.decode_step_seconds.values) == len(syncs)
    assert len(tel.prefill_seconds.values) == len(prefills)


def test_a_tick_with_nothing_to_do_leaves_no_serve_tick(toy):
    from mpi_operator_tpu.serve import Request
    eng, _ = _paged_engine(toy)
    spans.clear()
    clock = {"now": 0.0}
    eng.start(now_fn=lambda: clock["now"])
    assert eng.tick() is False                 # idle: not even a schedule
    assert spans.records() == []
    eng.submit(Request(id=0, prompt=[1, 2, 3, 4], max_new_tokens=2,
                       arrival=5.0))
    assert eng.tick() is False                 # the arrival is in the future
    assert spans.records() == []               # nor its schedule, orphaned
    clock["now"] = 6.0
    assert eng.tick() is True
    assert len(by_name("serve.tick")) == 1
    while eng.active:
        eng.tick()
    eng.finish()


def test_a_verify_step_is_named_by_its_sync_too(toy):
    from mpi_operator_tpu.serve import Request
    eng, tel = _paged_engine(toy, speculative="ngram", draft_k=3)
    spans.clear()
    prompt = [7, 8, 9, 10] * 6                 # repetitive: drafts match
    eng.run([Request(id=0, prompt=prompt, max_new_tokens=12)])
    verifies = by_name("serve.verify_step")
    assert verifies and all(set(v.attrs) == {"prefill_rows",
                                              "prefill_bucket"}
                            for v in verifies)
    causes = {s.caused_by for s in by_name("serve.sync")}
    assert {v.id for v in verifies} <= causes
    assert causes <= {r.id for r in verifies + by_name("serve.decode_step")}
    assert len(tel.host_gap_seconds.values) == len(by_name("serve.sync"))


# -- the request in the log --------------------------------------------------

def _small_pool_engine(toy, **kw):
    """Four slots and seven usable pages of 16: a request of this mix
    needs 2-4, so pages and not slots hold the queue."""
    from mpi_operator_tpu.serve import EngineConfig, ServingEngine
    model, params = toy
    return ServingEngine(model, params, EngineConfig(
        slots=4, chunk_buckets=(8, 16), page_size=16, num_pages=8, **kw))


def _long_requests(n=6):
    from mpi_operator_tpu.serve import Request
    rng = np.random.default_rng(0)
    return [Request(id=i, prompt=rng.integers(0, 250, 10 + 3 * i).tolist(),
                    max_new_tokens=20 + i) for i in range(n)]


PHASES = ["request.queued", "request.prefill", "request.decode"]


@pytest.fixture
def served(toy):
    eng = _small_pool_engine(toy)
    spans.clear()
    results = eng.run(_long_requests())
    return eng, results


def test_every_finished_request_is_a_root_and_three_phases_that_sum(served):
    _, results = served
    roots = {r.attrs["request"]: r for r in by_name("request")}
    assert sorted(roots) == sorted(results) == list(range(6))
    for rid, root in roots.items():
        kids = [r for r in spans.records()
                if r.parent == root.id and r.name in PHASES]
        assert [k.name for k in sorted(kids, key=lambda r: r.start_ns)] \
            == PHASES
        assert all(k.attrs["request"] == rid and k.thread == 0
                   for k in kids)
        queued, prefill, decode = sorted(kids, key=lambda r: r.start_ns)
        assert queued.start_ns == root.start_ns
        assert queued.end_ns == prefill.start_ns
        assert prefill.end_ns == decode.start_ns
        assert decode.end_ns == root.end_ns
        assert sum(k.duration_ns for k in kids) == root.duration_ns
        assert root.thread == 0 and root.parent is None


def test_the_roots_and_phases_carry_what_the_readers_take(served):
    from mpi_operator_tpu.serve.scheduler import plan_chunks
    _, results = served
    reqs = {r.id: r for r in _long_requests()}
    for root in by_name("request"):
        rid = root.attrs["request"]
        assert root.attrs == {
            "request": rid, "prompt_len": len(reqs[rid].prompt),
            "pages_reserved": (len(reqs[rid].prompt) - 2
                               + reqs[rid].max_new_tokens) // 16 + 1,
            "tokens": len(results[rid].tokens), "finish_reason": "length"}
    for r in by_name("request.prefill"):
        rid = r.attrs["request"]
        assert r.attrs == {"request": rid, "cached_tokens": 0, "calls": len(
            plan_chunks(len(reqs[rid].prompt) - 1, (8, 16)))}
    # a decode phase carries its request alone: the tokens are the root's
    assert all(set(r.attrs) == {"request"} for r in by_name("request.decode"))


def test_the_requests_that_waited_say_they_waited_for_pages(served):
    queued = {r.attrs["request"]: r for r in by_name("request.queued")}
    assert all(set(r.attrs) == {"request", "blocked_on"}
               for r in queued.values())
    held = {rid: r.attrs["blocked_on"] for rid, r in queued.items()}
    # the pool takes the first two (2 + 3 of 7 pages); four slots never
    # fill, so whoever waits, waits for pages
    assert held[0] == held[1] == "none"
    assert {held[i] for i in range(2, 6)} == {"pages"}
    first_out = min(r.end_ns for r in by_name("request"))
    for rid in range(2, 6):
        assert queued[rid].end_ns >= first_out       # not before pages freed
        assert queued[rid].duration_ns > 100 * queued[0].duration_ns


def test_serve_schedule_says_why_admission_stopped_and_counts_pages(served):
    eng, _ = served
    sched = by_name("serve.schedule")
    assert {r.attrs["blocked"] for r in sched} == {"none", "pages"}
    for r in sched:
        assert 0 <= r.attrs["pages_filled"] <= r.attrs["pages_reserved"] <= 7
    assert max(r.attrs["pages_filled"] for r in sched) > 0
    # while somebody waits for pages something is reserved, and a slot free
    for r in sched:
        if r.attrs["blocked"] == "pages":
            assert r.attrs["pages_reserved"] >= 4
    # who waits is counted after the tick's admissions: all six are sent
    # before the first tick, which admits two
    assert [r.attrs["waiting"] for r in sched][0] == 4
    assert [r.attrs["waiting"] for r in sched][-1] == 0
    assert all(r.attrs["waiting"] > 0 for r in sched
               if r.attrs["blocked"] == "pages")
    # when the last request has gone nothing is held
    assert eng.scheduler.page_counts(16) == (0, 0)
    assert eng._request_spans == {} and eng.scheduler.blocked_on == {}


def test_admission_blocked_on_slots_says_slot(toy):
    from mpi_operator_tpu.serve import EngineConfig, ServingEngine
    model, params = toy
    eng = ServingEngine(model, params, EngineConfig(
        slots=2, chunk_buckets=(8, 16), page_size=16, num_pages=40))
    spans.clear()
    eng.run(_long_requests(4))
    held = [r.attrs["blocked_on"] for r in by_name("request.queued")]
    assert sorted(held) == ["none", "none", "slot", "slot"]
    assert {r.attrs["blocked"] for r in by_name("serve.schedule")} == {
        "none", "slot"}


def test_a_gated_request_says_gate(toy):
    eng = _small_pool_engine(toy)
    spans.clear()
    ticks = {"n": 0}
    eng.scheduler.gate = lambda req: ticks["n"] > 3
    eng.start()
    for r in _long_requests(1):
        eng.submit(r)
    while eng.active:
        ticks["n"] += 1
        eng.tick()
        if ticks["n"] > 500:
            break
    eng.finish()
    (queued,) = by_name("request.queued")
    assert queued.attrs["blocked_on"] == "gate"


def test_a_withdrawn_request_closes_its_records_and_leaves_nothing(toy):
    """The router's drain takes a queued request back out: its root and
    its wait close as `withdrawn`, and neither the engine nor the scheduler
    keeps anything of it. A request still open at `finish` leaves none."""
    eng = _small_pool_engine(toy)
    spans.clear()
    reqs = _long_requests()
    eng.start()
    for r in reqs:
        eng.submit(r)
    eng.tick()                                # two admitted, four wait
    assert eng.scheduler.blocked_on[5] == "pages"
    eng.withdraw(reqs[5])
    assert reqs[5] not in eng.scheduler.queue
    assert 5 not in eng.scheduler.blocked_on and 5 not in eng._request_spans
    (root,) = by_name("request")
    (queued,) = by_name("request.queued")[-1:]
    assert root.attrs == {"request": 5, "prompt_len": len(reqs[5].prompt),
                          "tokens": 0, "finish_reason": "withdrawn"}
    assert queued.attrs == {"request": 5, "blocked_on": "pages"}
    assert queued.parent == root.id
    assert queued.duration_ns == root.duration_ns
    assert len(eng._request_spans) == 5
    eng.finish()                              # five still open
    assert eng._request_spans == {}
    assert [r.attrs["request"] for r in by_name("request")] == [5]


def test_a_timed_out_request_closes_with_its_finish_reason(toy):
    from mpi_operator_tpu.serve import Request
    eng = _small_pool_engine(toy, request_timeout=0.5)
    spans.clear()
    clock = {"now": 0.0}
    eng.start(now_fn=lambda: clock["now"])
    eng.submit(Request(id=9, prompt=list(range(1, 12)), max_new_tokens=40))
    for _ in range(6):
        eng.tick()
    clock["now"] = 1.0                        # past its deadline
    while eng.active:
        eng.tick()
    results = eng.finish()
    assert results[9].finish_reason == "timeout"
    (root,) = by_name("request")
    assert root.attrs["finish_reason"] == "timeout"
    assert root.attrs["tokens"] == len(results[9].tokens) > 0
    kids = sorted((r for r in spans.records() if r.parent == root.id),
                  key=lambda r: r.start_ns)
    assert [k.name for k in kids] == PHASES
    assert sum(k.duration_ns for k in kids) == root.duration_ns
    assert eng._request_spans == {}


def test_a_request_that_times_out_before_its_first_token_has_no_decode(toy):
    from mpi_operator_tpu.serve import Request
    eng = _small_pool_engine(toy, request_timeout=0.5)
    spans.clear()
    clock = {"now": 0.0}
    eng.start(now_fn=lambda: clock["now"])
    eng.submit(Request(id=3, prompt=list(range(1, 60)), max_new_tokens=8))
    eng.tick()                                # admitted, first chunk only
    clock["now"] = 1.0
    while eng.active:
        eng.tick()
    eng.finish()
    (root,) = by_name("request")
    kids = sorted((r for r in spans.records() if r.parent == root.id),
                  key=lambda r: r.start_ns)
    assert [k.name for k in kids] == PHASES[:2]
    assert root.attrs["finish_reason"] == "timeout"
    assert root.attrs["tokens"] == 0
    assert sum(k.duration_ns for k in kids) == root.duration_ns


# -- the trainer -------------------------------------------------------------

def test_trainer_set_up_names_shard_init_and_the_optimizers_init():
    from mpi_operator_tpu.models.transformer import (CausalLM,
                                                     TransformerConfig)
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh
    from mpi_operator_tpu.train.lm_trainer import LMTrainer, LMTrainerConfig
    model = CausalLM(TransformerConfig(
        vocab_size=128, max_len=16, num_layers=1, num_heads=2, embed_dim=32,
        mlp_dim=64, causal=True, dtype=jnp.float32))
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    trainer = LMTrainer(model, mesh, LMTrainerConfig(global_batch_size=2,
                                                     seq_len=16))
    state = trainer.init_state(jax.random.PRNGKey(0))
    assert int(state.step) == 0
    (tinit,) = by_name("train.trainer_init")
    (init,) = by_name("train.init_state")
    (shard,) = by_name("train.shard_init")
    (opt,) = by_name("train.optimizer_init")
    assert tinit.parent is None and init.parent is None
    assert shard.parent == init.id and opt.parent == init.id
    assert tinit.end_ns <= init.start_ns and shard.end_ns <= opt.start_ns
    compiled = {r.attrs["fun_name"]: r.parent
                for r in by_name("jax.compile") + by_name("jax.cache_load")}
    assert compiled.get("jit(unboxed_init)") == shard.id
    assert compiled.get("jit(init_opt)") == opt.id
