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


def test_many_threads_lose_no_record_and_share_no_id():
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


def test_the_log_is_bounded_and_keeps_the_newest():
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
    assert not any(r.attrs for name in TICK_ORDER + ["serve.tick"]
                   if name != "serve.decode_step" for r in by_name(name))

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
