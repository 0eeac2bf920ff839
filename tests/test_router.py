"""Serving front door (serve/router.py) + SLO autoscaling policy
(controller/autoscale.py) + the burst scrape-fault modifier.

The contracts under test:

- **Keying parity**: the router's affinity score and the replica's
  prefix-cache admission lookup walk the SAME
  ``prefix_chain_windows`` keying (PageAllocator.probe vs .lookup) —
  probe depth k promises a later lookup at least k hit pages, so a
  keying change in slots.py can never silently diverge the two sides.
- **Load wins over warmth**: a replica at its in-flight cap is
  ineligible no matter how warm its cache is; when EVERY replica is at
  cap the request sheds at the front door with finish_reason "shed"
  and zero replica contact.
- **Failover idempotence**: a dead replica's in-flight requests replay
  to survivors; results key by id, the dead replica's partials are
  dropped, so the caller sees exactly one result per request.
- **Autoscale hysteresis**: breach persistence, clear persistence, and
  the resize-cost cooldown each independently veto a scale step;
  missing observations never breach and always block scale-down.
- **Burst schedule**: ``:burst:<period>/<duty>`` oscillates a rule
  deterministically over per-rank fetch counts, and every in-burst
  injection names its window index next to the seed.
- **Live topology steps**: a detach mid-drain FAILS OVER the drained
  replica's queued requests to survivors (never sheds them); an attach
  joins only a pre-warmed engine (the compile pin) and absorbs overload
  the incumbent fleet would have shed; dispatch prefers a fresh
  heartbeat report over in-process probing and falls back when stale.
"""
import pytest

from mpi_operator_tpu.api.types import ServingSLO, ServingSpec, TPUJobSpec
from mpi_operator_tpu.api.validation import ValidationError, validate_spec
from mpi_operator_tpu.controller.autoscale import (
    DecodeAutoscaler,
    SLOObservation,
)
from mpi_operator_tpu.serve import Request, Router, RouterConfig
from mpi_operator_tpu.serve.engine import RequestResult
from mpi_operator_tpu.serve.slots import PageAllocator, prefix_chain_windows
from mpi_operator_tpu.telemetry.chaos import (
    ScrapeFaultInjector,
    ScrapeFaultRule,
)


# ---------------------------------------------------------------------------
# keying parity: router-side probe vs replica-side lookup
# ---------------------------------------------------------------------------

def _publish_chain(alloc, prompt, pages=None):
    """Prefill-publish `prompt`'s complete pages the way the engine
    does: alloc, publish under the chain key, release to the LRU."""
    parent = -1
    for window in prefix_chain_windows(prompt, alloc.page_size, pages):
        key = (parent, window)
        page = alloc._cache.get(key)
        if page is None:
            page = alloc.alloc()
            assert alloc.publish(page, parent, window)
            alloc.release(page)
        parent = alloc._cache[key]


def test_probe_matches_lookup_depth_and_counters():
    alloc = PageAllocator(num_pages=17, page_size=4)
    prompt = list(range(1, 14))                # 13 tokens -> 3 full pages
    _publish_chain(alloc, prompt)
    assert alloc.probe(prompt) == 3
    # a longer prompt sharing the prefix probes the same warm depth
    assert alloc.probe(prompt + [99, 98, 97, 96, 95]) == 3
    # a prompt diverging inside the second page keeps only page one
    assert alloc.probe([1, 2, 3, 4, 99, 6, 7, 8, 9]) == 1
    # probe touched no counters and pinned nothing
    assert (alloc.hits, alloc.misses) == (0, 0)
    assert all(r == 0 for r in alloc.ref)
    # lookup walks the identical windows: depth equals the probe's
    # promise and the hit counter moves by exactly that many pages
    chain = alloc.lookup(prompt, full_pages=3)
    assert len(chain) == 3
    assert (alloc.hits, alloc.misses) == (3, 0)
    for p in chain:
        alloc.release(p)
    alloc.check()


def test_probe_and_lookup_share_window_source():
    # both sides key off prefix_chain_windows — publishing under those
    # windows (and ONLY those windows) is sufficient for both to match,
    # for assorted prompt lengths incl. the len-1 bonus-token edge
    alloc = PageAllocator(num_pages=33, page_size=8)
    for n in (1, 7, 8, 9, 16, 17, 31):
        prompt = [n * 100 + i for i in range(n)]
        windows = prefix_chain_windows(prompt, 8)
        assert len(windows) == max(0, (n - 1) // 8)
        _publish_chain(alloc, prompt)
        assert alloc.probe(prompt) == len(windows)


# ---------------------------------------------------------------------------
# routing policy over fake replicas (no jax)
# ---------------------------------------------------------------------------

class _FakeScheduler:
    def __init__(self):
        self.queue = []

    def next_arrival(self):
        return None


class _FakeSlots:
    def __init__(self, n):
        self.free = list(range(n))


class _FakeEngine:
    """Duck-typed stand-in for ServingEngine's steppable session
    surface: submitted requests retire after `service_ticks` ticks with
    a deterministic token, publishing their prompt pages like a real
    prefill would."""

    def __init__(self, slots=4, num_pages=65, page_size=8,
                 service_ticks=1):
        self.page_allocator = PageAllocator(num_pages, page_size)
        self.scheduler = _FakeScheduler()
        self.slots = _FakeSlots(slots)
        self.service_ticks = service_ticks
        self.submitted = []
        self._work = {}
        self._results = {}

    def start(self, on_token=None, now_fn=None):
        self._results = {}

    def submit(self, req):
        self.submitted.append(req.id)
        self._work[req.id] = [req, self.service_ticks]

    @property
    def active(self):
        return bool(self._work)

    def tick(self):
        if not self._work:
            return False
        for rid in list(self._work):
            self._work[rid][1] -= 1
            if self._work[rid][1] <= 0:
                req, _ = self._work.pop(rid)
                _publish_chain(self.page_allocator, req.prompt)
                self._results[rid] = RequestResult(
                    id=rid, tokens=[sum(req.prompt) % 97], logprobs=[],
                    finish_reason="eos", ttft=0.0, token_times=[0.0],
                    cached_tokens=0, admitted_at=0.0)
        return True

    def session_results(self):
        return self._results

    def finish(self):
        return self._results


def _req(rid, prompt, arrival=0.0):
    return Request(id=rid, prompt=list(prompt), max_new_tokens=4,
                   arrival=arrival)


def test_affinity_routes_to_warm_replica():
    fakes = [_FakeEngine(), _FakeEngine()]
    prefix = list(range(1, 17))                   # 2 full pages @ 8
    _publish_chain(fakes[1].page_allocator, prefix)
    router = Router(fakes, RouterConfig())
    rep = router._pick(_req(0, prefix + [50, 51]))
    assert rep.index == 1                         # warmth beats index 0
    # affinity off: pure load, tie -> lowest index
    router_off = Router([_FakeEngine(), _FakeEngine()],
                        RouterConfig(affinity=False))
    _publish_chain(router_off.replicas[1].engine.page_allocator, prefix)
    assert router_off._pick(_req(0, prefix + [50, 51])).index == 0


def test_affinity_never_overrides_full_replica():
    fakes = [_FakeEngine(), _FakeEngine()]
    prefix = list(range(1, 17))
    _publish_chain(fakes[0].page_allocator, prefix)
    router = Router(fakes, RouterConfig(max_inflight=1))
    router.replicas[0].inflight[999] = _req(999, [1, 2, 3])
    # replica 0 is warm but AT CAP: the load filter runs before any
    # affinity scoring, so the cold survivor gets the request
    assert router._pick(_req(0, prefix + [50])).index == 1


def test_shed_semantics_end_to_end():
    fakes = [_FakeEngine(), _FakeEngine()]
    router = Router(fakes, RouterConfig(max_inflight=1))
    reqs = [_req(i, [10 + i, 11 + i, 12 + i]) for i in range(5)]
    out = router.run(reqs)
    assert set(out) == {0, 1, 2, 3, 4}
    sheds = {rid for rid, r in out.items() if r.finish_reason == "shed"}
    assert len(sheds) == 3                        # 5 due at once, 2 caps
    for rid in sheds:
        assert out[rid].tokens == [] and out[rid].ttft == -1.0
        # a shed request never touched any replica
        assert all(rid not in f.submitted for f in fakes)
    assert router.shed_count() == 3
    assert sorted(router.dispatch_counts()) == [1, 1]


def test_span_too_large_is_not_a_candidate():
    fake = _FakeEngine(num_pages=3, page_size=8)   # usable = 2 pages
    router = Router([fake], RouterConfig())
    out = router.run([_req(0, list(range(40)))])   # span > 2 pages
    assert out[0].finish_reason == "shed"
    assert fake.submitted == []


def test_failover_resubmits_and_dedups():
    fakes = [_FakeEngine(service_ticks=3), _FakeEngine(service_ticks=3)]
    calls = {"n": 0}
    real_tick = fakes[0].tick

    def dying_tick():
        calls["n"] += 1
        if calls["n"] > 1:
            raise IOError("injected")
        return real_tick()

    fakes[0].tick = dying_tick
    router = Router(fakes, RouterConfig())
    reqs = [_req(i, [20 + i, 21 + i, 22 + i]) for i in range(4)]
    out = router.run(reqs)
    assert router.dead_replicas() == [0]
    assert router.resubmitted_total >= 1
    # exactly one result per id, all completed (nothing lost, nothing
    # duplicated), every replayed id reached the survivor
    assert set(out) == {0, 1, 2, 3}
    assert all(r.finish_reason == "eos" for r in out.values())
    # every id ultimately completed on the survivor
    assert set(fakes[1].submitted) == {0, 1, 2, 3}


def test_all_replicas_dead_raises():
    fakes = [_FakeEngine(), _FakeEngine()]
    for f in fakes:
        f.tick = lambda: (_ for _ in ()).throw(IOError("down"))
    router = Router(fakes, RouterConfig())
    with pytest.raises(RuntimeError, match="every replica died"):
        router.run([_req(0, [1, 2, 3])])


def test_duplicate_request_ids_rejected():
    router = Router([_FakeEngine()], RouterConfig())
    with pytest.raises(ValueError, match="duplicate request id"):
        router.run([_req(7, [1, 2]), _req(7, [3, 4])])


def test_router_config_validation():
    with pytest.raises(ValueError):
        Router([], RouterConfig())
    with pytest.raises(ValueError):
        Router([_FakeEngine()], RouterConfig(max_inflight=0))


# ---------------------------------------------------------------------------
# live topology: attach / detach / heartbeats (no jax)
# ---------------------------------------------------------------------------

class _QueueingEngine(_FakeEngine):
    """Fake with a real admission queue: submissions wait in
    scheduler.queue until a serving lane frees (`concurrent` at a
    time) — the queued-behind-slots state a graceful drain must pull
    back and fail over."""

    def __init__(self, concurrent=1, **kw):
        super().__init__(**kw)
        self.concurrent = concurrent

    def submit(self, req):
        self.submitted.append(req.id)
        self.scheduler.queue.append(req)

    def withdraw(self, req):
        self.scheduler.queue.remove(req)

    @property
    def active(self):
        return bool(self._work or self.scheduler.queue)

    def tick(self):
        while self.scheduler.queue and len(self._work) < self.concurrent:
            req = self.scheduler.queue.pop(0)
            self._work[req.id] = [req, self.service_ticks]
        return super().tick()


class _WarmableEngine(_QueueingEngine):
    """Queueing fake that exposes compile_counts — the surface the
    attach warmup pin checks."""

    def __init__(self, step_compiles=1, **kw):
        super().__init__(**kw)
        self._step_compiles = step_compiles

    def compile_counts(self):
        return {"step": self._step_compiles, "prefill": 0}


def test_detach_mid_drain_fails_over_queued_requests():
    # scale-down RACING submission: four requests land, two queue behind
    # replica 0's single lane, then the drain begins — the queued ones
    # must fail over to the survivor (resubmit path), never shed, and
    # the resident one finishes in place on the draining replica
    fakes = [_QueueingEngine(service_ticks=2),
             _QueueingEngine(service_ticks=2)]
    router = Router(fakes, RouterConfig())
    orig_tick = fakes[0].tick
    fired = {"done": False}

    def tick_then_detach():
        r = orig_tick()
        if not fired["done"]:
            fired["done"] = True
            router.detach_replica(0)
        return r

    fakes[0].tick = tick_then_detach
    out = router.run([_req(i, [30 + i, 31 + i, 32 + i]) for i in range(4)])
    assert set(out) == {0, 1, 2, 3}
    assert all(r.finish_reason == "eos" for r in out.values())
    assert router.shed_count() == 0                 # failover, NOT shed
    assert router.resubmitted_total >= 1            # the pulled-back ones
    # a graceful exit is a detach, never a death
    assert router.detached_replicas() == [0]
    assert router.dead_replicas() == []
    assert router.active_count() == 1
    # the completed step landed in the live-scale log with its phase
    (entry,) = router.live_scale_log
    assert entry["action"] == "detach" and entry["replica"] == 0
    assert entry["drain_seconds"] >= 0.0
    assert entry["total_seconds"] == entry["drain_seconds"]
    # drained replica handed every page and slot back
    assert fakes[0].page_allocator.in_use == 0


def test_detach_verifies_reclaim_and_guards_last_replica():
    router = Router([_FakeEngine(), _FakeEngine()], RouterConfig())
    with pytest.raises(ValueError, match="no live replica"):
        router.detach_replica(7)
    router.replicas[1].alive = False
    with pytest.raises(ValueError, match="last active replica"):
        router.detach_replica(0)


def test_attach_requires_the_compile_pin():
    router = Router([_FakeEngine()], RouterConfig())
    with pytest.raises(ValueError, match="PRE-WARMED"):
        router.attach_replica(_WarmableEngine(step_compiles=0))
    # engines that don't expose compile_counts duck-pass; warmed pass
    router.attach_replica(_WarmableEngine(step_compiles=1))
    assert router.active_count() == 2


def test_attach_during_overload_absorbs_queue():
    # one replica, cap 2, four simultaneous arrivals: without the +1
    # step two requests shed at the front door (the
    # test_shed_semantics_end_to_end geometry). A pre-warmed attach at
    # t=0 absorbs the overflow instead — zero sheds, and the newcomer
    # never compiled anything new (its pin count is untouched).
    base = _QueueingEngine(service_ticks=2, concurrent=2)
    newcomer = _WarmableEngine(step_compiles=1, service_ticks=2,
                               concurrent=2)
    router = Router([base], RouterConfig(max_inflight=2))
    router.schedule_attach(0.0, newcomer, warmup_seconds=0.125)
    out = router.run([_req(i, [40 + i, 41 + i]) for i in range(4)])
    assert set(out) == {0, 1, 2, 3}
    assert router.shed_count() == 0
    assert all(r.finish_reason == "eos" for r in out.values())
    assert len(newcomer.submitted) == 2             # absorbed the overflow
    assert newcomer.compile_counts() == {"step": 1, "prefill": 0}
    (entry,) = router.live_scale_log
    assert entry["action"] == "attach"
    assert entry["warmup_seconds"] == 0.125
    assert entry["total_seconds"] == 0.125
    assert router.active_count() == 2


def test_heartbeat_preferred_until_stale():
    from mpi_operator_tpu.telemetry.worker import RouterTelemetry

    tel = RouterTelemetry()
    fakes = [_FakeEngine(), _FakeEngine()]
    router = Router(fakes, RouterConfig(affinity=False,
                                        heartbeat_interval=0.5),
                    telemetry=tel)
    # probing sees both replicas empty, but replica 0's PUBLISHED report
    # says it is buried — a fresh heartbeat must win over the probe
    tel.note_heartbeat(0, now=0.0, queue_depth=5, free_slots=0,
                       free_pages=0)
    tel.note_heartbeat(1, now=0.0, queue_depth=0, free_slots=4,
                       free_pages=64)
    assert router._pick(_req(0, [1, 2, 3]), now=0.2).index == 1
    # past the staleness threshold (2x interval) the report is dead
    # air: fall back to probing — a tie, so lowest index wins again
    assert router._pick(_req(1, [1, 2, 3]), now=5.0).index == 0
    # heartbeats off: the stored report is never consulted
    router_off = Router(fakes, RouterConfig(affinity=False), telemetry=tel)
    assert router_off._pick(_req(2, [1, 2, 3]), now=0.2).index == 0


# ---------------------------------------------------------------------------
# real-engine telemetry parity (jax)
# ---------------------------------------------------------------------------

@pytest.mark.serving
def test_affinity_hit_pages_match_replica_side_hits():
    # the router's predicted warm pages (probe at dispatch) must equal
    # the replicas' OWN prefix-cache hit counters (lookup at admission)
    # — the no-silent-divergence contract between router.py and
    # slots.py keying. Two rounds over the same fleet: round one plants
    # each tenant's pages on a distinct replica, round two re-serves
    # the tenants and every predicted page must cash in.
    import jax
    import jax.numpy as jnp
    from flax.core import meta as flax_meta
    from mpi_operator_tpu.models import CausalLM, gpt2_config
    from mpi_operator_tpu.serve import EngineConfig, ServingEngine

    cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                      vocab_size=64, max_len=64)
    model = CausalLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    params = flax_meta.unbox(
        model.init(jax.random.PRNGKey(0), probe))["params"]
    mk = lambda: ServingEngine(model, params, EngineConfig(  # noqa: E731
        slots=2, chunk_buckets=(8, 32), page_size=8,
        rng_seed=0))
    engines = [mk(), mk()]
    # 17 tokens = 2 complete pages @ 8 (+1 bonus token outside paging)
    tenant_a = [(7 * i + 3) % 60 + 1 for i in range(17)]
    tenant_b = [(5 * i + 11) % 60 + 1 for i in range(17)]

    def round_trip(prompts):
        router = Router(engines, RouterConfig())
        out = router.run([Request(id=i, prompt=p, max_new_tokens=3,
                                  arrival=0.0)
                          for i, p in enumerate(prompts)])
        assert all(r.finish_reason in ("eos", "length")
                   for r in out.values())
        return router

    hits_before = sum(e.page_allocator.hits for e in engines)
    r1 = round_trip([tenant_a, tenant_b])
    assert r1.affinity_hit_pages == 0            # cold fleet: no warmth
    hits_mid = sum(e.page_allocator.hits for e in engines)
    assert hits_mid == hits_before               # ...and no cache hits
    # round two: same 2 full pages per tenant, fresh bonus tails
    r2 = round_trip([tenant_a[:16] + [61], tenant_b[:16] + [62]])
    hit_delta = sum(e.page_allocator.hits for e in engines) - hits_mid
    assert r2.affinity_hit_pages == hit_delta == 4
    assert r2.affinity_hit_rate() == 1.0
    # the warm rounds routed each tenant back to its planted replica
    assert sorted(r2.dispatch_counts()) == [1, 1]


# ---------------------------------------------------------------------------
# DecodeAutoscaler policy (pure)
# ---------------------------------------------------------------------------

def _slo(**kw):
    base = dict(ttft_p99_seconds=1.0, min_decode_replicas=1,
                max_decode_replicas=8, breach_seconds=30.0,
                clear_seconds=60.0, cooldown_multiplier=4.0,
                cooldown_floor_seconds=10.0)
    base.update(kw)
    return ServingSLO(**base)


def test_breach_must_persist_before_scale_up():
    sc = DecodeAutoscaler(_slo())
    bad = SLOObservation(ttft_p99=2.0)
    d = sc.decide(100.0, bad, current=2, last_scaled_at=None,
                  last_resize_seconds=None)
    assert d.target is None and d.wake_after == pytest.approx(30.0)
    d = sc.decide(115.0, bad, current=2, last_scaled_at=None,
                  last_resize_seconds=None)
    assert d.target is None                      # held 15s < 30s
    d = sc.decide(130.0, bad, current=2, last_scaled_at=None,
                  last_resize_seconds=None)
    assert d.target == 3 and "ttft_p99" in d.reason


def test_one_good_scrape_resets_the_breach_timer():
    sc = DecodeAutoscaler(_slo())
    bad, good = SLOObservation(ttft_p99=2.0), SLOObservation(ttft_p99=0.5)
    sc.decide(0.0, bad, 2, None, None)
    sc.decide(20.0, good, 2, None, None)         # breach clears
    d = sc.decide(25.0, bad, 2, None, None)      # a NEW breach window
    assert d.target is None
    d = sc.decide(54.0, bad, 2, None, None)
    assert d.target is None                      # 29s into the new window
    assert sc.decide(55.0, bad, 2, None, None).target == 3


def test_cooldown_scales_with_measured_resize_cost():
    sc = DecodeAutoscaler(_slo())
    assert sc.cooldown_seconds(None) == 10.0     # floor until measured
    assert sc.cooldown_seconds(90.0) == 360.0    # 4 x the gang resize
    bad = SLOObservation(ttft_p99=2.0)
    sc.decide(0.0, bad, 2, None, 90.0)
    d = sc.decide(40.0, bad, 2, last_scaled_at=35.0,
                  last_resize_seconds=90.0)
    assert d.target is None and "cooling" in d.reason
    assert d.wake_after == pytest.approx(355.0)
    d = sc.decide(35.0 + 360.0, bad, 2, last_scaled_at=35.0,
                  last_resize_seconds=90.0)
    assert d.target == 3


def test_scale_up_clamped_at_max():
    sc = DecodeAutoscaler(_slo(max_decode_replicas=2))
    bad = SLOObservation(ttft_p99=2.0)
    sc.decide(0.0, bad, 2, None, None)
    d = sc.decide(31.0, bad, 2, None, None)
    assert d.target is None and "maxDecodeReplicas" in d.reason


def test_missing_observation_never_breaches_and_blocks_clear():
    sc = DecodeAutoscaler(_slo())
    dark = SLOObservation()                      # no data at all
    d = sc.decide(0.0, dark, 2, None, None)
    assert d.target is None and "insufficient" in d.reason
    # an hour of darkness still never scales in either direction
    d = sc.decide(3600.0, dark, 2, None, None)
    assert d.target is None


def test_partial_evidence_blocks_scale_down():
    sc = DecodeAutoscaler(_slo(tpot_p99_seconds=0.1))
    # ttft observed and clear, tpot configured but dark -> hold
    d = sc.decide(0.0, SLOObservation(ttft_p99=0.2), 3, None, None)
    assert d.target is None and "insufficient" in d.reason


def test_clear_must_persist_then_scales_down():
    sc = DecodeAutoscaler(_slo())
    good = SLOObservation(ttft_p99=0.2)
    d = sc.decide(0.0, good, 3, None, None)
    assert d.target is None and d.wake_after == pytest.approx(60.0)
    d = sc.decide(59.0, good, 3, None, None)
    assert d.target is None
    d = sc.decide(61.0, good, 3, None, None)
    assert d.target == 2


def test_scale_down_clamped_at_min():
    sc = DecodeAutoscaler(_slo())
    good = SLOObservation(ttft_p99=0.2)
    for t in (0.0, 61.0, 200.0):
        assert sc.decide(t, good, 1, None, None).target is None


def test_queue_depth_target_breaches():
    sc = DecodeAutoscaler(_slo(ttft_p99_seconds=None, queue_depth=4.0))
    deep = SLOObservation(queue_depth=9.0)
    sc.decide(0.0, deep, 2, None, None)
    d = sc.decide(30.0, deep, 2, None, None)
    assert d.target == 3 and "queue_depth" in d.reason


# ---------------------------------------------------------------------------
# spec.serving.slo validation
# ---------------------------------------------------------------------------

def _serving_spec(slo):
    return TPUJobSpec(tpus=8, serving=ServingSpec(
        prefill_replicas=1, decode_replicas=1, slo=slo))


def test_slo_validation():
    validate_spec(_serving_spec(ServingSLO(ttft_p99_seconds=0.5)))
    with pytest.raises(ValidationError, match="at least one target"):
        validate_spec(_serving_spec(ServingSLO()))
    with pytest.raises(ValidationError, match="must be > 0"):
        validate_spec(_serving_spec(ServingSLO(ttft_p99_seconds=-1.0)))
    with pytest.raises(ValidationError, match="maxDecodeReplicas"):
        validate_spec(_serving_spec(ServingSLO(
            ttft_p99_seconds=0.5, min_decode_replicas=4,
            max_decode_replicas=2)))
    with pytest.raises(ValidationError, match="inside the slo band"):
        validate_spec(_serving_spec(ServingSLO(
            ttft_p99_seconds=0.5, min_decode_replicas=2,
            max_decode_replicas=4)))
    with pytest.raises(ValidationError, match="breachSeconds"):
        validate_spec(_serving_spec(ServingSLO(
            ttft_p99_seconds=0.5, breach_seconds=-1.0)))


# ---------------------------------------------------------------------------
# burst scrape-fault schedule
# ---------------------------------------------------------------------------

def test_burst_rule_parse_and_validation():
    r = ScrapeFaultRule.parse("*/fail=0.6:burst:8/0.25")
    assert (r.rate, r.burst_period, r.burst_duty) == (0.6, 8, 0.25)
    assert ScrapeFaultRule.parse("3/delay=0.2").burst_period is None
    for bad in ("*/fail=0.5:burst:8", "*/fail=0.5:burst:x/0.5",
                "*/fail=0.5:gust:8/0.5"):
        with pytest.raises(ValueError):
            ScrapeFaultRule.parse(bad)
    with pytest.raises(ValueError, match="duty"):
        ScrapeFaultRule.parse("*/fail=0.5:burst:8/1.0")
    with pytest.raises(ValueError, match="period"):
        ScrapeFaultRule.parse("*/fail=0.5:burst:1/0.5")


def test_burst_phasing_is_a_square_wave():
    r = ScrapeFaultRule.parse("*/fail=1.0:burst:4/0.5")
    assert [r.live(i) for i in range(8)] == [True, True, False, False,
                                             True, True, False, False]
    assert [r.burst_index(i) for i in range(8)] == [0, 0, 0, 0,
                                                    1, 1, 1, 1]


def test_burst_messages_name_their_window():
    inj = ScrapeFaultInjector(["*/fail=1.0:burst:4/0.5"], seed=9)
    seen = []
    for i in range(8):
        try:
            inj.fetch(0, "u", lambda u: "ok")
            seen.append(None)
        except IOError as exc:
            seen.append(str(exc))
    assert seen[0] and "(seed=9, burst=0)" in seen[0]
    assert seen[4] and "(seed=9, burst=1)" in seen[4]
    assert seen[2] is None and seen[3] is None      # silent phase
    assert inj.burst_windows_hit() == 2
    # static rules keep the bare seed tag (no burst index)
    inj2 = ScrapeFaultInjector(["*/fail=1.0"], seed=9)
    with pytest.raises(IOError, match=r"\(seed=9\)$"):
        inj2.fetch(0, "u", lambda u: "ok")


def test_burst_schedule_is_deterministic_per_seed():
    def seq(seed):
        inj = ScrapeFaultInjector(["*/fail=0.5:burst:4/0.5"], seed=seed)
        out = []
        for i in range(32):
            try:
                inj.fetch(0, "u", lambda u: "ok")
                out.append("ok")
            except IOError as exc:
                out.append(str(exc))
        return out

    assert seq(3) == seq(3)
    assert seq(3) != seq(4)


def test_burst_silent_phase_rolls_no_randomness():
    # a second, always-live rule must see the SAME roll stream whether
    # the burst rule is in its storm or its calm — the burst phase is
    # decided by counters, never by consuming rng
    rules = ["0/fail=1.0:burst:2/0.4", "*/delay=0.0000001"]
    inj = ScrapeFaultInjector(rules, seed=5)
    # rank 1 never matches the burst rule; its delay rolls come straight
    # off the shared rng in fetch order regardless of rank 0's phase
    for i in range(6):
        inj.fetch(1, "u1", lambda u: "ok")
    assert inj.fault_count("delay") == 0


# ---------------------------------------------------------------------------
# controller integration: status-override scale-up
# ---------------------------------------------------------------------------

def test_autoscale_scale_up_lands_in_status_and_pools():
    from mpi_operator_tpu.controller.chaos import _observed_harness

    qd = {"v": 0.0}

    def fetch(url):
        if url.endswith("/metrics"):
            return f"tpu_worker_queue_depth {qd['v']}\n"
        raise IOError("no events endpoint")

    h, obs, clock = _observed_harness(0, fetch)
    # the autoscaler's persistence windows read controller time; pin it
    # to the same fake clock the observatory scrapes on
    h.controller.now = lambda: clock["now"]
    name = "as-up"
    slo = ServingSLO(queue_depth=4.0, breach_seconds=30.0,
                     clear_seconds=600.0, cooldown_floor_seconds=0.0,
                     max_decode_replicas=4)
    h.create_job(name, tpus=8, serving=ServingSpec(
        prefill_replicas=1, decode_replicas=1, slo=slo))
    h.drive_until(lambda: len(h.worker_sets(name)) == 2,
                  f"{name}: prefill+decode pools")
    h.make_workers_ready(name)
    h.drive_until(lambda: h.launcher(name) is not None, f"{name}: launcher")
    h.set_launcher_active(name)
    h.drive_until(lambda: h.cond(name, "Running") == "True",
                  f"{name}: Running")
    sync = lambda: h.controller.sync_handler(f"{h.ns}/{name}")  # noqa: E731
    # healthy queue: no override appears no matter how long we watch
    for _ in range(4):
        clock["now"] += 15
        sync()
        h.resync()
    assert h.job(name).status.serving_decode_replicas is None
    # the queue blows past the target and STAYS there past breachSeconds
    qd["v"] = 9.0
    for _ in range(4):
        clock["now"] += 15
        sync()
        h.resync()
    job = h.job(name)
    assert job.status.serving_decode_replicas == 2
    assert job.status.serving_scaled_at is not None
    # the override flows into the decode pool via the ordinary resize
    # machinery: the user's spec is untouched, the StatefulSet grows
    assert job.spec.serving.decode_replicas == 1
    h.drive_until(lambda: any(
        s.metadata.name.endswith("-decode") and s.spec.replicas == 2
        for s in h.worker_sets(name)), f"{name}: decode pool resized")
