#!/usr/bin/env bash
# The blessed tier-1 gate — the command the driver runs after every PR
# (`commands` of its record of the last run, /root/TESTS_LAST_RUN.json),
# verbatim. CI and local builders invoke THIS script so there is exactly
# one definition of "the tests pass": the driver's. (ROADMAP.md's
# "Tier-1 verify" line is an older, serial statement of it that sessions
# are told to leave as it is; see the budget-history note under it.)
#
# Semantics worth knowing before editing:
#   - JAX_PLATFORMS=cpu + tests/conftest.py give 8 virtual CPU devices
#     (real XLA collectives, no TPUs needed).
#   - -m 'not slow' excludes the multi-second compile variants; the
#     `multichip` marker (tests/conftest.py) stays INCLUDED here because
#     the virtual-device mesh satisfies it, and so do the `serving` and
#     `hfta` markers (run `pytest -m hfta` to gate the fused-trainer
#     surface alone).
#   - timeout -k 10 1470 with -p xdist -n 6 --dist loadfile: six worker
#     processes, a test FILE to a worker, the whole suite inside 24.5 min
#     (1088.6 s at PR 44; serially it is some 6 000 test-seconds — see the
#     budget history note in ROADMAP.md).
#   - DOTS_PASSED is read from the junit file (--junitxml), or from the
#     progress dots of the captured log when a cut run wrote none, so the
#     driver has a pass-count even when pytest's summary line is cut off
#     by the timeout; WORKERS_DOWN counts xdist workers that died.
#
#   ./scripts/tier1.sh --resilience additionally runs the OUT-OF-PROCESS
#   preemption smoke below (real SIGTERM, real exit codes, real resume —
#   the in-process pytest e2e can't observe the exit-status contract).
#
#   ./scripts/tier1.sh --serving runs the OUT-OF-PROCESS disaggregated
#   prefill/decode A/B smoke: the same greedy trace through the
#   colocated engine and the two-pool DisaggEngine, gated on
#   token identity + the per-pool compile pins + actual KV handoffs.
#
#   ./scripts/tier1.sh --router runs the OUT-OF-PROCESS front-door
#   smoke: 2 in-process engine replicas behind the prefix-affinity
#   router on a shared-system-prompt trace, gated on token identity vs
#   the single-engine oracle, a nonzero (and A/B-higher) affinity hit
#   rate, zero sheds at low load, and >= 1 shed + clean recovery at the
#   overload burst. Budget: ~5 min of the 10-min leg timeout on a cold
#   CPU cache (mirrored in ROADMAP.md).
#
#   ./scripts/tier1.sh --elastic runs the OUT-OF-PROCESS gang-resize
#   smoke: one training run resized 4 -> 2 -> 4 CPU-host devices via
#   SIGTERM drain + resharding restore (TPU_RESHARD_RESTORE=1), gated
#   on oracle loss parity, both gang_resize records in the merged
#   timeline, the resize_seconds phase split, and nonzero goodput.
#
#   ./scripts/tier1.sh --sched runs the OUT-OF-PROCESS fleet-scheduler
#   smoke: two competing jobs on a fake 4-device pool — the real
#   FleetScheduler preempts the low-priority elastic gang 4 -> 2 to
#   admit the high-priority job, grows it back after completion —
#   gated on BOTH jobs' final losses being token-identical to solo
#   oracles, the sched_* decision records in the merged timeline, and
#   the postmortem rendering its "scheduler actions:" section.

if [ "${1:-}" = "--serving" ]; then
  # Disagg A/B smoke via the benchmark CLI (examples/serve_benchmark.py
  # --disagg): one subprocess builds both engines from the same params,
  # replays one trace through each, and prints a JSON line. On CPU the
  # latency split is structural, so the gates are the CORRECTNESS
  # contracts: greedy tokens bitwise-identical across modes, prefill
  # pool compiled zero decode steps / decode pool zero prefills, and a
  # nonzero handoff count (pages actually moved between pools).
  set -u
  dir=$(mktemp -d)
  trap 'rm -rf "$dir"' EXIT
  echo "== serving smoke: disagg vs colocated A/B =="
  env JAX_PLATFORMS=cpu python -m mpi_operator_tpu.examples.serve_benchmark \
    --disagg --size test --slots 4 --num-requests 8 --page-size 16 \
    > "$dir/disagg.json" 2> "$dir/disagg.log"
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "FAIL: disagg benchmark exited $rc"
    tail -20 "$dir/disagg.log"; exit 1
  fi
  if ! grep -q '"disagg_token_identical": true' "$dir/disagg.json"; then
    echo "FAIL: disagg tokens differ from the colocated engine's"
    cat "$dir/disagg.json"; exit 1
  fi
  if ! grep -q '"disagg_pool_pins_held": true' "$dir/disagg.json"; then
    echo "FAIL: a pool compiled the other role's program"
    cat "$dir/disagg.json"; exit 1
  fi
  if grep -q '"disagg_handoffs": 0' "$dir/disagg.json"; then
    echo "FAIL: no KV handoffs — the A/B never crossed the pool boundary"
    cat "$dir/disagg.json"; exit 1
  fi
  for key in disagg_kv_handoff_p50_ms disagg_kv_handoff_p99_ms \
             disagg_ttft_p99_ms coloc_ttft_p99_ms; do
    if ! grep -q "\"$key\":" "$dir/disagg.json"; then
      echo "FAIL: missing $key in the benchmark JSON"
      cat "$dir/disagg.json"; exit 1
    fi
  done
  # request tracing: every measured request must reconstruct as a full
  # prefill -> kv_handoff -> decode span tree, and the kv_handoff hops
  # must carry the page counts the transfer actually moved
  if ! grep -q '"disagg_trace_complete": true' "$dir/disagg.json"; then
    echo "FAIL: a disagg request's span tree is missing a hop (or a root)"
    cat "$dir/disagg.json"; exit 1
  fi
  if grep -q '"disagg_trace_handoff_pages": 0' "$dir/disagg.json"; then
    echo "FAIL: the kv_handoff hops carry zero moved pages"
    cat "$dir/disagg.json"; exit 1
  fi
  echo "serving smoke: OK (disagg A/B token-identical, pool pins held," \
       "$(grep -o '"disagg_handoffs": [0-9]*' "$dir/disagg.json" | grep -o '[0-9]*') handoffs)"
  exit 0
fi

if [ "${1:-}" = "--router" ]; then
  # Front-door smoke via the benchmark CLI (examples/serve_benchmark.py
  # --router): one subprocess builds replica fleets from the same
  # params, replays one seeded multi-tenant shared-prefix trace with
  # affinity ON vs OFF plus an overload burst, and prints a JSON line.
  # On CPU the latency split is structural, so the gates are the
  # CORRECTNESS contracts below.
  set -u
  dir=$(mktemp -d)
  trap 'rm -rf "$dir"' EXIT
  echo "== router smoke: prefix-affinity front door over 2 replicas =="
  timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m mpi_operator_tpu.examples.serve_benchmark \
    --router --size test --slots 4 --num-requests 12 --page-size 16 \
    > "$dir/router.json" 2> "$dir/router.log"
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "FAIL: router benchmark exited $rc"
    tail -20 "$dir/router.log"; exit 1
  fi
  if ! grep -q '"router_token_identical": true' "$dir/router.json"; then
    echo "FAIL: routed tokens differ from the single-engine oracle"
    cat "$dir/router.json"; exit 1
  fi
  if ! grep -q '"router_affinity_nonzero": true' "$dir/router.json"; then
    echo "FAIL: zero affinity hit rate — routing never found a warm chain"
    cat "$dir/router.json"; exit 1
  fi
  if ! grep -q '"router_affinity_hit_gain": true' "$dir/router.json"; then
    echo "FAIL: affinity routing did not beat load-only on replica-side hit rate"
    cat "$dir/router.json"; exit 1
  fi
  if ! grep -q '"router_shed_low_load": 0' "$dir/router.json"; then
    echo "FAIL: the router shed requests at low offered load"
    cat "$dir/router.json"; exit 1
  fi
  if grep -q '"router_burst_sheds": 0' "$dir/router.json"; then
    echo "FAIL: the overload burst shed nothing — admission control never fired"
    cat "$dir/router.json"; exit 1
  fi
  if ! grep -q '"router_burst_recovery_clean": true' "$dir/router.json"; then
    echo "FAIL: post-burst recovery requests did not complete cleanly"
    cat "$dir/router.json"; exit 1
  fi
  if ! grep -q '"router_compile_pins_held": true' "$dir/router.json"; then
    echo "FAIL: a replica broke the compile-count pins"
    cat "$dir/router.json"; exit 1
  fi
  # request tracing: every routed request must reconstruct as one
  # queue_wait -> admission -> prefill -> decode span tree whose hop
  # durations sum to the root e2e within tolerance
  if ! grep -q '"router_trace_complete": true' "$dir/router.json"; then
    echo "FAIL: a routed request's span tree is incomplete or gapped"
    cat "$dir/router.json"; exit 1
  fi
  echo "router smoke: OK (token-identical, hit rate" \
       "$(grep -o '"router_affinity_hit_rate": [0-9.]*' "$dir/router.json" | grep -o '[0-9.]*$') vs" \
       "$(grep -o '"router_noaffinity_hit_rate": [0-9.]*' "$dir/router.json" | grep -o '[0-9.]*$') load-only," \
       "$(grep -o '"router_burst_sheds": [0-9]*' "$dir/router.json" | grep -o '[0-9]*$') burst sheds, clean recovery)"
  # Live-scale gate: the SAME trace through one +1 attach and one -1
  # graceful drain mid-trace. Zero sheds attributable to the steps,
  # bitwise token identity held for every request (drained-replica
  # failovers included), and the measured live_scale ledger total must
  # price strictly below the same trace's gang-restart total.
  echo "== livescale smoke: +1 attach / -1 drain mid-trace vs gang restart =="
  timeout -k 10 900 env JAX_PLATFORMS=cpu \
    python -m mpi_operator_tpu.examples.serve_benchmark \
    --livescale --size test --slots 4 --num-requests 12 --page-size 16 \
    > "$dir/livescale.json" 2> "$dir/livescale.log"
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "FAIL: livescale benchmark exited $rc"
    tail -20 "$dir/livescale.log"; exit 1
  fi
  if ! grep -q '"livescale_attaches": 1' "$dir/livescale.json" \
      || ! grep -q '"livescale_detaches": 1' "$dir/livescale.json"; then
    echo "FAIL: the livescale trace did not execute exactly one attach and one detach"
    cat "$dir/livescale.json"; exit 1
  fi
  if ! grep -q '"livescale_dropped": 0' "$dir/livescale.json" \
      || ! grep -q '"livescale_sheds": 0' "$dir/livescale.json"; then
    echo "FAIL: the live scale step dropped or shed a request"
    cat "$dir/livescale.json"; exit 1
  fi
  if ! grep -q '"livescale_token_identical": true' "$dir/livescale.json" \
      || ! grep -q '"livescale_gang_token_identical": true' "$dir/livescale.json"; then
    echo "FAIL: tokens diverged from the never-scaled oracle across a scale step"
    cat "$dir/livescale.json"; exit 1
  fi
  if ! grep -q '"livescale_compile_pins_held": true' "$dir/livescale.json"; then
    echo "FAIL: a survivor (or the newcomer) recompiled across the live step"
    cat "$dir/livescale.json"; exit 1
  fi
  if ! grep -q '"livescale_ledger_vs_gang_ok": true' "$dir/livescale.json"; then
    echo "FAIL: live_scale ledger total did not beat the gang-restart total"
    cat "$dir/livescale.json"; exit 1
  fi
  # tracing across the scale steps: failed-over requests must still
  # reconstruct as ONE contiguous root each
  if ! grep -q '"livescale_trace_complete": true' "$dir/livescale.json"; then
    echo "FAIL: a live-arm request's span tree is incomplete across the scale step"
    cat "$dir/livescale.json"; exit 1
  fi
  echo "livescale smoke: OK (ledger" \
       "$(grep -o '"livescale_ledger_total_seconds": [0-9.]*' "$dir/livescale.json" | grep -o '[0-9.]*$')s live vs" \
       "$(grep -o '"livescale_gang_total_seconds": [0-9.]*' "$dir/livescale.json" | grep -o '[0-9.]*$')s gang, p99 TTFT" \
       "$(grep -o '"livescale_ttft_p99_ms": [0-9.]*' "$dir/livescale.json" | grep -o '[0-9.]*$')ms vs" \
       "$(grep -o '"livescale_gang_ttft_p99_ms": [0-9.]*' "$dir/livescale.json" | grep -o '[0-9.]*$')ms, zero drops)"
  exit 0
fi

if [ "${1:-}" = "--resilience" ]; then
  # Preemption smoke, four runs: (1) SIGTERM at step 5 → exit 215 +
  # emergency step_5; (2) resume → stop at step 8, exit 0; (3) hard
  # death (die-at-step:11) → exit 217, NO checkpoint; (4) resume from
  # step_8 → stop at step 12. The collector CLI plays the controller
  # between runs (gang_restart records), then merges controller+worker
  # logs into ONE timeline.jsonl and renders the federated goodput
  # ledger — the controller-eye view of a preempted gang, end to end.
  set -u
  dir=$(mktemp -d)
  trap 'rm -rf "$dir"' EXIT
  run_env=(env JAX_PLATFORMS=cpu
           TPU_COORDINATOR_ADDRESS=localhost:8476 TPU_NUM_PROCESSES=1)
  args=(python -m mpi_operator_tpu.examples.lm_benchmark
        --workload gpt2 --size test --batch-per-device 1 --seq-len 16
        --dtype float32 --warmup-steps 1 --num-steps 20
        --train-dir "$dir/ckpt")
  emit=("${run_env[@]}" python -m mpi_operator_tpu.telemetry.collector
        emit --log "$dir/controller.jsonl" --job smoke)
  "${emit[@]}" job_created tpus=8 || exit 1
  echo "== resilience smoke: preempt at step 5 =="
  "${run_env[@]}" TPU_FAULT_INJECT=sigterm-at-step:5 \
    "${args[@]}" > "$dir/preempt.log" 2>&1
  rc=$?
  if [ "$rc" -ne 215 ]; then
    echo "FAIL: preempted run exited $rc (want 215, the retryable band)"
    tail -20 "$dir/preempt.log"; exit 1
  fi
  if [ ! -d "$dir/ckpt/step_5" ]; then
    echo "FAIL: no emergency checkpoint at step_5"; ls "$dir/ckpt"; exit 1
  fi
  # the structured event log (telemetry/events.py, default path
  # <train-dir>/events.jsonl) must carry the drain sequence, fsync'd
  # BEFORE exit(215) — the durability contract a postmortem relies on
  if ! grep -q '"event": "preemption_drain"' "$dir/ckpt/events.jsonl"; then
    echo "FAIL: no preemption_drain record in the event log"
    cat "$dir/ckpt/events.jsonl" 2>/dev/null; exit 1
  fi
  if ! grep -q '"event": "emergency_checkpoint"' "$dir/ckpt/events.jsonl"; then
    echo "FAIL: no emergency_checkpoint record in the event log"
    cat "$dir/ckpt/events.jsonl" 2>/dev/null; exit 1
  fi
  # play the controller's role: record the restart in the controller-
  # side log the merge below folds into the job timeline
  "${emit[@]}" gang_restart exit_code=215 restart=1 || exit 1
  echo "== resilience smoke: resume to step 8 =="
  "${run_env[@]}" "${args[@]}" --num-steps 20 --stop-at-step 8 \
    > "$dir/resume.log" 2>&1
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "FAIL: resumed run exited $rc"; tail -20 "$dir/resume.log"; exit 1
  fi
  if ! grep -q "resumed from .*step_5" "$dir/resume.log"; then
    echo "FAIL: resumed run did not restore the emergency checkpoint"
    tail -20 "$dir/resume.log"; exit 1
  fi
  if [ ! -d "$dir/ckpt/step_8" ]; then
    echo "FAIL: resumed run did not reach global step 8"
    ls "$dir/ckpt"; exit 1
  fi
  # Hard-death leg: the injector die()s at step 11 — os._exit(217), NO
  # emergency checkpoint — so the resume must fall back to step_8 and
  # RE-EXECUTE steps 9-11. That re-execution is exactly what the
  # restart-aware goodput ledger charges as lost steps: the durable
  # fault_injected record (fsync'd before _exit) is the only surviving
  # evidence of the pre-death step frontier.
  echo "== resilience smoke: hard death at step 11 =="
  "${run_env[@]}" TPU_FAULT_INJECT=die-at-step:11 \
    "${args[@]}" > "$dir/die.log" 2>&1
  rc=$?
  if [ "$rc" -ne 217 ]; then
    echo "FAIL: fault-injected run exited $rc (want 217)"
    tail -20 "$dir/die.log"; exit 1
  fi
  if [ -d "$dir/ckpt/step_11" ]; then
    echo "FAIL: hard death must NOT leave a step_11 checkpoint"; exit 1
  fi
  if ! grep -q '"event": "fault_injected"' "$dir/ckpt/events.jsonl"; then
    echo "FAIL: no durable fault_injected record (the step frontier is lost)"
    exit 1
  fi
  "${emit[@]}" gang_restart exit_code=217 restart=2 || exit 1
  echo "== resilience smoke: resume to step 12 =="
  "${run_env[@]}" "${args[@]}" --num-steps 20 --stop-at-step 12 \
    > "$dir/resume2.log" 2>&1
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "FAIL: second resume exited $rc"; tail -20 "$dir/resume2.log"; exit 1
  fi
  if [ ! -d "$dir/ckpt/step_12" ]; then
    echo "FAIL: second resume did not reach global step 12"
    ls "$dir/ckpt"; exit 1
  fi
  "${emit[@]}" job_succeeded || exit 1
  # Merge controller + worker logs into the job timeline and render the
  # federated goodput series — the same code path the operator's
  # /metrics uses (telemetry/collector.py goodput_ledger).
  echo "== resilience smoke: merged timeline + goodput ledger =="
  "${run_env[@]}" python -m mpi_operator_tpu.telemetry.collector merge \
    --job smoke --controller "$dir/controller.jsonl" \
    --worker "worker-0=$dir/ckpt/events.jsonl" \
    --out "$dir/timeline.jsonl" --metrics-out "$dir/federated.prom" \
    > "$dir/merge.json" || { echo "FAIL: timeline merge"; exit 1; }
  if [ ! -s "$dir/timeline.jsonl" ]; then
    echo "FAIL: no merged timeline.jsonl"; exit 1
  fi
  # ts-order interleave: the worker's drain records must land BEFORE the
  # controller's first gang_restart in the merged file (the controller
  # only learns of the exit after the worker drained)
  drain_line=$(grep -n '"event": "preemption_drain"' "$dir/timeline.jsonl" | head -1 | cut -d: -f1)
  ckpt_line=$(grep -n '"event": "emergency_checkpoint"' "$dir/timeline.jsonl" | head -1 | cut -d: -f1)
  restart_line=$(grep -n '"event": "gang_restart"' "$dir/timeline.jsonl" | head -1 | cut -d: -f1)
  if [ -z "$drain_line" ] || [ -z "$ckpt_line" ] || [ -z "$restart_line" ]; then
    echo "FAIL: merged timeline is missing drain/checkpoint/restart records"
    cat "$dir/timeline.jsonl"; exit 1
  fi
  if [ "$drain_line" -ge "$restart_line" ] || [ "$ckpt_line" -ge "$restart_line" ]; then
    echo "FAIL: timeline not in ts order (drain=$drain_line ckpt=$ckpt_line restart=$restart_line)"
    cat "$dir/timeline.jsonl"; exit 1
  fi
  # ledger arithmetic, checkable by hand from the timeline: the hard
  # death at step 11 forced a resume from step_8 — steps 9-11 re-ran, so
  # lost=3; the run finished at step 12, so useful=12 and
  # goodput = 12/(12+3) = 0.8. The clean drain (restore step == drain
  # step) contributes NOTHING — that's the point of the ledger.
  if ! grep -Eq 'tpu_job_steps_lost_total\{job="smoke"\} 3$' "$dir/federated.prom"; then
    echo "FAIL: federated steps_lost != 3"; cat "$dir/federated.prom"; exit 1
  fi
  if ! grep -Eq 'tpu_job_goodput\{job="smoke"\} 0\.8$' "$dir/federated.prom"; then
    echo "FAIL: federated goodput != 0.8"; cat "$dir/federated.prom"; exit 1
  fi
  # the postmortem CLI must render the timeline (exit 0) and refuse an
  # empty one (nonzero — the "did the run leave a usable postmortem"
  # one-liner)
  "${run_env[@]}" python -m mpi_operator_tpu.postmortem "$dir/timeline.jsonl" \
    > "$dir/postmortem.txt" \
    || { echo "FAIL: postmortem CLI on a real timeline"; exit 1; }
  : > "$dir/empty.jsonl"
  if "${run_env[@]}" python -m mpi_operator_tpu.postmortem "$dir/empty.jsonl" \
      > /dev/null 2>&1; then
    echo "FAIL: postmortem CLI must exit nonzero on an empty timeline"
    exit 1
  fi
  echo "resilience smoke: OK (215 -> step_5 -> resume 8 -> 217 -> resume 12; timeline + goodput 0.8, lost 3)"
  exit 0
fi

#   ./scripts/tier1.sh --chaos runs the OUT-OF-PROCESS chaos soak as a
#   TWO-SEED matrix (the given seed, default 42, plus seed+1000 — two
#   independent fault/kill schedules, so a schedule-shaped bug can't
#   hide behind one lucky seed): 25 mixed job lifecycles
#   (create/restart/resize/pack/serving/teardown) against seeded API
#   fault injection (transient writes, status conflicts, stale reads,
#   dropped watch events) with the controller killed at EVERY write
#   boundary, gated on oracle convergence, zero leaked resources, and
#   zero wedged workqueue keys — PLUS the data-plane legs: scrape
#   faults (one rank hard-dark, the rest flaky) must produce a
#   DegradedGang window and ZERO restarts; a wedged serving gang must
#   be caught via the frozen token frontier within
#   progressDeadlineSeconds; request timeouts must leak zero slots and
#   zero KV pages; bursty (time-varying) scrape faults must neither trip
#   nor disarm the serving lease; a mid-trace replica kill behind
#   the router must lose zero requests; and the same kill under a
#   sample=1.0 tracer must leave every request's span tree complete
#   (zero orphans, failovers folded into their roots) — PLUS the
#   fleet-scheduler legs:
#   the priority rebalance (preempt -> admit -> grow-back) must converge
#   under crash-at-every-write with zero double-shrinks and zero lost
#   admissions, the anti-thrash gate must record an explicit sched_skip
#   instead of a resize, and the degraded-rank migration must fire at
#   most ONCE per degraded window with zero gang restarts burned.
#   Deterministic per seed; each seed's reproducer line is printed on
#   failure (and a deliberately-failing run per seed proves it).

if [ "${1:-}" = "--chaos" ]; then
  set -u
  dir=$(mktemp -d)
  trap 'rm -rf "$dir"' EXIT
  seed="${2:-42}"
  for s in "$seed" "$((seed + 1000))"; do
  echo "== chaos soak: 25 fault-injected, crash-interrupted lifecycles + data plane + scheduler (seed $s) =="
  timeout -k 10 1200 env JAX_PLATFORMS=cpu \
    python -m mpi_operator_tpu.controller.chaos \
    --seed "$s" --lifecycles 25 \
    > "$dir/chaos-$s.json" 2> "$dir/chaos-$s.log"
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "FAIL: chaos soak exited $rc (reproduce: python -m" \
         "mpi_operator_tpu.controller.chaos --seed $s --lifecycles 25)"
    tail -30 "$dir/chaos-$s.log"; cat "$dir/chaos-$s.json" 2>/dev/null
    exit 1
  fi
  if ! grep -q '"completed": 25' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s soak did not complete all 25 lifecycles"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if grep -q '"crashes": 0,' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: zero injected crashes — the kill schedule never ran"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if grep -q '"total_faults": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: zero injected faults — the fault rules never fired"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  # data-plane gates: the degraded window opened and healed with no
  # false-positive restart, the wedged serving gang was caught via the
  # token frontier, and request timeouts reclaimed every slot and page
  if ! grep -q '"false_positive_restarts": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: scrape flakiness restarted a gang (or the degraded leg never ran)"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if grep -q '"degraded_windows": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"degraded_windows":' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: no DegradedGang window under the partial partition"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if grep -q '"scrape_faults_injected": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: zero injected scrape faults — the data-plane rules never fired"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if ! grep -q '"serving_stalls_detected": 1' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: wedged serving gang not detected via the token frontier"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if ! grep -q '"leaked_pages": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"leaked_slots": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: request timeouts leaked slots or KV pages"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if grep -q '"request_timeouts": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"request_timeouts":' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: the request-timeout leg retired nothing"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  # bursty scrape faults must oscillate without a false-positive restart
  # and still catch the real post-burst stall (lease re-armed)
  if ! grep -q '"burst_false_positive_restarts": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"burst_real_stall_detected": 1' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: the bursty-scrape leg tripped the lease (or never ran)"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  # the router must survive a mid-trace replica kill with zero lost
  # requests (resubmits to survivors, token-identical replays)
  if ! grep -q '"router_failover_lost": 0' "$dir/chaos-$s.json" \
      || grep -q '"router_resubmitted": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: the router-failover leg lost or never resubmitted requests"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  # trace completeness under the same kill: every request (shed and
  # failed-over alike) must reconstruct as ONE rooted span tree with
  # zero orphaned spans, the failover riding as an event inside the
  # surviving root, and hop sums within tolerance of the root e2e
  if ! grep -q '"trace_complete_orphans": 0' "$dir/chaos-$s.json" \
      || grep -q '"trace_complete_requests": 0' "$dir/chaos-$s.json" \
      || grep -q '"trace_complete_failover_roots": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: the trace-completeness leg orphaned spans or never ran"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  # live decode-pool scaling under burst scrape faults with the
  # controller crashed at the scalingReplica marker: replay must not
  # double-apply the step (exactly 2 ledger records, zero duplicate
  # tokens, zero gang entries), and the engine-level attach/drain cycle
  # must lose nothing and reclaim every page
  if ! grep -q '"live_scale_marker_crashes": 2' "$dir/chaos-$s.json" \
      || ! grep -q '"live_scale_ledger_records": 2' "$dir/chaos-$s.json" \
      || ! grep -q '"live_scale_double_records": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"live_scale_gang_entries": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: live-scale marker replay double-applied, gang-restarted, or never ran"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if ! grep -q '"live_scale_lost": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"live_scale_shed": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"live_scale_token_mismatches": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"live_scale_leaked_pages": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: the live attach/drain cycle lost requests, diverged tokens, or leaked pages"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  # fleet-scheduler gates: the rebalance converged crash-consistently
  # (no double-shrink, no lost admission, no leak), the anti-thrash
  # cost gate recorded an explicit skip instead of a resize, and the
  # degraded-rank migration fired exactly once per window with zero
  # gang restarts burned
  if ! grep -q '"sched_double_shrinks": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"sched_admissions_lost": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"sched_leaked": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: the scheduler rebalance double-shrank, lost an admission, or leaked"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if grep -q '"sched_preempts": 0' "$dir/chaos-$s.json" \
      || grep -q '"sched_grow_backs": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: the scheduler leg never preempted or never grew back"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if grep -q '"sched_skips_recorded": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"sched_thrash_resizes": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: the anti-thrash gate resized instead of recording sched_skip"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  if ! grep -q '"sched_migrations": 1' "$dir/chaos-$s.json" \
      || ! grep -q '"sched_migrations_per_window_max": 1' "$dir/chaos-$s.json" \
      || ! grep -q '"sched_migration_restarts": 0' "$dir/chaos-$s.json" \
      || ! grep -q '"sched_restarts_burned": 0' "$dir/chaos-$s.json"; then
    echo "FAIL: seed $s: degraded-rank migration missing, repeated, or burned a restart"
    cat "$dir/chaos-$s.json"; exit 1
  fi
  # failure discipline: a soak that DOES fail must print THIS seed's
  # reproducer. Every rank dark turns the degraded leg's partition
  # total, which must trip its zero-false-positive assertion — expected
  # exit 1 with the seed named on stderr.
  echo "== chaos soak: reproducer-seed discipline (deliberate failure, seed $s) =="
  if timeout -k 10 300 env JAX_PLATFORMS=cpu \
      python -m mpi_operator_tpu.controller.chaos \
      --seed "$s" --lifecycles 0 --scrape-faults '*/fail=1' \
      > "$dir/fail-$s.json" 2> "$dir/fail-$s.log"; then
    echo "FAIL: seed $s: all-ranks-dark soak was expected to fail and did not"
    cat "$dir/fail-$s.json"; exit 1
  fi
  if ! grep -q "CHAOS SOAK FAILED" "$dir/fail-$s.log" \
      || ! grep -q "seed=$s" "$dir/fail-$s.log" \
      || ! grep -q "^reproduce: python -m mpi_operator_tpu.controller.chaos" "$dir/fail-$s.log"; then
    echo "FAIL: seed $s: failing soak did not print the reproducer seed line"
    cat "$dir/fail-$s.log"; exit 1
  fi
  echo "chaos soak seed $s: OK ($(grep -o '"crashes": [0-9]*' "$dir/chaos-$s.json" | grep -o '[0-9]*') crashes," \
       "$(grep -o '"total_faults": [0-9]*' "$dir/chaos-$s.json" | grep -o '[0-9]*') API faults," \
       "$(grep -o '"scrape_faults_injected": [0-9]*' "$dir/chaos-$s.json" | grep -o '[0-9]*$') scrape faults," \
       "$(grep -o '"sched_preempts": [0-9]*' "$dir/chaos-$s.json" | grep -o '[0-9]*$') preempts)"
  done
  echo "chaos soak: OK (2-seed matrix $seed + $((seed + 1000)): lifecycles converged, degraded windows healed, scheduler crash-consistent, zero leaks)"
  exit 0
fi

if [ "${1:-}" = "--elastic" ]; then
  # Elastic gang-resize smoke (examples/elastic_benchmark.py): three
  # subprocess phases of ONE run — 4 devices, SIGTERM at step 5, exit
  # 215 -> gang_resize -> 2 devices resuming the dp=4 checkpoint via
  # the resharding reader, SIGTERM at step 10 -> gang_resize -> 4
  # devices to step 14, exit 0 — plus a straight-through oracle. The
  # orchestrator itself gates phase exit codes, 2 completed resizes
  # with drain/restore/recompile splits, and oracle loss parity; the
  # greps below re-check the contracts from the artifacts.
  set -u
  dir=$(mktemp -d)
  trap 'rm -rf "$dir"' EXIT
  echo "== elastic smoke: 4 -> 2 -> 4 gang resize =="
  timeout -k 10 1200 env JAX_PLATFORMS=cpu \
    python -m mpi_operator_tpu.examples.elastic_benchmark \
    --out-dir "$dir" > "$dir/elastic.json" 2> "$dir/elastic.log"
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "FAIL: elastic benchmark exited $rc"
    tail -30 "$dir/elastic.log"; cat "$dir/elastic.json" 2>/dev/null
    exit 1
  fi
  if ! grep -q '"elastic_token_identical": true' "$dir/elastic.json"; then
    echo "FAIL: resumed loss differs from the straight-through oracle"
    cat "$dir/elastic.json"; exit 1
  fi
  if ! grep -q '"resharded_restores": 2' "$dir/elastic.json"; then
    echo "FAIL: a resume went through the cold path, not the resharding reader"
    cat "$dir/elastic.json"; exit 1
  fi
  if [ "$(grep -c '"event": "gang_resize"' "$dir/timeline.jsonl")" -ne 2 ]; then
    echo "FAIL: merged timeline does not carry both gang_resize records"
    cat "$dir/timeline.jsonl"; exit 1
  fi
  # the worker-side restore must log its wall time + leaf count
  if ! grep -Eq 'INFO: restored .* in [0-9.]+s \([0-9]+ leaves\)' \
      "$dir"/phase1.log; then
    echo "FAIL: no restore INFO line (wall time + leaf count) in phase 1"
    tail -20 "$dir/phase1.log"; exit 1
  fi
  if ! grep -q 'tpu_job_resize_seconds_count{job="elastic"} 2' \
      "$dir/federated.prom"; then
    echo "FAIL: resize_seconds histogram missing both resizes"
    cat "$dir/federated.prom"; exit 1
  fi
  if grep -Eq 'tpu_job_goodput\{job="elastic"\} 0(\.0+)?$' \
      "$dir/federated.prom"; then
    echo "FAIL: zero federated goodput across the resizes"
    cat "$dir/federated.prom"; exit 1
  fi
  # the postmortem renders the resize phase split + the auto-cadence hint
  env JAX_PLATFORMS=cpu python -m mpi_operator_tpu.postmortem \
    "$dir/timeline.jsonl" > "$dir/postmortem.txt" \
    || { echo "FAIL: postmortem CLI on the elastic timeline"; exit 1; }
  if ! grep -q 'gang resizes:' "$dir/postmortem.txt"; then
    echo "FAIL: postmortem does not render the gang-resize section"
    cat "$dir/postmortem.txt"; exit 1
  fi
  if ! grep -q 'suggested --stop-check-every' "$dir/postmortem.txt"; then
    echo "FAIL: postmortem missing the stop-check-every suggestion"
    cat "$dir/postmortem.txt"; exit 1
  fi
  echo "elastic smoke: OK ($(grep -o '"resize_seconds": \[[^]]*\]' "$dir/elastic.json"); token-identical, goodput intact)"
  exit 0
fi

if [ "${1:-}" = "--sched" ]; then
  # Fleet-scheduler smoke (examples/sched_benchmark.py): two competing
  # jobs on a fake 4-device pool, every decision made by the REAL
  # FleetScheduler policy object — lo (priority 0, elastic, 4 devices)
  # is preempted 4 -> 2 to admit hi (priority 1, 2 devices), hi runs
  # solo to completion, lo grows back to 4 and finishes. The
  # orchestrator itself gates phase exit codes, both plan decisions,
  # 2 completed resizes, and solo-oracle loss parity for BOTH jobs;
  # the greps below re-check the contracts from the artifacts.
  set -u
  dir=$(mktemp -d)
  trap 'rm -rf "$dir"' EXIT
  echo "== sched smoke: preempt-to-admit + grow-back on a 4-device pool =="
  timeout -k 10 1200 env JAX_PLATFORMS=cpu \
    python -m mpi_operator_tpu.examples.sched_benchmark \
    --out-dir "$dir" > "$dir/sched.json" 2> "$dir/sched.log"
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "FAIL: sched benchmark exited $rc"
    tail -30 "$dir/sched.log"; cat "$dir/sched.json" 2>/dev/null
    exit 1
  fi
  # the scheduler may cost a job TIME, never data: both final losses
  # must be token-identical to uninterrupted solo runs
  if ! grep -q '"lo_token_identical": true' "$dir/sched.json"; then
    echo "FAIL: preempted job's loss differs from its solo oracle"
    cat "$dir/sched.json"; exit 1
  fi
  if ! grep -q '"hi_token_identical": true' "$dir/sched.json"; then
    echo "FAIL: admitted job's loss differs from its solo oracle"
    cat "$dir/sched.json"; exit 1
  fi
  if ! grep -q '"action": "preempt"' "$dir/sched.json" \
      || ! grep -q '"action": "grow_back"' "$dir/sched.json"; then
    echo "FAIL: the policy object did not decide preempt then grow_back"
    cat "$dir/sched.json"; exit 1
  fi
  # the merged timeline carries the decision records (shrink + grow)
  for evt in sched_queue sched_preempt sched_admit sched_grow_back; do
    if ! grep -q "\"event\": \"$evt\"" "$dir/timeline.jsonl"; then
      echo "FAIL: merged timeline is missing the $evt record"
      cat "$dir/timeline.jsonl"; exit 1
    fi
  done
  if [ "$(grep -c '"event": "gang_resize"' "$dir/timeline.jsonl")" -ne 2 ]; then
    echo "FAIL: merged timeline does not carry both gang_resize records"
    cat "$dir/timeline.jsonl"; exit 1
  fi
  # the postmortem tells the scheduler's story, with the preempt's
  # predicted cost paired against the measured resize total
  if ! grep -q 'scheduler actions:' "$dir/postmortem.txt"; then
    echo "FAIL: postmortem does not render the scheduler-actions section"
    cat "$dir/postmortem.txt"; exit 1
  fi
  if ! grep -q 'preempt .*victim .*beneficiary .*measured' "$dir/postmortem.txt" \
      || ! grep -q 'grow back' "$dir/postmortem.txt"; then
    echo "FAIL: postmortem scheduler section missing the preempt/grow-back lines"
    cat "$dir/postmortem.txt"; exit 1
  fi
  echo "sched smoke: OK ($(grep -o '"resize_seconds": \[[^]]*\]' "$dir/sched.json"); both jobs token-identical, scheduler actions rendered)"
  exit 0
fi

set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $rc
