#!/usr/bin/env python
"""Headline benchmark: ResNet-101, synthetic ImageNet — the reference's
published workload (reference README.md:97-133: 132.1 images/sec per GPU,
264.26 aggregate on 2 GPUs, fp32, batch 64/GPU, 100 steps).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N/132.1}

vs_baseline is per-device throughput against the reference's 132.1
images/sec-per-device number (BASELINE.md). Note the default batch here is
256/device (the v5e throughput sweet spot), not the reference's 64 — the
ratio compares each system at its own best operating point; pass
--batch-per-device 64 for the like-for-like config.

Without --smoke this is a measurement and needs a TPU: it exits non-zero
at start when jax's platform is anything else, and a leg that raises makes
the whole run exit non-zero after the remaining legs have run (each leg's
JSONL record, failures included, is on disk by then). --smoke is the
tiny CPU run CI uses to check the harness; its numbers are not device
numbers. One process holds the chip: nothing here probes the backend
from a child, and the legs that start children (gpt2_elastic) force them
to the CPU.
"""
import argparse
import json
import os
import signal
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REFERENCE_PER_DEVICE_IPS = 132.1      # ref README.md:113-125

# Signal-flush channel (BENCH_r05: rc=124, parsed=null — the external
# harness SIGTERMed the ladder and the summary line never printed, so
# every completed leg was invisible to the driver). main() parks the
# in-progress summary dict and its finish() here; the SIGTERM/SIGALRM
# handler flushes whatever legs completed, then exits 0 — a partial
# record beats a null one.
_SUMMARY_STATE = {"line": None, "finish": None, "done": False}


def _flush_on_signal(signum, frame):
    del frame
    name = signal.Signals(signum).name
    print(f"# {name}: flushing summary from completed legs", file=sys.stderr)
    line = _SUMMARY_STATE["line"]
    fin = _SUMMARY_STATE["finish"]
    if fin is not None and line is not None:
        line["interrupted"] = name
        fin(line)
    elif not _SUMMARY_STATE["done"]:
        print(json.dumps({"metric": "bench_interrupted", "value": None,
                          "unit": "none", "vs_baseline": 0.0,
                          "interrupted": name}))
    sys.stdout.flush()
    # plain exit: atexit/finally in a leg mid-flight could hang or
    # double-print; the record is already out
    os._exit(0)

def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="all",
                        choices=["all", "resnet", "gpt2", "bert", "vit",
                                 "llama", "moe", "allreduce", "generate",
                                 "serving"],
                        help="all = the FULL BASELINE ladder in one line "
                             "(the driver default): resnet headline + "
                             "gpt2/bert/llama/vit/moe/long-seq/decode/"
                             "serving legs; individual names run one leg; "
                             "allreduce = the scaling-efficiency "
                             "microbenchmark (BASELINE ≥90%% 4→32); "
                             "generate = KV-cache decode throughput; "
                             "serving = continuous batching vs sequential "
                             "generate() over a mixed-length trace")
    parser.add_argument("--model", default="resnet101")
    # resnet default 256/device is the single-chip throughput sweet spot on
    # v5e (measured: 64→1377, 128→1408, 256→1612, 512→1442 img/s); the
    # reference's own config (batch 64/GPU) is still reproducible via
    # --batch-per-device 64. LM workloads default to 16 (seq 512).
    parser.add_argument("--batch-per-device", type=int, default=None)
    parser.add_argument("--steps", type=int, default=100)     # ref README.md:89
    parser.add_argument("--warmup", type=int, default=10)
    parser.add_argument("--image-size", type=int, default=224)
    # conv7 default: vs_baseline divides by the reference's conv7-stem
    # number, so the headline must run the same stem or the ratio mixes
    # a stem swap into what reads as a framework speedup. The faster s2d
    # stem stays one flag away and reports under the same metric name
    # only when explicitly requested.
    parser.add_argument("--stem", default="conv7", choices=["s2d", "conv7"],
                        help="resnet stem: conv7 (default) = the "
                             "reference 7x7/s2 + maxpool (like-for-like "
                             "for vs_baseline); s2d = 4x4 space-to-depth "
                             "+ dense 2x2 conv (MXU-fed; +4.7%% img/s "
                             "measured)")
    parser.add_argument("--jsonl", default="bench_legs.jsonl",
                        help="per-leg JSONL path: one {'leg': ...} record "
                             "is appended and fsync'd after EVERY "
                             "measured leg, so a ladder killed mid-run "
                             "still leaves the finished legs parseable "
                             "on disk ('' disables)")
    parser.add_argument("--decode-legs", default=None,
                        help="comma-separated decode-leg prefixes to run "
                             "(default: all); the mid-kill harness test "
                             "uses this to shrink the ladder")
    parser.add_argument("--events-log", default="",
                        help="route every leg's worker event records "
                             "(drains, checkpoints, restores, faults) "
                             "into ONE shared events.jsonl; the summary "
                             "line then carries the restart-aware goodput "
                             "ledger over it, and the file feeds "
                             "python -m mpi_operator_tpu.postmortem "
                             "('' disables — the default)")
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--smoke", action="store_true",
                        help="tiny CPU config for CI/verification")
    # default 1800 (was 2400, before that 3000): the budget only gates
    # leg STARTS, so a leg launched near the budget edge still runs to
    # completion — r06 hit rc=124 with 2400 because the trailing legs it
    # admitted overshot the 3600s external timeout. 1800 + the shorter
    # per-leg step counts below leave the worst-case ladder tail
    # (one long leg + finish()) inside the timeout with real headroom.
    parser.add_argument("--budget-seconds", type=int, default=1800,
                        help="wall-clock budget for the --workload all "
                             "ladder: once exceeded, remaining legs are "
                             "marked *_skipped instead of running, so "
                             "the JSON record always lands inside the "
                             "driver's timeout (legs run most-important "
                             "first)")
    args = parser.parse_args()

    # External kills become partial records instead of nulls; the alarm
    # is the in-process backstop for a leg that blows through the budget
    # (it only gates starts) — fire while there's still headroom before
    # any external timeout.
    signal.signal(signal.SIGTERM, _flush_on_signal)
    signal.signal(signal.SIGALRM, _flush_on_signal)
    signal.alarm(args.budget_seconds + 300)

    _legs_written = [0]
    # platform / device_kind / device_count as jax reports them, filled
    # once the backend is up; every record written below carries them
    device = {}

    def emit_leg(prefix, fields):
        """Append one {"leg": ...} record to --jsonl, flushed + fsync'd.
        The summary JSON line prints only at ladder end; this is the
        crash-safe record — a leg measured minutes before a mid-ladder
        kill must still be parseable on disk, and a parser should prefer
        these records (summary carries jsonl_path) when both exist."""
        if not args.jsonl:
            return
        try:
            with open(args.jsonl, "a") as fh:
                fh.write(json.dumps({"leg": prefix, **fields, **device})
                         + "\n")
                fh.flush()
                os.fsync(fh.fileno())
            _legs_written[0] += 1
        except OSError as exc:
            print(f"# jsonl write failed for {prefix}: {exc!r}",
                  file=sys.stderr)

    failed_legs = []

    def guarded(line, prefix, fn):
        """Run one ladder leg. A leg that raises is recorded — its
        `<prefix>_error` field in the summary and a JSONL record — and
        the ladder goes on, so a late leg's OOM does not discard numbers
        measured minutes earlier; but the run exits non-zero at the end
        (finish), because a failed leg is a failure. A preemption drain
        keeps its retryable exit code: swallowing it would lose the gang
        restart."""
        from mpi_operator_tpu.train.resilience import Preempted
        try:
            fn()
        except Preempted:
            raise
        except Exception as exc:  # noqa: BLE001 — ladder boundary
            import traceback
            traceback.print_exc()
            print(f"# {prefix} bench leg failed: {exc!r}", file=sys.stderr)
            line[f"{prefix}_error"] = type(exc).__name__
            emit_leg(prefix, {f"{prefix}_error": type(exc).__name__})
            failed_legs.append(prefix)

    def finish(line) -> int:
        """Print the summary line; the process exit code."""
        if _SUMMARY_STATE["done"]:
            return 1                # signal flush already printed it
        _SUMMARY_STATE["done"] = True
        if failed_legs:
            line["failed_legs"] = list(failed_legs)
        line.update(device)
        if _legs_written[0]:
            line["jsonl_path"] = os.path.abspath(args.jsonl)
        # restart-aware goodput over the shared event log: all legs fed
        # one file, so the ledger sees any drain→restore re-execution a
        # preempted/retried run cost the ladder (1.0 on a clean pass)
        if args.events_log and os.path.exists(args.events_log):
            try:
                from mpi_operator_tpu.telemetry import (goodput_ledger,
                                                        read_events)
                ledger = goodput_ledger(read_events(args.events_log))
                line["events_log"] = os.path.abspath(args.events_log)
                line["steps_lost"] = ledger["lost_steps"]
                line["restart_goodput"] = round(ledger["goodput"], 4)
            except Exception as exc:
                print(f"# goodput ledger failed: {exc!r}", file=sys.stderr)
        print(json.dumps(line))
        return 1 if failed_legs else 0

    _SUMMARY_STATE["finish"] = finish

    if args.smoke:
        from mpi_operator_tpu.utils.hostplatform import force_host_platform
        force_host_platform(8)

    import jax
    if not args.smoke and jax.default_backend() != "tpu":
        # a measurement that found no chip must not time the CPU and
        # print the result under a device metric's name
        sys.exit(f"bench.py: needs a TPU, jax found platform "
                 f"{jax.default_backend()!r} (use --smoke for the CPU "
                 f"harness check)")
    from mpi_operator_tpu.examples._report import device_record
    from mpi_operator_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    device.update(device_record())
    if args.smoke:
        args.model = "resnet18"
        args.batch_per_device = 2
        args.steps = 4
        args.warmup = 1
        args.image_size = 64
    if args.batch_per_device is None:
        # per-workload single-v5e sweet spots (swept on the chip)
        args.batch_per_device = {
            "gpt2": 16, "bert": 16, "moe": 16, "llama": 8,
        }.get(args.workload, 256)

    def run_lm(workload, steps, warmup, batch=None, seq=None, size=None,
               **kw):
        from mpi_operator_tpu.examples.lm_benchmark import run_lm_benchmark
        if args.smoke:
            size = "test"
        # measured single-v5e sweet spots (gpt2-medium): seq 2048 wants
        # batch 4 NO remat + the kernel's 1024-tile auto policy — 34.4k
        # tok/s / 42.5% MFU, up from r02's 27.1k / 33%. seq 512: batch 16
        # NO remat — 44.5k tok/s (49.7% MFU) vs 39.4k with dots-remat and
        # 43.2k at batch 24; batch 32 no-remat OOMs. Flash attention +
        # bf16 LM head leave enough HBM that recompute buys nothing at
        # seq 512 (long-seq runs still want --remat).
        _state, metrics = run_lm_benchmark(
            workload=workload, size=size,
            batch_per_device=2 if args.smoke else (batch or 16),
            seq_len=32 if args.smoke else (seq or 512),
            num_steps=steps, warmup_steps=warmup,
            remat=False, event_log=args.events_log or None,
            dtype_name=args.dtype, log=lambda s: print(s, file=sys.stderr),
            **kw)
        del _state
        return metrics

    def mfu_fields(metrics):
        out = {}
        if metrics.get("mfu") is not None:
            out["mfu"] = round(metrics["mfu"], 4)
        if metrics.get("tflops_per_sec_per_device") is not None:
            out["tflops_per_sec_per_device"] = round(
                metrics["tflops_per_sec_per_device"], 2)
        # step-time tail from the telemetry histograms (trainers return
        # these since the telemetry PR) — every ladder leg carries its
        # p50/p99 so a throughput regression can be told apart from a
        # tail-latency one without rerunning
        for k in ("step_time_p50_ms", "step_time_p99_ms",
                  "host_gap_p50_ms", "host_gap_p99_ms"):
            if metrics.get(k) is not None:
                out[k] = round(metrics[k], 3)
        if metrics.get("goodput") is not None:
            out["goodput"] = round(metrics["goodput"], 4)
        return out

    if args.workload in ("gpt2", "bert", "llama", "moe"):
        line = {
            "metric": f"{args.workload}_tokens_per_sec",
            "value": None,
            "unit": "tokens/sec",
            "vs_baseline": 0.0,     # reference publishes no LM numbers
        }
        _SUMMARY_STATE["line"] = line
        if args.workload == "moe":
            # expert-capacity MoE on one chip (ep=1): MFU + the drop rate
            # the router's capacity dispatch actually loses
            metrics = run_lm("gpt2", args.steps, args.warmup,
                             batch=args.batch_per_device,
                             size=None if args.smoke else "small",
                             moe_experts=8)
        else:
            metrics = run_lm(args.workload, args.steps, args.warmup,
                             batch=args.batch_per_device)
        line.update({
            "value": round(metrics["tokens_per_sec"], 0),
            **mfu_fields(metrics),
        })
        if metrics.get("moe_drop_rate") is not None:
            line["moe_drop_rate"] = round(metrics["moe_drop_rate"], 4)
        emit_leg(args.workload, line)
        return finish(line)

    def decode_leg(family, kv_cache_dtype=None, runs=2, batch=None):
        """Median-of-N decode throughput with spread (a single run says
        nothing about run-to-run variance). Returns (median_tps, spread,
        mbu, impl) — MBU is the bandwidth roofline (bytes/step ÷ the
        chip's HBM peak, utils/flops.py), impl the decode implementation
        the step actually traced."""
        from mpi_operator_tpu.examples.lm_benchmark import (
            run_generate_benchmark)

        def one_run(num_iters):
            return run_generate_benchmark(
                size="test" if args.smoke else None,
                family=family,
                kv_cache_dtype=kv_cache_dtype,
                batch=2 if args.smoke else (batch or 8),
                prompt_len=16 if args.smoke else 128,
                new_tokens=8 if args.smoke else 128,
                num_iters=num_iters,
                dtype_name=args.dtype,
                log=lambda s: print(s, file=sys.stderr))

        # Explicit warmup with the SAME shapes/dtypes (batch, lengths, kv
        # dtype all identical -> the same executables): every cache-shape
        # or dtype change recompiles prefill+decode, and r05's first gpt2
        # run reported 2645 tok/s vs 4748 steady-state because compile +
        # cold dispatch leaked into run 1. One cheap single-iter pass
        # eats that here, so EVERY measured run below is steady-state
        # (previously the first full-length run was measured then
        # discarded — 8 iterations spent paying for what 1 buys).
        vals = []
        if not args.smoke:
            one_run(num_iters=1)
        for _ in range(1 if args.smoke else runs):
            gm = one_run(num_iters=1 if args.smoke else 8)
            vals.append((gm["decode_tokens_per_sec"], gm.get("mbu")))
            impl = gm.get("decode_impl")
        vals.sort(key=lambda v: v[0])
        median, med_mbu = vals[len(vals) // 2]
        spread = ((vals[-1][0] - vals[0][0]) / median) if median else 0.0
        return (round(median, 0), round(spread, 3),
                round(med_mbu, 4) if med_mbu is not None else None,
                impl)

    def decode_fields(line, prefix, family, kv_cache_dtype=None,
                      batch=None):
        med, spread, mbu_val, impl = decode_leg(
            family, kv_cache_dtype=kv_cache_dtype, batch=batch)
        fields = {f"{prefix}_tokens_per_sec": med,
                  f"{prefix}_spread": spread}
        if mbu_val is not None:
            fields[f"{prefix}_mbu"] = mbu_val
        fields[f"{prefix}_impl"] = impl
        line.update(fields)
        emit_leg(prefix, fields)
        return med

    # primary decode legs (MBU rooflines, batch 8) vs the batch-scaling
    # sweep (batch ∈ {8, 32, 64} with the primary llama leg as the b8
    # point): decode shifts from bandwidth- to compute-bound as the batch
    # amortizes the param reads; the sweep shows where this chip sits on
    # that curve with the Pallas decode kernel engaged (each leg records
    # a *_impl field), and runs LAST — sweep extras must never
    # budget-starve vit
    DECODE_LEGS = (
        ("gpt2_decode", dict(family="gpt2")),
        ("llama_decode", dict(family="llama")),
        ("llama_int8kv_decode", dict(family="llama",
                                     kv_cache_dtype="int8")),
    )
    DECODE_SWEEP_LEGS = (
        ("llama_decode_b32", dict(family="llama", batch=32)),
        ("llama_decode_b64", dict(family="llama", batch=64)),
        ("llama_int8kv_decode_b32", dict(family="llama",
                                         kv_cache_dtype="int8", batch=32)),
        ("llama_int8kv_decode_b64", dict(family="llama",
                                         kv_cache_dtype="int8", batch=64)),
    )

    def run_decode_legs(line, skip_check=None,
                        legs=DECODE_LEGS + DECODE_SWEEP_LEGS):
        # per-leg isolation everywhere decode runs: a late leg's OOM must
        # not discard the numbers measured minutes earlier; skip_check
        # (the --workload all wall-clock budget) may drop trailing legs
        if args.decode_legs is not None:
            wanted = {s.strip() for s in args.decode_legs.split(",")}
            legs = tuple(leg for leg in legs if leg[0] in wanted)
        for prefix, dkw in legs:
            if skip_check is not None and skip_check(prefix):
                continue
            guarded(line, prefix,
                    lambda: decode_fields(line, prefix, **dkw))

    def serving_metrics():
        # continuous-batching engine vs trace-sequential generate(): the
        # serving numbers a decode-throughput leg can't show (TTFT/TPOT
        # percentiles under mixed-length arrivals + the no-recompile
        # contract). Smoke shrinks the trace and model, not the shape of
        # the measurement.
        from mpi_operator_tpu.examples.serve_benchmark import (
            run_serving_benchmark)
        return run_serving_benchmark(
            size="test" if args.smoke else None,
            slots=4 if args.smoke else 8,
            num_requests=8 if args.smoke else 32,
            prompt_grid=(8, 16, 24) if args.smoke else (32, 64, 128),
            # decode-heavy smoke: the async-vs-sync A/B's win scales
            # with decode steps (host work hidden per step), so a
            # 4-8-token trace measures only prefill + noise
            new_grid=(16, 32) if args.smoke else (32, 64),
            chunk_buckets=(8, 16) if args.smoke else (32, 128),
            dtype_name=args.dtype,
            compare_sync=True,
            log=lambda s: print(s, file=sys.stderr))

    def serving_paged_metrics():
        # the engine over a shared-system-prompt trace: every
        # request carries the same seeded prefix, so the first wave
        # prefills it cold and publishes while later waves pin the shared
        # pages — prefix_hit_rate, cold-vs-hit TTFT, and page-occupancy
        # peaks land in the JSONL under serving_paged_*. No sequential
        # baseline rerun (the serving leg already priced that); the
        # serving leg in the same line is the trace with no shared prefix.
        from mpi_operator_tpu.examples.serve_benchmark import (
            run_serving_benchmark)
        m = run_serving_benchmark(
            size="test" if args.smoke else None,
            slots=4 if args.smoke else 8,
            num_requests=8 if args.smoke else 32,
            prompt_grid=(8, 16, 24) if args.smoke else (32, 64, 128),
            new_grid=(16, 32) if args.smoke else (32, 64),
            chunk_buckets=(8, 16) if args.smoke else (32, 128),
            dtype_name=args.dtype,
            page_size=16 if args.smoke else 64,
            shared_prefix_len=16 if args.smoke else 128,
            baseline=False,
            log=lambda s: print(s, file=sys.stderr))
        return {k.replace("serving_", "serving_paged_", 1): v
                for k, v in m.items()}

    def serving_disagg_metrics():
        # disaggregated prefill/decode A/B at equal chip count: the same
        # long-prompt-heavy greedy trace through a colocated
        # engine and the two-pool DisaggEngine, TTFT/TPOT p50/p99 for
        # both plus kv_handoff p50/p99 and the token-identity + per-pool
        # compile-pin gates. Keys already carry the disagg_/coloc_
        # prefixes — no rewrite needed.
        from mpi_operator_tpu.examples.serve_benchmark import (
            run_disagg_benchmark)
        return run_disagg_benchmark(
            size="test" if args.smoke else None,
            slots=4 if args.smoke else 8,
            num_requests=8 if args.smoke else 24,
            # prompt-heavy trace: prefill interference on the decode
            # stream is what disaggregation removes, so the grid skews
            # long relative to the serving leg's
            prompt_grid=(8, 16, 24) if args.smoke else (64, 256, 384),
            new_grid=(8, 16) if args.smoke else (16, 32),
            chunk_buckets=(8, 16) if args.smoke else (64, 128),
            dtype_name=args.dtype,
            page_size=16 if args.smoke else 64,
            log=lambda s: print(s, file=sys.stderr))

    def serving_spec_metrics():
        # speculative decoding A/B over the shared-system-prompt paged
        # trace: ngram self-drafting copies from history, and the
        # seeded shared prefix gives it real structure to copy, so the
        # smoke trace exercises acceptance > 0 (not just the machinery).
        # compare_spec replays the IDENTICAL trace with speculation off
        # through the same engine, so acceptance_rate,
        # effective_tokens_per_step, the no-spec baseline throughput
        # and the greedy token-identity gate all land in ONE record.
        from mpi_operator_tpu.examples.serve_benchmark import (
            run_serving_benchmark)
        m = run_serving_benchmark(
            size="test" if args.smoke else None,
            slots=4 if args.smoke else 8,
            num_requests=8 if args.smoke else 32,
            prompt_grid=(8, 16, 24) if args.smoke else (32, 64, 128),
            new_grid=(16, 32) if args.smoke else (32, 64),
            chunk_buckets=(8, 16) if args.smoke else (32, 128),
            dtype_name=args.dtype,
            page_size=16 if args.smoke else 64,
            shared_prefix_len=16 if args.smoke else 128,
            speculative="ngram",
            compare_spec=True,
            baseline=False,
            log=lambda s: print(s, file=sys.stderr))
        # spec/nospec keys already carry their own prefixes; everything
        # else (ttft/tpot/compile pins) gets the leg prefix
        keep = ("serving_spec_", "serving_nospec_")
        return {(k if k.startswith(keep)
                 else k.replace("serving_", "serving_spec_", 1)): v
                for k, v in m.items()}

    def serving_router_metrics():
        # front-door A/B over an engine fleet: the same seeded multi-
        # tenant shared-prefix trace with prefix-affinity routing ON vs
        # OFF, plus an overload-burst shed/recovery leg. ONE record
        # carries per-replica dispatch/shed counts, both hit rates,
        # admission-relative TTFT for both modes, p99 TTFT at the
        # offered load, and the token-identity + compile-pin gates.
        from mpi_operator_tpu.examples.serve_benchmark import (
            run_router_benchmark)
        return run_router_benchmark(
            size="test" if args.smoke else None,
            replicas=2,
            slots=4 if args.smoke else 8,
            num_requests=12 if args.smoke else 32,
            prompt_grid=(16, 32) if args.smoke else (32, 64),
            new_grid=(8, 16) if args.smoke else (32, 64),
            chunk_buckets=(16, 64) if args.smoke else (32, 128),
            dtype_name=args.dtype,
            page_size=16 if args.smoke else 64,
            shared_prefix_len=32 if args.smoke else 128,
            log=lambda s: print(s, file=sys.stderr))

    def serving_livescale_metrics():
        # live decode-pool scaling A/B: the same seeded trace through a
        # ±1 replica cycle done live (pre-warmed attach + graceful
        # drain, no survivor pause) vs as a gang restart (drain +
        # in-band fleet rebuild). ONE record carries p99 TTFT and
        # throughput for both arms, the measured live_scale ledger
        # totals vs the gang total, and the zero-drop / token-identity
        # / compile-pin gates.
        from mpi_operator_tpu.examples.serve_benchmark import (
            run_livescale_benchmark)
        return run_livescale_benchmark(
            size="test" if args.smoke else None,
            replicas=2,
            slots=4 if args.smoke else 8,
            num_requests=12 if args.smoke else 32,
            prompt_grid=(16, 32) if args.smoke else (32, 64),
            new_grid=(8, 16) if args.smoke else (32, 64),
            chunk_buckets=(16, 64) if args.smoke else (32, 128),
            dtype_name=args.dtype,
            page_size=16 if args.smoke else 64,
            shared_prefix_len=32 if args.smoke else 128,
            log=lambda s: print(s, file=sys.stderr))

    if args.workload == "serving":
        line = {
            "metric": "serving_tokens_per_sec",
            "value": None,
            "unit": "tokens/sec",
            "vs_baseline": 0.0,     # reference has no serving path
        }
        _SUMMARY_STATE["line"] = line
        m = serving_metrics()
        line.update(m)
        line["value"] = m["serving_tokens_per_sec"]
        emit_leg("serving", m)
        pm = serving_paged_metrics()
        line.update(pm)
        emit_leg("serving_paged", pm)
        dm = serving_disagg_metrics()
        line.update(dm)
        emit_leg("serving_disagg", dm)
        ssm = serving_spec_metrics()
        line.update(ssm)
        emit_leg("serving_spec", ssm)
        srm = serving_router_metrics()
        line.update(srm)
        emit_leg("serving_router", srm)
        lsm = serving_livescale_metrics()
        line.update(lsm)
        emit_leg("serving_livescale", lsm)
        return finish(line)
    if args.workload == "generate":
        line = {
            "metric": "gpt2_decode_tokens_per_sec",
            "unit": "tokens/sec",
            "vs_baseline": 0.0,     # reference has no inference path
        }
        _SUMMARY_STATE["line"] = line
        run_decode_legs(line)
        line["value"] = line.get("gpt2_decode_tokens_per_sec")
        return finish(line)
    if args.workload == "allreduce":
        _SUMMARY_STATE["line"] = {
            "metric": "allreduce_scaling_efficiency", "value": None,
            "unit": "fraction_of_smallest_ring_busbw", "vs_baseline": 0.0}
        from mpi_operator_tpu.examples.allreduce_bench import (
            run_allreduce_benchmark)
        result = run_allreduce_benchmark(
            payload_mb=[0.25, 1.0] if args.smoke else [1.0, 16.0, 64.0],
            iters=3 if args.smoke else 10,
            log=lambda s: print(s, file=sys.stderr))
        curve = result["efficiency_curve"]
        # a single visible device measures no ring at all — report that
        # honestly instead of fabricating a perfect score
        worst = min(curve.values()) if curve else None
        line = {
            "metric": "allreduce_scaling_efficiency",
            "value": round(worst, 4) if worst is not None else None,
            "unit": "fraction_of_smallest_ring_busbw",
            "vs_baseline": (round(worst / 0.90, 3)       # BASELINE ≥90%
                            if worst is not None else 0.0),
            "efficiency_curve": curve or "insufficient devices (need >1)",
        }
        emit_leg("allreduce", line)
        return finish(line)
    if args.workload == "vit":
        _SUMMARY_STATE["line"] = {
            "metric": "vit_images_per_sec", "value": None,
            "unit": "images/sec", "vs_baseline": 0.0}
        from mpi_operator_tpu.examples.lm_benchmark import run_vit_benchmark
        _state, metrics = run_vit_benchmark(
            size="test" if args.smoke else "b16",
            batch_per_device=args.batch_per_device if not args.smoke else 2,
            image_size=args.image_size if not args.smoke else 32,
            num_steps=args.steps, warmup_steps=args.warmup,
            dtype_name=args.dtype, log=lambda s: print(s, file=sys.stderr))
        line = {
            "metric": "vit_images_per_sec",
            "value": round(metrics["images_per_sec"], 2),
            "unit": "images/sec",
            "vs_baseline": 0.0,     # reference publishes no ViT numbers
            **mfu_fields(metrics),
        }
        emit_leg("vit", line)
        return finish(line)

    from mpi_operator_tpu.examples.benchmark import run_benchmark

    n = jax.device_count()
    print(f"# devices: {n} ({jax.devices()[0].device_kind}); model={args.model} "
          f"global_batch={args.batch_per_device * n} dtype={args.dtype}",
          file=sys.stderr)

    def measure():
        return run_benchmark(
            model_name=args.model,
            batch_per_device=args.batch_per_device,
            num_steps=args.steps,
            warmup_steps=args.warmup,
            image_size=args.image_size,
            dtype_name=args.dtype,
            stem=args.stem,
            log=lambda s: print(s, file=sys.stderr))

    line = {
        "metric": f"{args.model}_images_per_sec_per_device",
        "value": None,
        "unit": "images/sec",
        "vs_baseline": 0.0,
    }
    _SUMMARY_STATE["line"] = line

    def resnet_leg():
        state, metrics = measure()
        # release the resnet train state before the secondary LM leg
        # compiles, or its params+optimizer pin HBM and the gpt2 run OOMs
        del state
        per_device = metrics["images_per_sec_per_device"]
        fields = {
            "value": round(per_device, 2),
            "vs_baseline": round(per_device / REFERENCE_PER_DEVICE_IPS, 3),
            **mfu_fields(metrics),
        }
        line.update(fields)
        emit_leg("resnet", fields)

    if args.workload != "all":
        resnet_leg()
        return finish(line)

    # The FULL BASELINE ladder folded into the single JSON line the driver
    # records. Each leg is isolated (guarded): a failure (OOM on a small
    # chip, compile error) marks its own *_error field, does not discard
    # the legs already measured, and makes the run exit non-zero at the
    # end. jax.clear_caches between legs drops the previous executables'
    # HBM residue.
    guarded(line, "resnet", resnet_leg)

    import time as _time
    ladder_t0 = _time.perf_counter()

    def over_budget(prefix):
        if _time.perf_counter() - ladder_t0 <= args.budget_seconds:
            return False
        print(f"# {prefix} leg skipped: ladder wall-clock budget "
              f"({args.budget_seconds}s) exhausted", file=sys.stderr)
        line[f"{prefix}_skipped"] = "budget"
        return True

    def clear_residue():
        # drop compiled executables AND collect reference cycles
        # (trainer objects hold their jitted steps through bound
        # methods — a cycle the refcounter alone never frees, which
        # can keep the previous leg's buffers alive into this one)
        import gc
        gc.collect()
        jax.clear_caches()

    def leg(prefix, fn):
        """One budget-gated, isolated ladder leg; `fn` returns the
        fields it measured."""
        if over_budget(prefix):
            return

        def run():
            clear_residue()
            fields = fn()
            line.update(fields)
            emit_leg(prefix, fields)
        guarded(line, prefix, run)

    def lm_leg(prefix, **kw):
        def fn():
            m = run_lm(**kw)
            fields = {f"{prefix}_tokens_per_sec": round(
                m["tokens_per_sec"], 0)}
            fields.update({f"{prefix}_{k}": v
                           for k, v in mfu_fields(m).items()})
            if m.get("moe_drop_rate") is not None:
                fields[f"{prefix}_drop_rate"] = round(
                    m["moe_drop_rate"], 4)
            return fields
        leg(prefix, fn)

    # per-leg step caps sized so the full ladder (now incl. the
    # serving leg) lands inside --budget-seconds with margin: 15
    # steady-state steps bound the throughput estimate as tightly as
    # 20 did (spread < the run-to-run jitter already reported)
    steps = min(args.steps, 15)
    warm = min(args.warmup, 3)
    # BASELINE configs[2-4] ladder: GPT-2, BERT-large-class, llama
    lm_leg("gpt2", workload="gpt2", steps=steps, warmup=warm)
    lm_leg("bert", workload="bert", steps=steps, warmup=warm, batch=16)
    lm_leg("llama_train", workload="llama", steps=steps, warmup=warm,
           batch=8)
    # TP-overlap A/B (same config, one switch): gpt2 on a tp=2 mesh
    # with the GSPMD einsum path vs the ring collective-matmul path
    # (parallel/collectives.py, TransformerConfig.tp_overlap). The
    # MFU delta between these two legs IS the comm-hiding win — read
    # them as a pair, nothing else differs. Needs a real ring, so
    # single-device runs record a skip marker instead of a fake 1.0×.
    if jax.device_count() >= 2:
        lm_leg("gpt2_tp2", workload="gpt2", steps=steps, warmup=warm,
               batch=16, tp=2, fused_xent=True)
        lm_leg("gpt2_tp2_overlap", workload="gpt2", steps=steps,
               warmup=warm, batch=16, tp=2, fused_xent=True,
               tp_overlap=True)
        # third point of the A/B: same overlap bodies, halves of each
        # shard rotating in OPPOSITE directions (half the bytes per
        # hop on a bidirectional ICI link) — read against the
        # gpt2_tp2_overlap leg; nothing else differs
        lm_leg("gpt2_tp2_bidir", workload="gpt2", steps=steps,
               warmup=warm, batch=16, tp=2, fused_xent=True,
               tp_overlap=True, tp_ring="bidir")
    else:
        line["gpt2_tp2_skipped"] = "needs >=2 devices"
        line["gpt2_tp2_overlap_skipped"] = "needs >=2 devices"
        line["gpt2_tp2_bidir_skipped"] = "needs >=2 devices"
    # MoE: expert-capacity dispatch on one chip — MFU + drop rate
    lm_leg("moe", workload="gpt2",
           size=None if args.smoke else "small",
           steps=steps, warmup=warm, batch=16,
           moe_experts=8)
    # long-context legs: tuned configs — no remat, the kernel's
    # 1024-tile auto policy
    lm_leg("gpt2_seq2048", workload="gpt2", steps=steps,
           warmup=warm, batch=4, seq=2048)
    lm_leg("gpt2_seq4096", workload="gpt2", steps=min(args.steps, 10),
           warmup=warm, batch=2, seq=4096)

    def hfta_leg():
        # Horizontally fused job packing (train/hfta.py): K=8 sweep
        # replicas vmap-stacked into ONE jitted step, vs the SAME
        # per-replica config run solo. K sequential sweep members
        # process aggregate tokens at exactly the solo rate, so
        # fused_speedup = fused aggregate tokens/sec ÷ solo tokens/sec
        # IS the job-packing win. Both runs share size/batch/seq —
        # nothing else differs.
        from mpi_operator_tpu.examples.lm_benchmark import (
            run_hfta_benchmark)
        hfta_k = 8
        hsize = "test" if args.smoke else "small"
        hbatch = 2 if args.smoke else 8
        hseq = 32 if args.smoke else 512
        hsteps = min(args.steps, 10)
        seqm = run_lm("gpt2", hsteps, warm, batch=hbatch,
                      seq=hseq, size=hsize)
        clear_residue()
        _hs, hm = run_hfta_benchmark(
            workload="gpt2", size=hsize, batch_per_device=hbatch,
            seq_len=hseq, num_steps=hsteps, warmup_steps=warm,
            dtype_name=args.dtype, k=hfta_k,
            log=lambda s: print(s, file=sys.stderr))
        del _hs
        fused = hm["tokens_per_sec"]
        solo = seqm["tokens_per_sec"]
        fields = {
            "gpt2_hfta8_tokens_per_sec": round(fused, 0),
            "sequential_tokens_per_sec": round(solo, 0),
            "fused_speedup": round(fused / max(solo, 1e-9), 3),
            "per_replica_mfu": hm["per_replica"]["mfu"],
            "per_replica_goodput": hm["per_replica"]["goodput"],
        }
        if hm.get("mfu") is not None:
            fields["gpt2_hfta8_mfu"] = round(hm["mfu"], 4)
        return fields

    leg("gpt2_hfta8", hfta_leg)

    def elastic_leg():
        # Elastic gang resize (examples/elastic_benchmark.py): the full
        # 4 -> 2 -> 4 drain -> gang_resize -> resharding-restore cycle
        # with an oracle loss-parity gate. The phases are ALWAYS
        # CPU-host subprocesses (this process holds the chip; a child
        # that needed it would fail or hang), so the leg measures the
        # resize machinery — drain/restore/recompile split and resume
        # wall time — not chip throughput.
        from mpi_operator_tpu.examples.elastic_benchmark import (
            run_elastic_benchmark)
        em = run_elastic_benchmark(
            log=lambda s: print(s, file=sys.stderr))
        fields = {
            "gpt2_elastic_ok": em["ok"],
            "gpt2_elastic_resize_seconds":
                em.get("resize_seconds"),
            "gpt2_elastic_goodput": em.get("goodput"),
            "gpt2_elastic_token_identical":
                em.get("elastic_token_identical"),
            # resume wall = phase start -> exit for the two
            # post-resize incarnations (includes process boot)
            "gpt2_elastic_resume_wall_seconds": [
                p["wall_seconds"]
                for p in em.get("phases", [])[1:]],
        }
        worst = max((r for r in em.get("resizes") or []
                     if "total_seconds" in r),
                    key=lambda r: r["total_seconds"],
                    default=None)
        if worst is not None:
            for p in ("drain", "restore", "recompile"):
                if f"{p}_seconds" in worst:
                    fields[f"gpt2_elastic_{p}_seconds"] = \
                        worst[f"{p}_seconds"]
        return fields

    leg("gpt2_elastic", elastic_leg)
    # the SAME decode suite as --workload generate — the driver
    # records only this default run, so a leg measured in one mode
    # but not here would be effectively unmeasured. Primary MBU
    # rooflines run BEFORE vit; the b32 sweep extras run LAST (r05
    # lesson: they budget-starved vit).
    clear_residue()
    run_decode_legs(line, skip_check=over_budget, legs=DECODE_LEGS)
    # continuous-batching serving vs sequential generate() — rides
    # right behind the decode legs it builds on (same fast path,
    # ragged traffic); p50/p99 TTFT/TPOT land in the JSONL record
    leg("serving", serving_metrics)
    # serving over the shared-system-prompt trace (prefix hit rate +
    # cold/hit TTFT; the leg above is the trace with no shared prefix)
    leg("serving_paged", serving_paged_metrics)
    # speculative decoding over the same shared-prefix trace shape
    # (acceptance rate + effective tokens/row-step, no-spec A/B
    # throughput in the same record)
    leg("serving_spec", serving_spec_metrics)
    # prefix-affinity router over an engine fleet (affinity A/B +
    # overload shed/recovery; builds on the paged prefix cache the
    # serving_paged leg just measured)
    leg("serving_router", serving_router_metrics)

    def vit_leg():
        # ViT-B/16 (BASELINE configs[5] single-chip point; the
        # multi-slice variant is the dryrun's dcn leg)
        from mpi_operator_tpu.examples.lm_benchmark import (
            run_vit_benchmark)
        _vs, vm = run_vit_benchmark(
            size="test" if args.smoke else "b16",
            batch_per_device=2 if args.smoke else 256,
            image_size=32 if args.smoke else args.image_size,
            num_steps=steps, warmup_steps=warm,
            dtype_name=args.dtype,
            log=lambda s: print(s, file=sys.stderr))
        del _vs
        fields = {"vit_images_per_sec":
                  round(vm["images_per_sec"], 1)}
        fields.update({f"vit_{k}": v
                       for k, v in mfu_fields(vm).items()})
        return fields

    leg("vit", vit_leg)
    clear_residue()
    run_decode_legs(line, skip_check=over_budget,
                    legs=DECODE_SWEEP_LEGS)
    return finish(line)


if __name__ == "__main__":
    sys.exit(main())
