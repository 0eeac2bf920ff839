"""Elastic gang-resize benchmark: 4 -> 2 -> 4 without a cold restart.

Plays the controller's side of a user-driven ``spec.resize`` end to end,
out of process, on CPU hosts:

  phase 1   4 devices, batch 2/device — SIGTERM mid-run (drain ->
            emergency checkpoint -> exit 215, the retryable band)
  resize    the orchestrator records ``gang_resize`` in the controller
            event log (what TPUJobController.note_resize(gang=True) does
            when spec.resize lands)
  phase 2   2 devices, batch 4/device — the dp=4 checkpoint is restored
            onto the dp=2 mesh via the resharding reader
            (TPU_RESHARD_RESTORE=1, train/checkpoint.restore_resharded),
            then SIGTERM'd again
  resize    back to the original size
  phase 3   4 devices, batch 2/device — resharding restore again, runs
            to STOP_AT_STEP and exits 0

The global batch is constant (4x2 = 2x4 = 8) and the token stream is
step-keyed, so every phase consumes exactly the batches the
uninterrupted run would have at each global step — the final loss must
match a straight-through oracle run modulo cross-world reduction order.
The merged timeline (controller + worker events) feeds the SAME
resize_ledger/goodput_ledger the live controller renders, reporting the
``resize_seconds`` drain/restore/recompile split and goodput continuity
across both resizes.

    python -m mpi_operator_tpu.examples.elastic_benchmark \
        --out-dir /tmp/elastic

Prints one JSON line; exit 0 iff every gate held. ``--out-dir`` keeps
timeline.jsonl / federated.prom / per-phase logs for postmortem use.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

#: (devices, batch_per_device) per phase — the product (global batch) is
#: invariant, which is what makes the loss curves comparable at all
PHASE_SHAPES: Tuple[Tuple[int, int], ...] = ((4, 2), (2, 4), (4, 2))
#: global steps the two SIGTERMs land on, and the step every run ends at
RESIZE_AT, STOP_AT_STEP = (5, 10), 14
SEQ_LEN = 16


def _phase_env(devices: int, port: int, fault: Optional[str],
               reshard: bool) -> Dict[str, str]:
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env["TPU_COORDINATOR_ADDRESS"] = f"localhost:{port}"
    env["TPU_NUM_PROCESSES"] = "1"
    env.pop("TPU_FAULT_INJECT", None)
    if fault:
        env["TPU_FAULT_INJECT"] = fault
    if reshard:
        env["TPU_RESHARD_RESTORE"] = "1"
    else:
        env.pop("TPU_RESHARD_RESTORE", None)
    return env


def _run_phase(train_dir: str, devices: int, batch_per_device: int,
               port: int, stop_at_step: int, seq_len: int, log_path: str,
               fault: Optional[str] = None,
               reshard: bool = True) -> Tuple[int, float]:
    cmd = [sys.executable, "-m", "mpi_operator_tpu.examples.lm_benchmark",
           "--workload", "gpt2", "--size", "test",
           "--batch-per-device", str(batch_per_device),
           "--seq-len", str(seq_len), "--dtype", "float32",
           "--warmup-steps", "1", "--num-steps", "50",
           "--stop-at-step", str(stop_at_step),
           "--train-dir", train_dir]
    t0 = time.time()
    with open(log_path, "w", encoding="utf-8") as fh:
        proc = subprocess.run(cmd, stdout=fh, stderr=subprocess.STDOUT,
                              env=_phase_env(devices, port, fault, reshard),
                              check=False)
    return proc.returncode, round(time.time() - t0, 3)


def _headline(log_path: str) -> Dict:
    """Last parseable {"metric": ...} JSON line of a phase log."""
    out: Dict = {}
    try:
        with open(log_path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "metric" in rec:
                    out = rec
    except OSError:
        pass
    return out


def run_elastic_benchmark(out_dir: Optional[str] = None,
                          port: int = 8479, log=print) -> Dict:
    from ..telemetry import EventLog, read_events, events as tev
    from ..telemetry.collector import (goodput_ledger, ledger_lines,
                                       merge_timeline, resize_ledger,
                                       resize_lines)

    tmp = None
    if out_dir is None:
        tmp = out_dir = tempfile.mkdtemp(prefix="elastic_bench_")
    os.makedirs(out_dir, exist_ok=True)
    train_dir = os.path.join(out_dir, "ckpt")
    controller_log = os.path.join(out_dir, "controller.jsonl")
    job = "elastic"

    result: Dict = {"metric": "gpt2_elastic_resize_seconds",
                    "unit": "seconds", "phases": [], "ok": True}

    def fail(reason: str) -> None:
        result["ok"] = False
        result.setdefault("failures", []).append(reason)
        log(f"elastic: FAIL {reason}")

    try:
        with EventLog(controller_log) as clog:
            clog.emit(tev.JOB_CREATED, job=job, tpus=PHASE_SHAPES[0][0] * 2,
                      workers=PHASE_SHAPES[0][0])
            plan = [
                # (shape, fault step, expected rc)
                (PHASE_SHAPES[0], RESIZE_AT[0], 215),
                (PHASE_SHAPES[1], RESIZE_AT[1], 215),
                (PHASE_SHAPES[2], None, 0),
            ]
            for idx, ((devices, bpd), fault_step, want_rc) in enumerate(plan):
                fault = (f"sigterm-at-step:{fault_step}"
                         if fault_step is not None else None)
                log_path = os.path.join(out_dir, f"phase{idx}.log")
                log(f"elastic: phase {idx} — {devices} device(s) x "
                    f"batch {bpd}"
                    + (f", SIGTERM at step {fault_step}" if fault else
                       f", run to step {STOP_AT_STEP}"))
                rc, wall = _run_phase(train_dir, devices, bpd, port,
                                      STOP_AT_STEP, SEQ_LEN, log_path,
                                      fault=fault, reshard=idx > 0)
                result["phases"].append({"devices": devices,
                                         "batch_per_device": bpd,
                                         "rc": rc,
                                         "wall_seconds": wall})
                if rc != want_rc:
                    fail(f"phase {idx} exited {rc} (want {want_rc})")
                    break
                if fault_step is not None:
                    # the controller's side of the resize: the next
                    # phase's world size, stamped between the drain and
                    # the resharded restore
                    nxt = plan[idx + 1][0]
                    clog.emit(tev.GANG_RESIZE, job=job, workers=nxt[0],
                              tpus=nxt[0] * 2)
            else:
                clog.emit(tev.JOB_SUCCEEDED, job=job, step=STOP_AT_STEP)

        headline = _headline(os.path.join(out_dir, "phase2.log"))
        result["final_loss"] = headline.get("final_loss")

        # merged controller+worker timeline -> the same ledgers the live
        # controller's /metrics renders (ONE implementation)
        worker_log = os.path.join(train_dir, "events.jsonl")
        sources = [(None, read_events(controller_log))]
        if os.path.exists(worker_log):
            sources.append(("worker-0", read_events(worker_log)))
        timeline_path = os.path.join(out_dir, "timeline.jsonl")
        merged = merge_timeline(sources, out_path=timeline_path)
        result["timeline"] = timeline_path
        ledger = goodput_ledger(merged)
        result["goodput"] = round(ledger["goodput"], 4)
        result["useful_steps"] = ledger["useful_steps"]
        result["lost_steps"] = ledger["lost_steps"]
        resizes = resize_ledger(merged)
        result["resizes"] = resizes
        totals = [r["total_seconds"] for r in resizes
                  if "total_seconds" in r]
        result["resize_seconds"] = totals
        result["value"] = max(totals) if totals else None
        result["resharded_restores"] = sum(
            1 for r in merged if r.get("event") == tev.CHECKPOINT_RESTORE
            and r.get("resharded"))
        metrics_path = os.path.join(out_dir, "federated.prom")
        with open(metrics_path, "w", encoding="utf-8") as fh:
            fh.write("\n".join(ledger_lines(job, ledger)
                               + resize_lines(job, resizes)) + "\n")
        result["metrics"] = metrics_path

        if result["ok"]:
            if len(totals) != 2:
                fail(f"expected 2 completed resizes in the timeline, "
                     f"got {len(totals)} ({resizes})")
            for need in ("drain_seconds", "restore_seconds",
                         "recompile_seconds"):
                if any(need not in r for r in resizes):
                    fail(f"a resize entry is missing its {need} phase")
                    break
            if result["resharded_restores"] < 2:
                fail("fewer than 2 resharded restores in the timeline — "
                     "the resize resumed through the cold path")
            if ledger["goodput"] <= 0:
                fail("zero federated goodput across the resizes")

        if result["ok"]:
            # the straight-through control: same seed, same step-keyed
            # stream, same topology as phases 1/3, never interrupted
            log(f"elastic: oracle — {PHASE_SHAPES[0][0]} device(s) "
                f"straight to step {STOP_AT_STEP}")
            oracle_dir = os.path.join(out_dir, "oracle_ckpt")
            olog = os.path.join(out_dir, "oracle.log")
            rc, _wall = _run_phase(oracle_dir, PHASE_SHAPES[0][0],
                                   PHASE_SHAPES[0][1], port, STOP_AT_STEP,
                                   SEQ_LEN, olog, fault=None,
                                   reshard=False)
            if rc != 0:
                fail(f"oracle run exited {rc}")
            oracle_loss = _headline(olog).get("final_loss")
            result["oracle_final_loss"] = oracle_loss
            final_loss = result.get("final_loss")
            if final_loss is None or oracle_loss is None:
                fail("missing final_loss for the parity check")
            else:
                # identical tokens at every global step; only the 2-world
                # phase's reduction order differs from the oracle's
                identical = math.isclose(final_loss, oracle_loss,
                                         rel_tol=1e-3, abs_tol=1e-4)
                result["elastic_token_identical"] = identical
                if not identical:
                    fail(f"resumed loss {final_loss} != oracle "
                         f"{oracle_loss}")
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
            result.pop("timeline", None)
            result.pop("metrics", None)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m mpi_operator_tpu.examples.elastic_benchmark",
        description="out-of-process elastic gang-resize smoke/benchmark: "
                    "4 -> 2 -> 4 with resharding restore, resize_seconds "
                    "split, goodput continuity, and oracle loss parity")
    parser.add_argument("--out-dir", default=None,
                        help="keep artifacts (timeline.jsonl, "
                             "federated.prom, phase logs) here; default "
                             "is a temp dir removed on exit")
    parser.add_argument("--port", type=int, default=8479,
                        help="coordinator port for the phase subprocesses")
    args = parser.parse_args(argv)
    result = run_elastic_benchmark(
        out_dir=args.out_dir, port=args.port,
        log=lambda s: print(s, file=sys.stderr))
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())


__all__ = ["run_elastic_benchmark", "PHASE_SHAPES", "main"]
