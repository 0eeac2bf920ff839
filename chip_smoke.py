#!/usr/bin/env python3
"""Does the system still start on the chip? The quickest proof.

Drives the two halves of the data plane once each, at the full width of
GPT-2-medium (24 layers, 16 heads, embed 1024, vocab 50304; random
weights from a seed), through the entry points a user or the operator
would call, one child process after another:

  kernels  python -m mpi_operator_tpu.examples.kernel_parity
           every Pallas kernel the two legs below use, compiled by Mosaic
           and compared with its dense reference at the legs' shapes; and
           the kernels of the other served models at theirs (the latent
           decode kernel at 64 heads and, under DeepSeek-V2's scaled RoPE
           and a table of 256 pages, at 128, with that model's [64, 128]
           chunk in row groups; the paged decode kernel with its lower bound
           over a ring and a long table; the state-space scans, a chunk
           against its steps, Mamba-2's through its state-update kernel at
           Falcon-H1's head of 128 channels and at Granite-4.0-H's of 64,
           two heads a lane tile: `ssd_traced` names the form each took;
           the training step's head and loss in one pass at the cells'
           shape: `head_loss_traced` names the kernel's form)
  trainer  python -m mpi_operator_tpu.examples.lm_benchmark --workload gpt2
           --size medium --seq-len 512 (global batch 16 over all visible
           chips), started the way the operator starts a gang: a worker
           with the controller's env contract and a launcher that waits
           on rank 0's status channel and mirrors its exit code
  server   python -m mpi_operator_tpu.examples.serve_benchmark --family
           gpt2: 8 slots over the page pool, 16 mixed-length greedy
           requests, async decode and cache donation on

This process never imports jax (nor `mpi_operator_tpu`): a chip belongs
to one process at a time, so a parent that touched jax would hold it and
every child would fail or hang. The children run strictly one after the
other; the launcher beside the trainer is jax-free.

It fails — exit code other than 0, one line on stderr saying why, no
result on stdout — when jax finds no TPU (the first child checks the
platform before anything is built, so `JAX_PLATFORMS=cpu` fails within
seconds), when the `device_kind` is not in the peaks table
(utils/flops.py), when any leg fails or times out, or when a leg that
should have run a kernel traced the dense path instead.

Each record it prints names the device as jax reports it, and gives
compile time apart from step or request time. Nothing here is a
performance claim: 10 steps and 16 requests say that the system runs,
not how fast. Child logs go to chiprun_out/chip_smoke/.

The last line of stdout is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
#: the whole run, compilation included, must end inside the driver's
#: 1200 s; each child gets what is left of this
DEADLINE_SECONDS = 1140
#: the trainer's global batch, spread over however many chips are visible
#: (16 per device on one chip: the r03/r05 operating point), so a one-chip
#: and a four-chip run train on the same batches and their losses compare
GLOBAL_BATCH = 16
SEQ_LEN = 512
TRAIN_STEPS, WARMUP_STEPS = 10, 2
SLOTS, REQUESTS = 8, 16

LM = "mpi_operator_tpu.examples.lm_benchmark"
SERVE = "mpi_operator_tpu.examples.serve_benchmark"
KERNELS = "mpi_operator_tpu.examples.kernel_parity"
SSD_FORMS = ["pallas_ssd_update[P-minor,heads=2]",
             "pallas_ssd_update[P-minor]"]


class LegFailed(Exception):
    pass


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _kill(proc) -> None:
    """Stop a child and whatever it started (it leads its own group)."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    proc.wait()


def _spawn(name, argv, env):
    """Start `python argv` from the checkout; stdout and stderr go to
    chiprun_out/chip_smoke/<name>.{out,err}."""
    if sys.modules.get("jax") is not None:
        raise LegFailed("chip_smoke's own process imported jax; it would "
                        "hold the chip its children need")
    os.makedirs(LOG_DIR, exist_ok=True)
    paths = [os.path.join(LOG_DIR, f"{name}.{ext}") for ext in ("out", "err")]
    with open(paths[0], "w") as out, open(paths[1], "w") as err:
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=HERE, env={**os.environ, **env},
            stdout=out, stderr=err, start_new_session=True)
    return proc, paths


def _tail(path, nbytes=3000) -> str:
    with open(path, "rb") as fh:
        fh.seek(0, os.SEEK_END)
        fh.seek(max(0, fh.tell() - nbytes))
        return fh.read().decode(errors="replace")


def _headline(path) -> dict:
    """The last line of a child's stdout that is a JSON object."""
    with open(path) as fh:
        for line in reversed(fh.read().splitlines()):
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except ValueError:
                    continue
    raise LegFailed("printed no JSON headline")


def run_leg(name, argv, deadline, env=None, companion_env=None):
    """Run one child to its end and return (headline, wall seconds).
    `companion_env` starts the same command a second time beside it with
    that env on top (the jax-free launcher) and requires both to exit 0."""
    env = env or {}
    t0 = time.monotonic()
    proc, (out, err) = _spawn(name, argv, env)
    companion = None
    try:
        if companion_env is not None:
            companion, _ = _spawn(f"{name}_launcher", argv,
                                  {**env, **companion_env})
        try:
            rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            raise LegFailed(f"still running at the {DEADLINE_SECONDS} s "
                            f"deadline; killed") from None
        if rc != 0:
            sys.stderr.write(f"--- {name}: end of stdout ---\n{_tail(out)}\n"
                             f"--- {name}: end of stderr ---\n{_tail(err)}\n")
            reason = _tail(err, 400).strip().splitlines()
            raise LegFailed(f"exit code {rc}: "
                            f"{reason[-1] if reason else 'no stderr'}")
        if companion is not None:
            # rank 0 holds "done" until a poller has read it; the
            # launcher polls every 2 s and exits with the job's code
            try:
                lrc = companion.wait(timeout=30)
            except subprocess.TimeoutExpired:
                raise LegFailed("launcher did not see rank 0 finish") \
                    from None
            if lrc != 0:
                raise LegFailed(f"launcher exit code {lrc}, worker 0")
        return _headline(out), time.monotonic() - t0
    finally:
        _kill(proc)
        if companion is not None:
            _kill(companion)


def _require(cond, message) -> None:
    if not cond:
        raise LegFailed(message)


def _same_device(head, device) -> None:
    got = {k: head.get(k) for k in ("platform", "device_kind",
                                    "device_count")}
    _require(got == device, f"ran on {got}, the first leg on {device}")


def check_kernels(head, device) -> dict:
    del device                      # this leg is where the device is read
    _require(head.get("platform") == "tpu",
             f"platform {head.get('platform')!r}, not 'tpu'")
    _require(head.get("ok") is True, f"kernel parity failed: {head}")
    # the state update ran in both of its forms: a head a lane tile
    # (Falcon-H1's 128 channels) and two heads a tile (Granite's 64)
    _require(head.get("ssd_traced") == SSD_FORMS,
             f"state update traced {head.get('ssd_traced')!r}, "
             f"not {SSD_FORMS}")
    # the head and its loss ran as the one kernel, not as the scan
    _require(str(head.get("head_loss_traced")).startswith("pallas_xent["),
             f"head and loss traced {head.get('head_loss_traced')!r}, "
             f"not the kernel")
    return {"kernels": head["kernels"],
            "decode_traced": head["decode_traced"],
            "ssd_traced": head["ssd_traced"],
            "head_loss_traced": head["head_loss_traced"],
            "worst_max_rel_err": head["worst_max_rel_err"],
            "tol": head["tol"]}


def check_trainer(head, device) -> dict:
    _same_device(head, device)
    _require(head.get("attention_impl") == "flash",
             f"train step traced attention "
             f"{head.get('attention_impl')!r}, not the flash kernel")
    _require(str(head.get("head_loss_impl")).startswith("pallas_xent["),
             f"train step traced its head and loss as "
             f"{head.get('head_loss_impl')!r}, not the kernel")
    loss = head.get("final_loss")
    # random weights, random tokens: the loss starts at ln(vocab) = 10.8
    # and a dozen warm-up-rate steps move it little; it must be a finite
    # cross-entropy, not a particular value
    _require(isinstance(loss, float) and math.isfinite(loss)
             and 0.0 < loss < 12.0, f"final loss {loss!r}")
    _require(head.get("step_compiles") == 1,
             f"the train step compiled {head.get('step_compiles')} times")
    mfu = head.get("mfu")
    _require(isinstance(mfu, float) and 0.0 < mfu < 1.0,
             f"MFU {mfu!r} (peaks table row for {device['device_kind']})")
    ids = list(range(device["device_count"]))
    _require(head.get("state_device_ids") == ids
             and head.get("batch_device_ids") == ids,
             f"state on devices {head.get('state_device_ids')}, batch on "
             f"{head.get('batch_device_ids')}; expected all of {ids}")
    return {k: head[k] for k in (
        "value", "final_loss", "mfu", "compile_seconds", "step_compiles",
        "grad_reductions", "grad_reductions_async",
        "step_time_p50_ms", "attention_impl", "head_loss_impl",
        "device_bytes_in_use")}


def check_server(head, device) -> dict:
    _same_device(head, device)
    # the kernel's name carries its form (a walk of live pages), the pages
    # a turn and the kv heads a grid step took
    _require(str(head.get("decode_impl")).startswith("pallas_paged[live,"),
             f"decode step traced {head.get('decode_impl')!r}, not the "
             f"paged Pallas kernel")
    _require(head.get("serving_requests_complete") is True,
             "a request did not come back with the tokens it asked for")
    # a greedy trace uses one of the three sample_slots step programs
    _require(head.get("serving_no_recompile") is True
             and head.get("serving_step_compiles") == 1
             and head.get("serving_prefill_compiles", 99) <= 2
             and head.get("serving_compiles_after_warmup") == 0,
             f"compile pins broken: step "
             f"{head.get('serving_step_compiles')}, prefill "
             f"{head.get('serving_prefill_compiles')}, after warm-up "
             f"{head.get('serving_compiles_after_warmup')}")
    _require(head.get("serving_async_decode") is True
             and head.get("serving_cache_donated") is True,
             "async decode or cache donation was off")
    _require(head.get("serving_total_new_tokens", 0) > 0
             and math.isfinite(head.get("value", math.nan)),
             f"no tokens served: {head.get('serving_total_new_tokens')}")
    return {k: head[k] for k in (
        "value", "serving_total_new_tokens", "serving_warmup_seconds",
        "serving_wall_seconds", "serving_tpot_p50_ms", "decode_impl",
        "serving_step_compiles", "serving_prefill_compiles",
        "serving_param_device_ids", "serving_cache_device_ids")}


def main() -> int:
    if not os.path.isdir(os.path.join(HERE, "mpi_operator_tpu")):
        print("chip_smoke: FAILED: no mpi_operator_tpu package beside "
              "chip_smoke.py; run it from a checkout", file=sys.stderr)
        return 1
    deadline = time.monotonic() + DEADLINE_SECONDS
    device = {}
    failures = []
    legs = {}

    def leg(name, argv, check, **kw) -> bool:
        try:
            head, seconds = run_leg(name, argv, deadline, **kw)
            fields = check(head, device)
        except LegFailed as exc:
            failures.append(f"{name}: {exc}")
            return False
        if not device:
            device.update({k: head[k] for k in (
                "platform", "device_kind", "device_count")})
        legs[name] = {"seconds": round(seconds, 1), **fields}
        print(json.dumps({"leg": name, **legs[name], **device}), flush=True)
        return True

    # the kernels leg is also the platform check: nothing below runs, and
    # no model is built, unless jax found a TPU this repo has peaks for
    if leg("kernels", ["-m", KERNELS], check_kernels):
        n = device["device_count"]
        if GLOBAL_BATCH % n:
            failures.append(f"trainer: global batch {GLOBAL_BATCH} does "
                            f"not split over {n} devices")
        else:
            leg("trainer",
                ["-m", LM, "--workload", "gpt2", "--size", "medium",
                 "--batch-per-device", str(GLOBAL_BATCH // n),
                 "--seq-len", str(SEQ_LEN),
                 "--num-steps", str(TRAIN_STEPS),
                 "--warmup-steps", str(WARMUP_STEPS)],
                check_trainer,
                env={"TPU_COORDINATOR_ADDRESS": f"127.0.0.1:{_free_port()}",
                     "TPU_NUM_PROCESSES": "1"},
                companion_env={"TPU_LAUNCHER": "1"})
        leg("server",
            ["-m", SERVE, "--family", "gpt2",
             "--slots", str(SLOTS), "--num-requests", str(REQUESTS),
             "--no-baseline"],
            check_server)

    if failures:
        print("chip_smoke: FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"summary": "chip_smoke", "legs": legs, **device,
                      "claim": None}))
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
