"""Per-worker process launcher — the `orted` replacement for slots>1.

In the reference, `mpirun` reaches into each worker pod via the kubexec rsh
agent and spawns one `orted`, which forks `slots` ranks (reference hostfile
`slots=` lines, pkg/controllers/mpi_job_controller.go:857-869). TPU-native
workers run their own processes, so when a TPUJob sets slotsPerWorker > 1
the pod command wraps the training command with this module:

    python -m mpi_operator_tpu.bootstrap.launch -- python train.py ...

It forks `TPU_SLOTS_PER_WORKER` copies of the command, tagging each with
TPU_LOCAL_RANK=0..slots-1 (bootstrap.process_info turns that into the
global rank `ordinal*slots + local`), waits for all, and exits with the
first non-zero status — the same all-or-nothing semantics mpirun gave.

The usual TPU case is slots=1 (one process drives all local chips) and this
module is not needed at all. On a host that has TPU chips, slots>1 is
refused: every forked process would open ALL local chips, a chip belongs
to one process at a time, so the second process fails or hangs at backend
init. Giving each process its own chips (libtpu's visibility environment)
is not implemented; until it is, the launcher says so instead of hanging.
This module never imports jax — it holds no chip itself.
"""
from __future__ import annotations

import glob
import os
import signal
import subprocess
import sys
from typing import List, Optional

from .bootstrap import ENV_LOCAL_RANK, ENV_SLOTS, BootstrapError

#: device nodes a TPU VM exposes, one per chip (the accel driver, or vfio
#: on newer images) — how a process that must not load libtpu can tell
#: that the host has chips
TPU_DEVICE_GLOBS = ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*")


def local_tpu_chips() -> List[str]:
    return sorted(p for pat in TPU_DEVICE_GLOBS for p in glob.glob(pat))


def launch(command: List[str], slots: Optional[int] = None) -> int:
    slots = slots or int(os.environ.get(ENV_SLOTS, "1"))
    if slots == 1:
        return subprocess.call(command)
    chips = local_tpu_chips()
    if chips:
        raise BootstrapError(
            f"slotsPerWorker={slots} on a host with {len(chips)} TPU "
            f"chip(s) ({chips[0]}…): each of the {slots} processes would "
            f"open every local chip, and a chip belongs to one process at "
            f"a time — the second process fails or hangs at backend init. "
            f"Use slotsPerWorker=1 (one process drives all local chips).")

    procs: List[subprocess.Popen] = []
    for local_rank in range(slots):
        env = dict(os.environ)
        env[ENV_LOCAL_RANK] = str(local_rank)
        procs.append(subprocess.Popen(command, env=env))

    exit_code = 0
    try:
        import time

        remaining = list(procs)
        while remaining:
            done = [p for p in remaining if p.poll() is not None]
            for p in done:
                remaining.remove(p)
                if p.returncode != 0 and exit_code == 0:
                    exit_code = p.returncode
                    # one rank died → tear down the local gang, like mpirun
                    for q in remaining:
                        q.send_signal(signal.SIGTERM)
            if remaining:
                time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return exit_code


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--":
        argv = argv[1:]
    if not argv:
        print("usage: python -m mpi_operator_tpu.bootstrap.launch -- "
              "<command> [args...]", file=sys.stderr)
        return 2
    try:
        return launch(argv)
    except BootstrapError as exc:
        print(f"bootstrap.launch: {exc}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
