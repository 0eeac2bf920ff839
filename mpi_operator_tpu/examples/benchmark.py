"""The in-container benchmark workload — what worker pods actually run.

Replaces the reference's `mpirun python tf_cnn_benchmarks.py --model=...
--variable_update=horovod` entrypoint (reference examples/
tensorflow-benchmarks/Dockerfile:12-16): every worker runs this module
directly; `bootstrap.initialize()` forms the process group from controller-
injected env, and the gradient allreduce is XLA's, not Horovod's.

Role split (SURVEY §7): the LAUNCHER pod never joins the process group — it
polls rank-0's status channel and exits with the job's code, preserving the
reference's batch-Job completion semantics. Rank-0 serves that channel next
to training.

Output format matches the reference's launcher logs (README.md:97-133) so
`kubectl logs -f <launcher>` reads the same.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import Callable, Dict, Optional, Tuple

from ._report import with_run_report


@with_run_report
def run_benchmark(
    model_name: str = "resnet101",
    batch_per_device: int = 64,
    num_steps: int = 100,
    warmup_steps: int = 10,
    image_size: int = 224,
    dtype_name: str = "bfloat16",
    num_slices: int = 1,
    stem: str = "conv7",
    data_dir: Optional[str] = None,
    profile_dir: Optional[str] = None,
    train_dir: Optional[str] = None,
    ckpt_every: int = 0,
    log: Callable[[str], None] = print,
) -> Tuple[object, Dict[str, float]]:
    """Shared wiring for the container entrypoint and the tests: mesh over all visible devices, synthetic or on-disk
    data (`data_dir` — npy shards, data/imagefolder.py), DP train loop.
    Returns (final_state, metrics)."""
    import jax
    import jax.numpy as jnp

    from ..data import SyntheticImageDataset
    from ..models.resnet import create_model
    from ..parallel import MeshConfig, batch_sharding, make_mesh
    from ..train import Trainer, TrainerConfig

    n = jax.device_count()
    mesh = make_mesh(MeshConfig.data_parallel(n, num_slices=num_slices))
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    global_batch = batch_per_device * n

    model = create_model(model_name, num_classes=1000, dtype=dtype,
                         stem=stem)
    cfg = TrainerConfig(global_batch_size=global_batch,
                        image_size=image_size, num_classes=1000)
    trainer = Trainer(model, mesh, cfg)
    state = trainer.init_state(jax.random.PRNGKey(0))
    if data_dir is not None:
        from ..data.imagefolder import NpyImageDataset
        dataset = NpyImageDataset(
            data_dir, global_batch, image_size=image_size, dtype=dtype,
            sharding=batch_sharding(mesh))
    else:
        dataset = SyntheticImageDataset(
            global_batch, image_size=image_size, num_classes=1000,
            dtype=dtype, sharding=batch_sharding(mesh))
    from ..train.checkpoint import maybe_resume, periodic_saver
    state = maybe_resume(train_dir, state, log)
    try:
        return trainer.benchmark(
            state, dataset, num_steps=num_steps,
            warmup_steps=warmup_steps, log=log, profile_dir=profile_dir,
            step_hook=periodic_saver(train_dir, ckpt_every, log))
    finally:
        if hasattr(dataset, "close"):
            dataset.close()


def print_banner(model: str, global_batch: int, per_device: int, n: int,
                 data_dir: Optional[str]) -> None:
    """Reference log banner (ref README.md:97-109)."""
    print("Model:       %s" % model)
    print("Batch size:  %d global / %d per device" % (global_batch, per_device))
    print("Devices:     %s" % [f"tpu:{i}" for i in range(n)])
    print("Data format: NHWC")
    print("Data:        %s" % (data_dir or "synthetic"))
    print("Optimizer:   sgd+momentum", flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="tpu-benchmarks")
    parser.add_argument("--model", default="resnet101")
    parser.add_argument("--batch-per-device", type=int, default=64)
    parser.add_argument("--num-steps", type=int, default=100)
    parser.add_argument("--warmup-steps", type=int, default=10)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--data-dir", default=None,
                        help="real-data directory; synthetic when absent "
                             "(the reference benchmark's default too)")
    parser.add_argument("--train-dir", default=None,
                        help="checkpoint directory (orbax); resumes from "
                             "the latest checkpoint when one exists")
    parser.add_argument("--ckpt-every", type=int, default=0,
                        help="async checkpoint every N steps into "
                             "--train-dir (0 = final only)")
    parser.add_argument("--stem", default="s2d", choices=["s2d", "conv7"],
                        help="s2d (default): 4x4 space-to-depth stem — "
                             "feeds the MXU's input lanes (measured +4.7%% "
                             "img/s on v5e); conv7: the reference 7x7/s2 "
                             "conv + maxpool")
    parser.add_argument("--profile-dir", default=None,
                        help="write a jax.profiler trace of the first "
                             "measurement window here (XProf format)")
    args = parser.parse_args(argv)

    from ..bootstrap import initialize
    from ..bootstrap.bootstrap import StatusServer, launcher_wait

    info = initialize()
    print(f"TPUJob process {info.process_id}/{info.num_processes} "
          f"(launcher={info.is_launcher}) coordinator="
          f"{info.coordinator_address}", flush=True)

    if info.is_launcher:
        # thin coordinator: observe rank-0, mirror its exit code
        print("launcher: waiting on rank-0 status channel", flush=True)
        return launcher_wait(info)

    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    status = StatusServer() if info.is_coordinator else None
    exit_code = 1
    try:
        import jax

        n = jax.device_count()
        if info.is_coordinator:
            print_banner(args.model, args.batch_per_device * n,
                         args.batch_per_device, n, args.data_dir)
        if args.data_dir is not None and not os.path.isdir(args.data_dir):
            print(f"warning: --data-dir {args.data_dir} not found; "
                  f"falling back to synthetic data", file=sys.stderr)
            args.data_dir = None

        state, metrics = run_benchmark(
            model_name=args.model,
            batch_per_device=args.batch_per_device,
            num_steps=args.num_steps,
            warmup_steps=args.warmup_steps,
            image_size=args.image_size,
            dtype_name=args.dtype,
            num_slices=info.num_slices,
            stem=args.stem,
            data_dir=args.data_dir,
            profile_dir=args.profile_dir,
            train_dir=args.train_dir,
            ckpt_every=args.ckpt_every,
            log=print if info.is_coordinator else (lambda s: None))

        # EVERY process must enter the save: orbax's save is a collective
        # over all JAX processes (it barriers internally); gating it on
        # the coordinator deadlocks multi-host jobs. Orbax itself
        # restricts the actual write to the primary host. maybe_save also
        # skips a step the periodic hook already committed.
        from ..train.checkpoint import maybe_save
        maybe_save(args.train_dir, state,
                   log=print if info.is_coordinator else (lambda s: None))
        exit_code = 0
        return 0
    except Exception as exc:
        # preemption drain exits with its RETRYABLE code (128–255) so the
        # controller restarts the gang; everything else keeps exit 1
        from ..train.resilience import Preempted
        if isinstance(exc, Preempted):
            print(f"preempted: drained at step {exc.step}, exiting "
                  f"{exc.exit_code} (retryable)", flush=True)
            exit_code = exc.exit_code
            return exit_code
        raise
    finally:
        if status is not None:
            status.set_done(exit_code)
            status.close()


if __name__ == "__main__":
    sys.exit(main())
