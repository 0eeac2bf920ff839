"""Distributed trainer — the data-plane training loop.

The reference's training loop lives entirely outside its repo (TensorFlow
tf_cnn_benchmarks + Horovod DistributedOptimizer inside the example image,
reference examples/tensorflow-benchmarks/Dockerfile:12-16). This module is
the TPU-native equivalent: a single jitted train step over a
`jax.sharding.Mesh` where the batch is sharded over the data axes and
parameters are replicated (or fsdp-sharded) — XLA inserts the gradient
AllReduce over ICI exactly where Horovod's ring allreduce sat (SURVEY §7).

Throughput is logged in the reference's observable format
(`total images/sec: ...`, reference README.md:113-131) so launcher-pod logs
stay comparable.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import batch_spec
from ..telemetry import TrainTelemetry, span
from ..utils import flops
from ..utils.profiling import WindowProfiler


class TrainState(struct.PyTreeNode):
    """Carries params + mutable BN stats + optimizer state."""
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    apply_fn: Callable = struct.field(pytree_node=False)
    # consecutive non-finite (skipped) steps, maintained ON DEVICE by the
    # divergence guard (resilience.guard_nonfinite_update) so reading it
    # costs nothing until a log-window fetch; not persisted in
    # checkpoints (a restore starts a fresh streak)
    nonfinite_streak: Any = 0

    def apply_gradients(self, grads, batch_stats):
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            batch_stats=batch_stats,
            opt_state=new_opt_state,
        )


def cross_entropy_loss(logits, labels, num_classes: int = 0):
    del num_classes  # derivable from logits; kept for call-site clarity
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def make_sgd(lr: float = 0.1, momentum: float = 0.9,
             nesterov: bool = False) -> optax.GradientTransformation:
    """tf_cnn_benchmarks' default optimizer (SGD + momentum)."""
    return optax.sgd(lr, momentum=momentum, nesterov=nesterov)


@dataclass
class TrainerConfig:
    global_batch_size: int = 128       # reference run: 128 global / 64 per dev
    image_size: int = 224
    num_classes: int = 1000
    learning_rate: float = 0.1
    momentum: float = 0.9
    log_every: int = 10
    # divergence guard: a step with non-finite loss/grad-norm applies NO
    # update (resilience.guard_nonfinite_update); the selects are
    # numerically a no-op on finite steps and fuse into the update
    guard_nonfinite: bool = True


class Trainer:
    """pjit-style trainer: params replicated, batch sharded over data axes.

    The collective story: `jax.grad` of the sharded-batch loss produces
    partial gradients per data shard; because params are replicated, XLA
    inserts an AllReduce over the data axes before the optimizer update —
    the same reduction Horovod performed in C++/NCCL, now compiled onto ICI.
    """

    def __init__(self, model, mesh: Mesh, config: Optional[TrainerConfig] = None,
                 tx: Optional[optax.GradientTransformation] = None):
        self.model = model
        self.mesh = mesh
        self.config = config or TrainerConfig()
        self.tx = tx or make_sgd(self.config.learning_rate, self.config.momentum)
        self.batch_sharding = NamedSharding(mesh, batch_spec())
        self.replicated = NamedSharding(mesh, P())
        self._train_step = None

    # -- initialization -----------------------------------------------------

    def init_state(self, rng: jax.Array) -> TrainState:
        from flax.core import meta

        dummy = jnp.zeros(
            (2, self.config.image_size, self.config.image_size, 3),
            jnp.float32,
        )

        def init_all(rng):
            variables = self.model.init(rng, dummy, train=False)
            # models annotated with logical partitioning (ViT) come back
            # boxed; unbox is a no-op for plain arrays (ResNet)
            variables = meta.unbox(variables)
            params = variables["params"]
            return (params, variables.get("batch_stats", FrozenDict()),
                    self.tx.init(params))

        # initialize DIRECTLY into the target (replicated) layout — params
        # AND optimizer state materialize once, laid out by XLA, with no
        # single-device staging copy (the same out_shardings discipline
        # LMTrainer's shard_init uses for ruled layouts)
        params, batch_stats, opt_state = jax.jit(
            init_all, out_shardings=self.replicated)(rng)
        return TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), self.replicated),
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
            tx=self.tx,
            apply_fn=self.model.apply,
            nonfinite_streak=jax.device_put(jnp.zeros((), jnp.int32),
                                            self.replicated),
        )

    # -- the jitted step ----------------------------------------------------

    def _step_fn(self, state: TrainState, images, labels):
        def loss_fn(params):
            logits, mutated = state.apply_fn(
                {"params": params, "batch_stats": state.batch_stats},
                images, train=True, mutable=["batch_stats"],
            )
            loss = cross_entropy_loss(logits, labels, self.config.num_classes)
            # LayerNorm-only models (ViT) have no batch_stats collection
            return loss, (logits, mutated.get("batch_stats", state.batch_stats))

        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        # grads are partial sums per batch shard; with replicated params XLA
        # emits AllReduce(dp axes) here — the Horovod hook, compiler-inserted.
        new_state = state.apply_gradients(grads, new_stats)
        if self.config.guard_nonfinite:
            from .resilience import guard_nonfinite_update
            new_state = guard_nonfinite_update(state, new_state, loss, grads)
        state = new_state
        accuracy = jnp.mean(jnp.argmax(logits, -1) == labels)
        return state, {"loss": loss, "accuracy": accuracy,
                       "nonfinite_streak": state.nonfinite_streak}

    def compile_step(self, state: TrainState):
        if self._train_step is None:
            self._train_step = jax.jit(
                self._step_fn,
                in_shardings=(self.replicated, self.batch_sharding,
                              self.batch_sharding),
                out_shardings=(self.replicated, self.replicated),
                donate_argnums=(0,),
            )
        return self._train_step

    def train_step(self, state, images, labels):
        return self.compile_step(state)(state, images, labels)

    # -- benchmark loop (the reference's observable, README.md:97-133) ------

    def benchmark(self, state: TrainState, dataset, num_steps: int = 100,
                  warmup_steps: int = 10,
                  log: Callable[[str], None] = print,
                  profile_dir: Optional[str] = None,
                  step_hook: Optional[Callable] = None,
                  resilience=None, telemetry: Optional[TrainTelemetry] = None,
                  ) -> Tuple[TrainState, Dict[str, float]]:
        """Windowed throughput measurement, tf_cnn_benchmarks-style.
        Returns (final_state, metrics) — the input state is DONATED by the
        jitted step, so callers must use the returned state afterwards.

        resilience: an entered train.resilience.ResilienceContext. Per
        step its on_step() folds signals/faults into the replicated stop
        bit — True writes the emergency checkpoint and raises Preempted
        (the gang drains at the same boundary). At window fetches the
        on-device non-finite streak escalates to rollback-from-checkpoint
        at divergence_k.

        Synchronization note: each window is closed by FETCHING the loss
        scalar to the host — the log line needs the value anyway, and a
        host read of the last step's output waits for every step before
        it, so it is the barrier `block_until_ready` would be. The fetch
        itself happens OUTSIDE the timed window, so reported images/sec
        is pure step throughput. The headline number is
        the mean over steady-state windows (first window dropped — it
        absorbs pipeline fill), matching how tf_cnn_benchmarks averages
        per-step rates after warmup (ref README.md:113-131).

        telemetry: a telemetry.TrainTelemetry to feed (see
        LMTrainer.benchmark — same window-fetch-only discipline); a
        private recorder runs when None so step_time_p50/p99_ms and
        goodput always land in the returned metrics.
        """
        tel = telemetry if telemetry is not None else TrainTelemetry()
        if resilience is not None and resilience.telemetry is None:
            resilience.telemetry = tel    # rollback accounting → goodput
        step_fn = self.compile_step(state)
        it = iter(dataset)
        log_every = max(1, min(self.config.log_every, num_steps))
        # XLA's cost model for the exact executable (hits the compile
        # cache — same shapes as the benchmark steps), for MFU reporting.
        # The analysis sees the post-SPMD-partition module, so the count
        # is per device; scale to a global figure.
        probe = next(it)
        flops_per_step = flops.compiled_flops(
            step_fn.lower(state, *probe).compile())
        if flops_per_step is not None:
            flops_per_step *= self.mesh.size
        else:
            # analytic fallback resolved BEFORE the loop so per-window MFU
            # gauges have a numerator too, not just the final summary
            per_image = flops.resnet_train_flops_per_image(
                getattr(self.model, "arch", "") or "",
                self.config.image_size,
                stem=getattr(self.model, "stem", "conv7"))
            flops_per_step = (per_image * self.config.global_batch_size
                              if per_image else None)
        state, metrics = step_fn(state, *probe)
        for _ in range(max(0, warmup_steps - 1)):
            images, labels = next(it)
            state, metrics = step_fn(state, images, labels)
        float(metrics["loss"])       # true barrier (see docstring)
        base_step = int(state.step)  # one host read, OUTSIDE the loop

        window_ips = []
        profiler = WindowProfiler(profile_dir, log)
        profiler.start()
        wall0 = time.perf_counter()
        t0 = wall0
        try:
            for i in range(1, num_steps + 1):
                images, labels = next(it)
                with span("train.step"):
                    state, metrics = step_fn(state, images, labels)
                if step_hook is not None:
                    # periodic async checkpointing
                    # (train/checkpoint.periodic_saver)
                    step_hook(state, base_step + i)
                if resilience is not None \
                        and resilience.on_step(base_step + i):
                    from .resilience import Preempted
                    log(f"preemption drain: stopping the gang at step "
                        f"{base_step + i}")
                    resilience.emergency_save(state)
                    raise Preempted(base_step + i)
                if i % log_every == 0:
                    g0 = time.perf_counter()
                    loss = float(metrics["loss"])  # sync: closes the window
                    t1 = time.perf_counter()       # BEFORE the trace write
                    tel.host_gap_seconds.observe(t1 - g0)
                    profiler.stop_if_active()
                    ips = self.config.global_batch_size * log_every \
                        / (t1 - t0)
                    window_ips.append(ips)
                    tel.observe_steps((t1 - t0) / log_every, log_every)
                    tel.update_window(
                        examples_per_sec=ips,
                        mfu=flops.throughput_stats(
                            flops_per_step,
                            ips / self.config.global_batch_size,
                            self.mesh.size)["mfu"],
                        step=base_step + i)
                    streak = int(metrics.get("nonfinite_streak", 0))
                    if streak:
                        tel.record_streak(streak)
                    # tf_cnn_benchmarks log format (ref README.md:113-125)
                    log(f"{i}\timages/sec: {ips:.1f}\tloss: {loss:.3f}")
                    if resilience is not None \
                            and streak >= resilience.config.divergence_k:
                        state = resilience.rollback(state)
                        base_step = int(state.step) - i
                    t0 = time.perf_counter()       # fetch/log time excluded
        finally:
            profiler.stop_if_active()
        final_loss = float(metrics["loss"])
        wall = time.perf_counter() - wall0
        steady = window_ips[1:] if len(window_ips) > 1 else window_ips
        total_ips = sum(steady) / len(steady)
        n = self.mesh.size
        stats = flops.throughput_stats(
            flops_per_step, total_ips / self.config.global_batch_size, n)
        p50_ms, p99_ms = tel.step_percentiles_ms()
        gap50_ms, gap99_ms = tel.host_gap_percentiles_ms()
        log("-" * 40)
        log(f"total images/sec: {total_ips:.2f}")   # ref README.md:127-131
        if p50_ms is not None:
            log(f"step time: p50 {p50_ms:.1f} ms, p99 {p99_ms:.1f} ms, "
                f"goodput {tel.goodput.value:.1%}")
        if stats["mfu"] is not None:
            log(f"per-device: {stats['tflops_per_sec_per_device']:.1f} "
                f"TFLOP/s, MFU {stats['mfu']:.1%}")
        log("-" * 40)
        return state, {
            "images_per_sec": total_ips,
            "images_per_sec_per_device": total_ips / n,
            "steps": num_steps,
            "wall_seconds": wall,
            "final_loss": final_loss,
            "step_time_p50_ms": p50_ms,
            "step_time_p99_ms": p99_ms,
            "host_gap_p50_ms": gap50_ms,
            "host_gap_p99_ms": gap99_ms,
            "goodput": tel.goodput.value,
            **stats,
        }


__all__ = ["TrainState", "Trainer", "TrainerConfig", "make_sgd",
           "cross_entropy_loss"]
