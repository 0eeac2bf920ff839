"""Horizontally fused training arrays (HFTA) — K sweep replicas, ONE
jitted step.

Small jobs waste most of a big accelerator. Instead of running K
same-architecture sweep members as K sequential (or K gang-scheduled)
programs, this trainer stacks them along a leading ``[K, ...]`` axis —
params, optimizer state, and the per-step batch all carry the replica
dimension — and vmaps ONE train step over it. XLA then fuses the K
copies into batched matmuls, recovering the utilization a single small
model leaves on the floor (HFTA, PAPERS.md). The controller-side
counterpart (controller/packing.py) packs the *jobs* onto one slice;
this module packs the *arrays*.

Per-replica hyperparameters (learning rate, weight decay, warmup, init
seed) ride along as ``[K]`` vectors, so a fused run IS a hyperparameter
sweep. Replica k's update math is kept bitwise-identical to a plain
``LMTrainer`` with the same scalars:

  - init: each replica is initialized UNVMAPPED with its own seed via the
    exact ``shard_init`` call LMTrainer makes, then stacked — so replica
    k's params at step 0 equal the solo run's bit for bit.
  - loss/grad: the fused step vmaps ``LMTrainer._loss_fn`` itself — the
    same loss code, not a re-implementation.
  - optimizer: the replica-INVARIANT prefix of ``make_adamw`` (global-norm
    clip + scale_by_adam) runs as a shared transformation under vmap; the
    replica-VARYING tail (weight decay, lr schedule, sign flip) is applied
    with the per-replica ``[K]`` scalars using the same formulas optax
    evaluates, in the same order (clip -> adam -> +wd*p -> -lr_t * u).
  - guard: the divergence guard is per-replica — a replica whose
    loss/grad-norm goes non-finite has THAT update dropped (params and
    optimizer state roll back leaf-wise along axis k) while its K-1
    siblings apply theirs untouched. ``freeze_after`` consecutive bad
    steps freeze the replica for the rest of the run: a frozen replica
    stops consuming updates but never stalls the fused program (there is
    no host-side rollback to serialize on).

Checkpoints persist the stacked pytree through the ordinary
train/checkpoint.py path (the payload contract only needs
step/params/opt_state). ``extract_replica`` slices one member back out
as a plain ``LMTrainState`` — including an ``optax.adamw``-shaped
optimizer state rebuilt from the fused inner state — so a finished sweep
member exports a normal single-model checkpoint.

Scope (enforced in __init__): causal-LM loss, no masked-LM, no gradient
accumulation, no tp-overlap. The fused step runs WITHOUT
activation_rules_scope — logical sharding constraints are no-ops under
vmap's extra axis; fused replicas target single-slice packing where the
batch axes carry the parallelism.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, replace as dc_replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh

from ..parallel.mesh import BATCH_AXES
from ..telemetry import TrainTelemetry
from ..telemetry import events as tev
from ..telemetry.core import Registry
from ..utils import flops
from .lm_trainer import LMTrainer, LMTrainerConfig, LMTrainState, make_adamw
from .resilience import FaultInjector


class HFTATrainState(struct.PyTreeNode):
    """Stacked train state: ``step`` is a lockstep scalar; every other
    leaf carries a leading ``[K]`` replica axis."""
    step: jax.Array
    params: Any
    opt_state: Any
    nonfinite_streak: Any   # [K] int32 — consecutive dropped steps
    frozen: Any             # [K] bool  — permanently parked replicas

    @property
    def k(self) -> int:
        return int(self.frozen.shape[0])


@dataclass(frozen=True)
class HFTAHyperparams:
    """Per-replica sweep axes. All tuples have the same length K; scalars
    not swept are broadcast from the base LMTrainerConfig."""
    learning_rates: Tuple[float, ...]
    seeds: Tuple[int, ...]
    weight_decays: Tuple[float, ...]
    warmup_steps: Tuple[int, ...]

    @property
    def k(self) -> int:
        return len(self.learning_rates)

    @classmethod
    def sweep(cls, k: int, config: LMTrainerConfig,
              learning_rates: Optional[Sequence[float]] = None,
              seeds: Optional[Sequence[int]] = None,
              weight_decays: Optional[Sequence[float]] = None,
              warmup_steps: Optional[Sequence[int]] = None
              ) -> "HFTAHyperparams":
        def axis(given, default):
            if given is None:
                return (default,) * k
            if len(given) != k:
                raise ValueError(f"sweep axis has {len(given)} values, "
                                 f"expected K={k}")
            return tuple(given)
        hp = cls(
            learning_rates=axis(learning_rates, config.learning_rate),
            seeds=axis(seeds, 0) if seeds is None
            else axis(seeds, None),
            weight_decays=axis(weight_decays, config.weight_decay),
            warmup_steps=axis(warmup_steps, config.warmup_steps),
        )
        return hp

    def replica_config(self, base: LMTrainerConfig,
                       k: int) -> LMTrainerConfig:
        """The solo LMTrainerConfig replica k is equivalent to."""
        return dc_replace(base,
                          learning_rate=self.learning_rates[k],
                          weight_decay=self.weight_decays[k],
                          warmup_steps=self.warmup_steps[k])

    def as_arrays(self) -> Dict[str, jax.Array]:
        return {
            "lr": jnp.asarray(self.learning_rates, jnp.float32),
            "wd": jnp.asarray(self.weight_decays, jnp.float32),
            "warmup": jnp.asarray(self.warmup_steps, jnp.int32),
        }


def _select_replicas(ok, new_tree, old_tree):
    """Leaf-wise where along the leading [K] axis: keep `new` where ok."""
    def sel(n, o):
        mask = ok.reshape(ok.shape + (1,) * (n.ndim - 1))
        return jnp.where(mask, n, o)
    return jax.tree.map(sel, new_tree, old_tree)


def poison_replica(state: HFTATrainState, k: int) -> HFTATrainState:
    """Multiply replica k's params by NaN (fault injection: the
    nan-replica:K@N drill). Siblings are multiplied by 1.0 — bitwise
    unchanged — so the drill can assert true isolation."""
    kk = state.k
    bad = jnp.where(jnp.arange(kk) == k, jnp.nan, 1.0)

    def poison(p):
        return p * bad.reshape((kk,) + (1,) * (p.ndim - 1)).astype(p.dtype)
    return state.replace(params=jax.tree.map(poison, state.params))


class HFTATrainer:
    """K-replica horizontally fused LM trainer (see module docstring)."""

    def __init__(self, model, mesh: Mesh,
                 config: Optional[LMTrainerConfig] = None,
                 hparams: Optional[HFTAHyperparams] = None,
                 k: int = 2, freeze_after: int = 3):
        self.config = config or LMTrainerConfig()
        self.hparams = hparams or HFTAHyperparams.sweep(k, self.config)
        self.model = model
        self.mesh = mesh
        self.freeze_after = int(freeze_after)
        cfg = self.config
        if cfg.masked_lm:
            raise ValueError("HFTA fusion supports causal LM only "
                             "(masked_lm=False)")
        if cfg.accum_steps != 1:
            raise ValueError("HFTA fusion does not compose with gradient "
                             "accumulation (accum_steps must be 1)")
        if getattr(model.config, "tp_overlap", False):
            raise ValueError("HFTA fusion does not compose with tp_overlap")
        # The solo trainer we mirror: its _loss_fn is THE loss (vmapped
        # verbatim below) and its config carries the shared scalars.
        self._lm = LMTrainer(model, mesh, config=self.config)
        # Replica-invariant optimizer prefix of make_adamw: optax.adamw is
        # chain(scale_by_adam, add_decayed_weights, scale_by_learning_rate)
        # — the first link shares b1/b2/eps across replicas, so it runs as
        # one transformation under vmap; the wd/lr tail varies per replica
        # and is applied manually with the [K] hyperparameter vectors.
        self._inner_tx = optax.chain(
            optax.clip_by_global_norm(cfg.grad_clip),
            optax.scale_by_adam(b1=cfg.b1, b2=cfg.b2, eps=1e-8),
        )
        self._hp_arrays = self.hparams.as_arrays()
        # Slice sharing vs batch sharding. Replicas are INDEPENDENT (the
        # only cross-replica op is metric stacking), so when K divides
        # the mesh batch-axis extent the [K] axis itself shards over the
        # devices: whole replicas land on disjoint device groups, the
        # step runs with ZERO cross-device collectives, and the optimizer
        # touches each replica's state exactly once (replicated [K,...]
        # params would re-run all K adam updates on every device). When
        # K doesn't divide, fall back to sharding the per-replica batch
        # dim (dim 1 of [K, B, S]) with params replicated — still no
        # redundant forward/backward, at the cost of a grad all-reduce.
        # Both are placement-only at nb==1, which keeps the K=1
        # single-device bitwise pin intact.
        P = jax.sharding.PartitionSpec
        nb = math.prod(mesh.shape[a] for a in BATCH_AXES)
        self._replica_sharding = None
        self._batch_sharding = None
        if nb > 1 and self.k % nb == 0:
            self._replica_sharding = jax.sharding.NamedSharding(
                mesh, P(BATCH_AXES))
            self._batch_sharding = self._replica_sharding   # dim 0 = K
        elif nb > 1 and cfg.global_batch_size % nb == 0:
            self._batch_sharding = jax.sharding.NamedSharding(
                mesh, P(None, BATCH_AXES))
        self._step = jax.jit(self._fused_step_fn, donate_argnums=(0,))

    @property
    def k(self) -> int:
        return self.hparams.k

    # -- init ---------------------------------------------------------------

    def init_state(self) -> HFTATrainState:
        """Per-replica init stacked along axis 0. Each replica runs the
        EXACT solo init (same shard_init call, its own seed-derived key),
        so replica k starts bit-identical to a plain LMTrainer seeded the
        same way; the stack happens after the fact."""
        per_replica = [
            self._lm.init_state(jax.random.PRNGKey(seed))
            for seed in self.hparams.seeds
        ]
        params = jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[s.params for s in per_replica])
        opt_state = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[self._inner_tx.init(s.params) for s in per_replica])
        kk = self.k
        state = HFTATrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            nonfinite_streak=jnp.zeros((kk,), jnp.int32),
            frozen=jnp.zeros((kk,), bool),
        )
        # Commit EVERY leaf onto the mesh. The stacked params inherit the
        # solo init's mesh placement but optax scalars (adam count) and
        # the step counter are born on the default device, and
        # restore_checkpoint reuses this state's layout as the template —
        # a mixed device set poisons the fused jit after restore. Under
        # slice sharing the [K,...] leaves shard along K; everything else
        # (the step counter) is replicated.
        rep = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
        by_k = self._replica_sharding

        def _place(x):
            if by_k is not None and getattr(x, "ndim", 0) >= 1 \
                    and x.shape[0] == kk:
                return jax.device_put(x, by_k)
            return jax.device_put(x, rep)

        return jax.tree.map(_place, state)

    # -- the fused step -----------------------------------------------------

    def _lr_at(self, count, lr, warmup):
        """The schedule value optax's make_lr_schedule(cfg_k) yields at
        `count`, with lr/warmup as traced per-replica scalars. Formulas
        replicate optax.linear_schedule / warmup_cosine_decay_schedule
        term for term so the linear path is bitwise-pinned by the K=1
        exactness test."""
        cfg = self.config
        w = jnp.maximum(1, warmup)
        c = jnp.clip(count, 0, w)
        frac = 1 - c / w
        warm = (0.0 - lr) * (frac ** 1) + lr        # polynomial, power=1
        if cfg.lr_schedule == "linear":
            return warm
        if cfg.lr_schedule != "cosine":
            raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r}")
        alpha = cfg.end_lr_fraction                  # end/peak, shared
        total = jnp.maximum(cfg.decay_steps, w + 1)
        ds = total - w
        c2 = jnp.clip(count - w, 0, ds)
        cosine = 0.5 * (1 + jnp.cos(jnp.pi * (c2 / ds)))
        decayed = (1 - alpha) * (cosine ** 1.0) + alpha
        return jnp.where(count < w, warm, lr * decayed)

    def _map_replicas(self, fn):
        """vmap over the leading [K] axis — except K=1, which squeezes
        and re-expands instead. The batched K=1 program is numerically
        identical to the solo one for every op EXCEPT a ~1e-10
        reduction-order wobble in LayerNorm bias grads (XLA fuses the
        batched backward sum differently); squeezing preserves the solo
        program bit for bit, which is what pins the K=1 exactness test,
        and skips a pointless unit batch dim."""
        if self.k > 1:
            return jax.vmap(fn)

        def mapped(*xs):
            out = fn(*[jax.tree.map(lambda a: a[0], x) for x in xs])
            return jax.tree.map(lambda a: a[None], out)
        return mapped

    def _fused_step_fn(self, state, hp, tokens, targets, mask):
        def forward(params, t, y, m):
            (loss, acc), grads = jax.value_and_grad(
                self._lm._loss_fn, has_aux=True)(params, t, y, m)
            return loss, acc.astype(jnp.float32), grads

        loss, acc, grads = self._map_replicas(forward)(
            state.params, tokens, targets, mask)

        def update(params, inner, g, lr, wd, warmup):
            # pre-update count: scale_by_adam and scale_by_schedule march
            # in lockstep in the solo chain, so adam's count doubles as
            # the schedule step
            count = inner[1].count
            u, new_inner = self._inner_tx.update(g, inner, params)
            u = jax.tree.map(lambda ui, pi: ui + wd * pi, u, params)
            step_size = -self._lr_at(count, lr, warmup)
            u = jax.tree.map(
                lambda ui: jnp.array(step_size, dtype=ui.dtype) * ui, u)
            return optax.apply_updates(params, u), new_inner

        new_params, new_opt = self._map_replicas(update)(
            state.params, state.opt_state, grads,
            hp["lr"], hp["wd"], hp["warmup"])

        # per-replica divergence guard (vector form of
        # resilience.guard_nonfinite_update) + freeze
        gnorm = self._map_replicas(optax.global_norm)(grads)
        finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        ok = finite & ~state.frozen
        params = _select_replicas(ok, new_params, state.params)
        opt_state = _select_replicas(ok, new_opt, state.opt_state)
        streak = jnp.where(
            ok, 0,
            jnp.where(state.frozen, state.nonfinite_streak,
                      state.nonfinite_streak + 1)).astype(jnp.int32)
        frozen = state.frozen | (streak >= self.freeze_after)
        new_state = HFTATrainState(
            step=state.step + 1, params=params, opt_state=opt_state,
            nonfinite_streak=streak, frozen=frozen)
        metrics = {"loss": loss, "accuracy": acc,
                   "nonfinite_streak": streak, "frozen": frozen}
        return new_state, metrics

    def train_step(self, state: HFTATrainState, tokens, targets, mask=None):
        """One fused step over a [K, B, S] batch; metrics come back as
        [K] vectors. Deliberately NOT under activation_rules_scope (see
        module docstring)."""
        if mask is None:
            mask = jnp.ones(targets.shape, jnp.float32)
        if self._batch_sharding is not None:
            tokens = jax.device_put(tokens, self._batch_sharding)
            targets = jax.device_put(targets, self._batch_sharding)
            mask = jax.device_put(mask, self._batch_sharding)
        return self._step(state, self._hp_arrays, tokens, targets, mask)

    # -- per-replica extraction / checkpoints --------------------------------

    def extract_replica(self, state: HFTATrainState, k: int) -> LMTrainState:
        """Slice replica k back out as a plain LMTrainState whose
        opt_state has the exact make_adamw(cfg_k) chain shape, so it
        checkpoints/restores like any solo run."""
        take = lambda x: x[k]
        params = jax.tree.map(take, state.params)
        inner = jax.tree.map(take, state.opt_state)
        adam = inner[1]                              # ScaleByAdamState
        cfg_k = self.hparams.replica_config(self.config, k)
        tx = make_adamw(cfg_k)
        full = tx.init(params)
        # chain(clip, adamw) state:
        #   (EmptyState, (ScaleByAdamState, EmptyState, ScaleByScheduleState))
        opt_state = (full[0], (
            full[1][0]._replace(count=adam.count, mu=adam.mu, nu=adam.nu),
            full[1][1],
            full[1][2]._replace(count=adam.count),
        ))
        return LMTrainState(
            step=state.step, params=params, opt_state=opt_state,
            tx=tx, apply_fn=self.model.apply,
            nonfinite_streak=jax.tree.map(take, state.nonfinite_streak))

    def export_replica_checkpoint(self, directory: str,
                                  state: HFTATrainState, k: int,
                                  block: bool = True) -> str:
        """Write replica k as a NORMAL single-model checkpoint a plain
        LMTrainer can restore (the finished-sweep-member export path)."""
        from .checkpoint import save_checkpoint
        return save_checkpoint(directory, self.extract_replica(state, k),
                               block=block)

    # -- benchmark loop ------------------------------------------------------

    def _replica_flops_per_step(self, state) -> float:
        cfg, mcfg = self.config, self.model.config
        n_params = flops.param_count(state.params) // self.k
        per_token = flops.transformer_train_flops_per_token(
            n_params, mcfg.num_layers, mcfg.embed_dim, cfg.seq_len,
            causal=getattr(mcfg, "causal", True))
        return per_token * cfg.global_batch_size * cfg.seq_len

    def benchmark(self, state: HFTATrainState, dataset,
                  num_steps: int = 50, warmup_steps: int = 5,
                  log: Callable[[str], None] = print,
                  registry: Optional[Registry] = None,
                  faults: Optional[FaultInjector] = None,
                  step_hook: Optional[Callable] = None,
                  events=None
                  ) -> Tuple[HFTATrainState, Dict[str, Any]]:
        """Timed fused loop. `dataset` yields ([K,B,S] tokens, [K,B,S]
        targets). Per-replica throughput/MFU/goodput land as LABELED
        tpu_worker_* series (labels={"replica": k}) on one shared
        registry — the per-job view the packing controller scrapes.
        `events` (an EventLog) gets the same treatment: all K replicas
        share the file, so each replica's records are emitted through a
        bound view stamping the matching ``replica`` label."""
        cfg = self.config
        kk = self.k
        reg = registry if registry is not None else Registry()
        tels = [TrainTelemetry(reg, labels={"replica": str(k)})
                for k in range(kk)]
        evs = ([events.bind(replica=str(k)) for k in range(kk)]
               if events is not None else None)
        if faults is None:
            faults = FaultInjector.from_env()
        if faults is not None and faults.events is None:
            faults.events = events

        it = iter(dataset)
        tokens, targets = next(it)
        replica_flops = self._replica_flops_per_step(state)
        replica_tokens_per_step = cfg.global_batch_size * cfg.seq_len
        n_devices = self.mesh.size

        state, metrics = self.train_step(state, tokens, targets)  # compile
        for _ in range(max(0, warmup_steps - 1)):
            tokens, targets = next(it)
            state, metrics = self.train_step(state, tokens, targets)
        np.asarray(metrics["loss"])                  # sync before timing

        base_step = int(state.step)
        log_every = max(1, min(cfg.log_every, num_steps))
        prev_frozen = np.asarray(state.frozen).astype(bool).copy()
        windows: List[Dict[str, Any]] = []
        t0 = g0 = time.perf_counter()
        start = t0
        for i in range(1, num_steps + 1):
            if faults is not None:
                k_poison = faults.check_nan_replica(base_step + i - 1)
                if k_poison is not None:
                    log(f"fault-inject: NaN into replica {k_poison} "
                        f"at step {base_step + i - 1}")
                    state = poison_replica(state, k_poison)
            tokens, targets = next(it)
            state, metrics = self.train_step(state, tokens, targets)
            if step_hook is not None:
                step_hook(state, base_step + i)
            if i % log_every == 0:
                loss = np.asarray(metrics["loss"])   # host sync
                t1 = time.perf_counter()
                dt = max(t1 - t0, 1e-9)
                streaks = np.asarray(metrics["nonfinite_streak"])
                frozen = np.asarray(metrics["frozen"])
                tps_replica = replica_tokens_per_step * log_every / dt
                mfu_stats = flops.throughput_stats(
                    replica_flops, log_every / dt, 1)
                for k in range(kk):
                    tels[k].host_gap_seconds.observe(max(t1 - g0, 0.0))
                    tels[k].observe_steps(dt / log_every, log_every)
                    tels[k].update_window(tokens_per_sec=tps_replica,
                                          mfu=mfu_stats.get("mfu"),
                                          step=base_step + i)
                    tels[k].record_streak(int(streaks[k]))
                    # a replica freezing is a discrete, precious fact —
                    # one labeled record per transition, not per window
                    if evs is not None and frozen[k] and not prev_frozen[k]:
                        evs[k].emit(tev.REPLICA_FROZEN,
                                    step=base_step + i,
                                    streak=int(streaks[k]))
                prev_frozen = frozen.astype(bool).copy()
                windows.append({
                    "steps": log_every, "seconds": dt,
                    "loss": loss.tolist(), "frozen": frozen.tolist(),
                })
                log(f"hfta step {base_step + i} "
                    f"loss[K]={np.round(loss, 4).tolist()} "
                    f"agg_tokens/s={tps_replica * kk:,.0f} "
                    f"frozen={int(frozen.sum())}/{kk}")
                t0 = time.perf_counter()
                g0 = t0
        wall = time.perf_counter() - start

        steady = windows[1:] if len(windows) > 1 else windows
        steady_steps = sum(w["steps"] for w in steady)
        steady_secs = max(sum(w["seconds"] for w in steady), 1e-9)
        steps_per_sec = steady_steps / steady_secs
        agg_tokens_per_sec = replica_tokens_per_step * kk * steps_per_sec
        agg_stats = flops.throughput_stats(
            replica_flops * kk, steps_per_sec, n_devices)
        final_loss = windows[-1]["loss"] if windows else [float("nan")] * kk
        frozen_now = np.asarray(state.frozen)
        per_replica = {
            "tokens_per_sec": [replica_tokens_per_step * steps_per_sec] * kk,
            "mfu": [flops.throughput_stats(replica_flops, steps_per_sec,
                                           1).get("mfu")] * kk,
            "goodput": [float(t.goodput.value) for t in tels],
            "loss": [float(x) for x in final_loss],
            "frozen": frozen_now.tolist(),
            "nonfinite_streak": np.asarray(state.nonfinite_streak).tolist(),
        }
        result = {
            "k": kk,
            "tokens_per_sec": agg_tokens_per_sec,
            "tokens_per_sec_per_device": agg_tokens_per_sec / n_devices,
            "wall_seconds": wall,
            "final_loss": per_replica["loss"],
            "frozen_replicas": int(frozen_now.sum()),
            "per_replica": per_replica,
        }
        result.update(agg_stats)
        return state, result


__all__ = ["HFTAHyperparams", "HFTATrainState", "HFTATrainer",
           "poison_replica"]
