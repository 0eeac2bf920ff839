"""Transformer-ladder benchmark workload — the remaining BASELINE configs.

The reference ladder (BASELINE.json configs[2-4]) extends its in-repo ResNet
example with BERT-large pretraining, GPT-2-medium LM, and multi-slice
ViT-B/16 — workloads the reference would ship as opaque Horovod images
(SURVEY.md §2.2). This is the TPU-native entrypoint for all three:

  gpt2 / bert — LMTrainer over a dp×fsdp×tp mesh, synthetic token stream,
                tokens/sec reported;
  vit         — image Trainer over a dcn×dp mesh (multi-slice via
                --num-slices: the dcn axis carries the cross-slice gradient
                allreduce hierarchically), images/sec reported.

Same process contract as examples.benchmark: launcher polls rank-0's status
channel; workers train; --train-dir checkpoints and RESUMES (the gang-
restart story: on pod restart the whole gang relaunches and picks up from
the latest step).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from ._report import device_ids, with_run_report

#: BERT MLM objective constants, shared by the synthetic and real-data
#: paths so they stay comparable: corruption rate, mask id = vocab - 1
MLM_MASK_RATE = 0.15


def _worker_telemetry(metrics_port, train_dir, events, log):
    """The run's WorkerTelemetry: a /metrics server when --metrics-port
    is given (0 = ephemeral, for tests), an event log at
    <train_dir>/events.jsonl when a train dir exists (so resilience runs
    record their drains with zero extra flags). `events` borrows an
    already-open log (main opens --event-log before distributed init) —
    ownership stays with the caller. Returns (telemetry, owns_events)."""
    from ..telemetry import EventLog, WorkerTelemetry

    owns = events is None
    if events is None:
        path = os.path.join(train_dir, "events.jsonl") if train_dir else None
        events = EventLog(path) if path else None
    if events is not None and os.environ.get("TPU_PACK_GROUP"):
        # packed jobs share one worker process (and one event file);
        # stamp the pack group into every record, mirroring the
        # labeled-metrics contract (bind delegates close to the owner)
        events = events.bind(pack_group=os.environ["TPU_PACK_GROUP"])
    wtel = WorkerTelemetry(events=events)
    if metrics_port is not None:
        log(f"worker /metrics listening on port "
            f"{wtel.serve(port=metrics_port).port}")
    return wtel, owns and events is not None


@with_run_report
def run_lm_benchmark(
    workload: str = "gpt2",
    size: Optional[str] = None,
    batch_per_device: int = 8,
    seq_len: int = 512,
    num_steps: int = 50,
    warmup_steps: int = 5,
    eval_steps: int = 0,
    dtype_name: str = "bfloat16",
    tp: int = 1,
    pp: int = 1,
    pp_schedule: str = "gpipe",
    pp_interleave: int = 1,
    sp: int = 1,
    num_slices: int = 1,
    attention: str = "auto",
    remat: bool = False,
    remat_policy: str = "none",
    moe_experts: int = 0,
    ep: int = 1,
    fused_xent: bool = False,
    tp_overlap: bool = False,
    tp_ring: str = "uni",
    accum_steps: int = 1,
    data_dir: Optional[str] = None,
    train_dir: Optional[str] = None,
    ckpt_every: int = 0,
    ckpt_keep: int = 0,
    step_deadline: float = 0.0,
    divergence_k: int = 3,
    stop_check_every: Optional[int] = None,
    stop_at_step: Optional[int] = None,
    lr: Optional[float] = None,
    lr_warmup_steps: Optional[int] = None,
    profile_dir: Optional[str] = None,
    metrics_port: Optional[int] = None,
    events=None,
    log: Callable[[str], None] = print,
) -> Tuple[object, Dict[str, float]]:
    """GPT-2 / llama / BERT token-stream benchmark on a dcn×dp×fsdp×tp
    mesh.

    Preemption contract: the synthetic streams are STEP-KEYED (batch i is
    a pure function of global step i), so a run killed at step N and
    restarted resumes with exactly the batches the uninterrupted run
    would have trained on — resumption is token-identical, and
    --stop-at-step T makes the restarted run finish at the same global
    step the first run was aiming for. Real --data-dir shards replay from
    their own file order instead."""
    import jax
    import jax.numpy as jnp

    from ..data.synthetic import synthetic_token_batch
    from ..models.transformer import create_lm
    from ..parallel import MeshConfig, make_mesh
    from ..train.lm_trainer import LMTrainer, LMTrainerConfig
    from ..train.resilience import ResilienceConfig, ResilienceContext

    n = jax.device_count()
    if ep > 1 and not moe_experts:
        raise ValueError("--ep needs --moe-experts (nothing to shard)")
    if moe_experts and moe_experts % ep:
        # the sharding rules silently REPLICATE a non-divisible expert dim
        # (parallel/sharding._divisible_spec), which would mislabel a
        # data-parallel run as expert-parallel — reject instead
        raise ValueError(f"--moe-experts={moe_experts} must be divisible "
                         f"by --ep={ep}")
    if n % (tp * ep * sp * num_slices):
        raise ValueError(f"{n} devices not divisible by tp={tp} × ep={ep} "
                         f"× sp={sp} × slices={num_slices}")
    if sp > 1:
        # context parallelism: seq sharded over sp, attention rings the K/V
        # shards (parallel/ring_attention.py via the model's "ring" impl)
        if seq_len % sp:
            raise ValueError(f"--seq-len={seq_len} must be divisible by "
                             f"--sp={sp}")
        if attention == "auto":
            attention = "ring"
        elif attention != "ring":
            raise ValueError(f"--sp={sp} shards the sequence axis; "
                             f"--attention must be 'ring' (got "
                             f"{attention!r})")
    dp = n // (tp * ep * sp * num_slices)   # dp fills what the rest leaves
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp, ep=ep, sp=sp,
                                dcn=num_slices))
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32

    name = f"{workload}-{size}" if size else workload
    overrides = {}
    if moe_experts:
        # expert-parallel MoE: every other block's FFN becomes a top-2
        # mixture routed over the ep axis (parallel/moe.py); the trainer
        # folds the load-balancing aux loss in automatically
        overrides = dict(num_experts=moe_experts)
    if tp_overlap:
        # ring collective-matmul projections + vocab-parallel overlapped
        # loss (parallel/collectives.py): only meaningful with a tp ring
        if tp <= 1:
            raise ValueError("--tp-overlap needs --tp > 1 (nothing to "
                             "ring over)")
        if pp > 1:
            raise ValueError("--tp-overlap composes with the flat trainer "
                             "only (the pipeline's partial-manual "
                             "shard_map already binds pp)")
        overrides["tp_overlap"] = True
        overrides["tp_ring"] = tp_ring
    elif tp_ring != "uni":
        raise ValueError("--tp-ring=bidir only changes the overlap ring "
                         "collectives; it needs --tp-overlap")
    model = create_lm(name, dtype=dtype, attention=attention, remat=remat,
                      remat_policy=remat_policy, max_len=max(seq_len, 32),
                      **overrides)
    cfg_vocab = model.config.vocab_size
    masked = workload == "bert"
    if fused_xent and masked:
        raise ValueError("--fused-xent supports the causal LM only (BERT's "
                         "MLM head has extra layers before the tied "
                         "decoder)")

    global_batch = batch_per_device * n
    opt_overrides = {}
    if lr is not None:
        opt_overrides["learning_rate"] = lr
    if lr_warmup_steps is not None:
        opt_overrides["warmup_steps"] = lr_warmup_steps
    tcfg = LMTrainerConfig(global_batch_size=global_batch, seq_len=seq_len,
                           masked_lm=masked, fused_xent=fused_xent,
                           accum_steps=accum_steps, **opt_overrides)
    wtel, owns_events = _worker_telemetry(metrics_port, train_dir,
                                          events, log)
    if pp > 1:
        # GPipe over the pp axis: stage-sliced CausalLM — or MaskedLM
        # (bert): the mask stream rides the relays and the last stage
        # runs the MLM transform head (parallel/pipeline.py
        # pipeline_mlm_loss)
        # learned-position requirement is validated by PipelineLMTrainer
        # itself (the invariant lives there); MoE composition constraints
        # (gpipe-only, whole dense+MoE periods per stage) likewise. bert
        # and --sp compose with BOTH schedules (1F1B consumes the mask at
        # the last virtual stage / rings the sp shards in-schedule).
        if moe_experts and pp_schedule != "gpipe":
            raise ValueError("--pp with --moe-experts composes with "
                             "--pp-schedule gpipe only (1F1B stage bodies "
                             "are dense)")
        # --fused-xent composes: the chunked tied-head loss runs on the
        # LAST stage only (PipelineLMTrainer fused_xent)
        if accum_steps > 1:
            raise ValueError("--accum-steps is redundant with --pp: the "
                             "pipeline trainer already streams "
                             "microbatches; drop the flag")
        from ..train.pp_trainer import PipelineLMTrainer
        if n % (pp * tp * ep * sp * num_slices):
            raise ValueError(f"{n} devices not divisible by pp={pp} × "
                             f"tp={tp} × ep={ep} × sp={sp} × "
                             f"slices={num_slices}")
        # tp composes via GSPMD inside each stage (Megatron collectives);
        # ep likewise — the MoE stack's expert dim is PLACED over ep and
        # the stage's dispatch einsums lower to the expert all-to-all; sp
        # shards the stream's sequence dim and rings stage attention
        # (train/pp_trainer.py)
        pp_mesh = make_mesh(MeshConfig(
            pp=pp, tp=tp, ep=ep, sp=sp,
            dp=n // (pp * tp * ep * sp * num_slices),
            dcn=num_slices))
        pp_trainer = PipelineLMTrainer(model.config, pp_mesh, tcfg,
                                       schedule=pp_schedule,
                                       interleave=pp_interleave)
        pp_state = pp_trainer.init_state(jax.random.PRNGKey(0))
        from ..train.checkpoint import (last_restore_info, maybe_resume,
                                        maybe_save, wait_for_checkpoints)
        pp_resilience = ResilienceContext(
            ResilienceConfig.from_env(train_dir=train_dir,
                                      divergence_k=divergence_k,
                                      step_deadline=step_deadline,
                                      stop_check_every=stop_check_every),
            log=log, events=wtel.events, telemetry=wtel.train)
        pp_resilience.__enter__()
        # checkpoints live in CANONICAL layer order (schedule-agnostic);
        # the live state may be 1F1B-interleaved — convert around resume
        pp_state = pp_trainer.from_canonical_state(
            maybe_resume(train_dir, pp_trainer.canonical_state(pp_state),
                         log))
        pp_resumed_step = int(pp_state.step)
        pp_info = last_restore_info()
        pp_resilience.record_restore(pp_resumed_step,
                                     path=pp_info.get("path"),
                                     seconds=pp_info.get("seconds"),
                                     leaves=pp_info.get("leaves"),
                                     resharded=pp_info.get("resharded"))
        if stop_at_step is not None:
            remaining = (stop_at_step - pp_resumed_step
                         - max(1, warmup_steps))
            if remaining < 1:
                log(f"stop_at_step={stop_at_step} already reached at "
                    f"resumed step {pp_resumed_step}; running 1 step")
            num_steps = max(1, remaining)

        class RawStream:
            """Step-keyed like the unpiped TokenStream: batch i is
            fold_in(base, i), so resumed runs replay the same batches."""

            def __init__(self, start: int = 0):
                self._base = jax.random.PRNGKey(1)
                self._i = start

            def __iter__(self):
                return self

            def __next__(self):
                sub, msub = jax.random.split(
                    jax.random.fold_in(self._base, self._i))
                self._i += 1
                toks, tgts = synthetic_token_batch(sub, global_batch,
                                                   seq_len, cfg_vocab)
                if masked:
                    # same MLM objective as the unpiped stream: targets
                    # are the ORIGINAL tokens, inputs corrupted at the
                    # masked slots with the mask id
                    mask = jax.random.uniform(
                        msub, toks.shape) < MLM_MASK_RATE
                    return (jnp.where(mask, cfg_vocab - 1, toks), toks,
                            mask.astype(jnp.float32))
                return toks, tgts

            def close(self):
                pass

        if data_dir:
            from ..data.tokenstream import NpyTokenDataset
            # the feeder reshapes each window into the [M, mb, S] stream
            # and device_puts it with the TRAINER's 3-D batch sharding —
            # no flat PartitionSpec matches the [M, mb] split's element
            # distribution, so placing the final layout directly is the
            # only transfer-free option
            M = pp_trainer.num_microbatches
            mb = global_batch // M

            if masked:
                pp_mlm_rng = np.random.RandomState(3)

                def pp_transform(win):
                    toks = win[:, :-1]
                    mask = (pp_mlm_rng.random_sample(toks.shape)
                            < MLM_MASK_RATE)
                    return (np.where(mask, cfg_vocab - 1, toks)
                            .astype(np.int32).reshape(M, mb, seq_len),
                            toks.reshape(M, mb, seq_len),
                            mask.astype(np.float32).reshape(M, mb,
                                                            seq_len))
            else:
                def pp_transform(win):
                    return (win[:, :-1].reshape(M, mb, seq_len),
                            win[:, 1:].reshape(M, mb, seq_len))

            pp_stream = NpyTokenDataset(data_dir, global_batch, seq_len,
                                        sharding=pp_trainer.batch_sharding,
                                        host_transform=pp_transform,
                                        vocab_size=cfg_vocab)
        else:
            pp_stream = RawStream(start=pp_resumed_step)
        from ..train.checkpoint import periodic_saver
        saver = periodic_saver(train_dir, ckpt_every, log,
                               keep_last=ckpt_keep,
                               resilience=pp_resilience)
        canonical_hook = (None if saver is None else (
            lambda st, step: saver(pp_trainer.canonical_state(st), step)))
        try:
            pp_state, pp_metrics = pp_trainer.benchmark(
                pp_state, pp_stream, num_steps=num_steps,
                warmup_steps=warmup_steps, log=log,
                step_hook=canonical_hook, resilience=pp_resilience,
                telemetry=wtel.train)
            if eval_steps:
                # held-out evaluation continues the stream past the
                # trained batches (same contract as the unpiped path)
                ev = pp_trainer.evaluate(pp_state, pp_stream,
                                         num_batches=eval_steps)
                pp_metrics.update(ev)
                log(f"val_loss: {ev['val_loss']:.3f}  "
                    f"perplexity: {ev['perplexity']:.1f}  "
                    f"({eval_steps} batches)")
            if wtel.events is not None:
                from ..telemetry import events as tev
                wtel.events.emit(tev.RUN_COMPLETE,
                                 step=int(pp_state.step))
        finally:
            pp_stream.close()
            pp_resilience.__exit__(None, None, None)
            wtel.close(close_events=owns_events)
        # non-blocking final save: the write overlaps the canonical-state
        # host transfer teardown; the join below makes it durable before
        # the process can exit
        maybe_save(train_dir, pp_trainer.canonical_state(pp_state), log,
                   block=False)
        wait_for_checkpoints()
        return pp_state, pp_metrics
    trainer = LMTrainer(model, mesh, tcfg)
    state = trainer.init_state(jax.random.PRNGKey(0))

    from ..train.checkpoint import (last_restore_info, maybe_resume,
                                    maybe_save, wait_for_checkpoints)
    resilience = ResilienceContext(
        ResilienceConfig.from_env(train_dir=train_dir,
                                  divergence_k=divergence_k,
                                  step_deadline=step_deadline,
                                  stop_check_every=stop_check_every),
        log=log, events=wtel.events, telemetry=wtel.train)
    # entering fires the corrupt-latest-checkpoint fault (if injected)
    # BEFORE the resume below, so the fallback path is what gets tested
    resilience.__enter__()
    try:
        state = maybe_resume(train_dir, state, log)
        resumed_step = int(state.step)
        restore_info = last_restore_info()
        resilience.record_restore(resumed_step,
                                  path=restore_info.get("path"),
                                  seconds=restore_info.get("seconds"),
                                  leaves=restore_info.get("leaves"),
                                  resharded=restore_info.get("resharded"))
        if stop_at_step is not None:
            # finish at the same GLOBAL step the uninterrupted run would
            # have: warmup batches advance the step counter too
            remaining = stop_at_step - resumed_step - max(1, warmup_steps)
            if remaining < 1:
                log(f"stop_at_step={stop_at_step} already reached at "
                    f"resumed step {resumed_step}; running 1 step")
            num_steps = max(1, remaining)

        class TokenStream:
            """Step-keyed stream: batch i is fold_in(base, i) — a resumed
            run (start = restored step) consumes exactly the batches the
            uninterrupted run would have at each global step."""

            def __init__(self, start: int = 0):
                self._base = jax.random.PRNGKey(1)
                self._i = start

            def __iter__(self):
                return self

            def __next__(self):
                sub, msub = jax.random.split(
                    jax.random.fold_in(self._base, self._i))
                self._i += 1
                toks, tgts = synthetic_token_batch(sub, global_batch,
                                                   seq_len, cfg_vocab)
                if masked:
                    # real MLM objective: targets are the ORIGINAL tokens
                    # at the masked positions and the input is corrupted
                    # there with the mask id (last vocab slot) — without
                    # the corruption the 'loss' is a degenerate copy
                    # objective
                    mask = (jax.random.uniform(msub, toks.shape)
                            < MLM_MASK_RATE)
                    tgts = toks
                    toks = jnp.where(mask, cfg_vocab - 1, toks)
                    return (jax.device_put(toks, trainer.batch_sharding),
                            jax.device_put(tgts, trainer.batch_sharding),
                            jax.device_put(mask.astype(jnp.float32),
                                           trainer.batch_sharding))
                toks = jax.device_put(toks, trainer.batch_sharding)
                tgts = jax.device_put(tgts, trainer.batch_sharding)
                return toks, tgts

            def close(self):
                pass

        if data_dir:
            from ..data.tokenstream import NpyTokenDataset
            transform = None
            if masked:
                # MLM over the real stream: same objective constants as
                # the synthetic branch above (MLM_MASK_RATE, mask id);
                # numpy on the FEEDER thread so every output tensor is
                # device_put with the trainer's sharding (eager jax ops on
                # already-placed global arrays would break on multi-host)
                mlm_rng = np.random.RandomState(3)

                def transform(win):
                    toks = win[:, :-1]
                    mask = mlm_rng.random_sample(toks.shape) < MLM_MASK_RATE
                    return (np.where(mask, cfg_vocab - 1,
                                     toks).astype(np.int32),
                            toks, mask.astype(np.float32))
            stream = NpyTokenDataset(data_dir, global_batch, seq_len,
                                     sharding=trainer.batch_sharding,
                                     vocab_size=cfg_vocab,
                                     host_transform=transform)
        else:
            stream = TokenStream(start=resumed_step)
        from ..train.checkpoint import periodic_saver
        try:
            state, metrics = trainer.benchmark(
                state, stream, num_steps=num_steps,
                warmup_steps=warmup_steps, log=log,
                profile_dir=profile_dir,
                step_hook=periodic_saver(train_dir, ckpt_every, log,
                                         keep_last=ckpt_keep,
                                         resilience=resilience),
                resilience=resilience, telemetry=wtel.train)
            if eval_steps:
                # evaluation continues the stream past the trained
                # batches — fresh batches for synthetic/large-shard runs;
                # point --data-dir at held-out shards for a true
                # validation set
                ev = trainer.evaluate(state, stream,
                                      num_batches=eval_steps)
                metrics.update(ev)
                log(f"val_loss: {ev['val_loss']:.3f}  "
                    f"perplexity: {ev['perplexity']:.1f}  "
                    f"({eval_steps} batches)")
        finally:
            stream.close()
        # non-blocking final save: the write overlaps the resilience/
        # telemetry teardown (and the moe diagnostics probe below); the
        # join at the end makes it durable before return
        maybe_save(train_dir, state, log, block=False)
        if wtel.events is not None:
            # the terminal frontier marker: without it a timeline ends at
            # the last window fetch and the goodput ledger undercounts
            # the useful column
            from ..telemetry import events as tev
            wtel.events.emit(tev.RUN_COMPLETE, step=int(state.step))
    finally:
        resilience.__exit__(None, None, None)
        wtel.close(close_events=owns_events)
    if moe_experts:
        # observable drop rate (parallel/moe.py sows it into the
        # "diagnostics" collection, which train steps don't carry): one
        # forward apply on a fresh batch reads it out. Best-effort — a
        # diagnostics failure must not discard the measured throughput.
        try:
            toks, _ = synthetic_token_batch(
                jax.random.PRNGKey(7), global_batch, seq_len, cfg_vocab)
            # jitted: an eager full-batch apply dispatches (and compiles)
            # the whole transformer one op at a time
            _, diag = jax.jit(
                lambda p, t: model.apply(
                    {"params": p}, t,
                    mutable=["diagnostics", "intermediates"])
            )(state.params, toks)
            rates = jax.tree.leaves(diag.get("diagnostics", {}))
            if rates:
                metrics["moe_drop_rate"] = float(
                    sum(jnp.asarray(r).mean() for r in rates) / len(rates))
                log(f"moe drop rate: {metrics['moe_drop_rate']:.3f}")
        except Exception as exc:  # noqa: BLE001
            log(f"moe drop-rate probe failed: {exc!r}")
    wait_for_checkpoints()        # join the overlapped final save
    # where the run actually lived: a dp=N job whose state sits on one
    # device is a layout bug no throughput number would name
    metrics["state_device_ids"] = device_ids(
        (state.params, state.opt_state))
    metrics["batch_device_ids"] = sorted(
        d.id for d in trainer.batch_sharding.device_set)
    metrics["device_bytes_in_use"] = [
        (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.local_devices()]
    return state, metrics


@with_run_report
def run_hfta_benchmark(
    workload: str = "gpt2",
    size: Optional[str] = None,
    batch_per_device: int = 8,
    seq_len: int = 512,
    num_steps: int = 50,
    warmup_steps: int = 5,
    dtype_name: str = "bfloat16",
    k: int = 8,
    learning_rates=None,
    seeds=None,
    train_dir: Optional[str] = None,
    lr: Optional[float] = None,
    lr_warmup_steps: Optional[int] = None,
    metrics_port: Optional[int] = None,
    events=None,
    log: Callable[[str], None] = print,
) -> Tuple[object, Dict[str, float]]:
    """Horizontally fused sweep benchmark: K model replicas vmap-stacked
    into ONE jitted step (train/hfta.py). Each replica trains on its own
    batch_per_device × device_count batch, so the fused run does K× the
    token work of the solo benchmark per step — the aggregate tokens/sec
    it reports is directly comparable to K sequential solo runs.

    The token stream stays STEP-KEYED like the solo path (replica r's
    batch at global step i is fold_in(fold_in(PRNGKey(1), i), r)), so a
    restarted fused run replays the same per-replica tokens."""
    import jax
    import jax.numpy as jnp

    from ..data.synthetic import synthetic_token_batch
    from ..models.transformer import create_lm
    from ..parallel import MeshConfig, make_mesh
    from ..train.checkpoint import (maybe_resume, maybe_save,
                                    wait_for_checkpoints)
    from ..train.hfta import HFTAHyperparams, HFTATrainer
    from ..train.lm_trainer import LMTrainerConfig

    if workload not in ("gpt2", "llama"):
        raise ValueError(f"--hfta fuses causal-LM workloads only "
                         f"(got {workload!r})")
    n = jax.device_count()
    mesh = make_mesh(MeshConfig(dp=n))   # pure data-parallel gang
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32

    name = f"{workload}-{size}" if size else workload
    model = create_lm(name, dtype=dtype, max_len=max(seq_len, 32))
    vocab = model.config.vocab_size

    global_batch = batch_per_device * n        # PER-REPLICA batch
    opt_overrides = {}
    if lr is not None:
        opt_overrides["learning_rate"] = lr
    if lr_warmup_steps is not None:
        opt_overrides["warmup_steps"] = lr_warmup_steps
    tcfg = LMTrainerConfig(global_batch_size=global_batch, seq_len=seq_len,
                           **opt_overrides)
    hp = HFTAHyperparams.sweep(k, tcfg, learning_rates=learning_rates,
                               seeds=seeds)
    trainer = HFTATrainer(model, mesh, tcfg, hp)
    log(f"hfta: fusing K={k} × {name} replicas, "
        f"lrs={list(hp.learning_rates)} seeds={list(hp.seeds)}")

    wtel, owns_events = _worker_telemetry(metrics_port, train_dir,
                                          events, log)
    try:
        state = trainer.init_state()
        state = maybe_resume(train_dir, state, log)

        @jax.jit
        def fused_batch(i):
            step_key = jax.random.fold_in(jax.random.PRNGKey(1), i)
            keys = jax.vmap(
                lambda r: jax.random.fold_in(step_key, r))(jnp.arange(k))
            return jax.vmap(lambda key: synthetic_token_batch(
                key, global_batch, seq_len, vocab))(keys)

        def stream(start):
            i = start
            while True:
                yield fused_batch(i)
                i += 1

        state, metrics = trainer.benchmark(
            state, stream(int(state.step)), num_steps=num_steps,
            warmup_steps=warmup_steps, log=log, registry=wtel.registry,
            events=wtel.events)
        maybe_save(train_dir, state, log, block=False)
        if wtel.events is not None:
            from ..telemetry import events as tev
            wtel.events.emit(tev.RUN_COMPLETE, step=int(state.step))
    finally:
        wtel.close(close_events=owns_events)
    wait_for_checkpoints()
    metrics["replica_learning_rates"] = list(hp.learning_rates)
    metrics["replica_seeds"] = list(hp.seeds)
    return state, metrics


@with_run_report
def run_vit_benchmark(
    size: str = "b16",
    batch_per_device: int = 32,
    num_steps: int = 50,
    warmup_steps: int = 5,
    dtype_name: str = "bfloat16",
    num_slices: int = 1,
    data_dir: Optional[str] = None,
    train_dir: Optional[str] = None,
    ckpt_every: int = 0,
    ckpt_keep: int = 0,
    step_deadline: float = 0.0,
    divergence_k: int = 3,
    stop_check_every: Optional[int] = None,
    metrics_port: Optional[int] = None,
    events=None,
    log: Callable[[str], None] = print,
) -> Tuple[object, Dict[str, float]]:
    """ViT-B/16 image benchmark; --num-slices 2 is the BASELINE multi-slice
    config (hierarchical allreduce across the dcn axis). data_dir streams
    npy image shards (data/imagefolder.py) instead of synthetic data."""
    import jax
    import jax.numpy as jnp

    from ..data import SyntheticImageDataset
    from ..models.transformer import create_vit
    from ..parallel import MeshConfig, batch_sharding, make_mesh
    from ..train import Trainer, TrainerConfig
    from ..train.resilience import ResilienceConfig, ResilienceContext

    n = jax.device_count()
    mesh = make_mesh(MeshConfig.data_parallel(n, num_slices=num_slices))
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    global_batch = batch_per_device * n

    model = create_vit(f"vit-{size}", num_classes=1000, dtype=dtype)
    cfg = TrainerConfig(global_batch_size=global_batch, num_classes=1000)
    trainer = Trainer(model, mesh, cfg)
    state = trainer.init_state(jax.random.PRNGKey(0))
    from ..train.checkpoint import (maybe_resume, maybe_save,
                                        wait_for_checkpoints)
    wtel, owns_events = _worker_telemetry(metrics_port, train_dir,
                                          events, log)
    resilience = ResilienceContext(
        ResilienceConfig.from_env(train_dir=train_dir,
                                  divergence_k=divergence_k,
                                  step_deadline=step_deadline,
                                  stop_check_every=stop_check_every),
        log=log, events=wtel.events, telemetry=wtel.train)
    resilience.__enter__()
    try:
        state = maybe_resume(train_dir, state, log)
        resilience.record_restore(int(state.step))
        if data_dir is not None:
            from ..data.imagefolder import NpyImageDataset
            dataset = NpyImageDataset(
                data_dir, global_batch, dtype=dtype,
                sharding=batch_sharding(mesh))
        else:
            dataset = SyntheticImageDataset(
                global_batch, num_classes=1000,
                dtype=dtype, sharding=batch_sharding(mesh))
        from ..train.checkpoint import periodic_saver
        try:
            state, metrics = trainer.benchmark(
                state, dataset, num_steps=num_steps,
                warmup_steps=warmup_steps, log=log,
                step_hook=periodic_saver(train_dir, ckpt_every, log,
                                         keep_last=ckpt_keep,
                                         resilience=resilience),
                resilience=resilience, telemetry=wtel.train)
        finally:
            if hasattr(dataset, "close"):
                dataset.close()
        maybe_save(train_dir, state, log, block=False)
        if wtel.events is not None:
            from ..telemetry import events as tev
            wtel.events.emit(tev.RUN_COMPLETE, step=int(state.step))
    finally:
        resilience.__exit__(None, None, None)
        wtel.close(close_events=owns_events)
    wait_for_checkpoints()        # join the overlapped final save
    return state, metrics


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="tpu-lm-benchmarks")
    parser.add_argument("--workload", default="gpt2",
                        choices=["gpt2", "llama", "bert", "vit"])
    parser.add_argument("--size", default=None,
                        help="gpt2: small|medium|large|xl; llama: 1b|7b "
                             "(RoPE+RMSNorm+SwiGLU+GQA); bert: base|large; "
                             "vit: b16|l16 (defaults = BASELINE configs)")
    parser.add_argument("--batch-per-device", type=int, default=None)
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--num-steps", type=int, default=50)
    parser.add_argument("--warmup-steps", type=int, default=5)
    parser.add_argument("--eval-steps", type=int, default=0,
                        help="after training, report val_loss/perplexity "
                             "over N held-out batches (gpt2/bert only)")
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--pp", type=int, default=1,
                        help="pipeline stages (causal LM only)")
    parser.add_argument("--pp-schedule", default="gpipe",
                        choices=["gpipe", "1f1b"],
                        help="gpipe = fill/drain via autodiff; 1f1b = "
                             "interleaved one-forward-one-backward "
                             "(O(pp) in-flight memory, in-schedule grads)")
    parser.add_argument("--pp-interleave", type=int, default=1,
                        help="virtual stages per device for --pp-schedule "
                             "1f1b (divides the pipeline bubble)")
    parser.add_argument("--sp", type=int, default=1,
                        help="sequence/context-parallel degree: seq axis "
                             "sharded over sp, ring attention over the sp "
                             "ICI neighbors (long-context training)")
    parser.add_argument("--moe-experts", type=int, default=0,
                        help="replace every other FFN with an N-expert "
                             "top-2 MoE (expert-parallel over ep)")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel degree (shards MoE experts)")
    parser.add_argument("--accum-steps", type=int, default=1,
                        help="gradient accumulation: microbatches per "
                             "optimizer step (activation memory / N, "
                             "numerically identical update)")
    parser.add_argument("--tp-overlap", action="store_true",
                        help="ring collective-matmul TP projections + "
                             "overlapped vocab-parallel loss (needs "
                             "--tp > 1; see README 'TP overlap')")
    parser.add_argument("--tp-ring", default="uni",
                        choices=["uni", "bidir"],
                        help="overlap ring direction: bidir splits each "
                             "shard in half and rotates the halves in "
                             "opposite directions — half the bytes per "
                             "hop on a bidirectional ICI torus (needs "
                             "--tp-overlap)")
    parser.add_argument("--hfta", type=int, default=0,
                        help="fuse K sweep replicas into one vmap-stacked "
                             "train step (train/hfta.py): K× the token "
                             "work per step, aggregate tokens/sec "
                             "reported; causal LM only")
    parser.add_argument("--hfta-lrs", default=None,
                        help="comma-separated per-replica learning rates "
                             "(K values; default: config lr broadcast)")
    parser.add_argument("--hfta-seeds", default=None,
                        help="comma-separated per-replica init seeds "
                             "(K values; default: all 0)")
    parser.add_argument("--fused-xent", action="store_true",
                        help="chunked tied-head cross-entropy: the full "
                             "[B*S, vocab] logits never hit HBM - slower "
                             "at small scale (~3%% recompute tax) but the "
                             "memory headroom for long-seq/big-vocab runs")
    parser.add_argument("--attention", default="auto",
                        choices=["auto", "dense", "flash", "ring"])
    parser.add_argument("--remat", action="store_true")
    parser.add_argument("--remat-policy", default="none",
                        choices=["none", "dots"])
    parser.add_argument("--data-dir", default=None,
                        help="real-data shards: <stem>_tokens.npy packed "
                             "token streams for gpt2/bert "
                             "(data/tokenstream.py), <stem>_images.npy "
                             "pairs for vit (data/imagefolder.py); omit "
                             "for synthetic data")
    parser.add_argument("--train-dir", default=None)
    parser.add_argument("--ckpt-every", type=int, default=0,
                        help="async checkpoint every N steps into "
                             "--train-dir (mid-run gang restarts resume "
                             "from the last one; 0 = final only)")
    parser.add_argument("--ckpt-keep", type=int, default=0,
                        help="retain only the newest N step_ checkpoints "
                             "(garbage-collect older ones after each "
                             "save; 0 = keep everything)")
    parser.add_argument("--step-deadline", type=float, default=0.0,
                        help="watchdog: seconds a single post-compile "
                             "step may take before the process dumps all "
                             "stacks and aborts with a retryable exit "
                             "code (0 = off; env TPU_STEP_DEADLINE)")
    parser.add_argument("--divergence-k", type=int, default=3,
                        help="consecutive non-finite steps (skipped "
                             "updates) before rolling back to the newest "
                             "checkpoint")
    parser.add_argument("--stop-check-every", type=int, default=None,
                        help="gang stop-bit allgather cadence in steps "
                             "(multi-process only; default 8, env "
                             "TPU_STOP_CHECK_EVERY) — every step costs a "
                             "host round-trip per step, larger values "
                             "trade drain latency for step time")
    parser.add_argument("--stop-at-step", type=int, default=None,
                        help="finish at this GLOBAL step instead of "
                             "running --num-steps past the resume point "
                             "— a preempted+restarted run ends at the "
                             "same step the original was aiming for")
    parser.add_argument("--lr", type=float, default=None,
                        help="peak learning rate (default: trainer's "
                             "2.5e-4)")
    parser.add_argument("--lr-warmup-steps", type=int, default=None,
                        help="optimizer LR warmup steps (default 100; "
                             "short runs want a small value or the LR "
                             "never leaves the ramp)")
    parser.add_argument("--profile-dir", default=None,
                        help="write a jax.profiler trace of the first "
                             "measurement window here (XProf format)")
    parser.add_argument("--metrics-port", type=int,
                        default=(int(os.environ["TPU_METRICS_PORT"])
                                 if os.environ.get("TPU_METRICS_PORT")
                                 else None),
                        help="serve worker /metrics (Prometheus text) + "
                             "/healthz + /events on this port (0 = pick "
                             "a free port; omit to disable; defaults to "
                             "$TPU_METRICS_PORT, which the controller "
                             "injects so it can federate job metrics)")
    parser.add_argument("--event-log", default=None,
                        help="fsync'd JSONL event log path (preemption "
                             "drain, emergency checkpoint, rollback, init "
                             "retry); defaults to <train-dir>/events.jsonl "
                             "when --train-dir is set")
    args = parser.parse_args(argv)

    from ..bootstrap import initialize
    from ..bootstrap.bootstrap import StatusServer, launcher_wait
    from ..telemetry import EventLog

    # the event log opens BEFORE distributed init so bootstrap's retry
    # loop can record init_retry events (the earliest failure mode there
    # is); the benchmark borrows this instance rather than reopening
    ev_path = args.event_log or (
        os.path.join(args.train_dir, "events.jsonl")
        if args.train_dir else None)
    events = EventLog(ev_path) if ev_path else None

    info = initialize(events=events)
    if info.is_launcher:
        if events is not None:
            events.close()
        return launcher_wait(info)

    from ..train.resilience import Preempted
    from ..utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    status = StatusServer() if info.is_coordinator else None
    exit_code = 1
    log = print if info.is_coordinator else (lambda s: None)
    try:
        if args.workload == "vit":
            _state, metrics = run_vit_benchmark(
                size=args.size or "b16",
                batch_per_device=args.batch_per_device or 32,
                num_steps=args.num_steps,
                warmup_steps=args.warmup_steps, dtype_name=args.dtype,
                num_slices=info.num_slices, data_dir=args.data_dir,
                train_dir=args.train_dir, ckpt_every=args.ckpt_every,
                ckpt_keep=args.ckpt_keep,
                step_deadline=args.step_deadline,
                divergence_k=args.divergence_k,
                stop_check_every=args.stop_check_every,
                metrics_port=args.metrics_port, events=events,
                log=log)
            headline = {"metric": "vit_images_per_sec",
                        "value": round(metrics["images_per_sec"], 2),
                        "unit": "images/sec"}
        elif args.hfta:
            _state, metrics = run_hfta_benchmark(
                workload=args.workload, size=args.size,
                batch_per_device=args.batch_per_device or 8,
                seq_len=args.seq_len, num_steps=args.num_steps,
                warmup_steps=args.warmup_steps, dtype_name=args.dtype,
                k=args.hfta,
                learning_rates=[float(x) for x in args.hfta_lrs.split(",")]
                if args.hfta_lrs else None,
                seeds=[int(x) for x in args.hfta_seeds.split(",")]
                if args.hfta_seeds else None,
                train_dir=args.train_dir, lr=args.lr,
                lr_warmup_steps=args.lr_warmup_steps,
                metrics_port=args.metrics_port, events=events,
                log=log)
            headline = {"metric":
                        f"{args.workload}_hfta{args.hfta}_tokens_per_sec",
                        "value": round(metrics["tokens_per_sec"], 0),
                        "unit": "tokens/sec (aggregate)"}
        else:
            _state, metrics = run_lm_benchmark(
                workload=args.workload, size=args.size,
                batch_per_device=args.batch_per_device or 8,
                seq_len=args.seq_len, num_steps=args.num_steps,
                warmup_steps=args.warmup_steps,
                eval_steps=args.eval_steps, dtype_name=args.dtype,
                tp=args.tp, pp=args.pp,
                pp_schedule=args.pp_schedule,
                pp_interleave=args.pp_interleave, sp=args.sp,
                moe_experts=args.moe_experts, ep=args.ep,
                fused_xent=args.fused_xent,
                tp_overlap=args.tp_overlap,
                tp_ring=args.tp_ring,
                accum_steps=args.accum_steps,
                num_slices=info.num_slices,
                attention=args.attention, remat=args.remat,
                remat_policy=args.remat_policy,
                data_dir=args.data_dir,
                train_dir=args.train_dir,
                ckpt_every=args.ckpt_every,
                ckpt_keep=args.ckpt_keep,
                step_deadline=args.step_deadline,
                divergence_k=args.divergence_k,
                stop_check_every=args.stop_check_every,
                stop_at_step=args.stop_at_step,
                lr=args.lr,
                lr_warmup_steps=args.lr_warmup_steps,
                profile_dir=args.profile_dir,
                metrics_port=args.metrics_port, events=events,
                log=log)
            headline = {"metric": f"{args.workload}_tokens_per_sec",
                        "value": round(metrics["tokens_per_sec"], 0),
                        "unit": "tokens/sec"}
            if "final_loss" in metrics:
                # the elastic orchestrator gates resumed-vs-oracle loss
                # parity on this field (examples/elastic_benchmark.py)
                headline["final_loss"] = round(
                    float(metrics["final_loss"]), 6)
            if "steps" in metrics:
                headline["steps"] = int(metrics["steps"])
        # where and how the number was produced: the device as jax
        # reports it, the attention implementation the step traced, and
        # compile time apart from step time (with_run_report fills these)
        for key in ("platform", "device_kind", "device_count",
                    "attention_impl", "head_loss_impl", "compile_seconds",
                    "step_compiles", "grad_reductions", "grad_reductions_async", "mfu",
                    "step_time_p50_ms", "state_device_ids",
                    "batch_device_ids", "device_bytes_in_use"):
            if key in metrics:
                headline[key] = metrics[key]
        headline["compile_cache_dir"] = cache_dir
        if info.is_coordinator:
            print(json.dumps(headline))
        exit_code = 0
        return 0
    except Preempted as p:
        # the emergency checkpoint is already committed (the loop saves
        # before raising); exit in the 128–255 RETRYABLE band so the
        # controller restarts the gang instead of failing the job
        log(f"preempted: drained at step {p.step}, exiting "
            f"{p.exit_code} (retryable)")
        exit_code = p.exit_code
        return exit_code
    finally:
        # event log closes (flush + fsync) BEFORE the status channel so a
        # preemption exit never reports done with its drain record still
        # buffered — the shutdown-ordering contract the resilience smoke
        # greps for
        if events is not None:
            events.close()
        if status is not None:
            status.set_done(exit_code)
            status.close()


if __name__ == "__main__":
    sys.exit(main())
