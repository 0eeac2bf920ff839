"""Training traffic: `LMTrainer` on a data-parallel mesh over the cell's
chips, fed through `data/prefetch.py` from a seeded token stream.

Set-up builds one object — the compiled step with its state — drives it
from the seed through its first `check_steps` steps by the window's own
call and feed, and hands that same object to the window. The plain
reference follows those steps after the window, when the program's state
has been freed, and `correct` compares each step's loss, the first
gradient as the optimizer got it (from Adam's first moment after one
step) and the parameters' change, by the worst leaf.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

from perfbench import harness, weights
from perfbench.harness import Check, log, span


def token_windows(seed: int, rows: int, seq_len: int, vocab: int):
    """The stream: `[rows, seq_len + 1]` windows of ids, every row
    different, the same for the same seed."""
    rng = np.random.default_rng([int(seed), 7])
    while True:
        yield rng.integers(0, vocab, (rows, seq_len + 1), dtype=np.int32)


def make_stream(seed, rows, seq_len, vocab, sharding):
    import jax
    from mpi_operator_tpu.data.prefetch import PrefetchDataset

    class SeededTokens(PrefetchDataset):
        def __init__(self):
            self._start_feeder(prefetch=2)

        def _produce(self):
            for win in token_windows(seed, rows, seq_len, vocab):
                yield (jax.device_put(win[:, :-1], sharding),
                       jax.device_put(win[:, 1:], sharding))
    return SeededTokens()


def by_name(readings, layers: int):
    """The program's readings with each per-leaf tree regrouped by leaf
    name, as the reference's stacked tree names them."""
    losses, norms, proj, change = readings
    return ([float(x) for x in losses],
            weights.by_leaf_name(norms, layers),
            weights.by_leaf_name(proj, layers),
            weights.by_leaf_name(change, layers))


def _flat(by_leaf: dict, names) -> np.ndarray:
    """One row a leaf (name, layer), in the order of `names`."""
    return np.concatenate([np.asarray(by_leaf[n], np.float64).reshape(
        np.asarray(by_leaf[n]).shape[0], -1) for n in names])


def worst_leaf_gap(program: np.ndarray, reference: np.ndarray,
                   scale: np.ndarray) -> float:
    """Largest gap over the leaves between the program's number and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    return float(np.max(np.abs(program - reference)
                        / np.maximum(scale, np.median(scale))))


def adam_first_moment(opt_state):
    import jax
    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def build(ctx):
    """The trainer, a maker of states that hold the benchmark's seeded
    weights, and the model's sizes."""
    import jax
    import jax.numpy as jnp
    from mpi_operator_tpu.models.transformer import (CausalLM,
                                                     TransformerConfig)
    from mpi_operator_tpu.parallel import MeshConfig, make_mesh
    from mpi_operator_tpu.train.lm_trainer import LMTrainer, LMTrainerConfig

    t = ctx.traffic
    dims = weights.Dims.from_config(ctx.config)
    chips = len(ctx.devices)
    model = CausalLM(TransformerConfig(
        vocab_size=dims.vocab, max_len=dims.positions,
        num_layers=dims.layers, num_heads=dims.heads, embed_dim=dims.embed,
        mlp_dim=dims.mlp, causal=True, dtype=jnp.dtype(t["compute_dtype"]),
        attention=t["attention"], remat=bool(t["remat"])))
    mesh = make_mesh(MeshConfig(dp=chips), devices=ctx.devices)
    hp = t["optimizer"]
    trainer = LMTrainer(model, mesh, LMTrainerConfig(
        global_batch_size=t["rows_per_chip"] * chips, seq_len=t["seq_len"],
        learning_rate=hp["learning_rate"], weight_decay=hp["weight_decay"],
        b1=hp["b1"], b2=hp["b2"], grad_clip=hp["grad_clip"],
        warmup_steps=hp["warmup_steps"]))
    template = trainer.init_state(jax.random.PRNGKey(0))
    probe = jax.eval_shape(lambda k: weights.make_params(k, dims,
                                                          jnp.float32),
                           weights.seed_key(0))
    mine, theirs = weights.tree_shapes(probe), weights.tree_shapes(
        template.params)
    if mine != theirs:
        diff = sorted(set(mine.items()) ^ set(theirs.items()))[:6]
        raise RuntimeError(f"perfbench.weights does not make the program's "
                           f"parameter tree: {diff}")
    arrays = (template.params, template.opt_state, template.step,
              template.nonfinite_streak)

    def fresh(k):
        p = weights.make_params(k, dims, jnp.float32)
        zero = jnp.zeros((), jnp.int32)
        return p, trainer.tx.init(p), zero, zero
    fresh = jax.jit(fresh, out_shardings=jax.tree.map(
        lambda x: x.sharding, arrays))
    harness.delete_arrays(arrays)

    def new_state(key):
        """The trainer's state holding the benchmark's weights of `key`,
        born where `init_state` put its own (one program, on the device)."""
        p, o, step, streak = fresh(key)
        return template.replace(params=p, opt_state=o, step=step,
                                nonfinite_streak=streak)
    return trainer, new_state, dims


def _name_and_layer(path):
    """('blocks/attn/key/bias', 7) for backbone/block_7/attn/key/bias;
    (name, 0) for a leaf outside the blocks — the stacked tree's naming."""
    parts = [str(k.key) for k in path]
    if parts[0] == "backbone" and parts[1].startswith("block_"):
        return "blocks/" + "/".join(parts[2:]), int(parts[1][len("block_"):])
    return "/".join(parts[1:] if parts[0] == "backbone" else parts), 0


def program_readers(dims, b1, k=8):
    """Two jitted readers of the program's state, each one small program:
    the leaf norms and seeded projections of the first gradient as the
    optimizer got it (Adam's first moment after one step, over 1 - b1),
    and the leaf norms of the parameters' change from the seed's weights
    (remade inside, never held)."""
    import jax
    import jax.numpy as jnp
    from perfbench.reference import gpt2

    def norm(x):
        return jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))

    @jax.jit
    def first_grad(opt, key):
        g = jax.tree.map(lambda m: m.astype(jnp.float32) / (1 - b1),
                         adam_first_moment(opt))
        flat, treedef = jax.tree_util.tree_flatten_with_path(g)
        proj = []
        for path, x in flat:
            name, layer = _name_and_layer(path)
            lead = dims.layers if name.startswith("blocks/") else 1
            proj.append(gpt2.project(
                x.reshape(-1), gpt2.salts(key, name, lead, k)[layer]))
        return jax.tree.map(norm, g), jax.tree.unflatten(treedef, proj)

    @jax.jit
    def change(params, key):
        return jax.tree.map(lambda a, b: norm(a - b), params,
                            weights.make_params(key, dims, jnp.float32))
    return first_grad, change


def first_steps(trainer, state, stream, readers, key, n_check,
                after_first=None):
    """Drive `state` through its first `n_check` steps by the window's
    own call and feed. Returns the state and, still on the device, each
    step's loss and what `program_readers` read. `after_first()` is
    called when the first step's loss is on the host (the run splits its
    set-up time there)."""
    first_grad, change = readers
    losses, first = [], None
    for i in range(n_check):
        tokens, targets = next(stream)
        state, metrics = trainer.train_step(state, tokens, targets)
        losses.append(metrics["loss"])
        if i == 0:
            first = first_grad(state.opt_state, key)
            if after_first is not None:
                float(losses[0])
                after_first()
    return state, (losses, *first, change(state.params, key))


def run(ctx) -> harness.Outcome:
    import jax
    from perfbench.ops import transformer_train

    t = ctx.traffic
    chips = len(ctx.devices)
    rows, seq_len = t["rows_per_chip"] * chips, t["seq_len"]
    tokens_per_step = rows * seq_len
    compiles = harness.CompileCounter()
    phases = harness.Phases()
    phases.mark("reach the chip")
    trainer, new_state, dims = build(ctx)
    phases.mark("trainer and init_state")
    key = weights.seed_key(ctx.seed)
    state = new_state(key)
    readers = program_readers(dims, t["optimizer"]["b1"])
    stream = make_stream(ctx.seed, rows, seq_len, dims.vocab_real,
                         trainer.batch_sharding)
    try:
        # -- set-up: the first steps, through the window's call and feed
        n_check = int(t["check_steps"])
        jax.block_until_ready(state.params)
        phases.mark("seeded weights")
        state, program = first_steps(
            trainer, state, stream, readers, key, n_check,
            after_first=lambda: phases.mark(
                "first step: trace, compile or load, run"))
        program = by_name(jax.device_get(program), dims.layers)
        for _ in range(int(t["warm_steps"])):
            state, metrics = trainer.train_step(state, *next(stream))
        jax.block_until_ready(metrics["loss"])
        phases.mark(f"readers, steps 2-{n_check}, {t['warm_steps']} more")
        t_open = time.perf_counter()
        setup_s = t_open - harness.PROCESS_START
        log(phases.line(setup_s) + " (reference: after the window, not "
            "counted)")

        # -- the window: whole steps, the host at most two steps ahead
        tracer = harness.SubWindowTracer(ctx.trace, t["trace_start_s"],
                                         t["trace_seconds"])
        pending = collections.deque()
        done_at, input_wait = [], []
        with compiles.window():
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < ctx.seconds:
                tracer.poll(time.perf_counter() - t0)
                tw = time.perf_counter()
                with span("input"):
                    batch = next(stream)
                input_wait.append(time.perf_counter() - tw)
                with span("dispatch"):
                    state, metrics = trainer.train_step(state, *batch)
                pending.append(metrics["loss"])
                if len(pending) > 2:
                    with span("sync"):
                        last = float(pending.popleft())
                    done_at.append(time.perf_counter())
            while pending:
                with span("sync"):
                    last = float(pending.popleft())
                done_at.append(time.perf_counter())
            t1 = done_at[-1]
            tracer.stop()
    finally:
        stream.close()
    steps = len(done_at)
    window = t1 - t0
    if compiles.count:
        raise RuntimeError(f"{compiles.count} program(s) compiled inside "
                           f"the measured window")
    if not np.isfinite(last):
        raise RuntimeError(f"loss {last} at the window's last step")
    rate = steps * tokens_per_step / window / chips
    peak_bytes = harness.memory_peak_bytes(ctx.devices)
    log(f"window {window:.3f} s, {steps} whole steps of {tokens_per_step} "
        f"tokens on {chips} chip(s); last loss {last:.4f}; peak "
        f"{peak_bytes} bytes")

    # -- the reference, after the program's state and its compiled step
    # (whose scratch the device keeps reserved while it is loaded) are freed
    harness.delete_arrays(state)
    del state, metrics, batch, trainer, new_state
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    checks = compare(ctx, dims, key, program, t["limits"])
    log(f"reference {time.perf_counter() - t_ref:.3f} s; device peak after "
        f"it {harness.memory_peak_bytes(ctx.devices)} bytes")

    # steps that ran while the profiler started or stopped measure the
    # profiler: the per-layer step time and rate leave them out
    step_ms = [1e3 * (b - a) for a, b in zip(done_at, done_at[1:])
               if not tracer.overlaps(a, b)]
    clean_rate = tokens_per_step / (harness.median(step_ms) / 1e3) / chips
    ev = harness.Evidence(
        samples={"train.step_ms": step_ms,
                 "train.input_wait_ms": [1e3 * x for x in input_wait]},
        counters={"train.tokens_per_s_per_chip": clean_rate,
                  "train.flops_per_token": transformer_train.flops_per_token(
                      dims.param_count(), dims.layers, dims.embed, seq_len)},
        shapes={"rows_per_chip": t["rows_per_chip"], "seq_len": seq_len,
                "heads": dims.heads, "head_dim": dims.head_dim,
                "layers": dims.layers, "chips": chips,
                "steps_per_s": steps / window},
        trace=tracer.summary(ctx.keep_trace),
        peaks=harness.peaks_of(ctx.devices))
    return harness.Outcome(
        end_to_end={"train_tokens_per_s_per_chip": rate, "setup_s": setup_s},
        evidence=ev, correct=harness.judge(checks), attempted=steps,
        failed=0, memory_peak_bytes=peak_bytes)


def reference_readings(ctx, dims, key, precision="f32"):
    """Losses, first-gradient leaf norms and projections, and
    parameter-change leaf norms of the plain reference over the first
    `check_steps` batches, by leaf name."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from perfbench.reference import gpt2

    t = ctx.traffic
    chips = len(ctx.devices)
    rows = t["rows_per_chip"] * chips
    # the reference's own placement: rows over the chips, weights on each
    mesh = Mesh(np.array(ctx.devices), ("rows",))
    by_row = NamedSharding(mesh, PartitionSpec("rows"))
    everywhere = NamedSharding(mesh, PartitionSpec())
    wins = token_windows(ctx.seed, rows, t["seq_len"], dims.vocab_real)
    batches = []
    for _ in range(int(t["check_steps"])):
        win = next(wins)
        batches.append((jax.device_put(win[:, :-1], by_row),
                        jax.device_put(win[:, 1:], by_row)))
    params = jax.jit(lambda k: weights.make_stacked(k, dims, jnp.float32),
                     out_shardings=everywhere)(key)
    out = gpt2.train_steps(params, batches, t["optimizer"], key, precision,
                           rows=2 * chips)
    losses, norms, proj, change = jax.device_get(out)
    return (losses, *({n: np.asarray(v) for n, v in d.items()}
                      for d in (norms, proj, change)))


def gaps(program, reference) -> dict:
    """The numbers compared: the widest loss gap over the steps; the
    worst leaf of the first gradient's norms, of its seeded projections
    (root mean square of their gaps: rounding that leaves a norm where it
    was moves a projection in the first order) and of the parameter
    change's norms. The change is compared on the leaves that have a
    gradient: a leaf whose reference gradient is all but zero (a key
    bias, which the softmax cancels) moves by Adam's normalised rounding
    noise, which no two precisions share."""
    losses, norms, proj, change = program
    ref_losses, ref_norms, ref_proj, ref_change = reference
    names = sorted(ref_norms)
    g = _flat(ref_norms, names)[:, 0]
    live = g > 1e-4 * np.median(g)
    proj_rms = np.sqrt(np.mean(
        (_flat(proj, names) - _flat(ref_proj, names)) ** 2, axis=1))
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, ref_losses)),
        "first_grad_norm_gap_worst_leaf": worst_leaf_gap(
            _flat(norms, names)[:, 0], g, g),
        "first_grad_projection_gap_worst_leaf": worst_leaf_gap(
            proj_rms, np.zeros_like(proj_rms), g),
        "param_change_norm_gap_worst_leaf": worst_leaf_gap(
            _flat(change, names)[live, 0], _flat(ref_change, names)[live, 0],
            _flat(ref_change, names)[live, 0]),
    }


def compare(ctx, dims, key, program, limits):
    reference = reference_readings(ctx, dims, key)
    log("losses: program " + " ".join(f"{float(x):.6f}" for x in program[0])
        + " | reference " + " ".join(f"{x:.6f}" for x in reference[0]))
    return [Check(name, value, limits[name])
            for name, value in gaps(program, reference).items()]
