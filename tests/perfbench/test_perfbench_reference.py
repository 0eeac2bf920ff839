"""The plain reference against the program's CausalLM at a tiny width,
in float32 on the CPU, on weights made by `perfbench.weights`.

Tolerances and their reasons: both sides compute in float32 with
different orders of summation (flax Dense/LayerNorm against plain
einsum), so logits of magnitude ~1 agree to a few float32 ulps times the
depth: 2e-5 absolute holds with room. Gradients are compared leaf by
leaf against the largest entry of the leaf at 1e-4 relative. The bfloat16
control (the reference with operands rounded to bfloat16, the step below
float32) misses the logits tolerance by two orders of magnitude.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import weights
from perfbench.reference import gpt2

DIMS = weights.Dims(layers=2, heads=4, embed=64, mlp=256, positions=32,
                    vocab=128, vocab_real=120)
LOGIT_TOL = 2e-5
GRAD_TOL = 1e-4


@pytest.fixture(scope="module")
def setup():
    from mpi_operator_tpu.models.transformer import (CausalLM,
                                                     TransformerConfig)
    model = CausalLM(TransformerConfig(
        vocab_size=DIMS.vocab, max_len=DIMS.positions, num_layers=DIMS.layers,
        num_heads=DIMS.heads, embed_dim=DIMS.embed, mlp_dim=DIMS.mlp,
        causal=True, dtype=jnp.float32, attention="dense"))
    stacked = weights.make_stacked(weights.seed_key(2**31 + 9), DIMS,
                                   jnp.float32)
    params = weights.unstack(stacked, DIMS.layers)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, DIMS.vocab_real, (3, 32)),
                         jnp.int32)
    targets = jnp.asarray(rng.integers(0, DIMS.vocab_real, (3, 32)),
                          jnp.int32)
    return model, params, stacked, tokens, targets


def test_weights_make_the_programs_tree(setup):
    model, params, _, tokens, _ = setup
    theirs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               tokens))["params"]
    from flax.core import meta
    theirs = meta.unbox(theirs)
    assert weights.tree_shapes(params) == weights.tree_shapes(theirs)
    assert DIMS.param_count() == sum(x.size for x in jax.tree.leaves(params))


def test_logits_agree(setup):
    model, params, stacked, tokens, _ = setup
    with jax.default_matmul_precision("highest"):
        theirs = model.apply({"params": params}, tokens)
    mine = gpt2.forward(stacked, tokens)
    assert float(jnp.max(jnp.abs(mine - theirs))) < LOGIT_TOL


def test_bf16_control_fails_the_logits_tolerance(setup):
    _, _, stacked, tokens, _ = setup
    mine = gpt2.forward(stacked, tokens)
    control = gpt2.forward(stacked, tokens, "bf16")
    assert float(jnp.max(jnp.abs(mine - control))) > 10 * LOGIT_TOL


def test_loss_and_gradients_agree_with_the_trainers_loss(setup):
    from mpi_operator_tpu.train.lm_trainer import lm_loss
    model, params, stacked, tokens, targets = setup

    def theirs(p):
        with jax.default_matmul_precision("highest"):
            return lm_loss(model.apply({"params": p}, tokens), targets)
    loss_t, grad_t = jax.value_and_grad(theirs)(params)
    loss_m, grad_m = gpt2.loss_and_grad(stacked, tokens, targets, rows=2)
    assert abs(float(loss_t) - float(loss_m)) < 1e-5
    grad_m = weights.unstack(grad_m, DIMS.layers)
    # a key bias has no gradient (the softmax cancels it): what is left
    # there is rounding, so no leaf is held tighter than the median leaf
    tops = [float(jnp.max(jnp.abs(a))) for a in jax.tree.leaves(grad_t)]
    floor = float(np.median(tops))
    for a, b, top in zip(jax.tree.leaves(grad_t), jax.tree.leaves(grad_m),
                         tops):
        assert float(jnp.max(jnp.abs(a - b))) / max(top, floor) < GRAD_TOL


def test_layer_by_layer_from_the_seed_equals_the_whole_tree():
    key = weights.seed_key(11)
    stacked = weights.make_stacked(key, DIMS, jnp.bfloat16)
    params = weights.unstack(stacked, DIMS.layers)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, DIMS.vocab_real, (2, 32)), jnp.int32)
    one = weights.layer_params(key, DIMS, jnp.int32(1), jnp.bfloat16)
    for a, b in zip(jax.tree.leaves(one),
                    jax.tree.leaves(params["backbone"]["block_1"])):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    whole = gpt2.forward(stacked, tokens)
    by_layer = gpt2.logits_from_seed(key, tokens, DIMS, jnp.bfloat16)
    # the same weights, summed in another order (jitted layer by layer)
    assert float(jnp.max(jnp.abs(whole - by_layer))) < 1e-4


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 1, 2**32 + 3])
def test_seed_keys_differ_past_31_bits(seed):
    a = jax.random.key_data(weights.seed_key(seed))
    b = jax.random.key_data(weights.seed_key(seed + 1))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_adamw_follows_optax():
    import optax
    from mpi_operator_tpu.train.lm_trainer import (LMTrainerConfig,
                                                   make_adamw)
    hp = {"learning_rate": 2.5e-4, "weight_decay": 0.01, "b1": 0.9,
          "b2": 0.95, "grad_clip": 1.0, "warmup_steps": 100}
    tx = make_adamw(LMTrainerConfig(**{k: v for k, v in hp.items()}))
    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.normal(size=(5, 7)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    grads = [jax.tree.map(lambda x: jnp.asarray(
        rng.normal(size=x.shape) * 3, jnp.float32), params)
        for _ in range(3)]
    theirs, state = params, tx.init(params)
    mine = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    for count, g in enumerate(grads):
        upd, state = tx.update(g, state, theirs)
        theirs = optax.apply_updates(theirs, upd)
        clipped = gpt2.clip_by_global_norm(g, hp["grad_clip"])
        mine, m, v = gpt2.adamw_update(
            mine, clipped, m, v, count,
            gpt2.warmup_lr(count, hp["learning_rate"], hp["warmup_steps"]),
            hp["b1"], hp["b2"], 1e-8, hp["weight_decay"])
    for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(mine)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-7


def test_program_and_reference_name_their_leaves_alike():
    stacked = weights.make_stacked(weights.seed_key(5), DIMS, jnp.float32)
    params = weights.unstack(stacked, DIMS.layers)
    mine = {n: np.asarray(v) for n, v in gpt2.leaf_norms(stacked).items()}
    theirs = weights.by_leaf_name(jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(x ** 2)), params), DIMS.layers)
    assert sorted(mine) == sorted(theirs)
    for n in mine:
        assert np.allclose(mine[n], theirs[n], rtol=1e-5), n
