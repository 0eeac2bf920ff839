"""The tied head and its loss in one pass (`ops/xent.py`) against the
logits it never writes: `lm_loss(tied_logits(h, wte), targets, mask)` and
the arg-max of the step's accuracy. The kernel runs interpreted here; what
Mosaic makes of it is `tests/test_tpu_compile.py`'s and the chip's."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mpi_operator_tpu.models.transformer import (CausalLM, _head_matmul,
                                                 gpt2_config)
from mpi_operator_tpu.ops import xent
from mpi_operator_tpu.ops.attention import record_traced
from mpi_operator_tpu.parallel import MeshConfig, make_mesh
from mpi_operator_tpu.train.lm_trainer import (LMTrainer, LMTrainerConfig,
                                               lm_loss)

#: how a test names a form: the kernel interpreted, at steps small enough
#: for toy shapes to take several, or the scan
FORMS = {
    "kernel": dict(scan=False, interpret=True, rows=32, tile=256),
    "scan": dict(scan=True, rows=4),
}


def _with_logits(h, table, y, mask, denom):
    logits = _head_matmul(h, table.astype(h.dtype))
    m = jnp.ones(y.shape, jnp.float32) if mask is None else mask
    acc = jnp.sum((jnp.argmax(logits, -1) == y) * m) / jnp.maximum(
        m.sum(), 1)
    return lm_loss(logits, y, mask, denom=denom), acc


def _operands(B, S, E, V, seed=0, dtype=jnp.bfloat16):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = jax.random.normal(k[0], (B, S, E), dtype)
    # float32 masters, as the trainer holds them; large enough that some
    # rows' labels ARE the arg-max
    table = 0.3 * jax.random.normal(k[1], (V, E), jnp.float32)
    y = jax.random.randint(k[2], (B, S), 0, V)
    mask = (jax.random.uniform(k[3], (B, S)) > 0.3).astype(jnp.float32)
    return h, table, y, mask


def _check(h, table, y, mask, denom, **how):
    want, want_grads = jax.value_and_grad(
        _with_logits, argnums=(0, 1), has_aux=True)(h, table, y, mask, denom)
    got, got_grads = jax.value_and_grad(
        lambda h, t: xent.tied_head_xent(h, t, y, mask, denom, **how),
        argnums=(0, 1), has_aux=True)(h, table)
    alone = xent.tied_head_xent(h, table, y, mask, denom, **how)
    for (loss, acc) in (got, alone):
        np.testing.assert_allclose(loss, want[0], rtol=2e-6)
        np.testing.assert_allclose(acc, want[1], rtol=1e-6)
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = (np.asarray(x, np.float32) for x in (g, w))
        # the cotangent is rounded to bfloat16 once on either path, from
        # float32 values a rounding apart
        assert np.abs(g - w).max() <= 6e-3 * np.abs(w).max()
    return got


@pytest.mark.parametrize("how", FORMS)
@pytest.mark.parametrize("case", ["plain", "mask_with_zeros",
                                  "denom_override"])
def test_value_gradients_and_accuracy_match_the_logits_path(how, case):
    """[3, 40] tokens against 1000 rows: a vocabulary that is no multiple
    of the tile (its last tile is cut), blocks of 32 tokens that do not
    divide the 120, labels in the first and in the last tile."""
    h, table, y, mask = _operands(3, 40, 128, 1000)
    y = y.at[0, 0].set(0).at[0, 1].set(999).at[1, 0].set(255)
    mask = {"plain": None}.get(case, mask)
    denom = 57.0 if case == "denom_override" else None
    loss, acc = _check(h, table, y, mask, denom, **FORMS[how])
    assert np.isfinite(loss) and 0.0 <= float(acc) <= 1.0


@pytest.mark.parametrize("how", FORMS)
def test_hits_are_counted_where_the_label_is_the_row_maximum(how):
    """Rows whose label IS the arg-max (h a multiple of the label's row)
    beside rows where it is not: the accuracy is their masked share."""
    _, table, y, mask = _operands(2, 16, 128, 640, seed=3)
    h = 4.0 * table[y].astype(jnp.bfloat16)
    y_wrong = jnp.where(jnp.arange(16)[None] % 2 == 0, y, (y + 1) % 640)
    _, acc = _check(h, table, y_wrong, mask, None, **FORMS[how])
    want = jnp.sum((y_wrong == y) * mask) / mask.sum()
    np.testing.assert_allclose(acc, want, rtol=1e-6)
    assert 0.2 < float(acc) < 0.8


@pytest.mark.parametrize("how,label", [
    ("kernel", 50256), ("kernel", 50303), ("kernel", 0), ("scan", 50256)])
def test_gpt2s_padded_vocabulary_stays_whole_in_the_normaliser(how, label):
    """GPT-2's 50 304 rows, the last 47 of them weights no token selects:
    every one is in the sum of exponentials, as in `lm_loss` over the
    whole logits, whether the label is the last real id (50 256, a padded
    row's neighbour), a padded row, or the first."""
    h, table, y, _ = _operands(1, 16, 128, 50304, seed=1)
    kw = dict(FORMS[how])
    if how == "kernel":
        kw.update(rows=16, tile=None)        # the kernel's own tile
    _check(h, table, y.at[0, :4].set(label), None, None, **kw)


def test_the_kernels_default_steps_cover_gpt2_mediums_head():
    assert xent.covers(1024, 50304, jnp.bfloat16)
    assert not xent.covers(1024, 50304, jnp.float32)     # plain path
    assert not xent.covers(1000, 50304, jnp.bfloat16)    # no lane tiles
    assert not xent.covers(1024, 200064, jnp.bfloat16)   # logits past VMEM
    t = xent.tiling(8192, 50304, xent._KEPT_ROWS)
    assert t.row_blocks * t.rows == 8192
    assert t.vocab_tiles * t.vocab_tile >= 50304
    need = xent.kernel_vmem_bytes(t, 1024, 2, True)
    assert 50 << 20 < need < xent.VMEM_CEILING


def test_the_traced_form_is_reported():
    h, table, y, _ = _operands(2, 16, 128, 640)
    with record_traced() as traced:
        xent.tied_head_xent(h, table, y, **FORMS["kernel"])
        xent.tied_head_xent(h, table, y)      # off the TPU: the scan
    assert traced["head_loss"] == {
        "pallas_xent[rows=32,vocab_tile=256,products=3]",
        "xla_chunked[chunks=8,products=3]"}


def _one_step(mesh_kw, monkeypatch, how, one_pass=True, devices=None,
              **tcfg):
    """One SGD step of the toy model in bfloat16 on a mesh -> (metrics,
    the parameters' change, traced forms). `one_pass=False` holds the
    trainer to the logits path; `how` names the form the one pass takes."""
    if not one_pass:
        monkeypatch.setattr(LMTrainer, "_one_pass_head", lambda self: False)
    elif how is not None:
        monkeypatch.setattr(xent, "tied_head_xent", functools.partial(
            xent.tied_head_xent, **FORMS[how]))
    cfg = gpt2_config("test", attention="dense", dtype=jnp.bfloat16,
                      vocab_size=640, max_len=32)
    toks = jax.random.randint(jax.random.PRNGKey(5), (8, 33), 0, 640)
    mesh = make_mesh(MeshConfig(**mesh_kw), devices=devices)
    t = LMTrainer(CausalLM(cfg), mesh, LMTrainerConfig(
        global_batch_size=8, seq_len=32, **tcfg), tx=optax.sgd(0.1))
    s = t.init_state(jax.random.PRNGKey(0))
    before = jax.tree.map(np.asarray, s.params)
    put = lambda x: jax.device_put(x, t.batch_sharding)   # noqa: E731
    with record_traced() as traced:
        s, m = t.train_step(s, put(toks[:, :-1]), put(toks[:, 1:]))
    monkeypatch.undo()
    return ({k: float(v) for k, v in m.items()},
            jax.tree.map(lambda a, b: np.asarray(a) - b, s.params, before),
            traced["head_loss"])


def _assert_same_step(a, b, rel):
    """The same loss and, leaf by leaf, the same change of the parameters
    up to `rel` of its norm, or of the median leaf's where that is larger
    (a key bias's gradient is all rounding): bfloat16 gradients summed in
    another order differ by a few roundings, a missing or doubled term by
    its size."""
    assert abs(a[0]["loss"] - b[0]["loss"]) < 2e-5
    pairs = list(zip(jax.tree.leaves(a[1]), jax.tree.leaves(b[1])))
    floor = np.median([np.linalg.norm(y) for _, y in pairs])
    for x, y in pairs:
        assert np.linalg.norm(x - y) <= rel * max(np.linalg.norm(y), floor)


@pytest.mark.parametrize("how", FORMS)
def test_one_pass_step_matches_the_logits_step(how, monkeypatch):
    """The trainer takes the one pass for a bfloat16 causal model on a dp
    mesh and steps as the logits path does: the same loss, the same
    parameters after a step, and an accuracy that is a number."""
    want = _one_step(dict(dp=8), monkeypatch, None, one_pass=False)
    got = _one_step(dict(dp=8), monkeypatch, how)
    assert want[2] == set() and len(got[2]) == 1
    assert next(iter(got[2])).startswith(
        "pallas_xent[" if how == "kernel" else "xla_chunked[")
    _assert_same_step(got, want, rel=0.01)
    assert got[0]["accuracy"] == want[0]["accuracy"]
    assert np.isfinite(got[0]["accuracy"])


def test_fused_xent_still_selects_the_chunked_loss(monkeypatch):
    """`fused_xent` is the memory option it was (`fused_lm_loss`: no
    accuracy, nothing traced under "head_loss"), also where the one pass
    would apply."""
    got = _one_step(dict(dp=8), monkeypatch, None, fused_xent=True)
    want = _one_step(dict(dp=8), monkeypatch, None, one_pass=False)
    assert got[2] == set() and np.isnan(got[0]["accuracy"])
    _assert_same_step(got, want, rel=0.01)


@pytest.mark.parametrize("how", FORMS)
def test_a_dp8_step_matches_the_unsharded_step(how, monkeypatch):
    """On eight devices the kernels run under `shard_map` on each
    device's rows and `dtable` is summed across them: the parameters
    after a step are the one-device step's."""
    one = _one_step(dict(dp=1), monkeypatch, how, devices=jax.devices()[:1])
    eight = _one_step(dict(dp=8), monkeypatch, how)
    assert one[2] and eight[2]
    _assert_same_step(eight, one, rel=0.05)


def test_accumulated_microbatches_sum_to_the_full_step(monkeypatch):
    """The `denom` override through the trainer: two microbatches, each
    normalised by the whole batch's count, step as the whole batch."""
    whole = _one_step(dict(dp=4), monkeypatch, "kernel",
                      devices=jax.devices()[:4])
    halves = _one_step(dict(dp=4), monkeypatch, "kernel",
                       devices=jax.devices()[:4], accum_steps=2)
    _assert_same_step(halves, whole, rel=0.05)


@pytest.mark.parametrize("mesh_kw,dtype,masked", [
    (dict(dp=4, tp=2), jnp.bfloat16, False),      # a model axis
    (dict(dp=4, sp=2), jnp.bfloat16, False),      # a split sequence
    (dict(dp=8), jnp.float32, False),             # float32 compute
    (dict(dp=8), jnp.bfloat16, True),             # the masked objective
])
def test_what_the_one_pass_does_not_cover_keeps_its_path(mesh_kw, dtype,
                                                         masked):
    cfg = gpt2_config("test", attention="dense", dtype=dtype,
                      vocab_size=640, max_len=32)
    t = LMTrainer(CausalLM(cfg), make_mesh(MeshConfig(**mesh_kw)),
                  LMTrainerConfig(global_batch_size=8, seq_len=32,
                                  masked_lm=masked))
    assert not t._one_pass_head()
