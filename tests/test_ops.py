"""Pallas flash-attention kernel tests (interpret mode on CPU — the same
kernel code path that compiles to Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models.transformer import dense_attention
from mpi_operator_tpu.ops.attention import flash_attention, record_traced


def _qkv(B=2, S=128, H=2, D=16, dtype=jnp.float32):
    return tuple(
        jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D), dtype)
        for i in range(3))


#: head shapes and operand types, and the form of the kernels each takes
#: without a key mask (with one, every shape streams): two heads of 16 do
#: not fill a lane tile; a pair of 64 does, three of 64 do not; a head of
#: 128 is a tile; four heads of 32 would fill one and two tiles hold a
#: head of 256, but only pairs of 64 and heads of 128 were measured on
#: the chip, so those stream too
HEADS = {
    "d16": (dict(), "streamed"),
    "quad": (dict(H=4, D=32), "streamed"),
    "d256": (dict(H=1, D=256), "streamed"),
    "pair": (dict(H=2, D=64), "resident[heads=2"),
    "odd": (dict(H=3, D=64), "streamed"),
    "wide": (dict(H=1, D=128), "resident[heads=1"),
    "pair-bf16": (dict(H=2, D=64, dtype=jnp.bfloat16), "resident[heads=2"),
    "wide-bf16": (dict(H=1, D=128, dtype=jnp.bfloat16), "resident[heads=1"),
    "odd-bf16": (dict(H=3, D=64, dtype=jnp.bfloat16), "streamed"),
}
heads = pytest.mark.parametrize("heads", sorted(HEADS))


def _dense32(q, k, v, **kw):
    """The dense reference on the operands' values, in float32."""
    return dense_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                           dtype=jnp.float32, **kw)


def _assert_close(ref, got, atol):
    """`atol` for float32 operands; bfloat16 ones round what they return
    to 8 bits, so against the size of what is compared."""
    ref, got32 = np.asarray(ref), np.asarray(got.astype(jnp.float32))
    if got.dtype == jnp.bfloat16:
        atol = 2.0 ** -6 * max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(ref, got32, atol=atol)


def _flash(q, k, v, form=None, **kw):
    """`flash_attention`, on the flash path and in the form named."""
    with record_traced() as traced:
        out = flash_attention(q, k, v, **kw)
    assert traced["attention"] == {"flash"}
    if form is not None:
        name, = traced["flash"]
        assert name.startswith(form), name
    return out


@heads
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal, heads):
    kw, form = HEADS[heads]
    q, k, v = _qkv(**kw)
    ref = _dense32(q, k, v, causal=causal)
    out = _flash(q, k, v, form, causal=causal, block_q=64, block_k=64)
    _assert_close(ref, out, 2e-5)


@pytest.mark.parametrize("heads", ["d16", "pair", "pair-bf16"])
def test_flash_multiple_block_sizes(heads):
    kw, form = HEADS[heads]
    q, k, v = _qkv(S=256, **kw)
    ref = _dense32(q, k, v, causal=True)
    for bq, bk in [(64, 128), (128, 64), (256, 256)]:
        out = _flash(q, k, v, form, causal=True, block_q=bq, block_k=bk)
        _assert_close(ref, out, 2e-5)


@heads
@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_dense(causal, heads):
    kw, form = HEADS[heads]
    q, k, v = _qkv(S=64, **kw)

    def lf(q, k, v):
        return (_flash(q, k, v, form, causal=causal, block_q=32,
                       block_k=32).astype(jnp.float32) ** 2).sum()

    def ld(q, k, v):
        return (_dense32(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        _assert_close(b, a, 1e-4)


@pytest.mark.parametrize("S,dtype,form", [
    (1024, jnp.bfloat16, "resident[heads=2,q=512,k=512]"),
    (2048, jnp.bfloat16, "resident[heads=2,q=512,k=512]"),
    (1024, jnp.float32, "resident[heads=2,q=512,k=512]"),
    (2048, jnp.float32, "streamed[q=1024,k=1024]"),
])
def test_a_sequence_stays_in_vmem_or_streams(S, dtype, form):
    """The steps nobody names: a pair of 64-wide heads stays whole in
    VMEM over 1024 positions, and over 2048 in bfloat16; in float32 it
    streams there — and both are the same attention, outputs and all
    three gradients."""
    q, k, v = _qkv(B=1, S=S, H=2, D=64, dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

    def lf(q, k, v):
        return (_flash(q, k, v, form).astype(jnp.float32) * w).sum()

    def ld(q, k, v):
        return (_dense32(q, k, v, causal=True) * w).sum()

    _assert_close(_dense32(q, k, v, causal=True), _flash(q, k, v, form),
                  2e-5)
    for a, b in zip(jax.grad(lf, (0, 1, 2))(q, k, v),
                    jax.grad(ld, (0, 1, 2))(q, k, v)):
        _assert_close(b, a, 1e-4)


def test_flash_fallback_on_odd_lengths():
    """S that doesn't tile falls back to dense — still correct."""
    q, k, v = _qkv(S=100)
    ref = dense_attention(q, k, v, causal=True, dtype=jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_flash_under_jit():
    q, k, v = _qkv(S=64)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                block_q=32, block_k=32))
    out = f(q, k, v)
    ref = dense_attention(q, k, v, causal=True, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def _padding_mask(B=2, S=128, valid=96):
    # batch row 0 padded to `valid` tokens, row 1 full
    mask = np.ones((B, S), bool)
    mask[0, valid:] = False
    return jnp.asarray(mask)


@pytest.mark.parametrize("heads", ["d16", "pair", "pair-bf16", "wide"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_padding_mask_matches_dense(causal, heads):
    """The round-1 gap: padded BERT batches must keep the flash path
    (the streamed form, whatever the heads)."""
    q, k, v = _qkv(**HEADS[heads][0])
    mask = _padding_mask()
    ref = _dense32(q, k, v, mask=mask, causal=causal)
    out = _flash(q, k, v, "streamed", causal=causal, mask=mask,
                 block_q=64, block_k=64)
    _assert_close(ref, out, 2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads", ["d16", "pair", "pair-bf16"])
def test_flash_masked_gradients_match_dense(heads, causal):
    q, k, v = _qkv(**HEADS[heads][0])
    mask = _padding_mask()
    # score only valid query rows, as a real masked loss does
    w = mask.astype(jnp.float32)[:, :, None, None]

    def lf(q, k, v):
        return ((_flash(q, k, v, "streamed", causal=causal, mask=mask,
                        block_q=64, block_k=32).astype(jnp.float32)
                 * w) ** 2).sum()

    def ld(q, k, v):
        return ((_dense32(q, k, v, mask=mask, causal=causal) * w) ** 2).sum()

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        _assert_close(b, a, 1e-4)


@pytest.mark.parametrize("heads", ["d16", "pair", "pair-bf16"])
def test_flash_fully_masked_row_is_finite(heads):
    """A batch row whose keys are ALL padding must produce zeros/finite
    grads, not NaNs (degenerate lse guard in the backward kernels)."""
    q, k, v = _qkv(**HEADS[heads][0])
    mask = jnp.asarray(np.stack([np.zeros(128, bool), np.ones(128, bool)]))

    def lf(q, k, v):
        return (flash_attention(q, k, v, causal=False, mask=mask,
                                block_q=64, block_k=64)
                .astype(jnp.float32) ** 2).sum()

    out = flash_attention(q, k, v, causal=False, mask=mask,
                          block_q=64, block_k=64)
    assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()
    grads = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g.astype(jnp.float32))).all()


def test_bert_model_keeps_flash_with_mask():
    """models._attend must NOT fall back to dense for masked flash."""
    from unittest import mock

    from mpi_operator_tpu.models import transformer as tr

    cfg = tr.TransformerConfig(causal=False, attention="flash",
                               dtype=jnp.float32, num_heads=2, embed_dim=32,
                               vocab_size=64, max_len=128)
    q, k, v = _qkv(D=16)
    mask = _padding_mask()
    with mock.patch.object(tr, "dense_attention",
                           side_effect=AssertionError("fell back to dense")):
        out = tr._attend(q, k, v, mask, cfg)
    assert out.shape == q.shape


def test_auto_tile_policy_never_demotes_to_dense():
    """Seq lens that are 512-multiples but not 1024-multiples (2560,
    3584, ...) must keep 512 flash tiles — the 1024 auto tiles apply only
    when they divide S exactly (falling through to dense attention at
    long seq would OOM on a real chip)."""
    import numpy as np

    from mpi_operator_tpu.ops.attention import flash_attention
    from mpi_operator_tpu.models.transformer import dense_attention

    B, S, H, D = 1, 2560, 2, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D),
                                 jnp.float32) for i in range(3))
    out = flash_attention(q, k, v, causal=True)      # must take flash path
    ref = dense_attention(q, k, v, causal=True, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters (a
    kernel's body, a loop's, a branch's)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("heads,S,blocks,bodies", [
    # q blocks x heads x (a loop body where a block lies clear of the
    # diagonal + the blocks it cuts): (0 + 1) + (1 + 1) for two q blocks
    ("pair-bf16", 256, 128, 2 * 3),
    ("wide-bf16", 256, 128, 1 * 3),
    ("pair-bf16", 512, 128, 2 * (1 + 3 * 2)),
])
def test_backward_is_one_kernel_of_five_products_on_the_callers_type(
        heads, S, blocks, bodies):
    """What the step's backward executes, read from its jaxpr: TWO kernel
    calls for attention and its gradient (a forward, ONE backward that
    returns dq, dk and dv), the backward's body five products a block —
    the scores, dv, dp, dk, dq: s and dp computed once — the forward's
    two, and no product with a float32 operand when the caller's are
    bfloat16. Nothing 128 lanes wide is made for the row statistics."""
    kw, form = HEADS[heads]
    q, k, v = _qkv(B=1, S=S, **kw)

    def loss(q, k, v):
        return _flash(q, k, v, form, causal=True, block_q=blocks,
                      block_k=blocks).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr
    kernels = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]
    assert sorted(len(e.outvars) for e in kernels) == [2, 3]
    for kernel, products in zip(sorted(kernels,
                                       key=lambda e: len(e.outvars)),
                                (2, 5)):
        dots = [e for e in _eqns(kernel.params["jaxpr"])
                if e.primitive.name == "dot_general"]
        assert len(dots) == products * bodies
        for dot in dots:
            assert {x.aval.dtype for x in dot.invars} == {
                jnp.dtype(jnp.bfloat16)}
            assert dot.outvars[0].aval.dtype == jnp.float32
    wide = [x.aval.shape for e in _eqns(jaxpr) for x in e.outvars
            if e.primitive.name != "pallas_call" and x.aval.ndim == 3
            and x.aval.shape[-1] == 128 and x.aval.dtype == jnp.float32]
    assert wide == []
