"""The gated delta rule of `ops/gated_delta.py`: the chunked WY form and
the decode step's Pallas kernel (interpreted) against the recurrence
written position by position, float32 on the CPU, so a tolerance is what
summation order costs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.ops.attention import record_traced, traced_name
from mpi_operator_tpu.ops.gated_delta import (
    gated_delta_chunk_scan, gated_delta_scan, gated_delta_state_update)

TOL = 2e-6


def operands(G, T, Hk, Hv, Dk, Dv, seed=0, junk_from=None):
    """q, k L2-normalised (q scaled) as the model hands them; steps of
    e^-5 to e^0.5 a position, so a head forgets over one to a hundred
    positions; a state that is not zeros. `junk_from` [G]: positions from
    there on have beta = g = 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (G, T, Hk, Dk))) * Dk ** -0.5
    k = unit(jax.random.normal(ks[1], (G, T, Hk, Dk)))
    v = jax.random.normal(ks[2], (G, T, Hv, Dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (G, T, Hv), minval=-5, maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (G, T, Hv)))
    if junk_from is not None:
        real = (jnp.arange(T)[None] < jnp.asarray(junk_from)[:, None])[..., None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    return q, k, v, g, beta, jax.random.normal(ks[5], (G, Hv, Dk, Dv))


def by_hand(q, k, v, g, beta, state):
    """The recurrence in numpy, one head and position at a time."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    s = np.asarray(state, np.float64).copy()
    G, T, Hv, Dv = v.shape
    n = Hv // q.shape[2]
    out = np.zeros((G, T, Hv, Dv))
    for r in range(G):
        for t in range(T):
            for h in range(Hv):
                kt, qt = k[r, t, h // n], q[r, t, h // n]
                s[r, h] *= np.exp(g[r, t, h])
                read = s[r, h].T @ kt
                s[r, h] += np.outer(kt, beta[r, t, h] * (v[r, t, h] - read))
                out[r, t, h] = s[r, h].T @ qt
    return out, s


def test_the_scan_is_the_recurrence_as_it_is_written():
    ops = operands(2, 9, 2, 4, 8, 6)
    o, s = gated_delta_scan(*ops)
    want_o, want_s = by_hand(*ops)
    assert np.abs(np.asarray(o) - want_o).max() < TOL
    assert np.abs(np.asarray(s) - want_s).max() < TOL


@pytest.mark.parametrize("T,chunk", [(64, 64), (100, 64), (130, 64),
                                     (17, 64), (48, 16), (1, 64)])
def test_the_chunk_form_against_the_scan(T, chunk):
    """Chunks that divide the length and chunks that do not (the tail is
    padded with junk positions), a call shorter than a chunk, a call of
    one position; two key heads on four value heads."""
    ops = operands(2, T, 2, 4, 32, 16, seed=T)
    o, s = gated_delta_chunk_scan(*ops, chunk=chunk)
    want_o, want_s = gated_delta_scan(*ops)
    assert float(jnp.abs(o - want_o).max()) < TOL
    assert float(jnp.abs(s - want_s).max()) < TOL
    assert float(jnp.abs(want_o).max()) > 0.1


def test_the_chunk_form_at_16_key_heads_on_32_value_heads():
    ops = operands(1, 70, 16, 32, 16, 8, seed=5)
    o, s = gated_delta_chunk_scan(*ops, chunk=32)
    want_o, want_s = gated_delta_scan(*ops)
    assert float(jnp.abs(o - want_o).max()) < TOL
    assert float(jnp.abs(s - want_s).max()) < TOL
    # key head j serves value heads 2j and 2j + 1, and no other
    q, k, v, g, beta, state = ops
    moved = gated_delta_chunk_scan(q.at[:, :, 3].multiply(2.0), k, v, g, beta,
                                   state, chunk=32)[0]
    changed = jnp.abs(moved - o).max(axis=(0, 1, 3)) > 0
    assert list(np.flatnonzero(np.asarray(changed))) == [6, 7]


def test_junk_positions_leave_the_state_bit_identical():
    """Rows whose real positions end at 0 (no member of the call), 5 and
    40 of 70: after them beta = g = 0, and what the chunk form hands back
    is what the scan over the real positions alone hands back; a row of
    junk alone keeps its state to the bit."""
    junk_from = [0, 5, 40]
    ops = operands(3, 70, 2, 4, 16, 16, seed=2, junk_from=junk_from)
    o, s = gated_delta_chunk_scan(*ops, chunk=32)
    assert np.array_equal(np.asarray(s[0]), np.asarray(ops[5][0]))
    for r, n in enumerate(junk_from[1:], 1):
        want_o, want_s = gated_delta_scan(
            *(a[r:r + 1, :n] for a in ops[:5]), ops[5][r:r + 1])
        assert float(jnp.abs(s[r] - want_s[0]).max()) < TOL
        assert float(jnp.abs(o[r, :n] - want_o[0]).max()) < TOL


@pytest.mark.parametrize("Hk,Hv,rows", [(2, 4, 3), (16, 32, 2)],
                         ids=["toy", "published-heads"])
def test_the_state_update_kernel_against_the_scan(Hk, Hv, rows):
    """The Pallas kernel, interpreted, at tiles of [128, 128]: 4 value
    heads in one block, and the published 32 on 16 key heads in two blocks
    of 16; a fresh row starts from zeros whatever its state holds."""
    q, k, v, g, beta, state = operands(rows, 1, Hk, Hv, 128, 128, seed=Hv)
    step = tuple(a[:, 0] for a in (q, k, v, g, beta))
    fresh = jnp.arange(rows) == 1
    with record_traced() as traced:
        o, s = gated_delta_state_update(*step, state, fresh=fresh,
                                        interpret=True)
    assert traced_name(traced["gdn"]) == \
        f"pallas_gdn_update[Dv-minor,heads={min(Hv, 16)}]"
    want_o, want_s = gated_delta_scan(
        q, k, v, g, beta, jnp.where(fresh[:, None, None, None], 0.0, state))
    assert float(jnp.abs(o - want_o[:, 0]).max()) < TOL
    assert float(jnp.abs(s - want_s).max()) < TOL
    # off the TPU, uninterpreted, the same step in plain jax.numpy
    with record_traced() as traced:
        o2, s2 = gated_delta_state_update(*step, state, fresh=fresh)
    assert traced_name(traced["gdn"]) == "dense"
    assert float(jnp.abs(o2 - o).max()) < TOL
    assert float(jnp.abs(s2 - s).max()) < TOL


@pytest.mark.parametrize("interpret", [True, None], ids=["kernel", "dense"])
def test_a_step_of_beta_0_and_g_0_leaves_the_state_bit_identical(interpret):
    q, k, v, g, beta, state = operands(2, 1, 2, 4, 128, 128, seed=9)
    zero = jnp.zeros_like(g[:, 0])
    _, s = gated_delta_state_update(q[:, 0], k[:, 0], v[:, 0], zero, zero,
                                    state, interpret=interpret)
    assert np.array_equal(np.asarray(s), np.asarray(state))


def test_the_kernel_refuses_heads_it_cannot_block():
    q, k, v, g, beta, state = operands(1, 1, 2, 4, 128, 64)
    with pytest.raises(ValueError, match="value channels on the 128 lanes"):
        gated_delta_state_update(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], state, interpret=True)


def test_steps_after_a_chunk_carry_its_state():
    """Prefill then decode: a chunk call, then three single steps through
    the kernel, against one scan over all the positions."""
    ops = operands(2, 23, 2, 4, 128, 128, seed=4)
    q, k, v, g, beta, state = ops
    o, s = gated_delta_chunk_scan(*(a[:, :20] for a in ops[:5]), state,
                                  chunk=8)
    outs = [o]
    for t in range(20, 23):
        o, s = gated_delta_state_update(q[:, t], k[:, t], v[:, t], g[:, t],
                                        beta[:, t], s, interpret=True)
        outs.append(o[:, None])
    want_o, want_s = gated_delta_scan(*ops)
    assert float(jnp.abs(jnp.concatenate(outs, 1) - want_o).max()) < TOL
    assert float(jnp.abs(s - want_s).max()) < TOL
