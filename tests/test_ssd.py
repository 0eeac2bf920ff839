"""Mamba-2's recurrence as `ops/ssm.py` serves it: the chunked matmul form
against the recurrence position by position, whatever the plan of chunks;
junk positions and rows that are no member of a call; the decode step's
Pallas kernel, interpreted, against the `jax.numpy` step. Float32 on the
CPU: a tolerance is what summation order costs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.ops import ssm

TOL = 2e-5


def inputs(G=2, T=384, H=4, P=8, K=2, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (G, T, H, P))
    # steps of 0.01 to 1, decays a head of 1 to 16: a head forgets over
    # one position or over hundreds
    dt = jnp.exp(jax.random.uniform(ks[1], (G, T, H), minval=-4.6, maxval=0))
    A = -(1.0 + 15.0 * jax.random.uniform(ks[2], (H,)))
    B, C = (jax.random.normal(k, (G, T, K, N)) for k in ks[3:5])
    D = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    state = jax.random.normal(ks[6], (G, H, N, P))
    return x, dt, A, B, C, D, state


def by_positions(x, dt, A, B, C, D, state):
    """The recurrence itself, one position at a time, written out."""
    G, T, H, P = x.shape
    J = H // B.shape[2]
    ys = []
    for t in range(T):
        b, c = (jnp.repeat(a[:, t], J, axis=1) for a in (B, C))   # [G, H, N]
        state = jnp.exp(dt[:, t] * A)[..., None, None] * state \
            + b[..., None] * (dt[:, t, :, None] * x[:, t])[:, :, None, :]
        ys.append(jnp.sum(state * c[..., None], axis=2)
                  + D[:, None] * x[:, t])
    return jnp.stack(ys, 1), state


@pytest.fixture(scope="module")
def recurrence():
    args = inputs()
    return args, by_positions(*args)


PLANS = {
    "1x384": [384], "3x128": [128, 128, 128], "128+1+1": [128, 1, 1],
    "1+128+255": [1, 128, 255], "ragged": [100, 28, 200, 56],
    "ones": [1] * 5}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_chunk_plans_equal_the_recurrence_by_positions(recurrence, plan):
    """Each call of a plan carries the state in and hands it back; a call
    longer than a chunk (128) scans its chunks, one that is no multiple
    of it is padded with positions of dt 0; a call of one position is the
    decode step."""
    (x, dt, A, B, C, D, state), (want_y, _) = recurrence
    at, ys = 0, []
    for n in PLANS[plan]:
        cut = lambda a: a[:, at:at + n]                         # noqa: E731
        if n == 1:
            y, state = ssm.ssd_state_update(
                x[:, at], dt[:, at], A, B[:, at], C[:, at], D, state)
            y = y[:, None]
        else:
            y, state = ssm.ssd_chunk_scan(cut(x), cut(dt), A, cut(B), cut(C),
                                          D, state)
        ys.append(y)
        at += n
    got = jnp.concatenate(ys, 1)
    assert float(jnp.abs(got - want_y[:, :at]).max()) < TOL
    if at == x.shape[1]:
        assert float(jnp.abs(state - recurrence[1][1]).max()) < TOL


@pytest.mark.parametrize("chunk", [128, 64, 7])
def test_the_chunk_size_changes_nothing(recurrence, chunk):
    args, (want_y, want_state) = recurrence
    y, state = ssm.ssd_chunk_scan(*args, chunk=chunk)
    assert float(jnp.abs(y - want_y).max()) < TOL
    assert float(jnp.abs(state - want_state).max()) < TOL


@pytest.mark.parametrize("T", [1, 128, 200])
def test_a_row_whose_steps_are_all_zero_keeps_its_state_to_the_bit(T):
    """A row that is no member of a call: dt 0 at every position, whatever
    the tokens there project to."""
    x, dt, A, B, C, D, state = inputs(G=3, T=T, seed=1)
    dt = dt.at[1].set(0.0)
    _, held = ssm.ssd_chunk_scan(x, dt, A, B, C, D, state)
    assert np.array_equal(np.asarray(held[1]), np.asarray(state[1]))
    assert not np.array_equal(np.asarray(held[0]), np.asarray(state[0]))


def test_junk_positions_after_the_real_ones_change_nothing():
    """Pads after a row's real tokens: the state is what the real
    positions alone leave, whatever the pads hold."""
    x, dt, A, B, C, D, state = inputs(G=2, T=128, seed=2)
    real = 37
    dt = dt.at[:, real:].set(0.0)
    _, padded = ssm.ssd_chunk_scan(x, dt, A, B, C, D, state)
    _, other = ssm.ssd_chunk_scan(x.at[:, real:].set(9.0), dt, A,
                                  B.at[:, real:].set(-3.0), C, D, state)
    _, alone = ssm.ssd_chunk_scan(x[:, :real], dt[:, :real], A, B[:, :real],
                                  C[:, :real], D, state)
    assert np.array_equal(np.asarray(padded), np.asarray(other))
    assert float(jnp.abs(padded - alone).max()) < TOL


@pytest.mark.parametrize("H,P,K,N", [(4, 8, 2, 16), (32, 128, 2, 256),
                                     (16, 128, 1, 128)],
                         ids=["toy", "falcon-h1-34b", "one-group"])
def test_the_kernel_interpreted_is_the_step_in_jax_numpy(H, P, K, N):
    x, dt, A, B, C, D, state = inputs(G=3, T=1, H=H, P=P, K=K, N=N, seed=3)
    args = (x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D, state)
    want_y, want_state = ssm.ssd_state_update(*args)
    y, got = ssm.ssd_state_update(*args, interpret=True)
    assert float(jnp.abs(y - want_y).max()) < TOL * N ** 0.5
    assert float(jnp.abs(got - want_state).max()) < TOL


def test_the_kernel_holds_a_junk_row_and_starts_a_fresh_row_from_zeros():
    x, dt, A, B, C, D, state = inputs(G=4, T=1, seed=4)
    dt = dt.at[1].set(0.0)                       # row 1: a junk position
    fresh = jnp.asarray([False, False, True, True])
    # what a used slot holds may be anything: row 3's is not even finite
    state = state.at[3].set(jnp.inf)
    args = (x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D)
    for interpret in (None, True):
        y, got = ssm.ssd_state_update(*args, state, fresh=fresh,
                                      interpret=interpret)
        assert np.array_equal(np.asarray(got[1]), np.asarray(state[1]))
        y0, from_zeros = ssm.ssd_state_update(
            *args, jnp.zeros_like(state), interpret=interpret)
        assert np.array_equal(np.asarray(got[2:]), np.asarray(from_zeros[2:]))
        assert np.array_equal(np.asarray(y[2:]), np.asarray(y0[2:]))
        assert np.isfinite(np.asarray(y)).all()


def test_a_group_whose_heads_no_block_divides_is_refused():
    x, dt, A, B, C, D, state = inputs(G=1, T=1, H=24, K=2, seed=5)
    with pytest.raises(ValueError, match="no multiple"):
        ssm.ssd_state_update(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D,
                             state, interpret=True)
