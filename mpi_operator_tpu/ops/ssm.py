"""A selective state-space layer's recurrence (Mamba-1, arXiv:2312.00752)
with the state carried in and handed back: the form a served model needs,
where a prompt arrives in chunks and a decode step is a chunk of one.

    s_t = exp(delta_t A) * s_{t-1} + (delta_t x_t) B_t^T      [Din x N]
    y_t = s_t C_t + D * x_t                                    [Din]

The state is held `[N, Din]`, channels minor: a float32 array whose minor
dimension is the 16 states would fill an eighth of each 128-lane tile,
on the chip eight times its bytes.

Plain `jax.numpy`, float32: a `lax.scan` over the chunk's positions (one
position is the step itself, no loop). A position whose `delta` is 0
leaves the state exactly as it was (exp(0) = 1, and + 0), which is how a
caller holds the state over pad tokens and over rows that are no member
of a call. There is no kernel; XLA fuses a position's few elementwise
passes over the state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def selective_scan(x, delta, A, B, C, D, state):
    """x, delta [G, T, Din]; A [N, Din]; B, C [G, T, N]; D [Din];
    state [G, N, Din] -> (y [G, T, Din], state after position T-1), all
    float32."""
    def step(s, at):
        x_t, d_t, b_t, c_t = at
        s = jnp.exp(d_t[:, None, :] * A) * s \
            + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + D * x_t

    if x.shape[1] == 1:
        state, y = step(state, (x[:, 0], delta[:, 0], B[:, 0], C[:, 0]))
        return y[:, None], state
    state, y = jax.lax.scan(
        step, state, tuple(jnp.swapaxes(a, 0, 1) for a in (x, delta, B, C)))
    return jnp.swapaxes(y, 0, 1), state


def causal_conv(x, tail, w, b, count):
    """The depthwise causal convolution before the scan, over a chunk that
    continues a sequence: x [G, T, Din] the chunk's inputs, tail
    [G, K-1, Din] the K-1 inputs before it, w [K, Din], b [Din], count [G]
    how many of the chunk's positions are real (they come first).
    Returns (y [G, T, Din] float32, the K-1 inputs before position `count`:
    the next chunk's tail, the old one where `count` is 0)."""
    K = w.shape[0]
    T = x.shape[1]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = b.astype(jnp.float32) + sum(
        seq[:, k:k + T].astype(jnp.float32) * w[k].astype(jnp.float32)
        for k in range(K))
    at = count[:, None] + jnp.arange(K - 1)[None]            # [G, K-1]
    return y, jnp.take_along_axis(seq, at[..., None], axis=1).astype(
        tail.dtype)


__all__ = ["selective_scan", "causal_conv"]
