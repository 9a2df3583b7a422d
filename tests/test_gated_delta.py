"""The gated delta rule's two forms agree: ``gated_delta_chunked`` (prefill,
chunks of 64) against the token-by-token recurrence ``gated_delta_step``
(decode), at the chunk's edges, from a state that is not zero, with padding
the state must not see."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.ops.gated_delta import (
    CHUNK,
    _unit_lower_inverse,
    gated_delta_chunked,
    gated_delta_step,
)

B, H, DK, DV = 2, 2, 8, 16


def inputs(T, seed=0, zero_state=False):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, DK))) / DK ** 0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, DK)))
    v = jax.random.normal(ks[2], (B, T, H, DV))
    # alpha from 0.37 to 0.999, beta in (0, 2): the ranges the model produces
    log_alpha = -jnp.exp(jax.random.uniform(ks[3], (B, T, H), minval=-7.0, maxval=0.0))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    state = jnp.zeros((B, H, DV, DK)) if zero_state else jax.random.normal(ks[5], (B, H, DV, DK))
    return q, k, v, log_alpha, beta, state


def recurrence(q, k, v, log_alpha, beta, state, n_valid=None):
    """Token by token; positions at or past ``n_valid`` leave the state."""
    n_valid = q.shape[1] if n_valid is None else n_valid

    def token(state, x):
        t, *row = x
        o, new = gated_delta_step(*row, state)
        return jnp.where(t < n_valid, new, state), o

    rows = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_alpha, beta)]
    state, o = jax.lax.scan(token, state, (jnp.arange(q.shape[1]), *rows))
    return jnp.moveaxis(o, 0, 1), state


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


@pytest.mark.parametrize("size", [2, 8, CHUNK])
@pytest.mark.parametrize("scale", [0.3, 2.0])
def test_unit_lower_inverse_against_numpy(size, scale):
    """Entries up to ``scale`` in size (beta is at most 2, keys have unit
    length): at 2.0 the inverse of a 64 x 64 matrix has entries of 1e10 and
    the doubling still reads it to a few of float32's last digits of the
    largest."""
    rng = np.random.default_rng(size)
    a = np.tril(rng.uniform(-scale, scale, size=(3, size, size)), -1).astype(np.float32)
    want = np.linalg.inv(np.eye(size) + a.astype(np.float64))
    got = np.asarray(jax.jit(_unit_lower_inverse)(jnp.asarray(a)))
    assert np.array_equal(got, np.tril(got)) and np.all(np.diagonal(got, axis1=-2, axis2=-1) == 1.0)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("T", [1, CHUNK - 1, CHUNK, CHUNK + 1, 200])
@pytest.mark.parametrize("zero_state", [True, False])
def test_chunked_equals_the_recurrence(T, zero_state):
    x = inputs(T, seed=T, zero_state=zero_state)
    want_o, want_s = recurrence(*x)
    got_o, got_s = jax.jit(gated_delta_chunked)(*x)
    assert got_o.shape == (B, T, H, DV) and got_o.dtype == jnp.float32
    close(got_o, want_o)
    close(got_s, want_s)


@pytest.mark.parametrize("T,n_valid", [(CHUNK, 17), (2 * CHUNK, CHUNK), (200, 131), (200, 1)])
def test_positions_past_n_valid_are_the_identity_on_the_state(T, n_valid):
    x = inputs(T, seed=7)
    want_o, want_s = recurrence(*x, n_valid=n_valid)
    got_o, got_s = gated_delta_chunked(*x, n_valid=n_valid)
    close(got_o[:, :n_valid], want_o[:, :n_valid])
    close(got_s, want_s)
    # and the state is NOT what folding the padding in would give
    assert float(jnp.abs(got_s - recurrence(*x)[1]).max()) > 1e-2


def test_n_valid_may_differ_by_row():
    x = inputs(150, seed=3)
    _, got = gated_delta_chunked(*x, n_valid=jnp.array([150, 70]))
    close(got[0], recurrence(*x)[1][0])
    close(got[1], recurrence(*x, n_valid=70)[1][1])


@pytest.mark.parametrize("prefill,steps", [(CHUNK + 5, 20), (3, 70)])
def test_chunked_prefill_then_steps_equals_the_recurrence_over_the_whole(prefill, steps):
    x = inputs(prefill + steps, seed=11, zero_state=True)
    want_o, want_s = recurrence(*x)
    head = [a[:, :prefill] for a in x[:5]]
    o, state = gated_delta_chunked(*head, x[5])
    outs = [o]
    for t in range(prefill, prefill + steps):
        o, state = gated_delta_step(*(a[:, t] for a in x[:5]), state)
        outs.append(o[:, None])
    close(jnp.concatenate(outs, axis=1), want_o)
    close(state, want_s)


def test_bfloat16_inputs_keep_a_float32_state():
    x = inputs(70, seed=5)
    low = [a.astype(jnp.bfloat16) for a in x[:5]]
    o, state = gated_delta_chunked(*low, x[5])
    assert o.dtype == jnp.float32 and state.dtype == jnp.float32
    want_o, want_s = recurrence(*(a.astype(jnp.float32) for a in low), x[5])
    close(o, want_o)
    close(state, want_s)
