"""``ops/delta_rule.py``: the chunked gated delta rule against the
recurrence as it is written, one step at a time; values and gradients,
at one chunk, two, several, and a length that is no multiple."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.ops.delta_rule import _inverse_unit_lower, delta_rule


def recurrence(q, k, v, g, beta):
    """``S <- exp(g) S; u = beta (v - S^T k); S <- S + k u^T; o = S^T q``
    per head, a ``lax.scan`` over the positions."""
    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp            # [B,H,dk] .. [B,H]
        state = state * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhde,bhd->bhe", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    bsz, _, h, dk = q.shape
    _, o = lax.scan(step, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def inputs(seed, seq, heads=3, dk=8, dv=16, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (batch, seq, heads, dk), jnp.float32)
    k = jax.random.normal(ks[1], (batch, seq, heads, dk), jnp.float32)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, seq, heads, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("c", [2, 8, 64])
def test_the_blocked_inverse_inverts_a_unit_lower_triangle(c):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(c), (3, c, c)), -1) * 0.3
    inv = _inverse_unit_lower(a)
    np.testing.assert_allclose(
        np.asarray(inv @ (jnp.eye(c) + a)),
        np.broadcast_to(np.eye(c), a.shape), atol=2e-5)


@pytest.mark.parametrize("seq", [16, 32, 80, 27])
def test_chunked_values_match_the_recurrence(seq):
    args = inputs(seq, seq)
    with jax.default_matmul_precision("highest"):
        got = delta_rule(*args, chunk=16)
        want = recurrence(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("seq", [16, 32, 80, 27])
def test_chunked_gradients_match_the_recurrence(seq):
    args = inputs(100 + seq, seq)
    probe = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)

    def scalar(fn):
        return lambda *a: (fn(*a) * probe).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(scalar(lambda *a: delta_rule(*a, chunk=16)),
                       argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(scalar(recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def test_the_chunk_size_does_not_change_the_values():
    args = inputs(5, 64)
    with jax.default_matmul_precision("highest"):
        a, b = delta_rule(*args, chunk=8), delta_rule(*args, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-6)


def test_bfloat16_activations_keep_float32_decays():
    """In bfloat16 the output is the activations' type and stays near
    the float32 values: the decays and the solve never leave float32."""
    q, k, v, g, beta = inputs(9, 128, dk=16, dv=16)
    want = recurrence(q, k, v, g, beta)
    bf = jnp.bfloat16
    got = delta_rule(q.astype(bf), k.astype(bf), v.astype(bf), g, beta,
                     chunk=64)
    assert got.dtype == bf
    err = np.linalg.norm(np.asarray(got, np.float32) - np.asarray(want))
    assert err / np.linalg.norm(np.asarray(want)) < 0.03


def test_a_chunk_that_is_no_power_of_two_is_refused():
    with pytest.raises(ValueError):
        delta_rule(*inputs(1, 12), chunk=12)


def test_the_inverse_stays_exact_when_the_keys_point_the_same_way():
    """All keys of a chunk nearly equal and written at full strength:
    ``a`` is 0.95 everywhere under the diagonal. The true inverse's
    entries stay under 1; a product of powers over the whole chunk
    would cancel terms of 1e16 in float32."""
    c = 64
    a = jnp.tril(jnp.full((c, c), 0.95, jnp.float32), -1)
    want = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    got = np.asarray(_inverse_unit_lower(a[None])[0])
    assert np.abs(want).max() <= 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_many_steps_of_equal_keys_do_not_overflow():
    """The recurrence with one key for every position is a contraction;
    the chunked form has to stay one over a long sequence."""
    seq, h, dk, dv = 1024, 2, 16, 16
    k = jnp.ones((1, seq, h, dk)) / dk ** 0.5
    v = jax.random.normal(jax.random.PRNGKey(0), (1, seq, h, dv))
    g = jnp.full((1, seq, h), -1e-3)
    beta = jnp.full((1, seq, h), 0.97)
    got = delta_rule(k, k, v, g, beta, chunk=64)
    want = recurrence(k, k, v, g, beta)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
