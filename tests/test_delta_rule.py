"""``ops/delta_rule.py``: the chunked gated delta rule (its Pallas
kernels, in interpret mode here) against the recurrence as it is
written, one step at a time; values and gradients, at one chunk, two,
several, and a length that is no multiple."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.ops import delta_rule as rule_mod
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


def tile_of(blocks):
    """``[n, C, C]`` -> ``[n C, n C]``, the blocks on the diagonal: a
    kernel's tile of ``n`` chunks."""
    n, c, _ = blocks.shape
    return jnp.einsum("nij,nm->nimj", blocks, jnp.eye(n)).reshape(n * c, n * c)


@pytest.mark.parametrize("c", [2, 8, 64])
def test_the_blocked_inverse_inverts_a_unit_lower_triangle(c):
    """Three chunks a tile: the inverse of each, and nothing between
    them."""
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(c), (3, c, c)), -1) * 0.3
    inv = _inverse_unit_lower(tile_of(a), c)
    np.testing.assert_allclose(
        np.asarray(inv @ (jnp.eye(3 * c) + tile_of(a))), np.eye(3 * c),
        atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(inv)[np.asarray(tile_of(jnp.ones_like(a))) == 0], 0.0)


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
    got = np.asarray(_inverse_unit_lower(a, c))
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


def published(seed=3, seq=192, key_heads=1, rep=2, d=128):
    """bfloat16 ``q``, ``k`` at the key heads and ``v`` at ``rep`` value
    heads a key head, the published head width, three chunks of 64."""
    q, k, _, _, _ = inputs(seed, seq, heads=key_heads, dk=d, dv=d, batch=1)
    _, _, v, g, beta = inputs(seed + 1, seq, heads=key_heads * rep, dk=d,
                              dv=d, batch=1)
    bf = jnp.bfloat16
    return q.astype(bf), k.astype(bf), v.astype(bf), g * 0.3, beta


def at_value_heads(fn, rep=2):
    """``fn`` on ``q``, ``k`` repeated to the value heads, as
    ``qwen3_next._gdn_group`` calls it; float32 out."""
    def run(q, k, v, g, beta):
        q, k = (jnp.repeat(t, rep, axis=2) for t in (q, k))
        return fn(q, k, v, g, beta).astype(jnp.float32)
    return run


def rel(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def test_published_widths_in_bfloat16_match_the_recurrence():
    """``dk = dv = 128``, chunk 64, three chunks (a tile and a half),
    two value heads a key head: the values in the activations' type."""
    args = published()
    got = delta_rule(*(jnp.repeat(t, 2, axis=2) for t in args[:2]),
                     *args[2:], chunk=64)
    assert got.dtype == jnp.bfloat16 and got.shape == args[2].shape
    want = at_value_heads(recurrence)(
        *(t.astype(jnp.float32) for t in args))
    assert rel(got, want) < 0.01


@pytest.mark.parametrize("which", range(5), ids="q k v g beta".split())
def test_published_widths_in_bfloat16_gradients(which):
    """Each of the five gradients, the key heads' summed over their
    value heads, against the float32 recurrence's on the same rounded
    operands."""
    args = published()
    probe = jax.random.normal(jax.random.PRNGKey(11), args[2].shape)

    def grad(fn, args):
        return jax.grad(lambda *a: (at_value_heads(fn)(*a) * probe).sum(),
                        argnums=which)(*args)

    got = grad(lambda *a: delta_rule(*a, chunk=64), args)
    want = grad(recurrence, tuple(t.astype(jnp.float32) for t in args))
    assert got.dtype == args[which].dtype
    assert rel(got, want) < 0.02


def test_bfloat16_decays_change_the_result(monkeypatch):
    """``DECAY_DTYPE`` is read when ``delta_rule`` is traced and holds
    inside the kernel: in bfloat16 the values leave the float32
    recurrence by several times what float32 decays cost."""
    args = published()
    want = at_value_heads(recurrence)(
        *(t.astype(jnp.float32) for t in args))
    run = at_value_heads(lambda *a: delta_rule(*a, chunk=64))
    kept = rel(run(*args), want)
    monkeypatch.setattr(rule_mod, "DECAY_DTYPE", jnp.bfloat16)
    assert rel(run(*args), want) > 2 * kept


def test_a_head_width_the_tpu_cannot_tile_is_refused(monkeypatch):
    """On the TPU a block spec picks a head's columns of ``[B, S, H x
    d]``: they have to be whole lanes, and the error says which width
    is not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="dk = 8"):
        delta_rule(*inputs(1, 64), chunk=64)
