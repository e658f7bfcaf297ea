"""``ops/delta_rule.py``: the chunked gated delta rule (its Pallas
kernels, in interpret mode here) against the recurrence as it is
written, one step at a time; values and gradients, at one chunk, two,
several, and a length that is no multiple; at ``dk != dv``, at widths
that are no lane multiple (96 / 192, 12 / 24), at head counts no block
of 4 divides, and at a write strength ``beta`` up to 2."""

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


def inputs(seed, seq, heads=3, dk=8, dv=16, batch=2, beta_scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (batch, seq, heads, dk), jnp.float32)
    k = jax.random.normal(ks[1], (batch, seq, heads, dk), jnp.float32)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, seq, heads, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, heads)))
    beta = beta_scale * jax.nn.sigmoid(
        jax.random.normal(ks[4], (batch, seq, heads)))
    return q, k, v, g, beta


# beside the lengths: a tiny pair of widths that is no multiple of
# anything with ``dk != dv``, five and thirty heads (no block of 4
# divides either), and a write strength in (0, 2)
SHAPES = [(16, {}), (32, {}), (80, {}), (27, {}),
          (48, {"dk": 12, "dv": 24, "heads": 5, "beta_scale": 2.0}),
          (32, {"dk": 12, "dv": 24, "heads": 30, "batch": 1,
                "beta_scale": 2.0}),
          (27, {"dk": 24, "dv": 12, "beta_scale": 2.0})]
SHAPE_IDS = ["16", "32", "80", "27", "48-12x24-5heads-beta2",
             "32-12x24-30heads-beta2", "27-24x12-beta2"]


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


@pytest.mark.parametrize("seq,shape", SHAPES, ids=SHAPE_IDS)
def test_chunked_values_match_the_recurrence(seq, shape):
    args = inputs(seq, seq, **shape)
    with jax.default_matmul_precision("highest"):
        got = delta_rule(*args, chunk=16)
        want = recurrence(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("seq,shape", SHAPES, ids=SHAPE_IDS)
def test_chunked_gradients_match_the_recurrence(seq, shape):
    args = inputs(100 + seq, seq, **shape)
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


@pytest.mark.parametrize("strength,largest", [(0.95, 1.0), (1.9, 1.9)])
def test_the_inverse_stays_exact_when_the_keys_point_the_same_way(
        strength, largest):
    """All keys of a chunk nearly equal and written at full strength:
    ``a`` is 0.95 everywhere under the diagonal, or 1.9 where ``beta``
    reaches 2 (the state's part along the key then changes sign at
    every step, and the inverse's entries alternate, up to 1.9). The
    true inverse's entries stay small; a product of powers over the
    whole chunk would cancel terms of 1e16 in float32."""
    c = 64
    a = jnp.tril(jnp.full((c, c), strength, jnp.float32), -1)
    want = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    got = np.asarray(_inverse_unit_lower(a, c))
    assert np.abs(want).max() <= largest
    np.testing.assert_allclose(got, want, atol=2e-5 * largest)


@pytest.mark.parametrize("strength,dk,dv", [(0.97, 16, 16), (1.9, 12, 24)])
def test_many_steps_of_equal_keys_do_not_overflow(strength, dk, dv):
    """The recurrence with one key for every position is a contraction
    (along the key by ``1 - beta``: -0.9 at a strength of 1.9, the sign
    changing at every step); the chunked form has to stay one over a
    long sequence."""
    seq, h = 1024, 2
    k = jnp.ones((1, seq, h, dk)) / dk ** 0.5
    v = jax.random.normal(jax.random.PRNGKey(0), (1, seq, h, dv))
    g = jnp.full((1, seq, h), -1e-3)
    beta = jnp.full((1, seq, h), strength)
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
    ``gated_deltanet.gdn_group`` calls it; float32 out."""
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


def nearly_parallel(seed, seq, heads, dk, dv, spread=0.05):
    """Keys of every chunk nearly parallel and written at a strength
    near 2: the entries of the chunk's ``A`` reach 1.9."""
    q, k, v, g, _ = inputs(seed, seq, heads=heads, dk=dk, dv=dv, batch=1)
    one = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 1, heads, dk))
    k = one + spread * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jnp.full(g.shape, 1.9) + 0.1 * jax.nn.sigmoid(g)
    return q, k, v, 0.01 * g, beta


@pytest.mark.parametrize("dk,dv", [(12, 24), (96, 192)])
def test_beta_near_two_on_nearly_parallel_keys_matches_the_recurrence(
        dk, dv):
    """Forward and all five gradients where the chunk's ``A`` is at its
    largest (``a`` up to 1.9 under the whole diagonal), at the tiny and
    at the published pair of widths, one value head a key head."""
    args = nearly_parallel(21, 128, 2, dk, dv)
    a = 1.9 * jnp.einsum("bihd,bjhd->bhij", args[1], args[1])[0, 0, 1:, 0]
    assert float(a.max()) > 1.85
    probe = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)

    def both(fn):
        out, pull = jax.vjp(fn, *args)
        return (out,) + pull(probe)

    with jax.default_matmul_precision("highest"):
        got = both(lambda *x: delta_rule(*x, chunk=64))
        want = both(recurrence)
    for name, x, y in zip("o q k v g beta".split(), got, want):
        assert rel(x, y) < 3e-4, name


def test_published_olmo_widths_in_bfloat16_match_the_recurrence():
    """``dk`` 96, ``dv`` 192, ``beta`` in (0, 2), three chunks of 64 (a
    tile and a half), three heads: values and the five gradients in the
    activations' type against the float32 recurrence's on the same
    rounded operands."""
    q, k, v, g, beta = inputs(3, 192, heads=3, dk=96, dv=192, batch=1,
                              beta_scale=2.0)
    bf = jnp.bfloat16
    args = (q.astype(bf), k.astype(bf), v.astype(bf), g * 0.3, beta)
    args32 = tuple(t.astype(jnp.float32) for t in args)
    got = delta_rule(*args, chunk=64)
    assert got.dtype == bf and got.shape == v.shape
    assert rel(got, recurrence(*args32)) < 0.01
    probe = jax.random.normal(jax.random.PRNGKey(11), v.shape)

    def grads(fn, a):
        return jax.grad(lambda *x: (fn(*x).astype(jnp.float32) * probe).sum(),
                        argnums=(0, 1, 2, 3, 4))(*a)

    for name, x, y in zip("q k v g beta".split(),
                          grads(lambda *x: delta_rule(*x, chunk=64), args),
                          grads(recurrence, args32)):
        assert rel(x, y) < 0.02, name


def _kernel_operands(fn, *args):
    """The shapes of the operands of the first ``pallas_call`` in the
    jaxpr of ``fn(*args)`` (traced, never run)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append([v.aval.shape for v in eqn.invars])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found[0]


def test_on_the_tpu_a_head_is_padded_to_whole_lanes(monkeypatch):
    """Where Mosaic compiles the kernels a block spec picks a head's
    columns of ``[B, S, H x d]`` and they have to be whole lanes: heads
    of 96 / 192 meet the kernels as 128 / 256 columns of which the last
    are zeros, and the output is cut back; heads of 128 meet them as
    they are; in interpret mode nothing is padded."""
    args = inputs(1, 128, heads=3, dk=96, dv=192, batch=1)
    assert _kernel_operands(lambda *a: delta_rule(*a, chunk=64), *args)[:3] \
        == [(1, 128, 3 * 96), (1, 128, 3 * 96), (1, 128, 3 * 192)]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _kernel_operands(lambda *a: delta_rule(*a, chunk=64), *args)[:3] \
        == [(1, 128, 3 * 128), (1, 128, 3 * 128), (1, 128, 3 * 256)]
    assert jax.eval_shape(lambda *a: delta_rule(*a, chunk=64),
                          *args).shape == (1, 128, 3, 192)
    whole = inputs(1, 128, heads=2, dk=128, dv=128, batch=1)
    assert _kernel_operands(lambda *a: delta_rule(*a, chunk=64), *whole)[:3] \
        == [(1, 128, 256)] * 3


def test_columns_of_zeros_change_nothing(monkeypatch):
    """The padded path itself, run here: with the lane narrowed to 16
    the widths 12 / 24 are padded to 16 / 32 in interpret mode; values
    and gradients are the unpadded kernels'."""
    args = inputs(4, 48, heads=3, dk=12, dv=24, batch=1, beta_scale=2.0)
    probe = jax.random.normal(jax.random.PRNGKey(2), args[2].shape)

    def run():
        return jax.value_and_grad(
            lambda *a: (delta_rule(*a, chunk=16) * probe).sum(),
            argnums=(0, 1, 2, 3, 4))(*args)

    with jax.default_matmul_precision("highest"):
        plain = run()
        monkeypatch.setattr(rule_mod, "_lane_pad", lambda d: -d % 16)
        assert _kernel_operands(lambda *a: delta_rule(*a, chunk=16),
                                *args)[2] == (1, 128, 3 * 32)
        padded = run()
    np.testing.assert_allclose(padded[0], plain[0], rtol=1e-5)
    for x, y in zip(padded[1], plain[1]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-6)
