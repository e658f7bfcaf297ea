"""Gradient parity of the fused BN(+residual)(+ReLU) op vs the flax/XLA
reference (gradient-parity test vs the XLA BN backward). Covers the jnp
fallback and the Pallas kernels via
the interpreter on shapes spanning the channel-folding (C < 128) and
plain (C >= 128) layouts, plus the residual-add join."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import fused_bn

EPS = 1e-5


def _ref(x, gamma, beta, residual=None, relu=True):
    """flax-numerics reference: fp32 stats (mean of x, mean of x^2,
    biased var — flax.linen.normalization._compute_stats), fp32
    normalize, optional residual add then relu, cast back."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=tuple(range(x.ndim - 1)))
    var = jnp.mean(jnp.square(xf), axis=tuple(range(x.ndim - 1)))
    var = var - jnp.square(mean)
    rstd = jax.lax.rsqrt(var + EPS)
    z = (xf - mean) * (rstd * gamma) + beta
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    if relu:
        z = jnp.maximum(z, 0.0)
    return z.astype(x.dtype), mean, var


def _inputs(shape, seed=0, dtype=jnp.bfloat16, residual=False):
    rng = np.random.RandomState(seed)
    c = shape[-1]
    x = jnp.asarray(rng.randn(*shape), dtype)
    g = jnp.asarray(rng.randn(*shape), dtype)  # upstream cotangent
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, c), jnp.float32)
    beta = jnp.asarray(rng.randn(c) * 0.1, jnp.float32)
    r = jnp.asarray(rng.randn(*shape), dtype) if residual else None
    return x, g, gamma, beta, r


SHAPES = [
    (4, 8, 8, 256),   # plain layout
    (4, 8, 8, 64),    # folded layout (k=2)
    (8, 7, 7, 128),   # M with small pow2 factor (8*49)
    (2, 5, 3, 96),    # no 128-fold -> jnp fallback path
    (512, 1, 1, 384), # block cap (131072//384=341) must floor to pow2
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("residual", [False, True])
def test_grad_parity_interpret(shape, relu, residual):
    """Pallas (interpret) and the jnp fallback both match flax-numerics
    XLA autodiff for y, dx, dr, dgamma, dbeta, and the batch stats."""
    x, g, gamma, beta, r = _inputs(shape, residual=residual)
    impl = ("interpret" if fused_bn._can_pallas(x.size // shape[-1],
                                                shape[-1]) else "jnp")

    def loss_ref(x, gamma, beta, r):
        y, _, _ = _ref(x, gamma, beta, residual=r, relu=relu)
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32))

    def loss_fused(x, gamma, beta, r):
        y, _, _ = fused_bn.bn_act(x, gamma, beta, residual=r, eps=EPS,
                                  relu=relu, impl=impl)
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32))

    argnums = (0, 1, 2, 3) if residual else (0, 1, 2)
    ref_grads = jax.jit(jax.grad(loss_ref, argnums))(x, gamma, beta, r)
    fus_grads = jax.jit(jax.grad(loss_fused, argnums))(x, gamma, beta, r)

    y_ref, m_ref, v_ref = _ref(x, gamma, beta, residual=r, relu=relu)
    y_fus, m_fus, v_fus = fused_bn.bn_act(
        x, gamma, beta, residual=r, eps=EPS, relu=relu, impl=impl)
    np.testing.assert_allclose(np.asarray(y_fus, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=0.05, rtol=0.05)
    np.testing.assert_allclose(np.asarray(m_fus), np.asarray(m_ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(v_fus), np.asarray(v_ref),
                               atol=1e-3, rtol=1e-3)

    names = ["dx", "dgamma", "dbeta", "dr"][:len(argnums)]
    for name, a, b in zip(names, fus_grads, ref_grads):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(1.0, float(np.max(np.abs(b))))
        assert np.max(np.abs(a - b)) <= 0.05 * scale, (
            name, np.max(np.abs(a - b)), scale)


def test_inference_matches_running_stats():
    x, g, gamma, beta, r = _inputs((4, 8, 8, 64), residual=True)
    rm = jnp.asarray(np.random.RandomState(1).randn(64) * 0.1, jnp.float32)
    rv = jnp.asarray(np.random.RandomState(2).uniform(0.5, 1.5, 64),
                     jnp.float32)
    y = fused_bn.bn_act_inference(x, gamma, beta, rm, rv, residual=r,
                                  eps=EPS, relu=True)
    rstd = jax.lax.rsqrt(rv + EPS)
    z = (x.astype(jnp.float32) - rm) * (rstd * gamma) + beta
    z = jnp.maximum(z + r.astype(jnp.float32), 0.0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(z.astype(x.dtype), np.float32),
                               atol=0.05, rtol=0.05)


def test_block_rows_divides():
    # regression: a non-power-of-two cap (C=384 -> 341) must not yield a
    # block size that fails to divide the row count (truncated grid ->
    # silently skipped trailing rows)
    for m2, c2 in ((512, 384), (802816, 1024), (12544, 2048), (64, 640)):
        bm = fused_bn._block_rows(m2, c2)
        assert m2 % bm == 0, (m2, c2, bm)
        assert bm >= 8


def test_bad_impl_raises():
    import pytest as _pytest
    x = jnp.ones((4, 4, 4, 64), jnp.bfloat16)
    with _pytest.raises(ValueError):
        fused_bn.bn_act(x, jnp.ones(64), jnp.zeros(64), impl="palas")


def test_fold_helpers():
    assert fused_bn._fold(64) == 2
    assert fused_bn._fold(32) == 4
    assert fused_bn._fold(128) == 1
    assert fused_bn._fold(96) == 1
    assert fused_bn._pow2_div(802816) >= 512
    assert fused_bn._can_pallas(256 * 56 * 56, 256)
    assert fused_bn._can_pallas(256 * 112 * 112, 64)
    assert not fused_bn._can_pallas(30, 96)


def test_resnet_flax_vs_fused_parity():
    """The fused-BN ResNet shares the flax model's parameter tree
    (checkpoint compatibility) and computes the same function: same
    logits, same grads, same batch_stats update, on identical params."""
    import optax
    from horovod_tpu.models.resnet import ResNet

    model_flax = ResNet(stage_sizes=[1, 1], num_classes=10,
                        num_filters=8, bn_impl="flax")
    model_fused = ResNet(stage_sizes=[1, 1], num_classes=10,
                         num_filters=8, bn_impl="jnp")
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32, 3),
                    jnp.float32)
    labels = jnp.asarray([1, 2])
    v_flax = model_flax.init(jax.random.PRNGKey(0), x, train=True)
    v_fused = model_fused.init(jax.random.PRNGKey(0), x, train=True)
    # identical trees
    assert (jax.tree_util.tree_structure(v_flax)
            == jax.tree_util.tree_structure(v_fused))
    # run fused with flax's params to prove interchangeability
    def loss(params, model):
        logits, new_state = model.apply(
            {"params": params, "batch_stats": v_flax["batch_stats"]}, x,
            train=True, mutable=["batch_stats"])
        l = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return l, (logits, new_state["batch_stats"])

    (l_a, (lg_a, bs_a)), g_a = jax.value_and_grad(
        loss, has_aux=True)(v_flax["params"], model_flax)
    (l_b, (lg_b, bs_b)), g_b = jax.value_and_grad(
        loss, has_aux=True)(v_flax["params"], model_fused)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                               atol=0.15, rtol=0.1)
    assert abs(float(l_a) - float(l_b)) < 0.05
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_a),
            jax.tree_util.tree_leaves_with_path(g_b)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(1.0, float(np.max(np.abs(a))))
        assert np.max(np.abs(a - b)) <= 0.07 * scale, (
            jax.tree_util.keystr(pa), np.max(np.abs(a - b)), scale)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(bs_a),
            jax.tree_util.tree_leaves_with_path(bs_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-2, rtol=2e-2,
                                   err_msg=jax.tree_util.keystr(pa))
