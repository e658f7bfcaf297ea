"""``models/gated_deltanet.py``: the Gated DeltaNet group on the flat
layout (q, k, v, z and o as ``[B, S, heads x width]`` between the two
projections) against the per-head formulation kept here, forward and
``jax.grad``; the layout itself at the level of the jaxpr; and which
layout a group takes, by its head widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import gated_deltanet as gdn
from horovod_tpu.models import qwen3_next as qn
from horovod_tpu.ops.ssd_scan import causal_conv1d
from olmo_hybrid_common import make_cfg as make_olmo_cfg
from qwen3_next_common import make_cfg

SEQ = 32
# rep 2 at widths of whole lanes (qwen3next's geometry, which takes the
# flat layout), and rep 1 at widths that are not (olmo-hybrid's: 96 /
# 192 there, 12 / 24 here), with the write strength's factor 2
GEOMETRIES = {
    "rep2-whole-lanes": lambda: make_cfg(
        d_model=64, gdn_key_heads=4, gdn_value_heads=8, gdn_key_dim=128,
        gdn_value_dim=128, gdn_groups=2),
    "rep1-not-whole-lanes": lambda: make_olmo_cfg(allow_neg_eigval=True),
}


def _cfg(geometry):
    return GEOMETRIES[geometry]()


def _per_head_group(x, b, a, conv_w, dt_bias, a_log, gate_w, cfg,
                    recurrence):
    """The group as the per-head formulation computes it: every array
    ``[B, S, heads, width]``, each norm over the last axis."""
    bsz, s, _ = x.shape
    dk, dv, rep = cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_rep
    x = x.reshape(bsz, s, -1, cfg.gdn_head_width)
    hk = x.shape[2]
    z = x[..., cfg.gdn_conv_width:].reshape(bsz, s, hk * rep, dv)
    qkv = jax.nn.silu(causal_conv1d(
        x[..., :cfg.gdn_conv_width].reshape(bsz, s, -1), conv_w,
        jnp.zeros((conv_w.shape[0],), conv_w.dtype)))
    qkv = qkv.reshape(bsz, s, hk, cfg.gdn_conv_width).astype(jnp.float32)

    def l2(t):
        return t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + cfg.eps)
    q = jnp.repeat(l2(qkv[..., :dk]) * dk ** -0.5, rep, axis=2)
    k = jnp.repeat(l2(qkv[..., dk:2 * dk]), rep, axis=2)
    v = qkv[..., 2 * dk:].reshape(bsz, s, hk * rep, dv)
    beta = cfg.gdn_beta_scale * jax.nn.sigmoid(b)
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    o = recurrence(q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype),
                   g, beta, chunk=cfg.chunk).astype(jnp.float32)
    y = (o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.eps)
         * gate_w * jax.nn.silu(z))
    return y.astype(x.dtype).reshape(bsz, s, hk * rep * dv)


def _group_inputs(cfg, seed=0):
    """One group's inputs: ``in_proj``'s output for the group, ``b``,
    ``a``, and the group's slices of a layer's parameters (a gate that
    is not all ones)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = gdn.init_gdn(cfg, ks[0])
    g = cfg.gdn_groups
    hk, hv = cfg.gdn_key_heads // g, cfg.gdn_value_heads // g
    x = jax.random.normal(ks[1], (2, SEQ, hk * cfg.gdn_head_width))
    b = 2.0 * jax.random.normal(ks[2], (2, SEQ, hv))
    a = jax.random.normal(ks[3], (2, SEQ, hv))
    gate = p["gate_norm"] + 0.25 * jnp.cos(jnp.arange(cfg.gdn_value_dim))
    return (x, b, a, p["conv_w"].reshape(g, -1, cfg.conv_kernel)[0],
            p["dt_bias"][:hv], p["A_log"][:hv], gate)


def _running_sum(q, k, v, g, beta, *, chunk):
    """A stand-in with the recurrence's interface and per-head coupling
    of all five inputs (the group's own work is what is compared; the
    mixer's test runs the delta rule itself): per head, a running sum
    over the positions of ``beta exp(g) (q . k) v``."""
    w = (jnp.sum(q * k, -1) * beta * jnp.exp(g))[..., None] * v
    return jnp.cumsum(w, axis=1).astype(v.dtype)


def _value_and_grad(fn, args, cfg):
    """``fn``'s output and the gradient of a fixed random projection of
    it in every array argument, jitted, at float32's full precision."""
    def loss(*arrays):
        y = fn(*arrays, cfg, _running_sum)
        w = jax.random.normal(jax.random.PRNGKey(9), y.shape)
        return jnp.sum(y.astype(jnp.float32) * w), y
    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return y, grads


def _rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("path", ["gdn_group", "flat"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_the_group_equals_the_per_head_formulation(geometry, path):
    """``gdn_group`` as it dispatches, and the flat layout forced at
    either geometry, against the per-head formulation: the output and
    the gradients of ``x``, ``b``, ``a``, the convolution, ``dt_bias``,
    ``A_log`` and the gate within float32's rounding."""
    cfg = _cfg(geometry)
    args = _group_inputs(cfg)
    fn = gdn.gdn_group if path == "gdn_group" else gdn._flat_group
    got_y, got = _value_and_grad(fn, args, cfg)
    want_y, want = _value_and_grad(_per_head_group, args, cfg)
    assert got_y.shape == want_y.shape == args[0].shape[:2] + (
        cfg.gdn_value_heads // cfg.gdn_groups * cfg.gdn_value_dim,)
    assert _rel(got_y, want_y) < 1e-5
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-5


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_the_mixer_equals_the_per_head_formulation(geometry, monkeypatch):
    """``gdn_mixer`` whole (both projections, the groups under their
    checkpoints in ``lax.map``) with ``gdn_group`` as it dispatches,
    against the same mixer over the per-head formulation: output and
    the gradient of every parameter and of the input."""
    cfg = _cfg(geometry)
    params = gdn.init_gdn(cfg, jax.random.PRNGKey(1))
    params["gate_norm"] = params["gate_norm"] + 0.25 * jnp.cos(
        jnp.arange(cfg.gdn_value_dim))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, cfg.d_model))

    def run():
        def loss(p, u_):
            y = gdn.gdn_mixer(p, u_, cfg, qn._recurrence)
            w = jax.random.normal(jax.random.PRNGKey(9), y.shape)
            return jnp.sum(y * w), y
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True))(params, u)
    (_, got_y), got = run()
    monkeypatch.setattr(gdn, "gdn_group", _per_head_group)
    (_, want_y), want = run()
    assert _rel(got_y, want_y) < 1e-5
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        w = jax.tree_util.tree_flatten_with_path(want)[0]
        w = dict((jax.tree_util.keystr(k), v) for k, v in w)[
            jax.tree_util.keystr(path)]
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)


def _per_head_ops(jaxpr, heads, out=None):
    """``(primitive, shape)`` of every op of ``jaxpr`` (bodies of calls,
    checkpoints and loops included) outside the spies' scopes that
    reads or makes a per-head array ``[B, S, H, d]`` with ``H`` in
    ``heads``."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if "spy_" in str(eqn.source_info.name_stack):
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _per_head_ops(sub, heads, out)
        out.extend((eqn.primitive.name, tuple(v.aval.shape))
                   for v in (*eqn.invars, *eqn.outvars)
                   if getattr(v.aval, "ndim", 0) == 4
                   and v.aval.shape[:2] == (2, SEQ)
                   and v.aval.shape[2] in heads)
    return out


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_no_per_head_array_between_the_projections(geometry, monkeypatch):
    """The flat layout at the jaxpr: with the recurrence and the gated
    norm replaced by spies, no op of one group's forward or gradient
    (the convolution, the norms, the repeat to the value heads, the
    gates) reads or makes a per-head array ``[B, S, H, d]`` but the
    reshapes that hand the spies views of flat arrays and flatten what
    they return. The spies pin what the controls of the benchmark
    plant faults through: q, k, v ``[B, S, H, d]`` at the value heads,
    o and z ``[B, S, H, dv]``, ``g`` and ``beta`` ``[B, S, H]``."""
    cfg = _cfg(geometry)
    args = _group_inputs(cfg)
    hv = cfg.gdn_value_heads // cfg.gdn_groups
    hk = cfg.gdn_key_heads // cfg.gdn_groups
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    seen = []

    def recurrence(q, k, v, g, beta, *, chunk):
        with jax.named_scope("spy_recurrence"):
            seen.append("recurrence")
            assert q.shape == k.shape == (2, SEQ, hv, dk)
            assert v.shape == (2, SEQ, hv, dv)
            assert g.shape == beta.shape == (2, SEQ, hv)
            return (v * jnp.sum(q * k, -1, keepdims=True)
                    * (beta * jnp.exp(g))[..., None]).astype(v.dtype)

    def gated_norm(o, z, gate_w, eps):
        with jax.named_scope("spy_gated_norm"):
            seen.append("gated_norm")
            assert o.shape == z.shape == (2, SEQ, hv, dv)
            return o * jax.nn.silu(z) * gate_w

    monkeypatch.setattr(gdn, "gated_norm", gated_norm)

    def loss(*arrays):
        return gdn._flat_group(*arrays, cfg, recurrence).sum()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(7))))(*args)
    assert sorted(set(seen)) == ["gated_norm", "recurrence"]
    ops = _per_head_ops(jaxpr.jaxpr, {hk, hv} - {1})
    assert ops, "the views handed to the spies are per-head arrays"
    assert {prim for prim, _ in ops} == {"reshape"}, ops


def test_the_layout_follows_the_head_widths(monkeypatch):
    """A group whose head widths are whole lanes takes the flat layout,
    any other the per-head one; the gauge ``hvdtpu_gdn_layout`` says
    which, set when the group is traced."""
    from horovod_tpu.observability import registry
    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    monkeypatch.setattr(registry, "_enabled", True)
    assert gdn.side_by_side(128, 256) and not gdn.side_by_side(96, 192)
    for geometry, flat in (("rep2-whole-lanes", 1),
                           ("rep1-not-whole-lanes", 0)):
        cfg = _cfg(geometry)
        args = _group_inputs(cfg)
        jax.eval_shape(lambda *a: gdn.gdn_group(*a, cfg, qn._recurrence),
                       *args)
        values = registry.snapshot(prefix="hvdtpu_gdn_layout")[
            "hvdtpu_gdn_layout"]["values"]
        assert values == {'layout="flat"': flat,
                          'layout="per_head"': 1 - flat}, geometry
