"""The Gated DeltaNet mixer (Yang et al., arXiv:2412.06464) between a
layer's input and its residual add: the ONE implementation the models
that have such layers share (``models/qwen3_next.py``,
``models/olmo_hybrid.py``). What differs between them is in the
configuration's fields, not in code:

- ``gdn_rep`` value heads a key head (2 there, 1 here);
- ``gdn_beta_scale``, the write strength's range: ``beta = scale x
  sigmoid(b)``, 1 for a strength in (0, 1), 2 where the transition may
  have negative eigenvalues (Grazzi et al., arXiv:2411.12537);
- where the layer's norm sits and what it is: the caller's business,
  the mixer here starts at its first projection and ends at its last.

Per key head one projection (``in_proj``) gives ``q``, ``k`` (width
``gdn_key_dim``) and, for each of its value heads, ``v`` and the output
gate ``z`` (width ``gdn_value_dim``); a second (``in_ba``) gives a write
strength ``b`` and a decay input ``a`` per value head. ``q | k | v`` go
through a causal depthwise convolution (``ops/ssd_scan.py``'s, no bias)
and SiLU; ``q`` and ``k`` are L2-normalised per head, ``q`` scaled by
``gdn_key_dim ** -1/2``; the delta-rule recurrence runs per value head
on a ``[dk, dv]`` state under ``g = -exp(A_log) softplus(a + dt_bias)``
(float32); then an RMSNorm per head gated by ``silu(z)``, and the output
projection.

The recurrence is an ARGUMENT (``recurrence(q, k, v, g, beta, chunk=)``,
``q`` and ``k`` at the value heads): each model hands over a function
that looks ``delta_rule`` up in its OWN module when the layer is traced,
so that a control can plant a fault in one model's recurrence by
replacing that module's attribute (``benchmark/controls_*.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.ssd_scan import causal_conv1d

# Checkpoint name of the groups' output of a mixer, which the layer's
# checkpoint HOLDS (``remat_block(names=)``): one more run of every
# group's convolution, recurrence and gated norm otherwise
# (models/nemotron_h.py::_mamba_layer has the reasoning).
HELD_NAME = "hvd_gdn_y"


class GdnFields:
    """What the mixer reads of a configuration, beside its fields
    ``d_model``, ``eps``, ``dtype``, ``gdn_key_heads``,
    ``gdn_value_heads``, ``gdn_key_dim``, ``gdn_value_dim``,
    ``conv_kernel``, ``chunk`` and ``gdn_groups`` (the key heads are
    computed in this many groups, one after another, each under its own
    checkpoint; the arithmetic is the same for any)."""

    # ``beta = gdn_beta_scale x sigmoid(b)``
    gdn_beta_scale = 1.0

    @property
    def gdn_rep(self) -> int:
        """Value heads a key head serves."""
        return self.gdn_value_heads // self.gdn_key_heads

    @property
    def gdn_conv_width(self) -> int:
        """Channels of ``q | k | v`` of one key head."""
        return 2 * self.gdn_key_dim + self.gdn_rep * self.gdn_value_dim

    @property
    def gdn_head_width(self) -> int:
        """Columns of the input projection of one key head: ``q | k |
        v | z`` (``v`` and ``z`` of its value heads side by side)."""
        return self.gdn_conv_width + self.gdn_rep * self.gdn_value_dim

    def check_gdn(self):
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                f"gdn_value_heads ({self.gdn_value_heads}) must be a "
                f"multiple of gdn_key_heads ({self.gdn_key_heads})")
        if self.gdn_key_heads % self.gdn_groups:
            raise ValueError(
                f"gdn_key_heads ({self.gdn_key_heads}) must divide into "
                f"gdn_groups ({self.gdn_groups})")


def dense(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def init_gdn(cfg, key):
    """A mixer's parameters, the program's own seeded initialisation."""
    d, hk, hv = cfg.d_model, cfg.gdn_key_heads, cfg.gdn_value_heads
    k = jax.random.split(key, 6)
    # The sources' configurations have no key for the time step: the
    # scheme is the program's own (``assumed.dt_bias`` of the benchmark's
    # configuration files), Mamba-2's: dt log-uniform in [1e-3, 1e-1],
    # the bias its inverse softplus.
    dt_min, dt_max = 1e-3, 1e-1
    dt = jnp.exp(jax.random.uniform(k[3], (hv,), jnp.float32)
                 * (math.log(dt_max) - math.log(dt_min))
                 + math.log(dt_min))
    return {
        # columns by key head: q | k | v | z of that head
        "in_proj": dense(k[0], (d, hk * cfg.gdn_head_width), d),
        # columns: b of every value head, then a of every value head
        "in_ba": dense(k[1], (d, 2 * hv), d),
        # rows by key head: the channels q | k | v of that head
        "conv_w": dense(k[2], (hk * cfg.gdn_conv_width, cfg.conv_kernel),
                        cfg.conv_kernel),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[4], (hv,), jnp.float32,
                                            1e-3, 16.0)),
        "gate_norm": jnp.ones((cfg.gdn_value_dim,), jnp.float32),
        "out_proj": dense(k[5], (hv * cfg.gdn_value_dim, d),
                          hv * cfg.gdn_value_dim),
    }


def rmsnorm32(x, scale, eps):
    """``x / rms(x) * scale`` over the last axis, float32 out."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) \
        * scale


def gated_norm(o, z, gate_w, eps):
    """A head's output normed and under its gate: ``RMS(o; gate_w)
    silu(z)``, float32."""
    return rmsnorm32(o, gate_w, eps) * jax.nn.silu(z.astype(jnp.float32))


def _l2norm(x, eps):
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)


def gdn_group(x, b, a, conv_w, dt_bias, a_log, gate_w, cfg, recurrence):
    """One group of key heads between the mixer's projections: ``x``
    ``[B, S, heads x gdn_head_width]`` (``in_proj``'s output for the
    group), ``b``, ``a`` ``[B, S, value heads]``, and the group's slices
    of the layer's parameters. Returns the gated, normalised output
    ``[B, S, value heads x gdn_value_dim]``."""
    bsz, s, _ = x.shape
    dk, dv, rep = cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_rep
    x = x.reshape(bsz, s, -1, cfg.gdn_head_width)
    hk = x.shape[2]
    z = x[..., cfg.gdn_conv_width:].reshape(bsz, s, hk * rep, dv)
    with jax.named_scope("hvd_gdn_conv"):
        qkv = x[..., :cfg.gdn_conv_width].reshape(bsz, s, -1)
        qkv = jax.nn.silu(causal_conv1d(
            qkv, conv_w, jnp.zeros((conv_w.shape[0],), conv_w.dtype)))
        qkv = qkv.reshape(bsz, s, hk, cfg.gdn_conv_width)
    q = _l2norm(qkv[..., :dk], cfg.eps) * dk ** -0.5
    k = _l2norm(qkv[..., dk:2 * dk], cfg.eps)
    q, k = (t.astype(x.dtype) if rep == 1
            else jnp.repeat(t.astype(x.dtype), rep, axis=2) for t in (q, k))
    v = qkv[..., 2 * dk:].reshape(bsz, s, hk * rep, dv)
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    if cfg.gdn_beta_scale != 1:
        beta = cfg.gdn_beta_scale * beta
    g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
    o = recurrence(q, k, v, g, beta, chunk=cfg.chunk)
    y = gated_norm(o, z, gate_w, cfg.eps)
    return y.astype(x.dtype).reshape(bsz, s, hk * rep * dv)


def gdn_mixer(params, u, cfg, recurrence):
    """The mixer of ``u`` ``[B, S, d_model]`` (the layer's input as the
    model norms it, or not), up to and with the output projection. Call
    it under the scope ``hvd_gdn``.

    The key heads share nothing between the two projections, so
    ``in_proj`` writes its output group by group and the groups are
    computed one after another (``lax.map``), each under its own
    ``jax.checkpoint``: the backward then holds ONE group's chunk
    matrices, chunk states and float32 norm
    (``nemotron_h._mamba_layer`` is the precedent, and says why the
    map's output carries a name the layer's checkpoint holds)."""
    dt_, d, g = cfg.dtype, cfg.d_model, cfg.gdn_groups
    hv = cfg.gdn_value_heads
    bsz, s, _ = u.shape
    xg = jnp.einsum("bsd,dgw->gbsw", u,
                    params["in_proj"].astype(dt_).reshape(d, g, -1))
    ba = u @ params["in_ba"].astype(dt_)

    def by_group(t):
        """``[B, S, value heads]`` -> ``[G, B, S, value heads / G]``"""
        return jnp.moveaxis(t.reshape(bsz, s, g, -1), 2, 0)

    group = jax.checkpoint(lambda args: gdn_group(
        *args, params["gate_norm"], cfg, recurrence))
    y = checkpoint_name(lax.map(group, (
        xg, by_group(ba[..., :hv]), by_group(ba[..., hv:]),
        params["conv_w"].reshape(g, -1, cfg.conv_kernel),
        params["dt_bias"].reshape(g, -1),
        params["A_log"].reshape(g, -1))), HELD_NAME)
    return jnp.einsum("gbsw,gwd->bsd", y,
                      params["out_proj"].astype(dt_).reshape(g, -1, d))
