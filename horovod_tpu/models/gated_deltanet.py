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

Between the two projections a group's arrays keep one of two layouts,
chosen by the head widths (``gdn_group``): FLAT, ``[B, S, heads x
width]`` with the heads side by side on the TPU's lanes, where both
widths are whole lanes (``qwen3_next``: 128 / 128; a move between
``[.., H, d]`` and ``[.., H x d]`` there is a relayout copy, so none is
made); per head, ``[B, S, heads, width]``, otherwise (``olmo_hybrid``:
96 / 192, whose arrays XLA lays out with the sequence on the lanes,
where the per-head view costs nothing).

The recurrence is an ARGUMENT (``recurrence(q, k, v, g, beta, chunk=)``,
``q`` and ``k`` at the value heads): each model hands over a function
that looks ``delta_rule`` up in its OWN module when the layer is traced,
so that a control can plant a fault in one model's recurrence by
replacing that module's attribute (``benchmark/controls_*.py``).
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..observability import registry as _reg
from ..ops.ssd_scan import causal_conv1d

# Checkpoint name of the groups' output of a mixer, which the layer's
# checkpoint HOLDS (``remat_block(names=)``): one more run of every
# group's convolution, recurrence and gated norm otherwise
# (models/nemotron_h.py::_mamba_layer has the reasoning).
HELD_NAME = "hvd_gdn_y"

# The TPU's vector lanes: a tile is (8 | 16) x 128
_LANES = 128


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


def _head_sums(x32, width):
    """Sums of ``x32`` ``[..., H x width]`` (float32) over each head's
    ``width`` columns: ``[..., H]``, each head reduced where its block
    of columns lies."""
    return jnp.stack([x32[..., h * width:(h + 1) * width].sum(-1)
                      for h in range(x32.shape[-1] // width)], -1)


def _by_head(r, width):
    """``r`` ``[..., H]`` over each head's ``width`` columns: ``[...,
    H x width]``, as column blocks side by side."""
    return jnp.concatenate(
        [jnp.broadcast_to(r[..., h:h + 1], r.shape[:-1] + (width,))
         for h in range(r.shape[-1])], -1)


def _flat(t):
    """``[..., H, d]`` -> ``[..., H x d]``: the flat array a per-head
    view came from (the two reshapes cancel)."""
    return t.reshape(t.shape[:-2] + (-1,))


def side_by_side(*widths):
    """Whether heads of these widths lie side by side on the TPU's lanes
    as whole tiles (``_LANES`` columns): the flat layout, in which every
    per-head step is a block of whole lane tiles. A head of another
    width straddles tiles there; XLA then lays a group's arrays out with
    the sequence on the lanes, in which a per-head view ``[B, S, H, d]``
    of a flat array is no relayout, so the per-head layout stays."""
    return all(w % _LANES == 0 for w in widths)


def rms_over_last(x, scale, eps):
    """``rmsnorm32`` of an array whose last axis is the one to norm
    whole: the layer norms, and attention's per-head norms of
    ``qwen3_next``, which keep their own layouts."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) \
        * scale


def rmsnorm32(x, scale, eps):
    """``x / rms(x) * scale`` over the last axis, float32 out. Heads side
    by side, ``x`` ``[B, S, H, d]`` with ``d`` whole lanes (the gated
    norm's on the flat layout, ``side_by_side``), are normed on the flat
    view ``[B, S, H x d]`` (``_head_sums``), and the result is the view
    of a flat array again."""
    if x.ndim < 4 or not side_by_side(x.shape[-1]):
        return rms_over_last(x, scale, eps)
    d = x.shape[-1]
    flat = _flat(x.astype(jnp.float32))
    inv = _by_head(lax.rsqrt(_head_sums(flat * flat, d) / d + eps), d)
    scale = jnp.broadcast_to(scale, x.shape[-2:]).reshape(-1)
    return (flat * inv * scale).reshape(x.shape)


def gated_norm(o, z, gate_w, eps):
    """A head's output normed and under its gate: ``RMS(o; gate_w)
    silu(z)`` of ``o``, ``z`` ``[B, S, H, dv]``, float32 ``[B, S, H,
    dv]``. On the flat layout both come in as views of flat ``[B, S, H x
    dv]`` arrays and the product is taken flat: only reshapes that XLA
    cancels touch the per-head layout."""
    if not side_by_side(o.shape[-1]):
        return rmsnorm32(o, gate_w, eps) * jax.nn.silu(z.astype(jnp.float32))
    y = _flat(rmsnorm32(o, gate_w, eps)) * jax.nn.silu(
        _flat(z).astype(jnp.float32))
    return y.reshape(o.shape)


def _l2norm(x, eps):
    """``x`` ``[B, S, H, d]`` over each head's L2 norm, float32."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)


def _l2norm_flat(x, width, eps):
    """``_l2norm`` of the heads side by side in ``x`` ``[B, S, H x
    width]``."""
    x32 = x.astype(jnp.float32)
    return x32 * _by_head(lax.rsqrt(_head_sums(x32 * x32, width) + eps),
                          width)


def _blocks(t, widths):
    """``t`` ``[..., n x sum(widths)]``, ``n`` groups of column blocks of
    ``widths`` side by side, as one array per width: its ``n`` blocks
    side by side, ``[..., n x width]``. One ``split`` takes every block
    out, so the backward puts the blocks back with ONE concatenation
    (a slice a block would give one padded array a block, summed)."""
    n = t.shape[-1] // sum(widths)
    pieces = jnp.split(t, list(itertools.accumulate(widths * n))[:-1], -1)
    return [jnp.concatenate(pieces[i::len(widths)], -1) if n > 1
            else pieces[i] for i in range(len(widths))]


def _conv(qkv, conv_w):
    """The causal depthwise convolution and SiLU of ``q | k | v`` ``[B,
    S, channels]``."""
    return jax.nn.silu(causal_conv1d(
        qkv, conv_w, jnp.zeros((conv_w.shape[0],), conv_w.dtype)))


def _gates(b, a, dt_bias, a_log, cfg):
    """The write strength ``beta`` and the log-decay ``g``, float32 ``[B,
    S, value heads]``."""
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    if cfg.gdn_beta_scale != 1:
        beta = cfg.gdn_beta_scale * beta
    g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
    return beta, g


def _count_layout(flat):
    """Set the gauge ``hvdtpu_gdn_layout`` from the layout a group took;
    at trace time, once a traced group."""
    fam = _reg.registry().gauge(
        "hvdtpu_gdn_layout",
        "The layout the last traced Gated DeltaNet group took between "
        "its projections: 1 on the side it took, 0 on the other "
        "(flat: heads side by side, per_head: [B, S, H, d])")
    fam.labels(layout="flat").set(int(flat))
    fam.labels(layout="per_head").set(int(not flat))


def gdn_group(x, b, a, conv_w, dt_bias, a_log, gate_w, cfg, recurrence):
    """One group of key heads between the mixer's projections: ``x``
    ``[B, S, heads x gdn_head_width]`` (``in_proj``'s output for the
    group), ``b``, ``a`` ``[B, S, value heads]``, and the group's slices
    of the layer's parameters. Returns the gated, normalised output
    ``[B, S, value heads x gdn_value_dim]``.

    Where both head widths are whole lanes (``side_by_side``: 128 and
    128 in ``qwen3_next``) every array between the projections is FLAT,
    ``[B, S, heads x width]`` (sequence on sublanes, heads side by side
    on lanes): ``q``, ``k``, ``v`` and ``z`` are column blocks of ``x``
    and of the convolution's output, the per-head norms are reductions
    over column blocks (``_head_sums``), and the repeat of ``q``, ``k``
    to the value heads duplicates column blocks. Otherwise (96 and 192
    in ``olmo_hybrid``) they are per head, ``[B, S, heads, width]``.
    Either way the recurrence and the gated norm are handed ``[B, S, H,
    d]`` (``q``, ``k`` at the value heads): on the flat layout views of
    flat arrays, whose reshapes pair with their own back to flat. The
    gauge ``hvdtpu_gdn_layout`` records the side taken."""
    flat = side_by_side(cfg.gdn_key_dim, cfg.gdn_value_dim)
    _count_layout(flat)
    return (_flat_group if flat else _per_head_group)(
        x, b, a, conv_w, dt_bias, a_log, gate_w, cfg, recurrence)


def _flat_group(x, b, a, conv_w, dt_bias, a_log, gate_w, cfg, recurrence):
    """``gdn_group`` on the flat layout."""
    bsz, s, _ = x.shape
    dk, dv, rep = cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_rep
    hk = x.shape[-1] // cfg.gdn_head_width
    with jax.named_scope("hvd_gdn_conv"):
        qkv, z = _blocks(x, (cfg.gdn_conv_width, rep * dv))
        q, k, v = _blocks(_conv(qkv, conv_w), (dk, dk, rep * dv))
    q = (_l2norm_flat(q, dk, cfg.eps) * dk ** -0.5).astype(x.dtype)
    k = _l2norm_flat(k, dk, cfg.eps).astype(x.dtype)
    if rep > 1:
        q, k = (jnp.concatenate([c for c in jnp.split(t, hk, -1)
                                 for _ in range(rep)], -1) for t in (q, k))
    beta, g = _gates(b, a, dt_bias, a_log, cfg)

    def heads(t, width):
        return t.reshape(bsz, s, hk * rep, width)
    o = recurrence(heads(q, dk), heads(k, dk), heads(v, dv), g, beta,
                   chunk=cfg.chunk)
    return _flat(gated_norm(o, heads(z, dv), gate_w, cfg.eps)
                 .astype(x.dtype))


def _per_head_group(x, b, a, conv_w, dt_bias, a_log, gate_w, cfg,
                    recurrence):
    """``gdn_group`` on the per-head layout."""
    bsz, s, _ = x.shape
    dk, dv, rep = cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_rep
    x = x.reshape(bsz, s, -1, cfg.gdn_head_width)
    hk = x.shape[2]
    z = x[..., cfg.gdn_conv_width:].reshape(bsz, s, hk * rep, dv)
    with jax.named_scope("hvd_gdn_conv"):
        qkv = _conv(x[..., :cfg.gdn_conv_width].reshape(bsz, s, -1), conv_w)
        qkv = qkv.reshape(bsz, s, hk, cfg.gdn_conv_width)
    q = _l2norm(qkv[..., :dk], cfg.eps) * dk ** -0.5
    k = _l2norm(qkv[..., dk:2 * dk], cfg.eps)
    q, k = (t.astype(x.dtype) if rep == 1
            else jnp.repeat(t.astype(x.dtype), rep, axis=2) for t in (q, k))
    v = qkv[..., 2 * dk:].reshape(bsz, s, hk * rep, dv)
    beta, g = _gates(b, a, dt_bias, a_log, cfg)
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
    map's output carries a name the layer's checkpoint holds). What
    goes into and comes out of a group is flat, ``[B, S, heads x
    width]``, in either layout (``gdn_group``)."""
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
