"""Fused batch-norm(+residual+ReLU) with Pallas TPU kernels.

This is the measured test of docs/benchmarks.md's round-3 hypothesis
that a fused BN-backward kernel would lift ResNet-50 training toward a
~3000 img/s v5e ceiling. The verdict (round 4, one v5e,
[256,56,56,256] bf16, all in-process A/B;
experiments/pallas_shape_probe.py survives): the hypothesis is FALSE.
Once that box's fixed cost per host call is amortized out (k=100
chained steps), XLA's own BN fusion already runs at the arithmetic
minimum pass count (fwd ~2.8 passes vs optimum 3, bwd ~5.7 vs optimum
5 at the ~570 GB/s effective HBM rate measured there), while
Mosaic/Pallas streamed HBM at only ~310 GB/s on this chip
generation — so these kernels lose to XLA at equal pass counts,
and in the full model (where XLA fuses across op boundaries the custom
VJP makes opaque) the flax path wins outright: 2312 img/s flax vs 1586
hand-structured jnp VJP vs 1002 Pallas. The kernels and the custom-VJP
structure are kept as selectable impls and as the regression record of
that measurement; models default to the flax path.

The pass structure (the arithmetic minimum, with the bf16->fp32 cast
done in-register):

  forward:  stats kernel   reads x          -> channel sums(x, x^2)
            norm kernel    reads x, writes y = relu(x_hat*gamma+beta [+r])
  backward: reduce kernel  reads x, da      -> s1 = sum(dy),
                                               s2 = sum(dy * x_hat)
            dx kernel      reads x, da, writes dx (+ dr = dy)

where dy = da * relu_mask and the relu mask is RECOMPUTED in-register
from x (mask = pre-relu z > 0, z = x_hat*gamma+beta [+ r]) — the relu
backward costs zero extra HBM traffic, where the unfused graph reads a
saved mask or the forward output.

The backward closed form (per channel, m = reduction size):
  dx = (gamma * rstd) * (dy - s1/m - x_hat * s2/m);  dgamma = s2;
  dbeta = s1;  and for the residual variant dr = dy.

No reference counterpart: the reference ships no model/kernel code (its
ResNet comes from Keras applications, examples/tensorflow_synthetic_
benchmark.py:24-42); this is the TPU-native hot-op under the benchmark
the reference's docs/benchmarks.md headlines. Statistics follow flax
(`flax.linen.normalization._compute_stats`): fp32 mean of x and of x^2,
biased variance, so the module below is checkpoint-compatible with
`nn.BatchNorm`.

Channels: lanes want multiples of 128, so C < 128 folds row-pairs into
lanes ([M, C] -> [M/k, k*C], k = 128//C) — per-channel sums then fold
back with a [k, C] reshape-sum, and the per-channel vectors are tiled k
times. C not dividing 128 (or an M with no power-of-two factor >= 8)
falls back to a jnp implementation of the SAME 2+3-pass structure via
the same custom VJP, so CPU/odd shapes share one numerical definition.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MAX_BM = 1024
# Per-block byte budget (bf16 elements): the widest kernel holds ~5
# blocks (x, da, r, dx, dr) double-buffered plus fp32 temporaries in
# 16 MB of scoped VMEM; 256 KB bf16 blocks keep the worst case < 6 MB
# (measured: 1024x1024 blocks OOM'd scoped vmem at 17.8 MB on v5e).
_BLOCK_ELEMS = 128 * 1024


def _pow2_div(n: int, cap: int = _MAX_BM) -> int:
    d = n & (-n)  # largest power-of-two divisor
    return min(d, cap)


def _block_rows(m2: int, c2: int) -> int:
    cap = max(8, _BLOCK_ELEMS // c2)
    # Floor the cap to a power of two: _pow2_div returns a power-of-two
    # divisor of m2, and min() against a non-power-of-two cap (e.g.
    # C=384 -> cap 341) would yield a block that does not divide m2 —
    # a truncated grid that silently skips the trailing rows.
    cap = 1 << (cap.bit_length() - 1)
    return _pow2_div(m2, cap)


def _fold(c: int) -> int:
    return 128 // c if (c < 128 and 128 % c == 0) else 1


def _can_pallas(m: int, c: int) -> bool:
    k = _fold(c)
    c2 = c * k
    return c2 % 128 == 0 and m % k == 0 and _pow2_div(m // k) >= 8


# ------------------------------------------------------------------ kernels


def _stats_kernel(x_ref, s1_ref, s2_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    xf = x_ref[:].astype(jnp.float32)
    s1_ref[:] += jnp.sum(xf, axis=0, keepdims=True)
    s2_ref[:] += jnp.sum(xf * xf, axis=0, keepdims=True)


def _norm_kernel(x_ref, r_ref, sc_ref, sh_ref, y_ref, *, relu, residual):
    z = x_ref[:].astype(jnp.float32) * sc_ref[:] + sh_ref[:]
    if residual:
        z = z + r_ref[:].astype(jnp.float32)
    if relu:
        z = jnp.maximum(z, 0.0)
    y_ref[:] = z.astype(y_ref.dtype)


def _bwd_reduce_kernel(x_ref, da_ref, r_ref, mu_ref, rs_ref, sc_ref,
                       sh_ref, s1_ref, s2_ref, *, relu, residual):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    xf = x_ref[:].astype(jnp.float32)
    daf = da_ref[:].astype(jnp.float32)
    xhat = (xf - mu_ref[:]) * rs_ref[:]
    if relu:
        z = xf * sc_ref[:] + sh_ref[:]
        if residual:
            z = z + r_ref[:].astype(jnp.float32)
        daf = jnp.where(z > 0, daf, 0.0)
    s1_ref[:] += jnp.sum(daf, axis=0, keepdims=True)
    s2_ref[:] += jnp.sum(daf * xhat, axis=0, keepdims=True)


def _bwd_dx_kernel(x_ref, da_ref, r_ref, mu_ref, rs_ref, sc_ref, sh_ref,
                   g1_ref, g2_ref, dx_ref, dr_ref, *, relu, residual,
                   inv_m):
    xf = x_ref[:].astype(jnp.float32)
    daf = da_ref[:].astype(jnp.float32)
    xhat = (xf - mu_ref[:]) * rs_ref[:]
    if relu:
        z = xf * sc_ref[:] + sh_ref[:]
        if residual:
            z = z + r_ref[:].astype(jnp.float32)
        daf = jnp.where(z > 0, daf, 0.0)
    if residual:
        dr_ref[:] = daf.astype(dr_ref.dtype)
    dx = sc_ref[:] * (daf - g1_ref[:] * inv_m - xhat * (g2_ref[:] * inv_m))
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _vec(v, k):
    """Per-channel fp32 row vector [1, k*C] for lane broadcast."""
    v = jnp.asarray(v, jnp.float32)
    if k > 1:
        v = jnp.tile(v, k)
    return v[None, :]


def _row_spec(bm, c2):
    return pl.BlockSpec((bm, c2), lambda i: (i, 0))


def _vec_spec(c2):
    return pl.BlockSpec((1, c2), lambda i: (0, 0))


def _stats_pallas(x2, interpret):
    m2, c2 = x2.shape
    bm = _block_rows(m2, c2)
    s1, s2 = pl.pallas_call(
        _stats_kernel,
        grid=(m2 // bm,),
        in_specs=[_row_spec(bm, c2)],
        out_specs=[_vec_spec(c2), _vec_spec(c2)],
        out_shape=[jax.ShapeDtypeStruct((1, c2), jnp.float32)] * 2,
        interpret=interpret,
    )(x2)
    return s1[0], s2[0]


def _norm_pallas(x2, r2, scale, shift, relu, out_dtype, interpret):
    m2, c2 = x2.shape
    bm = _block_rows(m2, c2)
    residual = r2 is not None
    kernel = functools.partial(_norm_kernel, relu=relu, residual=residual)
    return pl.pallas_call(
        kernel,
        grid=(m2 // bm,),
        in_specs=[_row_spec(bm, c2),
                  _row_spec(bm, c2) if residual else _vec_spec(c2),
                  _vec_spec(c2), _vec_spec(c2)],
        out_specs=_row_spec(bm, c2),
        out_shape=jax.ShapeDtypeStruct((m2, c2), out_dtype),
        interpret=interpret,
    )(x2, r2 if residual else scale, scale, shift)


def _bwd_reduce_pallas(x2, da2, r2, mean, rstd, scale, shift, relu,
                       interpret):
    m2, c2 = x2.shape
    bm = _block_rows(m2, c2)
    residual = r2 is not None
    rfill = r2 if residual else mean  # unused slot when no residual
    red = functools.partial(_bwd_reduce_kernel, relu=relu,
                            residual=residual)
    s1, s2 = pl.pallas_call(
        red,
        grid=(m2 // bm,),
        in_specs=[_row_spec(bm, c2), _row_spec(bm, c2),
                  _row_spec(bm, c2) if residual else _vec_spec(c2),
                  _vec_spec(c2), _vec_spec(c2), _vec_spec(c2),
                  _vec_spec(c2)],
        out_specs=[_vec_spec(c2), _vec_spec(c2)],
        out_shape=[jax.ShapeDtypeStruct((1, c2), jnp.float32)] * 2,
        interpret=interpret,
    )(x2, da2, rfill, mean, rstd, scale, shift)
    return s1[0], s2[0]


def _bwd_dx_pallas(x2, da2, r2, mean, rstd, scale, shift, g1, g2, inv_m,
                   relu, interpret):
    m2, c2 = x2.shape
    bm = _block_rows(m2, c2)
    residual = r2 is not None
    rfill = r2 if residual else mean
    dxk = functools.partial(_bwd_dx_kernel, relu=relu, residual=residual,
                            inv_m=inv_m)
    out_specs = [_row_spec(bm, c2)]
    out_shape = [jax.ShapeDtypeStruct((m2, c2), x2.dtype)]
    if residual:
        out_specs.append(_row_spec(bm, c2))
        out_shape.append(jax.ShapeDtypeStruct((m2, c2), r2.dtype))
    else:
        out_specs.append(_vec_spec(c2))
        out_shape.append(jax.ShapeDtypeStruct((1, c2), jnp.float32))
    outs = pl.pallas_call(
        dxk,
        grid=(m2 // bm,),
        in_specs=[_row_spec(bm, c2), _row_spec(bm, c2),
                  _row_spec(bm, c2) if residual else _vec_spec(c2),
                  _vec_spec(c2), _vec_spec(c2), _vec_spec(c2),
                  _vec_spec(c2), _vec_spec(c2), _vec_spec(c2)],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x2, da2, rfill, mean, rstd, scale, shift, g1, g2)
    dx2 = outs[0]
    dr2 = outs[1] if residual else None
    return dx2, dr2


# ---------------------------------------------------------------- jnp path


def _jnp_stats(x2):
    xf = x2.astype(jnp.float32)
    return (jnp.sum(xf, axis=0), jnp.sum(jnp.square(xf), axis=0))


def _jnp_norm(x2, r2, scale, shift, relu, out_dtype):
    z = x2.astype(jnp.float32) * scale + shift
    if r2 is not None:
        z = z + r2.astype(jnp.float32)
    if relu:
        z = jnp.maximum(z, 0.0)
    return z.astype(out_dtype)


def _jnp_bwd_reduce(x2, da2, r2, mean, rstd, scale, shift, relu):
    xf = x2.astype(jnp.float32)
    daf = da2.astype(jnp.float32)
    xhat = (xf - mean) * rstd
    if relu:
        z = xf * scale + shift
        if r2 is not None:
            z = z + r2.astype(jnp.float32)
        daf = jnp.where(z > 0, daf, 0.0)
    return jnp.sum(daf, axis=0), jnp.sum(daf * xhat, axis=0)


def _jnp_bwd_dx(x2, da2, r2, mean, rstd, scale, shift, g1, g2, inv_m,
                relu):
    xf = x2.astype(jnp.float32)
    daf = da2.astype(jnp.float32)
    xhat = (xf - mean) * rstd
    if relu:
        z = xf * scale + shift
        if r2 is not None:
            z = z + r2.astype(jnp.float32)
        daf = jnp.where(z > 0, daf, 0.0)
    dx = scale * (daf - g1 * inv_m - xhat * (g2 * inv_m))
    dr2 = daf.astype(r2.dtype) if r2 is not None else None
    return dx.astype(x2.dtype), dr2


# ------------------------------------------------------------- public vjp


def _use_pallas(m: int, c: int, impl: str) -> Tuple[bool, bool]:
    """Resolve ``impl`` to (use pallas kernels?, interpreter flag).

    'jnp'       — the same 2+3-pass structure in plain jnp, fused by XLA.
    'pallas'    — compiled Pallas kernels (falls back to jnp when the
                  layout can't fold to 128 lanes).
    'interpret' — Pallas through the interpreter (CPU tests).
    'auto'      — 'jnp' everywhere: measured on v5e, XLA compiles each
                  jnp pass at ~570 GB/s effective while Mosaic streams
                  at ~310 GB/s, so the pass-optimal structure is fastest
                  when XLA does the streaming (experiments/
                  pallas_shape_probe.py; docs/benchmarks.md).
    """
    if not _can_pallas(m, c):
        return False, False
    if impl == "pallas":
        return True, False
    if impl == "interpret":
        return True, True
    return False, False


def _prep(x, r, gamma, beta):
    c = x.shape[-1]
    m = x.size // c
    k = _fold(c)
    x2 = x.reshape(m // k, k * c) if k > 1 else x.reshape(m, c)
    r2 = None
    if r is not None:
        r2 = r.reshape(x2.shape)
    return x2, r2, m, c, k


def _bn_act_fwd(x, r, gamma, beta, eps, relu, has_residual, impl):
    r_in = r if has_residual else None
    x2, r2, m, c, k = _prep(x, r_in, gamma, beta)
    pallas, interp = _use_pallas(m, c, impl)
    if pallas:
        s1, s2 = _stats_pallas(x2, interp)
    else:
        s1, s2 = _jnp_stats(x2)
    if k > 1:
        s1 = s1.reshape(k, c).sum(0)
        s2 = s2.reshape(k, c).sum(0)
    mean = s1 / m
    var = s2 / m - jnp.square(mean)
    rstd = jax.lax.rsqrt(var + eps)
    gf = jnp.asarray(gamma, jnp.float32)
    bf = jnp.asarray(beta, jnp.float32)
    scale = gf * rstd
    shift = bf - mean * scale
    scale_v, shift_v = _vec(scale, k), _vec(shift, k)
    if pallas:
        y2 = _norm_pallas(x2, r2, scale_v, shift_v, relu, x.dtype, interp)
    else:
        y2 = _jnp_norm(x2, r2, scale_v, shift_v, relu, x.dtype)
    y = y2.reshape(x.shape)
    return (y, mean, var), (x, r_in, mean, rstd, gf, bf)


def _bn_act_bwd(eps, relu, has_residual, impl, res, ct):
    day, _, _ = ct  # cotangents of (y, mean, var); stats feed only the
    #                 stop-gradient running-average update, so their
    #                 cotangents are structurally zero (flax BatchNorm
    #                 has the same property).
    x, r_in, mean, rstd, gf, bf = res
    x2, r2, m, c, k = _prep(x, r_in, gf, bf)
    da2 = day.reshape(x2.shape)
    pallas, interp = _use_pallas(m, c, impl)
    scale = gf * rstd
    shift = bf - mean * scale
    mean_v, rstd_v = _vec(mean, k), _vec(rstd, k)
    scale_v, shift_v = _vec(scale, k), _vec(shift, k)
    if pallas:
        s1, s2 = _bwd_reduce_pallas(x2, da2, r2, mean_v, rstd_v,
                                    scale_v, shift_v, relu, interp)
    else:
        s1, s2 = _jnp_bwd_reduce(x2, da2, r2, mean_v, rstd_v,
                                 scale_v, shift_v, relu)
    if k > 1:
        # Combine the per-lane partial sums of each real channel BEFORE
        # the dx pass: in the folded layout lane c and lane c + j*C each
        # hold 1/k of channel c's sum, but dx needs the full channel sum
        # over the true reduction size m.
        s1 = s1.reshape(k, c).sum(0)
        s2 = s2.reshape(k, c).sum(0)
    inv_m = 1.0 / float(m)
    g1_v, g2_v = _vec(s1, k), _vec(s2, k)
    if pallas:
        dx2, dr2 = _bwd_dx_pallas(x2, da2, r2, mean_v, rstd_v, scale_v,
                                  shift_v, g1_v, g2_v, inv_m, relu,
                                  interp)
    else:
        dx2, dr2 = _jnp_bwd_dx(x2, da2, r2, mean_v, rstd_v, scale_v,
                               shift_v, g1_v, g2_v, inv_m, relu)
    dx = dx2.reshape(x.shape)
    dr = dr2.reshape(x.shape) if dr2 is not None else None
    dgamma = s2.astype(jnp.float32)
    dbeta = s1.astype(jnp.float32)
    if not has_residual:
        dr = jnp.zeros((), x.dtype)  # placeholder cotangent, unused
    return dx, dr, dgamma, dbeta


# custom_vjp functions must return the primal output only; re-define the
# primal to return the full (y, mean, var) triple.
def _bn_act_primal(x, r, gamma, beta, eps, relu, has_residual, impl):
    out, _ = _bn_act_fwd(x, r, gamma, beta, eps, relu, has_residual,
                         impl)
    return out


_bn_act_core = jax.custom_vjp(_bn_act_primal, nondiff_argnums=(4, 5, 6, 7))
_bn_act_core.defvjp(_bn_act_fwd, _bn_act_bwd)


def bn_act(x, gamma, beta, *, residual=None, eps: float = 1e-5,
           relu: bool = True, impl: str = "auto"):
    """Train-mode fused batch-norm(+residual)(+ReLU).

    Returns ``(y, batch_mean, batch_var)``; the stats are fp32 biased
    moments for the caller's running-average update (use them under
    stop_gradient — their cotangents are treated as zero). ``residual``
    is added AFTER normalization, before the ReLU (the ResNet v1.5
    bottleneck join). Gradients: x, residual, gamma, beta.

    ``impl``: 'auto' (jnp passes, XLA-fused — fastest measured),
    'jnp', 'pallas' (compiled kernels), 'interpret' (Pallas interpreter,
    CPU tests). See _use_pallas for the measured rationale.
    """
    if impl not in ("auto", "jnp", "pallas", "interpret"):
        # A typo'd impl silently measuring the wrong implementation is
        # worse than an error — this repo's benchmark verdicts hang on
        # knowing which path actually ran.
        raise ValueError(f"unknown bn_act impl {impl!r}; expected "
                         "'auto', 'jnp', 'pallas' or 'interpret'")
    has_residual = residual is not None
    r = residual if has_residual else jnp.zeros((), x.dtype)
    return _bn_act_core(x, r, gamma, beta, float(eps), bool(relu),
                        has_residual, str(impl))


def bn_act_inference(x, gamma, beta, running_mean, running_var, *,
                     residual=None, eps: float = 1e-5, relu: bool = True):
    """Eval-mode normalize with running stats — plain jnp (a single
    elementwise chain XLA fuses on its own; no reduction pass exists)."""
    rstd = jax.lax.rsqrt(running_var.astype(jnp.float32) + eps)
    scale = gamma.astype(jnp.float32) * rstd
    shift = beta.astype(jnp.float32) - running_mean.astype(jnp.float32) * scale
    z = x.astype(jnp.float32) * scale + shift
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    if relu:
        z = jnp.maximum(z, 0.0)
    return z.astype(x.dtype)
