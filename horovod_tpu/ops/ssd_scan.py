"""Mamba-2's state-space recurrence, computed in chunks (the SSD form),
and the causal depthwise convolution that precedes it.

Per head (``x_t`` in R^P, ``B_t``/``C_t`` in R^N shared by the heads of a
group, ``a_t = dt_t * A <= 0``):

    S_t = exp(a_t) S_{t-1} + dt_t * x_t (x) B_t        S_0 = 0
    y_t = S_t C_t + D x_t

Unrolled, ``y_i = sum_{j<=i} exp(a_{j+1} + ... + a_i) dt_j (C_i . B_j)
x_j``: inside a chunk of ``Q`` steps that is a masked ``[Q, Q]`` matrix
(``C B^T`` weighted by the decays ``exp(cumsum(a)_i - cumsum(a)_j)``)
times ``x`` — matmuls; across chunks a short scan carries the state
``[P, N]`` a chunk ends with. The backward is JAX's own derivative of
this chunked form.

Written in XLA ops, no Pallas kernel: the decays and their cumulative
sums are float32 whatever the activations' type, every matmul takes the
activations' type in and accumulates in float32. A Pallas scan waits
for nothing: since PR 35 a Mosaic call inside ``hvd_ssd_scan`` is read
by the scope's metrics as these ops are (``ops/delta_rule.py``, PR 38).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# The type of ``dt * A``, its cumulative sums and their exponentials,
# whatever the activations' type: in bfloat16 a cumulative sum near 100
# resolves steps of 0.5, a factor of 1.6 in the decay.
DECAY_DTYPE = jnp.float32


def _conv_taps(xp, w, s: int, flip: bool):
    """``sum_j xp[:, j':j'+s] * w[:, j]`` in float32, ``j' = j`` or,
    flipped, ``K - 1 - j``."""
    k = w.shape[1]
    return sum(xp[:, (k - 1 - j if flip else j):][:, :s]
               * w[:, j].astype(jnp.float32) for j in range(k))


@jax.custom_vjp
def causal_conv1d(x, w, b):
    """Causal depthwise convolution over time: ``x`` ``[B, S, C]``,
    ``w`` ``[C, K]``, ``b`` ``[C]``; ``y_t = b + sum_j w[:, j] *
    x_{t-(K-1)+j}`` (the last tap meets the current step), zeros before
    the sequence. Float32 inside, ``x``'s type out.

    Its backward is written out (the same taps run the other way over
    the padded cotangent): JAX's transpose of the four shifted slices
    pads and holds four float32 copies of ``[B, S, C]``."""
    k = w.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    y = _conv_taps(xp, w, x.shape[1], False) + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _conv_fwd(x, w, b):
    return causal_conv1d(x, w, b), (x, w, b)


def _conv_bwd(res, dy):
    x, w, b = res
    k, s = w.shape[1], x.shape[1]
    dy = dy.astype(jnp.float32)
    dyp = jnp.pad(dy, ((0, 0), (0, k - 1), (0, 0)))
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    dw = jnp.stack([(dy * xp[:, j:j + s]).sum((0, 1)) for j in range(k)],
                   axis=1)
    return (_conv_taps(dyp, w, s, True).astype(x.dtype),
            dw.astype(w.dtype), dy.sum((0, 1)).astype(b.dtype))


causal_conv1d.defvjp(_conv_fwd, _conv_bwd)


def ssd_scan(x, dt, a, b, c, d, *, chunk: int):
    """The recurrence above for the heads of ONE group, which share
    ``B`` and ``C`` (a model with several groups calls it once a group:
    they share nothing).

    ``x`` ``[B, S, H, P]``; ``dt`` ``[B, S, H]`` float32, after its
    softplus; ``a`` ``[H]`` float32, negative; ``b``, ``c``
    ``[B, S, N]``; ``d`` ``[H]``. Returns ``y`` ``[B, S, H, P]`` in
    ``x``'s type.

    A sequence that is no multiple of ``chunk`` is padded with steps of
    ``dt = 0``: they decay nothing and add nothing, and their outputs
    are cut off."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                               * (t.ndim - 2)) for t in (x, dt, b, c))
    nc, q = (s + pad) // chunk, chunk
    dtype, f32 = x.dtype, jnp.float32
    a, d = a.astype(DECAY_DTYPE), d.astype(f32)

    with jax.named_scope("hvd_ssd_scan"):
        xc = x.reshape(bsz, nc, q, h, p)
        bc = b.reshape(bsz, nc, q, n)
        cc = c.reshape(bsz, nc, q, n)
        dtc = dt.astype(DECAY_DTYPE).reshape(bsz, nc, q, h)
        # cumulative log-decay inside each chunk, inclusive: [B,nc,Q,H]
        acs = jnp.cumsum(dtc * a, axis=2)

        # Inside a chunk: M[i, j] = (C_i . B_j) exp(acs_i - acs_j) dt_j
        # for j <= i. The mask goes on the exponent, so that no
        # exp(positive) is ever formed (and no inf meets a 0 backward).
        cb = jnp.einsum("bcin,bcjn->bcij", cc, bc,
                        preferred_element_type=f32)
        t_acs = acs.swapaxes(2, 3)                          # [B,nc,H,Q]
        seg = t_acs[..., :, None] - t_acs[..., None, :]     # [.., Q(i), Q(j)]
        causal = jnp.tril(jnp.ones((q, q), bool))
        m = (cb[:, :, None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))
             * dtc.swapaxes(2, 3)[..., None, :])
        y = jnp.einsum("bchij,bcjhp->bcihp", m.astype(dtype), xc,
                       preferred_element_type=f32)

        # What each chunk adds to the state by its end:
        # sum_j exp(acs_last - acs_j) dt_j x_j (x) B_j       [B,nc,H,P,N]
        to_end = jnp.exp(acs[:, :, -1:] - acs) * dtc
        xw = (xc.astype(f32) * to_end[..., None]).astype(dtype)
        added = jnp.einsum("bcjhp,bcjn->bchpn", xw, bc,
                           preferred_element_type=f32)

        # Across chunks: the state a chunk starts from.
        def carry(state, inp):
            dec, add = inp
            return state * dec[..., None, None] + add, state

        _, starts = lax.scan(
            carry, jnp.zeros((bsz, h, p, n), f32),
            (jnp.exp(acs[:, :, -1]).swapaxes(0, 1), added.swapaxes(0, 1)))
        starts = starts.swapaxes(0, 1)                      # [B,nc,H,P,N]

        # What the starting state gives each step: exp(acs_i) C_i . S
        y_off = jnp.einsum("bcin,bchpn->bcihp", cc, starts.astype(dtype),
                           preferred_element_type=f32)
        y = (y + y_off * jnp.exp(acs)[..., None]
             + xc.astype(f32) * d[:, None])
        y = y.astype(dtype).reshape(bsz, nc * q, h, p)
    return y[:, :s] if pad else y
