"""The gated delta rule of a linear-attention layer (Gated DeltaNet),
computed in chunks.

Per head (``q_t``, ``k_t`` in R^dk, ``v_t`` in R^dv, a log-decay
``g_t <= 0`` and a write strength ``beta_t`` in (0, 1); the state ``S``
is ``[dk, dv]``, ``S_0 = 0``):

    S   <- exp(g_t) S
    u_t  = beta_t (v_t - S^T k_t)        what the state lacks for k_t
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

Unlike ``ops/ssd_scan.py``'s scalar-decay recurrence, each step first
SUBTRACTS what the state already holds for its key, so the ``u_t`` of a
chunk depend on one another. With ``G_i`` the cumulative log-decay
inside a chunk of ``C`` steps and ``S`` the state the chunk starts from,

    (I + A) U = beta * V - (beta * exp(G) * K) S,
    A[i, j]   = beta_i exp(G_i - G_j) (k_i . k_j)   for j < i, else 0

so ``U = U0 - W S`` with ``T = (I + A)^-1`` (unit lower triangular),
``W = T (beta exp(G) K)``, ``U0 = T (beta V)``: the WY / UT form. Then

    O      = (exp(G) * Q) S + (exp(G_i - G_j) (q_i . k_j))_{j <= i} U
    S_next = exp(G_C) S + (exp(G_C - G) * K)^T U.

Everything but ``U = U0 - W S`` and the state's update is matmuls over
all chunks at once; those two run in a ``lax.scan`` over the chunks
(its trip count is the sequence's length over ``chunk``). ``T`` comes
from the product ``(I - A)(I + A^2)(I + A^4)``, exact because ``A`` is
nilpotent, on diagonal blocks of 8, and block substitution above them
(``_inverse_unit_lower``), float32 at HIGHEST precision.

Written in XLA ops, as ``ops/ssd_scan.py`` is: decays, their
cumulative sums and exponentials, ``A`` and ``T`` are float32 whatever
the activations' type; every other matmul takes the activations' type
in and accumulates in float32. The backward is JAX's own derivative of
this chunked form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# The type of the log-decays, their cumulative sums and exponentials,
# of ``A`` and of ``T``, whatever the activations' type.
DECAY_DTYPE = jnp.float32


# Side of the diagonal blocks whose inverse is taken as a product of
# powers; larger blocks are put together by block substitution.
_BASE = 8


def _inverse_unit_lower(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` ``[..., C, C]``
    (``C`` a power of two), float32 at HIGHEST precision.

    Diagonal blocks of ``_BASE``: with ``x = -a_block``, ``x^8 = 0`` and
    the inverse is ``(I + x)(I + x^2)(I + x^4)``, exact and, at this
    size, well conditioned (its terms stay under ``C(7, 3) = 35`` times
    the largest entry's power). Then pairs of blocks, again and again:
    ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``, which
    is forward substitution by blocks and as stable. (The product of
    powers over the WHOLE chunk is exact too, but its terms reach
    ``C(62, 31) = 4.5e17`` times the 32nd power of an entry: once the
    keys of a chunk point the same way, as they do after a few
    optimizer steps, float32 cancels them to noise and the state
    overflows within a sequence: my chip run, PR 37.)"""
    c = a.shape[-1]
    hi = lax.Precision.HIGHEST
    lead = a.shape[:-2]

    def blocks_on_diagonal(size, below):
        """``[..., C / size (/ 2), size, size]``: the blocks on the
        diagonal or, ``below``, the block under each odd one."""
        n = c // size
        tiled = a.reshape(lead + (n, size, n, size))
        picks = [(i + 1, i) for i in range(0, n, 2)] if below \
            else [(i, i) for i in range(n)]
        return jnp.stack([tiled[..., i, :, j, :] for i, j in picks],
                         axis=-3)

    size = min(c, _BASE)
    x = -blocks_on_diagonal(size, False)
    inv = jnp.eye(size, dtype=a.dtype) + x
    power = 2
    while power < size:
        x = jnp.matmul(x, x, precision=hi)
        inv = inv + jnp.matmul(inv, x, precision=hi)
        power *= 2
    while size < c:
        upper, lower = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        under = -jnp.matmul(
            jnp.matmul(lower, blocks_on_diagonal(size, True), precision=hi),
            upper, precision=hi)
        inv = jnp.concatenate(
            [jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
             jnp.concatenate([under, lower], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """The recurrence above for every head of ``q``, ``k`` ``[B, S, H,
    dk]`` (as the state meets them: normalised and scaled by the
    caller), ``v`` ``[B, S, H, dv]``, ``g`` ``[B, S, H]`` (log-decay,
    ``<= 0``) and ``beta`` ``[B, S, H]``. Returns ``o`` ``[B, S, H,
    dv]`` in ``v``'s type.

    A sequence that is no multiple of ``chunk`` is padded with steps of
    ``g = 0``, ``beta = 0`` and zero keys: they decay nothing and write
    nothing, and their outputs are cut off."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    nc, c = (s + pad) // chunk, chunk
    dtype, f32 = v.dtype, jnp.float32

    def chunks(t):
        """``[B, S, H, ...]`` -> ``[B, H, nc, C, ...]``."""
        t = t.reshape((bsz, nc, c, h) + t.shape[3:])
        return jnp.moveaxis(t, 3, 1)

    with jax.named_scope("hvd_delta_rule"):
        qc, kc, vc = chunks(q), chunks(k), chunks(v)
        beta = chunks(beta.astype(DECAY_DTYPE))             # [B,H,nc,C]
        gc = jnp.cumsum(chunks(g.astype(DECAY_DTYPE)), axis=-1)
        # exp(G_i - G_j) for j <= i. The mask goes on the exponent, so
        # that no exp(positive) is formed (nor an inf meets a 0 backward)
        seg = gc[..., :, None] - gc[..., None, :]
        lower = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))    # [B,H,nc,C,C]

        kk = jnp.einsum("bhnid,bhnjd->bhnij", kc, kc,
                        preferred_element_type=f32)
        a = jnp.where(jnp.tril(lower, -1),
                      kk * decay * beta[..., :, None], 0.0)
        t = _inverse_unit_lower(a).astype(dtype)
        k_in = (kc.astype(f32) * (beta * jnp.exp(gc))[..., None]).astype(dtype)
        v_in = (vc.astype(f32) * beta[..., None]).astype(dtype)
        w = jnp.einsum("bhnij,bhnjd->bhnid", t, k_in,
                       preferred_element_type=f32).astype(dtype)
        u0 = jnp.einsum("bhnij,bhnjd->bhnid", t, v_in,
                        preferred_element_type=f32)
        # what each position's key still adds to the state by the
        # chunk's end: exp(G_C - G_j) k_j
        k_end = (kc.astype(f32)
                 * jnp.exp(gc[..., -1:] - gc)[..., None]).astype(dtype)

        def carry(state, inp):
            w_c, u0_c, k_end_c, dec_c = inp
            u = u0_c - jnp.einsum("bhid,bhde->bhie", w_c,
                                  state.astype(dtype),
                                  preferred_element_type=f32)
            nxt = (state * dec_c[..., None, None]
                   + jnp.einsum("bhid,bhie->bhde", k_end_c, u.astype(dtype),
                                preferred_element_type=f32))
            return nxt, (state.astype(dtype), u.astype(dtype))

        def by_chunk(x):
            return jnp.moveaxis(x, 2, 0)

        _, (starts, u) = lax.scan(
            carry, jnp.zeros((bsz, h, dk, dv), f32),
            (by_chunk(w), by_chunk(u0), by_chunk(k_end),
             by_chunk(jnp.exp(gc[..., -1]))))
        starts, u = jnp.moveaxis(starts, 0, 2), jnp.moveaxis(u, 0, 2)

        qk = jnp.einsum("bhnid,bhnjd->bhnij", qc, kc,
                        preferred_element_type=f32) * decay
        q_in = (qc.astype(f32) * jnp.exp(gc)[..., None]).astype(dtype)
        o = (jnp.einsum("bhnid,bhnde->bhnie", q_in, starts,
                        preferred_element_type=f32)
             + jnp.einsum("bhnij,bhnje->bhnie", qk.astype(dtype), u,
                          preferred_element_type=f32))
        o = jnp.moveaxis(o.astype(dtype), 1, 3).reshape(bsz, nc * c, h, dv)
    return o[:, :s] if pad else o
