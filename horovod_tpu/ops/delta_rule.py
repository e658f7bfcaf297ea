"""The gated delta rule of a linear-attention layer (Gated DeltaNet),
computed in chunks by two Pallas kernels, forward and backward.

Per head (``q_t``, ``k_t`` in R^dk, ``v_t`` in R^dv, a log-decay
``g_t <= 0`` and a write strength ``beta_t`` in (0, 2); the state ``S``
is ``[dk, dv]``, ``S_0 = 0``):

    S   <- exp(g_t) S
    u_t  = beta_t (v_t - S^T k_t)        what the state lacks for k_t
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

The step's transition ``exp(g_t) (I - beta_t k_t k_t^T)`` (``|k_t| = 1``)
has the eigenvalue ``exp(g_t) (1 - beta_t)`` along the key: in (0, 1)
for a write strength ``sigmoid(.)``, down to -1 for ``2 sigmoid(.)``
(Grazzi et al., arXiv:2411.12537: what the state holds for a key can
change sign, not only fade). Nothing here assumes ``beta_t < 1``; the
entries of ``A`` below reach ``beta_t``.

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

**Where it runs.** The operands stay where they lie: ``q``, ``k``,
``v`` ``[B, S, H, d]`` are read as ``[B, S, H x d]`` and a block spec
picks the ``d`` columns of ``_HEADS`` heads; the only XLA ops are on
``g`` and ``beta`` (``[B, S, H]``: the cast to ``DECAY_DTYPE``, the
cumulative sum inside each chunk, eight rows a tile) and, where a
head's width is not whole lanes (``dk = 96``, ``dv = 192``), columns of
zeros up to the next multiple of 128 on ``q``, ``k``, ``v`` and their
cut from ``o`` (``_lane_pad``): a zero column of a key or a query adds
nothing to a dot product and a zero column of ``v`` is a column of the
state that stays zero, so ``o`` is the recurrence's at the widths
given. A tile is
``_ROWS`` = 128 positions: two chunks of 64, whose matrices are
independent and fill the MXU's height together. The grid is (batch,
blocks of heads, tiles), the tiles LAST and one after another.

*Forward* (``_forward_kernel``): the state ``S`` ``[dk, dv]`` float32
of each head lives in a VMEM scratch, zeroed at the first tile and
carried from chunk to chunk. Per tile, on values that never leave VMEM:
the decays ``exp(G_i - G_j)``, ``K K^T`` and ``Q K^T``, ``A``, ``T`` by
``_inverse_unit_lower`` (float32, below), ``W``, ``U0``; then chunk by
chunk ``U = U0 - W S``, the state's part of the output and the state's
update; then ``(decay * Q K^T) U``. Under differentiation it also
writes, in the activations' type, the state each chunk STARTS from
(``[B, H, chunks, dk, dv]``) and each tile's ``T`` (``[B, H, tiles, R,
R]``): the residuals besides the operands, alive only inside the
backward of the caller's checkpoint (``qwen3_next._gdn_layer``: one
group of heads).

*Backward* (``_backward_kernel``): the tiles and their chunks in
REVERSE, the state's cotangent ``dS`` ``[dk, dv]`` float32 in a VMEM
scratch the same way. Per tile it recomputes the chunk matrices from
``q``, ``k``, ``v``, the decays and the saved chunk-start states, all
but ``T``, which it is handed (forming it is more than half of the
forward's time: my chip run, PR 38), and writes ``dq``, ``dk``, ``dv``
where the operands lie and the cotangents of the tile's eight rows,
from which JAX's own transpose of the cumulative sum gives ``dg`` and
``dbeta``. The derivative of the solve needs no second inverse:
``dA = -(T^T dU0) U0^T - (T^T dW) W^T``.

``T``: the product ``(I - A)(I + A^2)(I + A^4)``, exact because ``A``
is nilpotent, on diagonal blocks of 8, and forward substitution by
blocks above them (``_inverse_unit_lower``), float32 at HIGHEST. Decays,
their cumulative sums and exponentials (``DECAY_DTYPE``), ``A`` and
``T`` are float32 whatever the activations' type; every other matmul
takes the activations' type in and accumulates in float32 (float32
activations: every matmul at HIGHEST). Off the TPU the same kernels run
in interpret mode.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import _interpret

# The type of the log-decays, their cumulative sums and exponentials,
# whatever the activations' type; read when ``delta_rule`` is traced.
DECAY_DTYPE = jnp.float32

# Lanes of a vector register: a block spec's last dimension, ``heads x d``
# columns, has to be whole ones.
_LANES = 128
# Side of the diagonal blocks whose inverse is taken as a product of
# powers; larger blocks are put together by block substitution.
_BASE = 8
# Positions a tile: the MXU's height (a chunk longer than this is its
# own tile).
_ROWS = 128
# Heads a grid step, at most: their chains of matmuls are independent,
# so one's latency is filled with another's work (on the chip, a group
# of the cell: 1 head 1.87 / 3.11 ms forward / forward and backward, 2
# heads 1.70 / 2.76, 4 heads 1.63 / 2.67, 8 heads 1.57 / 2.64 and twice
# the time to compile: my chip run, PR 38).
_HEADS = 4
# The rows of a tile's ``[8, rows]`` float32 block of per-position
# scalars: the cumulative log-decay inside the chunk, beta, and the
# cumulative log-decay at the chunk's end; five rows of zeros.
_G, _BETA, _G_END = 0, 1, 2

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN, exact=False):
    """Float32 out; ``exact`` is float32 in as well, at HIGHEST."""
    return lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if exact else None)


def _block_of(index, size):
    return index >> (size.bit_length() - 1)


def _inverse_unit_lower(a, chunk):
    """``(I + a)^-1`` of ``a`` ``[R, R]`` float32, strictly lower
    triangular inside diagonal blocks of ``chunk`` (a power of two that
    divides ``R``) and zero elsewhere; the inverse has the same blocks.

    Diagonal blocks of ``_BASE``: with ``x = -a_block``, ``x^8 = 0`` and
    the inverse is ``(I + x)(I + x^2)(I + x^4)``, exact and, at this
    size, well conditioned (its terms stay under ``C(7, 3) = 35`` times
    the largest entry's power). Then pairs of blocks, again and again:
    with ``D`` the blocks inverted so far and ``L`` what ``a`` holds
    under them inside the blocks twice their size,
    ``(D^-1 + L)^-1 = D - D L D`` (``L D L = 0``), which is forward
    substitution by blocks and as stable. (The product of powers over
    the WHOLE chunk is exact too, but its terms reach ``C(62, 31) =
    4.5e17`` times the 32nd power of an entry: once the keys of a chunk
    point the same way, as they do after a few optimizer steps, float32
    cancels them to noise and the state overflows within a sequence: my
    chip run, PR 37.) Every product is a whole-tile matmul at HIGHEST:
    what lies outside the blocks is zero and stays zero."""
    r = a.shape[-1]
    row = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    col = lax.broadcasted_iota(jnp.int32, (r, r), 1)

    def blocks(size):
        return _block_of(row, size) == _block_of(col, size)

    size = min(chunk, _BASE)
    x = jnp.where(blocks(size), -a, 0.0)
    inv = jnp.where(row == col, 1.0, x)
    power = 2
    while power < size:
        x = _dot(x, x, exact=True)
        inv = inv + _dot(inv, x, exact=True)
        power *= 2
    while size < chunk:
        below = jnp.where(blocks(2 * size) & ~blocks(size), a, 0.0)
        inv = inv - _dot(_dot(inv, below, exact=True), inv, exact=True)
        size *= 2
    return inv


def _lane_pad(d):
    """Columns of zeros that bring a head's width ``d`` to whole lanes
    where Mosaic compiles the kernels; none in interpret mode."""
    return 0 if _interpret() else -d % _LANES


def _rounded(x, decay_dtype):
    """``x`` (float32) as ``decay_dtype`` holds it."""
    if decay_dtype == jnp.float32:
        return x
    return x.astype(decay_dtype).astype(jnp.float32)


def _chunk_matrices(q, k, v, rows, *, chunk, decay_dtype, t=None):
    """What a tile's chunks compute before they meet the state, from
    ``q``, ``k`` ``[R, dk]``, ``v`` ``[R, dv]`` and ``rows`` ``[8, R]``
    (``t``: the tile's ``T`` where it was kept). ``[R, 1]`` columns
    scale rows; ``[R, R]`` matrices are zero outside the chunks'
    diagonal blocks."""
    f32, dtype = jnp.float32, v.dtype
    exact = dtype == f32
    r = q.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    col = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    same = _block_of(row, chunk) == _block_of(col, chunk)

    def dec(x):
        return _rounded(x, decay_dtype)

    cols = rows.T                                           # [R, 8]
    g, beta, g_end = (cols[:, i:i + 1] for i in (_G, _BETA, _G_END))
    m = types.SimpleNamespace(beta=beta, q32=q.astype(f32),
                              k32=k.astype(f32), v32=v.astype(f32))
    # exp(G_i - G_j) for j <= i. The mask goes on the exponent, so that
    # no exp(positive) is formed
    m.decay = dec(jnp.exp(dec(jnp.where(
        same & (col <= row), g - rows[_G:_G + 1], -jnp.inf))))
    m.grown = dec(jnp.exp(g))                               # exp(G)
    # what each position's key still adds to the state by the chunk's
    # end, exp(G_C - G_j), and what the state keeps of itself, exp(G_C)
    m.left = dec(jnp.exp(dec(g_end - g)))
    m.kept = dec(jnp.exp(g_end))
    m.before = same & (col < row)
    m.kk = jnp.where(m.before, _dot(k, k, _NT, exact) * m.decay, 0.0)
    m.a = m.kk * beta
    m.t = _inverse_unit_lower(m.a, chunk).astype(dtype) if t is None else t
    m.beta_grown = dec(beta * m.grown)
    k_in = (m.k32 * m.beta_grown).astype(dtype)
    v_in = (m.v32 * beta).astype(dtype)
    m.w = _dot(m.t, k_in, exact=exact).astype(dtype)
    m.u0 = _dot(m.t, v_in, exact=exact)
    m.qk = _dot(q, k, _NT, exact) * m.decay
    m.q_in = (m.q32 * m.grown).astype(dtype)
    m.k_end = (m.k32 * m.left).astype(dtype)
    return m


def _kept(m, c, chunk, height):
    """``exp(G_C)`` of the tile's chunk ``c`` as a ``[height, 1]``
    column: the chunk's rows of ``m.kept`` all hold it (Mosaic has no
    broadcast of one element along both axes)."""
    kept = m.kept[c * chunk:(c + 1) * chunk]
    return jnp.concatenate([kept] * -(-height // chunk))[:height]


def _tile_forward(q, k, v, rows, state, *, chunk, decay_dtype):
    """One tile: the output ``[R, dv]`` float32, the state each of its
    chunks started from and ``T`` (the activations' type); ``state`` (a
    ``[dk, dv]`` float32 ref) moves to the tile's end."""
    dtype = v.dtype
    exact = dtype == jnp.float32
    m = _chunk_matrices(q, k, v, rows, chunk=chunk, decay_dtype=decay_dtype)
    starts, u, o = [], [], []
    for c in range(q.shape[0] // chunk):
        at = slice(c * chunk, (c + 1) * chunk)
        s32 = state[...]
        s = s32.astype(dtype)
        u_c = (m.u0[at] - _dot(m.w[at], s, exact=exact)).astype(dtype)
        state[...] = (s32 * _kept(m, c, chunk, s32.shape[0])
                      + _dot(m.k_end[at], u_c, _TN, exact))
        starts.append(s)
        u.append(u_c)
        o.append(_dot(m.q_in[at], s, exact=exact))
    o = jnp.concatenate(o) + _dot(m.qk.astype(dtype), jnp.concatenate(u),
                                  exact=exact)
    return o, starts, m.t


def _tile_backward(q, k, v, rows, do, starts, t, dstate, *, chunk,
                   decay_dtype):
    """One tile's backward from ``do`` ``[R, dv]``, the states its
    chunks started from, its ``T`` and ``dstate`` (a ``[dk, dv]``
    float32 ref: the cotangent of the state at the tile's end, moved to
    its start): ``dq``, ``dk``, ``dv`` (float32) and the cotangent of
    ``rows``."""
    f32, dtype = jnp.float32, v.dtype
    exact = dtype == f32
    r = q.shape[0]
    n_sub = r // chunk
    m = _chunk_matrices(q, k, v, rows, chunk=chunk, decay_dtype=decay_dtype,
                        t=t)
    u = jnp.concatenate([
        (m.u0[c * chunk:(c + 1) * chunk]
         - _dot(m.w[c * chunk:(c + 1) * chunk], starts[c], exact=exact)
         ).astype(dtype) for c in range(n_sub)])
    du_out = _dot(m.qk.astype(dtype), do, _TN, exact)       # [R, dv]
    # zero where qk and decay are, in all it meets
    dqk = _dot(do, u, _NT, exact)
    position = lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    du, dw, dq_in, dk_end = ([None] * n_sub for _ in range(4))
    d_end = jnp.zeros((r, 1), f32)          # of the rows' _G_END
    for c in reversed(range(n_sub)):
        at = slice(c * chunk, (c + 1) * chunk)
        s = starts[c]
        ds32 = dstate[...]
        ds = ds32.astype(dtype)
        du[c] = du_out[at] + _dot(m.k_end[at], ds, exact=exact)
        du_c = du[c].astype(dtype)
        dk_end[c] = _dot(u[at], ds, _NT, exact)
        dq_in[c] = _dot(do[at], s, _NT, exact)
        dw[c] = -_dot(du_c, s, _NT, exact)
        kept = _kept(m, c, chunk, ds32.shape[0])
        # exp(G_C) <S, dS>, on the chunk's last position
        d_end = d_end + jnp.where(
            position == (c + 1) * chunk - 1,
            jnp.sum(jnp.sum(s.astype(f32) * ds32 * kept, axis=1,
                            keepdims=True), axis=0, keepdims=True), 0.0)
        dstate[...] = (ds32 * kept + _dot(m.q_in[at], do[at], _TN, exact)
                       - _dot(m.w[at], du_c, _TN, exact))
    du, dw, dq_in, dk_end = (jnp.concatenate(x)
                             for x in (du, dw, dq_in, dk_end))
    # U0 = T (beta V), W = T (beta exp(G) K), T = (I + A)^-1
    dv_in = _dot(m.t, du.astype(dtype), _TN, exact)         # [R, dv]
    dk_in = _dot(m.t, dw.astype(dtype), _TN, exact)         # [R, dk]
    da = -jnp.where(
        m.before,
        _dot(dv_in.astype(dtype), m.u0.astype(dtype), _NT, exact)
        + _dot(dk_in.astype(dtype), m.w, _NT, exact), 0.0)
    dkk = (da * m.beta * m.decay).astype(dtype)
    dqk_raw = (dqk * m.decay).astype(dtype)
    # through every exp(G_i - G_j), of A and of the output's matrix
    through_decay = da * m.a + dqk * m.qk
    k_dot_in = jnp.sum(dk_in * m.k32, axis=1, keepdims=True)
    k_dot_end = jnp.sum(dk_end * m.k32, axis=1, keepdims=True) * m.left
    d_g = (jnp.sum(through_decay, axis=1, keepdims=True)
           + jnp.sum(dq_in * m.q32, axis=1, keepdims=True) * m.grown
           + k_dot_in * m.beta_grown - k_dot_end)
    d_beta = (jnp.sum(da * m.kk, axis=1, keepdims=True)
              + k_dot_in * m.grown
              + jnp.sum(dv_in * m.v32, axis=1, keepdims=True))
    d_end = d_end + k_dot_end
    dq = _dot(dqk_raw, k, exact=exact) + dq_in * m.grown
    dk = (_dot(dqk_raw, q, _TN, exact) + _dot(dkk, k, _TN, exact)
          + _dot(dkk, k, exact=exact) + dk_in * m.beta_grown
          + dk_end * m.left)
    dv = dv_in * m.beta
    lane = lax.broadcasted_iota(jnp.int32, (r, 8), 1)
    drows = jnp.where(lane == _G, d_g, jnp.where(
        lane == _BETA, d_beta, jnp.where(lane == _G_END, d_end, 0.0))).T
    first = lax.broadcasted_iota(jnp.int32, (8, r), 0) == _G
    drows = drows - jnp.where(
        first, jnp.sum(through_decay, axis=0, keepdims=True), 0.0)
    return dq, dk, dv, drows


def _forward_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, *rest, chunk,
                    decay_dtype):
    """One tile of a block of heads; ``rest`` is the residuals' two
    outputs, if asked for, and the states' scratch."""
    state = rest[-1]
    heads = state.shape[0]
    dk, dv = q_ref.shape[2] // heads, v_ref.shape[2] // heads

    @pl.when(pl.program_id(2) == 0)
    def _first_tile():
        state[...] = jnp.zeros_like(state)

    for j in range(heads):
        keys, values = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        o, starts, t = _tile_forward(
            q_ref[0, :, keys], k_ref[0, :, keys], v_ref[0, :, values],
            rows_ref[0, j, 0], state.at[j], chunk=chunk,
            decay_dtype=decay_dtype)
        o_ref[0, :, values] = o.astype(o_ref.dtype)
        if len(rest) > 1:
            starts_ref, t_ref = rest[:2]
            for c, start in enumerate(starts):
                starts_ref[0, j, c] = start
            t_ref[0, j, 0] = t


def _backward_kernel(q_ref, k_ref, v_ref, rows_ref, do_ref, starts_ref,
                     t_ref, dq_ref, dk_ref, dv_ref, drows_ref, dstate, *,
                     chunk, decay_dtype):
    """One tile of a block of heads, the tiles from the last."""
    heads = dstate.shape[0]
    dk, dv = q_ref.shape[2] // heads, v_ref.shape[2] // heads

    @pl.when(pl.program_id(2) == 0)
    def _last_tile():
        dstate[...] = jnp.zeros_like(dstate)

    for j in range(heads):
        keys, values = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        dq, dk_, dv_, drows = _tile_backward(
            q_ref[0, :, keys], k_ref[0, :, keys], v_ref[0, :, values],
            rows_ref[0, j, 0], do_ref[0, :, values],
            [starts_ref[0, j, c] for c in range(starts_ref.shape[2])],
            t_ref[0, j, 0], dstate.at[j], chunk=chunk,
            decay_dtype=decay_dtype)
        dq_ref[0, :, keys] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, keys] = dk_.astype(dk_ref.dtype)
        dv_ref[0, :, values] = dv_.astype(dv_ref.dtype)
        drows_ref[0, j, 0] = drows


def _call(kernel, name, operands, in_specs, out_shape, out_specs, grid,
          state_shape):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(state_shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        # no ``hvd_`` of its own: a trace reads the kernel under the
        # scope it runs in, ``hvd_delta_rule`` (docs/tracing.md#names)
        name=name,
    )(*operands)


def _specs(rows, dk, dv, chunk, reverse):
    """The grid (batch, blocks of heads, tiles: the tiles LAST and one
    after another), the shape of a block's states and the block specs
    of ``q`` / ``k``, of ``v``, of the rows, of the chunk-start states
    and of the tiles' ``T``; ``reverse`` walks the tiles from the
    last."""
    bsz, h, tiles, _, r = rows.shape
    heads = max(n for n in range(1, _HEADS + 1) if h % n == 0)

    def spec(block, place):
        def index(b, head, i):
            return place(b, head, tiles - 1 - i if reverse else i)
        return pl.BlockSpec(block, index)

    def wide(d):
        return spec((1, r, heads * d), lambda b, head, i: (b, i, head))

    def per_head(*block):
        return spec((1, heads) + block, lambda b, head, i:
                    (b, head, i) + (0,) * (len(block) - 1))

    return ((bsz, h // heads, tiles), (heads, dk, dv), wide(dk), wide(dv),
            per_head(1, 8, r), per_head(r // chunk, dk, dv),
            per_head(1, r, r))


def _forward(q, k, v, rows, chunk, decay_dtype, residuals):
    """``q``, ``k`` ``[B, S, H x dk]``, ``v`` ``[B, S, H x dv]``, ``rows``
    ``[B, H, tiles, 8, R]``: ``o`` like ``v`` and, with ``residuals``,
    the chunk-start states ``[B, H, chunks, dk, dv]`` and the tiles'
    ``T`` ``[B, H, tiles, R, R]``."""
    bsz, h, tiles, _, r = rows.shape
    dk, dv = q.shape[2] // h, v.shape[2] // h
    grid, states, qk_spec, v_spec, rows_spec, starts_spec, t_spec = _specs(
        rows, dk, dv, chunk, False)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [v_spec]
    if residuals:
        out_shape += [
            jax.ShapeDtypeStruct((bsz, h, q.shape[1] // chunk, dk, dv),
                                 v.dtype),
            jax.ShapeDtypeStruct((bsz, h, tiles, r, r), v.dtype)]
        out_specs += [starts_spec, t_spec]
    out = _call(
        functools.partial(_forward_kernel, chunk=chunk,
                          decay_dtype=decay_dtype),
        "delta_rule_fwd", (q, k, v, rows),
        [qk_spec, qk_spec, v_spec, rows_spec], out_shape, out_specs, grid,
        states)
    return out if residuals else out[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _chunked(q, k, v, rows, chunk, decay_dtype):
    return _forward(q, k, v, rows, chunk, decay_dtype, False)


def _chunked_fwd(q, k, v, rows, chunk, decay_dtype):
    o, starts, t = _forward(q, k, v, rows, chunk, decay_dtype, True)
    return o, (q, k, v, rows, starts, t)


def _chunked_bwd(chunk, decay_dtype, res, do):
    q, k, v, rows, starts, t = res
    h = rows.shape[1]
    dk, dv = q.shape[2] // h, v.shape[2] // h
    grid, states, qk_spec, v_spec, rows_spec, starts_spec, t_spec = _specs(
        rows, dk, dv, chunk, True)
    return tuple(_call(
        functools.partial(_backward_kernel, chunk=chunk,
                          decay_dtype=decay_dtype),
        "delta_rule_bwd", (q, k, v, rows, do, starts, t),
        [qk_spec, qk_spec, v_spec, rows_spec, v_spec, starts_spec, t_spec],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, rows)],
        [qk_spec, qk_spec, v_spec, rows_spec], grid, states))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """The recurrence above for every head of ``q``, ``k`` ``[B, S, H,
    dk]`` (as the state meets them: normalised and scaled by the
    caller), ``v`` ``[B, S, H, dv]``, ``g`` ``[B, S, H]`` (log-decay,
    ``<= 0``) and ``beta`` ``[B, S, H]``. Returns ``o`` ``[B, S, H,
    dv]`` in ``v``'s type.

    A sequence that is no multiple of a tile is padded with steps of
    ``g = 0``, ``beta = 0`` and zero keys: they decay nothing and write
    nothing, and their outputs are cut off. On the TPU a head's width
    that is not whole lanes is padded with columns of zeros, cut off
    again (the module's docstring)."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    r = max(chunk, _ROWS)
    pad = -s % r
    wide_k, wide_v = dk + _lane_pad(dk), dv + _lane_pad(dv)

    def padded(t, width=None):
        """``t`` with steps of zeros up to a whole tile and, ``q``, ``k``
        and ``v``, columns of zeros up to ``width``."""
        to = [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)
        if width is not None:
            to[-1] = (0, width - t.shape[-1])
        return jnp.pad(t, to) if any(hi for _, hi in to) else t

    def by_chunk(t):
        """``[B, S, H]`` -> ``[B, H, chunks, C]``."""
        return jnp.moveaxis(t, 1, 2).reshape(bsz, h, -1, chunk)

    with jax.named_scope("hvd_delta_rule"):
        q, k, v = padded(q, wide_k), padded(k, wide_k), padded(v, wide_v)
        g, beta = padded(g), padded(beta)
        decay_dtype = jnp.dtype(DECAY_DTYPE)
        gc = jnp.cumsum(by_chunk(g.astype(decay_dtype)), axis=-1)
        rows = jnp.stack(
            [gc, by_chunk(beta.astype(decay_dtype)),
             jnp.broadcast_to(gc[..., -1:], gc.shape)],
            axis=2).astype(jnp.float32)
        # [B, H, 3, chunks, C] -> [B, H, tiles, 8, R]
        rows = jnp.pad(rows.reshape(bsz, h, 3, -1, r),
                       ((0, 0),) * 2 + ((0, 5),) + ((0, 0),) * 2)
        rows = jnp.moveaxis(rows, 2, 3)
        o = _chunked(q.reshape(bsz, s + pad, h * wide_k),
                     k.reshape(bsz, s + pad, h * wide_k),
                     v.reshape(bsz, s + pad, h * wide_v), rows, chunk,
                     decay_dtype)
        o = o.reshape(bsz, s + pad, h, wide_v)
        return o[:, :s, :, :dv] if pad or wide_v > dv else o
