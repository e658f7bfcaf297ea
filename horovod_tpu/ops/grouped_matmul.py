"""Grouped matmuls over rows sorted by group (an expert layer's rows
sorted by expert): Pallas kernels that read and write the rows BY INDEX,
forward and both backward products, and the sum of the rows back to
their tokens.

The buffer of rows has a STATIC size ``M`` (the worst case a dropless
expert layer can meet); the real rows are what the routing gave: group
0's, then group 1's, ...; ``group_sizes`` ``[G]`` says how many each
has. Row ``r`` is a copy of token ``token[r]``. ``w`` is ``[G, K, N]``.

    h[r]   = u[token[r]] @ w[g(r)]                 hvd_gmm_fwd (gathers u)
    out[t] = sum_{r of t} weight[r]
             (act(x[r]) @ w[g(r)])                 hvd_gmm_fwd, moe_combine
    dx[r]  = dy[r] @ w[g(r)]^T                        hvd_gmm_drows
    dw[g]  = sum_{r in g} x[r]^T dy[r]                hvd_gmm_dw

``gather_matmul`` is the first line with its backward, ``matmul_combine``
the second with its backward. A kernel that reads a token's row copies
it by DMA from the token array, for the real rows of its tile only; the
sum back to the tokens (``moe_combine``) walks token tiles and fetches
only the rows of each tile's tokens. No array of the buffer's rows is
made or read outside a kernel but the products' own outputs.

Rows come in tiles of ``TILE``; a product's grid walks VISITS, each one
(group, tile): a group visits every tile it has rows in (a tile on a
boundary is visited by each group that has rows there, which write
their own rows of it), an empty group visits once and writes nothing of
its own (its ``dw`` is zeroed by that visit). ``layout`` computes the
visits from ``group_sizes`` with static shapes (``M / TILE + G`` of
them, the most there can be); the unused ones at the end repeat the
last and do nothing. The tiles beyond the real rows get no visit: no
kernel writes them (their memory is whatever it was) and no kernel
reads them. So the shapes and the grids are functions of ``M``, ``G``
and the widths alone, and only what a kernel SKIPS follows the routing.

A kernel that reads or writes rows by index takes the whole width of
its matrices in one block, so each row moves once; rows it reads by
index, and rows it writes for the sum, are WHOLE-TILE ROWS ``[rows,
n / 128, 128]`` (``_slab``): one row of a ``[rows, n]`` array is a
sliver of tiles that a DMA cannot address.

``w`` is read in its own type (the float32 parameters) and cast to the
rows' type inside the kernel; products accumulate in float32; ``dw``
comes out float32, in ``w``'s type; the sum back to the tokens is
float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128          # rows a tile: the MXU's height
# the scope the work outside the three products' kernels is read under
# (docs/tracing.md#names): the sum back to the tokens, the relayouts of
# the token arrays into whole-tile rows, the weights' cotangent
DISPATCH = "hvd_moe_dispatch"
_RING = 32          # rows in flight in the sum back to the tokens


class Layout(NamedTuple):
    """The visits of a buffer of ``M`` rows in ``G`` groups, int32: per
    visit the group (``G`` where none: an unused visit), the tile it
    reads and writes, whether it is the first of its tile and the first
    of its group's ``dw`` block; per group where its rows start and end
    (0, 0 for ``G``)."""
    group: jax.Array
    tile: jax.Array
    first_of_tile: jax.Array
    first_of_group: jax.Array
    start: jax.Array
    end: jax.Array


def layout(group_sizes, rows: int) -> Layout:
    """``group_sizes`` ``[G]`` (their sum at most ``rows``, a multiple
    of ``TILE``): the visits, by static shapes."""
    if rows % TILE:
        raise ValueError(f"rows ({rows}) must be a multiple of {TILE}")
    sizes = group_sizes.astype(jnp.int32)
    g, tiles = sizes.shape[0], rows // TILE
    end = jnp.cumsum(sizes)
    start = end - sizes
    live = (end[-1] + TILE - 1) // TILE
    # an empty group at the end visits the last real tile, not one
    # beyond it
    first = jnp.minimum(start // TILE, jnp.maximum(live - 1, 0))
    last = jnp.where(sizes > 0, (end - 1) // TILE, first)
    count = last - first + 1
    upto = jnp.cumsum(count)
    visits = upto[-1]
    v = jnp.arange(tiles + g, dtype=jnp.int32)
    group = jnp.minimum((v[:, None] >= upto[None, :]).sum(1), g - 1)
    group = group.astype(jnp.int32)
    tile = first[group] + v - (upto - count)[group]
    used = v < visits
    tile = jnp.where(used, tile, tile[visits - 1])
    group = jnp.where(used, group, g)
    block = jnp.minimum(group, g - 1)           # the group's block of w
    zero = jnp.zeros((1,), jnp.int32)

    def changes(t):
        return jnp.append(1, (t[1:] != t[:-1]).astype(jnp.int32))

    return Layout(group=group, tile=tile, first_of_tile=changes(tile),
                  first_of_group=changes(block),
                  start=jnp.append(start, zero), end=jnp.append(end, zero))


def token_tile(tokens: int) -> int:
    """Tokens a step of the sum back to the tokens: 128 where it divides
    ``tokens``, else the largest power of two from 64 to 8 that does,
    else all of them."""
    for t in (128, 64, 32, 16, 8):
        if tokens % t == 0:
            return t
    return tokens


def _slab(n: int):
    """The trailing shape of a whole-tile row of width ``n``."""
    return (n // 128, 128) if n % 128 == 0 else (1, n)


def to_slabs(a):
    """``[rows, n]`` as whole-tile rows ``[rows, *_slab(n)]``."""
    return a.reshape(a.shape[0], *_slab(a.shape[1]))


def _interpret():
    return jax.default_backend() != "tpu"


# -- inside the kernels ------------------------------------------------

def _owned(lay, v):
    """The first and last + 1 row of visit ``v``'s tile that its group
    owns (an empty range for an unused visit)."""
    group, tile, _, _, start, end = lay
    g = group[v]
    base = tile[v] * TILE
    return (jnp.maximum(start[g], base), jnp.minimum(end[g], base + TILE),
            base)


def _own_mask(lay, v, shape):
    lo, hi, base = _owned(lay, v)
    row = base + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo) & (row < hi)


def _gather(src, buf, sem, token, lo, hi, base):
    """Rows ``lo .. hi - 1`` of the buffer, copied by DMA from the token
    array ``src`` (HBM, whole-tile rows) into ``buf[r - base]``; returns
    when all have landed. The other rows of ``buf`` keep what they held."""
    def start(r, carry):
        pltpu.make_async_copy(src.at[pl.ds(token[r], 1)],
                              buf.at[pl.ds(r - base, 1)], sem).start()
        return carry

    def wait(r, carry):
        pltpu.make_async_copy(src.at[pl.ds(0, 1)], buf.at[pl.ds(0, 1)],
                              sem).wait()
        return carry

    lax.fori_loop(lo, hi, start, 0)
    lax.fori_loop(lo, hi, wait, 0)


def _turned(vec):
    """``[1, TILE]`` as ``[TILE, 1]`` or back (a row's weights between
    lanes and sublanes): the diagonal of its broadcast, summed across."""
    eye = (lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
           == lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1))
    square = jnp.where(eye, jnp.broadcast_to(vec, (TILE, TILE)), 0.0)
    return jnp.sum(square, axis=1 if vec.shape[0] == 1 else 0,
                   keepdims=True)


def _in_float32(act):
    """``act`` on float32 rows, its result in the rows' type: a v5e's
    vector unit has no bfloat16 arithmetic (XLA computes a bfloat16
    activation the same way, outside a kernel)."""
    if act is None:
        return None
    return lambda x: act(x.astype(jnp.float32)).astype(x.dtype)


def _live(lay, v, groups):
    group, _, _, _, start, end = lay
    g = group[v]
    return (g < groups) & (end[g] > start[g])


def _rows_kernel(*refs, groups, contract_w, gathered, to_slabs_out,
                 weighted, dtype, act):
    """One visit of ``x @ w[g]`` (``contract_w`` 0) or ``x @ w[g]^T``
    (1) over the tile's rows that ``g`` owns. ``gathered``: the rows of
    ``x`` come by index from a token array. ``to_slabs_out``: the output
    is whole-tile rows. ``act``: the product is of ``act(x)``.
    ``weighted`` (the second product's ``drows``, whose rows are the
    tokens' cotangents): the product is scaled by the rows' weights and
    taken back through ``act`` at ``x_fwd``, and ``<act(x_fwd), x @
    w[g]^T>`` a row, the cotangent of its weight, is a second output."""
    refs = list(refs)
    lay = refs[:6]
    del refs[:6]
    token = refs.pop(0) if gathered else None
    x_ref, w_ref = refs.pop(0), refs.pop(0)
    if weighted:
        rw_ref, xf_ref = refs.pop(0), refs.pop(0)
    o_ref = refs.pop(0)
    drw_ref = refs.pop(0) if weighted else None
    v = pl.program_id(0)

    @pl.when(lay[2][v] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)
        if weighted:
            drw_ref[...] = jnp.zeros_like(drw_ref)

    @pl.when(_live(lay, v, groups))
    def _rows():
        if gathered:
            buf, sem = refs
            lo, hi, base = _owned(lay, v)
            _gather(x_ref, buf, sem, token, lo, hi, base)
            x = buf[...].reshape(TILE, -1).astype(dtype)
        else:
            x = x_ref[...]
            if act is not None and not weighted:
                x = act(x)
        acc = lax.dot_general(
            x, w_ref[...].astype(x.dtype),
            (((1,), (contract_w,)), ((), ())),
            preferred_element_type=jnp.float32)
        own = _own_mask(lay, v, (TILE, 1))
        if weighted:
            a, back = jax.vjp(act, xf_ref[...])
            part = jnp.sum(a.astype(jnp.float32) * acc, axis=1,
                           keepdims=True)
            drw_ref[...] = jnp.where(_turned(own.astype(jnp.float32)) > 0,
                                     _turned(part), drw_ref[...])
            acc = back((acc * _turned(rw_ref[...])).astype(a.dtype))[0]
        out = acc.astype(o_ref.dtype)
        if to_slabs_out:
            out = out.reshape(o_ref.shape)
            own = own.reshape(TILE, 1, 1)
        o_ref[...] = jnp.where(own, out, o_ref[...])


def _dw_kernel(*refs, groups, x_gathered, dy_gathered, dtype, act):
    """One visit of ``dw[g] += x_tile^T dy_tile`` (``act(x_tile)``
    with an ``act``) over the rows of the tile that ``g`` owns; either
    operand's rows may come by index from a token array (``dy``'s then
    scaled by the rows' weights)."""
    refs = list(refs)
    lay = refs[:6]
    del refs[:6]
    token = refs.pop(0) if x_gathered or dy_gathered else None
    x_ref, dy_ref = refs.pop(0), refs.pop(0)
    rw_ref = refs.pop(0) if dy_gathered else None
    o_ref = refs.pop(0)
    v = pl.program_id(0)

    @pl.when(lay[3][v] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(_live(lay, v, groups))
    def _rows():
        buf, sem = refs if refs else (None, None)
        if buf is not None:
            lo, hi, base = _owned(lay, v)
            _gather(x_ref if x_gathered else dy_ref, buf, sem, token, lo,
                    hi, base)
            rows = buf[...].reshape(TILE, -1)
        x = rows.astype(dtype) if x_gathered else x_ref[...]
        if act is not None:
            x = act(x)
        if dy_gathered:
            dy = (rows.astype(jnp.float32)
                  * _turned(rw_ref[...])).astype(dtype)
        else:
            dy = dy_ref[...]
        # rows a visit does not own hold another group's rows, or what
        # an earlier visit gathered: where, not a product with zero
        own = _own_mask(lay, v, (TILE, 1))
        x = jnp.where(own, x, jnp.zeros_like(x))
        dy = jnp.where(own, dy, jnp.zeros_like(dy))
        o_ref[...] += lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _combine_kernel(token, weight, bounds, y_ref, o_ref, buf, acc, sems,
                    slot_token, slot_weight, *, groups, tt, weighted):
    """One tile of ``tt`` tokens: ``out[t] = sum_{r of t} weight[r]
    y[r]`` in float32 over the real rows of the tile's tokens, which
    are, group by group, the rows ``bounds[i, g] .. bounds[i + 1, g] -
    1``; each is fetched by DMA, ``_RING`` in flight."""
    i = pl.program_id(0)
    acc[...] = jnp.zeros_like(acc)

    def take(k):
        slot = k % _RING
        pltpu.make_async_copy(y_ref.at[pl.ds(0, 1)],
                              buf.at[pl.ds(slot, 1)], sems.at[slot]).wait()
        row = buf[pl.ds(slot, 1)].astype(jnp.float32)
        if weighted:
            row = row * slot_weight[slot]
        t = slot_token[slot]
        acc[pl.ds(t, 1)] += row

    def fetch(r, k):
        @pl.when(k >= _RING)
        def _():
            take(k - _RING)

        slot = k % _RING
        pltpu.make_async_copy(y_ref.at[pl.ds(r, 1)],
                              buf.at[pl.ds(slot, 1)], sems.at[slot]).start()
        slot_token[slot] = token[r] - i * tt
        slot_weight[slot] = weight[r]
        return k + 1

    def group(g, k):
        return lax.fori_loop(bounds[i * groups + g],
                             bounds[(i + 1) * groups + g], fetch, k)

    k = lax.fori_loop(0, groups, group, 0)

    def drain(j, carry):
        take(j)
        return carry

    lax.fori_loop(jnp.maximum(k - _RING, 0), k, drain, 0)
    o_ref[...] = acc[...].reshape(o_ref.shape)


# -- the calls ---------------------------------------------------------

def _vmem_limit(*nbytes):
    """What a call's double-buffered blocks and scratch need, with room:
    whole-width weight blocks pass the default scoped limit."""
    return int(min(2 * sum(nbytes) + (8 << 20), 100 << 20))


def _call(kernel, name, prefetch, operands, in_specs, out_specs, out_shape,
          scratch, grid, nbytes):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(*nbytes)),
        interpret=_interpret(),
        # what a trace finds the kernel by (docs/tracing.md#names)
        name=name,
    )(*prefetch, *operands)


def _tile_spec(width):
    return pl.BlockSpec((TILE, width),
                        lambda v, group, tile, *_: (tile[v], 0))


def _slab_spec(width):
    return pl.BlockSpec((TILE, *_slab(width)),
                        lambda v, group, tile, *_: (tile[v], 0, 0))


def _row_weight_spec():
    return pl.BlockSpec((None, 1, TILE),
                        lambda v, group, tile, *_: (tile[v], 0, 0))


def _w_spec(w, groups):
    return pl.BlockSpec((None, *w.shape[1:]),
                        lambda v, group, *_: (jnp.minimum(group[v],
                                                          groups - 1), 0, 0))


def _buffer_rows(lay, groups):
    return (lay.tile.shape[0] - groups) * TILE


def _nbytes(shape, dtype):
    n = jnp.dtype(dtype).itemsize
    for s in shape:
        n *= s
    return n


def _rows_product(x, w, lay: Layout, *, transposed=False, token=None,
                  to_slabs_out=False, row_weight=None, x_fwd=None,
                  dtype=None, act=None):
    """``x @ w[g]`` (``w`` ``[G, K, N]``) or, ``transposed``, ``x @
    w[g]^T``, one row a row of the buffer, ``[M, width]`` (whole-tile
    rows with ``to_slabs_out``) in ``dtype``; ``act(x) @ w[g]`` with an
    ``act``. With ``token`` the rows of ``x`` are ``x[token[r]]`` (``x``
    whole-tile token rows); with ``row_weight`` (``[M]``, float32) and
    ``x_fwd`` the output is scaled by the rows' weights, taken back
    through ``act`` at ``x_fwd`` (its width), and ``(out, d_row_weight
    [M])`` is returned."""
    g = w.shape[0]
    weighted = row_weight is not None
    width = (x_fwd.shape[1] if weighted else w.shape[1] if transposed
             else w.shape[2])
    m = _buffer_rows(lay, g)
    gathered = token is not None
    prefetch = (*lay, token) if gathered else tuple(lay)
    operands = [x, w]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY) if gathered
                else _tile_spec(x.shape[1]), _w_spec(w, g)]
    nbytes = [_nbytes(w.shape[1:], w.dtype),
              _nbytes((TILE, width), jnp.float32)]
    if not gathered:
        nbytes.append(_nbytes((TILE, x.shape[1]), x.dtype))
    if weighted:
        operands += [row_weight.reshape(m // TILE, 1, TILE), x_fwd]
        in_specs += [_row_weight_spec(), _tile_spec(width)]
        nbytes.append(_nbytes((TILE, width), x_fwd.dtype))
    if to_slabs_out:
        out_shape = [jax.ShapeDtypeStruct((m, *_slab(width)), dtype)]
        out_specs = [_slab_spec(width)]
    else:
        out_shape = [jax.ShapeDtypeStruct((m, width), dtype)]
        out_specs = [_tile_spec(width)]
    if weighted:
        out_shape.append(
            jax.ShapeDtypeStruct((m // TILE, 1, TILE), jnp.float32))
        out_specs.append(_row_weight_spec())
    scratch = []
    if gathered:
        scratch = [pltpu.VMEM((TILE, *x.shape[1:]), x.dtype),
                   pltpu.SemaphoreType.DMA(())]
        nbytes.append(_nbytes((TILE, *x.shape[1:]), x.dtype))
    out = _call(
        functools.partial(_rows_kernel, groups=g,
                          contract_w=1 if transposed else 0,
                          gathered=gathered, to_slabs_out=to_slabs_out,
                          weighted=weighted, dtype=dtype,
                          act=_in_float32(act)),
        "hvd_gmm_drows" if transposed else "hvd_gmm_fwd", prefetch,
        operands, in_specs, out_specs if weighted else out_specs[0],
        out_shape if weighted else out_shape[0], scratch,
        (lay.group.shape[0],), nbytes)
    if weighted:
        return out[0], out[1].reshape(m)
    return out


def _dw_product(x, dy, lay: Layout, shape, *, token=None, x_gathered=False,
                row_weight=None, dtype=None, act=None):
    """``dw[g] = sum_{r in g} x[r]^T dy[r]`` (``act(x[r])`` with an
    ``act``): ``shape`` ``[G, K, N]`` float32. With ``token``, the rows
    of ``x`` (``x_gathered``) or of ``dy`` (else; scaled by
    ``row_weight``) are rows of a token array (whole-tile rows) by
    index."""
    groups, k, n = shape
    m = _buffer_rows(lay, groups)
    gathered = token is not None
    dy_gathered = gathered and not x_gathered
    prefetch = (*lay, token) if gathered else tuple(lay)
    operands = [x, dy]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY) if x_gathered
                else _tile_spec(x.shape[1]),
                pl.BlockSpec(memory_space=pl.ANY) if dy_gathered
                else _tile_spec(n)]
    nbytes = [_nbytes((k, n), jnp.float32),
              _nbytes((TILE, x.shape[1]), dtype), _nbytes((TILE, n), dtype)]
    if dy_gathered:
        operands.append(row_weight.reshape(m // TILE, 1, TILE))
        in_specs.append(_row_weight_spec())
    scratch = []
    if gathered:
        src = x if x_gathered else dy
        scratch = [pltpu.VMEM((TILE, *src.shape[1:]), src.dtype),
                   pltpu.SemaphoreType.DMA(())]
    return _call(
        functools.partial(_dw_kernel, groups=groups, x_gathered=x_gathered,
                          dy_gathered=dy_gathered, dtype=dtype,
                          act=_in_float32(act)),
        "hvd_gmm_dw", prefetch, operands, in_specs,
        pl.BlockSpec((None, k, n),
                     lambda v, group, *_: (jnp.minimum(group[v], groups - 1),
                                           0, 0)),
        jax.ShapeDtypeStruct(shape, jnp.float32), scratch,
        (lay.group.shape[0],), nbytes)


def combine(y, token, bounds, tokens: int, weight=None):
    """``out[t] = sum_{r of t} weight[r] y[r]`` ``[tokens, n]`` float32
    over the real rows, ``y`` whole-tile rows ``[M, *_slab(n)]``,
    ``bounds`` ``[tokens / token_tile + 1, G]`` each group's first row
    whose token is in tile ``i`` of ``token_tile`` tokens or after (the
    last line: each group's end); no weight is one. The kernel
    ``moe_combine``."""
    tt = token_tile(tokens)
    groups = bounds.shape[1]
    n = y.shape[1] * y.shape[2]
    weighted = weight is not None
    if weight is None:
        weight = jnp.ones(token.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_combine_kernel, groups=groups, tt=tt,
                          weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(tokens // tt,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, n), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((_RING, *y.shape[1:]), y.dtype),
                            pltpu.VMEM((tt, *y.shape[1:]), jnp.float32),
                            pltpu.SemaphoreType.DMA((_RING,)),
                            pltpu.SMEM((_RING,), jnp.int32),
                            pltpu.SMEM((_RING,), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="moe_combine",
    )(token, weight.astype(jnp.float32), bounds.reshape(-1), y)


# -- the operations, differentiable ------------------------------------

@jax.custom_vjp
def gather_matmul(u, w, lay: Layout, token, bounds):
    """``h[r] = u[token[r]] @ w[g(r)]`` ``[M, N]`` in ``u``'s type for
    the real rows (``u`` ``[T, K]``, ``w`` ``[G, K, N]``): the rows of
    ``u`` are read by index inside ``hvd_gmm_fwd``, and the tiles beyond
    the real rows are not written. Differentiable in ``u`` and ``w``;
    ``d_u[t]``, the sum over the rows of ``t``, is summed in float32 by
    ``moe_combine``."""
    return _gather_fwd(u, w, lay, token, bounds)[0]


def _gather_fwd(u, w, lay, token, bounds):
    with jax.named_scope(DISPATCH):
        slabs = to_slabs(u)
    h = _rows_product(slabs, w, lay, token=token, dtype=u.dtype)
    return h, (u, w, lay, token, bounds)


def _gather_bwd(res, dh):
    u, w, lay, token, bounds = res
    d_rows = _rows_product(dh, w, lay, transposed=True, to_slabs_out=True,
                           dtype=dh.dtype)
    with jax.named_scope(DISPATCH):
        d_u = combine(d_rows, token, bounds, u.shape[0]).astype(u.dtype)
        slabs = to_slabs(u)
    dw = _dw_product(slabs, dh, lay, w.shape, token=token, x_gathered=True,
                     dtype=u.dtype).astype(w.dtype)
    return d_u, dw, None, None, None


gather_matmul.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def matmul_combine(x, w, row_weight, lay: Layout, token, bounds,
                   tokens: int, act):
    """``out[t] = sum_{r of t} row_weight[r] (act(x[r]) @ w[g(r)])``
    ``[tokens, N]`` float32 over the real rows (``x`` ``[M, K']``, ``w``
    ``[G, K, N]``, ``act`` ``[., K'] -> [., K]`` elementwise along the
    rows; ``row_weight`` ``[M]`` float32): ``act`` and the product in
    ``hvd_gmm_fwd``, the weighted sum in ``moe_combine``. Differentiable in ``x``, ``w`` and
    ``row_weight`` (that cotangent is not written beyond the real
    rows); the backward reads the tokens' cotangents by index inside
    ``hvd_gmm_drows`` and ``hvd_gmm_dw``, takes them back through
    ``act`` and computes the weights' in ``hvd_gmm_drows``, where the
    rows are in VMEM."""
    return _combine_fwd(x, w, row_weight, lay, token, bounds, tokens,
                        act)[0]


def _combine_fwd(x, w, row_weight, lay, token, bounds, tokens, act):
    y = _rows_product(x, w, lay, to_slabs_out=True, dtype=x.dtype, act=act)
    with jax.named_scope(DISPATCH):
        out = combine(y, token, bounds, tokens, row_weight)
    return out, (x, w, row_weight, lay, token)


def _combine_bwd(tokens, act, res, d_out):
    x, w, row_weight, lay, token = res
    with jax.named_scope(DISPATCH):
        d_slabs = to_slabs(d_out.astype(x.dtype))
    dx, d_row_weight = _rows_product(
        d_slabs, w, lay, transposed=True, token=token,
        row_weight=row_weight, x_fwd=x, dtype=x.dtype, act=act)
    dw = _dw_product(x, d_slabs, lay, w.shape, token=token,
                     row_weight=row_weight, dtype=x.dtype,
                     act=act).astype(w.dtype)
    return dx, dw, d_row_weight, None, None, None


matmul_combine.defvjp(_combine_fwd, _combine_bwd)
