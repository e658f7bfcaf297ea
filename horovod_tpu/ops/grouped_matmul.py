"""Grouped matmuls over rows sorted by group (an expert layer's rows
sorted by expert): Pallas kernels, forward and both backward products.

``x`` ``[M, K]`` holds the rows of group 0, then group 1, ...;
``group_sizes`` ``[G]`` says how many each has, and the rows after the
last group are not real: the buffer has a STATIC size (the worst case a
dropless expert layer can meet), the real rows are what the routing
gave. ``w`` is ``[G, K, N]``.

    y[r]  = x[r] @ w[group of r]           hvd_gmm_fwd
    dx[r] = dy[r] @ w[group of r]^T        hvd_gmm_drows
    dw[g] = sum_{r in g} x[r]^T dy[r]      hvd_gmm_dw

Rows come in tiles of ``TILE``; a kernel's grid walks VISITS, each one
(group, tile): a group visits every tile it has rows in (a tile on a
boundary is visited by each group that has rows there, which write
their own rows of it), an empty group visits once and writes nothing
of its own (its ``dw`` is zeroed by that visit), and the tiles beyond
the real rows are visited once each by no group: no matmul runs for
them, ``y`` / ``dx`` are zero there. ``layout`` computes the visits
from ``group_sizes`` with static shapes (``M / TILE + G`` of them, the
most there can be; the unused ones at the end repeat the last and do
nothing). So the shapes and the grid are functions of ``M`` and ``G``
alone, and only what a kernel SKIPS follows the routing.

``w`` is read in its own type (the float32 parameters) and cast to
``x``'s inside the kernel; products accumulate in float32; ``dw`` comes
out float32, in ``w``'s type.
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


class Layout(NamedTuple):
    """The visits of a buffer of ``M`` rows in ``G`` groups, int32:
    per visit the group (``G`` where no group: a tile beyond the real
    rows, or an unused visit), the tile it writes, the tile of ``x`` it
    reads (0 where no group: nothing is read for it again), whether it
    is the first of its tile and the first of its group's ``dw`` block;
    per group where its rows start and end (0, 0 for ``G``)."""
    group: jax.Array
    tile: jax.Array
    x_tile: jax.Array
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
    total = end[-1]
    first = jnp.minimum(start // TILE, tiles - 1)
    last = jnp.where(sizes > 0, (end - 1) // TILE, first)
    live = (total + TILE - 1) // TILE
    # group ``g`` stands for "no group": the tiles beyond the real rows
    first = jnp.append(first, live)
    count = jnp.append(last - first[:g] + 1, tiles - live)
    upto = jnp.cumsum(count)
    visits = upto[-1]
    v = jnp.arange(tiles + g, dtype=jnp.int32)
    group = jnp.minimum((v[:, None] >= upto[None, :]).sum(1), g)
    group = group.astype(jnp.int32)
    tile = first[group] + v - (upto - count)[group]
    used = v < visits
    tile = jnp.where(used, tile, tile[visits - 1])
    group = jnp.where(used, group, g)
    block = jnp.minimum(group, g - 1)           # the group's block of w
    zero = jnp.zeros((1,), jnp.int32)

    def changes(t):
        return jnp.append(1, (t[1:] != t[:-1]).astype(jnp.int32))

    return Layout(
        group=group, tile=tile, x_tile=jnp.where(group < g, tile, 0),
        first_of_tile=changes(tile), first_of_group=changes(block),
        start=jnp.append(start, zero), end=jnp.append(end, zero))


def _interpret():
    return jax.default_backend() != "tpu"


def _own_rows(lay_refs, v, width):
    """``[TILE, width]`` bool: the rows of visit ``v``'s tile that its
    group owns."""
    group, tile, _, _, _, start, end = lay_refs
    g = group[v]
    row = tile[v] * TILE + lax.broadcasted_iota(jnp.int32, (TILE, width), 0)
    return (row >= start[g]) & (row < end[g])


def _rows_kernel(*refs, groups: int, contract_w: int):
    """One visit of ``y = x @ w[g]`` (``contract_w`` 0: ``w`` block
    ``[K, tn]``) or ``dx = dy @ w[g]^T`` (1: ``[tk, N]``)."""
    lay, (x_ref, w_ref, o_ref) = refs[:7], refs[7:]
    v = pl.program_id(1)
    g = lay[0][v]

    @pl.when(lay[3][v] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((g < groups) & (lay[6][g] > lay[5][g]))
    def _rows():
        x = x_ref[...]
        acc = lax.dot_general(
            x, w_ref[...].astype(x.dtype),
            (((1,), (contract_w,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(_own_rows(lay, v, acc.shape[1]),
                               acc.astype(o_ref.dtype), o_ref[...])


def _dw_kernel(*refs, groups: int):
    """One visit of ``dw[g] += x_tile^T dy_tile`` over the rows of the
    tile that ``g`` owns."""
    lay, (x_ref, dy_ref, o_ref) = refs[:7], refs[7:]
    v = pl.program_id(1)
    g = lay[0][v]

    @pl.when(lay[4][v] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((g < groups) & (lay[6][g] > lay[5][g]))
    def _rows():
        dy = dy_ref[...]
        dy = jnp.where(_own_rows(lay, v, dy.shape[1]), dy,
                       jnp.zeros_like(dy))
        o_ref[...] += lax.dot_general(
            x_ref[...], dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _width_tile(n: int) -> int:
    """Columns of a block: 256 where they divide the width; else the
    widest multiple of 128 up to 1024 that does (2688 = 3 x 896, where
    blocks of 128 would be 21 walks over every visit of the grid); else
    the whole width."""
    if n % 256 == 0:
        return 256
    for t in range(1024, 0, -128):
        if n % t == 0:
            return t
    return n


def _call(kernel, name, lay, operands, in_specs, out_spec, out_shape,
          n_tiles):
    visits = lay.group.shape[0]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=(n_tiles, visits),
            in_specs=in_specs, out_specs=out_spec),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        # what a trace finds the kernel by (docs/tracing.md#names)
        name=name,
    )(*lay, *operands)


def _rows_product(x, w, lay: Layout, transposed: bool):
    """``x @ w[g]`` (``w`` ``[G, K, N]``, ``x`` ``[M, K]``) or,
    ``transposed``, ``x @ w[g]^T`` (``x`` ``[M, N]``): ``[M, N]`` /
    ``[M, K]`` in ``x``'s type, zero beyond the real rows."""
    m, inner = x.shape
    g = w.shape[0]
    width = w.shape[1] if transposed else w.shape[2]
    tn = _width_tile(width)

    def block_of(v, group):
        return jnp.minimum(group[v], g - 1)

    if transposed:
        w_spec = pl.BlockSpec(
            (None, tn, inner),
            lambda n, v, group, *_: (block_of(v, group), n, 0))
    else:
        w_spec = pl.BlockSpec(
            (None, inner, tn),
            lambda n, v, group, *_: (block_of(v, group), 0, n))
    return _call(
        functools.partial(_rows_kernel, groups=g,
                          contract_w=1 if transposed else 0),
        "hvd_gmm_drows" if transposed else "hvd_gmm_fwd", lay, (x, w),
        [pl.BlockSpec((TILE, inner),
                      lambda n, v, group, tile, x_tile, *_: (x_tile[v], 0)),
         w_spec],
        pl.BlockSpec((TILE, tn),
                     lambda n, v, group, tile, *_: (tile[v], n)),
        jax.ShapeDtypeStruct((m, width), x.dtype), width // tn)


def _dw_product(x, dy, lay: Layout, groups: int):
    """``dw[g] = sum_{r in g} x[r]^T dy[r]``: ``[G, K, N]`` float32."""
    k, n = x.shape[1], dy.shape[1]
    tn = _width_tile(n)
    return _call(
        functools.partial(_dw_kernel, groups=groups), "hvd_gmm_dw", lay,
        (x, dy),
        [pl.BlockSpec((TILE, k),
                      lambda j, v, group, tile, x_tile, *_: (x_tile[v], 0)),
         pl.BlockSpec((TILE, tn),
                      lambda j, v, group, tile, x_tile, *_: (x_tile[v], j))],
        pl.BlockSpec(
            (None, k, tn),
            lambda j, v, group, *_: (jnp.minimum(group[v], groups - 1), 0,
                                     j)),
        jax.ShapeDtypeStruct((groups, k, n), jnp.float32), n // tn)


@jax.custom_vjp
def grouped_matmul(x, w, lay: Layout):
    """``y[r] = x[r] @ w[group of r]`` for the rows ``lay`` describes
    (``layout(group_sizes, x.shape[0])``); zero beyond the real rows.
    ``x`` ``[M, K]``, ``w`` ``[G, K, N]``; ``y`` ``[M, N]`` in ``x``'s
    type. Differentiable in ``x`` and ``w``."""
    return _rows_product(x, w, lay, False)


def _gmm_fwd(x, w, lay):
    return _rows_product(x, w, lay, False), (x, w, lay)


def _gmm_bwd(res, dy):
    x, w, lay = res
    dx = _rows_product(dy, w, lay, True)
    dw = _dw_product(x, dy, lay, w.shape[0]).astype(w.dtype)
    return dx, dw, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)
