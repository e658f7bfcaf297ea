"""An expert layer's held experts over ROWS SORTED BY EXPERT: the ONE
row plan and the one routed computation the models with expert layers
share (``models/qwen3_next.py``, ``models/nemotron_h.py``).

A layer is told which experts it holds (one chip's share of an
expert-parallel layer); a token has a row for each (token, held expert)
pair it made, the held experts' rows first by expert, in a buffer of the
worst case, ``T x min(top_k, held)`` rows: a token chooses an expert at
most once, so that many rows hold any routing and nothing is ever
dropped. ``ops/grouped_matmul.py``'s kernels run the experts' matrices
over the buffer, read the tokens' rows and their cotangents BY INDEX
(no copy of them is made in the buffer), run the activation between
the two products on the tiles they hold, sum the rows back to their
tokens, and skip the rows that are not real. What runs outside the
kernels (the plan: one sort of the pairs and their counts a tile of
tokens; the weights' cotangent, a sort of the rows by their pair) has
static shapes and no loop whose trip count is data: only what a kernel
skips follows the routing.

What a token's pairs are is a TABLE of ``P = min(top_k, held)`` columns
(``pair_table``), the one shape that adapts: where a token chooses no
more experts than are held, its choices ``[T, top_k]``; where it
chooses more (a top-22 over 8 held experts), the held experts
``[T, held]``, since at most that many of its choices can be held.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import grouped_matmul as gmm

# Checkpoint names of the plan (``plan_rows``), which the models' layers
# HOLD (their ``HELD_NAMES``): small integers, well under 2 MB a layer,
# so the backward runs the plan's sort again nowhere.
NAMES = ("hvd_moe_row_token", "hvd_moe_row_weight", "hvd_moe_row_pair",
         "hvd_moe_group_sizes", "hvd_moe_row_bounds")


class Plan(NamedTuple):
    """Where the pairs of a table lie in the buffer (``plan_rows``)."""
    row_token: jax.Array      # [rows] the token each row copies
    row_weight: jax.Array     # [rows] float32, 0 where not real
    row_pair: jax.Array       # [rows] the pair each row is
    group_sizes: jax.Array    # [held] the rows each held expert got
    bounds: jax.Array         # [T / token_tile + 1, held]


def buffer_rows(tokens: int, top_k: int, held: int) -> int:
    """Rows of the sorted buffer: the worst case, a row for every
    (token, held expert) pair a token can make, in whole tiles."""
    pairs = tokens * min(top_k, held)
    return -(-pairs // gmm.TILE) * gmm.TILE


def pair_table(idx, held):
    """The pairs of the tokens that chose ``idx`` ``[T, top_k]`` (expert
    ids) among the experts ``held`` (ids, in the order of the layer's
    weights), as a table ``[T, P]``, ``P = min(top_k, len(held))``:
    ``local``, the held expert of each pair by its place in ``held``
    (``len(held)`` where the pair is not held here), and ``hit``,
    whether it is. Where ``top_k <= len(held)`` the columns are the
    token's choices; else they are the held experts, column ``e`` a
    pair wherever the token chose ``held[e]``."""
    held = jnp.asarray(held, idx.dtype)
    n_held = held.shape[0]
    if idx.shape[1] <= n_held:
        match = idx[..., None] == held                    # [T, k, held]
        hit = match.any(-1)
        return jnp.where(hit, jnp.argmax(match, -1), n_held), hit
    hit = (idx[..., None] == held).any(1)                 # [T, held]
    return jnp.where(hit, jnp.arange(n_held), n_held), hit


def weights_of_choices(weight, idx, held):
    """The table's weights (``pair_table``) from a weight a choice,
    ``weight`` ``[T, top_k]`` beside ``idx``: the weights themselves
    where the columns are the choices, else each held expert's the
    weight of the choice that named it (0 where none did)."""
    if idx.shape[1] <= len(held):
        return weight
    match = idx[..., None] == jnp.asarray(held, idx.dtype)
    return jnp.where(match, weight[..., None], 0.0).sum(1)


def weights_of_experts(weight, local, top_k):
    """The table's weights (``pair_table``) from a weight a held expert,
    ``weight`` ``[T, held]`` (0 where not chosen): the weights
    themselves where the columns are the held experts, else each
    choice's its expert's (any where the choice is not held: such a
    pair has no row)."""
    n_held = weight.shape[1]
    if top_k > n_held:
        return weight
    return jnp.take_along_axis(weight, jnp.minimum(local, n_held - 1), 1)


def plan_rows(local, hit, weight, n_held: int) -> Plan:
    """Where each pair of the table ``local`` / ``hit`` ``[T, P]``
    (``pair_table``) lies in the buffer of rows sorted by held expert,
    all of it integers (and one float that is not differentiated) of
    static shape, from ONE sort of the pairs:

    - ``row_pair`` and ``row_token``: the pair and the token each row is
      (the held pairs first, by expert and then by pair; the pairs not
      held after them, and beyond the buffer's end never made);
    - ``row_weight``: the pair's ``weight`` ``[T, P]``;
    - ``group_sizes``: the rows each held expert got;
    - ``bounds``: for each tile of tokens of the sum back to the tokens
      (``grouped_matmul.token_tile``) and each held expert, its first
      row whose token is in that tile or after (a count of its pairs in
      the tiles before); the last line is each expert's end."""
    t, k = local.shape
    local = local.reshape(-1).astype(jnp.int32)
    pairs = t * k
    bits = max(1, (pairs - 1).bit_length())
    if (n_held + 1) << bits >= 2 ** 31:
        raise ValueError("too many (token, choice) pairs for one int32 key")
    pair = jnp.arange(pairs, dtype=jnp.int32)
    # the sort of (held expert, pair) as one integer: held experts are
    # few, so the key fits beside the pair's index
    key = (local << bits) | pair
    w_flat = lax.stop_gradient(jnp.where(hit, weight, 0.0)).reshape(-1)
    key, row_weight = lax.sort((key, w_flat), num_keys=1)
    row_pair = key & ((1 << bits) - 1)
    tt = gmm.token_tile(t)
    per_tile = (local.reshape(t // tt, tt * k, 1)
                == jnp.arange(n_held)).sum(1, dtype=jnp.int32)
    sizes = per_tile.sum(0)
    before = jnp.cumsum(per_tile, axis=0) - per_tile
    bounds = (jnp.cumsum(sizes) - sizes)[None] + jnp.concatenate(
        [before, sizes[None]])
    rows = buffer_rows(t, k, n_held)
    row_pair, row_weight = (
        jnp.pad(x, (0, rows - pairs)) if rows > pairs else x[:rows]
        for x in (row_pair, row_weight))
    return Plan(*(checkpoint_name(x, name) for x, name in zip(
        (row_pair // k, row_weight, row_pair, sizes, bounds), NAMES)))


@jax.custom_vjp
def _weights_of_rows(weight, row_weight, row_pair, hit):
    """``row_weight`` (the rows' weights, ``plan_rows``) as a function
    of the table's ``weight`` ``[T, P]``: its cotangent goes back to the
    pairs by a sort of the rows by their pair, which the chip runs
    several times faster than a gather of scalars."""
    return row_weight


def _weights_of_rows_fwd(weight, row_weight, row_pair, hit):
    return row_weight, (row_pair, hit)


def _weights_of_rows_bwd(res, d_row_weight):
    row_pair, hit = res
    pairs = hit.size
    _, d_weight = lax.sort((row_pair[:pairs], d_row_weight[:pairs]),
                           num_keys=1)
    # rows beyond the real ones hold what no kernel wrote: where, not a
    # product with zero
    return (jnp.where(hit, d_weight.reshape(hit.shape), 0.0), None, None,
            None)


_weights_of_rows.defvjp(_weights_of_rows_fwd, _weights_of_rows_bwd)


def routed(u, local, hit, weight, w_in, w_out, act):
    """The held experts' terms ``sum_p weight[t, p] act(u[t] W_in,e)
    W_out,e`` (``e`` the held expert of pair ``p``) of ``u`` ``[T, d]``,
    for the table ``local`` / ``hit`` / ``weight`` ``[T, P]``, over
    rows sorted by expert: the plan under the scope
    ``hvd_moe_dispatch``; the first product reads ``u``'s rows by index
    (``grouped_matmul.gather_matmul``), the second sums its rows back
    to the tokens (``matmul_combine``: the sum ``moe_combine`` under
    ``hvd_moe_dispatch``), both products under their own kernel names
    (``w_in`` ``[held, d, f]``, ``w_out`` ``[held, f', N]``, read in
    their own type and cast inside the kernels), ``act`` (``[., f] ->
    [., f']``, elementwise along the rows) inside the second product's
    kernels, forward and backward. Float32 ``[T, N]``: products
    accumulate in float32 and the weighted sum back is float32."""
    with jax.named_scope(gmm.DISPATCH):
        plan = plan_rows(local, hit, weight, w_in.shape[0])
        lay = gmm.layout(plan.group_sizes, plan.row_token.shape[0])
        row_weight = _weights_of_rows(weight, plan.row_weight,
                                      plan.row_pair, hit)
    h = gmm.gather_matmul(u, w_in, lay, plan.row_token, plan.bounds)
    return gmm.matmul_combine(h, w_out, row_weight, lay, plan.row_token,
                              plan.bounds, u.shape[0], act)
