"""An expert layer's held experts over ROWS SORTED BY EXPERT: the ONE
row plan and pair of gathers the models with expert layers share
(``models/qwen3_next.py``, ``models/nemotron_h.py``).

A layer is told which experts it holds (one chip's share of an
expert-parallel layer); a token has a row for each (token, held expert)
pair it made, the held experts' rows first by expert, in a buffer of the
worst case, ``T x min(top_k, held)`` rows: a token chooses an expert at
most once, so that many rows hold any routing and nothing is ever
dropped. ``ops/grouped_matmul.py``'s kernels run the experts' matrices
over the buffer and skip the rows that are not real. Everything else
that passes over the rows (the sorts, the gathers, the activation
between the kernels, the weighted sum back to the tokens, their
backwards) has the buffer's static shape and no loop whose trip count
is data: its time is a function of the shapes, not of the routing.

What a token's pairs are is a TABLE of ``P = min(top_k, held)`` columns
(``pair_table``), the one shape that adapts: where a token chooses no
more experts than are held, its choices ``[T, top_k]``; where it
chooses more (a top-22 over 8 held experts), the held experts
``[T, held]``, since at most that many of its choices can be held.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import grouped_matmul as gmm

# Checkpoint names of the plan (``plan_rows``), which the models' layers
# HOLD (their ``HELD_NAMES``): small integers of two sorts, well under
# 2 MB a layer, so the backward runs neither sort again.
NAMES = ("hvd_moe_row_token", "hvd_moe_row_weight", "hvd_moe_pair_row",
         "hvd_moe_group_sizes")


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


def plan_rows(local, hit, weight, n_held: int):
    """Where each pair of the table ``local`` / ``hit`` ``[T, P]``
    (``pair_table``) lies in the buffer of rows sorted by held expert,
    all of it integers (and one float that is not differentiated) of
    static shape:

    - ``row_token`` ``[rows]``: the token whose copy each row is (the
      held pairs first, by expert and then by pair; the pairs not held
      after them, and beyond the buffer's end never made);
    - ``row_weight`` ``[rows]`` float32: the pair's ``weight`` ``[T,
      P]``, 0 for a row that is not real;
    - ``pair_row`` ``[T, P]``: the row of each pair;
    - ``group_sizes`` ``[n_held]``: the rows each held expert got."""
    t, k = local.shape
    local = local.reshape(-1)
    pairs = t * k
    bits = max(1, (pairs - 1).bit_length())
    if (n_held + 1) << bits >= 2 ** 31:
        raise ValueError("too many (token, choice) pairs for one int32 key")
    pair = jnp.arange(pairs, dtype=jnp.int32)
    # ONE sort of (held expert, pair) as one integer: held experts are
    # few, so the key fits beside the pair's index
    key = (local.astype(jnp.int32) << bits) | pair
    w_flat = lax.stop_gradient(jnp.where(hit, weight, 0.0)).reshape(-1)
    key, row_weight = lax.sort((key, w_flat), num_keys=1)
    order = key & ((1 << bits) - 1)                       # row -> pair
    _, pair_row = lax.sort((order, pair), num_keys=1)     # pair -> row
    group_sizes = (local[None, :] == jnp.arange(n_held)[:, None]).sum(1)
    rows = buffer_rows(t, k, n_held)
    row_token, row_weight = (
        jnp.pad(x, (0, rows - pairs)) if rows > pairs else x[:rows]
        for x in (order // k, row_weight))
    return (checkpoint_name(row_token, NAMES[0]),
            checkpoint_name(row_weight, NAMES[1]),
            checkpoint_name(jnp.minimum(pair_row, rows - 1).reshape(t, k),
                            NAMES[2]),
            checkpoint_name(group_sizes.astype(jnp.int32), NAMES[3]))


@jax.custom_vjp
def _to_rows(u, row_token, pair_row, hit):
    """``u`` ``[T, d]`` copied into the buffer: row ``r`` is token
    ``row_token[r]``. Its backward is the gather the other way (each
    token sums the rows of its held pairs), not a scatter."""
    return u[row_token]


def _to_rows_fwd(u, row_token, pair_row, hit):
    return u[row_token], (pair_row, hit)


def _to_rows_bwd(res, d_rows):
    pair_row, hit = res
    d_u = jnp.where(hit[..., None], d_rows[pair_row], 0).sum(
        1, dtype=jnp.float32)
    return d_u.astype(d_rows.dtype), None, None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _from_rows(y, weight, row_token, row_weight, pair_row, hit):
    """The weighted sum back to the tokens: ``out[t] = sum_p weight[t,
    p] y[pair_row[t, p]]`` over the held pairs, float32. Its backward
    towards the rows is again a gather: row ``r`` gets ``row_weight[r]``
    times its token's cotangent (0 for a row that is not real)."""
    picked = jnp.where(hit[..., None], y[pair_row], 0).astype(jnp.float32)
    return (picked * weight[..., None]).sum(1)


def _from_rows_fwd(y, weight, row_token, row_weight, pair_row, hit):
    return (_from_rows(y, weight, row_token, row_weight, pair_row, hit),
            (y, row_token, row_weight, pair_row, hit))


def _from_rows_bwd(res, d_out):
    y, row_token, row_weight, pair_row, hit = res
    # ONE gather of the tokens' cotangents serves both: the rows' own
    # cotangent and, row by row against y, the weights'
    d_rows = d_out.astype(y.dtype)[row_token]
    d_y = (d_rows * row_weight[:, None]).astype(y.dtype)
    d_row_weight = (y.astype(jnp.float32) * d_rows).sum(-1)
    d_weight = jnp.where(hit, d_row_weight[pair_row], 0.0)
    return d_y, d_weight, None, None, None, None


_from_rows.defvjp(_from_rows_fwd, _from_rows_bwd)


def routed(u, local, hit, weight, w_in, w_out, act):
    """The held experts' terms ``sum_p weight[t, p] act(u[t] W_in,e)
    W_out,e`` (``e`` the held expert of pair ``p``) of ``u`` ``[T, d]``,
    for the table ``local`` / ``hit`` / ``weight`` ``[T, P]``, over
    rows sorted by expert: the plan and the gathers under the scope
    ``hvd_moe_dispatch``, the two grouped matmuls (``w_in`` ``[held, d,
    f]``, ``w_out`` ``[held, f', N]``, read in their own type and cast
    inside the kernels) under their own kernel names, ``act`` under the
    caller's scope. Float32 ``[T, N]``: products accumulate in float32
    and the weighted sum back is float32."""
    with jax.named_scope("hvd_moe_dispatch"):
        row_token, row_weight, pair_row, sizes = plan_rows(
            local, hit, weight, w_in.shape[0])
        lay = gmm.layout(sizes, row_token.shape[0])
        rows = _to_rows(u, row_token, pair_row, hit)
    y = gmm.grouped_matmul(act(gmm.grouped_matmul(rows, w_in, lay)), w_out,
                           lay)
    with jax.named_scope("hvd_moe_dispatch"):
        return _from_rows(y, weight, row_token, row_weight, pair_row, hit)
