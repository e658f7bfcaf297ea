"""``ops/grouped_matmul.py`` (the kernels interpreted on the CPU): the
product that reads the tokens' rows by index and the product whose rows
are summed back to their tokens, against a loop over the groups (values
and gradients, with empty groups, all rows in one group, no row at all
and a full buffer, and at widths that 128 does not divide), and the
expert layer they make (``models/expert_rows.routed``) against a plain
``jnp`` version, for both tables of pairs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import expert_rows
from horovod_tpu.ops import grouped_matmul as gmm
from horovod_tpu.ops.grouped_matmul import TILE, layout

ROWS, K, N = 4 * TILE, 64, 256
TOKENS, P = 128, 4                    # ROWS pairs, one a row

# group sizes of four groups in a buffer of 4 tiles
CASES = {
    "boundaries_inside_tiles": [100, 37, 200, 50],
    "empty_groups": [0, 130, 0, 70],
    "all_rows_in_one_group": [0, 0, 300, 0],
    "last_group_alone": [0, 0, 0, 129],
    "no_rows": [0, 0, 0, 0],
    "full_buffer": [128, 1, 255, 128],
    "tile_aligned": [128, 128, 0, 128],
}


def plan_of(sizes, seed):
    """A table of ``TOKENS x P`` pairs whose plan gives each group
    ``sizes`` rows (the pairs drawn at random, the rest not held), with
    a weight a pair: the plan and the table."""
    g = len(sizes)
    local = np.full(TOKENS * P, g)
    order = np.random.default_rng(seed).permutation(TOKENS * P)
    at = 0
    for group, size in enumerate(sizes):
        local[order[at:at + size]] = group
        at += size
    local = jnp.asarray(local.reshape(TOKENS, P), jnp.int32)
    hit = local < g
    weight = jax.random.uniform(jax.random.PRNGKey(seed), (TOKENS, P),
                                minval=0.5, maxval=1.5)
    return expert_rows.plan_rows(local, hit, weight, g), local, hit, weight


def groups_of_rows(plan):
    """The group of each row, ``G`` for a row that is not real."""
    ends = np.cumsum(np.asarray(plan.group_sizes))
    return np.searchsorted(ends, np.arange(ROWS), side="right")


def _by_group(x, w, plan):
    """``x[r] @ w[g]`` row by row, a loop over the groups; zero on the
    rows that are not real."""
    groups = jnp.asarray(groups_of_rows(plan))[:, None]
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for g in range(w.shape[0]):
        out = jnp.where(groups == g, x @ w[g], out)
    return out


def gathered_by_loop(u, w, plan):
    """``h[r] = u[token[r]] @ w[g]`` on the real rows, zero elsewhere."""
    return _by_group(u[plan.row_token], w, plan)


def combined_by_loop(x, w, row_weight, plan):
    """``out[t] = sum_{r of t} row_weight[r] x[r] @ w[g]``."""
    rows = row_weight[:, None] * _by_group(x, w, plan)
    return jnp.zeros((TOKENS, w.shape[2]), jnp.float32).at[
        plan.row_token].add(rows)


def _same(x):
    """The activation of a product summed back to its tokens, where the
    test wants the bare product."""
    return x


def operands(seed, sizes, k=K, n=N):
    ku, kx, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ku, (TOKENS, k), jnp.float32),
            jax.random.normal(kx, (ROWS, k), jnp.float32),
            jax.random.normal(kw, (len(sizes), k, n), jnp.float32) * k ** -0.5)


def _mask(plan):
    return groups_of_rows(plan) < len(plan.group_sizes)


def _real(a, plan):
    return np.asarray(a)[_mask(plan)]


@pytest.mark.parametrize("name", sorted(CASES))
def test_values_match_a_loop_over_the_groups(name):
    """The real rows of the gathering product and the sums of the
    combining one; what lies beyond the real rows is not written."""
    sizes = CASES[name]
    plan = plan_of(sizes, 1)[0]
    u, x, w = operands(1, sizes)
    lay = layout(plan.group_sizes, ROWS)
    with jax.default_matmul_precision("highest"):
        got = gmm.gather_matmul(u, w, lay, plan.row_token, plan.bounds)
        want = gathered_by_loop(u, w, plan)
        out = gmm.matmul_combine(x, w, plan.row_weight, lay, plan.row_token,
                                 plan.bounds, TOKENS, _same)
        out_want = combined_by_loop(x, w, plan.row_weight, plan)
    np.testing.assert_allclose(_real(got, plan), _real(want, plan),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", sorted(CASES))
def test_gradients_match_a_loop_over_the_groups(name):
    """``d_u`` sums each token's rows, ``dw`` is zero for an empty group
    and the rows' weights get their cotangent on the real rows, whatever
    lies in the buffer beyond them."""
    sizes = CASES[name]
    plan = plan_of(sizes, 2)[0]
    u, x, w = operands(2, sizes)
    lay = layout(plan.group_sizes, ROWS)
    # a quarter of a normal: ``dw`` sums up to 300 rows and ``d_u`` up
    # to P of them, so their float32 rounding stays within the limits
    probe = 0.25 * jax.random.normal(jax.random.PRNGKey(3), (ROWS, N))
    probe = jnp.where(_mask(plan)[:, None], probe, 0.0)
    out_probe = 0.25 * jax.random.normal(jax.random.PRNGKey(4),
                                         (TOKENS, N))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda u, w: (gmm.gather_matmul(
            u, w, lay, plan.row_token, plan.bounds) * probe).sum(),
            argnums=(0, 1))(u, w)
        want = jax.grad(lambda u, w: (gathered_by_loop(u, w, plan)
                                      * probe).sum(), argnums=(0, 1))(u, w)
        got += jax.grad(lambda x, w, rw: (gmm.matmul_combine(
            x, w, rw, lay, plan.row_token, plan.bounds, TOKENS, _same)
            * out_probe).sum(), argnums=(0, 1, 2))(x, w, plan.row_weight)
        want += jax.grad(lambda x, w, rw: (combined_by_loop(x, w, rw, plan)
                                           * out_probe).sum(),
                         argnums=(0, 1, 2))(x, w, plan.row_weight)
    for a, b, what, rows in zip(got, want, ("du", "dw_in", "dx", "dw_out",
                                            "drow_weight"),
                                (False, False, True, False, True)):
        if rows:
            a, b = _real(a, plan), _real(b, plan)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=what)


def test_the_visits_are_as_many_as_tiles_and_groups_whatever_the_sizes():
    """The grid is a function of the shapes: every routing gives the
    same number of visits; each tile that holds real rows is written,
    none beyond them (tile 0 alone where there is no real row), and
    each group's block of ``dw`` is zeroed exactly once."""
    for sizes in CASES.values():
        lay = layout(jnp.asarray(sizes), ROWS)
        assert lay.group.shape == (ROWS // TILE + len(sizes),)
        tiles = np.asarray(lay.tile)[np.asarray(lay.first_of_tile) == 1]
        live = max(1, -(-sum(sizes) // TILE))
        assert sorted(tiles.tolist()) == list(range(live)), sizes
        assert int(np.asarray(lay.first_of_group).sum()) == len(sizes)
        assert (np.diff(np.asarray(lay.tile)) >= 0).all()


def test_bfloat16_rows_meet_float32_weights():
    sizes = CASES["boundaries_inside_tiles"]
    plan = plan_of(sizes, 4)[0]
    u, _, w = operands(4, sizes)
    lay = layout(plan.group_sizes, ROWS)
    got = gmm.gather_matmul(u.astype(jnp.bfloat16), w, lay, plan.row_token,
                            plan.bounds)
    assert got.dtype == jnp.bfloat16
    want = _real(gathered_by_loop(u, w, plan), plan)
    err = np.linalg.norm(_real(got, plan).astype(np.float32) - want)
    assert err / np.linalg.norm(want) < 0.01
    dw = jax.grad(lambda w: (gmm.gather_matmul(
        u.astype(jnp.bfloat16), w, lay, plan.row_token,
        plan.bounds).astype(jnp.float32)[_mask(plan)]).sum())(w)
    assert dw.dtype == jnp.float32


def test_rows_must_fill_whole_tiles():
    with pytest.raises(ValueError):
        layout(jnp.asarray([1, 2]), TILE + 1)


def test_a_width_256_does_not_divide_takes_the_widest_block_of_128s():
    """A kernel takes the whole width of its matrices in one block;
    rows it moves by index are whole-tile rows where 128 divides their
    width (2688, the hybrid model's expert width: 21 x 128) and one
    row of tiles where it does not. Values and every gradient hold at
    widths of 384 (``K``) and 1152 (``N``)."""
    assert [gmm._slab(n) for n in (2688, 1152, 384, 2048, 24)] == [
        (21, 128), (9, 128), (3, 128), (16, 128), (1, 24)]
    sizes = CASES["boundaries_inside_tiles"]
    plan = plan_of(sizes, 5)[0]
    u, x, w = operands(5, sizes, k=384, n=1152)
    w = w * 0.5
    lay = layout(plan.group_sizes, ROWS)
    probe = jax.random.normal(jax.random.PRNGKey(6), (TOKENS, 1152))

    def chain(u, w, rw, gather, combine):
        h = gather(u, w)
        return (combine(h[:, :384], w, rw) * probe).sum()

    def program(u, w, rw):
        return chain(
            u, w, rw,
            lambda u, w: gmm.gather_matmul(u, w, lay, plan.row_token,
                                           plan.bounds),
            lambda h, w, rw: gmm.matmul_combine(
                h, w, rw, lay, plan.row_token, plan.bounds, TOKENS, _same))

    def by_loop(u, w, rw):
        return chain(u, w, rw, lambda u, w: gathered_by_loop(u, w, plan),
                     lambda h, w, rw: combined_by_loop(h, w, rw, plan))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(program, argnums=(0, 1, 2))(
            u, w, plan.row_weight)
        want = jax.value_and_grad(by_loop, argnums=(0, 1, 2))(
            u, w, plan.row_weight)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, b in zip(got[1][:2], want[1][:2]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-3)
    np.testing.assert_allclose(_real(got[1][2], plan),
                               _real(want[1][2], plan), rtol=1e-4, atol=1e-3)


# The expert layer whole: held experts 3, 7, 1, 5 of 12, a token's
# choices of top_k; the table is the choices (P = top_k <= held) or the
# held experts (P = held < top_k)
HELD = (3, 7, 1, 5)
LAYER_T, LAYER_D, LAYER_F = 64, 256, 96


def _choices(kind, top_k):
    """``idx`` ``[LAYER_T, top_k]``, distinct experts of 12 a token,
    such that the held experts' groups are: ``empty_groups`` two held
    experts never chosen; ``every_row_real`` every pair a row (the
    buffer's worst case); ``no_row_real`` none; ``tile_shared`` random
    choices, groups that end inside tiles."""
    rng = np.random.default_rng(7)
    others = [e for e in range(12) if e not in HELD]
    pools = {"no_row_real": others,
             "empty_groups": [HELD[0], HELD[2]] + others,
             "tile_shared": list(range(12))}
    rows = []
    for _ in range(LAYER_T):
        if kind == "every_row_real":
            pick = list(rng.permutation(HELD))[:top_k]
            pick += list(rng.permutation(others))[:top_k - len(pick)]
        else:
            pick = list(rng.permutation(pools[kind]))[:top_k]
        rows.append(pick)
    return jnp.asarray(rows, jnp.int32)


def _act(h):
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f].astype(jnp.float32))
            * h[..., f:].astype(jnp.float32)).astype(h.dtype)


def _plain(u, idx, weight, w_in, w_out):
    out = jnp.zeros((u.shape[0], w_out.shape[2]), jnp.float32)
    for e, expert in enumerate(HELD):
        share = jnp.where(idx == expert, weight, 0.0).sum(1)
        out += share[:, None] * (_act(u @ w_in[e]) @ w_out[e])
    return out


@pytest.mark.parametrize("table", ["P=top_k", "P=held"])
@pytest.mark.parametrize("kind", ["empty_groups", "every_row_real",
                                  "no_row_real", "tile_shared"])
def test_the_layer_gathers_and_combines_by_index_as_plain_jnp(kind, table):
    """Values and the gradients of ``u``, ``w_in``, ``w_out`` and the
    weights, as the models call it (``pair_table``,
    ``weights_of_choices``, ``routed``)."""
    top_k = 2 if table == "P=top_k" else 6
    idx = _choices(kind, top_k)
    local, hit = expert_rows.pair_table(idx, HELD)
    assert local.shape[1] == min(top_k, len(HELD))
    ku, ki, ko, kw, kp = jax.random.split(jax.random.PRNGKey(8), 5)
    u = jax.random.normal(ku, (LAYER_T, LAYER_D))
    w_in = jax.random.normal(ki, (len(HELD), LAYER_D, 2 * LAYER_F)) * 0.06
    w_out = jax.random.normal(ko, (len(HELD), LAYER_F, LAYER_D)) * 0.1
    weight = jax.random.uniform(kw, idx.shape, minval=0.1, maxval=1.0)
    probe = jax.random.normal(kp, (LAYER_T, LAYER_D))
    if kind == "every_row_real":
        plan = expert_rows.plan_rows(
            local, hit, expert_rows.weights_of_choices(weight, idx, HELD),
            len(HELD))
        assert int(plan.group_sizes.sum()) == plan.row_token.shape[0]

    def program(u, w_in, w_out, weight):
        local, hit = expert_rows.pair_table(idx, HELD)
        table_weight = expert_rows.weights_of_choices(weight, idx, HELD)
        return (expert_rows.routed(u, local, hit, table_weight, w_in, w_out,
                                   _act) * probe).sum()

    def plain(u, w_in, w_out, weight):
        return (_plain(u, idx, weight, w_in, w_out) * probe).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(program, argnums=(0, 1, 2, 3))(
            u, w_in, w_out, weight)
        want = jax.value_and_grad(plain, argnums=(0, 1, 2, 3))(
            u, w_in, w_out, weight)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    for a, b, what in zip(got[1], want[1], ("u", "w_in", "w_out", "weight")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=what)
