"""``ops/grouped_matmul.py`` (the kernels interpreted on the CPU)
against a loop over the groups: values and both gradients, with empty
groups, all rows in one group, no row at all and a full buffer, and at
widths that 256 does not divide."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import grouped_matmul as gmm
from horovod_tpu.ops.grouped_matmul import TILE, grouped_matmul, layout

ROWS, K, N = 4 * TILE, 64, 256

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


def by_loop(x, w, sizes):
    """Each group's rows times its matrix, zero beyond the real rows."""
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    start = 0
    for g, size in enumerate(sizes):
        out = out.at[start:start + size].set(x[start:start + size] @ w[g])
        start += size
    return out


def operands(seed, sizes):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (ROWS, K), jnp.float32),
            jax.random.normal(kw, (len(sizes), K, N), jnp.float32) * K ** -0.5)


@pytest.mark.parametrize("name", sorted(CASES))
def test_values_match_a_loop_over_the_groups(name):
    sizes = CASES[name]
    x, w = operands(1, sizes)
    with jax.default_matmul_precision("highest"):
        got = grouped_matmul(x, w, layout(jnp.asarray(sizes), ROWS))
        want = by_loop(x, w, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", sorted(CASES))
def test_gradients_match_a_loop_over_the_groups(name):
    """``dx`` is zero beyond the real rows and ``dw`` zero for an empty
    group, whatever lies in the buffer there."""
    sizes = CASES[name]
    x, w = operands(2, sizes)
    probe = jax.random.normal(jax.random.PRNGKey(3), (ROWS, N))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda x, w: (grouped_matmul(
            x, w, layout(jnp.asarray(sizes), ROWS)) * probe).sum(),
            argnums=(0, 1))(x, w)
        want = jax.grad(lambda x, w: (by_loop(x, w, sizes) * probe).sum(),
                        argnums=(0, 1))(x, w)
    for a, b, what in zip(got, want, ("dx", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=what)


def test_the_visits_are_as_many_as_tiles_and_groups_whatever_the_sizes():
    """The grid is a function of the shapes: every routing gives the
    same number of visits, each tile is written and each group's block
    of ``dw`` is zeroed exactly once."""
    for sizes in CASES.values():
        lay = layout(jnp.asarray(sizes), ROWS)
        assert lay.group.shape == (ROWS // TILE + len(sizes),)
        tiles = np.asarray(lay.tile)[np.asarray(lay.first_of_tile) == 1]
        assert sorted(tiles.tolist()) == list(range(ROWS // TILE)), sizes
        assert int(np.asarray(lay.first_of_group).sum()) == len(sizes)
        assert (np.diff(np.asarray(lay.tile)) >= 0).all()


def test_bfloat16_rows_meet_float32_weights():
    sizes = CASES["boundaries_inside_tiles"]
    x, w = operands(4, sizes)
    got = grouped_matmul(x.astype(jnp.bfloat16), w,
                         layout(jnp.asarray(sizes), ROWS))
    assert got.dtype == jnp.bfloat16
    want = by_loop(x, w, sizes)
    err = np.linalg.norm(np.asarray(got, np.float32) - np.asarray(want))
    assert err / np.linalg.norm(np.asarray(want)) < 0.01
    dw = jax.grad(lambda w: grouped_matmul(
        x.astype(jnp.bfloat16), w,
        layout(jnp.asarray(sizes), ROWS)).astype(jnp.float32).sum())(w)
    assert dw.dtype == jnp.float32


def test_rows_must_fill_whole_tiles():
    with pytest.raises(ValueError):
        layout(jnp.asarray([1, 2]), TILE + 1)


def test_a_width_256_does_not_divide_takes_the_widest_block_of_128s():
    """2688 (the hybrid model's expert width) goes in 3 blocks of 896,
    not 21 of 128; values and both gradients hold at widths whose
    blocks are 384 (``K``, and ``N`` in 3 blocks)."""
    assert [gmm._width_tile(n) for n in (2688, 1152, 384, 1024, 2048, 24)] \
        == [896, 384, 384, 256, 256, 24]
    sizes = CASES["boundaries_inside_tiles"]
    kx, kw, kp = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(kx, (ROWS, 384), jnp.float32)
    w = jax.random.normal(kw, (len(sizes), 384, 1152), jnp.float32) * 0.05
    probe = jax.random.normal(kp, (ROWS, 1152))
    lay = layout(jnp.asarray(sizes), ROWS)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda x, w: (grouped_matmul(
            x, w, lay) * probe).sum(), argnums=(0, 1))(x, w)
        want = jax.value_and_grad(lambda x, w: (by_loop(
            x, w, sizes) * probe).sum(), argnums=(0, 1))(x, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-3)
