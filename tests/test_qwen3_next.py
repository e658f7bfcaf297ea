"""The linear-attention model's layers (models/qwen3_next.py,
ops/delta_rule.py, ops/grouped_matmul.py) against the plain reference
(benchmark/qwen3_next_reference.py, which imports nothing of the
program): each kind of layer forward and ``jax.grad``, the dropless
expert layer under a skewed routing, the share test of the
model-configs guide (section 4), what the layers' checkpoints hold, and
what a configuration refuses. Small widths, float32, seeded. The whole
model through ``build_train_step`` is in ``test_qwen3_next_train.py``."""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from qwen3_next_common import assert_trees_close, file_config, make_cfg, qn

from benchmark import qwen3_next_reference as ref
from horovod_tpu.models import expert_rows
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.train import (build_pipeline_train_step,
                                        build_train_step)

INIT = {"D": qn._init_gdn, "A": qn._init_attn, "E": qn._init_moe}
LAYER = {"D": qn._gdn_layer, "A": qn._attn_layer,
         # the expert layer also returns its router's choices
         "E": lambda p, x, cfg: qn._moe_layer(p, x, cfg)[0]}


def _layer_inputs(cfg, kind, seq, seed=0):
    key = jax.random.PRNGKey(seed)
    params = INIT[kind](cfg, key)
    # norms start at a scale of one (a weight of zero): move them, so
    # that a norm applied without its weight would show
    params = {name: p + 0.1 * jax.random.normal(
        jax.random.fold_in(key, i), p.shape) if "norm" in name else p
        for i, (name, p) in enumerate(sorted(params.items()))}
    x = jax.random.normal(jax.random.fold_in(key, 99),
                          (1, seq, cfg.d_model), jnp.float32)
    return params, x


def _reference_layer(kind, params, x, cfg, seq):
    d = ref.dims(file_config(cfg))
    if kind == "D":
        return ref.deltanet_layer(params, x, d)
    if kind == "A":
        return ref.attention_layer(params, x, d, seq)
    return ref.moe_layer(params, x, d)[0]


# the DeltaNet mixer also at lengths that end inside a chunk of 16 and
# in one group; attention through the interpreted flash kernels too
@pytest.mark.parametrize("kind,seq,over", [
    ("D", 64, {}), ("D", 37, {}), ("D", 8, {}), ("D", 48, {"gdn_groups": 1}),
    ("D", 32, {"gdn_groups": 4}),
    ("A", 48, {}), ("A", 128, {"use_flash": True}),
    ("A", 32, {"rotary_dim": 16}),
    ("E", 64, {}), ("E", 40, {"experts_held": (3, 9, 30)}),
    ("E", 256, {"top_k": 2}), ("E", 24, {"experts_held": tuple(range(32))}),
])
def test_layer_forward_and_gradients_match_the_reference(kind, seq, over):
    cfg = make_cfg(**over)
    params, x = _layer_inputs(cfg, kind, seq)
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def program(p, xx):
        return (LAYER[kind](p, xx, cfg) * probe).sum()

    def reference(p, xx):
        return (_reference_layer(kind, p, xx[0], cfg, seq) * probe[0]).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(
            params, x)
        want = jax.jit(jax.value_and_grad(reference, argnums=(0, 1)))(
            params, x)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    assert_trees_close(got[1], want[1])


def test_rotary_turns_the_first_dims_and_passes_the_rest():
    cfg = make_cfg(rotary_dim=8, head_dim=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    y = qn._rotary(x, cfg)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    np.testing.assert_allclose(y[:, 0], x[:, 0], rtol=1e-6)   # position 0
    # a rotation: each pair (j, j + 4) keeps its length
    np.testing.assert_allclose(
        y[..., :4] ** 2 + y[..., 4:8] ** 2,
        x[..., :4] ** 2 + x[..., 4:8] ** 2, rtol=1e-5)
    assert not np.allclose(y[:, 3, :, :8], x[:, 3, :, :8])


def test_no_row_is_dropped_however_skewed_the_routing():
    """A router that sends every token to one held expert (every token
    has a large first coordinate, which that expert's column weighs):
    the buffer holds the worst case, so the layer still computes every
    (token, held expert) pair, as the reference does."""
    cfg = make_cfg(top_k=2)
    params, x = _layer_inputs(cfg, "E", 256)
    x = x.at[..., 0].add(8.0)
    skewed = dict(params, router=params["router"].at[0, 3].set(10.0))
    d = ref.dims(file_config(cfg))
    for p in (params, skewed):
        got, idx = qn._moe_layer(p, x, cfg)
        want, ref_idx = ref.moe_layer(p, x[0], d)
        np.testing.assert_allclose(got[0], want, rtol=0, atol=3e-5)
        chosen = np.asarray(ref.rows(idx, cfg.experts_held))
        assert (chosen == np.asarray(
            ref.rows(ref_idx, cfg.experts_held))).all()
    assert chosen.sum(1)[3] == 256


def test_all_rows_for_one_expert_fill_the_buffer_and_none_is_lost():
    """Every token chooses every held expert: the plan's buffer is full
    to its last row and the layer is the reference's."""
    cfg = make_cfg(n_experts=4, experts_held=(0, 1, 2, 3), top_k=4)
    params, x = _layer_inputs(cfg, "E", 64)
    idx, weight = qn.route(qn._norm(x, params["norm"], cfg.eps)[0],
                           params["router"], cfg)
    local, hit = expert_rows.pair_table(idx, cfg.experts_held)
    _, row_weight, _, sizes, _ = expert_rows.plan_rows(local, hit, weight, 4)
    assert int(sizes.sum()) == expert_rows.buffer_rows(64, 4, 4) == 256
    assert bool(hit.all()) and float(row_weight.min()) > 0
    want, _ = ref.moe_layer(params, x[0], ref.dims(file_config(cfg)))
    np.testing.assert_allclose(LAYER["E"](params, x, cfg)[0], want,
                               rtol=0, atol=3e-5)


def _leaf_shapes(jaxpr, out=None):
    """``(primitive, shape)`` of every array an op of ``jaxpr`` reads or
    makes, calls' and checkpoints' bodies included; a Pallas kernel is
    one op (what it does inside stays inside)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        inner = [] if eqn.primitive.name == "pallas_call" else list(
            jax.core.jaxprs_in_params(eqn.params))
        for sub in inner:
            _leaf_shapes(sub, out)
        if not inner:
            out.extend((eqn.primitive.name, tuple(v.aval.shape))
                       for v in (*eqn.invars, *eqn.outvars)
                       if hasattr(v.aval, "shape"))
    return out


def test_no_pass_over_the_worst_case_rows_outside_the_kernels():
    """The expert layer, forward and ``jax.grad``: no op but a kernel
    reads or makes an array of the buffer's worst-case rows (``[rows,
    width]`` at any width, whole-tile rows included: the activation
    between the two products runs inside the kernels) or of the pairs'
    at the model's width (``[T, P, d]``): the tokens' rows and their
    cotangents are read by index inside the grouped-matmul kernels, and
    the sum back to the tokens is a kernel. What is left outside is the
    plan's integers, one a row."""
    cfg = make_cfg()
    params, x = _layer_inputs(cfg, "E", 64)
    t, d = 64, cfg.d_model
    p = min(cfg.top_k, len(cfg.experts_held))
    rows = expert_rows.buffer_rows(t, cfg.top_k, len(cfg.experts_held))
    grad = jax.grad(lambda p_, xx: LAYER["E"](p_, xx, cfg).sum(),
                    argnums=(0, 1))
    shapes = _leaf_shapes(jax.make_jaxpr(grad)(params, x).jaxpr)
    assert ("pallas_call", (rows, 1, d)) in shapes     # the kernels' own
    assert ("pallas_call", (rows, 2 * cfg.moe_ff)) in shapes
    outside = [(prim, s) for prim, s in shapes if prim != "pallas_call"
               and (len(s) > 1 and s[0] == rows
                    or s in {(t, p, d), (t * p, d)})]
    assert not outside, outside


def test_the_plan_sorts_the_held_pairs_by_expert():
    cfg = make_cfg(experts_held=(5, 2, 9), top_k=3)
    idx = jnp.asarray([[5, 1, 9], [2, 5, 7], [0, 1, 3], [9, 2, 5]])
    weight = jnp.arange(12, dtype=jnp.float32).reshape(4, 3) + 1.0
    local, hit = expert_rows.pair_table(idx, cfg.experts_held)
    row_token, row_weight, row_pair, sizes, bounds = expert_rows.plan_rows(
        local, hit, weight, 3)
    # held experts in the order they are listed: 5, then 2, then 9
    assert sizes.tolist() == [3, 2, 2]
    assert row_token[:7].tolist() == [0, 1, 3, 1, 3, 0, 3]
    assert row_weight[:7].tolist() == [1.0, 5.0, 12.0, 4.0, 11.0, 3.0, 10.0]
    assert (np.asarray(row_weight)[7:] == 0).all()
    assert hit.tolist() == [[True, False, True], [True, True, False],
                            [False] * 3, [True] * 3]
    assert row_pair[0] == 0 and row_pair[2] == 11 and row_pair[3] == 3
    # the pairs not held follow the held ones, in the order of the pairs
    assert row_pair[7] == 1 and row_pair[11] == 8
    assert (row_token == row_pair // 3).all()
    assert row_token.shape == (expert_rows.buffer_rows(4, 3, 3),) == (128,)
    # the 4 tokens are one tile of the sum back to them: each expert's
    # first row, then its end
    assert bounds.tolist() == [[0, 3, 5], [3, 5, 7]]


def test_where_a_token_chooses_more_experts_than_are_held_its_pairs_are_them():
    """The shared plan's other table (the hybrid cell's top-22 over 8
    held): two held experts under a top-3, a hand-built case. A token's
    pairs are the held experts, each weighted by the choice that named
    it; the rows are the same as the choices' table would give."""
    idx = jnp.asarray([[5, 1, 9], [2, 5, 7], [0, 1, 3], [9, 2, 5]])
    weight = jnp.arange(12, dtype=jnp.float32).reshape(4, 3) + 1.0
    local, hit = expert_rows.pair_table(idx, (5, 2))
    assert local.tolist() == [[0, 2], [0, 1], [2, 2], [0, 1]]
    table_weight = expert_rows.weights_of_choices(weight, idx, (5, 2))
    assert table_weight.tolist() == [[1, 0], [5, 4], [0, 0], [12, 11]]
    row_token, row_weight, row_pair, sizes, _ = expert_rows.plan_rows(
        local, hit, table_weight, 2)
    assert sizes.tolist() == [3, 2]
    assert row_token[:5].tolist() == [0, 1, 3, 1, 3]
    assert row_weight[:5].tolist() == [1.0, 5.0, 12.0, 4.0, 11.0]
    assert (np.asarray(row_weight)[5:] == 0).all()
    assert row_pair[:8].tolist() == [0, 2, 6, 3, 7, 1, 4, 5]
    assert row_token.shape == (expert_rows.buffer_rows(4, 3, 2),) == (128,)


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Sixteen chips hold 2 of 32 experts each: the routed parts every
    share computes, with the shared expert applied once, are the uncut
    reference's layer; every choice is some share's row."""
    whole = make_cfg(experts_held=tuple(range(32)))
    params, x = _layer_inputs(whole, "E", 64, seed=4)
    want, _ = ref.moe_layer(params, x[0], ref.dims(file_config(whole)))

    u = qn._norm(x, params["norm"], whole.eps)[0]
    routed = jnp.zeros_like(u)
    rows = 0
    for share in range(16):
        ids = (2 * share, 2 * share + 1)
        cfg = make_cfg(experts_held=ids)
        idx, weight = qn.route(u, params["router"], cfg)
        routed += qn.routed_experts(
            u, idx, weight, params["w_in"][jnp.asarray(ids)],
            params["w_out"][jnp.asarray(ids)], cfg)
        rows += int(ref.rows(idx, ids).sum())
    got = x[0] + routed + qn.shared_expert(params, u, whole)
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-5)
    assert rows == 64 * whole.top_k


def test_routing_weights_are_the_chosen_scores_over_their_sum():
    cfg = make_cfg()
    params, x = _layer_inputs(cfg, "E", 32)
    idx, weight = qn.route(x[0], params["router"], cfg)
    np.testing.assert_allclose(np.asarray(weight).sum(1), 1.0, rtol=1e-5)
    scores = jax.nn.softmax(x[0] @ params["router"], axis=-1)
    np.testing.assert_allclose(
        np.sort(np.asarray(scores), axis=1)[:, -cfg.top_k:][:, ::-1],
        np.asarray(jnp.take_along_axis(scores, idx, axis=1)), rtol=1e-5)


@pytest.mark.parametrize("axis", ["tp_axis", "sp_axis", "ep_axis"])
def test_a_bound_model_parallel_axis_is_refused_by_the_step_builder(axis):
    cfg = make_cfg(**{axis: axis[:2]})
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("dp", axis[:2]))
    with pytest.raises(ValueError, match=f"a bound {axis} is not built "
                                         "for Qwen3NextConfig"):
        build_train_step(cfg, mesh, optax.sgd(0.1))


@pytest.mark.parametrize("bad,why", [
    ({"experts_held": (1, 1)}, "distinct ids"),
    ({"experts_held": (40,)}, "distinct ids"),
    ({"top_k": 33}, "top_k"),
    ({"gdn_groups": 3}, "gdn_groups"),
    ({"gdn_value_heads": 6}, "gdn_value_heads"),
    ({"n_kv_heads": 3}, "n_kv_heads"),
    ({"rotary_dim": 5}, "rotary_dim"),
    ({"rotary_dim": 32}, "rotary_dim"),
    ({"n_layers": 0}, "n_layers"),
    ({"remat_policy": "some"}, "remat_policy"),
])
def test_a_config_that_cannot_be_is_refused(bad, why):
    with pytest.raises(ValueError, match=why):
        make_cfg(**bad)


def test_the_pattern_is_three_linear_layers_then_one_of_attention():
    assert make_cfg().mixers == "DDDADDDA"
    assert make_cfg(n_layers=5, full_attention_interval=2).mixers == "DADAD"
    cfg = make_cfg()
    params = cfg.init_params(jax.random.PRNGKey(0))
    assert ["wq" in layer["mixer"] for layer in params["layers"]] == [
        kind == "A" for kind in cfg.mixers]
    assert all("router" in layer["moe"] for layer in params["layers"])


def test_pipeline_is_refused_clearly_and_dp_shards_the_update():
    cfg = make_cfg(n_layers=2)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="pipeline train step is not "
                                         "built for Qwen3NextConfig"):
        build_pipeline_train_step(cfg, mesh, optax.sgd(0.1))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    opt = optax.adam(1e-3)
    make, _, _ = build_train_step(cfg, mesh, opt)
    params = cfg.init_params(jax.random.PRNGKey(0))
    _, specs = make(params, jax.eval_shape(opt.init, params))
    assert "dp" in specs[0].mu["embed"]


def _primitives(jaxpr, counts=None):
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _primitives(inner, counts)
    return counts


# layers, interval, and for some primitives how often the gradient's
# program runs them: with the layers' names held | with none held
HELD_CASES = [
    # a DeltaNet layer: the loop over the groups (the one scan left:
    # the delta rule's chunks are its kernels' grid) runs forward and
    # backward, and not forward again in between; the sum back to the
    # tokens (a kernel, forward and in the backward) loops over the held
    # experts, two scans more either way; its expert layer sorts the
    # plan once and not twice (the backward's sort of the weights'
    # cotangent runs either way), and runs top_k once
    (1, 2, {"scan": (4, 5), "sort": (2, 3), "top_k": (1, 2)}),
    # an attention layer holds nothing of its own
    (1, 1, {"sort": (2, 3), "top_k": (1, 2)}),
    (8, 4, {"sort": (16, 24), "top_k": (8, 16)}),
]


@pytest.mark.parametrize("layers,interval,runs", HELD_CASES, ids=[
    f"{layers}-layers-interval-{interval}" for layers, interval, _
    in HELD_CASES])
def test_what_a_layer_holds_changes_how_often_not_what(
        layers, interval, runs, monkeypatch):
    """A checkpoint that holds the layer's named arrays
    (``HELD_NAMES``) gives the gradient of the same layers with no
    checkpoint at all and of a checkpoint that holds nothing, bit for
    bit, and runs the groups, the sorts and the router's top-k once
    fewer."""
    from nemotron_h_common import hold_nothing
    cfg = make_cfg(n_layers=layers, full_attention_interval=interval)
    params = cfg.init_params(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab)

    def program(cfg):
        jax.clear_caches()
        grad = jax.grad(lambda p: cfg.loss_fn(p, tok, tok))
        return (_primitives(jax.make_jaxpr(grad)(params).jaxpr),
                jax.jit(grad)(params))

    held_runs, held = program(cfg)
    _, plain = program(dataclasses.replace(cfg, remat=False))
    hold_nothing(monkeypatch)
    bare_runs, bare = program(cfg)
    for other in (plain, bare):
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(held)[0],
                jax.tree_util.tree_leaves(other)):
            assert (np.asarray(a) == np.asarray(b)).all(), (
                jax.tree_util.keystr(path))
    for primitive, want in runs.items():
        assert (held_runs[primitive], bare_runs[primitive]) == want, primitive


def test_remat_block_wraps_the_new_layers():
    cfg = make_cfg(remat=False)
    assert tfm.remat_block(cfg, qn._gdn_layer) is qn._gdn_layer
    wrapped = tfm.remat_block(dataclasses.replace(cfg, remat=True),
                              qn._gdn_layer, static_argnums=(2,),
                              names=qn.HELD_NAMES)
    params, x = _layer_inputs(cfg, "D", 16)
    np.testing.assert_allclose(wrapped(params, x, cfg),
                               qn._gdn_layer(params, x, cfg), rtol=1e-6)
