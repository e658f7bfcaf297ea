"""The hybrid model's layers (models/nemotron_h.py, ops/ssd_scan.py)
against the plain reference (benchmark/nemotron_h_reference.py, which
imports nothing of the program): each kind of layer forward and
``jax.grad``, the chunked scan and the convolution alone, the dropless
expert layer under a skewed routing, its routed part over sorted rows
against the dense formula, the share test of the model-configs guide
(section 4), and what a configuration refuses. Small widths, float32,
seeded. The whole pattern through ``build_train_step`` is in
``test_nemotron_h_train.py``."""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from nemotron_h_common import (PATTERN, assert_trees_close, file_config,
                               hold_nothing, make_cfg, nh)

from benchmark import nemotron_h_reference as ref
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.ssd_scan import causal_conv1d, ssd_scan
from horovod_tpu.parallel.train import (build_pipeline_train_step,
                                        build_train_step)


def _layer_inputs(cfg, kind, seq, seed=0):
    key = jax.random.PRNGKey(seed)
    params = nh._INIT[kind](cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, seq, cfg.d_model), jnp.float32)
    return params, x


def _reference_layer(kind, params, x, cfg, seq):
    d = ref.dims(file_config(cfg))
    if kind == "M":
        return ref.mamba_layer(params, x, d)
    if kind == "*":
        return ref.attention_layer(params, x, d, seq)
    return ref.moe_layer(params, x, d)[0]


# the Mamba-2 mixer also at a length that ends inside a chunk of 16
@pytest.mark.parametrize("kind,seq,over", [
    ("M", 64, {}), ("M", 37, {}), ("M", 8, {}),
    ("*", 48, {}), ("*", 128, {"use_flash": True}),
    ("E", 64, {}), ("E", 40, {"experts_held": (3, 9, 30)}),
    ("E", 256, {"top_k": 2}),
])
def test_layer_forward_and_gradients_match_the_reference(kind, seq, over):
    cfg = make_cfg(**over)
    params, x = _layer_inputs(cfg, kind, seq)
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def program(p, xx):
        return (nh._LAYER[kind](p, xx, cfg) * probe).sum()

    def reference(p, xx):
        return (_reference_layer(kind, p, xx[0], cfg, seq) * probe[0]).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(
            params, x)
        want = jax.jit(jax.value_and_grad(reference, argnums=(0, 1)))(
            params, x)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    assert_trees_close(got[1], want[1])


def test_no_pair_is_dropped_however_skewed_the_routing():
    """A correction bias that sends every token to one held expert: the
    layer still computes every (token, held expert) pair, as the
    reference does."""
    cfg = make_cfg(top_k=2)
    params, x = _layer_inputs(cfg, "E", 256)
    skewed = dict(params, b_corr=params["b_corr"].at[3].set(10.0))
    d = ref.dims(file_config(cfg))
    for p in (params, skewed):
        got = nh._moe_layer(p, x, cfg)
        idx, _ = nh.route(nh._rmsnorm(x, p["norm"], cfg.eps)[0],
                          p["router"], p["b_corr"], cfg)
        want, ref_idx = ref.moe_layer(p, x[0], d)
        np.testing.assert_allclose(got[0], want, rtol=0, atol=3e-5)
        chosen = np.asarray(ref.pairs(idx, cfg.experts_held))
        assert (chosen == np.asarray(
            ref.pairs(ref_idx, cfg.experts_held))).all()
    assert chosen.sum(1)[3] == 256


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips hold 8 of 32 experts each: the routed parts every
    share computes, with the latent projections and the shared expert
    applied once, are the uncut reference's layer."""
    whole = make_cfg(experts_held=tuple(range(32)))
    params, x = _layer_inputs(whole, "E", 64, seed=4)
    want, _ = ref.moe_layer(params, x[0], ref.dims(file_config(whole)))

    u = nh._rmsnorm(x, params["norm"], whole.eps)[0]
    h = u @ params["down"]
    routed = jnp.zeros_like(h)
    pairs = 0
    for share in range(4):
        ids = tuple(range(8 * share, 8 * share + 8))
        cfg = make_cfg(experts_held=ids)
        idx, weight = nh.route(u, params["router"], params["b_corr"], cfg)
        routed += nh.routed_experts(h, idx, weight,
                                    params["w1"][jnp.asarray(ids)],
                                    params["w2"][jnp.asarray(ids)], ids)
        pairs += int(ref.pairs(idx, ids).sum())
    shared = (nh._relu2(u @ params["shared_in"]) @ params["shared_out"])
    got = x[0] + routed @ params["up"] + shared
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-5)
    assert pairs == 64 * whole.top_k      # every choice is some share's


def _dense_routed(h, idx, weight, w1, w2, held):
    """The oracle: every token under every held expert, each term
    weighted by the routing's weight where the token chose the expert
    and by 0 elsewhere."""
    chosen = (idx[None, :, :] == jnp.asarray(held)[:, None, None]).any(-1)
    gate = jnp.where(chosen, weight, 0.0)                        # [held, T]
    terms = nh._relu2(jnp.einsum("tl,elf->etf", h, w1))
    terms = jnp.einsum("etf,efl->etl", terms, w2)
    return (gate[..., None] * terms).sum(0)


# held experts, top_k, experts, tokens, and how the choices are drawn:
# "free" at random; "one_empty" with no token choosing the first held
# expert; "all_held" with every token choosing every held expert, so
# each has T rows and the buffer is full to its worst case
ROUTED_CASES = [
    ((0, 3, 7, 9), 6, 32, 64, "free"),           # held < top_k, the cell's
    ((1, 2, 4, 5, 8, 11, 12, 14), 2, 16, 64, "free"),   # held > top_k
    ((0, 3, 7, 9), 3, 16, 64, "one_empty"),
    ((0, 1, 2), 5, 16, 128, "all_held"),
]


@pytest.mark.parametrize("held,top_k,experts,tokens,choices", ROUTED_CASES,
                         ids=[c[-1] + f"-held{len(c[0])}-k{c[1]}"
                              for c in ROUTED_CASES])
def test_routed_experts_over_sorted_rows_are_the_dense_formula(
        held, top_k, experts, tokens, choices):
    """The routed part over rows sorted by expert (the grouped-matmul
    kernels, interpreted) against every token under every held expert,
    forward and ``jax.grad`` in ``h``, the weights and both matrices."""
    keys = jax.random.split(jax.random.PRNGKey(len(held) * top_k), 6)
    lat, ff = 16, 24
    scores = jax.random.uniform(keys[0], (tokens, experts))
    if choices == "one_empty":
        scores = scores.at[:, held[0]].set(-1.0)
    if choices == "all_held":
        scores = scores.at[:, jnp.asarray(held)].add(2.0)
    _, idx = jax.lax.top_k(scores, top_k)
    chosen = (idx[None] == jnp.asarray(held)[:, None, None]).any(-1)
    weight = jnp.where(chosen, jax.random.uniform(
        keys[1], (len(held), tokens), minval=0.1), 0.0)
    h = jax.random.normal(keys[2], (tokens, lat))
    w1 = jax.random.normal(keys[3], (len(held), lat, ff)) * lat ** -0.5
    w2 = jax.random.normal(keys[4], (len(held), ff, lat)) * ff ** -0.5
    probe = jax.random.normal(keys[5], (tokens, lat))

    def loss(fn):
        return lambda *a: (fn(a[0], idx, a[1], a[2], a[3], held)
                           * probe).sum()

    rows = int(chosen.sum())
    if choices == "one_empty":
        assert not bool(chosen[0].any()) and rows > 0
    if choices == "all_held":
        assert rows == tokens * len(held)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss(nh.routed_experts),
                                         argnums=(0, 1, 2, 3)))(
            h, weight, w1, w2)
        want = jax.jit(jax.value_and_grad(loss(_dense_routed),
                                          argnums=(0, 1, 2, 3)))(
            h, weight, w1, w2)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    assert_trees_close(got[1], want[1])


def test_routing_weights_are_normalised_and_scaled():
    cfg = make_cfg(experts_held=tuple(range(32)))
    params, x = _layer_inputs(cfg, "E", 32)
    idx, weight = nh.route(x[0], params["router"], params["b_corr"], cfg)
    chosen = ref.pairs(idx, cfg.experts_held)
    assert (np.asarray(chosen).sum(0) == cfg.top_k).all()
    np.testing.assert_allclose(np.asarray(weight).sum(0),
                               cfg.routed_scaling, rtol=1e-5)
    assert (np.asarray(weight)[~np.asarray(chosen)] == 0).all()


@pytest.mark.parametrize("seq", [16, 21, 50])
def test_chunked_scan_is_the_recurrence(seq):
    """``ssd_scan`` against the recurrence one step at a time, at
    lengths that are and are not multiples of the chunk."""
    h, p, n = 3, 4, 5
    keys = jax.random.split(jax.random.PRNGKey(seq), 6)
    x = jax.random.normal(keys[0], (2, seq, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (2, seq, h)))
    a = -jnp.exp(jax.random.normal(keys[2], (h,)))
    b = jax.random.normal(keys[3], (2, seq, n))
    c = jax.random.normal(keys[4], (2, seq, n))
    d = jax.random.normal(keys[5], (h,))
    state = np.zeros((2, h, p, n))
    want = np.zeros((2, seq, h, p))
    for t in range(seq):
        decay = np.exp(np.asarray(dt[:, t] * a))[..., None, None]
        state = decay * state + np.einsum(
            "bh,bhp,bn->bhpn", dt[:, t], x[:, t], b[:, t])
        want[:, t] = (np.einsum("bhpn,bn->bhp", state, c[:, t])
                      + np.asarray(d)[:, None] * np.asarray(x[:, t]))
    got = ssd_scan(x, dt, a, b, c, d, chunk=16)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_conv_backward_is_the_derivative_of_its_forward():
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(keys[0], (2, 19, 6))
    w = jax.random.normal(keys[1], (6, 4))
    b = jax.random.normal(keys[2], (6,))
    probe = jax.random.normal(keys[3], (2, 19, 6))

    def plain(x, w, b):
        xp = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return sum(xp[:, j:j + 19] * w[:, j] for j in range(4)) + b

    np.testing.assert_allclose(causal_conv1d(x, w, b), plain(x, w, b),
                               rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda *a: (causal_conv1d(*a) * probe).sum(),
                   argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(lambda *a: (plain(*a) * probe).sum(),
                    argnums=(0, 1, 2))(x, w, b)
    assert_trees_close(got, want, rtol=1e-5)
    # causal: the first output sees the first input through the last tap
    first = causal_conv1d(x.at[:, 1:].set(0.0), w, jnp.zeros(6))[:, 0]
    np.testing.assert_allclose(first, x[:, 0] * w[:, 3], rtol=1e-5)


@pytest.mark.parametrize("axis", ["tp_axis", "sp_axis", "ep_axis"])
def test_a_bound_model_parallel_axis_is_refused_by_the_step_builder(axis):
    """One mechanism refuses every layout: ``cfg.layouts``, read by the
    step builders."""
    cfg = make_cfg(**{axis: axis[:2]})
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("dp", axis[:2]))
    with pytest.raises(ValueError, match=f"a bound {axis} is not built "
                                         "for NemotronHConfig"):
        build_train_step(cfg, mesh, optax.sgd(0.1))


@pytest.mark.parametrize("bad,why", [
    ({"pattern": "MXE"}, "a layer is one of"),
    ({"experts_held": (1, 1)}, "distinct ids"),
    ({"experts_held": (40,)}, "distinct ids"),
    ({"top_k": 33}, "top_k"),
    ({"mamba_groups": 3}, "mamba_groups"),
    ({"n_kv_heads": 3}, "n_kv_heads"),
])
def test_a_config_that_cannot_be_is_refused(bad, why):
    with pytest.raises(ValueError, match=why):
        make_cfg(**bad)


def test_pipeline_is_refused_clearly_and_dp_shards_the_update():
    """Of the layouts the model does not list the pipeline step says so;
    'dp', which it lists, brings the sharded weight update with it."""
    cfg = make_cfg(pattern="ME")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="pipeline train step is not "
                                         "built for NemotronHConfig"):
        build_pipeline_train_step(cfg, mesh, optax.sgd(0.1))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    opt = optax.adam(1e-3)
    make, _, _ = build_train_step(cfg, mesh, opt)
    params = cfg.init_params(jax.random.PRNGKey(0))
    _, specs = make(params, jax.eval_shape(opt.init, params))
    assert "dp" in specs[0].mu["embed"]


def test_remat_block_wraps_any_layer_function():
    cfg = make_cfg(remat=False)
    assert tfm.remat_block(cfg, nh._attn_layer) is nh._attn_layer
    flagship = tfm.TransformerConfig(remat=False)
    assert tfm.remat_block(flagship) is tfm._block
    wrapped = tfm.remat_block(dataclasses.replace(cfg, remat=True),
                              nh._attn_layer, static_argnums=(2,))
    params, x = _layer_inputs(cfg, "*", 16)
    np.testing.assert_allclose(wrapped(params, x, cfg),
                               nh._attn_layer(params, x, cfg))


def _primitives(jaxpr, counts=None):
    """How often each primitive stands in ``jaxpr``, loops' and
    checkpoints' bodies included."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _primitives(inner, counts)
    return counts


# pattern, policy, and for some primitives how often the gradient's
# program runs them: with the layers' names held | with none held
HELD_CASES = [
    # the groups (a scan over them, the chunk scan inside, each with a
    # backward) run twice a step and not three times: the layer's own
    # rerun of the map and of the scan in it is dead code
    ("M", "full", {"scan": (5, 7), "cumsum": (3, 4)}),
    ("M", "dots", {"scan": (5, 7)}),
    # the router's top-k, its matmul and its gather of the chosen
    # scores run once, not twice, and so does the row plan's sort (the
    # backward's sort of the weights' cotangent runs either way)
    ("E", "full", {"top_k": (1, 2), "sort": (2, 3), "dot_general": (28, 29),
                   "gather": (8, 10)}),
    ("E", "dots", {"top_k": (1, 2), "sort": (2, 3), "gather": (8, 10)}),
    ("*", "full", {}),                 # holds nothing: the same program
    # five expert layers: the sum back to the tokens (a kernel) loops
    # over the held experts, three scans a layer with or without names
    (PATTERN, "full", {"scan": (40, 50), "top_k": (5, 10),
                       "sort": (10, 15)}),
]


@pytest.mark.parametrize("pattern,policy,runs", HELD_CASES, ids=[
    f"{pattern}-{policy}" for pattern, policy, _ in HELD_CASES])
def test_what_a_layer_holds_changes_how_often_not_what(
        pattern, policy, runs, monkeypatch):
    """A layer's checkpoint that holds the layer's named arrays
    (``HELD_NAMES``) gives the gradient of the same layers with no
    checkpoint at all and of a checkpoint that holds nothing (the
    behaviour before the names), bit for bit, and runs the groups and
    the router's top-k once fewer. ``jax.checkpoint`` finds a traced
    layer again by its identity, hence ``jax.clear_caches()`` between
    the programs."""
    cfg = make_cfg(pattern=pattern, remat_policy=policy)
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
    if not runs:
        assert held_runs == bare_runs
