"""The linear-attention model (models/qwen3_next.py) through
``build_train_step``: two periods' loss and gradients against the plain
reference on one device and on a dp=2 mesh, the reference's own walk a
layer at a time, and the names the step puts on its layers. The layers
one by one are in ``test_qwen3_next.py``."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from qwen3_next_common import assert_trees_close, file_config, make_cfg, qn

from benchmark import qwen3_next_reference as ref
from horovod_tpu.parallel.train import build_train_step


def _batch(cfg, batch, seq, seed=1):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32),
            jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32))


@pytest.fixture(scope="module")
def whole():
    """Eight layers (DDDA DDDA), seeded weights, one batch of 2 x 48
    tokens, and the reference's loss, gradients and routing on them
    (computed once: its one-step-at-a-time scan is slow to
    differentiate)."""
    cfg = make_cfg(loss_chunk=16)
    params = cfg.init_params(jax.random.PRNGKey(3))
    tok, tgt = _batch(cfg, 2, 48)
    with jax.default_matmul_precision("highest"):
        (loss, chosen), grads = jax.value_and_grad(
            lambda p: ref.loss(p, tok, tgt, file_config(cfg)),
            has_aux=True)(params)
    return cfg, params, tok, tgt, loss, grads, chosen


def test_whole_model_loss_and_gradients_match_the_reference(whole):
    cfg, params, tok, tgt, want_loss, want, ref_chosen = whole
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: cfg.loss_fn(p, tok, tgt)))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert_trees_close(grads, want)
    chosen = qn.routing_choices(params, tok, cfg)
    assert chosen.shape == (8, 96, cfg.top_k)
    held = cfg.experts_held
    assert ref.rows(chosen, held).shape == (8, 8, 96)
    assert (np.asarray(ref.rows(chosen, held))
            == np.asarray(ref.rows(ref_chosen, held))).all()


def test_the_choices_are_an_output_of_the_one_gradient_program(whole):
    """``loss_and_choices`` under ``value_and_grad(has_aux=True)``: the
    loss and gradient of ``loss_fn`` to the bit, and the choices of
    ``routing_choices``, from one jitted program (what the benchmark's
    comparison runs)."""
    cfg, params, tok, tgt, *_ = whole
    (loss, chosen), grads = jax.jit(jax.value_and_grad(
        lambda p: qn.loss_and_choices(p, tok, tgt, cfg),
        has_aux=True))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: cfg.loss_fn(p, tok, tgt)))(params)
    assert float(loss) == float(want_loss)
    assert_trees_close(grads, want, rtol=0, atol=0)
    assert chosen.dtype == jnp.int32
    assert (np.asarray(chosen)
            == np.asarray(qn.routing_choices(params, tok, cfg))).all()


@pytest.mark.parametrize("given", ["own", "other"])
def test_the_reference_layer_by_layer_is_its_own_gradient(whole, given):
    """``loss_and_grads`` (one ``jax.vjp`` a mixer or expert layer, what
    the benchmark can hold at the published widths) gives ``jax.grad``
    of ``loss``; handed its own choices as ``routing`` it gives the
    same, handed others it computes under THOSE and still reports its
    own."""
    cfg, params, tok, tgt, want_loss, want, ref_chosen = whole
    routing = np.asarray(ref_chosen)
    if given == "other":
        routing = (routing + 1) % cfg.n_experts
    loss, own, grads = ref.loss_and_grads(
        params, np.asarray(tok), np.asarray(tgt), file_config(cfg), routing)
    grads = dict(grads)
    assert list(grads) == [("head",), ("norm_f",)] + [
        ("layers", i, part) for i in reversed(range(8))
        for part in ("moe", "mixer")] + [("embed",)]
    # the first expert layer sees the same input either way; the later
    # ones see what the given choices made of it
    n_same = 8 if given == "own" else 1
    assert (np.asarray(own)[:n_same] == np.asarray(ref_chosen)[:n_same]).all()
    got = {"embed": grads["embed",], "head": grads["head",],
           "norm_f": grads["norm_f",],
           "layers": [{"mixer": grads["layers", i, "mixer"],
                       "moe": grads["layers", i, "moe"]} for i in range(8)]}
    if given == "own":
        np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
        assert_trees_close(got, want)
    else:
        assert abs(loss - float(want_loss)) > 1e-5 * float(want_loss)
        w_in = np.asarray(got["layers"][1]["moe"]["w_in"])
        assert not np.allclose(
            w_in, np.asarray(want["layers"][1]["moe"]["w_in"]),
            rtol=1e-2, atol=0)


@pytest.mark.parametrize("dp", [1, 2])
def test_one_sgd_step_through_build_train_step_is_the_reference_gradient(
        whole, dp):
    """``build_train_step(cfg, mesh, opt)`` -> one step of plain SGD:
    the loss is the reference's and the parameters move by the
    reference's gradient (at rate 64, so that float32 keeps the small
    leaves' movement), on one device and with the batch split over a
    dp=2 mesh."""
    cfg, params, tok, tgt, want_loss, want, _ = whole
    mesh = Mesh(np.asarray(jax.devices()[:dp]), ("dp",))
    opt = optax.sgd(64.0)
    make, shard_params, shard_batch = build_train_step(cfg, mesh, opt)
    before = jax.tree_util.tree_map(np.asarray, params)
    opt_state = opt.init(params)
    step, _ = make(params, opt_state)
    with jax.default_matmul_precision("highest"):
        # the step donates: hand it a copy, the fixture keeps its own
        new, _, loss = step(
            shard_params(jax.tree_util.tree_map(jnp.array, params)),
            opt_state, shard_batch(tok), shard_batch(tgt))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    moved = jax.tree_util.tree_map(
        lambda a, b: (a - np.asarray(b)) / 64.0, before, new)
    assert_trees_close(moved, want)


def test_bfloat16_activations_stay_near_the_float32_reference():
    """The cell's numerics at a small size: bfloat16 activations over
    float32 parameters, one DeltaNet and one attention layer at heads of
    32 (at heads of 8 the keys of a chunk are too alike for bfloat16).
    Under the PROGRAM's routing choices (as the benchmark's comparison
    takes the reference) loss and gradient stay within the noise of
    bfloat16 of the reference's."""
    cfg = make_cfg(dtype=jnp.bfloat16, d_model=64, gdn_key_dim=32,
                   gdn_value_dim=32, head_dim=32, rotary_dim=8, n_layers=2,
                   full_attention_interval=2, loss_chunk=16)
    params = cfg.init_params(jax.random.PRNGKey(3))
    tok, tgt = _batch(cfg, 1, 96)
    routing = np.asarray(qn.routing_choices(params, tok, cfg))
    want_loss, _, want = ref.loss_and_grads(
        params, np.asarray(tok), np.asarray(tgt), file_config(cfg), routing)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: cfg.loss_fn(p, tok, tgt)))(params)
    assert abs(float(loss) - want_loss) < 2e-3 * want_loss
    err = norm = 0.0
    for key, g in want:
        got = grads
        for k in key:
            got = got[k]
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(g)):
            err += float(((a - b) ** 2).sum())
            norm += float((b ** 2).sum())
    assert err / norm < 0.05 ** 2, (err / norm) ** 0.5


def test_the_step_names_the_new_layers():
    cfg = make_cfg(n_layers=2, full_attention_interval=2)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    opt = optax.sgd(0.1)
    make, _, _ = build_train_step(cfg, mesh, opt)
    params = cfg.init_params(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    step, _ = make(params, opt_state)
    tok, tgt = _batch(cfg, 1, 32)
    text = step.lower(params, opt_state, tok, tgt).as_text(debug_info=True)
    for name in ("hvd_embed", "hvd_gdn", "hvd_gdn_conv", "hvd_delta_rule",
                 "hvd_attn", "hvd_moe", "hvd_moe_router", "hvd_moe_dispatch",
                 "hvd_moe_shared", "hvd_gmm_fwd", "hvd_gmm_drows",
                 "hvd_gmm_dw", "hvd_loss_head", "hvd_optimizer"):
        assert name in text, name
    for other in ("hvd_mlp", "hvd_ssm", "hvd_moe_routed"):
        assert other not in text, other


def test_the_model_comes_through_the_door_the_others_use():
    source = inspect.getsource(build_train_step)
    assert "qwen3" not in source.lower()
    cfg = make_cfg()
    for attr in ("param_specs", "loss_fn", "init_params", "layouts",
                 "tp_axis", "sp_axis", "ep_axis"):
        assert hasattr(cfg, attr), attr
    assert cfg.layouts == ("dp",)
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(cfg.param_specs()) == \
        jax.tree_util.tree_structure(params)
