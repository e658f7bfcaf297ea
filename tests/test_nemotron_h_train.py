"""The hybrid model (models/nemotron_h.py) through ``build_train_step``:
the whole 11-layer pattern's loss and gradients against the plain
reference on one device and on a dp=2 mesh, the names the step puts on
its layers, and the door the step builder reaches a model by. The
layers one by one are in ``test_nemotron_h.py``."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from nemotron_h_common import (assert_trees_close, file_config, make_cfg,
                               nh)

from benchmark import nemotron_h_reference as ref
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import train as ptrain
from horovod_tpu.parallel.train import build_train_step


def _batch(cfg, batch, seq, seed=1):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32),
            jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32))


@pytest.fixture(scope="module")
def whole():
    """The 11-layer pattern, seeded weights, one batch of 2 x 48 tokens,
    and the reference's loss, gradients and routing on them (computed
    once: its one-step-at-a-time scan is slow to differentiate)."""
    cfg = make_cfg(loss_chunk=16)
    params = cfg.init_params(jax.random.PRNGKey(3))
    tok, tgt = _batch(cfg, 2, 48)
    with jax.default_matmul_precision("highest"):
        (loss, chosen), grads = jax.value_and_grad(
            lambda p: ref.loss(p, tok, tgt, file_config(cfg)),
            has_aux=True)(params)
    return cfg, params, tok, tgt, loss, grads, chosen


def test_whole_pattern_loss_and_gradients_match_the_reference(whole):
    cfg, params, tok, tgt, want_loss, want, ref_chosen = whole
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: cfg.loss_fn(p, tok, tgt)))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert_trees_close(grads, want)
    # the correction bias is a buffer: no gradient reaches it
    for layer, kind in zip(grads["layers"], cfg.pattern):
        if kind == "E":
            assert not np.asarray(layer["b_corr"]).any()
    chosen = nh.routing_choices(params, tok, cfg)
    assert chosen.shape == (5, 96, cfg.top_k)
    held = cfg.experts_held
    assert ref.pairs(chosen, held).shape == (5, 8, 96)
    assert (np.asarray(ref.pairs(chosen, held))
            == np.asarray(ref.pairs(ref_chosen, held))).all()


@pytest.mark.parametrize("given", ["own", "other"])
def test_the_reference_layer_by_layer_is_its_own_gradient(whole, given):
    """``loss_and_grads`` (one ``jax.vjp`` a layer, what the benchmark
    can hold at the published widths) gives ``jax.grad`` of ``loss``;
    handed its own choices as ``routing`` it gives the same, handed
    others it computes under THOSE and still reports its own."""
    cfg, params, tok, tgt, want_loss, want, ref_chosen = whole
    routing = np.asarray(ref_chosen)
    if given == "other":
        routing = (routing + 1) % cfg.n_routed_experts
    loss, own, grads = ref.loss_and_grads(
        params, np.asarray(tok), np.asarray(tgt), file_config(cfg), routing)
    grads = dict(grads)
    assert list(grads) == [("head",), ("norm_f",)] + [
        ("layers", i) for i in reversed(range(11))] + [("embed",)]
    # the first expert layer sees the same input either way; the later
    # ones see what the given choices made of it
    n_same = 5 if given == "own" else 1
    assert (np.asarray(own)[:n_same] == np.asarray(ref_chosen)[:n_same]).all()
    got = {"embed": grads["embed",], "head": grads["head",],
           "norm_f": grads["norm_f",],
           "layers": [grads["layers", i] for i in range(11)]}
    if given == "own":
        np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
        assert_trees_close(got, want)
    else:
        assert abs(loss - float(want_loss)) > 1e-4 * float(want_loss)
        w1 = np.asarray(got["layers"][1]["w1"])
        assert not np.allclose(w1, np.asarray(want["layers"][1]["w1"]),
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


def test_the_step_names_the_new_layers():
    cfg = make_cfg(pattern="ME*")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    opt = optax.sgd(0.1)
    make, _, _ = build_train_step(cfg, mesh, opt)
    params = cfg.init_params(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    step, _ = make(params, opt_state)
    tok, tgt = _batch(cfg, 1, 32)
    text = step.lower(params, opt_state, tok, tgt).as_text(debug_info=True)
    for name in ("hvd_embed", "hvd_ssm", "hvd_ssm_conv", "hvd_ssd_scan",
                 "hvd_attn", "hvd_moe", "hvd_moe_router", "hvd_moe_routed",
                 "hvd_moe_shared", "hvd_loss_head", "hvd_optimizer"):
        assert name in text, name
    assert "hvd_mlp" not in text


def test_both_models_come_through_the_same_door():
    """``build_train_step`` reaches a model by what its configuration
    provides, never by the model's name or type."""
    source = inspect.getsource(build_train_step)
    assert "nemotron" not in source.lower().replace(
        "models.nemotron_h.nemotronhconfig", "")
    assert "isinstance(cfg" not in source and "tfm." not in source
    for config in (make_cfg(pattern="M"), tfm.TransformerConfig()):
        for attr in ("param_specs", "loss_fn", "init_params", "layouts",
                     "tp_axis", "sp_axis", "ep_axis"):
            assert hasattr(config, attr), (type(config).__name__, attr)
    flagship = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64, max_seq=16)
    assert jax.tree_util.tree_structure(flagship.param_specs()) == \
        jax.tree_util.tree_structure(tfm.param_specs(flagship))
    assert "pp" in flagship.layouts
    assert ptrain._check_layout(flagship, "pp", "x") is None


