"""The dense linear-attention hybrid (models/olmo_hybrid.py over
models/gated_deltanet.py and ops/delta_rule.py) against the plain
reference (benchmark/olmo_hybrid_reference.py, which imports nothing of
the program): each kind of sublayer forward and ``jax.grad``, the whole
period's loss and every leaf's gradient in float32 and in bfloat16, the
reference's own walk a part at a time, every planted fault of the
benchmark's controls, and what a configuration refuses. Small widths
(heads of 12 / 24: no lane multiple, ``dk != dv``; three heads: no
block of 4 divides them), seeded. The model through
``build_train_step`` is in ``test_olmo_hybrid_train.py``."""

import contextlib
import inspect
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from olmo_hybrid_common import assert_trees_close, file_config, make_cfg, oh

from benchmark import controls_olmo_hybrid
from benchmark import olmo_hybrid_reference as ref
from benchmark.kinds import train_olmo_hybrid as kind
from horovod_tpu.models import gated_deltanet as gdn
from horovod_tpu.models import qwen3_next as qn
from horovod_tpu.parallel.train import (build_pipeline_train_step,
                                        build_train_step)

INIT = {oh.LINEAR: oh._init_gdn, oh.FULL: oh._init_attn,
        "mlp": oh._init_mlp}
LAYER = {oh.LINEAR: oh._gdn_layer, oh.FULL: oh._attn_layer,
         "mlp": oh._mlp_layer}


def _batch(cfg, batch, seq, seed=1):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32),
            jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32))


def _layer_inputs(cfg, kind_, seq, seed=0):
    key = jax.random.PRNGKey(seed)
    params = INIT[kind_](cfg, key)
    # norms start at a scale of one: move them, so that a norm applied
    # without its weight would show
    params = {name: p + 0.1 * jax.random.normal(
        jax.random.fold_in(key, i), p.shape) if "norm" in name else p
        for i, (name, p) in enumerate(sorted(params.items()))}
    x = jax.random.normal(jax.random.fold_in(key, 99),
                          (1, seq, cfg.d_model), jnp.float32)
    return params, x


# the DeltaNet mixer also at a length that ends inside a chunk of 16,
# in three groups of one head and without the factor 2; attention
# through the interpreted flash kernels too
@pytest.mark.parametrize("kind_,seq,over", [
    (oh.LINEAR, 37, {"gdn_groups": 3}),
    (oh.LINEAR, 32, {"allow_neg_eigval": False}),
    (oh.FULL, 48, {}), (oh.FULL, 128, {"use_flash": True}),
    ("mlp", 40, {}),
])
def test_sublayer_forward_and_gradients_match_the_reference(kind_, seq,
                                                            over):
    cfg = make_cfg(**over)
    params, x = _layer_inputs(cfg, kind_, seq)
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    d = ref.dims(file_config(cfg))

    def program(p, xx):
        return (LAYER[kind_](p, xx, cfg) * probe).sum()

    def reference(p, xx):
        return (ref._part(kind_, p, xx[0], d, seq) * probe[0]).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(
            params, x)
        want = jax.jit(jax.value_and_grad(reference, argnums=(0, 1)))(
            params, x)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    assert_trees_close(got[1], want[1])


@pytest.fixture(scope="module")
def whole():
    """The period (three DeltaNet layers, one of attention), seeded
    weights, one batch of 2 x 48 tokens, and the reference's loss and
    gradients on them (computed once: its one-position-at-a-time scan
    is slow to differentiate)."""
    cfg = make_cfg(loss_chunk=16)
    params = cfg.init_params(jax.random.PRNGKey(3))
    tok, tgt = _batch(cfg, 2, 48)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: ref.loss(p, tok, tgt, file_config(cfg)))(params)
    return cfg, params, tok, tgt, loss, grads


def test_whole_model_loss_and_every_leafs_gradient_match_the_reference(
        whole):
    cfg, params, tok, tgt, want_loss, want = whole
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: cfg.loss_fn(p, tok, tgt)))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert_trees_close(grads, want)


def test_the_reference_part_by_part_is_its_own_gradient(whole):
    """``loss_and_grads`` (one ``jax.vjp`` a mixer or MLP, what the
    benchmark can hold at the published widths) gives ``jax.grad`` of
    ``loss``, from the head down."""
    cfg, params, tok, tgt, want_loss, want = whole
    loss, grads = ref.loss_and_grads(
        params, np.asarray(tok), np.asarray(tgt), file_config(cfg))
    grads = dict(grads)
    assert list(grads) == [("head",), ("norm_f",)] + [
        ("layers", i, part) for i in reversed(range(4))
        for part in ("mlp", "mixer")] + [("embed",)]
    got = {"embed": grads["embed",], "head": grads["head",],
           "norm_f": grads["norm_f",],
           "layers": [{"mixer": grads["layers", i, "mixer"],
                       "mlp": grads["layers", i, "mlp"]} for i in range(4)]}
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert_trees_close(got, want)


@pytest.fixture(scope="module")
def small():
    """One DeltaNet layer and one of attention, what the comparison of
    the kind (``train_olmo_hybrid.against_reference``) needs, and the
    sound program's numbers."""
    # no layer checkpoint: ``jax.checkpoint`` finds a traced layer again
    # by its identity, and a fault planted in a module attribute would
    # not be seen (the controls clear every cache instead)
    cfg = make_cfg(layer_types=(oh.LINEAR, oh.FULL), loss_chunk=16,
                   remat=False)
    params = cfg.init_params(jax.random.PRNGKey(5))
    tok, tgt = _batch(cfg, 1, 32)
    config = file_config(cfg)
    with jax.default_matmul_precision("highest"):
        sound = kind.against_reference(cfg, config, params, tok, tgt)
    return cfg, config, params, tok, tgt, sound


def test_the_kinds_comparison_reads_a_sound_program_as_sound(small):
    cfg, _, _, _, _, sound = small
    assert sound["loss_rel"] < 1e-5
    assert sound["grad_rel"] < 1e-4 and sound["grad_rel_worst_leaf"] < 1e-3
    # every leaf: a DeltaNet mixer of 8, an attention mixer of 7, two
    # MLPs of 3, and the three ends
    assert len(sound["grad_rel_by_leaf"]) == 8 + 7 + 2 * 3 + 3
    assert all(kind.within(sound, 32).values())


PLANTED = ["beta_without_its_factor", "no_delta_term", "gate_left_out",
           "qk_norm_per_head", "norm_on_the_input"]


def test_the_controls_are_the_issues():
    assert list(controls_olmo_hybrid._controls()) == [
        "sound", "decays_bf16", "float32_parts_bf16"] + PLANTED


@pytest.mark.parametrize("fault", PLANTED)
def test_every_planted_fault_fails_the_cells_limits(small, fault):
    """Each fault of ``benchmark/controls_olmo_hybrid.py``, planted from
    outside as the control plants it, reads over the limit the cell's
    ``correct`` holds ``grad_rel`` to (and far over the sound program's
    float32 reading)."""
    cfg, config, params, tok, tgt, sound = small
    _, patch, change_cfg = controls_olmo_hybrid._controls()[fault]
    with (patch() if patch else contextlib.nullcontext()), \
            jax.default_matmul_precision("highest"):
        numbers = kind.against_reference(
            change_cfg(cfg) if change_cfg else cfg, config, params, tok, tgt)
    assert numbers["grad_rel"] > ref.tolerances(32)["grad_rel"]
    assert numbers["grad_rel"] > 100 * sound["grad_rel"]
    assert not all(kind.within(numbers, 32).values())


def test_bfloat16_activations_stay_within_the_stated_tolerance():
    """The cell's numerics at a small size: bfloat16 activations over
    float32 parameters, one DeltaNet layer at heads of 32 / 64 and one
    of attention (at heads of 12 the keys of a chunk are too alike for
    bfloat16). Loss and gradient stay within the noise of bfloat16 of
    the reference's: 5% here, at 64 tokens and a width of 96, against
    the cell's ``grad_rel`` limit at 16384 and 3840."""
    cfg = make_cfg(dtype=jnp.bfloat16, d_model=96, d_ff=128,
                   gdn_key_dim=32, gdn_value_dim=64,
                   layer_types=(oh.LINEAR, oh.FULL), loss_chunk=16)
    params = cfg.init_params(jax.random.PRNGKey(3))
    tok, tgt = _batch(cfg, 1, 64)
    numbers = kind.against_reference(cfg, file_config(cfg), params, tok, tgt)
    assert numbers["loss_rel"] < 2e-3
    assert numbers["grad_rel"] < 0.05, numbers["grad_rel_by_leaf"]
    assert numbers["grad_rel_worst_leaf"] < 0.6


def test_beta_reaches_two_and_the_transition_turns_negative():
    """``allow_neg_eigval``: the write strength the recurrence gets is
    ``2 sigmoid(b)``, in (0, 2), and ``sigmoid(b)`` without it; one
    value head a key head, each at its own width."""
    seen = {}

    def spy(q, k, v, g, beta, *, chunk):
        seen.update(beta=np.asarray(beta), q=q.shape, v=v.shape)
        return jnp.zeros_like(v)

    for allow, top in ((True, 2.0), (False, 1.0)):
        cfg = make_cfg(allow_neg_eigval=allow)
        p = oh._init_gdn(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (1, 32, 3 * cfg.gdn_head_width))
        b = 8.0 * jax.random.normal(jax.random.PRNGKey(2), (1, 32, 3))
        gdn.gdn_group(x, b, b, p["conv_w"], p["dt_bias"], p["A_log"],
                      p["gate_norm"], cfg, spy)
        assert cfg.gdn_beta_scale == top
        assert seen["beta"].min() >= 0.0 and seen["beta"].max() <= top
        assert seen["beta"].max() > 0.95 * top
        assert seen["q"] == (1, 32, 3, 12) and seen["v"] == (1, 32, 3, 24)


def test_one_mixer_serves_both_models():
    """The Gated DeltaNet group is ONE function: both models' layers go
    through ``gated_deltanet.gdn_mixer``, each with the recurrence its
    own module holds (so that a control can plant a fault in one)."""
    for module in (oh, qn):
        source = inspect.getsource(module._gdn_layer)
        assert "gdn.gdn_mixer(" in source and "_recurrence" in source
        assert "delta_rule(" in inspect.getsource(module._recurrence)
        assert "causal_conv1d" not in inspect.getsource(module)
    calls = []
    cfg = make_cfg()
    params, x = _layer_inputs(cfg, oh.LINEAR, 16)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(oh, "delta_rule", lambda q, k, v, g, beta, *, chunk:
                      calls.append("oh") or jnp.zeros_like(v))
        patch.setattr(qn, "delta_rule", lambda *a, **kw: calls.append("qn"))
        oh._gdn_layer(params, x, cfg)
    assert calls == ["oh"]
    assert qn.HELD_NAMES[0] == gdn.HELD_NAME


def test_parameter_counts_at_the_published_widths():
    cfg = oh.OlmoHybridConfig(
        vocab=12544, d_model=3840, d_ff=11008, gdn_key_heads=30,
        gdn_value_heads=30, gdn_key_dim=96, gdn_value_dim=192, n_heads=30,
        gdn_groups=5, chunk=64)
    shapes = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))

    def count(tree):
        return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))

    linear, full = shapes["layers"][0], shapes["layers"][3]
    # W_q, W_k 3840 x 2880, W_v, W_g 3840 x 5760, W_o 5760 x 3840, W_a,
    # W_b 3840 x 30, convolution 11520 x 4, dt_bias, A_log, the head
    # norm of 192 and the norm on the output
    assert count(linear["mixer"]) == (
        2 * 3840 * 2880 + 2 * 3840 * 5760 + 5760 * 3840 + 2 * 3840 * 30
        + 11520 * 4 + 30 + 30 + 192 + 3840)
    assert round(count(linear) / 1e6, 2) == 215.57
    assert round(count(full) / 1e6, 2) == 185.81
    assert round(count(shapes) / 1e6, 1) == 928.9
    assert cfg.head_dim == 128 and cfg.gdn_head_width == 576


def test_what_a_configuration_refuses():
    with pytest.raises(ValueError, match="layer_types"):
        make_cfg(layer_types=("linear_attention", "sliding_attention"))
    with pytest.raises(ValueError, match="layer_types"):
        make_cfg(layer_types=())
    with pytest.raises(ValueError, match="gdn_groups"):
        make_cfg(gdn_groups=2)
    with pytest.raises(ValueError, match="n_heads"):
        make_cfg(n_heads=5)
    with pytest.raises(ValueError, match="remat_policy"):
        make_cfg(remat_policy="some")


def test_the_model_comes_through_the_door_the_others_use():
    source = inspect.getsource(build_train_step)
    assert "olmo" not in source.lower()
    cfg = make_cfg()
    for attr in ("param_specs", "loss_fn", "init_params", "layouts",
                 "tp_axis", "sp_axis", "ep_axis"):
        assert hasattr(cfg, attr), attr
    assert cfg.layouts == ("dp",)
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(cfg.param_specs()) == \
        jax.tree_util.tree_structure(params)
    # the other stages of its pipeline are not built
    import optax
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="dp"):
        build_pipeline_train_step(cfg, mesh, optax.sgd(0.1))
