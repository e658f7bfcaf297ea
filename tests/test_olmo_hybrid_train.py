"""The dense linear-attention hybrid (models/olmo_hybrid.py) through
``build_train_step``: three AdamW steps on a dp=1 and a dp=2 mesh match
three steps of the plain one-device update (``jax.value_and_grad`` of
``cfg.loss_fn`` and optax, no mesh), the losses fall, the first loss is
the reference's, and the step names its layers. The layers one by one
are in ``test_olmo_hybrid.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from olmo_hybrid_common import assert_trees_close, file_config, make_cfg, oh

from benchmark import olmo_hybrid_reference as ref
from horovod_tpu.parallel.train import build_train_step

STEPS = 3


def _batches(cfg, seq=32):
    # one batch of two sequences, repeated: the loss on it has to fall
    rng = np.random.default_rng(2)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (2, seq)), jnp.int32)
    return tok, jnp.roll(tok, -1, axis=1)


@pytest.fixture(scope="module")
def one_device():
    """Two layers (a DeltaNet mixer in three groups, attention), seeded
    weights, and three plain AdamW steps on one device: the losses and
    the parameters after them."""
    cfg = make_cfg(layer_types=(oh.LINEAR, oh.FULL), gdn_groups=3,
                   loss_chunk=16)
    params = cfg.init_params(jax.random.PRNGKey(4))
    tok, tgt = _batches(cfg)
    opt = optax.adamw(3e-3)

    @jax.jit
    def plain(p, state):
        loss, grads = jax.value_and_grad(cfg.loss_fn)(p, tok, tgt)
        updates, state = opt.update(grads, state, p)
        return optax.apply_updates(p, updates), state, loss

    p, state, losses = params, opt.init(params), []
    with jax.default_matmul_precision("highest"):
        for _ in range(STEPS):
            p, state, loss = plain(p, state)
            losses.append(float(loss))
    return cfg, params, tok, tgt, opt, losses, p


@pytest.mark.parametrize("dp", [1, 2])
def test_three_steps_through_build_train_step_are_the_one_device_steps(
        one_device, dp):
    cfg, params, tok, tgt, opt, want_losses, want = one_device
    mesh = Mesh(np.asarray(jax.devices()[:dp]), ("dp",))
    make, shard_params, shard_batch = build_train_step(cfg, mesh, opt)
    # the step donates: hand it a copy, the fixture keeps its own
    p = shard_params(jax.tree_util.tree_map(jnp.array, params))
    state = opt.init(p)
    step, _ = make(p, state)
    losses = []
    with jax.default_matmul_precision("highest"):
        for _ in range(STEPS):
            p, state, loss = step(p, state, shard_batch(tok),
                                  shard_batch(tgt))
            losses.append(float(loss))
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    assert losses[0] > losses[1] > losses[2]
    assert_trees_close(p, want, rtol=2e-3)


def test_the_first_loss_is_the_references(one_device):
    cfg, params, tok, tgt, _, losses, _ = one_device
    want = ref.loss(params, tok, tgt, file_config(cfg))
    np.testing.assert_allclose(losses[0], float(want), rtol=1e-5)


def test_the_step_names_the_new_layers(one_device):
    cfg, params, tok, tgt, _, _, _ = one_device
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    opt = optax.sgd(0.1)
    make, _, _ = build_train_step(cfg, mesh, opt)
    opt_state = opt.init(params)
    step, _ = make(params, opt_state)
    text = step.lower(params, opt_state, tok, tgt).as_text(debug_info=True)
    for name in ("hvd_embed", "hvd_gdn", "hvd_gdn_conv", "hvd_delta_rule",
                 "hvd_attn", "hvd_mlp", "hvd_loss_head", "hvd_optimizer",
                 "delta_rule_fwd", "delta_rule_bwd"):
        assert name in text, name
    for other in ("hvd_moe", "hvd_ssm", "hvd_gmm"):
        assert other not in text, other
