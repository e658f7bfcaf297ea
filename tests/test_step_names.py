"""The measured path names itself (docs/tracing.md#names): the in-jit
step's scopes and the flash kernels' names reach the lowered program
for every variant of the step, and ``reduce_gradients`` counts the
gradient bytes it hands to cross-device reductions. Names are metadata:
the step's numerical tests (test_parallel.py, test_models.py,
test_flash_attention.py) pass untouched."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.parallel.train import (_grad_reduce_bytes,
                                        build_pipeline_train_step,
                                        build_train_step,
                                        to_pipeline_params)

SCOPES = {"hvd_embed", "hvd_attn", "hvd_mlp", "hvd_loss_head",
          "hvd_grad_reduce", "hvd_optimizer"}
KERNELS = {"hvd_flash_fwd", "hvd_flash_dkv", "hvd_flash_dq"}


def _cfg(**kw):
    base = dict(vocab=128, d_model=128, n_heads=1, n_layers=2, d_ff=256,
                max_seq=128, remat=False, use_flash=False, loss_chunk=64)
    return tfm.TransformerConfig(**dict(base, **kw))


def _lowered(cfg, dp):
    """The tiny step lowered on a dp-way mesh of virtual CPU devices;
    returns its text with locations and the parameters' leaves."""
    mesh = create_mesh(devices=jax.devices()[:dp], dp=dp)
    opt = optax.adamw(1e-3)
    make, shard_params, shard_batch = build_train_step(cfg, mesh, opt)
    params = shard_params(tfm.init_params(cfg, jax.random.PRNGKey(0)))
    state = opt.init(params)
    step, _ = make(params, state)
    tokens = shard_batch(np.zeros((dp, cfg.max_seq), np.int32))
    text = step.lower(params, state, tokens, tokens).as_text(
        debug_info=True)
    return text, jax.tree_util.tree_leaves(params)


def _names(text):
    return set(re.findall(r"hvd_[a-z_]+", text))


@pytest.mark.parametrize("variant,kw,dp", [
    ("plain", {}, 4),
    ("plain-dp2", {}, 2),
    ("dots-remat", {"remat": True, "remat_policy": "dots"}, 4),
])
def test_the_lowered_step_holds_every_scope_and_its_own_name(
        variant, kw, dp):
    text, _ = _lowered(_cfg(**kw), dp)
    assert SCOPES <= _names(text), SCOPES - _names(text)
    assert "module @jit_hvd_train_step" in text
    # no name holds the separator JAX joins the stack with
    assert not [n for n in SCOPES | KERNELS if "/" in n]
    # the names survive the wrapping of the backward pass
    assert re.search(r"transpose\(jvp\([^\n\"]*\)\)[^\n\"]*hvd_attn|"
                     r"transpose\(jvp\(hvd_attn\)\)", text)


def test_the_flash_kernels_carry_their_names_in_interpret_mode():
    text, _ = _lowered(_cfg(use_flash=True, remat=True,
                            remat_policy="dots"), 1)
    assert KERNELS <= _names(text), KERNELS - _names(text)
    # each kernel inside the attention scope, forward and backward
    assert re.search(r"jvp\(hvd_attn\)/hvd_flash_fwd/", text)
    assert re.search(r"hvd_attn[^\n\"]*/hvd_flash_dkv/", text)
    assert re.search(r"hvd_attn[^\n\"]*/hvd_flash_dq/", text)


REMAT = {"none": {"remat": False},
         "full": {"remat": True, "remat_policy": "full"},
         "dots": {"remat": True, "remat_policy": "dots"}}


def _loss_and_grads(policy):
    """The differentiated model (two layers, interpreted flash kernel)
    under one remat setting: the function and its arguments."""
    cfg = _cfg(use_flash=True, dtype=jnp.float32, **REMAT[policy])
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.max_seq), 0,
                             cfg.vocab)
    return cfg, jax.value_and_grad(
        lambda p: tfm.loss_fn(p, tok, jnp.roll(tok, -1, axis=1), cfg)), params


@pytest.mark.parametrize("policy,forwards_a_layer",
                         [("none", 1), ("full", 2), ("dots", 1)])
def test_dots_remat_holds_one_forward_kernel_a_layer(policy,
                                                     forwards_a_layer):
    """``"dots"`` saves the kernel's named output and row statistics
    with the dots, so the backward needs no second forward; ``"full"``
    saves nothing and runs it again."""
    cfg, fn, params = _loss_and_grads(policy)
    jaxpr = str(jax.make_jaxpr(fn)(params))
    assert jaxpr.count("name=hvd_flash_fwd") == (
        forwards_a_layer * cfg.n_layers)
    assert jaxpr.count("name=hvd_flash_dkv") == cfg.n_layers
    assert jaxpr.count("name=hvd_flash_dq") == cfg.n_layers


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_remat_changes_no_bit_of_the_loss_or_a_gradient(policy):
    """Same kernel, same inputs: what is saved and what is recomputed
    are the same values, the compact row statistics included."""
    _, fn, params = _loss_and_grads(policy)
    _, plain, _ = _loss_and_grads("none")
    (loss, grads), (loss0, grads0) = jax.jit(fn)(params), jax.jit(plain)(
        params)
    assert float(loss) == float(loss0)
    for (path, g), g0 in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(grads0)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g0),
                                      err_msg=jax.tree_util.keystr(path))


def test_without_remat_the_block_is_the_block_itself():
    """``remat_block`` wraps nothing where ``cfg.remat`` is off: no
    ``jax.checkpoint``, so the names are identities and the program is
    the one without them."""
    assert tfm.remat_block(_cfg(**REMAT["none"])) is tfm._block
    assert tfm.remat_block(_cfg(**REMAT["dots"])) is not tfm._block


def _pipeline_step(cfg):
    """The tiny gpipe step on two virtual devices, unlowered, with its
    arguments."""
    mesh = create_mesh(devices=jax.devices()[:2], pp=2)
    opt = optax.sgd(0.1)
    make, shard_params, shard_batch = build_pipeline_train_step(
        cfg, mesh, opt, schedule="gpipe")
    params = shard_params(to_pipeline_params(
        cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)), 2))
    state = opt.init(params)
    step, _ = make(params, state)
    tokens = shard_batch(np.zeros((2, 1, cfg.max_seq), np.int32))
    return step, (params, state, tokens, tokens)


@pytest.mark.parametrize("policy,forwards", [("full", 3), ("dots", 2)])
def test_the_pipeline_step_shares_the_blocks_policy(policy, forwards):
    """``remat_block`` is the one place the policy is built. GPipe stashes
    stage inputs and runs each stage again for its vjp (two forward
    kernels in the program whatever the blocks do); inside that vjp
    ``"full"`` runs the kernel a third time and ``"dots"`` does not."""
    step, args = _pipeline_step(_cfg(use_flash=True, **REMAT[policy]))
    jaxpr = str(jax.make_jaxpr(step)(*args))
    assert jaxpr.count("name=hvd_flash_fwd") == forwards
    assert jaxpr.count("name=hvd_flash_dkv") == 1


def test_the_pipeline_step_is_named_too():
    step, args = _pipeline_step(_cfg())
    text = step.lower(*args).as_text(debug_info=True)
    assert "module @jit_hvd_pipeline_train_step" in text
    assert {"hvd_attn", "hvd_mlp", "hvd_optimizer"} <= _names(text)


def test_the_decode_path_reads_alike():
    cfg = _cfg(remat=False, loss_chunk=0)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    cache = tfm.init_cache(cfg, n_blocks=4, block_size=16)
    tokens = jnp.zeros((1, 4), jnp.int32)
    text = jax.jit(
        lambda p, c: tfm.apply_decode(p, tokens, jnp.zeros((1,), jnp.int32),
                                      jnp.array([[1, 2]], jnp.int32), c,
                                      cfg)).lower(params, cache).as_text(
        debug_info=True)
    assert {"hvd_embed", "hvd_attn", "hvd_mlp",
            "hvd_loss_head"} <= _names(text)


@pytest.mark.parametrize("dp", [4, 1, 2, 8])
def test_grad_reduce_bytes_counts_what_crosses_devices(dp):
    """float32 gradients: 4 bytes a gradient element on any dp mesh,
    whatever form the 'dp' sum takes and however many shards it is cut
    into; nothing on one device."""
    _, leaves = _lowered(_cfg(), dp)
    want = 4 * sum(x.size for x in leaves) if dp > 1 else 0
    assert _grad_reduce_bytes().value == want
    assert (want > 0) == (dp > 1)


# what models/qwen3_next.py adds to the names (docs/tracing.md#names):
# scopes of the mixers and the expert layer, and the three grouped-matmul
# kernels, which carry their names in interpret mode as the flash
# kernels do
QWEN3_NEXT_SCOPES = {"hvd_gdn", "hvd_gdn_conv", "hvd_delta_rule", "hvd_moe",
                     "hvd_moe_router", "hvd_moe_dispatch", "hvd_moe_shared"}
GMM_KERNELS = {"hvd_gmm_fwd", "hvd_gmm_drows", "hvd_gmm_dw"}


@pytest.mark.parametrize("dp", [1, 2])
def test_the_linear_attention_model_names_its_layers_and_kernels(dp):
    from horovod_tpu.models import qwen3_next as qn
    cfg = qn.Qwen3NextConfig(
        vocab=96, d_model=32, n_layers=2, full_attention_interval=2,
        dtype=jnp.float32, gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_dim=8, gdn_value_dim=8, chunk=16, n_heads=2, n_kv_heads=1,
        head_dim=16, rotary_dim=4, n_experts=8, experts_held=(0, 1, 2),
        top_k=2, moe_ff=16, shared_ff=16, use_flash=True, loss_chunk=16)
    mesh = create_mesh(devices=jax.devices()[:dp], dp=dp)
    opt = optax.adamw(1e-3)
    make, shard_params, shard_batch = build_train_step(cfg, mesh, opt)
    params = shard_params(cfg.init_params(jax.random.PRNGKey(0)))
    state = opt.init(params)
    step, _ = make(params, state)
    tokens = shard_batch(np.zeros((dp, 32), np.int32))
    text = step.lower(params, state, tokens, tokens).as_text(debug_info=True)
    names = _names(text)
    assert QWEN3_NEXT_SCOPES | GMM_KERNELS | KERNELS <= names
    assert {"hvd_embed", "hvd_attn", "hvd_loss_head", "hvd_optimizer",
            "hvd_train_step"} <= names
    assert not names & {"hvd_mlp", "hvd_ssm", "hvd_moe_routed"}
    # every kernel is a pallas_call under its own name
    for kernel in GMM_KERNELS:
        assert re.search(rf"{kernel}/pallas_call", text), kernel


# what models/olmo_hybrid.py adds (docs/tracing.md#names): no new scope,
# but ``hvd_mlp`` names a GATED MLP here and ``hvd_gdn`` a mixer whose
# norm sits on its output; the delta rule's kernels are read under
# ``hvd_delta_rule``
@pytest.mark.parametrize("dp", [1, 2])
def test_the_dense_hybrid_names_its_layers_and_kernels(dp):
    from horovod_tpu.models import olmo_hybrid as oh
    cfg = oh.OlmoHybridConfig(
        vocab=96, d_model=48, d_ff=80, dtype=jnp.float32,
        layer_types=("linear_attention", "full_attention"),
        gdn_key_heads=3, gdn_value_heads=3, gdn_key_dim=12,
        gdn_value_dim=24, chunk=16, gdn_groups=3, n_heads=3,
        use_flash=True, loss_chunk=16)
    mesh = create_mesh(devices=jax.devices()[:dp], dp=dp)
    opt = optax.adamw(1e-3)
    make, shard_params, shard_batch = build_train_step(cfg, mesh, opt)
    params = shard_params(cfg.init_params(jax.random.PRNGKey(0)))
    state = opt.init(params)
    step, _ = make(params, state)
    tokens = shard_batch(np.zeros((dp, 32), np.int32))
    text = step.lower(params, state, tokens, tokens).as_text(debug_info=True)
    names = _names(text)
    assert {"hvd_embed", "hvd_gdn", "hvd_gdn_conv", "hvd_delta_rule",
            "hvd_attn", "hvd_mlp", "hvd_loss_head", "hvd_optimizer",
            "hvd_train_step"} | KERNELS <= names
    assert not names & (QWEN3_NEXT_SCOPES - {"hvd_gdn", "hvd_gdn_conv",
                                             "hvd_delta_rule"})
    assert not names & GMM_KERNELS
    for kernel in ("delta_rule_fwd", "delta_rule_bwd"):
        assert re.search(rf"hvd_delta_rule/{kernel}", text), kernel
