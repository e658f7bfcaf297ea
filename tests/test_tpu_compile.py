"""The main path's TPU programs, compiled for a DESCRIBED v5e (no chip
attached, nothing runs): the flash kernels at the shapes the benches
use, and the donated ``build_train_step`` program at the 1.08B width.
What Mosaic or XLA:TPU would refuse on the chip, they refuse here, at
no chip time (/opt/skills/guides/on-chip-measurement, section 2).

All such compiles live in this one file: the worker that runs it loads
the TPU library and keeps it, so a second file on another worker could
not. The topology is described inside a fixture — never at import, in a
``skipif`` or a ``parametrize`` argument — so every worker collects the
same tests, and the compiles happen in the test's own process."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2, with the persistent compile cache off around
    the module: a compile for a described chip is written to the cache
    but cannot be read back without one, and the next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_names(text, kernel):
    """The name stacks of the compiled program's Mosaic kernels that
    hold ``kernel`` (what the trace reduction finds them by)."""
    return re.findall(rf'tpu_custom_call[^\n]*op_name="([^"]*{kernel}'
                      r'[^"]*pallas_call)"', text)


def _kernel_calls(text, kernel):
    return len(_kernel_names(text, kernel))


# [B, S, H, hd] and flash_block: the 1.08B row, the 111M ladder at
# head_dim 128 and 64, bench_lm.py's long_fb1024 long-context row, and
# the 16k cell's and qwen3next cell's attention at the default blocks
# (the grids over the visited pairs, their tables in scalar memory).
FLASH_CASES = [
    ((2, 2048, 16, 128), None),
    ((8, 2048, 6, 128), None),
    ((8, 2048, 12, 64), None),
    ((1, 8192, 6, 128), 1024),
    ((1, 16384, 12, 128), None),
    ((1, 8192, 16, 256), None),
]


@pytest.mark.parametrize("shape,block", FLASH_CASES,
                         ids=[f"{'x'.join(map(str, s))}-block{b}"
                              for s, b in FLASH_CASES])
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, shape, block):
    from horovod_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, block, block, False)
        return out.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    # Forward, dK/dV and dQ: three Mosaic kernels, none replaced, each
    # with its name in its name stack (what the trace reduction finds
    # it by, whatever it returns).
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    for kernel in ("hvd_flash_fwd", "hvd_flash_dkv", "hvd_flash_dq"):
        assert _kernel_calls(text, kernel) == 1, kernel


@pytest.fixture(scope="module")
def compiled_1b_step(topo):
    """``build_train_step`` at the full 1.08B width (depth cut to 2) on
    a one-device mesh of the described chip, compiled once for each
    remat policy asked for: ``build(remat_policy)`` returns the
    configuration, the state's bytes and the executable."""
    import optax

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.train import build_train_step

    @functools.lru_cache(maxsize=None)
    def build(remat_policy):
        cfg = tfm.TransformerConfig(
            vocab=32000, d_model=2048, n_layers=2, n_heads=16, d_ff=8192,
            max_seq=2048, dtype=jnp.bfloat16, remat=True,
            remat_policy=remat_policy, use_flash=True, logits_bf16=True,
            loss_chunk=512)
        mesh = Mesh(np.asarray(topo.devices[:1]), ("dp",))
        opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
        make, _, _ = build_train_step(cfg, mesh, opt)
        params = jax.eval_shape(
            lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
        opt_state = jax.eval_shape(opt.init, params)
        step, _ = make(params, opt_state)

        def on_mesh(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=NamedSharding(mesh, P())),
                tree)

        tokens = jax.ShapeDtypeStruct(
            (2, cfg.max_seq), jnp.int32,
            sharding=NamedSharding(mesh, P("dp", None)))
        # models/transformer.py reads the PROCESS's backend at trace time
        # to choose interpret mode; this process is on the CPU, the
        # target is not.
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            compiled = step.lower(on_mesh(params), on_mesh(opt_state),
                                  tokens, tokens).compile()
        state_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves((params, opt_state)))
        return cfg, state_bytes, compiled

    return build


def test_train_step_at_1b_width_donates_and_keeps_the_kernel(
        compiled_1b_step):
    """Params and optimizer state are aliased to the outputs (without
    donation the full-depth step needs ~22 GB of a v5e's 15.75), and
    attention is the Pallas kernel."""
    cfg, state_bytes, compiled = compiled_1b_step("dots")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0
    # Everything donated is reused: the whole state, not a leaf or two.
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3 * cfg.n_layers
    # The program names itself for the trace (docs/tracing.md#names):
    # the module, the scopes in the ops' metadata, the kernels.
    assert "HloModule jit_hvd_train_step" in text
    for name in ("hvd_embed", "hvd_attn", "hvd_mlp", "hvd_loss_head",
                 "hvd_optimizer", "hvd_flash_fwd", "hvd_flash_dkv",
                 "hvd_flash_dq"):
        assert re.search(rf'op_name="[^"]*{name}', text), name


@pytest.mark.parametrize("remat_policy,forwards_a_layer",
                         [("dots", 1), ("full", 2)])
def test_dots_remat_runs_the_flash_forward_once_a_layer(
        compiled_1b_step, remat_policy, forwards_a_layer):
    """Under ``"dots"`` the kernel's output and row statistics are saved
    with the dots (the checkpoint names ``hvd_flash_out`` /
    ``hvd_flash_lse``), so the backward starts from them; ``"full"``
    saves nothing and runs the forward kernel a second time. The policy
    is what differs, not the kernel."""
    cfg, _, compiled = compiled_1b_step(remat_policy)
    text = compiled.as_text()
    assert _kernel_calls(text, "hvd_flash_fwd") == (
        forwards_a_layer * cfg.n_layers)
    assert _kernel_calls(text, "hvd_flash_dkv") == cfg.n_layers
    assert _kernel_calls(text, "hvd_flash_dq") == cfg.n_layers


def test_on_four_chips_the_step_holds_a_quarter_of_the_moments(topo):
    """The dp=4 step at the 1.08B width (depth 2) for the described 2x2:
    make() asks for moments as 1/dp shards, so a chip's arguments are
    the parameters plus a QUARTER of the moments (a later change that
    replicates the state again fails here, on the CPU), everything
    donated is reused, and the exchange is a native reduce-scatter and
    an all-gather under ``hvd_grad_reduce``, with no all-reduce the
    size of a parameter."""
    import optax

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.train import build_train_step

    cfg = tfm.TransformerConfig(
        vocab=32000, d_model=2048, n_layers=2, n_heads=16, d_ff=8192,
        max_seq=2048, dtype=jnp.bfloat16, remat=True, remat_policy="dots",
        use_flash=True, logits_bf16=True, loss_chunk=512)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("dp",))
    opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    make, _, _ = build_train_step(cfg, mesh, opt)
    params = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    step, opt_specs = make(params, opt_state)

    def on_mesh(tree, specs):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)

    tokens = jax.ShapeDtypeStruct(
        (8, cfg.max_seq), jnp.int32,
        sharding=NamedSharding(mesh, P("dp", None)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = step.lower(
            on_mesh(params, tfm.param_specs(cfg)),
            on_mesh(opt_state, opt_specs), tokens, tokens).compile()

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))

    want = nbytes(params) + nbytes(opt_state) / 4
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes - want) <= 0.01 * want
    assert mem.alias_size_in_bytes >= 0.99 * want
    text = compiled.as_text()
    for op in ("reduce_scatter", "all_gather"):
        assert re.search(rf'op_name="[^"]*hvd_grad_reduce/{op}', text), op
    assert re.search(r'op_name="[^"]*hvd_optimizer', text)
    # Scattered on the LAST dimension every matrix keeps XLA:TPU's native
    # reduce-scatter (on the leading one it becomes an all-reduce over a
    # padded leaf, a slice and a halo exchange: zero.update_shard_specs);
    # the one all-reduce left sums the LayerNorm scales together.
    assert len(re.findall(r" reduce-scatter\(", text)) >= 6 * cfg.n_layers
    assert " collective-permute-start(" not in text
    for line in text.splitlines():
        if " all-reduce(" in line:
            result = line.split(" all-reduce(")[0]
            for dims in re.findall(r"f32\[([\d,]*)\]", result):
                assert np.prod([int(d) for d in dims.split(",") if d]
                               ) <= cfg.d_model, line[:200]


@pytest.fixture(scope="module")
def compiled_hybrid_step(topo):
    """``build_train_step`` on the hybrid model (models/nemotron_h.py)
    at the published widths, one layer of each kind, 4096 tokens, on a
    one-device mesh of the described chip: ``build(held)`` returns the
    state's bytes and the executable, with the layers' checkpoints
    holding their named arrays (``HELD_NAMES``) or, ``held`` false,
    nothing, which is the program before the names."""
    import optax

    from nemotron_h_common import hold_nothing

    from horovod_tpu.models import nemotron_h
    from horovod_tpu.parallel.train import build_train_step

    @functools.lru_cache(maxsize=None)
    def build(held):
        cfg = nemotron_h.NemotronHConfig(
            vocab=16384, d_model=4096, pattern="ME*", mamba_heads=128,
            mamba_head_dim=64, mamba_groups=8, state_size=128, chunk=128,
            n_heads=32, n_kv_heads=2, head_dim=128, n_routed_experts=512,
            experts_held=tuple(range(8)), top_k=22, routed_scaling=5.0,
            moe_latent=1024, moe_ff=2688, shared_ff=5376,
            dtype=jnp.bfloat16, remat=True, use_flash=True,
            logits_bf16=True, loss_chunk=512)
        mesh = Mesh(np.asarray(topo.devices[:1]), ("dp",))
        opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
        make, _, _ = build_train_step(cfg, mesh, opt)
        params = jax.eval_shape(
            lambda: cfg.init_params(jax.random.PRNGKey(0)))
        opt_state = jax.eval_shape(opt.init, params)
        step, _ = make(params, opt_state)

        def on_mesh(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=NamedSharding(mesh, P())),
                tree)

        tokens = jax.ShapeDtypeStruct(
            (1, 4096), jnp.int32,
            sharding=NamedSharding(mesh, P("dp", None)))
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            if not held:
                hold_nothing(patch)
            compiled = step.lower(on_mesh(params), on_mesh(opt_state),
                                  tokens, tokens).compile()
        state_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves((params, opt_state)))
        return state_bytes, compiled

    return build


def test_hybrid_step_at_published_widths_compiles_for_the_chip(
        compiled_hybrid_step):
    """It compiles for the chip, the state is donated, every scope is
    in the ops' metadata, and the attention layer runs the flash
    kernels as the flagship does: forward twice under full remat, for
    the hybrid layers hold the mixer's and the router's named arrays
    and NOT the kernel's (models/nemotron_h.py::HELD_NAMES says why).
    The expert layer's routed part is the two grouped matmuls over rows
    sorted by held expert, forward twice and each backward once, in a
    buffer of ``4096 x 8`` rows."""
    state_bytes, compiled = compiled_hybrid_step(True)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes
    text = compiled.as_text()
    assert _kernel_calls(text, "hvd_flash_fwd") == 2
    assert _kernel_calls(text, "hvd_flash_dkv") == 1
    assert _kernel_calls(text, "hvd_flash_dq") == 1
    assert _kernel_calls(text, "hvd_moe_routed/hvd_gmm_fwd") == 4
    assert _kernel_calls(text, "hvd_moe_routed/hvd_gmm_drows") == 2
    assert _kernel_calls(text, "hvd_moe_routed/hvd_gmm_dw") == 2
    rows = set(re.findall(r"\[(\d+),1024\][^\n]*hvd_moe_dispatch", text))
    assert "32768" in rows
    for name in ("hvd_embed", "hvd_ssm", "hvd_ssm_conv", "hvd_ssd_scan",
                 "hvd_attn", "hvd_moe", "hvd_moe_router", "hvd_moe_routed",
                 "hvd_moe_dispatch", "hvd_moe_shared", "hvd_loss_head",
                 "hvd_optimizer"):
        assert re.search(rf'op_name="[^"]*{name}', text), name


def _loops(text):
    return len(re.findall(r" while\(", text))


def _top_ks(text):
    """XLA:TPU lowers ``lax.top_k`` to a whole ``sort`` of the scores."""
    return len(re.findall(r' sort\([^\n]*op_name="[^"]*hvd_moe_router/top_k"',
                          text))


def _plan_sorts(text):
    return len(re.findall(r' sort\([^\n]*op_name="[^"]*hvd_moe_dispatch',
                          text))


def test_hybrid_layers_run_the_groups_twice_and_top_k_once(
        compiled_hybrid_step):
    """What the layers' checkpoints hold shows in the compiled program:
    against the same step with nothing held, the mixer layer loses the
    loop over its groups and the chunk loop inside it that only got
    ``y`` back for ``out_proj`` (forward and one rerun a group are
    left: twice a step, not three times), and the expert layer its
    second ``top_k`` and the second run of the row plan's two sorts.
    No Mosaic call comes or goes."""
    held = compiled_hybrid_step(True)[1].as_text()
    bare = compiled_hybrid_step(False)[1].as_text()
    assert _loops(bare) - _loops(held) == 2
    assert (_top_ks(held), _top_ks(bare)) == (1, 2)
    assert (_plan_sorts(held), _plan_sorts(bare)) == (2, 4)
    assert held.count("tpu_custom_call") == bare.count("tpu_custom_call")


@pytest.fixture(scope="module")
def compiled_linear_attention_step(topo):
    """``build_train_step`` on the linear-attention model
    (models/qwen3_next.py) at the published widths, one DeltaNet layer
    and one attention layer, each with its expert layer of 32 held
    experts out of 512, 8192 tokens, on a one-device mesh of the
    described chip: the state's bytes and the executable."""
    import optax

    from horovod_tpu.models import qwen3_next
    from horovod_tpu.parallel.train import build_train_step

    cfg = qwen3_next.Qwen3NextConfig(
        vocab=18992, d_model=2048, n_layers=2, full_attention_interval=2,
        gdn_key_heads=16, gdn_value_heads=32, gdn_key_dim=128,
        gdn_value_dim=128, chunk=64, gdn_groups=4, n_heads=16,
        n_kv_heads=2, head_dim=256, rotary_dim=64, rope_theta=1e7,
        n_experts=512, experts_held=tuple(range(32)), top_k=10, moe_ff=512,
        shared_ff=512, dtype=jnp.bfloat16, remat=True, use_flash=True,
        logits_bf16=True, loss_chunk=512)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("dp",))
    opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    make, _, _ = build_train_step(cfg, mesh, opt)
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    step, _ = make(params, opt_state)

    def on_mesh(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, P())), tree)

    tokens = jax.ShapeDtypeStruct(
        (1, 8192), jnp.int32, sharding=NamedSharding(mesh, P("dp", None)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = step.lower(on_mesh(params), on_mesh(opt_state),
                              tokens, tokens).compile()
    state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves((params, opt_state)))
    return state_bytes, compiled


def test_linear_attention_step_at_published_widths_fits_the_chip(
        compiled_linear_attention_step):
    """It compiles for the chip at 8192 tokens with the flash kernels at
    head_dim 256, the three grouped-matmul kernels and the two
    delta-rule kernels in place, the state is donated, every scope is
    in the ops' metadata, and the two layers' step fits in what it was
    seen to take (argument 3.485 GB + temp 1.988 GB, CPU-side compile,
    PR 38; 2.204 GB while the delta rule was XLA ops, PR 37): the
    worst-case buffers of 81920 rows are most of the temp."""
    state_bytes, compiled = compiled_linear_attention_step
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes
    assert mem.argument_size_in_bytes <= 1.01 * state_bytes + 1e6
    assert mem.temp_size_in_bytes <= 2.2e9
    text = compiled.as_text()
    # one DeltaNet layer, its groups one after another in a loop's body:
    # the forward kernel in the forward pass and in the group
    # checkpoint's recomputation, the backward kernel once; all three
    # are read under the scope's name (docs/tracing.md#names)
    forwards = _kernel_names(text, "hvd_delta_rule/delta_rule_fwd")
    assert len(forwards) == 2
    assert sum("rematted_computation" in name for name in forwards) == 1
    assert _kernel_calls(text, "hvd_delta_rule/delta_rule_bwd") == 1
    assert _kernel_calls(text, "hvd_delta_rule") == 3
    assert _kernel_calls(text, "hvd_flash_fwd") == 2
    assert _kernel_calls(text, "hvd_flash_dkv") == 1
    assert _kernel_calls(text, "hvd_flash_dq") == 1
    # two expert layers, two grouped matmuls each: forward and its
    # recomputation, and one backward of each kind
    assert _kernel_calls(text, "hvd_gmm_fwd") == 8
    assert _kernel_calls(text, "hvd_gmm_drows") == 4
    assert _kernel_calls(text, "hvd_gmm_dw") == 4
    for name in ("hvd_embed", "hvd_gdn", "hvd_gdn_conv", "hvd_delta_rule",
                 "hvd_attn", "hvd_moe", "hvd_moe_router", "hvd_moe_dispatch",
                 "hvd_moe_shared", "hvd_loss_head", "hvd_optimizer"):
        assert re.search(rf'op_name="[^"]*{name}', text), name


def test_no_loop_of_the_step_follows_the_routing(
        compiled_linear_attention_step):
    """Outside the grouped-matmul kernels nothing on the device has a
    trip count or a shape that depends on the routing: every ``while``
    of the compiled step is the mixer's loop over its head groups or
    the loss head's loop over its chunks (trip counts of the shapes),
    none lies in the expert layer, which has no conditional either; its
    buffers all have the worst case's 81920 rows. And none lies under
    ``hvd_delta_rule`` any more: the scan over the chunks is the
    kernels' sequential grid."""
    text = compiled_linear_attention_step[1].as_text()
    loops = re.findall(r' while\([^\n]*op_name="([^"]*)"', text)
    assert loops and len(loops) == _loops(text)
    for name in loops:
        assert re.search(r"hvd_(gdn|loss_head)", name), name
        assert "hvd_delta_rule" not in name, name
        assert "hvd_moe" not in name and "hvd_gmm" not in name, name
    assert not re.search(r' conditional\([^\n]*op_name="[^"]*hvd_(moe|gmm)',
                         text)
    rows = set(re.findall(r"\[(\d+),2048\][^\n]*hvd_moe_dispatch", text))
    assert "81920" in rows


@pytest.mark.parametrize("heads,dk,dv", [(6, 96, 192), (30, 96, 192),
                                         (8, 128, 128)])
def test_delta_rule_kernels_compile_for_v5e_at_the_cells_shapes(
        one_chip, heads, dk, dv):
    """Mosaic decides, not interpret mode: forward and backward of
    ``delta_rule`` at one group of the dense hybrid's cell (6 heads of
    96 / 192, padded inside the op to 128 / 256), at its whole 30 heads
    (no block of 4 divides them: blocks of 3), and at the other cell's
    128 / 128, at 16384 bfloat16 positions."""
    from horovod_tpu.ops.delta_rule import delta_rule

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    seq = 16384
    args = (shape(1, seq, heads, dk), shape(1, seq, heads, dk),
            shape(1, seq, heads, dv),
            shape(1, seq, heads, dtype=jnp.float32),
            shape(1, seq, heads, dtype=jnp.float32))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(jax.grad(
            lambda *a: delta_rule(*a, chunk=64).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, r"hvd_delta_rule\)+/delta_rule_fwd") == 1
    assert _kernel_calls(text, r"hvd_delta_rule\)+/delta_rule_bwd") == 1
    # the kernels meet whole lanes: a head's columns are padded, or not
    wide = heads * (dv + -dv % 128)
    assert re.search(rf"bf16\[1,{seq},{wide}\][^\n]*tpu_custom_call", text)


@pytest.fixture(scope="module")
def compiled_dense_hybrid_step(topo):
    """``build_train_step`` on the dense linear-attention hybrid
    (models/olmo_hybrid.py) as its cell runs it: the published widths,
    one period (three DeltaNet layers, one of attention), an eighth of
    the vocabulary, 16384 tokens, on a one-device mesh of the described
    chip: the state's bytes and the executable."""
    import optax

    from horovod_tpu.models import olmo_hybrid
    from horovod_tpu.parallel.train import build_train_step

    cfg = olmo_hybrid.OlmoHybridConfig(
        vocab=12544, d_model=3840, d_ff=11008, gdn_key_heads=30,
        gdn_value_heads=30, gdn_key_dim=96, gdn_value_dim=192, chunk=64,
        gdn_groups=5, n_heads=30, dtype=jnp.bfloat16, remat=True,
        use_flash=True, logits_bf16=True, loss_chunk=512)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("dp",))
    opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    make, _, _ = build_train_step(cfg, mesh, opt)
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    step, _ = make(params, opt_state)

    def on_mesh(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, P())), tree)

    tokens = jax.ShapeDtypeStruct(
        (1, 16384), jnp.int32, sharding=NamedSharding(mesh, P("dp", None)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = step.lower(on_mesh(params), on_mesh(opt_state),
                              tokens, tokens).compile()
    state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves((params, opt_state)))
    return state_bytes, compiled


def test_dense_hybrid_step_at_published_widths_fits_the_chip(
        compiled_dense_hybrid_step):
    """The cell's step compiles for the chip at 16384 tokens under the
    15.6 GB ceiling (argument 9.289 GB + temp 5.116 GB = 14.41 GB,
    CPU-side compile, PR 39), the state (928.9M parameters at 10 bytes)
    is donated, the compiler adds NO rematerialization of its own, the
    delta rule's kernels run once forward, once in the group
    checkpoint's recomputation and once backward a DeltaNet layer, the
    flash kernels at ``[30, 16384, 128]``, and every scope is in the
    ops' metadata."""
    state_bytes, compiled = compiled_dense_hybrid_step
    assert round(state_bytes / 1e9, 2) == 9.29
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes
    assert mem.argument_size_in_bytes <= 1.01 * state_bytes + 1e6
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= 15.6e9
    assert mem.temp_size_in_bytes <= 5.3e9
    text = compiled.as_text()
    assert len(re.findall(r"\.remat", text)) == 0
    forwards = _kernel_names(text, "hvd_delta_rule/delta_rule_fwd")
    assert len(forwards) == 6
    assert sum("rematted_computation" in name for name in forwards) == 3
    assert _kernel_calls(text, "hvd_delta_rule/delta_rule_bwd") == 3
    assert _kernel_calls(text, "hvd_flash_fwd") == 2
    assert _kernel_calls(text, "hvd_flash_dkv") == 1
    assert _kernel_calls(text, "hvd_flash_dq") == 1
    assert text.count("tpu_custom_call") >= 13
    for name in ("hvd_embed", "hvd_gdn", "hvd_gdn_conv", "hvd_delta_rule",
                 "hvd_attn", "hvd_mlp", "hvd_loss_head", "hvd_optimizer"):
        assert re.search(rf'op_name="[^"]*{name}', text), name
    # nothing on the device follows the data: every loop is a mixer's
    # over its head groups or the loss head's over its chunks
    loops = re.findall(r' while\([^\n]*op_name="([^"]*)"', text)
    assert loops and len(loops) == _loops(text)
    for name in loops:
        assert re.search(r"hvd_(gdn|loss_head)", name), name
    assert " conditional(" not in text
