"""Parallelism-layer tests: ring attention exactness, pipeline schedule,
MoE dispatch, and full train-step parity of sharded vs single-device runs.

These strategies are extensions beyond the reference (SURVEY.md §2.1 lists
TP/PP/SP/EP as absent there); the test strategy mirrors the reference's op
tests — numeric equality against an unsharded oracle."""

import functools

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import create_mesh
from horovod_tpu.parallel.ring_attention import (full_attention,
                                                 ring_attention)
from horovod_tpu.parallel.pipeline import pipeline_apply
from horovod_tpu.parallel.expert import moe_apply
from horovod_tpu.parallel.train import build_train_step
from horovod_tpu.models import transformer as tfm


def _copy_tree(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


@functools.lru_cache(maxsize=None)
def _flash_in_shardmap_supported():
    """Capability probe: some XLA builds (e.g. this container's CPU
    jaxlib) reject the Pallas interpret-mode flash kernels under
    jit+shard_map over a full single axis with ``UNIMPLEMENTED:
    PartitionId instruction is not supported for SPMD partitioning``.
    That is a backend capability gap, not a ring-attention bug — probe
    once on a tiny instance and skip (instead of fail) where the
    backend cannot run the construct. Any OTHER failure still fails
    the tests."""
    mesh = create_mesh(sp=8)
    q = jnp.ones((1, 16, 1, 4), jnp.float32)
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(
            q, k, v, axis_name="sp", causal=False, use_flash=True,
            flash_block=2, flash_interpret=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))
    try:
        f(q, q, q)
        return True
    except Exception as e:
        if "PartitionId" in str(e):
            return False
        raise


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        mesh = create_mesh(sp=8)
        B, S, H, D = 2, 64, 4, 16
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        ref = full_attention(q, k, v, causal=causal)
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                           causal=causal),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        out = f(q, k, v)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_grad_flows_through_ring(self):
        """Backward through ppermute routes cross-shard cotangents."""
        mesh = create_mesh(sp=4, dp=2)
        B, S, H, D = 2, 32, 2, 8
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

        def loss_ring(q, k, v):
            def shard(q, k, v):
                out = ring_attention(q, k, v, axis_name="sp", causal=True)
                l = (out.astype(jnp.float32) ** 2).sum()
                return lax.psum(l, ("sp", "dp"))
            return jax.shard_map(
                shard, mesh=mesh,
                in_specs=(P("dp", "sp"),) * 3, out_specs=P(),
                check_vma=False)(q, k, v)

        def loss_full(q, k, v):
            out = full_attention(q, k, v, causal=True)
            return (out.astype(jnp.float32) ** 2).sum()

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert float(jnp.max(jnp.abs(np.asarray(a) - np.asarray(b)))) \
                < 1e-3

    @pytest.mark.parametrize("causal", [True, False])
    def test_ring_flash_matches_full(self, causal):
        """Flash inner op (per-shard-pair Pallas kernels + logaddexp
        merge) against the unsharded oracle."""
        if not _flash_in_shardmap_supported():
            pytest.skip("backend lacks PartitionId under SPMD "
                        "partitioning (flash interpret in shard_map)")
        mesh = create_mesh(sp=8)
        B, S, H, D = 2, 64, 4, 16
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        ref = full_attention(q, k, v, causal=causal)
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(
                q, k, v, axis_name="sp", causal=causal, use_flash=True,
                flash_interpret=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        out = f(q, k, v)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_ring_flash_grads(self):
        """The custom ring backward (traveling dK/dV accumulators +
        global-lse per-block flash backward) against unsharded autodiff;
        shard 6 with block 4 also exercises the kernels' tail-block
        masked branch through the ring path."""
        mesh = create_mesh(sp=4, dp=2)
        B, S, H, D = 2, 24, 2, 8
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

        def loss_ring(q, k, v):
            def shard(q, k, v):
                out = ring_attention(q, k, v, axis_name="sp", causal=True,
                                     use_flash=True, flash_block=4,
                                     flash_interpret=True)
                l = (out.astype(jnp.float32) ** 2).sum()
                return lax.psum(l, ("sp", "dp"))
            return jax.shard_map(
                shard, mesh=mesh,
                in_specs=(P("dp", "sp"),) * 3, out_specs=P(),
                check_vma=False)(q, k, v)

        def loss_full(q, k, v):
            out = full_attention(q, k, v, causal=True)
            return (out.astype(jnp.float32) ** 2).sum()

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert float(jnp.max(jnp.abs(np.asarray(a) - np.asarray(b)))) \
                < 1e-3


class TestPipeline:
    def test_four_stage_product(self):
        mesh = create_mesh(pp=4, dp=2)
        scales = jnp.arange(1.0, 5.0)[:, None]
        xs = jnp.ones((3, 2, 8))

        def stage_fn(p, x):
            return x * p["scale"]

        def run(scale_local, x):
            return pipeline_apply(stage_fn, {"scale": scale_local[0]}, x,
                                  axis_name="pp")

        f = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P("pp"), P(None, "dp")),
            out_specs=P(None, "dp"), check_vma=False))
        out = f(scales, xs)
        assert np.allclose(np.asarray(out), 24.0)  # 1*2*3*4

    def test_microbatch_identity_order(self):
        """Outputs keep microbatch order through the skewed schedule."""
        mesh = create_mesh(pp=4, dp=2)
        xs = jnp.arange(4 * 2 * 4, dtype=jnp.float32).reshape(4, 2, 4)

        def stage_fn(p, x):
            return x + p["b"]

        ones = jnp.ones((4, 1))

        def run(b_local, x):
            return pipeline_apply(stage_fn, {"b": b_local[0]}, x,
                                  axis_name="pp")

        f = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P("pp"), P(None, "dp")),
            out_specs=P(None, "dp"), check_vma=False))
        out = f(ones, xs)
        assert np.allclose(np.asarray(out), np.asarray(xs) + 4.0)


class TestMoE:
    def test_matches_dense_with_ample_capacity(self):
        mesh = create_mesh(ep=8)
        F, H, E = 16, 32, 8
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, F), jnp.float32)
        pw = {
            "router": jax.random.normal(rng, (F, E)) * 0.25,
            "wi": jax.random.normal(rng, (E, F, H)) * 0.1,
            "wo": jax.random.normal(rng, (E, H, F)) * 0.1,
        }

        def run_moe(p, xl):
            return moe_apply(p, xl, num_experts=E, capacity_factor=8.0,
                             axis_name="ep", act=jax.nn.gelu,
                             dtype=jnp.float32)

        f = jax.jit(jax.shard_map(
            run_moe, mesh=mesh,
            in_specs=({"router": P(), "wi": P("ep"), "wo": P("ep")},
                      P("ep")),
            out_specs=P("ep"), check_vma=False))
        out = f(pw, x)

        logits = x @ pw["router"]
        e = jnp.argmax(logits, -1)
        gate = jax.nn.softmax(logits, -1)
        g = jnp.take_along_axis(gate, e[:, None], 1)[:, 0]
        wi = np.asarray(pw["wi"])
        wo = np.asarray(pw["wo"])
        ref = jnp.stack([
            (jax.nn.gelu(x[i] @ wi[int(e[i])]) @ wo[int(e[i])]) * g[i]
            for i in range(64)])
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


class TestTrainStepParity:
    """The flagship guarantee: a sharded multi-axis training step equals
    the single-device step bit-for-bit (up to fp reassociation)."""

    def _run(self, cfg, mesh, params, tok, tgt, opt):
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        state = opt.init(params)
        step, _ = make(params, state)
        # The step donates params/state; callers reuse ``params`` across
        # meshes, so each run trains a copy.
        p, _, loss = step(shard_p(_copy_tree(params)), state,
                          shard_b(tok), shard_b(tgt))
        leaves = [np.asarray(x, np.float32)
                  for x in jax.tree_util.tree_leaves(p)]
        return leaves, float(loss)

    def test_chunked_loss_matches_monolithic(self):
        """loss_chunk computes the identical loss AND gradients as the
        monolithic [B,S,V] path (it only changes memory layout), and
        logits_bf16 stays within bf16 rounding of the fp32 projection."""
        rng = jax.random.PRNGKey(0)
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, remat=False)
        params = tfm.init_params(cfg, rng)
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        tgt = jnp.roll(tok, -1, axis=1)

        def loss_with(**over):
            kw = dict(vocab=64, d_model=32, n_heads=2, n_layers=2,
                      d_ff=64, max_seq=32, dtype=jnp.float32, remat=False)
            kw.update(over)
            c = tfm.TransformerConfig(**kw)
            return jax.value_and_grad(
                lambda p: tfm.loss_fn(p, tok, tgt, c))(params)

        l0, g0 = loss_with()
        l1, g1 = loss_with(loss_chunk=8)
        # Chunking reassociates the fp32 mean; at loss ~21 one ulp is
        # ~1.9e-6, and a legitimate accumulation-order delta of exactly
        # that size was observed. Allow a few ulps, not bitwise equality.
        assert abs(float(l0) - float(l1)) < 5e-6
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)))
        assert err < 1e-5, f"chunked-loss grad divergence {err}"
        # remat_policy="dots" changes memory, never values.
        ld, gd = loss_with(remat=True, remat_policy="dots")
        assert abs(float(l0) - float(ld)) < 5e-6
        errd = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(gd)))
        assert errd < 1e-5, f"dots-policy grad divergence {errd}"
        # chunk must divide the sequence
        import pytest as _pytest
        with _pytest.raises(ValueError):
            loss_with(loss_chunk=7)
        # bf16 projection: same loss within rounding
        l2, _ = loss_with(logits_bf16=True, dtype=jnp.bfloat16)
        assert abs(float(l0) - float(l2)) < 0.1

    def test_dense_dp_tp_sp(self):
        rng = jax.random.PRNGKey(0)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
        tgt = jnp.roll(tok, -1, axis=1)
        opt = optax.sgd(0.1)
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, tp_axis="tp", sp_axis="sp",
            remat=True)
        params = tfm.init_params(cfg, rng)
        l1, loss1 = self._run(cfg, create_mesh(dp=2, tp=2, sp=2), params,
                              tok, tgt, opt)
        l2, loss2 = self._run(
            cfg, create_mesh(devices=jax.devices()[:1], dp=1, tp=1, sp=1),
            params, tok, tgt, opt)
        assert abs(loss1 - loss2) < 1e-5
        err = max(np.max(np.abs(a - b)) for a, b in zip(l1, l2))
        assert err < 1e-4, f"param divergence {err}"

    def test_moe_dp_ep(self):
        rng = jax.random.PRNGKey(0)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
        tgt = jnp.roll(tok, -1, axis=1)
        opt = optax.adam(1e-2)
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, ep_axis="ep", num_experts=4,
            capacity_factor=8.0, remat=False)
        params = tfm.init_params(cfg, rng)
        l1, loss1 = self._run(cfg, create_mesh(dp=2, ep=4), params, tok,
                              tgt, opt)
        l2, loss2 = self._run(
            cfg, create_mesh(devices=jax.devices()[:1], dp=1, ep=1),
            params, tok, tgt, opt)
        assert abs(loss1 - loss2) < 1e-4
        err = max(np.max(np.abs(a - b)) for a, b in zip(l1, l2))
        assert err < 1e-3, f"param divergence {err}"

    def test_loss_decreases_over_steps(self):
        rng = jax.random.PRNGKey(0)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
        tgt = jnp.roll(tok, -1, axis=1)
        opt = optax.adam(1e-2)
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, tp_axis="tp", sp_axis="sp")
        params = tfm.init_params(cfg, rng)
        mesh = create_mesh(dp=2, tp=2, sp=2)
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        state = opt.init(params)
        step, _ = make(params, state)
        p, s = shard_p(params), state
        tk, tg = shard_b(tok), shard_b(tgt)
        losses = []
        for _ in range(5):
            p, s, loss = step(p, s, tk, tg)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        from horovod_tpu.parallel.ulysses import ulysses_attention
        mesh = create_mesh(sp=8)
        B, S, H, D = 2, 64, 8, 16     # H == sp size: 1 head per shard
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        ref = full_attention(q, k, v, causal=causal)
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp",
                                              causal=causal),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        out = f(q, k, v)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_multiple_heads_per_shard(self):
        from horovod_tpu.parallel.ulysses import ulysses_attention
        mesh = create_mesh(sp=4, dp=2)
        B, S, H, D = 2, 32, 8, 8      # 2 heads per sp shard, dp batch
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        ref = full_attention(q, k, v, causal=True)
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(P("dp", "sp"),) * 3,
            out_specs=P("dp", "sp"), check_vma=False))
        out = f(q, k, v)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_grad_matches_full(self):
        from horovod_tpu.parallel.ulysses import ulysses_attention
        mesh = create_mesh(sp=4, dp=2)
        B, S, H, D = 1, 32, 4, 8
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

        def loss_uly(q, k, v):
            def shard(q, k, v):
                out = ulysses_attention(q, k, v, axis_name="sp")
                return lax.psum((out.astype(jnp.float32) ** 2).sum(), "sp")
            return jax.shard_map(
                shard, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(), check_vma=False)(q, k, v)

        def loss_full(q, k, v):
            out = full_attention(q, k, v, causal=True)
            return (out.astype(jnp.float32) ** 2).sum()

        g1 = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert float(jnp.max(jnp.abs(np.asarray(a) - np.asarray(b)))) \
                < 1e-3

    def test_head_divisibility_error(self):
        from horovod_tpu.parallel.ulysses import ulysses_attention
        mesh = create_mesh(sp=8)
        B, S, H, D = 1, 16, 4, 8      # 4 heads, 8 shards -> error
        q = jnp.ones((B, S, H, D), jnp.float32)
        with pytest.raises(ValueError, match="divisible"):
            jax.jit(jax.shard_map(
                lambda q: ulysses_attention(q, q, q, axis_name="sp"),
                mesh=mesh, in_specs=(P(None, "sp"),),
                out_specs=P(None, "sp"), check_vma=False))(q)

    def test_transformer_sp_impl_ulysses(self):
        """Flagship transformer trains a step with sp_impl='ulysses'."""
        import optax
        mesh = create_mesh(dp=2, sp=4)
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, sp_axis="sp",
            sp_impl="ulysses", remat=False)
        rng = jax.random.PRNGKey(0)
        params = tfm.init_params(cfg, rng)
        tokens = jax.random.randint(rng, (4, 32), 0, 64)
        targets = jnp.roll(tokens, -1, axis=1)
        opt = optax.adam(1e-3)
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        state = opt.init(params)
        step, _ = make(params, state)
        _, _, loss = step(shard_p(params), state, shard_b(tokens),
                          shard_b(targets))
        assert np.isfinite(float(loss))

    @pytest.mark.parametrize("policy", ["full", "dots"])
    def test_ulysses_flash_under_remat_trains_the_same_step(self, policy):
        """Under ``"dots"`` the saved set holds the local kernel's named
        output and compact row statistics (docs/parallelism.md); saved or
        recomputed, they are the same values, so the trained parameters
        equal those of the step without remat (to the ulp or two by which
        XLA's fusions differ between the two programs, "full" too)."""
        import optax
        mesh = create_mesh(dp=2, sp=4)
        base = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                    max_seq=32, dtype=jnp.float32, sp_axis="sp",
                    sp_impl="ulysses", use_flash=True)
        rng = jax.random.PRNGKey(0)
        tokens = jax.random.randint(rng, (4, 32), 0, 64)
        targets = jnp.roll(tokens, -1, axis=1)
        opt = optax.sgd(0.1)

        def trained(**kw):
            cfg = tfm.TransformerConfig(**base, **kw)
            params = tfm.init_params(cfg, rng)
            make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
            state = opt.init(params)
            step, _ = make(params, state)
            p, _, loss = step(shard_p(params), state, shard_b(tokens),
                              shard_b(targets))
            return [np.asarray(x) for x in
                    jax.tree_util.tree_leaves(p)], float(loss)

        want, loss0 = trained(remat=False)
        got, loss = trained(remat=True, remat_policy=policy)
        assert abs(loss - loss0) < 5e-6
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


class TestZero1:
    """ZeRO-1 optimizer-state sharding (parallel/zero.py): the sharded-
    state step must match the replicated-state step numerically, with
    every moment leaf stored as a 1/dp flat shard over 'dp'."""

    def _setup(self, opt):
        rng = jax.random.PRNGKey(0)
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        tgt = jnp.roll(tok, -1, axis=1)
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, remat=False)
        params = tfm.init_params(cfg, rng)
        return cfg, params, tok, tgt

    def _train(self, cfg, mesh, params, tok, tgt, opt, state, steps=4):
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        step, _ = make(params, state)
        p, s = shard_p(_copy_tree(params)), state
        tk, tg = shard_b(tok), shard_b(tgt)
        losses = []
        for _ in range(steps):
            p, s, loss = step(p, s, tk, tg)
            losses.append(float(loss))
        leaves = [np.asarray(x, np.float32)
                  for x in jax.tree_util.tree_leaves(p)]
        return leaves, losses, s

    def test_matches_replicated_state_adamw(self):
        from horovod_tpu.parallel.zero import zero1_init
        opt = optax.adamw(1e-2)
        cfg, params, tok, tgt = self._setup(opt)
        mesh = create_mesh(dp=8)
        l_ref, losses_ref, _ = self._train(
            cfg, mesh, params, tok, tgt, opt, opt.init(params))
        zstate = zero1_init(opt, params, n_shards=8)
        l_z, losses_z, _ = self._train(
            cfg, mesh, params, tok, tgt, opt, zstate)
        np.testing.assert_allclose(losses_z, losses_ref, rtol=1e-5)
        err = max(np.max(np.abs(a - b)) for a, b in zip(l_z, l_ref))
        assert err < 1e-5, f"param divergence {err}"

    def test_moments_sharded_one_over_dp(self):
        from horovod_tpu.parallel.zero import zero1_init
        opt = optax.adam(1e-2)
        cfg, params, tok, tgt = self._setup(opt)
        mesh = create_mesh(dp=8)
        zstate = zero1_init(opt, params, n_shards=8)
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        step, opt_specs = make(params, zstate)
        p, s, _ = step(shard_p(params), zstate, shard_b(tok),
                       shard_b(tgt))
        import jax as _jax
        from jax.sharding import PartitionSpec as P
        # Every vector moment leaf: sharded over dp, local shard = 1/8.
        checked = 0
        for leaf in _jax.tree_util.tree_leaves(s):
            if getattr(leaf, "ndim", 0) >= 1 and leaf.size >= 8:
                assert len(leaf.sharding.device_set) == 8
                shard = leaf.addressable_shards[0].data
                assert shard.size == leaf.size // 8
                checked += 1
        assert checked >= 4  # adam mu+nu over several params

    def test_zero_with_tp_combination(self):
        """The model-axis interaction: a tp-sharded parameter's moments
        must live as per-tp-block flat shards further split over dp —
        AdamW (stateful) so a layout bug cannot hide in an empty state."""
        from horovod_tpu.parallel.zero import zero1_init
        opt = optax.adamw(1e-2)
        rng = jax.random.PRNGKey(0)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
        tgt = jnp.roll(tok, -1, axis=1)
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, tp_axis="tp", remat=False)
        params = tfm.init_params(cfg, rng)
        mesh = create_mesh(dp=4, tp=2)
        zstate = zero1_init(opt, params, n_shards=4,
                            param_specs=tfm.param_specs(cfg), mesh=mesh)
        l_z, losses_z, _ = self._train(cfg, mesh, params, tok, tgt, opt,
                                       zstate)
        l_ref, losses_ref, _ = self._train(cfg, mesh, params, tok, tgt,
                                           opt, opt.init(params))
        np.testing.assert_allclose(losses_z, losses_ref, rtol=1e-5)
        err = max(np.max(np.abs(a - b)) for a, b in zip(l_z, l_ref))
        assert err < 1e-5, f"param divergence {err}"

    def test_requires_dp_axis(self):
        from horovod_tpu.parallel.zero import zero1_init
        opt = optax.sgd(0.1)
        cfg, params, tok, tgt = self._setup(opt)
        mesh = create_mesh(devices=jax.devices()[:2], tp=2)
        cfg2 = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, tp_axis="tp", remat=False)
        make, _, _ = build_train_step(cfg2, mesh, opt)
        with pytest.raises(ValueError, match="dp"):
            make(params, zero1_init(opt, params, n_shards=2))

    def test_n_shards_recorded_and_validated(self):
        """ADVICE low: a Zero1State built for one shard count must be
        rejected by make() against a mesh whose 'dp' axis differs — a
        clear ValueError naming both numbers, not an opaque jit
        sharding failure from mismatched flat-shard padding."""
        from horovod_tpu.parallel.zero import zero1_init
        opt = optax.adam(1e-2)
        cfg, params, tok, tgt = self._setup(opt)
        zstate = zero1_init(opt, params, n_shards=4)
        assert int(zstate.n_shards) == 4
        mesh = create_mesh(dp=8)
        make, _, _ = build_train_step(cfg, mesh, opt)
        with pytest.raises(ValueError,
                           match=r"n_shards=4.*'dp' axis has 8"):
            make(params, zstate)
        # The matching count passes validation and still trains.
        good = zero1_init(opt, params, n_shards=8)
        l_z, losses, s = self._train(cfg, mesh, params, tok, tgt, opt,
                                     good, steps=1)
        assert np.isfinite(losses[0])
        # n_shards survives the jitted step round-trip.
        assert int(np.asarray(s.n_shards)) == 8
