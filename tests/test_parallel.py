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


def _collective_scopes(step, *args):
    """``{primitive name: set of name stacks}`` for the collectives in
    the step's jaxpr, wherever they are nested."""
    from horovod_tpu.parallel.zero import sub_jaxprs
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("reduce_scatter", "all_gather"):
                found.setdefault(eqn.primitive.name, set()).add(
                    str(eqn.source_info.name_stack))
            for sub in sub_jaxprs(eqn):
                walk(sub)

    walk(jax.make_jaxpr(step)(*args).jaxpr)
    return found


class TestShardedUpdate:
    """The sharded weight update (parallel/train.py, arXiv:2004.13336):
    on a dp mesh an elementwise optimizer updates 1/dp of every
    parameter a shard, between a reduce-scatter of the gradients and an
    all-gather of the new parameters; the state make() asks for is
    dp-sharded in optax's own structure. Oracle: the one-device step on
    the whole batch."""

    CFG = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               max_seq=32, dtype=jnp.float32, remat=False)

    def _data(self, cfg, batch=8):
        tok = jax.random.randint(jax.random.PRNGKey(1),
                                 (batch, cfg.max_seq), 0, cfg.vocab)
        return tok, jnp.roll(tok, -1, axis=1)

    def _train(self, cfg, mesh, opt, params, tok, tgt, steps=3,
               from_specs=False):
        """``steps`` steps; the state either a replicated
        ``opt.init(params)`` (the examples' way) or made in the layout
        make() returns (the benchmark's way). Returns the parameters as
        global arrays, the state's specs, the state and the losses."""
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        if from_specs:
            step, specs = make(params, jax.eval_shape(opt.init, params))
            state = jax.jit(opt.init, out_shardings=jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P)))(params)
        else:
            state = opt.init(params)
            step, specs = make(params, state)
        p = shard_p(_copy_tree(params))
        tk, tg = shard_b(tok), shard_b(tgt)
        losses = []
        for _ in range(steps):
            p, state, loss = step(p, state, tk, tg)
            losses.append(float(loss))
        return p, specs, state, losses

    def _one_device(self, cfg, opt, params, tok, tgt, **kw):
        axes = {"dp": 1, "tp": 1} if cfg.tp_axis else {"dp": 1}
        mesh = create_mesh(devices=jax.devices()[:1], **axes)
        return self._train(cfg, mesh, opt, params, tok, tgt, **kw)

    @staticmethod
    def _assert_close(got, want, rtol=1e-6):
        """Within ``rtol`` of each leaf's largest magnitude (the order
        of the dp-way sum differs, nothing else)."""
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree_util.tree_leaves(want)):
            a, b = np.asarray(a), np.asarray(b)
            assert np.max(np.abs(a - b)) <= rtol * np.max(np.abs(b)), \
                jax.tree_util.keystr(path)

    @staticmethod
    def _assert_replicas_bit_equal(params):
        for path, leaf in jax.tree_util.tree_leaves_with_path(params):
            copies = [np.asarray(s.data) for s in leaf.addressable_shards
                      if s.index == leaf.addressable_shards[0].index]
            assert len(copies) > 1, jax.tree_util.keystr(path)
            for c in copies[1:]:
                np.testing.assert_array_equal(
                    c, copies[0], err_msg=jax.tree_util.keystr(path))

    @staticmethod
    def _moment_specs(specs):
        """The specs of the first param-shaped subtree of the state."""
        return specs[0].mu

    @pytest.mark.parametrize("dp", [2, 4, 8])
    def test_three_steps_match_the_one_device_step(self, dp):
        cfg = tfm.TransformerConfig(**self.CFG)
        opt = optax.adamw(1e-3)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh = create_mesh(devices=jax.devices()[:dp], dp=dp)
        got, specs, state, losses = self._train(cfg, mesh, opt, params,
                                                tok, tgt)
        want, _, _, losses1 = self._one_device(cfg, opt, params, tok, tgt)
        np.testing.assert_allclose(losses, losses1, rtol=1e-5)
        self._assert_close(got, want)
        self._assert_replicas_bit_equal(got)
        # every moment leaf of this model has a dimension to shard on,
        # and comes back holding 1/dp of it a device
        for spec in jax.tree_util.tree_leaves(
                self._moment_specs(specs),
                is_leaf=lambda x: isinstance(x, P)):
            assert "dp" in spec
        for m in jax.tree_util.tree_leaves((state[0].mu, state[0].nu)):
            assert m.addressable_shards[0].data.size == m.size // dp

    def test_every_moment_leaf_holds_one_over_dp_a_device(self):
        """Adam on dp=8, the state made in the layout make() returns:
        before and after a step every moment leaf of dp or more elements
        is on all 8 devices, 1/8 of it each; the count is replicated."""
        cfg = tfm.TransformerConfig(**self.CFG)
        opt = optax.adam(1e-2)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh = create_mesh(dp=8)
        for steps in (0, 1):
            _, _, state, _ = self._train(cfg, mesh, opt, params, tok, tgt,
                                         steps=steps, from_specs=True)
            moments = [m for m in jax.tree_util.tree_leaves(state)
                       if m.ndim >= 1 and m.size >= 8]
            assert len(moments) == 2 * len(
                jax.tree_util.tree_leaves(params))
            for m in moments:
                assert len(m.sharding.device_set) == 8
                assert {s.data.size for s in m.addressable_shards} == {
                    m.size // 8}
            assert state[0].count.sharding.is_fully_replicated

    def test_state_from_the_specs_and_a_replicated_state_train_alike(self):
        cfg = tfm.TransformerConfig(**self.CFG)
        opt = optax.adamw(1e-3)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh = create_mesh(devices=jax.devices()[:4], dp=4)
        a, specs_a, _, _ = self._train(cfg, mesh, opt, params, tok, tgt,
                                       from_specs=True)
        b, specs_b, _, _ = self._train(cfg, mesh, opt, params, tok, tgt)
        assert specs_a == specs_b
        mu = self._moment_specs(specs_a)
        assert mu["layers"][0]["wi"] == P(None, "dp")
        assert mu["layers"][0]["ln1"] == P("dp")
        assert specs_a[0].count == P()
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_a_global_norm_clip_keeps_the_replicated_update(self):
        """An optimizer that looks across elements would be wrong on
        shards: make() sees that from the optimizer itself, asks for a
        replicated state, and the step is the one it was."""
        cfg = tfm.TransformerConfig(**self.CFG)
        opt = optax.chain(optax.clip_by_global_norm(1.0),
                          optax.adamw(1e-3))
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh = create_mesh(devices=jax.devices()[:4], dp=4)
        got, specs, _, _ = self._train(cfg, mesh, opt, params, tok, tgt)
        for spec in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)):
            assert "dp" not in spec
        want, _, _, _ = self._one_device(cfg, opt, params, tok, tgt)
        self._assert_close(got, want)
        self._assert_replicas_bit_equal(got)

    def test_a_leaf_no_dimension_of_which_divides_stays_on_psum(self):
        """d_model 30 on dp=4: only the position table [32, 30] has a
        dimension that divides (its first); every other leaf is reduced
        with a psum and updated whole, in the same step."""
        cfg = tfm.TransformerConfig(**dict(
            self.CFG, vocab=63, d_model=30, n_heads=2, d_ff=62))
        opt = optax.adamw(1e-3)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh = create_mesh(devices=jax.devices()[:4], dp=4)
        got, specs, _, _ = self._train(cfg, mesh, opt, params, tok, tgt)
        mu = self._moment_specs(specs)
        assert mu["pos"] == P("dp", None)
        for name in ("ln1", "wq", "wi", "wo_mlp"):
            assert "dp" not in mu["layers"][0][name], name
        assert "dp" not in mu["embed"]
        want, _, _, _ = self._one_device(cfg, opt, params, tok, tgt)
        self._assert_close(got, want)
        self._assert_replicas_bit_equal(got)

    def test_a_tp_sharded_leaf_takes_dp_on_another_dimension(self):
        cfg = tfm.TransformerConfig(**dict(self.CFG, tp_axis="tp"))
        # a bfloat16 first moment, as the benchmark's cells hold it: a
        # sum that rounds the other way moves it by 2**-8 and a step by
        # 4e-6 (2**-8 of the learning rate), so this case is held to
        # three such steps
        opt = optax.adamw(1e-3, mu_dtype=jnp.bfloat16)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh = create_mesh(devices=jax.devices()[:4], dp=2, tp=2)
        got, specs, state, _ = self._train(cfg, mesh, opt, params, tok,
                                           tgt, from_specs=True)
        own = tfm.param_specs(cfg)["layers"][0]
        mu = self._moment_specs(specs)["layers"][0]
        assert own["wq"] == P(None, "tp") and mu["wq"] == P("dp", "tp")
        assert own["wo"] == P("tp", None) and mu["wo"] == P("tp", "dp")
        wq = state[0].nu["layers"][0]["wq"]
        assert wq.addressable_shards[0].data.shape == (16, 16)
        assert wq.dtype == jnp.float32
        assert state[0].mu["layers"][0]["wq"].dtype == jnp.bfloat16
        want, _, _, _ = self._one_device(cfg, opt, params, tok, tgt)
        self._assert_close(got, want, rtol=5e-5)

    def test_dp_by_tp_adamw_matches_the_one_device_step(self):
        """dp 4 x tp 2, float32 AdamW (stateful, so a layout fault
        cannot hide in an empty state): a tp-sharded leaf's moments are
        split over dp on its other dimension and training is the
        one-device step's."""
        cfg = tfm.TransformerConfig(**dict(self.CFG, tp_axis="tp"))
        opt = optax.adamw(1e-2)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh = create_mesh(dp=4, tp=2)
        got, specs, state, losses = self._train(
            cfg, mesh, opt, params, tok, tgt, steps=4, from_specs=True)
        mu = self._moment_specs(specs)["layers"][0]
        assert mu["wq"] == P("dp", "tp") and mu["wo"] == P("tp", "dp")
        assert state[0].nu["layers"][0]["wi"].addressable_shards[
            0].data.shape == (8, 32)
        want, _, _, losses1 = self._one_device(cfg, opt, params, tok, tgt,
                                               steps=4)
        np.testing.assert_allclose(losses, losses1, rtol=1e-5)
        self._assert_close(got, want, rtol=1e-5)

    @pytest.mark.parametrize("axes", [{"dp": 1}, {"tp": 2}],
                             ids=["dp1", "tp2-no-dp-axis"])
    def test_on_one_data_shard_nothing_is_sharded(self, axes):
        """A dp axis of one device, or a mesh with no 'dp' axis at all:
        the state's specs are the parameters' own, no collective joins
        the gradient exchange, and the step trains."""
        tp = "tp" if "tp" in axes else None
        cfg = tfm.TransformerConfig(**dict(self.CFG, tp_axis=tp))
        opt = optax.adamw(1e-3)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg, batch=2)
        n = int(np.prod(list(axes.values())))
        mesh = create_mesh(devices=jax.devices()[:n], **axes)
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        state = opt.init(params)
        step, specs = make(params, state)
        assert self._moment_specs(specs) == tfm.param_specs(cfg)
        for spec in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)):
            assert "dp" not in spec, spec
        args = (shard_p(_copy_tree(params)), state, shard_b(tok),
                shard_b(tgt))
        scopes = _collective_scopes(step, *args)
        for prim in ("reduce_scatter", "all_gather"):
            assert not any("hvd_grad_reduce" in s
                           for s in scopes.get(prim, ())), scopes
        if tp is None:
            # (the psums over the one-device axis are the program's as
            # it was; XLA drops them)
            assert not scopes
            text = step.lower(*args).as_text()
            assert "reduce_scatter" not in text
            assert "all_gather" not in text
        got, _, loss = step(*args)
        want, _, _, losses1 = self._one_device(
            tfm.TransformerConfig(**self.CFG), opt, params, tok, tgt,
            steps=1)
        np.testing.assert_allclose(float(loss), losses1[0], rtol=1e-5)
        self._assert_close(got, want, rtol=1e-5)

    def test_a_state_saved_under_one_dp_trains_on_under_another(
            self, tmp_path):
        """The layout does not depend on the shard count (no leaf is
        raveled or padded): parameters and moments after a step on
        dp=4, saved through ``CheckpointEngine``, restored and placed
        under dp=8's specs, train on bit for bit like the same values
        placed there without the checkpoint, and like the one-device
        run."""
        from horovod_tpu.checkpoint import CheckpointEngine
        cfg = tfm.TransformerConfig(**self.CFG)
        opt = optax.adamw(1e-3)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh4 = create_mesh(devices=jax.devices()[:4], dp=4)
        p4, specs4, s4, _ = self._train(cfg, mesh4, opt, params, tok, tgt,
                                        steps=1, from_specs=True)
        assert self._moment_specs(specs4)["layers"][0]["wi"] == P(
            None, "dp")
        eng = CheckpointEngine(str(tmp_path / "ck"),
                               barrier=lambda name: None)
        eng.save({"params": p4, "state": s4}, 1, block=True)
        restored = eng.restore(template={"params": p4, "state": s4})
        host = jax.tree_util.tree_map(np.asarray,
                                      {"params": p4, "state": s4})

        mesh8 = create_mesh(dp=8)
        make, shard_p, shard_b = build_train_step(cfg, mesh8, opt)
        step, specs8 = make(params, jax.eval_shape(opt.init, params))
        assert specs8 == specs4

        def two_more_steps(tree):
            p = shard_p(tree["params"])
            s = jax.tree_util.tree_map(
                lambda x, spec: jax.device_put(
                    x, NamedSharding(mesh8, spec)),
                tree["state"], specs8)
            for m in jax.tree_util.tree_leaves((s[0].mu, s[0].nu)):
                assert m.addressable_shards[0].data.size == m.size // 8
            for _ in range(2):
                p, s, loss = step(p, s, shard_b(tok), shard_b(tgt))
            return p, float(loss)

        got, loss = two_more_steps(restored)
        direct, loss_direct = two_more_steps(host)
        assert loss == loss_direct
        for x, y in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(direct)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        want, _, _, losses1 = self._one_device(cfg, opt, params, tok, tgt)
        np.testing.assert_allclose(loss, losses1[-1], rtol=1e-5)
        # (two orders of summation in one run, four-way then eight-way)
        self._assert_close(got, want, rtol=5e-6)

    def test_the_exchange_is_named_and_counts_the_same_bytes(self):
        from horovod_tpu.parallel.train import _grad_reduce_bytes
        cfg = tfm.TransformerConfig(**self.CFG)
        opt = optax.adamw(1e-3)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok, tgt = self._data(cfg)
        mesh = create_mesh(devices=jax.devices()[:4], dp=4)
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        state = opt.init(params)
        step, _ = make(params, state)
        args = (shard_p(params), state, shard_b(tok), shard_b(tgt))
        scopes = _collective_scopes(step, *args)
        n_leaves = len(jax.tree_util.tree_leaves(params))
        assert scopes["reduce_scatter"] and scopes["all_gather"]
        for prim in ("reduce_scatter", "all_gather"):
            assert all("hvd_grad_reduce" in s for s in scopes[prim]), \
                scopes[prim]
        text = step.lower(*args).as_text()
        assert text.count("stablehlo.reduce_scatter") == n_leaves
        assert text.count("stablehlo.all_gather") == n_leaves
        # float32 gradients, 4 bytes a parameter, as the all-reduce read
        assert _grad_reduce_bytes().value == 4 * sum(
            x.size for x in jax.tree_util.tree_leaves(params))


ELEMENTWISE_CASES = [
    ("adamw_bf16_mu", lambda: optax.adamw(1e-3, mu_dtype=jnp.bfloat16), True),
    ("adam", lambda: optax.adam(1e-3), True),
    ("sgd", lambda: optax.sgd(0.1), True),
    ("nesterov", lambda: optax.sgd(0.1, momentum=0.9, nesterov=True), True),
    ("rmsprop", lambda: optax.rmsprop(1e-3), True),
    ("lion", lambda: optax.lion(1e-4), True),
    ("adagrad", lambda: optax.adagrad(1e-2), True),
    ("adamw_on_a_schedule", lambda: optax.adamw(
        optax.warmup_cosine_decay_schedule(0.0, 1e-3, 10, 100)), True),
    ("value_clip", lambda: optax.chain(optax.clip(1.0),
                                       optax.adamw(1e-3)), True),
    ("multi_steps", lambda: optax.MultiSteps(
        optax.adam(1e-3), 2).gradient_transformation(), True),
    ("global_norm_clip", lambda: optax.chain(
        optax.clip_by_global_norm(1.0), optax.adamw(1e-3)), False),
    # a threshold no probe's norm would reach: the trace still sees it
    ("global_norm_clip_far_off", lambda: optax.chain(
        optax.clip_by_global_norm(1e30), optax.sgd(0.1)), False),
    ("lamb", lambda: optax.lamb(1e-3), False),
    ("lars", lambda: optax.lars(1e-3), False),
    ("adafactor", lambda: optax.adafactor(1e-3), False),
    ("apply_if_finite", lambda: optax.apply_if_finite(
        optax.adam(1e-3), 3), False),
    ("add_noise", lambda: optax.chain(optax.add_noise(0.1, 0.5, 0),
                                      optax.sgd(0.1)), False),
]


@pytest.mark.parametrize("build,want",
                         [(b, w) for _, b, w in ELEMENTWISE_CASES],
                         ids=[n for n, _, _ in ELEMENTWISE_CASES])
def test_is_elementwise_reads_it_off_the_optimizer(build, want):
    """Separable by element or not, from the traced ``update`` alone
    (parallel/zero.py): what passes may be updated a shard at a time."""
    from horovod_tpu.parallel.zero import is_elementwise
    opt = build()
    params = {"w": jax.ShapeDtypeStruct((8, 6), jnp.float32),
              "b": jax.ShapeDtypeStruct((6,), jnp.float32)}
    assert is_elementwise(
        opt, params, jax.eval_shape(opt.init, params)) is want


@pytest.mark.parametrize("shape,spec,n,want", [
    ((2048, 8192), P(), 4, P(None, "dp")),
    ((50257, 2048), P(), 4, P(None, "dp")),       # the tied embedding
    ((2048, 30), P(), 4, P("dp", None)),           # the last does not divide
    ((2048,), P(), 4, P("dp")),
    ((30,), P(), 4, P()),                          # nothing divides
    ((), P(), 4, P()),
    ((32, 32), P(None, "tp"), 2, P("dp", "tp")),
    ((32, 32), P("tp", None), 2, P("tp", "dp")),
    ((4, 32, 64), P("ep"), 2, P("ep", None, "dp")),
    ((6, 32), P(("tp", "ep")), 4, P(("tp", "ep"), "dp")),
    ((8, 8), P("dp"), 4, P("dp")),                 # already over dp
], ids=str)
def test_update_shard_specs_take_the_last_free_dimension(shape, spec, n,
                                                         want):
    from horovod_tpu.parallel.zero import shard_dim, update_shard_specs
    got = update_shard_specs(
        {"x": jax.ShapeDtypeStruct(shape, jnp.float32)}, {"x": spec}, n)["x"]
    assert got == want
    d = shard_dim(spec, got)
    if "dp" in want and "dp" not in tuple(spec):
        assert tuple(got)[d] == "dp" and shape[d] % n == 0
    else:
        assert d is None
