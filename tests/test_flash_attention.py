"""Pallas flash-attention kernel tests — interpreter mode on the CPU
mesh (the compiled Mosaic path is exercised on real TPU by bench/dev
runs; the math is identical).

Covers: forward vs full attention (causal and not, ragged block
boundaries), backward dq/dk/dv vs autodiff of full attention, bf16
tolerance, and the transformer's use_flash path end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import full_attention


def _rand(shape, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape), dtype)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full(self, causal):
        q = _rand((2, 64, 4, 16))
        k = _rand((2, 64, 4, 16), seed=1)
        v = _rand((2, 64, 4, 16), seed=2)
        out = flash_attention(q, k, v, causal, None, 32, 32, True)
        ref = full_attention(q, k, v, causal=causal)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_single_block(self):
        # Sequence smaller than the block: one grid step, no rescaling.
        q = _rand((1, 16, 2, 8))
        out = flash_attention(q, q, q, True, None, 128, 128, True)
        ref = full_attention(q, q, q, causal=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_uneven_blocks(self):
        # Blocks that do not divide the sequence evenly exercise cdiv
        # padding in the grid.
        q = _rand((1, 48, 2, 8))
        out = flash_attention(q, q, q, True, None, 32, 32, True)
        ref = full_attention(q, q, q, causal=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_bf16(self):
        q = _rand((1, 64, 4, 16), jnp.bfloat16)
        out = flash_attention(q, q, q, True, None, 32, 32, True)
        ref = full_attention(q, q, q, causal=True)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < 3e-2


class TestFlashBackward:
    def test_grads_match_full(self):
        q = _rand((1, 64, 2, 16))
        k = _rand((1, 64, 2, 16), seed=1)
        v = _rand((1, 64, 2, 16), seed=2)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, True, None, 32, 32, True)
            return (o.astype(jnp.float32) ** 2).sum()

        def loss_full(q, k, v):
            o = full_attention(q, k, v, causal=True)
            return (o.astype(jnp.float32) ** 2).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-3

    def test_noncausal_grads(self):
        q = _rand((1, 32, 2, 8))

        def lf(q):
            return (flash_attention(q, q, q, False, None, 16, 16,
                                    True) ** 2).sum()

        def lr(q):
            return (full_attention(q, q, q, causal=False) ** 2).sum()

        g1 = jax.grad(lf)(q)
        g2 = jax.grad(lr)(q)
        assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-3

    @pytest.mark.parametrize("causal", [True, False])
    def test_tail_block_grads(self, causal):
        """Backward through cdiv-padded tail blocks: seq 40 with 16x16
        blocks leaves a ragged tail row/column, exercising the
        masked=True branch of _block_dispatch in all three kernels
        (the even-seq tests only ever compile the unmasked branch)."""
        q = _rand((1, 40, 2, 8))
        k = _rand((1, 40, 2, 8), seed=1)
        v = _rand((1, 40, 2, 8), seed=2)

        def lf(q, k, v):
            o = flash_attention(q, k, v, causal, None, 16, 16, True)
            return (o.astype(jnp.float32) ** 2).sum()

        def lr(q, k, v):
            return (full_attention(q, k, v, causal=causal) ** 2).sum()

        g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-3

    def test_unequal_block_grads(self):
        """block_q != block_k in the backward kernels (the compiled
        defaults are rectangular: dkv 512x1024, dq 1024x512)."""
        q = _rand((1, 64, 2, 8))

        def lf(q):
            o = flash_attention(q, q, q, True, None, 16, 32, True)
            return (o.astype(jnp.float32) ** 2).sum()

        def lr(q):
            return (full_attention(q, q, q, causal=True) ** 2).sum()

        g1 = jax.grad(lf)(q)
        g2 = jax.grad(lr)(q)
        assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-3

    def test_bf16_grads(self):
        """bf16 inputs make the backward's operand casts (p, ds to
        bf16 before the MXU) real rather than no-ops; grads must stay
        within bf16 rounding of the full-attention autodiff."""
        q = _rand((1, 48, 2, 16), jnp.bfloat16)
        k = _rand((1, 48, 2, 16), jnp.bfloat16, seed=1)
        v = _rand((1, 48, 2, 16), jnp.bfloat16, seed=2)

        def lf(q, k, v):
            o = flash_attention(q, k, v, True, None, 16, 16, True)
            return (o.astype(jnp.float32) ** 2).sum()

        def lr(q, k, v):
            o = full_attention(q, k, v, causal=True)
            return (o.astype(jnp.float32) ** 2).sum()

        g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            # grads here are O(10); 1e-1 absolute is ~1% relative —
            # a few bf16 ulps across the two accumulation orders
            assert err < 1e-1, err


class TestNamedResiduals:
    """The kernel's output and row statistics carry the checkpoint names
    ``hvd_flash_out`` / ``hvd_flash_lse``: a policy that saves them
    spares the backward a second forward kernel, and the compact form
    in which the statistics are held changes no value."""

    @staticmethod
    def _loss(q, k, v):
        o = flash_attention(q, k, v, True, None, 16, 16, True)
        return (o.astype(jnp.float32) ** 2).sum()

    @staticmethod
    def _forward_kernels(fn, *args):
        return str(jax.make_jaxpr(fn)(*args)).count("name=hvd_flash_fwd")

    def test_the_statistics_are_held_compact_and_change_no_gradient(self):
        from horovod_tpu.ops import flash_attention as fa
        q = _rand((2, 48, 2, 16))
        k = _rand((2, 48, 2, 16), seed=1)
        v = _rand((2, 48, 2, 16), seed=2)
        named = {str(e.params["name"]): e.outvars[0].aval.shape
                 for e in jax.make_jaxpr(jax.grad(self._loss))(q, k, v).eqns
                 if e.primitive.name == "name"}
        assert named == {"hvd_flash_out": (2, 48, 2, 16),
                         "hvd_flash_lse": (2 * 2, 48)}
        # the kernels themselves, on the statistics as the forward emits
        # them ([B*H, S, 1]): what the rule computed before it named any
        qb, kb, vb = fa._to_bh(q), fa._to_bh(k), fa._to_bh(v)
        ob, lse = fa._flash_fwd(qb, kb, vb, 16 ** -0.5, True, 16, 16, True)
        gb = 2 * ob
        delta = jnp.sum(gb * ob, axis=-1, keepdims=True)
        want = fa._flash_bwd(qb, kb, vb, gb, lse, delta, 16 ** -0.5, True,
                             16, 16, True)
        got = jax.grad(self._loss, (0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(fa._to_bh(a)),
                                          np.asarray(b))

    @pytest.mark.parametrize("names,forwards", [
        (("hvd_flash_out", "hvd_flash_lse"), 1),
        (("hvd_flash_out",), 2),        # lse alone still needs the kernel
        (("hvd_flash_lse",), 2),        # and so does the output alone
        ((), 2),
    ])
    def test_a_policy_that_saves_both_names_spares_the_second_forward(
            self, names, forwards):
        q = _rand((1, 32, 2, 16))
        policy = jax.checkpoint_policies.save_only_these_names(*names)

        # sin: something between the inputs and the kernel to recompute
        def plain(q):
            return self._loss(jnp.sin(q), q, q)

        inner = jax.checkpoint(plain, policy=policy)
        assert self._forward_kernels(jax.grad(inner), q) == forwards
        np.testing.assert_array_equal(np.asarray(jax.grad(inner)(q)),
                                      np.asarray(jax.grad(plain)(q)))

    def test_the_names_are_identities_outside_a_checkpoint(self):
        q = _rand((1, 32, 2, 16))
        assert self._forward_kernels(
            jax.grad(lambda q: self._loss(q, q, q)), q) == 1
        # undifferentiated (serving): one kernel, nothing held
        assert self._forward_kernels(
            lambda q: flash_attention(q, q, q, True, None, 16, 16, True),
            q) == 1


class TestTransformerFlash:
    def test_use_flash_train_step(self):
        import optax

        from horovod_tpu.models import transformer as tfm

        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, use_flash=True, remat=False)
        rng = jax.random.PRNGKey(0)
        params = tfm.init_params(cfg, rng)
        tokens = jax.random.randint(rng, (2, 32), 0, 64)

        def loss_fn(p):
            logits = tfm.apply(p, tokens, cfg)
            tgt = jnp.roll(tokens, -1, axis=1)
            oh = jax.nn.one_hot(tgt, 64)
            return -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * oh, axis=-1))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(loss))
        gnorm = sum(float(jnp.sum(jnp.abs(g)))
                    for g in jax.tree_util.tree_leaves(grads))
        assert gnorm > 0

        # flash and full attention agree through the whole model
        cfg_full = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, use_flash=False, remat=False)
        logits_flash = tfm.apply(params, tokens, cfg)
        logits_full = tfm.apply(params, tokens, cfg_full)
        assert float(jnp.max(jnp.abs(logits_flash - logits_full))) < 1e-3

    def test_flash_block_config_threads_through(self, monkeypatch):
        """cfg.flash_block REACHES the kernel (round-4 long-seq sweep
        knob): capture the block args at the flash_attention call and
        check the output still matches full attention. The capture is
        what makes this a real thread-through test — default-block
        flash would also match full attention numerically."""
        from horovod_tpu.models import transformer as tfm
        from horovod_tpu.ops import flash_attention as fa

        seen = []
        real = fa.flash_attention

        def spy(q, k, v, causal=True, scale=None, block_q=None,
                block_k=None, interpret=False):
            seen.append((block_q, block_k))
            return real(q, k, v, causal, scale, block_q, block_k,
                        interpret)

        monkeypatch.setattr(fa, "flash_attention", spy)

        base = dict(vocab=64, d_model=32, n_heads=4, n_layers=2,
                    d_ff=64, max_seq=64, dtype=jnp.float32, remat=False)
        cfg_b16 = tfm.TransformerConfig(use_flash=True, flash_block=16,
                                        **base)
        cfg_full = tfm.TransformerConfig(use_flash=False, **base)
        rng = jax.random.PRNGKey(1)
        params = tfm.init_params(cfg_b16, rng)
        tokens = jax.random.randint(rng, (2, 64), 0, 64)
        lo_b = tfm.apply(params, tokens, cfg_b16)
        assert seen and all(bq == 16 and bk == 16 for bq, bk in seen), seen
        lo_f = tfm.apply(params, tokens, cfg_full)
        assert float(jnp.max(jnp.abs(lo_b - lo_f))) < 1e-3
