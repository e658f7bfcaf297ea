"""Pipeline-schedule + hierarchical-reduction tests (docs/pipeline.md).

Numerics strategy mirrors test_parallel.py: every schedule's loss AND
stage gradients must match an unsharded single-program oracle (jax
autodiff through the composed stages) at rtol 1e-5, on pp=2 and pp=4 CPU
meshes with 4/8 microbatches; the hierarchical in-slice/cross-slice
reduction must match the flat allreduce it replaces."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import create_mesh
from horovod_tpu.parallel.mesh import axis_kinds, dcn_axes, ici_axes
from horovod_tpu.parallel.collectives import (cross_slice_bytes,
                                              hierarchical_psum)
from horovod_tpu.parallel.pipeline import (PipelineSchedule,
                                           pipeline_apply,
                                           pipeline_value_and_grad,
                                           schedule_info)
from horovod_tpu.parallel.train import build_train_step
from horovod_tpu.models import transformer as tfm


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _loss_fn(y):
    return jnp.mean(y.astype(jnp.float32) ** 2)


def _make_stages(n_total, d, seed=0):
    rng = np.random.RandomState(seed)
    return [{"w": jnp.asarray(rng.randn(d, d), jnp.float32) * 0.5,
             "b": jnp.asarray(rng.randn(d), jnp.float32) * 0.1}
            for _ in range(n_total)]


def _reference(stages, x_mb):
    """Single-program oracle: autodiff through the composed stages."""
    def total(stages):
        losses = []
        for j in range(x_mb.shape[0]):
            h = x_mb[j]
            for p in stages:
                h = _stage_fn(p, h)
            losses.append(_loss_fn(h))
        return jnp.mean(jnp.asarray(losses))
    return jax.value_and_grad(total)(stages)


def _pack_stages(stages, n, V):
    """Per-rank packing: rank r holds chunk-stages v*n + r, leaves
    [n, V, ...] (V=1 leaves [n, ...])."""
    def pack(*ls):
        arr = jnp.stack(ls)                       # [n*V, ...] chunk order
        if V == 1:
            return arr
        return arr.reshape((V, n) + arr.shape[1:]).swapaxes(0, 1)
    return jax.tree_util.tree_map(pack, *stages)


def _run_pipeline(schedule, n, m, V=1, d=4, mb=2, seed=0):
    mesh = create_mesh(devices=jax.devices()[:n], pp=n)
    stages = _make_stages(n * V, d, seed)
    x = jnp.asarray(np.random.RandomState(100 + seed).randn(m, mb, d),
                    jnp.float32)
    packed = _pack_stages(stages, n, V)

    def run(p_local, x):
        p = jax.tree_util.tree_map(lambda l: l[0], p_local)
        loss, g = pipeline_value_and_grad(
            _stage_fn, _loss_fn, p, x, axis_name="pp",
            schedule=schedule, num_virtual=V)
        return loss, jax.tree_util.tree_map(lambda l: l[None], g)

    f = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), packed), P()),
        out_specs=(P(), P("pp")), check_vma=False))
    loss, grads = f(packed, x)
    ref_loss, ref_grads = _reference(stages, x)
    return loss, grads, ref_loss, ref_grads, stages


def _grad_errs(grads, ref_grads, n, V):
    errs = []
    for c in range(n * V):
        r, v = c % n, c // n
        got = jax.tree_util.tree_map(
            lambda l: l[r] if V == 1 else l[r][v], grads)
        ref = ref_grads[c]
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            denom = max(float(jnp.max(jnp.abs(b))), 1e-9)
            errs.append(float(jnp.max(jnp.abs(a - b))) / denom)
    return max(errs)


class TestScheduleInfo:
    """Static tick/bubble accounting — the numbers the
    hvdtpu_pipeline_bubble_share gauge and BENCH_PIPELINE.json report."""

    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("m", [4, 8, 16])
    def test_bubble_ordering(self, n, m):
        g = schedule_info("gpipe", n, m).bubble_share
        o = schedule_info("1f1b", n, m).bubble_share
        i = schedule_info("interleaved", n, m,
                          num_virtual=2).bubble_share
        assert i < o < g

    def test_bubble_shrinks_with_microbatches(self):
        for sched, kw in [("gpipe", {}), ("1f1b", {}),
                          ("interleaved", {"num_virtual": 2}),
                          ("zb-h1", {})]:
            shares = [schedule_info(sched, 4, m, **kw).bubble_share
                      for m in (4, 8, 16, 32)]
            assert shares == sorted(shares, reverse=True), (sched, shares)

    def test_zb_h1_closed_form(self):
        # Backward split cB = cBx + cBw (even halves): only cBx rides
        # the fill/drain skew, so with cB=2 the bubble is
        # 2(n-1)/(3m + 2(n-1)) — 1/3 at n=m=4 vs 1f1b's 3/7.
        s = schedule_info("zb-h1", 4, 4)
        assert s.bubble_share == pytest.approx(1 / 3)
        assert s.ticks == {"warmup": 3, "steady": 4, "drain": 3}
        assert schedule_info("zb-h1", 4, 16).bubble_share == \
            pytest.approx(6 / 54)

    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("m", [4, 8, 16])
    def test_zb_h1_strictly_below_1f1b(self, n, m):
        # The acceptance bar: at equal microbatch counts the static
        # bubble is STRICTLY below 1f1b's for every n > 1.
        zb = schedule_info("zb-h1", n, m).bubble_share
        o = schedule_info("1f1b", n, m).bubble_share
        assert zb < o, (n, m, zb, o)

    def test_1f1b_closed_form(self):
        # Residual stashing removes the recompute: bubble is exactly
        # the fill fraction (n-1)/(m+n-1).
        s = schedule_info("1f1b", 4, 12)
        assert s.bubble_share == pytest.approx(3 / 15)
        i = schedule_info("interleaved", 4, 12, num_virtual=3)
        assert i.bubble_share == pytest.approx(3 / 39)

    def test_tick_budgets(self):
        s = schedule_info("1f1b", 4, 8)
        assert s.ticks == {"warmup": 3, "steady": 8, "drain": 3}
        i = schedule_info("interleaved", 2, 4, num_virtual=3)
        assert i.ticks == {"warmup": 5, "steady": 8, "drain": 5}
        g = schedule_info("gpipe", 4, 8)
        assert g.ticks["warmup"] == g.ticks["drain"] == 11

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown"):
            schedule_info("dualpipe", 4, 8)
        with pytest.raises(ValueError, match="zb-h1"):
            schedule_info("zb-h1", 4, 2)
        with pytest.raises(ValueError, match="multiple"):
            schedule_info("interleaved", 4, 6, num_virtual=2)
        with pytest.raises(ValueError, match="multiple"):
            schedule_info("interleaved", 4, 2, num_virtual=2)
        with pytest.raises(ValueError, match="num_virtual"):
            schedule_info("interleaved", 4, 8, num_virtual=1)


class TestForwardPipeline:
    """pipeline_apply: the relay replication must equal both the old
    psum path and the unsharded composition."""

    @pytest.mark.parametrize("mode", ["relay", "psum"])
    def test_matches_composition(self, mode):
        n, m, d, mb = 4, 5, 4, 2
        mesh = create_mesh(devices=jax.devices()[:n], pp=n)
        stages = _make_stages(n, d)
        packed = _pack_stages(stages, n, 1)
        x = jnp.asarray(np.random.RandomState(7).randn(m, mb, d),
                        jnp.float32)

        def run(p_local, x):
            p = jax.tree_util.tree_map(lambda l: l[0], p_local)
            return pipeline_apply(_stage_fn, p, x, axis_name="pp",
                                  replicate_output=mode)

        f = jax.jit(jax.shard_map(
            run, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), packed),
                      P()),
            out_specs=P(), check_vma=False))
        out = f(packed, x)
        h = x
        for p in stages:
            h = jax.vmap(lambda xx, p=p: _stage_fn(p, xx))(h)
        assert float(jnp.max(jnp.abs(out - h))) < 1e-6

    def test_relay_equals_psum_bitwise(self):
        n, m, d, mb = 4, 6, 4, 2
        mesh = create_mesh(devices=jax.devices()[:n], pp=n)
        stages = _make_stages(n, d, seed=3)
        packed = _pack_stages(stages, n, 1)
        x = jnp.asarray(np.random.RandomState(8).randn(m, mb, d),
                        jnp.float32)
        outs = {}
        for mode in ("relay", "psum"):
            def run(p_local, x, mode=mode):
                p = jax.tree_util.tree_map(lambda l: l[0], p_local)
                return pipeline_apply(_stage_fn, p, x, axis_name="pp",
                                      replicate_output=mode)
            f = jax.jit(jax.shard_map(
                run, mesh=mesh,
                in_specs=(jax.tree_util.tree_map(lambda _: P("pp"),
                                                 packed), P()),
                out_specs=P(), check_vma=False))
            outs[mode] = np.asarray(f(packed, x))
        # Both replications move the SAME last-stage values (psum adds
        # exact zeros; relay copies) — bitwise equal.
        assert np.array_equal(outs["relay"], outs["psum"])

    def test_bad_replicate_kwarg(self):
        with pytest.raises(ValueError, match="relay"):
            mesh = create_mesh(devices=jax.devices()[:2], pp=2)
            jax.jit(jax.shard_map(
                lambda x: pipeline_apply(_stage_fn, {"w": x[0]}, x,
                                         replicate_output="bcast"),
                mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False))(jnp.ones((2, 2, 2)))


class TestScheduleParity:
    """The flagship guarantee: every schedule's loss and per-stage
    gradients equal the single-program reference at rtol 1e-5."""

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "zb-h1"])
    @pytest.mark.parametrize("n,m", [(2, 4), (2, 8), (4, 4), (4, 8)])
    def test_matches_single_program(self, schedule, n, m):
        loss, grads, ref_loss, ref_grads, _ = _run_pipeline(
            schedule, n, m)
        assert abs(float(loss) - float(ref_loss)) <= \
            1e-5 * max(abs(float(ref_loss)), 1e-9)
        assert _grad_errs(grads, ref_grads, n, 1) < 1e-5

    @pytest.mark.parametrize("n,m,V", [(2, 4, 2), (4, 4, 2), (4, 8, 2),
                                       (2, 8, 3)])
    def test_interleaved_matches_single_program(self, n, m, V):
        loss, grads, ref_loss, ref_grads, _ = _run_pipeline(
            "interleaved", n, m, V=V)
        assert abs(float(loss) - float(ref_loss)) <= \
            1e-5 * max(abs(float(ref_loss)), 1e-9)
        assert _grad_errs(grads, ref_grads, n, V) < 1e-5

    def test_1f1b_fewer_microbatches_than_stages(self):
        loss, grads, ref_loss, ref_grads, _ = _run_pipeline("1f1b", 4, 3)
        assert abs(float(loss) - float(ref_loss)) <= 1e-5
        assert _grad_errs(grads, ref_grads, 4, 1) < 1e-5

    def test_schedules_agree_with_each_other(self):
        """gpipe, 1f1b and zb-h1 are the same math on different
        schedules — they must agree with each other as tightly as with
        the oracle (zb-h1's Bx and W come from the same VJP closure the
        fused backward calls)."""
        l1, g1, _, _, _ = _run_pipeline("gpipe", 4, 8, seed=5)
        for sched in ("1f1b", "zb-h1"):
            l2, g2, _, _, _ = _run_pipeline(sched, 4, 8, seed=5)
            assert abs(float(l1) - float(l2)) < 1e-6, sched
            for a, b in zip(jax.tree_util.tree_leaves(g1),
                            jax.tree_util.tree_leaves(g2)):
                assert float(jnp.max(jnp.abs(a - b))) < 1e-6, sched

    def test_zb_h1_needs_enough_microbatches(self):
        with pytest.raises(ValueError, match="zb-h1"):
            _run_pipeline("zb-h1", 4, 3)

    def test_unknown_schedule_rejected(self):
        mesh = create_mesh(devices=jax.devices()[:2], pp=2)
        with pytest.raises(ValueError, match="unknown"):
            jax.jit(jax.shard_map(
                lambda x: pipeline_value_and_grad(
                    _stage_fn, _loss_fn, {"w": jnp.eye(2)}, x,
                    schedule="dualpipe"),
                mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
                check_vma=False))(jnp.ones((2, 2, 2)))


def _head_loss(lp, y, tgt):
    return jnp.mean((y @ lp["w"] - tgt) ** 2)


def _run_pipeline_heads(schedule, n, m, d=4, mb=2, seed=0):
    """Pipeline run with the loss-head extensions armed: trainable
    loss_params, per-microbatch loss_aux targets, and input grads."""
    mesh = create_mesh(devices=jax.devices()[:n], pp=n)
    stages = _make_stages(n, d, seed)
    rng = np.random.RandomState(200 + seed)
    x = jnp.asarray(rng.randn(m, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(m, mb, d), jnp.float32)
    lp = {"w": jnp.asarray(rng.randn(d, d), jnp.float32) * 0.3}
    packed = _pack_stages(stages, n, 1)

    def run(p_local, lp, x, tgt):
        p = jax.tree_util.tree_map(lambda l: l[0], p_local)
        loss, g, extras = pipeline_value_and_grad(
            _stage_fn, _head_loss, p, x, axis_name="pp",
            schedule=schedule, loss_aux=tgt, loss_params=lp,
            return_input_grads=True)
        return (loss, jax.tree_util.tree_map(lambda l: l[None], g),
                extras["loss_params_grads"], extras["input_grads"])

    f = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), packed),
                  P(), P(), P()),
        out_specs=(P(), P("pp"), P(), P()), check_vma=False))
    loss, grads, lp_g, x_g = f(packed, lp, x, tgt)

    def total(stages, lp, x):
        losses = []
        for j in range(m):
            h = x[j]
            for p in stages:
                h = _stage_fn(p, h)
            losses.append(_head_loss(lp, h, tgt[j]))
        return jnp.mean(jnp.asarray(losses))

    ref_loss, (ref_g, ref_lp_g, ref_x_g) = jax.value_and_grad(
        total, argnums=(0, 1, 2))(stages, lp, x)
    return (loss, grads, lp_g, x_g), (ref_loss, ref_g, ref_lp_g, ref_x_g)


class TestPipelineLossHeads:
    """The loss-head extensions (docs/pipeline.md): trainable
    loss_params gradients psum'd from the last stage, per-microbatch
    loss_aux, and stage-0 input grads — on the fused AND the
    split-backward (zb-h1) schedules."""

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "zb-h1"])
    def test_heads_match_oracle(self, schedule):
        n, m = 4, 8
        (loss, grads, lp_g, x_g), (ref_loss, ref_g, ref_lp_g, ref_x_g) \
            = _run_pipeline_heads(schedule, n, m)
        assert abs(float(loss) - float(ref_loss)) <= \
            1e-5 * max(abs(float(ref_loss)), 1e-9)
        assert _grad_errs(grads, ref_g, n, 1) < 1e-5
        for a, b in zip(jax.tree_util.tree_leaves(lp_g),
                        jax.tree_util.tree_leaves(ref_lp_g)):
            denom = max(float(jnp.max(jnp.abs(b))), 1e-9)
            assert float(jnp.max(jnp.abs(a - b))) / denom < 1e-5
        denom = max(float(jnp.max(jnp.abs(ref_x_g))), 1e-9)
        assert float(jnp.max(jnp.abs(x_g - ref_x_g))) / denom < 1e-5


class TestPipelineTrainStep:
    """build_pipeline_train_step cuts the flagship transformer over
    'pp' automatically; one optimizer step must match the unsharded
    single-program step (tied embedding: input-path pullback + softmax
    head) at rtol 1e-5."""

    def _cfg(self):
        return tfm.TransformerConfig(
            vocab=64, d_model=16, n_heads=2, n_layers=4, d_ff=32,
            max_seq=8, dtype=jnp.float32, use_flash=False, remat=False)

    def _parity(self, schedule, n, V=1, m=4):
        import optax
        from horovod_tpu.parallel.train import (build_pipeline_train_step,
                                                from_pipeline_params,
                                                to_pipeline_params)
        cfg = self._cfg()
        B, S = 8, cfg.max_seq
        rng = np.random.RandomState(11)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32)
        targets = jnp.asarray(rng.randint(0, cfg.vocab, (B, S)),
                              jnp.int32)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        opt = optax.sgd(0.05)

        # Single-program oracle: one SGD step on the flat layout.
        loss_ref, grads_ref = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tokens, targets, cfg))(params)
        updates, _ = opt.update(grads_ref, opt.init(params), params)
        params_ref = optax.apply_updates(params, updates)

        mesh = create_mesh(devices=jax.devices()[:n], pp=n)
        make, shard_params, shard_batch = build_pipeline_train_step(
            cfg, mesh, opt, schedule=schedule, num_virtual=V)
        pparams = to_pipeline_params(cfg, params, n, V)
        opt_state = opt.init(pparams)
        step, _ = make(pparams, opt_state)
        pparams = shard_params(pparams)
        tok_mb = shard_batch(tokens.reshape(m, B // m, S))
        tgt_mb = shard_batch(targets.reshape(m, B // m, S))
        pparams, opt_state, loss = step(pparams, opt_state, tok_mb,
                                        tgt_mb)
        assert abs(float(loss) - float(loss_ref)) <= \
            1e-5 * max(abs(float(loss_ref)), 1e-9), schedule
        back = from_pipeline_params(cfg, jax.device_get(pparams), n, V)
        flat_a = jax.tree_util.tree_leaves(back)
        flat_b = jax.tree_util.tree_leaves(params_ref)
        for a, b in zip(flat_a, flat_b):
            denom = max(float(jnp.max(jnp.abs(b))), 1e-9)
            assert float(jnp.max(jnp.abs(a - b))) / denom < 1e-5, schedule

    @pytest.mark.parametrize("schedule", ["1f1b", "zb-h1"])
    def test_flagship_step_matches_single_program(self, schedule):
        self._parity(schedule, n=4)

    def test_interleaved_flagship_step(self):
        self._parity("interleaved", n=2, V=2)

    def test_rejects_non_pp_mesh(self):
        import optax
        from horovod_tpu.parallel.train import build_pipeline_train_step
        cfg = self._cfg()
        mesh = create_mesh(devices=jax.devices()[:4], dp=4)
        with pytest.raises(ValueError, match="pp"):
            build_pipeline_train_step(cfg, mesh, optax.sgd(0.1))

    def test_rejects_indivisible_layers(self):
        import optax
        from horovod_tpu.parallel.train import build_pipeline_train_step
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=16, n_heads=2, n_layers=6, d_ff=32,
            max_seq=8, dtype=jnp.float32, use_flash=False, remat=False)
        mesh = create_mesh(devices=jax.devices()[:4], pp=4)
        with pytest.raises(ValueError, match="divide"):
            build_pipeline_train_step(cfg, mesh, optax.sgd(0.1))


class TestPipelineWithDataParallel:
    """pp × dp: per-dp-shard pipelines + gradient reduction over the
    data axes — hierarchical (in-slice 'dp' then cross-slice 'dcn')
    against the flat allreduce it replaces, identical gradients."""

    def _run(self, reduction):
        n, m, d, mb = 2, 4, 4, 2
        mesh = create_mesh(pp=n, dcn=2, dp=2)
        stages = _make_stages(n, d, seed=9)
        packed = _pack_stages(stages, n, 1)
        # Global batch: [m, dcn*dp*mb, d]; each data shard pipelines its
        # own microbatch slice.
        x = jnp.asarray(np.random.RandomState(11).randn(m, 4 * mb, d),
                        jnp.float32)

        def run(p_local, x_local):
            p = jax.tree_util.tree_map(lambda l: l[0], p_local)
            loss, g = pipeline_value_and_grad(
                _stage_fn, _loss_fn, p, x_local, axis_name="pp",
                schedule="1f1b")
            loss = lax.pmean(loss, ("dcn", "dp"))
            if reduction == "hier":
                g = jax.tree_util.tree_map(
                    lambda t: hierarchical_psum(t, "dp", "dcn",
                                                average=True), g)
            else:
                g = jax.tree_util.tree_map(
                    lambda t: lax.pmean(t, ("dcn", "dp")), g)
            return loss, jax.tree_util.tree_map(lambda l: l[None], g)

        f = jax.jit(jax.shard_map(
            run, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), packed),
                      P(None, ("dcn", "dp"))),
            out_specs=(P(), P("pp")), check_vma=False))
        loss, grads = f(packed, x)
        return float(loss), grads, stages, x

    def test_hierarchical_equals_flat(self):
        loss_h, g_h, _, _ = self._run("hier")
        loss_f, g_f, _, _ = self._run("flat")
        assert abs(loss_h - loss_f) < 1e-7
        for a, b in zip(jax.tree_util.tree_leaves(g_h),
                        jax.tree_util.tree_leaves(g_f)):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-6

    def test_matches_oracle(self):
        loss_h, g_h, stages, x = self._run("hier")
        ref_loss, ref_grads = _reference(stages, x)
        assert abs(loss_h - float(ref_loss)) <= 1e-5
        assert _grad_errs(g_h, ref_grads, 2, 1) < 1e-5


class TestHierarchicalCollectives:
    def test_hierarchical_psum_equals_flat(self):
        mesh = create_mesh(dcn=2, dp=4)
        x = jnp.asarray(np.random.RandomState(0).randn(777), jnp.float32)
        flat = jax.jit(jax.shard_map(
            lambda v: lax.psum(v, ("dcn", "dp")), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False))(x)
        hier = jax.jit(jax.shard_map(
            lambda v: hierarchical_psum(v, "dp", "dcn"), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False))(x)
        err = float(jnp.max(jnp.abs(flat - hier)))
        assert err < 1e-5 * float(jnp.max(jnp.abs(flat)))

    def test_hierarchical_psum_wire_quantized(self):
        mesh = create_mesh(dcn=2, dp=4)
        x = jnp.asarray(np.random.RandomState(1).randn(512), jnp.float32)
        flat = jax.jit(jax.shard_map(
            lambda v: lax.psum(v, ("dcn", "dp")), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False))(x)
        q = jax.jit(jax.shard_map(
            lambda v: hierarchical_psum(v, "dp", "dcn", wire="int8x256"),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))(x)
        rel = float(jnp.max(jnp.abs(q - flat)) / jnp.max(jnp.abs(flat)))
        assert rel < 1e-2   # int8 wire tolerance (docs/compression.md)

    def test_average(self):
        mesh = create_mesh(dcn=2, dp=4)
        x = jnp.ones((64,), jnp.float32)
        out = jax.jit(jax.shard_map(
            lambda v: hierarchical_psum(v, "dp", "dcn", average=True),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-6)

    def test_cross_slice_bytes(self):
        flat = cross_slice_bytes(1000, 4, hierarchical=False)
        hier = cross_slice_bytes(1000, 4)
        wired = cross_slice_bytes(1000, 4, wire="int8x256")
        assert flat == 4000
        assert hier == 1000            # 250 fp32 elements
        assert wired < hier < flat


class TestMeshTopology:
    def test_cpu_mesh_is_all_ici(self):
        mesh = create_mesh(dcn=2, dp=4)
        assert set(axis_kinds(mesh).values()) == {"ici"}
        assert dcn_axes(mesh) == ()
        assert set(ici_axes(mesh)) == {"dcn", "dp"}

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_DCN_AXES", "dcn")
        mesh = create_mesh(dcn=2, dp=4)
        assert axis_kinds(mesh) == {"dcn": "dcn", "dp": "ici"}
        assert dcn_axes(mesh) == ("dcn",)
        assert ici_axes(mesh) == ("dp",)


class TestTrainStepHierarchical:
    """build_train_step(dcn_axis=...): the two-stage reduction trains
    identically to the single-device step."""

    def _setup(self):
        import optax
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, remat=False)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        tgt = jnp.roll(tok, -1, axis=1)
        return cfg, params, tok, tgt, optax.sgd(0.1)

    def _train(self, cfg, mesh, params, tok, tgt, opt, **kw):
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt, **kw)
        state = opt.init(params)
        step, _ = make(params, state)
        # The step donates its params; each run trains a copy so the
        # caller's tree survives for the next mesh.
        p, _, loss = step(
            shard_p(jax.tree_util.tree_map(jnp.copy, params)), state,
            shard_b(tok), shard_b(tgt))
        return [np.asarray(l, np.float32)
                for l in jax.tree_util.tree_leaves(p)], float(loss)

    def test_hierarchical_equals_the_single_device_step(self):
        cfg, params, tok, tgt, opt = self._setup()
        mesh = create_mesh(dcn=2, dp=4)
        l_hier, loss_h = self._train(cfg, mesh, params, tok, tgt, opt,
                                     dcn_axis="dcn")
        mesh1 = create_mesh(devices=jax.devices()[:1], dp=1)
        l1, loss1 = self._train(cfg, params=params, mesh=mesh1, tok=tok,
                                tgt=tgt, opt=opt)
        assert abs(loss_h - loss1) < 1e-5
        err1 = max(np.max(np.abs(a - b)) for a, b in zip(l_hier, l1))
        assert err1 < 1e-4, f"hier vs single-device divergence {err1}"

    def test_auto_discovery_uses_env_override(self, monkeypatch):
        cfg, params, tok, tgt, opt = self._setup()
        mesh = create_mesh(dcn=2, dp=4)
        monkeypatch.setenv("HOROVOD_TPU_DCN_AXES", "dcn")
        l_auto, loss_a = self._train(cfg, mesh, params, tok, tgt, opt,
                                     dcn_axis="auto")
        l_expl, loss_e = self._train(cfg, mesh, params, tok, tgt, opt,
                                     dcn_axis="dcn")
        assert loss_a == loss_e
        for a, b in zip(l_auto, l_expl):
            assert np.array_equal(a, b)

    def test_bad_dcn_axis_rejected(self):
        cfg, params, tok, tgt, opt = self._setup()
        mesh = create_mesh(dcn=2, dp=4)
        with pytest.raises(ValueError, match="not a mesh axis"):
            build_train_step(cfg, mesh, opt, dcn_axis="nope")

    def test_under_dcn_the_state_stays_replicated_and_trains(self):
        """``hierarchical_psum`` owns the 'dp' reduction and ends in its
        own all-gather, so the weight update is not sharded: AdamW's
        state specs come back replicated (on the same mesh without
        ``dcn_axis`` they carry 'dp'), the moments come back whole on
        every device, and two steps are the one-device step's."""
        import optax
        cfg, params, tok, tgt, _ = self._setup()
        opt = optax.adamw(1e-2)
        mesh = create_mesh(dcn=2, dp=4)
        state_shapes = jax.eval_shape(opt.init, params)
        make_plain, _, _ = build_train_step(cfg, mesh, opt)
        _, plain_specs = make_plain(params, state_shapes)
        assert "dp" in plain_specs[0].mu["layers"][0]["wi"]

        def two_steps(mesh, **kw):
            make, shard_p, shard_b = build_train_step(cfg, mesh, opt, **kw)
            step, specs = make(params, state_shapes)
            p = shard_p(jax.tree_util.tree_map(jnp.copy, params))
            s = opt.init(params)
            for _ in range(2):
                p, s, loss = step(p, s, shard_b(tok), shard_b(tgt))
            return p, s, specs, float(loss)

        p, s, specs, loss = two_steps(mesh, dcn_axis="dcn")
        for spec in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)):
            assert all(entry is None for entry in spec), spec
        for m in jax.tree_util.tree_leaves(s):
            assert m.sharding.is_fully_replicated
        p1, _, _, loss1 = two_steps(
            create_mesh(devices=jax.devices()[:1], dp=1))
        assert abs(loss - loss1) < 1e-5
        for a, b in zip(jax.tree_util.tree_leaves(p),
                        jax.tree_util.tree_leaves(p1)):
            assert np.max(np.abs(np.asarray(a) - np.asarray(b))) < 1e-4


class TestPipelineObservability:
    def test_bubble_gauge_and_recorder_event(self):
        from horovod_tpu import metrics_snapshot
        from horovod_tpu.observability import flight_recorder as fr
        _run_pipeline("1f1b", 2, 4)
        snap = metrics_snapshot().get("hvdtpu_pipeline_bubble_share", {})
        vals = snap.get("values", {})
        got = {k: v for k, v in vals.items() if 'schedule="1f1b"' in k}
        assert got, vals
        expect = schedule_info("1f1b", 2, 4).bubble_share
        assert list(got.values())[0] == pytest.approx(expect, abs=1e-5)
        ticks = metrics_snapshot().get("hvdtpu_pipeline_ticks", {}).get(
            "values", {})
        assert any('phase="steady"' in k for k in ticks)
        events = [e for e in list(fr.recorder()._ring)
                  if e[1] == "pipeline"]
        assert events, "pipeline build must leave a flight-recorder event"
        payload = events[-1][2]
        assert payload[0] == "1f1b" and payload[1] == 2 and payload[2] == 4

    def test_postmortem_attributes_pipelined_step(self, tmp_path):
        from horovod_tpu.observability import flight_recorder as fr
        from horovod_tpu.tools import postmortem
        fr.reset()
        rec = fr.recorder()
        rec.configure(rank=0, world=1)
        rec.note("pipeline", ("1f1b", 4, 8, 1, 3, 8, 3, 0.2727))
        rec.note("step", (5,))
        path = rec.dump("exception", directory=str(tmp_path))
        dump = postmortem.load_dump(path)
        report = postmortem.analyze([dump])
        row = report["per_rank"]["0"]
        assert row["pipeline_schedule"] == "1f1b"
        assert "schedule 1f1b" in row["death_phase"]
        assert "3/8/3" in row["death_phase"]
        fr.reset()


@pytest.mark.slow
class TestBenchPipelineReproducible:
    def test_bench_pipeline_smoke_and_determinism(self, tmp_path):
        """bench_engine.py --pipeline regenerates BENCH_PIPELINE rows
        reproducibly (seeded, static bubble/byte accounting) and the
        acceptance ordering holds: 1f1b and interleaved bubble strictly
        below gpipe at every microbatch count, shrinking as microbatch
        count grows, numerics parity vs the single-program reference at
        rtol 1e-5, and the hierarchical reduction moving strictly fewer
        cross-slice bytes than flat with identical gradients."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        outs = []
        for i in range(2):
            out = tmp_path / f"bench{i}.json"
            subprocess.run(
                [sys.executable, os.path.join(root, "bench_engine.py"),
                 "--pipeline", "--pipeline-microbatches", "4,8",
                 "--out", str(out)],
                check=True, capture_output=True, text=True, timeout=600,
                cwd=root)
            outs.append(json.loads(out.read_text()))
        a, b = outs

        def strip_ms(obj):
            if isinstance(obj, dict):
                return {k: strip_ms(v) for k, v in obj.items()
                        if not k.endswith("_ms")}
            return obj

        assert strip_ms(a["bubble"]) == strip_ms(b["bubble"])
        for sched, rows in a["bubble"].items():
            for mkey, row in rows.items():
                assert row["parity_max_rel_err"] <= 1e-5, (sched, mkey)
        for m in ("4", "8"):
            gp = a["bubble"]["gpipe"][m]["bubble_share"]
            fb = a["bubble"]["1f1b"][m]["bubble_share"]
            il = a["bubble"]["interleaved"][m]["bubble_share"]
            assert il < fb < gp
        assert a["bubble"]["1f1b"]["8"]["bubble_share"] < \
            a["bubble"]["1f1b"]["4"]["bubble_share"]
        hier = a["hierarchical"]
        assert hier["hier"]["dcn_bytes_per_step"] < \
            hier["flat"]["dcn_bytes_per_step"]
        assert hier["hier_int8"]["dcn_bytes_per_step"] < \
            hier["hier"]["dcn_bytes_per_step"]
        assert hier["hier"]["grad_max_abs_diff_vs_flat"] < 1e-5
        assert strip_ms(hier) == strip_ms(b["hierarchical"])
