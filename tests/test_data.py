"""Pod-scale input pipeline (ISSUE 13, docs/data.md): deterministic
sharded loaders, prefetch-to-device, exactly-once resumable cursors,
and distributed batch norm."""

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import data
from horovod_tpu.data import sharding as shd


# ---------------------------------------------------------------------------
# Epoch plan determinism
# ---------------------------------------------------------------------------

class TestEpochPermutation:
    def test_pure_function_of_seed_and_epoch(self):
        a = shd.epoch_permutation(100, seed=7, epoch=3)
        b = shd.epoch_permutation(100, seed=7, epoch=3)
        np.testing.assert_array_equal(a, b)

    def test_epochs_and_seeds_differ(self):
        base = shd.epoch_permutation(100, seed=7, epoch=0)
        assert not np.array_equal(base, shd.epoch_permutation(100, 7, 1))
        assert not np.array_equal(base, shd.epoch_permutation(100, 8, 0))

    def test_is_a_permutation(self):
        p = shd.epoch_permutation(257, seed=0, epoch=5)
        np.testing.assert_array_equal(np.sort(p), np.arange(257))

    def test_no_shuffle_is_sequential(self):
        np.testing.assert_array_equal(
            shd.epoch_permutation(10, 3, 2, shuffle=False), np.arange(10))

    def test_drop_remainder_is_world_independent(self):
        # The usable count depends on (n, batch) only — the property the
        # elastic exactly-once contract rests on.
        for w in (1, 2, 4, 8):
            assert shd.usable_samples(70, 4) == 68, w


# ---------------------------------------------------------------------------
# Sources
# ---------------------------------------------------------------------------

class TestSources:
    def test_array_source_pairs(self):
        xs = np.arange(20).reshape(10, 2).astype(np.float32)
        ys = np.arange(10).astype(np.int32)
        src = data.as_source((xs, ys))
        got = src.take(np.array([3, 1]))
        np.testing.assert_array_equal(got[0], xs[[3, 1]])
        np.testing.assert_array_equal(got[1], ys[[3, 1]])

    def test_array_source_length_mismatch(self):
        with pytest.raises(ValueError, match="axis-0 length"):
            data.ArraySource(np.zeros((4, 2)), np.zeros((5,)))

    def test_file_list_source(self, tmp_path):
        paths = []
        for i in range(6):
            p = tmp_path / f"s{i}.npy"
            np.save(p, np.full((3,), float(i)))
            paths.append(str(p))
        src = data.as_source(paths)
        assert len(src) == 6
        (batch,) = src.take(np.array([4, 0, 5]))
        np.testing.assert_array_equal(batch[:, 0], [4.0, 0.0, 5.0])

    def test_callable_source_needs_length(self):
        fn = lambda ids: np.asarray(ids, np.float32) * 2  # noqa: E731
        with pytest.raises(ValueError, match="length"):
            data.as_source(fn)
        src = data.as_source(fn, length=9)
        assert len(src) == 9
        (b,) = src.take(np.array([1, 4]))
        np.testing.assert_array_equal(b, [2.0, 8.0])

    def test_synthetic_sample_is_pure_function_of_id(self):
        # Same id -> same sample regardless of which batch asks: the
        # property the exactly-once multiset checks rely on.
        a = data.synthetic("image", n=50, image_size=4, seed=3)
        b = data.synthetic("image", n=50, image_size=4, seed=3)
        ia, la = a.take(np.array([7, 3, 7]))
        ib, lb = b.take(np.array([7]))
        np.testing.assert_array_equal(ia[0], ia[2])
        np.testing.assert_array_equal(ia[0], ib[0])
        assert la[0] == lb[0]

    def test_synthetic_tokens_shape_and_range(self):
        src = data.synthetic("tokens", n=10, seq_len=16, vocab=100,
                             seed=1)
        (t,) = src.take(np.array([0, 9]))
        assert t.shape == (2, 16) and t.dtype == np.int32
        assert t.min() >= 0 and t.max() < 100

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="synthetic kind"):
            data.synthetic("video")


# ---------------------------------------------------------------------------
# Sharded loader
# ---------------------------------------------------------------------------

def _collect_epoch(src, *, world, batch, seed, **kw):
    """Run one epoch on `world` fresh loaders; returns per-rank id lists."""
    loaders = [data.build_loader(src, batch_size=batch, rank=r,
                                 world_size=world, seed=seed, epochs=1,
                                 **kw)
               for r in range(world)]
    out = [[] for _ in range(world)]
    for r, ld in enumerate(loaders):
        for b in ld:
            out[r].extend(b.ids.tolist())
    return out


class TestShardedLoader:
    def test_one_epoch_is_a_disjoint_cover(self):
        src = data.synthetic("image", n=70, image_size=4, seed=0)
        per_rank = _collect_epoch(src, world=2, batch=4, seed=11)
        flat = [i for ids in per_rank for i in ids]
        assert len(flat) == shd.usable_samples(70, 4) == 68
        assert len(set(flat)) == 68
        assert not (set(per_rank[0]) & set(per_rank[1]))
        ds = data.ShardedDataset(src, batch_size=4, seed=11)
        assert sorted(flat) == sorted(ds.epoch_ids(0).tolist())

    def test_identical_across_launches(self):
        # Two independent "launches" (fresh loaders) produce the same
        # per-rank batch sequence — the determinism contract.
        src = data.synthetic("image", n=64, image_size=4, seed=0)
        a = _collect_epoch(src, world=4, batch=4, seed=9)
        b = _collect_epoch(src, world=4, batch=4, seed=9)
        assert a == b

    def test_static_shapes_including_filler(self):
        # 3 microbatches on a world of 2: the final global step hands
        # rank 1 a zero-weight filler with the SAME static shapes.
        src = data.synthetic("image", n=12, image_size=4, num_classes=3,
                             seed=0)
        ld = data.build_loader(src, batch_size=4, rank=1, world_size=2,
                               seed=1, epochs=1)
        batches = list(ld)
        assert [b.weight for b in batches] == [4, 0]
        filler = batches[-1]
        assert filler.data[0].shape == (4, 4, 4, 3)
        assert filler.ids.size == 0
        np.testing.assert_array_equal(filler.data[0], 0.0)

    def test_epoch_rolls_over_with_new_permutation(self):
        src = data.synthetic("image", n=16, image_size=4, seed=0)
        ld = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                               seed=2, epochs=2)
        ids = [b.ids.tolist() for b in ld]
        assert len(ids) == 8
        e0, e1 = sum(ids[:4], []), sum(ids[4:], [])
        assert sorted(e0) == sorted(e1) == list(range(16))
        assert e0 != e1   # reshuffled per epoch

    def test_transform_applies_per_batch(self):
        src = data.synthetic("image", n=16, image_size=4, seed=0)
        ld = data.build_loader(
            src, batch_size=4, rank=0, world_size=1, seed=2, epochs=1,
            transform=lambda arrs: (arrs[0] * 0 + 7.0,) + arrs[1:])
        b = next(ld)
        np.testing.assert_array_equal(b.data[0], 7.0)

    def test_drop_remainder_false_rejected(self):
        with pytest.raises(ValueError, match="drop_remainder"):
            data.build_loader(np.zeros((10, 2)), batch_size=4,
                              rank=0, world_size=1, drop_remainder=False)

    def test_zero_microbatches_rejected(self):
        with pytest.raises(ValueError, match="zero whole"):
            data.build_loader(np.zeros((3, 2)), batch_size=4,
                              rank=0, world_size=1)

    def test_rank_outside_world_rejected(self):
        with pytest.raises(ValueError, match="outside world"):
            data.build_loader(np.zeros((8, 2)), batch_size=4, rank=2,
                              world_size=2)

    def test_metrics_families_registered(self):
        src = data.synthetic("image", n=8, image_size=4, seed=0)
        ld = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                               seed=0, epochs=1)
        list(ld)
        snap = hvd.metrics_snapshot()
        for fam in ("hvdtpu_data_samples_total",
                    "hvdtpu_data_load_seconds_total"):
            assert fam in snap, fam
        assert snap["hvdtpu_data_samples_total"]["values"][""] >= 8


# ---------------------------------------------------------------------------
# Cursor / exactly-once resume
# ---------------------------------------------------------------------------

class TestCursorResume:
    def test_cursor_roundtrip_continues_exactly(self):
        src = data.synthetic("image", n=40, image_size=4, seed=0)
        ld = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                               seed=5)
        seen = [next(ld).ids.tolist() for _ in range(3)]
        cur = ld.commit_cursor()
        resumed = data.build_loader(src, batch_size=4, rank=0,
                                    world_size=1, seed=5).restore(cur)
        ref = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                                seed=5)
        for _ in range(3):
            next(ref)
        for _ in range(4):
            np.testing.assert_array_equal(next(resumed).ids,
                                          next(ref).ids)
        assert seen  # consumed prefix untouched by the resume

    def test_exactly_once_across_world_shrink_and_regrow(self):
        # 2 ranks consume 4 global steps, commit; 1 rank consumes 3
        # more from the cursor, commits; 2 ranks finish the epoch. The
        # union is exactly one clean epoch — no duplicate, no gap.
        src = data.synthetic("image", n=100, image_size=4, seed=0)
        ds = data.ShardedDataset(src, batch_size=4, seed=21)
        consumed = []

        l2 = [data.build_loader(src, batch_size=4, rank=r, world_size=2,
                                seed=21) for r in range(2)]
        for _ in range(4):
            for ld in l2:
                consumed.extend(next(ld).ids.tolist())
        cur = l2[0].commit_cursor()

        l1 = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                               seed=21).restore(cur)
        for _ in range(3):
            consumed.extend(next(l1).ids.tolist())
        cur = l1.commit_cursor()

        l2b = [data.build_loader(src, batch_size=4, rank=r,
                                 world_size=2, seed=21, epochs=1
                                 ).restore(cur) for r in range(2)]
        for ld in l2b:
            for b in ld:
                consumed.extend(b.ids.tolist())

        assert len(consumed) == ds.usable == 100
        assert sorted(consumed) == sorted(ds.epoch_ids(0).tolist())

    def test_restore_counts_skips_and_notes_recorder(self):
        from horovod_tpu.observability import flight_recorder as fr

        src = data.synthetic("image", n=40, image_size=4, seed=0)
        ld = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                               seed=5)
        for _ in range(2):
            next(ld)
        cur = ld.commit_cursor()
        before = hvd.metrics_snapshot()[
            "hvdtpu_data_resume_skips_total"]["values"].get("", 0.0)
        data.build_loader(src, batch_size=4, rank=0, world_size=1,
                          seed=5).restore(cur)
        after = hvd.metrics_snapshot()[
            "hvdtpu_data_resume_skips_total"]["values"][""]
        assert after - before == 8.0
        kinds = [(k, p) for _, k, p in list(fr.recorder()._ring)
                 if k == "data"]
        assert any(p[0] == "cursor_commit" for _, p in kinds)
        assert any(p[0] == "resume" for _, p in kinds)

    def test_mismatched_plan_rejected(self):
        src = data.synthetic("image", n=40, image_size=4, seed=0)
        cur = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                                seed=5).commit_cursor()
        with pytest.raises(ValueError, match="exactly-once"):
            data.build_loader(src, batch_size=4, rank=0, world_size=1,
                              seed=6).restore(cur)
        with pytest.raises(ValueError, match="exactly-once"):
            data.build_loader(src, batch_size=8, rank=0, world_size=1,
                              seed=5).restore(cur)

    def test_cursor_rides_elastic_state(self, tmp_path):
        # The integration path docs/data.md#exactly-once shows: the
        # cursor is a tree in the same ElasticState commit as the model.
        src = data.synthetic("image", n=40, image_size=4, seed=0)
        ld = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                               seed=5)
        next(ld), next(ld)
        state = hvd.ElasticState(directory=str(tmp_path),
                                 params={"w": jnp.zeros((2,))},
                                 data=ld.commit_cursor())
        state.commit(2)
        fresh = hvd.ElasticState(directory=str(tmp_path),
                                 params={"w": jnp.ones((2,))},
                                 data=data.build_loader(
                                     src, batch_size=4, rank=0,
                                     world_size=1, seed=5).cursor())
        fresh.restore()
        resumed = data.build_loader(src, batch_size=4, rank=0,
                                    world_size=1, seed=5
                                    ).restore(fresh.data)
        assert resumed.offset == 2 and resumed.epoch == 0

    def test_cursor_rides_sharded_checkpoint_engine(self, tmp_path):
        # The tentpole path: the cursor checkpoints through the PR 4
        # sharded engine (ElasticState backend="sharded") like any
        # other replicated tree.
        src = data.synthetic("image", n=40, image_size=4, seed=0)
        ld = data.build_loader(src, batch_size=4, rank=0, world_size=1,
                               seed=5)
        for _ in range(3):
            next(ld)
        st = hvd.ElasticState(directory=str(tmp_path),
                              backend="sharded",
                              params={"w": jnp.arange(4.0)},
                              data=ld.commit_cursor())
        st.commit(3, block=True)
        fresh = hvd.ElasticState(
            directory=str(tmp_path), backend="sharded",
            params={"w": jnp.zeros(4)},
            data=data.build_loader(src, batch_size=4, rank=0,
                                   world_size=1, seed=5).cursor())
        fresh.restore()
        resumed = data.build_loader(src, batch_size=4, rank=0,
                                    world_size=1, seed=5
                                    ).restore(fresh.data)
        assert resumed.offset == 3 and resumed.epoch == 0
        np.testing.assert_array_equal(np.asarray(fresh.params["w"]),
                                      np.arange(4.0))

    def test_postmortem_surfaces_last_cursor(self, tmp_path):
        from horovod_tpu.observability import flight_recorder as fr
        from horovod_tpu.tools import postmortem

        fr.reset()
        rec = fr.recorder()
        rec.configure(rank=0, world=1)
        rec.note("data", ("cursor_commit", 2, 14, 0))
        rec.note("data", ("cursor_commit", 3, 6, 0))
        path = rec.dump("exception", directory=str(tmp_path))
        dump = postmortem.load_dump(path)
        report = postmortem.analyze([dump])
        assert report["per_rank"]["0"]["data_cursor"] == {
            "epoch": 3, "offset": 6}
        text = postmortem.format_report(report)
        assert "epoch 3 offset 6" in text
        fr.reset()


# ---------------------------------------------------------------------------
# Prefetch-to-device
# ---------------------------------------------------------------------------

class TestPrefetch:
    def _loader(self, n=32, batch=4, **kw):
        src = data.synthetic("image", n=n, image_size=4, seed=0)
        return data.build_loader(src, batch_size=batch, rank=0,
                                 world_size=1, seed=3, epochs=1, **kw)

    def test_batches_arrive_on_device_in_order(self):
        ref = [b.ids.tolist() for b in self._loader()]
        got = []
        for b in data.prefetch_to_device(self._loader(), depth=2):
            assert isinstance(b.data[0], jax.Array)
            got.append(b.ids.tolist())
        assert got == ref

    def test_mesh_shorthand_shards_over_dp(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        pf = data.prefetch_to_device(self._loader(), mesh, depth=2)
        b = next(pf)
        want = NamedSharding(mesh, P("dp"))
        assert b.data[0].sharding.is_equivalent_to(want, b.data[0].ndim)
        pf.close()

    def test_overlaps_a_slow_source(self):
        # With a 30 ms source and a 30 ms consumer, serial would take
        # ~2x the prefetched wall time; assert the overlap is real but
        # leave slack for CI scheduling noise.
        delay = 0.03
        steps = 6
        ld = self._loader(
            n=steps * 4,
            transform=lambda a: (time.sleep(delay), a)[1])
        t0 = time.perf_counter()
        n = 0
        for _ in data.prefetch_to_device(ld, depth=2):
            time.sleep(delay)   # the "step"
            n += 1
        wall = time.perf_counter() - t0
        assert n == steps
        assert wall < 2 * steps * delay * 0.95, wall

    def test_source_exception_propagates(self):
        def boom(arrs):
            raise RuntimeError("bad decode")
        pf = data.prefetch_to_device(self._loader(transform=boom))
        with pytest.raises(RuntimeError, match="bad decode"):
            next(pf)

    def test_depth_validated_and_gauges_set(self):
        with pytest.raises(ValueError, match="depth"):
            data.prefetch_to_device(self._loader(), depth=0)
        list(data.prefetch_to_device(self._loader(), depth=3))
        snap = hvd.metrics_snapshot()
        # depth bounds what can be resident ahead of the consumer
        assert 0 <= snap["hvdtpu_data_prefetch_occupancy"]["values"][""] <= 3
        assert snap["hvdtpu_data_wait_seconds_total"]["values"][""] > 0
        assert snap["hvdtpu_data_h2d_seconds_total"]["values"][""] > 0

    def test_stage_marks_timer(self):
        from horovod_tpu.observability import StepTimer
        timer = StepTimer("data_test_stage", batch_size=4)
        b = next(self._loader())
        timer.begin()
        staged = data.stage(b, timer=timer)
        timer.end()
        assert isinstance(staged.data[0], jax.Array)
        assert timer.last_phases["h2d"] > 0


class TestStepTimerH2DCredit:
    def test_credit_moves_gap_from_input_to_h2d(self):
        from horovod_tpu.observability import StepTimer
        timer = StepTimer("data_test_credit", batch_size=1)
        with timer:
            pass
        time.sleep(0.08)            # pre-step gap: 50/50 source vs copy
        timer.credit_h2d(0.04)
        with timer:
            time.sleep(0.01)
        ph = timer.last_phases
        assert 0.03 <= ph["h2d"] <= 0.06, ph
        assert ph["input"] >= 0.02, ph
        assert ph["input"] + ph["h2d"] >= 0.07, ph

    def test_credit_capped_at_actual_gap(self):
        from horovod_tpu.observability import StepTimer
        timer = StepTimer("data_test_cap", batch_size=1)
        with timer:
            pass
        timer.credit_h2d(10.0)      # absurd credit, tiny real gap
        with timer:
            pass
        ph = timer.last_phases
        assert ph["h2d"] <= 0.05, ph

    def test_credit_cleared_between_steps(self):
        from horovod_tpu.observability import StepTimer
        timer = StepTimer("data_test_clear", batch_size=1)
        with timer:
            pass
        time.sleep(0.03)
        timer.credit_h2d(0.03)
        with timer:
            pass
        first_h2d = timer.last_phases["h2d"]
        time.sleep(0.03)
        with timer:
            pass
        assert first_h2d > 0
        assert timer.last_phases["h2d"] == 0.0


# ---------------------------------------------------------------------------
# Distributed batch norm
# ---------------------------------------------------------------------------

class TestSyncBatchNorm:
    """Acceptance (ISSUE 13): dp=4 distributed BN matches single-device
    BN on the concatenated batch at rtol 1e-5, forward and gradients,
    via the fused (single-psum) collective path."""

    B, C = 16, 6

    def _mesh(self):
        return Mesh(np.array(jax.devices()[:4]), ("dp",))

    def _vars(self):
        rng = np.random.RandomState(0)
        return {
            "params": {
                "scale": jnp.asarray(rng.rand(self.C).astype(np.float32)
                                     + 0.5),
                "bias": jnp.asarray(rng.randn(self.C).astype(np.float32)),
            },
            "batch_stats": {"mean": jnp.zeros(self.C),
                            "var": jnp.ones(self.C)},
        }

    def _x(self):
        return jnp.asarray(np.random.RandomState(1).randn(
            self.B, 5, 5, self.C).astype(np.float32))

    def test_forward_matches_concatenated_batch(self):
        import flax.linen as nn
        from horovod_tpu.data.sync_bn import SyncBatchNorm

        x, variables = self._x(), self._vars()
        ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-5, dtype=jnp.float32)
        y_ref, upd_ref = ref.apply(variables, x, mutable=["batch_stats"])
        sbn = SyncBatchNorm(use_running_average=False, axis_name="dp")
        f = jax.jit(jax.shard_map(
            lambda xs: sbn.apply(variables, xs, mutable=["batch_stats"]),
            mesh=self._mesh(), in_specs=P("dp"),
            out_specs=(P("dp"), P())))
        y, upd = f(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-6)
        # Running statistics fold the identical global moments.
        for k in ("mean", "var"):
            np.testing.assert_allclose(
                np.asarray(upd["batch_stats"][k]),
                np.asarray(upd_ref["batch_stats"][k]), rtol=1e-5)

    def test_gradients_match_concatenated_batch(self):
        import flax.linen as nn
        from horovod_tpu.data.sync_bn import SyncBatchNorm

        x, variables = self._x(), self._vars()
        stats = variables["batch_stats"]
        ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-5, dtype=jnp.float32)

        def loss_ref(params, xs):
            y, _ = ref.apply({"params": params, "batch_stats": stats},
                             xs, mutable=["batch_stats"])
            return jnp.sum(jnp.sin(y))

        g_ref, gx_ref = jax.grad(loss_ref, argnums=(0, 1))(
            variables["params"], x)

        sbn = SyncBatchNorm(use_running_average=False, axis_name="dp")
        from horovod_tpu.parallel import collectives as coll

        def loss_dist(params, xs):
            def shard(xx):
                y, _ = sbn.apply(
                    {"params": params, "batch_stats": stats}, xx,
                    mutable=["batch_stats"])
                return coll.psum(jnp.sum(jnp.sin(y)), "dp")
            return jax.shard_map(shard, mesh=self._mesh(),
                                 in_specs=P("dp"), out_specs=P())(xs)

        g, gx = jax.grad(loss_dist, argnums=(0, 1))(
            variables["params"], x)
        for k in g_ref:
            np.testing.assert_allclose(np.asarray(g[k]),
                                       np.asarray(g_ref[k]),
                                       rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   rtol=1e-5, atol=1e-6)

    def test_single_psum_on_the_wire(self):
        # The fused path: ONE all-reduce carrying the concatenated
        # [sum, sum_sq] buffer — not one per moment.
        from horovod_tpu.data.sync_bn import sync_batch_norm

        variables = self._vars()

        def shard(xs):
            y, _, _ = sync_batch_norm(
                xs, variables["params"]["scale"],
                variables["params"]["bias"], axis_name="dp")
            return y

        f = jax.jit(jax.shard_map(shard, mesh=self._mesh(),
                                  in_specs=P("dp"), out_specs=P("dp")))
        text = f.lower(self._x()).as_text()
        assert text.count("all_reduce") == 1, text

    def test_inference_uses_running_stats_without_collective(self):
        from horovod_tpu.data.sync_bn import SyncBatchNorm

        variables = self._vars()
        sbn = SyncBatchNorm(use_running_average=True, axis_name="dp")
        # No mapped context at all: running-average mode must not touch
        # the axis.
        y = sbn.apply(variables, self._x())
        assert y.shape == self._x().shape

    def test_local_mode_without_axis(self):
        import flax.linen as nn
        from horovod_tpu.data.sync_bn import SyncBatchNorm

        variables = self._vars()
        x = self._x()
        y, _ = SyncBatchNorm(use_running_average=False,
                             axis_name=None).apply(
            variables, x, mutable=["batch_stats"])
        y_ref, _ = nn.BatchNorm(use_running_average=False, momentum=0.9,
                                epsilon=1e-5, dtype=jnp.float32).apply(
            variables, x, mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-6)


class TestModelAdoption:
    """The conv zoo takes distributed BN by constructor flag, sharing
    the local models' parameter trees (checkpoints interchangeable)."""

    def _mesh(self):
        return Mesh(np.array(jax.devices()[:4]), ("dp",))

    def test_resnet_sync_bn_matches_concatenated_batch(self):
        from horovod_tpu.models import ResNet

        kw = dict(stage_sizes=[1], num_classes=4, num_filters=8,
                  dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(0).rand(
            8, 16, 16, 3).astype(np.float32))
        local = ResNet(**kw)
        variables = local.init(jax.random.PRNGKey(0), x[:2], train=False)
        y_ref, _ = local.apply(variables, x, train=True,
                               mutable=["batch_stats"])

        dist = ResNet(bn_axis_name="dp", **kw)
        # Same parameter tree: a local checkpoint loads into the
        # sync-BN model unchanged.
        dv = dist.init(jax.random.PRNGKey(0), x[:2], train=False)
        assert jax.tree_util.tree_structure(dv) == \
            jax.tree_util.tree_structure(variables)

        f = jax.jit(jax.shard_map(
            lambda xs: dist.apply(variables, xs, train=True,
                                  mutable=["batch_stats"])[0],
            mesh=self._mesh(), in_specs=P("dp"), out_specs=P("dp")))
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_resnet_fused_plus_sync_rejected(self):
        from horovod_tpu.models import ResNet

        x = jnp.zeros((2, 16, 16, 3))
        model = ResNet(stage_sizes=[1], num_filters=8, num_classes=4,
                       bn_impl="jnp", bn_axis_name="dp")
        with pytest.raises(ValueError, match="bn_impl='flax'"):
            model.init(jax.random.PRNGKey(0), x, train=False)

    def test_vgg_sync_bn_param_tree_matches_local(self):
        from horovod_tpu.models import VGG

        cfg = ((1, 4), (1, 8))
        x = jnp.zeros((2, 8, 8, 3))
        local = VGG(cfg=cfg, num_classes=4, use_bn=True,
                    dtype=jnp.float32)
        dist = VGG(cfg=cfg, num_classes=4, use_bn=True,
                   dtype=jnp.float32, bn_axis_name="dp")
        vl = local.init({"params": jax.random.PRNGKey(0)}, x,
                        train=False)
        vd = dist.init({"params": jax.random.PRNGKey(0)}, x,
                       train=False)
        assert jax.tree_util.tree_structure(vl) == \
            jax.tree_util.tree_structure(vd)

    def test_inception_convbn_sync_matches_local(self):
        from horovod_tpu.models.inception import ConvBN

        x = jnp.asarray(np.random.RandomState(0).rand(
            8, 8, 8, 3).astype(np.float32))
        local = ConvBN(8, (3, 3), dtype=jnp.float32)
        variables = local.init(jax.random.PRNGKey(0), x[:2], train=False)
        y_ref = local.apply(variables, x, train=True,
                            mutable=["batch_stats"])[0]
        dist = ConvBN(8, (3, 3), dtype=jnp.float32, bn_axis_name="dp")
        f = jax.jit(jax.shard_map(
            lambda xs: dist.apply(variables, xs, train=True,
                                  mutable=["batch_stats"])[0],
            mesh=self._mesh(), in_specs=P("dp"), out_specs=P("dp")))
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
