"""Multi-process eager collectives over the TCP control plane.

The reference runs its suite under ``mpirun -np 2`` so real collectives
cross process boundaries (.travis.yml:100-111). The TPU-native analogue:
these tests launch REAL worker subprocesses through the runner; each
worker initializes ``jax.distributed`` (CPU platform, 1 device each), and
eager collectives negotiate through the rank-0 TCP coordinator
(ops/control_plane.py) and execute as SPMD XLA programs over the
2-device global mesh.

Marked slow: each test pays subprocess + jax.distributed startup.
"""

import numpy as np
import pytest

from horovod_tpu.runner.api import run

# Workers are plain CPU, one device each: N local workers on an
# accelerator host would all reach for the same chips.
_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}

pytestmark = pytest.mark.slow


class TestMultiProcessCollectives:
    def test_two_process_collectives(self):
        def worker():
            # Nested so cloudpickle ships it by value (module-level test
            # functions are not importable in the worker).
            import jax.numpy as jnp
            import numpy as np

            import horovod_tpu as hvd

            hvd.init()
            r, n = hvd.rank(), hvd.size()
            out = {}

            # allreduce: per-process values; sum == sum over ranks.
            x = jnp.full((4,), float(r + 1))
            s = hvd.allreduce(x, average=False, name="mp.sum")
            out["sum"] = np.asarray(s).tolist()

            a = hvd.allreduce(jnp.full((3,), float(r)), average=True,
                              name="mp.avg")
            out["avg"] = np.asarray(a).tolist()

            # fused pair enqueued together (same cycle -> one group)
            h1 = hvd.allreduce_async(jnp.ones((5,)), average=False,
                                     name="mp.f1")
            h2 = hvd.allreduce_async(jnp.full((5,), 2.0), average=False,
                                     name="mp.f2")
            out["f1"] = np.asarray(hvd.synchronize(h1)).tolist()
            out["f2"] = np.asarray(hvd.synchronize(h2)).tolist()

            # broadcast from the last virtual rank (process 1, 1 dev/proc)
            b = hvd.broadcast(jnp.full((2,), float(10 * (r + 1))),
                              root_rank=n - 1, name="mp.bc")
            out["bcast"] = np.asarray(b).tolist()

            g = hvd.allgather(jnp.full((2,), float(r)), name="mp.ag")
            out["gather"] = np.asarray(g).tolist()

            # uint32 broadcast: the dtype of jax PRNG keys — must ride
            # the wire (HVD_UINT32), not die in the codec.
            key = hvd.broadcast(jnp.asarray([r + 7, r + 9], jnp.uint32),
                                root_rank=0, name="mp.key")
            out["key"] = np.asarray(key).tolist()

            # ragged allgather: rank r contributes r+1 rows
            rg = hvd.allgather(jnp.full((r + 1, 2), float(r)),
                               name="mp.agv")
            out["ragged"] = np.asarray(rg).tolist()
            return out

        results = run(worker, np=2, extra_env=dict(_ENV),
                      start_timeout=300)
        for r in results:
            assert r["sum"] == [3.0] * 4          # 1 + 2
            assert r["avg"] == [0.5] * 3          # (0+1)/2
            assert r["f1"] == [2.0] * 5
            assert r["f2"] == [4.0] * 5
            assert r["bcast"] == [20.0, 20.0]     # root = rank 1
            assert r["gather"] == [0.0, 0.0, 1.0, 1.0]
            assert r["key"] == [7, 9]             # rank 0's uint32 values
        ragged = np.array(results[0]["ragged"])
        assert ragged.shape == (3, 2)             # 1 row + 2 rows
        assert np.allclose(ragged, [[0, 0], [1, 1], [1, 1]])
        assert results[0] == results[1]

    def test_training_loop_end_to_end(self):
        def train():
            import jax
            import jax.numpy as jnp
            import optax

            import horovod_tpu as hvd

            hvd.init()
            r = hvd.rank()
            # Per-rank data shard: y = 2x, rank r sees offset slice.
            xs = jnp.arange(8.0) + 4 * r
            ys = 2.0 * xs
            params = {"w": jnp.asarray(0.0)}
            params = hvd.broadcast_parameters(params, root_rank=0)
            opt = optax.sgd(0.02)
            state = opt.init(params)
            for step in range(40):
                def loss_fn(p):
                    return jnp.mean((p["w"] * xs - ys) ** 2)
                loss, grads = jax.value_and_grad(loss_fn)(params)
                # Eager cross-process gradient averaging (the
                # DistributedOptimizer hook path).
                grads = {"w": hvd.allreduce(grads["w"], average=True,
                                            name=f"g.{step}")}
                updates, state = opt.update(grads, state, params)
                params = optax.apply_updates(params, updates)
            return float(params["w"])

        results = run(train, np=2, extra_env=dict(_ENV), start_timeout=300)
        assert len(results) == 2
        # Both ranks converge to the same w ~= 2 (identical averaged grads).
        assert abs(results[0] - results[1]) < 1e-6
        assert abs(results[0] - 2.0) < 0.1

    def test_mismatched_shapes_error(self):
        def fn():
            import jax.numpy as jnp

            import horovod_tpu as hvd
            from horovod_tpu.ops import HorovodInternalError

            hvd.init()
            shape = (3,) if hvd.rank() == 0 else (5,)
            try:
                hvd.allreduce(jnp.ones(shape), name="mp.bad")
                return "no error"
            except (HorovodInternalError, ValueError) as e:
                return f"error: {e}"

        results = run(fn, np=2, extra_env=dict(_ENV), start_timeout=300)
        for r in results:
            assert "Mismatched allreduce tensor shapes" in r

    def test_mismatched_average_errors_not_hangs(self):
        """Two processes passing different
        ``average`` for one tensor get a Mismatched error, not a hang
        (the attribute rides the wire's device slot as an
        execution-semantics fingerprint)."""
        def fn():
            import jax.numpy as jnp

            import horovod_tpu as hvd
            from horovod_tpu.ops import HorovodInternalError

            hvd.init()
            avg = hvd.rank() == 0
            try:
                hvd.allreduce(jnp.ones((4,)), average=avg, name="mp.avgmix")
                return "no error"
            except (HorovodInternalError, ValueError) as e:
                return f"error: {e}"

        results = run(fn, np=2, extra_env=dict(_ENV), start_timeout=300)
        for r in results:
            assert "Mismatched execution attributes" in r


class TestMultiDevicePerProcess:
    def test_two_procs_two_devices_each(self):
        """2 processes x 2 virtual devices: size == 4 virtual ranks; each
        device contributes its process's eager value (the virtual-rank
        semantics extended across hosts), and ragged allgather expands
        per-process dims by local device count."""
        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        }

        def worker():
            import jax.numpy as jnp
            import numpy as np

            import horovod_tpu as hvd

            hvd.init()
            pr = hvd.process_rank()
            out = {"size": hvd.size(), "local_size": hvd.local_size()}

            # Each of this process's 2 devices contributes value pr+1:
            # sum = 2*(1) + 2*(2) = 6.
            s = hvd.allreduce(jnp.full((3,), float(pr + 1)),
                              average=False, name="md.sum")
            out["sum"] = np.asarray(s).tolist()

            # allgather: one segment per device -> 4 copies, grouped by
            # process (devices of a process are contiguous ranks).
            g = hvd.allgather(jnp.full((1, 2), float(pr)), name="md.ag")
            out["gather"] = np.asarray(g).tolist()

            # ragged: process 0 contributes 1 row/device, process 1 two.
            rg = hvd.allgather(jnp.full((pr + 1, 2), float(pr)),
                               name="md.agv")
            out["ragged_shape"] = list(np.asarray(rg).shape)
            return out

        results = run(worker, np=2, extra_env=env, start_timeout=300)
        for r in results:
            assert r["size"] == 4 and r["local_size"] == 2
            assert r["sum"] == [6.0] * 3
            assert r["gather"] == [[0.0, 0.0], [0.0, 0.0],
                                   [1.0, 1.0], [1.0, 1.0]]
            assert r["ragged_shape"] == [6, 2]   # 1+1+2+2 rows
        assert results[0] == results[1]


class TestHierarchicalMultiProcess:
    def test_hierarchical_allreduce_across_processes(self):
        """HOROVOD_TPU_HIERARCHICAL_ALLREDUCE=1 in a 2-process x 2-device
        job: psum_scatter over 'ici' + psum over 'dcn' + all_gather over
        'ici' must give the same sums as the flat path."""
        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "HOROVOD_TPU_HIERARCHICAL_ALLREDUCE": "1",
        }

        def worker():
            import jax.numpy as jnp
            import numpy as np

            import horovod_tpu as hvd

            hvd.init()
            pr = hvd.process_rank()
            # 2 devices/process each contribute pr+1: total = 2*1+2*2 = 6
            s = hvd.allreduce(jnp.full((5,), float(pr + 1)),
                              average=False, name="hier.sum")
            # odd size exercises the ici padding path
            s2 = hvd.allreduce(jnp.full((7,), 1.0), average=True,
                               name="hier.avg")
            return (np.asarray(s).tolist(), np.asarray(s2).tolist())

        results = run(worker, np=2, extra_env=env, start_timeout=300)
        for s, s2 in results:
            assert s == [6.0] * 5
            assert s2 == [1.0] * 7
        assert results[0] == results[1]


class TestNativeControlPlane:
    def test_native_core_is_mp_control_plane(self):
        """With process_count > 1 the
        native core is ACTIVE (tensor table, cycle, wire, timeline in
        C++), the rank-0 service plans with the native controller, and no
        Python fallback loop runs."""
        def worker():
            import jax.numpy as jnp
            import numpy as np

            import horovod_tpu as hvd
            from horovod_tpu.ops.collective import engine

            hvd.init()
            r = hvd.rank()
            s = hvd.allreduce(jnp.full((4,), float(r + 1)),
                              average=False, name="native.sum")
            eng = engine()
            return {
                "sum": np.asarray(s).tolist(),
                "native_core": eng._native_core is not None,
                "coordinator_native": (eng._mp_service.native_active
                                       if eng._mp_service else None),
                "python_loop": eng._thread is not None,
            }

        results = run(worker, np=2, extra_env=dict(_ENV), start_timeout=300)
        for r in results:
            assert r["sum"] == [3.0] * 4
            assert r["native_core"], "native core not active in MP mode"
            assert not r["python_loop"], "python fallback loop is running"
        assert results[0]["coordinator_native"] is True

    def test_mixed_fleet_native_and_fallback(self):
        """A process without the native runtime (toolchain missing /
        HOROVOD_TPU_DISABLE_NATIVE=1) interoperates with native peers:
        both speak the message.cc wire format — the fallback via the
        byte-exact Python mirror (ops/wire_format.py)."""
        def worker():
            import os

            import jax.numpy as jnp
            import numpy as np

            import horovod_tpu as hvd
            from horovod_tpu.ops.collective import engine

            # Rank 1 runs the degraded pure-Python path; rank 0 native.
            if os.environ.get("HOROVOD_TPU_PROCESS_ID") == "1":
                os.environ["HOROVOD_TPU_DISABLE_NATIVE"] = "1"
            hvd.init()
            r = hvd.rank()
            out = {}
            out["sum"] = np.asarray(hvd.allreduce(
                jnp.full((4,), float(r + 1)), average=False,
                name="mix.sum")).tolist()
            out["ragged"] = np.asarray(hvd.allgather(
                jnp.full((r + 1, 2), float(r)), name="mix.agv")).tolist()
            out["bcast"] = np.asarray(hvd.broadcast(
                jnp.full((2,), float(10 * (r + 1))), root_rank=1,
                name="mix.bc")).tolist()
            out["native"] = engine()._native_core is not None
            return out

        results = run(worker, np=2, extra_env=dict(_ENV), start_timeout=300)
        assert results[0]["native"] is True
        assert results[1]["native"] is False
        for r in results:
            assert r["sum"] == [3.0] * 4
            assert np.allclose(np.array(r["ragged"]),
                               [[0, 0], [1, 1], [1, 1]])
            assert r["bcast"] == [20.0, 20.0]


class TestFourProcesses:
    def test_four_process_collectives_and_ordering(self):
        """>= 3 processes, ragged cross-process
        allgather with differing per-process first dims, and a
        coordinator-ordering stress — many named ops enqueued in a
        DIFFERENT order on each process; the coordinator's agreed group
        sequence must keep every process's results identical."""
        def worker():
            import jax.numpy as jnp
            import numpy as np

            import horovod_tpu as hvd

            hvd.init()
            r, n = hvd.rank(), hvd.size()
            out = {}

            # Ragged MP allgather: rank r contributes r+1 rows of value r.
            rg = hvd.allgather(jnp.full((r + 1, 2), float(r)),
                               name="p4.agv")
            out["ragged"] = np.asarray(rg).tolist()

            # Ordering stress: 12 async ops enqueued in a rank-dependent
            # rotation; handles must all resolve to the right sums.
            names = [f"p4.x{i}" for i in range(12)]
            order = names[r:] + names[:r]
            handles = {}
            for i, nm in enumerate(order):
                val = float(int(nm.split("x")[1]) + 1)
                handles[nm] = hvd.allreduce_async(
                    jnp.full((3,), val), average=False, name=nm)
            out["sums"] = {nm: float(np.asarray(h.wait())[0])
                           for nm, h in handles.items()}

            # A broadcast from the last rank mixed into the stream.
            b = hvd.broadcast(jnp.full((2,), float(r)), root_rank=n - 1,
                              name="p4.bc")
            out["bcast"] = np.asarray(b).tolist()
            return out

        results = run(worker, np=4, extra_env=dict(_ENV), start_timeout=600)
        expect_ragged = []
        for r in range(4):
            expect_ragged += [[float(r)] * 2] * (r + 1)
        for r in results:
            assert np.allclose(np.array(r["ragged"]), expect_ragged)
            for nm, v in r["sums"].items():
                i = int(nm.split("x")[1])
                assert v == 4.0 * (i + 1), (nm, v)
            assert r["bcast"] == [3.0, 3.0]
        assert all(r == results[0] for r in results[1:])


class TestCrossProcessAutotune:
    def test_knobs_move_in_lockstep(self):
        """With HOROVOD_AUTOTUNE=1 the rank-0 controller
        tunes (fusion threshold, cycle time) and serves them through the
        fetch response (SyncParams, parameter_manager.cc:64-78,213-246);
        every process must apply the same knob sequence — knobs MOVE
        (the tuner explores) and END identical across processes."""
        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_CYCLE_TIME": "1",
        }

        def worker():
            import jax.numpy as jnp

            import horovod_tpu as hvd
            from horovod_tpu.ops.collective import engine

            hvd.init()
            x = jnp.ones((64, 64))
            seen = []
            for i in range(160):
                hvd.allreduce(x, average=False, name=f"tune.{i}")
                eng = engine()
                knobs = (round(eng.fusion_threshold / (1024.0 * 1024.0), 3),
                         round(eng.cycle_time_s * 1000.0, 3))
                if not seen or seen[-1] != knobs:
                    seen.append(knobs)
            active = bool(engine().mp_params.get("autotune_active")
                          or engine().mp_params.get("autotune_done"))
            return {"seen": seen, "tuner_on": active}

        results = run(worker, np=2, extra_env=env, start_timeout=600)
        for r in results:
            assert r["tuner_on"], r
            # The tuner explored: at least one knob change was applied.
            assert len(r["seen"]) >= 2, r["seen"]
        # Lockstep: both processes end on the SAME coordinator-tuned
        # knobs (the sequences may be sampled at different cycle points,
        # but the final state must agree).
        assert results[0]["seen"][-1] == results[1]["seen"][-1], results


class TestDevicePack:
    def test_device_packed_collectives_match(self):
        """The device-resident fusion-buffer pack
        (executor._pack_device + _mp_stacked_device) computes the same
        results as the host pack. Forced on via HOROVOD_TPU_DEVICE_PACK
        (CPU defaults it off), 2 processes, mixed sizes/dtypes and a
        fused burst so quantized buffers and cached DUS programs are
        exercised."""
        def worker():
            import jax.numpy as jnp
            import numpy as np

            import horovod_tpu as hvd
            from horovod_tpu.ops import collective

            hvd.init()
            r, n = hvd.rank(), hvd.size()
            ex = collective.engine().executor
            assert ex._device_pack() is True  # env forced
            out = {}
            s = hvd.allreduce(jnp.full((17,), float(r + 1)),
                              average=False, name="dp.sum")
            out["sum"] = np.asarray(s).tolist()
            h1 = hvd.allreduce_async(jnp.ones((5, 3)), average=False,
                                     name="dp.f1")
            h2 = hvd.allreduce_async(
                jnp.full((9,), 2.0, jnp.bfloat16), average=False,
                name="dp.f2")
            out["f1"] = np.asarray(hvd.synchronize(h1)).tolist()
            out["f2"] = np.asarray(hvd.synchronize(h2),
                                   dtype=np.float32).tolist()
            b = hvd.broadcast(jnp.full((4,), float(10 * (r + 1))),
                              root_rank=1, name="dp.bc")
            out["bcast"] = np.asarray(b).tolist()
            return out

        env = dict(_ENV)
        env["HOROVOD_TPU_DEVICE_PACK"] = "1"
        results = run(worker, np=2, extra_env=env, start_timeout=300)
        for r in results:
            assert r["sum"] == [3.0] * 17
            assert np.allclose(np.array(r["f1"]), 2.0)
            assert np.allclose(np.array(r["f2"]), 4.0)
            assert r["bcast"] == [20.0] * 4
        assert results[0] == results[1]

    def test_device_pack_multi_device_committed_inputs(self):
        """Device pack with 2 local devices per process and an input
        COMMITTED to the non-default local device: the pack must put it
        onto the buffer's device instead of raising 'incompatible
        devices' from the jitted update-slice (the host pack accepted
        any placement, so must this path)."""
        def worker():
            import jax
            import jax.numpy as jnp
            import numpy as np

            import horovod_tpu as hvd
            from horovod_tpu.ops import collective

            hvd.init()
            pr = hvd.process_rank()
            assert jax.local_device_count() == 2
            ex = collective.engine().executor
            assert ex._device_pack() is True
            x = jax.device_put(jnp.full((6,), float(pr + 1)),
                               jax.local_devices()[1])
            s = hvd.allreduce(x, average=False, name="dpm.sum")
            return {"sum": np.asarray(s).tolist()}

        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "HOROVOD_TPU_DEVICE_PACK": "1",
        }
        results = run(worker, np=2, extra_env=env, start_timeout=300)
        for r in results:
            assert r["sum"] == [6.0] * 6  # 2 devices x (1) + 2 x (2)
