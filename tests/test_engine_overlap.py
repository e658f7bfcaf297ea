"""Producer-fence policy tests.

The eager engine used to block on EVERY input's producer before
launching a fused collective — the fix for an XLA CPU rendezvous
deadlock (two threads enqueueing mesh-wide programs with no global
order; observed 4-of-8 on this mesh), at the cost of compute/collective
overlap. The fence is now scoped to where the hazard exists: processes
addressing >1 device. These tests pin (a) the deadlock scenario stays
fixed on the multi-device mesh, (b) the fence is OFF for single-device
processes (the real-pod shape, where the overlap matters), (c) the env
override works both ways.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.ops import collective


class TestFencePolicy:
    def test_fence_on_for_multi_device(self, monkeypatch):
        eng = collective.engine()
        monkeypatch.delenv("HOROVOD_TPU_PRODUCER_FENCE", raising=False)
        monkeypatch.setattr(eng, "_fence_decision", None)  # re-resolve
        assert jax.local_device_count() > 1  # conftest's 8-device mesh
        assert eng._fence_producers() is True

    def test_env_override(self, monkeypatch):
        """The knob is read-once (resolved on first use, like every
        other engine knob); tests reset the cached decision to exercise
        both values."""
        eng = collective.engine()
        monkeypatch.setenv("HOROVOD_TPU_PRODUCER_FENCE", "0")
        monkeypatch.setattr(eng, "_fence_decision", None)
        assert eng._fence_producers() is False
        monkeypatch.setenv("HOROVOD_TPU_PRODUCER_FENCE", "1")
        monkeypatch.setattr(eng, "_fence_decision", None)
        assert eng._fence_producers() is True
        # cached now: a mutated env no longer flips the decision
        monkeypatch.setenv("HOROVOD_TPU_PRODUCER_FENCE", "0")
        assert eng._fence_producers() is True

    def test_fence_off_for_single_device(self):
        """One device per process (the real-pod shape): launches land in
        one FIFO queue, rendezvous inversion is impossible, fence off —
        run in a subprocess with a 1-device platform."""
        script = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import horovod_tpu as hvd
from horovod_tpu.ops import collective
hvd.init()
assert jax.local_device_count() == 1
assert collective.engine()._fence_producers() is False
print("OK")
"""
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("HOROVOD_TPU_PRODUCER_FENCE", None)
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True,
            text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "OK" in proc.stdout


class TestOrderedLaunch:
    """HOROVOD_TPU_ORDERED_LAUNCH prototype:
    enqueue-ordering under a process-global launch lock instead of the
    completion fence. The 4-of-8 producer-feeding rendezvous scenario
    must pass with it on; the unrelated-stream scenario still aborts
    even fully locked (measured, experiments/ordered_launch_hazard.log
    — PJRT CPU fans out post-call), which is why the fence remains the
    default."""

    def test_knob_default_off(self, monkeypatch):
        eng = collective.engine()
        monkeypatch.delenv("HOROVOD_TPU_ORDERED_LAUNCH", raising=False)
        monkeypatch.setattr(eng, "_ordered_decision", None)
        assert eng._ordered_launch() is False

    def test_launch_lock_reentrant_and_exported(self):
        import horovod_tpu.ops as ops
        with ops.launch_lock():
            with ops.launch_lock():   # reentrant by design
                pass

    def test_rendezvous_regression_with_ordered_launch_on(self):
        """The producer-feeding scenario under ordered-launch: producers
        wrapped in launch_lock(), engine launching under the same lock,
        no completion fence. Runs in a subprocess (the knob is read-once
        engine state)."""
        script = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["HOROVOD_TPU_ORDERED_LAUNCH"] = "1"
os.environ["HOROVOD_TPU_PRODUCER_FENCE"] = "0"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.ops import launch_lock
from jax.sharding import NamedSharding, PartitionSpec as P
hvd.init()
mesh = hvd.mesh()

@jax.jit
def producer(x, i):
    return jnp.tanh(x) * 0 + i

x = jax.device_put(jnp.ones((256,), jnp.float32), NamedSharding(mesh, P()))
for round_i in range(10):
    with launch_lock():
        ys = [producer(x, float(i)) for i in range(4)]
    hs = [hvd.allreduce_async(y, name=f"ol.{round_i}.{i}", average=False)
          for i, y in enumerate(ys)]
    for i, h in enumerate(hs):
        np.testing.assert_allclose(np.asarray(h.wait(timeout=30.0)),
                                   float(i) * hvd.size())
print("ORDERED_OK")
"""
        env = dict(os.environ)
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True,
            text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "ORDERED_OK" in proc.stdout


class TestRendezvousScenario:
    def test_mesh_producers_feeding_eager_collectives(self):
        """The observed 4-of-8 deadlock scenario: a
        replicated mesh-wide jit PRODUCES the tensors, and its async
        dispatch is still fanning out across the per-device queues when
        the engine launches the fused collective on those outputs. The
        producer fence (active on this multi-device mesh) must retire
        the producer before the launch, so every round completes; a
        regression that drops the fence on multi-device wedges this
        test (XLA CPU aborts the rendezvous after its 40 s timeout).

        Scope note (measured, round 4): an UNRELATED mesh-wide jit
        stream running concurrently with eager collectives deadlocks
        regardless of the fence — no fence on producers can order two
        threads' unrelated launches. That pattern is outside the eager
        engine's contract on multi-device-per-process meshes (use the
        jit optimizer path); the fence's contract is exactly the
        producer-feeding pattern below."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = hvd.mesh()

        @jax.jit
        def producer(x, i):
            # replicated all-device program, like the replicated-param
            # train steps that fed eager allreduce_gradients when the
            # 4-of-8 deadlock was observed
            return jnp.tanh(x) * 0 + i

        x = jax.device_put(jnp.ones((256,), jnp.float32),
                           NamedSharding(mesh, P()))

        deadline = time.monotonic() + 120
        for round_i in range(10):
            assert time.monotonic() < deadline, "collective rounds wedged"
            # dispatch returns while the mesh-wide producer may still be
            # in flight; the engine must fence before its own launch
            ys = [producer(x, float(i)) for i in range(4)]
            hs = [hvd.allreduce_async(y, name=f"rdv.{round_i}.{i}",
                                      average=False)
                  for i, y in enumerate(ys)]
            outs = [h.wait(timeout=30.0) for h in hs]
            for i, o in enumerate(outs):
                np.testing.assert_allclose(np.asarray(o),
                                           float(i) * hvd.size())
