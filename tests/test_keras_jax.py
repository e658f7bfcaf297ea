"""Keras shim on the JAX backend — the TPU-native Keras path.

The main keras tests run on the torch backend (tests/test_keras.py);
Keras fixes its backend at import, so the jax-backend path gets its own
subprocess here: DistributedOptimizer inside Keras 3's jitted jax train
step routes gradients through the inline psum (keras/__init__.py:68-87).

Marked slow (subprocess + keras/jax startup).
"""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["KERAS_BACKEND"] = "jax"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import keras

    import horovod_tpu as hvd
    import horovod_tpu.keras as hvd_keras

    hvd.init()
    assert hvd.size() == 8

    keras.utils.set_random_seed(0)
    model = keras.Sequential([
        keras.layers.Input((8,)),
        keras.layers.Dense(16, activation="relu"),
        keras.layers.Dense(2),
    ])
    opt = hvd_keras.DistributedOptimizer(
        keras.optimizers.SGD(learning_rate=0.1))
    model.compile(optimizer=opt, loss="mse", jit_compile=True)

    x = np.random.rand(32, 8).astype("float32")
    y = np.random.rand(32, 2).astype("float32")
    before = [np.array(w) for w in model.get_weights()]
    hist = model.fit(x, y, batch_size=16, epochs=2, verbose=0,
                     shuffle=False)
    after = model.get_weights()
    assert any(not np.allclose(b, a) for b, a in zip(before, after)), \\
        "weights did not move"
    losses = hist.history["loss"]
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"

    # Replicated virtual ranks: wrapped == unwrapped steps must match.
    keras.utils.set_random_seed(0)
    ref = keras.Sequential([
        keras.layers.Input((8,)),
        keras.layers.Dense(16, activation="relu"),
        keras.layers.Dense(2),
    ])
    ref.compile(optimizer=keras.optimizers.SGD(learning_rate=0.1),
                loss="mse", jit_compile=True)
    ref.fit(x, y, batch_size=16, epochs=2, verbose=0,
            shuffle=False)
    for a, b in zip(after, ref.get_weights()):
        np.testing.assert_allclose(np.array(a), np.array(b),
                                   rtol=1e-4, atol=1e-5)
    print("KERAS-JAX OK")
""")


def test_keras_jax_backend_fit():
    pytest.importorskip("keras")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT],
                          capture_output=True, text=True, timeout=420,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-3000:]}")
    assert "KERAS-JAX OK" in proc.stdout


_SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["KERAS_BACKEND"] = "jax"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.keras import _jax_inline_allreduce

    hvd.init()
    mesh = hvd.mesh()

    # 1) Under shard_map with a 'dp' axis: psum-average across shards.
    def f(g):
        return _jax_inline_allreduce(g[0])

    xs = jnp.arange(8.0).reshape(8, 1)
    out = jax.shard_map(f, mesh=mesh, in_specs=P("dp"),
                        out_specs=P(), check_vma=False)(xs)
    np.testing.assert_allclose(np.asarray(out), 3.5)  # mean(0..7)
    print("PSUM-PATH OK")

    # 2) Under shard_map with a DIFFERENTLY-NAMED axis: must raise with
    # rename guidance, never silently train divergent shards.
    from jax.sharding import Mesh
    mesh2 = Mesh(np.array(jax.devices()), ("replica",))
    try:
        jax.shard_map(f, mesh=mesh2, in_specs=P("replica"),
                      out_specs=P(), check_vma=False)(xs)
        raise SystemExit("expected RuntimeError for wrong axis name")
    except RuntimeError as e:
        assert "'dp'" in str(e) and "replica" in str(e), e
    print("WRONG-AXIS OK")

    # 3) Plain jit, single process, replicated grads: pass-through is the
    # identity (XLA/the shardings own the reduction) — NOT a double
    # division by world size.
    @jax.jit
    def g(x):
        return _jax_inline_allreduce(x)

    val = jnp.full((3,), 5.0)
    np.testing.assert_allclose(np.asarray(g(val)), 5.0)
    print("PASSTHROUGH OK")
""")


def test_keras_jax_psum_passthrough_decisions():
    """The Keras-JAX pass-through logic makes
    silently-wrong-if-misjudged decisions (keras/__init__.py
    _jax_inline_allreduce); pin each branch — psum under 'dp',
    loud failure under a misnamed axis, identity pass-through in a plain
    single-process jit."""
    pytest.importorskip("keras")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARD_MAP_SCRIPT],
                          capture_output=True, text=True, timeout=420,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-3000:]}")
    for tag in ("PSUM-PATH OK", "WRONG-AXIS OK", "PASSTHROUGH OK"):
        assert tag in proc.stdout


_COMPRESSION_SCRIPT = textwrap.dedent("""
    import os
    os.environ["KERAS_BACKEND"] = "jax"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import keras

    import horovod_tpu as hvd
    import horovod_tpu.keras as hvd_keras
    from horovod_tpu.compression import Compression

    hvd.init()

    # Eager apply (run_eagerly): gradients cross the engine with fp16
    # compression; training must still converge to the uncompressed
    # result within half precision tolerance.
    keras.utils.set_random_seed(0)
    model = keras.Sequential([
        keras.layers.Input((4,)), keras.layers.Dense(1)])
    opt = hvd_keras.DistributedOptimizer(
        keras.optimizers.SGD(learning_rate=0.05),
        compression=Compression.fp16)
    model.compile(optimizer=opt, loss="mse", run_eagerly=True)
    x = np.random.rand(16, 4).astype("float32")
    y = (x @ np.array([[1.0], [-2.0], [0.5], [3.0]],
                      dtype=np.float32)).astype("float32")
    h = model.fit(x, y, batch_size=8, epochs=3, verbose=0, shuffle=False)
    losses = h.history["loss"]
    assert losses[-1] < losses[0], losses
    print("FP16-COMPRESSION OK")

    # broadcast_global_variables syncs weights + optimizer slots.
    hvd_keras.broadcast_global_variables(0, model=model)
    print("BCAST OK")

    # Host-value helpers mirror _keras/__init__.py:78-90.
    assert float(hvd_keras.allreduce(2.0, average=False)) == 2.0 * hvd.size()
    assert hvd_keras.allgather([1.0]).shape == (hvd.size(),)
    assert float(hvd_keras.broadcast(7.0, 0)) == 7.0
    print("HOST-VALUES OK")
""")


def test_keras_jax_compression_and_host_values():
    pytest.importorskip("keras")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _COMPRESSION_SCRIPT],
                          capture_output=True, text=True, timeout=420,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-3000:]}")
    for tag in ("FP16-COMPRESSION OK", "BCAST OK", "HOST-VALUES OK"):
        assert tag in proc.stdout
