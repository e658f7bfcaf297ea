"""TensorFlow shim tests — structural mirror of the reference's
test_tensorflow.py (806 LoC, 24 tests): dtype x dimension sweeps for the
three collectives, eager AND tf.function (graph-traced) execution,
registered gradients checked numerically, IndexedSlices sparse path,
DistributedGradientTape, variable broadcast.

Keras-optimizer integration runs in a subprocess with
KERAS_BACKEND=tensorflow (tests/test_keras_tf.py) to avoid pinning the
in-process Keras backend, which tests/test_keras.py sets to torch.
"""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

import horovod_tpu as hvd
import horovod_tpu.tensorflow as hvd_tf

SWEEP_DTYPES = [tf.uint8, tf.int8, tf.int32, tf.float16, tf.float32,
                tf.bfloat16]


@pytest.fixture(autouse=True)
def _init():
    hvd.init()
    yield


def _rand(shape, dtype):
    if dtype in (tf.uint8, tf.int8, tf.int32):
        return tf.cast(tf.random.uniform(shape, 0, 10, dtype=tf.int32),
                       dtype)
    return tf.cast(tf.random.uniform(shape), dtype)


class TestTFAllreduce:
    @pytest.mark.parametrize("dtype", SWEEP_DTYPES)
    @pytest.mark.parametrize("dim", [1, 2, 3])
    def test_allreduce_sum(self, dtype, dim):
        t = _rand([17] * dim, dtype)
        out = hvd_tf.allreduce(t, average=False)
        assert out.dtype == dtype
        expected = tf.cast(t, tf.float32) * hvd.size()
        tol = 1e-1 if dtype in (tf.float16, tf.bfloat16) else 1e-4
        assert np.allclose(tf.cast(out, tf.float32).numpy(),
                           expected.numpy(), rtol=tol, atol=tol)

    def test_allreduce_average(self):
        t = tf.constant([1.0, 2.0, 3.0])
        out = hvd_tf.allreduce(t, average=True)
        assert np.allclose(out.numpy(), t.numpy(), atol=1e-5)

    def test_allreduce_inside_tf_function(self):
        # The py_function bridge must survive graph tracing — the
        # AsyncOpKernel role (tensorflow/mpi_ops.cc:281-303).
        @tf.function
        def fn(x):
            return hvd_tf.allreduce(x, average=False)

        t = tf.constant([[1.0, 2.0], [3.0, 4.0]])
        out = fn(t)
        assert np.allclose(out.numpy(), t.numpy() * hvd.size())

    def test_allreduce_grad(self):
        # grad(allreduce(x)) = allreduce(grad) → for sum over identical
        # ranks: d(sum)/dx elementwise = size (test_tensorflow.py:334-368).
        t = tf.Variable([1.0, 2.0, 3.0])
        with tf.GradientTape() as tape:
            out = tf.reduce_sum(hvd_tf.allreduce(t, average=False))
        g = tape.gradient(out, t)
        assert np.allclose(g.numpy(), np.full(3, float(hvd.size())))

    def test_allreduce_compression_fp16(self):
        t = tf.constant([1.5, 2.5, 3.5])
        out = hvd_tf.allreduce(t, average=True,
                               compression=hvd_tf.Compression.fp16)
        assert out.dtype == tf.float32
        assert np.allclose(out.numpy(), t.numpy(), atol=1e-2)

    def test_allreduce_indexed_slices(self):
        # Sparse gradients travel as allgather(values)+allgather(indices)
        # (tensorflow/__init__.py:72-83).
        v = tf.IndexedSlices(values=tf.constant([[1.0, 2.0]]),
                             indices=tf.constant([3]),
                             dense_shape=tf.constant([8, 2]))
        out = hvd_tf.allreduce(v, average=False)
        assert isinstance(out, tf.IndexedSlices)
        assert out.values.shape[0] == hvd.size()
        assert out.indices.shape[0] == hvd.size()
        assert np.allclose(out.values.numpy()[0], [1.0, 2.0])


class TestTFAllgather:
    @pytest.mark.parametrize("dtype", [tf.int32, tf.float32])
    @pytest.mark.parametrize("dim", [1, 2])
    def test_allgather(self, dtype, dim):
        t = _rand([5] * dim, dtype)
        out = hvd_tf.allgather(t)
        assert out.shape[0] == 5 * hvd.size()
        assert np.allclose(tf.cast(out[:5], tf.float32).numpy(),
                           tf.cast(t, tf.float32).numpy())

    def test_allgather_grad(self):
        t = tf.Variable([[1.0, 2.0], [3.0, 4.0]])
        with tf.GradientTape() as tape:
            out = tf.reduce_sum(hvd_tf.allgather(t))
        g = tape.gradient(out, t)
        # Each rank's slice of the summed gathered grad = ones * size.
        assert np.allclose(g.numpy(), np.full((2, 2), float(hvd.size())))


class TestTFBroadcast:
    def test_broadcast(self):
        t = tf.constant([1.0, 2.0, 3.0])
        out = hvd_tf.broadcast(t, root_rank=0)
        assert np.allclose(out.numpy(), t.numpy())

    def test_broadcast_grad_root(self):
        t = tf.Variable([1.0, 2.0])
        with tf.GradientTape() as tape:
            out = tf.reduce_sum(hvd_tf.broadcast(t, root_rank=0))
        g = tape.gradient(out, t)
        if hvd.rank() == 0:
            assert np.allclose(g.numpy(), np.full(2, float(hvd.size())))

    def test_broadcast_variables(self):
        v1 = tf.Variable([1.0, 2.0])
        v2 = tf.Variable([[3.0]])
        before = [v1.numpy().copy(), v2.numpy().copy()]
        hvd_tf.broadcast_variables([v1, v2], root_rank=0)
        assert np.allclose(v1.numpy(), before[0])
        assert np.allclose(v2.numpy(), before[1])

    def test_broadcast_global_requires_variables(self):
        with pytest.raises(ValueError):
            hvd_tf.broadcast_global_variables(0)


class TestDistributedGradientTape:
    def test_tape_averages(self):
        v = tf.Variable([1.0, 2.0])
        with hvd_tf.DistributedGradientTape() as tape:
            loss = tf.reduce_sum(v * v)
        g = tape.gradient(loss, [v])[0]
        # average over identical ranks == local grad (2v)
        assert np.allclose(g.numpy(), 2 * v.numpy(), atol=1e-5)

    def test_tape_training_loop(self):
        v = tf.Variable([4.0])
        for _ in range(3):
            with hvd_tf.DistributedGradientTape() as tape:
                loss = tf.reduce_sum(v * v)
            (g,) = tape.gradient(loss, [v])
            v.assign_sub(0.1 * g)
        assert float(v.numpy()[0]) < 4.0

    def test_callback_hook(self):
        cb = hvd_tf.BroadcastGlobalVariablesCallback(0)

        class M:
            variables = [tf.Variable([1.0])]

        cb(model=M())
        assert cb._done


class TestGroupedBridge:
    def test_tape_many_variables_one_bridge(self):
        """A tape with >= 20 variables
        crosses the host bridge ONCE per gradient call (one engine-fused
        burst), not once per variable."""
        n_vars = 24
        vs = [tf.Variable(tf.fill([3], float(i + 1))) for i in range(n_vars)]
        with hvd_tf.DistributedGradientTape() as tape:
            loss = tf.add_n([tf.reduce_sum(v * v) for v in vs])
        before = hvd_tf._bridge_calls[0]
        grads = tape.gradient(loss, vs)
        bridged = hvd_tf._bridge_calls[0] - before
        assert bridged == 1, f"{bridged} host bridges for {n_vars} grads"
        for i, g in enumerate(grads):
            # Replicated virtual ranks: average == local value (2 * v).
            np.testing.assert_allclose(g.numpy(), 2.0 * (i + 1), rtol=1e-5)

    def test_grouped_allreduce_values_and_grad(self):
        xs = [tf.constant([1.0, 2.0]), tf.constant([[3.0]]),
              tf.constant([4.0, 5.0, 6.0])]
        outs = hvd_tf.grouped_allreduce(xs, average=False)
        for x, o in zip(xs, outs):
            np.testing.assert_allclose(o.numpy(), x.numpy() * hvd.size())
        # Differentiable through the group.
        v = tf.Variable([2.0, 3.0])
        with tf.GradientTape() as tape:
            out = hvd_tf.grouped_allreduce([v * v], average=True)[0]
            loss = tf.reduce_sum(out)
        g = tape.gradient(loss, v)
        np.testing.assert_allclose(g.numpy(), 2.0 * v.numpy(), rtol=1e-5)

    def test_grouped_allreduce_mixed_dtypes(self):
        outs = hvd_tf.grouped_allreduce(
            [tf.constant([1.0, 2.0]), tf.constant([3], tf.int32)],
            average=False)
        np.testing.assert_allclose(outs[0].numpy(),
                                   [hvd.size(), 2.0 * hvd.size()])
        assert outs[1].numpy().tolist() == [3 * hvd.size()]
        assert outs[1].dtype == tf.int32

    def test_v1_optimizer_compute_gradients_one_bridge(self):
        """The reference-shaped v1 wrapper (compute_gradients override,
        tensorflow/__init__.py:151-249): 21 variables cross in ONE
        bridged group, and the update applies. (A Keras-3 optimizer is
        not used here because other suite files pin the in-process Keras
        backend to torch; the Keras path is covered in
        tests/test_keras_tf.py's subprocess.)"""
        vs = [tf.Variable(tf.ones([2]) * (i + 1)) for i in range(21)]
        opt = hvd_tf.DistributedOptimizer(
            tf.compat.v1.train.GradientDescentOptimizer(0.1))

        def loss():
            return tf.add_n([tf.reduce_sum(v * v) for v in vs])

        before = hvd_tf._bridge_calls[0]
        gvs = opt.compute_gradients(loss, var_list=vs)
        assert hvd_tf._bridge_calls[0] - before == 1
        opt.apply_gradients(gvs)
        for i, v in enumerate(vs):
            # g = 2v -> v' = v - 0.1 * 2v = 0.8 * (i+1)
            np.testing.assert_allclose(v.numpy(), 0.8 * (i + 1),
                                       rtol=1e-5)


class TestSessionRunHook:
    def test_broadcast_hook_graph_mode(self):
        """SessionRunHook-shaped estimator integration
        (tensorflow/__init__.py:117-148): begin() builds the grouped
        assign over global variables; after_create_session runs it."""
        with tf.Graph().as_default():
            v1 = tf.compat.v1.get_variable(
                "hook_v1", initializer=tf.constant([1.0, 2.0]))
            v2 = tf.compat.v1.get_variable(
                "hook_v2", initializer=tf.constant(5.0))
            hook = hvd_tf.BroadcastGlobalVariablesHook(root_rank=0)
            hook.begin()
            assert hook.bcast_op is not None
            with tf.compat.v1.Session() as sess:
                sess.run(tf.compat.v1.global_variables_initializer())
                hook.after_create_session(sess, None)
                out1, out2 = sess.run([v1, v2])
        np.testing.assert_allclose(out1, [1.0, 2.0])
        np.testing.assert_allclose(out2, 5.0)
