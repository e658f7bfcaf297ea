"""horovod_tpu.keras — the Keras framework shim.

Parity target: horovod/keras/__init__.py (148) + horovod/tensorflow/keras/
__init__.py (155) + the shared impl horovod/_keras/__init__.py (109): a
``DistributedOptimizer`` built as a dynamic subclass of the wrapped
optimizer's class (so saved models restore without the framework,
_keras/__init__.py:63-70), eager ``allreduce/allgather/broadcast`` on
host values, ``broadcast_variables`` and ``load_model`` that re-wraps
every stock optimizer class (_keras/__init__.py:93-109).

The reference targets Keras 2 over TF sessions and hooks
``get_gradients`` (graph mode). Keras 3 is multi-backend and routes every
gradient application through ``Optimizer.apply`` — that is the hook here.
The collectives run on the TPU-native XLA engine; gradients cross from
whatever backend Keras is using:

- ``torch`` backend: tensors move through the torch shim's transport.
- ``tensorflow`` backend: eager tensors via numpy; inside a traced
  ``tf.function`` the allreduce is bridged with ``tf.py_function`` (the
  host-callback analogue of the reference's AsyncOpKernel,
  tensorflow/mpi_ops.cc:281-303).
- ``jax`` backend: concrete arrays go straight to the engine. Inside a
  jitted step (``model.fit``), collectives must be part of the SPMD
  program — use ``lax.psum`` over a mesh axis ('dp' is tried
  automatically under ``shard_map``) or Keras's own
  ``keras.distribution`` sharding; an un-shardable tracer raises with
  that guidance rather than silently skipping the reduction.
- ``numpy`` backend: direct.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import keras

from .. import ops as _ops
from .. import topology as _topo
from ..compression import Compression
from ..topology import (init, shutdown, is_initialized, rank, local_rank,
                        size, local_size, mpi_threads_supported)
from . import callbacks

__all__ = [
    "init", "shutdown", "is_initialized", "rank", "local_rank", "size",
    "local_size", "mpi_threads_supported", "Compression",
    "DistributedOptimizer", "broadcast_global_variables",
    "broadcast_variables", "allreduce", "allgather", "broadcast",
    "load_model", "callbacks",
]


# ---------------------------------------------------------------------------
# Backend bridging
# ---------------------------------------------------------------------------

def _backend() -> str:
    return keras.backend.backend()


def _is_jax_tracer(x) -> bool:
    import jax
    return isinstance(x, jax.core.Tracer)


def _jax_inline_allreduce(g):
    """Inside a jitted Keras-JAX train step the reduction must be part of
    the SPMD program. Under shard_map with a 'dp' axis, psum does it.

    Without an axis in scope, Keras 3's own jitted train step is an SPMD
    program over sharded arrays: if a Keras distribution (DataParallel)
    is active in this single-controller process, XLA already inserts the
    gradient reduction from the shardings and the wrapper must pass
    through (reducing twice would double-average). Only when neither an
    axis nor a distribution can do the reduction do we fail loudly
    instead of silently training divergent replicas (the multi-process
    no-sharding case)."""
    import jax
    from jax import lax
    try:
        return lax.psum(g, "dp") / lax.psum(
            jax.numpy.ones((), g.dtype), "dp")
    except NameError as e:
        # Other named axes in scope mean we are inside shard_map but the
        # data axis has a different name — pass-through would silently
        # train divergent shards, so fail with the rename guidance.
        from jax._src import core as _src_core
        axes = dict(_src_core.get_axis_env().axis_sizes)
        if axes:
            raise RuntimeError(
                "horovod_tpu.keras.DistributedOptimizer reduces over the "
                f"mesh axis named 'dp', but the axes in scope are "
                f"{sorted(axes)}. Name your data-parallel shard_map axis "
                "'dp' (or psum the gradients yourself).") from e
        if jax.process_count() == 1:
            # Plain jitted Keras step, no shard_map: either the arrays
            # are replicated (identical gradients everywhere — averaging
            # is the identity) or a keras.distribution shards them and
            # XLA inserts the reduction from the shardings. Both cases
            # pass through.
            return g
        raise RuntimeError(
            "horovod_tpu.keras.DistributedOptimizer was traced into a "
            "jitted train step with no 'dp' mesh axis in scope in a "
            "multi-process job. With the Keras JAX backend, either run "
            "the optimizer inside shard_map over a mesh with a 'dp' "
            "axis, or use SPMD data parallelism "
            "(keras.distribution.DataParallel / horovod_tpu.parallel) "
            "where XLA inserts the gradient reduction itself.") from e


def _allreduce_grad(g, name: Optional[str], compression) -> object:
    """Average one backend gradient tensor across ranks, preserving its
    backend type. Single-tensor convenience over the batch helpers (one
    copy of every backend branch lives in the *_batch functions)."""
    kb = _backend()
    if kb == "torch":
        from . import _torch_bridge
        return _torch_bridge.allreduce_average(g, name, compression)
    if kb == "tensorflow":
        import tensorflow as tf
        if not tf.executing_eagerly():
            return _tf_graph_allreduce_batch([g], [name], compression)[0]
        out = _engine_allreduce_batch([g.numpy()], [name], compression)[0]
        return tf.constant(out, dtype=g.dtype)
    if kb == "jax":
        if _is_jax_tracer(g):
            return _jax_inline_allreduce(g)
        import jax.numpy as jnp
        return jnp.asarray(_engine_allreduce_batch(
            [np.asarray(g)], [name], compression)[0])
    # numpy / anything array-like
    arr = keras.ops.convert_to_numpy(g)
    return keras.ops.convert_to_tensor(
        _engine_allreduce_batch([arr], [name], compression)[0])


def _engine_allreduce_batch(arrs, names, compression):
    """ONE engine burst for a list of host arrays: submit every gradient
    async (the engine fuses the burst into as few XLA collectives as the
    threshold allows), then wait all handles — the Keras-side counterpart
    of the TF shim's grouped bridge. Sequential blocking submits would
    pay one negotiation round-trip per gradient."""
    comp = compression if compression is not None else Compression.none
    blockwise = comp if getattr(comp, "wire_spec", None) is not None \
        else None
    handles = []
    with _ops.engine().burst():
        for arr, nm in zip(arrs, names):
            wire, ctx = comp.compress(arr)
            handles.append((_ops.allreduce_async(wire, average=True,
                                                 name=nm,
                                                 compression=blockwise),
                            ctx, arr.dtype))
    # Batched readback: one device_get for the whole group instead of a
    # per-gradient round trip (utils/interop.to_host_many — the
    # bridge-batching fix the BENCH_SHIMS measurement exposed).
    from ..utils.interop import to_host_many
    waited = to_host_many([h.wait() for h, _, _ in handles])
    outs = []
    for (h, ctx, dt), out in zip(handles, waited):
        out = comp.decompress(out, ctx)
        outs.append(np.asarray(out, dtype=dt))
    return outs


def _tf_graph_allreduce_batch(gs, names, compression):
    """One py_function crossing for the whole gradient group inside a
    traced tf.function (mirrors tensorflow._grouped_bridge)."""
    import tensorflow as tf
    blockwise = compression \
        if getattr(compression, "wire_spec", None) is not None else None
    wire = (None if blockwise is not None
            else getattr(compression, "wire_dtype", None))
    wire_np = np.dtype(wire) if wire is not None else None

    def host(*xs):
        handles = []
        dts = []
        with _ops.engine().burst():
            for x, nm in zip(xs, names):
                arr = x.numpy()
                dts.append(arr.dtype)
                if wire_np is not None and np.issubdtype(arr.dtype,
                                                         np.floating):
                    arr = arr.astype(wire_np)
                handles.append(_ops.allreduce_async(
                    arr, average=True, name=nm, compression=blockwise))
        # Batched readback (interop.to_host_many): one device_get for
        # the group, not one round trip per gradient.
        from ..utils.interop import to_host_many
        waited = to_host_many([h.wait() for h in handles])
        return [np.asarray(out, dtype=dt)
                for out, dt in zip(waited, dts)]

    outs = tf.py_function(host, list(gs), Tout=[g.dtype for g in gs])
    if len(gs) == 1 and not isinstance(outs, (list, tuple)):
        outs = [outs]
    for g, o in zip(gs, outs):
        o.set_shape(g.shape)
    return list(outs)


# ---------------------------------------------------------------------------
# DistributedOptimizer
# ---------------------------------------------------------------------------

class _DistributedOptimizer:
    """Mixin copied onto a dynamic subclass of the wrapped optimizer's
    class (_keras/__init__.py:63-70) so ``isinstance`` checks, LR
    schedules and model saving keep working."""

    _hvd_wrapped = True
    # Class-level defaults: instances deserialized by load_model() never
    # pass through DistributedOptimizer(), which sets instance attrs.
    _hvd_name = None
    _hvd_compression = Compression.none

    def apply(self, grads, trainable_variables=None):
        if not _topo.is_initialized():
            init()
        if _topo.size() > 1:
            prefix = self._hvd_name or f"Distributed{type(self).__name__}"
            grads = self._hvd_reduce(list(grads), prefix)
        return super(self.__class__, self).apply(grads, trainable_variables)

    def _hvd_reduce(self, grads, prefix):
        """Average the gradient list across ranks in ONE batched
        submission where the backend allows it (eager TF / concrete jax
        / numpy via an engine burst; traced tf.function via a single
        py_function group); jax tracers stay per-leaf (inline psum —
        XLA fuses those itself), torch delegates to its bridge."""
        comp = self._hvd_compression
        names = [f"{prefix}.grad.{i}" for i in range(len(grads))]
        idx = [i for i, g in enumerate(grads) if g is not None]
        if not idx:
            return grads
        kb = _backend()
        out = list(grads)
        if kb == "tensorflow":
            import tensorflow as tf
            if not tf.executing_eagerly():
                red = _tf_graph_allreduce_batch(
                    [grads[i] for i in idx], [names[i] for i in idx],
                    comp)
                for i, r in zip(idx, red):
                    out[i] = r
                return out
            arrs = [grads[i].numpy() for i in idx]
            red = _engine_allreduce_batch(arrs,
                                          [names[i] for i in idx], comp)
            for i, r in zip(idx, red):
                out[i] = tf.constant(r, dtype=grads[i].dtype)
            return out
        if kb == "jax" and not any(_is_jax_tracer(grads[i]) for i in idx):
            arrs = [np.asarray(grads[i]) for i in idx]
            red = _engine_allreduce_batch(arrs,
                                          [names[i] for i in idx], comp)
            import jax.numpy as jnp
            for i, r in zip(idx, red):
                out[i] = jnp.asarray(r)
            return out
        if kb == "numpy":
            arrs = [keras.ops.convert_to_numpy(grads[i]) for i in idx]
            red = _engine_allreduce_batch(arrs,
                                          [names[i] for i in idx], comp)
            for i, r in zip(idx, red):
                out[i] = keras.ops.convert_to_tensor(r)
            return out
        # torch backend / jax tracers: per-leaf path.
        return [g if g is None else _allreduce_grad(g, nm, comp)
                for g, nm in zip(grads, names)]


def _make_wrapped_class(cls):
    ns = {k: v for k, v in _DistributedOptimizer.__dict__.items()
          if k not in ("__dict__", "__weakref__")}
    return type(cls.__name__, (cls,), ns)


def DistributedOptimizer(optimizer, name: Optional[str] = None,
                         compression=Compression.none):
    """Wrap a ``keras.optimizers.Optimizer`` so every gradient is
    allreduce-averaged across ranks before the update rule runs
    (_keras/__init__.py:20-70). The returned object is an instance of a
    dynamic subclass with the SAME class name, so a model saved with it
    loads without horovod_tpu installed."""
    cls = _make_wrapped_class(optimizer.__class__)
    new = cls.from_config(optimizer.get_config())
    new._hvd_name = name or f"Distributed{optimizer.__class__.__name__}"
    new._hvd_compression = compression
    return new


# ---------------------------------------------------------------------------
# Eager host-value collectives (_keras/__init__.py:78-90)
# ---------------------------------------------------------------------------

def _host_array(value) -> np.ndarray:
    """Python scalars/lists default to 32-bit, as ``tf.constant`` does in
    the reference's host-value helpers (_keras/__init__.py:78-90);
    explicit numpy 64-bit arrays still hit the engine's narrowing guard."""
    if isinstance(value, np.ndarray):
        return value
    arr = np.asarray(value)
    if arr.dtype == np.float64:
        return arr.astype(np.float32)
    if arr.dtype == np.int64:
        return arr.astype(np.int32)
    return arr


def allreduce(value, name: Optional[str] = None, average: bool = True):
    """Allreduce a host value (scalar / array); returns numpy."""
    out = _ops.allreduce(_host_array(value), average=average, name=name)
    return np.asarray(out)


def allgather(value, name: Optional[str] = None):
    out = _ops.allgather(np.atleast_1d(_host_array(value)), name=name)
    return np.asarray(out)


def broadcast(value, root_rank: int = 0, name: Optional[str] = None):
    out = _ops.broadcast(_host_array(value), root_rank, name=name)
    return np.asarray(out)


# ---------------------------------------------------------------------------
# Variable broadcast + model loading
# ---------------------------------------------------------------------------

def broadcast_variables(variables, root_rank: int = 0) -> None:
    """Broadcast ``keras.Variable``s from ``root_rank`` in place — the
    rank-0 state sync used at (re)start (tensorflow/__init__.py:95-114)."""
    from ..utils.wire import movement_payload, movement_restore
    handles = []
    for i, v in enumerate(variables):
        arr = np.asarray(keras.ops.convert_to_numpy(v))  # not ascontiguousarray: it promotes 0-dim to (1,)
        wire, from_bits = movement_payload(arr)
        h = _ops.broadcast_async(
            wire, root_rank, name=f"keras.bcast.{i}.{getattr(v, 'path', i)}")
        handles.append((v, arr.dtype, arr.shape, from_bits, h))
    for v, dtype, shape, from_bits, h in handles:
        v.assign(movement_restore(h.wait(), dtype, shape, from_bits))


def broadcast_global_variables(root_rank: int = 0, model=None) -> None:
    """Broadcast all of a model's variables (weights + optimizer slots).
    Keras 3 has no global-variables collection; pass the model (the
    callback does this automatically)."""
    if model is None:
        raise ValueError(
            "Keras 3 has no global variable collection; pass model= or "
            "use callbacks.BroadcastGlobalVariablesCallback")
    broadcast_variables(model.variables, root_rank)
    if getattr(model, "optimizer", None) is not None:
        broadcast_variables(model.optimizer.variables, root_rank)


def load_model(filepath, custom_optimizers=None, custom_objects=None,
               compile=True):
    """Load a model, re-wrapping every stock optimizer class in
    ``DistributedOptimizer`` so restored training resumes distributed
    (_keras/__init__.py:93-109)."""
    import inspect

    horovod_objects = {}
    for attr in dir(keras.optimizers):
        obj = getattr(keras.optimizers, attr)
        if (inspect.isclass(obj)
                and issubclass(obj, keras.optimizers.Optimizer)
                and obj is not keras.optimizers.Optimizer):
            wrapped = _make_wrapped_class(obj)
            horovod_objects[obj.__name__] = wrapped
            horovod_objects[obj.__name__.lower()] = wrapped
    if custom_optimizers is not None:
        horovod_objects.update(
            {cls.__name__: _make_wrapped_class(cls)
             for cls in custom_optimizers})
    if custom_objects is not None:
        horovod_objects.update(custom_objects)
    return keras.models.load_model(filepath, custom_objects=horovod_objects,
                                   compile=compile)
