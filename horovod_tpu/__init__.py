"""horovod_tpu — a TPU-native distributed training framework with the
capabilities of Horovod (reference: lxx719/horovod, v0.15.2).

Built from scratch for TPU: JAX/XLA collectives over a ``jax.sharding.Mesh``
replace MPI/NCCL; a native C++ control-plane runtime (background cycle,
tensor fusion planning, timeline, autotuning) replaces the MPI coordinator;
``jax.distributed`` + the runner replace ``mpirun``.

Five-line usage, mirroring the reference README:

    import horovod_tpu as hvd
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adam(1e-3 * hvd.size()))
    state = hvd.broadcast_parameters(state, root_rank=0)
    ... standard JAX training loop ...
"""

from .topology import (NotInitializedError, generation, hierarchical_mesh,
                       init, is_initialized, local_rank, local_size, mesh,
                       mpi_threads_supported, process_count, process_rank,
                       rank, shutdown, size)
from .topology import topology as get_topology
from .ops import (Handle, HorovodInternalError, allgather, allgather_async,
                  allreduce, allreduce_async, broadcast, broadcast_async,
                  grouped_allreduce, poll, synchronize)
from .compression import Compression
from .optimizer import (DistributedOptimizer, DistributedGradientTransformation,
                        broadcast_parameters, broadcast_optimizer_state,
                        broadcast_object, allreduce_gradients)
from .utils.checkpoint import restore_checkpoint, save_checkpoint
from .checkpoint import CheckpointEngine, CorruptShardError
from .ops.timeline_jit import (step as timeline_jit_step,
                               merge_profiler_trace)
from .elastic import (ElasticState, SlowRankFailure, WorkerFailure,
                      run_elastic)
from .observability import (get_registry, metrics_snapshot,
                            prometheus_text)


def metrics_registry():
    """The process-global metrics registry (docs/metrics.md) — for
    registering application-level counters next to the framework's."""
    return get_registry()


def __getattr__(name):
    # The input-pipeline subsystem (docs/data.md) resolves lazily:
    # `hvd.data.build_loader(...)` works without paying its import on
    # every `import horovod_tpu`.
    if name == "data":
        import importlib
        return importlib.import_module(".data", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__version__ = "0.1.0"

__all__ = [
    "__version__",
    # topology
    "init", "shutdown", "is_initialized", "rank", "local_rank", "size",
    "local_size", "process_rank", "process_count", "mesh",
    "hierarchical_mesh", "get_topology", "mpi_threads_supported",
    "NotInitializedError", "generation",
    # collectives
    "allreduce", "allreduce_async", "allgather", "allgather_async",
    "broadcast", "broadcast_async", "grouped_allreduce", "poll",
    "synchronize", "Handle", "HorovodInternalError",
    "timeline_jit_step", "merge_profiler_trace",
    # training
    "Compression", "DistributedOptimizer",
    "DistributedGradientTransformation", "broadcast_parameters",
    "broadcast_optimizer_state", "broadcast_object", "allreduce_gradients",
    "save_checkpoint", "restore_checkpoint",
    "CheckpointEngine", "CorruptShardError",
    # elastic / adaptation
    "ElasticState", "WorkerFailure", "SlowRankFailure", "run_elastic",
    # observability
    "metrics_snapshot", "metrics_registry", "prometheus_text",
]
