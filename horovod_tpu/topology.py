"""Process & device topology layer — the TPU-native equivalent of Horovod's
process bring-up and rank/size API.

Reference parity (all paths relative to /root/reference):
  - ``hvd.init()`` / ``horovod_init`` / ``horovod_init_comm``
    (horovod/common/operations.cc:2384-2422, horovod/common/__init__.py:58-84)
  - ``rank/local_rank/size/local_size`` C API (operations.cc:2424-2460)
  - MPI communicator setup: world dup, node-local split via
    ``MPI_Comm_split_type(SHARED)``, cross-node split by local rank
    (operations.cc:1728-1797).

TPU-native redesign
-------------------
Horovod launches one *process per accelerator* and wires them with MPI. JAX
on TPU is single-controller-per-host SPMD: one process drives all local
chips, and ``jax.distributed`` + the XLA runtime replace MPI process wire-up.
We therefore map:

  =====================  =======================================================
  Horovod concept        TPU-native equivalent
  =====================  =======================================================
  rank                   *virtual rank* = global device index in the mesh.
                         ``rank()`` returns this process's first device's
                         index (the process "leads" its local devices).
  size                   ``jax.device_count()`` — total chips, matching
                         "number of GPUs" in the reference's benchmarks.
  local_rank/local_size  index/count of devices attached to this process.
  MPI world comm         a ``jax.sharding.Mesh`` over all devices with a flat
                         ``'dp'`` axis.
  local/cross comms      the same device set reshaped to ``('dcn', 'ici')``
                         axes (inter-host, intra-host) — the hierarchical
                         mesh used by hierarchical allreduce/allgather.
  =====================  =======================================================

Per-rank (per-device) data lives as a jax.Array sharded over the mesh's
``'dp'`` axis; host/replicated arrays mean "every local virtual rank
contributes this value", exactly as every Horovod rank passing the same
tensor.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh


class NotInitializedError(RuntimeError):
    """Raised when rank/size accessors are used before ``init()``.

    Mirrors the ``Horovod has not been initialized; use hvd.init()`` errors
    raised by the reference's ctypes basics layer
    (horovod/common/__init__.py:90-154).
    """


_NOT_INITIALIZED_MSG = (
    "Horovod-TPU has not been initialized; please call horovod_tpu.init()."
)


@dataclasses.dataclass
class Topology:
    """Immutable snapshot of the distributed topology created by ``init``."""

    devices: tuple            # all global devices, mesh order
    local_devices: tuple      # devices owned by this process
    mesh: Mesh                # flat mesh, axis 'dp'
    hier_mesh: Mesh           # ('dcn', 'ici') hierarchical mesh
    process_index: int
    process_count: int
    rank: int                 # first global device index of this process
    size: int                 # total device count
    local_rank: int           # == 0 for the leader virtual rank
    local_size: int           # local device count
    is_homogeneous: bool      # same local_size everywhere (operations.cc:1772-1790)
    # Elastic generation: 0 for the first launch (and all non-elastic
    # jobs); bumped by the elastic driver on every recovery relaunch
    # (HOROVOD_TPU_ELASTIC_GENERATION). A worker function uses it to
    # tell a cold start from a post-failure rejoin.
    generation: int = 0


_lock = threading.Lock()
_topology: Optional[Topology] = None


def _build_topology(devices: Sequence, process_index: int,
                    process_count: int) -> Topology:
    devices = tuple(devices)
    local_devices = tuple(d for d in devices if d.process_index == process_index)
    if not local_devices:
        # Single-process CPU emulation: every device is "local".
        local_devices = devices

    size = len(devices)
    local_size = len(local_devices)

    # Homogeneity check — reference allgathers local_sizes and compares
    # (operations.cc:1772-1790). Here the device list carries process ids.
    per_proc = {}
    for d in devices:
        per_proc[d.process_index] = per_proc.get(d.process_index, 0) + 1
    counts = set(per_proc.values())
    is_homogeneous = len(counts) <= 1

    mesh = Mesh(np.asarray(devices, dtype=object).reshape(size), ("dp",))
    # Hierarchical mesh: leading axis spans processes (DCN / inter-host),
    # trailing axis spans a process's chips (ICI / intra-host). This mirrors
    # the reference's cross_comm/local_comm split (operations.cc:1760-1797).
    if is_homogeneous and process_count >= 1 and size % max(local_size, 1) == 0:
        hier = np.asarray(devices, dtype=object).reshape(
            size // local_size, local_size)
    else:
        hier = np.asarray(devices, dtype=object).reshape(1, size)
    hier_mesh = Mesh(hier, ("dcn", "ici"))

    # Virtual-rank of this process's first device.
    first = devices.index(local_devices[0])
    return Topology(
        devices=devices,
        local_devices=local_devices,
        mesh=mesh,
        hier_mesh=hier_mesh,
        process_index=process_index,
        process_count=process_count,
        rank=first,
        size=size,
        local_rank=0,
        local_size=local_size,
        is_homogeneous=is_homogeneous,
        generation=_env_int("HOROVOD_TPU_ELASTIC_GENERATION") or 0,
    )


_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``.jax_cache``
    beside the package: one fixed path, because the path is part of the
    cache key and a directory that moves never hits. A TPU compile of a
    big train step costs a minute and is identical across restarts of
    the same job."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE


def init(*, coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None,
         devices: Optional[Sequence] = None) -> Topology:
    """Initialize the Horovod-TPU runtime.

    Equivalent of ``hvd.init()`` (horovod/common/__init__.py:58-84 →
    operations.cc:2384-2422). Where the reference spawns the background
    coordinator thread and calls ``MPI_Init_thread``, we:

      1. optionally call ``jax.distributed.initialize`` (the MPI_Init
         equivalent — rendezvous of all host processes), driven either by
         explicit arguments or by the standard JAX env vars that our
         launcher (``horovod_tpu.runner``) exports;
      2. snapshot the device topology into meshes;
      3. start the native background runtime (done lazily by the ops layer).

    Safe to call multiple times (the reference's InitializeHorovodOnce uses
    an atomic guard, operations.cc:2388-2397).
    """
    global _topology
    with _lock:
        if _topology is not None:
            return _topology

        coord = coordinator_address or os.environ.get(
            "HOROVOD_TPU_COORDINATOR")
        nproc = num_processes or _env_int("HOROVOD_TPU_NUM_PROCESSES")
        pid = process_id if process_id is not None else _env_int(
            "HOROVOD_TPU_PROCESS_ID")
        if coord and (nproc or 0) > 1:
            # Multi-process CPU meshes (the pod-shape test/dev harness)
            # need a real CPU collectives implementation: Gloo is
            # jaxlib's bundled TCP one. The knob only affects CPU client
            # creation, so it is a no-op on TPU backends. Must run
            # before the first backend touch.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=nproc,
                process_id=pid,
            )
            if jax.process_count() != nproc:
                # Split-brain guard: initialize() can "succeed" on a
                # backend that was already up and so never saw the
                # distributed config. Every worker then believes it is
                # rank 0 of 1 while the launcher env says N — rank-0-only
                # work (checkpoints, ETL) runs N times and races on
                # shared paths. Fail loudly instead.
                raise RuntimeError(
                    f"launcher requested {nproc} processes but the JAX "
                    f"backend initialized with process_count="
                    f"{jax.process_count()}: the backend was created "
                    "before jax.distributed.initialize() ran. Call "
                    "hvd.init() before any other JAX use in the worker.")

        compile_cache_dir()

        devs = tuple(devices) if devices is not None else tuple(jax.devices())
        _topology = _build_topology(
            devs, jax.process_index(), jax.process_count())
    # Telemetry exporters (docs/metrics.md): env-driven, idempotent,
    # no-op unless HOROVOD_TPU_METRICS_FILE / _PORT is set. Outside the
    # lock — the exporter reads topology through the public path.
    try:
        from .observability import maybe_start_exporters
        maybe_start_exporters()
    except Exception as e:  # never fail init over telemetry
        from .utils.logging import get_logger
        get_logger("topology").warning("metrics exporters not started: %s",
                                       e)
    # Flight recorder (docs/postmortem.md): stamp the process identity
    # on the always-on ring, arm the crash hooks (excepthook + SIGTERM
    # final gasp — only when a blackbox dir or metrics file is
    # configured), and record the init event itself.
    try:
        from .observability import flight_recorder as _flight
        _flight.recorder().configure(_topology.process_index,
                                     _topology.process_count,
                                     _topology.generation)
        _flight.recorder().note("init", (
            _topology.process_index, _topology.process_count,
            _topology.generation))
        _flight.maybe_install_hooks()
    except Exception as e:  # never fail init over telemetry
        from .utils.logging import get_logger
        get_logger("topology").warning("flight recorder not armed: %s", e)
    # Telemetry history + health detectors (docs/health.md): env-driven
    # (HOROVOD_TPU_HISTORY), idempotent, rides the shared telemetry
    # timer thread — the trend-aware plane the live gauges cannot be.
    try:
        from .observability import history as _history
        _history.maybe_start_sampler()
    except Exception as e:  # never fail init over telemetry
        from .utils.logging import get_logger
        get_logger("topology").warning("history sampler not started: %s",
                                       e)
    # Numerics plane (docs/numerics.md): env-driven single-flag arm —
    # nonfinite sentinels, gradient telemetry and fingerprint probes
    # all hang off this one module flag.
    try:
        from .observability import numerics as _numerics
        _numerics.maybe_enable_from_env()
    except Exception as e:  # never fail init over telemetry
        from .utils.logging import get_logger
        get_logger("topology").warning("numerics plane not armed: %s", e)
    return _topology


def shutdown() -> None:
    """Tear down the runtime (operations.cc:2425-2430 equivalent).

    Registered with ``atexit`` by the ops layer the same way the reference's
    Python basics register shutdown (horovod/common/__init__.py:69).
    """
    global _topology
    with _lock:
        _topology = None


def is_initialized() -> bool:
    return _topology is not None


def _get() -> Topology:
    if _topology is None:
        raise NotInitializedError(_NOT_INITIALIZED_MSG)
    return _topology


def topology() -> Topology:
    """The full topology snapshot (no reference equivalent — TPU extra)."""
    return _get()


def rank() -> int:
    """Global virtual rank of this process's leader device
    (operations.cc:2433-2438)."""
    return _get().rank


def local_rank() -> int:
    """Local rank within the host (operations.cc:2440-2445)."""
    return _get().local_rank


def size() -> int:
    """Total number of devices — the parity of "number of GPU ranks"
    (operations.cc:2447-2452)."""
    return _get().size


def local_size() -> int:
    """Number of devices driven by this process (operations.cc:2454-2460)."""
    return _get().local_size


def process_rank() -> int:
    """Host-process index (TPU-native extra; JAX ``process_index``)."""
    return _get().process_index


def process_count() -> int:
    """Host-process count (TPU-native extra; JAX ``process_count``)."""
    return _get().process_count


def mesh() -> Mesh:
    """The flat world mesh, axis name ``'dp'`` (the "world communicator")."""
    return _get().mesh


def hierarchical_mesh() -> Mesh:
    """The ``('dcn', 'ici')`` mesh (the local/cross communicator split)."""
    return _get().hier_mesh


def generation() -> int:
    """Elastic generation of this job (TPU-native extra): 0 on the first
    launch, incremented by the elastic driver on every recovery
    relaunch. See :mod:`horovod_tpu.elastic`."""
    return _get().generation


def mpi_threads_supported() -> bool:
    """Compatibility shim for ``hvd.mpi_threads_supported()``
    (operations.cc:2462-2468). There is no MPI on the TPU path; the JAX
    runtime is always safe to call from multiple Python threads, so this
    reports True after init."""
    _get()
    return True


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None
