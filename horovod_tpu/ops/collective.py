"""Eager collective ops — enqueue API, async handles, background cycle.

This is the TPU-native equivalent of the reference's L1 enqueue API and
background-thread runtime (horovod/common/operations.cc):

  - ``EnqueueTensorAllreduce/Allgather/Broadcast`` (operations.cc:2472-2591)
    → :func:`allreduce_async` / :func:`allgather_async` /
    :func:`broadcast_async`, returning integer handles like the torch binding
    (torch/mpi_ops_v2.cc:52-76, torch/handle_manager.cc:21-50).
  - The background thread + cycle (operations.cc:1921-1923, 2030-2380)
    → a dispatcher thread that wakes every ``cycle_time`` ms, drains the
    request queue, asks the native control plane (or the Python fallback)
    for a *fusion plan* — groups of same-op/same-dtype requests whose summed
    bytes fit the fusion threshold, with look-ahead over skipped requests
    (operations.cc:2149-2265) — and executes each group as ONE fused XLA
    program via :mod:`horovod_tpu.executor`.
  - Duplicate in-flight names are rejected with the reference's wording
    (DUPLICATE_NAME_ERROR, operations.cc:270-273).
  - ``poll``/``synchronize`` (torch/mpi_ops_v2.cc:228-234,
    torch/mpi_ops.py:406-438).

Negotiation: the reference's rank-0 coordinator gathers per-rank request
lists and only fuses tensors every rank has submitted (operations.cc:
2088-2134). Under JAX's single-controller model every *process* submits for
all its local virtual ranks at once, so intra-host negotiation is trivially
satisfied; the multi-host control plane (TCP coordinator in the native
runtime) mirrors the gather/bcast protocol across processes.
"""

from __future__ import annotations

import atexit
import contextlib
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import wire_format as _wire_flags
from .. import quantization as _quant
from .. import topology as _topo
from ..executor import (ALLGATHER, ALLREDUCE, BROADCAST, CollectiveExecutor,
                        default_executor)
from ..observability import flight_recorder as _flight
from ..observability import registry as _obs
from ..utils import env as _env
from ..utils.logging import get_logger

_log = get_logger("ops")


class _EngineMetrics:
    """Registry handles for the engine's hot paths, resolved ONCE at
    engine construction (docs/metrics.md): the per-op/per-phase child
    lookup must never sit inside the enqueue or dispatch loop. All
    counters are process-global registry state — they deliberately
    survive ``reset_engine()`` (the satellite fix: telemetry must not
    vanish with the instance that recorded it)."""

    _OPS = (ALLREDUCE, ALLGATHER, BROADCAST)

    def __init__(self):
        r = _obs.registry()
        phase = r.histogram(
            "hvdtpu_op_phase_seconds",
            "Per-collective latency by lifecycle phase (negotiate = "
            "enqueue until the group is agreed/delivered; queue = "
            "delivery until XLA dispatch; execute = fused program wall "
            "time)", buckets=_obs.LATENCY_BUCKETS)
        ops = r.counter("hvdtpu_ops_total", "Collective requests enqueued")
        exec_total = r.counter(
            "hvdtpu_op_execute_seconds_total",
            "Cumulative wall seconds executing fused collective groups")
        self.phase = {
            (op, ph): phase.labels(op=_op_name(op), phase=ph)
            for op in self._OPS
            for ph in ("negotiate", "queue", "execute")}
        self.ops = {op: ops.labels(op=_op_name(op)) for op in self._OPS}
        self.exec_total = {op: exec_total.labels(op=_op_name(op))
                           for op in self._OPS}
        self.group_size = r.histogram(
            "hvdtpu_fused_group_size",
            "Tensors per executed fusion group",
            buckets=_obs.SIZE_BUCKETS).labels()
        self.group_bytes = r.histogram(
            "hvdtpu_fused_group_bytes",
            "Wire bytes per executed fusion group",
            buckets=_obs.BYTE_BUCKETS).labels()
        self._wire = r.counter(
            "hvdtpu_wire_bytes_enqueued_total",
            "Bytes-on-wire enqueued, by compression wire spec ('raw' = "
            "the tensor's own dtype); matches _Request accounting")
        self._wire_children = {None: self._wire.labels(spec="raw")}
        self.cycles = r.counter(
            "hvdtpu_cycles_total",
            "Background dispatcher cycles (Python fallback loop)").labels()
        self.cycle_busy = r.counter(
            "hvdtpu_cycle_busy_seconds_total",
            "Dispatcher seconds spent draining/planning/executing").labels()
        self.cycle_idle = r.counter(
            "hvdtpu_cycle_idle_seconds_total",
            "Dispatcher seconds spent waiting for work").labels()
        self.stalled_count = r.gauge(
            "hvdtpu_engine_stalled_tensors",
            "In-flight collectives currently past the stall warning "
            "window (engine view)").labels()
        self.stalled_info = r.gauge(
            "hvdtpu_engine_stalled_tensor_seconds",
            "Seconds each stalled tensor has waited, labeled with the "
            "coordinator's missing-ranks report when available")
        self._adapted = r.counter(
            "hvdtpu_adaptation_applied_groups_total",
            "Fused allreduce groups executed under a policy wire "
            "override, by spec (docs/adaptation.md)")
        self._adapted_children: Dict[str, object] = {}

    def adapted_group(self, spec: str) -> None:
        child = self._adapted_children.get(spec)
        if child is None:
            child = self._adapted.labels(spec=spec)
            self._adapted_children[spec] = child
        child.inc()

    def wire_bytes(self, spec, nbytes: int) -> None:
        child = self._wire_children.get(spec)
        if child is None:
            child = self._wire.labels(spec=spec)
            self._wire_children[spec] = child
        child.inc(nbytes)

    def group_delivered(self, op: int, reqs, t_deliver: float) -> None:
        """Close the negotiate phase for every request in a delivered
        group and record the group's shape."""
        ph = self.phase.get((op, "negotiate"))
        if ph is None:
            return
        for r in reqs:
            ph.observe(t_deliver - r.enqueued_at)
        self.group_size.observe(len(reqs))
        self.group_bytes.observe(sum(r.nbytes for r in reqs))

    def group_executed(self, op: int, n: int, t_deliver: float,
                       t_start: float, t_end: float) -> None:
        key = (op, "queue")
        if key not in self.phase:
            return
        self.phase[key].observe(t_start - t_deliver)
        self.phase[(op, "execute")].observe(t_end - t_start)
        self.exec_total[op].inc(t_end - t_start)

    def set_stalls(self, entries) -> None:
        """Replace the stalled-tensor gauges with the current episode:
        ``entries`` is [(tensor, age_s, missing_ranks_str)]. Clearing
        first keeps resolved stalls from lingering in the export."""
        self.stalled_info.clear()
        self.stalled_count.set(len(entries))
        for tensor, age, missing in entries:
            self.stalled_info.labels(
                tensor=tensor, missing_ranks=missing).set(age)

DUPLICATE_NAME_ERROR = (
    "Requested to {op} a tensor with the same name as another tensor that is "
    "currently being processed. If you want to request another tensor, use a "
    "different tensor name.")

SHUT_DOWN_ERROR = (
    "Horovod has been shut down. This was caused by an exception on one of "
    "the ranks or an attempt to {op} a tensor after one of the ranks "
    "finished execution.")

# Enqueue-burst debounce for the fallback dispatcher (mirrors core.cc
# kDrainDebounceNs/kDrainMaxDeferNs): defer draining while a burst is
# still arriving so one step's requests always fuse into the same groups
# — stable compositions are what make the fused-program jit cache hit.
_DRAIN_DEBOUNCE_S = 0.002
_DRAIN_MAX_DEFER_S = 0.020
# Explicit burst scopes (engine.burst()) get a much larger valve: the
# scope's exit IS the drain boundary, and a 50-leaf enqueue loop alone
# can exceed 20 ms of wall time on an oversubscribed host. The valve only
# guards against a submitter hanging inside an open scope (mirrors
# core.cc kBurstMaxDeferNs).
_BURST_MAX_DEFER_S = 1.0


class HorovodInternalError(RuntimeError):
    pass


# Process-global launch lock for HOROVOD_TPU_ORDERED_LAUNCH=1: the engine
# takes it around each fused-collective enqueue, and producer streams take
# it via launch_lock() around their mesh-wide jit calls, making the host's
# launch order total WITHOUT waiting for producer completion (the fence's
# cost). Measured caveat (experiments/ordered_launch_ab.py): on the CPU
# backend PJRT fans executions out to per-device queues AFTER the Python
# call returns, so this ordering does NOT close the rendezvous-inversion
# window there — the completion fence stays the default.
_LAUNCH_LOCK = threading.RLock()


@contextlib.contextmanager
def launch_lock():
    """Order a producer launch against the engine's collective launches
    (ordered-launch mode). Wrap mesh-wide jit calls whose outputs feed
    eager collectives:

        with hvd.ops.launch_lock():
            grads = train_grads(params, batch)   # mesh-wide jit
        handles = [hvd.allreduce_async(g) for g in grads]

    A no-op contract note: taking the lock is only required when
    HOROVOD_TPU_ORDERED_LAUNCH=1; under the default fence policy it is
    harmless but unnecessary."""
    with _LAUNCH_LOCK:
        yield


class Handle:
    """Async operation handle (torch/handle_manager.{h,cc} equivalent)."""

    __slots__ = ("_event", "_result", "_error", "id", "name")

    def __init__(self, hid: int, name: str):
        self.id = hid
        self.name = name
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def _fulfill(self, result=None, error: Optional[BaseException] = None):
        self._result = result
        self._error = error
        self._event.set()

    def poll(self) -> bool:
        """Non-blocking completion check (mpi_ops_v2.cc ``PollHandle``)."""
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None):
        """Block until done; raise the op's error if any
        (``WaitAndClear`` semantics, torch/mpi_ops_v2.cc:228-234).

        About to block == the submitter's burst is fully enqueued (an
        async caller waits only after enqueueing everything), so hint the
        engine to drain immediately instead of waiting out the burst
        debounce."""
        if not self._event.is_set():
            _flush_hint()
            if not self._event.wait(timeout):
                raise TimeoutError(
                    f"collective '{self.name}' did not complete "
                    f"within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result


def _plan_dtype(dtype) -> np.dtype:
    """Size-equivalent numpy dtype for fusion planning (bfloat16 and fp8
    have no stable numpy identity across paths; only itemsize and
    same-key grouping matter here — execution dispatches on the real jax
    dtype)."""
    s = str(dtype)
    if s == "bfloat16":
        return np.dtype(np.float16)
    if s.startswith("float8"):
        return np.dtype(np.uint8)
    return np.dtype(dtype)


def _semantics_fingerprint(req) -> int:
    """Execution-semantic fingerprint carried in the wire's ``device``
    field (the reference records per-rank devices in each request and the
    coordinator rejects inconsistent groups, operations.cc:480-497; on
    the TPU path there is no per-op GPU id, so the slot carries the
    attributes that DO affect the execution program here). Processes
    passing different (average, prescale, postscale, sharded) for one
    tensor would silently compute different programs; fingerprinting
    them into the validated device slot turns that into the
    coordinator's Mismatched error instead. Also keys
    coordinator-side fusion: tensors with different semantics land in
    different groups on every process identically."""
    import zlib
    key = (f"{int(req.average)}|{req.prescale!r}|{req.postscale!r}|"
           f"{int(req.sharded)}|{int(req.per_rank is None)}|"
           f"{req.wire or ''}")
    return zlib.crc32(key.encode()) & 0x7FFFFFFF


class _Request:
    __slots__ = ("name", "op", "tensor", "per_rank", "root_rank", "average",
                 "prescale", "postscale", "handle", "nbytes", "dtype",
                 "enqueued_at", "sharded", "wire")

    def __init__(self, name, op, tensor, handle, *, per_rank=None,
                 root_rank=0, average=False, prescale=1.0, postscale=1.0,
                 sharded=False, wire=None):
        self.name = name
        self.op = op
        self.tensor = tensor
        self.per_rank = per_rank
        self.root_rank = root_rank
        self.average = average
        self.prescale = prescale
        self.postscale = postscale
        self.handle = handle
        self.sharded = sharded
        # Wire-format spec ("int8x256" / "fp8x256") for block-scaled
        # quantized allreduce; None = the tensor's own dtype is the wire.
        self.wire = wire
        if tensor is not None:
            self.dtype = _plan_dtype(tensor.dtype)
            n_elements = int(np.prod(tensor.shape))
            if wire is not None:
                # What fusion planning (and the engine's wire-byte
                # accounting) must count is bytes ON THE WIRE: quantized
                # payload + per-block scales, not the logical fp32 bytes.
                self.nbytes = _quant.wire_nbytes(wire, n_elements)
            else:
                self.nbytes = n_elements * self.dtype.itemsize
        else:
            self.dtype = _plan_dtype(per_rank[0].dtype)
            self.nbytes = sum(int(np.prod(t.shape)) for t in per_rank) * \
                self.dtype.itemsize
        self.enqueued_at = time.monotonic()


class CollectiveEngine:
    """Background dispatcher: queue → fusion plan → fused XLA programs.

    One instance per process, lazily started on first enqueue — mirroring
    ``InitializeHorovodOnce`` spawning the background thread
    (operations.cc:2384-2402).
    """

    def __init__(self, executor: Optional[CollectiveExecutor] = None):
        self._executor = executor
        self._lock = threading.Lock()
        self._queue: List[_Request] = []
        self._in_flight: Dict[str, _Request] = {}
        self._handle_counter = 0
        self._name_counter = 0
        self._thread: Optional[threading.Thread] = None
        self._shutdown = False
        self._wake = threading.Event()
        self._last_enqueue_t = 0.0
        self._oldest_enqueue_t = 0.0
        self._last_seen_qlen = 0
        # Flush hint (see flush_hint): a submitter about to block on a
        # handle declared the burst fully enqueued — drain NOW.
        self._flush = False
        # Explicit burst scope depth (see burst()): while > 0 the drain
        # defers regardless of queue growth. Owner threads (ident ->
        # open-scope count) are tracked so a FOREIGN waiter's flush hint
        # (a thread with no open scope blocking on a handle) cuts the
        # scope instead of being consumed by it — otherwise that wait
        # stalls until the 1 s burst valve fires.
        self._burst_depth = 0
        self._burst_owners: Dict[int, int] = {}
        self._foreign_flush = False
        # Producer-fence decision cache (see _fence_producers): resolved
        # once on first use — read-once env-knob semantics like every
        # other engine knob, and no environ/device lookups on the
        # per-group launch hot path.
        self._fence_decision: Optional[bool] = None
        self._ordered_decision: Optional[bool] = None
        self.mp_params: Dict = {}
        # name -> (latest coordinator missing-ranks stall line, wall time)
        # in MP mode; entries expire after 2x the warning window.
        self._coord_stall_lines: Dict[str, tuple] = {}
        # Knobs — reference defaults: 64 MiB fusion, 5 ms cycle
        # (operations.cc:1838,1846). We default the cycle to 1 ms: there is
        # no MPI round-trip to amortize on the single-controller path.
        self.fusion_threshold = _env.fusion_threshold_bytes()
        self.cycle_time_s = _env.cycle_time_ms() / 1000.0
        # Cumulative bytes-on-wire of every enqueued request (wire bytes,
        # i.e. quantized payload + scales for blockwise formats) — the
        # accounting the compression bench and acceptance tests read.
        # DEPRECATION ALIAS: the canonical series is the registry's
        # hvdtpu_wire_bytes_enqueued_total (labeled by wire spec, and it
        # survives reset_engine()); this attribute stays for existing
        # delta-based callers.
        self.wire_bytes_enqueued = 0
        # Registry handles (docs/metrics.md), resolved once — the
        # registry itself is process-global, so totals accumulate across
        # engine instances.
        self._metrics = _EngineMetrics()
        self.timeline = None          # Python-mode timeline (fallback path)
        self._timeline_tried = False  # decide once, off the hot path
        self._mark_cycles = _env.timeline_mark_cycles()
        # Cross-rank trace clock state (docs/tracing.md): the resolved
        # per-rank trace path, its monotonic epoch, and whether the
        # clock-alignment handshake still has to run (nonzero MP ranks
        # sync on their first control-plane cycle).
        self._trace_path: Optional[str] = None
        self._trace_start_mono_us = 0
        self._trace_clock_pending = False
        # Local fused-group counter for the single-process dispatch path
        # (MP groups carry the coordinator's seq instead) — keys the
        # merge tool's per-group attribution.
        self._local_group_seq = 0
        self.stall_warning_s = _env.stall_warning_secs()
        self._last_stall_check = time.monotonic()
        # Failure escalation window (elastic recovery): > 0 turns stalls
        # past the window — and coordinator-reported failure events —
        # into a typed WorkerFailure on the pending handles instead of
        # the warn-then-hang path. 0 (default) = seed behavior.
        self.failure_timeout_s = _env.failure_timeout_secs()
        # Env-forced hierarchical modes; the SP tuner's flags OR on top
        # (_on_native_execute).
        self._env_hier_allreduce = _env.hierarchical_allreduce()
        self._env_hier_allgather = _env.hierarchical_allgather()
        # Native control plane (C++ core, runtime/src/core.cc). When it
        # loads, the background cycle / tensor table / fusion planning /
        # timeline / stall check / autotune all run natively and this class
        # only executes the planned groups as XLA programs.
        self._native_core = None
        self._native_tried = False
        self._native_pending: Dict[int, _Request] = {}
        # Multi-process control plane (ops/control_plane.py): when more
        # than one host process participates, fusion groups must be agreed
        # across processes (SPMD programs over the global mesh), so the
        # rank-0 TCP coordinator replaces local planning.
        self._mp = None               # tri-state: None=unknown
        self._mp_client = None
        self._mp_service = None
        self._announced: set = set()
        # Fault harness (docs/adaptation.md): resolved once on first
        # enqueue; None (the default, no HOROVOD_TPU_FAULT_SPEC) keeps
        # the hot path at a single attribute check.
        self._faults = None
        self._faults_tried = False
        # Policy wire-override epochs from the coordinator's params
        # side-channel: [(from_seq, spec)] — groups with seq >= from_seq
        # execute with spec ('' = raw). Seq-keyed so every process flips
        # at the same group boundary (docs/adaptation.md).
        self._wire_epochs: List = []
        # Fusion-threshold epochs from the same side-channel:
        # [(from_seq, threshold_bytes)] stamped by the coordinator's
        # wire-epoch arbiter when the global autotuner re-caps the
        # fusion buffer (docs/autotune.md). The coordinator's planner is
        # the authority on grouping; this mirror exists so every
        # process's flight recorder shows the same seq-stamped move.
        self._fusion_epochs: List = []
        # Delivered-group counter for the native MP path (group
        # callbacks arrive in coordinator-seq order but carry no seq on
        # the wire) — mirrors the fallback path's group['seq'].
        self._mp_group_seq = 0

    # ------------------------------------------------------------- lifecycle

    @property
    def executor(self) -> CollectiveExecutor:
        if self._executor is None:
            self._executor = default_executor()
        return self._executor

    def _ensure_thread(self):
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._shutdown = False
                self._thread = threading.Thread(
                    target=self._loop, name="horovod_tpu_background",
                    daemon=True)
                self._thread.start()

    def _ensure_native(self):
        """Load + initialize the native control plane once (equivalent of
        InitializeHorovodOnce spawning the C++ background thread,
        operations.cc:2384-2402). Falls back to the Python control plane
        when the toolchain is unavailable or it is disabled via
        HOROVOD_TPU_DISABLE_NATIVE=1.

        In multi-process mode the native core IS the control plane too:
        its background cycle serializes this process's request batch
        (message.cc codec), hands it to :meth:`_native_transport` for the
        TCP announce/long-poll-fetch legs, parses the coordinator-agreed
        ResponseList, and delivers each group to :meth:`_on_native_group`
        for XLA execution — the worker half of the reference's
        RunLoopOnce (operations.cc:2323-2377) running in C++."""
        with self._lock:
            if self._native_tried:
                return self._native_core
            # Resolve under the lock: a concurrent first-enqueue must not
            # observe _native_tried=True with the core still loading (it
            # would silently split the control plane between the native and
            # Python paths).
            try:
                if os.environ.get("HOROVOD_TPU_DISABLE_NATIVE") == "1":
                    return None
                from ..runtime import native as _native_mod
                core = _native_mod.load()
                if core is None:
                    return None
                topo = _topo._get()
                # Per-rank trace capture (docs/tracing.md): the native
                # timeline reads HOROVOD_TPU_TIMELINE in C++ at init, so
                # expand the {rank} placeholder here — and drop the env
                # for nonzero ranks when there is NO placeholder, or
                # every process's native writer would open (and
                # truncate) the one shared file.
                tl_raw = _env.timeline_path()
                tl_resolved = (_env.resolved_timeline_path(
                    topo.process_index) if tl_raw else None)
                if tl_raw and tl_resolved is None:
                    os.environ.pop("HOROVOD_TPU_TIMELINE", None)
                    os.environ.pop("HOROVOD_TIMELINE", None)
                elif tl_resolved is not None and tl_resolved != tl_raw:
                    os.environ["HOROVOD_TPU_TIMELINE"] = tl_resolved
                t_before = time.monotonic()
                core.init(topo.process_index, topo.process_count,
                          topo.local_size, topo.size)
                if tl_resolved is not None and core.timeline_enabled():
                    # The native writer's epoch is steady_clock at its
                    # Initialize, somewhere inside core.init — the
                    # bracket midpoint approximates it to well under the
                    # init duration (same CLOCK_MONOTONIC domain).
                    self._trace_path = tl_resolved
                    self._trace_start_mono_us = int(
                        (t_before + time.monotonic()) / 2.0 * 1e6)
                    if topo.process_index == 0 or topo.process_count == 1:
                        self._write_clock_meta(0.0, 0.0, synced=True)
                    else:
                        self._trace_clock_pending = True
                else:
                    self._arm_blackbox_clock()
                core.set_execute_callback(self._on_native_execute)
                if topo.process_count > 1:
                    core.set_group_callback(self._on_native_group)
                    core.set_transport_callback(self._native_transport)
                self._native_core = core
            except Exception as e:  # pragma: no cover - degraded path
                _log.warning("native control plane init failed: %s", e)
                self._native_core = None
            finally:
                self._native_tried = True
        return self._native_core

    def _ensure_timeline(self):
        """Create the Python timeline writer for paths the native core
        does not cover (Python fallback, multi-process). Without a
        ``{rank}`` placeholder in the path, rank 0 writes like the
        reference (operations.cc:1824-1829) and an undeterminable rank
        does NOT write (a second writer would truncate rank 0's file);
        WITH the placeholder every rank writes its own file — the
        cross-rank capture mode (docs/tracing.md). Decision is made
        once; the monotonic flag makes the unlocked fast-path read
        safe."""
        if self._timeline_tried:
            return self.timeline
        with self._lock:
            if self._timeline_tried:
                return self.timeline
            self._timeline_tried = True
            if not _env.timeline_path() or self._shutdown:
                self._arm_blackbox_clock()
                return None
            try:
                topo = _topo._get()
                rank, world = topo.process_index, topo.process_count
            except Exception:
                return None
            path = _env.resolved_timeline_path(rank)
            if not path:
                self._arm_blackbox_clock()
                return None
            try:
                from .timeline_py import PyTimeline
                self.timeline = PyTimeline(path, rank=rank, world=world)
            except OSError as e:
                # Unwritable path disables the timeline, as the native
                # writer does (runtime/src/timeline.cc) — never fail the
                # user's collective over tracing.
                _log.warning("timeline disabled: cannot open %s: %s",
                             path, e)
                return None
            self._trace_path = path
            self._trace_start_mono_us = self.timeline.start_monotonic_us
            # Rank 0 (and single-process jobs) ARE the reference clock:
            # offset 0 by definition, sidecar written now. Other ranks
            # sync against the coordinator on their first MP cycle
            # (_maybe_sync_trace_clock).
            if rank == 0 or world == 1:
                self._write_clock_meta(0.0, 0.0, synced=True)
            else:
                self._trace_clock_pending = True
            return self.timeline

    def _arm_blackbox_clock(self) -> None:
        """With a blackbox dir configured but NO per-rank trace, the
        clock handshake must still run once so postmortem dumps align
        onto rank 0's clock: nonzero MP ranks mark the sync pending
        (the next control-plane cycle runs it); rank 0 and
        single-process jobs ARE the reference clock."""
        if not _env.blackbox_dir():
            return
        try:
            topo = _topo._get()
            rank, world = topo.process_index, topo.process_count
        except Exception:
            return
        if rank == 0 or world == 1:
            _flight.recorder().set_clock_meta(0.0, 0.0, True)
        else:
            self._trace_clock_pending = True

    def _write_clock_meta(self, offset_s: float, rtt_s: float,
                          synced: bool) -> None:
        """Record this rank's trace clock header: in-band metadata when
        the Python writer owns the file, plus the sidecar either way
        (the native writer's file is owned by C++ — the sidecar is the
        only channel there). ``offset_s`` is the estimated rank-0
        monotonic clock minus ours."""
        # The flight recorder's dump header carries the same clock
        # fields, so the postmortem tool aligns per-rank dumps exactly
        # like the trace merger aligns per-rank timelines
        # (docs/postmortem.md).
        _flight.recorder().set_clock_meta(offset_s, rtt_s, synced)
        path = self._trace_path
        if not path:
            return
        try:
            topo = _topo._get()
            rank, world = topo.process_index, topo.process_count
        except Exception:
            rank, world = 0, 1
        if self.timeline is not None and synced:
            self.timeline.set_clock_meta(offset_s, rtt_s)
        from . import timeline_py as _tlpy
        try:
            _tlpy.write_clock_sidecar(path, {
                "rank": rank, "world": world,
                "start_mono_us": self._trace_start_mono_us,
                "offset_to_rank0_us": offset_s * 1e6,
                "rtt_us": rtt_s * 1e6,
                "clock_synced": bool(synced)})
        except OSError as e:
            _log.warning("trace clock sidecar write failed: %s", e)

    def _maybe_sync_trace_clock(self, client) -> None:
        """Run the clock-alignment handshake once (nonzero MP ranks
        only; rank 0 is the reference clock): K NTP-style pings over the
        coordinator channel, min-RTT sample wins
        (CoordinatorClient.clock_sync), result recorded in the trace
        clock header. Runs on the background cycle thread right after
        the control plane comes up — a one-time cost of K tiny RPCs,
        never on the enqueue path."""
        if not self._trace_clock_pending:
            return
        self._trace_clock_pending = False
        probes = _env.trace_clock_probes()
        if probes <= 0:
            self._write_clock_meta(0.0, 0.0, synced=False)
            return
        try:
            res = client.clock_sync(probes=probes)
        except Exception as e:
            _log.warning("trace clock sync failed; offset recorded as "
                         "unsynced: %s", e)
            self._write_clock_meta(0.0, 0.0, synced=False)
            return
        self._write_clock_meta(res["offset_s"], res["rtt_s"], synced=True)

    def _is_multiprocess(self) -> bool:
        if self._mp is None:
            try:
                self._mp = _topo._get().process_count > 1
            except Exception:
                return False
        return self._mp

    def _ensure_mp(self):
        """Bring up the cross-process control plane once: process 0 hosts
        the coordinator (the rank-0 role, operations.cc:2061-2067), every
        process connects a client."""
        from . import control_plane as _cp
        with self._lock:
            if self._mp_client is not None:
                return self._mp_client
            topo = _topo._get()
            if topo.process_index == 0:
                self._mp_service = _cp.start_coordinator(
                    topo.process_count, self.fusion_threshold,
                    virtual_size=topo.size)
                self._mp_client = _cp.CoordinatorClient(
                    [("127.0.0.1", self._mp_service.port)],
                    self._mp_service.key, topo.process_index)
                return self._mp_client
            else:
                ep = _cp.control_endpoint()
                if ep is None:
                    raise HorovodInternalError(
                        "Multi-process eager collectives need the "
                        "coordinator address in HOROVOD_TPU_CONTROL "
                        "(exported by the horovod_tpu runner); launch "
                        "workers with `python -m horovod_tpu.runner` or "
                        "export it manually.")
                addr = [ep]
            self._mp_client = _cp.CoordinatorClient(
                addr, _cp.control_key(), topo.process_index)
            return self._mp_client

    def shutdown(self):
        """Drain and stop; outstanding handles get SHUT_DOWN_ERROR
        (operations.cc:1942-1998)."""
        if self._mp_client is not None:
            # Tell the controller first so peers' fetches see the flag;
            # the client reference stays until the native core is down
            # (its background thread may be mid-transport).
            self._mp_client.announce_shutdown()
        core = self._native_core
        if core is not None:
            # Native path: the C++ shutdown drains its queue (the execute
            # callback keeps firing until empty), then joins the background
            # thread and flushes the timeline.
            core.shutdown()
            self._native_core = None  # _native_tried stays True: terminal
            with self._lock:
                native_pending = list(self._native_pending.values())
                self._native_pending.clear()
            for req in native_pending:
                req.handle._fulfill(error=HorovodInternalError(
                    SHUT_DOWN_ERROR.format(op=_op_name(req.op))))
        self._mp_client = None
        with self._lock:
            self._shutdown = True
            pending = list(self._queue) + list(self._in_flight.values())
            self._queue.clear()
            self._in_flight.clear()
        self._wake.set()
        for req in pending:
            req.handle._fulfill(error=HorovodInternalError(
                SHUT_DOWN_ERROR.format(op=_op_name(req.op))))
        t = self._thread
        if t is not None and t.is_alive() and t is not threading.current_thread():
            t.join(timeout=5.0)
        self._thread = None
        if self._mp_service is not None:
            self._mp_service.shutdown()
            self._mp_service = None
        if self.timeline is not None:
            self.timeline.close()
            self.timeline = None
        from . import shm_transport as _shm
        _shm.reset()  # unmap + unlink this process's data-plane segments

    # --------------------------------------------------------------- enqueue

    def _next_name(self, prefix: str) -> str:
        with self._lock:
            self._name_counter += 1
            return f"{prefix}.noname.{self._name_counter}"

    def enqueue(self, req: _Request) -> Handle:
        if self._shutdown:
            # Terminal for this engine instance (operations.cc:2374-2377);
            # tests use reset_engine() to get a fresh one.
            raise HorovodInternalError(
                SHUT_DOWN_ERROR.format(op=_op_name(req.op)))
        if not self._faults_tried:
            # Fault harness (docs/adaptation.md), resolved once: with no
            # HOROVOD_TPU_FAULT_SPEC the enqueue path keeps exactly one
            # attribute check.
            self._faults_tried = True
            from ..adaptation import faults as _faults_mod
            self._faults = _faults_mod.injector()
        if self._faults is not None:
            poisoned = self._faults.on_enqueue(tensor=req.tensor)
            if poisoned is not None:
                # nan_at clause fired: the engine carries the poisoned
                # payload from here on, exactly as if the producer had
                # computed a NaN — detection happens downstream in the
                # numerics sentinel, not here (docs/numerics.md).
                req.tensor = poisoned
        self.wire_bytes_enqueued += req.nbytes
        self._metrics.wire_bytes(req.wire, req.nbytes)
        self._metrics.ops[req.op].inc()
        core = self._ensure_native()
        if core is not None:
            return self._enqueue_native(core, req)
        self._ensure_timeline()
        with self._lock:
            if self._shutdown:
                raise HorovodInternalError(
                    SHUT_DOWN_ERROR.format(op=_op_name(req.op)))
            if req.name in self._in_flight:
                raise ValueError(DUPLICATE_NAME_ERROR.format(
                    op=_op_name(req.op)))
            self._in_flight[req.name] = req
            if not self._queue:
                self._oldest_enqueue_t = time.monotonic()
            self._queue.append(req)
            self._last_enqueue_t = time.monotonic()
            # No timeline event here: the NEGOTIATE span is emitted as
            # one complete "X" event at group delivery, anchored at
            # req.enqueued_at — nothing on the user's enqueue path
            # (PyTimeline.negotiate_span).
        self._ensure_thread()
        self._wake.set()
        return req.handle

    # ----------------------------------------------------- native delegation

    def _enqueue_native(self, core, req: _Request) -> Handle:
        """EnqueueTensor* through the C++ tensor table
        (operations.cc:2472-2591)."""
        t = req.tensor if req.tensor is not None else req.per_rank[0]
        shape = list(t.shape)
        dtype = str(t.dtype)
        # Hold the engine lock across enqueue + registration: the native
        # cycle can fire the execute callback for this id before we return,
        # and the callback takes the same lock to pop the request — so it
        # blocks until registration is visible rather than dropping the op.
        with self._lock:
            native_id = core.enqueue(req.op, req.name, dtype, shape,
                                     root_rank=req.root_rank,
                                     device=_semantics_fingerprint(req),
                                     nbytes=req.nbytes)
            if native_id == -1:
                raise ValueError(DUPLICATE_NAME_ERROR.format(
                    op=_op_name(req.op)))
            if native_id == -2:
                raise HorovodInternalError(
                    SHUT_DOWN_ERROR.format(op=_op_name(req.op)))
            self._native_pending[native_id] = req
        return req.handle

    def _on_native_execute(self, op: int, native_ids: List[int], err: str):
        """Execute callback from the native background thread: the group was
        negotiated + fusion-planned in C++ (the PerformOperation dispatch
        point, operations.cc:768-791); run it as XLA programs."""
        core = self._native_core
        t_deliver = time.monotonic()
        with self._lock:
            pairs = [(i, self._native_pending.pop(i))
                     for i in native_ids if i in self._native_pending]
        if not pairs:
            return
        self._metrics.group_delivered(op, [r for _, r in pairs], t_deliver)
        # Flight-recorder group lifecycle (docs/postmortem.md): the
        # native SP wire carries no seq, so a local counter keys the
        # events (mirrors the timeline's _local_group_seq role).
        seq = self._local_group_seq
        self._local_group_seq += 1
        _flight.recorder().group_deliver(seq, _op_name(op), len(pairs))
        if err:
            _flight.recorder().group_error(seq, _op_name(op), len(pairs),
                                           err)
            core.complete([i for i, _ in pairs], 2, err)
            for i, r in pairs:
                core.release(i)
                r.handle._fulfill(error=HorovodInternalError(err))
            return
        # The native planner fuses on (op, dtype, bytes); execution-semantic
        # knobs the planner doesn't track (sharded-ness, averaging, scaling,
        # ragged gathers) subdivide the group here.
        subgroups: Dict[tuple, List] = {}
        for i, r in pairs:
            k = (r.sharded, r.average, r.prescale, r.postscale,
                 r.per_rank is None, r.root_rank, r.wire)
            subgroups.setdefault(k, []).append((i, r))
        ex = self.executor
        # Apply the SP tuner's execution-mode flags (hvdtpu_current_flags;
        # MP groups get theirs from the plan instead): env knobs force a
        # mode, the tuner explores on top — without this the tuned
        # hierarchical decision would never reach execution.
        flags = core.current_flags()
        ex.hierarchical_allreduce = (self._env_hier_allreduce or bool(
            flags & _wire_flags.FLAG_HIERARCHICAL_ALLREDUCE))
        ex.hierarchical_allgather = (self._env_hier_allgather or bool(
            flags & _wire_flags.FLAG_HIERARCHICAL_ALLGATHER))
        tl = core.timeline_enabled()
        for sub in subgroups.values():
            ids = [i for i, _ in sub]
            reqs = [r for _, r in sub]
            if tl:
                for r in reqs:
                    core.timeline_activity_end(r.name)       # close QUEUE
                    core.timeline_activity_start(r.name, _xla_activity(op))
            t_start = time.monotonic()
            try:
                results = self._execute_group(ex, reqs)
            except BaseException as e:
                msg = str(e)
                _flight.recorder().group_error(seq, _op_name(op),
                                               len(reqs), msg)
                core.complete(ids, 2, msg)
                for (i, r) in sub:
                    core.release(i)
                    r.handle._fulfill(error=_as_error(e))
                continue
            t_end = time.monotonic()
            self._metrics.group_executed(op, len(reqs), t_deliver,
                                         t_start, t_end)
            _flight.recorder().group_done(seq, _op_name(op), len(reqs),
                                          t_deliver, t_start, t_end)
            core.complete(ids, 0, "")
            for (i, r), out in zip(sub, results):
                core.release(i)
                r.handle._fulfill(result=out)

    # ------------------------------------- native multi-process bridge

    def _apply_fetch_side_channel(self, resp) -> None:
        """Coordinator side-channel shared by the native and fallback MP
        paths: log the authoritative missing-ranks stall report, and apply
        tuned SCALAR knobs (SyncParams, parameter_manager.cc:213-246) —
        cycle time paces this engine's announce cadence; program-affecting
        flags arrive per group instead (SPMD lockstep)."""
        for name, line in resp.stall:
            _log.warning("stalled tensor (coordinator report): %s", line)
            # Keep the authoritative missing-ranks line per tensor so the
            # engine's own stall warning can name the missing processes
            # (CheckForStalledTensors, operations.cc:1644-1668). The name
            # arrives as structured data in the (name, line) pair — never
            # parsed out of the display text. Stamped so stale lines
            # (tensor completed, name reused later) are never reported
            # and the cache cannot grow unboundedly.
            if name:
                self._coord_stall_lines[name] = (line, time.monotonic())
        failures = getattr(resp, "failures", None)
        if failures:
            for f in failures:
                _flight.recorder().note("failure", (
                    int(f.get("rank", -1)), str(f.get("kind", "")),
                    str(f.get("detail", ""))[:300]))
            # The coordinator escalated (heartbeat loss / stall past the
            # failure timeout): pending quorums can never complete, so
            # fail every in-flight handle with the TYPED event — the
            # elastic driver (or any caller) dispatches on
            # WorkerFailure.rank/host/kind instead of parsing log text.
            from ..elastic.failure import failure_from_event
            f = dict(failures[0])
            f["detail"] = "; ".join(
                str(x.get("detail", "")) for x in failures)
            # Typed construction: a slow_rank event becomes a
            # SlowRankFailure so the elastic driver can apply the
            # slow-rank blacklist window instead of the crash one.
            err = failure_from_event(f)
            _log.error("coordinator escalated worker failure: %s", err)
            self._fail_native_pending(err)
            self._fail_all(err)
        params = resp.params
        if params:
            we = params.get("wire_epochs")
            if we:
                # Policy wire-override epochs (docs/adaptation.md):
                # replace wholesale — the coordinator ships the full
                # (small) list every fetch, so a late joiner catches up
                # in one response.
                epochs = [(int(s), str(sp)) for s, sp in we]
                if epochs != self._wire_epochs:
                    _flight.recorder().note(
                        "wire_epoch", (";".join(
                            f"{s}:{sp or 'raw'}" for s, sp in epochs),))
                self._wire_epochs = epochs
            cyc = params.get("cycle_time_ms")
            if cyc and abs(cyc - self.cycle_time_s * 1000.0) > 1e-9:
                self.cycle_time_s = cyc / 1000.0
                core = self._native_core
                if core is not None:
                    core.cycle_time_ms = cyc
            fe = params.get("fusion_epochs")
            if fe:
                fepochs = [(int(s), int(t)) for s, t in fe]
                if fepochs != self._fusion_epochs:
                    _flight.recorder().note("autotune", (
                        "fusion_epoch", "fusion_threshold_mb",
                        str(fepochs[-1][1] >> 20), None, None,
                        ";".join(f"{s}:{t >> 20}" for s, t in fepochs)))
                self._fusion_epochs = fepochs
            ft = params.get("fusion_threshold")
            if ft:
                self.fusion_threshold = int(ft)
            # Last coordinator-served params (autotune_active/done etc.)
            # for tests and observability.
            self.mp_params = dict(params)

    def _fail_native_pending(self, err: BaseException) -> None:
        """Fail every native-tracked in-flight request loudly — the MP
        engine's _fail_all: clears the C++ tensor table (so names can be
        reused after the error) and fulfills the Python handles."""
        core = self._native_core
        with self._lock:
            pending = list(self._native_pending.items())
            self._native_pending.clear()
        for i, r in pending:
            if core is not None:
                core.complete([i], 2, str(err))
                core.release(i)
            r.handle._fulfill(error=_as_error(err))

    def _native_transport(self, req_bytes: bytes, nreq: int, complete: int,
                          pending: int) -> bytes:
        """The announce/fetch legs of the MP cycle, called from the native
        background thread (core.cc TransportCallback): ship this process's
        serialized RequestList to the rank-0 controller and long-poll the
        agreed ResponseList in ONE combined RPC, returning its bytes for
        the C++ parser. ``nreq == 0`` with a non-empty batch means
        retry-after-overflow (native.py caches the payload), so only
        announce fresh batches. ``complete`` marks the batch a complete
        enqueue burst — the coordinator plans eagerly on the last rank's
        complete announce, so long-poll for the imminent group; an
        INCOMPLETE (max-defer) announce short-polls to get back to
        announcing the burst remainder quickly.

        A transport failure (coordinator unreachable past the client's
        retries) is FATAL for the in-flight ops: the batch was already
        drained from the native queue and will never be re-announced, so
        peers would wait on quorum forever — fail the handles loudly
        instead of hanging the fleet."""
        try:
            client = self._ensure_mp()
            self._maybe_sync_trace_clock(client)
            if pending <= 0 and nreq <= 0:
                return b""
            wait = (self.cycle_time_s if (nreq > 0 and not complete)
                    else max(self.cycle_time_s, 0.05))
            if pending <= 0:
                wait = 0.0
            if nreq > 0:
                resp = client.announce_fetch(payload=req_bytes,
                                             complete=bool(complete),
                                             wait_s=wait)
            else:
                resp = client.fetch(wait_s=wait)
        except BaseException as e:
            _log.error("multi-process control plane failed: %s", e)
            self._fail_native_pending(_as_error(e))
            return b""
        self._apply_fetch_side_channel(resp)
        return resp.payload or b""

    def _on_native_group(self, op: int, native_ids: List[int], nnames: int,
                         sizes: List[int], flags: int, err: str):
        """Execute one coordinator-agreed group (core.cc GroupCallback) —
        the MP analogue of :meth:`_on_native_execute`, with group metadata
        (ragged allgather sizes, hierarchical flags) from the wire."""
        core = self._native_core
        if core is None:
            return
        t_deliver = time.monotonic()
        # Coordinator seq of this group: callbacks fire in seq order and
        # exactly once per group, so a local counter mirrors it (the
        # native wire carries no seq field) — keys the policy's
        # wire-override epochs identically to the fallback path.
        group_seq = self._mp_group_seq
        self._mp_group_seq += 1
        with self._lock:
            pairs = [(i, self._native_pending.pop(i))
                     for i in native_ids if i in self._native_pending]
        if len(native_ids) != nnames or len(pairs) != nnames:
            # Local/coordinator desync: peers will enter this group's SPMD
            # program; skipping it here would deadlock them. Fail loudly
            # (ADVICE r1) — every local in-flight op dies with a
            # diagnostic instead of the job hanging.
            desync = HorovodInternalError(
                f"coordinator/local state desync: group of {nnames} "
                f"tensors matched {len(pairs)} local handles; failing the "
                "engine rather than skipping a collective the other ranks "
                "will enter")
            _log.error("%s", desync)
            with self._lock:
                extra = list(self._native_pending.items())
                self._native_pending.clear()
            for i, r in pairs + extra:
                core.complete([i], 2, str(desync))
                core.release(i)
                r.handle._fulfill(error=desync)
            return
        self._metrics.group_delivered(op, [r for _, r in pairs], t_deliver)
        _flight.recorder().group_deliver(group_seq, _op_name(op),
                                         len(pairs))
        if err:
            _flight.recorder().group_error(group_seq, _op_name(op),
                                           len(pairs), err)
            ids = [i for i, _ in pairs]
            core.complete(ids, 2, err)
            for i, r in pairs:
                core.release(i)
                r.handle._fulfill(error=HorovodInternalError(err))
            return
        topo = _topo._get()
        nproc = topo.process_count
        # Per-process first dims in tensor_names (== handles) order.
        sizes_of = {}
        if op == ALLGATHER and len(sizes) == nnames * nproc:
            for j, (_, r) in enumerate(pairs):
                sizes_of[r.name] = sizes[j * nproc:(j + 1) * nproc]
        meta = {"sizes": sizes_of, "seq": group_seq}
        ex = self.executor
        # Plan-time flags rule execution for THIS group on every process —
        # the engine thread is the only executor user, so the flip is safe.
        ex.hierarchical_allreduce = bool(
            flags & _wire_flags.FLAG_HIERARCHICAL_ALLREDUCE)
        ex.hierarchical_allgather = bool(
            flags & _wire_flags.FLAG_HIERARCHICAL_ALLGATHER)
        subgroups: Dict[tuple, List] = {}
        for i, r in pairs:
            k = (r.sharded, r.average, r.prescale, r.postscale,
                 r.root_rank, r.wire)
            subgroups.setdefault(k, []).append((i, r))
        tl = core.timeline_enabled()
        for sub in subgroups.values():
            ids = [i for i, _ in sub]
            reqs = [r for _, r in sub]
            if tl:
                for r in reqs:
                    core.timeline_activity_end(r.name)       # close QUEUE
                    core.timeline_activity_start(r.name, _xla_activity(op))
            t_start = time.monotonic()
            try:
                results = self._execute_group_mp(ex, reqs, meta, topo, op)
            except BaseException as e:
                msg = str(e)
                _flight.recorder().group_error(group_seq, _op_name(op),
                                               len(reqs), msg)
                core.complete(ids, 2, msg)
                for (i, r) in sub:
                    core.release(i)
                    r.handle._fulfill(error=_as_error(e))
                continue
            t_end = time.monotonic()
            self._metrics.group_executed(op, len(reqs), t_deliver,
                                         t_start, t_end)
            _flight.recorder().group_done(group_seq, _op_name(op),
                                          len(reqs), t_deliver, t_start,
                                          t_end)
            core.complete(ids, 0, "")
            for (i, r), out in zip(sub, results):
                core.release(i)
                r.handle._fulfill(result=out)

    def make_handle(self, name: str) -> Handle:
        with self._lock:
            self._handle_counter += 1
            return Handle(self._handle_counter, name)

    def flush_hint(self) -> None:
        """Submitter hint that the current enqueue burst is complete (a
        handle is about to block): drain + announce NOW instead of
        waiting out the drain debounce — in tight synchronous training
        loops this collapses 1-3 ms of per-step control latency (the
        debounce window plus up to one cycle of pacing sleep)."""
        core = self._native_core
        if core is not None:
            core.flush()
        with self._lock:
            if threading.get_ident() not in self._burst_owners:
                # Foreign waiter: must not be consumed by an open burst
                # scope (see _loop's burst branch). Marked regardless of
                # CURRENT depth — a hint landing just before another
                # thread's burst() would otherwise be consumed by that
                # scope (the loop may not run in between); a stale mark
                # with no scope open is cleared by the loop. Scope exits
                # flush via _flush_now, never through here.
                self._foreign_flush = True
            self._flush = True
        self._wake.set()

    def _flush_now(self) -> None:
        """Scope-exit flush for the Python fallback dispatcher: drain
        immediately without the foreign-waiter marking (the exit IS the
        burst boundary, not a cut of it). The native path never comes
        here — hvdtpu_burst_end sets its flush hint in C++."""
        with self._lock:
            self._flush = True
        self._wake.set()

    @contextlib.contextmanager
    def burst(self):
        """Explicit burst scope for a multi-tensor submission: the cycle
        will not drain until the scope closes (bounded by the max-defer
        valve), so the whole group always lands as ONE fusion burst.
        Without it the drain debounce infers burst boundaries from queue
        growth, which misfires when the enqueueing thread is descheduled
        mid-burst on a busy host — a partial drain is a NEW fusion
        composition, and every distinct composition is a distinct
        compiled XLA program (measured: an unstable 53-leaf ResNet burst
        recompiled ~1 s/step on the CPU mesh; stable compositions hit
        the jit cache). Exiting the outermost scope flushes.

        Scope-owner threads are tracked: a blocking ``Handle.wait`` from
        a thread with NO open scope (a foreign waiter) cuts the scope
        and drains immediately instead of stalling until the 1 s
        max-defer valve — only the owner's own flush hints are
        superseded by the scope."""
        core = self._ensure_native()
        tid = threading.get_ident()
        if core is not None:
            core.burst_begin()
        else:
            with self._lock:
                self._burst_depth += 1
                self._burst_owners[tid] = self._burst_owners.get(tid, 0) + 1
        try:
            yield
        finally:
            if core is not None:
                core.burst_end()
            else:
                with self._lock:
                    self._burst_depth -= 1
                    outermost = self._burst_depth == 0
                    if self._burst_owners.get(tid, 0) <= 1:
                        self._burst_owners.pop(tid, None)
                    else:
                        self._burst_owners[tid] -= 1
                if outermost:
                    self._flush_now()

    # ------------------------------------------------------------ background

    def _loop(self):
        """``RunLoopOnce`` (operations.cc:2030-2380): sleep to cycle time,
        drain queue, plan fusion, execute. In multi-process mode the plan
        comes from the rank-0 coordinator instead of local fusion."""
        mp = self._is_multiprocess()
        m = self._metrics
        prev_cycle_end = time.monotonic()
        while not self._shutdown:
            self._wake.wait(timeout=self.cycle_time_s)
            self._wake.clear()
            if self._shutdown:
                return
            # Cycle utilization (docs/metrics.md): busy = this
            # iteration's drain/plan/execute work, idle = the wait
            # above. utilization = busy / (busy + idle).
            t_wake = time.monotonic()
            m.cycles.inc()
            m.cycle_idle.inc(t_wake - prev_cycle_end)
            if self._mark_cycles and self.timeline is not None:
                self.timeline.mark_cycle()  # HOROVOD_TIMELINE_MARK_CYCLES
            with self._lock:
                # Burst debounce (mirrors core.cc DrainShouldDefer):
                # draining mid-burst cuts timing-dependent fusion groups,
                # and every distinct composition is a distinct compiled
                # program. Bounded so a continuous stream cannot starve
                # dispatch, and overridden by a flush hint (a submitter
                # about to block declared the burst fully enqueued).
                now = time.monotonic()
                qlen = len(self._queue)
                grew = qlen > self._last_seen_qlen
                self._last_seen_qlen = qlen
                complete = True
                if qlen > 0 and self._burst_depth > 0:
                    # Explicit burst scope open: defer regardless of
                    # growth (the growth heuristic misfires when the
                    # enqueuer is descheduled on a busy host), bounded
                    # by the burst valve. The scope OWNER's flush hint
                    # is consumed — the scope supersedes it (its own
                    # exit will flush). A FOREIGN waiter's hint cuts
                    # the scope: stalling that wait until the 1 s valve
                    # is worse than one timing-dependent composition.
                    # Mirrors DrainShouldDefer.
                    self._flush = False
                    if self._foreign_flush:
                        self._foreign_flush = False
                        defer = False
                        complete = False  # mid-scope cut
                    elif (now - self._oldest_enqueue_t
                            >= _BURST_MAX_DEFER_S):
                        defer = False
                        complete = False  # valve cut a mid-scope burst
                    else:
                        defer = True
                else:
                    self._foreign_flush = False
                    flush = self._flush
                    # Defer only while the burst is still GROWING — a
                    # lone blocking caller's single request must not pay
                    # the debounce (its submitter is stuck on the
                    # handle).
                    defer = (qlen > 0 and grew and not flush
                             and now - self._last_enqueue_t
                             < _DRAIN_DEBOUNCE_S
                             and now - self._oldest_enqueue_t
                             < _DRAIN_MAX_DEFER_S)
                    if not defer:
                        # Complete unless the max-defer valve cut a
                        # still-growing burst.
                        complete = flush or not (
                            grew
                            and now - self._oldest_enqueue_t
                            >= _DRAIN_MAX_DEFER_S)
                if defer:
                    batch = []
                else:
                    batch = self._queue
                    self._queue = []
                    self._last_seen_qlen = 0
                    self._flush = False
            if defer:
                # Also skip the MP fetch: a long-poll here would hold the
                # rest of the burst back past the coordinator's quiet
                # window.
                prev_cycle_end = time.monotonic()
                m.cycle_busy.inc(prev_cycle_end - t_wake)
                continue
            if mp:
                try:
                    self._mp_cycle(batch, complete)
                except BaseException as e:   # pragma: no cover - safety net
                    _log.error("multi-process cycle failed: %s", e)
                    self._fail_all(_as_error(e))
            elif batch:
                try:
                    self._dispatch(batch)
                except BaseException as e:   # pragma: no cover - safety net
                    _log.error("background dispatch failed: %s", e)
            self._maybe_check_stalls()
            prev_cycle_end = time.monotonic()
            m.cycle_busy.inc(prev_cycle_end - t_wake)

    def _fail_all(self, err: BaseException):
        with self._lock:
            pending = list(self._in_flight.values())
            self._in_flight.clear()
        for r in pending:
            r.handle._fulfill(error=err)

    # ------------------------------------------- multi-process cycle

    def _mp_cycle(self, batch: List[_Request], complete: bool = True):
        """The worker half of RunLoopOnce (operations.cc:2323-2377):
        announce newly-ready requests (the Gatherv) and fetch the agreed
        ordered group list (the Bcast) in ONE combined RPC, then execute
        each group. A complete-burst announce long-polls (the coordinator
        plans eagerly on the last rank's complete announce); an
        incomplete one short-polls to announce the remainder quickly."""
        client = self._ensure_mp()
        self._maybe_sync_trace_clock(client)
        requests = [{
            "name": r.name, "op": r.op,
            "dtype": str((r.tensor if r.tensor is not None
                          else r.per_rank[0]).dtype),
            "shape": tuple((r.tensor if r.tensor is not None
                            else r.per_rank[0]).shape),
            "root_rank": r.root_rank, "nbytes": r.nbytes,
            "device": _semantics_fingerprint(r),
        } for r in batch]
        with self._lock:
            waiting = bool(self._in_flight)
        if not waiting and not requests:
            return
        wait = (self.cycle_time_s if (batch and not complete)
                else max(self.cycle_time_s, 0.05))
        if not waiting:
            wait = 0.0
        resp = client.announce_fetch(requests=requests or None,
                                     complete=complete, wait_s=wait)
        self._apply_fetch_side_channel(resp)
        if resp.shutdown:
            # A peer announced shutdown — possibly from its teardown path,
            # in which case it will never enter the still-pending SPMD
            # programs; executing them would hang the surviving ranks in
            # XLA collectives. Fail everything with SHUT_DOWN_ERROR
            # instead, matching the reference's drain of queued tensors on
            # shutdown (operations.cc:1942-1998).
            self._fail_all(HorovodInternalError(
                SHUT_DOWN_ERROR.format(op="run")))
            return
        for group in resp.groups:
            self._execute_mp_group(group)

    def _execute_mp_group(self, group: dict):
        """Execute one coordinator-agreed group. All names were announced
        by this process (a group forms only when every process announced),
        so the requests MUST be in our in-flight table — a missing name
        means local/coordinator desync (e.g. _fail_all cleared in-flight
        after a cycle exception while announcements remained registered).
        Skipping the collective while peers execute it would deadlock the
        SPMD program, so desync is fatal for the engine instead."""
        t_deliver = time.monotonic()
        with self._lock:
            reqs = [self._in_flight.pop(n) for n in group["names"]
                    if n in self._in_flight]
        if len(reqs) != len(group["names"]):
            have = {r.name for r in reqs}
            missing = [n for n in group["names"] if n not in have]
            err = HorovodInternalError(
                "coordinator/local state desync: coordinator group "
                f"{group['names']} includes tensors this process no longer "
                f"has in flight ({missing}); failing the engine rather than "
                "skipping a collective the other ranks will enter")
            _log.error("%s", err)
            for r in reqs:
                r.handle._fulfill(error=err)
            # Propagate: _loop's guard fails every remaining in-flight
            # request, so the job dies with a diagnostic instead of
            # hanging all ranks.
            raise err
        if reqs:
            self._metrics.group_delivered(reqs[0].op, reqs, t_deliver)
            _flight.recorder().group_deliver(
                group.get("seq"), _op_name(reqs[0].op), len(reqs))
        tl = self.timeline
        if tl is not None:
            for r in reqs:
                # One complete NEGOTIATE span per tensor, anchored at
                # its true enqueue tick, carrying the coordinator seq —
                # identical on every rank for this group, the merge
                # tool's cross-rank group key (docs/tracing.md).
                tl.negotiate_span(r.name, _op_name(r.op), r.enqueued_at,
                                  t_deliver, group=group.get("seq"))
        if group["error"]:
            if reqs:
                _flight.recorder().group_error(
                    group.get("seq"), _op_name(reqs[0].op), len(reqs),
                    group["error"])
            for r in reqs:
                r.handle._fulfill(error=HorovodInternalError(group["error"]))
            return
        ex = self.executor
        # Plan-time flags rule execution for this group on every process
        # (SPMD lockstep; the engine thread is the executor's only user).
        flags = int(group.get("flags", 0))
        ex.hierarchical_allreduce = bool(
            flags & _wire_flags.FLAG_HIERARCHICAL_ALLREDUCE)
        ex.hierarchical_allgather = bool(
            flags & _wire_flags.FLAG_HIERARCHICAL_ALLGATHER)
        # Execution-semantic attributes the coordinator doesn't track
        # subdivide the group — deterministically, since SPMD call sites
        # pass identical attributes on every process.
        subgroups: Dict[tuple, List[_Request]] = {}
        for r in reqs:
            k = (r.sharded, r.average, r.prescale, r.postscale,
                 r.root_rank, r.wire)
            subgroups.setdefault(k, []).append(r)
        topo = _topo._get()
        for sub in subgroups.values():
            t_start = time.monotonic()
            try:
                results = self._execute_group_mp(ex, sub, group, topo)
            except BaseException as e:
                if tl is not None:
                    t_end = time.monotonic()
                    for r in sub:
                        tl.execute_span(r.name, _xla_activity(sub[0].op),
                                        t_start, t_end)
                _flight.recorder().group_error(
                    group.get("seq"), _op_name(sub[0].op), len(sub),
                    str(e))
                err = _as_error(e)
                for r in sub:
                    r.handle._fulfill(error=err)
                continue
            t_end = time.monotonic()
            self._metrics.group_executed(sub[0].op, len(sub), t_deliver,
                                         t_start, t_end)
            _flight.recorder().group_done(
                group.get("seq"), _op_name(sub[0].op), len(sub),
                t_deliver, t_start, t_end)
            for r, out in zip(sub, results):
                if tl is not None:
                    # One complete XLA span per tensor, shape riding
                    # along (the reference's activity + shape-on-end).
                    tl.execute_span(r.name, _xla_activity(sub[0].op),
                                    t_start, t_end,
                                    getattr(out, "shape", None))
                r.handle._fulfill(result=out)

    def _execute_group_mp(self, ex: CollectiveExecutor,
                          group: List[_Request], meta: dict, topo,
                          op: Optional[int] = None) -> List:
        """One coordinator-agreed subgroup as XLA programs — shared by the
        native (_on_native_group) and fallback (_execute_mp_group) MP
        paths; ``meta['sizes']`` carries the per-process allgather dims."""
        if op is None:
            op = group[0].op
        if op == ALLREDUCE:
            if group[0].sharded:
                return [ex.allreduce_sharded(
                    r.tensor, average=r.average, prescale=r.prescale,
                    postscale=r.postscale) for r in group]
            post = group[0].postscale
            if group[0].average:
                post = post / ex.world_size
            wire = group[0].wire
            tensors = [r.tensor for r in group]
            restore = None
            if wire is None:
                # Policy wire override (docs/adaptation.md), keyed on
                # the coordinator seq so every process flips at the
                # same group boundary. 'bf16' is a cast transport (the
                # fused program moves bf16); the blockwise specs ride
                # the executor's quantized wire path.
                ov = self._wire_override_for(meta.get("seq"), group)
                if ov == "bf16":
                    restore = [t.dtype for t in tensors]
                    tensors = [t.astype(jnp.bfloat16) for t in tensors]
                elif ov:
                    wire = ov
            outs = ex.allreduce_fused_mp(
                tensors, prescale=group[0].prescale,
                postscale=post, wire=wire)
            if restore is not None:
                outs = [o.astype(dt) for o, dt in zip(outs, restore)]
            return outs
        if op == BROADCAST:
            if group[0].sharded:
                return [ex.broadcast_sharded(r.tensor, r.root_rank)
                        for r in group]
            # Root from the request (validated identical across ranks by
            # the coordinator); the native wire carries no root field.
            return ex.broadcast_fused_mp([r.tensor for r in group],
                                         group[0].root_rank)
        if op == ALLGATHER:
            outs: List = []
            for r in group:
                if r.sharded:
                    # Already a global dp-sharded array: re-gather in
                    # place (cannot be pulled host-side across processes).
                    outs.append(ex.allgather_sharded_mp(r.tensor))
                    continue
                proc_dims = meta["sizes"].get(r.name)
                if proc_dims is None:
                    proc_dims = [int(r.tensor.shape[0])] * topo.process_count
                # One segment per virtual rank: expand the per-process
                # first dims by each process's device count (homogeneous
                # topology, checked at init like operations.cc:1772-1790).
                dev_dims = [d for d in proc_dims
                            for _ in range(topo.local_size)]
                if len(set(dev_dims)) == 1:
                    outs.append(ex.allgather_fused_mp([r.tensor])[0])
                else:
                    outs.append(ex.allgather_ragged_mp(r.tensor, dev_dims))
            return outs
        raise ValueError(f"unknown op {op}")

    def _wire_override_for(self, seq, group) -> Optional[str]:
        """Wire spec the policy's epoch list imposes on this fused
        allreduce group, or None. Epochs are [(from_seq, spec)] in
        ascending from_seq order; the last epoch at or below ``seq``
        wins ('' = back to raw). Only clean floating full-precision
        groups are eligible — an explicit user wire spec, sharded
        arrays, and non-float dtypes are left untouched."""
        epochs = self._wire_epochs
        if not epochs or seq is None:
            return None
        spec = None
        for fs, sp in epochs:
            if seq >= fs:
                spec = sp
            else:
                break
        if not spec:
            return None
        for r in group:
            t = r.tensor
            if (t is None or r.sharded or r.wire is not None
                    or not jnp.issubdtype(t.dtype, jnp.floating)):
                return None
        self._metrics.adapted_group(spec)
        return spec

    def _maybe_check_stalls(self):
        """Stall detector (CheckForStalledTensors, operations.cc:1625-1672):
        warn about requests stuck in flight past the warning time, with
        the reference report's per-tensor diagnostic quality — op type,
        wait duration, and (multi-process) WHICH ranks are missing, taken
        from the coordinator's authoritative table. In single-process
        mode every virtual rank is driven by this process, so no rank can
        be 'missing' — a stall there means the dispatcher is wedged or an
        async handle was never awaited, and the report says so."""
        if self.stall_warning_s <= 0:
            return
        now = time.monotonic()
        if now - self._last_stall_check < self.stall_warning_s:
            return
        self._last_stall_check = now
        with self._lock:
            stalled = [(r.name, _op_name(r.op), now - r.enqueued_at)
                       for r in self._in_flight.values()
                       if now - r.enqueued_at > self.stall_warning_s]
        if not stalled:
            # The previous episode resolved: zero the gauges so the
            # export stops naming tensors that completed.
            self._metrics.set_stalls([])
            return
        mp = self._is_multiprocess()
        # Expire coordinator lines from a PREVIOUS stall episode: a line
        # older than two warning windows describes ranks that were
        # missing then, not now (names are commonly reused per step).
        cutoff = now - 2.0 * self.stall_warning_s
        self._coord_stall_lines = {
            n: (ln, ts) for n, (ln, ts) in self._coord_stall_lines.items()
            if ts >= cutoff}
        lines = []
        gauge_entries = []
        for name, op, age in sorted(stalled):
            coord = self._coord_stall_lines.get(name)
            if coord is not None:
                lines.append(f"{coord[0]} [{op}, waiting {int(age)}s]")
                gauge_entries.append(
                    (name, age, _missing_ranks_of(coord[0])))
            elif mp:
                lines.append(
                    f"{name} [{op}, waiting {int(age)}s; announced, "
                    "awaiting coordinator grouping — see coordinator "
                    "report for missing ranks]")
                gauge_entries.append((name, age, "unknown"))
            else:
                lines.append(
                    f"{name} [{op}, waiting {int(age)}s; single-process: "
                    "all virtual ranks are local, so no rank is missing — "
                    "likely a wedged dispatcher or an unawaited handle]")
                gauge_entries.append((name, age, "none(single-process)"))
        self._metrics.set_stalls(gauge_entries)
        _log.warning(
            "One or more tensors were submitted to be reduced, gathered "
            "or broadcasted by subset of ranks and are waiting for "
            "remainder of ranks for more than %d seconds. This may "
            "indicate that different ranks are trying to submit "
            "different tensors or that only subset of ranks is "
            "submitting tensors, which will cause deadlock.\n"
            "Stalled ops:\n%s",
            int(self.stall_warning_s), "\n".join(lines))
        self._maybe_escalate_stalls(now)

    def _maybe_escalate_stalls(self, now: float) -> None:
        """Escalation past the failure timeout (elastic recovery): a
        request stuck longer than ``failure_timeout_s`` will never
        complete — some rank is gone — so fail its handle with a typed
        WorkerFailure instead of warning forever. The blocked submitter
        unblocks with an event the elastic driver can act on. Off by
        default (``failure_timeout_s == 0`` keeps warn-only parity with
        the reference's stall report)."""
        if self.failure_timeout_s <= 0:
            return
        with self._lock:
            overdue = [r for r in self._in_flight.values()
                       if now - r.enqueued_at > self.failure_timeout_s]
            for r in overdue:
                self._in_flight.pop(r.name, None)
                if r in self._queue:
                    self._queue.remove(r)
        if not overdue:
            return
        from ..elastic.failure import WorkerFailure
        names = ", ".join(sorted(r.name for r in overdue))
        for r in overdue:
            coord = self._coord_stall_lines.get(r.name)
            err = WorkerFailure(
                kind="stall",
                detail=(f"collective '{r.name}' ({_op_name(r.op)}) "
                        f"incomplete after "
                        f"{now - r.enqueued_at:.1f}s "
                        f"(> failure timeout {self.failure_timeout_s:.1f}s)"
                        + (f"; coordinator report: {coord[0]}"
                           if coord else "")))
            r.handle._fulfill(error=err)
        _log.error("escalated %d stalled collectives to WorkerFailure "
                   "after %.1fs: %s", len(overdue),
                   self.failure_timeout_s, names)
        # Stall escalation is a death sentence for the pending work —
        # capture the evidence NOW, while the engine still remembers the
        # episode (the submitter may hang instead of exiting cleanly).
        _flight.recorder().note(
            "stall", (names, round(max(now - r.enqueued_at
                                       for r in overdue), 3)))
        _flight.dump_on("stall_escalation")

    # ------------------------------------------------------------- execution

    @staticmethod
    def _fusion_key(req: _Request) -> tuple:
        """Attributes that must agree for two requests to share one fused
        program: op, planning dtype, WIRE format (wire bytes are what the
        threshold counts, and a quantized program is a different program),
        sharded-ness, root, and the execution-scaling knobs."""
        return (req.op, str(req.dtype), req.wire, req.sharded,
                req.root_rank, req.average, req.prescale, req.postscale)

    def _plan_fusion(self, batch: List[_Request]) -> List[List[_Request]]:
        """Greedy fusion with look-ahead (operations.cc:2149-2265).

        Requests fuse when they share a fusion key and the group's wire
        bytes stay under the threshold. Single pass over the batch:
        requests bucket by fusion key, and within a key a request joins
        the FIRST open group with room (first-fit) or opens a new group
        at its submission position. This reproduces the reference's
        round-based look-ahead exactly — in round r a request joins
        group r iff it didn't fit groups 1..r-1, which is first-fit in
        group-creation order — without the old O(n²) full rescan per
        group. Groups come out ordered by their first member's
        submission position. Per-rank (ragged allgather) requests never
        fuse and form singleton groups in place.
        """
        groups: List[List[_Request]] = []
        open_groups: Dict[tuple, List[List]] = {}  # key -> [group, total]s
        for req in batch:
            if req.per_rank is not None:
                groups.append([req])
                continue
            buckets = open_groups.setdefault(self._fusion_key(req), [])
            for entry in buckets:
                if entry[1] + req.nbytes <= self.fusion_threshold:
                    entry[0].append(req)
                    entry[1] += req.nbytes
                    break
            else:
                group = [req]
                groups.append(group)
                buckets.append([group, req.nbytes])
        return groups

    def _dispatch(self, batch: List[_Request]):
        ex = self.executor
        tl = self.timeline
        t_drain = time.monotonic()
        for group in self._plan_fusion(batch):
            names = [r.name for r in group]
            op = group[0].op
            self._metrics.group_delivered(op, group, t_drain)
            seq = self._local_group_seq
            self._local_group_seq += 1
            _flight.recorder().group_deliver(seq, _op_name(op), len(group))
            if tl is not None:
                for r in group:
                    # Same span diet as the MP path: one complete
                    # NEGOTIATE span anchored at the enqueue tick, one
                    # XLA span after execution.
                    tl.negotiate_span(r.name, _op_name(op),
                                      r.enqueued_at, t_drain, group=seq)
            t_start = time.monotonic()
            try:
                results = self._execute_group(ex, group)
            except BaseException as e:
                with self._lock:
                    for r in group:
                        self._in_flight.pop(r.name, None)
                if tl is not None:
                    t_end = time.monotonic()
                    for n in names:
                        tl.execute_span(n, _xla_activity(op), t_start,
                                        t_end)
                _flight.recorder().group_error(seq, _op_name(op),
                                               len(group), str(e))
                for r in group:
                    r.handle._fulfill(error=_as_error(e))
                continue
            t_end = time.monotonic()
            self._metrics.group_executed(op, len(group), t_drain,
                                         t_start, t_end)
            _flight.recorder().group_done(seq, _op_name(op), len(group),
                                          t_drain, t_start, t_end)
            with self._lock:
                for r in group:
                    self._in_flight.pop(r.name, None)
            for r, out in zip(group, results):
                if tl is not None:
                    # One complete XLA span per tensor, shape attached.
                    tl.execute_span(r.name, _xla_activity(op), t_start,
                                    t_end, getattr(out, "shape", None))
                r.handle._fulfill(result=out)

    def _fence_producers(self) -> bool:
        """Whether collective launches must wait for input producers.

        The hazard (observed 4-of-8 on the CPU mesh): this
        engine thread launching a mesh-wide program while a user
        thread's mesh-wide program dispatch is still fanning out across
        the per-device queues leaves no global enqueue order — two
        all-device programs queued in opposite orders on different
        devices deadlock in XLA's collective rendezvous. The inversion
        NEEDS more than one addressable device: with one device per
        process (the real-pod shape, and the single-chip bench) every
        launch lands in one FIFO queue and ordering is total, so the
        fence is skipped and the collective enqueues behind the
        still-running producer — restoring the compute/collective
        overlap the reference gets from ready-events + NCCL streams
        (operations.cc:816-840, 1117-1191). HOROVOD_TPU_PRODUCER_FENCE
        forces either way.

        Contract (measured, test_engine_overlap.py): the fence covers
        PRODUCER-feeding flows — mesh programs whose outputs are the
        collective's inputs. An unrelated mesh-wide jit stream from
        another thread concurrent with eager collectives deadlocks on
        a multi-device process regardless (no fence can order two
        threads' unrelated launches); that pattern must use the jit
        optimizer path."""
        if self._fence_decision is None:
            forced = _env.producer_fence()
            self._fence_decision = (forced if forced is not None
                                    else jax.local_device_count() > 1)
        return self._fence_decision

    def _ordered_launch(self) -> bool:
        """HOROVOD_TPU_ORDERED_LAUNCH=1 (read once, like every engine
        knob): replace the completion fence with enqueue-ordering under
        _LAUNCH_LOCK. Prototype for platforms whose per-device enqueue
        is host-call-ordered; see utils/env.ordered_launch for the
        measured CPU-backend caveat."""
        if self._ordered_decision is None:
            self._ordered_decision = _env.ordered_launch()
        return self._ordered_decision

    def _execute_group(self, ex: CollectiveExecutor,
                       group: List[_Request]) -> List:
        if self._ordered_launch():
            # Enqueue-ordered launch: no producer completion wait; the
            # lock only serializes the enqueue against producer streams
            # that take launch_lock(). The XLA dispatch below returns
            # futures, so the lock hold time is the enqueue, not the
            # collective.
            with _LAUNCH_LOCK:
                return self._execute_group_ops(ex, group)
        if self._fence_producers():
            # Multi-device process: retire producers first (see
            # _fence_producers). Tensors that are already on device and
            # committed (is_ready) — or host arrays — skip the block,
            # so an async submitter whose grads landed early pays
            # nothing.
            pending = []
            for r in group:
                ts = r.per_rank if r.per_rank is not None else (r.tensor,)
                for t in ts:
                    if t is None:
                        continue
                    ready = getattr(t, "is_ready", None)
                    if ready is not None and not ready():
                        pending.append(t)
            if pending:
                jax.block_until_ready(pending)
        return self._execute_group_ops(ex, group)

    def _execute_group_ops(self, ex: CollectiveExecutor,
                           group: List[_Request]) -> List:
        op = group[0].op
        if op == ALLREDUCE:
            if group[0].sharded:
                return [ex.allreduce_sharded(
                    r.tensor, average=r.average, prescale=r.prescale,
                    postscale=r.postscale) for r in group]
            n = ex.world_size
            pre = group[0].prescale
            post = group[0].postscale
            if group[0].average:
                post = post / n
            outs = ex.allreduce_fused([r.tensor for r in group],
                                      prescale=pre, postscale=post,
                                      wire=group[0].wire)
            return outs
        if op == BROADCAST:
            if group[0].sharded:
                return [ex.broadcast_sharded(r.tensor, r.root_rank)
                        for r in group]
            return ex.broadcast_fused([r.tensor for r in group],
                                      group[0].root_rank)
        if op == ALLGATHER:
            outs: List = [None] * len(group)
            fused_idx = [i for i, r in enumerate(group)
                         if r.per_rank is None and not r.sharded]
            if fused_idx:
                fused_out = ex.allgather_fused(
                    [group[i].tensor for i in fused_idx])
                for i, o in zip(fused_idx, fused_out):
                    outs[i] = o
            for i, r in enumerate(group):
                if r.per_rank is not None:
                    outs[i] = ex.allgather_ragged(r.per_rank)
                elif r.sharded:
                    outs[i] = ex.allgather_ragged(list(r.tensor))
            return outs
        raise ValueError(f"unknown op {op}")


def _op_name(op: int) -> str:
    return {ALLREDUCE: "allreduce", ALLGATHER: "allgather",
            BROADCAST: "broadcast"}[op]


def _missing_ranks_of(display_line: str) -> str:
    """Best-effort extraction of the missing-rank list from a
    coordinator stall display line ("name [missing ranks: 1, 3]") for
    the gauge label. The structured source is the coordinator's own
    metrics (control_plane.check_stalls); this is the worker-side echo,
    parsed from OUR controller's stable wording — worst case the label
    degrades to 'unknown', never to a wrong rank."""
    marker = "missing ranks:"
    i = display_line.find(marker)
    if i < 0:
        return "unknown"
    tail = display_line[i + len(marker):]
    ranks = []
    for tok in tail.replace("]", " ").split(","):
        tok = tok.strip()
        if tok.isdigit():
            ranks.append(tok)
        elif ranks:
            break
    return ",".join(ranks) if ranks else "unknown"


def _xla_activity(op: int) -> str:
    # Timeline activity names; the reference's are NCCL_ALLREDUCE /
    # MPI_ALLREDUCE etc. (operations.h:29-50).
    return {ALLREDUCE: "XLA_ALLREDUCE", ALLGATHER: "XLA_ALLGATHER",
            BROADCAST: "XLA_BROADCAST"}[op]


def _as_error(e: BaseException) -> BaseException:
    if isinstance(e, (ValueError, TypeError, HorovodInternalError)):
        return e
    from .control_plane import CoordinatorUnreachableError
    if isinstance(e, CoordinatorUnreachableError):
        # Typed for the elastic plane: a dead rank-0 process is a
        # recoverable worker loss (the driver re-rendezvouses), not an
        # anonymous internal error.
        from ..elastic.failure import WorkerFailure
        return WorkerFailure(rank=0, kind="coordinator_unreachable",
                             detail=str(e))
    return HorovodInternalError(str(e))


_engine: Optional[CollectiveEngine] = None
_engine_lock = threading.Lock()


def _flush_hint() -> None:
    """Forward a Handle.wait flush hint to the live engine (no-op when no
    engine is up — e.g. a handle fulfilled synchronously)."""
    eng = _engine
    if eng is not None and not eng._shutdown:
        try:
            eng.flush_hint()
        except Exception:  # pragma: no cover - teardown race
            pass


def engine() -> CollectiveEngine:
    global _engine
    with _engine_lock:
        if _engine is None:
            _engine = CollectiveEngine()
            atexit.register(_shutdown_atexit)
        return _engine


def _shutdown_atexit():
    global _engine
    if _engine is not None:
        _engine.shutdown()
        _engine = None


def reset_engine():
    """Test hook: drop the engine and the default executor (and with it the
    jitted-program cache keyed on the old mesh)."""
    from .. import executor as _exec
    global _engine
    with _engine_lock:
        if _engine is not None:
            _engine.shutdown()
        _engine = None
    _exec.reset_default_executor()


# ---------------------------------------------------------------------------
# Public eager API — mirrors horovod/torch/mpi_ops.py + tensorflow/mpi_ops.py
# ---------------------------------------------------------------------------

def _prep(tensor):
    """Accept numpy / python / jax inputs; detect per-rank leading-axis
    sharding.

    The per-rank convention is: a jax.Array whose *leading* axis is sharded
    over the mesh axis ('dp') and whose other axes are unsharded represents
    one tensor per virtual rank. Any other non-replicated layout is
    ambiguous for eager Horovod semantics and is rejected with guidance
    (rather than silently reinterpreted).
    """
    if isinstance(tensor, jax.Array):
        sh = tensor.sharding
        if sh.is_fully_replicated or len(sh.device_set) <= 1:
            return tensor, False
        spec = getattr(sh, "spec", None)
        if spec is not None:
            leading = spec[0] if len(spec) > 0 else None
            rest = [s for s in spec[1:] if s is not None]
            if leading in ("dp", ("dp",)) and not rest:
                return tensor, True
        raise ValueError(
            "Eager collectives accept replicated arrays (every rank "
            "contributes a copy) or arrays sharded over the mesh 'dp' axis "
            f"on the LEADING dimension only (per-rank values); got sharding "
            f"{sh}. For other layouts use the in-jit collectives "
            "(horovod_tpu.allreduce_gradients inside shard_map) instead.")
    src_dtype = getattr(tensor, "dtype", None)
    arr = jnp.asarray(tensor)
    if (src_dtype is not None
            and np.dtype(src_dtype).itemsize > arr.dtype.itemsize):
        # jnp.asarray silently narrowed a 64-bit input (jax_enable_x64 is
        # off) — refuse rather than corrupt values; the reference reduces
        # int64/float64 natively over MPI (mpi_message.h:26-37).
        raise ValueError(
            f"collective on {src_dtype} requires 64-bit JAX mode; enable "
            "it with jax.config.update('jax_enable_x64', True) before "
            "hvd.init(), or cast to a 32-bit dtype first")
    return arr, False


def _wire_for(tensor, sharded: bool, compression) -> Optional[str]:
    """Wire-format spec a blockwise compression selects for this request,
    or None (cast compressors transform the tensor before enqueue; the
    wire IS the tensor dtype then). Sharded per-rank arrays keep the
    full-precision path — their reduce is per-request, not fused."""
    spec = getattr(compression, "wire_spec", None)
    if spec is None or sharded:
        return None
    if not jnp.issubdtype(tensor.dtype, jnp.floating):
        return None
    return _quant.parse(spec).encoded()


def allreduce_async(tensor, average: bool = True, name: Optional[str] = None,
                    prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0,
                    compression=None) -> Handle:
    """Asynchronous allreduce; returns a :class:`Handle`.

    Parity: ``hvd.allreduce_async`` (torch/mpi_ops.py:110-180). ``average``
    divides by ``size()`` after summation, as the torch binding does in its
    completion callback (torch/mpi_ops_v2.cc:62-69).

    ``compression`` here only selects a blockwise WIRE format
    (``Compression.int8_blockwise`` / ``fp8_blockwise``): the tensor is
    submitted at its logical dtype and the quantize → reduce-scatter →
    requantize → allgather pipeline runs inside the fused XLA program.
    Cast compressors transform the tensor before enqueue (see
    :func:`allreduce`) and are ignored here.
    """
    _topo._get()
    eng = engine()
    t, sharded = _prep(tensor)
    nm = name or eng._next_name("allreduce")
    h = eng.make_handle(nm)
    req = _Request(nm, ALLREDUCE, t, h, average=average,
                   prescale=prescale_factor, postscale=postscale_factor,
                   sharded=sharded, wire=_wire_for(t, sharded, compression))
    return eng.enqueue(req)


def allreduce(tensor, average: bool = True, name: Optional[str] = None,
              compression=None, prescale_factor: float = 1.0,
              postscale_factor: float = 1.0):
    """Synchronous allreduce (sum / average over all virtual ranks).

    ``compression`` mirrors ``hvd.Compression`` usage in
    tensorflow/__init__.py:46-92: a cast compressor transforms the tensor
    before the collective and restores it after; a blockwise compressor
    (``Compression.int8_blockwise`` / ``fp8_blockwise``) instead selects
    the quantized wire format executed inside the fused program.
    """
    if compression is not None:
        t, ctx = compression.compress(jnp.asarray(tensor))
        out = allreduce_async(t, average=average, name=name,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor,
                              compression=compression).wait()
        return compression.decompress(out, ctx)
    return allreduce_async(tensor, average=average, name=name,
                           prescale_factor=prescale_factor,
                           postscale_factor=postscale_factor).wait()


def grouped_allreduce(tensors: Sequence, average: bool = True,
                      name: Optional[str] = None) -> List:
    """Allreduce a list of tensors as one fused submission."""
    with engine().burst():
        handles = [allreduce_async(t, average=average,
                                   name=(f"{name}.{i}" if name else None))
                   for i, t in enumerate(tensors)]
    return [h.wait() for h in handles]


def allgather_async(tensor, name: Optional[str] = None) -> Handle:
    """Asynchronous allgather along dim 0 (torch/mpi_ops.py:236-290).

    Accepts a replicated tensor (every rank contributes a copy), a jax.Array
    sharded over 'dp' (per-rank rows), or a list of per-rank tensors with
    varying first dims (the MPI_Allgatherv case, operations.cc:843-1113).
    """
    _topo._get()
    eng = engine()
    if isinstance(tensor, (list, tuple)):
        if eng._is_multiprocess():
            raise ValueError(
                "per-virtual-rank tensor lists are a single-process "
                "convenience; in multi-process mode pass this process's "
                "tensor (first dims may differ across processes — the "
                "MPI_Allgatherv case, operations.cc:843-1113)")
        per_rank = [jnp.asarray(t) for t in tensor]
        nm = name or eng._next_name("allgather")
        h = eng.make_handle(nm)
        req = _Request(nm, ALLGATHER, None, h, per_rank=per_rank)
        return eng.enqueue(req)
    t, sharded = _prep(tensor)
    nm = name or eng._next_name("allgather")
    h = eng.make_handle(nm)
    req = _Request(nm, ALLGATHER, t, h, sharded=sharded)
    return eng.enqueue(req)


def allgather(tensor, name: Optional[str] = None):
    return allgather_async(tensor, name=name).wait()


def broadcast_async(tensor, root_rank: int, name: Optional[str] = None
                    ) -> Handle:
    """Asynchronous broadcast from ``root_rank`` (torch/mpi_ops.py:318-392)."""
    topo = _topo._get()
    if not (0 <= root_rank < topo.size):
        # ConstructMPIResponse rejects invalid root ranks
        # (operations.cc:472-478) instead of silently deadlocking.
        raise ValueError(
            f"Invalid root_rank {root_rank}: root rank must be in "
            f"[0, {topo.size})")
    eng = engine()
    t, sharded = _prep(tensor)
    nm = name or eng._next_name("broadcast")
    h = eng.make_handle(nm)
    req = _Request(nm, BROADCAST, t, h, root_rank=root_rank, sharded=sharded)
    return eng.enqueue(req)


def broadcast(tensor, root_rank: int, name: Optional[str] = None):
    return broadcast_async(tensor, root_rank, name=name).wait()


def poll(handle: Handle) -> bool:
    """True iff the op behind ``handle`` finished (torch/mpi_ops.py:406-417)."""
    return handle.poll()


def synchronize(handle: Handle, timeout: Optional[float] = None):
    """Wait for ``handle`` and return its output (torch/mpi_ops.py:419-438)."""
    return handle.wait(timeout)
