"""XLA collective executor — the TPU-native data plane.

This module is the equivalent of the *execution half* of the reference's
``PerformOperation`` (horovod/common/operations.cc:768-1621): where the
reference memcpys tensors into a fusion buffer and calls
``MPI_Allreduce`` / ``ncclAllReduce`` / ``MPI_Allgatherv`` / ``MPI_Bcast``,
we build (and cache) jitted ``shard_map`` programs over the device mesh that
do the same thing with XLA collectives:

  ==========================================  =================================
  Reference (MPI/NCCL)                        TPU-native (XLA over ICI)
  ==========================================  =================================
  MPI_Allreduce / ncclAllReduce               ``jax.lax.psum``
  hierarchical ReduceScatter+MPI+AllGather    ``psum_scatter`` over 'ici' +
  (operations.cc:1284-1436)                   ``psum`` over 'dcn' +
                                              ``all_gather`` over 'ici'
  MPI_Allgatherv (variable first dim)         pad + ``all_gather`` + trim
  (operations.cc:843-1113)                    (static shapes for XLA)
  MPI_Bcast (operations.cc:1592-1612)         masked ``psum`` from root shard
  fusion buffer memcpy in/out                 flatten + concat / split inside
  (operations.cc:1221-1243, 1491-1586)        the same jitted program (XLA
                                              fuses the copies away)
  ==========================================  =================================

Fused programs are compiled once per (shapes, dtypes, op) signature and
cached — the analogue of NCCL communicator/stream caching
(operations.cc:1117-1191) is jit's executable cache.

Numerics: fp16/bf16 sums are accumulated in fp32 inside the program (the
reference instead registers a custom fp16 MPI op with AVX intrinsics,
horovod/common/half.cc:42-90 — on TPU the MXU/VPU natively handles bf16, and
fp32 accumulation is the idiomatic way to keep small-dtype reductions exact).
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import time

from . import quantization as _quant
from . import topology as _topo
from .observability import numerics as _numerics
from .observability import registry as _obs


class _ExecMetrics:
    """Registry handles for executor counters — module-global so every
    executor instance feeds the same process-wide totals: the snapshot
    survives ``reset_default_executor()`` (the per-instance ints below
    remain as deprecation aliases for existing steady-state tests)."""

    _instance = None

    def __init__(self):
        r = _obs.registry()
        self.cache_hits = r.counter(
            "hvdtpu_executor_cache_hits_total",
            "Fused-program cache hits").labels()
        self.cache_misses = r.counter(
            "hvdtpu_executor_cache_misses_total",
            "Fused-program cache misses (program builds)").labels()
        self.device_puts = r.counter(
            "hvdtpu_executor_device_puts_total",
            "Host-to-device transfers for collective inputs").labels()
        self.compile_seconds = r.histogram(
            "hvdtpu_executor_compile_seconds",
            "Wall seconds building + jitting one collective program",
            buckets=_obs.LATENCY_BUCKETS).labels()

    @classmethod
    def get(cls) -> "_ExecMetrics":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

# Ops wire-enum kept numerically aligned with the native runtime
# (runtime/src/message.h) and the reference's MPIRequest::RequestType
# (horovod/common/mpi_message.h:52-58).
ALLREDUCE = 0
ALLGATHER = 1
BROADCAST = 2


# Fusion-buffer size quantization for the host-assembled multi-process
# path (min 512 elements; a power of two is always a multiple of the
# reference fusion buffer's 64-byte atomic unit,
# FUSION_BUFFER_ATOMIC_UNIT, operations.h:52-54).
def _fusion_padded_size(n: int) -> int:
    """Padded size with at most 3 significant mantissa bits (1, 1.125,
    ... 1.875 x 2^k; minimum 512). Two forces pull on this quantization:

    - COMPILE STABILITY: linear (fine-quantum) padding let the
      coordinator's timing-dependent group compositions produce a fresh
      padded size almost every step, and padded size keys BOTH the
      fused reduce program and the per-tensor unpack slices — a
      120-tensor MP group measured 11 s/step of per-composition
      recompiles. Few distinct sizes per octave => caches converge.
    - TRAFFIC: the padded size is what the shm plane moves and the
      reduce program chews; pure power-of-two padding (round-5 first
      fix) costs up to 2x on mid-octave buffers and measurably dragged
      the np=8 weak-scaling proxy (0.95 -> 0.80 capacity-adjusted).

    Three mantissa bits bounds overhead at 12.5% with 8 sizes per
    octave; every value stays a multiple of 64 bytes at any dtype width
    (the reference fusion buffer's atomic unit)."""
    if n <= 512:
        return 512
    k = n.bit_length() - 1          # floor(log2(n))
    step = 1 << max(k - 3, 0)       # 1/8 of the leading power of two
    return ((n + step - 1) // step) * step


def _accum_dtype(dtype) -> Optional[np.dtype]:
    """Accumulation dtype for exact small-float / bool reductions."""
    d = np.dtype(dtype)
    if (d == np.dtype(np.float16) or str(d) == "bfloat16"
            or str(d).startswith("float8")):
        return np.dtype(np.float32)
    if d == np.dtype(bool):
        return np.dtype(np.int32)
    return None


# Cached unpack programs keyed by (tensor shape/dtype, buffer
# shape/dtype) with the OFFSET as a traced scalar — the same
# compile-stability trick as _pack_device. An eager dynamic_slice bakes
# the Python-int offset in as a constant, so every timing-dependent MP
# group composition recompiled one slice program per tensor per step
# (measured: 13 s of a 15 s step on a 120-tensor group; the round-5
# autotune sweep's 10x "threshold pocket" was exactly this cost).
# Bounded LRU: shape churn (ragged gathers, changing batch shapes) must
# not grow the program table without limit over a long job — each entry
# pins a compiled XLA executable.
_UNPACK_CACHE: OrderedDict = OrderedDict()
_UNPACK_CACHE_MAX = 512

# The traced offset rides the wire as int32 (cheap, and a traced int64
# would be downcast anyway without jax_enable_x64); a fused buffer big
# enough to overflow it cannot be sliced correctly.
_INT32_MAX = 2 ** 31 - 1


def _unpack(out, arrs, idxs, results, align: int = 1) -> None:
    """Device-side unpack of a fused buffer shared by every
    _run_fused_buffers branch: slice each tensor's span back out,
    reshape, restore its dtype. ``align`` mirrors the pack-side span
    alignment (quantized wire formats align each tensor to whole
    blocks)."""
    if int(out.size) > _INT32_MAX:
        raise ValueError(
            f"fused buffer has {int(out.size)} elements; unpack offsets "
            "are traced as int32 and would overflow. Lower the fusion "
            "threshold (HOROVOD_TPU_FUSION_THRESHOLD) below 2**31 "
            "elements per buffer.")
    off = 0
    for i in idxs:
        a = arrs[i]
        key = (tuple(a.shape), str(a.dtype), out.shape, str(out.dtype))
        prog = _UNPACK_CACHE.get(key)
        if prog is None:
            size, shape, dt = int(a.size), tuple(a.shape), a.dtype
            prog = jax.jit(
                lambda b, o, _s=size, _sh=shape, _dt=dt:
                jax.lax.dynamic_slice(b, (o,), (_s,))
                .reshape(_sh).astype(_dt))
            _UNPACK_CACHE[key] = prog
            while len(_UNPACK_CACHE) > _UNPACK_CACHE_MAX:
                _UNPACK_CACHE.popitem(last=False)
        else:
            _UNPACK_CACHE.move_to_end(key)
        results[i] = prog(out, np.int32(off))
        off += _quant.padded_size(int(a.size), align)


def _fused_reduce(vals, reduce_fn, prescale: float, postscale: float,
                  wire=None, axis: str = "dp", world: int = 1):
    """The fusion-buffer body shared by the single- and multi-process
    allreduce programs: group per-shard values by dtype, flatten + concat
    (the "fusion buffer", operations.cc:1221-1243), reduce each buffer
    with ``reduce_fn``, split back out. One collective per dtype mirrors
    one collective per fused response (operations.cc:2149-2265).

    With ``wire`` set (a quantization.WireSpec) floating groups run the
    dual block-quantized allreduce over ``axis`` instead of ``reduce_fn``:
    each tensor's flat span is padded to whole blocks (block boundaries
    never cross tensors, so the optimizer's per-leaf error-feedback
    residual matches the wire exactly), the buffer is padded to
    ``world * block_size``, and quantization.allreduce_blocks moves wire
    bytes — not fp32 bytes — through the collectives."""
    by_dtype = {}
    for i, v in enumerate(vals):
        by_dtype.setdefault(v.dtype, []).append((i, v))
    results = [None] * len(vals)
    for dt, items in by_dtype.items():
        if (wire is not None and jnp.issubdtype(jnp.dtype(dt), jnp.floating)
                and sum(int(v.size) for _, v in items) > 0):
            _fused_reduce_quantized(items, wire, axis, world, prescale,
                                    postscale, results)
            continue
        acc = _accum_dtype(dt)
        flat = [jnp.ravel(v).astype(acc or dt) for _, v in items]
        if prescale != 1.0:
            flat = [f * prescale for f in flat]
        buf = jnp.concatenate(flat) if len(flat) > 1 else flat[0]
        red = reduce_fn(buf)
        if postscale != 1.0:
            red = red * postscale
        off = 0
        for (i, v), f in zip(items, flat):
            n = f.size
            piece = jax.lax.dynamic_slice(red, (off,), (n,))
            results[i] = piece.reshape(v.shape).astype(dt)
            off += n
    return tuple(results)


def _fused_reduce_quantized(items, wire, axis: str, world: int,
                            prescale: float, postscale: float,
                            results) -> None:
    """Quantized-wire fusion-buffer body: per-tensor block padding +
    concat, dual-quantized allreduce over ``axis``, split back out."""
    bs = wire.block_size
    pieces = []
    spans = []
    off = 0
    for i, v in items:
        f = jnp.ravel(v).astype(jnp.float32)
        if prescale != 1.0:
            f = f * prescale
        n = int(f.size)
        m = _quant.padded_size(max(n, 1), bs)
        if m != n:
            f = jnp.concatenate([f, jnp.zeros((m - n,), jnp.float32)])
        pieces.append(f)
        spans.append((off, n))
        off += m
    extra = (-off) % (world * bs)
    if extra:
        pieces.append(jnp.zeros((extra,), jnp.float32))
    buf = jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]
    red = _quant.allreduce_blocks(buf, axis, wire, world)
    if postscale != 1.0:
        red = red * postscale
    for (i, v), (o, n) in zip(items, spans):
        piece = jax.lax.dynamic_slice(red, (o,), (n,))
        results[i] = piece.reshape(v.shape).astype(v.dtype)


def _hier_reduce(buf, ici: int):
    """Hierarchical fused-buffer reduction (operations.cc:1284-1436 as
    XLA collectives): psum_scatter over 'ici' -> psum over 'dcn' on the
    scattered shard -> all_gather over 'ici'. The buffer pads so its
    length divides the ici size, as the reference rounds its fusion
    buffer to local_size x FUSION_BUFFER_ATOMIC_UNIT
    (operations.cc:742-764). Shared by the single- and multi-process
    allreduce programs."""
    n = buf.size
    pad = (-n) % ici
    if pad:
        buf = jnp.concatenate([buf, jnp.zeros((pad,), buf.dtype)])
    piece = jax.lax.psum_scatter(buf, "ici", tiled=True)
    piece = jax.lax.psum(piece, "dcn")
    out = jax.lax.all_gather(piece, "ici", tiled=True)
    return out[:n] if pad else out


def _hier_gather(x, tiled: bool):
    """Two-stage hierarchical allgather (operations.cc:929-1032 — node
    shared-memory window + cross-node MPI_Allgatherv — as XLA
    collectives): gather within the slice over 'ici', then across slices
    over 'dcn'. The hierarchical mesh is the flat device list reshaped to
    (dcn, ici) (topology.py:112-117), so the dcn-major/ici-minor result
    ordering is bit-identical to a flat all_gather over 'dp'."""
    g = jax.lax.all_gather(x, "ici", axis=0, tiled=tiled)
    return jax.lax.all_gather(g, "dcn", axis=0, tiled=True)


def _trim_concat(gathered, per_rank_dims):
    """Trim a padded [n, max_dim, ...] gather back to ragged segments and
    concatenate — the MPI_Allgatherv displacement math
    (operations.cc:862-897)."""
    segs = [jax.lax.slice_in_dim(gathered[i], 0, int(d), axis=0)
            for i, d in enumerate(per_rank_dims)]
    return jnp.concatenate(segs, axis=0)


class CollectiveExecutor:
    """Builds and caches jitted collective programs for one mesh."""

    def __init__(self, mesh: Optional[Mesh] = None,
                 hier_mesh: Optional[Mesh] = None,
                 hierarchical_allreduce: bool = False,
                 hierarchical_allgather: bool = False):
        self._mesh = mesh
        self._hier_mesh = hier_mesh
        self.hierarchical_allreduce = hierarchical_allreduce
        self.hierarchical_allgather = hierarchical_allgather
        self._cache = {}
        self._shm_checked = False
        self._shm_transport = None
        self._device_pack_flag: Optional[bool] = None
        # Observability counters: fused-program cache behaviour and input
        # transfers (tests guard that replicated inputs neither recompile
        # nor re-transfer — the hot-loop steady state). DEPRECATION
        # ALIASES: per-instance views of the registry counters
        # (hvdtpu_executor_*_total), which are the canonical series and
        # survive reset_default_executor().
        self.cache_hits = 0
        self.cache_misses = 0
        self.device_put_count = 0
        self._metrics = _ExecMetrics.get()

    @property
    def mesh(self) -> Mesh:
        return self._mesh if self._mesh is not None else _topo.mesh()

    @property
    def hier_mesh(self) -> Mesh:
        if self._hier_mesh is not None:
            return self._hier_mesh
        return _topo.hierarchical_mesh()

    @property
    def world_size(self) -> int:
        return self.mesh.devices.size

    # ---------------------------------------------------------------- helpers

    def _replicated(self, x):
        """Device-put a host / single-device array replicated on the mesh."""
        return self._put_replicated([x], self.mesh)[0]

    def _put_replicated(self, tensors, mesh: Mesh) -> List[jax.Array]:
        """Replicate inputs on ``mesh``, skipping the transfer for arrays
        that already carry the replicated sharding — in a steady-state
        training loop the outputs of step N are the inputs of step N+1
        and re-running device_put on them is a per-tensor dispatch for
        nothing. Everything that DOES transfer rides ONE ``device_put``
        call: on a latency-heavy host↔device link each put is its own
        round-trip dispatch, so a fused group of host gradients (the
        torch/keras shim shape) pays the floor once, not once per
        tensor."""
        sh = NamedSharding(mesh, P())
        out: List = [None] * len(tensors)
        moving = []
        for i, t in enumerate(tensors):
            if isinstance(t, jax.Array):
                try:
                    if t.sharding.is_equivalent_to(sh, t.ndim):
                        out[i] = t
                        continue
                except Exception:
                    pass
            moving.append(i)
        if moving:
            self.device_put_count += len(moving)
            self._metrics.device_puts.inc(len(moving))
            put = jax.device_put([tensors[i] for i in moving], sh)
            for i, a in zip(moving, put):
                out[i] = a
        return out

    def _program(self, key, builder):
        prog = self._cache.get(key)
        if prog is None:
            self.cache_misses += 1
            self._metrics.cache_misses.inc()
            built = builder()
            metrics, cache = self._metrics, self._cache

            def timed_first_call(*args, **kwargs):
                # jax.jit is lazy: trace + lower + compile all happen on
                # the first invocation, so THAT is what the compile
                # histogram must time (building the closure above is
                # microseconds). After the first call the raw program
                # replaces this shim in the cache.
                t0 = time.perf_counter()
                out = built(*args, **kwargs)
                metrics.compile_seconds.observe(time.perf_counter() - t0)
                cache[key] = built
                return out

            cache[key] = timed_first_call
            return timed_first_call
        self.cache_hits += 1
        self._metrics.cache_hits.inc()
        return prog

    # -------------------------------------------------------------- allreduce

    def allreduce_fused(self, tensors: Sequence[jax.Array],
                        prescale: float = 1.0,
                        postscale: float = 1.0,
                        wire=None) -> List[jax.Array]:
        """Sum-allreduce a fused group of replicated tensors.

        Semantics: every virtual rank (device) contributes its copy, so a
        replicated input comes back multiplied by ``size`` — identical to
        every Horovod rank passing the same tensor. ``prescale``/``postscale``
        implement compression/averaging scaling hooks.

        The whole group runs as ONE jitted program: flatten → concat (the
        "fusion buffer", operations.cc:1221-1243) → psum → split.

        ``wire`` (a quantization spec, e.g. "int8x256") switches floating
        tensors to the dual block-quantized allreduce — quantize →
        reduce-scatter in the wire domain → fp32 dequant-accumulate →
        requantize → allgather — inside the same fused program. The
        quantized path always runs on the flat 'dp' mesh: its all_to_all
        reduce-scatter is already the bandwidth-optimal single-phase
        exchange, so the two-level hierarchy buys nothing on top.
        """
        wire = _quant.parse(wire)
        hier = self.hierarchical_allreduce and wire is None
        mesh = self.hier_mesh if hier else self.mesh
        ici = int(mesh.shape["ici"]) if hier else 1
        world = int(mesh.devices.size)
        shapes = tuple(t.shape for t in tensors)
        dtypes = tuple(str(np.dtype(t.dtype) if t.dtype != jnp.bfloat16
                           else "bfloat16") for t in tensors)
        key = ("ar", shapes, dtypes, float(prescale), float(postscale),
               hier, wire.encoded() if wire else None, id(mesh))

        def reduce_buf(buf):
            if not hier:
                return jax.lax.psum(buf, "dp")
            return _hier_reduce(buf, ici)

        def build():
            def fused(*xs):
                def shard_fn(*ys):
                    return _fused_reduce(ys, reduce_buf, prescale,
                                         postscale, wire=wire,
                                         axis="dp", world=world)

                return jax.shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=tuple(P() for _ in xs),
                    out_specs=tuple(P() for _ in xs),
                    check_vma=False)(*xs)

            return jax.jit(fused)

        prog = self._program(key, build)
        outs = prog(*self._put_replicated(tensors, mesh))
        return list(outs)

    # ------------------------------------------------------------- radcast &c

    def broadcast_fused(self, tensors: Sequence[jax.Array],
                        root_rank: int) -> List[jax.Array]:
        """Broadcast each tensor from virtual rank ``root_rank``.

        Implemented as a masked psum from the root shard — with replicated
        eager inputs every rank already holds the root's value, but the
        program still moves the data through the collective so the semantics
        (and the timeline/fusion machinery around it) match
        operations.cc:1592-1612.
        """
        mesh = self.mesh
        shapes = tuple(t.shape for t in tensors)
        dtypes = tuple(str(t.dtype) for t in tensors)
        key = ("bc", shapes, dtypes, int(root_rank), id(mesh))

        def build():
            def fused(*xs):
                def shard_fn(*ys):
                    idx = jax.lax.axis_index("dp")
                    outs = []
                    for y in ys:
                        acc = _accum_dtype(y.dtype)
                        z = y.astype(acc) if acc is not None else y
                        masked = jnp.where(idx == root_rank, z,
                                           jnp.zeros_like(z))
                        out = jax.lax.psum(masked, "dp")
                        outs.append(out.astype(y.dtype))
                    return tuple(outs)
                return jax.shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=tuple(P() for _ in xs),
                    out_specs=tuple(P() for _ in xs),
                    check_vma=False)(*xs)
            return jax.jit(fused)

        prog = self._program(key, build)
        ins = [self._replicated(t) for t in tensors]
        return list(prog(*ins))

    def allgather_fused(self, tensors: Sequence[jax.Array]) -> List[jax.Array]:
        """Allgather along dim 0 from every virtual rank.

        Replicated input ⇒ output is ``size`` stacked copies along dim 0,
        exactly what the reference returns when all ranks pass the same
        tensor (operations.cc:843-1113). Per-rank distinct inputs use
        :meth:`allgather_sharded`. With ``hierarchical_allgather`` set
        (HOROVOD_TPU_HIERARCHICAL_ALLGATHER), the gather runs in two
        stages over ('ici', 'dcn') — the reference's shared-memory-window
        + cross-node path (operations.cc:929-1032).
        """
        hier = self.hierarchical_allgather
        mesh = self.hier_mesh if hier else self.mesh
        shapes = tuple(t.shape for t in tensors)
        dtypes = tuple(str(t.dtype) for t in tensors)
        key = ("ag", shapes, dtypes, hier, id(mesh))

        def build():
            def fused(*xs):
                def shard_fn(*ys):
                    if hier:
                        return tuple(_hier_gather(y, tiled=True)
                                     for y in ys)
                    return tuple(
                        jax.lax.all_gather(y, "dp", axis=0, tiled=True)
                        for y in ys)
                return jax.shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=tuple(P() for _ in xs),
                    out_specs=tuple(P() for _ in xs),
                    check_vma=False)(*xs)
            return jax.jit(fused)

        prog = self._program(key, build)
        return list(prog(*self._put_replicated(tensors, mesh)))

    # ---------------------------------------------- per-rank (sharded) inputs

    def allreduce_sharded(self, x: jax.Array, average: bool = False,
                          prescale: float = 1.0, postscale: float = 1.0):
        """Allreduce where ``x[i]`` is virtual rank i's tensor (leading axis
        sharded over 'dp'). Returns the reduced tensor of shape x.shape[1:]."""
        mesh = self.mesh
        n = self.world_size
        if x.shape[0] != n:
            raise ValueError(
                f"sharded allreduce expects leading axis == size ({n}), "
                f"got shape {x.shape}")
        key = ("ars", x.shape, str(x.dtype), bool(average), float(prescale),
               float(postscale), id(mesh))

        def build():
            def fn(y):
                def shard_fn(z):
                    acc = _accum_dtype(z.dtype)
                    w = z[0].astype(acc) if acc is not None else z[0]
                    if prescale != 1.0:
                        w = w * prescale
                    out = jax.lax.psum(w, "dp")
                    if postscale != 1.0:
                        out = out * postscale
                    if average:
                        out = out / n
                    return out.astype(z.dtype)
                return jax.shard_map(
                    shard_fn, mesh=mesh, in_specs=P("dp"),
                    out_specs=P(), check_vma=False)(y)
            return jax.jit(fn)

        prog = self._program(key, build)
        xin = jax.device_put(x, NamedSharding(mesh, P("dp")))
        return prog(xin)

    def broadcast_sharded(self, x: jax.Array, root_rank: int):
        """Broadcast where ``x[i]`` is rank i's value; returns root's slice."""
        mesh = self.mesh
        n = self.world_size
        key = ("bcs", x.shape, str(x.dtype), int(root_rank), id(mesh))

        def build():
            def fn(y):
                def shard_fn(z):
                    idx = jax.lax.axis_index("dp")
                    v = z[0]
                    acc = _accum_dtype(v.dtype)
                    w = v.astype(acc) if acc is not None else v
                    masked = jnp.where(idx == root_rank, w, jnp.zeros_like(w))
                    return jax.lax.psum(masked, "dp").astype(v.dtype)
                return jax.shard_map(
                    shard_fn, mesh=mesh, in_specs=P("dp"),
                    out_specs=P(), check_vma=False)(y)
            return jax.jit(fn)

        prog = self._program(key, build)
        xin = jax.device_put(x, NamedSharding(mesh, P("dp")))
        return prog(xin)

    def allgather_ragged(self, per_rank: Sequence[jax.Array]) -> jax.Array:
        """Allgather of per-rank tensors with *different first dims* —
        the reference's MPI_Allgatherv path (operations.cc:862-897,
        1037-1094). XLA needs static shapes, so: pad every rank's tensor to
        the max first dim, all_gather, then trim each segment and concat.
        """
        n = self.world_size
        if len(per_rank) != n:
            raise ValueError(f"need one tensor per rank ({n}), got "
                             f"{len(per_rank)}")
        first_dims = [int(t.shape[0]) for t in per_rank]
        rest = per_rank[0].shape[1:]
        dtype = per_rank[0].dtype
        for t in per_rank:
            if t.shape[1:] != rest or t.dtype != dtype:
                raise ValueError(
                    "allgather tensors must agree on dtype and all dims "
                    "except the first (mpi_message validation, "
                    "operations.cc:398-446)")
        m = max(first_dims)
        hier = self.hierarchical_allgather
        mesh = self.hier_mesh if hier else self.mesh
        axes = ("dcn", "ici") if hier else ("dp",)
        key = ("agr", (m,) + tuple(rest), str(dtype), tuple(first_dims),
               hier, id(mesh))

        def build():
            def fn(stacked):
                def shard_fn(z):
                    if hier:
                        return _hier_gather(z[0], tiled=False)
                    return jax.lax.all_gather(z[0], "dp", axis=0, tiled=False)
                return jax.shard_map(
                    shard_fn, mesh=mesh, in_specs=P(axes),
                    out_specs=P(), check_vma=False)(stacked)
            return jax.jit(fn)

        padded = np.zeros((n, m) + tuple(rest), dtype=np.dtype(
            dtype if dtype != jnp.bfloat16 else "bfloat16"))
        for i, t in enumerate(per_rank):
            padded[i, : first_dims[i]] = np.asarray(t)
        prog = self._program(key, build)
        gathered = prog(jax.device_put(
            padded, NamedSharding(mesh, P(axes))))
        return _trim_concat(gathered, first_dims)


    # ------------------------------------------- multi-process (multi-host)
    #
    # In multi-process mode the mesh spans devices this process cannot
    # address, and each process holds *different* eager values, so the
    # replicated-input programs above would lie to XLA about consistency.
    # Instead every tensor becomes a global [size, ...] array whose leading
    # axis is sharded over 'dp' — each device holds its process's value —
    # built from process-local data only. The group sequence executed here
    # is agreed through the TCP coordinator (ops/control_plane.py), so all
    # processes enter the same program in the same order (the SPMD
    # requirement the reference meets with its MPI_Bcast'd response list,
    # operations.cc:2282-2287).

    def _shm(self):
        """Shared-memory data plane for same-host jobs (ops/shm_transport
        — the reference's MPI shared-memory CPU path), or None. Gated on
        the launcher's placement verdict (HOROVOD_TPU_ALL_LOCAL) or the
        explicit HOROVOD_TPU_SHM knob; every process of a job sees the
        same launcher env, so the fleet gates identically."""
        if not self._shm_checked:
            self._shm_checked = True
            from .utils import env as _env
            # Everything below the (launcher-uniform) env gate and the
            # (uniform) process count is per-process fallible, so the
            # fleet-wide agreement must run UNCONDITIONALLY once past
            # those two gates — a rank whose topology probe or segment
            # creation failed must still vote, or the fleet's XLA
            # program order diverges at the handshake itself.
            if _env.shm_data_plane() and jax.process_count() > 1:
                transport = None
                try:
                    # The shm reduction scales the process-sum by ONE
                    # local device count and maps virtual root ranks by
                    # division, both valid only for homogeneous
                    # placements (equal devices per process) — the same
                    # init-time invariant the reference asserts
                    # (operations.cc:1772-1790).
                    homogeneous = (
                        jax.local_device_count() * jax.process_count()
                        == jax.device_count())
                    homogeneous = (homogeneous
                                   and _topo._get().is_homogeneous)
                    if homogeneous:
                        from .ops import shm_transport
                        transport = shm_transport.get(
                            jax.process_index(), jax.process_count())
                except Exception as e:
                    transport = None
                    from .utils.logging import get_logger
                    get_logger("executor").warning(
                        "shared-memory data plane disabled: %s "
                        "(falling back to XLA collectives)", e)
                # Readiness handshake: the launcher env gates all ranks
                # identically, but the plane can still fail on a SUBSET
                # (per-process segment-creation error) — and a split
                # fleet deadlocks: shm-side ranks die on the 120 s spin
                # while XLA-side ranks hang in collective rendezvous.
                # Agree once through the XLA data plane (always
                # available, same program on every process at this point
                # in the agreed group order): the plane is used only if
                # EVERY process reports it up.
                if self._agree_all(transport is not None):
                    self._shm_transport = transport
                elif transport is not None:
                    # Release the locally-created segments — the job
                    # keeps running on XLA and must not pin bucket-sized
                    # /dev/shm allocations for its lifetime.
                    from .ops import shm_transport
                    shm_transport.reset()
                    from .utils.logging import get_logger
                    get_logger("executor").warning(
                        "shared-memory data plane up locally but not on "
                        "every process; whole fleet falls back to XLA "
                        "collectives")
        return self._shm_transport

    def _agree_all(self, ok: bool) -> bool:
        """True iff every process votes ``ok`` — one tiny psum over the
        'dp' mesh (each device votes its process's verdict)."""
        mesh = self.mesh
        arr = self._mp_stacked(
            np.asarray([1.0 if ok else 0.0], np.float32), mesh=mesh)
        prog = self._program(
            ("shm_agree", id(mesh)),
            lambda: jax.jit(jax.shard_map(
                lambda y: jax.lax.psum(y[0], "dp"), mesh=mesh,
                in_specs=P("dp"), out_specs=P(), check_vma=False)))
        return float(np.asarray(prog(arr))[0]) >= self.world_size

    def _mp_stacked(self, x, mesh: Optional[Mesh] = None,
                    axes=("dp",)) -> jax.Array:
        """Global [size, ...] array with the leading axis sharded over
        ``axes``; every local device holds this process's value."""
        mesh = mesh if mesh is not None else self.mesh
        local_devices = [d for d in mesh.devices.flat
                         if d.process_index == jax.process_index()]
        arr = np.asarray(x)
        local = np.broadcast_to(arr, (len(local_devices),) + arr.shape)
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, P(axes)), local)

    def _device_pack(self) -> bool:
        """Device-resident MP fusion buffers: on by
        default on accelerator backends, off on CPU (where host memory
        IS device memory and numpy packing is cheaper than a
        dynamic-update-slice program cascade).
        HOROVOD_TPU_DEVICE_PACK=1/0 forces; resolved once."""
        if self._device_pack_flag is None:
            from .utils import env as _env
            forced = _env.device_pack()
            self._device_pack_flag = (
                forced if forced is not None
                else jax.default_backend() != "cpu")
        return self._device_pack_flag

    def _pack_device(self, ts: Sequence[jax.Array], padded: int,
                     buf_dt, align: int = 1) -> jax.Array:
        """Build the size-quantized fusion buffer on device: one cached
        zero-init program per (padded, dtype) plus one cached
        dynamic-update-slice program per (tensor shape/dtype, padded) —
        offsets are traced scalars, so any group composition reuses the
        same executables (the compile-stability property the host pack
        was built for), while the payload never leaves the device."""
        dt_s = str(np.dtype(buf_dt))
        zero = self._program(
            ("pack_zero", padded, dt_s),
            lambda: jax.jit(lambda: jnp.zeros((padded,), buf_dt)))
        buf = zero()
        dev = next(iter(buf.devices()))
        off = 0
        for t in ts:
            if t.devices() != {dev}:
                # Inputs committed to another local device (or
                # replicated across several) would make the jitted
                # DUS raise 'incompatible devices'; a D2D put onto
                # the buffer's device keeps the cascade legal — the
                # host pack accepted any placement, so must this.
                t = jax.device_put(t, dev)
            key = ("pack_dus", tuple(t.shape), str(t.dtype), padded, dt_s)
            prog = self._program(key, lambda: jax.jit(
                lambda b, v, o: jax.lax.dynamic_update_slice(
                    b, v.ravel().astype(buf_dt), (o,)),
                donate_argnums=(0,)))
            buf = prog(buf, t, np.int32(off))
            off += _quant.padded_size(int(t.size), align)
        return buf

    def _mp_stacked_device(self, buf: jax.Array, mesh: Mesh,
                           axes) -> jax.Array:
        """Device-side _mp_stacked: assemble the global [ndev, n] array
        from per-local-device copies of the packed buffer (D2D, no host
        staging)."""
        local_devices = [d for d in mesh.devices.flat
                         if d.process_index == jax.process_index()]
        row = buf.reshape((1,) + buf.shape)
        shards = [jax.device_put(row, d) for d in local_devices]
        global_shape = (len(list(mesh.devices.flat)),) + buf.shape
        return jax.make_array_from_single_device_arrays(
            global_shape, NamedSharding(mesh, P(axes)), shards)

    def allreduce_fused_mp(self, tensors: Sequence[jax.Array],
                           prescale: float = 1.0,
                           postscale: float = 1.0,
                           wire=None) -> List[jax.Array]:
        """Fused sum-allreduce across processes: every virtual rank
        (device) contributes its process's copy.

        The fusion buffer is assembled HOST-SIDE (numpy concat into a
        size-quantized flat buffer — the reference's memcpy into the
        fusion buffer, operations.cc:1221-1243), so the compiled XLA
        program is keyed only by (padded size, dtype): the coordinator
        may legitimately cut one step's burst into different group
        compositions on different steps (announce chunking is timing-
        dependent), and per-composition programs would mean a fresh XLA
        compile per step instead of a cache hit. The eager MP path
        already stages through the host (_mp_stacked), so the concat
        adds no extra device transfer.

        With hierarchical mode on, the reduction pipelines over the
        ('dcn', 'ici') mesh — psum_scatter on ICI, psum across DCN on
        the scattered shard, all_gather back on ICI — the reference's
        2-level NCCL+MPI allreduce (operations.cc:1284-1436) as XLA
        collectives; otherwise one flat psum over 'dp'.
        """
        wire = _quant.parse(wire)
        # The quantized path runs on the flat mesh (see allreduce_fused)
        # and through XLA — the shm plane reduces host-side in full
        # precision and would silently skip the wire format.
        hier = self.hierarchical_allreduce and wire is None
        mesh = self.hier_mesh if hier else self.mesh
        axes = ("dcn", "ici") if hier else ("dp",)
        ici = int(mesh.shape["ici"]) if hier else 1
        world = int(mesh.devices.size)

        shm = None if (hier or wire is not None) else self._shm()
        if shm is not None:
            # Same-host fast path: reduce the host-staged fusion buffer
            # through /dev/shm instead of a socket ring. Every VIRTUAL
            # rank contributes its process's copy, so the process-sum is
            # scaled by the (homogeneous) local device count.
            local = max(1, self.world_size // jax.process_count())

            def host_op(buf):
                if prescale != 1.0:
                    buf = buf * prescale
                out = shm.allreduce(buf)
                scale = float(local) * postscale
                if scale != 1.0:
                    out = out * scale
                return out

            return self._run_fused_buffers(
                tensors, None, key_fn=None, mesh=mesh, axes=axes,
                host_op=host_op)

        def reduce_buf(buf):
            if not hier:
                return jax.lax.psum(buf, "dp")
            return _hier_reduce(buf, ici)

        def build(padded, buf_dt):
            quantize = (wire is not None and
                        jnp.issubdtype(jnp.dtype(buf_dt), jnp.floating))

            def fused(x):
                def shard_fn(y):
                    v = y[0]  # this device's block of [size, n]
                    if prescale != 1.0:
                        v = v * prescale
                    if quantize:
                        # The packed buffer is already size-quantized
                        # (multiples of 512 ⊇ whole 256-blocks for the
                        # default block size); pad the tail so every
                        # rank's shard is whole blocks. Unlike the SP
                        # path the host pack is back-to-back, so blocks
                        # may span tensor boundaries here — the error
                        # stays bounded by block absmax either way.
                        n = int(v.size)
                        m = _quant.padded_size(
                            max(n, 1), world * wire.block_size)
                        b = (jnp.concatenate(
                                [v.astype(jnp.float32),
                                 jnp.zeros((m - n,), jnp.float32)])
                             if m != n else v.astype(jnp.float32))
                        red = _quant.allreduce_blocks(
                            b, "dp", wire, world)[:n].astype(v.dtype)
                    else:
                        red = reduce_buf(v)
                    if postscale != 1.0:
                        red = red * postscale
                    return red

                return jax.shard_map(
                    shard_fn, mesh=mesh, in_specs=P(axes),
                    out_specs=P(), check_vma=False)(x)

            return jax.jit(fused)

        return self._run_fused_buffers(
            tensors, build,
            key_fn=lambda padded, dt: ("armp_buf", padded, dt,
                                       float(prescale), float(postscale),
                                       hier, wire.encoded() if wire
                                       else None, id(mesh)),
            mesh=mesh, axes=axes,
            align=wire.block_size if wire is not None else 1)

    def _run_fused_buffers(self, tensors, build, key_fn, mesh, axes,
                           host_op=None, align: int = 1):
        """Shared host-assembled fusion-buffer scaffolding for the MP
        collectives (the reference's memcpy into the fusion buffer,
        operations.cc:1221-1243): group by accumulation dtype (one
        collective per dtype, like one fused response per dtype,
        operations.cc:2149-2265), pack into a size-QUANTIZED flat buffer
        so the compiled program is keyed by padded size instead of group
        composition, run ``build(padded, dtype_str)``'s program, and
        unpack device-side (no D2H round trip of the payload).

        ``host_op(buf) -> np.ndarray`` replaces the XLA program with a
        host-side reduction over the packed buffer (the shared-memory
        data plane); pack and unpack stay in numpy — no device round
        trip at all.

        On accelerator backends with jax.Array inputs, the packing also
        happens ON DEVICE (``_pack_device``): the reference's GPU path
        keeps its fusion buffer device-side end to end
        (operations.cc:1221-1243 memcpyAsync into a device buffer, NCCL
        on device memory), and a host-staged pack on a real pod pays a
        full D2H+H2D of the gradient payload every step. The device
        pack builds the quantized buffer with one cached
        dynamic-update-slice program per (tensor shape, buffer size) —
        the offset is a traced scalar, so timing-dependent group
        compositions still hit the program cache (the reason the host
        path packed host-side in the first place)."""
        device_pack = (host_op is None and self._device_pack()
                       and all(isinstance(t, jax.Array) for t in tensors))
        arrs = (list(tensors) if device_pack
                else [np.asarray(t) for t in tensors])
        by_dtype: Dict = {}
        for i, a in enumerate(arrs):
            acc = _accum_dtype(a.dtype)
            by_dtype.setdefault(np.dtype(acc) if acc else a.dtype,
                                []).append(i)
        results: List[Optional[jax.Array]] = [None] * len(arrs)
        for buf_dt, idxs in by_dtype.items():
            # ``align`` > 1 (the quantized wire): each tensor's span is
            # padded to whole blocks so block scales never mix tensors
            # of different magnitudes — same layout the SP fused path
            # and the optimizer's error-feedback residual assume.
            n = int(sum(_quant.padded_size(int(arrs[i].size), align)
                        for i in idxs))
            padded = _fusion_padded_size(n)

            if device_pack:
                buf = self._pack_device([arrs[i] for i in idxs], padded,
                                        buf_dt, align)
                key = key_fn(padded, str(buf_dt))
                prog = self._program(key, lambda: build(padded, buf_dt))
                out = prog(self._mp_stacked_device(buf, mesh, axes))
                _unpack(out, arrs, idxs, results, align)
                continue

            buf = np.zeros((padded,), dtype=buf_dt)
            off = 0
            for i in idxs:
                flat = arrs[i].ravel()
                buf[off:off + flat.size] = flat.astype(buf_dt)
                off += _quant.padded_size(int(flat.size), align)

            # Numerics sentinel (docs/numerics.md): the pack above just
            # touched every byte, so one isfinite pass over the same
            # contiguous LOCAL buffer is the cheapest possible place to
            # catch a NaN *before* the reduction spreads it to every
            # rank. Single flag check when the plane is off.
            if _numerics.enabled():
                _numerics.scan_payload(buf)

            if host_op is not None:
                # The reduced buffer is HOST memory (the shm plane's
                # truth): ONE whole-buffer jnp.asarray, then the cached
                # traced-offset device slices (_UNPACK_CACHE). One
                # transfer beats per-tensor jnp.asarray (each is its
                # own copy+dispatch — measured as a drag on the np=8
                # scaling proxy when tried), and the compile storm the
                # device slicing used to have is fixed by the
                # offset-traced programs + quantized padding.
                _unpack(jnp.asarray(np.asarray(host_op(buf))),
                        arrs, idxs, results, align)
                continue

            key = key_fn(padded, str(buf_dt))
            prog = self._program(
                key, lambda: build(padded, buf_dt))
            out = prog(self._mp_stacked(buf, mesh=mesh, axes=axes))
            _unpack(out, arrs, idxs, results, align)
        return [r for r in results]

    def broadcast_fused_mp(self, tensors: Sequence[jax.Array],
                           root_rank: int) -> List[jax.Array]:
        """Cross-process broadcast from virtual rank ``root_rank``.

        Host-assembled, size-quantized fusion buffer like
        allreduce_fused_mp: a parameter-broadcast burst (hundreds of
        variables at job start) must compile one program keyed by padded
        buffer size, not one per group composition.
        """
        mesh = self.mesh
        shm = self._shm()
        if shm is not None:
            # Root VIRTUAL rank maps to its owning process (homogeneous
            # local device counts, checked at init).
            local = max(1, self.world_size // jax.process_count())
            root_proc = int(root_rank) // local
            return self._run_fused_buffers(
                tensors, None, key_fn=None, mesh=mesh, axes=("dp",),
                host_op=lambda buf: shm.broadcast(buf, root_proc))

        def build(padded, buf_dt):
            def fused(x):
                def shard_fn(y):
                    v = y[0]
                    idx = jax.lax.axis_index("dp")
                    masked = jnp.where(idx == root_rank, v,
                                       jnp.zeros_like(v))
                    return jax.lax.psum(masked, "dp")

                return jax.shard_map(
                    shard_fn, mesh=mesh, in_specs=P("dp"),
                    out_specs=P(), check_vma=False)(x)

            return jax.jit(fused)

        return self._run_fused_buffers(
            tensors, build,
            key_fn=lambda padded, dt: ("bcmp_buf", padded, dt,
                                       int(root_rank), id(mesh)),
            mesh=mesh, axes=("dp",))

    def allgather_fused_mp(self, tensors: Sequence[jax.Array]
                           ) -> List[jax.Array]:
        """Cross-process allgather, equal first dims: one segment per
        virtual rank, concatenated along dim 0. Hierarchical mode gathers
        over 'ici' first (intra-host), then 'dcn' (operations.cc:929-1032)."""
        hier = self.hierarchical_allgather
        mesh = self.hier_mesh if hier else self.mesh
        axes = ("dcn", "ici") if hier else ("dp",)
        shapes = tuple(tuple(t.shape) for t in tensors)
        dtypes = tuple(str(t.dtype) for t in tensors)
        key = ("agmp", shapes, dtypes, hier, id(mesh))

        def build():
            def fused(*xs):
                def shard_fn(*ys):
                    if hier:
                        return tuple(_hier_gather(y[0], tiled=True)
                                     for y in ys)
                    return tuple(
                        jax.lax.all_gather(y[0], "dp", axis=0, tiled=True)
                        for y in ys)
                return jax.shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=tuple(P(axes) for _ in xs),
                    out_specs=tuple(P() for _ in xs),
                    check_vma=False)(*xs)
            return jax.jit(fused)

        prog = self._program(key, build)
        return list(prog(*[self._mp_stacked(t, mesh=mesh, axes=axes)
                           for t in tensors]))

    def allgather_sharded_mp(self, x: jax.Array) -> jax.Array:
        """Allgather of a global array already sharded P('dp') on the
        leading axis: each virtual rank contributes its row block; the
        result is the same rows, replicated. (The single-process path
        routes this through allgather_ragged; a multi-host sharded array
        cannot be pulled to one host, so it is re-gathered in place.)"""
        hier = self.hierarchical_allgather
        mesh = self.hier_mesh if hier else self.mesh
        axes = ("dcn", "ici") if hier else ("dp",)
        key = ("agsmp", tuple(x.shape), str(x.dtype), hier, id(mesh))

        def build():
            def fn(z):
                def shard_fn(y):
                    if hier:
                        return _hier_gather(y, tiled=True)
                    return jax.lax.all_gather(y, "dp", axis=0, tiled=True)
                return jax.shard_map(
                    shard_fn, mesh=mesh, in_specs=P(axes),
                    out_specs=P(), check_vma=False)(z)
            return jax.jit(fn)

        xin = jax.device_put(x, NamedSharding(mesh, P(axes)))
        return self._program(key, build)(xin)

    def allgather_ragged_mp(self, tensor: jax.Array,
                            per_device_dims: Sequence[int]) -> jax.Array:
        """Cross-process MPI_Allgatherv: first dims differ per process.
        ``per_device_dims`` (one per virtual rank, from the coordinator's
        announced shapes) drives pad-to-max + gather + trim."""
        hier = self.hierarchical_allgather
        mesh = self.hier_mesh if hier else self.mesh
        axes = ("dcn", "ici") if hier else ("dp",)
        n = self.world_size
        m = max(int(d) for d in per_device_dims)
        arr = np.asarray(tensor)
        rest = arr.shape[1:]
        key = ("agrmp", (m,) + tuple(rest), str(tensor.dtype),
               tuple(int(d) for d in per_device_dims), hier, id(mesh))

        def build():
            def fn(stacked):
                def shard_fn(z):
                    if hier:
                        return _hier_gather(z[0], tiled=False)
                    return jax.lax.all_gather(z[0], "dp", axis=0,
                                              tiled=False)
                return jax.shard_map(
                    shard_fn, mesh=mesh, in_specs=P(axes),
                    out_specs=P(), check_vma=False)(stacked)
            return jax.jit(fn)

        padded = np.zeros((m,) + rest, dtype=arr.dtype)
        padded[: arr.shape[0]] = arr
        prog = self._program(key, build)
        gathered = prog(self._mp_stacked(padded, mesh=mesh, axes=axes))
        return _trim_concat(gathered, per_device_dims)


_default_executor: Optional[CollectiveExecutor] = None


def default_executor() -> CollectiveExecutor:
    global _default_executor
    if _default_executor is None:
        from .utils import env as _env
        _default_executor = CollectiveExecutor(
            hierarchical_allreduce=_env.hierarchical_allreduce(),
            hierarchical_allgather=_env.hierarchical_allgather())
    return _default_executor


def reset_default_executor() -> None:
    """Drop the default executor (and its jitted-program cache).

    Counter state is NOT lost: the canonical cache-hit/miss/device-put
    series live on the process-global metrics registry
    (hvdtpu_executor_*_total) and are mirrored live, so a snapshot taken
    after a reset still accounts for everything the dropped instance did
    — only the per-instance deprecation aliases restart at zero."""
    global _default_executor
    _default_executor = None
