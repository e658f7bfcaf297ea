"""Torch tensor collectives over the TPU-native engine.

This is the shim the reference implements as a C++ extension
(horovod/torch/mpi_ops_v2.cc + horovod/torch/mpi_ops.py): sync / async /
in-place variants of allreduce / allgather / broadcast on ``torch.Tensor``s,
integer-handle ``poll``/``synchronize`` semantics, and autograd Functions
whose backward passes are themselves collectives (torch/mpi_ops.py:110-121,
236-254, 318-332).

Where the reference operates on the tensor's own memory
(torch/adapter_v2.cc:40-105), this shim hands torch (CPU) tensors to the
JAX collective engine zero-copy via DLPack (utils/interop.py) — bf16
crosses natively — and aliases engine output buffers on the way back.
The numpy fallback path covers what DLPack can't carry exactly: 64-bit
dtypes in 32-bit JAX mode (as int32 bit pairs for movement collectives,
reinterpreted via ml_dtypes for bf16), non-contiguous tensors, and
non-exportable output buffers (real-TPU outputs cross via one D2H copy).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

import numpy as np
import torch

from .. import ops as _ops
from ..ops import HorovodInternalError
from .. import topology as _topo
from ..utils import interop as _interop

try:
    import ml_dtypes as _mld
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    _mld = None


# ---------------------------------------------------------------------------
# torch <-> jax conversion
# ---------------------------------------------------------------------------

_64BIT = (torch.int64, torch.float64)


def _x64_enabled() -> bool:
    import jax
    return bool(jax.config.jax_enable_x64)


def _to_numpy(t: torch.Tensor) -> np.ndarray:
    t = t.detach().cpu().contiguous()
    if t.dtype == torch.bfloat16:
        bits = t.view(torch.uint16).numpy()
        return bits.view(_mld.bfloat16)
    return t.numpy()


def _ingress(t: torch.Tensor):
    """Tensor -> engine payload: DLPack zero-copy when possible, numpy
    otherwise. The payload aliases the tensor's memory either way (for a
    contiguous CPU tensor ``.numpy()`` is also an alias); the engine's
    device_put is the one real transfer."""
    a = _interop.try_torch_to_jax(t)
    return a if a is not None else _to_numpy(t)


def _bits32(t: torch.Tensor) -> np.ndarray:
    """Reinterpret a 64-bit tensor as int32 pairs — exact transport for
    data-movement collectives (broadcast/allgather) under 32-bit JAX."""
    t = t.detach().cpu().contiguous()
    if t.dim() == 0:
        # torch refuses to view a 0-dim tensor as a narrower dtype; the
        # original shape is restored from the handle at synchronize time.
        t = t.reshape(1)
    return t.view(torch.int32).numpy()


def _np_private(arr: np.ndarray) -> np.ndarray:
    """EXACTLY one host copy: a contiguous, writable array that does not
    alias the source. ``np.ascontiguousarray(x).copy()`` paid two copies
    for a non-contiguous source (ascontiguousarray already copies) and
    one avoidable copy chain for bf16; branch instead of stacking."""
    if arr.flags["C_CONTIGUOUS"]:
        # May alias an engine/XLA buffer (np.asarray on a CPU backend
        # array is zero-copy and read-only) — one defensive copy.
        return arr.copy()
    return np.ascontiguousarray(arr)


def _to_torch_host(arr: np.ndarray, dtype: torch.dtype,
                   from_bits: bool = False) -> torch.Tensor:
    """Host numpy array (already transferred) -> torch tensor."""
    if from_bits:
        return torch.from_numpy(_np_private(arr)).view(dtype)
    if dtype == torch.bfloat16:
        return torch.from_numpy(
            _np_private(arr.view(np.uint16))).view(torch.bfloat16)
    return torch.from_numpy(np.array(arr)).to(dtype)


def _to_torch(a, dtype: torch.dtype, from_bits: bool = False) -> torch.Tensor:
    return _to_torch_host(_interop.to_host(a), dtype, from_bits)


# ---------------------------------------------------------------------------
# Handle manager — integer handles like the reference's HandleManager
# (horovod/torch/handle_manager.cc:21-50)
# ---------------------------------------------------------------------------

class _TorchHandle:
    __slots__ = ("inner", "dtype", "shape", "output", "target", "from_bits")

    def __init__(self, inner, dtype, shape, target=None, from_bits=False):
        self.inner = inner          # engine Handle
        self.dtype = dtype          # torch dtype of the result
        self.shape = shape
        self.output = None          # materialized torch result
        self.target = target        # in-place target tensor, if any
        self.from_bits = from_bits  # 64-bit value sent as int32 bit pairs


_lock = threading.Lock()
_next_handle = [0]
_handles: Dict[int, _TorchHandle] = {}


def _register(h: _TorchHandle) -> int:
    with _lock:
        _next_handle[0] += 1
        hid = _next_handle[0]
        _handles[hid] = h
    return hid


def poll(handle: int) -> bool:
    """True iff the collective behind ``handle`` completed
    (mpi_ops_v2.cc:226, torch/mpi_ops.py:406-417)."""
    with _lock:
        th = _handles.get(handle)
    if th is None:
        raise ValueError(f"Unknown handle {handle}")
    return _ops.poll(th.inner)


def synchronize(handle: int) -> torch.Tensor:
    """Block until done; return the output tensor. In-place variants copy
    the result into the submitted tensor (WaitAndClear,
    mpi_ops_v2.cc:228-234 + torch/mpi_ops.py:419-438). One code path
    with the batched variant: this is synchronize_many of one."""
    return synchronize_many([handle])[0]


def synchronize_many(handles) -> list:
    """Synchronize a batch of handles through ONE engine flush and
    BATCHED device-to-host egress. The first ``wait`` hints the engine
    to drain the whole burst; per-handle ``synchronize`` would instead
    pay one readback round trip each — on accelerators behind a
    latency-heavy link batching the list measured ~2x on a
    ResNet-50-shaped gradient set. Egress is DLPack wherever the backend allows
    (zero-copy alias on the CPU mesh, one batched device→CPU transfer
    on chip — interop.torch_egress_many); only what DLPack cannot carry
    (64-bit bit-pair transport, export refusals) is fetched via
    numpy."""
    handles = list(handles)
    with _lock:
        # Validate BEFORE popping: one bad id must not destroy the
        # other handles in the call (per-handle synchronize never did).
        if len(set(handles)) != len(handles):
            raise ValueError("duplicate handle in synchronize_many")
        missing = [h for h in handles if h not in _handles]
        if missing:
            raise ValueError(f"Unknown handle {missing[0]}")
        ths = [_handles.pop(h) for h in handles]
    outs = [th.inner.wait() for th in ths]
    results: list = [None] * len(ths)
    # DLPack egress for everything but the 64-bit bit-pair transport:
    # zero-copy alias on the CPU mesh, ONE batched device->CPU transfer
    # + alias on accelerator backends (interop.torch_egress_many). The
    # remainder (bits transport, export refusals, kill switch) is
    # batch-fetched through numpy.
    egress_idx = [i for i, th in enumerate(ths) if not th.from_bits]
    exported = _interop.torch_egress_many([outs[i] for i in egress_idx])
    rest = [i for i, th in enumerate(ths) if th.from_bits]
    for i, exp in zip(egress_idx, exported):
        th = ths[i]
        if exp is None or exp[0].dtype != th.dtype:
            rest.append(i)
            continue
        t, private = exp
        if th.target is None and not private:
            # Out-of-place result aliasing an ENGINE-RETAINED buffer
            # (zero-copy CPU-mesh egress): torch has no read-only
            # tensors, and handing the alias out would let ordinary
            # in-place math (result.add_(...)) silently mutate an array
            # the engine still retains. Clone before release. Transfer
            # egress (private=True) and in-place variants (the alias is
            # only a copy_ source) keep the single-copy path.
            t = t.clone()
        results[i] = t
    if rest:
        rest.sort()
        hosts = _interop.to_host_many([outs[i] for i in rest])
        for i, arr in zip(rest, hosts):
            results[i] = _to_torch_host(arr, ths[i].dtype,
                                        ths[i].from_bits)
    final = []
    for th, result in zip(ths, results):
        if th.target is not None:
            with torch.no_grad():
                th.target.copy_(result.reshape(th.target.shape))
            final.append(th.target)
            continue
        if th.shape is not None:
            result = result.reshape(th.shape)
        final.append(result)
    return final


# ---------------------------------------------------------------------------
# Async ops
# ---------------------------------------------------------------------------

def allreduce_async(tensor: torch.Tensor, average: bool = True,
                    name: Optional[str] = None, compression=None) -> int:
    """Returns a handle; result via synchronize() (torch/mpi_ops.py:128-152).

    64-bit reductions without jax_enable_x64 are rejected by the engine's
    narrowing guard (ops/collective.py::_prep) at enqueue time.
    ``compression`` only forwards a blockwise wire spec
    (Compression.int8_blockwise / fp8_blockwise) to the engine — the
    quantization runs inside the fused XLA program."""
    arr = _ingress(tensor)
    inner = _ops.allreduce_async(arr, average=average, name=name,
                                 compression=compression)
    return _register(_TorchHandle(inner, tensor.dtype, tensor.shape))


def allreduce_async_(tensor: torch.Tensor, average: bool = True,
                     name: Optional[str] = None, compression=None) -> int:
    """In-place: the result lands in ``tensor`` (torch/mpi_ops.py:182-207)."""
    arr = _ingress(tensor)
    inner = _ops.allreduce_async(arr, average=average, name=name,
                                 compression=compression)
    return _register(
        _TorchHandle(inner, tensor.dtype, tensor.shape, target=tensor))


def _movement_payload(tensor: torch.Tensor):
    """(engine payload, from_bits) for data-movement collectives: 64-bit
    dtypes travel as exact int32 bit pairs when JAX is in 32-bit mode;
    everything else crosses via DLPack when possible."""
    if tensor.dtype in _64BIT and not _x64_enabled():
        return _bits32(tensor), True
    return _ingress(tensor), False


def allgather_async(tensor: torch.Tensor, name: Optional[str] = None) -> int:
    """Gather along dim 0 from every rank (torch/mpi_ops.py:256-280)."""
    arr, from_bits = _movement_payload(tensor)
    inner = _ops.allgather_async(arr, name=name)
    return _register(
        _TorchHandle(inner, tensor.dtype, None, from_bits=from_bits))


def broadcast_async(tensor: torch.Tensor, root_rank: int,
                    name: Optional[str] = None) -> int:
    arr, from_bits = _movement_payload(tensor)
    inner = _ops.broadcast_async(arr, root_rank, name=name)
    return _register(_TorchHandle(inner, tensor.dtype, tensor.shape,
                                  from_bits=from_bits))


def broadcast_async_(tensor: torch.Tensor, root_rank: int,
                     name: Optional[str] = None) -> int:
    """In-place broadcast (torch/mpi_ops.py:360-392)."""
    arr, from_bits = _movement_payload(tensor)
    inner = _ops.broadcast_async(arr, root_rank, name=name)
    return _register(
        _TorchHandle(inner, tensor.dtype, tensor.shape, target=tensor,
                     from_bits=from_bits))


# ---------------------------------------------------------------------------
# Autograd functions — backward passes are collectives, exactly as the
# reference registers them (torch/mpi_ops.py:110-121, 236-254, 318-332)
# ---------------------------------------------------------------------------

class _HorovodAllreduce(torch.autograd.Function):
    @staticmethod
    def forward(ctx, tensor, average, name, compression=None):
        ctx.average = average
        ctx.compression = compression
        return synchronize(allreduce_async(tensor, average, name,
                                           compression=compression))

    @staticmethod
    def backward(ctx, grad_output):
        # d(allreduce(x))/dx distributes the same allreduce over the grads.
        return (synchronize(allreduce_async(grad_output, ctx.average,
                                            compression=ctx.compression)),
                None, None, None)


class _HorovodAllgather(torch.autograd.Function):
    @staticmethod
    def forward(ctx, tensor, name):
        ctx.dim0 = tensor.shape[0]
        return synchronize(allgather_async(tensor, name))

    @staticmethod
    def backward(ctx, grad_output):
        # Sum-allreduce the full gathered grad, then take this rank's
        # segment (torch/mpi_ops.py:236-254).
        summed = synchronize(allreduce_async(grad_output, average=False))
        r = _topo.rank()
        return summed[r * ctx.dim0:(r + 1) * ctx.dim0], None


class _HorovodBroadcast(torch.autograd.Function):
    @staticmethod
    def forward(ctx, tensor, root_rank, name):
        ctx.root_rank = root_rank
        return synchronize(broadcast_async(tensor, root_rank, name))

    @staticmethod
    def backward(ctx, grad_output):
        grad = synchronize(allreduce_async(grad_output, average=False))
        if _topo.rank() != ctx.root_rank:
            grad = torch.zeros_like(grad)
        return grad, None, None


# ---------------------------------------------------------------------------
# Sync ops
# ---------------------------------------------------------------------------

def allreduce(tensor: torch.Tensor, average: bool = True,
              name: Optional[str] = None, compression=None) -> torch.Tensor:
    """Differentiable synchronous allreduce (torch/mpi_ops.py:110-126)."""
    from .compression import Compression
    compression = compression or Compression.none
    wire, cctx = compression.compress(tensor)
    blockwise = compression \
        if getattr(compression, "wire_spec", None) is not None else None
    out = _HorovodAllreduce.apply(wire, average, name, blockwise)
    return compression.decompress(out, cctx)


def allreduce_(tensor: torch.Tensor, average: bool = True,
               name: Optional[str] = None) -> torch.Tensor:
    """In-place synchronous allreduce (torch/mpi_ops.py:209-233)."""
    return synchronize(allreduce_async_(tensor, average, name))


def allgather(tensor: torch.Tensor,
              name: Optional[str] = None) -> torch.Tensor:
    """Differentiable allgather along dim 0 (torch/mpi_ops.py:282-316)."""
    return _HorovodAllgather.apply(tensor, name)


def broadcast(tensor: torch.Tensor, root_rank: int,
              name: Optional[str] = None) -> torch.Tensor:
    """Differentiable broadcast (torch/mpi_ops.py:318-358)."""
    return _HorovodBroadcast.apply(tensor, root_rank, name)


def broadcast_(tensor: torch.Tensor, root_rank: int,
               name: Optional[str] = None) -> torch.Tensor:
    return synchronize(broadcast_async_(tensor, root_rank, name))
