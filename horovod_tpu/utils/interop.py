"""DLPack zero-copy framework boundary.

BASELINE.json's north star names DLPack explicitly: the TF/Keras/PyTorch
``DistributedOptimizer`` wrappers hand gradients to the JAX collective
path *via DLPack*. The reference's torch adapter operates directly on the
tensor's own memory with zero host copies
(/root/reference/horovod/torch/adapter_v2.cc:40-105 — ``tensor_util``
resize/copy exists only for the CudaOnCPU staging path); the TPU-native
analogue is buffer aliasing across the DLPack boundary:

  ingress  torch/TF CPU tensor --``__dlpack__``--> ``jax.Array`` on the
           JAX CPU backend (zero-copy alias, bf16/fp16 carried natively);
           the engine's ``device_put`` onto the collective mesh is then
           the ONE unavoidable host->device transfer.
  egress   engine output (replicated over the mesh) -> shard-0
           single-device buffer --``__dlpack__``--> torch/TF tensor.
           Zero-copy on the CPU mesh. On a real TPU the device buffer
           cannot export DLPack directly, so egress transfers it onto
           the always-present JAX *CPU backend* first (``jax.device_put``
           — the one unavoidable D2H copy, batched for a whole handle
           group) and exports THAT buffer: still exactly one host copy,
           but the torch tensor aliases it instead of paying the numpy
           materialize + ``torch.from_numpy`` + ``.copy()`` chain.
           bf16 rides the same path; where the DLPack exchange refuses
           bfloat16, the buffer crosses as a uint16 bitcast and is
           re-viewed as bf16 on the torch side (bitcast transport).

Fallbacks (the numpy path) cover everything DLPack cannot carry exactly:

- 64-bit dtypes in 32-bit JAX mode: ``jax.dlpack.from_dlpack`` silently
  TRUNCATES int64/float64 to 32 bits (measured: 2**40 -> 0), so those
  route through the shims' explicit guards / int32 bit-pair transport.
- non-CPU or non-contiguous source tensors, sharded-but-not-replicated
  outputs, and any ``__dlpack__`` refusal.

Aliasing contract (identical to the reference's): a tensor handed to an
async collective must not be mutated until ``synchronize()`` returns;
egress tensors alias buffers that nothing else references once the
handle is cleared from the handle table.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = [
    "try_torch_to_jax", "try_jax_to_torch", "torch_egress_many",
    "transfer_egress_supported",
    "try_tf_to_jax", "try_jax_to_tf", "jax_to_tf",
    "exportable_buffer", "to_host", "stats", "reset_stats",
]

# Observability: tests assert the fast path actually ran; the A/B bench
# reports the split. The same four series are mirrored into the metrics
# registry (hvdtpu_interop_transfers_total{direction,path}) so the
# steady-state split is visible next to the engine counters; this dict
# stays the reset-able per-process view tests and benches diff.
_stats = {"dlpack_in": 0, "numpy_in": 0, "dlpack_out": 0, "numpy_out": 0}

_reg_children = None


def _bump(key: str, n: int = 1) -> None:
    global _reg_children
    _stats[key] += n
    if _reg_children is None:
        from ..observability import registry as _obs
        fam = _obs.registry().counter(
            "hvdtpu_interop_transfers_total",
            "Framework-boundary tensor crossings by direction and path "
            "(dlpack = zero-copy / single-transfer export, numpy = host "
            "materialize fallback)")
        _reg_children = {
            k: fam.labels(direction=k.split("_")[1], path=k.split("_")[0])
            for k in _stats}
    _reg_children[key].inc(n)


def stats() -> dict:
    return dict(_stats)


def reset_stats() -> None:
    for k in _stats:
        _stats[k] = 0


def _x64_enabled() -> bool:
    import jax
    return bool(jax.config.jax_enable_x64)


def _enabled() -> bool:
    from . import env
    return env.dlpack_boundary()


# ---------------------------------------------------------------------------
# Ingress
# ---------------------------------------------------------------------------

def try_torch_to_jax(tensor) -> Optional["jax.Array"]:
    """torch.Tensor -> jax.Array via DLPack, or None if the numpy fallback
    must be used. Zero-copy for contiguous CPU tensors; bf16 crosses
    natively (no uint16 bit-reinterpret dance)."""
    import torch
    import jax

    t = tensor.detach()
    if not _enabled() or t.device.type != "cpu" or not t.is_contiguous():
        _bump("numpy_in")
        return None
    wide = (torch.int64, torch.float64, torch.complex128,
            getattr(torch, "uint64", torch.int64))
    if t.dtype in wide and not _x64_enabled():
        # DLPack import would truncate (int64/uint64 -> 32-bit,
        # complex128 -> complex64, all measured); the shim's
        # guard/bits transport handles 64-bit explicitly.
        _bump("numpy_in")
        return None
    try:
        a = jax.dlpack.from_dlpack(t)
    except Exception:
        _bump("numpy_in")
        return None
    _bump("dlpack_in")
    return a


def try_tf_to_jax(tensor) -> Optional["jax.Array"]:
    """tf.Tensor (eager) -> jax.Array via DLPack, or None for fallback.
    TF eager tensors expose ``__dlpack__``/``__dlpack_device__``; CPU
    tensors import zero-copy."""
    import jax

    if not _enabled():
        _bump("numpy_in")
        return None
    dt = getattr(tensor, "dtype", None)
    if dt is not None and getattr(dt, "name", "") in (
            "int64", "uint64", "float64", "complex128") \
            and not _x64_enabled():
        _bump("numpy_in")
        return None
    if not hasattr(tensor, "__dlpack__") \
            or not hasattr(tensor, "__dlpack_device__"):
        _bump("numpy_in")
        return None
    try:
        if tensor.__dlpack_device__()[0] != 1:  # kDLCPU
            _bump("numpy_in")
            return None
        a = jax.dlpack.from_dlpack(tensor)
    except Exception:
        _bump("numpy_in")
        return None
    _bump("dlpack_in")
    return a


# ---------------------------------------------------------------------------
# Egress
# ---------------------------------------------------------------------------

def _single_buffer(a):
    """The single-device array behind ``a``: ``a`` itself when unsharded,
    shard 0 when fully replicated (every shard holds the same bytes),
    else None."""
    import jax

    if not isinstance(a, jax.Array):
        return None
    try:
        if len(a.sharding.device_set) > 1:
            if not (a.sharding.is_fully_replicated and a.is_fully_addressable):
                return None
            a = a.addressable_shards[0].data
    except Exception:
        return None
    return a


def exportable_buffer(a):
    """Like :func:`_single_buffer` but only when the buffer can export
    DLPack — jax refuses non-CPU platforms ("__dlpack__ device only
    supported for CPU and GPU", and GPU never occurs here)."""
    buf = _single_buffer(a)
    if buf is None:
        return None
    try:
        if next(iter(buf.sharding.device_set)).platform != "cpu":
            return None
    except Exception:
        return None
    return buf


def try_jax_to_torch(a) -> Optional["torch.Tensor"]:
    """jax.Array -> torch.Tensor aliasing the engine buffer (no copy), or
    None for fallback. The DLPack capsule keeps the XLA buffer alive for
    the torch tensor's lifetime."""
    import torch

    buf = exportable_buffer(a) if _enabled() else None
    if buf is None:
        _bump("numpy_out")
        return None
    try:
        t = torch.from_dlpack(buf)
    except Exception:
        _bump("numpy_out")
        return None
    _bump("dlpack_out")
    return t


_transfer_probe: Optional[bool] = None


def _buffer_platform(buf) -> Optional[str]:
    """Platform string of a single-device buffer, or None when it cannot
    be determined (fallback slot). Separated out so tests can simulate a
    chip-resident buffer on the CPU backend."""
    try:
        return next(iter(buf.sharding.device_set)).platform
    except Exception:
        return None


def _cpu_device():
    import jax
    try:
        return jax.devices("cpu")[0]
    except Exception:
        return None


def transfer_egress_supported() -> bool:
    """Capability probe, resolved once: can a default-backend buffer be
    copied onto the always-present JAX CPU backend and exported through
    DLPack? This is what lets egress stay on the DLPack path on a real
    chip, whose device buffers refuse ``__dlpack__`` directly. Trivially
    true when the default backend IS cpu; False disables the transfer
    leg and egress falls back to numpy (``HOROVOD_TPU_DLPACK=0`` kills
    both)."""
    global _transfer_probe
    if _transfer_probe is None:
        _transfer_probe = _probe_transfer()
    return _transfer_probe


def _probe_transfer() -> bool:
    try:
        import jax
        import jax.numpy as jnp
        import torch

        dev = _cpu_device()
        if dev is None:
            return False
        moved = jax.device_put(jnp.zeros((2,), jnp.float32), dev)
        torch.from_dlpack(moved)
        return True
    except Exception:
        return False


def _export_cpu_buffer_torch(buf) -> Optional["torch.Tensor"]:
    """CPU jax buffer -> torch tensor aliasing it, or None. bf16 exports
    natively where the exchange allows; otherwise it crosses as a uint16
    bitcast re-viewed as bf16 torch-side (bitcast transport — the bits
    buffer is a fresh CPU array the capsule keeps alive)."""
    import torch

    if str(buf.dtype) == "bfloat16":
        try:
            return torch.from_dlpack(buf)
        except Exception:
            pass
        try:
            import jax
            import jax.numpy as jnp
            bits = jax.lax.bitcast_convert_type(buf, jnp.uint16)
            return torch.from_dlpack(bits).view(torch.bfloat16)
        except Exception:
            return None
    try:
        return torch.from_dlpack(buf)
    except Exception:
        return None


def torch_egress_many(arrays) -> list:
    """Batched DLPack egress for a group of engine outputs: one slot per
    input, each ``None`` (numpy fallback needed) or ``(tensor, private)``.

    ``private=False``: the tensor ALIASES an engine-retained buffer (the
    zero-copy CPU-mesh case) — out-of-place callers must clone before
    releasing it to user code. ``private=True``: the tensor aliases a
    buffer created by this call's device→CPU transfer, which nothing
    else references — safe to hand out directly, so the chip path stays
    at exactly one host copy.

    All device→CPU transfers in the group ride ONE ``jax.device_put``
    call (each read through a latency-heavy link is its own round trip —
    the to_host_many lesson applied to the DLPack path). Counts one
    dlpack_out or numpy_out per slot; callers falling back must not
    re-count."""
    n = len(arrays)
    results: list = [None] * n
    if n == 0:
        return results
    if not _enabled():
        _bump("numpy_out", n)
        return results
    import jax

    bufs = [_single_buffer(a) for a in arrays]
    moved = [False] * n
    transfer = []
    for i, buf in enumerate(bufs):
        if buf is None:
            continue
        plat = _buffer_platform(buf)
        if plat is None:
            bufs[i] = None
        elif plat != "cpu":
            transfer.append(i)
    if transfer:
        if transfer_egress_supported():
            try:
                put = jax.device_put([bufs[i] for i in transfer],
                                     _cpu_device())
                for i, m in zip(transfer, put):
                    bufs[i] = m
                    moved[i] = True
            except Exception:
                for i in transfer:
                    bufs[i] = None
        else:
            for i in transfer:
                bufs[i] = None
    for i, buf in enumerate(bufs):
        if buf is None:
            _bump("numpy_out")
            continue
        t = _export_cpu_buffer_torch(buf)
        if t is None:
            _bump("numpy_out")
            continue
        _bump("dlpack_out")
        results[i] = (t, moved[i])
    return results


def try_jax_to_tf(a):
    """Gated zero-copy jax -> tf egress, or None for fallback (the
    HOROVOD_TPU_DLPACK kill switch and the stats counters both apply —
    callers that batch their own fallback readback must come through
    here, not exportable_buffer, or the A/B lever lies)."""
    import tensorflow as tf

    buf = exportable_buffer(a) if _enabled() else None
    if buf is None:
        _bump("numpy_out")
        return None
    try:
        out = tf.experimental.dlpack.from_dlpack(buf.__dlpack__())
    except Exception:
        _bump("numpy_out")
        return None
    _bump("dlpack_out")
    return out


def jax_to_tf(a):
    """jax.Array -> tf.Tensor, zero-copy via DLPack when the buffer is an
    exportable CPU buffer, else one host copy via numpy. Always returns a
    tf.Tensor (this is the py_function host-side return path)."""
    import tensorflow as tf

    out = try_jax_to_tf(a)
    if out is not None:
        return out
    return tf.convert_to_tensor(to_host(a))


def to_host(a) -> np.ndarray:
    """One-copy host materialization: read shard 0 of a replicated array
    (works for TPU buffers too — this is the D2H transfer) rather than
    letting numpy assemble the global view."""
    buf = _single_buffer(a)
    return np.asarray(buf if buf is not None else a)


def to_host_many(arrays) -> list:
    """Batched host materialization: ONE ``jax.device_get`` over the
    whole list instead of a per-array readback. Each read through a
    latency-heavy device link is its own round trip; batching the
    group measured ~2x on a ResNet-50-shaped gradient set. Shard-0 extraction as in
    :func:`to_host`."""
    import jax

    gets = []
    for a in arrays:
        buf = _single_buffer(a)
        gets.append(buf if buf is not None else a)
    return [np.asarray(h) for h in jax.device_get(gets)]
