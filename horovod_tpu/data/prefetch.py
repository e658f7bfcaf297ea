"""Double-buffered prefetch-to-device (docs/data.md#prefetch).

``prefetch_to_device(loader, sharding, depth=2)`` runs the loader AND
the host→device copy on a background thread, keeping up to ``depth``
batches resident on device ahead of the consumer. While the step
executes batch *k*, the thread is already materializing and staging
batch *k+1* — the overlap that turns "input-bound" into
"compute-bound" when the source can keep up, and the mechanism the
``tools/trace report`` bound verdict is calibrated against.

StepTimer wiring: the consumer's blocking wait inside ``__next__``
lands in the pre-step gap, which :class:`StepTimer` already attributes
to the ``input`` phase. When a ``timer=`` is passed, the prefetcher
additionally *credits* the staged copy time of each batch the consumer
actually waited for to the ``h2d`` phase
(:meth:`StepTimer.credit_h2d`), so the input/h2d split stays honest in
both regimes: fully overlapped (wait ≈ 0 → everything is compute),
and starved (wait > 0 → split between source time = ``input`` and copy
time = ``h2d``).

``sharding`` may be a ``jax.sharding.Sharding``, a ``Mesh`` (batches go
to ``PartitionSpec('dp')`` when the mesh has a ``dp`` axis, else its
first axis), or None (default device placement).
"""

from __future__ import annotations

import queue
import threading
from typing import Optional

from ..observability import registry as _reg
from .loader import Batch

_SENTINEL = object()


def _metrics():
    r = _reg.registry()
    return {
        "occupancy": r.gauge(
            "hvdtpu_data_prefetch_occupancy",
            "Batches resident on device ahead of the consumer at the "
            "last fetch (0 with a starved source: the consumer is "
            "waiting — the input-bound signature)").labels(),
        "wait": r.counter(
            "hvdtpu_data_wait_seconds_total",
            "Seconds the training loop blocked waiting on the "
            "prefetcher (input starvation as a number)").labels(),
        "h2d": r.counter(
            "hvdtpu_data_h2d_seconds_total",
            "Seconds spent copying batches host-to-device (on the "
            "prefetch thread — overlapped with the step unless the "
            "wait counter is climbing too)").labels(),
    }


_cached = None


def _m():
    global _cached
    if _cached is None:
        _cached = _metrics()
    return _cached


def _resolve_sharding(sharding):
    if sharding is None:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    if isinstance(sharding, Mesh):
        axis = "dp" if "dp" in sharding.axis_names \
            else sharding.axis_names[0]
        return NamedSharding(sharding, PartitionSpec(axis))
    return sharding


class DevicePrefetcher:
    """Iterator produced by :func:`prefetch_to_device`."""

    def __init__(self, it, sharding=None, *, depth: int = 2,
                 timer=None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._it = iter(it)
        self._sharding = _resolve_sharding(sharding)
        self._timer = timer
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._closed = False
        self._thread = threading.Thread(
            target=self._producer, name="hvd-tpu-data-prefetch",
            daemon=True)
        self._thread.start()

    def _stage(self, batch):
        import jax
        with _reg.span("data/h2d", _m()["h2d"]) as h2d:
            if self._sharding is not None:
                data = tuple(jax.device_put(a, self._sharding)
                             for a in batch.data)
            else:
                data = tuple(jax.device_put(a) for a in batch.data)
            jax.block_until_ready(data)
        if isinstance(batch, Batch):
            batch = batch._replace(data=data)
        else:  # plain tuples/arrays prefetch too
            batch = data
        return batch, h2d.seconds

    def _producer(self):
        try:
            for batch in self._it:
                if self._closed:
                    return
                self._q.put(self._stage(batch))
            self._q.put(_SENTINEL)
        except BaseException as e:  # propagate into the consumer
            self._q.put(e)

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        mt = _m()
        with _reg.span("data/wait", mt["wait"]) as wait:
            item = self._q.get()
        wait_s = wait.seconds
        mt["occupancy"].set(self._q.qsize())
        if item is _SENTINEL:
            self._closed = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._closed = True
            raise item
        batch, h2d_s = item
        if self._timer is not None and wait_s > 0:
            # The consumer stalled; the staged copy of THIS batch is the
            # h2d share of that stall, the rest was the source.
            self._timer.credit_h2d(min(wait_s, h2d_s))
        return batch

    def close(self) -> None:
        """Stop the background thread (the loader may be infinite)."""
        self._closed = True
        # Unblock a producer waiting on a full queue.
        try:
            self._q.get_nowait()
        except queue.Empty:
            pass


def prefetch_to_device(it, sharding=None, *, depth: int = 2,
                       timer=None) -> DevicePrefetcher:
    """Wrap a loader (or any iterator of :class:`Batch` / array tuples)
    with background host→device staging ``depth`` batches deep
    (``depth=2`` is classic double buffering). See module docstring for
    the StepTimer wiring."""
    return DevicePrefetcher(it, sharding, depth=depth, timer=timer)


def stage(batch, sharding=None, *, timer=None):
    """Synchronous (un-prefetched) device staging for simple loops:
    ``device_put`` + ``StepTimer.mark_h2d_done()``. The A in the
    prefetch A/B."""
    import jax
    sh = _resolve_sharding(sharding)
    data = batch.data if isinstance(batch, Batch) else batch
    with _reg.span("data/h2d", _m()["h2d"]):
        if sh is not None:
            staged = tuple(jax.device_put(a, sh) for a in data)
        else:
            staged = tuple(jax.device_put(a) for a in data)
        jax.block_until_ready(staged)
    if timer is not None:
        timer.mark_h2d_done()
    if isinstance(batch, Batch):
        return batch._replace(data=staged)
    return staged
