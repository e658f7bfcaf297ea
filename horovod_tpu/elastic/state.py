"""ElasticState — commit/rollback training state that survives worker loss.

Built on the repo's checkpoint convention (utils/checkpoint.py: rank-0
atomic save, broadcast-on-restore) and extended with the elastic
contract:

  commit(step)   durably record the wrapped trees as of ``step``:
                 rank 0 writes ``<dir>/<step>.pkl`` then atomically
                 repoints ``<dir>/LATEST``; every rank keeps an
                 in-memory host copy for I/O-free rollback; a barrier
                 collective keeps ranks from racing past an unfinished
                 commit.
  rollback()     restore the wrapped trees from the last in-memory
                 commit (same process — e.g. after a caught
                 WorkerFailure, before re-entering the step loop).
  restore()      cold-start path for a (re)joined process: load the
                 LATEST commit from disk on rank 0 and broadcast it so
                 every rank — old survivor or fresh replacement — resumes
                 from identical state. With no commit on disk the
                 *initial* trees are broadcast from rank 0 instead, which
                 is exactly the reference's BroadcastGlobalVariablesHook
                 restart recipe.

Backends (``backend=``):

  ``"pickle"``   the default — the rank-0 single-pickle convention above,
                 unchanged for compatibility.
  ``"sharded"``  rides :class:`horovod_tpu.checkpoint.CheckpointEngine`
                 (docs/checkpoint.md): each process writes only its
                 addressable shards, serialization happens on a
                 background thread (``commit`` returns after the host
                 snapshot; the engine's two-phase manifest/LATEST flip
                 keeps every instant crash-consistent), and ``restore``
                 reads from the shared checkpoint directory on every
                 rank — dp-sharded optimizer moments never transit one host,
                 and a changed world size restores through the manifest
                 resharding path instead of a full broadcast. Requires a
                 directory on a filesystem all ranks share.

Both backends apply keep-last-N retention after each commit
(``HOROVOD_TPU_CHECKPOINT_KEEP``, default 10, 0 = unlimited; the commit
``LATEST`` names is never deleted) — previously ``commit`` grew the
state directory without bound.

The state directory defaults to ``HOROVOD_TPU_ELASTIC_DIR`` (exported by
``run_elastic``); without one, commits are memory-only — rollback works,
but a killed-and-relaunched worker starts from the initial trees (fine
for single-process use and tests of the in-memory path).

Trees are arbitrary JAX pytrees addressed by name::

    state = ElasticState(params=params, opt_state=opt_state)
    state.restore()
    for step in range(state.step, total_steps):
        params, opt_state, loss = train_step(...)
        state.params, state.opt_state = params, opt_state
        if (step + 1) % commit_every == 0:
            state.commit(step + 1)

``state.step`` is the step index training should resume from — 0 before
any commit, the committed ``step`` argument after.
"""

from __future__ import annotations

import os
import pickle
import re
from typing import Any, Dict, Optional

import jax

from .. import topology as _topo
from ..utils.checkpoint import (_fsync_dir, restore_checkpoint,
                                save_checkpoint)
from ..utils.env import checkpoint_keep
from ..utils.logging import get_logger

_log = get_logger("elastic.state")

ELASTIC_DIR_ENV = "HOROVOD_TPU_ELASTIC_DIR"
_LATEST = "LATEST"
_BACKENDS = ("pickle", "sharded")
_PKL_RE = re.compile(r"^(\d+)\.pkl$")


class ElasticState:
    """Named pytrees with commit/rollback/restore semantics."""

    def __init__(self, directory: Optional[str] = None,
                 backend: str = "pickle",
                 keep_last: Optional[int] = None, **trees: Any):
        if not trees:
            raise ValueError(
                "ElasticState needs at least one named tree, e.g. "
                "ElasticState(params=params, opt_state=opt_state)")
        if backend not in _BACKENDS:
            raise ValueError(
                f"unknown checkpoint backend {backend!r}; "
                f"choose from {_BACKENDS}")
        # All bookkeeping attrs go through object.__setattr__ so the
        # tree-name __setattr__ below stays unambiguous.
        object.__setattr__(self, "_dir",
                           directory or os.environ.get(ELASTIC_DIR_ENV))
        object.__setattr__(self, "_backend", backend)
        object.__setattr__(self, "_keep",
                           checkpoint_keep() if keep_last is None
                           else int(keep_last))
        object.__setattr__(self, "_engine", None)
        object.__setattr__(self, "_trees", dict(trees))
        object.__setattr__(self, "_committed", None)
        object.__setattr__(self, "step", 0)
        if backend == "sharded" and not self._dir:
            raise ValueError(
                "backend='sharded' needs a checkpoint directory on a "
                "shared filesystem (directory= or "
                f"{ELASTIC_DIR_ENV})")

    # ----------------------------------------------------- tree access

    def __getattr__(self, name: str) -> Any:
        trees = object.__getattribute__(self, "_trees")
        if name in trees:
            return trees[name]
        raise AttributeError(name)

    def __setattr__(self, name: str, value: Any) -> None:
        if name == "step":
            object.__setattr__(self, name, value)
            return
        self._trees[name] = value

    def tree_names(self):
        return tuple(self._trees)

    @property
    def backend(self) -> str:
        return self._backend

    # ------------------------------------------------------- internals

    def _latest_path(self) -> Optional[str]:
        return os.path.join(self._dir, _LATEST) if self._dir else None

    def _snapshot(self) -> Optional[Dict[str, Any]]:
        # Host copies: device buffers may be donated/overwritten by the
        # next jitted step, so the rollback copy must not alias them.
        # With multi-host-sharded trees (sharded backend) the global
        # values are not addressable from one process — rollback then
        # falls back to a disk restore instead of a memory copy.
        try:
            return {"step": int(self.step),
                    "trees": jax.device_get(self._trees)}
        except Exception:
            if self._backend == "sharded":
                return None
            raise

    def _is_rank0(self) -> bool:
        try:
            return _topo._get().process_index == 0
        except Exception:
            return True

    def _adopt(self, payload: Dict[str, Any]) -> None:
        object.__setattr__(self, "_trees", dict(payload["trees"]))
        object.__setattr__(self, "step", int(payload["step"]))

    def _get_engine(self):
        if self._engine is None:
            from ..checkpoint import CheckpointEngine
            object.__setattr__(
                self, "_engine",
                CheckpointEngine(self._dir, keep_last=self._keep))
        return self._engine

    # ------------------------------------------------------- contract

    def commit(self, step: Optional[int] = None,
               block: bool = False) -> "ElasticState":
        """Durably record the current trees as of ``step``.

        Ordering guarantee (both backends): the LATEST pointer is
        repointed only after the commit data is fully on disk, so a
        crash at any instant leaves LATEST naming a complete commit.

        Pickle backend: rank 0 serializes the whole state and the
        closing barrier means no rank runs past a commit its peers have
        not durably finished. Sharded backend: ``commit`` returns after
        the device→host snapshot; serialization, the cross-rank commit
        barrier and the LATEST flip run on the engine's background
        thread (joined by the next commit, ``wait()``, or
        ``block=True``) — until the flip, LATEST keeps naming the
        previous complete commit."""
        if step is not None:
            object.__setattr__(self, "step", int(step))
        snap = self._snapshot()
        object.__setattr__(self, "_committed", snap)
        from ..observability import flight_recorder as _flight
        _flight.recorder().note("checkpoint",
                                ("commit", int(self.step), self._backend))
        if self._backend == "sharded":
            self._get_engine().save(self._trees, self.step,
                                    extra={"elastic": True},
                                    block=block)
            return self
        if self._dir and self._is_rank0():
            os.makedirs(self._dir, exist_ok=True)
            save_checkpoint(snap, self._dir, step=self.step)
            tmp = self._latest_path() + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(self.step))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._latest_path())
            _fsync_dir(self._dir)
            self._gc_pickle()
        self._barrier(f"elastic.commit.{self.step}")
        return self

    def wait(self) -> "ElasticState":
        """Join an in-flight sharded commit (no-op for pickle)."""
        if self._engine is not None:
            self._engine.wait()
        return self

    def rollback(self) -> "ElasticState":
        """Restore trees from the last in-memory commit (no I/O). With
        no commit yet, this is a no-op on the initial trees. (Sharded
        backend with non-addressable trees: falls back to a disk
        restore of the committed step.)"""
        if self._committed is not None:
            self._adopt(self._committed)
        elif self._backend == "sharded" and \
                self._get_engine().latest_step() is not None:
            self.restore()
        return self

    def restore(self, step: Optional[int] = None) -> "ElasticState":
        """(Re)join path: adopt the last durable commit — or the initial
        trees — identically on every rank.

        Rank 0 resolves ``step`` (explicit, else LATEST, else none);
        with the pickle backend the broadcast built into
        ``restore_checkpoint`` ships the payload to all ranks, so a
        replacement worker with no shared filesystem still receives
        full state. The sharded backend instead has EVERY rank read
        from the shared directory through the engine (manifest
        resharding path) — only the resolved step is broadcast."""
        resolved = step
        if resolved is None and self._dir and self._is_rank0():
            if self._backend == "sharded":
                resolved = self._get_engine().latest_step()
            else:
                latest = self._latest_path()
                if latest and os.path.exists(latest):
                    with open(latest) as f:
                        resolved = int(f.read().strip())
        multi = self._process_count() > 1
        if multi:
            # Every rank must agree whether a commit exists before anyone
            # enters the conditional load (a split decision deadlocks the
            # broadcast). Rank 0 announces the resolved step. Explicit
            # names: cross-rank agreement must not depend on the engine's
            # per-process name counters lining up.
            from ..optimizer import broadcast_object
            resolved = broadcast_object(resolved, root_rank=0,
                                        name="elastic.restore.step")
        if resolved is None:
            if multi:
                from ..optimizer import broadcast_object
                self._adopt(broadcast_object(self._snapshot(), root_rank=0,
                                             name="elastic.restore.init"))
            object.__setattr__(self, "_committed", self._snapshot())
            return self
        if self._backend == "sharded":
            trees = self._get_engine().restore(step=int(resolved),
                                               template=self._trees)
            self._adopt({"step": int(resolved), "trees": trees})
        else:
            payload = restore_checkpoint(self._dir, step=int(resolved),
                                         broadcast=multi)
            self._adopt(payload)
        object.__setattr__(self, "_committed", self._snapshot())
        _log.info("restored elastic state at step %d", self.step)
        return self

    # -------------------------------------------------------- plumbing

    def _gc_pickle(self) -> None:
        """Keep-last-N retention for the pickle backend (rank 0, after
        the LATEST flip). Never deletes the step LATEST names."""
        if self._keep <= 0:
            return
        steps = []
        for name in os.listdir(self._dir):
            m = _PKL_RE.match(name)
            if m:
                steps.append(int(m.group(1)))
        steps.sort()
        keep = set(steps[-self._keep:])
        keep.add(int(self.step))
        for s in steps:
            if s not in keep:
                try:
                    os.remove(os.path.join(self._dir, f"{s}.pkl"))
                except OSError:
                    pass

    def _process_count(self) -> int:
        try:
            return _topo._get().process_count
        except Exception:
            return 1

    def _barrier(self, name: str) -> None:
        """Commit barrier: a tiny allreduce every rank must enter. Only
        meaningful (and only run) across processes."""
        if self._process_count() <= 1:
            return
        import jax.numpy as jnp

        from ..ops import collective as _coll
        _coll.allreduce(jnp.zeros((1,), jnp.float32), average=False,
                        name=name)
