"""Sharded async checkpoint engine (docs/checkpoint.md, ISSUE 4).

Multi-host layouts are SIMULATED on the 8-device single-process CPU
mesh via the layout layer's ``process_fn`` (``lambda d: d.id // k``
acts like ``8/k`` hosts): one engine instance per simulated rank saves
only its shards, non-zero ranks first and rank 0 (the manifest writer)
last — the order the real commit barrier enforces. That is what lets
the acceptance matrix (save at world size 4, restore at 2 and 1, and
the reverse) run inside tier 1, with the true multi-process path
covered by the existing runner-based slow tier.
"""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import optax

import horovod_tpu as hvd
from horovod_tpu.checkpoint import (CheckpointEngine, CorruptShardError,
                                    read_latest, read_manifest,
                                    tree_layout)
from horovod_tpu.checkpoint import layout as _layout
from horovod_tpu.checkpoint import manifest as _manifest
from horovod_tpu.checkpoint import reader as _reader
from horovod_tpu.checkpoint.writer import AsyncWriter


@pytest.fixture(autouse=True)
def _init():
    hvd.init()
    yield


def _dp_mesh():
    return Mesh(np.asarray(jax.devices(), dtype=object).reshape(8),
                ("dp",))


def _proc_fn(world):
    """8 CPU devices grouped into ``world`` simulated hosts."""
    per = 8 // world
    return lambda d: d.id // per


def _sim_save(directory, tree, step, world, **kw):
    """Save ``tree`` as a simulated ``world``-process job: every rank's
    engine writes its shards; rank 0 last (it assembles the manifest
    after the shard barrier, which is a no-op in simulation)."""
    engines = [CheckpointEngine(directory, process_index=p,
                                process_count=world,
                                process_fn=_proc_fn(world),
                                barrier=lambda name: None, **kw)
               for p in range(world)]
    for p in list(range(1, world)) + [0]:
        engines[p].save(tree, step, block=True)
    return engines[0]


def _sharded_state(scale=1.0):
    """A ZeRO-ish mixed tree: one dp-sharded flat leaf, one replicated
    matrix, one scalar."""
    mesh = _dp_mesh()
    flat = jax.device_put(
        jnp.arange(64.0) * scale, NamedSharding(mesh, P("dp")))
    return {"moments": flat,
            "params": jnp.arange(12.0).reshape(3, 4) * scale,
            "count": np.int64(3)}


class TestLayout:
    def test_sharded_vs_replicated_leaves(self):
        tree = _sharded_state()
        layouts = tree_layout(tree, _proc_fn(4))
        lm = layouts["['moments']"]
        assert not lm.replicated
        assert len(lm.shards) == 8            # one block per device
        assert {s.process for s in lm.shards} == {0, 1, 2, 3}
        # contiguous cover of [0, 64)
        spans = sorted(s.index[0] for s in lm.shards)
        assert spans[0][0] == 0 and spans[-1][1] == 64
        for (a, b), (c, d) in zip(spans, spans[1:]):
            assert b == c
        lp = layouts["['params']"]
        assert lp.replicated and lp.shards[0].process == 0
        assert layouts["['count']"].shape == ()

    def test_replica_dedup_single_writer(self):
        """A dp-replicated jax array (P(None)) must be written once, by
        process 0 — never once per replica."""
        mesh = _dp_mesh()
        x = jax.device_put(jnp.ones((4, 2)), NamedSharding(mesh, P()))
        ll = _layout.leaf_layout(x, _proc_fn(4))
        assert ll.replicated and len(ll.shards) == 1
        assert ll.shards[0].process == 0

    def test_intersect_and_relative(self):
        a = ((0, 16),)
        b = ((8, 32),)
        assert _layout.intersect_spans(a, b) == ((8, 16),)
        assert _layout.intersect_spans(((0, 4),), ((4, 8),)) is None
        assert _layout.relative_slices(b, ((8, 16),)) == (slice(0, 8),)


class TestCommitProtocol:
    def test_manifest_schema_and_latest(self, tmp_path):
        d = str(tmp_path / "ck")
        tree = _sharded_state()
        _sim_save(d, tree, 7, world=4)
        assert read_latest(d) == 7
        man = read_manifest(d, 7)
        assert man["format"] == "horovod_tpu.checkpoint/1"
        assert man["step"] == 7 and man["process_count"] == 4
        keys = {e["key"] for e in man["leaves"]}
        assert keys == {"['moments']", "['params']", "['count']"}
        for entry in man["leaves"]:
            for shard in entry["shards"]:
                assert set(shard) == {"file", "index", "process",
                                      "crc32", "nbytes"}
                path = os.path.join(d, "step-7", shard["file"])
                assert os.path.getsize(path) == shard["nbytes"]
                # sidecar agrees with the manifest
                with open(path + ".crc32") as f:
                    crc, nbytes = f.read().split()
                assert crc == shard["crc32"]
                assert int(nbytes) == shard["nbytes"]

    def test_crash_between_shards_and_manifest(self, tmp_path,
                                               monkeypatch):
        """Shards of step 2 on disk but no manifest: LATEST stays on
        step 1 and restore returns step 1's data — a crash in the
        window between phase 1 and phase 2 loses nothing committed."""
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save({"w": np.arange(4.0)}, 1, block=True)

        def boom(self, handle, layouts, pcount, extra):
            raise RuntimeError("simulated crash before manifest")

        monkeypatch.setattr(CheckpointEngine, "_commit_rank0", boom)
        eng2 = CheckpointEngine(d, barrier=lambda name: None)
        eng2.save({"w": np.arange(4.0) * 2}, 2)
        with pytest.raises(RuntimeError, match="checkpoint write"):
            eng2.wait()
        monkeypatch.undo()
        # step-2 shards exist, but the commit never happened
        assert glob.glob(os.path.join(d, "step-2", "*.npy"))
        assert not os.path.exists(os.path.join(d, "step-2",
                                               "manifest.json"))
        assert read_latest(d) == 1
        eng3 = CheckpointEngine(d, barrier=lambda name: None)
        restored = eng3.restore()
        np.testing.assert_allclose(restored["w"], np.arange(4.0))

    def test_latest_flip_is_ordered(self, tmp_path):
        """LATEST only ever names a step whose manifest exists."""
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        for step in (1, 2, 3):
            eng.save({"w": np.full(8, float(step))}, step, block=True)
            latest = read_latest(d)
            assert latest == step
            assert os.path.exists(os.path.join(
                d, f"step-{latest}", "manifest.json"))

    def test_async_save_returns_before_commit(self, tmp_path):
        """save() must hand control back while the write is in flight:
        a gate inside the barrier holds the background commit open and
        the foreground still owns the handle."""
        d = str(tmp_path / "ck")
        gate = threading.Event()
        entered = threading.Event()

        def slow_barrier(name):
            if name.startswith("ckpt.shards."):
                entered.set()
                assert gate.wait(10)

        eng = CheckpointEngine(d, barrier=slow_barrier)
        handle = eng.save({"w": np.arange(32.0)}, 5)
        assert not handle.committed          # still in flight
        assert entered.wait(10)              # writer reached the barrier
        assert read_latest(d) is None        # not committed yet
        gate.set()
        eng.wait()
        assert handle.committed and read_latest(d) == 5

    def test_blocked_vs_total_seconds_reported(self, tmp_path):
        reg = hvd.metrics_snapshot()
        blocked0 = reg.get("hvdtpu_checkpoint_blocked_seconds_total",
                           {"values": {}})["values"].get("", 0.0)
        d = str(tmp_path / "ck")

        def slow_barrier(name):
            time.sleep(0.05)

        eng = CheckpointEngine(d, barrier=slow_barrier)
        t0 = time.perf_counter()
        eng.save({"w": np.arange(1024.0)}, 1)
        foreground = time.perf_counter() - t0
        eng.wait()
        snap = hvd.metrics_snapshot()
        blocked = snap["hvdtpu_checkpoint_blocked_seconds_total"][
            "values"][""] - blocked0
        # the loop never paid the two slow barriers (>= 0.1 s)
        assert foreground < 0.1
        assert blocked <= foreground + 0.01
        assert snap["hvdtpu_checkpoint_save_seconds"]["values"][""][
            "count"] >= 1

    def test_write_failure_surfaces_on_wait(self, tmp_path, monkeypatch):
        """A dead disk mid-write must fail the NEXT wait/save loudly —
        the loop cannot silently keep 'committing'."""
        from horovod_tpu.checkpoint import engine as _engine_mod

        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save({"w": np.arange(4.0)}, 1, block=True)

        def dead_disk(directory, filename, arr):
            raise IOError("No space left on device")

        monkeypatch.setattr(_engine_mod, "write_shard", dead_disk)
        eng.save({"w": np.arange(4.0) * 2}, 2)
        with pytest.raises(RuntimeError, match="checkpoint write"):
            eng.wait()
        monkeypatch.undo()
        assert read_latest(d) == 1           # commit 2 never happened


class TestReshardedRestore:
    def test_ws4_to_ws2_ws1_and_reverse(self, tmp_path):
        """The acceptance matrix: a world-size-4 commit restores
        bit-exactly into world sizes 2 and 1 through the manifest
        overlap path (and a ws-2 commit restores into 4 and 1)."""
        tree = _sharded_state(scale=3.0)
        ref = {k: np.asarray(jax.device_get(v))
               for k, v in tree.items()}

        for save_ws, restore_ws in [(4, 2), (4, 1), (2, 4), (2, 1)]:
            d = str(tmp_path / f"ck{save_ws}to{restore_ws}")
            eng = _sim_save(d, tree, 11, world=save_ws)
            if restore_ws == 1:
                restored = eng.restore(template=tree)
                for k in ref:
                    np.testing.assert_allclose(
                        np.asarray(restored[k]), ref[k], rtol=1e-6)
                continue
            # per-rank resharded loads: each simulated new rank reads
            # only its overlapping spans; blocks reassemble exactly.
            new_layouts = tree_layout(tree, _proc_fn(restore_ws))
            got = np.full(64, np.nan)
            for p in range(restore_ws):
                blocks = eng.restore_addressable(
                    new_layouts, process_index=p)
                for shard, arr in blocks["['moments']"]:
                    got[slice(*shard.index[0])] = arr
                # replicated leaves come back whole to every rank
                np.testing.assert_allclose(
                    blocks["['params']"][0][1], ref["params"],
                    rtol=1e-6)
            np.testing.assert_allclose(got, ref["moments"], rtol=1e-6)

    def test_resharded_reads_only_overlapping_files(self, tmp_path):
        """ws4 → ws2: the new rank 1 needs only the second half of the
        sharded leaf — the files for the first half must not be read
        (delete them and the restore must still succeed)."""
        tree = _sharded_state()
        d = str(tmp_path / "ck")
        eng = _sim_save(d, tree, 4, world=4)
        man = read_manifest(d, 4)
        entry = {e["key"]: e for e in man["leaves"]}["['moments']"]
        upper = _layout.Shard(index=((32, 64),), process=1)
        needed = {s["file"] for s in
                  _reader.shards_overlapping(entry, upper.index)}
        all_files = {s["file"] for s in entry["shards"]}
        assert needed < all_files and len(needed) == 4
        for fname in all_files - needed:     # lower-half shards gone
            os.remove(os.path.join(d, "step-4", fname))
        block = _reader.read_block(os.path.join(d, "step-4"), entry,
                                   upper.index)
        np.testing.assert_allclose(block, np.arange(32.0, 64.0))
        # ...and reading the DELETED half is a typed corruption error
        with pytest.raises(CorruptShardError, match="missing"):
            _reader.read_block(os.path.join(d, "step-4"), entry,
                               ((0, 32),))

    @pytest.mark.parametrize("tp,want", [
        (1, {"wi": P(None, "dp"), "wo": P(None, "dp"), "ln1": P("dp")}),
        (2, {"wi": P("dp", "tp"), "wo": P("tp", "dp"), "ln1": P("dp")}),
    ], ids=["dp8", "dp4-tp2"])
    def test_sharded_update_state_roundtrip(self, tmp_path, tp, want):
        """The state ``build_train_step`` hands back on a dp mesh (the
        default since PR 32): optax's own structure, every moment a
        global array with 'dp' on a dimension of the parameter's shape
        (the last, so a matrix is split by columns; beside 'tp' where
        the parameter is tensor-parallel, a leaf cut both ways).
        Committed at simulated ws 4, restored whole through the
        template, and by span at ws 2 — it needs nothing a dp-sharded
        leaf does not already have."""
        from horovod_tpu.models import transformer as tfm
        from horovod_tpu.parallel import create_mesh
        from horovod_tpu.parallel.train import build_train_step
        mesh = create_mesh(dp=8 // tp, tp=tp)
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
            max_seq=32, dtype=jnp.float32, remat=False,
            tp_axis="tp" if tp > 1 else None)
        opt = optax.adamw(1e-3, mu_dtype=jnp.bfloat16)
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        step, specs = make(params, jax.eval_shape(opt.init, params))
        for name, spec in want.items():
            assert specs[0].nu["layers"][0][name] == spec, name
        state = jax.jit(opt.init, out_shardings=jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)))(params)
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        _, state, _ = step(shard_p(params), state, shard_b(tok),
                           shard_b(jnp.roll(tok, -1, axis=1)))
        ref = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), state)
        assert np.any(ref[0].nu["embed"] != 0)

        eng = _sim_save(str(tmp_path / "sharded"), state, 7, world=4)
        restored = eng.restore(template=state)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), restored, ref)

        new_layouts = tree_layout(state, _proc_fn(2))
        by_key = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
                  jax.tree_util.tree_flatten_with_path(ref)[0]}
        sharded = [k for k, ll in new_layouts.items() if not ll.replicated]
        assert len(sharded) == 2 * len(jax.tree_util.tree_leaves(params))
        for key in sharded:
            ll = new_layouts[key]
            got = np.zeros(ll.shape, dtype=by_key[key].dtype)
            for p in range(2):
                for s, arr in eng.restore_addressable(
                        {key: ll}, process_index=p)[key]:
                    got[s.slices] = arr
            np.testing.assert_array_equal(got, by_key[key])

    def test_templateless_restore_dict_tree(self, tmp_path):
        d = str(tmp_path / "ck")
        tree = {"a": {"b": np.arange(6.0).reshape(2, 3)},
                "c": [np.ones(2), np.zeros(3)]}
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save(tree, 1, block=True)
        restored = eng.restore()
        np.testing.assert_allclose(restored["a"]["b"], tree["a"]["b"])
        np.testing.assert_allclose(restored["c"][0], 1.0)
        np.testing.assert_allclose(restored["c"][1], 0.0)

    def test_namedtuple_tree_needs_template(self, tmp_path):
        d = str(tmp_path / "ck")
        state = optax.scale_by_adam().init({"w": jnp.ones((8,))})
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save(state, 1, block=True)
        with pytest.raises(ValueError, match="template"):
            eng.restore()
        restored = eng.restore(template=state)
        assert type(restored).__name__ == "ScaleByAdamState"


class TestCorruptionAndFallback:
    def _commit(self, d, step, scale):
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save({"w": np.arange(16.0) * scale,
                  "b": np.ones(3) * scale}, step, block=True)
        return eng

    def test_corrupt_shard_falls_back_to_previous_commit(self, tmp_path):
        d = str(tmp_path / "ck")
        self._commit(d, 1, 1.0)
        eng = self._commit(d, 2, 2.0)
        target = sorted(glob.glob(os.path.join(d, "step-2",
                                               "*.npy")))[0]
        with open(target, "r+b") as f:
            f.seek(80)
            f.write(b"\x13\x37\x13\x37")
        restored = eng.restore()            # falls back to step 1
        np.testing.assert_allclose(restored["w"], np.arange(16.0))
        with pytest.raises(CorruptShardError):
            eng.restore(strict=True)
        snap = hvd.metrics_snapshot()
        assert snap["hvdtpu_checkpoint_corrupt_shards_total"][
            "values"][""] >= 1

    def test_truncated_and_missing_shard_are_typed(self, tmp_path):
        d = str(tmp_path / "ck")
        eng = self._commit(d, 1, 1.0)
        files = sorted(glob.glob(os.path.join(d, "step-1", "*.npy")))
        with open(files[0], "r+b") as f:
            f.truncate(10)
        with pytest.raises(CorruptShardError, match="size"):
            eng.restore(strict=True)
        os.remove(files[0])
        with pytest.raises(CorruptShardError, match="missing"):
            eng.restore(strict=True)


class TestRetentionGC:
    def test_keep_last_n_never_latest(self, tmp_path):
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, keep_last=3, barrier=lambda name: None)
        for step in range(1, 8):
            eng.save({"w": np.full(4, float(step))}, step, block=True)
        assert eng.steps() == [5, 6, 7]
        assert read_latest(d) == 7
        assert not os.path.exists(os.path.join(d, "step-1"))
        restored = eng.restore(step=5)
        np.testing.assert_allclose(restored["w"], 5.0)
        snap = hvd.metrics_snapshot()
        assert snap["hvdtpu_checkpoint_gc_steps_total"][
            "values"][""] >= 4

    def test_keep_zero_is_unlimited(self, tmp_path):
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, keep_last=0, barrier=lambda name: None)
        for step in range(1, 6):
            eng.save({"w": np.zeros(2)}, step, block=True)
        assert eng.steps() == [1, 2, 3, 4, 5]

    def test_env_knob(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_CHECKPOINT_KEEP", "2")
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        assert eng.keep_last == 2
        for step in range(1, 5):
            eng.save({"w": np.zeros(2)}, step, block=True)
        assert eng.steps() == [3, 4]


class TestAsyncWriter:
    def test_fifo_and_wait(self):
        w = AsyncWriter()
        out = []
        for i in range(5):
            w.submit(lambda i=i: out.append(i))
        w.wait()
        assert out == [0, 1, 2, 3, 4]
        w.close()

    def test_error_poisons_until_waited(self):
        w = AsyncWriter()
        w.submit(lambda: (_ for _ in ()).throw(IOError("disk gone")))
        with pytest.raises(RuntimeError, match="checkpoint write"):
            w.wait()
        w.submit(lambda: None)               # usable again after wait
        w.wait()
        w.close()


class TestMultiProcessSharded:
    @pytest.mark.slow
    def test_two_process_commit_and_restore(self, tmp_path):
        """REAL two-process sharded commit: each rank writes only its
        shard of a dp-sharded leaf, the commit barrier is the actual
        cross-process collective (entered from the background writer
        thread), rank 0 writes the manifest, and both ranks restore the
        full tree from the shared directory."""
        from horovod_tpu.runner.api import run

        def worker(d):
            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as P

            import horovod_tpu as hvd
            from horovod_tpu.checkpoint import read_manifest

            hvd.init()
            mesh = Mesh(np.asarray(jax.devices(),
                                   dtype=object).reshape(2), ("dp",))
            x = jax.device_put(jnp.arange(8.0),
                               NamedSharding(mesh, P("dp")))
            tree = {"x": x, "rep": jnp.full((3,), 2.0)}
            eng = hvd.CheckpointEngine(d)
            eng.save(tree, 7)
            eng.wait()
            man = read_manifest(d, 7)
            restored = eng.restore(template=tree)
            return {
                "rank": hvd.process_rank(),
                "latest": eng.latest_step(),
                "procs": sorted({s["process"]
                                 for e in man["leaves"]
                                 for s in e["shards"]}),
                "x": np.asarray(restored["x"]).tolist(),
                "rep": np.asarray(restored["rep"]).tolist(),
            }

        env = {"JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
        results = run(worker, args=(str(tmp_path / "mp"),), np=2,
                      extra_env=env, start_timeout=300)
        assert sorted(r["rank"] for r in results) == [0, 1]
        for r in results:
            assert r["latest"] == 7
            assert r["procs"] == [0, 1]     # both ranks wrote shards
            assert r["x"] == list(np.arange(8.0))
            assert r["rep"] == [2.0] * 3


@pytest.mark.slow
class TestCheckpointBenchReproducible:
    def test_bench_checkpoint_determinism_and_headline(self, tmp_path):
        """bench_engine.py --checkpoint regenerates BENCH_CHECKPOINT
        reproducibly (seeded byte/shard counts identical across runs)
        and supports the acceptance claim: the sharded-async save
        blocks the training loop for less time than the rank-0
        pickle."""
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        outs = []
        for i in range(2):
            out = tmp_path / f"bench{i}.json"
            subprocess.run(
                [sys.executable, os.path.join(root, "bench_engine.py"),
                 "--checkpoint", "--commits", "3", "--out", str(out)],
                check=True, capture_output=True, text=True, timeout=600,
                cwd=root)
            outs.append(json.loads(out.read_text()))
        a, b = outs
        assert a["logical_bytes"] == b["logical_bytes"]
        assert a["pickle"]["bytes_rank0"] == b["pickle"]["bytes_rank0"]
        assert a["sharded"]["bytes_per_rank"] == \
            b["sharded"]["bytes_per_rank"]
        assert a["sharded"]["shards_per_rank"] == \
            b["sharded"]["shards_per_rank"]
        # sharded state never funnels through one host: every rank
        # writes, and rank 0 writes well under the full pickle payload
        per_rank = {int(k): v
                    for k, v in a["sharded"]["bytes_per_rank"].items()}
        assert all(v > 0 for v in per_rank.values())
        assert per_rank[0] < a["pickle"]["bytes_rank0"] / 2
        # the headline delta (wall-clock, generous margin): async save
        # blocks the loop less than the serial rank-0 pickle
        for run in outs:
            assert run["blocked_ratio_sharded_vs_pickle"] < 1.0, run


class TestShimHooks:
    def test_torch_checkpoint_hook(self, tmp_path):
        torch = pytest.importorskip("torch")
        import horovod_tpu.torch as hvd_torch

        model = torch.nn.Linear(4, 2)
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        save = hvd_torch.checkpoint_hook(
            str(tmp_path / "pt"), model=model, optimizer=opt, every=2)
        assert save(1) is None               # off-cadence: no write
        handle = save(2, block=True)
        assert handle is not None and handle.committed
        restored = save.engine.restore()
        np.testing.assert_allclose(
            restored["model"]["weight"],
            model.state_dict()["weight"].detach().numpy())
        assert "optimizer" in restored
