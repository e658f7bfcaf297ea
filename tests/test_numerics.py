"""Numerics observability plane units (ISSUE 20, docs/numerics.md):
nonfinite sentinels, deferred in-graph step stats, cross-rank
fingerprint compare, the bitflip fault hook, the adaptation policy's
quantization-drift quality backoff, and checkpoint value fingerprints.
"""

import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.adaptation import faults as faults_mod
from horovod_tpu.adaptation.policy import (AdaptationConfig,
                                           AdaptationPolicy)
from horovod_tpu.checkpoint import CheckpointEngine, CorruptShardError
from horovod_tpu.checkpoint import engine as _ck_engine
from horovod_tpu.checkpoint import manifest as _manifest
from horovod_tpu.observability import numerics


@pytest.fixture(autouse=True)
def _init():
    hvd.init()
    yield


@pytest.fixture(autouse=True)
def _plane():
    """Arm the plane for the test, and leave no pending state behind."""
    numerics.set_enabled(True)
    numerics.reset_fingerprints()
    yield
    numerics.step_stats().flush()
    numerics.set_enabled(False)
    numerics.reset_fingerprints()


def _counter(family, key):
    snap = hvd.metrics_snapshot(prefix=family)
    return (snap.get(family) or {"values": {}})["values"].get(key, 0)


# ---------------------------------------------------------------------------
# Nonfinite sentinels
# ---------------------------------------------------------------------------

class TestCountNonfinite:
    def test_clean_buffer_is_zero(self):
        assert numerics.count_nonfinite(
            np.arange(1024, dtype=np.float32)) == 0

    def test_exact_count(self):
        a = np.zeros(64, np.float32)
        a[3] = np.nan
        a[10], a[11] = np.inf, -np.inf
        assert numerics.count_nonfinite(a) == 3

    def test_integer_dtype_is_finite_by_construction(self):
        assert numerics.count_nonfinite(np.arange(8)) == 0

    def test_overflowing_finite_buffer_is_zero(self):
        # The fast path (finite dot => all finite) overflows on large
        # finite values and must fall through to the exact count, not
        # report a false positive.
        a = np.full(16, 3e19, np.float32)     # square overflows f32
        assert not math.isfinite(float(np.dot(a, a)))
        assert numerics.count_nonfinite(a) == 0

    def test_multidim_buffer(self):
        a = np.ones((4, 4), np.float32)
        a[1, 2] = np.nan
        assert numerics.count_nonfinite(a) == 1


class TestScanPayload:
    def test_disabled_is_noop(self):
        numerics.set_enabled(False)
        a = np.full(8, np.nan, np.float32)
        assert numerics.scan_payload(a) == 0

    def test_poisoned_buffer_counts_and_alerts(self):
        key = 'source="collective"'
        fam = "hvdtpu_numerics_nonfinite_total"
        before = _counter(fam, key)
        a = np.ones(128, np.float32)
        a[17] = np.nan
        assert numerics.scan_payload(a) == 1
        assert _counter(fam, key) == before + 1
        # The same-step alert went through the health fan-out.
        akey = 'kind="nonfinite_rate",severity="critical"'
        assert _counter("hvdtpu_health_alerts_total", akey) >= 1


# ---------------------------------------------------------------------------
# Deferred step stats (the build_train_step aux channel's host sink)
# ---------------------------------------------------------------------------

class TestStepStats:
    def test_one_step_deferral(self):
        stats = numerics.StepStats()
        aux0 = {"grad_norm": np.float32(2.5),
                "update_ratio": np.float32(0.01),
                "nonfinite_by_rank": np.zeros(2, np.float32)}
        stats.note(0, np.float32(1.0), aux0)
        # Step 0 is pending: the gauges must not hold 2.5 yet unless a
        # later note materializes it.
        stats.note(1, np.float32(0.9), {"grad_norm": np.float32(3.5)})
        snap = hvd.metrics_snapshot(prefix="hvdtpu_numerics_")
        gn = snap["hvdtpu_numerics_grad_norm"]["values"][""]
        assert gn == pytest.approx(2.5)     # step 0, not step 1
        stats.flush()
        snap = hvd.metrics_snapshot(prefix="hvdtpu_numerics_")
        gn = snap["hvdtpu_numerics_grad_norm"]["values"][""]
        assert gn == pytest.approx(3.5)
        loss = snap["hvdtpu_numerics_loss"]["values"][""]
        assert loss == pytest.approx(0.9)

    def test_per_rank_nonfinite_vector_names_the_rank(self):
        fam = "hvdtpu_numerics_nonfinite_total"
        key = 'source="grad"'
        before = _counter(fam, key)
        stats = numerics.StepStats()
        stats.note(5, np.float32(1.0),
                   {"nonfinite_by_rank": np.array([0.0, 4.0, 0.0])})
        stats.flush()
        assert _counter(fam, key) == before + 4

    def test_nonfinite_loss_is_itself_a_sentinel(self):
        fam = "hvdtpu_numerics_nonfinite_total"
        key = 'source="loss"'
        before = _counter(fam, key)
        stats = numerics.StepStats()
        stats.note(9, np.float32(np.nan), {})
        stats.flush()
        assert _counter(fam, key) == before + 1


class TestInGraphNorms:
    """The aux channel of ``build_train_step`` under the sharded weight
    update: gradients and updates reach ``_numerics_aux`` as 1/dp
    shards, so 'dp' counts as an axis that shards them, and the norms
    are the one-device step's."""

    def _gauges(self, dp, opt):
        from horovod_tpu.models import transformer as tfm
        from horovod_tpu.parallel.mesh import create_mesh
        from horovod_tpu.parallel.train import build_train_step
        cfg = tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype=jnp.float32, remat=False)
        mesh = create_mesh(devices=jax.devices()[:dp], dp=dp)
        make, shard_p, shard_b = build_train_step(cfg, mesh, opt)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        state = opt.init(params)
        step, specs = make(params, state)
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        step(shard_p(params), state, shard_b(tok),
             shard_b(jnp.roll(tok, -1, axis=1)))
        numerics.step_stats().flush()
        snap = hvd.metrics_snapshot(prefix="hvdtpu_numerics_")
        return specs, tuple(
            snap[f"hvdtpu_numerics_{name}"]["values"][""]
            for name in ("grad_norm", "update_ratio"))

    @pytest.mark.parametrize("dp", [2, 4])
    def test_sharded_update_reports_the_one_device_norms(self, dp):
        import optax
        opt = optax.adamw(1e-3)
        specs, got = self._gauges(dp, opt)
        assert "dp" in specs[0].nu["layers"][0]["wi"]
        _, want = self._gauges(1, opt)
        assert got == pytest.approx(want, rel=1e-5)
        assert want[0] > 0 and want[1] > 0


# ---------------------------------------------------------------------------
# Fingerprints + divergence compare
# ---------------------------------------------------------------------------

class TestFingerprints:
    def test_identical_values_identical_digests(self):
        a = np.arange(4096, dtype=np.float32) / 3.0
        assert numerics.fingerprint_leaf("w", a) == \
            numerics.fingerprint_leaf("w", a.copy())

    def test_element0_bitflip_changes_crc(self):
        a = np.arange(1.0, 4097.0, dtype=np.float32)
        fp = numerics.fingerprint_leaf("w", a)
        flipped = numerics.flip_mantissa_bit(a, index=0, bit=5)
        fp2 = numerics.fingerprint_leaf("w", flipped)
        assert fp2[1] != fp[1]              # element 0 is always sampled

    def test_unsampled_bitflip_still_changes_norm(self):
        a = np.arange(1.0, 4097.0, dtype=np.float32)
        fp = numerics.fingerprint_leaf("w", a)
        # Whichever element the seeded subsample skips, the float64
        # norm covers the whole buffer.
        flipped = numerics.flip_mantissa_bit(a, index=1234, bit=12)
        assert numerics.fingerprint_leaf("w", flipped)[0] != fp[0]

    def test_majority_compare_names_leaf_and_rank(self):
        a = np.arange(256, dtype=np.float32)
        good = numerics.fingerprint_tree({"w": a, "b": a[:8]})
        bad = numerics.fingerprint_tree(
            {"w": numerics.flip_mantissa_bit(a, index=0, bit=3),
             "b": a[:8]})
        out = numerics.compare_fingerprints({0: good, 1: bad, 2: good})
        assert out == [("['w']", 1)]

    def test_record_fingerprint_fires_rank_divergence(self):
        a = np.arange(64, dtype=np.float32)
        good = numerics.fingerprint_tree({"w": a})
        bad = numerics.fingerprint_tree(
            {"w": numerics.flip_mantissa_bit(a, index=0, bit=3)})
        fam = "hvdtpu_numerics_fingerprints_total"
        before = _counter(fam, 'event="mismatch"')
        assert numerics.record_fingerprint(0, 10, good, 3) == []
        assert numerics.record_fingerprint(2, 10, good, 3) == []
        out = numerics.record_fingerprint(1, 10, bad, 3)
        assert out == [("['w']", 1)]
        assert _counter(fam, 'event="mismatch"') == before + 1
        akey = 'kind="rank_divergence",severity="critical"'
        assert _counter("hvdtpu_health_alerts_total", akey) >= 1

    def test_stale_step_evicted_and_still_compared(self):
        a = np.arange(64, dtype=np.float32)
        good = numerics.fingerprint_tree({"w": a})
        bad = numerics.fingerprint_tree(
            {"w": numerics.flip_mantissa_bit(a, index=0, bit=3)})
        # Step 0 never completes (rank 1 of 3 missing); newer steps pile
        # up until the pending window (4) evicts it — the partial pair
        # must still be compared so the divergence is not lost.
        assert numerics.record_fingerprint(0, 0, good, 3) == []
        assert numerics.record_fingerprint(2, 0, bad, 3) == []
        out = []
        for step in range(1, 6):
            out += numerics.record_fingerprint(0, step, good, 3)
        assert ("['w']", 2) in out


# ---------------------------------------------------------------------------
# bitflip_param fault hook
# ---------------------------------------------------------------------------

class TestMaybeBitflip:
    def test_armed_clause_flips_target_leaf_once(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_FAULT_SPEC",
                           "rank=0:bitflip_param=2:leaf=w")
        faults_mod.reset()
        try:
            before = _counter("hvdtpu_fault_injections_total",
                              'kind="bitflip"')
            tree = {"w": jnp.arange(1.0, 9.0), "b": jnp.zeros(4)}
            same = numerics.maybe_bitflip(tree, 0)
            assert same is tree              # not armed for this step
            out = numerics.maybe_bitflip(tree, 2)
            w = np.asarray(out["w"])
            assert w[0] != 1.0               # element 0 of 'w' flipped
            np.testing.assert_array_equal(np.asarray(out["b"]),
                                          np.zeros(4))
            assert _counter("hvdtpu_fault_injections_total",
                            'kind="bitflip"') == before + 1
            # Fires once: replaying the step is a no-op.
            assert numerics.maybe_bitflip(tree, 2) is tree
        finally:
            faults_mod.reset()

    def test_unarmed_is_identity(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_TPU_FAULT_SPEC", raising=False)
        faults_mod.reset()
        try:
            tree = {"w": jnp.ones(4)}
            assert numerics.maybe_bitflip(tree, 0) is tree
        finally:
            faults_mod.reset()


# ---------------------------------------------------------------------------
# Adaptation policy: quantization-drift quality backoff
# ---------------------------------------------------------------------------

class TestQualityBackoff:
    def _policy(self):
        cfg = AdaptationConfig(threshold_s=0.05, sustain_s=1.0,
                               cooldown_s=2.0, interval_s=0.0,
                               alert_hold_s=10.0)
        return AdaptationPolicy(cfg, allow_evict=False)

    def test_drift_unwinds_wire_tiers(self):
        p = self._policy()
        p.tier = 3                   # shrink + bf16 + int8x256 active
        p.note_alert("quantization_drift", rank=1, now=100.0)
        # Unwound until no wire tier is active; the structural shrink
        # tier survives (it does not change arithmetic).
        assert p.tier == 1
        assert p.config.tiers[:p.tier] == ("shrink",)

    def test_wire_reescalation_blocked_during_hold(self):
        p = self._policy()
        p.tier = 2                   # shrink + bf16
        p.note_alert("quantization_drift", rank=0, now=100.0)
        assert p.tier == 1
        # tiers[1] is bf16 — a wire rung; blocked while the hold is on.
        assert p._escalate(0, 1.0, now=105.0) is None
        assert p._escalate(0, 1.0, now=100.0 + 10.0 + 1.0) is not None

    def test_drift_does_not_add_escalation_pressure(self):
        p = self._policy()
        p.note_alert("quantization_drift", rank=0, now=50.0)
        # The usual alert path clamps lateness upward; drift must not.
        assert p.tier == 0
        assert p._alert_pressure(now=51.0) == {}


# ---------------------------------------------------------------------------
# Checkpoint value fingerprints
# ---------------------------------------------------------------------------

class TestCheckpointFingerprints:
    def test_manifest_carries_per_leaf_digests(self, tmp_path):
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        tree = {"w": np.arange(8.0), "b": np.zeros(3, np.float32)}
        eng.save(tree, 1, block=True)
        man = _manifest.read_manifest(d, 1)
        fps = man["fingerprints"]
        assert set(fps) == {"['w']", "['b']"}
        assert fps["['w']"] == numerics.fingerprint_leaf(
            "['w']", tree["w"])

    def test_verify_fingerprint_roundtrip_and_mismatch(self, tmp_path):
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        w = np.arange(8.0)
        eng.save({"w": w}, 1, block=True)
        man = _manifest.read_manifest(d, 1)
        _ck_engine.verify_fingerprint("['w']", w, man)   # clean: no raise
        with pytest.raises(CorruptShardError, match="fingerprint"):
            _ck_engine.verify_fingerprint(
                "['w']", numerics.flip_mantissa_bit(w, index=5, bit=2),
                man, where="step-1")

    def _corrupt_leaf_file(self, d, step, value):
        """Tamper the shard's VALUES and fix up the byte-crc sidecar —
        the corruption class only the value fingerprint can catch."""
        import glob as _glob
        import zlib
        sdir = os.path.join(d, f"step-{step}")
        path = sorted(_glob.glob(os.path.join(sdir, "*.npy")))[0]
        arr = np.load(path)
        arr = arr.copy()
        arr.flat[0] = value
        np.save(path, arr)
        man = _manifest.read_manifest(d, step)
        data = open(path, "rb").read()
        with open(path + ".crc32", "w") as f:
            f.write(f"{zlib.crc32(data) & 0xFFFFFFFF:08x} {len(data)}")
        for entry in man["leaves"]:
            for shard in entry["shards"]:
                if shard["file"] == os.path.basename(path):
                    shard["crc32"] = f"{zlib.crc32(data) & 0xFFFFFFFF:08x}"
                    shard["nbytes"] = len(data)
        with open(os.path.join(sdir, "manifest.json"), "wb") as f:
            f.write(_manifest.dumps(man))

    def test_restore_raises_on_value_corruption(self, tmp_path):
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save({"w": np.arange(8.0)}, 1, block=True)
        self._corrupt_leaf_file(d, 1, 99.0)
        with pytest.raises(CorruptShardError, match="fingerprint"):
            CheckpointEngine(d, barrier=lambda name: None).restore()

    def test_restore_falls_back_to_clean_commit(self, tmp_path):
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save({"w": np.arange(8.0)}, 1, block=True)
        eng.save({"w": np.arange(8.0) * 2}, 2, block=True)
        self._corrupt_leaf_file(d, 2, 99.0)
        restored = CheckpointEngine(d,
                                    barrier=lambda name: None).restore()
        np.testing.assert_allclose(restored["w"], np.arange(8.0))

    def test_restore_addressable_verifies_full_blocks(self, tmp_path):
        from horovod_tpu.checkpoint import tree_layout
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save({"w": np.arange(8.0)}, 1, block=True)
        self._corrupt_leaf_file(d, 1, 99.0)
        layouts = tree_layout({"w": np.arange(8.0)}, lambda dev: 0)
        with pytest.raises(CorruptShardError, match="fingerprint"):
            eng.restore_addressable(layouts, 1)

    def test_old_manifest_without_fingerprints_restores(self, tmp_path):
        d = str(tmp_path / "ck")
        eng = CheckpointEngine(d, barrier=lambda name: None)
        eng.save({"w": np.arange(8.0)}, 1, block=True)
        sdir = os.path.join(d, "step-1")
        man = _manifest.read_manifest(d, 1)
        del man["fingerprints"]                 # a pre-plane checkpoint
        with open(os.path.join(sdir, "manifest.json"), "wb") as f:
            f.write(_manifest.dumps(man))
        restored = CheckpointEngine(d,
                                    barrier=lambda name: None).restore()
        np.testing.assert_allclose(restored["w"], np.arange(8.0))
