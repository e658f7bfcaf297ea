"""Unified metrics & health telemetry (docs/metrics.md).

Acceptance coverage:
- metrics_snapshot() after a fused allreduce + allgather run contains
  per-op latency histograms (non-zero counts, monotone cumulative bucket
  sums) and wire-byte counters matching the engine's _Request accounting;
- the Prometheus endpoint serves the same values in valid text
  exposition format (parsed here, not eyeballed);
- the stall report surfaces as metrics in BOTH control planes: the
  coordinator (one rank withheld → a non-empty stalled-tensors gauge
  naming the missing rank, native and Python planners) and the engine;
- registry counters survive executor/engine resets (the ad-hoc-counter
  migration fix).
"""

import json
import math
import re
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import executor as _exec
from horovod_tpu.observability import (MetricsServer, StepTimer, enabled,
                                       get_registry, prometheus_text,
                                       set_enabled, write_json_snapshot)
from horovod_tpu.observability import registry as _reg
from horovod_tpu.ops import collective as _coll


def _hist(snap, name, labels):
    return snap[name]["values"][labels]


def _assert_monotone_histogram(h):
    cums = [c for _, c in h["buckets"]]
    assert cums == sorted(cums), "cumulative bucket sums must be monotone"
    assert h["buckets"][-1][0] == math.inf
    assert h["buckets"][-1][1] == h["count"]


class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        r = get_registry()
        c = r.counter("t_reg_counter", "test").labels(x="1")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = r.gauge("t_reg_gauge", "test").labels()
        g.set(7)
        g.inc(3)
        g.dec(1)
        assert g.value == 9.0
        h = r.histogram("t_reg_hist", "test",
                        buckets=[0.1, 1.0, 10.0]).labels()
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(55.55)
        assert [c for _, c in snap["buckets"]] == [1, 2, 3, 4]
        _assert_monotone_histogram(snap)

    def test_type_conflict_rejected(self):
        r = get_registry()
        r.counter("t_reg_conflict", "test")
        with pytest.raises(ValueError):
            r.gauge("t_reg_conflict", "test")

    def test_disabled_mode_is_noop(self):
        r = get_registry()
        c = r.counter("t_reg_disabled", "test").labels()
        assert enabled()
        set_enabled(False)
        try:
            c.inc(100)
            assert c.value == 0.0
        finally:
            set_enabled(True)
        c.inc(1)
        assert c.value == 1.0

    def test_snapshot_plain_dict(self):
        r = get_registry()
        r.counter("t_reg_snap", "help text").labels(a="b").inc(4)
        snap = _reg.snapshot()
        fam = snap["t_reg_snap"]
        assert fam["type"] == "counter"
        assert fam["help"] == "help text"
        assert fam["values"]['a="b"'] == 4.0


class TestEngineInstrumentation:
    def test_fused_allreduce_allgather_histograms_and_wire_bytes(self):
        """ACCEPTANCE: latency histograms for both ops with non-zero
        counts and monotone cumulative sums; wire-byte counter delta ==
        the engine's _Request accounting delta."""
        eng = _coll.engine()
        before = hvd.metrics_snapshot()

        def count_of(snap, op, phase):
            fam = snap.get("hvdtpu_op_phase_seconds", {"values": {}})
            key = f'op="{op}",phase="{phase}"'
            v = fam["values"].get(key)
            return v["count"] if v else 0

        def wire_total(snap):
            fam = snap.get("hvdtpu_wire_bytes_enqueued_total",
                           {"values": {}})
            return sum(fam["values"].values())

        wire_attr_before = eng.wire_bytes_enqueued
        with eng.burst():
            h1 = hvd.allreduce_async(jnp.ones((128,)), average=False,
                                     name="metrics.ar.a")
            h2 = hvd.allreduce_async(jnp.full((64,), 2.0), average=False,
                                     name="metrics.ar.b")
        hvd.synchronize(h1)
        hvd.synchronize(h2)
        hvd.allgather(jnp.ones((4, 4)), name="metrics.ag")

        after = hvd.metrics_snapshot()
        for op in ("allreduce", "allgather"):
            for phase in ("negotiate", "queue", "execute"):
                assert count_of(after, op, phase) > count_of(
                    before, op, phase), (op, phase)
            h = _hist(after, "hvdtpu_op_phase_seconds",
                      f'op="{op}",phase="execute"')
            _assert_monotone_histogram(h)
            assert h["sum"] > 0
        # Wire bytes: registry delta == attribute (_Request) delta.
        wire_delta = wire_total(after) - wire_total(before)
        assert wire_delta == eng.wire_bytes_enqueued - wire_attr_before
        assert wire_delta >= (128 + 64 + 16) * 4

    def test_wire_bytes_labeled_by_spec(self):
        from horovod_tpu.compression import Compression
        snap0 = hvd.metrics_snapshot()

        def spec_val(snap, spec):
            fam = snap.get("hvdtpu_wire_bytes_enqueued_total",
                           {"values": {}})
            return fam["values"].get(f'spec="{spec}"', 0.0)

        hvd.allreduce(jnp.ones((512,)), average=True,
                      name="metrics.wire.q",
                      compression=Compression.int8_blockwise)
        snap1 = hvd.metrics_snapshot()
        delta = spec_val(snap1, "int8x256") - spec_val(snap0, "int8x256")
        # 512 floats → 512 int8 payload + 2 blocks × 4B scales
        assert delta == 512 + 2 * 4

    def test_fused_group_size_observed(self):
        snap = hvd.metrics_snapshot()
        h = _hist(snap, "hvdtpu_fused_group_size", "")
        assert h["count"] >= 1
        _assert_monotone_histogram(h)

    def test_engine_stall_gauges(self):
        """Engine view: a request stuck past the warning window shows up
        in the stalled-tensor gauges; a clean check zeroes them."""
        eng = _coll.CollectiveEngine.__new__(_coll.CollectiveEngine)
        # Minimal fields _maybe_check_stalls touches.
        import threading
        eng._lock = threading.Lock()
        eng._metrics = _coll._EngineMetrics()
        eng.stall_warning_s = 0.01
        eng._last_stall_check = 0.0
        eng._coord_stall_lines = {}
        eng._mp = False
        h = _coll.Handle(1, "stuck.t")
        req = _coll._Request("stuck.t", _coll.ALLREDUCE,
                             jnp.ones((4,)), h)
        req.enqueued_at = time.monotonic() - 10.0
        eng._in_flight = {"stuck.t": req}
        eng._queue = []
        eng.failure_timeout_s = 0.0
        eng._maybe_check_stalls()
        snap = hvd.metrics_snapshot()
        assert snap["hvdtpu_engine_stalled_tensors"]["values"][""] == 1.0
        info = snap["hvdtpu_engine_stalled_tensor_seconds"]["values"]
        key = ('missing_ranks="none(single-process)",tensor="stuck.t"')
        assert key in info and info[key] >= 9.0
        # Episode resolves → gauges clear on the next check.
        eng._in_flight = {}
        eng._last_stall_check = 0.0
        eng._maybe_check_stalls()
        snap = hvd.metrics_snapshot()
        assert snap["hvdtpu_engine_stalled_tensors"]["values"][""] == 0.0
        assert snap["hvdtpu_engine_stalled_tensor_seconds"]["values"] == {}


class TestCoordinatorStallMetrics:
    @pytest.mark.parametrize("native", [True, False],
                             ids=["native", "python"])
    def test_withheld_rank_named_in_gauge(self, native):
        """ACCEPTANCE: coordinator mode with one rank withheld → a
        non-empty stalled-tensors gauge naming the missing rank, with
        both planners."""
        from horovod_tpu.ops.control_plane import (CoordinatorClient,
                                                   CoordinatorService)
        from horovod_tpu.runner.secret import make_secret_key
        svc = CoordinatorService(nproc=2, key=make_secret_key(),
                                 fusion_threshold=1024, native=native,
                                 stall_warning_s=0.05)
        try:
            c0 = CoordinatorClient([("127.0.0.1", svc.port)], svc.key, 0)
            c0.announce([{"name": "metrics.stuck", "op": 0,
                          "dtype": "float32", "shape": (4,),
                          "root_rank": -1}])       # rank 1 withheld
            time.sleep(0.1)
            svc._last_stall_check = 0.0
            lines = svc.check_stalls()
            assert lines
            snap = hvd.metrics_snapshot()
            count = snap["hvdtpu_coordinator_stalled_tensors"]["values"][""]
            assert count >= 1.0
            info = snap["hvdtpu_coordinator_stalled_tensor_seconds"][
                "values"]
            key = 'missing_ranks="1",tensor="metrics.stuck"'
            assert key in info, info
            assert info[key] > 0
        finally:
            svc.shutdown()

    def test_resolved_stall_clears_gauge(self):
        from horovod_tpu.ops.control_plane import (CoordinatorClient,
                                                   CoordinatorService)
        from horovod_tpu.runner.secret import make_secret_key
        svc = CoordinatorService(nproc=2, key=make_secret_key(),
                                 fusion_threshold=1024, native=False,
                                 stall_warning_s=0.05)
        try:
            c0 = CoordinatorClient([("127.0.0.1", svc.port)], svc.key, 0)
            c1 = CoordinatorClient([("127.0.0.1", svc.port)], svc.key, 1)
            c0.announce([{"name": "metrics.res", "op": 0,
                          "dtype": "float32", "shape": (4,),
                          "root_rank": -1}])
            time.sleep(0.1)
            svc._last_stall_check = 0.0
            assert svc.check_stalls()
            c1.announce([{"name": "metrics.res", "op": 0,
                          "dtype": "float32", "shape": (4,),
                          "root_rank": -1}])       # quorum → resolved
            svc._last_stall_check = 0.0
            svc.check_stalls()
            snap = hvd.metrics_snapshot()
            info = snap["hvdtpu_coordinator_stalled_tensor_seconds"][
                "values"]
            assert not any("metrics.res" in k for k in info)
        finally:
            svc.shutdown()


class TestExecutorMigration:
    def test_registry_counters_survive_executor_reset(self):
        """Satellite fix: reset_default_executor() used to silently
        discard counter state; the registry series accumulate across
        instances."""
        def totals():
            snap = hvd.metrics_snapshot()
            return tuple(
                sum(snap[n]["values"].values()) for n in
                ("hvdtpu_executor_cache_misses_total",
                 "hvdtpu_executor_cache_hits_total",
                 "hvdtpu_executor_device_puts_total"))

        before = totals()
        ex = _exec.CollectiveExecutor(mesh=hvd.mesh())
        xs = [jnp.full((32,), 3.0)]
        out = ex.allreduce_fused(xs)
        ex.allreduce_fused(out)
        inst = (ex.cache_misses, ex.cache_hits, ex.device_put_count)
        assert inst[0] >= 1 and inst[1] >= 1 and inst[2] >= 1
        _exec.reset_default_executor()   # must NOT lose registry totals
        after = totals()
        for b, a, i in zip(before, after, inst):
            assert a - b >= i

    def test_compile_seconds_recorded(self):
        """An executor program's trace, lowering and compile are JAX's
        own events, counted under the program's name (``fused``) by
        ``registry.watch_compiles``, without its first execution."""
        def fused_seconds():
            values = hvd.metrics_snapshot().get(
                "hvdtpu_compile_seconds_total", {}).get("values", {})
            return {k: v for k, v in values.items()
                    if k.endswith(',program="fused"')}

        before = fused_seconds()
        ex = _exec.CollectiveExecutor(mesh=hvd.mesh())
        ex.allreduce_fused([jnp.full((48,), 1.0)])
        after = fused_seconds()
        trace = 'phase="trace",program="fused"'
        assert after[trace] > before.get(trace, 0.0)
        assert sum(after.values()) > sum(before.values())


_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? ([-+0-9.eE]+|\+Inf|-Inf|NaN)$')


def _parse_prometheus(text):
    """Minimal text-exposition parser: validates every sample line and
    returns {series_name: {label_block: float}}."""
    out = {}
    types = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        assert m, f"invalid exposition line: {line!r}"
        name = line.split("{")[0].split(" ")[0]
        labels = m.group(1) or ""
        out.setdefault(name, {})[labels] = float(m.group(2))
    return out, types


class TestPrometheusExposition:
    def test_text_format_valid_and_consistent(self):
        hvd.allreduce(jnp.ones((16,)), name="metrics.prom.ar")
        snap = hvd.metrics_snapshot()
        series, types = _parse_prometheus(prometheus_text(snap))
        assert types["hvdtpu_op_phase_seconds"] == "histogram"
        assert types["hvdtpu_wire_bytes_enqueued_total"] == "counter"
        # Histogram invariants in the exposition itself: per label set,
        # _bucket cumulative counts are monotone in le and the +Inf
        # bucket equals _count.
        buckets = series["hvdtpu_op_phase_seconds_bucket"]
        counts = series["hvdtpu_op_phase_seconds_count"]
        by_labelset = {}
        for lab, v in buckets.items():
            m = re.search(r'le="([^"]*)"', lab)
            base = lab.replace("{", "").replace("}", "")
            base = ",".join(p for p in base.split(",")
                            if not p.startswith('le='))
            le = math.inf if m.group(1) == "+Inf" else float(m.group(1))
            by_labelset.setdefault(base, []).append((le, v))
        for base, pairs in by_labelset.items():
            pairs.sort()
            cums = [v for _, v in pairs]
            assert cums == sorted(cums), base
            assert pairs[-1][0] == math.inf
            assert counts[f"{{{base}}}"] == pairs[-1][1]
        # Counter value matches the snapshot it was rendered from.
        fam = snap["hvdtpu_wire_bytes_enqueued_total"]["values"]
        for label_key, val in fam.items():
            assert series["hvdtpu_wire_bytes_enqueued_total"][
                f"{{{label_key}}}"] == val

    def test_http_endpoint_serves_both_formats(self):
        """ACCEPTANCE: the endpoint serves valid exposition (parsed, not
        eyeballed) and the JSON snapshot."""
        hvd.allreduce(jnp.ones((8,)), name="metrics.http.ar")
        srv = MetricsServer(0)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(f"{base}/metrics",
                                        timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
                text = resp.read().decode()
            series, types = _parse_prometheus(text)
            assert "hvdtpu_op_phase_seconds_bucket" in series
            assert any(v > 0 for v in
                       series["hvdtpu_ops_total"].values())
            with urllib.request.urlopen(f"{base}/metrics.json",
                                        timeout=10) as resp:
                assert resp.status == 200
                snap = json.loads(resp.read().decode())
            assert "hvdtpu_op_phase_seconds" in snap
            with urllib.request.urlopen(f"{base}/nope", timeout=10) as r:
                pass
        except urllib.error.HTTPError as e:
            assert e.code == 404
        finally:
            srv.stop()


class TestJsonSnapshotFile:
    def test_atomic_write_and_strict_json(self, tmp_path):
        hvd.allreduce(jnp.ones((8,)), name="metrics.json.ar")
        path = tmp_path / "metrics.json"
        write_json_snapshot(str(path))
        snap = json.loads(path.read_text())   # strict JSON (no Infinity)
        h = snap["hvdtpu_op_phase_seconds"]["values"][
            'op="allreduce",phase="execute"']
        assert h["buckets"][-1][0] == "+Inf"
        assert h["buckets"][-1][1] == h["count"]

    def test_periodic_writer_env_driven(self, tmp_path, monkeypatch):
        from horovod_tpu.observability import export as _export
        path = tmp_path / "periodic.json"
        monkeypatch.setenv("HOROVOD_TPU_METRICS_FILE", str(path))
        monkeypatch.setenv("HOROVOD_TPU_METRICS_INTERVAL", "0.05")
        _export.stop_exporters()   # reset the idempotency latch
        _export.maybe_start_exporters()
        try:
            deadline = time.monotonic() + 10
            while not path.exists() and time.monotonic() < deadline:
                time.sleep(0.02)
            assert path.exists()
            json.loads(path.read_text())
        finally:
            _export.stop_exporters()


class TestStepTimer:
    def test_samples_per_sec_and_allreduce_share(self):
        timer = StepTimer("test_fw", batch_size=32)
        timer.begin()
        hvd.allreduce(jnp.ones((256,)), name="metrics.step.ar")
        timer.end()
        assert timer.last_step_s > 0
        assert timer.last_samples_per_s > 0
        # The step WAS an allreduce, so its execute time is a real
        # fraction of the step.
        assert 0.0 < timer.last_allreduce_share <= 1.0
        snap = hvd.metrics_snapshot()
        assert snap["hvdtpu_samples_per_second"]["values"][
            'framework="test_fw"'] > 0
        h = _hist(snap, "hvdtpu_step_seconds", 'framework="test_fw"')
        assert h["count"] == 1

    def test_context_manager_form(self):
        timer = StepTimer("test_fw2", batch_size=4)
        with timer:
            np.ones((8,)).sum()
        assert timer.last_step_s > 0


class TestStepAttribution:
    """Tentpole: per-step input/h2d/compute/collective attribution, MFU
    and HBM gauges (docs/metrics.md)."""

    def test_collective_share_counts_all_ops(self):
        """Satellite fix: the share must count allgather/broadcast
        execute seconds, not only op="allreduce" — proven by feeding
        the registry counter directly (what the engine does)."""
        from horovod_tpu.observability import registry as _reg
        fam = _reg.registry().counter(
            "hvdtpu_op_execute_seconds_total",
            "Cumulative wall seconds executing fused collective groups")
        timer = StepTimer("attr_allops")
        timer.begin()
        fam.labels(op="allgather").inc(0.5)
        fam.labels(op="broadcast").inc(0.25)
        time.sleep(0.01)
        timer.end()
        # 0.75 s of collective execute inside a ~10 ms step: clamped
        # share of 1.0 — under the old allreduce-only read this was 0.
        assert timer.last_collective_share == 1.0
        assert timer.last_allreduce_share == 1.0    # alias, same value
        snap = hvd.metrics_snapshot()
        vals = snap["hvdtpu_collective_step_share"]["values"]
        legacy = snap["hvdtpu_allreduce_step_share"]["values"]
        assert vals['framework="attr_allops"'] == 1.0
        assert legacy['framework="attr_allops"'] == 1.0
        assert "DEPRECATED" in snap["hvdtpu_allreduce_step_share"]["help"]

    def test_input_wait_attributed_to_input_phase(self):
        timer = StepTimer("attr_input")
        with timer:
            pass
        time.sleep(0.05)           # "the loader" between steps
        with timer:
            time.sleep(0.01)       # "compute"
        phases = timer.last_phases
        assert phases["input"] >= 0.04
        assert phases["compute"] >= 0.005
        snap = hvd.metrics_snapshot()
        share = snap["hvdtpu_step_phase_share"]["values"]
        key = 'framework="attr_input",phase="input"'
        assert share[key] > 0.5    # the cycle was input-dominated
        h = _hist(snap, "hvdtpu_step_phase_seconds",
                  'framework="attr_input",phase="input"')
        assert h["count"] == 2

    def test_h2d_mark(self):
        timer = StepTimer("attr_h2d")
        with timer:
            time.sleep(0.02)
            timer.mark_h2d_done()
            time.sleep(0.005)
        assert timer.last_phases["h2d"] >= 0.015
        assert timer.last_phases["compute"] < timer.last_phases["h2d"]

    def test_mfu_and_flops_gauges(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_PEAK_FLOPS", "1e12")
        timer = StepTimer("attr_mfu", flops_per_step=1e9)
        with timer:
            time.sleep(0.01)
        snap = hvd.metrics_snapshot()
        flops = snap["hvdtpu_model_flops_per_second"]["values"][
            'framework="attr_mfu"']
        assert flops > 0
        mfu = snap["hvdtpu_mfu"]["values"]['framework="attr_mfu"']
        assert mfu == pytest.approx(flops / 1e12)
        assert 0 < mfu < 1

    def test_mfu_not_exported_without_peak(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_TPU_PEAK_FLOPS", raising=False)
        timer = StepTimer("attr_nopeak", flops_per_step=1e9)
        with timer:
            pass
        snap = hvd.metrics_snapshot()
        # flops rate always exported; MFU needs a peak (none on CPU).
        assert snap["hvdtpu_model_flops_per_second"]["values"][
            'framework="attr_nopeak"'] > 0
        assert 'framework="attr_nopeak"' not in \
            snap.get("hvdtpu_mfu", {}).get("values", {})

    def test_hbm_gauges_present(self):
        """Acceptance: HBM gauges appear in metrics_snapshot() — on the
        CPU test backend via the host-RSS fallback."""
        timer = StepTimer("attr_hbm")
        with timer:
            pass
        snap = hvd.metrics_snapshot()
        live = snap["hvdtpu_hbm_bytes_in_use"]["values"]
        peak = snap["hvdtpu_hbm_peak_bytes"]["values"]
        assert any(v > 0 for v in live.values())
        assert any(v > 0 for v in peak.values())

    def test_flops_of_lowered(self):
        import jax

        from horovod_tpu.observability import flops_of_lowered
        f = jax.jit(lambda x: x @ x)
        lowered = f.lower(jnp.ones((64, 64)))
        flops = flops_of_lowered(lowered.compile())
        # CPU backends may or may not expose a cost analysis; when they
        # do, a 64x64 matmul is ~2*64^3 flops.
        if flops is not None:
            assert flops >= 64 * 64 * 64

    def test_step_spans_emitted_into_live_timeline(self, tmp_path):
        """With the engine's Python timeline active, end() emits STEP_*
        spans the trace report turns into the bound verdict."""
        from horovod_tpu.ops import collective as _coll
        from horovod_tpu.ops.timeline_py import PyTimeline
        eng = _coll.engine()
        old_tl = eng.timeline
        tl = PyTimeline(str(tmp_path / "steps.json"))
        eng.timeline = tl
        try:
            timer = StepTimer("attr_spans")
            with timer:
                time.sleep(0.002)
            time.sleep(0.02)   # input gap
            with timer:
                time.sleep(0.002)
        finally:
            eng.timeline = old_tl
            tl.close()
        events = json.loads((tmp_path / "steps.json").read_text())
        names = [e.get("name") for e in events if e.get("ph") == "X"]
        assert "STEP_COMPUTE" in names
        assert "STEP_INPUT" in names


class TestElasticMetrics:
    def test_health_line_and_gauges(self):
        """The driver's structured health line renders from the registry
        (world size, failures, last re-rendezvous ms)."""
        import logging
        from horovod_tpu.elastic.driver import _ElasticMetrics, _log
        m = _ElasticMetrics()
        m.world_size.set(4)
        m.generation.set(2)
        m.failure("sigkill")
        m.last_rendezvous_ms.set(123.0)
        records = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        cap = _Capture(level=logging.INFO)
        old_level = _log.level
        _log.addHandler(cap)
        _log.setLevel(logging.INFO)
        try:
            m.health_line("shrink", 4, 2, "a:2,b:2")
        finally:
            _log.removeHandler(cap)
            _log.setLevel(old_level)
        text = " ".join(records)
        assert "elastic_health" in text
        assert "event=shrink" in text and "world_size=4" in text
        assert "last_rendezvous_ms=123" in text
        snap = hvd.metrics_snapshot()
        fails = snap["hvdtpu_elastic_worker_failures_total"]["values"]
        assert fails['kind="sigkill"'] >= 1
        assert fails['kind="all"'] >= 1


class TestPerRankMetricsPort:
    """Satellite: HOROVOD_TPU_METRICS_PORT {rank}/base+rank forms make
    every rank scrapeable in multi-process mode (docs/metrics.md)."""

    def test_plain_port_rank0_only(self, monkeypatch):
        from horovod_tpu.utils import env as _env
        monkeypatch.setenv("HOROVOD_TPU_METRICS_PORT", "9091")
        assert _env.metrics_port(0) == 9091
        assert _env.metrics_port(3) == 9091
        assert _env.metrics_port_per_rank() is False

    def test_placeholder_form(self, monkeypatch):
        from horovod_tpu.utils import env as _env
        monkeypatch.setenv("HOROVOD_TPU_METRICS_PORT", "909{rank}")
        assert _env.metrics_port(0) == 9090
        assert _env.metrics_port(7) == 9097
        assert _env.metrics_port_per_rank() is True

    def test_base_plus_rank_form(self, monkeypatch):
        from horovod_tpu.utils import env as _env
        monkeypatch.setenv("HOROVOD_TPU_METRICS_PORT", "9091+rank")
        assert _env.metrics_port(0) == 9091
        assert _env.metrics_port(5) == 9096
        assert _env.metrics_port_per_rank() is True

    def test_two_ranks_bind_distinct_ports(self, monkeypatch):
        """Two ranks' resolved ports bind two live endpoints, each
        serving the exposition."""
        import socket

        from horovod_tpu.utils import env as _env

        hvd.allreduce(jnp.ones((4,)), name="metrics.perrank.ar")
        for _ in range(5):   # free-port race: retry with a fresh base
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
            s.close()
            monkeypatch.setenv("HOROVOD_TPU_METRICS_PORT",
                               f"{base}+rank")
            ports = [_env.metrics_port(r) for r in (0, 1)]
            assert ports == [base, base + 1]
            try:
                servers = [MetricsServer(p) for p in ports]
            except OSError:
                continue
            try:
                assert sorted(s.port for s in servers) == ports
                for p in ports:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{p}/metrics",
                            timeout=10) as resp:
                        assert b"hvdtpu_ops_total" in resp.read()
            finally:
                for srv in servers:
                    srv.stop()
            return
        pytest.skip("could not find two adjacent free ports")


class TestJsonPercentiles:
    """Satellite: the endpoint's JSON view carries p50/p90/p99 estimated
    from the log buckets (shared estimator with the trace report)."""

    def test_metrics_json_includes_percentiles(self):
        from horovod_tpu.observability import with_percentiles
        from horovod_tpu.observability.export import json_safe_snapshot

        hvd.allreduce(jnp.ones((16,)), name="metrics.pct.ar")
        snap = with_percentiles(json_safe_snapshot())
        fam = snap["hvdtpu_op_phase_seconds"]["values"]
        key = 'op="allreduce",phase="execute"'
        assert key in fam
        pct = fam[key]["percentiles"]
        assert set(pct) == {"p50", "p90", "p99"}
        assert 0 < pct["p50"] <= pct["p90"] <= pct["p99"]

    def test_http_json_view_serves_percentiles(self):
        hvd.allreduce(jnp.ones((16,)), name="metrics.pct.http")
        srv = MetricsServer(0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics.json",
                    timeout=10) as resp:
                snap = json.loads(resp.read().decode())
            hists = [v for fam in snap.values()
                     if fam["type"] == "histogram"
                     for v in fam["values"].values() if v["count"]]
            assert hists
            assert all("percentiles" in v for v in hists)
        finally:
            srv.stop()


class TestSpan:
    """``registry.span``: one stretch of host work on the profiler's
    clock (docs/tracing.md#names)."""

    def test_yields_its_seconds_after_exit(self):
        with _reg.span("span_test/outer") as outer:
            assert outer.seconds == 0.0         # still open
            with _reg.span("span_test/inner") as inner:
                time.sleep(0.002)
        assert outer.name == "span_test/outer"
        assert 0.002 <= inner.seconds <= outer.seconds

    def test_feeds_a_given_counter_and_nothing_without_one(self):
        before = set(hvd.metrics_snapshot())
        with _reg.span("span_test/uncounted"):
            pass
        assert set(hvd.metrics_snapshot()) == before
        total = get_registry().counter(
            "hvdtpu_test_span_seconds_total", "x").labels()
        with _reg.span("span_test/counted", total) as s:
            time.sleep(0.002)
        assert total.value == pytest.approx(s.seconds) and s.seconds > 0
        with pytest.raises(RuntimeError):
            with _reg.span("span_test/raises", total) as failed:
                raise RuntimeError("inside")
        assert failed.seconds > 0               # closed by the error
        assert total.value == pytest.approx(s.seconds + failed.seconds)

    def test_two_threads_time_apart(self):
        """The prefetcher's producer and its consumer hold spans open
        at once; each times its own stretch and feeds its own counter."""
        import threading
        seen = {}
        gate = threading.Barrier(2, timeout=10)

        def work(tag, pause):
            total = get_registry().counter(
                f"hvdtpu_test_span_thread_{tag}_seconds_total",
                "x").labels()
            with _reg.span(f"span_test/thread/{tag}", total) as s:
                gate.wait()          # both spans are open now
                time.sleep(pause)
            seen[tag] = (s.seconds, total.value)

        threads = [threading.Thread(target=work, args=a)
                   for a in (("a", 0.001), ("b", 0.02))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        assert seen["a"][0] == seen["a"][1] >= 0.001
        assert seen["b"][0] == seen["b"][1] >= 0.02

    def test_spans_reach_the_profilers_trace(self, tmp_path):
        """Under ``jax.profiler.trace`` a span is an event of the host
        plane, named ``hvd/<name>``."""
        import jax
        with jax.profiler.trace(str(tmp_path)):
            with _reg.span("span_test/traced"):
                time.sleep(0.001)
        (path,) = tmp_path.rglob("*.xplane.pb")
        assert b"hvd/span_test/traced" in path.read_bytes()

    def test_registry_and_span_work_without_jax(self, monkeypatch):
        """The registry imports no jax of its own, and where the
        profiler cannot be imported a span still times and counts."""
        import ast
        import sys
        tree = ast.parse(open(_reg.__file__).read())
        top = [n for n in tree.body
               if isinstance(n, (ast.Import, ast.ImportFrom))]
        assert not [n for n in top if "jax" in ast.dump(n)]
        monkeypatch.setattr(_reg, "_trace_annotation", None)
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        total = get_registry().counter(
            "hvdtpu_test_span_nojax_seconds_total", "x").labels()
        with _reg.span("span_test/nojax", total) as s:
            pass
        assert _reg._trace_annotation is False
        assert total.value == s.seconds >= 0


def test_the_programs_peak_table_agrees_with_the_benchmarks():
    """``observability/step_metrics.py`` holds the program's one table
    (``bench.py`` reads it); ``benchmark/peaks.py`` keeps its own copy by
    design. On every device kind both know, they agree."""
    import bench
    from benchmark import peaks
    from horovod_tpu.observability import step_metrics
    both = {kind: row for kind, row in peaks.PEAKS.items()
            if step_metrics.peak_flops_of_kind(kind) is not None}
    assert both, "no device kind in common: a table was renamed"
    for kind, row in both.items():
        assert step_metrics.peak_flops_of_kind(kind) == \
            row["bf16_flops_per_s"]
        device = type("Device", (), {"device_kind": kind})()
        assert bench.peak_tflops(device) * 1e12 == row["bf16_flops_per_s"]
    assert step_metrics.peak_flops_of_kind("cpu") is None
    with pytest.raises(ValueError, match="no published peak"):
        bench.peak_tflops(type("Device", (), {"device_kind": "cpu"})())
