"""Telemetry history ring (docs/health.md): snapshot-delta reduction,
the crash-safe rotating writer, the merger's torn-tail tolerance, the
prefix-filtered snapshot satellite, and the one-telemetry-thread
consolidation regression test."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import horovod_tpu as hvd
from horovod_tpu.observability import history as _history
from horovod_tpu.observability import registry as _reg
from horovod_tpu.observability import ticker as _ticker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hist_snap(values):
    """Build a cumulative histogram snapshot from raw observations
    through a real registry Histogram (the exact shape snapshots
    carry)."""
    h = _reg.Histogram(_reg.LATENCY_BUCKETS)
    for v in values:
        h.observe(v)
    return h.snapshot()


class TestSeriesReduction:
    def test_counter_becomes_rate(self):
        prev = {"hvdtpu_x_total": {"type": "counter", "help": "",
                                   "values": {"": 10.0}}}
        cur = {"hvdtpu_x_total": {"type": "counter", "help": "",
                                  "values": {"": 30.0}}}
        s = _history.series_from_snapshots(prev, cur, dt_s=2.0)
        assert s["hvdtpu_x_total"] == pytest.approx(10.0)

    def test_counter_reset_uses_prometheus_rate_semantics(self):
        """A scraped replica restarted: cur < prev must not emit a
        negative rate — the new value IS the delta since the reset."""
        prev = {"hvdtpu_x_total": {"type": "counter", "help": "",
                                   "values": {"": 100.0}}}
        cur = {"hvdtpu_x_total": {"type": "counter", "help": "",
                                  "values": {"": 4.0}}}
        s = _history.series_from_snapshots(prev, cur, dt_s=2.0)
        assert s["hvdtpu_x_total"] == pytest.approx(2.0)

    def test_gauge_passes_through(self):
        cur = {"hvdtpu_g": {"type": "gauge", "help": "",
                            "values": {'device="host"': 42.0}}}
        s = _history.series_from_snapshots({}, cur, dt_s=5.0)
        assert s['hvdtpu_g{device="host"}'] == 42.0

    def test_histogram_windowed_mean_is_exact(self):
        """The |mean series must reflect ONLY the window's
        observations, exactly — a 20% shift inside one log bucket is
        invisible to bucket percentiles but not to the mean."""
        prev = {"hvdtpu_h": {"type": "histogram", "help": "",
                             "values": {"": _hist_snap([0.010] * 50)}}}
        cur_h = _hist_snap([0.010] * 50 + [0.012] * 10)
        cur = {"hvdtpu_h": {"type": "histogram", "help": "",
                            "values": {"": cur_h}}}
        s = _history.series_from_snapshots(prev, cur, dt_s=1.0)
        assert s["hvdtpu_h|mean"] == pytest.approx(0.012, rel=1e-6)
        assert s["hvdtpu_h|rate"] == pytest.approx(10.0)
        assert s["hvdtpu_h|p50"] > 0
        assert s["hvdtpu_h|p99"] >= s["hvdtpu_h|p50"]

    def test_histogram_empty_window_emits_nothing(self):
        snap = {"hvdtpu_h": {"type": "histogram", "help": "",
                             "values": {"": _hist_snap([0.01])}}}
        s = _history.series_from_snapshots(snap, snap, dt_s=1.0)
        assert not [k for k in s if k.startswith("hvdtpu_h")]

    def test_json_safe_inf_bounds_tolerated(self):
        """Scraped /metrics.json snapshots carry "+Inf" strings."""
        raw = _hist_snap([0.01] * 4)
        prev_h = {"buckets": [["+Inf" if le == float("inf") else le, c]
                              for le, c in raw["buckets"][:1]] +
                             raw["buckets"][1:],
                  "sum": 0.0, "count": 0}
        cur_h = dict(raw)
        cur_h["buckets"] = [["+Inf" if le == float("inf") else le, c]
                            for le, c in raw["buckets"]]
        s = _history.series_from_snapshots(
            {"h": {"type": "histogram", "values": {"": prev_h}}},
            {"h": {"type": "histogram", "values": {"": cur_h}}}, 1.0)
        assert s["h|mean"] == pytest.approx(0.01, rel=1e-6)


class TestPrefixSnapshot:
    def test_metrics_snapshot_prefix_filters(self):
        r = _reg.registry()
        r.counter("hvdtpu_histtest_a_total", "x").inc()
        r.gauge("hvdtpu_othertest_b", "x").set(1)
        snap = hvd.metrics_snapshot(prefix="hvdtpu_histtest_")
        assert "hvdtpu_histtest_a_total" in snap
        assert all(k.startswith("hvdtpu_histtest_") for k in snap)
        # tuple prefixes work too (str.startswith semantics)
        snap2 = hvd.metrics_snapshot(
            prefix=("hvdtpu_histtest_", "hvdtpu_othertest_"))
        assert "hvdtpu_othertest_b" in snap2

    def test_endpoint_prefix_query(self):
        import urllib.request

        from horovod_tpu.observability import MetricsServer
        _reg.registry().counter("hvdtpu_histtest_ep_total", "x").inc()
        srv = MetricsServer(0)
        try:
            url = (f"http://127.0.0.1:{srv.port}/metrics.json"
                   f"?prefix=hvdtpu_histtest_")
            with urllib.request.urlopen(url, timeout=10) as resp:
                snap = json.loads(resp.read())
            assert "hvdtpu_histtest_ep_total" in snap
            assert all(k.startswith("hvdtpu_histtest_") for k in snap)
        finally:
            srv.stop()


class TestWriterRotation:
    def test_header_then_samples(self, tmp_path):
        w = _history.HistoryWriter(str(tmp_path), "rank0",
                                   meta=lambda: {"rank": 0, "world": 2})
        w.append({"t_us": 1, "s": {"a": 1.0}})
        w.append({"t_us": 2, "s": {"a": 2.0}})
        w.close()
        lines = [json.loads(x) for x in
                 open(tmp_path / "history-rank0.jsonl")]
        assert lines[0]["history"] == _history.SCHEMA_VERSION
        assert lines[0]["rank"] == 0
        assert [x["t_us"] for x in lines[1:]] == [1, 2]

    def test_rotation_bounds_disk_and_keeps_headers(self, tmp_path):
        w = _history.HistoryWriter(str(tmp_path), "rank0",
                                   max_bytes=400, segments=2,
                                   meta=lambda: {"rank": 0})
        for i in range(60):
            w.append({"t_us": i, "s": {"a": float(i)}})
        w.close()
        live = tmp_path / "history-rank0.jsonl"
        segs = sorted(tmp_path.glob("history-rank0.jsonl.*"))
        assert live.exists()
        assert len(segs) == 2            # bounded: .1 and .2 only
        for p in [live] + segs:
            assert p.stat().st_size <= 400 + 200  # cap + one line slack
            first = json.loads(open(p).readline())
            assert first["history"] == _history.SCHEMA_VERSION
        # The merger folds segments oldest-first with no duplicates.
        hf = _history.load_label(str(live))
        ts = [s["t_us"] for s in hf.samples]
        assert ts == sorted(ts)
        assert len(ts) == len(set(ts))
        assert ts[-1] == 59              # newest survived
        assert ts[0] > 0                 # oldest rotated away

    def test_torn_tail_tolerated(self, tmp_path):
        w = _history.HistoryWriter(str(tmp_path), "rank0",
                                   meta=lambda: {"rank": 0})
        for i in range(5):
            w.append({"t_us": i, "s": {"a": float(i)}})
        w.close()
        path = tmp_path / "history-rank0.jsonl"
        with open(path, "a") as f:
            f.write('{"t_us": 5, "s": {"a": 5')   # torn mid-write
        hf = _history.load_label(str(path))
        assert [s["t_us"] for s in hf.samples] == [0, 1, 2, 3, 4]

    def test_load_history_expands_directories(self, tmp_path):
        for label in ("rank0", "rank1", "replica0"):
            w = _history.HistoryWriter(str(tmp_path), label,
                                       meta=lambda: {})
            w.append({"t_us": 1, "s": {"a": 1.0}})
            w.close()
        files = _history.load_history([str(tmp_path)])
        assert sorted(f.label for f in files) == ["rank0", "rank1",
                                                  "replica0"]

    def test_load_history_missing_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            _history.load_history([str(tmp_path)])

    def test_clock_alignment_shifts_onto_rank0(self, tmp_path):
        w0 = _history.HistoryWriter(str(tmp_path), "rank0",
                                    meta=lambda: {"rank": 0,
                                                  "offset_to_rank0_us":
                                                  0.0})
        w0.append({"t_us": 1000, "s": {"a": 1.0}})
        w0.close()
        w1 = _history.HistoryWriter(str(tmp_path), "rank1",
                                    meta=lambda: {"rank": 1,
                                                  "offset_to_rank0_us":
                                                  500.0})
        w1.append({"t_us": 600, "s": {"a": 1.0}})
        w1.close()
        files = {f.label: f for f in _history.load_history(
            [str(tmp_path)])}
        assert files["rank1"].samples[0]["t_aligned_us"] == 1100.0
        assert files["rank0"].samples[0]["t_aligned_us"] == 1000.0


class TestSampler:
    def test_tick_writes_delta_sample(self, tmp_path):
        r = _reg.registry()
        c = r.counter("hvdtpu_histtest_tick_total", "x").labels()
        s = _history.HistorySampler(
            str(tmp_path), "rank0", interval_s=60,
            prefix="hvdtpu_histtest_", meta=lambda: {"rank": 0})
        assert s.tick() is None          # first tick: nothing to delta
        c.inc(10)
        sample = s.tick()
        s.writer.close()
        assert sample is not None
        key = "hvdtpu_histtest_tick_total"
        assert sample["s"][key] > 0
        hf = _history.load_label(str(tmp_path / "history-rank0.jsonl"))
        assert len(hf.samples) == 1

    def test_set_enabled_gates_sampling(self, tmp_path):
        s = _history.HistorySampler(
            str(tmp_path), "rank0", interval_s=60,
            prefix="hvdtpu_histtest_", meta=lambda: {})
        _history.set_enabled(False)
        try:
            assert s.tick() is None
            assert s.tick() is None
        finally:
            _history.set_enabled(True)

    def test_source_failure_counts_error_not_raise(self, tmp_path):
        def bad_source():
            raise ConnectionError("replica down")

        s = _history.HistorySampler(
            str(tmp_path), "replica9", interval_s=60,
            source=bad_source, meta=lambda: {})
        before = _reg.registry().counter(
            "hvdtpu_history_sample_errors_total", "").labels().value
        assert s.tick() is None
        after = _reg.registry().counter(
            "hvdtpu_history_sample_errors_total", "").labels().value
        assert after == before + 1


class TestSingleTelemetryThread:
    """Satellite bugfix regression: the periodic JSON metrics exporter
    and the history sampler must share ONE timer thread — each used to
    (or would) spawn its own."""

    def test_json_writer_and_sampler_share_one_thread(self, tmp_path):
        from horovod_tpu.observability.export import _JsonWriter
        jw = _JsonWriter(str(tmp_path / "m.json"), interval_s=60)
        sampler = _history.HistorySampler(
            str(tmp_path), "rank0", interval_s=60,
            prefix="hvdtpu_histtest_", meta=lambda: {}).start()
        try:
            names = [t.name for t in threading.enumerate()]
            assert names.count(_ticker.THREAD_NAME) == 1
            # The old per-exporter thread name must be gone for good.
            assert "hvd-tpu-metrics-file" not in names
            tasks = set(_ticker.ticker().tasks().values())
            assert "metrics-file" in tasks
            assert "history-rank0" in tasks
        finally:
            sampler.stop()
            jw.stop()
        # Removal ran both final flushes: the JSON file exists even
        # though the 60 s interval never elapsed.
        assert (tmp_path / "m.json").exists()

    def test_ticker_runs_tasks_at_interval(self):
        t = _ticker.Ticker()
        hits = []
        h = t.add("t", 0.05, lambda: hits.append(time.monotonic()))
        time.sleep(0.35)
        t.remove(h)
        n = len(hits)
        assert n >= 3
        time.sleep(0.15)
        assert len(hits) == n            # removed tasks stop firing
        t.stop()

    def test_ticker_survives_raising_task(self):
        t = _ticker.Ticker()
        hits = []

        def boom():
            raise RuntimeError("bad exporter")

        t.add("boom", 0.05, boom)
        t.add("good", 0.05, lambda: hits.append(1))
        time.sleep(0.3)
        t.stop()
        assert len(hits) >= 2            # one bad task != all dead


class TestFleetHistory:
    """The supervisor samples each replica's scraped serving metrics
    into history-replica{i}.jsonl (docs/health.md#fleet) — replica
    trends survive replica death because the files belong to the
    supervisor."""

    def test_supervisor_samples_replicas_and_fleet(self, tmp_path,
                                                   monkeypatch):
        from horovod_tpu.observability import MetricsServer
        from horovod_tpu.serving.fleet import Fleet

        # A live in-process registry endpoint stands in for the
        # replica's metrics server. The registry is the PROCESS's, so
        # whatever the files that ran before this one on the same xdist
        # worker registered is in it too: the gauge read back is this
        # test's own, and the filter is held to the scrape's own prefix
        # union (a worker that had run test_slo.py holds hvdtpu_slo_*
        # families, which the scrape lets through by design).
        probe = _reg.registry().gauge(
            "hvdtpu_serving_history_test_probe", "x").labels()
        probe.set(3.0)
        srv = MetricsServer(0)
        monkeypatch.setenv("HOROVOD_TPU_HISTORY", str(tmp_path))
        monkeypatch.setenv("HOROVOD_TPU_HISTORY_INTERVAL", "3600")
        fleet = Fleet(1, [], host="127.0.0.1")

        class FakeProc:                      # alive, never polled out
            def poll(self):
                return None

        rep = fleet.replicas[0]
        rep.proc = FakeProc()
        rep.port = srv.port
        rep.metrics_port = srv.port
        try:
            fleet._maybe_start_history()
            labels = {s.writer.label for s in fleet._history}
            assert labels == {"replica0", "fleet"}
            for s in fleet._history:
                s.tick()                      # establish the baseline
            probe.set(5.0)
            for s in fleet._history:
                s.tick()
        finally:
            for s in fleet._history:
                s.stop()
            fleet._history = []
            srv.stop()
        hf = _history.load_label(
            str(tmp_path / "history-replica0.jsonl"))
        assert hf.meta["replica"] == 0
        assert hf.meta["role"] == "serving_replica"
        depths = [s["s"].get("hvdtpu_serving_history_test_probe")
                  for s in hf.samples]
        assert 5.0 in depths
        # Only the scrape's families crossed it (prefix= filter).
        from horovod_tpu.serving.fleet import _REPLICA_HISTORY_PREFIX
        scraped = tuple(_REPLICA_HISTORY_PREFIX.split(","))
        assert "hvdtpu_serving_" in scraped
        for s in hf.samples:
            assert all(k.startswith(scraped) for k in s["s"])
        assert (tmp_path / "history-fleet.jsonl").exists()

    def test_replica_sampler_skipped_in_replica_process(
            self, tmp_path, monkeypatch):
        """A fleet replica must not start its own rank-named sampler —
        the supervisor owns replica history (two replicas would both
        claim history-rank0.jsonl)."""
        monkeypatch.setenv("HOROVOD_TPU_HISTORY", str(tmp_path))
        monkeypatch.setenv("HOROVOD_TPU_REPLICA_ID", "1")
        assert _history.maybe_start_sampler() is None


_KILL_SCRIPT = r"""
import os, sys, time
from horovod_tpu.observability import history as _history
from horovod_tpu.observability import registry as _reg

d = sys.argv[1]
r = _reg.registry()
c = r.counter("hvdtpu_histtest_kill_total", "x").labels()
# Tiny segments: rotation happens every few samples.
w = _history.HistoryWriter(d, "rank0", max_bytes=500, segments=3,
                           meta=lambda: {"rank": 0})
s = _history.HistorySampler(d, "rank0", interval_s=60,
                            prefix="hvdtpu_histtest_", writer=w)
i = 0
while True:
    c.inc(7)
    s.tick()
    i += 1
    if i == 3:
        print("SAMPLING", flush=True)
    time.sleep(0.002)
"""


class TestCrashSafety:
    def test_sigkill_mid_write_leaves_valid_prefixes(self, tmp_path):
        """ACCEPTANCE (satellite): SIGKILL a sampling subprocess
        mid-write; every rotated segment must be a valid JSONL prefix
        and the merger must tolerate the torn tail."""
        proc = subprocess.Popen(
            [sys.executable, "-c", _KILL_SCRIPT, str(tmp_path)],
            stdout=subprocess.PIPE, text=True, cwd=ROOT)
        try:
            assert proc.stdout.readline().strip() == "SAMPLING"
            # Let it rotate a few segments, then kill at a random
            # moment relative to the write cadence.
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if list(tmp_path.glob("history-rank0.jsonl.*")):
                    break
                time.sleep(0.01)
            time.sleep(0.013)
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        live = tmp_path / "history-rank0.jsonl"
        segs = sorted(tmp_path.glob("history-rank0.jsonl.*"))
        assert segs, "subprocess never rotated a segment"
        # Every ROTATED segment is complete JSONL (rotation happens at
        # append boundaries); the live file may have one torn tail.
        for p in segs:
            for line in open(p):
                json.loads(line)
        lines = open(live).read().splitlines()
        for line in lines[:-1]:
            json.loads(line)
        # The merger reads everything, skipping any torn tail.
        hf = _history.load_label(str(live))
        assert hf is not None
        assert len(hf.samples) >= 3
        ts = [s["t_us"] for s in hf.samples]
        assert ts == sorted(ts)
        for s in hf.samples:
            assert "hvdtpu_histtest_kill_total" in s["s"]
