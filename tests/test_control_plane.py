"""Coordinator protocol unit tests — in-process, no subprocesses.

Covers the rank-0 negotiation logic the reference implements in
IncrementTensorCount / ConstructMPIResponse / the fusion loop
(operations.cc:287-313, 321-523, 2149-2265): quorum counting, cross-rank
validation errors, fusion grouping under the byte threshold, ordered
sequence delivery, history pruning, and shutdown propagation.
"""

import threading

import pytest

from horovod_tpu.ops.control_plane import (AnnounceRequest, CoordinatorClient,
                                           CoordinatorService, FetchRequest)
from horovod_tpu.runner.secret import make_secret_key


@pytest.fixture
def svc():
    s = CoordinatorService(nproc=2, key=make_secret_key(),
                           fusion_threshold=1024)
    yield s
    s.shutdown()


def _client(svc, rank):
    return CoordinatorClient([("127.0.0.1", svc.port)], svc.key, rank)


def _req(name, op=0, dtype="float32", shape=(4,), root=-1):
    # Payload bytes are derived from shape × dtype by both planners (the
    # native wire carries no byte count — mpi_message.h:44-86).
    return {"name": name, "op": op, "dtype": dtype, "shape": shape,
            "root_rank": root}


class TestNegotiation:
    def test_quorum_then_group(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        c0.announce([_req("t")])
        # only one rank announced: no group yet
        assert c0.fetch(wait_s=0.05).groups == []
        c1.announce([_req("t")])
        groups = c0.fetch(wait_s=2.0).groups
        assert len(groups) == 1
        assert groups[0]["names"] == ["t"] and groups[0]["error"] == ""
        # the other rank sees the same sequence
        g1 = c1.fetch(wait_s=2.0).groups
        assert g1 == groups

    def test_fusion_same_dtype_under_threshold(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        reqs = [_req("a", shape=(100,)), _req("b", shape=(100,)),
                _req("c", shape=(100,))]  # 400 bytes each (float32)
        c0.announce(reqs)
        c1.announce(reqs)
        groups = c0.fetch(wait_s=2.0).groups
        # 400+400 fits in 1024; c overflows into a second group
        assert [g["names"] for g in groups] == [["a", "b"], ["c"]]

    def test_lookahead_skips_mismatched_dtype(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        reqs = [_req("f1", dtype="float32"), _req("i1", dtype="int32"),
                _req("f2", dtype="float32")]
        c0.announce(reqs)
        c1.announce(reqs)
        groups = c0.fetch(wait_s=2.0).groups
        assert [g["names"] for g in groups] == [["f1", "f2"], ["i1"]]

    def test_shape_mismatch_error(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        c0.announce([_req("t", shape=(3,))])
        c1.announce([_req("t", shape=(5,))])
        groups = c0.fetch(wait_s=2.0).groups
        assert len(groups) == 1
        assert "Mismatched allreduce tensor shapes" in groups[0]["error"]

    @pytest.mark.parametrize("native", [True, False],
                             ids=["native", "python"])
    def test_execution_attribute_mismatch_error(self, native):
        """(average, prescale, postscale, sharded) ride
        the wire's device slot as a fingerprint; ranks disagreeing get a
        Mismatched-execution-attributes error group instead of silently
        subdividing into divergent programs (operations.cc:480-497
        role)."""
        svc = CoordinatorService(nproc=2, key=make_secret_key(),
                                 fusion_threshold=1024, native=native)
        try:
            c0, c1 = _client(svc, 0), _client(svc, 1)
            r0 = dict(_req("t"), device=111)
            r1 = dict(_req("t"), device=222)
            c0.announce([r0])
            c1.announce([r1])
            groups = c0.fetch(wait_s=2.0).groups
            assert len(groups) == 1
            assert "Mismatched execution attributes" in groups[0]["error"]
        finally:
            svc.shutdown()

    def test_op_mismatch_error(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        c0.announce([_req("t", op=0)])
        c1.announce([_req("t", op=2, root=0)])
        groups = c0.fetch(wait_s=2.0).groups
        assert "Mismatched collective operations" in groups[0]["error"]

    def test_broadcast_root_mismatch(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        c0.announce([_req("t", op=2, root=0)])
        c1.announce([_req("t", op=2, root=1)])
        groups = c0.fetch(wait_s=2.0).groups
        assert "Mismatched root ranks" in groups[0]["error"]

    def test_allgather_sizes_per_rank(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        c0.announce([_req("g", op=1, shape=(2, 4))])
        c1.announce([_req("g", op=1, shape=(5, 4))])
        groups = c0.fetch(wait_s=2.0).groups
        assert groups[0]["error"] == ""
        assert groups[0]["sizes"]["g"] == [2, 5]

    def test_history_pruned_after_all_ack(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        for i in range(5):
            c0.announce([_req(f"t{i}", dtype="int32" if i % 2 else "float32",
                              shape=(500,))])
            c1.announce([_req(f"t{i}", dtype="int32" if i % 2 else "float32",
                              shape=(500,))])
            assert c0.fetch(wait_s=2.0).groups
            assert c1.fetch(wait_s=2.0).groups
        # both clients acked everything -> history collapses
        c0.fetch(wait_s=0.01)
        c1.fetch(wait_s=0.01)
        assert svc.history_len() <= 1
        assert svc.base_seq() >= 4

    def test_shutdown_propagates(self, svc):
        c0, c1 = _client(svc, 0), _client(svc, 1)
        c0.announce([], )  # no-op announce
        c1.announce_shutdown()
        resp = c0.fetch(wait_s=2.0)
        assert resp.shutdown

    def test_concurrent_announce_consistent_order(self, svc):
        """Both ranks see identical group order even with racing
        announcements from different threads."""
        c0, c1 = _client(svc, 0), _client(svc, 1)
        names = [f"x{i}" for i in range(20)]

        def announce(client, order):
            for n in order:
                client.announce([_req(n, shape=(150,))])  # 600 bytes

        t0 = threading.Thread(target=announce, args=(c0, names))
        t1 = threading.Thread(target=announce, args=(c1, list(reversed(
            names))))
        t0.start(); t1.start(); t0.join(); t1.join()
        g0, g1 = [], []
        while sum(len(g) for g in g0) < len(names):
            g0.extend(g["names"] for g in c0.fetch(wait_s=2.0).groups)
        while sum(len(g) for g in g1) < len(names):
            g1.extend(g["names"] for g in c1.fetch(wait_s=2.0).groups)
        assert g0 == g1
        assert sorted(n for g in g0 for n in g) == sorted(names)


class TestAnnounceIdempotency:
    def test_retried_announce_is_dropped(self, svc):
        """A retry of an announce whose response was lost (same
        announce_id re-delivered) must not resurrect a quorum-deleted
        entry with stale shape metadata (ADVICE r1, medium)."""
        c0, c1 = _client(svc, 0), _client(svc, 1)
        c0.announce([_req("t", op=1, shape=(3, 2))])
        c1.announce([_req("t", op=1, shape=(5, 2))])
        groups = c0.fetch(wait_s=2.0).groups
        assert len(groups) == 1
        assert groups[0]["sizes"]["t"] == [3, 5]
        # Simulate the retry: re-deliver rank 0's announce with the SAME
        # announce_id straight to the service handler (BasicClient would
        # do this after a lost response).
        svc._handle(AnnounceRequest(0, [_req("t", op=1, shape=(3, 2))],
                                    announce_id=c0._announce_seq), None)
        with svc._mu:
            assert "t" not in svc._table  # no stale one-rank entry
        # The next step's announce of the same tensor name must form a
        # FRESH quorum with the NEW shapes, not reuse last step's sizes.
        c0.announce([_req("t", op=1, shape=(7, 2))])
        c1.announce([_req("t", op=1, shape=(1, 2))])
        groups = c0.fetch(wait_s=2.0).groups
        assert len(groups) == 1
        assert groups[0]["sizes"]["t"] == [7, 1]


class TestStallDetection:
    @pytest.mark.parametrize("native", [True, False],
                             ids=["native", "python"])
    def test_missing_ranks_reported(self, native):
        """Coordinator names the missing ranks per stalled tensor
        (operations.cc:1644-1668) — with both the native controller and
        the Python fallback planner."""
        svc = CoordinatorService(nproc=2, key=make_secret_key(),
                                 fusion_threshold=1024, native=native,
                                 stall_warning_s=0.05)
        try:
            assert svc.native_active is native
            c0 = _client(svc, 0)
            c0.announce([_req("stuck.a"), _req("stuck.b")])
            import time as _t
            _t.sleep(0.1)
            svc._last_stall_check = 0.0
            lines = svc.check_stalls()
            assert len(lines) == 2
            name0, line0 = lines[0]
            assert name0 == "stuck.a"
            assert "stuck.a" in line0 and "missing ranks" in line0
            assert "1" in line0.split("missing ranks")[1]
        finally:
            svc.shutdown()

    def test_no_report_inside_window(self, svc):
        c0 = _client(svc, 0)
        c0.announce([_req("fresh")])
        svc.stall_warning_s = 60.0
        assert svc.check_stalls() == []


class TestBoundedPlanDefer:
    @pytest.mark.parametrize("native", [True, False],
                             ids=["native", "python"])
    def test_continuous_announces_cannot_starve_ready_work(self, native):
        """ADVICE r2: a fully-announced tensor must be planned even when
        the announce stream NEVER goes quiet (overlapping bursts from
        async submission keep refreshing last_announce). The bounded
        valve (PLAN_MAX_DEFER_FACTOR debounce windows, mirroring the
        client-side kDrainMaxDeferNs cap) fires regardless of quiet."""
        import time

        svc = CoordinatorService(nproc=2, key=make_secret_key(),
                                 fusion_threshold=1024, native=native)
        try:
            assert svc.native_active is native
            c0, c1 = _client(svc, 0), _client(svc, 1)
            c0.announce([_req("ready")])
            c1.announce([_req("ready")])
            # Noise: rank 0 announces a new PARTIAL tensor every ~1ms so
            # the 2ms quiet window never opens.
            got = []
            deadline = time.monotonic() + 2.0
            i = 0
            while time.monotonic() < deadline:
                c0.announce([_req(f"noise.{i}")])
                i += 1
                groups = c0.fetch(wait_s=0.003).groups
                if groups:
                    got = groups
                    break
            assert got, "ready tensor starved by continuous announces"
            assert got[0]["names"] == ["ready"]
            elapsed = 2.0 - (deadline - time.monotonic())
            assert elapsed < 1.0, f"valve fired too late: {elapsed:.3f}s"
        finally:
            svc.shutdown()


class TestClockSync:
    """Clock-alignment handshake (docs/tracing.md): NTP-style pings with
    round-trip halving over the coordinator channel."""

    def test_clock_sync_local_offset_near_zero(self, svc):
        c1 = _client(svc, 1)
        res = c1.clock_sync(probes=6)
        # Same host, same monotonic clock: the measured offset must be
        # tiny (bounded by scheduling noise) and the RTT positive.
        assert res["rtt_s"] > 0.0
        assert abs(res["offset_s"]) < 0.05
        assert res["probes"] == 6

    def test_min_rtt_sample_wins(self, svc, monkeypatch):
        """The kept offset is the one measured on the cleanest round
        trip, not the last or the mean."""
        from horovod_tpu.ops import control_plane as cp

        c1 = _client(svc, 1)
        rtts = iter([0.010, 0.002, 0.030])
        real_request = c1._client.request

        def jittered(req):
            import time as _t
            resp = real_request(req)
            if isinstance(req, cp.ClockProbeRequest):
                _t.sleep(next(rtts))   # inflate this probe's RTT
            return resp

        monkeypatch.setattr(c1._client, "request", jittered)
        res = c1.clock_sync(probes=3)
        # The winning sample is the middle one (min inflated RTT).
        assert 0.002 <= res["rtt_s"] < 0.010


class TestSkewTelemetry:
    """Live straggler metrics (docs/tracing.md): the coordinator turns
    its announce ticks into per-rank lateness histograms and a
    straggler gauge — visible on the Prometheus plane without traces."""

    def _lateness(self, snap, rank):
        fam = snap.get("hvdtpu_negotiate_lateness_seconds",
                       {"values": {}})["values"]
        return fam.get(f'rank="{rank}"')

    def test_late_rank_measured_and_elected(self, svc):
        import time

        from horovod_tpu.observability import metrics_snapshot

        before = self._lateness(metrics_snapshot(), 1)
        n0 = before["count"] if before else 0
        s0 = before["sum"] if before else 0.0
        c0, c1 = _client(svc, 0), _client(svc, 1)
        for step in range(3):
            c0.announce([_req(f"skew.{step}")])
            time.sleep(0.06)
            c1.announce([_req(f"skew.{step}")])
            assert c0.fetch(wait_s=2.0).groups
        snap = metrics_snapshot()
        h1 = self._lateness(snap, 1)
        assert h1["count"] - n0 == 3
        # Each quorum saw rank 1 ~60 ms behind rank 0.
        mean = (h1["sum"] - s0) / 3
        assert 0.03 <= mean <= 0.3
        assert snap["hvdtpu_straggler_rank"]["values"][""] == 1.0
        assert snap["hvdtpu_straggler_lateness_seconds"]["values"][""] \
            > 0.01

    def test_native_coordinator_decodes_payload_announces(self):
        """Skew telemetry must also work when announces arrive as
        pre-serialized RequestList bytes (native-engine workers)."""
        import time

        from horovod_tpu.observability import metrics_snapshot
        from horovod_tpu.ops import wire_format as wire

        svc = CoordinatorService(nproc=2, key=make_secret_key(),
                                 fusion_threshold=1024, native=True)
        if not svc.native_active:
            svc.shutdown()
            pytest.skip("native controller unavailable")
        try:
            before = self._lateness(metrics_snapshot(), 1)
            n0 = before["count"] if before else 0
            c0, c1 = _client(svc, 0), _client(svc, 1)
            payload = wire.encode_request_list(
                0, [dict(_req("native.skew"), device=0, nbytes=16)])
            c0.announce_bytes(payload)
            time.sleep(0.05)
            payload1 = wire.encode_request_list(
                1, [dict(_req("native.skew"), device=0, nbytes=16)])
            c1.announce_bytes(payload1)
            assert c0.fetch(wait_s=2.0).groups
            h1 = self._lateness(metrics_snapshot(), 1)
            assert h1 is not None and h1["count"] - n0 == 1
        finally:
            svc.shutdown()

    def test_stall_warning_includes_measured_lateness(self):
        """The upgraded stall report carries the per-rank lateness tail
        next to the missing-ranks line. (The horovod_tpu logger does not
        propagate to root — caplog misses it — so attach a handler
        directly.)"""
        import logging
        import time

        records = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record)

        handler = _Capture(level=logging.WARNING)
        logging.getLogger("horovod_tpu.control_plane").addHandler(handler)
        svc = CoordinatorService(nproc=2, key=make_secret_key(),
                                 fusion_threshold=1024, native=False,
                                 stall_warning_s=0.05)
        try:
            c0, c1 = _client(svc, 0), _client(svc, 1)
            # One completed tensor establishes rank 1's lateness...
            c0.announce([_req("warm")])
            time.sleep(0.08)
            c1.announce([_req("warm")])
            assert c0.fetch(wait_s=2.0).groups
            # ...then a stuck one triggers the stall report.
            c0.announce([_req("stuck")])
            time.sleep(0.1)
            svc._last_stall_check = 0.0
            lines = svc.check_stalls()
            assert lines and lines[0][0] == "stuck"
            text = "\n".join(r.getMessage() for r in records)
            assert "Recent negotiate lateness by rank" in text
            assert "rank 1:" in text
        finally:
            svc.shutdown()
            logging.getLogger(
                "horovod_tpu.control_plane").removeHandler(handler)

    def test_partial_entries_pruned(self):
        """Ticks of tensors that never reach quorum are dropped after
        the stall window — coordinator memory must not grow with a
        misbehaving job."""
        import time

        svc = CoordinatorService(nproc=2, key=make_secret_key(),
                                 fusion_threshold=1024, native=False,
                                 stall_warning_s=0.05)
        try:
            c0 = _client(svc, 0)
            for i in range(5):
                c0.announce([_req(f"orphan.{i}")])
            assert len(svc._skew._pending) == 5
            time.sleep(0.15)
            svc._last_stall_check = 0.0
            svc.check_stalls()
            assert len(svc._skew._pending) == 0
        finally:
            svc.shutdown()
