"""Native runtime core tests — exercise the C++ control plane directly via
the ctypes surface: wire protocol round-trips (mpi_message parity, N2),
ConstructResponse mismatch diagnostics (operations.cc:321-523), fp16
software conversion (half.{h,cc}, N8), and knob plumbing."""

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.runtime import native

# Wire enums (runtime/src/common.h / message.h).
ALLREDUCE, ALLGATHER, BROADCAST, ERROR = 0, 1, 2, 3
F32 = 7


@pytest.fixture(scope="module")
def core():
    c = native.load(required=True)
    assert c is not None
    return c


class TestWire:
    def test_request_list_roundtrip(self, core):
        """Serialize → parse → serialize must be byte-identical
        (mpi_message.cc:134-230 SerializeToString/ParseFromBytes parity)."""
        reqs = b"".join([
            core.wire_make_request(r, ALLREDUCE, F32, f"grad/layer{r}",
                                   -1, -1, [17, 17]) for r in range(4)])
        # Wrap into a RequestList by hand: shutdown=0, count=4.
        import struct
        payload = struct.pack("<ii", 0, 4) + reqs
        out = core.wire_roundtrip_request_list(payload)
        assert out == payload

    def test_request_fields_survive(self, core):
        a = core.wire_make_request(3, BROADCAST, F32, "weights", 2, 5,
                                   [8, 4, 2])
        b = core.wire_make_request(3, BROADCAST, F32, "weights", 2, 5,
                                   [8, 4, 2])
        assert a == b
        c = core.wire_make_request(3, BROADCAST, F32, "weights", 1, 5,
                                   [8, 4, 2])
        assert a != c


class TestNegotiation:
    def _reqs(self, core, shapes, op=ALLREDUCE, dtypes=None, roots=None):
        dtypes = dtypes or [F32] * len(shapes)
        roots = roots or [-1] * len(shapes)
        ops = op if isinstance(op, list) else [op] * len(shapes)
        return b"".join([
            core.wire_make_request(r, ops[r], dtypes[r], "t", roots[r], -1,
                                   list(shapes[r]))
            for r in range(len(shapes))])

    def test_consistent_allreduce_ok(self, core):
        data = self._reqs(core, [[17, 17]] * 4)
        rtype, err, _ = core.negotiate(data, 4, 4)
        assert rtype == ALLREDUCE and err == ""

    def test_mismatched_shape_diagnosed(self, core):
        """Shape disagreement produces the reference's diagnostic instead of
        a deadlock (operations.cc:378-396; test_tensorflow.py:265-333)."""
        data = self._reqs(core, [[17, 17], [17, 17], [17, 18], [17, 17]])
        rtype, err, _ = core.negotiate(data, 4, 4)
        assert rtype == ERROR
        assert "Mismatched allreduce tensor shapes" in err

    def test_mismatched_dtype_diagnosed(self, core):
        data = self._reqs(core, [[4], [4]], dtypes=[F32, 5])
        rtype, err, _ = core.negotiate(data, 2, 2)
        assert rtype == ERROR and "Mismatched data types" in err

    def test_mismatched_op_diagnosed(self, core):
        data = self._reqs(core, [[4], [4]], op=[ALLREDUCE, ALLGATHER])
        rtype, err, _ = core.negotiate(data, 2, 2)
        assert rtype == ERROR and "Mismatched collective operations" in err

    def test_mismatched_root_diagnosed(self, core):
        data = self._reqs(core, [[4], [4]], op=BROADCAST, roots=[0, 1])
        rtype, err, _ = core.negotiate(data, 2, 2)
        assert rtype == ERROR and "Mismatched root ranks" in err

    def test_partial_submission_diagnosed(self, core):
        """Fewer submissions than world size (operations.cc:341 precheck)."""
        data = self._reqs(core, [[4], [4]])
        rtype, err, _ = core.negotiate(data, 2, 4)
        assert rtype == ERROR and "Only 2 out of 4" in err

    def test_allgather_sizes_collected(self, core):
        data = b"".join([
            core.wire_make_request(r, ALLGATHER, F32, "t", -1, -1, [r + 1, 3])
            for r in range(4)])
        rtype, err, sizes = core.negotiate(data, 4, 4)
        assert rtype == ALLGATHER and err == ""
        assert sizes == [1, 2, 3, 4]

    def test_allgather_trailing_dim_mismatch(self, core):
        data = b"".join([
            core.wire_make_request(0, ALLGATHER, F32, "t", -1, -1, [2, 3]),
            core.wire_make_request(1, ALLGATHER, F32, "t", -1, -1, [2, 4])])
        rtype, err, _ = core.negotiate(data, 2, 2)
        assert rtype == ERROR and "Mismatched allgather tensor shapes" in err


class TestHalf:
    def test_roundtrip_exact_halves(self, core):
        vals = np.array([0.0, 1.0, -1.5, 0.5, 65504.0, -65504.0], np.float32)
        bits = core.float_to_half(vals)
        back = core.half_to_float(bits)
        assert np.array_equal(vals, back)

    def test_matches_numpy_float16(self, core):
        rng = np.random.RandomState(7)
        vals = rng.uniform(-1000, 1000, size=1024).astype(np.float32)
        bits = core.float_to_half(vals)
        expected = vals.astype(np.float16).view(np.uint16)
        assert np.array_equal(bits, expected)
        back = core.half_to_float(bits)
        assert np.array_equal(back, vals.astype(np.float16).astype(np.float32))

    def test_special_values(self, core):
        vals = np.array([np.inf, -np.inf, np.nan, 1e10, -1e10, 1e-10],
                        np.float32)
        bits = core.float_to_half(vals)
        expected = vals.astype(np.float16)
        back = core.half_to_float(bits)
        assert np.isinf(back[0]) and back[0] > 0
        assert np.isinf(back[1]) and back[1] < 0
        assert np.isnan(back[2])
        assert np.array_equal(back[3:], expected[3:].astype(np.float32))

    def test_halfsum(self, core):
        """float16_sum MPI-op parity (half.cc:42-90)."""
        a = np.array([1.5, 2.5, -3.0], np.float16)
        b = np.array([0.5, 0.25, 1.0], np.float16)
        dst = a.view(np.uint16).copy()
        core.halfsum(b.view(np.uint16).copy(), dst)
        assert np.array_equal(dst.view(np.float16), a + b)


class TestKnobs:
    def test_fusion_threshold_roundtrip(self, core):
        # engine must be initialized (session fixture ran collectives)
        import jax.numpy as jnp
        hvd.allreduce(jnp.ones((2,)))  # force native init
        old = core.fusion_threshold
        try:
            core.fusion_threshold = 1234567
            assert core.fusion_threshold == 1234567
        finally:
            core.fusion_threshold = old

    def test_cycle_time_roundtrip(self, core):
        import jax.numpy as jnp
        hvd.allreduce(jnp.ones((2,)))
        old = core.cycle_time_ms
        try:
            core.cycle_time_ms = 7.5
            assert abs(core.cycle_time_ms - 7.5) < 1e-9
        finally:
            core.cycle_time_ms = old


class TestPyWireMirror:
    """ops/wire_format.py must be byte-exact against the native codec —
    it is the wire for processes without the toolchain (mixed fleets)."""

    def test_request_list_encoding_matches_native(self, core):
        from horovod_tpu.ops import wire_format as wf
        dicts = [
            {"name": "grad/a", "op": ALLREDUCE, "dtype": "float32",
             "shape": (17, 17)},
            {"name": "gath", "op": ALLGATHER, "dtype": "bfloat16",
             "shape": (3, 5)},
            {"name": "bc", "op": BROADCAST, "dtype": "int64",
             "shape": (2,), "root_rank": 3},
        ]
        py = wf.encode_request_list(2, dicts)
        # The native parser must accept it and re-serialize identically.
        assert core.wire_roundtrip_request_list(py) == py
        # And decoding recovers the fields.
        back, shutdown = wf.decode_request_list(py)
        assert not shutdown
        assert [r["name"] for r in back] == ["grad/a", "gath", "bc"]
        assert back[0]["nbytes"] == 17 * 17 * 4
        assert back[1]["dtype"] == "bfloat16"
        assert back[2]["root_rank"] == 3

    def test_response_list_decoding_matches_native(self, core):
        """Encode a response list with the Python mirror, decode it, and
        cross-check against a native controller's serialization of the
        same plan."""
        from horovod_tpu.ops import wire_format as wf
        ctl = native.NativeController(core, 2, 4, 1 << 20, 1.0, 60.0,
                                      False, False, False)
        for rank in range(2):
            ctl.announce(wf.encode_request_list(rank, [
                {"name": "x", "op": ALLREDUCE, "dtype": "float32",
                 "shape": (4,)},
                {"name": "g", "op": ALLGATHER, "dtype": "float32",
                 "shape": (rank + 1, 3)},
            ]))
        # Planning is deferred until the announce stream is quiescent
        # (or the service's fetch-timeout valve fires); driving the
        # controller directly, cut the groups explicitly.
        ctl.plan()
        raw = ctl.fetch(0, 0)
        groups, shutdown = wf.decode_response_list(raw, 2)
        assert not shutdown
        assert [g["names"] for g in groups] == [["x"], ["g"]]
        assert groups[1]["sizes"]["g"] == [1, 2]
        # Python re-encoding of the same plan decodes identically.
        py = wf.encode_response_list(groups, False, 2)
        again, _ = wf.decode_response_list(py, 2)
        for a, b in zip(groups, again):
            assert a["names"] == b["names"]
            assert a["sizes"] == b["sizes"]
            assert a["flags"] == b["flags"]


class TestPlannerEquivalence:
    """The native controller (controller.cc) and the Python fallback
    planner (control_plane.py) must emit IDENTICAL fusion plans for the
    same request stream — one planner contract, two implementations."""

    def _drive(self, native_mode, stream, nproc=2):
        from horovod_tpu.ops.control_plane import (AnnounceRequest,
                                                   CoordinatorService,
                                                   FetchRequest)
        from horovod_tpu.runner.secret import make_secret_key
        svc = CoordinatorService(nproc=nproc, key=make_secret_key(),
                                 fusion_threshold=1024, native=native_mode)
        try:
            assert svc.native_active is native_mode
            aid = 0
            for rank, reqs in stream:
                aid += 1
                svc._handle(AnnounceRequest(rank, reqs, announce_id=aid),
                            None)
            # Let the announce stream go quiescent, then fetch with a
            # window long enough for the timeout valve (which plans past
            # the deliberately-partial entries in some streams).
            import time as _t
            from horovod_tpu.ops.control_plane import PLAN_DEBOUNCE_S
            _t.sleep(PLAN_DEBOUNCE_S * 2)
            resp = svc._handle(
                FetchRequest(0, 0, wait_s=PLAN_DEBOUNCE_S * 4), None)
            return [(g["op"], tuple(g["names"]),
                     {k: tuple(v) for k, v in (g.get("sizes") or {}).items()},
                     bool(g["error"]), g.get("flags", 0))
                    for g in resp.groups]
        finally:
            svc.shutdown()

    def test_identical_plans(self):
        def r(name, op=ALLREDUCE, dtype="float32", shape=(100,), root=-1):
            return {"name": name, "op": op, "dtype": dtype, "shape": shape,
                    "root_rank": root}

        # A gnarly stream: fusion-threshold overflow, mixed dtypes with
        # look-ahead, ragged allgather sizes, a broadcast, a shape
        # mismatch error, and interleaved announce order across ranks.
        stream = [
            (0, [r("a"), r("b"), r("i1", dtype="int32"), r("c")]),
            (1, [r("a"), r("b")]),
            (1, [r("i1", dtype="int32"), r("c")]),
            (0, [r("g1", op=ALLGATHER, shape=(2, 8)),
                 r("bc", op=BROADCAST, shape=(4,), root=1)]),
            (1, [r("g1", op=ALLGATHER, shape=(5, 8)),
                 r("bc", op=BROADCAST, shape=(4,), root=1)]),
            (0, [r("bad", shape=(3,))]),
            (1, [r("bad", shape=(4,))]),
            (0, [r("d", shape=(50,)), r("e", shape=(300,))]),
            (1, [r("d", shape=(50,)), r("e", shape=(300,))]),
        ]
        native_plan = self._drive(True, stream)
        python_plan = self._drive(False, stream)
        assert native_plan == python_plan
        # Sanity on the shared plan: fusion respected the 1024-byte
        # threshold with look-ahead over the whole quiescent stream —
        # a+b = 800 bytes, c (400) would overflow and spilled, d (200)
        # was pulled forward into the 1000-byte group.
        names = [set(g[1]) for g in native_plan]
        assert {"a", "b", "d"} in names
        assert all("c" not in s for s in names if "a" in s)

    def test_identical_plans_under_hierarchical_env(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_HIERARCHICAL_ALLGATHER", "1")

        def r(name, op=ALLGATHER, shape=(4, 4)):
            return {"name": name, "op": op, "dtype": "float32",
                    "shape": shape, "root_rank": -1}

        stream = [(0, [r("g")]), (1, [r("g")])]
        native_plan = self._drive(True, stream)
        python_plan = self._drive(False, stream)
        assert native_plan == python_plan
        from horovod_tpu.ops import wire_format as wf
        assert native_plan[0][4] & wf.FLAG_HIERARCHICAL_ALLGATHER
