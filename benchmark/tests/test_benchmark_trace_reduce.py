"""The reduction from a trace to numbers: interval arithmetic on a
hand-made trace, and the whole of it on a trace recorded on the v5e
(``data/``, see its README line in PERF.md)."""

from pathlib import Path

import pytest

from benchmark import flops, peaks, trace_reduce as tr
from benchmark.xplane import Event, Line, Plane

MS = 1e6    # ns
RECORDED = (Path(__file__).parent / "data"
            / "gpt1b3-s2k-1chip.v5e.2steps.xplane.pb.gz")


def _event(name, start_ms, dur_ms, text="", **stats):
    return Event(name, text or name, start_ms * MS,
                 (start_ms + dur_ms) * MS, stats)


def _trace(ops, modules, spans, devices=1, async_ops=()):
    planes = [Plane(f"/device:TPU:{d}", [
        Line("XLA Modules", modules), Line("XLA Ops", ops),
        Line("Async XLA Ops", list(async_ops))]) for d in range(devices)]
    planes.append(Plane("/host:CPU", [Line("python3", spans)]))
    planes.append(Plane("/device:TPU:0 SparseCore", []))
    return planes


def test_interval_helpers():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert tr.covered(merged) == 6
    assert tr.subtract([(0, 10)], [[2, 3], [5, 8]]) == \
        [(0, 2), (3, 5), (8, 10)]
    assert tr.subtract([(0, 4)], [[0, 4]]) == []


@pytest.fixture()
def summary():
    # two whole steps of 100 ms and the start of a third; each step:
    # a matmul fusion 0-60, an all-reduce from 50 whose done op holds
    # the core 60-90 (10 ms under compute, 30 exposed), idle 90-100
    # while the host syncs and dispatches.
    ops, modules, spans, async_ops = [], [], [], []
    for i in range(3):
        t = 100 * i
        modules.append(_event("jit_step(1)", t, 90))
        ops.append(_event(f"fusion.{i}", t, 60,
                          hlo_category="convolution fusion"))
        ops.append(_event(f"all-reduce-done.{i}", t + 60, 30,
                          hlo_category="all-reduce-done"))
        async_ops.append(_event(f"all-reduce-start.{i}", t + 50, 40,
                                hlo_category="all-reduce-start"))
        spans.append(_event("bench/sync", t + 1, 91))
        spans.append(_event("bench/read_loss", t + 92, 2))
        spans.append(_event("bench/dispatch", t + 94, 6))
    modules.append(_event("jit_small", 95, 1))
    return tr.summarize_planes(_trace(ops, modules, spans, devices=2,
                                      async_ops=async_ops))


def test_window_holds_whole_steps(summary):
    assert summary.devices == 2 and summary.steps == 2
    assert summary.window_s == pytest.approx(0.200)
    assert summary.busy_s == pytest.approx(0.180)


def test_idle_gaps_go_to_the_host_span_that_covers_them(summary):
    gaps = dict(summary.top_gaps(10))
    # each 10 ms gap: dispatch covers 6, read_loss 2, sync 2
    assert gaps == {"bench/dispatch": pytest.approx(0.012),
                    "bench/read_loss": pytest.approx(0.004),
                    "bench/sync": pytest.approx(0.004)}


def test_collectives_and_their_exposed_part(summary):
    assert summary.collective_s == pytest.approx(0.080)
    assert summary.collective_exposed_s == pytest.approx(0.060)


def test_classes_are_stable_names(summary):
    ops = dict(summary.top_ops(10))
    assert ops["matmul_fusion"] == pytest.approx(0.120)
    assert ops["all-reduce"] == pytest.approx(0.060)   # the done op's wait


def test_a_trace_without_a_device_plane_has_no_devices():
    assert tr.summarize_planes([Plane("/host:CPU", [])]).devices == 0


def test_a_container_is_counted_without_what_it_contains():
    ops = [_event("while.1", 0, 100, hlo_category="while"),
           _event("fusion.1", 10, 30, hlo_category="loop fusion"),
           _event("fusion.2", 50, 40, hlo_category="loop fusion"),
           _event("copy.3", 100, 5, hlo_category="data formatting")]
    got = {op.name: s for op, s in tr.self_seconds(ops)}
    assert got == {"while.1": pytest.approx(0.030),
                   "fusion.1": pytest.approx(0.030),
                   "fusion.2": pytest.approx(0.040),
                   "copy.3": pytest.approx(0.005)}


def test_an_async_collective_counts_from_start_to_done():
    # all-reduce-start at 60, its done op waits 80-100 on the core;
    # compute runs 0-80: 20 of the 40 ms are exposed.
    modules = [_event("jit_step", 0, 100), _event("jit_step", 100, 1)]
    ops = [_event("fusion.1", 0, 80, hlo_category="convolution fusion"),
           _event("all-reduce-done.1", 80, 20, hlo_category="all-reduce-done")]
    span = [_event("all-reduce-start.1", 60, 40,
                   hlo_category="all-reduce-start")]
    s = tr.summarize_planes(_trace(ops, modules, [], async_ops=span))
    assert s.collective_s == pytest.approx(0.040)
    assert s.collective_exposed_s == pytest.approx(0.020)
    assert s.busy_s == pytest.approx(0.100)


# ---- the trace recorded on the v5e (PR 25, gpt1b3-s2k-1chip, cut to
# its first two steps and the events the reduction reads) -------------

@pytest.fixture(scope="module")
def recorded():
    return tr.summarize(RECORDED)


def test_recorded_window_and_idle_share(recorded):
    assert recorded.devices == 1 and recorded.steps == 2
    assert recorded.window_s == pytest.approx(0.50554, abs=1e-4)
    idle = 1 - recorded.busy_s / recorded.window_s
    assert idle == pytest.approx(0.0118, abs=5e-4)


def test_recorded_classes(recorded):
    per_step = {k: 1e3 * v / recorded.steps
                for k, v in recorded.class_seconds.items()}
    assert per_step["matmul_fusion__bwd_"] == pytest.approx(118.7, abs=0.1)
    assert per_step["matmul_fusion__fwd_"] == pytest.approx(54.8, abs=0.1)
    assert per_step["matmul_fusion__remat_"] == pytest.approx(8.9, abs=0.1)
    assert per_step[
        "mosaic_bf16_32_2048_128__f32_32_2048_1__fwd_"] == \
        pytest.approx(9.46, abs=0.05)
    # XLA's numbering and suffixes are not part of a class name
    assert not [k for k in per_step if not k.startswith("mosaic")
                and ("." in k or any(c.isdigit() for c in k))]
    # self times add up to the busy time: nothing is counted twice
    assert sum(recorded.class_seconds.values()) == pytest.approx(
        recorded.busy_s, rel=1e-3)
    assert recorded.collective_s == 0.0


def test_recorded_flash_kernels_and_their_roofline(recorded):
    # 20 layers: forward and its recomputation, dK/dV, dQ in each
    assert {k: v[0] for k, v in recorded.flash.items()} == \
        {"fwd": 80, "dkv": 40, "dq": 40}
    assert all(v[2] == (32, 2048, 128) for v in recorded.flash.values())
    v5e = peaks.peaks_for("TPU v5 lite")
    least = sum(calls * flops.least_seconds(
        *flops.flash_kernel_work(kind, *shape), v5e)[0]
        for kind, (calls, _, shape) in recorded.flash.items())
    spent = sum(v[1] for v in recorded.flash.values())
    assert 1e3 * spent / recorded.steps == pytest.approx(45.24, abs=0.05)
    assert 100 * least / spent == pytest.approx(34.7, abs=0.1)


def test_recorded_gaps_are_the_hosts_spans(recorded):
    gaps = dict(recorded.top_gaps(10))
    assert max(gaps, key=gaps.get) == "bench/sync"
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s, rel=0.02)


def test_the_reader_hands_out_the_metadata_the_classes_need():
    from benchmark import xplane
    planes = xplane.read(
        RECORDED, want_plane=lambda n: n == "/device:TPU:0",
        want_line=lambda n: n == "XLA Ops")
    assert [p.name for p in planes] == ["/device:TPU:0"]
    assert [l.name for l in planes[0].lines] == ["XLA Ops"]
    kernel = next(e for e in planes[0].lines[0].events if tr.is_mosaic(e))
    assert kernel.stats["hlo_category"] == "custom-call"
    assert kernel.stats["tf_op"].endswith("pallas_call:")
    assert tr.output_shapes(kernel) == [("bf16", (32, 2048, 128)),
                                        ("f32", (32, 2048, 1))]
    assert tr.flash_kind(kernel) == ("fwd", (32, 2048, 128))
    assert 0 < kernel.seconds < 1e-3
