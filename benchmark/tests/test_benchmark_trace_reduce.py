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


# ---- a flash kernel is found by the name the program gave it ---------

STEP = "jit(hvd_train_step)/"
BF = "bf16[{}]{{2,1,0:T(8,128)(2,1)}}"
F32 = "f32[{}]{{2,1,0:T(8,128)S(1)}}"


def _mosaic(name, start_ms, dur_ms, outputs, operands, tf_op):
    """A ``tpu_custom_call`` event with the HLO text the v5e's traces
    hold: tiled layouts with parentheses of their own."""
    head = ", ".join(outputs)
    if len(outputs) > 1:
        head = f"({head})"
    args = ", ".join(f"{o} %p.{i}" for i, o in enumerate(operands))
    text = (f"%{name} = {head} custom-call({args}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={bf16[1]{0}}")
    return _event(name, start_ms, dur_ms, text=text, tf_op=tf_op,
                  hlo_category="custom-call")


def _flash_dq(name, start_ms, dur_ms, bh, seq, d_qk, d_v):
    qk, v = BF.format(f"{bh},{seq},{d_qk}"), BF.format(f"{bh},{seq},{d_v}")
    stat = F32.format(f"{bh},{seq},1")
    return _mosaic(name, start_ms, dur_ms, [qk], [qk, qk, v, v, stat, stat],
                   STEP + "transpose(jvp(hvd_attn))/hvd_flash_dq/"
                   "pallas_call:")


def _ragged(name, start_ms, dur_ms):
    """XLA:TPU's own lowering of a ``lax.ragged_dot`` weight gradient:
    ONE rank-3 output, as a dQ kernel returns."""
    return _mosaic(name, start_ms, dur_ms, [BF.format("8,2688,1024")],
                   [BF.format("4096,2688"), BF.format("4096,1024"),
                    "s32[8]{0}"],
                   STEP + "transpose(jvp(hvd_moe))/hvd_moe_routed/"
                   "ragged_dot:")


def _steps(make_ops):
    """Two whole steps of 100 ms and the start of a third."""
    ops, modules = [], []
    for i in range(3):
        modules.append(_event("jit_hvd_train_step(1)", 100 * i, 95))
        ops += make_ops(100 * i)
    return _trace(ops, modules, [])


def _flash_readers():
    from benchmark import manifest
    readers_dir = manifest.ROOT / "benchmark" / "layer_metrics"
    return (manifest.load_reader(readers_dir, "flash_ms_per_step"),
            manifest.load_reader(readers_dir, "flash_roofline"))


def test_a_mosaic_call_that_is_no_flash_kernel_is_in_no_flash_entry():
    planes = _steps(lambda t: [_ragged("ragged-dot.1", t, 40),
                               _ragged("ragged-dot.2", t + 40, 20)])
    summary = tr.summarize_planes(planes)
    assert summary.flash == {}
    # it stays a class of the breakdown, under its shapes and phase
    assert summary.class_seconds == {
        "mosaic_bf16_8_2688_1024__bwd_": pytest.approx(0.120)}
    run = {"trace": summary, "peaks": peaks.peaks_for("TPU v5 lite")}
    ms, roofline = _flash_readers()
    assert ms(run) is None and roofline(run) is None
    # and its time reads under the name the program gave it
    from benchmark import program_trace
    trace = program_trace.reduce(planes)
    assert trace.per_step_ms("hvd_moe_routed") == pytest.approx(60)
    assert trace.per_step_ms(program_trace.KERNEL_PREFIX) == 0


def test_a_flash_kernel_is_counted_with_its_two_widths():
    planes = _steps(lambda t: [_flash_dq("dq.1", t, 10, 4, 256, 192, 128),
                               _ragged("ragged-dot.1", t + 10, 40)])
    summary = tr.summarize_planes(planes)
    assert summary.flash == {("dq", 4, 256, 192, 128):
                             (2, pytest.approx(0.020))}
    v5e = peaks.peaks_for("TPU v5 lite")
    ms, roofline = _flash_readers()
    run = {"trace": summary, "peaks": v5e}
    assert ms(run) == pytest.approx(10)
    least = flops.least_seconds(
        *flops.flash_kernel_work("dq", 4, 256, 192, 128), v5e)[0]
    assert roofline(run) == pytest.approx(100 * 2 * least / 0.020)


def test_two_shapes_of_one_kind_are_two_entries():
    planes = _steps(lambda t: [_flash_dq("dq.1", t, 10, 4, 256, 192, 128),
                               _flash_dq("dq.2", t + 10, 30, 32, 4096, 128,
                                         128)])
    summary = tr.summarize_planes(planes)
    assert summary.flash == {
        ("dq", 4, 256, 192, 128): (2, pytest.approx(0.020)),
        ("dq", 32, 4096, 128, 128): (2, pytest.approx(0.060))}
    ms, _ = _flash_readers()
    assert ms({"trace": summary}) == pytest.approx(40)


def test_whether_a_call_is_a_flash_kernel_is_its_name_alone():
    dq = _flash_dq("dq.1", 0, 1, 4, 256, 192, 128)
    assert tr.flash_kind(dq) == ("dq", (4, 256, 192, 128))
    assert tr.output_shapes(dq) == [("bf16", (4, 256, 192))]
    assert tr.operand_shapes(dq)[2] == ("bf16", (4, 256, 128))
    ragged = _ragged("r.1", 0, 1)
    assert tr.flash_kind(ragged) == (None, None)
    # XLA:TPU's own lowering puts ITS name on the call and drops JAX's
    # stack (CPU-side compile, PR 35): no flash kernel, and no scope
    ragged.stats["tf_op"] = "ragged-dot-none"
    assert tr.flash_kind(ragged) == (None, None)
    assert tr.name_of(ragged) == tr.UNSCOPED
    # the same call without the kernel's name, and the kernel's name on
    # an op that is no Mosaic call (the row statistics' reduce keeps it)
    dq.stats["tf_op"] = STEP + "transpose(jvp(hvd_attn))/pallas_call:"
    assert tr.flash_kind(dq) == (None, None)
    reduce_ = _event("reduce.1", 0, 1, text="%reduce.1 = f32[32,2048] "
                     "reduce(f32[32,2048,1] %x)",
                     tf_op=STEP + "jvp(hvd_attn)/hvd_flash_fwd/reduce_sum:")
    assert tr.flash_kind(reduce_) == (None, None)


# ---- the trace recorded on the v5e (PR 35, gpt1b3-s2k-1chip from the
# committed files, seed 2147483659; cut to its first two steps and the
# events the reductions read by ``cut_trace.py``). The program names its
# work in it; PR 25's recording predated the names ---------------------

FLASH_MS, FLASH_ROOFLINE = 33.22, 36.75    # the traced run's own: 33.219, 36.753

@pytest.fixture(scope="module")
def recorded():
    return tr.summarize(RECORDED)


def test_recorded_window_and_idle_share(recorded):
    assert recorded.devices == 1 and recorded.steps == 2
    assert recorded.window_s == pytest.approx(0.46272, abs=1e-4)
    idle = 1 - recorded.busy_s / recorded.window_s
    assert idle == pytest.approx(0.0120, abs=5e-4)


def test_recorded_classes(recorded):
    per_step = {k: 1e3 * v / recorded.steps
                for k, v in recorded.class_seconds.items()}
    assert per_step["matmul_fusion__bwd_"] == pytest.approx(114.7, abs=0.1)
    assert per_step["matmul_fusion__fwd_"] == pytest.approx(54.5, abs=0.1)
    assert per_step["matmul_fusion__remat_"] == pytest.approx(4.45, abs=0.1)
    assert per_step[
        "mosaic_bf16_32_2048_128__f32_32_2048_1__fwd_"] == \
        pytest.approx(9.37, abs=0.05)
    # XLA's numbering and suffixes are not part of a class name
    assert not [k for k in per_step if not k.startswith("mosaic")
                and ("." in k or any(c.isdigit() for c in k))]
    # self times add up to the busy time: nothing is counted twice
    assert sum(recorded.class_seconds.values()) == pytest.approx(
        recorded.busy_s, rel=1e-3)
    assert recorded.collective_s == 0.0


def test_recorded_flash_kernels_and_their_roofline(recorded):
    # 20 layers, two steps: under "dots" remat the forward runs once a
    # layer (its output and row statistics are saved), dK/dV, dQ once
    assert {k: v[0] for k, v in recorded.flash.items()} == {
        ("fwd", 32, 2048, 128, 128): 40, ("dkv", 32, 2048, 128, 128): 40,
        ("dq", 32, 2048, 128, 128): 40}
    v5e = peaks.peaks_for("TPU v5 lite")
    least = sum(calls * flops.least_seconds(
        *flops.flash_kernel_work(kind, *sizes), v5e)[0]
        for (kind, *sizes), (calls, _) in recorded.flash.items())
    spent = sum(v[1] for v in recorded.flash.values())
    assert 1e3 * spent / recorded.steps == pytest.approx(FLASH_MS, abs=0.05)
    assert 100 * least / spent == pytest.approx(FLASH_ROOFLINE, abs=0.1)


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
    assert kernel.stats["tf_op"].endswith("hvd_flash_fwd/pallas_call:")
    assert tr.output_shapes(kernel) == [("bf16", (32, 2048, 128)),
                                        ("f32", (32, 2048, 1))]
    assert tr.operand_shapes(kernel) == [("bf16", (32, 2048, 128))] * 3
    assert tr.flash_kind(kernel) == ("fwd", (32, 2048, 128, 128))
    assert 0 < kernel.seconds < 1e-3
