"""``program_trace``: the program's own names in a trace. The reduction
on a hand-made trace, on the trace recorded on the v5e (PR 35: the
program names its work there), on a trace that names nothing (every
reader finds nothing), and the eight readers of PR 27 on the tiny
cell."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import harness, manifest, program_trace as pt
from benchmark import trace_reduce as tr
from benchmark.xplane import Event, Line, Plane

MS = 1e6    # ns
RECORDED = (Path(__file__).parent / "data"
            / "gpt1b3-s2k-1chip.v5e.2steps.xplane.pb.gz")
NEW_METRICS = ["attn_kernel_ms_per_step", "attn_ms_per_step",
               "mlp_ms_per_step", "loss_head_ms_per_step",
               "optimizer_ms_per_step", "scope_coverage",
               "grad_reduce_gb_per_step", "input_queue_wait_ms_per_step"]
STEP = "jit(hvd_train_step)/"


def _op(name, start_ms, dur_ms, tf_op=None, **stats):
    if tf_op is not None:
        stats["tf_op"] = tf_op
    return Event(name, name, start_ms * MS, (start_ms + dur_ms) * MS, stats)


def _planes(devices=1, spans=True):
    """Two whole steps of 100 ms and the start of a third. A step:
    embed 0-2, attention matmul 2-22 with a forward kernel 22-30, MLP
    30-50, a ``while`` of the loss head 50-70 whose body op takes
    52-68, the backward attention fused with nothing 70-80 with its dQ
    kernel 80-85, an all-reduce 85-90 under the reduction's name, the
    optimizer 90-94, an unnamed copy 94-95, idle 95-100."""
    ops, modules, host = [], [], []
    for i in range(3):
        t = 100 * i
        modules.append(_op("jit_hvd_train_step(1)", t, 95))
        ops += [
            _op("gather.1", t, 2, STEP + "jvp(hvd_embed)/gather:"),
            _op("fusion.1", t + 2, 20, STEP + "jvp(hvd_attn)/dot_general:",
                hlo_category="convolution fusion"),
            _op("jvp_hvd_flash_fwd_.1", t + 22, 8,
                STEP + "jvp(hvd_attn)/hvd_flash_fwd/pallas_call:"),
            _op("fusion.2", t + 30, 20, STEP + "jvp(hvd_mlp)/dot_general:"),
            _op("while.1", t + 50, 20,
                STEP + "jvp(hvd_loss_head)/while:", hlo_category="while"),
            _op("fusion.3", t + 52, 16, STEP + "jvp(hvd_loss_head)/while/"
                "body/closed_call/checkpoint/dot_general:"),
            _op("fusion.4", t + 70, 10, STEP + "transpose(jvp(jvp()))/"
                "checkpoint/hvd_attn/dot_general:"),
            _op("transpose_jvp_hvd_flash_dq__.1", t + 80, 5,
                STEP + "transpose(jvp(jvp()))/checkpoint/hvd_attn/"
                "hvd_flash_dq/pallas_call:"),
            _op("all-reduce.1", t + 85, 5, STEP + "hvd_grad_reduce/psum:"),
            _op("fusion.5", t + 90, 4, STEP + "hvd_optimizer/add:"),
            _op("copy.1", t + 94, 1),
        ]
        host += [
            # inside bench/input, which trace_reduce reads and this not
            _op("bench/input", t + 95.0, 2.0),
            _op("hvd/data/wait", t + 95.2, 0.3),
            _op("hvd/other/inside", t + 96.0, 0.8),
            _op("hvd/data/h2d", t + 40, 1.5),      # the producer's thread
        ]
    # before the window, and a span that straddles its end (200 ms)
    host.append(_op("hvd/other/before", -50, 10))
    host.append(_op("hvd/data/load", 199, 4))
    planes = [Plane(f"/device:TPU:{d}", [
        Line("XLA Modules", list(modules)), Line("XLA Ops", list(ops))])
        for d in range(devices)]
    planes.append(Plane("/host:CPU", [Line("python3", host if spans
                                           else host[:1])]))
    return planes


def test_self_time_goes_to_the_innermost_name():
    trace = pt.reduce(_planes(devices=2))
    assert (trace.steps, trace.devices) == (2, 2)
    per_step = {name: 1e3 * sum(p.values()) / trace.steps
                for name, p in trace.names.items()}
    assert per_step == pytest.approx({
        "hvd_embed": 2, "hvd_attn": 30, "hvd_flash_fwd": 8,
        "hvd_flash_dq": 5, "hvd_mlp": 20,
        "hvd_loss_head": 20,        # the while's 4 + its body's 16
        "hvd_grad_reduce": 5, "hvd_optimizer": 4, "unscoped": 1})
    # by phase, from JAX's name stack as trace_reduce reads it
    assert trace.names["hvd_attn"] == pytest.approx(
        {"fwd": 0.040, "bwd": 0.020})
    assert set(trace.names["hvd_optimizer"]) == {""}
    # the names' self times are the device's busy time
    assert sum(per_step.values()) == pytest.approx(95)
    assert trace.busy_s == pytest.approx(0.190)
    assert trace.per_step_ms("hvd_attn", pt.KERNEL_PREFIX) == \
        pytest.approx(43)
    assert trace.per_step_ms(pt.KERNEL_PREFIX) == pytest.approx(13)


def test_host_spans_are_counted_inside_the_window_only():
    trace = pt.reduce(_planes())
    # window: 0 to 200 ms (the third step's start)
    assert trace.spans["hvd/data/wait"] == (2, pytest.approx(0.6e-3))
    assert trace.spans["hvd/other/inside"] == \
        (2, pytest.approx(1.6e-3))
    assert trace.spans["hvd/data/h2d"] == (2, pytest.approx(3e-3))
    assert trace.spans["hvd/data/load"] == (1, pytest.approx(1e-3))
    assert "hvd/other/before" not in trace.spans
    assert not [n for n in trace.spans if n.startswith("bench/")]


def test_a_name_is_a_whole_word_and_the_steps_own_name_is_none():
    assert pt.name_of(_op("x", 0, 1, STEP + "jvp(hvd_mlp)/dot:")) == \
        "hvd_mlp"
    assert pt.name_of(_op("x", 0, 1, STEP + "add:")) == pt.UNSCOPED
    assert pt.name_of(_op("x", 0, 1, "jit(f)/my_hvd_attn_like/add:")) == \
        pt.UNSCOPED
    assert pt.name_of(_op("x", 0, 1)) == pt.UNSCOPED
    # a kernel a later PR adds is found by the prefix, and a scope
    # nobody listed by the open rule
    assert pt.name_of(_op("x", 0, 1, STEP + "hvd_attn/hvd_flash_bwd/"
                          "pallas_call:")) == "hvd_flash_bwd"
    assert pt.name_of(_op("x", 0, 1, STEP + "jvp(hvd_moe)/hvd_moe_routed/"
                          "ragged_dot:")) == "hvd_moe_routed"


def _readers():
    readers_dir = manifest.ROOT / "benchmark" / "layer_metrics"
    return {m: manifest.load_reader(readers_dir, m) for m in NEW_METRICS}


def test_nothing_to_read_gives_nothing_never_zero(monkeypatch):
    readers = _readers()
    trace_readers = [m for m in NEW_METRICS
                     if m != "grad_reduce_gb_per_step"]
    # no device plane (a CPU trace); then no names and no spans
    assert pt.reduce([Plane("/host:CPU", [Line("python3", [])])]) is None
    unnamed = _planes(spans=False)
    for plane in unnamed[:-1]:
        for op in plane.line("XLA Ops").events:
            op.stats.pop("tf_op", None)
    trace = pt.reduce(unnamed)
    assert trace.names is None and trace.spans is None
    for m in trace_readers:
        assert readers[m]({pt.CACHE_KEY: trace}) is None, m
        assert readers[m]({pt.CACHE_KEY: None}) is None, m
    # a run without a trace, and one whose trace held no plane
    assert pt.load({}) is None and pt.load({"planes": []}) is None
    # a program without the gauge: nothing; with it, its value
    from horovod_tpu.observability import registry
    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    assert readers["grad_reduce_gb_per_step"]({}) is None
    registry.registry().gauge(
        "hvdtpu_jit_grad_reduce_bytes", "x").labels().set(4.0e9)
    assert readers["grad_reduce_gb_per_step"]({}) == 4.0


def test_the_readers_on_a_named_trace():
    readers = _readers()
    run = {pt.CACHE_KEY: pt.reduce(_planes(devices=2))}
    got = {m: readers[m](run) for m in NEW_METRICS
           if m != "grad_reduce_gb_per_step"}
    assert got == pytest.approx({
        "attn_kernel_ms_per_step": 13, "attn_ms_per_step": 43,
        "mlp_ms_per_step": 20, "loss_head_ms_per_step": 20,
        "optimizer_ms_per_step": 4, "scope_coverage": 100 * 94 / 95,
        "input_queue_wait_ms_per_step": 0.3})


def test_the_recorded_v5e_trace_through_load(capsys):
    """The trace in ``data/`` through the way a run takes: the harness
    parses the planes once (``trace_reduce.read_planes``), ``load``
    reduces them once and keeps the result, and the readers read the
    names the program gave its work on the chip."""
    run = {"planes": tr.read_planes(RECORDED)}
    trace = pt.load(run)
    assert trace is run[pt.CACHE_KEY] is pt.load(run)
    # said once, however many readers load it
    assert capsys.readouterr().out.count("program_trace: busy") == 1
    assert trace.steps == 2 and trace.devices == 1
    summary = tr.summarize_planes(run["planes"])
    assert trace.busy_s == pytest.approx(summary.busy_s, rel=1e-6)
    assert set(trace.names) == {
        "hvd_embed", "hvd_attn", "hvd_flash_fwd", "hvd_flash_dkv",
        "hvd_flash_dq", "hvd_mlp", "hvd_loss_head", "hvd_optimizer",
        pt.UNSCOPED}
    assert "hvd/data/wait" in trace.spans
    readers = _readers()
    got = {m: readers[m](run) for m in NEW_METRICS
           if m != "grad_reduce_gb_per_step"}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the kernels' names hold the kernels (trace_reduce.flash) and the
    # row statistics' squeeze, a reduce that keeps the forward's name
    flash_ms = 1e3 * sum(s for _, s in summary.flash.values()) / 2
    assert 0 < got["attn_kernel_ms_per_step"] - flash_ms < 1.0
    assert got["attn_ms_per_step"] > got["attn_kernel_ms_per_step"]
    assert 90 < got["scope_coverage"] < 100


@pytest.fixture(scope="module")
def named_root(tiny_root, tmp_path_factory):
    """A copy of the tiny checkout in which the two of the eight
    entries that are one model's own list the tiny cells too."""
    root = tmp_path_factory.mktemp("named")
    shutil.copytree(tiny_root / "benchmark", root / "benchmark")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        # six of the eight carry no list: every training cell has them
        if m["name"] in NEW_METRICS and "workloads" in m:
            m["workloads"] = m["workloads"] + ["tiny-dp1", "tiny-dp4"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("cell,chips", [("tiny-dp1", 1), ("tiny-dp4", 4)])
def test_the_tiny_cell_runs_with_the_eight_readers(named_root, cell, chips):
    """On the CPU the trace has no device plane, so the trace's readers
    find nothing and the line leaves them out; the counter is read from
    the program's registry: 0 on one device, 4 bytes a parameter on
    four."""
    names = {m["name"] for m in manifest.cell(cell, named_root)["per_layer"]}
    assert set(NEW_METRICS) <= names
    result = harness.run_cell(cell, 2**31 + 5, 1.5, True, root=named_root,
                              allow_cpu=True)
    assert result["correct"] is True
    got = result["metrics"]
    assert set(NEW_METRICS) & set(got) == {"grad_reduce_gb_per_step"}
    assert {"step_ms_p50", "input_wait_ms_per_step"} <= set(got)
    n_params = (512 * 256 + 256 * 256 + 256          # embed, pos, ln_f
                + 2 * (2 * 256 + 4 * 256 * 256 + 2 * 256 * 512))
    want = 0.0 if chips == 1 else 4 * n_params / 1e9
    assert got["grad_reduce_gb_per_step"] == {
        "value": pytest.approx(want), "unit": "GB"}
