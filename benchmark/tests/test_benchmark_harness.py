"""The harness end to end at a tiny size on the CPU, through the
``allow_cpu`` argument that only tests pass; the command itself refuses
without a chip. The tiny cells are ADDED to a copy as files, which is
also the proof that a cell, a configuration, a traffic mix and a
per-layer metric need no edit of the harness."""

import json
import statistics
import subprocess
import sys

import pytest

from benchmark import harness, manifest
from benchmark.kinds import train
from conftest import ROOT

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def untraced(tiny_root):
    return harness.run_cell("tiny-dp1", 2**31 + 11, 1.5, False,
                            root=tiny_root, allow_cpu=True)


def test_untraced_line_has_the_end_to_end_metrics(untraced, tiny_root):
    assert LINE_KEYS <= set(untraced)
    assert untraced["correct"] is True and untraced["failed"] == 0
    assert set(untraced["metrics"]) == {"train_tok_s_per_chip", "setup_s"}
    assert untraced["metrics"]["train_tok_s_per_chip"]["unit"] == \
        "tokens/s/chip"
    assert all(m["value"] > 0 for m in untraced["metrics"].values())
    assert untraced["device"]["count"] == 1
    record = json.loads((tiny_root / harness.OUT_DIR / "tiny-dp1"
                         / f"seed-{2**31 + 11}-trace-0.json").read_text())
    assert len(record["segment_rates"]) == 5
    steps = 5 * record["steps_per_segment"]
    assert untraced["attempted"] == steps
    # all the window's tokens over all its seconds, not a choice of them
    assert untraced["metrics"]["train_tok_s_per_chip"]["value"] == \
        pytest.approx(steps * record["tokens_per_step"]
                      / sum(record["segment_seconds"]))
    assert record["segment_median_rate"] == \
        statistics.median(record["segment_rates"])
    json.dumps(untraced)


def test_traced_line_has_the_per_layer_metrics_that_found_something(
        tiny_root):
    result = harness.run_cell("tiny-dp1", 5, 1.5, True, root=tiny_root,
                              allow_cpu=True)
    got = set(result["metrics"])
    # host-clock readers always find their spans; the added reader is
    # found by its file; one that returns nothing is left out; the CPU
    # trace has no device plane, so the trace's readers find nothing.
    assert {"lower_s", "compile_s", "input_wait_ms_per_step",
            "step_ms_p50", "step_ms_p90", "tokens_per_step"} <= got
    assert "never_there" not in got and "device_idle_share" not in got
    assert result["metrics"]["tokens_per_step"]["value"] == 2 * 256
    assert "train_tok_s_per_chip" not in got
    assert result["correct"] is True


@pytest.mark.parametrize("cell", ["tiny-dp4", "tiny-dp2tp2"])
def test_a_layout_over_four_devices_holds_equal_replicas(tiny_root, cell):
    """The layout is a field of the traffic file, handed to the mesh
    builder: dp=4, and dp=2 x tp=2 with no edit of any file."""
    result = harness.run_cell(cell, 7, 1.5, False, root=tiny_root,
                              allow_cpu=True)
    assert result["correct"] is True
    assert result["device"]["count"] == 4


def test_the_dense_kind_goes_through_the_one_training_loop(
        tiny_root, monkeypatch):
    """Kind ``train`` hands ``_run`` its model like any other kind; the
    kind's file is loaded by its path, so the spy goes into the loaded
    function's own globals."""
    run_kind = manifest.load_kind(tiny_root / "benchmark" / "kinds", "train")
    seen = []
    real = run_kind.__globals__["_run"]

    def spy(ctx, mesh, model, *args):
        seen.append(model)
        return real(ctx, mesh, model, *args)
    monkeypatch.setitem(run_kind.__globals__, "_run", spy)
    monkeypatch.setattr(manifest, "load_kind", lambda *_: run_kind)
    result = harness.run_cell("tiny-dp1", 13, 1.0, False, root=tiny_root,
                              allow_cpu=True)
    assert result["correct"] is True and len(seen) == 1
    assert {"init_params", "param_specs", "against_reference",
            "flops_per_step"} <= set(vars(seen[0]))
    assert list(result)[-1] == "compared"
    (number, limit), = result["compared"].values()
    assert 0 <= number <= limit


def test_a_traced_run_keeps_a_floor_of_synced_samples(tiny_root):
    """The percentiles need samples, not seconds: a traced run whose
    window is shorter than ten steps goes on until ten are in."""
    result = harness.run_cell("tiny-dp1", 17, 0.01, True, root=tiny_root,
                              allow_cpu=True)
    record = json.loads((tiny_root / harness.OUT_DIR / "tiny-dp1"
                         / "seed-17-trace-1.json").read_text())
    assert len(record["step_seconds"]) == train.SYNCED_MIN_SAMPLES == 10
    assert "step_ms_p90" in result["metrics"]


def test_a_replica_that_differs_is_seen():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    specs = {"w": P(None, "tp"), "b": P()}
    equal = jax.shard_map(
        lambda: {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}, mesh=mesh,
        in_specs=(), out_specs=specs, check_vma=False)
    # the second dp row's copy of 'w' differs in one bit pattern
    skewed = jax.shard_map(
        lambda: {"w": jnp.ones((2, 2)) + jax.lax.axis_index("dp"),
                 "b": jnp.ones((2,))}, mesh=mesh,
        in_specs=(), out_specs=specs, check_vma=False)
    assert train.replicas_equal(jax.jit(equal)(), specs, mesh)
    assert not train.replicas_equal(jax.jit(skewed)(), specs, mesh)


def test_a_kind_added_as_a_file_is_found_by_the_traffic_file(tiny_root):
    result = harness.run_cell("tiny-fixed", 1, 1.0, False, root=tiny_root,
                              allow_cpu=True)
    assert result["correct"] is True and result["attempted"] == 3
    assert result["metrics"]["train_tok_s_per_chip"]["value"] == 7.0
    assert result["metrics"]["setup_s"]["value"] == 1.5


def test_a_kind_with_no_file_is_refused(tiny_root):
    (tiny_root / "benchmark" / "traffic" / "tiny-nokind.json").write_text(
        json.dumps({"kind": "serve"}))
    bad = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bad["workloads"].append({"name": "tiny-nokind", "config": "tiny",
                             "traffic": "tiny-nokind", "chips": 1,
                             "why": "test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bad))
    with pytest.raises(harness.Refused, match="serve"):
        harness.run_cell("tiny-nokind", 1, 1.0, False, root=tiny_root,
                         allow_cpu=True)


def test_same_seed_same_first_loss(tiny_root, untraced):
    harness.run_cell("tiny-dp1", 2**31 + 11, 1.0, False, root=tiny_root,
                     allow_cpu=True)
    path = (tiny_root / harness.OUT_DIR / "tiny-dp1"
            / f"seed-{2**31 + 11}-trace-0.json")
    again = json.loads(path.read_text())
    assert again["reference_loss"] == pytest.approx(
        again["first_loss"], rel=1e-3)


def test_a_cell_is_refused_without_an_accelerator(tiny_root):
    with pytest.raises(harness.Refused, match="no accelerator"):
        harness.run_cell("tiny-dp1", 1, 1.0, False, root=tiny_root)


@pytest.mark.parametrize("layout,why", [
    ({"dp": 4}, "does not multiply"),       # four ways on one chip
    ({"dp": 1, "pp": 1}, "axes"),           # an axis this kind cannot bind
])
def test_a_layout_the_cell_cannot_have_is_refused(tiny_root, layout, why):
    name = "tiny-bad-" + "-".join(layout)
    traffic = json.loads(
        (tiny_root / "benchmark" / "traffic" / "tiny-dp1.json").read_text())
    (tiny_root / "benchmark" / "traffic" / f"{name}.json").write_text(
        json.dumps(dict(traffic, layout=layout)))
    bad = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bad["workloads"].append({"name": name, "config": "tiny",
                             "traffic": name, "chips": 1, "why": "test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bad))
    with pytest.raises(harness.Refused, match=why):
        harness.run_cell(name, 1, 1.0, False, root=tiny_root,
                         allow_cpu=True)


def test_the_command_refuses_on_the_cpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "gpt1b3-s2k-1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_added_files_are_found_by_name(tiny_root):
    cell = manifest.cell("tiny-dp4", tiny_root)
    assert cell["config"]["n_embd"] == 256 and cell["chips"] == 4
    assert cell["traffic"]["layout"] == {"dp": 4}
    names = {m["name"] for m in cell["per_layer"]}
    assert "tokens_per_step" in names and "mfu" in names
    other = manifest.cell("gpt1b3-s2k-1chip", tiny_root)
    assert "tokens_per_step" not in {m["name"] for m in other["per_layer"]}
    read = manifest.load_reader(cell["readers_dir"], "tokens_per_step")
    assert read({"tokens_per_step": 3}) == 3
