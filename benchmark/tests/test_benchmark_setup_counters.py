"""The readers of set-up on the program's own counters
(``step_trace_s``, ``step_to_mlir_s``, ``step_compile_s``,
``setup_compile_s``, ``programs_compiled``, ``step_make_s``): on a tiny
traced cell in a process of its own, since the registry holds process
totals and the other tests of this suite add to them; and on a program
without the counters, as the parent of the change that added them is."""

import json
import subprocess
import sys

import pytest

from benchmark import manifest
from conftest import ROOT

READERS = ("step_trace_s", "step_to_mlir_s", "step_compile_s",
           "setup_compile_s", "programs_compiled", "step_make_s")

_RUN = """
import json, sys
from pathlib import Path
sys.path.insert(0, {root!r})
from benchmark import harness
result = harness.run_cell("tiny-dp1", 2**31 + 29, 0.5, True,
                          root=Path({tiny!r}), allow_cpu=True)
print(json.dumps(result["metrics"]))
"""


@pytest.fixture(scope="module")
def traced(tiny_root):
    done = subprocess.run(
        [sys.executable, "-c", _RUN.format(root=str(ROOT),
                                            tiny=str(tiny_root))],
        cwd=ROOT, capture_output=True, text=True, timeout=400)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.splitlines()[-1])


@pytest.mark.parametrize("name", READERS)
def test_a_traced_run_reads_a_positive_number(traced, name):
    assert traced[name]["value"] > 0


def test_at_least_the_step_was_compiled_and_its_twins_lie_in_the_clocks(
        traced):
    assert traced["programs_compiled"]["value"] >= 1
    assert traced["programs_compiled"]["unit"] == "programs"
    # JAX's own events lie inside the kind's clocks around the same calls
    split = traced["step_trace_s"]["value"] + traced["step_to_mlir_s"][
        "value"]
    assert split <= traced["lower_s"]["value"]
    assert traced["step_compile_s"]["value"] <= traced["compile_s"]["value"]
    assert traced["setup_compile_s"]["value"] > split


@pytest.mark.parametrize("name", READERS)
def test_without_the_counters_a_reader_reads_nothing(name, monkeypatch):
    from horovod_tpu.observability import registry
    read = manifest.load_reader(ROOT / "benchmark" / "layer_metrics", name)
    monkeypatch.setattr(registry, "snapshot", lambda prefix=None: {})
    assert read({}) is None
    monkeypatch.setattr(registry, "enabled", lambda: False)
    assert read({}) is None
