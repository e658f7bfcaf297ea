"""Tests of the benchmark itself, run on the CPU:

    python -m pytest benchmark/tests -q

They are not among the repo's tier-1 tests (``tests/``): a benchmark PR
adds files under the benchmark's own directories only."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# A kind added as a file: it measures nothing and reports fixed values.
FIXED_KIND = """
def run(ctx):
    return {"setup_s": 1.5, "checks": {"ran": True}, "attempted": 3,
            "failed": 0, "record": {"chips": len(ctx.devices)},
            "values": {"setup_s": 1.5, "train_tok_s_per_chip": 7.0}}
"""

TINY_CONFIG = {
    "source": "test", "family": "gpt2-dense", "n_embd": 256, "n_head": 2,
    "n_inner": 512, "n_layer": 2, "n_positions": 256, "vocab_size": 512,
    "layer_norm_epsilon": 1e-5, "reduced": []}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A copy of BENCHMARK.json and ``benchmark/`` to which a tiny
    configuration, traffic mixes (three layouts of kind ``train`` and
    one of a new kind), their cells and two per-layer metrics are ADDED
    as files and entries — no file of the copy is edited except the
    manifest, as a later PR would do it."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(ROOT / "BENCHMARK.json", encoding="utf-8") as f:
        manifest = json.load(f)
    bench = root / "benchmark"
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    traffic = json.loads(
        (bench / "traffic" / "pretrain-s2k-b2.json").read_text())
    traffic.update(seq=256, sequences=64, loss_chunk=128)
    mixes = {"tiny-dp1": (1, {"dp": 1}), "tiny-dp4": (4, {"dp": 4}),
             "tiny-dp2tp2": (4, {"dp": 2, "tp": 2}),
             "tiny-fixed": (1, None)}
    for name, (chips, layout) in mixes.items():
        mix = dict(traffic, layout=layout) if layout else {"kind": "fixed"}
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        manifest["workloads"].append(
            {"name": name, "config": "tiny", "traffic": name,
             "chips": chips, "why": "test"})
    (bench / "kinds" / "fixed.py").write_text(FIXED_KIND)
    manifest["configs"].append(
        {"name": "tiny", "source": "test",
         "file": "benchmark/configs/tiny.json", "reduced": [],
         "why": "test"})
    (bench / "layer_metrics" / "tokens_per_step.py").write_text(
        "def read(run):\n    return run['tokens_per_step']\n")
    (bench / "layer_metrics" / "never_there.py").write_text(
        "def read(run):\n    return None\n")
    for name in ("tokens_per_step", "never_there"):
        manifest["per_layer"].append(
            {"name": name, "unit": "tokens", "better": "higher",
             "source": "program_counter", "layer": "input",
             "moves": "train_tok_s_per_chip",
             "workloads": ["tiny-dp1", "tiny-dp4", "tiny-dp2tp2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root
