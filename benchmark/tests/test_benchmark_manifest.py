"""BENCHMARK.json against the contract's limits on names, units, keys
and files."""

import json
import math
import re

import pytest

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def test_top_level_keys_and_size(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(bench)) < 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert (runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200) <= 43200
    assert 1 <= len(bench["command"]) <= 32
    for path in bench["paths"]:
        assert PATH.match(path) and not path.startswith("/")
        assert ".." not in path.split("/")
        assert (manifest.ROOT / path).is_dir()


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"])
        assert _line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        on_disk = json.loads((manifest.ROOT / c["file"]).read_text())
        assert on_disk["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            # never a width
            assert not key.endswith(("_dim", "_rank"))
            assert key not in {"n_embd", "n_inner", "n_head",
                               "hidden_size", "intermediate_size"}
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        cell = manifest.cell(w["name"])
        assert math.prod(cell["traffic"]["layout"].values()) == w["chips"]
        manifest.load_kind(cell["kinds_dir"], cell["traffic"]["kind"])
        config = cell["config"]
        context = config.get("n_positions",
                             config.get("max_position_embeddings"))
        assert cell["traffic"]["seq"] <= context
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 4)


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert _line(m["layer"])
        layers.setdefault(m["layer"], []).append(m["name"])
        manifest.load_reader(manifest.ROOT / bench["paths"][0]
                             / "layer_metrics", m["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert set(layers) == {"entry", "input", "in-jit step", "model",
                           "kernel", "collectives", "device"}


def test_an_unknown_cell_is_an_error():
    with pytest.raises(manifest.ManifestError):
        manifest.cell("no-such-cell")
