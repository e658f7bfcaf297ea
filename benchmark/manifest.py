"""BENCHMARK.json and the files it names: a cell is found by its name,
its configuration by the entry's ``file``, its traffic mix as
``benchmark/traffic/<traffic>.json``, the traffic's kind as
``benchmark/kinds/<kind>.py`` and each per-layer metric's reader as
``benchmark/layer_metrics/<metric>.py`` — so adding any of them is new
files plus one entry, and no edit here."""

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _read_json(path):
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def load(root=ROOT):
    return _read_json(Path(root) / "BENCHMARK.json")


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(
        f"no {what} named {name!r} in BENCHMARK.json (it has: "
        f"{', '.join(e['name'] for e in entries)})")


def applies(metric, cell_name):
    """A metric with no ``workloads`` key is reported in every cell."""
    return cell_name in metric.get("workloads", [cell_name])


def cell(name, root=ROOT):
    """Everything one cell is made of, read from the files alone."""
    root = Path(root)
    manifest = load(root)
    workload = _by_name(manifest["workloads"], name, "workload")
    config_entry = _by_name(manifest["configs"], workload["config"],
                            "configuration")
    bench_dir = root / manifest["paths"][0]
    return {
        "name": name,
        "chips": int(workload["chips"]),
        "config_name": workload["config"],
        "traffic_name": workload["traffic"],
        "config": _read_json(root / config_entry["file"]),
        "traffic": _read_json(
            bench_dir / "traffic" / f"{workload['traffic']}.json"),
        "end_to_end": [m for m in manifest["end_to_end"]
                       if applies(m, name)],
        "per_layer": [m for m in manifest["per_layer"]
                      if applies(m, name)],
        "readers_dir": bench_dir / "layer_metrics",
        "kinds_dir": bench_dir / "kinds",
    }


def _load(path, what, attr):
    """Function ``attr`` of the module at ``path``, loaded by file."""
    if not path.is_file():
        raise ManifestError(f"{what} has no file at {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + re.sub(r"\W", "_", f"{path.parent.name}_{path.stem}"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, attr)


def load_reader(readers_dir, metric_name):
    """The ``read(run)`` function of ``layer_metrics/<metric>.py``."""
    return _load(Path(readers_dir) / f"{metric_name}.py",
                 f"per-layer metric {metric_name!r}", "read")


def load_kind(kinds_dir, kind):
    """The ``run(ctx)`` function of ``kinds/<kind>.py``."""
    return _load(Path(kinds_dir) / f"{kind}.py",
                 f"traffic kind {kind!r}", "run")
