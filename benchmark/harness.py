"""One run of one cell: what every kind of traffic shares. ``run.py`` is
the command; this is what it calls.

A cell is read from files alone (``manifest.cell``): a configuration, a
traffic mix and the metrics BENCHMARK.json lists for it. The traffic
file's ``kind`` names the module that runs it,
``benchmark/kinds/<kind>.py``, found by name as the per-layer readers
are; nothing here is keyed on a cell's name, a kind or a layout.

Here: the chip checks (no chip is a refusal, never a CPU fallback), the
count of compilations, the profiler and the reduction of its trace, the
per-layer readers, the peak memory, the result line and the run's file
under ``benchmark_out/``.

A kind's ``run(ctx)`` gets ``ctx.cell`` (``manifest.cell``),
``ctx.seed``, ``ctx.seconds``, ``ctx.trace``, ``ctx.t_start`` (process
start, for ``setup_s``), ``ctx.devices`` (the cell's chips),
``ctx.watch`` (``CompileWatch``) and ``ctx.profile()`` (a context
manager: the profiler on, for the traced run). It returns a dict with
``setup_s``, ``checks`` (name -> bool; ``correct`` is all of them),
``attempted``, ``failed``, ``record`` (what else goes into the run's
file) and, untraced, ``values`` (every end-to-end metric it measures,
by name) or, traced, ``layer_run`` (what the readers under
``layer_metrics/`` read, beside what is added here: ``cell``, ``chips``,
``peaks``, ``trace``, ``planes``, ``memory_peak_bytes``). ``compared``,
where a kind gives it, is every number its checks compared beside its
limit (name -> ``[number, limit]``): it goes last into the result line
and, by ``run.py``, on the last lines of standard error."""

import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

from . import manifest, peaks

OUT_DIR = "benchmark_out"


class Refused(RuntimeError):
    """The run cannot be made here (no chip, too few chips, a file the
    cell needs is wrong): non-zero exit and no result line."""


def say(msg):
    print(f"[bench] {msg}", flush=True)


class CompileWatch:
    """Counts what JAX compiles or loads from its persistent cache, by
    JAX's own monitoring events; ``requests`` inside the window must
    stay 0."""

    def __init__(self):
        import jax
        self.requests = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1


def _profiler(trace_dir):
    """``ctx.profile``: the JAX profiler writing under ``trace_dir``."""
    def profile():
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        # The Python tracer stays off: it slows the host it shares with
        # the loop and fills the trace with frames no metric reads.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        return jax.profiler.trace(str(trace_dir), profiler_options=options)
    return profile


def run_cell(name, seed, seconds, trace, *, root=manifest.ROOT,
             t_start=None, allow_cpu=False):
    """Run cell ``name`` once; returns the result line as a dict.

    ``allow_cpu`` is passed by the tests alone (tiny sizes on the CPU,
    interpreted kernel): the command refuses without a chip."""
    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(root)
    try:
        cell = manifest.cell(name, root)
        run_kind = manifest.load_kind(cell["kinds_dir"],
                                      cell["traffic"]["kind"])
    except manifest.ManifestError as e:
        raise Refused(str(e)) from e

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise Refused(f"no accelerator: JAX reports platform "
                      f"{platform!r}; nothing was measured")
    if len(devices) < cell["chips"]:
        raise Refused(f"cell {name} needs {cell['chips']} chip(s), JAX "
                      f"reports {len(devices)}")
    devices = devices[:cell["chips"]]
    kind = devices[0].device_kind
    chip_peaks = None if allow_cpu and platform != "tpu" else \
        peaks.peaks_for(kind)
    say(f"cell {name}: {cell['config_name']} x {cell['traffic_name']} "
        f"on {len(devices)} x {kind} ({platform}); seed {seed}, window "
        f"{seconds} s, trace {int(trace)}")

    out_dir = root / OUT_DIR / name
    trace_dir = out_dir / f"trace-seed-{seed}"
    ran = run_kind(SimpleNamespace(
        cell=cell, seed=seed, seconds=seconds, trace=trace,
        t_start=t_start, devices=devices, watch=CompileWatch(),
        profile=_profiler(trace_dir)))

    checks = ran["checks"]
    for key, ok in checks.items():
        say(f"check {key}: {'ok' if ok else 'FAILED'}")
    stats = [d.memory_stats() for d in devices]
    peak_bytes = max((s or {}).get("peak_bytes_in_use", 0) for s in stats)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}

    summary = None
    if trace:
        found = sorted(trace_dir.rglob("*.xplane.pb"))
        metrics, summary = _per_layer(
            cell, dict(ran["layer_run"], cell=cell, chips=len(devices),
                       peaks=chip_peaks, memory_peak_bytes=peak_bytes),
            found[-1] if found else None)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
    else:
        metrics = _end_to_end(cell, ran["values"])

    result = {"correct": all(checks.values()),
              "attempted": ran["attempted"], "failed": ran["failed"],
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.top_gaps(10)}
    result["compared"] = ran.get("compared", {})
    record = dict(ran["record"], cell=name, seed=seed, seconds=seconds,
                  trace=trace, setup_s=ran["setup_s"], checks=checks,
                  result=result)
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"seed-{seed}-trace-{int(bool(trace))}.json", "w",
              encoding="utf-8") as f:
        json.dump(record, f, indent=1)
    return result


def _per_layer(cell, run, xplane):
    """The traced run's metrics: every per-layer metric of the cell
    whose reader (``layer_metrics/<name>.py``) finds something to read.
    Returns them with the trace's summary (``None`` without a device
    plane)."""
    from . import trace_reduce
    summary = None
    if xplane is not None:
        # parsed once: the readers of the program's names reduce the
        # same planes (``program_trace.load``)
        run["planes"] = trace_reduce.read_planes(xplane)
        summary = trace_reduce.summarize_planes(run["planes"])
        if not summary.devices:
            say("the trace holds no device plane: trace metrics are "
                "left out")
            summary = None
    run["trace"] = summary
    metrics = {}
    for m in cell["per_layer"]:
        value = manifest.load_reader(cell["readers_dir"], m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, summary


def _end_to_end(cell, values):
    """The untraced run's metrics: the cell's end-to-end metrics, each
    of which the kind has to have measured."""
    metrics = {}
    for m in cell["end_to_end"]:
        if m["name"] not in values:
            raise Refused(f"end-to-end metric {m['name']!r} is not one "
                          f"kind {cell['traffic']['kind']!r} measures")
        metrics[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return metrics
