"""What the program says of itself in the run's trace: the names it
puts on its own work (``docs/tracing.md#names`` in the program's docs).

- On the device, ``jax.named_scope`` names in an op's name stack (the
  ``tf_op`` stat, e.g. ``jit(hvd_train_step)/transpose(jvp(hvd_attn))/
  hvd_flash_dq/pallas_call``). The rule is OPEN: an op belongs to the
  INNERMOST ``hvd_<name>`` of its stack (the last in it), whatever the
  name, so a scope that a later change or another model adds
  (``hvd_ssd_scan``, ``hvd_moe_routed``) is read without an edit here;
  to ``unscoped`` with none. The jitted function's own name
  (``jit(hvd_train_step)``) is no scope. A fused op carries the one
  stack XLA kept for it. The rule itself is ``trace_reduce.NAME``,
  which also finds the flash kernels by it.
- On the host, the program's spans ``hvd/<layer>/<what>``
  (``jax.profiler.TraceAnnotation``), beside the benchmark's own
  ``bench/...`` spans, which ``trace_reduce`` reads and these are not.

Both are reduced inside ``trace_reduce``'s own step window and with its
``self_seconds``, so the names' self times add up to its ``busy_s``.

A reader gets ``run``; ``load`` reduces the planes the harness parsed
for the run (``run["planes"]``: the trace is parsed once, for
``trace_reduce`` and for this) and keeps the result in ``run``. A trace
without a device plane, or from a program that names nothing (every
trace before the names existed), gives nothing to read: ``None``, never
0."""

import collections
from dataclasses import dataclass

from . import trace_reduce
from .trace_reduce import (KERNEL_PREFIX, NAME, UNSCOPED,  # noqa: F401
                           name_of, name_of_stack)

SPAN_PREFIX = "hvd/"
CACHE_KEY = "program_trace"


@dataclass
class ProgramTrace:
    """Seconds are per chip (mean over the chips) over the whole traced
    window of ``steps`` steps."""
    steps: int = 0
    devices: int = 0
    busy_s: float = 0.0
    # name -> {phase ('fwd', 'bwd', 'remat', ''): self seconds}; None
    # where no op of the window carries a name
    names: dict = None
    # 'hvd/...' -> (count, seconds) inside the window, clipped to it;
    # None where the trace holds no such span at all
    spans: dict = None
    window: tuple = None    # (lo, hi) ns of the first chip's window

    def seconds(self, *names):
        """Self seconds under any of ``names``; a name ending in ``_``
        is a prefix (``hvd_flash_``)."""
        return sum(sum(phases.values())
                   for name, phases in (self.names or {}).items()
                   if any(name == n or (n.endswith("_")
                                        and name.startswith(n))
                          for n in names))

    def per_step_ms(self, *names):
        if self.names is None or not self.steps:
            return None
        return 1e3 * self.seconds(*names) / self.steps


def reduce(planes):
    """``ProgramTrace`` of a trace's planes; ``None`` without a device
    plane that ran the step."""
    names = collections.defaultdict(collections.Counter)
    out = ProgramTrace()
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        ops_line = plane.line(trace_reduce.OPS_LINE)
        modules = plane.line(trace_reduce.MODULES_LINE)
        if ops_line is None or modules is None:
            continue
        window = trace_reduce._step_window(modules.events)
        if window is None:
            continue
        lo, hi, out.steps = window
        out.window = out.window or (lo, hi)
        out.devices += 1
        for op, seconds in trace_reduce.self_seconds(
                trace_reduce._clipped(ops_line.events, lo, hi)):
            names[name_of(op)][trace_reduce.phase_of(op)] += seconds
            out.busy_s += seconds
    if not out.devices:
        return None
    n = out.devices
    out.busy_s /= n
    if set(names) - {UNSCOPED}:
        out.names = {name: {phase: s / n for phase, s in phases.items()}
                     for name, phases in names.items()}
    spans = [e for p in planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith(SPAN_PREFIX)]
    if spans:
        lo, hi = out.window
        out.spans = {}
        for e in trace_reduce._clipped(spans, lo, hi):
            count, seconds = out.spans.get(e.name, (0, 0.0))
            out.spans[e.name] = (count + 1, seconds + e.seconds)
    return out


def load(run):
    """The run's ``ProgramTrace``, reduced once and kept in ``run``;
    ``None`` where the run has no trace with a device plane."""
    if CACHE_KEY not in run:
        planes = run.get("planes")
        run[CACHE_KEY] = reduce(planes) if planes else None
        _say(run[CACHE_KEY])
    return run[CACHE_KEY]


def _say(trace):
    """The split by name and phase, and the spans, on earlier lines of
    the run: what ``PERF.md`` section 5 is written from."""
    if trace is None:
        return
    if trace.names is not None:
        for name, phases in sorted(trace.names.items(),
                                   key=lambda kv: -sum(kv[1].values())):
            parts = " ".join(
                f"{phase or 'none'}={1e3 * s / trace.steps:.3f}"
                for phase, s in sorted(phases.items()))
            print(f"[bench] program_trace: {name} "
                  f"{1e3 * sum(phases.values()) / trace.steps:.3f} ms/step"
                  f" ({parts})", flush=True)
        print(f"[bench] program_trace: busy "
              f"{1e3 * trace.busy_s / trace.steps:.3f} ms/step over "
              f"{trace.steps} steps, {trace.devices} chip(s)", flush=True)
    for name, (count, seconds) in sorted((trace.spans or {}).items()):
        print(f"[bench] program_trace: span {name} x{count} "
              f"{1e3 * seconds / trace.steps:.4f} ms/step", flush=True)
