"""The program's names in the run's trace, by an OPEN rule: an op
belongs to the innermost ``hvd_<name>`` of its name stack, whatever the
name — so a scope a later change adds is read without an edit here.

``program_trace.NAME`` is a closed list (``hvd_embed|attn|mlp|...``): a
new scope reads as ``unscoped`` there, and its file may not be edited by
the PR that adds a cell. This is a copy of its reducer with the open
rule, built on the same ``trace_reduce`` / ``xplane`` pieces and
returning the same ``ProgramTrace``; folding the two is a ``benchmark``
issue's (PERF.md section 7).

One name is NOT a scope: the jitted function's own
(``jit(hvd_train_step)/...`` heads every stack), so a name directly
inside ``jit(`` is skipped."""

import collections
import re

from . import program_trace, trace_reduce, xplane
from .program_trace import UNSCOPED, ProgramTrace

NAME = re.compile(r"(?<![A-Za-z0-9_])(?<!jit\()hvd_[a-z0-9_]+"
                  r"(?![A-Za-z0-9_])")
CACHE_KEY = "scope_trace"


def name_of_stack(stack):
    """The innermost scope name in a name stack (a string)."""
    found = NAME.findall(stack)
    return found[-1] if found else UNSCOPED


def name_of(op):
    return name_of_stack(str(op.stats.get("tf_op", "")))


def reduce(planes):
    """``ProgramTrace`` (device names only, no host spans) of a trace's
    planes; ``None`` without a device plane that ran the step."""
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
    out.busy_s /= out.devices
    if set(names) - {UNSCOPED}:
        out.names = {name: {phase: s / out.devices
                            for phase, s in phases.items()}
                     for name, phases in names.items()}
    return out


def load(run):
    """The run's ``ProgramTrace`` under the open rule, read once and
    kept in ``run``; ``None`` where the run has no trace with a device
    plane. The split by name and phase goes on earlier lines."""
    if CACHE_KEY not in run:
        result = None
        path = (program_trace._newest_trace(run)
                if run.get("trace") is not None else None)
        if path is not None:
            result = reduce(xplane.read(
                path, want_plane=lambda n: bool(
                    trace_reduce.DEVICE_PLANE.match(n))))
            if result is not None and result.names is not None:
                for name, phases in sorted(
                        result.names.items(),
                        key=lambda kv: -sum(kv[1].values())):
                    parts = " ".join(
                        f"{phase or 'none'}={1e3 * s / result.steps:.3f}"
                        for phase, s in sorted(phases.items()))
                    print(f"[bench] scope_trace: {name} "
                          f"{1e3 * sum(phases.values()) / result.steps:.3f}"
                          f" ms/step ({parts})", flush=True)
        run[CACHE_KEY] = result
    return run[CACHE_KEY]
