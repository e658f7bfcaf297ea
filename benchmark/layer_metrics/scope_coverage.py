"""``scope_coverage``: the share of the traced window's device self
time that carries any of the program's names — the guard that the names
have not rotted (a refactor that drops a scope shows here before the
per-scope readings quietly shrink)."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None or trace.names is None or not trace.busy_s:
        return None
    return 100.0 * (1.0 - trace.seconds(program_trace.UNSCOPED)
                    / trace.busy_s)
