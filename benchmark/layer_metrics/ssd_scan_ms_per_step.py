"""``ssd_scan_ms_per_step``: device self time under the program's scope
``hvd_ssd_scan`` — the chunked state-space recurrence alone, forward,
backward and every recomputation of it; per step and chip."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_ssd_scan") or None
