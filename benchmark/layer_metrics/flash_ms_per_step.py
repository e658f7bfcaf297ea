"""``flash_ms_per_step``: device durations of the Mosaic flash kernels'
events in the trace (forward, its recomputation, dK/dV and dQ), per
step and chip."""


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.flash or not trace.steps:
        return None
    seconds = sum(s for _, s, _ in trace.flash.values())
    return 1e3 * seconds / trace.steps
