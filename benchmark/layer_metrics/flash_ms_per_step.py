"""``flash_ms_per_step``: device durations of the flash kernels' events
in the trace (forward, its recomputation, dK/dV and dQ: the Mosaic
calls the program named ``hvd_flash_<kernel>``), per step and chip. No
such call in the trace is nothing to read, not 0 ms."""


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.flash or not trace.steps:
        return None
    seconds = sum(s for _, s in trace.flash.values())
    return 1e3 * seconds / trace.steps
