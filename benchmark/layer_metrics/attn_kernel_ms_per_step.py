"""``attn_kernel_ms_per_step``: device time of the attention kernels,
found by the name the program gives them (``hvd_flash_<kernel>`` in the
op's name stack), however many kernels there are and whatever they
return; per step and chip."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    # no kernel in the trace is nothing to read, not 0 ms
    return trace.per_step_ms(program_trace.KERNEL_PREFIX) or None
