"""``hybrid_attn_kernel_ms_per_step``: device time of the attention
kernels by the name the program gives them (``hvd_flash_<kernel>`` in
the op's name stack) — ``attn_kernel_ms_per_step`` for the cell that
cannot join that metric's list; per step and chip."""

from benchmark import scope_trace


def read(run):
    trace = scope_trace.load(run)
    if trace is None:
        return None
    # no kernel in the trace is nothing to read, not 0 ms
    return trace.per_step_ms("hvd_flash_") or None
