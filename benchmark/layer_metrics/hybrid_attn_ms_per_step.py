"""``hybrid_attn_ms_per_step``: device self time under the program's
scope ``hvd_attn`` plus the flash kernels' ``hvd_flash_*`` inside it —
``attn_ms_per_step`` for the cell that cannot join that metric's list;
per step and chip."""

from benchmark import scope_trace


def read(run):
    trace = scope_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_attn", "hvd_flash_") or None
