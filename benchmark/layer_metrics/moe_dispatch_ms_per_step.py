"""``moe_dispatch_ms_per_step``: device self time under the program's
scope ``hvd_moe_dispatch`` — what the expert layer spends on bringing
rows to their experts and back: the plan of the rows (two sorts), the
gather of the tokens into the buffer sorted by expert and the weighted
sum back to the tokens, and their backwards; every one a pass of static
shape over the worst-case buffer, so its time does not follow the
routing; per step and chip."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_moe_dispatch") or None
