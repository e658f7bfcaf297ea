"""``linattn_kernel_ms_per_step``: device self time under the program's
scope ``hvd_delta_rule`` (``ops/delta_rule.py``: the two Pallas kernels,
the cumulative sums of the decays and, at head widths that are no lane
multiple, the columns of zeros put on ``q``, ``k``, ``v`` and cut from
the output); forward, backward and recomputation; per step and chip.
The same scope as ``delta_rule_ms_per_step``, whose list of cells is an
accepted entry."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_delta_rule") or None
