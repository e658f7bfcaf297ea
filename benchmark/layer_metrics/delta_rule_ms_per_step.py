"""``delta_rule_ms_per_step``: device self time under the program's
scope ``hvd_delta_rule`` (``ops/delta_rule.py``: the chunked gated
delta rule alone, XLA ops: the chunk matrices, the triangular solve,
the scan over the chunks and the output); forward, backward and
recomputation; per step and chip."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_delta_rule") or None
