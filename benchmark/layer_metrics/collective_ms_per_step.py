"""``collective_ms_per_step``: time the collective ops (all-reduce and
its kin) take on a chip, per step: the union of their intervals in the
trace. 0 on one chip."""


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.steps:
        return None
    return 1e3 * trace.collective_s / trace.steps
