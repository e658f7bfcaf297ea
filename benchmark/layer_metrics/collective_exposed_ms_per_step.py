"""``collective_exposed_ms_per_step``: the part of the collectives'
time during which no compute op runs on that chip, per step — what
overlap would win back. 0 on one chip."""


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.steps:
        return None
    return 1e3 * trace.collective_exposed_s / trace.steps
