"""``step_ms_p90``: 90th percentile of the same synced step times as
``step_ms_p50``. With a few tens of samples it is a coarse tail: the
first end-to-end candidate once a stall can be told from a shift."""

import statistics


def read(run):
    steps = run.get("step_seconds")
    if not steps or len(steps) < 2:
        return None
    return 1e3 * statistics.quantiles(steps, n=10, method="inclusive")[-1]
