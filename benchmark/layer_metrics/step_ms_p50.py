"""``step_ms_p50``: median of the host-clock times of single steps,
each ended by ``block_until_ready``, with the profiler off (the traced
run's second phase; the sample count is on an earlier line)."""

import statistics


def read(run):
    steps = run.get("step_seconds")
    if not steps:
        return None
    return 1e3 * statistics.median(steps)
