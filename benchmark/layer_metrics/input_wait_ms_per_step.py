"""``input_wait_ms_per_step``: host clock around ``next(loader)`` +
``shard_batch`` inside the window, per step — what the step waits for
the input layer."""


def read(run):
    steps = run.get("step_seconds")
    if not steps:
        return None
    return 1e3 * run["spans"]["input_wait_s"] / len(steps)
