"""``lower_s``: host clock around ``step.lower(...)`` in set-up —
tracing the step and building its MLIR, which no cache serves."""


def read(run):
    return run["spans"].get("lower_s")
