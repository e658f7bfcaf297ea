"""``mfu``: model FLOPs per step (``flops.model_flops_per_step``: 6 N T
plus causal attention, recomputation not counted) over the median synced
step time, the chips and the published bf16 peak."""

import statistics


def read(run):
    steps = run.get("step_seconds")
    if not steps or run.get("peaks") is None:
        return None
    rate = run["model_flops_per_step"] / statistics.median(steps)
    return 100.0 * rate / run["chips"] / run["peaks"]["bf16_flops_per_s"]
