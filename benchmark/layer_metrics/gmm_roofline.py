"""``gmm_roofline``: the least time the chip could take for the grouped
matmuls of one step — the FLOPs and bytes they NEED at the rows the
held experts got on the FIRST batch, at the initial weights (the
program's probe; the buffer's other rows are no work), forward and
backward (``qwen3_next_flops.gmm_work``; recomputation not credited),
the larger of FLOPs over the bf16 peak and bytes over the HBM peak —
over the kernels' time (``gmm_ms_per_step``). With few rows an expert the
weights' bytes bound it (printed), so the work hardly follows the rows:
1.74 ms a layer at 5120 rows, 1.47 at none. That matters because the
count is an UPPER bound of the traced steps' rows: the router trains
away from the held experts while the run goes on (PERF.md section 6,
PR 37: a sixth of the first batch's rows after 16 steps), so the share
reads up to a sixth high."""

from benchmark import flops, program_trace, qwen3_next_flops


def read(run):
    trace = program_trace.load(run)
    rows, layers = run.get("moe_pairs_per_step"), run.get("moe_layers")
    if trace is None or run.get("peaks") is None or not rows or not layers:
        return None
    spent = trace.per_step_ms("hvd_gmm_")
    if not spent:
        return None
    work = qwen3_next_flops.gmm_work_of(run["cell"]["config"], rows / layers)
    least, bound = flops.least_seconds(*work, run["peaks"])
    print(f"[bench] gmm_roofline: bound by {bound}", flush=True)
    return 100.0 * layers * least / (spent / 1e3)
