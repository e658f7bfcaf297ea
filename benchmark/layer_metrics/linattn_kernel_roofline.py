"""``linattn_kernel_roofline``: the least time the chip could take for
the delta-rule recurrences of one step (the FLOPs and bytes the chunked
algorithm NEEDS at the PUBLISHED head widths, forward and backward,
from the cell's shapes: ``olmo_hybrid_flops.delta_rule_work_of``;
neither recomputation nor the columns of zeros that bring a head to
whole lanes are credited), the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, times the linear-attention layers, over the
time under ``hvd_delta_rule``."""

from benchmark import flops, olmo_hybrid_flops, program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None or run.get("peaks") is None:
        return None
    spent = trace.per_step_ms("hvd_delta_rule")
    if not spent:
        return None
    config, traffic = run["cell"]["config"], run["cell"]["traffic"]
    work = olmo_hybrid_flops.delta_rule_work_of(
        config, traffic["batch_per_chip"], traffic["seq"])
    least, bound = flops.least_seconds(*work, run["peaks"])
    print(f"[bench] linattn_kernel_roofline: bound by {bound}", flush=True)
    layers = olmo_hybrid_flops.layer_kinds(config).count("linear_attention")
    return 100.0 * layers * least / (spent / 1e3)
