"""``delta_rule_roofline``: the least time the chip could take for the
delta-rule recurrences of one step — the FLOPs and bytes the chunked
algorithm NEEDS, forward and backward, from the cell's shapes
(``qwen3_next_flops.delta_rule_work``; recomputation not credited), the
larger of FLOPs over the bf16 peak and bytes over the HBM peak, times
the Gated DeltaNet layers — over the time under ``hvd_delta_rule``."""

from benchmark import flops, program_trace, qwen3_next_flops


def read(run):
    trace = program_trace.load(run)
    if trace is None or run.get("peaks") is None:
        return None
    spent = trace.per_step_ms("hvd_delta_rule")
    if not spent:
        return None
    config, traffic = run["cell"]["config"], run["cell"]["traffic"]
    work = qwen3_next_flops.delta_rule_work_of(
        config, traffic["batch_per_chip"], traffic["seq"])
    least, bound = flops.least_seconds(*work, run["peaks"])
    print(f"[bench] delta_rule_roofline: bound by {bound}", flush=True)
    layers = qwen3_next_flops.mixer_kinds(config).count("D")
    return 100.0 * layers * least / (spent / 1e3)
