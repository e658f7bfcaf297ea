"""``ssd_scan_roofline``: the least time the chip could take for the
scans of one step — the FLOPs and bytes the chunked algorithm NEEDS,
forward and backward, from the cell's shapes
(``nemotron_h_flops.ssd_scan_work``; recomputation not credited), the
larger of FLOPs over the bf16 peak and bytes over the HBM peak, times
the Mamba-2 layers — over the time under ``hvd_ssd_scan``."""

from benchmark import flops, nemotron_h_flops, program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None or run.get("peaks") is None:
        return None
    spent = trace.per_step_ms("hvd_ssd_scan")
    if not spent:
        return None
    config, traffic = run["cell"]["config"], run["cell"]["traffic"]
    work = nemotron_h_flops.scan_work_of(
        config, traffic["batch_per_chip"], traffic["seq"])
    least, bound = flops.least_seconds(*work, run["peaks"])
    print(f"[bench] ssd_scan_roofline: bound by {bound}", flush=True)
    layers = config["hybrid_override_pattern"].count("M")
    return 100.0 * layers * least / (spent / 1e3)
