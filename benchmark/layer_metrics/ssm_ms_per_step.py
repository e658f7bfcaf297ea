"""``ssm_ms_per_step``: device self time under the program's scope
``hvd_ssm`` — the Mamba-2 mixers whole: norm, both projections, and
inside it ``hvd_ssm_conv`` (the causal convolution and its SiLU) and
``hvd_ssd_scan`` (the chunked recurrence); forward, backward and
recomputation together; per step and chip. A fused op carries one name
(``mlp_ms_per_step``): the backward includes what of the optimizer's
update XLA fused into its matmuls."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_ssm", "hvd_ssm_", "hvd_ssd_scan") or None
