"""``linattn_ms_per_step``: device self time under the program's scope
``hvd_gdn`` (the Gated DeltaNet mixers of the dense hybrid whole: both
projections, the gated norm, the norm on the mixer's output and the
residual add, and inside it ``hvd_gdn_conv``, the causal convolution
and its SiLU, and ``hvd_delta_rule``, the chunked recurrence with its
columns of zeros); forward, backward and recomputation together; per
step and chip. A fused op carries one name (``mlp_ms_per_step``): the
backward includes what of the optimizer's update XLA fused into its
matmuls. The same scopes as ``gdn_ms_per_step``, whose list of cells is
an accepted entry."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_gdn", "hvd_gdn_", "hvd_delta_rule") or None
