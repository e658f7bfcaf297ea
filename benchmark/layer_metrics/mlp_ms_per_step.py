"""``mlp_ms_per_step``: device self time under the program's scope
``hvd_mlp`` — the MLP half of every block (second LayerNorm, both
matmuls, GELU, residual); forward, backward and recomputation together;
per step and chip.

A fused op carries ONE name stack. Where XLA fuses the optimizer's
update into a backward matmul (every one-chip cell today; on four chips
the all-reduces cut it out), the fused op reads under the MODEL's scope:
this reading then includes that part of the update, and
``optimizer_ms_per_step`` lacks it. A change that only moves fusion
moves this reading with no change to the layer: judge a layer by its
``fwd`` phase (``program_trace``'s earlier lines), which holds no
update, or by this reading plus ``optimizer_ms_per_step`` together."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_mlp")
