"""``optimizer_ms_per_step``: device self time under the program's scope
``hvd_optimizer`` — the optimizer's update and its application to the
parameters; per step and chip — of the ops that KEPT that name.

A fused op carries ONE name stack. Where XLA fuses the update into a
backward matmul (every one-chip cell today: 4 ms here against 33 ms on
four chips, where the all-reduces cut the update out of the fusions),
the fused op reads under ``hvd_mlp`` / ``hvd_attn`` / ``hvd_loss_head``
backward, and this reading is only the unfused rest. So it is a floor,
not the optimizer's cost: a change that only moves fusion moves it and
the model's readings in opposite directions with no change to either
layer. Read it together with them (their sum is what holds)."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_optimizer")
