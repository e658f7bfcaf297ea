"""``loss_head_ms_per_step``: device self time under the program's scope
``hvd_loss_head`` — everything after the last block (final LayerNorm,
the tied vocabulary projection, log-softmax and the chunked loss with
its recomputation); forward, backward and recomputation together; per
step and chip. Its ``bwd`` part includes whatever of the optimizer's
update XLA fused into the head's backward matmul (a fused op carries
one name stack; see ``mlp_ms_per_step``)."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_loss_head")
