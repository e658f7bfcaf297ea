"""``hybrid_loss_head_ms_per_step``: device self time under the
program's scope ``hvd_loss_head`` (final norm, the untied head's
chunked projection, log-softmax and loss; forward, backward and the
chunks' recomputation) — ``loss_head_ms_per_step`` for the cell that
cannot join that metric's list; per step and chip."""

from benchmark import scope_trace


def read(run):
    trace = scope_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_loss_head") or None
