"""``hybrid_optimizer_ms_per_step``: device self time of the ops that
KEPT the scope ``hvd_optimizer`` (``optimizer.update`` and
``apply_updates`` of the in-jit step) — ``optimizer_ms_per_step`` for
the cell that cannot join that metric's list; per step and chip. What
of the update XLA fuses into a backward matmul reads under that
layer's name, so this is a floor (docs/tracing.md#names)."""

from benchmark import scope_trace


def read(run):
    trace = scope_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_optimizer") or None
