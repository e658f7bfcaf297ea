"""``hybrid_input_queue_wait_ms_per_step``: what the training loop
waits for the prefetcher's queue — the program's own ``hvd/data/wait``
spans inside the traced window, per step —
``input_queue_wait_ms_per_step`` for the cell that cannot join that
metric's list. Host spans do not depend on the scopes' names, so this
reads them as that metric does, through ``program_trace``."""

from benchmark import program_trace

SPAN = "hvd/data/wait"


def read(run):
    trace = program_trace.load(run)
    if trace is None or trace.spans is None or not trace.steps:
        return None
    return 1e3 * trace.spans.get(SPAN, (0, 0.0))[1] / trace.steps
