"""``input_queue_wait_ms_per_step``: what the training loop waits for
the prefetcher's queue — the program's own ``hvd/data/wait`` spans
(around the consumer's ``q.get``) inside the traced window, per step.
``input_wait_ms_per_step`` times the same layer from outside and
includes ``shard_batch``."""

from benchmark import program_trace

SPAN = "hvd/data/wait"


def read(run):
    trace = program_trace.load(run)
    if trace is None or trace.spans is None or not trace.steps:
        return None
    return 1e3 * trace.spans.get(SPAN, (0, 0.0))[1] / trace.steps
