"""``hybrid_scope_coverage``: the share of the traced window's device
self time that carries ANY ``hvd_*`` scope name (``scope_trace``'s open
rule) — ``scope_coverage`` for the cell that cannot join that metric's
list."""

from benchmark import scope_trace


def read(run):
    trace = scope_trace.load(run)
    if trace is None or trace.names is None or not trace.busy_s:
        return None
    return 100.0 * (1.0 - trace.seconds(scope_trace.UNSCOPED)
                    / trace.busy_s)
