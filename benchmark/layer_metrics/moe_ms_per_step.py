"""``moe_ms_per_step``: device self time under the program's scope
``hvd_moe`` — the expert layers whole: norm, the two latent
projections, and inside it ``hvd_moe_router``, ``hvd_moe_routed`` and
``hvd_moe_shared``; forward, backward and recomputation together; per
step and chip. A fused op carries one name (``mlp_ms_per_step``)."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_moe", "hvd_moe_") or None
