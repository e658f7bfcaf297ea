"""``moe_routed_ms_per_step``: device self time under the program's
scope ``hvd_moe_routed`` — the routed experts held here alone (the
grouped matmuls over them and the routing weights' gating, apart from
the router, the latent projections and the shared expert); per step and
chip."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_moe_routed") or None
