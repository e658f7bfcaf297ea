"""``expert_layer_ms_per_step``: device self time of the expert layers
whole, kernels included — the program's scopes ``hvd_moe`` (norm, the
gated activation over the buffer, residual) with ``hvd_moe_router``,
``hvd_moe_dispatch`` and ``hvd_moe_shared`` inside it, and the three
grouped-matmul kernels ``hvd_gmm_fwd`` / ``_drows`` / ``_dw``, whose
names lie outside the ``hvd_moe_`` prefix (so ``moe_ms_per_step``'s
reader would leave them out); forward, backward and recomputation
together; per step and chip. A fused op carries one name
(``mlp_ms_per_step``)."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_moe", "hvd_moe_", "hvd_gmm_") or None
