"""``gated_mlp_ms_per_step``: device self time under the program's
scope ``hvd_mlp`` where it names a GATED MLP as a dense layer's
feed-forward part (``models/olmo_hybrid.py::_mlp_layer``: the gate and
up projections as one matmul, ``silu(gate) * up``, the down projection,
the norm on its output and the residual add); forward, backward and
recomputation together; per step and chip. The same scope as
``mlp_ms_per_step`` (which says what a fused op's one name does to the
reading), whose list of cells is an accepted entry."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_mlp") or None
