"""``gmm_ms_per_step``: device time of the grouped-matmul kernels
(``ops/grouped_matmul.py``: the Mosaic calls the program named
``hvd_gmm_fwd`` / ``_drows`` / ``_dw``), forward, backward and
recomputation, per step and chip: the one part of the expert layer
whose work follows the routing (it skips the tiles beyond the real
rows: about 0.3 us a real row, 1% of the step between the first
batch's rows and none)."""

from benchmark import program_trace


def read(run):
    trace = program_trace.load(run)
    if trace is None:
        return None
    return trace.per_step_ms("hvd_gmm_") or None
