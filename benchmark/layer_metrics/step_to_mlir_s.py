"""``step_to_mlir_s``: JAX's own seconds of lowering the step's jaxpr
to an MLIR module in set-up (``mlir.jaxpr_subcomp``): the program's
counter ``hvdtpu_compile_seconds_total{program="hvd_train_step",
phase="lower"}``, read from the program's registry after the run. With
``step_trace_s`` it is ``lower_s`` split in two. Nothing from a program
without the counter."""

FAMILY = "hvdtpu_compile_seconds_total"
KEY = 'phase="lower",program="hvd_train_step"'


def read(run):
    from horovod_tpu.observability import registry
    if not registry.enabled():
        return None
    values = registry.snapshot(prefix=FAMILY).get(FAMILY, {}).get("values")
    return values.get(KEY) if values else None
