"""``step_trace_s``: JAX's own seconds of tracing the step
(``hvd_train_step``) to a jaxpr in set-up: the program's counter
``hvdtpu_compile_seconds_total{program="hvd_train_step",phase="trace"}``
(``registry.watch_compiles``), read from the program's registry after
the run. With ``step_to_mlir_s`` it is ``lower_s`` split in two.
Nothing from a program without the counter."""

FAMILY = "hvdtpu_compile_seconds_total"
KEY = 'phase="trace",program="hvd_train_step"'


def read(run):
    from horovod_tpu.observability import registry
    if not registry.enabled():
        return None
    values = registry.snapshot(prefix=FAMILY).get(FAMILY, {}).get("values")
    return values.get(KEY) if values else None
