"""``step_compile_s``: JAX's own seconds of the step's XLA compile, or
of its load from the persistent cache, in set-up: the program's counter
``hvdtpu_compile_seconds_total{program="hvd_train_step",
phase="compile"}``, read from the program's registry after the run; the
in-program twin of ``compile_s``. Nothing from a program without the
counter."""

FAMILY = "hvdtpu_compile_seconds_total"
KEY = 'phase="compile",program="hvd_train_step"'


def read(run):
    from horovod_tpu.observability import registry
    if not registry.enabled():
        return None
    values = registry.snapshot(prefix=FAMILY).get(FAMILY, {}).get("values")
    return values.get(KEY) if values else None
