"""``setup_compile_s``: what compiling cost the whole run, by JAX's own
events: the program's counter ``hvdtpu_compile_seconds_total`` summed
over every program and phase (trace, lower, compile or cache load),
read from the program's registry after the run. It holds the weights'
init, the optimizer state's init, the reference's and the gradient
check's programs and the step; on more than one chip also the replica
check after the window, a constant of the cell. Nothing from a program
without the counter."""

FAMILY = "hvdtpu_compile_seconds_total"


def read(run):
    from horovod_tpu.observability import registry
    if not registry.enabled():
        return None
    values = registry.snapshot(prefix=FAMILY).get(FAMILY, {}).get("values")
    return sum(values.values()) if values else None
