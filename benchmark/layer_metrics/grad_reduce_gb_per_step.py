"""``grad_reduce_gb_per_step``: gradient bytes one chip hands to
cross-device reductions in one step, in GB (1e9 bytes): the program's
gauge ``hvdtpu_jit_grad_reduce_bytes``, set where ``reduce_gradients``
is traced, read from the program's registry after the run. 0 on one
chip; nothing from a program without the gauge."""

FAMILY = "hvdtpu_jit_grad_reduce_bytes"


def read(run):
    from horovod_tpu.observability import registry
    if not registry.enabled():
        return None
    values = registry.snapshot(prefix=FAMILY).get(FAMILY, {}).get("values")
    if not values or "" not in values:
        return None
    return values[""] / 1e9
