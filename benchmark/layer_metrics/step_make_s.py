"""``step_make_s``: the step builder's ``make()`` in set-up (the
optimizer's trace by ``zero.is_elementwise``, the update and state
layouts, the jitted step built): the program's span ``hvd/step/make``
through its counter ``hvdtpu_setup_seconds_total{phase="make"}``, read
from the program's registry after the run. Nothing from a program
without the counter."""

FAMILY = "hvdtpu_setup_seconds_total"
KEY = 'phase="make"'


def read(run):
    from horovod_tpu.observability import registry
    if not registry.enabled():
        return None
    values = registry.snapshot(prefix=FAMILY).get(FAMILY, {}).get("values")
    return values.get(KEY) if values else None
