"""``programs_compiled``: programs XLA really compiled in the run, not
loaded from the persistent cache: the program's counter
``hvdtpu_compile_programs_total`` with ``cache="miss"`` (compiled and
written) or ``cache="off"`` (compiled, the cache not used: under its
minimum compile time), over every program, read from the program's
registry after the run. On a warm start: the programs under the
cache's threshold, a constant of the cell, plus any the cache evicted.
Nothing from a program without the counter."""

FAMILY = "hvdtpu_compile_programs_total"
COMPILED = ('cache="miss",', 'cache="off",')


def read(run):
    from horovod_tpu.observability import registry
    if not registry.enabled():
        return None
    values = registry.snapshot(prefix=FAMILY).get(FAMILY, {}).get("values")
    if not values:
        return None
    return sum(n for key, n in values.items() if key.startswith(COMPILED))
