"""``flash_blocks_skipped_share``: of one head's (query block, key block)
pairs in the three flash kernels together, the share in % that the
kernels' grids do not visit (wholly above the causal diagonal): the
program's gauge ``hvdtpu_flash_block_pairs{kernel, visited}``, set where
each kernel is traced (its last traced call), read from the program's
registry after the run. 0 without ``causal``; nothing from a program
without the gauge."""

FAMILY = "hvdtpu_flash_block_pairs"


def read(run):
    from horovod_tpu.observability import registry
    if not registry.enabled():
        return None
    values = registry.snapshot(prefix=FAMILY).get(FAMILY, {}).get("values")
    if not values:
        return None
    total = sum(values.values())
    if not total:
        return None
    skipped = sum(n for key, n in values.items() if 'visited="no"' in key)
    return 100.0 * skipped / total
