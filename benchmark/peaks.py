"""The one table of published peaks, keyed by ``device_kind``.

Source for the v5e row: Google Cloud documentation, "TPU v5e" system
architecture (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip). A
device that is not in the table is an error, never a default: a share
of a guessed peak is worse than none. (Copied in spirit from
``bench.py``'s ``PEAK_TFLOPS_BY_KIND``; that table and the one in
``observability/step_metrics.py`` are listed in PERF.md for deletion.)"""

PEAKS = {
    # device_kind as jax reports it on the chip machine
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(ValueError):
    """The device's kind has no row in PEAKS."""


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peak for device kind {device_kind!r}; the "
            f"table has {sorted(PEAKS)}") from None
