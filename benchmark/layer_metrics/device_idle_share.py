"""``device_idle_share``: 1 - (union of device-op intervals) / (traced
window), in percent, mean over the chips."""


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
