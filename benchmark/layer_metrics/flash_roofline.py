"""``flash_roofline``: the least time the chip could take for the flash
kernels' calls in the traced window — each call's FLOPs and bytes from
its sizes (``flops.flash_kernel_work``, query/key and value widths
apart), the larger of FLOPs over the bf16 peak and bytes over the HBM
peak — over the kernels' time. The kernels are the Mosaic calls the
program named ``hvd_flash_<kernel>`` (``trace_reduce.flash_kind``); a
trace without one gives nothing to read. At head_dim 128 every call is
bound by compute (printed)."""

from benchmark import flops


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.flash or run.get("peaks") is None:
        return None
    least = spent = 0.0
    bounds = set()
    for (kind, *sizes), (calls, seconds) in trace.flash.items():
        work = flops.flash_kernel_work(kind, *sizes)
        t, bound = flops.least_seconds(*work, run["peaks"])
        least += calls * t
        spent += seconds
        bounds.add(bound)
    print(f"[bench] flash_roofline: bound by {'/'.join(sorted(bounds))}",
          flush=True)
    return 100.0 * least / spent if spent else None
