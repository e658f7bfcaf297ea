"""``flash_roofline``: the least time the chip could take for the flash
kernels' calls in the traced window — each call's FLOPs and bytes from
its shapes (``flops.flash_kernel_work``), the larger of FLOPs over the
bf16 peak and bytes over the HBM peak — over the kernels' time. At
head_dim 128 every call is bound by compute (printed)."""

from benchmark import flops


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.flash or run.get("peaks") is None:
        return None
    least = spent = 0.0
    bounds = set()
    for kind, (calls, seconds, shape) in trace.flash.items():
        bh, seq, head_dim = shape
        work = flops.flash_kernel_work(kind, bh, seq, head_dim)
        t, bound = flops.least_seconds(*work, run["peaks"])
        least += calls * t
        spent += seconds
        bounds.add(bound)
    print(f"[bench] flash_roofline: bound by {'/'.join(sorted(bounds))}",
          flush=True)
    return 100.0 * least / spent if spent else None
