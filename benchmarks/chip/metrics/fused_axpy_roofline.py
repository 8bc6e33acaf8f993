"""fused_axpy_roofline: the local SGD step's fused x + a·y kernel's least
time (``flops/fused_axpy.py`` against the peak table) over the device
time of its calls in the traced window, over all chips.  Reads nothing
where the kernel did not run."""
import math

from bench import trace as T

ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def read(ctx):
    fl = ctx.flops("fused_axpy")
    least = spent = 0.0
    for chip in ctx.trace["devices"]:
        for op, s, e in T.kernel_events(ctx.trace, chip, "_axpy_kernel"):
            dtype, shape = ctx.trace["kernels"][op]["result"]
            least += fl.least_time_s(math.prod(shape), ITEMSIZE[dtype],
                                     ctx.peak)
            spent += (e - s) / 1e9
    return 100.0 * least / spent if spent > 0 else None
