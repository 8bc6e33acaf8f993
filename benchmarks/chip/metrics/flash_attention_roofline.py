"""flash_attention_roofline: the causal flash-attention forward kernel's
least time (``flops/flash_attention.py`` against the peak table) over the
device time of its calls in the traced window, over all chips.  Reads
nothing where the kernel did not run."""
from bench import trace as T

ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def read(ctx):
    fl = ctx.flops("flash_attention")
    least = spent = 0.0
    for chip in ctx.trace["devices"]:
        for op, s, e in T.kernel_events(ctx.trace, chip, "_flash_kernel"):
            info = ctx.trace["kernels"][op]
            (dtype, (bh, seq, hd)), (_, (bkv, _, _)) = info["operands"][:2]
            least += fl.least_time_s(bh, bkv, seq, hd, ITEMSIZE[dtype],
                                     ctx.peak)
            spent += (e - s) / 1e9
    return 100.0 * least / spent if spent > 0 else None
