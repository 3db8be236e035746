"""The fused round-close kernel's share of its roofline, in percent.

Least time = max(FLOPs / bf16 peak, bytes / HBM bandwidth) per close,
from ``counts``; at cohort 256 the HBM term bounds it.  Divided by the
kernel's device seconds per close in the trace, found by its name.  The
kernel's own bound is integer work on the vector unit (about
``counts.CLOSE_VPU_OPS_PER_ELEMENT`` operations per regenerated
element), which has no published peak, so this share reads far below
100 and rises as the kernel gets faster.
"""
from trace_reduce import op_seconds


def read(ctx):
    w, c, p = ctx.window, ctx.counts, ctx.peaks
    if not (ctx.trace and w and w["rounds"] and p and c.get("close_kernel")):
        return None
    kernel_s = op_seconds(ctx.trace, c["close_kernel"])
    if not kernel_s:
        return None
    least = max(c["close_flops"] / p["bf16_flops_per_s"],
                c["close_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_s / w["rounds"])
