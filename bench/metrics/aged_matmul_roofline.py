"""Kernel layer: the fused aged-matmul kernel's share of its roofline, in %.

The least time the chip needs for the faulted weight matmuls of the
generate calls wholly inside the traced window, each matmul bound by int8
operations or by HBM bytes counted from the model's shapes, over the
summed device time of the kernel's events inside those calls.  On several
chips both are the first chip's: its column block of each matmul, and its
own kernel events."""
import costs
import tracefile

KERNEL = r"^fused_aged_matmul"


def read(ctx):
    progs = ctx.generate_programs()
    if not ctx.aged or not progs:
        return None
    kernel_ns = tracefile.ops_within(ctx.trace, progs, KERNEL)
    if kernel_ns <= 0:
        return None
    floor = len(progs) * costs.aged_matmul_floor_s(
        ctx.dims, *ctx.call_shape(), peak_ops=ctx.peak["int8_ops_per_s"],
        peak_bytes=ctx.peak["hbm_bytes_per_s"], chips=ctx.chips)
    return 100.0 * floor / (kernel_ns * 1e-9)
