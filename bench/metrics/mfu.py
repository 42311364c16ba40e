"""Model step layer: model FLOPs of the generate calls completed in the
traced window, over the window times the cell's chips times one chip's
peak at the cell's matmul precision (int8 for aged cells, bf16 for clean
ones), in %."""
import costs


def read(ctx):
    n = len(ctx.generate_programs())
    if n == 0:
        return None
    flops = n * costs.generate_flops(ctx.dims, *ctx.call_shape())
    peak = ctx.peak["int8_ops_per_s" if ctx.aged else "bf16_flops_per_s"]
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips * peak)
