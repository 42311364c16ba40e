"""Device layer: share of the traced window in which no operation ran on
the device, in %."""
import tracefile


def read(ctx):
    lo, hi = ctx.trace.window
    if hi <= lo:
        return None
    return 100.0 * (1.0 - tracefile.busy_ns(ctx.trace.ops, lo, hi)
                    / (hi - lo))
