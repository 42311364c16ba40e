"""Engine layer: the 95th percentile over the traced window's requests of
their call's latency, in ms, from the harness's host spans.

Every request of a call shares the call's ``generate`` span, so each span
counts once per request of the batch.  In a closed loop of a few calls
this is the slowest call or the two slowest, so one host stall moves it
whole: it stands here, beside ``tok_s``, where the window holds too few
calls for an end-to-end tail."""
import numpy as np


def read(ctx):
    lo, hi = ctx.trace.window
    took = [s.dur for s in ctx.trace.spans
            if s.name == "generate" and s.start >= lo and s.end <= hi]
    if not took:
        return None
    batch = ctx.call_shape()[0]
    return float(np.percentile(np.repeat(took, batch), 95)) * 1e-6
