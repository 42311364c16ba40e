"""Engine layer: device-idle time between consecutive generate programs,
per gap, in ms.  It is the host's work per call (prompts, fault config,
BER lookup, token transfer) that the device waits for."""
import tracefile


def read(ctx):
    progs = ctx.generate_programs()
    if len(progs) < 2:
        return None
    idle = [(b.start - a.end) - tracefile.busy_ns(ctx.trace.ops, a.end,
                                                  b.start)
            for a, b in zip(progs, progs[1:])]
    return sum(idle) / len(idle) * 1e-6
