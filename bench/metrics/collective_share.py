"""Device layer: the share of the first chip's busy time inside the
generate programs that its collective operations take, in %.

Under the serve layout every weight matmul's output is all-gathered to
every chip, so this is what tensor parallelism costs in exchanges: the
summed device time of the chip's ``all-gather``, ``all-reduce``,
``reduce-scatter``, ``collective-permute``, ``all-to-all`` and
``async-collective-*`` operations that start inside the generate programs
wholly in the traced window, over the union of the chip's operations in
those programs."""
import tracefile

COLLECTIVE = (r"^(all-gather|all-reduce|reduce-scatter|collective-permute|"
              r"all-to-all|async-collective)")


def read(ctx):
    progs = ctx.generate_programs()
    if not progs:
        return None
    busy = sum(tracefile.busy_ns(ctx.trace.ops, p.start, p.end)
               for p in progs)
    if busy <= 0:
        return None
    return 100.0 * tracefile.ops_within(ctx.trace, progs, COLLECTIVE) / busy
