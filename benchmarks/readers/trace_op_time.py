"""Per statement, the summed device time (ms) of operations whose XLA name
contains `pattern`, on the slowest chip; reduced over the statements."""

from benchmarks.lib import profile, stats


def read(ctx, pattern, classes=None, reduce="median"):
    if ctx.trace is None:
        return None
    xs = profile.op_time_per_annotation(ctx.trace, pattern, classes)
    return stats.reduce([x * 1e3 for x in xs], reduce) if xs else None
