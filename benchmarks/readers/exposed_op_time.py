"""Per statement, on the slowest chip, the part (ms) of the ops whose XLA
name contains `pattern` during which nothing else runs on that chip
(lib/xplane.py): a collective's exposed time."""

from benchmarks.lib import stats, xplane


def read(ctx, pattern, classes=None, reduce="median"):
    trace = xplane.of_this_run(ctx)
    if trace is None:
        return None
    xs = xplane.exposed_ms_per_statement(trace, pattern, classes)
    return stats.reduce(xs, reduce) if xs else None
