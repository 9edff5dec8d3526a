"""Per statement, the device time (ms) of the ops under the given `otb.`
scopes (lib/xplane.py), on the slowest chip, reduced over the traced
statements of the classes; `scopes` null reads the ops under no scope."""

from benchmarks.lib import stats, xplane


def read(ctx, scopes=None, classes=None, reduce="median"):
    trace = xplane.of_this_run(ctx)
    if trace is None:
        return None
    xs = xplane.scope_ms_per_statement(trace, scopes, classes)
    return stats.reduce(xs, reduce) if xs else None
