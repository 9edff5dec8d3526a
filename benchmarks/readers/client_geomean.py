"""Geometric mean, over the step classes seen in the window, of each class's
median reply time in ms (TPC-H clause 5.4.1's arithmetic)."""

from benchmarks.lib import stats


def read(ctx, classes=None):
    by = ctx.latencies_ms_by_class(classes)
    if not by:
        return None
    return stats.geomean([stats.median(xs) for xs in by.values()])
