"""Reply time on the client's clock, in ms, of the steps of the given
classes (all classes where none is given), reduced as asked."""

from benchmarks.lib import stats


def read(ctx, classes=None, reduce="median"):
    xs = ctx.latencies_ms(classes)
    return stats.reduce(xs, reduce) if xs else None
