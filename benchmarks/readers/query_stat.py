"""One key of the server-side per-statement stats
(`ClusterSession.last_query_stats()`, read right after each reply in the
traced run), reduced over the steps of the given classes."""

from benchmarks.lib import stats


def read(ctx, key, classes=None, reduce="median"):
    xs = [st[key] for cls, st in ctx.step_stats()
          if st and key in st and (not classes or cls in classes)]
    return stats.reduce(xs, reduce) if xs else None
