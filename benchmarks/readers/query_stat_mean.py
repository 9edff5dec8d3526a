"""The MEAN of one key of the server-side per-statement stats
(`ClusterSession.last_query_stats()`, read right after each reply in the
traced run) over the steps of the given classes: for the keys read from the
thread CPU clock (`cpu_ms`, `offcpu_ms`).  The host the chip tool gives accounts a thread's
CPU in 10 ms ticks (PERF.md section 6, PR 36), so ONE statement's `cpu_ms`
reads 0 or 10 and the median of a window says nothing; a tick lands in a
span as often as the thread runs there, so the mean over a window's
statements is what such a clock does say (lib/stats.py has no mean)."""


def read(ctx, key, classes=None):
    xs = [st[key] for cls, st in ctx.step_stats()
          if st and key in st and (not classes or cls in classes)]
    return float(sum(xs) / len(xs)) if xs else None
