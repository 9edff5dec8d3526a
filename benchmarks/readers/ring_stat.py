"""One key of the finished statement traces in the program's ring
(`opentenbase_tpu.obs.trace.recent()`), read after the window: what
`last_query_stats()` cannot hold when it is read at the reply, because the
span ends after the client has it (`wire.send`).

A SAMPLE, not the window: the ring is one per process and keeps the last 64
statements (`OTB_TRACE_RING`) of all sessions, so of a window's ~1,700 point
reads the value is reduced over those among the last 64 finished, whatever
ran after the window included.  A trace belongs to a class when its
signature (the statement's first 200 characters) starts as the class's SQL
template does up to its first parameter."""

from benchmarks.lib import stats


def read(ctx, key, classes=None, reduce="median"):
    from opentenbase_tpu.obs import trace as obs_trace
    heads = [step["sql"].split("{")[0].strip()[:200]
             for st in ctx.mix.statements for step in st.steps
             if not classes or step["class"] in classes]
    xs = []
    for qt in obs_trace.recent():
        if any(qt.signature.startswith(h) for h in heads):
            value = qt.summary().get(key)
            if value is not None:
                xs.append(value)
    return stats.reduce(xs, reduce) if xs else None
