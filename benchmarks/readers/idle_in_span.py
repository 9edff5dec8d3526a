"""Of the chips' idle time inside the `bench:` statements of `classes` (the
whole traced window where `classes` is null), the % that lies inside at
least one `otb:` host span whose name is in `spans`; `spans` null means any
`otb:` span EXCEPT the statement's root `otb:query` and the `otb:wait:*`
events.  The device clock's twin of `unattributed_ms`: the root covers a
whole statement, so "inside any span" (`idle_attributed`) is true of nearly
all idle by construction, and says nothing of which PHASE the device waited
for (lib/xplane.py gives the trace; nothing of it is changed)."""

from benchmarks.lib import xplane
from benchmarks.lib.profile import union

ROOT_SPAN = xplane.SPAN_PREFIX + "query"


def _clip(xs, ys):
    """The intersection of two merged, sorted interval lists, as one."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            out.append([lo, hi])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(xs):
    return sum(e - s for s, e in xs)


def idle_in_span_pct(trace, spans=None, classes=None):
    """None where the trace has no `otb:` span or no device op at all (a
    program without the names), or no statement of the classes; 0 where
    the spans asked for cover none of the idle."""
    if not trace.spans or not trace.ops:
        return None
    if classes:
        where = union(xplane._statements(trace, classes))
    else:
        where = [list(xplane.window_of(trace))]
    if not where:
        return None
    named = union([(s, e) for s, e, name in trace.spans
                   if (name in spans if spans else name != ROOT_SPAN
                       and not name.startswith(xplane.WAIT_PREFIX))])
    inside = _clip(named, where)
    idle = attributed = 0.0
    for ops in trace.ops.values():
        busy = union([o[:2] for o in ops])
        idle += _length(where) - xplane._overlap(where, busy)
        attributed += _length(inside) - xplane._overlap(inside, busy)
    return 100.0 * attributed / idle if idle > 0 else None


def read(ctx, spans=None, classes=None):
    trace = xplane.of_this_run(ctx)
    return idle_in_span_pct(trace, spans, classes) \
        if trace is not None else None
