"""An independent count from the device plane: the events of its "XLA
Modules" line (one per executable launched, a compiled program or an eager
op alike) that start inside a `bench:` statement of `classes`, per
statement, reduced; the busiest chip's.  Beside the program's own
`program_calls` it says how many launches of a statement nobody named.

Where several clients' statements are open at once (the point cell's four)
a launch cannot be told one statement's from another's on the device plane:
it is shared equally among the statements open when it starts, of whatever
class, so that the counts of a window still add up to its launches; with one
client a statement's share is the plain count.

lib/xplane.py reads a device plane's "XLA Ops" only, so this reads the one
more line from the run's own `.xplane.pb`, the file `xplane.of_this_run`
found, with that module's wire-format readers.  None where the trace has
no such line: nothing is guessed from gaps between ops."""

from benchmarks.lib import stats, xplane
from benchmarks.lib.profile import ANNOTATION_PREFIX, DEVICE_PREFIX

MODULE_LINE = "XLA Modules"


def _name_of(buf, span):
    """Field 2 of a message (XPlane.name, XLine.name), read no further."""
    for f, v in xplane._fields(buf, *span):
        if f == 2:
            return xplane._text(buf, v)
    return ""


def module_starts(raw):
    """{device plane: sorted start_ns of its module events}, or None where
    no device plane has the line."""
    buf = memoryview(raw)
    out = {}
    for f, v in xplane._fields(buf, 0, len(buf)):
        if f != 1 or not _name_of(buf, v).startswith(DEVICE_PREFIX):
            continue
        for f2, v2 in xplane._fields(buf, *v):
            if f2 == 3 and _name_of(buf, v2) == MODULE_LINE:
                out.setdefault(_name_of(buf, v), []).extend(
                    s for s, _e, _m in xplane._line(buf, v2)[1])
    return {p: sorted(xs) for p, xs in out.items()} or None


def launches_per_statement(starts, bench, classes=None):
    """For each `bench:` statement of the classes its share of the launches
    that start inside it, on the busiest chip."""
    worst = None
    for xs in starts.values():
        share = [0.0] * len(bench)
        opened, nxt = [], 0
        for t in xs:
            while nxt < len(bench) and bench[nxt][0] <= t:
                opened.append(nxt)
                nxt += 1
            opened = [i for i in opened if bench[i][1] > t]
            for i in opened:
                share[i] += 1.0 / len(opened)
        if worst is None or sum(share) > sum(worst):
            worst = share
    return [n for n, (_s, _e, name) in zip(worst or (), bench)
            if not classes or name[len(ANNOTATION_PREFIX):] in classes]


def read(ctx, classes=None, reduce="median"):
    trace = xplane.of_this_run(ctx)
    if trace is None:
        return None
    with open(xplane._LAST[0][0], "rb") as f:   # the file it has just parsed
        starts = module_starts(f.read())
    if starts is None:
        return None
    xs = launches_per_statement(starts, trace.bench, classes)
    return stats.reduce(xs, reduce) if xs else None
