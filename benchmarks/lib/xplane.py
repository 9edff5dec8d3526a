"""The program's own names in a profiler trace (.xplane.pb): the `otb:` host
spans that opentenbase_tpu/obs/trace.py writes on the profiler's clock, and
the `otb.` scope (jax.named_scope in ops/kernels.py and at the program steps
of exec/) of each device op.  lib/profile.py reads the file with
jax.profiler.ProfileData, which gives an event's name and its own stats but
not its metadata's stats, and the scope lives there: XLA's `op_name`
(`jit(otb_mesh)/.../otb.agg/scatter-add`) is the stat `tf_op` of the
XEventMetadata.  So this module reads the protobuf's wire format itself, the
few fields it needs, with nothing but Python.

  XSpace.planes=1
  XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5   (maps: key=1 value=2)
  XLine.name=2 .timestamp_ns=3 .events=4
  XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3
  XEventMetadata.name=2 .stats=5      XStatMetadata.name=2
  XStat.metadata_id=1 .str_value=5 .ref_value=7

A device plane's name starts with "/device:TPU" and its op line is "XLA Ops"
(as lib/profile.py takes them); host spans are events of any line of a
"/host:" plane whose name starts with "otb:", the benchmark's own wrappers
those that start with "bench:".  Times are ns on the trace's clock.

The op line nests: a `while` or a `conditional` is one event that lasts as
long as its body, and the body's ops are events inside it (seen in PR 25's
first trace: the scopes' times summed to 29 s of a 20 s window).  A time by
scope is therefore a sum of SELF times, an op's duration less its direct
children's, which adds up to the busy time; XLA's control-flow ops carry no
`op_name`, so their self time, the loop's own overhead, reads as unnamed.

A trace of a program that has no such names (the parent of the PR that
brought them) has no `otb:` event and no scoped op: every reduction then
returns None, and the metric is left out of the line.
"""

import glob
import os
import re

from . import files
from .profile import (ANNOTATION_PREFIX, DEVICE_PREFIX, OP_LINE, busy_ns,
                      union)

SPAN_PREFIX = "otb:"
WAIT_PREFIX = "otb:wait:"
SCOPE_STAT = "tf_op"
_SCOPE = re.compile(r"otb\.[a-z_]+")
# what the CN fragment compiles is `otb.finalize` whatever lies further in;
# otherwise the innermost scope is the op's (a kernel inside a program step)
OUTER_WINS = "otb.finalize"


def scope_of(op_name):
    found = _SCOPE.findall(op_name or "")
    if not found:
        return None
    return OUTER_WINS if OUTER_WINS in found else found[-1]


# -- the wire format ------------------------------------------------------

def _fields(buf, pos, end):
    """(field number, value) of one message: an int for a varint, a
    (start, end) pair for a length-delimited field; fixed-width fields
    are skipped."""
    while pos < end:
        key = buf[pos]
        pos += 1
        if key & 0x80:
            key &= 0x7F
            shift = 7
            while True:
                b = buf[pos]
                pos += 1
                key |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
        wire = key & 7
        if wire == 0 or wire == 2:
            val = 0
            shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            if wire == 0:
                yield key >> 3, val
            else:
                yield key >> 3, (pos, pos + val)
                pos += val
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, val = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, span):
    """(name, [line spans], {metadata id: (name, op_name)})."""
    name, lines, events, stats = "", [], {}, {}
    meta_spans = []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta_spans.append(v)
        elif f == 5:
            key, val = _map_entry(buf, v)
            for f2, v2 in _fields(buf, *val):
                if f2 == 2:
                    stats[key] = _text(buf, v2)
    for v in meta_spans:
        key, val = _map_entry(buf, v)
        ev_name, op_name = "", None
        for f2, v2 in _fields(buf, *val):
            if f2 == 2:
                ev_name = _text(buf, v2)
            elif f2 == 5:
                stat = dict(_fields(buf, *v2))
                if stats.get(stat.get(1)) == SCOPE_STAT:
                    op_name = _text(buf, stat[5]) if 5 in stat \
                        else stats.get(stat.get(7))
        events[key] = (ev_name, op_name)
    return name, lines, events


def _line(buf, span):
    """(name, [(start_ns, end_ns, metadata id)])."""
    name, t0_ns, events = "", 0, []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            t0_ns = v
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = off = dur = 0
        for f, v in _fields(buf, *ev):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        start = t0_ns + off / 1e3
        out.append((start, start + dur / 1e3, mid))
    return name, out


def _nest(events):
    """[(start, end, ...)] sorted by start (longest first) -> per event
    its self time and the index of the event it lies inside (-1: none)."""
    selfs = [e[1] - e[0] for e in events]
    parents = [-1] * len(events)
    stack = []
    for i, ev in enumerate(events):
        while stack and events[stack[-1]][1] <= ev[0]:
            stack.pop()
        if stack and ev[1] <= events[stack[-1]][1]:
            parents[i] = stack[-1]
            selfs[stack[-1]] -= ev[1] - ev[0]
        stack.append(i)
    return selfs, parents


class Trace:
    """ops: {device plane: [(start_ns, end_ns, op name, scope | None,
    self_ns, index of the enclosing op | -1)]}; spans: the `otb:` host
    events [(start_ns, end_ns, name)]; bench: the `bench:` ones.  All
    sorted by start."""

    def __init__(self, ops, spans, bench):
        self.ops, self.spans, self.bench = ops, spans, bench

    @classmethod
    def from_bytes(cls, raw):
        buf = memoryview(raw)
        ops, spans, bench = {}, [], []
        for f, v in _fields(buf, 0, len(buf)):
            if f != 1:
                continue
            name, lines, meta = _plane(buf, v)
            if name.startswith(DEVICE_PREFIX):
                scopes = {k: scope_of(op) for k, (_n, op) in meta.items()}
                found = []
                for ln in lines:
                    lname, events = _line(buf, ln)
                    if lname == OP_LINE:
                        found += [(s, e, meta[m][0], scopes[m])
                                  for s, e, m in events]
                found.sort(key=lambda o: (o[0], -o[1]))
                selfs, parents = _nest(found)
                ops[name] = [o + (selfs[i], parents[i])
                             for i, o in enumerate(found)]
            elif name.startswith("/host:"):
                for ln in lines:
                    for s, e, m in _line(buf, ln)[1]:
                        ev = meta.get(m, ("",))[0]
                        if ev.startswith(SPAN_PREFIX):
                            spans.append((s, e, ev))
                        elif ev.startswith(ANNOTATION_PREFIX):
                            bench.append((s, e, ev))
        return cls(ops, sorted(spans), sorted(bench))

    @classmethod
    def from_file(cls, path):
        with open(path, "rb") as f:
            return cls.from_bytes(f.read())

    def has_scopes(self):
        return any(o[3] for ops in self.ops.values() for o in ops)


_LAST = [None, None]        # (path, mtime) and its Trace: one parse a run


def of_this_run(ctx):
    """The traced run's own trace: run.py writes it under
    run_out/<cell>/trace and removes it when the run ends; the readers are
    called in between.  The cell is the one whose traffic the run's mix was
    made from (run.py hands the readers no name), so another cell's
    leftovers are never read.  None where the run is not traced."""
    if ctx.trace is None:
        return None
    found = []
    for w in files.benchmark_json()["workloads"]:
        if files.traffic(w["traffic"]) == ctx.mix.spec:
            found += glob.glob(os.path.join(
                files.BENCH_DIR, "run_out", w["name"], "trace", "plugins",
                "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if _LAST[0] != key:
        _LAST[:] = [key, Trace.from_file(path)]
    return _LAST[1]


# -- reductions -----------------------------------------------------------

def _statements(trace, classes):
    return [(a0, a1) for a0, a1, name in trace.bench
            if not classes or name[len(ANNOTATION_PREFIX):] in classes]


def scope_ms_per_statement(trace, scopes, classes=None):
    """For each `bench:` statement of the classes, the device time (ms), on
    the slowest chip, of the ops that start inside it under one of
    `scopes`, each op's self time; `scopes` None asks for the ops under no
    scope at all."""
    if not trace.has_scopes():
        return None
    out = []
    for a0, a1 in _statements(trace, classes):
        out.append(max((sum(o[4] for o in ops if a0 <= o[0] < a1
                            and (o[3] in scopes if scopes else o[3] is None))
                        for ops in trace.ops.values()), default=0.0) / 1e6)
    return out


def _overlap(xs, ys):
    """Length of the intersection of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def window_of(trace):
    """(lo, hi) of everything the trace holds."""
    every = [o[:2] for ops in trace.ops.values() for o in ops] \
        + [s[:2] for s in trace.spans] + [b[:2] for b in trace.bench]
    if not every:
        return 0.0, 0.0
    return min(s for s, _ in every), max(e for _, e in every)


def idle_attributed_pct(trace):
    """Of the chips' idle time inside the trace's window, the % that lies
    inside at least one `otb:` host span: how much of the device's waiting
    the program's own spans can be asked about.  The `otb:wait:` events do
    not count: a wait that delayed a statement is inside that statement's
    spans already, and one outside any (the benchmark's in-process client
    waiting for its reply, a background thread) says nothing about what the
    serving path was doing."""
    if not trace.spans or not trace.ops:
        return None
    lo, hi = window_of(trace)
    covered = union([(max(s, lo), min(e, hi)) for s, e, name in trace.spans
                     if not name.startswith(WAIT_PREFIX)])
    span_len = sum(e - s for s, e in covered)
    idle = attributed = 0.0
    for ops in trace.ops.values():
        busy = union([o[:2] for o in ops])
        idle += (hi - lo) - busy_ns([(s, e, None) for s, e in busy], lo, hi)
        attributed += span_len - _overlap(covered, busy)
    return 100.0 * attributed / idle if idle > 0 else None


def exposed_ms_per_statement(trace, pattern, classes=None):
    """For each `bench:` statement, on the slowest chip: the part (ms) of
    the intervals of ops whose XLA name contains `pattern`, and that start
    inside the statement, during which no other op runs on that chip.  An
    op that encloses one of them (the `conditional` it sits in) is not
    another op running beside it."""
    if not trace.ops:
        return None
    out = []
    for a0, a1 in _statements(trace, classes):
        worst = 0.0
        for ops in trace.ops.values():
            idx = [i for i, o in enumerate(ops)
                   if a0 <= o[0] < a1 and pattern in o[2]]
            if not idx:
                continue
            around = set()
            for i in idx:
                while ops[i][5] >= 0:
                    i = ops[i][5]
                    around.add(i)
            mine = union([ops[i][:2] for i in idx])
            others = union([o[:2] for i, o in enumerate(ops)
                            if pattern not in o[2] and i not in around
                            and o[1] > mine[0][0] and o[0] < mine[-1][1]])
            worst = max(worst, sum(e - s for s, e in mine)
                        - _overlap(mine, others))
        out.append(worst / 1e6)
    return out
