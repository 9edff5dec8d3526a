"""From a profiler trace (.xplane.pb) to numbers: device busy union, idle
share, time of named operations, top operations and the longest idle gaps
by which `bench:` annotation covered them.  Reads the file with
jax.profiler.ProfileData and nothing else.

A device plane is one whose name starts with "/device:TPU"; its operation
line is "XLA Ops" (one event per executed XLA op; "XLA Modules" and "Steps"
would count every op a second time).  Host annotations are events whose
name starts with "bench:" on any line of a "/host:" plane.
"""

import glob
import os
import re

OP_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU"
ANNOTATION_PREFIX = "bench:"
BETWEEN = "between statements"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


class Trace:
    """device_ops: {plane name: [(start_ns, end_ns, op name)]} sorted by
    start; annotations: [(start_ns, end_ns, name)] sorted by start."""

    def __init__(self, device_ops, annotations):
        self.device_ops = device_ops
        self.annotations = annotations

    @classmethod
    def from_file(cls, path):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        device_ops, annotations = {}, []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                ops = []
                for line in plane.lines:
                    if line.name != OP_LINE:
                        continue
                    ops += [(float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns),
                             e.name) for e in line.events]
                device_ops[plane.name] = sorted(ops)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    annotations += [
                        (float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns), e.name)
                        for e in line.events
                        if e.name.startswith(ANNOTATION_PREFIX)]
        return cls(device_ops, sorted(annotations))

    def describe(self):
        return {p: len(ops) for p, ops in self.device_ops.items()}


def union(intervals):
    """Merged [(start, end)] of possibly overlapping, sorted intervals."""
    out = []
    for s, e in intervals:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_ns(ops, lo=None, hi=None):
    """Union length of the ops' intervals, clipped to [lo, hi]."""
    total = 0.0
    for s, e in union([(a, b) for a, b, _ in ops]):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e > s:
            total += e - s
    return total


def span_of(trace):
    """(lo, hi) of everything the trace holds: the traced window on the
    trace's own clock."""
    starts = [ops[0][0] for ops in trace.device_ops.values() if ops]
    ends = [max(e for _, e, _ in ops)
            for ops in trace.device_ops.values() if ops]
    starts += [a[0] for a in trace.annotations[:1]]
    ends += [max(e for _, e, _ in trace.annotations)] \
        if trace.annotations else []
    return (min(starts), max(ends)) if starts else (0.0, 0.0)


def busy_s_mean(trace, lo=None, hi=None):
    """Device-busy seconds, the mean over the chips' planes."""
    if not trace.device_ops:
        return 0.0
    return sum(busy_ns(ops, lo, hi) for ops in trace.device_ops.values()) \
        / len(trace.device_ops) / 1e9


def op_time_per_annotation(trace, pattern, classes=None):
    """For each annotation (of the given classes), the summed device time
    of ops whose name contains `pattern` that START inside it, on the
    slowest chip.  Returns [seconds]."""
    out = []
    for a0, a1, name in trace.annotations:
        if classes and name[len(ANNOTATION_PREFIX):] not in classes:
            continue
        worst = 0.0
        for ops in trace.device_ops.values():
            worst = max(worst, sum(e - s for s, e, n in ops
                                   if a0 <= s < a1 and pattern in n))
        out.append(worst / 1e9)
    return out


def busy_per_annotation(trace, classes=None):
    """[(class, device-busy seconds inside the annotation, mean over
    chips)] for each annotation."""
    out = []
    for a0, a1, name in trace.annotations:
        cls = name[len(ANNOTATION_PREFIX):]
        if classes and cls not in classes:
            continue
        out.append((cls, busy_s_mean(trace, a0, a1)))
    return out


def top_ops(trace, n=10):
    """[(op name, seconds)] summed over chips' mean, largest first."""
    total = {}
    for ops in trace.device_ops.values():
        for s, e, name in ops:
            total[name] = total.get(name, 0.0) + (e - s)
    k = max(len(trace.device_ops), 1)
    rows = sorted(((v / k / 1e9, name) for name, v in total.items()),
                  reverse=True)[:n]
    return [[short_name(name), sec] for sec, name in rows]


def short_name(hlo):
    """XLA's own name of an op, its opcode and what it calls, without the
    shapes: '%fusion.7 fusion kind=kCustom calls=%fused_computation.2'."""
    name, _, rest = hlo.partition(" = ")
    m = re.search(r"[\]})]\s([a-z][a-z0-9\-]*)\(", rest)
    out = name + (" " + m.group(1) if m else "")
    for key in ("kind=", "calls=", "body="):
        k = re.search(key + r"[%\w.\-]+", rest)
        if k:
            out += " " + k.group(0)
    return out[:160]


def idle_gaps(trace, lo, hi, n=10):
    """The device's idle time inside [lo, hi] on the first chip's plane,
    by what the host was doing: each gap between merged ops is split over
    the `bench:` annotations that cover it, the rest is "between
    statements".  Returns [(name, seconds)] largest first."""
    if not trace.device_ops:
        return []
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    merged = union([(a, b) for a, b, _ in ops])
    gaps, cur = [], lo
    for s, e in merged:
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    total = {}
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        covered = 0.0
        for a0, a1, name in trace.annotations:
            ov = min(g1, a1) - max(g0, a0)
            if ov > 0:
                total[name] = total.get(name, 0.0) + ov
                covered += ov
        rest = (g1 - g0) - covered
        if rest > 0:
            total[BETWEEN] = total.get(BETWEEN, 0.0) + rest
    rows = sorted(((v / 1e9, name) for name, v in total.items()),
                  reverse=True)[:n]
    return [[name, sec] for sec, name in rows]
