"""Unified metrics registry — counters, gauges, log-bucket histograms.

Reference analog: the pgstat shared-memory counters behind the
``pg_stat_*`` views, plus the cumulative-histogram exposition format
popularized by Prometheus.

One process-global ``REGISTRY``:

- native metrics: ``counter()/gauge()/histogram()`` get-or-create by
  (name, labels).  Histograms use FIXED log-scale latency buckets
  (factor 2^1/4 from 1 µs to ~4.6 min) so p50/p95/p99 estimation
  needs no stored samples — quantile error is bounded by one bucket
  width (≤ ~19 %).
- registered collectors: the engine's existing stat surfaces
  (exec/plancache, storage/bufferpool, executor EXEC_STATS) register a
  sample generator at import instead of growing another bespoke locked
  dict — the registry is the single pane of glass that the
  ``otb_metrics`` view and ``metrics_text()`` exposition read.

Thread-safety: the registry dict is guarded by ``_LOCK``; each metric
carries its own lock so hot-path ``inc``/``observe`` never contend on
the registry.  Collector generators must do their own locking (they
already read under their subsystem's lock).
"""

from __future__ import annotations

import functools
import math
import threading
from typing import Callable, Iterable, Optional
from ..utils import locks

# fixed log-scale bucket bounds (ms): 2^-10 .. 2^18, quarter-power steps
_BUCKET_LO_EXP = -10.0
_BUCKET_STEP = 0.25
_NBUCKETS = 113                 # [2^-10, 2^18) in 2^0.25 steps, + overflow
BUCKET_BOUNDS = tuple(
    2.0 ** (_BUCKET_LO_EXP + _BUCKET_STEP * i) for i in range(_NBUCKETS))


class Counter:
    kind = "counter"
    __slots__ = ("name", "labels", "_v", "_lock")

    def __init__(self, name: str, labels: tuple):
        self.name = name
        self.labels = labels
        self._v = 0.0
        self._lock = locks.Lock("obs.metrics.metric._lock")

    def inc(self, n: float = 1) -> None:
        with self._lock:
            self._v += n

    @property
    def value(self) -> float:   # otblint: eager-only
        return self._v

    def samples(self):
        yield (self.name, self.labels, "counter", self._v)


class Gauge:
    kind = "gauge"
    __slots__ = ("name", "labels", "_v", "_lock")

    def __init__(self, name: str, labels: tuple):
        self.name = name
        self.labels = labels
        self._v = 0.0
        self._lock = locks.Lock("obs.metrics.metric._lock")

    def set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    @property
    def value(self) -> float:   # otblint: eager-only
        return self._v

    def samples(self):
        yield (self.name, self.labels, "gauge", self._v)


class Histogram:
    """Fixed log-bucket histogram: O(1) observe, O(buckets) quantile,
    zero sample storage."""

    kind = "histogram"
    __slots__ = ("name", "labels", "counts", "count", "sum", "_lock")

    def __init__(self, name: str, labels: tuple):
        self.name = name
        self.labels = labels
        self.counts = [0] * (_NBUCKETS + 1)    # +1: overflow bucket
        self.count = 0
        self.sum = 0.0
        self._lock = locks.Lock("obs.metrics.metric._lock")

    @staticmethod
    def _bucket(v: float) -> int:
        if v <= BUCKET_BOUNDS[0]:
            return 0
        i = int((math.log2(v) - _BUCKET_LO_EXP) / _BUCKET_STEP) + 1
        return min(i, _NBUCKETS)

    def observe(self, v: float) -> None:
        i = self._bucket(v)
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum += v

    def quantile(self, q: float) -> float:
        """Estimated q-quantile: geometric midpoint of the bucket where
        the cumulative count crosses q·total."""
        with self._lock:
            total = self.count
            counts = list(self.counts)
        if total == 0:
            return 0.0
        target = q * total
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= target:
                if i == 0:
                    return BUCKET_BOUNDS[0]
                lo = BUCKET_BOUNDS[i - 1]
                hi = BUCKET_BOUNDS[min(i, _NBUCKETS - 1)]
                return math.sqrt(lo * hi)
        return BUCKET_BOUNDS[-1]

    def samples(self):
        yield (self.name + "_count", self.labels, "histogram", self.count)
        yield (self.name + "_sum", self.labels, "histogram", self.sum)
        for q, tag in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            yield (self.name + "_" + tag, self.labels, "histogram",
                   self.quantile(q))


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class Registry:
    def __init__(self):
        self._lock = locks.Lock("obs.metrics.Registry._lock")
        self._metrics: dict = {}        # (name, labels) -> metric
        self._collectors: dict = {}     # name -> sample generator fn

    def _get(self, kind: str, name: str, labels: dict):
        lt = tuple(sorted(labels.items()))
        key = (name, lt)
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = self._metrics[key] = _KINDS[kind](name, lt)
            elif m.kind != kind:
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}")
            return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get("counter", name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get("gauge", name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._get("histogram", name, labels)

    def register_collector(self, name: str,
                           fn: Callable[[], Iterable]) -> None:
        """Idempotent: a subsystem exports its live counters by name.
        `fn` yields (metric_name, labels_dict, value) samples."""
        with self._lock:
            self._collectors[name] = fn

    # ------------------------------------------------------------------
    def samples(self):
        """Every sample, native + collected:
        (name, labels_tuple, kind, value)."""
        with self._lock:
            metrics = list(self._metrics.values())
            collectors = list(self._collectors.items())
        for m in sorted(metrics, key=lambda m: (m.name, m.labels)):
            yield from m.samples()
        for _cname, fn in sorted(collectors):
            try:
                rows = list(fn())
            except Exception:
                continue            # a broken collector never breaks the scrape
            for name, labels, value in rows:
                yield (name, tuple(sorted(labels.items())), "gauge",
                       float(value))

    def rows(self):
        """(name, labels_text, kind, value) rows — the otb_metrics view."""
        for name, labels, kind, value in self.samples():
            lbl = ",".join(f"{k}={v}" for k, v in labels)
            yield (name, lbl, kind, float(value))

    def text(self) -> str:
        """Prometheus-style text exposition.  Histograms additionally
        emit cumulative ``_bucket`` lines (every 4th bound + +Inf, so
        the bucket count stays scrape-friendly)."""
        out = []
        typed = set()
        with self._lock:
            metrics = sorted(self._metrics.values(),
                             key=lambda m: (m.name, m.labels))
        for m in metrics:
            if m.name not in typed:
                typed.add(m.name)
                out.append(f"# TYPE {m.name} {m.kind}")
            if m.kind == "histogram":
                with m._lock:
                    counts = list(m.counts)
                    count, total = m.count, m.sum
                cum = 0
                for i, c in enumerate(counts):
                    cum += c
                    if i % 4 == 0 and i < _NBUCKETS:
                        out.append(_sample_line(
                            m.name + "_bucket",
                            m.labels + (("le", f"{BUCKET_BOUNDS[i]:g}"),),
                            cum))
                out.append(_sample_line(
                    m.name + "_bucket", m.labels + (("le", "+Inf"),),
                    count))
                out.append(_sample_line(m.name + "_sum", m.labels, total))
                out.append(_sample_line(m.name + "_count", m.labels,
                                        count))
            else:
                out.append(_sample_line(m.name, m.labels, m.value))
        with self._lock:
            collectors = sorted(self._collectors.items())
        for _cname, fn in collectors:
            try:
                rows = list(fn())
            except Exception:
                continue            # a broken collector never breaks the scrape
            for name, labels, value in rows:
                if name not in typed:
                    typed.add(name)
                    out.append(f"# TYPE {name} gauge")
                out.append(_sample_line(
                    name, tuple(sorted(labels.items())), float(value)))
        return "\n".join(out) + "\n"


def _escape_label(v) -> str:
    """Prometheus text-format label escaping: backslash, double quote,
    and newline must be escaped inside quoted label values."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _sample_line(name: str, labels: tuple, value) -> str:
    if labels:
        lbl = ",".join(f'{k}="{_escape_label(v)}"' for k, v in labels)
        return f"{name}{{{lbl}}} {value:g}"
    return f"{name} {value:g}"


REGISTRY = Registry()


_OBSERVED_PHASES = ("plan", "stage", "execute", "finalize")


# `observe_query`'s own metrics, looked up in the registry once a tier
# and once a phase (a metric is never unregistered), not every statement
@functools.lru_cache(maxsize=32)
def _query_metrics(tier: str) -> tuple:
    return (REGISTRY.counter("otb_queries_total", tier=tier),
            REGISTRY.histogram("otb_query_ms", tier=tier))


@functools.lru_cache(maxsize=len(_OBSERVED_PHASES))
def _phase_histogram(phase: str) -> Histogram:
    return REGISTRY.histogram("otb_phase_ms", phase=phase)


def observe_query(qt) -> None:
    """Trace-finish hook: fold one QueryTrace into the registry.  This
    runs on the connection's own thread, and a closed-loop client's
    next statement waits for it: one walk of the tree for the four
    phases, no registry lookup."""
    total, ms_total = _query_metrics(qt.tier or "single")
    total.inc()
    ms_total.observe(max(qt.total_ms, 0.0))
    for ph, ms in qt.phases_ms(_OBSERVED_PHASES).items():
        if ms > 0:
            _phase_histogram(ph).observe(ms)
