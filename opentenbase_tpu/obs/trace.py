"""Query tracing — lightweight span trees over the read path.

Reference analog: the per-node InstrumentOption timers that feed
EXPLAIN ANALYZE (instrument.c) generalized to the whole CN pipeline:
parse+plan, plancache hit/compile, bufferpool staging, fused/mesh
program dispatch, exchanges, host gather/finalize.

Design constraints (TPU-first):
- Device phases are timed ONLY at the existing materialization /
  sync boundaries (program-call overflow ``device_get``s, ``DBatch``
  materialization, gather conversion) — instrumentation never adds a
  host sync, and never appears inside a traced closure (enforced by
  the otblint ``obs-purity`` pass).
- ~zero overhead when disabled (``OTB_TRACE=0``): ``span()`` returns a
  shared no-op singleton, no Span objects are allocated, no locks are
  taken on the statement path.
- Thread-safe by construction: the active span stack is thread-local
  (each CN server session is a thread); only trace FINISH touches the
  shared ring, under ``_LOCK``.
- One clock with the device: every real span lives inside a
  ``jax.profiler.TraceAnnotation("otb:<name>")``, so a profiler session
  started by anyone records the span on the host plane of the same
  ``.xplane.pb`` as the device's "XLA Ops".  Outside a session the
  annotation is a no-op in the profiler; there is no flag.
- Thread CPU beside wall time, on the statement's ROOT only: the
  serving thread's CPU clock is read where the trace opens and where it
  closes (``cpu_ms``).  Wall less CPU is the time that thread was NOT
  running: blocked on the device, a socket or a lock, or runnable and
  waiting for the interpreter lock or a core.  Not on every span: on the
  chip tool's host a read of that clock is a 6 us system call that held
  up four serving threads for ~60 us, two a span cost a point read 15 %
  (PERF.md section 6, PR 36); and it ticks in 10 ms there, so only a
  MEAN over many statements says anything.
- Host↔device traffic is COUNTED where it happens, as span attributes
  (``d2h``/``d2h_bytes``: blocking device→host round trips, a batched
  ``device_get`` of many arrays ONE, and the bytes that crossed;
  ``h2d``/``h2d_bytes``: put calls, a batched ``device_put`` ONE, a
  scalar riding a program's own argument transfer none; ``calls``:
  compiled programs launched): only at the sync points above, by the
  code that makes the copy or the call.

Env vars: ``OTB_TRACE`` (default on), ``OTB_SLOW_MS`` (slow-query log
threshold, 0 = off), ``OTB_TRACE_RING`` (recent-trace ring size).
"""

from __future__ import annotations

import itertools
import json
import operator
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

from ..utils import locks

ENABLED = os.environ.get("OTB_TRACE", "1").strip().lower() \
    not in ("0", "off", "false")
SLOW_MS = float(os.environ.get("OTB_SLOW_MS", "0") or "0")
SLOW_STREAM = sys.stderr        # swappable in tests / by embedders
RING_CAP = int(os.environ.get("OTB_TRACE_RING", "64") or "64")

_TLS = threading.local()        # .stack: list[Span], .trace: QueryTrace
_LOCK = locks.Lock("obs.trace._LOCK")
_RING: deque = deque(maxlen=RING_CAP)   # guarded_by: _LOCK
_LAST: list = [None]                    # guarded_by: _LOCK
_IDS = itertools.count(1)
# per-process trace-id prefix: qids restart at 1 in every process, so
# cluster-wide correlation (slow log ↔ flight bundle ↔ shipped span)
# needs a process-unique component
_SEED = os.urandom(4).hex()

# canonical phase names summarized per query (otb_stat_query columns)
PHASES = ("plan", "stage", "execute", "exchange", "finalize")
# every span name `summary()` sums into a `*_ms` key
_SUMMED = PHASES + ("wire.recv", "wire.send", "parse", "autoprep", "bind",
                    "wait", "finalize.gather", "finalize.fetch",
                    "finalize.decode", "gather", "inputs", "release",
                    "initplan")
# what the `execute` span of a compiled tier says of the program that
# answered (`Executor.shape`, counted while it is traced): the ones that
# add up over a program's fragments and a statement's programs, and the
# ones that are a largest
SHAPE_SUMS = ("semi_joins", "anti_joins", "outer_joins", "sorted_aggs",
              "final_aggs")
SHAPE_MAXIMA = ("residual_semi_lanes", "sorted_agg_lanes",
                 "sorted_agg_groups", "strpred_codes", "final_agg_lanes",
                 "exchange_src_lanes")
_BY_START = operator.attrgetter("t0_ms")


class Span:
    """One timed region: name, start (``t0_ms``, an offset from the
    statement root's start), duration (``ms``) and, through the tree,
    its parent.  Context-manager protocol only: creation via ``span()``
    attaches nothing — ``__enter__`` pushes onto the thread's stack and
    enters the profiler annotation, ``__exit__`` stamps ``ms`` and
    pops."""

    __slots__ = ("name", "attrs", "t0_ms", "ms", "children", "_t0",
                 "_ann")

    def __init__(self, name: str, attrs: Optional[dict] = None):
        self.name = name
        self.attrs = attrs if attrs else {}
        self.t0_ms = 0.0
        self.ms = 0.0
        self.children: list = []
        self._t0 = 0.0
        self._ann = None

    def set(self, **kw) -> "Span":
        self.attrs.update(kw)
        return self

    def _start(self, st: list, since: Optional[float] = None) -> None:
        """Stamp the start (`since`, a `time.perf_counter()` reading,
        where the caller took it earlier), as an offset from the root's
        when there is one below, and enter the profiler's annotation."""
        self._ann = TraceAnnotation("otb:" + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter() if since is None else since
        if st:
            self.t0_ms = (self._t0 - st[0]._t0) * 1e3
        st.append(self)

    def _stop(self) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1e3
        self._ann.__exit__(None, None, None)
        self._ann = None

    def elapsed_ms(self) -> float:
        """`ms` once the span has ended; until then, the time so far."""
        if self._ann is None:
            return self.ms
        return (time.perf_counter() - self._t0) * 1e3

    def __enter__(self) -> "Span":
        st = _TLS.stack
        st[-1].children.append(self)
        self._start(st)
        return self

    def __exit__(self, et, ev, tb):
        self._stop()
        _TLS.stack.pop()
        return False

    def shift(self, by_ms: float) -> None:
        """Move the subtree on the statement's timeline (a grafted
        remote subtree arrives with offsets from its own root)."""
        work = [self]
        while work:
            s = work.pop()
            s.t0_ms += by_ms
            work.extend(s.children)

    def self_ms(self) -> float:
        """The span's duration less the part its children cover."""
        ms = self.elapsed_ms()
        covered, end = 0.0, self.t0_ms
        for c in sorted(self.children, key=_BY_START):
            lo = max(c.t0_ms, end)
            hi = min(c.t0_ms + c.elapsed_ms(), self.t0_ms + ms)
            if hi > lo:
                covered += hi - lo
                end = hi
        return max(ms - covered, 0.0)

    def to_dict(self) -> dict:
        d = {"name": self.name, "t0_ms": round(self.t0_ms, 4),
             "ms": round(self.ms, 4)}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d


class _NullSpan:
    """The disabled-path span: one shared instance, every operation a
    no-op — the zero-allocation fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, et, ev, tb):
        return False

    def set(self, **kw):
        return self


NULL_SPAN = _NullSpan()


def _stack() -> Optional[list]:
    return getattr(_TLS, "stack", None)


def _thread_clock() -> int:
    """The calling thread's CPU-time clock: `time.thread_time()`'s, by
    an id another thread can read too."""
    return time.pthread_getcpuclockid(threading.get_ident())


def thread_cpu() -> float:
    """The calling thread's CPU time, in seconds, on the clock a
    trace reads: what `trace_query(cpu_since=...)` takes."""
    return time.clock_gettime(_thread_clock())


def _now_ms(st: list) -> float:
    """Now, on the timeline of the statement whose root is st[0]."""
    return (time.perf_counter() - st[0]._t0) * 1e3


def active() -> bool:
    """True when a query trace is open on THIS thread."""
    return bool(getattr(_TLS, "stack", None))


def span(name: str, **attrs):
    """Open a child span under the current one.  Use as a context
    manager.  No active trace (or tracing disabled) → the shared
    no-op singleton."""
    st = getattr(_TLS, "stack", None)
    if not st:
        return NULL_SPAN
    return Span(name, attrs)


def event(name: str, **attrs) -> None:
    """Record a zero-duration child (cache hit/miss, retrace, upload)."""
    st = getattr(_TLS, "stack", None)
    if st:
        ev = Span(name, attrs)
        ev.t0_ms = _now_ms(st)
        st[-1].children.append(ev)


def record(name: str, ms: float, **attrs) -> None:
    """Record a child that has just ENDED and took `ms` (a wait the
    caller timed itself: obs/xray.py), on the statement's timeline."""
    st = getattr(_TLS, "stack", None)
    if st:
        sp = Span(name, attrs)
        sp.ms = ms
        sp.t0_ms = _now_ms(st) - ms
        st[-1].children.append(sp)


def annotate(**kw) -> None:
    """Attach attributes to the innermost open span, if any."""
    st = getattr(_TLS, "stack", None)
    if st:
        st[-1].attrs.update(kw)


def count(**kw) -> None:
    """Add to counters of the innermost open span, if any (a compiled
    program launched, a device↔host copy made: by whoever makes it)."""
    st = getattr(_TLS, "stack", None)
    if st:
        a = st[-1].attrs
        for k, v in kw.items():
            a[k] = a.get(k, 0) + v


# ---------------------------------------------------------------------------
# cross-node helpers (obs/xray.py) — server-side bare roots + grafting
# ---------------------------------------------------------------------------

def push_root(name: str, **attrs) -> Span:
    """Open a span on THIS thread even without an active trace — a
    server handler thread has no QueryTrace; the bare root becomes the
    piggy-backed subtree's top.  Pair with ``pop_root``."""
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    sp = Span(name, attrs)
    if st:                           # nested server op: ride the stack
        st[-1].children.append(sp)
    sp._start(st)
    return sp


def pop_root(sp: Span) -> Span:
    sp._stop()
    st = getattr(_TLS, "stack", None)
    if st and st[-1] is sp:
        st.pop()
    return sp


def span_from_dict(d: dict) -> Span:
    """Rehydrate a shipped span subtree (inverse of Span.to_dict)."""
    sp = Span(str(d.get("name", "?")), dict(d.get("attrs") or {}))
    sp.t0_ms = float(d.get("t0_ms") or 0.0)
    sp.ms = float(d.get("ms") or 0.0)
    sp.children = [span_from_dict(c) for c in d.get("children") or ()]
    return sp


def graft(d: dict) -> None:
    """Attach a shipped subtree under the current span (remote phase
    spans nest INSIDE the CN's RPC span, so ``phase_ms``'s
    outermost-only rule never double-counts them)."""
    st = getattr(_TLS, "stack", None)
    if st:
        sp = span_from_dict(d)
        # the subtree's offsets count from its own root: lay it so that
        # it ends now, when its reply has arrived
        sp.shift(_now_ms(st) - sp.ms - sp.t0_ms)
        st[-1].children.append(sp)


class QueryTrace:
    """One statement's span tree plus identity/summary fields."""

    __slots__ = ("qid", "signature", "root", "tier", "rows", "started",
                 "trace_id", "failed", "cpu_ms", "_c0", "_clk")

    def __init__(self, signature: str):
        self.qid = next(_IDS)
        self.signature = signature
        self.root = Span("query")
        self.tier = ""
        self.rows = 0
        self.started = time.time()
        self.trace_id = f"{_SEED}-{self.qid:x}"
        # set by whoever catches the statement's error INSIDE the trace
        # (the CN server, which still has the reply to send)
        self.failed = False
        # the owning thread's CPU over the statement (`_open` to
        # `_close`): an `adopt`ed thread's spans spend their own
        self.cpu_ms = 0.0
        self._c0 = 0.0
        self._clk = None             # that thread's CPU clock, while open

    @property
    def total_ms(self) -> float:
        return self.root.elapsed_ms()

    def _open(self, st: list, since: Optional[float],
              cpu_since: Optional[float]) -> None:
        """Start the root, on both clocks.  The CPU clock is read
        inside the wall clock's interval at both ends, so that `cpu_ms
        <= total_ms` where it is as fine as the wall's; no reading is
        clamped, so that on a clock that TICKS the mean over many
        statements stays what the thread spent."""
        self.root._start(st, since)
        self._clk = _thread_clock()
        self._c0 = time.clock_gettime(self._clk) if cpu_since is None \
            else cpu_since

    def _close(self) -> None:
        self.cpu_ms = self.elapsed_cpu_ms()
        self._clk = None
        self.root._stop()

    def elapsed_cpu_ms(self) -> float:
        """`cpu_ms` once the trace has closed; until then the owning
        thread's CPU so far, whichever thread asks (by the clock's id):
        `last_query_stats()` reads the CN server's open trace from the
        client's side."""
        clk = self._clk
        if clk is None:
            return self.cpu_ms
        try:
            return (time.clock_gettime(clk) - self._c0) * 1e3
        except OSError:              # the owning thread is gone
            return 0.0

    def phases_ms(self, names) -> dict:
        """{name: sum of ms over the spans of that name}, counting only
        the outermost of any nested same-name runs, in one walk."""
        ms = dict.fromkeys(names, 0.0)
        work = [(self.root, ())]
        while work:
            s, inside = work.pop()
            for c in s.children:
                hit = c.name in ms and c.name not in inside
                if hit:
                    ms[c.name] += c.ms
                if c.children:
                    work.append((c, inside + (c.name,) if hit else inside))
        return ms

    def phase_ms(self, name: str) -> float:
        return self.phases_ms((name,))[name]

    def sum_attr(self, span_name: str, key: str) -> float:
        total = 0.0
        work = [self.root]
        while work:
            s = work.pop()
            if s.name == span_name:
                total += float(s.attrs.get(key, 0) or 0)
            work.extend(s.children)
        return total

    def count_events(self, span_name: str, **match) -> int:
        n = 0
        work = [self.root]
        while work:
            s = work.pop()
            if s.name == span_name and all(
                    s.attrs.get(k) == v for k, v in match.items()):
                n += 1
            work.extend(s.children)
        return n

    def summary(self) -> dict:
        """The statement's numbers by key (`last_query_stats()`, the
        `otb_stat_query` view, the slow log).  One walk of the tree:
        `<span>_ms` sums the outermost spans of that name.  Of a trace
        still open (the CN server's, read by `last_query_stats()` while
        the reply is on its way) every span reads as of now."""
        ms = dict.fromkeys(_SUMMED, 0.0)
        staged = materialized = overlapped = 0.0
        fetches = fetch_bytes = 0
        exchanges = exchange_bytes = pack_lanes = 0
        traced = baked = retraces = 0
        shape = dict.fromkeys(SHAPE_SUMS + SHAPE_MAXIMA, 0)
        initplans = 0
        hits = misses = 0
        d2h = d2h_bytes = h2d = h2d_bytes = calls = 0
        work = [(self.root, ())]
        while work:
            s, inside = work.pop()
            name = s.name
            inside_c = inside
            if name in ms and name not in inside:
                ms[name] += s.elapsed_ms()
                inside_c = inside + (name,)     # nested runs count once
            a = s.attrs
            if a:
                # host<->device traffic, on whichever span made it
                d2h += a.get("d2h", 0)
                d2h_bytes += a.get("d2h_bytes", 0)
                h2d += a.get("h2d", 0)
                h2d_bytes += a.get("h2d_bytes", 0)
                calls += a.get("calls", 0)
                if name == "upload":
                    staged += a.get("bytes", 0) or 0
                elif name == "finalize":
                    materialized += a.get("bytes", 0) or 0
                elif name == "stage":
                    overlapped += a.get("overlapped_ms", 0) or 0
                elif name == "finalize.fetch":
                    fetches += a.get("fetches", 0) or 0
                    fetch_bytes += a.get("bytes", 0) or 0
                elif name == "execute":
                    exchanges += a.get("exchanges", 0) or 0
                    exchange_bytes += a.get("exchange_bytes", 0) or 0
                    pack_lanes += a.get("pack_lanes", 0) or 0
                    retraces += a.get("retraces", 0) or 0
                    if not a.get("retraces"):
                        # the program that answered, not one whose
                        # size class overflowed and was replayed
                        for k in SHAPE_SUMS:
                            shape[k] += a.get(k, 0) or 0
                        for k in SHAPE_MAXIMA:
                            shape[k] = max(shape[k], a.get(k, 0) or 0)
                elif name == "bind":
                    traced += a.get("traced", 0) or 0
                    baked += a.get("baked", 0) or 0
                elif name == "pool":
                    if a.get("hit") is True:
                        hits += 1
                    elif a.get("hit") is False:
                        misses += 1
            if name == "initplan":
                initplans += 1
            for c in s.children:
                work.append((c, inside_c))
        d = {
            "qid": self.qid,
            "trace_id": self.trace_id,
            "signature": self.signature,
            "tier": self.tier or "single",
            "fallback": self.root.attrs.get("fallback", ""),
            "total_ms": self.total_ms,
            "rows": self.rows,
            "bytes_staged": int(staged),
            "bytes_materialized": int(materialized),
            "pool_hits": hits,
            "pool_misses": misses,
        }
        for ph in PHASES:
            d[f"{ph}_ms"] = ms[ph]
        # overlap-adjusted staging (otbpipe): wall time the dispatch
        # path actually WAITED on staging.  Producers mark staging that
        # ran behind device compute with an `overlapped_ms` attr on the
        # stage span; without overlap this equals stage_ms, so the new
        # pipeline doesn't misread as staging going to zero.
        d["stage_wait_ms"] = max(d["stage_ms"] - overlapped, 0.0)
        d["wire_ms"] = ms["wire.recv"] + ms["wire.send"]
        d["parse_ms"] = ms["parse"]
        d["autoprep_ms"] = ms["autoprep"]
        # the lifted literals: host time to bind them (values, then
        # dictionary codes in the tier that ran), how many rode as
        # program inputs, how many WHERE literals stayed in the keys,
        # and how often a size class overflowed and the program replayed
        d["bind_ms"] = ms["bind"]
        d["params_traced"] = int(traced)
        d["params_baked"] = int(baked)
        d["retraces"] = int(retraces)
        d["wait_ms"] = ms["wait"]
        d["finalize_gather_ms"] = ms["finalize.gather"]
        d["finalize_fetch_ms"] = ms["finalize.fetch"]
        d["finalize_decode_ms"] = ms["finalize.decode"]
        d["finalize_fetches"] = int(fetches)
        d["finalize_fetch_bytes"] = int(fetch_bytes)
        d["exchanges"] = int(exchanges)
        d["exchange_bytes"] = int(exchange_bytes)
        # the destination slots those exchanges' packs search and fetch
        # (`kernels.bucket_rows`: ndn * bucket a redistribute), fixed
        # when the program was traced like the two above
        d["pack_lanes"] = int(pack_lanes)
        # the shape of the compiled programs that answered, fixed when
        # they were traced: joins answered by a mask (semi, anti: no
        # expansion) and the anti ones among them, joins through the
        # expansion's left-outer arm, the largest class a semi or anti
        # join with a residual expands into (0: none does), sorted
        # aggregates, the padded rows of the largest and the largest
        # output class (group slots), the largest code set or bitmap a
        # string predicate brings; the final halves of two-phase
        # aggregates (`Agg final` over redistributed partials) and the
        # padded lanes the largest one's partials arrive in; the largest
        # padded SOURCE class a redistribute packs from (`pack_lanes`
        # is the destination side); and the scalar subqueries run
        # before the statement, with their time
        for k, v in shape.items():
            d[k] = int(v)
        d["initplans"] = int(initplans)
        d["initplan_ms"] = ms["initplan"]
        d["unattributed_ms"] = self.root.self_ms()
        # the host path around a program call: its inputs made ready
        # (staged arrays looked up; the fused tier's scalars put), the
        # mesh tier's gathered rows re-padded on the host and put back
        # for the CN fragment, device buffers dropped
        d["inputs_ms"] = ms["inputs"]
        d["gather_ms"] = ms["gather"]
        d["release_ms"] = ms["release"]
        # thread CPU beside wall time.  `cpu_ms` is the serving
        # thread's for the statement (an adopted thread's spans spent
        # their own thread's and are not in it); `offcpu_ms` the part of
        # `total_ms` that thread did not run: blocked on the device, a
        # socket or a lock, or waiting for the interpreter lock or a
        # core.  Not clamped (see `_open`): on a tick clock one
        # statement's reading can pass its wall time, the mean does not
        d["cpu_ms"] = self.elapsed_cpu_ms()
        d["offcpu_ms"] = self.root.elapsed_ms() - d["cpu_ms"]
        # host<->device round trips, counted where they are made:
        # blocking device->host round trips (`execute`'s one copy of
        # the overflow vectors, in the mesh tier with every gathered
        # array; `finalize.fetch`'s one copy: a batched copy counts
        # once), host->device put calls (the fused tier's scalars in
        # `inputs` and `execute`, `gather`'s one batched put back,
        # `upload`s; `h2d_bytes` adds up the arrays', not the scalars')
        # and compiled programs launched (`execute`, `finalize.gather`)
        d["host_syncs"] = int(d2h + fetches)
        d["d2h_bytes"] = int(d2h_bytes + fetch_bytes)
        d["h2d_puts"] = int(h2d)
        d["h2d_bytes"] = int(h2d_bytes + staged)
        d["program_calls"] = int(calls)
        return d

    def to_dict(self) -> dict:
        d = self.summary()
        d["spans"] = self.root.to_dict()
        return d


class _TraceCtx:
    """``trace_query`` context: opens a fresh QueryTrace unless one is
    already active on this thread (nested statements — triggers, the
    EXPLAIN ANALYZE inner run — ride the enclosing trace)."""

    __slots__ = ("signature", "since", "cpu_since", "owned")

    def __init__(self, signature: str, since: Optional[float],
                 cpu_since: Optional[float]):
        self.signature = signature
        self.since = since
        self.cpu_since = cpu_since
        self.owned = None

    def __enter__(self) -> Optional[QueryTrace]:
        if not ENABLED:
            return None
        st = _stack()
        if st is None:
            st = _TLS.stack = []
        if st:                       # nested: join the active trace
            return getattr(_TLS, "trace", None)
        qt = QueryTrace(self.signature)
        self.owned = qt
        _TLS.trace = qt
        qt._open(st, self.since, self.cpu_since)
        return qt

    def __exit__(self, et, ev, tb):
        qt = self.owned
        if qt is not None:
            qt._close()
            _TLS.stack.pop()
            _TLS.trace = None
            _finish(qt, failed=qt.failed or et is not None)
        return False


class _NullTraceCtx:
    """Disabled-path trace context: one shared instance, yields None."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, et, ev, tb):
        return False


_NULL_CTX = _NullTraceCtx()


def trace_query(signature: str = "", since: Optional[float] = None,
                cpu_since: Optional[float] = None):
    """The statement's trace; `since` (a `time.perf_counter()` reading)
    and `cpu_since` (a `thread_cpu()` one) backdate its start to when
    the caller says the statement began: the CN server decodes a
    message before it knows it is one."""
    if not ENABLED:
        return _NULL_CTX
    return _TraceCtx(signature, since, cpu_since)


class _Adopted:
    """`adopt` context: this thread's spans go under another thread's
    open trace (the serving tier runs a statement on a dispatcher
    thread while its connection thread, which owns the trace, waits).
    Both threads append children to the root; the owner finishes it."""

    __slots__ = ("qt", "_prev")

    def __init__(self, qt: QueryTrace):
        self.qt = qt
        self._prev = None

    def __enter__(self) -> QueryTrace:
        self._prev = (_stack(), getattr(_TLS, "trace", None))
        _TLS.stack = [self.qt.root]
        _TLS.trace = self.qt
        return self.qt

    def __exit__(self, et, ev, tb):
        _TLS.stack, _TLS.trace = self._prev
        return False


def adopt(qt: Optional[QueryTrace]):
    return _Adopted(qt) if qt is not None else _NULL_CTX


def current_trace() -> Optional[QueryTrace]:
    """The trace open on this thread, else None."""
    return getattr(_TLS, "trace", None) if active() else None


def last_trace() -> Optional[QueryTrace]:
    """The most recently FINISHED trace (any thread)."""
    with _LOCK:
        return _LAST[0]


def recent() -> list:
    """Finished traces, oldest → newest (the otb_stat_query backing)."""
    with _LOCK:
        return list(_RING)


def _finish(qt: QueryTrace, failed: bool = False) -> None:
    try:
        # graft remote subtrees absorbed on worker threads BEFORE the
        # trace becomes visible in the ring / metrics / slow log
        from . import xray
        xray.on_trace_finish(qt)
    except Exception:
        pass                         # observability never fails a query
    with _LOCK:
        _RING.append(qt)
        _LAST[0] = qt
    from . import metrics
    metrics.observe_query(qt)
    if SLOW_MS > 0 and qt.total_ms >= SLOW_MS and not failed:
        metrics.REGISTRY.counter("otb_slow_queries_total").inc()
        rec = qt.summary()
        rec["event"] = "slow_query"
        try:
            SLOW_STREAM.write(json.dumps(rec, sort_keys=True) + "\n")
            SLOW_STREAM.flush()
        except (OSError, ValueError):
            pass                     # a closed log stream never aborts a query
