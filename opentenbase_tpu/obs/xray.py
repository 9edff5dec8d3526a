"""Cluster-wide observability: distributed trace context, wait-event
accounting, a live activity view, and a failure flight recorder.

Reference analogs: the (trace-id carrying) remote instrumentation that
explain_dist.c ships back to the coordinator, pg_stat_activity's
wait_event/wait_event_type columns, and the forensic surface a core
dump + log_min_error_statement gives a postgres operator — rebuilt for
the TPU engine's thread-per-session, RPC-per-fragment shape.

Three legs:

- **Trace context** (`inject`/`absorb`/`server_span`): the CN stamps a
  ``_xray`` key ({tid}) onto every outbound wire msg dict (backward
  compatible — servers that don't know it ignore it).  Servers open a
  bare root span around the handler body, so ALL existing server-side
  instrumentation (stage/execute/pool spans) nests under it for free,
  then piggy-back a byte-capped ``compact()`` of the subtree on the
  reply.  The CN grafts replies into the live trace: directly under
  the calling span when absorbed on the session thread, or into a
  pending map (``_REMOTE``) when absorbed on a dispatch worker thread
  — drained into the trace root at finish via ``on_trace_finish``.

- **Wait events** (`wait_event`/`mark`): a per-thread current-wait
  register plus cumulative log-bucket histograms (``otb_wait_ms``
  {event=...}) over the engine's named blocking points.  The register
  joins the activity view (below) so a live query shows WHAT it is
  waiting on, not just that it is slow.  A wait is also a ``wait``
  child span of the statement it delayed (``wait_ms`` of its summary)
  and an ``otb:wait:<event>`` annotation on the profiler's clock.

- **Flight recorder** (`flight`): guard-rail trips (quarantine,
  statement timeout, OOM downshift, breaker trip, poison bisection)
  snapshot a postmortem JSON bundle — trace tree (remote subtrees
  included), wait profile, recent guard transitions, counter snapshot
  — into a bounded ring and, when ``$OTB_FLIGHT_DIR`` is set, onto
  disk.  Retrievable over the wire via the CN ``flight`` op.

Everything here is fail-open: a broken flight write or a malformed
piggy-back must never abort a query, so the recording paths swallow
their own exceptions.  With ``OTB_TRACE=0`` the context functions take
the shared-NULL fast path (no dict writes, no allocation).

Env vars: ``OTB_XRAY_MAX_BYTES`` (piggy-back subtree cap, default
8192), ``OTB_FLIGHT_DIR`` (bundle directory, empty = ring only),
``OTB_FLIGHT_RING`` (bundle ring size, default 32).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

from ..utils import locks
from . import trace as _trace
from .metrics import REGISTRY

MAX_BYTES = int(os.environ.get("OTB_XRAY_MAX_BYTES", "8192") or "8192")
FLIGHT_DIR = os.environ.get("OTB_FLIGHT_DIR", "") or ""
FLIGHT_RING = int(os.environ.get("OTB_FLIGHT_RING", "32") or "32")

_TLS = threading.local()                # .tid: propagated trace id

_RLOCK = locks.Lock("obs.xray._RLOCK")
# trace_id -> [span dict subtrees pending graft]
_REMOTE: dict = {}                      # guarded_by: _RLOCK
_REMOTE_TRACES = 64                     # distinct in-flight traces kept
_REMOTE_SPANS = 64                      # subtrees kept per trace


# ---------------------------------------------------------------------------
# trace context: client side
# ---------------------------------------------------------------------------

def _current_tid() -> Optional[str]:
    qt = _trace.current_trace()
    if qt is not None:
        return qt.trace_id
    return getattr(_TLS, "tid", None)


def capture() -> Optional[str]:
    """Snapshot this thread's trace context for hand-off to a worker
    thread (the dispatch pool fans fragments out on threads that have
    no span stack of their own)."""
    return _current_tid()


class _Propagated:
    __slots__ = ("tid", "_prev")

    def __init__(self, tid):
        self.tid = tid
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_TLS, "tid", None)
        _TLS.tid = self.tid
        return self

    def __exit__(self, et, ev, tb):
        _TLS.tid = self._prev
        return False


def propagated(tid: Optional[str]) -> _Propagated:
    """Install a captured trace context on a worker thread for the
    duration of the block — `inject`/`absorb` then correlate the
    worker's RPCs with the originating query."""
    return _Propagated(tid)


def inject(msg: dict) -> dict:
    """Stamp the active trace context onto an outbound wire msg.
    Disabled tracing / no active trace → the msg is untouched (the
    shared-NULL fast path: one attr read, no allocation)."""
    if _trace.ENABLED:
        tid = _current_tid()
        if tid:
            msg["_xray"] = {"tid": tid}
    return msg


def absorb(resp, node: str = "", op: str = "") -> None:
    """Strip a reply's piggy-backed span subtree and graft it into the
    live trace.  On the session thread the subtree nests under the
    calling span (so remote `execute` never double-counts against the
    CN-observed RPC span); on a worker thread it parks in the pending
    map and is grafted at trace finish."""
    if not isinstance(resp, dict):
        return
    d = resp.pop("_xray", None)
    if not isinstance(d, dict):
        return
    sub = d.get("span")
    if not isinstance(sub, dict):
        return
    wrap = {"name": "remote", "ms": float(sub.get("ms") or 0.0),
            "attrs": {"node": node, "op": op}, "children": [sub]}
    if _trace.active():
        _trace.graft(wrap)
        return
    tid = d.get("tid") or getattr(_TLS, "tid", None)
    if not tid:
        return
    with _RLOCK:
        lst = _REMOTE.setdefault(tid, [])
        if len(lst) < _REMOTE_SPANS:
            lst.append(wrap)
        while len(_REMOTE) > _REMOTE_TRACES:     # oldest trace out
            _REMOTE.pop(next(iter(_REMOTE)))


def on_trace_finish(qt) -> None:
    """trace._finish hook: drain this trace's pending remote subtrees
    (absorbed on worker threads, where no span stack exists) into the
    finished tree so the ring/slow-log/flight views see them."""
    with _RLOCK:
        pend = _REMOTE.pop(qt.trace_id, None)
    if pend:
        for d in pend:
            try:
                qt.root.children.append(_trace.span_from_dict(d))
            except Exception:
                pass                  # a bad subtree never breaks finish


def peek_remote(tid: Optional[str]) -> list:
    """Pending remote subtrees for a still-open trace (EXPLAIN ANALYZE
    reads these before finish grafts them)."""
    if not tid:
        return []
    with _RLOCK:
        return [dict(d) for d in _REMOTE.get(tid, ())]


# ---------------------------------------------------------------------------
# trace context: server side
# ---------------------------------------------------------------------------

class _ServerSpan:
    """Handler-scope span: opened when the inbound msg carries trace
    context, so every span the server's own code opens nests under it;
    `attach()` piggy-backs the byte-capped subtree on the reply."""

    __slots__ = ("tid", "root", "_op", "_node")

    def __init__(self, msg, op: str, node: str = ""):
        ctx = msg.get("_xray") if isinstance(msg, dict) else None
        self.tid = ctx.get("tid") if isinstance(ctx, dict) else None
        self.root = None
        self._op = op
        self._node = node

    def __enter__(self):
        if self.tid and _trace.ENABLED:
            self.root = _trace.push_root("server", op=self._op,
                                         node=self._node)
        return self

    def __exit__(self, et, ev, tb):
        if self.root is not None:
            _trace.pop_root(self.root)
        return False

    # manual protocol for handler loops where the reply is built
    # across several suites and a `with` block would be awkward
    def open(self) -> "_ServerSpan":
        return self.__enter__()

    def close(self) -> None:
        self.__exit__(None, None, None)

    def attach(self, resp) -> None:
        if self.root is not None and isinstance(resp, dict):
            try:
                resp["_xray"] = {
                    "tid": self.tid,
                    "span": compact(self.root.to_dict(), MAX_BYTES)}
            except Exception:
                pass                  # never let tracing break a reply


def server_span(msg, op: str, node: str = "") -> _ServerSpan:
    return _ServerSpan(msg, op, node)


def compact(d: dict, max_bytes: int = MAX_BYTES) -> dict:
    """Shrink a span dict under `max_bytes` of JSON by progressively
    capping fan-out and depth; degenerates to a bare root."""
    def size(x) -> int:
        return len(json.dumps(x))

    if size(d) <= max_bytes:
        return d
    for width, depth in ((8, 8), (4, 6), (2, 4), (1, 2), (0, 0)):
        _prune(d, width, depth)
        if size(d) <= max_bytes:
            return d
    return {"name": str(d.get("name", "server")),
            "ms": float(d.get("ms") or 0.0),
            "attrs": {"truncated": True}}


def _prune(d: dict, width: int, depth: int) -> None:
    ch = d.get("children")
    if not ch:
        return
    if depth <= 0 or width <= 0:
        dropped = len(ch)
        d.pop("children", None)
        d.setdefault("attrs", {})["dropped"] = dropped
        return
    if len(ch) > width:
        d.setdefault("attrs", {})["dropped"] = len(ch) - width
        d["children"] = ch = ch[:width]
    for c in ch:
        _prune(c, width, depth - 1)


# ---------------------------------------------------------------------------
# per-DN remote phase rollup (EXPLAIN ANALYZE)
# ---------------------------------------------------------------------------

def remote_rows(qt=None) -> list:
    """[(node, {phase: ms, server_ms, rpcs})] aggregated from shipped
    subtrees — grafted ones plus any still pending for this trace."""
    qt = qt or _trace.current_trace() or _trace.last_trace()
    if qt is None:
        return []
    dicts = []
    work = [qt.root]
    while work:
        s = work.pop()
        for c in s.children:
            if c.name == "remote":
                dicts.append(c.to_dict())
            else:
                work.append(c)
    dicts.extend(peek_remote(getattr(qt, "trace_id", None)))
    agg: dict = {}
    for d in dicts:
        node = str((d.get("attrs") or {}).get("node") or "?")
        a = agg.setdefault(node, {"rpcs": 0})
        a["rpcs"] += 1
        stack = list(d.get("children") or ())
        while stack:
            c = stack.pop()
            nm = c.get("name")
            if nm in _trace.PHASES:
                # outermost-only, matching QueryTrace.phase_ms
                a[nm] = a.get(nm, 0.0) + float(c.get("ms") or 0.0)
            else:
                if nm == "server":
                    a["server_ms"] = a.get("server_ms", 0.0) \
                        + float(c.get("ms") or 0.0)
                stack.extend(c.get("children") or ())
    return sorted(agg.items())


# ---------------------------------------------------------------------------
# wait events
# ---------------------------------------------------------------------------

_WLOCK = locks.Lock("obs.xray._WLOCK")
# thread ident -> (event, started)
_WAITING: dict = {}                     # guarded_by: _WLOCK
# event names ever seen
_EVENTS: set = set()                    # guarded_by: _WLOCK


class _WaitCtx:
    __slots__ = ("event", "_t0", "_prev", "_ann")

    def __init__(self, event: str):
        self.event = event
        self._t0 = 0.0
        self._prev = None
        self._ann = TraceAnnotation("otb:wait:" + event) \
            if _trace.ENABLED else None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        ident = threading.get_ident()
        with _WLOCK:
            self._prev = _WAITING.get(ident)    # nested waits restore
            _WAITING[ident] = (self.event, time.time())
            _EVENTS.add(self.event)
        return self

    def __exit__(self, et, ev, tb):
        ms = (time.perf_counter() - self._t0) * 1e3
        ident = threading.get_ident()
        with _WLOCK:
            if self._prev is None:
                _WAITING.pop(ident, None)
            else:
                _WAITING[ident] = self._prev
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        REGISTRY.histogram("otb_wait_ms", event=self.event).observe(ms)
        if self._prev is None:
            # the statement it delayed: a `wait` child on its timeline
            # (a wait nested in another is covered by the outer one)
            _trace.record("wait", ms, event=self.event)
        return False


def wait_event(event: str, **detail) -> _WaitCtx:
    """Name a blocking wait: registers the event as this thread's
    current wait (otb_stat_activity joins on it) and folds the wall
    time into the ``otb_wait_ms{event=...}`` histogram on exit.
    `detail` kwargs are accepted for call-site documentation only."""
    return _WaitCtx(event)


def mark(event: str, **detail) -> None:
    """An instantaneous wait observation — e.g. a breaker-open
    fail-fast, which rejects instead of blocking but still belongs in
    the wait profile."""
    with _WLOCK:
        _EVENTS.add(event)
    REGISTRY.histogram("otb_wait_ms", event=event).observe(0.0)
    _trace.record("wait", 0.0, event=event)


def wait_rows() -> list:
    """(event, count, total_ms, p50, p95, p99) — otb_wait_events."""
    with _WLOCK:
        events = sorted(_EVENTS)
    rows = []
    for e in events:
        h = REGISTRY.histogram("otb_wait_ms", event=e)
        rows.append((e, int(h.count), float(h.sum),
                     h.quantile(0.5), h.quantile(0.95),
                     h.quantile(0.99)))
    return rows


def current_wait(ident) -> str:
    with _WLOCK:
        w = _WAITING.get(ident)
    return w[0] if w else ""


# ---------------------------------------------------------------------------
# activity view (otb_stat_activity)
# ---------------------------------------------------------------------------

_AIDS = itertools.count(1)
_ALOCK = locks.Lock("obs.xray._ALOCK")
# aid -> row dict
_ACTIVITY: dict = {}                    # guarded_by: _ALOCK


def activity_begin(sql: str, cancel=None, trace_id: str = "") -> int:
    """Register a live statement; returns its activity id (the cancel
    handle).  Caller owns the matching `activity_end`."""
    aid = next(_AIDS)
    with _ALOCK:
        _ACTIVITY[aid] = {"aid": aid, "sql": (sql or "")[:200],
                          "state": "queued", "t0": time.time(),
                          "thread": threading.get_ident(),
                          "cancel": cancel,
                          "trace_id": trace_id or ""}
    return aid


def activity_state(aid: int, state: str, thread=None) -> None:
    with _ALOCK:
        a = _ACTIVITY.get(aid)
        if a is not None:
            a["state"] = state
            if thread is not None:
                a["thread"] = thread


def activity_end(aid: int) -> None:
    with _ALOCK:
        _ACTIVITY.pop(aid, None)


def activity_cancel(aid: int) -> bool:
    """Fire a live statement's cancel handle (pg_cancel_backend's
    moral equivalent).  True if the statement was live and cancelable."""
    with _ALOCK:
        a = _ACTIVITY.get(aid)
        ev = a.get("cancel") if a else None
    if ev is None:
        return False
    ev.set()
    return True


def activity_rows() -> list:
    """(aid, state, wait_event, age_ms, cancelable, trace_id, sql) —
    one row per live statement, current wait joined by thread."""
    now = time.time()
    with _ALOCK:
        acts = [dict(a) for a in _ACTIVITY.values()]
    rows = []
    for a in sorted(acts, key=lambda a: a["aid"]):
        rows.append((a["aid"], a["state"], current_wait(a["thread"]),
                     (now - a["t0"]) * 1e3,
                     a["cancel"] is not None, a["trace_id"], a["sql"]))
    return rows


# ---------------------------------------------------------------------------
# guard-transition ring + flight recorder
# ---------------------------------------------------------------------------

_GLOCK = locks.Lock("obs.xray._GLOCK")
_GUARD_EVENTS: deque = deque(maxlen=256)    # guarded_by: _GLOCK

_FIDS = itertools.count(1)
_FLOCK = locks.Lock("obs.xray._FLOCK")
_FLIGHTS: deque = deque(maxlen=max(FLIGHT_RING, 1))  # guarded_by: _FLOCK


def guard_event(kind: str, **detail) -> None:
    """Record a guard transition (trip/shed/failover/quarantine...) in
    the bounded ring postmortem bundles snapshot, correlated with the
    active trace when there is one."""
    rec = {"ts": time.time(), "kind": kind}
    tid = _current_tid()
    if tid:
        rec["trace_id"] = tid
    for k, v in detail.items():
        rec[k] = v if isinstance(v, (str, int, float, bool,
                                     type(None))) else str(v)
    with _GLOCK:
        _GUARD_EVENTS.append(rec)


def guard_events() -> list:
    with _GLOCK:
        return [dict(r) for r in _GUARD_EVENTS]


def _counters_snapshot() -> dict:
    snap = {}
    try:
        for name, labels, kind, value in REGISTRY.samples():
            if kind != "counter":
                continue
            key = name
            if labels:
                key += "{" + ",".join(f"{k}={v}" for k, v in labels) + "}"
            snap[key] = value
    except Exception:
        pass                          # a broken collector never breaks a flight
    return snap


def flight(kind: str, sig: str = "", **extras) -> Optional[dict]:
    """Emit a postmortem bundle: ring it, count it, and (when
    ``$OTB_FLIGHT_DIR`` is set) persist it as JSON.  Fail-open — the
    recorder must never turn an incident into a second failure."""
    try:
        qt = _trace.current_trace() or _trace.last_trace()
        tid, trace_d = "", None
        if qt is not None:
            tid = getattr(qt, "trace_id", "") or ""
            try:
                trace_d = qt.to_dict()
                pend = peek_remote(tid)
                if pend:
                    trace_d.setdefault("spans", {}) \
                        .setdefault("children", []).extend(pend)
            except Exception:
                trace_d = None
        bundle = {"event": "flight", "kind": kind, "ts": time.time(),
                  "trace_id": tid, "signature": sig,
                  "waits": [list(r) for r in wait_rows()],
                  "guard_events": guard_events(),
                  "counters": _counters_snapshot(),
                  "trace": trace_d}
        if extras:
            bundle["extras"] = dict(extras)
        # round-trip through JSON now: a bundle that can be ringed can
        # always be retrieved/persisted later
        bundle = json.loads(json.dumps(bundle, default=str))
        with _FLOCK:
            _FLIGHTS.append(bundle)
        REGISTRY.counter("otb_flight_bundles_total", kind=kind).inc()
        if FLIGHT_DIR:
            try:
                os.makedirs(FLIGHT_DIR, exist_ok=True)
                path = os.path.join(
                    FLIGHT_DIR,
                    f"flight-{kind}-{int(time.time() * 1e3)}"
                    f"-{next(_FIDS)}.json")
                with open(path, "w") as f:
                    json.dump(bundle, f, sort_keys=True)
            except OSError:
                pass                  # a full/readonly disk never aborts a query
        return bundle
    except Exception:
        return None


def flights() -> list:
    """Ringed postmortem bundles, oldest → newest (the CN `flight`
    wire op's backing)."""
    with _FLOCK:
        return [dict(b) for b in _FLIGHTS]
