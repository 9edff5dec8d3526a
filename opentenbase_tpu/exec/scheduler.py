"""Concurrent serving tier: async admission + same-program batching.

Reference analog: the coordinator's session pooler + resource queues
(OpenTenBase pools hundreds of pgwire backends per CN and gates them
through resource-group concurrency slots).  Here the pool is an
admission/scheduling layer between sessions and the executor, built
around what an accelerator-resident engine can do that a tuple-at-a-
time one cannot: queries that share a literal-masked fused-program
signature (exec/plancache.py keys, exec/fused.py masking) are the SAME
compiled XLA program with different constants — so N of them arriving
within a short window coalesce into ONE dispatch.  Their masked
literals and MVCC (snapshot, txid) pairs stack along a leading batch
axis and `jax.lax.map` runs the shared fragment once per batch element
inside one executable (fused.run_fused_batch), then per-query results
demux as device views into the stacked output.

Pipelining (otbpipe): the dispatcher thread only classifies, coalesces,
stages, and launches — JAX async dispatch returns before device compute
finishes, so while the device computes batch i the dispatcher is
already staging batch i+1 (bufferpool uploads + program lookup).  The
one host sync a coalesced dispatch needs (the join-ladder overflow
check, fused.finish_fused_batch) runs on a dedicated DRAINER thread fed
by a bounded completion queue, so the dispatch loop never blocks on the
device; per-query materialization stays on each CLIENT thread.  GTM
slot ownership transfers to the drainer when a flight enqueues, and the
drainer releases it — the slot ledger stays exact across the thread
boundary.  `enable_pipeline` GUC (env OTB_SCHED_PIPELINE, default on)
switches the overlap off, falling back to the synchronous dispatch
path with bit-identical results.

Admission: GTM resource-group slots (owner + lease, gtm/server.py)
throttle concurrent dispatches per group — a coalesced batch holds one
slot (it is one device dispatch), serial statements hold one each.
Over-admission sheds: a full per-group queue rejects at submit, and a
query that cannot acquire a slot before its shed deadline is dropped
with an error, releasing nothing it does not hold.  Non-batchable
statements (DML, DDL, multi-statement strings, open transactions,
init-plan SELECTs) run serially on a worker pool under the same
admission throttle; writes additionally serialize on one lane.

Knobs: OTB_SCHED_WINDOW_MS (coalescing window, default 2), OTB_SCHED_
MAX_BATCH (default 16), OTB_SCHED_QUEUE_DEPTH (per-group, default
128), OTB_SCHED_SHED_TIMEOUT_MS (default 5000), OTB_SCHED_SLOTS
(default admission cap when the group has no catalog entry, default
8), OTB_SCHED_WORKERS (serial lanes, default 8).

Observability: the otb_scheduler stat view (parallel/statviews.py)
reports admitted/queued/batched/shed counts, a batch-size histogram,
and queue-wait p50/p99 from the module-level counters below.
"""

from __future__ import annotations

import collections
import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from ..sql import ast as A
from ..sql.parser import parse_sql
from . import share as workshare
from . import shield
from .executor import ExecContext, ExecError, materialize
from .fused import (batch_signature, finish_fused_batch,
                    launch_fused_batch, run_fused_batch,
                    stage_fused_batch)
from .session import Result
from ..obs import trace as obs_trace
from ..obs import xray as obs_xray
from ..utils import locks, snapcheck

# ---------------------------------------------------------------------------
# serving-tier telemetry (surfaced by the otb_scheduler view).  Counters
# are process-global across Scheduler instances so the view aggregates
# every serving front-end in the process.
# ---------------------------------------------------------------------------
_STATS_LOCK = locks.Lock("exec.scheduler._STATS_LOCK")
_STATS: dict = {          # guarded_by: _STATS_LOCK
    "admitted": 0,        # queries that passed admission and executed
    "batched": 0,         # queries served by a multi-query dispatch
    "shed": 0,            # rejected: queue full or shed-deadline passed
    "dispatches": 0,      # device dispatches (a batch counts once)
    "batch_dispatches": 0,
    # slot-discipline ledger: every successful GTM slot acquire must be
    # matched by exactly one release, no matter which exception path a
    # statement dies on — asserted equal after drain (otbshield)
    "slots_acquired": 0,
    "slots_released": 0,
    # statement-deadline / cancel outcomes (otbshield)
    "expired": 0,         # statement_timeout fired (queued or in-flight)
    "canceled": 0,        # cancel event consumed (queued or in-flight)
    # two-stage pipeline (otbpipe): dispatches whose finish-phase host
    # sync ran on the drainer thread, and how much staging wall time
    # overlapped an in-flight device dispatch (staging wait ≪ staging
    # work once warm)
    "pipelined_dispatches": 0,
    "drained": 0,         # flights the drainer completed
    "stage_work_ms": 0.0,     # total staging wall time
    "stage_overlap_ms": 0.0,  # staging wall time hidden behind compute
}
_HIST: dict = {}          # guarded_by: _STATS_LOCK — batch size -> count
_WAITS: collections.deque = collections.deque(  # guarded_by: _STATS_LOCK
    maxlen=4096)          # recent queue waits (ms), submit -> execution
_SCHEDULERS: list = []    # guarded_by: _STATS_LOCK — live instances


def _pct(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return float(sorted_vals[idx])


def stats_snapshot() -> dict:
    """Aggregate serving-tier counters (otb_scheduler view backing)."""
    with _STATS_LOCK:
        d = dict(_STATS)
        waits = sorted(_WAITS)
        hist = dict(sorted(_HIST.items()))
        scheds = list(_SCHEDULERS)
    d["queued"] = sum(s.queue_depth() for s in scheds)
    d["queue_wait_p50_ms"] = _pct(waits, 0.50)
    d["queue_wait_p99_ms"] = _pct(waits, 0.99)
    d["batch_hist"] = " ".join(f"{k}:{v}" for k, v in hist.items())
    d["hist"] = hist
    # otbpipe surfaces: how deep the completion queue sits right now,
    # and what fraction of staging work the pipeline hid behind compute
    d["drain_queue_depth"] = sum(s.drain_depth() for s in scheds)
    work = float(d.get("stage_work_ms", 0.0))
    d["pipeline_overlap_ratio"] = \
        (float(d.get("stage_overlap_ms", 0.0)) / work) if work > 0 \
        else 0.0
    return d


def stats_rows() -> list:
    """One row for the otb_scheduler view."""
    d = stats_snapshot()
    return [(d["admitted"], d["queued"], d["batched"], d["shed"],
             d["dispatches"], d["batch_dispatches"],
             d["queue_wait_p50_ms"], d["queue_wait_p99_ms"],
             d["batch_hist"])]


def reset_stats():
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0
        _HIST.clear()
        _WAITS.clear()


def _bump(field: str, n: int = 1):
    with _STATS_LOCK:
        _STATS[field] += n


def _note_dispatch(items, t_start: float):
    k = len(items)
    with _STATS_LOCK:
        _STATS["admitted"] += k
        _STATS["dispatches"] += 1
        if k > 1:
            _STATS["batched"] += k
            _STATS["batch_dispatches"] += 1
        _HIST[k] = _HIST.get(k, 0) + 1
        for it in items:
            _WAITS.append((t_start - it.t_submit) * 1e3)


def _note_stage(ms: float, overlapped: bool):
    """Account one staging pass; `overlapped` when at least one flight
    was computing on-device while this staging ran (the wall time the
    dispatch loop did NOT spend idle waiting on the device)."""
    with _STATS_LOCK:
        _STATS["stage_work_ms"] += ms
        if overlapped:
            _STATS["stage_overlap_ms"] += ms


def _metrics_samples():
    """otb_sched_* samples for the unified registry (obs/metrics.py) —
    the otbtrace pane the ISSUE's pipeline counters surface through."""
    d = stats_snapshot()
    for k in ("admitted", "queued", "batched", "shed", "dispatches",
              "batch_dispatches", "slots_acquired", "slots_released",
              "expired", "canceled", "pipelined_dispatches", "drained"):
        yield (f"otb_sched_{k}", {}, d[k])
    yield ("otb_sched_stage_work_ms", {}, d["stage_work_ms"])
    yield ("otb_sched_stage_overlap_ms", {}, d["stage_overlap_ms"])
    yield ("otb_sched_pipeline_overlap_ratio", {},
           d["pipeline_overlap_ratio"])
    yield ("otb_sched_drain_queue_depth", {}, d["drain_queue_depth"])


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def slot_balance() -> tuple:
    """(acquired, released) across every scheduler in the process —
    equal once all submitted work has drained (the no-leak invariant
    the chaos harness asserts)."""
    with _STATS_LOCK:
        return _STATS["slots_acquired"], _STATS["slots_released"]


def assert_slot_balance():
    acq, rel = slot_balance()
    assert acq == rel, f"admission slot leak: acquired={acq} released={rel}"


def _stmt_timeout_s(session) -> Optional[float]:
    """The session's statement_timeout GUC in seconds (PG semantics:
    milliseconds, 0/unset = disabled)."""
    owner = getattr(session, "node", None) or \
        getattr(session, "cluster", None)
    gucs = getattr(owner, "gucs", None) or {}
    raw = str(gucs.get("statement_timeout", "") or "").strip()
    if not raw:
        return None
    try:
        ms = float(raw)
    except ValueError:
        return None
    return ms / 1e3 if ms > 0 else None


class _Shed(Exception):
    pass


class _Gone(Exception):
    """Admission abandoned: the item expired/canceled while waiting for
    a slot — it is already finished, and NO slot is held."""


class CancelEvent(threading.Event):
    """A cancel signal that can WAKE parked waiters.  A plain Event
    forces `Scheduler.wait` to poll (an idle-spin that wastes CPU at
    low load); this variant notifies every registered
    per-item condition when it fires, so waiters park on their
    completion CV and still observe an out-of-band cancel promptly.
    The CN server hands one of these to every connection session."""

    def __init__(self):
        super().__init__()
        self._waiters: list = []
        self._wlk = threading.Lock()

    def register(self, cv) -> None:
        with self._wlk:
            self._waiters.append(cv)

    def unregister(self, cv) -> None:
        with self._wlk:
            try:
                self._waiters.remove(cv)
            except ValueError:
                pass

    def set(self):
        super().set()
        with self._wlk:
            cvs = list(self._waiters)
        for cv in cvs:
            with cv:
                cv.notify_all()


class _Flight:
    """One launched coalesced dispatch crossing the dispatcher→drainer
    boundary.  The GTM slot acquired for the dispatch is OWNED by this
    record once enqueued — the drainer releases it."""

    __slots__ = ("items", "flight", "sb", "group", "t_start")

    def __init__(self, items, flight, sb, group, t_start):
        self.items = items
        self.flight = flight
        self.sb = sb
        self.group = group
        self.t_start = t_start


_STOP = object()


class _Item:
    """One submitted statement moving through the scheduler."""
    __slots__ = ("session", "sql", "planned", "info", "group",
                 "t_submit", "ev", "error", "results", "batch",
                 "out_names", "is_write", "deadline", "cancel_event",
                 "lk", "cv", "detached", "degraded", "lits",
                 "snap", "vkey", "aid", "trace")

    def __init__(self, session, sql):
        self.session = session
        self.sql = sql
        self.aid = 0              # otb_stat_activity handle (0 = none)
        # the statement's trace, open on the submitting (connection)
        # thread: the serial lane's dispatcher thread adopts it
        self.trace = obs_trace.current_trace()
        self.planned = None
        self.info = None          # FragSig when batchable, else None
        self.group = "default"
        self.t_submit = time.monotonic()
        self.ev = threading.Event()
        self.error: Optional[BaseException] = None
        self.results: Optional[list] = None   # serial path (materialized)
        self.batch = None         # batched path: demuxed DBatch view
        self.out_names = None
        self.is_write = False
        # statement deadline (absolute monotonic) from the session's
        # statement_timeout GUC at submit time; None = unbounded
        to = _stmt_timeout_s(session)
        self.deadline = None if to is None else self.t_submit + to
        # out-of-band cancel propagates into QUEUED and BATCHED items
        # (previously only the serial lane's execute() polled it)
        self.cancel_event = getattr(session, "cancel_event", None)
        # completion/detach handshake: the waiter may abandon the item
        # (deadline, cancel) while a dispatcher/worker is completing it
        self.lk = threading.Lock()
        # the waiter parks on this (instead of polling ev) — _complete
        # notifies it, and a CancelEvent wakes it out-of-band
        self.cv = threading.Condition(self.lk)
        self.detached = False     # guarded_by: lk
        self.degraded = False     # served by the spill path (shield)
        self.lits = None          # literal bindings (poison fault surface)
        # result-cache tags (exec/share.py): the snapshot GTS drawn for
        # this statement and the per-table store-version tuple captured
        # WITH it — both set at dispatch, consumed at materialization
        self.snap = None
        self.vkey = None

    @property
    def sig(self):
        return None if self.info is None else self.info.sig


class Scheduler:
    """Admission + coalescing front-end over single-node sessions.

    Client threads call `run(session, sql)`; a dispatcher thread drains
    the arrival queue, groups same-signature SELECTs arriving within
    the batch window into one compiled dispatch, and hands everything
    else to an admission-capped serial worker pool."""

    def __init__(self, node=None, gtm=None,
                 window_ms: Optional[float] = None,
                 max_batch: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 shed_timeout_ms: Optional[float] = None,
                 slots: Optional[int] = None,
                 workers: Optional[int] = None,
                 lease_s: float = 30.0):
        self.node = node
        if gtm is None:
            # in-process GTM core: the same slot/lease semantics a
            # cluster deployment gets from the GTM service
            from ..gtm.server import GtmCore
            gtm = GtmCore()
        self.gtm = gtm
        self.window_s = (_env_float("OTB_SCHED_WINDOW_MS", 2.0)
                         if window_ms is None else window_ms) / 1e3
        self.max_batch = _env_int("OTB_SCHED_MAX_BATCH", 16) \
            if max_batch is None else max_batch
        self.max_queue = _env_int("OTB_SCHED_QUEUE_DEPTH", 128) \
            if queue_depth is None else queue_depth
        self.shed_s = (_env_float("OTB_SCHED_SHED_TIMEOUT_MS", 5000.0)
                       if shed_timeout_ms is None else shed_timeout_ms) \
            / 1e3
        self.slots = _env_int("OTB_SCHED_SLOTS", 8) \
            if slots is None else slots
        self.workers = _env_int("OTB_SCHED_WORKERS", 8) \
            if workers is None else workers
        self.lease_s = lease_s
        self._owner = f"sched{os.getpid()}-{id(self):x}"
        self._q: queue.Queue = queue.Queue()
        self._deferred: collections.deque = collections.deque()
        self._depth: dict = {}          # group -> queued count
        self._lock = locks.Lock("exec.scheduler.Scheduler._lock")
        self._write_lock = locks.Lock("exec.scheduler.Scheduler._write_lock")   # one write lane
        self._pool: Optional[ThreadPoolExecutor] = None
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        # two-stage pipeline: launched flights await their finish-phase
        # host sync here.  Bounded — a full queue back-pressures the
        # dispatcher (it blocks on put), capping device work in flight.
        self._drainq: queue.Queue = queue.Queue(
            maxsize=max(1, _env_int("OTB_SCHED_DRAIN_DEPTH", 4)))
        self._drain_thread: Optional[threading.Thread] = None
        # flights launched but not yet finished, and staging passes
        # currently running: staging that starts while inflight > 0 is
        # overlapped with device compute (the pipeline_overlap_ratio)
        self._pipe_lock = locks.Lock(
            "exec.scheduler.Scheduler._pipe_lock")
        self._inflight = 0              # guarded_by: _pipe_lock
        # admission parking: _release notifies; waiters still wake on a
        # bounded timeout because GTM-side releases (other processes,
        # lease reaping) can't notify this condition
        self._slot_cv = locks.Condition(
            name="exec.scheduler.Scheduler._slot_cv")
        with _STATS_LOCK:
            _SCHEDULERS.append(self)

    # -- lifecycle --------------------------------------------------------
    def _ensure_started(self):
        with self._lock:
            if self._thread is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=max(1, self.workers),
                    thread_name_prefix="otb-sched")
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="otb-sched-disp")
                self._thread.start()

    def _ensure_drainer(self):
        with self._lock:
            if self._drain_thread is None:
                self._drain_thread = threading.Thread(
                    target=self._drain_loop, daemon=True,
                    name="otb-sched-drain")
                self._drain_thread.start()

    def stop(self):
        with self._lock:
            self._stopped = True
            started = self._thread is not None
            drainer = self._drain_thread
        if started:
            self._q.put(_STOP)
            self._thread.join(timeout=30)
            if drainer is not None:
                # FIFO: every flight the dispatcher enqueued drains
                # before the sentinel — no result is abandoned.
                # Shutdown path, not a query-visible stall: no wait
                # event (the dispatcher is already stopped, so the
                # queue only shrinks from here).
                self._drainq.put(_STOP)  # otblint: disable=wait-discipline
                drainer.join(timeout=30)
            self._pool.shutdown(wait=True)
        try:
            self.gtm.resq_disconnect(self._owner)
        except Exception:
            pass
        with _STATS_LOCK:
            if self in _SCHEDULERS:
                _SCHEDULERS.remove(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    def queue_depth(self) -> int:
        with self._lock:
            return sum(self._depth.values())

    def drain_depth(self) -> int:
        return self._drainq.qsize()

    # -- client API -------------------------------------------------------
    def run(self, session, sql: str) -> list:
        """Submit and wait: the serving tier's `session.execute`."""
        item = self.submit(session, sql)
        return self.wait(item)

    def submit(self, session, sql: str) -> _Item:
        if self._stopped:
            raise ExecError("scheduler is stopped")
        self._ensure_started()
        item = _Item(session, sql)
        self._classify(item)
        if self._serve_cached(item):
            return item     # result-cache hit: zero device dispatches
        with self._lock:
            depth = self._depth.get(item.group, 0)
            if self.max_queue > 0 and depth >= self.max_queue:
                over = True
            else:
                over = False
                self._depth[item.group] = depth + 1
        if over:
            _bump("shed")
            raise ExecError(
                f"resource group '{item.group}' queue is full "
                f"({self.max_queue} queued): query shed")
        # live-statement registration (otb_stat_activity): born queued,
        # state advances at dispatch; the waiter unregisters in wait()
        item.aid = obs_xray.activity_begin(item.sql,
                                           cancel=item.cancel_event)
        self._q.put(item)
        return item

    def wait(self, item: _Item, timeout: float = 600.0) -> list:
        """Wait for completion, honoring the statement deadline and the
        session's cancel event.  On expiry/cancel the item DETACHES: it
        finishes here, batch-mates are untouched, and whichever
        dispatcher later tries to complete it becomes a no-op.

        The waiter PARKS on the item's condition — _complete notifies
        it, and a CancelEvent wakes it out-of-band.  Only a legacy
        plain-Event cancel still forces the short poll slice (it has no
        way to wake a parked waiter)."""
        end = time.monotonic() + timeout
        if item.deadline is not None:
            end = min(end, item.deadline)
        cancel = item.cancel_event
        wakeable = isinstance(cancel, CancelEvent)
        if wakeable:
            cancel.register(item.cv)
        try:
            with item.cv:
                while not item.ev.is_set():
                    now = time.monotonic()
                    rem = end - now
                    if rem <= 0:
                        # the detach check, inlined under item.lk (cv
                        # wraps the same lock _complete takes)
                        if not item.detached:
                            item.detached = True
                            if item.deadline is not None \
                                    and now >= item.deadline:
                                _bump("expired")
                                obs_xray.flight("statement_timeout",
                                                sig=item.sql)
                                raise ExecError(
                                    "canceling statement due to "
                                    "statement timeout")
                            raise ExecError(
                                "scheduler: query timed out awaiting "
                                "dispatch")
                        break    # completed under the wire
                    if cancel is not None and cancel.is_set():
                        cancel.clear()
                        if not item.detached:
                            item.detached = True
                            _bump("canceled")
                            raise ExecError(
                                "canceling statement due to user "
                                "request")
                        break
                    with obs_xray.wait_event("sched-result"):
                        item.cv.wait(
                            rem if (wakeable or cancel is None)
                            else min(0.05, rem))
        finally:
            if wakeable:
                cancel.unregister(item.cv)
            obs_xray.activity_end(item.aid)
        if item.error is not None:
            raise item.error
        if item.results is not None:
            return item.results
        # batched path: materialize HERE, on the client thread — the
        # device→host sync for query i happens while the dispatcher is
        # already staging/launching query i+1
        try:
            names, rows = materialize(item.batch, item.out_names)
        except BaseException as e:
            # per-member materialization fault: isolate and re-run this
            # ONE member serially; batch-mates already hold their views
            return self._recover_member(item, e)
        self._cache_result(item, names, rows)
        return [Result("SELECT", names=names, rows=rows,
                       rowcount=len(rows))]

    # -- result cache (exec/share.py rung b) ------------------------------
    def _sharing_on(self, session) -> bool:
        node = getattr(session, "node", None) or self.node
        return workshare.enabled(getattr(node, "gucs", None) or {})

    # snapshot-gate: snap
    # version-gate: vkey
    def _serve_cached(self, item: _Item) -> bool:
        """Serve a batchable SELECT straight from the GTS-versioned
        result cache: servable iff every referenced table still sits
        at the entry's captured store version AND this read's snapshot
        GTS covers the entry's.  A hit completes the item without ever
        queueing it — no admission slot, no device dispatch."""
        if item.info is None or not self._sharing_on(item.session):
            return False
        node = item.session.node
        vkey = item.info.version_key()
        snap = node.gts.next_gts()
        hit = workshare.RESULT_CACHE.lookup(
            item.info.sig, [v for _n, v, _t in item.info.lits],
            vkey, snap)
        if hit is None:
            return False
        names, rows, rowcount = hit
        if snapcheck.enabled() or snapcheck.history_on():
            snapcheck.serve("exec.scheduler.Scheduler._serve_cached",
                            snapshot_gts=snap, versions=vkey,
                            session=id(item.session), source="cache")
        return self._complete(item, results=[Result(
            "SELECT", names=list(names), rows=rows,
            rowcount=rowcount)])

    def _cache_result(self, item: _Item, names, rows):
        """Admit one materialized SELECT result, tagged with the
        snapshot GTS and the store-version tuple captured when that
        snapshot was drawn (so a DML racing the execution makes the
        entry unservable instead of stale)."""
        if item.info is None or item.vkey is None \
                or item.snap is None or item.degraded \
                or not self._sharing_on(item.session):
            return
        node = item.session.node
        gucs = getattr(node, "gucs", None) or {}
        workshare.RESULT_CACHE.put(
            (item.info.sig,
             tuple(v for _n, v, _t in item.info.lits), item.vkey),
            item.snap, names, rows, rowcount=len(rows),
            budget=workshare.cache_budget(gucs))
        if snapcheck.history_on() \
                and item.info.version_key() == item.vkey:
            # the producing execution is itself a primary read at
            # item.snap over the captured version tuple — the SI
            # checker cross-checks cache hits against it.  Not when a
            # DML raced it: versions taken before and a tag drawn
            # after name no snapshot the statement read at (the entry
            # is unservable; the record would be a false stale read)
            snapcheck.note_read(id(item.session), item.snap,
                                "primary", obs=item.vkey)

    # -- completion handshake ---------------------------------------------
    def _complete(self, item: _Item, error=None, results=None,
                  batch=None, out_names=None) -> bool:
        """Deliver a result/error unless the waiter already left.
        Returns False (and delivers nothing) for detached items."""
        with item.lk:
            if item.detached or item.ev.is_set():
                return False
            item.error = error
            if results is not None:
                item.results = results
            if batch is not None:
                item.batch = batch
                item.out_names = out_names
            item.ev.set()
            item.cv.notify_all()    # wake the parked waiter
            return True

    def _detach(self, item: _Item) -> bool:
        """Waiter abandons the item (deadline/cancel).  False when a
        completion already landed — the waiter must consume it."""
        with item.lk:
            if item.ev.is_set():
                return False
            item.detached = True
            return True

    def _expire_if_dead(self, item: _Item) -> bool:
        """Dispatcher-side reap: True when the item is already detached
        or just expired/canceled here.  Queued items die in place — no
        slot was ever acquired for them."""
        with item.lk:
            if item.detached:
                return True
        now = time.monotonic()
        if item.deadline is not None and now >= item.deadline:
            if self._complete(item, error=ExecError(
                    "canceling statement due to statement timeout")):
                _bump("expired")
                obs_xray.flight("statement_timeout", sig=item.sql)
            return True
        cancel = item.cancel_event
        if cancel is not None and cancel.is_set():
            cancel.clear()
            if self._complete(item, error=ExecError(
                    "canceling statement due to user request")):
                _bump("canceled")
            return True
        return False

    def _recover_member(self, item: _Item, exc: BaseException) -> list:
        """A batched member failed at materialization (client thread):
        record the batch failure for quarantine accounting and re-run
        this one member serially, inline.  Batch-mates are unaffected —
        they hold independent views into the stacked output."""
        shield.note_batch_failure(item.sig)
        shield.bump("isolated")
        try:
            self._admit(item.group, time.monotonic() + self.shed_s,
                        item=item)
        except (_Shed, _Gone):
            raise exc
        try:
            return item.session.execute(item.sql)
        finally:
            self._release(item.group)

    # -- classification ---------------------------------------------------
    def _classify(self, item: _Item):
        """Attach the literal-masked fragment signature when the
        statement can ride a coalesced dispatch; otherwise mark the
        serial lane (and whether it needs the write lane)."""
        session, sql = item.session, item.sql
        item.group = getattr(session, "resource_group", "") or "default"
        stmts = parse_sql(sql)
        item.is_write = any(not isinstance(s, (A.SelectStmt, A.ShowStmt,
                                               A.ExplainStmt))
                            for s in stmts)
        node = getattr(session, "node", None)
        if (len(stmts) != 1 or not isinstance(stmts[0], A.SelectStmt)
                or stmts[0].for_update
                or getattr(session, "txn", None) is not None
                or node is None or not hasattr(node, "stores")):
            return
        raw_budget = node.gucs.get("work_mem_rows", "")
        if raw_budget.isdigit() and int(raw_budget) > 0:
            return    # spill tier: serial path owns multi-pass execution
        try:
            planned = session._plan_select(stmts[0])
        except Exception:
            return    # let the serial path surface the planning error
        if planned.init_plans:
            return
        ctx = ExecContext(node.stores, 0, 0, node.cache)
        info = batch_signature(ctx, planned.plan)
        if info is None:
            return
        item.planned = planned
        item.lits = info.lits     # serial lane shares the poison surface
        if shield.quarantined(info.sig):
            return    # repeat offender: barred from coalescing, runs
        item.info = info          # alone on the serial lane (cooldown)

    # -- admission --------------------------------------------------------
    def _cap(self, group: str) -> int:
        node = self.node
        cfg = None
        if node is not None:
            cfg = getattr(node.catalog, "resource_groups", {}).get(group)
        if cfg:
            try:
                return int(cfg.get("concurrency", 0)) or self.slots
            except (TypeError, ValueError):
                pass
        return self.slots

    def _admit(self, group: str, deadline: float,
               item: Optional[_Item] = None):
        """Acquire one GTM slot or shed at the deadline.  Exponential
        backoff mirrors the cluster session's resource-queue wait.

        Slot-discipline contract: `slots_acquired` bumps ONLY on a
        successful acquire, so every exit from this function — _Shed,
        _Gone, or a GTM failure raising mid-acquire — leaves the ledger
        consistent with zero slots held.  Callers must reach _release
        via finally once this returns."""
        delay = 0.0005
        # the sanctioned wrapper: callers pair THIS acquire with
        # _release in their own finally
        while not self.gtm.resq_acquire(  # otblint: disable=slot-discipline
                group, self._cap(group), owner=self._owner,
                lease_s=self.lease_s):
            if item is not None and self._expire_if_dead(item):
                raise _Gone()
            if time.monotonic() >= deadline:
                raise _Shed(
                    f"resource group '{group}' queue wait timeout: "
                    "query shed")
            # park instead of sleep-polling: a local _release notifies
            # immediately; the bounded timeout still catches GTM-side
            # frees this condition can't observe (other owners, lease
            # reaping)
            with obs_xray.wait_event("sched-admission", group=group):
                with self._slot_cv:
                    self._slot_cv.wait(timeout=delay)
            delay = min(delay * 2, 0.05)
        _bump("slots_acquired")

    def _release(self, group: str):
        # ledger counts the scheduler's release INTENT: resq_release is
        # a no-op when GTM already reaped an expired lease (that side is
        # accounted by gtm resq_stats), and a GTM error must not unwind
        # the caller's completion path
        _bump("slots_released")
        try:
            self.gtm.resq_release(group, owner=self._owner)
        except Exception:
            pass
        with self._slot_cv:
            self._slot_cv.notify_all()

    def _shed_item(self, item: _Item, exc: _Shed):
        if not self._complete(item, error=ExecError(str(exc))):
            return    # waiter already gone: don't count a shed
        _bump("shed")
        # the overload arm of the guard's degradation ladder: a shed is
        # "this CN is degraded by load", same surface as "that DN is
        # degraded by failures" (otb_node_health + otb_guard_shed_total)
        from ..net.guard import note_shed
        note_shed(getattr(item, "group", "default") or "default")

    # -- dispatcher -------------------------------------------------------
    def _next(self, timeout: Optional[float]):
        if self._deferred:
            return self._deferred.popleft()
        try:
            # dispatcher idle dequeue, not a query-visible stall
            if timeout is None:
                return self._q.get()  # otblint: disable=wait-discipline
            return self._q.get(timeout=timeout)  # otblint: disable=wait-discipline
        except queue.Empty:
            return None

    def _depth_dec(self, item: _Item):
        with self._lock:
            d = self._depth.get(item.group, 0)
            if d > 0:
                self._depth[item.group] = d - 1

    def _loop(self):
        while True:
            head = self._next(None)
            if head is _STOP:
                self._drain_on_stop()
                return
            batch = [head]
            if head.info is not None and self.max_batch > 1 \
                    and self.window_s > 0:
                # coalescing window: wait a beat for same-signature
                # arrivals; non-matching items defer (FIFO preserved)
                deadline = time.monotonic() + self.window_s
                skipped = []
                while len(batch) < self.max_batch:
                    rem = deadline - time.monotonic()
                    if rem <= 0:
                        break
                    nxt = self._next(rem)
                    if nxt is None:
                        break
                    if nxt is _STOP:
                        self._deferred.appendleft(_STOP)
                        break
                    if nxt.info is not None and nxt.sig == head.sig:
                        batch.append(nxt)
                    else:
                        skipped.append(nxt)
                self._deferred.extend(skipped)
            for it in batch:
                self._depth_dec(it)
            if len(batch) > 1:
                self._dispatch_batch(batch)
            else:
                self._pool.submit(self._run_serial, head)

    def _drain_on_stop(self):
        while True:
            it = self._next(0)
            if it is None:
                return
            if it is _STOP:
                continue
            self._complete(it, error=ExecError("scheduler stopped"))

    # -- execution paths --------------------------------------------------
    def _dispatch_batch(self, items: list):
        """Coalesced dispatch entry: reap dead members, pre-shrink the
        batch to the admission byte estimate, launch each chunk."""
        live = [it for it in items if not self._expire_if_dead(it)]
        if not live:
            return
        if len(live) == 1:
            self._pool.submit(self._run_serial, live[0])
            return
        cap = shield.batch_cap(live[0].session.node, live[0].info,
                               self.max_batch)
        pipelined = self._pipeline_on(live[0].session)
        for i in range(0, len(live), cap):
            chunk = live[i:i + cap]
            if len(chunk) == 1:
                self._pool.submit(self._run_serial, chunk[0])
            elif pipelined:
                self._dispatch_pipelined(chunk)
            else:
                self._dispatch_one(chunk)

    def _dispatch_one(self, items: list, isolating: bool = False):
        group = items[0].group
        deadline = min(it.t_submit for it in items) + self.shed_s
        try:
            self._admit(group, deadline)
        except _Shed as e:
            for it in items:
                self._shed_item(it, e)
            return
        except BaseException as e:
            # admission infrastructure failure (GTM died mid-acquire):
            # nothing is held, fail the members with the ledger intact
            for it in items:
                self._complete(it, error=e)
            return
        out = err = None
        t_start = time.monotonic()
        try:
            node = items[0].session.node
            vkey = items[0].info.version_key()
            queries = []
            for it in items:
                # per-query MVCC: each batch element carries its own
                # snapshot/txid as traced inputs (drawn AFTER admission,
                # matching when serial execution would begin)
                txid = node.gts.next_txid()
                snap = node.gts.next_gts()
                it.snap, it.vkey = snap, vkey
                queries.append(
                    (snap, txid, [v for _n, v, _t in it.info.lits]))
            for attempt in (0, 1):
                try:
                    shield.pre_dispatch(items[0].info, queries)
                    out = run_fused_batch(items[0].info, queries)
                    err = None
                    break
                except BaseException as e:
                    err = e
                    if shield.is_oom(e) and attempt == 0:
                        # memory-pressure ladder, rung 1: evict the
                        # coldest bufferpool entries and retry ONCE
                        shield.bump("oom_dispatches")
                        shield.relieve()
                        continue
                    break
        finally:
            self._release(group)
        if err is not None:
            if shield.is_oom(err):
                # rung 2: relief did not help — hand the members to
                # shield.run_degraded, which tries the morsel chunk
                # stream first (bounded device windows, the ladder's
                # middle rung) and only then leaves the device for the
                # spill tier (an answer instead of an error)
                for it in items:
                    self._pool.submit(self._serve_degraded, it)
                return
            if not isolating:
                shield.note_batch_failure(items[0].sig)
            self._isolate(items)
            return
        if out is None:
            # batched path declined (mask refused / ladder exhausted /
            # program error): serial fallback reproduces per-query
            # results and attributes per-query errors
            for it in items:
                self._pool.submit(self._run_serial, it)
            return
        _note_dispatch(items, t_start)
        for it, b in zip(items, out):
            self._complete(it, batch=b, out_names=it.planned.output_names)

    def _pipeline_on(self, session) -> bool:
        """`enable_pipeline` GUC (env default OTB_SCHED_PIPELINE, on).
        Off falls back to the synchronous dispatch path — bit-identical
        results, no drainer thread."""
        node = getattr(session, "node", None) or self.node
        gucs = getattr(node, "gucs", None) or {}
        v = str(gucs.get("enable_pipeline", "") or "").strip().lower()
        if not v:
            v = os.environ.get("OTB_SCHED_PIPELINE", "on").strip().lower()
        return v not in ("off", "0", "false")

    def _dispatch_pipelined(self, items: list):
        """Two-stage pipeline entry (dispatcher thread only): admit →
        stage → async launch → enqueue the flight for the drainer.  The
        dispatch loop returns without ever touching the device result —
        the finish-phase host sync runs on the drainer, so the loop is
        already staging the NEXT batch while this one computes.

        Slot discipline across the thread boundary: the GTM slot this
        dispatch holds transfers to the _Flight at enqueue; every error
        path BEFORE the enqueue releases it here."""
        group = items[0].group
        deadline = min(it.t_submit for it in items) + self.shed_s
        try:
            # ownership transfer, not a leak: the slot rides the _Flight
            # to the drainer, whose finish path releases in finally;
            # every path between here and the enqueue releases explicitly
            self._admit(group, deadline)  # otblint: disable=slot-discipline
        except _Shed as e:
            for it in items:
                self._shed_item(it, e)
            return
        except BaseException as e:
            for it in items:
                self._complete(it, error=e)
            return
        t_start = time.monotonic()
        flight = sb = None
        for it in items:
            obs_xray.activity_state(it.aid, "staging")
        try:
            node = items[0].session.node
            vkey = items[0].info.version_key()
            queries = []
            for it in items:
                txid = node.gts.next_txid()
                snap = node.gts.next_gts()
                it.snap, it.vkey = snap, vkey
                queries.append(
                    (snap, txid, [v for _n, v, _t in it.info.lits]))
            with self._pipe_lock:
                overlapped = self._inflight > 0
            # same pressure ladder as the synchronous path: one
            # evict-coldest + retry pass covers the fault surface,
            # staging uploads, AND the async launch
            for attempt in (0, 1):
                try:
                    shield.pre_dispatch(items[0].info, queries)
                    if sb is None:
                        t0 = time.perf_counter()
                        sb = stage_fused_batch(items[0].info, queries)
                        _note_stage((time.perf_counter() - t0) * 1e3,
                                    overlapped)
                    if sb is not None:
                        for it in items:
                            obs_xray.activity_state(it.aid, "device")
                        flight = launch_fused_batch(sb)
                    break
                except BaseException as e:
                    if shield.is_oom(e) and attempt == 0:
                        shield.bump("oom_dispatches")
                        shield.relieve()
                        continue
                    raise
        except BaseException as e:
            self._release(group)
            self._flight_error(items, e)
            return
        if flight is None:
            # staging/launch declined (mask refused, program fell back):
            # serial fallback reproduces per-query results
            self._release(group)
            for it in items:
                self._pool.submit(self._run_serial, it)
            return
        self._ensure_drainer()
        with self._pipe_lock:
            self._inflight += 1
        _bump("pipelined_dispatches")
        for it in items:
            obs_xray.activity_state(it.aid, "draining")
        # bounded queue: a slow drainer back-pressures the dispatcher
        # here, capping how much device work can pile up in flight
        with obs_xray.wait_event("sched-drain-queue"):
            self._drainq.put(_Flight(items, flight, sb, group, t_start))

    def _drain_loop(self):
        """Drainer thread: the finish-phase host sync (join-ladder
        read-back — where deferred device errors also surface) for every
        launched flight, then per-item completion.  Deadlines/cancels,
        quarantine bisection, and the slot ledger keep their exact
        semantics: _complete/_isolate re-check liveness per item, and
        the flight's slot releases HERE, in the finally.
        # may-acquire: exec.scheduler._STATS_LOCK
        # may-acquire: exec.shield._LOCK
        # may-acquire: exec.scheduler.Scheduler._pipe_lock
        # may-acquire: exec.scheduler.Scheduler._slot_cv
        """
        while True:
            # drainer idle dequeue, not a query-visible stall
            fl = self._drainq.get()  # otblint: disable=wait-discipline
            if fl is _STOP:
                return
            self._drain_one(fl)

    def _drain_one(self, fl: _Flight):
        out = err = None
        try:
            try:
                out = finish_fused_batch(fl.flight)
            except BaseException as e:
                if shield.is_oom(e):
                    # deferred device OOM surfaced at the sync point:
                    # same rung-1 response as the synchronous path —
                    # evict-coldest, relaunch from the staged batch once
                    shield.bump("oom_dispatches")
                    shield.relieve()
                    try:
                        f2 = launch_fused_batch(fl.sb)
                        out = finish_fused_batch(f2) \
                            if f2 is not None else None
                    except BaseException as e2:
                        err = e2
                else:
                    err = e
        finally:
            with self._pipe_lock:
                self._inflight -= 1
            self._release(fl.group)
            _bump("drained")
        items = fl.items
        if err is not None:
            if shield.is_oom(err):
                for it in items:
                    self._pool.submit(self._serve_degraded, it)
                return
            shield.note_batch_failure(items[0].sig)
            # bisection re-dispatches run SYNCHRONOUSLY on the drainer
            # (never back into _drainq — the drainer must not block on
            # the queue it is the only consumer of)
            self._isolate(items)
            return
        if out is None:
            for it in items:
                self._pool.submit(self._run_serial, it)
            return
        _note_dispatch(items, fl.t_start)
        for it, b in zip(items, out):
            self._complete(it, batch=b, out_names=it.planned.output_names)

    def _flight_error(self, items: list, err: BaseException):
        """Pre-enqueue pipeline failure: mirror the synchronous dispatch
        error ladder (the slot is already released by the caller)."""
        if shield.is_oom(err):
            for it in items:
                self._pool.submit(self._serve_degraded, it)
            return
        shield.note_batch_failure(items[0].sig)
        self._isolate(items)

    def _isolate(self, items: list):
        """Quarantine by bisection: re-dispatch the failed batch in
        halves, so innocents complete batched while the offender bottoms
        out on the serial lane and fails ALONE — per-backend crash
        isolation re-created for a shared device dispatch."""
        live = [it for it in items if not self._expire_if_dead(it)]
        if not live:
            return
        obs_xray.flight("poison_bisect",
                        sig=str(live[0].sig or live[0].sql),
                        members=len(live))
        if len(live) == 1:
            shield.bump("isolated")
            self._pool.submit(self._run_serial, live[0])
            return
        mid = len(live) // 2
        for half in (live[:mid], live[mid:]):
            if len(half) == 1:
                shield.bump("isolated")
                self._pool.submit(self._run_serial, half[0])
            else:
                self._dispatch_one(half, isolating=True)

    def _serve_degraded(self, item: _Item):
        """Brownout lane: serve one member through the morsel stream
        (or, failing that, the spill tier) after dispatch-level memory
        pressure."""
        if self._expire_if_dead(item):
            return
        try:
            self._admit(item.group, time.monotonic() + self.shed_s,
                        item=item)
        except _Gone:
            return
        except _Shed as e:
            self._shed_item(item, e)
            return
        except BaseException as e:
            self._complete(item, error=e)
            return
        try:
            _note_dispatch([item], time.monotonic())
            try:
                res = shield.run_degraded(item)
                item.degraded = True
                self._complete(item, results=res)
            except BaseException as e:
                self._complete(item, error=e)
        finally:
            self._release(item.group)

    def _run_serial(self, item: _Item):
        if self._expire_if_dead(item):
            return    # died queued: no slot was ever acquired
        try:
            self._admit(item.group, item.t_submit + self.shed_s,
                        item=item)
        except _Gone:
            return
        except _Shed as e:
            self._shed_item(item, e)
            return
        except BaseException as e:
            # admission infrastructure failure: no slot held
            self._complete(item, error=e)
            return
        try:
            _note_dispatch([item], time.monotonic())
            # slot held: only NOW is the statement on the device path
            # (marking before _admit would show a slot-starved query
            # as "device" while it is really still queued)
            obs_xray.activity_state(item.aid, "device",
                                    thread=threading.get_ident())
            try:
                shield.serial_guard(item.lits)
                # this thread's spans go under the statement's trace,
                # which the connection thread opened and will finish
                with obs_trace.adopt(item.trace):
                    if item.is_write:
                        with self._write_lock:
                            # may-acquire: storage.store.TableStore._mu
                            # may-acquire: storage.lockmgr.LockManager._cond
                            # may-acquire: obs.metrics.Registry._lock
                            # may-acquire: obs.metrics.metric._lock
                            # may-acquire: obs.trace._LOCK
                            res = item.session.execute(item.sql)
                    else:
                        if item.info is not None:
                            # versions BEFORE execution, GTS tag AFTER:
                            # a DML racing the statement leaves the
                            # entry keyed at a tuple that no longer
                            # matches, and the late tag only narrows
                            # servability
                            item.vkey = item.info.version_key()
                        res = item.session.execute(item.sql)
                        if item.info is not None and len(res) == 1 \
                                and res[0].command == "SELECT":
                            node = item.session.node
                            item.snap = node.gts.next_gts()
                            self._cache_result(item, res[0].names,
                                               res[0].rows)
                self._complete(item, results=res)
            except BaseException as e:
                self._complete(item, error=e)
        finally:
            self._release(item.group)


def serve(node, host: str = "127.0.0.1", port: int = 0,
          users_path: Optional[str] = None, **knobs):
    """One-call serving tier over a LocalNode: starts a CN wire server
    whose per-connection sessions all route through one Scheduler.
    Returns (CnServer, Scheduler) — both started."""
    from ..net.cn_server import CnServer
    from .session import Session
    sched = Scheduler(node=node, **knobs)
    srv = CnServer(lambda: Session(node), users_path=users_path,
                   host=host, port=port, scheduler=sched).start()
    return srv, sched


from ..obs.metrics import REGISTRY as _METRICS  # noqa: E402
_METRICS.register_collector("scheduler", _metrics_samples)
