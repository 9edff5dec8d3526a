"""Multi-process deployment: datanode TCP server + coordinator-side proxy.

Reference analog: the DN backend serving pooled coordinator connections —
plan messages ('p', tcop/postgres.c:7752), parameterized DML, txn control
(gxid/snapshot/prepare/commit msgs, include/pgxc/pgxcnode.h:320-395) —
plus the pooler's persistent connections (poolmgr.c).  One frame protocol
(net/wire.py) carries plan fragments, column batches, and txn control.

RemoteDataNode mirrors DataNode's service surface exactly, so Cluster and
the executors work unchanged against in-process or remote nodes.
"""

from __future__ import annotations

import os
import socket
import socketserver
import threading
from typing import Optional

from ..catalog.catalog import Catalog
from ..catalog.schema import TableDef
from ..gtm.server import GtmClient
from ..obs import xray
from ..parallel.cluster import DataNode
from . import guard
from .wire import recv_msg, send_msg
from ..utils import locks


class DnServer:
    """Hosts one DataNode behind TCP (the DN 'postmaster')."""

    def __init__(self, index: int, datadir: str, catalog_path: str,
                 gtm_addr: Optional[tuple] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self.node = DataNode(index, datadir)
        catalog = Catalog.load(catalog_path) \
            if os.path.exists(catalog_path) else Catalog()
        gtm = GtmClient(*gtm_addr) if gtm_addr else _NullGtm()
        self.node.recover(catalog, gtm)
        self.node.open_wal()
        node = self.node
        lock = locks.Lock("net.dn_server.DnServer.device_lock")   # one DEVICE executor at a time per DN

        # host-side ops run without the executor lock: DML marking, txn
        # resolution, and lock-manager traffic must interleave freely —
        # a session blocked in a row-lock wait must never stop the
        # holder's commit from being processed (the reference gets this
        # from per-backend processes; here it's lock scoping)
        host_ops = {"ping", "insert_raw", "delete_where", "lock_where",
                    "prepare", "commit", "abort", "wrote_in",
                    "row_count", "table_version", "wait_edges",
                    "gdd_kill", "savepoint_mark", "rollback_to_mark",
                    "prepared_txns"}

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                while True:
                    try:
                        msg = recv_msg(self.request)
                    except (ConnectionError, EOFError):
                        return
                    if msg is None:
                        return
                    # inbound trace context (if any) opens a handler
                    # span; every span the executor opens below nests
                    # under it, and the compacted subtree rides the
                    # reply back to the CN
                    sx = xray.server_span(msg, msg.get("op") or "",
                                          node=f"dn{node.index}")
                    try:
                        with sx:
                            if msg.get("op") in host_ops:
                                resp = {"ok": _dispatch(node, msg)}
                            else:
                                with lock:
                                    # device execution compiles through
                                    # the plan cache under this lock; in
                                    # a fresh process the first dispatch
                                    # also IMPORTS executor/plancache
                                    # here, whose module bodies register
                                    # metrics collectors:
                                    # may-acquire: exec.plancache._LOCK
                                    # may-acquire: obs.metrics.Registry._lock
                                    # staging under this lock also
                                    # chooses/validates codec
                                    # descriptors:
                                    # may-acquire: storage.codec._STATE_LOCK
                                    # a fused fragment recalls its
                                    # learned size classes:
                                    # may-acquire: exec.plancache.Ladder._lock
                                    # execution parks at named wait
                                    # points (gts-grant, lockmgr, ...)
                                    # whose enter/exit touch the wait
                                    # register + histograms:
                                    # may-acquire: obs.xray._WLOCK
                                    # may-acquire: obs.metrics.metric._lock
                                    resp = {"ok": _dispatch(node, msg)}
                    except Exception as e:
                        resp = {"error": f"{type(e).__name__}: {e}",
                                "etype": type(e).__name__}
                    sx.attach(resp)
                    send_msg(self.request, resp)

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._server.shutdown()
        self._server.server_close()


class _NullGtm:
    def txn_verdict(self, gid):
        return "unknown"

    def prepared_list(self):
        return {}


def _dispatch(node: DataNode, msg: dict):
    op = msg["op"]
    if op == "ddl_create":
        return node.ddl_create(TableDef.from_json(msg["table"]))
    if op == "ddl_drop":
        return node.ddl_drop(msg["name"])
    if op == "insert_raw":
        return node.insert_raw(msg["table"], msg["coldata"], msg["n"],
                               msg["txid"], msg.get("shardids"))
    if op == "delete_where":
        return node.delete_where(msg["table"], msg["quals"],
                                 msg["snapshot_ts"], msg["txid"])
    if op == "truncate":
        return node.truncate(msg["table"])
    if op == "savepoint_mark":
        return node.savepoint_mark(msg["txid"])
    if op == "rollback_to_mark":
        return node.rollback_to_mark(msg["txid"], msg["keep"])
    if op == "lock_where":
        return node.lock_where(msg["table"], msg["quals"],
                               msg["snapshot_ts"], msg["txid"],
                               msg.get("nowait", False))
    if op == "wait_edges":
        return node.lockmgr.wait_edges()
    if op == "gdd_kill":
        return node.lockmgr.kill(msg["txid"])
    if op == "alter_table":
        return node.alter_table(msg["rec"])
    if op == "exec_plan":
        # snapshot-gate: msg["snapshot_ts"]
        # (the wire carries the CN's transaction snapshot; the DN
        # filters tuple visibility against it)
        return node.exec_plan(msg["plan"], msg["snapshot_ts"],
                              msg["txid"], msg.get("params", {}),
                              msg.get("sources", {}))
    if op == "build_ann_index":
        return node.build_ann_index(msg["table"], msg["col"],
                                    msg.get("lists", 0),
                                    msg.get("metric", "l2"),
                                    msg.get("nprobe", 0))
    if op == "build_btree_index":
        return node.build_btree_index(msg["table"], msg["cols"])
    if op == "analyze_table":
        return node.analyze_table(msg["table"])
    if op == "extract_shards":
        return node.extract_shards(msg["table"], msg["shard_ids"],
                                   msg["txid"])
    if op == "create_barrier":
        return node.create_barrier(msg["name"], msg["gts"])
    if op == "restore_barrier":
        return node.restore_barrier(msg["name"], msg["tables"])
    if op == "build_hnsw_index":
        return node.build_hnsw_index(msg["table"], msg["col"],
                                     msg.get("m", 16),
                                     msg.get("ef_construction", 64),
                                     msg.get("metric", "l2"))
    if op == "prepare":
        return node.prepare(msg["gid"], msg["txid"])
    if op == "commit":
        return node.commit(msg["txid"], msg["ts"])
    if op == "abort":
        return node.abort(msg["txid"])
    if op == "wrote_in":
        return node.wrote_in(msg["txid"])
    if op == "prepared_txns":
        return node.prepared_txns()
    if op == "inflight":
        return node.inflight()
    if op == "checkpoint":
        return node.checkpoint(None)
    if op == "vacuum":
        return node.vacuum(msg.get("table"), msg["cutoff"])
    if op == "row_count":
        st = node.stores.get(msg["table"])
        return st.row_count() if st else 0
    if op == "table_version":
        st = node.stores.get(msg["table"])
        return st.version if st is not None else None
    if op == "stage_table":
        # driver-host mesh staging: ship this DN's live columns (value +
        # MVCC sys + null masks), dictionaries, and version to the mesh
        # owner (reference: the FN receiver pulling producer pages,
        # forwardrecv.c — here one bulk snapshot instead of a stream).
        # Served from the shared buffer pool's version-keyed host
        # snapshot, so an unchanged table never re-concatenates even
        # across coordinators.
        st = node.stores.get(msg["table"])
        if st is None:
            return None
        from ..storage.bufferpool import POOL
        # version-gate: snap
        # (the pool rebuilds the snapshot unless its cached image
        # matches the live store.version; the version ships with the
        # columns so the mesh owner re-keys its own cache on it)
        snap = POOL.host_snapshot(st)
        return {**snap, "null_columns": sorted(snap["null_columns"])}
    if op == "ping":
        return "pong"
    raise ValueError(f"unknown op {op!r}")


class DnConnectionPool:
    """Warm connection pool to ONE datanode, shared by every session on
    the coordinator (reference: the pooler process, poolmgr.c:632 —
    per-node connection slots leased per request and returned warm).

    Leasing a socket per CALL (not per session) is what lets a session
    blocked in a row-lock wait coexist with the lock holder's commit on
    the same node: each RPC rides its own connection, so a long-blocked
    lock_where cannot starve txn-resolution traffic.

    Every entry carries the GENERATION it was opened under; ``retire``
    bumps the generation, so sockets warmed against a DN that has since
    restarted are closed on their way through the pool instead of being
    handed back (a stale socket to a restarted server fails every
    request it carries).  Accounting is exact: leases are tracked per
    socket, release is idempotent, and a non-pool exception between
    send and recv can never strand a slot — so a burst of broken
    sockets can neither leak slots nor deadlock ``acquire`` at
    ``max_conns``."""

    def __init__(self, addr: tuple, max_conns: int = 32,
                 connect_timeout: float = 5.0):
        self.addr = addr
        self.max_conns = max_conns
        self.connect_timeout = connect_timeout
        self._lock = locks.Lock("net.dn_server.DnConnectionPool._lock")
        self._cv = locks.Condition(self._lock)
        self._free: list = []    # guarded_by: _lock -- [(gen, sock)]
        self._leased: dict = {}  # guarded_by: _lock -- sock -> gen
        self._count = 0          # guarded_by: _lock -- open sockets
        self.gen = 0             # guarded_by: _lock -- retirement epoch
        self.leases = 0          # observability: total acquisitions
        self.created = 0         # sockets ever opened (reuse proof)
        self.retired = 0         # stale-generation sockets closed

    def _discard_locked(self, sock):
        self._count -= 1
        try:
            sock.close()
        except OSError:
            pass

    def acquire(self) -> socket.socket:
        with self._cv:
            self.leases += 1
            while True:
                while self._free:
                    g, s = self._free.pop()
                    if g == self.gen:
                        self._leased[s] = g
                        return s
                    # opened before the last retire(): never hand back
                    self.retired += 1
                    self._discard_locked(s)
                if self._count < self.max_conns:
                    self._count += 1
                    g = self.gen
                    break
                with xray.wait_event("pool-conn"):
                    self._cv.wait(1.0)
        try:
            s = socket.create_connection(self.addr,
                                         timeout=self.connect_timeout)
        except OSError:
            with self._cv:
                self._count -= 1
                self._cv.notify()
            raise
        with self._cv:
            self.created += 1
            self._leased[s] = g
            return s

    def release(self, sock: socket.socket, broken: bool = False):
        with self._cv:
            g = self._leased.pop(sock, None)
            if g is None:
                # double release / foreign socket: accounting already
                # settled, never decrement twice
                self._cv.notify()
                return
            if broken or g != self.gen:
                if g != self.gen and not broken:
                    self.retired += 1
                self._discard_locked(sock)
            else:
                self._free.append((g, sock))
            self._cv.notify()

    def retire(self):
        """Start a new generation: every pooled socket (idle now, or
        leased and returned later) is closed instead of reused.  Called
        when an exchange fails at the connection level — the cheapest
        correct response to 'that DN probably restarted'."""
        with self._cv:
            self.gen += 1
            while self._free:
                _, s = self._free.pop()
                self.retired += 1
                self._discard_locked(s)
            self._cv.notify_all()

    def stats(self) -> dict:
        with self._cv:
            return {"open": self._count, "free": len(self._free),
                    "leased": len(self._leased), "gen": self.gen,
                    "leases": self.leases, "created": self.created,
                    "retired": self.retired}

    def close_all(self):
        self.retire()


# ops safe to re-issue after a broken exchange: pure reads, staging,
# and probes.  DML marking and 2PC verbs are NEVER retried here — a
# lost commit/abort is the in-doubt resolver's job, not the RPC layer's
# (a blind re-send could double-apply on a server that processed the
# first copy before the connection died).
IDEMPOTENT_OPS = frozenset({
    "ping", "row_count", "table_version", "exec_plan", "stage_table",
    "wait_edges", "inflight", "wrote_in", "analyze_table",
    "prepared_txns",
})


class RemoteDataNode:
    """Coordinator-side proxy with DataNode's service surface
    (reference: PGXCNodeHandle, pgxcnode.c, riding the pooler's
    per-node connection slots).  All calls flow through net/guard.py:
    per-op deadline, breaker admission, and — for IDEMPOTENT_OPS —
    bounded retry with jittered backoff."""

    def __init__(self, index: int, host: str, port: int):
        self.index = index
        self.addr = (host, port)
        self.pool = DnConnectionPool((host, port))
        # guard state is keyed by ADDRESS so every proxy and probe to
        # one server shares a breaker, while a promoted standby (new
        # port) starts clean
        self.guard_key = f"dn{index}@{host}:{port}"
        # chaos points are keyed by INDEX: tests arm dn1.send without
        # knowing the ephemeral port
        self._fault_send = f"dn{index}.send"
        self._fault_recv = f"dn{index}.recv"

    def _call(self, **msg):
        op = msg.get("op", "")
        return guard.guarded(self.guard_key,
                             lambda: self._call_once(msg),
                             idempotent=op in IDEMPOTENT_OPS, op=op)

    def _call_once(self, msg):
        xray.inject(msg)
        sock = self.pool.acquire()
        broken = True   # assume the worst; cleared on a clean exchange
        try:
            sock.settimeout(guard.rpc_deadline())
            with xray.wait_event("rpc-wire", node=f"dn{self.index}"):
                send_msg(sock, msg, fault=self._fault_send)
                # expect_reply: a close here is a broken conversation,
                # never "no message" (the server owes an answer to
                # every request)
                resp = recv_msg(sock, expect_reply=True,
                                fault=self._fault_recv)
            broken = False
        except (ConnectionError, OSError, EOFError):
            # a connection-level failure usually means the DN died or
            # restarted: retire the generation so warm-but-stale
            # sockets are not handed to the next caller
            self.pool.retire()
            raise
        finally:
            # exactly-once accounting even for non-connection errors
            # (e.g. an unpicklable payload): a desynced socket is never
            # reused, and the slot can never leak
            self.pool.release(sock, broken=broken)
        xray.absorb(resp, node=f"dn{self.index}", op=msg.get("op", ""))
        if "error" in resp:
            et = resp.get("etype", "")
            # concurrency-control errors keep their type across the
            # wire: the CN's retry/NOWAIT logic dispatches on them
            if et == "SerializationConflict":
                from ..storage.store import SerializationConflict
                raise SerializationConflict(resp["error"])
            if et in ("LockTimeout", "DeadlockDetected",
                      "LockNotAvailable"):
                from ..storage import lockmgr as _lm
                raise getattr(_lm, et)(resp["error"])
            raise RuntimeError(f"dn{self.index}: {resp['error']}")
        return resp["ok"]

    def close_locked(self):
        self.pool.close_all()

    def close(self):
        self.pool.close_all()

    # ---- mirrored surface ----
    def ddl_create(self, td):
        return self._call(op="ddl_create", table=td.to_json())

    def ddl_drop(self, name):
        return self._call(op="ddl_drop", name=name)

    def insert_raw(self, table, coldata, n, txid, shardids=None):
        return self._call(op="insert_raw", table=table, coldata=coldata,
                          n=n, txid=txid, shardids=shardids)

    def delete_where(self, table, quals, snapshot_ts, txid):
        return self._call(op="delete_where", table=table, quals=quals,
                          snapshot_ts=snapshot_ts, txid=txid)

    def exec_plan(self, plan, snapshot_ts, txid, params, sources):
        return self._call(op="exec_plan", plan=plan,
                          snapshot_ts=snapshot_ts, txid=txid,
                          params=params, sources=sources)

    def alter_table(self, rec):
        return self._call(op="alter_table", rec=rec)

    def build_ann_index(self, table, col, lists=0, metric="l2", nprobe=0):
        return self._call(op="build_ann_index", table=table, col=col,
                          lists=lists, metric=metric, nprobe=nprobe)

    def build_btree_index(self, table, cols):
        return self._call(op="build_btree_index", table=table, cols=cols)

    def analyze_table(self, table):
        return self._call(op="analyze_table", table=table)

    def extract_shards(self, table, shard_ids, txid):
        return self._call(op="extract_shards", table=table,
                          shard_ids=shard_ids, txid=txid)

    def create_barrier(self, name, gts):
        return self._call(op="create_barrier", name=name, gts=gts)

    def restore_barrier(self, name, tables):
        return self._call(op="restore_barrier", name=name, tables=tables)

    def build_hnsw_index(self, table, col, m=16, ef_construction=64,
                         metric="l2"):
        return self._call(op="build_hnsw_index", table=table, col=col,
                          m=m, ef_construction=ef_construction,
                          metric=metric)

    def prepare(self, gid, txid):
        return self._call(op="prepare", gid=gid, txid=txid)

    def commit(self, txid, ts):
        return self._call(op="commit", txid=txid, ts=ts)

    def abort(self, txid):
        return self._call(op="abort", txid=txid)

    def wrote_in(self, txid):
        return self._call(op="wrote_in", txid=txid)

    def prepared_txns(self):
        return self._call(op="prepared_txns")

    def checkpoint(self, _catalog=None):
        return self._call(op="checkpoint")

    def vacuum(self, table, cutoff):
        return self._call(op="vacuum", table=table, cutoff=cutoff)

    def row_count(self, table):
        return self._call(op="row_count", table=table)

    def table_version(self, table):
        return self._call(op="table_version", table=table)

    def lock_where(self, table, quals, snapshot_ts, txid,
                   nowait=False):
        return self._call(op="lock_where", table=table, quals=quals,
                          snapshot_ts=snapshot_ts, txid=txid,
                          nowait=nowait)

    def wait_edges(self):
        return self._call(op="wait_edges")

    def truncate(self, table):
        return self._call(op="truncate", table=table)

    def inflight(self):
        return self._call(op="inflight")

    def savepoint_mark(self, txid):
        return self._call(op="savepoint_mark", txid=txid)

    def rollback_to_mark(self, txid, keep):
        return self._call(op="rollback_to_mark", txid=txid, keep=keep)

    def gdd_kill(self, txid):
        return self._call(op="gdd_kill", txid=txid)

    def stage_table(self, table):
        return self._call(op="stage_table", table=table)

    def ping(self) -> bool:
        try:
            return self._call(op="ping") == "pong"
        except (ConnectionError, OSError, RuntimeError):
            return False


class StandbyReadNode:
    """Coordinator-side proxy for READ fragments on a hot standby
    (storage/replication.py HotStandby behind a DnStandbyServer).  One
    persistent connection per replica — the router is the only caller
    and serializes per replica anyway (the replica's own apply/read
    lock is the scale-out unit, not connection fan-in)."""

    def __init__(self, host: str, port: int, name: str = ""):
        self.addr = (host, port)
        self.name = name or f"standby@{host}:{port}"
        self._sock = None
        self._lock = locks.Lock("net.dn_server.StandbyReadNode._lock")

    # one conversation per call; the hold is bounded by the socket
    # deadline, exactly the WalShip contract
    def _call(self, msg: dict):  # otblint: disable=lock-blocking
        xray.inject(msg)
        with self._lock:
            try:
                if self._sock is None:
                    self._sock = socket.create_connection(
                        self.addr, timeout=guard.rpc_deadline())
                with xray.wait_event("rpc-wire", node=self.name):
                    send_msg(self._sock, msg)
                    resp = recv_msg(self._sock, expect_reply=True)
            except (ConnectionError, OSError, EOFError):
                try:
                    if self._sock is not None:
                        self._sock.close()
                finally:
                    self._sock = None
                raise
        xray.absorb(resp, node=self.name, op=msg.get("op", ""))
        if "error" in resp:
            et = resp.get("etype", "")
            if et == "StandbyLag":
                from ..storage.replication import StandbyLag
                raise StandbyLag(resp["error"],
                                 hwm=resp.get("hwm", 0))
            # anything else (cold standby AttributeError, unknown op)
            # means this standby cannot serve reads at all
            raise RuntimeError(f"{self.name}: {resp['error']}")
        return resp

    def hwm(self) -> int:
        return int(self._call({"op": "hwm"})["hwm"])

    def exec_plan(self, plan, snapshot_ts, txid, params, sources,
                  min_hwm=0):
        return self._call({"op": "exec_plan", "plan": plan,
                           "snapshot_ts": snapshot_ts, "txid": txid,
                           "params": params, "sources": sources,
                           "min_hwm": min_hwm})["ok"]

    def close(self):
        with self._lock:
            if self._sock is not None:
                self._sock.close()
                self._sock = None
