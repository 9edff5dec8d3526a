"""Single-node engine + session: the "centralized mode" of the reference
(IS_CENTRALIZED_MODE, src/include/pgxc/pgxc.h:111-117 — one node acting as
access node and datanode at once).  The distributed CN/DN split layers on
top of this engine in net/ and parallel/.

A LocalNode owns: catalog, table stores, WAL, a device cache, and a local
timestamp source (stand-in for the GTM; the gtm/ service replaces it in
cluster mode).  Session wraps it with the SQL statement loop
(reference: exec_simple_query, tcop/postgres.c:1370).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Optional

import numpy as np

from ..catalog.catalog import Catalog, CatalogError
from ..catalog.schema import DistType, NodeDef, TableDef
from ..catalog.types import TypeKind
from ..obs import trace as obs_trace
from ..parallel.locator import Locator
from ..plan import physical as P
from ..plan.planner import PlannedStmt, Planner
from ..sql import ast as A
from ..sql.analyze import Binder, split_conjuncts
from ..sql.ddl import sequence_def_from_ast, table_def_from_ast
from ..sql.parser import parse_sql
from ..storage.store import TableStore
from ..storage.wal import Wal, checkpoint_store, restore_store
from .executor import (DBatch, DeviceTableCache, ExecContext, ExecError,
                       Executor, materialize, scalars_from_batch)


@dataclasses.dataclass
class Result:
    """One statement's result."""
    command: str
    names: list[str] = dataclasses.field(default_factory=list)
    rows: list[tuple] = dataclasses.field(default_factory=list)
    rowcount: int = 0
    text: str = ""                      # EXPLAIN etc.


def _text_log_array(v) -> np.ndarray:
    """WAL representation of a TEXT column: must be a string-kind array —
    numeric-looking values (zip codes) logged as ints would be mistaken
    for dictionary codes at recovery."""
    arr = np.asarray(v)
    if arr.dtype.kind in "SU":
        return arr
    return np.asarray([str(x) for x in v])


def replay_alter(catalog, stores: dict, rec: dict) -> None:
    """WAL replay of an ALTER TABLE record (shared by the single-node
    and datanode recovery paths)."""
    table = rec["table"]
    act = rec["action"]
    st = stores.get(table)
    if act == "rename_table":
        if catalog is not None and table in catalog.tables:
            catalog.tables[rec["new_name"]] = catalog.tables.pop(table)
            catalog.tables[rec["new_name"]].name = rec["new_name"]
        if table in stores:
            stores[rec["new_name"]] = stores.pop(table)
        return
    if st is None:
        return
    if act == "add_column":
        from ..catalog import types as T
        from ..catalog.schema import ColumnDef
        name, tname, targs = rec["column"]
        st.alter_add_column(
            ColumnDef(name, T.type_from_name(tname, tuple(targs))))
    elif act == "drop_column":
        st.alter_drop_column(rec["name"])
    elif act == "rename_column":
        st.alter_rename_column(rec["name"], rec["new_name"])


def conform_replay_columns(st, enc: dict, n: int, nulls):
    """An insert WAL record written before an ALTER may lack new
    columns (-> all-NULL fill) or carry dropped ones (-> ignore)."""
    enc = {c: v for c, v in enc.items() if st.td.has_column(c)}
    missing = [c for c in st.td.columns if c.name not in enc]
    if missing:
        nulls = dict(nulls or {})
        for c in missing:
            enc[c.name] = np.zeros((n, *c.type.shape_suffix),
                                   c.type.np_dtype)
            nulls[c.name] = np.ones(n, dtype=bool)
    return enc, (nulls or None)


def copy_rows_to_file(path: str, rows, delim: str) -> int:
    """COPY ... TO: delimiter-separated text, NULL spelled \\N, with
    backslash/delimiter/newline escaping so any value round-trips (the
    reference's text format, commands/copy.c CopyAttributeOutText)."""
    def esc(v):
        if v is None:
            return "\\N"
        s = str(v)
        return (s.replace("\\", "\\\\").replace(delim, "\\" + delim)
                 .replace("\n", "\\n"))

    n = 0
    with open(path, "w") as f:
        for row in rows:
            f.write(delim.join(esc(v) for v in row))
            f.write("\n")
            n += 1
    return n


def copy_to_select(table: str, cols) -> A.SelectStmt:
    """The SELECT a COPY TO reads through (shared by the single-node
    and cluster sessions)."""
    return A.SelectStmt(
        items=[A.SelectItem(A.ColRef((c,))) for c in cols],
        from_=[A.TableRef(table)])


def _in_list(table: str, col: str, keys) -> A.Node:
    """col IN (k1, k2, ...) qual for MERGE's matched-key DML."""
    consts = []
    for k in keys:
        if isinstance(k, bool):
            consts.append(A.Const(k, "bool"))
        elif isinstance(k, (int, np.integer)):
            consts.append(A.Const(int(k), "int"))
        elif isinstance(k, (float, np.floating)):
            consts.append(A.Const(repr(float(k)), "num"))
        else:
            consts.append(A.Const(str(k), "str"))
    return A.InExpr(A.ColRef((table, col)), consts, None, False)


class TxnState:
    def __init__(self, txid: int, snapshot_ts: int):
        self.txid = txid
        self.snapshot_ts = snapshot_ts
        # per-store write sets for commit/abort backfill
        self.insert_spans: list[tuple[TableStore, list]] = []
        self.delete_spans: list[tuple[TableStore, tuple]] = []
        self.lock_spans: list[tuple[TableStore, tuple]] = []
        self.explicit = False
        self.wal_ops = 0          # WAL-visible ops (for subabort keep)
        # name -> (ins_len, del_len, lock_len, wal_ops), insert-ordered
        self.savepoints: dict[str, tuple] = {}


class LocalGts:
    """Monotonic local timestamp source — the in-process stand-in for the
    GTM (reference: GetGlobalTimestampGTM, access/transam/gtm.c:1962).
    Cluster mode swaps in gtm/client.py with the same interface."""

    def __init__(self, start: int = 100):
        # the serving tier (exec/scheduler.py) draws snapshots from
        # concurrent dispatch threads; unlocked += would drop grants
        self._lock = threading.Lock()
        self._ts = start
        self._txid = 1

    def next_gts(self) -> int:
        with self._lock:
            self._ts += 1
            return self._ts

    def next_txid(self) -> int:
        with self._lock:
            self._txid += 1
            return self._txid


class LocalNode:
    def __init__(self, datadir: Optional[str] = None, node_name: str = "dn0"):
        self.catalog = Catalog()
        self.catalog.register_node(NodeDef(node_name, "datanode", index=0))
        self.catalog.build_default_shard_map(1)
        self.stores: dict[str, TableStore] = {}
        self.active_txns: set[int] = set()
        self.gts = LocalGts()
        from ..storage.lockmgr import LockManager
        self.lockmgr = LockManager()
        self.lock_timeout = 10.0
        self.cache = DeviceTableCache()
        self.datadir = datadir
        self.wal: Optional[Wal] = None
        self.gucs: dict[str, str] = {
            "enable_fast_query_shipping": "on",
            "enable_datanode_push": "on",
        }
        if datadir:
            os.makedirs(datadir, exist_ok=True)
            self._recover()
            self.wal = Wal(os.path.join(datadir, "wal.log"))

    # ---- persistence ----
    def _recover(self):
        # clock state first: recovered rows carry commit GTS that must be
        # in this node's past (reference: pg_control checkpoint record +
        # GTM's persistent store gtm_store.c)
        metapath = os.path.join(self.datadir, "meta.json")
        if os.path.exists(metapath):
            import json
            with open(metapath) as f:
                meta = json.load(f)
            self.gts._ts = max(self.gts._ts, meta["gts"])
            self.gts._txid = max(self.gts._txid, meta["txid"])
        catpath = os.path.join(self.datadir, "catalog.json")
        if os.path.exists(catpath):
            self.catalog = Catalog.load(catpath)
            for name, td in self.catalog.tables.items():
                st = TableStore(td)
                ckpt = os.path.join(self.datadir, f"{name}.ckpt")
                if os.path.exists(ckpt):
                    restore_store(st, ckpt)
                    # checkpoint older than ALTER ADD COLUMN: reconcile
                    for c in td.columns:
                        st.alter_add_column(c)
                self.stores[name] = st
        walpath = os.path.join(self.datadir, "wal.log")
        replayed: dict[int, list] = {}
        for rec in Wal.replay(walpath):
            self._replay_record(rec, replayed)

    def _replay_record(self, rec: dict, pending: dict):
        op = rec.get("op")
        # never reuse any txid seen in the log: a crashed (uncommitted) txn's
        # rows would become visible to a new txn that drew the same id
        if "txid" in rec:
            self.gts._txid = max(self.gts._txid, rec["txid"])
        if op == "create_table":
            td = TableDef.from_json(rec["table"])
            if td.name not in self.catalog.tables:
                self.catalog.create_table(td)
            self.stores.setdefault(td.name, TableStore(td))
        elif op == "drop_table":
            self.catalog.drop_table(rec["name"], if_exists=True)
            self.stores.pop(rec["name"], None)
            self.catalog.partitioned.pop(rec["name"], None)
            for pi in self.catalog.partitioned.values():
                pi["parts"] = [p for p in pi["parts"]
                               if p["name"] != rec["name"]]
        elif op == "insert":
            st = self.stores[rec["table"]]
            enc = {}
            for cname, v in rec["columns"].items():
                if not st.td.has_column(cname):
                    continue      # column dropped after this record
                arr = np.asarray(v)
                if arr.dtype.kind == "S":
                    enc[cname] = st.encode_column(cname, arr)
                elif arr.dtype.kind in "UO":
                    # TEXT columns are logged as raw strings (dictionary
                    # codes are not stable across restarts)
                    enc[cname] = st.encode_column(cname, list(arr))
                else:
                    # all other columns were logged in storage
                    # representation — re-encoding would double-scale
                    # decimals
                    if st.td.has_column(cname):
                        enc[cname] = arr.astype(
                            st.td.column(cname).type.np_dtype)
            enc, nulls = conform_replay_columns(st, enc, rec["n"],
                                                rec.get("nulls"))
            spans = st.insert(enc, rec["n"], rec["txid"], nulls=nulls)
            pending.setdefault(rec["txid"], []).append(("ins", st, spans))
        elif op == "delete":
            st = self.stores[rec["table"]]
            span = st.mark_delete(rec["chunk"],
                                  np.asarray(rec["mask"]), rec["txid"])
            pending.setdefault(rec["txid"], []).append(("del", st, span))
        elif op == "commit":
            ts = np.int64(rec["ts"])
            for kind, st, sp in pending.pop(rec["txid"], []):
                if kind == "ins":
                    st.backfill_insert(sp, ts)
                else:
                    st.backfill_delete([sp], ts)
            self.gts._ts = max(self.gts._ts, int(rec["ts"]))
            self.gts._txid = max(self.gts._txid, rec["txid"])
        elif op == "abort":
            for kind, st, sp in pending.pop(rec["txid"], []):
                if kind == "ins":
                    st.abort_insert(sp)
                else:
                    st.revert_delete([sp])
        elif op == "partition_parent":
            self.catalog.partitioned[rec["table"]] = {
                "method": rec["method"], "key": rec["key"], "parts": []}
        elif op == "create_partition":
            self.catalog.partitioned[rec["parent"]]["parts"].append(
                rec["rec"])
        elif op == "create_view":
            self.catalog.views[rec["name"]] = rec["text"]
        elif op == "trigger_ddl":
            self.catalog.functions = dict(rec["functions"])
            self.catalog.triggers = dict(rec["triggers"])
        elif op == "security_ddl":
            self.catalog.masks = dict(rec["masks"])
            self.catalog.fga_policies = dict(rec["fga"])
        elif op == "drop_view":
            self.catalog.views.pop(rec["name"], None)
        elif op == "alter_table":
            replay_alter(self.catalog, self.stores, rec)
        elif op == "truncate":
            st = self.stores.get(rec["table"])
            if st is not None:
                st.truncate()
        elif op == "create_node_group":
            if rec["name"] not in self.catalog.node_groups:
                self.catalog.create_node_group(rec["name"],
                                               rec["members"])
        elif op == "subabort":
            # ROLLBACK TO SAVEPOINT: revert this txn's ops beyond the
            # savepoint's WAL position (reference: subxact abort
            # records, xact.c)
            lst = pending.get(rec["txid"], [])
            undo = lst[rec["keep"]:]
            del lst[rec["keep"]:]
            for kind, st, sp in undo:
                if kind == "ins":
                    st.abort_insert(sp)
                else:
                    st.revert_delete([sp])

    def checkpoint(self) -> bool:
        if not self.datadir:
            return False
        if self.active_txns:
            # truncating the WAL would orphan in-flight txns' records: a
            # later COMMIT would replay against nothing (the reference's
            # checkpointer coordinates with open xacts via the proc array)
            return False
        import json
        self.catalog.save(os.path.join(self.datadir, "catalog.json"))
        for name, st in self.stores.items():
            checkpoint_store(st, os.path.join(self.datadir, f"{name}.ckpt"))
        tmp = os.path.join(self.datadir, "meta.json.tmp")
        with open(tmp, "w") as f:
            json.dump({"gts": self.gts._ts, "txid": self.gts._txid}, f)
        os.replace(tmp, os.path.join(self.datadir, "meta.json"))
        if self.wal:
            self.wal.truncate()
        return True

    def _log(self, rec: dict, sync: bool = False):
        if self.wal:
            self.wal.append(rec, sync=sync)

    def serve(self, host: str = "127.0.0.1", port: int = 0,
              users_path: Optional[str] = None, **knobs):
        """Thin serving-tier facade: start a CN wire server whose
        connections each get a Session over this node, with every
        statement routed through the admission/batching scheduler
        (exec/scheduler.py).  Returns (server, scheduler)."""
        from .scheduler import serve
        return serve(self, host=host, port=port,
                     users_path=users_path, **knobs)


def _trace_explain_lines() -> str:
    """EXPLAIN ANALYZE footer from the open query trace: staging,
    program-cache, buffer-pool and exchange activity of the inner run
    (empty when OTB_TRACE=0 — the per-node actuals don't need it)."""
    qt = obs_trace.current_trace()
    if qt is None:
        return ""
    lines = [
        f"Stage: {qt.phase_ms('stage'):.2f} ms "
        f"({int(qt.sum_attr('upload', 'bytes'))} bytes uploaded)",
        f"Programs: hits={qt.count_events('program', hit=True)} "
        f"compiles={qt.count_events('compile')} "
        f"compile_ms={qt.sum_attr('compile', 'ms'):.1f}",
        f"Buffer Pool: hits={qt.count_events('pool', hit=True)} "
        f"misses={qt.count_events('pool', hit=False)}",
    ]
    if qt.count_events("bind"):
        # the statement's WHERE literals: lifted ones ride as program
        # inputs, the rest stay in the plan and program keys (an
        # instrumented run lifts none: its plan keeps every literal)
        lines.append(
            f"Bind: {qt.phase_ms('bind'):.2f} ms "
            f"traced={int(qt.sum_attr('bind', 'traced'))} "
            f"baked={int(qt.sum_attr('bind', 'baked'))} "
            f"dict_miss={int(qt.sum_attr('bind', 'dict_miss'))} "
            f"retraces={int(qt.sum_attr('execute', 'retraces'))}")
    summary = qt.summary()
    # host<->device round trips of the inner run, counted where made
    lines.append("Transfers: " + " ".join(
        f"{k}={summary[k]}" for k in ("host_syncs", "d2h_bytes",
                                      "h2d_puts", "h2d_bytes",
                                      "program_calls")))
    lines.append("Shape: " + " ".join(
        f"{k}={summary[k]}" for k in (
            "semi_joins", "sorted_aggs", "sorted_agg_lanes",
            "sorted_agg_groups", "initplans", "anti_joins", "outer_joins",
            "residual_semi_lanes", "strpred_codes", "final_aggs",
            "final_agg_lanes", "exchange_src_lanes", "pack_lanes")))
    rounds = int(qt.sum_attr("exchange", "rounds"))
    if rounds:
        lines.append(
            f"Exchanges: rounds={rounds} "
            f"bytes={int(qt.sum_attr('exchange', 'bytes'))} "
            f"time={qt.phase_ms('exchange'):.2f} ms")
    # cluster tier over TCP: per-DN phase timings from the span
    # subtrees each server piggy-backed on its replies — real remote
    # stage/execute time, not the CN-observed RPC wall total
    from ..obs import xray as obs_xray
    for node, a in obs_xray.remote_rows(qt):
        parts = [f"rpcs={a.get('rpcs', 0)}",
                 f"server={a.get('server_ms', 0.0):.2f} ms"]
        for ph in obs_trace.PHASES:
            if a.get(ph):
                parts.append(f"{ph}={a[ph]:.2f} ms")
        lines.append(f"Remote {node}: " + " ".join(parts))
    return "".join("\n" + ln for ln in lines)


class Session:
    def __init__(self, node: LocalNode):
        self.node = node
        self.txn: Optional[TxnState] = None
        self.txn_aborted = False
        # out-of-band cancel (CnServer wires the cancel-protocol peer to
        # this; the scheduler propagates it into queued/batched items)
        self.cancel_event = threading.Event()

    # ------------------------------------------------------------------
    def _check_interrupts(self, deadline: Optional[float]):
        """Statement-boundary interrupt poll (CHECK_FOR_INTERRUPTS):
        consume a pending cancel, enforce the statement deadline."""
        if self.cancel_event.is_set():
            self.cancel_event.clear()
            raise ExecError("canceling statement due to user request")
        if deadline is not None and time.monotonic() >= deadline:
            from ..obs import xray as obs_xray
            obs_xray.flight("statement_timeout")
            raise ExecError(
                "canceling statement due to statement timeout")

    def _stmt_deadline(self) -> Optional[float]:
        """Absolute deadline from the statement_timeout GUC (PG
        semantics: milliseconds, 0/unset disabled)."""
        raw = str(self.node.gucs.get("statement_timeout", "")
                  or "").strip()
        if not raw:
            return None
        try:
            ms = float(raw)
        except ValueError:
            return None
        return time.monotonic() + ms / 1e3 if ms > 0 else None

    def execute(self, sql: str) -> list[Result]:
        """Parse and run one message's SQL under ONE statement trace
        (the CN server's when the message came over the wire, else it
        opens here), so that the parse is inside it."""
        out = []
        self._cur_sql = sql.strip()
        deadline = self._stmt_deadline()
        with obs_trace.trace_query(self._cur_sql[:200]):
            with obs_trace.span("parse"):
                stmts = parse_sql(sql)
            for s in stmts:
                self._check_interrupts(deadline)
                if self.txn is not None and self.txn_aborted \
                        and not isinstance(s, A.TxnStmt) \
                        and not (isinstance(s, A.SavepointStmt)
                                 and s.op == "rollback_to"):
                    raise ExecError(
                        "current transaction is aborted, commands ignored "
                        "until end of transaction block")
                try:
                    out.append(self._exec_retryable(s))
                except Exception:
                    if self.txn is not None and not self.txn_aborted \
                            and not isinstance(s, A.TxnStmt):
                        self.txn_aborted = True
                        if not self.txn.savepoints:
                            # abort NOW: writes revert and locks release
                            # immediately (PG: AbortCurrentTransaction).
                            # With live savepoints the txn must survive
                            # for ROLLBACK TO, so only poison it.
                            self._abort(self.txn)
                            self.txn.rolled_back = True
                    raise
        return out

    def _exec_retryable(self, s: A.Node) -> Result:
        """Implicit (single-statement) transactions retry with a FRESH
        snapshot when a concurrent writer committed first — the
        READ COMMITTED re-check; explicit transactions surface the
        serialization error (REPEATABLE READ semantics, PG's 'could
        not serialize access due to concurrent update')."""
        from ..storage.store import SerializationConflict
        sig = getattr(self, "_cur_sql", "") or type(s).__name__
        with obs_trace.trace_query(sig[:200]) as qt:
            if qt is not None:
                self._last_trace = qt
            for _attempt in range(100):
                try:
                    return self._exec_stmt(s)
                except SerializationConflict as e:
                    if self.txn is not None:
                        raise ExecError(str(e)) from None
                    continue
            raise ExecError(
                "could not serialize access due to concurrent update "
                "(retries exhausted)")

    def query(self, sql: str) -> list[tuple]:
        """Convenience: single SELECT -> rows."""
        res = self.execute(sql)
        return res[-1].rows

    def last_query_stats(self) -> dict:
        """Trace-backed per-phase breakdown of the most recent
        statement on this session (plan/stage/execute/finalize ms,
        rows, bytes, pool hit counts).  Empty when OTB_TRACE=0."""
        qt = getattr(self, "_last_trace", None)
        return qt.summary() if qt is not None else {}

    # ------------------------------------------------------------------
    def _begin_implicit(self) -> tuple[TxnState, bool]:
        if self.txn is not None:
            return self.txn, False
        t = TxnState(self.node.gts.next_txid(), self.node.gts.next_gts())
        return t, True

    def _track_write(self, t: TxnState):
        """Register a txn as having in-flight WAL records (blocks
        checkpoint truncation until commit/abort)."""
        self.node.active_txns.add(t.txid)

    def _commit(self, t: TxnState):
        ts = np.int64(self.node.gts.next_gts())
        self.node._log({"op": "commit", "txid": t.txid, "ts": int(ts)},
                       sync=True)
        for st, spans in t.insert_spans:
            st.backfill_insert(spans, ts)
        for st, span in t.delete_spans:
            st.backfill_delete([span], ts)
        for st, span in t.lock_spans:
            st.clear_locks([span])
        from ..utils import snapcheck
        if snapcheck.history_on() and (t.insert_spans or t.delete_spans):
            # SI history: post-backfill store versions tagged with the
            # commit GTS — the write half analysis/sicheck.py orders by
            snapcheck.note_write(
                t.txid, int(ts),
                {st.td.name: st.version
                 for st, _sp in (t.insert_spans + t.delete_spans)})
        self.node.active_txns.discard(t.txid)
        self.node.lockmgr.resolve(t.txid, committed=True)

    def _abort(self, t: TxnState):
        self.node._log({"op": "abort", "txid": t.txid})
        for st, spans in t.insert_spans:
            st.abort_insert(spans)
        for st, span in t.delete_spans:
            st.revert_delete([span])
        for st, span in t.lock_spans:
            st.clear_locks([span])
        self.node.active_txns.discard(t.txid)
        self.node.lockmgr.resolve(t.txid, committed=False)

    # ------------------------------------------------------------------
    def _fire_triggers(self, t, implicit: bool, table: str,
                       timing: str, event: str, rows_new, rows_old,
                       colnames):
        """Fire row triggers inside txn `t` (installed as the session
        txn for the duration so body statements join it — a trigger
        failure aborts the whole DML statement)."""
        from .triggers import fire
        installed = False
        if implicit and self.txn is None:
            self.txn = t
            installed = True
        try:
            fire(self, self.node.catalog, table, timing, event,
                 rows_new, rows_old, colnames)
        finally:
            if installed:
                self.txn = None

    def _exec_stmt(self, stmt: A.Node) -> Result:
        from .security import _SECURITY_DDL
        from .security import ddl as security_ddl
        if isinstance(stmt, _SECURITY_DDL):
            self.node.ddl_gen = getattr(self.node, "ddl_gen", 0) + 1
            tag = security_ddl(self.node.catalog, stmt)
            self.node._log({"op": "security_ddl",
                            "masks": self.node.catalog.masks,
                            "fga": self.node.catalog.fga_policies},
                           sync=True)
            return Result(tag)
        from .triggers import _TRIGGER_DDL
        from .triggers import ddl as trigger_ddl
        if isinstance(stmt, _TRIGGER_DDL):
            self.node.ddl_gen = getattr(self.node, "ddl_gen", 0) + 1
            tag = trigger_ddl(self.node.catalog, stmt)
            self.node._log({"op": "trigger_ddl",
                            "functions": self.node.catalog.functions,
                            "triggers": self.node.catalog.triggers},
                           sync=True)
            return Result(tag)
        if isinstance(stmt, (A.CreateTableStmt, A.DropTableStmt,
                             A.AlterTableStmt, A.CreateViewStmt,
                             A.DropViewStmt, A.CreatePartitionStmt,
                             A.CreateIndexStmt, A.DropIndexStmt,
                             A.AnalyzeStmt)):
            # any schema/stats change invalidates cached plans
            self.node.ddl_gen = getattr(self.node, "ddl_gen", 0) + 1
        if isinstance(stmt, (A.SelectStmt, A.InsertStmt, A.ExplainStmt)):
            from .recursive import expand_in_stmt
            stmt2, cleanup = expand_in_stmt(self, stmt)
            if stmt2 is not stmt:
                try:
                    return self._exec_stmt(stmt2)
                finally:
                    cleanup()
        if isinstance(stmt, A.SelectStmt):
            return self._exec_select(stmt)
        if isinstance(stmt, A.CreateTableStmt):
            td = table_def_from_ast(stmt)
            if stmt.partition_by and not any(
                    c.name == stmt.partition_by[1] for c in td.columns):
                raise ExecError(f"partition key "
                                f"{stmt.partition_by[1]!r} not in table")
            self.node.catalog.create_table(td, stmt.if_not_exists)
            self.node.stores.setdefault(td.name, TableStore(td))
            self.node._log({"op": "create_table", "table": td.to_json()},
                           sync=True)
            if stmt.partition_by:
                from ..parallel.partition import (PartitionError,
                                                  register_parent)
                try:
                    register_parent(self.node.catalog, stmt)
                except PartitionError as e:
                    raise ExecError(str(e)) from None
                self.node._log({"op": "partition_parent",
                                "table": td.name,
                                "method": stmt.partition_by[0],
                                "key": stmt.partition_by[1]}, sync=True)
            return Result("CREATE TABLE")
        if isinstance(stmt, A.CreatePartitionStmt):
            from ..parallel.partition import (PartitionError,
                                              child_tabledef,
                                              partition_bounds)
            try:
                ptd, rec = partition_bounds(self.node.catalog, stmt)
            except PartitionError as e:
                raise ExecError(str(e)) from None
            child = child_tabledef(ptd, stmt.name)
            self.node.catalog.create_table(child)
            self.node.stores[child.name] = TableStore(child)
            self.node._log({"op": "create_table",
                            "table": child.to_json()}, sync=True)
            self.node.catalog.partitioned[stmt.parent]["parts"].append(
                rec)
            self.node._log({"op": "create_partition",
                            "parent": stmt.parent, "rec": rec},
                           sync=True)
            return Result("CREATE TABLE")
        if isinstance(stmt, A.DropTableStmt):
            if stmt.name in self.node.catalog.tables:
                from .constraints import drop_guards
                drop_guards(self.node.catalog, stmt.name)
            pinfo = self.node.catalog.partitioned.get(stmt.name)
            if pinfo is not None:
                for p in list(pinfo["parts"]):
                    self._exec_stmt(A.DropTableStmt(p["name"], True))
                del self.node.catalog.partitioned[stmt.name]
            else:
                for parent, pi in self.node.catalog.partitioned.items():
                    pi["parts"] = [p for p in pi["parts"]
                                   if p["name"] != stmt.name]
            self.node.catalog.drop_table(stmt.name, stmt.if_exists)
            st = self.node.stores.pop(stmt.name, None)
            if st is not None:
                self.node.cache.invalidate(st)
            self.node._log({"op": "drop_table", "name": stmt.name},
                           sync=True)
            return Result("DROP TABLE")
        if isinstance(stmt, A.CreateSequenceStmt):
            self.node.catalog.create_sequence(sequence_def_from_ast(stmt))
            return Result("CREATE SEQUENCE")
        if isinstance(stmt, A.CreateIndexStmt):
            if stmt.method == "ivfflat":
                try:
                    self.node.stores[stmt.table].build_ann_index(
                        stmt.columns[0],
                        int(stmt.options.get("lists", 0)),
                        str(stmt.options.get("metric", "l2")))
                except ValueError as e:
                    raise ExecError(str(e)) from None
            elif stmt.method == "hnsw":
                try:
                    self.node.stores[stmt.table].build_hnsw_index(
                        stmt.columns[0],
                        int(stmt.options.get("m", 16)),
                        int(stmt.options.get("ef_construction", 64)),
                        str(stmt.options.get("metric", "l2")))
                except ValueError as e:
                    raise ExecError(str(e)) from None
            else:  # btree (the default access method)
                try:
                    for col in stmt.columns:
                        self.node.stores[stmt.table].build_btree_index(col)
                except (ValueError, KeyError) as e:
                    raise ExecError(str(e)) from None
                self.node.catalog.btree_cols.setdefault(
                    stmt.table, set()).update(stmt.columns)
            return Result("CREATE INDEX")
        if isinstance(stmt, A.CreateViewStmt):
            try:
                self.node.catalog.create_view(stmt.name, stmt.text,
                                              stmt.or_replace)
            except CatalogError as e:
                raise ExecError(str(e)) from None
            self.node._log({"op": "create_view", "name": stmt.name,
                            "text": stmt.text}, sync=True)
            return Result("CREATE VIEW")
        if isinstance(stmt, A.DropViewStmt):
            try:
                self.node.catalog.drop_view(stmt.name, stmt.if_exists)
            except CatalogError as e:
                raise ExecError(str(e)) from None
            self.node._log({"op": "drop_view", "name": stmt.name}, sync=True)
            return Result("DROP VIEW")
        if isinstance(stmt, A.AlterTableStmt):
            return self._exec_alter(stmt)
        if isinstance(stmt, A.InsertStmt):
            return self._exec_insert(stmt)
        if isinstance(stmt, A.DeleteStmt):
            return self._exec_delete(stmt)
        if isinstance(stmt, A.UpdateStmt):
            return self._exec_update(stmt)
        if isinstance(stmt, A.CopyStmt):
            return self._exec_copy(stmt)
        if isinstance(stmt, A.TxnStmt):
            return self._exec_txn(stmt)
        if isinstance(stmt, A.ExplainStmt):
            return self._exec_explain(stmt)
        if isinstance(stmt, A.SetStmt):
            self.node.gucs[stmt.name] = str(stmt.value)
            return Result("SET")
        if isinstance(stmt, A.ShowStmt):
            v = self.node.gucs.get(stmt.name, "")
            return Result("SHOW", names=[stmt.name], rows=[(v,)])
        if isinstance(stmt, A.VacuumStmt):
            self.node.checkpoint()
            return Result("VACUUM")
        if isinstance(stmt, A.AnalyzeStmt):
            from ..parallel.statistics import analyze_store
            names = [stmt.table] if stmt.table else \
                list(self.node.stores)
            for name in names:
                st = self.node.stores.get(name)
                if st is None:
                    raise ExecError(f"table {name!r} does not exist")
                self.node.catalog.stats[name] = analyze_store(st)
            return Result("ANALYZE")
        if isinstance(stmt, A.BarrierStmt):
            self.node.checkpoint()
            return Result("BARRIER")
        if isinstance(stmt, A.CreateNodeGroupStmt):
            name_to_idx = {nd.name: nd.index
                           for nd in self.node.catalog.datanodes()}
            members = []
            for m in stmt.members:
                if m not in name_to_idx:
                    raise ExecError(f"unknown datanode {m!r}")
                members.append(name_to_idx[m])
            try:
                self.node.catalog.create_node_group(stmt.name, members)
            except CatalogError as e:
                raise ExecError(str(e)) from None
            # WAL-logged: recovery must rebuild the group BEFORE
            # replaying dependent CREATE TABLE records (the catalog
            # validates TO GROUP at create time)
            self.node._log({"op": "create_node_group",
                            "name": stmt.name, "members": members},
                           sync=True)
            return Result("CREATE NODE GROUP")
        if isinstance(stmt, A.TruncateStmt):
            return self._exec_truncate(stmt)
        if isinstance(stmt, A.SavepointStmt):
            return self._exec_savepoint(stmt)
        if isinstance(stmt, A.MergeStmt):
            return self._exec_merge(stmt)
        raise ExecError(f"unsupported statement {type(stmt).__name__}")

    # ---- TRUNCATE (reference: ExecuteTruncate, commands/tablecmds.c:
    # non-MVCC relfilenode swap; like PG, refused when the table is
    # referenced by a foreign key) ----
    def _exec_truncate(self, stmt: A.TruncateStmt) -> Result:
        cat = self.node.catalog
        cat.table(stmt.table)                     # existence check
        if self.txn is not None:
            raise ExecError("TRUNCATE cannot run inside a transaction "
                            "block (non-MVCC bulk clear)")
        from .constraints import drop_guards
        drop_guards(cat, stmt.table, action="truncate")
        if self.node.active_txns:
            raise ExecError(
                "cannot truncate: in-flight transactions hold row "
                "spans")
        names = [stmt.table]
        if stmt.table in cat.partitioned:
            names += [p["name"]
                      for p in cat.partitioned[stmt.table]["parts"]]
        for nm in names:
            st = self.node.stores[nm]
            st.truncate()
            self.node.cache.invalidate(st)
            self.node._log({"op": "truncate", "table": nm}, sync=True)
        return Result("TRUNCATE TABLE")

    # ---- SAVEPOINT / ROLLBACK TO / RELEASE (reference: subxact
    # machinery, access/transam/xact.c DefineSavepoint /
    # RollbackToSavepoint) ----
    def _exec_savepoint(self, stmt: A.SavepointStmt) -> Result:
        t = self.txn
        if t is None or not t.explicit:
            raise ExecError(f"{stmt.op.replace('_', ' ').upper()} can "
                            "only be used in transaction blocks")
        if stmt.op == "savepoint":
            t.savepoints[stmt.name] = (len(t.insert_spans),
                                       len(t.delete_spans),
                                       len(t.lock_spans), t.wal_ops)
            return Result("SAVEPOINT")
        if stmt.name not in t.savepoints:
            raise ExecError(f"savepoint {stmt.name!r} does not exist")
        if stmt.op == "release":
            # drop the named savepoint and everything after it
            drop = False
            for nm in list(t.savepoints):
                if nm == stmt.name:
                    drop = True
                if drop:
                    del t.savepoints[nm]
            return Result("RELEASE")
        mi, md, ml, keep_wal = t.savepoints[stmt.name]
        for st, spans in t.insert_spans[mi:]:
            st.abort_insert(spans)
        del t.insert_spans[mi:]
        for st, span in t.delete_spans[md:]:
            st.revert_delete([span])
        del t.delete_spans[md:]
        for st, span in t.lock_spans[ml:]:
            st.clear_locks([span])
        del t.lock_spans[ml:]
        self.node._log({"op": "subabort", "txid": t.txid,
                        "keep": keep_wal})
        t.wal_ops = keep_wal
        drop = False
        for nm in list(t.savepoints):
            if drop:
                del t.savepoints[nm]
            if nm == stmt.name:
                drop = True
        # ROLLBACK TO recovers a failed transaction (PG semantics)
        self.txn_aborted = False
        return Result("ROLLBACK")

    # ---- MERGE (reference: executor/execMerge.c ExecMerge) ----
    def _merge_parts(self, stmt: A.MergeStmt):
        """Decompose MERGE set-wise.  ON must be one equality between
        a target and a source column; each WHEN branch becomes one
        engine query + one DML (columnar, not per-row)."""
        cat = (self.node.catalog if hasattr(self, "node")
               else self.cluster.catalog)
        tgt = cat.table(stmt.target)
        cat.table(stmt.source)
        on = stmt.on
        if not (isinstance(on, A.BinOp) and on.op == "="
                and isinstance(on.left, A.ColRef)
                and isinstance(on.right, A.ColRef)):
            raise ExecError("MERGE ON must be a single equality "
                            "tgt.col = src.col")
        sides = {}
        for e in (on.left, on.right):
            if len(e.parts) != 2:
                raise ExecError("MERGE ON columns must be qualified")
            sides[e.parts[0]] = e.parts[1]
        if set(sides) != {stmt.target, stmt.source}:
            raise ExecError("MERGE ON must join target to source")
        return tgt, sides[stmt.target], sides[stmt.source]

    def _exec_merge(self, stmt: A.MergeStmt) -> Result:
        tgt, tkey, skey = self._merge_parts(stmt)
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        total = 0
        try:
            total = self._merge_steps(stmt, tgt, tkey, skey)
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        return Result("MERGE", rowcount=total)

    def _merge_steps(self, stmt: A.MergeStmt, tgt, tkey: str,
                     skey: str) -> int:
        total = 0
        join = A.JoinRef("inner", A.TableRef(stmt.target),
                         A.TableRef(stmt.source), stmt.on)
        if stmt.matched_set is not None:
            assigned = {c: e for c, e in stmt.matched_set}
            if tkey in assigned:
                raise ExecError("MERGE may not update the join key")
            items = [A.SelectItem(
                assigned.get(c.name, A.ColRef((stmt.target, c.name))),
                alias=c.name) for c in tgt.columns]
            rows = self._exec_stmt(
                A.SelectStmt(items=items, from_=[join])).rows
            if rows:
                ki = [c.name for c in tgt.columns].index(tkey)
                keys = sorted({r[ki] for r in rows})
                # PG errors only when ONE TARGET row is matched by
                # MULTIPLE SOURCE rows; several target rows matching
                # one source row each update once (execMerge.c)
                from collections import Counter
                scnt = Counter(r[0] for r in self._exec_stmt(
                    A.SelectStmt(
                        items=[A.SelectItem(
                            A.ColRef((stmt.source, skey)), alias="k")],
                        from_=[A.TableRef(stmt.source)])).rows)
                if any(scnt[k] > 1 for k in keys):
                    raise ExecError(
                        "MERGE command cannot affect row a second "
                        "time (duplicate source join keys)")
                self._exec_stmt(A.DeleteStmt(
                    stmt.target, _in_list(stmt.target, tkey, keys)))
                cols = {c.name: [r[i] for r in rows]
                        for i, c in enumerate(tgt.columns)}
                self._merge_insert(tgt, cols, len(rows))
                total += len(rows)
        elif stmt.matched_delete:
            rows = self._exec_stmt(A.SelectStmt(
                items=[A.SelectItem(
                    A.ColRef((stmt.target, tkey)), alias="k")],
                from_=[join], distinct=True)).rows
            if rows:
                keys = sorted({r[0] for r in rows})
                r = self._exec_stmt(A.DeleteStmt(
                    stmt.target, _in_list(stmt.target, tkey, keys)))
                total += r.rowcount
        if stmt.insert_values is not None:
            cols = stmt.insert_cols or [c.name for c in tgt.columns]
            if len(cols) != len(stmt.insert_values):
                raise ExecError("MERGE INSERT column count mismatch")
            # anti-join: source rows with no target match
            items = [A.SelectItem(e, alias=cn)
                     for cn, e in zip(cols, stmt.insert_values)]
            sel = A.SelectStmt(
                items=items,
                from_=[A.JoinRef("left", A.TableRef(stmt.source),
                                 A.TableRef(stmt.target), stmt.on)],
                where=A.NullTest(A.ColRef((stmt.target, tkey)), True))
            rows = self._exec_stmt(sel).rows
            if rows:
                coldata = {cn: [r[i] for r in rows]
                           for i, cn in enumerate(cols)}
                self._merge_insert(tgt, coldata, len(rows),
                                   cols=cols)
                total += len(rows)
        return total

    def _merge_insert(self, td, coldata, n, cols=None):
        # partition-aware: route through the same paths INSERT uses
        if td.name in self.node.catalog.partitioned:
            self._insert_partitioned(td.name, coldata, n)
            return
        self._check_partition_bound(td.name, coldata, n)
        self._insert_rows(td, self.node.stores[td.name], coldata, n)

    # ---- ALTER TABLE (reference: tablecmds.c ATExecCmd subset) ----
    @staticmethod
    def _alter_guards(catalog, stmt: A.AlterTableStmt):
        """Shared validation: a dist key, indexed column, or partition
        key cannot be dropped/renamed; returns the TableDef."""
        td = catalog.table(stmt.table)
        part_parent = next(
            (p for p, pi in catalog.partitioned.items()
             if any(pt["name"] == stmt.table for pt in pi["parts"])),
            None)
        if stmt.action in ("drop_column", "rename_column"):
            if stmt.name in td.distribution.dist_cols:
                raise ExecError(
                    f"cannot alter distribution column {stmt.name!r}")
            pkey = (catalog.partitioned.get(stmt.table) or
                    (catalog.partitioned[part_parent]
                     if part_parent else None))
            if pkey is not None and stmt.name == pkey["key"]:
                raise ExecError(
                    f"cannot alter partition key column {stmt.name!r}")
            from .constraints import column_drop_guards
            column_drop_guards(catalog, stmt.table, stmt.name)
            if not td.has_column(stmt.name):
                raise ExecError(f"column {stmt.name!r} does not exist")
            idx_cols = catalog.btree_cols.get(stmt.table, set())
            gidx = catalog.global_indexes.get(stmt.table, {})
            if stmt.name in idx_cols or stmt.name in gidx:
                raise ExecError(
                    f"column {stmt.name!r} is indexed; drop the index "
                    "first")
        if stmt.action == "add_column" and \
                td.has_column(stmt.column.name):
            raise ExecError(
                f"column {stmt.column.name!r} already exists")
        if stmt.action == "rename_column" and \
                td.has_column(stmt.new_name):
            raise ExecError(
                f"column {stmt.new_name!r} already exists")
        if stmt.action == "rename_table":
            if stmt.new_name in catalog.tables:
                raise ExecError(
                    f"table {stmt.new_name!r} already exists")
            if catalog.global_indexes.get(stmt.table):
                raise ExecError("cannot rename a table with global "
                                "indexes; drop them first")
            if part_parent is not None:
                raise ExecError(
                    f"cannot rename partition {stmt.table!r} of "
                    f"table {part_parent!r}")
        return td

    def _exec_alter(self, stmt: A.AlterTableStmt) -> Result:
        cat = self.node.catalog
        if stmt.table in cat.partitioned:
            if stmt.action == "rename_table":
                raise ExecError("renaming a partitioned table is not "
                                "supported")
            # DDL recurses to every partition (reference: ATExecCmd
            # recursing over inheritance children)
            r = self._exec_alter_one(stmt)
            for part in cat.partitioned[stmt.table]["parts"]:
                self._exec_alter_one(
                    dataclasses.replace(stmt, table=part["name"]))
            return r
        return self._exec_alter_one(stmt)

    def _exec_alter_one(self, stmt: A.AlterTableStmt) -> Result:
        cat = self.node.catalog
        td = self._alter_guards(cat, stmt)
        st = self.node.stores[stmt.table]
        if stmt.action == "add_column":
            from ..catalog import types as T
            from ..catalog.schema import ColumnDef
            c = stmt.column
            cd = ColumnDef(c.name,
                           T.type_from_name(c.type_name, c.type_args))
            st.alter_add_column(cd)
        elif stmt.action == "drop_column":
            st.alter_drop_column(stmt.name)
        elif stmt.action == "rename_column":
            st.alter_rename_column(stmt.name, stmt.new_name)
        elif stmt.action == "rename_table":
            cat.tables[stmt.new_name] = cat.tables.pop(stmt.table)
            cat.tables[stmt.new_name].name = stmt.new_name
            self.node.stores[stmt.new_name] = \
                self.node.stores.pop(stmt.table)
            cat.btree_cols.pop(stmt.table, None)
        self.node.cache.invalidate(st)
        cat.stats.pop(stmt.table, None)
        self.node._log({"op": "alter_table", "table": stmt.table,
                        "action": stmt.action,
                        "column": (stmt.column.name, stmt.column.type_name,
                                   list(stmt.column.type_args))
                        if stmt.column else None,
                        "name": stmt.name, "new_name": stmt.new_name},
                       sync=True)
        return Result("ALTER TABLE")

    # ---- SELECT ----
    def _plan_select(self, stmt: A.SelectStmt,
                     apply_masks: bool = True) -> PlannedStmt:
        # generic ad-hoc plan cache (exec/plancache.py; the cluster
        # session's twin): identical statements reuse the PlannedStmt
        # and, through the fused tier's memoization, the compiled
        # program
        from .plancache import get_or_build
        node = self.node
        gen = (getattr(node, "ddl_gen", 0),
               len(node.catalog.tables), len(node.catalog.views),
               tuple(sorted(node.gucs.items())))

        masks = apply_masks and \
            not getattr(self, "_unmasked_reads", False) and \
            node.gucs.get("bypass_datamask", "off") != "on"

        def build():
            bq = Binder(node.catalog,
                        apply_masks=masks).bind_select(stmt)
            return Planner(node.catalog).plan(bq)

        with obs_trace.span("plan") \
                if obs_trace.ENABLED else obs_trace.NULL_SPAN:
            return get_or_build(node, "_plan_cache", stmt,
                                (gen, masks), build)

    def _exec_select(self, stmt: A.SelectStmt,
                     instrument: bool = False):
        """Plain SELECT.  With ``instrument`` (the EXPLAIN ANALYZE
        path) the eager tier runs under an InstrumentedExecutor and
        the return value is ``(Result, executor_or_None, planned)`` —
        per-node actuals ride ``executor.node_stats``."""
        if stmt.for_update:
            res = self._exec_select_for_update(stmt)
            return (res, None, None) if instrument else res
        planned = self._plan_select(stmt)
        t, implicit = self._begin_implicit()
        batch = None
        exe = None

        def prerun_init_plans():
            # init plans must run first so their scalars reach the
            # chunk/slab/partition passes (the in-memory path does
            # this in Executor.run); returns (params, stripped plan)
            if not planned.init_plans:
                return {}, planned
            ctx0 = ExecContext(self.node.stores, t.snapshot_ts,
                               t.txid, self.node.cache)
            ex0 = Executor(ctx0)
            for ip in planned.init_plans:
                ctx0.params.update(scalars_from_batch(
                    ex0.exec_node(ip.plan), ip.outputs()))
            return dict(ctx0.params), PlannedStmt(
                planned.plan, [], planned.output_names)

        raw_morsel = self.node.gucs.get("morsel", "auto")
        if raw_morsel != "off" and not instrument:
            # out-of-core streaming tier: the dominant scan streams
            # through fixed-shape device chunk windows (exec/morsel.py)
            from .morsel import MorselDriver, default_chunk_rows
            raw_cr = self.node.gucs.get("morsel_chunk_rows", "")
            cr = int(raw_cr) if raw_cr.isdigit() and int(raw_cr) > 0 \
                else default_chunk_rows()
            from .share import enabled as sharing_enabled
            drv_m = MorselDriver(self.node.stores, self.node.cache,
                                 t.snapshot_ts, t.txid, chunk_rows=cr,
                                 forced=(raw_morsel == "on"),
                                 share=sharing_enabled(self.node.gucs))
            params_m, planned_m = prerun_init_plans()
            drv_m.params = dict(params_m)
            batch = drv_m.try_run(planned_m)
        raw_budget = self.node.gucs.get("work_mem_rows", "")
        if batch is None and raw_budget.isdigit() \
                and int(raw_budget) > 0:
            # beyond-HBM tier: multi-pass partitioned execution when a
            # scanned table exceeds the staging budget (exec/spill.py)
            from .spill import SpillDriver
            drv = SpillDriver(self.node.stores, self.node.cache,
                              t.snapshot_ts, t.txid, int(raw_budget))
            params_s, planned_spill = prerun_init_plans()
            drv.params = dict(params_s)
            batch = drv.try_run(planned_spill)
        if batch is None:
            ctx = ExecContext(self.node.stores, t.snapshot_ts, t.txid,
                              self.node.cache)
            with obs_trace.span("execute", tier="single") \
                    if obs_trace.ENABLED else obs_trace.NULL_SPAN as sp:
                if instrument:
                    from .executor import InstrumentedExecutor
                    exe = InstrumentedExecutor(ctx)
                else:
                    exe = Executor(ctx)
                batch = exe.run(planned)
                # the operators this tier ran itself (a fused fragment
                # under it carries its own on its own span)
                sp.set(**exe.shape)
        names, rows = materialize(batch, planned.output_names)
        qt = obs_trace.current_trace() if obs_trace.ENABLED else None
        if qt is not None:
            qt.rows = len(rows)
        res = Result("SELECT", names=names, rows=rows,
                     rowcount=len(rows))
        if instrument:
            return res, exe, planned
        return res

    # ---- DML ----
    def _exec_insert(self, stmt: A.InsertStmt) -> Result:
        td = self.node.catalog.table(stmt.table)
        st = self.node.stores[stmt.table]
        cols = stmt.columns or td.column_names
        if stmt.select is not None:
            planned = self._plan_select(stmt.select)
            t0, _ = self._begin_implicit()
            ctx = ExecContext(self.node.stores, t0.snapshot_ts, t0.txid,
                              self.node.cache)
            batch = Executor(ctx).run(planned)
            _, rows = materialize(batch, planned.output_names)
        else:
            rows = []
            for vr in stmt.values:
                row = []
                for v in vr:
                    if isinstance(v, A.Const):
                        row.append(v.value)
                    elif isinstance(v, A.TypedConst) and v.type_name == "date":
                        row.append(v.value)
                    elif isinstance(v, A.UnaryOp) and v.op == "-" \
                            and isinstance(v.arg, A.Const):
                        row.append(-float(v.arg.value)
                                   if "." in str(v.arg.value)
                                   else -int(v.arg.value))
                    else:
                        raise ExecError("INSERT values must be literals")
                rows.append(row)
        if not rows:
            return Result("INSERT", rowcount=0)
        if len(cols) != len(rows[0]):
            raise ExecError("INSERT column count mismatch")
        coldata = {c: [r[i] for r in rows] for i, c in enumerate(cols)}
        missing = [c for c in td.column_names if c not in coldata]
        if missing:
            raise ExecError(f"INSERT missing columns {missing} "
                            "(defaults unsupported)")
        if stmt.table in self.node.catalog.partitioned:
            return self._insert_partitioned(stmt.table, coldata,
                                            len(rows))
        self._check_partition_bound(stmt.table, coldata, len(rows))
        return Result("INSERT",
                      rowcount=self._insert_rows(td, st, coldata, len(rows)))

    def _check_partition_bound(self, table: str, coldata: dict, n: int):
        from ..parallel.partition import (PartitionError,
                                          check_child_bounds)
        try:
            check_child_bounds(self.node.catalog, table, coldata, n)
        except PartitionError as e:
            raise ExecError(str(e)) from None

    def _insert_partitioned(self, parent: str, coldata: dict,
                            n: int) -> Result:
        """Route inserted rows to their partitions, one transaction
        (reference: ExecFindPartition per row, here batched)."""
        from ..parallel.partition import PartitionError, split_insert
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        total = 0
        try:
            for child, sub, cn in split_insert(self.node.catalog,
                                               parent, coldata, n):
                ctd = self.node.catalog.table(child)
                total += self._insert_rows(ctd, self.node.stores[child],
                                           sub, cn)
        except PartitionError as e:
            if implicit:
                self.txn = None
                self._abort(t)
            raise ExecError(str(e)) from None
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        return Result("INSERT", rowcount=total)

    def _partition_dml_fanout(self, stmt) -> Result:
        """UPDATE/DELETE on a partitioned parent: fan out per surviving
        child in one transaction; updating the partition key is
        rejected (reference: pre-v11 behavior, no row movement)."""
        from ..parallel.partition import prune_partitions
        cat = self.node.catalog
        pinfo = cat.partitioned[stmt.table]
        key_t = cat.table(stmt.table).column(pinfo["key"]).type
        is_update = isinstance(stmt, A.UpdateStmt)
        if is_update and any(col == pinfo["key"]
                             for col, _ in stmt.assignments):
            raise ExecError("updating the partition key is not "
                            "supported (no row movement)")
        names = prune_partitions(pinfo, key_t, stmt.where, stmt.table)
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        total = 0
        try:
            from ..parallel.partition import rewrite_parent_refs
            for nm in names:
                w = rewrite_parent_refs(stmt.where, stmt.table, nm)
                if is_update:
                    asg = [(cn, rewrite_parent_refs(e, stmt.table, nm))
                           for cn, e in stmt.assignments]
                    child_stmt = A.UpdateStmt(nm, asg, w)
                else:
                    child_stmt = A.DeleteStmt(nm, w)
                total += self._exec_stmt(child_stmt).rowcount
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        return Result("UPDATE" if is_update else "DELETE",
                      rowcount=total)

    def _run_check_query(self, sel: A.SelectStmt, t) -> list:
        """Constraint-validation SELECT inside txn `t` (sees its own
        uncommitted rows through MVCC own-txid visibility)."""
        planned = self._plan_select(sel, apply_masks=False)
        ctx = ExecContext(self.node.stores, t.snapshot_ts, t.txid,
                          self.node.cache)
        batch = Executor(ctx).run(planned)
        _, rows = materialize(batch, planned.output_names)
        return rows

    def _validate_write(self, table: str, t, kind: str = "insert"):
        from .constraints import (tables_needing_validation,
                                  validate_after_write)
        if not tables_needing_validation(self.node.catalog, table,
                                         kind):
            return
        validate_after_write(
            lambda sel: self._run_check_query(sel, t),
            self.node.catalog, table, kind)

    def _insert_rows(self, td: TableDef, st: TableStore,
                     coldata: dict, n: int,
                     fire_triggers: bool = True) -> int:
        from .constraints import check_not_null
        from .triggers import has_triggers
        check_not_null(td, coldata, n)
        t, implicit = self._begin_implicit()
        self._track_write(t)
        trig = fire_triggers and has_triggers(self.node.catalog,
                                              td.name, "insert")
        if trig:
            colnames = list(coldata)
            new_rows = [tuple(coldata[cn][i] for cn in colnames)
                        for i in range(n)]
            try:
                self._fire_triggers(t, implicit, td.name, "before",
                                    "insert", new_rows, None, colnames)
            except Exception:
                if implicit:
                    self._abort(t)
                raise
        clean, masks = {}, {}
        for c, vals in coldata.items():
            cv, m = st.split_nulls(c, vals)
            clean[c] = cv
            if m is not None:
                masks[c] = m
        enc = {c: st.encode_column(c, vals) for c, vals in clean.items()}
        loc = Locator(self.node.catalog)
        raw_for_route = {c: np.asanyarray(clean[c])
                         for c in td.distribution.dist_cols} \
            if td.distribution.dist_type == DistType.SHARD else {}
        sid = loc.shard_ids_for_rows(td, raw_for_route) \
            if raw_for_route else None
        rec = {"op": "insert", "table": td.name, "n": n,
               "txid": t.txid,
               "columns": {c: (_text_log_array(v)
                               if td.column(c).type.kind
                               == TypeKind.TEXT else
                               np.asarray(enc[c]))
                           for c, v in clean.items()}}
        if masks:
            rec["nulls"] = masks
        self.node._log(rec)
        spans = st.insert(enc, n, t.txid, shardids=sid,
                          nulls=masks or None)
        t.insert_spans.append((st, spans))
        t.wal_ops += 1
        try:
            self._validate_write(td.name, t)
            if trig:
                self._fire_triggers(t, implicit, td.name, "after",
                                    "insert", new_rows, None, colnames)
        except Exception:
            if implicit:
                self._abort(t)
            raise
        if implicit:
            self._commit(t)
        return n

    def _old_rows(self, table: str, where, t) -> list:
        """Materialize the pre-image rows a DELETE/UPDATE will touch
        (trigger OLD.*), inside txn t."""
        td = self.node.catalog.table(table)
        sel = A.SelectStmt(
            items=[A.SelectItem(A.ColRef((cn,)), alias=cn)
                   for cn in td.column_names],
            from_=[A.TableRef(table)], where=where)
        return self._run_check_query(sel, t)

    def _exec_delete(self, stmt: A.DeleteStmt,
                     fire_triggers: bool = True) -> Result:
        if stmt.table in self.node.catalog.partitioned:
            return self._partition_dml_fanout(stmt)
        td = self.node.catalog.table(stmt.table)
        st = self.node.stores[stmt.table]
        t, implicit = self._begin_implicit()
        self._track_write(t)
        binder = Binder(self.node.catalog)
        quals = []
        if stmt.where is not None:
            sel = A.SelectStmt(items=[A.SelectItem(A.Star())],
                               from_=[A.TableRef(stmt.table)],
                               where=stmt.where)
            bq = binder.bind_select(sel)
            quals = bq.where
        from .triggers import has_triggers
        trig = fire_triggers and has_triggers(self.node.catalog,
                                              td.name, "delete")
        n_deleted = 0
        try:
            old_rows = None
            if trig:
                old_rows = self._old_rows(stmt.table, stmt.where, t)
                self._fire_triggers(t, implicit, td.name, "before",
                                    "delete", None, old_rows,
                                    td.column_names)
            for span, ci, mask in self._mark_with_wait(
                    st, stmt.table, quals, t, lock_only=False):
                t.delete_spans.append((st, span))
                t.wal_ops += 1
                self.node._log({"op": "delete", "table": td.name,
                                "chunk": ci, "mask": mask,
                                "txid": t.txid})
                n_deleted += int(mask.sum())
            if n_deleted:
                self._validate_write(td.name, t, kind="delete")
            if trig and old_rows and n_deleted:
                self._fire_triggers(t, implicit, td.name, "after",
                                    "delete", None, old_rows,
                                    td.column_names)
        except Exception:
            if implicit:
                self._abort(t)
            raise
        if implicit:
            self._commit(t)
        return Result("DELETE", rowcount=n_deleted)

    def _exec_select_for_update(self, stmt: A.SelectStmt) -> Result:
        """SELECT ... FOR UPDATE [NOWAIT]: lock matching rows first
        (blocking on in-progress writers), then read under the same
        snapshot — locked rows cannot change until txn end (reference:
        LockRows on top of the scan, nodeLockRows.c).  Restricted to a
        single plain table, as aggregation/joins destroy row identity
        (PG rejects FOR UPDATE with aggregates too)."""
        if (len(stmt.from_) != 1
                or not isinstance(stmt.from_[0], A.TableRef)
                or stmt.group_by or stmt.group_sets or stmt.setop
                or stmt.distinct or stmt.ctes or stmt.having):
            raise ExecError(
                "FOR UPDATE is only supported on a single-table "
                "SELECT without aggregation/set operations")
        table = stmt.from_[0].name
        st = self.node.stores.get(table)
        if st is None:
            raise ExecError(f"table {table!r} does not exist")
        quals = []
        if stmt.where is not None:
            bq = Binder(self.node.catalog).bind_select(
                A.SelectStmt(items=[A.SelectItem(A.Star())],
                             from_=[A.TableRef(table)],
                             where=stmt.where))
            quals = bq.where
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        self._track_write(t)
        try:
            for span, _ci, _mask in self._mark_with_wait(
                    st, table, quals, t, lock_only=True,
                    nowait=stmt.for_update == "nowait"):
                t.lock_spans.append((st, span))
            r = self._exec_select(
                dataclasses.replace(stmt, for_update=None))
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        return r

    def _target_masks(self, st, table: str, quals: list, t) -> list:
        from .expr_compile import compile_pred, host_chunk_env
        out = []
        for ci, ch in st.scan_chunks():
            mask = st.visible_mask(ch, t.snapshot_ts, t.txid)
            if quals:
                env, nullable = host_chunk_env(table, ch)
                dicts = {f"{table}.{k}": d
                         for k, d in st.dicts.items()}
                for q in quals:
                    mask = mask & np.asarray(
                        compile_pred(q, dicts, nullable)(env))
            if mask.any():
                out.append((ci, mask))
        return out

    def _mark_with_wait(self, st, table: str, quals: list, t,
                        lock_only: bool, nowait: bool = False) -> list:
        """Statement-atomic row marking with lock waits (the
        single-node twin of DataNode.delete_where/lock_where;
        reference: heap_delete / heap_lock_tuple blocking on the
        updater xid then re-checking)."""
        from ..storage.lockmgr import LockNotAvailable
        from ..storage.store import (SerializationConflict,
                                     WriteConflict)
        node = self.node
        while True:
            targets = self._target_masks(st, table, quals, t)
            done = []
            try:
                for ci, mask in targets:
                    span = st.lock_rows(ci, mask, t.txid) if lock_only \
                        else st.mark_delete(ci, mask, t.txid)
                    done.append((span, ci, mask))
            except WriteConflict as e:
                if lock_only:
                    st.clear_locks([sp for sp, _c, _m in done])
                else:
                    st.revert_delete([sp for sp, _c, _m in done])
                if nowait:
                    raise LockNotAvailable(
                        "could not obtain lock on row (held by txn "
                        f"{e.holder})") from None
                v = node.lockmgr.verdict(e.holder)
                if v is None:
                    v = node.lockmgr.wait_for(e.holder, t.txid,
                                              node.lock_timeout)
                if v == "committed":
                    raise SerializationConflict(
                        "could not serialize access due to concurrent "
                        f"update (txn {e.holder} committed first)") \
                        from None
                continue
            return done

    def _exec_update(self, stmt: A.UpdateStmt) -> Result:
        # MVCC update = delete + insert of new row versions (the reference
        # heap does the same at tuple level)
        if stmt.table in self.node.catalog.partitioned:
            return self._partition_dml_fanout(stmt)
        td = self.node.catalog.table(stmt.table)
        sel_items = []
        assigned = {c: e for c, e in stmt.assignments}
        for c in td.columns:
            src = assigned.get(c.name, A.ColRef((c.name,)))
            sel_items.append(A.SelectItem(src, alias=c.name))
        sel = A.SelectStmt(items=sel_items, from_=[A.TableRef(stmt.table)],
                           where=stmt.where)
        # UPDATE composes a delete + insert and must be ONE transaction:
        # install the implicit txn as the session txn so the nested
        # statements join it instead of drawing (and committing) their own
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        try:
            # row locks first: concurrent updaters queue instead of
            # racing the read-write window (reference: heap_update's
            # tuple lock; see the cluster session's twin)
            lock_quals = []
            if stmt.where is not None:
                lock_quals = Binder(self.node.catalog).bind_select(
                    A.SelectStmt(items=[A.SelectItem(A.Star())],
                                 from_=[A.TableRef(stmt.table)],
                                 where=stmt.where)).where
            st_lock = self.node.stores[stmt.table]
            for span, _ci, _m in self._mark_with_wait(
                    st_lock, stmt.table, lock_quals, t, lock_only=True):
                t.lock_spans.append((st_lock, span))
            from .triggers import has_triggers
            trig = has_triggers(self.node.catalog, td.name, "update")
            if trig:
                # OLD images ride the same scan as the NEW values so
                # the two row sets stay aligned row-for-row
                sel = dataclasses.replace(sel, items=list(sel.items) + [
                    A.SelectItem(A.ColRef((c.name,)),
                                 alias="__old__" + c.name)
                    for c in td.columns])
            planned = self._plan_select(sel, apply_masks=False)
            ctx = ExecContext(self.node.stores, t.snapshot_ts, t.txid,
                              self.node.cache)
            batch = Executor(ctx).run(planned)
            names, rows = materialize(batch, planned.output_names)
            old_rows = None
            if trig:
                ncol = len(td.columns)
                old_rows = [r[ncol:] for r in rows]
                rows = [r[:ncol] for r in rows]
                names = names[:ncol]
                self._fire_triggers(t, implicit, td.name, "before",
                                    "update", rows, old_rows, names)
            self._exec_delete(A.DeleteStmt(stmt.table, stmt.where),
                              fire_triggers=False)
            if rows:
                coldata = {c: [r[i] for r in rows]
                           for i, c in enumerate(names)}
                self._insert_rows(td, self.node.stores[stmt.table],
                                  coldata, len(rows),
                                  fire_triggers=False)
            if trig:
                self._fire_triggers(t, implicit, td.name, "after",
                                    "update", rows, old_rows, names)
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        return Result("UPDATE", rowcount=len(rows))

    # ---- COPY ----
    def _exec_copy(self, stmt: A.CopyStmt) -> Result:
        td = self.node.catalog.table(stmt.table)
        st = self.node.stores[stmt.table]
        delim = str(stmt.options.get("delimiter", "|"))
        cols = stmt.columns or td.column_names
        if stmt.direction == "to":
            rows = self._exec_select(copy_to_select(stmt.table,
                                                    cols)).rows
            n = copy_rows_to_file(stmt.filename, rows, delim)
            return Result("COPY", rowcount=n)
        from ..storage.loader import load_tbl
        coldata = load_tbl(stmt.filename, td, cols, delim)
        n = len(next(iter(coldata.values())))
        if stmt.table in self.node.catalog.partitioned:
            r = self._insert_partitioned(stmt.table, coldata, n)
            return Result("COPY", rowcount=r.rowcount)
        self._check_partition_bound(stmt.table, coldata, n)
        return Result("COPY", rowcount=self._insert_rows(td, st, coldata, n))

    # ---- txn / explain ----
    def _exec_txn(self, stmt: A.TxnStmt) -> Result:
        if stmt.op == "begin":
            if self.txn is None:
                self.txn = TxnState(self.node.gts.next_txid(),
                                    self.node.gts.next_gts())
                self.txn.explicit = True
                self.txn_aborted = False
            return Result("BEGIN")
        if stmt.op == "commit":
            if self.txn is not None:
                if self.txn_aborted:
                    # COMMIT of an aborted txn rolls back (PG); abort
                    # already ran at error time unless savepoints kept
                    # the txn alive for a possible ROLLBACK TO
                    if not getattr(self.txn, "rolled_back", False):
                        self._abort(self.txn)
                    self.txn = None
                    self.txn_aborted = False
                    return Result("ROLLBACK")
                self._commit(self.txn)
                self.txn = None
            return Result("COMMIT")
        if self.txn is not None:
            if not getattr(self.txn, "rolled_back", False):
                self._abort(self.txn)
            self.txn = None
        self.txn_aborted = False
        return Result("ROLLBACK")

    def _exec_explain(self, stmt: A.ExplainStmt) -> Result:
        if not isinstance(stmt.stmt, A.SelectStmt):
            raise ExecError("EXPLAIN supports SELECT only")
        planned = self._plan_select(stmt.stmt)
        text = P.explain(planned.plan)
        if stmt.analyze:
            t0 = time.perf_counter()
            _res, exe, planned2 = self._exec_select(stmt.stmt,
                                                    instrument=True)
            total = (time.perf_counter() - t0) * 1e3
            if exe is not None:
                stats = exe.node_stats

                def ann(nd):
                    st = stats.get(id(nd))
                    if st is None:
                        return ""
                    return (f" (actual rows={st['rows']} "
                            f"time={st['ms']:.2f} ms)")

                text = P.explain(planned2.plan, annotate=ann)
            text += _trace_explain_lines()
            text += f"\nExecution Time: {total:.2f} ms"
        return Result("EXPLAIN", names=["QUERY PLAN"],
                      rows=[(line,) for line in text.split("\n")], text=text)
