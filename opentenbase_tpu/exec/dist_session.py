"""ClusterSession — the coordinator-side SQL session.

Reference analog: a CN backend (tcop/postgres.c session loop) planning into
fragments (pgxc_planner) and driving remote execution (execRemote.c /
execDispatchFragment.c), with implicit 2PC on multi-node writes
(xact.c:3234 + pgxc_node_remote_prepare/commit).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

import copy

from ..catalog import types as T
from ..catalog.schema import DistType, TableDef
from ..catalog.types import TypeKind
from ..obs import trace as obs_trace
from ..parallel.cluster import Cluster
from ..plan import physical as P
from ..plan.distribute import (DistPlan, Distributor, Fragment,
                               fqs_param_router)
from ..plan.planner import PlannedStmt, Planner
from ..sql import ast as A
from ..sql.analyze import Binder
from ..sql.ddl import sequence_def_from_ast, table_def_from_ast
from ..sql.parser import parse_sql
from .dist import DistExecutor
from .executor import ExecContext, ExecError, Executor, materialize
from .session import Result, _trace_explain_lines


@dataclasses.dataclass
class Prepared:
    """A named prepared statement (reference: CachedPlanSource,
    tcop/postgres.c:2411 + commands/prepare.c).

    mode 'plan': the statement was bound ONCE with $n as runtime-parameter
    columns; EXECUTE seeds the executor's param dict and reuses the same
    physical plan — and, through the fused/mesh tiers' traced-parameter
    inputs, the same compiled XLA program — for every binding.  A router
    (the light-coordinator analog, execLight.c:34) ships dist-key-pinned
    statements whole to one datanode.

    mode 'ast': binding with abstract params failed (a TEXT param
    anywhere but `column = $n` / `column <> $n` over a dictionary-coded
    base column); EXECUTE substitutes argument literals into the stored
    parse tree and replans — still skipping the parse.
    """
    stmt: A.Node
    param_types: dict
    mode: str = "ast"
    baked: int = 0    # an autoprep template's WHERE literals left baked
    planned: object = None        # pristine PlannedStmt (FQS fragment)
    dp: object = None             # generic distributed DistPlan
    router: object = None         # params -> datanode index | None
    ddl_gen: object = -1   # _prep_gen() tuple (DDL+stats+GUC state)


def _subst_params(obj, args: list):
    """Rebuild an AST with $n replaced by the EXECUTE argument literals
    (the custom-plan path: re-bound per execution)."""
    if isinstance(obj, A.Param):
        if obj.index - 1 >= len(args):
            raise ExecError(f"no value for parameter ${obj.index}")
        return copy.deepcopy(args[obj.index - 1])
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return type(obj)(**{f.name: _subst_params(getattr(obj, f.name),
                                                  args)
                            for f in dataclasses.fields(obj)})
    if isinstance(obj, list):
        return [_subst_params(x, args) for x in obj]
    if isinstance(obj, tuple):
        return tuple(_subst_params(x, args) for x in obj)
    return obj


class ClusterTxn:
    def __init__(self, txid: int, snapshot_ts: int):
        self.txid = txid
        self.snapshot_ts = snapshot_ts
        self.written_dns: set[int] = set()   # 2PC participant tracking
        self.explicit = False
        self.savepoints: dict = {}      # name -> {dn_index: op mark}


class ClusterSession:
    def __init__(self, cluster: Cluster):
        self.cluster = cluster
        self.txn: Optional[ClusterTxn] = None
        self.txn_aborted = False
        # how SELECTs ran: last_query_stats() answers for the last
        # statement (tier, fallback, per-phase ms); these two count
        # every SELECT's data plane ('mesh' | 'fqs' | 'gidx' | 'host')
        # and keep the host tier's fallback reasons, tracing on or off
        # (the proof that no suite falls back to the host in silence)
        self.tier_counts: dict[str, int] = {}
        self.fallbacks: list[str] = []
        self._last_trace = None     # see last_query_stats
        # named prepared statements + plan-cache telemetry
        self.prepared: dict[str, Prepared] = {}
        self.plan_cache_hits = 0
        # out-of-band statement cancel (set by the CN server's cancel
        # protocol; reference: CHECK_FOR_INTERRUPTS / StatementCancel)
        self.cancel_event = None
        # absolute monotonic deadline of the CURRENT statement, set at
        # execute() entry from the statement_timeout GUC (PG semantics:
        # milliseconds, 0/unset disabled) and enforced at every cancel
        # poll point — queue waits, fragment boundaries, retries
        self._stmt_deadline = None

    def _check_cancel(self):
        ev = self.cancel_event
        if ev is not None and ev.is_set():
            ev.clear()
            raise ExecError("canceling statement due to user request")
        dl = self._stmt_deadline
        if dl is not None and time.monotonic() >= dl:
            raise ExecError(
                "canceling statement due to statement timeout")

    def _arm_deadline(self):
        raw = str(self.cluster.gucs.get("statement_timeout", "")
                  or "").strip()
        ms = None
        try:
            ms = float(raw) if raw else None
        except ValueError:
            ms = None
        self._stmt_deadline = (time.monotonic() + ms / 1e3
                               if ms and ms > 0 else None)

    def _resq_owner(self) -> str:
        """Stable per-session acquirer identity for GTM resource-group
        slots (reference: gtm_resqueue ties slots to connections)."""
        o = getattr(self, "_resq_owner_id", None)
        if o is None:
            import os as _os
            o = self._resq_owner_id = f"cn{_os.getpid()}-{id(self):x}"
        return o

    # ------------------------------------------------------------------
    def execute(self, sql: str) -> list[Result]:
        """Parse and run one message's SQL under ONE statement trace,
        so that the parse is inside it.  The trace is the CN server's
        when the message came over the wire (it opened at the message's
        arrival), else it opens here; each statement's
        `_exec_retryable` joins it."""
        out = []
        self._cur_sql = sql.strip()
        self._arm_deadline()
        audit = getattr(self.cluster, "audit", None) \
            if self.cluster.gucs.get("audit_enabled", "off") == "on" \
            else None
        with obs_trace.trace_query(self._cur_sql[:200]):
            with obs_trace.span("parse"):
                stmts = parse_sql(sql)
            for s in stmts:
                try:
                    r = self.execute_ast(s)
                except Exception as e:
                    if audit:
                        audit.record(type(s).__name__, str(e), ok=False)
                    raise
                if audit:
                    audit.record(type(s).__name__, r.command,
                                 r.rowcount)
                out.append(r)
        return out

    def query(self, sql: str) -> list[tuple]:
        return self.execute(sql)[-1].rows

    def last_query_stats(self) -> dict:
        """Trace-backed per-phase breakdown of the most recent
        statement on this session (plan/stage/execute/exchange/
        finalize ms, tier, fallback reason, rows, bytes, pool hit
        counts).  Empty when OTB_TRACE=0: `tier_counts` and `fallbacks`
        count on regardless.  The trace of a statement that came
        over the wire is the CN server's and is still open while the
        reply is on its way: read then, every span counts as of now and
        `wire_ms` lacks part of the send; the finished trace in
        `obs.trace.recent()` has it all."""
        qt = self._last_trace
        return qt.summary() if qt is not None else {}

    def metrics_text(self) -> str:
        """Prometheus text exposition of the unified registry (also
        served by the CN server's 'metrics' wire op)."""
        from ..obs.metrics import REGISTRY
        return REGISTRY.text()

    def execute_ast(self, s: A.Node) -> Result:
        """Execute ONE already-parsed statement — the shared core of
        execute() and the PG extended protocol's Execute message, where
        the parse happened at Parse time (reference:
        exec_execute_message, tcop/postgres.c).

        PG txn semantics: after an error the txn is poisoned — only
        COMMIT (which rolls back) or ROLLBACK may follow; a failed
        statement aborts the explicit txn NOW (writes revert, row locks
        release — AbortCurrentTransaction), except failures INSIDE
        commit/rollback (2PC outcome belongs to recovery), and live
        savepoints keep the txn alive for ROLLBACK TO."""
        self._check_cancel()
        # multi-CN: reload the shared catalog if another coordinator's
        # DDL (or a failover) bumped the GTM generation
        if self.txn is None:
            self.cluster.maybe_sync_catalog()
        if self.txn is not None and self.txn_aborted \
                and not isinstance(s, A.TxnStmt) \
                and not (isinstance(s, A.SavepointStmt)
                         and s.op == "rollback_to"):
            raise ExecError(
                "current transaction is aborted, commands ignored "
                "until end of transaction block")
        try:
            return self._exec_retryable(s)
        except Exception:
            if self.txn is not None and not self.txn_aborted \
                    and not isinstance(s, A.TxnStmt):
                self.txn_aborted = True
                if not getattr(self.txn, "savepoints", None):
                    self._abort(self.txn)
                    self.txn.rolled_back = True
            raise

    def _exec_retryable(self, s: A.Node) -> Result:
        """READ COMMITTED re-check for implicit statements: a
        concurrent committed writer triggers a whole-statement retry
        under a FRESH snapshot; explicit (REPEATABLE READ-like) txns
        surface PG's serialization error instead."""
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

    # ---- txn helpers ----
    def _begin_implicit(self) -> tuple[ClusterTxn, bool]:
        if self.txn is not None:
            return self.txn, False
        t = ClusterTxn(self.cluster.gtm.next_txid(),
                       self.cluster.gtm.next_gts())
        return t, True

    def _commit(self, t: ClusterTxn):
        self.cluster.commit_txn(t.txid, sorted(t.written_dns))

    def _abort(self, t: ClusterTxn):
        self.cluster.abort_txn(t.txid, t.written_dns)

    # ------------------------------------------------------------------
    def _fire_triggers(self, t, implicit: bool, table: str,
                       timing: str, event: str, rows_new, rows_old,
                       colnames):
        """Fire row triggers inside txn `t` (see exec/triggers.py)."""
        from .triggers import fire
        installed = False
        if implicit and self.txn is None:
            self.txn = t
            installed = True
        try:
            fire(self, self.cluster.catalog, table, timing, event,
                 rows_new, rows_old, colnames)
        finally:
            if installed:
                self.txn = None

    def _old_rows(self, table: str, where, t) -> list:
        td = self.cluster.catalog.table(table)
        sel = A.SelectStmt(
            items=[A.SelectItem(A.ColRef((cn,)), alias=cn)
                   for cn in td.column_names],
            from_=[A.TableRef(table)], where=where)
        return self._run_check_query(sel, t)

    def _exec_stmt(self, stmt: A.Node) -> Result:
        c = self.cluster
        from .security import _SECURITY_DDL
        from .security import ddl as security_ddl
        if isinstance(stmt, _SECURITY_DDL):
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            tag = security_ddl(c.catalog, stmt)
            c._save_catalog()
            return Result(tag)
        from .triggers import _TRIGGER_DDL
        from .triggers import ddl as trigger_ddl
        if isinstance(stmt, _TRIGGER_DDL):
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            tag = trigger_ddl(c.catalog, stmt)
            c._save_catalog()
            return Result(tag)
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
            c.create_table(table_def_from_ast(stmt), stmt.if_not_exists)
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            if stmt.partition_by:
                from ..parallel.partition import (PartitionError,
                                                  register_parent)
                try:
                    register_parent(c.catalog, stmt)
                except PartitionError as e:
                    raise ExecError(str(e)) from None
                c._save_catalog()
            return Result("CREATE TABLE")
        if isinstance(stmt, A.CreatePartitionStmt):
            from ..parallel.partition import (PartitionError,
                                              child_tabledef,
                                              partition_bounds)
            try:
                ptd, rec = partition_bounds(c.catalog, stmt)
            except PartitionError as e:
                raise ExecError(str(e)) from None
            child = child_tabledef(ptd, stmt.name)
            c.create_table(child)
            c.catalog.partitioned[stmt.parent]["parts"].append(rec)
            c._save_catalog()
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            return Result("CREATE TABLE")
        if isinstance(stmt, A.DropTableStmt):
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            if stmt.name in c.catalog.tables:
                from .constraints import drop_guards
                drop_guards(c.catalog, stmt.name)
            pinfo = c.catalog.partitioned.get(stmt.name)
            if pinfo is not None:
                for p in list(pinfo["parts"]):
                    c.drop_table(p["name"], if_exists=True)
                del c.catalog.partitioned[stmt.name]
            else:
                for pi in c.catalog.partitioned.values():
                    pi["parts"] = [p for p in pi["parts"]
                                   if p["name"] != stmt.name]
            c.drop_table(stmt.name, stmt.if_exists)
            c._save_catalog()
            return Result("DROP TABLE")
        if isinstance(stmt, A.CreateSequenceStmt):
            sd = sequence_def_from_ast(stmt)
            c.gtm.seq_create(sd.name, sd.start, sd.increment)
            return Result("CREATE SEQUENCE")
        if isinstance(stmt, A.CreateIndexStmt):
            if stmt.global_:
                from ..parallel import gindex
                try:
                    gindex.create(self, stmt)
                except gindex.GIndexError as e:
                    raise ExecError(str(e)) from None
                return Result("CREATE INDEX")
            if stmt.method == "ivfflat":
                td = c.catalog.table(stmt.table)
                col = stmt.columns[0]
                from ..catalog.types import TypeKind as TK
                if td.column(col).type.kind != TK.VECTOR:
                    raise ExecError("ivfflat requires a vector column")
                lists = int(stmt.options.get("lists", 0))
                metric = str(stmt.options.get("metric", "l2"))
                for dn in c.datanodes:
                    dn.build_ann_index(stmt.table, col, lists, metric)
            elif stmt.method == "hnsw":
                try:
                    for dn in c.datanodes:
                        dn.build_hnsw_index(
                            stmt.table, stmt.columns[0],
                            int(stmt.options.get("m", 16)),
                            int(stmt.options.get("ef_construction", 64)),
                            str(stmt.options.get("metric", "l2")))
                except (ValueError, KeyError, RuntimeError) as e:
                    raise ExecError(str(e)) from None
            else:  # btree: built per DN over its shard (a LOCAL index;
                   # global secondary indexes are a design note in
                   # PARITY.md — the planner still fans point queries
                   # to all DNs, each answering via its local index)
                try:
                    for dn in c.datanodes:
                        dn.build_btree_index(stmt.table,
                                             list(stmt.columns))
                except (ValueError, KeyError, RuntimeError) as e:
                    raise ExecError(str(e)) from None
                c.catalog.btree_cols.setdefault(
                    stmt.table, set()).update(stmt.columns)
            c.catalog.local_indexes[stmt.name] = {
                "table": stmt.table, "cols": list(stmt.columns),
                "method": stmt.method or "btree"}
            c._save_catalog()
            # cached plans must replan to see the new access path
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            return Result("CREATE INDEX")
        if isinstance(stmt, A.CreateViewStmt):
            from ..catalog.catalog import CatalogError
            try:
                c.catalog.create_view(stmt.name, stmt.text,
                                      stmt.or_replace)
            except CatalogError as e:
                raise ExecError(str(e)) from None
            c._save_catalog()
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            return Result("CREATE VIEW")
        if isinstance(stmt, A.DropViewStmt):
            from ..catalog.catalog import CatalogError
            try:
                c.catalog.drop_view(stmt.name, stmt.if_exists)
            except CatalogError as e:
                raise ExecError(str(e)) from None
            c._save_catalog()
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            return Result("DROP VIEW")
        if isinstance(stmt, A.AlterTableStmt):
            return self._exec_alter(stmt)
        if isinstance(stmt, A.CreatePublicationStmt):
            from ..catalog.catalog import CatalogError
            try:
                c.logical_publisher().create_publication(stmt.name,
                                                         stmt.tables)
            except (KeyError, CatalogError) as e:
                raise ExecError(str(e)) from None
            return Result("CREATE PUBLICATION")
        if isinstance(stmt, A.DropPublicationStmt):
            c.logical_publisher().drop_publication(stmt.name)
            return Result("DROP PUBLICATION")
        if isinstance(stmt, A.CreateSubscriptionStmt):
            from ..storage.logical import Subscription
            if stmt.name in c.subscriptions:
                raise ExecError(
                    f"subscription {stmt.name!r} already exists")
            try:
                c.subscriptions[stmt.name] = Subscription(
                    stmt.name, c, stmt.conninfo, stmt.publication)
            except (KeyError, ValueError, ConnectionError, OSError) as e:
                raise ExecError(f"CREATE SUBSCRIPTION: {e}") from None
            return Result("CREATE SUBSCRIPTION")
        if isinstance(stmt, A.DropSubscriptionStmt):
            sub = c.subscriptions.pop(stmt.name, None)
            if sub is not None:
                sub.stop()
            return Result("DROP SUBSCRIPTION")
        if isinstance(stmt, A.DropIndexStmt):
            from ..parallel import gindex
            try:
                if gindex.drop(self, stmt.name, if_exists=True):
                    return Result("DROP INDEX")
            except gindex.GIndexError as e:
                raise ExecError(str(e)) from None
            li = c.catalog.local_indexes.pop(stmt.name, None)
            if li is None:
                if stmt.if_exists:
                    return Result("DROP INDEX")
                raise ExecError(f"index {stmt.name!r} does not exist")
            if li["method"] == "btree":
                # deregister from the planner; other named indexes on
                # the same (table, col) keep it eligible
                still = {c2 for n2, e2 in c.catalog.local_indexes.items()
                         if e2["table"] == li["table"]
                         and e2["method"] == "btree"
                         for c2 in e2["cols"]}
                cols = c.catalog.btree_cols.get(li["table"], set())
                c.catalog.btree_cols[li["table"]] = cols & still | \
                    (cols - set(li["cols"]))
            c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
            c._save_catalog()
            return Result("DROP INDEX")
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
        if isinstance(stmt, (A.CreateJobStmt, A.DropJobStmt)):
            from ..parallel import jobs as _jobs
            try:
                tag = _jobs.ddl(c, stmt)
            except _jobs.JobError as e:
                raise ExecError(str(e)) from None
            return Result(tag)
        if isinstance(stmt, A.CreateResourceGroupStmt):
            if stmt.name in c.catalog.resource_groups:
                raise ExecError(
                    f"resource group {stmt.name!r} already exists")
            grp = {"concurrency": 0, "staging_budget_rows": 0,
                   "device_time_share": 1.0}
            for k, v in stmt.options.items():
                if k not in grp:
                    raise ExecError(f"unknown resource group option "
                                    f"{k!r}")
                grp[k] = float(v) if k == "device_time_share"                     else int(v)
            c.catalog.resource_groups[stmt.name] = grp
            c._save_catalog()
            return Result("CREATE RESOURCE GROUP")
        if isinstance(stmt, A.DropResourceGroupStmt):
            if stmt.name not in c.catalog.resource_groups:
                if stmt.if_exists:
                    return Result("DROP RESOURCE GROUP")
                raise ExecError(
                    f"resource group {stmt.name!r} does not exist")
            del c.catalog.resource_groups[stmt.name]
            c._save_catalog()
            return Result("DROP RESOURCE GROUP")
        if isinstance(stmt, A.SetStmt):
            if stmt.name == "resource_group":
                # SESSION-scoped (PG semantics): the group binds this
                # session's queries, not the whole cluster
                v = str(stmt.value)
                if v and v not in ("", "none", "default") \
                        and v not in c.catalog.resource_groups:
                    raise ExecError(
                        f"resource group {v!r} does not exist")
                self.resource_group = "" if v in ("none", "default") \
                    else v
                return Result("SET")
            c.gucs[stmt.name] = str(stmt.value)
            return Result("SET")
        if isinstance(stmt, A.ShowStmt):
            return Result("SHOW", names=[stmt.name],
                          rows=[(c.gucs.get(stmt.name, ""),)])
        if isinstance(stmt, A.VacuumStmt):
            from ..parallel.maintenance import vacuum_cluster
            n = vacuum_cluster(c, stmt.table)
            if n < 0:
                raise ExecError("VACUUM refused: transactions in flight")
            return Result("VACUUM", rowcount=n)
        if isinstance(stmt, A.AnalyzeStmt):
            c.stats_gen = getattr(c, "stats_gen", 0) + 1
            from ..parallel.statistics import merge_stats
            names = [stmt.table] if stmt.table else \
                list(c.catalog.tables)
            for name in names:
                if name.startswith("otb_"):
                    continue
                if name not in c.catalog.tables:
                    raise ExecError(f"table {name!r} does not exist")
                try:
                    parts = [dn.analyze_table(name)
                             for dn in c.datanodes]
                except (KeyError, RuntimeError) as e:
                    raise ExecError(str(e)) from None
                c.catalog.stats[name] = merge_stats(parts)
            c._save_catalog()
            return Result("ANALYZE")
        if isinstance(stmt, A.BarrierStmt):
            # 2-phase cluster-wide restore point (reference:
            # pgxc/barrier/barrier.c): barrier WAL records on every DN +
            # retained artifacts + GTM registration; restore via
            # `ctl restore --barrier` / Cluster.restore_barrier
            if not c.create_barrier(stmt.name):
                raise ExecError("BARRIER refused: transactions in flight")
            return Result("BARRIER")
        if isinstance(stmt, A.ExecuteDirectStmt):
            return self._exec_direct(stmt)
        if isinstance(stmt, A.PrepareStmt):
            return self._exec_prepare(stmt)
        if isinstance(stmt, A.ExecuteStmt):
            return self._exec_execute(stmt)
        if isinstance(stmt, A.DeallocateStmt):
            if stmt.name is None:
                self.prepared.clear()
            elif self.prepared.pop(stmt.name, None) is None:
                raise ExecError(
                    f"prepared statement {stmt.name!r} does not exist")
            return Result("DEALLOCATE")
        if isinstance(stmt, A.CreateNodeGroupStmt):
            from ..catalog.catalog import CatalogError
            name_to_idx = {nd.name: nd.index
                           for nd in c.catalog.datanodes()}
            members = []
            for m in stmt.members:
                if m not in name_to_idx:
                    raise ExecError(f"unknown datanode {m!r}")
                members.append(name_to_idx[m])
            try:
                c.catalog.create_node_group(stmt.name, members)
            except CatalogError as e:
                raise ExecError(str(e)) from None
            c._save_catalog()
            return Result("CREATE NODE GROUP")
        if isinstance(stmt, A.TruncateStmt):
            return self._exec_truncate(stmt)
        if isinstance(stmt, A.SavepointStmt):
            return self._exec_savepoint(stmt)
        if isinstance(stmt, A.MergeStmt):
            return self._exec_merge(stmt)
        raise ExecError(f"unsupported statement {type(stmt).__name__}")

    # ---- TRUNCATE: DDL-style fan-out to every datanode ----
    def _exec_truncate(self, stmt: A.TruncateStmt) -> Result:
        c = self.cluster
        c.catalog.table(stmt.table)
        if self.txn is not None:
            raise ExecError("TRUNCATE cannot run inside a transaction "
                            "block (non-MVCC bulk clear)")
        from .constraints import drop_guards
        drop_guards(c.catalog, stmt.table, action="truncate")
        # Cluster-level precheck BEFORE touching any node: a later DN
        # refusing (it alone holds txn spans) after earlier DNs were
        # irreversibly cleared would leave the table inconsistent
        # across nodes.  ddl_mutex is held through the fan-out so no
        # new txn can register mid-clear (register_txn takes the same
        # mutex); existing txns are excluded by the precheck itself.
        with c.ddl_mutex:
            if c.active_txns:
                raise ExecError("cannot truncate: in-flight "
                                "transactions exist on this cluster")
            for dn in c.datanodes:
                if dn.inflight():
                    raise ExecError(
                        f"cannot truncate: in-flight transactions hold "
                        f"row spans on datanode {dn.index}")
            names = [stmt.table]
            if stmt.table in c.catalog.partitioned:
                names += [
                    p["name"]
                    for p in c.catalog.partitioned[stmt.table]["parts"]]
            for nm in names:
                for dn in c.datanodes:
                    dn.truncate(nm)
        return Result("TRUNCATE TABLE")

    # ---- SAVEPOINT / ROLLBACK TO / RELEASE: per-DN span markers
    # (reference: subxact machinery, xact.c; the CN records each DN's
    # op-list position, ROLLBACK TO reverts past it on every DN) ----
    def _exec_savepoint(self, stmt: A.SavepointStmt) -> Result:
        t = self.txn
        if t is None or not t.explicit:
            raise ExecError(f"{stmt.op.replace('_', ' ').upper()} can "
                            "only be used in transaction blocks")
        c = self.cluster
        if not hasattr(t, "savepoints"):
            t.savepoints = {}
        if stmt.op == "savepoint":
            t.savepoints[stmt.name] = {
                dn.index: dn.savepoint_mark(t.txid)
                for dn in c.datanodes}
            return Result("SAVEPOINT")
        if stmt.name not in t.savepoints:
            raise ExecError(f"savepoint {stmt.name!r} does not exist")
        if stmt.op == "release":
            drop = False
            for nm in list(t.savepoints):
                if nm == stmt.name:
                    drop = True
                if drop:
                    del t.savepoints[nm]
            return Result("RELEASE")
        marks = t.savepoints[stmt.name]
        for dn in c.datanodes:
            dn.rollback_to_mark(t.txid, marks[dn.index])
        drop = False
        for nm in list(t.savepoints):
            if drop:
                del t.savepoints[nm]
            if nm == stmt.name:
                drop = True
        self.txn_aborted = False
        return Result("ROLLBACK")

    # ---- MERGE: the set-wise decomposition is shared with the
    # single-node session (duck-typed on _exec_stmt/_merge_insert) ----
    def _exec_merge(self, stmt: A.MergeStmt) -> Result:
        from .session import Session
        tgt, tkey, skey = Session._merge_parts(self, stmt)
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        self.cluster.register_txn(t.txid)
        total = 0
        try:
            total = Session._merge_steps(self, stmt, tgt, tkey, skey)
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        return Result("MERGE", rowcount=total)

    def _merge_insert(self, td, coldata, n, cols=None):
        # partition-aware: route through the same paths INSERT uses
        if td.name in self.cluster.catalog.partitioned:
            self._insert_partitioned(td.name, coldata, n)
            return
        self._check_partition_bound(td.name, coldata, n)
        self._insert_rows(td, coldata, n)

    # ---- prepared statements / OLTP fast path ----
    def _ddl_gen(self) -> int:
        return getattr(self.cluster, "ddl_gen", 0)

    def _exec_prepare(self, stmt: A.PrepareStmt) -> Result:
        ptypes = {i + 1: T.type_from_name(nm, targs)
                  for i, (nm, targs) in enumerate(stmt.types)}
        prep = self._build_prepared(stmt.stmt, ptypes)
        self.prepared[stmt.name] = prep
        self._schedule_warm(prep)
        return Result("PREPARE")

    def _schedule_warm(self, prep: Prepared, params: dict = None) -> None:
        """AOT warmup at PREPARE time (ISSUE 1): trace+compile the
        statement's mesh program on the background warmup thread, so
        the first EXECUTE lands warm instead of paying the multi-second
        XLA compile on the query path.  Numeric/date params, and the
        dictionary code of a TEXT param compared with a column, ride as
        traced inputs, so the warmed program serves EVERY later binding
        (zero-valued dummies and the empty string stand in when no
        binding is known); BOOL params bake into program structure and
        can't be abstracted — those preps warm on first execution
        instead.  Router (FQS) preps run single-node eager plans:
        nothing to compile ahead of time."""
        if prep.mode != "plan" or prep.router is not None \
                or prep.dp is None:
            return
        if params is None:
            params = {}
            for i, t in prep.param_types.items():
                if t.kind == TypeKind.BOOL:
                    return
                params[f"__bindparam{i}"] = (
                    "" if t.kind == TypeKind.TEXT else 0, t)
        self._schedule_warm_dp(prep.dp, params)

    def _schedule_warm_dp(self, dp: DistPlan, params: dict) -> None:
        c = self.cluster
        if c.gucs.get("enable_mesh_exchange", "on") == "off":
            return
        from .mesh_exec import mesh_runner_for
        from .plancache import warm_async

        def job():
            runner = mesh_runner_for(c)
            if runner is not None:
                runner.warm(dp, int(c.gtm.next_gts()), params)
        warm_async(job)

    def warm_statement(self, sql: str) -> int:
        """Hot-statement AOT warmup — the restart story's other half:
        after `ctl start` (or any cluster attach), feed the workload's
        hot statements here and their mesh programs compile on the
        background warmup thread THROUGH THE SAME autoprep template the
        first real execution will hit, so that execution finds the
        template, the staged tables, the learned size-class ladder, and
        (with the persistent XLA cache) the compiled executable all
        warm.  Returns how many statements were scheduled."""
        from ..sql.parser import parse_sql
        c = self.cluster
        n = 0
        for stmt in parse_sql(sql):
            if not isinstance(stmt, A.SelectStmt):
                continue
            prep = params = None
            if not (c.catalog.global_indexes
                    or c.gucs.get("enable_autoprepare", "on") == "off"
                    or c.gucs.get("enable_spm", "off") == "on"
                    or c.gucs.get("spm_capture", "off") == "on"):
                prep, arg_nodes = self._autoprep_template(stmt)
                params = self._bind_lifted(prep, arg_nodes)
            if params is not None and prep.router is None \
                    and prep.dp is not None:
                self._schedule_warm(prep, params)
                n += 1
                continue
            try:
                dp = self._plan_distributed(stmt)
            except Exception:
                continue
            if dp.fqs_node is None:
                self._schedule_warm_dp(dp, {})
                n += 1
        return n

    def _prep_gen(self):
        """Prepared-plan staleness key: DDL, stats, AND GUCs — a SET
        (e.g. bypass_datamask flipping masking back on) must replan
        EXECUTE just like it replans the ad-hoc caches."""
        return self._plan_gen()

    def _build_prepared(self, inner: A.Node, ptypes: dict) -> Prepared:
        from ..sql.analyze import BindError
        prep = Prepared(inner, ptypes, ddl_gen=self._prep_gen())
        if self.cluster.catalog.global_indexes and any(
                t.kind == TypeKind.TEXT for t in ptypes.values()):
            # global-index routing reads the string at plan time
            # (gindex.route): substitute and replan, as the ad-hoc path
            # does wherever a global index exists (_try_autoprep)
            return prep
        if isinstance(inner, A.SelectStmt):
            try:
                masks = self.cluster.gucs.get(
                    "bypass_datamask", "off") != "on"
                binder = Binder(self.cluster.catalog,
                                param_types=ptypes, apply_masks=masks)
                bq = binder.bind_select(inner)
                planned = Planner(self.cluster.catalog).plan(bq)
                # distribute() rewrites the tree in place: keep a pristine
                # copy as the whole-statement (FQS/light) fragment
                pristine = copy.deepcopy(planned)
                d = Distributor(self.cluster.catalog, self.cluster.ndn)
                prep.dp = d.distribute(planned, None)
                prep.planned = pristine
                prep.router = fqs_param_router(bq, self.cluster.catalog)
                prep.mode = "plan"
            except BindError as e:
                if "substitution path" not in str(e):
                    # invalid statement: error at PREPARE time (PG does)
                    raise ExecError(str(e)) from None
                # a TEXT param that is no `column = $n` / `<> $n` (LIKE,
                # IN, a range, a projection): fall back to literal
                # substitution + replan per EXECUTE (PostgreSQL's
                # custom-plan path)
                prep.mode = "ast"
            except ValueError:
                # binds fine but this shape can't pre-plan with abstract
                # params (e.g. a bare-param projection): substitute
                prep.mode = "ast"
        return prep

    def _bind_arg(self, node: A.Node, t) -> object:
        """EXECUTE argument literal -> storage-representation value
        matching the declared type (scaled int for DECIMAL, days for
        DATE) — the form E.Lit carries."""
        if isinstance(node, A.UnaryOp) and node.op == "-":
            v = self._bind_arg(node.arg, t)
            if isinstance(v, (int, float)):
                return -v
            raise ExecError("cannot negate a non-numeric argument")
        if isinstance(node, A.TypedConst) and node.type_name == "date":
            return T.date_to_days(node.value)
        if isinstance(node, A.BinOp) and node.op in ("+", "-") \
                and isinstance(node.right, A.TypedConst) \
                and node.right.type_name == "interval":
            # a date-valued constant expression, evaluated here so that
            # it rides as ONE DATE parameter (the binder folds a baked
            # one with the same function)
            qty = node.right.qty if node.op == "+" else -node.right.qty
            try:
                return T.add_interval(self._bind_arg(node.left, t), qty,
                                      node.right.unit)
            except ValueError as e:
                raise ExecError(str(e)) from None
        if not isinstance(node, A.Const):
            raise ExecError("EXECUTE arguments must be literals")
        v = node.value
        k = t.kind
        if k == TypeKind.DECIMAL:
            return T.decimal_to_int(str(v), t.scale)
        if k == TypeKind.DATE:
            return T.date_to_days(str(v))
        if k == TypeKind.FLOAT64:
            return float(v)
        if k == TypeKind.TEXT:
            return str(v)
        if k == TypeKind.BOOL:
            return bool(v)
        return int(v)

    def _exec_execute(self, stmt: A.ExecuteStmt) -> Result:
        prep = self.prepared.get(stmt.name)
        if prep is None:
            raise ExecError(
                f"prepared statement {stmt.name!r} does not exist")
        if prep.ddl_gen != self._prep_gen():
            # DDL / stats / GUC change since PREPARE: replan against
            # the current catalog + settings
            prep = self._build_prepared(prep.stmt, prep.param_types)
            self.prepared[stmt.name] = prep
        if prep.mode != "plan":
            sub = _subst_params(prep.stmt, stmt.args)
            return self._exec_stmt(sub)
        if len(stmt.args) != len(prep.param_types):
            raise ExecError(
                f"wrong number of parameters: got {len(stmt.args)}, "
                f"need {len(prep.param_types)}")
        params = {}
        for i, arg in enumerate(stmt.args, start=1):
            t = prep.param_types[i]
            params[f"__bindparam{i}"] = (self._bind_arg(arg, t), t)
        self.plan_cache_hits += 1
        self._refresh_stat_views(prep.stmt)
        t, implicit = self._begin_implicit()
        node = prep.router(params) if prep.router is not None else None
        if node is not None:
            # light-coordinator path: the whole statement runs on ONE
            # datanode with bound params (reference: execLight.c:34-59)
            dp = DistPlan([Fragment(0, prep.planned.plan, "dn")], [], 0,
                          prep.planned.init_plans,
                          prep.planned.output_names, fqs_node=node)
        else:
            dp = prep.dp
        res, _ex = self._run_select_dp(dp, t, params)
        return res

    # ---- SELECT ----
    def _plan_distributed(self, stmt: A.SelectStmt,
                          txn: "ClusterTxn" = None,
                          apply_masks: bool = True) -> DistPlan:
        # generic ad-hoc plan cache (exec/plancache.py): repeated
        # identical SELECTs reuse the DistPlan, and through the mesh
        # tier's program cache the compiled XLA program.  The
        # generation covers DDL, stats, AND the planning GUCs, so SET
        # changes invalidate cached plans.
        from .plancache import get_or_build
        c0 = self.cluster
        masks = apply_masks and \
            not getattr(self, "_unmasked_reads", False) and \
            c0.gucs.get("bypass_datamask", "off") != "on"
        gen = (self._plan_gen(), masks)
        with obs_trace.span("plan"):
            return get_or_build(
                c0, "_dp_cache", stmt, gen,
                lambda: self._plan_distributed_uncached(stmt, txn, masks),
                cacheable=lambda dp: dp.fqs_node is None)

    def _plan_distributed_uncached(self, stmt: A.SelectStmt,
                                   txn: "ClusterTxn" = None,
                                   apply_masks: bool = True) -> DistPlan:
        binder = Binder(self.cluster.catalog, apply_masks=apply_masks)
        bq = binder.bind_select(stmt)
        # SPM plan baselines: replay the accepted join order for this
        # normalized statement; capture the first plan when asked
        # (reference: optimizer/spm/spm.c — enable_spm applies,
        # spm_capture records)
        gucs = self.cluster.gucs
        forced = None
        fp = None
        if gucs.get("enable_spm", "off") == "on" or \
                gucs.get("spm_capture", "off") == "on":
            from ..sql.fingerprint import fingerprint
            fp = fingerprint(stmt)
            if gucs.get("enable_spm", "off") == "on":
                forced = self.cluster.catalog.spm.get(fp)
        planned = Planner(self.cluster.catalog).plan(
            bq, forced_order=forced)
        if fp is not None and forced is None and \
                gucs.get("spm_capture", "off") == "on" and \
                len(planned.join_order_chosen) > 1:
            self.cluster.catalog.spm[fp] = \
                list(planned.join_order_chosen)
            self.cluster._save_catalog()
        fqs_enabled = self.cluster.gucs.get(
            "enable_fast_query_shipping", "on") != "off"
        gidx_enabled = self.cluster.gucs.get(
            "enable_global_indexscan", "on") != "off"
        if fqs_enabled and gidx_enabled and txn is not None \
                and self.cluster.catalog.global_indexes:
            from ..parallel import gindex
            from ..plan.distribute import fqs_target_node
            if fqs_target_node(bq, self.cluster.catalog) is None:
                hit = gindex.route(self, bq, txn.snapshot_ts, txn.txid)
                if hit is not None:
                    node, via = hit
                    return DistPlan([Fragment(0, planned.plan, "dn")],
                                    [], 0, planned.init_plans,
                                    planned.output_names, fqs_node=node,
                                    via_gidx=via)
        d = Distributor(self.cluster.catalog, self.cluster.ndn)
        return d.distribute(planned, bq if fqs_enabled else None)

    def _refresh_stat_views(self, stmt: A.SelectStmt):
        from ..parallel import statviews

        # collect every table name anywhere in the statement, including
        # WHERE/target-list subqueries
        names = []

        def walk(obj):
            if isinstance(obj, A.TableRef):
                names.append(obj.name)
            if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
                for f in dataclasses.fields(obj):
                    walk(getattr(obj, f.name))
            elif isinstance(obj, (list, tuple)):
                for x in obj:
                    walk(x)

        walk(stmt)
        wanted = statviews.referenced_stat_tables(names)
        if wanted:
            statviews.refresh(self.cluster, wanted)

    def _run_select_dp(self, dp: DistPlan, txn: ClusterTxn,
                       params: dict = None, instrument: bool = False):
        """Run a SELECT DistPlan under admission control and record the
        data-plane telemetry — shared by plain SELECT and EXECUTE.  The
        device-mesh data plane is the default (reference: the FN plane is
        the default tuple transport); 'off' forces the host tier.

        Resource-group enforcement (reference: resgroup-ops-linux.c +
        gtm_resqueue.c, TPU-native): per-group concurrency slots are
        acquired on the GTM (cluster-wide — every coordinator shares
        the cap), the group's HBM staging budget routes over-budget
        queries through the spill tier, and device wall time is
        accounted per group."""
        import time as _t
        c = self.cluster
        queue = c.resource_queue()
        if queue is not None:
            queue.acquire()
        group = getattr(self, "resource_group", "")
        ginfo = c.catalog.resource_groups.get(group) if group else None
        gtm_held = False
        try:
            if ginfo and ginfo.get("concurrency", 0) > 0:
                cap = int(ginfo["concurrency"])
                deadline = _t.monotonic() + 30.0
                # slots carry this coordinator's identity + a lease so
                # a crashed CN can't permanently shrink the group's
                # cluster-wide concurrency (the GTM reaps on lease
                # expiry and on connection close)
                owner = self._resq_owner()
                try:
                    lease = float(c.gucs.get("resgroup_lease_s", "30"))
                except ValueError:
                    lease = 30.0
                # jittered exponential backoff (net/guard.py): a
                # saturated group must not hammer the GTM (GTS/commit
                # traffic shares it), and concurrent waiters must not
                # retry in lockstep.  Timing out here is the overload
                # arm of the guard's degradation ladder — same counter
                # surface as the scheduler's shed path.
                from ..net.guard import backoff_s, note_shed
                attempt = 0
                while not c.gtm.resq_acquire(group, cap, owner, lease):
                    if _t.monotonic() > deadline:
                        note_shed(group or "default")
                        raise ExecError(
                            f"resource group {group!r} queue wait "
                            f"timeout ({cap} slots busy cluster-wide)")
                    self._check_cancel()
                    attempt += 1
                    _t.sleep(backoff_s(attempt, base=0.002, cap=0.1))
                gtm_held = True
        except Exception:
            # cancel / GTM error while waiting: the admission slot
            # must not leak (it would shrink cluster concurrency
            # permanently)
            if queue is not None:
                queue.release()
            raise
        t0 = _t.perf_counter()
        try:
            ex = DistExecutor(self.cluster, txn.snapshot_ts, txn.txid,
                              cancel_check=self._check_cancel,
                              instrument=instrument,
                              use_mesh=self.cluster.gucs.get(
                                  "enable_mesh_exchange", "on") != "off",
                              group_budget_rows=int(ginfo.get(
                                  "staging_budget_rows", 0))
                              if ginfo else 0,
                              # standby routing only for reads of txns
                              # with no writes: own uncommitted rows
                              # exist nowhere but the primary
                              replica_reads=self.cluster.gucs.get(
                                  "replica_reads", "off") == "on"
                              and not txn.written_dns)
            if params:
                ex.params.update(params)
            batch = ex.run(dp)
        finally:
            elapsed = _t.perf_counter() - t0
            if group:
                usage = getattr(c, "resgroup_usage", None)
                if usage is None:
                    usage = c.resgroup_usage = {}
                u = usage.setdefault(group,
                                     {"device_s": 0.0, "queries": 0})
                u["device_s"] += elapsed
                u["queries"] += 1
            if gtm_held:
                try:
                    c.gtm.resq_release(group, self._resq_owner())
                except Exception:
                    pass
            if queue is not None:
                queue.release()
        names, rows = materialize(batch, dp.output_names)
        # the result batch's device buffers go here (see fused.py)
        with obs_trace.span("release"):
            del batch
        self.tier_counts[ex.tier] = self.tier_counts.get(ex.tier, 0) + 1
        if ex.tier == "host" and ex.fallback_reason:
            self.fallbacks.append(ex.fallback_reason)
        qt = obs_trace.current_trace()
        if qt is not None:
            qt.tier = ex.tier or qt.tier
            qt.rows = len(rows)
            if ex.fallback_reason:
                qt.root.attrs.setdefault("fallback", ex.fallback_reason)
        return Result("SELECT", names=names, rows=rows,
                      rowcount=len(rows)), ex

    def _exec_select(self, stmt: A.SelectStmt,
                     instrument: bool = False) -> tuple:
        if stmt.for_update:
            return self._exec_select_for_update(stmt)
        self._refresh_stat_views(stmt)
        t, implicit = self._begin_implicit()
        res = None
        if not instrument:
            res = self._try_autoprep(stmt, t)
        elif stmt.where is not None:
            # EXPLAIN ANALYZE plans the statement as written, so that
            # every node reports actuals: no literal is lifted
            from .autoprep import count_literals
            with obs_trace.span("bind") as sp:
                sp.set(traced=0, baked=count_literals(stmt.where))
        if res is None:
            dp = self._plan_distributed(stmt, txn=t)
            res, ex = self._run_select_dp(dp, t, instrument=instrument)
            if instrument:
                return res, ex, dp
        if self.cluster.catalog.fga_policies:
            from .security import fga_check
            fga_check(self, stmt)
        return res

    def _plan_gen(self) -> tuple:
        """Plan-cache generation: any DDL, stats refresh, or GUC change
        invalidates cached plans (shared by the exact-statement cache
        and the auto-prepare cache so they can never diverge)."""
        c = self.cluster
        return (getattr(c, "ddl_gen", 0), getattr(c, "stats_gen", 0),
                tuple(sorted(c.gucs.items())))

    def _try_autoprep(self, stmt: A.SelectStmt, t) -> "Result | None":
        """Raw-literal OLTP fast path: lift WHERE literals to params,
        reuse a cluster-wide Prepared keyed by the template — fresh
        literals then cost a router call, not a plan cycle (reference:
        FQS pgxc/plan/planner.c:390 answering unprepared single-shard
        reads; the exact-statement cache only helps REPEATED
        literals)."""
        c = self.cluster
        if c.gucs.get("enable_autoprepare", "on") == "off" \
                or getattr(self, "_unmasked_reads", False):
            return None
        # paths with extra ad-hoc planning intelligence keep the full
        # plan cycle: global-index routing consults DATA at plan time,
        # SPM baselines key on the ad-hoc fingerprint
        if c.catalog.global_indexes \
                or c.gucs.get("enable_spm", "off") == "on" \
                or c.gucs.get("spm_capture", "off") == "on":
            return None
        with obs_trace.span("autoprep"):
            prep, arg_nodes = self._autoprep_template(stmt)
        # bind: the lifted literals' values, each in its parameter's
        # storage representation (a date expression evaluated, a string
        # as it is: the tier that runs the statement binds it to a
        # dictionary code, in a `bind` span of its own)
        with obs_trace.span("bind") as sp:
            params = self._bind_lifted(prep, arg_nodes)
            if params is None:
                from .autoprep import count_literals
                sp.set(traced=0, baked=count_literals(stmt.where))
            else:
                sp.set(traced=len(params), baked=prep.baked)
        if params is None:
            return None     # normal plan path (original stmt)
        self.plan_cache_hits += 1
        node = prep.router(params) if prep.router is not None else None
        if node is not None:
            dp = DistPlan([Fragment(0, prep.planned.plan, "dn")], [], 0,
                          prep.planned.init_plans,
                          prep.planned.output_names, fqs_node=node)
        else:
            dp = prep.dp
        res, _ex = self._run_select_dp(dp, t, params)
        return res

    def _autoprep_template(self, stmt: A.SelectStmt):
        """(Prepared, lifted literal nodes) for the statement's autoprep
        template, or (None, None).  The SHARED core of the ad-hoc fast
        path and warm_statement — both must build the same template
        under the same cache key so warmup compiles exactly the program
        the first execution looks up.  Strings are lifted first; where
        that template does not pre-plan (the binder found a lifted
        string no dictionary-coded base column takes), the template
        with the strings baked stands in, so one string in a view or a
        CTE does not cost the statement its other parameters."""
        from .autoprep import parameterize
        for text in (True, False):
            try:
                hit = parameterize(stmt, text)
            except Exception:
                return None, None
            if hit is None:
                return None, None
            template, arg_nodes, ptypes = hit
            strings = any(t.kind == TypeKind.TEXT for t in ptypes.values())
            prep = self._template_prepared(template, ptypes)
            if not strings or (prep is not None and prep.mode == "plan"):
                break
        if prep is None:
            return None, None
        return prep, arg_nodes

    def _template_prepared(self, template, ptypes) -> "Prepared | None":
        """The cluster-wide Prepared of one autoprep template, or None
        for a template that cannot bind (remembered, like any other)."""
        from ..sql.fingerprint import fingerprint
        from .autoprep import cached_template, count_literals
        try:
            # the type signature is part of the key: A.Param carries
            # only an index, so `k = 10` (INT64) and `k = 10.5`
            # (DECIMAL(30,1)) share a template but must not share a
            # plan (the int plan would bind 10.5 as a truncated int)
            key = (fingerprint(template, mask_literals=False),
                   tuple(str(ptypes[i])
                         for i in range(1, len(ptypes) + 1)))
        except Exception:
            return None

        def build():
            try:
                prep = self._build_prepared(template, ptypes)
            except Exception:
                return None     # remember: this template can't bind
            prep.baked = count_literals(template.where)
            return prep

        return cached_template(self.cluster, key, self._plan_gen(), build)

    def _bind_lifted(self, prep, arg_nodes) -> "dict | None":
        """The template's parameters bound to this statement's lifted
        literals, or None when the statement takes the normal plan path
        (no template, one that does not pre-plan, or a literal its
        parameter's type cannot hold)."""
        if prep is None or prep.mode != "plan":
            return None
        params = {}
        try:
            for i, arg in enumerate(arg_nodes, start=1):
                t = prep.param_types[i]
                params[f"__bindparam{i}"] = (self._bind_arg(arg, t), t)
        except Exception:
            return None
        return params

    def _exec_select_for_update(self, stmt: A.SelectStmt) -> Result:
        """Cluster SELECT ... FOR UPDATE [NOWAIT]: lock matching rows
        on every datanode holding the table (lock_where RPC, waits
        ride the DN lock managers), then read under the same snapshot
        (reference: RowMarkClause shipped in the RemoteQuery,
        nodeLockRows.c on each DN)."""
        if (len(stmt.from_) != 1
                or not isinstance(stmt.from_[0], A.TableRef)
                or stmt.group_by or stmt.group_sets or stmt.setop
                or stmt.distinct or stmt.ctes or stmt.having):
            raise ExecError(
                "FOR UPDATE is only supported on a single-table "
                "SELECT without aggregation/set operations")
        c = self.cluster
        table = stmt.from_[0].name
        td = c.catalog.table(table)
        c.ensure_gdd()
        quals = []
        if stmt.where is not None:
            quals = Binder(c.catalog).bind_select(
                A.SelectStmt(items=[A.SelectItem(A.Star())],
                             from_=[A.TableRef(table)],
                             where=stmt.where)).where
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        c.register_txn(t.txid)
        try:
            for dn in c.datanodes:
                n = dn.lock_where(td.name, quals, t.snapshot_ts,
                                  t.txid, stmt.for_update == "nowait")
                if n:
                    # lock spans must be cleared at txn end on that DN
                    t.written_dns.add(dn.index)
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

    # ---- ALTER TABLE: catalog change + DDL fan-out to every DN
    # (reference: utility.c remote DDL broadcast of ATExecCmd) ----
    def _exec_alter(self, stmt: A.AlterTableStmt) -> Result:
        c = self.cluster
        if stmt.table in c.catalog.partitioned:
            if stmt.action == "rename_table":
                raise ExecError("renaming a partitioned table is not "
                                "supported")
            # DDL recurses to every partition (reference: ATExecCmd
            # recursing over inheritance children)
            r = self._exec_alter_one(stmt)
            for part in c.catalog.partitioned[stmt.table]["parts"]:
                self._exec_alter_one(
                    dataclasses.replace(stmt, table=part["name"]))
            return r
        return self._exec_alter_one(stmt)

    def _exec_alter_one(self, stmt: A.AlterTableStmt) -> Result:
        from .session import Session
        c = self.cluster
        Session._alter_guards(c.catalog, stmt)
        rec = {"table": stmt.table, "action": stmt.action,
               "column": (stmt.column.name, stmt.column.type_name,
                          list(stmt.column.type_args))
               if stmt.column else None,
               "name": stmt.name, "new_name": stmt.new_name}
        if stmt.action == "rename_table":
            c.catalog.tables[stmt.new_name] = \
                c.catalog.tables.pop(stmt.table)
            c.catalog.tables[stmt.new_name].name = stmt.new_name
            c.catalog.btree_cols.pop(stmt.table, None)
        else:
            # apply the schema change to the CN catalog explicitly —
            # remote (TCP) datanodes hold their OWN TableDef copies, so
            # the shared-object mutation in-proc DNs perform never
            # reaches this catalog; every edit is idempotent for when
            # the objects ARE shared
            td = c.catalog.table(stmt.table)
            if stmt.action == "add_column" and \
                    not td.has_column(stmt.column.name):
                from ..catalog import types as T
                from ..catalog.schema import ColumnDef
                td.columns.append(ColumnDef(
                    stmt.column.name,
                    T.type_from_name(stmt.column.type_name,
                                     stmt.column.type_args)))
            elif stmt.action == "drop_column":
                td.columns = [cc for cc in td.columns
                              if cc.name != stmt.name]
            elif stmt.action == "rename_column":
                for cc in td.columns:
                    if cc.name == stmt.name:
                        cc.name = stmt.new_name
        for dn in c.datanodes:
            dn.alter_table(dict(rec))
        c.catalog.stats.pop(stmt.table, None)
        c._save_catalog()
        c.ddl_gen = getattr(c, "ddl_gen", 0) + 1
        return Result("ALTER TABLE")

    # ---- writes ----
    def _exec_insert(self, stmt: A.InsertStmt) -> Result:
        td = self.cluster.catalog.table(stmt.table)
        cols = stmt.columns or td.column_names
        if stmt.select is not None:
            dp = self._plan_distributed(stmt.select)
            t0, _ = self._begin_implicit()
            batch = DistExecutor(
                self.cluster, t0.snapshot_ts, t0.txid,
                cancel_check=self._check_cancel).run(dp)
            _, rows = materialize(batch, dp.output_names)
        else:
            rows = []
            for vr in stmt.values:
                row = []
                for v in vr:
                    if isinstance(v, A.Const):
                        row.append(v.value)
                    elif isinstance(v, A.TypedConst) and \
                            v.type_name == "date":
                        row.append(v.value)
                    elif isinstance(v, A.UnaryOp) and v.op == "-" \
                            and isinstance(v.arg, A.Const):
                        row.append(-float(v.arg.value)
                                   if "." in str(v.arg.value)
                                   else -int(v.arg.value))
                    elif isinstance(v, A.FuncCall) \
                            and v.name == "nextval" \
                            and len(v.args) == 1 \
                            and isinstance(v.args[0], A.Const):
                        # GTM-served sequence draw (reference:
                        # gtm_seq.c — nextval in a VALUES list is the
                        # standard serial-column INSERT shape)
                        row.append(int(self.cluster.gtm.seq_next(
                            str(v.args[0].value))))
                    else:
                        raise ExecError("INSERT values must be literals")
                rows.append(row)
        if not rows:
            return Result("INSERT", rowcount=0)
        if len(cols) != len(rows[0]):
            raise ExecError("INSERT column count mismatch")
        coldata = {cname: [r[i] for r in rows]
                   for i, cname in enumerate(cols)}
        missing = [cn for cn in td.column_names if cn not in coldata]
        if missing:
            raise ExecError(f"INSERT missing columns {missing}")
        if stmt.table in self.cluster.catalog.partitioned:
            if stmt.on_conflict is not None:
                raise ExecError("ON CONFLICT through a partitioned "
                                "parent is not supported")
            return self._insert_partitioned(stmt.table, coldata,
                                            len(rows))
        self._check_partition_bound(stmt.table, coldata, len(rows))
        if stmt.on_conflict is not None:
            return self._exec_upsert(td, stmt.on_conflict, coldata,
                                     len(rows))
        n = self._insert_rows(td, coldata, len(rows))
        return Result("INSERT", rowcount=n)

    def _check_partition_bound(self, table: str, coldata: dict, n: int):
        """Reject rows outside a partition child's declared bounds
        (reference: ExecPartitionCheck; the single-node session's twin)."""
        from ..parallel.partition import (PartitionError,
                                          check_child_bounds)
        try:
            check_child_bounds(self.cluster.catalog, table, coldata, n)
        except PartitionError as e:
            raise ExecError(str(e)) from None

    def _insert_partitioned(self, parent: str, coldata: dict,
                            n: int) -> Result:
        """Route rows to partitions in one (2PC when multi-DN) txn."""
        from ..parallel.partition import PartitionError, split_insert
        c = self.cluster
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        total = 0
        try:
            for child, sub, cn in split_insert(c.catalog, parent,
                                               coldata, n):
                total += self._insert_rows(c.catalog.table(child),
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
        """UPDATE/DELETE on a partitioned parent (see the single-node
        session's twin)."""
        from ..parallel.partition import prune_partitions
        c = self.cluster
        pinfo = c.catalog.partitioned[stmt.table]
        key_t = c.catalog.table(stmt.table).column(pinfo["key"]).type
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

    # ---- UPSERT (reference: the select/insert/update legs built by
    # pgxc_build_upsert_statement, pgxc/plan/planner.c:1070, executed by
    # nodeRemoteModifyTable.c) ----
    def _key_quals(self, td: TableDef, target: list, keys: set) -> list:
        """Device-evaluable quals selecting rows whose key is in `keys`
        (single-column targets; multi-column callers filter host-side)."""
        from ..parallel import gindex
        if len(target) != 1 or not keys:
            return []
        cname = target[0]
        return gindex.key_quals(td, cname, f"{td.name}.{cname}",
                                [k[0] for k in keys])

    def _exec_upsert(self, td: TableDef, oc: A.OnConflict, coldata: dict,
                     n: int) -> Result:
        from ..parallel import gindex
        c = self.cluster
        target = list(oc.columns) or list(td.distribution.dist_cols)
        if not target:
            raise ExecError("ON CONFLICT requires a conflict target "
                            "column list on this table")
        if td.distribution.dist_type != DistType.REPLICATED and \
                not set(td.distribution.dist_cols) <= set(target):
            raise ExecError(
                "ON CONFLICT target must include the distribution key")
        for cn in target:
            if cn not in coldata:
                raise ExecError(
                    f"ON CONFLICT target column {cn!r} not inserted")
        if oc.action == "update":
            # validate the SET list BEFORE any destructive leg runs
            bad = [cn for cn, _ in oc.assignments
                   if not td.has_column(cn)]
            if bad:
                raise ExecError(
                    f"unknown columns in DO UPDATE SET: {bad}")
            if {cn for cn, _ in oc.assignments} & set(target):
                raise ExecError(
                    "DO UPDATE may not change the conflict target")

        key_cols = {}
        for cn in target:
            ks = gindex.storage_keys(td, cn, coldata[cn])
            if any(k is None for k in ks):
                raise ExecError("ON CONFLICT key value may not be NULL")
            key_cols[cn] = ks
        in_keys = [tuple(key_cols[cn][i] for cn in target)
                   for i in range(n)]
        # batch-internal duplicates: PG errors for DO UPDATE ("cannot
        # affect row a second time"); DO NOTHING keeps the first
        seen: dict = {}
        keep_rows = []
        for i, k in enumerate(in_keys):
            if k in seen:
                if oc.action == "update":
                    raise ExecError("ON CONFLICT DO UPDATE command cannot "
                                    "affect row a second time")
                continue
            seen[k] = i
            keep_rows.append(i)

        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
            c.register_txn(t.txid)
        try:
            # the SELECT leg: existing visible rows matching incoming keys
            from ..plan import exprs as E
            quals = self._key_quals(td, target, set(in_keys))
            plan = P.SeqScan(
                td, td.name, quals,
                [(f"{td.name}.{col.name}",
                  E.Col(f"{td.name}.{col.name}", col.type))
                 for col in td.columns])
            existing: dict = {}   # key tuple -> (row dict, null set)
            match_counts: dict = {}
            if td.distribution.dist_type == DistType.REPLICATED:
                dns = c.datanodes[:1]
            else:
                # the conflict target covers the dist key, so matching
                # rows can only live on the incoming rows' owner nodes —
                # no full fan-out on the OLTP path
                route_cols = {dc: np.asanyarray(
                    [0 if v is None else v for v in coldata[dc]])
                    for dc in td.distribution.dist_cols}
                owner = c.locator.route_rows(td, route_cols, n)
                dns = [c.datanodes[i] for i in sorted(set(owner.tolist()))]
            for dn in dns:
                # snapshot-gate: t.snapshot_ts
                hb = dn.exec_plan(plan, t.snapshot_ts, t.txid, {}, {})
                kcols = [hb.cols[f"{td.name}.{cn}"] for cn in target]
                for ri in range(hb.nrows):
                    k = tuple(kc[ri].item() if hasattr(kc[ri], "item")
                              else kc[ri] for kc in kcols)
                    if k in seen:
                        match_counts[k] = match_counts.get(k, 0) + 1
                        row = {cn: hb.cols[f"{td.name}.{cn}"][ri]
                               for cn in td.column_names}
                        nulls = {cn for cn in td.column_names
                                 if f"{td.name}.{cn}" in hb.nulls
                                 and hb.nulls[f"{td.name}.{cn}"][ri]}
                        existing[k] = (row, nulls)
            if oc.action == "update":
                # the arbiter must identify ONE row per key: a duplicate
                # match would be silently collapsed by delete+reinsert
                # (PostgreSQL requires a unique arbiter index for the
                # same reason)
                multi = [k for k, cnt in match_counts.items() if cnt > 1]
                if multi:
                    raise ExecError(
                        "ON CONFLICT DO UPDATE requires the conflict "
                        f"target to be unique; key {multi[0]!r} matches "
                        f"{match_counts[multi[0]]} rows")

            conflict_rows = [i for i in keep_rows
                             if in_keys[i] in existing]
            fresh_rows = [i for i in keep_rows
                          if in_keys[i] not in existing]

            inserted = updated = 0
            if fresh_rows:
                sub = {cn: [coldata[cn][i] for i in fresh_rows]
                       for cn in coldata}
                inserted = self._insert_rows(td, sub, len(fresh_rows))
            if conflict_rows and oc.action == "update":
                # the UPDATE leg: delete conflicting rows, re-insert with
                # assignments applied (MVCC update = delete + insert)
                ckeys = {in_keys[i] for i in conflict_rows}
                dquals = self._key_quals(td, target, ckeys)
                if not dquals:
                    raise ExecError("multi-column ON CONFLICT DO UPDATE "
                                    "is not supported yet")
                ddns = c.datanodes if td.distribution.dist_type == \
                    DistType.REPLICATED else dns
                for dn in ddns:
                    nd = dn.delete_where(td.name, dquals, t.snapshot_ts,
                                         t.txid)
                    if nd:
                        t.written_dns.add(dn.index)
                greg = gindex.indexes_on(c.catalog, td.name)
                if greg:
                    # drop the deleted rows' mapping entries BEFORE the
                    # replacement insert re-adds (and unique-checks) them
                    affected = {}
                    for gcol in greg:
                        ks = set()
                        for i in conflict_rows:
                            row, nulls = existing[in_keys[i]]
                            if gcol in nulls:
                                continue
                            v = row[gcol]
                            ks.add(v.item() if hasattr(v, "item") else v)
                        affected[gcol] = ks
                    gindex.resync_keys(self, td, affected, t)
                assigned = {cn: e for cn, e in oc.assignments}
                newdata: dict = {}
                for cn in td.column_names:
                    col = td.column(cn)
                    dec_carry = col.type.kind == TypeKind.DECIMAL
                    vals = []
                    for i in conflict_rows:
                        row, nulls = existing[in_keys[i]]
                        if cn in assigned:
                            vals.append(self._eval_upsert_assign(
                                assigned[cn], td, coldata, i, row, nulls))
                        elif cn in nulls:
                            vals.append(None)
                        else:
                            v = row[cn]
                            v = v.item() if hasattr(v, "item") else v
                            if dec_carry:
                                # carried DECIMALs are storage-scaled:
                                # exact decimal strings survive re-encode
                                # (and mix freely with None)
                                from ..storage.store import _decimal_str
                                v = _decimal_str(int(v), col.type.scale)
                            vals.append(v)
                    newdata[cn] = vals
                updated = self._insert_rows(td, newdata,
                                            len(conflict_rows))
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        return Result("INSERT", rowcount=inserted + updated)

    def _eval_upsert_assign(self, node: A.Node, td: TableDef,
                            coldata: dict, row_i: int, existing_row: dict,
                            existing_nulls: set):
        """DO UPDATE SET expression for one row: literals, excluded.col
        (the incoming row), or an existing column value."""
        if isinstance(node, A.Const):
            return node.value
        if isinstance(node, A.TypedConst) and node.type_name == "date":
            return node.value
        if isinstance(node, A.UnaryOp) and node.op == "-":
            v = self._eval_upsert_assign(node.arg, td, coldata, row_i,
                                         existing_row, existing_nulls)
            return None if v is None else -v
        if isinstance(node, A.ColRef):
            parts = node.parts
            if len(parts) == 2 and parts[0] == "excluded":
                return coldata[parts[1]][row_i]
            name = parts[-1]
            if td.has_column(name) and \
                    td.column(name).type.kind == TypeKind.DECIMAL:
                # existing DECIMAL values are storage-scaled; re-encoding
                # them as raw would double-scale — not supported yet
                raise ExecError("DO UPDATE SET from an existing DECIMAL "
                                "column is not supported; use "
                                "excluded.col or a literal")
            if name in existing_nulls:
                return None
            v = existing_row[name]
            return v.item() if hasattr(v, "item") else v
        raise ExecError("ON CONFLICT DO UPDATE supports literals, "
                        "excluded.col, and plain column references")

    def _run_check_query(self, sel: A.SelectStmt, t) -> list:
        """Constraint-validation SELECT inside txn `t` (cluster twin of
        the single-node session's helper).  Binds unmasked: constraint
        and trigger-image reads must see REAL values."""
        dp = self._plan_distributed(sel, txn=t, apply_masks=False)
        batch = DistExecutor(self.cluster, t.snapshot_ts, t.txid).run(dp)
        _, rows = materialize(batch, dp.output_names)
        return rows

    def _validate_write(self, table: str, t, kind: str = "insert"):
        from .constraints import (tables_needing_validation,
                                  validate_after_write)
        if not tables_needing_validation(self.cluster.catalog, table,
                                         kind):
            return
        validate_after_write(
            lambda sel: self._run_check_query(sel, t),
            self.cluster.catalog, table, kind)

    def _insert_rows(self, td: TableDef, coldata: dict, n: int,
                     fire_triggers: bool = True) -> int:
        from .constraints import check_not_null
        from .triggers import has_triggers
        check_not_null(td, coldata, n)
        c = self.cluster
        t, implicit = self._begin_implicit()
        if implicit:
            # expose the txn so nested writes (global-index maintenance)
            # join it instead of committing independently
            self.txn = t
        c.register_txn(t.txid)
        trig = fire_triggers and has_triggers(c.catalog, td.name,
                                              "insert")
        new_rows = colnames = None
        if trig:
            colnames = list(coldata)
            new_rows = [tuple(coldata[cn][i] for cn in colnames)
                        for i in range(n)]
        try:
            if trig:
                self._fire_triggers(t, implicit, td.name, "before",
                                    "insert", new_rows, None, colnames)
            if td.distribution.dist_type == DistType.REPLICATED:
                dests = {i: np.arange(n)
                         for i in range(c.ndn)}          # write everywhere
                sid = None
            else:
                route_cols = {}
                for cn in td.distribution.dist_cols:
                    vals = coldata[cn]
                    if not (isinstance(vals, np.ndarray)
                            and vals.dtype.kind != "O"):
                        # NULL dist keys route deterministically on a
                        # type-default fill (they can never be targeted
                        # by key equality anyway)
                        from ..catalog.types import TypeKind as _TK
                        fill = "" if td.column(cn).type.kind == _TK.TEXT \
                            else 0
                        vals = [fill if v is None else v for v in vals]
                    # asanyarray: the loader's _PreScaled decimal marker
                    # must survive into the locator's canonicalization
                    route_cols[cn] = np.asanyarray(vals)
                nodes = c.locator.route_rows(td, route_cols, n)
                sid = c.locator.shard_ids_for_rows(td, route_cols)
                dests = {i: np.nonzero(nodes == i)[0]
                         for i in set(nodes.tolist())}
            for dn_idx, idx in dests.items():
                if len(idx) == 0:
                    continue
                # ndarray fancy indexing preserves subclass markers
                # (loader._PreScaled decimals must not be re-scaled)
                sub = {cn: (coldata[cn][idx]
                            if isinstance(coldata[cn], np.ndarray)
                            else [coldata[cn][j] for j in idx])
                       for cn in coldata}
                sub_sid = sid[idx] if sid is not None else None
                c.datanodes[dn_idx].insert_raw(td.name, sub, len(idx),
                                               t.txid, sub_sid)
                t.written_dns.add(dn_idx)
            if sid is not None:
                from ..parallel import gindex
                if gindex.indexes_on(c.catalog, td.name):
                    try:
                        gindex.maintain_insert(self, td, coldata, n, sid,
                                               t)
                    except gindex.GIndexError as e:
                        raise ExecError(str(e)) from None
            self._validate_write(td.name, t)
            if trig:
                self._fire_triggers(t, implicit, td.name, "after",
                                    "insert", new_rows, None, colnames)
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        return n

    def _exec_delete(self, stmt: A.DeleteStmt,
                     fire_triggers: bool = True) -> Result:
        from ..parallel import gindex
        c = self.cluster
        if stmt.table in c.catalog.partitioned:
            return self._partition_dml_fanout(stmt)
        td = c.catalog.table(stmt.table)
        c.ensure_gdd()
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        c.register_txn(t.txid)
        binder = Binder(c.catalog)
        quals = []
        if stmt.where is not None:
            sel = A.SelectStmt(items=[A.SelectItem(A.Star())],
                               from_=[A.TableRef(stmt.table)],
                               where=stmt.where)
            quals = binder.bind_select(sel).where
        has_gidx = bool(gindex.indexes_on(c.catalog, td.name))
        from .triggers import has_triggers
        trig = fire_triggers and has_triggers(c.catalog, td.name,
                                              "delete")
        n_deleted = 0
        try:
            old_rows = None
            if trig:
                old_rows = self._old_rows(stmt.table, stmt.where, t)
                self._fire_triggers(t, implicit, td.name, "before",
                                    "delete", None, old_rows,
                                    td.column_names)
            affected = gindex.affected_keys(self, td, quals, t) \
                if has_gidx else None
            for dn in c.datanodes:
                nd = dn.delete_where(td.name, quals, t.snapshot_ts, t.txid)
                if nd:
                    t.written_dns.add(dn.index)
                n_deleted += nd
            if has_gidx and n_deleted:
                # mapping entries follow the base rows in the SAME txn
                gindex.resync_keys(self, td, affected, t)
            if n_deleted:
                self._validate_write(td.name, t, kind="delete")
            if trig and old_rows and n_deleted:
                self._fire_triggers(t, implicit, td.name, "after",
                                    "delete", None, old_rows,
                                    td.column_names)
        except Exception:
            if implicit:
                self.txn = None
                self._abort(t)
            raise
        if implicit:
            self.txn = None
            self._commit(t)
        # replicated deletes count each copy once
        if td.distribution.dist_type == DistType.REPLICATED and c.ndn:
            n_deleted //= c.ndn
        return Result("DELETE", rowcount=n_deleted)

    def _exec_update(self, stmt: A.UpdateStmt) -> Result:
        if stmt.table in self.cluster.catalog.partitioned:
            return self._partition_dml_fanout(stmt)
        td = self.cluster.catalog.table(stmt.table)
        assigned = {cn: e for cn, e in stmt.assignments}
        sel_items = [A.SelectItem(assigned.get(col.name,
                                               A.ColRef((col.name,))),
                                  alias=col.name)
                     for col in td.columns]
        sel = A.SelectStmt(items=sel_items,
                           from_=[A.TableRef(stmt.table)],
                           where=stmt.where)
        t, implicit = self._begin_implicit()
        if implicit:
            self.txn = t
        try:
            # lock target rows FIRST so concurrent updaters queue on the
            # row locks instead of optimistically racing the read-write
            # window (reference: heap_update taking the tuple lock before
            # constructing the new version) — this is what makes
            # concurrent increments lose zero updates
            c = self.cluster
            c.ensure_gdd()
            quals = []
            if stmt.where is not None:
                quals = Binder(c.catalog).bind_select(
                    A.SelectStmt(items=[A.SelectItem(A.Star())],
                                 from_=[A.TableRef(stmt.table)],
                                 where=stmt.where)).where
            for dn in c.datanodes:
                if dn.lock_where(td.name, quals, t.snapshot_ts,
                                 t.txid, False):
                    t.written_dns.add(dn.index)
            from .triggers import has_triggers
            trig = has_triggers(c.catalog, td.name, "update")
            if trig:
                # OLD images ride the same scan as NEW values: aligned
                sel = dataclasses.replace(sel, items=list(sel.items) + [
                    A.SelectItem(A.ColRef((col.name,)),
                                 alias="__old__" + col.name)
                    for col in td.columns])
            dp = self._plan_distributed(sel, apply_masks=False)
            batch = DistExecutor(
                self.cluster, t.snapshot_ts, t.txid,
                cancel_check=self._check_cancel).run(dp)
            names, rows = materialize(batch, dp.output_names)
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
                coldata = {cn: [r[i] for r in rows]
                           for i, cn in enumerate(names)}
                self._insert_rows(td, coldata, len(rows),
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

    def _exec_copy(self, stmt: A.CopyStmt) -> Result:
        td = self.cluster.catalog.table(stmt.table)
        delim = str(stmt.options.get("delimiter", "|"))
        if stmt.direction == "to":
            # gather the table through the normal distributed read path
            # and write it coordinator-side (reference: COPY OUT merge,
            # execRemote.c DataNodeCopyOut)
            from .session import copy_rows_to_file, copy_to_select
            cols = stmt.columns or td.column_names
            rows = self._exec_select(copy_to_select(stmt.table,
                                                    cols)).rows
            n = copy_rows_to_file(stmt.filename, rows, delim)
            return Result("COPY", rowcount=n)
        cols = stmt.columns or td.column_names
        from ..storage.loader import load_tbl
        coldata = load_tbl(stmt.filename, td, cols, delim)
        n = len(next(iter(coldata.values())))
        if stmt.table in self.cluster.catalog.partitioned:
            return dataclasses.replace(
                self._insert_partitioned(stmt.table, coldata, n),
                command="COPY")
        self._check_partition_bound(stmt.table, coldata, n)
        n = self._insert_rows(td, coldata, n)
        return Result("COPY", rowcount=n)

    # ---- txn / utility ----
    def _exec_txn(self, stmt: A.TxnStmt) -> Result:
        if stmt.op == "begin":
            if self.txn is None:
                self.txn = ClusterTxn(self.cluster.gtm.next_txid(),
                                      self.cluster.gtm.next_gts())
                self.txn.explicit = True
                self.txn_aborted = False
                self.cluster.register_txn(self.txn.txid)
            return Result("BEGIN")
        if stmt.op == "commit":
            if self.txn is not None:
                if self.txn_aborted:
                    # COMMIT of an aborted txn rolls back (PG); the
                    # abort already ran at error time unless savepoints
                    # kept the txn alive for a possible ROLLBACK TO
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
        t, _ = self._begin_implicit()
        dp = self._plan_distributed(stmt.stmt, txn=t)
        lines = []
        if dp.via_gidx:
            lines.append(f"Global Index Route via {dp.via_gidx} "
                         f"-> dn{dp.fqs_node}")
        elif dp.fqs_node is not None:
            lines.append(f"Fast Query Shipping -> dn{dp.fqs_node}")
        for frag in reversed(dp.fragments):
            loc = "CN" if frag.index == dp.top_fragment \
                and dp.fqs_node is None else \
                (f"dn{dp.fqs_node}" if dp.fqs_node is not None
                 else "all DNs")
            lines.append(f"Fragment {frag.index} [{loc}]:")
            lines.append(P.explain(frag.plan))
        for ex in dp.exchanges:
            lines.append(f"Exchange {ex.index}: {ex.kind} "
                         f"(from fragment {ex.source_fragment})")
        text = "\n".join(lines)
        if stmt.analyze:
            t0 = time.perf_counter()
            _, ex, dp2 = self._exec_select(stmt.stmt, instrument=True)
            total = (time.perf_counter() - t0) * 1e3
            # re-render the fragment plans with per-fragment actuals on
            # the fragment ROOT nodes (DN fragments execute whole — the
            # reference ships per-fragment instrumentation DN->CN, not
            # per plan node; commands/explain_dist.c)
            agg: dict = {}
            for (fidx, where), st in ex.stats.items():
                a = agg.setdefault(fidx, {"rows": 0, "ms": 0.0})
                a["rows"] += int(st["rows"])
                a["ms"] = max(a["ms"], float(st["ms"]))
            roots = {id(f.plan): f.index for f in dp2.fragments}

            def ann(nd):
                st = agg.get(roots.get(id(nd)))
                if st is None:
                    return ""
                return (f" (actual rows={st['rows']} "
                        f"time={st['ms']:.2f} ms)")

            lines2 = []
            if dp2.via_gidx:
                lines2.append(f"Global Index Route via {dp2.via_gidx} "
                              f"-> dn{dp2.fqs_node}")
            elif dp2.fqs_node is not None:
                lines2.append(f"Fast Query Shipping -> dn{dp2.fqs_node}")
            for frag in reversed(dp2.fragments):
                loc = "CN" if frag.index == dp2.top_fragment \
                    and dp2.fqs_node is None else \
                    (f"dn{dp2.fqs_node}" if dp2.fqs_node is not None
                     else "all DNs")
                lines2.append(f"Fragment {frag.index} [{loc}]:")
                lines2.append(P.explain(frag.plan, annotate=ann))
            for ex_ in dp2.exchanges:
                lines2.append(f"Exchange {ex_.index}: {ex_.kind} "
                              f"(from fragment {ex_.source_fragment})")
            text = "\n".join(lines2)
            # the data plane that actually carried the query + why the
            # device tier declined, if it did (reference: FN vs PQ
            # protocol choice surfaced per fragment)
            text += f"\nData Plane: {ex.tier}"
            if ex.tier != "mesh" and ex.fallback_reason:
                text += f" (mesh fallback: {ex.fallback_reason})"
            # per-fragment DN instrumentation shipped back to the CN
            # (reference: commands/explain_dist.c)
            for (fidx, where), st in sorted(
                    ex.stats.items(),
                    key=lambda kv: (kv[0][0], str(kv[0][1]))):
                loc = "CN" if where == "cn" else \
                    ("mesh" if where == "mesh" else f"dn{where}")
                text += (f"\n  Fragment {fidx} @ {loc}: "
                         f"rows={st['rows']} time={st['ms']:.2f} ms")
            text += _trace_explain_lines()
            text += f"\nExecution Time: {total:.2f} ms"
        return Result("EXPLAIN", names=["QUERY PLAN"],
                      rows=[(ln,) for ln in text.split("\n")], text=text)

    def _exec_direct(self, stmt: A.ExecuteDirectStmt) -> Result:
        """EXECUTE DIRECT ON (node) 'sql' — run a statement on one
        datanode (reference: ExecDirectType, pgxc/planner.h:65-75)."""
        name = stmt.node
        dn = None
        for dnode in self.cluster.datanodes:
            if f"dn{dnode.index}" == name:
                dn = dnode
                break
        if dn is None:
            raise ExecError(f"unknown node {name!r}")
        inner = parse_sql(stmt.sql)
        if len(inner) != 1 or not isinstance(inner[0], A.SelectStmt):
            raise ExecError("EXECUTE DIRECT supports a single SELECT")
        binder = Binder(self.cluster.catalog)
        bq = binder.bind_select(inner[0])
        planned = Planner(self.cluster.catalog).plan(bq)
        if planned.init_plans:
            raise ExecError("EXECUTE DIRECT does not support subqueries")
        t, _ = self._begin_implicit()
        from .dist import _to_device
        # snapshot-gate: t.snapshot_ts
        hb = dn.exec_plan(planned.plan, t.snapshot_ts, t.txid, {}, {})
        names, rows = materialize(_to_device(hb), planned.output_names)
        return Result("SELECT", names=names, rows=rows, rowcount=len(rows))
