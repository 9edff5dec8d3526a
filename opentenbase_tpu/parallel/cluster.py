"""Cluster: N datanodes + GTM + coordinator-side metadata.

Reference analog: the CN/DN/GTM topology (README.md:10-14) with node
management (pgxc/nodemgr), the shard map, and the 2PC machinery
(execRemote.c pgxc_node_remote_prepare/commit, clean2pc.c).  In-process
form: each DataNode owns its stores/WAL/device-cache; the multi-process
form (net/dn_server.py) wraps the same DataNode behind a TCP protocol.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from ..catalog.catalog import Catalog
from ..catalog.schema import DistType, NodeDef, TableDef
from ..catalog.types import TypeKind
from ..exec.executor import DeviceTableCache
from ..gtm.server import GtmCore
from ..parallel.locator import Locator
from ..storage.lockmgr import LockNotAvailable
from ..storage.store import (SerializationConflict, TableStore,
                             WriteConflict)
from ..storage.wal import Wal, checkpoint_store, restore_store
from ..utils.faultinject import fault_point
from ..utils import locks, snapcheck


class DataNode:
    """One datanode: table stores + WAL + device cache + executor service.

    (reference: a DN postgres instance.)  The public service surface —
    ddl_create/ddl_drop, insert_raw, delete_where, exec_plan,
    prepare/commit/abort, checkpoint_node — is everything the coordinator
    uses; net/dn_server.py exposes exactly these methods over TCP so the
    in-process and multi-process deployments share one code path."""

    def __init__(self, index: int, datadir: Optional[str] = None):
        from ..storage.lockmgr import LockManager
        self.index = index
        self.stores: dict[str, TableStore] = {}
        self.cache = DeviceTableCache()
        self.datadir = datadir
        self.wal: Optional[Wal] = None
        self.txn_spans: dict[int, list] = {}  # txid -> [(kind, table, span)]
        # gid -> (txid, prepared_at): live prepared txns awaiting their
        # verdict.  The resolver sweeps these to catch the window where
        # DNs prepared but the GTM was never told (coordinator death at
        # REMOTE_PREPARE_AFTER_SEND) — presumed abort after a grace
        # period, exactly the reference's clean2pc rule.
        self.prepared_gids: dict[str, tuple] = {}
        # row-lock waits + wait-for edges (storage/lockmgr.py)
        self.lockmgr = LockManager()
        self.lock_timeout = 10.0
        # logical decoding hook (storage/logical.py LogicalDecoder),
        # attached by a LogicalPublisher
        self.decoder = None
        # streaming replication (storage/replication.py WalShip); set via
        # attach_standby BEFORE open_wal
        self._ship = None
        # GTS high-water mark: newest commit ts applied on this node —
        # checkpointed to hwm.json so a hot standby seeds caught-up
        self.last_commit_ts = 0
        if datadir:
            os.makedirs(datadir, exist_ok=True)

    def attach_standby(self, host: str, port: int,
                       sync: bool = True) -> None:
        """Start shipping WAL + checkpoints to a DnStandbyServer
        (reference: walsender registration).  Seeds the standby with the
        current checkpoint artifacts so it can catch up mid-life.
        Called again for another standby, shipping fans out — N hot
        standby read replicas each receive the full stream."""
        from ..storage.replication import FanoutShip, WalShip
        ship = WalShip(host, port)
        if self._ship is None:
            self._ship = ship
        elif isinstance(self._ship, FanoutShip):
            self._ship.add(ship)
        else:
            self._ship = FanoutShip([self._ship, ship])
        self._sync_standby = sync
        if self.datadir:
            # base backup: checkpoint ships its artifacts itself now
            # that _ship is set (snapshot + empty WAL on the standby)
            self.checkpoint(None)
        if self.wal is not None:
            self.wal._ship = self._ship.frame
            self.wal._sync_ship = sync

    # ---- service surface -------------------------------------------------
    @staticmethod
    def _unlogged(table: str) -> bool:
        """System stat views are UNLOGGED relations (PG concept): rebuilt
        on read, never WAL'd — a monitoring loop must not grow the WAL."""
        return table.startswith("otb_")

    def ddl_create(self, td: TableDef):
        if td.name not in self.stores:
            self.stores[td.name] = TableStore(td)
            if not self._unlogged(td.name):
                self.log({"op": "create_table", "table": td.to_json()})

    def ddl_drop(self, name: str):
        st = self.stores.pop(name, None)
        if st is not None:
            self.cache.invalidate(st)
            from ..storage import codec
            codec.invalidate_ladder(name)
        if not self._unlogged(name):
            self.log({"op": "drop_table", "name": name})

    def insert_raw(self, table: str, coldata: dict, n: int, txid: int,
                   shardids=None) -> int:
        """Insert raw (unencoded) values; encoding happens node-side where
        the dictionaries live.  Python None entries become NULLs."""
        from ..exec.session import _text_log_array
        st = self.stores[table]
        td = st.td
        clean, masks = {}, {}
        for cn, vals in coldata.items():
            cv, m = st.split_nulls(cn, vals)
            clean[cn] = cv
            if m is not None:
                masks[cn] = m
        enc = {cn: st.encode_column(cn, vals)
               for cn, vals in clean.items()}
        if not self._unlogged(table):
            rec = {"op": "insert", "table": table, "n": n,
                   "txid": txid, "shardids": shardids,
                   "columns": {cn: (_text_log_array(v)
                                    if td.column(cn).type.kind
                                    == TypeKind.TEXT
                                    else np.asarray(enc[cn]))
                               for cn, v in clean.items()}}
            if masks:
                rec["nulls"] = masks
            self.log(rec)
        spans = st.insert(enc, n, txid, shardids=shardids,
                          nulls=masks or None)
        self.txn_spans.setdefault(txid, []).append(("ins", table, spans))
        if self.decoder is not None and not self._unlogged(table):
            self.decoder.on_insert(table, st, enc, masks, n, txid)
        return n

    def _target_masks(self, table: str, quals: list, snapshot_ts: int,
                      txid: int) -> list:
        from ..exec.expr_compile import compile_pred, host_chunk_env
        st = self.stores[table]
        out = []
        for ci, ch in st.scan_chunks():
            mask = st.visible_mask(ch, snapshot_ts, txid)
            if quals:
                env, nullable = host_chunk_env(table, ch)
                dicts = {f"{table}.{k}": d for k, d in st.dicts.items()}
                for q in quals:
                    mask = mask & np.asarray(
                        compile_pred(q, dicts, nullable)(env))
            if mask.any():
                out.append((ci, ch, mask))
        return out

    def _await_holder(self, holder: int, waiter: int):
        """Block until the conflicting txn resolves (reference:
        XactLockTableWait).  Committed holder -> the targeted row
        version is gone: serialization conflict (the CN retries
        implicit statements with a fresh snapshot).  Aborted -> caller
        simply retries the marking pass."""
        v = self.lockmgr.verdict(holder)
        if v is None:
            v = self.lockmgr.wait_for(holder, waiter,
                                      self.lock_timeout)
        if v == "committed":
            raise SerializationConflict(
                "could not serialize access due to concurrent "
                f"update (txn {holder} committed first)")

    def delete_where(self, table: str, quals: list, snapshot_ts: int,
                     txid: int) -> int:
        """Mark matching rows deleted; a write-write conflict WAITS for
        the holder (reference: heap_delete blocking on the updater xid)
        then retries — first-deleter-wins only applies between two
        still-in-progress transactions racing the same mark."""
        st = self.stores[table]
        while True:
            targets = self._target_masks(table, quals, snapshot_ts,
                                         txid)
            marked = []
            try:
                for ci, ch, mask in targets:
                    marked.append((st.mark_delete(ci, mask, txid),
                                   ci, ch, mask))
            except WriteConflict as e:
                # atomic statement retry: revert THIS pass's marks so
                # the decoder/WAL never see a half-marked statement
                st.revert_delete([sp for sp, _ci, _ch, _m in marked])
                self._await_holder(e.holder, txid)
                continue
            n_deleted = 0
            for span, ci, ch, mask in marked:
                if self.decoder is not None and \
                        not self._unlogged(table):
                    self.decoder.on_delete(table, st, ch, mask, txid)
                self.txn_spans.setdefault(txid, []).append(
                    ("del", table, span))
                self.log({"op": "delete", "table": table, "chunk": ci,
                          "mask": mask, "txid": txid})
                n_deleted += int(mask.sum())
            return n_deleted

    def lock_where(self, table: str, quals: list, snapshot_ts: int,
                   txid: int, nowait: bool = False) -> int:
        """SELECT ... FOR UPDATE: exclusive row locks, held to txn end
        (reference: heap_lock_tuple / LockRows node).  Locks are
        transient (not WAL'd) — a crash aborts the holder anyway."""
        st = self.stores[table]
        while True:
            targets = self._target_masks(table, quals, snapshot_ts,
                                         txid)
            locked = []
            try:
                for ci, _ch, mask in targets:
                    locked.append(st.lock_rows(ci, mask, txid))
            except WriteConflict as e:
                st.clear_locks(locked)
                if nowait:
                    raise LockNotAvailable(
                        "could not obtain lock on row "
                        f"(held by txn {e.holder})") from None
                self._await_holder(e.holder, txid)
                continue
            n = 0
            for span in locked:
                self.txn_spans.setdefault(txid, []).append(
                    ("lock", table, span))
                n += len(span[1])
            return n

    def exec_plan_device(self, plan, snapshot_ts: int, txid: int,
                         params: dict, sources: dict):
        """In-process fast path: run a fragment and return the device
        batch directly (no host materialization) — used for FQS where the
        coordinator and datanode share the process.

        A '__work_mem_rows' pseudo-param (the reference ships work_mem
        inside every RemoteStmt, include/pgxc/execRemote.h) activates
        the spill tier for this fragment: scans larger than the budget
        execute as multi-pass slab/grace plans instead of staging whole
        tables to device HBM."""
        from ..exec.dist import _bind_sources_host
        from ..exec.executor import ExecContext, Executor
        params = dict(params)
        wm = params.pop("__work_mem_rows", None)
        bound = _bind_sources_host(plan, sources)
        if wm:
            from ..exec.spill import SpillDriver
            drv = SpillDriver(self.stores, self.cache, snapshot_ts,
                              txid, int(wm[0]), params=params)
            out = drv.try_run_plan(bound)
            if out is not None:
                self.last_spill_passes = drv.passes
                return out
        ctx = ExecContext(self.stores, snapshot_ts, txid, self.cache,
                          params=params)
        return Executor(ctx).exec_node(bound)

    def alter_table(self, rec: dict) -> None:
        """Apply an ALTER TABLE action to this node's store + WAL
        (reference: the DDL fan-out executing ATExecCmd per node)."""
        from ..exec.session import replay_alter
        replay_alter(None, self.stores, rec)
        self.log({"op": "alter_table", **rec}, sync=True)
        target = rec["new_name"] if rec["action"] == "rename_table" \
            else rec["table"]
        st = self.stores.get(target)
        if st is not None:
            self.cache.invalidate(st)

    # snapshot-gate: snapshot_ts
    # (visibility happens below: the executor filters MVCC system
    # columns against this snapshot on every scan)
    def exec_plan(self, plan, snapshot_ts: int, txid: int,
                  params: dict, sources: dict):
        """Run a plan fragment against this node's stores; exchange inputs
        arrive as HostBatches keyed by exchange index."""
        from ..exec.dist import _to_host
        return _to_host(self.exec_plan_device(plan, snapshot_ts, txid,
                                              params, sources))

    def build_ann_index(self, table: str, col: str, lists: int = 0,
                        metric: str = "l2", nprobe: int = 0) -> int:
        """Build an IVFFlat index over a VECTOR column on this node."""
        return self.stores[table].build_ann_index(col, lists, metric,
                                                  nprobe)

    def build_hnsw_index(self, table: str, col: str, m: int = 16,
                         ef_construction: int = 64,
                         metric: str = "l2") -> int:
        """Build an HNSW graph over a VECTOR column on this node."""
        return self.stores[table].build_hnsw_index(col, m,
                                                   ef_construction,
                                                   metric)

    def analyze_table(self, table: str) -> dict:
        """Per-shard statistics for ANALYZE (reference: analyze.c run on
        each DN, merged at the CN)."""
        from .statistics import analyze_store
        return analyze_store(self.stores[table])

    def extract_shards(self, table: str, shard_ids: list, txid: int):
        """Online shard movement, source side (reference: the COPY-based
        data pull of pgxc/locator/redistrib.c): atomically read the live
        rows of the given shard groups AND mark them deleted under
        `txid` — one op so the rows read are exactly the rows deleted.
        The txn's 2PC commit/abort finalizes or reverts the deletion."""
        st = self.stores.get(table)
        if st is None:
            return {"columns": {}, "shardids": None, "n": 0}
        ext = st.rows_of_shards(set(int(s) for s in shard_ids))
        for ci, mask in ext.pop("masks"):
            if mask.any():
                span = st.mark_delete(ci, mask, txid)
                self.txn_spans.setdefault(txid, []).append(
                    ("del", table, span))
                self.log({"op": "delete", "table": table, "chunk": ci,
                          "mask": mask, "txid": txid})
        return ext

    def build_btree_index(self, table: str, cols: list) -> int:
        """Build btree-equivalent sorted indexes on this node's shard."""
        total = 0
        for col in cols:
            total += self.stores[table].build_btree_index(col)
        return total

    def truncate(self, table: str):
        """Non-MVCC bulk clear (reference: ExecuteTruncate's
        relfilenode swap); WAL-logged so recovery replays it in order
        against earlier inserts.  Refused while ANY transaction holds
        positional spans on this node — emptying the chunk list would
        crash their commit backfill (same rule as vacuum)."""
        st = self.stores.get(table)
        if st is None:
            return 0
        if self.txn_spans:
            raise RuntimeError(
                "cannot truncate: in-flight transactions hold row "
                "spans on this node")
        st.truncate()
        self.cache.invalidate(st)
        self.log({"op": "truncate", "table": table}, sync=True)
        return 0

    def inflight(self) -> bool:
        """Any transaction currently holding positional spans here."""
        return bool(self.txn_spans)

    def savepoint_mark(self, txid: int) -> int:
        """Current position in this txn's op list (reference:
        subxact start, xact.c DefineSavepoint)."""
        return len(self.txn_spans.get(txid, []))

    def rollback_to_mark(self, txid: int, keep: int):
        """Revert this txn's ops past `keep` (reference: subxact
        abort).  The WAL subabort record carries the count of
        WAL-VISIBLE ops kept (locks are never logged)."""
        ops = self.txn_spans.get(txid, [])
        undo = ops[keep:]
        del ops[keep:]
        wal_keep = sum(1 for kind, _t, _s in ops if kind != "lock")
        logged = False
        for kind, table, sp in reversed(undo):
            st = self.stores.get(table)
            if st is None:
                continue
            if kind == "ins":
                st.abort_insert(sp)
                logged = True
            elif kind == "lock":
                st.clear_locks([sp])
            else:
                st.revert_delete([sp])
                logged = True
        if logged:
            self.log({"op": "subabort", "txid": txid,
                      "keep": wal_keep})

    def vacuum(self, table, cutoff: int) -> int:
        """Compact dead rows.  Refuses (-1) while any txn holds positional
        spans on this node — compaction would shift the rows they
        reference.  Checkpoints afterwards: WAL records must never be
        replayed across a compaction (chunk offsets shift)."""
        if self.txn_spans:
            return -1
        total = 0
        for name, st in self.stores.items():
            if table and name != table:
                continue
            total += st.vacuum(cutoff)
            self.cache.invalidate(st)
        if total:
            self.checkpoint(None)
        return total

    def prepare(self, gid: str, txid: int):
        self.log({"op": "prepare", "gid": gid, "txid": txid}, sync=True)
        self.prepared_gids[gid] = (txid, time.monotonic())

    def _forget_prepared(self, txid: int):
        for g, (t, _) in list(self.prepared_gids.items()):
            if t == txid:
                del self.prepared_gids[g]

    def prepared_txns(self) -> dict:
        """Live prepared-but-undecided txns: gid -> {txid, age_s}
        (resolver surface; reference: pg_prepared_xacts per node)."""
        now = time.monotonic()
        return {g: {"txid": t, "age_s": now - at}
                for g, (t, at) in self.prepared_gids.items()}

    def commit(self, txid: int, ts: int):
        self.log({"op": "commit", "txid": txid, "ts": int(ts)}, sync=True)
        self.last_commit_ts = max(self.last_commit_ts, int(ts))
        self._forget_prepared(txid)
        touched: dict = {}
        for kind, table, sp in self.txn_spans.pop(txid, []):
            st = self.stores.get(table)
            if st is None:
                continue
            if kind == "ins":
                st.backfill_insert(sp, np.int64(ts))
            elif kind == "lock":
                st.clear_locks([sp])
            else:
                st.backfill_delete([sp], np.int64(ts))
            if kind != "lock":
                touched[table] = st
        if snapcheck.history_on() and touched:
            # SI history: one write event per DN commit, table names
            # DN-qualified — same-named stores on different DNs have
            # independent version sequences and must not alias
            snapcheck.note_write(
                txid, ts, {f"dn{self.index}.{t}": st.version
                           for t, st in touched.items()})
        if self.decoder is not None:
            self.decoder.on_commit(txid, ts)
        # wake lock waiters LAST: they retry against settled state
        self.lockmgr.resolve(txid, committed=True)

    def abort(self, txid: int):
        ops = self.txn_spans.pop(txid, [])
        self._forget_prepared(txid)
        if ops:
            self.log({"op": "abort", "txid": txid})
        for kind, table, sp in ops:
            st = self.stores.get(table)
            if st is None:
                continue
            if kind == "ins":
                st.abort_insert(sp)
            elif kind == "lock":
                st.clear_locks([sp])
            else:
                st.revert_delete([sp])
        if self.decoder is not None:
            self.decoder.on_abort(txid)
        self.lockmgr.resolve(txid, committed=False)

    def wrote_in(self, txid: int) -> bool:
        return bool(self.txn_spans.get(txid))

    # ---- infrastructure --------------------------------------------------

    def open_wal(self):
        if self.datadir:
            self.wal = Wal(os.path.join(self.datadir, "wal.log"),
                           ship=self._ship.frame if self._ship else None,
                           sync_ship=getattr(self, "_sync_standby", True))

    def log(self, rec: dict, sync: bool = False):
        if self.wal:
            self.wal.append(rec, sync=sync)

    # ---- recovery (driven by the cluster, which owns the catalog) ----
    def load_checkpoint(self, catalog: Catalog):
        """Rebuild stores from the catalog's tables + on-disk .ckpt
        snapshots — the first half of recovery, also the hot standby's
        base-backup load (storage/replication.py HotStandby)."""
        for name, td in catalog.tables.items():
            st = TableStore(td)
            ckpt = os.path.join(self.datadir, f"{name}.ckpt")
            if os.path.exists(ckpt):
                restore_store(st, ckpt)
                # a checkpoint older than an ALTER .. ADD COLUMN lacks
                # the column's arrays; reconcile to the catalog schema
                # (idempotent per-chunk fill)
                for c in td.columns:
                    st.alter_add_column(c)
            self.stores[name] = st

    def apply_record(self, rec: dict, pending: dict, gid_of: dict):
        """Apply ONE replayed WAL record against the live stores.
        Shared by crash recovery (`recover`) and the hot standby's
        incremental apply (storage/replication.py HotStandby): a hot
        standby IS recovery running continuously, one shipped frame at
        a time, with `pending`/`gid_of` carried across frames instead
        of resolved at the end."""
        op = rec.get("op")
        if op == "create_table":
            # recover() pre-builds stores from the catalog, so this is
            # a no-op there; the standby sees DDL only through the WAL
            td = TableDef.from_json(rec["table"])
            if td.name not in self.stores:
                self.stores[td.name] = TableStore(td)
        elif op == "drop_table":
            st = self.stores.pop(rec["name"], None)
            if st is not None:
                self.cache.invalidate(st)
        elif op == "insert":
            st = self.stores.get(rec["table"])
            if st is None:   # table dropped after this record
                return
            enc = {}
            for cname, v in rec["columns"].items():
                if not st.td.has_column(cname):
                    continue   # column dropped after this record
                arr = np.asarray(v)
                if arr.dtype.kind == "S":
                    enc[cname] = st.encode_column(cname, arr)
                elif arr.dtype.kind in "UO":
                    enc[cname] = st.encode_column(cname, list(arr))
                else:
                    enc[cname] = arr.astype(
                        st.td.column(cname).type.np_dtype)
            from ..exec.session import conform_replay_columns
            enc, rnulls = conform_replay_columns(
                st, enc, rec["n"], rec.get("nulls"))
            spans = st.insert(enc, rec["n"], rec["txid"],
                              shardids=rec.get("shardids"),
                              nulls=rnulls)
            pending.setdefault(rec["txid"], []).append(
                ("ins", st, spans))
        elif op == "delete":
            st = self.stores.get(rec["table"])
            if st is None:
                return
            span = st.mark_delete(rec["chunk"], np.asarray(rec["mask"]),
                                  rec["txid"])
            pending.setdefault(rec["txid"], []).append(
                ("del", st, span))
        elif op == "alter_table":
            from ..exec.session import replay_alter
            replay_alter(None, self.stores, rec)
        elif op == "truncate":
            st = self.stores.get(rec["table"])
            if st is not None:
                st.truncate()
        elif op == "subabort":
            lst = pending.get(rec["txid"], [])
            undo = lst[rec["keep"]:]
            del lst[rec["keep"]:]
            for kind, st, sp in undo:
                if kind == "ins":
                    st.abort_insert(sp)
                else:
                    st.revert_delete([sp])
        elif op == "prepare":
            gid_of[rec["txid"]] = rec["gid"]
        elif op == "commit":
            ts = np.int64(rec["ts"])
            self.last_commit_ts = max(self.last_commit_ts,
                                      int(rec["ts"]))
            for kind, st, sp in pending.pop(rec["txid"], []):
                (st.backfill_insert if kind == "ins"
                 else lambda s, t_: st.backfill_delete([s], t_))(sp, ts)
            gid_of.pop(rec["txid"], None)
        elif op == "abort":
            for kind, st, sp in pending.pop(rec["txid"], []):
                if kind == "ins":
                    st.abort_insert(sp)
                else:
                    st.revert_delete([sp])
            gid_of.pop(rec["txid"], None)

    def recover(self, catalog: Catalog, gtm: GtmCore):
        self.load_checkpoint(catalog)
        pending: dict[int, list] = {}
        gid_of: dict[int, str] = {}
        walpath = os.path.join(self.datadir, "wal.log")
        max_txid = 0
        for rec in Wal.replay(walpath):
            if "txid" in rec:
                max_txid = max(max_txid, rec["txid"])
            self.apply_record(rec, pending, gid_of)
        # in-doubt resolution: prepared but no commit/abort record — ask
        # the GTM for the verdict (reference: clean2pc workers + pg_clean)
        for txid, ops in list(pending.items()):
            gid = gid_of.get(txid)
            verdict = gtm.txn_verdict(gid) if gid else "unknown"
            if gid and verdict == "committed":
                ts = np.int64(gtm.prepared_list()[gid]["commit_ts"])
                for kind, st, sp in ops:
                    if kind == "ins":
                        st.backfill_insert(sp, ts)
                    else:
                        st.backfill_delete([sp], ts)
                self.log({"op": "commit", "txid": txid, "ts": int(ts)},
                         sync=True)
            else:
                # never prepared, or prepared-but-undecided with the
                # coordinator gone: presumed abort
                for kind, st, sp in ops:
                    if kind == "ins":
                        st.abort_insert(sp)
                    else:
                        st.revert_delete([sp])
                self.log({"op": "abort", "txid": txid})
            pending.pop(txid)
        return max_txid

    def checkpoint(self, catalog: Catalog):
        if not self.datadir:
            return
        for name, st in self.stores.items():
            checkpoint_store(st, os.path.join(self.datadir, f"{name}.ckpt"))
        # hot-standby sidecars: table schemas (a .ckpt has arrays, not a
        # TableDef) + the GTS high-water mark, so a replica rebuilt from
        # these artifacts is queryable and knows how fresh it is
        self._write_sidecar("schema.json", {
            name: st.td.to_json() for name, st in self.stores.items()})
        self._write_sidecar("hwm.json",
                            {"gts_hwm": int(self.last_commit_ts)})
        if self.wal:
            self.wal.truncate()
        if self._ship is not None:
            # the standby mirrors the truncation: snapshot + fresh log
            from ..storage.replication import checkpoint_files
            self._ship.checkpoint(checkpoint_files(self.datadir))

    def _write_sidecar(self, name: str, obj: dict) -> None:
        import json
        tmp = os.path.join(self.datadir, name + ".tmp")
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, os.path.join(self.datadir, name))

    # ---- restorable barriers (reference: the two-phase barrier WAL
    # records of pgxc/barrier/barrier.c:33-40 + PITR restore target) ----
    def create_barrier(self, name: str, gts: int):
        """Phase on this node: barrier_prepare WAL record -> full node
        checkpoint (seal + truncate keeps replay layouts consistent) ->
        retain the checkpoint artifacts under barriers/<name>/ ->
        barrier WAL record at the head of the fresh log."""
        import shutil
        if not self.datadir:
            raise RuntimeError("barriers require a datadir")
        if self.txn_spans:
            raise RuntimeError("transactions in flight")
        self.log({"op": "barrier_prepare", "name": name,
                  "gts": int(gts)}, sync=True)
        self.checkpoint(None)
        bdir = os.path.join(self.datadir, "barriers", name)
        os.makedirs(bdir, exist_ok=True)
        for tname in self.stores:
            src = os.path.join(self.datadir, f"{tname}.ckpt")
            if os.path.exists(src):
                shutil.copy2(src, os.path.join(bdir, f"{tname}.ckpt"))
        self.log({"op": "barrier", "name": name, "gts": int(gts)},
                 sync=True)

    def restore_barrier(self, name: str, tables: list):
        """Rebuild this node's state exactly as retained at the barrier:
        barrier artifacts become the current checkpoint, the WAL resets,
        all later history is discarded."""
        import shutil
        if not self.datadir:
            raise RuntimeError("barriers require a datadir")
        bdir = os.path.join(self.datadir, "barriers", name)
        if not os.path.isdir(bdir):
            raise RuntimeError(f"no barrier {name!r} on dn{self.index}")
        self.stores = {}
        self.cache = DeviceTableCache()
        self.txn_spans = {}
        # current checkpoints are replaced by the barrier's; stray ckpts
        # of tables created after the barrier are removed
        for fn in os.listdir(self.datadir):
            if fn.endswith(".ckpt"):
                os.remove(os.path.join(self.datadir, fn))
        for td in tables:
            st = TableStore(td)
            src = os.path.join(bdir, f"{td.name}.ckpt")
            if os.path.exists(src):
                shutil.copy2(src,
                             os.path.join(self.datadir, f"{td.name}.ckpt"))
                restore_store(st, src)
            self.stores[td.name] = st
        if self.wal:
            self.wal.truncate()
        self.log({"op": "barrier_restored", "name": name}, sync=True)


class Cluster:
    """The whole deployment: catalog + shard map + GTM + datanodes.
    Single-process 'mesh mode': datanodes are objects; multi-process mode
    swaps DataNode for a client stub (net/)."""

    def __init__(self, n_datanodes: int = 2,
                 datadir: Optional[str] = None):
        self.datadir = datadir
        self.catalog = Catalog()
        gtm_path = os.path.join(datadir, "gtm.json") if datadir else None
        if datadir:
            os.makedirs(datadir, exist_ok=True)
        self.gtm = GtmCore(gtm_path)
        catpath = os.path.join(datadir, "catalog.json") if datadir else None
        recovered = False
        if catpath and os.path.exists(catpath):
            self.catalog = Catalog.load(catpath)
            n_datanodes = max(len(self.catalog.datanodes()), 1)
            recovered = True
        else:
            for i in range(n_datanodes):
                self.catalog.register_node(
                    NodeDef(f"dn{i}", "datanode", index=i))
            self.catalog.register_node(NodeDef("cn0", "coordinator"))
            self.catalog.register_node(NodeDef("gtm0", "gtm"))
            self.catalog.build_default_shard_map(n_datanodes)
        self.datanodes = [
            DataNode(i, os.path.join(datadir, f"dn{i}") if datadir else None)
            for i in range(n_datanodes)]
        self.locator = Locator(self.catalog)
        self.active_txns: set[int] = set()
        # txids created by logical-replication apply on THIS cluster —
        # the decoder drops them so multi-active A<->B subscriptions do
        # not loop (reference: replication origins)
        self.replication_origin_txids: set[int] = set()
        self.gucs: dict[str, str] = {"enable_fast_query_shipping": "on"}
        for dn in self.datanodes:
            if recovered and dn.datadir:
                max_txid = dn.recover(self.catalog, self.gtm)
                self.gtm._txid = max(self.gtm._txid, max_txid)
            elif not recovered:
                for td in self.catalog.tables.values():
                    dn.stores[td.name] = TableStore(td)
            dn.open_wal()
        from . import statviews
        statviews.register(self)
        self._init_services()
        if recovered:
            self._warm_start()

    def _warm_start(self):
        """Background warmup after a restart: re-stage recovered tables
        into the device caches (MVCC columns at their size classes) so
        the first query pays neither host->device staging nor — with
        the persistent compilation cache — XLA compiles (ISSUE 1 AOT
        warmup; scheduled off the query path)."""
        from ..exec.plancache import warm_async

        def job():
            for dn in self.datanodes:
                if not hasattr(dn, "stores"):
                    continue          # remote DN: stages on first query
                for name, st in list(dn.stores.items()):
                    if name.startswith("otb_") or st.row_count() == 0:
                        continue
                    dn.cache.get(st, [c.name for c in st.td.columns])
        warm_async(job)

    def _init_services(self):
        import threading
        # serializes txn registration against non-MVCC bulk ops
        # (TRUNCATE): held across its precheck + fan-out so no txn can
        # begin mid-clear and refuse a later DN after earlier DNs were
        # irreversibly emptied
        self.ddl_mutex = locks.RLock("parallel.cluster.Cluster.ddl_mutex")
        from .maintenance import AuditLogger, ResourceQueue
        self._resqueue: Optional[ResourceQueue] = None
        self._resqueue_slots = 0
        audit_path = os.path.join(self.datadir, "audit.log") \
            if self.datadir else None
        self.audit = AuditLogger(audit_path)
        self._gdd = None
        self._monitor = None
        self._resolver = None
        # read-failover serialization: concurrent fragment threads that
        # all hit the same dead DN coalesce into ONE promotion
        self._failover_lock = locks.Lock("parallel.cluster.Cluster._failover_lock")
        self._promoted_at: dict[int, float] = {}
        # standby read scale-out (net/guard.py ReplicaRouter): per-DN
        # newest ACKNOWLEDGED commit ts — a replica whose hwm covers
        # this has applied everything this coordinator committed there,
        # so any snapshot this coordinator issues is servable on it
        self.dn_commit_hwm: dict[int, int] = {}
        from ..net.guard import ReplicaRouter
        self.read_router = ReplicaRouter(self)
        # restart survival: persisted catalog.jobs resume scheduling as
        # soon as the cluster initializes, not only on CREATE JOB
        from .jobs import resume_jobs
        resume_jobs(self)

    def ensure_gdd(self):
        """Start the cross-node deadlock detector on first DML that can
        wait (reference: the gdd worker is launched per cluster)."""
        if self._gdd is None:
            from .gdd import GddDetector
            self._gdd = GddDetector(self)
            self._gdd.start()
        return self._gdd

    def ensure_monitor(self, period: float = 2.0,
                       auto_failover: bool = False):
        """Start the liveness daemon feeding the health map consumed
        by otb_nodes (reference: clustermon.c + the node health map).
        With auto_failover, dead DNs with a registered standby are
        promoted automatically (pgxc_ctl failover, zero operator
        steps)."""
        if getattr(self, "_monitor", None) is None:
            from .monitor import ClusterMonitor
            self._monitor = ClusterMonitor(self, period,
                                           auto_failover=auto_failover)
            self._monitor.start()
        return self._monitor

    def resource_queue(self):
        """Admission-control queue per max_concurrent_queries GUC
        (reference: resource queues, commands/resqueue.c)."""
        from .maintenance import ResourceQueue
        raw = self.gucs.get("max_concurrent_queries", "")
        try:
            slots = int(raw)
        except ValueError:
            slots = 0
        if slots <= 0:
            return None
        if self._resqueue is None or self._resqueue_slots != slots:
            self._resqueue = ResourceQueue("default", slots)
            self._resqueue_slots = slots
        return self._resqueue

    @classmethod
    def connect(cls, catalog_path: str, dn_addrs: list[tuple],
                gtm_addr: tuple) -> "Cluster":
        """Multi-process mode: attach to running DN servers and GTM
        (reference: a CN joining the cluster via pgxc_node + pooler)."""
        from ..gtm.server import GtmClient
        from ..net.dn_server import RemoteDataNode
        self = object.__new__(cls)
        self.datadir = os.path.dirname(catalog_path) or "."
        self.catalog = Catalog.load(catalog_path) \
            if os.path.exists(catalog_path) else Catalog()
        if not self.catalog.datanodes():
            for i, (h, p) in enumerate(dn_addrs):
                self.catalog.register_node(
                    NodeDef(f"dn{i}", "datanode", host=h, port=p, index=i))
            self.catalog.build_default_shard_map(len(dn_addrs))
        self.gtm = GtmClient(*gtm_addr)
        self.datanodes = [RemoteDataNode(i, h, p)
                          for i, (h, p) in enumerate(dn_addrs)]
        self.locator = Locator(self.catalog)
        self.active_txns = set()
        self.replication_origin_txids = set()
        self.gucs = {"enable_fast_query_shipping": "on"}
        from . import statviews
        statviews.register(self)
        self._init_services()
        return self

    @property
    def ndn(self) -> int:
        return len(self.datanodes)

    # ---- DDL fan-out (reference: RemoteQuery EXEC_ON_ALL_NODES) ----
    def _save_catalog(self):
        if self.datadir:
            self.catalog.save(os.path.join(self.datadir, "catalog.json"))
        # multi-coordinator DDL sync: publish the new catalog
        # generation on the GTM so every other CN reloads before its
        # next statement (reference: CN DDL fan-out EXEC_ON_COORDS)
        if hasattr(self.gtm, "bump_catalog_gen"):
            try:
                self._seen_catalog_gen = self.gtm.bump_catalog_gen()
            except Exception:
                pass

    def maybe_sync_catalog(self, ttl_s: float = 0.25) -> bool:
        """Cheap per-statement staleness gate for multi-CN topologies:
        poll the GTM's catalog generation at most every `ttl_s` and
        reload the shared catalog when another coordinator changed it.
        Returns True when a reload happened."""
        if not hasattr(self.gtm, "bump_catalog_gen") or not self.datadir:
            return False
        import time as _t
        raw = self.gucs.get("catalog_sync_interval_ms", "")
        if raw:
            try:
                ttl_s = float(raw) / 1e3
            except ValueError:
                pass
        now = _t.monotonic()
        last = getattr(self, "_cat_checked", 0.0)
        if now - last < ttl_s:
            return False
        self._cat_checked = now
        try:
            gen = self.gtm.catalog_gen()
        except Exception:
            return False
        if gen == getattr(self, "_seen_catalog_gen", 0):
            return False
        self.reload_catalog()
        self._seen_catalog_gen = gen
        return True

    def reload_catalog(self):
        """Re-read the shared catalog (another CN's DDL or a failover
        changed it): rebuild locator + routing, refresh datanode
        proxies whose addresses moved, invalidate every plan cache."""
        path = os.path.join(self.datadir, "catalog.json")
        if not os.path.exists(path):
            return
        self.catalog = Catalog.load(path)
        self.locator = Locator(self.catalog)
        epochs = getattr(self, "_node_epochs", {})
        for nd in self.catalog.datanodes():
            if nd.index < len(self.datanodes):
                cur = self.datanodes[nd.index]
                addr = getattr(cur, "addr", None)
                # re-resolve on an address change OR an epoch bump: a
                # failover can reuse the old address, and warm pooled
                # sockets to the fenced primary must be dropped
                if addr is not None and nd.port and (
                        tuple(addr) != (nd.host, nd.port)
                        or epochs.get(nd.index, 0) != nd.epoch):
                    from ..net.dn_server import RemoteDataNode
                    try:
                        cur.close()
                    except Exception:
                        pass
                    self.datanodes[nd.index] = RemoteDataNode(
                        nd.index, nd.host, nd.port)
            epochs[nd.index] = nd.epoch
        self._node_epochs = epochs
        self.ddl_gen = getattr(self, "ddl_gen", 0) + 1
        from . import statviews
        statviews.register(self)

    # ---- standby registration + automatic failover (reference:
    # pgxc_ctl failover + pooler re-resolving primaries, nodemgr.c:80;
    # detection feeds from ClusterMonitor) ----
    def register_standby(self, dn_index: int, host: str = "",
                         port: int = 0, datadir: str = ""):
        """Record dn_index's standby in the shared catalog so the
        monitor can promote it without operator action."""
        for nd in self.catalog.datanodes():
            if nd.index == dn_index:
                nd.standby = {"host": host, "port": port,
                              "datadir": datadir}
                self._save_catalog()
                return
        raise KeyError(f"no datanode {dn_index}")

    def register_read_replica(self, dn_index: int, host: str,
                              port: int, datadir: str = ""):
        """Record a HOT standby of dn_index in the catalog as a read
        replica: the ReplicaRouter routes snapshot-covered read
        fragments there when GUC replica_reads=on (reference:
        hot_standby=on + a read-balancing pooler)."""
        for nd in self.catalog.datanodes():
            if nd.index == dn_index:
                if not nd.standbys:
                    nd.standbys = []
                nd.standbys.append({"host": host, "port": port,
                                    "datadir": datadir})
                self._save_catalog()
                self.read_router.invalidate()
                return
        raise KeyError(f"no datanode {dn_index}")

    def note_dn_commit(self, dn_index: int, ts: int) -> None:
        """Track the newest commit this coordinator ACKNOWLEDGED per DN
        — the replica router's freshness floor (a replica at or past it
        has every commit any snapshot from this coordinator can see)."""
        hwm = getattr(self, "dn_commit_hwm", None)
        if hwm is not None:
            hwm[dn_index] = max(hwm.get(dn_index, 0), int(ts))

    def auto_failover(self, dn_index: int):
        """Promote dn_index's registered standby and reroute: crash
        recovery over the standby's shipped directory, a fresh DN
        server over it, catalog address swap + epoch bump (fencing:
        supervisors must not resurrect the old address), and a catalog
        generation bump so every coordinator re-resolves."""
        nd = next(n for n in self.catalog.datanodes()
                  if n.index == dn_index)
        sb = nd.standby
        if not sb or not sb.get("datadir"):
            raise RuntimeError(f"dn{dn_index} has no registered "
                               "standby")
        cur = self.datanodes[dn_index]
        if hasattr(cur, "addr"):
            # TCP topology: host a fresh DN server over the recovered
            # standby directory (single-host deployment: DN servers
            # already live in the coordinator/supervisor process)
            from ..net.dn_server import DnServer, RemoteDataNode
            catalog_path = os.path.join(self.datadir, "catalog.json")
            srv = DnServer(dn_index, sb["datadir"], catalog_path,
                           gtm_addr=getattr(self.gtm, "addr", None))
            srv.start()
            # old-proxy teardown + fresh-server handshake do RPC while
            # the failover lock serializes promotion:
            # may-acquire: gtm.server.GtmClient._lock
            # may-acquire: net.dn_server.DnConnectionPool._lock
            # may-acquire: utils.faultinject._lock
            # the RPCs park at named wait points and graft/park remote
            # trace subtrees on reply:
            # may-acquire: obs.xray._WLOCK
            # may-acquire: obs.xray._RLOCK
            # may-acquire: obs.metrics.Registry._lock
            # may-acquire: obs.metrics.metric._lock
            try:
                cur.close()
            except Exception:
                pass
            self.datanodes[dn_index] = RemoteDataNode(
                dn_index, srv.host, srv.port)
            nd.host, nd.port = srv.host, srv.port
            promoted = srv
        else:
            promoted = self.promote_standby(dn_index, sb["datadir"])
        nd.epoch += 1
        nd.standby = None
        self._save_catalog()
        self.ddl_gen = getattr(self, "ddl_gen", 0) + 1
        from ..net.guard import note_failover
        note_failover("dn")
        self._promoted_at[dn_index] = time.monotonic()
        return promoted

    def failover_read(self, dn_index: int):
        """Re-resolve `dn_index` for a READ re-dispatch after a
        connection failure: promote its registered standby (threads
        racing on the same dead DN coalesce into one promotion) and
        return the replacement proxy, or None when no standby exists.
        Only safe for reads — an executor retries the fragment on the
        promoted node; writes go through 2PC + the resolver instead."""
        with self._failover_lock:
            nd = next((n for n in self.catalog.datanodes()
                       if n.index == dn_index), None)
            if nd is None:
                return None
            sb = nd.standby
            if sb and sb.get("datadir"):
                self.auto_failover(dn_index)
                return self.datanodes[dn_index]
            # no standby registered NOW — if a concurrent thread just
            # promoted one, the current proxy is already the successor
            if dn_index in self._promoted_at:
                return self.datanodes[dn_index]
            return None

    def create_table(self, td: TableDef, if_not_exists: bool = False):
        td = self.catalog.create_table(td, if_not_exists)
        for dn in self.datanodes:
            dn.ddl_create(td)
        self.ddl_gen = getattr(self, "ddl_gen", 0) + 1
        self._save_catalog()
        return td

    def drop_table(self, name: str, if_exists: bool = False):
        self.catalog.drop_table(name, if_exists)
        for dn in self.datanodes:
            dn.ddl_drop(name)
        # global indexes die with their base table: drop the mapping
        # tables and the registry entries, or a recreated table would
        # inherit stale routing and phantom unique violations
        for cinfo in self.catalog.global_indexes.pop(name, {}).values():
            mt = cinfo["map"]
            if mt in self.catalog.tables:
                self.catalog.drop_table(mt)
                for dn in self.datanodes:
                    dn.ddl_drop(mt)
        self.ddl_gen = getattr(self, "ddl_gen", 0) + 1
        self._save_catalog()

    def checkpoint(self) -> bool:
        if self.active_txns:
            return False
        if self.datadir:
            self.catalog.save(os.path.join(self.datadir, "catalog.json"))
        for dn in self.datanodes:
            dn.checkpoint(self.catalog)
        return True

    # ---- restorable barriers (reference: CREATE BARRIER two-phase WAL
    # records + consistent PITR, pgxc/barrier/barrier.c:33-40) ----
    def create_barrier(self, name: str) -> bool:
        """Cluster-wide restore point at one GTS.  Phase 1: every DN
        writes barrier_prepare + checkpoints + retains artifacts; phase
        2: the GTM registers the barrier — the registration is the
        commit point, so a crash mid-way leaves no half-barrier a
        restore could pick."""
        if self.active_txns:
            return False
        if not self.datadir:
            # in-memory deployment: a consistent checkpoint is all that
            # exists to retain
            return self.checkpoint()
        gts = int(self.gtm.next_gts())
        bdir = os.path.join(self.datadir, "barriers", name)
        os.makedirs(bdir, exist_ok=True)
        self.catalog.save(os.path.join(bdir, "catalog.json"))
        self.catalog.save(os.path.join(self.datadir, "catalog.json"))
        for dn in self.datanodes:
            dn.create_barrier(name, gts)
        self.gtm.barrier_create(name, gts)
        return True

    def restore_barrier(self, name: str):
        """Rebuild the whole cluster at the barrier: catalog + every
        datanode's stores revert; later history is discarded.  The GTM
        clock keeps running forward (timestamps are never reused)."""
        barriers = self.gtm.barrier_list()
        if name not in barriers:
            raise KeyError(f"barrier {name!r} is not registered")
        if not self.datadir:
            raise RuntimeError("restore requires a datadir deployment")
        bcat = os.path.join(self.datadir, "barriers", name, "catalog.json")
        if os.path.exists(bcat):
            self.catalog = Catalog.load(bcat)
            self.catalog.save(os.path.join(self.datadir, "catalog.json"))
        tables = list(self.catalog.tables.values())
        for dn in self.datanodes:
            dn.restore_barrier(name, tables)
        self.active_txns.clear()
        self.locator = Locator(self.catalog)
        self.ddl_gen = getattr(self, "ddl_gen", 0) + 1
        from . import statviews
        statviews.register(self)

    def register_txn(self, txid: int):
        """All txn registration funnels through here so bulk ops can
        exclude new txns by holding ddl_mutex."""
        with self.ddl_mutex:
            self.active_txns.add(txid)

    # ---- distributed commit (reference: execRemote.c
    # pgxc_node_remote_prepare :3944 / pgxc_node_remote_commit :4883) ----
    def commit_txn(self, txid: int, dns: Optional[list[int]] = None) -> int:
        """Commit on every datanode the txn wrote to; implicit 2PC when
        more than one.  The coordinator passes the participant list it
        tracked (one RPC per participant); falls back to polling wrote_in.
        Returns commit ts."""
        if dns is None:
            dns = [dn.index for dn in self.datanodes if dn.wrote_in(txid)]
        if len(dns) <= 1:
            ts = int(self.gtm.next_gts())
            for i in dns:
                self.datanodes[i].commit(txid, ts)
                self.note_dn_commit(i, ts)
            self.active_txns.discard(txid)
            self.replication_origin_txids.discard(txid)
            return ts

        # implicit 2PC
        gid = f"gxid_{txid}"
        fault_point("REMOTE_PREPARE_BEFORE_SEND")
        for i in dns:
            self.datanodes[i].prepare(gid, txid)
        fault_point("REMOTE_PREPARE_AFTER_SEND")
        self.gtm.prepare_txn(gid, [f"dn{i}" for i in dns], txid)
        fault_point("AFTER_GTM_PREPARE")
        ts = int(self.gtm.next_gts())
        self.gtm.commit_txn(gid, ts)
        fault_point("AFTER_GTM_COMMIT_BEFORE_DN")
        # past the GTM commit record the txn IS committed: a DN that
        # cannot take delivery right now does not un-commit it.  Keep
        # fanning out to the others, leave the gid registered, and let
        # the in-doubt resolver redeliver (reference: 2PC commit sends
        # are never retried inline — execRemote.c hands stragglers to
        # clean2pc).  Raw send failures are therefore survivable here.
        undelivered = []
        for k, i in enumerate(dns):
            if k == 1:
                fault_point("REMOTE_COMMIT_PARTIAL")
            try:
                self.datanodes[i].commit(txid, ts)
                self.note_dn_commit(i, ts)
            except (ConnectionError, OSError, EOFError):
                undelivered.append(i)
        fault_point("BEFORE_GTM_FORGET")
        if not undelivered:
            self.gtm.forget_txn(gid)
        self.active_txns.discard(txid)
        # the decoders have seen this commit by now: the origin tag has
        # served its purpose (bounded set, not a leak)
        self.replication_origin_txids.discard(txid)
        return ts

    def abort_txn(self, txid: int, dns: Optional[set] = None):
        for dn in self.datanodes:
            if dns is None or dn.index in dns:
                dn.abort(txid)
        self.active_txns.discard(txid)
        self.replication_origin_txids.discard(txid)

    # ---- logical replication (reference: logical/worker.c,
    # contrib/opentenbase_subscription) ----
    def logical_publisher(self):
        """Lazy LogicalPublisher: attaches decoders to every datanode
        and registers this cluster for local: subscriptions."""
        if getattr(self, "_logical_pub", None) is None:
            from ..storage.logical import (LogicalPublisher,
                                           register_local_publisher)
            self._logical_pub = LogicalPublisher(self)
            register_local_publisher(f"{id(self):x}", self._logical_pub)
        return self._logical_pub

    @property
    def subscriptions(self) -> dict:
        if not hasattr(self, "_subscriptions"):
            self._subscriptions = {}
        return self._subscriptions

    # ---- GTM failover: guard wrap + standby promotion on loss ----
    def attach_gtm_standby(self, standby):
        """Wrap the GTM handle in the guard: deadlines/retry/breaker on
        every GTM op, and on hard loss the given GtmStandby promotes in
        place — queries keep allocating timestamps past the failover
        (reference: gtm_standby promotion driven by gtm_ctl)."""
        from ..net.guard import GtmGuard
        if not isinstance(self.gtm, GtmGuard):
            self.gtm = GtmGuard(self.gtm, standby=standby)
        else:
            self.gtm._standby = standby
        return self.gtm

    # ---- failover (reference: pg_ctl promote + pgxc_ctl failover) ----
    def promote_standby(self, dn_index: int, standby_datadir: str):
        """Replace a (dead) datanode with its promoted standby: normal
        crash recovery over the standby's shipped directory, then swap
        it into the node table.  In-doubt prepared txns resolve against
        the GTM exactly as after a primary crash."""
        dn = DataNode(dn_index, standby_datadir)
        max_txid = dn.recover(self.catalog, self.gtm)
        if hasattr(self.gtm, "_txid"):
            self.gtm._txid = max(self.gtm._txid, max_txid)
        dn.open_wal()
        self.datanodes[dn_index] = dn
        return dn

    # ---- in-doubt resolver (reference: clean2pc launcher/workers) ----
    def _datanode_by_name(self, name: str):
        for dn in self.datanodes:
            if f"dn{dn.index}" == name:
                return dn
        return None

    def ensure_resolver(self, period_s: float = 1.0,
                        grace_s: float = 5.0):
        """Start the background in-doubt sweeper (reference: the
        clean2pc launcher — one per coordinator, walking the GTM's
        prepared registry plus each DN's orphaned prepares)."""
        if getattr(self, "_resolver", None) is None:
            from ..net.guard import IndoubtResolver
            self._resolver = IndoubtResolver(self, period_s=period_s,
                                             grace_s=grace_s)
            self._resolver.start()
        return self._resolver

    def resolve_indoubt(self, orphan_grace_s: float = 5.0) -> dict:
        """Resolve prepared-but-undecided global txns; still-'prepared'
        ones are presumed aborted.  A 'committed' gid is only forgotten
        after the commit has been re-delivered to EVERY participant: a
        participant that crashed before writing its commit WAL record and
        recovers after the forget would get verdict 'unknown' and
        presume-abort a committed txn (advisor r1).  Delivery is
        idempotent (DataNode.commit replays as a no-op when already
        applied).

        Second sweep: DN-side ORPHANED prepares — gids a datanode holds
        prepared but the GTM has no record of (coordinator died between
        the DN prepares and the GTM registration).  Presumed abort once
        older than `orphan_grace_s` (the grace keeps the sweeper off
        the back of healthy in-flight 2PCs mid-window).

        Returns {"committed": n, "aborted": n} resolved this pass."""
        from ..obs.metrics import REGISTRY
        resolved = {"committed": 0, "aborted": 0}
        done = getattr(self, "_redelivered", None)
        if done is None:
            done = self._redelivered = set()  # (gid, participant) acked
        registered = self.gtm.prepared_list()
        for gid, info in list(registered.items()):
            if info["state"] == "committed":
                ts = int(info["commit_ts"])
                delivered = True
                for name in info["participants"]:
                    if (gid, name) in done:
                        continue  # already acked this run: don't re-WAL
                    dn = self._datanode_by_name(name)
                    if dn is None:
                        continue  # decommissioned node: nothing to deliver
                    try:
                        dn.commit(info["txid"], ts)
                        self.note_dn_commit(getattr(dn, "index", -1), ts)
                        done.add((gid, name))
                    except (ConnectionError, OSError, EOFError,
                            RuntimeError):
                        # unreachable, or net-mode stub surfaced a server
                        # error reply as RuntimeError: retry next pass
                        delivered = False
                if delivered:
                    self.gtm.forget_txn(gid)
                    resolved["committed"] += 1
                    # prune acks: a reused gid must re-deliver, and the
                    # set must not grow for the cluster's lifetime
                    self._redelivered = {e for e in done if e[0] != gid}
                    done = self._redelivered
            elif info["state"] in ("prepared", "aborted"):
                aborted_all = True
                for dn in self.datanodes:
                    try:
                        dn.abort(info["txid"])
                    except (ConnectionError, OSError, EOFError,
                            RuntimeError):
                        aborted_all = False
                if aborted_all:
                    self.gtm.forget_txn(gid)
                    resolved["aborted"] += 1
        # ---- orphaned prepares (GTM never told) ----
        orphans: dict[str, int] = {}
        for dn in self.datanodes:
            try:
                plist = dn.prepared_txns()
            except (ConnectionError, OSError, EOFError, RuntimeError,
                    AttributeError):
                continue   # unreachable / pre-upgrade node: next pass
            for gid, ent in plist.items():
                if gid in registered:
                    continue   # GTM-owned: handled above
                if ent["age_s"] >= orphan_grace_s:
                    orphans[gid] = ent["txid"]
        for gid, txid in orphans.items():
            verdict = "unknown"
            try:
                verdict = self.gtm.txn_verdict(gid)
            except (ConnectionError, OSError, EOFError, RuntimeError):
                continue       # can't consult the authority: next pass
            if verdict == "unknown":
                aborted_all = True
                for dn in self.datanodes:
                    try:
                        dn.abort(txid)
                    except (ConnectionError, OSError, EOFError,
                            RuntimeError):
                        aborted_all = False
                if aborted_all:
                    resolved["aborted"] += 1
        for verdict, n in resolved.items():
            if n:
                REGISTRY.counter("otb_guard_indoubt_resolved_total",
                                 verdict=verdict).inc(n)
        return resolved
