"""Distributed executor: runs a DistPlan's fragment DAG over the cluster.

Reference analog: fragment dispatch + the FN data plane —
ExecDispatchRemoteFragment (execDispatchFragment.c:1124) sends serialized
fragments to DNs; tuples move between fragments as tagged FnPages
(forward/).  Here: each fragment executes per-datanode with that node's
stores (device kernels inside); exchange edges move columnar batches
between fragments:

- redistribute: rows hash-routed to owner datanodes by key (the
  all_to_all; host-mediated in this engine tier, with the device
  all_to_all path exercised by parallel/mesh.py)
- broadcast: every datanode receives the full child output
- gather: the coordinator receives the concatenation (optionally
  merge-ordered)

Dictionary-coded TEXT columns are decoded to strings at exchange
boundaries and re-encoded under a shared destination dictionary — code
spaces are node-local (storage/store.py), strings are the wire format.
"""

from __future__ import annotations

import copy as _copy
import dataclasses
import time as _time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..catalog.types import SqlType, TypeKind
from ..obs import trace as obs_trace
from ..obs import xray as obs_xray
from ..parallel.cluster import Cluster
from ..plan import exprs as E
from ..plan.distribute import (BatchSource, DistPlan, Exchange, ExchangeRef,
                               Fragment)
from ..plan import physical as P
from ..plan.planner import PlannedStmt
from ..storage.batch import next_pow2
from ..utils.hashing import hash_columns_np, hash_string
from .executor import (DBatch, ExecContext, ExecError, Executor, materialize,
                       scalars_from_batch)


@dataclasses.dataclass
class HostBatch:
    """Exchange wire format: host numpy columns, TEXT as decoded values,
    NULL masks carried alongside (outer-join null extension survives
    exchange boundaries)."""
    cols: dict[str, np.ndarray]       # TEXT columns: object arrays of str
    types: dict[str, SqlType]
    nrows: int
    nulls: dict[str, np.ndarray] = dataclasses.field(default_factory=dict)


def _to_host(b: DBatch) -> HostBatch:
    b.ensure_all()   # exchange boundary: rows physically move
    valid = np.asarray(b.valid)
    idx = np.nonzero(valid)[0]
    cols = {}
    nulls = {}
    for n, arr in b.cols.items():
        a = np.asarray(arr)[idx]
        t = b.types[n]
        if t.kind == TypeKind.TEXT:
            # vectorized decode: one fancy-index through the dictionary
            # (was a per-row python loop — the r1 bench bottleneck)
            d = np.asarray(b.dicts.get(n, []) or [""], dtype=object)
            a = d[np.clip(a, 0, len(d) - 1)]
        if n in b.nulls:
            m = np.asarray(b.nulls[n])[idx]
            if m.any():
                nulls[n] = m
        cols[n] = a
    return HostBatch(cols, dict(b.types), len(idx), nulls)


def _concat_host(parts: list[HostBatch]) -> HostBatch:
    parts = [p for p in parts if p is not None]
    first = parts[0]
    cols = {n: np.concatenate([p.cols[n] for p in parts])
            for n in first.cols}
    nulls = {}
    null_names = set()
    for p in parts:
        null_names |= set(p.nulls)
    for n in null_names:
        nulls[n] = np.concatenate(
            [p.nulls.get(n, np.zeros(p.nrows, dtype=bool)) for p in parts])
    return HostBatch(cols, first.types, sum(p.nrows for p in parts), nulls)


def _to_device(hb: HostBatch) -> DBatch:
    padded = next_pow2(max(hb.nrows, 1))
    cols, dicts, nulls = {}, {}, {}
    for n, arr in hb.cols.items():
        t = hb.types[n]
        if t.kind == TypeKind.TEXT:
            # re-encode under a fresh local dictionary — vectorized
            # factorize (np.unique at C speed, not a per-row dict loop)
            if len(arr):
                uniq, inv = np.unique(np.asarray(arr, dtype=object),
                                      return_inverse=True)
                values = [str(u) for u in uniq]
                codes = inv.astype(np.int32).reshape(-1)
            else:
                values, codes = [], np.empty(0, dtype=np.int32)
            buf = np.zeros(padded, dtype=np.int32)
            buf[:len(codes)] = codes
            cols[n] = jnp.asarray(buf)
            dicts[n] = values
        else:
            from ..utils.dtypes import stage_cast
            arr = stage_cast(np.asarray(arr))
            buf = np.zeros((padded, *np.shape(arr)[1:]), dtype=arr.dtype)
            buf[:len(arr)] = arr
            cols[n] = jnp.asarray(buf)
    for n, m in hb.nulls.items():
        buf = np.zeros(padded, dtype=bool)
        buf[:len(m)] = m
        nulls[n] = jnp.asarray(buf)
    valid = jnp.asarray(np.arange(padded) < hb.nrows)
    return DBatch(cols, valid, dict(hb.types), dicts, nulls)


class DistExecutor:
    def __init__(self, cluster: Cluster, snapshot_ts: int, txid: int,
                 instrument: bool = False, use_mesh: bool = False,
                 cancel_check=None, group_budget_rows: int = 0,
                 replica_reads: bool = False):
        self.group_budget_rows = group_budget_rows
        # standby read scale-out (GUC replica_reads, net/guard.py
        # ReplicaRouter): read fragments may run on a hot standby whose
        # GTS hwm covers the snapshot.  The session only enables this
        # for snapshot-read statements of txns that have not written —
        # own uncommitted writes exist nowhere but the primary.
        self.replica_reads = replica_reads
        self.cluster = cluster
        # statement-cancel probe (reference: CHECK_FOR_INTERRUPTS at the
        # executor's safe points) — raises when the client canceled
        self.cancel_check = cancel_check
        self.snapshot_ts = snapshot_ts
        self.txid = txid
        self.params: dict[str, tuple] = {}
        self.instrument = instrument
        self.use_mesh = use_mesh
        # (fragment, where) -> {"ms": float, "rows": int} — the
        # distributed-EXPLAIN instrumentation the reference ships DN->CN
        # (commands/explain_dist.c)
        self.stats: dict = {}
        # which data plane actually ran, surfaced by EXPLAIN (reference:
        # the FN-vs-PQ protocol choice in execFragment.c): 'mesh' (one
        # shard_map program), 'fqs' (whole query on one DN), or 'host';
        # when the mesh tier declined, fallback_reason says why
        self.tier: str = ""
        self.fallback_reason: str = ""

    # ------------------------------------------------------------------
    def run(self, dp: DistPlan) -> DBatch:
        if self.cancel_check is not None:
            self.cancel_check()
        for ip in dp.init_plans:
            # init plans are whole little queries: distribute + run
            # them.  Distribution MUTATES the plan tree (exchange refs
            # spliced in), and the generic plan cache re-runs the same
            # DistPlan object — so distribute a fresh copy every time
            # (cheap: init-plan trees are small)
            from ..plan.distribute import Distributor
            d = Distributor(self.cluster.catalog, self.cluster.ndn)
            sub = d.distribute(
                PlannedStmt(_copy.deepcopy(ip.plan), [], []), None)
            # one span an init plan: `initplans` of summary() counts them
            with obs_trace.span("initplan", plan=ip.name):
                vals = scalars_from_batch(self._run_distplan(sub),
                                          ip.outputs())
            self.params.update(vals)
        return self._run_distplan(dp)

    def _scan_exceeds_budget(self, dp, budget: int) -> bool:
        """Does any per-DN scan of this plan exceed the work_mem
        budget?  Remote datanodes (no local stores) are conservatively
        treated as over budget — the DN side re-checks and only spills
        what actually overflows."""
        from ..plan import physical as P
        tables = set()
        for frag in dp.fragments:
            for nd in P.walk(frag.plan):
                if isinstance(nd, P.SeqScan):
                    tables.add(nd.table.name)
        for t in tables:
            for dn in self.cluster.datanodes:
                stores = getattr(dn, "stores", None)
                if stores is None:
                    return True
                st = stores.get(t)
                if st is not None and st.row_count() > budget:
                    return True
        return False

    def _run_distplan(self, dp: DistPlan) -> DBatch:
        if dp.fqs_node is None and len(dp.fragments) == 1 \
                and not dp.exchanges:
            # CN-local statement: the main plan scans no tables (e.g. a
            # SELECT of init-plan scalars).  Nothing to ship — this is
            # not a data-plane fallback (reference: queries that never
            # leave the coordinator, pgxc_query_needs_coord)
            self.tier = "local"
            return self._exec_fragment_on(dp.fragments[dp.top_fragment],
                                          dp, "cn", {})
        wm_raw = self.cluster.gucs.get("work_mem_rows", "")
        budget = int(wm_raw) if wm_raw.isdigit() else 0
        # resource-group HBM staging budget: the TIGHTER of the session
        # GUC and the group cap applies (reference: resource-group
        # memory enforcement, re-targeted at device staging)
        gb = getattr(self, "group_budget_rows", 0)
        if gb > 0:
            budget = min(budget, gb) if budget > 0 else gb
        if budget > 0 and self._scan_exceeds_budget(dp, budget):
            # budgeted execution AND a scanned table is actually over
            # budget: the mesh tier stages whole tables to device HBM,
            # so route through the host tier whose DN fragments spill
            # (slab/grace multi-pass).  Queries under the budget keep
            # the device data plane.
            self.params.setdefault("__work_mem_rows", (budget, None))
            self.fallback_reason = self.fallback_reason or \
                "work_mem_rows budget (spill tier)"
        elif self.use_mesh and dp.fqs_node is None:
            # device data plane: DN fragments + exchanges compile into one
            # shard_map program (all_to_all/all_gather over the mesh)
            from .mesh_exec import MeshUnsupported, mesh_runner_for
            runner = mesh_runner_for(self.cluster)
            if runner is None:
                self.fallback_reason = self.fallback_reason or \
                    "cluster not mesh-capable"
            else:
                try:
                    t_run = _time.perf_counter()
                    gathered, executed = runner.run(
                        dp, self.snapshot_ts, self.txid, self.params)
                    mesh_ms = (_time.perf_counter() - t_run) * 1e3
                    top = dp.fragments[dp.top_fragment]
                    if self.instrument:
                        # mesh fragments execute as ONE shard_map
                        # program — each gathered fragment reports its
                        # own output rows but shares the program's
                        # wall time (EXPLAIN ANALYZE annotation)
                        for ex_ in dp.exchanges:
                            b = gathered.get(ex_.index)
                            if b is not None:
                                self.stats[(ex_.source_fragment,
                                            "mesh")] = {
                                    "ms": mesh_ms,
                                    "rows": int(b.count())}
                    self.tier = "mesh"   # overwritten by later subplans:
                    # the LAST _run_distplan call is the main plan, so the
                    # recorded tier is always the main plan's
                    ex_out = {(gi, "cn"): b
                              for gi, b in gathered.items()}
                    # hybrid: fragments the mesh could not carry (CN
                    # combines consuming gathers) finish host-side over
                    # the device-computed gather outputs
                    for frag in dp.fragments:
                        if frag.index == dp.top_fragment or \
                                frag.index in executed:
                            continue
                        self._feed_exchanges(frag, dp, ex_out)
                    return self._exec_fragment_on(top, dp, "cn",
                                                  ex_out)
                except MeshUnsupported as e:
                    # host-mediated tier handles everything else
                    self.fallback_reason = str(e)
                except (ConnectionError, OSError, EOFError) as e:
                    # a DN died under the mesh's whole-table staging:
                    # degrade to the host fragment tier, whose per-DN
                    # dispatch re-routes read fragments to a promoted
                    # standby (the next statement rides the mesh again)
                    self.fallback_reason = (
                        f"mesh staging connection failure: {e}")
        # snapshot-gate: self.snapshot_ts
        # (every dispatched fragment carries the transaction snapshot;
        # the datanode filters tuple visibility against it)
        if dp.fqs_node is not None:
            # whole-query shipped to one datanode (FQS).  An in-process
            # datanode returns the device batch directly (no host
            # round-trip on the OLTP fast path).  'gidx' = the node was
            # pinned through a global-index lookup rather than dist keys.
            self.tier = "gidx" if getattr(dp, "via_gidx", "") else "fqs"
            dn = self.cluster.datanodes[dp.fqs_node]
            frag = dp.fragments[dp.top_fragment]
            out = self._try_replica(dp.fqs_node, frag, {})
            if out is not None:
                return _to_device(out)
            if hasattr(dn, "exec_plan_device"):
                return dn.exec_plan_device(frag.plan, self.snapshot_ts,
                                           self.txid, self.params, {})
            try:
                return _to_device(dn.exec_plan(
                    frag.plan, self.snapshot_ts, self.txid,
                    self.params, {}))
            except (ConnectionError, OSError, EOFError):
                # whole-query-shipped read on a dead DN: same standby
                # re-dispatch as the fragment path
                dn2 = self._failover_target(dp.fqs_node)
                if dn2 is None:
                    raise
                return _to_device(dn2.exec_plan(
                    frag.plan, self.snapshot_ts, self.txid,
                    self.params, {}))
        # exchange outputs, keyed (exchange_index, dest) where dest is a
        # dn index or 'cn'
        self.tier = "host"
        ex_out: dict = {}
        # execute fragments bottom-up (they were appended children-first)
        for frag in dp.fragments:
            if frag.index == dp.top_fragment:
                continue
            if self.cancel_check is not None:
                self.cancel_check()
            self._feed_exchanges(frag, dp, ex_out)
        top = dp.fragments[dp.top_fragment]
        return self._exec_fragment_on(top, dp, "cn", ex_out)

    # ------------------------------------------------------------------
    def _feed_exchanges(self, frag: Fragment, dp: DistPlan, ex_out: dict):
        """Run `frag` on every datanode and route its output through the
        exchange(s) that consume it."""
        consumers = [ex for ex in dp.exchanges
                     if ex.source_fragment == frag.index]
        only_one = consumers and all(ex.kind == "gather_one"
                                     for ex in consumers)
        # a fragment whose inputs were GATHERED lives on the CN: run it
        # once there and fan its output back out (reference: the CN
        # materializing a step other fragments consume — e.g. a set-op
        # combine feeding a redistribution, execRemote.c merge then
        # re-ship).  Slower than a true per-DN pipeline but correct for
        # every plan shape; the mesh tier declines these plans.
        needed = {n.index for n in P.walk(frag.plan)
                  if isinstance(n, ExchangeRef)}
        ndn = self.cluster.ndn
        cn_only = {i for i in needed
                   if (i, "cn") in ex_out
                   and not any((i, d) in ex_out for d in range(ndn))}
        scans_tables = any(isinstance(n, P.SeqScan)
                           for n in P.walk(frag.plan))
        if cn_only and scans_tables:
            # the fragment must run on the DNs (it scans shards) but an
            # input was gathered to the CN: replicate that input to
            # every DN (each joins its shard against the full copy)
            for i in cn_only:
                for d in range(ndn):
                    ex_out[(i, d)] = ex_out[(i, "cn")]
            cn_only = set()
        cn_fed = needed and not scans_tables and (
            all((i, "cn") in ex_out for i in needed) or cn_only)
        if cn_fed:
            # synthesize CN copies of any per-DN-only inputs: concat
            # redistribute parts (all rows), take one broadcast copy
            kinds = {ex.index: ex.kind for ex in dp.exchanges}
            for i in needed:
                if (i, "cn") in ex_out:
                    continue
                parts = [ex_out[(i, d)] for d in range(ndn)
                         if (i, d) in ex_out]
                ex_out[(i, "cn")] = parts[0] \
                    if kinds.get(i) == "broadcast" \
                    else _concat_host(parts)
            batch = self._exec_fragment_on(frag, dp, "cn", ex_out)
            hb = _to_host(batch)
            with obs_trace.span("exchange", fragment=frag.index) as exsp:
                for ex in consumers:
                    if ex.kind in ("gather", "gather_one"):
                        ex_out[(ex.index, "cn")] = hb
                    elif ex.kind == "broadcast":
                        ex_out[(ex.index, "cn")] = hb
                        for d in range(self.cluster.ndn):
                            ex_out[(ex.index, d)] = hb
                    elif ex.kind == "redistribute":
                        routed = self._route([hb], ex.keys)
                        for d in range(self.cluster.ndn):
                            ex_out[(ex.index, d)] = routed[d]
                    else:
                        raise ExecError(
                            f"unknown exchange kind {ex.kind}")
                if obs_trace.active():
                    exsp.set(rounds=len(consumers), bytes=_hb_bytes(hb))
            return
        dn_range = [0] if only_one else list(range(self.cluster.ndn))
        remote = all(not hasattr(dn, "stores")
                     for dn in self.cluster.datanodes)
        if remote and len(dn_range) > 1:
            # concurrent dispatch: every datanode executes the fragment
            # at once; socket IO releases the GIL so wall-clock ≈
            # max(DN), not sum(DN) (reference: RunRemoteController's
            # parallel connection pump, execDispatchFragment.c:1024)
            from concurrent.futures import ThreadPoolExecutor
            # the span stack is thread-local, so the workers can't open
            # spans — but a CAPTURED trace context still rides each RPC
            # (xray.inject reads it), and the DN-side subtrees it brings
            # back are grafted into this trace at finish
            xctx = obs_xray.capture()

            def _on(i):
                with obs_xray.propagated(xctx):
                    return self._exec_fragment_on(frag, dp, i, ex_out)

            with ThreadPoolExecutor(len(dn_range)) as pool:
                per_dn: list[HostBatch] = list(pool.map(_on, dn_range))
        else:
            per_dn = [self._exec_fragment_on(frag, dp, dn_idx, ex_out)
                      for dn_idx in dn_range]
        with obs_trace.span("exchange", fragment=frag.index) as exsp:
            for ex in consumers:
                if ex.kind == "gather_one":
                    ex_out[(ex.index, "cn")] = per_dn[0]
                elif ex.kind == "gather":
                    ex_out[(ex.index, "cn")] = _concat_host(per_dn)
                elif ex.kind == "broadcast":
                    full = _concat_host(per_dn)
                    ex_out[(ex.index, "cn")] = full
                    for d in range(self.cluster.ndn):
                        ex_out[(ex.index, d)] = full
                elif ex.kind == "redistribute":
                    routed = self._route(per_dn, ex.keys)
                    for d in range(self.cluster.ndn):
                        ex_out[(ex.index, d)] = routed[d]
                else:
                    raise ExecError(f"unknown exchange kind {ex.kind}")
            if obs_trace.active():
                exsp.set(rounds=len(consumers),
                         bytes=sum(_hb_bytes(h) for h in per_dn))

    def _route(self, per_dn: list[HostBatch],
               keys: list[E.Expr]) -> list[HostBatch]:
        """Hash-route rows to their owner datanode (the reference's
        per-tuple GetDataRouting loop, execFragment.c:2360 — vectorized)."""
        ndn = self.cluster.ndn
        shard_map = self.cluster.catalog.shard_map
        outs: list[list[HostBatch]] = [[] for _ in range(ndn)]
        for hb in per_dn:
            if hb.nrows == 0:
                continue
            karrs = []
            for k in keys:
                arr = self._eval_host_key(k, hb)
                # canonicalize NULL key positions so the NULL group lands
                # on ONE node (joins never match them; group-by must not
                # split them across nodes)
                kname = k.col.name if isinstance(k, E.TextExpr) else \
                    getattr(k, "name", None)
                nm = hb.nulls.get(kname) if kname else None
                if nm is not None:
                    arr = np.where(nm, np.uint64(0), arr)
                karrs.append(arr)
            h = hash_columns_np(karrs)
            # route exactly like storage placement: hash -> 4096-entry
            # shard map -> node (NOT mod ndn — the two only coincide for
            # power-of-two node counts).  This keeps redistributed rows
            # colocated with the SHARD table they join against.
            from ..catalog.schema import NUM_SHARDS
            sid = (h % np.uint64(NUM_SHARDS)).astype(np.int64)
            dest = shard_map[sid]
            for d in range(ndn):
                m = dest == d
                if m.any():
                    outs[d].append(HostBatch(
                        {n: a[m] for n, a in hb.cols.items()},
                        hb.types, int(m.sum()),
                        {n: a[m] for n, a in hb.nulls.items()}))
        return [
            _concat_host(o) if o else
            HostBatch({n: np.empty(0, dtype=(object
                                             if per_dn[0].types[n].kind
                                             == TypeKind.TEXT
                                             else per_dn[0].types[n].np_dtype))
                       for n in per_dn[0].cols},
                      per_dn[0].types, 0)
            for o in outs]

    @staticmethod
    def _hash_strings(arr: np.ndarray, transform=None) -> np.ndarray:
        """Hash a string column via its uniques (python hashing runs once
        per distinct value, the C-speed inverse maps rows)."""
        if not len(arr):
            return np.empty(0, dtype=np.uint64)
        uniq, inv = np.unique(np.asarray(arr, dtype=object),
                              return_inverse=True)
        hu = np.asarray([hash_string(transform(str(s)) if transform
                                     else str(s)) for s in uniq],
                        dtype=np.uint64)
        return hu[inv.reshape(-1)]

    def _eval_host_key(self, k: E.Expr, hb: HostBatch) -> np.ndarray:
        """Evaluate a routing key over a host batch -> uint64 hash input."""
        if isinstance(k, E.TextExpr):
            return self._hash_strings(hb.cols[k.col.name], k.apply)
        if isinstance(k, E.Col):
            arr = hb.cols[k.name]
            if hb.types[k.name].kind == TypeKind.TEXT:
                return self._hash_strings(arr)
            return arr.astype(np.int64).view(np.uint64)
        raise ExecError("redistribution keys must be simple columns "
                        f"(got {type(k).__name__})")

    # ------------------------------------------------------------------
    def _try_replica(self, dn_index: int, frag: Fragment,
                     sources: dict):
        """Route one read fragment to a hot standby of dn_index, or
        None -> run on the primary as always (router trouble never
        fails a statement)."""
        # snapshot-gate: self.snapshot_ts
        # (the router only serves from a replica whose replayed hwm
        # covers this snapshot; net/guard.py re-checks)
        if not self.replica_reads:
            return None
        router = getattr(self.cluster, "read_router", None)
        if router is None:
            return None
        with obs_trace.span("execute", fragment=frag.index,
                            where=f"dn{dn_index}-standby"):
            return router.try_exec(dn_index, frag.plan,
                                   self.snapshot_ts, self.txid,
                                   self.params, sources)

    def _failover_target(self, dn_index: int):
        """Resolve the replacement datanode for a read re-dispatch, or
        None when the cluster has no standby to promote (the original
        connection error then propagates)."""
        fo = getattr(self.cluster, "failover_read", None)
        if fo is None:
            return None
        try:
            return fo(dn_index)
        except Exception:
            # promotion itself failed (standby dir gone, catalog race):
            # surface the ORIGINAL connection error, not this one
            return None

    # ------------------------------------------------------------------
    def _exec_fragment_on(self, frag: Fragment, dp: DistPlan, where,
                          ex_out: dict):
        """Run one fragment at `where` ('cn' or dn index).  Returns a
        DBatch for 'cn', a HostBatch from a datanode (the datanode may be
        remote — its exec_plan is the RPC surface)."""
        # snapshot-gate: self.snapshot_ts
        sources = {ex_idx: hb for (ex_idx, dest), hb in ex_out.items()
                   if dest == where}
        t0 = _time.perf_counter() if self.instrument else 0
        if where == "cn":
            from .executor import DeviceTableCache
            plan = _bind_sources_host(frag.plan, sources)
            ctx = ExecContext({}, self.snapshot_ts, self.txid,
                              DeviceTableCache(),
                              params=dict(self.params))
            # what the CN fragment compiles reads `otb.finalize` on the
            # device, whatever step or kernel scope lies further in
            with obs_trace.span("execute", fragment=frag.index,
                                where="cn"), \
                    jax.named_scope("otb.finalize"):
                out = Executor(ctx).exec_node(plan)
            if self.instrument:
                self.stats[(frag.index, where)] = {
                    "ms": (_time.perf_counter() - t0) * 1e3,
                    "rows": out.count()}
            return out
        dn = self.cluster.datanodes[where]
        # on a remote cluster this runs from dispatch worker threads,
        # where span() is a no-op (the trace stack is thread-local) —
        # per-fragment timing still lands in self.stats under instrument
        out = self._try_replica(where, frag, sources)
        if out is not None:
            if self.instrument:
                self.stats[(frag.index, where)] = {
                    "ms": (_time.perf_counter() - t0) * 1e3,
                    "rows": out.nrows}
            return out
        with obs_trace.span("execute", fragment=frag.index,
                            where=f"dn{where}"):
            try:
                out = dn.exec_plan(frag.plan, self.snapshot_ts,
                                   self.txid, self.params, sources)
            except (ConnectionError, OSError, EOFError):
                # read-only fragment on a dead DN: promote its standby
                # (coalesced across racing fragment threads) and replay
                # the fragment there — exec_plan never mutates, so the
                # re-dispatch cannot double-apply anything
                dn2 = self._failover_target(where)
                if dn2 is None:
                    raise
                out = dn2.exec_plan(frag.plan, self.snapshot_ts,
                                    self.txid, self.params, sources)
        if self.instrument:
            self.stats[(frag.index, where)] = {
                "ms": (_time.perf_counter() - t0) * 1e3,
                "rows": out.nrows}
        return out


def _hb_bytes(hb) -> int:
    """Approximate exchange wire size: numpy/jax array nbytes (shape
    metadata only — never a device sync; TEXT object columns count
    pointer width, a stable lower bound)."""
    try:
        return int(sum(int(a.nbytes) for a in hb.cols.values())
                   + sum(int(a.nbytes) for a in hb.nulls.values()))
    except (AttributeError, TypeError):
        return 0


def _bind_sources_host(node: P.PhysNode, sources: dict):
    """Copy the fragment plan with ExchangeRef leaves replaced by
    BatchSource over the staged exchange input (HostBatch from the host
    tier, or an already-device DBatch from the mesh tier)."""
    if isinstance(node, ExchangeRef):
        hb = sources.get(node.index)
        if hb is None:
            raise ExecError(f"exchange {node.index} has no input here")
        if isinstance(hb, DBatch):
            return BatchSource(hb)
        return BatchSource(_to_device(hb))
    clone = dataclasses.replace(node)
    for attr in ("child", "left", "right"):
        c = getattr(clone, attr, None)
        if isinstance(c, P.PhysNode):
            setattr(clone, attr, _bind_sources_host(c, sources))
    if isinstance(clone, (P.Append, P.SetOp)):
        clone.inputs = [_bind_sources_host(c, sources)
                        for c in clone.inputs]
    return clone
