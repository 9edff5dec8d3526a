"""Morsel tier: out-of-core partitioned streaming execution.

Reference analog: Postgres never assumes a table fits shared_buffers —
the buffer manager streams pages through a bounded cache (and the
bulk-read path uses a small ring buffer, src/backend/storage/buffer/
freelist.c GetAccessStrategy) while operators above it are oblivious.
Every device-side tier here DID assume residency: a scanned table's
padded columns had to fit OTB_DEVICE_CACHE_BYTES or the query fell off
the device entirely (shield's degrade-to-spill runs EAGER passes).
This tier is the streaming middle ground Tailwind / "Accelerating
Presto with GPUs" (PAPERS.md) identify as the central design problem of
accelerator-resident engines: host RAM holds the data, the device sees
a bounded window of it at a time, and the copy engine overlaps with
compute.

Mechanics:

- the dominant scan splits into fixed-shape row-range chunks; EVERY
  chunk of a stream shares one padded shape (storage/batch.py
  chunk_class — pow2, floor 4k), so the per-chunk compiled fragment
  (exec/fused.py FragmentProgram) never retraces: the chunk SIZE class
  is in the program key, the chunk COUNT and offsets are not
- chunks stage through the bufferpool's pinned chunk cache
  (storage/bufferpool.py get_chunk/unpin_chunk): device_put is async,
  so fetching chunk i+1 before blocking on chunk i's output
  double-buffers host→device copies against device compute
- blocking operators decompose exactly like the spill tier's slabs
  (the partial/final protocol DN fan-out uses): hash-agg accumulates
  per-chunk partials and merges under one final aggregate; hash joins
  keep their small sides device-RESIDENT and PINNED (a streaming probe
  must not evict its own build side) and stream the big side through
  the join; a top-level sort runs the streamable core per chunk —
  with the sort's own top-k pushed down per chunk when the planner
  bounded it — and re-sorts the merged survivors once
- an on-device OOM mid-stream downshifts the chunk size (halving,
  chunk_class-quantized, floor OTB_MORSEL_MIN_CHUNK_ROWS) and resumes
  from the SAME row offset — shield's pressure ladder gains its middle
  rung: shrink the window before leaving the device

Activation: GUC `morsel` = auto (default; stream when the dominant
scan's staged estimate exceeds OTB_MORSEL_FRACTION of the device
budget) | on (stream whenever a scan exceeds one chunk) | off.  GUC
`morsel_chunk_rows` / OTB_MORSEL_CHUNK_ROWS set the window (default
65536).  The driver returns None for shapes it does not cover — the
spill tier and the in-memory path run as before.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

from ..obs import trace as obs_trace
from ..obs import xray as obs_xray
from ..plan import exprs as E
from ..plan import physical as P
from ..plan.distribute import BatchSource
from ..storage import codec
from ..storage.batch import chunk_class, size_class
from ..utils import locks, snapcheck
from . import share as workshare
from .spill import (_clone_replacing, _ScanInfo,
                    has_order_sensitive, node_contains,
                    sliced_side_ok, staged_host_columns)

_LOCK = locks.Lock("exec.morsel._LOCK")
_STATS: dict = {              # guarded_by: _LOCK
    "streams": 0,             # queries served by the morsel tier
    "chunks": 0,              # chunk windows executed
    "bytes_streamed": 0,      # host->device bytes staged for windows
    "chunk_downshifts": 0,    # OOM-driven chunk-size halvings
    "declined": 0,            # shapes handed back to spill/in-memory
}


def bump(field: str, n: int = 1):
    with _LOCK:
        _STATS[field] += n


def stats_snapshot() -> dict:
    with _LOCK:
        return dict(_STATS)


def stats_rows() -> list:
    """One row for the otb_morsel view."""
    d = stats_snapshot()
    return [(d["streams"], d["chunks"], d["bytes_streamed"],
             d["chunk_downshifts"], d["declined"])]


def reset_stats():
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _metrics_samples():
    for k, v in stats_snapshot().items():
        yield (f"otb_morsel_{k}", {}, v)


def _env_i(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def default_chunk_rows() -> int:
    return chunk_class(_env_i("OTB_MORSEL_CHUNK_ROWS", 65536))


def min_chunk_rows() -> int:
    return chunk_class(_env_i("OTB_MORSEL_MIN_CHUNK_ROWS", 4096))


def stream_fraction() -> float:
    try:
        return float(os.environ.get("OTB_MORSEL_FRACTION", "0.5"))
    except ValueError:
        return 0.5


def _est_staged_bytes(rows: int, ncols: int) -> int:
    """Staged-residency estimate: padded rows x (value + MVCC sys
    columns) x 8 — the same arithmetic shield's admission estimate
    uses."""
    return size_class(max(rows, 1)) * (ncols + 4) * 8


def _node_exprs(nd):
    """Expr sources of ONE node (non-recursive), excluding a SeqScan's
    own passthrough outputs — those are the prune candidates."""
    for attr in ("filters", "quals"):
        for q in getattr(nd, attr, None) or []:
            yield from E.walk(q)
    if not isinstance(nd, P.SeqScan):
        for _name, e in getattr(nd, "outputs", None) or []:
            yield from E.walk(e)
    if isinstance(nd, P.Agg):
        for _, ke in nd.group_keys:
            yield from E.walk(ke)
        for _, ac in nd.aggs:
            yield from E.walk(ac)
    if isinstance(nd, P.Sort):
        for ke, _ in nd.keys:
            yield from E.walk(ke)
    if isinstance(nd, P.HashJoin):
        for e in (list(nd.left_keys) + list(nd.right_keys)
                  + list(nd.residual or [])):
            yield from E.walk(e)


def _surface_scan_ids(plan) -> set:
    """Scans whose outputs ARE the statement's result: reachable from
    the root through passthrough nodes only (no Project/Agg contract in
    between).  Pruning those would change what the query returns."""
    out: set = set()

    def down(nd):
        if isinstance(nd, P.SeqScan):
            out.add(id(nd))
            return
        if isinstance(nd, P.Agg) or getattr(nd, "outputs", None):
            return   # this node defines the column contract upward
        for attr in ("child", "left", "right"):
            c = getattr(nd, attr, None)
            if isinstance(c, P.PhysNode):
                down(c)

    down(plan)
    return out


def _prune_scan_outputs(plan):
    """Deep-copied plan with each SeqScan's projection narrowed to the
    outputs the rest of the plan references by name.  The planner's
    scans project every table column; the in-memory fragment never pays
    for that, but a stream stages every scan output for every chunk,
    and _classify charges pinned residents the same arithmetic — so an
    SF-scale build side misreads as over-budget.  Output names are the
    exact strings upstream Col lookups use, so exact-name matching is
    the executor's own contract."""
    import copy
    plan = copy.deepcopy(plan)
    refs = {x.name for nd in P.walk(plan)
            for x in _node_exprs(nd) if isinstance(x, E.Col)}
    surface = _surface_scan_ids(plan)
    for nd in P.walk(plan):
        if not isinstance(nd, P.SeqScan) or id(nd) in surface:
            continue
        outs = nd.outputs
        if not outs:
            continue   # None = "all columns" contract: leave intact
        kept = [(n, e) for n, e in outs if n in refs]
        nd.outputs = kept or outs[:1]   # keep row-count semantics
    return plan


@dataclasses.dataclass
class _StreamShape:
    """One eligible plan decomposition."""
    per_plan: object          # subtree executed per chunk
    replace_target: object    # node the merged stream replaces
    agg: object               # the Agg being decomposed, or None
    finalize: bool            # merge partials under a final Agg?
    big: _ScanInfo            # the streamed scan
    resident: list            # [_ScanInfo] staged whole + pinned


class _ShareFallback(Exception):
    """A follower left its shared stream (expelled, or the leader
    failed) — the query reruns on a private stream."""


class MorselDriver:
    """Plan-shape matcher + chunk-streaming executor for one node."""

    def __init__(self, stores: dict, cache, snapshot_ts: int,
                 txid: int, chunk_rows: Optional[int] = None,
                 params: dict = None, forced: bool = False,
                 share: Optional[bool] = None):
        self.stores = stores
        self.cache = cache
        self.snapshot_ts = snapshot_ts
        self.txid = txid
        self.params = dict(params or {})
        self.chunk_rows = chunk_class(int(chunk_rows)
                                      if chunk_rows else
                                      default_chunk_rows())
        self.forced = forced
        # cross-query shared scans (exec/share.py): on unless the
        # enable_work_sharing GUC / OTB_WORK_SHARING says otherwise
        self.share = workshare.enabled(None) if share is None \
            else bool(share)
        # per-consumer pin identity: every chunk pin this driver takes
        # is accounted to this token, so a shared stream's other
        # consumers can never be released by this one erroring
        self.token = workshare.new_token()
        # per-stream counts, folded into the module stats at the end
        self.chunks = 0
        self.downshifts = 0
        self.bytes_streamed = 0

    # -- shape analysis ------------------------------------------------
    def _scan_infos(self, plan) -> Optional[list]:
        infos = []
        for nd in P.walk(plan):
            if isinstance(nd, P.SeqScan):
                st = self.stores.get(nd.table.name)
                if st is None:
                    return None
                infos.append(_ScanInfo(nd, st, st.row_count()))
            elif isinstance(nd, (P.AnnSearch, P.Window, P.SetOp,
                                 P.Append, P.IndexScan, BatchSource)):
                return None
        return infos

    def _classify(self, plan) -> Optional[_StreamShape]:
        infos = self._scan_infos(plan)
        if not infos:
            return None
        names = [i.node.table.name for i in infos]
        if len(set(names)) != len(names):
            return None   # self-joins: staging is keyed by table name
        joins = [nd for nd in P.walk(plan)
                 if isinstance(nd, P.HashJoin)]
        if any(j.kind == "cross" for j in joins):
            return None   # output sized by a host count: spill's BNL
        aggs = [nd for nd in P.walk(plan) if isinstance(nd, P.Agg)]
        if len(aggs) > 1 or any(a.mode not in ("single", "partial")
                                for a in aggs):
            return None
        if any(any(ac.distinct for _, ac in a.aggs) for a in aggs):
            return None
        agg = aggs[0] if aggs else None

        # the dominant scan streams; everything else must be resident
        def est(i):
            needed = (P.needed_columns(plan, i.node.alias)
                      | P.needed_columns(plan, i.node.table.name))
            return _est_staged_bytes(i.rows, len(needed))
        big = max(infos, key=est)
        if big.rows <= self.chunk_rows:
            return None   # nothing to stream
        if not self.forced:
            from ..storage import bufferpool
            if est(big) <= stream_fraction() * bufferpool._budget():
                return None   # fits comfortably: stay in-memory
        from ..storage import bufferpool
        if any(est(i) > bufferpool._budget()
               for i in infos if i is not big):
            return None   # a second over-budget table: grace territory
        if not sliced_side_ok(plan, (big.node,)):
            return None

        per_plan, replace_target, finalize = self._per_chunk_plan(
            plan, joins, agg)
        if per_plan is None \
                or not node_contains(per_plan, big.node):
            return None
        resident = [i for i in infos if i is not big
                    and node_contains(per_plan, i.node)]
        if len(resident) != len(infos) - 1:
            return None   # a scan outside the streamed subtree
        return _StreamShape(per_plan, replace_target, agg, finalize,
                            big, resident)

    def _per_chunk_plan(self, plan, joins, agg):
        """(subtree per chunk, node the merged stream replaces,
        finalize?) — the spill tier's slab decomposition plus the
        sort-core case it refuses: a top-level Sort/Limit chain peels
        off the streamable core, the sort's own top-k (when the planner
        bounded it) re-applies per chunk, and the ORIGINAL order nodes
        re-run over the merged survivors."""
        if agg is not None:
            if agg.mode == "single":
                partial = dataclasses.replace(agg, mode="partial")
                if has_order_sensitive(partial):
                    return None, None, False
                return partial, agg, True
            if has_order_sensitive(agg):
                return None, None, False
            return agg, agg, False
        if joins:
            top = next(nd for nd in P.walk(plan)
                       if isinstance(nd, P.HashJoin))
            if has_order_sensitive(top):
                return None, None, False
            return top, top, False
        # scan-only chain: peel Limit/Sort/Project wrappers down to the
        # deepest order-sensitive node; its child is the streamable core
        node, bottom_order = plan, None
        while isinstance(node, (P.Limit, P.Sort, P.Project, P.Filter)):
            if isinstance(node, (P.Limit, P.Sort)):
                bottom_order = node
            node = node.child
        if bottom_order is None:
            if has_order_sensitive(plan):
                return None, None, False
            return plan, plan, False
        core = bottom_order.child
        if has_order_sensitive(core):
            return None, None, False
        if isinstance(bottom_order, P.Sort) \
                and bottom_order.limit is not None:
            # planner-bounded top-k: any row in the global top-k is in
            # its own chunk's top-k under the same (keys, row-order)
            # comparator, so per-chunk truncation is exact — the final
            # Sort re-ranks the merged survivors
            return dataclasses.replace(bottom_order), core, False
        return core, core, False

    # -- execution -----------------------------------------------------
    def try_run(self, planned) -> Optional[object]:
        """The result DBatch, or None when the plan is not streamable
        (caller falls through to spill / in-memory)."""
        if planned.init_plans:
            return None
        return self.try_run_plan(planned.plan)

    def _quick_gate(self, plan) -> bool:
        """Cheap pre-checks on the ORIGINAL plan so the common decline
        (tiny tables, comfortable residency) never pays the pruning
        deep copy.  The un-pruned estimate only OVERstates staged
        bytes, so an under-threshold answer here is final."""
        infos = self._scan_infos(plan)
        if not infos:
            return False
        if max(i.rows for i in infos) <= self.chunk_rows:
            return False   # nothing to stream
        if not self.forced:
            from ..storage import bufferpool
            hi = max(_est_staged_bytes(
                i.rows, len(P.needed_columns(plan, i.node.alias)
                            | P.needed_columns(plan, i.node.table.name)))
                for i in infos)
            if hi <= stream_fraction() * bufferpool._budget():
                return False   # fits comfortably even un-pruned
        return True

    def try_run_plan(self, plan) -> Optional[object]:
        if not self._quick_gate(plan):
            return None
        plan = _prune_scan_outputs(plan)
        shape = self._classify(plan)
        if shape is None:
            return None
        out = self._run_stream(plan, shape)
        if out is None:
            bump("declined")
        return out

    def _exec_ctx(self):
        from .executor import ExecContext
        return ExecContext(self.stores, self.snapshot_ts, self.txid,
                           self.cache, params=dict(self.params))

    def _run_stream(self, plan, shape: _StreamShape):
        from ..storage.bufferpool import POOL

        big = shape.big
        needed = sorted(P.needed_columns(shape.per_plan, big.node.alias)
                        | P.needed_columns(shape.per_plan,
                                           big.node.table.name))
        host = staged_host_columns(big.store, needed)
        # codec descriptors for the streamed table, ensured against the
        # FULL host columns BEFORE the fragment program is built: every
        # window provably fits one descriptor (no mid-stream class
        # fork) and FragmentProgram's _table_sig sees the classes the
        # chunks will actually carry
        encs = codec.ensure_classes(big.store, host)

        # cross-query sharing: the first stream over (store, version,
        # chunk shape) leads; compatible concurrent streams follow its
        # published windows instead of staging their own
        # version-gate: (big.store, self.chunk_rows)
        # (ShareHub.attach keys streams on (id(store), store.version,
        # chunk_rows) — a follower can only join a stream staged at
        # the SAME store version it would stage itself)
        role, stream, token, join_lo = None, None, self.token, 0
        if self.share:
            names = frozenset(host) \
                | {codec.aux_name(c, en) for c, en in encs.items()}
            classes = {c: codec.codec_class(en)
                       for c, en in encs.items()}
            att = workshare.HUB.attach(big.store, self.chunk_rows,
                                       names, classes)
            if att is None:
                workshare.bump("private_fallbacks")
            else:
                role, stream, token, join_lo = att

        if role == "follower":
            try:
                out = self._follower_pass(plan, shape, host, encs,
                                          stream, token, join_lo)
                POOL.check_pin_ledger()
                return out
            except _ShareFallback:
                workshare.bump("private_fallbacks")
                return self._stream_pass(plan, shape, host, encs,
                                         None, self.token)
        if role == "leader":
            try:
                out = self._stream_pass(plan, shape, host, encs,
                                        stream, token)
            except Exception:
                # shared pass must not downshift under live followers
                # (the chunk shape is the stream's contract): fail the
                # stream — followers fall back privately — and retry
                # this query on a private stream with the full
                # pressure ladder
                stream.finish(failed=True)
                workshare.HUB.remove(stream)
                workshare.bump("private_fallbacks")
                return self._stream_pass(plan, shape, host, encs,
                                         None, self.token)
            fanin = stream.finish()
            workshare.HUB.remove(stream)
            if fanin:
                workshare.bump("shared_streams")
                POOL.check_pin_ledger()
            return out
        return self._stream_pass(plan, shape, host, encs, None,
                                 self.token)

    def _pin_residents(self, shape: _StreamShape):
        """Stage + pin the non-streamed sides: per-chunk pressure
        relief must never evict the build side a stream is probing
        against.  Returns (arrs by table, counts by table, pin
        handles)."""
        from ..storage.bufferpool import POOL
        resident_arrs: dict = {}
        resident_ns: dict = {}
        pins = []
        for info in shape.resident:
            rneed = sorted(
                P.needed_columns(shape.per_plan, info.node.alias)
                | P.needed_columns(shape.per_plan, info.node.table.name))
            arrs, n = self.cache.get(info.store, rneed)
            resident_arrs[info.node.table.name] = arrs
            resident_ns[info.node.table.name] = n
            handle = POOL.pin_table(info.store)
            if handle is not None:
                pins.append(handle)
        return resident_arrs, resident_ns, pins

    def _stream_pass(self, plan, shape: _StreamShape, host, encs,
                     stream, token):
        """Drive the chunk stream: private when `stream` is None, else
        as the LEADER — each staged window fans into every follower
        before this driver consumes it, and run-ahead is throttled so
        follower backlogs stay bounded."""
        from ..storage.bufferpool import POOL
        from .dist import _concat_host, _to_device, _to_host
        from .fused import FragmentProgram
        from . import shield

        big = shape.big
        resident_arrs, resident_ns, pins = {}, {}, []
        try:
            # snapshot-gate: self.snapshot_ts
            # (every window runs the fragment under this query's
            # snapshot; MVCC system columns ride in the chunk)
            resident_arrs, resident_ns, pins = self._pin_residents(shape)
            prog = FragmentProgram(self._exec_ctx(), shape.per_plan,
                                   self.chunk_rows)
            if not prog.ok():
                return None

            # version-gate: (big.store, self.chunk_rows)
            def stage(at):
                if stream is not None:
                    stream.throttle()
                e = POOL.get_chunk(big.store, host, at,
                                   self.chunk_rows, encs,
                                   consumer=token)
                if stream is not None:
                    stream.publish(e, at, at + self.chunk_rows)
                return e

            bname = big.node.table.name
            floor = min_chunk_rows()
            outs = []
            lo = 0
            nxt = stage(0)
            with obs_trace.span("execute", tier="morsel") \
                    if obs_trace.ENABLED else obs_trace.NULL_SPAN:
                while lo < big.rows:
                    entry, nxt = nxt, None
                    hi = lo + self.chunk_rows
                    if hi < big.rows:
                        # prefetch: the NEXT window's device_put
                        # enqueues before this window's output blocks
                        nxt = stage(hi)
                    staged_arrs = dict(resident_arrs)
                    staged_arrs[bname] = entry.arrs
                    staged_ns = dict(resident_ns)
                    staged_ns[bname] = entry.live
                    try:
                        out = prog.run(staged_arrs, staged_ns,
                                       self.snapshot_ts, self.txid)
                        if out is not None:
                            # blocks on THIS chunk's device compute;
                            # the next chunk's copy is already in
                            # flight
                            outs.append(_to_host(out))
                    except Exception as e:
                        POOL.unpin_chunk(entry, consumer=token)
                        if nxt is not None:
                            POOL.unpin_chunk(nxt, consumer=token)
                        if stream is not None:
                            # downshifting would fork the shared chunk
                            # shape; a lone leader (nobody ever
                            # joined) closes the stream and takes the
                            # private ladder in place
                            with stream.cond:
                                lone = stream.fanin == 0
                                if lone:
                                    stream.accepting = False
                            if not lone:
                                raise
                            workshare.HUB.remove(stream)
                            stream = None
                        if shield.is_oom(e) \
                                and self.chunk_rows > floor:
                            # the middle rung of the pressure ladder:
                            # shrink the window, stay on device, resume
                            # from the SAME offset (completed chunks
                            # keep their partials)
                            self.chunk_rows = chunk_class(
                                max(self.chunk_rows // 2, floor))
                            self.downshifts += 1
                            bump("chunk_downshifts")
                            obs_trace.event(
                                "morsel_downshift",
                                chunk_rows=self.chunk_rows)
                            shield.relieve()
                            prog = FragmentProgram(
                                self._exec_ctx(), shape.per_plan,
                                self.chunk_rows)
                            if not prog.ok():
                                return None
                            nxt = POOL.get_chunk(big.store, host, lo,
                                                 self.chunk_rows, encs,
                                                 consumer=token)
                            continue
                        raise
                    self.chunks += 1
                    self.bytes_streamed += entry.nbytes
                    POOL.unpin_chunk(entry, consumer=token)
                    if out is None:
                        if nxt is not None:
                            POOL.unpin_chunk(nxt, consumer=token)
                        return None   # fusion refused mid-stream
                    lo = hi
        finally:
            for handle in pins:
                POOL.unpin_table(handle)

        bump("streams")
        bump("chunks", self.chunks)
        bump("bytes_streamed", self.bytes_streamed)
        obs_trace.event("morsel_stream", table=big.node.table.name,
                        chunks=self.chunks, chunk_rows=self.chunk_rows)
        if not outs:
            return None
        combined = _to_device(_concat_host(outs))
        return self._finalize(plan, shape, combined)

    def _follower_pass(self, plan, shape: _StreamShape, host, encs,
                       stream, token, join_lo):
        """Consume a leader's published windows instead of staging our
        own: each delivered chunk runs THIS query's compiled fragment
        under THIS query's snapshot (MVCC system columns ride in the
        shared window, so visibility is per consumer), then releases
        only this consumer's pin.  A late joiner re-reads its missed
        prefix [0, join_lo) privately after the live stream drains —
        warm chunk-cache hits when the leader staged the same column
        set.  Raises _ShareFallback when expelled or the stream fails;
        the caller reruns privately (sharing is never a semantic)."""
        from ..storage.bufferpool import POOL
        from .dist import _concat_host, _to_device, _to_host
        from .fused import FragmentProgram

        big = shape.big
        bname = big.node.table.name
        staged_names = list(host) \
            + [codec.aux_name(c, en) for c, en in encs.items()]
        resident_arrs, resident_ns, pins = {}, {}, []
        outs = []   # (lo, host batch) — re-sorted to stream order
        # snapshot-gate: self.snapshot_ts
        # version-gate: entry.version == stream.version
        # (every consumed window — published OR the private prefix
        # re-read — must carry the stream's attach-time store version;
        # mixing physical versions inside one result would fracture
        # the read even though each window is MVCC-filtered)
        try:
            resident_arrs, resident_ns, pins = self._pin_residents(shape)
            prog = FragmentProgram(self._exec_ctx(), shape.per_plan,
                                   self.chunk_rows)
            if not prog.ok():
                stream.detach(token)
                return None

            def run_window(lo, entry):
                staged_arrs = dict(resident_arrs)
                staged_arrs[bname] = {nm: entry.arrs[nm]
                                      for nm in staged_names}
                staged_ns = dict(resident_ns)
                staged_ns[bname] = entry.live
                out = prog.run(staged_arrs, staged_ns,
                               self.snapshot_ts, self.txid)
                if out is not None:
                    outs.append((lo, _to_host(out)))
                self.chunks += 1
                return out is not None

            with obs_trace.span("execute", tier="morsel",
                                shared=True) \
                    if obs_trace.ENABLED else obs_trace.NULL_SPAN:
                while True:
                    with stream.cond:
                        f = stream.followers[token]
                        while not f["deque"] and not stream.done \
                                and not f["expelled"]:
                            with obs_xray.wait_event("share-backlog"):
                                stream.cond.wait(timeout=0.25)
                        if f["expelled"] or stream.failed:
                            raise _ShareFallback()
                        if f["deque"]:
                            lo, entry = f["deque"].popleft()
                        else:
                            break   # done and fully drained
                    if snapcheck.enabled() or snapcheck.history_on():
                        snapcheck.serve(
                            "exec.morsel.MorselDriver._follower_pass",
                            snapshot_gts=self.snapshot_ts,
                            versions=[(bname, entry.version)],
                            expect_versions=[(bname, stream.version)],
                            session=self.txid, source="shared")
                    try:
                        ok = run_window(lo, entry)
                    finally:
                        POOL.unpin_chunk(entry, consumer=token)
                        with stream.cond:
                            stream.cond.notify_all()
                    if not ok:
                        stream.detach(token)
                        return None   # fusion refused mid-stream
                # missed prefix: re-read privately (warm hits when the
                # leader staged the same columns)
                lo = 0
                while lo < join_lo:
                    entry = POOL.get_chunk(big.store, host, lo,
                                           self.chunk_rows, encs,
                                           consumer=token)
                    if entry.version != stream.version:
                        # a DML committed mid-stream: the prefix would
                        # restage at the NEW store version while the
                        # consumed windows carry the attach-time one —
                        # two physical images in one result.  Bail to
                        # a private stream (consistent by construction)
                        POOL.unpin_chunk(entry, consumer=token)
                        raise _ShareFallback()
                    if snapcheck.enabled() or snapcheck.history_on():
                        snapcheck.serve(
                            "exec.morsel.MorselDriver._follower_pass",
                            snapshot_gts=self.snapshot_ts,
                            versions=[(bname, entry.version)],
                            expect_versions=[(bname, stream.version)],
                            session=self.txid, source="shared")
                    try:
                        ok = run_window(lo, entry)
                    finally:
                        POOL.unpin_chunk(entry, consumer=token)
                    if not ok:
                        return None
                    lo += self.chunk_rows
        except _ShareFallback:
            raise
        except Exception:
            stream.detach(token)
            raise
        finally:
            for handle in pins:
                POOL.unpin_table(handle)

        bump("streams")
        bump("chunks", self.chunks)
        obs_trace.event("morsel_stream", table=bname,
                        chunks=self.chunks,
                        chunk_rows=self.chunk_rows, shared=True)
        if not outs:
            return None
        outs.sort(key=lambda p: p[0])
        combined = _to_device(_concat_host([o for _lo, o in outs]))
        return self._finalize(plan, shape, combined)

    def _finalize(self, plan, shape: _StreamShape, combined):
        """Merge the stream: per-chunk agg partials final-merge (the
        DN fan-out protocol); everything else concatenates and the rest
        of the plan — including any peeled Sort/Limit — re-runs over
        the merged batch."""
        from .executor import Executor
        if shape.agg is not None and shape.finalize:
            replacement = P.Agg(
                BatchSource(combined),
                [(n, E.Col(n, ke.type))
                 for n, ke in shape.agg.group_keys],
                shape.agg.aggs, "final")
        else:
            replacement = BatchSource(combined)
        rest = _clone_replacing(plan, shape.replace_target, replacement)
        return Executor(self._exec_ctx()).exec_node(rest)


from ..obs.metrics import REGISTRY as _METRICS  # noqa: E402
_METRICS.register_collector("morsel", _metrics_samples)
