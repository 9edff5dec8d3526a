"""Spill tier: beyond-HBM execution by partitioned multi-pass plans.

Reference analog: the hybrid hash join's nbatch partitioning
(src/backend/executor/nodeHash.c:584 ExecChooseHashTableSize nbatch
growth) and the workfile manager
(src/backend/utils/workfile_manager/workfile_mgr.c).  In this engine
host RAM is the spill tier (SURVEY §7.3: "the host becomes the disk"):
table chunks already live on the host, so spilling means staging only a
BOUNDED SLICE of rows to device HBM per pass:

- scan→aggregate plans: row-range slabs, each aggregated in partial
  mode; the final aggregate merges slab partials (the same partial/
  final protocol DN fan-out uses, so NULL/avg/count semantics are
  identical)
- single equi-join plans: grace hash — both sides partitioned by the
  join-key hash (host-side numpy over chunks), each partition pair
  joined on device independently; TEXT keys hash their strings so the
  two tables' private dictionaries agree
- cross joins: block-nested-loop over left-side slabs (this replaces
  the old hard 2^22 cap for plans routed through the spill tier)

Activation: GUC `work_mem_rows` (rows stageable per operator input).
The driver returns None for shapes it does not cover — the in-memory
path runs as before.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from ..catalog.types import TypeKind
from ..plan import exprs as E
from ..plan import physical as P
from ..plan.distribute import BatchSource
from ..storage.batch import next_pow2, stage_padded
from ..utils.hashing import hash_columns_np, hash_string


def _clone_replacing(node, target, replacement):
    if node is target:
        return replacement
    clone = dataclasses.replace(node)
    for attr in ("child", "left", "right"):
        c = getattr(clone, attr, None)
        if isinstance(c, P.PhysNode):
            setattr(clone, attr, _clone_replacing(c, target, replacement))
    return clone


def _host_key_hash(store, key: E.Expr, alias: str) -> Optional[np.ndarray]:
    """Join-key hash over ALL live rows of a table, host-side (the
    grace-partition assignment).  Plain columns only."""
    if isinstance(key, E.Col):
        plain = key.name.split(".", 1)[1] if "." in key.name else key.name
        if key.name.split(".", 1)[0] != alias:
            return None
        if plain not in store.td.column_names:
            return None
    else:
        return None
    arrs = [ch.columns[plain][:ch.nrows] for _, ch in store.scan_chunks()]
    arr = np.concatenate(arrs) if arrs else np.empty(0, np.int64)
    if store.td.column(plain).type.kind == TypeKind.TEXT:
        d = store.dicts[plain].values
        lut = np.asarray([hash_string(v) for v in d] or [0],
                         dtype=np.uint64)
        return lut[np.clip(arr, 0, len(lut) - 1)]
    return hash_columns_np([arr.astype(np.int64)])


@dataclasses.dataclass(frozen=True, eq=False)
class _ScanInfo:
    node: P.SeqScan
    store: object
    rows: int
    # eq=False: identity hashing so infos key _stage_for's dicts


# -- shared slice-decomposition predicates (spill + morsel tiers) -------
def node_contains(node, target) -> bool:
    return any(nd is target for nd in P.walk(node))


def sliced_side_ok(plan, big_nodes, exclude=None) -> bool:
    """A sliced table must sit on the preserved/probe side of every
    outer/semi/anti join above it: slicing the null-extended or lookup
    side would emit unmatched rows once per slice.  An excluded join
    (the grace-partitioned one) is exempt — partitioning by its OWN key
    hash keeps matches partition-aligned, so its join semantics survive
    on both sides (reference: the hybrid hash join's nbatch
    partitioning, nodeHash.c)."""
    for nd in P.walk(plan):
        if not isinstance(nd, P.HashJoin) or nd is exclude:
            continue
        if nd.kind == "full" and any(
                node_contains(nd, b) for b in big_nodes):
            return False
        if nd.kind in ("left", "semi", "anti") and any(
                node_contains(nd.right, b) for b in big_nodes):
            return False
    return True


def has_order_sensitive(subtree) -> bool:
    """A Limit or Sort INSIDE the per-pass subtree would re-apply per
    slice/chunk — those plans are not slice-decomposable."""
    return any(isinstance(nd, (P.Limit, P.Sort))
               for nd in P.walk(subtree))


# version-gate: snap
# (snap is non-None ONLY when the pool's cached host snapshot matches
# the live store.version — peek_host_snapshot's own gate; the miss
# path reads the live columns directly, so no stale image can serve)
def staged_host_columns(store, needed) -> dict:
    """One store's host columns in the staged namespace (values + MVCC
    sys columns + null masks), reusing the pool's host snapshot when a
    current one is resident — the shared host source for spill slabs
    and morsel chunk windows."""
    from ..storage.bufferpool import POOL
    snap = POOL.peek_host_snapshot(store)
    if snap is not None:
        keys = set(needed) | {
            "__xmin_ts", "__xmax_ts", "__xmin_txid",
            "__xmax_txid"} | {
            f"__null.{c}" for c in needed
            if c in store.null_columns}
        return {k: snap["cols"][k] for k in keys}
    return store.host_live_columns(needed)


class SpillDriver:
    """Plan-shape matcher + multi-pass executor for one session node."""

    def __init__(self, stores: dict, cache, snapshot_ts: int, txid: int,
                 budget: int, params: dict = None):
        self.stores = stores
        self.cache = cache
        self.snapshot_ts = snapshot_ts
        self.txid = txid
        self.params = dict(params or {})
        self.budget = max(int(budget), 1024)
        self.passes = 0   # instrumentation: device passes executed
        self._host_cache: dict = {}  # (id(store), version) -> host cols

    # -- shape analysis ------------------------------------------------
    def _scan_infos(self, plan) -> Optional[list[_ScanInfo]]:
        infos = []
        for nd in P.walk(plan):
            if isinstance(nd, P.SeqScan):
                st = self.stores.get(nd.table.name)
                if st is None:
                    return None
                infos.append(_ScanInfo(nd, st, st.row_count()))
            elif isinstance(nd, (P.AnnSearch, P.Window, P.SetOp,
                                 P.Append, BatchSource)):
                return None
        return infos

    def try_run(self, planned) -> Optional[object]:
        """Returns the result DBatch, or None when the plan/shape is not
        spill-eligible (caller uses the in-memory path)."""
        if planned.init_plans:
            return None
        return self.try_run_plan(planned.plan)

    def try_run_plan(self, plan) -> Optional[object]:
        infos = self._scan_infos(plan)
        if not infos:
            return None
        if max(i.rows for i in infos) <= self.budget:
            return None
        names = [i.node.table.name for i in infos]
        if len(set(names)) != len(names):
            return None   # self-joins: staging is keyed by table name
        joins = [nd for nd in P.walk(plan)
                 if isinstance(nd, P.HashJoin)]
        aggs = [nd for nd in P.walk(plan) if isinstance(nd, P.Agg)]
        # 'single' aggs slab in partial mode and re-merge in final mode;
        # a 'partial' agg (the DN side of a distributed split) slabs
        # as-is and CONCATENATES -- the CN's final aggregate merges the
        # slab partials exactly as it merges per-DN partials
        if len(aggs) > 1 or any(a.mode not in ("single", "partial")
                                for a in aggs):
            return None
        if any(any(ac.distinct for _, ac in a.aggs) for a in aggs):
            return None
        agg = aggs[0] if aggs else None
        over = [i for i in infos if i.rows > self.budget]
        if not joins:
            if len(infos) != 1 or agg is None:
                return None
            return self._run_slabbed_agg(plan, agg, infos[0])
        if len(joins) == 1 and joins[0].kind == "cross" \
                and len(infos) == 2:
            return self._run_block_cross(plan, joins[0], agg, infos)
        if len(over) == 1:
            # one over-budget table in an arbitrary join tree (the star
            # shape: fact + dims): row-range slabs of the big table, the
            # whole subtree per slab, dims staged whole from the cache.
            # When slabbing is invalid (big on the null-extended side of
            # an outer join), fall through to grace-partitioning the
            # join that touches it — partition-aligned slicing preserves
            # outer semantics on both sides.
            out = self._run_slabbed_tree(plan, joins, agg, over[0])
            if out is not None:
                return out
        if 1 <= len(over) <= 2:
            # grace-partition an equi join with an over-budget side;
            # each partition pass runs the whole subtree with both
            # partitioned sides sliced and dims staged whole
            return self._run_grace_tree(plan, joins, agg, infos, over)
        return None

    @staticmethod
    def _has_order_sensitive(subtree) -> bool:
        return has_order_sensitive(subtree)

    # -- execution helpers --------------------------------------------
    def _exec_with_staged(self, plan, staged):
        from .executor import ExecContext, Executor
        ctx = ExecContext(self.stores, self.snapshot_ts, self.txid,
                          self.cache, staged=staged,
                          params=dict(self.params))
        self.passes += 1
        return Executor(ctx).exec_node(plan)

    def _combine_host(self, batches):
        from .dist import _concat_host, _to_device, _to_host
        return _to_device(_concat_host([_to_host(b) for b in batches]))

    def _stage_for(self, subtree, infos_sel: dict):
        """Stage each scanned table's selected rows; returns ctx.staged.
        The host concatenation comes from the buffer pool's snapshot
        when a current one is resident (mesh staging / dn_server built
        it already), else it is built once per (store, version) locally
        and sliced per pass."""
        staged = {}
        for info, sel in infos_sel.items():
            needed = sorted(P.needed_columns(subtree, info.node.alias)
                            | P.needed_columns(subtree, info.node.table.name))
            hkey = (id(info.store), info.store.version, tuple(needed))
            host = self._host_cache.get(hkey)
            if host is None:
                host = staged_host_columns(info.store, needed)
                self._host_cache = {hkey: host, **{
                    k: v for k, v in list(self._host_cache.items())[-3:]}}
            arrs, n = stage_padded(host, sel)
            staged[info.node.table.name] = (arrs, n)
        return staged

    # -- shapes --------------------------------------------------------
    def _run_slabbed_agg(self, plan, agg, info: _ScanInfo):
        """scan→agg: row-range slabs in partial mode + one final (a
        'partial' fragment agg concatenates for the CN's final)."""
        finalize = agg.mode == "single"
        partial = dataclasses.replace(agg, mode="partial") if finalize \
            else agg
        if self._has_order_sensitive(partial):
            return None
        partials = []
        for lo in range(0, info.rows, self.budget):
            sel = slice(lo, min(lo + self.budget, info.rows))
            staged = self._stage_for(partial, {info: sel})
            partials.append(self._exec_with_staged(partial, staged))
        combined = self._combine_host(partials)
        if not finalize:
            return self._finish_with(plan, agg, BatchSource(combined))
        final = P.Agg(BatchSource(combined),
                      [(n, E.Col(n, ke.type))
                       for n, ke in agg.group_keys], agg.aggs, "final")
        return self._finish_with(plan, agg, final)

    def _finish_with(self, plan, target, replacement_node):
        rest = _clone_replacing(plan, target, replacement_node)
        from .executor import ExecContext, Executor
        ctx = ExecContext(self.stores, self.snapshot_ts, self.txid,
                          self.cache, params=dict(self.params))
        return Executor(ctx).exec_node(rest)

    def _finalize(self, plan, replace_target, agg, finalize, combined):
        """Shared tail of every shape runner: final-merge the combined
        partials (or hand the concatenation straight to the rest of the
        plan for a 'partial' fragment agg)."""
        if agg is not None and finalize:
            final = P.Agg(BatchSource(combined),
                          [(n, E.Col(n, ke.type))
                           for n, ke in agg.group_keys], agg.aggs,
                          "final")
            return self._finish_with(plan, replace_target, final)
        return self._finish_with(plan, replace_target,
                                 BatchSource(combined))

    def _per_pass_plan(self, plan, joins, agg):
        """(subtree to run per slice, node it replaces, finalize?).
        A 'single' agg slabs in partial mode and re-merges under a
        final aggregate; a 'partial' agg (DN fragment) runs as-is and
        its slab outputs concatenate for the CN's final merge."""
        if agg is not None and agg.mode == "single":
            return dataclasses.replace(agg, mode="partial"), agg, True
        if agg is not None:
            return agg, agg, False
        top = self._top_join(plan, joins)
        return top, top, False

    def _run_block_cross(self, plan, join, agg, infos):
        left_info = self._info_for_side(join.left, infos)
        right_info = self._info_for_side(join.right, infos)
        if left_info is None or right_info is None:
            return None
        per_plan, replace_target, finalize = self._per_pass_plan(
            plan, [join], agg)
        if self._has_order_sensitive(per_plan):
            return None
        outs = []
        # bound the cross PRODUCT per pass (the padded pair expansion is
        # the memory cost), not just the left staging
        r_padded = next_pow2(max(right_info.rows, 1))
        pair_budget = max(self.budget * 8, 1 << 20)
        slab = max(pair_budget // r_padded, 64)
        for lo in range(0, left_info.rows, slab):
            lsel = slice(lo, min(lo + slab, left_info.rows))
            rsel = slice(0, right_info.rows)
            staged = self._stage_for(per_plan, {left_info: lsel,
                                                right_info: rsel})
            outs.append(self._exec_with_staged(per_plan, staged))
        combined = self._combine_host(outs)
        return self._finalize(plan, replace_target, agg, finalize,
                              combined)

    def _info_for_side(self, side_plan, infos) -> Optional[_ScanInfo]:
        scans = [nd for nd in P.walk(side_plan)
                 if isinstance(nd, P.SeqScan)]
        if len(scans) != 1:
            return None
        for i in infos:
            if i.node is scans[0]:
                return i
        return None

    @staticmethod
    def _contains(node, target) -> bool:
        return node_contains(node, target)

    def _sliced_side_ok(self, plan, big_nodes, exclude=None) -> bool:
        return sliced_side_ok(plan, big_nodes, exclude)

    def _top_join(self, plan, joins):
        for nd in P.walk(plan):
            if isinstance(nd, P.HashJoin):
                return nd
        return joins[0]

    def _run_slabbed_tree(self, plan, joins, agg, big: _ScanInfo):
        """Arbitrary join tree with ONE over-budget scan: row-range
        slabs of the big table; per slab the whole subtree executes with
        the dims fully staged (they fit the budget and stay cached
        across passes); partial-aggregate slabs merge in final mode."""
        if not self._sliced_side_ok(plan, (big.node,)):
            return None
        per_plan, replace_target, finalize = self._per_pass_plan(
            plan, joins, agg)
        if not self._contains(per_plan, big.node) \
                or self._has_order_sensitive(per_plan):
            return None
        outs = []
        for lo in range(0, big.rows, self.budget):
            sel = slice(lo, min(lo + self.budget, big.rows))
            staged = self._stage_for(per_plan, {big: sel})
            outs.append(self._exec_with_staged(per_plan, staged))
        combined = self._combine_host(outs)
        return self._finalize(plan, replace_target, agg, finalize,
                              combined)

    def _run_grace_tree(self, plan, joins, agg, infos, over):
        """Grace-partition an equi join with over-budget side(s): both
        sides slice by the join-key hash, the whole subtree runs per
        partition (dims staged whole).  Covers two-big-table joins AND
        the one-big-table shapes slabbing must refuse (big on the
        null-extended side of the join — partition-aligned slicing
        keeps outer semantics)."""
        over_set = set(over)
        gjoin = None
        for j in joins:
            if j.kind not in ("inner", "left", "semi", "anti"):
                continue
            li = self._info_for_side(j.left, infos)
            ri = self._info_for_side(j.right, infos)
            if li is not None and ri is not None and li is not ri \
                    and (li in over_set or ri in over_set) \
                    and over_set <= {li, ri}:
                gjoin = (j, li, ri)
                break
        if gjoin is None:
            return None
        join, left_info, right_info = gjoin
        big_nodes = (left_info.node, right_info.node)
        if not self._sliced_side_ok(plan, big_nodes, exclude=join):
            return None
        lh = self._side_hash(left_info, join.left_keys)
        rh = self._side_hash(right_info, join.right_keys)
        if lh is None or rh is None:
            return None
        per_plan, replace_target, finalize = self._per_pass_plan(
            plan, joins, agg)
        if not (self._contains(per_plan, left_info.node)
                and self._contains(per_plan, right_info.node)) \
                or self._has_order_sensitive(per_plan):
            return None
        nparts = max(1, 2 ** math.ceil(math.log2(max(
            1, math.ceil(max(left_info.rows, right_info.rows)
                         / self.budget)))))
        lp = (lh % np.uint64(nparts)).astype(np.int64)
        rp = (rh % np.uint64(nparts)).astype(np.int64)
        outs = []
        for p in range(nparts):
            lsel = np.nonzero(lp == p)[0]
            rsel = np.nonzero(rp == p)[0]
            if len(lsel) == 0:
                continue
            if join.kind in ("inner", "semi") and len(rsel) == 0:
                continue
            staged = self._stage_for(per_plan, {left_info: lsel,
                                                right_info: rsel})
            outs.append(self._exec_with_staged(per_plan, staged))
        if not outs:
            return None
        combined = self._combine_host(outs)
        return self._finalize(plan, replace_target, agg, finalize,
                              combined)


    def _side_hash(self, info: _ScanInfo, keys) -> Optional[np.ndarray]:
        hs = []
        for k in keys:
            h = _host_key_hash(info.store, k, info.node.alias)
            if h is None:
                return None
            hs.append(h)
        if not hs:
            return None
        out = hs[0]
        for h in hs[1:]:
            from ..utils.hashing import combine_np
            out = combine_np(out, h)
        return out
