"""Device-mesh SQL execution: fragment DAGs as ONE shard_map program.

Reference analog: the FN data plane — producer fragments append tuples to
per-destination FnPages that sender/receiver processes stream over TCP
(src/backend/forward/, postmaster/forwardsend.c:165, execFragment.c:2148
FragmentSendTuple / :2515 FragmentRedistributeData).  On a TPU mesh the
whole apparatus collapses into XLA collectives inside one compiled
program: each logical datanode is a mesh device, table shards are
device-sharded arrays, and

    hash-redistribute  ->  all_to_all over ICI
    broadcast          ->  all_gather
    gather-to-CN       ->  sharded program output, host-assembled
    partial aggregates ->  computed per shard, finalised after exchange

The per-tuple routing loop the reference runs (GetDataRouting,
execFragment.c:2360) is here ONE hash kernel + ONE all_to_all per batch,
and routing matches storage placement exactly: dest = shard_map[hash %
4096] — the same 4096-entry map the locator uses, so redistributed rows
land where colocated base-table shards already live.

Dynamic shapes are handled by the size-class ladder (SURVEY §7.3): join
outputs use a static probe-proportional size and a2a buckets a static
per-destination capacity; the compiled program reports overflow via psum
and the host re-traces one size class up.

TEXT columns cross exchanges as dictionary CODES: staging builds one
UNION dictionary per column across all datanodes (host work proportional
to dictionary size, not rows), so no decode/re-encode ever touches the
row data — the host exchange tier's remaining python cost disappears.

Staged tables are DEVICE-RESIDENT across queries via the shared buffer
pool (storage/bufferpool.py): entries are keyed by the per-DN version
tuple, so a warm repeat stages nothing at all, append-only growth
uploads only the per-DN tail rows (union dictionaries extend in place),
and any other mutation drops the stale arrays lazily.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as PS

from ..catalog.schema import NUM_SHARDS
from ..catalog.types import TypeKind
from ..obs import trace as obs_trace
from ..plan import exprs as E
from ..plan import physical as P
from ..plan.distribute import BatchSource, DistPlan, ExchangeRef
from ..storage import codec
from ..storage.batch import next_pow2
from ..utils.dtypes import dev_dtype
from ..utils.hashing import (combine_jax, hash_string, splitmix64_jax)
from . import plancache

# Observability hook (see exec/fused.py EXPORT_HOOK): called as
# EXPORT_HOOK("mesh", fn, flat_args) after each successful program run.
EXPORT_HOOK = None


class MeshUnsupported(Exception):
    """This plan (or cluster) can't run on the device mesh — callers
    fall back to the host-mediated exchange tier."""


class _DictView:
    """One column's UNION dictionary: `values` by code and `index`, the
    string -> code map staging keeps beside it (both grow in place when
    a table's tail is staged)."""

    def __init__(self, values, index):
        self.values = values
        self.index = index

    def code_of(self, s: str) -> int:
        return self.index.get(s, -1)


class _MeshStoreView:
    """TableStore facade used by the traced scan: schema + UNION
    dictionaries (codes comparable across every shard)."""

    def __init__(self, td, union_dicts: dict, dict_state: dict,
                 null_columns: set):
        self.td = td
        self.dicts = {c: _DictView(v, dict_state[c]["index"])
                      for c, v in union_dicts.items()}
        self.null_columns = set(null_columns)


@dataclasses.dataclass
class _StagedTable:
    arrs: dict          # name -> (ndn*P,) sharded device array
    nrows: object       # (ndn,) int64 sharded — per-shard live row count
    padded: int         # per-shard P (static)
    view: _MeshStoreView
    vkey: tuple


_ALLOWED = (P.SeqScan, P.Filter, P.Project, P.HashJoin, P.Agg, P.Sort,
            P.Limit, P.Window, P.Append, ExchangeRef)


class MeshRunner:
    def __init__(self, cluster):
        from ..parallel.mesh import make_mesh
        if any(not hasattr(dn, "stores")
               and not hasattr(dn, "stage_table")
               for dn in cluster.datanodes):
            raise MeshUnsupported("datanodes are not mesh-stageable")
        if len(jax.devices()) < cluster.ndn:
            raise MeshUnsupported(
                f"{cluster.ndn} datanodes but only "
                f"{len(jax.devices())} devices")
        self.cluster = cluster
        self.mesh = make_mesh(cluster.ndn)
        self.axis = self.mesh.axis_names[0]
        # staged tables live in the SHARED device buffer pool
        # (storage/bufferpool.py): version-keyed residency across
        # queries under one byte budget, with an incremental tail path
        # for append-only growth — this runner only assembles entries
        self._snapshots: dict = {}   # (dn_index, table) -> snapshot
        # compiled shard_map programs live in the SHARED program cache
        # (exec/plancache.py MESH tier: bounded LRU, global
        # live-executable budget, hit/miss telemetry), keyed per
        # runner; _programs is this runner's build registry — the
        # observability surface (did THIS query compile or reuse?)
        self._programs: dict = {}
        # the learned size classes of this runner's plan shapes: join
        # and aggregate factors, exchange bucket multipliers, gather
        # classes (four sessions of a CN share one runner: it locks)
        self._ladder = plancache.Ladder(256)

    # ------------------------------------------------------------------
    # plan screening
    # ------------------------------------------------------------------
    def _screen(self, dp: DistPlan) -> set:
        """Validate the plan and return the mesh-computable fragment
        set (the split fixpoint runs ONCE per query)."""
        if dp.fqs_node is not None:
            raise MeshUnsupported("FQS plan runs on one node")
        for ex in dp.exchanges:
            if ex.kind not in ("redistribute", "broadcast", "gather",
                              "gather_one"):
                raise MeshUnsupported(f"exchange {ex.kind}")
            for k in ex.keys or []:
                if not isinstance(k, (E.Col, E.TextExpr)):
                    raise MeshUnsupported("non-column exchange key")
        included = self._split_fragments(dp)
        for fi in included:
            self._screen_node(
                next(f for f in dp.fragments if f.index == fi).plan)
        return included

    def _split_fragments(self, dp) -> set:
        """The MESH-COMPUTABLE fragment frontier.  Fragments consuming
        a gather run at the coordinator (a set-op combine, a cross join
        of scalar subqueries): the device program computes everything
        UP TO the gathers and the host finishes from there — hybrid
        execution instead of declining the whole plan (reference: the
        CN always executes the top combine in the FN plane too).  A
        non-gather exchange consumed by a CN-side fragment drags its
        producer off the mesh as well (its output would otherwise only
        exist in device memory), propagated to a fixpoint."""
        gathers = {ex.index for ex in dp.exchanges
                   if ex.kind in ("gather", "gather_one")}
        src_of = {ex.index: ex.source_fragment
                  for ex in dp.exchanges}
        needs = {}
        for frag in dp.fragments:
            if frag.index == dp.top_fragment:
                continue
            needs[frag.index] = {
                n.index for n in P.walk(frag.plan)
                if isinstance(n, ExchangeRef)}
        excluded: set = set()
        changed = True
        while changed:
            changed = False
            for fi, nd in needs.items():
                if fi in excluded:
                    continue
                if any(i in gathers or src_of[i] in excluded
                       for i in nd):
                    excluded.add(fi)
                    changed = True
            for fi, nd in needs.items():
                if fi not in excluded:
                    continue
                for i in nd:
                    if i not in gathers and                             src_of[i] not in excluded:
                        excluded.add(src_of[i])
                        changed = True
        included = {fi for fi in needs if fi not in excluded}
        if not any(src_of[g] in included for g in gathers):
            raise MeshUnsupported(
                "no mesh-computable gather fragment")
        return included

    @staticmethod
    def _screen_node(node):
        for nd in P.walk(node):
            if not isinstance(nd, _ALLOWED):
                raise MeshUnsupported(type(nd).__name__)
            if isinstance(nd, P.HashJoin) and nd.kind == "cross":
                raise MeshUnsupported("cross join sizing")
            if isinstance(nd, P.SeqScan) and nd.table.name.startswith(
                    "otb_"):
                raise MeshUnsupported("stat view scan")

    # ------------------------------------------------------------------
    # staging: per-DN host chunks -> sharded device arrays + union dicts
    # ------------------------------------------------------------------
    # version-gate: cached["version"] == ver
    def _snapshot(self, dn, name: str) -> dict:
        """One DN's live columns + dictionaries at its current version —
        the shared buffer-pool host snapshot for in-process stores, over
        the wire for TCP datanodes (both version-cached, so an unchanged
        table never re-concatenates or re-ships).  In-process stores
        delegate to POOL.host_snapshot (its own version gate); the wire
        path re-validates the cached snapshot against a fresh
        dn.table_version probe before reuse."""
        if hasattr(dn, "stores"):
            st = dn.stores.get(name)
            if st is None:
                raise MeshUnsupported(f"table {name} missing on dn")
            from ..storage.bufferpool import POOL
            return POOL.host_snapshot(st)
        key = (dn.index, name)
        cached = self._snapshots.get(key)
        ver = dn.table_version(name)
        if ver is None:
            raise MeshUnsupported(f"table {name} missing on "
                                  f"dn{dn.index}")
        if cached is not None and cached["version"] == ver:
            return cached
        snap = dn.stage_table(name)
        if snap is None:
            raise MeshUnsupported(f"table {name} missing on "
                                  f"dn{dn.index}")
        snap["null_columns"] = set(snap["null_columns"])
        self._snapshots[key] = snap
        if len(self._snapshots) > 256:
            self._snapshots.pop(next(iter(self._snapshots)))
        return snap

    def _version_of(self, dn, name: str):
        """Cheap per-DN version probe — staging must NOT materialize a
        snapshot (host_live_columns concatenates the whole table) just
        to discover nothing changed."""
        if hasattr(dn, "stores"):
            st = dn.stores.get(name)
            if st is None:
                raise MeshUnsupported(f"table {name} missing on dn")
            return st.version
        v = dn.table_version(name)
        if v is None:
            raise MeshUnsupported(f"table {name} missing on "
                                  f"dn{dn.index}")
        return v

    def _stage_table(self, name: str) -> _StagedTable:
        from ..storage.bufferpool import POOL, MeshEntry
        vkey = tuple(self._version_of(dn, name)
                     for dn in self.cluster.datanodes)
        ent = POOL.mesh_get(self, name, vkey)
        if ent is not None:
            return ent.staged
        stale = POOL.mesh_peek(self, name)
        if stale is not None:
            entry = self._stage_incremental(name, stale, vkey)
            if entry is not None:
                POOL.mesh_put(self, name, entry)
                return entry.staged
        snaps = [self._snapshot(dn, name)
                 for dn in self.cluster.datanodes]
        vkey = tuple(s["version"] for s in snaps)
        td = self.cluster.catalog.table(name)
        ndn = len(snaps)

        # union dictionaries + per-store code LUTs; the index/LUT state
        # rides along in the pool entry so append-only growth can EXTEND
        # the union (existing codes stay valid) instead of rebuilding
        union_dicts: dict[str, list] = {}
        luts: dict[str, list[np.ndarray]] = {}
        dict_state: dict[str, dict] = {}
        for c in td.columns:
            if c.type.kind != TypeKind.TEXT:
                continue
            values: list[str] = []
            index: dict[str, int] = {}
            col_luts = []
            dn_lens = []
            for s in snaps:
                vals = s["dicts"].get(c.name, [])
                lut = np.empty(max(len(vals), 1), dtype=np.int32)
                for i, v in enumerate(vals):
                    j = index.get(v)
                    if j is None:
                        j = len(values)
                        values.append(v)
                        index[v] = j
                    lut[i] = j
                col_luts.append(lut)
                dn_lens.append(len(vals))
            union_dicts[c.name] = values
            luts[c.name] = col_luts
            dict_state[c.name] = {
                "index": index,
                "luts": [col_luts[i][:dn_lens[i]].copy()
                         for i in range(ndn)],
                "dn_lens": dn_lens}

        null_columns = set()
        for s in snaps:
            null_columns |= s["null_columns"]

        per_dn: list[dict[str, np.ndarray]] = []
        counts = []
        for si, s in enumerate(snaps):
            # shared host-staging source (storage/store.py), with this
            # node's TEXT codes remapped into the union dictionary
            cols = dict(s["cols"])
            counts.append(s["count"])
            for c in td.columns:
                if c.type.kind == TypeKind.TEXT and len(cols[c.name]):
                    cols[c.name] = luts[c.name][si][cols[c.name]]
            for nc in null_columns:
                if f"__null.{nc}" not in cols:
                    cols[f"__null.{nc}"] = np.zeros(counts[-1], bool)
            per_dn.append(cols)

        from ..storage.batch import size_class
        from ..utils.dtypes import stage_cast
        padded = size_class(max(max(counts), 1))
        sh = NamedSharding(self.mesh, PS(self.axis))

        # codec: ONE global descriptor per eligible column, proven
        # against every shard's values at once (storage/codec.py) —
        # codes stay comparable across the mesh, like the TEXT union
        # dictionary.  TEXT code columns stay raw here: mesh union
        # codes live in a different value space than the per-store
        # codes the single-device ladder entry was proven on.
        text_names = {c.name for c in td.columns
                      if c.type.kind == TypeKind.TEXT}
        encs: dict = {}
        enc_aux: dict = {}
        shard_codes: dict = {}
        for colname in per_dn[0]:
            if colname in text_names:
                continue
            parts = [stage_cast(np.asarray(per_dn[si][colname]))
                     for si in range(ndn)]
            r = codec.encode_staged(name, colname,
                                    np.concatenate(parts)
                                    if ndn > 1 else parts[0])
            if r is None:
                continue
            codes, enc, aux = r
            encs[colname] = enc
            enc_aux[colname] = aux
            offs = np.cumsum([0] + [len(p) for p in parts])
            shard_codes[colname] = [codes[offs[i]:offs[i + 1]]
                                    for i in range(ndn)]

        arrs = {}
        nbytes = 0
        for colname, sample in per_dn[0].items():
            sample = stage_cast(sample)
            enc = encs.get(colname)
            if enc is not None:
                buf = np.zeros((ndn, padded), dtype=enc.code_dtype)
                for si in range(ndn):
                    a = shard_codes[colname][si]
                    buf[si, :len(a)] = a
            else:
                buf = np.zeros((ndn, padded, *sample.shape[1:]),
                               dtype=sample.dtype)
                for si in range(ndn):
                    a = per_dn[si][colname]
                    buf[si, :len(a)] = a
            arrs[colname] = jax.device_put(
                buf.reshape(ndn * padded, *buf.shape[2:]), sh)
            nbytes += buf.nbytes
        for colname, enc in encs.items():
            # aux arrays replicate per shard: a (ndn, len) tile sharded
            # on the mesh axis hands every shard its own (len,) copy
            aux = enc_aux[colname]
            rep = np.tile(aux, (ndn, 1))
            arrs[codec.aux_name(colname, enc)] = jax.device_put(
                rep.reshape(ndn * aux.shape[0]), sh)
            nbytes += rep.nbytes
        nrows = jax.device_put(np.asarray(counts, np.int64), sh)
        view = _MeshStoreView(td, union_dicts, dict_state, null_columns)
        codec.note_staged(view, encs)
        staged = _StagedTable(arrs, nrows, padded, view, vkey)
        POOL.note_upload(nbytes, puts=len(arrs) + 1)
        POOL.mesh_put(self, name, MeshEntry(
            name, vkey, staged, list(counts), dict_state,
            set(null_columns), nbytes, encs=encs,
            bytes_logical=codec.logical_nbytes(arrs)))
        return staged

    def _stage_incremental(self, name: str, ent, vkey: tuple):
        """Append-only growth on every DN: keep the resident sharded
        prefix, upload only the per-DN tail rows, extend the union
        dictionaries in place (append-only: resident codes stay valid).
        Returns a fresh pool entry, or None when any DN changed
        non-append-only (or shifted size class) — caller restages."""
        from ..storage.bufferpool import MeshEntry, POOL
        from ..storage.batch import size_class
        from ..utils.dtypes import stage_cast
        dns = self.cluster.datanodes
        if any(not hasattr(dn, "stores") for dn in dns):
            return None     # remote DNs: no mutation log to consult
        stores = []
        for dn in dns:
            st = dn.stores.get(name)
            if st is None:
                return None
            stores.append(st)
        new_counts = []
        for i, st in enumerate(stores):
            if st.version != vkey[i]:
                return None     # raced a writer; take the full path
            if vkey[i] != ent.vkey[i] and not st.appended_only_since(
                    ent.vkey[i], ent.counts[i]):
                return None
            new_counts.append(st.row_count())
        ndn = len(stores)
        P = ent.staged.padded
        if size_class(max(max(new_counts), 1)) != P:
            return None     # size class moved: buffers must grow
        td = self.cluster.catalog.table(name)
        value_cols = [c.name for c in td.columns]
        tails = [st.host_live_columns(value_cols, start=ent.counts[i])
                 for i, st in enumerate(stores)]

        # extend union dictionaries + LUTs, remap tail codes
        view = ent.staged.view
        for c in td.columns:
            if c.type.kind != TypeKind.TEXT:
                continue
            state = ent.dict_state[c.name]
            values = view.dicts[c.name].values
            index = state["index"]
            for i, st in enumerate(stores):
                vals = st.dicts[c.name].values
                lold = state["dn_lens"][i]
                if len(vals) > lold:
                    ext = np.empty(len(vals) - lold, np.int32)
                    for j, v in enumerate(vals[lold:]):
                        code = index.get(v)
                        if code is None:
                            code = len(values)
                            values.append(v)
                            index[v] = code
                        ext[j] = code
                    state["luts"][i] = np.concatenate(
                        [state["luts"][i], ext])
                    state["dn_lens"][i] = len(vals)
                tc = tails[i]
                if len(tc[c.name]):
                    tc[c.name] = state["luts"][i][tc[c.name]]

        # encoded columns: every tail must fit the entry's resident
        # descriptor (the prefix codes can't be rewritten in place).
        # Encode BEFORE any device work — a misfit, or a ladder that
        # moved past this entry, falls back to a full restage.
        for colname, enc in ent.encs.items():
            for i in range(ndn):
                if new_counts[i] <= ent.counts[i]:
                    continue
                codes = codec.encode_tail(
                    name, colname, enc,
                    stage_cast(np.asarray(tails[i][colname])))
                if codes is None:
                    return None
                tails[i][colname] = codes

        new_null = set(ent.null_columns)
        for st in stores:
            new_null |= set(st.null_columns)

        sh = NamedSharding(self.mesh, PS(self.axis))
        arrs = {}
        up = 0
        tail_total = sum(new_counts) - sum(ent.counts)

        def tail_piece(colname, i, length):
            t = tails[i].get(colname)
            if t is None:     # null mask with no NULLs on this DN
                t = np.zeros(length, bool)
            return stage_cast(t)

        aux_cols = codec.enc_names(ent.staged.arrs)
        aux_keys = set(aux_cols.values())
        for colname, devarr in ent.staged.arrs.items():
            if colname in aux_keys:
                continue
            new = devarr
            for i in range(ndn):
                lo, hi = ent.counts[i], new_counts[i]
                if hi <= lo:
                    continue
                t = tail_piece(colname, i, hi - lo)
                new = new.at[i * P + lo:i * P + hi].set(jnp.asarray(t))
                up += t.nbytes
            arrs[colname] = jax.device_put(new, sh)
        for colname, akey in aux_cols.items():
            enc = ent.encs[colname]
            if enc.family != "dict":
                arrs[akey] = ent.staged.arrs[akey]
                continue
            # dictionary tails may have extended the append-only LUT
            # in place: re-upload the fresh replicated copy (same pow2
            # capacity, so no program class changes)
            ah = codec.aux_host(name, colname, enc)
            if ah is None:
                return None
            arrs[akey] = jax.device_put(
                np.tile(ah, (ndn, 1)).reshape(ndn * ah.shape[0]), sh)
            up += ah.nbytes * ndn
        for c in sorted(new_null - ent.null_columns):
            # first NULLs arrived in a tail: the prefix mask is zeros
            buf = jnp.zeros(ndn * P, bool)
            for i in range(ndn):
                lo, hi = ent.counts[i], new_counts[i]
                if hi <= lo:
                    continue
                buf = buf.at[i * P + lo:i * P + hi].set(
                    jnp.asarray(tail_piece(f"__null.{c}", i, hi - lo)))
            arrs[f"__null.{c}"] = jax.device_put(buf, sh)
            view.null_columns.add(c)
        nrows = jax.device_put(np.asarray(new_counts, np.int64), sh)
        staged = _StagedTable(arrs, nrows, P, view, vkey)
        nbytes = sum(int(a.nbytes) for a in arrs.values())
        POOL.note_upload(up, tail_rows=tail_total,
                         puts=1 + sum(1 for k, a in arrs.items()
                                      if ent.staged.arrs.get(k) is not a))
        return MeshEntry(name, vkey, staged, list(new_counts),
                         ent.dict_state, new_null, nbytes,
                         encs=ent.encs,
                         bytes_logical=codec.logical_nbytes(arrs))

    # ------------------------------------------------------------------
    # exchange collectives (inside the traced program)
    # ------------------------------------------------------------------
    def _route_hash(self, b, keys):
        """uint64 routing hash of a local batch — bit-identical to the
        host tier's _route/_eval_host_key + locator placement."""
        hs = []
        for k in keys:
            if isinstance(k, E.TextExpr) or (
                    isinstance(k, E.Col)
                    and b.types[k.name].kind == TypeKind.TEXT):
                col = k.col if isinstance(k, E.TextExpr) else k
                d = b.dicts.get(col.name, [])
                transform = k.apply if isinstance(k, E.TextExpr) \
                    else (lambda s: s)
                lut = np.asarray(
                    [hash_string(transform(v)) for v in d] or [0],
                    dtype=np.uint64)
                codes = jnp.clip(b.cols[col.name], 0, len(lut) - 1)
                hs.append(jnp.asarray(lut)[codes])
            else:
                nm = b.nulls.get(k.name)
                arr = b.cols[k.name].astype(jnp.int64)
                if nm is not None:
                    # NULL keys coalesce onto one node (host tier rule)
                    arr = jnp.where(nm, 0, arr)
                hs.append(arr.astype(jnp.uint64))
        h = splitmix64_jax(hs[0])
        for x in hs[1:]:
            h = combine_jax(h, x)
        return h

    def _a2a_batch(self, b, keys, mult: int):
        """Pack rows per destination + one all_to_all per column.
        Returns (local redistributed DBatch, overflow scalar).

        The per-destination bucket is sized from the SOURCE batch's
        static padding (not the base table's): `src_pad/ndn * mult`,
        where `mult` is this exchange's ladder value — 1 assumes a
        uniform spread, overflow doubles it, and `next_pow2(src_pad)`
        is an absolute cap at which overflow is impossible (a source
        shard cannot send more rows than it has).  The pack is a gather:
        every slot of a destination's bucket finds its source row
        (`kernels.bucket_rows`: a search of one running count per
        destination — no argsort, no scatter) and the batch's columns
        and null masks come through that index as ONE gather of 32-bit
        rows (`kernels.take_rows`).  Dead rows are bound nowhere, so the
        exchange also compacts."""
        from .executor import DBatch
        from ..ops import kernels as K
        ndn = self.cluster.ndn
        if ndn == 1:
            # single-node mesh: routing is the identity; no collective —
            # and no materialization: the consumer fragment keeps
            # composing through the indirection in the same program
            return b, jnp.int64(0)
        b.ensure_all()   # exchange: rows physically move between shards
        src_pad = int(b.valid.shape[0])
        cap = next_pow2(src_pad)
        bucket = min(cap, max(64, next_pow2(-(-src_pad // ndn)) * mult))
        h = self._route_hash(b, keys)
        sid = (h % jnp.uint64(NUM_SHARDS)).astype(jnp.int64)
        smap = jnp.asarray(
            np.asarray(self.cluster.catalog.shard_map, np.int32))
        dest = jnp.where(b.valid, smap[sid].astype(jnp.int32), ndn)
        src, keep, overflow = K.bucket_rows(dest, ndn, bucket)
        moved = iter(K.take_rows(
            (*b.cols.values(), *b.nulls.values()), src, keep))

        def a2a(buf):
            return jax.lax.all_to_all(
                buf.reshape(ndn, bucket, *buf.shape[1:]),
                self.axis, 0, 0).reshape(buf.shape)

        cols = {n: a2a(next(moved)) for n in b.cols}
        nulls = {n: a2a(next(moved)) for n in b.nulls}
        return (DBatch(cols, a2a(keep), dict(b.types), dict(b.dicts),
                       nulls, spans=dict(b.spans)),
                jax.lax.psum(overflow, self.axis))

    def _a2a_sent_bytes(self, rb) -> int:
        """Bytes ONE chip sends over ICI in the all_to_all that produced
        `rb`, known when the program is traced: ndn - 1 of the ndn equal
        buckets of every column, null mask and the validity flags (the
        chip's own bucket does not leave it)."""
        ndn = self.cluster.ndn
        whole = sum(int(a.size) * a.dtype.itemsize
                    for a in (*rb.cols.values(), *rb.nulls.values(),
                              rb.valid))
        return whole // ndn * (ndn - 1)

    def _broadcast_batch(self, b):
        from .executor import DBatch
        if self.cluster.ndn == 1:
            return b     # identity broadcast: keep the indirection
        b.ensure_all()   # exchange: rows replicate to every shard

        def ag(arr):
            return jax.lax.all_gather(arr, self.axis, tiled=True)

        return DBatch({n: ag(a) for n, a in b.cols.items()},
                      ag(b.valid), dict(b.types), dict(b.dicts),
                      {n: ag(a) for n, a in b.nulls.items()},
                      spans=dict(b.spans))

    # ------------------------------------------------------------------
    @staticmethod
    def _bind(node, ex_batches: dict):
        if isinstance(node, ExchangeRef):
            batch = ex_batches.get(node.index)
            if batch is None:
                raise MeshUnsupported(
                    f"exchange {node.index} not materialized")
            return BatchSource(batch)
        clone = dataclasses.replace(node)
        for attr in ("child", "left", "right"):
            c = getattr(clone, attr, None)
            if isinstance(c, P.PhysNode):
                setattr(clone, attr, MeshRunner._bind(c, ex_batches))
        if getattr(clone, "inputs", None):
            clone.inputs = [MeshRunner._bind(c, ex_batches)
                            for c in clone.inputs]
        return clone

    def run(self, dp: DistPlan, snapshot_ts: int, txid: int,
            params: dict) -> dict:
        """Execute the DN side of `dp` on the mesh; returns a dict of
        {gather exchange index: DBatch} — every CN-bound exchange output,
        host-reachable."""
        from .executor import TRACE_HOST_SYNC, DBatch

        included = self._screen(dp)
        tables = set()
        for frag in dp.fragments:
            if frag.index not in included:
                continue
            for nd in P.walk(frag.plan):
                if isinstance(nd, P.SeqScan):
                    tables.add(nd.table.name)
        for t in tables:
            for dn in self.cluster.datanodes:
                if hasattr(dn, "stores") and t not in dn.stores:
                    raise MeshUnsupported(f"table {t} missing on dn")

        for k, (v, _t) in params.items():
            if not isinstance(v, (int, float, str, bool, type(None))):
                raise MeshUnsupported("non-scalar init-plan param")

        staged = {}
        for t in sorted(tables):
            with obs_trace.span("stage", table=t, tier="mesh") as sp:
                staged[t] = self._stage_table(t)
                sp.set(padded=staged[t].padded)
        if not staged:
            raise MeshUnsupported("no mesh-stageable scans")
        base_pad = max((s.padded for s in staged.values()), default=64)
        # ladder values (join factors, exchange bucket multipliers,
        # gather classes) LEARNED on a previous execution of the same
        # plan shape are remembered, so steady state runs the compiled
        # program exactly once — no overflow replay per query
        skey = self._shape_key(dp, staged, included)
        lkey = self._ladder_key(skey)
        factors, mults, gathers = \
            self._ladder.recall(lkey) or ({}, {}, {})
        for ex in dp.exchanges:
            if ex.kind == "redistribute":
                mults.setdefault(ex.index, 1)
            elif ex.kind in ("gather", "gather_one"):
                # per-gather output size classes: traced fragment
                # outputs are padded to static classes (a join's or a
                # laddered aggregate's buffer is a quarter of its
                # input), but the rows that actually cross to the CN
                # are usually few — start small, compact in-program,
                # grow on overflow (the same ladder joins, aggregates
                # and redistributes ride)
                gathers.setdefault(ex.index, min(base_pad, 1 << 16))
        for _attempt in range(plancache.Ladder.ATTEMPTS):
            try:
                out, meta, over_jids, a2a_over, g_over = self._execute(
                    dp, staged, snapshot_ts, txid, params,
                    dict(factors), dict(mults), dict(gathers),
                    included, skey)
            except TRACE_HOST_SYNC as e:
                raise MeshUnsupported(f"host sync in plan: {e}") from None
            # the program reports an overflow as a bit: each class that
            # overflowed doubles and the statement replays
            for ei in a2a_over:
                plancache.Ladder.grow(mults, ei)
            for jid in over_jids:
                if not plancache.Ladder.grow(factors, jid):
                    raise MeshUnsupported("join size ladder exhausted")
            for gi in g_over:
                plancache.Ladder.grow(gathers, gi)
            if not (a2a_over or over_jids or g_over):
                self._ladder.remember(lkey, factors, mults, gathers)
                result = {}
                # the gather span is the host side of every CN-bound
                # exchange output, which _call_program brought down with
                # the overflow vectors: live rows selected and re-padded,
                # then ONE batched put back for the CN fragment
                with obs_trace.span("gather", tier="mesh") as gsp:
                    host = []
                    for batch in out.values():
                        # only the live rows go on to the CN fragment,
                        # re-padded to their OWN size class: its eager
                        # kernels (final agg, sort) then compile and run
                        # at that size, not at the gather class — on a
                        # v5e the 12-operand final sort of Q1's 4 groups
                        # in a 65536-row buffer compiled in 443 s
                        valid = batch[1]
                        live = np.flatnonzero(valid)
                        rows = next_pow2(len(live))
                        if rows >= len(valid):
                            host.append(batch)      # as it came down
                            continue

                        def to_cn(a):
                            t = np.zeros((rows,) + a.shape[1:], a.dtype)
                            t[:len(live)] = a[live]
                            return t

                        host.append(jax.tree_util.tree_map(to_cn, batch))
                    # a statement's transient batch, a few KB freed
                    # with the statement: no residency for the pool
                    back = jax.device_put(host)  # otblint: disable=device-residency
                    for gi, (cols, valid, nulls) in zip(out, back):
                        result[gi] = DBatch(
                            cols, valid, dict(meta[gi]["types"]),
                            dict(meta[gi]["dicts"]), nulls)
                    gsp.set(h2d=1, h2d_bytes=sum(
                        a.nbytes for a in jax.tree_util.tree_leaves(host)))
                return result, included
        raise MeshUnsupported("size-class ladder exhausted")

    def warm(self, dp: DistPlan, snapshot_ts: int, params: dict) -> bool:
        """AOT warmup: run the plan once OFF the query path, discarding
        the result (reference has no analog — the reference's planner
        has no multi-second compile to hide).  Going through run()
        warms everything the first real execution needs: table staging,
        the traced+compiled shard_map programs (written to the
        persistent XLA cache and to the jit dispatch caches), AND the
        learned size-class ladder — every lifted param (a number, a
        date, a text param's dictionary code) is a traced input and in
        no key, so any later binding reuses all of it; one that selects
        more rows may overflow a class once, and the ladder only
        grows."""
        try:
            self.run(dp, snapshot_ts, 0, params)
            return True
        except MeshUnsupported:
            return False

    @staticmethod
    def _shape_key(dp, staged, included) -> tuple:
        """`(fragments, exchanges, tables)`: what a statement's program
        key and its ladder key both hold of the plan and the staged
        tables, computed ONCE a statement (`physical.plan_key` walks
        every included fragment)."""
        frags = []
        for f in dp.fragments:
            if f.index in included:
                key = P.plan_key(f.plan, _ALLOWED)
                if key is None:
                    raise MeshUnsupported("plan node outside the mesh")
                frags.append((f.index, key))
        return (
            tuple(frags),
            tuple((ex.index, ex.kind, tuple(ex.keys or ()),
                   ex.source_fragment,
                   tuple(getattr(ex, "sort_keys", None) or ()),
                   getattr(ex, "limit", None))
                  for ex in dp.exchanges),
            tuple((t, staged[t].padded,
                   tuple(sorted((c, len(d.values)) for c, d in
                         staged[t].view.dicts.items())),
                   # the staged-array namespace: a null column appearing
                   # after DML adds a __null input, which must recompile
                   # (the flat-arg list and in_specs grow with it)
                   tuple(sorted(staged[t].arrs)),
                   # quantized codec classes (storage/codec.py): an enc
                   # family/width/LUT-capacity change alters aux avals,
                   # so the class token must be key-visible
                   codec.codec_classes(staged[t].view))
                  for t in sorted(staged)))

    @staticmethod
    def _ladder_key(skey):
        """Identity of a plan shape + data scale, independent of the
        ladder values themselves — the key under which learned join
        factors / bucket multipliers / gather classes persist.  Of a
        table it holds the padded class, the staged names and the codec
        classes: a dictionary's length is the program key's alone."""
        frags, exchanges, tables = skey
        try:
            return hash((frags, exchanges,
                         tuple(e[:2] + e[3:] for e in tables)))
        except TypeError:
            raise MeshUnsupported("unhashable plan content") from None

    @staticmethod
    def _compact_local(b, gsz: int):
        """Inside the traced program: compress a fragment's output to
        its live prefix in a (static) gather-class buffer of gsz rows
        per shard.  Returns (cols, valid, nulls, overflowed?) — only
        these gsz rows cross device->host at the CN gather, instead of
        the worst-case padded buffer (at SF1 that was ~0.5 GB/query).
        Gather formulation: output slot j takes the input position
        where the live count first reaches j+1."""
        padded = int(b.valid.shape[0])
        csum = jnp.cumsum(b.valid.astype(jnp.int64))
        n_live = csum[-1]
        idx = jnp.clip(
            jnp.searchsorted(csum, jnp.arange(1, gsz + 1)), 0,
            padded - 1)
        valid = jnp.arange(gsz) < n_live
        over = (n_live > gsz).astype(jnp.int64)
        # indirection-aware: gather_rows composes the compaction index
        # straight through any join indirection, so a gather fragment
        # ending in a join chain ships gsz rows WITHOUT ever
        # materializing the full-width join output buffer
        cols, nulls = b.gather_rows(idx)
        return (cols, valid, nulls, over)

    @staticmethod
    def _topk_spec(ob, ex):
        """(key names, descs, limit) when this gather can cut to a
        per-shard top-k INSIDE the program — sort keys are plain
        non-TEXT columns without null masks (the common
        ORDER BY agg/col LIMIT n tail, e.g. TPC-H Q3/Q10/Q18).
        None = ship the full compacted gather (always correct)."""
        if not ex.sort_keys or not ex.limit:
            return None
        names, descs = [], []
        for k, desc in ex.sort_keys:
            if not isinstance(k, E.Col) or not ob.has_col(k.name) \
                    or ob.maybe_null(k.name) \
                    or ob.types[k.name].kind == TypeKind.TEXT:
                return None
            names.append(k.name)
            descs.append(bool(desc))
        return names, tuple(descs), int(ex.limit)

    @staticmethod
    def _topk_local(cols, valid, nulls, spec):
        """Sort the compacted gather buffer by the sort keys and keep
        the first `limit` rows (reference: SimpleSort on RemoteSubplan
        — each DN pre-sorts/cuts, the CN merge re-sorts ndn*limit
        rows instead of every group)."""
        from ..ops import kernels as K
        names, descs, limit = spec
        keys = tuple(cols[n] for n in names)
        pnames = sorted(cols)
        nnames = sorted(nulls)
        payload = tuple([cols[n] for n in pnames]
                        + [nulls[n] for n in nnames])
        out, s_valid = K.sort_rows(keys, valid, payload, descs, limit)
        new_cols = {n: out[i] for i, n in enumerate(pnames)}
        new_nulls = {n: out[len(pnames) + i]
                     for i, n in enumerate(nnames)}
        return new_cols, s_valid, new_nulls

    def _execute(self, dp, staged, snapshot_ts, txid, params, factors,
                 mults, gathers, included, skey):
        from .executor import (ExecContext, Executor, bind_text_params,
                               split_params)

        table_names = sorted(staged)
        gather_ex = [ex for ex in dp.exchanges
                     if ex.kind in ("gather", "gather_one")
                     and ex.source_fragment in included]
        if not gather_ex:
            raise MeshUnsupported("no gather exchange")
        gather_idx = [ex.index for ex in gather_ex]

        # canonical program signature: numeric params (lifted literals,
        # bound $n params, scalar-subquery results, and the union-
        # dictionary code of a text param compared with a column) are
        # MASKED out of the key and ride as TRACED inputs, so same-shape
        # statements with different literals reuse the compiled
        # shard_map program
        params = bind_text_params(
            (x for f in dp.fragments if f.index in included
             for x in P.walk_exprs(f.plan)),
            params, {t: staged[t].view for t in table_names}, "mesh")
        traced_names, baked = split_params(params)
        # a literal string predicate with more verdicts than a program
        # unrolls arrives as a bitmap over its dictionary's codes, an
        # ARGUMENT like the parameters above: which predicates those are
        # follows from the plan and the dictionaries' lengths, both in
        # the key, and the verdicts are the host's (exec/strtable.py)
        str_tables = self._str_tables(dp, included, staged)
        # nine parts, read by position (plancache._census_classes):
        # runner, fragments, exchanges, tables, the three ladders'
        # classes, baked values, traced types; hashable, since
        # `_ladder_key` hashed the plan's parts and `run` admits scalar
        # parameters alone
        prog_key = (
            id(self), *skey,
            tuple(sorted(factors.items())),
            tuple(sorted(mults.items())),
            tuple(sorted(gathers.items())),
            tuple(sorted((k, v) for k, (v, _t) in baked.items())),
            tuple((k, params[k][1]) for k in traced_names),
        )
        has_join = any(
            isinstance(n, P.HashJoin)
            for f in dp.fragments if f.index in included
            for n in P.walk(f.plan))
        cached = plancache.MESH.get(prog_key)
        if cached is not None:
            fn, meta = cached
            if has_join:
                from .executor import bump_stat
                bump_stat("mesh", "fused_join_hits")
            return self._call_program(fn, meta, gather_idx, staged,
                                      table_names, snapshot_ts, txid,
                                      params, str_tables)

        meta: dict = {"traced": traced_names}

        def otb_mesh(snap, txn, *flat):
            pvals = flat[:len(traced_names)]
            flat = flat[len(traced_names):]
            run_params = dict(baked)
            for name, pv in zip(traced_names, pvals):
                run_params[name] = (pv, params[name][1])
            bitmaps = {pred: (values, words) for (pred, values, _w), words
                       in zip(str_tables, flat)}
            flat = flat[len(str_tables):]
            arrs_by_table = {}
            i = 0
            for t in table_names:
                names = sorted(staged[t].arrs)
                arrs_by_table[t] = (
                    {n: flat[i + j] for j, n in enumerate(names)},
                    flat[i + len(names)][0])
                i += len(names) + 1
            ctx = ExecContext(
                stores={t: staged[t].view for t in table_names},
                snapshot_ts=snap, txid=txn, cache=None,
                params=run_params,
                staged=arrs_by_table,
                join_factors=dict(factors),
                str_tables=bitmaps)
            ex_batches: dict = {}
            overflows = []
            meta["ex_order"] = []
            meta["exchanges"] = meta["exchange_bytes"] = 0
            meta["pack_lanes"] = 0
            shape = meta["shape"] = {}
            join_reqs = []
            gather_out: dict = {}
            gather_over: list = []
            meta["gi_order"] = []
            for frag in dp.fragments:
                if frag.index not in included:
                    continue
                plan = self._bind(frag.plan, ex_batches)
                exe = Executor(ctx, frag_tag=frag.index)
                exe._traced = True
                b = exe.exec_node(plan)
                join_reqs.extend(exe.join_required)
                for k, v in exe.shape.items():
                    shape[k] = max(shape.get(k, 0), v) \
                        if k in obs_trace.SHAPE_MAXIMA \
                        else shape.get(k, 0) + v
                for ex in dp.exchanges:
                    if ex.source_fragment != frag.index:
                        continue
                    if ex.kind == "redistribute":
                        with jax.named_scope("otb.exchange"):
                            rb, over = self._a2a_batch(
                                b, ex.keys, mults.get(ex.index, 1))
                        ex_batches[ex.index] = rb
                        meta["ex_order"].append(ex.index)
                        if rb is not b:
                            meta["exchanges"] += 1
                            meta["exchange_bytes"] += self._a2a_sent_bytes(rb)
                            # the pack's destination slots: ndn * bucket
                            meta["pack_lanes"] += int(rb.valid.shape[0])
                            # and the padded rows it packs from
                            shape["exchange_src_lanes"] = max(
                                shape.get("exchange_src_lanes", 0),
                                b.padded)
                        overflows.append(over)
                    elif ex.kind == "broadcast":
                        with jax.named_scope("otb.exchange"):
                            ex_batches[ex.index] = \
                                self._broadcast_batch(b)
                    else:  # gather / gather_one: program output
                        ob = b
                        if ex.kind == "gather_one":
                            keep1 = jax.lax.axis_index(self.axis) == 0
                            ob = dataclasses.replace(
                                ob, valid=ob.valid & keep1)
                        meta[ex.index] = {"types": ob.types,
                                          "dicts": ob.dicts}
                        # the gather exchange's device side: only
                        # the compacted rows cross to the CN
                        with jax.named_scope("otb.exchange"):
                            cols, valid, nulls, gov = \
                                self._compact_local(ob, gathers[ex.index])
                        spec = self._topk_spec(ob, ex)
                        if spec is not None:
                            cols, valid, nulls = self._topk_local(
                                cols, valid, nulls, spec)
                        gather_out[ex.index] = (cols, valid, nulls)
                        meta["gi_order"].append(ex.index)
                        gather_over.append(
                            jax.lax.psum(gov, self.axis))
            missing = [gi for gi in gather_idx if gi not in gather_out]
            if missing:
                raise MeshUnsupported(f"gather {missing} not produced")
            a2a_over = jnp.stack(overflows) if overflows \
                else jnp.zeros(0, jnp.int64)
            meta["jid_order"] = [jid for jid, _r, _c in join_reqs]
            if join_reqs:
                join_over = jnp.stack([
                    jax.lax.psum((req > cap).astype(jnp.int64),
                                 self.axis)
                    for _jid, req, cap in join_reqs])
            else:
                join_over = jnp.zeros(0, jnp.int64)
            g_over = jnp.stack(gather_over) if gather_over \
                else jnp.zeros(0, jnp.int64)
            return (tuple(gather_out[gi] for gi in gather_idx),
                    a2a_over, join_over, g_over)

        in_specs = [PS(), PS()] + [PS()] * (len(traced_names)
                                            + len(str_tables))
        for t in table_names:
            in_specs.extend([PS(self.axis)] * (len(staged[t].arrs) + 1))

        kwargs = dict(mesh=self.mesh, in_specs=tuple(in_specs),
                      out_specs=(tuple((PS(self.axis), PS(self.axis),
                                        PS(self.axis))
                                       for _ in gather_idx),
                                 PS(), PS(), PS()))
        smapped = shard_map(otb_mesh, check_vma=False, **kwargs)
        fn = jax.jit(smapped)
        plancache.MESH.put(prog_key, (fn, meta))
        self._programs[prog_key] = True
        while len(self._programs) > 256:
            self._programs.pop(next(iter(self._programs)))
        return self._call_program(fn, meta, gather_idx, staged,
                                  table_names, snapshot_ts, txid,
                                  params, str_tables)

    def _str_tables(self, dp, included, staged) -> list:
        """[(literal StrPred, its dictionary's list, bitmap words)] of the
        included fragments, in plan order, one a predicate: those over a
        scanned table's TEXT column whose verdicts are a bitmap."""
        from . import strtable
        # which predicates read which table's column is the plan's own:
        # walked once a DistPlan (the plan cache hands the same one back)
        memo = dp.__dict__.setdefault("_literal_strpreds", {})
        preds = memo.get(tuple(sorted(included)))
        if preds is None:
            scans, found = {}, {}
            for f in dp.fragments:
                if f.index not in included:
                    continue
                for nd in P.walk(f.plan):
                    if isinstance(nd, P.SeqScan):
                        scans[nd.alias] = nd.table.name
                for x in P.walk_exprs(f.plan):
                    if isinstance(x, E.StrPred) and x.param is None:
                        found[x] = strtable.column_of(x).partition(".")
            # (a derived column's predicate stays the trace's constant)
            preds = memo[tuple(sorted(included))] = [
                (x, scans[alias], column)
                for x, (alias, _dot, column) in found.items()
                if alias in scans]
        out = []
        for x, table, column in preds:
            d = staged[table].view.dicts.get(column)
            words = strtable.resolve(x, d.values)[1] if d is not None \
                else None
            if words is not None:
                out.append((x, d.values, words))
        return out

    def _call_program(self, fn, meta, gather_idx, staged, table_names,
                      snapshot_ts, txid, params,
                      str_tables):  # otblint: sync-boundary
        # the ONE sync of a mesh program call, after the call: the three
        # overflow vectors and every gathered array in one device_get
        from .executor import stats_tier
        # `inputs`: the snapshot, the txid and every traced parameter as
        # numpy scalars of the device dtype: they travel with the
        # program's own argument transfer (no put, no eager convert of
        # their own); the staged arrays are resident
        with obs_trace.span("inputs"):
            flat_args = [np.int64(snapshot_ts), np.int64(txid)]
            for k in meta.get("traced", ()):
                v, t = params[k]
                flat_args.append(np.asarray(v, dtype=dev_dtype(t)))
            # the string predicates' bitmaps: host arrays of a few KB
            # to ~190 KB (1.5 M codes), riding the same transfer
            flat_args.extend(words for _p, _v, words in str_tables)
        for t in table_names:
            for n in sorted(staged[t].arrs):
                flat_args.append(staged[t].arrs[n])
            flat_args.append(staged[t].nrows)
        t0 = time.perf_counter()
        # the execute span covers the program call and the device_get of
        # what it returned — the mesh tier's one legal sync point per
        # call, so the span's wall time includes the device work
        with obs_trace.span("execute", tier="mesh") as sp:
            with stats_tier("mesh"):
                # executor counters inside the trace attribute to the
                # mesh tier (first call of a fresh program traces here)
                dev = fn(*flat_args)
            # the all_to_all exchanges this program holds and the bytes
            # one chip sends in them: fixed when the program was traced
            # (meta is filled by the trace the first call made)
            sp.set(exchanges=meta.get("exchanges", 0),
                   exchange_bytes=meta.get("exchange_bytes", 0),
                   pack_lanes=meta.get("pack_lanes", 0),
                   **meta.get("shape", {}))
            plancache.MESH.record_call(fn, t0)
            if EXPORT_HOOK is not None:
                EXPORT_HOOK("mesh", fn, tuple(flat_args))
            # every leaf's copy starts before any is waited for: one
            # round trip.  The gathered arrays come down WITH their
            # overflow vectors and are thrown away on the rare overflow
            host = jax.device_get(dev)
            outs, av, over_vec, gv = host
            sp.set(d2h=1, d2h_bytes=sum(
                a.nbytes for a in jax.tree_util.tree_leaves(host)))
            over_jids = sorted({jid for jid, ov in
                                zip(meta.get("jid_order", ()), over_vec)
                                if ov > 0})
            a2a_over = sorted({ei for ei, ov in
                               zip(meta.get("ex_order", ()), av)
                               if ov > 0})
            g_over = sorted({gi for gi, ov in
                             zip(meta.get("gi_order", ()), gv) if ov > 0})
            # a class overflowed: run() replays the statement one class
            # up (`retraces` of summary() sums these)
            sp.set(retraces=int(bool(over_jids or a2a_over or g_over)))
        return (dict(zip(gather_idx, outs)), meta, over_jids,
                a2a_over, g_over)


def mesh_runner_for(cluster) -> Optional[MeshRunner]:
    """Lazily build (and cache) the cluster's mesh runner; None when the
    deployment can't use the device tier."""
    r = getattr(cluster, "_mesh_runner", None)
    if r is not None:
        return r if isinstance(r, MeshRunner) else None
    try:
        runner = MeshRunner(cluster)
    except MeshUnsupported:
        cluster._mesh_runner = False
        return None
    cluster._mesh_runner = runner
    return runner
