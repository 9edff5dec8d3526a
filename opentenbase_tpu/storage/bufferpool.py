"""Device-resident columnar buffer pool: version-keyed HBM residency.

Reference analog: the buffer manager (src/backend/storage/buffer) — the
reference keeps hot heap pages pinned in shared_buffers so executors
never re-read disk for unchanged data.  Here the device HBM plays that
role for host-RAM chunk storage: staged (padded, concatenated, possibly
mesh-sharded) device columns stay resident ACROSS queries, keyed by the
per-store monotonic `version` counter (storage/store.py — bumped on
every mutation, process-globally unique so a recycled id() can never
alias).  The round-5 bench showed why: the mesh tier re-uploaded a full
host snapshot of every referenced table per query and ran Q1 at 0.27-
0.51 GB/s effective bandwidth — staging, not compute, was the bottleneck.

One pool serves every execution tier:

- single-device entries (exec/executor.py DeviceTableCache facade):
  per-store padded device columns, the fused tier and FQS scans read
  them; staged once per (store, version, column set).
- mesh entries (exec/mesh_exec.py): per-runner sharded arrays + union
  dictionaries + per-DN counts, keyed by the per-DN version tuple.
- host snapshots: the full live-row concatenation one store ships to
  the mesh owner (net/dn_server.py stage_table) or slices for spill
  passes (exec/spill.py) — version-keyed so an unchanged table never
  re-concatenates.

Budget + eviction mirror the compiled-program subsystem
(exec/plancache.py): one byte budget (OTB_DEVICE_CACHE_BYTES) over all
device entries, LRU eviction across both tiers; host snapshots have
their own smaller budget (OTB_HOST_SNAPSHOT_BYTES).

Invalidation is exact and lazy: DML/DDL/vacuum bump the store version,
the stale entry is detected (and dropped or tail-patched) on next
access; DROP/TRUNCATE paths call invalidate() eagerly so big tables
release HBM immediately.  Append-only growth takes the incremental
path: TableStore's mutation log proves every change since the cached
version touched only rows past the cached count, so staging uploads
just the tail instead of re-shipping the prefix (the dominant OLTP/
bulk-load pattern: INSERT then re-query).

Telemetry per table — hits / misses / bytes_live / evictions /
invalidations — surfaces as the otb_buffercache stat view
(parallel/statviews.py), next to otb_plancache.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import weakref

import numpy as np

from ..obs import trace as obs_trace
from ..obs import xray as obs_xray
from ..utils import locks, snapcheck
from . import codec

_LOCK = locks.RLock("storage.bufferpool._LOCK")
_SEQ = itertools.count()

_SYS_COLS = ("__xmin_ts", "__xmax_ts", "__xmin_txid", "__xmax_txid")
_NULL = "__null."


def _budget() -> int:
    """Byte budget over all device-resident entries (both tiers)."""
    try:
        return int(os.environ.get("OTB_DEVICE_CACHE_BYTES",
                                  str(8 << 30)))
    except ValueError:
        return 8 << 30


def _host_budget() -> int:
    """Byte budget for cached host snapshots (host RAM, not HBM)."""
    try:
        return int(os.environ.get("OTB_HOST_SNAPSHOT_BYTES",
                                  str(1 << 30)))
    except ValueError:
        return 1 << 30


@dataclasses.dataclass
class DevEntry:
    """Single-device tier: one store's padded device columns."""
    table: str
    version: int
    arrs: dict            # staged name -> device array [padded, ...]
    n: int                # live (staged) row count
    null_at_cache: set    # store.null_columns when staged
    nbytes: int           # actual device bytes (post-encoding)
    pins: int = 0         # refcount: >0 bars eviction (resident build
    # side of a streaming join, exec/morsel.py); guarded_by: _LOCK
    pins_by: dict = dataclasses.field(default_factory=dict)
    # consumer token -> refcount; sums to `pins`; guarded_by: _LOCK
    encs: dict = dataclasses.field(default_factory=dict)
    # staged name -> storage/codec.Enc for encoded columns (tail path)
    bytes_logical: int = 0  # unencoded bytes these arrays represent


@dataclasses.dataclass
class ChunkEntry:
    """Morsel tier: one fixed-shape row-range window of a store's host
    columns, staged to device.  All chunks of a stream share one padded
    shape (`chunk_rows`, storage/batch.py chunk_class) so the compiled
    per-chunk program never retraces; `live` is the real row count of
    this window (the tail chunk zero-pads).  Pinned while a stream
    holds it — eviction skips pinned entries."""
    table: str
    version: int
    start: int            # first source row of the window
    chunk_rows: int       # padded window shape (chunk_class-quantized)
    live: int             # real rows in [start, start+live)
    arrs: dict            # staged name -> device array [chunk_rows,...]
    nbytes: int           # actual device bytes (post-encoding)
    pins: int = 0         # guarded_by: _LOCK
    pins_by: dict = dataclasses.field(default_factory=dict)
    # consumer token -> refcount: a shared morsel stream
    # (exec/share.py) pins one window once per consumer, and a
    # consumer erroring mid-stream can only release its OWN pins —
    # never a pin another fragment is still probing; guarded_by: _LOCK
    bytes_logical: int = 0  # unencoded bytes this window represents


@dataclasses.dataclass
class MeshEntry:
    """Mesh tier: one table's sharded arrays + union-dict state."""
    table: str
    vkey: tuple           # per-DN store versions at staging time
    staged: object        # exec/mesh_exec._StagedTable
    counts: list          # per-DN live row counts
    dict_state: dict      # TEXT col -> {"index", "luts", "dn_lens"}
    null_columns: set     # union null-column set at staging time
    nbytes: int           # actual device bytes (post-encoding)
    encs: dict = dataclasses.field(default_factory=dict)
    # staged name -> storage/codec.Enc (incremental tail path)
    bytes_logical: int = 0  # unencoded bytes these shards represent


class DeviceBufferPool:
    """Version-keyed device residency with one LRU byte budget."""

    def __init__(self):
        self._dev: dict = {}    # id(store) -> [seq, DevEntry]
        self._mesh: dict = {}   # (runner_id, table) -> [seq, MeshEntry]
        self._host: dict = {}   # id(store) -> [seq, snapshot, nbytes]
        # morsel chunk windows: (id(store), start, chunk_rows,
        # names_key) -> [seq, ChunkEntry]
        self._chunks: dict = {}
        # entries must not outlive their owners: a weakref per store /
        # mesh runner drops the owner's entries at GC, so the pool never
        # pins device arrays for dead nodes (the per-node caches this
        # replaces died with their nodes; the shared pool must match)
        self._refs: dict = {}   # id(owner) -> weakref
        # table -> [hits, misses, evictions, invalidations, pins,
        # unpins]
        self._stats: dict[str, list] = {}
        self.uploaded_bytes = 0   # cumulative host->device bytes staged
        self.tail_rows = 0        # rows staged via the incremental path
        # pin ledger (the PR-10 slot-ledger pattern): every pin must be
        # balanced by an unpin, and eviction must never destroy a
        # pinned entry silently.  pins_total == unpins_total +
        # live-pinned (in-dict entries + orphans invalidation popped
        # while still pinned — their holders unpin through the entry
        # object they kept).
        self._pins_total = 0      # guarded_by: _LOCK
        self._unpins_total = 0    # guarded_by: _LOCK
        self._orphans: list = []  # guarded_by: _LOCK — popped-but-pinned

    def _watch_store(self, store):
        # caller holds _LOCK
        key = id(store)
        if key in self._refs:
            return

        def drop(_r, pool=weakref.ref(self), key=key):
            p = pool()
            if p is None:
                return
            with _LOCK:
                p._dev.pop(key, None)
                p._host.pop(key, None)
                for ck in [k for k in p._chunks if k[0] == key]:
                    p._chunks.pop(ck, None)
                p._refs.pop(key, None)
        try:
            self._refs[key] = weakref.ref(store, drop)
        except TypeError:
            pass

    def _watch_runner(self, runner):
        # caller holds _LOCK
        key = id(runner)
        if key in self._refs:
            return

        def drop(_r, pool=weakref.ref(self), key=key):
            p = pool()
            if p is None:
                return
            with _LOCK:
                for k in [k for k in p._mesh if k[0] == key]:
                    p._mesh.pop(k, None)
                p._refs.pop(key, None)
        try:
            self._refs[key] = weakref.ref(runner, drop)
        except TypeError:
            pass

    # -- accounting -----------------------------------------------------
    def _tstats(self, table: str) -> list:
        s = self._stats.get(table)
        if s is None:
            s = self._stats[table] = [0, 0, 0, 0, 0, 0]
        elif len(s) < 6:
            s.extend([0] * (6 - len(s)))
        return s

    def note_upload(self, nbytes: int, tail_rows: int = 0,
                    puts: int = 0):
        """`puts`: the device arrays the upload put (`h2d_puts` of a
        statement's summary)."""
        with _LOCK:
            self.uploaded_bytes += int(nbytes)
            self.tail_rows += int(tail_rows)
        if nbytes:
            obs_trace.event("upload", bytes=int(nbytes),
                            tail_rows=int(tail_rows), h2d=int(puts))

    def stats_rows(self) -> list[tuple]:
        """(table, hits, misses, bytes_live, evictions, invalidations,
        pinned, pins, unpins, bytes_logical, bytes_resident) rows for
        the otb_buffercache view (system otb_ tables omitted).
        `pinned` is the live pinned-entry count; pins/unpins are the
        cumulative refcount ledger; bytes_logical is what the resident
        entries would occupy UNENCODED vs bytes_resident, the actual
        post-encoding device bytes (== bytes_live) — their ratio is the
        effective-cache multiplier the codecs buy.  Columns append so
        positional consumers of the original six stay valid."""
        with _LOCK:
            live: dict[str, int] = {}
            logical: dict[str, int] = {}
            pinned: dict[str, int] = {}

            def acct(e):
                live[e.table] = live.get(e.table, 0) + e.nbytes
                logical[e.table] = logical.get(e.table, 0) \
                    + (e.bytes_logical or e.nbytes)

            for _s, e in self._dev.values():
                acct(e)
                if e.pins > 0:
                    pinned[e.table] = pinned.get(e.table, 0) + 1
            for _s, e in self._mesh.values():
                acct(e)
            for _s, e in self._chunks.values():
                acct(e)
                if e.pins > 0:
                    pinned[e.table] = pinned.get(e.table, 0) + 1
            rows = []
            for t in sorted(set(self._stats) | set(live)):
                if t.startswith("otb_"):
                    continue
                h, m, ev, inv, pi, up = self._tstats(t) \
                    if t in self._stats else (0, 0, 0, 0, 0, 0)
                rows.append((t, h, m, live.get(t, 0), ev, inv,
                             pinned.get(t, 0), pi, up,
                             logical.get(t, 0), live.get(t, 0)))
            return rows

    def totals(self) -> dict:
        with _LOCK:
            return {
                "hits": sum(s[0] for s in self._stats.values()),
                "misses": sum(s[1] for s in self._stats.values()),
                "evictions": sum(s[2] for s in self._stats.values()),
                "invalidations": sum(s[3] for s in self._stats.values()),
                "bytes_live": sum(e.nbytes for _s, e in
                                  self._dev.values())
                + sum(e.nbytes for _s, e in self._mesh.values())
                + sum(e.nbytes for _s, e in self._chunks.values()),
                "bytes_logical": sum(
                    (e.bytes_logical or e.nbytes)
                    for tier in (self._dev, self._mesh, self._chunks)
                    for _s, e in tier.values()),
                "uploaded_bytes": self.uploaded_bytes,
                "tail_rows": self.tail_rows,
                "pins": self._pins_total,
                "unpins": self._unpins_total,
                "pinned_live": self._live_pinned_locked(),
                "chunks_live": len(self._chunks),
            }

    def clear(self):
        """Drop everything (tests)."""
        with _LOCK:
            self._dev.clear()
            self._mesh.clear()
            self._host.clear()
            self._chunks.clear()
            self._refs.clear()
            self._orphans.clear()
            self._pins_total = 0
            self._unpins_total = 0

    # -- pin ledger -----------------------------------------------------
    def _live_pinned_locked(self) -> int:
        # caller holds _LOCK
        return (sum(e.pins for _s, e in self._dev.values())
                + sum(e.pins for _s, e in self._chunks.values())
                + sum(e.pins for e in self._orphans))

    def _note_pin_locked(self, entry, table: str, consumer=None):
        # caller holds _LOCK
        entry.pins += 1
        entry.pins_by[consumer] = entry.pins_by.get(consumer, 0) + 1
        self._pins_total += 1
        self._tstats(table)[4] += 1

    def _note_unpin_locked(self, entry, table: str, consumer=None):
        # caller holds _LOCK
        held = entry.pins_by.get(consumer, 0)
        assert held > 0, (
            f"bufferpool: unpin for {table} by consumer {consumer!r} "
            f"holding no pin (holders: {sorted(map(repr, entry.pins_by))})")
        entry.pins -= 1
        assert entry.pins >= 0, \
            f"bufferpool: unbalanced unpin for {table}"
        if held == 1:
            del entry.pins_by[consumer]
        else:
            entry.pins_by[consumer] = held - 1
        self._unpins_total += 1
        self._tstats(table)[5] += 1
        if entry.pins == 0:
            # identity filter: dataclass __eq__ would compare arrays
            self._orphans = [o for o in self._orphans if o is not entry]

    def check_pin_ledger(self):
        """Ledger invariant (mirrors the PR-10 slot ledgers): every pin
        is either balanced by an unpin or visible as a live pinned
        entry — eviction/invalidation can never make a pin disappear —
        and every live entry's total refcount equals the sum of its
        per-consumer counts, all positive (a consumer can never hold a
        negative balance or release another consumer's pin)."""
        with _LOCK:
            live = self._live_pinned_locked()
            assert self._pins_total == self._unpins_total + live, (
                f"bufferpool pin ledger broken: pins={self._pins_total} "
                f"unpins={self._unpins_total} live={live}")
            entries = ([e for _s, e in self._dev.values()]
                       + [e for _s, e in self._chunks.values()]
                       + list(self._orphans))
            for e in entries:
                assert e.pins == sum(e.pins_by.values()), (
                    f"bufferpool pin ledger broken for {e.table}: "
                    f"pins={e.pins} != per-consumer "
                    f"{dict(e.pins_by)}")
                assert all(c > 0 for c in e.pins_by.values()), (
                    f"bufferpool pin ledger broken for {e.table}: "
                    f"non-positive consumer count {dict(e.pins_by)}")
            return {"pins": self._pins_total,
                    "unpins": self._unpins_total, "live": live}

    # -- eviction -------------------------------------------------------
    def _evictable_locked(self) -> list:
        """(kind, key, seq, entry) over every UNPINNED device entry —
        pinned entries (streaming joins' resident build sides, in-flight
        morsel chunks) are wired down and never eviction candidates."""
        return ([("dev", k, s, e)
                 for k, (s, e) in self._dev.items() if e.pins == 0]
                + [("mesh", k, s, e)
                   for k, (s, e) in self._mesh.items()]
                + [("chunk", k, s, e)
                   for k, (s, e) in self._chunks.items()
                   if e.pins == 0])

    def _pop_entry_locked(self, kind: str, key):
        d = {"dev": self._dev, "mesh": self._mesh,
             "chunk": self._chunks}[kind]
        d.pop(key, None)

    def trim(self):
        """Enforce the device byte budget: evict globally-LRU UNPINNED
        entries (single-device, mesh and chunk tiers) until the
        resident population fits.  A lone over-budget entry stays — the
        active query holds references anyway, so evicting it frees
        nothing."""
        budget = _budget()
        with obs_xray.wait_event("bufpool-evict"), _LOCK:
            while True:
                items = self._evictable_locked()
                resident = (
                    sum(e.nbytes for _s, e in self._dev.values())
                    + sum(e.nbytes for _s, e in self._mesh.values())
                    + sum(e.nbytes for _s, e in self._chunks.values()))
                if len(items) <= 1 or resident <= budget:
                    return
                kind, key, _s, e = min(items, key=lambda it: it[2])
                self._pop_entry_locked(kind, key)
                self._tstats(e.table)[2] += 1

    def shed_coldest(self, frac: float = 0.5) -> int:
        """Memory-pressure relief (exec/shield.py): evict the coldest
        UNPINNED device entries until `frac` of the resident bytes are
        freed, regardless of budget.  Returns bytes freed.  Unlike
        trim() this may evict down to nothing — after a
        RESOURCE_EXHAUSTED the retry restages only what the failed
        dispatch actually needs.  Pinned entries survive: evicting a
        wired chunk/build side would crash the very stream the relief
        is trying to save."""
        freed = 0
        with obs_xray.wait_event("bufpool-evict"), _LOCK:
            resident = (
                sum(e.nbytes for _s, e in self._dev.values())
                + sum(e.nbytes for _s, e in self._mesh.values())
                + sum(e.nbytes for _s, e in self._chunks.values()))
            target = int(resident * max(0.0, min(1.0, frac)))
            while freed < target:
                items = self._evictable_locked()
                if not items:
                    break
                kind, key, _s, e = min(items, key=lambda it: it[2])
                self._pop_entry_locked(kind, key)
                self._tstats(e.table)[2] += 1
                freed += e.nbytes
        return freed

    def _trim_host(self):
        budget = _host_budget()
        with _LOCK:
            while len(self._host) > 1 and \
                    sum(nb for _s, _snap, nb in
                        self._host.values()) > budget:
                key = min(self._host, key=lambda k: self._host[k][0])
                self._host.pop(key)

    # -- invalidation ---------------------------------------------------
    def invalidate(self, store):
        """Eagerly drop every entry backed by this store (DROP TABLE,
        TRUNCATE, vacuum, ALTER fan-out); mesh entries of the same table
        go too — their per-DN version tuple is stale by construction."""
        table = store.td.name
        with _LOCK:
            dropped = self._dev.pop(id(store), None)
            hit = dropped is not None
            if dropped is not None and dropped[1].pins > 0:
                self._orphans.append(dropped[1])
            self._host.pop(id(store), None)
            for key in [k for k, (_s, e) in self._mesh.items()
                        if e.table == table]:
                self._mesh.pop(key)
                hit = True
            for key in [k for k in self._chunks if k[0] == id(store)]:
                _s, e = self._chunks.pop(key)
                # a stream may hold this entry mid-flight: the arrays
                # stay alive through its reference and it unpins through
                # the entry object — track it so the ledger still sees
                # the live pin (check_pin_ledger)
                if e.pins > 0:
                    self._orphans.append(e)
                hit = True
            if hit:
                self._tstats(table)[3] += 1
        # cached RESULTS over this table die with its residency (outside
        # _LOCK: the result cache has its own lock and never calls back
        # into the pool) — DML is caught lazily by the version-tuple
        # mismatch, but DROP/TRUNCATE must reclaim CN memory now
        from ..exec.share import RESULT_CACHE
        RESULT_CACHE.invalidate_table(table)

    # ------------------------------------------------------------------
    # single-device tier (exec/executor.py scans, fused tier, FQS)
    # ------------------------------------------------------------------
    # version-gate: e.version == ver
    def get_device(self, store, colnames):
        """Staged (padded, concatenated) device columns for a store at
        its current version: value columns + MVCC sys columns + null
        masks.  Returns (arrs, n).  Warm path is a dict lookup; version
        drift re-stages — incrementally (tail only) when the store's
        mutation log proves append-only growth."""
        table = store.td.name
        ver = store.version
        nullwant = {_NULL + c for c in colnames
                    if c in store.null_columns}
        want = set(colnames) | set(_SYS_COLS) | nullwant
        with _LOCK:
            ent = self._dev.get(id(store))
            e = ent[1] if ent is not None else None
            if ent is not None:
                ent[0] = next(_SEQ)
            if e is not None and e.version == ver \
                    and want <= set(e.arrs):
                self._tstats(table)[0] += 1
                if obs_trace.ENABLED:
                    obs_trace.event("pool", table=table, hit=True)
                if snapcheck.enabled():
                    snapcheck.serve(
                        "storage.bufferpool.DeviceBufferPool"
                        ".get_device",
                        versions=[(table, e.version)],
                        expect_versions=[(table, ver)])
                return e.arrs, e.n
        obs_trace.event("pool", table=table, hit=False)
        # stage outside the lock (defensive: racing stagers both build,
        # last put wins — same policy as the compiled-program caches)
        stage_span = obs_trace.span("stage", table=table, tier="single")
        with stage_span:
            done = False
            if e is not None and e.version == ver:
                # same version, new columns: keep the resident buffers,
                # stage only what is missing (padded_of skips __enc.*
                # aux arrays — their shapes aren't the row geometry)
                padded = codec.padded_of(e.arrs)
                add, up, aencs = self._stage_columns(
                    store, want - set(e.arrs), e.n, padded)
                arrs = dict(e.arrs)
                arrs.update(add)
                encs = dict(e.encs)
                encs.update(aencs)
                n, tail = e.n, 0
                done = True
            elif e is not None \
                    and store.appended_only_since(e.version, e.n):
                r = self._tail_stage(store, e, want)
                if r is not None:
                    arrs, n, up, tail, encs = r
                    done = True
            if not done:
                # full (re)stage — also the fallback when an encoded
                # column's tail drifted out of its proven range and the
                # descriptor must re-choose (key-visible, like join-
                # ladder growth)
                from .batch import size_class
                n = store.row_count()
                padded = size_class(max(n, 1))
                arrs, up, encs = self._stage_columns(store, want, n,
                                                     padded)
                tail = 0
        stage_span.set(rows=n, tail_rows=tail)
        if up:
            # every array that is not the resident entry's own was put
            had = e.arrs if e is not None else {}
            obs_trace.event("upload", table=table, bytes=int(up),
                            h2d=sum(1 for k, a in arrs.items()
                                    if had.get(k) is not a))
        nbytes = sum(int(a.nbytes) for a in arrs.values())
        codec.note_staged(store, encs)
        with _LOCK:
            st = self._tstats(table)
            st[1] += 1
            if e is not None and e.version != ver and tail == 0:
                st[3] += 1    # stale residency fully replaced
            self.uploaded_bytes += up
            self.tail_rows += tail
            self._dev[id(store)] = [next(_SEQ), DevEntry(
                table, ver, arrs, n, set(store.null_columns), nbytes,
                encs=encs, bytes_logical=codec.logical_nbytes(arrs))]
            self._watch_store(store)
        self.trim()
        return arrs, n

    def _stage_columns(self, store, names, n: int, padded: int):
        """Full staging of rows [0:n] for the given staged-namespace
        names (value columns / __xmin_ts... / __null.c) into padded
        device arrays.  Eligible integer columns stage ENCODED
        (storage/codec.py): the device buffer holds the narrow codes
        and the column's aux array (__enc.*) rides along as a traced
        input.  Returns (arrs, bytes_uploaded, encs)."""
        import jax

        from ..utils.dtypes import stage_cast
        table = store.td.name
        plain = sorted({nm for nm in names if not nm.startswith("__")}
                       | {nm[len(_NULL):] for nm in names
                          if nm.startswith(_NULL)})
        host = store.host_live_columns(plain)
        arrs = {}
        encs = {}
        up = 0
        for name in names:
            h = stage_cast(host[name])
            r = codec.encode_staged(table, name, h[:n])
            if r is not None:
                code, enc, aux = r
                encs[name] = enc
                buf = np.zeros(padded, dtype=code.dtype)
                buf[:n] = code
                arrs[name] = jax.device_put(buf)
                arrs[codec.aux_name(name, enc)] = jax.device_put(aux)
                up += buf.nbytes + aux.nbytes
            else:
                buf = np.zeros((padded, *h.shape[1:]), dtype=h.dtype)
                buf[:n] = h[:n]
                arrs[name] = jax.device_put(buf)
                up += buf.nbytes
        return arrs, up, encs

    def _tail_stage(self, store, e: DevEntry, want):
        """Append-only growth: keep the device prefix, upload only rows
        [e.n:n].  Columns never staged before (or null masks that
        already had prefix NULLs) stage in full; masks whose first NULL
        arrived in the tail get a zeros prefix for free.  Encoded
        columns encode the tail under the entry's EXISTING descriptor
        (resident codes stay valid); a tail outside the proven range
        returns None and the caller takes the full-restage path.
        Dictionary tails may extend the append-only LUT — the aux
        array re-uploads (tiny), the resident codes don't move."""
        import jax
        import jax.numpy as jnp

        from ..utils.dtypes import stage_cast
        from .batch import size_class
        table = store.td.name
        n = store.row_count()
        padded = size_class(max(n, 1))
        aux_keys = set(codec.enc_names(e.arrs).values())
        all_names = (set(e.arrs) - aux_keys) | set(want)
        fresh_nulls = {nm for nm in all_names - set(e.arrs)
                       if nm.startswith(_NULL)
                       and nm[len(_NULL):] not in e.null_at_cache}
        full_names = all_names - set(e.arrs) - fresh_nulls
        plain = sorted({nm for nm in e.arrs if not nm.startswith("__")}
                       | {nm[len(_NULL):] for nm in fresh_nulls})
        tail_host = store.host_live_columns(plain, start=e.n)
        # encode every tail FIRST: a tail outside the proven range
        # PROMOTES that one column (full re-encode under a widened
        # descriptor via the _stage_columns path below) while every
        # other column still takes the tail path — the bounded,
        # key-visible recompile of join-ladder growth, never a full
        # restage of the whole table
        tails = {}
        promote = set()
        if n > e.n:
            for name in e.arrs:
                if name in aux_keys:
                    continue
                t = stage_cast(tail_host[name])
                enc = e.encs.get(name)
                if enc is not None:
                    t = codec.encode_tail(table, name, enc, t)
                    if t is None:
                        promote.add(name)
                        continue
                tails[name] = t
        arrs = {}
        up = 0
        for name, old in e.arrs.items():
            if name in aux_keys or name in promote:
                continue
            if int(old.shape[0]) != padded:
                buf = jnp.zeros((padded, *old.shape[1:]), old.dtype)
                old = buf.at[:e.n].set(old[:e.n])
            t = tails.get(name)
            if t is not None:
                old = old.at[e.n:n].set(jnp.asarray(t))
                up += t.nbytes
            arrs[name] = old
        for name, enc in e.encs.items():
            if name in promote:
                continue     # fresh aux stages with the new descriptor
            akey = codec.aux_name(name, enc)
            if akey not in e.arrs:
                continue
            if enc.family == "dict" and n > e.n:
                aux = codec.aux_host(table, name, enc)
                if aux is None:
                    return None   # ladder moved past the entry
                arrs[akey] = jax.device_put(aux)
                up += aux.nbytes
            else:
                arrs[akey] = e.arrs[akey]
        for name in fresh_nulls:
            buf = jnp.zeros(padded, bool)
            t = tail_host.get(name)
            if t is not None and n > e.n:
                buf = buf.at[e.n:n].set(jnp.asarray(t))
                up += t.nbytes
            arrs[name] = buf
        encs = {k: v for k, v in e.encs.items() if k not in promote}
        if full_names or promote:
            add, up2, aencs = self._stage_columns(
                store, sorted(set(full_names) | promote), n, padded)
            arrs.update(add)
            encs.update(aencs)
            up += up2
        return arrs, n, up, n - e.n, encs

    # ------------------------------------------------------------------
    # morsel chunk tier (exec/morsel.py streaming windows)
    # ------------------------------------------------------------------
    def pin_table(self, store):
        """Pin the store's resident device entry (a streaming join's
        build side must survive per-chunk pressure relief).  Returns
        the DevEntry handle for unpin_table, or None when nothing
        current is resident — the caller stages via get_device first."""
        with _LOCK:
            ent = self._dev.get(id(store))
            if ent is None or ent[1].version != store.version:
                return None
            self._note_pin_locked(ent[1], ent[1].table)
            return ent[1]

    def unpin_table(self, entry: DevEntry):
        with _LOCK:
            self._note_unpin_locked(entry, entry.table)

    # version-gate: ent[1].version == ver
    def get_chunk(self, store, host_cols: dict, start: int,
                  chunk_rows: int, encs: dict = None,
                  consumer=None) -> ChunkEntry:
        """One fixed-shape streaming window of `host_cols` (the staged
        namespace: value columns + MVCC sys columns + null masks),
        staged to device and returned PINNED — the caller unpins via
        unpin_chunk when the window's program call has consumed it.
        device_put is async, so fetching chunk i+1 before blocking on
        chunk i's output double-buffers the host→device copy.  Windows
        are version-keyed like every pool entry; a re-requested warm
        window is a hit (repeat streams over an unchanged table).
        `encs` (from codec.ensure_classes at stream start) encodes the
        window's eligible columns — ensured against the FULL host
        column, so every window of a stream provably shares one
        program class."""
        import jax

        from ..utils.dtypes import stage_cast
        table = store.td.name
        ver = store.version
        # the quantized codec classes are part of the window key: a
        # warm raw window must never alias an encoded stream (mixed
        # avals inside one stream would fork its program class)
        key = (id(store), int(start), int(chunk_rows),
               tuple(sorted(host_cols)),
               tuple(sorted((c, codec.codec_class(en))
                            for c, en in (encs or {}).items())))
        with _LOCK:
            ent = self._chunks.get(key)
            if ent is not None and ent[1].version == ver:
                ent[0] = next(_SEQ)
                self._tstats(table)[0] += 1
                self._note_pin_locked(ent[1], table, consumer)
                if snapcheck.enabled():
                    snapcheck.serve(
                        "storage.bufferpool.DeviceBufferPool"
                        ".get_chunk",
                        versions=[(table, ent[1].version)],
                        expect_versions=[(table, ver)])
                return ent[1]
            if ent is not None:
                self._chunks.pop(key, None)
                if ent[1].pins > 0:
                    self._orphans.append(ent[1])
                self._tstats(table)[3] += 1
        # stage outside the lock (same policy as get_device)
        total = len(next(iter(host_cols.values()))) if host_cols else 0
        live = max(0, min(total, start + chunk_rows) - start)
        arrs = {}
        up = 0
        for name, arr in host_cols.items():
            h = stage_cast(arr)
            r = codec.encode_window(table, name, h[start:start + live]) \
                if (encs and name in encs) else None
            if r is not None:
                code, enc, aux = r
                buf = np.zeros(chunk_rows, dtype=code.dtype)
                if live:
                    buf[:live] = code
                arrs[name] = jax.device_put(buf)
                arrs[codec.aux_name(name, enc)] = jax.device_put(aux)
                up += buf.nbytes + aux.nbytes
            else:
                buf = np.zeros((chunk_rows, *h.shape[1:]),
                               dtype=h.dtype)
                if live:
                    buf[:live] = h[start:start + live]
                arrs[name] = jax.device_put(buf)
                up += buf.nbytes
        e = ChunkEntry(table, ver, int(start), int(chunk_rows),
                       int(live), arrs, up,
                       bytes_logical=codec.logical_nbytes(arrs))
        with _LOCK:
            self._tstats(table)[1] += 1
            self.uploaded_bytes += up
            self._chunks[key] = [next(_SEQ), e]
            self._note_pin_locked(e, table, consumer)
            self._watch_store(store)
        self.trim()
        return e

    def pin_chunk(self, entry: ChunkEntry, consumer=None):
        """Additional per-consumer pin on an already-staged window — a
        shared morsel stream (exec/share.py) fans one leader-staged
        window into every follower, each holding its own refcount."""
        with _LOCK:
            self._note_pin_locked(entry, entry.table, consumer)
        return entry

    def unpin_chunk(self, entry: ChunkEntry, consumer=None):
        with _LOCK:
            self._note_unpin_locked(entry, entry.table, consumer)

    # ------------------------------------------------------------------
    # mesh tier (exec/mesh_exec.py staging)
    # ------------------------------------------------------------------
    def mesh_get(self, runner, table: str, vkey: tuple):
        """Entry for (runner, table) at exactly this per-DN version
        tuple, or None.  Counts the hit/miss; a stale entry counts an
        invalidation but stays resident for mesh_peek's incremental
        tail-patch."""
        with _LOCK:
            ent = self._mesh.get((id(runner), table))
            st = self._tstats(table)
            if ent is not None and ent[1].vkey == vkey:
                ent[0] = next(_SEQ)
                st[0] += 1
                obs_trace.event("pool", table=table, hit=True)
                return ent[1]
            st[1] += 1
            if ent is not None:
                st[3] += 1
            obs_trace.event("pool", table=table, hit=False)
            return None

    def mesh_peek(self, runner, table: str):
        """The resident entry regardless of version (incremental path)."""
        with _LOCK:
            ent = self._mesh.get((id(runner), table))
            return ent[1] if ent is not None else None

    def mesh_put(self, runner, table: str, entry: MeshEntry):
        with _LOCK:
            self._mesh[(id(runner), table)] = [next(_SEQ), entry]
            self._watch_runner(runner)
        self.trim()

    # ------------------------------------------------------------------
    # host snapshots (dn_server stage_table wire op, spill passes)
    # ------------------------------------------------------------------
    # version-gate: store.version == ver
    def host_snapshot(self, store) -> dict:
        """One store's live columns + dictionaries at its current
        version — {"version", "count", "cols", "dicts",
        "null_columns"}.  Version-cached: an unchanged table never
        re-concatenates (the shared staging source for the dn_server
        stage_table op and the mesh runner's in-process snapshots).
        The build re-reads the store version after materializing and
        retries on movement: without the stability loop a DML landing
        mid-concatenation produced a snapshot TAGGED with the old
        version but containing (some of) the new rows — exactly the
        torn entry peek_host_snapshot's version gate cannot catch."""
        snap = self.peek_host_snapshot(store)
        if snap is not None:
            return snap
        while True:
            ver = store.version
            cols = store.host_live_columns([c.name for c in
                                            store.td.columns])
            n = len(next(iter(cols.values()))) if cols \
                else store.row_count()
            snap = {"version": ver, "count": n, "cols": cols,
                    "dicts": {c: list(d.values)
                              for c, d in store.dicts.items()},
                    "null_columns": set(store.null_columns)}
            if store.version == ver:
                break
        if snapcheck.enabled():
            snapcheck.serve(
                "storage.bufferpool.DeviceBufferPool.host_snapshot",
                versions=[(store.td.name, snap["version"])],
                expect_versions=[(store.td.name, ver)])
        nbytes = sum(int(a.nbytes) for a in cols.values())
        if nbytes <= _host_budget():
            with _LOCK:
                self._host[id(store)] = [next(_SEQ), snap, nbytes]
                self._watch_store(store)
            self._trim_host()
        return snap

    def resident(self, store) -> bool:
        """Does this store have a device entry at its CURRENT version?
        (warm-start assertions, tests)."""
        with _LOCK:
            ent = self._dev.get(id(store))
            return ent is not None and ent[1].version == store.version

    # version-gate: ent[1]["version"] == ver
    def peek_host_snapshot(self, store):
        """The cached host snapshot IF current, else None (never
        builds) — spill passes reuse it instead of re-concatenating."""
        with _LOCK:
            ent = self._host.get(id(store))
            ver = store.version
            if ent is not None and ent[1]["version"] == ver:
                ent[0] = next(_SEQ)
                if snapcheck.enabled():
                    snapcheck.serve(
                        "storage.bufferpool.DeviceBufferPool"
                        ".peek_host_snapshot",
                        versions=[(store.td.name,
                                   ent[1]["version"])],
                        expect_versions=[(store.td.name, ver)])
                return ent[1]
        return None


#: process-global pool — every LocalNode / DataNode / MeshRunner in the
#: process shares one budget (entries are keyed by store identity, so
#: nodes never alias each other's tables)
POOL = DeviceBufferPool()


def _metrics_samples():
    """Registry collector: pool totals as samples (obs/metrics.py)."""
    for k, v in POOL.totals().items():
        yield (f"otb_buffercache_{k}", {}, v)


from ..obs.metrics import REGISTRY as _METRICS  # noqa: E402
_METRICS.register_collector("bufferpool", _metrics_samples)
