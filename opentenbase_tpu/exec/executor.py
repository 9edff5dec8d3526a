"""Fragment executor: runs a physical plan over one datanode's stores.

Reference analog: src/backend/executor (ExecutorStart/Run, ExecProcNode
Volcano loop).  Architectural differences (SURVEY.md §7.1):

- Whole-batch execution: each operator consumes/produces a DBatch — padded
  device arrays + a validity mask — instead of pulling tuples.  Padding is
  power-of-two size classes so XLA compiles one program per class.
- The scan stages table chunks into a device cache once per table version
  (the device is the buffer cache; host RAM is the source of truth) and
  fuses MVCC visibility + quals + projection in one jitted kernel.
- NULLs are per-column boolean masks (DBatch.nulls) flowing from storage
  bitmaps through scans, joins (null-extension), aggregates and sorts;
  expressions compile to (value, null-mask) pairs (exec/expr_compile.py)
  so the NOT NULL fast paths carry zero mask overhead.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..catalog import types as T
from ..catalog.types import SqlType, TypeKind
from ..obs import trace as obs_trace
from ..ops import kernels as K
from ..plan import exprs as E
from ..plan import physical as P
from ..plan.planner import PlannedStmt, rewrite
from ..storage import codec
from ..storage.batch import next_pow2, size_class
from ..storage.store import ABORTED_TS, TableStore
from ..utils.dtypes import (bits_to_float, dev_dtype, device_float,
                            float_to_bits)
from ..utils.hashing import hash_columns_jax
from ..utils import locks
from . import strtable


class ExecError(Exception):
    pass


# ---------------------------------------------------------------------------
# executor telemetry (surfaced by the otb_execstats view,
# parallel/statviews.py).  Per-tier counter bundles: "single" is the
# eager per-operator dispatch, "fused"/"mesh" count TRACE-time events
# (a cached program re-executes without re-tracing, so those tiers'
# structural counters grow once per compile) plus program-hit counts.
# All increments go through bump_stat() under STATS_LOCK, and the
# attribution tier is thread-local, so concurrent CN-server threads
# neither lose increments nor cross-attribute each other's tiers.
# ---------------------------------------------------------------------------
STAT_FIELDS = ("joins", "index_compositions", "deferred_cols",
               "eager_cols", "cols_materialized", "bytes_materialized",
               "host_syncs", "fused_join_hits")
STATS_LOCK = locks.Lock("exec.executor.STATS_LOCK")
EXEC_STATS: dict = {t: {f: 0 for f in STAT_FIELDS}   # guarded_by: STATS_LOCK
                    for t in ("single", "fused", "mesh", "morsel")}
_TIER = threading.local()   # per-thread counter attribution

#: late-materialization master switch — off reverts joins to the eager
#: full-width gather path (the bit-identical baseline the tests compare
#: against)
LATE_MAT = os.environ.get("OTB_LATE_MAT", "1") != "0"


def _cur_tier() -> str:
    return getattr(_TIER, "value", "single")


# Trace-time counter bumps are sanctioned: they fire once per compile
# (Python side of the trace), never inside the compiled program.
def bump_stat(tier: str, field: str, n: int = 1):  # otblint: disable=trace-purity
    with STATS_LOCK:
        EXEC_STATS[tier][field] += n


def _bump(field: str, n: int = 1):
    """Thread-safe increment against the current attribution tier."""
    bump_stat(_cur_tier(), field, n)


@contextlib.contextmanager
def stats_tier(tier: str):
    """Attribute executor counters to `tier` for the duration (the
    fused/mesh tiers wrap their trace + execution in this)."""
    prev = _cur_tier()
    _TIER.value = tier
    try:
        yield
    finally:
        _TIER.value = prev


def exec_stats_rows() -> list:
    """(tier, *STAT_FIELDS) rows for the otb_execstats view."""
    with STATS_LOCK:
        return [(t, *(EXEC_STATS[t][f] for f in STAT_FIELDS))
                for t in ("single", "fused", "mesh", "morsel")]


def exec_stats_snapshot() -> dict:
    """Flat totals across tiers (delta accounting in tests)."""
    with STATS_LOCK:
        return {f: sum(EXEC_STATS[t][f] for t in EXEC_STATS)
                for f in STAT_FIELDS}


def _arr_bytes(a, n: int) -> int:
    """Bytes of an n-row gather of a column shaped like `a` (works on
    tracers: shape/dtype only)."""
    per = a.dtype.itemsize
    for d in a.shape[1:]:
        per *= int(d)
    return per * n


@dataclasses.dataclass
class LazyCol:
    """A deferred (late-materialized) column: `src` holds the payload in
    SOURCE row space and `idx` maps output positions to source rows.
    Joins compose `idx` instead of gathering `src`, so a left-deep join
    chain moves O(out_size) indices per join instead of O(width x
    out_size) payload values (reference contrast: ExecHashJoin copies
    minimal tuples into the hash/output slots at every join).

    `null_src` is the source-space null mask (gathered through `idx` at
    materialization); `null_out` is an OUTPUT-space mask OR'd on top —
    outer-join null extension, which exists only in the join's row
    space."""
    src: object
    idx: object
    null_src: object = None
    null_out: object = None

    def value(self):
        return self.src[self.idx]

    def null(self):
        m = None
        if self.null_src is not None:
            m = self.null_src[self.idx]
        if self.null_out is not None:
            m = self.null_out if m is None else (m | self.null_out)
        return m

    def dispatches(self) -> int:
        """The device ops `value()` and `null()` launch, run eagerly."""
        return 1 + (self.null_src is not None) * (
            1 + (self.null_out is not None))


# a pytree, so a whole program can take one (executor._gather_live)
jax.tree_util.register_dataclass(
    LazyCol, data_fields=["src", "idx", "null_src", "null_out"],
    meta_fields=[])


@dataclasses.dataclass
class DBatch:
    cols: dict[str, object]            # name -> jnp array [P]
    valid: object                      # jnp bool [P]
    types: dict[str, SqlType]
    dicts: dict[str, list]             # TEXT col name -> code->str list
    nulls: dict[str, object] = dataclasses.field(default_factory=dict)
    # late materialization: deferred columns living behind an
    # indirection (see LazyCol).  `cols`/`nulls` hold only materialized
    # columns; `types`/`dicts` always cover every column.
    lazy: dict[str, LazyCol] = dataclasses.field(default_factory=dict)
    # what the host KNOWS of a column's range when the program is built:
    # name -> an upper bound on max - min of its values, for a staged
    # integer column carried unchanged from its scan (the column's codec
    # class, storage/codec.span_bound: program-key material).  A join
    # chooses its algorithm by it (ops/kernels.join_build); an operator
    # that computes a column leaves it out: nothing is known of it.
    spans: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def padded(self) -> int:
        return int(self.valid.shape[0])

    def count(self) -> int:
        return int(jnp.sum(self.valid))

    # -- late-materialization surface ----------------------------------
    def names(self) -> list[str]:
        return list(self.cols) + [n for n in self.lazy
                                  if n not in self.cols]

    def has_col(self, name: str) -> bool:
        return name in self.cols or name in self.lazy

    def maybe_null(self, name: str) -> bool:
        """Whether the column can carry a null mask (no materialization)."""
        if name in self.nulls:
            return True
        lc = self.lazy.get(name)
        return lc is not None and (lc.null_src is not None
                                   or lc.null_out is not None)

    def _materialize_one(self, name: str):
        lc = self.lazy.pop(name)
        _bump("cols_materialized")
        _bump("bytes_materialized",
              _arr_bytes(lc.src, int(lc.idx.shape[0])))
        self.cols[name] = lc.value()
        m = lc.null()
        if m is not None:
            self.nulls[name] = m

    def ensure(self, names) -> "DBatch":
        """Materialize exactly the named columns (unknown names are
        fine: init-plan params etc. are not batch columns)."""
        if self.lazy:
            for n in names:
                if n in self.lazy:
                    self._materialize_one(n)
        return self

    def ensure_all(self) -> "DBatch":
        """The single materialization pass: a width-consuming operator
        (Sort, Window, exchange, final projection) needs real columns."""
        if self.lazy:
            for n in list(self.lazy):
                self._materialize_one(n)
        return self

    def col(self, name: str):
        if name in self.lazy:
            self._materialize_one(name)
        return self.cols[name]

    def col_opt(self, name: str):
        if name in self.lazy:
            self._materialize_one(name)
        return self.cols.get(name)

    def gather_rows(self, take):
        """(cols, nulls) gathered at output positions `take`, composing
        straight through any indirection — a len(take)-row consumer
        (e.g. the mesh gather compaction) never pays a full-width
        materialization of the source row space."""
        cols, nulls = {}, {}
        composed: dict = {}
        for n, a in self.cols.items():
            cols[n] = a[take]
        for n, m in self.nulls.items():
            nulls[n] = m[take]
        for n, lc in self.lazy.items():
            key = id(lc.idx)
            src_idx = composed.get(key)
            if src_idx is None:
                src_idx = lc.idx[take]
                composed[key] = src_idx
                _bump("index_compositions")
            _bump("cols_materialized")
            _bump("bytes_materialized",
                  _arr_bytes(lc.src, int(take.shape[0])))
            cols[n] = lc.src[src_idx]
            m = None
            if lc.null_src is not None:
                m = lc.null_src[src_idx]
            if lc.null_out is not None:
                no = lc.null_out[take]
                m = no if m is None else (m | no)
            if m is not None:
                nulls[n] = m
        return cols, nulls


def _empty_batch(types: dict[str, SqlType], dicts: dict) -> DBatch:
    cols = {n: jnp.zeros(256, dtype=dev_dtype(t)) for n, t in types.items()}
    return DBatch(cols, jnp.zeros(256, dtype=bool), dict(types), dict(dicts))


class DeviceTableCache:
    """Per-node facade over the process-global device buffer pool
    (storage/bufferpool.py) — the bufmgr analog: device HBM caches host
    chunks, version-keyed, under one OTB_DEVICE_CACHE_BYTES budget with
    LRU eviction and an incremental tail path for append-only growth.
    Kept as a facade so every existing `node.cache` call site works
    unchanged while all nodes share one budget + telemetry."""

    # version-gate: POOL.get_device(store, colnames)
    # (pure delegate: the pool compares entry.version == store.version
    # before serving and restages on mismatch)
    def get(self, store: TableStore, colnames: list[str]):
        from ..storage.bufferpool import POOL
        return POOL.get_device(store, colnames)

    def invalidate(self, store: TableStore):
        from ..storage.bufferpool import POOL
        POOL.invalidate(store)


def text_param_key(param: tuple) -> str:
    """The name under which a text parameter's dictionary code rides in
    `params`: one per (parameter, table, column), because one `$n` set
    against two columns is two codes of two dictionaries."""
    name, table, column = param
    return f"{name}@{table}.{column}"


def bind_text_params(exprs, params: dict, stores: dict, tier: str) -> dict:
    """`params` for a COMPILED tier: every text parameter that `exprs`
    compare with a column (StrPred.param) leaves as a string and comes
    back as that column's dictionary code in `stores` (the dictionaries
    this tier's scans put into their batches: a DataNode's own, or the
    mesh's union), under `text_param_key`; -1, a code no row holds,
    where the dictionary holds no such string.  An int rides as a traced
    scalar like any numeric parameter, so no string reaches a program
    key.  `Executor._prep` is the one place that reads the binding.
    Timed as a `bind` span whose `dict_miss` counts the strings no
    dictionary held.  `params` itself comes back when it holds no
    string."""
    if not any(isinstance(v, str) for v, _t in params.values()):
        return params
    out, misses = dict(params), 0
    with obs_trace.span("bind", tier=tier) as sp:
        for x in exprs:
            if not (isinstance(x, E.StrPred) and x.param is not None):
                continue
            name, table, column = x.param
            key = text_param_key(x.param)
            v = params[name][0]
            if key in out or not isinstance(v, str):
                continue
            store = stores.get(table)
            d = store.dicts.get(column) if store is not None else None
            if d is None:
                raise ExecError(f"no dictionary for {table}.{column}")
            code = d.code_of(v)
            misses += code < 0
            out[key] = (code, T.INT32)
            out.pop(name, None)
        sp.set(dict_miss=misses)
    return out


# what jax raises where a traced value feeds a host branch (`bool()`,
# `int()`, `np.asarray` of a tracer): how a compiled tier learns that a
# plan its screen let through still syncs
TRACE_HOST_SYNC = (jax.errors.TracerBoolConversionError,
                   jax.errors.ConcretizationTypeError,
                   jax.errors.TracerArrayConversionError)


def split_params(params: dict) -> tuple:
    """`(traced_names, baked)` of a compiled tier's `params` (bound by
    `bind_text_params` first): a number rides its program as a traced
    argument, named here in sorted order, the order of the call's
    values; everything else (a string no column took, a bool, a NULL:
    they change a program's structure) is a constant of the program and
    belongs in its key."""
    traced_names = tuple(sorted(
        k for k, (v, _t) in params.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)))
    return traced_names, {k: params[k] for k in params
                          if k not in traced_names}


@dataclasses.dataclass
class ExecContext:
    stores: dict[str, TableStore]
    snapshot_ts: int
    txid: int
    cache: DeviceTableCache
    params: dict[str, tuple] = dataclasses.field(default_factory=dict)
    # init-plan results: name -> (value, SqlType)
    staged: Optional[dict] = None
    # fused-execution override: table -> (arrs, n) traced arrays replacing
    # the device cache inside a jitted fragment program (exec/fused.py).
    # n may itself be traced (per-shard row counts under shard_map).
    join_size_factor: int = 1
    # traced joins can't sync their output size: out_size =
    # max(probe, build) padded * factor; the mesh runner doubles the
    # factor of exactly the joins that report overflow and re-traces
    # (the size-class ladder, SURVEY §7.3).  join_factors maps a stable
    # join id (fragment tag, sequence within fragment) -> factor so a
    # small-probe/large-output join can grow without inflating every
    # other join's buffers.
    join_factors: Optional[dict] = None
    # a compiled tier's string-predicate bitmaps (exec/strtable.py):
    # literal StrPred -> (the dictionary's list it was resolved against,
    # the traced int32 words), bound where a batch carries that very list
    str_tables: Optional[dict] = None


# the device-side name of each plan node's own ops (ops/kernels.py has
# the vocabulary); a node not listed scans, filters or projects
_NODE_SCOPES = {"Agg": "otb.agg", "HashJoin": "otb.join_expand",
                "Sort": "otb.sort", "Window": "otb.sort"}


@dataclasses.dataclass
class _DictView:
    """A batch's dictionary as the expression compiler reads one."""
    values: list


class Executor:
    #: True inside a jit trace (exec/fused.py): host-sync shortcuts like
    #: count()-sized output classes switch to static worst-case shapes
    _traced = False
    #: False disables whole-fragment fusion (InstrumentedExecutor: the
    #: EXPLAIN ANALYZE path runs eagerly so EVERY node gets actuals)
    _fuse = True

    def __init__(self, ctx: ExecContext, frag_tag=None):
        self.ctx = ctx
        # traced-join overflow telemetry: (join id, required_rows,
        # out_size) per join, checked host-side after the program runs
        # (mesh runner doubles that join's factor on overflow)
        self.join_required: list = []
        self.frag_tag = frag_tag
        self._join_seq = 0
        # what the traversal built, for the compiled tiers to put on
        # their `execute` span (obs/trace.py `summary()`): joins
        # answered by a mask, sorted aggregates, the largest one's lanes
        # (its INPUT's padded rows) and the largest OUTPUT class; of
        # those joins the anti ones, joins through join_expand's
        # left_outer arm, the largest class a semi or anti join with a
        # residual EXPANDS into (0: every one was answered by a mask),
        # the largest code set or bitmap a string predicate brings, the
        # aggregates in `final` mode and the padded lanes the largest
        # one's partials arrive in (`exchange_src_lanes` is the mesh
        # tier's own: the largest source class a redistribute packs from)
        self.shape = dict.fromkeys(
            obs_trace.SHAPE_SUMS + obs_trace.SHAPE_MAXIMA, 0)

    # ------------------------------------------------------------------
    def run(self, planned: PlannedStmt):
        for ip in planned.init_plans:
            with obs_trace.span("initplan", plan=ip.name):
                vals = scalars_from_batch(self.exec_node(ip.plan),
                                          ip.outputs())
            self.ctx.params.update(vals)
        out = self.exec_node(planned.plan)
        return out

    # ------------------------------------------------------------------
    def _prep(self, e: E.Expr, batch: Optional[DBatch] = None) -> E.Expr:
        """Substitute init-plan results and bound parameters before
        compiling; with the `batch` the expression is about to read,
        also a literal string predicate's bitmap where a compiled tier
        bound one for the dictionary that batch carries (CodeBitmap;
        any other literal compiles against the batch's dictionaries),
        and count what the predicate brings (`strpred_codes`).  A text
        parameter compared with a column
        (StrPred.param) has ONE reading, here: where a compiled tier
        bound it to the column's dictionary code (bind_text_params), a
        compare of the column's codes with that traced scalar; where the
        string itself arrives (the host tier, a DataNode's or the
        coordinator's fragment), the literal's own StrPred, which
        compiles against the dictionaries of the batch it filters
        (re-encoded after an exchange, so no stored code would do).
        Both keep SQL's three-valued result for NULL rows."""
        params = self.ctx.params
        tables = self.ctx.str_tables or {}

        def sub(x: E.Expr):
            if isinstance(x, E.Col) and x.name in params:
                v, t = params[x.name]
                return E.Lit(v, t)
            if isinstance(x, E.StrPred) and x.param is None:
                values = batch.dicts.get(strtable.column_of(x)) \
                    if batch is not None else None
                if values is None:
                    return None
                bound = tables.get(x)
                if bound is not None and bound[0] is values:
                    brings = len(values)
                    x = E.CodeBitmap(x.col, bound[1],
                                     x.kind in strtable.NEGATED)
                else:
                    codes, _words = strtable.resolve(x, values)
                    brings = len(values) if codes is None else len(codes)
                self.shape["strpred_codes"] = max(
                    self.shape["strpred_codes"], brings)
                return x
            if isinstance(x, E.StrPred):
                code = params.get(text_param_key(x.param))
                if code is not None:
                    return E.Cmp("=" if x.kind == "eq" else "<>", x.col,
                                 E.Lit(code[0], T.INT32))
                v = params.get(x.param[0], (None,))[0]
                if not isinstance(v, str):
                    raise ExecError(
                        f"text parameter {x.param[0]} is not bound")
                return E.StrPred(x.col, x.kind, (v,))
            return None
        return rewrite(e, sub)

    @staticmethod
    def _dictviews(batch: DBatch):
        return {n: _DictView(v) for n, v in batch.dicts.items()}

    @staticmethod
    def _env(batch: DBatch):
        """Eval namespace: columns plus null masks under NULLKEY."""
        from .expr_compile import NULLKEY
        if not batch.nulls:
            return batch.cols
        env = dict(batch.cols)
        for n, m in batch.nulls.items():
            env[NULLKEY + n] = m
        return env

    def _ensure_expr(self, e: E.Expr, batch: DBatch) -> E.Expr:
        """Prep `e` and materialize exactly the deferred columns it
        touches — expression eval gathers on demand, never the whole
        carried width.  Must run BEFORE compile: the null-awareness set
        (frozenset(batch.nulls)) is part of the compiled program."""
        pe = self._prep(e, batch)
        if batch.lazy:
            # sorted: a set of names iterates in string-hash order, which
            # differs from process to process — the gathers would be
            # traced in another order, the program text would change, and
            # the persistent compilation cache would miss after a restart
            batch.ensure(sorted(_cols_of(pe)))
        return pe

    def _eval(self, e: E.Expr, batch: DBatch):
        """Value-only eval (garbage at NULL positions)."""
        from .expr_compile import compile_expr
        pe = self._ensure_expr(e, batch)
        return compile_expr(pe, self._dictviews(batch),
                            frozenset(batch.nulls))(self._env(batch))

    def _eval_pair(self, e: E.Expr, batch: DBatch):
        """(value, null_mask|None) eval; the mask is broadcast to batch
        shape so downstream gathers can index it."""
        from .expr_compile import compile_pair
        pe = self._ensure_expr(e, batch)
        vf, nf = compile_pair(pe, self._dictviews(batch),
                              frozenset(batch.nulls))
        env = self._env(batch)
        val = vf(env)
        if nf is None:
            return val, None
        mask = nf(env)
        if getattr(mask, "ndim", 1) == 0:
            mask = jnp.broadcast_to(mask, batch.valid.shape)
        return val, mask

    def _eval_pred(self, e: E.Expr, batch: DBatch):
        """SQL 3VL predicate eval: True where definitely true."""
        from .expr_compile import compile_pred
        pe = self._ensure_expr(e, batch)
        return compile_pred(pe, self._dictviews(batch),
                            frozenset(batch.nulls))(self._env(batch))

    # ------------------------------------------------------------------
    def exec_node(self, node: P.PhysNode) -> DBatch:
        if not self._traced and self._fuse:
            from .fused import try_fused
            out = try_fused(self, node)
            if out is not None:
                return out
        kind = type(node).__name__
        m = getattr(self, f"_exec_{kind.lower()}", None)
        if m is None:
            raise ExecError(f"no executor for {kind}")
        # a program step: the ops this node traces (its expressions,
        # masks, late gathers) carry the step's scope; a kernel's or a
        # child node's own scope, further in, wins (ops/kernels.py)
        with jax.named_scope(_NODE_SCOPES.get(kind, "otb.scan")):
            return m(node)

    # ---- scan ----
    def _scan_base(self, table, alias: str, filters, outputs,
                   extra_needed: set = frozenset()):
        """Shared scan scaffolding (SeqScan + AnnSearch): stage needed
        columns via the device cache, build the qualified-name eval
        namespace, fuse MVCC visibility + filter quals into one mask."""
        store = self.ctx.stores.get(table.name)
        if store is None:
            raise ExecError(f"no store for table {table.name}")
        # substitute init-plan results first: a '__initplanN' Col is a
        # parameter, not a table column
        filters = [self._prep(f) for f in filters]
        outputs = [(n, self._prep(e)) for n, e in (outputs or [])]
        needed = set(extra_needed)
        for f in filters:
            needed |= {c.split(".", 1)[1] if "." in c else c
                       for c in _cols_of(f)}
        for _, oe in outputs:
            needed |= {c.split(".", 1)[1] if "." in c else c
                       for c in _cols_of(oe)}
        staged = (self.ctx.staged or {}).get(table.name)
        if staged is not None:
            # fused/mesh path: traced program inputs; n may be a traced
            # per-shard scalar, so the static pad comes from the arrays
            # (codec.padded_of skips __enc.* aux arrays — their shapes
            # are (1,)/(cap,), not the padded row geometry)
            arrs, n = staged
            padded_static = codec.padded_of(arrs)
        else:
            arrs, n = self.ctx.cache.get(store, sorted(needed))
            # quarter-step size classes: the pad is whatever the cache
            # staged (size_class, not next_pow2) — read it off the
            # arrays, never recompute
            padded_static = codec.padded_of(arrs) if arrs else None

        # codec decode (storage/codec.py): staged columns may be
        # encoded (pack/for/dict codes + traced aux arrays).  Decode is
        # an elementwise map XLA fuses into the consumers, so payload
        # columns never materialize decoded outside the final
        # projection; predicates on encoded columns compare in code
        # space below and skip even that.
        encm = codec.enc_names(arrs)

        def _dcol(name):
            a = arrs[name]
            k = encm.get(name)
            if k is None:
                return a
            return K.decode_column(a, arrs[k], codec.family_of(k))

        qcols, types, dicts, qnulls = {}, {}, {}, {}
        for c in store.td.columns:
            qname = f"{alias}.{c.name}"
            if c.name in arrs:
                qcols[qname] = _dcol(c.name)
            if f"__null.{c.name}" in arrs:
                qnulls[qname] = arrs[f"__null.{c.name}"]
            types[qname] = c.type
            if c.type.kind == TypeKind.TEXT and c.name in store.dicts:
                dicts[qname] = store.dicts[c.name].values

        padded = padded_static if padded_static is not None \
            else next_pow2(max(n, 1))
        base = DBatch(qcols, jnp.ones(padded, dtype=bool), types, dicts,
                      qnulls)
        vis = K.visibility_mask(
            _dcol("__xmin_ts"), _dcol("__xmax_ts"), _dcol("__xmin_txid"),
            _dcol("__xmax_txid"), jnp.int64(self.ctx.snapshot_ts),
            jnp.int64(self.ctx.txid), jnp.int64(ABORTED_TS))
        vis = vis & (jnp.arange(padded) < n)
        for f in filters:
            m = self._pred_on_codes(f, arrs, encm, alias)
            vis = vis & (m if m is not None
                         else self._eval_pred(f, base))
        return store, base, vis, arrs, n, padded, outputs, dicts

    def _pred_on_codes(self, f, arrs, encm: dict, alias: str):
        """Predicate eval in code space: a bare `col <op> literal` over
        an encoded, null-free column compares shifted codes against the
        traced literal (ops/kernels.py cmp_on_codes) — no padding
        select, no decode for filter-only columns.  Live rows compare
        exactly (code = value - lo + 1 is order-preserving); padding
        rows are masked by the scan's row-count belt.  Returns None
        when the shape doesn't qualify and the 3VL path must run."""
        if not encm or not isinstance(f, E.Cmp) \
                or f.op not in ("=", "<>", "<", "<=", ">", ">="):
            return None
        lhs, rhs, op = f.left, f.right, f.op
        if isinstance(rhs, E.Col) and isinstance(lhs, E.Lit):
            lhs, rhs = rhs, lhs
            op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)
        if not (isinstance(lhs, E.Col) and isinstance(rhs, E.Lit)):
            return None
        # storage-representation alignment (expr_compile.py Cmp): a
        # DECIMAL column stores value * 10**scale, so an int / coarser-
        # scale literal must rescale UP to the column's scale (exact);
        # shapes the eval path handles by rescaling the COLUMN fall
        # back to the 3VL path
        lt, rt = lhs.type, rhs.type
        ik = (TypeKind.INT32, TypeKind.INT64, TypeKind.DATE)
        if lt.kind == TypeKind.DECIMAL:
            rs = rt.scale if rt.kind == TypeKind.DECIMAL else 0
            if (rt.kind != TypeKind.DECIMAL and rt.kind not in ik) \
                    or rs > lt.scale:
                return None
            mult = 10 ** (lt.scale - rs)
        elif lt.kind in ik and rt.kind in ik:
            mult = 1
        else:
            return None
        cname = lhs.name.split(".", 1)[1] if "." in lhs.name else lhs.name
        k = encm.get(cname)
        if k is None or f"__null.{cname}" in arrs:
            return None
        v = rhs.value
        if v is None:
            return None
        vdt = getattr(v, "dtype", None)
        if vdt is not None:
            if not jnp.issubdtype(vdt, jnp.integer):
                return None
        elif not isinstance(v, (int, np.integer)):
            return None
        if mult != 1:
            v = v * mult
        return K.cmp_on_codes(arrs[cname], arrs[k], codec.family_of(k),
                              op, v)

    def _exec_seqscan(self, node: P.SeqScan) -> DBatch:
        (_store, base, vis, _arrs, _n, _padded, outputs,
         dicts) = self._scan_base(node.table, node.alias, node.filters,
                                  node.outputs)
        out_cols, out_types, out_dicts, out_nulls = {}, {}, {}, {}
        # what the host knows of each output's range: the class of the
        # ENCODED array this scan reads (a raw array, e.g. an index
        # scan's gathered subset, proves nothing whatever the store's
        # last staging recorded)
        classes = dict(codec.codec_classes(_store))
        encm = codec.enc_names(_arrs)
        spans = {}
        for name, oe in outputs:
            out_cols[name], nm = self._eval_pair(oe, base)
            if nm is not None:
                out_nulls[name] = nm
            out_types[name] = oe.type
            d = _dict_for_expr(oe, dicts)
            if d is not None:
                out_dicts[name] = d
            plain = oe.name.rsplit(".", 1)[-1] \
                if isinstance(oe, E.Col) else None
            cls = classes.get(plain, "")
            if plain in encm and cls.startswith(
                    codec.family_of(encm[plain])):
                bound = codec.span_bound(cls)
                if bound is not None:
                    spans[name] = bound
        return DBatch(out_cols, vis, out_types, out_dicts, out_nulls,
                      spans=spans)

    # Index scans never fuse: neither tier's screen admits P.IndexScan
    # (outside fused._KINDS and mesh _ALLOWED: plan_key gives None).
    def _exec_indexscan(self, node: P.IndexScan) -> DBatch:  # otblint: eager-only
        """Index scan: host binary search -> gather only the candidate
        rows -> the regular fused scan path over that staged subset
        (reference: ExecIndexScan; visibility/filters re-verify on the
        subset, so a stale bound can only over-select, never miss)."""
        seq = P.SeqScan(node.table, node.alias, node.filters,
                        node.outputs)
        store = self.ctx.stores.get(node.table.name)
        if store is None:
            raise ExecError(f"no store for table {node.table.name}")
        if (self.ctx.staged or {}).get(node.table.name) is not None:
            return self._exec_seqscan(seq)  # already subset-staged
        pos = store.btree_lookup(node.key_col, node.lo, node.hi,
                                 node.lo_strict, node.hi_strict)
        if pos is None:
            return self._exec_seqscan(seq)  # index dropped: full scan
        needed = sorted((P.needed_columns(seq, node.alias)
                         | P.needed_columns(seq, node.table.name))
                        & set(store.td.column_names))
        host = store.gather_rows(pos, needed)
        from ..storage.batch import stage_padded
        arrs, n = stage_padded(host, slice(None))
        old = self.ctx.staged
        self.ctx.staged = {**(old or {}), node.table.name: (arrs, n)}
        try:
            return self._exec_seqscan(seq)
        finally:
            self.ctx.staged = old

    # ANN search is host-driven (HNSW graph walk, int() sizing) and is
    # rejected by both fusability screens — asserted eager-only.
    def _exec_annsearch(self, node) -> DBatch:  # otblint: eager-only
        """Top-k vector search: visibility+filters mask, IVF probe when an
        index exists, exact distances otherwise, lax.top_k, gather."""
        from ..ops import ann as ANN
        plain_vec = node.vec_col.split(".", 1)[1] if "." in node.vec_col \
            else node.vec_col
        (store, base, valid, arrs, n, padded, outputs,
         dicts) = self._scan_base(node.table, node.alias, node.filters,
                                  node.outputs, {plain_vec})
        vecs = arrs[plain_vec]
        q = jnp.asarray(np.asarray(node.query, dtype=np.float32))
        k = min(node.k, padded)
        idx_info = store.ann_indexes.get(plain_vec)
        hnsw_info = store.hnsw_index(plain_vec) \
            if idx_info is not None and idx_info.get("kind") == "hnsw" \
            else None
        if hnsw_info is not None and hnsw_info["metric"] == node.metric:
            # graph traversal host-side, exact re-rank of candidates
            # (ops/hnsw.py); over-fetch so visibility filtering can
            # still fill k
            hidx = hnsw_info["index"]
            qh = np.asarray(node.query, dtype=np.float32)
            ids = hidx.search(qh, min(4 * k, max(len(hidx.vecs), 1)))
            vmask = np.asarray(valid)[ids] if len(ids) else \
                np.zeros(0, bool)
            ids = ids[vmask]
            from ..ops.hnsw import _dist as _hdist
            ds = _hdist(node.metric, qh, hidx.vecs[ids]) if len(ids) \
                else np.zeros(0)
            if node.metric == "l2":
                ds = np.sqrt(np.maximum(ds, 0.0))  # match ANN.distances
            order = np.argsort(ds)[:k]
            idx_h = np.zeros(k, np.int64)
            dist_h = np.full(k, np.inf)
            idx_h[:len(order)] = ids[order]
            dist_h[:len(order)] = ds[order]
            idx, dist = jnp.asarray(idx_h), jnp.asarray(dist_h)
        elif idx_info is not None and idx_info.get("kind") != "hnsw" \
                and idx_info["metric"] == node.metric:
            assign, centroids = _ann_assignments(store, plain_vec, vecs, n)
            nprobe = min(idx_info["nprobe"], centroids.shape[0])
            idx, dist = ANN.ivf_search(vecs, assign, centroids, q, valid,
                                       nprobe, k, node.metric)
        else:
            d = ANN.distances(vecs, q, node.metric)
            idx, dist = ANN.topk_nearest(d, valid, k)
        found = int(jnp.sum(jnp.isfinite(dist)))

        out_cols, out_types, out_dicts = {}, {}, {}
        for name, oe in outputs:
            if isinstance(oe, E.DistExpr):
                out_cols[name] = dist.astype(device_float())
            else:
                out_cols[name] = self._eval(oe, base)[idx]
            out_types[name] = oe.type
            dd = _dict_for_expr(oe, dicts)
            if dd is not None:
                out_dicts[name] = dd
        out_valid = jnp.arange(k) < found
        return DBatch(out_cols, out_valid, out_types, out_dicts)

    # ---- filter / project ----
    def _exec_filter(self, node: P.Filter) -> DBatch:
        b = self.exec_node(node.child)
        valid = b.valid
        for q in node.quals:
            valid = valid & self._eval_pred(q, b)
        return DBatch(b.cols, valid, b.types, b.dicts, b.nulls, b.lazy,
                      b.spans)

    def _exec_project(self, node: P.Project) -> DBatch:
        b = self.exec_node(node.child)
        cols, types, dicts, nulls, spans = {}, {}, {}, {}, {}
        for name, oe in node.outputs:
            arr, nm = self._eval_pair(oe, b)
            if getattr(arr, "ndim", 1) == 0:   # constant: broadcast
                arr = jnp.full((b.padded,), arr)
            cols[name] = arr
            types[name] = oe.type
            d = _dict_for_expr(oe, b.dicts)
            if d is not None:
                dicts[name] = d
            if nm is not None:
                nulls[name] = nm
            if isinstance(oe, E.Col) and oe.name in b.spans:
                spans[name] = b.spans[oe.name]    # the same values
        return DBatch(cols, b.valid, types, dicts, nulls, spans=spans)

    # ---- join ----
    def _join_key(self, keys: list[E.Expr], b: DBatch):
        """Combine join key exprs into one int64 key column.  A NULL key
        never matches (SQL: NULL = x is unknown): null positions take the
        kernels' reserved unmatchable sentinel INT64_MAX (ops/kernels.py
        join_probe_counts).  TEXT keys are translated to stable string
        hashes so both sides share a key space (dictionary codes are
        column-local); text pairs are excluded from the hash recheck —
        the hash IS the equality.  Returns (key, hashed, recheck_mask,
        span): recheck_mask[i] says key i can be re-verified by value;
        span is the host-known bound on the key's range (DBatch.spans)
        for a single plain column, else None."""
        from .expr_compile import _text_hash_fn
        for k in keys:
            self._ensure_expr(k, b)
        arrs, nulls, recheckable = [], None, []
        env = self._env(b)
        for k in keys:
            if k.type.kind == TypeKind.TEXT:
                a = _text_hash_fn(self._prep(k),
                                  self._dictviews(b))(env)
                _, nm = self._eval_pair(k, b)
                recheckable.append(False)
            else:
                a, nm = self._eval_pair(k, b)
                recheckable.append(True)
            arrs.append(a)
            if nm is not None:
                nulls = nm if nulls is None else (nulls | nm)
        span = None
        if len(arrs) == 1:
            a = arrs[0]
            if a.dtype == jnp.bool_:
                a = a.astype(jnp.int64)
            a = a.astype(jnp.int64)
            hashed = False
            if isinstance(keys[0], E.Col) and recheckable[0]:
                span = b.spans.get(keys[0].name)
        else:
            a = hash_columns_jax([x.astype(jnp.int64) for x in arrs])
            a = a.astype(jnp.int64)
            hashed = True   # hashed: residual recheck needed
        if nulls is not None:
            a = jnp.where(nulls, K.INT64_MAX, a)
        return a, hashed, recheckable, span

    def _defer_side(self, batch: DBatch, take, out: DBatch,
                    extra_null=None):
        """Late materialization: carry one join input's columns into the
        output batch as LazyCols behind `take` (output -> input row
        indices) instead of gathering payloads.  Existing indirections
        compose — ONE index gather per distinct source index vector,
        shared by every column riding it.  `extra_null` is an
        output-space mask (outer-join null extension) OR'd onto every
        carried column's null."""
        composed: dict = {}
        out.spans.update(batch.spans)
        for n_, a in batch.cols.items():
            out.lazy[n_] = LazyCol(a, take, batch.nulls.get(n_),
                                   extra_null)
            out.types[n_] = batch.types[n_]
            if n_ in batch.dicts:
                out.dicts[n_] = batch.dicts[n_]
            _bump("deferred_cols")
        for n_, lc in batch.lazy.items():
            key = id(lc.idx)
            nidx = composed.get(key)
            if nidx is None:
                nidx = K.compose_index(lc.idx, take)
                composed[key] = nidx
                _bump("index_compositions")
            no = lc.null_out[take] if lc.null_out is not None else None
            if extra_null is not None:
                no = extra_null if no is None else (no | extra_null)
            out.lazy[n_] = LazyCol(lc.src, nidx, lc.null_src, no)
            out.types[n_] = batch.types[n_]
            if n_ in batch.dicts:
                out.dicts[n_] = batch.dicts[n_]
            _bump("deferred_cols")

    def _gather_side(self, batch: DBatch, take, out: DBatch,
                     extra_null=None):
        """Eager (pre-late-materialization) path: gather every carried
        column of one input through `take` — kept as the bit-identical
        baseline (LATE_MAT off)."""
        batch.ensure_all()
        out.spans.update(batch.spans)
        for n_, a in batch.cols.items():
            out.cols[n_] = a[take]
            out.types[n_] = batch.types[n_]
            if n_ in batch.dicts:
                out.dicts[n_] = batch.dicts[n_]
            nm = batch.nulls[n_][take] if n_ in batch.nulls else None
            if extra_null is not None:
                nm = extra_null if nm is None else (nm | extra_null)
            if nm is not None:
                out.nulls[n_] = nm
            _bump("eager_cols")

    def _carry_side(self, batch, take, out, extra_null=None):
        if LATE_MAT:
            self._defer_side(batch, take, out, extra_null)
        else:
            self._gather_side(batch, take, out, extra_null)

    @staticmethod
    def _or_null_out(out: DBatch, names, mask):
        """OR an output-space null mask onto the named columns (lazy or
        materialized) — the outer-join revert path."""
        for n_ in names:
            lc = out.lazy.get(n_)
            if lc is not None:
                lc.null_out = mask if lc.null_out is None \
                    else (lc.null_out | mask)
            else:
                m = out.nulls.get(n_)
                out.nulls[n_] = mask if m is None else (m | mask)

    def _exec_hashjoin(self, node: P.HashJoin) -> DBatch:
        left = self.exec_node(node.left)
        right = self.exec_node(node.right)

        if node.kind == "cross":
            return self._cross_join(left, right)

        if node.kind == "inner" and right.padded > left.padded:
            # build the SMALLER side (reference: nodeHash.c hashes the
            # cheaper input): inner joins are symmetric, and the
            # planner's left-deep accumulation otherwise makes the
            # freshly-joined big table the build side — sorting 2M
            # build rows instead of 130k
            node = dataclasses.replace(node, left=node.right,
                                       right=node.left,
                                       left_keys=node.right_keys,
                                       right_keys=node.left_keys)
            left, right = right, left

        with jax.named_scope("otb.join_probe"):
            lkey, lhashed, lcheck, _ = self._join_key(node.left_keys, left)
        with jax.named_scope("otb.join_build"):
            rkey, rhashed, rcheck, span = self._join_key(node.right_keys,
                                                         right)
        hash_recheck = []
        if lhashed or rhashed:
            hash_recheck = [
                (lk, rk) for (lk, rk), lok, rok in
                zip(zip(node.left_keys, node.right_keys), lcheck, rcheck)
                if lok and rok]
        differ = None if hash_recheck \
            else self._differ_residual(node, left, right)
        # one sort and one probe algorithm per program, chosen here, when
        # the program is built, from what the host knows of the build
        # key's range; nothing known (a computed or hashed key, a column
        # whose codec proves no range) takes the algorithms that need no
        # range
        if differ is None:
            skeys, perm = K.join_build(rkey, right.valid, key_span=span)
        else:
            # EXISTS / NOT EXISTS (... and build.c <> probe.c): the build
            # side sorted by (key, c), so that a probe row's matches show
            # their smallest and largest c at the ends of its range
            probe_c, build_c = differ
            with jax.named_scope("otb.join_build"):
                bc, bnull = self._eval_pair(build_c, right)
            skeys, _perm, sminor, base = K.join_build_minor(
                rkey, right.valid if bnull is None
                else right.valid & ~bnull, bc, key_span=span,
                minor_span=right.spans.get(build_c.name))
        lo, counts = K.join_probe_counts(skeys, lkey, left.valid,
                                         key_span=span)

        _bump("joins")
        if node.kind in ("semi", "anti") and not hash_recheck \
                and (differ is not None or not node.residual):
            # answered by a mask: no pair is made
            if differ is not None:
                with jax.named_scope("otb.join_probe"):
                    pc, pnull = self._eval_pair(probe_c, left)
                found = K.range_differs(
                    lo, counts, sminor, base, pc, left.valid
                    if pnull is None else left.valid & ~pnull)
                mask = found if node.kind == "semi" else ~found
            elif node.kind == "semi":
                mask = K.semi_mask(counts)
            else:
                mask = K.anti_mask(counts, left.valid)
            self.shape["semi_joins"] += 1
            self.shape["anti_joins"] += int(node.kind == "anti")
            return DBatch(left.cols, left.valid & mask, left.types,
                          left.dicts, left.nulls, left.lazy, left.spans)

        left_outer = node.kind in ("left", "full")
        self.shape["outer_joins"] += int(left_outer)
        # the counts are int32 words; their sum may pass one
        total = jnp.sum(jnp.where(left.valid, jnp.maximum(counts, 1), 0)
                        if left_outer else counts, dtype=jnp.int64)
        if self._traced:
            # no host sync inside a compiled (shard_map) program: static
            # output class laddered per join id.  join_expand packs live
            # pairs as a prefix, so the class starts at 1/4 of the larger
            # input (most joins SHRINK: filters + selective keys) and
            # overflow retraces one step up — the learned value persists
            # in the mesh runner's ladder memory, and every op downstream
            # of the join (agg sorts, exchanges, gathers) scales with it
            # (an outer join emits every valid probe row at least and,
            # joined to the many side, every build row: it starts at the
            # larger input, where a quarter would be overflowed twice)
            jid, factor = self._ladder_slot()
            out_size = max(64, (max(left.padded, right.padded)
                                // (1 if left_outer else 4)) * factor)
            self.join_required.append((jid, total, out_size))
        else:
            out_size = next_pow2(max(int(total), 1))
        if node.kind in ("semi", "anti"):
            # a residual no mask answers: every pair is made and judged
            self.shape["residual_semi_lanes"] = max(
                self.shape["residual_semi_lanes"], out_size)
        pi, bi, tot = K.join_expand(lo, counts, perm, out_size,
                                    left_outer=left_outer,
                                    probe_valid=left.valid)
        if not self._traced:
            _bump("host_syncs")
            tot = int(tot)
        valid = jnp.arange(out_size) < tot
        null_right = (bi < 0) if left_outer else None
        bi_safe = jnp.where(bi < 0, 0, bi) if left_outer else bi

        # late materialization: the join output carries both inputs'
        # columns behind the fresh pair indices (pi / bi, int32: every
        # index composed from them is a one-word table) — prior
        # indirections compose, payloads stay untouched until a
        # width-consuming operator materializes (SURVEY: move indices,
        # not payloads)
        out = DBatch({}, valid, {}, {}, {})
        self._carry_side(left, pi, out)
        self._carry_side(right, bi_safe, out, extra_null=null_right)
        right_names = right.names()

        # residual quals (incl. hash recheck for multi-key joins)
        res_valid = out.valid
        for lk, rk in hash_recheck:
            res_valid = res_valid & (self._eval(lk, out) ==
                                     self._eval(rk, out))
        for q in node.residual:
            res_valid = res_valid & self._eval_pred(q, out)

        if node.kind in ("semi", "anti"):
            # per-probe-row any(): scatter surviving pairs back to probe rows
            hits = jax.ops.segment_sum(
                res_valid.astype(jnp.int32), pi,
                num_segments=left.valid.shape[0])
            mask = hits > 0 if node.kind == "semi" else \
                (left.valid & (hits == 0))
            return DBatch(left.cols, left.valid & mask, left.types,
                          left.dicts, left.nulls, left.lazy, left.spans)
        if left_outer:
            null_ext = null_right
            if hash_recheck or node.residual:
                # Null-extended pairs (bi<0) gathered build row 0's
                # columns, so the key recheck/residual verdict on them is
                # garbage — they are judged by whether any REAL pair of
                # their probe row survived.  A probe row whose real pairs
                # were ALL killed reverts to null-extension (reference:
                # ExecHashJoin emits the null-filled tuple when
                # HJ_FILL_OUTER and no match passed joinqual,
                # nodeHashjoin.c) — we convert its first output pair into
                # the null-extended one.
                real_surv = res_valid & ~null_ext & out.valid
                hits = jax.ops.segment_sum(
                    real_surv.astype(jnp.int32), pi,
                    num_segments=left.valid.shape[0])
                need_null = left.valid & (hits == 0)
                idx = jnp.arange(out_size)
                first_idx = jax.ops.segment_min(
                    jnp.where(out.valid, idx, out_size), pi,
                    num_segments=left.valid.shape[0])
                is_first = out.valid & (idx == first_idx[pi])
                to_null = is_first & need_null[pi]
                self._or_null_out(out, right_names, to_null)
                out.valid = real_surv | to_null
                null_ext = null_ext | to_null
            if node.kind != "full":
                return out
            # FULL: append the unmatched BUILD rows null-extended on the
            # left — computed AFTER recheck/revert so pairs killed there
            # count their build row as unmatched (reference: ExecHashJoin
            # HJ_FILL_INNER / ExecScanHashTableForUnmatched).  The tail
            # concat is width-consuming: materialize both row spaces.
            out.ensure_all()
            right.ensure_all()
            bhits = jax.ops.segment_sum(
                (out.valid & ~null_ext).astype(jnp.int32), bi_safe,
                num_segments=right.padded)
            r_unmatched = right.valid & (bhits == 0)
            cols2, nulls2 = {}, {}
            for n_, a in out.cols.items():
                if n_ in right.cols:
                    cols2[n_] = jnp.concatenate([a, right.cols[n_]])
                    tail_m = right.nulls.get(
                        n_, jnp.zeros(right.padded, dtype=bool))
                else:  # left column: null-extended in the appended rows
                    pad = jnp.zeros((right.padded, *a.shape[1:]), a.dtype)
                    cols2[n_] = jnp.concatenate([a, pad])
                    tail_m = jnp.ones(right.padded, dtype=bool)
                base_m = out.nulls.get(
                    n_, jnp.zeros(out.padded, dtype=bool))
                nulls2[n_] = jnp.concatenate([base_m, tail_m])
            valid2 = jnp.concatenate([out.valid, r_unmatched])
            return DBatch(cols2, valid2, out.types, out.dicts, nulls2)
        out.valid = res_valid
        return out

    @staticmethod
    def _differ_residual(node: P.HashJoin, left: DBatch, right: DBatch):
        """(probe column, build column) where a semi or anti join's ONLY
        residual is `a <> b` between a plain column of each side, of one
        exact type (integers, dates, decimals of one scale: the stored
        words compare as the values do); else None."""
        if node.kind not in ("semi", "anti") or len(node.residual) != 1:
            return None
        q = node.residual[0]
        if not (isinstance(q, E.Cmp) and q.op == "<>"
                and isinstance(q.left, E.Col)
                and isinstance(q.right, E.Col)
                and q.left.type == q.right.type
                and q.left.type.kind in (TypeKind.INT32, TypeKind.INT64,
                                         TypeKind.DATE, TypeKind.DECIMAL)):
            return None
        for a, b in ((q.left, q.right), (q.right, q.left)):
            if left.has_col(a.name) and not right.has_col(a.name) \
                    and right.has_col(b.name) and not left.has_col(b.name):
                return a, b
        return None

    def _cross_join(self, left: DBatch, right: DBatch) -> DBatch:
        ln, rn = left.count(), right.count()
        if ln * rn > 1 << 22:
            raise ExecError("cross join too large")
        lidx = jnp.repeat(jnp.arange(left.padded), right.padded)
        ridx = jnp.tile(jnp.arange(right.padded), left.padded)
        valid = left.valid[lidx] & right.valid[ridx]
        out = DBatch({}, valid, {}, {}, {})
        self._carry_side(left, lidx, out)
        self._carry_side(right, ridx, out)
        return out

    def _exec_batchsource(self, node) -> DBatch:
        return node.batch

    # SetOps size their output with host syncs (int(ng), int(total));
    # P.SetOp is outside fused._KINDS and mesh _ALLOWED, so this
    # operator only ever runs on the eager tier.
    def _exec_setop(self, node: P.SetOp) -> DBatch:  # otblint: eager-only
        """INTERSECT/EXCEPT [ALL]: side-tagged merge, per-group per-side
        counts by sort, then emit min(c1,c2) / max(c1-c2,0) copies (the
        reference's hashed SETOPCMD_* counting, nodeSetOp.c:49-66).
        NULLs compare equal here (null-indicator grouping columns), per
        SQL set-operation semantics."""
        from .dist import _concat_host, _to_device, _to_host
        parts = []
        for side, child in enumerate(node.inputs):
            hb = _to_host(self.exec_node(child))
            hb.cols["__side"] = np.full(hb.nrows, side, np.int64)
            hb.types["__side"] = T.INT64
            parts.append(hb)
        b = _to_device(_concat_host(parts))
        side = b.cols["__side"]
        key_arrs = []
        for n in node.names:
            arr = b.cols[n]
            if b.types[n].kind == TypeKind.FLOAT64:
                # canonicalize -0.0 so SQL equality groups it with +0.0
                arr = jnp.where(arr == 0, jnp.zeros((), arr.dtype),
                                arr)
                arr = float_to_bits(arr)
            arr = arr.astype(jnp.int64)
            nm = b.nulls.get(n)
            if nm is not None:
                key_arrs.append(jnp.where(nm, 0, arr))
                key_arrs.append(nm.astype(jnp.int64))
            else:
                key_arrs.append(arr)
        if not key_arrs:
            key_arrs = [jnp.zeros(b.padded, jnp.int64)]
        max_groups = next_pow2(max(b.count(), 1))
        c_left = (b.valid & (side == 0)).astype(jnp.int64)
        c_right = (b.valid & (side == 1)).astype(jnp.int64)
        gkeys, (c1, c2), ng = self._sorted_agg(
            tuple(key_arrs), b.valid, (c_left, c_right), max_groups,
            ("sum", "sum"))
        ng = int(ng)
        gvalid = jnp.arange(max_groups) < ng
        if node.op == "intersect":
            copies = jnp.minimum(c1, c2)
            if not node.all:
                copies = jnp.minimum(copies, 1)
        elif node.all:   # except all: multiset difference
            copies = jnp.maximum(c1 - c2, 0)
        else:            # except distinct: present left, absent right
            copies = ((c1 > 0) & (c2 == 0)).astype(jnp.int64)
        copies = jnp.where(gvalid, copies, 0)
        total = int(jnp.sum(copies))
        out_size = next_pow2(max(total, 1))
        gi = K.lane_rows(jnp.cumsum(copies), out_size)
        out_valid = jnp.arange(out_size) < total
        cols, types, nulls = {}, {}, {}
        ki = 0
        for n in node.names:
            t = b.types[n]
            arr = gkeys[ki][gi]
            ki += 1
            if n in b.nulls:
                nulls[n] = gkeys[ki][gi].astype(bool)
                ki += 1
            if t.kind == TypeKind.FLOAT64:
                arr = bits_to_float(arr)
            cols[n] = arr.astype(dev_dtype(t))
            types[n] = t
        dicts = {n: b.dicts[n] for n in node.names if n in b.dicts}
        return DBatch(cols, out_valid, types, dicts, nulls)

    def _exec_append(self, node) -> DBatch:
        """Concatenate children (UNION branches).  Untraced: through
        the host wire format so node-local TEXT dictionaries merge
        correctly.  Traced (mesh): a device concat — TEXT dictionaries
        are trace CONSTANTS, so union dictionaries and code LUTs are
        built host-side at trace time and each branch's codes remap
        with one static gather (zero host work per execution)."""
        if not self._traced:
            from .dist import _concat_host, _to_device, _to_host
            parts = [_to_host(self.exec_node(c)) for c in node.inputs]
            return _to_device(_concat_host(parts))
        parts = [self.exec_node(c).ensure_all() for c in node.inputs]
        first = parts[0]
        out_cols, out_dicts, out_nulls = {}, {}, {}
        for nme in first.cols:
            t = first.types[nme]
            if t.kind == TypeKind.TEXT:
                values: list = []
                index: dict = {}
                remapped = []
                for p in parts:
                    vals = p.dicts.get(nme, [])
                    lut = np.empty(max(len(vals), 1), np.int32)
                    for i, v in enumerate(vals):
                        j = index.get(v)
                        if j is None:
                            j = len(values)
                            values.append(v)
                            index[v] = j
                        lut[i] = j
                    codes = jnp.clip(p.cols[nme], 0,
                                     max(len(vals) - 1, 0))
                    remapped.append(jnp.asarray(lut)[codes])
                out_cols[nme] = jnp.concatenate(remapped)
                out_dicts[nme] = values
            else:
                dt = first.cols[nme].dtype
                out_cols[nme] = jnp.concatenate(
                    [p.cols[nme].astype(dt) for p in parts])
        valid = jnp.concatenate([p.valid for p in parts])
        null_names = set()
        for p in parts:
            null_names |= set(p.nulls)
        for nme in null_names:
            out_nulls[nme] = jnp.concatenate(
                [p.nulls.get(nme,
                             jnp.zeros(p.valid.shape[0], bool))
                 for p in parts])
        return DBatch(out_cols, valid, dict(first.types), out_dicts,
                      out_nulls)

    # ---- aggregate ----
    def _eval_group_keys(self, node: P.Agg, b: DBatch):
        """Group key arrays + per-key null masks.  NULL keys group
        together (SQL: GROUP BY treats NULLs as equal — nodeAgg.c grouping
        equality): the value is canonicalized to 0 and the null bit
        becomes an extra grouping column."""
        key_arrs, key_types, key_dicts, dup_dicts = [], [], [], False
        key_nulls = []
        for name, ke in node.group_keys:
            arr, nm = self._eval_pair(ke, b)
            arr = arr.astype(jnp.int64)
            if nm is not None:
                arr = jnp.where(nm, 0, arr)
            d = _dict_for_expr(ke, b.dicts)
            if d is not None and len(set(d)) < len(d):
                # a transformed dictionary (substring etc.) can map
                # several codes to one string: canonicalize codes
                # sharing a string BEFORE grouping, so groups never
                # over-split (canonical codes still decode correctly)
                canon: dict = {}
                lut = np.empty(max(len(d), 1), np.int64)
                for ci, v in enumerate(d):
                    lut[ci] = canon.setdefault(v, ci)
                arr = jnp.asarray(lut)[jnp.clip(arr, 0, len(d) - 1)]
            key_arrs.append(arr)
            key_nulls.append(nm)
            key_types.append(ke.type)
            key_dicts.append(d)
        return key_arrs, key_types, key_dicts, dup_dicts, key_nulls

    @staticmethod
    def _grouping_arrays(key_arrs, key_nulls):
        """Key tuple for the sort kernels: values plus null-indicator
        columns (so the NULL group is distinct from the value-0 group)."""
        extra = [nm.astype(jnp.int64) for nm in key_nulls
                 if nm is not None]
        return tuple(key_arrs) + tuple(extra)

    @staticmethod
    def _group_key_spans(node: P.Agg, b: DBatch, key_dicts, key_nulls):
        """What the host knows of each grouping array's range (the
        order of `_grouping_arrays`): a plain column's span as its scan
        proved it (DBatch.spans), a dictionary's codes (the 0 a NULL is
        set to is one of them), 1 for a null indicator, None where
        nothing is known (a computed key; a plain key that may be NULL:
        its NULLs were set to 0, outside the column's class)."""
        spans = [max(len(d) - 1, 0) if d is not None
                 else b.spans.get(ke.name)
                 if isinstance(ke, E.Col) and nm is None else None
                 for (_n, ke), d, nm in zip(node.group_keys, key_dicts,
                                            key_nulls)]
        return tuple(spans) + (1,) * sum(nm is not None for nm in key_nulls)

    def _ladder_slot(self):
        """The next id of this fragment's laddered operators (traced
        joins and sorted aggregates, in plan order) and the factor the
        runner has learned for it."""
        jid = (self.frag_tag, self._join_seq)
        self._join_seq += 1
        return jid, (self.ctx.join_factors or {}).get(
            jid, self.ctx.join_size_factor)

    def _agg_class(self, b: DBatch, key_spans=None):
        """Output class of a sorted aggregate over `b`: (max_groups,
        ladder id).  Eager, the live rows are counted on the host.
        Traced, no count can be: where the host knows every key's range
        (`key_spans`, _group_key_spans) their product bounds the groups
        and no row can overflow the class (Q17: 200,000 part keys in
        229,376 slots under 6,291,456 rows; no ladder id, nothing to
        report).  Otherwise the class rides the ladder traced joins
        ride: a quarter of the input's padded rows times the factor the
        runner learned for this id, the groups found reported in
        `join_required`, an overflowed call replayed one class up.
        Groups never exceed rows, so the class is capped at the input's
        and the ladder has three rungs (Q18's 1,500,000 orders fit the
        first, 1,572,864, at SF1)."""
        if not self._traced:
            return next_pow2(max(b.count(), 1)), None
        if key_spans is not None and None not in key_spans:
            proven = size_class(math.prod(sp + 1 for sp in key_spans))
            if proven < b.padded:
                return proven, None
        jid, factor = self._ladder_slot()
        return min(b.padded, max(64, b.padded // 4) * factor), jid

    def _sorted_agg(self, keys, valid, inputs, max_groups, kinds,
                    key_spans=None, jid=None):
        """grouped_agg_sort at the class `_agg_class` gave; `jid` is its
        ladder id, None where the class cannot overflow.  The passes of
        a DISTINCT aggregate share one class and find the same groups:
        the first reports for all."""
        self.shape["sorted_aggs"] += 1
        self.shape["sorted_agg_lanes"] = max(
            self.shape["sorted_agg_lanes"], int(valid.shape[0]))
        self.shape["sorted_agg_groups"] = max(
            self.shape["sorted_agg_groups"], max_groups)
        gkeys, outs, ng = K.grouped_agg_sort(
            keys, valid, inputs, max_groups, kinds, key_spans=key_spans)
        if jid is not None and all(j != jid for j, _r, _c
                                   in self.join_required):
            self.join_required.append(
                (jid, ng.astype(jnp.int64), max_groups))
        return gkeys, outs, ng

    def _assemble_agg_output(self, node: P.Agg, gkey_out, key_types,
                             key_dicts, outs, out_specs, out_valid,
                             gkey_nulls=None, empty=None):
        """`empty` (an aggregate without GROUP BY only): whether no row
        reached it — its SUM, MIN and MAX are then NULL, not 0 (COUNT is
        0); a grouped aggregate has no group without a row."""
        cols, types, dicts, nulls = {}, {}, {}, {}
        not_counts = {n for n, ac in node.aggs if ac.func != "count"} \
            if empty is not None else ()
        for i, ((kname, _), karr, kt, kd) in enumerate(
                zip(node.group_keys, gkey_out, key_types, key_dicts)):
            cols[kname] = karr.astype(dev_dtype(kt))
            types[kname] = kt
            if kd is not None:
                dicts[kname] = kd
            if gkey_nulls is not None and gkey_nulls[i] is not None:
                nulls[kname] = gkey_nulls[i]
        oi = 0
        for name, t, special in out_specs:
            if special is not None and special[0] == "avg":
                s, c = outs[oi], outs[oi + 1]
                oi += 2
                cols[name] = jnp.where(
                    c > 0, s.astype(device_float()) / jnp.maximum(c, 1)
                    / (10 ** special[1]), jnp.zeros((), device_float()))
                nulls[name] = c == 0  # avg over zero non-null inputs
            elif special is not None and special[0] == "nullable":
                # value plus its non-null contribution count: the SQL
                # aggregate is NULL when every input in the group was NULL
                v, c = outs[oi], outs[oi + 1]
                oi += 2
                cols[name] = v
                nulls[name] = c == 0
            else:
                cols[name] = outs[oi]
                oi += 1
                if name in not_counts:
                    nulls[name] = empty
            types[name] = t
        return DBatch(cols, out_valid, types, dicts, nulls)

    def _agg_inputs(self, node: P.Agg, b: DBatch, final: bool):
        """Kernel inputs for the agg list.  `final` combines partial
        columns (named inputs with exchange-carried null masks) instead of
        raw argument expressions.  Aggregates over nullable inputs get a
        parallel non-null-count input so all-NULL groups yield SQL NULL
        (the ("nullable",) out_spec)."""
        kinds, inputs, out_specs = [], [], []
        for name, ac in node.aggs:
            if final:
                if ac.func == "avg":
                    arg_arr = null_mask = None
                else:
                    arg_arr = b.col_opt(name)
                    null_mask = b.nulls.get(name)
            elif ac.arg is not None:
                arg_arr, null_mask = self._eval_pair(ac.arg, b)
            else:
                arg_arr = null_mask = None

            def non_null(v, neutral):
                if null_mask is None:
                    return v
                return jnp.where(null_mask, jnp.asarray(neutral, v.dtype), v)

            base = b.valid if null_mask is None else (b.valid & ~null_mask)
            if ac.func == "count":
                if final:
                    kinds.append("sum")
                    inputs.append(non_null(arg_arr, 0))
                else:
                    kinds.append("sum")
                    inputs.append(base.astype(jnp.int64))
                out_specs.append((name, T.INT64, None))
            elif ac.func == "avg":
                scale = ac.arg.type.scale \
                    if ac.arg.type.kind == TypeKind.DECIMAL else 0
                # integers and scaled decimals sum EXACTLY in int64 and
                # become a float only at the final division: a device-
                # float running sum is f32 on a TPU, and over SF1's
                # 1.5M-row groups Q1's averages came back 3.8e-4 off
                # (first chip run, CHANGES.md PR 22)
                exact = ac.arg.type.kind != TypeKind.FLOAT64
                kinds.append("sum" if exact else "sumf")
                if final:
                    inputs.append(b.col(name + "__s"))
                elif exact:
                    inputs.append(non_null(arg_arr, 0).astype(jnp.int64))
                else:
                    inputs.append(non_null(arg_arr, 0))
                kinds.append("sum")
                inputs.append(b.col(name + "__c") if final
                              else base.astype(jnp.int64))
                if node.mode == "partial":
                    # components travel separately to the final agg
                    out_specs.append((name + "__s",
                                      T.INT64 if exact else T.FLOAT64,
                                      None))
                    out_specs.append((name + "__c", T.INT64, None))
                else:
                    out_specs.append((name, T.FLOAT64, ("avg", scale)))
            elif ac.func == "sum":
                if ac.arg.type.kind == TypeKind.FLOAT64:
                    kinds.append("sumf")
                    t = T.FLOAT64
                else:
                    kinds.append("sum")
                    t = ac.arg.type if ac.arg.type.kind == TypeKind.DECIMAL \
                        else T.INT64
                inputs.append(non_null(arg_arr, 0))
                if null_mask is not None:
                    kinds.append("sum")
                    inputs.append(base.astype(jnp.int64))
                    out_specs.append((name, t, ("nullable",)))
                else:
                    out_specs.append((name, t, None))
            elif ac.func in ("min", "max"):
                kinds.append(ac.func)
                if null_mask is not None:
                    if jnp.issubdtype(arg_arr.dtype, jnp.integer):
                        info = jnp.iinfo(arg_arr.dtype)
                        neutral = info.max if ac.func == "min" else info.min
                    else:
                        neutral = np.inf if ac.func == "min" else -np.inf
                    arg_arr = non_null(arg_arr, neutral)
                inputs.append(arg_arr)
                if null_mask is not None:
                    kinds.append("sum")
                    inputs.append(base.astype(jnp.int64))
                    out_specs.append((name, ac.arg.type, ("nullable",)))
                else:
                    out_specs.append((name, ac.arg.type, None))
            else:
                raise ExecError(f"aggregate {ac.func} unsupported")
        return kinds, inputs, out_specs

    def _exec_agg(self, node: P.Agg) -> DBatch:
        b = self.exec_node(node.child)
        if node.mode == "final":
            return self._exec_agg_final(node, b)
        key_arrs, key_types, key_dicts, text_transformed, key_nulls = \
            self._eval_group_keys(node, b)

        if any(ac.distinct for _, ac in node.aggs):
            return self._exec_distinct_agg(node, b, key_arrs, key_types,
                                           key_dicts, key_nulls)

        kinds, inputs, out_specs = self._agg_inputs(node, b, final=False)

        n = b.padded
        any_null_keys = any(nm is not None for nm in key_nulls)
        gkey_nulls = [None] * len(key_arrs)
        empty, out_spans = None, {}
        if not key_arrs:
            gid = jnp.zeros(n, dtype=jnp.int64)
            (outs, present) = K.grouped_agg_dense(
                gid, b.valid, tuple(inputs), 1, tuple(kinds))
            out_valid = jnp.ones(1, dtype=bool)
            gkey_out = []
            padded_groups = 1
            empty = present == 0
        else:
            dense_bound = _dense_bound(key_types, key_dicts) \
                if not any_null_keys else None
            if dense_bound is not None and dense_bound <= 4096:
                gid = jnp.zeros(n, dtype=jnp.int64)
                mult = 1
                for arr, t, d in zip(key_arrs, key_types, key_dicts):
                    dom = len(d) if d is not None else 2
                    gid = gid * dom + jnp.clip(arr, 0, dom - 1)
                    mult *= dom
                (outs, present) = K.grouped_agg_dense(
                    gid, b.valid, tuple(inputs), mult, tuple(kinds))
                padded_groups = mult
                out_valid = present > 0
                # decode group keys from gid
                gidx = jnp.arange(mult)
                gkey_out = []
                rem = gidx
                doms = [len(d) if d is not None else 2 for d in key_dicts]
                for i in reversed(range(len(key_arrs))):
                    gkey_out.insert(0, (rem % doms[i]).astype(jnp.int64))
                    rem = rem // doms[i]
            else:
                key_spans = self._group_key_spans(node, b, key_dicts,
                                                  key_nulls)
                max_groups, jid = self._agg_class(b, key_spans)
                gkeys, outs, ng = self._sorted_agg(
                    self._grouping_arrays(key_arrs, key_nulls), b.valid,
                    tuple(inputs), max_groups, tuple(kinds), key_spans,
                    jid)
                if not self._traced:
                    ng = int(ng)
                padded_groups = max_groups
                # a group's key is one of its rows' keys: the bound holds
                out_spans = {kn: sp for (kn, _), sp, d in
                             zip(node.group_keys, key_spans, key_dicts)
                             if sp is not None and d is None}
                out_valid = jnp.arange(max_groups) < ng
                gkey_out = list(gkeys[:len(key_arrs)])
                extra = list(gkeys[len(key_arrs):])
                for i, nm in enumerate(key_nulls):
                    if nm is not None:
                        gkey_nulls[i] = extra.pop(0).astype(bool)

        out = self._assemble_agg_output(node, gkey_out, key_types,
                                        key_dicts, outs, out_specs,
                                        out_valid, gkey_nulls, empty)
        out.spans.update(out_spans)
        return out

    def _exec_agg_final(self, node: P.Agg, b: DBatch) -> DBatch:
        """Finalise partial aggregates (reference: rq_finalise_aggs —
        the CN-side combine of DN partials).  Input columns follow the
        partial naming convention; group keys are passthrough columns.
        Exchange re-encoding guarantees unique dictionary values here, so
        no post-decode re-merge is needed.  Null masks on partial columns
        (a DN-group whose inputs were all NULL) combine through the same
        skip-null rule as raw arguments."""
        self.shape["final_aggs"] += 1
        self.shape["final_agg_lanes"] = max(self.shape["final_agg_lanes"],
                                            b.padded)
        # nested under the node's `otb.agg`, so that a trace's op names
        # tell the final half of a two-phase aggregate from the partial
        with jax.named_scope("otb.agg.final"):
            return self._agg_final(node, b)

    def _agg_final(self, node: P.Agg, b: DBatch) -> DBatch:
        """`_exec_agg_final`'s combine, traced under its scope."""
        key_arrs, key_types, key_dicts, _, key_nulls = \
            self._eval_group_keys(node, b)
        kinds, inputs, out_specs = self._agg_inputs(node, b, final=True)

        n = b.padded
        gkey_nulls = [None] * len(key_arrs)
        if not key_arrs:
            gid = jnp.zeros(n, dtype=jnp.int64)
            outs, present = K.grouped_agg_dense(
                gid, b.valid, tuple(inputs), 1, tuple(kinds))
            out_valid = jnp.ones(1, dtype=bool)
            gkey_out = []
        else:
            max_groups, jid = self._agg_class(b)
            gkeys, outs, ng = self._sorted_agg(
                self._grouping_arrays(key_arrs, key_nulls), b.valid,
                tuple(inputs), max_groups, tuple(kinds), jid=jid)
            if not self._traced:
                ng = int(ng)
            out_valid = jnp.arange(max_groups) < ng
            gkey_out = list(gkeys[:len(key_arrs)])
            extra = list(gkeys[len(key_arrs):])
            for i, nm in enumerate(key_nulls):
                if nm is not None:
                    gkey_nulls[i] = extra.pop(0).astype(bool)

        return self._assemble_agg_output(node, gkey_out, key_types,
                                         key_dicts, outs, out_specs,
                                         out_valid, gkey_nulls)

    def _exec_distinct_agg(self, node: P.Agg, b: DBatch, key_arrs,
                           key_types, key_dicts, key_nulls) -> DBatch:
        """DISTINCT aggregates — count/sum/avg/min/max(DISTINCT x), any
        number, freely mixed with plain aggregates (reference: the
        sorted Agg transition, nodeAgg.c DISTINCT path).  Each DISTINCT
        aggregate runs dedupe-then-reduce (two sorted passes); plain
        aggregates run one pass.  Every pass groups on the SAME key
        columns with the same validity, so group ordering is identical
        and per-pass outputs align positionally."""
        gkeys_full = self._grouping_arrays(key_arrs, key_nulls)
        # every pass below finds the same groups in the same order: ONE
        # output class under ONE ladder id (no GROUP BY: no span, one
        # group, the smallest class)
        max_g, g_jid = self._agg_class(b, self._group_key_spans(
            node, b, key_dicts, key_nulls))
        n_gk = len(gkeys_full)

        out_cols: dict = {}
        out_types: dict = {}
        out_nulls: dict = {}
        base = None

        def knulls_from(gkeys_out):
            extra = list(gkeys_out[len(key_arrs):n_gk])
            return [extra.pop(0).astype(bool) if nm is not None else None
                    for nm in key_nulls]

        plain = [(n_, ac) for n_, ac in node.aggs if not ac.distinct]
        if plain:
            pseudo = dataclasses.replace(node, aggs=plain)
            kinds, inputs, out_specs = self._agg_inputs(pseudo, b,
                                                        final=False)
            gkeys_p, outs, ng = self._sorted_agg(
                gkeys_full or (jnp.zeros(b.padded, jnp.int64),),
                b.valid, tuple(inputs), max_g, tuple(kinds), jid=g_jid)
            if not self._traced:
                ng = int(ng)
            pb = self._assemble_agg_output(
                pseudo, list(gkeys_p[:len(key_arrs)]), key_types,
                key_dicts, outs, out_specs,
                jnp.arange(max_g) < (ng if key_arrs else 1),
                knulls_from(gkeys_p))
            base = pb
            for n_, _ac in plain:
                out_cols[n_] = pb.cols[n_]
                out_types[n_] = pb.types[n_]
                if n_ in pb.nulls:
                    out_nulls[n_] = pb.nulls[n_]

        for name, ac in node.aggs:
            if not ac.distinct:
                continue
            arg_arr, arg_null = self._eval_pair(ac.arg, b)
            is_float = jnp.issubdtype(arg_arr.dtype, jnp.floating)
            if is_float:
                # -0.0 == +0.0 in SQL: normalize before the bit-pattern
                # dedupe
                fv = arg_arr.astype(device_float())
                fv = jnp.where(fv == 0, jnp.zeros((), fv.dtype), fv)
                enc = float_to_bits(fv)
            else:
                enc = arg_arr.astype(jnp.int64)
            nn = jnp.zeros(b.padded, bool) if arg_null is None \
                else arg_null
            # pass 1: dedupe (group keys, value, value-null); null rows
            # KEEP their group alive so passes stay aligned
            enc = jnp.where(nn, 0, enc)
            keys1 = gkeys_full + (enc, nn.astype(jnp.int64))
            g1_pad, jid1 = self._agg_class(b)
            gkeys1, _, ng1 = self._sorted_agg(
                keys1, b.valid, (b.valid.astype(jnp.int64),), g1_pad,
                ("count",), jid=jid1)
            valid1 = jnp.arange(g1_pad) < ng1
            dval = gkeys1[n_gk]
            dnull = gkeys1[n_gk + 1].astype(bool)
            contrib = valid1 & ~dnull
            if is_float:
                fval = bits_to_float(dval)
            else:
                fval = dval
            # pass 2: reduce the deduped values per group
            if ac.func == "count":
                kinds2 = ("sum",)
                ins2 = (contrib.astype(jnp.int64),)
            elif ac.func in ("sum", "avg"):
                v = jnp.where(contrib, fval,
                              jnp.zeros((), fval.dtype))
                # non-float values sum exactly in int64 (AVG divides
                # at the end; see _agg_inputs)
                kinds2 = ("sumf" if is_float else "sum", "sum")
                ins2 = (v, contrib.astype(jnp.int64))
            elif ac.func in ("min", "max"):
                if is_float:
                    neutral = np.inf if ac.func == "min" else -np.inf
                else:
                    info = jnp.iinfo(jnp.int64)
                    neutral = info.max if ac.func == "min" else info.min
                kinds2 = (ac.func, "sum")
                ins2 = (jnp.where(contrib, fval,
                                  jnp.asarray(neutral, fval.dtype)),
                        contrib.astype(jnp.int64))
            else:
                raise ExecError(
                    f"DISTINCT {ac.func} unsupported")
            gkeys2, outs2, ng2 = self._sorted_agg(
                tuple(gkeys1[:n_gk]) if n_gk else
                (jnp.zeros(g1_pad, jnp.int64),),
                valid1, ins2, max_g, kinds2, jid=g_jid)
            if not self._traced:
                ng2 = int(ng2)
            if base is None:
                base = self._assemble_agg_output(
                    dataclasses.replace(node, aggs=[]),
                    list(gkeys2[:len(key_arrs)]), key_types, key_dicts,
                    [], [],
                    jnp.arange(max_g) < (ng2 if key_arrs else 1),
                    knulls_from(gkeys2))
            if ac.func == "count":
                out_cols[name] = outs2[0]
                out_types[name] = T.INT64
            elif ac.func == "avg":
                s, c = outs2
                scale = ac.arg.type.scale \
                    if ac.arg.type.kind == TypeKind.DECIMAL else 0
                out_cols[name] = jnp.where(
                    c > 0, s.astype(device_float()) / jnp.maximum(c, 1)
                    / 10 ** scale, jnp.zeros((), device_float()))
                out_types[name] = T.FLOAT64
                out_nulls[name] = c == 0
            else:
                v, c = outs2
                out_cols[name] = v
                out_types[name] = ac.arg.type if ac.func != "count" \
                    else T.INT64
                out_nulls[name] = c == 0

        cols = dict(base.cols)
        types = dict(base.types)
        nulls = dict(base.nulls)
        for n_, a in out_cols.items():
            cols[n_] = a
            types[n_] = out_types[n_]
        for n_, m in out_nulls.items():
            nulls[n_] = m
        return DBatch(cols, base.valid, types, base.dicts, nulls)

    # ---- window functions ----
    def _win_key(self, e: E.Expr, b: DBatch, for_order: bool):
        """Sortable key + null mask for a window partition/order
        expression.  The caller adds the null mask as its OWN sort/
        grouping column, so NULL never collides with +inf/INT64_MAX
        values (PG sorts NULL as a distinct peer group)."""
        arr, nm = self._eval_pair(e, b)
        if getattr(arr, "ndim", 1) == 0:   # constant key: broadcast
            arr = jnp.broadcast_to(arr, b.valid.shape)
        d = _dict_for_expr(e, b.dicts)
        if d is not None and for_order:
            # dictionary codes are unordered: map code -> rank
            arr = jnp.asarray(_text_ranks(d))[jnp.clip(arr, 0, len(d) - 1)]
        if arr.dtype == jnp.bool_:
            arr = arr.astype(jnp.int32)
        if not jnp.issubdtype(arr.dtype, jnp.floating):
            arr = arr.astype(jnp.int64)
        if nm is not None:
            # canonicalize the value under NULL so grouping is stable
            arr = jnp.where(nm, jnp.zeros((), arr.dtype), arr)
        return arr, nm

    def _exec_window(self, node: P.Window) -> DBatch:
        """Sorted-partition window computation (reference:
        nodeWindowAgg.c): one lax.sort per distinct (partition, order)
        spec, partition/peer boundaries by neighbor compare, running
        aggregates via prefix sums over the SQL default frame (RANGE
        UNBOUNDED PRECEDING..CURRENT ROW — peers share values), results
        scattered back to input row order."""
        b = self.exec_node(node.child).ensure_all()
        n = b.padded
        iota = jnp.arange(n, dtype=jnp.int64)
        new_cols: dict = {}
        new_nulls: dict = {}
        new_dicts: dict = {}
        specs: dict = {}
        for name, wc in node.calls:
            specs.setdefault((wc.partition, wc.order), []).append(
                (name, wc))
        for (part, order), calls in specs.items():
            pkeys = []
            for pe in part:
                arr, nm = self._win_key(pe, b, for_order=False)
                if nm is not None:
                    pkeys.append(nm.astype(jnp.int64))
                pkeys.append(arr)
            okeys = []
            for oe, desc in order:
                arr, nm = self._win_key(oe, b, for_order=True)
                if nm is not None:
                    # NULLS LAST asc / FIRST desc, as a separate key so
                    # NULL stays a distinct peer group
                    okeys.append(K._order_key(nm.astype(jnp.int32),
                                              desc))
                okeys.append(K._order_key(arr, desc))
            operands = [~b.valid] + pkeys + okeys + [iota]
            sorted_ = jax.lax.sort(operands,
                                   num_keys=len(operands) - 1)
            s_iota = sorted_[-1]
            s_pk = sorted_[1:1 + len(pkeys)]
            s_ok = sorted_[1 + len(pkeys):-1]
            s_valid = b.valid[s_iota]
            first = iota == 0
            p_bound = first
            for k in s_pk:
                p_bound = p_bound | (k != jnp.roll(k, 1))
            o_bound = p_bound
            for k in s_ok:
                o_bound = o_bound | (k != jnp.roll(k, 1))
            p_start = jax.lax.cummax(jnp.where(p_bound, iota, 0))
            peer_start = jax.lax.cummax(jnp.where(o_bound, iota, 0))
            # next peer boundary strictly after i -> end of i's peer group
            nb = jnp.where(o_bound, iota, n)
            nxt = jax.lax.cummin(nb[::-1])[::-1]
            peer_end = jnp.concatenate(
                [nxt[1:], jnp.asarray([n], jnp.int64)]) - 1
            pid = jnp.cumsum(p_bound.astype(jnp.int64)) - 1
            ob_cum = jnp.cumsum(o_bound.astype(jnp.int64))
            # last VALID row of each partition: padding rows sort after
            # every valid row, so a frame end must never reach into them
            idxv = jnp.where(s_valid, iota, -1)
            p_end = jax.ops.segment_max(idxv, pid.astype(jnp.int32),
                                        num_segments=n)[pid]
            peer_end_v = jnp.minimum(peer_end, p_end)

            def scatter(res):
                return jnp.zeros(n, res.dtype).at[s_iota].set(res)

            for name, wc in calls:
                if wc.func == "row_number":
                    new_cols[name] = scatter(iota - p_start + 1)
                    continue
                if wc.func == "rank":
                    new_cols[name] = scatter(peer_start - p_start + 1)
                    continue
                if wc.func == "dense_rank":
                    dr = ob_cum - ob_cum[p_start] + 1
                    new_cols[name] = scatter(dr)
                    continue
                if wc.func in ("lag", "lead"):
                    # ROW-offset within the partition (reference:
                    # WinGetFuncArgInPartition); default fills only
                    # out-of-partition offsets, a NULL source value
                    # stays NULL
                    a, anm = self._eval_pair(wc.arg, b)
                    a_s = a[s_iota]
                    anm_s = anm[s_iota] if anm is not None else None
                    src = iota - wc.offset if wc.func == "lag" \
                        else iota + wc.offset
                    srcc = jnp.clip(src, 0, n - 1)
                    inside = (src >= 0) & (src < n) & \
                        (p_start[srcc] == p_start[iota]) & s_valid[srcc]
                    val = a_s[srcc]
                    src_null = anm_s[srcc] if anm_s is not None else \
                        jnp.zeros(n, bool)
                    if wc.default is not None:
                        dv, dnm = self._eval_pair(wc.default, b)
                        # default evaluates in INPUT row order: re-sort
                        # alongside the values before combining
                        if getattr(dv, "ndim", 0):
                            dv = dv[s_iota]
                        if dnm is not None:
                            dnm = dnm[s_iota]
                        val = jnp.where(inside, val,
                                        jnp.asarray(dv).astype(
                                            val.dtype))
                        nullm = inside & src_null
                        if dnm is not None:
                            nullm = nullm | (~inside & dnm)
                    else:
                        nullm = ~inside | src_null
                    new_cols[name] = scatter(val)
                    new_nulls[name] = scatter(nullm)
                    d = _dict_for_expr(wc.arg, b.dicts)
                    if d is not None:   # TEXT codes keep their decode
                        new_dicts[name] = d
                    continue
                # aggregate / value function over the frame: every call
                # reduces over per-row [fs, fe] (sorted-position bounds)
                # — prefix sums for sum/count/avg, a log-doubling sparse
                # table for min/max, a gather for first/last_value
                # (reference: nodeWindowAgg.c eval_windowaggregates +
                # WinGetFuncArgInFrame, generalized to vector form)
                if wc.arg is not None:
                    a, anm = self._eval_pair(wc.arg, b)
                    a_s = a[s_iota]
                    anm_s = anm[s_iota] if anm is not None else None
                else:
                    a_s, anm_s = None, None
                contrib = s_valid if anm_s is None else \
                    (s_valid & ~anm_s)
                fs, fe = self._frame_bounds(wc.frame, bool(order), iota,
                                            p_start, p_end, peer_start,
                                            peer_end_v)
                fsc = jnp.clip(fs, 0, n - 1)
                fec = jnp.clip(fe, 0, n - 1)
                empty = (fe < fs) | ~s_valid
                cvals = contrib.astype(jnp.int64)
                ccum = jnp.cumsum(cvals)
                cex = ccum - cvals
                rcount = jnp.where(empty, 0, ccum[fec] - cex[fsc])
                if wc.func == "count":
                    new_cols[name] = scatter(rcount)
                    continue
                if wc.func in ("first_value", "last_value"):
                    pos = fsc if wc.func == "first_value" else fec
                    val = a_s[pos]
                    nullm = empty
                    if anm_s is not None:
                        nullm = nullm | anm_s[pos]
                    new_cols[name] = scatter(val)
                    new_nulls[name] = scatter(nullm)
                    d = _dict_for_expr(wc.arg, b.dicts)
                    if d is not None:
                        new_dicts[name] = d
                    continue
                if wc.func in ("min", "max"):
                    d = _dict_for_expr(wc.arg, b.dicts) \
                        if wc.arg is not None else None
                    if d is not None:
                        # dictionary codes are unordered: reduce over
                        # lexicographic ranks, then map the winning rank
                        # back to its code (same trick as _win_key)
                        dorder = np.argsort(np.asarray(d, dtype=object))
                        rank = np.empty(max(len(d), 1), dtype=np.int32)
                        rank[dorder] = np.arange(len(d), dtype=np.int32)
                        ranked = jnp.asarray(rank)[
                            jnp.clip(a_s, 0, len(d) - 1)]
                        rr = self._range_minmax(ranked, contrib, fsc,
                                                fec, wc.func == "min")
                        res = jnp.asarray(dorder.astype(np.int32))[
                            jnp.clip(rr, 0, len(d) - 1)]
                        new_dicts[name] = d
                    else:
                        res = self._range_minmax(a_s, contrib, fsc, fec,
                                                 wc.func == "min")
                    new_cols[name] = scatter(res)
                    new_nulls[name] = scatter(rcount == 0)
                    continue
                if wc.func in ("sum", "avg"):
                    av = a_s.astype(device_float()) \
                        if wc.func == "avg" else a_s
                    av = jnp.where(contrib, av, jnp.zeros((), av.dtype))
                    scum = jnp.cumsum(av)
                    sex = scum - av
                    rsum = jnp.where(empty, 0, scum[fec] - sex[fsc])
                    if wc.func == "avg":
                        scale = wc.arg.type.scale \
                            if wc.arg.type.kind == TypeKind.DECIMAL else 0
                        res = jnp.where(
                            rcount > 0,
                            rsum.astype(device_float())
                            / jnp.maximum(rcount, 1) / 10 ** scale,
                            jnp.zeros((), device_float()))
                    else:
                        res = rsum
                    new_cols[name] = scatter(res)
                    new_nulls[name] = scatter(rcount == 0)
                    continue
                raise ExecError(f"window function {wc.func} unsupported")
        cols = dict(b.cols)
        cols.update(new_cols)
        types = dict(b.types)
        for name, wc in node.calls:
            types[name] = wc.type
        nulls = dict(b.nulls)
        nulls.update(new_nulls)
        dicts = dict(b.dicts)
        dicts.update(new_dicts)
        return DBatch(cols, b.valid, types, dicts, nulls)

    @staticmethod
    def _frame_bounds(frame, has_order, iota, p_start, p_end,
                      peer_start, peer_end_v):
        """Per-row inclusive [fs, fe] sorted-position bounds of a window
        frame.  Defaults: RANGE UNBOUNDED PRECEDING..CURRENT ROW with an
        ORDER BY, the whole partition without (SQL92 / nodeWindowAgg.c
        update_frameheadpos/update_frametailpos semantics)."""
        if frame is None:
            if has_order:
                return p_start, peer_end_v
            return p_start, p_end
        mode, sb, eb = frame
        if mode == "rows":
            def rows_bound(bd):
                kind, k = bd
                if kind == "unbounded_preceding":
                    return p_start
                if kind == "unbounded_following":
                    return p_end
                if kind == "current":
                    return iota
                if kind == "preceding":
                    return iota - k
                return iota + k
            fs = jnp.maximum(rows_bound(sb), p_start)
            fe = jnp.minimum(rows_bound(eb), p_end)
            return fs, fe
        # RANGE: only unbounded / current-row bounds (peer-aligned)
        fs = p_start if sb[0] == "unbounded_preceding" else peer_start
        fe = p_end if eb[0] == "unbounded_following" else peer_end_v
        return fs, fe

    @staticmethod
    def _range_minmax(a_s, contrib, fsc, fec, is_min):
        """min/max over arbitrary inclusive ranges via a log-doubling
        sparse table: level j holds the reduction of [i, i+2^j-1]; a
        query [l, r] is the reduction of two (overlapping) power-of-two
        spans.  O(n log n) build, fully vectorized — the TPU-friendly
        replacement for nodeWindowAgg.c's per-row frame rescans."""
        dtype = a_s.dtype
        if jnp.issubdtype(dtype, jnp.floating):
            neutral = jnp.asarray(np.inf if is_min else -np.inf, dtype)
        else:
            info = jnp.iinfo(dtype)
            neutral = jnp.asarray(info.max if is_min else info.min, dtype)
        op = jnp.minimum if is_min else jnp.maximum
        n = a_s.shape[0]
        v = jnp.where(contrib, a_s, neutral)
        levels = [v]
        j = 0
        while (1 << (j + 1)) <= n:
            half = 1 << j
            prev = levels[-1]
            shifted = jnp.concatenate(
                [prev[half:], jnp.full((half,), neutral, dtype)])
            levels.append(op(prev, shifted))
            j += 1
        st = jnp.stack(levels)                      # (L, n)
        length = jnp.maximum(fec - fsc + 1, 1)
        jq = jnp.floor(jnp.log2(length.astype(device_float()))).astype(
            jnp.int32)
        jq = jnp.clip(jq, 0, len(levels) - 1)
        span = jnp.left_shift(jnp.int64(1), jq.astype(jnp.int64))
        lo = st[jq, fsc]
        hi = st[jq, jnp.maximum(fec - span + 1, 0)]
        return op(lo, hi)

    # ---- sort / limit ----
    def _exec_sort(self, node: P.Sort) -> DBatch:
        # width-consuming: every carried column rides the sort payload
        b = self.exec_node(node.child).ensure_all()
        key_arrs, descs = [], []
        for ke, desc in node.keys:
            arr, nm = self._eval_pair(ke, b)
            d = _dict_for_expr(ke, b.dicts)
            if d is not None:
                # dictionary codes are unordered: map code -> rank
                arr = jnp.asarray(_text_ranks(d))[
                    jnp.clip(arr, 0, len(d) - 1)]
            if nm is not None:
                # NULLs sort as +infinity: last under ASC, first under
                # DESC — PostgreSQL's default NULLS LAST/FIRST pairing
                if arr.dtype == jnp.bool_:
                    big = jnp.asarray(True)
                elif jnp.issubdtype(arr.dtype, jnp.floating):
                    big = jnp.asarray(np.inf, arr.dtype)
                else:
                    big = jnp.asarray(jnp.iinfo(arr.dtype).max, arr.dtype)
                arr = jnp.where(nm, big, arr)
            key_arrs.append(arr)
            descs.append(bool(desc))
        names = list(b.cols.keys())
        null_names = list(b.nulls.keys())
        payload = tuple(b.cols[n] for n in names) + \
            tuple(b.nulls[n] for n in null_names)
        limit = node.limit
        sorted_payload, s_valid = K.sort_rows(
            tuple(key_arrs), b.valid, payload, tuple(descs),
            limit=limit)
        cols = dict(zip(names, sorted_payload[:len(names)]))
        nulls = dict(zip(null_names, sorted_payload[len(names):]))
        return DBatch(cols, s_valid, b.types, b.dicts, nulls)

    def _exec_limit(self, node: P.Limit) -> DBatch:
        b = self.exec_node(node.child)
        # valid rows are in order (post-sort); mask beyond count+offset
        idx = jnp.cumsum(b.valid.astype(jnp.int32))
        keep = b.valid
        if node.offset:
            keep = keep & (idx > node.offset)
        if node.count is not None:
            keep = keep & (idx <= (node.count + node.offset))
        return DBatch(b.cols, keep, b.types, b.dicts, b.nulls, b.lazy)

    def _exec_result(self, node: P.Result) -> DBatch:
        cols, types, nulls = {}, {}, {}
        base = DBatch({}, jnp.ones(1, dtype=bool), {}, {})
        for name, oe in node.outputs:
            arr, nm = self._eval_pair(oe, base)
            cols[name] = jnp.broadcast_to(arr, (1,)) \
                if getattr(arr, "ndim", 0) == 0 else arr
            if nm is not None:
                nulls[name] = nm
            types[name] = oe.type
        return DBatch(cols, jnp.ones(1, dtype=bool), types, {}, nulls)

    def _exec_gather(self, node: P.Gather) -> DBatch:
        return self.exec_node(node.child)


# ---------------------------------------------------------------------------

def _cols_of(e: E.Expr) -> set[str]:
    return {x.name for x in E.walk(e) if isinstance(x, E.Col)}


def _ann_assignments(store, col: str, vecs, n: int):
    """Cluster assignments for the IVF index, recomputed lazily when rows
    were added since the build (pgvector re-lists on insert; we re-assign
    on demand — one matmul)."""
    import jax.numpy as _jnp

    from ..ops import ann as ANN
    info = store.ann_indexes[col]
    centroids = _jnp.asarray(info["centroids"])
    cached = info.get("_assign_cache")
    if cached is not None and cached[0] == store.version:
        return cached[1], centroids
    assign = ANN.assign_clusters(vecs, centroids, info["metric"])
    info["_assign_cache"] = (store.version, assign)
    return assign, centroids


def _dict_for_expr(e: E.Expr, dicts: dict):
    """Decode dictionary for a TEXT-valued expr output (transformed for
    TextExpr — many codes may map to one string downstream)."""
    if isinstance(e, E.Col) and e.name in dicts:
        return dicts[e.name]
    if isinstance(e, E.TextExpr):
        base = dicts.get(e.col.name)
        if base is None:
            return None
        return [e.apply(v) for v in base]
    if isinstance(e, E.Lit) and e.lit_type.kind == TypeKind.TEXT \
            and e.value is not None:
        # projected TEXT literal: every row decodes to the one value
        return [str(e.value)]
    if isinstance(e, E.Case) and e.type.kind == TypeKind.TEXT:
        from .expr_compile import case_text_dict
        return case_text_dict(e)
    return None


def _text_ranks(d) -> np.ndarray:
    """code -> the rank of its string among the dictionary's DISTINCT
    strings, int32.  Two codes of one string (a transformed column's:
    `substring(c_phone from 1 for 2)` keeps c_phone's 150,000 codes over
    25 strings) share a rank: they are ONE sort key, and the next key
    orders their rows.  Sorting the distinct strings, not an object array
    of every code's: Q22's ORDER BY cntrycode ranked 150,000 strings a
    statement, ~100 ms of a 141 ms reply on the chip's host (PERF.md
    section 6, PR 39)."""
    place = {v: i for i, v in enumerate(sorted(set(d)))}
    return np.fromiter(map(place.__getitem__, d), np.int32, len(d)) \
        if d else np.zeros(1, np.int32)


def scalars_from_batch(b: DBatch, outputs: list) -> dict:
    """{parameter: (value | None, type)} of an init plan's result: the
    batch's columns in order, one a name of `outputs` (InitPlan.outputs:
    one, or the several values one run gives).  An empty subquery is SQL
    NULL (None), not 0 (reference: ExecScanSubPlan's unset-param NULL).
    Shared by the local and distributed executors."""
    b.ensure_all()
    valid = np.asarray(b.valid)
    if int(valid.sum()) > 1:
        raise ExecError("scalar subquery returned more than one row")
    out = {}
    for col, (name, t) in zip(b.cols, outputs):
        vals = np.asarray(b.cols[col])[valid]
        null = len(vals) == 0 or (
            col in b.nulls and bool(np.asarray(b.nulls[col])[valid][0]))
        out[name] = (None if null else vals[0].item(), t)
    return out


def materialize(b: DBatch, names: Optional[list[str]] = None):
    """DBatch -> (column_names, list of python row tuples), decoded.
    The final-projection materialization point: only the REQUESTED
    columns leave the indirection layer."""
    if not obs_trace.ENABLED:
        return _materialize(b, names)
    with obs_trace.span("finalize"):
        return _materialize(b, names)


#: A result batch whose named columns and null masks, at the batch's
#: padded width, come to at least this many bytes has its live rows
#: selected on the device and only those copied; a smaller one is
#: copied whole, which is cheaper than one more program and, per new
#: padded size, one more executable.  Measured on the chip, PERF.md
#: section 6 (PR 26).
_COMPACT_MIN_BYTES = 1 << 20

#: the selection's first output class (storage/batch.size_class's floor)
_LIVE_FLOOR = 256


@functools.partial(jax.jit, static_argnames=("out_size",))
def _gather_live(valid, cols, nulls, lazy, out_size: int):
    """One program for a wide batch's way out: the positions of its
    first `out_size` live rows (ops/kernels.live_positions), and the
    columns and null masks gathered there, a lazy column straight
    through its indirection (never at full width).  The dicts are keyed
    by the column's place in the select list, so the program depends on
    shapes and not on names.  Returns ONE uint8 buffer: the count
    (int32), the columns in select-list order, then the null masks; an
    array a column costs the host more to dispatch, to copy and to free
    than the program's whole device time (PERF.md section 6, PR 26)."""
    count, idx = K.live_positions(valid, out_size)
    with jax.named_scope("otb.finalize"):
        gcols, gnulls = DBatch(cols, valid, {}, {}, nulls,
                               lazy).gather_rows(idx)
        parts = [count, *(gcols[i] for i in sorted(gcols)),
                 *(gnulls[i] for i in sorted(gnulls))]
        return jnp.concatenate([
            jax.lax.bitcast_convert_type(
                a.astype(jnp.uint8) if a.dtype == jnp.bool_ else a,
                jnp.uint8).reshape(-1) for a in parts])


def _fetch_live(b: DBatch, names: list[str], srcs: list, nullable: list):
    """(count, cols, nulls) with the live rows first, as numpy, or None
    where so many rows live that the whole copy is the shorter way.
    `srcs` holds each named column's array (a lazy one's source),
    `nullable` the names that carry a null mask."""
    at = {n: i for i, n in enumerate(names)}
    parts = [{at[n]: d[n] for n in names if n in d}
             for d in (b.cols, b.nulls, b.lazy)]
    out_size = _LIVE_FLOOR
    while True:
        with obs_trace.span("finalize.gather") as sp:
            dev = _gather_live(b.valid, *parts, out_size=out_size)
            sp.set(calls=1)
        with obs_trace.span("finalize.fetch") as sp:
            buf = np.asarray(dev)
            sp.set(fetches=1, bytes=buf.nbytes, compacted=out_size)
        count = int(buf[:4].view(np.int32)[0])
        if count <= out_size:
            break
        out_size = size_class(count)
        if 2 * out_size > b.padded:
            return None
    cols, lo = [], 4
    for a in srcs:
        hi = lo + _arr_bytes(a, out_size)
        cols.append(buf[lo:hi].view(a.dtype).reshape(
            (out_size,) + a.shape[1:]))
        lo = hi
    nulls = {n: buf[lo + i * out_size:lo + (i + 1) * out_size].view(bool)
             for i, n in enumerate(nullable)}
    return count, cols, nulls


def _materialize(b: DBatch,  # otblint: sync-boundary
                 names: Optional[list[str]] = None):
    """Three kinds of work, a span each: the device programs that ready
    the columns (dispatched, not waited for), the device-to-host copy,
    and the numpy-to-Python decode.  The ONE sync of finalize is
    `finalize.fetch`'s: it also waits for those programs and for
    whatever program produced the batch.  A batch under
    _COMPACT_MIN_BYTES is copied whole, validity, columns and null masks
    in one `device_get`, and its live rows found on the host; a wider
    one leaves only its live rows, one buffer (_fetch_live)."""
    if names is None:
        names = b.names()
    srcs = [b.lazy[n].src if n in b.lazy else b.cols[n] for n in names]
    nullable = [n for n in names if b.maybe_null(n)]
    row_bytes = sum(_arr_bytes(a, 1) for a in srcs) + len(nullable)
    live = None
    if b.padded * row_bytes >= _COMPACT_MIN_BYTES:
        live = _fetch_live(b, names, srcs, nullable)
    if live is None:
        with obs_trace.span("finalize.gather") as sp:
            if b.lazy:
                sp.set(calls=sum(b.lazy[n].dispatches()
                                 for n in names if n in b.lazy))
            b.ensure(names)
        with obs_trace.span("finalize.fetch") as sp:
            # one batched copy: every array's transfer starts before
            # any is waited for
            valid, cols, nulls = jax.device_get((
                b.valid, [b.cols[n] for n in names],
                {n: b.nulls[n] for n in names if n in b.nulls}))
            sp.set(fetches=1,
                   bytes=valid.nbytes + sum(a.nbytes for a in cols)
                   + sum(a.nbytes for a in nulls.values()),
                   compacted=0)
    else:
        valid = None
        count, cols, nulls = live
    with obs_trace.span("finalize.decode"):
        rows_idx = slice(count) if valid is None else np.nonzero(valid)[0]
        out_cols = [
            _decode_column(arr[rows_idx], b.types[n], b.dicts.get(n, []),
                           nulls[n][rows_idx] if n in nulls else None)
            for n, arr in zip(names, cols)]
        rows = list(zip(*out_cols)) if out_cols else []
    # the statement's host-materialized footprint (its columns, at the
    # width they were copied), on `finalize`
    obs_trace.annotate(rows=len(rows), bytes=sum(a.nbytes for a in cols))
    return names, rows


def _decode_column(arr, t: SqlType, d, nullm) -> list:
    """One fetched column (numpy, live rows only) as Python values."""
    if t.kind == TypeKind.TEXT:
        if d:
            table = np.asarray(list(d) + [None], dtype=object)
            codes = np.where((arr >= 0) & (arr < len(d)), arr, len(d))
            vals = table[codes].tolist()
        else:
            vals = [None] * len(arr)
    elif t.kind == TypeKind.DECIMAL:
        vals = (arr / 10 ** t.scale).tolist()
    elif t.kind == TypeKind.DATE:
        epoch = np.datetime64("1970-01-01", "D")
        vals = [str(v) for v in (epoch + arr.astype("timedelta64[D]"))]
    elif t.kind == TypeKind.BOOL:
        vals = arr.astype(bool).tolist()
    elif t.kind == TypeKind.FLOAT64:
        vals = arr.astype(np.float64).tolist()
    elif t.kind == TypeKind.VECTOR:
        vals = [tuple(float(x) for x in v) for v in arr]
    else:
        vals = arr.astype(np.int64).tolist() \
            if arr.dtype.kind in "iu" else arr.tolist()
    if nullm is not None:
        vals = [None if m else v for v, m in zip(vals, nullm)]
    return vals


class InstrumentedExecutor(Executor):
    """EXPLAIN ANALYZE executor: wall time + output rows per plan node
    (the reference's InstrumentOption timers, commands/explain.c).

    Eager-only by construction — built solely on the session ANALYZE
    path, never inside a trace — so the per-node ``count()`` syncs
    below are a sanctioned instrumentation price, exactly like the
    reference's per-node gettimeofday pairs.  Whole-fragment fusion is
    disabled (``_fuse``): a compiled program's interior is opaque, and
    ANALYZE promises actuals on EVERY node — the reference's
    tuple-at-a-time instrumentation has the same "observed run is the
    slow run" caveat."""

    _fuse = False

    def __init__(self, ctx, frag_tag=None):  # otblint: eager-only
        super().__init__(ctx, frag_tag)
        self.node_stats: dict = {}   # id(plan node) -> {"rows","ms","calls"}

    def exec_node(self, node):  # otblint: eager-only
        import time
        t0 = time.perf_counter()
        b = super().exec_node(node)
        ms = (time.perf_counter() - t0) * 1e3
        try:
            rows = int(b.count())
        except Exception:
            rows = -1
        st = self.node_stats.get(id(node))
        if st is None:
            self.node_stats[id(node)] = {"rows": rows, "ms": ms,
                                         "calls": 1}
        else:     # rescanned node (init plans / subplans): accumulate
            st["rows"] = rows
            st["ms"] += ms
            st["calls"] += 1
        return b


def _metrics_samples():
    """Registry collector: EXEC_STATS as labeled samples
    (obs/metrics.py — one pane with plancache/bufferpool)."""
    for tier, *vals in exec_stats_rows():
        for f, v in zip(STAT_FIELDS, vals):
            yield (f"otb_execstats_{f}", {"tier": tier}, v)


from ..obs.metrics import REGISTRY as _METRICS  # noqa: E402
_METRICS.register_collector("execstats", _metrics_samples)


def _dense_bound(key_types: list[SqlType], key_dicts: list) -> Optional[int]:
    """Combined group-domain bound if all keys have small known domains."""
    bound = 1
    for t, d in zip(key_types, key_dicts):
        if t.kind == TypeKind.TEXT and d is not None:
            bound *= max(len(d), 1)
        elif t.kind == TypeKind.BOOL:
            bound *= 2
        else:
            return None
    return bound
