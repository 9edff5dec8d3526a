"""Whole-fragment fusion: one XLA program per plan subtree.

Reference analog: this is where the rebuild's "XLA is the JIT" thesis
pays — the reference interprets plans tuple-at-a-time (ExecProcNode) and
JITs only expressions (src/backend/jit/llvm); here an entire
SeqScan → Filter/Project → [HashJoin...] → Agg → Sort/Limit fragment
compiles into ONE jitted program, so XLA fuses visibility, quals,
projections, join index-composition, aggregate transition and sort into
a single pass over the columns with no intermediate materialization
(the eager per-operator dispatch this replaces left ~10 full-column
temporaries per query on the hot path).

Mechanics: `try_fused` pattern-matches a traceable subtree (SeqScan
leaves — join subtrees with multiple scans included — no operators that
need host-side dynamic output sizing), stages every leaf table's device
columns once (outside the trace), and runs the REGULAR Executor over
the plan inside `jax.jit` with `_traced=True` — host-sync size classes
switch to static worst-case shapes.  Join outputs inside the trace use
the SAME static size-class ladder the mesh tier runs under shard_map
(exec/executor.py _exec_hashjoin `_traced` branch): a join's output
class starts at a quarter of its larger input, the program reports
per-join required totals, and the host retraces one step up on
overflow — the learned factors persist in the tier's ladder (`_LADDER`,
a plancache.Ladder: the one rule of growth both compiled tiers share)
keyed by the literal-masked fragment shape, so steady state is one
program call with ZERO per-join device→host syncs (the eager path pays
one `int(total)` sync per join per query).  A sorted aggregate whose
keys' ranges do not bound its groups rides the same ladder under an id
of the same sequence (executor._agg_class): a quarter of its input's
rows, its groups reported beside the joins' totals.

Compiled programs live in the shared program cache (exec/plancache.py
FUSED tier) under a CANONICAL FRAGMENT SIGNATURE whose plan part is
`plan/physical.plan_key` (the one spelling both compiled tiers key a
plan by): numeric/date literals in scan filters and quals are masked
out of the plan and ride as traced program inputs instead, as does the
dictionary code of a text parameter compared with a column
(`_bound_ctx`), so `WHERE l_shipdate <= X` with a different constant
reuses the compiled executable (the reference's generic-plan arm, taken
further: the plan cache there saves planning, this saves the XLA
compile).  Multi-table fragments key per-table components (store
identity + TEXT dictionary lengths — dictionaries are trace constants).
jax re-traces per array shape automatically — the pow2/quarter-step
size classes bound that — and the cache's global live-executable budget
evicts LRU programs deterministically.

What names a fragment's programs and how one is called is `_Prepared`,
for a statement (`_try_fused`), a morsel stream's chunks
(`FragmentProgram`) and the scheduler's coalesced batch alike.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..catalog.types import TypeKind
from ..plan import exprs as E
from ..plan import physical as P
from ..plan.planner import rewrite as rewrite_expr
from ..obs import trace as obs_trace
from ..sql.fingerprint import struct_key
from ..storage import codec
from . import plancache
from ..utils import locks
from ..utils.dtypes import dev_dtype

# the lock of this module's learned-state dict: CN-server threads
# share it, and the add-then-evict sequence below must be atomic
_STATE_LOCK = locks.Lock("exec.fused._STATE_LOCK")

# plan shapes whose literal-masked trace host-synced (a masked value
# fed a host branch): retried and cached baked instead.  Bounded FIFO
# (insertion-ordered dict): the oldest learned fallback is evicted one
# at a time — a wholesale clear() would drop every learned entry at
# once and force a burst of doomed literal-masked retraces.
_MASK_REFUSED: dict = {}    # guarded_by: _STATE_LOCK
_MASK_REFUSED_MAX = 512

# learned size-class ladder: literal-masked fragment shape -> {id of a
# traced join or laddered sorted aggregate: factor}, so a fragment's
# second statement (any literal binding) starts at the right output
# class instead of replaying the overflow walk
_LADDER = plancache.Ladder(512)

# the plan nodes a fragment may hold (`physical.plan_key`'s `kinds`)
_KINDS = (P.SeqScan, P.Filter, P.Project, P.Agg, P.Sort, P.Limit,
          P.HashJoin)

# Observability hook: when set, called as EXPORT_HOOK(tag, fn, args)
# after each successful fused execution — the TPU lowering proof
# (utils/lowering_check.py) uses it to AOT-export the very programs the
# engine ran.
EXPORT_HOOK = None


def _mask_refused_add(k):
    with _STATE_LOCK:
        _MASK_REFUSED[k] = True
        while len(_MASK_REFUSED) > _MASK_REFUSED_MAX:
            _MASK_REFUSED.pop(next(iter(_MASK_REFUSED)))


def _find_scans(node) -> Optional[list]:
    """The SeqScan leaves of a fusable subtree, or None.  Join subtrees
    (multi-scan fragments) fuse: every leaf must bottom out in a
    SeqScan through Filter/Project/Sort/Limit chains; one non-distinct
    Agg is allowed above the joins (the Q3/Q5 shape)."""
    scans: list = []
    state = {"agg": False}

    def chain(nd, under_join: bool) -> bool:
        while True:
            if isinstance(nd, P.SeqScan):
                scans.append(nd)
                return True
            if isinstance(nd, (P.Filter, P.Project, P.Sort, P.Limit)):
                nd = nd.child
                continue
            if isinstance(nd, P.Agg):
                if nd.mode == "final":
                    return False  # operates on exchange input
                if state["agg"] or under_join:
                    return False
                if any(ac.distinct for _, ac in nd.aggs):
                    return False  # host-driven two-pass path
                state["agg"] = True
                nd = nd.child
                continue
            if isinstance(nd, P.HashJoin):
                if nd.kind == "cross":
                    return False  # output sized by a host count
                return chain(nd.left, True) and chain(nd.right, True)
            return False

    return scans if chain(node, False) else None


def _plan_has_join(node) -> bool:
    return any(isinstance(nd, P.HashJoin) for nd in P.walk(node))


def _below_join_floor(stores: dict) -> bool:
    """Tiny JOIN fragments stay on the eager path: its per-join host
    sync costs microseconds while a fresh XLA compile costs seconds, so
    fusing only pays above a row floor, summed across the fragment's
    leaf tables (0 = always fuse; read per call so tests and operators
    can flip it live)."""
    try:
        floor = int(os.environ.get("OTB_FUSE_JOIN_MIN_ROWS", "8192"))
    except ValueError:
        floor = 8192
    return sum(st.row_count() for st in stores.values()) < floor


def _has_transformed_dup_dict(node, stores: dict) -> bool:
    """True when a group key is a TextExpr whose transformed dictionary
    (in one of `stores`) maps several codes to one string — key
    canonicalization builds a host LUT per batch
    (executor._eval_group_keys), which is fine eager but not worth
    special-casing under the trace: fall back."""
    for x in P.walk_exprs(node):
        if isinstance(x, E.TextExpr):
            for store in stores.values():
                base = store.dicts.get(x.col.name.split(".", 1)[-1])
                if base is not None:
                    vals = [x.apply(v) for v in base.values]
                    if len(set(vals)) < len(vals):
                        return True
    return False


# literal kinds that mask out of the fragment signature and ride as
# traced inputs (TEXT/BOOL/NULL literals change program structure —
# dictionary predicates, 3VL — and stay baked)
_LIFT_KINDS = (TypeKind.INT32, TypeKind.INT64, TypeKind.FLOAT64,
               TypeKind.DECIMAL, TypeKind.DATE)


def _mask_expr(e, lits: list):
    def sub(x):
        if isinstance(x, E.Lit) and x.value is not None \
                and not isinstance(x.value, bool) \
                and isinstance(x.value, (int, float)) \
                and x.type.kind in _LIFT_KINDS:
            name = f"__fraglit{len(lits)}"
            lits.append((name, x.value, x.type))
            return E.Col(name, x.type)
        return None
    return rewrite_expr(e, sub)


def _mask_node(node, lits: list):
    """Canonical fragment form: clone the fusable subtree with numeric
    predicate literals replaced by __fraglitN parameter columns (walk
    order = positional identity, so equal-shaped fragments bind their
    literals to the same traced slots)."""
    if isinstance(node, P.SeqScan):
        if not node.filters:
            return node
        return dataclasses.replace(
            node, filters=[_mask_expr(f, lits) for f in node.filters])
    if isinstance(node, P.Filter):
        return dataclasses.replace(
            node, quals=[_mask_expr(q, lits) for q in node.quals],
            child=_mask_node(node.child, lits))
    if isinstance(node, P.HashJoin):
        return dataclasses.replace(
            node,
            residual=[_mask_expr(q, lits)
                      for q in (node.residual or [])],
            left=_mask_node(node.left, lits),
            right=_mask_node(node.right, lits))
    if isinstance(node, (P.Project, P.Agg, P.Sort, P.Limit)):
        return dataclasses.replace(node,
                                   child=_mask_node(node.child, lits))
    return node


def _bound_ctx(ctx, node):
    """`ctx` with the text parameters `node` compares with a column
    bound to that column's code in `ctx.stores`: an int rides as a
    traced input like any numeric parameter, so the program key holds
    no string."""
    from .executor import bind_text_params
    params = bind_text_params(P.walk_exprs(node), ctx.params,
                              ctx.stores, "fused")
    if params is ctx.params:
        return ctx
    return dataclasses.replace(ctx, params=params)


def _screen_fragment(ctx, node):
    """Shared fusability screen: `(stores, need_by_table)` when `node`
    is a traceable fragment over live SeqScan leaves (`_find_scans`
    admits `_KINDS` alone, so it has a plan key), else None; a
    self-join's scans share one staged entry per table with the union
    of their columns.  Used by the serial path (`_try_fused`) and the
    serving tier's batch classification (`batch_signature`) so both
    agree on what can run as one program."""
    if isinstance(node, P.SeqScan) or not isinstance(node, _KINDS):
        return None   # bare SeqScan gains nothing
    scans = _find_scans(node)
    if not scans:
        return None
    stores: dict = {}
    need_by_table: dict = {}
    for scan in scans:
        store = ctx.stores.get(scan.table.name)
        if store is None or \
                (ctx.staged and scan.table.name in ctx.staged):
            return None
        stores[scan.table.name] = store
        need_by_table.setdefault(scan.table.name, set()).update(
            P.needed_columns(node, scan.alias))
    if _has_transformed_dup_dict(node, stores):
        return None
    return stores, need_by_table


def _table_sig(stores: dict) -> tuple:
    """Per-table signature components: store identity + TEXT dictionary
    lengths (dictionaries are baked trace constants) + the staged codec
    classes (storage/codec.py codec_classes — QUANTIZED family/width
    tokens; an encoding change alters the traced avals, so it must be
    key-visible).  Callers must stage before keying: codec_classes
    reads what staging recorded, so key and avals always agree."""
    return tuple(
        (t, id(st), tuple(sorted((c, len(d.values))
                                 for c, d in st.dicts.items())),
         codec.codec_classes(st))
        for t, st in sorted(stores.items()))


def _call_args(staged_arrs: dict, nrows: dict, snapshot_ts, txid,
               params) -> tuple:
    """The argument tree of a fused program call: the ONE way
    `_try_fused`, `FragmentProgram.run` and `stage_fused_batch` hand a
    program what the host knows.  `nrows` maps a table to its live row
    count, `params` lists `(value, SqlType)` in traced order (numeric
    parameters, then masked literals); a batch passes a list of K
    values where a single call passes one.  Every value leaves as a
    numpy value of the dtype the program computes in (`dev_dtype` of
    its SQL type, what `expr_compile` casts a literal to: a 32-bit
    literal meets its 32-bit column unwidened), as
    `mesh_exec._call_program` hands its own: jax transfers them with
    the program's launch, so there is no put, no eager convert and no
    device scalar to free.  Row counts stay traced arguments
    (`_build_program`).  A value its declared type cannot hold (an
    `integer` parameter past 32 bits) is an error here: a cast inside
    the program would wrap around and answer."""
    try:
        pvals = tuple(np.asarray(v, dtype=dev_dtype(t)) for v, t in params)
    except OverflowError as e:
        from .executor import ExecError
        raise ExecError(f"value out of range for its type: {e}") from None
    return (staged_arrs,
            np.asarray(snapshot_ts, np.int64), np.asarray(txid, np.int64),
            pvals, {t: np.int64(nrows[t]) for t in sorted(nrows)})


def _stage(cache, stores: dict, need_by_table: dict) -> tuple:
    """`(arrays, row counts)` by table from the device cache (a pool
    lookup, version-keyed; a miss stages under it), ONCE, outside the
    trace and BEFORE the key is made (`_table_sig`): a cold start must
    mint the key the warm repeat will see, or the census sanitizer
    would count a phantom recompile."""
    arrs: dict = {}
    ns: dict = {}
    for t, need in sorted(need_by_table.items()):
        arrs[t], ns[t] = cache.get(stores[t], sorted(need))
    return arrs, ns


def _dbatch(meta, cols, valid, nulls):
    from .executor import DBatch
    return DBatch(dict(cols), valid, dict(meta["types"]),
                  dict(meta["dicts"]), dict(nulls))


# _Prepared.call: a traced value fed a host branch, and the shape is
# in _MASK_REFUSED from now on: its literals bake
_REFUSED = object()


class _Prepared:
    """A fragment ready to call: what names its compiled programs and
    how one is called, written ONCE for `_try_fused`, `FragmentProgram`
    and the scheduler's batch.  `base_key` is (plan key, table
    signature, baked values, traced types, masked literals' types); a
    program's key is `base_key + suffix + (factors,)`
    (plancache._census_classes reads it by position).  Spans, a tier's
    counters, and what a refusal or an error means stay the callers'."""

    __slots__ = ("ctx", "plan", "lits", "traced_names", "baked",
                 "base_key", "suffix", "batch", "lkey", "factors",
                 "_mkey")

    @classmethod
    def of(cls, ctx, plan, lits: list, stores: dict, suffix: tuple = (),
           batch: bool = False) -> Optional["_Prepared"]:
        """`plan` is the fragment as it will be traced (`lits` its
        masked literals, none where it runs baked), `ctx.params` are
        bound (`_bound_ctx`) and `stores` staged.  `suffix` is what the
        caller's programs add to the key between `base_key` and the
        factors: nothing, `("__morsel", chunk class)` or `("__batch",
        batch class)`; `batch` builds the program mapped over a batch
        axis.  None where the fragment cannot be keyed."""
        from .executor import split_params
        key = P.plan_key(plan, _KINDS)
        if key is None:
            return None
        self = cls()
        self.ctx, self.plan, self.lits = ctx, plan, lits
        self.suffix, self.batch = suffix, batch
        # numeric params ride as traced inputs beside the masked
        # literals (a re-planned scalar subquery value must not
        # recompile the fragment either); the rest is baked and keyed
        self.traced_names, self.baked = split_params(ctx.params)
        baked_key = tuple(sorted(
            (k, v) for k, (v, _t) in self.baked.items()
            if isinstance(v, (str, bool, type(None)))))
        if len(baked_key) != len(self.baked):
            return None  # non-scalar param: don't risk a stale closure
        self.base_key = (
            key, _table_sig(stores), baked_key,
            tuple((k, ctx.params[k][1]) for k in self.traced_names),
            tuple(t for _n, _v, t in lits))
        try:
            hash(self.base_key)
        except TypeError:
            return None  # unhashable plan content (e.g. an unrewritten link)
        self._mkey = self.lkey = self.factors = None
        return self

    def mask_key(self):
        """Codec-free fingerprint of `base_key`: the batching signature
        and the _MASK_REFUSED ledger must be STABLE across the staging
        boundary — codec classes are chosen at stage time, so a
        signature read at classification (before the table ever staged)
        would differ from the same fragment's post-stage signature,
        splitting quarantine accounting and coalescing groups in two.
        Mask refusal is a property of the plan structure + dtypes, not
        of the encodings, so stripping the codec component loses
        nothing.  The PROGRAM keys keep the full _table_sig: encodings
        change traced avals, and key and avals must agree."""
        if self._mkey is None:
            key, tsig, *rest = self.base_key
            self._mkey = struct_key(
                (key, tuple(e[:3] for e in tsig), *rest))
        return self._mkey

    def mask_refused(self) -> bool:
        """Did this shape's literal-masked trace host-sync before?  (A
        membership test of a dict is atomic: no lock on a read's path.)"""
        return self.mask_key() in _MASK_REFUSED

    def recall(self) -> None:
        """Start from the factors the last statement of this shape (any
        literal binding) ended on."""
        self.lkey = struct_key(self.base_key)
        (self.factors,) = _LADDER.recall(self.lkey) or ({},)

    def traced_params(self) -> list:
        """`(value, SqlType)` of what one call hands over, in traced
        order: numeric parameters, then masked literals."""
        return [self.ctx.params[k] for k in self.traced_names] \
            + [(v, t) for _n, v, t in self.lits]

    def program(self) -> tuple:
        """`(full_key, fn, meta, fresh)`: the program of the current
        factors, built (`fresh`) where the cache does not hold it; `fn`
        is None where the shape fell back for good."""
        full_key = self.base_key + self.suffix \
            + (tuple(sorted(self.factors.items())),)
        hit = plancache.FUSED.get(full_key)
        fresh = hit is None
        if fresh:
            hit = plancache.FUSED.put(
                full_key, _build_program(
                    self.ctx, self.plan, self.baked, self.traced_names,
                    self.lits, self.factors, batch=self.batch))
        return (full_key, *hit, fresh)

    def call(self, full_key, fn, args, tier: str):
        """One call of `fn`: its `(cols, valid, nulls, join_req)`.
        `_REFUSED` where a MASKED literal fed a host sync (value-
        dependent program structure): the shape is remembered, under
        the key `mask_refused` probes, and its program dropped; the
        caller retries baked (a batch, which cannot, is refused with
        or without a literal and goes serial).  None where there was no literal to
        blame (a host sync slipped through the fusability screen): the
        program's key keeps a `(None, None)` for good.  Any other error
        drops the program and is the caller's."""
        from .executor import TRACE_HOST_SYNC, stats_tier
        t0 = time.perf_counter()
        try:
            with stats_tier(tier):
                # trace-time executor counters attribute to the caller's
                # tier (re-executions don't re-trace)
                out = fn(*args)
        except TRACE_HOST_SYNC:
            if self.lits or self.batch:
                _mask_refused_add(self.mask_key())
                plancache.FUSED.pop(full_key)
                return _REFUSED
            plancache.FUSED.replace(full_key, (None, None))
            return None
        except Exception:
            plancache.FUSED.pop(full_key)
            raise
        plancache.FUSED.record_call(fn, t0)
        return out

    def settle(self, meta, join_req, note) -> Optional[bool]:  # otblint: sync-boundary
        """The size-class ladder's one read: the program reports each
        traced join's required output rows (and each laddered sorted
        aggregate's groups, executor._agg_class) and an overflow grows
        exactly that operator's factor: ONE host sync a program call,
        never one a join, told to `note` (`d2h`, `d2h_bytes`).  True:
        every class held; False: one grew, call again at the new
        factors; None: the ladder is exhausted.  What was learned
        persists per shape."""
        caps = meta.get("join_caps") or ()
        if not caps:
            return True
        req = np.asarray(jax.device_get(join_req))
        note(d2h=1, d2h_bytes=req.nbytes)
        if self.batch:
            # required totals arrive stacked (K, njoins): grow to the
            # most any batch element needs
            req = req.max(axis=0)
        grew = False
        for (jid, cap), r in zip(caps, req):
            if r > cap:
                if not plancache.Ladder.grow(self.factors, jid, cap, r):
                    return None
                grew = True
        _LADDER.remember(self.lkey, self.factors)
        return not grew


def try_fused(executor, node) -> Optional[object]:
    """Execute `node` as one jitted program, or None if unsupported."""
    return _try_fused(executor, node, allow_mask=True)


def _try_fused(executor, node, allow_mask: bool) -> Optional[object]:
    ctx = executor.ctx
    screened = _screen_fragment(ctx, node)
    if screened is None:
        return None
    stores, need_by_table = screened

    lits: list = []
    plan = _mask_node(node, lits) if allow_mask else node
    # `inputs`: the staged arrays with each table's row count; every
    # host scalar of the call rides its argument tree (`_call_args`):
    # nothing is put by itself
    with obs_trace.span("inputs"):
        staged_arrs, staged_ns = _stage(ctx.cache, stores, need_by_table)
    prep = _Prepared.of(_bound_ctx(ctx, plan), plan, lits, stores)
    if prep is None:
        return None
    if lits and prep.mask_refused():
        return _try_fused(executor, node, allow_mask=False)

    has_join = _plan_has_join(plan)
    if has_join and _below_join_floor(stores):
        return None

    prep.recall()
    args = _call_args(staged_arrs, staged_ns, ctx.snapshot_ts, ctx.txid,
                      prep.traced_params())
    from .executor import bump_stat

    for _attempt in range(plancache.Ladder.ATTEMPTS):
        full_key, fn, meta, fresh = prep.program()
        if has_join and not fresh and fn is not None:
            bump_stat("fused", "fused_join_hits")
        if fn is None:
            return None  # permanently fell back for this plan shape
        # the execute span covers the program call AND settle's
        # device_get of the overflow vector — that device read is the
        # tier's ONE legal sync boundary, so the span's wall time
        # includes device work
        with (obs_trace.span("execute", tier="fused")
              if obs_trace.ENABLED else obs_trace.NULL_SPAN) as sp:
            got = prep.call(full_key, fn, args, "fused")
            if got is _REFUSED:
                return _try_fused(executor, node, allow_mask=False)
            if got is None:
                return None
            cols, valid, nulls, join_req = got
            held = prep.settle(meta, join_req, sp.set)
            if held is None:
                return None  # ladder exhausted: eager fallback
            if not held:
                # this call's output overflowed a class: the statement
                # replays one class up (`retraces` of summary() sums
                # these)
                sp.set(retraces=1)
                continue
            # what the program holds, fixed when it was traced
            sp.set(**meta.get("shape", {}))
            if EXPORT_HOOK is not None:
                EXPORT_HOOK("fused", fn, args)
            out = _dbatch(meta, cols, valid, nulls)
        # `release`: the overflow vector, the one device buffer the
        # call itself made, is dropped here, not on the way out, so
        # that what freeing it costs has a name
        with obs_trace.span("release"):
            del join_req, got
        return out
    return None  # overflow never converged: eager fallback


def _build_program(ctx, frag_plan, baked, traced_names, lits, factors,
                   batch=False):
    """jit the fragment runner.  The program's leaf tables arrive as a
    dict-of-dicts of traced arrays; per-table live row counts are
    traced scalars (a write changes the count every time — a static
    count would recompile the fragment per insert-then-read cycle);
    only the padded shapes (size classes) retrace.

    With `batch=True` the returned program maps the SAME traced
    fragment over a leading batch axis of (snapshot, txid, literal)
    tuples via `jax.lax.map` — K same-signature queries become ONE
    compiled dispatch over shared staged tables, each batch element
    carrying its own MVCC snapshot and literal bindings (the serving
    tier's coalesced-dispatch path, exec/scheduler.py)."""
    from .executor import ExecContext, Executor

    meta: dict = {}
    traced_types = [ctx.params[k][1] for k in traced_names] \
        + [t for _n, _v, t in lits]
    all_traced = list(traced_names) + [nm for nm, _v, _t in lits]
    join_factors = dict(factors)

    # the programs' names are what the device trace's "XLA Modules" line
    # shows (jit_otb_fragment, jit_otb_fragment_batch; the mesh tier's
    # is jit_otb_mesh)
    def otb_fragment(arrs_in, snap, txid, pvals, ns_in):
        sub_params = dict(baked)
        for name, pv, t in zip(all_traced, pvals, traced_types):
            sub_params[name] = (pv, t)
        sub_ctx = ExecContext(
            ctx.stores, snap, txid, ctx.cache,
            params=sub_params,
            staged={t: (arrs_in[t], ns_in[t]) for t in arrs_in},
            join_factors=join_factors)
        sub = Executor(sub_ctx, frag_tag="__fused")
        sub._traced = True
        b = sub.exec_node(frag_plan)
        # the single deferred materialization pass: program outputs are
        # real columns (only what survived projection/agg)
        with jax.named_scope("otb.finalize"):
            b.ensure_all()
        meta["types"] = b.types
        meta["dicts"] = b.dicts
        meta["join_caps"] = tuple(
            (jid, cap) for jid, _req, cap in sub.join_required)
        meta["shape"] = dict(sub.shape)
        # join_required is a host-side Python list (one entry per join
        # in the fragment, fixed at trace time) — its truthiness is not
        # a device read
        join_req = jnp.stack(  # otblint: disable=host-sync
            [req for _jid, req, _cap in sub.join_required]) \
            if sub.join_required else jnp.zeros(0, jnp.int64)
        return b.cols, b.valid, b.nulls, join_req

    if not batch:
        return jax.jit(otb_fragment), meta

    def otb_fragment_batch(arrs_in, snaps, txids, pvals, ns_in):
        # lax.map traces the fragment body ONCE and scans it over the
        # batch axis — one executable, one dispatch, K queries; staged
        # tables are closed over (shared), snapshot/txid/literals are
        # the mapped leaves so every query keeps its own visibility
        return jax.lax.map(
            lambda q: otb_fragment(arrs_in, q[0], q[1], q[2], ns_in),
            (snaps, txids, tuple(pvals)))

    return jax.jit(otb_fragment_batch), meta


# ---------------------------------------------------------------------------
# Serving-tier batch entry points (exec/scheduler.py)

@dataclasses.dataclass
class FragSig:
    """One query's literal-masked fused-fragment signature plus the
    pieces a coalesced batch dispatch needs.  Two queries with equal
    `sig` run the same compiled program and differ only in their
    (snapshot, txid, literal-value) bindings — exactly the batching
    the serving tier exploits."""
    sig: object            # hashable canonical signature (_mask_key)
    plan: object           # literal-masked physical plan
    lits: list             # this query's [(name, value, type)] bindings
    stores: dict           # table name -> TableStore
    cache: object          # DeviceTableCache handle for staging
    need_by_table: dict    # table name -> needed column set

    def version_key(self) -> tuple:
        """Per-table store-version tuple over this fragment's scanned
        stores — the exact-invalidation component of a result-cache
        key (exec/share.py): any mutation of any referenced table
        bumps a version and the tuple stops matching."""
        from .share import store_versions
        return store_versions(self.stores)


def batch_signature(ctx, node) -> Optional[FragSig]:
    """Classify a plan subtree for same-program batching: the fragment
    signature the serial path would cache under, or None when the
    fragment can't ride the batched dispatch (not fusable, prepared
    params in play, mask previously refused, or a join below the fuse
    row floor).  Mirrors `_try_fused`'s screens so classification and
    execution agree."""
    if ctx.params:
        # init-plan / prepared params would need per-query host work
        # before the dispatch; keep those on the serial path
        return None
    screened = _screen_fragment(ctx, node)
    if screened is None:
        return None
    stores, need_by_table = screened

    lits: list = []
    masked = _mask_node(node, lits)
    prep = _Prepared.of(ctx, masked, lits, stores)
    if prep is None or prep.mask_refused():
        return None  # masked trace host-synced before: literals bake
    if _plan_has_join(masked) and _below_join_floor(stores):
        return None
    # the signature is the mask ledger's key: stable before and after
    # staging (codec-free)
    return FragSig(sig=prep.mask_key(), plan=masked, lits=lits,
                   stores=stores, cache=ctx.cache,
                   need_by_table=need_by_table)


# ---------------------------------------------------------------------------
# Morsel-tier fragment programs (exec/morsel.py)

class FragmentProgram:
    """One literal-masked compiled fragment, re-dispatched per streamed
    chunk (the morsel tier's unit of execution).

    The serial path's `_try_fused` screens, stages and runs in one
    shot; a morsel stream instead compiles ONCE and calls the program
    per chunk with the streamed table's staged window swapped in — the
    chunk's padded shape (`chunk_rows`, chunk_class-quantized) is part
    of the cache key (`("__morsel", class)`), the chunk COUNT and row
    offsets are not, so a thousand-chunk stream is one compile.  Mask
    fallback and the size-class ladder are the serial path's
    (`_Prepared`): a masked literal that host-syncs rebuilds baked, a
    join overflow re-runs the SAME chunk one factor class up."""

    def __init__(self, ctx, plan, chunk_rows: int):
        from ..storage.batch import chunk_class
        self.ctx = _bound_ctx(ctx, plan)
        self.plan = plan
        self._suffix = (("__morsel", chunk_class(int(chunk_rows))),)
        self._prep = self._prepare(allow_mask=True)

    def _prepare(self, allow_mask: bool) -> Optional[_Prepared]:
        lits: list = []
        plan = _mask_node(self.plan, lits) if allow_mask else self.plan
        stores = {nd.table.name: self.ctx.stores[nd.table.name]
                  for nd in P.walk(self.plan)
                  if isinstance(nd, P.SeqScan)}
        if _has_transformed_dup_dict(self.plan, stores):
            return None
        prep = _Prepared.of(self.ctx, plan, lits, stores, self._suffix)
        if prep is None:
            return None
        if lits and prep.mask_refused():
            return self._prepare(allow_mask=False)
        prep.recall()
        return prep

    def ok(self) -> bool:
        return self._prep is not None

    def run(self, staged_arrs: dict, staged_ns: dict, snapshot_ts, txid):
        """One chunk through the compiled fragment.  `staged_arrs` maps
        every leaf table to its traced arrays — the streamed table's
        window plus the resident (pinned) sides — and `staged_ns` to
        its live row count.  Returns a device DBatch, or None when the
        shape permanently refuses fusion (caller declines the stream);
        an error (OOM among them: the driver's downshift ladder wants
        it) is raised."""
        for _attempt in range(plancache.Ladder.ATTEMPTS):
            prep = self._prep
            full_key, fn, meta, _fresh = prep.program()
            if fn is None:
                return None  # permanently fell back for this shape
            # built per attempt: a refused mask re-prepares with its
            # literals baked, and the traced list shrinks with it
            args = _call_args(staged_arrs, staged_ns, snapshot_ts, txid,
                              prep.traced_params())
            got = prep.call(full_key, fn, args, "morsel")
            if got is _REFUSED:
                # rebuild baked, re-run this chunk
                self._prep = self._prepare(allow_mask=False)
                if self._prep is None:
                    return None
                continue
            if got is None:
                return None
            cols, valid, nulls, join_req = got
            held = prep.settle(meta, join_req, obs_trace.count)
            if held is None:
                return None  # ladder exhausted
            if held:
                return _dbatch(meta, cols, valid, nulls)
            # else the SAME chunk, one factor class up
        return None  # overflow never converged


class StagedBatch(NamedTuple):
    """A coalesced batch after the STAGE phase: keys and learned
    factors made, the call's arguments built (literal and MVCC columns
    as numpy vectors of the batch class), leaf tables resident on
    device — host work only, no program launched yet.  The pipelined
    scheduler stages batch i+1 while batch i computes."""
    prep: _Prepared
    k: int                 # live queries (the class pads the rest)
    args: tuple


class FusedFlight(NamedTuple):
    """One launched (asynchronously dispatched) coalesced batch.  The
    device arrays here are futures — JAX async dispatch returned before
    compute finished; `finish_fused_batch` performs the only host sync
    (the join-ladder check) and demuxes per-query views."""
    sb: StagedBatch
    meta: dict
    out: tuple             # the program's (cols, valid, nulls, join_req)


def stage_fused_batch(info: FragSig, queries: list) \
        -> Optional[StagedBatch]:
    """STAGE phase of a coalesced dispatch: upload every needed table
    through the device cache, recompute the dispatch-time key, and
    stack per-query MVCC/literal columns.  Returns None when the
    batched path refuses this group (mask-refused shape, empty batch)."""
    from ..storage.batch import next_pow2
    from .executor import ExecContext

    if not queries:
        return None
    staged_arrs, staged_ns = _stage(info.cache, info.stores,
                                    info.need_by_table)
    # the key is made at dispatch time: DML between classification and
    # dispatch can grow a TEXT dictionary, and the dictionaries are
    # baked trace constants — the key must match what the program will
    # actually bake (same property as the serial path)
    # the batch pads to a power of two: K concurrent arrivals hit a
    # bounded set of compiled batch classes
    k = len(queries)
    kclass = next_pow2(k, 1)
    prep = _Prepared.of(ExecContext(info.stores, 0, 0, info.cache),
                        info.plan, info.lits, info.stores,
                        (("__batch", kclass),), batch=True)
    if prep is None or prep.mask_refused():
        return None
    prep.recall()
    padded = list(queries) + [queries[-1]] * (kclass - k)
    return StagedBatch(prep, k, _call_args(
        staged_arrs, staged_ns, [q[0] for q in padded],
        [q[1] for q in padded],
        [([q[2][i] for q in padded], t)
         for i, (_n, _v, t) in enumerate(info.lits)]))


def launch_fused_batch(sb: StagedBatch) -> Optional[FusedFlight]:
    """LAUNCH phase: program lookup/compile + ONE asynchronous dispatch.
    No host sync happens here — the returned flight's arrays are device
    futures.  Returns None when the program declined this shape (a
    masked literal fed value-dependent program structure: it bakes its
    literals and is never batchable) or failed (caller falls back to
    serial, which reproduces and attributes the error per query).  A
    device allocation failure must REACH the scheduler: its pressure
    ladder (evict-coldest + retry, then degrade to spill) is the
    correct response — a serial fallback would just re-discover the
    same OOM K times."""
    full_key, fn, meta, _fresh = sb.prep.program()
    if fn is None:
        return None
    try:
        got = sb.prep.call(full_key, fn, sb.args, "fused")
    except Exception as e:
        from . import shield
        if shield.is_oom(e):
            raise
        return None
    if got is None or got is _REFUSED:
        return None
    return FusedFlight(sb, meta, got)


def finish_fused_batch(flight: FusedFlight) -> Optional[list]:
    """FINISH phase: the ONLY host sync of a coalesced dispatch — the
    join-ladder overflow check reads `join_req` back (which also
    surfaces any deferred device error from the async launch), growing
    factors and relaunching until the batch converges.  Returns the
    per-query DBatch device views, or None when the batched path gave
    up (caller falls back to serial)."""
    sb = flight.sb
    for attempt in range(plancache.Ladder.ATTEMPTS):
        if attempt:     # the last call overflowed: one class up
            flight = launch_fused_batch(sb)
            if flight is None:
                return None
        cols, valid, nulls, join_req = flight.out
        held = sb.prep.settle(flight.meta, join_req, obs_trace.count)
        if held:
            # demux: per-query device views into the stacked output
            # (the padded tail, if any, is discarded)
            return [_dbatch(flight.meta,
                            {n: a[i] for n, a in cols.items()}, valid[i],
                            {n: a[i] for n, a in nulls.items()})
                    for i in range(sb.k)]
        if held is None:
            return None
    return None  # overflow never converged


def run_fused_batch(info: FragSig, queries: list) -> Optional[list]:
    """Run K same-signature queries as ONE compiled dispatch: the
    synchronous composition of the three phases (the pipelined
    scheduler calls them separately so the finish-phase host sync lands
    on its drainer thread instead of the dispatch loop).

    `queries` is [(snapshot_ts, txid, [literal values])] — one entry
    per query, literal order matching `info.lits`.  Returns a list of
    per-query DBatch results (device views into the stacked program
    output — materialization happens on the caller's thread), or None
    when the batched path can't serve this group (caller falls back to
    serial execution)."""
    sb = stage_fused_batch(info, queries)
    if sb is None:
        return None
    flight = launch_fused_batch(sb)
    if flight is None:
        return None
    return finish_fused_batch(flight)
