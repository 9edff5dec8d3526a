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
overflow — the learned factors persist in _JOIN_LADDER keyed by the
literal-masked fragment shape, so steady state is one program call with
ZERO per-join device→host syncs (the eager path pays one `int(total)`
sync per join per query).  A sorted aggregate whose keys' ranges do not
bound its groups rides the same ladder under an id of the same sequence
(executor._agg_class): a quarter of its input's rows, its groups
reported beside the joins' totals.

Compiled programs live in the shared program cache (exec/plancache.py
FUSED tier) under a CANONICAL FRAGMENT SIGNATURE: numeric/date literals
in scan filters and quals are masked out of the plan and ride as traced
program inputs instead, as does the dictionary code of a text parameter
compared with a column (`_bound_ctx`), so `WHERE l_shipdate <= X` with a
different constant reuses the compiled executable (the reference's
generic-plan arm, taken further: the plan cache there saves planning,
this saves the XLA compile).  Multi-table fragments key per-table components (store
identity + TEXT dictionary lengths — dictionaries are trace constants).
jax re-traces per array shape automatically — the pow2/quarter-step
size classes bound that — and the cache's global live-executable budget
evicts LRU programs deterministically.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Optional

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

# one lock for this module's learned-state dicts: CN-server threads
# share them, and the add-then-evict sequences below must be atomic
_STATE_LOCK = locks.Lock("exec.fused._STATE_LOCK")

# plan shapes whose literal-masked trace host-synced (a masked value
# fed a host branch): retried and cached baked instead.  Bounded FIFO
# (insertion-ordered dict): the oldest learned fallback is evicted one
# at a time — a wholesale clear() would drop every learned entry at
# once and force a burst of doomed literal-masked retraces.
_MASK_REFUSED: dict = {}    # guarded_by: _STATE_LOCK
_MASK_REFUSED_MAX = 512

# learned size-class ladder: literal-masked fragment shape -> {id of a
# traced join or laddered sorted aggregate: factor} — the single-device
# twin of MeshRunner._ladder, so a fragment's second statement (any
# literal binding) starts at the right output class instead of
# replaying the overflow walk
_JOIN_LADDER: dict = {}     # guarded_by: _STATE_LOCK
_JOIN_LADDER_MAX = 512

# Observability hook: when set, called as EXPORT_HOOK(tag, fn, args)
# after each successful fused execution — the TPU lowering proof
# (utils/lowering_check.py) uses it to AOT-export the very programs the
# engine ran.
EXPORT_HOOK = None


def _fuse_join_min_rows() -> int:
    """Row floor (summed across the fragment's leaf tables) below which
    join subtrees stay on the eager path — read per call so tests and
    operators can flip it live."""
    try:
        return int(os.environ.get("OTB_FUSE_JOIN_MIN_ROWS", "8192"))
    except ValueError:
        return 8192


def _mask_refused_add(k):
    with _STATE_LOCK:
        _MASK_REFUSED[k] = True
        while len(_MASK_REFUSED) > _MASK_REFUSED_MAX:
            _MASK_REFUSED.pop(next(iter(_MASK_REFUSED)))


def _mask_key(base_key):
    """Codec-free fingerprint of a fragment key.  The batching
    signature and the _MASK_REFUSED ledger must be STABLE across the
    staging boundary — codec classes are chosen at stage time, so a
    signature read at classification (before the table ever staged)
    would differ from the same fragment's post-stage signature,
    splitting quarantine accounting and coalescing groups in two.
    Mask refusal is a property of the plan structure + dtypes, not of
    the encodings, so stripping the codec component loses nothing.
    The PROGRAM keys keep the full _table_sig: encodings change traced
    avals, and key and avals must agree."""
    plan_key, tsig, baked_key, types_key, lit_types = base_key
    return struct_key((plan_key, tuple(e[:3] for e in tsig),
                       baked_key, types_key, lit_types))


def _key_of_expr(e) -> tuple:
    return e  # Expr dataclasses are frozen/hashable


def _key_of(node) -> Optional[tuple]:
    """Structural key for a physical subtree (None = unsupported)."""
    t = type(node).__name__
    if isinstance(node, P.SeqScan):
        return (t, node.table.name, node.alias,
                tuple(node.filters), tuple(node.outputs or ()))
    if isinstance(node, P.Filter):
        c = _key_of(node.child)
        return None if c is None else (t, tuple(node.quals), c)
    if isinstance(node, P.Project):
        c = _key_of(node.child)
        return None if c is None else (t, tuple(node.outputs), c)
    if isinstance(node, P.Agg):
        c = _key_of(node.child)
        return None if c is None else (
            t, node.mode, tuple(node.group_keys), tuple(node.aggs), c)
    if isinstance(node, P.Sort):
        c = _key_of(node.child)
        return None if c is None else (
            t, tuple((k, bool(d)) for k, d in node.keys), node.limit, c)
    if isinstance(node, P.Limit):
        c = _key_of(node.child)
        return None if c is None else (t, node.count, node.offset, c)
    if isinstance(node, P.HashJoin):
        lk, rk = _key_of(node.left), _key_of(node.right)
        if lk is None or rk is None:
            return None
        return (t, node.kind, tuple(node.left_keys),
                tuple(node.right_keys), tuple(node.residual or ()),
                lk, rk)
    return None


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
    if isinstance(node, P.HashJoin):
        return True
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if isinstance(c, P.PhysNode) and _plan_has_join(c):
            return True
    return False


def _has_transformed_dup_dict(node, store) -> bool:
    """True when a group key is a TextExpr whose transformed dictionary
    maps several codes to one string — key canonicalization builds a
    host LUT per batch (executor._eval_group_keys), which is fine eager
    but not worth special-casing under the trace: fall back."""
    for x in P.walk_exprs(node):
        if isinstance(x, E.TextExpr):
            base = store.dicts.get(x.col.name.split(".", 1)[-1])
            if base is not None:
                vals = [x.apply(v) for v in base.values]
                if len(set(vals)) < len(vals):
                    return True
    return False


def _needed_columns(node, alias: str) -> set[str]:
    need = set()
    for x in P.walk_exprs(node):
        if isinstance(x, E.Col) and x.name.startswith(alias + "."):
            need.add(x.name.split(".", 1)[1])
    return need


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
    """Shared fusability screen: `(scans, stores)` when `node` is a
    traceable fragment over live SeqScan leaves, else None.  Used by
    the serial path (`_try_fused`) and the serving tier's batch
    classification (`batch_signature`) so both agree on what can run
    as one program."""
    if not isinstance(node, (P.Agg, P.Project, P.Filter, P.Sort,
                             P.Limit, P.HashJoin)):
        return None   # bare SeqScan gains nothing
    scans = _find_scans(node)
    if not scans:
        return None
    stores: dict = {}
    for scan in scans:
        store = ctx.stores.get(scan.table.name)
        if store is None or \
                (ctx.staged and scan.table.name in ctx.staged):
            return None
        stores[scan.table.name] = store
    if _key_of(node) is None:
        return None
    for store in stores.values():
        if _has_transformed_dup_dict(node, store):
            return None
    return scans, stores


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


def try_fused(executor, node) -> Optional[object]:
    """Execute `node` as one jitted program, or None if unsupported."""
    return _try_fused(executor, node, allow_mask=True)


def _try_fused(executor, node, allow_mask: bool) -> Optional[object]:  # otblint: sync-boundary
    ctx = executor.ctx
    screened = _screen_fragment(ctx, node)
    if screened is None:
        return None
    scans, stores = screened

    # canonical fragment signature: literal-masked plan + per-table
    # components (store identity + dictionary lengths — dictionaries
    # are baked trace constants) + dtypes; the masked literals ride as
    # traced inputs alongside numeric init-plan params (re-planned
    # scalar subquery values must not recompile the fragment either);
    # everything else (strings, NULLs — they change program structure)
    # is baked and keyed
    lits: list = []
    exec_node_plan = _mask_node(node, lits) if allow_mask else node
    key = _key_of(exec_node_plan)
    if key is None:
        return None

    # stage ONCE outside the trace (device cache, version-keyed) and
    # BEFORE computing the key: staging chooses/validates the codec
    # descriptors whose quantized classes are part of _table_sig — a
    # cold start must mint the same key the warm repeat will see, or
    # the census sanitizer would count a phantom recompile.  A
    # self-join's scans share one staged entry per table with the
    # union of their needed columns.
    need_by_table: dict = {}
    for scan in scans:
        need_by_table.setdefault(scan.table.name, set()).update(
            _needed_columns(node, scan.alias))
    staged_arrs: dict = {}
    staged_ns: dict = {}
    # `inputs`: the staged arrays (a pool lookup; a miss stages under
    # it) with each table's row count; every host scalar of the call
    # rides its argument tree (`_call_args`): nothing is put by itself
    with obs_trace.span("inputs"):
        for t, need in sorted(need_by_table.items()):
            staged_arrs[t], staged_ns[t] = ctx.cache.get(
                stores[t], sorted(need))

    table_sig = _table_sig(stores)
    ctx = _bound_ctx(ctx, exec_node_plan)
    traced_names = tuple(sorted(
        k for k, (v, _t) in ctx.params.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)))
    baked = {k: ctx.params[k] for k in ctx.params
             if k not in traced_names}
    baked_key = tuple(sorted(
        (k, v) for k, (v, _t) in baked.items()
        if isinstance(v, (str, bool, type(None)))))
    if len(baked_key) != len(baked):
        return None  # non-scalar param: don't risk a stale closure
    types_key = tuple((k, ctx.params[k][1]) for k in traced_names)
    lit_types = tuple(t for _n, _v, t in lits)
    base_key = (key, table_sig, baked_key, types_key, lit_types)
    try:
        hash(base_key)
    except TypeError:
        return None  # unhashable plan content (e.g. an unrewritten link)
    if lits and _mask_key(base_key) in _MASK_REFUSED:
        return _try_fused(executor, node, allow_mask=False)

    has_join = _plan_has_join(exec_node_plan)
    if has_join and sum(
            st.row_count() for st in stores.values()) \
            < _fuse_join_min_rows():
        # tiny join fragments: the eager path's per-join host sync
        # costs microseconds while a fresh XLA compile costs seconds —
        # fusing only pays above a row floor (0 = always fuse)
        return None

    lkey = struct_key(base_key)
    factors: dict = dict(_JOIN_LADDER.get(lkey, {}))

    args = _call_args(
        staged_arrs, staged_ns, ctx.snapshot_ts, ctx.txid,
        [ctx.params[k] for k in traced_names]
        + [(v, t) for _n, v, t in lits])
    from .executor import bump_stat, stats_tier

    for _attempt in range(24):
        full_key = base_key + (tuple(sorted(factors.items())),)
        hit = plancache.FUSED.get(full_key)
        if hit is None:
            hit = plancache.FUSED.put(
                full_key, _build_program(ctx, exec_node_plan, baked,
                                         traced_names, lits, factors))
        elif has_join and hit[0] is not None:
            bump_stat("fused", "fused_join_hits")
        fn, meta = hit
        if fn is None:
            return None  # permanently fell back for this plan shape
        t0 = time.perf_counter()
        # the execute span covers the program call AND the join-overflow
        # device_get below — that device read is the tier's ONE legal
        # sync boundary, so the span's wall time includes device work
        with (obs_trace.span("execute", tier="fused")
              if obs_trace.ENABLED else obs_trace.NULL_SPAN) as sp:
            try:
                with stats_tier("fused"):
                    # trace-time executor counters attribute to the
                    # fused tier (re-executions don't re-trace)
                    cols, valid, nulls, join_req = fn(*args)
            except (jax.errors.TracerBoolConversionError,
                    jax.errors.ConcretizationTypeError,
                    jax.errors.TracerArrayConversionError):
                if lits:
                    # a MASKED literal fed a host-sync (value-dependent
                    # program structure): remember and retry with
                    # literals baked
                    _mask_refused_add(_mask_key(base_key))
                    plancache.FUSED.pop(full_key)
                    return _try_fused(executor, node, allow_mask=False)
                # a host-sync slipped through the fusability screen:
                # permanently fall back for this plan shape
                plancache.FUSED.replace(full_key, (None, None))
                return None
            except Exception:
                plancache.FUSED.pop(full_key)
                raise
            plancache.FUSED.record_call(fn, t0)

            # size-class ladder: the program reports each traced join's
            # required output rows (and each laddered sorted aggregate's
            # groups, executor._agg_class); overflow grows exactly that
            # operator's factor and retraces (one host sync per program
            # call — never per join).  Learned factors persist per shape.
            caps = meta.get("join_caps") or ()
            if caps:
                req = np.asarray(jax.device_get(join_req))
                sp.set(d2h=1, d2h_bytes=req.nbytes)
                grew = False
                for (jid, cap), r in zip(caps, req):
                    if r <= cap:
                        continue
                    # the program reports the EXACT required rows
                    # (unlike the mesh tier's overflow bit): jump the
                    # factor straight to the class that fits — ONE
                    # retrace, not a doubling walk of compiles
                    mult = 1
                    while cap * mult < r:
                        mult *= 2
                    factors[jid] = factors.get(jid, 1) * mult
                    if factors[jid] > 4096:
                        return None  # ladder exhausted: eager fallback
                    grew = True
                if grew:
                    _ladder_remember(lkey, factors)
                    # this call's output overflowed a join class: the
                    # statement replays one class up (`retraces` of
                    # summary() sums these)
                    sp.set(retraces=1)
                    continue
            if caps:
                _ladder_remember(lkey, factors)
            # what the program holds, fixed when it was traced
            sp.set(**meta.get("shape", {}))
            if EXPORT_HOOK is not None:
                EXPORT_HOOK("fused", fn, args)
            from .executor import DBatch
            out = DBatch(dict(cols), valid, dict(meta["types"]),
                         dict(meta["dicts"]), dict(nulls))
        # `release`: the overflow vector, the one device buffer the
        # call itself made, is dropped here, not on the way out, so
        # that what freeing it costs has a name
        with obs_trace.span("release"):
            del join_req
        return out
    return None  # overflow never converged: eager fallback


def _ladder_remember(lkey, factors: dict):
    with _STATE_LOCK:
        _JOIN_LADDER[lkey] = dict(factors)
        while len(_JOIN_LADDER) > _JOIN_LADDER_MAX:
            _JOIN_LADDER.pop(next(iter(_JOIN_LADDER)))


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
    sig: object            # hashable canonical signature (struct_key)
    plan: object           # literal-masked physical plan
    lits: list             # this query's [(name, value, type)] bindings
    stores: dict           # table name -> TableStore
    cache: object          # DeviceTableCache handle for staging
    need_by_table: dict    # table name -> needed column set
    plan_key: tuple        # _key_of(masked plan)
    lit_types: tuple

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
    scans, stores = screened

    lits: list = []
    masked = _mask_node(node, lits)
    plan_key = _key_of(masked)
    if plan_key is None:
        return None
    lit_types = tuple(t for _n, _v, t in lits)
    base_key = (plan_key, _table_sig(stores), (), (), lit_types)
    try:
        hash(base_key)
    except TypeError:
        return None
    sig = _mask_key(base_key)   # stable pre/post staging (codec-free)
    with _STATE_LOCK:
        refused = sig in _MASK_REFUSED
    if refused:
        return None  # masked trace host-synced before: literals bake

    if _plan_has_join(masked) \
            and sum(st.row_count() for st in stores.values()) \
            < _fuse_join_min_rows():
        return None

    need_by_table: dict = {}
    for scan in scans:
        need_by_table.setdefault(scan.table.name, set()).update(
            _needed_columns(node, scan.alias))
    return FragSig(sig=sig, plan=masked, lits=lits,
                   stores=stores, cache=ctx.cache,
                   need_by_table=need_by_table,
                   plan_key=plan_key, lit_types=lit_types)


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
    fallback and the learned join-size ladder work exactly as on the
    serial path: a masked literal that host-syncs rebuilds baked, a
    join overflow re-runs the SAME chunk one factor class up."""

    def __init__(self, ctx, plan, chunk_rows: int):
        from ..storage.batch import chunk_class
        self.ctx = _bound_ctx(ctx, plan)
        self.plan = plan
        self.chunk_rows = int(chunk_rows)
        self._chunk_key = ("__morsel", chunk_class(int(chunk_rows)))
        self._ok = self._prepare(allow_mask=True)

    def _prepare(self, allow_mask: bool) -> bool:
        ctx = self.ctx
        lits: list = []
        exec_plan = _mask_node(self.plan, lits) if allow_mask \
            else self.plan
        key = _key_of(exec_plan)
        if key is None:
            return False
        stores = {nd.table.name: ctx.stores[nd.table.name]
                  for nd in _morsel_walk(self.plan)
                  if isinstance(nd, P.SeqScan)}
        for store in stores.values():
            if _has_transformed_dup_dict(self.plan, store):
                return False
        self.traced_names = tuple(sorted(
            k for k, (v, _t) in ctx.params.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)))
        baked = {k: ctx.params[k] for k in ctx.params
                 if k not in self.traced_names}
        baked_key = tuple(sorted(
            (k, v) for k, (v, _t) in baked.items()
            if isinstance(v, (str, bool, type(None)))))
        if len(baked_key) != len(baked):
            return False  # non-scalar param: don't risk a stale closure
        types_key = tuple((k, ctx.params[k][1])
                          for k in self.traced_names)
        lit_types = tuple(t for _n, _v, t in lits)
        base_key = (key, _table_sig(stores), baked_key, types_key,
                    lit_types)
        try:
            hash(base_key)
        except TypeError:
            return False
        if lits and _mask_key(base_key) in _MASK_REFUSED:
            return self._prepare(allow_mask=False)
        self.exec_plan = exec_plan
        self.lits = lits
        self.baked = baked
        self.base_key = base_key
        self.lkey = struct_key(base_key)
        with _STATE_LOCK:
            self.factors = dict(_JOIN_LADDER.get(self.lkey, {}))
        return True

    def ok(self) -> bool:
        return self._ok

    def run(self, staged_arrs: dict, staged_ns: dict, snapshot_ts,
            txid):  # otblint: sync-boundary
        """One chunk through the compiled fragment.  `staged_arrs` maps
        every leaf table to its traced arrays — the streamed table's
        window plus the resident (pinned) sides — and `staged_ns` to
        its live row count.  Returns a device DBatch, or None when the
        shape permanently refuses fusion (caller declines the stream)."""
        from .executor import DBatch, stats_tier
        ctx = self.ctx
        for _attempt in range(24):
            full_key = self.base_key + (
                self._chunk_key, tuple(sorted(self.factors.items())))
            hit = plancache.FUSED.get(full_key)
            if hit is None:
                hit = plancache.FUSED.put(
                    full_key, _build_program(
                        ctx, self.exec_plan, self.baked,
                        self.traced_names, self.lits, self.factors))
            fn, meta = hit
            if fn is None:
                return None  # permanently fell back for this shape
            # built per attempt: a refused mask re-prepares with its
            # literals baked, and the traced list shrinks with it
            args = _call_args(
                staged_arrs, staged_ns, snapshot_ts, txid,
                [ctx.params[k] for k in self.traced_names]
                + [(v, t) for _n, v, t in self.lits])
            t0 = time.perf_counter()
            try:
                with stats_tier("morsel"):
                    cols, valid, nulls, join_req = fn(*args)
            except (jax.errors.TracerBoolConversionError,
                    jax.errors.ConcretizationTypeError,
                    jax.errors.TracerArrayConversionError):
                plancache.FUSED.pop(full_key)
                if self.lits:
                    # a masked literal fed value-dependent structure:
                    # remember, rebuild baked, re-run this chunk
                    _mask_refused_add(struct_key(self.base_key))
                    if self._prepare(allow_mask=False):
                        continue
                    return None
                plancache.FUSED.replace(full_key, (None, None))
                return None
            except Exception:
                plancache.FUSED.pop(full_key)
                raise  # OOM must reach the driver's downshift ladder
            plancache.FUSED.record_call(fn, t0)

            caps = meta.get("join_caps") or ()
            if caps:
                req = np.asarray(jax.device_get(join_req))
                obs_trace.count(d2h=1, d2h_bytes=req.nbytes)
                grew = False
                for (jid, cap), r in zip(caps, req):
                    if r <= cap:
                        continue
                    mult = 1
                    while cap * mult < r:
                        mult *= 2
                    self.factors[jid] = self.factors.get(jid, 1) * mult
                    if self.factors[jid] > 4096:
                        return None  # ladder exhausted
                    grew = True
                if grew:
                    _ladder_remember(self.lkey, self.factors)
                    continue  # SAME chunk, one factor class up
            if caps:
                _ladder_remember(self.lkey, self.factors)
            return DBatch(dict(cols), valid, dict(meta["types"]),
                          dict(meta["dicts"]), dict(nulls))
        return None  # overflow never converged


def _morsel_walk(node):
    yield node
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if isinstance(c, P.PhysNode):
            yield from _morsel_walk(c)


def _batch_class(k: int) -> int:
    """Pad batch size to a power of two so K concurrent arrivals hit a
    bounded set of compiled batch classes."""
    c = 1
    while c < k:
        c *= 2
    return c


class StagedBatch:
    """A coalesced batch after the STAGE phase: keys computed, the
    call's arguments built (`_call_args`: literal and MVCC columns as
    numpy vectors of K), leaf tables resident on device — host work
    only, no program launched yet.  The pipelined scheduler stages batch
    i+1 while batch i computes; `launch_fused_batch` turns one of these
    into an in-flight dispatch."""

    __slots__ = ("info", "k", "kclass", "base_key", "lkey", "args",
                 "bctx", "factors")


class FusedFlight:
    """One launched (asynchronously dispatched) coalesced batch.  The
    device arrays here are futures — JAX async dispatch returned before
    compute finished; `finish_fused_batch` performs the only host sync
    (the join-ladder check) and demuxes per-query views."""

    __slots__ = ("sb", "fn", "meta", "cols", "valid", "nulls",
                 "join_req", "attempt")


def stage_fused_batch(info: FragSig, queries: list) \
        -> Optional[StagedBatch]:
    """STAGE phase of a coalesced dispatch: recompute the dispatch-time
    key, stack per-query MVCC/literal columns, and upload every needed
    table through the device cache.  Returns None when the batched path
    refuses this group (mask-refused shape, empty batch)."""
    from .executor import ExecContext

    if not queries:
        return None
    # stage ONCE for the whole batch (device cache, version-keyed) —
    # BEFORE the key: staging chooses/validates the codec descriptors
    # whose quantized classes ride _table_sig (serial-path property)
    staged_arrs: dict = {}
    staged_ns: dict = {}
    for t, need in sorted(info.need_by_table.items()):
        staged_arrs[t], staged_ns[t] = info.cache.get(
            info.stores[t], sorted(need))

    # recompute the table signature at dispatch time: DML between
    # classification and dispatch can grow a TEXT dictionary, and the
    # dictionaries are baked trace constants — the key must match what
    # the program will actually bake (same property as the serial path)
    base_key = (info.plan_key, _table_sig(info.stores), (), (),
                info.lit_types)
    with _STATE_LOCK:
        refused = _mask_key(base_key) in _MASK_REFUSED
    if refused:
        return None

    sb = StagedBatch()
    sb.info = info
    sb.base_key = base_key
    sb.lkey = struct_key(base_key)
    sb.k = len(queries)
    sb.kclass = _batch_class(sb.k)
    padded = list(queries) + [queries[-1]] * (sb.kclass - sb.k)
    sb.args = _call_args(
        staged_arrs, staged_ns, [q[0] for q in padded],
        [q[1] for q in padded],
        [([q[2][i] for q in padded], t)
         for i, t in enumerate(info.lit_types)])

    with _STATE_LOCK:
        sb.factors = dict(_JOIN_LADDER.get(sb.lkey, {}))
    sb.bctx = ExecContext(info.stores, 0, 0, info.cache)
    return sb


def launch_fused_batch(sb: StagedBatch, attempt: int = 0) \
        -> Optional[FusedFlight]:
    """LAUNCH phase: program lookup/compile + ONE asynchronous dispatch.
    No host sync happens here — the returned flight's arrays are device
    futures.  Returns None when the program permanently declined this
    shape (caller falls back to serial); re-raises device OOM so the
    scheduler's pressure ladder can respond."""
    from .executor import stats_tier

    full_key = sb.base_key + (("__batch", sb.kclass),
                              tuple(sorted(sb.factors.items())))
    hit = plancache.FUSED.get(full_key)
    if hit is None:
        hit = plancache.FUSED.put(
            full_key, _build_program(sb.bctx, sb.info.plan, {}, (),
                                     sb.info.lits, sb.factors,
                                     batch=True))
    fn, meta = hit
    if fn is None:
        return None
    t0 = time.perf_counter()
    try:
        with stats_tier("fused"):
            cols, valid, nulls, join_req = fn(*sb.args)
    except (jax.errors.TracerBoolConversionError,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        # a masked literal fed value-dependent program structure:
        # this shape bakes its literals — never batchable
        _mask_refused_add(struct_key(sb.base_key))
        plancache.FUSED.pop(full_key)
        return None
    except Exception as e:
        from . import shield
        if shield.is_oom(e):
            # device allocation failure must REACH the scheduler:
            # its pressure ladder (evict-coldest + retry, then
            # degrade to spill) is the correct response — a serial
            # fallback would just re-discover the same OOM K times
            plancache.FUSED.pop(full_key)
            raise
        # fall back to serial execution, which reproduces (and
        # attributes) the error per query
        plancache.FUSED.pop(full_key)
        return None
    plancache.FUSED.record_call(fn, t0)

    fl = FusedFlight()
    fl.sb = sb
    fl.fn, fl.meta = fn, meta
    fl.cols, fl.valid, fl.nulls = cols, valid, nulls
    fl.join_req = join_req
    fl.attempt = attempt
    return fl


def finish_fused_batch(flight: FusedFlight) -> Optional[list]:  # otblint: sync-boundary
    """FINISH phase: the ONLY host sync of a coalesced dispatch — the
    join-ladder overflow check reads `join_req` back (which also
    surfaces any deferred device error from the async launch), growing
    factors and relaunching until the batch converges.  Returns the
    per-query DBatch device views, or None when the batched path gave
    up (caller falls back to serial)."""
    from .executor import DBatch

    while True:
        sb = flight.sb
        caps = flight.meta.get("join_caps") or ()
        if caps:
            # per-join required totals arrive stacked (K, njoins):
            # grow to the max any batch element needs
            req = np.asarray(jax.device_get(flight.join_req))
            obs_trace.count(d2h=1, d2h_bytes=req.nbytes)
            req = req.max(axis=0)
            grew = False
            for (jid, cap), r in zip(caps, req):
                if r <= cap:
                    continue
                mult = 1
                while cap * mult < r:
                    mult *= 2
                sb.factors[jid] = sb.factors.get(jid, 1) * mult
                if sb.factors[jid] > 4096:
                    return None
                grew = True
            if grew:
                _ladder_remember(sb.lkey, sb.factors)
                if flight.attempt + 1 >= 24:
                    return None  # overflow never converged
                flight = launch_fused_batch(sb, attempt=flight.attempt + 1)
                if flight is None:
                    return None
                continue
        if caps:
            _ladder_remember(sb.lkey, sb.factors)

        # demux: per-query device views into the stacked output (the
        # padded tail, if any, is discarded)
        out = []
        for i in range(sb.k):
            out.append(DBatch(
                {n: a[i] for n, a in flight.cols.items()},
                flight.valid[i],
                dict(flight.meta["types"]), dict(flight.meta["dicts"]),
                {n: a[i] for n, a in flight.nulls.items()}))
        return out


def run_fused_batch(info: FragSig, queries: list) -> Optional[list]:  # otblint: sync-boundary
    """Run K same-signature queries as ONE compiled dispatch.

    `queries` is [(snapshot_ts, txid, [literal values])] — one entry
    per query, literal order matching `info.lits`.  Returns a list of
    per-query DBatch results (device views into the stacked program
    output — materialization happens on the caller's thread, which is
    what lets the scheduler overlap the next batch's staging with this
    batch's device compute), or None when the batched path can't serve
    this group (caller falls back to serial execution).

    This is the synchronous composition of the three pipeline phases
    (stage → launch → finish); the pipelined scheduler calls them
    separately so the finish-phase host sync lands on its drainer
    thread instead of the dispatch loop."""
    sb = stage_fused_batch(info, queries)
    if sb is None:
        return None
    flight = launch_fused_batch(sb)
    if flight is None:
        return None
    return finish_fused_batch(flight)
