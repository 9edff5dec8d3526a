"""Auto-prepare: raw-literal statements ride the prepared-plan path.

Reference analog: the reference answers UNPREPARED single-shard reads in
sub-ms because FQS ships the SQL text without a full plan cycle
(pgxc/plan/planner.c:390, execLight.c:34).  Here the equivalent is the
prepared-statement machinery (bound once with $n parameter columns, FQS
param router, traced-parameter XLA programs) — so the ad-hoc path
auto-parameterizes: WHERE-clause literals are lifted into Params, the
resulting TEMPLATE keys a cluster-wide cache of Prepared objects, and
every statement that differs only in those literal values reuses the
same plan, router, and compiled program.

What is lifted (each with the parameter type the binder's literal typing
gives it, so param semantics == literal semantics):
- int -> INT64, non-exponent numerics -> DECIMAL(30, frac), exponent
  numerics -> FLOAT64, `date '...'` -> DATE;
- a date-valued constant expression, `date '...' +/- interval 'n'
  day|month|year` (nested too), as ONE DATE parameter: the session
  evaluates it on the host (catalog.types.add_interval), so TPC-H's
  `date '1998-12-01' - interval '90' day` is one template for every
  DELTA;
- a string compared by `=` or `<>` with a column reference -> TEXT: the
  binder ties it to that column (StrPred.param), a compiled tier binds
  it to the column's dictionary code, a traced scalar of its programs
  (executor.bind_text_params), and Executor._prep reads it.  Where the
  binder finds no dictionary-coded base column behind the reference (a
  view, a CTE, a derived table, a non-text column), the session takes
  the template with every string baked instead (`text=False`), so the
  other literals stay lifted.

What stays baked into the template (`count_literals` of the template's
WHERE counts them; `params_baked` of the statement's stats): strings in
IN-lists, LIKE patterns and range comparisons (a code SET, not a code),
bools and NULLs (3VL changes program structure), anything under a nested
query, and every literal outside the top-level WHERE.  The template
fingerprint stays distinct per baked value, so correctness never depends
on the lift being complete.  Templates that fail to bind with abstract
params fall back to the normal plan path (and are remembered, so the
failed bind is paid once per template).
"""

from __future__ import annotations

import dataclasses

from ..catalog import types as T
from ..sql import ast as A


def _liftable_type(node):
    """SqlType a lifted literal should declare, or None to keep baked.
    Must mirror Binder._bind_const so param semantics == literal
    semantics."""
    if isinstance(node, A.Const):
        if node.kind == "int":
            return T.INT64
        if node.kind == "num":
            s = str(node.value)
            if "e" in s.lower():
                return T.FLOAT64
            frac = len(s.split(".")[1]) if "." in s else 0
            return T.decimal(30, frac)
        return None
    if isinstance(node, A.TypedConst) and node.type_name == "date":
        return T.DATE
    if isinstance(node, A.BinOp) and node.op in ("+", "-") \
            and isinstance(node.right, A.TypedConst) \
            and node.right.type_name == "interval" \
            and node.right.unit in ("day", "month", "year"):
        # date +/- constant interval: the whole expression is one DATE
        inner = _liftable_type(node.left)
        return inner if inner is not None \
            and inner.kind == T.TypeKind.DATE else None
    if isinstance(node, A.UnaryOp) and node.op == "-":
        inner = _liftable_type(node.arg)
        # negation is handled by _bind_arg; only numeric kinds
        if inner is not None and inner.kind != T.TypeKind.DATE:
            return inner
        return None
    return None


def _is_str(node) -> bool:
    return isinstance(node, A.Const) and node.kind == "str"


# node types whose subtrees keep literals baked: nested queries replan
# with their own cache entries; IN-lists need literal values at bind
# time (code-set membership); LIMIT/OFFSET are plan structure.
_OPAQUE = (A.SelectStmt, A.InExpr, A.ScalarSubquery, A.ExistsExpr,
           A.QuantifiedCmp, A.SubqueryRef)


def parameterize(stmt: A.SelectStmt, text: bool = True):
    """Lift WHERE literals of the top-level query into Params.
    Returns (template_stmt, arg_nodes, param_types) or None when
    nothing lifted.  `text=False` leaves every string baked: the
    template the session falls back to when the binder finds a lifted
    string that no dictionary-coded base column takes (a view's or a
    CTE's column, a number compared with a quoted value), so that the
    statement's other literals stay parameters."""
    if stmt.where is None:
        return None
    args: list = []
    types: dict = {}

    def param(node, t):
        args.append(node)
        types[len(args)] = t
        return A.Param(len(args))

    def lift(node):
        if isinstance(node, _OPAQUE):
            return node
        t = _liftable_type(node)
        if t is not None:
            return param(node, t)
        if text and isinstance(node, A.BinOp) and node.op in ("=", "<>"):
            # a string against a column reference: the binder decides
            # whether the column takes a code (a dictionary-coded base
            # column) or this template is not for the prepared path
            l, r = node.left, node.right
            if _is_str(r) and isinstance(l, A.ColRef):
                return dataclasses.replace(node, right=param(r, T.TEXT))
            if _is_str(l) and isinstance(r, A.ColRef):
                return dataclasses.replace(node, left=param(l, T.TEXT))
        if dataclasses.is_dataclass(node) and not isinstance(node, type):
            changed = {}
            for f in dataclasses.fields(node):
                v = getattr(node, f.name)
                nv = lift(v)
                if nv is not v:
                    changed[f.name] = nv
            if changed:
                return dataclasses.replace(node, **changed)
            return node
        if isinstance(node, list):
            out = [lift(x) for x in node]
            return out if any(a is not b for a, b in zip(out, node)) \
                else node
        if isinstance(node, tuple):
            out = tuple(lift(x) for x in node)
            return out if any(a is not b for a, b in zip(out, node)) \
                else node
        return node

    new_where = lift(stmt.where)
    if not args:
        return None
    template = dataclasses.replace(stmt, where=new_where)
    return template, args, types


def count_literals(node) -> int:
    """Literals under `node`: what a template's WHERE still bakes."""
    if isinstance(node, A.TypedConst):
        return 1
    if isinstance(node, A.Const):
        return int(node.kind != "null")
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        return sum(count_literals(getattr(node, f.name))
                   for f in dataclasses.fields(node))
    if isinstance(node, (list, tuple)):
        return sum(count_literals(x) for x in node)
    return 0


def cached_template(cluster, key, gen, build):
    """Cluster-wide Prepared-template cache, backed by the shared
    program-cache subsystem (exec/plancache.py AUTOPREP tier) so
    template reuse shows up in otb_plancache next to the compiled-
    program tiers it feeds.  `gen` is the plan-cache generation (DDL +
    stats + GUCs): a stale entry counts as a miss and rebuilds.  A
    None result is cached too — a template that can't bind with
    abstract params is remembered, so the failed bind is paid once."""
    from .plancache import AUTOPREP
    full = (id(cluster), key)
    ent = AUTOPREP.peek(full)
    if ent is not None and ent[0] == gen:
        AUTOPREP.count(hit=True)
        return ent[1]
    AUTOPREP.count(hit=False)
    prep = build()
    # gen/prep ride in the VALUE by design: the generation is validated
    # at peek (ent[0] == gen above), so it need not be in the key.
    AUTOPREP.put(full, (gen, prep))  # otblint: disable=program-key
    return prep
