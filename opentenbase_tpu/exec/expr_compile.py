"""Expression compiler: typed Expr trees -> jax-traceable closures.

Reference analog: ExecReadyInterpretedExpr building the EEOP_* opcode program
(src/backend/executor/execExpr.c, execExprInterp.c:120-124) and the LLVM JIT
tier (src/backend/jit/llvm/llvmjit_expr.c).  Here both tiers are one step:
`compile_expr` returns a python closure over a dict of column arrays; traced
under jax.jit it becomes fused XLA ops — the TPU executes the whole
qual+projection as part of the scan kernel, no per-tuple dispatch.

NULL semantics are compiled as a parallel mask program (compile_pair):
every expression yields (value_fn, null_fn|None).  Strict operators union
their children's masks and leave garbage at null positions of the value
array (the positions are masked before anything observes them — the
vectorized version of the reference's per-step NULL flag in
execExprInterp.c).  Non-strict nodes (AND/OR/NOT via Kleene 3VL, CASE,
COALESCE, NULLIF, IS NULL) manipulate the masks directly.  `null_fn is
None` proves the expression can never be NULL — the TPC-H hot paths
compile exactly as before, zero mask overhead.

Predicates go through `compile_pred`, which returns the SQL "is true"
test (value & ~null): a WHERE clause keeps a row only when the qual is
definitely true (reference: ExecQual's treatment of NULL as false).

String predicates (LIKE/=/< over TEXT) are resolved at compile time against
the store's dictionary into code sets; on device they are integer membership
tests.  This trades the reference's per-tuple varlena compares for one
host-side dictionary pass per (query, dictionary version).
"""

from __future__ import annotations

import re
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from ..catalog.types import TypeKind
from ..plan import exprs as E
from ..utils.dtypes import device_float, dev_dtype
from . import strtable

Arrays = dict  # name -> jnp array (null masks under NULLKEY + name)

NULLKEY = "__null__:"   # env key prefix for column null masks


def like_to_regex(pattern: str) -> re.Pattern:
    """SQL LIKE -> anchored python regex (%, _ wildcards)."""
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("^" + "".join(out) + "$", re.S)


def _np_dtype(t) -> np.dtype:
    # device-path dtype: FLOAT64 maps to f32 in tpu-safe mode
    return dev_dtype(t)


def _rescale(fn, from_scale: int, to_scale: int):
    if from_scale == to_scale:
        return fn
    if to_scale > from_scale:
        mult = 10 ** (to_scale - from_scale)
        return lambda cols, _f=fn, _m=mult: _f(cols) * jnp.int64(_m)
    div = 10 ** (from_scale - to_scale)
    return lambda cols, _f=fn, _d=div: jnp.floor_divide(_f(cols),
                                                        jnp.int64(_d))


def case_text_dict(e) -> "list | None":
    """Branch dictionary for a TEXT-valued CASE whose THEN/ELSE values
    are all literals: distinct non-null strings in first-occurrence
    order (the codes the compiled expression emits index into it).
    None when any branch is not a TEXT literal."""
    branches = [v for _, v in e.whens]
    if e.else_ is not None:
        branches.append(e.else_)
    values: list = []
    for v in branches:
        if not isinstance(v, E.Lit):
            return None
        if v.value is None:
            continue
        if v.lit_type.kind != TypeKind.TEXT:
            return None
        s = str(v.value)
        if s not in values:
            values.append(s)
    return values or [""]


def _membership(arr, codes: np.ndarray):
    """Integer membership test, shaped for TPU: small sets unroll to fused
    compares; larger sets use a sorted-search.  Comparison values take the
    array's own dtype (dictionary codes are int32, but InList values may be
    full int64)."""
    if len(codes) == 0:
        return jnp.zeros(arr.shape, dtype=bool)
    if len(codes) <= 16:
        m = arr == jnp.asarray(int(codes[0]), dtype=arr.dtype)
        for c in codes[1:]:
            m = m | (arr == jnp.asarray(int(c), dtype=arr.dtype))
        return m
    sorted_codes = jnp.asarray(np.sort(codes)).astype(arr.dtype)
    pos = jnp.searchsorted(sorted_codes, arr)
    pos = jnp.clip(pos, 0, len(codes) - 1)
    return sorted_codes[pos] == arr


# days-since-epoch -> civil date fields (branchless; Howard Hinnant's
# civil_from_days, public-domain algorithm)
def _civil(days):
    z = days.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(doe - doe // 1460 + doe // 36524 - doe // 146096,
                           365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = jnp.floor_divide(5 * doy + 2, 153)
    day = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    month = mp + jnp.where(mp < 10, 3, -9)
    year = y + (month <= 2)
    return year, month, day


NullFn = Optional[Callable[[Arrays], object]]


def _text_hash_fn(e: E.Expr, dicts: dict) -> Callable[[Arrays], object]:
    """Codes -> stable string-hash translation for one TEXT column
    (possibly transformed): cross-dictionary comparisons happen in the
    shared 64-bit hash space (utils/hashing.hash_string, the same hash
    routing/distribution uses)."""
    from ..utils.hashing import hash_string
    if isinstance(e, E.TextExpr):
        name, transform = e.col.name, e.apply
    elif isinstance(e, E.Col):
        name, transform = e.name, (lambda s: s)
    else:
        raise E.ExprError(
            "text comparison requires plain text columns")
    d = dicts.get(name)
    if d is None:
        raise E.ExprError(f"no dictionary for TEXT column {name!r}")
    lut = np.asarray([hash_string(transform(v)) for v in d.values]
                     or [0], dtype=np.uint64).view(np.int64)
    jl = jnp.asarray(lut)
    return lambda cols, _j=jl, _n=name: \
        _j[jnp.clip(cols[_n], 0, _j.shape[0] - 1)]


def _union(*nfs: NullFn) -> NullFn:
    """OR-combine null masks (strict-operator propagation)."""
    live = [f for f in nfs if f is not None]
    if not live:
        return None
    if len(live) == 1:
        return live[0]

    def nf(env, _fs=tuple(live)):
        m = _fs[0](env)
        for f in _fs[1:]:
            m = m | f(env)
        return m
    return nf


def _truth(vf, nf: NullFn):
    """SQL three-valued 'is true' / 'is false' closures from a pair."""
    if nf is None:
        return vf, (lambda env, _v=vf: ~_v(env))
    t = lambda env, _v=vf, _n=nf: _v(env) & ~_n(env)
    f = lambda env, _v=vf, _n=nf: ~_v(env) & ~_n(env)
    return t, f


def compile_pair(e: E.Expr, dicts: dict, nullable=frozenset()):
    """Return (value_fn, null_fn|None).  `nullable` is the set of column
    names that carry a null mask in the eval env (under NULLKEY+name);
    null_fn None proves the result is never NULL."""

    def c(x: E.Expr):
        if isinstance(x, E.Col):
            name = x.name
            vf = lambda cols: cols[name]
            if name in nullable:
                key = NULLKEY + name
                return vf, (lambda env: env[key])
            return vf, None

        if isinstance(x, E.Lit):
            t = x.lit_type
            if t.kind == TypeKind.TEXT and x.value is not None:
                # a projected TEXT literal: code 0 under a one-entry
                # dictionary (the executor's _dict_for_expr supplies it)
                return (lambda cols: jnp.asarray(0, dtype=jnp.int32)), None
            dt = _np_dtype(t)
            if x.value is None:
                return (lambda cols: jnp.asarray(0, dtype=dt),
                        lambda env: jnp.asarray(True))
            val = x.value
            return (lambda cols: jnp.asarray(val, dtype=dt)), None

        if isinstance(x, E.Arith):
            lt, rt = x.left.type, x.right.type
            (lf, ln), (rf, rn) = c(x.left), c(x.right)
            nf = _union(ln, rn)
            if x.type.kind == TypeKind.FLOAT64:
                lf2 = (lambda cols, _f=lf, _s=lt.scale:
                       _f(cols).astype(device_float()) / 10 ** _s) \
                    if lt.kind == TypeKind.DECIMAL else \
                    (lambda cols, _f=lf: _f(cols).astype(device_float()))
                rf2 = (lambda cols, _f=rf, _s=rt.scale:
                       _f(cols).astype(device_float()) / 10 ** _s) \
                    if rt.kind == TypeKind.DECIMAL else \
                    (lambda cols, _f=rf: _f(cols).astype(device_float()))
                op = x.op
                return {"+": lambda cols: lf2(cols) + rf2(cols),
                        "-": lambda cols: lf2(cols) - rf2(cols),
                        "*": lambda cols: lf2(cols) * rf2(cols),
                        "/": lambda cols: lf2(cols) / rf2(cols)}[op], nf
            if x.type.kind == TypeKind.DECIMAL and x.op in "+-":
                s = x.type.scale
                lf = _rescale(lf, lt.scale if lt.kind == TypeKind.DECIMAL
                              else 0, s) if lt.kind == TypeKind.DECIMAL \
                    else _rescale(lambda cols, _f=lf:
                                  _f(cols).astype(jnp.int64), 0, s)
                rf = _rescale(rf, rt.scale if rt.kind == TypeKind.DECIMAL
                              else 0, s) if rt.kind == TypeKind.DECIMAL \
                    else _rescale(lambda cols, _f=rf:
                                  _f(cols).astype(jnp.int64), 0, s)
            if x.op == "+":
                return (lambda cols: lf(cols) + rf(cols)), nf
            if x.op == "-":
                return (lambda cols: lf(cols) - rf(cols)), nf
            if x.op == "*":
                return (lambda cols: (lf(cols).astype(jnp.int64)
                                      * rf(cols).astype(jnp.int64))
                        if x.type.kind == TypeKind.DECIMAL
                        else lf(cols) * rf(cols)), nf
            if x.op == "%":
                # SQL modulo truncates toward zero (sign of the dividend);
                # python/numpy % floors (sign of the divisor)
                return (lambda cols: jnp.fmod(lf(cols), rf(cols))), nf
            raise E.ExprError(f"bad arith op {x.op}")

        if isinstance(x, E.Neg):
            f, nf = c(x.arg)
            return (lambda cols: -f(cols)), nf

        if isinstance(x, E.Cmp):
            lt, rt = x.left.type, x.right.type
            if lt.kind == TypeKind.TEXT and rt.kind == TypeKind.TEXT:
                # text-to-text equality: dictionary codes live in
                # DIFFERENT code spaces per column — translate both
                # sides to stable string hashes (64-bit; collisions
                # vanishingly unlikely) and compare those
                if x.op not in ("=", "<>"):
                    raise E.ExprError(
                        "text-to-text ordering comparison unsupported "
                        "(dictionary orders are column-local)")
                lh = _text_hash_fn(x.left, dicts)
                rh = _text_hash_fn(x.right, dicts)
                _, lnn = c(x.left)
                _, rnn = c(x.right)
                if x.op == "=":
                    vf = lambda cols: lh(cols) == rh(cols)
                else:
                    vf = lambda cols: lh(cols) != rh(cols)
                return vf, _union(lnn, rnn)
            (lf, ln), (rf, rn) = c(x.left), c(x.right)
            # align decimal scales / promote to float if either is float
            if TypeKind.FLOAT64 in (lt.kind, rt.kind):
                def mk(f, t):
                    if t.kind == TypeKind.DECIMAL:
                        return lambda cols: (f(cols).astype(device_float())
                                             / 10 ** t.scale)
                    return lambda cols: f(cols).astype(device_float())
                lf, rf = mk(lf, lt), mk(rf, rt)
            elif TypeKind.DECIMAL in (lt.kind, rt.kind):
                s = max(lt.scale, rt.scale)
                lf = _rescale(lf, lt.scale, s)
                rf = _rescale(rf, rt.scale, s)
            op = x.op
            vf = {"=": lambda cols: lf(cols) == rf(cols),
                  "<>": lambda cols: lf(cols) != rf(cols),
                  "<": lambda cols: lf(cols) < rf(cols),
                  "<=": lambda cols: lf(cols) <= rf(cols),
                  ">": lambda cols: lf(cols) > rf(cols),
                  ">=": lambda cols: lf(cols) >= rf(cols)}[op]
            return vf, _union(ln, rn)

        if isinstance(x, E.BoolOp):
            pairs = [c(a) for a in x.args]
            if all(n is None for _, n in pairs):
                fs = [v for v, _ in pairs]
                if x.op == "and":
                    def andf(cols, _fs=tuple(fs)):
                        m = _fs[0](cols)
                        for f in _fs[1:]:
                            m = m & f(cols)
                        return m
                    return andf, None

                def orf(cols, _fs=tuple(fs)):
                    m = _fs[0](cols)
                    for f in _fs[1:]:
                        m = m | f(cols)
                    return m
                return orf, None
            # Kleene 3VL: value = "definitely true", false = "definitely
            # false", null = neither (reference: ExecEvalBoolAndStep /
            # OrStep NULL handling in execExprInterp.c)
            truths = [_truth(v, n) for v, n in pairs]
            if x.op == "and":
                def tf(env, _ts=tuple(t for t, _ in truths)):
                    m = _ts[0](env)
                    for t in _ts[1:]:
                        m = m & t(env)
                    return m

                def ff(env, _fs=tuple(f for _, f in truths)):
                    m = _fs[0](env)
                    for f in _fs[1:]:
                        m = m | f(env)
                    return m
            else:
                def tf(env, _ts=tuple(t for t, _ in truths)):
                    m = _ts[0](env)
                    for t in _ts[1:]:
                        m = m | t(env)
                    return m

                def ff(env, _fs=tuple(f for _, f in truths)):
                    m = _fs[0](env)
                    for f in _fs[1:]:
                        m = m & f(env)
                    return m
            return tf, (lambda env: ~tf(env) & ~ff(env))

        if isinstance(x, E.Not):
            vf, nf = c(x.arg)
            if nf is None:
                return (lambda cols: ~vf(cols)), None
            t, f = _truth(vf, nf)
            return f, nf  # NOT null is null; NOT true=false, NOT false=true

        if isinstance(x, E.IsNull):
            _, nf = c(x.arg)
            if nf is None:
                const = bool(x.negated)  # never null
                return (lambda cols: jnp.asarray(const)), None
            if x.negated:
                return (lambda env: ~nf(env)), None
            return nf, None

        if isinstance(x, E.Coalesce):
            pairs = [c(a) for a in x.args]
            dt = _np_dtype(x.type)
            first_vf = pairs[0][0]
            if pairs[0][1] is None:
                return (lambda cols: first_vf(cols).astype(dt)), None

            def vf(env, _pairs=tuple(pairs)):
                out = _pairs[-1][0](env).astype(dt)
                for v, n in reversed(_pairs[:-1]):
                    if n is None:
                        out = v(env).astype(dt)
                    else:
                        out = jnp.where(n(env), out, v(env).astype(dt))
                return out
            nfs = [n for _, n in pairs]
            if any(n is None for n in nfs):
                return vf, None  # some arg can never be null

            def nf(env, _ns=tuple(nfs)):
                m = _ns[0](env)
                for n in _ns[1:]:
                    m = m & n(env)
                return m
            return vf, nf

        if isinstance(x, E.NullIf):
            lf, ln = c(x.left)
            # the equality goes through Cmp so decimal scales/floats align
            eqt, _ = _truth(*c(E.Cmp("=", x.left, x.right)))
            nf = (lambda env: ln(env) | eqt(env)) if ln is not None \
                else eqt
            return lf, nf

        if isinstance(x, E.Case) and x.type.kind == TypeKind.TEXT:
            # TEXT result: branches must be literals; the value is a code
            # into the shared branch dictionary (case_text_dict — the
            # executor attaches it to the output column)
            values = case_text_dict(x)
            if values is None:
                raise E.ExprError(
                    "CASE over TEXT requires literal THEN/ELSE values")
            index = {s: i for i, s in enumerate(values)}

            def code_of(v):
                return 0 if v.value is None else index[str(v.value)]

            cond_truths = [_truth(*c(w[0]))[0] for w in x.whens]
            when_codes = [code_of(v) for _, v in x.whens]
            else_code = code_of(x.else_) if x.else_ is not None else 0

            def casef(env):
                out = jnp.asarray(else_code, dtype=jnp.int32)
                for cond, wc in zip(reversed(cond_truths),
                                    reversed(when_codes)):
                    out = jnp.where(cond(env),
                                    jnp.asarray(wc, jnp.int32), out)
                return out

            when_nulls = [v.value is None for _, v in x.whens]
            else_is_null = x.else_ is None or x.else_.value is None
            if not any(when_nulls) and not else_is_null:
                return casef, None

            def case_nf(env):
                out = jnp.asarray(else_is_null)
                for cond, bn in zip(reversed(cond_truths),
                                    reversed(when_nulls)):
                    out = jnp.where(cond(env), jnp.asarray(bn), out)
                return out
            return casef, case_nf

        if isinstance(x, E.Case):
            cond_truths = [_truth(*c(w[0]))[0] for w in x.whens]
            val_pairs = [c(w[1]) for w in x.whens]
            else_pair = c(x.else_) if x.else_ is not None else None
            dt = _np_dtype(x.type)

            def casef(env):
                out = else_pair[0](env) if else_pair is not None \
                    else jnp.zeros((), dtype=dt)
                for cond, (val, _) in zip(reversed(cond_truths),
                                          reversed(val_pairs)):
                    out = jnp.where(cond(env), val(env), out)
                return out

            # null when the chosen branch is null; a missing ELSE is NULL
            branch_nulls = [n for _, n in val_pairs]
            else_null = None if else_pair is None else else_pair[1]
            if all(n is None for n in branch_nulls) and (
                    x.else_ is not None and else_null is None):
                return casef, None

            def case_nf(env):
                if x.else_ is None:
                    out = jnp.asarray(True)
                elif else_null is None:
                    out = jnp.asarray(False)
                else:
                    out = else_null(env)
                for cond, bn in zip(reversed(cond_truths),
                                    reversed(branch_nulls)):
                    bval = jnp.asarray(False) if bn is None else bn(env)
                    out = jnp.where(cond(env), bval, out)
                return out
            return casef, case_nf

        if isinstance(x, E.InList):
            f, nf = c(x.arg)
            vals = np.asarray(x.values)
            return (lambda cols: _membership(f(cols), vals)), nf

        if isinstance(x, (E.StrPred, E.CodeBitmap)):
            # the verdicts a dictionary value (exec/strtable.py): a few
            # codes to compare with, or a bitmap over the codes, which a
            # compiled tier may already have bound as a program argument
            # (CodeBitmap: Executor._ensure_expr)
            name = strtable.column_of(x)
            if isinstance(x, E.StrPred):
                d = dicts.get(name)
                if d is None:
                    raise E.ExprError(
                        f"no dictionary for TEXT column {name!r}")
                codes, words = strtable.resolve(x, d.values)
                neg = x.kind in strtable.NEGATED
            else:
                codes, words, neg = None, x.words, x.negated
            if codes is not None:
                member = lambda cols: _membership(cols[name], codes)
            else:
                member = lambda cols: strtable.bit_of(cols[name],
                                                      jnp.asarray(words))
            nf = (lambda env, _k=NULLKEY + name: env[_k]) \
                if name in nullable else None
            if neg:
                return (lambda cols: ~member(cols)), nf
            return member, nf

        if isinstance(x, E.TextExpr):
            # codes pass through; only the decode dictionary changes
            name = x.col.name
            nf = (lambda env, _k=NULLKEY + name: env[_k]) \
                if name in nullable else None
            return (lambda cols: cols[name]), nf

        if isinstance(x, E.DistExpr):
            from ..ops.ann import distances
            name = x.col.name
            q = np.asarray(x.query, dtype=np.float32)
            metric = x.metric
            return (lambda cols: distances(cols[name], jnp.asarray(q),
                                           metric).astype(device_float())), None

        if isinstance(x, E.Extract):
            f, nf = c(x.arg)
            idx = {"year": 0, "month": 1, "day": 2}[x.field]
            return (lambda cols: _civil(f(cols))[idx].astype(jnp.int32)), nf

        if isinstance(x, E.Cast):
            f, nf = c(x.arg)
            src, dst = x.arg.type, x.to
            if src.kind == TypeKind.NULL:
                dt = _np_dtype(dst)
                return (lambda cols: jnp.asarray(0, dtype=dt)), \
                    (lambda env: jnp.asarray(True))
            if dst.kind == TypeKind.FLOAT64 and src.kind == TypeKind.DECIMAL:
                return (lambda cols: f(cols).astype(device_float())
                        / 10 ** src.scale), nf
            if dst.kind == TypeKind.DECIMAL and src.kind == TypeKind.DECIMAL:
                return _rescale(f, src.scale, dst.scale), nf
            if dst.kind in (TypeKind.INT32, TypeKind.INT64) \
                    and src.kind == TypeKind.DECIMAL:
                dt = _np_dtype(dst)
                sc = 10 ** src.scale
                return (lambda cols: jnp.floor_divide(
                    f(cols), jnp.int64(sc)).astype(dt)), nf
            if dst.kind == TypeKind.DECIMAL and src.kind in (
                    TypeKind.INT32, TypeKind.INT64):
                return (lambda cols: f(cols).astype(jnp.int64)
                        * 10 ** dst.scale), nf
            if dst.kind == TypeKind.DECIMAL and src.kind == TypeKind.FLOAT64:
                return (lambda cols: jnp.round(
                    f(cols) * 10 ** dst.scale).astype(jnp.int64)), nf
            dt = _np_dtype(dst)
            return (lambda cols: f(cols).astype(dt)), nf

        raise E.ExprError(f"cannot compile {type(x).__name__}")

    return c(e)


def compile_expr(e: E.Expr, dicts: dict,
                 nullable=frozenset()) -> Callable[[Arrays], object]:
    """Value-only compile: fn(columns) -> array (garbage at null
    positions — pair with compile_pair's null_fn when they matter)."""
    return compile_pair(e, dicts, nullable)[0]


def compile_pred(e: E.Expr, dicts: dict,
                 nullable=frozenset()) -> Callable[[Arrays], object]:
    """Predicate compile under SQL 3VL: fn(env) -> bool array that is True
    exactly where the qual is definitely true (NULL counts as false —
    reference: ExecQual)."""
    vf, nf = compile_pair(e, dicts, nullable)
    if nf is None:
        return vf
    return _truth(vf, nf)[0]


def host_chunk_env(alias: str, ch):
    """Qual-eval namespace over one raw storage chunk (host numpy): the
    alias-qualified columns plus null masks under NULLKEY.  Returns
    (env, nullable_names) for compile_pred — DML paths (DELETE/UPDATE
    scans) share NULL semantics with the device executor this way."""
    n = ch.nrows
    env = {f"{alias}.{name}": arr[:n] for name, arr in ch.columns.items()}
    nullable = set()
    for name, m in ch.nulls.items():
        q = f"{alias}.{name}"
        env[NULLKEY + q] = m[:n]
        nullable.add(q)
    return env, nullable
