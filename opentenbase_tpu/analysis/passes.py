"""The four otblint passes.

host-sync
    Inside functions reachable from a traced region, a device value
    must never be forced to the host: ``int()/float()/bool()/len()``
    over a traced expression, ``.item()/.tolist()``, ``np.asarray``,
    ``jax.device_get``, or branching (``if``/``while``) on a traced
    value.  Device-ness is tracked by a light intraprocedural taint:
    results of ``jnp.* / jax.* / ops.kernels / utils.hashing`` calls
    (and anything derived from them) are traced; ``.shape/.dtype``
    reads and static kernel parameters (jit ``static_argnames``,
    int/bool/str-annotated args) are not.  Proven-traced only — the
    pass prefers missing a sync over crying wolf.

trace-purity
    Traced code must be replayable: no ``os.environ`` reads, no
    wall-clock (``time.*``/``datetime.*``), no RNG, no writes to
    module-level state.  Env flags are read at module import or at
    program-key construction — never mid-trace.

program-key
    At every ``ProgramCache.put(key, builder)`` site, each input the
    builder captures (closure free variables, call arguments) must be
    derivable from names that reach the key expression — the
    compiled program's identity must cover everything that shaped it.
    This is the PR-2 staged-array-namespace bug class, enforced.

lock-discipline
    A module-level mutable container in the threaded trees (exec/,
    storage/, gtm/, net/, utils/, obs/) that is written from function
    scope must declare ``# guarded_by: <lock>`` on its definition, and
    every such write must hold that lock (lexical ``with <lock>:`` or a
    ``# holds: <lock>`` contract on the enclosing def).

obs-purity
    Instrumentation must observe the engine, never become part of it:
    no ``obs.trace`` / ``obs.metrics`` call may be reachable inside a
    traced closure (spans would be captured at trace time, re-execute
    never, and their timers would read as zero — silently wrong).
    Spans/events belong at host boundaries only; eager-only regions
    (``if not self._traced:`` branches) are exempt.  Two names draw the
    line: ``jax.named_scope`` IS allowed inside a traced closure (it is
    metadata on the ops being traced, the device-side half of the
    naming), and ``jax.profiler.TraceAnnotation`` lives only in
    ``obs/``, host side (every span enters one there; a second place
    that writes on the profiler's clock is a second tracing system).

net-deadline
    Network conversations in the RPC-bearing modules (net/, gtm/,
    storage/replication.py) must carry a deadline: ``create_connection``
    needs ``timeout=``, and raw ``.recv``/``.sendall``/
    ``settimeout(None)`` are reserved for the frame codecs (wire.py,
    pgwire.py) — everything else flows through send_msg/recv_msg under
    the net/guard.py wrapper, which owns the per-op deadline.
"""

from __future__ import annotations

import ast
import builtins
from typing import Optional

from .callgraph import (TracedClosure, _GuardedWalker,
                        is_traced_guard_test)
from .core import Finding, FuncInfo, Project, _stmt_pragma_lines

_BUILTINS = frozenset(dir(builtins))

#: attribute reads that return static metadata, not device data
_DETAINT_ATTRS = frozenset({"shape", "dtype", "ndim", "itemsize",
                            "names", "types", "dicts"})
#: method calls that force a traced receiver to the host
_SYNC_METHODS = frozenset({"item", "tolist", "block_until_ready"})
#: container-mutating method names (lock-discipline / trace-purity)
_MUTATORS = frozenset({"append", "add", "update", "pop", "clear",
                       "setdefault", "extend", "remove", "discard",
                       "insert", "popitem", "appendleft", "popleft"})
_SCALAR_ANNOTS = frozenset({"int", "bool", "str", "float", "bytes"})
#: jax/jnp helpers that inspect dtypes statically — their results are
#: host booleans/infos, not traced values
_INTROSPECT = frozenset({"issubdtype", "iinfo", "finfo", "result_type",
                         "promote_types", "can_cast", "isdtype",
                         "dtype"})
#: identity/membership comparisons yield host bools (``x is None``,
#: ``name in batch.cols``) — never tracers
_HOST_CMP = (ast.Is, ast.IsNot, ast.In, ast.NotIn)

_IMPURE_CALL_PREFIXES = ("time.", "datetime.", "random.", "secrets.",
                         "numpy.random.", "uuid.")


def _dotted(expr, mi) -> Optional[str]:
    """Resolve an attribute chain to a dotted name, mapping the root
    through the module's import aliases (``jnp.sum`` -> ``jax.numpy.sum``,
    ``K.join_expand`` -> ``<pkg>.ops.kernels.join_expand``)."""
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if not isinstance(expr, ast.Name):
        return None
    root = expr.id
    if root in mi.import_modules:
        base = mi.import_modules[root]
    elif root in mi.import_symbols:
        mod, attr = mi.import_symbols[root]
        base = f"{mod}.{attr}" if mod else attr
    else:
        base = root
    return ".".join([base] + list(reversed(parts)))


def _func_locals(fn_node) -> set:
    """Names bound inside a function (params + assignments + loop/with
    targets + nested defs); ``global``-declared names are excluded."""
    out, globals_ = set(), set()
    a = fn_node.args
    for arg in (list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
                + ([a.vararg] if a.vararg else [])
                + ([a.kwarg] if a.kwarg else [])):
        out.add(arg.arg)

    def targets_of(t):
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                targets_of(e)
        elif isinstance(t, ast.Starred):
            targets_of(t.value)

    for st in ast.walk(fn_node):
        if st is fn_node:
            continue
        if isinstance(st, ast.Global):
            globals_.update(st.names)
        elif isinstance(st, (ast.Assign,)):
            for t in st.targets:
                targets_of(t)
        elif isinstance(st, (ast.AnnAssign, ast.AugAssign)):
            targets_of(st.target)
        elif isinstance(st, ast.For):
            targets_of(st.target)
        elif isinstance(st, ast.withitem) and st.optional_vars:
            targets_of(st.optional_vars)
        elif isinstance(st, ast.comprehension):
            targets_of(st.target)
        elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            out.add(st.name)
        elif isinstance(st, ast.NamedExpr):
            targets_of(st.target)
        elif isinstance(st, ast.ExceptHandler) and st.name:
            out.add(st.name)
    return out - globals_


def free_vars(fn_node) -> set:
    """Loaded names in a function body that are not bound locally —
    what a closure captures from its environment."""
    bound = _func_locals(fn_node)
    loads = set()
    for n in ast.walk(fn_node):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
            loads.add(n.id)
    return loads - bound - _BUILTINS


def _fn_disabled(fi: FuncInfo, rule: str) -> bool:
    return any(fi.src.disabled(ln, rule)
               for ln in _stmt_pragma_lines(fi.node))


class _Emitter:
    def __init__(self, rule: str):
        self.rule = rule
        self.findings: list = []
        self._seen: set = set()

    def emit(self, fi: FuncInfo, line: int, message: str):
        if fi.src.disabled(line, self.rule) or \
                _fn_disabled(fi, self.rule):
            return
        key = (fi.src.rel, line, message)
        if key in self._seen:   # loop bodies are walked twice
            return
        self._seen.add(key)
        self.findings.append(Finding(
            self.rule, fi.src.rel, line, fi.qualname, message))


# ===========================================================================
# host-sync
# ===========================================================================
class HostSyncPass:
    """Taint walk over every function in the traced closure."""

    rule = "host-sync"

    def __init__(self, project: Project, closure: TracedClosure):
        self.project = project
        self.closure = closure

    def run(self) -> list:
        em = _Emitter(self.rule)
        for fi in self.closure.functions():
            self._check(fi, em,
                        taint_params=(fi.module, fi.qualname)
                        in self.closure.root_keys)
        return em.findings

    # -- taint seeds ----------------------------------------------------
    def _static_params(self, fi: FuncInfo) -> set:
        """Params that are static config, not traced data: jit
        static_argnames + scalar-annotated + kwonly args."""
        out = set()
        node = fi.node
        for dec in getattr(node, "decorator_list", []) or []:
            for kw in getattr(dec, "keywords", []) or []:
                if kw.arg == "static_argnames":
                    for el in getattr(kw.value, "elts", []) or []:
                        if isinstance(el, ast.Constant):
                            out.add(str(el.value))
        a = node.args
        for arg in a.kwonlyargs:
            out.add(arg.arg)
        for arg in list(a.posonlyargs) + list(a.args):
            ann = arg.annotation
            if isinstance(ann, ast.Name) and ann.id in _SCALAR_ANNOTS:
                out.add(arg.arg)
            elif isinstance(ann, ast.BinOp):  # "int | None"
                names = {n.id for n in ast.walk(ann)
                         if isinstance(n, ast.Name)}
                if names & _SCALAR_ANNOTS:
                    out.add(arg.arg)
        return out

    def _check(self, fi: FuncInfo, em: _Emitter, taint_params: bool):
        mi = self.project.modules[fi.module]
        env: dict = {}
        if taint_params:
            static = self._static_params(fi)
            a = fi.node.args
            for arg in list(a.posonlyargs) + list(a.args) \
                    + ([a.vararg] if a.vararg else []):
                if arg.arg not in static and \
                        arg.arg not in ("self", "cls"):
                    env[arg.arg] = True

        pkg = self.project.package
        #: local names currently bound to plain Python containers
        #: (list/dict literals) — len()/truthiness on them is host-safe
        #: even when they hold traced elements
        py_containers: set = set()

        def producer(call) -> bool:
            d = _dotted(call.func, mi)
            if d is None:
                return False
            if d.split(".")[-1] in _INTROSPECT:
                return False
            return (d.startswith("jax.")
                    or d == "jax"
                    or d.startswith(f"{pkg}.ops.kernels.")
                    or d.startswith(f"{pkg}.utils.hashing."))

        def taint(e) -> bool:
            if isinstance(e, ast.Name):
                return env.get(e.id, False)
            if isinstance(e, ast.Attribute):
                if e.attr in _DETAINT_ATTRS:
                    return False
                return taint(e.value)
            if isinstance(e, ast.Subscript):
                return taint(e.value)
            if isinstance(e, ast.Call):
                if producer(e):
                    return True
                if isinstance(e.func, ast.Name) and \
                        e.func.id == "getattr" and len(e.args) >= 2 \
                        and isinstance(e.args[1], ast.Constant) \
                        and e.args[1].value in _DETAINT_ATTRS:
                    return False
                args = list(e.args) + [kw.value for kw in e.keywords]
                if any(taint(x) for x in args):
                    return True
                # method on a traced receiver stays traced (.astype,
                # .at[..].set, ...)
                if isinstance(e.func, ast.Attribute) and \
                        taint(e.func.value):
                    return True
                return False
            if isinstance(e, (ast.BinOp,)):
                return taint(e.left) or taint(e.right)
            if isinstance(e, ast.UnaryOp):
                return taint(e.operand)
            if isinstance(e, ast.BoolOp):
                return any(taint(v) for v in e.values)
            if isinstance(e, ast.Compare):
                if all(isinstance(op, _HOST_CMP) for op in e.ops):
                    return False
                return taint(e.left) or any(taint(c)
                                            for c in e.comparators)
            if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
                return any(taint(x) for x in e.elts)
            if isinstance(e, ast.IfExp):
                return taint(e.body) or taint(e.orelse)
            if isinstance(e, ast.NamedExpr):
                return taint(e.value)
            if isinstance(e, ast.Starred):
                return taint(e.value)
            return False

        def check_expr(e, eager: bool):
            """Recursive sink scan (guard-aware via `eager`)."""
            if isinstance(e, ast.IfExp):
                side = is_traced_guard_test(e.test)
                check_expr(e.test, eager)
                check_expr(e.body, eager or side == "eager")
                check_expr(e.orelse, eager or side == "traced")
                if not eager and taint(e.test) and side is None:
                    em.emit(fi, e.lineno,
                            "traced value in conditional expression")
                return
            if isinstance(e, ast.Call) and not eager:
                f = e.func
                if isinstance(f, ast.Name) and e.args:
                    a0 = e.args[0]
                    if f.id in ("int", "float", "bool", "len") and \
                            taint(a0) and not (
                                isinstance(a0, ast.Name)
                                and a0.id in py_containers):
                        em.emit(fi, e.lineno,
                                f"{f.id}() forces a traced value to "
                                f"the host")
                # dotted resolution covers BOTH spellings of a sink:
                # ``jax.device_get(x)`` and ``from jax import
                # device_get; device_get(x)`` map to the same name
                d = _dotted(f, mi) or ""
                if d in ("jax.device_get", "jax.block_until_ready"):
                    em.emit(fi, e.lineno,
                            f"{d}() inside a traced region")
                elif d.startswith("numpy.") and \
                        d.split(".")[-1] in ("asarray", "array",
                                             "copy") and \
                        e.args and taint(e.args[0]):
                    em.emit(fi, e.lineno,
                            "np.%s() copies a traced value to the "
                            "host" % d.split(".")[-1])
                elif isinstance(f, ast.Attribute) and \
                        f.attr in _SYNC_METHODS and taint(f.value):
                    em.emit(fi, e.lineno,
                            f".{f.attr}() forces a traced value "
                            f"to the host")
            for c in ast.iter_child_nodes(e):
                if isinstance(c, ast.expr):
                    check_expr(c, eager)
                elif isinstance(c, ast.comprehension):
                    check_expr(c.iter, eager)
                    for cond in c.ifs:
                        check_expr(cond, eager)

        def assign_target(t, v: bool):
            if isinstance(t, ast.Name):
                env[t.id] = env.get(t.id, False) or v
            elif isinstance(t, (ast.Tuple, ast.List)):
                for x in t.elts:
                    assign_target(x, v)
            elif isinstance(t, ast.Starred):
                assign_target(t.value, v)
            elif isinstance(t, (ast.Subscript, ast.Attribute)):
                # storing a traced value into a container taints the
                # container (cols[n] = a[take])
                root = t
                while isinstance(root, (ast.Subscript, ast.Attribute)):
                    root = root.value
                if isinstance(root, ast.Name) and v:
                    env[root.id] = True

        def host_truthy(test) -> bool:
            """Truthiness of a plain Python container is host-safe."""
            if isinstance(test, ast.UnaryOp) and \
                    isinstance(test.op, ast.Not):
                return host_truthy(test.operand)
            return isinstance(test, ast.Name) and \
                test.id in py_containers

        def is_py_container(v) -> bool:
            if isinstance(v, (ast.List, ast.ListComp, ast.Dict,
                              ast.DictComp, ast.Set, ast.SetComp)):
                return True
            return (isinstance(v, ast.Call)
                    and isinstance(v.func, ast.Name)
                    and v.func.id in ("list", "dict", "set", "sorted"))

        def for_targets(st, eager: bool):
            """``for a, b in zip(xs, ys)`` taints a from xs and b from
            ys — not everything from everything (the kernels'
            ``zip(agg_kinds, agg_inputs)`` walks a static kind list
            next to traced columns)."""
            it = st.iter
            if isinstance(it, ast.Call) and \
                    isinstance(it.func, ast.Name) and \
                    isinstance(st.target, ast.Tuple):
                elts = st.target.elts
                if it.func.id == "zip" and len(elts) == len(it.args):
                    for t, src in zip(elts, it.args):
                        assign_target(t, taint(src))
                    return
                if it.func.id == "enumerate" and len(elts) == 2 \
                        and it.args:
                    assign_target(elts[0], False)
                    assign_target(elts[1], taint(it.args[0]))
                    return
            assign_target(st.target, taint(it))

        def walk(stmts, eager: bool):
            for st in stmts:
                if isinstance(st, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.ClassDef)):
                    continue
                if isinstance(st, ast.Assign):
                    check_expr(st.value, eager)
                    v = taint(st.value)
                    for t in st.targets:
                        assign_target(t, v)
                        if isinstance(t, ast.Name):
                            if is_py_container(st.value):
                                py_containers.add(t.id)
                            else:
                                py_containers.discard(t.id)
                elif isinstance(st, ast.AnnAssign):
                    if st.value is not None:
                        check_expr(st.value, eager)
                        assign_target(st.target, taint(st.value))
                        if isinstance(st.target, ast.Name) and \
                                is_py_container(st.value):
                            py_containers.add(st.target.id)
                elif isinstance(st, ast.AugAssign):
                    check_expr(st.value, eager)
                    assign_target(st.target,
                                  taint(st.value) or taint(st.target))
                elif isinstance(st, ast.If):
                    side = is_traced_guard_test(st.test)
                    check_expr(st.test, eager)
                    if not eager and side is None and \
                            taint(st.test) and not host_truthy(st.test):
                        em.emit(fi, st.lineno,
                                "branching on a traced value "
                                "(TracerBoolConversionError at trace "
                                "time)")
                    walk(st.body, eager or side == "eager")
                    walk(st.orelse, eager or side == "traced")
                elif isinstance(st, ast.While):
                    check_expr(st.test, eager)
                    if not eager and taint(st.test) and \
                            not host_truthy(st.test):
                        em.emit(fi, st.lineno,
                                "while-loop over a traced value")
                    walk(st.body, eager)
                    walk(st.body, eager)   # loop-carried taint
                    walk(st.orelse, eager)
                elif isinstance(st, ast.For):
                    check_expr(st.iter, eager)
                    for_targets(st, eager)
                    walk(st.body, eager)
                    walk(st.body, eager)   # loop-carried taint
                    walk(st.orelse, eager)
                elif isinstance(st, ast.With):
                    for item in st.items:
                        check_expr(item.context_expr, eager)
                        if item.optional_vars is not None:
                            assign_target(item.optional_vars,
                                          taint(item.context_expr))
                    walk(st.body, eager)
                elif isinstance(st, ast.Try):
                    walk(st.body, eager)
                    for h in st.handlers:
                        walk(h.body, eager)
                    walk(st.orelse, eager)
                    walk(st.finalbody, eager)
                else:
                    for e in ast.iter_child_nodes(st):
                        if isinstance(e, ast.expr):
                            check_expr(e, eager)

        walk(fi.node.body, eager=False)


# ===========================================================================
# trace-purity
# ===========================================================================
class TracePurityPass:
    rule = "trace-purity"

    def __init__(self, project: Project, closure: TracedClosure):
        self.project = project
        self.closure = closure

    def run(self) -> list:
        em = _Emitter(self.rule)
        for fi in self.closure.functions():
            self._check(fi, em)
        return em.findings

    def _module_global(self, fi: FuncInfo, mi, name: str,
                       locals_: set) -> bool:
        """Whether `name` (not shadowed locally) refers to module-level
        state — of this module or imported from a scanned one."""
        if name in locals_:
            return False
        if name in mi.module_names:
            return True
        if name in mi.import_symbols:
            dmod, attr = mi.import_symbols[name]
            other = self.project.modules.get(dmod)
            return other is not None and attr in other.module_names
        return False

    def _check(self, fi: FuncInfo, em: _Emitter):
        mi = self.project.modules[fi.module]
        locals_ = _func_locals(fi.node)
        globals_decl: set = set()

        def check_expr(e, eager: bool):
            if isinstance(e, ast.IfExp):
                side = is_traced_guard_test(e.test)
                check_expr(e.test, eager)
                check_expr(e.body, eager or side == "eager")
                check_expr(e.orelse, eager or side == "traced")
                return
            if not eager:
                if isinstance(e, ast.Attribute):
                    d = _dotted(e, mi) or ""
                    if d in ("os.environ",):
                        em.emit(fi, e.lineno,
                                "os.environ read mid-trace — snapshot "
                                "at import or into the program key")
                if isinstance(e, ast.Call):
                    d = _dotted(e.func, mi) or ""
                    if d == "os.getenv":
                        em.emit(fi, e.lineno,
                                "os.getenv() mid-trace — snapshot at "
                                "import or into the program key")
                    elif d.startswith(_IMPURE_CALL_PREFIXES):
                        em.emit(fi, e.lineno,
                                f"impure call {d}() inside a traced "
                                f"region")
                    elif isinstance(e.func, ast.Attribute) and \
                            e.func.attr in _MUTATORS:
                        root = e.func.value
                        while isinstance(root, (ast.Subscript,
                                                ast.Attribute)):
                            root = root.value
                        if isinstance(root, ast.Name) and \
                                self._module_global(fi, mi, root.id,
                                                    locals_):
                            em.emit(fi, e.lineno,
                                    f"mutation of module-level "
                                    f"'{root.id}' inside a traced "
                                    f"region")
            for c in ast.iter_child_nodes(e):
                if isinstance(c, ast.expr):
                    check_expr(c, eager)
                elif isinstance(c, ast.comprehension):
                    check_expr(c.iter, eager)
                    for cond in c.ifs:
                        check_expr(cond, eager)

        def check_write(target, lineno: int, eager: bool):
            if eager:
                return
            root = target
            while isinstance(root, (ast.Subscript, ast.Attribute)):
                root = root.value
            if not isinstance(root, ast.Name):
                return
            name = root.id
            if name in globals_decl or (
                    not isinstance(target, ast.Name)
                    and self._module_global(fi, mi, name, locals_)):
                em.emit(fi, lineno,
                        f"write to module-level '{name}' inside a "
                        f"traced region")

        def walk(stmts, eager: bool):
            for st in stmts:
                if isinstance(st, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.ClassDef)):
                    continue
                if isinstance(st, ast.Global):
                    globals_decl.update(st.names)
                elif isinstance(st, ast.If):
                    side = is_traced_guard_test(st.test)
                    check_expr(st.test, eager)
                    walk(st.body, eager or side == "eager")
                    walk(st.orelse, eager or side == "traced")
                    continue
                elif isinstance(st, ast.Assign):
                    check_expr(st.value, eager)
                    for t in st.targets:
                        check_write(t, st.lineno, eager)
                elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
                    if getattr(st, "value", None) is not None:
                        check_expr(st.value, eager)
                    check_write(st.target, st.lineno, eager)
                elif isinstance(st, ast.Delete):
                    for t in st.targets:
                        check_write(t, st.lineno, eager)
                else:
                    for e in ast.iter_child_nodes(st):
                        if isinstance(e, ast.expr):
                            check_expr(e, eager)
                for field in ("body", "orelse", "finalbody"):
                    for s in getattr(st, field, []) or []:
                        walk([s], eager)
                for h in getattr(st, "handlers", []) or []:
                    walk(h.body, eager)

        walk(fi.node.body, eager=False)


# ===========================================================================
# obs-purity
# ===========================================================================
class ObsPurityPass:
    """No tracing/metrics call may execute under a trace: a span opened
    inside a jitted closure is captured once at trace time, never
    re-executed, and times nothing — and ``event()`` would mutate the
    thread-local stack mid-trace.  Flags (a) any call in the traced
    closure resolving into ``<pkg>.obs.``, (b) any ``obs`` module
    function that becomes reachable from a traced root at all, and (c)
    any function outside ``<pkg>.obs`` that names a profiler annotation
    (``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation``): the
    profiler's clock is written from ``obs/`` only.  ``jax.named_scope``
    inside a traced closure is not instrumentation in this sense — it
    names the ops being traced and runs nothing — and is never flagged."""

    rule = "obs-purity"
    ANNOTATIONS = ("jax.profiler.TraceAnnotation",
                   "jax.profiler.StepTraceAnnotation",
                   "jax._src.profiler.TraceAnnotation",
                   "jax._src.profiler.StepTraceAnnotation")

    def __init__(self, project: Project, closure: TracedClosure):
        self.project = project
        self.closure = closure
        self.obs_root = f"{project.package}.obs"

    def run(self) -> list:
        em = _Emitter(self.rule)
        for fi in self.closure.functions():
            if fi.module == self.obs_root or \
                    fi.module.startswith(self.obs_root + "."):
                em.emit(fi, fi.lineno,
                        f"obs function '{fi.qualname}' is reachable "
                        f"from a traced root — instrumentation became "
                        f"part of the program")
                continue
            self._check(fi, em)
        for mi in self.project.modules.values():
            if mi.dotted == self.obs_root or \
                    mi.dotted.startswith(self.obs_root + "."):
                continue
            for fi in mi.functions.values():
                for node in ast.walk(fi.node):
                    if isinstance(node, (ast.Name, ast.Attribute)) and \
                            _dotted(node, mi) in self.ANNOTATIONS:
                        em.emit(fi, node.lineno,
                                f"profiler annotation {_dotted(node, mi)} "
                                f"outside {self.obs_root}: spans go "
                                f"through obs.trace")
        return em.findings

    def _check(self, fi: FuncInfo, em: _Emitter):
        mi = self.project.modules[fi.module]
        prefix = self.obs_root + "."
        obs_root = self.obs_root

        class _W(_GuardedWalker):
            def on_call(self, call, eager: bool):
                if eager:
                    return
                d = _dotted(call.func, mi) or ""
                if d == obs_root or d.startswith(prefix):
                    em.emit(fi, call.lineno,
                            f"instrumentation call {d}() inside a "
                            f"traced region")

        _W().walk_function(fi.node)


# ===========================================================================
# program-key
# ===========================================================================
class ProgramKeyPass:
    rule = "program-key"

    def __init__(self, project: Project):
        self.project = project
        # every module-level name bound to a ProgramCache() anywhere
        self.cache_names: set = set()
        for mi in project.modules.values():
            for st in mi.src.tree.body:
                if isinstance(st, ast.Assign) and \
                        isinstance(st.value, ast.Call):
                    f = st.value.func
                    nm = f.id if isinstance(f, ast.Name) else (
                        f.attr if isinstance(f, ast.Attribute) else None)
                    if nm == "ProgramCache":
                        for t in st.targets:
                            if isinstance(t, ast.Name):
                                self.cache_names.add(t.id)

    def run(self) -> list:
        em = _Emitter(self.rule)
        for mi in self.project.modules.values():
            for fi in mi.functions.values():
                for call in ast.walk(fi.node):
                    if isinstance(call, ast.Call) and \
                            self._is_cache_put(call):
                        self._check_put(mi, fi, call, em)
        return em.findings

    def _is_cache_put(self, call) -> bool:
        f = call.func
        if not (isinstance(f, ast.Attribute) and f.attr == "put"
                and len(call.args) >= 2):
            return False
        owner = f.value
        name = owner.id if isinstance(owner, ast.Name) else (
            owner.attr if isinstance(owner, ast.Attribute) else None)
        return name in self.cache_names

    # -- local data-flow ------------------------------------------------
    @staticmethod
    def _assignments(fn_node) -> dict:
        """name -> list of RHS-name sets, from every binding form in the
        function (subscript stores contribute to their root name)."""
        out: dict = {}

        def names_of(e) -> set:
            return {n.id for n in ast.walk(e)
                    if isinstance(n, ast.Name)
                    and isinstance(n.ctx, ast.Load)}

        def bind(t, rhs_names: set):
            if isinstance(t, ast.Name):
                out.setdefault(t.id, []).append(rhs_names)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for x in t.elts:
                    bind(x, rhs_names)
            elif isinstance(t, ast.Starred):
                bind(t.value, rhs_names)
            elif isinstance(t, (ast.Subscript, ast.Attribute)):
                root = t
                extra = set()
                while isinstance(root, (ast.Subscript, ast.Attribute)):
                    if isinstance(root, ast.Subscript):
                        extra |= names_of(root.slice)
                    root = root.value
                if isinstance(root, ast.Name):
                    out.setdefault(root.id, []).append(
                        rhs_names | extra)

        for st in ast.walk(fn_node):
            if isinstance(st, ast.Assign):
                for t in st.targets:
                    bind(t, names_of(st.value))
            elif isinstance(st, (ast.AnnAssign, ast.AugAssign)) and \
                    getattr(st, "value", None) is not None:
                bind(st.target, names_of(st.value))
            elif isinstance(st, ast.For):
                bind(st.target, names_of(st.iter))
            elif isinstance(st, ast.NamedExpr):
                bind(st.target, names_of(st.value))
            elif isinstance(st, ast.withitem) and st.optional_vars:
                bind(st.optional_vars, names_of(st.context_expr))
        return out

    def _check_put(self, mi, fi: FuncInfo, call, em: _Emitter):
        assigns = self._assignments(fi.node)
        key_expr, value_expr = call.args[0], call.args[1]

        # reverse closure: every name that reaches the key expression
        key_names = {n.id for n in ast.walk(key_expr)
                     if isinstance(n, ast.Name)}
        changed = True
        while changed:
            changed = False
            for nm in list(key_names):
                for rhs in assigns.get(nm, ()):
                    new = rhs - key_names
                    if new:
                        key_names |= new
                        changed = True

        module_level = (set(mi.module_names) | set(mi.functions)
                        | set(mi.import_modules)
                        | set(mi.import_symbols)
                        | {f.name for f in mi.top_level_functions()})

        memo: dict = {}

        def covered(name: str, stack: frozenset) -> bool:
            if name in memo:
                return memo[name]
            if name in key_names or name in _BUILTINS or \
                    name in module_level:
                memo[name] = True
                return True
            if name in stack:
                return False
            # a nested def used as the builder: its captures must be
            # covered
            nested = mi.functions.get(f"{fi.qualname}.{name}")
            if nested is not None:
                ok = all(covered(n, stack | {name})
                         for n in free_vars(nested.node))
                memo[name] = ok
                return ok
            # derivable through a local assignment whose inputs are all
            # covered
            for rhs in assigns.get(name, ()):
                if all(covered(n, stack | {name}) for n in rhs):
                    memo[name] = True
                    return True
            memo[name] = False
            return False

        value_names = {n.id for n in ast.walk(value_expr)
                       if isinstance(n, ast.Name)
                       and isinstance(n.ctx, ast.Load)}
        for nm in sorted(value_names):
            if not covered(nm, frozenset()):
                em.emit(fi, call.lineno,
                        f"program builder input '{nm}' does not reach "
                        f"the cache key — a change in it would reuse a "
                        f"stale compiled program")


# ===========================================================================
# lock-discipline
# ===========================================================================
class LockDisciplinePass:
    rule = "lock-discipline"

    def __init__(self, project: Project,
                 trees: tuple = ("exec", "storage", "gtm", "net",
                                 "utils", "obs")):
        self.project = project
        self.trees = trees
        # (module, name) -> {"line", "lock", "module"}
        self.registry: dict = {}
        for mi in project.modules.values():
            if self._in_scope(mi.dotted):
                for name, info in mi.containers.items():
                    self.registry[(mi.dotted, name)] = info

    def _in_scope(self, dotted: str) -> bool:
        parts = dotted.split(".")
        return len(parts) >= 2 and parts[1] in self.trees

    def run(self) -> list:
        em = _Emitter(self.rule)
        mutated_unannotated: dict = {}   # (module, name) -> first site
        for mi in self.project.modules.values():
            if not self._in_scope(mi.dotted):
                continue
            for fi in mi.functions.values():
                self._check_fn(mi, fi, em, mutated_unannotated)
        # one finding per unannotated container, at its definition
        for (dmod, name), (fi, line) in sorted(
                mutated_unannotated.items()):
            info = self.registry[(dmod, name)]
            dmi = self.project.modules[dmod]
            def_line = info["line"]
            if dmi.src.disabled(def_line, self.rule):
                continue
            em.findings.append(Finding(
                self.rule, dmi.src.rel, def_line, "",
                f"module-level mutable '{name}' is written from "
                f"function scope ({fi.src.rel}:{line}) but has no "
                f"# guarded_by: <lock> annotation"))
        # annotations must reference a real module-level lock
        for (dmod, name), info in sorted(self.registry.items()):
            lock = info["lock"]
            dmi = self.project.modules[dmod]
            if lock is not None and lock not in dmi.locks and \
                    not dmi.src.disabled(info["line"], self.rule):
                em.findings.append(Finding(
                    self.rule, dmi.src.rel, info["line"], "",
                    f"'{name}' is guarded_by '{lock}' but no "
                    f"module-level lock of that name exists"))
        return em.findings

    def _resolve(self, mi, name: str) -> Optional[tuple]:
        """(module, name) of a registered container this name refers
        to, following from-imports."""
        if (mi.dotted, name) in self.registry:
            return (mi.dotted, name)
        if name in mi.import_symbols:
            dmod, attr = mi.import_symbols[name]
            if (dmod, attr) in self.registry:
                return (dmod, attr)
        return None

    def _check_fn(self, mi, fi: FuncInfo, em: _Emitter,
                  unannotated: dict):
        locals_ = _func_locals(fi.node)
        held0 = tuple(fi.holds)

        def lock_name(e) -> Optional[str]:
            if isinstance(e, ast.Name):
                return e.id
            if isinstance(e, ast.Attribute):
                return e.attr
            if isinstance(e, ast.Call):
                return None
            return None

        def mutation_root(node) -> Optional[ast.Name]:
            root = node
            while isinstance(root, (ast.Subscript, ast.Attribute)):
                root = root.value
            return root if isinstance(root, ast.Name) else None

        def report(name: str, line: int, held):
            if name in locals_:
                return
            key = self._resolve(mi, name)
            if key is None:
                return
            info = self.registry[key]
            lock = info["lock"]
            if lock is None:
                unannotated.setdefault(key, (fi, line))
                return
            if lock not in held:
                em.emit(fi, line,
                        f"write to '{name}' without holding its "
                        f"guarded_by lock '{lock}'")

        def bare_lock_op(st):
            """('acquire'|'release', name) for a statement-level
            ``lock.acquire()`` / ``lock.release()`` call."""
            call = st.value if isinstance(st, ast.Expr) and \
                isinstance(st.value, ast.Call) else None
            if call is None and isinstance(st, ast.Assign) and \
                    isinstance(st.value, ast.Call):
                call = st.value
            if call is None or not isinstance(call.func, ast.Attribute) \
                    or call.func.attr not in ("acquire", "release"):
                return None
            name = lock_name(call.func.value)
            return (call.func.attr, name) if name else None

        def walk(stmts, held: tuple):
            #: locks taken by bare .acquire() earlier in this body —
            #: they stay held across the following sibling statements
            #: (the classic acquire();try:...finally:release() shape)
            bare: list = []
            for st in stmts:
                if isinstance(st, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.ClassDef)):
                    continue
                eff = held + tuple(bare)
                if isinstance(st, ast.With):
                    add = [lock_name(item.context_expr)
                           for item in st.items]
                    walk(st.body, eff + tuple(a for a in add if a))
                    continue
                op = bare_lock_op(st)
                if op is not None:
                    if op[0] == "acquire":
                        bare.append(op[1])
                    elif op[1] in bare:
                        bare.remove(op[1])
                    continue
                if isinstance(st, ast.Assign):
                    for t in st.targets:
                        if not isinstance(t, ast.Name):
                            r = mutation_root(t)
                            if r is not None:
                                report(r.id, st.lineno, eff)
                elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
                    t = st.target
                    if not isinstance(t, ast.Name):
                        r = mutation_root(t)
                        if r is not None:
                            report(r.id, st.lineno, eff)
                elif isinstance(st, ast.Delete):
                    for t in st.targets:
                        r = mutation_root(t)
                        if r is not None and not isinstance(t, ast.Name):
                            report(r.id, st.lineno, eff)
                # mutating method calls in THIS statement's own
                # expressions — nested statements (e.g. a `with lock:`
                # block under an `if`) are walked by the recursion
                # below with their correct held-lock set
                stack: list = [v for f, v in ast.iter_fields(st)
                               if f not in ("body", "orelse",
                                            "finalbody", "handlers")]
                while stack:
                    x = stack.pop()
                    if isinstance(x, list):
                        stack.extend(x)
                        continue
                    if not isinstance(x, ast.AST) or \
                            isinstance(x, ast.stmt):
                        continue
                    if isinstance(x, ast.Call) and \
                            isinstance(x.func, ast.Attribute) and \
                            x.func.attr in _MUTATORS:
                        r = mutation_root(x.func.value)
                        if r is not None:
                            report(r.id, x.lineno, eff)
                    stack.extend(v for _, v in ast.iter_fields(x))
                # nested bodies walked WHOLE so a bare acquire() inside
                # (say) a try body stays held for its later siblings
                for field in ("body", "orelse", "finalbody"):
                    walk(getattr(st, field, []) or [], eff)
                for h in getattr(st, "handlers", []) or []:
                    walk(h.body, eff)

        walk(fi.node.body, held0)


# ===========================================================================
# net-deadline
# ===========================================================================
class NetDeadlinePass:
    """Every network conversation in the RPC-bearing modules must carry
    a deadline.  In scope (``net/``, ``gtm/``, ``storage/replication``):

    - ``socket.create_connection(...)`` must pass ``timeout=`` — a
      connect without one blocks a coordinator thread on a dead peer
      for the kernel default (minutes), starving the pool.
    - raw ``.recv(`` / ``.sendall(`` and ``.settimeout(None)`` are
      reserved for the frame codecs (``net/wire.py``, ``net/pgwire.py``)
      — everything else talks through ``send_msg``/``recv_msg`` under a
      ``guard.guarded`` wrapper, which owns the deadline.

    Per-site escapes use ``# otblint: disable=net-deadline``."""

    rule = "net-deadline"

    def __init__(self, project: Project):
        self.project = project
        pkg = project.package
        self.scope_dirs = (f"{pkg}/net/", f"{pkg}/gtm/")
        self.scope_files = (f"{pkg}/storage/replication.py",)
        self.frame_codecs = (f"{pkg}/net/wire.py", f"{pkg}/net/pgwire.py")

    def _in_scope(self, norm: str) -> bool:
        return norm.startswith(self.scope_dirs) or norm in self.scope_files

    def run(self) -> list:
        import os as _os
        findings = []
        for rel, mi in self.project.by_rel.items():
            norm = rel.replace(_os.sep, "/")
            if not self._in_scope(norm):
                continue
            codec = norm in self.frame_codecs
            self._check_module(mi, codec, findings)
        return findings

    # -- helpers --------------------------------------------------------
    def _enclosing(self, mi, line: int):
        """Innermost function containing `line` (None = module level)."""
        best, best_start = None, -1
        for fi in mi.functions.values():
            node = fi.node
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= line <= end and node.lineno > best_start:
                best, best_start = fi, node.lineno
        return best

    def _emit(self, findings, mi, line: int, message: str):
        src = mi.src
        if src.disabled(line, self.rule):
            return
        fi = self._enclosing(mi, line)
        if fi is not None and _fn_disabled(fi, self.rule):
            return
        findings.append(Finding(self.rule, src.rel, line,
                                fi.qualname if fi else "", message))

    def _check_module(self, mi, codec: bool, findings):
        for node in ast.walk(mi.src.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func, mi)
            if d == "socket.create_connection":
                if not any(kw.arg == "timeout" for kw in node.keywords) \
                        and len(node.args) < 2:
                    self._emit(findings, mi, node.lineno,
                               "socket.create_connection without a "
                               "timeout — a dead peer blocks this "
                               "thread for the kernel default")
                continue
            if codec:
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            if f.attr in ("recv", "sendall"):
                self._emit(findings, mi, node.lineno,
                           f"raw socket .{f.attr}() outside the frame "
                           f"codec — use send_msg/recv_msg under a "
                           f"guard wrapper (deadline ownership)")
            elif f.attr == "settimeout" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    node.args[0].value is None:
                self._emit(findings, mi, node.lineno,
                           "settimeout(None) disables the RPC "
                           "deadline on this socket")


# ===========================================================================
# wait-discipline
# ===========================================================================
class WaitDisciplinePass:
    """Every blocking wait on the serving path must be attributed to a
    named wait event.  In scope (``exec/``, ``net/``, ``gtm/``,
    ``storage/``), these calls must run lexically inside a
    ``with ...wait_event("..."):`` block (obs/xray.py) or carry a
    justified ``# otblint: disable=wait-discipline`` pragma:

    - ``<cond-or-event>.wait(...)`` — a Condition/Event park is exactly
      the stall ``otb_wait_events`` exists to explain; an unnamed one
      is invisible to the histogram AND to ``otb_stat_activity``.
    - ``.get(...)`` on a ``queue.Queue`` attribute, and ``.put(...)``
      when that queue was constructed bounded (a bounded put blocks on
      backpressure; ``get_nowait``/unbounded puts never park).
    - ``recv_msg(..., expect_reply=True)`` — the caller is owed a
      reply, so this recv IS the RPC on-wire wait.

    The frame codecs (``net/wire.py``, ``net/pgwire.py``) are exempt —
    they are the mechanism under the named waits, not call sites.
    Method calls on ``self`` named ``wait`` (e.g. ``Scheduler.wait``)
    are wrappers, not primitives — the primitive they park on is
    checked at its own site."""

    rule = "wait-discipline"

    def __init__(self, project: Project):
        self.project = project
        pkg = project.package
        self.scope_dirs = (f"{pkg}/exec/", f"{pkg}/net/",
                          f"{pkg}/gtm/", f"{pkg}/storage/")
        self.exempt_files = (f"{pkg}/net/wire.py", f"{pkg}/net/pgwire.py")

    def _in_scope(self, norm: str) -> bool:
        return norm.startswith(self.scope_dirs) \
            and norm not in self.exempt_files

    def run(self) -> list:
        import os as _os
        findings = []
        for rel, mi in self.project.by_rel.items():
            norm = rel.replace(_os.sep, "/")
            if self._in_scope(norm):
                self._check_module(mi, findings)
        return findings

    # -- helpers --------------------------------------------------------
    def _enclosing(self, mi, line: int):
        best, best_start = None, -1
        for fi in mi.functions.values():
            node = fi.node
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= line <= end and node.lineno > best_start:
                best, best_start = fi, node.lineno
        return best

    def _emit(self, findings, mi, line: int, message: str):
        src = mi.src
        if src.disabled(line, self.rule):
            return
        fi = self._enclosing(mi, line)
        if fi is not None and _fn_disabled(fi, self.rule):
            return
        findings.append(Finding(self.rule, src.rel, line,
                                fi.qualname if fi else "", message))

    @staticmethod
    def _base_name(expr) -> Optional[str]:
        """Last name segment of a call receiver: `self._q` -> `_q`."""
        if isinstance(expr, ast.Attribute):
            return expr.attr
        if isinstance(expr, ast.Name):
            return expr.id
        return None

    def _check_module(self, mi, findings):
        tree = mi.src.tree
        # line intervals of `with ...wait_event(...):` blocks — a wait
        # lexically inside one is attributed, whatever thread runs it
        covered = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.With):
                continue
            for item in node.items:
                call = item.context_expr
                if isinstance(call, ast.Call):
                    d = _dotted(call.func, mi) or ""
                    if d.split(".")[-1] == "wait_event":
                        covered.append((node.lineno,
                                        getattr(node, "end_lineno",
                                                node.lineno)))
                        break

        def attributed(line: int) -> bool:
            return any(a <= line <= b for a, b in covered)

        # harvest queue.Queue attribute/name assignments; remember
        # which were constructed with a capacity (bounded => put blocks)
        queues, bounded = set(), set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign):   # self._q: Queue = ...
                targets = [node.target]
            else:
                continue
            if not isinstance(node.value, ast.Call):
                continue
            d = _dotted(node.value.func, mi) or ""
            if d.split(".")[-1] != "Queue":
                continue
            for t in targets:
                name = self._base_name(t)
                if name is None:
                    continue
                queues.add(name)
                if node.value.args or any(kw.arg == "maxsize"
                                          for kw in node.value.keywords):
                    bounded.add(name)

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            line = node.lineno
            d = _dotted(node.func, mi) or ""
            if d.split(".")[-1] == "recv_msg" and any(
                    kw.arg == "expect_reply"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value for kw in node.keywords):
                if not attributed(line):
                    self._emit(findings, mi, line,
                               "recv_msg(expect_reply=True) outside a "
                               "wait_event context — this recv is the "
                               "RPC on-wire wait; name it")
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            base = self._base_name(f.value)
            if f.attr == "wait":
                # `self.wait(...)` is a wrapper method, not a primitive
                if isinstance(f.value, ast.Name) and f.value.id == "self":
                    continue
                if not attributed(line):
                    self._emit(findings, mi, line,
                               f"blocking .wait() on {base or '?'} "
                               f"outside a wait_event context — "
                               f"unnamed stall, invisible to "
                               f"otb_wait_events")
            elif f.attr == "get" and base in queues:
                if not attributed(line):
                    self._emit(findings, mi, line,
                               f"queue {base}.get() outside a "
                               f"wait_event context — an empty queue "
                               f"parks this thread unnamed")
            elif f.attr == "put" and base in bounded:
                if not attributed(line):
                    self._emit(findings, mi, line,
                               f"bounded queue {base}.put() outside a "
                               f"wait_event context — backpressure "
                               f"parks this thread unnamed")


# ===========================================================================
# slot-discipline
# ===========================================================================
class SlotDisciplinePass:
    """Every admission-slot acquire must have a release reachable via
    ``finally``.  A GTM resource-queue slot (``resq_acquire``) or a
    scheduler admission (``_admit``) that a statement dies holding
    shrinks cluster-wide concurrency until the lease reaper notices —
    and with long leases that is minutes of a slot doing nothing.

    Accepted shapes, within the enclosing function:

    - ``acquire(); try: ... finally: release()`` — the ``try`` starts
      at/after the acquire, so every post-acquire exception path runs
      the release; or
    - ``try: acquire(); ... finally: release()`` — the acquire sits
      inside the protected body (release must tolerate not-held, which
      resq_release's owner identity check provides).

    Wrappers that intentionally delegate the release to their caller
    (the scheduler's ``_admit`` itself, the GTM wire passthrough) mark
    the site ``# otblint: disable=slot-discipline``."""

    rule = "slot-discipline"

    _ACQUIRES = ("resq_acquire", "_admit")
    _RELEASES = ("resq_release", "_release", "release",
                 "resq_disconnect")

    def __init__(self, project: Project):
        self.project = project

    def run(self) -> list:
        findings = []
        for mi in self.project.by_rel.values():
            for node in ast.walk(mi.src.tree):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func, mi)
                if d is None or d.split(".")[-1] not in self._ACQUIRES:
                    continue
                self._check_site(mi, node, findings)
        return findings

    # -- helpers --------------------------------------------------------
    def _enclosing(self, mi, line: int):
        best, best_start = None, -1
        for fi in mi.functions.values():
            node = fi.node
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= line <= end and node.lineno > best_start:
                best, best_start = fi, node.lineno
        return best

    def _releases(self, stmts) -> bool:
        for st in stmts:
            for node in ast.walk(st):
                if isinstance(node, ast.Call):
                    d = _dotted(node.func, self._mi)
                    if d is not None and \
                            d.split(".")[-1] in self._RELEASES:
                        return True
        return False

    def _check_site(self, mi, call: ast.Call, findings):
        src = mi.src
        if src.disabled(call.lineno, self.rule):
            return
        fi = self._enclosing(mi, call.lineno)
        if fi is None:
            findings.append(Finding(
                self.rule, src.rel, call.lineno, "",
                "module-level slot acquire cannot pair with a "
                "finally-reachable release"))
            return
        if _fn_disabled(fi, self.rule):
            return
        self._mi = mi
        ok = False
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Try) or not node.finalbody:
                continue
            if not self._releases(node.finalbody):
                continue
            end = getattr(node, "end_lineno", node.lineno)
            encloses = node.lineno <= call.lineno <= end
            follows = node.lineno >= call.lineno
            if encloses or follows:
                ok = True
                break
        if not ok:
            findings.append(Finding(
                self.rule, src.rel, call.lineno, fi.qualname,
                "slot acquire without a release reachable via "
                "finally — an exception here leaks cluster-wide "
                "admission concurrency until lease expiry"))
