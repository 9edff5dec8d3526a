"""otbcard: compile-cardinality & device-residency analysis.

The plancache bounds how many compiled XLA programs stay LIVE; these
passes bound how many can EXIST.  Every compiled-program key component
must have a bounded domain — literal-masked plan structure, quantized
size classes (``size_class``/``next_pow2``/``_batch_class``), pow2
join-ladder factors — because one unbounded component (a raw row
count, wall clock, dict iteration order) turns the LRU into a conveyor
belt: every query compiles, nothing ever hits.  Residency is the dual
constraint: device arrays parked outside the bufferpool are invisible
to ``OTB_DEVICE_CACHE_BYTES`` and to ``shed_coldest``, so the OOM
ladder fires blind.  Four static passes plus a runtime cross-check:

program-cardinality
    Interprocedural dataflow from every ``ProgramCache.put`` site:
    wall-clock / RNG / uuid results, raw ``row_count()`` values not
    passed through a quantizer, and unsorted dict iteration
    (``.items()/.keys()/.values()`` outside ``sorted(...)``) must not
    reach the key expression.  Follows one level into same-project
    callees that feed the key (the ``_table_sig`` shape).

retrace-risk
    Program identity minted per VALUE instead of per CLASS:
    unhashable key components (``ProgramCache.put`` silently skips
    caching on TypeError — every call recompiles), generator/ephemeral
    ``id()`` components (fresh object per call — the key never
    matches), ``int()/float()`` of device data feeding a key, and —
    inside the traced closure — branching that compares a raw
    ``.shape`` int against a non-constant without quantization.

device-residency
    ``jax.device_put`` outside the sanctioned staging layer
    (storage/bufferpool.py, storage/batch.py, parallel/mesh.py, or a
    function that accounts via ``POOL.note_upload``), and
    device-produced values stored into module-level containers outside
    the pool — both are bytes the device budget cannot see.

transfer-discipline
    HostSyncPass (passes.py) proves traced closures sync-free; this
    pass audits the EAGER side of the device-hot trees (exec/,
    storage/, parallel/, ops/): ``jax.device_get`` / ``np.asarray`` of
    device data / ``.tolist()`` / ``.item()`` are findings unless the
    enclosing function is a declared ``# otblint: sync-boundary`` —
    the annotation enumerates every legal materialization point in the
    engine, greppably.

retrace-witness
    Cross-check of ``analysis/program_census.json`` — per-program
    compile provenance recorded by the OTB_TRACECHECK=1 sanitizer in
    exec/plancache.py — against the static ladder predictions: every
    witnessed class int must be ladder-shaped (pow2 or the
    quarter-step {4,5,6,7}*2^k classes — at most 3 significant bits),
    join factors must respect the 4096 ladder cap, a key re-put
    without an eviction is an unexplained retrace, and a fragment
    fanning out past ``_STORM_LIMIT`` class combinations is a compile
    storm.  The same witness pattern as analysis/concurrency.py's
    lock_order.json: runtime reality may never exceed what the static
    model predicts.
"""

from __future__ import annotations

import ast
import json
import os
from typing import Optional

from .callgraph import TracedClosure, is_traced_guard_test
from .core import Finding, FuncInfo, Project
from .passes import ProgramKeyPass, _dotted, _Emitter, _fn_disabled

#: functions that collapse an unbounded int into a bounded class
_QUANT_FUNCS = frozenset({"size_class", "next_pow2", "_batch_class",
                          "chunk_class", "lut_capacity", "codec_class",
                          "codec_classes"})
#: identifier tokens that smell like a raw encoding descriptor — an
#: Enc's reference / LUT contents drift with appends, so only the
#: quantized codec-class token (codec_class/codec_classes) may reach
#: program-key material (storage/codec.py)
_ENC_TOKENS = frozenset({"enc", "encs", "encm", "encoding", "encodings",
                         "codec", "codecs"})
#: call prefixes whose results have an unbounded / per-process domain
_UNBOUNDED_PREFIXES = ("time.", "datetime.", "random.", "secrets.",
                       "uuid.", "numpy.random.")
_UNBOUNDED_CALLS = frozenset({"os.getpid", "os.urandom",
                              "threading.get_ident"})
#: list-producing calls — unhashable as a direct key component
_LIST_CALLS = frozenset({"sorted", "list"})
#: calls that return hashable scalars/containers — safe key components
_HASHABLE_CALLS = frozenset({"tuple", "frozenset", "struct_key",
                             "fingerprint", "hash", "id", "int", "str",
                             "float", "bool", "len", "min", "max",
                             "sum", "repr", "next_pow2", "size_class",
                             "_batch_class", "chunk_class", "getattr",
                             "lut_capacity", "codec_class",
                             "codec_classes"})
#: constructors of fresh per-call objects — id() of one is ephemeral
_FRESH_CALLS = frozenset({"dict", "list", "set", "object", "bytearray"})

_STORM_LIMIT = 64      # class combinations per fragment signature
_FACTOR_CAP = 4096     # exec/plancache.py Ladder.CAP (a test holds them equal)


def _loads(e) -> set:
    return {n.id for n in ast.walk(e)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}


def _assign_exprs(fn_node) -> dict:
    """name -> list of (RHS expression, via_iter) from every binding
    form (the expression-level sibling of
    ProgramKeyPass._assignments).  ``via_iter`` marks loop/
    comprehension-target bindings: the bound name holds one ELEMENT of
    the iterable, so iteration-ORDER concerns do not transfer through
    it (the comprehension expression itself is walked in its real
    sorted(...) context)."""
    out: dict = {}

    def bind(t, value, via_iter=False):
        if isinstance(t, ast.Name):
            out.setdefault(t.id, []).append((value, via_iter))
        elif isinstance(t, (ast.Tuple, ast.List)):
            for x in t.elts:
                bind(x, value, via_iter)
        elif isinstance(t, ast.Starred):
            bind(t.value, value, via_iter)
        elif isinstance(t, (ast.Subscript, ast.Attribute)):
            root = t
            while isinstance(root, (ast.Subscript, ast.Attribute)):
                root = root.value
            if isinstance(root, ast.Name):
                out.setdefault(root.id, []).append((value, via_iter))

    for st in ast.walk(fn_node):
        if isinstance(st, ast.Assign):
            for t in st.targets:
                bind(t, st.value)
        elif isinstance(st, (ast.AnnAssign, ast.AugAssign)) and \
                getattr(st, "value", None) is not None:
            bind(st.target, st.value)
        elif isinstance(st, ast.For):
            bind(st.target, st.iter, via_iter=True)
        elif isinstance(st, ast.NamedExpr):
            bind(st.target, st.value)
        elif isinstance(st, ast.withitem) and st.optional_vars:
            bind(st.optional_vars, st.context_expr)
        elif isinstance(st, ast.comprehension):
            bind(st.target, st.iter, via_iter=True)
    return out


def _flow_exprs(fi: FuncInfo, seed_expr) -> list:
    """[(expr, via_iter)] — the seed expression plus the RHS of every
    assignment that (transitively) feeds a name appearing in it: the
    set of expressions whose values can reach the seed."""
    assigns = _assign_exprs(fi.node)
    exprs = [(seed_expr, False)]
    seen_ids = {id(seed_expr)}
    names = _loads(seed_expr)
    frontier = list(names)
    while frontier:
        nm = frontier.pop()
        for rhs, via_iter in assigns.get(nm, ()):
            if id(rhs) in seen_ids:
                continue
            seen_ids.add(id(rhs))
            exprs.append((rhs, via_iter))
            for n2 in _loads(rhs):
                if n2 not in names:
                    names.add(n2)
                    frontier.append(n2)
    return exprs


def _return_exprs(fi: FuncInfo) -> list:
    return [st.value for st in ast.walk(fi.node)
            if isinstance(st, ast.Return) and st.value is not None]


def _producer_call(e, mi, pkg: str) -> bool:
    """Whether the expression subtree contains a device-data producer
    (a jax/jnp/kernels call)."""
    for n in ast.walk(e):
        if isinstance(n, ast.Call):
            d = _dotted(n.func, mi) or ""
            if d.startswith("jax.") or d == "jax" or \
                    d.startswith(f"{pkg}.ops.kernels."):
                return True
    return False


# ===========================================================================
# program-cardinality
# ===========================================================================
class ProgramCardinalityPass:
    """Every ``ProgramCache.put`` key component must have a bounded
    domain.  Positive-evidence detection only (the repo convention:
    prefer missing a case over crying wolf) — a finding names the
    unbounded source it actually saw in the key's dataflow."""

    rule = "program-cardinality"

    def __init__(self, project: Project,
                 closure: Optional[TracedClosure] = None):
        self.project = project
        self._pk = ProgramKeyPass(project)
        self.closure = closure

    def run(self) -> list:
        em = _Emitter(self.rule)
        for mi in self.project.modules.values():
            for fi in mi.functions.values():
                for call in ast.walk(fi.node):
                    if isinstance(call, ast.Call) and \
                            self._pk._is_cache_put(call):
                        self._check_put(mi, fi, call, em)
        return em.findings

    def _callee(self, mi, fi: FuncInfo, call) -> Optional[FuncInfo]:
        """Same-project callee of a Call in key flow (one level)."""
        f = call.func
        if isinstance(f, ast.Name):
            tgt = mi.functions.get(f"{fi.qualname}.{f.id}") \
                or mi.functions.get(f.id)
            if tgt is None and fi.class_name:
                tgt = mi.functions.get(f"{fi.class_name}.{f.id}")
            if tgt is None and f.id in mi.import_symbols:
                dmod, attr = mi.import_symbols[f.id]
                tgt = self.project.function(dmod, attr)
            return tgt
        if isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name):
            if f.value.id in ("self", "cls") and fi.class_name:
                return mi.functions.get(f"{fi.class_name}.{f.attr}")
            dmod = mi.import_modules.get(f.value.id)
            if dmod is not None:
                return self.project.function(dmod, f.attr)
        return None

    def _check_put(self, mi, fi: FuncInfo, call, em: _Emitter):
        key_expr = call.args[0]
        sites = [(e, it, fi, mi) for e, it in _flow_exprs(fi, key_expr)]
        # one level into same-project callees feeding the key
        # (_table_sig's id()/dict-iteration must be visible here)
        seen_fns = {(fi.module, fi.qualname)}
        for e, _it, _fi, _mi in list(sites):
            for n in ast.walk(e):
                if not isinstance(n, ast.Call):
                    continue
                tgt = self._callee(_mi, _fi, n)
                if tgt is None or (tgt.module, tgt.qualname) in seen_fns:
                    continue
                if tgt.qualname.split(".")[-1] in _QUANT_FUNCS:
                    # a quantizer's INTERNALS aren't key material — its
                    # whole point is collapsing the raw domain before
                    # the key sees it
                    continue
                seen_fns.add((tgt.module, tgt.qualname))
                tmi = self.project.modules[tgt.module]
                for ret in _return_exprs(tgt):
                    sites.extend((x, it, tgt, tmi)
                                 for x, it in _flow_exprs(tgt, ret))
        for e, via_iter, efi, emi in sites:
            self._scan(e, via_iter, efi, emi, em)

    def _scan(self, expr, via_iter: bool, fi: FuncInfo, mi,
              em: _Emitter):
        def walk(e, in_sorted: bool, in_quant: bool):
            if isinstance(e, ast.Call):
                d = _dotted(e.func, mi) or ""
                short = d.split(".")[-1]
                if short == "sorted":
                    for c in ast.iter_child_nodes(e):
                        if isinstance(c, ast.expr):
                            walk(c, True, in_quant)
                        elif isinstance(c, ast.comprehension):
                            walk(c.iter, True, in_quant)
                    return
                if short in _QUANT_FUNCS:
                    for c in ast.iter_child_nodes(e):
                        if isinstance(c, ast.expr):
                            walk(c, in_sorted, True)
                    return
                if d.startswith(_UNBOUNDED_PREFIXES) or \
                        d in _UNBOUNDED_CALLS:
                    em.emit(fi, e.lineno,
                            f"{d}() in program-key material — wall "
                            f"clock / RNG / process identity has an "
                            f"unbounded domain, so every call mints a "
                            f"fresh compiled program")
                elif short == "row_count" and not in_quant:
                    em.emit(fi, e.lineno,
                            "raw row count in program-key material — "
                            "quantize through size_class()/next_pow2() "
                            "so the compile population stays a ladder, "
                            "not one program per table size")
                elif isinstance(e.func, ast.Attribute) and \
                        e.func.attr in ("items", "keys", "values") and \
                        not e.args and not in_sorted:
                    em.emit(fi, e.lineno,
                            f".{e.func.attr}() iteration order in "
                            f"program-key material — wrap in "
                            f"sorted(...) or two processes with "
                            f"different insertion orders compile "
                            f"distinct programs for one fragment")
            elif isinstance(e, ast.Name) and \
                    isinstance(e.ctx, ast.Load) and not in_quant and \
                    "chunk" in e.id.lower():
                em.emit(fi, e.lineno,
                        f"raw chunk count/size '{e.id}' in program-key "
                        f"material — a morsel stream re-sizes its "
                        f"window under pressure, so quantize through "
                        f"chunk_class() or one stream mints one "
                        f"compiled program per chunk geometry")
                return
            elif isinstance(e, ast.Name) and \
                    isinstance(e.ctx, ast.Load) and not in_quant and \
                    any(t in _ENC_TOKENS
                        for t in e.id.lower().split("_")):
                em.emit(fi, e.lineno,
                        f"raw encoding descriptor '{e.id}' in "
                        f"program-key material — FOR references and "
                        f"dictionary LUTs drift with appends, so key "
                        f"on the quantized codec-class token "
                        f"(codec_class()/codec_classes(); LUT shapes "
                        f"through lut_capacity()) or every descriptor "
                        f"drift mints a fresh compiled program")
                return
            for c in ast.iter_child_nodes(e):
                if isinstance(e, ast.Call) and c is e.func and \
                        isinstance(c, ast.Name):
                    continue   # callee name, not key material
                if isinstance(c, ast.expr):
                    walk(c, in_sorted, in_quant)
                elif isinstance(c, ast.comprehension):
                    walk(c.iter, in_sorted, in_quant)
                    for cond in c.ifs:
                        walk(cond, in_sorted, in_quant)

        # iter-bound flow: the name holds an ELEMENT, so iteration
        # order of the RHS does not transfer — start in sorted context
        walk(expr, via_iter, False)


# ===========================================================================
# result-key
# ===========================================================================
class ResultKeyPass:
    """Result-cache key discipline (exec/share.py, the GTS-versioned
    result cache).  An entry is servable to ANY later snapshot that
    covers its GTS, so every ``ResultCache.put`` key component must
    derive from the literal-masked signature, the literal vector, or
    the store-version/GTS tuple — the three inputs that exactly
    determine the result.  Positive-evidence detection (the repo
    convention): wall-clock / RNG / process-identity reads in the key
    flow defeat reuse (every put mints a fresh never-matching entry),
    and a raw row count keys the entry on what the result LOOKED like
    instead of what produced it — a post-DML table at the same
    cardinality would wrongly match."""

    rule = "result-key"

    def __init__(self, project: Project):
        self.project = project
        # every module-level name bound to a ResultCache() anywhere
        # (the ProgramKeyPass receiver convention)
        self.cache_names: set = set()
        for mi in project.modules.values():
            for st in mi.src.tree.body:
                if isinstance(st, ast.Assign) and \
                        isinstance(st.value, ast.Call):
                    f = st.value.func
                    nm = f.id if isinstance(f, ast.Name) else (
                        f.attr if isinstance(f, ast.Attribute) else None)
                    if nm == "ResultCache":
                        for t in st.targets:
                            if isinstance(t, ast.Name):
                                self.cache_names.add(t.id)

    def _is_cache_put(self, call) -> bool:
        f = call.func
        if not (isinstance(f, ast.Attribute) and f.attr == "put"
                and len(call.args) >= 2):
            return False
        owner = f.value
        name = owner.id if isinstance(owner, ast.Name) else (
            owner.attr if isinstance(owner, ast.Attribute) else None)
        return name in self.cache_names

    def run(self) -> list:
        em = _Emitter(self.rule)
        for mi in self.project.modules.values():
            for fi in mi.functions.values():
                for call in ast.walk(fi.node):
                    if isinstance(call, ast.Call) and \
                            self._is_cache_put(call):
                        self._check_put(mi, fi, call, em)
        return em.findings

    # one level into same-project callees feeding the key — the
    # resolution rules are ProgramCardinalityPass's, shared verbatim
    _callee = ProgramCardinalityPass._callee

    #: tokens that mark a value as coming from the producing snapshot
    _SNAP_TOKENS = frozenset({"snap", "snapshot", "gts", "snapshot_ts",
                              "snapshot_gts", "next_gts"})

    def _check_gts_tag(self, fi: FuncInfo, call, em: _Emitter):
        """The put's GTS tag (2nd positional arg) bounds which future
        snapshots the entry may serve — it must flow from the snapshot
        the result was PRODUCED under (``item.snap`` /
        ``gts.next_gts()``), not from a constant or an unrelated
        counter: a fabricated tag lets ``lookup``'s
        ``snapshot_gts >= tag`` gate hand tomorrow's rows to
        yesterday's snapshot."""
        toks: set = set()
        for e, _it in _flow_exprs(fi, call.args[1]):
            for n in ast.walk(e):
                if isinstance(n, ast.Name):
                    toks.add(n.id)
                elif isinstance(n, ast.Attribute):
                    toks.add(n.attr)
        if not toks & self._SNAP_TOKENS:
            em.emit(fi, call.lineno,
                    "result-cache GTS tag does not flow from the "
                    "producing snapshot (no snap/gts/next_gts "
                    "material in its flow) — a fabricated tag defeats "
                    "the lookup staleness gate")

    def _check_put(self, mi, fi: FuncInfo, call, em: _Emitter):
        self._check_gts_tag(fi, call, em)
        key_expr = call.args[0]
        sites = [(e, fi, mi) for e, _it in _flow_exprs(fi, key_expr)]
        seen_fns = {(fi.module, fi.qualname)}
        for e, _fi, _mi in list(sites):
            for n in ast.walk(e):
                if not isinstance(n, ast.Call):
                    continue
                tgt = self._callee(_mi, _fi, n)
                if tgt is None or (tgt.module, tgt.qualname) in seen_fns:
                    continue
                seen_fns.add((tgt.module, tgt.qualname))
                tmi = self.project.modules[tgt.module]
                for ret in _return_exprs(tgt):
                    sites.extend((x, tgt, tmi)
                                 for x, _it in _flow_exprs(tgt, ret))
        for e, efi, emi in sites:
            self._scan(e, efi, emi, em)

    def _scan(self, expr, fi: FuncInfo, mi, em: _Emitter):
        for n in ast.walk(expr):
            if not isinstance(n, ast.Call):
                continue
            d = _dotted(n.func, mi) or ""
            short = d.split(".")[-1]
            if d.startswith(_UNBOUNDED_PREFIXES) or \
                    d in _UNBOUNDED_CALLS:
                em.emit(fi, n.lineno,
                        f"{d}() in result-cache key material — wall "
                        f"clock / RNG / process identity never "
                        f"repeats, so every put mints an entry no "
                        f"lookup can match; key on the masked "
                        f"signature, literal vector, and "
                        f"store-version/GTS tuple only")
            elif short == "row_count":
                em.emit(fi, n.lineno,
                        "raw row count in result-cache key material — "
                        "it keys the entry on what the result looked "
                        "like, not what produced it: a post-DML table "
                        "at the same cardinality would wrongly match; "
                        "use the store-version tuple for exact "
                        "invalidation instead")
            elif short == "len" and n.args and any(
                    isinstance(x, ast.Name) and "row" in x.id.lower()
                    for x in ast.walk(n.args[0])):
                em.emit(fi, n.lineno,
                        "raw result size in result-cache key material "
                        "— len(rows) is a property of the answer, not "
                        "of the (signature, literals, store-version) "
                        "inputs that determine it; drop it from the "
                        "key")


# ===========================================================================
# retrace-risk
# ===========================================================================
class RetraceRiskPass:
    """Per-value program identity: the program still caches, but the
    key (or the jit signature) can never repeat — functionally a
    compile per call."""

    rule = "retrace-risk"

    def __init__(self, project: Project, closure: TracedClosure):
        self.project = project
        self.closure = closure
        self._pk = ProgramKeyPass(project)

    def run(self) -> list:
        em = _Emitter(self.rule)
        for mi in self.project.modules.values():
            for fi in mi.functions.values():
                for call in ast.walk(fi.node):
                    if isinstance(call, ast.Call) and \
                            self._pk._is_cache_put(call):
                        self._check_put(mi, fi, call, em)
        for fi in self.closure.functions():
            self._check_traced(fi, em)
        return em.findings

    # -- put-site checks ------------------------------------------------
    def _check_put(self, mi, fi: FuncInfo, call, em: _Emitter):
        assigns = _assign_exprs(fi.node)
        self._hashable(call.args[0], fi, mi, assigns, em, set())
        for e, _via_iter in _flow_exprs(fi, call.args[0]):
            self._scan_flow(e, fi, mi, assigns, em)

    def _hashable(self, e, fi, mi, assigns, em: _Emitter,
                  stack: set) -> None:
        """Flag key components that make ``put`` silently not cache
        (TypeError) or never match (fresh object identity)."""
        if isinstance(e, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                          ast.DictComp, ast.SetComp)):
            em.emit(fi, e.lineno,
                    "unhashable program-key component — "
                    "ProgramCache.put swallows the TypeError and "
                    "skips caching, so every call recompiles; wrap "
                    "in tuple(...)")
            return
        if isinstance(e, ast.GeneratorExp):
            em.emit(fi, e.lineno,
                    "generator object as a program-key component — "
                    "hashable by identity, fresh per call, the key "
                    "never matches; materialize with tuple(...)")
            return
        if isinstance(e, ast.Tuple):
            for x in e.elts:
                self._hashable(x, fi, mi, assigns, em, stack)
            return
        if isinstance(e, ast.BinOp):
            self._hashable(e.left, fi, mi, assigns, em, stack)
            self._hashable(e.right, fi, mi, assigns, em, stack)
            return
        if isinstance(e, ast.Call):
            d = _dotted(e.func, mi) or ""
            short = d.split(".")[-1]
            if short in _LIST_CALLS:
                em.emit(fi, e.lineno,
                        f"{short}(...) is a list — unhashable as a "
                        f"program-key component; wrap in tuple(...)")
            return  # other calls: unknown return, assume hashable
        if isinstance(e, ast.Name) and e.id not in stack:
            for rhs, via_iter in assigns.get(e.id, ()):
                if via_iter:
                    continue   # element of an iterable, not the list
                self._hashable(rhs, fi, mi, assigns, em,
                               stack | {e.id})

    def _scan_flow(self, e, fi, mi, assigns, em: _Emitter):
        pkg = self.project.package
        for n in ast.walk(e):
            if not isinstance(n, ast.Call):
                continue
            d = _dotted(n.func, mi) or ""
            short = d.split(".")[-1]
            if short == "id" and len(n.args) == 1 and \
                    isinstance(n.args[0], ast.Name):
                for rhs, via_iter in assigns.get(n.args[0].id, ()):
                    if via_iter:
                        continue   # id() of an element, not the list
                    fresh = isinstance(rhs, (ast.List, ast.Dict,
                                             ast.Set, ast.ListComp,
                                             ast.DictComp, ast.SetComp,
                                             ast.GeneratorExp)) or (
                        isinstance(rhs, ast.Call)
                        and (_dotted(rhs.func, mi) or ""
                             ).split(".")[-1] in _FRESH_CALLS)
                    if fresh:
                        em.emit(fi, n.lineno,
                                f"id() of the ephemeral local "
                                f"'{n.args[0].id}' in program-key "
                                f"material — a fresh object per call "
                                f"means the key never repeats")
                        break
            elif short in ("int", "float") and n.args and \
                    _producer_call(n.args[0], mi, pkg):
                em.emit(fi, n.lineno,
                        f"{short}() of a device value in program-key "
                        f"material — a per-value host read minting "
                        f"one compiled program per datum; quantize "
                        f"the value or mask it as a traced input")

    # -- traced-closure checks ------------------------------------------
    def _check_traced(self, fi: FuncInfo, em: _Emitter):
        mi = self.project.modules[fi.module]

        def shape_side(e) -> bool:
            for n in ast.walk(e):
                if isinstance(n, ast.Attribute) and n.attr == "shape":
                    return True
            return False

        def quantized(e) -> bool:
            for n in ast.walk(e):
                if isinstance(n, ast.Call):
                    d = (_dotted(n.func, mi) or "").split(".")[-1]
                    if d in _QUANT_FUNCS:
                        return True
            return False

        def const_side(e) -> bool:
            return all(isinstance(n, (ast.Constant, ast.UnaryOp,
                                      ast.BinOp, ast.operator,
                                      ast.unaryop, ast.expr_context))
                       for n in ast.walk(e))

        def check_test(test):
            if isinstance(test, ast.BoolOp):
                for v in test.values:
                    check_test(v)
                return
            if not isinstance(test, ast.Compare) or quantized(test):
                return
            sides = [test.left] + list(test.comparators)
            shapes = [s for s in sides if shape_side(s)]
            others = [s for s in sides if not shape_side(s)]
            if shapes and others and \
                    not all(const_side(o) for o in others):
                em.emit(fi, test.lineno,
                        "traced-code branch compares a raw .shape int "
                        "against a runtime value — program structure "
                        "specializes per value; quantize through "
                        "size_class()/next_pow2() first")

        for st in ast.walk(fi.node):
            if isinstance(st, (ast.If, ast.While)):
                if is_traced_guard_test(st.test) is None:
                    check_test(st.test)
            elif isinstance(st, ast.IfExp):
                if is_traced_guard_test(st.test) is None:
                    check_test(st.test)


# ===========================================================================
# device-residency
# ===========================================================================
class DeviceResidencyPass:
    """Device bytes must be visible to the budget.  Uploads happen in
    the staging layer (which accounts them via ``POOL.note_upload``);
    anything else parking device arrays — a stray ``jax.device_put``,
    a module-global holding kernel outputs — is residency the OOM
    ladder cannot evict."""

    rule = "device-residency"

    def __init__(self, project: Project):
        self.project = project
        pkg = project.package
        self.sanctioned_files = (f"{pkg}/storage/bufferpool.py",
                                 f"{pkg}/storage/batch.py",
                                 f"{pkg}/parallel/mesh.py")

    def _accounts(self, fi: FuncInfo) -> bool:
        for n in ast.walk(fi.node):
            if isinstance(n, ast.Call):
                f = n.func
                name = f.attr if isinstance(f, ast.Attribute) else (
                    f.id if isinstance(f, ast.Name) else None)
                if name == "note_upload":
                    return True
        return False

    def run(self) -> list:
        em = _Emitter(self.rule)
        for rel, mi in self.project.by_rel.items():
            norm = rel.replace(os.sep, "/")
            if norm in self.sanctioned_files:
                continue
            # cheap text pre-filter: only parse-walk modules that can
            # possibly trip either check
            has_put = "device_put" in mi.src.text
            if not has_put and not mi.containers:
                continue
            if has_put:
                for fi in mi.functions.values():
                    if self._accounts(fi):
                        continue
                    self._check_fn(mi, fi, em)
            if mi.containers:
                self._check_globals(mi, em)
        return em.findings

    def _check_fn(self, mi, fi: FuncInfo, em: _Emitter):
        for n in ast.walk(fi.node):
            if isinstance(n, ast.Call):
                d = _dotted(n.func, mi) or ""
                if d == "jax.device_put":
                    em.emit(fi, n.lineno,
                            "jax.device_put outside the bufferpool "
                            "staging layer — these bytes are invisible "
                            "to OTB_DEVICE_CACHE_BYTES and to "
                            "shed_coldest; stage through the pool")

    def _check_globals(self, mi, em: _Emitter):
        """Device-produced values stored into module-level containers:
        long-lived residency with no pool accounting."""
        pkg = self.project.package
        for fi in mi.functions.values():
            for st in ast.walk(fi.node):
                target = None
                value = None
                if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                        and isinstance(st.targets[0], ast.Subscript):
                    target, value = st.targets[0].value, st.value
                elif isinstance(st, ast.Call) and \
                        isinstance(st.func, ast.Attribute) and \
                        st.func.attr in ("append", "add", "update",
                                         "setdefault", "insert"):
                    target = st.func.value
                    value = ast.Tuple(elts=list(st.args), ctx=None) \
                        if st.args else None
                if not isinstance(target, ast.Name) or value is None:
                    continue
                if target.id not in mi.containers:
                    continue
                if _producer_call(value, mi, pkg):
                    em.emit(fi, st.lineno,
                            f"device-produced value stored into "
                            f"module-level '{target.id}' — untracked "
                            f"device residency outside the bufferpool "
                            f"(OTB_DEVICE_CACHE_BYTES cannot see it)")


# ===========================================================================
# transfer-discipline
# ===========================================================================
class TransferDisciplinePass:
    """Host pulls in EAGER engine code (HostSyncPass owns the traced
    closure).  Every ``jax.device_get``, ``np.asarray``-of-device-data,
    ``.tolist()``, ``.item()`` in the device-hot trees must sit inside
    a function declared ``# otblint: sync-boundary`` — the complete,
    greppable inventory of where the engine is allowed to wait on the
    device."""

    rule = "transfer-discipline"

    def __init__(self, project: Project, closure: TracedClosure):
        self.project = project
        self.closure = closure
        pkg = project.package
        self.scope = (f"{pkg}/exec/", f"{pkg}/storage/",
                      f"{pkg}/parallel/", f"{pkg}/ops/")

    _SINK_TEXT = ("device_get", "asarray", "block_until_ready",
                  ".tolist", ".item")

    def run(self) -> list:
        em = _Emitter(self.rule)
        for rel, mi in self.project.by_rel.items():
            if not rel.replace(os.sep, "/").startswith(self.scope):
                continue
            # cheap text pre-filter: a module with no sink spelling
            # anywhere cannot produce a finding
            if not any(s in mi.src.text for s in self._SINK_TEXT):
                continue
            for fi in mi.functions.values():
                if (fi.module, fi.qualname) in self.closure.reachable:
                    continue   # HostSyncPass territory
                if fi.sync_boundary or _fn_disabled(fi, self.rule):
                    continue
                self._check_fn(mi, fi, em)
        return em.findings

    def _check_fn(self, mi, fi: FuncInfo, em: _Emitter):
        pkg = self.project.package
        tainted: set = set()

        def is_producer(call) -> bool:
            d = _dotted(call.func, mi) or ""
            if d in ("jax.devices", "jax.local_devices",
                     "jax.device_count"):
                return False   # device HANDLES, not device data
            return (d.startswith("jax.") or d == "jax"
                    or d.startswith(f"{pkg}.ops.kernels."))

        def taint(e) -> bool:
            if isinstance(e, ast.Name):
                return e.id in tainted
            if isinstance(e, ast.Attribute):
                return False   # attr reads: unknown provenance
            if isinstance(e, ast.Subscript):
                return taint(e.value)
            if isinstance(e, ast.Call):
                if is_producer(e):
                    return True
                return any(taint(x) for x in e.args)
            if isinstance(e, (ast.BinOp,)):
                return taint(e.left) or taint(e.right)
            if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
                return any(taint(x) for x in e.elts)
            if isinstance(e, ast.IfExp):
                return taint(e.body) or taint(e.orelse)
            return False

        def note_assign(st):
            v = st.value if hasattr(st, "value") else None
            if v is None:
                return
            is_t = taint(v)
            targets = st.targets if isinstance(st, ast.Assign) \
                else [st.target]
            for t in targets:
                if isinstance(t, ast.Name):
                    if is_t:
                        tainted.add(t.id)
                    else:
                        tainted.discard(t.id)

        def check_call(n):
            d = _dotted(n.func, mi) or ""
            short = d.split(".")[-1]
            if d == "jax.device_get":
                em.emit(fi, n.lineno,
                        "jax.device_get in eager engine code outside "
                        "a declared sync boundary — mark the function "
                        "'# otblint: sync-boundary' if this is a "
                        "sanctioned materialization point")
            elif d.startswith("numpy.") and \
                    short in ("asarray", "array", "copy") and n.args:
                a0 = n.args[0]
                direct_get = isinstance(a0, ast.Call) and \
                    (_dotted(a0.func, mi) or "") == "jax.device_get"
                if taint(a0) and not direct_get:
                    em.emit(fi, n.lineno,
                            f"np.{short}() pulls device data to the "
                            f"host outside a declared sync boundary")
            elif isinstance(n.func, ast.Attribute) and \
                    n.func.attr in ("tolist", "item") and \
                    taint(n.func.value):
                em.emit(fi, n.lineno,
                        f".{n.func.attr}() pulls device data to the "
                        f"host outside a declared sync boundary")

        # two passes over the body: taint fixpoint, then sinks — cheap
        # and order-insensitive for the straight-line staging helpers
        # this pass audits
        for _ in range(2):
            for st in ast.walk(fi.node):
                if isinstance(st, (ast.Assign, ast.AnnAssign,
                                   ast.AugAssign)):
                    note_assign(st)
        for n in ast.walk(fi.node):
            if isinstance(n, ast.Call):
                check_call(n)


# ===========================================================================
# retrace-witness
# ===========================================================================
def is_ladder_int(v) -> bool:
    """True when v is a legal size/factor class: pow2 (join factors,
    batch classes, exchange multipliers) or quarter-step
    {4,5,6,7}*2^k (staged-table size classes) — equivalently, at most
    3 significant bits."""
    if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
        return False
    bl = v.bit_length()
    if bl <= 3:
        return True
    return (v >> (bl - 3)) << (bl - 3) == v


_CODEC_FAMS = frozenset({"pack8", "pack16", "pack32",
                         "for8", "for16", "for32"})


def _codec_class_ok(tok) -> bool:
    """A witnessed codec class must be one of the quantized tokens
    storage/codec.py codec_class() can mint — raw, a family+width from
    the fixed enum, or dictN with a pow2 LUT capacity.  Anything else
    in a "codec:" census dimension means a raw encoding descriptor
    leaked into a program key."""
    if not isinstance(tok, str):
        return False
    if tok == "raw" or tok in _CODEC_FAMS:
        return True
    if tok.startswith(("pack32/", "for32/")):
        # a 32-bit class with its proven code limit (codec._proven_limit):
        # one of batch.size_class's quarter steps
        limit = tok.partition("/")[2]
        return limit.isdigit() and 1 << 16 <= int(limit) < 1 << 32 \
            and is_ladder_int(int(limit))
    if tok.startswith("dict"):
        base, _, cap = tok.partition("/")
        if base not in ("dict8", "dict16") or not cap.isdigit():
            return False
        c = int(cap)
        return c >= 16 and (c & (c - 1)) == 0
    return False


def check_census(data) -> list:
    """Validate a program-census dict against the static ladder
    predictions; returns human-readable violation strings.  Shared by
    RetraceWitnessPass and the tier-1 witness test."""
    out: list = []
    entries = data.get("entries", [])
    if not isinstance(entries, list):
        return ["malformed census: 'entries' is not a list"]
    combos: dict = {}
    for ent in entries:
        if not isinstance(ent, dict):
            out.append(f"malformed census entry: {ent!r}")
            continue
        tier = ent.get("tier", "?")
        kfp = ent.get("key", "?")
        for cls in ent.get("classes", []):
            if not (isinstance(cls, (list, tuple)) and len(cls) == 2):
                out.append(f"{tier}/{kfp}: malformed class {cls!r}")
                continue
            dim, v = cls
            if str(dim).startswith("codec:"):
                if not _codec_class_ok(v):
                    out.append(
                        f"{tier}/{kfp}: witnessed codec class {v!r} "
                        f"for {dim} is not a quantized codec-class "
                        f"token — a raw encoding descriptor (FOR "
                        f"reference / dict LUT) reached a program key")
            elif not is_ladder_int(v):
                out.append(
                    f"{tier}/{kfp}: witnessed {dim} class {v!r} is "
                    f"not ladder-shaped (pow2 or quarter-step) — an "
                    f"unquantized value reached a program key")
            elif str(dim).startswith("factor") and v > _FACTOR_CAP:
                out.append(
                    f"{tier}/{kfp}: witnessed join factor {v} exceeds "
                    f"the {_FACTOR_CAP} ladder cap — the exhaustion "
                    f"fallback did not fire")
        puts = ent.get("puts", 1)
        if isinstance(puts, int) and puts > 1:
            out.append(
                f"{tier}/{kfp}: program signature compiled {puts} "
                f"times without an eviction — an unexplained retrace")
        frag = ent.get("frag")
        if frag is not None:
            combos[(tier, frag)] = combos.get((tier, frag), 0) + 1
    for (tier, frag), n in sorted(combos.items()):
        if n > _STORM_LIMIT:
            out.append(
                f"{tier}/{frag}: {n} class combinations for one "
                f"fragment signature (> {_STORM_LIMIT}) — compile "
                f"storm")
    return out


class RetraceWitnessPass:
    """Cross-check the runtime program census (OTB_TRACECHECK=1,
    exec/plancache.py) against the static ladder predictions."""

    rule = "retrace-witness"

    def __init__(self, project: Project):
        self.project = project

    def run(self) -> list:
        path = os.path.join(self.project.root, self.project.package,
                            "analysis", "program_census.json")
        if not os.path.exists(path):
            return []
        rel = os.path.relpath(path, self.project.root).replace(
            os.sep, "/")
        try:
            with open(path, encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            return [Finding(self.rule, rel, 1, "",
                            f"unreadable program census: {e}")]
        return [Finding(self.rule, rel, 1, "", msg)
                for msg in check_census(data)]
