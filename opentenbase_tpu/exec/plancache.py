"""Compiled-program subsystem: one cache for every execution tier.

Reference analog: CachedPlanSource (utils/cache/plancache.c) generalized
to the thing that actually costs seconds here — compiled XLA programs.
The round-5 ladder paid 11-12s of XLA compile against <1s of engine
time per cold mesh query, and an unmanaged live-executable population
segfaulted XLA:CPU at a few hundred programs.  Its pieces:

1. ProgramCache — a bounded LRU of live compiled programs, shared by
   the fused tier (exec/fused.py) and the mesh tier (exec/mesh_exec.py),
   with a GLOBAL live-executable budget (OTB_MAX_LIVE_PROGRAMS):
   eviction calls PjitFunction.clear_cache() so the XLA executable is
   actually released, deterministically, instead of the old
   "drop every cache every 25 tests" workaround in the TPC-DS suite.
   Keys are canonical fragment signatures: literal-masked plan
   structure + dtype tuple + size-class bucket (the pow2/quarter-step
   classes of storage/batch.py), so `WHERE k <= X` with a different
   constant — or the same fragment over a different-but-same-size-class
   batch — reuses the compiled executable.

2. Persistent compilation cache — enable_persistent_cache(), called
   by the entry points (ctl, chip_smoke.py, benchmarks/), keeps compiled
   programs where $JAX_COMPILATION_CACHE_DIR says, else at one fixed
   path inside the checkout, so process restarts, `ctl start`, and
   repeated runs skip the XLA compile entirely.

3. AOT warmup — warm_async() runs lower-and-compile jobs on a
   background daemon thread, off the query path: PREPARE warms its
   mesh program (dist_session._warm_prepared), cluster start re-stages
   recovered tables (parallel/cluster.py), aot_compile() does
   jit(...).lower(args).compile() without executing.

4. Telemetry — per-tier hit/miss/compile/compile_ms/eviction counters
   surfaced by the otb_plancache stat view (parallel/statviews.py).

5. Ladder — the learned size classes of a program shape (join and
   aggregate factors, exchange multipliers, gather classes): one
   bounded, locked map a tier and the one rule by which a class grows
   on overflow.

6. Retrace sanitizer — OTB_TRACECHECK=1 records every jit-tier put's
   quantized class components (join factors, size classes, batch
   classes) into a program census; save_census() merges it into
   analysis/program_census.json, where the retrace-witness lint pass
   cross-checks witnessed compiles against the static ladder
   predictions (analysis/cardinality.py).

The exact-statement plan cache (get_or_build, used by both sessions)
keeps its holder-attached storage but now feeds the same counters.
Mutation stays defensive: sessions on a CN server share these caches
across handler threads, so races must never fail a query.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import queue
import threading
import time
from typing import Optional

from ..obs import trace as obs_trace
from ..sql.fingerprint import fingerprint, struct_key
from ..utils import locks

_LOCK = locks.RLock("exec.plancache._LOCK")
_SEQ = itertools.count()
_REGISTRY: list = []   # guarded_by: _LOCK  (jit caches under the budget)


def _live_budget() -> int:
    """Global cap on live compiled executables across all program
    tiers — set below the population where XLA:CPU's jit compiler was
    observed to segfault (a few hundred; round 5 hit it at ~66% of the
    TPC-DS suite)."""
    try:
        return int(os.environ.get("OTB_MAX_LIVE_PROGRAMS", "224"))
    except ValueError:
        return 224


def _fn_live(fn) -> int:
    """Live executables held by a jitted function (0 for tombstones)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return 1 if fn is not None else 0


def _entry_fns(value):
    """Jitted functions inside a cache value ((fn, meta) tuples or a
    bare fn); tolerant of None tombstones."""
    vals = value if isinstance(value, (tuple, list)) else (value,)
    return [v for v in vals if hasattr(v, "clear_cache")]


def _release(value) -> None:
    """Drop the XLA executables a cache value holds."""
    for fn in _entry_fns(value):
        try:
            fn.clear_cache()
        except Exception:
            pass


class ProgramCache:
    """Bounded LRU keyed by canonical fragment signature.  `jit=True`
    caches hold compiled programs and participate in the global
    live-executable budget; `jit=False` caches (plan/template tiers)
    only bound entry count and feed counters."""

    def __init__(self, name: str, max_entries: int, jit: bool = True):
        self.name = name
        self.max_entries = max_entries
        self.jit = jit
        self._d: dict = {}            # key -> [seq, value]
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        self.compile_ms = 0.0
        self.evictions = 0
        with _LOCK:
            if jit:
                _REGISTRY.append(self)

    # -- lookup / insert ------------------------------------------------
    def get(self, key):
        with _LOCK:
            ent = self._d.get(key)
            if ent is None:
                self.misses += 1
            else:
                ent[0] = next(_SEQ)
                self.hits += 1
        if obs_trace.ENABLED:
            obs_trace.event("program", tier=self.name,
                            hit=ent is not None)
        return None if ent is None else ent[1]

    def peek(self, key):
        """Lookup that refreshes LRU order but defers hit/miss
        accounting to count() — for callers whose hit criterion is
        richer than key presence (generation-checked entries)."""
        with _LOCK:
            ent = self._d.get(key)
            if ent is None:
                return None
            ent[0] = next(_SEQ)
            return ent[1]

    def count(self, hit: bool):
        with _LOCK:
            if hit:
                self.hits += 1
            else:
                self.misses += 1

    def put(self, key, value):
        with _LOCK:
            try:
                self._d[key] = [next(_SEQ), value]
            except TypeError:
                return value          # unhashable key: just don't cache
            if self.jit and tracecheck_enabled():
                _census_note(self, key)
            while len(self._d) > self.max_entries:
                self._evict_lru()
        if self.jit:
            trim_live()
        return value

    def replace(self, key, value):
        """Swap a value in place (permanent-fallback tombstones) without
        touching LRU order or eviction."""
        with _LOCK:
            ent = self._d.get(key)
            if ent is not None:
                _release(ent[1])
                ent[1] = value
                if self.jit and tracecheck_enabled():
                    _census_forget(self, key)

    def pop(self, key):
        with _LOCK:
            ent = self._d.pop(key, None)
            if ent is not None and self.jit and tracecheck_enabled():
                _census_forget(self, key)
        if ent is not None:
            _release(ent[1])

    # -- accounting -----------------------------------------------------
    def note_compile(self, n: int = 1, ms: float = 0.0):
        with _LOCK:
            self.compiles += n
            self.compile_ms += ms

    def record_call(self, fn, t0: float):
        """Post-execution compile detection: a grown per-fn cache means
        this call traced+compiled (a new shape/dtype bucket); attribute
        the call's wall time to compile_ms and re-check the budget.
        Every call of a compiled program passes here once: it counts as
        one of the open span's `calls`."""
        obs_trace.count(calls=1)
        after = _fn_live(fn)
        before = getattr(fn, "_otb_seen", 0)
        if after > before:
            dt = (time.perf_counter() - t0) * 1e3
            self.note_compile(after - before, dt)
            obs_trace.event("compile", tier=self.name, ms=round(dt, 3))
            try:
                fn._otb_seen = after
            except Exception:
                pass
            trim_live()

    def live(self) -> int:
        with _LOCK:
            return sum(_fn_live(fn) for _s, v in self._d.values()
                       for fn in _entry_fns(v))

    def __len__(self):
        return len(self._d)

    def clear(self):
        with _LOCK:
            keys = list(self._d)
        for k in keys:
            self.pop(k)

    # -- eviction -------------------------------------------------------
    def _evict_lru(self):
        # caller holds _LOCK
        if not self._d:
            return
        key = min(self._d, key=lambda k: self._d[k][0])
        _s, value = self._d.pop(key)
        self.evictions += 1
        if self.jit and tracecheck_enabled():
            _census_forget(self, key)
        _release(value)


def trim_live():
    """Enforce the global live-executable budget: evict globally-LRU
    entries (across every jit cache) that actually hold executables
    until the population fits.  Deterministic, targeted — replaces the
    conftest hack of dropping every cache every N tests."""
    budget = _live_budget()
    with _LOCK:
        for _ in range(4096):
            total = sum(c.live() for c in _REGISTRY)
            if total <= budget:
                return
            best = None
            for c in _REGISTRY:
                for k, (seq, v) in c._d.items():
                    if not any(_fn_live(fn) for fn in _entry_fns(v)):
                        continue
                    if best is None or seq < best[0]:
                        best = (seq, c, k)
            if best is None:
                return
            _seq, c, k = best
            _s, value = c._d.pop(k)
            c.evictions += 1
            if tracecheck_enabled():
                _census_forget(c, k)   # _REGISTRY holds jit caches only
            _release(value)


class Ladder:
    """The learned size classes of compiled program shapes: ONE bounded,
    insertion-ordered, locked map from a shape's key (a program's key
    less its classes) to the class maps a statement of that shape ended
    on, so that the next one starts there instead of replaying the
    overflow walk.  Each compiled tier holds one (exec/fused.py one a
    process: `{slot: factor}` of its traced joins and laddered sorted
    aggregates; a MeshRunner its own: those factors, the exchanges'
    bucket multipliers and the gathers' classes) and the growth rule
    lives here alone: a program reports what overflowed, its caller
    takes each such slot one step up with `grow` and calls again, at
    most `ATTEMPTS` times a statement."""

    CAP = 4096        # a factor past this: the ladder is exhausted
    ATTEMPTS = 24     # program calls a statement may spend climbing

    def __init__(self, max_entries: int):
        self.max_entries = max_entries
        self._lock = locks.Lock("exec.plancache.Ladder._lock")
        self._d: dict = {}            # guarded_by: _lock

    def recall(self, key) -> Optional[tuple]:
        """Copies of the class maps remembered under `key`, else None."""
        with self._lock:
            maps = self._d.get(key)
        return None if maps is None else tuple(dict(m) for m in maps)

    def remember(self, key, *maps) -> None:
        """Keep copies of `maps` under `key`; past the bound the oldest
        key goes, one at a time (a key learned again keeps its place)."""
        with self._lock:
            self._d[key] = tuple(dict(m) for m in maps)
            while len(self._d) > self.max_entries:
                self._d.pop(next(iter(self._d)))

    def snapshot(self) -> dict:
        """`{key: class maps}` as of now, oldest first (tests, views)."""
        with self._lock:
            return dict(self._d)

    def __len__(self):
        return len(self._d)

    @classmethod
    def grow(cls, classes: dict, slot, have=None, need=None) -> bool:
        """Take `classes[slot]` (1 where absent) one step up, in place.
        Where the program reported the rows it needed (`need`, against
        the `have` its class held: the fused tier) the step is the
        power of two that fits, so ONE retrace and no walk of compiles;
        where it reported a bit (the mesh tier's psum of overflow
        flags) the class doubles.  False where it has passed `CAP`: a
        factor's caller gives up there (an exchange's multiplier and a
        gather's class are bounded by what their source holds, so their
        caller does not ask)."""
        mult = 2
        if need is not None:
            mult = 1
            while have * mult < need:
                mult *= 2
        classes[slot] = classes.get(slot, 1) * mult
        return classes[slot] <= cls.CAP


# ---------------------------------------------------------------------------
# retrace sanitizer (OTB_TRACECHECK=1): per-program compile census
# ---------------------------------------------------------------------------
_CENSUS: dict = {}        # guarded_by: _LOCK  (tier, frag, key) -> entry
_CENSUS_ATEXIT = [False]  # guarded_by: _LOCK


def tracecheck_enabled() -> bool:
    """OTB_TRACECHECK=1 arms the retrace sanitizer: every jit-tier
    ``put`` records its signature's quantized class components so the
    lint gate can cross-check witnessed compiles against the static
    ladder predictions (analysis/cardinality.py, retrace-witness) —
    the lock-witness pattern of utils/locks.py applied to program
    cardinality.  Read at use time, not import, so subprocess tests
    can flip it."""
    return os.environ.get("OTB_TRACECHECK", "").strip().lower() \
        in ("1", "on", "true", "yes")


def _census_classes(tier: str, key):
    """Split a program key into (classes, frag_key): the quantized
    size/factor components — each must be ladder-shaped — and the key
    with those positions masked out (the fragment signature whose
    class combinations share one compile budget).  Returns None for
    key shapes this extractor does not recognize."""
    if tier == "fused" and isinstance(key, tuple) and len(key) >= 6:
        # base_key(5) [+ ("__batch", class) | ("__morsel", class)]
        # + sorted factor items
        classes, tail = [], []
        for part in key[5:]:
            if (isinstance(part, tuple) and len(part) == 2
                    and part[0] == "__batch"):
                classes.append(("batch", part[1]))
                tail.append(("__batch", "*"))
            elif (isinstance(part, tuple) and len(part) == 2
                    and part[0] == "__morsel"):
                # the chunk-size class of a morsel stream — quantized
                # by storage/batch.py chunk_class, so the witness gate
                # can hold it to the ladder like any batch class
                classes.append(("chunk", part[1]))
                tail.append(("__morsel", "*"))
            elif isinstance(part, tuple):
                for it in part:
                    if isinstance(it, tuple) and len(it) == 2:
                        classes.append((f"factor:{it[0]}", it[1]))
                tail.append("*")
            else:
                tail.append(part)
        # table_sig (key[1]) carries store id()s and per-snapshot dict
        # sizes — execution environment, not fragment identity.  The
        # codec classes riding in it ARE witness material though: pull
        # them out first so the gate can hold encoding drift to the
        # quantized token enum (codec ladder promotions must mint
        # class-shaped keys, never raw-descriptor keys).
        for el in key[1]:
            if isinstance(el, tuple) and len(el) >= 4 \
                    and isinstance(el[3], tuple):
                for it in el[3]:
                    if isinstance(it, tuple) and len(it) == 2:
                        classes.append(
                            (f"codec:{el[0]}.{it[0]}", it[1]))
        frag = (key[0], "*", key[2], key[3], key[4]) + tuple(tail)
        return classes, frag
    if tier == "mesh" and isinstance(key, tuple) and len(key) == 9:
        # (runner_id, frags, exchanges, tables, factors, mults,
        #  gathers, baked, traced-types) — see mesh_exec.prog_key
        classes, tabs = [], []
        for el in key[3]:     # (table, padded, dicts, arrs, codecs)
            classes.append((f"pad:{el[0]}", el[1]))
            if len(el) >= 5 and isinstance(el[4], tuple):
                for it in el[4]:
                    if isinstance(it, tuple) and len(it) == 2:
                        classes.append(
                            (f"codec:{el[0]}.{it[0]}", it[1]))
            tabs.append((el[0], "*", el[2], el[3]))
        for label, part in (("factor", key[4]), ("mult", key[5]),
                            ("gather", key[6])):
            for k, v in part:
                classes.append((f"{label}:{k}", v))
        frag = ("*", key[1], key[2], tuple(tabs), "*", "*", "*",
                key[7], key[8])
        return classes, frag
    return None


def _census_note(cache: "ProgramCache", key) -> None:  # holds: _LOCK
    # the sanitizer must never fail a query
    try:
        split = _census_classes(cache.name, key)
        if split is None:
            classes, frag_fp = [], "?"
        else:
            classes, frag_fp = split[0], struct_key(split[1])
        kfp = struct_key(key)
        ent = _CENSUS.get((cache.name, frag_fp, kfp))
        if ent is None:
            _CENSUS[(cache.name, frag_fp, kfp)] = {
                "tier": cache.name, "frag": frag_fp, "key": kfp,
                "classes": [[d, v] for d, v in classes], "puts": 1}
        else:
            ent["puts"] += 1
        _census_arm_atexit()
    except Exception:
        pass


def _census_forget(cache: "ProgramCache", key) -> None:  # holds: _LOCK
    # an evicted program's later re-put is a legitimate recompile, not
    # a retrace — drop its census entry
    try:
        split = _census_classes(cache.name, key)
        frag_fp = "?" if split is None else struct_key(split[1])
        _CENSUS.pop((cache.name, frag_fp, struct_key(key)), None)
    except Exception:
        pass


def _census_arm_atexit() -> None:  # holds: _LOCK
    if _CENSUS_ATEXIT[0]:
        return
    _CENSUS_ATEXIT[0] = True
    if os.environ.get("OTB_TRACECHECK_REPORT", "").strip() or \
            os.environ.get("OTB_TRACECHECK_PERSIST", "").strip():
        atexit.register(save_census)


def census() -> list:
    """This process's witnessed program census entries (copies)."""
    with _LOCK:
        return [dict(e) for e in _CENSUS.values()]


def reset_census() -> None:
    with _LOCK:
        _CENSUS.clear()


def default_census_path() -> str:
    env = os.environ.get("OTB_TRACECHECK_REPORT", "").strip()
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(pkg, "analysis", "program_census.json")


def save_census(path: Optional[str] = None) -> dict:
    """Merge this process's program census into the report file (max
    puts per signature survives across shards/processes); the static
    pass cross-checks every witnessed class against the ladder
    predictions (analysis/cardinality.py, retrace-witness)."""
    path = path or default_census_path()
    merged = {(e["tier"], e["frag"], e["key"]): dict(e)
              for e in census()}
    try:
        with open(path, encoding="utf-8") as f:
            prior = json.load(f)
        for e in prior.get("entries", []):
            k = (e.get("tier"), e.get("frag"), e.get("key"))
            cur = merged.get(k)
            if cur is None:
                merged[k] = e
            else:
                cur["puts"] = max(cur.get("puts", 1),
                                  e.get("puts", 1))
    except (OSError, ValueError):
        pass
    data = {
        "comment": "program compile census (OTB_TRACECHECK=1 runs); "
                   "every witnessed class must be ladder-shaped and "
                   "every live signature must compile exactly once — "
                   "see analysis/cardinality.py (retrace-witness)",
        "entries": sorted(merged.values(),
                          key=lambda e: (str(e.get("tier")),
                                         str(e.get("frag")),
                                         str(e.get("key")))),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


# ---------------------------------------------------------------------------
# tier singletons
# ---------------------------------------------------------------------------
FUSED = ProgramCache("fused", max_entries=192)
MESH = ProgramCache("mesh", max_entries=128)
PLAN = ProgramCache("plan", max_entries=256, jit=False)
AUTOPREP = ProgramCache("autoprep", max_entries=256, jit=False)


def stats() -> list:
    """Per-tier counters for the otb_plancache view:
    (tier, hits, misses, compiles, compile_ms, evictions, live)."""
    out = []
    for c in (FUSED, MESH, PLAN, AUTOPREP):
        live = c.live() if c.jit else len(c)
        out.append((c.name, c.hits, c.misses, c.compiles,
                    round(c.compile_ms, 3), c.evictions, live))
    return out


def _metrics_samples():
    """Registry collector: the plancache counters as labeled samples
    (obs/metrics.py — the unified pane behind otb_metrics and the
    Prometheus exposition)."""
    for tier, hits, misses, compiles, compile_ms, ev, live in stats():
        lbl = {"tier": tier}
        yield ("otb_plancache_hits", lbl, hits)
        yield ("otb_plancache_misses", lbl, misses)
        yield ("otb_plancache_compiles", lbl, compiles)
        yield ("otb_plancache_compile_ms", lbl, compile_ms)
        yield ("otb_plancache_evictions", lbl, ev)
        yield ("otb_plancache_live", lbl, live)


from ..obs.metrics import REGISTRY as _METRICS  # noqa: E402
_METRICS.register_collector("plancache", _metrics_samples)


# ---------------------------------------------------------------------------
# persistent XLA compilation cache
# ---------------------------------------------------------------------------
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Arm jax's persistent compilation cache so XLA compiles survive
    process restarts; returns the directory in force.  Called by the
    entry points (ctl, chip_smoke.py, benchmarks/), never as a side effect
    of opening a session or a cluster.

    Where $JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and no
    directory is set here; otherwise the cache lives at one fixed,
    git-ignored path inside the checkout: a directory that moves between
    runs (a datadir, a temp name) is a cache that never hits."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = _REPO_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # default thresholds skip sub-second/small programs — exactly the
    # fragment programs this engine compiles by the hundreds
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    return path


def persistent_cache_dir() -> Optional[str]:
    """The compilation-cache directory jax has in force (None = off)."""
    import jax
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# AOT warmup (background, off the query path)
# ---------------------------------------------------------------------------
_warm_q: "queue.Queue" = queue.Queue()
_warm_thread: Optional[threading.Thread] = None


def _warm_loop():
    while True:
        # warmup daemon idle dequeue, not a query-visible stall
        job = _warm_q.get()  # otblint: disable=wait-discipline
        try:
            job()
        except Exception:
            pass          # warmup must never surface errors
        finally:
            _warm_q.task_done()


def warm_async(job) -> None:
    """Run `job` (a no-arg callable that compiles something) on the
    warmup daemon thread."""
    global _warm_thread
    with _LOCK:
        if _warm_thread is None or not _warm_thread.is_alive():
            _warm_thread = threading.Thread(
                target=_warm_loop, daemon=True, name="plancache-warm")
            _warm_thread.start()
    _warm_q.put(job)


def warm_drain(timeout: float = 60.0) -> bool:
    """Block until queued warmup jobs finish (tests)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if _warm_q.unfinished_tasks == 0:
            return True
        time.sleep(0.01)
    return False


def aot_compile(fn, *args) -> bool:
    """jit(...).lower(args).compile() without executing: populates the
    persistent XLA cache so a later call of the same program skips the
    XLA compile (args may be jax.ShapeDtypeStructs — no data needed).
    Warm paths that hold REAL staged arrays prefer running the jitted
    fn once instead, which also fills its dispatch cache."""
    try:
        fn.lower(*args).compile()
        return True
    except Exception:
        return False


# ---------------------------------------------------------------------------
# exact-statement plan cache (the CachedPlanSource generic-plan arm)
# ---------------------------------------------------------------------------
_MAX = 256


def get_or_build(holder, attr: str, stmt, gen, build,
                 cacheable=lambda obj: True):
    """Return the cached object for (stmt, gen) on `holder.attr`, or
    build, insert, and return it.  Keyed by the EXACT statement
    (literals included, sql/fingerprint.py unmasked mode) plus a
    generation tuple covering DDL, stats, and the GUCs that shape
    planning.  `build()` runs at most once per call; uncacheable
    statements/objects just build (e.g. FQS/gidx plans, whose target
    node was chosen from DATA at plan time).  Feeds the PLAN tier's
    hit/miss counters (otb_plancache)."""
    cache = getattr(holder, attr, None)
    if cache is None:
        cache = {}
        setattr(holder, attr, cache)
    try:
        fp = fingerprint(stmt, mask_literals=False)
    except Exception:
        return build()
    hit = cache.get(fp)
    if hit is not None and hit[0] == gen:
        with _LOCK:
            PLAN.hits += 1
        return hit[1]
    with _LOCK:
        PLAN.misses += 1
    obj = build()
    if obj is None or not cacheable(obj):
        return obj
    try:
        cache[fp] = (gen, obj)
        while len(cache) > _MAX:
            cache.pop(next(iter(cache)))
    except (KeyError, RuntimeError):
        pass      # concurrent evictors raced; the cache stays bounded
    return obj
