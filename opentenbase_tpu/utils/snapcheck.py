"""Runtime snapshot sanitizer — serve-point witnessing + SI history.

Reference analog: PostgreSQL's visibility checks (tqual.c /
HeapTupleSatisfiesMVCC): every tuple read re-derives visibility from
the snapshot, so a wrong answer is impossible by construction.  Our
reproduction serves reads from version-sensitive FAST PATHS that
bypass the tuple-at-a-time check — the GTS-versioned result cache,
shared morsel streams, replica routing, hot standbys, and version-
keyed bufferpool entries — each guarded by a hand-written
``snapshot_gts >= tag`` / store-version comparison.  This module is
the runtime half of the otbsnap trilogy (static half:
``analysis/visibility.py``):

- **serve witnessing** — under ``OTB_SNAPCHECK=1`` every serve point
  calls :func:`serve` with its canonical name (the same dotted name
  the static visibility pass derives), the reader's snapshot GTS, the
  served entry's tag GTS, and the per-table version tuple.  Three
  invariants are asserted LIVE:

  * ``tag <= snapshot`` — a cached result produced at GTS t is never
    served to a snapshot older than t (stale-serve);
  * exact version match — the entry's captured store-version tuple
    equals the live one (version-mismatch);
  * per-session monotone reads — a session never observes a table at
    a version OLDER than one it already observed (monotone-violation),
    and its snapshot GTS never regresses.

- **witness persistence** — at exit (or :func:`save_report`) the
  witnessed serve-point set is merged into
  ``analysis/visibility_witness.json``; the lint gate cross-checks
  that every witnessed point is a member of the STATICALLY-GATED set
  (``# snapshot-gate:`` / ``# version-gate:`` contracts), so a new
  runtime serve path that skips annotation fails CI.

- **SI history** — with ``$OTB_SNAP_HISTORY`` set to a path, reads
  (with source = primary/cache/replica/shared/pool/standby) and
  commits (write sets with commit GTS) append to a bounded in-memory
  history; :func:`save_history` writes it for the post-hoc Adya-style
  G1/G-SI checker (``analysis/sicheck.py``), which certifies the
  serving tiers against the commits they raced
  (tests/test_visibility.py runs it over a concurrent workload).

Fast path: the flag is ONE env read per serve (``enabled()``), and
every hook site guards with ``if snapcheck.enabled():`` so argument
construction is never paid when off — tests/test_visibility.py bounds
the OFF-path cost at <3% of a point-op p50.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
from typing import Optional

__all__ = ["enabled", "history_on", "serve", "note_read", "note_write",
           "witness", "violations", "history_events", "reset",
           "save_report", "save_history", "default_report_path"]

#: bounded history: beyond this many events, appends are counted but
#: dropped (the SI checker reports the truncation)
HISTORY_CAP = 200_000


def enabled() -> bool:
    return os.environ.get("OTB_SNAPCHECK", "").strip().lower() \
        in ("1", "on", "true", "yes")


def history_on() -> bool:
    return bool(os.environ.get("OTB_SNAP_HISTORY", "").strip())


# ---------------------------------------------------------------------------
# sanitizer state (process-global, guarded by a RAW lock — the
# sanitizer's own bookkeeping must not recurse into the engine's
# checked locks)
# ---------------------------------------------------------------------------

_STATE = threading.Lock()
_POINTS: dict = {}       # guarded_by: _STATE — name -> serve count
_VIOLATIONS: list = []   # guarded_by: _STATE — kind/point/message
_SESS_GTS: dict = {}     # guarded_by: _STATE — session -> max snap gts
_SESS_VER: dict = {}     # guarded_by: _STATE — (session, table) -> ver
_HISTORY: list = []      # guarded_by: _STATE — SI history events
_DROPPED = [0]           # guarded_by: _STATE
_ATEXIT = [False]        # guarded_by: _STATE


def _record_violation(kind: str, point: str, message: str) -> None:
    with _STATE:
        _VIOLATIONS.append({
            "kind": kind, "point": point, "message": message,
            "thread": threading.current_thread().name,
        })


def _norm_versions(versions):
    """Canonical [[table, version], ...] from a version tuple/dict."""
    if versions is None:
        return None
    if isinstance(versions, dict):
        versions = versions.items()
    out = []
    for item in versions:
        try:
            t, v = item
        except (TypeError, ValueError):
            continue
        out.append([str(t), int(v)])
    return sorted(out)


def serve(point: str, snapshot_gts=None, entry_gts=None, versions=None,
          expect_versions=None, session=None, source=None,
          tables=None) -> None:
    """Witness one serve event at `point` (the canonical dotted name,
    e.g. ``"exec.share.ResultCache.lookup"``).  ``versions`` is the
    served entry's captured per-table version material;
    ``expect_versions`` is the live tuple it must exactly equal.
    No-op unless OTB_SNAPCHECK or $OTB_SNAP_HISTORY is on — call
    sites guard with ``if snapcheck.enabled() or
    snapcheck.history_on():`` so arguments are never built on the
    fast path."""
    on, hist = enabled(), history_on()
    if not on and not hist:
        return
    ver = _norm_versions(versions)
    if on:
        with _STATE:
            _POINTS[point] = _POINTS.get(point, 0) + 1
        if snapshot_gts is not None and entry_gts is not None \
                and int(entry_gts) > int(snapshot_gts):
            _record_violation(
                "stale-serve", point,
                f"entry tagged GTS {int(entry_gts)} served to "
                f"snapshot GTS {int(snapshot_gts)} — the cached "
                f"state postdates the reader's snapshot")
        want = _norm_versions(expect_versions)
        if ver is not None and want is not None and ver != want:
            _record_violation(
                "version-mismatch", point,
                f"served entry versions {ver} != live store versions "
                f"{want} — a DML the gate did not observe")
        if session is not None:
            with _STATE:
                if snapshot_gts is not None:
                    last = _SESS_GTS.get(session)
                    if last is not None and int(snapshot_gts) < last:
                        _VIOLATIONS.append({
                            "kind": "snapshot-regression",
                            "point": point,
                            "message": f"session snapshot GTS "
                                       f"{int(snapshot_gts)} < "
                                       f"previously drawn {last}",
                            "thread":
                                threading.current_thread().name})
                    else:
                        _SESS_GTS[session] = int(snapshot_gts)
                for t, v in (ver or []):
                    key = (session, t)
                    last = _SESS_VER.get(key)
                    if last is not None and v < last:
                        _VIOLATIONS.append({
                            "kind": "monotone-violation",
                            "point": point,
                            "message": f"session observed {t}@{v} "
                                       f"after already observing "
                                       f"{t}@{last} — reads went "
                                       f"back in time",
                            "thread":
                                threading.current_thread().name})
                    else:
                        _SESS_VER[key] = v
    if hist:
        note_read(session, snapshot_gts,
                  source or point.rsplit(".", 1)[-1],
                  obs=versions, tables=tables, point=point)
    _register_atexit()


# ---------------------------------------------------------------------------
# SI history (analysis/sicheck.py input)
# ---------------------------------------------------------------------------

def _append_history(ev: dict) -> None:
    with _STATE:
        if len(_HISTORY) >= HISTORY_CAP:
            _DROPPED[0] += 1
            return
        _HISTORY.append(ev)


def note_read(session, gts, source: str, obs=None, tables=None,
              point: Optional[str] = None) -> None:
    """One read in the SI history: ``obs`` is the observed per-table
    version material when the serving tier knows it exactly (cache
    vkey, pool entry version); ``tables`` names the read set when only
    inference from the write history is possible (primary/replica)."""
    if not history_on():
        return
    ev = {"t": "r", "sess": session if isinstance(session, (str, int))
          else id(session) if session is not None else None,
          "gts": None if gts is None else int(gts), "src": source}
    o = _norm_versions(obs)
    if o is not None:
        ev["obs"] = o
    if tables:
        ev["tables"] = sorted(str(t) for t in tables)
    if point:
        ev["point"] = point
    _append_history(ev)
    _register_atexit()


def note_write(session, gts, writes) -> None:
    """One commit in the SI history: ``writes`` is the committed
    write set as (table, post-commit store version) pairs, ``gts`` the
    commit GTS."""
    if not history_on():
        return
    _append_history(
        {"t": "w", "sess": session if isinstance(session, (str, int))
         else id(session) if session is not None else None,
         "gts": None if gts is None else int(gts),
         "writes": _norm_versions(writes) or []})
    _register_atexit()


# ---------------------------------------------------------------------------
# introspection + persistence
# ---------------------------------------------------------------------------

def witness() -> dict:
    """name -> serve count for every witnessed serve point."""
    with _STATE:
        return dict(_POINTS)


def violations() -> list:
    with _STATE:
        return list(_VIOLATIONS)


def history_events() -> list:
    with _STATE:
        return list(_HISTORY)


def reset() -> None:
    with _STATE:
        _POINTS.clear()
        _VIOLATIONS.clear()
        _SESS_GTS.clear()
        _SESS_VER.clear()
        _HISTORY.clear()
        _DROPPED[0] = 0


def default_report_path() -> str:
    env = os.environ.get("OTB_SNAPCHECK_REPORT", "").strip()
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(pkg, "analysis", "visibility_witness.json")


def save_report(path: Optional[str] = None) -> dict:
    """Merge this process's witnessed serve points into the report
    file (the union survives across shards/processes) and write
    violations from THIS process."""
    path = path or default_report_path()
    points = witness()
    try:
        with open(path, encoding="utf-8") as f:
            prior = json.load(f)
        for name, n in (prior.get("serve_points") or {}).items():
            points[name] = points.get(name, 0) + int(n)
    except (OSError, ValueError):
        pass
    data = {
        "comment": "witnessed serve points (OTB_SNAPCHECK=1 runs); "
                   "every name must be in the statically-gated set — "
                   "see analysis/visibility.py",
        "serve_points": {k: points[k] for k in sorted(points)},
        "violations": violations(),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


def save_history(path: Optional[str] = None) -> dict:
    """Write the bounded SI history for analysis/sicheck.py; returns
    the written dict.  Path defaults to $OTB_SNAP_HISTORY."""
    path = path or os.environ.get("OTB_SNAP_HISTORY", "").strip()
    with _STATE:
        data = {"events": list(_HISTORY), "dropped": _DROPPED[0]}
    if path:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(data, f)
            f.write("\n")
    return data


def _register_atexit() -> None:
    with _STATE:
        if _ATEXIT[0]:
            return
        _ATEXIT[0] = True
    if os.environ.get("OTB_SNAPCHECK_REPORT", "").strip() or \
            os.environ.get("OTB_SNAPCHECK_PERSIST", "").strip():
        atexit.register(save_report)
    if history_on():
        atexit.register(save_history)
