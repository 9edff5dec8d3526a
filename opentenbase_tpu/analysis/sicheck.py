"""History-based snapshot-isolation checker (Adya G1 / G-SI).

Input: the bounded read/write history `utils/snapcheck.py` records
under ``$OTB_SNAP_HISTORY`` while a workload runs —
commits as ``{"t": "w", "sess", "gts", "writes": [[table, version],
...]}`` (post-commit store versions tagged with the commit GTS) and
reads as ``{"t": "r", "sess", "gts", "src", "obs": [[table, version],
...]}`` (src = primary/cache/replica/shared/pool/standby; ``obs`` is
the exact observed version material when the serving tier knows it,
else ``tables`` names the read set and the observed version is
inferred as the latest committed at the read's snapshot GTS).

From the history we build Adya-style dependency edges between
transactions (one committed write event = one write txn; one read
event = one read-only txn):

- ``ww``: per-table version order — the writer of version v depends
  on the writer of the previous version of the same table;
- ``wr``: the writer of the version a read observed → the reader;
- ``rw`` (anti-dependency): a reader that observed version v → the
  writer of the NEXT version of that table.

and reject:

- **future-read** — a read observed a version whose writer committed
  AFTER the read's snapshot GTS (the serve gate let tomorrow's data
  through: exactly what a broken ``snapshot_gts >= tag`` check does);
- **stale-read** — a read observed an OLDER version than the latest
  committed at its snapshot (a cache/replica served data the gate
  should have refused);
- **G1b intermediate-read** — a read observed a non-final version of
  some txn's writes;
- **G1c cycle** — a cycle in wr ∪ ww (impossible when commit GTS
  totally orders writers — checked anyway, it catches corrupt
  histories);
- **G-SI cycle** — a cycle with exactly ONE rw anti-dependency edge:
  for each rw edge r→w, w must not reach r through wr ∪ ww.  (Write
  skew — a cycle with TWO rw edges — is ALLOWED under SI and is not
  flagged.)

Because wr/ww edges strictly increase commit GTS, reachability is
pruned by GTS, keeping the check near-linear in the history's length.

CLI::

    python -m opentenbase_tpu.analysis.sicheck [history.json]
"""

from __future__ import annotations

import json
import sys

__all__ = ["load_history", "check_history", "main"]


def load_history(path: str) -> list:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict):
        return list(data.get("events", []))
    return list(data)


def _normalize(events):
    """(writers, reads): writers is {(table, version): txn}, one txn
    dict per committed write event; reads is a list of read dicts with
    resolved per-table observations."""
    writers: dict = {}          # (table, ver) -> write txn
    by_table: dict = {}         # table -> sorted [(ver, txn)]
    txns: list = []
    reads: list = []
    for ev in events:
        if not isinstance(ev, dict):
            continue
        if ev.get("t") == "w":
            txn = {"id": len(txns), "sess": ev.get("sess"),
                   "gts": ev.get("gts"),
                   "writes": [(str(t), int(v))
                              for t, v in ev.get("writes", [])]}
            txns.append(txn)
            for t, v in txn["writes"]:
                writers[(t, v)] = txn
                by_table.setdefault(t, []).append((v, txn))
        elif ev.get("t") == "r":
            reads.append(ev)
    for t in by_table:
        by_table[t].sort(key=lambda x: x[0])
    resolved = []
    for ev in reads:
        gts = ev.get("gts")
        obs = []
        if ev.get("obs"):
            obs = [(str(t), int(v)) for t, v in ev["obs"]]
        elif ev.get("tables") and gts is not None:
            # infer: latest version whose writer committed at or
            # before the read snapshot
            for t in ev["tables"]:
                best = None
                for v, txn in by_table.get(t, []):
                    if txn["gts"] is not None and txn["gts"] <= gts:
                        best = (t, v)
                obs.extend([best] if best else [])
        # reads with no resolvable version material (e.g. a replica
        # fragment whose table set the router doesn't know) still
        # count toward by_source — they witness the tier served, they
        # just contribute no dependency edges
        resolved.append({"sess": ev.get("sess"), "gts": gts,
                         "src": ev.get("src", "?"), "obs": obs,
                         "point": ev.get("point")})
    return writers, by_table, txns, resolved


def check_history(events) -> dict:
    """Run the G1/G-SI analysis; returns ``{"ok", "anomalies",
    "reads", "writes", "by_source"}`` with one dict per anomaly."""
    writers, by_table, txns, reads = _normalize(events)
    anomalies: list = []
    by_source: dict = {}

    # per-txn final version per table (G1b: observing a non-final one
    # is an intermediate read)
    final: dict = {}
    for txn in txns:
        for t, v in txn["writes"]:
            cur = final.get((id(txn), t))
            if cur is None or v > cur:
                final[(id(txn), t)] = v

    # wr / ww / rw edges over txns + read events
    succ: dict = {}             # id(txn) -> set of txn (wr ∪ ww)
    rw_edges: list = []         # (read, observed writer txn, next writer)
    for t, entries in by_table.items():
        for i in range(1, len(entries)):
            a, b = entries[i - 1][1], entries[i][1]
            if a is not b:
                succ.setdefault(id(a), set()).add(id(b))
    txn_by_id = {id(txn): txn for txn in txns}

    def note(kind, read, t, v, extra=""):
        anomalies.append({
            "kind": kind, "table": t, "version": v,
            "src": read.get("src"), "gts": read.get("gts"),
            "sess": read.get("sess"), "detail": extra})

    for read in reads:
        by_source[read["src"]] = by_source.get(read["src"], 0) + 1
        gts = read.get("gts")
        for t, v in read["obs"]:
            w = writers.get((t, v))
            entries = by_table.get(t, [])
            if w is not None:
                if gts is not None and w["gts"] is not None \
                        and w["gts"] > gts:
                    note("future-read", read, t, v,
                         f"writer committed at GTS {w['gts']} > read "
                         f"snapshot {gts}")
                if final.get((id(w), t), v) != v:
                    note("intermediate-read", read, t, v,
                         "observed a non-final version of the "
                         "writer's txn (G1b)")
            if gts is not None and entries:
                latest = None
                for ev_v, txn in entries:
                    if txn["gts"] is not None and txn["gts"] <= gts:
                        latest = ev_v
                if latest is not None and v < latest:
                    note("stale-read", read, t, v,
                         f"latest committed at snapshot {gts} is "
                         f"version {latest}")
            # rw anti-dependency: this read -> writer of the next
            # version of t
            for ev_v, txn in entries:
                if ev_v > v:
                    rw_edges.append((read, txn, t, v))
                    break

    # G1c: cycle in wr ∪ ww between write txns.  wr edges into READS
    # terminate (reads are read-only txns, no outgoing wr/ww), so
    # cycles can only involve writers.  Iterative DFS: a per-table ww
    # chain can be tens of thousands of versions long.
    color: dict = {}
    cyclic_at = None
    for txn in txns:
        root = id(txn)
        if color.get(root, 0):
            continue
        color[root] = 1
        stack = [(root, iter(succ.get(root, ())))]
        while stack and cyclic_at is None:
            nid, it = stack[-1]
            for m in it:
                c = color.get(m, 0)
                if c == 1:
                    cyclic_at = txn
                    break
                if c == 0:
                    color[m] = 1
                    stack.append((m, iter(succ.get(m, ()))))
                    break
            else:
                color[nid] = 2
                stack.pop()
        if cyclic_at is not None:
            break
    if cyclic_at is not None:
        anomalies.append({
            "kind": "g1c-cycle", "table": None, "version": None,
            "src": None, "gts": cyclic_at["gts"],
            "sess": cyclic_at["sess"],
            "detail": "cycle in wr/ww dependency graph"})

    # G-SI: for each rw anti-dependency read->w_next, the cycle closes
    # iff w_next reaches ANY txn that SUPPLIED the read (a wr edge
    # supplier->read) through wr ∪ ww — including w_next itself, the
    # zero-length case where one txn both supplied part of the read
    # and overwrote another part the read missed.  One rw edge in the
    # cycle = G-SIb.  (Write skew needs TWO rw edges and is allowed.)
    # wr/ww edges strictly increase commit GTS, so the search prunes
    # on the suppliers' max GTS.
    for read, w_next, t, v in rw_edges:
        targets: set = set()
        limit = None
        for ot, ov in read["obs"]:
            s = writers.get((ot, ov))
            if s is not None:
                targets.add(id(s))
                if s["gts"] is not None and (limit is None
                                             or s["gts"] > limit):
                    limit = s["gts"]
        if not targets:
            continue
        stack, seen = [id(w_next)], {id(w_next)}
        found = False
        while stack:
            nid = stack.pop()
            if nid in targets:
                found = True
                break
            txn = txn_by_id.get(nid)
            if txn is not None and limit is not None and \
                    txn["gts"] is not None and txn["gts"] > limit:
                continue
            for m in succ.get(nid, ()):
                if m not in seen:
                    seen.add(m)
                    stack.append(m)
        if found:
            note("g-si-cycle", read, t, v,
                 "rw anti-dependency closes a wr/ww path back to a "
                 "txn that supplied this read (G-SIb: cycle with "
                 "exactly one rw edge)")

    return {
        "ok": not anomalies,
        "anomalies": anomalies,
        "reads": len(reads),
        "writes": len(txns),
        "by_source": by_source,
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    import os
    path = argv[0] if argv else os.environ.get("OTB_SNAP_HISTORY", "")
    if not path:
        print("usage: python -m opentenbase_tpu.analysis.sicheck "
              "<history.json>  (or set $OTB_SNAP_HISTORY)",
              file=sys.stderr)
        return 2
    res = check_history(load_history(path))
    json.dump(res, sys.stdout, indent=1, sort_keys=True)
    print()
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
