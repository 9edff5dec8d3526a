"""Cluster-aware dump/restore — the pg_dump / pg_restore analog.

Reference analog: src/bin/pg_dump (schema + data as reloadable SQL),
cluster-aware in the XC lineage (distribution clauses are part of the
dumped DDL).  The dump is ONE portable SQL script: schema DDL in
dependency order (FK parents before children, partition parents before
partitions), then data as batched INSERTs, then secondary DDL (indexes,
views, sequences, triggers/functions, masks, audit policies, resource
groups).  `restore` replays it through a normal session, so a dump
taken from a 4-DN cluster restores into a 2-DN one — the locator
re-routes every row (the reference needs pg_restore + redistribution
for that).

Data reads run with bypass_datamask so the dump contains REAL values
(a masked dump could never round-trip); the flag is restored after.
"""

from __future__ import annotations

from ..catalog.types import TypeKind


def _type_sql(t) -> str:
    return {
        TypeKind.BOOL: "bool",
        TypeKind.INT32: "int",
        TypeKind.INT64: "bigint",
        TypeKind.FLOAT64: "float",
        TypeKind.DATE: "date",
        TypeKind.TEXT: "text",
    }.get(t.kind) or (
        f"decimal({t.precision},{t.scale})"
        if t.kind == TypeKind.DECIMAL else f"vector({t.max_len})")


def _quote(v) -> str:
    if v is None:
        return "null"
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return repr(v)
    s = str(v).replace("'", "''")
    return f"'{s}'"


def _table_ddl(catalog, td, pinfo=None) -> str:
    cols = []
    for c in td.columns:
        d = f"{c.name} {_type_sql(c.type)}"
        if not c.nullable:
            d += " not null"
        cols.append(d)
    for src in td.checks:
        cols.append(f"check ({src})")
    for fk in td.fks:
        cols.append(
            f"foreign key ({', '.join(fk['cols'])}) references "
            f"{fk['ref_table']} ({', '.join(fk['ref_cols'])})")
    ddl = f"create table {td.name} ({', '.join(cols)})"
    dt = td.distribution.dist_type.value \
        if hasattr(td.distribution.dist_type, "value") \
        else str(td.distribution.dist_type)
    if dt in ("shard", "hash", "modulo"):
        ddl += (f" distribute by {dt}"
                f"({', '.join(td.distribution.dist_cols)})")
    elif dt == "replicated":
        ddl += " distribute by replication"
    if pinfo is not None:
        ddl += (f" partition by {pinfo['method']} "
                f"({pinfo['key']})")
    return ddl


def _topo_tables(catalog) -> list:
    """FK parents (and partition parents) before dependents; cycles
    other than self-references are emitted in name order (the engine
    validates at insert, and a dump of a cyclic schema is already
    unrestorable by any tool without deferred constraints)."""
    children = {p["name"] for pi in catalog.partitioned.values()
                for p in pi["parts"]}
    names = [n for n in catalog.tables
             if not n.startswith("otb_") and n not in children
             and not n.startswith("__gidx_")]
    deps = {n: {fk["ref_table"] for fk in catalog.tables[n].fks
                if fk["ref_table"] != n} for n in names}
    out, done = [], set()
    while names:
        ready = [n for n in names if deps[n] <= done]
        if not ready:
            ready = sorted(names)[:1]     # cycle: break it
        for n in sorted(ready):
            out.append(n)
            done.add(n)
            names.remove(n)
    return out


def dump_sql(session, batch_rows: int = 500) -> str:
    """The full reloadable script for `session`'s catalog + data."""
    catalog = session.cluster.catalog if hasattr(session, "cluster") \
        else session.node.catalog
    out = ["-- opentenbase_tpu dump"]
    order = _topo_tables(catalog)
    part_children = {p["name"]: (parent, p)
                     for parent, pi in catalog.partitioned.items()
                     for p in pi["parts"]}
    for name in order:
        td = catalog.tables[name]
        out.append(_table_ddl(catalog, td,
                              catalog.partitioned.get(name)) + ";")
        for p in catalog.partitioned.get(name, {}).get("parts", []):
            if "values" in p:
                vals = ", ".join(_quote(v) for v in p["values"])
                out.append(f"create table {p['name']} partition of "
                           f"{name} for values in ({vals});")
            else:
                out.append(f"create table {p['name']} partition of "
                           f"{name} for values from "
                           f"({_quote(p['from'])}) to "
                           f"({_quote(p['to'])});")
    live = {}
    gtm = getattr(getattr(session, "cluster", None), "gtm", None)
    if gtm is not None and hasattr(gtm, "seq_list"):
        try:
            live = gtm.seq_list()
        except Exception:
            live = {}
    for sd in catalog.sequences.values():
        # resume POSITION, not definition (pg_dump emits setval): a
        # restored sequence must never re-issue consumed values
        nxt = live.get(sd.name, {}).get(
            "next", getattr(sd, "next_value", sd.start))
        out.append(f"create sequence {sd.name} start with {nxt} "
                   f"increment by {sd.increment};")
    for name, s in live.items():
        if name not in catalog.sequences:
            out.append(f"create sequence {name} start with "
                       f"{s['next']} increment by {s['increment']};")

    # session-scoped unmasked reads: the dump must contain REAL
    # values WITHOUT flipping the cluster-wide bypass GUC (which would
    # unmask every concurrent session's reads)
    session._unmasked_reads = True
    try:
        for name in order:
            td = catalog.tables[name]
            colnames = ", ".join(td.column_names)
            rows = session.query(
                f"select {colnames} from {name}")
            for i in range(0, len(rows), batch_rows):
                chunk = rows[i:i + batch_rows]
                vals = ", ".join(
                    "(" + ", ".join(_quote(v) for v in r) + ")"
                    for r in chunk)
                out.append(f"insert into {name} ({colnames}) "
                           f"values {vals};")
    finally:
        session._unmasked_reads = False

    for t, cols in sorted(catalog.btree_cols.items()):
        for i, c in enumerate(sorted(cols)):
            out.append(f"create index {t}_{c}_idx on {t} ({c});")
    # global indexes: emitted AFTER the data so restore's backfill sees
    # the rows (the __gidx_* mapping tables themselves are excluded
    # from _topo_tables — CREATE GLOBAL INDEX rebuilds them, re-routed
    # for the restored cluster's topology); dropping these silently
    # lost cluster-wide UNIQUE + point routing
    for t, cols in sorted(catalog.global_indexes.items()):
        for col, cinfo in sorted(cols.items()):
            uq = "unique " if cinfo.get("unique") else ""
            out.append(f"create {uq}global index {cinfo['name']} "
                       f"on {t} ({col});")
    for vname, text in catalog.views.items():
        out.append(f"create view {vname} as {text};")
    for fname, fn in catalog.functions.items():
        body = fn["body"].replace("'", "''")
        out.append(f"create function {fname}() returns trigger as "
                   f"'{body}' language sql;")
    for tg in catalog.triggers.values():
        w = f" when ({tg['when']})" if tg.get("when") else ""
        out.append(f"create trigger {tg['name']} {tg['timing']} "
                   f"{tg['event']} on {tg['table']} for each row{w} "
                   f"execute function {tg['func']}();")
    for mname, m in catalog.masks.items():
        e = m["expr"].replace("'", "''")
        out.append(f"create mask {mname} on {m['table']} "
                   f"({m['column']}) as '{e}';")
    for pname, pol in catalog.fga_policies.items():
        out.append(f"create audit policy {pname} on {pol['table']} "
                   f"when ({pol['pred']});")
    for gname, g in catalog.resource_groups.items():
        opts = ", ".join(f"{k} = {v}" for k, v in g.items())
        out.append(f"create resource group {gname} with ({opts});")
    return "\n".join(out) + "\n"


def restore_sql(session, script: str) -> int:
    """Replay a dump script; returns the statement count."""
    n = 0
    for stmt in _split_statements(script):
        session.execute(stmt)
        n += 1
    return n


def _split_statements(script: str):
    """Split on top-level semicolons (string literals respected);
    comment lines are stripped first."""
    script = "\n".join(ln for ln in script.splitlines()
                       if not ln.lstrip().startswith("--"))
    buf, in_str = [], False
    for ch in script:
        if ch == "'":
            in_str = not in_str
            buf.append(ch)
        elif ch == ";" and not in_str:
            s = "".join(buf).strip()
            buf = []
            if s:
                yield s
        else:
            buf.append(ch)
    s = "".join(buf).strip()
    if s:
        yield s
