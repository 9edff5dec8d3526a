"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls: client on the wire -> CN server -> parser -> planner -> plancache ->
bufferpool staging -> fused/mesh XLA programs on the device -> finalize ->
reply, at TPC-H SF1 (BASELINE config 1), and compares every answer with a
plain numpy/pandas reference computed from the same generated data.

    python chip_smoke.py                 # one chip: device, start, load,
                                         # query, no-hidden-path
    python chip_smoke.py --chips 4       # ONLY the 4-DataNode mesh path
                                         # (Q3/Q5) and what it is compared with
    JAX_PLATFORMS=cpu python chip_smoke.py --sf 0.01     # rehearsal: every
                                         # phase runs, still ends "ok": false

One JSON object per phase goes to stdout as soon as it is known; the LAST
line is {"ok": ..., "device": {"platform", "kind", "count"}}.  "ok": true is
printed only on platform "tpu" with every phase passed; anything else exits
non-zero.  Where jax finds no accelerator (and JAX_PLATFORMS=cpu did not ask
for a rehearsal), or the package is not beside this file, nothing goes to
stdout at all.  Times printed here are smoke observations, not benchmark
results.
"""

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np
import pandas as pd

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, "smoke_out")
LOAD_ORDER = ("region", "nation", "supplier", "customer", "part",
              "partsupp", "orders", "lineitem")
COPY_TABLES = ("region", "nation", "supplier", "customer")
QUERIES = (1, 3, 5)
FLOAT_RTOL = 2e-4          # tests/test_tpu_lowering.py _approx_rows
CLIENT_TIMEOUT_S = 1100.0  # inside the smoke's 1200 s limit
# one chip runs the full SF1.  With --chips 4 the default is cut to SF0.1:
# the host-exchange tier that the mesh answers are compared with dispatches
# ~250 eager kernels per query, and at SF1 shard sizes the chip's compiler
# needs minutes for each sort-bearing one (CHANGES.md, PR 22) — on four
# chips, charged four times over.
DEFAULT_SF = {1: 1.0, 4: 0.1}
SF_CUT_CAUSE = {4: "compile time of the host-exchange comparison arm at SF1 "
                   "shard sizes on four chips (CHANGES.md, PR 22)"}


class PhaseFailed(Exception):
    pass


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


# ---------------------------------------------------------------------------
# the plain reference: same semantics, independent code.  DECIMAL(15,2)
# columns become integer cents and are summed exactly, as the engine's
# scaled-int64 storage does, so decimal answers compare with ==.
# ---------------------------------------------------------------------------

def _days(iso):
    return int((np.datetime64(iso, "D")
                - np.datetime64("1970-01-01", "D")).astype(np.int64))


def _iso(days):
    return str(np.datetime64("1970-01-01", "D") + np.timedelta64(int(days), "D"))


def _cents(col):
    return np.rint(np.asarray(col, dtype=np.float64) * 100).astype(np.int64)


def _frame(table, int_cols=(), cent_cols=(), text_cols=()):
    cols = {c: np.asarray(table[c]).astype(np.int64) for c in int_cols}
    cols.update({c: _cents(table[c]) for c in cent_cols})
    cols.update({c: np.asarray(table[c]) for c in text_cols})
    return pd.DataFrame(cols)


def ref_q1(data):
    li = _frame(data["lineitem"], ("l_shipdate",),
                ("l_quantity", "l_extendedprice", "l_discount", "l_tax"),
                ("l_returnflag", "l_linestatus"))
    li = li[li.l_shipdate <= _days("1998-09-02")]
    dp = li.l_extendedprice * (100 - li.l_discount)
    g = li.assign(dp=dp, ch=dp * (100 + li.l_tax)).groupby(
        ["l_returnflag", "l_linestatus"]).agg(
        sq=("l_quantity", "sum"), sp=("l_extendedprice", "sum"),
        sdp=("dp", "sum"), sch=("ch", "sum"), sd=("l_discount", "sum"),
        n=("l_quantity", "count")).reset_index().sort_values(
        ["l_returnflag", "l_linestatus"])
    return [(r.l_returnflag, r.l_linestatus, int(r.sq) / 100,
             int(r.sp) / 100, int(r.sdp) / 10**4, int(r.sch) / 10**6,
             r.sq / 100 / r.n, r.sp / 100 / r.n, r.sd / 100 / r.n,
             int(r.n)) for r in g.itertuples()]


def _revenue_join(data):
    """customer |x| orders |x| lineitem with rev in 1e-4 units."""
    c = _frame(data["customer"], ("c_custkey", "c_nationkey"),
               text_cols=("c_mktsegment",))
    o = _frame(data["orders"], ("o_orderkey", "o_custkey", "o_orderdate",
                                "o_shippriority"))
    li = _frame(data["lineitem"], ("l_orderkey", "l_suppkey", "l_shipdate"),
                ("l_extendedprice", "l_discount"))
    li["rev"] = li.l_extendedprice * (100 - li.l_discount)
    return c, o, li


def ref_q3(data):
    c, o, li = _revenue_join(data)
    df = c[c.c_mktsegment == "BUILDING"].merge(
        o[o.o_orderdate < _days("1995-03-15")],
        left_on="c_custkey", right_on="o_custkey")
    df = df.merge(li[li.l_shipdate > _days("1995-03-15")],
                  left_on="o_orderkey", right_on="l_orderkey")
    g = df.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])[
        "rev"].sum().reset_index().sort_values(
        ["rev", "o_orderdate"], ascending=[False, True]).head(10)
    return [(int(r.l_orderkey), int(r.rev) / 10**4, _iso(r.o_orderdate),
             int(r.o_shippriority)) for r in g.itertuples()]


def ref_q5(data):
    c, o, li = _revenue_join(data)
    s = _frame(data["supplier"], ("s_suppkey", "s_nationkey"))
    n = _frame(data["nation"], ("n_nationkey", "n_regionkey"),
               text_cols=("n_name",))
    r = _frame(data["region"], ("r_regionkey",), text_cols=("r_name",))
    o = o[(o.o_orderdate >= _days("1994-01-01"))
          & (o.o_orderdate < _days("1995-01-01"))]
    df = c.merge(o, left_on="c_custkey", right_on="o_custkey")
    df = df.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    df = df.merge(s, left_on="l_suppkey", right_on="s_suppkey")
    df = df[df.c_nationkey == df.s_nationkey]
    df = df.merge(n, left_on="s_nationkey", right_on="n_nationkey")
    df = df.merge(r[r.r_name == "ASIA"], left_on="n_regionkey",
                  right_on="r_regionkey")
    g = df.groupby("n_name")["rev"].sum().reset_index().sort_values(
        "rev", ascending=False)
    return [(r_.n_name, int(r_.rev) / 10**4) for r_ in g.itertuples()]


REFERENCE = {1: ref_q1, 3: ref_q3, 5: ref_q5}
# result columns that are device floats (AVG): compared to FLOAT_RTOL;
# every other column (text, date, count, decimal sums) compares exactly
FLOAT_COLS = {1: (6, 7, 8), 3: (), 5: ()}


def rows_mismatch(got, want, float_cols):
    """None when equal, else a description of the first difference."""
    if len(got) != len(want):
        return f"{len(got)} rows, reference has {len(want)}"
    for i, (g, w) in enumerate(zip(got, want)):
        if len(g) != len(w):
            return f"row {i}: arity {len(g)} vs {len(w)}"
        for j, (a, b) in enumerate(zip(g, w)):
            if j in float_cols:
                if abs(a - b) > FLOAT_RTOL * max(abs(a), abs(b), 1.0):
                    return f"row {i} col {j}: {a!r} vs reference {b!r}"
            elif a != b:
                return f"row {i} col {j}: {a!r} vs reference {b!r}"
    return None


# ---------------------------------------------------------------------------
# counters: XLA compile requests as jax itself reports them (every request
# passes the persistent cache, armed in phase_device), the engine's program
# caches, and the bufferpool's upload bytes
# ---------------------------------------------------------------------------

XLA = {"requests": 0, "cache_hits": 0, "compile_s": 0.0}


def _listen_to_jax():
    from jax import monitoring

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            XLA["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            XLA["cache_hits"] += 1

    def on_duration(event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            XLA["compile_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def counters():
    from opentenbase_tpu.exec import plancache
    from opentenbase_tpu.storage.bufferpool import POOL
    return {"programs": sum(r[3] for r in plancache.stats()),
            "xla_requests": XLA["requests"],
            "xla_cache_hits": XLA["cache_hits"],
            "xla_compile_s": XLA["compile_s"],
            "uploaded": POOL.totals()["uploaded_bytes"]}


def delta(before):
    now = counters()
    return {k: (round(now[k] - before[k], 3)
                if isinstance(now[k], float) else now[k] - before[k])
            for k in now}


def cache_entries(path):
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_of():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def phase_device(want_chips):
    import jax
    from opentenbase_tpu.exec import plancache
    from opentenbase_tpu.storage import bufferpool
    from opentenbase_tpu.utils import dtypes
    dev = device_of()
    cache_dir = plancache.enable_persistent_cache()
    _listen_to_jax()
    emit("device", backend=jax.default_backend(), **dev,
         dtype_mode=dtypes.mode(), compile_cache_dir=cache_dir,
         compile_cache_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         compile_cache_entries_before=cache_entries(cache_dir),
         memory_stats=jax.devices()[0].memory_stats(),  # None: not reported
         bufferpool_assumed_budget_bytes=bufferpool._budget())
    check(plancache.persistent_cache_dir() == cache_dir,
          f"compile cache not armed: jax has "
          f"{plancache.persistent_cache_dir()!r}, wanted {cache_dir!r}")
    if dev["platform"] == "tpu":
        check(dtypes.mode() == "tpu",
              f"dtype mode {dtypes.mode()!r} on a tpu backend")
        check(dev["count"] >= want_chips,
              f"need {want_chips} chips, jax reports {dev['count']}")
    return dev, cache_dir


def start_stack(n_datanodes, datadir):
    """In-process Cluster (GTM + WAL + checkpoints under datadir) fronted
    by the CN wire server; a CnClient connects over TCP from this same
    process (one process per chip — the client never touches jax)."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.net.cn_server import CnClient, CnServer
    from opentenbase_tpu.parallel.cluster import Cluster
    t0 = time.perf_counter()
    cluster = Cluster(n_datanodes=n_datanodes, datadir=datadir)
    served = []                 # the server-side sessions, for their stats

    def make_session():
        s = ClusterSession(cluster)
        served.append(s)
        return s

    srv = CnServer(make_session).start()
    # a cold reply waits for the chip's compiler: minutes, not the
    # client's default 300 s
    client = CnClient(srv.host, srv.port, timeout=CLIENT_TIMEOUT_S)
    emit("start", shape="in-process Cluster + CnServer + TCP CnClient",
         datanodes=n_datanodes, datadir=datadir,
         cn=f"{srv.host}:{srv.port}",
         seconds=round(time.perf_counter() - t0, 3))
    return cluster, srv, client, served


def _write_tbl(path, table, date_cols):
    from opentenbase_tpu.tpch import datagen
    pd.DataFrame(datagen.to_date_strings(table, date_cols)).to_csv(
        path, sep="|", header=False, index=False)


def phase_load(sf, seed, cluster, client, copy_tables):
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.storage import loader
    from opentenbase_tpu.tpch import datagen
    from opentenbase_tpu.tpch.schema import SCHEMA
    t0 = time.perf_counter()
    data = datagen.generate(sf=sf, seed=seed)
    datagen_s = time.perf_counter() - t0
    client.execute(SCHEMA)                       # DDL over the wire
    bulk = ClusterSession(cluster)               # the column path
    rows, served0 = {}, dict(loader.SERVED)
    t0 = time.perf_counter()
    for tname in LOAD_ORDER:
        n = len(next(iter(data[tname].values())))
        if tname in copy_tables:
            path = os.path.join(OUT_DIR, f"{tname}.tbl")
            _write_tbl(path, data[tname], datagen.DATE_COLS.get(tname, []))
            res = client.execute(
                f"copy {tname} from '{path}' with (delimiter '|')")
            check(res[0]["rowcount"] == n,
                  f"COPY {tname}: {res[0]['rowcount']} rows, wrote {n}")
        else:
            bulk._insert_rows(cluster.catalog.table(tname), data[tname], n)
        rows[tname] = n
    load_s = time.perf_counter() - t0
    served = {k: loader.SERVED[k] - served0[k] for k in served0}
    emit("load", sf=sf, seed=seed, rows=rows, rows_total=sum(rows.values()),
         datagen_s=round(datagen_s, 3), load_s=round(load_s, 3),
         copy_over_wire=list(copy_tables), bulk_column_path=[
             t for t in LOAD_ORDER if t not in copy_tables],
         native_loader_available=loader.native_available(),
         copy_served_by=served)
    # the COPY-loaded tables are read back over the wire here; the bulk
    # tables are held to the reference by Q1/Q3/Q5, whose cold runs should
    # show their own staging
    for tname in copy_tables:
        got = client.query(f"select count(*) from {tname}")[0][0]
        check(got == rows[tname],
              f"{tname}: count(*) = {got}, loaded {rows[tname]}")
    return data


def phase_point_ops(client, served, data):
    """FQS path: acknowledged single-row INSERTs read back by key, and
    point SELECTs on orders checked against the generated data."""
    client.execute("create table smoke_kv (k bigint primary key, v bigint, "
                   "amt decimal(15,2), note varchar(16)) "
                   "distribute by shard(k)")
    tiers, lat = set(), []
    for i in range(5):
        k, v, amt, note = 1000 + i, 7 * i - 3, i + 0.25, f"row-{i}"
        res = client.execute(f"insert into smoke_kv values "
                             f"({k}, {v}, {amt}, '{note}')")
        check(res[0]["rowcount"] == 1, f"insert k={k} not acknowledged")
        t0 = time.perf_counter()
        got = client.query(f"select k, v, amt, note from smoke_kv "
                           f"where k = {k}")
        lat.append((time.perf_counter() - t0) * 1e3)
        tiers.add(served[-1].last_query_stats().get("tier"))
        check(got == [(k, v, amt, note)],
              f"acknowledged insert k={k} read back as {got}")
    o = data["orders"]
    for idx in (0, len(o["o_orderkey"]) // 2, len(o["o_orderkey"]) - 1):
        key = int(o["o_orderkey"][idx])
        want = [(key, int(o["o_custkey"][idx]),
                 float(np.round(o["o_totalprice"][idx], 2)),
                 _iso(o["o_orderdate"][idx]))]
        got = client.query("select o_orderkey, o_custkey, o_totalprice, "
                           f"o_orderdate from orders where o_orderkey = {key}")
        tiers.add(served[-1].last_query_stats().get("tier"))
        check(got == want, f"orders key {key}: {got} vs reference {want}")
    emit("point_ops", inserts_read_back=5, orders_keys=3,
         tiers=sorted(str(t) for t in tiers),
         select_ms=[round(x, 3) for x in lat])
    check(tiers <= {"fqs", "gidx"},
          f"point ops were not shipped whole to a datanode: {tiers}")


def timed_query(client, session, sql):
    c0 = counters()
    t0 = time.perf_counter()
    rows = client.query(sql)    # the reply is host data: the device is done
    ms = (time.perf_counter() - t0) * 1e3
    st = session.last_query_stats()
    return rows, {"ms": round(ms, 3), "tier": st.get("tier"),
                  "stage_ms": round(st.get("stage_ms", 0.0), 3),
                  "execute_ms": round(st.get("execute_ms", 0.0), 3),
                  **delta(c0)}


def phase_queries(client, served, data, want_tiers):
    """Q1/Q3/Q5 over the wire, cold then warm, each against the plain
    reference; then the no-hidden-path verdict."""
    from opentenbase_tpu.exec import shield
    from opentenbase_tpu.storage.bufferpool import POOL
    from opentenbase_tpu.tpch.queries import Q
    session = served[-1]
    problems = []
    for qn in QUERIES:
        t0 = time.perf_counter()
        want = REFERENCE[qn](data)
        ref_s = time.perf_counter() - t0
        cold_rows, cold = timed_query(client, session, Q[qn])
        warm_rows, warm = timed_query(client, session, Q[qn])
        bad = (rows_mismatch(cold_rows, want, FLOAT_COLS[qn])
               or rows_mismatch(warm_rows, want, FLOAT_COLS[qn]))
        emit("query", q=f"Q{qn}", rows=len(cold_rows), correct=bad is None,
             cold_ms=cold["ms"], warm_ms=warm["ms"], tier=cold["tier"],
             programs_compiled=cold["programs"],
             cold=cold, warm=warm, reference_s=round(ref_s, 3),
             pool_uploaded_bytes_total=POOL.totals()["uploaded_bytes"],
             first_row=cold_rows[0] if cold_rows else None)
        if bad:
            problems.append(f"Q{qn} differs from the reference: {bad}")
        for run, r in (("cold", cold), ("warm", warm)):
            if r["tier"] not in want_tiers:
                problems.append(f"Q{qn} {run} served by tier {r['tier']!r}, "
                                f"not one of {sorted(want_tiers)}")
        if warm["programs"] or warm["xla_requests"]:
            problems.append(
                f"Q{qn} warm repeat compiled: {warm['programs']} programs, "
                f"{warm['xla_requests']} XLA compile requests")
        if warm["uploaded"]:
            problems.append(f"Q{qn} warm repeat uploaded "
                            f"{warm['uploaded']} table bytes")
    sh = shield.stats_snapshot()
    fallbacks = [f for s in served for f in s.fallbacks]
    if fallbacks:
        problems.append(f"host-tier fallbacks: {fallbacks}")
    for k in ("degraded", "streamed", "oom_dispatches", "quarantined"):
        if sh[k]:
            problems.append(f"shield recorded {k}={sh[k]}")
    tier_counts = {}
    for s in served:
        for k, n in s.tier_counts.items():
            tier_counts[k] = tier_counts.get(k, 0) + n
    emit("no_hidden_path", passed=not problems, problems=problems,
         fallbacks=fallbacks, shield=sh, tier_counts=tier_counts)
    check(not problems, "; ".join(problems))


MESH_PROGRAMS = {}     # id(fn) -> (jitted shard_map program, arg shapes)


def _capture_mesh_program(_tag, fn, args):
    import jax

    def shape_of(a):
        if not isinstance(a, jax.Array):
            return a
        if len(a.sharding.device_set) > 1:      # a staged, sharded column
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    if id(fn) not in MESH_PROGRAMS:
        MESH_PROGRAMS[id(fn)] = (fn, tuple(shape_of(a) for a in args))


def phase_mesh4(client, served, cluster, data):
    """--chips 4 only: the shard_map + all_to_all path and its comparisons
    (plain reference, host-exchange tier)."""
    import jax
    from opentenbase_tpu.exec import mesh_exec
    from opentenbase_tpu.exec.mesh_exec import mesh_runner_for
    from opentenbase_tpu.storage.bufferpool import POOL
    from opentenbase_tpu.tpch.queries import Q
    mesh_exec.EXPORT_HOOK = _capture_mesh_program
    session = served[-1]
    problems = []
    for qn in (3, 5):
        want = REFERENCE[qn](data)
        client.execute("set enable_mesh_exchange = off")
        host_rows, host = timed_query(client, session, Q[qn])
        client.execute("set enable_mesh_exchange = on")
        cold_rows, cold = timed_query(client, session, Q[qn])
        warm_rows, warm = timed_query(client, session, Q[qn])
        bad = (rows_mismatch(cold_rows, want, ())
               or rows_mismatch(warm_rows, want, ()))
        bad_host = rows_mismatch(cold_rows, host_rows, ())
        emit("mesh_query", q=f"Q{qn}", rows=len(cold_rows),
             correct=bad is None, equals_host_tier=bad_host is None,
             tier=cold["tier"], host_tier=host["tier"],
             cold_ms=cold["ms"], warm_ms=warm["ms"], host_ms=host["ms"],
             cold=cold, warm=warm, host=host)
        if bad:
            problems.append(f"Q{qn} differs from the reference: {bad}")
        if bad_host:
            problems.append(f"Q{qn} mesh differs from host tier: {bad_host}")
        if cold["tier"] != "mesh" or warm["tier"] != "mesh":
            problems.append(f"Q{qn} tier {cold['tier']!r}/{warm['tier']!r}, "
                            f"not 'mesh'")
        if host["tier"] != "host":
            problems.append(f"Q{qn} comparison arm ran on tier "
                            f"{host['tier']!r}, not 'host'")
    # the comparison arm is a requested host run, not a fallback
    fallbacks = [f for s in served for f in s.fallbacks
                 if f and "enable_mesh_exchange" not in f]
    if fallbacks:
        problems.append(f"host-tier fallbacks: {fallbacks}")
    runner = mesh_runner_for(cluster)
    ent = POOL.mesh_peek(runner, "lineitem") if runner is not None else None
    shard_devices = []
    if ent is None:
        problems.append("lineitem is not staged for the mesh runner")
    else:
        shard_devices = [sorted(str(s.device) for s in arr.addressable_shards)
                         for arr in ent.staged.arrs.values()]
        if any(len(set(d)) != 4 for d in shard_devices):
            problems.append(f"lineitem shards not on 4 distinct devices: "
                            f"{shard_devices[0]}")
    # the programs the mesh tier actually ran (captured by EXPORT_HOOK),
    # compiled text searched for the ICI collective
    all_to_all = ["all-to-all" in fn.lower(*shapes).compile().as_text()
                  for fn, shapes in MESH_PROGRAMS.values()]
    if not any(all_to_all):
        problems.append(f"none of {len(all_to_all)} compiled mesh programs "
                        f"contains all-to-all")
    emit("mesh_placement", passed=not problems, problems=problems,
         devices=[str(d) for d in jax.devices()],
         lineitem_shard_devices=shard_devices[0] if shard_devices else None,
         lineitem_columns_checked=len(shard_devices),
         programs_with_all_to_all=sum(all_to_all),
         mesh_programs=len(all_to_all), fallbacks=fallbacks)
    check(not problems, "; ".join(problems))


def emit_end(t_all, cache_dir):
    """Printed whether the phases passed or not: where the time and the
    compiles went, and what the cache and the device hold now."""
    import jax
    emit("end", seconds=round(time.perf_counter() - t_all, 3),
         compile_cache_dir=cache_dir,
         compile_cache_entries_after=cache_entries(cache_dir),
         xla_compile_requests=XLA["requests"],
         xla_cache_hits=XLA["cache_hits"],
         xla_backend_compile_s=round(XLA["compile_s"], 3),
         memory_stats=jax.devices()[0].memory_stats())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1, and 0.1 with "
                         "--chips 4; lower to rehearse)")
    ap.add_argument("--seed", type=int, default=19980802,
                    help="data generator seed")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the four-DataNode mesh path")
    args = ap.parse_args()
    sf = DEFAULT_SF[args.chips] if args.sf is None else args.sf

    # nothing goes to stdout unless there is a system and a device to test:
    # alone in a directory, or with jax's silent CPU default standing in for
    # a chip that was wanted, the script says why on stderr and exits
    try:
        import opentenbase_tpu  # noqa: F401  (x64 on before first use)
        dev = device_of()
    except (ImportError, RuntimeError) as e:
        print(f"chip_smoke: cannot start: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(3)
    if dev["platform"] == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("chip_smoke: jax found no accelerator", file=sys.stderr)
        sys.exit(3)

    t_all = time.perf_counter()
    srv = client = cache_dir = None
    ok, failed = False, None
    try:
        dev, cache_dir = phase_device(args.chips)
        if sf < 1.0:
            emit("scale", sf=sf, full_sf=1.0,
                 cut=SF_CUT_CAUSE[args.chips] if args.sf is None
                 else "--sf given on the command line")
        shutil.rmtree(OUT_DIR, ignore_errors=True)
        os.makedirs(OUT_DIR)
        cluster, srv, client, served = start_stack(
            args.chips, os.path.join(OUT_DIR, "cluster"))
        if args.chips == 1:
            data = phase_load(sf, args.seed, cluster, client, COPY_TABLES)
            phase_point_ops(client, served, data)
            phase_queries(client, served, data, {"fused", "mesh"})
        else:
            data = phase_load(sf, args.seed, cluster, client, ())
            phase_mesh4(client, served, cluster, data)
        ok = True
    except PhaseFailed as e:
        failed = str(e)
    except Exception as e:      # noqa: BLE001 — report, then fail
        import traceback
        traceback.print_exc()
        failed = f"{type(e).__name__}: {e}"
    finally:
        if cache_dir is not None:
            emit_end(t_all, cache_dir)
        if client is not None:
            try:
                client.close()
            except OSError:
                pass
        if srv is not None:
            srv.stop()
        # at SF1 the datadir (WAL, checkpoints) and the COPY files are GBs
        shutil.rmtree(OUT_DIR, ignore_errors=True)
    if failed:
        emit("failed", error=failed,
             seconds=round(time.perf_counter() - t_all, 3))
    on_chip = dev["platform"] == "tpu" and dev["count"] >= args.chips
    if ok and not on_chip:
        emit("rehearsal", note=f"every phase passed on {dev['platform']} x"
             f"{dev['count']}; only a tpu run may print ok: true")
    print(json.dumps({"ok": ok and on_chip, "device": dev}), flush=True)
    sys.exit(0 if ok and on_chip else 1)


if __name__ == "__main__":
    main()
