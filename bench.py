"""Benchmark driver: the BASELINE.md measurement ladder through the engine.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "ladder"}.
- headline value: TPC-H Q1 throughput in Mrows/s of lineitem scanned on the
  device-mesh data plane (or single-node fused when only one config runs)
- vs_baseline: speedup over the CPU control arm (pandas, BASELINE.md's
  "CPU DataNode" stand-in) on the same machine & data
- ladder: per-config results — Q1/Q3/Q5 single-node fused (BASELINE
  config 1; Q3/Q5 run as fused JOIN fragments — late-materialized
  index-composition joins in one XLA program) plus Q1/Q3/Q5 through
  the mesh tier (config 2: joins + all_to_all redistribution as ONE
  shard_map program per query).  Every query entry reports the
  late-materialization counters (mat_deferred_cols / mat_eager_cols /
  mat_cols_gathered / mat_bytes_gathered / join_host_syncs) for its
  timed runs.  Mesh entries
  split a warm repeat into stage_ms (host->device upload; ~0 when the
  device buffer pool serves every table resident) vs compute_ms, and
  report the pool hit rate + bytes staged on that repeat
  (storage/bufferpool.py — engine_ms stays the min-of-warm-runs number
  comparable to earlier rounds), plus the compressed-residency block
  (storage/codec.py): bytes_logical / bytes_resident /
  effective_cache_ratio of the live pool
- device: {platform, kind, count} as jax reports it — every JSON line
  names the machine it measured.  A run that finds no accelerator exits
  non-zero unless JAX_PLATFORMS=cpu asked for a CPU rehearsal (whose
  numbers are NOT device measurements)

Modes via env:
- BENCH_SF (default 1.0), BENCH_REPEAT (default 5)
- BENCH_MODE=ladder (default) | single | mesh — single/mesh run only that
  one arm (the r1/r2 behavior) for quick checks
- BENCH_MODE=qps: the serving-tier arm (exec/scheduler.py) — sustained
  throughput with 8/64/256 concurrent clients over (a) a same-signature
  point-SELECT workload (varying key literal: every query is the SAME
  literal-masked compiled program, so the scheduler coalesces them into
  multi-query dispatches and amortizes per-query host overhead), (b) a
  same-signature analytics workload (Q1 with a varying shipdate
  literal), and (c) a mixed Q1/Q3/Q5 + point-SELECT workload.
  Reports per-arm qps, p50/p99 latency, batch_rate
  (fraction of admitted queries served by a multi-query dispatch), shed
  count, and the dispatch-size histogram, plus a single-session
  serial-loop baseline per workload.  Knobs: BENCH_QPS_SECONDS (timed
  window per arm, default 4), BENCH_QPS_WARM_SECONDS (untimed
  compile-warm phase per arm, default 2), BENCH_QPS_CLIENTS (default
  "8,64,256"), BENCH_QPS_BASELINE_N (serial baseline queries, default
  60); BENCH_SF defaults to 0.05 in this mode.  A zipf_cache arm per
  client count drives zipfian-skewed repeated statements through the
  GTS-versioned result cache (exec/share.py): device dispatches stay
  near the distinct-statement count while served queries scale with
  clients, every response verified (knobs: BENCH_QPS_ZIPF_DISTINCT
  default 48, BENCH_QPS_ZIPF_SKEW default 1.2)
- BENCH_OLTP=1: additionally measure the point-op latency path (FQS
  INSERT/SELECT p50) — the reference's execLight.c OLTP story
- --trace: after each timed arm, dump the full last-query span tree
  (obs/trace.py) as one JSON line on stderr; every ladder entry also
  carries a "phases" breakdown (plan/stage/execute/exchange/finalize
  ms of the arm's last warm run), and the final JSON gains a
  "latency" block with p50/p95/p99 per tier from the unified metrics
  registry's otb_query_ms histograms (obs/metrics.py)
- JAX_COMPILATION_CACHE_DIR: where the persistent XLA compilation
  cache lives (default: the fixed .jax_cache/ inside the checkout —
  exec/plancache.enable_persistent_cache), so a second invocation of
  the same command starts warm; that second top-level run IS the
  restart measurement (no arm starts a python child)
- --chaos: SKIP the ladder; instead run point reads against a live
  TCP cluster while one DN flaps (wire-level close faults) and print
  p50/p99 latency, error rate, wrong-result count, and the otbguard
  counters (net/guard.py).  Knobs: BENCH_CHAOS_OPS (400),
  BENCH_CHAOS_FLAP_EVERY (50), plus the OTB_RPC_*/OTB_BREAKER_* envs.
- --chaos-concurrent: the otbshield acceptance arm — 64 client threads
  (coalescing scheduler + a flapping TCP cluster) under simultaneous
  poisoned-literal, cancel-storm, dispatch-OOM, wire-flap, and shed
  pressure.  ONE JSON line with qps, p50/p99, the offender-vs-
  collateral error split (collateral must be 0), wrong_results (must
  be 0), degraded count, and the admission-slot + GTM-lease ledgers
  (must balance); exits nonzero when any acceptance number fails.
  Knobs: BENCH_CHAOSC_SECONDS (8), BENCH_CHAOSC_WARM_SECONDS (2),
  BENCH_CHAOSC_CLIENTS (64), BENCH_CHAOSC_SF (0.02),
  BENCH_CHAOSC_ANALYTICS=0 for a quick smoke run.
- --oob: the out-of-core arm (exec/morsel.py) — SKIP the ladder; cap
  OTB_DEVICE_CACHE_BYTES at what the BENCH_OOB_CAP_SF (default 1)
  dataset would occupy staged, then run Q1/Q3/Q5 at BENCH_OOB_SF
  (default 10) through the morsel streaming tier.  ONE JSON line with
  per-query GB/s of bytes touched (vs the uncapped in-memory run),
  chunk count, chunk_downshifts, bytes_streamed, bit_identical, and
  warm_programs_compiled (must be 0 — chunk count never reaches a
  program key), plus the bufferpool pin ledger (must balance).  Each
  query also reports compressed residency (bytes_logical /
  bytes_resident / effective_cache_ratio; effective_cache_x =
  min over queries, acceptance floor 2.5x) and a codec-off control
  (OTB_CODEC=0: raw_ms, gb_per_s_raw, x_codec_off,
  bit_identical_codec_off — encoded execution must match raw
  byte-for-byte).
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import opentenbase_tpu  # noqa: E402,F401  (enables x64 before first use)
import jax  # noqa: E402
import numpy as np  # noqa: E402

platform = jax.default_backend()
if platform == "cpu" and os.environ.get("JAX_PLATFORMS", "") != "cpu":
    sys.exit("bench.py: jax found no accelerator (default_backend() == "
             "'cpu'); set JAX_PLATFORMS=cpu for a CPU rehearsal")
DEVICE = {"platform": platform, "kind": jax.devices()[0].device_kind,
          "count": len(jax.devices())}


def _d(iso):
    return int((np.datetime64(iso, "D")
                - np.datetime64("1970-01-01", "D")).astype(np.int64))


def _pandas_q1(dfs):
    li = dfs["lineitem"]
    df = li[li.l_shipdate <= _d("1998-09-02")]
    dp = df.l_extendedprice * (1 - df.l_discount)
    ch = dp * (1 + df.l_tax)
    df.assign(dp=dp, ch=ch).groupby(
        ["l_returnflag", "l_linestatus"]).agg(
        sq=("l_quantity", "sum"), sp=("l_extendedprice", "sum"),
        sdp=("dp", "sum"), sch=("ch", "sum"),
        aq=("l_quantity", "mean"), ap=("l_extendedprice", "mean"),
        ad=("l_discount", "mean"), n=("l_quantity", "count"))


def _pandas_q3(dfs):
    c, o, li = dfs["customer"], dfs["orders"], dfs["lineitem"]
    df = c[c.c_mktsegment == "BUILDING"].merge(
        o, left_on="c_custkey", right_on="o_custkey")
    df = df[df.o_orderdate < _d("1995-03-15")]
    df = df.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    df = df[df.l_shipdate > _d("1995-03-15")]
    df = df.assign(rev=df.l_extendedprice * (1 - df.l_discount))
    df.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])[
        "rev"].sum().reset_index().sort_values(
        ["rev", "o_orderdate"], ascending=[False, True]).head(10)


def _pandas_q5(dfs):
    t = dfs
    df = t["customer"].merge(t["orders"], left_on="c_custkey",
                             right_on="o_custkey")
    df = df.merge(t["lineitem"], left_on="o_orderkey",
                  right_on="l_orderkey")
    df = df.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
    df = df[df.c_nationkey == df.s_nationkey]
    df = df.merge(t["nation"], left_on="s_nationkey",
                  right_on="n_nationkey")
    df = df.merge(t["region"], left_on="n_regionkey",
                  right_on="r_regionkey")
    df = df[(df.r_name == "ASIA") & (df.o_orderdate >= _d("1994-01-01"))
            & (df.o_orderdate < _d("1995-01-01"))]
    df.assign(rev=df.l_extendedprice * (1 - df.l_discount)).groupby(
        "n_name")["rev"].sum().reset_index().sort_values(
        "rev", ascending=False)


# columns each query actually touches (8 bytes/value storage) — the
# bytes-touched estimate under perfect column pruning
_Q_COLS = {
    1: {"lineitem": 7},                       # shipdate,qty,price,disc,tax,rf,ls
    3: {"lineitem": 4, "orders": 4, "customer": 2},
    5: {"lineitem": 4, "orders": 3, "customer": 2, "supplier": 2,
        "nation": 3, "region": 2},
}


def _gb_touched(qn, data):
    total = 0
    for t, ncols in _Q_COLS.get(qn, {}).items():
        rows = len(next(iter(data[t].values())))
        total += rows * ncols * 8
    return total / 1e9


def _time(fn, repeat):
    """(best_warm_s, cold_s): cold = first run including compile +
    staging — the interactive first-query cost min() alone hides
    (VERDICT r4 weak #8)."""
    t0 = time.perf_counter()
    fn()  # cold (compile + staging)
    cold = time.perf_counter() - t0
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times), cold


def _oltp_latencies(s, n=200):
    """Point-op p50 (ms): single-shard INSERT, raw-literal SELECT (replan
    + recompile per value), and PREPAREd SELECT (plan cache + light
    coordinator — the execLight.c OLTP fast path)."""
    s.execute("create table if not exists bench_kv (k bigint primary key, "
              "v bigint) distribute by shard(k)")
    s.execute("prepare __bget (bigint) as "
              "select v from bench_kv where k = $1")
    s.execute("prepare __bins (bigint, bigint) as "
              "insert into bench_kv values ($1, $2)")
    ins, raw, prep = [], [], []
    for i in range(n):
        t0 = time.perf_counter()
        s.execute(f"execute __bins ({i}, {i * 7})")
        ins.append(time.perf_counter() - t0)
        if i < 30:   # the slow arm: cap its share of bench wall-clock
            t0 = time.perf_counter()
            s.query(f"select v from bench_kv where k = {i}")
            raw.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        s.query(f"execute __bget ({i})")
        prep.append(time.perf_counter() - t0)
    return (float(np.median(ins) * 1e3), float(np.median(raw) * 1e3),
            float(np.median(prep) * 1e3))


TRACE_DUMP = "--trace" in sys.argv[1:]
CHAOS = "--chaos" in sys.argv[1:]
CHAOS_CONCURRENT = "--chaos-concurrent" in sys.argv[1:]
OOB = "--oob" in sys.argv[1:]


def _oob_arm():
    """--oob: the out-of-core acceptance arm (exec/morsel.py) — SF10 on
    an SF1 device budget.  OTB_DEVICE_CACHE_BYTES is capped at what the
    cap-SF dataset would occupy staged (the "SF1 device"), then
    Q1/Q3/Q5 run at BENCH_OOB_SF through the morsel tier: the dominant
    scan streams in fixed-shape pinned chunks, blocking operators
    decompose per chunk, and the answer must be bit-identical to the
    uncapped in-memory run.  Prints ONE JSON line; per query it
    reports gb_touched / gb_per_s (bytes-touched throughput, the
    out-of-core figure of merit vs gb_per_s_in_memory), chunk count,
    chunk_downshifts, bytes_streamed, bit_identical, and
    warm_programs_compiled (MUST be 0: chunk count/offsets never reach
    a program key, so a warm stream recompiles nothing).  Each query
    also carries the compressed-residency block (storage/codec.py):
    bytes_logical / bytes_resident / effective_cache_ratio of the live
    pool after the streamed run, plus a codec-off control arm
    (OTB_CODEC=0, raw residency, SAME streamed query) reporting
    raw_ms / gb_per_s_raw / x_codec_off (the GB/s-touched delta the
    codecs buy) and bit_identical_codec_off (encoded execution must
    return byte-for-byte the raw arm's rows).  Knobs:
    BENCH_OOB_SF (default 10), BENCH_OOB_CAP_SF (default 1),
    BENCH_REPEAT (default 3) — smoke runs use e.g. BENCH_OOB_SF=0.2
    BENCH_OOB_CAP_SF=0.02."""
    from opentenbase_tpu.exec import morsel as morsel_mod
    from opentenbase_tpu.exec.session import LocalNode, Session
    from opentenbase_tpu.storage import codec
    from opentenbase_tpu.storage.batch import size_class
    from opentenbase_tpu.storage.bufferpool import POOL
    from opentenbase_tpu.tpch import datagen
    from opentenbase_tpu.tpch.queries import Q
    from opentenbase_tpu.tpch.schema import SCHEMA

    sf = float(os.environ.get("BENCH_OOB_SF", "10"))
    cap_sf = float(os.environ.get("BENCH_OOB_CAP_SF", "1"))
    repeat = max(1, int(os.environ.get("BENCH_REPEAT", "3")))

    t0 = time.time()
    data = datagen.generate(sf=sf)
    gen_s = time.time() - t0
    n_rows = len(data["lineitem"]["l_orderkey"])

    # the SF-cap device budget: what the FULL cap-SF dataset would
    # occupy staged (value + MVCC sys columns, size_class padding) —
    # a device sized to hold SF1 resident, which SF10 streams through
    cap = 0
    for cols in data.values():
        rows = len(next(iter(cols.values())))
        cap += size_class(max(int(rows * cap_sf / sf), 1)) \
            * (len(cols) + 4) * 8
    os.environ["OTB_DEVICE_CACHE_BYTES"] = str(cap)

    node = LocalNode()
    s = Session(node)
    s.execute(SCHEMA)
    for tname in ("region", "nation", "supplier", "customer",
                  "orders", "lineitem"):
        td = node.catalog.table(tname)
        nn = len(next(iter(data[tname].values())))
        s._insert_rows(td, node.stores[tname], data[tname], nn)

    ladder = []
    for qn in (1, 3, 5):
        # uncapped in-memory truth + timing (the comparison arm)
        s.execute("set morsel = off")
        ref = s.query(Q[qn])
        eng_mem, _ = _time(lambda: s.query(Q[qn]),
                           max(1, repeat // 2))
        # the streamed arm: auto-activation under the capped budget
        s.execute("set morsel = auto")
        POOL.clear()
        m0 = morsel_mod.stats_snapshot()
        c0 = _compile_snapshot()
        t1 = time.perf_counter()
        got = s.query(Q[qn])
        cold = time.perf_counter() - t1
        c1 = _compile_snapshot()
        times = []
        for _ in range(repeat):
            t1 = time.perf_counter()
            s.query(Q[qn])
            times.append(time.perf_counter() - t1)
        c2 = _compile_snapshot()
        m1 = morsel_mod.stats_snapshot()
        eng = min(times)
        gb = _gb_touched(qn, data)
        res = _residency_block()
        pool_snap = POOL.totals()

        # codec-off control: the SAME streamed query with OTB_CODEC=0
        # (raw device residency) — encoded execution must be
        # bit-identical, and the GB/s-touched delta is what compressed
        # residency buys end to end under the same cap
        codec_env = os.environ.get("OTB_CODEC")
        os.environ["OTB_CODEC"] = "0"
        codec.reset_state()
        POOL.clear()
        try:
            got_raw = s.query(Q[qn])
            raw_times = []
            for _ in range(max(1, repeat // 2)):
                t1 = time.perf_counter()
                s.query(Q[qn])
                raw_times.append(time.perf_counter() - t1)
            eng_raw = min(raw_times)
        finally:
            if codec_env is None:
                os.environ.pop("OTB_CODEC", None)
            else:
                os.environ["OTB_CODEC"] = codec_env
            codec.reset_state()
            POOL.clear()

        entry = {"config": f"Q{qn} oob SF{sf:g}",
                 "engine_ms": eng * 1e3, "cold_ms": cold * 1e3,
                 "in_memory_ms": eng_mem * 1e3,
                 "x_in_memory": eng / eng_mem,
                 "gb_touched": gb, "gb_per_s": gb / eng,
                 "gb_per_s_in_memory": gb / eng_mem,
                 "streamed": m1["streams"] - m0["streams"] > 0,
                 "chunks": m1["chunks"] - m0["chunks"],
                 "chunk_downshifts": m1["chunk_downshifts"]
                 - m0["chunk_downshifts"],
                 "bytes_streamed": m1["bytes_streamed"]
                 - m0["bytes_streamed"],
                 "bit_identical": _rows_close(got, ref),
                 "warm_programs_compiled": c2[0] - c1[0],
                 **res,
                 "raw_ms": eng_raw * 1e3,
                 "gb_per_s_raw": gb / eng_raw,
                 "x_codec_off": eng_raw / eng,
                 "bit_identical_codec_off": got == got_raw}
        entry.update(_compile_counters(c0, c1))
        ladder.append(entry)
        s.execute("set morsel = off")

    head = ladder[0]
    # the codec-off control clears the pool; report the LAST encoded
    # run's live-pool numbers, not the post-clear zeros
    pool = pool_snap
    out = {
        "metric": f"out-of-core Q1 SF{sf:g} bytes-touched throughput "
                  f"(SF{cap_sf:g}-sized device cache, {platform})",
        "value": round(head["gb_per_s"], 3),
        "unit": "GB/s",
        "vs_baseline": round(head["gb_per_s"]
                             / head["gb_per_s_in_memory"], 3)
        if head["gb_per_s_in_memory"] else 0.0,
        "device_cache_bytes": cap,
        # compressed residency: the effective device-cache multiplier
        # (min over Q1/Q3/Q5 — the acceptance floor is >= 2.5x)
        "effective_cache_x": round(
            min(e["effective_cache_ratio"] for e in ladder), 3),
        "ladder": [{k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in e.items()} for e in ladder],
        "pin_ledger": POOL.check_pin_ledger(),
        "pool": {k: pool[k] for k in ("bytes_live", "chunks_live",
                                      "evictions", "uploaded_bytes")},
    }
    out["device"] = DEVICE
    print(json.dumps(out))
    print(f"# oob: sf={sf} cap_sf={cap_sf} cap={cap} rows={n_rows} "
          f"datagen={gen_s:.1f}s platform={platform}", file=sys.stderr)


def _chaos_arm():
    """--chaos: point reads against a live TCP cluster while one DN
    flaps — wire-level close faults (utils/faultinject.py) tear dn0's
    conversations every BENCH_CHAOS_FLAP_EVERY ops.  Prints ONE JSON
    line: p50/p99 latency, error rate, wrong-result count (must be 0:
    a retried or failed read may error but never lie), and the
    otbguard counters (retries, breaker trips, half-open recoveries)
    — the ISSUE-8 acceptance numbers under sustained flapping."""
    import shutil
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.gtm.server import GtmCore, GtmServer
    from opentenbase_tpu.obs.metrics import REGISTRY
    from opentenbase_tpu.net.dn_server import DnServer
    from opentenbase_tpu.parallel.cluster import Cluster
    from opentenbase_tpu.utils import faultinject as FI

    n_ops = int(os.environ.get("BENCH_CHAOS_OPS", "400"))
    flap_every = int(os.environ.get("BENCH_CHAOS_FLAP_EVERY", "50"))
    # fast breaker so trips AND half-open recoveries land inside the
    # run (production defaults are read per-call from the same knobs)
    os.environ.setdefault("OTB_BREAKER_THRESHOLD", "3")
    os.environ.setdefault("OTB_BREAKER_COOLDOWN", "0.2")
    os.environ.setdefault("OTB_RPC_RETRIES", "2")

    d = tempfile.mkdtemp(prefix="otb-chaos-")
    Cluster(n_datanodes=2, datadir=d).checkpoint()
    gtm = GtmServer(GtmCore(os.path.join(d, "gtm.json"))).start()
    catalog_path = os.path.join(d, "catalog.json")
    servers = [DnServer(i, os.path.join(d, f"dn{i}"), catalog_path,
                        gtm_addr=(gtm.host, gtm.port)).start()
               for i in range(2)]
    cluster = Cluster.connect(catalog_path,
                              [(s.host, s.port) for s in servers],
                              (gtm.host, gtm.port))
    try:
        s = ClusterSession(cluster)
        s.execute("create table chaos_kv (k bigint primary key, "
                  "v bigint) distribute by shard(k)")
        s.execute("insert into chaos_kv values " + ", ".join(
            f"({i}, {i * 3})" for i in range(64)))

        lat, errors, wrong = [], 0, 0
        wv0 = _wait_snapshot()
        t_all = time.perf_counter()
        for i in range(n_ops):
            if i and i % flap_every == 0:
                # flap dn0: tear its next 6 wire conversations —
                # enough failed attempts to trip the breaker through
                # the retry budget, then let it half-open-recover
                FI.arm_wire("dn0.recv", "close", times=6)
            k = i % 64
            t0 = time.perf_counter()
            try:
                rows = s.query(f"select v from chaos_kv where k = {k}")
                if rows != [(k * 3,)]:
                    wrong += 1
            except Exception:   # noqa: BLE001 — the error rate IS the metric
                errors += 1
            lat.append(time.perf_counter() - t0)
        wall_s = time.perf_counter() - t_all
        FI.disarm_wire()

        counters = {}
        for name, labels, kind, value in REGISTRY.samples():
            if kind == "counter" and name.startswith("otb_guard_"):
                counters[name] = counters.get(name, 0) + int(value)

        # flight-recorder smoke: the flapping DN tripped the breaker,
        # so at least one postmortem bundle must exist AND round-trip
        # through JSON — a chaos run that leaves no forensics is a
        # regression in the recorder, not a quiet success
        from opentenbase_tpu.obs import xray
        bundles = xray.flights()
        assert bundles, "DN flap produced no flight bundle"
        for b in bundles:
            json.loads(json.dumps(b))

        ms = np.asarray(lat) * 1e3
        out = {
            "metric": "chaos point-read p99 (one DN flapping)",
            "value": round(float(np.percentile(ms, 99)), 3),
            "unit": "ms",
            "ops": n_ops,
            "wall_s": round(wall_s, 2),
            "p50_ms": round(float(np.percentile(ms, 50)), 3),
            "p99_ms": round(float(np.percentile(ms, 99)), 3),
            "error_rate": round(errors / n_ops, 4),
            "wrong_results": wrong,
            "guard_counters": dict(sorted(counters.items())),
            "flight_bundles": len(bundles),
            "wait_events": _wait_block(wv0),
        }
        out["device"] = DEVICE
        print(json.dumps(out))
    finally:
        FI.disarm_wire()
        res = getattr(cluster, "_resolver", None)
        if res is not None:
            res.stop()
        for srv in servers:
            try:
                srv.stop()
            except Exception:   # noqa: BLE001 — best-effort teardown
                pass
        gtm.stop()
        shutil.rmtree(d, ignore_errors=True)


def _rows_close(got, want):
    """Wrong-result check: exact for ints/strings, tight relative
    tolerance for floats (a degraded/spill re-execution may legally
    re-associate float reductions; it may never change an answer)."""
    if got == want:
        return True
    if got is None or want is None or len(got) != len(want):
        return False
    for rg, rw in zip(got, want):
        if len(rg) != len(rw):
            return False
        for a, b in zip(rg, rw):
            if isinstance(a, float) or isinstance(b, float):
                if abs(float(a) - float(b)) > 1e-6 * max(
                        1.0, abs(float(b))):
                    return False
            elif a != b:
                return False
    return True


def _snap_certificate():
    """Post-hoc otbsnap certificate for the current process: run the
    Adya G1/G-SI checker (analysis/sicheck.py) over the in-memory
    snapcheck history, persist the history to $OTB_SNAP_HISTORY, and
    report the runtime sanitizer's violation count.  The bench gates on
    si_anomalies == 0 and snapcheck_violations == 0 — the three
    serving tiers (cache / replica / shared) certified against the
    commit history they actually raced."""
    from opentenbase_tpu.analysis import sicheck
    from opentenbase_tpu.utils import snapcheck
    res = sicheck.check_history(snapcheck.history_events())
    if snapcheck.history_on():
        snapcheck.save_history()
    return {"si_anomalies": len(res["anomalies"]),
            "si_reads": res["reads"], "si_writes": res["writes"],
            "si_by_source": res["by_source"],
            "snapcheck_violations": len(snapcheck.violations()),
            "si_detail": res["anomalies"][:5]}


def _chaosc_streams(analytics):
    """The mixed chaos workload: point SELECTs (one tiny coalescable
    signature), a small-agg signature, and — unless disabled for smoke
    runs — the Q1-varying-literal / Q3 / Q5 analytics shapes from the
    qps arm.  Key 251 is reserved for the poison offender's stream and
    never appears in a clean literal."""
    points = [f"select v from qps_kv where k = {(i * 37) % 250}"
              for i in range(64)]
    aggs = [f"select sum(v), count(*) from qps_kv where k < {60 + 7 * i}"
            for i in range(8)]
    mixed = []
    if analytics:
        _, same, _ = _qps_queries()
        from opentenbase_tpu.tpch.queries import Q
        for i in range(16):
            mixed.append(points[i % len(points)])
            mixed.append(same[i % len(same)])
            mixed.append(aggs[i % len(aggs)])
            if i % 5 == 0:
                mixed.append(Q[3])
            if i % 8 == 0:
                mixed.append(Q[5])
            mixed.append(points[(i * 3 + 1) % len(points)])
    else:
        for i in range(16):
            mixed.append(points[i % len(points)])
            mixed.append(aggs[i % len(aggs)])
            mixed.append(points[(i * 3 + 1) % len(points)])
    return mixed


def _chaosc_flap_cluster(tmp):
    """Plane B of --chaos-concurrent: a live 2-DN TCP cluster whose
    dn0 wire will flap mid-run.  Gentle knobs — the retry budget must
    absorb every tear (times=2 faults < 3 retries, breaker threshold
    high enough to never fast-fail): errors here are COLLATERAL."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.gtm.server import GtmCore, GtmServer
    from opentenbase_tpu.net.dn_server import DnServer
    from opentenbase_tpu.parallel.cluster import Cluster

    os.environ.setdefault("OTB_RPC_RETRIES", "3")
    os.environ.setdefault("OTB_BREAKER_THRESHOLD", "16")
    Cluster(n_datanodes=2, datadir=tmp).checkpoint()
    gtm = GtmServer(GtmCore(os.path.join(tmp, "gtm.json"))).start()
    catalog_path = os.path.join(tmp, "catalog.json")
    servers = [DnServer(i, os.path.join(tmp, f"dn{i}"), catalog_path,
                        gtm_addr=(gtm.host, gtm.port)).start()
               for i in range(2)]
    cluster = Cluster.connect(catalog_path,
                              [(s.host, s.port) for s in servers],
                              (gtm.host, gtm.port))
    s = ClusterSession(cluster)
    s.execute("create table chaos_kv (k bigint primary key, v bigint) "
              "distribute by shard(k)")
    s.execute("insert into chaos_kv values " + ", ".join(
        f"({i}, {i * 3})" for i in range(64)))
    # one hot standby per DN, registered as a read replica: the chaos
    # run exercises the replica serving tier (net/guard.py hwm gate)
    # under live DML + wire flaps, and the otbsnap certificate checks
    # its reads against the commit history
    from opentenbase_tpu.storage.replication import (DnStandbyServer,
                                                     HotStandby)
    rep_servers = []
    for i, srv in enumerate(servers):
        sb = HotStandby(os.path.join(tmp, f"chaos_sb_dn{i}"), index=i)
        rsrv = DnStandbyServer(sb).start()
        srv.node.attach_standby(rsrv.host, rsrv.port)
        cluster.register_read_replica(i, rsrv.host, rsrv.port,
                                      sb.datadir)
        rep_servers.append(rsrv)
    s.execute("set replica_reads = on")
    return cluster, gtm, servers + rep_servers


def _chaos_concurrent_arm():
    """--chaos-concurrent: the full otbshield acceptance run.  64
    client threads (56 through the coalescing scheduler on mixed
    Q1/Q3/agg/point ops, 8 point-reading a live TCP cluster) while a
    chaos driver injects, concurrently:

    - a poisoned literal (key 251) that kills any batched dispatch it
      rides in — bisection must fail ONLY the offender's queries and
      repeat offenses must trip the signature quarantine;
    - cancel storms (random sessions' cancel_event set mid-flight);
    - device OOM at dispatch (alternating recover-after-eviction and
      degrade-to-spill severities);
    - DN wire flaps on the TCP plane (otbguard retries absorb them);
    - shed pressure (queue_depth below the client count).

    Prints ONE JSON line: qps + p50/p99 over clean queries, the error
    split (offender_poison / offender_cancel / offender_timeout / shed
    vs collateral — collateral MUST be 0), wrong_results (MUST be 0),
    degraded count (injected OOM answers, not errors), and the slot /
    lease ledgers (MUST balance: zero leaks after drain).  Knobs:
    BENCH_CHAOSC_SECONDS (8), BENCH_CHAOSC_WARM_SECONDS (2),
    BENCH_CHAOSC_CLIENTS (64), BENCH_CHAOSC_SF (0.02),
    BENCH_CHAOSC_ANALYTICS=0 to drop Q1/Q3/Q5 for quick smoke runs."""
    import shutil
    import threading
    from opentenbase_tpu.exec import scheduler as sched_mod
    from opentenbase_tpu.exec import shield
    from opentenbase_tpu.exec.session import Session
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.utils import faultinject as FI

    seconds = float(os.environ.get("BENCH_CHAOSC_SECONDS", "8"))
    warm_s = float(os.environ.get("BENCH_CHAOSC_WARM_SECONDS", "2"))
    n_clients = int(os.environ.get("BENCH_CHAOSC_CLIENTS", "64"))
    sf = float(os.environ.get("BENCH_CHAOSC_SF", "0.02"))
    analytics = os.environ.get("BENCH_CHAOSC_ANALYTICS", "1") != "0"
    # short cooldown so the quarantine trips AND lifts inside the run
    # (brownout-and-recover, not a permanent serial lane)
    os.environ.setdefault("OTB_SHIELD_COOLDOWN_S", "2")

    n_flap = max(1, min(8, n_clients // 8))
    n_sched = n_clients - n_flap

    # otbsnap: the chaos run doubles as the snapshot-visibility
    # acceptance shard — sanitizer live on every serve point, bounded
    # SI history recorded for the post-hoc G1/G-SI checker, and the
    # committed witness (analysis/visibility_witness.json) refreshed
    # from what this shard actually served
    from opentenbase_tpu.utils import snapcheck as snapcheck_mod
    os.environ.setdefault("OTB_SNAPCHECK", "1")
    os.environ.setdefault("OTB_SNAP_HISTORY", os.path.join(
        tempfile.gettempdir(), f"otb-chaosc-history-{os.getpid()}.json"))
    snapcheck_mod.reset()

    node, setup_s, _ = _qps_setup(sf)
    mixed = _chaosc_streams(analytics)
    poison_sql = "select v from qps_kv where k = 251"
    refs = {}
    for q in sorted(set(mixed + [poison_sql])):
        refs[q] = setup_s.execute(q)[-1].rows   # serial truth + compile

    tmp = tempfile.mkdtemp(prefix="otb-chaosc-")
    cluster, fgtm, servers = _chaosc_flap_cluster(tmp)

    sched_mod.reset_stats()
    shield.reset_stats()
    FI.arm_poison(251, times=-1)

    stats = {"ok": 0, "wrong": 0, "offender_poison": 0,
             "offender_cancel": 0, "offender_timeout": 0, "shed": 0,
             "collateral": 0}
    flap = {"ops": 0, "errors": 0, "wrong": 0}
    coll_samples = []
    lats = []
    sessions = []
    lock = threading.Lock()
    stop_at = [0.0]
    timed_from = [float("inf")]

    def classify(msg):
        if "poison-literal" in msg:
            return "offender_poison"
        if "user request" in msg:
            return "offender_cancel"
        if "statement timeout" in msg:
            return "offender_timeout"
        if "shed" in msg:
            return "shed"
        return "collateral"

    def sched_client(ci):
        sess = Session(node)
        with lock:
            sessions.append(sess)
        offender = ci % 7 == 0
        i = ci
        while time.perf_counter() < stop_at[0]:
            sql = (poison_sql if offender and i % 4 == 0
                   else mixed[i % len(mixed)])
            t0 = time.perf_counter()
            try:
                rows = sched.run(sess, sql)[-1].rows
                dt = time.perf_counter() - t0
                with lock:
                    if _rows_close(rows, refs[sql]):
                        stats["ok"] += 1
                    else:
                        stats["wrong"] += 1
                    if t0 >= timed_from[0]:
                        lats.append(dt)
            except Exception as e:  # noqa: BLE001 — the split IS the metric
                kind = classify(str(e))
                with lock:
                    stats[kind] += 1
                    if kind == "collateral" and len(coll_samples) < 3:
                        coll_samples.append(str(e)[:160])
            i += 1

    def flap_client(fi):
        fsess = ClusterSession(cluster)
        i = fi
        while time.perf_counter() < stop_at[0]:
            k = i % 64
            try:
                rows = fsess.query(f"select v from chaos_kv "
                                   f"where k = {k}")
                with lock:
                    flap["ops"] += 1
                    if rows != [(k * 3,)]:
                        flap["wrong"] += 1
            except Exception:  # noqa: BLE001 — collateral by definition
                with lock:
                    flap["ops"] += 1
                    flap["errors"] += 1
            i += 1

    def dml_client():
        # live write stream on the cluster plane, keys >= 1000 so the
        # verified point reads (k < 64) never see it — its job is to
        # move store versions + the replica hwm under the sanitizer
        # and to populate the SI history's write half
        dsess = ClusterSession(cluster)
        j = 0
        while time.perf_counter() < stop_at[0]:
            k = 1000 + (j % 50)
            try:
                if j % 2 == 0:
                    dsess.execute(
                        f"insert into chaos_kv values ({k}, {j})")
                else:
                    dsess.execute(
                        f"delete from chaos_kv where k = {k}")
            except Exception:  # noqa: BLE001 — flaps hit DML too
                pass
            j += 1
            time.sleep(0.02)

    def chaos_driver():
        n = 0
        while time.perf_counter() < stop_at[0]:
            time.sleep(0.4)
            n += 1
            with lock:
                live = list(sessions)
            if live:   # cancel storm: two victims per tick
                live[(n * 13) % len(live)].cancel_event.set()
                live[(n * 29) % len(live)].cancel_event.set()
            if n % 2 == 0:
                # OOM at dispatch: odd doses recover after eviction,
                # every 4th dose defeats the retry → spill degradation
                FI.arm_oom("dispatch", times=2 if n % 4 == 0 else 1)
            else:
                FI.arm_wire("dn0.recv", "close", times=2)

    # queue_depth below the client count: admission overflow IS the
    # shed-pressure injection (classified separately, never collateral)
    sched = sched_mod.Scheduler(node=node,
                                queue_depth=max(8, 3 * n_sched // 4),
                                max_batch=16)
    try:
        stop_at[0] = time.perf_counter() + warm_s + seconds
        timed_from[0] = time.perf_counter() + warm_s
        threads = ([threading.Thread(target=sched_client, args=(ci,),
                                     daemon=True)
                    for ci in range(n_sched)]
                   + [threading.Thread(target=flap_client, args=(fi,),
                                      daemon=True)
                      for fi in range(n_flap)]
                   + [threading.Thread(target=dml_client, daemon=True),
                      threading.Thread(target=chaos_driver,
                                       daemon=True)])
        t_begin = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_begin
        timed_wall = min(wall, seconds)
    finally:
        FI.disarm_poison()
        FI.disarm_oom()
        FI.disarm_wire()
        sched.stop()
        res = getattr(cluster, "_resolver", None)
        if res is not None:
            res.stop()
        for srv in servers:
            try:
                srv.stop()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        fgtm.stop()
        shutil.rmtree(tmp, ignore_errors=True)

    # otbsnap certificate: SI-check the recorded history, persist the
    # witnessed serve-point set into the committed witness file (the
    # lint gate cross-checks witnessed points against the statically
    # gated set)
    cert = _snap_certificate()
    snapcheck_mod.save_report()

    acq, rel = sched_mod.slot_balance()
    lst = sched.gtm.resq_stats()
    live_slots = sum(sched.gtm.resq_counts().values())
    sst = shield.stats_snapshot()
    dst = sched_mod.stats_snapshot()
    lats.sort()
    n_queries = sum(stats.values()) + flap["ops"]
    collateral = stats["collateral"] + flap["errors"]
    out = {
        "metric": f"chaos-concurrent p99 ({n_clients} clients, DN flap"
                  f" + cancel storm + OOM + poison, {platform})",
        "value": round(_qps_pct(lats, 0.99) * 1e3, 3),
        "unit": "ms",
        "clients": {"scheduler": n_sched, "flap": n_flap},
        "queries": n_queries,
        "qps": round(len(lats) / timed_wall, 1) if timed_wall else 0.0,
        "p50_ms": round(_qps_pct(lats, 0.50) * 1e3, 3),
        "p99_ms": round(_qps_pct(lats, 0.99) * 1e3, 3),
        "wrong_results": stats["wrong"] + flap["wrong"],
        "errors": {
            "offender_poison": stats["offender_poison"],
            "offender_cancel": stats["offender_cancel"],
            "offender_timeout": stats["offender_timeout"],
            "shed": stats["shed"],
            "collateral": collateral,
        },
        "collateral_rate": round(collateral / max(1, n_queries), 6),
        "degraded": sst["degraded"],
        "oom_dispatches": sst["oom_dispatches"],
        "oom_retries": sst["oom_retries"],
        "batch_failures": sst["batch_failures"],
        "isolated": sst["isolated"],
        "quarantined": sst["quarantined"],
        "batch_rate": round(dst["batched"] / dst["admitted"], 3)
        if dst["admitted"] else 0.0,
        "slot_ledger": {"acquired": acq, "released": rel,
                        "leaked": acq - rel},
        "gtm_leases": {**lst, "live_slots": live_slots},
        "flap": dict(flap),
        "snapshot_soundness": cert,
    }
    if coll_samples:
        out["collateral_samples"] = coll_samples
    out["device"] = DEVICE
    print(json.dumps(out))
    ok = (collateral == 0 and out["wrong_results"] == 0
          and acq == rel and live_slots == 0
          and lst["acquired"] == lst["released"] + lst["expired"]
          and cert["si_anomalies"] == 0
          and cert["snapcheck_violations"] == 0)
    print(f"# chaos-concurrent: {'PASS' if ok else 'FAIL'} "
          f"(collateral={collateral} wrong={out['wrong_results']} "
          f"slots {acq}/{rel} si={cert['si_anomalies']} "
          f"snapviol={cert['snapcheck_violations']} leases {lst})",
          file=sys.stderr)
    if not ok:
        sys.exit(1)


def _phases(qs):
    """Span-tree phase breakdown of the arm's last warm run
    (session.last_query_stats(); all zeros when OTB_TRACE=0)."""
    return {k: round(float(qs.get(k, 0.0)), 3)
            for k in ("plan_ms", "stage_ms", "execute_ms",
                      "exchange_ms", "finalize_ms")}


def _dump_trace(cfg):
    """--trace: full last-query span tree, one JSON line on stderr
    (stdout stays the single bench JSON line).  Cluster runs include
    the piggy-backed remote DN/GTM subtrees — obs/xray.py grafts them
    into the CN tree before the trace reaches the ring."""
    if not TRACE_DUMP:
        return
    from opentenbase_tpu.obs import trace as obs_trace
    qt = obs_trace.last_trace()
    if qt is not None:
        print(json.dumps({"trace_for": cfg, **qt.to_dict()}),
              file=sys.stderr)


def _latency_block():
    """p50/p95/p99 per tier from the otb_query_ms histograms — the
    registry aggregates EVERY query the process ran, not just the
    min-of-warm arms the ladder reports."""
    from opentenbase_tpu.obs.metrics import REGISTRY
    out = {}
    for name, labels, kind, value in REGISTRY.samples():
        if kind != "histogram" or \
                not name.startswith("otb_query_ms_"):
            continue
        tag = name[len("otb_query_ms_"):]
        if tag not in ("count", "p50", "p95", "p99"):
            continue
        lbl = ",".join(f"{k}={v}" for k, v in labels) or "all"
        out.setdefault(lbl, {})[tag] = (
            int(value) if tag == "count" else round(float(value), 3))
    return out


def _wait_snapshot():
    """(event -> (count, total_ms)) snapshot of the cumulative
    wait-event registry, so arms can report their own deltas."""
    from opentenbase_tpu.obs import xray
    return {ev: (cnt, tot) for ev, cnt, tot, _p50, _p95, _p99
            in xray.wait_rows()}


def _wait_block(w0=None):
    """Where this arm's threads actually blocked: top-5 wait events by
    total stalled ms (delta against the `w0` snapshot when given) with
    the cumulative p50/p95/p99 per event — the bench-side twin of the
    otb_wait_events view."""
    from opentenbase_tpu.obs import xray
    w0 = w0 or {}
    rows = []
    for ev, cnt, tot, p50, p95, p99 in xray.wait_rows():
        c0, t0 = w0.get(ev, (0, 0.0))
        if cnt - c0 <= 0:
            continue
        rows.append((tot - t0, ev, cnt - c0, p50, p95, p99))
    rows.sort(reverse=True)
    return {ev: {"count": cnt, "total_ms": round(tot, 3),
                 "p50_ms": round(p50, 3), "p95_ms": round(p95, 3),
                 "p99_ms": round(p99, 3)}
            for tot, ev, cnt, p50, p95, p99 in rows[:5]}


def _mat_counters(x0, x1):
    """Ladder-entry materialization telemetry: deferred vs. eager
    column-gathers and bytes gathered between two exec_stats snapshots
    (exec/executor.py EXEC_STATS; trace-time counts for compiled
    tiers)."""
    return {
        "mat_deferred_cols": x1["deferred_cols"] - x0["deferred_cols"],
        "mat_eager_cols": x1["eager_cols"] - x0["eager_cols"],
        "mat_cols_gathered": x1["cols_materialized"]
        - x0["cols_materialized"],
        "mat_bytes_gathered": x1["bytes_materialized"]
        - x0["bytes_materialized"],
        "join_host_syncs": x1["host_syncs"] - x0["host_syncs"],
    }


def _compile_snapshot():
    """Total (programs_compiled, compile_ms) across every plancache
    tier — the otb_plancache counters the arms report as deltas so a
    compile storm is visible per-arm in the perf trajectory."""
    from opentenbase_tpu.exec import plancache
    c, ms = 0, 0.0
    for _t, _h, _m, comp, cms, _e, _l in plancache.stats():
        c += comp
        ms += cms
    return c, ms


def _compile_counters(c0, c1):
    return {"programs_compiled": c1[0] - c0[0],
            "compile_ms": round(c1[1] - c0[1], 3)}


def _residency_block():
    """Compressed-residency telemetry (storage/codec.py): what the
    live pool entries would occupy UNENCODED (bytes_logical) vs the
    actual post-encoding device bytes (bytes_resident) — their ratio
    is the effective device-cache multiplier the codecs buy."""
    from opentenbase_tpu.storage.bufferpool import POOL
    t = POOL.totals()
    res = t["bytes_live"]
    return {"bytes_logical": t["bytes_logical"],
            "bytes_resident": res,
            "effective_cache_ratio": round(t["bytes_logical"] / res, 3)
            if res else 0.0}


def _mesh_session(data):
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.parallel.cluster import Cluster
    ndn = max(len(jax.devices()), 1)
    s = ClusterSession(Cluster(n_datanodes=ndn))
    from opentenbase_tpu.tpch.schema import SCHEMA
    s.execute(SCHEMA)
    for tname in ("region", "nation", "supplier", "customer", "part",
                  "partsupp", "orders", "lineitem"):
        td = s.cluster.catalog.table(tname)
        n = len(next(iter(data[tname].values())))
        s._insert_rows(td, data[tname], n)
    return s


# ---------------------------------------------------------------------------
# BENCH_MODE=qps — the serving-tier sustained-throughput arm
# ---------------------------------------------------------------------------

def _qps_pct(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              int(round(q * (len(sorted_vals) - 1))))
    return float(sorted_vals[idx])


def _qps_queries():
    """Three SQL streams, all same-signature-friendly to different
    degrees.  point_sig: point SELECTs with a varying key literal —
    every query masks to ONE tiny fused program, so per-query host
    overhead dominates and coalescing amortizes it (the decisive
    batching demonstration; on a 1-core CPU host the analytics shapes
    are compute-bound and batching can only tie serial).  q1_sig: Q1
    with a varying shipdate literal — one analytics signature.  mixed:
    Q1 variants + Q3 + Q5 + point SELECTs — several signatures plus
    join shapes."""
    from opentenbase_tpu.tpch.queries import Q
    base = Q[1].replace("date '1998-12-01' - interval '90' day",
                        "date '{}'")
    same = [base.format(f"1998-{m:02d}-{d:02d}")
            for m in (7, 8, 9) for d in (2, 9, 16, 23)]
    points = [f"select v from qps_kv where k = {(i * 37) % 400}"
              for i in range(64)]
    mixed = []
    for i in range(16):
        mixed.append(same[i % len(same)])
        if i % 4 == 0:
            mixed.append(Q[3])
        if i % 8 == 0:
            mixed.append(Q[5])
        mixed.append(points[i % len(points)])
    return points, same, mixed


def _qps_setup(sf):
    from opentenbase_tpu.exec.session import LocalNode, Session
    from opentenbase_tpu.tpch import datagen
    from opentenbase_tpu.tpch.schema import SCHEMA
    data = datagen.generate(sf=sf)
    node = LocalNode()
    s = Session(node)
    s.execute(SCHEMA)
    for tname in ("region", "nation", "supplier", "customer",
                  "orders", "lineitem"):
        td = node.catalog.table(tname)
        nn = len(next(iter(data[tname].values())))
        s._insert_rows(td, node.stores[tname], data[tname], nn)
    s.execute("create table qps_kv (k bigint, v bigint)")
    rows = ", ".join(f"({i}, {i * 7})" for i in range(400))
    s.execute(f"insert into qps_kv values {rows}")
    return node, s, len(data["lineitem"]["l_orderkey"])


def _qps_serial(node, stream, n):
    """Serial-loop baseline: one session, one query at a time — the
    number the scheduler arms must beat on sustained throughput."""
    from opentenbase_tpu.exec.session import Session
    s = Session(node)
    lats = []
    t_begin = time.perf_counter()
    for i in range(n):
        t0 = time.perf_counter()
        s.execute(stream[i % len(stream)])
        lats.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t_begin
    lats.sort()
    return {"clients": 1, "queries": n, "qps": n / wall,
            "p50_ms": _qps_pct(lats, 0.50) * 1e3,
            "p99_ms": _qps_pct(lats, 0.99) * 1e3}


def _qps_drive(sched, node, stream, clients, seconds):
    """Closed-loop load: `clients` threads, each its own Session over
    the shared node, issuing through the scheduler back-to-back.
    Returns (merged latencies s, shed count, wall s)."""
    import threading
    from opentenbase_tpu.exec.session import Session
    lats = [[] for _ in range(clients)]
    sheds = [0] * clients
    stop_at = [0.0]
    gate = threading.Barrier(clients + 1)

    def client(ci):
        s = Session(node)
        i = ci
        gate.wait()
        while time.perf_counter() < stop_at[0]:
            t0 = time.perf_counter()
            try:
                sched.run(s, stream[i % len(stream)])
                lats[ci].append(time.perf_counter() - t0)
            except Exception:
                sheds[ci] += 1
            i += 1

    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(clients)]
    for t in threads:
        t.start()
    stop_at[0] = time.perf_counter() + seconds
    t_begin = time.perf_counter()
    gate.wait()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_begin
    merged = sorted(x for per in lats for x in per)
    return merged, sum(sheds), wall


def _qps_arm(name, node, stream, clients, seconds, warm_s):
    from opentenbase_tpu.exec import scheduler as sched_mod
    sched = sched_mod.Scheduler(node=node,
                                queue_depth=max(128, 4 * clients))
    try:
        if warm_s > 0:   # untimed phase: batch-class compiles land here
            _qps_drive(sched, node, stream, clients, warm_s)
        s0 = sched_mod.stats_snapshot()
        c0 = _compile_snapshot()
        wv0 = _wait_snapshot()
        lats, shed, wall = _qps_drive(sched, node, stream, clients,
                                      seconds)
        c1 = _compile_snapshot()
        s1 = sched_mod.stats_snapshot()
    finally:
        sched.stop()
    admitted = s1["admitted"] - s0["admitted"]
    batched = s1["batched"] - s0["batched"]
    hist = {k: s1["hist"].get(k, 0) - s0["hist"].get(k, 0)
            for k in s1["hist"]
            if s1["hist"].get(k, 0) > s0["hist"].get(k, 0)}
    # otbpipe: what fraction of THIS arm's staging work the two-stage
    # pipeline hid behind device compute (delta, not lifetime ratio)
    stage_work = s1["stage_work_ms"] - s0["stage_work_ms"]
    stage_overlap = s1["stage_overlap_ms"] - s0["stage_overlap_ms"]
    return {"arm": name, "clients": clients, "replicas": 0,
            "queries": len(lats),
            "overlap_ratio": stage_overlap / stage_work
            if stage_work > 0 else 0.0,
            "pipelined": s1["pipelined_dispatches"]
            - s0["pipelined_dispatches"],
            "qps": len(lats) / wall if wall > 0 else 0.0,
            "p50_ms": _qps_pct(lats, 0.50) * 1e3,
            "p99_ms": _qps_pct(lats, 0.99) * 1e3,
            "shed": shed,
            "batch_rate": batched / admitted if admitted else 0.0,
            "batch_dispatches": s1["batch_dispatches"]
            - s0["batch_dispatches"],
            "batch_hist": " ".join(f"{k}:{v}"
                                   for k, v in sorted(hist.items())),
            "wait_events": _wait_block(wv0),
            **_compile_counters(c0, c1)}


def _qps_zipf_arm(node, clients, seconds, warm_s):
    """otbshare rung (b) under dashboard-shaped load: a zipfian-skewed
    pool of repeated statements (rank r drawn with p ~ r^-skew), every
    response verified against its serially-computed answer.  The
    sublinearity proof is `dispatches`: device dispatches stay near
    the DISTINCT statement count while served queries scale with the
    client count — repeats are CN memory hits that never touch the
    device."""
    import threading

    import numpy as np
    from opentenbase_tpu.exec import scheduler as sched_mod
    from opentenbase_tpu.exec import share as share_mod
    from opentenbase_tpu.exec.session import Session

    # otbsnap: record the SI history for this arm — every cache hit
    # lands as a read with its exact GTS-versioned key material, every
    # producing execution as a primary read, so the post-hoc checker
    # certifies result-cache serving against snapshot isolation
    from opentenbase_tpu.utils import snapcheck as snapcheck_mod
    hist_preset = bool(os.environ.get("OTB_SNAP_HISTORY", "").strip())
    if not hist_preset:
        os.environ["OTB_SNAP_HISTORY"] = os.path.join(
            tempfile.gettempdir(),
            f"otb-zipf-history-{os.getpid()}.json")
    snapcheck_mod.reset()

    n_distinct = int(os.environ.get("BENCH_QPS_ZIPF_DISTINCT", "48"))
    skew = float(os.environ.get("BENCH_QPS_ZIPF_SKEW", "1.2"))
    pool = [f"select sum(v), count(*) from qps_kv "
            f"where k < {13 * (r + 1)}" for r in range(n_distinct)]
    rng = np.random.default_rng(31)
    w = 1.0 / np.arange(1, n_distinct + 1) ** skew
    stream = [pool[i] for i in
              rng.choice(n_distinct, size=4096, p=w / w.sum())]
    expect = {}
    s = Session(node)
    for q in pool:                       # compile once + golden answers
        expect[q] = s.execute(q)[-1].rows

    lats = [[] for _ in range(clients)]
    wrong = [0] * clients
    sheds = [0] * clients
    stop_at = [0.0]

    def drive(sched, secs):
        gate = threading.Barrier(clients + 1)

        def client(ci):
            cs = Session(node)
            i = ci
            gate.wait()
            while time.perf_counter() < stop_at[0]:
                q = stream[i % len(stream)]
                t0 = time.perf_counter()
                try:
                    rows = sched.run(cs, q)[-1].rows
                    lats[ci].append(time.perf_counter() - t0)
                    if rows != expect[q]:
                        wrong[ci] += 1
                except Exception:
                    sheds[ci] += 1
                i += 1

        threads = [threading.Thread(target=client, args=(ci,),
                                    daemon=True)
                   for ci in range(clients)]
        for t in threads:
            t.start()
        stop_at[0] = time.perf_counter() + secs
        t_begin = time.perf_counter()
        gate.wait()
        for t in threads:
            t.join()
        return time.perf_counter() - t_begin

    sched = sched_mod.Scheduler(node=node,
                                queue_depth=max(128, 4 * clients))
    try:
        if warm_s > 0:
            drive(sched, warm_s)
        for per in lats:
            per.clear()
        wrong[:] = [0] * clients
        sheds[:] = [0] * clients
        s0 = sched_mod.stats_snapshot()
        w0 = share_mod.stats_snapshot()
        wv0 = _wait_snapshot()
        wall = drive(sched, seconds)
        s1 = sched_mod.stats_snapshot()
        w1 = share_mod.stats_snapshot()
    finally:
        sched.stop()
    cert = _snap_certificate()
    if not hist_preset:
        os.environ.pop("OTB_SNAP_HISTORY", None)
    merged = sorted(x for per in lats for x in per)
    hits = w1["result_cache_hits"] - w0["result_cache_hits"]
    misses = w1["result_cache_misses"] - w0["result_cache_misses"]
    return {"arm": "zipf_cache", "clients": clients, "replicas": 0,
            "si_anomalies": cert["si_anomalies"],
            "snapshot_soundness": cert,
            "queries": len(merged),
            "qps": len(merged) / wall if wall > 0 else 0.0,
            "p50_ms": _qps_pct(merged, 0.50) * 1e3,
            "p99_ms": _qps_pct(merged, 0.99) * 1e3,
            "shed": sum(sheds),
            "wrong": sum(wrong),
            "distinct": n_distinct, "skew": skew,
            "dispatches": s1["dispatches"] - s0["dispatches"],
            "cache_hits": hits,
            "cache_hit_rate": hits / (hits + misses)
            if hits + misses else 0.0,
            "fanin": w1["shared_scan_fanin"] - w0["shared_scan_fanin"],
            "wait_events": _wait_block(wv0)}


def _replica_counter(prefix):
    from opentenbase_tpu.obs.metrics import REGISTRY
    total = 0.0
    for line in REGISTRY.text().splitlines():
        if line.startswith(prefix) and not line.startswith("#"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _qps_replica_setup(n_replicas, tmpdir):
    """A 2-DN cluster with `n_replicas` hot standbys per DN registered
    as read replicas (0 = primary-only baseline)."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.parallel.cluster import Cluster
    from opentenbase_tpu.storage.replication import (DnStandbyServer,
                                                     HotStandby)
    cl = Cluster(n_datanodes=2,
                 datadir=os.path.join(tmpdir, f"cl_r{n_replicas}"))
    s = ClusterSession(cl)
    s.execute("create table rkv (k bigint primary key, v bigint)"
              " distribute by shard(k)")
    rows = ", ".join(f"({i}, {i * 7})" for i in range(400))
    s.execute(f"insert into rkv values {rows}")
    servers = []
    for rep in range(n_replicas):
        for i, dn in enumerate(cl.datanodes):
            sb = HotStandby(
                os.path.join(tmpdir, f"sb_r{n_replicas}_{rep}_dn{i}"),
                index=i)
            srv = DnStandbyServer(sb).start()
            dn.attach_standby(srv.host, srv.port)
            cl.register_read_replica(i, srv.host, srv.port, sb.datadir)
            servers.append(srv)
    if n_replicas:
        s.execute("set replica_reads = on")
    return cl, servers


def _qps_replica_arm(n_replicas, clients, seconds, tmpdir):
    """Closed-loop snapshot point reads over the cluster; every result
    is checked against the known v = 7k ground truth — routing to a
    standby must NEVER change an answer (wrong is asserted 0)."""
    import threading
    from opentenbase_tpu.exec.dist_session import ClusterSession
    cl, servers = _qps_replica_setup(n_replicas, tmpdir)
    routed0 = _replica_counter("otb_replica_reads_total")
    fall0 = _replica_counter("otb_replica_fallthrough_total")
    wv0 = _wait_snapshot()
    lats = [[] for _ in range(clients)]
    wrong = [0] * clients
    stop_at = [0.0]
    gate = threading.Barrier(clients + 1)

    def client(ci):
        s = ClusterSession(cl)
        i = ci
        gate.wait()
        while time.perf_counter() < stop_at[0]:
            k = (i * 37) % 400
            t0 = time.perf_counter()
            r = s.query(f"select v from rkv where k = {k}")
            lats[ci].append(time.perf_counter() - t0)
            if r != [(k * 7,)]:
                wrong[ci] += 1
            i += 1

    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(clients)]
    for t in threads:
        t.start()
    stop_at[0] = time.perf_counter() + seconds
    t_begin = time.perf_counter()
    gate.wait()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_begin
    for srv in servers:
        srv.stop()
    merged = sorted(x for per in lats for x in per)
    n_wrong = sum(wrong)
    assert n_wrong == 0, f"replica routing changed {n_wrong} answers"
    return {"arm": "replica_point", "clients": clients,
            "replicas": n_replicas, "queries": len(merged),
            "qps": len(merged) / wall if wall > 0 else 0.0,
            "p50_ms": _qps_pct(merged, 0.50) * 1e3,
            "p99_ms": _qps_pct(merged, 0.99) * 1e3,
            "wrong": n_wrong,
            "routed_reads":
                _replica_counter("otb_replica_reads_total") - routed0,
            "fallthrough":
                _replica_counter("otb_replica_fallthrough_total")
                - fall0,
            "wait_events": _wait_block(wv0)}


def _qps_mode():
    sf = float(os.environ.get("BENCH_SF", "0.02"))
    seconds = float(os.environ.get("BENCH_QPS_SECONDS", "4"))
    warm_s = float(os.environ.get("BENCH_QPS_WARM_SECONDS", "2"))
    clients_list = [int(c) for c in os.environ.get(
        "BENCH_QPS_CLIENTS", "8,64,256").split(",") if c.strip()]
    baseline_n = int(os.environ.get("BENCH_QPS_BASELINE_N", "60"))
    node, s, n_rows = _qps_setup(sf)
    points, same, mixed = _qps_queries()
    serial = {}
    arms = []
    for name, stream in (("point_sig", points), ("q1_sig", same),
                         ("mixed", mixed)):
        for q in sorted(set(stream)):   # compile every serial shape once
            s.execute(q)
        serial[name] = _qps_serial(node, stream, baseline_n)
        for clients in clients_list:
            arms.append(_qps_arm(name, node, stream, clients, seconds,
                                 warm_s))
    # work-sharing axis (otbshare): zipfian repeated statements — the
    # dispatch count must stay near the distinct-statement count while
    # served queries scale with clients (result-cache sublinearity)
    for clients in clients_list:
        arms.append(_qps_zipf_arm(node, clients, seconds, warm_s))
    # standby read scale-out axis: same point-read stream over a
    # cluster, replicas=0 (primary only) vs replicas=N hot standbys
    replicas_list = [int(r) for r in os.environ.get(
        "BENCH_QPS_REPLICAS", "0,2").split(",") if r.strip() != ""]
    if replicas_list:
        import tempfile
        rep_clients = clients_list[-1] if clients_list else 64
        with tempfile.TemporaryDirectory() as tmpdir:
            for n_rep in replicas_list:
                arms.append(_qps_replica_arm(n_rep, rep_clients,
                                             seconds, tmpdir))
    pick = [a for a in arms if a["arm"] == "point_sig"]
    head = next((a for a in pick if a["clients"] == 64),
                (pick or arms)[-1])
    out = {
        "metric": f"sustained QPS SF{sf:g} (point_sig, "
                  f"{head['clients']} clients, {platform})",
        "value": round(head["qps"], 1),
        "unit": "qps",
        "vs_baseline": round(head["qps"] / serial["point_sig"]["qps"], 3)
        if serial["point_sig"]["qps"] else 0.0,
        "schema": "serial: per-workload single-session loop "
                  "{clients, queries, qps, p50_ms, p99_ms}; arms: "
                  "per (workload, client-count) scheduler run "
                  "{arm, clients, replicas, queries, qps, p50_ms, "
                  "p99_ms, batch_rate = batched/admitted, "
                  "batch_dispatches, batch_hist 'size:count ...', "
                  "shed, overlap_ratio = staged-behind-compute ms / "
                  "staging ms, pipelined}; zipf_cache arms: zipfian "
                  "repeated statements through the GTS-versioned "
                  "result cache {distinct, skew, dispatches (device "
                  "dispatches — sublinear vs clients), cache_hits, "
                  "cache_hit_rate, fanin, wrong (asserted 0)}; "
                  "replica_point arms: cluster "
                  "point reads {replicas = hot standbys per DN, wrong "
                  "(asserted 0), routed_reads, fallthrough}; "
                  "vs_baseline = headline qps / serial point_sig qps",
        "serial": {k: {f: (round(v, 3) if isinstance(v, float) else v)
                       for f, v in e.items()} for k, e in serial.items()},
        "arms": [{k: (round(v, 3) if isinstance(v, float) else v)
                  for k, v in e.items()} for e in arms],
        "lineitem_rows": n_rows,
    }
    out["device"] = DEVICE
    print(json.dumps(out))
    print(f"# qps mode: sf={sf} seconds={seconds} warm={warm_s} "
          f"clients={clients_list} platform={platform}",
          file=sys.stderr)


def main():
    if CHAOS_CONCURRENT:
        _chaos_concurrent_arm()
        return
    if CHAOS:
        _chaos_arm()
        return
    if OOB:
        _oob_arm()
        return
    sf = float(os.environ.get("BENCH_SF", "1.0"))
    repeat = int(os.environ.get("BENCH_REPEAT", "5"))
    mode = os.environ.get("BENCH_MODE", "ladder")
    if mode not in ("ladder", "single", "mesh", "qps"):
        print(f"unknown BENCH_MODE={mode!r} (ladder|single|mesh|qps)",
              file=sys.stderr)
        sys.exit(2)

    # persistent XLA compilation cache at a path that does not move, so
    # a second run of the same command reads compiled programs back
    from opentenbase_tpu.exec import plancache
    plancache.enable_persistent_cache()

    if mode == "qps":
        _qps_mode()
        return

    from opentenbase_tpu.tpch import datagen
    from opentenbase_tpu.tpch.queries import Q
    from opentenbase_tpu.tpch.schema import SCHEMA

    t0 = time.time()
    data = datagen.generate(sf=sf)
    dfs = datagen.as_dataframes(data)
    n_rows = len(data["lineitem"]["l_orderkey"])
    gen_s = time.time() - t0

    ladder = []
    notes = []

    # ---- config 1: Q1/Q3/Q5 single node (fused fragment path: Q1 is
    # the scan+agg kernel program, Q3/Q5 are fused JOIN fragments —
    # late-materialized index-composition joins in one XLA program,
    # exec/fused.py) ----
    from opentenbase_tpu.exec.executor import exec_stats_snapshot
    controls = {1: _pandas_q1, 3: _pandas_q3, 5: _pandas_q5}
    if mode in ("ladder", "single"):
        from opentenbase_tpu.exec.session import LocalNode, Session
        node = LocalNode()
        s1 = Session(node)
        s1.execute(SCHEMA)
        for tname in ("region", "nation", "supplier", "customer",
                      "orders", "lineitem"):
            td = node.catalog.table(tname)
            nn = len(next(iter(data[tname].values())))
            s1._insert_rows(td, node.stores[tname], data[tname], nn)
        for qn in (1, 3, 5):
            x0 = exec_stats_snapshot()
            c0 = _compile_snapshot()
            eng, cold = _time(lambda: s1.query(Q[qn]), repeat)
            c1 = _compile_snapshot()
            x1 = exec_stats_snapshot()
            phases = _phases(s1.last_query_stats())
            _dump_trace(f"Q{qn} single")
            ctl, _ = _time(lambda: controls[qn](dfs),
                           max(2, repeat // 2))
            gb = _gb_touched(qn, data)
            entry = {"config": f"Q{qn} single", "engine_ms": eng * 1e3,
                     "cold_ms": cold * 1e3,
                     "mrows_s": n_rows / eng / 1e6,
                     "vs_pandas": ctl / eng,
                     "gb_touched": gb, "gb_per_s": gb / eng,
                     "phases": phases}
            entry.update(_mat_counters(x0, x1))
            entry.update(_compile_counters(c0, c1))
            ladder.append(entry)
        del s1, node

    # ---- config 2: Q1/Q3/Q5 through the device-mesh data plane ----
    mesh_q1 = None
    if mode in ("ladder", "mesh"):
        from opentenbase_tpu.storage.bufferpool import POOL
        ndn = max(len(jax.devices()), 1)
        s2 = _mesh_session(data)
        for qn in (1, 3, 5):
            x0 = exec_stats_snapshot()
            c0 = _compile_snapshot()
            eng, cold = _time(lambda: s2.query(Q[qn]), repeat)
            c1 = _compile_snapshot()
            x1 = exec_stats_snapshot()
            ctl, _ = _time(lambda: controls[qn](dfs), max(2, repeat // 2))
            gb = _gb_touched(qn, data)
            # warm-repeat arm: one more run against the populated
            # buffer pool — stage_ms should be ~0 and the pool hit
            # rate 100% (device_put of table columns skipped entirely)
            t0 = POOL.totals()
            t_run = time.perf_counter()
            s2.query(Q[qn])
            warm_ms = (time.perf_counter() - t_run) * 1e3
            t1 = POOL.totals()
            phases = _phases(s2.last_query_stats())
            _dump_trace(f"Q{qn} mesh")
            dh = t1["hits"] - t0["hits"]
            dm = t1["misses"] - t0["misses"]
            stage = s2.last_stage_ms
            entry = {"config": f"Q{qn} mesh x{ndn}",
                     "engine_ms": eng * 1e3,
                     "cold_ms": cold * 1e3,
                     "stage_ms": stage,
                     "compute_ms": max(warm_ms - stage, 0.0),
                     "pool_hit_rate": dh / max(dh + dm, 1),
                     "pool_staged_bytes": t1["uploaded_bytes"]
                     - t0["uploaded_bytes"],
                     "mrows_s_chip": n_rows / eng / 1e6 / ndn,
                     "vs_pandas": ctl / eng,
                     "gb_touched": gb,
                     "gb_per_s": gb / eng,
                     "tier": s2.last_tier,
                     "phases": phases}
            entry.update(_residency_block())
            entry.update(_mat_counters(x0, x1))
            entry.update(_compile_counters(c0, c1))
            if s2.last_tier != "mesh":
                entry["fallback"] = s2.last_fallback
            ladder.append(entry)
            if qn == 1:
                mesh_q1 = entry
        if os.environ.get("BENCH_OLTP", "1") != "0":
            c0 = _compile_snapshot()
            ins_p50, raw_p50, prep_p50 = _oltp_latencies(s2)
            entry = {"config": "point ops",
                     "insert_p50_ms": ins_p50,
                     "select_raw_p50_ms": raw_p50,
                     "select_prepared_p50_ms": prep_p50}
            entry.update(_compile_counters(c0, _compile_snapshot()))
            ladder.append(entry)

    # ---- optional: BASELINE config-2 scale (SF10) — opt-in via
    # BENCH_SF10=1.  NOT default: SF10 datagen alone takes ~1h on a
    # 1-core control box (measured 3694s); the committed SF10_RESULTS.md
    # records a full run.  On real multi-core TPU hosts set the env.
    if os.environ.get("BENCH_SF10", "0") == "1":
        from opentenbase_tpu.exec.dist_session import ClusterSession
        from opentenbase_tpu.parallel.cluster import Cluster
        data10 = datagen.generate(sf=10.0)
        n10 = len(data10["lineitem"]["l_orderkey"])
        s3 = ClusterSession(Cluster(
            n_datanodes=max(len(jax.devices()), 1)))
        s3.execute(SCHEMA)
        for tname in ("region", "nation", "supplier", "customer",
                      "part", "partsupp", "orders", "lineitem"):
            td = s3.cluster.catalog.table(tname)
            nn = len(next(iter(data10[tname].values())))
            s3._insert_rows(td, data10[tname], nn)
        for qn in (1, 3, 5):
            c0 = _compile_snapshot()
            eng, cold = _time(lambda: s3.query(Q[qn]), 2)
            entry = {"config": f"SF10 Q{qn}",
                     "engine_ms": eng * 1e3,
                     "cold_ms": cold * 1e3,
                     "mrows_s_chip": n10 / eng / 1e6,
                     "tier": s3.last_tier}
            entry.update(_compile_counters(c0, _compile_snapshot()))
            ladder.append(entry)

    head = mesh_q1 or ladder[0]
    out = {
        "metric": f"TPC-H Q1 SF{sf:g} throughput "
                  f"({platform}, {head['config']})",
        "value": round(head.get("mrows_s", head.get("mrows_s_chip", 0))
                       * (1 if "mrows_s" in head
                          else max(len(jax.devices()), 1)), 3),
        "unit": "Mrows/s",
        "vs_baseline": round(head["vs_pandas"], 3),
        "ladder": [{k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in e.items()} for e in ladder],
        "plancache": [dict(zip(("tier", "hits", "misses", "compiles",
                                "compile_ms", "evictions", "live"), r))
                      for r in plancache.stats()],
        "latency": _latency_block(),
    }
    from opentenbase_tpu.storage.bufferpool import POOL
    out["buffercache"] = [
        dict(zip(("table", "hits", "misses", "bytes_live", "evictions",
                  "invalidations", "pinned", "pins", "unpins",
                  "bytes_logical", "bytes_resident"), r))
        for r in POOL.stats_rows()]
    out["device"] = DEVICE
    print(json.dumps(out))
    print(f"# rows={n_rows} datagen={gen_s:.1f}s platform={platform} "
          f"mode={mode}", file=sys.stderr)


if __name__ == "__main__":
    main()
