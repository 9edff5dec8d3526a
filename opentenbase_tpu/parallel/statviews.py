"""Cluster system views — observability surfaces queryable in SQL.

Reference analog: pg_stat_cluster_activity + fn page stats + pg_prepared_
xacts (catalog/system_views.sql:726,758,1598) and the pgstat collector.
Implemented as virtual tables materialized on read: the coordinator
refreshes the backing rows (on datanode 0, SINGLE distribution) right
before a query that references them.

Views:
- otb_stat_tables(table_name, datanode, rows, version)
- otb_stat_gtm(current_gts, next_txid, active_txns, prepared_txns)
- otb_prepared_xacts(gid, state, txid, commit_ts)
- otb_nodes(name, kind, host, port, healthy)
- otb_plancache(tier, hits, misses, compiles, compile_ms, evictions,
  live) — the compiled-program subsystem's counters (exec/plancache.py)
- otb_buffercache(table_name, hits, misses, bytes_live, evictions,
  invalidations, pinned, pins, unpins) — the device buffer pool's
  per-table counters, pin-refcount ledger included
  (storage/bufferpool.py)
- otb_morsel(streams, chunks, bytes_streamed, chunk_downshifts,
  declined) — the out-of-core streaming tier's counters
  (exec/morsel.py)
- otb_execstats(tier, joins, index_compositions, deferred_cols,
  eager_cols, cols_materialized, bytes_materialized, host_syncs,
  fused_join_hits) — the executor's late-materialization join counters
  (exec/executor.py EXEC_STATS)
- otb_scheduler(admitted, queued, batched, shed, dispatches,
  batch_dispatches, queue_wait_p50_ms, queue_wait_p99_ms, batch_hist)
  — the serving tier's admission/coalescing counters
  (exec/scheduler.py)
- otb_shield(batch_failures, isolated, quarantined, quarantine_active,
  quarantine_hits, oom_dispatches, oom_retries, oom_evicted_bytes,
  degraded, shrunk_batches, streamed) — the serving tier's
  fault-isolation counters (exec/shield.py)
- otb_workshare(shared_streams, shared_scan_fanin, shared_chunks,
  late_joins, private_fallbacks, result_cache_hits,
  result_cache_misses, result_cache_invalidations, result_cache_puts,
  result_cache_evictions, result_cache_bytes, result_cache_entries) —
  the cross-query work-sharing counters (exec/share.py)
"""

from __future__ import annotations

from ..catalog.schema import ColumnDef, Distribution, DistType, TableDef
from ..catalog import types as T

STAT_TABLES = {
    "otb_stat_tables": [
        ColumnDef("table_name", T.TEXT), ColumnDef("datanode", T.INT32),
        ColumnDef("rows", T.INT64), ColumnDef("version", T.INT64)],
    "otb_stat_gtm": [
        ColumnDef("current_gts", T.INT64), ColumnDef("next_txid", T.INT64),
        ColumnDef("active_txns", T.INT64),
        ColumnDef("prepared_txns", T.INT64)],
    "otb_prepared_xacts": [
        ColumnDef("gid", T.TEXT), ColumnDef("state", T.TEXT),
        ColumnDef("txid", T.INT64), ColumnDef("commit_ts", T.INT64)],
    "otb_nodes": [
        ColumnDef("name", T.TEXT), ColumnDef("kind", T.TEXT),
        ColumnDef("host", T.TEXT), ColumnDef("port", T.INT32),
        ColumnDef("healthy", T.BOOL)],
    # resource-group usage (reference: pg_resgroup status views).
    # concurrency/staging are cluster-wide DEFINITIONS; queries/
    # query_seconds are THIS coordinator's accounting (each CN
    # accumulates its own executor wall time — whole-query, host work
    # included; cross-CN aggregation is a future GTM rollup)
    # scheduled-job status (reference: the pg_dbms_job views)
    "otb_jobs": [
        ColumnDef("name", T.TEXT), ColumnDef("interval_s", T.FLOAT64),
        ColumnDef("runs", T.INT64), ColumnDef("failures", T.INT64),
        ColumnDef("last_error", T.TEXT)],
    "otb_resgroups": [
        ColumnDef("name", T.TEXT), ColumnDef("concurrency", T.INT64),
        ColumnDef("staging_budget_rows", T.INT64),
        ColumnDef("queries", T.INT64),
        ColumnDef("query_seconds", T.FLOAT64)],
    # compiled-program subsystem telemetry (exec/plancache.py): one row
    # per tier — fused / mesh hold live XLA executables (bounded by the
    # global budget), plan / autoprep are the statement-level caches
    # feeding them.  `live` = live executables (program tiers) or
    # cached entries (statement tiers); compile_ms is cumulative.
    "otb_plancache": [
        ColumnDef("tier", T.TEXT), ColumnDef("hits", T.INT64),
        ColumnDef("misses", T.INT64), ColumnDef("compiles", T.INT64),
        ColumnDef("compile_ms", T.FLOAT64),
        ColumnDef("evictions", T.INT64), ColumnDef("live", T.INT64)],
    # device buffer-pool telemetry (storage/bufferpool.py): one row per
    # user table that has touched the pool — device-resident bytes and
    # hit/miss/eviction/invalidation counters across BOTH executor
    # tiers (single-device scans and mesh staging).  The compiled-
    # program view's twin: plancache kills repeat compiles, this kills
    # repeat uploads.
    "otb_buffercache": [
        ColumnDef("table_name", T.TEXT), ColumnDef("hits", T.INT64),
        ColumnDef("misses", T.INT64), ColumnDef("bytes_live", T.INT64),
        ColumnDef("evictions", T.INT64),
        ColumnDef("invalidations", T.INT64),
        ColumnDef("pinned", T.INT64), ColumnDef("pins", T.INT64),
        ColumnDef("unpins", T.INT64),
        # compressed residency (storage/codec.py): bytes_logical is
        # what the resident arrays would occupy UNENCODED; the ratio
        # bytes_logical / bytes_resident is the effective-cache
        # multiplier the codecs bought
        ColumnDef("bytes_logical", T.INT64),
        ColumnDef("bytes_resident", T.INT64)],
    # out-of-core streaming telemetry (exec/morsel.py): chunk windows
    # executed, bytes streamed through the pinned chunk cache, and
    # OOM-driven chunk-size downshifts — the observable record of
    # queries that exceeded device residency yet stayed on-device
    "otb_morsel": [
        ColumnDef("streams", T.INT64), ColumnDef("chunks", T.INT64),
        ColumnDef("bytes_streamed", T.INT64),
        ColumnDef("chunk_downshifts", T.INT64),
        ColumnDef("declined", T.INT64)],
    # executor late-materialization telemetry (exec/executor.py
    # EXEC_STATS): one row per execution tier.  "single" counts every
    # eager operator dispatch; "fused"/"mesh" count TRACE-time events
    # (a cached program re-executes without re-tracing) plus compiled
    # join-program cache-hit executions (fused_join_hits).
    # deferred_cols = column gathers a join AVOIDED (index composition
    # carried the column instead); eager_cols = full-width join gathers
    # (the pre-late-materialization path, or LATE_MAT off);
    # cols/bytes_materialized = what the deferred pass actually gathered
    # when a width-consuming operator (Agg input, Sort, exchange, final
    # projection) demanded real columns; host_syncs = per-join
    # device->host size syncs on the eager path (zero when a join chain
    # runs as one fused program).
    "otb_execstats": [
        ColumnDef("tier", T.TEXT), ColumnDef("joins", T.INT64),
        ColumnDef("index_compositions", T.INT64),
        ColumnDef("deferred_cols", T.INT64),
        ColumnDef("eager_cols", T.INT64),
        ColumnDef("cols_materialized", T.INT64),
        ColumnDef("bytes_materialized", T.INT64),
        ColumnDef("host_syncs", T.INT64),
        ColumnDef("fused_join_hits", T.INT64)],
    # serving-tier telemetry (exec/scheduler.py): admission/coalescing
    # counters aggregated across every Scheduler in the process.
    # admitted = queries that passed admission and executed; queued =
    # current queue depth (gauge); batched = queries served by a
    # multi-query dispatch; shed = rejected (queue full or shed
    # deadline); batch_hist = "size:count ..." dispatch histogram;
    # queue waits are submit -> execution-start, recent window.
    "otb_scheduler": [
        ColumnDef("admitted", T.INT64), ColumnDef("queued", T.INT64),
        ColumnDef("batched", T.INT64), ColumnDef("shed", T.INT64),
        ColumnDef("dispatches", T.INT64),
        ColumnDef("batch_dispatches", T.INT64),
        ColumnDef("queue_wait_p50_ms", T.FLOAT64),
        ColumnDef("queue_wait_p99_ms", T.FLOAT64),
        ColumnDef("batch_hist", T.TEXT)],
    # serving-tier fault isolation (exec/shield.py): batch quarantine,
    # memory-pressure degradation, and admission pre-shrink counters —
    # the observable record of faults the tier absorbed instead of
    # spreading (reference: per-backend crash accounting + resgroup
    # memory-limit kills, except here absorption is the success path)
    "otb_shield": [
        ColumnDef("batch_failures", T.INT64),
        ColumnDef("isolated", T.INT64),
        ColumnDef("quarantined", T.INT64),
        ColumnDef("quarantine_active", T.INT64),
        ColumnDef("quarantine_hits", T.INT64),
        ColumnDef("oom_dispatches", T.INT64),
        ColumnDef("oom_retries", T.INT64),
        ColumnDef("oom_evicted_bytes", T.INT64),
        ColumnDef("degraded", T.INT64),
        ColumnDef("shrunk_batches", T.INT64),
        ColumnDef("streamed", T.INT64)],
    # cross-query work sharing (exec/share.py): shared-scan fan-in and
    # GTS-versioned result-cache counters — shared_streams = leader
    # streams that fed >=1 follower; fanin = follower attachments
    # (extra consumers served by someone else's pass); late_joins =
    # mid-stream attachments; private_fallbacks = expels and
    # incompatibilities that reverted to a private stream
    "otb_workshare": [
        ColumnDef("shared_streams", T.INT64),
        ColumnDef("shared_scan_fanin", T.INT64),
        ColumnDef("shared_chunks", T.INT64),
        ColumnDef("late_joins", T.INT64),
        ColumnDef("private_fallbacks", T.INT64),
        ColumnDef("result_cache_hits", T.INT64),
        ColumnDef("result_cache_misses", T.INT64),
        ColumnDef("result_cache_invalidations", T.INT64),
        ColumnDef("result_cache_puts", T.INT64),
        ColumnDef("result_cache_evictions", T.INT64),
        ColumnDef("result_cache_bytes", T.INT64),
        ColumnDef("result_cache_entries", T.INT64)],
    # recent-query trace ring (obs/trace.py): one row per finished
    # top-level statement, newest last — per-phase wall-time breakdown
    # plus staging/materialization byte counts and buffer-pool hit
    # counts (reference: pg_stat_activity + pg_stat_statements timing
    # columns, backed here by the span tree instead of bespoke timers)
    "otb_stat_query": [
        ColumnDef("qid", T.INT64), ColumnDef("signature", T.TEXT),
        ColumnDef("tier", T.TEXT), ColumnDef("total_ms", T.FLOAT64),
        ColumnDef("plan_ms", T.FLOAT64), ColumnDef("stage_ms", T.FLOAT64),
        ColumnDef("execute_ms", T.FLOAT64),
        ColumnDef("exchange_ms", T.FLOAT64),
        ColumnDef("finalize_ms", T.FLOAT64),
        ColumnDef("rows", T.INT64),
        ColumnDef("bytes_staged", T.INT64),
        ColumnDef("bytes_materialized", T.INT64),
        ColumnDef("pool_hits", T.INT64), ColumnDef("pool_misses", T.INT64),
        # the shape of the compiled programs that answered (summary())
        ColumnDef("semi_joins", T.INT64), ColumnDef("sorted_aggs", T.INT64),
        ColumnDef("sorted_agg_lanes", T.INT64),
        ColumnDef("initplans", T.INT64),
        ColumnDef("sorted_agg_groups", T.INT64),
        # the serving thread's CPU beside wall time, the spans under
        # the root's self time, host<->device round trips (summary())
        ColumnDef("cpu_ms", T.FLOAT64), ColumnDef("offcpu_ms", T.FLOAT64),
        ColumnDef("unattributed_ms", T.FLOAT64),
        ColumnDef("inputs_ms", T.FLOAT64),
        ColumnDef("gather_ms", T.FLOAT64),
        ColumnDef("release_ms", T.FLOAT64),
        ColumnDef("host_syncs", T.INT64), ColumnDef("d2h_bytes", T.INT64),
        ColumnDef("h2d_puts", T.INT64), ColumnDef("h2d_bytes", T.INT64),
        ColumnDef("program_calls", T.INT64),
        # more of the programs' shape: the anti joins among the masks,
        # left-outer expansions, the largest class a residual semi or
        # anti join expands into, the largest string-predicate code set
        # or bitmap; the scalar subqueries' time
        ColumnDef("anti_joins", T.INT64), ColumnDef("outer_joins", T.INT64),
        ColumnDef("residual_semi_lanes", T.INT64),
        ColumnDef("strpred_codes", T.INT64),
        ColumnDef("initplan_ms", T.FLOAT64),
        # the destination slots of the mesh programs' exchange packs
        ColumnDef("pack_lanes", T.INT64),
        # two-phase aggregates: the final halves the programs hold, the
        # lanes the largest one's redistributed partials arrive in; and
        # the largest source class a redistribute packs from
        ColumnDef("final_aggs", T.INT64),
        ColumnDef("final_agg_lanes", T.INT64),
        ColumnDef("exchange_src_lanes", T.INT64)],
    # per-node guard health (net/guard.py): breaker state + failure
    # accounting for every RPC peer this coordinator talks to
    # (reference: pgxc_node health columns fed by clustermon pings;
    # here the accounting is call-outcome-driven, no probe traffic)
    "otb_node_health": [
        ColumnDef("node", T.TEXT), ColumnDef("state", T.TEXT),
        ColumnDef("breaker", T.TEXT),
        ColumnDef("consec_failures", T.INT64),
        ColumnDef("retries", T.INT64),
        ColumnDef("last_error", T.TEXT)],
    # cumulative wait-event accounting (obs/xray.py): one row per named
    # wait point (admission queue, GTS grant, bufferpool eviction, RPC
    # on-wire, ...) with log-bucket latency quantiles — the answer to
    # "where do queries actually block" (reference: pg_stat_activity
    # wait_event / wait_event_type, aggregated over time instead of
    # sampled)
    "otb_wait_events": [
        ColumnDef("event", T.TEXT), ColumnDef("count", T.INT64),
        ColumnDef("total_ms", T.FLOAT64), ColumnDef("p50_ms", T.FLOAT64),
        ColumnDef("p95_ms", T.FLOAT64), ColumnDef("p99_ms", T.FLOAT64)],
    # live per-query activity (obs/xray.py): one row per statement
    # currently inside the serving tier — lifecycle state (queued /
    # staging / device / draining), the wait event its thread is
    # blocked on RIGHT NOW, age, and whether a cancel handle exists
    # (reference: pg_stat_activity + pg_cancel_backend)
    "otb_stat_activity": [
        ColumnDef("aid", T.INT64), ColumnDef("state", T.TEXT),
        ColumnDef("wait_event", T.TEXT), ColumnDef("age_ms", T.FLOAT64),
        ColumnDef("cancelable", T.BOOL), ColumnDef("trace_id", T.TEXT),
        ColumnDef("sql", T.TEXT)],
    # the unified metrics registry (obs/metrics.py): every native
    # counter/gauge/histogram sample plus every registered subsystem
    # collector, flattened to (name, labels, kind, value) — the SQL
    # twin of the Prometheus text exposition
    "otb_metrics": [
        ColumnDef("name", T.TEXT), ColumnDef("labels", T.TEXT),
        ColumnDef("kind", T.TEXT), ColumnDef("value", T.FLOAT64)],
}


def register(cluster):
    """Create the view tables in the catalog (idempotent)."""
    for name, cols in STAT_TABLES.items():
        if name not in cluster.catalog.tables:
            td = TableDef(name, list(cols), Distribution(DistType.SINGLE))
            cluster.catalog.create_table(td, if_not_exists=True)
            for dn in cluster.datanodes:
                dn.ddl_create(td)


def referenced_stat_tables(sql_tables) -> list[str]:
    return [t for t in sql_tables if t in STAT_TABLES]


def refresh(cluster, names: list[str]):
    """Re-materialize the requested views (rows live on datanode 0)."""
    gtm = cluster.gtm
    for name in names:
        rows = []
        if name == "otb_stat_tables":
            for dn in cluster.datanodes:
                for tname in cluster.catalog.tables:
                    if tname in STAT_TABLES:
                        continue
                    if hasattr(dn, "stores"):
                        st = dn.stores.get(tname)
                        if st is not None:
                            rows.append((tname, dn.index, st.row_count(),
                                         st.version))
                    else:
                        rows.append((tname, dn.index,
                                     dn.row_count(tname), -1))
        elif name == "otb_stat_gtm":
            st = gtm.stats()   # read-only: never allocates a timestamp
            rows.append((st["ts"], st["txid"],
                         len(cluster.active_txns), st["prepared"]))
        elif name == "otb_prepared_xacts":
            for gid, info in gtm.prepared_list().items():
                rows.append((gid, info["state"], info["txid"],
                             info.get("commit_ts", 0)))
        elif name == "otb_nodes":
            mon = getattr(cluster, "_monitor", None)
            hmap = mon.health if mon is not None else None
            for nd in cluster.catalog.nodes.values():
                if nd.kind == "datanode" and nd.index < cluster.ndn:
                    if hmap is not None and nd.index in hmap:
                        # monitor-fed health map: bounded staleness,
                        # no live ping per query (clustermon.c model)
                        healthy = hmap[nd.index]["healthy"]
                    else:
                        dn = cluster.datanodes[nd.index]
                        healthy = dn.ping() if hasattr(dn, "ping") \
                            else True
                else:
                    healthy = True
                rows.append((nd.name, nd.kind, nd.host, nd.port,
                             healthy))
        elif name == "otb_jobs":
            sch = getattr(cluster, "_job_scheduler", None)
            state = sch.state if sch is not None else {}
            for jname, j in cluster.catalog.jobs.items():
                st = state.get(jname, {})
                rows.append((jname, float(j["interval_s"]),
                             int(st.get("runs", 0)),
                             int(st.get("failures", 0)),
                             st.get("last_error", "")))
        elif name == "otb_plancache":
            from ..exec import plancache
            rows = list(plancache.stats())
        elif name == "otb_buffercache":
            from ..storage.bufferpool import POOL
            rows = list(POOL.stats_rows())
        elif name == "otb_execstats":
            from ..exec.executor import exec_stats_rows
            rows = list(exec_stats_rows())
        elif name == "otb_scheduler":
            from ..exec.scheduler import stats_rows
            rows = list(stats_rows())
        elif name == "otb_shield":
            from ..exec.shield import stats_rows as shield_rows
            rows = list(shield_rows())
        elif name == "otb_morsel":
            from ..exec.morsel import stats_rows as morsel_rows
            rows = list(morsel_rows())
        elif name == "otb_workshare":
            from ..exec.share import stats_rows as workshare_rows
            rows = list(workshare_rows())
        elif name == "otb_stat_query":
            from ..obs import trace as obs_trace
            for qt in obs_trace.recent():
                s = qt.summary()
                rows.append((
                    s["qid"], s["signature"], s["tier"],
                    s["total_ms"], s["plan_ms"], s["stage_ms"],
                    s["execute_ms"], s["exchange_ms"], s["finalize_ms"],
                    s["rows"], s["bytes_staged"],
                    s["bytes_materialized"], s["pool_hits"],
                    s["pool_misses"], s["semi_joins"], s["sorted_aggs"],
                    s["sorted_agg_lanes"], s["initplans"],
                    s["sorted_agg_groups"], s["cpu_ms"], s["offcpu_ms"],
                    s["unattributed_ms"], s["inputs_ms"],
                    s["gather_ms"], s["release_ms"], s["host_syncs"],
                    s["d2h_bytes"], s["h2d_puts"], s["h2d_bytes"],
                    s["program_calls"], s["anti_joins"], s["outer_joins"],
                    s["residual_semi_lanes"], s["strpred_codes"],
                    s["initplan_ms"], s["pack_lanes"], s["final_aggs"],
                    s["final_agg_lanes"], s["exchange_src_lanes"]))
        elif name == "otb_node_health":
            from ..net.guard import health_rows
            rows = list(health_rows())
        elif name == "otb_wait_events":
            from ..obs import xray
            rows = list(xray.wait_rows())
        elif name == "otb_stat_activity":
            from ..obs import xray
            rows = list(xray.activity_rows())
        elif name == "otb_metrics":
            from ..obs.metrics import REGISTRY
            rows = list(REGISTRY.rows())
        elif name == "otb_resgroups":
            usage = getattr(cluster, "resgroup_usage", {})
            for gname, g in cluster.catalog.resource_groups.items():
                u = usage.get(gname, {})
                rows.append((gname, int(g.get("concurrency", 0)),
                             int(g.get("staging_budget_rows", 0)),
                             int(u.get("queries", 0)),
                             float(u.get("device_s", 0.0))))
        _replace_rows(cluster, name, rows)


def _replace_rows(cluster, name: str, rows: list[tuple]):
    from ..storage.store import TableStore
    td = cluster.catalog.table(name)
    dn0 = cluster.datanodes[0]
    if hasattr(dn0, "stores"):
        old = dn0.stores.get(name)
        if old is not None:
            dn0.cache.invalidate(old)   # evict the replaced store's buffers
        st = TableStore(td)
        if rows:
            cols = {c.name: [r[i] for r in rows]
                    for i, c in enumerate(td.columns)}
            enc = {cn: st.encode_column(cn, v) for cn, v in cols.items()}
            st.insert(enc, len(rows), txid=1, commit_ts=1)
        dn0.stores[name] = st
    else:
        # remote datanode: rebuild over RPC
        dn0.ddl_drop(name)
        dn0.ddl_create(td)
        if rows:
            cols = {c.name: [r[i] for r in rows]
                    for i, c in enumerate(td.columns)}
            dn0.insert_raw(name, cols, len(rows), txid=1)
            dn0.commit(1, 1)
