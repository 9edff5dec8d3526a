"""Device buffer pool: version-keyed HBM residency across queries.

Reference analog: the buffer manager's page residency
(src/backend/storage/buffer) — here the assertions are that a warm
repeat stages NOTHING (zero host->device upload of table columns),
every mutation class (DML, DDL, vacuum, truncate) invalidates exactly,
append-only INSERT takes the incremental tail path with cold-run-equal
results, and the OTB_DEVICE_CACHE_BYTES budget evicts LRU entries.
"""

import numpy as np
import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.storage.bufferpool import POOL


@pytest.fixture()
def cs():
    s = ClusterSession(Cluster(n_datanodes=4))
    s.execute("create table t (k bigint primary key, grp int, "
              "v decimal(10,2), nm varchar(8)) distribute by shard(k)")
    s.execute("create table u (uk bigint primary key, tk bigint, "
              "w decimal(10,2)) distribute by shard(uk)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i % 3}, {i}.25, 'g{i % 3}')" for i in range(40)))
    s.execute("insert into u values " + ", ".join(
        f"({100 + i}, {i % 40}, {i}.5)" for i in range(60)))
    return s


Q_AGG = "select nm, count(*), sum(v) from t group by nm order by nm"
Q_JOIN = ("select nm, count(*), sum(w) from t, u where k = tk "
          "group by nm order by nm")


def host_oracle(cs, sql):
    cs.execute("set enable_mesh_exchange = off")
    try:
        return cs.query(sql)
    finally:
        cs.execute("set enable_mesh_exchange = on")


class TestMeshResidency:
    def test_warm_repeat_stages_nothing(self, cs):
        r1 = cs.query(Q_JOIN)
        assert cs.last_query_stats()["tier"] == "mesh"
        t0 = POOL.totals()
        r2 = cs.query(Q_JOIN)
        t1 = POOL.totals()
        assert r2 == r1
        assert cs.last_query_stats()["tier"] == "mesh"
        # both tables resident: zero host->device upload, 100% hit rate
        assert t1["uploaded_bytes"] - t0["uploaded_bytes"] == 0
        assert t1["misses"] - t0["misses"] == 0
        assert t1["hits"] - t0["hits"] >= 2
        assert cs.last_query_stats()["stage_ms"] < 50.0

    def test_warm_repeat_zero_table_staging(self, cs, monkeypatch):
        """Zero device_put of TABLE columns on a warm repeat: every
        staging path reads the host through host_live_columns, so a
        repeat that never touches it uploaded nothing (result-batch
        reassembly still makes small device transfers)."""
        from opentenbase_tpu.storage.store import TableStore
        cs.query(Q_AGG)
        assert cs.last_query_stats()["tier"] == "mesh"
        calls = []
        real = TableStore.host_live_columns

        def counting(self, *a, **kw):
            calls.append(self.td.name)
            return real(self, *a, **kw)

        monkeypatch.setattr(TableStore, "host_live_columns", counting)
        cs.query(Q_AGG)
        assert cs.last_query_stats()["tier"] == "mesh"
        assert not calls, "warm repeat re-staged table columns"

    def test_insert_takes_tail_path(self, cs):
        r1 = cs.query(Q_AGG)
        assert cs.last_query_stats()["tier"] == "mesh"
        cs.execute("insert into t values (100, 1, 7.00, 'g1'), "
                   "(101, 2, 8.00, 'gX')")
        t0 = POOL.totals()
        r2 = cs.query(Q_AGG)
        t1 = POOL.totals()
        assert cs.last_query_stats()["tier"] == "mesh"
        # only the appended tail crossed host->device (the new 'gX'
        # dictionary value extends the union in place)
        assert t1["tail_rows"] - t0["tail_rows"] >= 2
        assert r2 != r1
        assert r2 == host_oracle(cs, Q_AGG)
        # and matches a COLD run on a fresh runner over the same data
        cs.cluster._mesh_runner = None
        POOL.clear()
        r3 = cs.query(Q_AGG)
        assert cs.last_query_stats()["tier"] == "mesh"
        assert r3 == r2

    def test_update_delete_invalidate(self, cs):
        cs.query(Q_AGG)
        for dml in ("update t set v = 99.00 where k = 3",
                    "delete from t where k >= 30 and k < 35"):
            t0 = POOL.totals()
            cs.execute(dml)
            got = cs.query(Q_AGG)
            t1 = POOL.totals()
            assert cs.last_query_stats()["tier"] == "mesh"
            assert t1["invalidations"] > t0["invalidations"], dml
            assert got == host_oracle(cs, Q_AGG), dml

    def test_alter_and_drop_invalidate(self, cs):
        cs.query(Q_AGG)
        t0 = POOL.totals()
        cs.execute("alter table t add column extra bigint")
        got = cs.query("select count(*) from t where extra is null")
        assert got[0][0] == 40
        t1 = POOL.totals()
        assert t1["invalidations"] > t0["invalidations"]
        cs.query(Q_JOIN)
        live_before = {r[0]: r[3] for r in POOL.stats_rows()}
        assert live_before.get("u", 0) > 0
        cs.execute("drop table u")
        live_after = {r[0]: r[3] for r in POOL.stats_rows()}
        # DROP releases the table's device residency eagerly
        assert live_after.get("u", 0) == 0

    def test_vacuum_invalidates(self, cs):
        cs.execute("delete from t where k < 10")
        before = cs.query(Q_AGG)
        assert cs.last_query_stats()["tier"] == "mesh"
        t0 = POOL.totals()
        from opentenbase_tpu.parallel.maintenance import vacuum_cluster
        assert vacuum_cluster(cs.cluster, "t") == 10
        got = cs.query(Q_AGG)
        t1 = POOL.totals()
        assert cs.last_query_stats()["tier"] == "mesh"
        assert got == before
        assert t1["invalidations"] > t0["invalidations"]

    def test_truncate_invalidates(self, cs):
        cs.query(Q_AGG)
        cs.execute("truncate table t")
        assert cs.query("select count(*) from t")[0][0] == 0

    def test_buffercache_stat_view(self, cs):
        cs.query(Q_AGG)
        cs.query(Q_AGG)
        rows = cs.query("select table_name, hits, misses, bytes_live "
                        "from otb_buffercache where table_name = 't'")
        assert len(rows) == 1
        _name, hits, misses, bytes_live = rows[0]
        assert hits >= 1 and misses >= 1
        assert bytes_live > 0


class TestBudgetEviction:
    def test_byte_budget_evicts_lru(self, cs, monkeypatch):
        cs.query(Q_AGG)          # stage t
        cs.query(Q_JOIN)         # stage t + u
        t0 = POOL.totals()
        assert t0["bytes_live"] > 0
        monkeypatch.setenv("OTB_DEVICE_CACHE_BYTES", "1")
        POOL.trim()
        t1 = POOL.totals()
        assert t1["evictions"] > t0["evictions"]
        # everything but the single most-recent entry is evicted; a
        # lone over-budget entry may stay (the active query holds it)
        n_entries = len(POOL._dev) + len(POOL._mesh)
        assert n_entries <= 1
        monkeypatch.delenv("OTB_DEVICE_CACHE_BYTES")
        # queries still work after eviction (restage on demand)
        assert cs.query(Q_JOIN) == host_oracle(cs, Q_JOIN)


class TestSingleTierResidency:
    @pytest.fixture()
    def ls(self):
        s = Session(LocalNode())
        s.execute("create table st (k bigint primary key, v bigint, "
                  "nm varchar(8))")
        s.execute("insert into st values " + ", ".join(
            f"({i}, {i * 2}, 'n{i % 4}')" for i in range(20)))
        return s

    def test_warm_repeat_hits(self, ls):
        q = "select nm, sum(v) from st group by nm order by nm"
        r1 = ls.query(q)
        t0 = POOL.totals()
        r2 = ls.query(q)
        t1 = POOL.totals()
        assert r2 == r1
        assert t1["uploaded_bytes"] - t0["uploaded_bytes"] == 0
        assert t1["hits"] - t0["hits"] >= 1

    def test_insert_tail_path(self, ls):
        q = "select sum(v) from st"
        assert ls.query(q)[0][0] == 380
        t0 = POOL.totals()
        ls.execute("insert into st values (100, 1000, 'tail')")
        assert ls.query(q)[0][0] == 1380
        t1 = POOL.totals()
        assert t1["tail_rows"] - t0["tail_rows"] >= 1

    def test_null_mask_appears_in_tail(self, ls):
        q = "select count(*) from st where v is null"
        assert ls.query(q)[0][0] == 0
        # first NULL ever in column v arrives via the tail path: the
        # prefix mask is synthesized as zeros, no full restage
        ls.execute("insert into st values (200, null, 'z')")
        t0 = POOL.totals()
        assert ls.query(q)[0][0] == 1
        t1 = POOL.totals()
        assert t1["tail_rows"] - t0["tail_rows"] >= 1

    def test_update_restages_fully(self, ls):
        q = "select sum(v) from st"
        ls.query(q)
        t0 = POOL.totals()
        ls.execute("update st set v = 0 where k = 1")
        assert ls.query(q)[0][0] == 378
        t1 = POOL.totals()
        assert t1["tail_rows"] == t0["tail_rows"]  # not the tail path


class TestAppendedOnlyLog:
    def test_mutation_log_semantics(self):
        from opentenbase_tpu.catalog.schema import (ColumnDef,
                                                    Distribution,
                                                    DistType, TableDef)
        from opentenbase_tpu.catalog import types as T
        from opentenbase_tpu.storage.store import TableStore
        td = TableDef("x", [ColumnDef("a", T.INT64)],
                      Distribution(DistType.REPLICATED))
        st = TableStore(td)
        v0, n0 = st.version, st.row_count()
        st.insert({"a": np.arange(5)}, 5, txid=1, commit_ts=1)
        assert st.appended_only_since(v0, n0)
        v1, n1 = st.version, st.row_count()
        spans = st.insert({"a": np.arange(3)}, 3, txid=2)
        st.backfill_insert(spans, np.int64(50))
        # insert + its own commit backfill touch only rows >= n1
        assert st.appended_only_since(v1, n1)
        # ...but not a snapshot that already included those rows as
        # uncommitted: the backfill rewrote xmin_ts below the fence
        st2_spans = st.insert({"a": np.arange(2)}, 2, txid=3)
        v2, n2 = st.version, st.row_count()
        st.backfill_insert(st2_spans, np.int64(60))
        assert not st.appended_only_since(v2, n2)
        # deletes of existing rows break the prefix
        v3, n3 = st.version, st.row_count()
        sp4 = st.mark_delete(0, np.asarray([True] + [False] * 9),
                             txid=4)
        assert not st.appended_only_since(v3, n3)
        st.revert_delete([sp4])
        # pure appends are unlogged: an arbitrarily long burst stays
        # provable, and the old delete entry keeps failing older fences
        for _ in range(200):
            st.insert({"a": np.arange(1)}, 1, txid=5, commit_ts=70)
        assert not st.appended_only_since(v3, n3)
        v4, n4 = st.version, st.row_count()
        st.insert({"a": np.arange(4)}, 4, txid=6, commit_ts=71)
        assert st.appended_only_since(v4, n4)
        # the bounded log refuses what it can no longer prove: >128
        # prefix-touching mutations trim the floor past v4
        for _ in range(140):
            span = st.mark_delete(0, np.asarray([True] + [False] * 9),
                                  txid=7)
            st.revert_delete([span])
        assert not st.appended_only_since(v4, n4)
        v5, n5 = st.version, st.row_count()
        st.insert({"a": np.arange(1)}, 1, txid=8, commit_ts=72)
        assert st.appended_only_since(v5, n5)
        # shrinkage then re-append: the high-water mark forces logging,
        # so a pre-truncate fence can never claim the new prefix
        v6, n6 = st.version, st.row_count()
        st.truncate()
        st.insert({"a": np.arange(2)}, 2, txid=9, commit_ts=73)
        assert not st.appended_only_since(v6, n6)


def test_smoke_warm_repeat_mini_mesh():
    """CI smoke (non-slow): a mini mesh query twice must hit the pool —
    tier-1 guards device residency without any TPC-H datagen cost."""
    s = ClusterSession(Cluster(n_datanodes=2))
    s.execute("create table mini (k bigint primary key, v bigint) "
              "distribute by shard(k)")
    s.execute("insert into mini values (1, 10), (2, 20), (3, 30)")
    q = "select sum(v) from mini"
    assert s.query(q)[0][0] == 60
    t0 = POOL.totals()
    assert s.query(q)[0][0] == 60
    t1 = POOL.totals()
    assert t1["hits"] - t0["hits"] >= 1
    assert t1["uploaded_bytes"] - t0["uploaded_bytes"] == 0
