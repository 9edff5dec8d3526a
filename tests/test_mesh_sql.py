"""Device-mesh SQL execution: fragment DAGs through shard_map.

Reference analog: the FN forwarding-plane tests (src/test/forward/
test_fnbuf.c) plus the cluster-harness queries — here the assertion is
that a planned SQL query produces IDENTICAL results through the device
data plane (all_to_all/all_gather inside one compiled program,
exec/mesh_exec.py) and through the host-mediated exchange tier."""

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.mesh_exec import mesh_runner_for
from opentenbase_tpu.parallel.cluster import Cluster


@pytest.fixture()
def cs():
    s = ClusterSession(Cluster(n_datanodes=4))
    s.execute("create table t (k bigint primary key, grp int, "
              "v decimal(10,2), nm varchar(8)) distribute by shard(k)")
    s.execute("create table u (uk bigint primary key, tk bigint, "
              "w decimal(10,2)) distribute by shard(uk)")
    s.execute("create table d (id int primary key, label varchar(8)) "
              "distribute by replication")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i % 3}, {i}.25, 'g{i % 3}')" for i in range(40)))
    s.execute("insert into u values " + ", ".join(
        f"({100 + i}, {i % 40}, {i}.5)" for i in range(60)))
    s.execute("insert into d values (0, 'zero'), (1, 'one'), (2, 'two')")
    return s


def both(cs, sql, expect_mesh=True):
    """Run under both tiers, assert identical results; with expect_mesh,
    also assert the mesh tier actually compiled a program (no silent
    host fallback)."""
    cs.execute("set enable_mesh_exchange = off")
    host = cs.query(sql)
    cs.execute("set enable_mesh_exchange = on")
    runner = mesh_runner_for(cs.cluster)
    n0 = len(runner._programs) if runner else 0
    mesh = cs.query(sql)
    assert mesh == host, f"mesh != host for {sql}"
    if expect_mesh:
        assert runner is not None and len(runner._programs) > n0, \
            f"query fell back to the host tier: {sql}"
    return mesh


class TestMeshParity:
    def test_global_agg(self, cs):
        got = both(cs, "select count(*), sum(v), min(v), max(v) from t")
        assert got[0][0] == 40

    def test_group_by_text(self, cs):
        got = both(cs, "select nm, count(*), sum(v) from t "
                        "group by nm order by nm")
        assert [r[0] for r in got] == ["g0", "g1", "g2"]

    def test_redistribute_join(self, cs):
        # join on non-dist key of u: all_to_all moves u's rows
        got = both(cs, "select nm, count(*), sum(w) from t, u "
                        "where k = tk group by nm order by nm")
        assert sum(r[1] for r in got) == 60

    def test_join_replicated_dim(self, cs):
        got = both(cs, "select label, count(*) from t, d "
                        "where grp = id group by label order by label")
        assert sum(r[1] for r in got) == 40

    def test_left_join_through_mesh(self, cs):
        got = both(cs, "select k, w from t left join u on k = tk "
                        "and w > 25 where k < 6 order by k, w")
        assert len(got) >= 6

    def test_filter_sort_limit(self, cs):
        got = both(cs, "select k, v from t where v > 10 "
                        "order by v desc limit 5")
        assert len(got) == 5

    def test_nulls_through_mesh(self, cs):
        cs.execute("insert into t values (900, 0, null, null)")
        both(cs, "select nm, count(v), count(*) from t "
                 "group by nm order by nm")
        got = both(cs, "select k from t where v is null")
        assert got == [(900,)]

    def test_mesh_programs_cached(self, cs):
        cs.execute("set enable_mesh_exchange = on")
        cs.query("select count(*) from t")
        r = mesh_runner_for(cs.cluster)
        assert r is not None
        n0 = len(r._programs)
        cs.query("select count(*) from t")   # same plan: cache hit
        assert len(r._programs) == n0

    def test_mesh_sees_new_rows(self, cs):
        cs.execute("set enable_mesh_exchange = on")
        before = cs.query("select count(*) from t")[0][0]
        cs.execute("insert into t values (901, 0, 1.00, 'g0')")
        assert cs.query("select count(*) from t")[0][0] == before + 1

    def test_window_local_partition_via_mesh(self, cs):
        # partitioned by the dist key: the Window node stays in the DN
        # fragment and traces into the shard_map program
        got = both(cs, "select k, row_number() over (partition by k "
                       "order by v) from t where k < 5 order by k")
        assert [r[1] for r in got] == [1] * len(got)

    def test_unsupported_falls_back(self, cs):
        # DISTINCT aggregate is host-tier only: must still answer
        cs.execute("set enable_mesh_exchange = on")
        got = cs.query("select count(distinct nm) from t")
        assert got == [(3,)]


class TestMeshTpch:
    def test_q5_shape_parity(self, cs):
        # the canonical multi-join + group-by + order-by shape: one
        # all_to_all (u by tk) + one local replicated join + partial/
        # final agg split, compiled as a single shard_map program
        sql = ("select label, sum(v * w) as rev from t, u, d "
               "where k = tk and grp = id "
               "group by label order by rev desc")
        both(cs, sql)


class TestGatherTrim:
    def test_cn_fragment_runs_at_the_live_size_class(self, monkeypatch):
        """Gathered fragment outputs reach the CN fragment re-padded to
        the size class of their LIVE rows, not the (worst-case) gather
        class: the CN's eager kernels compile at that size — on a v5e a
        final sort over a 65536-row buffer compiled for minutes."""
        from opentenbase_tpu.exec.mesh_exec import MeshRunner
        s = ClusterSession(Cluster(n_datanodes=2))
        s.execute("create table g (k bigint primary key, grp int, "
                  "v bigint) distribute by shard(k)")
        s.execute("insert into g values " + ", ".join(
            f"({i}, {i % 3}, {i})" for i in range(5000)))
        seen = []
        orig = MeshRunner.run

        def spy(self, *a, **kw):
            result, included = orig(self, *a, **kw)
            seen.extend(int(b.valid.shape[0]) for b in result.values())
            return result, included

        monkeypatch.setattr(MeshRunner, "run", spy)
        s.execute("set enable_mesh_exchange = on")
        got = s.query("select grp, count(*), sum(v) from g "
                      "group by grp order by grp")
        assert s.last_query_stats()["tier"] == "mesh"
        assert [r[:2] for r in got] == [(0, 1667), (1, 1667), (2, 1666)]
        assert seen and max(seen) == 256, seen   # 6 live rows, not 2x2560
