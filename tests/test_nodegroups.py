"""Node groups, RANGE distribution, multi-column SHARD keys
(catalog/schema.py, parallel/locator.py, plan/distribute.py;
reference: pgxc_group.h, pgxc_class.h:17-29, locator.h:20-56)."""

import pandas as pd
import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.sql.parser import parse_sql


@pytest.fixture()
def cs():
    return ClusterSession(Cluster(n_datanodes=4))


class TestMultiColumnShardKeys:
    def test_routing_and_point_lookup(self, cs):
        cs.execute("create table mk (a bigint, b bigint, v bigint) "
                   "distribute by shard(a, b)")
        cs.execute("insert into mk values " + ", ".join(
            f"({i % 7}, {i % 5}, {i})" for i in range(100)))
        assert cs.query("select count(*) from mk") == [(100,)]
        got = cs.query("select sum(v) from mk where a = 3 and b = 2")
        want = sum(i for i in range(100) if i % 7 == 3 and i % 5 == 2)
        assert got == [(want,)]

    def test_colocated_join_elision_two_column_key(self, cs):
        """The VERDICT done-criterion: a join on BOTH components of a
        two-column SHARD key moves no rows (no redistribute exchange)
        and still answers correctly on the mesh."""
        cs.execute("create table mk1 (a bigint, b bigint, v bigint) "
                   "distribute by shard(a, b)")
        cs.execute("create table mk2 (a bigint, b bigint, w bigint) "
                   "distribute by shard(a, b)")
        cs.execute("insert into mk1 values " + ", ".join(
            f"({i % 7}, {i % 5}, {i})" for i in range(200)))
        cs.execute("insert into mk2 values " + ", ".join(
            f"({i % 7}, {i % 5}, {i * 2})" for i in range(100)))
        q = ("select count(*), sum(mk1.v + mk2.w) from mk1, mk2 "
             "where mk1.a = mk2.a and mk1.b = mk2.b")
        dp = cs._plan_distributed(parse_sql(q)[0])
        assert [e.kind for e in dp.exchanges].count("redistribute") \
            == 0
        df1 = pd.DataFrame({"a": [i % 7 for i in range(200)],
                            "b": [i % 5 for i in range(200)],
                            "v": range(200)})
        df2 = pd.DataFrame({"a": [i % 7 for i in range(100)],
                            "b": [i % 5 for i in range(100)],
                            "w": [i * 2 for i in range(100)]})
        m = df1.merge(df2, on=["a", "b"])
        assert cs.query(q) == [(len(m), int((m.v + m.w).sum()))]
        st = cs.last_query_stats()
        assert st["tier"] == "mesh", st["fallback"]

    def test_partial_key_join_redistributes(self, cs):
        cs.execute("create table p1 (a bigint, b bigint) "
                   "distribute by shard(a, b)")
        cs.execute("create table p2 (a bigint, w bigint) "
                   "distribute by shard(a)")
        cs.execute("insert into p1 values (1, 1), (2, 2)")
        cs.execute("insert into p2 values (1, 10), (2, 20)")
        # join only on `a` cannot use p1's (a,b) placement
        q = "select count(*) from p1, p2 where p1.a = p2.a"
        dp = cs._plan_distributed(parse_sql(q)[0])
        assert any(e.kind in ("redistribute", "broadcast")
                   for e in dp.exchanges)
        assert cs.query(q) == [(2,)]


class TestRangeDistribution:
    def test_split_point_placement(self, cs):
        cs.execute("create table r (k bigint, v bigint) "
                   "distribute by range (k) split (100, 200, 300)")
        cs.execute("insert into r values (5, 1), (150, 2), (250, 3), "
                   "(900, 4), (100, 5)")
        counts = [dn.stores["r"].row_count()
                  for dn in cs.cluster.datanodes]
        # [*,100) -> dn0; [100,200) -> dn1; [200,300) -> dn2; rest dn3
        assert counts == [1, 2, 1, 1], counts
        assert cs.query("select sum(v) from r") == [(15,)]

    def test_point_query_pins_one_node(self, cs):
        cs.execute("create table r2 (k bigint primary key, v bigint) "
                   "distribute by range (k) split (10, 20, 30)")
        cs.execute("insert into r2 values (5, 50), (25, 250)")
        assert cs.query("select v from r2 where k = 25") == [(250,)]
        td = cs.cluster.catalog.table("r2")
        assert cs.cluster.locator.node_for_values(td, [25]) == 2

    def test_date_split_points(self, cs):
        cs.execute("create table rd (d date, v bigint) distribute by "
                   "range (d) split ('1999-04-01', '1999-07-01', "
                   "'1999-10-01')")
        cs.execute("insert into rd values ('1999-02-01', 1), "
                   "('1999-05-01', 2), ('1999-08-01', 3), "
                   "('1999-12-01', 4)")
        counts = [dn.stores["rd"].row_count()
                  for dn in cs.cluster.datanodes]
        assert counts == [1, 1, 1, 1], counts
        assert cs.query("select sum(v) from rd "
                        "where d >= '1999-06-01'") == [(7,)]

    def test_unsorted_split_rejected(self, cs):
        with pytest.raises(Exception, match="ascending"):
            cs.execute("create table rb (k bigint) distribute by "
                       "range (k) split (20, 10)")


class TestNodeGroups:
    def test_group_placement_and_queries(self, cs):
        cs.execute("create node group g2 (dn0, dn1)")
        cs.execute("create table gt (k bigint primary key, v bigint) "
                   "distribute by shard(k) to group g2")
        cs.execute("insert into gt values " + ", ".join(
            f"({i}, {i})" for i in range(50)))
        counts = [dn.stores["gt"].row_count()
                  for dn in cs.cluster.datanodes]
        assert counts[2] == 0 and counts[3] == 0
        assert counts[0] + counts[1] == 50
        assert cs.query("select count(*) from gt") == [(50,)]
        assert cs.query("select v from gt where k = 33") == [(33,)]
        cs.execute("update gt set v = 999 where k = 33")
        assert cs.query("select v from gt where k = 33") == [(999,)]

    def test_same_group_colocated_join(self, cs):
        cs.execute("create node group g3 (dn1, dn2)")
        cs.execute("create table ga (k bigint, v bigint) "
                   "distribute by shard(k) to group g3")
        cs.execute("create table gb (k bigint, w bigint) "
                   "distribute by shard(k) to group g3")
        cs.execute("insert into ga values (1, 10), (2, 20), (3, 30)")
        cs.execute("insert into gb values (1, 1), (3, 3)")
        q = ("select count(*), sum(ga.v + gb.w) from ga, gb "
             "where ga.k = gb.k")
        dp = cs._plan_distributed(parse_sql(q)[0])
        assert [e.kind for e in dp.exchanges].count("redistribute") \
            == 0
        assert cs.query(q) == [(2, 44)]

    def test_cross_group_join_redistributes_both(self, cs):
        cs.execute("create node group g4 (dn0, dn1)")
        cs.execute("create table xa (k bigint, v bigint) "
                   "distribute by shard(k) to group g4")
        cs.execute("create table xb (k bigint, w bigint) "
                   "distribute by shard(k)")
        cs.execute("insert into xa values (1, 10), (2, 20)")
        cs.execute("insert into xb values (1, 1), (2, 2), (9, 9)")
        q = "select count(*) from xa, xb where xa.k = xb.k"
        # a group table's placement cannot anchor a default-map
        # redistribute: both sides move (correctness over elision)
        assert cs.query(q) == [(2,)]

    def test_unknown_group_rejected(self, cs):
        with pytest.raises(Exception, match="does not exist"):
            cs.execute("create table bad (k bigint) "
                       "distribute by shard(k) to group ghost")

    def test_duplicate_group_rejected(self, cs):
        cs.execute("create node group g5 (dn0)")
        with pytest.raises(ExecError, match="already exists"):
            cs.execute("create node group g5 (dn1)")

    def test_group_survives_catalog_reload(self, cs, tmp_path):
        from opentenbase_tpu.catalog.catalog import Catalog
        cs.execute("create node group g6 (dn2, dn3)")
        path = str(tmp_path / "cat.json")
        cs.cluster.catalog.save(path)
        cat2 = Catalog.load(path)
        assert cat2.node_groups["g6"] == [2, 3]
        assert set(cat2.shard_map_for_group("g6").tolist()) == {2, 3}
