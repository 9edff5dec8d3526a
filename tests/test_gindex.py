"""Global secondary indexes: CREATE GLOBAL INDEX, write-path maintenance
under the SAME transaction/2PC as the base write, single-node routing of
point queries on non-distribution keys, and crash-window consistency.

Reference analogs: allow_global_index_path (optimizer/path/
indxpath.c:4331), exec-time routing through the index relation's
distribution (pgxc/locator/locator.c:2396).
"""

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.utils import faultinject as FI


@pytest.fixture()
def s():
    sess = ClusterSession(Cluster(n_datanodes=4))
    sess.execute("create table emp (id bigint primary key, badge bigint, "
                 "name varchar(12)) distribute by shard(id)")
    sess.execute("insert into emp values " + ", ".join(
        f"({i}, {1000 + i}, 'e{i}')" for i in range(100)))
    yield sess
    FI.disarm()


def _count_touches(sess):
    calls = {"n": 0}
    for dn in sess.cluster.datanodes:
        orig = dn.exec_plan

        def wrap(o):
            def f(*a, **k):
                calls["n"] += 1
                return o(*a, **k)
            return f
        dn.exec_plan = wrap(orig)
    return calls


class TestRouting:
    def test_point_query_routes_single_node(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        calls = _count_touches(s)
        assert s.query("select id, name from emp where badge = 1042") \
            == [(42, "e42")]
        assert s.last_query_stats()["tier"] == "gidx"
        # mapping lookup + (in-process fast path) base exec: <= 2 nodes
        assert calls["n"] <= 2

    def test_explain_shows_route(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        txt = s.execute("explain select id from emp "
                        "where badge = 1005")[-1].text
        assert "Global Index Route via gi_badge" in txt

    def test_missing_key_proven_empty_via_mapping(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        calls = _count_touches(s)
        assert s.query("select id from emp where badge = 99999") == []
        assert s.last_query_stats()["tier"] == "gidx"
        assert calls["n"] <= 1

    def test_prepared_text_key_still_routes(self, s):
        # a TEXT parameter's string is what the mapping probe reads at
        # plan time: with a global index about, such a PREPARE
        # substitutes and replans, and routes like the ad-hoc statement
        s.execute("create unique global index gi_name on emp (name)")
        s.execute("prepare byname (varchar(12)) as "
                  "select id from emp where name = $1")
        assert s.prepared["byname"].mode == "ast"
        assert s.query("execute byname ('e42')") == [(42,)]
        assert s.last_query_stats()["tier"] == "gidx"
        assert s.query("select id from emp where name = 'e7'") == [(7,)]
        assert s.last_query_stats()["tier"] == "gidx"

    def test_guc_disables_route(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        s.execute("set enable_global_indexscan = off")
        assert s.query("select id from emp where badge = 1042") == [(42,)]
        assert s.last_query_stats()["tier"] != "gidx"

    def test_non_selective_key_falls_through_correctly(self, s):
        # dozens of rows share cat=3 across nodes: no single-node pin,
        # the distributed plan answers (correctness over routing)
        s.execute("create table ev (eid bigint primary key, cat bigint) "
                  "distribute by shard(eid)")
        s.execute("insert into ev values " + ", ".join(
            f"({i}, {i % 5})" for i in range(100)))
        s.execute("create global index gi_cat on ev (cat)")
        got = s.query("select eid from ev where cat = 3 order by eid")
        assert got == [(i,) for i in range(100) if i % 5 == 3]


class TestMaintenance:
    def test_insert_delete_update_follow(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        s.execute("insert into emp values (500, 9500, 'new')")
        assert s.query("select id from emp where badge = 9500") == [(500,)]
        assert s.last_query_stats()["tier"] == "gidx"
        s.execute("update emp set badge = 9501 where id = 500")
        assert s.query("select id from emp where badge = 9501") == [(500,)]
        assert s.query("select id from emp where badge = 9500") == []
        s.execute("delete from emp where id = 500")
        assert s.query("select id from emp where badge = 9501") == []

    def test_upsert_maintains_index(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        s.execute("insert into emp values (42, 8042, 'x') "
                  "on conflict (id) do update set badge = excluded.badge")
        assert s.query("select id from emp where badge = 8042") == [(42,)]
        assert s.query("select id from emp where badge = 1042") == []

    def test_unique_violation_rolls_back_base_row(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        with pytest.raises(ExecError, match="unique"):
            s.execute("insert into emp values (600, 1042, 'dup')")
        assert s.query("select count(*) from emp") == [(100,)]
        assert s.query("select id from emp where id = 600") == []

    def test_duplicate_backfill_blocks_unique_create(self, s):
        s.execute("insert into emp values (700, 1001, 'dup')")
        with pytest.raises(ExecError, match="duplicate"):
            s.execute("create unique global index gi_bad on emp (badge)")
        # failed create leaves no registry entry or mapping table
        assert "emp" not in s.cluster.catalog.global_indexes
        assert "__gidx_emp_badge" not in s.cluster.catalog.tables

    def test_nonunique_duplicate_keys_survive_partial_delete(self, s):
        s.execute("create table t2 (a bigint primary key, g bigint, "
                  "v bigint) distribute by shard(a)")
        s.execute("insert into t2 values (1, 7, 10), (2, 7, 20), "
                  "(3, 8, 30)")
        s.execute("create global index gi_g on t2 (g)")
        s.execute("delete from t2 where a = 1")
        # the surviving g=7 row is still reachable through the index
        assert s.query("select a from t2 where g = 7") == [(2,)]

    def test_txn_rollback_undoes_index_entries(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        s.execute("begin")
        s.execute("insert into emp values (800, 9800, 'rb')")
        assert s.query("select id from emp where badge = 9800") == [(800,)]
        s.execute("rollback")
        assert s.query("select id from emp where badge = 9800") == []
        # and the key is reusable afterwards
        s.execute("insert into emp values (801, 9800, 'ok')")
        assert s.query("select id from emp where badge = 9800") == [(801,)]


class TestDdl:
    def test_create_refused_inside_txn_block(self, s):
        s.execute("begin")
        with pytest.raises(ExecError, match="transaction block"):
            s.execute("create global index gi_b on emp (badge)")
        s.execute("rollback")
        assert "emp" not in s.cluster.catalog.global_indexes

    def test_unique_violation_poisons_explicit_txn(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        s.execute("begin")
        with pytest.raises(ExecError, match="unique"):
            s.execute("insert into emp values (900, 1042, 'dup')")
        # PG semantics: the txn is aborted; COMMIT rolls back
        with pytest.raises(ExecError, match="aborted"):
            s.query("select 1")
        r = s.execute("commit")[-1]
        assert r.command == "ROLLBACK"
        # the staged duplicate base row must NOT have survived
        assert s.query("select count(*) from emp") == [(100,)]
        assert s.query("select id from emp where id = 900") == []

    def test_drop_local_btree_index(self, s):
        s.execute("create index li_name on emp (badge)")
        assert "badge" in s.cluster.catalog.btree_cols.get("emp", set())
        s.execute("drop index li_name")
        assert "badge" not in s.cluster.catalog.btree_cols.get("emp",
                                                               set())
        with pytest.raises(ExecError):
            s.execute("drop index li_name")
        s.execute("drop index if exists li_name")

    def test_drop_table_drops_its_global_indexes(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        s.execute("drop table emp")
        assert "emp" not in s.cluster.catalog.global_indexes
        assert "__gidx_emp_badge" not in s.cluster.catalog.tables
        # a recreated table must not inherit phantom uniqueness/routing
        s.execute("create table emp (id bigint primary key, "
                  "badge bigint, name varchar(12)) "
                  "distribute by shard(id)")
        s.execute("insert into emp values (7, 1042, 'fresh')")
        assert s.query("select id from emp where badge = 1042") == [(7,)]
        assert s.last_query_stats()["tier"] != "gidx"

    def test_drop_index(self, s):
        s.execute("create unique global index gi_badge on emp (badge)")
        s.execute("drop index gi_badge")
        assert "__gidx_emp_badge" not in s.cluster.catalog.tables
        assert s.query("select id from emp where badge = 1042") == [(42,)]
        with pytest.raises(ExecError):
            s.execute("drop index gi_badge")
        s.execute("drop index if exists gi_badge")

    def test_requires_shard_table_and_non_dist_key(self, s):
        with pytest.raises(ExecError, match="already"):
            s.execute("create global index gi_id on emp (id)")
        s.execute("create table rt (a bigint primary key, b bigint) "
                  "distribute by replication")
        with pytest.raises(ExecError, match="SHARD"):
            s.execute("create global index gi_rt on rt (b)")


class TestCrashConsistency:
    """The mapping write rides the base txn's 2PC: every crash-window
    outcome must leave heap and index agreeing (the done-condition of
    VERDICT r3 item #3)."""

    def _setup(self, tmp_path):
        s = ClusterSession(Cluster(datadir=str(tmp_path / "cl"),
                                   n_datanodes=4))
        s.execute("create table emp (id bigint primary key, "
                  "badge bigint, name varchar(12)) "
                  "distribute by shard(id)")
        s.execute("insert into emp values " + ", ".join(
            f"({i}, {1000 + i}, 'e{i}')" for i in range(40)))
        s.execute("create unique global index gi_badge on emp (badge)")
        return s

    def _crashy_insert(self, s, point):
        s.execute("begin")
        s.execute("insert into emp values " + ", ".join(
            f"({i}, {2000 + i}, 'n{i}')" for i in range(100, 140)))
        FI.arm(point)
        with pytest.raises(FI.InjectedFault):
            s.execute("commit")
        s.txn = None

    def _check_consistent(self, s2, expect_new: bool):
        n = 80 if expect_new else 40
        assert s2.query("select count(*) from emp") == [(n,)]
        assert s2.query("select count(*) from __gidx_emp_badge") == [(n,)]
        # index answers match a full scan for both old and new keys
        assert s2.query("select id from emp where badge = 1005") == [(5,)]
        want = [(105,)] if expect_new else []
        assert s2.query("select id from emp "
                        "where badge = 2105") == want

    @pytest.mark.parametrize("point,expect_new", [
        ("REMOTE_PREPARE_AFTER_SEND", False),
        ("AFTER_GTM_COMMIT_BEFORE_DN", True),
        ("REMOTE_COMMIT_PARTIAL", True),
    ])
    def test_crash_window_keeps_heap_and_index_agreeing(
            self, tmp_path, point, expect_new):
        s = self._setup(tmp_path)
        self._crashy_insert(s, point)
        FI.disarm()
        s2 = ClusterSession(Cluster(datadir=str(tmp_path / "cl")))
        self._check_consistent(s2, expect_new)


class TestPersistence:
    def test_registry_survives_restart(self, tmp_path):
        s = ClusterSession(Cluster(datadir=str(tmp_path / "cl"),
                                   n_datanodes=2))
        s.execute("create table emp (id bigint primary key, "
                  "badge bigint) distribute by shard(id)")
        s.execute("insert into emp values (1, 100), (2, 200)")
        s.execute("create unique global index gi_badge on emp (badge)")
        s.cluster.checkpoint()
        s2 = ClusterSession(Cluster(datadir=str(tmp_path / "cl")))
        assert s2.query("select id from emp where badge = 200") == [(2,)]
        assert s2.last_query_stats()["tier"] == "gidx"
        s2.execute("insert into emp values (3, 300)")
        assert s2.query("select id from emp where badge = 300") == [(3,)]
