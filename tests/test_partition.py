"""Declarative partitioning: RANGE/LIST parents, bind-time pruning,
partition-routed DML (parallel/partition.py; reference:
src/backend/partitioning + nodePartIterator.c)."""

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.parallel.cluster import Cluster

DDL = [
    "create table m (id bigint, d date, v bigint) "
    "distribute by shard(id) partition by range (d)",
    "create table m_q1 partition of m "
    "for values from ('1999-01-01') to ('1999-04-01')",
    "create table m_q2 partition of m "
    "for values from ('1999-04-01') to ('1999-07-01')",
]
ROWS = ("insert into m values (1,'1999-02-10',10),"
        "(2,'1999-05-05',20),(3,'1999-03-03',30)")


@pytest.fixture()
def sess():
    s = Session(LocalNode())
    for d in DDL:
        s.execute(d)
    s.execute(ROWS)
    return s


@pytest.fixture()
def cs():
    s = ClusterSession(Cluster(n_datanodes=3))
    for d in DDL:
        s.execute(d)
    s.execute(ROWS)
    return s


class TestRangePartitions:
    def test_routing_and_union_read(self, sess):
        assert sess.query("select count(*) from m_q1") == [(2,)]
        assert sess.query("select count(*) from m_q2") == [(1,)]
        assert sorted(sess.query("select id, v from m")) == \
            [(1, 10), (2, 20), (3, 30)]

    def test_pruning_single_partition(self, sess):
        assert sess.query("select sum(v) from m "
                          "where d < '1999-04-01'") == [(40,)]
        assert sess.query("select sum(v) from m "
                          "where d between '1999-04-02' and "
                          "'1999-06-30'") == [(20,)]

    def test_pruned_query_keeps_mesh_tier(self, cs):
        """One surviving partition binds as a plain table, so the
        device data plane still carries the query."""
        assert cs.query("select sum(v) from m "
                        "where d < '1999-04-01'") == [(40,)]
        st = cs.last_query_stats()
        assert st["tier"] == "mesh", st["fallback"]

    def test_update_delete_through_parent(self, cs):
        cs.execute("update m set v = v + 1 where d >= '1999-04-01'")
        assert sorted(cs.query("select id, v from m")) == \
            [(1, 10), (2, 21), (3, 30)]
        cs.execute("delete from m where id = 1")
        assert sorted(cs.query("select id from m")) == [(2,), (3,)]

    def test_update_partition_key_rejected(self, cs):
        with pytest.raises(ExecError, match="partition key"):
            cs.execute("update m set d = '1999-06-01' where id = 1")

    def test_no_partition_for_row(self, sess):
        with pytest.raises(ExecError, match="no partition"):
            sess.execute("insert into m values (9,'2001-01-01',0)")

    def test_overlapping_bounds_rejected(self, sess):
        with pytest.raises(ExecError, match="overlap"):
            sess.execute("create table m_bad partition of m "
                         "for values from ('1999-03-01') to "
                         "('1999-05-01')")

    def test_drop_parent_drops_children(self, sess):
        sess.execute("drop table m")
        with pytest.raises(Exception):
            sess.query("select count(*) from m_q1")

    def test_joins_through_parent(self, cs):
        cs.execute("create table dim (dk bigint, nm varchar(4)) "
                   "distribute by replication")
        cs.execute("insert into dim values (1,'a'),(2,'b'),(3,'c')")
        got = sorted(cs.query(
            "select nm, v from m, dim where id = dk "
            "and d < '1999-04-01'"))
        assert got == [("a", 10), ("c", 30)]


class TestListPartitions:
    @pytest.fixture()
    def ls(self):
        s = Session(LocalNode())
        s.execute("create table ev (id bigint, region varchar(4), "
                  "v bigint) partition by list (region)")
        s.execute("create table ev_amer partition of ev "
                  "for values in ('us', 'ca')")
        s.execute("create table ev_emea partition of ev "
                  "for values in ('eu', 'uk')")
        s.execute("insert into ev values (1,'us',1),(2,'eu',2),"
                  "(3,'ca',3)")
        return s

    def test_routing(self, ls):
        assert ls.query("select count(*) from ev_amer") == [(2,)]
        assert sorted(ls.query("select id from ev")) == \
            [(1,), (2,), (3,)]

    def test_list_pruning(self, ls):
        assert ls.query("select sum(v) from ev "
                        "where region = 'us'") == [(1,)]
        assert ls.query("select sum(v) from ev "
                        "where region in ('us', 'ca')") == [(4,)]

    def test_duplicate_value_rejected(self, ls):
        with pytest.raises(ExecError, match="covered"):
            ls.execute("create table ev_x partition of ev "
                       "for values in ('us')")


class TestPartitionRecovery:
    def test_wal_replay(self, tmp_path):
        d = str(tmp_path / "node")
        s = Session(LocalNode(d))
        for ddl in DDL:
            s.execute(ddl)
        s.execute(ROWS)
        s2 = Session(LocalNode(d))
        assert sorted(s2.query("select id, v from m")) == \
            [(1, 10), (2, 20), (3, 30)]
        assert s2.query("select sum(v) from m "
                        "where d < '1999-04-01'") == [(40,)]
        s2.execute("insert into m values (4,'1999-06-20',40)")
        assert s2.query("select count(*) from m_q2") == [(2,)]

    def test_cluster_catalog_recovery(self, tmp_path):
        d = str(tmp_path / "c")
        c = Cluster(n_datanodes=2, datadir=d)
        s = ClusterSession(c)
        for ddl in DDL:
            s.execute(ddl)
        s.execute(ROWS)
        for dn in c.datanodes:
            dn.checkpoint(c.catalog)
        c2 = Cluster(datadir=d)
        s2 = ClusterSession(c2)
        assert sorted(s2.query("select id, v from m")) == \
            [(1, 10), (2, 20), (3, 30)]
        s2.execute("insert into m values (4,'1999-01-20',40)")
        assert s2.query("select count(*) from m_q1") == [(3,)]


class TestClusterParentParity:
    """Round-3 advisor findings: cluster-mode partition paths must match
    the single-node session (bounds check on child insert, ALTER
    recursion, parent-qualified DML)."""

    def test_child_insert_bound_enforced(self, cs):
        with pytest.raises(ExecError, match="partition constraint"):
            cs.execute("insert into m_q1 values (9,'1999-06-15',0)")
        # nothing silently dropped from parent reads
        assert cs.query("select count(*) from m "
                        "where d > '1999-06-01'") == [(0,)]

    def test_alter_recurses_to_children(self, cs):
        cs.execute("alter table m add column note bigint")
        cs.execute("insert into m values (7,'1999-02-02',70,700)")
        got = sorted(cs.query("select id, note from m"))
        assert got == [(1, None), (2, None), (3, None), (7, 700)] or \
            got == [(1, 0), (2, 0), (3, 0), (7, 700)]
        cs.execute("alter table m drop column note")
        assert len(cs.query("select * from m")[0]) == 3

    def test_parent_qualified_dml(self, cs):
        cs.execute("delete from m where m.d < '1999-04-01'")
        assert sorted(cs.query("select id from m")) == [(2,)]
        cs.execute("update m set v = m.v + 5 where m.id = 2")
        assert cs.query("select v from m") == [(25,)]


class TestRecursiveTypeCheck:
    def test_wider_recursive_term_rejected(self):
        s = Session(LocalNode())
        with pytest.raises(ExecError, match="recursive"):
            s.query("with recursive t(n) as (select 1 union all "
                    "select n+0.5 from t where n < 3) "
                    "select * from t")

    def test_null_and_float_base_columns_ok(self):
        s = Session(LocalNode())
        assert s.query(
            "with recursive t(n, m) as (select 1, null union all "
            "select n+1, m from t where n < 3) select n, m from t") == \
            [(1, None), (2, None), (3, None)]
        assert s.query(
            "with recursive t(n) as (select 1.5 union all "
            "select n+1 from t where n < 3) "
            "select count(*) from t") == [(3,)]


class TestAlterPartitionGuards:
    def test_child_rename_rejected(self, cs):
        with pytest.raises(ExecError, match="rename partition"):
            cs.execute("alter table m_q1 rename to zz")

    def test_partition_key_alter_rejected(self, cs):
        for bad in ("alter table m drop column d",
                    "alter table m rename column d to e",
                    "alter table m_q1 drop column d"):
            with pytest.raises(ExecError, match="partition key"):
                cs.execute(bad)
