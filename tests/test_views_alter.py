"""Views (rewriter expansion) + ALTER TABLE (column surgery).

Reference analogs: view.c DefineView + rewriteHandler.c inlining;
tablecmds.c ATExecAddColumn/ATExecDropColumn/renameatt with XC DDL
fan-out to every datanode."""

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.parallel.cluster import Cluster


@pytest.fixture()
def sess():
    s = Session(LocalNode())
    s.execute("create table emp (id bigint, dept varchar(8), sal bigint)")
    s.execute("insert into emp values (1,'eng',100),(2,'sales',80),"
              "(3,'hr',60)")
    return s


@pytest.fixture()
def cs():
    s = ClusterSession(Cluster(n_datanodes=3))
    s.execute("create table emp (id bigint, dept varchar(8), sal bigint)"
              " distribute by shard(id)")
    s.execute("insert into emp values (1,'eng',100),(2,'sales',80),"
              "(3,'hr',60)")
    return s


class TestViews:
    def test_basic_and_join(self, sess):
        sess.execute("create view rich as select id, sal from emp "
                     "where sal > 70")
        assert sorted(sess.query("select * from rich")) == \
            [(1, 100), (2, 80)]
        assert sess.query("select dept from emp, rich "
                          "where emp.id = rich.id and rich.sal = 100") \
            == [("eng",)]

    def test_or_replace_and_drop(self, sess):
        sess.execute("create view v1 as select id from emp")
        with pytest.raises(ExecError):
            sess.execute("create view v1 as select sal from emp")
        sess.execute("create or replace view v1 as select sal from emp "
                     "where sal > 90")
        assert sess.query("select * from v1") == [(100,)]
        sess.execute("drop view v1")
        with pytest.raises(Exception):
            sess.query("select * from v1")

    def test_view_on_view(self, sess):
        sess.execute("create view a1 as select id, sal from emp")
        sess.execute("create view b1 as select id from a1 "
                     "where sal >= 80")
        assert sorted(sess.query("select * from b1")) == [(1,), (2,)]

    def test_view_alias_and_aggregate(self, sess):
        sess.execute("create view per_dept as select dept, "
                     "sum(sal) as total from emp group by dept")
        got = sess.query("select p.total from per_dept p "
                         "where p.dept = 'eng'")
        assert got == [(100,)]

    def test_view_distributed_mesh(self, cs):
        cs.execute("create view rich as select id, sal from emp "
                   "where sal > 70")
        assert sorted(cs.query("select * from rich")) == \
            [(1, 100), (2, 80)]
        st = cs.last_query_stats()
        assert st["tier"] == "mesh", st["fallback"]

    def test_view_name_collision_with_table(self, sess):
        with pytest.raises(ExecError):
            sess.execute("create view emp as select 1")


class TestAlterTable:
    def test_add_column_nulls_then_insert(self, sess):
        sess.execute("alter table emp add column bonus decimal(8,2)")
        assert sorted(sess.query("select id, bonus from emp")) == \
            [(1, None), (2, None), (3, None)]
        sess.execute("insert into emp values (4,'ops',90,7.50)")
        assert sess.query("select id, bonus from emp "
                          "where bonus is not null") == [(4, 7.5)]
        # aggregates skip the NULL backfill
        assert sess.query("select count(bonus), sum(bonus) from emp") \
            == [(1, 7.5)]

    def test_rename_column(self, sess):
        sess.execute("alter table emp rename column sal to salary")
        assert sess.query("select salary from emp where id = 1") == \
            [(100,)]
        with pytest.raises(Exception):
            sess.query("select sal from emp")

    def test_drop_column(self, sess):
        sess.execute("alter table emp drop column dept")
        assert sess.query("select * from emp where id = 2") == \
            [(2, 80)]

    def test_rename_table(self, sess):
        sess.execute("alter table emp rename to staff")
        assert sess.query("select count(*) from staff") == [(3,)]
        with pytest.raises(Exception):
            sess.query("select count(*) from emp")

    def test_guards(self, cs):
        with pytest.raises(ExecError):
            cs.execute("alter table emp drop column id")     # dist key
        with pytest.raises(ExecError):
            cs.execute("alter table emp add column id int")  # duplicate
        with pytest.raises(ExecError):
            cs.execute("alter table emp rename column dept to sal")

    def test_alter_distributed(self, cs):
        cs.execute("alter table emp add column bonus decimal(8,2)")
        cs.execute("insert into emp values (4,'ops',90,7.50)")
        assert sorted(cs.query("select id, bonus from emp")) == \
            [(1, None), (2, None), (3, None), (4, 7.5)]
        cs.execute("alter table emp rename column dept to division")
        assert cs.query("select count(*) from emp "
                        "where division = 'eng'") == [(1,)]
        cs.execute("alter table emp drop column division")
        assert cs.query("select count(*) from emp") == [(4,)]


class TestAlterRecovery:
    def test_wal_replay_across_alter(self, tmp_path):
        """Inserts logged BEFORE an ALTER replay against the post-ALTER
        schema: missing columns read NULL, dropped ones are ignored."""
        d = str(tmp_path / "node")
        s = Session(LocalNode(d))
        s.execute("create table t (a bigint, b varchar(4))")
        s.execute("insert into t values (1,'x'),(2,'y')")
        s.execute("alter table t add column c decimal(6,2)")
        s.execute("insert into t values (3,'z',1.25)")
        s.execute("alter table t drop column b")
        want = sorted(s.query("select a, c from t"))
        # crash (no checkpoint): full WAL replay
        s2 = Session(LocalNode(d))
        assert sorted(s2.query("select a, c from t")) == want == \
            [(1, None), (2, None), (3, 1.25)]

    def test_checkpoint_then_alter_replay(self, tmp_path):
        d = str(tmp_path / "node")
        s = Session(LocalNode(d))
        s.execute("create table t (a bigint)")
        s.execute("insert into t values (1),(2)")
        s.node.checkpoint()
        s.execute("alter table t add column c bigint")
        s.execute("insert into t values (3, 30)")
        s2 = Session(LocalNode(d))
        assert sorted(s2.query("select a, c from t")) == \
            [(1, None), (2, None), (3, 30)]

    def test_view_persistence(self, tmp_path):
        d = str(tmp_path / "node")
        s = Session(LocalNode(d))
        s.execute("create table t (a bigint)")
        s.execute("insert into t values (5)")
        s.execute("create view v as select a from t where a > 1")
        s2 = Session(LocalNode(d))
        assert s2.query("select * from v") == [(5,)]
