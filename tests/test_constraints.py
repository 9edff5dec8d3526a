"""Constraints (NOT NULL / CHECK / FOREIGN KEY), TRUNCATE, MERGE, and
SAVEPOINT — on BOTH the single-node and cluster tiers (reference:
ExecConstraints execMain.c, ri_triggers.c, ExecuteTruncate tablecmds.c,
ExecMerge execMerge.c, subxact machinery xact.c)."""

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.parallel.cluster import Cluster


@pytest.fixture(params=["single", "cluster"])
def sess(request):
    if request.param == "single":
        return Session(LocalNode())
    return ClusterSession(Cluster(n_datanodes=3))


DIST = " distribute by shard({})"


def _mk(sess, ddl_single: str, key: str):
    """Run DDL with a dist clause only on the cluster tier."""
    if isinstance(sess, ClusterSession):
        ddl_single += DIST.format(key)
    sess.execute(ddl_single)


class TestNotNull:
    def test_insert_null_rejected(self, sess):
        _mk(sess, "create table n1 (k bigint primary key, "
                  "v bigint not null)", "k")
        sess.execute("insert into n1 values (1, 10)")
        with pytest.raises(ExecError, match="not-null"):
            sess.execute("insert into n1 values (2, null)")
        assert sess.query("select count(*) from n1") == [(1,)]

    def test_update_to_null_rejected(self, sess):
        _mk(sess, "create table n2 (k bigint primary key, "
                  "v bigint not null)", "k")
        sess.execute("insert into n2 values (1, 10)")
        with pytest.raises(ExecError, match="not-null"):
            sess.execute("update n2 set v = null where k = 1")
        assert sess.query("select v from n2") == [(10,)]


class TestCheck:
    def test_column_check(self, sess):
        _mk(sess, "create table c1 (k bigint primary key, "
                  "amt bigint check (amt > 0))", "k")
        sess.execute("insert into c1 values (1, 5)")
        with pytest.raises(ExecError, match="check constraint"):
            sess.execute("insert into c1 values (2, -1)")
        assert sess.query("select count(*) from c1") == [(1,)]

    def test_table_check_multi_column(self, sess):
        _mk(sess, "create table c2 (k bigint primary key, lo bigint, "
                  "hi bigint, check (lo < hi))", "k")
        sess.execute("insert into c2 values (1, 1, 2)")
        with pytest.raises(ExecError, match="check constraint"):
            sess.execute("insert into c2 values (2, 9, 3)")

    def test_check_null_passes(self, sess):
        # SQL: a NULL check result is not a violation
        _mk(sess, "create table c3 (k bigint primary key, "
                  "amt bigint check (amt > 0))", "k")
        sess.execute("insert into c3 values (1, null)")
        assert sess.query("select count(*) from c3") == [(1,)]

    def test_update_violating_check_rejected(self, sess):
        _mk(sess, "create table c4 (k bigint primary key, "
                  "amt bigint check (amt > 0))", "k")
        sess.execute("insert into c4 values (1, 5)")
        with pytest.raises(ExecError, match="check constraint"):
            sess.execute("update c4 set amt = -9 where k = 1")
        assert sess.query("select amt from c4") == [(5,)]


class TestForeignKey:
    @pytest.fixture(autouse=True)
    def _tables(self, sess):
        _mk(sess, "create table fparent (pk bigint primary key, "
                  "nm bigint)", "pk")
        _mk(sess, "create table fchild (ck bigint primary key, "
                  "fk bigint references fparent (pk))", "ck")
        sess.execute("insert into fparent values (1, 10), (2, 20)")
        self.s = sess

    def test_insert_orphan_rejected(self):
        self.s.execute("insert into fchild values (100, 1)")
        with pytest.raises(ExecError, match="foreign key"):
            self.s.execute("insert into fchild values (101, 9)")
        assert self.s.query("select count(*) from fchild") == [(1,)]

    def test_null_fk_passes(self):
        self.s.execute("insert into fchild values (100, null)")
        assert self.s.query("select count(*) from fchild") == [(1,)]

    def test_referenced_parent_delete_rejected(self):
        self.s.execute("insert into fchild values (100, 1)")
        with pytest.raises(ExecError, match="foreign key"):
            self.s.execute("delete from fparent where pk = 1")
        # the unreferenced parent row deletes fine
        self.s.execute("delete from fparent where pk = 2")
        assert self.s.query("select count(*) from fparent") == [(1,)]

    def test_parent_key_update_away_rejected(self):
        self.s.execute("insert into fchild values (100, 1)")
        with pytest.raises(ExecError, match="foreign key"):
            self.s.execute("update fparent set nm = 0, pk = 7 "
                           "where pk = 1")


class TestTruncate:
    def test_truncate_and_reuse(self, sess):
        _mk(sess, "create table t1 (k bigint primary key, v bigint)",
            "k")
        sess.execute("insert into t1 values (1, 1), (2, 2), (3, 3)")
        sess.execute("truncate table t1")
        assert sess.query("select count(*) from t1") == [(0,)]
        sess.execute("insert into t1 values (9, 9)")
        assert sess.query("select k from t1") == [(9,)]

    def test_truncate_referenced_rejected(self, sess):
        _mk(sess, "create table tp (pk bigint primary key)", "pk")
        _mk(sess, "create table tc (ck bigint primary key, "
                  "fk bigint references tp (pk))", "ck")
        with pytest.raises(ExecError, match="referenced"):
            sess.execute("truncate table tp")

    def test_truncate_in_txn_rejected(self, sess):
        _mk(sess, "create table t2 (k bigint primary key)", "k")
        sess.execute("begin")
        with pytest.raises(ExecError, match="transaction block"):
            sess.execute("truncate table t2")
        sess.execute("rollback")

    def test_truncate_survives_recovery(self, tmp_path):
        d = str(tmp_path / "n")
        s = Session(LocalNode(d))
        s.execute("create table tw (k bigint primary key)")
        s.execute("insert into tw values (1), (2)")
        s.execute("truncate table tw")
        s.execute("insert into tw values (7)")
        s2 = Session(LocalNode(d))
        assert s2.query("select k from tw") == [(7,)]


class TestSavepoint:
    def test_nested_rollback_to(self, sess):
        _mk(sess, "create table s1 (k bigint primary key, v bigint)",
            "k")
        sess.execute("begin")
        sess.execute("insert into s1 values (1, 1)")
        sess.execute("savepoint a")
        sess.execute("insert into s1 values (2, 2)")
        sess.execute("savepoint b")
        sess.execute("delete from s1 where k = 1")
        sess.execute("rollback to b")
        assert sess.query("select count(*) from s1") == [(2,)]
        sess.execute("rollback to a")
        assert sess.query("select count(*) from s1") == [(1,)]
        sess.execute("commit")
        assert sess.query("select k from s1") == [(1,)]

    def test_recovers_failed_txn(self, sess):
        _mk(sess, "create table s2 (k bigint primary key)", "k")
        sess.execute("begin")
        sess.execute("savepoint sp")
        with pytest.raises(Exception):
            sess.execute("select * from nonexistent")
        sess.execute("rollback to sp")
        sess.execute("insert into s2 values (5)")
        sess.execute("commit")
        assert sess.query("select k from s2") == [(5,)]

    def test_release_then_commit(self, sess):
        _mk(sess, "create table s3 (k bigint primary key)", "k")
        sess.execute("begin")
        sess.execute("savepoint a")
        sess.execute("insert into s3 values (1)")
        sess.execute("release a")
        with pytest.raises(ExecError, match="does not exist"):
            sess.execute("rollback to a")
        sess.execute("rollback")   # the error poisoned the txn
        assert sess.query("select count(*) from s3") == [(0,)]

    def test_outside_txn_rejected(self, sess):
        with pytest.raises(ExecError, match="transaction block"):
            sess.execute("savepoint x")

    def test_subabort_survives_recovery(self, tmp_path):
        d = str(tmp_path / "n")
        s = Session(LocalNode(d))
        s.execute("create table sw (k bigint primary key)")
        s.execute("begin")
        s.execute("insert into sw values (1)")
        s.execute("savepoint a")
        s.execute("insert into sw values (2)")
        s.execute("rollback to a")
        s.execute("commit")
        s2 = Session(LocalNode(d))
        assert s2.query("select k from sw") == [(1,)]


class TestMerge:
    @pytest.fixture(autouse=True)
    def _tables(self, sess):
        _mk(sess, "create table mt (k bigint primary key, v bigint)",
            "k")
        _mk(sess, "create table ms (k bigint primary key, v bigint)",
            "k")
        sess.execute("insert into mt values (1, 10), (2, 20)")
        sess.execute("insert into ms values (2, 200), (3, 300)")
        self.s = sess

    def test_upsert_shape(self):
        self.s.execute(
            "merge into mt using ms on mt.k = ms.k "
            "when matched then update set v = ms.v "
            "when not matched then insert values (ms.k, ms.v)")
        assert sorted(self.s.query("select k, v from mt")) == \
            [(1, 10), (2, 200), (3, 300)]

    def test_matched_delete(self):
        self.s.execute("merge into mt using ms on mt.k = ms.k "
                       "when matched then delete")
        assert self.s.query("select k from mt") == [(1,)]

    def test_update_expression_mixes_sides(self):
        self.s.execute("merge into mt using ms on mt.k = ms.k "
                       "when matched then update set v = mt.v + ms.v")
        assert sorted(self.s.query("select k, v from mt")) == \
            [(1, 10), (2, 220)]

    def test_insert_only(self):
        self.s.execute(
            "merge into mt using ms on mt.k = ms.k "
            "when not matched then insert values (ms.k, ms.v)")
        assert sorted(self.s.query("select k, v from mt")) == \
            [(1, 10), (2, 20), (3, 300)]


class TestOuterJoinQualPlacement:
    """The planner must not push WHERE quals on the null-extended side
    below an outer join (found while building the FK anti-join;
    reference: initsplan.c qual placement rules)."""

    def test_is_null_above_left_join(self, sess):
        _mk(sess, "create table qp (pk bigint primary key)", "pk")
        _mk(sess, "create table qc (ck bigint primary key, fk bigint)",
            "ck")
        sess.execute("insert into qp values (1), (2)")
        sess.execute("insert into qc values (100, 1), (101, null), "
                     "(102, 9)")
        q = ("select c.ck from qc c left join qp p on c.fk = p.pk "
             "where ")
        assert sorted(sess.query(q + "p.pk is null")) == \
            [(101,), (102,)]
        assert sess.query(q + "c.fk is not null and p.pk is null") == \
            [(102,)]
        assert sess.query(q + "p.pk is not null") == [(100,)]


class TestDependencyGuards:
    def test_drop_referenced_parent_rejected(self, sess):
        _mk(sess, "create table dp (pk bigint primary key)", "pk")
        _mk(sess, "create table dc (ck bigint primary key, "
                  "fk bigint references dp (pk))", "ck")
        with pytest.raises(ExecError, match="referenced"):
            sess.execute("drop table dp")
        sess.execute("drop table dc")
        sess.execute("drop table dp")   # children gone: parent drops

    def test_drop_check_column_rejected(self, sess):
        _mk(sess, "create table dk (k bigint primary key, a bigint, "
                  "b bigint, check (a < b))", "k")
        for bad in ("alter table dk drop column b",
                    "alter table dk rename column a to z"):
            with pytest.raises(ExecError, match="check constraint"):
                sess.execute(bad)

    def test_drop_fk_column_rejected(self, sess):
        _mk(sess, "create table fp2 (pk bigint primary key, "
                  "rk bigint, x bigint)", "pk")
        _mk(sess, "create table fc2 (ck bigint primary key, "
                  "fk bigint references fp2 (rk))", "ck")
        with pytest.raises(ExecError, match="foreign key"):
            sess.execute("alter table fc2 drop column fk")
        with pytest.raises(ExecError, match="foreign key"):
            sess.execute("alter table fp2 drop column rk")
        sess.execute("alter table fp2 drop column x")  # unrelated: ok


class TestMergeEdgeCases:
    def test_duplicate_source_keys_rejected(self, sess):
        _mk(sess, "create table md (k bigint primary key, v bigint)",
            "k")
        _mk(sess, "create table msd (sk bigint primary key, k bigint, "
                  "v bigint)", "sk")
        sess.execute("insert into md values (1, 10)")
        sess.execute("insert into msd values (7, 1, 100), (8, 1, 200)")
        with pytest.raises(ExecError, match="second time"):
            sess.execute("merge into md using msd on md.k = msd.k "
                         "when matched then update set v = msd.v")
        assert sess.query("select v from md") == [(10,)]

    def test_merge_into_partitioned_parent(self, sess):
        ddl = ("create table mp (k bigint, d date, v bigint)"
               + (DIST.format("k") if isinstance(sess, ClusterSession)
                  else "") + " partition by range (d)")
        sess.execute(ddl)
        sess.execute("create table mp_a partition of mp for values "
                     "from ('1999-01-01') to ('1999-06-01')")
        sess.execute("create table mp_b partition of mp for values "
                     "from ('1999-06-01') to ('2000-01-01')")
        sess.execute("insert into mp values (1, '1999-02-01', 10)")
        _mk(sess, "create table mps (k bigint primary key, d date, "
                  "v bigint)", "k")
        sess.execute("insert into mps values (1, '1999-02-01', 100), "
                     "(2, '1999-07-01', 200)")
        sess.execute(
            "merge into mp using mps on mp.k = mps.k "
            "when matched then update set v = mps.v "
            "when not matched then insert values (mps.k, mps.d, mps.v)")
        assert sorted(sess.query("select k, v from mp")) == \
            [(1, 100), (2, 200)]
        # rows landed in the right partitions (parent reads see them)
        assert sess.query("select count(*) from mp_a") == [(1,)]
        assert sess.query("select count(*) from mp_b") == [(1,)]


class TestTruncateConcurrency:
    def test_truncate_refused_under_open_txn(self):
        cl = Cluster(n_datanodes=2)
        s1, s2 = ClusterSession(cl), ClusterSession(cl)
        s1.execute("create table tt (k bigint primary key) "
                   "distribute by shard(k)")
        s1.execute("begin")
        s1.execute("insert into tt values (1), (2)")
        with pytest.raises(Exception, match="in-flight"):
            s2.execute("truncate table tt")
        s1.execute("commit")
        s2.execute("truncate table tt")
        assert s1.query("select count(*) from tt") == [(0,)]


class TestMergeCardinality:
    def test_target_duplicates_legal(self, sess):
        _mk(sess, "create table mt2 (k bigint, v bigint)", "k")
        _mk(sess, "create table ms2 (k bigint primary key, v bigint)",
            "k")
        sess.execute("insert into mt2 values (1, 10), (1, 11)")
        sess.execute("insert into ms2 values (1, 100)")
        sess.execute("merge into mt2 using ms2 on mt2.k = ms2.k "
                     "when matched then update set v = ms2.v")
        assert sorted(sess.query("select k, v from mt2")) == \
            [(1, 100), (1, 100)]


class TestNodeGroupRecovery:
    def test_single_node_group_survives_restart(self, tmp_path):
        d = str(tmp_path / "n")
        s = Session(LocalNode(d))
        s.execute("create node group g1 (dn0)")
        s.execute("create table gt (k bigint primary key) "
                  "distribute by shard(k) to group g1")
        s.execute("insert into gt values (1)")
        s2 = Session(LocalNode(d))
        assert s2.node.catalog.node_groups.get("g1") == [0]
        assert s2.query("select count(*) from gt") == [(1,)]


class TestSelfReferencingFk:
    """The delete-side orphan scan must include the table's
    own self-FKs (reference: ri_triggers.c enforces them identically)."""

    @pytest.fixture(autouse=True)
    def _tables(self, sess):
        _mk(sess, "create table emp (id bigint primary key, "
                  "mgr bigint references emp (id))", "id")
        self.s = sess

    def test_delete_referenced_parent_rejected(self):
        self.s.execute("insert into emp values (1, 1)")
        self.s.execute("insert into emp values (2, 1)")
        with pytest.raises(ExecError, match="foreign key"):
            self.s.execute("delete from emp where id = 1")
        assert self.s.query("select count(*) from emp") == [(2,)]

    def test_delete_parent_and_children_together_passes(self):
        self.s.execute("insert into emp values (1, 1)")
        self.s.execute("insert into emp values (2, 1)")
        self.s.execute("delete from emp where id >= 1")
        assert self.s.query("select count(*) from emp") == [(0,)]

    def test_delete_leaf_passes(self):
        self.s.execute("insert into emp values (1, 1)")
        self.s.execute("insert into emp values (2, 1)")
        self.s.execute("delete from emp where id = 2")
        assert self.s.query("select count(*) from emp") == [(1,)]


class TestPartitionConstraintInheritance:
    """CHECK/FK declared on a partitioned parent must be
    enforced for rows routed to partition children (reference:
    ExecConstraints runs after ExecFindPartition)."""

    @staticmethod
    def _mkpart(sess, head: str, key: str, tail: str):
        """DDL with dist clause BEFORE the partition clause (grammar
        order: distribute by ... partition by ...)."""
        d = DIST.format(key) if isinstance(sess, ClusterSession) else ""
        sess.execute(head + d + " " + tail)

    def test_parent_check_enforced_on_routed_insert(self, sess):
        self._mkpart(sess, "create table pc (k bigint primary key, "
                     "v bigint check (v > 0))", "k",
                     "partition by range (k)")
        sess.execute("create table pc_a partition of pc "
                     "for values from (0) to (100)")
        sess.execute("insert into pc values (1, 5)")
        with pytest.raises(ExecError, match="check constraint"):
            sess.execute("insert into pc values (2, -5)")
        assert sess.query("select count(*) from pc") == [(1,)]

    def test_parent_check_enforced_on_direct_child_insert(self, sess):
        self._mkpart(sess, "create table pd (k bigint primary key, "
                     "v bigint check (v > 0))", "k",
                     "partition by range (k)")
        sess.execute("create table pd_a partition of pd "
                     "for values from (0) to (100)")
        with pytest.raises(ExecError, match="check constraint"):
            sess.execute("insert into pd_a values (2, -5)")

    def test_parent_fk_enforced_on_routed_insert(self, sess):
        _mk(sess, "create table pref (r bigint primary key)", "r")
        self._mkpart(sess, "create table pf (k bigint primary key, "
                     "fk bigint references pref (r))", "k",
                     "partition by range (k)")
        sess.execute("create table pf_a partition of pf "
                     "for values from (0) to (100)")
        sess.execute("insert into pref values (7)")
        sess.execute("insert into pf values (1, 7)")
        with pytest.raises(ExecError, match="foreign key"):
            sess.execute("insert into pf values (2, 99)")
        assert sess.query("select count(*) from pf") == [(1,)]


class TestGddIterativeDfs:
    def test_long_wait_chain_no_recursion_error(self):
        from opentenbase_tpu.parallel.gdd import find_cycle
        # chain 0 -> 1 -> ... -> N, with a cycle closing at the tail
        n = 5000
        edges = {i: {i + 1} for i in range(n)}
        edges[n] = {n - 3}
        cycle = find_cycle(edges)
        assert cycle is not None
        assert set(cycle) == {n - 3, n - 2, n - 1, n}

    def test_chain_without_cycle(self):
        from opentenbase_tpu.parallel.gdd import find_cycle
        edges = {i: {i + 1} for i in range(5000)}
        assert find_cycle(edges) is None

    def test_small_cycle_still_found(self):
        from opentenbase_tpu.parallel.gdd import find_cycle
        got = find_cycle({1: {2}, 2: {1}})
        assert set(got) == {1, 2}


class TestChildDeleteParentFk:
    """DELETE against a partition child must still enforce FKs that
    reference the partitioned PARENT (FK targets resolve through the
    parent name)."""

    def test_child_delete_orphan_rejected(self, sess):
        d = DIST.format("id") if isinstance(sess, ClusterSession) else ""
        sess.execute("create table parentp (id bigint primary key)"
                     + d + " partition by range (id)")
        sess.execute("create table parentp_a partition of parentp "
                     "for values from (0) to (100)")
        _mk(sess, "create table childt (c bigint primary key, "
                  "p bigint references parentp (id))", "c")
        sess.execute("insert into parentp values (5)")
        sess.execute("insert into childt values (1, 5)")
        with pytest.raises(ExecError, match="foreign key"):
            sess.execute("delete from parentp_a where id = 5")
        with pytest.raises(ExecError, match="foreign key"):
            sess.execute("delete from parentp where id = 5")
        sess.execute("delete from childt where c = 1")
        sess.execute("delete from parentp_a where id = 5")
        assert sess.query("select count(*) from parentp") == [(0,)]
