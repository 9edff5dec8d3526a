"""Dump/restore round-trip (pg_dump/pg_restore analog, cli/dump.py)."""

import pytest

from opentenbase_tpu.cli.dump import dump_sql, restore_sql
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.parallel.cluster import Cluster


def _mk(ndn=3):
    return ClusterSession(Cluster(n_datanodes=ndn))


class TestRoundTrip:
    def test_schema_data_and_policies(self):
        s = _mk()
        s.execute("create table dp (id bigint primary key, nm text, "
                  "amt decimal(10,2), d date, f float, ok bool) "
                  "distribute by shard(id)")
        s.execute("create table dref (r bigint primary key, "
                  "pid bigint references dp (id)) "
                  "distribute by shard(r)")
        s.execute("insert into dp values "
                  "(1, 'it''s', 12.34, '1995-01-02', 1.5, true), "
                  "(2, null, 0.05, '1996-12-31', -2.25, false)")
        s.execute("insert into dref values (10, 1)")
        s.execute("create view v_dp as select id, amt from dp")
        s.execute("create function f_d() returns trigger as "
                  "'insert into dref values (old.id + 100, null)' "
                  "language sql")
        s.execute("create mask m_nm on dp (nm) as '''hidden'''")
        s.execute("create audit policy big on dp when (amt > 10)")
        s.execute("create resource group rg1 with (concurrency = 3)")
        script = dump_sql(s)

        # restore into a DIFFERENT topology (4 DNs vs 3)
        s2 = _mk(4)
        n = restore_sql(s2, script)
        assert n > 5
        s2.execute("set bypass_datamask = on")
        assert sorted(s2.query("select id, nm, amt, d, f, ok from dp")) \
            == [(1, "it's", 12.34, "1995-01-02", 1.5, True),
                (2, None, 0.05, "1996-12-31", -2.25, False)]
        s2.execute("set bypass_datamask = off")
        # mask restored
        assert s2.query("select nm from dp where id = 1") == \
            [("hidden",)]
        # view restored
        assert sorted(s2.query("select * from v_dp")) == \
            [(1, 12.34), (2, 0.05)]
        # FK restored and enforced
        import pytest as _p
        from opentenbase_tpu.exec.executor import ExecError
        with _p.raises(ExecError, match="foreign key"):
            s2.execute("insert into dref values (11, 999)")
        # resource group restored
        assert s2.cluster.catalog.resource_groups["rg1"][
            "concurrency"] == 3

    def test_partitioned_table_round_trip(self):
        s = _mk()
        s.execute("create table pp (k bigint primary key, v bigint) "
                  "distribute by shard(k) partition by range (k)")
        s.execute("create table pp_a partition of pp "
                  "for values from (0) to (100)")
        s.execute("create table pp_b partition of pp "
                  "for values from (100) to (200)")
        s.execute("insert into pp values (5, 50), (150, 1500)")
        script = dump_sql(s)
        s2 = _mk(2)
        restore_sql(s2, script)
        assert sorted(s2.query("select k, v from pp")) == \
            [(5, 50), (150, 1500)]
        assert s2.query("select count(*) from pp_b") == [(1,)]

    def test_trigger_round_trip_fires_after_restore(self):
        s = _mk()
        s.execute("create table tt (id bigint primary key)"
                  " distribute by shard(id)")
        s.execute("create table ta (aid bigint)"
                  " distribute by shard(aid)")
        s.execute("create function f_t() returns trigger as "
                  "'insert into ta values (new.id)' language sql")
        s.execute("create trigger tr_t after insert on tt "
                  "for each row execute function f_t()")
        s.execute("insert into tt values (1)")
        script = dump_sql(s)
        s2 = _mk(2)
        restore_sql(s2, script)
        # restored data did NOT re-fire (triggers created after data)
        assert s2.query("select count(*) from ta") == [(1,)]
        s2.execute("insert into tt values (2)")
        assert sorted(s2.query("select aid from ta")) == [(1,), (2,)]


class TestGlobalIndexDump:
    def test_global_index_round_trip(self):
        """Dump emits CREATE [UNIQUE] GLOBAL INDEX so a
        restored cluster keeps cluster-wide uniqueness and gidx point
        routing (the __gidx_* mapping tables are rebuilt, re-routed
        for the restored topology)."""
        s = _mk(ndn=4)
        s.execute("create table acc (id bigint primary key, "
                  "email bigint, v bigint) distribute by shard(id)")
        s.execute("insert into acc values (1, 100, 7), (2, 200, 8), "
                  "(3, 300, 9)")
        s.execute("create unique global index acc_email on acc "
                  "(email)")
        script = dump_sql(s)
        assert "create unique global index acc_email on acc (email);" \
            in script

        dst = _mk(ndn=2)           # different topology on purpose
        restore_sql(dst, script)
        gidx = dst.cluster.catalog.global_indexes
        assert "acc" in gidx and "email" in gidx["acc"]
        assert gidx["acc"]["email"]["unique"] is True
        # routed point read through the restored index
        assert dst.query("select v from acc where email = 200") \
            == [(8,)]
        # cluster-wide uniqueness survives the round trip
        import pytest as _pytest
        from opentenbase_tpu.exec.executor import ExecError
        with _pytest.raises(ExecError, match="unique|duplicate"):
            dst.execute("insert into acc values (9, 200, 1)")
