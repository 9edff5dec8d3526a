"""OLTP fast path: PREPARE/EXECUTE (plan cache + parameterized plans),
the light-coordinator single-node routing for dist-key-pinned statements,
and INSERT ... ON CONFLICT (UPSERT).

Reference analogs: commands/prepare.c + the extended-protocol plan cache
(tcop/postgres.c:2411 CreateCachedPlan), execLight.c:34-59
(enable_light_coord single-node fast path), and the UPSERT legs of
pgxc_build_upsert_statement (pgxc/plan/planner.c:1070).
"""

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.parallel.cluster import Cluster


@pytest.fixture(scope="module")
def s():
    sess = ClusterSession(Cluster(n_datanodes=4))
    sess.execute("create table kv (k bigint primary key, v bigint, "
                 "note varchar(16)) distribute by shard(k)")
    sess.execute("insert into kv values " + ", ".join(
        f"({i}, {i * 10}, 'n{i}')" for i in range(50)))
    return sess


class TestPrepared:
    def test_point_select_routes_to_one_node(self, s):
        s.execute("prepare getv (bigint) as "
                  "select v, note from kv where k = $1")
        assert s.query("execute getv (7)") == [(70, "n7")]
        # light-coordinator path: whole statement shipped to ONE datanode
        assert s.last_query_stats()["tier"] == "fqs"
        assert s.query("execute getv (33)") == [(330, "n33")]
        assert s.prepared["getv"].mode == "plan"
        assert s.prepared["getv"].router is not None

    def test_plan_cache_reuses_one_plan(self, s):
        s.execute("prepare g2 (bigint) as select v from kv where k = $1")
        before = s.plan_cache_hits
        for i in range(5):
            s.query(f"execute g2 ({i})")
        assert s.plan_cache_hits == before + 5

    def test_parameterized_generic_plan(self, s):
        s.execute("prepare agg1 (bigint) as "
                  "select count(*), sum(v) from kv where k > $1")
        assert s.query("execute agg1 (25)") == [(24, 9000)]
        assert s.query("execute agg1 (40)") == [(9, 4050)]
        # no single-node pin -> the distributed plan (mesh tier)
        assert s.last_query_stats()["tier"] == "mesh"

    def test_text_param_substitution_mode(self, s):
        # a TEXT param compared by = with a dictionary-coded column binds
        # to the column's code and pre-plans; any other place a TEXT
        # param stands (here a range) substitutes and replans
        s.execute("prepare byname (varchar(16)) as "
                  "select k from kv where note = $1 order by k")
        assert s.prepared["byname"].mode == "plan"
        assert s.query("execute byname ('n5')") == [(5,)]
        assert s.query("execute byname ('n41')") == [(41,)]
        assert s.query("execute byname ('no such note')") == []
        s.execute("prepare fromname (varchar(16)) as "
                  "select k from kv where note >= $1 and k < 3 order by k")
        assert s.prepared["fromname"].mode == "ast"
        assert s.query("execute fromname ('n1')") == [(1,), (2,)]

    def test_prepared_insert_and_arity_errors(self, s):
        s.execute("prepare pin (bigint, bigint, varchar(16)) as "
                  "insert into kv values ($1, $2, $3)")
        s.execute("execute pin (300, 3000, 'p300')")
        assert s.query("select v from kv where k = 300") == [(3000,)]
        with pytest.raises(ExecError):
            s.execute("execute nosuch (1)")
        with pytest.raises(ExecError):
            s.execute("execute getv (1, 2)")

    def test_deallocate(self, s):
        s.execute("prepare tmp (bigint) as select $1")
        # the bare-param projection may bind or not; deallocate must work
        s.execute("deallocate tmp")
        with pytest.raises(ExecError):
            s.execute("execute tmp (1)")

    def test_ddl_invalidates_cached_plan(self, s):
        s.execute("create table pz (a bigint primary key, b bigint) "
                  "distribute by shard(a)")
        s.execute("insert into pz values (1, 10)")
        s.execute("prepare pget (bigint) as select b from pz where a = $1")
        assert s.query("execute pget (1)") == [(10,)]
        gen = s.prepared["pget"].ddl_gen
        s.execute("drop table pz")
        s.execute("create table pz (a bigint primary key, b bigint, "
                  "c bigint) distribute by shard(a)")
        s.execute("insert into pz values (1, 77, 5)")
        # replanned against the new catalog, not the stale TableDef
        assert s.query("execute pget (1)") == [(77,)]
        assert s.prepared["pget"].ddl_gen != gen
        s.execute("drop table pz")


class TestUpsert:
    def test_do_nothing(self, s):
        r = s.execute("insert into kv values (7, 999, 'dup') "
                      "on conflict (k) do nothing")[-1]
        assert r.rowcount == 0
        assert s.query("select v from kv where k = 7") == [(70,)]

    def test_do_update_mixed_batch(self, s):
        r = s.execute(
            "insert into kv values (8, 888, 'u8'), (400, 4000, 'new') "
            "on conflict (k) do update set v = excluded.v, "
            "note = excluded.note")[-1]
        assert r.rowcount == 2
        assert s.query("select v, note from kv where k = 8") == \
            [(888, "u8")]
        assert s.query("select v, note from kv where k = 400") == \
            [(4000, "new")]

    def test_do_update_keeps_unassigned_columns(self, s):
        s.execute("insert into kv values (400, 5000, 'zzz') "
                  "on conflict (k) do update set v = excluded.v")
        assert s.query("select v, note from kv where k = 400") == \
            [(5000, "new")]

    def test_batch_duplicate_key_errors_for_update(self, s):
        with pytest.raises(ExecError, match="second time"):
            s.execute("insert into kv values (1, 1, 'a'), (1, 2, 'b') "
                      "on conflict (k) do update set v = excluded.v")

    def test_batch_duplicate_key_first_wins_for_nothing(self, s):
        s.execute("insert into kv values (500, 1, 'a'), (500, 2, 'b') "
                  "on conflict (k) do nothing")
        assert s.query("select v from kv where k = 500") == [(1,)]

    def test_rollback_undoes_upsert(self, s):
        before = s.query("select v from kv where k = 9")
        s.execute("begin")
        s.execute("insert into kv values (9, 1, 'rb') "
                  "on conflict (k) do update set v = excluded.v")
        assert s.query("select v from kv where k = 9") == [(1,)]
        s.execute("rollback")
        assert s.query("select v from kv where k = 9") == before

    def test_target_must_cover_dist_key(self, s):
        with pytest.raises(ExecError, match="distribution key"):
            s.execute("insert into kv values (1, 1, 'x') "
                      "on conflict (v) do nothing")

    def test_text_key_and_decimal_value(self, s):
        s.execute("create table dk (name varchar(8) primary key, "
                  "amt decimal(10,2)) distribute by shard(name)")
        s.execute("insert into dk values ('a', 1.25), ('b', 2.50)")
        s.execute("insert into dk values ('a', 9.75) "
                  "on conflict (name) do update set amt = excluded.amt")
        assert s.query("select amt from dk where name = 'a'") == [(9.75,)]
        s.execute("insert into dk values ('b', 0.01) "
                  "on conflict (name) do nothing")
        assert s.query("select amt from dk where name = 'b'") == [(2.5,)]
        s.execute("drop table dk")

    def test_duplicate_arbiter_match_refused_for_update(self, s):
        # two existing rows share g=7: DO UPDATE must refuse rather than
        # collapse them into one (silent data destruction)
        s.execute("create table du (a bigint primary key, g bigint) "
                  "distribute by shard(g)")
        s.execute("insert into du values (1, 7), (2, 7)")
        with pytest.raises(ExecError, match="unique"):
            s.execute("insert into du values (9, 7) "
                      "on conflict (g) do update set a = excluded.a")
        assert s.query("select count(*) from du") == [(2,)]
        s.execute("drop table du")

    def test_set_list_validated_before_any_delete(self, s):
        s.execute("create table vb (a bigint primary key, b bigint) "
                  "distribute by shard(a)")
        s.execute("insert into vb values (1, 10)")
        s.execute("begin")
        with pytest.raises(ExecError, match="unknown"):
            s.execute("insert into vb values (1, 20) "
                      "on conflict (a) do update set nosuch = 1")
        s.execute("commit")
        # the bad statement must not have deleted the existing row
        assert s.query("select b from vb where a = 1") == [(10,)]
        s.execute("drop table vb")

    def test_replicated_upsert_requires_explicit_target(self, s):
        s.execute("create table rx (a bigint primary key, b bigint) "
                  "distribute by replication")
        s.execute("insert into rx values (1, 1)")
        with pytest.raises(ExecError, match="target"):
            s.execute("insert into rx values (2, 2) "
                      "on conflict do nothing")
        # with an explicit target distinct rows insert normally
        s.execute("insert into rx values (2, 2), (3, 3) "
                  "on conflict (a) do nothing")
        assert s.query("select count(*) from rx") == [(3,)]
        s.execute("drop table rx")

    def test_replicated_table_upsert(self, s):
        s.execute("create table rdim (id bigint primary key, "
                  "label varchar(8)) distribute by replication")
        s.execute("insert into rdim values (1, 'one'), (2, 'two')")
        s.execute("insert into rdim values (1, 'ONE'), (3, 'three') "
                  "on conflict (id) do update set label = excluded.label")
        assert s.query("select label from rdim where id = 1 ") == \
            [("ONE",)]
        assert s.query("select label from rdim where id = 3") == \
            [("three",)]
        # every replica applied the same upsert
        for dn in s.cluster.datanodes:
            assert dn.stores["rdim"].row_count() >= 3
        s.execute("drop table rdim")


class TestAutoPrepare:
    """VERDICT r4 #6: unprepared point reads must ride the prepared
    machinery via literal lifting (exec/autoprep.py)."""

    def _mk(self):
        from opentenbase_tpu.exec.dist_session import ClusterSession
        from opentenbase_tpu.parallel.cluster import Cluster
        cl = Cluster(n_datanodes=3)
        s = ClusterSession(cl)
        s.execute("create table apv (k bigint primary key, v bigint, "
                  "d decimal(10,2), dt date) distribute by shard(k)")
        s.execute("insert into apv values "
                  + ",".join(f"({i},{i * 3},{i}.5,'1995-01-{1 + i % 28:02d}')"
                             for i in range(200)))
        return s

    def test_fresh_literals_share_plan(self):
        s = self._mk()
        assert s.query("select v from apv where k = 10") == [(30,)]
        h0 = s.plan_cache_hits
        assert s.query("select v from apv where k = 11") == [(33,)]
        assert s.query("select v from apv where k = 12") == [(36,)]
        assert s.plan_cache_hits >= h0 + 2     # autoprep, not replans
        from opentenbase_tpu.exec import plancache
        templates = [k for k in plancache.AUTOPREP._d
                     if k[0] == id(s.cluster)]
        assert len(templates) == 1             # one template

    def test_literal_kinds(self):
        s = self._mk()
        assert s.query("select count(*) from apv where d > 100.5") \
            == [(99,)]
        assert s.query("select count(*) from apv where d > 150.5") \
            == [(49,)]
        assert s.query("select count(*) from apv "
                       "where dt = '1995-01-05' and k < 100") == [(4,)]
        assert s.query("select count(*) from apv where k = -1") == [(0,)]

    def test_string_literals_stay_distinct(self):
        s = self._mk()
        s.execute("create table apn (k bigint primary key, nm text) "
                  "distribute by shard(k)")
        s.execute("insert into apn values (1,'a'),(2,'b'),(3,'a')")
        assert s.query("select count(*) from apn where nm = 'a' "
                       "and k > 0") == [(2,)]
        assert s.query("select count(*) from apn where nm = 'b' "
                       "and k > 0") == [(1,)]

    def test_ddl_invalidates(self):
        s = self._mk()
        assert s.query("select v from apv where k = 5") == [(15,)]
        s.execute("alter table apv add column z bigint")
        assert s.query("select v from apv where k = 5") == [(15,)]
        s.execute("update apv set v = 99 where k = 5")
        assert s.query("select v from apv where k = 5") == [(99,)]

    def test_in_list_not_lifted(self):
        s = self._mk()
        assert s.query("select count(*) from apv where k in (1,2,3)") \
            == [(3,)]
        assert s.query("select count(*) from apv where k in (4,5)") \
            == [(2,)]

    def test_subquery_literals_stay_baked(self):
        s = self._mk()
        assert s.query("select count(*) from apv where v > "
                       "(select min(v) + 30 from apv)") == [(189,)]
        assert s.query("select count(*) from apv where v > "
                       "(select min(v) + 60 from apv)") == [(179,)]

    def test_type_distinct_literals_do_not_share_plans(self):
        # `k = 10` (INT64) vs `k = 10.5` (DECIMAL) share a template but
        # must not share a plan — the int plan would truncate 10.5
        s = self._mk()
        assert s.query("select v from apv where k = 10") == [(30,)]
        assert s.query("select v from apv where k = 10.5") == []
        assert s.query("select count(*) from apv where d > 100.5") \
            == [(99,)]
        assert s.query("select count(*) from apv where d > 100.25") \
            == [(100,)]
        assert s.query("select count(*) from apv where d > 100.55") \
            == [(99,)]
