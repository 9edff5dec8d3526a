"""A statement that differs from one already run only in its literal values
runs that one's compiled programs (ISSUE 31, the cell `tpch_sf1_fresh`).

On the CPU at SF0.01, through the benchmark's own served stack (CnServer ->
ClusterSession -> autoprep -> planner -> MeshRunner) on one DataNode and on
four virtual ones: TPC-H Q1/Q3/Q5 with substitution parameters drawn from a
seed inside clauses 2.4.1.3/2.4.3.3/2.4.5.3's domains answer as the plain
reference does (`benchmarks/reference/`), and from the second statement of a
type on neither `plancache.stats()`'s programs nor jax's compile requests
grow.  Then the edges of the lift, each a case of its own, against the same
statement with `enable_autoprepare` off: the literal-baked path IS the plain
semantics of a literal."""

import os

import numpy as np
import pytest
from jax import monitoring

from benchmarks.lib import datagen, files, params as params_mod
from benchmarks.lib import stack as stack_mod
from benchmarks.lib.traffic import Mix, Request
from opentenbase_tpu.catalog import types as T
from opentenbase_tpu.exec import plancache
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.mesh_exec import _ALLOWED, mesh_runner_for
from opentenbase_tpu.obs import trace as obs_trace
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.plan.physical import plan_key
from opentenbase_tpu.sql.parser import parse_sql

SEED = 20260929
XLA_REQUESTS = [0]


def _on_event(event, **_kw):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        XLA_REQUESTS[0] += 1


monitoring.register_event_listener(_on_event)


def programs():
    return sum(r[3] for r in plancache.stats())


# ---------------------------------------------------------------------------
# Q1/Q3/Q5 x {1, 4} DataNodes, literal sets drawn from a seed
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=[1, 4], ids=["1dn", "4dn"])
def served(request, tmp_path_factory):
    ndn = request.param
    run_dir = str(tmp_path_factory.mktemp(f"fresh_{ndn}dn"))
    data = datagen.generate(sf=0.01, seed=SEED)
    stack = stack_mod.Stack(ndn, os.path.join(run_dir, "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data, (), run_dir)
        yield Mix("fresh", SEED, data), client, session
    finally:
        stack.stop()


@pytest.mark.parametrize("qname", ["q1_fresh", "q3_fresh", "q5_fresh"])
def test_fresh_literals_run_the_compiled_programs(served, qname):
    mix, client, session = served
    st = next(s for s in mix.statements if s.name == qname)
    rng = np.random.default_rng([SEED, mix.statements.index(st)])
    limits = files.load_json("lib", "limits.json")
    drawn, grew = [], []
    for n in range(6):
        p = params_mod.draw(st.domains, rng)
        before = programs(), XLA_REQUESTS[0]
        req = mix.run_request(Request(st, p), client, session)
        grew.append((programs() - before[0], XLA_REQUESTS[0] - before[1]))
        bad, _avg, ulps = mix.check(req, limits)
        assert bad == [] and ulps <= limits["decimal_ulp_gap"], bad
        stats = req.steps[0][5]
        assert stats["tier"] in mix.served_tiers
        assert stats["params_baked"] == 0 and stats["params_traced"] >= 1
        assert stats["retraces"] == 0 or n == 0
        drawn.append(tuple(sorted(p.items())))
    assert len(set(drawn)) >= 5, drawn       # at least five literal sets
    assert grew[0][0] >= 1                   # the type's first statement
    assert grew[1:] == [(0, 0)] * 5, grew    # every later one: nothing new
    assert session.fallbacks == []


def test_the_cells_set_up_statements_run(served):
    """q1_fresh's `setup_sql` asks, before any warm-up, for the step the
    configuration rests on: EXECUTE takes `date - interval 'n' day` as one
    DATE value.  A tree without it raises there ("arguments must be
    literals") and the benchmark's run ends with no result; here every
    set-up statement of the mix answers, and the probe with all of region."""
    mix, client, _session = served
    replies = [client.execute(sql)
               for st in mix.statements for sql in st.setup_statements()]
    assert [r[-1]["command"] for r in replies] == ["PREPARE", "SELECT"]
    assert replies[-1][-1]["rowcount"] == 5


# ---------------------------------------------------------------------------
# the edges, against the literal-baked path
# ---------------------------------------------------------------------------

ROWS = [
    # k, a, b, d          a and b hold the same strings under other codes
    (1, "x", "z", "1997-02-27"), (2, "y", "x", "1997-02-28"),
    (3, "z", "y", "1997-03-01"), (4, None, "x", "1995-02-28"),
    (5, "x", "x", "1995-03-01"), (6, "y", None, "1995-02-27"),
    (7, "x", "y", "1996-02-29"), (8, None, None, "1995-12-31"),
    (9, "z", "z", "1994-12-31"), (10, "y", "z", "1995-01-01"),
]


def sql_lit(v):
    return "null" if v is None else f"'{v}'"


@pytest.fixture(scope="module", params=[1, 4], ids=["1dn", "4dn"])
def edge(request):
    s = ClusterSession(Cluster(n_datanodes=request.param))
    s.execute("create table ft (k bigint primary key, a varchar(16), "
              "b varchar(16), d date) distribute by shard(k)")
    s.execute("insert into ft values " + ", ".join(
        f"({k}, {sql_lit(a)}, {sql_lit(b)}, date '{d}')"
        for k, a, b, d in ROWS))
    return s


def both_paths(s, sql):
    """The statement's rows and stats with the lift, and its rows with
    every literal baked (autoprepare off): (lifted, stats, baked)."""
    lifted = s.query(sql)
    stats = dict(s.last_query_stats(),
                 spans=obs_trace.last_trace().to_dict()["spans"])
    s.execute("set enable_autoprepare = off")
    try:
        baked = s.query(sql)
    finally:
        s.execute("set enable_autoprepare = on")
    return lifted, stats, baked


def keys(where, rows=ROWS):
    return [(r[0],) for r in rows if where(*r)]


def test_text_value_in_no_dictionary(edge):
    got, st, baked = both_paths(
        edge, "select k from ft where a = 'nowhere' order by k")
    assert got == baked == []
    assert (st["params_traced"], st["params_baked"]) == (1, 0)
    got, st, baked = both_paths(
        edge, "select k from ft where a <> 'nowhere' order by k")
    assert got == baked == keys(lambda k, a, b, d: a is not None)
    assert (st["params_traced"], st["params_baked"]) == (1, 0)
    # the tier that ran looked the code up and found none
    misses = [c["attrs"]["dict_miss"] for c in walk_spans(st["spans"])
              if c["name"] == "bind" and "dict_miss" in c.get("attrs", {})]
    assert misses == [1]


def walk_spans(d):
    yield d
    for c in d.get("children", ()):
        yield from walk_spans(c)


@pytest.mark.parametrize("op, want", [
    ("=", lambda k, a, b, d: a == "x"),
    ("<>", lambda k, a, b, d: a is not None and a != "x"),
])
def test_null_rows_under_eq_and_ne(edge, op, want):
    got, st, baked = both_paths(
        edge, f"select k from ft where a {op} 'x' order by k")
    assert got == baked == keys(want)
    assert st["params_baked"] == 0
    # NOT over the comparison keeps NULL rows out too (three values)
    got, _st, baked = both_paths(
        edge, f"select k from ft where not (a {op} 'x') order by k")
    assert got == baked == keys(
        lambda k, a, b, d: a is not None and not want(k, a, b, d))


@pytest.mark.parametrize("sql, want", [
    ("a = 'x' and b = 'x'", lambda k, a, b, d: a == "x" and b == "x"),
    ("a = 'x' or b = 'x'", lambda k, a, b, d: a == "x" or b == "x"),
    ("a = 'z' and b <> 'z'",
     lambda k, a, b, d: a == "z" and b is not None and b != "z"),
    ("'y' = a and 'x' = b", lambda k, a, b, d: a == "y" and b == "x"),
])
def test_one_literal_against_two_dictionaries(edge, sql, want):
    got, st, baked = both_paths(
        edge, f"select k from ft where {sql} order by k")
    assert got == baked == keys(want)
    assert (st["params_traced"], st["params_baked"]) == (2, 0)


def test_dictionary_grows_between_two_statements(edge):
    sql = "select k from ft where a = 'brand new' order by k"
    assert edge.query(sql) == []
    edge.execute("insert into ft values (77, 'brand new', 'x', "
                 "date '1999-01-01')")
    try:
        got, st, baked = both_paths(edge, sql)
        assert got == baked == [(77,)]
        assert st["params_baked"] == 0
        assert edge.query(
            "select k from ft where a <> 'brand new' and b = 'x' "
            "order by k") == [(2,), (5,)]
    finally:
        edge.execute("delete from ft where k = 77")


@pytest.mark.parametrize("expr, day", [
    ("date '1996-02-29' + interval '1' year", "1997-02-28"),     # leap day
    ("date '1996-02-29' - interval '1' year", "1995-02-28"),
    ("date '1995-01-31' + interval '1' month", "1995-02-28"),    # month end
    ("date '1996-01-31' + interval '1' month", "1996-02-29"),
    ("date '1995-03-31' - interval '1' month", "1995-02-28"),
    ("date '1995-03-01' + interval '-1' day", "1995-02-28"),     # negative
    ("date '1997-04-28' + interval '-2' month", "1997-02-28"),
    ("date '1998-12-01' - interval '90' day", "1998-09-02"),     # Q1's
    ("date '1994-01-01' + interval '1' year - interval '1' day",
     "1994-12-31"),
])
def test_date_valued_constant_expressions(edge, expr, day):
    assert T.days_to_date(T.add_interval(0, 0, "day")) == "1970-01-01"
    got, st, baked = both_paths(
        edge, f"select k from ft where d <= {expr} order by k")
    assert got == baked == keys(lambda k, a, b, d: d <= day)
    # the whole expression rode as ONE parameter, nothing stayed baked
    assert (st["params_traced"], st["params_baked"]) == (1, 0)
    got, _st, baked = both_paths(
        edge, f"select k from ft where d = {expr} order by k")
    assert got == baked == keys(lambda k, a, b, d: d == day)


def test_date_expressions_share_one_template(edge):
    edge.query("select k from ft where d < date '1995-06-01' "
               "- interval '3' month")
    before = programs(), XLA_REQUESTS[0]
    for lit, n, unit in (("1996-02-29", 1, "year"), ("1995-01-31", 7, "day"),
                         ("1997-03-31", 11, "month")):
        edge.query(f"select k from ft where d < date '{lit}' "
                   f"- interval '{n}' {unit}")
    assert (programs(), XLA_REQUESTS[0]) == before


@pytest.mark.parametrize("sql, want", [
    ("a in ('x', 'y')", lambda k, a, b, d: a in ("x", "y")),
    ("a like 'x%'", lambda k, a, b, d: a is not None and a.startswith("x")),
    ("a >= 'y'", lambda k, a, b, d: a is not None and a >= "y"),
    ("a = 'x' and b in ('x', 'z')",
     lambda k, a, b, d: a == "x" and b in ("x", "z")),
])
def test_literal_kinds_that_stay_baked(edge, sql, want):
    got, st, baked = both_paths(
        edge, f"select k from ft where {sql} order by k")
    assert got == baked == keys(want)
    assert st["params_baked"] >= 1


def test_stats_and_explain_report_the_binding(edge):
    edge.query("select k from ft where a = 'x' and d < date '1996-01-01' "
               "+ interval '1' year and k < 100")
    st = edge.last_query_stats()
    assert (st["params_traced"], st["params_baked"], st["retraces"]) == \
        (3, 0, 0)
    assert 0 < st["bind_ms"] < st["total_ms"]
    text = "\n".join(r[0] for r in edge.query(
        "explain analyze select k from ft where a = 'x' and k < 100"))
    # an instrumented run plans the statement as written
    assert "Bind: " in text and "traced=0 baked=2" in text, text


def test_no_key_holds_a_lifted_value(edge):
    """Two literals, one template, one plan key, one ladder entry, one
    program: `plan_key` (which `prog_key` and `_ladder_key` are built
    from) shows the parameter's name and no value."""
    sqls = [f"select a, count(*) from ft where b = '{v}' and d < date "
            f"'{d}' + interval '{n}' month group by a order by a"
            for v, d, n in (("x", "1996-01-31", 13), ("z", "1996-05-05", 2))]
    preps = [edge._autoprep_template(parse_sql(q)[0])[0] for q in sqls]
    assert preps[0] is preps[1] and preps[0].mode == "plan"
    pkeys = repr([plan_key(f.plan, _ALLOWED)
                  for f in preps[0].dp.fragments if f.location != "cn"])
    assert "__bindparam1" in pkeys and "__bindparam2" in pkeys
    for value in ("'x'", "'z'", str(T.date_to_days("1997-02-28")),
                  str(T.date_to_days("1996-07-05"))):
        assert value not in pkeys, (value, pkeys)
    runner = mesh_runner_for(edge.cluster)
    edge.query(sqls[0])
    assert edge.last_query_stats()["tier"] == "mesh"
    before = len(runner._ladder), len(runner._programs), programs(), \
        XLA_REQUESTS[0]
    # b = 'z' and d < 1996-07-05: rows 9 (a = 'z') and 10 (a = 'y')
    assert edge.query(sqls[1]) == [("y", 1), ("z", 1)]
    assert (len(runner._ladder), len(runner._programs), programs(),
            XLA_REQUESTS[0]) == before


def test_a_string_against_a_date_column_is_a_date_parameter(edge):
    got, st, baked = both_paths(
        edge, "select k from ft where d = '1995-03-01' and k < 100")
    assert got == baked == [(5,)]
    assert (st["params_traced"], st["params_baked"]) == (2, 0)
    got, _st, baked = both_paths(
        edge, "select k from ft where d <> '1995-03-01' and k < 3 order by k")
    assert got == baked == [(1,), (2,)]


# ---------------------------------------------------------------------------
# a lifted string above a join, and one `$n` against two columns: the code
# belongs to the dictionary of the batch that is filtered, per column
# ---------------------------------------------------------------------------

# fu.c holds ft's strings in another insertion order (other codes than
# ft.a's and ft.b's).  Joined on fu.k the tables are collocated; joined on
# fu.j the rows of fu move between DataNodes, and a batch that arrives
# from an exchange has its dictionary re-encoded from the rows that moved:
# no stored code fits it
FU_ROWS = [(9, 1, "zed"), (3, 2, "x"), (7, 3, "m"), (1, 4, "y"),
           (5, 5, "x"), (2, 6, None), (12, 7, "x"), (10, 8, "a"),
           (11, 9, "y"), (13, 10, "q")]


@pytest.fixture(scope="module")
def joined(edge):
    edge.execute("create table fu (k bigint primary key, j bigint, "
                 "c varchar(16)) distribute by shard(k)")
    edge.execute("insert into fu values " + ", ".join(
        f"({k}, {j}, {sql_lit(c)})" for k, j, c in FU_ROWS))
    yield edge
    edge.execute("drop table fu")


def fu_by(key):
    """ft.k -> the c values of the fu rows that join it on `key`."""
    out = {}
    for k, j, c in FU_ROWS:
        out.setdefault(k if key == "k" else j, []).append(c)
    return out


@pytest.mark.parametrize("mesh", ["on", "off"])
@pytest.mark.parametrize("key", ["k", "j"])
@pytest.mark.parametrize("sql, want", [
    # a WHERE qual on the nullable side: a Filter above the outer join
    ("select ft.k from ft left join fu on ft.k = fu.{key} "
     "where fu.c = 'x' order by ft.k",
     lambda cs, k, a, b, d: "x" in cs),
    ("select ft.k from ft left join fu on ft.k = fu.{key} "
     "where fu.c <> 'x' order by ft.k",
     lambda cs, k, a, b, d: any(c is not None and c != "x" for c in cs)),
    # a cross-table OR: the join's residual
    ("select ft.k from ft join fu on ft.k = fu.{key} "
     "where ft.a = 'x' or fu.c = 'x' order by ft.k",
     lambda cs, k, a, b, d: bool(cs) and (a == "x" or "x" in cs)),
    ("select ft.k from ft join fu on ft.k = fu.{key} "
     "where ft.b = 'y' or fu.c = 'y' order by ft.k",
     lambda cs, k, a, b, d: bool(cs) and (b == "y" or "y" in cs)),
    # a string neither dictionary holds, under <>
    ("select ft.k from ft left join fu on ft.k = fu.{key} "
     "where fu.c <> 'nowhere' and ft.a <> 'nowhere' order by ft.k",
     lambda cs, k, a, b, d: any(c is not None for c in cs)
     and a is not None),
    # the qual above the join, below an aggregate
    ("select count(*) from ft left join fu on ft.k = fu.{key} "
     "where fu.c = 'y'", None),
], ids=["outer_eq", "outer_ne", "or_a", "or_b", "nowhere", "under_agg"])
def test_a_lifted_string_above_a_join(joined, mesh, key, sql, want):
    joined.execute(f"set enable_mesh_exchange = {mesh}")
    try:
        got, st, baked = both_paths(joined, sql.format(key=key))
    finally:
        joined.execute("set enable_mesh_exchange = on")
    by = fu_by(key)
    if want is None:
        assert got == baked == [(sum(
            cs.count("y") for k, cs in by.items() if k <= 10),)]
    else:
        assert got == baked == keys(
            lambda k, a, b, d: want(by.get(k, []), k, a, b, d))
    assert st["params_baked"] == 0 and st["params_traced"] >= 1


@pytest.mark.parametrize("mesh", ["on", "off"])
def test_one_parameter_against_two_columns(edge, mesh):
    """`$1` set against a and against b is two codes of two
    dictionaries, in every tier."""
    edge.execute(f"set enable_mesh_exchange = {mesh}")
    try:
        edge.execute("prepare ab (varchar(16)) as select k from ft "
                     "where a = $1 or b = $1 order by k")
        assert edge.prepared["ab"].mode == "plan"
        for v in ("x", "y", "z", "nowhere"):
            assert edge.query(f"execute ab ('{v}')") == keys(
                lambda k, a, b, d: a == v or b == v), v
        edge.execute("prepare anb (varchar(16)) as select k from ft "
                     "where a = $1 and b <> $1 order by k")
        for v in ("x", "y", "z"):
            assert edge.query(f"execute anb ('{v}')") == keys(
                lambda k, a, b, d: a == v and b is not None and b != v), v
        # one string read as a day number AND as a code: substituted
        edge.execute("prepare ad (varchar(16)) as select k from ft "
                     "where d = $1 or a = $1 order by k")
        assert edge.prepared["ad"].mode == "ast"
        assert edge.query("execute ad ('1995-03-01')") == [(5,)]
    finally:
        edge.execute("set enable_mesh_exchange = on")
        for name in ("ab", "anb", "ad"):
            edge.execute(f"deallocate {name}")


@pytest.mark.parametrize("sql, want", [
    # a view's, a CTE's and a derived table's column: no dictionary-coded
    # base column to bind to
    ("select k from fv where a = 'x' and k < {n} order by k",
     lambda n: lambda k, a, b, d: a == "x" and k < n),
    ("with c as (select k, a from ft) select k from c "
     "where a = 'y' and k < {n} order by k",
     lambda n: lambda k, a, b, d: a == "y" and k < n),
    ("select k from (select k, b from ft) s "
     "where b <> 'x' and k < {n} order by k",
     lambda n: lambda k, a, b, d: b is not None and b != "x" and k < n),
], ids=["view", "cte", "derived"])
def test_a_string_that_cannot_bind_keeps_the_other_parameters(edge, sql,
                                                              want):
    edge.execute("create view fv as select k, a from ft")
    try:
        edge.query(sql.format(n=4))
        before = programs(), XLA_REQUESTS[0], edge.plan_cache_hits
        for n in (6, 9, 11):
            assert edge.query(sql.format(n=n)) == keys(want(n))
            # the string stays in the template, the number rides
            st = edge.last_query_stats()
            assert (st["params_traced"], st["params_baked"]) == (1, 1)
        assert (programs(), XLA_REQUESTS[0]) == before[:2]
        assert edge.plan_cache_hits == before[2] + 3
        got, _st, baked = both_paths(edge, sql.format(n=8))
        assert got == baked == keys(want(8))
    finally:
        edge.execute("drop view fv")


def test_a_number_compared_with_a_quoted_value(edge):
    # no dictionary behind k: the string stays baked and means what a
    # plain bind makes of it, the date still rides
    got, st, baked = both_paths(
        edge, "select a from ft where k = '5' and d < date '1996-01-01'")
    assert got == baked
    assert (st["params_traced"], st["params_baked"]) == (1, 1)
