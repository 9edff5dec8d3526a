"""The output class of a traced sorted aggregate (ISSUE 35,
`Executor._agg_class`): the keys' host-known spans where they bound the
groups below the rows, else a rung of the size-class ladder traced joins
ride — never the input's padded rows for want of a count.

The helper's arithmetic first (no program runs), then an aggregate whose
groups pass the first rung, through the fused tier (ONE retrace: the
program reports its groups exactly) and the mesh tier (the overflow bit:
at most two), its answer the eager tier's and the learned factor reused;
then DISTINCT aggregates, whose passes share one class."""

import numpy as np
import pytest

import jax.numpy as jnp

from opentenbase_tpu.exec import fused, plancache
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import DBatch, ExecContext, Executor
from opentenbase_tpu.exec.mesh_exec import mesh_runner_for
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.parallel.cluster import Cluster

LINEITEM_SF1 = 6_291_456    # lineitem's padded rows at SF1


def traced_executor(factors=None):
    ex = Executor(ExecContext({}, 0, 0, None, join_factors=factors),
                  frag_tag="f")
    ex._traced = True
    return ex


def batch(padded, live=None):
    valid = jnp.arange(padded) < (padded if live is None else live)
    return DBatch({}, valid, {}, {})


# ---------------------------------------------------------------------------
# the helper
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spans, padded, want", [
    ((199_999,), LINEITEM_SF1, 229_376),        # Q17: 200,000 part keys
    ((9, 1), 65_536, 256),          # a null indicator's span is 1: 20 slots
    ((), 4_096, 256),               # DISTINCT without GROUP BY: one group
    ((2_047, 2), 65_536, 6_144),    # a product of spans, a quarter-step class
])
def test_proven_spans_bound_the_class_and_ride_no_ladder(spans, padded,
                                                         want):
    ex = traced_executor()
    assert ex._agg_class(batch(padded), spans) == (want, None)
    # no id taken from the joins' sequence, nothing for the runner to read
    assert ex._join_seq == 0 and ex.join_required == []


@pytest.mark.parametrize("spans", [None, (None,), (199_999, None),
                                   (5_999_999,)],
                         ids=["none", "unknown", "one-unknown",
                              "bound-not-below-the-rows"])
def test_unknown_spans_start_at_a_quarter_of_the_rows(spans):
    """Q18's inner aggregate: its key's span (6,000,000 order keys) is no
    bound below the rows, so 1,572,864 slots for 1,500,000 groups."""
    ex = traced_executor()
    cls, jid = ex._agg_class(batch(LINEITEM_SF1), spans)
    assert (cls, jid) == (LINEITEM_SF1 // 4, ("f", 0))
    # the next laddered operator of the fragment takes the next id
    assert ex._agg_class(batch(1024))[1] == ("f", 1)


@pytest.mark.parametrize("factor, padded, want", [
    (1, 4_096, 1_024), (2, 4_096, 2_048), (4, 4_096, 4_096),
    (8, 4_096, 4_096),              # groups never exceed rows: capped
    (1, 128, 64), (1, 32, 32),      # the ladder's floor, under the cap
])
def test_the_learned_factor_climbs_to_the_rows_and_no_further(factor, padded,
                                                              want):
    ex = traced_executor({("f", 0): factor})
    assert ex._agg_class(batch(padded)) == (want, ("f", 0))


def test_the_eager_tier_counts_its_rows():
    ex = Executor(ExecContext({}, 0, 0, None))
    assert ex._agg_class(batch(4_096, live=700), (9,)) == (1_024, None)


def test_the_groups_found_are_reported_beside_the_joins_totals():
    """`(id, n_groups, class)` in `join_required`; the shape counters hold
    the largest input and the largest output class; a proven class
    reports nothing; passes that share an id report once."""
    ex = traced_executor()
    keys = (jnp.arange(1_024, dtype=jnp.int64) % 300,)
    valid = jnp.ones(1_024, bool)
    ones = (valid.astype(jnp.int64),)
    cls, jid = ex._agg_class(batch(1_024))
    for _ in range(2):
        _k, (counts,), ng = ex._sorted_agg(keys, valid, ones, cls, ("sum",),
                                           jid=jid)
    (entry,) = ex.join_required
    assert (entry[0], int(entry[1]), entry[2]) == (("f", 0), 300, 256)
    assert int(ng) == 300           # past the class: the runner replays
    cls, jid = ex._agg_class(batch(1_024), (299,))
    _k, (counts,), ng = ex._sorted_agg(keys, valid, ones, cls, ("sum",),
                                       (299,), jid)
    assert (cls, jid, len(ex.join_required)) == (320, None, 1)
    assert int(ng) == 300 and int(counts[:300].min()) == 3
    assert {k: v for k, v in ex.shape.items() if v} == {
        "sorted_aggs": 3, "sorted_agg_lanes": 1_024,
        "sorted_agg_groups": 320}


# ---------------------------------------------------------------------------
# an aggregate whose groups pass the first rung
# ---------------------------------------------------------------------------

ROWS = 3_000
# the key is computed (`k + 0`), so nothing is known of its span
MANY_GROUPS = ("select kk, sum(v) as sv, count(*) as c from "
               "(select k + 0 as kk, v from t where v >= {v}) x "
               "group by kk order by kk")


def table_rows():
    rng = np.random.default_rng(35)
    return {"k": np.arange(ROWS), "g": rng.integers(0, 40, ROWS),
            "v": rng.integers(0, 100, ROWS), "w": rng.integers(0, 7, ROWS)}


def expected_many_groups(cols, v):
    keep = cols["v"] >= v
    return [(int(k), int(x), 1) for k, x in zip(cols["k"][keep],
                                                cols["v"][keep])]


def test_fused_tier_answers_after_one_retrace_and_remembers(monkeypatch):
    node = LocalNode()
    s = Session(node)
    s.execute("create table t (k bigint, g bigint, v bigint, w bigint)")
    cols = table_rows()
    s._insert_rows(node.catalog.table("t"), node.stores["t"], cols, ROWS)
    monkeypatch.setattr(fused, "try_fused", lambda *_a, **_k: None)
    eager = s.query(MANY_GROUPS.format(v=0))
    monkeypatch.undo()
    assert eager == expected_many_groups(cols, 0)

    ladder0 = fused._LADDER.snapshot()
    misses = plancache.FUSED.misses
    assert s.query(MANY_GROUPS.format(v=0)) == eager
    st = s.last_query_stats()
    # 3,000 groups of 3,072 or 4,096 padded rows: the first rung is a
    # quarter; the program reports 3,000 and the runner jumps to the
    # class that holds them, once
    assert st["retraces"] == 1 and st["sorted_aggs"] == 1
    assert plancache.FUSED.misses - misses == 2
    assert st["sorted_agg_groups"] == st["sorted_agg_lanes"] >= ROWS
    ((learned,),) = [f for k, f in fused._LADDER.snapshot().items()
                     if k not in ladder0]
    assert learned == {("__fused", 0): 4}
    # another literal, the same programs: no second retrace
    misses = plancache.FUSED.misses
    assert s.query(MANY_GROUPS.format(v=3)) == expected_many_groups(cols, 3)
    assert s.last_query_stats()["retraces"] == 0
    assert plancache.FUSED.misses == misses


@pytest.fixture(scope="module", params=[1, 4], ids=["1dn", "4dn"])
def cluster_session(request):
    s = ClusterSession(Cluster(n_datanodes=request.param))
    s.execute("create table t (k bigint primary key, g bigint, v bigint, "
              "w bigint) distribute by shard(k)")
    cols = table_rows()
    for i in range(0, ROWS, 500):
        s.execute("insert into t values " + ", ".join(
            f"({k}, {g}, {v}, {w})" for k, g, v, w in zip(
                *(cols[c][i:i + 500].tolist() for c in "kgvw"))))
    return request.param, s, cols


def mesh_and_host(s, sql):
    """The statement's rows and stats from the mesh tier, and its rows
    from the host tier (every operator eager)."""
    got, st = s.query(sql), s.last_query_stats()
    assert st["tier"] == "mesh" and st["fallback"] == "", st
    s.execute("set enable_mesh_exchange = off")
    try:
        host = s.query(sql)
        assert s.last_query_stats()["tier"] == "host"
    finally:
        s.execute("set enable_mesh_exchange = on")
    return got, st, host


def test_mesh_tier_answers_after_at_most_two_retraces_and_remembers(
        cluster_session):
    ndn, s, cols = cluster_session
    sql = MANY_GROUPS.format(v=0)
    got, st, host = mesh_and_host(s, sql)
    assert got == host == expected_many_groups(cols, 0)
    assert st["sorted_agg_groups"] <= st["sorted_agg_lanes"]
    if ndn == 1:
        # nothing else of this program overflows: the aggregate's bit
        # doubles its factor twice, a quarter of the rows to all of them
        assert st["retraces"] == 2
        factors = [f for f, _m, _g in mesh_runner_for(s.cluster)
                   ._ladder.snapshot().values() if f]
        assert {(0, 0): 4} in factors
    else:
        assert 1 <= st["retraces"] <= 4     # the exchange's classes too
    # the learned classes serve the next call: no second walk
    assert s.query(sql) == got
    assert s.last_query_stats()["retraces"] == 0


DISTINCTS = [
    # group keys of known span (a class proven from it), DISTINCT and
    # plain aggregates mixed: every pass one class
    "select g, count(distinct w) as c, sum(distinct v) as sd, sum(v) as "
    "sv, min(distinct v) as mn from t group by g order by g",
    # a computed key: the dedupe pass and the groups ride the ladder
    "select vv, count(distinct k) as c, avg(distinct g) as a from "
    "(select v + 0 as vv, k, g from t) x group by vv order by vv",
    # more (group, value) pairs than a quarter of the rows: the dedupe
    # pass climbs, the 40 groups do not
    "select g, count(distinct k) as c from t group by g order by g",
]


@pytest.mark.parametrize("sql", DISTINCTS,
                         ids=["mixed", "computed-key", "dedupe-climbs"])
def test_distinct_aggregates_answer_as_the_eager_tier(cluster_session, sql):
    _ndn, s, _cols = cluster_session
    got, st, host = mesh_and_host(s, sql)
    assert got == host and len(got) in (40, 100)
    assert 0 < st["sorted_agg_groups"] <= st["sorted_agg_lanes"]
    assert s.query(sql) == got
    assert s.last_query_stats()["retraces"] == 0
