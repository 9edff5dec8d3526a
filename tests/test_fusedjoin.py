"""Fused join fragments (exec/fused.py): a Q3-shaped multi-scan join
statement compiles to ONE FUSED-tier XLA program with zero per-join
host syncs, literal-masked reuse survives changed constants, and the
join-size ladder retraces overflow one step up without wrong results."""

import numpy as np
import pytest

from opentenbase_tpu.exec import executor as X
from opentenbase_tpu.exec import fused, plancache
from opentenbase_tpu.exec.session import LocalNode, Session


@pytest.fixture(autouse=True)
def _fuse_small(monkeypatch):
    """These fixtures are tiny by design: lift the row floor that keeps
    real small joins on the eager path."""
    monkeypatch.setenv("OTB_FUSE_JOIN_MIN_ROWS", "0")


def _q3_sess(n_cust=30, n_orders=120, n_items=360):
    """A miniature Q3 world: customer / orders / lineitem."""
    rng = np.random.default_rng(7)
    node = LocalNode()
    s = Session(node)
    s.execute("create table customer (c_custkey bigint, "
              "c_mktsegment text)")
    s.execute("create table orders (o_orderkey bigint, "
              "o_custkey bigint, o_orderdate bigint, "
              "o_shippriority bigint)")
    s.execute("create table lineitem (l_orderkey bigint, "
              "l_extendedprice bigint, l_shipdate bigint)")
    segs = ["BUILDING", "MACHINERY", "AUTOMOBILE"]
    s._insert_rows(node.catalog.table("customer"),
                   node.stores["customer"],
                   {"c_custkey": np.arange(n_cust),
                    "c_mktsegment": [segs[i % 3]
                                     for i in range(n_cust)]}, n_cust)
    s._insert_rows(node.catalog.table("orders"),
                   node.stores["orders"],
                   {"o_orderkey": np.arange(n_orders),
                    "o_custkey": rng.integers(0, n_cust, n_orders),
                    "o_orderdate": rng.integers(0, 1000, n_orders),
                    "o_shippriority": rng.integers(0, 2, n_orders)},
                   n_orders)
    s._insert_rows(node.catalog.table("lineitem"),
                   node.stores["lineitem"],
                   {"l_orderkey": rng.integers(0, n_orders, n_items),
                    "l_extendedprice": rng.integers(1, 5000, n_items),
                    "l_shipdate": rng.integers(0, 1000, n_items)},
                   n_items)
    return s


Q3ISH = ("select lineitem.l_orderkey, "
         "sum(lineitem.l_extendedprice) as revenue, "
         "orders.o_orderdate, orders.o_shippriority "
         "from customer, orders, lineitem "
         "where customer.c_mktsegment = 'BUILDING' "
         "and customer.c_custkey = orders.o_custkey "
         "and lineitem.l_orderkey = orders.o_orderkey "
         "and orders.o_orderdate < {d} and lineitem.l_shipdate > {d} "
         "group by lineitem.l_orderkey, orders.o_orderdate, "
         "orders.o_shippriority "
         "order by revenue desc, orders.o_orderdate limit 10")


class TestFusedJoinFragment:
    def test_q3_shape_is_one_fused_program_no_join_syncs(self):
        s = _q3_sess()
        q = Q3ISH.format(d=500)
        # eager baseline (fusion bypassed) for correctness
        real = fused.try_fused
        fused.try_fused = lambda *_a, **_k: None
        try:
            want = s.query(q)
        finally:
            fused.try_fused = real
        m0, h0 = plancache.FUSED.misses, plancache.FUSED.hits
        x0 = X.exec_stats_snapshot()
        got = s.query(q)
        assert got == want
        x1 = X.exec_stats_snapshot()
        # the whole 2-join fragment compiled as ONE program...
        assert plancache.FUSED.misses > m0
        # ...with ZERO per-join device->host size syncs
        assert x1["host_syncs"] == x0["host_syncs"]
        # warm repeat: FUSED-tier hit, still no syncs, and the
        # join-program hit counter advances
        j0 = X.EXEC_STATS["fused"]["fused_join_hits"]
        got2 = s.query(q)
        assert got2 == want
        assert plancache.FUSED.hits > h0
        assert X.exec_stats_snapshot()["host_syncs"] == x0["host_syncs"]
        assert X.EXEC_STATS["fused"]["fused_join_hits"] > j0

    def test_literal_masked_reuse_across_constants(self):
        s = _q3_sess()
        s.query(Q3ISH.format(d=400))          # compile once
        c0 = plancache.FUSED.compiles
        h0 = plancache.FUSED.hits
        got = s.query(Q3ISH.format(d=700))    # same shape, new constant
        assert plancache.FUSED.compiles == c0, \
            "a literal change must not recompile the fused join program"
        assert plancache.FUSED.hits > h0
        # cross-check the reused program against the eager path
        real = fused.try_fused
        fused.try_fused = lambda *_a, **_k: None
        try:
            want = s.query(Q3ISH.format(d=700))
        finally:
            fused.try_fused = real
        assert got == want

    def test_ladder_overflow_retraces_without_wrong_results(self):
        """An expanding join (every probe row matches every build row)
        overflows the quarter-size starting class; the ladder must walk
        factors up and the final answer must be exact."""
        node = LocalNode()
        s = Session(node)
        s.execute("create table pa (k bigint, v bigint)")
        s.execute("create table pb (k bigint, w bigint)")
        n = 200
        s._insert_rows(node.catalog.table("pa"), node.stores["pa"],
                       {"k": np.ones(n, np.int64),
                        "v": np.arange(n)}, n)
        s._insert_rows(node.catalog.table("pb"), node.stores["pb"],
                       {"k": np.ones(n, np.int64),
                        "w": np.arange(n)}, n)
        lad0 = fused._LADDER.snapshot()
        rows = s.query("select count(*) as c from pa, pb "
                       "where pa.k = pb.k")
        assert rows == [(n * n,)]
        learned = [v for k, (v,) in fused._LADDER.snapshot().items()
                   if k not in lad0]
        assert learned and any(f > 1 for d in learned
                               for f in d.values()), \
            "overflow must have walked the join ladder up"
        # steady state: the learned factor serves the next statement
        # with zero additional compiles of the overflow walk
        c0 = plancache.FUSED.compiles + plancache.FUSED.misses
        assert s.query("select count(*) as c from pa, pb "
                       "where pa.k = pb.k") == [(n * n,)]
        assert plancache.FUSED.compiles + plancache.FUSED.misses == c0

    def test_self_join_shares_staging(self):
        node = LocalNode()
        s = Session(node)
        s.execute("create table sj (k bigint, v bigint)")
        s._insert_rows(node.catalog.table("sj"), node.stores["sj"],
                       {"k": np.arange(20) % 5,
                        "v": np.arange(20)}, 20)
        got = s.query("select a.v, b.v from sj a, sj b "
                      "where a.k = b.k and a.v < b.v "
                      "order by a.v, b.v")
        real = fused.try_fused
        fused.try_fused = lambda *_a, **_k: None
        try:
            want = s.query("select a.v, b.v from sj a, sj b "
                           "where a.k = b.k and a.v < b.v "
                           "order by a.v, b.v")
        finally:
            fused.try_fused = real
        assert got == want


class TestMaskRefusedFifo:
    def test_bounded_fifo_eviction_not_wholesale_clear(self):
        saved = dict(fused._MASK_REFUSED)
        fused._MASK_REFUSED.clear()
        try:
            for i in range(fused._MASK_REFUSED_MAX + 90):
                fused._mask_refused_add(("k", i))
            assert len(fused._MASK_REFUSED) == fused._MASK_REFUSED_MAX
            # newest retained, oldest evicted one-at-a-time (FIFO) —
            # a wholesale clear() would have dropped everything
            assert ("k", fused._MASK_REFUSED_MAX + 89) \
                in fused._MASK_REFUSED
            assert ("k", 90) in fused._MASK_REFUSED
            assert ("k", 89) not in fused._MASK_REFUSED
        finally:
            fused._MASK_REFUSED.clear()
            fused._MASK_REFUSED.update(saved)
