"""Serving tier (exec/scheduler.py): same-signature coalescing returns
bit-identical results to serial execution, mixed batches split across
signatures, admission sheds at queue depth and at the shed deadline
without leaking GTM slots, a statement's staging time never leaks across
sessions, and the otb_scheduler view surfaces the counters."""

import threading
import time

import pytest

from opentenbase_tpu.exec import scheduler as sm
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.gtm.server import GtmCore


@pytest.fixture(autouse=True)
def _fresh_stats():
    sm.reset_stats()
    yield
    sm.reset_stats()


def _mk_node():
    node = LocalNode()
    s = Session(node)
    s.execute("create table t (a bigint, b double precision, g bigint)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i * 0.5}, {i % 3})" for i in range(200)))
    s.execute("create table kv (k bigint, v bigint)")
    s.execute("insert into kv values " + ", ".join(
        f"({i}, {i * 7})" for i in range(50)))
    return node, s


AGG_Q = ("select g, sum(b) as sb, count(*) as c from t where a < {} "
         "group by g order by g")


def _run_concurrent(sched, node, sqls):
    """Submit every statement from its own client thread (each with its
    own Session) and return the row lists in submit order."""
    res = [None] * len(sqls)
    errs = [None] * len(sqls)

    def go(i):
        try:
            res[i] = sched.run(Session(node), sqls[i])[-1].rows
        except Exception as e:   # noqa: BLE001 — re-raised below
            errs[i] = e

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(sqls))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for e in errs:
        if e is not None:
            raise e
    return res


class TestBatchedCorrectness:
    """N same-shape queries with different literals coalesced into one
    program must return BIT-identical results to N serial runs."""

    def test_agg_sort_shape_bit_identical(self):
        node, _ = _mk_node()
        sqls = [AGG_Q.format(n) for n in (50, 80, 120, 199)]
        ref = [Session(node).execute(q)[-1].rows for q in sqls]
        with sm.Scheduler(node=node, window_ms=150.0) as sched:
            got = _run_concurrent(sched, node, sqls)
        assert got == ref
        st = sm.stats_snapshot()
        assert st["batched"] >= 2
        assert st["batch_dispatches"] >= 1
        assert any(k > 1 for k in st["hist"])

    def test_point_shape_bit_identical(self):
        node, _ = _mk_node()
        sqls = [f"select v from kv where k = {i}" for i in (3, 11, 29, 42)]
        ref = [Session(node).execute(q)[-1].rows for q in sqls]
        with sm.Scheduler(node=node, window_ms=150.0) as sched:
            got = _run_concurrent(sched, node, sqls)
        assert got == ref
        assert sm.stats_snapshot()["batched"] >= 2

    def test_join_shape_bit_identical(self, monkeypatch):
        monkeypatch.setenv("OTB_FUSE_JOIN_MIN_ROWS", "0")
        node = LocalNode()
        s = Session(node)
        s.execute("create table c (ck bigint, seg text)")
        s.execute("create table o (ok bigint, ck bigint, "
                  "price double precision)")
        segs = ["A", "B", "C"]
        s.execute("insert into c values " + ", ".join(
            f"({i}, '{segs[i % 3]}')" for i in range(30)))
        s.execute("insert into o values " + ", ".join(
            f"({i}, {i % 30}, {i * 1.5})" for i in range(120)))
        q = ("select seg, count(*) as n, sum(price) as sp "
             "from c, o where c.ck = o.ck and ok < {} "
             "group by seg order by seg")
        sqls = [q.format(n) for n in (40, 70, 100, 119)]
        ref = [Session(node).execute(x)[-1].rows for x in sqls]
        with sm.Scheduler(node=node, window_ms=200.0) as sched:
            got = _run_concurrent(sched, node, sqls)
        assert got == ref
        assert sm.stats_snapshot()["batched"] >= 2

    def test_mixed_batch_splits_by_signature(self):
        """Interleaved point + agg queries: two distinct signatures
        must land in (at least) two separate dispatches, each query
        still bit-identical to serial."""
        node, _ = _mk_node()
        sqls = []
        for i, n in enumerate((50, 80, 120, 199)):
            sqls.append(AGG_Q.format(n))
            sqls.append(f"select v from kv where k = {i * 9 + 1}")
        ref = [Session(node).execute(q)[-1].rows for q in sqls]
        with sm.Scheduler(node=node, window_ms=150.0) as sched:
            got = _run_concurrent(sched, node, sqls)
        assert got == ref
        st = sm.stats_snapshot()
        # one dispatch cannot serve two signatures: >= 2 dispatches,
        # and coalescing still happened within each signature
        assert st["dispatches"] >= 2
        assert st["batched"] >= 2

    def test_serial_lane_still_works(self):
        """Non-batchable statements (DML, SHOW, multi-statement) ride
        the serial worker pool under the same scheduler."""
        node, _ = _mk_node()
        with sm.Scheduler(node=node, window_ms=50.0) as sched:
            s = Session(node)
            sched.run(s, "insert into kv values (990, 6930)")
            rows = sched.run(s, "select v from kv where k = 990")[-1].rows
        assert rows == [(6930,)]


class TestAdmissionAndShed:
    def test_queue_depth_shed(self):
        """With the dispatcher parked in a long coalescing window, the
        per-group queue fills and the next submit is shed at once."""
        node, _ = _mk_node()
        sched = sm.Scheduler(node=node, window_ms=1500.0, queue_depth=3)
        try:
            items = [sched.submit(Session(node), AGG_Q.format(50))]
            time.sleep(0.1)   # dispatcher takes the head, opens window
            items.append(sched.submit(Session(node), "show all"))
            items.append(sched.submit(Session(node), "show all"))
            with pytest.raises(ExecError, match="queue is full"):
                sched.submit(Session(node), "show all")
            for it in items:
                sched.wait(it)
        finally:
            sched.stop()
        assert sm.stats_snapshot()["shed"] == 1

    def test_shed_timeout_releases_no_lease(self):
        """A query that times out waiting for a slot holds nothing: the
        external owner's slot is the only one left, and once it frees,
        the next query admits and releases cleanly (drains to zero)."""
        node, _ = _mk_node()
        gtm = GtmCore()
        assert gtm.resq_acquire("default", 1, owner="hog", lease_s=60)
        sched = sm.Scheduler(node=node, gtm=gtm, slots=1,
                             shed_timeout_ms=150.0)
        try:
            with pytest.raises(ExecError, match="queue wait timeout"):
                sched.run(Session(node), "select v from kv where k = 1")
            assert gtm.resq_counts()["default"] == 1   # hog only
            gtm.resq_release("default", owner="hog")
            rows = sched.run(Session(node),
                             "select v from kv where k = 1")[-1].rows
            assert rows == [(7,)]
            assert gtm.resq_counts()["default"] == 0   # lease released
        finally:
            sched.stop()
        assert sm.stats_snapshot()["shed"] == 1


class TestStatsAndView:
    def test_stats_rows_shape(self):
        node, _ = _mk_node()
        with sm.Scheduler(node=node, window_ms=100.0) as sched:
            _run_concurrent(sched, node,
                            [AGG_Q.format(n) for n in (50, 80)])
        rows = sm.stats_rows()
        assert len(rows) == 1
        (admitted, queued, batched, shed, dispatches, batch_dispatches,
         p50, p99, hist) = rows[0]
        assert admitted == 2 and shed == 0 and queued == 0
        assert dispatches >= 1
        assert isinstance(p50, float) and isinstance(p99, float)
        assert isinstance(hist, str)

    def test_otb_scheduler_view(self):
        from opentenbase_tpu.exec.dist_session import ClusterSession
        from opentenbase_tpu.parallel.cluster import Cluster
        cs = ClusterSession(Cluster(n_datanodes=2))
        rows = cs.query("select admitted, shed, batch_hist "
                        "from otb_scheduler")
        assert len(rows) == 1
        assert rows[0][0] >= 0 and rows[0][1] >= 0

    def test_reset(self):
        sm._bump("admitted")
        assert sm.stats_snapshot()["admitted"] == 1
        sm.reset_stats()
        assert sm.stats_snapshot()["admitted"] == 0


class TestTimingIsolation:
    """Satellite: staging time is scoped per statement — a session
    whose statement staged nothing reads 0.0 instead of the staging
    time of another session on the same cluster (the shared-mesh-runner
    leak)."""

    def test_stage_ms_is_per_statement(self):
        from opentenbase_tpu.exec.dist_session import ClusterSession
        from opentenbase_tpu.exec.mesh_exec import mesh_runner_for
        from opentenbase_tpu.parallel.cluster import Cluster
        cs = ClusterSession(Cluster(n_datanodes=2))
        cs.execute("create table mt (k bigint primary key, v bigint) "
                   "distribute by shard(k)")
        cs.execute("insert into mt values " + ", ".join(
            f"({i}, {i * 3})" for i in range(64)))
        cs.query("select sum(v) from mt")
        assert mesh_runner_for(cs.cluster) is not None
        st = cs.last_query_stats()
        assert st["tier"] == "mesh"
        mine = st["stage_ms"]
        assert mine > 0.0          # this statement staged
        seen = []

        def other():               # same cluster, same runner
            s2 = ClusterSession(cs.cluster)
            s2.execute("set enable_fqs = on")
            seen.append(s2.last_query_stats()["stage_ms"])

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert seen == [0.0]       # other sessions see no leak
        assert cs.last_query_stats()["stage_ms"] == mine   # mine survives
