"""Multi-process(-style) deployment: CN talking to DN servers + GTM over
real TCP sockets (servers run as threads here; the protocol and process
separation are identical to subprocess deployment — the reference tests
multi-node the same way, all on localhost: opentenbase_test.py:45-48)."""

import os

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.gtm.server import GtmClient, GtmCore, GtmServer
from opentenbase_tpu.net.dn_server import DnServer, RemoteDataNode
from opentenbase_tpu.parallel.cluster import Cluster


@pytest.fixture()
def tcp_cluster(tmp_path):
    d = str(tmp_path)
    # init catalog via an embedded cluster, then serve it over TCP
    Cluster(n_datanodes=2, datadir=d).checkpoint()
    gtm = GtmServer(GtmCore(os.path.join(d, "gtm.json"))).start()
    catalog_path = os.path.join(d, "catalog.json")
    servers = [DnServer(i, os.path.join(d, f"dn{i}"), catalog_path,
                        gtm_addr=(gtm.host, gtm.port)).start()
               for i in range(2)]
    cluster = Cluster.connect(catalog_path,
                              [(s.host, s.port) for s in servers],
                              (gtm.host, gtm.port))
    yield ClusterSession(cluster), servers, gtm, d
    for s in servers:
        s.stop()
    gtm.stop()


class TestTcpCluster:
    def test_end_to_end_sql(self, tcp_cluster):
        s, servers, gtm, d = tcp_cluster
        s.execute("create table t (k bigint primary key, v decimal(10,2)) "
                  "distribute by shard(k)")
        rows = ", ".join(f"({i}, {i}.25)" for i in range(20))
        s.execute(f"insert into t values {rows}")
        # rows actually live in the server processes
        counts = [srv.node.stores["t"].row_count() for srv in servers]
        assert sum(counts) == 20 and all(c > 0 for c in counts)
        assert s.query("select count(*), sum(v) from t") == \
            [(20, 20 * 19 / 2 + 20 * 0.25)]
        assert s.query("select v from t where k = 7") == [(7.25,)]

    def test_distributed_join_over_tcp(self, tcp_cluster):
        s, *_ = tcp_cluster
        s.execute("create table a (x bigint primary key) "
                  "distribute by shard(x)")
        s.execute("create table b (y bigint primary key, x2 bigint) "
                  "distribute by shard(y)")
        s.execute("insert into a values (1), (2), (3)")
        s.execute("insert into b values (10, 1), (20, 2), (30, 9)")
        assert s.query("select count(*) from a, b where x = x2") == [(2,)]

    def test_2pc_over_tcp_and_gtm(self, tcp_cluster):
        s, servers, gtm, d = tcp_cluster
        s.execute("create table t2 (k bigint primary key) "
                  "distribute by shard(k)")
        s.execute("begin")
        rows = ", ".join(f"({i})" for i in range(30))
        s.execute(f"insert into t2 values {rows}")
        s.execute("commit")
        assert s.query("select count(*) from t2") == [(30,)]

    def test_gtm_client_monotonic(self, tcp_cluster):
        s, servers, gtm, d = tcp_cluster
        c = GtmClient(gtm.host, gtm.port)
        ts = [c.next_gts() for _ in range(10)]
        assert ts == sorted(ts) and len(set(ts)) == 10

    def test_supervisor_restarts_dead_dn(self, tcp_cluster):
        """The postmaster-restart analog: a dead DN server comes back
        with its data (WAL recovery) on the same port."""
        s, servers, gtm, d = tcp_cluster
        from opentenbase_tpu.cli.ctl import Supervisor
        s.execute("create table t (k bigint primary key, "
                  "v decimal(10,2)) distribute by shard(k)")
        s.execute("insert into t values " + ", ".join(
            f"({i}, {i}.25)" for i in range(20)))
        catalog_path = os.path.join(d, "catalog.json")

        def make_factory(i, port):
            def factory():
                return DnServer(i, os.path.join(d, f"dn{i}"),
                                catalog_path,
                                gtm_addr=(gtm.host, gtm.port),
                                port=port).start()
            return factory

        factories = [make_factory(i, srv.port)
                     for i, srv in enumerate(servers)]
        sup = Supervisor(servers, factories)
        assert sup.check_once() == []       # all healthy: no restarts
        servers[0].stop()                   # "kill" dn0
        assert sup.check_once() == [0]      # detected + restarted
        s2 = ClusterSession(Cluster.connect(
            catalog_path, [(srv.host, srv.port) for srv in servers],
            (gtm.host, gtm.port)))
        assert s2.query("select count(*) from t") == [(20,)]
        s2.execute("insert into t values (999, 1.00)")
        assert s2.query("select v from t where k = 999") == [(1.0,)]

    def test_concurrent_fragment_dispatch(self):
        """Fragment fan-out must overlap datanodes: wall-clock ≈
        max(DN), not sum(DN) (reference: RunRemoteController)."""
        import time

        from opentenbase_tpu.exec.dist import DistExecutor
        from opentenbase_tpu.plan.distribute import (DistPlan, Exchange,
                                                     ExchangeRef,
                                                     Fragment)

        DELAY = 0.25

        class SlowRemote:                 # no .stores => remote-shaped
            def __init__(self, index):
                self.index = index

            def exec_plan(self, plan, snapshot_ts, txid, params,
                          sources):
                time.sleep(DELAY)
                from opentenbase_tpu.exec.dist import HostBatch
                import numpy as np
                from opentenbase_tpu.catalog import types as T
                return HostBatch({"x": np.asarray([self.index])},
                                 {"x": T.INT64}, 1)

        class FakeCluster:
            datanodes = [SlowRemote(i) for i in range(3)]
            ndn = 3

        ex = DistExecutor(FakeCluster(), 10**15, 1)
        frag = Fragment(0, ExchangeRef(99), "dn")  # plan is unused
        dp = DistPlan([frag], [Exchange(0, "gather", [], 0)], 0, [], [])
        t0 = time.perf_counter()
        out: dict = {}
        ex._feed_exchanges(frag, dp, out)
        elapsed = time.perf_counter() - t0
        assert (0, "cn") in out and out[(0, "cn")].nrows == 3
        # sequential would take 3*DELAY; concurrent ≈ DELAY
        assert elapsed < 2 * DELAY, \
            f"dispatch not concurrent: {elapsed:.2f}s for 3x{DELAY}s"

    def test_dn_restart_recovers_over_tcp(self, tcp_cluster, tmp_path):
        s, servers, gtm, d = tcp_cluster
        s.execute("create table t3 (k bigint primary key, "
                  "name varchar(10)) distribute by shard(k)")
        s.execute("insert into t3 values (1, 'a'), (2, 'b'), (3, 'c')")
        # stop dn servers, restart from their datadirs
        for srv in servers:
            srv.stop()
        catalog_path = os.path.join(d, "catalog.json")
        new_servers = [DnServer(i, os.path.join(d, f"dn{i}"), catalog_path,
                                gtm_addr=(gtm.host, gtm.port)).start()
                       for i in range(2)]
        try:
            cluster2 = Cluster.connect(
                catalog_path, [(x.host, x.port) for x in new_servers],
                (gtm.host, gtm.port))
            s2 = ClusterSession(cluster2)
            assert s2.query("select count(*) from t3") == [(3,)]
            assert s2.query("select name from t3 where k = 2") == [("b",)]
        finally:
            for srv in new_servers:
                srv.stop()

    def test_online_shard_move_over_rpc(self, tcp_cluster):
        """Rebalancing works on the production (TCP) deployment: shard
        extraction rides the DN wire protocol (extract_shards op), the
        movement commits under implicit 2PC, values survive exactly."""
        import numpy as np
        from opentenbase_tpu.parallel.maintenance import move_shards
        s, servers, gtm, d = tcp_cluster
        s.execute("create table mt (k bigint primary key, "
                  "v decimal(10,2), name varchar(10)) "
                  "distribute by shard(k)")
        s.execute("insert into mt values " + ", ".join(
            f"({i}, {i}.25, 'n{i}')" for i in range(40)))
        before = sorted(s.query("select k, v, name from mt"))
        sids = np.nonzero(s.cluster.catalog.shard_map == 0)[0].tolist()
        moved = move_shards(s.cluster, sids, 1)
        assert moved > 0
        assert sorted(s.query("select k, v, name from mt")) == before
        # the source server really lost the rows; target really has them
        s.cluster.gtm.next_gts()
        total = sum(srv.node.stores["mt"].row_count() for srv in servers)
        assert total >= 40
        # routing follows the updated map for new writes
        s.execute("insert into mt values (999, 9.75, 'post')")
        assert s.query("select v from mt where k = 999") == [(9.75,)]

    def test_shard_move_fault_injection_aborts_cleanly(self, tcp_cluster):
        """A crash in the 2PC commit window mid-move must not lose or
        duplicate rows once the in-doubt txn resolves."""
        import numpy as np
        from opentenbase_tpu.parallel.maintenance import move_shards
        from opentenbase_tpu.utils import faultinject as FI
        s, servers, gtm, d = tcp_cluster
        s.execute("create table ft (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        s.execute("insert into ft values " + ", ".join(
            f"({i}, {i})" for i in range(40)))
        before = sorted(s.query("select k, v from ft"))
        sids = np.nonzero(s.cluster.catalog.shard_map == 0)[0].tolist()
        FI.arm("REMOTE_PREPARE_AFTER_SEND")
        try:
            with pytest.raises(FI.InjectedFault):
                move_shards(s.cluster, sids, 1)
        finally:
            FI.disarm()
        # the move aborted: no data lost, no duplicates, map unchanged
        assert sorted(s.query("select k, v from ft")) == before
        assert int(s.cluster.catalog.shard_map[sids[0]]) == 0
        # and a clean retry succeeds
        assert move_shards(s.cluster, sids, 1) > 0
        assert sorted(s.query("select k, v from ft")) == before

    def test_node_health(self, tcp_cluster):
        s, servers, gtm, d = tcp_cluster
        proxy = RemoteDataNode(0, servers[0].host, servers[0].port)
        assert proxy.ping() is True
        servers[0].stop()
        proxy.close()
        assert proxy.ping() is False


class TestTcpMeshTier:
    def test_join_query_rides_device_mesh(self, tcp_cluster):
        """The device data plane works ACROSS process boundaries: remote
        DNs ship version-cached shard snapshots to the mesh owner
        (stage_table RPC), and the query compiles to the same shard_map
        program as the in-process deployment (reference: the FN
        sender/receiver pair as separate processes, forwardsend.c:165,
        forwardrecv.c:141)."""
        s, *_ = tcp_cluster
        s.execute("create table f (k bigint primary key, g bigint, "
                  "v bigint) distribute by shard(k)")
        s.execute("create table dm (g bigint primary key, nm bigint) "
                  "distribute by shard(g)")
        s.execute("insert into f values " + ", ".join(
            f"({i}, {i % 3}, {i * 10})" for i in range(30)))
        s.execute("insert into dm values (0, 100), (1, 200), (2, 300)")
        got = sorted(s.query(
            "select nm, sum(v) from f, dm where f.g = dm.g "
            "group by nm"))
        assert got == [(100, 1350), (200, 1450), (300, 1550)]
        st = s.last_query_stats()
        assert st["tier"] == "mesh", st["fallback"]

    def test_snapshot_cache_invalidates_on_write(self, tcp_cluster):
        s, *_ = tcp_cluster
        s.execute("create table w (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        s.execute("insert into w values (1, 10), (2, 20), (3, 30)")
        assert s.query("select count(*), sum(v) from w") == [(3, 60)]
        st = s.last_query_stats()
        s.execute("update w set v = v + 1 where k = 2")
        assert s.query("select count(*), sum(v) from w") == [(3, 61)]
        s.execute("delete from w where k = 1")
        assert s.query("select count(*), sum(v) from w") == [(2, 51)]
        assert st["tier"] == "mesh", st["fallback"]


class TestConnectionPool:
    def test_session_churn_reuses_sockets(self, tcp_cluster):
        """The pooler criterion (reference: poolmgr.c:632): connections
        survive session end — N short-lived sessions lease warm sockets
        instead of opening new ones."""
        s, *_ = tcp_cluster
        cluster = s.cluster
        s.execute("create table pc (k bigint primary key) "
                  "distribute by shard(k)")
        s.execute("insert into pc values (1), (2), (3)")
        created0 = sum(dn.pool.created for dn in cluster.datanodes)
        for _ in range(6):
            churn = ClusterSession(cluster)
            assert churn.query("select count(*) from pc") == [(3,)]
        created1 = sum(dn.pool.created for dn in cluster.datanodes)
        leases = sum(dn.pool.leases for dn in cluster.datanodes)
        assert created1 == created0, "session churn opened new sockets"
        assert leases > created1

    def test_concurrent_rpcs_one_node(self, tcp_cluster):
        """A blocked lock RPC must not starve other traffic to the same
        DN (per-call leasing)."""
        import threading
        import time as _t
        s, *_ = tcp_cluster
        s2 = ClusterSession(s.cluster)
        s.execute("create table cc (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        s.execute("insert into cc values (1, 0), (2, 0)")
        s.execute("begin")
        s.query("select v from cc where k = 1 for update")
        done = []

        def blocked():
            s2.execute("update cc set v = 1 where k = 1")
            done.append(1)

        t = threading.Thread(target=blocked)
        t.start()
        _t.sleep(0.3)
        # the same DN still answers other sessions while one is blocked
        s3 = ClusterSession(s.cluster)
        assert s3.query("select count(*) from cc") == [(2,)]
        s.execute("commit")
        t.join(20)
        assert done


class TestClusterMonitor:
    def test_dead_dn_flips_health_map(self, tcp_cluster):
        """clustermon.c analog: the liveness daemon detects a dead DN
        within a bounded interval and otb_nodes reflects it."""
        import time as _t
        s, servers, gtm, d = tcp_cluster
        mon = s.cluster.ensure_monitor(period=0.2)
        _t.sleep(0.5)
        assert all(h["healthy"] for h in mon.health.values())
        rows = dict((r[0], r[1]) for r in
                    s.query("select name, healthy from otb_nodes"))
        assert rows.get("dn0") and rows.get("dn1")
        servers[0].stop()
        deadline = _t.monotonic() + 5.0
        while _t.monotonic() < deadline:
            if not mon.health.get(0, {}).get("healthy", True):
                break
            _t.sleep(0.1)
        assert not mon.health[0]["healthy"], \
            "dead DN not detected within the bound"
        rows = dict((r[0], r[1]) for r in
                    s.query("select name, healthy from otb_nodes"))
        assert rows["dn0"] is False and rows["dn1"] is True
        mon.stop()
