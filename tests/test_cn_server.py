"""Client-facing SQL wire protocol: startup/auth, query results,
out-of-band cancel, disconnect cleanup (net/cn_server.py; reference:
tcop/postgres.c:6703 PostgresMain + postmaster.c processCancelRequest)."""

import threading
import time

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.net.cn_server import (CnClient, CnServer,
                                           check_password, write_users)
from opentenbase_tpu.parallel.cluster import Cluster


@pytest.fixture()
def served(tmp_path):
    cluster = Cluster(n_datanodes=2)
    users = str(tmp_path / "users.json")
    write_users(users, {"alice": "s3cret"})
    srv = CnServer(lambda: ClusterSession(cluster),
                   users_path=users).start()
    yield srv, cluster
    srv.stop()


def _client(srv, **kw):
    kw.setdefault("user", "alice")
    kw.setdefault("password", "s3cret")
    return CnClient(srv.host, srv.port, **kw)


class TestWireProtocol:
    def test_query_roundtrip(self, served):
        srv, _ = served
        c = _client(srv)
        c.execute("create table t (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        c.execute("insert into t values (1, 10), (2, 20), (3, 30)")
        assert c.query("select sum(v) from t") == [(60,)]
        # a second client sees the same cluster
        c2 = _client(srv)
        assert c2.query("select count(*) from t") == [(3,)]
        c.close()
        c2.close()

    def test_auth_rejected(self, served):
        srv, _ = served
        with pytest.raises(ConnectionError, match="authentication"):
            _client(srv, password="wrong")
        with pytest.raises(ConnectionError, match="authentication"):
            _client(srv, user="mallory", password="s3cret")

    def test_statement_error_keeps_connection(self, served):
        srv, _ = served
        c = _client(srv)
        with pytest.raises(RuntimeError, match="does not exist"):
            c.execute("select * from nope")
        assert c.query("select 1 + 1")[0][0] == 2
        c.close()

    def test_password_file(self, tmp_path):
        p = str(tmp_path / "u.json")
        write_users(p, {"u": "pw"})
        assert check_password(p, "u", "pw")
        assert not check_password(p, "u", "bad")
        assert not check_password(p, "nobody", "pw")

    def test_disconnect_aborts_open_txn(self, served):
        srv, cluster = served
        c = _client(srv)
        c.execute("create table d (k bigint primary key) "
                  "distribute by shard(k)")
        c.execute("begin")
        c.execute("insert into d values (1)")
        c.close()
        time.sleep(0.3)
        c2 = _client(srv)
        assert c2.query("select count(*) from d") == [(0,)]
        # cluster is clean: no dangling active transaction poisons later
        c2.execute("insert into d values (2)")
        assert c2.query("select count(*) from d") == [(1,)]
        c2.close()

    def test_cancel_mid_statement(self, served):
        """PQcancel analog: a second connection cancels a running
        statement; the canceled session survives and the cluster stays
        consistent."""
        srv, _ = served
        c = _client(srv)
        c.execute("create table big (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        rows = ", ".join(f"({i}, {i})" for i in range(500))
        c.execute(f"insert into big values {rows}")

        errs = []

        def long_query():
            try:
                # self-join fanout — enough fragments that a cancel
                # lands at a dispatch boundary
                c.execute("select count(*) from big a, big b, big c2 "
                          "where a.v = b.v and b.v = c2.v")
            except RuntimeError as e:
                errs.append(str(e))

        t = threading.Thread(target=long_query)
        t.start()
        time.sleep(0.05)
        assert c.cancel() is True
        t.join(timeout=120)
        assert not t.is_alive()
        # whether the cancel landed mid-flight or the query won the
        # race, the session must remain usable afterwards (the socket
        # is free again once the worker thread joined)
        assert c.query("select count(*) from big") == [(500,)]
        if errs:
            assert "canceling statement" in errs[0]
        c.close()

    def test_cancel_requires_secret(self, served):
        srv, _ = served
        c = _client(srv)
        good = c.secret
        c.secret = "wrong"
        assert c.cancel() is False
        c.secret = good
        c.close()


class TestServedPathAccounting:
    """A point read through the CN server, by span and by count: the
    spans under the root's self time, the thread's CPU beside the wall
    time, the compiled programs it launched (PR 36)."""

    def test_a_point_read_by_span_and_by_count(self, served):
        from opentenbase_tpu.obs import trace as obs_trace
        srv, cluster = served
        sessions = []
        srv.make_session = lambda: sessions.append(
            ClusterSession(cluster)) or sessions[-1]
        c = _client(srv)
        c.execute("create table pr (k bigint primary key, v bigint, "
                  "w text) distribute by shard(k)")
        c.execute("insert into pr values " + ", ".join(
            f"({i}, {i * 10}, 'w{i % 3}')" for i in range(1, 41)))
        sql = "select k, v, w from pr where k = {}"
        assert c.query(sql.format(7)) == [(7, 70, "w1")]   # builds
        assert c.query(sql.format(8)) == [(8, 80, "w2")]   # its node stages
        while not obs_trace.recent()[-1].signature.endswith("= 8"):
            time.sleep(0.01)                    # that trace has finished
        last = obs_trace.recent()[-1].qid
        assert c.query(sql.format(8)) == [(8, 80, "w2")]
        st = sessions[0].last_query_stats()     # at the reply: still open
        assert st["tier"] == "fqs"
        # one compiled program answered; its host scalars (row count,
        # literal, snapshot, txid) rode the call's own arguments as numpy
        # values: no put of their own; the validity and three columns
        # came down in ONE batched copy
        assert st["program_calls"] == 1
        assert st["h2d_puts"] == 0 and st["h2d_bytes"] == 0
        assert st["host_syncs"] == st["finalize_fetches"] == 1
        assert st["d2h_bytes"] == st["finalize_fetch_bytes"]
        # the steps that were the root's self time have names now, and
        # what is left is no larger than it was: before, all of it read
        # as `unattributed_ms`
        for key in ("inputs_ms", "release_ms"):
            assert st[key] > 0, key
        was = st["unattributed_ms"] + st["inputs_ms"] + st["release_ms"]
        assert st["unattributed_ms"] < was < st["total_ms"]
        assert st["cpu_ms"] >= 0
        assert st["offcpu_ms"] == pytest.approx(
            st["total_ms"] - st["cpu_ms"], abs=0.5)
        c.close()
        deadline = time.time() + 5
        done = []
        while not done and time.time() < deadline:
            done = [q for q in obs_trace.recent() if q.qid > last
                    and q.signature == sql.format(8)]
            time.sleep(0.01)
        qt = done[0]
        assert [s.name for s in qt.root.children if s.ms > 0] == [
            "wire.recv", "parse", "autoprep", "bind", "inputs", "execute",
            "release", "finalize", "release", "wire.send"]
        fin = qt.summary()
        # the serving thread's CPU, read at the statement's two ends
        assert fin["cpu_ms"] == qt.cpu_ms
        for key in ("program_calls", "host_syncs", "h2d_puts",
                    "d2h_bytes", "h2d_bytes", "inputs_ms", "release_ms"):
            assert fin[key] == st[key], key


class TestTpchOverWire:
    def test_tpch_suite_over_tcp(self, served):
        """An external-process-shaped client (wire protocol only) runs
        TPC-H Q1/Q3/Q5; results must match the in-process session on
        the same cluster exactly (oracle correctness itself is
        test_tpch.py's job)."""
        from opentenbase_tpu.tpch import datagen
        from opentenbase_tpu.tpch.queries import Q
        from opentenbase_tpu.tpch.schema import SCHEMA

        srv, cluster = served
        data = datagen.generate(sf=0.01)
        c = _client(srv)
        c.execute(SCHEMA)
        # bulk-load through the session API (COPY-equivalent staging);
        # the queries themselves go over the wire
        s = ClusterSession(cluster)
        for tname in ("region", "nation", "supplier", "customer",
                      "part", "partsupp", "orders", "lineitem"):
            td = cluster.catalog.table(tname)
            n = len(next(iter(data[tname].values())))
            s._insert_rows(td, data[tname], n)
        for qn in (1, 3, 5):
            assert c.query(Q[qn]) == s.query(Q[qn]), qn
        c.close()
