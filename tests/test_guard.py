"""otbguard proof: cluster-wide fault tolerance (ISSUE 8).

Layers, bottom-up:
- wire close semantics: clean hangup vs. mid-conversation close are
  never conflated (satellite 1), plus the chaos modes (garble/delay);
- connection-pool accounting under broken sockets + generations
  (satellite 2);
- circuit breaker / guarded() retry unit behavior;
- the fault-point matrix: every 2PC crash window drives to a converged
  verdict via the in-doubt resolver, including the REMOTE_COMMIT_PARTIAL
  divergence window (satellite 3);
- chaos acceptance: a DN dies mid-workload and reads keep answering via
  standby failover; a flapping DN trips the breaker which half-open
  recovers; all of it visible in guard counters and otb_node_health.

Reference analog: xact_whitebox stub points + clean2pc + pgxc node
health — see ISSUE 8 / README "Fault tolerance".
"""

import os
import socket
import threading
import time

import pytest

from opentenbase_tpu.catalog import types as T
from opentenbase_tpu.catalog.schema import (ColumnDef, Distribution,
                                            DistType, TableDef)
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.gtm.server import GtmCore, GtmServer
from opentenbase_tpu.net import guard
from opentenbase_tpu.net.dn_server import (DnConnectionPool, DnServer,
                                           RemoteDataNode)
from opentenbase_tpu.net.wire import WireError, recv_msg, send_msg
from opentenbase_tpu.obs.metrics import REGISTRY
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.utils import faultinject as FI


@pytest.fixture(autouse=True)
def _clean_guard_state():
    """Guard registry and chaos arms are process-global: every test
    starts from a clean slate and leaves one behind."""
    guard.reset()
    FI.disarm()
    FI.disarm_wire()
    yield
    guard.reset()
    FI.disarm()
    FI.disarm_wire()


@pytest.fixture()
def tcp_cluster(tmp_path):
    d = str(tmp_path)
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
    res = getattr(cluster, "_resolver", None)
    if res is not None:
        res.stop()
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass
    gtm.stop()


def _counter_value(name, **labels):
    """Sum of every sample of `name` whose label string matches."""
    total = 0.0
    for n, lbl, kind, v in REGISTRY.rows():
        if n == name and all(str(val) in lbl
                             for val in labels.values()):
            total += v
    return total


# ---------------------------------------------------------------------------
# satellite 1: wire close semantics + chaos modes
# ---------------------------------------------------------------------------

class TestWireCloseSemantics:
    def test_clean_close_at_boundary_is_none(self):
        a, b = socket.socketpair()
        send_msg(a, {"x": 1})
        assert recv_msg(b) == {"x": 1}
        a.close()
        assert recv_msg(b) is None    # boundary hangup: clean
        b.close()

    def test_close_mid_message_raises(self):
        a, b = socket.socketpair()
        import pickle
        import struct
        import zlib
        blob = pickle.dumps({"x": 1}, protocol=4)
        hdr = struct.Struct("<II").pack(len(blob), zlib.crc32(blob))
        a.sendall(hdr + blob[:3])     # torn frame
        a.close()
        with pytest.raises(WireError, match="mid-message"):
            recv_msg(b)
        b.close()

    def test_expect_reply_close_raises(self):
        # the satellite-1 fix: a peer that hangs up while it OWES a
        # reply must never read as "no message"
        a, b = socket.socketpair()
        a.close()
        with pytest.raises(WireError, match="awaiting reply"):
            recv_msg(b, expect_reply=True)
        b.close()

    def test_garble_mode_is_checksum_mismatch(self):
        a, b = socket.socketpair()
        FI.arm_wire("t.garble", mode="garble")
        send_msg(a, {"x": list(range(50))}, fault="t.garble")
        with pytest.raises(WireError, match="checksum"):
            recv_msg(b)
        a.close()
        b.close()

    def test_drop_mode_times_out_peer(self):
        a, b = socket.socketpair()
        FI.arm_wire("t.drop", mode="drop")
        send_msg(a, {"x": 1}, fault="t.drop")   # silently lost
        b.settimeout(0.2)
        with pytest.raises(OSError):
            recv_msg(b, expect_reply=True)
        a.close()
        b.close()

    def test_delay_mode_then_delivers(self):
        a, b = socket.socketpair()
        FI.arm_wire("t.delay", mode="delay", delay_s=0.05)
        t0 = time.monotonic()
        send_msg(a, {"x": 1}, fault="t.delay")
        assert time.monotonic() - t0 >= 0.05
        assert recv_msg(b) == {"x": 1}
        a.close()
        b.close()

    def test_arm_times_n_then_self_disarms(self):
        FI.arm_wire("t.n", mode="drop", times=2)
        assert FI.wire_action("t.n")["mode"] == "drop"
        assert FI.wire_action("t.n")["mode"] == "drop"
        assert FI.wire_action("t.n") is None


# ---------------------------------------------------------------------------
# satellite 2: pool accounting + generations
# ---------------------------------------------------------------------------

class _EchoServer:
    """Minimal framed echo server for pool unit tests."""

    def __init__(self):
        self._srv = socket.socket()
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(16)
        self.addr = self._srv.getsockname()
        self._stop = False
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def _loop(self):
        while not self._stop:
            try:
                c, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(c,),
                             daemon=True).start()

    def _serve(self, c):
        try:
            while True:
                msg = recv_msg(c)
                if msg is None:
                    return
                send_msg(c, {"ok": msg})
        except (ConnectionError, EOFError):
            pass
        finally:
            c.close()

    def stop(self):
        self._stop = True
        self._srv.close()


class TestPoolAccounting:
    def test_broken_release_never_leaks_slots(self):
        srv = _EchoServer()
        try:
            pool = DnConnectionPool(srv.addr, max_conns=2)
            # 10 broken exchanges through a 2-slot pool: if release
            # leaked accounting, acquire #3 would block forever
            for _ in range(10):
                s = pool.acquire()
                pool.release(s, broken=True)
            st = pool.stats()
            assert st["open"] == 0 and st["leased"] == 0
            # and the pool still serves
            s = pool.acquire()
            send_msg(s, {"op": "ping"})
            assert recv_msg(s, expect_reply=True) == {"ok": {"op": "ping"}}
            pool.release(s)
            assert pool.stats()["free"] == 1
        finally:
            srv.stop()

    def test_double_release_is_idempotent(self):
        srv = _EchoServer()
        try:
            pool = DnConnectionPool(srv.addr, max_conns=2)
            s = pool.acquire()
            pool.release(s, broken=True)
            pool.release(s, broken=True)   # must not double-decrement
            st = pool.stats()
            assert st["open"] == 0 and st["leased"] == 0
        finally:
            srv.stop()

    def test_generation_retires_stale_sockets(self):
        srv = _EchoServer()
        try:
            pool = DnConnectionPool(srv.addr)
            s1 = pool.acquire()
            pool.release(s1)               # warm in free list
            pool.retire()                  # "the DN restarted"
            s2 = pool.acquire()            # must NOT be s1
            assert s2 is not s1
            assert pool.retired >= 1 and pool.gen == 1
            pool.release(s2)
            # a leased-then-released socket from an old gen is closed
            s3 = pool.acquire()
            pool.retire()
            pool.release(s3)               # returns AFTER the retire
            assert pool.stats()["free"] == 0
        finally:
            srv.stop()

    def test_socket_killed_mid_call_recovers(self, tcp_cluster):
        """The satellite-2 regression: a socket dies between send and
        recv; the idempotent op retries on a fresh socket, accounting
        stays exact, and the stale generation is retired."""
        s, servers, gtm, d = tcp_cluster
        dn0 = s.cluster.datanodes[0]
        # warm a socket, then kill the conversation on the next recv
        assert dn0.ping() is True
        FI.arm_wire("dn0.recv", mode="close", times=1)
        assert dn0.ping() is True          # retried transparently
        st = dn0.pool.stats()
        assert st["leased"] == 0, st
        assert dn0.pool.gen >= 1           # connection failure retired
        g = guard.guard_for(dn0.guard_key)
        assert g.retries >= 1
        assert _counter_value("otb_guard_retries_total") >= 1

    def test_nonidempotent_op_is_not_retried(self, tcp_cluster):
        s, servers, gtm, d = tcp_cluster
        s.execute("create table nr (k bigint primary key) "
                  "distribute by shard(k)")
        dn0 = s.cluster.datanodes[0]
        txid = int(s.cluster.gtm.next_txid())
        FI.arm_wire("dn0.recv", mode="close", times=1)
        with pytest.raises((ConnectionError, OSError)):
            dn0.commit(txid, 1)            # 2PC verb: never auto-resent
        assert FI.wire_action("dn0.recv") is None  # fired exactly once


# ---------------------------------------------------------------------------
# breaker + guarded() unit behavior
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_trip_halfopen_recover(self):
        br = guard.CircuitBreaker("n", threshold=3, cooldown_s=0.05)
        for _ in range(3):
            br.admit()
            br.fail()
        assert br.state == "open"
        with pytest.raises(guard.CircuitOpen):
            br.admit()                      # cooling down: fail fast
        time.sleep(0.06)
        br.admit()                          # this caller is THE probe
        assert br.state == "half_open"
        with pytest.raises(guard.CircuitOpen):
            br.admit()                      # single-flight probe
        br.ok()
        assert br.state == "closed"
        br.admit()                          # traffic flows again

    def test_probe_failure_reopens(self):
        br = guard.CircuitBreaker("n", threshold=1, cooldown_s=0.05)
        br.admit()
        br.fail()
        assert br.state == "open"
        time.sleep(0.06)
        br.admit()
        br.fail()                           # probe failed
        assert br.state == "open"
        with pytest.raises(guard.CircuitOpen):
            br.admit()                      # cooldown restarted

    def test_success_resets_consecutive_count(self):
        br = guard.CircuitBreaker("n", threshold=3)
        br.admit(); br.fail()
        br.admit(); br.fail()
        br.admit(); br.ok()
        br.admit(); br.fail()
        assert br.state == "closed"         # never 3 CONSECUTIVE


class TestGuarded:
    def test_idempotent_retries_then_succeeds(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise ConnectionError("boom")
            return "ok"

        assert guard.guarded("u1", flaky, idempotent=True,
                             retries=3) == "ok"
        assert calls["n"] == 3
        assert guard.guard_for("u1").retries == 2

    def test_non_idempotent_raises_first_failure(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            raise ConnectionError("boom")

        with pytest.raises(ConnectionError):
            guard.guarded("u2", flaky, idempotent=False)
        assert calls["n"] == 1

    def test_statement_errors_pass_through_unretried(self):
        calls = {"n": 0}

        def bad_sql():
            calls["n"] += 1
            raise RuntimeError("syntax error")

        with pytest.raises(RuntimeError):
            guard.guarded("u3", bad_sql, idempotent=True, retries=5)
        assert calls["n"] == 1              # not a connection failure

    def test_open_breaker_fails_fast(self, monkeypatch):
        monkeypatch.setenv("OTB_BREAKER_COOLDOWN", "60")
        g = guard.guard_for("u4")
        for _ in range(g.breaker.threshold):
            g.breaker.admit()
            g.breaker.fail()
        calls = {"n": 0}

        def fn():
            calls["n"] += 1

        with pytest.raises(guard.CircuitOpen):
            guard.guarded("u4", fn)
        assert calls["n"] == 0              # never reached the wire

    def test_backoff_bounded_with_jitter(self):
        for attempt in range(1, 12):
            b = guard.backoff_s(attempt, base=0.05, cap=1.0)
            assert 0.0 < b <= 1.0

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("OTB_RPC_TIMEOUT", "7.5")
        monkeypatch.setenv("OTB_RPC_RETRIES", "4")
        assert guard.rpc_deadline() == 7.5
        assert guard.rpc_retries() == 4
        monkeypatch.setenv("OTB_RPC_TIMEOUT", "junk")
        assert guard.rpc_deadline() == 300.0


# ---------------------------------------------------------------------------
# satellite 3: the fault-point matrix
# ---------------------------------------------------------------------------

def _make_2dn_table(cluster, name="gt"):
    td = TableDef(name, [ColumnDef("k", T.INT64)],
                  Distribution(DistType.MODULO, ["k"]))
    cluster.create_table(td)
    return td


def _write_both_dns(cluster, name, base):
    """One row per datanode under one txid -> guaranteed implicit 2PC."""
    txid = int(cluster.gtm.next_txid())
    cluster.register_txn(txid)
    for i, dn in enumerate(cluster.datanodes):
        dn.insert_raw(name, {"k": [base + i]}, 1, txid)
    return txid


def _converge(cluster, rounds=10, grace=0.0):
    out = {"committed": 0, "aborted": 0}
    for _ in range(rounds):
        r = cluster.resolve_indoubt(orphan_grace_s=grace)
        out["committed"] += r["committed"]
        out["aborted"] += r["aborted"]
        if not cluster.gtm.prepared_list() and not any(
                _dn_prepared(dn) for dn in cluster.datanodes):
            break
    return out


def _dn_prepared(dn):
    try:
        return dn.prepared_txns()
    except Exception:
        return {}


# expected converged outcome per crash window: before the GTM commit
# record the txn must ABORT everywhere; after it, COMMIT everywhere
_MATRIX = [
    ("REMOTE_PREPARE_BEFORE_SEND", 0),
    ("REMOTE_PREPARE_AFTER_SEND", 0),      # orphaned prepares
    ("AFTER_GTM_PREPARE", 0),              # presumed abort
    ("AFTER_GTM_COMMIT_BEFORE_DN", 2),     # redelivery
    ("REMOTE_COMMIT_PARTIAL", 2),          # divergence -> redelivery
    ("BEFORE_GTM_FORGET", 2),
]


class TestFaultPointMatrix:
    @pytest.mark.parametrize("point,expect_rows", _MATRIX)
    def test_resolver_converges(self, tcp_cluster, point, expect_rows):
        s, servers, gtm, d = tcp_cluster
        cluster = s.cluster
        _make_2dn_table(cluster)
        FI.arm(point)
        try:
            with pytest.raises(FI.InjectedFault):
                txid = _write_both_dns(cluster, "gt", 0)
                cluster.commit_txn(txid, dns=[0, 1])
        finally:
            FI.disarm()
        _converge(cluster)
        # converged: no in-doubt state anywhere...
        assert cluster.gtm.prepared_list() == {}
        for dn in cluster.datanodes:
            assert _dn_prepared(dn) == {}
        # ...and both DNs agree with the GTM verdict
        cluster.active_txns.clear()
        assert s.query("select count(*) from gt") == [(expect_rows,)]
        if expect_rows:
            assert _counter_value(
                "otb_guard_indoubt_resolved_total") >= 1

    def test_remote_commit_partial_divergence_then_heals(
            self, tcp_cluster):
        """The REMOTE_COMMIT_PARTIAL window is OBSERVABLY divergent
        (one DN committed, one still prepared) before the resolver
        heals it — the whitebox check that the matrix actually covers
        the split-brain moment, not just the end state."""
        s, servers, gtm, d = tcp_cluster
        cluster = s.cluster
        _make_2dn_table(cluster)
        FI.arm("REMOTE_COMMIT_PARTIAL")
        try:
            with pytest.raises(FI.InjectedFault):
                txid = _write_both_dns(cluster, "gt", 0)
                cluster.commit_txn(txid, dns=[0, 1])
        finally:
            FI.disarm()
        prepared = [bool(srv.node.prepared_gids) for srv in servers]
        assert sorted(prepared) == [False, True], \
            f"expected split-brain window, got {prepared}"
        _converge(cluster)
        cluster.active_txns.clear()
        assert s.query("select count(*) from gt") == [(2,)]
        assert all(not srv.node.prepared_gids for srv in servers)

    def test_background_resolver_thread_converges(self, tcp_cluster):
        s, servers, gtm, d = tcp_cluster
        cluster = s.cluster
        _make_2dn_table(cluster)
        FI.arm("AFTER_GTM_COMMIT_BEFORE_DN")
        try:
            with pytest.raises(FI.InjectedFault):
                txid = _write_both_dns(cluster, "gt", 0)
                cluster.commit_txn(txid, dns=[0, 1])
        finally:
            FI.disarm()
        res = cluster.ensure_resolver(period_s=0.05, grace_s=0.0)
        assert cluster.ensure_resolver() is res   # idempotent
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if not cluster.gtm.prepared_list():
                break
            time.sleep(0.05)
        assert cluster.gtm.prepared_list() == {}
        assert res.sweeps >= 1
        cluster.active_txns.clear()
        assert s.query("select count(*) from gt") == [(2,)]
        res.stop()


# ---------------------------------------------------------------------------
# GTM guard: deadline/retry + standby promotion on loss
# ---------------------------------------------------------------------------

class _DeadGtm:
    addr = ("127.0.0.1", 1)

    def __getattr__(self, name):
        def dead(*a, **kw):
            raise ConnectionError("gtm down")
        return dead


class TestGtmGuard:
    def test_promotes_standby_on_loss(self, monkeypatch):
        from opentenbase_tpu.gtm.standby import GtmStandby
        monkeypatch.setenv("OTB_RPC_RETRIES", "0")
        sb = GtmStandby()
        primary = GtmCore(None, ship=sb.apply)
        issued = [primary.next_gts() for _ in range(5)]
        primary.prepare_txn("g1", ["dn0"], 7)
        # the primary "dies": every call to it now fails hard
        g = guard.GtmGuard(_DeadGtm(), standby=sb, key="gtm-t1")
        ts = g.next_gts()                   # promoted transparently
        assert ts > max(issued)
        assert g.txn_verdict("g1") == "prepared"  # 2PC registry survived
        assert _counter_value("otb_guard_failovers_total") >= 1

    def test_no_standby_raises(self, monkeypatch):
        monkeypatch.setenv("OTB_RPC_RETRIES", "0")
        g = guard.GtmGuard(_DeadGtm(), key="gtm-t2")
        with pytest.raises(ConnectionError):
            g.next_gts()

    def test_transparent_delegation(self):
        core = GtmCore(None)
        g = guard.GtmGuard(core, key="gtm-t3")
        t1 = g.next_gts()
        assert g.next_gts() > t1            # methods flow through
        g._txid = 500                       # attribute writes hit target
        assert core._txid == 500
        assert g.stats()["txid"] == 500

    def test_cluster_attach_and_2pc_still_works(self, tmp_path):
        cl = Cluster(n_datanodes=2, datadir=str(tmp_path / "cl"))
        from opentenbase_tpu.gtm.standby import GtmStandby
        cl.attach_gtm_standby(GtmStandby())
        s = ClusterSession(cl)
        s.execute("create table t (k bigint primary key) "
                  "distribute by shard(k)")
        s.execute("begin")
        s.execute("insert into t values " + ", ".join(
            f"({i})" for i in range(20)))
        s.execute("commit")
        assert s.query("select count(*) from t") == [(20,)]


# ---------------------------------------------------------------------------
# chaos acceptance: DN failure mid-workload
# ---------------------------------------------------------------------------

class TestChaosFailover:
    def test_breaker_trips_then_halfopen_recovers(self, tcp_cluster,
                                                  monkeypatch):
        """A FLAPPING DN (wire faults, server alive): consecutive
        failures trip the breaker (fail-fast), the cooldown admits one
        probe, the probe succeeds, traffic resumes — all visible in
        counters and otb_node_health."""
        monkeypatch.setenv("OTB_BREAKER_THRESHOLD", "3")
        monkeypatch.setenv("OTB_BREAKER_COOLDOWN", "0.1")
        monkeypatch.setenv("OTB_RPC_RETRIES", "0")
        s, servers, gtm, d = tcp_cluster
        dn0 = s.cluster.datanodes[0]
        key = dn0.guard_key
        assert dn0.ping() is True
        assert guard.guard_for(key).state() == "up"
        FI.arm_wire("dn0.recv", mode="close", times=3)
        for _ in range(3):
            assert dn0.ping() is False
        br = guard.guard_for(key).breaker
        assert br.state == "open"
        assert guard.guard_for(key).state() == "down"
        assert _counter_value("otb_guard_breaker_trips_total") >= 1
        # fail-fast while cooling: the wire is never touched
        assert dn0.ping() is False
        time.sleep(0.12)
        assert dn0.ping() is True           # the half-open probe
        assert br.state == "closed"
        assert _counter_value("otb_guard_breaker_halfopen_total") >= 1
        rows = dict((r[0], r[1]) for r in guard.health_rows())
        assert rows[key] == "up"

    def test_flapping_dn_point_reads_never_lie(self, tcp_cluster,
                                               monkeypatch):
        """Point reads over SQL while dn0's wire flaps.  Inside the
        retry budget every tear is absorbed: no error, no wrong row.
        Past it a read may fail but never lies, the breaker's trip
        leaves a flight bundle that survives JSON, and after the
        cooldown every read answers again."""
        import json
        from opentenbase_tpu.obs import xray
        s, servers, gtm, d = tcp_cluster
        s.execute("create table fk (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        s.execute("insert into fk values " + ", ".join(
            f"({i}, {i * 3})" for i in range(64)))

        def read(k):
            return s.query(f"select v from fk where k = {k}")

        # a breaker takes its knobs when it is built: rebuild the guards
        monkeypatch.setenv("OTB_RPC_RETRIES", "3")
        monkeypatch.setenv("OTB_BREAKER_THRESHOLD", "16")
        guard.reset()
        retries0 = _counter_value("otb_guard_retries_total")
        for i in range(48):
            if i % 8 == 0:
                FI.arm_wire("dn0.recv", "close", times=2)
            assert read(i % 64) == [((i % 64) * 3,)]
        FI.disarm_wire()
        assert _counter_value("otb_guard_retries_total") > retries0

        monkeypatch.setenv("OTB_RPC_RETRIES", "0")
        monkeypatch.setenv("OTB_BREAKER_THRESHOLD", "3")
        monkeypatch.setenv("OTB_BREAKER_COOLDOWN", "0.1")
        guard.reset()
        key = s.cluster.datanodes[0].guard_key
        FI.arm_wire("dn0.recv", "close", times=6)
        failed = 0
        for k in range(24):
            try:
                rows = read(k)
            except Exception:   # noqa: BLE001 — a torn read may fail
                failed += 1
                continue
            assert rows == [(k * 3,)]
        FI.disarm_wire()
        assert failed >= 3
        assert _counter_value("otb_guard_breaker_trips_total") >= 1
        trips = [b for b in xray.flights()
                 if b["kind"] == "breaker_trip" and b["signature"] == key]
        assert trips, [b["kind"] for b in xray.flights()]
        for b in trips:
            assert json.loads(json.dumps(b))["kind"] == "breaker_trip"
        time.sleep(0.15)
        for k in range(24):
            assert read(k) == [(k * 3,)]

    def test_dead_dn_reads_fail_over_to_standby(self, tcp_cluster):
        """The tentpole acceptance: kill one DN mid-workload; read-only
        fragments re-dispatch to its promoted standby with ZERO wrong
        results; the failover is visible in counters."""
        from opentenbase_tpu.storage.replication import (DnStandby,
                                                         DnStandbyServer)
        s, servers, gtm, d = tcp_cluster
        cluster = s.cluster
        s.execute("create table ct (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        s.execute("insert into ct values " + ", ".join(
            f"({i}, {i * 10})" for i in range(40)))
        # ship dn0's data to a standby, register it in the catalog
        sb = DnStandby(os.path.join(d, "standby0"))
        sbs = DnStandbyServer(sb).start()
        try:
            servers[0].node.attach_standby(sbs.host, sbs.port)
            s.execute("insert into ct values (100, 1000), (101, 1010)")
            before = s.query("select count(*), sum(v) from ct")
            by_k = sorted(s.query("select k, v from ct"))
            cluster.register_standby(0, datadir=sb.datadir)
            failovers0 = _counter_value("otb_guard_failovers_total")
            # kill dn0 mid-workload
            servers[0].stop()
            cluster.datanodes[0].close()
            # reads keep answering, results exactly right
            s2 = ClusterSession(cluster)
            assert s2.query("select count(*), sum(v) from ct") == before
            assert sorted(s2.query("select k, v from ct")) == by_k
            assert _counter_value("otb_guard_failovers_total") > failovers0
            # the promoted node serves writes too
            s2.execute("insert into ct values (999, 9990)")
            assert s2.query("select v from ct where k = 999") == [(9990,)]
        finally:
            sbs.stop()

    def test_no_standby_read_surfaces_original_error(self, tcp_cluster):
        s, servers, gtm, d = tcp_cluster
        s.execute("create table ne (k bigint primary key) "
                  "distribute by shard(k)")
        s.execute("insert into ne values (1), (2), (3)")
        servers[0].stop()
        s.cluster.datanodes[0].close()
        s2 = ClusterSession(s.cluster)
        with pytest.raises(Exception):
            s2.query("select count(*) from ne")


# ---------------------------------------------------------------------------
# observability: otb_node_health + shed arm
# ---------------------------------------------------------------------------

class TestObservability:
    def test_node_health_view(self, tcp_cluster):
        s, servers, gtm, d = tcp_cluster
        for dn in s.cluster.datanodes:
            assert dn.ping() is True
        rows = s.query("select node, state, breaker from otb_node_health")
        states = {r[0]: (r[1], r[2]) for r in rows}
        for dn in s.cluster.datanodes:
            assert states[dn.guard_key] == ("up", "closed"), states

    def test_node_health_reflects_degraded(self, tcp_cluster,
                                           monkeypatch):
        monkeypatch.setenv("OTB_RPC_RETRIES", "0")
        s, servers, gtm, d = tcp_cluster
        dn0 = s.cluster.datanodes[0]
        FI.arm_wire("dn0.recv", mode="close", times=1)
        assert dn0.ping() is False
        rows = s.query("select node, state, consec_failures, last_error "
                       "from otb_node_health")
        ent = {r[0]: r for r in rows}[dn0.guard_key]
        assert ent[1] == "degraded"
        assert ent[2] >= 1
        assert "close" in ent[3] or "Wire" in ent[3]

    def test_shed_reports_to_ladder(self):
        shed0 = _counter_value("otb_guard_shed_total")
        guard.note_shed("default")
        assert _counter_value("otb_guard_shed_total") == shed0 + 1
        assert guard.guard_for("scheduler").state() == "degraded"

    def test_health_rows_shape(self):
        guard.guard_for("x").note_success()
        rows = guard.health_rows()
        assert any(r[0] == "x" and r[1] == "up" and r[2] == "closed"
                   for r in rows)
        assert all(len(r) == 6 for r in rows)
