"""otbxray proof: cluster-wide tracing, wait events, flight recorder.

Layers, bottom-up:
- trace context: a query over a REAL TCP mini-cluster (CN + 2 DN +
  GTM) stitches the servers' piggy-backed span subtrees into one tree;
  EXPLAIN ANALYZE prints per-DN remote phase timings from those spans;
  remote server time never exceeds what the CN observed end-to-end;
- piggy-back discipline: the shipped subtree respects the byte cap,
  degenerating gracefully instead of bloating replies;
- wait events: a saturated scheduler populates the admission/result
  histograms; nested waits restore the outer register entry; the live
  otb_stat_activity view shows a queued statement and then empties;
- flight recorder: induced quarantine and statement timeout each
  produce a parseable JSON bundle (ring + on-disk when OTB_FLIGHT_DIR
  is set), the ring stays bounded, and the CN `flight` wire op serves
  the bundles;
- the disabled path: OTB_TRACE=0 keeps inject/absorb/server_span on
  the shared-NULL fast path, asserted at <3% of a measured point-op
  p50;
- Prometheus hygiene: label values with quotes/backslashes/newlines
  escape cleanly in the text exposition.

Reference analogs: explain_dist.c remote instrumentation,
pg_stat_activity wait_event columns, and core-dump forensics — see
README "Distributed tracing & wait events".
"""

import json
import os
import threading
import time

import pytest

from opentenbase_tpu.exec import scheduler as sm
from opentenbase_tpu.exec import shield
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.gtm.server import GtmCore, GtmServer
from opentenbase_tpu.net import guard
from opentenbase_tpu.net.dn_server import DnServer
from opentenbase_tpu.obs import trace as obs_trace
from opentenbase_tpu.obs import xray
from opentenbase_tpu.obs.metrics import REGISTRY
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.utils import faultinject as FI


@pytest.fixture(autouse=True)
def _clean_state():
    """xray keeps process-global registries (flights, activity, guard
    ring, pending remote spans); every test starts and leaves clean."""
    def wipe():
        guard.reset()
        FI.disarm()
        FI.disarm_wire()
        FI.disarm_poison()
        FI.disarm_oom()
        sm.reset_stats()
        shield.reset_stats()
        with xray._FLOCK:
            xray._FLIGHTS.clear()
        with xray._GLOCK:
            xray._GUARD_EVENTS.clear()
        with xray._ALOCK:
            xray._ACTIVITY.clear()
        with xray._RLOCK:
            xray._REMOTE.clear()
    wipe()
    yield
    wipe()


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


def _mk_node(rows: int = 64):
    node = LocalNode()
    s = Session(node)
    s.execute("create table kv (k bigint, v bigint)")
    s.execute("insert into kv values " + ", ".join(
        f"({i}, {i * 7})" for i in range(rows)))
    return node, s


POINT_Q = "select v from kv where k = {}"


# ---------------------------------------------------------------------------
# distributed tracing over a real TCP mini-cluster
# ---------------------------------------------------------------------------

class TestDistributedTrace:
    def _setup(self, s):
        s.execute("create table xkv (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        s.execute("insert into xkv values " + ", ".join(
            f"({i}, {i * 3})" for i in range(64)))

    def test_cross_node_trace_stitched(self, tcp_cluster):
        s, _servers, _gtm, _d = tcp_cluster
        self._setup(s)
        assert s.query("select sum(v) from xkv") == [(sum(
            i * 3 for i in range(64)),)]
        qt = obs_trace.last_trace()
        assert qt is not None
        rows = dict(xray.remote_rows(qt))
        # both datanodes AND the GTM shipped subtrees into ONE tree
        assert "dn0" in rows and "dn1" in rows, rows
        assert "gtm" in rows, rows
        for node in ("dn0", "dn1"):
            a = rows[node]
            assert a["rpcs"] >= 1
            # the server measured real time, and the remote clock can
            # never exceed what the CN observed end-to-end
            assert 0 < a["server_ms"] <= qt.total_ms, (node, a)

    def test_remote_phases_bounded_by_rpc_wall(self, tcp_cluster):
        s, _servers, _gtm, _d = tcp_cluster
        self._setup(s)
        s.query("select v from xkv where k = 7")     # FQS point read
        qt = obs_trace.last_trace()
        # CN-observed wall for all RPC conversations of this query
        rpc_ms = qt.summary()["wait_ms"]
        assert rpc_ms > 0
        for node, a in xray.remote_rows(qt):
            phase_sum = sum(v for k, v in a.items()
                            if k in obs_trace.PHASES)
            server = a.get("server_ms", 0.0)
            assert phase_sum <= server + 1e-6, (node, a)
            assert server <= max(rpc_ms, qt.total_ms) + 1e-6, (node, a)

    def test_explain_analyze_shows_remote_phase_lines(self, tcp_cluster):
        s, _servers, _gtm, _d = tcp_cluster
        self._setup(s)
        r = s.execute("explain analyze select sum(v) from xkv")[0]
        assert "Remote dn0:" in r.text, r.text
        assert "Remote dn1:" in r.text, r.text
        remote = [ln for ln in r.text.splitlines()
                  if ln.startswith("Remote dn")]
        for ln in remote:
            assert "rpcs=" in ln and "server=" in ln, ln

    def test_trace_ids_correlate_slow_log_and_flights(self, tcp_cluster,
                                                      monkeypatch):
        s, _servers, _gtm, _d = tcp_cluster
        self._setup(s)
        import io
        buf = io.StringIO()
        monkeypatch.setattr(obs_trace, "SLOW_MS", 0.0001)
        monkeypatch.setattr(obs_trace, "SLOW_STREAM", buf)
        s.query("select v from xkv where k = 3")
        qt = obs_trace.last_trace()
        assert qt.trace_id
        logged = json.loads(buf.getvalue().splitlines()[-1])
        assert logged["trace_id"] == qt.trace_id
        b = xray.flight("manual", sig="corr-test")
        assert b["trace_id"] == qt.trace_id


# ---------------------------------------------------------------------------
# piggy-back byte discipline
# ---------------------------------------------------------------------------

class TestCompact:
    @staticmethod
    def _tree(width, depth):
        d = {"name": f"s{depth}", "ms": 1.0, "attrs": {"x": "y" * 16}}
        if depth:
            d["children"] = [TestCompact._tree(width, depth - 1)
                             for _ in range(width)]
        return d

    def test_cap_respected_and_lossy_ladder(self):
        big = self._tree(width=6, depth=5)
        assert len(json.dumps(big)) > 8192
        for cap in (8192, 2048, 512):
            out = xray.compact(self._tree(6, 5), cap)
            assert len(json.dumps(out)) <= cap, cap
            assert out["name"]                  # still a span
        # the floor: a root whose own attrs bust the cap degenerates
        # to the bare truncation marker instead of an oversized reply
        fat = self._tree(6, 3)
        fat["attrs"]["note"] = "z" * 500
        out = xray.compact(fat, 120)
        assert out["attrs"].get("truncated") is True
        assert len(json.dumps(out)) <= 120

    def test_small_tree_untouched(self):
        d = self._tree(1, 1)
        assert xray.compact(dict(d), 8192) == d


# ---------------------------------------------------------------------------
# wait events + live activity
# ---------------------------------------------------------------------------

class TestWaitEvents:
    def test_saturated_scheduler_populates_histograms(self):
        node, _ = _mk_node()
        gtm = GtmCore()
        assert gtm.resq_acquire("default", 1, owner="hog", lease_s=60)
        done = []
        with sm.Scheduler(node=node, gtm=gtm, slots=1,
                          shed_timeout_ms=30000.0) as sched:
            t = threading.Thread(
                target=lambda: done.append(
                    sched.run(Session(node), POINT_Q.format(3))),
                daemon=True)
            t.start()
            time.sleep(0.25)         # dispatcher parks on admission
            gtm.resq_release("default", owner="hog")
            t.join(timeout=30)
        assert done and done[0][-1].rows == [(21,)]
        waits = {e: (c, tot) for e, c, tot, _a, _b, _c
                 in xray.wait_rows()}
        assert "sched-admission" in waits, waits
        cnt, tot = waits["sched-admission"]
        assert cnt >= 1 and tot > 100.0, waits   # really stalled
        assert "sched-result" in waits, waits

    def test_nested_waits_restore_outer_register(self):
        ident = threading.get_ident()
        with xray.wait_event("outer-ev"):
            assert xray.current_wait(ident) == "outer-ev"
            with xray.wait_event("inner-ev"):
                assert xray.current_wait(ident) == "inner-ev"
            assert xray.current_wait(ident) == "outer-ev"
        assert xray.current_wait(ident) == ""

    def test_a_wait_inside_a_statement_is_in_its_tree_and_its_wait_ms(self):
        with obs_trace.trace_query("select waits") as qt:
            with obs_trace.span("execute"):
                with xray.wait_event("lockmgr"):
                    time.sleep(0.003)
            with xray.wait_event("gts-grant"):
                with xray.wait_event("rpc-wire"):     # nested: the outer
                    time.sleep(0.002)                 # one covers it
            xray.mark("breaker-open")
        ex = qt.root.children[0]
        (w,) = ex.children
        assert w.name == "wait" and w.attrs == {"event": "lockmgr"}
        assert w.ms >= 3.0
        # on the statement's timeline, inside the span it delayed
        assert ex.t0_ms <= w.t0_ms
        assert w.t0_ms + w.ms <= ex.t0_ms + ex.ms + 1e-3
        waits = [(c.attrs["event"], c.ms) for c in qt.root.children[1:]]
        assert [e for e, _ in waits] == ["gts-grant", "breaker-open"]
        assert waits[1][1] == 0.0
        s = qt.summary()
        assert s["wait_ms"] == pytest.approx(w.ms + waits[0][1])
        assert s["wait_ms"] >= 5.0
        # and still in the global histogram, the nested one too
        seen = {e: c for e, c, *_ in xray.wait_rows()}
        assert {"lockmgr", "gts-grant", "rpc-wire", "breaker-open"} \
            <= set(seen)

    def test_a_wait_outside_any_statement_only_feeds_the_histogram(self):
        assert not obs_trace.active()
        with xray.wait_event("bufpool-evict"):
            pass
        assert not obs_trace.active()
        assert "bufpool-evict" in {e for e, *_ in xray.wait_rows()}

    def test_stat_activity_live_then_empty(self):
        node, _ = _mk_node()
        gtm = GtmCore()
        assert gtm.resq_acquire("default", 1, owner="hog", lease_s=60)
        with sm.Scheduler(node=node, gtm=gtm, slots=1,
                          shed_timeout_ms=30000.0) as sched:
            t = threading.Thread(
                target=lambda: sched.run(Session(node),
                                         POINT_Q.format(5)),
                daemon=True)
            t.start()
            time.sleep(0.25)
            rows = xray.activity_rows()
            assert len(rows) == 1, rows
            aid, state, wait_ev, age_ms, cancelable, _tid, sql = rows[0]
            assert state == "queued"
            assert wait_ev == "sched-result"   # submitter parked
            assert age_ms > 100.0
            assert "kv" in sql
            gtm.resq_release("default", owner="hog")
            t.join(timeout=30)
        assert xray.activity_rows() == []      # end drains the view

    def test_stat_views_queryable_in_sql(self):
        with xray.wait_event("view-probe"):
            pass
        cluster = Cluster(n_datanodes=2)
        s = ClusterSession(cluster)
        rows = s.query("select event, count, total_ms, p50_ms "
                       "from otb_wait_events")
        events = {r[0] for r in rows}
        assert "view-probe" in events, events
        assert all(r[1] >= 0 and r[2] >= 0 for r in rows)
        # no statement is live inside the serving tier right now
        assert s.query("select aid from otb_stat_activity") == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_bundle_on_quarantine(self, tmp_path, monkeypatch):
        monkeypatch.setattr(xray, "FLIGHT_DIR", str(tmp_path / "fl"))
        node, _ = _mk_node()
        node.gucs["enable_work_sharing"] = "off"
        FI.arm_poison(5)
        with sm.Scheduler(node=node, window_ms=300.0) as sched:
            for _round in range(2):          # threshold: 2 failures
                items = [sched.submit(Session(node), POINT_Q.format(q))
                         for q in (5, 9)]
                errs = []
                for it in items:
                    try:
                        sched.wait(it)
                        errs.append(None)
                    except Exception as e:   # noqa: BLE001
                        errs.append(e)
                assert errs[0] is not None and errs[1] is None
        kinds = [b["kind"] for b in xray.flights()]
        assert "quarantine" in kinds, kinds
        b = next(b for b in xray.flights() if b["kind"] == "quarantine")
        # the barred batch's signature (a digest), the same one the guard
        # transition recorded
        assert b["signature"]
        assert isinstance(b["counters"], dict)
        assert any(g["kind"] == "quarantine"
                   and g.get("sig") == b["signature"][:80]
                   for g in b["guard_events"])
        # persisted: every bundle on disk parses back
        files = sorted(os.listdir(tmp_path / "fl"))
        assert any("quarantine" in f for f in files), files
        for f in files:
            with open(tmp_path / "fl" / f) as fh:
                assert json.load(fh)["event"] == "flight"

    def test_bundle_on_statement_timeout(self, tmp_path, monkeypatch):
        monkeypatch.setattr(xray, "FLIGHT_DIR", str(tmp_path / "fl"))
        node, _ = _mk_node()
        node.gucs["statement_timeout"] = "200"
        gtm = GtmCore()
        assert gtm.resq_acquire("default", 1, owner="hog", lease_s=60)
        with sm.Scheduler(node=node, gtm=gtm, slots=1,
                          shed_timeout_ms=30000.0) as sched:
            with pytest.raises(ExecError, match="statement timeout"):
                sched.run(Session(node), POINT_Q.format(1))
        gtm.resq_release("default", owner="hog")
        bundles = [b for b in xray.flights()
                   if b["kind"] == "statement_timeout"]
        assert bundles, [b["kind"] for b in xray.flights()]
        assert "kv" in bundles[0]["signature"]
        files = os.listdir(tmp_path / "fl")
        assert any("statement_timeout" in f for f in files), files

    def test_ring_bounded_and_json_clean(self):
        cap = xray._FLIGHTS.maxlen
        for i in range(cap + 8):
            assert xray.flight("ring-test", sig=f"s{i}") is not None
        got = xray.flights()
        assert len(got) == cap
        # newest kept, oldest dropped
        assert got[-1]["signature"] == f"s{cap + 7}"
        assert got[0]["signature"] == "s8"
        for b in got:
            json.loads(json.dumps(b))          # round-trips clean

    def test_cn_flight_wire_op(self):
        from opentenbase_tpu.net.cn_server import CnClient, CnServer
        node, _ = _mk_node()
        srv = CnServer(lambda: Session(node)).start()
        try:
            xray.flight("wire-test", sig="over-the-wire")
            c = CnClient(srv.host, srv.port)
            got = c.flight()
            assert any(b["kind"] == "wire-test" for b in got), got
            c.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# the disabled path
# ---------------------------------------------------------------------------

class TestDisabledPath:
    def test_null_fast_path_semantics(self, monkeypatch):
        monkeypatch.setattr(obs_trace, "ENABLED", False)
        msg = {"op": "execute"}
        assert xray.inject(msg) is msg
        assert "_xray" not in msg              # untouched, no context
        xray.absorb({"ok": 1}, node="dn0")     # no-op, no error
        sx = xray.server_span(msg, "execute", node="dn0")
        with sx:
            assert sx.root is None             # no span opened
        resp = {"ok": 1}
        sx.attach(resp)
        assert "_xray" not in resp

    def test_disabled_overhead_under_3pct_of_point_p50(self, monkeypatch):
        node, s = _mk_node()
        q = POINT_Q.format(3)
        for _ in range(3):                     # warm: compile + pool
            s.execute(q)
        lat = []
        for _ in range(30):
            t0 = time.perf_counter()
            s.execute(q)
            lat.append(time.perf_counter() - t0)
        p50_s = sorted(lat)[len(lat) // 2]

        monkeypatch.setattr(obs_trace, "ENABLED", False)
        msg = {"op": "execute", "sql": q}
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            xray.inject(msg)
            xray.absorb(msg, node="dn0", op="execute")
            sx = xray.server_span(msg, "execute", node="dn0")
            sx.open()
            sx.close()
            sx.attach(msg)
        per_trio_s = (time.perf_counter() - t0) / n
        # a TCP point op runs ~4 such client+server context trios
        # (DN rpc, GTM gts, plus slack); the disabled path must cost
        # under 3% of the cheapest real execution
        assert per_trio_s * 4 < 0.03 * p50_s, (per_trio_s, p50_s)


# ---------------------------------------------------------------------------
# Prometheus exposition hygiene
# ---------------------------------------------------------------------------

class TestMetricsEscaping:
    def test_label_values_escape_cleanly(self):
        REGISTRY.counter("otb_xray_escape_probe_total",
                         q='say "hi"\\ and\nnewline').inc()
        text = REGISTRY.text()
        lines = [ln for ln in text.splitlines()
                 if ln.startswith("otb_xray_escape_probe_total{")]
        assert len(lines) == 1, lines          # newline did NOT split it
        ln = lines[0]
        assert '\\"hi\\"' in ln, ln            # quote escaped
        assert "\\\\ and" in ln, ln            # backslash escaped
        assert "\\nnewline" in ln, ln          # newline escaped
