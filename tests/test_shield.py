"""Serving-tier fault isolation (exec/shield.py + scheduler wiring):

- poisoned-batch matrix: one bad member in a coalesced dispatch fails
  ALONE after bisection; the K-1 innocents return bit-identical rows to
  serial execution, and no admission slot leaks;
- repeat-offender quarantine: a signature that keeps killing batches is
  barred from coalescing for the cooldown (serial lane still serves
  it — and still attributes the failure to the offender);
- statement deadlines: statement_timeout covers the queue wait (expire
  in place, slot never acquired), the scheduler wait (detach without
  sinking batch-mates), and cancel events propagate into queued items;
- memory pressure: RESOURCE_EXHAUSTED at dispatch evicts-and-retries
  once, then degrades members to the spill tier — an answer, not an
  error;
- slot-discipline: acquired == released across success/shed/cancel/
  poison/GTM-failure paths, and the GTM's own lease ledger agrees;
- the idle-cancel race in the CN server: a cancel landing between
  query receipt and execution start must be honored, not dropped.
"""

import threading
import time

import pytest

from opentenbase_tpu.exec import scheduler as sm
from opentenbase_tpu.exec import shield
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.gtm.server import GtmCore
from opentenbase_tpu.utils import faultinject as FI


@pytest.fixture(autouse=True)
def _fresh():
    sm.reset_stats()
    shield.reset_stats()
    FI.disarm_poison()
    FI.disarm_oom()
    yield
    sm.reset_stats()
    shield.reset_stats()
    FI.disarm_poison()
    FI.disarm_oom()


def _mk_node(rows: int = 64):
    node = LocalNode()
    s = Session(node)
    s.execute("create table kv (k bigint, v bigint)")
    s.execute("insert into kv values " + ", ".join(
        f"({i}, {i * 7})" for i in range(rows)))
    return node, s


POINT_Q = "select v from kv where k = {}"


def _submit_window(sched, node, sqls):
    """Submit in ORDER from one thread while the dispatcher's window is
    open — deterministic batch membership AND batch position."""
    items = [sched.submit(Session(node), q) for q in sqls]
    outs, errs = [], []
    for it in items:
        try:
            outs.append(sched.wait(it)[-1].rows)
            errs.append(None)
        except Exception as e:      # noqa: BLE001 — asserted by caller
            outs.append(None)
            errs.append(e)
    return outs, errs


class TestPoisonedBatchMatrix:
    """K in {2, 8, 16} x offender position first/middle/last: the
    poisoned member errors, every innocent is bit-identical to serial,
    and the admission ledger drains balanced."""

    @pytest.mark.parametrize("k", [2, 8, 16])
    @pytest.mark.parametrize("pos", ["first", "middle", "last"])
    def test_matrix(self, k, pos):
        node, _ = _mk_node()
        keys = list(range(3, 3 + k))
        sqls = [POINT_Q.format(i) for i in keys]
        ref = [Session(node).execute(q)[-1].rows for q in sqls]
        bad = {"first": 0, "middle": k // 2, "last": k - 1}[pos]
        FI.arm_poison(keys[bad])    # persists: serial re-run must fail
        with sm.Scheduler(node=node, window_ms=400.0,
                          max_batch=16) as sched:
            outs, errs = _submit_window(sched, node, sqls)
        for i in range(k):
            if i == bad:
                assert errs[i] is not None
                assert "poison-literal" in str(errs[i])
            else:
                assert errs[i] is None, errs[i]
                assert outs[i] == ref[i]
        st = shield.stats_snapshot()
        assert st["batch_failures"] >= 1
        assert st["isolated"] >= 1
        sm.assert_slot_balance()

    def test_innocents_stay_batched_on_the_way_down(self):
        """K=8, one offender: bisection re-dispatches halves, so some
        innocents still complete through a BATCHED dispatch."""
        node, _ = _mk_node()
        sqls = [POINT_Q.format(i) for i in range(10, 18)]
        ref = [Session(node).execute(q)[-1].rows for q in sqls]
        FI.arm_poison(10)
        with sm.Scheduler(node=node, window_ms=400.0,
                          max_batch=16) as sched:
            outs, errs = _submit_window(sched, node, sqls)
        assert [e is not None for e in errs].count(True) == 1
        assert outs[1:] == ref[1:]
        assert sm.stats_snapshot()["batched"] >= 2
        sm.assert_slot_balance()


class TestQuarantine:
    def test_repeat_offender_barred_then_serial(self):
        node, _ = _mk_node()
        # quarantine needs BOTH rounds to dispatch as 2-member batches;
        # the result cache would serve the innocent at submit in round 2
        node.gucs["enable_work_sharing"] = "off"
        FI.arm_poison(5)
        with sm.Scheduler(node=node, window_ms=300.0) as sched:
            for _round in range(2):      # threshold: 2 failures
                _, errs = _submit_window(
                    sched, node, [POINT_Q.format(5), POINT_Q.format(9)])
                assert errs[0] is not None and errs[1] is None
            st = shield.stats_snapshot()
            assert st["quarantined"] == 1
            assert st["quarantine_active"] == 1
            # barred: the next pair classifies to the serial lane —
            # innocent fine, offender STILL attributed
            before = sm.stats_snapshot()["batch_dispatches"]
            outs, errs = _submit_window(
                sched, node, [POINT_Q.format(5), POINT_Q.format(9)])
            assert errs[0] is not None and "poison-literal" in str(errs[0])
            assert errs[1] is None
            assert sm.stats_snapshot()["batch_dispatches"] == before
            assert shield.stats_snapshot()["quarantine_hits"] >= 1
        sm.assert_slot_balance()


class TestStatementDeadlines:
    def test_queued_statement_expires_in_place(self):
        """statement_timeout fires while the query waits for a slot a
        hog holds: timeout error, and the slot is NEVER acquired."""
        node, _ = _mk_node()
        node.gucs["statement_timeout"] = "200"
        gtm = GtmCore()
        assert gtm.resq_acquire("default", 1, owner="hog", lease_s=60)
        with sm.Scheduler(node=node, gtm=gtm, slots=1,
                          shed_timeout_ms=30000.0) as sched:
            t0 = time.monotonic()
            with pytest.raises(ExecError, match="statement timeout"):
                sched.run(Session(node), POINT_Q.format(1))
            took = time.monotonic() - t0
        assert took < 5.0            # the 600s wait and the 30s shed
        assert sm.stats_snapshot()["expired"] == 1
        acq, rel = sm.slot_balance()
        assert acq == 0 and rel == 0
        gtm.resq_release("default", owner="hog")

    def test_deadline_bounds_scheduler_wait(self):
        """wait()'s 600s dispatch timeout is clamped by the statement
        deadline — a parked item returns at the deadline, not at 600s
        (and not at the shed timeout either)."""
        node, _ = _mk_node()
        node.gucs["statement_timeout"] = "150"
        gtm = GtmCore()
        assert gtm.resq_acquire("default", 1, owner="hog", lease_s=60)
        sched = sm.Scheduler(node=node, gtm=gtm, slots=1,
                             shed_timeout_ms=30000.0)
        try:
            item = sched.submit(Session(node), POINT_Q.format(1))
            t0 = time.monotonic()
            with pytest.raises(ExecError, match="statement timeout"):
                sched.wait(item)
            assert time.monotonic() - t0 < 5.0
        finally:
            sched.stop()
            gtm.resq_release("default", owner="hog")
        sm.assert_slot_balance()

    def test_cancel_propagates_into_queued_item(self):
        node, _ = _mk_node()
        gtm = GtmCore()
        assert gtm.resq_acquire("default", 1, owner="hog", lease_s=60)
        sched = sm.Scheduler(node=node, gtm=gtm, slots=1,
                             shed_timeout_ms=30000.0)
        try:
            sess = Session(node)
            item = sched.submit(sess, POINT_Q.format(1))
            sess.cancel_event.set()
            with pytest.raises(ExecError, match="due to user request"):
                sched.wait(item)
        finally:
            sched.stop()
            gtm.resq_release("default", owner="hog")
        assert sm.stats_snapshot()["canceled"] == 1
        acq, rel = sm.slot_balance()
        assert acq == 0 and rel == 0

    def test_expired_member_does_not_sink_batch_mates(self):
        """One member of a coalescing group times out while queued;
        the survivors still dispatch and answer correctly."""
        node, _ = _mk_node()
        with sm.Scheduler(node=node, window_ms=300.0) as sched:
            fast = Session(node)
            node.gucs["statement_timeout"] = "1"
            doomed = sched.submit(Session(node), POINT_Q.format(2))
            node.gucs["statement_timeout"] = ""
            time.sleep(0.05)         # let the deadline lapse in-queue
            ok = sched.submit(fast, POINT_Q.format(4))
            with pytest.raises(ExecError, match="statement timeout"):
                sched.wait(doomed)
            assert sched.wait(ok)[-1].rows == [(28,)]
        sm.assert_slot_balance()


class TestMemoryPressure:
    def test_oom_evict_retry_then_degrade(self):
        """Two consecutive injected OOMs defeat the evict-and-retry
        pass: every member degrades to the spill path and still gets
        the right answer."""
        node, _ = _mk_node()
        sqls = [POINT_Q.format(i) for i in (20, 21, 22, 23)]
        ref = [Session(node).execute(q)[-1].rows for q in sqls]
        FI.arm_oom("dispatch", times=2)
        with sm.Scheduler(node=node, window_ms=400.0) as sched:
            outs, errs = _submit_window(sched, node, sqls)
        assert errs == [None] * 4
        assert outs == ref
        st = shield.stats_snapshot()
        assert st["oom_dispatches"] == 1
        assert st["oom_retries"] == 1
        assert st["degraded"] == 4
        sm.assert_slot_balance()

    def test_single_oom_recovers_via_retry(self):
        """One injected OOM: pressure relief + one retry serves the
        batch NORMALLY (no degradation)."""
        node, _ = _mk_node()
        sqls = [POINT_Q.format(i) for i in (30, 31)]
        ref = [Session(node).execute(q)[-1].rows for q in sqls]
        FI.arm_oom("dispatch", times=1)
        with sm.Scheduler(node=node, window_ms=400.0) as sched:
            outs, errs = _submit_window(sched, node, sqls)
        assert errs == [None, None]
        assert outs == ref
        st = shield.stats_snapshot()
        assert st["oom_retries"] == 1
        assert st["degraded"] == 0
        sm.assert_slot_balance()

    def test_shed_coldest_frees_bytes(self):
        from opentenbase_tpu.storage.bufferpool import POOL
        node, s = _mk_node()
        s.execute("select sum(v) from kv")     # stage something
        live = POOL.totals()["bytes_live"]
        if live == 0:
            pytest.skip("nothing staged on this backend")
        freed = POOL.shed_coldest(1.0)
        assert freed > 0
        assert POOL.totals()["bytes_live"] < live


class TestSlotDiscipline:
    def test_gtm_failure_mid_acquire_is_balanced(self):
        """resq_acquire raising (GTM connection lost) surfaces the
        error, holds nothing, and the next statement works."""
        node, _ = _mk_node()
        gtm = GtmCore()
        orig = gtm.resq_acquire
        state = {"boom": 1}

        def flaky(*a, **kw):
            if state["boom"]:
                state["boom"] -= 1
                raise RuntimeError("GTM connection lost")
            return orig(*a, **kw)

        gtm.resq_acquire = flaky
        with sm.Scheduler(node=node, gtm=gtm) as sched:
            with pytest.raises(RuntimeError, match="GTM connection"):
                sched.run(Session(node), POINT_Q.format(1))
            assert sched.run(Session(node),
                             POINT_Q.format(1))[-1].rows == [(7,)]
        sm.assert_slot_balance()
        assert sum(gtm.resq_counts().values()) == 0
        st = gtm.resq_stats()
        assert st["acquired"] == st["released"] + st["expired"]

    def test_storm_drains_balanced(self):
        """Concurrent mix of clean, poisoned, and canceled statements:
        acquired == released, GTM slot table empty, innocents right."""
        node, _ = _mk_node()
        FI.arm_poison(40)
        ref = {i: Session(node).execute(
            POINT_Q.format(i))[-1].rows for i in range(36, 48)}
        results = {}
        lock = threading.Lock()

        def client(i, sess):
            try:
                rows = sched.run(sess, POINT_Q.format(i))[-1].rows
                with lock:
                    results[i] = ("ok", rows)
            except Exception as e:   # noqa: BLE001 — classified below
                with lock:
                    results[i] = ("err", str(e))

        with sm.Scheduler(node=node, window_ms=30.0) as sched:
            sessions = {i: Session(node) for i in range(36, 48)}
            threads = [threading.Thread(target=client,
                                        args=(i, sessions[i]))
                       for i in sessions]
            for t in threads:
                t.start()
            sessions[44].cancel_event.set()   # cancel storm sample
            sessions[46].cancel_event.set()
            for t in threads:
                t.join()
        for i, (kind, val) in results.items():
            if i == 40:
                assert kind == "err" and "poison-literal" in val
            elif i in (44, 46):
                # canceled sessions either finished first or canceled
                if kind == "err":
                    assert "user request" in val
            else:
                assert kind == "ok" and val == ref[i], (i, kind, val)
        sm.assert_slot_balance()
        gtm = sched.gtm
        assert sum(gtm.resq_counts().values()) == 0
        st = gtm.resq_stats()
        assert st["acquired"] == st["released"] + st["expired"]


class TestGtmLeaseLedger:
    def test_expired_lease_is_accounted(self):
        gtm = GtmCore()
        assert gtm.resq_acquire("g", 4, owner="w1", lease_s=0.01)
        time.sleep(0.05)
        assert gtm.resq_counts().get("g", 0) == 0   # reaped
        st = gtm.resq_stats()
        assert st == {"acquired": 1, "released": 0, "expired": 1,
                      "live": 0}

    def test_disconnect_counts_as_release(self):
        gtm = GtmCore()
        assert gtm.resq_acquire("g", 4, owner="w1", lease_s=60)
        assert gtm.resq_disconnect("w1") == 1
        st = gtm.resq_stats()
        assert st["released"] == 1 and st["live"] == 0


class TestCnServerCancelRace:
    def test_cancel_between_receive_and_execute(self, monkeypatch):
        """The fixed race: a cancel arriving AFTER the query message is
        read but BEFORE execution starts must cancel that statement
        (the old code cleared the flag in that window, dropping it)."""
        from opentenbase_tpu.net import cn_server as cn
        node, _ = _mk_node()
        # the server reads a frame, then decodes it inside the
        # statement's trace: the window opens once the frame is read
        real_decode = cn.decode_msg
        got_query = threading.Event()
        cancel_landed = threading.Event()

        def gated_decode(blob):
            msg = real_decode(blob)
            if isinstance(msg, dict) and msg.get("op") == "query":
                got_query.set()
                cancel_landed.wait(timeout=10)
            return msg

        monkeypatch.setattr(cn, "decode_msg", gated_decode)
        srv = cn.CnServer(lambda: Session(node)).start()
        try:
            cli = cn.CnClient(srv.host, srv.port)
            err = []

            def go():
                try:
                    cli.execute(POINT_Q.format(1))
                    err.append(None)
                except Exception as e:    # noqa: BLE001
                    err.append(str(e))

            t = threading.Thread(target=go)
            t.start()
            assert got_query.wait(timeout=10)
            assert cli.cancel()           # lands in the race window
            cancel_landed.set()
            t.join(timeout=30)
            assert err and err[0] is not None
            assert "user request" in err[0]
            # the session survives: next statement runs clean
            assert cli.query(POINT_Q.format(2)) == [(14,)]
            cli.close()
        finally:
            srv.stop()

    def test_stale_cancel_is_dropped_at_idle_clear(self, monkeypatch):
        """A cancel consumed BEFORE the loop returns to its idle point
        (here: while the previous statement's response is in flight)
        does not poison the next statement."""
        from opentenbase_tpu.net import cn_server as cn
        node, _ = _mk_node()
        real_send = cn.send_msg
        state = {"armed": True}
        resp_gated = threading.Event()
        cancel_landed = threading.Event()

        def gated_send(sock, msg):
            if state["armed"] and isinstance(msg.get("ok"), list):
                state["armed"] = False
                resp_gated.set()
                cancel_landed.wait(timeout=10)
            return real_send(sock, msg)

        monkeypatch.setattr(cn, "send_msg", gated_send)
        srv = cn.CnServer(lambda: Session(node)).start()
        try:
            cli = cn.CnClient(srv.host, srv.port)
            out = []
            t = threading.Thread(
                target=lambda: out.append(cli.query(POINT_Q.format(1))))
            t.start()
            assert resp_gated.wait(timeout=10)
            assert cli.cancel()      # lands before the idle clear
            cancel_landed.set()
            t.join(timeout=30)
            assert out == [[(7,)]]
            assert cli.query(POINT_Q.format(2)) == [(14,)]
            cli.close()
        finally:
            srv.stop()


class TestShieldView:
    def test_otb_shield_view(self):
        from opentenbase_tpu.exec.dist_session import ClusterSession
        from opentenbase_tpu.parallel.cluster import Cluster
        shield.bump("degraded")
        cs = ClusterSession(Cluster(n_datanodes=2))
        rows = cs.query("select degraded, quarantine_active, "
                        "oom_retries from otb_shield")
        assert len(rows) == 1
        assert rows[0][0] >= 1 and rows[0][1] == 0
