"""Observability subsystem proof (obs/): span trees, the unified
metrics registry, trace-backed stat views, EXPLAIN ANALYZE actuals on
both execution tiers, and the warm-query staging story (stage ~ 0 with
a 100% buffer-pool hit rate once tables are device-resident).

Reference analog: the instrument.c / EXPLAIN ANALYZE plumbing plus the
pg_stat_* view family, exercised the way pg_regress drives them.
"""

import io
import json
import re
import threading
import time

import numpy as np
import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.obs import metrics as obs_metrics
from opentenbase_tpu.obs import trace as obs_trace
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.tpch import datagen
from opentenbase_tpu.tpch.queries import Q
from opentenbase_tpu.tpch.schema import SCHEMA


# ---------------------------------------------------------------------------
# span primitives (no engine involved)
# ---------------------------------------------------------------------------

class TestSpans:
    def test_disabled_fast_path_is_shared_singleton(self):
        # no active trace on this thread: span() must return the one
        # shared no-op instance — zero allocation on the hot path
        assert obs_trace.span("execute") is obs_trace.NULL_SPAN
        assert obs_trace.span("stage", table="t") is obs_trace.NULL_SPAN
        obs_trace.event("pool", hit=True)       # no-ops, no error
        obs_trace.annotate(rows=3)
        with obs_trace.span("x") as sp:
            assert sp is obs_trace.NULL_SPAN
            assert sp.set(rows=1) is sp

    def test_trace_disabled_globally(self, monkeypatch):
        monkeypatch.setattr(obs_trace, "ENABLED", False)
        with obs_trace.trace_query("select 1") as qt:
            assert qt is None
            assert obs_trace.span("execute") is obs_trace.NULL_SPAN
            assert obs_trace.current_trace() is None

    def test_nesting_and_phase_semantics(self):
        with obs_trace.trace_query("q") as qt:
            with obs_trace.span("execute", tier="single"):
                with obs_trace.span("execute", tier="fused"):
                    time.sleep(0.002)
                obs_trace.event("pool", hit=True)
                obs_trace.event("pool", hit=False)
            with obs_trace.span("finalize") as sp:
                sp.set(bytes=128, rows=4)
        root = qt.root
        assert [c.name for c in root.children] == ["execute", "finalize"]
        inner = root.children[0].children
        assert inner[0].name == "execute"
        assert {c.name for c in inner[1:]} == {"pool"}
        # nested same-name spans count ONCE (the outermost)
        assert qt.phase_ms("execute") == pytest.approx(
            root.children[0].ms)
        assert qt.phase_ms("execute") >= inner[0].ms
        assert qt.sum_attr("finalize", "bytes") == 128
        assert qt.count_events("pool", hit=True) == 1
        assert qt.count_events("pool") == 2
        s = qt.summary()
        assert s["pool_hits"] == 1 and s["pool_misses"] == 1
        assert s["total_ms"] >= s["execute_ms"] > 0
        # after exit: the thread stack is gone again
        assert not obs_trace.active()
        assert obs_trace.span("x") is obs_trace.NULL_SPAN

    def test_nested_statement_joins_outer_trace(self):
        with obs_trace.trace_query("outer") as qt1:
            with obs_trace.trace_query("inner") as qt2:
                assert qt2 is qt1
                obs_trace.event("program", hit=True)
        # only the OWNING context finished the trace (one ring entry)
        assert obs_trace.last_trace() is qt1
        assert qt1.count_events("program", hit=True) == 1

    def test_thread_isolation(self):
        out = {}

        def worker(name):
            with obs_trace.trace_query(name) as qt:
                with obs_trace.span("execute", who=name):
                    time.sleep(0.001)
                out[name] = qt

        ts = [threading.Thread(target=worker, args=(f"t{i}",))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len({id(q) for q in out.values()}) == 4
        for name, qt in out.items():
            assert qt.signature == name
            assert [c.attrs.get("who") for c in qt.root.children] == [name]
        recents = {q.signature for q in obs_trace.recent()}
        assert {"t0", "t1", "t2", "t3"} <= recents

    def test_slow_query_log(self, monkeypatch):
        buf = io.StringIO()
        monkeypatch.setattr(obs_trace, "SLOW_MS", 0.0001)
        monkeypatch.setattr(obs_trace, "SLOW_STREAM", buf)
        with obs_trace.trace_query("select pg_sleep") as qt:
            with obs_trace.span("execute"):
                time.sleep(0.002)
            qt.rows = 7
        lines = [ln for ln in buf.getvalue().splitlines() if ln]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["event"] == "slow_query"
        assert rec["signature"] == "select pg_sleep"
        assert rec["rows"] == 7 and rec["total_ms"] > 0

    def test_ring_is_bounded(self):
        for i in range(obs_trace.RING_CAP + 5):
            with obs_trace.trace_query(f"r{i}"):
                pass
        assert len(obs_trace.recent()) == obs_trace.RING_CAP

    def test_span_exports_start_and_duration_inside_its_parent(self):
        with obs_trace.trace_query("q") as qt:
            time.sleep(0.001)
            with obs_trace.span("execute"):
                time.sleep(0.001)
                with obs_trace.span("stage", table="t"):
                    time.sleep(0.001)
                obs_trace.event("pool", hit=True)
            with obs_trace.span("finalize"):
                time.sleep(0.001)
        d = qt.to_dict()["spans"]
        assert d["name"] == "query" and d["t0_ms"] == 0.0

        def inside(parent, lo, hi):
            for c in parent.get("children", ()):
                # name, start and duration are exported; the tree gives
                # the parent, and a child lies inside it
                assert {"name", "t0_ms", "ms"} <= set(c)
                assert lo - 1e-3 <= c["t0_ms"]
                assert c["t0_ms"] + c["ms"] <= hi + 1e-3, (c, hi)
                inside(c, c["t0_ms"], c["t0_ms"] + c["ms"])

        inside(d, 0.0, d["ms"])
        ex, fin = d["children"]
        assert ex["t0_ms"] >= 1.0                  # after the first sleep
        assert fin["t0_ms"] >= ex["t0_ms"] + ex["ms"]   # siblings in order
        stage, pool = ex["children"]
        assert pool["ms"] == 0.0 and pool["t0_ms"] >= stage["t0_ms"]
        # every span of the statement shares its trace_id
        assert qt.summary()["trace_id"] == qt.trace_id
        # a shipped subtree comes back with its starts
        back = obs_trace.span_from_dict(ex)
        assert back.t0_ms == ex["t0_ms"]
        assert back.children[0].t0_ms == stage["t0_ms"]

    def test_self_ms_of_a_hand_built_tree(self):
        def mk(name, t0, ms, *children):
            sp = obs_trace.Span(name)
            sp.t0_ms, sp.ms = t0, ms
            sp.children = list(children)
            return sp

        qt = obs_trace.QueryTrace("hand")
        # root [0,100): parse [2,5) execute [10,60) finalize [60,95);
        # execute holds stage [12,20) and an inner execute [30,50);
        # finalize holds fetch [61,90) and a grafted remote [85,99) that
        # overlaps fetch and sticks out of its parent
        qt.root = mk(
            "query", 0, 100,
            mk("parse", 2, 3),
            mk("execute", 10, 50,
               mk("stage", 12, 8), mk("execute", 30, 20)),
            mk("finalize", 60, 35,
               mk("finalize.fetch", 61, 29), mk("remote", 85, 14)))
        parse, execute, finalize = qt.root.children
        assert qt.root.self_ms() == pytest.approx(100 - 3 - 50 - 35)
        assert parse.self_ms() == pytest.approx(3)
        # the outer execute less its two children; the inner has none
        assert execute.self_ms() == pytest.approx(50 - 8 - 20)
        assert execute.children[1].self_ms() == pytest.approx(20)
        # children cover [61,95) of finalize's [60,95): the overlap
        # counts once and what sticks out does not count
        assert finalize.self_ms() == pytest.approx(1)
        s = qt.summary()
        assert s["unattributed_ms"] == pytest.approx(12)
        assert s["parse_ms"] == 3 and s["execute_ms"] == 50
        assert s["finalize_fetch_ms"] == 29

    def test_summary_keeps_its_keys_and_gains_the_layers(self):
        with obs_trace.trace_query("q") as qt:
            with obs_trace.span("finalize") as sp:
                sp.set(bytes=64)
                with obs_trace.span("finalize.fetch") as f:
                    f.set(fetches=3, bytes=96)
        s = qt.summary()
        # the keys the ledger's metrics read by name, unchanged
        assert {"qid", "trace_id", "signature", "tier", "total_ms", "rows",
                "bytes_staged", "bytes_materialized", "pool_hits",
                "pool_misses", "plan_ms", "stage_ms", "execute_ms",
                "exchange_ms", "finalize_ms", "stage_wait_ms"} <= set(s)
        assert {"wire_ms", "parse_ms", "autoprep_ms", "wait_ms",
                "finalize_gather_ms", "finalize_fetch_ms",
                "finalize_decode_ms", "finalize_fetches",
                "finalize_fetch_bytes", "unattributed_ms"} <= set(s)
        assert s["bytes_materialized"] == 64
        assert s["finalize_fetches"] == 3
        assert s["finalize_fetch_bytes"] == 96
        for ph in obs_trace.PHASES:
            assert s[f"{ph}_ms"] == pytest.approx(qt.phase_ms(ph))

    def test_disabled_tracing_allocates_nothing(self, monkeypatch):
        monkeypatch.setattr(obs_trace, "ENABLED", False)
        made = []
        real = obs_trace.Span.__init__

        def counting(self, *a, **kw):
            made.append(self)
            real(self, *a, **kw)

        monkeypatch.setattr(obs_trace.Span, "__init__", counting)
        ctx = obs_trace.trace_query("select 1")
        assert ctx is obs_trace.trace_query("select 2")     # one shared
        with ctx as qt:
            assert qt is None
            for name in ("wire.recv", "parse", "autoprep", "finalize",
                         "finalize.gather", "finalize.fetch",
                         "finalize.decode", "wire.send", "inputs",
                         "gather", "release"):
                with obs_trace.span(name) as sp:
                    assert sp is obs_trace.NULL_SPAN
                    sp.set(bytes=1, d2h=1, h2d=1, calls=1)
            obs_trace.event("pool", hit=True)
            obs_trace.record("wait", 1.0, event="lockmgr")
            obs_trace.count(calls=1, d2h=3)     # lands nowhere
            with obs_trace.adopt(qt) as adopted:
                assert adopted is None
        assert made == []
        assert not obs_trace.active()
        # the no-op span holds nothing: no clock reading, no attribute
        assert obs_trace._NullSpan.__slots__ == ()
        assert not hasattr(obs_trace.NULL_SPAN, "cpu_ms")

    def test_a_real_span_is_an_annotation_on_the_profilers_clock(
            self, tmp_path):
        # a profiler session started by anyone records the spans, under
        # otb:<name>, on the host plane of the trace it writes
        import glob

        import jax
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs_trace.trace_query("q"):
                with obs_trace.span("execute"):
                    with obs_trace.span("finalize.fetch"):
                        time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()
        pb = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(pb[0])
        names = {e.name for p in data.planes if p.name.startswith("/host:")
                 for ln in p.lines for e in ln.events
                 if e.name.startswith("otb:")}
        assert {"otb:query", "otb:execute", "otb:finalize.fetch"} <= names


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def _spans(sp):
    yield sp
    for c in sp.children:
        yield from _spans(c)


def _cpu_tick_ms():
    """The thread CPU clock's step: nanoseconds on most hosts, 10 ms on
    one whose kernel accounts CPU in ticks (PERF.md, PR 36); there a
    span's reading may pass its wall time by a tick."""
    seen = set()
    end = time.perf_counter() + 0.03
    while time.perf_counter() < end:
        seen.add(obs_trace.thread_cpu())
    seen = sorted(seen)
    return min((b - a for a, b in zip(seen, seen[1:])), default=0.03) * 1e3


def _busy(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _burn(seconds):
    """Spin until this thread has SPENT `seconds` of CPU (a loaded
    machine stretches the wall time, not this)."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


class TestCpuBesideWall:
    """The serving thread's CPU beside the statement's wall time, and
    the host<->device counters of `summary()` (PR 36)."""

    @pytest.mark.parametrize("kind", ["sleep", "busy"])
    def test_a_sleep_is_off_cpu_and_a_busy_loop_is_cpu(self, kind):
        with obs_trace.trace_query("q") as qt:
            with obs_trace.span("execute"):
                (time.sleep if kind == "sleep" else _burn)(0.03)
        st = qt.summary()
        assert st["total_ms"] >= 29
        assert st["cpu_ms"] == qt.cpu_ms
        assert st["offcpu_ms"] == pytest.approx(
            st["total_ms"] - st["cpu_ms"])
        if kind == "sleep":
            if _cpu_tick_ms() > 0.5:
                pytest.skip("a tick-granular CPU clock says this of a "
                            "mean only")
            assert st["cpu_ms"] < 10 and st["offcpu_ms"] > 20
        else:
            # what the loop burnt is in it, however long the machine
            # made it wait for a core meanwhile
            assert st["cpu_ms"] >= 29 - _cpu_tick_ms()

    def test_cpu_is_within_wall_on_every_finished_trace(self):
        tick = _cpu_tick_ms()
        slack = tick if tick > 0.5 else 0.0     # none on a fine clock
        for work in (0.0, 0.002, 0.01):
            for _ in range(20):
                with obs_trace.trace_query("q") as qt:
                    with obs_trace.span("parse"):
                        _busy(work / 2)
                    with obs_trace.span("execute"):
                        time.sleep(work / 2)
                    obs_trace.record("wait", 1.5, event="lockmgr")
                st = qt.summary()
                assert 0 <= st["cpu_ms"] <= st["total_ms"] + slack, st
                assert st["offcpu_ms"] >= -slack
        # the clock is the trace's, not a span's: a span ships as before
        d = qt.root.to_dict()
        assert "cpu_ms" not in d and "cpu_ms" not in d["children"][0]
        assert qt.to_dict()["cpu_ms"] == qt.cpu_ms

    def test_the_cpu_is_the_owning_threads_not_an_adopted_ones(self):
        """The serving tier: the connection thread owns the trace and
        waits, a dispatcher thread runs the statement under it.  The
        trace's CPU is the owner's; the dispatcher's is not in it."""
        with obs_trace.trace_query("q") as qt:
            with obs_trace.span("parse"):
                _busy(0.005)

            def work():
                with obs_trace.adopt(qt):
                    with obs_trace.span("execute"):
                        _busy(0.03)

            th = threading.Thread(target=work)
            th.start()
            th.join(10)
            assert not th.is_alive()
        st = qt.summary()
        assert st["execute_ms"] >= 29 and st["total_ms"] >= 34
        # the owner slept in join() while the other thread burnt CPU
        assert st["cpu_ms"] < 0.5 * st["total_ms"] + _cpu_tick_ms()
        assert st["offcpu_ms"] == pytest.approx(
            st["total_ms"] - st["cpu_ms"])

    def test_an_open_traces_cpu_reads_from_another_thread(self):
        """`last_query_stats()` reads the CN server's open trace from
        the client's side: the CPU is the trace's OWN thread's so far."""
        opened, done = threading.Event(), threading.Event()
        box = {}

        def serve():
            with obs_trace.trace_query(
                    "q", since=time.perf_counter(),
                    cpu_since=obs_trace.thread_cpu()) as qt:
                box["qt"] = qt
                _burn(0.03)
                opened.set()
                done.wait(10)

        th = threading.Thread(target=serve)
        th.start()
        assert opened.wait(10)
        st = box["qt"].summary()            # this thread has burnt none
        done.set()
        th.join(10)
        assert not th.is_alive()
        assert 10 < st["cpu_ms"] <= st["total_ms"] + _cpu_tick_ms()
        assert box["qt"].summary()["cpu_ms"] >= st["cpu_ms"]
        assert box["qt"].cpu_ms == box["qt"].summary()["cpu_ms"]

    def test_cpu_since_backdates_the_cpu_clock(self):
        c0 = obs_trace.thread_cpu()
        _burn(0.02)
        with obs_trace.trace_query("q", since=time.perf_counter() - 0.02,
                                   cpu_since=c0) as qt:
            pass
        with obs_trace.trace_query("q") as bare:
            pass
        assert qt.cpu_ms > 10 > bare.cpu_ms or _cpu_tick_ms() > 0.5

    def test_transfers_are_summed_where_they_were_counted(self):
        def mk(name, ms=1.0, **attrs):
            sp = obs_trace.Span(name, attrs)
            sp.ms = ms
            return sp

        qt = obs_trace.QueryTrace("hand")
        ex = mk("execute", 9.0, d2h=3, d2h_bytes=24, calls=1, h2d=2,
                h2d_bytes=16)
        fin = mk("finalize", 4.0)
        fin.children = [mk("finalize.gather", calls=2),
                        mk("finalize.fetch", fetches=5, bytes=100)]
        qt.root.children = [
            mk("inputs", 0.5, h2d=1, h2d_bytes=8), ex,
            mk("gather", 7.0, d2h=14, d2h_bytes=1000, h2d=14,
               h2d_bytes=900),
            mk("upload", 0.0, bytes=4096, h2d=3), fin,
            mk("release", 0.75), mk("inputs", 0.25, h2d=1, h2d_bytes=8)]
        st = qt.summary()
        assert st["host_syncs"] == 3 + 14 + 5
        assert st["d2h_bytes"] == 24 + 1000 + 100
        assert st["h2d_puts"] == 1 + 2 + 14 + 3 + 1
        assert st["h2d_bytes"] == 8 + 16 + 900 + 4096 + 8
        assert st["program_calls"] == 1 + 2
        assert st["gather_ms"] == 7.0 and st["inputs_ms"] == 0.75
        assert st["release_ms"] == 0.75
        assert st["finalize_fetches"] == 5 and st["bytes_staged"] == 4096

    def test_count_adds_to_the_innermost_open_span(self):
        obs_trace.count(calls=1)                # no trace: nothing
        with obs_trace.trace_query("q") as qt:
            with obs_trace.span("execute") as sp:
                obs_trace.count(calls=1)
                obs_trace.count(calls=1, d2h=2)
            obs_trace.count(d2h=1, d2h_bytes=8)  # on the root
        assert sp.attrs == {"calls": 2, "d2h": 2}
        st = qt.summary()
        assert st["program_calls"] == 2 and st["host_syncs"] == 3
        assert st["d2h_bytes"] == 8

    def test_phases_come_from_one_walk(self, monkeypatch):
        with obs_trace.trace_query("q") as qt:
            with obs_trace.span("plan"):
                pass
            with obs_trace.span("execute"):
                with obs_trace.span("stage"):
                    pass
                with obs_trace.span("execute"):
                    time.sleep(0.001)
            with obs_trace.span("finalize"):
                pass
        names = ("plan", "stage", "execute", "finalize", "nothing")
        got = qt.phases_ms(names)
        assert got == {n: qt.phase_ms(n) for n in names}
        assert got["execute"] == qt.root.children[1].ms  # outermost only
        assert got["nothing"] == 0.0
        # the registry hook asks once for its four phases
        walks = []
        real = obs_trace.QueryTrace.phases_ms
        monkeypatch.setattr(
            obs_trace.QueryTrace, "phases_ms",
            lambda self, names: walks.append(names) or real(self, names))
        obs_metrics.observe_query(qt)
        assert walks == [("plan", "stage", "execute", "finalize")]


class TestMetrics:
    def test_counter_gauge(self):
        r = obs_metrics.Registry()
        c = r.counter("otb_test_total", tier="x")
        c.inc()
        c.inc(2)
        assert c.value == 3
        assert r.counter("otb_test_total", tier="x") is c
        g = r.gauge("otb_test_live")
        g.set(42)
        assert g.value == 42
        with pytest.raises(TypeError):
            r.gauge("otb_test_total", tier="x")

    def test_histogram_percentiles_vs_numpy(self):
        r = obs_metrics.Registry()
        h = r.histogram("otb_test_ms")
        rng = np.random.default_rng(7)
        vals = np.exp(rng.normal(2.0, 1.0, size=4000))   # lognormal ms
        for v in vals:
            h.observe(float(v))
        # log-bucket width is 2^0.25 (~19%): quantile estimates must
        # land within one bucket of the exact sample percentile
        for q in (0.5, 0.95, 0.99):
            exact = float(np.percentile(vals, q * 100))
            got = h.quantile(q)
            assert exact / 1.2 <= got <= exact * 1.2, (q, got, exact)
        assert h.count == len(vals)
        assert h.sum == pytest.approx(float(vals.sum()), rel=1e-6)

    def test_prometheus_text_format(self):
        r = obs_metrics.Registry()
        r.counter("otb_q_total", tier="mesh").inc(5)
        h = r.histogram("otb_q_ms", tier="mesh")
        h.observe(1.0)
        h.observe(100.0)
        r.register_collector(
            "fix", lambda: [("otb_fix_live", {"t": "a"}, 2.0)])
        text = r.text()
        assert "# TYPE otb_q_total counter" in text
        assert 'otb_q_total{tier="mesh"} 5' in text
        assert "# TYPE otb_q_ms histogram" in text
        assert 'le="+Inf"' in text
        assert "otb_q_ms_sum" in text and "otb_q_ms_count" in text
        assert 'otb_fix_live{t="a"} 2' in text
        # bucket lines are cumulative and end at the total count
        buckets = [ln for ln in text.splitlines()
                   if ln.startswith("otb_q_ms_bucket")]
        assert buckets and buckets[-1].split()[-1] == "2"

    def test_broken_collector_never_breaks_scrape(self):
        r = obs_metrics.Registry()
        r.counter("otb_ok_total").inc()

        def boom():
            raise RuntimeError("collector died")

        r.register_collector("boom", boom)
        assert any(n == "otb_ok_total" for n, *_ in r.samples())
        assert "otb_ok_total" in r.text()

    def test_observe_query_feeds_registry(self):
        before = obs_metrics.REGISTRY.counter(
            "otb_queries_total", tier="single").value
        with obs_trace.trace_query("select 1") as qt:
            qt.tier = "single"
            with obs_trace.span("execute"):
                pass
        after = obs_metrics.REGISTRY.counter(
            "otb_queries_total", tier="single").value
        assert after == before + 1


# ---------------------------------------------------------------------------
# single-node tier: traces + EXPLAIN ANALYZE per-node actuals
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def single_env():
    node = LocalNode()
    s = Session(node)
    s.execute(SCHEMA)
    data = datagen.generate(sf=0.005)
    datagen.load_into(s, data)
    return s


class TestSingleTier:
    def test_last_query_stats(self, single_env):
        s = single_env
        rows = s.query(Q[1])
        st = s.last_query_stats()
        assert st["tier"] == "single"
        assert st["rows"] == len(rows)
        assert st["total_ms"] > 0
        assert st["execute_ms"] > 0
        assert st["total_ms"] >= st["execute_ms"]
        assert st["signature"].lower().startswith("select")

    def test_explain_analyze_q1_per_node_actuals(self, single_env):
        s = single_env
        r = s.execute("explain analyze " + Q[1])[0]
        plan = [ln for ln in r.text.splitlines()
                if "(actual rows=" in ln]
        # EVERY plan node carries actuals (fusion is disabled on the
        # instrumented path so interior nodes execute individually)
        assert "SeqScan" in r.text and "Agg" in r.text
        assert len(plan) >= 3, r.text
        assert "Execution Time:" in r.text
        assert "Buffer Pool:" in r.text
        assert "Programs:" in r.text
        m = re.search(r"actual rows=(\d+) time=([\d.]+) ms", r.text)
        assert m and int(m.group(1)) >= 0

    def test_explain_analyze_q3(self, single_env):
        s = single_env
        r = s.execute("explain analyze " + Q[3])[0]
        assert r.text.count("(actual rows=") >= 4, r.text
        assert "Join" in r.text
        assert "Execution Time:" in r.text

    def test_explain_analyze_matches_plain_result(self, single_env):
        # ANALYZE runs the statement: row counts in the annotation of
        # the root node match what the query actually returns
        s = single_env
        want = len(s.query(Q[1]))
        r = s.execute("explain analyze " + Q[1])[0]
        top = re.search(r"actual rows=(\d+)", r.text.splitlines()[0])
        assert top and int(top.group(1)) == want

    def test_stage_wait_is_stage_without_overlap(self, single_env):
        # a serial statement hides no staging behind device compute:
        # the overlap-adjusted wait is the whole of the staging time
        s = single_env
        s.query(Q[1])
        st = s.last_query_stats()
        assert st["stage_wait_ms"] == pytest.approx(st["stage_ms"])


# ---------------------------------------------------------------------------
# cluster tier: views, EXPLAIN ANALYZE fragments, warm staging
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster_env():
    cluster = Cluster(n_datanodes=2)
    s = ClusterSession(cluster)
    s.execute(SCHEMA)
    data = datagen.generate(sf=0.005)
    for tname in ("region", "nation", "supplier", "customer", "part",
                  "partsupp", "orders", "lineitem"):
        tbl = data[tname]
        td = cluster.catalog.table(tname)
        n = len(next(iter(tbl.values())))
        s._insert_rows(td, tbl, n)
    return s


class TestClusterTier:
    def test_last_query_stats(self, cluster_env):
        s = cluster_env
        rows = s.query(Q[1])
        st = s.last_query_stats()
        assert st["rows"] == len(rows)
        assert st["tier"] in ("mesh", "host", "local", "fqs", "gidx")
        assert st["total_ms"] > 0 and st["execute_ms"] > 0

    @pytest.mark.parametrize("traced", [True, False],
                             ids=["traced", "untraced"])
    @pytest.mark.parametrize("setup, sql, tier, reason", [
        ("", "select count(*), sum(o_totalprice) from orders",
         "mesh", ""),
        ("", "select o_totalprice from orders where o_orderkey = 1",
         "fqs", ""),
        ("set work_mem_rows = 16",
         "select count(*), sum(o_totalprice) from orders",
         "host", "work_mem_rows budget"),
    ], ids=["mesh", "fqs", "host"])
    def test_how_a_select_ran(self, cluster_env, monkeypatch, setup, sql,
                              tier, reason, traced):
        """The one answer to "how did that statement run":
        last_query_stats() names the tier and the fallback reason of
        the last statement, and tier_counts / fallbacks move by exactly
        one SELECT whether or not the statement was traced."""
        s = ClusterSession(cluster_env.cluster)   # counters start empty
        if setup:
            cluster_env.execute(setup)            # GUCs are the cluster's
        monkeypatch.setattr(obs_trace, "ENABLED", traced)
        try:
            s.query(sql)
            st = s.last_query_stats()
        finally:
            monkeypatch.setattr(obs_trace, "ENABLED", True)
            if setup:
                cluster_env.execute("set work_mem_rows = 0")
        assert s.tier_counts == {tier: 1}
        assert len(s.fallbacks) == (1 if reason else 0)
        assert all(reason in r for r in s.fallbacks)
        if traced:
            assert st["tier"] == tier
            assert reason in st["fallback"]
            assert bool(st["fallback"]) == bool(reason)
        else:
            assert st == {}

    def test_warm_q1_stage_is_zero_with_full_pool_hits(self, cluster_env):
        s = cluster_env
        s.query(Q[1])            # populate the device buffer pool
        s.query(Q[1])            # warm run
        st = s.last_query_stats()
        qt = obs_trace.last_trace()
        hits = qt.count_events("pool", hit=True)
        misses = qt.count_events("pool", hit=False)
        assert hits > 0 and misses == 0, (hits, misses)
        # staging a pool-resident table is bookkeeping only
        assert st["stage_ms"] < max(st["total_ms"] * 0.25, 5.0), st

    def test_explain_analyze_q1_fragments(self, cluster_env):
        s = cluster_env
        r = s.execute("explain analyze " + Q[1])[0]
        assert "(actual rows=" in r.text, r.text
        assert "Fragment 0" in r.text
        assert "Execution Time:" in r.text
        assert "Buffer Pool:" in r.text
        assert "Programs:" in r.text

    def test_explain_analyze_q3_fragments(self, cluster_env):
        s = cluster_env
        r = s.execute("explain analyze " + Q[3])[0]
        assert "(actual rows=" in r.text, r.text
        assert "rows=" in r.text and "time=" in r.text
        assert "Execution Time:" in r.text

    def test_q1_shaped_transfers_are_exact(self, cluster_env):
        """A grouped aggregate through the mesh tier: every blocking
        device->host copy and every put of the statement is counted
        where it is made, and on CPU devices the counts are exact: they
        follow from the answer's column count."""
        s = cluster_env
        sql = ("select l_returnflag, l_linestatus, sum(l_quantity), "
               "count(*) from lineitem where l_shipdate <= "
               "date '1998-09-01' group by l_returnflag, l_linestatus "
               "order by l_returnflag, l_linestatus")
        s.query(sql)                            # builds, learns classes
        seen = []
        for _ in range(2):
            rows = s.query(sql)
            st = s.last_query_stats()
            seen.append((st["host_syncs"], st["h2d_puts"],
                         st["d2h_bytes"], st["h2d_bytes"],
                         st["program_calls"]))
        assert seen[0] == seen[1]               # the same on every reply
        assert st["tier"] == "mesh" and st["retraces"] == 0
        # one batched copy after the program call (the three overflow
        # vectors and every gathered array), one at finalize: a batched
        # copy is ONE round trip however many arrays it carries
        assert st["host_syncs"] == 2
        assert st["finalize_fetches"] == 1
        # the gather's way back is one put call; the snapshot, the txid
        # and the lifted date ride the program's own argument transfer
        assert st["h2d_puts"] == 1
        assert st["program_calls"] == 1
        assert st["gather_ms"] > 0 and st["inputs_ms"] > 0
        qt = obs_trace.last_trace()
        row_bytes = 4 + 4 + 8 + 8 + 1   # two codes, two int64, validity
        padded = max(sp.attrs["padded"] for sp in _spans(qt.root)
                     if sp.name == "stage")
        down = row_bytes * 2 * min(padded, 1 << 16)  # the gather class,
        up = row_bytes * 256        # two shards; the live rows' class
        over = 3 * 8    # the overflow vectors: one int64 flag each here
        assert qt.sum_attr("inputs", "h2d") == 0
        assert qt.sum_attr("execute", "d2h") == 1
        assert qt.sum_attr("execute", "d2h_bytes") == down + over
        assert qt.sum_attr("gather", "d2h") == 0
        assert qt.sum_attr("gather", "h2d") == 1
        assert qt.sum_attr("gather", "h2d_bytes") == up
        assert st["finalize_fetch_bytes"] == up
        assert st["d2h_bytes"] == down + over + up   # bytes: unchanged
        assert st["h2d_bytes"] == up        # arrays: scalars add none
        # root's children by name: the host path around the program
        assert [c.name for c in qt.root.children if c.ms > 0] == [
            "parse", "autoprep", "bind", "stage", "inputs", "execute",
            "gather", "execute", "finalize", "release"]

    def test_new_summary_keys_reach_view_slow_log_and_explain(
            self, cluster_env, monkeypatch):
        s = cluster_env
        keys = ("cpu_ms", "offcpu_ms", "unattributed_ms", "inputs_ms",
                "gather_ms",
                "release_ms", "host_syncs", "d2h_bytes", "h2d_puts",
                "h2d_bytes", "program_calls")
        buf = io.StringIO()
        monkeypatch.setattr(obs_trace, "SLOW_STREAM", buf)
        monkeypatch.setattr(obs_trace, "SLOW_MS", 0.001)
        s.query(Q[1])
        monkeypatch.setattr(obs_trace, "SLOW_MS", 0)
        st = s.last_query_stats()
        rec = json.loads(buf.getvalue().splitlines()[-1])
        assert rec["event"] == "slow_query"
        for k in keys:
            assert rec[k] == st[k], k
        row = s.query("select qid, " + ", ".join(keys)
                      + " from otb_stat_query where qid = "
                      + str(st["qid"]))
        assert len(row) == 1
        for k, v in zip(keys, row[0][1:]):
            assert v == pytest.approx(st[k]), k
        # Q1 in the mesh tier: a batched copy after the call, one at
        # finalize; the gather's one put back
        assert st["host_syncs"] == 2 and st["h2d_puts"] == 1
        text = s.execute("explain analyze " + Q[1])[0].text
        m = re.search(r"Transfers: host_syncs=(\d+) d2h_bytes=(\d+) "
                      r"h2d_puts=(\d+) h2d_bytes=(\d+) "
                      r"program_calls=(\d+)", text)
        assert m, text
        assert int(m.group(1)) >= 2 and int(m.group(5)) >= 1
        assert text.index("Programs:") < text.index("Transfers:")

    def test_otb_stat_query_view(self, cluster_env):
        s = cluster_env
        s.query(Q[1])
        rows = s.query("select signature, tier, total_ms, rows "
                       "from otb_stat_query")
        assert rows, "ring empty"
        sigs = [r[0] for r in rows]
        assert any(sig.lower().startswith("select") for sig in sigs)
        assert all(r[2] >= 0 for r in rows)

    def test_otb_metrics_view(self, cluster_env):
        s = cluster_env
        s.query(Q[1])
        rows = s.query("select name, kind, value from otb_metrics")
        names = {r[0] for r in rows}
        assert "otb_queries_total" in names
        assert any(n.startswith("otb_plancache_") for n in names)
        assert any(n.startswith("otb_buffercache_") for n in names), names

    def test_metrics_text_exposition(self, cluster_env):
        s = cluster_env
        s.query(Q[1])
        text = s.metrics_text()
        assert "# TYPE otb_queries_total counter" in text
        assert "# TYPE otb_query_ms histogram" in text
        assert 'le="+Inf"' in text

    def test_scheduler_pipeline_gauges_exposed(self, cluster_env):
        # importing the scheduler registers its collector; the pipeline
        # gauges must appear in the exposition even with no scheduler
        # running (zeros), so dashboards never see a gap
        import opentenbase_tpu.exec.scheduler  # noqa: F401
        text = cluster_env.metrics_text()
        for name in ("otb_sched_pipeline_overlap_ratio",
                     "otb_sched_drain_queue_depth",
                     "otb_sched_stage_work_ms",
                     "otb_sched_pipelined_dispatches",
                     "otb_sched_drained"):
            assert name in text, name


    def test_otb_workshare_view(self, cluster_env):
        s = cluster_env
        rows = s.query("select shared_streams, shared_scan_fanin, "
                       "result_cache_hits, result_cache_bytes "
                       "from otb_workshare")
        assert len(rows) == 1, rows
        assert all(v >= 0 for v in rows[0]), rows

    def test_workshare_counters_exposed(self, cluster_env):
        # importing exec.share registers its collector; the work-
        # sharing counters must appear even before any sharing happens
        # (zeros), so dashboards never see a gap
        import opentenbase_tpu.exec.share  # noqa: F401
        text = cluster_env.metrics_text()
        for name in ("otb_workshare_shared_streams",
                     "otb_workshare_shared_scan_fanin",
                     "otb_workshare_shared_chunks",
                     "otb_workshare_late_joins",
                     "otb_workshare_private_fallbacks",
                     "otb_workshare_result_cache_hits",
                     "otb_workshare_result_cache_misses",
                     "otb_workshare_result_cache_invalidations",
                     "otb_workshare_result_cache_bytes"):
            assert name in text, name


def test_cn_server_metrics_op():
    from opentenbase_tpu.net.cn_server import CnClient, CnServer
    cluster = Cluster(n_datanodes=2)
    srv = CnServer(lambda: ClusterSession(cluster)).start()
    try:
        c = CnClient(srv.host, srv.port)
        c.execute("create table mt (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        c.execute("insert into mt values (1, 10), (2, 20)")
        assert c.query("select sum(v) from mt") == [(30,)]
        text = c.metrics()
        assert "otb_queries_total" in text
        assert "# TYPE" in text
        ws = c.workshare()
        assert "shared_scan_fanin" in ws and "result_cache_hits" in ws
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# the spans where the work happens: a point read at SF0.01, in process and
# through the CN server
# ---------------------------------------------------------------------------

POINT = ("select o_orderkey, o_custkey, o_totalprice, o_orderdate "
         "from orders where o_orderkey = {}")


@pytest.fixture(scope="module")
def point_env():
    cluster = Cluster(n_datanodes=1)
    s = ClusterSession(cluster)
    s.execute(SCHEMA)
    data = datagen.generate(sf=0.01)
    td = cluster.catalog.table("orders")
    s._insert_rows(td, data["orders"], len(data["orders"]["o_orderkey"]))
    keys = [int(k) for k in data["orders"]["o_orderkey"][:40:4]]
    s.query(POINT.format(keys[0]))          # builds and stages
    return cluster, s, keys


class TestPointReadSpans:
    def test_finalize_children_sum_to_finalize(self, point_env):
        _cluster, s, keys = point_env
        for k in keys[:3]:
            assert len(s.query(POINT.format(k))) == 1
            st = s.last_query_stats()
            parts = (st["finalize_gather_ms"] + st["finalize_fetch_ms"]
                     + st["finalize_decode_ms"])
            assert 0 < parts <= st["finalize_ms"]
            # the three cover finalize but for the spans' own entry and
            # exit (a fifth of a ms would be a fourth kind of work)
            assert st["finalize_ms"] - parts < max(
                0.2, 0.25 * st["finalize_ms"]), st
            qt = obs_trace.last_trace()
            fin = [c for c in qt.root.children if c.name == "finalize"]
            assert [c.name for c in fin[0].children] == [
                "finalize.gather", "finalize.fetch", "finalize.decode"]

    @pytest.mark.parametrize("side", ["under", "over"])
    def test_fetches_are_exact_and_repeat(self, point_env, side,
                                          monkeypatch):
        """Each side of finalize's threshold: 15000 orders at SF0.01 pad
        to 16384 rows of 28 B, under it; with the constant lowered to
        that width the same reads are over it."""
        from opentenbase_tpu.exec import executor
        _cluster, s, keys = point_env
        padded, row = 16384, 8 + 8 + 8 + 4
        assert padded * row < executor._COMPACT_MIN_BYTES
        if side == "over":
            monkeypatch.setattr(executor, "_COMPACT_MIN_BYTES", padded * row)
        seen = set()
        for k in keys:
            assert len(s.query(POINT.format(k))) == 1
            st = s.last_query_stats()
            seen.add((st["finalize_fetches"], st["finalize_fetch_bytes"],
                      st["bytes_materialized"]))
        # whatever the key
        assert len(seen) == 1, seen
        fetches, nbytes, materialized = seen.pop()
        if side == "under":
            # `valid` and the four columns read, no null mask: five
            # arrays of the whole padded table in ONE batched copy
            assert fetches == 1
            assert nbytes == padded * (1 + row)
            assert materialized == padded * row
        else:
            # one buffer: the count and the four columns at the first
            # out class; `valid` stays on the device
            assert fetches == 1
            assert nbytes == 4 + 256 * row
            assert materialized == 256 * row
        (fetch,) = [c for f in obs_trace.last_trace().root.children
                    if f.name == "finalize"
                    for c in f.children if c.name == "finalize.fetch"]
        assert fetch.attrs["compacted"] == (256 if side == "over" else 0)

    def test_parse_and_autoprep_are_spans_of_the_statement(self, point_env):
        _cluster, s, keys = point_env
        s.query(POINT.format(keys[1]))
        st = s.last_query_stats()
        assert st["parse_ms"] > 0 and st["autoprep_ms"] > 0
        assert st["wire_ms"] == 0           # no wire in process
        assert st["tier"] == "fqs"
        # what no span covers is the root's self time
        qt = obs_trace.last_trace()
        assert st["unattributed_ms"] == pytest.approx(qt.root.self_ms())
        assert 0 < st["unattributed_ms"] < st["total_ms"]
        total = st["unattributed_ms"] + sum(
            c.ms for c in qt.root.children)
        assert total == pytest.approx(st["total_ms"], rel=1e-6)

    @pytest.mark.parametrize("side, fetches, fetch_bytes", [
        ("under", 1, 16384 * 29), ("over", 1, 4 + 256 * 28)])
    def test_wire_and_parse_for_a_statement_sent_through_cnserver(
            self, point_env, side, fetches, fetch_bytes, monkeypatch):
        from opentenbase_tpu.exec import executor
        from opentenbase_tpu.net.cn_server import CnClient, CnServer
        cluster, _s, keys = point_env
        if side == "over":                  # of finalize's threshold
            monkeypatch.setattr(executor, "_COMPACT_MIN_BYTES", 16384 * 28)
        sessions = []

        def make():
            sessions.append(ClusterSession(cluster))
            return sessions[-1]

        srv = CnServer(make).start()
        try:
            c = CnClient(srv.host, srv.port)
            last = obs_trace.recent()[-1].qid

            def since():
                return [q for q in obs_trace.recent() if q.qid > last]

            assert len(c.query(POINT.format(keys[2]))) == 1
            # read at the reply, as the benchmark does: the server's
            # trace may still be open (the send is ending), and what
            # has ended by then is all there
            st = sessions[0].last_query_stats()
            assert st["parse_ms"] > 0
            assert st["finalize_fetches"] == fetches
            assert st["finalize_fetch_bytes"] == fetch_bytes
            assert st["wire_ms"] > 0            # wire.recv: the decode
            assert st["total_ms"] >= st["finalize_ms"] + st["parse_ms"]
            assert st["unattributed_ms"] < st["total_ms"] - st["finalize_ms"]
            c.metrics()                         # not a statement
            c.close()
            deadline = time.time() + 5
            while not since() and time.time() < deadline:
                time.sleep(0.01)
        finally:
            srv.stop()
        # one trace a message, and the metrics op left none
        assert [q.signature for q in since()] == [POINT.format(keys[2])]
        qt = since()[0]
        names = [c.name for c in qt.root.children]
        assert names[0] == "wire.recv" and names[1] == "parse"
        assert names[-1] == "wire.send"
        done = qt.summary()
        # the finished trace has all of the reply's send
        assert done["wire_ms"] >= st["wire_ms"]
        assert done["total_ms"] >= st["total_ms"]
        assert sessions[0].last_query_stats() == done
        for key in ("parse_ms", "plan_ms", "execute_ms", "finalize_ms",
                    "finalize_fetch_ms", "finalize_fetches",
                    "finalize_fetch_bytes", "rows"):
            assert done[key] == st[key]
        assert done["wire_ms"] == pytest.approx(
            qt.root.children[0].ms + qt.root.children[-1].ms)


# ---------------------------------------------------------------------------
# one trace a statement, whichever thread runs it
# ---------------------------------------------------------------------------

def _queries_total():
    return sum(r[-1] for r in obs_metrics.REGISTRY.samples()
               if r[0] == "otb_queries_total")


def _traces_since(qid, n, timeout=5.0):
    """The ring's traces after `qid`, once `n` have finished (the
    server's trace ends after the client has its reply)."""
    deadline = time.time() + timeout
    while True:
        new = [q for q in obs_trace.recent() if q.qid > qid]
        if len(new) >= n or time.time() > deadline:
            return new
        time.sleep(0.01)


def _names(sp):
    out = {sp.name}
    for c in sp.children:
        out |= _names(c)
    return out


class TestOneTraceAStatement:
    def test_adopt_puts_another_threads_spans_under_the_trace(self):
        with obs_trace.trace_query("q") as qt:
            def work():
                assert not obs_trace.active()
                with obs_trace.adopt(qt) as same:
                    assert same is qt and obs_trace.current_trace() is qt
                    with obs_trace.trace_query("inner") as joined:
                        assert joined is qt         # joins, opens none
                        with obs_trace.span("execute"):
                            time.sleep(0.001)
                assert not obs_trace.active()
            t = threading.Thread(target=work)
            t.start()
            t.join()
            assert obs_trace.current_trace() is qt  # the owner's still
        ex, = qt.root.children
        assert ex.name == "execute" and 0 < ex.t0_ms < qt.total_ms
        assert ex.t0_ms + ex.ms <= qt.total_ms

    def test_trace_query_since_backdates_the_root(self):
        t0 = time.perf_counter()
        time.sleep(0.002)
        with obs_trace.trace_query("q", since=t0) as qt:
            obs_trace.record("wire.recv", 1.0)
        assert qt.total_ms >= 2.0
        recv, = qt.root.children
        assert 0 <= recv.t0_ms and recv.t0_ms + recv.ms <= qt.total_ms

    def test_summary_of_an_open_trace_reads_as_of_now(self):
        with obs_trace.trace_query("q") as qt:
            with obs_trace.span("wire.send"):
                time.sleep(0.002)
                st = qt.summary()
                # the open span covers the time up to now
                assert st["wire_ms"] >= 2.0
                assert st["unattributed_ms"] < 1.0
                assert st["total_ms"] >= st["wire_ms"]

    def test_serving_tier_n_statements_n_traces(self):
        # the serving tier runs a statement on a dispatcher thread while
        # its connection thread, which opened the trace, waits: still
        # ONE ring entry and ONE otb_queries_total a statement, holding
        # the wire and the execution together
        from opentenbase_tpu.exec import scheduler as sm
        from opentenbase_tpu.net.cn_server import CnClient
        node = LocalNode()
        s = Session(node)
        s.execute("create table t (a bigint, b double precision, g bigint)")
        s.execute("insert into t values " + ", ".join(
            f"({i}, {i * 0.5}, {i % 3})" for i in range(200)))
        sqls = ["select g, sum(b) as sb from t where a < 100 "
                "group by g order by g",
                "select a, b from t where a = 7",
                "insert into t values (1000, 1.0, 1)",
                "select a from t where a = 1000; select count(*) from t",
                "select g, sum(b) as sb from t where a < 100 "
                "group by g order by g",
                "select nope from t"]
        srv, sched = sm.serve(node)
        try:
            c = CnClient(srv.host, srv.port)
            last = obs_trace.recent()[-1].qid
            before = _queries_total()
            for sql in sqls[:-1]:
                c.execute(sql)
            with pytest.raises(Exception, match="nope"):
                c.query(sqls[-1])
            new = _traces_since(last, len(sqls))
            c.close()
        finally:
            srv.stop()
            sched.stop()
        assert [q.signature for q in new] == sqls
        assert _queries_total() == before + len(sqls)
        assert len({q.trace_id for q in new}) == len(sqls)
        for q in new:
            first, last_ = q.root.children[0], q.root.children[-1]
            assert first.name == "wire.recv" and last_.name == "wire.send"
            assert "parse" in _names(q.root)
        for q in (new[0], new[1], new[3]):
            assert {"execute", "finalize.fetch"} <= _names(q.root)
        assert new[1].rows == 1 and new[3].rows == 1

    def test_single_node_session_over_cnserver_has_parse_and_wire(self):
        from opentenbase_tpu.net.cn_server import CnClient, CnServer
        node = LocalNode()
        Session(node).execute("create table t1 (a bigint)")
        sessions = []

        def make():
            sessions.append(Session(node))
            return sessions[-1]

        srv = CnServer(make).start()
        try:
            c = CnClient(srv.host, srv.port)
            last = obs_trace.recent()[-1].qid
            c.execute("insert into t1 values (1), (2)")
            assert c.query("select a from t1 order by a") == [(1,), (2,)]
            st = sessions[0].last_query_stats()
            assert st["parse_ms"] > 0 and st["wire_ms"] > 0
            new = _traces_since(last, 2)
            c.close()
        finally:
            srv.stop()
        assert len(new) == 2
        assert sessions[0].last_query_stats() == new[1].summary()
        # in process: the session opens the trace, around the parse
        s = Session(node)
        s.execute("select a from t1; select count(*) from t1")
        st = s.last_query_stats()
        assert st["parse_ms"] > 0 and st["wire_ms"] == 0
        assert obs_trace.last_trace().signature \
            == "select a from t1; select count(*) from t1"

    def test_malformed_query_message_gets_an_error_reply(self):
        # no `sql`, or one that is no string: an error reply, and the
        # session's thread lives on
        from opentenbase_tpu.net.cn_server import CnClient, CnServer
        from opentenbase_tpu.net.wire import recv_msg, send_msg
        node = LocalNode()
        srv = CnServer(lambda: Session(node)).start()
        try:
            c = CnClient(srv.host, srv.port)
            for bad in ({"op": "query"}, {"op": "query", "sql": 7}):
                send_msg(c._sock, bad)
                assert "error" in recv_msg(c._sock, expect_reply=True)
            assert c.query("select 1") == [(1,)]
            c.close()
        finally:
            srv.stop()
