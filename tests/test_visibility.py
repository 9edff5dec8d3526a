"""otbsnap: snapshot-visibility soundness — static passes, runtime
sanitizer, and the history-based SI checker.

Three layers under test:

- the static ``snapshot-gate`` / ``version-key`` passes
  (analysis/visibility.py) on fixture packages with exactly one
  violation and a clean twin each;
- the runtime sanitizer (utils/snapcheck.py): each violation kind
  caught live, the OFF path costing nothing measurable, and a real
  OTB_SNAPCHECK=1 workload whose witnessed serve points are a subset
  of the repo's statically-gated set with zero violations;
- the Adya-style G1/G-SI history checker (analysis/sicheck.py) on
  canned histories: clean, future-read, stale-read, intermediate-read
  (G1b), G-SIb one-rw cycle, and the allowed write-skew shape.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from opentenbase_tpu.analysis.lint import lint
from opentenbase_tpu.analysis.sicheck import check_history
from opentenbase_tpu.utils import snapcheck

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _write_pkg(root, files: dict):
    for rel, src in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            fh.write(textwrap.dedent(src))


def _scan(root, rule):
    report = lint(root=str(root), package="fixpkg", rules={rule})
    return [(f["rule"], f["file"]) for f in report["findings"]
            if not f.get("suppressed")]


# ---------------------------------------------------------------------------
# snapshot-gate: visibility discipline
# ---------------------------------------------------------------------------

class TestVisibilityDisciplinePass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/ungated.py": """\
            def run(dn, plan, snapshot_ts, txid):
                return dn.exec_plan(plan, snapshot_ts, txid, {}, {})
        """,
        "fixpkg/exec/gated.py": """\
            def run(dn, plan, snapshot_ts, txid):
                # snapshot-gate: snapshot_ts
                return dn.exec_plan(plan, snapshot_ts, txid, {}, {})
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _scan(tmp_path, "snapshot-gate")
        assert got == [("snapshot-gate", "fixpkg/exec/ungated.py")], got

    def test_stale_contract_flagged(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/exec/gated.py"] = """\
            def run(dn, plan, snapshot_ts, txid):
                # snapshot-gate: vanished_guard_token
                return dn.exec_plan(plan, snapshot_ts, txid, {}, {})
        """
        _write_pkg(tmp_path, files)
        got = _scan(tmp_path, "snapshot-gate")
        assert ("snapshot-gate", "fixpkg/exec/gated.py") in got, got

    def test_decorator_position_gate(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/exec/gated.py"] = """\
            # snapshot-gate: snapshot_ts
            def run(dn, plan, snapshot_ts, txid):
                return dn.exec_plan(plan, snapshot_ts, txid, {}, {})
        """
        _write_pkg(tmp_path, files)
        got = _scan(tmp_path, "snapshot-gate")
        assert got == [("snapshot-gate", "fixpkg/exec/ungated.py")], got

    def test_pragma_suppresses(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/exec/ungated.py"] = files[
            "fixpkg/exec/ungated.py"].replace(
            "{}, {})", "{}, {})  # otblint: disable=snapshot-gate")
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "snapshot-gate") == []


# ---------------------------------------------------------------------------
# version-key: content caches DML can invalidate
# ---------------------------------------------------------------------------

class TestVersionKeyPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/storage/__init__.py": "",
        "fixpkg/storage/badcache.py": """\
            class SnapCache:
                def __init__(self):
                    self.tab = {}

                def pull(self, name, store):
                    self.tab[name] = store.host_snapshot()
                    return self.tab[name]
        """,
        "fixpkg/storage/goodcache.py": """\
            class SnapCache:
                def __init__(self):
                    self.tab = {}

                def pull(self, name, store):
                    key = (name, store.version)
                    self.tab[key] = store.host_snapshot()
                    return self.tab[key]
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _scan(tmp_path, "version-key")
        assert got == [("version-key", "fixpkg/storage/badcache.py")], got

    def test_invalidate_edge_accepted(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/storage/badcache.py"] = """\
            class SnapCache:
                def __init__(self):
                    self.tab = {}

                def invalidate(self, name):
                    self.tab.pop(name, None)

                def pull(self, name, store):
                    self.tab[name] = store.host_snapshot()
                    return self.tab[name]
        """
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "version-key") == []


# ---------------------------------------------------------------------------
# runtime sanitizer units
# ---------------------------------------------------------------------------

@pytest.fixture
def snapcheck_on(monkeypatch):
    monkeypatch.setenv("OTB_SNAPCHECK", "1")
    monkeypatch.delenv("OTB_SNAP_HISTORY", raising=False)
    snapcheck.reset()
    yield
    snapcheck.reset()


class TestSanitizer:
    def test_clean_serve_records_witness(self, snapcheck_on):
        snapcheck.serve("exec.share.ResultCache.lookup",
                        snapshot_gts=20, entry_gts=15,
                        versions=[("t", 3)], expect_versions=[("t", 3)],
                        session="s0")
        assert snapcheck.violations() == []
        assert snapcheck.witness() == {
            "exec.share.ResultCache.lookup": 1}

    def test_stale_served_entry_caught_live(self, snapcheck_on):
        # a cached result produced at GTS 30 handed to a snapshot
        # drawn at 20 — exactly what a broken `snapshot >= tag` lets
        # through
        snapcheck.serve("exec.share.ResultCache.lookup",
                        snapshot_gts=20, entry_gts=30)
        kinds = [v["kind"] for v in snapcheck.violations()]
        assert kinds == ["stale-serve"]

    def test_version_mismatch_caught(self, snapcheck_on):
        snapcheck.serve("storage.bufferpool.DeviceBufferPool.get_device",
                        versions=[("t", 3)], expect_versions=[("t", 4)])
        kinds = [v["kind"] for v in snapcheck.violations()]
        assert kinds == ["version-mismatch"]

    def test_monotone_reads_per_session(self, snapcheck_on):
        pt = "exec.share.ResultCache.lookup"
        snapcheck.serve(pt, versions=[("t", 5)], session="s1")
        snapcheck.serve(pt, versions=[("t", 4)], session="s1")
        kinds = [v["kind"] for v in snapcheck.violations()]
        assert kinds == ["monotone-violation"]
        # a DIFFERENT session observing the older version is fine
        snapcheck.reset()
        snapcheck.serve(pt, versions=[("t", 5)], session="s1")
        snapcheck.serve(pt, versions=[("t", 4)], session="s2")
        assert snapcheck.violations() == []

    def test_snapshot_regression_caught(self, snapcheck_on):
        pt = "net.guard.ReplicaRouter.try_exec"
        snapcheck.serve(pt, snapshot_gts=10, session="s3")
        snapcheck.serve(pt, snapshot_gts=8, session="s3")
        kinds = [v["kind"] for v in snapcheck.violations()]
        assert kinds == ["snapshot-regression"]

    def test_off_is_noop(self, monkeypatch):
        monkeypatch.delenv("OTB_SNAPCHECK", raising=False)
        monkeypatch.delenv("OTB_SNAP_HISTORY", raising=False)
        snapcheck.reset()
        snapcheck.serve("x.y", snapshot_gts=1, entry_gts=99)
        assert snapcheck.witness() == {}
        assert snapcheck.violations() == []
        assert snapcheck.history_events() == []

    def test_report_merges_across_shards(self, snapcheck_on, tmp_path):
        path = str(tmp_path / "w.json")
        with open(path, "w") as f:
            json.dump({"serve_points": {"exec.share.ResultCache.lookup":
                                        2}, "violations": []}, f)
        snapcheck.serve("exec.share.ResultCache.lookup")
        snapcheck.serve("exec.share.ShareHub.attach")
        data = snapcheck.save_report(path)
        assert data["serve_points"] == {
            "exec.share.ResultCache.lookup": 3,
            "exec.share.ShareHub.attach": 1}
        assert data["violations"] == []

    def test_history_records_when_enabled_off(self, monkeypatch,
                                              tmp_path):
        # SI history is independent of the sanitizer flag: the zipf
        # arm records history without paying assertion cost
        monkeypatch.delenv("OTB_SNAPCHECK", raising=False)
        monkeypatch.setenv("OTB_SNAP_HISTORY",
                           str(tmp_path / "h.json"))
        snapcheck.reset()
        snapcheck.serve("exec.share.ResultCache.lookup",
                        snapshot_gts=9, versions=[("t", 1)],
                        session="s", source="cache")
        snapcheck.note_write("w", 10, {"t": 2})
        evs = snapcheck.history_events()
        assert [e["t"] for e in evs] == ["r", "w"]
        assert snapcheck.witness() == {}    # sanitizer stayed off
        snapcheck.save_history()
        saved = json.load(open(tmp_path / "h.json"))
        assert len(saved["events"]) == 2
        snapcheck.reset()


# ---------------------------------------------------------------------------
# SI history checker (analysis/sicheck.py)
# ---------------------------------------------------------------------------

def _w(sess, gts, writes):
    return {"t": "w", "sess": sess, "gts": gts,
            "writes": [[t, v] for t, v in writes]}


def _r(sess, gts, obs, src="cache"):
    return {"t": "r", "sess": sess, "gts": gts, "src": src,
            "obs": [[t, v] for t, v in obs]}


class TestSiChecker:
    def test_clean_history(self):
        res = check_history([
            _w("t0", 10, [("x", 1), ("y", 1)]),
            _r("r0", 12, [("x", 1), ("y", 1)]),
            _w("t1", 20, [("x", 2)]),
            _r("r1", 25, [("x", 2), ("y", 1)]),
        ])
        assert res["ok"], res["anomalies"]
        assert res["reads"] == 2 and res["writes"] == 2
        assert res["by_source"] == {"cache": 2}

    def test_future_read(self):
        res = check_history([
            _w("t0", 10, [("x", 1)]),
            _r("r0", 5, [("x", 1)]),     # snapshot predates the commit
        ])
        assert [a["kind"] for a in res["anomalies"]] == ["future-read"]

    def test_stale_read(self):
        res = check_history([
            _w("t0", 10, [("x", 1)]),
            _w("t1", 20, [("x", 2)]),
            _r("r0", 25, [("x", 1)]),    # x@2 was visible at 25
        ])
        assert [a["kind"] for a in res["anomalies"]] == ["stale-read"]

    def test_intermediate_read_g1b(self):
        res = check_history([
            _w("t0", 10, [("x", 1), ("x", 2)]),   # one txn, two versions
            _r("r0", 12, [("x", 1)]),             # non-final observed
        ])
        kinds = {a["kind"] for a in res["anomalies"]}
        assert "intermediate-read" in kinds, res["anomalies"]

    def test_gsib_one_rw_cycle(self):
        # T_a wrote x AND y at GTS 20; the read (snapshot 25) saw
        # T_a's x but pre-T_a y — a fractured read: the rw edge on y
        # closes a cycle back to T_a, who supplied x (G-SIb)
        res = check_history([
            _w("t0", 10, [("x", 1), ("y", 1)]),
            _w("ta", 20, [("x", 2), ("y", 2)]),
            _r("r0", 25, [("x", 2), ("y", 1)], src="shared"),
        ])
        kinds = {a["kind"] for a in res["anomalies"]}
        assert "g-si-cycle" in kinds, res["anomalies"]

    def test_write_skew_allowed(self):
        # two concurrent writers each overwrote ONE of the tables a
        # snapshot read observed — a cycle needs TWO rw edges, which
        # SI permits: no anomaly
        res = check_history([
            _w("t0", 10, [("x", 1), ("y", 1)]),
            _r("r0", 15, [("x", 1), ("y", 1)]),
            _w("t1", 20, [("x", 2)]),
            _w("t2", 21, [("y", 2)]),
        ])
        assert res["ok"], res["anomalies"]

    def test_obsless_reads_counted_not_edged(self):
        res = check_history([
            _w("t0", 10, [("x", 1)]),
            {"t": "r", "sess": "r0", "gts": 12, "src": "replica"},
        ])
        assert res["ok"]
        assert res["by_source"] == {"replica": 1}

    def test_inferred_obs_from_tables(self):
        res = check_history([
            _w("t0", 10, [("x", 1)]),
            _w("t1", 20, [("x", 2)]),
            {"t": "r", "sess": "r0", "gts": 15, "src": "primary",
             "tables": ["x"]},       # inferred: x@1 at snapshot 15
        ])
        assert res["ok"], res["anomalies"]


# ---------------------------------------------------------------------------
# the certificate over a real concurrent run
# ---------------------------------------------------------------------------

class TestSiCertificate:
    """Readers through the coalescing scheduler (result cache, buffer
    pool, primary) race a DML writer with the sanitizer live; the
    history they leave holds no G1/G-SI anomaly and the sanitizer saw
    no violation."""

    def test_concurrent_serving_history_certifies(self, monkeypatch,
                                                  tmp_path):
        import threading
        from opentenbase_tpu.exec import scheduler as sm
        from opentenbase_tpu.exec import share
        from opentenbase_tpu.exec.session import LocalNode, Session
        monkeypatch.setenv("OTB_SNAPCHECK", "1")
        monkeypatch.setenv("OTB_SNAP_HISTORY", str(tmp_path / "h.json"))
        snapcheck.reset()
        sm.reset_stats()
        share.RESULT_CACHE.clear()
        node = LocalNode()
        Session(node).execute(
            "create table kv (k bigint, v bigint); "
            "insert into kv values " + ", ".join(
                f"({i}, {i * 7})" for i in range(64)))
        # the writer only touches keys >= 1000
        want = {"select v from kv where k = 3": [(21,)],
                "select count(*) from kv where k < 64": [(64,)]}
        reads = list(want)
        wrong, errs = [], []

        def reader(i):
            sess = Session(node)
            try:
                for j in range(24):
                    sql = reads[(i + j) % 2]
                    rows = sched.run(sess, sql)[-1].rows
                    if rows != want[sql]:
                        wrong.append((sql, rows))
            except Exception as e:   # noqa: BLE001 — asserted below
                errs.append(e)

        def writer():
            sess = Session(node)
            try:
                for j in range(16):
                    k = 1000 + j // 2
                    sched.run(sess, f"insert into kv values ({k}, {j})"
                              if j % 2 == 0 else
                              f"delete from kv where k = {k}")
            except Exception as e:   # noqa: BLE001 — asserted below
                errs.append(e)

        with sm.Scheduler(node=node, window_ms=5.0) as sched:
            threads = [threading.Thread(target=reader, args=(i,))
                       for i in range(4)]
            threads.append(threading.Thread(target=writer))
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
        try:
            assert errs == [] and wrong == []
            res = check_history(snapcheck.history_events())
            assert res["anomalies"] == []
            assert snapcheck.violations() == []
            assert res["writes"] >= 16 and res["reads"] > 0
            assert res["by_source"].get("cache", 0) > 0
            assert res["by_source"].get("primary", 0) > 0
            sm.assert_slot_balance()
        finally:
            snapcheck.reset()
            share.RESULT_CACHE.clear()
            sm.reset_stats()


# ---------------------------------------------------------------------------
# witnessed ⊆ statically-gated, on a real OTB_SNAPCHECK=1 workload
# ---------------------------------------------------------------------------

_WORKLOAD = """\
import json, os, sys
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.utils import snapcheck

s = Session(LocalNode())
s.execute("create table kv (k bigint primary key, v bigint) "
          "distribute by shard(k)")
s.execute("insert into kv values (1, 10), (2, 20), (3, 30)")
for _ in range(3):
    s.query("select k, v from kv where k = 2")
    s.query("select sum(v) from kv")
s.execute("insert into kv values (4, 40)")
s.query("select sum(v) from kv")
data = snapcheck.save_report(sys.argv[1])
json.dump({"n": len(data["serve_points"])}, sys.stdout)
"""


class TestWitnessSubsetOfGated:
    def test_workload_witness_validates(self, tmp_path):
        path = str(tmp_path / "witness.json")
        script = str(tmp_path / "wl.py")
        with open(script, "w") as f:
            f.write(_WORKLOAD)
        env = {**_ENV, "OTB_SNAPCHECK": "1", "PYTHONPATH": _REPO}
        env.pop("OTB_SNAP_HISTORY", None)
        proc = subprocess.run(
            [sys.executable, script, path], env=env, cwd=_REPO,
            capture_output=True, text=True, timeout=420)
        assert proc.returncode == 0, proc.stderr[-2000:]
        data = json.load(open(path))
        assert data["serve_points"], "workload witnessed no serve point"
        assert data["violations"] == [], data["violations"]

        from opentenbase_tpu.analysis.core import Project
        from opentenbase_tpu.analysis.visibility import (
            VisibilityDisciplinePass, check_witness)
        disc = VisibilityDisciplinePass(Project(_REPO, "opentenbase_tpu"))
        assert check_witness(data, disc.gated()) == []

    def test_committed_witness_validates(self):
        path = os.path.join(_REPO, "opentenbase_tpu", "analysis",
                            "visibility_witness.json")
        data = json.load(open(path))
        assert data["serve_points"], "committed witness is empty"
        assert data["violations"] == []

        from opentenbase_tpu.analysis.core import Project
        from opentenbase_tpu.analysis.visibility import (
            VisibilityDisciplinePass, check_witness)
        disc = VisibilityDisciplinePass(Project(_REPO, "opentenbase_tpu"))
        assert check_witness(data, disc.gated()) == []


# ---------------------------------------------------------------------------
# OFF-path overhead: the guard must cost < 3% of a point op
# ---------------------------------------------------------------------------

class TestOffPathOverhead:
    def test_overhead_within_three_pct_of_point_op(self, monkeypatch):
        monkeypatch.delenv("OTB_SNAPCHECK", raising=False)
        monkeypatch.delenv("OTB_SNAP_HISTORY", raising=False)

        # per-guard OFF cost: every serve site pays exactly one
        # short-circuited `enabled() or history_on()` check; argument
        # construction sits BEHIND the guard and is never built
        n = 20000

        def guards():
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                for _i in range(n):
                    if snapcheck.enabled() or snapcheck.history_on():
                        raise AssertionError("flag leaked on")
                best = min(best, time.perf_counter() - t0)
            return best / n

        # real point-op p50 with the shipped (hooked, flag-off) code
        from opentenbase_tpu.exec.session import LocalNode, Session
        s = Session(LocalNode())
        s.execute("create table pt (k bigint primary key, v bigint) "
                  "distribute by shard(k)")
        s.execute("insert into pt values (1, 10), (2, 20), (3, 30)")
        for _ in range(5):                          # warm compile
            s.query("select v from pt where k = 2")
        lat = []
        for _ in range(60):
            t0 = time.perf_counter()
            s.query("select v from pt where k = 2")
            lat.append(time.perf_counter() - t0)
        p50 = sorted(lat)[len(lat) // 2]

        per_guard = guards()
        # a point op crosses at most a handful of serve points; 16 is
        # a generous ceiling (cache + pool + scheduler + dispatch)
        assert 16 * per_guard <= 0.03 * p50, (per_guard, p50)
