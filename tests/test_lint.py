"""otblint proof (analysis/): each pass catches its known violation,
stays silent on the clean twin, and the repo itself scans clean.

Three layers:
- fixture packages written to tmp_path with exactly one violation per
  rule next to a clean twin — no false negatives, no false positives;
- scan_hlo_text unit tests on canned MLIR (no jax.export needed);
- the real gate: ``python -m opentenbase_tpu.analysis.lint --json`` as
  a subprocess over the whole repo must exit 0 with zero unsuppressed
  findings in well under the 30s CI budget, and the checked-in
  baseline must be empty for the exec/ and storage/ trees.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from opentenbase_tpu.analysis.hlo_audit import scan_hlo_text
from opentenbase_tpu.analysis.lint import lint

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
    return [(f["rule"], f["file"]) for f in report["findings"]]


# ---------------------------------------------------------------------------
# per-rule fixtures: one violation + one clean twin
# ---------------------------------------------------------------------------

class TestHostSyncPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/hot.py": """\
            import jax

            def run(x):
                return helper(x)

            def helper(x):
                y = jax.numpy.cumsum(x)
                n = int(y)        # host sync on a traced value
                return n

            def build():
                return jax.jit(run)
        """,
        "fixpkg/exec/cold.py": """\
            import jax

            def run(x):
                return helper(x)

            def helper(x):
                y = jax.numpy.cumsum(x)
                n = int(y.shape[0])   # shape is static metadata
                return n

            def build():
                return jax.jit(run)
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _scan(tmp_path, "host-sync")
        assert got == [("host-sync", "fixpkg/exec/hot.py")], got

    def test_pragma_suppresses(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/exec/hot.py"] = files["fixpkg/exec/hot.py"].replace(
            "n = int(y)        #",
            "n = int(y)  # otblint: disable=host-sync #")
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "host-sync") == []

    def test_eager_only_cuts_closure(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/exec/hot.py"] = files["fixpkg/exec/hot.py"].replace(
            "def helper(x):",
            "def helper(x):  # otblint: eager-only")
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "host-sync") == []


class TestTracePurityPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/hot.py": """\
            import jax
            import os

            def run(x):
                lim = os.environ.get("FIX_LIMIT", "0")  # mid-trace env
                return x + int(lim)

            def build():
                return jax.jit(run)
        """,
        "fixpkg/exec/cold.py": """\
            import jax
            import os

            _LIMIT = int(os.environ.get("FIX_LIMIT", "0"))  # at import

            def run(x):
                return x + _LIMIT

            def build():
                return jax.jit(run)
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _scan(tmp_path, "trace-purity")
        assert got == [("trace-purity", "fixpkg/exec/hot.py")], got


class TestProgramKeyPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/caches.py": """\
            from opentenbase_tpu.exec.plancache import ProgramCache

            CACHE = ProgramCache(8)

            def build_prog(v):
                return v

            def put_bad(key, flavor):
                prog = build_prog(flavor)   # flavor not in the key
                CACHE.put(key, prog)

            def put_good(key):
                prog = build_prog(key)
                CACHE.put(key, prog)
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"program-key"})
        got = [(f["rule"], f["file"], f["symbol"])
               for f in report["findings"]]
        assert got == [("program-key", "fixpkg/exec/caches.py",
                        "put_bad")], got
        assert "cache key" in report["findings"][0]["message"]


class TestLockDisciplinePass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/state.py": """\
            import threading

            _LOCK = threading.Lock()
            _GOOD: dict = {}   # guarded_by: _LOCK
            _BAD: dict = {}    # guarded_by: _LOCK

            def good(k, v):
                with _LOCK:
                    _GOOD[k] = v
                    if len(_GOOD) > 8:
                        _GOOD.pop(next(iter(_GOOD)))

            def bad(k, v):
                _BAD[k] = v    # write outside the declared lock
        """,
        "fixpkg/exec/naked.py": """\
            _REG: list = []    # mutated, never annotated

            def add(x):
                _REG.append(x)
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = sorted(_scan(tmp_path, "lock-discipline"))
        assert got == [("lock-discipline", "fixpkg/exec/naked.py"),
                       ("lock-discipline", "fixpkg/exec/state.py")], got

    def test_locked_pop_under_if_is_clean(self, tmp_path):
        # regression: a mutator call nested under `if` inside `with`
        # must inherit the held lock
        files = {k: v for k, v in self.FILES.items()
                 if "naked" not in k}
        _write_pkg(tmp_path, files)
        got = [f for f in _scan(tmp_path, "lock-discipline")
               if "_GOOD" in f[1] or "pop" in f[1]]
        assert got == []


class TestObsPurityPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/obs/__init__.py": "",
        "fixpkg/obs/trace.py": """\
            def span(name, **attrs):
                return None
        """,
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/hot.py": """\
            import jax
            from ..obs import trace as obs_trace

            def run(x):
                obs_trace.span("execute")   # span under a trace
                return jax.numpy.cumsum(x)

            def build():
                return jax.jit(run)
        """,
        "fixpkg/exec/cold.py": """\
            import jax
            from ..obs import trace as obs_trace

            def run(x):
                return jax.numpy.cumsum(x)

            def host(x):
                # instrumentation at the host boundary is the point
                with obs_trace.span("execute"):
                    return run(x)

            def build():
                return jax.jit(run)
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = sorted(_scan(tmp_path, "obs-purity"))
        # the call site is flagged AND the obs function it pulled into
        # the closure; cold.py's host-boundary usage stays silent
        assert got == [("obs-purity", "fixpkg/exec/hot.py"),
                       ("obs-purity", "fixpkg/obs/trace.py")], got

    def test_eager_region_exempt(self, tmp_path):
        # the engine's sanctioned traced/eager split: obs calls on the
        # eager side of an `if not _traced:` guard are host-side
        files = dict(self.FILES)
        files["fixpkg/exec/hot.py"] = files["fixpkg/exec/hot.py"].replace(
            '                obs_trace.span("execute")   '
            '# span under a trace',
            '                _traced = False\n'
            '                if not _traced:\n'
            '                    obs_trace.span("execute")')
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "obs-purity") == []


    def test_named_scope_is_allowed_inside_a_traced_closure(self, tmp_path):
        # the device-side half of the naming: metadata on the ops being
        # traced, nothing that runs — not instrumentation in this sense
        files = dict(self.FILES)
        files["fixpkg/exec/hot.py"] = """\
            import jax

            def run(x):
                with jax.named_scope("otb.scan"):
                    return jax.numpy.cumsum(x)

            def build():
                return jax.jit(run)
        """
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "obs-purity") == []

    def test_trace_annotation_lives_only_in_obs(self, tmp_path):
        # the profiler's clock is written from obs/ alone, host side:
        # an annotation anywhere else is a second tracing system
        files = dict(self.FILES)
        files["fixpkg/obs/trace.py"] = """\
            from jax.profiler import TraceAnnotation

            def span(name, **attrs):
                return TraceAnnotation("otb:" + name)
        """
        files["fixpkg/exec/hot.py"] = """\
            import jax
            from jax.profiler import TraceAnnotation

            def host(x):
                with TraceAnnotation("mine"):
                    return x

            def other(x):
                with jax.profiler.StepTraceAnnotation("step"):
                    return x
        """
        _write_pkg(tmp_path, files)
        got = sorted(_scan(tmp_path, "obs-purity"))
        assert got == [("obs-purity", "fixpkg/exec/hot.py")] * 2, got


class TestNetDeadlinePass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/net/__init__.py": "",
        "fixpkg/net/wire.py": """\
            # the frame codec: raw socket I/O is its job
            def recv_exact(sock, n):
                buf = b""
                while len(buf) < n:
                    buf += sock.recv(n - len(buf))
                return buf

            def send_msg(sock, blob):
                sock.sendall(blob)
        """,
        "fixpkg/net/client.py": """\
            import socket
            from .wire import send_msg

            def connect_bad(addr):
                return socket.create_connection(addr)  # no deadline

            def connect_good(addr):
                return socket.create_connection(addr, timeout=5.0)

            def call_bad(sock, blob):
                sock.sendall(blob)        # raw I/O outside the codec
                return sock.recv(4096)    # ditto

            def call_good(sock, blob):
                send_msg(sock, blob)

            def unbound_bad(sock):
                sock.settimeout(None)     # deadline disabled

            def rearm_good(sock):
                sock.settimeout(30.0)
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"net-deadline"})
        got = sorted((f["file"], f["symbol"])
                     for f in report["findings"])
        # wire.py (the codec) is exempt; client.py trips once per bad
        # site: connect without timeout, raw sendall, raw recv,
        # settimeout(None)
        assert got == [("fixpkg/net/client.py", "call_bad"),
                       ("fixpkg/net/client.py", "call_bad"),
                       ("fixpkg/net/client.py", "connect_bad"),
                       ("fixpkg/net/client.py", "unbound_bad")], got

    def test_pragma_suppresses(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/net/client.py"] = files[
            "fixpkg/net/client.py"].replace(
            "# no deadline", "# otblint: disable=net-deadline").replace(
            "# raw I/O outside the codec",
            "# otblint: disable=net-deadline").replace(
            "# ditto", "# otblint: disable=net-deadline").replace(
            "# deadline disabled", "# otblint: disable=net-deadline")
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "net-deadline") == []

    def test_out_of_scope_module_silent(self, tmp_path):
        # raw socket use outside net//gtm//replication is not this
        # rule's business (e.g. a test helper or the bench driver)
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/utils/__init__.py": "",
            "fixpkg/utils/probe.py": """\
                import socket

                def poke(addr):
                    s = socket.create_connection(addr)
                    s.sendall(b"x")
                    return s.recv(1)
            """,
        }
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "net-deadline") == []


class TestWaitDisciplinePass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/sched.py": """\
            import queue

            class Sched:
                def __init__(self):
                    self._q = queue.Queue(8)       # bounded
                    self._logq = queue.Queue()     # unbounded

                def park_bad(self, cv):
                    cv.wait(1.0)                   # unnamed stall

                def park_good(self, cv, xray):
                    with xray.wait_event("sched-result"):
                        cv.wait(1.0)

                def pull_bad(self):
                    return self._q.get()

                def pull_good(self, xray):
                    with xray.wait_event("sched-drain-queue"):
                        return self._q.get()

                def push_bad(self, it):
                    self._q.put(it)                # bounded: blocks

                def push_free(self, it):
                    self._logq.put(it)             # unbounded: never

                def peek_free(self):
                    return self._q.get_nowait()    # never parks
        """,
        "fixpkg/net/__init__.py": "",
        "fixpkg/net/wire.py": """\
            # frame codec: exempt — it is the mechanism under the waits
            def recv_msg(sock, expect_reply=False):
                return sock
        """,
        "fixpkg/net/client.py": """\
            from .wire import recv_msg

            def call_bad(sock):
                return recv_msg(sock, expect_reply=True)  # owed

            def call_good(sock, xray):
                with xray.wait_event("rpc-wire"):
                    return recv_msg(sock, expect_reply=True)

            def drain_free(sock):
                return recv_msg(sock)              # no reply owed
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"wait-discipline"})
        got = sorted((f["file"], f["symbol"])
                     for f in report["findings"])
        assert got == [("fixpkg/exec/sched.py", "Sched.park_bad"),
                       ("fixpkg/exec/sched.py", "Sched.pull_bad"),
                       ("fixpkg/exec/sched.py", "Sched.push_bad"),
                       ("fixpkg/net/client.py", "call_bad")], got

    def test_pragma_suppresses(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/exec/sched.py"] = files[
            "fixpkg/exec/sched.py"].replace(
            "# unnamed stall", "# otblint: disable=wait-discipline"
        ).replace(
            "return self._q.get()",
            "return self._q.get()  # otblint: disable=wait-discipline"
        ).replace(
            "# bounded: blocks", "# otblint: disable=wait-discipline")
        files["fixpkg/net/client.py"] = files[
            "fixpkg/net/client.py"].replace(
            "# owed", "# otblint: disable=wait-discipline")
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "wait-discipline") == []

    def test_out_of_scope_module_silent(self, tmp_path):
        # a bare Condition.wait outside exec//net//gtm//storage (e.g.
        # a test helper) is not this rule's business
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/utils/__init__.py": "",
            "fixpkg/utils/poll.py": """\
                def wait_for(cv):
                    cv.wait(0.5)
            """,
        }
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "wait-discipline") == []


class TestSlotDisciplinePass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/leaky.py": """\
            def run_leaky(gtm, group, sql, execute):
                if not gtm.resq_acquire(group, 8, owner="w"):
                    raise RuntimeError("shed")
                res = execute(sql)      # an exception leaks the slot
                gtm.resq_release(group, owner="w")
                return res
        """,
        "fixpkg/exec/clean.py": """\
            def run_clean(gtm, group, sql, execute):
                if not gtm.resq_acquire(group, 8, owner="w"):
                    raise RuntimeError("shed")
                try:
                    return execute(sql)
                finally:
                    gtm.resq_release(group, owner="w")

            def run_clean_inside(gtm, group, sql, execute):
                try:
                    gtm.resq_acquire(group, 8, owner="w")
                    return execute(sql)
                finally:
                    gtm.resq_release(group, owner="w")
        """,
    }

    def test_violation_and_clean_twin(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _scan(tmp_path, "slot-discipline")
        assert got == [("slot-discipline", "fixpkg/exec/leaky.py")], got

    def test_pragma_suppresses(self, tmp_path):
        files = dict(self.FILES)
        files["fixpkg/exec/leaky.py"] = files[
            "fixpkg/exec/leaky.py"].replace(
            'owner="w"):\n',
            'owner="w"):  # otblint: disable=slot-discipline\n', 1)
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "slot-discipline") == []

    def test_admit_wrapper_needs_finally_too(self, tmp_path):
        # the scheduler-side spelling: _admit() is an acquire
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/exec/__init__.py": "",
            "fixpkg/exec/sched.py": """\
                def serve(self, item):
                    self._admit(item.group, 1.0)
                    item.results = item.session.execute(item.sql)
                    self._release(item.group)
            """,
        }
        _write_pkg(tmp_path, files)
        got = _scan(tmp_path, "slot-discipline")
        assert got == [("slot-discipline", "fixpkg/exec/sched.py")], got


# ---------------------------------------------------------------------------
# HLO text scan (no jax export involved)
# ---------------------------------------------------------------------------

class TestScanHloText:
    def test_f64(self):
        txt = ("module @m {\n"
               "  func.func @main(%a: tensor<4xf64>) -> tensor<4xf64>\n"
               "}\n")
        assert [f.rule for f in scan_hlo_text("k", txt)] == ["hlo-f64"]
        assert scan_hlo_text("k", txt)[0].line == 2

    def test_host_transfer(self):
        txt = ('  %0 = stablehlo.custom_call '
               '@xla_python_cpu_callback(%arg0)\n')
        assert [f.rule for f in scan_hlo_text("k", txt)] == \
            ["hlo-host-transfer"]
        txt2 = '  "stablehlo.send"(%arg0, %tok)\n'
        assert [f.rule for f in scan_hlo_text("k", txt2)] == \
            ["hlo-host-transfer"]

    def test_dynamic_shape(self):
        txt = ("  %1 = stablehlo.real_dynamic_slice %a, %s, %l, %st :"
               " tensor<?xf32>\n")
        assert [f.rule for f in scan_hlo_text("k", txt)] == \
            ["hlo-dynamic-shape"]

    def test_clean_program(self):
        txt = ("module @m {\n"
               "  func.func @main(%a: tensor<64xf32>) {\n"
               "    %0 = stablehlo.custom_call @Sharding(%a)\n"
               "    %1 = stablehlo.dynamic_slice %0, %c\n"
               "  }\n}\n")
        assert scan_hlo_text("k", txt) == []


# ---------------------------------------------------------------------------
# the repo itself scans clean (the actual CI gate), fast
# ---------------------------------------------------------------------------

class TestRepoGate:
    def test_repo_scans_clean_under_budget(self):
        t0 = time.monotonic()
        out = subprocess.run(
            [sys.executable, "-m", "opentenbase_tpu.analysis.lint",
             "--json"],
            capture_output=True, text=True, env=_ENV, cwd=_REPO,
            timeout=120)
        took = time.monotonic() - t0
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        report = json.loads(out.stdout)
        assert report["ok"] is True
        assert report["unsuppressed"] == 0
        assert report["files"] > 50
        assert took < 30, f"lint took {took:.1f}s (budget 30s)"

    def test_combined_gate_lint_plus_hlo(self):
        # the actual CI entry: lint + kernel-battery HLO audit
        t0 = time.monotonic()
        out = subprocess.run(
            [sys.executable, "-m", "opentenbase_tpu.analysis"],
            capture_output=True, text=True, env=_ENV, cwd=_REPO,
            timeout=120)
        took = time.monotonic() - t0
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        hlo = json.loads(out.stdout.strip().splitlines()[-1])
        assert hlo["ok"] is True and hlo["export_errors"] == []
        assert hlo["kernels"] >= 20
        assert took < 30, f"gate took {took:.1f}s (budget 30s)"

    def test_baseline_empty_for_exec_and_storage(self):
        path = os.path.join(_REPO, "opentenbase_tpu", "analysis",
                            "baseline.json")
        with open(path) as fh:
            data = json.load(fh)
        burned = [s for s in data["suppressions"]
                  if s["file"].startswith(("opentenbase_tpu/exec/",
                                           "opentenbase_tpu/storage/"))]
        assert burned == [], burned


# ---------------------------------------------------------------------------
# concurrency suite (analysis/concurrency.py)
# ---------------------------------------------------------------------------

def _msgs(root, rule):
    report = lint(root=str(root), package="fixpkg", rules={rule})
    return [(f["file"], f["message"]) for f in report["findings"]]


class TestLockOrderPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/order.py": """\
            from ..utils import locks

            A = locks.Lock("exec.order.A")
            B = locks.Lock("exec.order.B")

            def fwd():
                with A:
                    with B:
                        pass

            def rev():
                with B:
                    with A:
                        pass
        """,
    }

    def test_cycle_found(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _msgs(tmp_path, "lock-order")
        assert len(got) == 1 and "potential deadlock" in got[0][1], got
        assert "exec.order.A -> exec.order.B" in got[0][1]

    CLEAN = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/order.py": """\
            from ..utils import locks

            A = locks.Lock("exec.order.A")
            B = locks.Lock("exec.order.B")

            def fwd():
                with A:
                    with B:
                        pass

            def also_fwd():
                with A:
                    with B:
                        pass
        """,
    }

    def test_consistent_order_clean(self, tmp_path):
        _write_pkg(tmp_path, self.CLEAN)
        assert _scan(tmp_path, "lock-order") == []

    def test_may_acquire_contract_feeds_graph(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/exec/__init__.py": "",
            "fixpkg/exec/contract.py": """\
                from ..utils import locks

                A = locks.Lock("exec.contract.A")
                B = locks.Lock("exec.contract.B")

                def fwd(cb):
                    with A:
                        cb()  # may-acquire: exec.contract.B

                def rev():
                    with B:
                        with A:
                            pass
            """,
        }
        _write_pkg(tmp_path, files)
        got = _msgs(tmp_path, "lock-order")
        assert len(got) == 1 and "potential deadlock" in got[0][1], got

    def test_witness_cross_check(self, tmp_path):
        # runtime witnessed an order the static graph doesn't know:
        # that is a gate failure, not a shrug
        files = dict(self.CLEAN)
        files["fixpkg/analysis/lock_order.json"] = """\
            {"edges": [["exec.order.B", "exec.order.A"],
                       ["exec.order.A", "nosuch.lock"]]}
        """
        _write_pkg(tmp_path, files)
        got = _msgs(tmp_path, "lock-order")
        assert len(got) == 2, got
        assert any("under-approximates" in m for _f, m in got), got
        assert any("unknown to the static registry" in m
                   for _f, m in got), got


class TestLockBlockingPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/blk.py": """\
            import time
            from ..utils import locks

            L = locks.Lock("exec.blk.L")

            def hot():
                with L:
                    time.sleep(0.01)
        """,
        "fixpkg/exec/blk_clean.py": """\
            import os
            from ..utils import locks

            M = locks.Lock("exec.blk_clean.M")

            def cold():
                with M:
                    p = os.path.join("a", "b")   # not a thread join
                return p
        """,
    }

    def test_sleep_under_lock_vs_clean(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _msgs(tmp_path, "lock-blocking")
        assert len(got) == 1, got
        assert got[0][0] == "fixpkg/exec/blk.py"
        assert "latency" in got[0][1] and "time.sleep" in got[0][1]

    def test_deadlock_capable_waits(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/exec/__init__.py": "",
            "fixpkg/exec/blk2.py": """\
                from ..utils import locks

                L = locks.Lock("exec.blk2.L")
                CV = locks.Condition(name="exec.blk2.CV")

                def bad_wait():
                    with L:
                        with CV:
                            CV.wait()

                def bad_join(worker):
                    with L:
                        worker.join()

                def ok_wait():
                    with CV:
                        CV.wait()   # releases the only held lock
            """,
        }
        _write_pkg(tmp_path, files)
        got = _msgs(tmp_path, "lock-blocking")
        assert len(got) == 2, got
        assert all("deadlock-capable" in m for _f, m in got), got


class TestLockAtomicityPass:
    def test_check_then_act_vs_recheck(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/exec/__init__.py": "",
            "fixpkg/exec/atom.py": """\
                from ..utils import locks

                _LOCK = locks.Lock("exec.atom._LOCK")
                _CACHE = {}   # guarded_by: _LOCK

                def bad(key):
                    v = _CACHE.get(key)
                    if v is None:
                        v = object()
                        with _LOCK:
                            _CACHE[key] = v
                    return v
            """,
            "fixpkg/exec/atom_clean.py": """\
                from ..utils import locks

                _LOCK2 = locks.Lock("exec.atom_clean._LOCK2")
                _CACHE2 = {}   # guarded_by: _LOCK2

                def good(key):
                    v = _CACHE2.get(key)
                    if v is None:
                        with _LOCK2:
                            v = _CACHE2.get(key)   # re-validate
                            if v is None:
                                v = object()
                                _CACHE2[key] = v
                    return v
            """,
        }
        _write_pkg(tmp_path, files)
        got = _msgs(tmp_path, "lock-atomicity")
        assert len(got) == 1, got
        assert got[0][0] == "fixpkg/exec/atom.py"

    def test_live_view_escape_vs_copy(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/exec/__init__.py": "",
            "fixpkg/exec/esc.py": """\
                from ..utils import locks

                _LOCKE = locks.Lock("exec.esc._LOCKE")
                _ITEMS = {}   # guarded_by: _LOCKE

                def leak():
                    with _LOCKE:
                        return _ITEMS.values()

                def safe():
                    with _LOCKE:
                        return list(_ITEMS.values())
            """,
        }
        _write_pkg(tmp_path, files)
        got = _msgs(tmp_path, "lock-atomicity")
        assert len(got) == 1 and "escape" in got[0][1], got


class TestThreadDaemonPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/threads.py": """\
            import threading

            def bad():
                t = threading.Thread(target=print)
                t.start()
                return t
        """,
        "fixpkg/exec/threads_clean.py": """\
            import threading

            def ok_daemon():
                t = threading.Thread(target=print, daemon=True)
                t.start()

            def ok_joined():
                w = threading.Thread(target=print)
                w.start()
                w.join()
        """,
    }

    def test_leaked_thread_vs_clean_twins(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _scan(tmp_path, "thread-daemon")
        assert got == [("thread-daemon", "fixpkg/exec/threads.py")], got

    def test_thread_subclass_must_daemonize(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/exec/__init__.py": "",
            "fixpkg/exec/sub.py": """\
                import threading

                class Loose(threading.Thread):
                    def run(self):
                        pass

                class Tight(threading.Thread):
                    def __init__(self):
                        super().__init__(daemon=True)
            """,
        }
        _write_pkg(tmp_path, files)
        got = _msgs(tmp_path, "thread-daemon")
        assert len(got) == 1 and "Loose" in got[0][1], got


class TestLockDisciplineBareAndMulti:
    def test_bare_pair_and_multi_with_are_held(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/exec/__init__.py": "",
            "fixpkg/exec/disc.py": """\
                from ..utils import locks

                _LOCK = locks.Lock("exec.disc._LOCK")
                _OTHER = locks.Lock("exec.disc._OTHER")
                _ITEMS = []   # guarded_by: _LOCK

                def bare_ok():
                    _LOCK.acquire()
                    try:
                        _ITEMS.append(1)
                    finally:
                        _LOCK.release()

                def multi_ok():
                    with _OTHER, _LOCK:
                        _ITEMS.append(2)

                def bad():
                    _ITEMS.append(3)
            """,
        }
        _write_pkg(tmp_path, files)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"lock-discipline"})
        got = [(f["line"], f["message"])
               for f in report["findings"]]
        assert len(got) == 1, got
        assert "without holding" in got[0][1], got


# ---------------------------------------------------------------------------
# otbcard suite (analysis/cardinality.py)
# ---------------------------------------------------------------------------

class TestHostSyncSinkSpellings:
    """Every spelling of a host sync on a traced value is a finding:
    ``.tolist()``, dotted ``jax.device_get(...)``, and the bare-name
    ``from jax import device_get`` form."""

    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/hot.py": """\
            import jax
            from jax import device_get

            def run(x):
                y = jax.numpy.cumsum(x)
                a = y.tolist()          # host sync: method
                b = jax.device_get(y)   # host sync: dotted
                c = device_get(y)       # host sync: bare from-import
                return a, b, c

            def build():
                return jax.jit(run)
        """,
        "fixpkg/exec/cold.py": """\
            import jax

            def run(x):
                y = jax.numpy.cumsum(x)
                n = y.shape[0]          # static metadata, no sync
                return y + n

            def build():
                return jax.jit(run)
        """,
    }

    def test_all_three_spellings_flagged(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"host-sync"})
        got = sorted((f["file"], f["line"]) for f in report["findings"])
        assert got == [("fixpkg/exec/hot.py", 6),
                       ("fixpkg/exec/hot.py", 7),
                       ("fixpkg/exec/hot.py", 8)], got


class TestProgramCardinalityPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/hotkeys.py": """\
            import time
            from opentenbase_tpu.exec.plancache import ProgramCache

            CACHE = ProgramCache("fix", 8)

            def next_pow2(n):
                c = 1
                while c < n:
                    c *= 2
                return c

            def put_clock(prog):
                key = (time.time(),)       # wall clock in the key
                CACHE.put(key, prog)

            def put_rowcount(store, prog):
                n = store.row_count()      # raw row count, no ladder
                CACHE.put((n,), prog)

            def put_dictorder(opts, prog):
                key = tuple(opts.items())  # iteration order in the key
                CACHE.put(key, prog)

            def put_clean(store, opts, prog):
                key = (next_pow2(store.row_count()),
                       tuple(sorted(opts.items())))
                CACHE.put(key, prog)
        """,
    }

    def test_unbounded_sources_flagged_clean_twin_silent(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"program-cardinality"})
        got = sorted(f["symbol"] for f in report["findings"])
        assert got == ["put_clock", "put_dictorder", "put_rowcount"], \
            [(f["symbol"], f["message"]) for f in report["findings"]]


class TestChunkKeyQuantization:
    """Morsel-tier key discipline: a chunk count/size reaching a
    program key raw is a finding; the chunk_class()-wrapped twin is
    silent (exec/morsel.py re-sizes its window under memory pressure,
    so an unquantized chunk geometry mints one program per downshift)."""

    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/morselkeys.py": """\
            from opentenbase_tpu.exec.plancache import ProgramCache

            CACHE = ProgramCache("fix", 8)

            def chunk_class(n):
                c = 4096
                while c < n:
                    c *= 2
                return c

            def put_chunk_size(plan_key, chunk_rows, prog):
                key = (plan_key, ("__morsel", chunk_rows))  # raw size
                CACHE.put(key, prog)

            def put_chunk_count(plan_key, n_chunks, prog):
                CACHE.put((plan_key, n_chunks), prog)       # raw count

            def put_clean(plan_key, chunk_rows, prog):
                key = (plan_key, ("__morsel", chunk_class(chunk_rows)))
                CACHE.put(key, prog)
        """,
    }

    def test_raw_chunk_geometry_flagged_quantized_twin_silent(
            self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"program-cardinality"})
        got = sorted(f["symbol"] for f in report["findings"])
        assert got == ["put_chunk_count", "put_chunk_size"], \
            [(f["symbol"], f["message"]) for f in report["findings"]]
        assert all("chunk_class" in f["message"]
                   for f in report["findings"]), report["findings"]


class TestCodecKeyQuantization:
    """Codec-tier key discipline: an encoding descriptor (FOR
    reference, dict LUT contents, Enc fields) reaching a program key
    raw is a finding; the codec_class()-quantized twin is silent
    (storage/codec.py — references and LUTs drift with appends, so an
    unquantized descriptor mints one program per drift)."""

    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/codeckeys.py": """\
            from opentenbase_tpu.exec.plancache import ProgramCache

            CACHE = ProgramCache("fix", 8)

            def codec_class(enc):
                return f"{enc.family}{enc.width}"

            def put_raw_descriptor(plan_key, enc, prog):
                key = (plan_key, ("__codec", enc))        # raw Enc
                CACHE.put(key, prog)

            def put_raw_classes(plan_key, encs, prog):
                CACHE.put((plan_key, tuple(sorted(encs))), prog)

            def put_clean(plan_key, enc, prog):
                key = (plan_key, ("__codec", codec_class(enc)))
                CACHE.put(key, prog)
        """,
    }

    def test_raw_descriptor_flagged_quantized_twin_silent(
            self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"program-cardinality"})
        got = sorted(f["symbol"] for f in report["findings"])
        assert got == ["put_raw_classes", "put_raw_descriptor"], \
            [(f["symbol"], f["message"]) for f in report["findings"]]
        assert all("codec_class" in f["message"]
                   for f in report["findings"]), report["findings"]


class TestResultKeyPass:
    """Result-cache key discipline (otbshare rung b): a wall-clock
    read or a raw row count reaching a ``ResultCache.put`` key is a
    finding; the clean twin keyed on (masked signature, literal
    vector, store-version tuple) is silent — those three inputs
    exactly determine the result, a timestamp or result size does
    not."""

    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/resultkeys.py": """\
            import time
            from opentenbase_tpu.exec.share import ResultCache

            RCACHE = ResultCache()

            def put_clock(sig, lits, gts, names, rows):
                key = (sig, lits, time.time())    # wall clock in key
                RCACHE.put(key, gts, names, rows)

            def put_rowcount(sig, lits, store, gts, names, rows):
                n = store.row_count()             # raw row count
                RCACHE.put((sig, lits, n), gts, names, rows)

            def put_rowlen(sig, lits, gts, names, rows):
                RCACHE.put((sig, lits, len(rows)), gts, names, rows)

            def put_clean(sig, lits, versions, gts, names, rows):
                key = (sig, tuple(lits), versions)
                RCACHE.put(key, gts, names, rows)
        """,
    }

    def test_clock_and_rowcount_flagged_clean_twin_silent(
            self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"result-key"})
        got = sorted(f["symbol"] for f in report["findings"])
        assert got == ["put_clock", "put_rowcount", "put_rowlen"], \
            [(f["symbol"], f["message"]) for f in report["findings"]]


class TestRetraceRiskPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/keys.py": """\
            import jax
            from opentenbase_tpu.exec.plancache import ProgramCache

            CACHE = ProgramCache("fix", 8)

            def put_list(parts, prog):
                CACHE.put([p for p in parts], prog)   # unhashable

            def put_sorted(parts, prog):
                CACHE.put((sorted(parts),), prog)     # list component

            def put_ephemeral(prog):
                scratch = {}
                CACHE.put((id(scratch),), prog)       # fresh identity

            def put_pervalue(x, prog):
                k = int(jax.numpy.sum(x))             # per-value read
                CACHE.put((k,), prog)

            def put_clean(parts, prog):
                CACHE.put(tuple(sorted(parts)), prog)
        """,
        "fixpkg/exec/traced.py": """\
            import jax

            def run(x, lim):
                if x.shape[0] > lim:   # raw shape vs runtime value
                    return x
                return x + 1

            def build():
                return jax.jit(run)
        """,
        "fixpkg/exec/traced_clean.py": """\
            import jax

            def run2(x):
                if x.shape[0] > 128:   # constant comparison: fine
                    return x
                return x + 1

            def build2():
                return jax.jit(run2)
        """,
    }

    def test_per_value_identity_flagged(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"retrace-risk"})
        got = sorted(f["symbol"] for f in report["findings"])
        assert got == ["put_ephemeral", "put_list", "put_pervalue",
                       "put_sorted", "run"], \
            [(f["symbol"], f["message"]) for f in report["findings"]]


class TestDeviceResidencyPass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/storage/__init__.py": "",
        "fixpkg/storage/stray.py": """\
            import jax

            _PARKED: dict = {}

            def park(k, x):
                _PARKED[k] = jax.device_put(x)   # untracked residency
        """,
        "fixpkg/storage/pool.py": """\
            import jax

            class Pool:
                def note_upload(self, n):
                    pass

            POOL = Pool()

            def stage(x):
                a = jax.device_put(x)
                POOL.note_upload(8)   # accounted: the pool can evict it
                return a
        """,
    }

    def test_stray_device_put_and_global_store(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        got = _scan(tmp_path, "device-residency")
        # park trips twice: the raw device_put AND the module-global
        # store of device-produced bytes; the accounting twin is silent
        assert got == [("device-residency", "fixpkg/storage/stray.py"),
                       ("device-residency",
                        "fixpkg/storage/stray.py")], got

    def test_sanctioned_staging_file_exempt(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/storage/__init__.py": "",
            "fixpkg/storage/bufferpool.py":
                self.FILES["fixpkg/storage/stray.py"],
        }
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "device-residency") == []


class TestTransferDisciplinePass:
    FILES = {
        "fixpkg/__init__.py": "",
        "fixpkg/exec/__init__.py": "",
        "fixpkg/exec/pulls.py": """\
            import jax
            import numpy as np

            def leak(x):
                y = jax.numpy.cumsum(x)
                return np.asarray(y)      # undeclared host pull

            def grab(x):
                y = jax.numpy.cumsum(x)
                return jax.device_get(y)  # undeclared host pull

            def listify(x):
                y = jax.numpy.cumsum(x)
                return y.tolist()         # undeclared host pull

            def declared(x):  # otblint: sync-boundary
                y = jax.numpy.cumsum(x)
                return np.asarray(y)

            def declared_multiline(x,
                                   n):  # otblint: sync-boundary
                y = jax.numpy.cumsum(x)
                return np.asarray(y)[:n]

            def handles(n):
                # device HANDLES, not device data — no pull
                return np.asarray(jax.devices()[:n])
        """,
    }

    def test_undeclared_pulls_flagged_boundaries_exempt(self, tmp_path):
        _write_pkg(tmp_path, self.FILES)
        report = lint(root=str(tmp_path), package="fixpkg",
                      rules={"transfer-discipline"})
        got = sorted(f["symbol"] for f in report["findings"])
        assert got == ["grab", "leak", "listify"], \
            [(f["symbol"], f["message"]) for f in report["findings"]]

    def test_out_of_scope_module_silent(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/utils/__init__.py": "",
            "fixpkg/utils/dump.py": """\
                import jax
                import numpy as np

                def snapshot(x):
                    return np.asarray(jax.numpy.cumsum(x))
            """,
        }
        _write_pkg(tmp_path, files)
        assert _scan(tmp_path, "transfer-discipline") == []


class TestRetraceWitnessPass:
    def test_bad_census_fails_gate(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/analysis/program_census.json": """\
                {"entries": [
                  {"tier": "fused", "frag": "f1", "key": "k1",
                   "classes": [["factor:j0", 1000]], "puts": 1},
                  {"tier": "mesh", "frag": "f2", "key": "k2",
                   "classes": [["pad:t", 256]], "puts": 3}
                ]}
            """,
        }
        _write_pkg(tmp_path, files)
        got = _msgs(tmp_path, "retrace-witness")
        assert len(got) == 2, got
        assert any("not ladder-shaped" in m for _f, m in got), got
        assert any("unexplained retrace" in m for _f, m in got), got

    def test_clean_census_silent(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/analysis/program_census.json": """\
                {"entries": [
                  {"tier": "mesh", "frag": "f", "key": "k",
                   "classes": [["pad:t", 256], ["factor:j", 4],
                               ["gather:0", 96]], "puts": 1}
                ]}
            """,
        }
        _write_pkg(tmp_path, files)
        assert _msgs(tmp_path, "retrace-witness") == []

    def test_unreadable_census_is_a_finding(self, tmp_path):
        files = {
            "fixpkg/__init__.py": "",
            "fixpkg/analysis/program_census.json": "{not json",
        }
        _write_pkg(tmp_path, files)
        got = _msgs(tmp_path, "retrace-witness")
        assert len(got) == 1 and "unreadable" in got[0][1], got


# ---------------------------------------------------------------------------
# CI ergonomics: --github annotations + --changed-only
# ---------------------------------------------------------------------------

_VIOLATION = """\
import threading

def bad():
    t = threading.Thread(target=print)
    t.start()
    return t
"""


def _mini_repo(tmp_path, name="threads.py"):
    pkg = tmp_path / "opentenbase_tpu" / "exec"
    pkg.mkdir(parents=True, exist_ok=True)
    (tmp_path / "opentenbase_tpu" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / name).write_text(_VIOLATION)
    return tmp_path


class TestCliErgonomics:
    def test_github_annotations(self, tmp_path):
        _mini_repo(tmp_path)
        out = subprocess.run(
            [sys.executable, "-m", "opentenbase_tpu.analysis.lint",
             "--root", str(tmp_path), "--no-baseline", "--github"],
            capture_output=True, text=True, env=_ENV, cwd=_REPO,
            timeout=120)
        assert out.returncode == 1
        assert "::error file=opentenbase_tpu/exec/threads.py,line=4::" \
            in out.stdout, out.stdout

    def test_changed_only_filters_to_merge_base(self, tmp_path):
        _mini_repo(tmp_path)

        def git(*a):
            subprocess.run(["git", *a], cwd=tmp_path, check=True,
                           capture_output=True, timeout=30)

        git("init", "-q", "-b", "main")
        git("add", "-A")
        git("-c", "user.email=t@t", "-c", "user.name=t",
            "commit", "-qm", "seed")
        # a NEW violating file on top of the committed one
        (tmp_path / "opentenbase_tpu" / "exec" /
         "threads2.py").write_text(_VIOLATION)
        env = {**_ENV}
        env.pop("OTB_LINT_BASE", None)
        out = subprocess.run(
            [sys.executable, "-m", "opentenbase_tpu.analysis.lint",
             "--root", str(tmp_path), "--no-baseline",
             "--changed-only", "--json"],
            capture_output=True, text=True, env=env, cwd=_REPO,
            timeout=120)
        assert out.returncode == 1, out.stdout + out.stderr
        report = json.loads(out.stdout)
        files = {f["file"] for f in report["findings"]}
        assert files == {"opentenbase_tpu/exec/threads2.py"}, files
