"""Compiled-program subsystem (exec/plancache.py) — canonical fragment
signatures, the bounded executable LRU, PREPARE-time AOT warmup, the
persistent XLA compilation cache, and the otb_plancache stat view.
"""

import json
import os
import subprocess
import sys

import pytest

from opentenbase_tpu.exec import plancache
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.parallel.cluster import Cluster


def _fused():
    return plancache.FUSED


def _mesh():
    return plancache.MESH


class TestCanonicalSignatures:
    """Same fragment shape + different literals = ONE compiled program
    (the literal rides as a traced input, not a baked constant)."""

    def test_fused_literal_reuse(self):
        s = Session(LocalNode())
        s.execute("create table lit_t (k bigint, v bigint)")
        s.execute("insert into lit_t values "
                  + ", ".join(f"({i}, {i * 3})" for i in range(40)))
        assert s.query("select sum(v) from lit_t where k <= 9")[0][0] \
            == sum(i * 3 for i in range(10))
        c0, h0 = _fused().compiles, _fused().hits
        assert s.query("select sum(v) from lit_t where k <= 19")[0][0] \
            == sum(i * 3 for i in range(20))
        assert _fused().compiles == c0, \
            "a literal change must not recompile the fused program"
        assert _fused().hits > h0

    def test_fused_structure_change_does_recompile(self):
        s = Session(LocalNode())
        s.execute("create table lit_u (k bigint, v bigint)")
        s.execute("insert into lit_u values (1, 2), (3, 4)")
        s.query("select sum(v) from lit_u where k <= 9")
        c0 = _fused().compiles + _fused().misses
        s.query("select sum(v + k) from lit_u where k <= 9")
        assert _fused().compiles + _fused().misses > c0

    def test_mesh_literal_reuse(self):
        cs = ClusterSession(Cluster(n_datanodes=4))
        cs.execute("create table lit_m (k bigint, v bigint) "
                   "distribute by shard(k)")
        cs.execute("insert into lit_m values "
                   + ", ".join(f"({i}, {i * 3})" for i in range(40)))
        assert cs.query("select sum(v) from lit_m where k <= 9")[0][0] \
            == sum(i * 3 for i in range(10))
        assert cs.last_query_stats()["tier"] == "mesh"
        c0, h0 = _mesh().compiles, _mesh().hits
        assert cs.query("select sum(v) from lit_m where k <= 29")[0][0] \
            == sum(i * 3 for i in range(30))
        assert cs.last_query_stats()["tier"] == "mesh"
        assert _mesh().compiles == c0, \
            "an autoprep'd literal change must reuse the mesh program"
        assert _mesh().hits > h0

    def test_dates_and_decimals_mask_too(self):
        s = Session(LocalNode())
        s.execute("create table lit_d (d date, p decimal(10,2))")
        s.execute("insert into lit_d values (date '1995-01-01', 3.50), "
                  "(date '1997-06-15', 8.25)")
        r1 = s.query("select count(*) from lit_d "
                     "where d < date '1996-01-01' and p < 5.00")
        c0 = _fused().compiles
        r2 = s.query("select count(*) from lit_d "
                     "where d < date '1998-01-01' and p < 9.00")
        assert (r1[0][0], r2[0][0]) == (1, 2)
        assert _fused().compiles == c0


class TestExecutableLru:
    def test_over_100_programs_bounded(self, monkeypatch):
        """The regression the round-5 conftest hack papered over:
        >100 distinct fragment programs in ONE process.  The LRU's
        global live-executable budget keeps the population bounded
        (deterministic eviction) — no periodic cache dropping."""
        monkeypatch.setenv("OTB_MAX_LIVE_PROGRAMS", "48")
        ncol = 12
        s = Session(LocalNode())
        cols = ", ".join(f"c{i} bigint" for i in range(ncol))
        s.execute(f"create table many_t ({cols})")
        s.execute("insert into many_t values ("
                  + ", ".join(str(i) for i in range(ncol)) + "), ("
                  + ", ".join(str(i * 2) for i in range(ncol)) + ")")
        e0 = _fused().evictions
        built = 0
        for a in range(ncol):
            for b in range(ncol):
                if built >= 110:
                    break
                r = s.query(f"select sum(c{a} + c{b} * 2) from many_t "
                            f"where c{(a + b) % ncol} >= 0")
                assert r[0][0] == (a + b * 2) * 3, (a, b)
                built += 1
        assert built >= 110
        assert _fused().evictions > e0, "the LRU must have evicted"
        total_live = _fused().live() + _mesh().live()
        assert total_live <= 48, \
            f"{total_live} live executables exceed the budget"


class TestAotWarmup:
    def test_prepare_warms_mesh_program(self):
        cs = ClusterSession(Cluster(n_datanodes=4))
        cs.execute("create table warm_t (k bigint, v bigint) "
                   "distribute by shard(k)")
        cs.execute("insert into warm_t values "
                   + ", ".join(f"({i}, {i})" for i in range(30)))
        cs.execute("prepare wq (bigint) as "
                   "select sum(v) from warm_t where k <= $1")
        assert plancache.warm_drain(timeout=120), "warmup never drained"
        c0, h0 = _mesh().compiles, _mesh().hits
        r = cs.query("execute wq (9)")
        assert r[0][0] == sum(range(10))
        assert cs.last_query_stats()["tier"] == "mesh"
        assert _mesh().hits > h0
        assert _mesh().compiles == c0, \
            "EXECUTE after PREPARE warmup must find the program compiled"

    def test_warm_statement_hot_adhoc(self):
        """The restart story's API: feed hot statements after start;
        the first ad-hoc execution finds its autoprep template AND its
        compiled mesh program already warm."""
        cs = ClusterSession(Cluster(n_datanodes=4))
        cs.execute("create table ws_t (k bigint, v bigint) "
                   "distribute by shard(k)")
        cs.execute("insert into ws_t values "
                   + ", ".join(f"({i}, {i})" for i in range(30)))
        assert cs.warm_statement(
            "select sum(v) from ws_t where k <= 5") == 1
        assert plancache.warm_drain(timeout=120)
        c0 = _mesh().compiles
        # a DIFFERENT literal: the traced-param program still serves it
        assert cs.query("select sum(v) from ws_t where k <= 9")[0][0] \
            == sum(range(10))
        assert cs.last_query_stats()["tier"] == "mesh"
        assert _mesh().compiles == c0, \
            "warm_statement must precompile the ad-hoc mesh program"

    def test_cluster_restart_restages(self, tmp_path):
        d = str(tmp_path / "cl")
        cs = ClusterSession(Cluster(n_datanodes=2, datadir=d))
        cs.execute("create table wt (k bigint, v bigint) "
                   "distribute by shard(k)")
        cs.execute("insert into wt values (1, 10), (2, 20)")
        cs.cluster.checkpoint()
        cl2 = Cluster(datadir=d)
        assert plancache.warm_drain(timeout=120)
        # the restart warm staged the recovered tables' device columns
        # into the shared buffer pool (storage/bufferpool.py)
        from opentenbase_tpu.storage.bufferpool import POOL
        staged = any(
            POOL.resident(st)
            for dn in cl2.datanodes if hasattr(dn, "cache")
            for st in [dn.stores.get("wt")] if st is not None)
        assert staged
        assert ClusterSession(cl2).query(
            "select sum(v) from wt")[0][0] == 30


class TestPersistentCache:
    def test_restart_skips_xla_compiles(self, tmp_path):
        """Two fresh processes, one cache dir: the first populates the
        persistent compilation cache, the second's queries read the
        compiled executables back from disk (the warm-restart story:
        a second top-level run against the same cache starts warm)."""
        cache = str(tmp_path / "xla")
        prog = (
            "import os\n"
            "from opentenbase_tpu.exec import plancache\n"
            "from opentenbase_tpu.exec.session import LocalNode, Session\n"
            "d = plancache.enable_persistent_cache()\n"
            "assert d == os.environ['JAX_COMPILATION_CACHE_DIR'], d\n"
            "assert plancache.persistent_cache_dir() == d\n"
            "s = Session(LocalNode())\n"
            "s.execute('create table pt (k bigint, v bigint)')\n"
            "s.execute('insert into pt values (1, 5), (2, 7)')\n"
            "assert s.query('select sum(v) from pt where k <= 2')"
            "[0][0] == 12\n"
        )
        env = dict(os.environ)
        env.update({"JAX_COMPILATION_CACHE_DIR": cache,
                    "JAX_PLATFORMS": "cpu"})
        env.pop("XLA_FLAGS", None)
        for _run in range(2):
            r = subprocess.run([sys.executable, "-c", prog], env=env,
                               capture_output=True, text=True,
                               timeout=300,
                               cwd=os.path.dirname(os.path.dirname(
                                   os.path.abspath(__file__))))
            assert r.returncode == 0, r.stderr[-2000:]
        assert os.path.isdir(cache) and any(
            f.endswith("-cache") for f in os.listdir(cache)), \
            "persistent compilation cache never populated"

    def test_env_dir_means_no_dir_set_in_code(self, monkeypatch, tmp_path):
        """$JAX_COMPILATION_CACHE_DIR set: jax reads it itself — the
        program sets NO cache directory, only the size/time thresholds."""
        import jax
        updates = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append(k))
        assert plancache.enable_persistent_cache() == str(tmp_path)
        assert updates and "jax_compilation_cache_dir" not in updates

    def test_unset_env_uses_fixed_in_repo_dir(self, monkeypatch):
        """No env: the one fixed, git-ignored path inside the checkout —
        never a datadir, a temp name, a pid or a time."""
        import jax
        updates = {}
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax.config, "update", updates.__setitem__)
        monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert plancache.enable_persistent_cache() == want
        assert plancache.enable_persistent_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_sessions_do_not_arm_the_cache(self, tmp_path):
        """Opening a durable node or cluster is not an entry point: it
        must not point the process-global compile cache anywhere."""
        import jax
        before = jax.config.jax_compilation_cache_dir
        from opentenbase_tpu.exec.session import LocalNode
        LocalNode(datadir=str(tmp_path / "n"))
        Cluster(n_datanodes=1, datadir=str(tmp_path / "c"))
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "n" / "xla-cache").exists()
        assert not (tmp_path / "c" / "xla-cache").exists()


class TestStatView:
    def test_otb_plancache_view(self):
        cs = ClusterSession(Cluster(n_datanodes=2))
        cs.execute("create table pv (k bigint, v bigint) "
                   "distribute by shard(k)")
        cs.execute("insert into pv values (1, 2), (3, 4)")
        cs.query("select sum(v) from pv where k >= 0")
        rows = cs.query("select tier, hits, misses, compiles, "
                        "compile_ms, evictions, live from otb_plancache")
        tiers = {r[0]: r for r in rows}
        assert set(tiers) == {"fused", "mesh", "plan", "autoprep"}
        mesh = tiers["mesh"]
        assert mesh[3] >= 1          # at least one compile recorded
        assert mesh[4] > 0           # with nonzero compile_ms
        total = sum(r[1] + r[2] for r in rows)
        assert total > 0
