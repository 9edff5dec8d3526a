"""chip_smoke.py rehearsed on the CPU: every phase runs, and nothing but a
TPU run may end in "ok": true.  Plus the two rules the smoke leans on —
the dtype mode follows jax's own backend, and the plain reference agrees
with the independent pandas oracle."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
import tpch_oracle as O
from opentenbase_tpu.utils import dtypes

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rehearse(tmp_path, *args, devices=1):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                # keep CPU entries out of the checkout's fixed cache dir
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
                "XLA_FLAGS":
                f"--xla_force_host_platform_device_count={devices}"})
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"),
         "--sf", "0.01", *args],
        capture_output=True, text=True, env=env, cwd=_REPO, timeout=600)
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    return out, lines, {ln.get("phase"): ln for ln in lines}


def test_cpu_rehearsal_runs_every_phase_and_is_never_ok(tmp_path):
    out, lines, phases = _rehearse(tmp_path)
    assert out.returncode != 0
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert '"ok": true' not in out.stdout
    assert "failed" not in phases, phases["failed"]
    for name in ("device", "start", "load", "point_ops", "query",
                 "no_hidden_path", "end", "rehearsal"):
        assert name in phases, f"phase {name} did not run"
    dev = phases["device"]
    assert dev["dtype_mode"] == "x64"
    assert dev["compile_cache_env"] is True
    assert dev["compile_cache_dir"] == str(tmp_path / "jax_cache")
    assert phases["end"]["compile_cache_entries_after"] > 0
    assert phases["load"]["copy_served_by"]["native"] == 4
    queries = [ln for ln in lines if ln.get("phase") == "query"]
    assert [q["q"] for q in queries] == ["Q1", "Q3", "Q5"]
    for q in queries:
        assert q["correct"] and q["tier"] in ("fused", "mesh")
        assert q["warm"]["programs"] == 0 and q["warm"]["uploaded"] == 0
    assert phases["no_hidden_path"]["fallbacks"] == []


def test_four_chip_option_runs_only_the_mesh_path(tmp_path):
    out, lines, phases = _rehearse(tmp_path, "--chips", "4", devices=4)
    assert out.returncode != 0
    assert lines[-1]["ok"] is False and lines[-1]["device"]["count"] == 4
    assert "failed" not in phases, phases["failed"]
    assert not {"point_ops", "query", "no_hidden_path"} & set(phases)
    assert phases["load"]["copy_over_wire"] == []
    mq = [ln for ln in lines if ln.get("phase") == "mesh_query"]
    assert [q["q"] for q in mq] == ["Q3", "Q5"]
    for q in mq:
        assert q["correct"] and q["equals_host_tier"]
        assert q["tier"] == "mesh" and q["host_tier"] == "host"
    place = phases["mesh_placement"]
    assert place["passed"] and place["programs_with_all_to_all"] >= 1
    assert len(set(place["lineitem_shard_devices"])) == 4


def test_wanted_chip_found_none_prints_nothing(monkeypatch, capsys):
    """jax's silent CPU default standing in for a chip that was wanted:
    non-zero exit and no result line."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(chip_smoke, "device_of", lambda: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("env_mode,backend,want", [
    ("", "tpu", "tpu"), ("", "cpu", "x64"), ("", "gpu", "x64"),
    ("x64", "tpu", "x64"), ("tpu", "cpu", "tpu")])
def test_dtype_mode_follows_default_backend(monkeypatch, env_mode, backend,
                                            want):
    import jax
    monkeypatch.setattr(dtypes, "_mode", None)
    monkeypatch.setattr(dtypes, "_ENV_MODE", env_mode)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert dtypes.mode() == want


def test_rows_mismatch_is_exact_off_the_float_columns():
    want = [("A", 12.34, 0.5, 7)]
    assert chip_smoke.rows_mismatch([("A", 12.34, 0.50004, 7)], want,
                                    (2,)) is None
    assert chip_smoke.rows_mismatch([("A", 12.34, 0.51, 7)], want, (2,))
    assert chip_smoke.rows_mismatch([("A", 12.340001, 0.5, 7)], want, (2,))
    assert chip_smoke.rows_mismatch([("A", 12.34, 0.5, 8)], want, (2,))
    assert chip_smoke.rows_mismatch([], want, ())


def test_reference_agrees_with_the_pandas_oracle():
    """Two independent references on the same data: the smoke's exact
    integer-cents one and tests/tpch_oracle.py's float one."""
    from opentenbase_tpu.tpch import datagen
    data = datagen.generate(sf=0.01, seed=7)
    dfs = datagen.as_dataframes(data)
    q1 = [(r.l_returnflag, r.l_linestatus, r.sum_qty, r.sum_base_price,
           r.sum_disc_price, r.sum_charge, r.avg_qty, r.avg_price,
           r.avg_disc, r.count_order) for r in O.q1(dfs).itertuples()]
    q3 = [(r.l_orderkey, r.rev, chip_smoke._iso(r.o_orderdate),
           r.o_shippriority) for r in O.q3(dfs).itertuples()]
    q5 = [(r.n_name, r.rev) for r in O.q5(dfs).itertuples()]
    for qn, oracle in ((1, q1), (3, q3), (5, q5)):
        mine = chip_smoke.REFERENCE[qn](data)
        assert len(mine) == len(oracle) > 0
        for a, b in zip(mine, oracle):
            assert a == pytest.approx(b, rel=1e-9, abs=1e-6), f"Q{qn}"


# --- what the first chip runs found (CHANGES.md, PR 22) -------------------

_TRACE_ORDER_PROG = r"""
import hashlib
from opentenbase_tpu.exec import mesh_exec
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.parallel.cluster import Cluster
texts = []
mesh_exec.EXPORT_HOOK = lambda tag, fn, args: texts.append(
    fn.lower(*args).as_text())
s = ClusterSession(Cluster(n_datanodes=1))
s.execute("create table t (k bigint primary key, price decimal(10,2), "
          "disc decimal(10,2), tax decimal(10,2)) distribute by shard(k)")
s.execute("create table u (uk bigint primary key, tk bigint) "
          "distribute by shard(uk)")
s.execute("insert into t values " + ", ".join(
    f"({i}, {i}.25, 0.0{i % 9}, 0.0{i % 7})" for i in range(64)))
s.execute("insert into u values " + ", ".join(
    f"({100 + i}, {i % 64})" for i in range(96)))
s.execute("set enable_mesh_exchange = on")
s.query("select sum(price * (1 - disc) * (1 + tax)) from t, u where k = tk")
assert s.last_query_stats()["tier"] == "mesh" and texts
print(hashlib.md5("".join(texts).encode()).hexdigest())
"""


def test_program_text_does_not_depend_on_the_string_hash_seed():
    """The persistent compile cache only hits after a restart if a
    restarted process traces the SAME program: deferred join columns must
    materialize in a fixed order, not in set (string-hash) order."""
    digests = set()
    for seed in ("1", "2", "3", "4"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=seed,
                   OTB_FUSE_JOIN_MIN_ROWS="0")
        out = subprocess.run([sys.executable, "-c", _TRACE_ORDER_PROG],
                             capture_output=True, text=True, env=env,
                             cwd=_REPO, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        digests.add(out.stdout.strip().splitlines()[-1])
    assert len(digests) == 1, digests


def test_avg_of_decimals_sums_exactly_until_the_final_division(monkeypatch):
    """AVG over ints and scaled decimals carries an exact int64 sum and
    becomes a float only at the end: a device-float running sum is f32 on
    a TPU, and at SF1 Q1's averages came back 3.8e-4 off."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.exec.executor import Executor
    from opentenbase_tpu.parallel.cluster import Cluster
    seen = []
    orig = Executor._agg_inputs

    def spy(self, node, b, final):
        kinds, inputs, specs = orig(self, node, b, final)
        seen.append((node.mode, tuple(kinds),
                     tuple(str(i.dtype) for i in inputs)))
        return kinds, inputs, specs

    monkeypatch.setattr(Executor, "_agg_inputs", spy)
    s = ClusterSession(Cluster(n_datanodes=2))
    s.execute("create table a (k bigint primary key, d decimal(12,2), "
              "f float) distribute by shard(k)")
    s.execute("insert into a values " + ", ".join(
        f"({i}, {i}.25, {i}.5)" for i in range(40)))
    s.execute("set enable_mesh_exchange = off")
    assert s.query("select avg(d) from a")[0][0] == pytest.approx(19.75)
    assert seen and all(kinds == ("sum", "sum") and dts[0] == "int64"
                        for _, kinds, dts in seen), seen
    seen.clear()
    assert s.query("select avg(f) from a")[0][0] == pytest.approx(20.0)
    assert seen and all(kinds[0] == "sumf" for _, kinds, _ in seen), seen
