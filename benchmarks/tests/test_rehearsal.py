"""run.py end to end at the rehearsal scale under JAX_PLATFORMS=cpu, every
cell's whole path (mesh4 on four virtual devices, from the pending
BENCHMARK.json that holds its entry).  Having found no chip it reports
failure and exits non-zero; without the rehearsal switch it prints no
result at all."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PENDING = os.path.join(ROOT, "benchmarks", "pending",
                       "tpch_sf1_mesh4.BENCHMARK.json")
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(cell, trace, extra=(), devices=1, rehearse=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", cell, "--seed", "2862933555", "--seconds", "2",
           "--trace", str(trace), *extra]
    if rehearse:
        cmd += ["--rehearse-sf", "0.01"]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)


def metric_names(bench, kind, cell):
    return {m["name"] for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell,devices,pending", [
    ("tpch_sf1_power", 1, False),
    ("tpch_sf1_point", 1, False),
    ("tpch_sf1_mesh4", 4, True),
])
def test_whole_path_at_rehearsal_scale(cell, devices, pending):
    bench_path = PENDING if pending else os.path.join(ROOT, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    extra = ["--benchmark-json", bench_path] if pending else []
    for trace, kind in ((0, "end_to_end"), (1, "per_layer")):
        p = run_cell(cell, trace, extra, devices)
        assert p.returncode == 1, p.stderr[-2000:]
        last = json.loads(p.stdout.strip().splitlines()[-1])
        want = CONTRACT_KEYS | ({"breakdown"} if trace else set())
        assert set(last) == want
        assert last["correct"] is False         # no chip: never a success
        assert last["failed"] == 0, p.stdout[-3000:]
        assert last["attempted"] > 0
        assert last["device"]["platform"] == "cpu"
        assert last["device"]["count"] == devices
        assert "memory_peak_bytes" in last["device"]
        if trace:
            assert {"busy_s", "window_s"} <= set(last["device"])
        names = metric_names(bench, kind, cell)
        # a reader that finds nothing to read leaves its metric out: the
        # CPU's trace has no device plane, so the roofline and the
        # all-to-all time are not there
        assert set(last["metrics"]) <= names
        assert names - set(last["metrics"]) <= {"q1_hbm_roofline",
                                                "all_to_all_ms"}
        for m in last["metrics"].values():
            assert set(m) == {"value", "unit"}
        assert "correct=True" in p.stdout       # what the run itself found


def test_no_chip_and_no_rehearsal_prints_nothing():
    p = run_cell("tpch_sf1_power", 0, rehearse=False)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "no accelerator" in p.stderr


def test_fewer_devices_than_the_cell_asks_prints_nothing():
    p = run_cell("tpch_sf1_mesh4", 0, ["--benchmark-json", PENDING],
                 devices=1)
    assert p.returncode == 3
    assert p.stdout == ""
