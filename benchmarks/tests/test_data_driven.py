"""A later PR's cell is files and entries, never an edit: a copy of the
benchmark gets one more config, mix, statement with its reference, and
layer metric, plus one `workloads` entry, and runs — no file that was there
is touched."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def digest(top):
    out = {}
    for d, _dirs, names in os.walk(top):
        if "__pycache__" in d or os.sep + "run_out" in d:
            continue
        for n in names:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def test_a_new_cell_is_only_new_files(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "run_out"))
    os.symlink(os.path.join(ROOT, "opentenbase_tpu"),
               root / "opentenbase_tpu")
    before = digest(root / "benchmarks")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    b = root / "benchmarks"
    cfg = json.load(open(b / "configs" / "tpch_sf1_1dn.json"))
    cfg["name"] = "tpch_tiny_1dn"
    dump(b / "configs" / "tpch_tiny_1dn.json", cfg)
    dump(b / "traffic" / "q6_two_clients.json", {
        "loop": "closed", "clients": 2, "order": "rotation", "pool": 2,
        "statements": [{"name": "q6", "share": 1}],
        "served_tiers": ["mesh", "fused"], "trace": {"seconds": 1}})
    dump(b / "statements" / "q6.json", {
        "name": "q6", "draw": "pool", "reference": "q6", "float_cols": [],
        "params": {"year": {"kind": "choice", "values": [
            "1993-01-01", "1994-01-01", "1995-01-01"]},
            "qty": {"kind": "int", "lo": 24, "hi": 25}},
        "steps": [{"class": "q6", "check": "rows", "sql":
                   "select count(*) from lineitem where l_shipdate >= "
                   "date '{year}' and l_shipdate < date '{year}' + "
                   "interval '1' year and l_quantity < {qty}"}]})
    (b / "reference" / "q6.py").write_text(
        "import numpy as np\n"
        "from benchmarks.lib.datagen import days\n\n\n"
        "def expected(data, params, shared, precision='exact'):\n"
        "    li = data['lineitem']\n"
        "    lo = days(params['year'])\n"
        "    hi = days(str(int(params['year'][:4]) + 1) + '-01-01')\n"
        "    sel = (li['l_shipdate'] >= lo) & (li['l_shipdate'] < hi) \\\n"
        "        & (li['l_quantity'] < params['qty'])\n"
        "    return [(int(sel.sum()),)]\n")
    dump(b / "layer_metrics" / "stage_ms.q6.json", {
        "layer": "staging", "reader": "query_stat",
        "args": {"key": "stage_ms", "classes": ["q6"], "reduce": "max"}})
    cell = "tpch_tiny_q6"
    bench["configs"].append({
        "name": "tpch_tiny_1dn", "source": cfg["source"],
        "file": "benchmarks/configs/tpch_tiny_1dn.json",
        "reduced": cfg["reduced"], "why": "a test's extra configuration"})
    bench["workloads"].append({
        "name": cell, "config": "tpch_tiny_1dn",
        "traffic": "q6_two_clients", "chips": 1, "why": "a test's cell"})
    bench["end_to_end"].append({
        "name": "q6_ms", "unit": "ms", "better": "lower", "bound": 0.05,
        "source": "host_clock", "workloads": [cell]})
    dump(b / "end_to_end" / "q6_ms.json", {
        "reader": "client_latency", "args": {"classes": ["q6"]}})
    bench["per_layer"].append({
        "name": "stage_ms.q6", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "staging", "moves": "q6_ms",
        "workloads": [cell]})
    dump(root / "BENCHMARK.json", bench)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for trace, want in ((0, {"q6_ms", "setup_s"}), (1, {"stage_ms.q6"})):
        p = subprocess.run(
            [sys.executable, str(b / "run.py"), "--workload", cell,
             "--seed", "77", "--seconds", "1", "--trace", str(trace),
             "--rehearse-sf", "0.01"],
            cwd=root, env=env, capture_output=True, text=True, timeout=600)
        assert p.returncode == 1, p.stderr[-2000:]
        last = json.loads(p.stdout.strip().splitlines()[-1])
        assert set(last["metrics"]) == want, p.stdout[-2000:]
        assert last["failed"] == 0 and last["attempted"] > 0
        assert "correct=True" in p.stdout

    after = digest(root / "benchmarks")
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == sorted([
        "configs/tpch_tiny_1dn.json", "traffic/q6_two_clients.json",
        "statements/q6.json", "reference/q6.py",
        "layer_metrics/stage_ms.q6.json", "end_to_end/q6_ms.json"])
