"""Where the harness finds what a cell names: every file by its name under
the benchmark's directory, so that a new cell is new files and one entry of
BENCHMARK.json, and no edit to a file that is there."""

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
PACKAGE = os.path.basename(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")


def benchmark_json():
    with open(BENCHMARK_JSON) as f:
        return json.load(f)


def workload(name):
    for w in benchmark_json()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name):
    return load_json("configs", f"{name}.json")


def traffic(name):
    return load_json("traffic", f"{name}.json")


def statement(name):
    return load_json("statements", f"{name}.json")


def reference(name):
    return importlib.import_module(f"{PACKAGE}.reference.{name}")


def reader(name):
    return importlib.import_module(f"{PACKAGE}.readers.{name}")


def layer_metrics():
    """Every layer_metrics/<metric>.json, by metric name."""
    d = os.path.join(BENCH_DIR, "layer_metrics")
    return {fn[:-5]: load_json("layer_metrics", fn)
            for fn in sorted(os.listdir(d)) if fn.endswith(".json")}
