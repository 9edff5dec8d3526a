"""The control of `correct`, at a cell's own size: for each seed, the plain
reference in the nearest precision below the configuration's (float32), and
for traffic with writes the reference with the read-your-write guarantee
broken (a stale read), put in the program's place and held to the same
comparison and limits as a run.  Every control has to come out refused.
Needs no chip (the reference runs on the host); prints one JSON line per
seed and exits 0 only if every control of every seed was refused.

    python benchmarks/control.py --workload tpch_sf1_power --seeds 1 2 3
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import control, datagen, files  # noqa: E402
from benchmarks.lib.traffic import Mix  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sf", type=float, default=None)
    ap.add_argument("--benchmark-json", default=None)
    args = ap.parse_args()
    if args.benchmark_json:
        files.BENCHMARK_JSON = os.path.abspath(args.benchmark_json)
    cell = files.workload(args.workload)
    sf = args.sf or float(files.config(cell["config"])["scale_factor"])
    limits = files.load_json("lib", "limits.json")
    all_refused = True
    for seed in args.seeds:
        data = datagen.generate(sf, seed)
        mix = Mix(cell["traffic"], seed, data)
        mix.build_pools()
        writes = [s.name for s in mix.statements
                  if any(st["check"] == "ack" for st in s.steps)]
        reads = [s.name for s in mix.statements if s.name not in writes]
        rows = control.gaps(mix, "float32", reads)
        if writes:
            rows += control.gaps(mix, "stale", writes)
        by_stmt = {}
        for name, bad, avg_gap, ulp_gap in rows:
            s = by_stmt.setdefault(name, {
                "cases": 0, "refused": 0, "exact_mismatches": 0,
                "smallest_avg_gap": None, "smallest_ulp_gap": None})
            s["cases"] += 1
            s["exact_mismatches"] += bad is not None
            s["refused"] += (bad is not None
                             or avg_gap > limits["avg_rel_gap"]
                             or ulp_gap > limits["decimal_ulp_gap"])
            for key, gap in (("smallest_avg_gap", avg_gap),
                             ("smallest_ulp_gap", ulp_gap)):
                if gap > 0 and (s[key] is None or gap < s[key]):
                    s[key] = gap
        # as in a run, one refused case makes a statement's control not
        # correct; the counts say how many of the cases were
        refused = {n: s["refused"] > 0 for n, s in by_stmt.items()}
        all_refused &= all(refused.values())
        print(json.dumps({"seed": seed, "scale_factor": sf,
                          "limits": {k: v for k, v in limits.items()
                                     if not k.startswith("_")},
                          "refused": refused, "by_statement": by_stmt}),
              flush=True)
    sys.exit(0 if all_refused else 1)


if __name__ == "__main__":
    main()
