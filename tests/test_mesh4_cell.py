"""The cell `tpch_sf1_mesh4` (BENCHMARK.json) on the CPU's virtual devices
at SF0.01: the benchmark's own served stack with four DataNodes, Q3 (every
parameter pinned) and Q5 over the wire, each reply against the benchmark's
plain reference by the comparison that decides `correct`, served by the
mesh tier alone with no fallback, a compiled mesh program that holds an
all-to-all, and the exchange counted: `exchanges` and `exchange_bytes` of
`last_query_stats()` are fixed when the program is traced and repeat from
reply to reply."""

import json
import os

import pytest

from benchmarks.lib import datagen, files, mesh_check
from benchmarks.lib import stack as stack_mod
from benchmarks.lib.traffic import Mix, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpch_sf1_mesh4"


def test_benchmark_json_holds_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch_sf1_4dn", "mesh4_pinned", 4)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert files.config(cfg["name"])["datanodes"] == 4
    assert cfg["reduced"] == files.config(cfg["name"])["reduced"]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    assert {"analytic_geomean_ms", "setup_s", "all_to_all_ms",
            "all_to_all_exposed_ms", "exchange_bytes.mesh4",
            "all_to_all_ici_share", "join_ms.q3", "join_ms.q5",
            "device_idle.analytic"} <= listed
    specs = files.layer_metrics()
    for m in bench["per_layer"]:
        assert m["name"] in specs, m["name"]


@pytest.fixture(scope="module", params=[20260928, 3000000019])
def served(request, tmp_path_factory):
    """One seed's stack, loaded, with the mix's two statements run twice."""
    seed = request.param
    run_dir = str(tmp_path_factory.mktemp(f"mesh4_{seed}"))
    data = datagen.generate(sf=0.01, seed=seed)
    mesh_check.PROGRAMS.clear()
    mesh_check.arm()
    stack = stack_mod.Stack(4, os.path.join(run_dir, "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data, (), run_dir)
        mix = Mix(files.workload(CELL)["traffic"], seed, data)
        mix.build_pools()
        done = []
        for _ in range(2):
            for st in mix.statements:
                p, want = mix.pools[st.name][0]
                done.append(mix.run_request(Request(st, p, want), client,
                                            session))
        yield mix, stack, session, done
    finally:
        from opentenbase_tpu.exec import mesh_exec
        mesh_exec.EXPORT_HOOK = None
        stack.stop()


def test_replies_equal_the_plain_reference(served):
    mix, _stack, _session, done = served
    limits = files.load_json("lib", "limits.json")
    assert [r.stmt.name for r in done] == ["q3_pinned", "q5"] * 2
    for req in done:
        bad, avg_gap, ulp_gap = mix.check(req, limits)
        assert bad == [] and ulp_gap <= limits["decimal_ulp_gap"], bad


def test_mesh_tier_alone_and_no_fallback(served):
    mix, stack, session, done = served
    assert set(session.tier_counts) <= set(mix.served_tiers) == {"mesh"}
    assert session.tier_counts["mesh"] >= len(done)
    assert session.fallbacks == []
    assert mesh_check.problems(stack, 4) == []


def test_exchange_counted_and_repeats(served):
    _mix, _stack, _session, done = served
    by = {}
    for req in done:
        st = req.steps[0][5]
        assert st["tier"] == "mesh"
        by.setdefault(req.stmt.name, []).append(
            (st["exchanges"], st["exchange_bytes"], st["pack_lanes"]))
    for name, seen in by.items():
        assert len(set(seen)) == 1, (name, seen)
        exchanges, sent, lanes = seen[0]
        assert exchanges >= 1 and sent > 0
        # every redistribute's pack has ndn buckets of at least 64 slots
        assert lanes >= exchanges * 4 * 64 and lanes % (4 * 64) == 0
    # Q5 redistributes once more than Q3 (lineitem's rows to the suppliers)
    assert by["q5"][0][0] > by["q3_pinned"][0][0]
    assert by["q5"][0][2] > by["q3_pinned"][0][2]


def test_no_join_algorithm_is_chosen_by_the_data(served):
    """The compiled mesh programs hold no `conditional` under an
    `otb.join_*` scope: the join kernels' packed-or-exact sort and
    direct-or-searched probe are chosen when the program is built
    (ops/kernels.join_build), so which arm runs is not the shard's data's
    to say.  The reading can see one: the sorted aggregate's pack test
    (otb.agg) is still a conditional in Q5's program (its final aggregate
    groups by a dictionary code, whose range the host does not state;
    Q3's three keys' ranges are known and its sort is chosen when the
    program is built, PR 34)."""
    conditionals = []
    for fn, shapes in mesh_check.PROGRAMS.values():
        for line in fn.lower(*shapes).compile().as_text().splitlines():
            if " conditional(" in line and "op_name=" in line:
                conditionals.append(
                    line.split('op_name="', 1)[1].split('"', 1)[0])
    assert len(mesh_check.PROGRAMS) >= 2
    assert any("otb.agg" in c for c in conditionals), conditionals
    assert [c for c in conditionals if "otb.join_" in c] == []


def test_the_pack_is_a_gather_and_the_collectives_stand(served):
    """The compiled mesh programs hold NO scatter under `otb.exchange`:
    a destination's slot finds its source row (ops/kernels.bucket_rows)
    and the columns come through that index as 32-bit rows (take_rows);
    the scatter a column, a null mask and the validity that packed the
    buckets before cost a v5e ~30 ms an exchange.  The collectives are
    what they were: one all-to-all a column, null mask and the validity,
    9 in Q3's program (two redistributes) and 14 in Q5's (four)."""
    scatters, a2a = [], []
    for fn, shapes in mesh_check.PROGRAMS.values():
        lines = fn.lower(*shapes).compile().as_text().splitlines()
        scatters += [ln.split('op_name="', 1)[1].split('"', 1)[0]
                     for ln in lines
                     if " scatter(" in ln and "op_name=" in ln]
        a2a.append(sum(" all-to-all(" in ln for ln in lines))
    assert [s for s in scatters if "otb.exchange" in s] == []
    assert sorted(a2a) == [9, 14]
