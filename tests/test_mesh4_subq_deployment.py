"""TPC-H Q17 and Q21 on several DataNodes through the served stack (ISSUE 42,
the configuration `tpch_sf1_4dn_subq` and its cell `tpch_sf1_mesh4_subq`):
Q17 redistributes ALL of lineitem to meet part and runs its correlated
average as a partial aggregate a DataNode, a redistribute of the partials
and a final aggregate; Q21 answers EXISTS and NOT EXISTS by masks over
co-located lineitem scans, then redistributes three times under a text-keyed
partial/final aggregate.

On the CPU at SF0.01, CnServer -> ClusterSession -> planner -> MeshRunner on
four and on eight virtual DataNodes: the cell's rotation as
`benchmarks/traffic/mesh4_subq.json` defines it (loaded through
`benchmarks/lib/files.py`), every reply against the plain references under
`benchmarks/lib/limits.json`, from the `mesh` tier with no fallback, with
what the plans imply in `last_query_stats()`: among it the three counters
this cell brought, `final_aggs`, `final_agg_lanes` and `exchange_src_lanes`.
One parametrised test a case, so each counts."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import compare, datagen, files
from benchmarks.lib import stack as stack_mod
from benchmarks.lib.traffic import Mix, Statement
from opentenbase_tpu.exec.mesh_exec import mesh_runner_for
from opentenbase_tpu.storage.batch import next_pow2
from opentenbase_tpu.storage.bufferpool import POOL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpch_sf1_mesh4_subq"
SF = 0.01
LIMITS = files.load_json("lib", "limits.json")
NEW_KEYS = ("final_aggs", "final_agg_lanes", "exchange_src_lanes")
TURNS = 8           # of the rotation: Q17, Q21, ... over the pool's members


def bucket(src_lanes, ndn):
    """A redistribute's first bucket (`MeshRunner._a2a_batch`, mult 1)."""
    return min(next_pow2(src_lanes),
               max(64, next_pow2(-(-src_lanes // ndn))))


@pytest.fixture(scope="module", params=[(4, 20261003), (8, 2654435769)],
                ids=["4dn", "8dn"])
def served(request, tmp_path_factory):
    """A loaded stack a (DataNodes, data seed), the mix warmed as
    `benchmarks/run.py` warms it (every pool member once), then TURNS
    requests of the window's plan: (ndn, mix, stack, session, warm
    requests, window requests)."""
    ndn, seed = request.param
    run_dir = str(tmp_path_factory.mktemp(f"mesh4_subq_{ndn}dn"))
    data = datagen.generate(sf=SF, seed=seed)
    stack = stack_mod.Stack(ndn, os.path.join(run_dir, "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data, (), run_dir)
        mix = Mix(files.workload(CELL)["traffic"], seed, data)
        mix.build_pools()
        for st in mix.statements:
            for sql in st.setup_statements():
                client.execute(sql)
        # (a stat view is answered by the CN: the probe is one `fallback`)
        probes = len(session.fallbacks)
        warm = [mix.run_request(req, client, session)
                for req in mix.warm_requests(0)]
        plan = mix.plan(0)
        window = [mix.run_request(next(plan), client, session)
                  for _ in range(TURNS)]
        assert session.fallbacks[probes:] == []
        yield ndn, mix, stack, session, warm, window
    finally:
        stack.stop()


def test_the_rotation_is_the_traffic_files(served):
    _ndn, mix, _stack, _session, warm, window = served
    spec = files.traffic("mesh4_subq")
    assert (spec["clients"], spec["order"], spec["pool"],
            spec["served_tiers"]) == (1, "rotation", 3, ["mesh"])
    names = [s["name"] for s in spec["statements"]]
    assert names == ["q17_mesh", "q21"]
    assert [r.stmt.name for r in warm] == ["q17_mesh"] * 3 + ["q21"] * 3
    assert [r.stmt.name for r in window] == names * (TURNS // 2)
    # the pool's members in turn: the window's k-th Q17 is the k-th member
    for k, req in enumerate(window):
        assert req.params == mix.pools[req.stmt.name][(k // 2) % 3][0]
    # the statement file differs from q17.json by its set-up probe alone
    q17, mesh = files.statement("q17"), files.statement("q17_mesh")
    for key in ("draw", "params", "reference", "float_cols", "steps"):
        assert mesh[key] == q17[key], key
    assert all(k in mesh["setup_sql"][0] for k in NEW_KEYS)


@pytest.mark.parametrize("turn", range(TURNS))
def test_a_reply_of_the_rotation_answers_as_the_reference(served, turn):
    """Each reply of the window against reference/q17.py or q21.py by the
    comparison that decides `correct`, from the mesh tier, no program
    built and no class overflowed after warm-up."""
    _ndn, mix, _stack, session, _warm, window = served
    req = window[turn]
    bad, avg_gap, ulp_gap = mix.check(req, LIMITS)
    assert bad == [], bad
    assert avg_gap <= LIMITS["avg_rel_gap"]
    assert ulp_gap <= LIMITS["decimal_ulp_gap"]
    (step,) = req.steps
    assert step[0] == ("q17" if turn % 2 == 0 else "q21")
    stats = step[5]
    assert stats["tier"] == "mesh" and stats["fallback"] == ""
    assert (stats["retraces"], stats["program_calls"]) == (0, 1)
    assert stats["params_baked"] == (1 if turn % 2 == 0 else 0)
    assert session.tier_counts["mesh"] >= TURNS + 6


def test_warm_up_builds_each_program_for_all_its_members(served):
    """BRAND, CONTAINER and NATION are lifted strings: the pool's second
    and third members run the program the first built."""
    _ndn, mix, _stack, _session, warm, _window = served
    for req in warm:
        assert mix.check(req, LIMITS)[0] == []
    for first in (0, 3):
        for req in warm[first + 1:first + 3]:
            stats = req.steps[0][5]
            assert (stats["retraces"], stats["program_calls"]) == (0, 1)


def lineitem_lanes(stack):
    return POOL.mesh_peek(mesh_runner_for(stack.cluster),
                          "lineitem").staged.padded


def test_q17_counters_are_the_plans(served):
    """Q17: two redistributes (all of lineitem by l_partkey; the partial
    aggregates by l_partkey), ONE final aggregate in the program (the
    CN's keyless one runs outside it), whose input is the second
    redistribute's ndn buckets; the largest source class is lineitem's
    padded rows a DataNode; two sorted aggregates, no mask."""
    ndn, _mix, stack, _session, _warm, window = served
    seen = [r.steps[0][5] for r in window if r.stmt.name == "q17_mesh"]
    lanes = lineitem_lanes(stack)
    for stats in seen:
        assert stats["exchanges"] == 2
        assert stats["final_aggs"] == 1
        assert stats["exchange_src_lanes"] == lanes
        assert stats["sorted_aggs"] == 2
        assert stats["sorted_agg_lanes"] == lanes
        assert (stats["semi_joins"], stats["anti_joins"],
                stats["residual_semi_lanes"]) == (0, 0, 0)
        # the partials arrive in ndn buckets; what a bucket holds at
        # least is the first rung's (an overflow in warm-up doubles one)
        partial = stats["sorted_agg_groups"]
        assert stats["final_agg_lanes"] % (ndn * bucket(partial, ndn)) == 0
        assert stats["pack_lanes"] >= ndn * bucket(lanes, ndn) \
            + stats["final_agg_lanes"]
        assert (stats["pack_lanes"] - stats["final_agg_lanes"]) \
            % (ndn * bucket(lanes, ndn)) == 0
    assert len({tuple(s[k] for k in NEW_KEYS + ("pack_lanes",))
                for s in seen}) == 1, "fixed at trace time: they repeat"


EXACT = {
    # ndn: (Q17 pack_lanes, final_agg_lanes, exchange_src_lanes,
    #       Q21 pack_lanes, final_agg_lanes, exchange_src_lanes)
    4: (20480, 4096, 16384, 21504, 1024, 16384),
    8: (20480, 4096, 8192, 12288, 2048, 8192),
}


def test_the_exact_counters_of_a_reply(served):
    """The numbers themselves at SF0.01, exact on a CPU.  On eight
    DataNodes lineitem's 8,192 lanes a DataNode over eight buckets of
    1,024 are 92 % full and Q17's first call overflows both redistributes
    once (its buckets double: 8 x 2,048 + 8 x 512); at SF1 a bucket is
    72-76 % full and nothing overflows (PERF.md section 6, PR 42)."""
    ndn, _mix, _stack, _session, _warm, window = served
    q17 = window[0].steps[0][5]
    q21 = window[1].steps[0][5]
    got = tuple(q17[k] for k in ("pack_lanes",) + NEW_KEYS[1:]) \
        + tuple(q21[k] for k in ("pack_lanes",) + NEW_KEYS[1:])
    assert got == EXACT[ndn]


def test_q21_counters_are_the_plans(served):
    """Q21: the semi and the anti mask over co-located scans (no
    redistribute under them, no pair made), three redistributes (the
    survivors by l_suppkey, the supplier join's output by l_orderkey, the
    partial counts by s_name), ONE final aggregate."""
    ndn, _mix, stack, _session, _warm, window = served
    lanes = lineitem_lanes(stack)
    for stats in [r.steps[0][5] for r in window if r.stmt.name == "q21"]:
        assert stats["exchanges"] == 3
        assert (stats["semi_joins"], stats["anti_joins"],
                stats["residual_semi_lanes"]) == (2, 1, 0)
        assert stats["final_aggs"] == 1
        assert stats["exchange_src_lanes"] == lanes
        assert 0 < stats["final_agg_lanes"] < lanes
        assert stats["final_agg_lanes"] % (ndn * 64) == 0
        assert stats["pack_lanes"] > ndn * bucket(lanes, ndn) \
            + stats["final_agg_lanes"]


@pytest.mark.parametrize("qname", ["q17_mesh", "q21"])
def test_stat_view_and_explain_analyze_show_the_new_counters(served, qname):
    """`final_aggs`, `final_agg_lanes` and `exchange_src_lanes` are columns
    of `otb_stat_query` and fields of EXPLAIN ANALYZE's `Shape:` line."""
    _ndn, mix, stack, session, _warm, _window = served
    client = stack.clients[0]
    stmt = Statement(qname)
    sql = stmt.steps[0]["sql"].format(**mix.pools[qname][0][0])
    client.query(sql)
    stats = session.last_query_stats()
    (row,) = client.query("select " + ", ".join(NEW_KEYS) + " from "
                          f"otb_stat_query where qid = {stats['qid']}")
    assert row == tuple(stats[k] for k in NEW_KEYS) and min(row) > 0
    text = "\n".join(r[0] for r in client.query("explain analyze " + sql))
    line = next(ln for ln in text.splitlines() if ln.startswith("Shape: "))
    shape = {k: int(v) for k, v in (f.split("=") for f in line.split()[1:])}
    assert set(NEW_KEYS) < set(shape)
    # an instrumented cluster run is the mesh program again, its literals
    # baked (a program of its own, up its own ladder): the same shape
    assert {k: shape[k] for k in NEW_KEYS} == {k: stats[k] for k in NEW_KEYS}
    assert line.split()[-1].startswith("pack_lanes=")


def test_the_final_half_has_a_name_on_the_device(served):
    """The final half of a two-phase aggregate is traced under
    `otb.agg.final`, nested in the node's `otb.agg`: the op names tell the
    halves apart, and the benchmark's reduction (`otb\\.[a-z_]+`, the
    innermost match) still reads both as `otb.agg`."""
    from benchmarks.lib import mesh_check, xplane
    from opentenbase_tpu.exec import mesh_exec
    _ndn, mix, stack, _session, _warm, _window = served
    mesh_check.PROGRAMS.clear()
    mesh_check.arm()
    try:
        for qname in ("q17_mesh", "q21"):
            stmt = Statement(qname)
            stack.clients[0].query(stmt.steps[0]["sql"].format(
                **mix.pools[qname][0][0]))
        texts = [fn.lower(*shapes).as_text(debug_info=True)
                 for fn, shapes in mesh_check.PROGRAMS.values()]
    finally:
        mesh_exec.EXPORT_HOOK = None
        mesh_check.PROGRAMS.clear()
    assert len(texts) == 2
    for text in texts:
        names = set(re.findall(r'loc\("([^"]*otb\.[^"]*)"', text))
        final = [n for n in names if "otb.agg.final" in n]
        partial = [n for n in names
                   if "otb.agg" in n and "otb.agg.final" not in n]
        assert final and partial
        assert {xplane.scope_of(n) for n in final} == {"otb.agg"}
        assert all("otb.agg/otb.agg.final" in n for n in final)


# ---------------------------------------------------------------------------
# a bucket that overflows: one l_partkey takes a third of lineitem
# ---------------------------------------------------------------------------

def test_a_forced_bucket_overflow_is_one_retrace(tmp_path):
    """A data set in which ONE l_partkey holds 35 % of lineitem: every
    DataNode sends that key's rows to one destination, far more than
    `src_pad / ndn` (4,096 of 16,384 lanes), under two buckets' worth: the
    first call overflows the first redistribute ONCE, is replayed one
    class up and answers as the reference; the next call retraces
    nothing."""
    ndn, seed = 4, 20261003
    data = datagen.generate(sf=SF, seed=seed)
    part, li = data["part"], data["lineitem"]
    # the hot key: the first part's; the query's part: one of another
    # brand (so that the join's output stays small) with small orders
    hot, hot_brand = int(part["p_partkey"][0]), part["p_brand"][0]
    rng = np.random.default_rng(seed)
    li["l_partkey"] = np.where(rng.random(len(li["l_partkey"])) < 0.35,
                               hot, li["l_partkey"]).astype(
                                   li["l_partkey"].dtype)
    stmt = Statement("q17_mesh")
    shared = {}
    for b, c in zip(part["p_brand"], part["p_container"]):
        p = {"brand": b.decode(), "container": c.decode()}
        want = stmt.reference.expected(data, p, shared)
        if b != hot_brand and want[0][0] is not None:
            break
    stack = stack_mod.Stack(ndn, str(tmp_path / "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data, (), str(tmp_path))
        sql = stmt.steps[0]["sql"].format(**p)
        for call, retraces in enumerate((1, 0)):
            got = client.query(sql)
            bad, avg_gap, ulp_gap = compare.rows_gap(got, want,
                                                     stmt.float_cols)
            assert bad is None and avg_gap <= LIMITS["avg_rel_gap"] \
                and ulp_gap <= LIMITS["decimal_ulp_gap"], (got, want)
            stats = session.last_query_stats()
            assert stats["tier"] == "mesh" and stats["fallback"] == ""
            assert stats["retraces"] == retraces, (call, stats["retraces"])
            assert stats["program_calls"] == 1 + retraces
        # the program that answered packs the first redistribute into
        # buckets twice the first rung's
        lanes = lineitem_lanes(stack)
        assert stats["exchange_src_lanes"] == lanes
        assert stats["pack_lanes"] == \
            2 * ndn * bucket(lanes, ndn) + stats["final_agg_lanes"]
        assert session.fallbacks == []
    finally:
        stack.stop()


# ---------------------------------------------------------------------------
# the cell, from its own files
# ---------------------------------------------------------------------------

def test_benchmark_json_holds_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf1_4dn_subq", "mesh4_subq", 4)
    base, cfg = files.config("tpch_sf1_4dn"), files.config(
        "tpch_sf1_4dn_subq")
    for key in ("schema", "scale_factor", "datanodes", "chips",
                "copy_tables", "guarantees", "reduced"):
        assert cfg[key] == base[key], key
    entry = {c["name"]: c for c in bench["configs"]}["tpch_sf1_4dn_subq"]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["tpch_sf1_mesh4", CELL]
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in end["analytic_geomean_ms"]["workloads"]
    specs = files.layer_metrics()
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    mine = {m["name"] for m in bench["per_layer"]
            if m["workloads"] == [CELL]}
    assert mine == {"exchange_ms.q17", "exchange_ms.q21",
                    "final_aggs.mesh4_subq", "final_agg_lanes.mesh4_subq",
                    "exchange_src_lanes.mesh4_subq"}
    assert listed - mine == {
        "compiles_in_window", "staged_bytes.analytic",
        "execute_ms.analytic", "finalize_ms.analytic",
        "device_idle.analytic", "idle_attributed.analytic",
        "unnamed_ms.analytic", "agg_ms.q17", "join_ms.q17", "agg_ms.q21",
        "join_ms.q21", "all_to_all_ms", "all_to_all_exposed_ms",
        "pack_lanes.mesh4", "semi_joins.subq", "anti_joins.neg",
        "residual_semi_lanes.neg", "sorted_agg_lanes.subq",
        "sorted_agg_groups.subq", "retraces.fresh", "programs_built.fresh"}
    # what counts traced columns as sent bytes, or the whole table's probe
    # rows, would read over 100 % here (PERF.md section 7)
    assert not listed & {"all_to_all_ici_share", "exchange_bytes.mesh4",
                         "join_residual_roofline"}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            assert m["name"] in specs
            assert m["moves"] == "analytic_geomean_ms"


REHEARSALS = [
    (0, {"analytic_geomean_ms": None, "setup_s": None}),
    (1, {"compiles_in_window": 0, "programs_built.fresh": 0,
         "retraces.fresh": 0, "semi_joins.subq": 2, "anti_joins.neg": 1,
         "residual_semi_lanes.neg": 0, "final_aggs.mesh4_subq": 1,
         "final_agg_lanes.mesh4_subq": 4096,
         "exchange_src_lanes.mesh4_subq": 16384, "pack_lanes.mesh4": 21504,
         "sorted_agg_lanes.subq": 16384, "sorted_agg_groups.subq": 4096,
         "staged_bytes.analytic": 0, "execute_ms.analytic": None,
         "finalize_ms.analytic": None}),
]


@pytest.mark.parametrize("trace, want", REHEARSALS,
                         ids=[f"trace{t}" for t, _ in REHEARSALS])
def test_the_cell_rehearses_from_its_own_files(trace, want):
    """benchmarks/run.py on the CPU's four virtual devices at SF0.01, as
    the driver calls it: the set-up probe answered, 0 failed, every reply
    from the mesh tier, no fallback, the mesh placed on four devices with
    an all-to-all in a program, and the metrics the cell is listed under
    that a CPU run can read (a device time needs a device trace)."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", str(trace), "--rehearse-sf", str(SF)],
        cwd=ROOT, env=dict(
            os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 1, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    compared = {ln["compared"]["number"]: ln["compared"]["value"]
                for ln in lines if "compared" in ln}
    assert compared["statements_failing_the_comparison"] == 0
    assert compared["replies_from_unserved_tier"] == 0
    assert compared["fallbacks"] == 0 and compared["set_up_failures"] == 0
    assert "correct=True" in p.stdout
    last = lines[-1]
    assert last["failed"] == 0 and last["attempted"] > 0
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(want) <= set(got), got
    for name, value in want.items():
        assert value is None or got[name] == value, (name, got[name])
