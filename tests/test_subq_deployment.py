"""TPC-H's three subquery forms over lineitem through the served stack
(ISSUE 34, the configuration `tpch_sf1_1dn_subq` and its cell
`tpch_sf1_subq`): Q4 (EXISTS: a semi join answered by a mask), Q17 (a
correlated scalar aggregate: decorrelated into a group-by joined back, its
comparison with the average decided in integers) and Q18 (IN over a grouped
HAVING: a sorted aggregate, a semi join on its output, a five-key group-by).

On the CPU at SF0.01, CnServer -> ClusterSession -> planner -> MeshRunner on
one DataNode and on four virtual ones, every reply against the plain
references of `benchmarks/reference/` under `benchmarks/lib/limits.json`,
from the `mesh` tier with no fallback, with what the plans imply in
`last_query_stats()`.  One parametrised test a case, so each counts."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import compare, datagen, files, params as params_mod
from benchmarks.lib import stack as stack_mod
from benchmarks.lib.traffic import Mix, Statement
from opentenbase_tpu.tpch.queries import Q

SF = 0.01
LIMITS = files.load_json("lib", "limits.json")
SHAPE_KEYS = ("semi_joins", "sorted_aggs", "sorted_agg_lanes",
              "sorted_agg_groups", "initplans", "anti_joins", "outer_joins",
              "residual_semi_lanes", "strpred_codes", "final_aggs",
              "final_agg_lanes", "exchange_src_lanes", "pack_lanes")
# the spec's validation values (tpch/queries.py holds them as literals)
VALIDATION = {"q4": {"date": "1993-07-01"},
              "q17": {"brand": "Brand#23", "container": "MED BOX"},
              "q18": {"quantity": 300}}


def run(stmt, params, client, session, shared, data):
    """One statement over the wire: (rows, reference rows, stats)."""
    (step,) = stmt.steps
    reply = client.query(step["sql"].format(**params))
    return (reply, stmt.reference.expected(data, params, shared),
            session.last_query_stats())


def agrees(got, want, float_cols):
    bad, avg_gap, ulp_gap = compare.rows_gap(got, want, float_cols)
    return bad is None and avg_gap <= LIMITS["avg_rel_gap"] \
        and ulp_gap <= LIMITS["decimal_ulp_gap"]


@pytest.fixture(scope="module",
                params=[(1, 20260930), (4, 20260930), (1, 2862933555),
                        (4, 2862933555)],
                ids=["1dn-a", "4dn-a", "1dn-b", "4dn-b"])
def served(request, tmp_path_factory):
    """A loaded stack a (DataNodes, data seed): (ndn, seed, data, client,
    session, the references' shared frames)."""
    ndn, seed = request.param
    run_dir = str(tmp_path_factory.mktemp(f"subq_{ndn}dn"))
    data = datagen.generate(sf=SF, seed=seed)
    stack = stack_mod.Stack(ndn, os.path.join(run_dir, "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data, (), run_dir)
        yield ndn, seed, data, client, session, {}
    finally:
        stack.stop()


# what each statement's one mesh program holds, by DataNodes: joins
# answered by a mask, sorted aggregates (on four DataNodes the final
# aggregate after a redistribute is a sorted one, Q4's five groups too)
SHAPES = {("q4", 1): (1, 0), ("q4", 4): (1, 1),
          ("q17", 1): (0, 1), ("q17", 4): (0, 2),
          ("q18", 1): (1, 2), ("q18", 4): (1, 3)}


@pytest.mark.parametrize("qname, quantity", [
    ("q4", None), ("q17", None), ("q18", 300), ("q18", 250), ("q18", 200)])
def test_subquery_statement_answers_as_the_reference(served, qname,
                                                     quantity):
    """Several parameter draws a statement (Q18's QUANTITY is pinned in the
    cell; here also at values low enough that rows exist at this scale)."""
    ndn, seed, data, client, session, shared = served
    stmt = Statement(qname)
    rng = np.random.default_rng([seed, ndn, len(qname)])
    draws = [dict(params_mod.draw(stmt.domains, rng),
                  **({"quantity": quantity} if quantity else {}))
             for _ in range(1 if quantity else 4)]
    lanes = len(data["lineitem"]["l_orderkey"])
    nonempty = 0
    for n, p in enumerate(draws):
        got, want, stats = run(stmt, p, client, session, shared, data)
        assert agrees(got, want, stmt.float_cols), (p, got[:3], want[:3])
        nonempty += bool(want) and want[0][0] is not None
        assert stats["tier"] == "mesh" and stats["fallback"] == ""
        semi, sorted_aggs = SHAPES[qname, ndn]
        assert stats["semi_joins"] == semi
        assert stats["sorted_aggs"] == sorted_aggs
        assert stats["initplans"] == 0
        if qname != "q4":
            # the largest sorted aggregate runs over lineitem's padded rows
            assert stats["sorted_agg_lanes"] >= lanes // ndn
            # into a class of its own (ISSUE 35): Q17's proven from its
            # key's span (the part keys' codec class), Q18's the first
            # rung of the ladder (its order keys' span bounds nothing
            # below the rows; every order is a group and fits, as at SF1)
            groups = stats["sorted_agg_groups"]
            assert 0 < groups <= stats["sorted_agg_lanes"]
            if ndn == 1 and qname == "q17":
                assert len(data["part"]["p_partkey"]) <= groups \
                    < stats["sorted_agg_lanes"] // 2
            elif ndn == 1:
                assert len(data["orders"]["o_orderkey"]) <= groups \
                    == stats["sorted_agg_lanes"] // 4
        else:
            assert (stats["sorted_agg_lanes"] > 0) == (sorted_aggs > 0)
            assert (stats["sorted_agg_groups"] > 0) == (sorted_aggs > 0)
        assert stats["retraces"] == 0 or n == 0
    assert session.fallbacks == []
    if quantity != 300:
        assert nonempty, "no draw had rows at this scale"


def test_an_uncorrelated_scalar_subquery_is_an_initplan(served):
    """`initplans` counts the scalar subqueries run before the program:
    none for Q4, Q17, Q18 (the test above), one here."""
    _ndn, _seed, data, client, session, _shared = served
    rows = client.query(
        "select count(*) from orders where o_totalprice > "
        "(select avg(o_totalprice) from orders)")
    total = np.rint(np.asarray(data["orders"]["o_totalprice"]) * 100)
    assert rows == [(int((total > total.mean()).sum()),)]
    stats = session.last_query_stats()
    assert stats["initplans"] == 1
    assert set(SHAPE_KEYS) <= set(stats)


@pytest.mark.parametrize("qname, sql, more", [
    ("q18", Q[18].replace("> 300", "> 250"), {}),
    # the anti join among the masks; none expands, no string set
    ("q4", Q[4].replace("and exists", "and not exists"),
     {"anti_joins": 1})])
def test_explain_analyze_shows_the_shape(served, qname, sql, more):
    ndn, _seed, _data, client, _session, _shared = served
    text = "\n".join(r[0] for r in client.query("explain analyze " + sql))
    line = next(ln for ln in text.splitlines() if ln.startswith("Shape: "))
    assert line.split()[1:3] == [
        "semi_joins=1", f"sorted_aggs={SHAPES[qname, ndn][1]}"], text
    assert "initplans=0" in line
    shape = dict(f.split("=") for f in line.split()[1:])
    assert set(SHAPE_KEYS) == set(shape)
    # an instrumented run is eager: its classes follow the counted rows
    assert (0 < int(shape["sorted_agg_groups"])) == (
        SHAPES[qname, ndn][1] > 0)
    want = dict({"anti_joins": 0, "outer_joins": 0,
                 "residual_semi_lanes": 0, "strpred_codes": 0}, **more)
    assert {k: int(shape[k]) for k in want} == want


COMPILED_AND_EAGER = {
    "q3": Q[3], "q17": Q[17], "q18": Q[18].replace("> 300", "> 250"),
    "distinct": "select l_returnflag, count(distinct l_suppkey) as "
                "suppliers, sum(l_quantity) as qty from lineitem "
                "group by l_returnflag order by l_returnflag"}


@pytest.mark.parametrize("name", list(COMPILED_AND_EAGER))
def test_the_compiled_answer_is_the_eager_tiers(served, name):
    """A class is a buffer size: the rows of the mesh program (sorted
    aggregates at a class of their own, an overflowed call replayed) are
    those of the host tier, where every operator counts its rows."""
    _ndn, _seed, _data, client, session, _shared = served
    sql = COMPILED_AND_EAGER[name]
    got = client.query(sql)
    stats = session.last_query_stats()
    assert stats["tier"] == "mesh" and stats["fallback"] == ""
    assert 0 < stats["sorted_agg_groups"] <= stats["sorted_agg_lanes"]
    session.execute("set enable_mesh_exchange = off")
    try:
        want = client.query(sql)
        assert session.last_query_stats()["tier"] == "host"
    finally:
        session.execute("set enable_mesh_exchange = on")
    assert want and agrees(got, want, ())


# ---------------------------------------------------------------------------
# Q17's tie: 5 * qty * count == sum is "not less", as numeric decides it
# ---------------------------------------------------------------------------

TIE_PARAMS = {"brand": "Brand#23", "container": "MED BOX"}
TIE_QTY_CENTS = (1, 2, 4, 7, 8, 30, 70, 110)


@pytest.fixture(scope="module")
def tied(tmp_path_factory):
    """SF0.01's data with the lineitems of a few Brand#23 / MED BOX parts
    given quantities in cents such that the part's first lineitem sits
    exactly on a fifth of the part's average."""
    seed = 34
    data = datagen.generate(sf=SF, seed=seed)
    part, li = data["part"], data["lineitem"]
    qty = np.rint(li["l_quantity"] * 100).astype(np.int64)
    lines_of = np.bincount(li["l_partkey"], minlength=len(part["p_partkey"])
                           + 1)
    chosen = [int(k) for k in part["p_partkey"] if lines_of[k] >= 3][:len(
        TIE_QTY_CENTS)]
    brand, container = part["p_brand"].copy(), part["p_container"].copy()
    ties = []
    for pk, q in zip(chosen, TIE_QTY_CENTS):
        brand[pk - 1] = TIE_PARAMS["brand"].encode()
        container[pk - 1] = TIE_PARAMS["container"].encode()
        rows = np.flatnonzero(li["l_partkey"] == pk)
        rest = 5 * q * len(rows) - q        # so that sum == 5 * q * count
        each = rest // (len(rows) - 1)
        qty[rows] = each
        qty[rows[0]] = q
        qty[rows[-1]] += rest - each * (len(rows) - 1)
        assert 5 * q * len(rows) == qty[rows].sum()
        ties.append((rows[0], len(rows), int(qty[rows].sum())))
    part["p_brand"], part["p_container"] = brand, container
    li["l_quantity"] = qty / 100.0
    run_dir = str(tmp_path_factory.mktemp("subq_tie"))
    stack = stack_mod.Stack(1, os.path.join(run_dir, "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data, (), run_dir)
        yield data, ties, qty, client, session
    finally:
        stack.stop()


def test_q17_decides_a_tie_as_numeric_does(tied):
    data, ties, qty, client, session = tied
    # the case bites: a float64 `0.2 * (sum / count)` calls some of the
    # tied rows "less"
    as_float = [qty[row] / 100 < 0.2 * (total / count / 100)
                for row, count, total in ties]
    assert any(as_float), "no tie that float64 gets wrong: rebuild the case"
    stmt = Statement("q17")
    got, want, stats = run(stmt, TIE_PARAMS, client, session, {}, data)
    assert want[0][0] is not None
    assert agrees(got, want, stmt.float_cols), (got, want)
    assert stats["tier"] == "mesh" and stats["fallback"] == ""
    # and the comparison refuses the answer that takes the ties as "less"
    price = np.rint(data["lineitem"]["l_extendedprice"] * 100)
    wrong = want[0][0] + sum(
        price[row] for (row, _c, _t), f in zip(ties, as_float) if f) / 700
    assert not agrees([(wrong,)], want, stmt.float_cols)


# ---------------------------------------------------------------------------
# the statements' files and the controls of their references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qname", ["q4", "q17", "q18"])
def test_statement_file_holds_the_spec_query(qname):
    """The cell's SQL is opentenbase_tpu/tpch/queries.py's text, parameters
    as {...}: with the validation values put in, the same words."""
    (step,) = files.statement(qname)["steps"]
    assert step["sql"].format(**VALIDATION[qname]).split() == \
        Q[int(qname[1:])].split()


def test_the_new_references_import_nothing_of_the_program():
    for qname in ("q4", "q17", "q18"):
        with open(files.reference(qname).__file__) as f:
            assert "opentenbase_tpu" not in f.read()


@pytest.fixture(scope="module")
def plain():
    return datagen.generate(sf=0.05, seed=77)


@pytest.mark.parametrize("qname, cases", [
    ("q4", [{"date": d} for d in ("1993-07-01", "1995-02-01",
                                  "1997-10-01")]),
    ("q18", [{"quantity": q} for q in (300, 280, 250)])])
def test_the_control_of_a_new_reference_is_refused(plain, qname, cases):
    """The reference's control arm put in the program's place: float32 for
    Q18 (an order's total in cents has no float32 past 2**24), the nearest
    formulation below for Q4 (EXISTS as a join: Q4 holds no decimal, so no
    precision below moves it), each refused by lib/compare.py under
    lib/limits.json in every case that has rows."""
    stmt, shared = Statement(qname), {}
    seen = 0
    for p in cases:
        want = stmt.reference.expected(plain, p, shared)
        got = stmt.reference.expected(plain, p, shared, "float32")
        if want:
            seen += 1
            assert not agrees(got, want, stmt.float_cols), (p, got[:2])
    assert seen >= 2


def test_q17s_float32_control_is_refused_where_a_tie_exists(tied):
    """On the generator's whole-number quantities no float rounding moves
    Q17's inner comparison and a float32 sum of its few hundred prices
    stays inside the AVG limit (PERF.md section 2 says so); where the data
    holds ties, float32 takes some as "less" and the reply is refused."""
    data = tied[0]
    stmt = Statement("q17")
    want = stmt.reference.expected(data, TIE_PARAMS, {})
    got = stmt.reference.expected(data, TIE_PARAMS, {}, "float32")
    assert not agrees(got, want, stmt.float_cols), (got, want)


# ---------------------------------------------------------------------------
# the two cells, from their own files
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUBQ, THROUGHPUT = "tpch_sf1_subq", "tpch_sf1_throughput"


def test_benchmark_json_holds_both_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells[SUBQ]["config"], cells[SUBQ]["traffic"],
            cells[SUBQ]["chips"]) == ("tpch_sf1_1dn_subq", "subq", 1)
    assert (cells[THROUGHPUT]["config"], cells[THROUGHPUT]["traffic"],
            cells[THROUGHPUT]["chips"]) == ("tpch_sf1_1dn_qgen",
                                            "throughput", 1)
    base, cfg = files.config("tpch_sf1_1dn"), files.config(
        "tpch_sf1_1dn_subq")
    for key in ("schema", "scale_factor", "datanodes", "chips", "layout",
                "copy_tables", "guarantees"):
        assert cfg[key] == base[key], key
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert SUBQ in end["analytic_geomean_ms"]["workloads"]
    assert THROUGHPUT in end["stmt_rate"]["workloads"]
    assert THROUGHPUT not in end["analytic_geomean_ms"]["workloads"]
    specs = files.layer_metrics()
    for m in bench["per_layer"]:
        assert m["name"] in specs and m["workloads"], m["name"]
        # a cell is listed only under a metric that moves what it reports
        for cell in m["workloads"]:
            assert cell in end[m["moves"]].get("workloads", [cell]), \
                (m["name"], cell)


REHEARSALS = [
    (SUBQ, 0, {"analytic_geomean_ms": None, "setup_s": None}),
    (SUBQ, 1, {"compiles_in_window": 0, "programs_built.fresh": 0,
               "params_baked.fresh": 1, "retraces.fresh": 0,
               "semi_joins.subq": 1, "initplans.subq": 0,
               "sorted_agg_lanes.subq": None, "sorted_agg_groups.subq": None,
               "execute_ms.analytic": None}),
    (THROUGHPUT, 0, {"stmt_rate": None, "setup_s": None}),
    (THROUGHPUT, 1, {"compiles_in_window.throughput": 0,
                     "programs_built.throughput": 0,
                     "params_baked.throughput": 0,
                     "retraces.throughput": 0,
                     "execute_ms.throughput": None}),
]


@pytest.mark.parametrize("cell, trace, want", REHEARSALS,
                         ids=[f"{c}-trace{t}" for c, t, _ in REHEARSALS])
def test_the_cell_rehearses_from_its_own_files(cell, trace, want):
    """benchmarks/run.py on the CPU at SF0.01, as the driver calls it: 0
    failed, every reply from a served tier, no fallback, and the metrics
    the cell is listed under."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "3",
         "--trace", str(trace), "--rehearse-sf", "0.01"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
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


def test_throughput_streams_have_their_own_orders_and_draws():
    """Each of the four clients permutes every block of Q1, Q3, Q5 for
    itself and draws its own parameters (clause 5.3's streams)."""
    data = datagen.generate(sf=SF, seed=5)
    mix = Mix(files.workload(THROUGHPUT)["traffic"], 3000000019, data)
    assert mix.clients == 4
    streams = []
    for client in range(mix.clients):
        plan = mix.plan(client)
        streams.append([next(plan) for _ in range(12)])
    for reqs in streams:
        for block in range(0, 12, 3):
            assert sorted(r.stmt.name for r in reqs[block:block + 3]) == \
                ["q1_fresh", "q3_fresh", "q5_fresh"]
    orders = {tuple(r.stmt.name for r in reqs) for reqs in streams}
    draws = {tuple(tuple(sorted(r.params.items())) for r in sorted(
        reqs, key=lambda r: r.stmt.name)) for reqs in streams}
    assert len(orders) == 4 and len(draws) == 4


def test_every_throughput_stream_completes_statements(tmp_path):
    """Four connections in the mesh tier at once, three seconds: every
    session answers statements, all as the reference does."""
    seed = 3000000019
    data = datagen.generate(sf=SF, seed=seed)
    stack = stack_mod.Stack(1, str(tmp_path / "cluster"))
    try:
        conns = [stack.connect()]
        stack_mod.load_tpch(stack, conns[0][0], data, (), str(tmp_path))
        mix = Mix(files.workload(THROUGHPUT)["traffic"], seed, data)
        mix.build_pools()
        for st in mix.statements:
            for sql in st.setup_statements():
                conns[0][0].execute(sql)
        conns += [stack.connect() for _ in range(mix.clients - 1)]
        for i, (client, session) in enumerate(conns):
            for req in mix.warm_requests(i):
                mix.run_request(req, client, session)
        warmed = [sum(s.tier_counts.values()) for _c, s in conns]
        requests, _t0, _t1 = mix.drive(conns, 3.0)
        for req in requests:
            bad, _a, _u = mix.check(req, LIMITS)
            assert bad == [], bad
        for (_c, s), before in zip(conns, warmed):
            assert sum(s.tier_counts.values()) > before
            assert set(s.tier_counts) <= mix.served_tiers
            assert s.fallbacks == []
    finally:
        stack.stop()


# ---------------------------------------------------------------------------
# what the deployment forced in the engine, each at its smallest
# ---------------------------------------------------------------------------

def test_the_in_subquery_filters_orders_before_the_customer_join(served):
    """Q18's semi join sits on `orders` alone, below the join with
    customer: a few dozen orders reach that join, not 1.5 M pairs whose
    class overflows twice (three programs built where one does)."""
    _ndn, _seed, _data, client, _session, _shared = served
    plan = [r[0] for r in client.query("explain " + Q[18])]
    semi = next(i for i, ln in enumerate(plan) if "HashJoin semi" in ln)
    assert "SeqScan orders" in plan[semi + 1]
    customer = next(i for i, ln in enumerate(plan)
                    if "SeqScan customer" in ln)
    assert customer < semi      # the semi join is the customer join's input


@pytest.mark.parametrize("ndn", [1, 4])
def test_an_aggregate_without_group_by_over_no_row_is_null(ndn):
    """SUM, MIN, MAX and AVG over no row are NULL and COUNT is 0 (Q17 at
    a BRAND and CONTAINER no part has: NULL / 7.0, not 0.0)."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.parallel.cluster import Cluster
    s = ClusterSession(Cluster(n_datanodes=ndn))
    s.execute("create table nt (k bigint primary key, v decimal(15,2)) "
              "distribute by shard(k)")
    s.execute("insert into nt values (1, 2.50), (2, 3.25), (3, 4.00)")
    sql = "select sum(v), min(v), max(v), avg(v), count(*), sum(v) / 7.0 " \
          "from nt where k > {k}"
    assert s.query(sql.format(k=100)) == [(None, None, None, None, 0, None)]
    assert s.query(sql.format(k=1)) == [(7.25, 3.25, 4.0, 3.625, 2,
                                         7.25 / 7.0)]


@pytest.mark.parametrize("key_spans", [None, (999,), (1 << 62,),
                                       (999, None)])
def test_sorted_aggregate_is_the_same_whichever_sort_is_built(key_spans):
    """`grouped_agg_sort` with the sort chosen when the program is built
    (the bound proves the pack; it cannot; nothing known) groups as the
    data-chosen one does."""
    import jax.numpy as jnp
    from opentenbase_tpu.ops import kernels as K
    rng = np.random.default_rng(34)
    n = 4096
    k1 = jnp.asarray(rng.integers(1000, 2000, n))
    k2 = jnp.asarray(rng.integers(-3, 4, n))
    valid = jnp.asarray(rng.random(n) < 0.9)
    vals = jnp.asarray(rng.integers(0, 10**6, n))
    keys = (k1,) if key_spans is None or len(key_spans) == 1 else (k1, k2)

    def agg(spans):
        gk, outs, ng = K.grouped_agg_sort(
            keys, valid, (vals, vals), n, ("sum", "max"), key_spans=spans)
        ng = int(ng)
        return [np.asarray(a)[:ng].tolist() for a in (*gk, *outs)]
    assert agg(key_spans) == agg(None)


@pytest.mark.parametrize("dtype, lo, live", [
    ("int64", -700, 0.9), ("int32", 5, 0.5), ("int64", 1 << 40, 1.0),
    ("int64", 0, 0.0)])
def test_one_packed_key_is_read_from_the_sorted_image(dtype, lo, live):
    """Where ONE key's bound proves the pack, a group's key is its offset
    in the sorted image plus the least key, not a gather of the column
    (most slots lie past the last group and would read ONE address, whose
    cost on the chip went by where the column lay): the same keys and
    totals as the gather gives, whatever the key's dtype and least value,
    with no live row too."""
    import jax.numpy as jnp
    from opentenbase_tpu.ops import kernels as K
    rng = np.random.default_rng(3434)
    n = 2048
    key = jnp.asarray(rng.integers(lo, lo + 300, n), dtype)
    valid = jnp.asarray(rng.random(n) < live)
    vals = jnp.asarray(rng.integers(0, 10**6, n))

    def agg(spans):
        (gk,), outs, ng = K.grouped_agg_sort(
            (key,), valid, (vals, vals), n, ("sum", "count"),
            key_spans=spans)
        assert gk.dtype == key.dtype
        ng = int(ng)
        return [np.asarray(a)[:ng].tolist() for a in (gk, *outs)]
    got = agg((299,))
    assert got == agg(None)
    assert len(got[0]) == len(set(np.asarray(key)[np.asarray(valid)]
                                  .tolist()))
