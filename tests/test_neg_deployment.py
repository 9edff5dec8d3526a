"""TPC-H's three ways of asking for what does NOT match, through the served
stack (ISSUE 39, the configuration `tpch_sf1_1dn_neg` and its cell
`tpch_sf1_neg`): Q13 (a LEFT OUTER JOIN under a count, its ON clause's NOT
LIKE a filter of the right input, decided a dictionary value and read as a
bitmap), Q21 (EXISTS and NOT EXISTS over lineitem with a `<>` residual, each
answered by a mask: no pair is made) and Q22 (NOT EXISTS as an anti mask
beside an uncorrelated scalar subquery whose average is compared in
integers).

On the CPU at SF0.01, CnServer -> ClusterSession -> planner -> MeshRunner on
one DataNode and on four virtual ones, every reply against the plain
references of `benchmarks/reference/` under `benchmarks/lib/limits.json`,
from the `mesh` tier with no fallback, with what the plans imply in
`last_query_stats()`.  One parametrised test a case, so each counts."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import compare, datagen, files
from benchmarks.lib import stack as stack_mod
from benchmarks.lib.traffic import Statement
from opentenbase_tpu.exec import plancache
from opentenbase_tpu.ops import kernels as K
from opentenbase_tpu.tpch.queries import Q

SF = 0.01
LIMITS = files.load_json("lib", "limits.json")
NEW_KEYS = ("anti_joins", "outer_joins", "residual_semi_lanes",
            "strpred_codes")
# the spec's validation values (tpch/queries.py holds them as literals)
CODES = ("i1", "i2", "i3", "i4", "i5", "i6", "i7")
VALIDATION = {"q13": {"word1": "special", "word2": "requests"},
              "q21": {"nation": "SAUDI ARABIA"},
              "q22": dict(zip(CODES, (13, 31, 23, 29, 30, 18, 17)))}
# clause 2.4.13.3's WORD1 and WORD2: all 16 pairs, four a served stack
WORD_PAIRS = [(a, b) for a in ("special", "pending", "unusual", "express")
              for b in ("packages", "requests", "accounts", "deposits")]
STACKS = [(1, 20260930), (4, 20260930), (1, 2862933555), (4, 2862933555)]


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


@pytest.fixture(scope="module", params=STACKS,
                ids=["1dn-a", "4dn-a", "1dn-b", "4dn-b"])
def served(request, tmp_path_factory):
    """A loaded stack a (DataNodes, data seed): (ndn, seed, data, client,
    session, the references' shared frames)."""
    ndn, seed = request.param
    run_dir = str(tmp_path_factory.mktemp(f"neg_{ndn}dn"))
    data = datagen.generate(sf=SF, seed=seed)
    stack = stack_mod.Stack(ndn, os.path.join(run_dir, "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data, (), run_dir)
        yield ndn, seed, data, client, session, {}
    finally:
        stack.stop()


def draws(qname, ndn, seed, n):
    """The n-th parameter set of a statement on a served stack."""
    if qname == "q13":
        a, b = WORD_PAIRS[4 * STACKS.index((ndn, seed)) + n]
        return {"word1": a, "word2": b}
    rng = np.random.default_rng([seed, ndn, n, len(qname)])
    if qname == "q21":
        return {"nation": datagen.NATIONS[int(rng.integers(0, 25))][0]}
    # seven distinct country codes of the generator's 25 (clause 2.4.22.3;
    # benchmarks/lib/datagen.py's are the nation's key plus 11)
    return dict(zip(CODES, (int(c) for c in rng.permutation(25)[:7] + 11)))


def shape_of(qname, ndn, data):
    """What the statement's mesh program holds, by DataNodes (on four, the
    final aggregate after a redistribute is a sorted one more)."""
    distinct = lambda t, c: len(np.unique(data[t][c]))  # noqa: E731
    return {
        "q13": dict(semi_joins=0, anti_joins=0, outer_joins=1,
                    sorted_aggs=2 + (ndn > 1), initplans=0,
                    strpred_codes=distinct("orders", "o_comment")),
        "q21": dict(semi_joins=2, anti_joins=1, outer_joins=0,
                    sorted_aggs=int(ndn > 1), initplans=0, strpred_codes=0),
        "q22": dict(semi_joins=1, anti_joins=1, outer_joins=0,
                    sorted_aggs=int(ndn > 1), initplans=1,
                    strpred_codes=distinct("customer", "c_phone")),
    }[qname]


CASES = [("q13", n) for n in range(4)] + [("q21", n) for n in range(5)] \
    + [("q22", n) for n in range(3)]


@pytest.mark.parametrize("qname, n", CASES,
                         ids=[f"{q}-{n}" for q, n in CASES])
def test_statement_answers_as_the_reference(served, qname, n):
    """Q13 over all 16 WORD pairs (four a stack), Q21 over five nations,
    Q22 over three draws of seven codes: the reference's rows, tier mesh,
    no fallback, and the plan's shape in the counters."""
    ndn, seed, data, client, session, shared = served
    stmt = Statement(qname)
    p = draws(qname, ndn, seed, n)
    got, want, stats = run(stmt, p, client, session, shared, data)
    assert agrees(got, want, stmt.float_cols), (p, got[:3], want[:3])
    assert stats["tier"] == "mesh" and stats["fallback"] == ""
    for key, value in shape_of(qname, ndn, data).items():
        assert stats[key] == value, (key, stats[key], value)
    # no semi or anti join expands into pairs: each is a mask
    assert stats["residual_semi_lanes"] == 0
    assert (stats["initplan_ms"] > 0) == (qname == "q22")
    if qname == "q13":
        assert want[0][0] == 0, "the customers without an order lead"
        # a new pattern is a new program, and ONE: the outer join's class
        # starts at the larger input (on one DataNode, orders' padded
        # rows hold every pair), no rung of the ladder is overflowed
        assert ndn > 1 or (stats["retraces"], stats["program_calls"]) \
            == (0, 1)
    if qname == "q22":
        assert len(want) == 7 and stats["program_calls"] == 2
    assert session.fallbacks == []


def test_a_second_nation_builds_no_program(served):
    """Q21's NATION is a lifted string: another value runs the programs
    the first built (no trace, no XLA request, no retrace)."""
    ndn, seed, data, client, session, shared = served
    stmt = Statement("q21")
    run(stmt, {"nation": "GERMANY"}, client, session, shared, data)
    requests = []
    listener = lambda event, **_kw: requests.append(event)  # noqa: E731
    jax.monitoring.register_event_listener(listener)
    try:
        programs = sum(r[3] for r in plancache.stats())
        for nation in ("KENYA", "UNITED STATES"):
            got, want, stats = run(stmt, {"nation": nation}, client,
                                   session, shared, data)
            assert agrees(got, want, stmt.float_cols), nation
            assert stats["retraces"] == 0 and stats["params_baked"] == 0
            assert stats["program_calls"] == 1
        assert sum(r[3] for r in plancache.stats()) == programs
        assert "/jax/compilation_cache/compile_requests_use_cache" \
            not in requests
    finally:
        jax.monitoring.unregister_event_listener(listener)


STATEMENT_SHAPES = [(qname, Q[int(qname[1:])]) for qname in VALIDATION]


@pytest.mark.parametrize("qname, sql", STATEMENT_SHAPES,
                         ids=[q for q, _ in STATEMENT_SHAPES])
def test_stat_view_and_explain_analyze_show_the_counters(served, qname,
                                                         sql):
    """The four new counters and the init plans' time are columns of
    `otb_stat_query` and fields of EXPLAIN ANALYZE's `Shape:` line."""
    ndn, _seed, data, client, session, _shared = served
    client.query(sql)
    stats = session.last_query_stats()
    (row,) = client.query(
        "select " + ", ".join(NEW_KEYS) + ", initplan_ms from "
        f"otb_stat_query where qid = {stats['qid']}")
    assert row[:4] == tuple(stats[k] for k in NEW_KEYS)
    assert row[4] == pytest.approx(stats["initplan_ms"])
    want = shape_of(qname, ndn, data)
    assert dict(zip(NEW_KEYS, row)) == {
        "anti_joins": want["anti_joins"], "residual_semi_lanes": 0,
        "outer_joins": want["outer_joins"],
        "strpred_codes": want["strpred_codes"]}
    text = "\n".join(r[0] for r in client.query("explain analyze " + sql))
    line = next(ln for ln in text.splitlines() if ln.startswith("Shape: "))
    shape = {k: int(v) for k, v in (f.split("=") for f in line.split()[1:])}
    assert set(NEW_KEYS) < set(shape)
    # an instrumented run is eager: the joins and the predicates are the
    # same ones, counted as the compiled program's are (and it lifts no
    # literal: Q21's `n_name = '...'` is a string predicate of one code)
    want["strpred_codes"] = max(want["strpred_codes"], qname == "q21")
    for key in ("anti_joins", "outer_joins", "strpred_codes"):
        assert shape[key] == want[key], (key, line)
    assert shape["residual_semi_lanes"] == 0
    assert shape["initplans"] == want["initplans"]


# ---------------------------------------------------------------------------
# the statements' files and the controls of their references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qname", list(VALIDATION))
def test_statement_file_holds_the_spec_query(qname):
    """The cell's SQL is opentenbase_tpu/tpch/queries.py's text, parameters
    as {...}: with the validation values put in, the same words; and what
    the cell pins are those values."""
    spec = files.statement(qname)
    (step,) = spec["steps"]
    assert step["sql"].format(**VALIDATION[qname]).split() == \
        Q[int(qname[1:])].split()
    if qname != "q21":
        assert spec["pinned"] == [VALIDATION[qname]]
    else:
        assert spec["params"]["nation"]["values"] == [
            n for n, _r in datagen.NATIONS]


def test_the_new_references_import_nothing_of_the_program():
    for qname in VALIDATION:
        with open(files.reference(qname).__file__) as f:
            assert "opentenbase_tpu" not in f.read()


@pytest.fixture(scope="module", params=[77, 2862933555], ids=["a", "b"])
def plain(request):
    """Host only, at a scale where a code's balances pass 2**24 cents."""
    return datagen.generate(sf=0.1, seed=request.param)


@pytest.mark.parametrize("qname, cases", [
    ("q13", [{"word1": a, "word2": b} for a, b in WORD_PAIRS[::5]]),
    ("q21", [{"nation": n} for n in ("SAUDI ARABIA", "FRANCE", "PERU")]),
    ("q22", [VALIDATION["q22"],
             dict(zip(CODES, (11, 12, 14, 15, 16, 19, 20)))])])
def test_the_control_of_a_new_reference_is_refused(plain, qname, cases):
    """The reference's control arm put in the program's place: float32 for
    Q22 (a code's balances in cents have no float32 past 2**24), the
    nearest formulation below for the two that hold no decimal (Q13 as an
    inner join: the `c_count = 0` row is gone; Q21 without its NOT EXISTS),
    each refused by lib/compare.py under lib/limits.json in every case."""
    stmt, shared = Statement(qname), {}
    for p in cases:
        want = stmt.reference.expected(plain, p, shared)
        got = stmt.reference.expected(plain, p, shared, "float32")
        assert want and not agrees(got, want, stmt.float_cols), (p, got[:2])


# ---------------------------------------------------------------------------
# the cell, from its own files
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEG = "tpch_sf1_neg"


def test_benchmark_json_holds_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[NEG]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf1_1dn_neg", "neg", 1)
    base, cfg = files.config("tpch_sf1_1dn"), files.config(
        "tpch_sf1_1dn_neg")
    for key in ("schema", "scale_factor", "datanodes", "chips", "layout",
                "copy_tables", "guarantees"):
        assert cfg[key] == base[key], key
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert NEG in end["analytic_geomean_ms"]["workloads"]
    specs = files.layer_metrics()
    # (the cell's own metrics list it first; a later cell may follow it)
    mine = [m for m in bench["per_layer"] if m["workloads"][0] == NEG]
    assert {m["name"] for m in mine} >= {
        "join_ms.q13", "join_ms.q21", "join_ms.q22", "agg_ms.q13",
        "agg_ms.q21", "scan_ms.q13", "anti_joins.neg", "outer_joins.neg",
        "residual_semi_lanes.neg", "strpred_codes.neg", "initplan_ms.neg",
        "program_calls.neg"}
    for m in mine:
        assert m["name"] in specs and m["moves"] == "analytic_geomean_ms"


REHEARSALS = [
    (0, {"analytic_geomean_ms": None, "setup_s": None}),
    (1, {"compiles_in_window": 0, "programs_built.fresh": 0,
         "retraces.fresh": 0, "semi_joins.subq": 2, "initplans.subq": 1,
         "anti_joins.neg": 1, "outer_joins.neg": 1,
         "residual_semi_lanes.neg": 0, "strpred_codes.neg": None,
         "initplan_ms.neg": None, "program_calls.neg": 2,
         "sorted_agg_lanes.subq": None, "execute_ms.analytic": None}),
]


@pytest.mark.parametrize("trace, want", REHEARSALS,
                         ids=[f"trace{t}" for t, _ in REHEARSALS])
def test_the_cell_rehearses_from_its_own_files(trace, want):
    """benchmarks/run.py on the CPU at SF0.01, as the driver calls it: 0
    failed, every reply from a served tier, no fallback, and the metrics
    the cell is listed under."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", NEG, "--seed", "3000000019", "--seconds", "3",
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


# ---------------------------------------------------------------------------
# what the deployment forced in the engine, each at its smallest
# ---------------------------------------------------------------------------

def expanded(bkey, bvalid, bminor, bnull, pkey, pvalid, pminor, pnull):
    """EXISTS (build.key = probe.key and build.minor <> probe.minor) a
    probe row, by every pair: what `_exec_hashjoin` did before the mask."""
    out = np.zeros(len(pkey), bool)
    for i in range(len(pkey)):
        if pvalid[i] and not pnull[i]:
            out[i] = bool(np.any(bvalid & ~bnull & (bkey == pkey[i])
                                 & (bminor != pminor[i])))
    return out


RANGE_CASES = {
    # (build rows, probe rows, keys, minors, share of NULL minors)
    "duplicates": (300, 200, 40, 5, 0.0),
    "one-line-orders": (60, 200, 60, 5, 0.0),       # a key, a build row
    "one-supplier": (300, 200, 40, 1, 0.0),         # every minor the same
    "null-minors": (300, 200, 40, 3, 0.3),
    "empty-build": (0, 50, 10, 3, 0.0),
    "no-live-build-row": (64, 50, 10, 3, 0.0),
    "wide": (5_000, 3_000, 700, 9, 0.1),
}


@pytest.mark.parametrize("spans", ["packed", "exact"])
@pytest.mark.parametrize("case", list(RANGE_CASES))
def test_range_differs_is_the_expanded_residual(case, spans):
    """`join_build_minor` + `range_differs` against the expanded form, on
    random keys with duplicates, invalid rows, NULL minors on both sides,
    an empty build side, keys with one build row, ranges whose every minor
    equals the probe row's: in the packed single sort (the host knows both
    spans) and in the two-key sort (it knows neither)."""
    nb, np_, nkeys, nminors, null_share = RANGE_CASES[case]
    rng = np.random.default_rng([len(case), nb, np_])
    bkey = rng.integers(0, nkeys, nb) * 4 + 1
    bminor = rng.integers(0, nminors, nb) + 1_000
    bvalid = rng.random(nb) > (1.0 if case == "no-live-build-row" else 0.1)
    bnull = rng.random(nb) < null_share
    pkey = rng.integers(0, nkeys + 5, np_) * 4 + 1
    pminor = rng.integers(0, nminors + 1, np_) + 1_000
    pvalid = rng.random(np_) > 0.1
    pnull = rng.random(np_) < null_share
    # (a build side of no row at all has no smallest key to pack from:
    # the engine's batches are padded, `no-live-build-row` is its case)
    packed = spans == "packed" and nb > 0
    key_span, minor_span = (4 * nkeys + 20, nminors + 2) if packed \
        else (None, None)
    if case == "one-line-orders":
        bkey = np.arange(nb) * 4 + 1
    skeys, _perm, sminor, base = K.join_build_minor(
        jnp.asarray(bkey, jnp.int64), jnp.asarray(bvalid & ~bnull),
        jnp.asarray(bminor, jnp.int64), key_span=key_span,
        minor_span=minor_span)
    assert sminor.dtype == (jnp.int32 if packed else jnp.int64)
    lo, counts = K.join_probe_counts(
        skeys, jnp.asarray(pkey, jnp.int64), jnp.asarray(pvalid),
        key_span=key_span)
    got = np.asarray(K.range_differs(
        lo, counts, sminor, base, jnp.asarray(pminor, jnp.int64),
        jnp.asarray(pvalid & ~pnull)))
    want = expanded(bkey, bvalid, bminor, bnull, pkey, pvalid, pminor,
                    pnull)
    assert np.array_equal(got, want), np.flatnonzero(got != want)[:5]
    if case == "one-supplier":
        # every build minor is 1,000: only another probe value differs
        assert not got[pminor == 1_000].any() and got.any()
    assert want.any() == (case not in ("empty-build", "no-live-build-row"))


def test_range_differs_runs_in_passes(monkeypatch):
    """More probe rows than `_MAX_LANES` run in static passes."""
    monkeypatch.setattr(K, "_MAX_LANES", 256)
    jax.clear_caches()
    try:
        rng = np.random.default_rng(5)
        bkey, bminor = rng.integers(0, 50, 900), rng.integers(0, 4, 900)
        pkey, pminor = rng.integers(0, 55, 1_000), rng.integers(0, 4, 1_000)
        ones = np.ones(900, bool), np.ones(1_000, bool)
        skeys, _perm, sminor, base = K.join_build_minor(
            jnp.asarray(bkey), jnp.asarray(ones[0]), jnp.asarray(bminor),
            key_span=60, minor_span=4)
        lo, counts = K.join_probe_counts(skeys, jnp.asarray(pkey),
                                         jnp.asarray(ones[1]), key_span=60)
        got = np.asarray(K.range_differs(lo, counts, sminor, base,
                                         jnp.asarray(pminor),
                                         jnp.asarray(ones[1])))
        want = expanded(bkey, ones[0], bminor, ~ones[0], pkey, ones[1],
                        pminor, ~ones[1])
        assert np.array_equal(got, want)
    finally:
        jax.clear_caches()


LATE = """
create table sup (s bigint primary key, nm text) distribute by shard(s);
create table ln (o bigint, n bigint, s bigint, late bigint,
                 primary key (o, n)) distribute by shard(o);
insert into sup values (1, 'one'), (2, 'two'), (3, 'three'), (4, 'four');
insert into ln values
  (10, 1, 1, 1),
  (20, 1, 1, 1), (20, 2, 2, 0),
  (30, 1, 1, 1), (30, 2, 2, 1),
  (40, 1, 3, 1), (40, 2, 3, 1), (40, 3, 2, 0),
  (50, 1, 4, 1), (50, 2, null, 1), (50, 3, 2, 0),
  (60, 1, null, 1), (60, 2, 2, 0),
  (70, 1, 2, 1), (70, 2, 2, 1)
"""
LATE_SQL = """
select nm, count(*) from sup, ln l1 where sup.s = l1.s and l1.late = 1
  and exists (select * from ln l2 where l2.o = l1.o and l2.s <> l1.s)
  and not exists (select * from ln l3 where l3.o = l1.o and l3.s <> l1.s
                  and l3.late = 1)
group by nm order by nm
"""


@pytest.mark.parametrize("ndn", [1, 4])
def test_exists_and_not_exists_with_a_differing_column(ndn):
    """Q21's shape at its smallest: an order of one line (no other
    supplier), an order whose every line is late (another late supplier),
    one supplier twice in an order (both lines count), a NULL supplier on
    the build side (it is nobody's partner and nobody's rival) and on the
    probe side (`<>` with NULL is not true: EXISTS fails)."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.parallel.cluster import Cluster
    s = ClusterSession(Cluster(n_datanodes=ndn))
    for stmt in LATE.strip().split(";"):
        s.execute(stmt)
    # 10: alone; 20: supplier 1 waits; 30: both late; 40: supplier 3
    # twice; 50: the NULL supplier's late line is no rival of 4's; 60: a
    # NULL probe supplier joins no `sup` row; 70: one supplier, no other
    assert s.query(LATE_SQL) == [("four", 1), ("one", 1), ("three", 2)]
    stats = s.last_query_stats()
    assert (stats["semi_joins"], stats["anti_joins"],
            stats["residual_semi_lanes"]) == (2, 1, 0)
    # a residual no mask answers still expands, and says so: suppliers
    # with a LARGER partner and no larger late rival (20: 1 < 2; 40: 3 has
    # no larger partner; 50: 4 neither)
    assert s.query(LATE_SQL.replace("l2.s <> l1.s", "l2.s > l1.s")
                   .replace("l3.s <> l1.s", "l3.s > l1.s")) == [("one", 1)]
    stats = s.last_query_stats()
    assert stats["semi_joins"] == 0 and stats["residual_semi_lanes"] >= 64


OUTER = """
create table cu (c bigint primary key) distribute by shard(c);
create table od (o bigint primary key, c bigint, note text)
  distribute by shard(o);
insert into cu values (1), (2), (3), (4);
insert into od values (10, 1, 'plain'), (11, 1, 'special requests'),
  (12, 2, 'special requests'), (13, 3, 'plain'), (14, 3, 'plain')
"""


@pytest.mark.parametrize("ndn", [1, 4])
def test_an_on_conjunct_of_the_right_side_filters_the_right_input(ndn):
    """`left join ... on key and right-only predicate`: the predicate is a
    filter of the right scan, the join judges no pair (no residual), and
    a left row none of whose matches pass it is null-extended: customer 2
    (its one order filtered) and customer 4 (no order) count 0."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.parallel.cluster import Cluster
    s = ClusterSession(Cluster(n_datanodes=ndn))
    for stmt in OUTER.strip().split(";"):
        s.execute(stmt)
    sql = ("select cu.c, count(o) from cu left outer join od on cu.c = "
           "od.c and note not like '%special%requests%' group by cu.c "
           "order by cu.c")
    assert s.query(sql) == [(1, 1), (2, 0), (3, 2), (4, 0)]
    plan = s.execute("explain " + sql)[0].text
    join = next(ln for ln in plan.splitlines() if "HashJoin left" in ln)
    assert "residual" not in join
    assert "SeqScan od as od filter=1" in plan
    # a conjunct that names BOTH sides stays the join's own
    both = sql.replace("note not like '%special%requests%'", "od.o > cu.c")
    assert s.query(both) == [(1, 2), (2, 1), (3, 2), (4, 0)]


@pytest.mark.parametrize("ndn", [1, 4])
def test_an_uncorrelated_average_is_compared_in_integers(ndn):
    """`exact > (select avg(exact) ...)`: ONE init plan gives the sum and
    the count, both traced scalars of the statement's program, and a value
    exactly on the average is not above it whatever a float would round
    to; the average over no row is NULL and nothing is above it."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.parallel.cluster import Cluster
    s = ClusterSession(Cluster(n_datanodes=ndn))
    s.execute("create table bal (k bigint primary key, v decimal(15,2)) "
              "distribute by shard(k)")
    # sum 1000000.02 over 3: the average 333333.34 exactly; in float32
    # (the chip's float) 333333.34 and 333333.35 are one number
    s.execute("insert into bal values (1, 333333.33), (2, 333333.34), "
              "(3, 333333.35), (4, -5.00)")
    sql = "select k from bal where v > (select avg(v) from bal where " \
          "v > {floor}) order by k"
    assert s.query(sql.format(floor="0.00")) == [(3,)]
    stats = s.last_query_stats()
    assert stats["initplans"] == 1 and stats["initplan_ms"] > 0
    assert s.query(sql.format(floor="-10.00")) == [(1,), (2,), (3,)]
    assert s.query(sql.format(floor="999999.00")) == []
    # k * avg on the subquery's side, the subquery on the left
    assert s.query("select k from bal where (select 2 * avg(v) from bal "
                   "where v > 0.00) < v order by k") == []
    assert s.query("select k from bal where (select avg(v) from bal "
                   "where v > 0.00) <= v order by k") == [(2,), (3,)]


def test_a_large_code_set_is_a_bitmap_argument_of_the_program():
    """A LIKE over a column of many distinct strings: the program reads a
    bit a code from an ARGUMENT (no constant of the dictionary's size in
    its text), another pattern's verdicts run through the same words'
    shape, and a small dictionary still unrolls into compares."""
    from opentenbase_tpu.exec import mesh_exec
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.parallel.cluster import Cluster
    s = ClusterSession(Cluster(n_datanodes=1))
    s.execute("create table notes (k bigint primary key, note text, "
              "kind text) distribute by shard(k)")
    words = ("special", "plain", "requests", "late", "early")
    rows = ", ".join(
        f"({i}, '{words[i % 5]} {i} {words[i % 3]}', 'k{i % 4}')"
        for i in range(400))
    s.execute(f"insert into notes values {rows}")
    seen = []
    mesh_exec.EXPORT_HOOK = lambda tier, fn, args: seen.append((fn, args))
    try:
        got = s.query("select count(*) from notes where note not like "
                      "'%special%requests%'")
        stats = s.last_query_stats()
        small = s.query("select count(*) from notes where kind in "
                        "('k1', 'k3')")
        few = s.last_query_stats()
    finally:
        mesh_exec.EXPORT_HOOK = None
    want = sum(1 for i in range(400)
               if not (words[i % 5] == "special"
                       and words[i % 3] == "requests"))
    assert got == [(want,)] and small == [(200,)]
    assert stats["tier"] == "mesh" and stats["strpred_codes"] == 400
    assert few["strpred_codes"] == 2        # two codes, two compares
    fn, args = seen[0]
    (bitmap,) = [a for a in args if isinstance(a, np.ndarray)
                 and a.dtype == np.int32 and a.ndim == 1]
    assert bitmap.shape == (14,)    # 400 codes, 32 a word: 13, a class up
    # bit i: does the i-th distinct note (rows were inserted in order)
    # hold `special` and then `requests`
    like = np.asarray([words[i % 5] == "special"
                       and words[i % 3] == "requests" for i in range(400)])
    assert np.array_equal(
        np.unpackbits(bitmap.view(np.uint8), bitorder="little")[:400],
        like)
    # the verdicts are an argument, no constant: the program's text holds
    # no literal of the bitmap's 14 words or the dictionary's 400 values
    text = fn.lower(*args).as_text()
    assert "tensor<14xi32>" in text
    assert not any(f"dense<{lit}" in ln and f"<{n}x" in ln
                   for ln in text.splitlines() for n in (14, 400)
                   for lit in ("[", '"'))
