"""Parameter draws, the comparison that decides `correct`, its control, and
a run whose timed path is broken underneath.  All at the rehearsal scale
(SF0.01) on the CPU."""

import io
import json
import math
import os
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import compare, control, datagen, files  # noqa: E402
from benchmarks.lib import params as params_mod  # noqa: E402
from benchmarks.lib.traffic import Mix  # noqa: E402

SF = 0.01
SEED = 2862933555          # more than 31 bits, as the driver's are
LIMITS = files.load_json("lib", "limits.json")


@pytest.fixture(scope="module")
def data():
    return datagen.generate(SF, SEED)


@pytest.fixture(scope="module")
def power(data):
    mix = Mix("power", SEED, data)
    mix.build_pools()
    return mix


def test_data_repeats_for_a_seed_and_changes_with_it(data):
    again = datagen.generate(SF, SEED)
    other = datagen.generate(SF, SEED + 1)
    for t in data:
        for c in data[t]:
            assert np.array_equal(data[t][c], again[t][c]), (t, c)
    assert not np.array_equal(data["lineitem"]["l_extendedprice"][:100],
                              other["lineitem"]["l_extendedprice"][:100])


def test_draws_stay_in_the_specs_domains_and_repeat(data):
    for seed in (1, SEED, 2**31 + 12345):
        mix = Mix("power", seed, data)
        mix.build_pools()
        again = Mix("power", seed, data)
        again.build_pools()
        for name in ("q1", "q3", "q5"):
            assert [p for p, _ in mix.pools[name]] == \
                [p for p, _ in again.pools[name]]
            assert len(mix.pools[name]) == 3
        # parameters whose every value is a program of its own are pinned:
        # the same set for every seed, dealt in an order from the seed
        assert sorted(p["delta"] for p, _ in mix.pools["q1"]) == [60, 90, 120]
        assert {p["segment"] for p, _ in mix.pools["q3"]} == {"BUILDING"}
        assert {(p["region"], p["date"]) for p, _ in mix.pools["q5"]} == \
            {("ASIA", "1994-01-01")}
        for p, _ in mix.pools["q1"]:
            assert 60 <= p["delta"] <= 120              # clause 2.4.1.3
        for p, _ in mix.pools["q3"]:
            assert p["segment"] in datagen.SEGMENTS     # clause 2.4.3.3
            assert "1995-03-01" <= p["date"] <= "1995-03-31"
        for p, _ in mix.pools["q5"]:
            assert p["region"] in datagen.REGIONS       # clause 2.4.5.3
            assert p["date"] in [f"{y}-01-01" for y in range(1993, 1998)]


def test_zipf_keys_exist_are_skewed_and_repeat(data):
    keys = data["orders"]["o_orderkey"]
    law = params_mod.KeyLaw(keys, {"theta": 0.99}, SEED)
    a = law.draw(np.random.default_rng(7), 20000)
    b = law.draw(np.random.default_rng(7), 20000)
    assert np.array_equal(a, b)
    assert np.isin(a, keys).all()
    _, counts = np.unique(a, return_counts=True)
    # zipf(0.99) over 15000 keys: the hottest key takes ~10% of the draws
    assert counts.max() / len(a) > 0.05
    uniform = params_mod.KeyLaw(keys, None, SEED).draw(
        np.random.default_rng(7), 20000)
    assert np.unique(uniform, return_counts=True)[1].max() / 20000 < 0.005


def test_fresh_keys_never_collide():
    spec = files.statement("kv_write")["params"]
    seen = set()
    for client in range(4):
        for n in range(500):
            p = params_mod.draw(spec, np.random.default_rng(1), None,
                                client, n)
            assert p["k"] not in seen
            seen.add(p["k"])
            assert p["v"] == 7 * p["k"] - 3
            assert len(p["note"]) <= 16


@pytest.fixture(scope="module")
def engine_answers(data, power, tmp_path_factory):
    """The engine's replies to every pool statement, over the wire."""
    import opentenbase_tpu  # noqa: F401
    from benchmarks.lib import stack as stack_mod
    run_dir = str(tmp_path_factory.mktemp("run"))
    stack = stack_mod.Stack(1, os.path.join(run_dir, "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data,
                            ("region", "nation", "supplier", "customer"),
                            run_dir)
        reqs = [power.run_request(r, client, session)
                for r in power.warm_requests(0)]
    finally:
        stack.stop()
    return reqs


def test_comparison_passes_on_the_engines_answers(power, engine_answers):
    assert len(engine_answers) == 9
    for req in engine_answers:
        bad, avg_gap, ulp_gap = power.check(req, LIMITS)
        assert bad == [], bad
        assert avg_gap <= LIMITS["avg_rel_gap"]
        assert ulp_gap <= LIMITS["decimal_ulp_gap"]
        assert req.steps[0][5]["tier"] in power.served_tiers


def _reply(req):
    return [tuple(r) for r in req.steps[0][3]]


def _with_reply(req, rows):
    from benchmarks.lib.traffic import Request
    out = Request(req.stmt, req.params, req.expected)
    cls, t0, t1, _rows, err, st = req.steps[0]
    out.steps.append((cls, t0, t1, rows, err, st))
    return out


def test_comparison_fails_on_one_cent_a_dropped_row_and_an_f32_avg(
        power, engine_answers, data):
    q1 = next(r for r in engine_answers if r.stmt.name == "q1")
    rows = _reply(q1)
    # a decimal sum one cent off
    off = list(rows[0])
    off[3] = round(off[3] + 0.01, 2)
    bad, _, ulps = power.check(_with_reply(q1, [tuple(off)] + rows[1:]),
                               LIMITS)
    assert bad and "ulps" in bad[0] and ulps > 100
    # the wire's float64 one ulp off (int64 -> float64 -> scaled) is not
    off[3] = math.nextafter(rows[0][3], math.inf)
    assert power.check(_with_reply(q1, [tuple(off)] + rows[1:]),
                       LIMITS)[0] == []
    # a dropped row
    bad, _, _ = power.check(_with_reply(q1, rows[:-1]), LIMITS)
    assert bad and "rows" in bad[0]
    # an AVG taken from a float32 running sum (PR 22's fault on the chip)
    li = data["lineitem"]
    sel = (li["l_returnflag"] == rows[0][0].encode()) \
        & (li["l_linestatus"] == rows[0][1].encode()) \
        & (li["l_shipdate"] <= datagen.days("1998-12-01")
           - q1.params["delta"])
    running = np.cumsum(li["l_extendedprice"][sel].astype(np.float32),
                        dtype=np.float32)[-1]
    off = list(rows[0])
    off[7] = float(running) / int(sel.sum())
    bad, gap, _ = power.check(_with_reply(q1, [tuple(off)] + rows[1:]),
                              LIMITS)
    assert bad and "AVG" in bad[0] and gap > LIMITS["avg_rel_gap"]
    q3 = next(r for r in engine_answers if r.stmt.name == "q3")
    rows = _reply(q3)
    swapped = [rows[1], rows[0]] + rows[2:]
    assert power.check(_with_reply(q3, swapped), LIMITS)[0]


def test_control_float32_is_refused(power):
    """The reference in float32, in the program's place, fails an exact
    number of every analytic statement (sums off by far more than a cent)."""
    for name, _bad, _avg, ulps in control.gaps(power, "float32"):
        assert ulps > 1000 * LIMITS["decimal_ulp_gap"], name


def test_control_point_cell(data):
    mix = Mix("point", SEED, data)
    mix.build_pools()
    # o_totalprice held in float32 no longer equals the decimal
    reads = control.gaps(mix, "float32", ["point_read"])
    assert sum(1 for _n, _b, _a, ulps in reads
               if ulps > LIMITS["decimal_ulp_gap"]) >= len(reads) // 2
    # a read-back served from before the acknowledged write finds nothing
    assert all(bad for _n, bad, _a, _u in control.gaps(mix, "stale",
                                                       ["kv_write"]))


@pytest.mark.parametrize("fault", ["none", "cent", "row", "stale_read"])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, monkeypatch):
    """Skips the harness's look for a chip (the rehearsal switch) and
    drives the rest of a run; an answer altered where the client receives
    it has to come out as failed statements and a verdict of not correct."""
    import runpy
    from opentenbase_tpu.net import cn_server
    real = cn_server.CnClient.query

    def broken(self, sql):
        rows = real(self, sql)
        if fault == "cent" and "sum_qty" in sql:
            r = list(rows[0])
            r[3] = round(r[3] + 0.01, 2)
            rows = [tuple(r)] + rows[1:]
        elif fault == "row" and "n_name" in sql:
            rows = rows[:-1]
        elif fault == "stale_read" and "bench_kv where k = 1" in sql \
                and "k = 1" != sql[-5:]:
            rows = []
        return rows

    monkeypatch.setattr(cn_server.CnClient, "query", broken)
    cell = "tpch_sf1_point" if fault == "stale_read" else "tpch_sf1_power"
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", cell, "--seed", str(SEED), "--seconds", "2",
        "--trace", "0", "--rehearse-sf", str(SF)])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    out = io.StringIO()
    with redirect_stdout(out), pytest.raises(SystemExit) as ex:
        runpy.run_path(os.path.join(ROOT, "benchmarks", "run.py"),
                       run_name="__main__")
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()
             if ln.startswith("{")]
    result = lines[-1]
    verdict = next(ln["rehearsal"] for ln in lines
                   if isinstance(ln.get("rehearsal"), str))
    assert ex.value.code == 1
    assert result["correct"] is False
    if fault == "none":
        assert result["failed"] == 0 and "correct=True" in verdict
    else:
        assert result["failed"] > 0 and "correct=False" in verdict
