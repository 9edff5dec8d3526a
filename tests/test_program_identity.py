"""What identifies and sizes a compiled program is written once (ISSUE 46).

`plan/physical.plan_key` is the one structural key of a physical plan:
the fused tier's `base_key` and the mesh tier's program and ladder keys
hold the SAME tuple for the same plan, node type by node type, and a node
outside the asking tier's kinds gives None (the mesh tier turns that into
`MeshUnsupported`).  `plancache.Ladder` is the one bounded, locked map of
learned size classes and the one growth rule both tiers climb by."""

import threading
import types

import pytest

from opentenbase_tpu.analysis import cardinality
from opentenbase_tpu.catalog import types as T
from opentenbase_tpu.catalog.schema import (ColumnDef, Distribution,
                                            DistType, TableDef)
from opentenbase_tpu.exec import fused
from opentenbase_tpu.exec.executor import ExecContext, split_params
from opentenbase_tpu.exec.mesh_exec import (_ALLOWED, MeshRunner,
                                            MeshUnsupported)
from opentenbase_tpu.exec.plancache import Ladder
from opentenbase_tpu.plan import exprs as E
from opentenbase_tpu.plan import physical as P
from opentenbase_tpu.plan.distribute import (BatchSource, ExchangeRef,
                                             Fragment)

TD = TableDef("t", [ColumnDef("k", T.INT64), ColumnDef("v", T.INT64)],
              Distribution(DistType.SHARD, ["k"]))
K, V = E.Col("a.k", T.INT64), E.Col("a.v", T.INT64)
BK = E.Col("b.k", T.INT64)
QUAL = E.Cmp("<", K, E.Lit(7, T.INT64))
SCAN = P.SeqScan(TD, "a", [QUAL], [("a.k", K), ("a.v", V)])
SCAN_B = P.SeqScan(TD, "b", [], None)
SCAN_KEY = ("SeqScan", "t", "a", (QUAL,), (("a.k", K), ("a.v", V)))
SUM = E.AggCall("sum", V)

# node type -> (a plan whose top is that node, its key element for element)
PLANS = {
    "SeqScan": (SCAN, SCAN_KEY),
    "Filter": (P.Filter(SCAN, [QUAL]), ("Filter", (QUAL,), SCAN_KEY)),
    "Project": (P.Project(SCAN, [("x", K)]),
                ("Project", (("x", K),), SCAN_KEY)),
    "Agg": (P.Agg(SCAN, [("a.k", K)], [("s", SUM)], "partial"),
            ("Agg", "partial", (("a.k", K),), (("s", SUM),), SCAN_KEY)),
    "Sort": (P.Sort(SCAN, [(K, 1), (V, False)], 5),
             ("Sort", ((K, True), (V, False)), 5, SCAN_KEY)),
    "Limit": (P.Limit(SCAN, 3, 2), ("Limit", 3, 2, SCAN_KEY)),
    "HashJoin": (P.HashJoin(SCAN, SCAN_B, [K], [BK], "semi",
                            [E.Cmp("<>", V, BK)]),
                 ("HashJoin", "semi", (K,), (BK,), (E.Cmp("<>", V, BK),),
                  SCAN_KEY, ("SeqScan", "t", "b", (), ()))),
}


def fused_plan_key(plan):
    """The plan part of the fused tier's `base_key` for `plan`."""
    prep = fused._Prepared.of(ExecContext({}, 0, 0, None), plan, [], {})
    return None if prep is None else prep.base_key[0]


def mesh_keys(plan):
    """`(program key's fragments part, ladder key)` of the mesh tier for
    a statement whose one fragment is `plan`."""
    dp = types.SimpleNamespace(fragments=[Fragment(0, plan, "dn")],
                               exchanges=[])
    skey = MeshRunner._shape_key(dp, {}, {0})
    return skey[0], MeshRunner._ladder_key(skey)


@pytest.mark.parametrize("kind", sorted(PLANS))
def test_both_tiers_key_a_plan_by_the_same_tuple(kind):
    plan, want = PLANS[kind]
    assert P.plan_key(plan, fused._KINDS) == want
    assert fused_plan_key(plan) == want
    frags, lkey = mesh_keys(plan)
    assert frags == ((0, want),)
    assert lkey == hash((frags, (), ()))
    # the key is the plan's structure, not the object's identity
    assert hash(P.plan_key(plan, _ALLOWED)) == hash(want)


def test_a_field_that_shapes_the_node_is_in_the_key():
    """The join's residual reached the two old key functions in two
    different commits: here one edit moves both tiers' keys."""
    join, _ = PLANS["HashJoin"]
    other = P.HashJoin(join.left, join.right, join.left_keys,
                       join.right_keys, join.kind, [])
    assert fused_plan_key(other) != fused_plan_key(join)
    assert mesh_keys(other)[0] != mesh_keys(join)[0]
    assert fused_plan_key(other) == mesh_keys(other)[0][0][1]


MESH_ONLY = {
    "Window": (P.Window(SCAN, [("w", SUM)]),
               ("Window", (("w", SUM),), SCAN_KEY)),
    "Append": (P.Append([SCAN, SCAN_B]),
               ("Append", (SCAN_KEY, ("SeqScan", "t", "b", (), ())))),
    "ExchangeRef": (P.Filter(ExchangeRef(3), [QUAL]),
                    ("Filter", (QUAL,), ("ExchangeRef", 3))),
}


@pytest.mark.parametrize("kind", sorted(MESH_ONLY))
def test_a_node_outside_the_fused_kinds_gives_none(kind):
    plan, want = MESH_ONLY[kind]
    assert P.plan_key(plan, fused._KINDS) is None
    assert fused_plan_key(plan) is None
    assert mesh_keys(plan)[0] == ((0, want),)


@pytest.mark.parametrize("plan", [
    P.SetOp([SCAN, SCAN_B]), P.Filter(BatchSource(None), [QUAL]),
    P.HashJoin(SCAN, P.Result([]), [K], [BK])],
    ids=["SetOp", "BatchSource_below", "Result_below"])
def test_a_node_outside_both_gives_none_and_mesh_unsupported(plan):
    assert P.plan_key(plan, _ALLOWED) is None
    assert fused_plan_key(plan) is None
    with pytest.raises(MeshUnsupported):
        mesh_keys(plan)


def test_walk_and_needed_columns_read_the_whole_plan():
    join, _ = PLANS["HashJoin"]
    top = P.Sort(P.Agg(join, [("a.k", K)], [("s", SUM)]), [(K, False)])
    assert [type(n).__name__ for n in P.walk(top)] == [
        "Sort", "Agg", "HashJoin", "SeqScan", "SeqScan"]
    assert P.needed_columns(top, "a") == {"k", "v"}
    assert P.needed_columns(top, "b") == {"k"}


def test_split_params_traces_numbers_and_bakes_the_rest():
    params = {"n": (3, T.INT64), "f": (1.5, T.FLOAT64),
              "s": ("x", T.TEXT), "b": (True, T.BOOL), "z": (None, T.NULLT)}
    traced, baked = split_params(params)
    assert traced == ("f", "n")
    assert baked == {k: params[k] for k in ("s", "b", "z")}


# ---------------------------------------------------------------------------
# the one ladder
# ---------------------------------------------------------------------------

def test_ladder_evicts_the_oldest_key_at_its_bound():
    lad = Ladder(3)
    for i in range(4):
        lad.remember(("k", i), {"j": i})
    assert len(lad) == 3 and lad.recall(("k", 0)) is None
    assert list(lad.snapshot()) == [("k", 1), ("k", 2), ("k", 3)]
    # a key learned again keeps its place; the next new key evicts it
    lad.remember(("k", 1), {"j": 10})
    lad.remember(("k", 4), {"j": 4})
    assert list(lad.snapshot()) == [("k", 2), ("k", 3), ("k", 4)]
    assert lad.recall(("k", 4)) == ({"j": 4},)


def test_ladder_hands_out_and_keeps_copies():
    lad = Ladder(4)
    factors, mults = {"j": 2}, {0: 4}
    lad.remember("shape", factors, mults)
    factors["j"] = 64                      # the caller goes on growing
    got = lad.recall("shape")
    assert got == ({"j": 2}, {0: 4})
    got[0]["j"] = 128
    assert lad.recall("shape") == ({"j": 2}, {0: 4})
    assert lad.recall("other") is None


def test_two_threads_remember_past_the_bound():
    """MeshRunner's inline `pop(next(iter(d)))` had no lock though four
    sessions share a runner: an insert between `next(iter(d))` and the
    pop raised RuntimeError and let the dict pass its bound."""
    lad, errors = Ladder(64), []

    def learn(tag):
        try:
            for i in range(4000):
                lad.remember((tag, i), {"j": i})
                lad.recall((tag, i - 1))
        except Exception as e:          # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=learn, args=(t,)) for t in "ab"]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert errors == [] and len(lad) == 64


def test_growth_jumps_where_rows_are_reported_and_doubles_on_a_bit():
    factors = {}
    # the fused tier: the class held 100 rows, the program needed 1,000:
    # ONE step to the power of two that fits (800 < 1000 <= 1600)
    assert Ladder.grow(factors, "j", have=100, need=1000)
    assert factors == {"j": 16}
    # from a learned factor the step multiplies what is there
    assert Ladder.grow(factors, "j", have=1600, need=1601)
    assert factors == {"j": 32}
    # the mesh tier: an overflow bit doubles
    mults = {}
    assert Ladder.grow(mults, 3) and mults == {3: 2}
    assert Ladder.grow(mults, 3) and mults == {3: 4}


def test_a_factor_past_the_cap_exhausts_the_ladder():
    assert Ladder.CAP == cardinality._FACTOR_CAP == 4096
    assert Ladder.ATTEMPTS == 24
    factors = {"j": 2048}
    assert Ladder.grow(factors, "j") and factors["j"] == 4096
    assert not Ladder.grow(factors, "j")
    assert not Ladder.grow({}, "j", have=1, need=4097)
    assert Ladder.grow({}, "j", have=1, need=4096)
