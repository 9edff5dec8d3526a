"""otbcard runtime half: warm-repeat compile discipline and the
OTB_TRACECHECK census witness.

The static ladder proof (analysis/cardinality.py) claims program-cache
keys quantize every data-dependent dimension, so re-running a query with
changed literals must hit the same compiled programs.  These tests are
the executable form of that claim: a warm Q1/Q3/Q5 repeat with changed
numeric/date literals compiles ZERO new programs, and the census
recorded by the runtime witness validates against the same invariants
the lint pass checks statically.
"""

import json
import os

import pytest

from opentenbase_tpu.analysis.cardinality import check_census, is_ladder_int
from opentenbase_tpu.exec import plancache
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.tpch import datagen
from opentenbase_tpu.tpch.queries import Q
from opentenbase_tpu.tpch.schema import SCHEMA

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Literal rewrites that keep each query's shape but change its baked-in
# numeric/date parameters — the prepared-statement re-bind case.  TEXT
# literals ('BUILDING', 'ASIA') are deliberately untouched: those are
# baked into the program and legitimately recompile.
_VARIANTS = {
    1: ("'90'", "'75'"),
    3: ("1995-03-15", "1995-05-01"),
    5: ("1994-01-01", "1995-01-01"),
}


@pytest.fixture(scope="module")
def warm_env():
    os.environ["OTB_FUSE_JOIN_MIN_ROWS"] = "0"
    try:
        node = LocalNode()
        s = Session(node)
        s.execute(SCHEMA)
        data = datagen.generate(sf=0.01)
        datagen.load_into(s, data)
        yield s
    finally:
        os.environ.pop("OTB_FUSE_JOIN_MIN_ROWS", None)


def _total_compiles() -> int:
    return sum(comp for _t, _h, _m, comp, _ms, _e, _l in plancache.stats())


class TestWarmRepeatZeroCompile:
    def test_changed_literals_reuse_programs(self, warm_env):
        s = warm_env
        for qn in _VARIANTS:
            s.query(Q[qn])                    # cold pass: compiles
        base = _total_compiles()
        for qn, (old, new) in _VARIANTS.items():
            sql = Q[qn].replace(old, new)
            assert sql != Q[qn], f"Q{qn} variant literal not found"
            s.query(sql)                      # warm pass: must not
        assert _total_compiles() == base, \
            "warm repeat with changed literals compiled new programs"


class TestTracecheckCensus:
    def test_witness_records_and_validates(self, warm_env, monkeypatch):
        s = warm_env
        monkeypatch.setenv("OTB_TRACECHECK", "1")
        plancache.reset_census()
        plancache.FUSED.clear()               # force fresh witnessed puts
        for qn in _VARIANTS:
            s.query(Q[qn])
        ents = plancache.census()
        assert ents, "census empty despite fresh compiles"
        assert check_census({"entries": ents}) == []
        # warm variants must add no entries (and no repeat-puts)
        n = len(ents)
        for qn, (old, new) in _VARIANTS.items():
            s.query(Q[qn].replace(old, new))
        ents2 = plancache.census()
        assert len(ents2) == n
        assert check_census({"entries": ents2}) == []


    def test_an_aggregates_factor_is_a_ladder_shaped_key_component(
            self, monkeypatch):
        """A traced sorted aggregate whose keys' ranges bound nothing rides
        the joins' size-class ladder (executor._agg_class): the factor it
        learns is a `factor:` class of the program key like a join's, a
        power of two under the cap, one compile a class."""
        import numpy as np
        monkeypatch.setenv("OTB_TRACECHECK", "1")
        plancache.reset_census()
        node = LocalNode()
        s = Session(node)
        s.execute("create table ct (k bigint, v bigint)")
        n = 1500
        s._insert_rows(node.catalog.table("ct"), node.stores["ct"],
                       {"k": np.arange(n), "v": np.arange(n) % 7}, n)
        # 1,500 groups, a first rung of a quarter of the padded rows
        rows = s.query("select k + 0 as kk, sum(v) as sv from ct "
                       "group by k + 0 order by kk limit 3")
        assert rows == [(0, 0), (1, 1), (2, 2)]
        ents = plancache.census()
        factors = sorted(v for e in ents for dim, v in e["classes"]
                         if dim.startswith("factor:('__fused', 0)"))
        assert factors == [4], ents
        assert len({e["frag"] for e in ents}) == 1 and len(ents) == 2
        assert check_census({"entries": ents}) == []
        plancache.reset_census()


class TestCommittedCensus:
    def test_repo_census_is_clean(self):
        path = os.path.join(_REPO, "opentenbase_tpu", "analysis",
                            "program_census.json")
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        assert data["entries"], "committed census should not be empty"
        assert check_census(data) == []


class TestLadderShape:
    def test_ladder_members(self):
        for v in (1, 2, 3, 4, 5, 6, 7, 8, 10, 12, 14, 16, 96, 256, 640,
                  1792, 4096):
            assert is_ladder_int(v), v

    def test_non_members(self):
        for v in (0, -1, 9, 1000, 100, 257, True, False, "256", 2.0):
            assert not is_ladder_int(v), v


class TestCheckCensus:
    @staticmethod
    def _ent(**kw):
        e = {"tier": "fused", "frag": "f", "key": "k",
             "classes": [], "puts": 1}
        e.update(kw)
        return e

    def test_compile_storm(self):
        ents = [self._ent(key=f"k{i}", classes=[["factor:j", 2 ** (i % 12)]])
                for i in range(65)]
        msgs = check_census({"entries": ents})
        assert any("compile storm" in m for m in msgs), msgs

    def test_factor_cap(self):
        msgs = check_census(
            {"entries": [self._ent(classes=[["factor:j0", 8192]])]})
        assert any("cap" in m for m in msgs), msgs

    def test_malformed_entry(self):
        msgs = check_census({"entries": ["bogus"]})
        assert any("malformed" in m for m in msgs), msgs

    def test_malformed_class(self):
        msgs = check_census({"entries": [self._ent(classes=[["solo"]])]})
        assert any("malformed class" in m for m in msgs), msgs


class TestCensusRuntime:
    # Hand-built 9-tuple matching the mesh prog_key layout lets us
    # exercise note/forget without standing up a cluster.
    _KEY = (1, (), (), (("t", 256, (), ()),), (("j", 4),), (), (), (), ())

    def test_note_class_split_and_forget(self, monkeypatch):
        monkeypatch.setenv("OTB_TRACECHECK", "1")
        plancache.reset_census()
        c = plancache.ProgramCache("mesh", max_entries=4)
        c.put(self._KEY, object())
        ents = plancache.census()
        assert len(ents) == 1
        assert ents[0]["classes"] == [["pad:t", 256], ["factor:j", 4]]
        assert ents[0]["puts"] == 1
        # a second put of the SAME key is an unexplained retrace
        c.put(self._KEY, object())
        ents = plancache.census()
        assert ents and ents[0]["puts"] >= 2
        assert any("unexplained retrace" in m
                   for m in check_census({"entries": ents}))
        c.pop(self._KEY)
        assert plancache.census() == []
        plancache.reset_census()

    def test_save_census_merges_prior(self, tmp_path, monkeypatch):
        monkeypatch.setenv("OTB_TRACECHECK", "1")
        path = tmp_path / "census.json"
        prior = {"entries": [{"tier": "mesh", "frag": "f", "key": "k",
                              "classes": [["pad:t", 128]], "puts": 2}]}
        path.write_text(json.dumps(prior))
        plancache.reset_census()
        c = plancache.ProgramCache("mesh", max_entries=4)
        c.put(self._KEY, object())
        out = plancache.save_census(str(path))
        ents = out["entries"]
        assert len(ents) == 2
        # prior entry survives the merge with its puts count intact
        assert any(e["key"] == "k" and e["puts"] == 2 for e in ents)
        on_disk = json.loads(path.read_text())
        assert on_disk["entries"] == ents
        c.pop(self._KEY)
        plancache.reset_census()
