"""A mesh-tier statement crosses each host-device boundary once (ISSUE 37).

After the program call, ONE `jax.device_get` brings down the three
overflow vectors and every gathered array (`mesh_exec._call_program`);
the live rows are re-padded on the host and go back in ONE put
(`MeshRunner.run`'s `gather`); the snapshot, the txid and the traced
parameters ride the program's own argument transfer as numpy scalars;
and a narrow result leaves in ONE copy (`executor._materialize`).  On
CPU devices the counts repeat exactly: `host_syncs` counts blocking
round trips (a batched copy is one), `h2d_puts` put calls, the bytes
what crossed.  The answers are the host tier's, row for row."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opentenbase_tpu.catalog import types as T
from opentenbase_tpu.exec import executor as X
from opentenbase_tpu.exec import mesh_exec
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.obs import trace as obs_trace
from opentenbase_tpu.parallel.cluster import Cluster

ROWS = 1100     # 1,280 padded rows on one DataNode, 4 x 320 on four


def table_rows():
    rng = np.random.default_rng(37)
    k = np.arange(ROWS)
    g = rng.integers(0, 8, ROWS)
    v = rng.integers(0, 100, ROWS)
    w_null = (k % 5 == 0) | (g == 7)    # group 7 holds no w at all
    return k, g, v, w_null


def _session(ndn):
    s = ClusterSession(Cluster(n_datanodes=ndn))
    s.execute("create table t (k bigint primary key, g bigint, v bigint, "
              "w bigint) distribute by shard(k)")
    k, g, v, w_null = table_rows()
    for i in range(0, ROWS, 550):
        s.execute("insert into t values " + ", ".join(
            f"({a}, {b}, {c}, {'null' if n else 2 * c})"
            for a, b, c, n in zip(*(x[i:i + 550].tolist()
                                    for x in (k, g, v, w_null)))))
    return s


@pytest.fixture(scope="module")
def envs():
    """{DataNodes: session} and the batches every MeshRunner.run
    returned since the list was last emptied."""
    gathered = []
    orig = mesh_exec.MeshRunner.run

    def run(self, *a, **kw):
        out = orig(self, *a, **kw)
        gathered.append(out[0])
        return out

    mp = pytest.MonkeyPatch()
    mp.setattr(mesh_exec.MeshRunner, "run", run)
    yield {1: _session(1), 4: _session(4)}, gathered
    mp.undo()


def host_rows(s, sql):
    s.execute("set enable_mesh_exchange = off")
    try:
        rows = s.query(sql)
        assert s.last_query_stats()["tier"] == "host"
    finally:
        s.execute("set enable_mesh_exchange = on")
    return rows


# (sql, the DataNode counts at which the planner sends it to the mesh: a
#  cross join's sizing keeps the two-gather shape off it on one DataNode;
#  the answer's rows and what the gathered batches must look like)
GATHERS = {
    # (a) nothing lives: the smallest class goes on, the answer is empty
    "no-live-row": (
        "select g, sum(v) as s from t where v < 0 group by g order by g",
        (1, 4), dict(rows=0, gathers=1, padded=256, nulls=False)),
    # (b) more than half the gather class lives: `rows >= len(valid)`,
    # the arrays go back as they came down, nothing selected
    "whole-class-live": (
        "select k, v from t where v >= 0 order by k",
        (1, 4), dict(rows=ROWS, gathers=1, padded=1280, nulls=False)),
    # (c) a partial sum over no value is NULL: the mask crosses too
    "null-mask": (
        "select g, sum(w) as s, count(w) as c from t group by g "
        "order by g",
        (1, 4), dict(rows=8, gathers=1, padded=256, nulls=True)),
    # (d) two CN-bound exchanges of one program: one copy, one put
    "two-gathers": (
        "select a.s, b.c from (select sum(w) as s from t where g = 7) a, "
        "(select count(*) as c from t where g = 3) b",
        (4,), dict(rows=1, gathers=2, padded=256, nulls=True)),
    # (e) Q1's shape: grouped partial aggregates of every DataNode (an
    # average's partial sum carries a mask, as Q1's three do)
    "grouped": (
        "select g, sum(v) as s, avg(v) as a, count(*) as c from t "
        "where k <= 1000 group by g order by g",
        (1, 4), dict(rows=8, gathers=1, padded=256, nulls=True)),
}


@pytest.mark.parametrize("ndn, case", [
    (ndn, case) for case, (_sql, where, _want) in GATHERS.items()
    for ndn in where], ids=lambda x: f"{x}dn" if isinstance(x, int) else x)
def test_the_gathered_batch_crosses_once_each_way(envs, ndn, case):
    sessions, gathered = envs
    s = sessions[ndn]
    sql, _where, want = GATHERS[case]
    s.query(sql)                        # builds, learns its classes
    del gathered[:]
    rows, st = s.query(sql), s.last_query_stats()
    qt = obs_trace.last_trace()
    assert st["tier"] == "mesh" and st["fallback"] == "", st
    assert rows == host_rows(s, sql) and len(rows) == want["rows"]
    # one round trip after the call, one at finalize; one put back; the
    # scalars of `inputs` are no puts; whatever the arrays' number
    assert (st["host_syncs"], st["h2d_puts"], st["finalize_fetches"],
            st["program_calls"], st["retraces"]) == (2, 1, 1, 1, 0), st
    (batches,) = gathered
    assert len(batches) == want["gathers"]
    assert {b.padded for b in batches.values()} == {want["padded"]}
    assert any(b.nulls for b in batches.values()) == want["nulls"]
    arrays = [a for b in batches.values()
              for a in (b.valid, *b.cols.values(), *b.nulls.values())]
    assert all(isinstance(a, jax.Array) for a in arrays)
    assert qt.sum_attr("gather", "h2d_bytes") == \
        sum(a.nbytes for a in arrays) == st["h2d_bytes"]
    assert qt.sum_attr("gather", "d2h") == qt.sum_attr("inputs", "h2d") == 0
    down = qt.sum_attr("execute", "d2h_bytes")
    assert qt.sum_attr("execute", "d2h") == 1
    assert st["d2h_bytes"] == down + st["finalize_fetch_bytes"]
    if case == "whole-class-live":
        # the gather class itself went on: up what came down, less the
        # overflow vectors
        assert 0 < down - st["h2d_bytes"] <= 3 * 8 * 4
    else:
        assert down > 4 * st["h2d_bytes"]
    # the same on every reply
    s.query(sql)
    again = s.last_query_stats()
    for key in ("host_syncs", "h2d_puts", "d2h_bytes", "h2d_bytes"):
        assert again[key] == st[key], key


def test_an_overflowed_gather_class_is_replayed_before_any_row_is_used():
    """70,000 live rows against the first gather class of 65,536: the
    overflow vector comes down WITH the (short) gathered arrays, which
    are thrown away; the statement replays one class up, answers right,
    and remembers the class."""
    rows = 70_000
    cluster = Cluster(n_datanodes=1)
    s = ClusterSession(cluster)
    s.execute("create table big (k bigint primary key, v bigint) "
              "distribute by shard(k)")
    s._insert_rows(cluster.catalog.table("big"),
                   {"k": np.arange(rows), "v": np.arange(rows) % 100}, rows)
    sql = "select k from big where v >= {}"
    got, st = s.query(sql.format(0)), s.last_query_stats()
    assert st["tier"] == "mesh" and st["retraces"] == 1
    assert sorted(got) == [(k,) for k in range(rows)]
    qt = obs_trace.last_trace()
    # two calls, a batched copy after each; ONE put: the overflowed
    # call's arrays never went back (the third program is finalize's
    # selection of the wide answer's live rows)
    assert qt.sum_attr("execute", "d2h") == 2 == st["program_calls"] - 1
    assert qt.sum_attr("gather", "h2d") == 1
    again = s.query(sql.format(1))      # another literal, the same class
    st = s.last_query_stats()
    assert (st["retraces"], st["program_calls"], st["h2d_puts"]) == (0, 2, 1)
    assert sorted(again) == [(k,) for k in range(rows) if k % 100 >= 1]


@pytest.mark.parametrize("ndn", [1, 4], ids=["1dn", "4dn"])
def test_scalars_ride_the_programs_own_argument_transfer(envs, ndn,
                                                         monkeypatch):
    """Snapshot, txid and a lifted literal reach the program as numpy
    scalars of the device dtype: the same values and dtypes, no put and
    no eager convert of their own, and no new program for a new value."""
    s = envs[0][ndn]
    calls = []
    monkeypatch.setattr(mesh_exec, "EXPORT_HOOK",
                        lambda _tier, fn, args: calls.append((fn, args)))
    sql = "select g, count(*) as c from t where v >= {} group by g order by g"
    s.query(sql.format(10))
    first = s.query(sql.format(10))
    assert s.query(sql.format(60)) != first
    st = s.last_query_stats()
    assert (st["tier"], st["params_traced"], st["params_baked"]) == \
        ("mesh", 1, 0)
    assert len({fn for fn, _args in calls}) == 1    # one program for both
    (_f, a10), (_f, a60) = calls[1:]
    for args, lit in ((a10, 10), (a60, 60)):
        snap, txid, param = args[:3]
        assert all(isinstance(x, (np.generic, np.ndarray))
                   for x in (snap, txid, param))
        assert snap.dtype == txid.dtype == np.int64 and snap > 0
        assert param.shape == () and param == lit
        assert param.dtype == np.asarray(jnp.asarray(lit, X.dev_dtype(
            T.INT64))).dtype
        # what follows is resident: the staged arrays and the row count
        assert all(isinstance(x, jax.Array) for x in args[3:])
    assert a60[0] >= a10[0]             # a later snapshot
    assert obs_trace.last_trace().sum_attr("inputs", "h2d") == 0
    assert st["inputs_ms"] > 0 and st["h2d_puts"] == 1


# ---------------------------------------------------------------------------
# finalize's narrow path: validity, columns and null masks in one copy
# ---------------------------------------------------------------------------

P = 512


def _narrow_batch(lazy: bool, nulls: bool):
    rng = np.random.default_rng(3)
    valid = rng.random(P) < 0.3
    host = {"i": rng.integers(0, 1 << 40, P), "d": rng.integers(0, 9999, P)}
    masks = {"d": rng.random(P) < 0.25} if nulls else {}
    b = X.DBatch({n: jnp.asarray(a) for n, a in host.items()},
                 jnp.asarray(valid),
                 {"i": T.INT64, "d": T.decimal(12, 2)}, {},
                 {n: jnp.asarray(m) for n, m in masks.items()})
    if lazy:
        src = rng.integers(0, 1 << 30, 3000)
        src_null = rng.random(3000) < 0.4
        take = rng.integers(0, 3000, P)
        b.types["lz"] = T.INT64
        b.lazy["lz"] = X.LazyCol(jnp.asarray(src), jnp.asarray(take),
                                 null_src=jnp.asarray(src_null))
        host["lz"], masks["lz"] = src[take], src_null[take]
    at = np.nonzero(valid)[0]
    want = list(zip(*(
        [None if (n in masks and masks[n][r]) else
         (host[n][r] / 100 if n == "d" else int(host[n][r])) for r in at]
        for n in host)))
    return b, list(host), want, valid.nbytes + sum(
        a.nbytes for a in host.values()) + sum(
        m.nbytes for m in masks.values())


@pytest.mark.parametrize("lazy, nulls", [(False, False), (False, True),
                                         (True, False), (True, True)],
                         ids=["plain", "null-mask", "lazy-column",
                              "lazy-and-null-mask"])
def test_a_narrow_result_leaves_in_one_copy(lazy, nulls):
    b, names, want, nbytes = _narrow_batch(lazy, nulls)
    assert b.padded * 32 < X._COMPACT_MIN_BYTES
    with obs_trace.trace_query("narrow") as qt:
        got_names, rows = X.materialize(b, names)
    assert got_names == names and rows == want
    st = qt.summary()
    assert st["finalize_fetches"] == st["host_syncs"] == 1
    assert st["finalize_fetch_bytes"] == st["d2h_bytes"] == nbytes
    # a lazy column's gathers are dispatched before the copy, counted as
    # launches, and the batch keeps what they made
    assert st["program_calls"] == (2 if lazy else 0)
    assert not b.lazy and ("lz" in b.cols) == lazy
