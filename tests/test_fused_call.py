"""How the fused tier calls a compiled program (ISSUE 45).

Every host-known scalar of a call (each table's live row count, traced
parameters and masked literals, the snapshot, the txid) is a numpy value
in the call's argument tree, built by ONE helper, `fused._call_args`, for
`_try_fused`, `FragmentProgram.run` (morsels) and `stage_fused_batch` (the
scheduler's coalesced dispatch): no `jnp.*` of a Python scalar, so no put,
no eager `convert_element_type` launch and no device scalar to free.  Each
value carries the dtype the program computes in (`dev_dtype` of its SQL
type, as `mesh_exec._call_program` hands its own), the program is the one
the parent's idiom (`jnp.asarray(7)`: a weak int64 the program casts) traces
but for that scalar cast, row counts stay traced arguments, and answers are
the eager tier's."""

import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

from opentenbase_tpu.exec import fused, plancache
from opentenbase_tpu.exec import scheduler as sm
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError, Executor
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.obs import trace as obs_trace
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.utils import dtypes

XLA_REQUESTS = [0]


def _on_event(event, **_kw):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        XLA_REQUESTS[0] += 1


monitoring.register_event_listener(_on_event)


def programs():
    return sum(r[3] for r in plancache.stats())


DDL = ("create table {t} (k bigint primary key, v bigint, d date, "
       "p decimal(15,2), i integer, f double precision, w text){dist}")


def _rows(lo, hi):
    return ", ".join(
        f"({i}, {i * 10}, date '1995-01-{1 + i % 28:02d}', {i}.25, {i}, "
        f"{i}.5, 'w{i % 3}')" for i in range(lo, hi))


def _fill(s, t, dist=""):
    s.execute(DDL.format(t=t, dist=dist))
    s.execute(f"insert into {t} values (-5, -50, date '1995-02-01', "
              f"-5.25, -5, -5.5, 'w0'), " + _rows(1, 41))


@pytest.fixture()
def calls(monkeypatch):
    """Every argument tree `_call_args` built, in order."""
    seen = []
    orig = fused._call_args

    def spy(*a):
        seen.append(orig(*a))
        return seen[-1]

    monkeypatch.setattr(fused, "_call_args", spy)
    return seen


@pytest.fixture()
def exported(monkeypatch):
    """(fn, args) of every successful `_try_fused` call."""
    seen = []
    monkeypatch.setattr(fused, "EXPORT_HOOK",
                        lambda _tag, fn, args: seen.append((fn, args)))
    return seen


def assert_numpy_call(args, param_dtypes):
    """The call's host scalars are numpy, never a device array; the
    traced values carry `param_dtypes` in order."""
    arrs, snap, txid, pvals, ns = args
    for leaf in (snap, txid, *pvals, *ns.values()):
        assert isinstance(leaf, (np.ndarray, np.generic)), type(leaf)
        assert not isinstance(leaf, jax.Array)
    assert snap.dtype == txid.dtype == np.int64
    assert all(n.dtype == np.int64 for n in ns.values())
    assert [p.dtype for p in pvals] == [np.dtype(d) for d in param_dtypes]
    # the staged columns are the pool's device arrays, as before
    assert all(isinstance(a, jax.Array)
               for cols in arrs.values() for a in cols.values())


def parent_idiom(args):
    """The same call as the parent made it: a device scalar per value,
    a literal weakly typed."""
    arrs, snap, txid, pvals, ns = args
    return (arrs, jnp.int64(int(snap)), jnp.int64(int(txid)),
            tuple(jnp.asarray(p.item()) for p in pvals),
            {t: jnp.int64(int(n)) for t, n in ns.items()})


_SSA = re.compile(r"[%@][A-Za-z_0-9#]+")      # values and private functions


def _lines(text, keep):
    return [_SSA.sub("%", ln.strip()) for ln in text.splitlines()
            if keep(ln) and "func.func" not in ln]


def vector_ops(text):
    """The program's ops over arrays (any tensor with a dimension),
    SSA names erased: where the rows and their dtypes are."""
    return _lines(text, lambda ln: re.search(r"tensor<\d", ln))


def but_scalar_casts(text):
    """Every op of the program except casts of a scalar."""
    return _lines(text, lambda ln: not re.search(
        r"stablehlo\.convert %\S+ : (\(tensor<\w+>\) -> )?tensor<\w+>$",
        ln.strip()))


def lowered_pair(fn, args):
    return (fn.lower(*args).as_text(),
            fn.lower(*parent_idiom(args)).as_text())


# ---------------------------------------------------------------------------
# _try_fused: what a statement's literals and parameters leave as
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def node():
    n = LocalNode()
    _fill(Session(n), "pr")
    return n


CASES = [
    ("int64_key", "select k, v, w from pr where k = 7", ["int64"],
     [(7, 70, "w1")]),
    ("negative_key", "select k, v from pr where k = -5", ["int64"],
     [(-5, -50)]),
    # the parser types a bare integer BIGINT, whatever it is compared with
    ("int32_column", "select count(*) from pr where i < 9", ["int64"],
     [(9,)]),
    ("date", "select count(*) from pr where d < date '1995-01-05'",
     ["int32"], [(7,)]),
    ("decimal", "select count(*) from pr where p > 35.25", ["int64"],
     [(5,)]),
    # a plain decimal point makes a DECIMAL (a scaled int64), an exponent
    # a double
    ("float", "select count(*) from pr where f < 3.75e0", ["float64"],
     [(4,)]),
    ("four_kinds", "select count(*) from pr where i > 2 and d < date "
     "'1995-01-20' and p < 30.25 and f > 4.75e0",
     ["int64", "int32", "int64", "float64"], [(16,)]),
]


@pytest.mark.parametrize("sql,want_dtypes,want_rows",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_literals_ride_the_call_as_numpy_of_the_program_dtype(
        node, calls, exported, monkeypatch, sql, want_dtypes, want_rows):
    s = Session(node)
    assert s.query(sql) == want_rows
    assert len(calls) == len(exported) == 1
    fn, args = exported[0]
    assert args is calls[0]                 # the helper's tree IS the call
    assert_numpy_call(args, want_dtypes)
    # one trace serves the call: the argument avals are the program's
    assert fn._cache_size() == 1
    ours, parents = lowered_pair(fn, args)
    # every op over rows is the parent's, dtype for dtype: a literal
    # beside a 32-bit column does not widen the scan
    assert vector_ops(ours) == vector_ops(parents)
    # the eager tier answers the same
    monkeypatch.setattr(Executor, "_fuse", False)
    assert Session(node).query(sql) == want_rows


def test_point_read_program_is_the_parents_but_for_a_scalar_cast(
        node, exported):
    """The parent's `jnp.asarray(7)` is a WEAK int64 the program casts to
    the literal's type; a numpy value is never weak, so that no-op cast
    of one scalar leaves the text and nothing else does."""
    s = Session(node)
    assert s.query("select k, v, w from pr where k = 11") == \
        [(11, 110, "w2")]
    fn, args = exported[-1]
    ours, parents = lowered_pair(fn, args)
    assert but_scalar_casts(ours) == but_scalar_casts(parents)
    assert len(parents.splitlines()) - len(ours.splitlines()) == 1
    sig = next(ln for ln in ours.splitlines() if "func.func public" in ln)
    assert sig == next(ln for ln in parents.splitlines()
                       if "func.func public" in ln)


def test_program_without_a_traced_value_is_the_parents_text(
        node, exported):
    """Snapshot, txid and row counts were strongly typed int64 before:
    as numpy values they lower to the same text, letter for letter."""
    assert Session(node).query("select count(*), max(v) from pr") == \
        [(41, 400)]
    fn, args = exported[-1]
    assert args[3] == ()
    ours, parents = lowered_pair(fn, args)
    assert ours == parents


def test_joined_fragment_keeps_its_rows_ops(monkeypatch, calls, exported):
    monkeypatch.setenv("OTB_FUSE_JOIN_MIN_ROWS", "0")
    n = LocalNode()
    s = Session(n)
    s.execute("create table c (ck bigint, seg text)")
    s.execute("create table o (ok bigint, ck bigint, qty integer, "
              "price decimal(12,2))")
    s.execute("insert into c values " + ", ".join(
        f"({i}, '{'ABC'[i % 3]}')" for i in range(30)))
    s.execute("insert into o values " + ", ".join(
        f"({i}, {i % 30}, {i % 7}, {i}.50)" for i in range(120)))
    q = ("select seg, count(*) as n, sum(price) as sp from c, o "
         "where c.ck = o.ck and qty < {} and price > {} "
         "group by seg order by seg")
    first = s.query(q.format(5, "10.50"))
    before = programs(), XLA_REQUESTS[0]
    again = s.query(q.format(6, "20.50"))
    assert (programs(), XLA_REQUESTS[0]) == before      # literals traced
    assert first != again
    fn, args = exported[-1]
    assert_numpy_call(args, ["int64", "int64"])
    assert sorted(args[0]) == sorted(args[4]) == ["c", "o"]
    ours, parents = lowered_pair(fn, args)
    assert vector_ops(ours) == vector_ops(parents)
    monkeypatch.setattr(Executor, "_fuse", False)
    assert Session(n).query(q.format(6, "20.50")) == again


def test_prepared_statement_arguments(calls):
    s = ClusterSession(Cluster(n_datanodes=1))
    _fill(s, "pr", " distribute by shard(k)")
    s.execute("prepare rd (bigint, integer, date) as select k, v from pr "
              "where k = $1 and i < $2 and d < $3")
    assert s.query("execute rd (7, 30, date '1995-01-20')") == [(7, 70)]
    assert s.last_query_stats()["tier"] == "fqs"
    built = programs(), XLA_REQUESTS[0]
    assert s.query("execute rd (-5, 12, date '1995-02-09')") == [(-5, -50)]
    assert s.query("execute rd (8, 8, date '1995-02-09')") == []
    assert (programs(), XLA_REQUESTS[0]) == built
    assert len(calls) == 3
    for args in calls:
        assert_numpy_call(args, ["int64", "int32", "int32"])
    assert [int(p) for p in calls[1][3]][:2] == [-5, 12]
    # an `integer` the type cannot hold is refused at the call (cast
    # inside the program it wrapped around and answered `i < -1294967296`)
    with pytest.raises(ExecError, match="out of range"):
        s.query("execute rd (7, 3000000000, date '1995-02-09')")
    assert s.query("execute rd (7, 30, date '1995-02-09')") == [(7, 70)]


def test_tpu_dtype_mode_hands_no_float64(monkeypatch, calls, exported):
    """Under OTB_DTYPE_MODE=tpu a float literal leaves as float32, the
    dtype the program computes in: the parent's idiom handed the chip a
    weak float64 scalar."""
    monkeypatch.setattr(dtypes, "_mode", "tpu")
    n = LocalNode()
    s = Session(n)
    _fill(s, "prt")
    assert s.query("select count(*) from prt where f < 3.75e0 and "
                   "d > date '1995-01-02'") == [(3,)]
    fn, args = exported[-1]
    assert_numpy_call(args, ["float32", "int32"])
    ours, parents = lowered_pair(fn, args)
    assert "f64" not in ours and "tensor<f64>" in parents
    assert vector_ops(ours) == vector_ops(parents)


# ---------------------------------------------------------------------------
# row counts stay traced: a write changes them, no program is built
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ndn", [1, 4], ids=["1dn", "4dn"])
def test_insert_then_read_builds_no_program(ndn, calls, exported,
                                            monkeypatch):
    s = ClusterSession(Cluster(n_datanodes=ndn))
    _fill(s, "kv", " distribute by shard(k)")
    sql = "select k, v, w from kv where k = {}"
    # a growing tail builds a reader at each size class (16, 32, ...: the
    # point cell's `kv_write.json` says why): 15 writes put every node's
    # tail inside one
    for k in range(101, 116):
        s.execute("insert into kv values " + _rows(k, k + 1))
        assert s.query(sql.format(k)) == [(k, k * 10, f"w{k % 3}")]
    assert s.last_query_stats()["tier"] == "fqs"
    calls.clear()
    built = programs(), XLA_REQUESTS[0]
    counts = []
    for k in (116, 117, 118):
        s.execute("insert into kv values " + _rows(k, k + 1))
        assert s.query(sql.format(k)) == [(k, k * 10, f"w{k % 3}")]
        st = s.last_query_stats()
        assert st["program_calls"] == 1
        # the tail the INSERT left is uploaded (`upload`); the call
        # itself puts nothing
        qt = obs_trace.last_trace()
        assert qt.sum_attr("inputs", "h2d") == 0
        assert qt.sum_attr("execute", "h2d") == 0
        assert_numpy_call(calls[-1], ["int64"])
        counts.append(int(calls[-1][4]["kv"]))
    # an acknowledged write is read back: the row count the call carries
    # grew with every INSERT (on four DataNodes each key has its own
    # node's count), and nothing compiled for it
    assert ndn > 1 or counts[0] < counts[1] < counts[2]
    assert (programs(), XLA_REQUESTS[0]) == built
    assert s.query(sql.format(7)) == [(7, 70, "w1")]    # nothing to upload
    assert s.last_query_stats()["h2d_puts"] == 0
    # the read-back's program, tail and all: the parent's ops over rows
    ours, parents = lowered_pair(*exported[-1])
    assert vector_ops(ours) == vector_ops(parents)
    assert but_scalar_casts(ours) == but_scalar_casts(parents)
    # the eager tier on the DataNode answers the same
    monkeypatch.setattr(Executor, "_fuse", False)
    assert s.query(sql.format(117)) == [(117, 1170, "w0")]
    assert s.query(sql.format(-5)) == [(-5, -50, "w0")]


# ---------------------------------------------------------------------------
# a masked literal the trace refuses still bakes, and answers
# ---------------------------------------------------------------------------

def test_refused_mask_bakes_and_answers(node, calls, monkeypatch):
    def refuses(*_args):
        # what a literal-dependent host branch raises under a trace
        return jax.jit(lambda x: 1 if x > 0 else 0)(np.int64(1))

    orig = fused._build_program

    def build(ctx, plan, baked, traced, lits, factors, batch=False):
        if lits:
            return refuses, {}
        return orig(ctx, plan, baked, traced, lits, factors, batch)

    monkeypatch.setattr(fused, "_build_program", build)
    saved = dict(fused._MASK_REFUSED)
    try:
        s = Session(node)
        sql = "select count(*), sum(v) from pr where i < {} and v > 15"
        assert s.query(sql.format(9)) == [(7, 350)]
        assert len(fused._MASK_REFUSED) == len(saved) + 1
        # the masked call was tried with both literals, the retry has
        # none to hand over: they are constants of its program
        assert [len(a[3]) for a in calls] == [2, 0]
        assert s.query(sql.format(4)) == [(2, 50)]      # refused: bakes
        assert [len(a[3]) for a in calls] == [2, 0, 0]
        assert_numpy_call(calls[-1], [])
    finally:
        with fused._STATE_LOCK:
            fused._MASK_REFUSED.clear()
            fused._MASK_REFUSED.update(saved)


# ---------------------------------------------------------------------------
# a refused shape is remembered under the key its readers probe, whoever
# the caller (ISSUE 46: the morsel and the batch path wrote a key no one
# read, so every later statement traced masked, failed and fell back)
# ---------------------------------------------------------------------------

@pytest.fixture()
def refusing(monkeypatch):
    """Arms `_build_program` so that every literal-MASKED program
    refuses as a masked literal feeding a host branch does, and counts
    the builds by kind; the mask ledger is put back afterwards."""
    built = {"masked": 0, "baked": 0}
    orig = fused._build_program

    def refuses(*_args):
        return jax.jit(lambda x: 1 if x > 0 else 0)(np.int64(1))

    def build(ctx, plan, baked, traced, lits, factors, batch=False):
        built["masked" if lits else "baked"] += 1
        if lits:
            return refuses, {}
        return orig(ctx, plan, baked, traced, lits, factors, batch)

    def arm():
        monkeypatch.setattr(fused, "_build_program", build)
        return built

    saved = dict(fused._MASK_REFUSED)
    yield arm
    with fused._STATE_LOCK:
        fused._MASK_REFUSED.clear()
        fused._MASK_REFUSED.update(saved)


def _big_table():
    n = LocalNode()
    s = Session(n)
    s.execute("create table f (k bigint, q integer, v decimal(8,2))")
    ks = np.arange(30000) % 5000
    s._insert_rows(n.catalog.table("f"), n.stores["f"],
                   {"k": ks, "q": (ks % 50).astype(np.int32),
                    "v": (ks % 100).astype(float)}, 30000)
    return n, s


def _refused_serial(refusing):
    sql = "select count(*), sum(v) from f where q < {} and k >= 100"
    # (another node's programs: this one's cache must be cold)
    want = [_big_table()[1].query(sql.format(q)) for q in (25, 26)]
    n, s = _big_table()
    built = refusing()
    assert s.query(sql.format(25)) == want[0]
    first = dict(built)
    assert s.query(sql.format(26)) == want[1]
    return first, built


def _refused_morsel(refusing):
    n, s = _big_table()
    sql = "select count(*), sum(v) from f where q < {} and k >= 100"
    want = [s.query(sql.format(q)) for q in (25, 26)]
    s.execute("set morsel = on")
    s.execute("set morsel_chunk_rows = 4096")
    built = refusing()
    try:
        assert s.query(sql.format(25)) == want[0]
        first = dict(built)
        assert s.query(sql.format(26)) == want[1]
    finally:
        s.execute("set morsel = auto")
    return first, built


def _refused_batch(refusing):
    from opentenbase_tpu.exec.executor import ExecContext
    from opentenbase_tpu.sql.parser import parse_sql
    sql = "select count(*), sum(v) from f where q < {} and k >= 100"
    want = _big_table()[1].query(sql.format(26))
    n, s = _big_table()
    ctx = ExecContext(n.stores, 0, 0, n.cache)

    def classify(q):
        return fused.batch_signature(
            ctx, s._plan_select(parse_sql(sql.format(q))[0]).plan)

    built = refusing()
    info = classify(25)
    queries = [(1 << 60, 0, [v for _n, v, _t in info.lits])] * 2
    # the masked batch program refuses: the group goes serial
    assert fused.run_fused_batch(info, queries) is None
    first = dict(built)
    # the NEXT statement of the shape is not batchable at once, and its
    # serial run bakes at once
    assert classify(26) is None
    assert fused.stage_fused_batch(info, queries) is None
    assert s.query(sql.format(26)) == want
    return first, built


@pytest.mark.parametrize("caller", [_refused_serial, _refused_morsel,
                                    _refused_batch],
                         ids=["serial", "morsel_chunk", "coalesced_batch"])
def test_a_refused_mask_is_remembered_for_the_next_statement(
        caller, refusing):
    first, built = caller(refusing)
    # the first statement traced masked ONCE and was refused ...
    assert first["masked"] == 1, first
    # ... and the next one of that shape, another literal, probed
    # _MASK_REFUSED and built baked at once: no second masked trace
    assert built["masked"] == 1, built
    assert built["baked"] > first["baked"]


# ---------------------------------------------------------------------------
# FragmentProgram.run (morsels) and stage_fused_batch (the scheduler)
# ---------------------------------------------------------------------------

def test_morsel_chunks_call_through_the_helper(calls):
    n = LocalNode()
    s = Session(n)
    s.execute("create table f (k bigint, q integer, v decimal(8,2))")
    ks = np.arange(30000) % 5000
    s._insert_rows(n.catalog.table("f"), n.stores["f"],
                   {"k": ks, "q": (ks % 50).astype(np.int32),
                    "v": (ks % 100).astype(float)}, 30000)
    sql = "select count(*), sum(v) from f where q < 25 and k >= 100"
    s.execute("set morsel = off")
    want = s.query(sql)
    calls.clear()
    s.execute("set morsel = on")
    s.execute("set morsel_chunk_rows = 4096")
    try:
        assert s.query(sql) == want
        chunks = list(calls)
        assert len(chunks) == 8                 # ceil(30000 / 4096)
        for args in chunks:
            assert_numpy_call(args, ["int64", "int64"])
        # the last window is short: its row count is a traced value
        assert sorted({int(a[4]["f"]) for a in chunks}) == [1328, 4096]
        built = programs(), XLA_REQUESTS[0]
        assert s.query(sql.replace("25", "26")) != want
        assert (programs(), XLA_REQUESTS[0]) == built
    finally:
        s.execute("set morsel = auto")


def test_scheduler_batch_stacks_numpy_vectors(calls):
    n = LocalNode()
    s = Session(n)
    s.execute("create table t (a bigint, b double precision, g integer)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i * 0.5}, {i % 3})" for i in range(200)))
    q = ("select g, sum(b) as sb, count(*) as c from t where a < {} "
         "and g < {} and b > {} group by g order by g")
    sqls = [q.format(a, g, b) for a, g, b in
            ((50, 2, "1.5e0"), (80, 3, "2.5e0"), (120, 2, "5e-1"))]
    ref = [Session(n).execute(x)[-1].rows for x in sqls]
    calls.clear()
    res = [None] * len(sqls)

    def go(i, sched):
        res[i] = sched.run(Session(n), sqls[i])[-1].rows

    sm.reset_stats()
    with sm.Scheduler(node=n, window_ms=300.0) as sched:
        threads = [threading.Thread(target=go, args=(i, sched))
                   for i in range(len(sqls))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert res == ref
    batched = [a for a in calls if np.ndim(a[1]) == 1]
    assert batched and sm.stats_snapshot()["batch_dispatches"] >= 1
    for args in batched:
        assert_numpy_call(args, ["int64", "int64", "float64"])
        k = len(args[1])
        assert k & (k - 1) == 0                 # padded to its class
        assert all(p.shape == (k,) for p in (args[2], *args[3]))
        assert all(np.ndim(cnt) == 0 for cnt in args[4].values())
    sm.reset_stats()
