"""Finalize's way out of a wide, sparse batch: the live rows are selected
on the device (ops/kernels.live_positions) and only they are copied to
the host (exec/executor._materialize).

One padded size (P) serves every case, so the suite compiles the
selection once per output class it meets (256, 320) and the gathers
once per column layout."""

import jax.numpy as jnp
import numpy as np
import pytest

from opentenbase_tpu.analysis import hlo_audit
from opentenbase_tpu.catalog import types as T
from opentenbase_tpu.exec import executor as X
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.obs import trace as obs_trace
from opentenbase_tpu.ops import kernels as K
from opentenbase_tpu.storage.batch import size_class

P = 40960                       # size_class(40000): a quarter step
rng = np.random.default_rng(26)

TYPES = {"i": T.INT64, "t": T.TEXT, "d": T.decimal(12, 2), "dt": T.DATE,
         "b": T.BOOL, "v": T.SqlType(T.TypeKind.VECTOR, max_len=4),
         "n": T.INT64, "f": T.FLOAT64}
WORDS = ["ash", "birch", "cedar", "elm", "fir"]
# one row of the named columns and their null masks, on the device
ROW_BYTES = 8 + 4 + 8 + 4 + 1 + 16 + (8 + 1) + 8


def _host_columns():
    return {"i": rng.integers(-1 << 40, 1 << 40, P),
            "t": rng.integers(0, len(WORDS), P).astype(np.int32),
            "d": rng.integers(-10**9, 10**9, P),
            "dt": rng.integers(0, 20000, P).astype(np.int32),
            "b": rng.random(P) < 0.5,
            "v": rng.random((P, 4)).astype(np.float32),
            "n": rng.integers(0, 100, P),
            "f": rng.random(P)}


HOST = _host_columns()
NULL_N = rng.random(P) < 0.3


def _batch(valid) -> X.DBatch:
    return X.DBatch({n: jnp.asarray(a) for n, a in HOST.items()},
                    jnp.asarray(valid), dict(TYPES), {"t": WORDS},
                    {"n": jnp.asarray(NULL_N)})


def _reference(valid, host=HOST, nulls=None, names=None):
    """The rows a plain numpy pass keeps: position order, decoded."""
    nulls = {"n": NULL_N} if nulls is None else nulls
    at = np.nonzero(valid)[0]
    cols = [X._decode_column(host[n][at], TYPES[n],
                             WORDS if n == "t" else [],
                             nulls[n][at] if n in nulls else None)
            for n in (names or list(host))]
    return list(zip(*cols))


def _valid(live) -> np.ndarray:
    v = np.zeros(P, bool)
    v[np.asarray(live, dtype=np.int64)] = True
    return v


def _fetch_spans(qt):
    return [c for f in qt.root.children if f.name == "finalize"
            for c in f.children if c.name == "finalize.fetch"]


LIVE = {
    "none": [],
    "one": [P // 3],
    "first": [0],
    "last": [P - 1],
    "first_and_last": [0, P - 1],
    "255": np.sort(rng.choice(P, 255, replace=False)),
    "256": np.sort(rng.choice(P, 256, replace=False)),
    "257": np.sort(rng.choice(P, 257, replace=False)),
    "300": np.sort(rng.choice(P, 300, replace=False)),
}


class TestLivePositions:
    @pytest.mark.parametrize("case", list(LIVE))
    def test_positions_in_order(self, case):
        valid = _valid(LIVE[case])
        count, idx = K.live_positions(jnp.asarray(valid), out_size=256)
        want = np.nonzero(valid)[0]
        assert int(count) == len(want)
        assert idx.shape == (256,) and idx.dtype == jnp.int32
        got = np.asarray(idx)
        np.testing.assert_array_equal(got[:len(want)], want[:256])
        # the lanes past the count point at some row of the batch
        assert ((got >= 0) & (got < P)).all()

    def test_every_row_live(self):
        count, idx = K.live_positions(jnp.ones(P, bool), out_size=256)
        assert int(count) == P
        np.testing.assert_array_equal(np.asarray(idx), np.arange(256))

    def test_a_width_that_is_no_multiple_of_a_block(self):
        valid = np.zeros(1000, bool)
        valid[[3, 998, 999]] = True
        count, idx = K.live_positions(jnp.asarray(valid), out_size=256)
        assert int(count) == 3
        np.testing.assert_array_equal(np.asarray(idx)[:3], [3, 998, 999])

    def test_program_has_no_scatter_and_no_sort(self):
        report: dict = {}
        hlo_audit.check_kernels(report)
        assert report["programs"] >= 20
        assert not report.get("export_errors")
        assert not [f for f in report.get("findings", [])
                    if f.rule == "hlo-scatter-sort"]
        # the rule bites: jnp.nonzero(size=) is a scatter-add with one
        # update per input row
        control: dict = {}
        hlo_audit.export_check(
            lambda m: jnp.nonzero(m, size=256, fill_value=0)[0],
            (jnp.zeros(P, bool),), "nonzero", control,
            no_scatter_sort=True)
        assert [f.rule for f in control["findings"]] == ["hlo-scatter-sort"]


class TestMaterializeWide:
    """`_materialize` over the threshold against the numpy reference."""

    def test_the_batch_is_over_the_threshold(self):
        assert P * ROW_BYTES >= X._COMPACT_MIN_BYTES
        assert P == size_class(40000)

    @pytest.mark.parametrize("case", list(LIVE))
    def test_rows_and_copies(self, case):
        valid = _valid(LIVE[case])
        with obs_trace.trace_query("wide") as qt:
            names, rows = X.materialize(_batch(valid))
        assert names == list(HOST)
        assert rows == _reference(valid)
        n = len(LIVE[case])
        classes = [256] if n <= 256 else [256, size_class(n)]
        spans = _fetch_spans(qt)
        assert [s.attrs["compacted"] for s in spans] == classes
        for s, c in zip(spans, classes):
            # one buffer: the count, eight columns and one null mask at
            # the out class; `valid` never leaves the device
            assert s.attrs["fetches"] == 1
            assert s.attrs["bytes"] == c * ROW_BYTES + 4
        st = qt.summary()
        assert st["finalize_fetches"] == len(classes)
        assert st["bytes_materialized"] == classes[-1] * (ROW_BYTES - 1)
        assert len(rows) == n

    @pytest.mark.parametrize("live", [P, P // 2 + 1],
                             ids=["all", "over_half"])
    def test_a_dense_batch_is_copied_whole(self, live):
        valid = np.zeros(P, bool)
        valid[np.sort(rng.choice(P, live, replace=False))] = True
        with obs_trace.trace_query("dense") as qt:
            _names, rows = X.materialize(_batch(valid))
        assert rows == _reference(valid)
        spans = _fetch_spans(qt)
        # one look at the count, then today's copy of everything
        assert [s.attrs["compacted"] for s in spans] == [256, 0]
        assert spans[1].attrs["fetches"] == 1   # one batched copy of ten arrays
        assert spans[1].attrs["bytes"] == P * (ROW_BYTES + 1)

    def test_named_columns_only(self):
        valid = _valid(LIVE["255"])
        b = _batch(valid)
        b.cols["__sort0"] = jnp.zeros(P, jnp.int64)
        with obs_trace.trace_query("named") as qt:
            names, rows = X.materialize(b, ["v", "i", "n"])
        assert names == ["v", "i", "n"]
        assert rows == _reference(valid, names=names)
        (span,) = _fetch_spans(qt)
        assert span.attrs["fetches"] == 1
        assert span.attrs["bytes"] == 256 * (16 + 8 + 8 + 1) + 4

    @pytest.mark.parametrize("case", ["one", "300"])
    def test_lazy_columns_are_gathered_at_the_out_class(self, case):
        """A late-materialized column goes through its indirection at
        out_size rows: `null_src` rides the indirection, `null_out` the
        output rows, and nothing is materialized at full width."""
        valid = _valid(LIVE[case])
        src_rows = 5000
        src = rng.integers(0, 1 << 30, src_rows)
        src_null = rng.random(src_rows) < 0.4
        take = rng.integers(0, src_rows, P)
        out_null = rng.random(P) < 0.2
        idx = jnp.asarray(take)
        b = _batch(valid)
        b.types.update(lz=T.INT64, lo=T.INT64)
        b.lazy["lz"] = X.LazyCol(jnp.asarray(src), idx,
                                 null_src=jnp.asarray(src_null))
        b.lazy["lo"] = X.LazyCol(jnp.asarray(src), idx,
                                 null_out=jnp.asarray(out_null))
        names, rows = X.materialize(b, ["i", "lz", "lo"])
        assert names == ["i", "lz", "lo"]
        host = {"i": HOST["i"], "lz": src[take], "lo": src[take]}
        at = np.nonzero(valid)[0]
        want = list(zip(
            host["i"][at].tolist(),
            [None if m else v for v, m in
             zip(src[take][at].tolist(), src_null[take][at])],
            [None if m else v for v, m in
             zip(src[take][at].tolist(), out_null[at])]))
        assert rows == want
        # still deferred: the full-width gather never ran
        assert set(b.lazy) == {"lz", "lo"}

    def test_gather_rows_composes_like_the_full_width_pass(self):
        """`gather_rows(take)` equals materializing everything and then
        taking `take`, for plain, nullable and both lazy flavours."""
        valid = _valid(LIVE["255"])
        take = jnp.asarray(LIVE["255"][:16].astype(np.int32))
        src = jnp.asarray(rng.integers(0, 99, 700))
        idx = jnp.asarray(rng.integers(0, 700, P))

        def build():
            b = _batch(valid)
            b.lazy["lz"] = X.LazyCol(
                src, idx, null_src=jnp.asarray(rng.random(700) < 0.5))
            b.lazy["lo"] = X.LazyCol(
                src, idx, null_src=b.lazy["lz"].null_src,
                null_out=jnp.asarray(NULL_N))
            return b
        rng_state = rng.bit_generator.state
        cols, nulls = build().gather_rows(take)
        rng.bit_generator.state = rng_state
        full = build().ensure_all()
        assert set(cols) == set(full.cols) and set(nulls) == set(full.nulls)
        for n, a in cols.items():
            np.testing.assert_array_equal(a, np.asarray(full.cols[n])[take])
        for n, m in nulls.items():
            np.testing.assert_array_equal(m, np.asarray(full.nulls[n])[take])

    def test_under_the_threshold_nothing_changes(self, monkeypatch):
        """The same batch, the constant one byte above it: the whole
        copy, `valid` included, and the same rows."""
        valid = _valid(LIVE["257"])
        monkeypatch.setattr(X, "_COMPACT_MIN_BYTES", P * ROW_BYTES + 1)
        with obs_trace.trace_query("narrow") as qt:
            _names, rows = X.materialize(_batch(valid))
        assert rows == _reference(valid)
        (span,) = _fetch_spans(qt)
        assert span.attrs["compacted"] == 0
        assert span.attrs["fetches"] == 1   # one batched copy of ten arrays
        assert span.attrs["bytes"] == P * (ROW_BYTES + 1)
        assert qt.summary()["bytes_materialized"] == P * (ROW_BYTES - 1)


# ---------------------------------------------------------------------------
# through SQL: a table whose four-column read is over the threshold (the
# single-node session hands `materialize` the scan's batch at the table's
# padded width, as the FQS tier does; test_obs.py reads through that one)
# ---------------------------------------------------------------------------

ROWS = 40000


@pytest.fixture(scope="module")
def wide_env():
    s = Session(LocalNode())
    s.execute("create table wide (k bigint primary key, g bigint, "
              "p decimal(12,2), d date, c text)")
    k = np.arange(ROWS, dtype=np.int64)
    data = {"k": k, "g": (k * 7919) % 1000,
            "p": rng.integers(0, 10**7, ROWS),
            "d": rng.integers(8000, 12000, ROWS).astype(np.int32),
            "c": np.asarray([WORDS[i % 5] for i in range(ROWS)],
                            dtype=object)}
    s._insert_rows(s.node.catalog.table("wide"), s.node.stores["wide"],
                   data, ROWS)
    return s, data


def _compacted():
    return [sp.attrs["compacted"]
            for sp in _fetch_spans(obs_trace.last_trace())]


def _row(data, k, cols):
    as_sql = {"k": int, "g": int, "p": float,
              "d": lambda v: T.days_to_date(int(v)), "c": str}
    return tuple(as_sql[c](data[c][k]) for c in cols)


class TestWideTableThroughSql:
    def test_point_read(self, wide_env):
        s, data = wide_env
        for k in (0, 17, ROWS - 1):
            rows = s.query(f"select k, g, p, d, c from wide where k = {k}")
            assert rows == [_row(data, k, "kgpdc")]
            assert _compacted() == [256]

    def test_sorted_result_keeps_its_order(self, wide_env):
        s, data = wide_env
        at = np.nonzero(data["g"] == 7)[0]
        want = sorted((_row(data, k, "kgpc") for k in at),
                      key=lambda r: (-r[2], r[0]))
        assert len(want) == 40
        rows = s.query("select k, g, p, c from wide where g = 7 "
                       "order by p desc, k")
        assert rows == want
        assert _compacted() == [256]        # the sort's batch, table-wide
        assert s.query("select k, g, p, c from wide where g = 7 "
                       "order by p desc, k limit 20") == want[:20]

    def test_more_rows_than_the_first_class(self, wide_env):
        s, data = wide_env
        at = np.nonzero(data["g"] < 8)[0]
        assert len(at) == 320
        rows = s.query("select k, g, p, d from wide where g < 8")
        assert rows == [_row(data, k, "kgpd") for k in at]  # each row once
        assert _compacted() == [256, size_class(len(at))]

    def test_no_rows(self, wide_env):
        s, _data = wide_env
        assert s.query("select k, g, p, d from wide where k = -5") == []
        assert _compacted() == [256]
