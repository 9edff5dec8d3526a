"""Device kernels vs numpy oracles (runs on CPU backend; same code path
runs on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opentenbase_tpu.ops import kernels as K

rng = np.random.default_rng(42)


class TestLivePositions:
    def test_basic(self):
        x = np.arange(100, dtype=np.int64)
        mask = (x % 3) == 0
        cnt, idx = K.live_positions(jnp.asarray(mask), 128)
        cnt = int(cnt)
        np.testing.assert_array_equal(x[np.asarray(idx)[:cnt]], x[mask])


class TestGroupedAggDense:
    def test_sum_count_min_max(self):
        n = 1000
        gid = rng.integers(0, 4, n)
        vals = rng.integers(-50, 50, n).astype(np.int64)
        valid = rng.random(n) > 0.3
        (s, c, mn, mx), present = K.grouped_agg_dense(
            jnp.asarray(gid), jnp.asarray(valid),
            (jnp.asarray(vals),) * 4, 4, ("sum", "count", "min", "max"))
        for g in range(4):
            m = (gid == g) & valid
            assert int(s[g]) == vals[m].sum()
            assert int(c[g]) == m.sum()
            assert int(mn[g]) == vals[m].min()
            assert int(mx[g]) == vals[m].max()
            assert int(present[g]) == m.sum()

    def test_min_max_int32_date(self):
        gid = np.zeros(5, dtype=np.int64)
        dates = np.asarray([100, 50, 200, 5, 75], dtype=np.int32)
        (mn, mx), _ = K.grouped_agg_dense(
            jnp.asarray(gid), jnp.ones(5, bool),
            (jnp.asarray(dates),) * 2, 1, ("min", "max"))
        assert int(mn[0]) == 5 and int(mx[0]) == 200

    def test_sum_int32_widens(self):
        gid = np.zeros(3, dtype=np.int64)
        vals = np.full(3, 2**30, dtype=np.int32)
        (s,), _ = K.grouped_agg_dense(
            jnp.asarray(gid), jnp.ones(3, bool), (jnp.asarray(vals),),
            1, ("sum",))
        assert int(s[0]) == 3 * 2**30  # would wrap in int32

    def test_sumf_float_accum(self):
        gid = np.zeros(10, dtype=np.int64)
        vals = np.full(10, 1.5)
        (s,), _ = K.grouped_agg_dense(
            jnp.asarray(gid), jnp.ones(10, bool), (jnp.asarray(vals),),
            1, ("sumf",))
        assert float(s[0]) == pytest.approx(15.0)


_DENSE_KINDS = ("sum", "count", "min", "max", "sumf")
_DENSE_INPUTS = ("wrapping_int64", "int32_widens", "decimal_scaled",
                 "all_invalid", "one_empty_group", "stray_ids_on_invalid")
#: an aggregate without keys, Q1's and Q5's domains, the largest domain
#: executor._exec_agg sends here, and one past it
_DENSE_GROUPS = (1, 6, 25, 4096, 4097)


def _dense_case(inputs: str, g: int, n: int = 389):
    """(group ids, valid, integer column, float column) of one case."""
    r = np.random.default_rng(len(inputs) * 7919 + g)
    gid = r.integers(0, g, n).astype(np.int64)
    valid = r.random(n) > 0.25
    ints = r.integers(-10**6, 10**6, n).astype(np.int64)
    if inputs == "wrapping_int64":      # a group's sum passes 2**63
        ints = r.choice(np.asarray([2**62, 2**62 + 5, -2**62, -2**62 - 9]),
                        n).astype(np.int64)
    elif inputs == "int32_widens":      # three rows overflow int32
        ints = r.integers(2**30, 2**31 - 1, n).astype(np.int32)
    elif inputs == "decimal_scaled":    # DECIMAL(15,2) x (1-d) x (1+t): 1e-6
        ints = r.integers(90_000, 10_499_550, n) * \
            r.integers(90, 101, n) * r.integers(100, 109, n)
    elif inputs == "all_invalid":
        valid = np.zeros(n, bool)
    elif inputs == "one_empty_group":
        valid &= gid != g - 1
    elif inputs == "stray_ids_on_invalid":
        gid = np.where(valid, gid, r.choice(
            np.asarray([-1, -7, g, g + 3, 2**31 + 2, 2**40, -2**40]), n))
    return gid, valid, ints, r.normal(0, 1e3, n)


def _by_scatter(gid, valid, ints, g):
    """The formulation the kernel had before: `segment_*`, one scatter
    update per row into g + 1 cells (invalid rows into the last)."""
    cell = jnp.where(valid, gid, g)
    wide = jnp.where(valid, ints.astype(jnp.int64), 0)
    return (jax.ops.segment_sum(wide, cell, g + 1)[:g],
            jax.ops.segment_sum(valid.astype(jnp.int64), cell, g + 1)[:g],
            jax.ops.segment_min(ints, cell, g + 1)[:g],
            jax.ops.segment_max(ints, cell, g + 1)[:g])


class TestGroupedAggDenseExact:
    """grouped_agg_dense against numpy and against the scatter it
    replaced: integer kinds to the bit (sums wrap modulo 2**64 in every
    order), `sumf` within float64's rounding of a reordered sum."""

    @pytest.mark.parametrize("inputs", _DENSE_INPUTS)
    @pytest.mark.parametrize("g", _DENSE_GROUPS)
    def test_against_numpy_and_the_scatter(self, g, inputs):
        gid, valid, ints, flts = _dense_case(inputs, g)
        live = valid & (gid >= 0) & (gid < g)
        wide = ints.astype(np.int64)
        want_sum = np.zeros(g, np.int64)
        np.add.at(want_sum, gid[live], wide[live])          # wraps
        if inputs == "wrapping_int64" and g == 1:
            assert int(want_sum[0]) != sum(int(v) for v in wide[live])
        want_cnt = np.bincount(gid[live], minlength=g).astype(np.int64)
        info = np.iinfo(ints.dtype)
        want_min = np.full(g, info.max, ints.dtype)
        np.minimum.at(want_min, gid[live], ints[live])
        want_max = np.full(g, info.min, ints.dtype)
        np.maximum.at(want_max, gid[live], ints[live])
        want_f = np.zeros(g)
        np.add.at(want_f, gid[live], flts[live])

        gid, valid, ints = map(jnp.asarray, (gid, valid, ints))
        (s, c, mn, mx, sf), present = K.grouped_agg_dense(
            gid, valid, (ints,) * 4 + (jnp.asarray(flts),), g, _DENSE_KINDS)
        for o, w in ((s, want_sum), (c, want_cnt), (mn, want_min),
                     (mx, want_max), (present, want_cnt)):
            assert o.dtype == w.dtype and o.shape == (g,)
            np.testing.assert_array_equal(np.asarray(o), w)
        np.testing.assert_allclose(np.asarray(sf), want_f,
                                   rtol=1e-12, atol=1e-9)
        for o, w in zip((s, c, mn, mx), _by_scatter(gid, valid, ints, g)):
            assert o.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(o), np.asarray(w))

    @pytest.mark.parametrize("g", [6, 25, 4096])
    def test_program_has_no_scatter_and_no_sort(self, g):
        """What the audit's kernel battery declares of this kernel
        (analysis/hlo_audit.check_kernels), at Q1's and Q5's domains and
        at the largest the executor sends."""
        from opentenbase_tpu.analysis import hlo_audit
        i, v = jnp.zeros(4096, jnp.int64), jnp.zeros(4096, bool)
        report: dict = {}
        hlo_audit.export_check(
            lambda gid, m, a: K.grouped_agg_dense(
                gid, m, a, num_groups=g, agg_kinds=_DENSE_KINDS),
            (i, v, (i, i, i, i, i.astype(float))), f"dense/{g}", report,
            no_scatter_sort=True)
        assert report["programs"] == 1 and not report.get("export_errors")
        rules = [f.rule for f in report.get("findings", [])]
        assert "hlo-scatter-sort" not in rules


class TestGroupedAggSort:
    def test_vs_oracle(self):
        n = 2048
        k1 = rng.integers(0, 50, n).astype(np.int64)
        k2 = rng.integers(0, 3, n).astype(np.int64)
        vals = rng.integers(0, 1000, n).astype(np.int64)
        valid = rng.random(n) > 0.2
        gkeys, (s, c), ng = K.grouped_agg_sort(
            (jnp.asarray(k1), jnp.asarray(k2)), jnp.asarray(valid),
            (jnp.asarray(vals),) * 2, 256, ("sum", "count"))
        ng = int(ng)
        # oracle via python dict
        oracle = {}
        for i in range(n):
            if valid[i]:
                key = (k1[i], k2[i])
                acc = oracle.setdefault(key, [0, 0])
                acc[0] += vals[i]
                acc[1] += 1
        assert ng == len(oracle)
        got = {(int(gkeys[0][i]), int(gkeys[1][i])): (int(s[i]), int(c[i]))
               for i in range(ng)}
        assert got == {k: tuple(v) for k, v in oracle.items()}

    def test_exact_branch_full_range_keys(self):
        """Keys spanning more than 62 bits cannot pack into one sort
        word: the exact multi-pass branch groups them (mixed signs,
        duplicates, invalid rows interleaved)."""
        n = 1024
        pool = rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max,
                            12)
        k1 = rng.choice(pool, n)
        k2 = rng.choice(pool[:3], n)
        k3 = rng.integers(-2, 2, n).astype(np.int64)
        vals = rng.integers(0, 1000, n).astype(np.int64)
        valid = rng.random(n) > 0.3
        gkeys, (s, c, mx), ng = K.grouped_agg_sort(
            tuple(jnp.asarray(k) for k in (k1, k2, k3)), jnp.asarray(valid),
            (jnp.asarray(vals),) * 3, 256, ("sum", "count", "max"))
        oracle = {}
        for i in range(n):
            if valid[i]:
                acc = oracle.setdefault((k1[i], k2[i], k3[i]), [0, 0, 0])
                acc[0] += vals[i]
                acc[1] += 1
                acc[2] = max(acc[2], vals[i])
        ng = int(ng)
        assert ng == len(oracle)
        got = {tuple(int(g[i]) for g in gkeys):
               (int(s[i]), int(c[i]), int(mx[i])) for i in range(ng)}
        assert got == {k: tuple(v) for k, v in oracle.items()}

    def test_empty_input(self):
        gkeys, (s,), ng = K.grouped_agg_sort(
            (jnp.zeros(16, jnp.int64),), jnp.zeros(16, bool),
            (jnp.ones(16, jnp.int64),), 8, ("sum",))
        assert int(ng) == 0


# --- grouped_agg_sort's read of its sorted rows (PR 43) --------------------
# An arm of the kernel is what the host knows of the keys: (key_spans, keys).
_I64 = np.iinfo(np.int64)
_SORT_N = 700


def _arm_keys(arm, n, r):
    """Three key columns with duplicates and `key_spans` for one arm of the
    kernel: `proven` (the bounds prove the pack: the fast sort alone),
    `unprovable` (bounds too wide: the exact passes over two packed words),
    `none_fast` / `none_exact` (nothing known: the `lax.cond`, whose data
    take the pack or refuse it)."""
    if arm == "unprovable":
        pools = (r.integers(0, 2**40, 9), r.integers(0, 2**30, 4),
                 np.arange(8))
        spans = (2**40, 2**30, 7)
    elif arm == "none_exact":
        pools = (np.concatenate([[_I64.min, _I64.max, -1, 0],
                                 r.integers(_I64.min, _I64.max, 6)]),
                 r.integers(_I64.min, _I64.max, 3), np.arange(-2, 2))
        spans = None
    else:
        pools = (np.arange(-20, 31), np.arange(3), np.arange(5))
        spans = (50, 2, 4) if arm == "proven" else None
    return tuple(r.choice(pl, n).astype(np.int64) for pl in pools), spans


def _sorted_agg_case(case, n, r, keys):
    """(valid, inputs, kinds, max_groups, lanes a pass) of one scenario."""
    valid = r.random(n) > 0.25
    ints = r.integers(-1000, 1000, n).astype(np.int64)
    groups, lanes = 256, None
    if case == "int64_extremes":
        wild = r.choice(np.asarray(
            [_I64.min, _I64.max, -1, 0, 1, _I64.min + 1, -2**40]), n)
        inputs, kinds = (wild, wild, wild, ints.astype(np.int32)), \
            ("sum", "min", "max", "sum")
    elif case == "float_sum":
        f = r.standard_normal(n)
        f[r.random(n) < 0.2] = -0.0
        f[np.flatnonzero(valid)[:2]] = np.nan
        inputs, kinds = (f, ints, f.astype(np.float32)), \
            ("sumf", "sum", "sumf")
    elif case == "dead_group":
        # every row of the most frequent first key is invalid
        vals, counts = np.unique(keys[0], return_counts=True)
        valid = valid & (keys[0] != vals[np.argmax(counts)])
        inputs, kinds = (ints, ints, ints), ("min", "max", "sum")
    elif case == "count_beside_sums":
        inputs, kinds = (ints, ints, ints, ints.astype(np.int32)), \
            ("count", "sum", "count", "sum")
    elif case == "overflow":
        inputs, kinds, groups = (ints, ints, ints), ("sum", "count", "min"), 8
    elif case == "no_valid_row":
        valid = np.zeros(n, bool)
        inputs, kinds = (ints, ints, ints), ("sum", "min", "count")
    elif case == "three_passes":
        inputs, kinds, lanes = (ints, ints), ("sum", "max"), 256
    else:
        assert case == "second_matrix"       # 132 words of inputs
        inputs = tuple(r.integers(_I64.min, _I64.max, n) for _ in range(66))
        kinds = ("sum",) * 66
    return valid, inputs, kinds, groups, lanes


def _sorted_agg_oracle(keys, valid, inputs, kinds):
    """A plain group-by: groups in the order of their keys, a group's rows
    in source order; integer sums wrap in int64, float sums add one row
    after another from 0."""
    order = np.lexsort((*reversed(keys), ~valid))[:valid.sum()]
    assert valid[order].all()
    sk = np.stack([k[order] for k in keys], axis=1)
    cut = np.flatnonzero(np.r_[True, (sk[1:] != sk[:-1]).any(axis=1)]) \
        if len(order) else np.zeros(0, int)
    gkeys = sk[cut].T if len(order) else np.zeros((len(keys), 0), np.int64)
    outs = []
    for kind, v in zip(kinds, inputs):
        parts = np.split(v[order], cut[1:]) if len(order) else []
        if kind == "count":
            outs.append(np.asarray([len(p) for p in parts], np.int64))
        elif kind == "sum":
            with np.errstate(over="ignore"):
                outs.append(np.asarray(
                    [p.astype(np.int64).sum(dtype=np.int64) for p in parts],
                    np.int64))
        elif kind == "sumf":
            tot = []
            for p in parts:
                acc = np.float64(0)
                for x in p.astype(np.float64):
                    acc = acc + x
                tot.append(acc)
            outs.append(np.asarray(tot, np.float64))
        else:
            outs.append(np.asarray(
                [getattr(p, kind)() for p in parts], v.dtype))
    return gkeys, outs


def _scalar_take_rows(arrays, idx, keep):
    """What `_take_rows` stands for: `vals[perm]` an array, dead rows 0."""
    return tuple(jnp.where(keep, a[idx], jnp.zeros((), a.dtype))
                 for a in arrays)


class TestSortedAggReadsItsRowsOnce:
    """`grouped_agg_sort` reads its sorted rows through ONE row gather of
    32-bit words and takes the sorted validity as a prefix mask: against a
    plain numpy group-by, and bit for bit against the same kernel reading
    `vals[perm]` an array by scalar gathers."""

    @pytest.mark.parametrize("case", [
        "int64_extremes", "float_sum", "dead_group", "count_beside_sums",
        "overflow", "no_valid_row", "three_passes", "second_matrix"])
    @pytest.mark.parametrize("arm", ["proven", "unprovable", "none_fast",
                                     "none_exact"])
    def test_against_numpy_and_scalar_gathers(self, arm, case, monkeypatch):
        r = np.random.default_rng(sum(map(ord, arm + case)))
        n = _SORT_N
        keys, spans = _arm_keys(arm, n, r)
        valid, inputs, kinds, max_groups, lanes = _sorted_agg_case(
            case, n, r, keys)
        if lanes:
            monkeypatch.setattr(K, "_MAX_LANES", lanes)

        def run():
            # a fresh jit: traced anew under whatever is patched in
            fn = jax.jit(K.grouped_agg_sort.__wrapped__, static_argnames=(
                "max_groups", "agg_kinds", "key_spans"))
            gk, outs, ng = fn(
                tuple(jnp.asarray(k) for k in keys), jnp.asarray(valid),
                tuple(jnp.asarray(v) for v in inputs),
                max_groups=max_groups, agg_kinds=kinds, key_spans=spans)
            return [np.asarray(a) for a in gk], \
                [np.asarray(o) for o in outs], int(ng)
        gk, outs, ng = run()
        want_keys, want = _sorted_agg_oracle(keys, valid, inputs, kinds)
        assert ng == want_keys.shape[1]
        # an overflowed call answers for the first max_groups groups; its
        # last slot's running totals have no next group to end at
        full = min(ng, max_groups)
        whole = full if ng <= max_groups else full - 1
        for g, w in zip(gk, want_keys):
            np.testing.assert_array_equal(g[:full], w[:full])
        for kind, o, w in zip(kinds, outs, want):
            m = whole if kind in ("sum", "count") else full
            assert o.dtype == w.dtype
            np.testing.assert_array_equal(o[:m], w[:m])     # NaN == NaN
            np.testing.assert_array_equal(np.signbit(o[:m]),
                                          np.signbit(w[:m]))
        monkeypatch.setattr(K, "_take_rows", _scalar_take_rows)
        gk2, outs2, ng2 = run()
        assert ng2 == ng
        for a, b in zip(gk + outs, gk2 + outs2):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


class TestJoin:
    def _oracle_pairs(self, probe, build, pvalid, bvalid):
        out = []
        for i, (pk, pv) in enumerate(zip(probe, pvalid)):
            if not pv:
                continue
            for j, (bk, bv) in enumerate(zip(build, bvalid)):
                if bv and pk == bk:
                    out.append((i, j))
        return set(out)

    def test_inner_with_dups(self):
        probe = rng.integers(0, 20, 64).astype(np.int64)
        build = rng.integers(0, 20, 48).astype(np.int64)
        pvalid = rng.random(64) > 0.1
        bvalid = rng.random(48) > 0.1
        skeys, perm = K.join_build(jnp.asarray(build), jnp.asarray(bvalid))
        lo, counts = K.join_probe_counts(skeys, jnp.asarray(probe),
                                         jnp.asarray(pvalid))
        total = int(np.asarray(counts).sum())
        out_size = max(256, total)
        pi, bi, tot = K.join_expand(lo, counts, perm, out_size)
        assert int(tot) == total
        assert perm.dtype == pi.dtype == bi.dtype == jnp.int32
        assert tot.dtype == jnp.int64
        got = {(int(pi[i]), int(bi[i])) for i in range(total)}
        assert got == self._oracle_pairs(probe, build, pvalid, bvalid)

    def test_left_outer(self):
        probe = np.asarray([1, 2, 3, 99], dtype=np.int64)
        build = np.asarray([2, 2, 3], dtype=np.int64)
        skeys, perm = K.join_build(jnp.asarray(build), jnp.ones(3, bool))
        lo, counts = K.join_probe_counts(skeys, jnp.asarray(probe),
                                         jnp.ones(4, bool))
        pi, bi, tot = K.join_expand(lo, counts, perm, 16, left_outer=True,
                                    probe_valid=jnp.ones(4, bool))
        tot = int(tot)
        pairs = sorted((int(pi[i]), int(bi[i])) for i in range(tot))
        # row0 (k=1): null match; row3 (k=99): null match
        assert tot == 5
        assert (0, -1) in pairs and (3, -1) in pairs
        assert (2, 2) in pairs
        assert {p for p, b in pairs if b in (0, 1)} == {1}

    def test_semi_anti(self):
        probe = np.asarray([1, 2, 3], dtype=np.int64)
        build = np.asarray([2], dtype=np.int64)
        skeys, perm = K.join_build(jnp.asarray(build), jnp.ones(1, bool))
        lo, counts = K.join_probe_counts(skeys, jnp.asarray(probe),
                                         jnp.ones(3, bool))
        assert np.asarray(K.semi_mask(counts)).tolist() == [False, True, False]
        assert np.asarray(K.anti_mask(counts, jnp.ones(3, bool))).tolist() \
            == [True, False, True]

    def test_invalid_build_never_matches(self):
        build = np.asarray([5, 5], dtype=np.int64)
        skeys, perm = K.join_build(jnp.asarray(build),
                                   jnp.asarray([True, False]))
        lo, counts = K.join_probe_counts(skeys, jnp.asarray([5], np.int64),
                                         jnp.ones(1, bool))
        assert int(counts[0]) == 1

    def test_left_outer_padding_rows_do_not_null_extend(self):
        probe = np.asarray([1, 2, 7], dtype=np.int64)
        pvalid = np.asarray([True, True, False])
        build = np.asarray([2, 3], dtype=np.int64)
        skeys, perm = K.join_build(jnp.asarray(build), jnp.ones(2, bool))
        lo, counts = K.join_probe_counts(skeys, jnp.asarray(probe),
                                         jnp.asarray(pvalid))
        pi, bi, tot = K.join_expand(lo, counts, perm, 16, left_outer=True,
                                    probe_valid=jnp.asarray(pvalid))
        tot = int(tot)
        pairs = sorted((int(pi[i]), int(bi[i])) for i in range(tot))
        assert pairs == [(0, -1), (1, 0)]

    def test_sentinel_probe_key_unmatchable(self):
        build = np.asarray([7, 7], dtype=np.int64)
        skeys, perm = K.join_build(jnp.asarray(build),
                                   jnp.asarray([False, False]))
        probe = np.asarray([2**63 - 1], dtype=np.int64)
        lo, counts = K.join_probe_counts(skeys, jnp.asarray(probe),
                                         jnp.ones(1, bool))
        assert int(counts[0]) == 0


class TestSort:
    def test_multikey_desc_limit(self):
        n = 500
        a = rng.integers(0, 10, n).astype(np.int64)
        b = rng.integers(0, 1000, n).astype(np.int64)
        valid = rng.random(n) > 0.2
        (sa, sb), svalid = K.sort_rows(
            (jnp.asarray(a), jnp.asarray(b)), jnp.asarray(valid),
            (jnp.asarray(a), jnp.asarray(b)), (False, True), limit=50)
        order = np.lexsort((-b[valid], a[valid]))
        oa = a[valid][order][:50]
        ob = b[valid][order][:50]
        np.testing.assert_array_equal(np.asarray(sa)[:len(oa)], oa)
        np.testing.assert_array_equal(np.asarray(sb)[:len(ob)], ob)

    def test_float_desc(self):
        x = np.asarray([1.5, -2.0, 3.25], dtype=np.float64)
        (sx,), sv = K.sort_rows((jnp.asarray(x),), jnp.ones(3, bool),
                                (jnp.asarray(x),), (True,))
        np.testing.assert_array_equal(np.asarray(sx), [3.25, 1.5, -2.0])


class TestVisibility:
    def test_mask(self):
        xmin_ts = jnp.asarray([10, 10**18 + 1, 1 << 62], dtype=jnp.int64)
        xmax_ts = jnp.asarray([1 << 62, 1 << 62, 1 << 62], dtype=jnp.int64)
        xmin_txid = jnp.asarray([1, 2, 3], dtype=jnp.int64)
        xmax_txid = jnp.zeros(3, dtype=jnp.int64)
        m = K.visibility_mask(xmin_ts, xmax_ts, xmin_txid, xmax_txid,
                              snap_ts=100, my_txid=3,
                              aborted_ts=(1 << 62) + 1)
        assert np.asarray(m).tolist() == [True, False, True]


class TestBuckets:
    def test_matches_host_locator(self):
        from opentenbase_tpu.parallel.locator import shard_ids_for_columns
        keys = np.arange(1000, dtype=np.int64)
        host = shard_ids_for_columns([keys])
        dev = np.asarray(K.bucket_ids((jnp.asarray(keys),), 4096))
        np.testing.assert_array_equal(host, dev)
