"""ops/kernels.join_expand against a numpy reference: the same pairs in the
same order (`np.repeat` of the probe rows by their counts, the build rows
`perm[lo : lo + count]` in order), the exact int64 `tot` the size ladder
compares with `out_size`, 32-bit pair indices out, and a lowered kernel
with no 64-bit gather (a chip with no 64-bit lanes pays two word-gathers
for one)."""

import jax.numpy as jnp
import numpy as np
import pytest

from opentenbase_tpu.analysis import hlo_audit
from opentenbase_tpu.ops import kernels as K


def _reference(lo, counts, perm, out_size, left_outer, probe_valid):
    """(probe_idx, build_idx, total): the live prefix only, at most
    `out_size` pairs; `total` is a Python int (no width)."""
    eff = counts.copy()
    if left_outer:
        eff = np.maximum(counts, 1)
        if probe_valid is not None:
            eff = np.where(probe_valid, eff, 0)
    total = sum(int(e) for e in eff)
    # the copies of each row that fall inside the output class: a count of
    # 2**31 cannot be handed to np.repeat
    upto = np.minimum(np.cumsum(eff.astype(object)), out_size)
    kept = np.diff(np.concatenate([[0], upto])).astype(np.int64)
    probe = np.repeat(np.arange(counts.shape[0]), kept)
    build = [np.full(k, -1) if c == 0 else perm[s:s + k]
             for s, c, k in zip(lo, counts, kept) if k]
    build = np.concatenate(build) if build else np.zeros(0, np.int64)
    return probe, build, total


def _case(name):
    """(lo, counts, perm, out_size, left_outer, probe_valid) by name; each
    builds its own data from a generator seeded by the name."""
    rng = np.random.default_rng(sum(map(ord, name)))

    def joined(np_, nb, hit, dup=3):
        counts = np.where(rng.random(np_) < hit,
                          rng.integers(1, dup + 1, np_), 0)
        lo = np.where(counts > 0, rng.integers(0, nb - dup, np_), 0)
        return lo.astype(np.int64), counts.astype(np.int64), \
            rng.permutation(nb).astype(np.int64)

    if name == "many_zeros":
        return (*joined(5000, 700, 0.03), 1024, False, None)
    if name == "all_zero":
        lo, counts, perm = joined(300, 50, 0.0)
        return lo, counts, perm, 64, False, None
    if name == "total_equals_out_size":
        lo, counts, perm = joined(2000, 500, 0.2)
        return lo, counts, perm, int(counts.sum()), False, None
    if name == "total_over_out_size":
        lo, counts, perm = joined(2000, 500, 0.5)
        assert counts.sum() > 256
        return lo, counts, perm, 256, False, None
    if name == "left_outer":
        return (*joined(900, 200, 0.3), 2048, True, None)
    if name == "left_outer_probe_valid":
        return (*joined(900, 200, 0.3), 2048, True, rng.random(900) < 0.7)
    if name == "left_outer_over_out_size":
        return (*joined(900, 200, 0.3), 128, True, rng.random(900) < 0.7)
    if name == "left_outer_last_rows_invalid":
        pv = np.arange(400) < 350
        return (*joined(400, 100, 0.5), 1024, True, pv)
    if name in ("sum_past_2_31", "sum_past_2_32"):
        # every count stays under the build side, their running sum does
        # not stay under a word: the searched table is clamped at out_size
        nb = 1 << 20
        np_ = 4096 if name == "sum_past_2_31" else 16384
        counts = np.full(np_, nb - 7, np.int64)
        counts[:40] = rng.integers(0, 4, 40)
        lo = np.where(counts > 3, 3, rng.integers(0, nb - 4, np_))
        assert counts.sum() > (1 << 31 if np_ == 4096 else 1 << 32)
        return (lo.astype(np.int64), counts,
                rng.permutation(nb).astype(np.int64), 512, False, None)
    if name == "out_size_64":
        return (*joined(130, 40, 0.1), 64, False, None)
    if name == "class_above_the_probe_side":
        return (*joined(100, 3000, 0.9, dup=40), 8192, False, None)
    if name == "two_levels_under_the_root":
        # more probe rows than the root and one level of pivot rows hold
        return (*joined(140000, 900, 0.01), 1024, False, None)
    if name == "empty_build_side":
        # every valid probe row is null-extended
        return (np.zeros(90, np.int64), np.zeros(90, np.int64),
                np.zeros(0, np.int64), 128, True, rng.random(90) < 0.5)
    if name == "one_probe_row":
        return (np.zeros(1, np.int64), np.full(1, 5, np.int64),
                np.arange(9, dtype=np.int64)[::-1].copy(), 64, False, None)
    raise KeyError(name)


CASES = ["many_zeros", "all_zero", "total_equals_out_size",
         "total_over_out_size", "left_outer", "left_outer_probe_valid",
         "left_outer_over_out_size", "left_outer_last_rows_invalid",
         "sum_past_2_31", "sum_past_2_32", "out_size_64",
         "class_above_the_probe_side", "two_levels_under_the_root",
         "empty_build_side", "one_probe_row"]


@pytest.mark.parametrize("name", CASES)
def test_pairs_order_and_total(name):
    lo, counts, perm, out_size, left_outer, pv = _case(name)
    pi, bi, tot = K.join_expand(
        jnp.asarray(lo), jnp.asarray(counts), jnp.asarray(perm), out_size,
        left_outer=left_outer,
        probe_valid=None if pv is None else jnp.asarray(pv))
    assert pi.dtype == jnp.int32 and bi.dtype == jnp.int32
    assert tot.dtype == jnp.int64
    assert pi.shape == bi.shape == (out_size,)
    probe, build, total = _reference(lo, counts, perm, out_size,
                                     left_outer, pv)
    assert int(tot) == total            # exact, past the class and a word
    k = min(total, out_size)
    pi, bi = np.asarray(pi), np.asarray(bi)
    np.testing.assert_array_equal(pi[:k], probe)
    np.testing.assert_array_equal(bi[:k], build)
    # lanes at or past `total` point at rows in range: the executor
    # gathers through them before `valid` cuts them.  Spread over the
    # sides, not all at row 0: a gather nearly all of whose lanes read
    # ONE address costs the chip by the run (PERF.md section 6, PR 34)
    assert ((pi[k:] >= 0) & (pi[k:] < counts.shape[0])).all()
    assert ((bi[k:] >= -1) & (bi[k:] < max(perm.shape[0], 1))).all()
    if not left_outer:
        assert (bi[k:] >= 0).all()
    if out_size - k >= 64 and counts.shape[0] >= 64:
        assert len(set(pi[k:].tolist())) > min(out_size - k,
                                               counts.shape[0]) // 4


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.int64])
def test_the_kernels_before_it_may_hand_it_either_width(dtype):
    lo, counts, perm, out_size, _, _ = _case("many_zeros")
    want = _reference(lo, counts, perm, out_size, False, None)
    pi, bi, tot = K.join_expand(jnp.asarray(lo, dtype),
                                jnp.asarray(counts, dtype),
                                jnp.asarray(perm, dtype), out_size)
    assert tot.dtype == jnp.int64 and int(tot) == want[2]
    np.testing.assert_array_equal(np.asarray(pi)[:want[2]], want[0])
    np.testing.assert_array_equal(np.asarray(bi)[:want[2]], want[1])


def test_no_probe_rows():
    none = jnp.zeros(0, jnp.int64)
    pi, bi, tot = K.join_expand(none, none, jnp.arange(8), 64)
    assert int(tot) == 0 and not pi.any() and not bi.any()
    assert pi.dtype == bi.dtype == jnp.int32 and tot.dtype == jnp.int64


@pytest.mark.parametrize("name", ["many_zeros", "left_outer_probe_valid",
                                  "total_over_out_size"])
def test_a_class_past_one_pass_runs_in_passes(name, monkeypatch):
    """A class of more than _MAX_LANES lanes is the same kernel over that
    many lanes at a time (the row gathers' temporaries stay bounded)."""
    import jax
    lo, counts, perm, out_size, left_outer, pv = _case(name)
    args = (jnp.asarray(lo), jnp.asarray(counts), jnp.asarray(perm))
    kw = dict(left_outer=left_outer,
              probe_valid=None if pv is None else jnp.asarray(pv))
    out_size += 5                    # no multiple of a pass
    csum = jnp.cumsum(args[1])
    want = (*K.join_expand(*args, out_size, **kw),
            K.lane_rows(csum, out_size))
    monkeypatch.setattr(K, "_MAX_LANES", 96)
    jax.clear_caches()
    try:
        got = (*K.join_expand(*args, out_size, **kw),
               K.lane_rows(csum, out_size))
    finally:
        jax.clear_caches()
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_lane_rows_is_searchsorted_right():
    rng = np.random.default_rng(7)
    csum = np.cumsum(rng.integers(0, 3, 3000)).astype(np.int64)
    got = K.lane_rows(jnp.asarray(csum), 2048)
    want = np.minimum(np.searchsorted(csum, np.arange(2048), side="right"),
                      2999)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


def test_a_class_past_a_word_is_refused():
    i = jnp.zeros(8, jnp.int64)
    with pytest.raises(ValueError, match="2\\*\\*31"):
        K.join_expand(i, i, i, out_size=1 << 31)


@pytest.mark.parametrize("left_outer", [False, True])
def test_the_lowered_kernel_has_no_64_bit_gather(left_outer):
    n = 65536
    i = jnp.zeros(n, jnp.int64)
    report: dict = {}
    hlo_audit.export_check(
        lambda lo, c, p, v: K.join_expand(lo, c, p, out_size=2 * n,
                                          left_outer=left_outer,
                                          probe_valid=v),
        (i, i, i, jnp.zeros(n, bool)), "join_expand", report,
        no_wide_gather=True)
    assert report["programs"] == 1 and not report.get("export_errors")
    assert not report.get("findings"), report["findings"]


def test_the_audit_rule_sees_a_64_bit_gather():
    """The parent's kernel in one line: a gather from an int64 table (jnp
    narrows the indices itself wherever the table's size fits a word)."""
    control: dict = {}
    hlo_audit.export_check(
        lambda t, ix: t[ix],
        (jnp.zeros(64, jnp.int64), jnp.zeros(16, jnp.int32)), "gather",
        control, no_wide_gather=True)
    assert [f.rule for f in control["findings"]] == ["hlo-wide-gather"]
