"""The join kernels choose ONE algorithm when a program is built, from what
the host knows of the build key's range (`key_span`, an upper bound on
max - min: storage/codec.span_bound): join_build the packed single-word
sort or the exact argsort, join_probe_counts the WORD its search by rows
of pivots compares (int32 offsets where the span fits 32 bits, the two
halves of the int64 otherwise).  Each gives what a plain numpy reference
gives, on every shape of data; the probe holds no conditional, no
`while` and no scatter at either width (the choice used to be a
`lax.cond` on the shard's own span, then a direct-address table of
scatters against a binary search in a `while`); and the codec's class
token proves the bound."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opentenbase_tpu.analysis.hlo_audit import scan_hlo_text
from opentenbase_tpu.ops import kernels as K
from opentenbase_tpu.storage import codec

MAX = np.int64(2**63 - 1)
MIN = np.int64(-2**63)
NB, NP = 64, 256            # T = max(2 * NB, NP) = 256


CASES = ["dense", "dense_dups", "span_just_under_T", "span_at_T", "sparse",
         "negative", "hashed", "all_invalid_build", "all_invalid_probe",
         "int64_max_keys", "sparse_max_keys", "probe_far_below"]


def _case(name):
    """(build keys, build valid, probe keys, probe valid, true span of the
    valid build keys or None where no bound is honest)."""
    rng = np.random.default_rng(CASES.index(name))
    bv = rng.random(NB) < 0.7
    pv = rng.random(NP) < 0.6
    if name == "dense":                 # span 99 < T: the old direct arm
        b = rng.integers(1000, 1100, NB)
        p = rng.integers(990, 1110, NP)
    elif name == "dense_dups":
        b = rng.integers(5, 15, NB)
        p = rng.integers(0, 20, NP)
    elif name == "span_just_under_T":   # 255 < 256
        b = np.concatenate([[7, 7 + 255], rng.integers(7, 7 + 256, NB - 2)])
        bv[:2] = True
        p = rng.integers(0, 300, NP)
    elif name == "span_at_T":           # 256 == T: the old searched arm
        b = np.concatenate([[7, 7 + 256], rng.integers(7, 7 + 257, NB - 2)])
        bv[:2] = True
        p = rng.integers(0, 300, NP)
    elif name == "sparse":              # TPC-H orderkeys: 8 of every 32
        b = rng.choice(np.arange(1, 60000, 4), NB, replace=False)
        p = rng.choice(np.arange(1, 60000, 2), NP)
    elif name == "negative":
        b = rng.integers(-500, -300, NB)
        p = rng.integers(-520, -280, NP)
    elif name == "hashed":              # full-range: an int64 span wraps
        b = rng.integers(MIN, MAX, NB, dtype=np.int64)
        p = np.concatenate([b[:NB // 2],
                            rng.integers(MIN, MAX, NP - NB // 2,
                                         dtype=np.int64)])
        return b, bv, p, pv, None
    elif name == "all_invalid_build":
        b = rng.integers(0, 50, NB)
        bv[:] = False
        p = rng.integers(0, 50, NP)
        return b, bv, p, pv, 49
    elif name == "all_invalid_probe":
        b = rng.integers(0, 50, NB)
        p = rng.integers(0, 50, NP)
        pv[:] = False
    elif name == "int64_max_keys":      # NULL keys arrive as INT64_MAX
        b = rng.integers(100, 140, NB)
        b[::5] = MAX
        p = rng.integers(100, 140, NP)
        p[::7] = MAX
        return b, bv, p, pv, 39
    elif name == "sparse_max_keys":     # the narrowed search and NULL keys
        b = rng.choice(np.arange(1, 60000, 4), NB, replace=True)
        b[::6] = MAX
        p = rng.choice(np.arange(1, 60000, 2), NP)
        p[::9] = MAX
        b = b.astype(np.int64)
        return b, bv, p.astype(np.int64), pv, 59999
    elif name == "probe_far_below":     # the offset would wrap in int64
        b = rng.integers(MAX - 200, MAX - 1, NB, dtype=np.int64)
        p = np.concatenate([rng.integers(MIN, MIN + 300, NP // 2,
                                         dtype=np.int64),
                            rng.integers(MAX - 200, MAX - 1, NP // 2,
                                         dtype=np.int64)])
    else:
        raise KeyError(name)
    b = b.astype(np.int64)
    live = b[bv & (b != MAX)]
    return b, bv, p.astype(np.int64), pv, int(live.max() - live.min())


#: the bounds a host may hold for a case: nothing; and, where a bound is
#: honest (not the hashed case), the span itself, a loose class above it,
#: and one far past any table or packed word
BOUNDS = {"unknown": lambda span: None, "exact": lambda span: span,
          "loose": lambda span: 4 * span + 100,
          "huge": lambda span: 1 << 61}

ARMS = [(name, bound) for name in CASES for bound in BOUNDS
        if name != "hashed" or bound == "unknown"]


def _span(name, bound):
    return BOUNDS[bound](_case(name)[4])


def _sorted_ref(b, bv):
    masked = np.where(bv & (b != MAX), b, MAX)
    perm = np.argsort(masked, kind="stable")
    return masked[perm], perm


@pytest.mark.parametrize("name, bound", ARMS)
def test_build_equals_numpy(name, bound):
    span = _span(name, bound)
    b, bv, _p, _pv, _true = _case(name)
    keys, perm = K.join_build(jnp.asarray(b), jnp.asarray(bv),
                              key_span=span)
    want_keys, want_perm = _sorted_ref(b, bv)
    np.testing.assert_array_equal(np.asarray(keys), want_keys)
    np.testing.assert_array_equal(np.asarray(perm), want_perm)


def _check_probe(skeys, p, pv, span, hits=0):
    lo, counts = K.join_probe_counts(jnp.asarray(skeys), jnp.asarray(p),
                                     jnp.asarray(pv), key_span=span)
    assert lo.dtype == counts.dtype == jnp.int32
    usable = pv & (p != MAX)
    left = np.searchsorted(skeys, p, side="left")
    right = np.searchsorted(skeys, p, side="right")
    want = np.where(usable, right - left, 0)
    np.testing.assert_array_equal(np.asarray(counts), want)
    hit = want > 0
    assert hit.sum() >= hits
    np.testing.assert_array_equal(np.asarray(lo)[hit], left[hit])


@pytest.mark.parametrize("name, bound", ARMS)
def test_probe_equals_numpy(name, bound):
    b, bv, p, pv, _true = _case(name)
    _check_probe(_sorted_ref(b, bv)[0], p, pv, _span(name, bound))


@pytest.mark.parametrize("nb, np_", [(0, 8), (8, 0)],
                         ids=["no_build_rows", "no_probe_rows"])
def test_an_empty_side(nb, np_):
    lo, counts = K.join_probe_counts(jnp.arange(nb, dtype=jnp.int64),
                                     jnp.arange(np_, dtype=jnp.int64),
                                     jnp.ones(np_, bool), key_span=3)
    assert lo.shape == counts.shape == (np_,)
    assert not np.asarray(counts).any() and not np.asarray(lo).any()
    assert lo.dtype == counts.dtype == jnp.int32


# -- the search by rows of pivots, level by level ------------------------------

#: build classes whose search has 0, 1 and 2 levels of pivot rows under
#: the root (_ROOT 1,024 pivots, _ROW 128 entries a row)
LEVELS = {"root_only": 1000, "one_level": 5000, "two_levels": 140000}
#: key spaces: a dense one (a cell of the span a key, two a probe row:
#: what a direct-address table used to take) and a sparse one, both int32
#: offsets; a hashed one, the int64's two halves
SPACES = ["dense", "sparse", "hashed"]


def _level_case(nb, space, seed=5):
    """A build side with runs of duplicates that cross a row of 128 and a
    pivot (a row's last entry, and the row of pivots above it), and probe
    keys equal to a pivot, below the first and above the last key, absent
    between two keys, repeated, NULL (INT64_MAX) and invalid.  Returns
    the host's bound too: the true span, or None for a hashed space."""
    rng = np.random.default_rng(seed + nb)
    scale = 1 << 40 if space == "hashed" else 1
    gap = 1 if space == "dense" else 3
    base = -(1 << 62) if space == "hashed" else -700
    keys = base + np.sort(rng.choice(4 * nb // 3 * gap, nb,
                                     replace=False)) * scale
    for at, run in ((100, 60), (250, 300), (127, 2), (128 * 128 - 70, 200),
                    (nb - 90, 40)):
        at = min(max(at, 0), nb - run - 1)
        keys[at:at + run] = keys[at]
    keys = np.sort(keys).astype(np.int64)
    bv = rng.random(nb) < 0.9
    bv[:300] = True
    skeys = np.sort(np.where(bv, keys, MAX))
    live = skeys[skeys != MAX]
    pivots = skeys[127::128]
    p = np.concatenate([
        pivots[:400], pivots[-50:], pivots[:8] - scale, pivots[:8] + scale,
        [live[0] - scale, live[0], live[-1], live[-1] + scale, MAX, MAX],
        rng.choice(live, 500), rng.choice(live, 200) + scale,
        np.full(40, keys[250])]).astype(np.int64)
    span = int(live[-1] - live[0])
    if space == "dense":        # two cells a probe row: as many probes
        p = np.concatenate([p, rng.integers(live[0] - 50, live[-1] + 50,
                                            max(span // 2 + 7
                                                - p.shape[0], 0))])
    pv = rng.random(p.shape[0]) < 0.85
    pv[:460] = True
    return skeys, p, pv, None if space == "hashed" else span


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("level", LEVELS)
def test_every_width_level_by_level(level, space):
    _check_probe(*_level_case(LEVELS[level], space), hits=400)


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("level", LEVELS)
def test_all_dead_probes_match_nothing(level, space):
    skeys, p, _pv, span = _level_case(LEVELS[level], space)
    _lo, counts = K.join_probe_counts(
        jnp.asarray(skeys), jnp.asarray(p), jnp.zeros(p.shape[0], bool),
        key_span=span)
    assert not np.asarray(counts).any()


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("level", ["root_only", "one_level"])
def test_a_probe_class_past_one_pass_runs_in_passes(level, space,
                                                    monkeypatch):
    """More than _MAX_LANES probe rows are the same search over that many
    lanes at a time (the row gathers' temporaries stay bounded); the last
    pass is no whole one."""
    skeys, p, pv, span = _level_case(LEVELS[level], space)
    assert p.shape[0] % 192
    monkeypatch.setattr(K, "_MAX_LANES", 192)
    jax.clear_caches()
    try:
        _check_probe(skeys, p, pv, span, hits=400)
    finally:
        jax.clear_caches()


def _text(fn, *args, **kw):
    return jax.jit(lambda *a: fn(*a, **kw)).lower(*args).as_text()


@pytest.mark.parametrize("nb, np_, span, planes", [
    # the word follows the bound alone, whatever the classes
    (64, 256, 511, 1), (64, 256, 512, 1),
    (256, 64, 127, 1), (256, 64, (1 << 31) - 2, 1),
    (64, 256, None, 2), (64, 256, (1 << 31) - 1, 2),
    # the cell's joins at SF1 over four DataNodes (PERF.md section 6)
    (40960, 524288, 163839, 1),
    (131072, 1572864, 6291455, 1),
    # and over one: lineitem pads to orderkey's own class
    (393216, 6291456, 6291455, 1),
])
def test_the_choice_is_a_function_of_shapes_and_the_bound(nb, np_, span,
                                                          planes):
    """Row gathers of 32-bit words at every class: never a loop, never
    a scatter, no gather from or by 64-bit words (lowered, not compiled:
    shapes alone).  ONE formulation, the search by rows of pivots (two
    descents of a gather a level), over ONE plane of int32 offsets
    where the bound fits a word, else the int64's two halves, half as
    many lanes a pass."""
    s = jax.ShapeDtypeStruct
    probe = _text(K.join_probe_counts, s((nb,), jnp.int64),
                  s((np_,), jnp.int64), s((np_,), bool), key_span=span)
    assert "stablehlo.while" not in probe
    assert "stablehlo.scatter" not in probe
    assert [f.rule for f in scan_hlo_text(
        "k", probe, no_scatter_sort=True, no_conditional=True,
        no_wide_gather=True, no_loop=True)] == []
    levels = 0
    while nb > K._ROOT:
        nb, levels = -(-nb // K._ROW), levels + 1
    passes = -(-np_ // (K._MAX_LANES // planes))
    gathers = len(re.findall(r'= "stablehlo\.gather"', probe))
    assert gathers == passes * 2 * levels * planes
    assert (f"tensor<{min(np_, K._MAX_LANES // planes)}x{nb}xi32>"
            in probe)


@pytest.mark.parametrize("span", [None, 99, 1000, 1 << 40, 1 << 61])
def test_one_arm_is_compiled(span):
    """No conditional in either kernel, whatever the host knows; the
    packed sort is ONE operand wide, the exact one two; the probe's
    search compares one int32 plane where the span fits a word, two
    where it does not, and holds no loop and no scatter."""
    i = jnp.zeros(NB, jnp.int64)
    big = jnp.zeros(NP, jnp.int64)
    build = _text(K.join_build, i, jnp.zeros(NB, bool), key_span=span)
    probe = _text(K.join_probe_counts, i, big, jnp.zeros(NP, bool),
                  key_span=span)
    for txt in (build, probe):
        assert "stablehlo.case" not in txt and "stablehlo.if" not in txt
        assert [f.rule for f in scan_hlo_text("k", txt, no_conditional=True)
                ] == []
    packed = span is not None and span < 1 << 50
    operands = [len(m.split(",")) for m in
                re.findall(r'stablehlo\.sort"?\(([^)]*)\)', build)]
    assert operands == ([1] if packed else [2]), operands
    assert "stablehlo.scatter" not in probe
    assert "stablehlo.while" not in probe
    # the root's compares against every lane, [NP, NB]: two planes tie
    # on the high word (`EQ`, in both descents), one plane never does
    ties = len(re.findall(
        rf"stablehlo\.compare\s+EQ,.*tensor<{NP}x{NB}xi32>", probe))
    assert ties == {None: 2, 99: 0, 1000: 0, 1 << 40: 2, 1 << 61: 2}[span]
    below = len(re.findall(
        rf"stablehlo\.compare\s+LT,.*tensor<{NP}x{NB}xi32>", probe))
    assert below == {None: 3, 99: 1, 1000: 1, 1 << 40: 3, 1 << 61: 3}[span]


def test_the_audit_rule_sees_a_conditional():
    txt = _text(lambda x: jax.lax.cond(x[0] > 0, lambda: x + 1,
                                       lambda: x - 1),
                jnp.zeros(4, jnp.int64))
    assert [f.rule for f in scan_hlo_text("k", txt, no_conditional=True)] \
        == ["hlo-conditional"]
    assert scan_hlo_text("k", txt) == []


@pytest.mark.parametrize("values, cls, bound", [
    (np.arange(1, 150001), "pack32/163840", 163839),      # c_custkey, SF1
    (np.arange(1, 6000001, 4), "pack32/6291456", 6291455),  # o_orderkey
    (np.arange(1, 10001), "pack16", 65535),               # s_suppkey
    (np.arange(0, 25), "pack8", 255),                     # n_nationkey
    (np.arange(-100000, 100000), "for32/229376", 229375),
    (np.arange(1 << 41, (1 << 41) + 70000), "for32", (1 << 32) - 1),
])
def test_the_codec_class_proves_the_bound(values, cls, bound):
    codec.reset_state()
    try:
        h = values.astype(np.int64)
        codes, enc, _aux = codec.encode_staged("t_arms", "k", h)
        assert codec.codec_class(enc) == cls
        assert codec.span_bound(cls) == bound >= int(h.max() - h.min())
        # a value past the proven limit does not fit the descriptor: the
        # column re-chooses, the token changes, the program is built anew
        grown = np.append(h, h.min() + bound + 1)
        again = codec.encode_staged("t_arms", "k", grown)    # None: raw
        cls2 = codec.codec_class(again[1] if again else None)
        assert cls2 != cls
        bound2 = codec.span_bound(cls2)
        assert bound2 is None or \
            bound2 >= int(grown.max() - grown.min())
    finally:
        codec.reset_state()


@pytest.mark.parametrize("cls", ["raw", "dict8/256", "dict16/4096", None])
def test_a_class_without_a_range_proves_nothing(cls):
    assert codec.span_bound(cls) is None


def test_system_columns_keep_the_width_alone():
    codec.reset_state()
    try:
        h = np.arange(1, 200000, dtype=np.int64)
        _c, enc, _a = codec.encode_staged("t_arms", "__xmin_txid", h)
        assert codec.codec_class(enc) == "pack32"
    finally:
        codec.reset_state()


# -- end to end: the bound follows the data ----------------------------------

def _joined(s):
    return sorted(s.query("select a.k, a.v, b.w from a, b where a.k = b.k"))


@pytest.mark.parametrize("fuse_floor", ["0", "1000000000"],
                         ids=["fused", "eager"])
def test_a_key_past_the_proven_range_is_still_joined(monkeypatch, fuse_floor):
    """A join's algorithm rests on the key column's codec class; a key
    written past what the class proves re-stages the column under a wider
    class before the next join reads it, on the fused tier and on the
    eager one, so the direct-address table and the packed sort never see
    a key outside their bound."""
    from opentenbase_tpu.exec.session import LocalNode, Session
    monkeypatch.setenv("OTB_FUSE_JOIN_MIN_ROWS", fuse_floor)
    codec.reset_state()
    try:
        s = Session(LocalNode())
        s.execute("create table a (k bigint, v bigint)")
        s.execute("create table b (k bigint, w bigint)")
        want = []
        for lo, hi in ((1, 200), (60000, 70050), (4999990, 5000020)):
            keys = list(range(lo, hi, 7))
            s.execute("insert into a values " + ", ".join(
                f"({k}, {k * 3})" for k in keys))
            s.execute("insert into b values " + ", ".join(
                f"({k}, {k + 1})" for k in keys[::2]))
            want += [(k, k * 3, k + 1) for k in keys[::2]]
            assert _joined(s) == sorted(want)
    finally:
        codec.reset_state()


@pytest.mark.parametrize("fuse_floor", ["0", "1000000000"],
                         ids=["fused", "eager"])
def test_a_chain_of_joins_repeats(monkeypatch, fuse_floor):
    """Two joins in one statement, each probe searched over its own
    column's bound, on the fused tier and on the eager one: the same
    rows on every call (the first fused call climbs the size ladder) and
    under EXPLAIN ANALYZE."""
    from opentenbase_tpu.exec.session import LocalNode, Session
    monkeypatch.setenv("OTB_FUSE_JOIN_MIN_ROWS", fuse_floor)
    codec.reset_state()
    try:
        s = Session(LocalNode())
        s.execute("create table a (k bigint, v bigint)")
        s.execute("create table b (k bigint, w bigint)")
        s.execute("create table c (w bigint, x bigint)")
        keys = list(range(1, 400, 3))
        s.execute("insert into a values " + ", ".join(
            f"({k}, {k * 3})" for k in keys))
        s.execute("insert into b values " + ", ".join(
            f"({k}, {k + 1})" for k in keys[::2]))
        s.execute("insert into c values " + ", ".join(
            f"({k + 1}, {k})" for k in keys[::4]))
        sql = ("select a.k, c.x from a, b, c "
               "where a.k = b.k and b.w = c.w")
        for _ in range(3):
            assert sorted(s.query(sql)) == [(k, k) for k in keys[::4]]
        foot = [r[0] for r in s.execute("explain analyze " + sql)[0].rows]
        assert any(f"rows={len(keys[::4])}" in ln for ln in foot), foot
    finally:
        codec.reset_state()
