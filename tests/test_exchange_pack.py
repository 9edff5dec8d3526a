"""The exchange's pack as a gather (ops/kernels.bucket_rows + take_rows,
PR 41): every slot of a destination's bucket finds its source row, and a
batch's columns come through that index as ONE gather of 32-bit rows.
Held here against the scatter form it replaced, kept as a numpy reference
of this file: the same rows in the same slots, the same dropped rows and
the same overflow count, every moved column bit for bit; and
`MeshRunner._a2a_batch` on four virtual devices, array for array."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as PS

from opentenbase_tpu.catalog import types as T
from opentenbase_tpu.catalog.schema import NUM_SHARDS
from opentenbase_tpu.exec.executor import DBatch
from opentenbase_tpu.exec.mesh_exec import MeshRunner
from opentenbase_tpu.ops import kernels as K
from opentenbase_tpu.plan import exprs as E
from opentenbase_tpu.storage.batch import next_pow2
from opentenbase_tpu.utils.hashing import splitmix64_np


def scatter_pack(dest, arrays, ndn, bucket):
    """The form the kernels replaced: every SOURCE row computes its slot
    (its rank among the rows bound for its destination, in source order)
    and is put there; a row past the bucket is dropped and counted."""
    n = len(dest)
    slot = np.zeros(n, np.int64)
    for d in range(ndn):
        m = dest == d
        slot[m] = np.cumsum(m)[m] - 1
    keep = (dest < ndn) & (slot < bucket)
    at = dest[keep] * bucket + slot[keep]
    out = [np.zeros((ndn * bucket, *a.shape[1:]), a.dtype) for a in arrays]
    for o, a in zip(out, arrays):
        o[at] = a[keep]
    mask = np.zeros(ndn * bucket, bool)
    mask[at] = True
    return out, mask, int(np.sum((dest < ndn) & (slot >= bucket)))


def same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return got.dtype == want.dtype and got.shape == want.shape \
        and got.tobytes() == want.tobytes()


def dests(rng, n, ndn, live=0.7, empty=None):
    d = rng.integers(0, ndn, n)
    if empty is not None:
        d[d == empty] = (empty + 1) % ndn
    return np.where(rng.random(n) < live, d, ndn).astype(np.int32)


# tables under the search's root (1,024 pivots), over it (one level of
# row gathers), past root * 128 rows (two levels); rows not a multiple
# of 128; 64 is the floor of a bucket
SIZES = [(64, 64), (200, 64), (1000, 256), (1024, 512), (1025, 512),
         (5000, 2048), (131072 + 77, 65536)]


@pytest.mark.parametrize("ndn", [2, 4, 8])
@pytest.mark.parametrize("n,bucket", SIZES)
def test_slot_s_of_destination_d_takes_the_sth_row_bound_for_d(
        ndn, n, bucket):
    rng = np.random.default_rng(n * 31 + ndn)
    dest = dests(rng, n, ndn)
    src, keep, over = K.bucket_rows(jnp.asarray(dest), ndn, bucket)
    assert (src.dtype, keep.dtype, over.dtype) == \
        (jnp.int32, jnp.bool_, jnp.int64)
    src, keep = np.asarray(src), np.asarray(keep)
    assert src.shape == keep.shape == (ndn * bucket,)
    assert src.min() >= 0 and src.max() < n
    dropped = 0
    for d in range(ndn):
        rows = np.flatnonzero(dest == d)
        fits = min(len(rows), bucket)
        dropped += len(rows) - fits
        assert (src[d * bucket:d * bucket + fits] == rows[:fits]).all()
        assert keep[d * bucket:d * bucket + fits].all()
        assert not keep[d * bucket + fits:(d + 1) * bucket].any()
    assert int(over) == dropped
    (ids,), mask, over_ref = scatter_pack(
        dest, [np.arange(n, dtype=np.int32)], ndn, bucket)
    assert (mask == keep).all() and over_ref == dropped
    assert (np.where(keep, src, 0) == ids).all()


@pytest.mark.parametrize("case", ["empty_destination", "all_dead",
                                  "all_one_destination", "overflow",
                                  "exactly_full", "no_rows"])
def test_edges_equal_the_scatter_form(case):
    rng = np.random.default_rng(7)
    ndn, n, bucket = 4, 3000, 1024
    if case == "empty_destination":
        dest = dests(rng, n, ndn, empty=2)
    elif case == "all_dead":
        dest = np.full(n, ndn, np.int32)
    elif case == "all_one_destination":
        dest = np.full(n, 1, np.int32)          # 3,000 rows, 1,024 slots
    elif case == "overflow":
        dest = dests(rng, n, ndn, live=1.0)
        dest[dest == 3] = 0                     # ~1,500 rows bound for 0
    elif case == "exactly_full":
        dest = np.full(n, ndn, np.int32)
        dest[rng.permutation(n)[:bucket]] = 2
    else:
        n, dest = 0, np.zeros(0, np.int32)
    vals = rng.integers(-2**62, 2**62, n)
    src, keep, over = K.bucket_rows(jnp.asarray(dest), ndn, bucket)
    (got,) = K.take_rows((jnp.asarray(vals),), src, keep)
    (want,), mask, over_ref = scatter_pack(dest, [vals], ndn, bucket)
    assert same_bits(keep, mask) and int(over) == over_ref
    assert same_bits(got, want)
    if case in ("all_one_destination", "overflow"):
        assert over_ref > 0
    if case == "exactly_full":
        assert over_ref == 0 and mask[2 * bucket:3 * bucket].all()


def _column(rng, n, kind):
    if kind == "int64":
        a = rng.integers(-2**63, 2**63 - 1, n)
        a[:4] = [-2**63, 2**63 - 1, -1, 2**32]
        return a
    if kind == "uint64":
        a = rng.integers(0, 2**64 - 1, n, dtype=np.uint64)
        a[:2] = [2**64 - 1, 2**63]
        return a
    if kind == "int32":
        a = rng.integers(-2**31, 2**31 - 1, n).astype(np.int32)
        a[:3] = [-2**31, 2**31 - 1, -1]
        return a
    if kind == "uint32":
        return rng.integers(0, 2**32 - 1, n, dtype=np.uint32)
    if kind in ("int16", "int8", "uint8"):
        info = np.iinfo(kind)
        return rng.integers(info.min, info.max, n).astype(kind)
    if kind == "bool":
        return rng.integers(0, 2, n).astype(bool)
    if kind == "vector":
        return rng.standard_normal((n, 3)).astype(np.float32)
    # floats: the payloads arithmetic would lose
    bits = {"float32": np.uint32, "float64": np.uint64}[kind]
    a = rng.standard_normal(n).astype(kind)
    a[:6] = [-0.0, 0.0, np.inf, -np.inf, np.nan, 1e-40]
    a[6:8] = np.array([0x7fc00123, 0xffc0beef] if kind == "float32"
                      else [0x7ff8000000000123, 0xfff80000deadbeef],
                      bits).view(kind)
    return a


KINDS = ["int64", "uint64", "int32", "uint32", "int16", "int8", "uint8",
         "bool", "float32", "float64", "vector"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_moved_column_arrives_bit_for_bit(kind):
    rng = np.random.default_rng(len(kind))
    ndn, n, bucket = 4, 2000, 512
    dest = dests(rng, n, ndn, live=0.9)
    dest[:8] = np.arange(8) % ndn          # the special values all move
    a = _column(rng, n, kind)
    src, keep, _ = K.bucket_rows(jnp.asarray(dest), ndn, bucket)
    (got,) = K.take_rows((jnp.asarray(a),), src, keep)
    (want,), _mask, _over = scatter_pack(dest, [a], ndn, bucket)
    assert same_bits(got, want)


@pytest.mark.parametrize("width", [1, 12, 127, 128, 129, 300])
def test_an_exchange_of_any_width_is_one_gather_a_128_words(width):
    """`width` int32 words a row: columns and null masks side by side;
    past 128 words a second matrix (and a third)."""
    rng = np.random.default_rng(width)
    ndn, n, bucket = 4, 700, 256
    dest = dests(rng, n, ndn)
    kinds = (["int64", "bool", "float32", "int32"] * width)
    arrays, words = [], 0
    for k in kinds:
        w = 2 if k == "int64" else 1
        if words + w > width:
            continue
        arrays.append(_column(rng, n, k))
        words += w
        if words == width:
            break
    src, keep, _ = K.bucket_rows(jnp.asarray(dest), ndn, bucket)
    take = jax.jit(lambda a, i, k: K.take_rows(a, i, k))
    args = (tuple(jnp.asarray(a) for a in arrays), src, keep)
    got = take(*args)
    want, _mask, _over = scatter_pack(dest, arrays, ndn, bucket)
    for g, w in zip(got, want):
        assert same_bits(g, w)
    text = take.lower(*args).as_text()
    assert text.count('"stablehlo.gather"(') == -(-width // 128)
    assert "stablehlo.scatter" not in text


def test_a_lane_class_past_the_pass_limit_runs_in_passes(monkeypatch):
    monkeypatch.setattr(K, "_MAX_LANES", 256)
    jax.clear_caches()
    try:
        rng = np.random.default_rng(3)
        ndn, n, bucket = 4, 3000, 1024      # four passes a search
        dest = dests(rng, n, ndn, live=1.0)
        vals = rng.integers(-2**62, 2**62, n)
        src, keep, over = K.bucket_rows(jnp.asarray(dest), ndn, bucket)
        (got,) = K.take_rows((jnp.asarray(vals),), src, keep)
        (want,), mask, over_ref = scatter_pack(dest, [vals], ndn, bucket)
        assert same_bits(keep, mask) and int(over) == over_ref
        assert same_bits(got, want)
    finally:
        jax.clear_caches()


def test_a_column_nobody_reads_stays_out_of_the_matrix():
    """One matrix of every array would keep alive what made each of
    them; `take_rows` packs the arrays whose taken rows are read."""
    def moved(dest, a, b, c):
        src, keep, _ = K.bucket_rows(dest, 2, 64)
        return K.take_rows((a, jnp.cumsum(b), c), src, keep)[0::2]

    def first(dest, a, b, c):
        return moved(dest, a, b, c)[0]
    n = 100
    args = (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
            jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.int32))
    both = jax.jit(moved).lower(*args).as_text()
    one = jax.jit(first).lower(*args).as_text()
    # b's running sum is made for nobody in either; c's word is in the
    # matrix only where c's rows are read
    assert "f32" not in both and "f32" not in one
    assert "tensor<100x2xi32>" in both and "tensor<100x2xi32>" not in one


# ---------------------------------------------------------------------------
# MeshRunner._a2a_batch on four virtual devices
# ---------------------------------------------------------------------------

NDN = 4


def _runner(shard_map_):
    r = MeshRunner.__new__(MeshRunner)
    r.axis = "dn"
    r.cluster = types.SimpleNamespace(
        ndn=NDN, catalog=types.SimpleNamespace(shard_map=shard_map_))
    return r


@pytest.mark.parametrize("per_shard,mult,live", [
    (100, 1, 0.8),       # the 64-slot floor, buckets that overflow
    (100, 2, 0.8),
    (1500, 1, 0.6),      # a class over the search's root
    (1500, 1, 1.0),      # every row live: some bucket overflows
    (1500, 4, 1.0),      # the cap: nothing can overflow
    (300, 1, 0.0),       # no live row
])
def test_a2a_batch_equals_the_scatter_form_array_for_array(
        per_shard, mult, live):
    rng = np.random.default_rng(per_shard * 7 + mult)
    mesh = Mesh(np.asarray(jax.devices()[:NDN]), ("dn",))
    smap = rng.integers(0, NDN, NUM_SHARDS).astype(np.int32)
    runner = _runner(smap)
    n = per_shard * NDN
    host = {"k": rng.integers(0, 10**6, n),
            "v": _column(rng, n, "int64"),
            "f": _column(rng, n, "float32"),
            "c": _column(rng, n, "int32")}
    null_k = rng.random(n) < 0.1
    null_f = rng.random(n) < 0.3
    valid = rng.random(n) < live
    sqlt = {"k": T.INT64, "v": T.INT64, "f": T.FLOAT64, "c": T.INT32}

    def prog(k, v, f, c, nk, nf, ok):
        b = DBatch({"k": k, "v": v, "f": f, "c": c}, ok, dict(sqlt), {},
                   {"k": nk, "f": nf})
        rb, over = runner._a2a_batch(b, [E.Col("k", T.INT64)], mult)
        assert list(rb.cols) == ["k", "v", "f", "c"]
        assert list(rb.nulls) == ["k", "f"]
        return (tuple(rb.cols.values()), tuple(rb.nulls.values()),
                rb.valid, over)

    spec = PS("dn")
    fn = jax.jit(shard_map(
        prog, mesh=mesh, in_specs=(spec,) * 7,
        out_specs=((spec,) * 4, (spec,) * 2, spec, PS()),
        check_vma=False))
    cols, nulls, new_valid, over = jax.device_get(fn(
        *(jnp.asarray(a) for a in (*host.values(), null_k, null_f, valid))))

    # the reference: each shard packs by the scatter form, then bucket d
    # of shard s lands in slot-range s of shard d
    bucket = min(next_pow2(per_shard),
                 max(64, next_pow2(-(-per_shard // NDN)) * mult))
    arrays = [*host.values(), null_k, null_f]
    packed, dropped = [], 0
    for s in range(NDN):
        at = slice(s * per_shard, (s + 1) * per_shard)
        key = np.where(null_k[at], 0, host["k"][at]).astype(np.uint64)
        sid = splitmix64_np(key) % np.uint64(NUM_SHARDS)
        dest = np.where(valid[at], smap[sid.astype(np.int64)],
                        NDN).astype(np.int32)
        out, mask, over_s = scatter_pack(dest, [a[at] for a in arrays],
                                         NDN, bucket)
        packed.append([o.reshape(NDN, bucket, *o.shape[1:])
                       for o in (*out, mask)])
        dropped += over_s
    want = [np.concatenate([packed[s][i][d] for d in range(NDN)
                            for s in range(NDN)])
            for i in range(len(arrays) + 1)]
    assert int(over) == dropped
    for g, w in zip((*cols, *nulls, new_valid), want):
        assert same_bits(g, w)
    if live == 1.0 and mult == 1:
        assert dropped > 0
    if mult == 4:
        assert dropped == 0
