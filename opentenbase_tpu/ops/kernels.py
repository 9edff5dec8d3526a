"""Device kernel library — the DataNode executor hot loops as XLA programs.

Reference analog (SURVEY.md §7.4): ExecSeqScan + qual/projection
(execScan.c, execExprInterp.c), ExecAgg's TupleHashTable (nodeAgg.c,
execGrouping.c), ExecHashJoin's bucketed probe loop (nodeHash.c:570,
nodeHashjoin.c), tuplesort.  Those are per-tuple, pointer-chasing designs;
here every operator is a static-shape array program:

- dynamic result sizes are handled by (padded arrays + count) pairs with
  power-of-two size classes (storage/batch.py:next_pow2), so XLA compiles
  one program per size class, not per query;
- group-by is either *dense* (a compare-and-reduce over the rows against
  a precomputed bounded group id — the path TPC-H Q1 takes, no sort, no
  scatter) or *sort-based* (lexicographic sort + segment reduce) for
  unbounded keys;
- join is sort+search (build side sorted once; probe via a search by rows
  of pivots, then a static-size pair expansion; both find their rows by
  row gathers, in 32-bit words) — the
  TPU-friendly replacement for a chained hash table; multi-key joins combine
  via a 64-bit hash with a residual equality filter added by the planner;
- all kernels take/return whole batches; invalid rows ride along masked.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.custom_dce import custom_dce

from ..utils.dtypes import device_float

INT64_MAX = np.int64(2**63 - 1)
INT64_MIN = np.int64(-2**63)
_INT32_MIN = np.int32(-2**31)
_INT32_MAX = np.int32(2**31 - 1)


def _scoped(scope: str):
    """Run the kernel's body under `jax.named_scope(scope)`: every op it
    traces carries the scope in its metadata, so a device trace can say
    which kernel an XLA op came from, whatever number XLA gave it.  One
    flat vocabulary, shared with the program steps of exec/: otb.scan,
    otb.agg, otb.join_build, otb.join_probe, otb.join_expand,
    otb.join_residual, otb.sort, otb.exchange, otb.finalize.  A scope is
    metadata only: no op, no cost at run time, no part of the persistent
    cache's key."""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)
        return scoped
    return deco


# ---------------------------------------------------------------------------
# visibility (reference: HeapTupleSatisfiesMVCC, utils/time/tqual.c:1203 —
# per-tuple; here one vector compare fused into the scan)
# ---------------------------------------------------------------------------

@_scoped("otb.scan")
def visibility_mask(xmin_ts, xmax_ts, xmin_txid, xmax_txid,
                    snap_ts, my_txid, aborted_ts):
    ins = (xmin_ts <= snap_ts) | ((xmin_txid == my_txid)
                                  & (xmin_ts != aborted_ts))
    dele = (xmax_ts <= snap_ts) | (xmax_txid == my_txid)
    return ins & ~dele


# ---------------------------------------------------------------------------
# codec decode (storage/codec.py): encoded staged column -> original
# values.  Elementwise affine map / LUT gather — XLA fuses it into the
# consuming kernel, so a decoded column never materializes unless the
# final projection needs it.
# ---------------------------------------------------------------------------

@_scoped("otb.scan")
def decode_column(codes, aux, family: str):
    """Decode one encoded staged column.  `aux` carries the original
    dtype (pack marker / FOR reference lo-1 / dict LUT); code 0 is the
    padding sentinel for the for/dict families so zero-padded rows
    decode to exactly 0 — visibility_mask depends on padded __xmax_ts
    staying 0."""
    if family == "pack":
        return codes.astype(aux.dtype)
    if family == "for":
        v = codes.astype(aux.dtype) + aux[0]
        return jnp.where(codes == 0, jnp.zeros((), aux.dtype), v)
    return jnp.take(aux, codes.astype(jnp.int32))


@_scoped("otb.scan")
def cmp_on_codes(codes, aux, family: str, op: str, lit):
    """Predicate eval on encoded values without the padding select:
    live rows carry code >= 1 (for) or the exact value (pack), so
    comparing the shifted codes against the traced literal equals
    comparing decoded values — padding rows are masked by the scan's
    row-count belt anyway.  Returns None when the family has no
    code-space compare (dict ranges)."""
    if family == "pack":
        lhs = codes.astype(aux.dtype)
    elif family == "for":
        lhs = codes.astype(aux.dtype) + aux[0]
    else:
        lhs = jnp.take(aux, codes.astype(jnp.int32))
    rhs = jnp.asarray(lit, aux.dtype)
    if op == "=":
        return lhs == rhs
    if op == "<>":
        return lhs != rhs
    if op == "<":
        return lhs < rhs
    if op == "<=":
        return lhs <= rhs
    if op == ">":
        return lhs > rhs
    if op == ">=":
        return lhs >= rhs
    return None


# ---------------------------------------------------------------------------
# finalize: where a wide, sparse result batch keeps its live rows
# ---------------------------------------------------------------------------

#: flags per block of live_positions' two levels (measured on the chip
#: at 2**21 flags, PERF.md section 6 PR 26: 512 runs 0.06 ms faster and
#: compiles three times as long)
_LIVE_BLOCK = 1024


@functools.partial(jax.jit, static_argnames=("out_size",))
@_scoped("otb.finalize")
def live_positions(valid, out_size: int):
    """(count, idx): how many flags of `valid[P]` are set, and the
    positions of the first `out_size` of them IN POSITION ORDER (a
    sorted batch's row order is its position order).  Lanes >= count
    point at some row in range and are cut by `count` on the host.

    Scatter-free and sort-free: `jnp.nonzero(size=)` lowers to a
    bincount with one scatter update per INPUT row, the op class that
    costs seconds per 6 M rows on the chip (PERF.md section 5), and a
    sort of this size compiles for tens of seconds.  Two levels
    instead: a count per block of flags, a binary search of each output
    lane's rank in the blocks' running count, then a running count
    inside that lane's block alone.  One pass over the flags; a running
    count over all P of them took 4x the device time and 14 s to
    compile."""
    p = valid.shape[0]
    blocks = jnp.pad(valid, (0, -p % _LIVE_BLOCK)).reshape(-1, _LIVE_BLOCK)
    per_block = jnp.sum(blocks, axis=1, dtype=jnp.int32)
    upto = jnp.cumsum(per_block)
    rank = jnp.arange(1, out_size + 1, dtype=jnp.int32)
    blk = jnp.minimum(jnp.searchsorted(upto, rank, side="left"),
                      blocks.shape[0] - 1).astype(jnp.int32)
    rank_in_blk = rank - (upto[blk] - per_block[blk])
    inside = jnp.cumsum(blocks[blk], axis=1, dtype=jnp.int32)
    off = jnp.sum(inside < rank_in_blk[:, None], axis=1, dtype=jnp.int32)
    idx = blk * _LIVE_BLOCK + jnp.minimum(off, _LIVE_BLOCK - 1)
    return upto[-1], jnp.minimum(idx, p - 1)


# ---------------------------------------------------------------------------
# grouped aggregation
# ---------------------------------------------------------------------------

_AGG_KINDS = ("sum", "count", "min", "max", "sumf")


def _extreme(dtype, kind: str):
    """What an empty group's min or max reads: the dtype's far end."""
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return info.max if kind == "min" else info.min
    return np.inf if kind == "min" else -np.inf


@functools.partial(jax.jit, static_argnames=("num_groups", "agg_kinds"))
@_scoped("otb.agg")
def grouped_agg_dense(group_id, valid, agg_inputs: tuple,
                      num_groups: int, agg_kinds: tuple):
    """Aggregate with a precomputed dense group id in [0, num_groups).

    The planner uses this when the grouping keys have a statically
    bounded combined domain (dictionary codes, BOOLs: up to 4,096 cells,
    executor._exec_agg) and for an aggregate without keys (`num_groups`
    1).  Compare-and-reduce: ONE variadic reduction over the rows of
    `[num_groups, n]` operands XLA never materialises (the compare with
    the group's index and each aggregate's select fuse into the
    reduction's input), so the rows are read once for all aggregates.
    No scatter, no sort: a scatter with one update per row serialises
    into its few cells on the chip (65-110 ns a row and aggregate, 2.9 s
    of TPC-H Q1's reply at SF1), while this costs ~0.1 ms a group at
    6.3 M rows and stays ahead of the scatter up to ~65,536 groups
    (PERF.md section 6, PR 28).  A caller with a larger domain wants a
    scatter arm back.

    Outputs are `[num_groups]` each: integer sums and counts int64 (they
    wrap as a serial sum does: addition modulo 2**64 is associative;
    only the row count is summed in int32, which n < 2**31 rows cannot
    overflow), a min's or max's far end for an empty group, and nothing
    from an invalid row or a group id out of range.
    """
    n = valid.shape[0]
    in_range = valid & (group_id >= 0) & (group_id < num_groups)
    gid = jnp.where(in_range, group_id, num_groups).astype(jnp.int32)
    hot = gid[None, :] == jnp.arange(num_groups, dtype=jnp.int32)[:, None]
    operands = [hot.astype(jnp.int32 if n < 2**31 else jnp.int64)]
    inits, combine = [0], [jnp.add]
    for kind, vals in zip(agg_kinds, agg_inputs):
        if kind == "count":
            continue                    # the row count, reduced once
        if kind == "sumf":
            vals = vals.astype(device_float())
        elif kind == "sum" and jnp.issubdtype(vals.dtype, jnp.integer):
            vals = vals.astype(jnp.int64)  # SQL widens sum(int4) -> bigint
        if kind in ("min", "max"):
            inits.append(_extreme(vals.dtype, kind))
            combine.append(jnp.minimum if kind == "min" else jnp.maximum)
        else:
            inits.append(0)
            combine.append(jnp.add)
        operands.append(jnp.where(hot, vals[None, :],
                                  jnp.asarray(inits[-1], vals.dtype)))
    reduced = jax.lax.reduce(
        tuple(operands),
        tuple(jnp.asarray(i, o.dtype) for i, o in zip(inits, operands)),
        lambda a, b: tuple(f(x, y) for f, x, y in zip(combine, a, b)),
        (1,))
    present = reduced[0].astype(jnp.int64)
    rest = iter(reduced[1:])
    outs = tuple(present if kind == "count" else next(rest)
                 for kind in agg_kinds)
    return outs, present


def _sortable_int(k, valid):
    """Key column -> int64 equality-preserving image + (min, max) over
    the valid rows (floats ride their bit pattern with -0.0
    canonicalized — grouping needs equality, not order)."""
    if jnp.issubdtype(k.dtype, jnp.floating):
        from ..utils.dtypes import float_to_bits
        k = float_to_bits(jnp.where(k == 0, jnp.zeros((), k.dtype), k))
    else:
        k = k.astype(jnp.int64)
    i64 = jnp.iinfo(jnp.int64)
    mn = jnp.min(jnp.where(valid, k, i64.max))
    mx = jnp.max(jnp.where(valid, k, i64.min))
    return k, mn, mx


@functools.partial(jax.jit, static_argnames=("max_groups", "agg_kinds",
                                             "key_spans"))
@_scoped("otb.agg")
def grouped_agg_sort(key_cols: tuple, valid, agg_inputs: tuple,
                     max_groups: int, agg_kinds: tuple,
                     key_spans: tuple | None = None):
    """General grouped aggregation: sort on the key columns (invalid
    rows last), boundary detection, segment reduce.

    Sort formulation (measured on 524k rows, XLA CPU): a single-array
    `jnp.sort` is ~4x faster than ANY multi-operand comparator sort
    (41ms vs 182ms for 2 operands, 452ms for 6).  So the fast path
    packs (keys, iota) into ONE int64 word — `acc = acc*range +
    (k-min)` with RUNTIME ranges, then `word = acc*n + iota` (invalid
    rows pack as the maximal acc so they sort last) — sorts it, and
    recovers perm = word % n and the group image word // n.  The pack
    is injective exactly when prod(ranges)*n fits 62 bits, checked at
    runtime; `lax.cond` falls back to the exact multi-operand
    comparator sort otherwise (hashed/full-range keys).  Both sorts put
    the invalid rows last, so the sorted validity is a prefix mask
    (`arange(n) < sum(valid)`, no gather), and everything read a ROW
    through the final perm (an int32 word) comes in ONE gather of 32-bit
    rows (`_take_rows`: every aggregate input that is not a count and,
    after exact passes, the key words whose sorted images are compared,
    side by side in one matrix, dead rows 0), where a scalar gather an
    array cost the chip 50-92 ms each at 6,291,456 lanes, five of them
    in Q17 (PERF.md section 6, PR 43).  The sorted groups are runs: a
    group's first row is found by join_expand's search of a running count, its
    COUNT is the distance to the next group's, its integer SUM the
    difference of one running sum there; float sums, min and max reduce
    by segment (indices_are_sorted).  A group's keys are those of its
    first row, `k[perm[starts]]`, or, where ONE key's bound proves the
    pack, the sorted image there plus the least key.

    `key_spans` is what the host knows of each key column when the
    program is built (an upper bound on max - min over the valid rows,
    None where it knows nothing; executor._group_key_spans).  Where
    every bound is known the sort is chosen THEN, as join_build chooses
    its own: the pack if the bounds prove it injective, else exact
    passes over the keys packed into as few words as the bounds allow
    (Q18's five keys: two) — ONE of the two in the program, where the
    `lax.cond` compiles both (each sort costs the chip's compiler most
    of a minute, CHANGES.md PR 34).

    Returns (group_key_cols, agg_outputs, n_groups).  Everything per
    group SLOT (the search for first rows, one gather a SUM and a key)
    runs at `max_groups` lanes, whatever the rows.  The caller picks it
    (executor._agg_class): the eager tier from the live rows, a traced
    program from the keys' host-known spans where they bound the groups
    below the rows, else a quarter of the rows on the joins' size-class
    ladder.  A call whose n_groups passes max_groups answers for the
    first max_groups groups only: the traced caller reports n_groups
    beside its joins' totals and the runner (fused._try_fused,
    MeshRunner.run) discards the reply and replays one class up.
    """
    n = valid.shape[0]
    _check_word("grouped_agg_sort", n)
    invalid = ~valid
    iota = jnp.arange(n, dtype=jnp.int64)

    ints, mns, mxs = [], [], []
    for k in key_cols:
        ki, mn, mx = _sortable_int(k, valid)
        ints.append(ki)
        mns.append(mn)
        mxs.append(mx)

    # runtime injectivity check: sum of key bit-widths + log2(n+1)
    # must fit a 62-bit pack (f32 log2 overestimates by <1e-6 per
    # term; the 62 vs 63 margin absorbs it).  Ranges are measured in
    # uint64: mx - mn over int64 WRAPS when keys span more than 2^63
    # (float bit patterns of mixed sign, full-range hashes) and a
    # wrapped range would slip past the gate as tiny.
    bits = jnp.float32(0)
    spans = []
    for mn, mx in zip(mns, mxs):
        span = jnp.where(mx >= mn,
                         mx.astype(jnp.uint64) - mn.astype(jnp.uint64),
                         jnp.uint64(0))
        spans.append(span)
        bits = bits + jnp.log2(span.astype(jnp.float32) + 2)
    bits = bits + jnp.log2(jnp.float32(n + 2))
    pack_ok = bits < jnp.float32(62.0)

    # both sorts put the invalid rows LAST (the pack gives them the
    # maximal image, the exact passes sort the invalid flag as the most
    # significant bit), so the sorted validity is a prefix: no gather
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    s_valid = jnp.arange(n, dtype=jnp.int32) < n_valid
    first = jnp.arange(n) == 0
    carried = tuple(v for kind, v in zip(agg_kinds, agg_inputs)
                    if kind != "count")

    def fast(_):
        acc = jnp.zeros(n, dtype=jnp.int64)
        for ki, mn, span in zip(ints, mns, spans):
            # only evaluated under pack_ok: span < 2^62 fits int64
            rng = span.astype(jnp.int64) + 1
            acc = acc * rng + jnp.clip(ki - mn, 0, rng - 1)
        top = jnp.max(jnp.where(valid, acc, 0)) + 1
        word = jnp.where(invalid, top, acc) * n + iota
        sw = jnp.sort(word)
        perm = (sw % n).astype(jnp.int32)
        img = sw // n
        boundary = s_valid & (first | (img != jnp.roll(img, 1)))
        return perm, boundary, _take_rows(carried, perm, s_valid), img

    def exact(words):
        # stable single-word passes, least significant word first and
        # the one that holds the invalid flag last: equal tuples end up
        # adjacent, invalid rows last.  Each pass is a 2-operand sort —
        # the TPU compiler's time for a sort grows with every operand
        # and key (one 6-operand sort at 163840 rows was most of Q3's
        # compile; CHANGES.md, PR 22)
        every = jnp.ones(n, dtype=bool)
        head, perm = jax.lax.sort(
            [words[-1], jnp.arange(n, dtype=jnp.int32)], num_keys=1)
        for w in words[-2::-1]:
            head, perm = jax.lax.sort(
                [_take_rows((w,), perm, every)[0], perm], num_keys=1)
        # the last pass leaves its own word sorted; the other words'
        # sorted images come with the aggregates' inputs
        got = _take_rows((*words[1:], *carried), perm, s_valid)
        differs = head != jnp.roll(head, 1)
        for k in got[:len(words) - 1]:
            differs = differs | (k != jnp.roll(k, 1))
        boundary = s_valid & (first | differs)
        return perm, boundary, got[len(words) - 1:]

    def packed_words():
        """The invalid flag and the keys, most significant first, in as
        few int64 words as the host's bounds allow: a key takes the bits
        its bound needs, as an offset from its run-time minimum (which
        cannot pass the bound); one whose bound fills a word rides
        alone, as its own image."""
        bits = [1] + [max(1, math.ceil(math.log2(sp + 2)))
                      for sp in key_spans]
        cols = [(invalid.astype(jnp.int64), jnp.int64(0))] \
            + list(zip(ints, mns))
        groups, used = [[]], 0
        for i, b in enumerate(bits):
            if groups[-1] and used + b > 62:
                groups.append([])
                used = 0
            groups[-1].append(i)
            used += b
        words = []
        for grp in groups:
            if len(grp) == 1:
                words.append(cols[grp[0]][0])
                continue
            acc = jnp.zeros(n, dtype=jnp.int64)
            for i in grp:
                k, mn = cols[i]
                acc = (acc << bits[i]) | jnp.clip(k - mn, 0,
                                                  (1 << bits[i]) - 1)
            words.append(acc)
        return words

    img = None      # the packed keys in sorted order, where the pack is proven
    if key_spans is not None and None not in key_spans:
        # the same sum over the bounds (the run-time spans cannot pass
        # them), in Python: the sort is the program's, not the data's
        proven = sum(math.log2(sp + 2) for sp in key_spans) \
            + math.log2(n + 2) < 62
        if proven:
            perm, boundary, rows, img = fast(None)
        else:
            perm, boundary, rows = exact(packed_words())
    else:
        perm, boundary, rows = jax.lax.cond(
            pack_ok, lambda _: fast(None)[:3],
            lambda _: exact([invalid, *ints]), None)
    rows = iter(rows)
    n_groups = jnp.sum(boundary)
    run = jnp.cumsum(boundary)      # groups begun up to and at a row
    # each group's first row, by the search join_expand makes of a
    # running count: row gathers, where jnp.nonzero is a scatter
    starts = _by_passes(_lane_search(run, max_groups), max_groups) \
        if n else jnp.zeros(max_groups, jnp.int32)
    slot = jnp.arange(max_groups)
    live, last = slot < n_groups, slot == n_groups - 1

    def run_totals(below, total):
        """A group's total from a running total taken BELOW each group's
        first row (the sorted groups are runs: the next group's reading
        less this one's; the last group's from the grand total)."""
        above = jnp.where(last, total, jnp.roll(below, -1))
        return jnp.where(live, above - below, 0)

    outs = []
    for kind, vals in zip(agg_kinds, agg_inputs):
        if kind == "count":
            # the valid rows are a prefix: a row's index counts them
            outs.append(run_totals(starts.astype(jnp.int64),
                                   n_valid.astype(jnp.int64)))
            continue
        # in sorted order, the dead rows 0 (`_take_rows`' keep)
        vals = next(rows)
        if kind == "sum" and jnp.issubdtype(vals.dtype, jnp.integer):
            # exact in int64 whatever wraps on the way: differences of
            # ONE running sum, one gather, where a scatter-add over
            # 6,291,456 sorted rows cost the chip 0.6 s (PR 34)
            vals = vals.astype(jnp.int64)   # SQL widens sum(int4) -> bigint
            below = (jnp.cumsum(vals) - vals)[starts]
            outs.append(run_totals(below, jnp.sum(vals)))
            continue
        # float sums (a difference of running sums would cancel) and
        # min/max: reduced by segment
        gid = jnp.where(s_valid, run - 1, max_groups)
        if kind == "sumf":
            vals = vals.astype(device_float())
        else:
            vals = jnp.where(s_valid, vals, jnp.asarray(
                _extreme(vals.dtype, kind), vals.dtype))
        reduce = {"min": jax.ops.segment_min,
                  "max": jax.ops.segment_max}.get(kind, jax.ops.segment_sum)
        outs.append(reduce(vals, gid, num_segments=max_groups + 1,
                           indices_are_sorted=True)[:max_groups])
    if img is not None and len(key_spans) == 1 \
            and key_spans[0] + 2 < 1 << 31 \
            and jnp.issubdtype(key_cols[0].dtype, jnp.integer):
        # ONE packed key: the sorted image at a group's first row is the
        # key's offset from the least, a word.  No `k[perm[starts]]`:
        # most slots lie past the last group (6.1 M of Q17's 6,291,456)
        # and read ONE address, and such a gather from a table column
        # took 118.8 or 188.4 ms by where the column lay in HBM, a Q17
        # reply 1,210 or 1,280 ms from one run to the next of ONE seed
        # (PERF.md section 6, PR 34)
        off = img.astype(jnp.int32)[starts].astype(jnp.int64)
        gkeys = ((off + mns[0]).astype(key_cols[0].dtype),)
    else:
        take = perm[starts]
        gkeys = tuple(k[take] for k in key_cols)
    return gkeys, tuple(outs), n_groups


# ---------------------------------------------------------------------------
# join: sort build side once, probe by row gathers, expand pairs
# ---------------------------------------------------------------------------

def _sorted_build(build_keys, build_valid, key_span, minor=None,
                  minor_span=None):
    """join_build's sort, with or without a minor column: (sorted keys,
    perm, minor in that order | None, the minor's base)."""
    n = build_keys.shape[0]
    slots = (key_span + 2) * (n + 1) if key_span is not None else None
    if minor is not None and slots is not None:
        slots = slots * (minor_span + 1) if minor_span is not None else None
    if slots is not None and slots < 1 << 62:
        # a NULL key arrives as INT64_MAX (executor._join_key): outside
        # the host's bound, and unmatchable like an invalid row
        ok = build_valid & (build_keys != INT64_MAX)
        mn = jnp.min(jnp.where(ok, build_keys, INT64_MAX))
        iota = jnp.arange(n, dtype=jnp.int64)
        rng = key_span + 1
        acc = jnp.where(ok, jnp.clip(build_keys - mn, 0, rng - 1), rng)
        if minor is not None:
            mrng = minor_span + 1
            base = jnp.min(jnp.where(ok, minor, INT64_MAX))
            acc = acc * mrng + jnp.where(
                ok, jnp.clip(minor - base, 0, mrng - 1), 0)
        sw = jnp.sort(acc * n + iota)
        perm = (sw % n).astype(jnp.int32)
        acc_s = sw // n
        sminor = None
        if minor is not None:
            # offsets from the base, in a word: the probe subtracts it
            sminor = (acc_s % mrng).astype(jnp.int32)
            acc_s = acc_s // mrng
        skeys = jnp.where(acc_s >= rng, INT64_MAX, acc_s + mn)
        return skeys, perm, sminor, (base if minor is not None else None)
    keys = jnp.where(build_valid, build_keys, INT64_MAX)
    if minor is None:
        perm = jnp.argsort(keys).astype(jnp.int32)
        return keys[perm], perm, None, None
    perm = jax.lax.sort([keys, minor, jnp.arange(n, dtype=jnp.int32)],
                        num_keys=2)[-1]
    return keys[perm], perm, minor[perm], jnp.int64(0)


@functools.partial(jax.jit, static_argnames=("key_span",))
@_scoped("otb.join_build")
def join_build(build_keys, build_valid, key_span: int | None = None):
    """Sort the build side; invalid rows get key INT64_MAX so they sort
    last and can never match a (clamped) probe key.  Returns (sorted
    keys, perm): perm int32, a position in a class a chip can hold.

    ONE algorithm per program, chosen when the program is built:
    `key_span` is what the host knows of the keys (an upper bound on
    max - min over the valid rows: storage/codec.span_bound of the key
    column's class), None when it knows nothing (hashed multi-column
    keys, computed keys).  When the bound times n fits 62 bits, (key -
    min, position) pack into one int64 and a single-array `jnp.sort`
    does it (the single-word trick of grouped_agg_sort: ONE sort
    operand, and the chip's compiler pays for every operand of a sort,
    CHANGES.md PR 22; not timed against the argsort on the chip);
    otherwise the exact argsort.  join_probe_counts reads the same
    bound to choose the word its search compares.
    The choice used to be a `lax.cond` on the shard's own span: both
    sorts compiled into every program, and which one ran was the
    data's."""
    return _sorted_build(build_keys, build_valid, key_span)[:2]


@functools.partial(jax.jit, static_argnames=("key_span", "minor_span"))
@_scoped("otb.join_build")
def join_build_minor(build_keys, build_valid, minor,
                     key_span: int | None = None,
                     minor_span: int | None = None):
    """join_build with the rows of one key in the order of a second
    column: (sorted keys, perm, the minor column in that order, its
    base).  For a semi or anti join whose residual is `minor <> x`: a
    probe row's matches are the slots [lo, lo + count) of ONE sort, the
    smallest minor among them stands at lo and the largest at lo + count
    - 1, and some match differs from x exactly when one of those two
    does (`range_differs`).  The same single sort as join_build's where
    the host's bounds on both columns (`key_span`, `minor_span`) let
    (key, minor, position) pack into one int64: the minor comes back as
    int32 offsets from `base`; else a two-key `lax.sort`, the minor as
    it came and a base of 0.  A row whose minor is NULL is the caller's
    to leave out of `build_valid`: `<>` with NULL is not true."""
    return _sorted_build(build_keys, build_valid, key_span,
                         minor.astype(jnp.int64), minor_span)


#: words per row of join_expand's tables: a row of a [n / 128, 128] int32
#: array is one sublane of the chip's (8, 128) tile, and ONE row gather
#: plus a compare over the row costs a third of one scalar gather there
#: (4.9 against 14.3 ms at 1,572,864 lanes; 64 and 32 cost the same and
#: are padded to 128 in HBM, 16 costs 10.7, 256 7.3: PERF.md section 6,
#: PR 30)
_ROW = 128
#: the search's root: at most this many pivots are compared against
#: every lane with no gather (a level less: 26.0 against 28.9 ms)
_ROOT = 1024
#: lanes per pass: a row gather leaves [lanes, _ROW] int32 in HBM, 1 GiB
#: at 2**21 lanes; a larger class runs in passes of this many (6,291,456
#: lanes: 83.5 ms and 1.3 GB in three passes, 78.1 ms and 3.3 GB in one)
_MAX_LANES = 1 << 21


def _rows_of(table, fill):
    """`table` as rows of _ROW words, the last one padded with `fill`
    (an empty table is one row of it)."""
    n = table.shape[0]
    return jnp.pad(table, (0, -n % _ROW if n else _ROW),
                   constant_values=fill).reshape(-1, _ROW)


def _take(rows, idx):
    """`table[idx]` for `rows = _rows_of(table, ...)` and idx in range:
    ONE row gather and a one-hot select over the row, where a scalar
    gather costs the chip three times as much."""
    hot = jnp.arange(_ROW, dtype=jnp.int32) == (idx % _ROW)[:, None]
    return jnp.sum(jnp.where(hot, rows[idx // _ROW], 0), axis=1,
                   dtype=rows.dtype)


def _by_passes(fn, out_size: int):
    """`fn(j)` over the lanes j = 0 .. out_size - 1, at most _MAX_LANES
    of them at a time (fn returns per-lane arrays; a lane past out_size
    in the last pass is computed and cut)."""
    if out_size <= _MAX_LANES:
        return fn(jnp.arange(out_size, dtype=jnp.int32))
    passes = -(-out_size // _MAX_LANES)
    j = jnp.arange(passes * _MAX_LANES, dtype=jnp.int32)
    outs = jax.lax.map(fn, j.reshape(passes, _MAX_LANES))
    return jax.tree.map(lambda o: o.reshape(-1)[:out_size], outs)


def _in_passes(fn, lanes: tuple, per_pass: int):
    """`fn(*lanes)`, per-lane arrays in and out, over at most `per_pass`
    lanes at a time: each pass a static slice of the inputs, one after
    another in the program.  Not `_by_passes`: its `lax.map` is a
    `while`, which join_probe_counts declares itself free of
    (analysis/hlo_audit, `hlo-loop`), and its lanes are an iota, not
    inputs."""
    outs = [fn(*(a[i:i + per_pass] for a in lanes))
            for i in range(0, lanes[0].shape[0], per_pass)]
    return jax.tree.map(lambda *o: jnp.concatenate(o), *outs)


def _lane_search(csum, out_size: int):
    """j -> the first row whose running count `csum` passes lane j
    (`searchsorted(csum, j, side="right")`), clipped to the last row.

    In 32-bit words: a count past `out_size` decides nothing about a
    lane below it, so the searched table is `min(csum, out_size)`.  By
    ROWS of pivots, not step by step: the table is cut into rows of
    _ROW entries, each row's last entry is a pivot of the level above,
    and a lane descends by ONE row gather a level and a compare-and-
    count over the row; the root's pivots are compared against every
    lane with no gather.  6,291,456 rows are two gathers deep."""
    n = csum.shape[0]
    t = jnp.minimum(csum, out_size).astype(jnp.int32)
    levels, width = [], n
    while width > _ROOT:
        # a pad of out_size is past every lane: never counted
        rows = _rows_of(t, out_size)
        levels.append(rows)
        t, width = rows[:, -1], rows.shape[0]

    def search(j):
        pos = jnp.sum(t <= j[:, None], axis=1, dtype=jnp.int32)
        for rows in reversed(levels):
            blk = jnp.minimum(pos, rows.shape[0] - 1)
            pos = blk * _ROW + jnp.sum(rows[blk] <= j[:, None], axis=1,
                                       dtype=jnp.int32)
        return jnp.minimum(pos, n - 1)
    return search


def _key_search(planes: tuple):
    """q -> (lo, count): where a lane's query starts in a SORTED table
    and how many entries equal it (`searchsorted` left, and right less
    left), the table and the queries given as int32 word planes, most
    significant first, compared lexicographically (one plane: a key that
    fits a word; two: an int64's halves, `_planes`).

    By rows of pivots as `_lane_search`: each level is the table below
    it cut into rows of _ROW entries, a row's last entry its pivot; the
    root's pivots are compared against every lane with no gather, then a
    lane descends by ONE row gather a level and a compare-and-count over
    the row.  Twice: once counting the entries below the query, once
    those at or below it.  No table of run ends is built: a scan over
    the build side costs the chip's compiler 12-165 s at 393,216 to
    1,572,864 rows, this compiles in a second (PERF.md section 6,
    PR 33).  A pad of INT32_MAX in every plane is INT64_MAX's image: at
    or past every query a live lane asks."""
    levels, top, width = [], planes, planes[0].shape[0]
    while width > _ROOT:
        rows = tuple(_rows_of(p, _INT32_MAX) for p in top)
        levels.append(rows)
        top, width = tuple(r[:, -1] for r in rows), rows[0].shape[0]

    def count(last, entries, q):
        hit = last(entries[-1], q[-1])
        for e, x in zip(entries[-2::-1], q[-2::-1]):
            hit = (e < x) | ((e == x) & hit)
        return jnp.sum(hit, axis=1, dtype=jnp.int32)

    def descend(last, q):
        pos = count(last, [p[None, :] for p in top], q)
        for rows in reversed(levels):
            blk = jnp.minimum(pos, rows[0].shape[0] - 1)
            pos = blk * _ROW + count(last, [r[blk] for r in rows], q)
        return pos

    def search(*q):
        q = [x[:, None] for x in q]
        lo = descend(jnp.less, q)
        # one descent's gathered rows in memory at a time
        lo, q = jax.lax.optimization_barrier((lo, q))
        return lo, descend(jnp.less_equal, q) - lo
    return search


def _planes(keys):
    """int64 -> (high, low) int32 words that compare lexicographically,
    as signed words, the way the int64s compare: the chip has no 64-bit
    lanes, and a compare over a gathered row of int64 is two of each."""
    low = (keys & 0xFFFFFFFF).astype(jnp.uint32) ^ jnp.uint32(1 << 31)
    return (keys >> 32).astype(jnp.int32), low.astype(jnp.int32)


def _check_word(what: str, *sizes):
    if sum(sizes) >= 1 << 31:
        raise ValueError(f"{what}: positions in classes of {sizes} rows "
                         "do not fit below 2**31")


@functools.partial(jax.jit, static_argnames=("key_span",))
@_scoped("otb.join_probe")
def join_probe_counts(sorted_keys, probe_keys, probe_valid,
                      key_span: int | None = None):
    """Per-probe-row match range in the sorted build side: (lo, count),
    int32 (positions in a class a chip can hold; sum them in int64).

    ONE formulation: the sorted build side is searched by rows of
    pivots (`_key_search`: two descents of one row gather of 32-bit
    words a level, nothing built but the pivots; no `while`, no scatter,
    no 64-bit gather).  What the host knows of the keys (`key_span`,
    join_build's) chooses the WORD it compares when the program is
    built: int32 offsets from the build side's smallest key where the
    span fits 32 bits, else (a hashed or unknown key) the two halves of
    the int64 compared lexicographically, half as many lanes a pass.
    On a v5e, kernel alone (PERF.md section 6, PR 33): 6,291,456 probe
    rows into 393,216 build rows over a span of 6,291,455 69.6 ms, where
    two int64 direct-address tables read by scalar gathers took 278.7;
    1,572,864 into 131,072 over the same span 12.3, the
    `jnp.searchsorted` it replaced 248.1; 1,572,864 into 16,384 hashed
    keys 21.5 for 375.4.  A direct-address table in words (`start[c]`,
    one sorted scatter-add, ONE row gather a probe row) lost to the
    search at one chip's classes (72.8 and 83.1 against 69.6 and 70.9),
    won 0.8-2.9 ms at a shard's, and went (the same section has its
    curve and the cell's numbers without it).

    A device gather's time depends on WHICH addresses its lanes ask
    for, so what an invalid probe row looks up matters: every one of
    them asks for the least value (offset -1, INT64_MIN) and reads the
    same leftmost row at every level whatever the build side holds.
    They used to search INT64_MAX - 1 and home in on the boundary
    between the live keys and the invalid-build sentinels, a path set
    by the live COUNT (PERF.md section 6, PR 27).

    INT64_MAX is a reserved key value (the invalid-build sentinel): a
    valid probe row carrying it is treated as unmatchable rather than
    matching masked-out build rows.
    """
    nb = sorted_keys.shape[0]
    np_ = probe_keys.shape[0]
    _check_word("join_probe_counts", nb)
    if not nb or not np_:
        none = jnp.zeros(np_, jnp.int32)
        return none, none
    ok = probe_valid & (probe_keys != INT64_MAX)
    wide = key_span is None or key_span >= (1 << 31) - 1
    if wide:
        table = _planes(sorted_keys)
        query = _planes(jnp.where(probe_valid, probe_keys, INT64_MIN))
    else:
        # offsets from the build side's smallest key (INT64_MAX when no
        # build row is live); an invalid build row takes the first one
        # past the span.  The probe's in uint64: an int64 difference
        # wraps for keys far below the build side's (a full-range space)
        mn = sorted_keys[0]
        table = (jnp.where(sorted_keys != INT64_MAX,
                           jnp.clip(sorted_keys - mn, 0, key_span),
                           key_span + 1).astype(jnp.int32),)
        off = probe_keys.astype(jnp.uint64) - mn.astype(jnp.uint64)
        ok = ok & (probe_keys >= mn) & (off <= jnp.uint64(key_span))
        query = (jnp.where(ok, off.astype(jnp.int32), -1),)
    # a level's gathered rows are a temporary a plane
    lo, counts = _in_passes(_key_search(table), query,
                            _MAX_LANES // (2 if wide else 1))
    return lo, jnp.where(ok, counts, 0)


@functools.partial(jax.jit, static_argnames=("out_size",))
@_scoped("otb.join_expand")
def lane_rows(csum, out_size: int):
    """For each output lane j < out_size, the row whose run of lanes
    holds it, given the rows' running count of lanes `csum` (int64,
    non-decreasing); the last row for the lanes no row reaches.  int32.
    The search of join_expand, for an operator that repeats rows by a
    count and joins nothing (INTERSECT/EXCEPT ALL)."""
    _check_word("lane_rows", out_size, csum.shape[0])
    if not csum.shape[0]:
        return jnp.zeros(out_size, jnp.int32)
    return _by_passes(_lane_search(csum, out_size), out_size)


@functools.partial(jax.jit, static_argnames=("out_size", "left_outer"))
@_scoped("otb.join_expand")
def join_expand(lo, counts, perm, out_size: int, left_outer: bool = False,
                probe_valid=None):
    """Materialize (probe_idx, build_idx) pairs into a static out_size.

    With left_outer, *valid* probe rows with zero matches emit one pair with
    build_idx == -1 (the null row); pass probe_valid so padding rows don't
    null-extend.  Returns (probe_idx, build_idx, total): the indices
    int32, `total` the exact int64 number of pairs (it may pass out_size
    and a word: the size ladder compares it with out_size).  A lane at or
    past `total` carries indices in range, spread over the two sides.

    Every position is below a static class, so only `total` and the
    running count behind it need 64 bits.  A lane finds its probe row p
    by `_lane_search`; its build row is `perm[j + d[p]]` with `d = lo -
    (csum - eff)` computed once per PROBE row and clamped into a word
    (a row that starts past out_size is read by no live lane); under
    left_outer a row with no match carries INT32_MIN there, which is
    its null flag.  `d[p]` and `perm[...]` are row gathers too (_take).

    ONE formulation, kept from a sweep on a v5e at the cells' shapes
    (PERF.md section 6, PR 30; 1,572,864 lanes over 6,291,456 probe
    rows, kernel alone): this one 26.0 ms; the same with two scalar
    gathers behind the search 39.9; a 32-bit coarse binary search and
    one row gather 216; the plain 32-bit binary search 305; what it
    replaced (a 64-bit `searchsorted` of the running count, then
    `csum[p]`, `eff[p]`, `lo[p]`, `perm[...]`, all int64) 1,120.  What
    the lanes at or past `total` look up INSIDE the kernel moved nothing
    (26.0 or 26.6 ms): they search their own j and `valid` cuts them.
    """
    np_, nb = counts.shape[0], perm.shape[0]
    _check_word("join_expand", out_size, max(np_, nb))
    if not np_:
        none = jnp.zeros(out_size, jnp.int32)
        return none, none, jnp.int64(0)
    counts = counts.astype(jnp.int64)
    if left_outer:
        eff = jnp.maximum(counts, 1)
        if probe_valid is not None:
            eff = jnp.where(probe_valid, eff, 0)
    else:
        eff = counts
    csum = jnp.cumsum(eff)
    total = csum[-1]
    d = jnp.clip(lo.astype(jnp.int64) - (csum - eff), -out_size,
                 nb).astype(jnp.int32)
    if left_outer:
        d = jnp.where(counts == 0, _INT32_MIN, d)
    search = _lane_search(csum, out_size)
    d_rows = _rows_of(d, 0)
    perm_rows = _rows_of(perm.astype(jnp.int32), 0)

    def pairs(j):
        p = search(j)
        dp = _take(d_rows, p)
        # j + INT32_MIN stays in the word: j >= 0
        build_idx = _take(perm_rows, jnp.clip(j + dp, 0, max(nb - 1, 0)))
        valid = j < total
        # a lane at or past `total` is cut by the caller's mask, but the
        # column gathers behind the join still read where it points.
        # Not row 0 for all of them: 1,572,864 lanes of which a few
        # thousand are live read ONE address of a table column in 28 to
        # 62 ms, by where the column lies and what the live lanes ask
        # (Q17 on one of three levels, Q18 on 1,178-1,228 ms; PERF.md
        # section 6, PR 34).  Each reads a row of its own, spread over
        # the side: what a gather of live lanes costs, whatever the run
        spread = j.astype(jnp.uint32) * jnp.uint32(2654435761)
        if left_outer:
            build_idx = jnp.where(dp == _INT32_MIN, -1, build_idx)
        else:
            build_idx = jnp.where(valid, build_idx,
                                  (spread % max(nb, 1)).astype(jnp.int32))
        return jnp.where(valid, p, (spread % np_).astype(jnp.int32)), \
            build_idx

    return (*_by_passes(pairs, out_size), total)


@jax.jit
@_scoped("otb.join_expand")
def compose_index(prior, take):
    """Late-materialization index composition: `prior` maps an operator's
    output positions to source rows, `take` re-points a downstream
    operator's output into that space — the result maps the downstream
    output DIRECTLY to source rows.  One int gather of len(take),
    regardless of how many payload columns ride the indirection: this is
    the whole-join replacement for per-column payload gathers."""
    return prior[take]


@jax.jit
@_scoped("otb.join_probe")
def semi_mask(counts):
    return counts > 0


@jax.jit
@_scoped("otb.join_probe")
def anti_mask(counts, probe_valid):
    return probe_valid & (counts == 0)


@jax.jit
@_scoped("otb.join_residual")
def range_differs(lo, counts, sorted_minor, base, probe_minor, probe_ok):
    """Per probe row: does its match range [lo, lo + count) of a build
    side sorted by (key, minor) (`join_build_minor`) hold a row whose
    minor differs from the probe row's own?  EXISTS (... and b.c <> a.c)
    as a mask: the range's smallest minor stands first and its largest
    last, and all of them equal x exactly when both do.  Two gathers a
    probe row and a compare, where the expanded form pays a pair a match
    (`join_expand`, the residual over the pairs, a scatter-add back).
    `probe_ok` is the probe row's validity and its minor's not being
    NULL.  The minor as int32 offsets is read by row gathers (`_take`),
    at most _MAX_LANES probe rows a pass."""
    nb = sorted_minor.shape[0]
    if not nb or not lo.shape[0]:
        return jnp.zeros(lo.shape[0], bool)
    x = probe_minor.astype(jnp.int64) - base
    first = jnp.clip(lo, 0, nb - 1)
    last = jnp.clip(lo + counts - 1, 0, nb - 1)
    if sorted_minor.dtype == jnp.int32:
        rows = _rows_of(sorted_minor, 0)

        def ends(a, b):
            a = _take(rows, a)
            # one gather's rows in memory at a time
            a, b = jax.lax.optimization_barrier((a, b))
            return a, _take(rows, b)
        smallest, largest = _in_passes(ends, (first, last), _MAX_LANES)
    else:
        smallest, largest = sorted_minor[first], sorted_minor[last]
    return probe_ok & (counts > 0) & ((smallest.astype(jnp.int64) != x)
                                      | (largest.astype(jnp.int64) != x))


# ---------------------------------------------------------------------------
# sort / top-k
# ---------------------------------------------------------------------------

def _order_key(col, desc: bool):
    """Make an ascending-sortable key implementing DESC by bit tricks."""
    if col.dtype == jnp.bool_:
        col = col.astype(jnp.int32)
    if desc:
        if col.dtype in (jnp.float64, jnp.float32):
            return -col
        return ~col  # bitwise not reverses order for ints
    return col


@functools.partial(jax.jit, static_argnames=("descs", "limit"))
@_scoped("otb.sort")
def sort_rows(key_cols: tuple, valid, payload_cols: tuple,
              descs: tuple, limit: int | None = None):
    """Lexicographic multi-key sort; invalid rows last; optional limit slice.
    TEXT keys must be pre-mapped to order-preserving ranks by the operator
    (dictionary codes are not ordered)."""
    keys = [_order_key(k, d) for k, d in zip(key_cols, descs)]
    # only the flag, the keys and a row index ride the (stable) variadic
    # sort; payloads are gathered through the permutation afterwards.
    # The TPU compiler's time for a sort grows with every operand — an
    # 8-operand top-10 at 65536 rows was most of Q3's 251 s compile
    # (CHANGES.md, PR 22) — and a LIMIT gathers only `limit` rows.
    iota = jnp.arange(valid.shape[0], dtype=jnp.int32)
    perm = jax.lax.sort([~valid] + keys + [iota],
                        num_keys=1 + len(keys))[-1]
    if limit is not None:
        perm = perm[:limit]
    return tuple(p[perm] for p in payload_cols), valid[perm]


# ---------------------------------------------------------------------------
# redistribution hashing (feeds all_to_all bucketing — the FN-plane analog)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_buckets",))
@_scoped("otb.exchange")
def bucket_ids(key_cols: tuple, num_buckets: int):
    from ..utils.hashing import hash_columns_jax
    h = hash_columns_jax(list(key_cols))
    return (h % jnp.uint64(num_buckets)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("ndn", "bucket"))
@_scoped("otb.exchange")
def bucket_rows(dest, ndn: int, bucket: int):
    """The exchange's pack, seen from where the rows arrive: (src, keep,
    overflow) for `ndn` buckets of `bucket` slots each.  `dest[n]` is a
    row's destination, int32, `ndn` for a row that goes nowhere (dead).
    Slot s of destination d takes the s-th row bound for d IN SOURCE
    ORDER, `src[d * bucket + s]` (int32); `keep` says which slots a row
    fills (`s < min(count_d, bucket)`), `overflow` how many rows found
    no slot (`sum(max(count_d - bucket, 0))`, int64: the size ladder
    compares it with 0).  An unfilled slot points at some row in range.

    A gather formulation: every DESTINATION slot finds its row, by
    `_lane_search` of the running count of `dest == d` (int32 words, rows
    of 128 pivots, `ndn` searches of `bucket` lanes), where every SOURCE
    row used to compute its slot and be scattered there, a column at a
    time (62-166 ms alone for 4 to 8 arrays of 393,216 rows on a v5e,
    this pack 5.5-6.6 with `take_rows`: PERF.md section 6, PR 41).  No
    scatter, no sort, no `while`, no 64-bit gather."""
    n = dest.shape[0]
    _check_word("bucket_rows", ndn * bucket, n)
    if not n:
        return (jnp.zeros(ndn * bucket, jnp.int32),
                jnp.zeros(ndn * bucket, bool), jnp.int64(0))
    slot = jnp.arange(bucket, dtype=jnp.int32)
    src, keep, overflow = [], [], jnp.int64(0)
    for d in range(ndn):
        csum = jnp.cumsum(dest == d, dtype=jnp.int32)
        # a level's gathered rows are [lanes, _ROW] words in HBM
        src.append(_in_passes(_lane_search(csum, bucket), (slot,),
                              _MAX_LANES))
        count = csum[-1]
        keep.append(slot < count)
        overflow += jnp.maximum(count - bucket, 0)
    return jnp.concatenate(src), jnp.concatenate(keep), overflow


#: the signed integer of a float's width: its bits, by bitcast
_BITS = {2: jnp.int16, 4: jnp.int32, 8: jnp.int64}


def _words_of(a):
    """The bits of `a[n, ...]`, any fixed-width dtype, as [n] int32
    planes: a 64-bit element is two (high, low), a narrower one widens.
    Floats by `bitcast_convert_type`, never arithmetic: -0.0 and a NaN's
    payload come back as they went (`_of_words`)."""
    a = a.reshape(a.shape[0], -1)
    if jnp.issubdtype(a.dtype, jnp.floating):
        a = jax.lax.bitcast_convert_type(a, _BITS[a.dtype.itemsize])
    if a.dtype.itemsize == 8:
        u = a.astype(jnp.uint64)
        halves = ((u >> 32).astype(jnp.uint32),
                  (u & 0xFFFFFFFF).astype(jnp.uint32))
        a = jnp.stack([jax.lax.bitcast_convert_type(h, jnp.int32)
                       for h in halves], axis=2).reshape(a.shape[0], -1)
    elif a.dtype == jnp.uint32:
        a = jax.lax.bitcast_convert_type(a, jnp.int32)
    else:
        a = a.astype(jnp.int32)
    return [a[:, i] for i in range(a.shape[1])]


def _of_words(planes, like):
    """`_words_of` undone: the [m] int32 planes of one array back in the
    dtype and trailing shape of `like`."""
    dt = like.dtype
    bits = _BITS[dt.itemsize] if jnp.issubdtype(dt, jnp.floating) else dt
    if dt.itemsize == 8:
        high, low = (jax.lax.bitcast_convert_type(
            jnp.stack(half, axis=1), jnp.uint32).astype(jnp.uint64)
            for half in (planes[0::2], planes[1::2]))
        w = ((high << 32) | low).astype(bits)
    elif bits == jnp.uint32:
        w = jax.lax.bitcast_convert_type(jnp.stack(planes, axis=1),
                                         jnp.uint32)
    else:
        w = jnp.stack(planes, axis=1).astype(bits)
    if bits != dt:
        w = jax.lax.bitcast_convert_type(w, dt)
    return w.reshape(w.shape[0], *like.shape[1:])


def _take_rows(arrays: tuple, idx, keep):
    if not arrays:
        return ()
    if not arrays[0].shape[0]:
        return tuple(jnp.zeros((idx.shape[0], *a.shape[1:]), a.dtype)
                     for a in arrays)
    words = [_words_of(a) for a in arrays]
    flat = [p for planes in words for p in planes]

    def rows(i, k):
        out = []
        for at in range(0, len(flat), _ROW):
            part = flat[at:at + _ROW]
            matrix = jnp.pad(jnp.stack(part, axis=1),
                             ((0, 0), (0, _ROW - len(part))))
            got = jnp.where(k[:, None], matrix[i], 0)
            out.extend(got[:, w] for w in range(len(part)))
        return tuple(out)
    got = iter(_in_passes(rows, (idx, keep), _MAX_LANES))
    return tuple(_of_words([next(got) for _ in planes], a)
                 for planes, a in zip(words, arrays))


_take_live_rows = custom_dce(_take_rows)


@_take_live_rows.def_dce
def _take_rows_read(used: tuple, arrays: tuple, idx, keep):
    read = iter(_take_rows(tuple(a for a, u in zip(arrays, used) if u),
                           idx, keep))
    return tuple(next(read) if u else None for u in used)


@jax.jit
@_scoped("otb.exchange")
def take_rows(arrays: tuple, idx, keep):
    """`where(keep, a[idx], 0)` for every array of `arrays` (columns and
    null masks of one batch, [n, ...] of any dtype; idx int32 in range),
    bit for bit, through ONE gather of 32-bit rows: the arrays' words
    stand side by side in a [n, _ROW] int32 matrix (`_words_of`; a second
    matrix past _ROW words), `matrix[idx]` fetches a lane's whole row,
    and each array is cut out of the gathered rows and put back together
    in its own dtype.  A scalar gather an array costs the chip three
    times a row gather a lane, six from an int64 table (PERF.md section
    6, PR 30), and an exchange moves 6 to 12 words a row.  At most
    _MAX_LANES lanes a pass, as join_expand.  The body, `_take_rows`, is
    also how grouped_agg_sort reads its rows in sorted order (idx its
    perm, keep the valid rows' prefix: 2 to 10 words a row).

    An array whose taken rows nothing reads stays out of the matrix, and
    what made it is dead code with it, as if it had a gather of its own:
    jax removes an equation none of whose results is read, and one
    matrix of every array would keep them all (`custom_dce`: the rule
    builds the matrix of the arrays that ARE read).  An exchange hands
    on its batch's every column, the plan's consumers read a few."""
    return _take_live_rows(arrays, idx, keep)
