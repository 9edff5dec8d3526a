"""Main-path kernels compiled by the TPU's own compiler for a DESCRIBED
v5e:2x2 (no chip attached): what the chip's compiler would refuse is
refused here, at no chip time.  Nothing runs, so these say nothing about
results or times.  Full SF1 size-class compile times are in CHANGES.md
(PR 22); the 64-bit sort family needs minutes there, so the sort-based
kernels are kept at a small class here.

The topology is described inside a module-scoped fixture — never at
import — and every compile happens in this process, in this one file
(only one process at a time may load the TPU's library).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from opentenbase_tpu.ops import kernels as K
from opentenbase_tpu.parallel import mesh as M
from opentenbase_tpu.utils import dtypes

HBM_BYTES = 16 << 30          # one v5e chip
BIG = 1 << 23                 # lineitem at SF1, padded to its size class
SMALL = 1 << 12               # sort-family kernels: seconds, not minutes

I64, I32, BOOL = jnp.int64, jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def tpu_mode(topo):
    """dtype mode forced to 'tpu' (the env override is read once at
    import) and the persistent compile cache off around these compiles:
    an entry written for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    mp = pytest.MonkeyPatch()
    mp.setattr(dtypes, "_mode", "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    mp.undo()
    jax.clear_caches()          # no tpu-mode trace outlives this module


@pytest.fixture(scope="module")
def one_chip(topo, tpu_mode):
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,) if n else (), dtype, sharding=chip)
    return shape


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one chip"
    text = compiled.as_text()
    assert "f64[" not in text, "float64 reached the chip's program"
    return text


def test_fused_q1_step_at_sf1(one_chip):
    """The flagship fused TPC-H Q1 fragment (__graft_entry__.entry) at
    lineitem's SF1 size class."""
    import __graft_entry__ as G
    step, _ = G.entry()
    s = one_chip
    cols = {"qty": s(BIG, I64), "price": s(BIG, I64), "disc": s(BIG, I64),
            "tax": s(BIG, I64), "ship": s(BIG, I32), "rf": s(BIG, I32),
            "ls": s(BIG, I32), "orderkey": s(BIG, I64)}
    _compile(jax.jit(step), cols)


def test_visibility_mask_at_sf1(one_chip):
    s = one_chip
    _compile(jax.jit(K.visibility_mask), s(BIG, I64), s(BIG, I64),
             s(BIG, I64), s(BIG, I64), s(0, I64), s(0, I64), s(0, I64))


def test_finalize_live_rows_at_orders_class(one_chip):
    """A point read's way out at SF1: `orders` pads to 1,572,864 rows;
    the selection and the four columns' gathers are one program with
    one uint8 result, and the chip's compiler puts no scatter and no
    sort in it."""
    from opentenbase_tpu.exec import executor as X
    s, orders = one_chip, 1572864
    cols = {0: s(orders, I64), 1: s(orders, I64), 2: s(orders, I64),
            3: s(orders, I32)}
    text = _compile(jax.jit(lambda v, c: X._gather_live(
        v, c, {}, {}, out_size=256)), s(orders, BOOL), cols)
    assert " scatter(" not in text and " sort(" not in text
    assert "u8[7172]" in text


def test_join_build(one_chip):
    s = one_chip
    _compile(K.join_build, s(SMALL, I64), s(SMALL, BOOL))


def test_join_build_minor(one_chip):
    """Q21's build side: (order, supplier, position) in one sorted word."""
    s = one_chip
    text = _compile(jax.jit(lambda k, v, c: K.join_build_minor(
        k, v, c, key_span=4 * SMALL, minor_span=10_000)),
        s(SMALL, I64), s(SMALL, BOOL), s(SMALL, I64))
    assert text.count(" sort(") == 1


def test_range_differs_at_lineitems_class(one_chip):
    """Sort-free, so it goes to Q21's real class: 6,291,456 probe rows
    against 6,291,456 build rows, in passes whose gathered rows stay
    under 2 GiB; no `while`, no scatter, no 64-bit gather."""
    s = one_chip
    compiled = jax.jit(K.range_differs).lower(
        s(BIG * 3 // 4, I32), s(BIG * 3 // 4, I32), s(BIG * 3 // 4, I32),
        s(0, I64), s(BIG * 3 // 4, I32), s(BIG * 3 // 4, BOOL)).compile()
    text = compiled.as_text()
    assert " while(" not in text and " scatter(" not in text
    assert "s64[" not in "".join(
        ln for ln in text.splitlines() if " gather(" in ln)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("nb, np_, span", [
    (SMALL, 4 * SMALL, None), (SMALL, 4 * SMALL, SMALL // 2),
    # the cells' joins at SF1: lineitem into orders on one chip; a
    # lineitem shard into an orders shard and an orders shard into a
    # customer shard on four; Q5's hashed (suppkey, nationkey) key
    (393216, 6291456, 6291455), (131072, 1572864, 6291455),
    (40960, 524288, 163839), (16384, 1572864, None),
    # tpch_sf1_subq's semi joins (Q4's EXISTS, Q18's IN): lineitem's
    # 6,291,456 lanes as the BUILD side, orders' rows probing it
    (6291456, 1572864, 6291455)])
def test_join_probe_counts(one_chip, nb, np_, span):
    """Sort-free, so it goes to the real classes (a second or two each).
    No `while` walks a binary search, no gather reads a 64-bit word,
    nothing scatters, and the row gathers' temporaries ([lanes, 128]
    int32) stay under 2 GiB because a class past 2**21 lanes runs in
    passes."""
    s = one_chip
    compiled = jax.jit(lambda sk, pk, pv: K.join_probe_counts(
        sk, pk, pv, key_span=span)).lower(
        s(nb, I64), s(np_, I64), s(np_, BOOL)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    text = compiled.as_text()
    assert "f64[" not in text
    gathers = [ln for ln in text.splitlines() if " gather(" in ln]
    assert bool(gathers) is (nb > K._ROOT)
    assert not [g for g in gathers if "s64[" in g or "u64[" in g]
    assert " scatter(" not in text and " while(" not in text


def test_join_expand(one_chip):
    s = one_chip
    _compile(jax.jit(lambda lo, counts, perm: K.join_expand(
        lo, counts, perm, out_size=4 * SMALL)),
        s(4 * SMALL, I32), s(4 * SMALL, I32), s(SMALL, I32))


@pytest.mark.parametrize("out_size, left_outer", [(1572864, False),
                                                  (6291456, True)])
def test_join_expand_at_sf1(one_chip, out_size, left_outer):
    """The cells' largest join (lineitem's 6,291,456 probe rows into
    1,572,864 lanes) and the class the size ladder reaches at factor 4:
    sort-free, so it may go to the real class.  No `while` walks a
    binary search, no gather reads a 64-bit table, and the row gathers'
    temporaries ([lanes, 128] int32) stay under 2 GiB because a class
    past 2**21 lanes runs in passes."""
    s = one_chip
    compiled = jax.jit(lambda lo, counts, perm, pv: K.join_expand(
        lo, counts, perm, out_size=out_size, left_outer=left_outer,
        probe_valid=pv)).lower(
        s(6291456, I64), s(6291456, I64), s(393216, I32),
        s(6291456, BOOL)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    text = compiled.as_text()
    gathers = [ln for ln in text.splitlines() if " gather(" in ln]
    assert gathers and not [g for g in gathers if "s64[" in g]
    # one pass: no loop at all; three passes: the loop over them
    assert (" while(" in text) is (out_size > K._MAX_LANES)


@pytest.mark.parametrize("key_spans, sorts, chosen_by_data", [
    (None, 3, True),            # nothing known: both sorts, a conditional
    ((6291455,), 1, False),     # the bound proves the pack: one sort
    ((1 << 62,), 2, False)])    # it cannot: the exact passes alone
def test_grouped_agg_sort(one_chip, key_spans, sorts, chosen_by_data):
    """What the host knows of the keys' range chooses the sort when the
    program is built (Q17's 200 K- and Q18's 1.5 M-group aggregates over
    lineitem: ONE sort where there were three; each costs the chip's
    compiler most of a minute at 6,291,456 lanes, so this stays small)."""
    s = one_chip
    text = _compile(jax.jit(lambda k, v, a: K.grouped_agg_sort(
        (k,), v, (a,), max_groups=SMALL, agg_kinds=("sum",),
        key_spans=key_spans)),
        s(SMALL, I64), s(SMALL, BOOL), s(SMALL, I64))
    assert text.count(" sort(") == sorts
    assert (" conditional(" in text) is chosen_by_data


@pytest.mark.parametrize("slots", [SMALL // 4, 96])
def test_grouped_agg_sort_with_fewer_slots_than_rows(one_chip, slots):
    """A traced aggregate's output class is its own (executor._agg_class:
    Q17's 229,376 slots under 6,291,456 rows): the chip's compiler takes
    a class below the rows, one that is no power of two among them, and
    the slot search runs in one pass (no `while`)."""
    s = one_chip
    text = _compile(jax.jit(lambda k, v, a: K.grouped_agg_sort(
        (k,), v, (a, a), max_groups=slots, agg_kinds=("sum", "count"),
        key_spans=(SMALL - 1,))),
        s(SMALL, I64), s(SMALL, BOOL), s(SMALL, I64))
    assert text.count(" sort(") == 1 and " while(" not in text


@pytest.mark.parametrize("key_spans", [None, (SMALL - 1,), (1 << 62,)])
def test_grouped_agg_sort_reads_its_sorted_rows_in_32_bit_rows(one_chip,
                                                               key_spans):
    """The kernel's per-ROW reads (the aggregates' inputs in sorted order,
    the exact passes' key words) are row gathers of 32-bit words through
    an int32 perm, and the sorted validity is a prefix mask: with fewer
    slots than rows, no gather at the input's lanes reads or is indexed
    by a 64-bit array (the chip has no 64-bit lanes: its compiler splits
    one into two u32 gathers) and none reads `valid`.  In all three arms;
    integer sums bring no scatter, and nothing loops."""
    import re
    s = one_chip
    fn = jax.jit(lambda k, v, a: K.grouped_agg_sort(
        (k,), v, (a, a), max_groups=SMALL // 4, agg_kinds=("sum", "sum"),
        key_spans=key_spans))
    args = (s(SMALL, I64), s(SMALL, BOOL), s(SMALL, I64))
    traced = [ln for ln in fn.lower(*args).as_text().splitlines()
              if "stablehlo.gather" in ln and f"-> tensor<{SMALL}x" in ln]
    assert traced and not [
        ln for ln in traced
        if re.search(r":\s*\([^)]*(i64|i1)>[^)]*\)\s*->", ln)], traced
    text = _compile(fn, *args)
    per_row = [ln.split(" gather(")[0] for ln in text.splitlines()
               if " gather(" in ln and re.search(rf"= \w+\[{SMALL}[,\]]", ln)]
    assert per_row and all(f"= s32[{SMALL}" in g for g in per_row), per_row
    assert " scatter(" not in text and " while(" not in text


def test_sort_rows_top10(one_chip):
    """Payloads stay out of the variadic sort (flag + 2 keys + row index
    = 4 operands): the chip's compile time grows with every operand."""
    import re
    s = one_chip
    fn = jax.jit(lambda k1, k2, v, p1, p2: K.sort_rows(
        (k1, k2), v, (p1, p2), descs=(True, False), limit=10))
    args = (s(SMALL, I64), s(SMALL, I32), s(SMALL, BOOL), s(SMALL, I64),
            s(SMALL, I64))
    _compile(fn, *args)
    sorts = re.findall(r'stablehlo\.sort"?\(([^)]*)\)',
                       fn.lower(*args).as_text())
    assert [len(ops.split(",")) for ops in sorts] == [4], sorts


def test_redistribute_is_one_all_to_all_program(topo, tpu_mode):
    """The exchange of parallel/mesh.py on a 4-chip mesh, two int64
    columns: the compiled text carries the ICI collective.  (Its pack
    step sorts, so it too is kept small; 2^23 rows compiled in 37 s.)"""
    mesh = Mesh(topo.devices, ("dn",))
    sh = NamedSharding(mesh, P("dn"))

    def s(dtype):
        return jax.ShapeDtypeStruct((16 * SMALL,), dtype, sharding=sh)
    text = _compile(M.redistribute_program(mesh, ["k", "v"], "k", SMALL),
                    s(BOOL), s(I64), s(I64))
    assert "all-to-all" in text


def test_q3_mesh_program_on_four_chips(topo, tpu_mode, tmp_path):
    """The cell tpch_sf1_mesh4's Q3 (every parameter pinned), whole: the
    program the mesh tier builds over four DataNodes, exported from a CPU
    run in the chip's dtype mode and compiled for the described 2x2 (at
    SF0.01's size classes: SF1's compile in minutes, on the chip, PERF.md
    section 6).  The compiled text carries the exchange, and no
    `conditional` under an `otb.join_*` or `otb.agg` scope: the join
    kernels' algorithm and the sorted aggregate's sort are chosen when the
    program is built, not by the shard's data."""
    from benchmarks.lib import datagen, files, mesh_check
    from benchmarks.lib import stack as stack_mod
    from benchmarks.lib.traffic import Mix, Request
    from jax import export
    from opentenbase_tpu.exec import mesh_exec

    data = datagen.generate(sf=0.01, seed=27)
    mesh_check.PROGRAMS.clear()
    mesh_check.arm()
    stack = stack_mod.Stack(4, str(tmp_path / "cluster"))
    try:
        client, session = stack.connect()
        stack_mod.load_tpch(stack, client, data, (), str(tmp_path))
        mix = Mix(files.workload("tpch_sf1_mesh4")["traffic"], 27, data)
        mix.build_pools()
        st = next(s for s in mix.statements if s.name == "q3_pinned")
        req = mix.run_request(Request(st, *mix.pools[st.name][0]), client,
                              session)
        assert req.steps[0][4] is None and session.fallbacks == []
    finally:
        mesh_exec.EXPORT_HOOK = None
        stack.stop()
    (fn, shapes), = mesh_check.PROGRAMS.values()
    mesh_check.PROGRAMS.clear()

    mesh = Mesh(topo.devices, ("dn",))

    def described(a):
        if not isinstance(a, jax.ShapeDtypeStruct):
            return a
        sharded = a.sharding is not None
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, P("dn") if sharded else P()))

    exp = export.export(fn, platforms=("tpu",))(*shapes)
    text = _compile(jax.jit(exp.call), *[described(a) for a in shapes])
    assert "all-to-all" in text
    conditionals = [ln for ln in text.splitlines() if " conditional(" in ln]
    # the names survive the export
    assert "otb.agg" in text and "otb.join_probe" in text
    # nor under `otb.agg` since PR 34: the ranges of Q3's three group keys
    # are known when the program is built, and the sorted aggregate's
    # pack-or-exact sort is chosen then
    assert not [ln for ln in conditionals
                if "otb.join_" in ln or "otb.agg" in ln]
