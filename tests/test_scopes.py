"""Names on the device: every kernel of ops/kernels.py, lowered at a small
shape, carries its `otb.` scope in the lowered text (the op metadata a
device trace shows, whatever number XLA gives the op), the program steps
of the fused and mesh tiers carry theirs, and a mesh program over four
virtual devices has `otb.exchange` around its all-to-all.  The vocabulary
is flat and closed: benchmarks/lib/xplane.py reduces traces by it."""

import re

import jax
import jax.numpy as jnp
import pytest

from opentenbase_tpu.exec import fused, mesh_exec
from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.ops import kernels as K
from opentenbase_tpu.parallel.cluster import Cluster

VOCABULARY = {"otb.scan", "otb.agg", "otb.join_build", "otb.join_probe",
              "otb.join_expand", "otb.join_residual", "otb.sort",
              "otb.exchange", "otb.finalize"}

N = 64
I = jnp.arange(N, dtype=jnp.int64)
B = I % 2 == 0
AUX = jnp.zeros(1, jnp.int64)


def _scopes(text):
    return set(re.findall(r"otb\.[a-z_]+", text))


def _lowered(fn, *args, **kw):
    if not hasattr(fn, "lower"):        # a plain helper, traced by callers
        fn = jax.jit(fn, static_argnames=tuple(kw))
    return fn.lower(*args, **kw).as_text(debug_info=True)


KERNELS = [
    ("visibility_mask", "otb.scan",
     lambda: _lowered(K.visibility_mask, I, I, I, I, I[0], I[0], I[0])),
    ("decode_column", "otb.scan",
     lambda: _lowered(K.decode_column, I, AUX, family="for")),
    ("cmp_on_codes", "otb.scan",
     lambda: _lowered(K.cmp_on_codes, I, AUX, family="pack", op="<",
                      lit=3)),
    ("live_positions", "otb.finalize",
     lambda: _lowered(K.live_positions, B, out_size=N)),
    ("grouped_agg_dense", "otb.agg",
     lambda: _lowered(K.grouped_agg_dense, I % 4, B, (I,), num_groups=4,
                      agg_kinds=("sum",))),
    ("grouped_agg_sort", "otb.agg",
     lambda: _lowered(K.grouped_agg_sort, (I % 4,), B, (I,), max_groups=N,
                      agg_kinds=("sum",))),
    ("join_build", "otb.join_build", lambda: _lowered(K.join_build, I, B)),
    ("join_build_minor", "otb.join_build",
     lambda: _lowered(K.join_build_minor, I, B, I % 4)),
    ("join_probe_counts", "otb.join_probe",
     lambda: _lowered(K.join_probe_counts, I, I, B)),
    ("lane_rows", "otb.join_expand",
     lambda: _lowered(K.lane_rows, I, out_size=N)),
    ("join_expand", "otb.join_expand",
     lambda: _lowered(K.join_expand, I, I % 2, I, out_size=N)),
    ("compose_index", "otb.join_expand",
     lambda: _lowered(K.compose_index, I, I)),
    ("semi_mask", "otb.join_probe", lambda: _lowered(K.semi_mask, I)),
    ("anti_mask", "otb.join_probe", lambda: _lowered(K.anti_mask, I, B)),
    ("range_differs", "otb.join_residual",
     lambda: _lowered(K.range_differs, I.astype(jnp.int32),
                      (I % 2).astype(jnp.int32), I.astype(jnp.int32), I[0],
                      I, B)),
    ("sort_rows", "otb.sort",
     lambda: _lowered(K.sort_rows, (I,), B, (I,), descs=(True,), limit=8)),
    ("bucket_ids", "otb.exchange",
     lambda: _lowered(K.bucket_ids, (I,), num_buckets=4)),
    ("bucket_rows", "otb.exchange",
     lambda: _lowered(K.bucket_rows, (I % 3).astype(jnp.int32), ndn=2,
                      bucket=N)),
    ("take_rows", "otb.exchange",
     lambda: _lowered(K.take_rows, (I, B), I.astype(jnp.int32), B)),
]


@pytest.mark.parametrize("name, scope, lower", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_kernel_carries_its_scope(name, scope, lower):
    text = lower()
    assert _scopes(text) == {scope}, name
    # the scope is metadata: the kernel keeps its own name as a program
    assert f"jit({name})/{scope}/" in text or f"/{scope}/" in text


def test_every_kernel_of_the_library_is_listed():
    public = {n for n, f in vars(K).items()
              if callable(f) and not n.startswith("_")
              and getattr(f, "__module__", "") == K.__name__}
    assert public == {k[0] for k in KERNELS}
    assert {k[1] for k in KERNELS} <= VOCABULARY


def test_a_scope_is_not_part_of_the_program():
    """Metadata only: the lowered program is the same text with and
    without it once locations are left out, so the persistent cache's key
    (computed with debug info stripped) does not see a scope."""
    def body(x):
        return jnp.cumsum(x * 3)

    def scoped(x):
        with jax.named_scope("otb.scan"):
            return body(x)

    scoped.__name__ = body.__name__
    plain_text = jax.jit(body).lower(I).as_text()
    assert jax.jit(scoped).lower(I).as_text() == plain_text
    assert "otb.scan" in jax.jit(scoped).lower(I).as_text(debug_info=True)


@pytest.fixture()
def programs(monkeypatch):
    """(tag, lowered text) of every fused or mesh program a test runs."""
    texts = []

    def capture(tag, fn, args):
        texts.append((tag, fn.lower(*args).as_text(debug_info=True)))

    monkeypatch.setattr(mesh_exec, "EXPORT_HOOK", capture)
    monkeypatch.setattr(fused, "EXPORT_HOOK", capture)
    return texts


def _join_tables(s, n=96):
    s.execute("create table t (k bigint primary key, g bigint, "
              "price decimal(10,2)) distribute by shard(k)")
    s.execute("create table u (uk bigint primary key, tk bigint) "
              "distribute by shard(uk)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i % 5}, {i}.25)" for i in range(n)))
    s.execute("insert into u values " + ", ".join(
        f"({100 + i}, {(i * 7) % n})" for i in range(n)))


def test_mesh_program_on_four_devices_names_its_exchange(programs,
                                                         monkeypatch):
    monkeypatch.setenv("OTB_FUSE_JOIN_MIN_ROWS", "0")
    s = ClusterSession(Cluster(n_datanodes=4))
    _join_tables(s)
    # u is sharded on uk and joins on tk: its rows are redistributed
    rows = s.query("select g, sum(price) from t, u where k = tk "
                   "group by g order by g")
    assert len(rows) == 5 and s.last_query_stats()["tier"] == "mesh"
    mesh = [t for tag, t in programs if tag == "mesh"]
    assert mesh
    text = mesh[0]
    assert "jit_otb_mesh" in text or "otb_mesh" in text
    assert "all_to_all" in text
    # every all_to_all of the program sits under otb.exchange
    a2a_locs = set(re.findall(r'all_to_all.*?loc\((#loc\d+)\)', text))
    assert a2a_locs
    for ref in a2a_locs:
        line = re.search(rf'^{re.escape(ref)} = loc\((.*)\)$', text,
                         re.M).group(1)
        assert "otb.exchange" in line, line
    # and the steps around it carry theirs
    assert {"otb.scan", "otb.exchange", "otb.agg", "otb.join_build",
            "otb.join_probe", "otb.join_expand"} <= _scopes(text)
    assert _scopes(text) <= VOCABULARY


@pytest.mark.parametrize("ndn", [1, 4])
def test_pack_lanes_counted_where_rows_move(ndn, monkeypatch):
    """`pack_lanes`: the destination slots of the mesh program's exchange
    packs (`ndn * bucket` a redistribute, a bucket 64 slots at least),
    fixed when the program is traced: a key of
    `last_query_stats()`, a column of `otb_stat_query`, a field of
    EXPLAIN ANALYZE's `Shape:` line.  On one DataNode nothing moves and
    it reads 0."""
    monkeypatch.setenv("OTB_FUSE_JOIN_MIN_ROWS", "0")
    s = ClusterSession(Cluster(n_datanodes=ndn))
    _join_tables(s)
    sql = ("select g, sum(price) from t, u where k = tk "
           "group by g order by g")
    seen = []
    for _ in range(2):
        assert len(s.query(sql)) == 5
        st = s.last_query_stats()
        seen.append((st["exchanges"], st["exchange_bytes"],
                     st["pack_lanes"]))
    assert seen[0] == seen[1]
    exchanges, _sent, lanes = seen[0]
    if ndn == 1:
        assert (exchanges, lanes) == (0, 0)
    else:
        assert st["tier"] == "mesh" and exchanges >= 1
        assert lanes >= exchanges * ndn * 64 and lanes % (ndn * 64) == 0
    (row,) = s.query("select pack_lanes from otb_stat_query "
                     f"where qid = {st['qid']}")
    assert row == (lanes,)
    text = "\n".join(r[0] for r in s.query("explain analyze " + sql))
    line = next(ln for ln in text.splitlines() if ln.startswith("Shape: "))
    assert line.split()[-1] == f"pack_lanes={lanes}", line


def test_fused_program_names_its_steps(programs, monkeypatch):
    monkeypatch.setenv("OTB_FUSE_JOIN_MIN_ROWS", "0")
    from opentenbase_tpu.exec.session import LocalNode, Session
    s = Session(LocalNode())
    s.execute("create table t (k bigint, g bigint, price decimal(10,2))")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i % 5}, {i}.25)" for i in range(96)))
    assert len(s.query("select g, sum(price) from t where k > 3 "
                       "group by g")) == 5
    texts = [t for tag, t in programs if tag == "fused"]
    assert texts, programs
    assert "otb_fragment" in texts[0]
    assert {"otb.scan", "otb.agg"} <= _scopes(texts[0]) <= VOCABULARY
