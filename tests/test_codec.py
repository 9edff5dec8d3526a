"""otbcodec: compressed device residency (storage/codec.py).

Five layers:
- descriptor choice + round-trips: pack / FOR / dict pick the narrowest
  paying family, code 0 is the padding sentinel (decodes to exactly 0,
  so visibility masks survive), wall-clock-scale FOR references floor
  at 32 bits, and the OTB_CODEC=0 escape hatch stages raw;
- tail appends encode under the EXISTING descriptor (dictionaries
  extend append-only within capacity) and a misfit promotes exactly
  the outgrown column — a key-visible, bounded recompile, like
  join-ladder growth;
- bit-identity: the same workload with OTB_CODEC on and off returns
  identical rows on both the fused and mesh tiers — encoding is a
  residency optimisation, never a semantics change;
- zero warm recompiles: changed literals over encoded tables reuse the
  compiled program, and the OTB_TRACECHECK census witnesses only
  quantized codec-class tokens (the retrace-sanitizer extension);
- HotStandby replicas: append-driven union-dict growth keeps resident
  codes valid (append-only LUT, same class token) and routed replica
  reads stay bit-identical to the primary.
"""

import types

import numpy as np
import pytest

from opentenbase_tpu.analysis.cardinality import check_census
from opentenbase_tpu.exec import plancache
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.obs.metrics import REGISTRY
from opentenbase_tpu.ops import kernels as K
from opentenbase_tpu.storage import codec
from opentenbase_tpu.storage.bufferpool import POOL


@pytest.fixture(autouse=True)
def _fresh():
    POOL.clear()
    codec.reset_state()
    yield
    POOL.clear()
    codec.reset_state()


def _counter_sum(prefix: str) -> float:
    """Sum every sample of a (labeled) counter family."""
    total = 0.0
    for line in REGISTRY.text().splitlines():
        if line.startswith(prefix) and not line.startswith("#"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _decode(codes, aux, family):
    return np.asarray(K.decode_column(codes, aux, family))


class TestDescriptorChoice:
    def test_pack_roundtrip(self):
        h = np.arange(0, 200, dtype=np.int64)
        codes, enc, aux = codec.encode_staged("cd_p", "v", h)
        assert (enc.family, enc.width) == ("pack", 8)
        assert codes.dtype == np.uint8
        assert aux.dtype == np.int64
        np.testing.assert_array_equal(_decode(codes, aux, "pack"), h)

    def test_for_roundtrip_and_padding_sentinel(self):
        h = np.arange(100_000, 100_100, dtype=np.int64)
        codes, enc, aux = codec.encode_staged("cd_f", "v", h)
        assert (enc.family, enc.width) == ("for", 8)
        assert int(codes.min()) >= 1, "code 0 is reserved for padding"
        np.testing.assert_array_equal(_decode(codes, aux, "for"), h)
        padded = np.concatenate([codes, np.zeros(4, np.uint8)])
        dec = _decode(padded, aux, "for")
        np.testing.assert_array_equal(dec[-4:], np.zeros(4, np.int64))

    def test_cmp_on_codes_matches_decoded_compare(self):
        h = np.arange(100_000, 100_100, dtype=np.int64)
        codes, enc, aux = codec.encode_staged("cd_c", "v", h)
        for op, fn in (("<", np.less), ("<=", np.less_equal),
                       (">", np.greater), (">=", np.greater_equal),
                       ("=", np.equal), ("<>", np.not_equal)):
            got = np.asarray(K.cmp_on_codes(codes, aux, enc.family,
                                            op, 100_050))
            np.testing.assert_array_equal(got, fn(h, 100_050), op)

    def test_dict_roundtrip(self):
        vals = np.asarray([10 ** 12 * k for k in (1, 3, 5, 7, 9, 11, 13)],
                          dtype=np.int64)
        h = vals[np.arange(500) % len(vals)]
        codes, enc, aux = codec.encode_staged("cd_d", "v", h)
        assert (enc.family, enc.width) == ("dict", 8)
        assert enc.cap >= 16 and enc.cap & (enc.cap - 1) == 0
        assert aux.shape == (enc.cap,)
        assert aux[0] == 0, "LUT slot 0 is the padding sentinel"
        np.testing.assert_array_equal(_decode(codes, aux, "dict"), h)

    def test_wallclock_reference_floors_at_32_bits(self):
        # MVCC-timestamp-scale values drift forward forever: a width
        # proven on today's span would promote on every append batch
        h = np.arange(1 << 50, (1 << 50) + 5000, dtype=np.int64)
        codes, enc, _aux = codec.encode_staged("cd_w", "ts", h)
        assert (enc.family, enc.width) == ("for", 32)
        assert codes.dtype == np.uint32

    def test_escape_hatch_stages_raw(self, monkeypatch):
        monkeypatch.setenv("OTB_CODEC", "0")
        h = np.arange(0, 50, dtype=np.int64)
        assert codec.encode_staged("cd_off", "v", h) is None
        assert codec.codec_class(None) == "raw"

    def test_eligibility(self):
        assert codec.eligible("v", np.arange(4, dtype=np.int64))
        assert not codec.eligible("v", np.zeros(4, np.bool_))
        assert not codec.eligible("v", np.zeros(4, np.float64))
        assert not codec.eligible("v", np.zeros((2, 2), np.int64))
        assert not codec.eligible("v", np.zeros(4, np.uint8))
        assert not codec.eligible("__enc.pack.v",
                                  np.arange(4, dtype=np.int64))


class TestTailEncoding:
    def test_tail_fits_then_misfit_promotes(self):
        h = np.arange(0, 200, dtype=np.int64)
        _codes, enc, _aux = codec.encode_staged("cd_t", "v", h)
        assert codec.codec_class(enc) == "pack8"
        tail = codec.encode_tail("cd_t", "v", enc,
                                 np.asarray([5, 6], np.int64))
        assert tail is not None and tail.dtype == np.uint8
        assert codec.encode_tail("cd_t", "v", enc,
                                 np.asarray([70_000], np.int64)) is None
        grown = np.concatenate([h, np.asarray([70_000], np.int64)])
        codes2, enc2, aux2 = codec.encode_staged("cd_t", "v", grown)
        assert codec.codec_class(enc2) != "pack8"
        np.testing.assert_array_equal(
            _decode(codes2, aux2, enc2.family), grown)

    def test_dict_tail_extends_lut_in_place(self):
        vals = [10 ** 12, 3 * 10 ** 12, 5 * 10 ** 12]
        h = np.asarray(vals * 50, dtype=np.int64)
        _codes, enc, _aux = codec.encode_staged("cd_dt", "v", h)
        assert enc.family == "dict"
        cls0 = codec.codec_class(enc)
        tail = codec.encode_tail("cd_dt", "v", enc,
                                 np.asarray([7 * 10 ** 12], np.int64))
        assert tail is not None, "within-capacity growth is a tail fit"
        aux = codec.aux_host("cd_dt", "v", enc)
        assert aux is not None and 7 * 10 ** 12 in aux
        # append-only growth: same capacity class, old codes untouched
        assert [(t, c, k) for t, c, k in codec.ladder_snapshot()
                if (t, c) == ("cd_dt", "v")] == [("cd_dt", "v", cls0)]

    def test_window_encoding_is_validate_only(self):
        store = types.SimpleNamespace(td=types.SimpleNamespace(name="cd_m"))
        h = np.arange(1000, 1200, dtype=np.int64)
        encs = codec.ensure_classes(store, {"v": h})
        assert codec.codec_class(encs["v"]) == "for8"
        assert codec.codec_classes(store) == (("v", "for8"),)
        win = codec.encode_window("cd_m", "v", h[50:100])
        assert win is not None
        codes, enc, aux = win
        np.testing.assert_array_equal(_decode(codes, aux, enc.family),
                                      h[50:100])
        # an out-of-descriptor window NEVER re-chooses mid-stream: it
        # stages raw so every chunk provably shares one program class
        assert codec.encode_window(
            "cd_m", "v", np.asarray([10 ** 9], np.int64)) is None

    def test_ladder_persists_across_reset(self, tmp_path, monkeypatch):
        monkeypatch.setenv("OTB_CODEC_STATE",
                           str(tmp_path / "codec.json"))
        h = np.arange(500, 700, dtype=np.int64)
        _c, enc, _a = codec.encode_staged("cd_s", "v", h)
        snap = codec.ladder_snapshot()
        assert (tmp_path / "codec.json").exists()
        codec.reset_state()
        # a fresh process (reset) reloads the persisted descriptor and
        # encodes identically — the join-ladder persistence idiom
        _c2, enc2, _a2 = codec.encode_staged("cd_s", "v", h)
        assert enc2 == enc
        assert codec.ladder_snapshot() == snap


def _mk_mixed(node):
    s = Session(node)
    s.execute("create table cdm (k bigint, grp int, ts bigint, "
              "price decimal(10,2), d date, nm varchar(8))")
    rows = []
    for i in range(240):
        rows.append(
            f"({i}, {i % 5}, {10 ** 15 + i * 1000}, "
            f"{(i % 37) + 0.25:.2f}, "
            f"date '1995-{1 + i % 12:02d}-{1 + i % 28:02d}', "
            f"'g{i % 4}')")
    s.execute("insert into cdm values " + ", ".join(rows))
    return s


_MIXED_QS = (
    "select grp, sum(price) as sp, count(*) as c from cdm "
    "where k < 120 group by grp order by grp",
    "select grp, count(*) as c from cdm where price >= 5.00 "
    "and price < 30.00 group by grp order by grp",
    f"select count(*) from cdm where ts >= {10 ** 15 + 120_000}",
    "select nm, sum(k) as sk from cdm where d < date '1995-07-01' "
    "group by nm order by nm",
)


class TestBitIdentity:
    def test_fused_encoded_vs_raw(self, monkeypatch):
        node = LocalNode()
        s = _mk_mixed(node)
        got = [s.query(q) for q in _MIXED_QS]
        classes = {(t, c): cls for t, c, cls in codec.ladder_snapshot()
                   if t == "cdm"}
        assert classes, "the mixed table must have staged encoded"
        assert any(cls != "raw" for cls in classes.values())
        tot = POOL.totals()
        assert tot["bytes_logical"] > tot["bytes_live"], \
            "encoded residency must be smaller than logical bytes"

        monkeypatch.setenv("OTB_CODEC", "0")
        POOL.clear()
        codec.reset_state()
        ref = [s.query(q) for q in _MIXED_QS]
        assert got == ref, "OTB_CODEC must be bit-invisible"
        assert POOL.totals()["bytes_logical"] \
            == POOL.totals()["bytes_live"]

    def test_mesh_encoded_vs_raw(self, monkeypatch):
        from opentenbase_tpu.exec.dist_session import ClusterSession
        from opentenbase_tpu.parallel.cluster import Cluster
        cs = ClusterSession(Cluster(n_datanodes=4))
        cs.execute("create table cdk (k bigint, v bigint) "
                   "distribute by shard(k)")
        cs.execute("insert into cdk values " + ", ".join(
            f"({i}, {10 ** 12 + i % 6})" for i in range(80)))
        q = "select sum(v) from cdk where k <= {}"
        got = cs.query(q.format(40))
        assert cs.last_query_stats()["tier"] == "mesh"
        assert any(t == "cdk" and cls != "raw"
                   for t, _c, cls in codec.ladder_snapshot())
        c0, h0 = plancache.MESH.compiles, plancache.MESH.hits
        got2 = cs.query(q.format(60))
        assert plancache.MESH.compiles == c0, \
            "a literal change must not recompile the encoded mesh program"
        assert plancache.MESH.hits > h0

        monkeypatch.setenv("OTB_CODEC", "0")
        POOL.clear()
        codec.reset_state()
        assert [cs.query(q.format(n)) for n in (40, 60)] == [got, got2]


class TestWarmRepeatCensus:
    def test_changed_literals_compile_zero_new_programs(self, monkeypatch):
        """The satellite retrace-sanitizer extension: a warm repeat
        over ENCODED tables with changed literals compiles zero new
        programs, and every class the census witnessed — including the
        codec:<table>.<col> dimensions — passes check_census."""
        monkeypatch.setenv("OTB_TRACECHECK", "1")
        node = LocalNode()
        s = _mk_mixed(node)
        plancache.reset_census()
        warm = ("select grp, sum(price) as sp from cdm where k < {} "
                "group by grp order by grp")
        ref = s.query(warm.format(100))
        assert ref
        c0 = plancache.FUSED.compiles
        for lit in (40, 77, 150, 239):
            assert s.query(warm.format(lit))
        assert plancache.FUSED.compiles == c0, \
            "literal drift over encoded columns must stay warm"
        ents = plancache.census()
        assert ents, "the armed sanitizer must have witnessed the put"
        assert check_census({"entries": ents}) == []
        dims = [d for e in ents for d, _v in e.get("classes", [])]
        assert any(str(d).startswith("codec:cdm.") for d in dims), \
            "the census must witness the staged codec classes"

    def test_census_rejects_raw_descriptor_classes(self):
        bad = {"entries": [
            {"tier": "fused", "frag": "f", "key": "k1", "puts": 1,
             "classes": [["codec:t.v", "dict8/17"]]},
            {"tier": "fused", "frag": "f", "key": "k2", "puts": 1,
             "classes": [["codec:t.v", (1786088887683204,)]]},
            {"tier": "fused", "frag": "f", "key": "k3", "puts": 1,
             "classes": [["codec:t.v", "for16"], ["batch", 1024]]},
        ]}
        msgs = check_census(bad)
        assert len(msgs) == 2
        assert all("codec" in m for m in msgs)


class TestTailPromotionThroughSession:
    def test_append_promotes_only_the_outgrown_column(self):
        node = LocalNode()
        s = Session(node)
        s.execute("create table cdp (k bigint, v bigint)")
        s.execute("insert into cdp values " + ", ".join(
            f"({i}, {i % 100})" for i in range(200)))
        q = "select sum(v) from cdp where k >= 0"
        assert s.query(q) == [(sum(i % 100 for i in range(200)),)]
        classes = dict((c, cls) for t, c, cls in codec.ladder_snapshot()
                       if t == "cdp")
        assert classes.get("v") == "pack8"
        k_cls = classes.get("k")

        tail0 = POOL.totals()["tail_rows"]
        # k=200 still fits pack8; v=70000 outgrows it -> v alone promotes
        s.execute("insert into cdp values (200, 70000)")
        assert s.query(q) == \
            [(sum(i % 100 for i in range(200)) + 70000,)]
        assert s.query("select v from cdp where k = 200") == [(70000,)]
        classes2 = dict((c, cls) for t, c, cls in codec.ladder_snapshot()
                        if t == "cdp")
        assert classes2.get("v") != "pack8", "v must have promoted"
        assert classes2.get("k") == k_cls, "k keeps its descriptor"
        assert POOL.totals()["tail_rows"] > tail0, \
            "non-promoted columns must still ride the tail path"


class TestStandbyDictGrowth:
    _SPREAD = [(j + 1) * 10 ** 12 + 7 for j in range(4)]

    def _cluster(self, tmp_path, n=2):
        from opentenbase_tpu.exec.dist_session import ClusterSession
        from opentenbase_tpu.parallel.cluster import Cluster
        cl = Cluster(n_datanodes=n, datadir=str(tmp_path / "cl"))
        s = ClusterSession(cl)
        s.execute("create table cdg (k bigint primary key, v bigint)"
                  " distribute by shard(k)")
        s.execute("insert into cdg values " + ", ".join(
            f"({i}, {self._SPREAD[i % 4]})" for i in range(60)))
        return s

    def _attach_hot(self, cl, tmp_path):
        from opentenbase_tpu.storage.replication import (DnStandbyServer,
                                                         HotStandby)
        servers = []
        for i, dn in enumerate(cl.datanodes):
            sb = HotStandby(str(tmp_path / f"standby{i}"), index=i)
            srv = DnStandbyServer(sb).start()
            dn.attach_standby(srv.host, srv.port)
            cl.register_read_replica(i, srv.host, srv.port, sb.datadir)
            servers.append(srv)
        return servers

    def test_union_dict_growth_keeps_routed_reads_identical(
            self, tmp_path):
        s = self._cluster(tmp_path)
        servers = self._attach_hot(s.cluster, tmp_path)
        try:
            # stage the dict-encoded column device-side
            assert s.query("select sum(v) from cdg") == \
                [(sum(self._SPREAD[i % 4] for i in range(60)),)]
            cls0 = [cls for t, c, cls in codec.ladder_snapshot()
                    if (t, c) == ("cdg", "v")]
            assert cls0 and cls0[0].startswith("dict8/")

            # append rows carrying NEW dictionary values through the
            # standby apply path (union-dict growth within capacity)
            new_vals = [5 * 10 ** 12 + 7, 6 * 10 ** 12 + 7]
            s.execute("insert into cdg values " + ", ".join(
                f"({60 + i}, {v})" for i, v in enumerate(new_vals)))
            total = sum(self._SPREAD[i % 4] for i in range(60)) \
                + sum(new_vals)
            assert s.query("select sum(v) from cdg") == [(total,)]
            # append-only LUT growth: same class token, resident codes
            # staged before the append stayed valid
            assert [cls for t, c, cls in codec.ladder_snapshot()
                    if (t, c) == ("cdg", "v")] == cls0

            keys = (3, 17, 42, 60, 61)
            ref = [s.query(f"select v from cdg where k = {k}")
                   for k in keys]
            s.execute("set replica_reads = on")
            before = _counter_sum("otb_replica_reads_total")
            got = [s.query(f"select v from cdg where k = {k}")
                   for k in keys]
            assert got == ref
            assert got[3] == [(new_vals[0],)]
            assert got[4] == [(new_vals[1],)]
            assert _counter_sum("otb_replica_reads_total") \
                >= before + len(keys)
        finally:
            for srv in servers:
                srv.stop()
