"""The two readers of the device's clock that PR 36 brought
(readers/idle_in_span.py, readers/module_launches.py) on a hand-made trace
(data/hand_phase.xplane.textproto, whose comments give the intervals): the
idle by PHASE and the launches of a statement come out as worked out by
hand, a root alone attributes nothing, and a trace without the names or
without the line gives None."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.lib import xplane  # noqa: E402
from benchmarks.readers import idle_in_span, module_launches  # noqa: E402

FIXTURE = "hand_phase.xplane.textproto"


def text(name=FIXTURE):
    with open(os.path.join(HERE, "data", name)) as f:
        return f.read()


def serialized(proto_text):
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(proto_text)


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_bytes(serialized(text()))


def test_the_fixture_reads_as_its_comments_say(trace):
    assert [s[2] for s in trace.spans] == [
        "otb:wait:rpc-wire", "otb:query", "otb:execute", "otb:gather",
        "otb:finalize", "otb:query", "otb:query", "otb:execute"]
    assert [b[:2] for b in trace.bench] == [
        (500.0, 5000.0), (5500.0, 9000.0), (5800.0, 8000.0)]
    assert xplane.window_of(trace) == (500.0, 9900.0)


@pytest.mark.parametrize("spans, classes, attributed, idle", [
    # any phase, the whole window [500,9900): chip 0 idles 9400 - 2700, its
    # phases cover 4800 of which 2500 busy; chip 1 idles 8900, 4800 - 500
    (None, None, 2300 + 4300, 6700 + 8900),
    # the gather inside Q1's statement [500,5000): chip 0 idles 3000 there,
    # the gather's 1800 hold op B's 500; chip 1 idles 4000, all 1800 idle
    (["otb:gather"], ["q1"], 1300 + 1800, 3000 + 4000),
    # the reads [5500,9000): execute [5900,7100) holds op C; chip 1 is idle
    (None, ["point_read"], 200 + 1200, (3500 - 1200) + 3500),
    # a span no statement of the class holds
    (["otb:gather"], ["point_read"], 0, 1),
    # asked for by name, the root counts like any span
    (["otb:query"], ["q1"], (4000 - 1500) + (4000 - 500), 3000 + 4000),
])
def test_idle_by_phase(trace, spans, classes, attributed, idle):
    assert idle_in_span.idle_in_span_pct(trace, spans, classes) == \
        pytest.approx(100 * attributed / idle)


def test_a_root_alone_attributes_nothing(trace):
    roots = xplane.Trace(trace.ops, [s for s in trace.spans
                                     if s[2] == "otb:query"], trace.bench)
    assert idle_in_span.idle_in_span_pct(roots) == 0.0
    assert idle_in_span.idle_in_span_pct(roots, None, ["q1"]) == 0.0
    # what `idle_attributed` says of the same trace: nearly all of it
    assert xplane.idle_attributed_pct(roots) > 40


def test_idle_by_phase_of_nothing_is_none(trace):
    assert idle_in_span.idle_in_span_pct(trace, None, ["q9"]) is None
    nameless = xplane.Trace.from_bytes(
        serialized(text("hand.xplane.textproto")))
    assert idle_in_span.idle_in_span_pct(nameless) is None
    assert idle_in_span.idle_in_span_pct(
        xplane.Trace({}, trace.spans, trace.bench)) is None


@pytest.mark.parametrize("classes, want", [
    # chip 0 is the busiest: two launches inside Q1; the reads share the
    # launches at 6000 and 7200 (both open), the one at 8500 is the first's
    (None, [2.0, 2.0, 1.0]),
    (["q1"], [2.0]),
    (["point_read"], [2.0, 1.0]),
    (["q9"], []),
])
def test_launches_per_statement(trace, classes, want):
    starts = module_launches.module_starts(serialized(text()))
    assert starts == {"/device:TPU:0": [1000.0, 3000.0, 6000.0, 7200.0,
                                        8500.0],
                      "/device:TPU:1": [1000.0]}
    assert module_launches.launches_per_statement(
        starts, trace.bench, classes) == pytest.approx(want)


def test_no_module_line_is_none():
    raw = serialized(text().replace('"XLA Modules"', '"Steps"'))
    assert module_launches.module_starts(raw) is None


@pytest.mark.parametrize("reader, args, want", [
    (module_launches, {"classes": ["point_read"], "reduce": "median"}, 1.5),
    (module_launches, {"classes": ["q1"], "reduce": "max"}, 2.0),
    (idle_in_span, {"spans": ["otb:gather"], "classes": ["q1"]},
     100 * 3100 / 7000),
])
def test_the_readers_read_the_runs_own_trace(tmp_path, monkeypatch, reader,
                                             args, want):
    from benchmarks.lib import files
    d = tmp_path / "run_out" / "tpch_sf1_point" / "trace" / "plugins" \
        / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(serialized(text()))
    monkeypatch.setattr(files, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(files, "traffic", lambda name: {"mix": name})
    ctx = types.SimpleNamespace(
        trace=object(), mix=types.SimpleNamespace(spec={"mix": "point"}))
    assert reader.read(ctx, **args) == pytest.approx(want)
    ctx.trace = None                    # not a traced run
    assert reader.read(ctx, **args) is None
