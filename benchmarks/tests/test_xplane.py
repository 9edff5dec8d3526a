"""The reduction from the program's own names in a profiler trace to
numbers (lib/xplane.py), on a hand-made trace (data/hand_otb.xplane.textproto,
whose comments give the intervals): scope time, unnamed time, idle
attributed and exposed collective time come out as worked out by hand, and
a trace without the names gives nothing."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.lib import profile, xplane  # noqa: E402

NS = 1e-6       # the reductions answer in ms


def serialized(name):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", name)) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_bytes(serialized("hand_otb.xplane.textproto"))


@pytest.fixture(scope="module")
def nameless():
    """PR 24's hand-made trace: a program without scopes or spans."""
    return xplane.Trace.from_bytes(serialized("hand.xplane.textproto"))


def test_wire_reader_agrees_with_profile_data(nameless):
    other = profile.Trace.from_file(os.path.join(HERE, "data",
                                                 "hand.xplane.pb"))
    assert {p: sorted(o[:3] for o in ops)
            for p, ops in nameless.ops.items()} == other.device_ops
    assert nameless.bench == other.annotations
    assert nameless.spans == []


@pytest.mark.parametrize("op_name, scope", [
    ("jit(otb_mesh)/jit(main)/otb.agg/scatter-add:", "otb.agg"),
    ("jit(otb_mesh)/otb.join_expand/jit(join_probe_counts)/otb.join_probe/"
     "while:", "otb.join_probe"),
    ("jit(sort_rows)/otb.finalize/otb.sort/sort:", "otb.finalize"),
    ("jit(probe_fn)/add:", None),
    (None, None),
])
def test_scope_of_an_op(op_name, scope):
    assert xplane.scope_of(op_name) == scope


def test_ops_carry_their_scope_and_spans_are_found(trace):
    assert [o[3] for o in trace.ops["/device:TPU:0"]] == [
        "otb.agg", "otb.exchange", None, "otb.join_probe", None,
        "otb.finalize"]
    assert [o[3] for o in trace.ops["/device:TPU:1"]] == [
        "otb.agg", "otb.exchange", None]
    # the op line nests: XLA's while holds its body's op, and keeps only
    # its own 200 ns; ops that merely overlap do not nest
    assert [(o[4], o[5]) for o in trace.ops["/device:TPU:0"]] == [
        (2000, -1), (1500, -1), (200, -1), (1000, 2), (500, -1), (200, -1)]
    assert [(o[4], o[5]) for o in trace.ops["/device:TPU:1"]] == [
        (1000, -1), (1500, -1), (500, 1)]
    assert [s[2] for s in trace.spans] == [
        "otb:wait:rpc-wire", "otb:query", "otb:execute", "otb:query"]
    assert [b[2] for b in trace.bench] == ["bench:q3", "bench:q5"]
    assert xplane.window_of(trace) == (500.0, 10000.0)


@pytest.mark.parametrize("scopes, classes, want_ns", [
    (["otb.agg"], None, [2000, 0]),             # slowest chip: 0
    (["otb.agg"], ["q3"], [2000]),
    (["otb.join_build", "otb.join_probe", "otb.join_expand"], ["q5"],
     [1000]),
    (["otb.finalize"], ["q5"], [200]),
    (["otb.exchange"], None, [1500, 1500]),     # self times: chip 1's
                                                # holds copy.4 for 500
    (None, None, [0, 700]),     # no scope: copy.4 and while.6's own 200
])
def test_scope_time_per_statement(trace, scopes, classes, want_ns):
    assert xplane.scope_ms_per_statement(trace, scopes, classes) == \
        pytest.approx([w * NS for w in want_ns])


def test_idle_attributed(trace):
    # the client's own wait [600,9900) attributes nothing;
    # chip 0 idles 4600: spans cover 200 + 200 + 100 + 1900 of it;
    # chip 1 idles 6500: spans cover 200 + 2200 + 200 + 1600
    assert xplane.idle_attributed_pct(trace) == pytest.approx(
        100 * (2400 + 4200) / (4600 + 6500))


def test_exposed_collective_time(trace):
    # q3: chip 0's all-to-all [2500,4000) runs beside fusion.1 until 3000;
    # q5: chip 1's [6000,8000) beside copy.4 for 500 (an op inside it is
    # another op; one around it would not be)
    assert xplane.exposed_ms_per_statement(trace, "all-to-all") == \
        pytest.approx([1000 * NS, 1500 * NS])
    assert xplane.exposed_ms_per_statement(trace, "all-to-all", ["q5"]) == \
        pytest.approx([1500 * NS])


def test_a_program_without_names_gives_nothing(nameless):
    assert not nameless.has_scopes()
    assert xplane.scope_ms_per_statement(nameless, ["otb.agg"]) is None
    assert xplane.scope_ms_per_statement(nameless, None) is None
    assert xplane.idle_attributed_pct(nameless) is None
    # a collective's exposed time needs XLA's names only
    assert xplane.exposed_ms_per_statement(nameless, "all-to-all") == \
        pytest.approx([1000 * NS, 2000 * NS])


def test_of_this_run_reads_only_its_own_cells_trace(tmp_path, monkeypatch):
    """A stale trace under another cell's run directory (a run that was
    killed) is newer here, and is not this run's."""
    import types

    from benchmarks.lib import files

    def leave(cell, name):
        d = tmp_path / "run_out" / cell / "trace" / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        (d / "h.xplane.pb").write_bytes(serialized(name))

    leave("tpch_sf1_point", "hand_otb.xplane.textproto")
    leave("tpch_sf1_power", "hand.xplane.textproto")        # the newer
    monkeypatch.setattr(files, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(files, "traffic", lambda name: {"mix": name})
    mix = types.SimpleNamespace(spec={"mix": "point"})
    ctx = types.SimpleNamespace(trace=object(), mix=mix)
    assert xplane.of_this_run(ctx).has_scopes()
    mix.spec = {"mix": "power"}
    assert not xplane.of_this_run(ctx).has_scopes()
    mix.spec = {"mix": "no_cell_has_it"}
    assert xplane.of_this_run(ctx) is None
    ctx.trace = None
    assert xplane.of_this_run(ctx) is None
