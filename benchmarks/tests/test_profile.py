"""The reduction from a profiler trace to numbers, on a hand-made trace
(data/hand.xplane.pb, built from data/hand.xplane.textproto, whose comments
give the intervals): busy union, idle share, all-to-all time, top
operations and gap attribution come out as worked out by hand."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.lib import peaks, profile  # noqa: E402

PB = os.path.join(HERE, "data", "hand.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return profile.Trace.from_file(PB)


def test_pb_is_the_textproto(trace, tmp_path):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "hand.xplane.textproto")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    again = tmp_path / "again.xplane.pb"
    again.write_bytes(raw)
    other = profile.Trace.from_file(str(again))
    assert other.device_ops == trace.device_ops
    assert other.annotations == trace.annotations


def test_planes_lines_and_annotations(trace):
    # "XLA Modules" is not counted a second time; the host's own events
    # that are not `bench:` annotations are left out
    assert trace.describe() == {"/device:TPU:0": 4, "/device:TPU:1": 2}
    assert [a[2] for a in trace.annotations] == ["bench:q3", "bench:q5"]
    assert profile.span_of(trace) == (500.0, 10000.0)


def test_busy_union_and_idle_share(trace):
    # chip 0: [1000,4000) + [6000,7000) + [9000,9500) = 4500 (the two
    # overlapping ops count once); chip 1: 1000 + 2000 = 3000
    assert profile.busy_ns(trace.device_ops["/device:TPU:0"]) == 4500.0
    assert profile.busy_ns(trace.device_ops["/device:TPU:1"]) == 3000.0
    assert profile.busy_s_mean(trace) == pytest.approx(3750e-9)
    # idle share over the trace's span, as readers/trace_idle.py takes it
    lo, hi = profile.span_of(trace)
    assert 1 - profile.busy_s_mean(trace) * 1e9 / (hi - lo) == \
        pytest.approx(1 - 3750 / 9500)
    # clipped to a window
    assert profile.busy_ns(trace.device_ops["/device:TPU:0"],
                           2000.0, 6500.0) == 2500.0


def test_all_to_all_time_is_the_slowest_chips(trace):
    # q3: chip 0 has 1500 ns, chip 1 none; q5: chip 0 500, chip 1 2000
    assert profile.op_time_per_annotation(trace, "all-to-all") == \
        pytest.approx([1500e-9, 2000e-9])
    assert profile.op_time_per_annotation(trace, "all-to-all", ["q5"]) == \
        pytest.approx([2000e-9])


def test_busy_inside_each_statement(trace):
    got = dict(profile.busy_per_annotation(trace))
    assert got["q3"] == pytest.approx((3000 + 1000) / 2 * 1e-9)
    assert got["q5"] == pytest.approx((1500 + 2000) / 2 * 1e-9)


def test_top_ops_and_gap_attribution(trace):
    assert dict(map(tuple, profile.top_ops(trace))) == pytest.approx(
        {"fusion.1": 2000e-9, "all-to-all.2": 2000e-9})
    # chip 0 idles in [500,1000) [4000,6000) [7000,9000) [9500,10000):
    # q3 covers 500 + 1000, q5 covers 500 + 2000 + 500, nobody [5000,5500)
    gaps = profile.idle_gaps(trace, 500.0, 10000.0)
    assert [g[0] for g in gaps] == ["bench:q5", "bench:q3",
                                    profile.BETWEEN]
    assert [g[1] for g in gaps] == pytest.approx([3000e-9, 1500e-9, 500e-9])


def test_peaks_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("_source")
