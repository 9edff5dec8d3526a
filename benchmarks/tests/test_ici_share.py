"""`all_to_all_ici_share` (readers/ici_share.py, lib/exchange_model.py) on
the hand-made trace data/hand_otb.xplane.textproto: q3's all-to-all takes
1500 ns on its slowest chip (chip 0, [2500,4000)), q5's 2000 ns (chip 1,
[6000,8000)); with a counter of 300 and 800 bytes a statement and a peak of
one byte a nanosecond the share is (300 + 800) / (1500 + 2000).  A program
without the counter, a run without a trace and a trace without the
collective give nothing."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.lib import exchange_model, profile  # noqa: E402
from benchmarks.readers import ici_share  # noqa: E402

PEAKS = {"ici_bits_per_s": 8e9}     # one byte a nanosecond


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "hand_otb.xplane.textproto")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("ici") / "hand_otb.xplane.pb"
    path.write_bytes(raw)
    return profile.Trace.from_file(str(path))


def ctx_of(trace, stats):
    return types.SimpleNamespace(trace=trace, peaks=PEAKS,
                                 step_stats=lambda: stats)


COUNTED = [("q3", {"exchange_bytes": 300}), ("q5", {"exchange_bytes": 800}),
           ("q3", {"exchange_bytes": 300}), ("q5", None)]


def test_bytes_by_class_and_least_time():
    assert exchange_model.sent_bytes_by_class(COUNTED) == \
        {"q3": 300.0, "q5": 800.0}
    assert exchange_model.least_seconds(1100, PEAKS) == \
        pytest.approx(1100e-9)


def test_share_of_the_interconnect_peak(trace):
    assert ici_share.read(ctx_of(trace, COUNTED), "all-to-all") == \
        pytest.approx(100.0 * 1100 / 3500)
    # one class alone: its bytes over its own statements' time
    assert ici_share.read(ctx_of(trace, COUNTED[:1]), "all-to-all") == \
        pytest.approx(100.0 * 300 / 1500)


@pytest.mark.parametrize("stats, pattern", [
    ([("q3", {"execute_ms": 1.0}), ("q5", {})], "all-to-all"),  # the parent
    (COUNTED, "all-gather"),            # no such op in the trace
    ([], "all-to-all"),
])
def test_nothing_to_read_gives_none(trace, stats, pattern):
    assert ici_share.read(ctx_of(trace, stats), pattern) is None


def test_untraced_run_gives_none():
    assert ici_share.read(ctx_of(None, COUNTED), "all-to-all") is None
