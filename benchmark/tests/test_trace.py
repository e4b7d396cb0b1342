"""The trace reduction on a small recorded trace, against hand-worked values."""

import json
import os

import pytest

from lib import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "recorded_trace.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        d = json.load(f)
    return ([tuple(e) for e in d["device"]], [tuple(e) for e in d["host"]])


def test_busy_union_and_idle_share(recorded):
    device, host = recorded
    r = trace.reduce({"/device:TPU:0": device}, host, "timed_window")
    # the block's %while covers [0, 164124224], the scoring's
    # [9390719141, 9472323237]; everything else is nested in them
    assert r["window_s"] == pytest.approx(9.472323237, abs=1e-9)
    assert r["busy_s"] == pytest.approx((164124224 + 81604096) / 1e9, abs=1e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.974058286, abs=1e-8)


def test_kernel_sum(recorded):
    device, host = recorded
    r = trace.reduce({"/device:TPU:0": device}, host, "timed_window")
    assert r["hist_kernel_calls"] == 1
    assert r["hist_kernel_s"] == pytest.approx(0.152302094, abs=1e-9)


def test_self_time_leaves_out_nested_operations(recorded):
    device, _ = recorded
    self_s = trace.self_seconds(device)
    block = next(k for k in self_s if k.startswith("%while.6 "))
    nested = 152302094 + 3 + 2 + 3 + 2 + 108 + 567 + 2 + 233
    assert self_s[block] == pytest.approx((164124224 - nested) / 1e9, abs=1e-9)
    r = trace.reduce({"/device:TPU:0": device}, [("timed_window", 0.0, 9472323237.0)],
                     "timed_window")
    assert r["device_ops"][0][0].startswith("_build_histogram_pallas_jit.32")


def test_gap_attribution(recorded):
    device, host = recorded
    r = trace.reduce({"/device:TPU:0": device}, host, "timed_window")
    # one gap, from the end of the block to the scoring traversal; the
    # innermost host span covering half of it is the host binning
    assert r["idle_gaps"][0][0] == "$histogram.py:176 apply_bins"
    assert r["idle_gaps"][0][1] == pytest.approx((9390719141 - 164124224) / 1e9, abs=1e-9)
    assert trace.gaps(device, 0.0, 9472323237.0) == [(164124224.0, 9390719141.0)]


def test_union_and_gaps_on_overlapping_intervals():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2), ("e", 50, 100)]
    assert trace.union_seconds(ev, 0, 60) == pytest.approx((15 + 5 + 10) / 1e9)
    assert trace.gaps(ev, 0, 60) == [(15, 30), (35, 50)]
    assert trace.attribute_gap((15, 30), [("outer", 0, 100), ("inner", 14, 10),
                                          ("tiny", 16, 2)]) == "inner"


def test_no_marker_reads_nothing(recorded):
    device, host = recorded
    assert trace.reduce({"/device:TPU:0": device}, host, "no_such_span") is None
    assert trace.reduce({}, host, "timed_window") is None
