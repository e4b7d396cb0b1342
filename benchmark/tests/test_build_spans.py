"""The four readers of a first fit's build and of the host under the
device's idle time, on a small hand-made ring and trace whose answers are
worked by hand; and each reads nothing from a program without their spans."""

import pytest

from lib import build_spans, harness, spans

from conftest import ROOT

MS = 1_000_000
T = 10 ** 18  # an epoch, in ns
NAMES = ("warmup_trace_lower_s", "warmup_entry_unspanned_s", "budget_download_s",
         "idle_unspanned_pct")


def _ring():
    """A warm-up fit (trace a) and a window's fit (trace b): (kind, id,
    parent, start ms, end ms, fields)."""
    rows = [
        # the warm-up: train() at 0, its first tree_block at 500
        ("train", "w0", None, 0, 1000, {"trace_s": 0.3, "lower_s": 0.2}),
        ("tree_setup", "w1", "w0", 0, 100, {}),
        ("data_info", "w2", "w1", 20, 70, {"cat_columns": 0}),
        ("train_boosted", "w3", "w0", 100, 990, {}),
        ("make_bins", "w4", "w3", 110, 130, {}),
        ("bins_resident", "w5", "w3", 130, 400, {"hit": False}),
        ("apply_bins", "w6", "w5", 150, 250, {}),
        ("bins_upload", "w7", "w5", 250, 300, {}),
        ("jit_build", "w8", "w5", 320, 380, {"steps": 4}),
        ("state_upload", "w9", "w3", 400, 420, {}),
        ("jit_trace", "wa", "w3", 430, 470, {}),
        ("tree_block", "wb", "w3", 500, 900, {"trees": 1}),
        ("jit_build", "wc", "wb", 505, 880, {}),
        # the window's fit
        ("train", "b0", None, 2000, 3000, {}),
        ("tree_setup", "b1", "b0", 2000, 2050, {}),
        ("data_info", "b2", "b1", 2010, 2040, {}),
        ("train_boosted", "b3", "b0", 2050, 2990, {}),
        ("make_bins", "b4", "b3", 2060, 2070, {}),
        ("bins_resident", "b5", "b3", 2070, 2080, {"hit": True}),
        ("state_upload", "b6", "b3", 2080, 2090, {}),
        ("tree_block", "b7", "b3", 2100, 2500, {"trees": 1}),
        ("tree_readback", "b8", "b3", 2500, 2510, {}),
        ("budget_check", "b9", "b3", 2510, 2560, {}),
        ("margin_download", "ba", "b9", 2512, 2552, {"bytes": 64}),
        ("tree_block", "bb", "b3", 2600, 2900, {"trees": 1}),
        ("tree_readback", "bc", "b3", 2900, 2910, {}),
        ("budget_check", "bd", "b3", 2910, 2940, {}),
        ("margin_download", "be", "bd", 2911, 2931, {"bytes": 64}),
        ("model_performance", "bf", "b0", 2950, 2990, {}),
        ("score_link", "bg", "bf", 2950, 2960, {}),
        ("score_metrics", "bh", "bf", 2960, 2985, {}),
    ]
    events = []
    for kind, sid, parent, s, e, fields in rows:
        events.append({"kind": kind, "span_id": sid, "parent_id": parent,
                       "trace_id": sid[0] * 16, "start_ns": T + s * MS,
                       "ns": T + e * MS, "duration_ms": float(e - s), **fields})
    return sorted(events, key=lambda e: e["ns"])


def _run(events, monkeypatch, trace=True):
    monkeypatch.setattr(spans, "ring_events", lambda: events)
    return {"warmup": {"t0_ns": T - MS, "t1_ns": T + 1001 * MS},
            "served": [{"t0_ns": T + 1999 * MS, "t1_ns": T + 3001 * MS}],
            "trace": {"busy_s": 0.7} if trace else None}


#: the trace of the window, on its own time axis (here the ring's ms): the
#: device busy in both blocks and in the metrics' sort; every span annotated
OPS = [("block", 2100 * MS, 400 * MS), ("block", 2600 * MS, 300 * MS),
       ("sort", 2962 * MS, 8 * MS)]
WINDOW = (2000 * MS, 3000 * MS)


def _annotated(events):
    return {e["span_id"]: (e["start_ns"] - T, e["ns"] - T) for e in events
            if e["trace_id"] == "b" * 16}


@pytest.fixture()
def traced(monkeypatch):
    events = _ring()
    monkeypatch.setattr(build_spans, "read_trace",
                        lambda d, marker, kinds: (OPS, WINDOW, _annotated(events)))
    return events


@pytest.mark.parametrize("name,value", [
    ("warmup_trace_lower_s", 0.5),
    # [0, 500] ms less the leaves data_info 50, make_bins 20, apply_bins and
    # bins_upload 150, the merged builds 60, state_upload 20, the trace 40
    ("warmup_entry_unspanned_s", 0.160),
    ("budget_download_s", 0.060),
    # idle 100 + 100 + 62 + 30 ms; under no leaf 40 + 50 + 20 + 15
    ("idle_unspanned_pct", 100.0 * 125 / 292),
])
def test_readers(traced, monkeypatch, name, value):
    run = _run(traced, monkeypatch)
    assert harness.load_reader(ROOT, name)(run) == pytest.approx(value)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_spans_reads_nothing(traced, monkeypatch, name):
    """The parent: no data_info, no margin_download, no jit leaves and no
    trace_s / lower_s on any span."""
    new = {"data_info", "margin_download", "jit_build", "jit_trace", "jit_lower"}
    old = [{k: v for k, v in e.items() if k not in ("trace_s", "lower_s")}
           for e in traced if e["kind"] not in new]
    run = _run(old, monkeypatch)
    assert harness.load_reader(ROOT, name)(run) is None


def test_an_untraced_run_reads_no_idle_share(traced, monkeypatch):
    run = _run(traced, monkeypatch, trace=False)
    assert harness.load_reader(ROOT, "idle_unspanned_pct")(run) is None


def test_a_leaf_missing_from_the_trace_leaves_its_idle_bare(monkeypatch):
    events = _ring()
    ann = _annotated(events)
    del ann["ba"]  # the first margin_download: 40 ms of idle now bare
    monkeypatch.setattr(build_spans, "read_trace", lambda d, m, k: (OPS, WINDOW, ann))
    run = _run(events, monkeypatch)
    value = harness.load_reader(ROOT, "idle_unspanned_pct")(run)
    assert value == pytest.approx(100.0 * 165 / 292)


def test_uncovered_counts_overlaps_once():
    assert build_spans.uncovered([(10, 60), (40, 80), (90, 95)], 0, 100) == 100 - 75
    assert build_spans.uncovered([], 5, 7) == 2
    assert build_spans.uncovered([(0, 100)], 20, 30) == 0
    assert build_spans.uncovered([(10, 20), (120, 130)], 0, 100) == 90  # one past hi


def test_idle_share_of_a_device_that_never_idles_is_none():
    assert build_spans.idle_unspanned_share([("op", 0, 100)], (0, 100), []) is None
    assert build_spans.idle_unspanned_share([("op", 0, 50)], (0, 100), []) == 100.0
