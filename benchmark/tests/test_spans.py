"""The span-tree reduction on a small recorded ring, against hand-worked
values, and the readers that sit on it."""

import json
import os

import pytest

from lib import harness, spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1_000_000


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "recorded_ring.json")) as f:
        return json.load(f)


@pytest.fixture()
def run(recorded, monkeypatch):
    """A reader's view of a run whose ring is the recorded one."""
    monkeypatch.setattr(spans, "ring_events", lambda: recorded["events"])
    return {"warmup": recorded["warmup"], "served": recorded["served"]}


def _window(recorded):
    w = recorded["served"][0]
    return spans.fit_tree(recorded["events"], w["t0_ns"], w["t1_ns"])


def test_tree_is_the_fit_inside_the_window(recorded):
    tree = _window(recorded)
    assert tree["train"]["trace_id"] == "b" * 16
    assert len(tree["spans"]) == 19  # nothing of the warm-up, the plain event or the other trace
    kids = [e["kind"] for e in tree["children"][tree["train"]["span_id"]]]
    assert kids == ["tree_setup", "train_boosted", "model_performance"]
    boosted = next(e for e in tree["spans"] if e["kind"] == "train_boosted")
    assert [e["kind"] for e in tree["children"][boosted["span_id"]]] == [
        "make_bins", "bins_resident", "state_upload", "tree_block",
        "tree_readback", "budget_check", "tree_block", "tree_readback",
        "budget_check"]


def test_sums_by_kind_keep_entry_and_scoring_apart(recorded):
    tree = _window(recorded)
    sums = spans.sums_by_kind(tree)
    assert sums["tree_matrix"] == pytest.approx(0.040)
    assert sums["score/tree_matrix"] == pytest.approx(0.040)
    assert sums["score/apply_bins"] == pytest.approx(0.120)
    assert sums["tree_block"] == pytest.approx(0.400)
    assert "apply_bins" not in sums  # the window found the codes resident
    assert spans.kind_seconds(tree, "apply_bins", under="bins_resident") is None
    assert spans.kind_seconds(tree, "apply_bins", under="model_performance") == pytest.approx(0.120)


def test_uncovered_wall(recorded):
    # wall 1000 ms; leaves: tree_matrix 40, make_bins 10, bins_resident 1,
    # state_upload 10, blocks 400, read-backs 4, checks 4, scoring 264
    assert spans.uncovered_seconds(_window(recorded)) == pytest.approx(0.267)


def test_overlapping_leaves_count_once():
    tree = {"train": {"span_id": "t", "start_ns": 0, "ns": 100, "kind": "train"}}
    a = {"span_id": "a", "start_ns": 10, "ns": 60, "kind": "a", "parent_id": "t"}
    b = {"span_id": "b", "start_ns": 40, "ns": 80, "kind": "b", "parent_id": "t"}
    tree.update(spans=[tree["train"], a, b], children={"t": [a, b]})
    assert spans.uncovered_seconds(tree) == pytest.approx(30e-9)


@pytest.mark.parametrize("name,value", [
    ("bin_sketch_s", 0.010),
    ("bin_apply_s", 0.200),
    ("bin_device_put_s", 0.070),
    ("fit_entry_s", 0.100),
    ("score_matrix_s", 0.040),
    ("score_bin_s", 0.120),
    ("score_traverse_s", 0.070),
    ("score_metrics_s", 0.030),
    ("fit_unspanned_pct", 26.7),
])
def test_readers(run, name, value):
    assert harness.load_reader(ROOT, name)(run) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "bin_sketch_s", "bin_apply_s", "bin_device_put_s", "fit_entry_s",
    "score_matrix_s", "score_bin_s", "score_traverse_s", "score_metrics_s",
    "fit_unspanned_pct"])
def test_a_program_without_the_spans_reads_nothing(recorded, monkeypatch, name):
    """The parent records `train` and `tree_block` alone, with no start."""
    old = [{k: v for k, v in e.items() if k != "start_ns"}
           for e in recorded["events"] if e["kind"] in ("train", "tree_block")]
    monkeypatch.setattr(spans, "ring_events", lambda: old)
    run = {"warmup": recorded["warmup"], "served": recorded["served"]}
    assert harness.load_reader(ROOT, name)(run) is None


def test_a_wrapped_ring_is_refused(recorded):
    events = recorded["events"]
    t0 = recorded["warmup"]["t0_ns"]
    spans.check_reach(events, total_events=38, capacity=8192, t0_ns=t0)  # never wrapped
    # wrapped, but still reaching back before the fit: sound
    spans.check_reach(events, total_events=9000, capacity=8192, t0_ns=events[0]["ns"])
    with pytest.raises(spans.RingWrapped):
        spans.check_reach(events[5:], total_events=9000, capacity=8192, t0_ns=t0)
    with pytest.raises(spans.RingWrapped):
        spans.check_reach([], total_events=9000, capacity=8192, t0_ns=t0)


def test_in_order_walks_depth_first(recorded):
    rows = spans.in_order(_window(recorded))
    assert [(r["kind"], r["depth"]) for r in rows[:4]] == [
        ("train", 0), ("tree_setup", 1), ("tree_matrix", 2), ("train_boosted", 1)]
    check = [r for r in rows if r["kind"] == "budget_check"]
    assert [r["stop"] for r in check] == [False, True]
    assert rows[3]["start_s"] == pytest.approx(0.060)
