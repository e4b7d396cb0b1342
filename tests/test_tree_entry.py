"""A tree fit's entry on a frame whose bin codes are resident.

The builders hand the booster their training rows as deferred rows
(``common.TreeRows``): the quantile sketch reads the rows it samples from
the frame's columns, and the ``[N, F]`` float matrix is built only where
every row is read: the codes' placement on a device-cache miss and a
checkpoint's margin.  Here: the deferred rows are the matrix's rows bit for
bit and give the same edges; a second fit on a frame builds no matrix, says
so, and trains the trees and margin of a fit that built one; a
checkpoint-continue, an evicted entry and a frame without version stamps
still build it and train.
"""

import numpy as np
import pytest

from h2o3_tpu.frame import devcache
from h2o3_tpu.frame.frame import NA_CAT, ColType, Column, Frame
from h2o3_tpu.keyed import DKV
from h2o3_tpu.models.data_info import response_vector
from h2o3_tpu.models.tree import common
from h2o3_tpu.models.tree.common import (
    ENTRY_MATRIX, TreeModelBase, resolve_tree_encoding, training_rows,
    tree_cat_levels, tree_data_info, tree_matrix,
)
from h2o3_tpu.models.tree.drf import DRF
from h2o3_tpu.models.tree.gbm import GBM, GBMParameters
from h2o3_tpu.models.tree.xgboost import XGBoost
from h2o3_tpu.ops.histogram import make_bins
from h2o3_tpu.util import timeline

pytestmark = pytest.mark.leaks_keys

#: past the quantile sketch's sample of 200,000 rows
LARGE = 210_000


def frame_of(n=3000, seed=4, offset=True):
    """Four numeric predictors (one with NA), two categorical ones (one
    with NA), a binary response with NA rows, weights with zeros and an
    offset column."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4))
    X[::37, 3] = np.nan
    a = rng.integers(0, 12, n)
    score = X[:, 0] - X[:, 1] * X[:, 2] + rng.normal(size=12)[a]
    a = np.where(np.arange(n) % 53 == 0, NA_CAT, a).astype(np.int32)
    y = (score > 0).astype(np.int32)
    y[::29] = NA_CAT
    w = rng.uniform(0.5, 2.0, n)
    w[::17] = 0.0
    cols = [Column(f"x{i}", X[:, i]) for i in range(4)] + [
        Column("a", a, ColType.CAT, [f"L{i:02d}" for i in range(12)]),
        Column("b", rng.integers(0, 5, n).astype(np.int32), ColType.CAT,
               list("pqrst")),
        Column("y", y, ColType.CAT, ["no", "yes"]),
        Column("w", w),
    ]
    if offset:
        cols.append(Column("off", 0.3 * rng.normal(size=n)))
    return Frame(cols)


@pytest.mark.parametrize("n", [3000, LARGE])
@pytest.mark.parametrize("encoding", ["enum", "label_encoder", "one_hot_explicit"])
def test_deferred_rows_are_the_matrix_rows_and_give_its_edges(encoding, n):
    frame = frame_of(n)
    p = GBMParameters(response_column="y", weights_column="w",
                      offset_column="off", categorical_encoding=encoding)
    enc = resolve_tree_encoding(encoding)
    info = tree_data_info(frame, "y", ["w", "off"])
    X, y, w, off = training_rows(frame, p, info, enc, response_vector(info, frame),
                                 use_offset=True)
    dense = tree_matrix(info, frame, encoding=enc)[X.keep]
    assert X.shape == dense.shape and X.shape[0] < n  # rows were dropped
    assert len(y) == len(w) == len(off) == X.shape[0]
    idx = np.random.default_rng(1).choice(X.shape[0], 500, replace=False)
    assert X.rows(idx).tobytes() == dense[idx].tobytes()
    levels = tree_cat_levels(info, enc)
    for seed in (0, 7):
        edges = make_bins(X, 16, seed=seed, cat_levels=levels)
        assert edges.tobytes() == make_bins(
            dense, 16, seed=seed, cat_levels=levels).tobytes()
    assert X.count() == "resident"  # the sketch built no matrix
    assert X.materialize().tobytes() == dense.tobytes()
    assert X.materialize() is X.materialize()


def test_rows_that_drop_none_are_not_copied():
    frame = frame_of(offset=False)
    info = tree_data_info(frame, "x0", ["y", "w"])  # x0 has no NA
    y = response_vector(info, frame)
    X, kept, w, _ = training_rows(
        frame, GBMParameters(response_column="x0"), info, "label_encoder", y)
    assert X.shape == (frame.nrows, 5)
    assert kept is y and w is None
    assert X.materialize().tobytes() == tree_matrix(info, frame).tobytes()


#: builder -> (its parameters, the frame's aux columns it reads)
BUILDERS = {
    "gbm": (GBM, dict(weights_column="w", offset_column="off")),
    "xgboost": (XGBoost, dict(weights_column="w", ignored_columns=["off"])),
    "drf": (DRF, dict(weights_column="w", ignored_columns=["off"])),
}

#: (encoding, rows): each encoding under the sketch's sample, and past it
CASES = [("enum", 3000), ("label_encoder", 3000), ("one_hot_explicit", 3000),
         ("label_encoder", LARGE)]


@pytest.fixture
def final_margins(monkeypatch):
    """The final margin each fit hands its own training metrics."""
    seen = []
    real = TreeModelBase.model_performance

    def spy(self, frame):
        ev = getattr(self.booster, "fit_eval", None)
        if ev is not None and frame is ev["frame"]:
            seen.append(np.array(ev["margin"]))
        return real(self, frame)

    monkeypatch.setattr(TreeModelBase, "model_performance", spy)
    return seen


def fit(builder, frame, encoding, **more):
    """One fit, the kinds of its spans and the path it counted."""
    algo, params = BUILDERS[builder]
    before = {k: ENTRY_MATRIX.value(path=k) for k in ("built", "resident")}
    model = algo(**dict(dict(response_column="y", ntrees=3, max_depth=3, nbins=16,
                             seed=5, categorical_encoding=encoding), **params, **more)
                 ).train(frame)
    train = [e for e in timeline.snapshot(timeline.CAPACITY)
             if e["kind"] == "train" and "parent_id" in e][-1]
    kinds = [e["kind"] for e in timeline.snapshot(timeline.CAPACITY)
             if e.get("trace_id") == train["trace_id"]]
    counted = [k for k in before if ENTRY_MATRIX.value(path=k) == before[k] + 1]
    return model, kinds, counted


def trees_of(model):
    return [np.stack(getattr(t, f)).tobytes()
            for t in model.booster.trees_per_class for f in t._fields()]


@pytest.mark.parametrize("encoding,n", CASES)
@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_a_second_fit_builds_no_matrix_and_trains_the_same(
        builder, encoding, n, final_margins):
    frame = frame_of(n)
    first, kinds, counted = fit(builder, frame, encoding)
    assert counted == ["built"] and "tree_matrix" in kinds
    second, kinds, counted = fit(builder, frame, encoding)
    assert counted == ["resident"]
    assert "tree_matrix" not in kinds and "tree_rows" not in kinds
    assert second.fit_profile["train_boosted"]["matrix_resident"] == 1
    devcache.DEVCACHE.clear()
    third, kinds, counted = fit(builder, frame, encoding)
    assert counted == ["built"] and "tree_rows" in kinds
    assert third.fit_profile["train_boosted"]["matrix_built"] == 1
    assert trees_of(second) == trees_of(third) == trees_of(first)
    assert len(final_margins) == 3
    assert (final_margins[0].tobytes() == final_margins[1].tobytes()
            == final_margins[2].tobytes())


def test_the_matrix_sits_under_the_codes_placement_on_a_miss():
    frame = frame_of()
    fit("gbm", frame, "enum")
    by_id = {e["span_id"]: e for e in timeline.snapshot(timeline.CAPACITY)
             if "span_id" in e}
    train = [e for e in by_id.values() if e["kind"] == "train"][-1]
    spans = [e for e in by_id.values() if e.get("trace_id") == train["trace_id"]]
    parents = {e["kind"]: by_id[e["parent_id"]]["kind"] for e in spans
               if e["kind"] in ("tree_matrix", "tree_rows")}
    assert parents == {"tree_matrix": "bins_resident", "tree_rows": "bins_resident"}
    # a resident fit's tree_setup has no tree_matrix under it
    _, _, counted = fit("gbm", frame, "enum")
    assert counted == ["resident"]
    by_id = {e["span_id"]: e for e in timeline.snapshot(timeline.CAPACITY)
             if "span_id" in e}
    train = [e for e in by_id.values() if e["kind"] == "train"][-1]
    setup = [e for e in by_id.values()
             if e["kind"] == "tree_setup" and e["parent_id"] == train["span_id"]]
    assert len(setup) == 1
    under = [e["kind"] for e in by_id.values() if e.get("parent_id") == setup[0]["span_id"]]
    assert "data_info" in under and "tree_matrix" not in under


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_a_checkpoint_continue_builds_the_matrix_where_it_needs_the_margin(builder):
    frame = frame_of()
    first, _, _ = fit(builder, frame, "label_encoder")
    more, _, counted = fit(builder, frame, "label_encoder", ntrees=6,
                            checkpoint=first.key)
    assert more.ntrees_built == 6
    assert trees_of(more)[:1] != trees_of(first)[:1]  # three trees more
    # the codes are resident: a boosted ensemble's margin needs every row,
    # an averaged one's starts from zero (and its metrics walk the frame,
    # which fit_profile keys ``score/tree_matrix``)
    if builder == "drf":
        assert counted == ["resident"] and "tree_matrix" not in more.fit_profile
    else:
        assert counted == ["built"] and "tree_matrix" in more.fit_profile


def test_an_evicted_entry_builds_the_matrix_again():
    frame = frame_of()
    frame.key = "tree_entry_evicted.hex"
    DKV.put(frame.key, frame)
    try:
        first, _, _ = fit("gbm", frame, "enum")
        assert fit("gbm", frame, "enum")[2] == ["resident"]
        assert devcache.DEVCACHE.invalidate_frame(frame.key) >= 1
        again, kinds, counted = fit("gbm", frame, "enum")
        assert counted == ["built"] and "tree_matrix" in kinds
        assert [e["hit"] for e in timeline.snapshot(timeline.CAPACITY)
                if e["kind"] == "bins_resident"][-1] is False
        assert trees_of(again) == trees_of(first)
    finally:
        DKV.remove(frame.key)


def test_a_frame_without_version_stamps_builds_the_matrix_every_fit(monkeypatch):
    monkeypatch.setattr(devcache, "frame_token", lambda *a, **k: None)
    frame = frame_of()
    assert common.tree_cache_token(frame, GBMParameters(response_column="y"),
                                   "enum") is None
    first, _, counted = fit("gbm", frame, "enum")
    assert counted == ["built"]
    second, kinds, counted = fit("gbm", frame, "enum")
    assert counted == ["built"] and "tree_matrix" in kinds
    assert trees_of(second) == trees_of(first)
