"""Training metrics from the margin the fit holds (ISSUE 33).

A tree fit ends with the final margin of every row it kept: the blocks add
each tree's leaves to it, and the budget check has it on the host.  The
fit's own ``model_performance(frame)`` scores from that margin and drops
it; any other frame, and the training frame afterwards, is binned and
walked.  Here: the two agree case by case to float32 summation order, the
hand-off is dropped and never saved, the spans and the counter say which
path ran, and the block programs and the scoring program the benchmark
lowers in set-up are the parent's.
"""

import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.frame.frame import NA_CAT, ColType, Column, Frame
from h2o3_tpu.models.tree import booster
from h2o3_tpu.models.tree.common import SPAN_COUNTS, TRAIN_METRICS
from h2o3_tpu.models.tree.drf import DRF
from h2o3_tpu.models.tree.gbm import GBM, GBMParameters
from h2o3_tpu.models.tree.xgboost import XGBoost
from h2o3_tpu.parallel.mesh import default_mesh
from h2o3_tpu.util import timeline

pytestmark = pytest.mark.leaks_keys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 2400


def frame_of(seed=5, response="bin", weights=False, offset=False, na_response=False,
             cats=False, n=N):
    """Four numeric predictors (one with NA), optionally two categorical,
    and a response of the kind asked for."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4))
    score = X[:, 0] - X[:, 1] * X[:, 2] + 0.5 * rng.normal(size=n)
    X[::41, 3] = np.nan
    cols = [Column(f"x{i}", X[:, i]) for i in range(4)]
    if cats:
        a = rng.integers(0, 30, n)
        effect = rng.normal(size=30)  # by the level, not by its index
        score = score + effect[a]
        a = np.where(np.arange(n) % 53 == 0, NA_CAT, a).astype(np.int32)
        cols.append(Column("a", a, ColType.CAT, [f"L{i:02d}" for i in range(30)]))
        cols.append(Column("b", rng.integers(0, 5, n).astype(np.int32), ColType.CAT,
                           list("pqrst")))
    if response == "reg":
        y = score.astype(np.float64)
        if na_response:
            y[::29] = np.nan
        cols.append(Column("y", y))
    else:
        k = 2 if response == "bin" else 3
        y = np.digitize(score, np.quantile(score, np.arange(1, k) / k)).astype(np.int32)
        if na_response:
            y[::29] = NA_CAT
        cols.append(Column("y", y, ColType.CAT, [f"c{i}" for i in range(k)]))
    if weights:
        w = rng.uniform(0.5, 2.0, n)
        w[::17] = 0.0  # the fit drops them; the walk gives them no weight
        cols.append(Column("w", w))
    if offset:
        cols.append(Column("off", 0.3 * rng.normal(size=n)))
    return Frame(cols)


BASE = dict(response_column="y", ntrees=6, max_depth=4, nbins=16, seed=9)

#: name -> (builder, parameters beyond BASE, what frame_of is asked for)
CASES = {
    "bernoulli": (GBM, {}, {}),
    "multinomial": (GBM, {}, {"response": "multi"}),
    "gaussian": (GBM, {}, {"response": "reg"}),
    "sampled": (GBM, {"sample_rate": 0.8, "col_sample_rate_per_tree": 0.8}, {}),
    "weights": (GBM, {"weights_column": "w"}, {"weights": True}),
    "offset": (GBM, {"offset_column": "off"}, {"offset": True}),
    "offset_gaussian": (GBM, {"offset_column": "off"}, {"offset": True, "response": "reg"}),
    "na_response": (GBM, {}, {"na_response": True}),
    "na_response_gaussian": (GBM, {"weights_column": "w"},
                             {"na_response": True, "response": "reg", "weights": True}),
    "budget_monitor": (GBM, {"max_runtime_secs": 600.0}, {}),
    "early_stopping_monitor": (GBM, {"stopping_rounds": 2, "score_tree_interval": 2,
                                     "ntrees": 8}, {}),
    "enum_sets": (GBM, {"categorical_encoding": "enum"}, {"cats": True}),
    "xgboost": (XGBoost, {"reg_lambda": 1.0}, {}),
    "xgboost_multinomial": (XGBoost, {}, {"response": "multi"}),
    "drf_bernoulli": (DRF, {"sample_rate": 0.7}, {}),
    "drf_multinomial": (DRF, {"sample_rate": 0.7}, {"response": "multi"}),
    "drf_regression": (DRF, {"weights_column": "w"}, {"response": "reg", "weights": True}),
}


def last_performance_span():
    return [e for e in timeline.snapshot(timeline.CAPACITY)
            if e["kind"] == "model_performance"][-1]


def assert_same_metrics(ours, walk, zero_weight=0):
    """Float32 summation order apart: relative 1e-6 on the losses, absolute
    1e-6 on AUC.  ``nobs`` of a fit's own metrics leaves out the rows of
    weight zero, which the fit dropped (as the reference's metric builders
    skip them); a walk of the frame counts them and gives them no weight."""
    assert type(ours) is type(walk)
    for name in ("logloss", "mse", "rmse", "mae", "mean_residual_deviance", "r2",
                 "mean_per_class_error"):
        if hasattr(walk, name):
            assert getattr(ours, name) == pytest.approx(getattr(walk, name), rel=1e-6), name
    if hasattr(walk, "auc"):
        assert ours.auc == pytest.approx(walk.auc, abs=1e-6)
    assert ours.nobs == walk.nobs - zero_weight


@pytest.mark.parametrize("case", sorted(CASES))
def test_training_metrics_are_the_walks(case):
    builder, extra, shape = CASES[case]
    frame = frame_of(**shape)
    margin0 = TRAIN_METRICS.value(source="fit_margin")
    model = builder(**{**BASE, **extra}).train(frame)
    assert TRAIN_METRICS.value(source="fit_margin") == margin0 + 1
    assert last_performance_span()["source"] == "fit_margin"
    # consumed by the fit's own call: the same frame is walked now
    assert model.booster.fit_eval is None
    walked = model.model_performance(frame)
    assert last_performance_span()["source"] == "walk"
    dropped = 0
    if shape.get("weights"):
        y, w = frame.col("y").numeric_view(), frame.col("w").numeric_view()
        dropped = int((~np.isnan(y) & (w == 0)).sum())
        assert dropped > 0
    assert_same_metrics(model.training_metrics, walked, zero_weight=dropped)


def test_checkpoint_continue_holds_the_whole_ensembles_margin():
    frame = frame_of()
    first = GBM(**dict(BASE, ntrees=3)).train(frame)
    more = GBM(**dict(BASE, ntrees=7, checkpoint=first.key)).train(frame)
    assert last_performance_span()["source"] == "fit_margin"
    assert more.ntrees_built == 7
    assert_same_metrics(more.training_metrics, more.model_performance(frame))
    straight = GBM(**dict(BASE, ntrees=7)).train(frame)
    assert_same_metrics(more.training_metrics, straight.training_metrics)


def test_an_averaged_ensemble_continued_from_a_checkpoint_keeps_the_walk():
    """DRF's margin is a sum of trees that starts at zero, and a continued
    fit's blocks add only their own trees to it: what the device holds is
    not the ensemble's margin, so the fit leaves none."""
    frame = frame_of()
    first = DRF(**dict(BASE, ntrees=3)).train(frame)
    assert last_performance_span()["source"] == "fit_margin"
    more = DRF(**dict(BASE, ntrees=6, checkpoint=first.key)).train(frame)
    assert last_performance_span()["source"] == "walk"
    assert more.booster.fit_eval is None
    assert_same_metrics(more.training_metrics, more.model_performance(frame))


def test_another_frame_is_walked_and_leaves_the_margin_alone():
    frame, other = frame_of(), frame_of(seed=6, n=900)
    held = {}

    class Peek(GBM):
        def _fit(self, frame, valid=None):
            model = super()._fit(frame, valid)
            held["after_fit"] = model.booster.fit_eval
            return model

    walks0 = TRAIN_METRICS.value(source="walk")
    model = Peek(**BASE).train(frame, other)
    # training frame from the margin, validation frame by the walk, in a fit
    assert held["after_fit"] is None
    assert TRAIN_METRICS.value(source="walk") == walks0 + 1
    assert last_performance_span()["source"] == "walk"
    assert last_performance_span()["rows"] == 900
    assert_same_metrics(model.validation_metrics, model.model_performance(other))
    # an equal frame that is not the fit's own object is a frame like any other
    twin = frame_of()
    raw = booster.train_boosted(
        np.zeros((8, 2), np.float32), "bernoulli", np.zeros(8), 1, np.zeros(1),
        booster.TreeParams(ntrees=1, max_depth=1, nbins=4, seed=0),
        fit_eval={"frame": twin, "y": np.zeros(8), "w": None})
    assert raw.fit_eval["frame"] is twin and raw.fit_eval["margin"].shape == (8, 1)
    model.booster.fit_eval = dict(raw.fit_eval, frame=twin)
    model.model_performance(frame)
    assert last_performance_span()["source"] == "walk"
    assert model.booster.fit_eval is not None  # not this frame's: not consumed


def test_a_direct_fit_without_rows_leaves_no_margin():
    X = np.random.default_rng(0).normal(size=(64, 3)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    bt = booster.train_boosted(X, "bernoulli", y, 1, np.zeros(1),
                               booster.TreeParams(ntrees=2, max_depth=2, nbins=8, seed=0))
    assert bt.fit_eval is None


def test_the_margin_left_is_predict_margin():
    """Monitor path (the last budget check's host copy) and no-monitor path
    (one read-back after the last block) leave ``predict_margin``'s answer,
    the offset within."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(700, 3)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    off = 0.2 * rng.normal(size=700)
    p = booster.TreeParams(ntrees=5, max_depth=3, nbins=8, seed=1)
    rows = {"frame": object(), "y": y, "w": None}
    for monitor in (None, lambda t, m: False):
        bt = booster.train_boosted(X, "bernoulli", y, 1, np.array([0.1]), p,
                                   monitor=monitor, score_interval=2, offset=off,
                                   fit_eval=rows)
        assert bt.fit_eval["margin"].shape == (700, 1)
        np.testing.assert_allclose(bt.fit_eval["margin"][:, 0],
                                   bt.predict_margin(X)[:, 0] + off, rtol=0, atol=2e-6)
    kinds = [e["kind"] for e in timeline.snapshot(64)]
    assert "margin_readback" in kinds and "budget_check" in kinds


def test_a_saved_model_carries_no_margin(tmp_path):
    from h2o3_tpu.models import persist

    frame = frame_of()
    model = GBM(**BASE).train(frame)
    # even a margin still held (the fit's call not made yet) is never written
    model.booster.fit_eval = {"frame": frame, "y": np.zeros(N), "w": None,
                              "margin": np.zeros((N, 1))}
    path = persist.save_model(model, str(tmp_path / "model.bin"))
    loaded = persist.load_model(path)
    assert getattr(loaded.booster, "fit_eval", None) is None
    model.booster.fit_eval = None
    assert_same_metrics(loaded.model_performance(frame), model.training_metrics)
    assert last_performance_span()["source"] == "walk"


def test_spans_and_counter_of_a_fit():
    frame = frame_of()
    before = {s: TRAIN_METRICS.value(source=s) for s in ("fit_margin", "walk")}
    model = GBM(**dict(BASE, max_runtime_secs=600.0)).train(frame)
    assert TRAIN_METRICS.value(source="fit_margin") == before["fit_margin"] + 1
    assert TRAIN_METRICS.value(source="walk") == before["walk"]
    events = [e for e in timeline.snapshot(timeline.CAPACITY) if "parent_id" in e]
    perf = [e for e in events if e["kind"] == "model_performance"][-1]
    assert perf["source"] == "fit_margin" and perf["rows"] == N
    under = [e["kind"] for e in events if e["parent_id"] == perf["span_id"]]
    assert sorted(under) == ["score_link", "score_metrics"]
    fit = {e["kind"] for e in events if e["trace_id"] == perf["trace_id"]}
    assert not fit & {"score_traverse", "margin_readback"}  # the budget check had it
    by_id = {e["span_id"]: e for e in events}
    for kind in ("tree_matrix", "apply_bins"):
        parents = {by_id[e["parent_id"]]["kind"] for e in events
                   if e["kind"] == kind and e["trace_id"] == perf["trace_id"]}
        assert "model_performance" not in parents
    # the fit's profile and its `train done` line say so
    assert "fit_margin" in SPAN_COUNTS
    prof = model.fit_profile
    assert prof["model_performance"] == {"s": prof["model_performance"]["s"], "n": 1,
                                         "fit_margin": 1}
    assert set(k for k in prof if k.startswith("score/")) == {
        "score/score_link", "score/score_metrics"}
    from h2o3_tpu.util import log

    done = [ln for ln in log.recent(500)
            if "gbm train done" in ln and str(model.key) in ln]
    assert done and "fit_margin=1" in done[-1]
    model.model_performance(frame)
    assert TRAIN_METRICS.value(source="walk") == before["walk"] + 1


# ---------------------------------------------------------------------------
# what must not move: the block programs and the scoring program


def cell_block(name):
    """The block a cell of the benchmark trains with, lowered at a small
    row count the way ``benchmark/lib/programs.compile_block`` lowers it:
    the ``TreeParams`` ``GBM._fit`` makes of the configuration's
    parameters, the Pallas flow with subtraction as on the chip, the
    cell's features and tree block."""
    import importlib.util

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    p = GBMParameters(response_column="y", **config["params"])
    cat_levels = ()
    if p.categorical_encoding == "enum":
        path = os.path.join(ROOT, "benchmark", "tables",
                            config["table"]["generator"] + ".py")
        spec = importlib.util.spec_from_file_location("table_generator_t", path)
        gen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(gen)
        cat_levels = tuple(len(c["domain"]) if c["type"] == "cat" else 0
                           for c in gen.columns(config["table"]))
    tp = booster.TreeParams(
        ntrees=0, seed=0, max_depth=p.max_depth, learn_rate=p.learn_rate,
        nbins=p.nbins, min_rows=p.min_rows,
        min_split_improvement=p.min_split_improvement, reg_lambda=0.0, reg_alpha=0.0,
        sample_rate=p.sample_rate, col_sample_rate_per_tree=p.col_sample_rate_per_tree,
        cat_levels=cat_levels)
    block = int(config.get("env", {}).get("H2O3_TPU_TREE_BLOCK", booster.DEFAULT_TREE_BLOCK))
    n, F = 1024, int(config["table"]["features"])
    S = jax.ShapeDtypeStruct
    fn = booster._make_block_fn("bernoulli", 1, block, tp, default_mesh(n_devices=1),
                                subtract=True)
    return fn.lower(
        S((n, F), jnp.int32), S((n,), jnp.float32), S((n,), jnp.bool_),
        S((n, 1), jnp.float32), S((block, 2), jnp.uint32),
        S((F + (-F) % min(8, F), n), jnp.int32), None, None).as_text()


#: sha256 of the StableHLO text of the three cells' training blocks,
#: recorded from commit db3b09e (the parent of ISSUE 33) BEFORE the change
PARENT_BLOCKS = {
    "gbm-higgs-d6-b256": "5bdb4c67fd88973c58e3865d585bf109d17ee7f64dbe173232fba922dd855b6f",
    "gbm-higgs-automl-d10": "fc9b8373e0f9d314949d7869e80b645b677bca2b97397462c937f29831ec96d1",
    "gbm-airline-10m-d10": "6c2e297f618ce6f26c54f9fcc195d652c71f122c922cbf80d9b49aa3cb60dec9",
}


#: the same blocks since ISSUE 35 (a level launches the slots of the nodes it
#: builds, up to 64; the node-matmul kernel's operands sit behind a barrier):
#: recorded from that PR's tree
LADDER_BLOCKS = {
    "gbm-higgs-d6-b256": "938b950ff5a52008f5af36b1d1c7cc7bf68889af77896218695423b7530ebf58",
    "gbm-higgs-automl-d10": "675a6dd25da05fa30cc4c1d23c0911867ecb43682d6efaa2f16d5e01fdd770b7",
    "gbm-airline-10m-d10": "3bac0978934729575f168abbf4565f87ef5985c8a96fa12b5fd03e35374298ff",
}


@pytest.mark.parametrize("cell", sorted(PARENT_BLOCKS))
@pytest.mark.parametrize("plan,want", [
    ("parents", PARENT_BLOCKS), ("todays", LADDER_BLOCKS)])
def test_the_cells_blocks_lower_to_the_parents_programs(
        cell, plan, want, monkeypatch, request):
    """Traced with the node ladder of ISSUE 33's parent and without the
    barrier on the node-matmul kernel's operands (the fixture), the blocks
    are still that commit's text, digest for digest: the node slots a level
    launches and that barrier are all that ISSUE 35 changed in a compiled
    program. As the program is they are the text recorded with it."""
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "pallas")
    if plan == "parents":
        request.getfixturevalue("parents_level_plan")
    assert hashlib.sha256(cell_block(cell).encode()).hexdigest() == want[cell]


def test_the_benchmark_can_still_build_its_scoring_programs(capsys):
    """``benchmark/lib/programs.build_scoring_programs`` lowers
    ``_predict_stacked`` in set-up by today's signature, and fails soft
    with a note: no note."""
    from benchmark.lib import programs

    model = GBM(**dict(BASE, ntrees=2)).train(frame_of(n=300))
    programs.build_scoring_programs(model, 300, 4, [2, 4])
    assert "could not be built ahead" not in capsys.readouterr().err
    trees = model.booster.trees_per_class[0]
    assert len(trees.stacked()) == 5
    # and the walk is still what predict() and an unseen frame run
    assert callable(booster._tree_walk) and callable(booster._predict_sets)


def test_labels_do_not_hang_on_the_summation_order(tmp_path):
    """Few distinct scores, many rows on each (a separable response): the
    training max-F1 threshold IS a training score, reached in the fit's
    float32 order, and ``predict`` reaches the same leaves by the walk's.
    Every row is labelled as the training confusion matrix counted it, in
    the cluster and from the MOJO."""
    from h2o3_tpu import genmodel

    rng = np.random.default_rng(2)
    X = rng.normal(size=(300, 3))
    fr = Frame([Column(f"x{i}", X[:, i]) for i in range(3)]
               + [Column("y", (X[:, 0] > 0).astype(np.int32), ColType.CAT, ["n", "p"])])
    model = GBM(response_column="y", ntrees=10, max_depth=3, seed=1).train(fr)
    tm = model.training_metrics
    assert tm.max_f1_threshold in tm.thresholds
    assert model.default_threshold() < tm.max_f1_threshold
    lower = tm.thresholds[tm.thresholds < tm.max_f1_threshold]
    assert lower.size == 0 or model.default_threshold() > lower.max()
    labels = model.predict(fr).col("predict").numeric_view()
    assert int((labels == 1).sum()) == int(tm.cm.tp + tm.cm.fp) > 0
    # an explicit threshold is taken as given
    model.reset_threshold(0.25)
    assert model.default_threshold() == 0.25
    model._threshold_override = None
    mojo = genmodel.load_mojo(model.download_mojo(str(tmp_path / "m.zip")))
    assert float(mojo.meta["default_threshold"]) == model.default_threshold()
