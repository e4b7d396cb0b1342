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
from h2o3_tpu.models import metrics as M
from h2o3_tpu.models.tree import booster
from h2o3_tpu.models.tree.common import SPAN_COUNTS, TRAIN_METRICS, sigmoid
from h2o3_tpu.models.tree.drf import DRF
from h2o3_tpu.models.tree.gbm import GBM, GBMParameters
from h2o3_tpu.models.tree.xgboost import XGBoost
from h2o3_tpu.parallel.mesh import default_mesh, row_sharding
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


#: the cases whose margin is a binomial ensemble's own and whose rows all
#: weigh 1: ordered and counted on the device (ISSUE 37); every other case's
#: training metrics are ``metrics.binomial_metrics`` and its kin on the host
ON_DEVICE = {"bernoulli", "sampled", "offset", "na_response", "budget_monitor",
             "early_stopping_monitor", "enum_sets", "xgboost"}


def margin_sources():
    return {s: TRAIN_METRICS.value(source=s)
            for s in ("fit_margin", "fit_margin_device", "walk")}


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
    before = margin_sources()
    model = builder(**{**BASE, **extra}).train(frame)
    source = "fit_margin_device" if case in ON_DEVICE else "fit_margin"
    assert margin_sources() == {**before, source: before[source] + 1}
    perf = last_performance_span()
    assert perf["source"] == "fit_margin" and perf[source] == 1
    assert perf.get("roc") == (
        None if model.nclasses != 2 else "device" if case in ON_DEVICE else "host")
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
    before = margin_sources()
    model = GBM(**dict(BASE, max_runtime_secs=600.0)).train(frame)
    assert margin_sources() == {
        **before, "fit_margin_device": before["fit_margin_device"] + 1}
    events = [e for e in timeline.snapshot(timeline.CAPACITY) if "parent_id" in e]
    perf = [e for e in events if e["kind"] == "model_performance"][-1]
    assert perf["source"] == "fit_margin" and perf["rows"] == N
    assert perf["roc"] == "device"
    # beside the programs a process's first such fit builds there (jit_*)
    under = {e["kind"]: e for e in events if e["parent_id"] == perf["span_id"]
             and not e["kind"].startswith("jit_")}
    assert sorted(under) == ["score_link", "score_metrics"]
    # the thresholds counted, and dispatch to ready on the device
    assert under["score_metrics"]["distinct"] == len(model.training_metrics.thresholds)
    assert 0 < under["score_metrics"]["device_s"] < 60
    fit = {e["kind"] for e in events if e["trace_id"] == perf["trace_id"]}
    assert not fit & {"score_traverse", "margin_readback"}  # the budget check had it
    by_id = {e["span_id"]: e for e in events}
    for kind in ("tree_matrix", "apply_bins"):
        parents = {by_id[e["parent_id"]]["kind"] for e in events
                   if e["kind"] == kind and e["trace_id"] == perf["trace_id"]}
        assert "model_performance" not in parents
    # the fit's profile and its `train done` line say so
    assert {"fit_margin", "fit_margin_device"} <= set(SPAN_COUNTS)
    prof = model.fit_profile
    assert prof["model_performance"] == {"s": prof["model_performance"]["s"], "n": 1,
                                         "fit_margin_device": 1}
    assert set(k for k in prof if k.startswith("score/")
               and not k.startswith("score/jit_")) == {
        "score/score_link", "score/score_metrics"}
    from h2o3_tpu.util import log

    done = [ln for ln in log.recent(500)
            if "gbm train done" in ln and str(model.key) in ln]
    assert done and "fit_margin_device=1" in done[-1]
    model.model_performance(frame)
    assert TRAIN_METRICS.value(source="walk") == before["walk"] + 1


# ---------------------------------------------------------------------------
# the ROC counted where the margin lives (ISSUE 37)


def assert_the_hosts_metrics(ours, y, margin):
    """``ours`` against ``metrics.binomial_metrics`` of the same rows' scores:
    what is order and counting bit for bit, what is a float64 sum to the
    order of its additions."""
    y, margin = np.asarray(y, np.float64), np.asarray(margin, np.float64)
    with np.errstate(over="ignore", invalid="ignore", divide="ignore"):
        host = M.binomial_metrics(y, sigmoid(margin))
    for name in ("thresholds", "tps", "fps"):
        assert getattr(ours, name).dtype == np.float64
        assert np.array_equal(getattr(ours, name), getattr(host, name)), name
    assert ours.max_f1_threshold == host.max_f1_threshold
    assert ours.cm == host.cm and ours.nobs == host.nobs
    assert (ours._p, ours._n) == (host._p, host._n)
    assert ours.confusion_matrix(0.3) == host.confusion_matrix(0.3)
    for name in ("auc", "pr_auc", "gini", "logloss", "mse", "rmse",
                 "mean_per_class_error"):
        a, b = getattr(ours, name), getattr(host, name)
        assert (a != a and b != b) or a == pytest.approx(b, rel=1e-12, abs=0), name


def on_the_device(y, margin, n_pad=None, mesh=None, piece=M._ROC_PIECE, run=M._RUN_ROWS):
    """``MarginRoc`` and ``binomial_losses`` of rows padded to ``n_pad`` and
    dealt over ``mesh``, as a fit leaves them."""
    mesh = mesh or default_mesh(n_devices=1)
    n = len(y)
    n_pad = n_pad or n + (-n) % mesh.devices.size
    held = np.zeros((n_pad, 1), np.float32)
    held[:n, 0] = margin
    held[n:] = 7.0  # padding holds anything
    y_pad = np.zeros(n_pad, np.float32)
    y_pad[:n] = y
    roc = M.MarginRoc(
        jax.device_put(held, row_sharding(mesh, 2)),
        jax.device_put(y_pad, row_sharding(mesh, 1)),
        jax.device_put(np.arange(n_pad) < n, row_sharding(mesh, 1)), mesh, piece)
    with np.errstate(over="ignore"):
        losses = M.binomial_losses(y, np.asarray(held[:n, 0], np.float64), sigmoid, run)
        return roc.metrics(losses, sigmoid, run), roc


def margins_of(case, n=3000):
    rng = np.random.default_rng(len(case))
    m = rng.normal(0, 2, n).astype(np.float32)
    y = (rng.random(n) < sigmoid(m)).astype(np.float64)
    if case == "two_scores":  # a depth-1 model
        m = np.where(rng.random(n) < 0.4, np.float32(-0.7), np.float32(0.9))
    elif case == "no_positives":
        y[:] = 0
    elif case == "no_negatives":
        y[:] = 1
    elif case == "one_score":
        m[:] = 0.25
    elif case == "saturating":  # the link gives whole runs of margins one score
        m[: n // 2] = rng.choice(np.float32([40, 40.5, 100, -100, -800, 36.5, 37, 25,
                                             25.000002, 3e38, -3e38]), n // 2)
    elif case == "signed_zeros":
        m[: n // 2] = rng.choice(np.float32([-0.0, 0.0, 1e-30, -1e-30, 1e-9]), n // 2)
    elif case == "nan_margin":
        m[::37] = np.nan
    elif case == "infinite":
        m[::41], m[::43] = np.inf, -np.inf
    elif case == "ties":
        m = np.round(m, 1)
    return y, m


@pytest.mark.filterwarnings("ignore:overflow encountered in exp")
@pytest.mark.parametrize("layout", ["whole", "padded", "mesh4", "pieces"])
@pytest.mark.parametrize("case", [
    "plain", "ties", "two_scores", "no_positives", "no_negatives", "one_score",
    "saturating", "signed_zeros", "nan_margin", "infinite"])
def test_the_devices_counts_are_the_hosts(case, layout):
    y, m = margins_of(case)
    how = {"whole": {}, "padded": {"n_pad": 4096},
           "mesh4": {"mesh": default_mesh(n_devices=4), "n_pad": 3008},
           "pieces": {"piece": 512, "run": 700}}[layout]
    ours, roc = on_the_device(y, m, **how)
    assert_the_hosts_metrics(ours, y, m)
    assert roc.distinct == len(ours.thresholds) and roc.device_s > 0
    if case == "saturating":
        # 40, 40.5, 100 and 3e38 are one score, and so are -800 and -3e38
        assert len(ours.thresholds) < len(np.unique(m))
    if case == "signed_zeros":
        assert np.sum(ours.thresholds == 0.5) == 1


def test_no_rows_at_all():
    ours, roc = on_the_device(np.zeros(0), np.zeros(0, np.float32), n_pad=8)
    assert ours.nobs == 0 and roc.distinct == 0 and len(ours.thresholds) == 0
    assert ours.auc != ours.auc and ours.logloss != ours.logloss
    assert ours.max_f1_threshold == 0.5


def test_host_and_device_must_hold_the_same_rows():
    y, m = margins_of("plain", 64)
    roc = M.MarginRoc(jnp.asarray(m[:, None]), jnp.asarray(y, jnp.float32),
                      jnp.ones(64, bool), default_mesh(n_devices=1))
    with pytest.raises(ValueError, match="counted 64 rows"):
        roc.metrics(M.binomial_losses(y[:60], m[:60], sigmoid), sigmoid)


@pytest.mark.parametrize("case,params,shape", [
    ("depth_one", {"ntrees": 1, "max_depth": 1}, {}),
    ("na_response", {}, {"na_response": True}),
    ("offset", {"offset_column": "off"}, {"offset": True}),
    ("budget", {"max_runtime_secs": 600.0}, {}),
    ("odd_rows", {}, {"n": 2401}),
])
def test_a_fits_training_metrics_are_the_hosts(case, params, shape, monkeypatch):
    """Through the whole fit, on the default mesh of eight CPU devices with
    its padded rows: the metrics the model reports are
    ``metrics.binomial_metrics`` of the margin the fit held."""
    from h2o3_tpu.models.tree import gbm as gbm_mod

    held = {}

    def keeping(*a, **kw):
        bt = booster.train_boosted(*a, **kw)
        held.update(bt.fit_eval)
        return bt

    monkeypatch.setattr(gbm_mod, "train_boosted", keeping)
    frame = frame_of(**shape)
    before = margin_sources()
    model = GBM(**{**BASE, **params}).train(frame)
    assert margin_sources()["fit_margin_device"] == before["fit_margin_device"] + 1
    assert held["device"]["margin"].shape[0] % 8 == 0
    assert held["device"]["margin"].shape[0] >= len(held["y"]) == held["margin"].shape[0]
    assert_the_hosts_metrics(model.training_metrics, held["y"], held["margin"][:, 0])
    if case == "depth_one":
        assert len(model.training_metrics.thresholds) == 2
    if case == "na_response":
        assert len(held["y"]) < frame.nrows


def test_what_the_device_path_asks_of_a_fit():
    """Only what ``fit_eval`` holds selects it: a device margin that is the
    ensemble's own (DRF's is averaged: none is left), a binomial model,
    rows without weights."""
    X = np.random.default_rng(0).normal(size=(64, 3)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    p = booster.TreeParams(ntrees=2, max_depth=2, nbins=8, seed=0)
    rows = {"frame": object(), "y": y, "w": None}
    summed = booster.train_boosted(X, "bernoulli", y, 1, np.zeros(1), p, fit_eval=rows)
    dev = summed.fit_eval["device"]
    assert sorted(dev) == ["margin", "mesh", "valid", "y"]
    assert dev["margin"].dtype == jnp.float32 and dev["margin"].shape[1] == 1
    np.testing.assert_array_equal(
        np.asarray(dev["margin"])[:64], summed.fit_eval["margin"].astype(np.float32))
    averaged = booster.train_boosted(X, "fixed", y[:, None], 1, np.zeros(1), p,
                                     average=True, fit_eval=rows)
    assert "device" not in averaged.fit_eval


def test_a_second_fit_builds_no_program_for_its_metrics():
    """The window's rule: after a warm-up fit on the same frame nothing of
    the metrics compiles (one program a padded row count and mesh, every
    shape static, the pieces fetched by the count)."""
    frame = frame_of(seed=12, n=2477)

    def perf_spans():
        events = [e for e in timeline.snapshot(timeline.CAPACITY) if "parent_id" in e]
        perf = [e for e in events if e["kind"] == "model_performance"][-1]
        return [perf] + [e for e in events if e["parent_id"] == perf["span_id"]]

    first = GBM(**BASE).train(frame)
    assert sum(e.get("compiles", 0) + e.get("cache_loads", 0) for e in perf_spans()) >= 1
    # another count of trees, so another margin and other thresholds
    second = GBM(**dict(BASE, ntrees=9)).train(frame)
    spans = perf_spans()
    assert spans[0]["roc"] == "device" and len(spans) == 3
    assert all("compiles" not in e and "cache_loads" not in e for e in spans)
    assert len(second.training_metrics.thresholds) != len(first.training_metrics.thresholds)
    assert second.fit_profile["model_performance"]["fit_margin_device"] == 1


def test_the_benchmarks_check_sees_a_reported_metric_altered(monkeypatch):
    """``benchmark/tests/test_correct.py::test_reported_metric_altered``
    plants its fault in ``metrics.binomial_metrics``, which a cell's fit no
    longer calls for its training rows. The same fault where the numbers
    are now produced (losses over every other row; counts of a margin that
    is not the fit's): the harness's run comes out not ``correct``."""
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from benchmark.lib import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]

    def load(kind, name):
        with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
            return json.load(f)

    def drive():
        return harness.run(
            cell=cell, config=load("configs", cell["config"]),
            traffic=load("traffic", cell["traffic"]), seed=11, seconds=3.0,
            trace=False, rehearse=True, t_start=0.0, root=ROOT, metrics=[])

    losses, order = M.binomial_losses, M._margin_order
    with monkeypatch.context() as planted:
        planted.setattr(M, "binomial_losses", lambda y, m, link: (
            *losses(y[::2], m[::2], link)[:2], len(y)))
        out = drive()
    assert out["correct"] is False
    assert out["checks"]["logloss_gap"][0] > out["checks"]["logloss_gap"][1]
    assert out["checks"]["auc_gap"][0] <= out["checks"]["auc_gap"][1]
    with monkeypatch.context() as planted:
        planted.setattr(M, "_margin_order", lambda margin, *a, **kw: order(
            jnp.round(margin, 1), *a, **kw))
        out = drive()
    assert out["correct"] is False
    assert out["checks"]["auc_gap"][0] > out["checks"]["auc_gap"][1]
    assert out["checks"]["logloss_gap"][0] <= out["checks"]["logloss_gap"][1]


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
