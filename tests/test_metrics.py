"""Metric parity tests vs sklearn (the M2 model-framework tier).

Reference analogue: hex/AUC2 tests, ModelMetrics tests (SURVEY.md §4)."""

import numpy as np
import pytest
from sklearn import metrics as skm

from h2o3_tpu.models import metrics as M


@pytest.fixture()
def binom_data(rng):
    n = 5000
    y = (rng.random(n) < 0.35).astype(np.float64)
    p = np.clip(0.35 + 0.4 * (y - 0.35) + rng.normal(0, 0.25, n), 1e-6, 1 - 1e-6)
    return y, p


def test_auc_exact_matches_sklearn(binom_data):
    y, p = binom_data
    m = M.binomial_metrics(y, p)
    assert m.auc == pytest.approx(skm.roc_auc_score(y, p), abs=1e-10)
    assert m.logloss == pytest.approx(skm.log_loss(y, p), abs=1e-10)
    assert m.gini == pytest.approx(2 * m.auc - 1)


def test_auc_400_bins_close_to_exact(binom_data):
    """The reference's 400-bin approximation (AUC2.java:36) stays within ~1e-3."""
    y, p = binom_data
    exact = M.binomial_metrics(y, p, nbins=0).auc
    approx = M.binomial_metrics(y, p, nbins=400).auc
    assert approx == pytest.approx(exact, abs=2e-3)


@pytest.mark.parametrize("nbins", [0, 400])
@pytest.mark.parametrize("n", [1, 7, 5000])
def test_unweighted_roc_points_are_the_weighted_ones_bit_for_bit(n, nbins):
    """Rows that all weigh 1 take a path with no argsort: thresholds, counts
    and every metric read from them equal the general path's under weights of
    one, with tied scores, one class absent (n = 1) and NaN scores."""
    rng = np.random.default_rng(n)
    p = (1 / (1 + np.exp(-rng.normal(0, 1.5, n)))).astype(np.float32)
    p[rng.integers(0, n, n // 5)] = 0.5
    y = (rng.random(n) < p).astype(np.int32)
    if n > 1:
        p[rng.integers(0, n, n // 50)] = np.nan
    fast = M.binomial_metrics(y, p, nbins=nbins)
    general = M.binomial_metrics(y, p, weights=np.ones(n), nbins=nbins)
    for name in ("thresholds", "tps", "fps"):
        assert np.array_equal(getattr(fast, name), getattr(general, name)), name
    for name in ("auc", "pr_auc", "logloss", "mse", "max_f1_threshold",
                 "mean_per_class_error", "nobs"):
        a, b = getattr(fast, name), getattr(general, name)
        assert a == b or (a != a and b != b), name
    assert fast.cm == general.cm


def tied_scores(n, seed=3):
    rng = np.random.default_rng(seed)
    p = np.round(1 / (1 + np.exp(-rng.normal(0, 1.5, n))), 3)  # ~1000 distinct
    return (rng.random(n) < p).astype(np.float64), p


def test_the_integer_area_is_the_trapezoids_and_the_pairs():
    """``roc_area_counts``: twice the ROC's area times P x N is an integer,
    2 x (pairs a positive outscores a negative) + (pairs that tie)."""
    y, p = tied_scores(100_000)
    m = M.binomial_metrics(y, p)
    P, N = int(m._p), int(m._n)
    area = M.roc_area_counts(m.tps, m.fps)
    assert isinstance(area, int)
    assert area / (2 * P * N) == pytest.approx(m.auc, rel=1e-13)
    tpr, fpr = np.concatenate([[0.0], m.tps / P]), np.concatenate([[0.0], m.fps / N])
    assert area / (2 * P * N) == pytest.approx(float(np.trapezoid(tpr, fpr)), rel=1e-13)
    neg = np.sort(p[y == 0])
    below = np.searchsorted(neg, p[y == 1], side="left")
    upto = np.searchsorted(neg, p[y == 1], side="right")
    assert area == 2 * int(below.sum()) + int((upto - below).sum())
    # a run's first entry needs the one before the run: any run length
    for run in (1, 7, 999, 1 << 20):
        assert M.roc_area_counts(m.tps.astype(np.int32), m.fps.astype(np.int32), run) == area


def test_the_integer_area_holds_what_float64_cannot():
    """Counts near 2^31: a term passes 2^53 and the whole 2^63."""
    tps = np.array([2**30, 2**31 - 2], np.int64)
    fps = np.array([2**30, 2**31 - 2], np.int64)
    want = 2**30 * 2**30 + (2**30 - 2) * (2**31 - 2 + 2**30)
    assert M.roc_area_counts(tps, fps) == want
    assert M.roc_area_counts(np.zeros(0), np.zeros(0)) == 0


@pytest.mark.parametrize("run", [1, 7, 4999, 5000, 1 << 20])
def test_losses_in_runs_are_the_one_shot_sums(run):
    """``binomial_losses``: ``binomial_metrics``' logloss and mse with the
    link inside a run of rows, whatever the run's length (one row, a length
    that does not divide the rows, all of them), NaN rows left out."""
    y, _ = tied_scores(5000, seed=run)
    rng = np.random.default_rng(run)
    margin = rng.normal(0, 3, 5000).astype(np.float32)
    margin[::97] = np.nan
    margin[5:9] = [60.0, -60.0, 800.0, -800.0]  # the clip at 1e-15, and past exp

    def link(x):
        with np.errstate(over="ignore"):
            return 1 / (1 + np.exp(-x))

    whole = M.binomial_metrics(y, link(margin.astype(np.float64)))
    logloss, mse, rows = M.binomial_losses(y, margin, link, run=run)
    assert rows == whole.nobs == 5000 - len(margin[::97])
    assert logloss == pytest.approx(whole.logloss, rel=1e-13)
    assert mse == pytest.approx(whole.mse, rel=1e-13)


def test_losses_of_no_rows():
    link = lambda x: 1 / (1 + np.exp(-x))  # noqa: E731
    logloss, mse, rows = M.binomial_losses(np.zeros(0), np.zeros(0), link)
    assert rows == 0 and logloss != logloss and mse != mse
    logloss, mse, rows = M.binomial_losses(np.array([np.nan, 1.0]),
                                           np.array([0.5, np.nan]), link, run=1)
    assert rows == 0 and logloss != logloss and mse != mse


def test_max_f1_threshold_and_cm(binom_data):
    y, p = binom_data
    m = M.binomial_metrics(y, p)
    # compare to brute-force F1 over all candidate thresholds
    prec, rec, thr = skm.precision_recall_curve(y, p)
    f1 = 2 * prec * rec / np.maximum(prec + rec, 1e-300)
    best_f1 = f1.max()
    assert m.cm.f1 == pytest.approx(best_f1, abs=1e-6)
    cm = m.confusion_matrix(0.5)
    sk_cm = skm.confusion_matrix(y, (p >= 0.5).astype(int))
    np.testing.assert_allclose(cm.table, sk_cm)


def test_pr_auc_close(binom_data):
    y, p = binom_data
    m = M.binomial_metrics(y, p)
    assert m.pr_auc == pytest.approx(skm.average_precision_score(y, p), abs=5e-3)


def test_regression_metrics(rng):
    y = rng.normal(10, 2, 1000)
    p = y + rng.normal(0, 1, 1000)
    m = M.regression_metrics(y, p)
    assert m.mse == pytest.approx(skm.mean_squared_error(y, p))
    assert m.mae == pytest.approx(skm.mean_absolute_error(y, p))
    assert m.r2 == pytest.approx(skm.r2_score(y, p))


def test_regression_weights(rng):
    y = rng.normal(size=500)
    p = y + rng.normal(0, 1, 500)
    w = rng.random(500) + 0.5
    m = M.regression_metrics(y, p, weights=w)
    assert m.mse == pytest.approx(skm.mean_squared_error(y, p, sample_weight=w))


def test_multinomial_metrics(rng):
    n, k = 3000, 4
    y = rng.integers(0, k, n)
    logits = rng.normal(0, 1, (n, k))
    logits[np.arange(n), y] += 1.5
    probs = np.exp(logits) / np.exp(logits).sum(1, keepdims=True)
    m = M.multinomial_metrics(y, probs, domain=["a", "b", "c", "d"])
    assert m.logloss == pytest.approx(skm.log_loss(y, probs), abs=1e-9)
    acc = (probs.argmax(1) == y).mean()
    assert m.hit_ratios[0] == pytest.approx(acc, abs=1e-9)
    assert m.hit_ratios[-1] == pytest.approx(1.0)
    assert m.confusion_matrix.sum() == n


def test_stop_early_semantics():
    # monotone improving: never stops
    hist = list(np.linspace(1.0, 0.5, 20))
    assert not M.stop_early(hist, stopping_rounds=3, more_is_better=False, stopping_tolerance=1e-3)
    # plateaued: stops
    hist = [1.0, 0.8, 0.6, 0.5] + [0.45] * 10
    assert M.stop_early(hist, stopping_rounds=3, more_is_better=False, stopping_tolerance=1e-3)
    # too-short history: no decision
    assert not M.stop_early([1.0, 0.9], stopping_rounds=3, more_is_better=False, stopping_tolerance=1e-3)
    # more-is-better plateau (e.g. AUC)
    hist = [0.6, 0.7, 0.75] + [0.76] * 10
    assert M.stop_early(hist, stopping_rounds=3, more_is_better=True, stopping_tolerance=1e-3)
    # still improving AUC
    hist = list(np.linspace(0.6, 0.9, 20))
    assert not M.stop_early(hist, stopping_rounds=3, more_is_better=True, stopping_tolerance=1e-3)
