"""Model metrics — the ModelMetrics* hierarchy, TPU-native.

Reference: ``hex/ModelMetrics*.java`` (~30 classes), ``hex/AUC2.java`` (AUC via
a 400-bin threshold histogram, ``AUC2.java:36`` NBINS=400), ``hex/ConfusionMatrix``,
GainsLift. Metric definitions below match the reference's semantics:

  * AUC: trapezoidal over the threshold-histogram ROC. ``nbins=400`` gives the
    reference's approximation; ``nbins=0`` computes the exact (perfect) AUC,
    equivalent to ``AUC2.perfectAUC`` (``AUC2.java:589``).
  * Max-F1 threshold is the default classification threshold, as in
    ``AUC2.defaultThreshold`` / ``ThresholdCriterion.f1``.
  * Deviances per family follow ``hex/Distribution.java`` definitions.

Inputs are host numpy arrays (predictions already gathered); each metric is a
cheap O(N) or O(N log N) pass. The one exception is the binomial metrics of a
margin that is still on the device (a tree fit's own training rows):
``MarginRoc`` sorts and counts there, as integers, and the host adds what is
a float64 sum in runs of rows (``binomial_losses``).
"""

from __future__ import annotations

import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec


# ---------------------------------------------------------------------------
# shared helpers


def _weighted(x: np.ndarray, w: Optional[np.ndarray]) -> Tuple[np.ndarray, float]:
    if w is None:
        w = np.ones_like(x, dtype=np.float64)
    return w.astype(np.float64), float(w.sum())


# ---------------------------------------------------------------------------
# regression


@dataclass
class RegressionMetrics:
    mse: float
    rmse: float
    mae: float
    rmsle: float
    mean_residual_deviance: float
    r2: float
    nobs: int

    def __repr__(self) -> str:
        return (
            f"RegressionMetrics(rmse={self.rmse:.6g}, mse={self.mse:.6g}, "
            f"mae={self.mae:.6g}, r2={self.r2:.4f}, "
            f"mean_residual_deviance={self.mean_residual_deviance:.6g})"
        )


def regression_metrics(
    actual: np.ndarray,
    predicted: np.ndarray,
    weights: Optional[np.ndarray] = None,
    deviance: Optional[np.ndarray] = None,
) -> RegressionMetrics:
    y = np.asarray(actual, dtype=np.float64)
    p = np.asarray(predicted, dtype=np.float64)
    ok = ~(np.isnan(y) | np.isnan(p))
    y, p = y[ok], p[ok]
    w, wsum = _weighted(y, None if weights is None else np.asarray(weights)[ok])
    err = y - p
    mse = float(np.sum(w * err**2) / wsum)
    mae = float(np.sum(w * np.abs(err)) / wsum)
    if np.all(y >= 0) and np.all(p >= 0):
        rmsle = float(np.sqrt(np.sum(w * (np.log1p(p) - np.log1p(y)) ** 2) / wsum))
    else:
        rmsle = float("nan")
    ybar = float(np.sum(w * y) / wsum)
    ss_tot = float(np.sum(w * (y - ybar) ** 2))
    r2 = 1.0 - np.sum(w * err**2) / ss_tot if ss_tot > 0 else float("nan")
    mrd = (
        float(np.sum(w * deviance[ok]) / wsum)
        if deviance is not None
        else mse  # gaussian deviance == squared error (hex/Distribution.java)
    )
    return RegressionMetrics(
        mse=mse,
        rmse=float(np.sqrt(mse)),
        mae=mae,
        rmsle=rmsle,
        mean_residual_deviance=mrd,
        r2=float(r2),
        nobs=int(len(y)),
    )


# ---------------------------------------------------------------------------
# binomial


@dataclass
class ConfusionMatrix:
    """2x2 at a threshold: [[tn, fp], [fn, tp]] (hex/ConfusionMatrix.java layout
    is domain x domain with actual rows, predicted columns)."""

    tn: float
    fp: float
    fn: float
    tp: float
    threshold: float

    @property
    def table(self) -> np.ndarray:
        return np.array([[self.tn, self.fp], [self.fn, self.tp]])

    @property
    def accuracy(self) -> float:
        t = self.tn + self.fp + self.fn + self.tp
        return (self.tn + self.tp) / t if t else float("nan")

    @property
    def precision(self) -> float:
        d = self.tp + self.fp
        return self.tp / d if d else float("nan")

    @property
    def recall(self) -> float:
        d = self.tp + self.fn
        return self.tp / d if d else float("nan")

    @property
    def specificity(self) -> float:
        d = self.tn + self.fp
        return self.tn / d if d else float("nan")

    @property
    def f1(self) -> float:
        p, r = self.precision, self.recall
        return 2 * p * r / (p + r) if (p + r) else float("nan")

    @property
    def mcc(self) -> float:
        d = np.sqrt(
            (self.tp + self.fp) * (self.tp + self.fn) * (self.tn + self.fp) * (self.tn + self.fn)
        )
        return ((self.tp * self.tn - self.fp * self.fn) / d) if d else float("nan")


@dataclass
class BinomialMetrics:
    auc: float
    pr_auc: float
    gini: float
    logloss: float
    mse: float
    rmse: float
    mean_per_class_error: float
    max_f1_threshold: float
    cm: ConfusionMatrix
    nobs: int
    thresholds: np.ndarray = field(default_factory=lambda: np.empty(0), repr=False)
    tps: np.ndarray = field(default_factory=lambda: np.empty(0), repr=False)
    fps: np.ndarray = field(default_factory=lambda: np.empty(0), repr=False)

    def confusion_matrix(self, threshold: Optional[float] = None) -> ConfusionMatrix:
        return self.cm if threshold is None else _cm_at(self.thresholds, self.tps, self.fps, self._p, self._n, threshold)

    _p: float = 0.0
    _n: float = 0.0

    def __repr__(self) -> str:
        return (
            f"BinomialMetrics(auc={self.auc:.6f}, logloss={self.logloss:.6f}, "
            f"pr_auc={self.pr_auc:.6f}, rmse={self.rmse:.6g}, "
            f"max_f1_threshold={self.max_f1_threshold:.4f})"
        )


def _roc_points(
    actual: np.ndarray, prob: np.ndarray, weights: Optional[np.ndarray], nbins: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, float, float]:
    """Sorted-descending unique thresholds with cumulative tp/fp counts.

    nbins=0 → exact (one threshold per distinct score, AUC2.perfectAUC);
    nbins=400 → the reference's histogram approximation (AUC2.java:36).
    """
    y = np.asarray(actual, dtype=np.float64)
    p = np.asarray(prob, dtype=np.float64)
    ok = ~(np.isnan(y) | np.isnan(p))
    y, p = y[ok], p[ok]
    if nbins and len(np.unique(p)) > nbins:
        # histogram thresholds: uniform quantile-ish bin centers over score range
        edges = np.quantile(p, np.linspace(0, 1, nbins + 1))
        centers = np.unique(edges)
        idx = np.clip(np.searchsorted(centers, p, side="right") - 1, 0, len(centers) - 1)
        p = centers[idx]
    if weights is None:
        # rows that all weigh 1 need no order among themselves: count, for
        # each distinct score, the rows of either class at or above it.  The
        # same numbers as the sums below, without the argsort and the three
        # gathers over every row (a third of a 32M-row fit's metrics)
        pos = y > 0.5
        ths = np.unique(p)
        tps, fps = (
            (len(s) - np.searchsorted(s, ths, side="left")).astype(np.float64)[::-1]
            for s in (np.sort(p[pos]), np.sort(p[~pos])))
        return ths[::-1], tps, fps, float(pos.sum()), float((~pos).sum())
    w, _ = _weighted(y, np.asarray(weights)[ok])
    order = np.argsort(-p, kind="stable")
    ps, ys, ws = p[order], y[order], w[order]
    pos_w = np.where(ys > 0.5, ws, 0.0)
    neg_w = np.where(ys > 0.5, 0.0, ws)
    cum_tp = np.cumsum(pos_w)
    cum_fp = np.cumsum(neg_w)
    # keep last occurrence of each distinct threshold
    last = np.ones(len(ps), dtype=bool)
    last[:-1] = ps[:-1] != ps[1:]
    return ps[last], cum_tp[last], cum_fp[last], float(pos_w.sum()), float(neg_w.sum())


def _cm_at(ths, tps, fps, P, N, threshold) -> ConfusionMatrix:
    i = np.searchsorted(-ths, -threshold, side="right") - 1
    tp = tps[i] if i >= 0 else 0.0
    fp = fps[i] if i >= 0 else 0.0
    return ConfusionMatrix(tn=N - fp, fp=fp, fn=P - tp, tp=tp, threshold=float(threshold))


def binomial_metrics(
    actual: np.ndarray,
    prob: np.ndarray,
    weights: Optional[np.ndarray] = None,
    nbins: int = 0,
) -> BinomialMetrics:
    """Binomial metrics from actual labels {0,1} and P(class=1)."""
    y = np.asarray(actual, dtype=np.float64)
    p = np.asarray(prob, dtype=np.float64)
    ok = ~(np.isnan(y) | np.isnan(p))
    y, p = y[ok], p[ok]
    w, wsum = _weighted(y, None if weights is None else np.asarray(weights)[ok])

    ths, tps, fps, P, N = _roc_points(y, p, None if weights is None else w, nbins)
    if P == 0 or N == 0:
        auc = pr = float("nan")
    else:
        tpr = np.concatenate([[0.0], tps / P])
        fpr = np.concatenate([[0.0], fps / N])
        auc = float(np.trapezoid(tpr, fpr))
        prec = tps / np.maximum(tps + fps, 1e-300)
        rec = tps / P
        # PR-AUC by trapezoid over recall (reference pr_auc, AUC2.java:288)
        pr = float(np.trapezoid(np.concatenate([[prec[0]], prec]), np.concatenate([[0.0], rec])))

    eps = 1e-15
    pc = np.clip(p, eps, 1 - eps)
    logloss = float(np.sum(w * -(y * np.log(pc) + (1 - y) * np.log(1 - pc))) / wsum)
    mse = float(np.sum(w * (y - p) ** 2) / wsum)

    # max-F1 threshold scan (default threshold, AUC2 ThresholdCriterion.f1)
    if P > 0 and N > 0 and len(ths):
        precs = tps / np.maximum(tps + fps, 1e-300)
        recs = tps / P
        f1s = np.where(precs + recs > 0, 2 * precs * recs / np.maximum(precs + recs, 1e-300), 0.0)
        best = int(np.argmax(f1s))
        thr = float(ths[best])
    else:
        thr = 0.5
    cm = _cm_at(ths, tps, fps, P, N, thr) if len(ths) else ConfusionMatrix(N, 0, P, 0, thr)
    tpr_ = cm.tp / P if P else float("nan")
    tnr_ = cm.tn / N if N else float("nan")
    mpce = float(1 - (tpr_ + tnr_) / 2)

    m = BinomialMetrics(
        auc=auc,
        pr_auc=pr,
        gini=2 * auc - 1 if auc == auc else float("nan"),
        logloss=logloss,
        mse=mse,
        rmse=float(np.sqrt(mse)),
        mean_per_class_error=mpce,
        max_f1_threshold=thr,
        cm=cm,
        nobs=int(len(y)),
        thresholds=ths,
        tps=tps,
        fps=fps,
    )
    m._p, m._n = P, N
    return m


# ---------------------------------------------------------------------------
# binomial, from a margin that is on the device
#
# One definition of each metric and two ways to count. ``binomial_metrics``
# orders the scores on the host. A tree fit that ends with its rows' margin
# on the device (float32), a float64 copy of it on the host and no weights
# has the device sort it once and count the positives down the order, and
# the host read every sum from those counts and that copy in runs of rows,
# float sums in float64 and the ROC's area as the integer it is:
# ``MarginRoc`` and ``binomial_losses``. ``tests/test_tree_train_metrics.py``
# holds the two together.

#: rows a pass over every row takes at a time, and the threads it takes them
#: on (numpy's ufuncs release the interpreter)
_RUN_ROWS = 1 << 20
_RUN_THREADS = 16

#: sorted rows a piece of the device's output holds: the host fetches the
#: pieces that hold a row it counted, not the padding's
_ROC_PIECE = 1 << 22

_I32_MAX = np.iinfo(np.int32).max


def _in_runs(fn: Callable[[int, int], object], n: int, run: int) -> list:
    """``fn(start, stop)`` over ``[0, n)`` in runs of ``run`` rows, results
    in the runs' order; on threads where there are several runs."""
    spans = [(a, min(a + run, n)) for a in range(0, n, run)]
    workers = min(len(spans), os.cpu_count() or 1, _RUN_THREADS)
    if workers < 2:
        return [fn(a, b) for a, b in spans]
    with ThreadPoolExecutor(workers) as pool:
        return list(pool.map(lambda ab: fn(*ab), spans))


def binomial_losses(actual: np.ndarray, margin: np.ndarray,
                    link: Callable[[np.ndarray], np.ndarray],
                    run: int = _RUN_ROWS) -> Tuple[float, float, int]:
    """(logloss, mse, rows) of the scores ``link(margin)`` against labels
    {0, 1}, each row weighing 1: ``binomial_metrics``' own expressions, the
    link within, over runs of ``run`` rows, so nothing the size of the rows
    is made. A row whose label or margin is NaN is left out."""
    eps = 1e-15

    def one(a, b):
        y = np.asarray(actual[a:b], dtype=np.float64)
        m = np.asarray(margin[a:b], dtype=np.float64)
        ok = ~(np.isnan(y) | np.isnan(m))
        if not ok.all():
            y, m = y[ok], m[ok]
        p = link(m)
        pc = np.clip(p, eps, 1 - eps)
        return (float(np.sum(-(y * np.log(pc) + (1 - y) * np.log(1 - pc)))),
                float(np.sum((y - p) ** 2)), len(y))

    parts = _in_runs(one, len(actual), run)
    n = sum(k for _, _, k in parts)
    if not n:
        return float("nan"), float("nan"), 0
    return (float(np.sum([ll for ll, _, _ in parts]) / n),
            float(np.sum([se for _, se, _ in parts]) / n), n)


def roc_area_counts(tps: np.ndarray, fps: np.ndarray, run: int = _RUN_ROWS) -> int:
    """Twice the area under the ROC's trapezoids times P x N, as the exact
    integer it is: over the thresholds, (fp - the fp before) x (tp + the tp
    before), from cumulative counts (under 2^31). A term can pass 2^53, so
    tp + tp is taken in two 16-bit halves, whose sums int64 holds (the fp
    differences sum to N)."""

    def one(a, b):
        tp = np.asarray(tps[max(a - 1, 0):b]).astype(np.int64)
        fp = np.asarray(fps[max(a - 1, 0):b]).astype(np.int64)
        if a == 0:
            tp, fp = np.concatenate([[0], tp]), np.concatenate([[0], fp])
        d, s = np.diff(fp), tp[1:] + tp[:-1]
        return int(np.sum(d * (s & 0xFFFF))), int(np.sum(d * (s >> 16)))

    parts = _in_runs(one, len(tps), run)
    return sum(lo for lo, _ in parts) + (sum(hi for _, hi in parts) << 16)


@functools.partial(jax.jit, static_argnames=("mesh", "piece"))
def _margin_order(margin, y, valid, *, mesh, piece):
    """The order of a binomial margin and the positives down it, for
    ``MarginRoc``: one program a (row count, mesh), every shape static.

    margin [n, 1] float32, y [n] (0 / 1), valid [n] bool, dealt over
    ``mesh`` by rows or whole on its one device; they are gathered first, so
    every device sorts the whole (9 bytes a row: 288 MB at 32M rows).
    Returns ``(counts, pieces)``: counts = (rows counted, positives among
    them) int32, and per piece of ``piece`` rows (margin float32, positives
    at or above it int32), descending by margin (-0.0 under 0.0), the rows
    counted first and what follows them to be ignored."""
    whole = NamedSharding(mesh, PartitionSpec())
    m, y, valid = (lax.with_sharding_constraint(a, whole)
                   for a in (margin[:, 0], y, valid))
    ok = valid & ~jnp.isnan(m)
    # an int32 that ascends with the float, turned over: ascending keys are
    # descending margins, dropped rows and padding last (no float's key is
    # INT_MAX but a NaN's). Rows of one margin need no order among them
    bits = lax.bitcast_convert_type(m, jnp.int32)
    key = jnp.where(ok, ~jnp.where(bits < 0, bits ^ _I32_MAX, bits), _I32_MAX)
    key, pos = lax.sort((key, ((y > 0.5) & ok).astype(jnp.int32)),
                        num_keys=1, is_stable=False)
    tp = jnp.cumsum(pos, dtype=jnp.int32)
    turned = ~key
    score = lax.bitcast_convert_type(
        jnp.where(turned < 0, turned ^ _I32_MAX, turned), jnp.float32)
    counts = jnp.stack([jnp.sum(ok, dtype=jnp.int32), tp[-1]])
    pieces = tuple((score[a:a + piece], tp[a:a + piece])
                   for a in range(0, m.shape[0], piece))
    return counts, pieces


class MarginRoc:
    """``binomial_metrics`` of a margin that is on the device, for rows that
    all weigh 1. Creating it starts the device's part and returns (dispatch
    is asynchronous): one sort by margin and the count of positives down the
    order. ``metrics`` waits for it, fetches (margin, positives at or above
    it) a row and reads every number from those counts in runs of rows: a
    threshold a distinct score (the link in float64), the ROC's area as an
    integer, the max-F1 scan, PR-AUC and the confusion matrix in float64.
    Logloss and mse come from the host's copy of the margin
    (``binomial_losses``), which the host can add meanwhile."""

    def __init__(self, margin, y, valid, mesh, piece: int = _ROC_PIECE):
        self.t0 = time.perf_counter()
        self._out = _margin_order(margin, y, valid, mesh=mesh, piece=piece)
        self._piece = piece
        #: seconds from the dispatch until the host found the counts ready,
        #: and the thresholds (distinct scores) read from them
        self.device_s = self.distinct = None

    def _fetch(self) -> Tuple[np.ndarray, np.ndarray, int]:
        """(margin, positives at or above it) of the rows counted, as host
        arrays in the device's order, and the positives in all; drops the
        device's arrays."""
        counts, pieces = self._out
        self._out = None
        nobs, npos = (int(v) for v in jax.device_get(counts))
        self.device_s = time.perf_counter() - self.t0
        pieces = pieces[:-(-nobs // self._piece)]
        for arrays in pieces:
            for a in arrays:
                a.copy_to_host_async()
        score, tp = np.empty(nobs, np.float32), np.empty(nobs, np.int32)
        for i, arrays in enumerate(pieces):
            a = i * self._piece
            for col, dev in zip((score, tp), arrays):
                col[a:a + self._piece] = np.asarray(dev)[:nobs - a]
        return score, tp, npos

    def metrics(self, losses: Tuple[float, float, int],
                link: Callable[[np.ndarray], np.ndarray],
                run: int = _RUN_ROWS) -> BinomialMetrics:
        """The metrics; ``losses`` is ``binomial_losses`` of the same rows,
        ``link`` what turns a margin into the score of class 1."""
        score, tp, npos = self._fetch()
        nobs = len(score)
        logloss, mse, counted = losses
        if counted != nobs:
            raise ValueError(f"the device counted {nobs} rows of the margin, "
                             f"the host's copy holds {counted}")
        P, N = float(npos), float(nobs - npos)

        def thresholds(a, b):
            """The last row of every run of one SCORE in [a, b): the host
            tells rows apart by their score, so margins that the link gives
            one score (it saturates; -0.0 and 0.0) are one threshold."""
            p = link(score[a:min(b + 1, nobs)].astype(np.float64))
            last = np.ones(b - a, bool)
            last[:len(p) - 1] = p[:-1] != p[1:]
            at = np.flatnonzero(last)
            tps = tp[a:b][at].astype(np.float64)
            return p[at], tps, (at + (a + 1)) - tps

        parts = _in_runs(thresholds, nobs, run)
        ths, tps, fps = (np.concatenate([part[i] for part in parts]) if parts
                         else np.empty(0) for i in range(3))
        k = self.distinct = len(ths)
        both = P > 0 and N > 0

        def scan(a, b):
            """A run's best F1 and where, and its part of PR-AUC's
            trapezoids: ``binomial_metrics``' expressions, with the
            threshold before the run beside it."""
            lo = max(a - 1, 0)
            t, f = tps[lo:b], fps[lo:b]
            prec = t / np.maximum(t + f, 1e-300)
            rec = t / P
            f1 = np.where(prec + rec > 0,
                          2 * prec * rec / np.maximum(prec + rec, 1e-300), 0.0)[a - lo:]
            best = int(np.argmax(f1))
            if a == 0:
                prec, rec = np.concatenate([[prec[0]], prec]), np.concatenate([[0.0], rec])
            return (float(f1[best]), a + best,
                    float(np.sum(np.diff(rec) * (prec[1:] + prec[:-1]) / 2.0)))

        if both:
            parts = _in_runs(scan, k, run)
            auc = roc_area_counts(tps, fps, run) / (2 * npos * (nobs - npos))
            pr = float(np.sum([part[2] for part in parts]))
            # the first of the best, as argmax over all of them finds it
            best = max(parts, key=lambda part: part[0])[1]
            thr = float(ths[best])
            cm = ConfusionMatrix(tn=N - fps[best], fp=fps[best], fn=P - tps[best],
                                 tp=tps[best], threshold=thr)
        else:
            auc = pr = float("nan")
            thr = 0.5
            cm = _cm_at(ths, tps, fps, P, N, thr) if k else ConfusionMatrix(N, 0, P, 0, thr)
        tpr_ = cm.tp / P if P else float("nan")
        tnr_ = cm.tn / N if N else float("nan")
        m = BinomialMetrics(
            auc=auc, pr_auc=pr, gini=2 * auc - 1 if auc == auc else float("nan"),
            logloss=logloss, mse=mse, rmse=float(np.sqrt(mse)),
            mean_per_class_error=float(1 - (tpr_ + tnr_) / 2),
            max_f1_threshold=thr, cm=cm, nobs=nobs,
            thresholds=ths, tps=tps, fps=fps,
        )
        m._p, m._n = P, N
        return m


# ---------------------------------------------------------------------------
# multinomial


@dataclass
class MultinomialMetrics:
    logloss: float
    mse: float
    rmse: float
    mean_per_class_error: float
    confusion_matrix: np.ndarray
    hit_ratios: np.ndarray  # top-k hit ratio, k=1..K (hex/HitRatio semantics)
    domain: List[str]
    nobs: int

    def __repr__(self) -> str:
        return (
            f"MultinomialMetrics(logloss={self.logloss:.6f}, "
            f"mean_per_class_error={self.mean_per_class_error:.4f}, "
            f"top1={self.hit_ratios[0]:.4f})"
        )


def multinomial_metrics(
    actual: np.ndarray,
    probs: np.ndarray,
    domain: List[str],
    weights: Optional[np.ndarray] = None,
    max_hit_ratio_k: int = 10,
) -> MultinomialMetrics:
    """actual: int class ids [N]; probs: [N, K] class probabilities."""
    y = np.asarray(actual)
    P = np.asarray(probs, dtype=np.float64)
    ok = y >= 0
    if not ok.all():  # rows with no response
        y, P = y[ok], P[ok]
        weights = None if weights is None else np.asarray(weights)[ok]
    y = y.astype(np.int64)
    w, wsum = _weighted(y.astype(np.float64), None if weights is None else np.asarray(weights))
    K = P.shape[1]
    eps = 1e-15
    at = (np.arange(len(y)), y)
    py = P[at]
    logloss = float(np.sum(w * -np.log(np.clip(py, eps, 1.0))) / wsum)
    # MSE over the 1-of-K residual (reference ModelMetricsMultinomial)
    sq = P.copy()
    sq[at] -= 1.0
    sq *= sq
    sq *= w[:, None]
    mse = float(np.sum(sq) / wsum)
    pred = P.argmax(axis=1)
    cm = np.bincount(y * K + pred, weights=w, minlength=K * K).reshape(K, K)
    row = cm.sum(axis=1)
    per_class_err = np.where(row > 0, 1 - np.diag(cm) / np.maximum(row, 1e-300), np.nan)
    mpce = float(np.nanmean(per_class_err))
    # top-k hit ratios: the true class's place in the order of -P, ties by
    # class index (a stable sort's)
    kk = min(max_hit_ratio_k, K)
    rank = (P > py[:, None]).sum(axis=1) + (
        (P == py[:, None]) & (np.arange(K) < y[:, None])).sum(axis=1)
    hr = np.bincount(rank, weights=w, minlength=K)[:kk]
    hit_ratios = np.cumsum(hr) / wsum
    return MultinomialMetrics(
        logloss=logloss,
        mse=mse,
        rmse=float(np.sqrt(mse)),
        mean_per_class_error=mpce,
        confusion_matrix=cm,
        hit_ratios=hit_ratios,
        domain=list(domain),
        nobs=int(len(y)),
    )


# ---------------------------------------------------------------------------
# early stopping — exact ScoreKeeper.stopEarly semantics


#: metrics where larger is better (ScoreKeeper.StoppingMetric convergence strategies)
MORE_IS_BETTER = {"auc", "pr_auc", "r2", "accuracy", "f1", "lift_top_group"}
#: metrics bounded below by 0 (ScoreKeeper IStoppingMetric.isLowerBoundBy0)
LOWER_BOUND_0 = {"deviance", "logloss", "mse", "rmse", "mae", "rmsle", "misclassification", "anomaly_score"}


def stop_early(
    history: List[float],
    stopping_rounds: int,
    more_is_better: bool,
    stopping_tolerance: float,
) -> bool:
    """Replicates hex/ScoreKeeper.stopEarly (ScoreKeeper.java:261-337):
    k+1 simple moving averages of window k over the last 2k scoring events
    (skipping the first event); converged when the best of the k new averages
    fails to improve on the reference average by rel tolerance."""
    k = stopping_rounds
    if k == 0:
        return False
    if len(history) - 1 < 2 * k:
        return False
    vals = np.asarray(history, dtype=np.float64)
    mov = np.empty(k + 1)
    for i in range(k + 1):
        start = len(vals) - 2 * k + i
        mov[i] = vals[start : start + k].mean()
        if np.isnan(mov[i]):
            return False
    last_before = mov[0]
    min_in, max_in = mov[1:].min(), mov[1:].max()
    if not more_is_better and last_before == 0.0:
        return True  # converged to lower bound
    if np.sign(mov.max()) != np.sign(mov.min()):
        return False  # zero crossing — don't divide
    if more_is_better:
        ratio = max_in / last_before
        return bool(not np.isnan(ratio) and ratio <= 1 + stopping_tolerance)
    ratio = min_in / last_before
    return bool(not np.isnan(ratio) and ratio >= 1 - stopping_tolerance)


# ---------------------------------------------------------------------------
# DKV-resident scoring records + makeMetrics


@dataclass
class ScoringRecord:
    """A cached scoring result, queryable over REST.

    Reference: ``hex/ModelMetrics.java`` ``buildKey``/``getFromDKV`` —
    scoring a frame with a model leaves a ModelMetrics object in the DKV
    keyed by (model, frame), which the 10 /3/ModelMetrics routes fetch,
    filter and delete."""

    model_id: str
    frame_id: str
    metrics: object
    model_category: str
    scoring_time: float

    @staticmethod
    def key_for(model_id: str, frame_id: str) -> str:
        return f"modelmetrics_{model_id}@{frame_id}"


def make_metrics(
    predictions: np.ndarray,
    actuals: np.ndarray,
    domain: Optional[List[str]] = None,
    distribution: str = "gaussian",
    weights: Optional[np.ndarray] = None,
):
    """Build metrics from raw predictions + actuals with no model.

    Reference: ``ModelMetricsHandler.make`` (the ``h2o.make_metrics``
    client call): a domain means classification (binomial for 2 levels,
    multinomial above), otherwise regression under ``distribution``.

    ``predictions`` column conventions match the reference's: regression
    takes one column; binomial takes p1 directly, [p0 p1], or
    [predict p0 p1] (the extra leading column is the label and is
    dropped); multinomial likewise K or 1+K columns.
    """
    P = np.asarray(predictions, dtype=np.float64)
    if P.ndim == 1:
        P = P[:, None]
    if domain is None:
        if P.shape[1] != 1:
            raise ValueError(
                f"regression expects 1 prediction column, got {P.shape[1]}")
        y = np.asarray(actuals, dtype=np.float64)
        dev = None
        if distribution and distribution != "gaussian":
            from h2o3_tpu.models.glm import GLMParameters, deviance

            dev = deviance(distribution, y, P[:, 0],
                           GLMParameters(response_column=""))
        return regression_metrics(y, P[:, 0], weights=weights, deviance=dev)
    K = len(domain)
    if K == 2:
        if P.shape[1] == 1:
            p1 = P[:, 0]
        elif P.shape[1] == 2:
            p1 = P[:, 1]
        elif P.shape[1] == 3:
            p1 = P[:, 2]
        else:
            raise ValueError(
                f"binomial expects 1, 2 or 3 prediction columns, got {P.shape[1]}")
        return binomial_metrics(np.asarray(actuals, dtype=np.float64), p1,
                                weights=weights)
    if P.shape[1] == K + 1:
        P = P[:, 1:]
    if P.shape[1] != K:
        raise ValueError(
            f"multinomial expects {K} or {K + 1} prediction columns, "
            f"got {P.shape[1]}")
    return multinomial_metrics(np.asarray(actuals).astype(np.int64), P,
                               domain, weights=weights)
