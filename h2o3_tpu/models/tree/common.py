"""Shared plumbing for tree models: matrices, distributions, monitors.

Reference: trees consume raw (non-standardized) predictors with categorical
codes; ``hex/tree/SharedTree.java`` + ``hex/DataInfo`` handle the layout and
``hex/Distribution.java`` the gradient families. Categorical handling
(``categorical_encoding``):

* ``"enum"`` — the reference's own handling (``hex/tree/DTree.java``): a
  categorical column is binned a bin a level (at most ``nbins_cats``
  levels; more are refused) and a node splits it on a SET of its levels,
  the best prefix of the levels ordered by Σg/(Σh+λ); NA, a level the node
  had no row of and a level unseen at fit follow the split's NA side;
* ``"label_encoder"`` — level codes as ordinals: quantile-binned like a
  numeric column and split by a threshold on the code;
* ``"one_hot_explicit"`` — one indicator feature a level (``hex/DataInfo``
  OneHotExplicit);
* ``"auto"`` — ``label_encoder`` (H2O-3's ``auto`` is ``enum`` for GBM and
  DRF; here the exporters and explainers that read a split as a threshold
  do not carry a set yet, so ``auto`` keeps to what they can read).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

from h2o3_tpu.frame.frame import ColType, Frame
from h2o3_tpu.models.data_info import DataInfo, _align_codes, build_data_info
from h2o3_tpu.models.framework import Model
from h2o3_tpu.models import metrics as M
from h2o3_tpu.util import telemetry
from h2o3_tpu.util.telemetry import Span

TRAIN_METRICS = telemetry.counter(
    "tree_train_metrics_total",
    "metrics of a tree model over a frame, by where the margin came from: "
    "the fit's own final margin of its training frame, ordered and counted "
    "on the host (fit_margin) or on the device where it lives "
    "(fit_margin_device), or a walk of the trees over the frame (walk)",
    labels=("source",),
)

ENTRY_MATRIX = telemetry.counter(
    "tree_entry_matrix_total",
    "tree fits by whether their training rows were built as a float matrix: "
    "never, the bin codes being resident on the device (resident), or for "
    "the codes' placement or a checkpoint's margin (built)",
    labels=("path",),
)


def tree_data_info(frame: Frame, y: str, ignored=()) -> DataInfo:
    """Layout for tree models: raw numerics, and one column of level codes
    a categorical (``tree_matrix`` expands it under ``one_hot_explicit``;
    whether the codes are split as ordinals or as sets is
    ``resolve_tree_encoding``'s word).  Under the span ``data_info``: a
    frame's first fit pays here for its numeric columns' rollups and each
    categorical column's most frequent level, a pass over every row."""
    with Span("data_info", rows=frame.nrows) as span:
        info = build_data_info(
            frame, y=y, ignored=ignored, standardize=False, use_all_factor_levels=True
        )
        span.set(cat_columns=len(info.cat_domains))
    return info


TREE_ENCODINGS = ("auto", "enum", "label_encoder", "one_hot_explicit")


#: H2O's default cap on the bins of a categorical column
NBINS_CATS = 1024

#: counts that a tree fit's spans state and its ``fit_profile`` sums (the
#: tree builders' ``profile_counts``): split nodes read back and how many
#: test a set of levels (``tree_readback``), the categorical features binned
#: a level a bin (``make_bins``), scoring walks by sets and their chunks
#: (``score_traverse``), metrics from the margin a fit held
#: (``model_performance``); and, kept as stated and not summed, what every
#: level of a block launches: ``hist_slots``, one ``[nodes built, node slots
#: launched, kernel]`` a level (``tree_block``; ``booster.level_plan``); on a
#: mesh of several devices the bytes a shard of what was uploaded or read
#: back (``bins_upload``, ``state_upload``, ``margin_readback``) and the bytes
#: a device handed to the levels' psums (``tree_block``); whether the fit's
#: training rows were built as a float matrix (``train_boosted``:
#: ``matrix_built`` or ``matrix_resident``, ``TreeRows.count``); the row
#: gathers a frontier level makes (``tree_block`` of a deep tree, summed over
#: the blocks); the histogram kernel launches of a block (``tree_block``'s
#: ``hist_launches``: a level of K class trees is one while one kernel holds
#: its class x node slots)
SPAN_COUNTS = ("splits", "set_splits", "cat_features", "sets", "chunks",
               "fit_margin", "fit_margin_device", "hist_slots",
               "bytes_per_shard", "bytes_psummed", "matrix_built",
               "matrix_resident", "frontier_row_gathers", "hist_launches")


def resolve_tree_encoding(categorical_encoding: str) -> str:
    """Map the categorical_encoding param to how a tree treats a
    categorical column: ``enum`` (a bin a level, set-valued splits),
    ``label_encoder`` (ordinal codes, threshold splits; also what ``auto``
    means) or ``one_hot_explicit``."""
    if categorical_encoding in ("auto", "label_encoder"):
        return "label_encoder"
    if categorical_encoding in ("enum", "one_hot_explicit"):
        return categorical_encoding
    raise ValueError(
        f"categorical_encoding {categorical_encoding!r} not supported for "
        f"tree models; choose from {TREE_ENCODINGS}"
    )


def tree_feature_names(info: DataInfo, encoding: str = "label_encoder") -> List[str]:
    """Feature names in tree_matrix column order (one-hot expands levels)."""
    names: List[str] = []
    for name in info.predictor_names:
        if encoding == "one_hot_explicit" and name in info.cat_domains:
            names += [f"{name}.{lv}" for lv in info.cat_domains[name]]
        else:
            names.append(name)
    return names


def tree_cat_levels(info: DataInfo, encoding: str,
                    nbins_cats: int = NBINS_CATS) -> Tuple[int, ...]:
    """Per tree feature, the levels of a categorical that splits on sets of
    them and 0 for any other feature (``TreeParams.cat_levels``); () where
    the encoding is not ``enum`` or no predictor is categorical. A column
    with more levels than ``nbins_cats`` is refused: the reference groups
    levels into bins there, and this build does not guess how."""
    if encoding != "enum" or not info.cat_domains:
        return ()
    levels = tuple(len(info.cat_domains.get(name, ()))
                   for name in info.predictor_names)
    for name, n in zip(info.predictor_names, levels):
        if n > nbins_cats:
            raise ValueError(
                f"categorical column {name!r} has {n} levels, more than "
                f"nbins_cats={nbins_cats}: categorical_encoding='enum' "
                "bins a level a bin and does not group levels; raise "
                "nbins_cats or choose another categorical_encoding")
    return levels if any(levels) else ()


def tree_matrix(
    info: DataInfo, frame: Frame, encoding: str = "label_encoder"
) -> np.ndarray:
    """[N, F] float32 raw-feature matrix; NaN for NA.

    label_encoder, enum: cat codes (one column per predictor; a level the
    training domain lacks is NaN).
    one_hot_explicit: one 0/1 column per level; an NA row is NaN across the
    whole block so NA routing still learns a default direction per split.
    """
    with Span("tree_matrix", rows=frame.nrows) as span:
        X = _feature_rows(info, frame, encoding)
        span.set(features=X.shape[1])
    return X


def _feature_rows(info: DataInfo, frame: Frame, encoding: str,
                  rows: Optional[np.ndarray] = None) -> np.ndarray:
    """``tree_matrix``'s conversion of the frame rows at ``rows`` (every row
    where None): each column is selected first and converted after, and
    every conversion is elementwise, so a row reads the same bits either
    way."""
    cols = []
    for name in info.predictor_names:
        col = frame.col(name)
        if name in info.cat_domains:
            codes = _align_codes(col, info.cat_domains[name])
            if rows is not None:
                codes = codes[rows]
            if encoding == "one_hot_explicit":
                dom = info.cat_domains[name]
                block = (codes[:, None] == np.arange(len(dom))[None, :]).astype(
                    np.float32
                )
                block[codes < 0] = np.nan
                cols.append(block)
            else:
                cols.append(
                    np.where(codes >= 0, codes.astype(np.float32), np.nan)[:, None]
                )
        else:
            values = col.numeric_view()
            if rows is not None:
                values = values[rows]
            cols.append(values.astype(np.float32)[:, None])
    return np.concatenate(cols, axis=1)


class TreeRows:
    """A fit's training rows as a deferred ``[N, F]`` float32 matrix: the
    rows of ``frame`` that ``keep`` marks, in ``tree_matrix``'s layout.

    A fit whose bin codes are resident on the device (``frame/devcache``)
    reads only the rows its quantile sketch samples (:meth:`rows`); the
    whole matrix is built (:meth:`materialize`) only where every row is
    read: the codes' placement on a cache miss, a checkpoint's margin."""

    def __init__(self, info: DataInfo, frame: Frame, encoding: str,
                 keep: np.ndarray) -> None:
        self.info, self.frame, self.encoding, self.keep = info, frame, encoding, keep
        self.shape = (int(keep.sum()),
                      len(tree_feature_names(info, encoding)))
        self._X: Optional[np.ndarray] = None

    def rows(self, idx: Optional[np.ndarray] = None) -> np.ndarray:
        """float32 ``[len(idx), F]`` of the kept rows at ``idx`` (every
        kept row where None), equal bit for bit to those rows of
        :meth:`materialize`."""
        if self.shape[0] < self.keep.size:
            where = np.flatnonzero(self.keep)
            idx = where if idx is None else where[idx]
        return _feature_rows(self.info, self.frame, self.encoding, idx)

    def materialize(self) -> np.ndarray:
        """``tree_matrix(...)[keep]``, built once; no copy where ``keep``
        drops no row."""
        if self._X is None:
            X = tree_matrix(self.info, self.frame, encoding=self.encoding)
            with Span("tree_rows") as span:
                if self.shape[0] < self.keep.size:
                    X = X[self.keep]
                span.set(rows=X.shape[0], dropped=int(self.keep.size - X.shape[0]))
            self._X = X
        return self._X

    def count(self) -> str:
        """Count the fit in ``tree_entry_matrix_total`` and name its path:
        ``built`` where the matrix was materialized, else ``resident``."""
        path = "resident" if self._X is None else "built"
        ENTRY_MATRIX.inc(path=path)
        return path


# -- distributions (hex/Distribution.java gradient/hessian families) ---------


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def softmax(m):
    z = m - m.max(axis=1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=1, keepdims=True)


def grad_hess(distribution: str, y: np.ndarray, margin: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row (g, h) of the loss wrt the margin. y: [N] (codes for classif),
    margin: [N, C]. Returns [N, C] arrays. Host oracle mirroring
    booster.grad_hess_device (parameterized families use 'name:arg')."""
    name, _, arg = distribution.partition(":")
    if name == "custom":
        from h2o3_tpu.udf import get_distribution

        g, h = get_distribution(arg)["grad_hess"](y, margin[:, 0])
        return (np.asarray(g, np.float64)[:, None],
                np.maximum(np.asarray(h, np.float64), 1e-16)[:, None])
    if name == "gaussian":
        g = margin[:, 0] - y
        return g[:, None], np.ones_like(g)[:, None]
    if name == "bernoulli":
        p = sigmoid(margin[:, 0])
        return (p - y)[:, None], np.maximum(p * (1 - p), 1e-16)[:, None]
    if name == "multinomial":
        p = softmax(margin)
        onehot = np.zeros_like(p)
        onehot[np.arange(len(y)), y.astype(np.int64)] = 1.0
        return p - onehot, np.maximum(p * (1 - p), 1e-16)
    if name == "poisson":
        mu = np.exp(margin[:, 0])
        return (mu - y)[:, None], np.maximum(mu, 1e-16)[:, None]
    if name == "gamma":
        ymf = y * np.exp(-margin[:, 0])
        return (1.0 - ymf)[:, None], np.maximum(ymf, 1e-16)[:, None]
    if name == "tweedie":
        pw = float(arg)
        a = y * np.exp((1.0 - pw) * margin[:, 0])
        b = np.exp((2.0 - pw) * margin[:, 0])
        return (b - a)[:, None], np.maximum((pw - 1) * a + (2 - pw) * b, 1e-16)[:, None]
    if name == "huber":
        delta = float(arg)
        r = margin[:, 0] - y
        return np.clip(r, -delta, delta)[:, None], np.ones_like(r)[:, None]
    if name == "laplace":
        g = np.sign(margin[:, 0] - y)
        return g[:, None], np.ones_like(g)[:, None]
    if name == "quantile" or distribution == "quantile_0.5":
        alpha = float(arg) if arg else 0.5
        g = np.where(margin[:, 0] < y, -alpha, 1.0 - alpha)
        return g[:, None], np.ones_like(g)[:, None]
    raise ValueError(f"unknown distribution {distribution!r}")


def _wmean(y: np.ndarray, w: Optional[np.ndarray]) -> float:
    if w is None:
        return float(np.nanmean(y))
    m = ~np.isnan(y)
    return float(np.average(y[m], weights=w[m]))


def _family_param(params, field: str, distribution: str) -> float:
    """A family parameter must exist on the builder's Parameters dataclass —
    a builder that lists a distribution but lacks its parameter would
    otherwise silently train with a hardcoded default (the
    accepted-and-ignored failure mode the param guard exists to prevent)."""
    val = getattr(params, field, None)
    if val is None:
        raise ValueError(
            f"distribution {distribution!r} needs parameter {field!r}, which "
            f"{type(params).__name__} does not declare"
        )
    return float(val)


def resolve_objective(distribution: str, params, y: np.ndarray) -> str:
    """Builder distribution name -> booster objective string, folding the
    family parameter in (``hex/Distribution.java``'s per-family params).
    huber: delta is the huber_alpha quantile of |y - median(y)| residuals
    (the reference re-estimates it per iteration; fixed-at-init here)."""
    if distribution.partition(":")[0] == "custom":
        from h2o3_tpu.udf import get_distribution

        name = distribution.partition(":")[2]
        if not name:
            raise ValueError(
                "custom distribution needs a name: 'custom:<registered>'")
        get_distribution(name)  # unregistered name fails HERE, not mid-train
        return distribution
    if distribution == "gamma":
        # gamma deviance needs strictly positive y (zero rows give ~0
        # hessians and exploding leaves; the reference validates this too)
        if np.nanmin(y) <= 0:
            raise ValueError("gamma requires a strictly positive response")
    elif distribution in ("poisson", "tweedie"):
        if np.nanmin(y) < 0:
            raise ValueError(f"{distribution} requires a non-negative response")
    if distribution == "tweedie":
        pw = _family_param(params, "tweedie_power", distribution)
        if not 1.0 < pw < 2.0:
            raise ValueError(f"tweedie_power must be in (1, 2), got {pw}")
        return f"tweedie:{pw}"
    if distribution == "quantile":
        alpha = _family_param(params, "quantile_alpha", distribution)
        if not 0.0 < alpha < 1.0:
            raise ValueError(f"quantile_alpha must be in (0, 1), got {alpha}")
        return f"quantile:{alpha}"
    if distribution == "huber":
        ha = _family_param(params, "huber_alpha", distribution)
        r = np.abs(y - np.nanmedian(y))
        delta = max(float(np.nanquantile(r, ha)), 1e-10)
        return f"huber:{delta:.8g}"
    return distribution


def init_margin(
    distribution: str, y: np.ndarray, nclasses: int,
    weights: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Initial margin f0 (SharedTree init: response moments / priors),
    weighted when an observation-weights column is in play."""
    name, _, arg = distribution.partition(":")
    if name == "custom":
        from h2o3_tpu.udf import get_distribution

        init = get_distribution(arg)["init"]
        return np.array([float(init(y, weights)) if init is not None
                         else _wmean(y, weights)])
    if name in ("gaussian", "huber"):
        return np.array([_wmean(y, weights)])
    if name == "bernoulli":
        p = _wmean(y, weights)
        p = min(max(p, 1e-10), 1 - 1e-10)
        return np.array([np.log(p / (1 - p))])
    if name == "multinomial":
        m = ~np.isnan(y)
        w = weights[m] if weights is not None else None
        pri = np.bincount(
            y[m].astype(np.int64), weights=w, minlength=nclasses
        ).astype(np.float64)
        pri = np.maximum(pri / pri.sum(), 1e-10)
        return np.log(pri)
    if name in ("poisson", "gamma", "tweedie"):
        return np.array([np.log(max(_wmean(y, weights), 1e-10))])
    if name == "laplace" or distribution == "quantile_0.5":
        return np.array([float(np.nanmedian(y))])
    if name == "quantile":
        return np.array([float(np.nanquantile(y, float(arg)))])
    raise ValueError(f"unknown distribution {distribution!r}")


def margin_to_probs(distribution: str, margin: np.ndarray) -> np.ndarray:
    if distribution == "bernoulli":
        p = sigmoid(margin[:, 0])
        return np.stack([1 - p, p], axis=1)
    if distribution == "multinomial":
        return softmax(margin)
    return margin  # regression: identity


def link_inverse(distribution: str, margin: np.ndarray) -> np.ndarray:
    """Regression margin -> response scale (Distribution.linkInv): the
    log-link families train on log(mu), predictions report mu."""
    name, _, arg = distribution.partition(":")
    if name == "custom":
        from h2o3_tpu.udf import get_distribution

        inv = get_distribution(arg)["link_inv"]
        return np.asarray(inv(margin), np.float64) if inv is not None \
            else margin
    if name in ("poisson", "gamma", "tweedie"):
        return np.exp(margin)
    return margin


def auto_distribution(nclasses: int) -> str:
    if nclasses == 2:
        return "bernoulli"
    if nclasses > 2:
        return "multinomial"
    return "gaussian"


def training_score(
    distribution: str, y: np.ndarray, margin: np.ndarray,
    weights: Optional[np.ndarray] = None,
) -> float:
    """Scalar stopping metric from the current margin (deviance-flavored,
    weighted mean when observation weights are in play)."""

    def wavg(v):
        return float(np.average(v, weights=weights))

    name, _, arg = distribution.partition(":")
    if name == "bernoulli":
        p = np.clip(sigmoid(margin[:, 0]), 1e-15, 1 - 1e-15)
        return wavg(-(y * np.log(p) + (1 - y) * np.log(1 - p)))
    if name == "multinomial":
        p = softmax(margin)
        return wavg(-np.log(np.clip(p[np.arange(len(y)), y.astype(np.int64)], 1e-15, 1)))
    if name == "poisson":
        mu = np.exp(margin[:, 0])
        return wavg(2 * (np.where(y > 0, y * np.log(np.where(y > 0, y, 1) / mu), 0) - (y - mu)))
    if name == "gamma":
        mu = np.maximum(np.exp(margin[:, 0]), 1e-15)
        ys = np.maximum(y, 1e-15)
        return wavg(2 * (ys / mu - np.log(ys / mu) - 1))
    if name == "tweedie":
        pw = float(arg)
        mu = np.maximum(np.exp(margin[:, 0]), 1e-15)
        return wavg(
            2 * (
                np.power(np.maximum(y, 0), 2 - pw) / ((1 - pw) * (2 - pw))
                - y * np.power(mu, 1 - pw) / (1 - pw)
                + np.power(mu, 2 - pw) / (2 - pw)
            )
        )
    if name == "huber":
        delta = float(arg)
        r = np.abs(margin[:, 0] - y)
        return wavg(np.where(r <= delta, 0.5 * r * r, delta * (r - 0.5 * delta)))
    if name == "laplace":
        return wavg(np.abs(margin[:, 0] - y))
    if name == "quantile" or distribution == "quantile_0.5":
        alpha = float(arg) if arg else 0.5
        r = y - margin[:, 0]
        return wavg(np.where(r >= 0, alpha * r, (alpha - 1) * r))
    return wavg((margin[:, 0] - y) ** 2)


def tree_cache_token(frame: Frame, p, encoding: str):
    """Devcache identity of a booster's bin-code placement.

    The binned matrix is a pure function of (frame column versions, the
    categorical encoding, and the params that shape X / the keep mask:
    ignored + response + weights + offset columns) — algo-independent, so
    GBM/DRF/XGBoost fits on the same frame + binning spec share one entry.
    Returns None (cache bypass) for frames without version stamps."""
    from h2o3_tpu.frame import devcache

    if (getattr(frame, "chunk_layout", None) is not None
            and getattr(frame, "_materialized", None) is None):
        # chunk-homed frame, rows still on their homes: the layout stamp
        # identifies the distributed data state (chunks are immutable DKV
        # puts under (frame_key, stamp) keys; remove/rekey evicts via the
        # frame-key link) — the same identity the per-home bind cache
        # keys on, without materializing chunks just to stamp versions.
        # Once materialized, resident columns carry versions; use those
        # so caller-side mutations invalidate as usual.
        lay = frame.chunk_layout
        tok = ("dist", lay["frame_key"], lay["stamp"],
               int(lay["espc"][-1]))
    else:
        tok = devcache.frame_token(frame)
    if tok is None:
        return None
    return (
        tok, encoding, tuple(p.ignored_columns), p.response_column,
        getattr(p, "weights_column", None),
        getattr(p, "offset_column", None),
    )


def training_rows(frame: Frame, p, info: DataInfo, encoding: str,
                  y: np.ndarray, use_offset: bool = False):
    """The rows a tree fit keeps (a row with an NA response, a zero or NA
    weight or an NA offset is dropped) as deferred :class:`TreeRows`, and
    the response, weights and offset of those rows: (X, y, weights,
    offset). Nothing here reads a predictor column."""
    keep = ~np.isnan(y)
    weights = None
    if p.weights_column:
        weights = frame.col(p.weights_column).numeric_view().astype(np.float64)
        if np.nanmin(weights) < 0:
            raise ValueError("weights_column must be non-negative")
        # dropping a zero-weight row is the reference's zero contribution
        keep &= ~np.isnan(weights) & (weights > 0)
    offset = None
    if use_offset and p.offset_column:
        offset = frame.col(p.offset_column).numeric_view().astype(np.float64)
        keep &= ~np.isnan(offset)
    X = TreeRows(info, frame, encoding, keep)
    if X.shape[0] < keep.size:
        y = y[keep]
        weights = weights[keep] if weights is not None else None
        offset = offset[keep] if offset is not None else None
    return X, y, weights, offset


def tree_fit_setup(frame: Frame, p, model_cls, use_offset: bool):
    """Shared GBM/XGBoost front half of _fit: layout, kept rows, aux
    columns, objective resolution, init margin, monotone validation.

    Returns (model, X, y, weights, offset, objective, f0, n_class_trees,
    mono) with the keep mask (NA response / zero-weight / NA-offset rows)
    already applied to y/weights/offset; X is those rows as deferred
    :class:`TreeRows`, built as a float matrix only where the booster must
    read every row (``booster._train_boosted``)."""
    from h2o3_tpu.models.data_info import response_vector

    if getattr(frame, "chunk_layout", None) is not None:
        from h2o3_tpu.models.tree import dist_hist

        enc = resolve_tree_encoding(
            getattr(p, "categorical_encoding", "auto"))
        if dist_hist.use_dist(frame, p, enc):
            # chunk-homed frame + map-side engine eligible: rows stay on
            # their homes, only sketches/aux vectors gather once
            return dist_hist.dist_fit_setup(frame, p, model_cls, use_offset)
        # ineligible combination (knob off, checkpoint, monotone, custom
        # objective, explicit one-hot): materialize and run the legacy path

    with Span("tree_setup") as span:
        ignored = list(p.ignored_columns)
        aux_cols = [p.weights_column] + ([p.offset_column] if use_offset else [])
        for aux in aux_cols:
            if aux and aux not in ignored:
                ignored.append(aux)
        info = tree_data_info(frame, p.response_column, ignored)
        y = response_vector(info, frame)
        nclasses = len(info.response_domain) if info.response_domain else 1
        dist = auto_distribution(nclasses) if p.distribution == "auto" else p.distribution

        model = model_cls(p, info, dist)
        enc = model.tree_encoding
        X, y, weights, offset = training_rows(frame, p, info, enc, y, use_offset)
        span.set(rows=X.shape[0], features=X.shape[1])

        objective = resolve_objective(dist, p, y)
        f0 = init_margin(objective, y, nclasses, weights=weights)
        n_class_trees = nclasses if dist == "multinomial" else 1
        mono = monotone_array(getattr(p, "monotone_constraints", None), info, enc)
        if mono is not None and dist == "multinomial":
            # softmax normalization voids per-margin monotonicity; the
            # reference rejects this combination too (GBM.java validation)
            raise ValueError("monotone_constraints not supported for multinomial")
        return model, X, y, weights, offset, objective, f0, n_class_trees, mono


def make_tree_monitor(model, p, objective, y, weights, history, score=None):
    """ScoreKeeper monitor closure shared by GBM/XGBoost/DRF: wall-clock
    budget (max_runtime_secs) + stopping_rounds early stopping. Returns
    (monitor_or_None, score_interval): when only the deadline is active the
    interval stays at the device block size so the budget check does not
    force a host sync every tree. ``score(margin)`` is the stopping metric
    (``training_score`` of ``objective`` where None); a ``score_tree_interval``
    of 0 (DRF's default) scores every tree block."""
    import time as _time

    from h2o3_tpu.models.tree.booster import tree_block_size

    deadline = (_time.time() + p.max_runtime_secs) if p.max_runtime_secs > 0 else None
    interval = p.score_tree_interval or tree_block_size()
    if score is None:
        def score(margin):
            return training_score(objective, y, margin, weights=weights)

    def monitor(t: int, margin: np.ndarray) -> bool:
        model.ntrees_built = t + 1
        if deadline is not None and _time.time() >= deadline:
            return True
        if p.stopping_rounds <= 0 or (t + 1) % interval:
            return False
        history.append(score(margin))
        model.scoring_history.append({"tree": t + 1, "score": history[-1]})
        return M.stop_early(
            history, p.stopping_rounds, more_is_better=False,
            stopping_tolerance=p.stopping_tolerance,
        )

    if p.stopping_rounds > 0:
        return monitor, interval
    if deadline is not None:
        return monitor, max(interval, tree_block_size())
    return None, interval


def checkpoint_booster(
    p, n_class_trees: int, algo_name: str = None,
    n_features: int = None, encoding: str = None,
):
    """Resolve the ``checkpoint`` param to the prior model's booster
    (checkpoint-continue, ``hex/tree/SharedTree.java:131-136``). The
    reference validates that non-modifiable params match the checkpoint
    (CheckpointUtils); here: same algo, class count, depth, binning, and
    feature layout (count + categorical encoding) — trees from two
    different layouts index features incompatibly."""
    if not p.checkpoint:
        return None
    from h2o3_tpu.keyed import DKV

    prior = DKV.get(p.checkpoint)
    if prior is None:
        raise ValueError(f"checkpoint model {p.checkpoint!r} not found")
    b = getattr(prior, "booster", None)
    if b is None:
        raise ValueError(f"checkpoint model {p.checkpoint!r} is not a tree model")
    if algo_name is not None and getattr(prior, "algo_name", None) != algo_name:
        raise ValueError(
            f"checkpoint model is {getattr(prior, 'algo_name', '?')!r}, "
            f"cannot continue it as {algo_name!r}"
        )
    if b.nclasses_trees != n_class_trees:
        raise ValueError("checkpoint class count differs from this training frame")
    t0 = b.trees_per_class[0]
    if t0.max_depth != p.max_depth:
        raise ValueError(
            f"checkpoint max_depth={t0.max_depth} differs from requested {p.max_depth}"
        )
    from h2o3_tpu.ops.histogram import na_code

    if t0.n_bins1 != na_code(p.nbins, t0.cat_levels) + 1:
        raise ValueError(
            f"checkpoint bin axis of {t0.n_bins1 - 1} (nbins, or the most "
            f"levels of a categorical) differs from requested nbins {p.nbins}"
        )
    if n_features is not None and t0.edges.shape[0] != n_features:
        raise ValueError(
            f"checkpoint was trained on {t0.edges.shape[0]} tree features, "
            f"this frame/encoding produces {n_features}"
        )
    prior_enc = getattr(prior, "tree_encoding", None)
    if encoding is not None and prior_enc is not None and prior_enc != encoding:
        raise ValueError(
            f"checkpoint categorical_encoding={prior_enc!r} differs from "
            f"requested {encoding!r}"
        )
    return b


def extra_trees(p, n_class_trees: int) -> int:
    """Trees still to build on top of the checkpoint; ``ntrees`` is the TOTAL
    (reference: restart validation requires ntrees > checkpoint's)."""
    b = checkpoint_booster(p, n_class_trees)
    if b is None:
        return p.ntrees
    built = b.trees_per_class[0].ntrees
    if p.ntrees <= built:
        raise ValueError(
            f"checkpoint already has {built} trees; ntrees={p.ntrees} must exceed it"
        )
    return p.ntrees - built


def monotone_array(
    constraints: Optional[dict], info: DataInfo, encoding: str
) -> Optional[np.ndarray]:
    """monotone_constraints dict {col: ±1} -> per-tree-feature int array.

    Reference semantics (hex/tree/gbm/GBM.java monotone validation):
    constraints apply to numeric predictors only; unknown columns and
    categorical columns are errors, not silently dropped."""
    if not constraints:
        return None
    names = tree_feature_names(info, encoding)
    arr = np.zeros(len(names), dtype=np.int32)
    for col, direction in constraints.items():
        if direction not in (-1, 0, 1):
            raise ValueError(
                f"monotone_constraints[{col!r}] must be -1, 0 or 1, got {direction!r}"
            )
        if col in info.cat_domains:
            raise ValueError(
                f"monotone_constraints not supported on categorical column {col!r}"
            )
        if col not in names:
            raise ValueError(f"monotone_constraints column {col!r} not in predictors")
        arr[names.index(col)] = direction
    return arr


class TreeModelBase(Model):
    """Common prediction path for GBM/DRF/XGBoost models."""

    cat_levels: Tuple[int, ...] = ()  # a model saved before there were sets

    def __init__(self, params, data_info, distribution: str):
        super().__init__(params, data_info)
        self.distribution = distribution
        self.booster = None  # BoostedTrees
        self.ntrees_built = 0
        self.tree_encoding = resolve_tree_encoding(
            getattr(params, "categorical_encoding", "auto")
        )
        self.cat_levels = tree_cat_levels(
            data_info, self.tree_encoding,
            getattr(params, "nbins_cats", NBINS_CATS))

    def default_threshold(self) -> float:
        """The binomial label threshold of a tree model: not the training
        max-F1 score itself but the middle between it and the next lower
        training score. A training score comes from the margin the fit held
        and ``predict``'s from a walk of the trees (as a MOJO's from its own
        scorer): the same leaves summed in another order, equal to float32
        rounding and not to the bit. So a row that scores what a training
        row scored is labelled as the training metrics counted it, whichever
        way its score was summed."""
        thr = super().default_threshold()
        ths = getattr(self.training_metrics, "thresholds", None)
        if (getattr(self, "_threshold_override", None) is not None
                or ths is None or not len(ths)):
            return thr
        below = int(np.searchsorted(-ths, -thr, side="right"))  # descending
        return 0.5 * (thr + (float(ths[below]) if below < len(ths) else 0.0))

    def _predict_raw(self, frame: Frame) -> np.ndarray:
        X = tree_matrix(self.data_info, frame, encoding=self.tree_encoding)
        margin = self.booster.predict_margin(X)
        # margin to raw scores: the offset, then the inverse link
        with Span("score_link", rows=margin.shape[0]):
            off = getattr(self.params, "offset_column", None)
            if off:
                # Model.score: the offset column of the SCORING frame shifts
                # the margin (hex/Model.java adaptTestForTrain offset handling)
                if off not in frame.names:
                    raise ValueError(
                        f"offset_column {off!r} must be present in the scoring frame"
                    )
                off_vals = frame.col(off).numeric_view()
                if np.isnan(off_vals).any():
                    # match the MOJO scorer: loud, not silently-NaN predictions
                    raise ValueError(
                        f"offset_column {off!r} has NA values in the scoring frame"
                    )
                margin = margin + off_vals[:, None]
            return self._raw_from_margin(margin)

    def _raw_from_margin(self, margin: np.ndarray) -> np.ndarray:
        """Raw scores (probabilities / inverse-linked response) from the
        ensemble margin — shared by the walk of a frame and the metrics
        from the margin a fit holds."""
        return (
            margin_to_probs(self.distribution, margin)
            if self.is_classifier
            else link_inverse(self.distribution, margin[:, 0])
        )

    def model_performance(self, frame: Frame) -> Any:
        """Metrics over ``frame``. The fit's own call for its training frame
        finds that frame's final margins with the ensemble (``fit_eval``:
        over the rows the fit kept, the offset within), scores from them and
        drops them; any other frame, and the training frame once they are
        gone, is binned and walked."""
        ev = getattr(self.booster, "fit_eval", None)
        if ev is None or frame is not ev["frame"]:
            TRAIN_METRICS.inc(source="walk")
            with Span("model_performance", rows=frame.nrows, source="walk"):
                frame = self._apply_preprocessors(frame)
                return self._metrics_from_raw(frame, self._predict_raw(frame))
        self.booster.fit_eval = None
        # a binomial ensemble's own margin, still on the device, of rows that
        # all weigh 1, is ordered and counted there (``metrics.MarginRoc``)
        dev = ev.get("device")
        if not (ev["w"] is None and self.nclasses == 2
                and self.distribution == "bernoulli"):
            dev = None
        source = "fit_margin" if dev is None else "fit_margin_device"
        rows = len(ev["y"])
        says = {source: 1}
        if self.nclasses == 2:
            says["roc"] = "host" if dev is None else "device"
        TRAIN_METRICS.inc(source=source)
        with Span("model_performance", rows=rows, source="fit_margin", **says):
            if dev is not None:
                roc = M.MarginRoc(**dev)
                # while the device sorts: the losses in runs of rows, with
                # what is left of the link inside a run
                with Span("score_link", rows=rows):
                    losses = M.binomial_losses(ev["y"], ev["margin"][:, 0], sigmoid)
                with Span("score_metrics", rows=rows) as span:
                    metrics = roc.metrics(losses, sigmoid)
                    span.set(distinct=roc.distinct, device_s=round(roc.device_s, 6))
                    return metrics
            with Span("score_link", rows=rows):
                raw = self._raw_from_margin(np.asarray(ev["margin"], np.float64))
            # a K-class model's metrics say its classes (one host thread)
            k = {"classes": self.nclasses} if self.nclasses > 2 else {}
            with Span("score_metrics", rows=rows, **k):
                return self._metrics(np.asarray(ev["y"], np.float64), raw, ev["w"])

    def predict_contributions(self, frame: Frame, background_frame=None) -> Frame:
        """Exact per-feature SHAP contributions on the margin scale
        (Model.scoreContributions / TreeSHAPPredictor): one column per tree
        feature plus BiasTerm; rows sum to the raw margin exactly."""
        from h2o3_tpu.frame.frame import Column
        from h2o3_tpu.models.tree.shap import predict_contributions as _pc

        contribs = _pc(self, frame, background_frame=background_frame)
        names = tree_feature_names(self.data_info, self.tree_encoding)
        cols = [
            Column(names[j], contribs[:, j], ColType.NUM)
            for j in range(len(names))
        ]
        cols.append(Column("BiasTerm", contribs[:, -1], ColType.NUM))
        return Frame(cols)

    def variable_importances(self) -> dict:
        """Split-count/gain-weighted importances (SharedTree varimp analogue:
        squared-error reduction summed per feature)."""
        names = tree_feature_names(self.data_info, self.tree_encoding)
        imp = np.zeros(len(names))
        for trees in self.booster.trees_per_class:
            for t in range(trees.ntrees):
                sp = trees.is_split[t]
                feats = trees.feat[t][sp]
                np.add.at(imp, feats, 1.0)
        total = imp.sum()
        rel = imp / total if total > 0 else imp
        return dict(zip(names, rel.tolist()))
