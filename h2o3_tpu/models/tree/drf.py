"""DRF — distributed random forest on the shared tree machinery.

Reference: ``hex/tree/drf/DRF.java`` — same SharedTree driver as GBM, but
bagged trees fit the raw response (no boosting), sample_rate 0.632, and
predictions aggregate by averaging. H2O's defaults hold: ``max_depth`` 20,
``min_rows`` 1, ``nbins`` 20, ``mtries`` -1 (floor(sqrt(p)) features a split
for classification, p/3 for regression). Levels past the dense node ladder
are frontier levels (``booster._frontier_levels``): a node sits in a slot
carrying its heap id and is histogrammed over its own mtries features only.
A node's mtries draw is keyed by (the tree's key, the node's heap id), at
every level. ``max_runtime_secs`` and ``stopping_rounds`` bound the fit
through ``common.make_tree_monitor`` on the averaged margin, checked every
``score_tree_interval`` trees (0: every tree block).

Classification leaves hold class frequencies; this build realizes that as a
per-class indicator-regression tree (leaf = class fraction in the leaf),
averaged over trees and normalized — same estimator, SPMD-friendly shapes.
The training metrics are in-bag, over every row of the averaged margin;
H2O reports a DRF's training metrics out of bag, which this build does not.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.data_info import response_vector
from h2o3_tpu.models.framework import ModelBuilder, ModelParameters
from h2o3_tpu.models.tree.booster import TreeParams, train_boosted
from h2o3_tpu.models.tree.common import (
    SPAN_COUNTS,
    TreeModelBase,
    checkpoint_booster as _checkpoint_booster,
    extra_trees as _extra_trees,
    make_tree_monitor,
    training_rows,
    tree_cache_token,
    tree_data_info,
)
from h2o3_tpu.util.telemetry import Span


@dataclass
class DRFParameters(ModelParameters):
    ntrees: int = 50
    max_depth: int = 20  # the reference default (DRFParametersV3)
    nbins: int = 20
    nbins_cats: int = 1024  # most levels a categorical may have under enum
    min_rows: float = 1.0
    min_split_improvement: float = 1e-5
    sample_rate: float = 0.632  # reference DRF default (DRFParametersV3)
    mtries: int = -1  # -1: sqrt(F) classif, F/3 regression (DRF.java)
    score_tree_interval: int = 0  # 0: the budget is checked every tree block


class DRFModel(TreeModelBase):
    algo_name = "drf"

    def _raw_from_margin(self, margin: np.ndarray) -> np.ndarray:
        # margin: averaged leaf values per class
        if not self.is_classifier:
            return margin[:, 0]
        p = np.clip(margin, 1e-9, None)
        if p.shape[1] == 1:  # binomial: single tree-set predicts P(class 1)
            p1 = np.clip(margin[:, 0], 0.0, 1.0)
            return np.stack([1 - p1, p1], axis=1)
        return p / p.sum(axis=1, keepdims=True)


def _forest_score(model: DRFModel, y, margin, weights) -> float:
    """The stopping metric of a forest's averaged margin: logloss of its
    class probabilities, mse of a regression."""
    raw = model._raw_from_margin(margin)
    if model.is_classifier:
        p = np.clip(raw[np.arange(len(y)), y.astype(np.int64)], 1e-15, 1.0)
        return float(np.average(-np.log(p), weights=weights))
    return float(np.average((raw - y) ** 2, weights=weights))


class DRF(ModelBuilder):

    SUPPORTED_COMMON = frozenset(
        {"checkpoint", "weights_column", "categorical_encoding",
         "stopping_rounds", "max_runtime_secs"}
    )
    algo_name = "drf"
    profile_counts = SPAN_COUNTS

    def __init__(self, params: Optional[DRFParameters] = None, **kw) -> None:
        super().__init__(params or DRFParameters(**kw))

    def _fit(self, frame: Frame, valid: Optional[Frame] = None) -> DRFModel:
        from h2o3_tpu.models.tree import dist_hist
        from h2o3_tpu.models.tree.common import resolve_tree_encoding

        p: DRFParameters = self.params
        if dist_hist.use_dist(frame, p, resolve_tree_encoding(
                getattr(p, "categorical_encoding", "auto"))):
            # chunk-homed frame: rows stay on their homes; the targets
            # (and grad/hess) are rebuilt map-side at bind time
            model, X, y, weights, nclasses = dist_hist.dist_drf_front(
                frame, p, DRFModel)
        else:
            with Span("tree_setup") as span:
                ignored = list(p.ignored_columns)
                if p.weights_column and p.weights_column not in ignored:
                    ignored.append(p.weights_column)
                info = tree_data_info(frame, p.response_column, ignored)
                y = response_vector(info, frame)
                nclasses = (len(info.response_domain)
                            if info.response_domain else 1)
                model = DRFModel(p, info, "gaussian")
                X, y, weights, _ = training_rows(
                    frame, p, info, model.tree_encoding, y)
                span.set(rows=X.shape[0], features=X.shape[1])
        F = X.shape[1]

        mtries = p.mtries
        if mtries <= 0:
            mtries = max(1, int(np.sqrt(F)) if nclasses > 1 else max(1, F // 3))

        # targets: raw y (regression) or per-class indicators (classification)
        if nclasses > 1 and nclasses != 2:
            targets = np.zeros((len(y), nclasses), dtype=np.float64)
            targets[np.arange(len(y)), y.astype(np.int64)] = 1.0
            n_class_trees = nclasses
        elif nclasses == 2:
            targets = y[:, None]
            n_class_trees = 1
        else:
            targets = y[:, None]
            n_class_trees = 1

        tp = TreeParams(
            ntrees=_extra_trees(p, n_class_trees),
            max_depth=p.max_depth,
            learn_rate=1.0,  # no shrinkage: each tree predicts the target itself
            nbins=p.nbins,
            min_rows=p.min_rows,
            min_split_improvement=p.min_split_improvement,
            reg_lambda=0.0,
            reg_alpha=0.0,
            sample_rate=p.sample_rate,
            mtries=mtries,
            seed=p.actual_seed(),
            cat_levels=model.cat_levels,
        )

        history = []
        monitor, score_interval = make_tree_monitor(
            model, p, None, y, weights, history,
            score=lambda margin: _forest_score(model, y, margin, weights))
        # objective='fixed': each tree independently fits the raw targets
        # (g = -target, h = 1 gives Newton leaf = mean(target in leaf);
        # with weights g = -w*t, h = w gives the weighted in-leaf mean)
        model.booster = train_boosted(
            X,
            objective="fixed",
            y=targets,
            n_class_trees=n_class_trees,
            init_margin=np.zeros(n_class_trees),
            params=tp,
            average=True,
            monitor=monitor,
            score_interval=score_interval,
            resume_from=_checkpoint_booster(
                p, n_class_trees, self.algo_name,
                n_features=F, encoding=model.tree_encoding,
            ),
            weights=weights,
            cache_token=tree_cache_token(frame, p, model.tree_encoding),
            cache_frame_key=getattr(frame, "key", None),
            fit_eval={"frame": frame, "y": y, "w": weights},
        )
        model.ntrees_built = model.booster.trees_per_class[0].ntrees
        model.training_metrics = model.model_performance(frame)
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
        return model
