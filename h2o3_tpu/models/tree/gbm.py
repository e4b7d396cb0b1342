"""GBM — H2O-style gradient boosting on the tpu_hist booster core.

Reference: ``hex/tree/gbm/GBM.java:452,493,571`` (buildNextKTrees / growTrees),
distributions from ``hex/Distribution.java``, defaults from GBMParametersV3.
One tree per class per iteration (SharedTree k-trees), Newton leaf values,
row/column sampling, ScoreKeeper early stopping.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.framework import ModelBuilder, ModelParameters
from h2o3_tpu.models.tree.booster import TreeParams, train_boosted
from h2o3_tpu.models.tree.common import (
    SPAN_COUNTS,
    TreeModelBase,
    checkpoint_booster as _checkpoint_booster,
    extra_trees as _extra_trees,
    make_tree_monitor,
    tree_cache_token,
    tree_fit_setup,
)


@dataclass
class GBMParameters(ModelParameters):
    ntrees: int = 50
    max_depth: int = 5
    learn_rate: float = 0.1
    nbins: int = 20  # reference GBM default nbins=20 (GBMParametersV3)
    nbins_cats: int = 1024  # most levels a categorical may have under enum
    min_rows: float = 10.0
    min_split_improvement: float = 1e-5
    sample_rate: float = 1.0
    col_sample_rate_per_tree: float = 1.0
    distribution: str = "auto"
    score_tree_interval: int = 1
    tweedie_power: float = 1.5  # hex/Distribution.java tweedie variance power
    quantile_alpha: float = 0.5
    huber_alpha: float = 0.9
    monotone_constraints: Optional[dict] = None  # {col: -1|+1}


class GBMModel(TreeModelBase):
    algo_name = "gbm"


class GBM(ModelBuilder):

    SUPPORTED_COMMON = frozenset(
        {
            "checkpoint",
            "stopping_rounds",
            "weights_column",
            "offset_column",
            "categorical_encoding",
            "max_runtime_secs",
        }
    )
    algo_name = "gbm"
    profile_counts = SPAN_COUNTS

    def __init__(self, params: Optional[GBMParameters] = None, **kw) -> None:
        super().__init__(params or GBMParameters(**kw))

    def _fit(self, frame: Frame, valid: Optional[Frame] = None) -> GBMModel:
        p: GBMParameters = self.params
        model, X, y, weights, offset, objective, f0, n_class_trees, mono = (
            tree_fit_setup(frame, p, GBMModel, use_offset=True)
        )

        tp = TreeParams(
            ntrees=_extra_trees(p, n_class_trees),
            max_depth=p.max_depth,
            learn_rate=p.learn_rate,
            nbins=p.nbins,
            min_rows=p.min_rows,
            min_split_improvement=p.min_split_improvement,
            reg_lambda=0.0,  # the reference GBM has no leaf L2
            reg_alpha=0.0,
            sample_rate=p.sample_rate,
            col_sample_rate_per_tree=p.col_sample_rate_per_tree,
            seed=p.actual_seed(),
            cat_levels=model.cat_levels,
        )

        history = []
        monitor, score_interval = make_tree_monitor(
            model, p, objective, y, weights, history
        )
        model.booster = train_boosted(
            X,
            objective=objective,
            y=y,
            n_class_trees=n_class_trees,
            init_margin=f0,
            params=tp,
            monitor=monitor,
            score_interval=score_interval,
            resume_from=_checkpoint_booster(
                p, n_class_trees, self.algo_name,
                n_features=X.shape[1], encoding=model.tree_encoding,
            ),
            weights=weights,
            offset=offset,
            monotone=mono,
            cache_token=tree_cache_token(frame, p, model.tree_encoding),
            cache_frame_key=getattr(frame, "key", None),
            fit_eval={"frame": frame, "y": y, "w": weights},
        )
        model.ntrees_built = model.booster.trees_per_class[0].ntrees
        model.training_metrics = model.model_performance(frame)
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
        return model
