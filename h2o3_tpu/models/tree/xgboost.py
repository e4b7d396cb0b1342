"""XGBoost-style booster — tree_method="tpu_hist", the north-star config.

Reference: ``h2o-extensions/xgboost`` — Java glue around native libxgboost
(``XGBoostModel.java:240-292,382-394`` resolves backend/tree_method to
``grow_gpu_hist``; ``task/XGBoostUpdater.java:124,155`` steps the native
booster; Rabit allreduce merges histograms across nodes, SURVEY.md §2.3).

TPU-native: no JNI, no Rabit, no DMatrix conversion — the booster IS the
tpu_hist core (h2o3_tpu/models/tree/booster.py): quantized features, Pallas/
XLA scatter-add histograms, psum merge over ICI, second-order split gains
with lambda/alpha/gamma regularization exactly as libxgboost defines them.
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
class XGBoostParameters(ModelParameters):
    ntrees: int = 50
    max_depth: int = 6
    learn_rate: float = 0.3  # eta
    nbins: int = 256  # max_bins (hist/gpu_hist default)
    nbins_cats: int = 1024  # most levels a categorical may have under enum
    #: fewest ROWS a child may hold (H2O's min_rows, a count): the test a
    #: split passes unless ``min_child_weight`` is given
    min_rows: float = 1.0
    #: libxgboost's min_child_weight, a floor on a child's sum of hessians:
    #: when given, a split needs Σh >= it on both children and ``min_rows``
    #: is not tested; None (the default) keeps the count test
    min_child_weight: Optional[float] = None
    #: libxgboost's scale_pos_weight: the gradient and hessian of a binary
    #: fit's positive rows times it (training metrics stay unweighted)
    scale_pos_weight: float = 1.0
    min_split_improvement: float = 0.0
    reg_lambda: float = 1.0
    reg_alpha: float = 0.0
    gamma: float = 0.0
    sample_rate: float = 1.0  # subsample
    col_sample_rate_per_tree: float = 1.0  # colsample_bytree
    tree_method: str = "tpu_hist"
    distribution: str = "auto"
    score_tree_interval: int = 1
    tweedie_power: float = 1.5  # reg:tweedie variance power
    monotone_constraints: Optional[dict] = None  # {col: -1|+1}


class XGBoostModel(TreeModelBase):
    algo_name = "xgboost"


class XGBoost(ModelBuilder):

    SUPPORTED_COMMON = frozenset(
        {
            "checkpoint",
            "stopping_rounds",
            "weights_column",
            "categorical_encoding",
            "max_runtime_secs",
        }
    )
    algo_name = "xgboost"
    profile_counts = SPAN_COUNTS

    def __init__(self, params: Optional[XGBoostParameters] = None, **kw) -> None:
        super().__init__(params or XGBoostParameters(**kw))

    #: distributions the XGBoost objective surface supports (libxgboost's
    #: reg:squarederror / binary:logistic / multi:softprob / count:poisson /
    #: reg:gamma / reg:tweedie — no huber/quantile/laplace objectives there)
    DISTRIBUTIONS = frozenset(
        {"auto", "gaussian", "bernoulli", "multinomial", "poisson", "gamma", "tweedie"}
    )

    def _fit(self, frame: Frame, valid: Optional[Frame] = None) -> XGBoostModel:
        p: XGBoostParameters = self.params
        if p.distribution not in self.DISTRIBUTIONS:
            raise ValueError(
                f"xgboost does not support distribution {p.distribution!r}; "
                f"choose from {sorted(self.DISTRIBUTIONS)}"
            )
        if p.min_child_weight is not None and p.min_child_weight < 0:
            raise ValueError("min_child_weight must be non-negative")
        if p.scale_pos_weight <= 0:
            raise ValueError("scale_pos_weight must be positive")
        # the init margin is the builder's data-driven prior, as GBM's: the
        # link of the response's (weighted) mean, log(p / (1 - p)) for a
        # binary fit, with ``scale_pos_weight`` left out of it. libxgboost
        # starts from base_score (0.5 -> margin 0), which this builder has no
        # parameter for
        model, X, y, weights, _, objective, f0, n_class_trees, mono = (
            tree_fit_setup(frame, p, XGBoostModel, use_offset=False)
        )
        if p.scale_pos_weight != 1.0 and objective != "bernoulli":
            raise ValueError(
                "scale_pos_weight weighs the positive class of a binary "
                f"response; this fit's objective is {objective!r}")

        tp = TreeParams(
            ntrees=_extra_trees(p, n_class_trees),
            max_depth=p.max_depth,
            learn_rate=p.learn_rate,
            nbins=p.nbins,
            min_rows=p.min_rows,
            min_split_improvement=p.min_split_improvement,
            reg_lambda=p.reg_lambda,
            reg_alpha=p.reg_alpha,
            gamma=p.gamma,
            sample_rate=p.sample_rate,
            col_sample_rate_per_tree=p.col_sample_rate_per_tree,
            seed=p.actual_seed(),
            cat_levels=model.cat_levels,
            min_child_weight=p.min_child_weight,
            scale_pos_weight=p.scale_pos_weight,
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
