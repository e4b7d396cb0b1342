"""Stored-oracle accuracy regression tier.

Reference: ``h2o-test-accuracy`` — dataset x algo test cases with stored
expected metrics (``src/test/java/water/TestCase.java``,
``AccuracyTestingSuite.java``). The sklearn-oracle tests elsewhere use loose
tolerances; this tier pins exact metric values on fixed synthetic datasets
so silent accuracy drift (a changed default, a broken kernel, an RNG
regression) fails loudly. Values were recorded on the 8-device CPU mesh the
test tier always runs on (conftest pins the backend), so they are
bit-reproducible up to minor XLA version drift — hence the small epsilon.
"""

import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.models.deeplearning import DeepLearning
from h2o3_tpu.models.glm import GLM
from h2o3_tpu.models.kmeans import KMeans
from h2o3_tpu.models.tree import DRF, GBM, XGBoost


# legacy module predating the CheckKeysTask fixture: tests here
# share/train keys without per-test cleanup; the module-level
# sweeper still removes everything at module end
pytestmark = pytest.mark.leaks_keys

#: golden metrics; regenerate deliberately (never casually) with
#: the snippet in this file's git history if an intentional algorithm
#: change shifts them
GOLDEN = {
    "glm_binomial_auc": 0.8022620737109191,
    "gbm_binomial_auc": 0.8310825609898799,
    "xgboost_binomial_auc": 0.8873523696367261,
    # re-recorded when DRF's default max_depth went from 12 to H2O's 20 and
    # a node's mtries draw came to be keyed by its heap id
    "drf_binomial_auc": 0.9997969754340276,
    "gbm_regression_rmse": 0.6585004906238698,
    "dl_regression_rmse": 1.0634751969103902,
    "kmeans_tot_withinss": 108.05436325073242,
}

#: tolerance: tight enough to catch real drift, loose enough for
#: XLA-version-level float reassociation
EPS = 2e-3


def _binom_frame(seed=7, n=2000):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5))
    logit = 1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(int)
    d = {f"x{i}": X[:, i] for i in range(5)}
    d["y"] = np.where(y > 0, "yes", "no")
    return Frame.from_dict(d)


def _reg_frame():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2000, 4))
    y = 2 * X[:, 0] - X[:, 1] + 0.5 * rng.normal(size=2000)
    return Frame.from_dict({f"x{i}": X[:, i] for i in range(4)} | {"y": y})


@pytest.fixture(scope="module")
def binom():
    return _binom_frame()


@pytest.fixture(scope="module")
def reg():
    return _reg_frame()


def _check(name, value):
    golden = GOLDEN[name]
    assert value == pytest.approx(golden, abs=EPS), (
        f"{name}: got {value!r}, golden {golden!r} — accuracy drift; if the "
        f"change is intentional, re-record the golden deliberately"
    )


def test_glm_binomial_golden(binom):
    m = GLM(response_column="y", family="binomial", lambda_=0.0, seed=1).train(binom)
    _check("glm_binomial_auc", m.training_metrics.auc)


def test_gbm_binomial_golden(binom):
    m = GBM(response_column="y", ntrees=20, max_depth=4, seed=1,
            min_rows=5.0).train(binom)
    _check("gbm_binomial_auc", m.training_metrics.auc)


def test_xgboost_binomial_golden(binom):
    m = XGBoost(response_column="y", ntrees=20, max_depth=4, seed=1).train(binom)
    _check("xgboost_binomial_auc", m.training_metrics.auc)


def test_drf_binomial_golden(binom):
    m = DRF(response_column="y", ntrees=20, seed=1).train(binom)
    _check("drf_binomial_auc", m.training_metrics.auc)


def test_gbm_regression_golden(reg):
    m = GBM(response_column="y", ntrees=20, max_depth=4, seed=1,
            min_rows=5.0).train(reg)
    _check("gbm_regression_rmse", m.training_metrics.rmse)


def test_dl_regression_golden(reg):
    m = DeepLearning(response_column="y", hidden=[16, 16], epochs=10,
                     seed=1).train(reg)
    _check("dl_regression_rmse", m.training_metrics.rmse)


def test_kmeans_golden():
    rng = np.random.default_rng(5)
    X = np.concatenate(
        [rng.normal(loc=c, scale=0.5, size=(300, 3)) for c in (-3, 0, 3)]
    )
    m = KMeans(k=3, seed=1).train(
        Frame.from_dict({f"x{i}": X[:, i] for i in range(3)})
    )
    _check("kmeans_tot_withinss", m.tot_withinss)
