"""XGBoost's own parameters in the booster: the regularised gain (lambda,
gamma), the floor on a child's sum of hessians (``min_child_weight``) and the
class weight (``scale_pos_weight``), judged by the plain reference
``benchmark/references/hist-xgb.py``; the compiled programs with the two new
fields at their defaults; what a fit over several devices says of its shards
(ISSUE 36).  A fit over a mesh against the same fit on one device is in
``test_multichip_dryrun.py``."""

import hashlib
import importlib.util
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.frame.frame import ColType, Column, Frame
from h2o3_tpu.models.tree import booster
from h2o3_tpu.models.tree.xgboost import XGBoost, XGBoostParameters
from h2o3_tpu.parallel.mesh import default_mesh
from h2o3_tpu.util import telemetry, timeline

pytestmark = pytest.mark.leaks_keys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    path = os.path.join(ROOT, "benchmark", "references", "hist-xgb.py")
    spec = importlib.util.spec_from_file_location("references_hist_xgb_t", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()

#: what every fit of this file shares; small enough that each parameter binds
BASE = dict(ntrees=3, max_depth=4, nbins=16, learn_rate=0.3)


def table(n=4000, seed=11):
    """Five numeric columns, NA in one, a binary response with an
    interaction and a rare positive class."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5))
    X[rng.random(n) < 0.05, 3] = np.nan
    logit = X[:, 0] + 0.8 * X[:, 1] * X[:, 2] - 1.2 + 0.5 * np.nan_to_num(X[:, 3])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int32)
    return X, y


def frame_of(X, y):
    return Frame([Column(f"f{i}", X[:, i].astype(np.float64)) for i in range(X.shape[1])]
                 + [Column("y", y, ColType.CAT, ["0", "1"])])


def fit_and_judge(X, y, fitted_with: dict, judged_with: dict):
    """The builder's trees under ``fitted_with``, judged by the reference
    holding ``judged_with``: the four judged numbers."""
    model = XGBoost(response_column="y", seed=7, **BASE, **fitted_with).train(frame_of(X, y))
    answer = ref.extract(model, ref.NUMBERS)
    p = ref.RefParams(distribution="bernoulli", seed=7,
                      **{k: BASE[k] for k in ("max_depth", "nbins", "learn_rate")},
                      **judged_with)
    codes = ref.bin_codes(X.astype(np.float32), answer["edges"])
    return model, ref.judge(codes, y.astype(np.float64), p, answer, [0, 1, 2])


#: name -> (the parameter as the fit is given it, the same fit's judge
#: without it, the number that must then fail)
PARAMETERS = {
    "lambda": ({"reg_lambda": 25.0}, {"reg_lambda": 1.0}, "leaf_gap"),
    "gamma": ({"gamma": 3.0}, {"gamma": 0.0}, "split_gap"),
    "hessian_floor": ({"min_child_weight": 40.0}, {"min_child_weight": None}, "split_gap"),
    "class_weight": ({"scale_pos_weight": 3.0}, {"scale_pos_weight": 1.0}, "leaf_gap"),
}


@pytest.mark.parametrize("name", sorted(PARAMETERS))
def test_the_builder_holds_each_parameter_as_the_reference_does(name):
    """Judged by the reference that holds the same value the trees forgo
    nothing and the leaves are the reference's to float32; judged by one
    that holds another value they are not: the parameter moved the trees,
    and the way the reference says."""
    given, without, number = PARAMETERS[name]
    X, y = table()
    _, same = fit_and_judge(X, y, given, given)
    assert same["split_gap"] < 1e-4 and same["gain_forgone"] < 1e-5, same
    assert same["leaf_gap"] < 1e-3 and same["leaf_gap_mean"] < 1e-4, same
    _, other = fit_and_judge(X, y, given, {**given, **without})
    assert other[number] > 0.05, other


def test_every_parameter_at_once_and_the_reported_metrics():
    """The deployment's own values together; ``training_metrics`` are
    unweighted and the starting margin is the response's prior."""
    given = {"reg_lambda": 1.0, "gamma": 0.1, "min_child_weight": 1.0,
             "scale_pos_weight": 2.0}
    X, y = table()
    model, judged = fit_and_judge(X, y, given, given)
    assert judged["split_gap"] < 1e-4 and judged["leaf_gap"] < 1e-3, judged
    answer = ref.extract(model, ref.NUMBERS)
    p = ref.RefParams(distribution="bernoulli", max_depth=4, nbins=16, learn_rate=0.3)
    mine = ref.score(ref.bin_codes(X.astype(np.float32), answer["edges"]),
                     y.astype(np.float64), p, answer)
    assert abs(answer["reported"]["logloss"] - mine["logloss"]) < 1e-6 * mine["logloss"]
    assert abs(answer["reported"]["auc"] - mine["auc"]) < 1e-6
    prior = float(np.mean(y))
    assert answer["init_margin"][0] == pytest.approx(np.log(prior / (1 - prior)))


def test_the_floor_is_on_the_hessians_and_not_on_the_rows():
    """One node, hand-made: the best threshold leaves a child of 30 rows
    whose hessians sum to 0.3.  The count test lets it pass, the floor of 1
    does not and takes the next best."""
    hist = np.zeros((1, 1, 5, 3), np.float32)
    hist[0, 0, :4] = [(-9.0, 0.3, 30), (1.0, 3.0, 30), (2.0, 3.0, 30), (6.0, 3.0, 30)]
    args = (jnp.asarray(hist), jnp.float32(1.0), jnp.float32(0.0), jnp.float32(0.0),
            jnp.float32(1.0), jnp.ones((1,), bool))
    by_rows = booster._split_search(*args, min_rows=1.0, n_bins1=5)
    by_hess = booster._split_search(*args, min_rows=1.0, n_bins1=5, min_child_weight=1.0)
    assert int(by_rows[1][0]) == 0 and int(by_hess[1][0]) == 1
    assert float(by_hess[3][0]) < float(by_rows[3][0])
    # the reference reads the same node the same way
    p = ref.RefParams(distribution="bernoulli", max_depth=1, nbins=4, learn_rate=1.0,
                      min_child_weight=1.0)
    gains = ref._gains(hist.astype(np.float64), p)[0]
    assert not np.isfinite(gains[0, 0, 0]).any()
    assert int(np.argmax(gains[0, 0, :, 0])) == 1
    assert float(by_hess[3][0]) == pytest.approx(float(gains[0, 0, 1, 0]), rel=1e-6)


def test_what_the_builder_refuses():
    X, y = table(n=300)
    with pytest.raises(ValueError, match="scale_pos_weight must be positive"):
        XGBoost(response_column="y", scale_pos_weight=0.0, **BASE).train(frame_of(X, y))
    with pytest.raises(ValueError, match="min_child_weight must be non-negative"):
        XGBoost(response_column="y", min_child_weight=-1.0, **BASE).train(frame_of(X, y))
    reg = Frame([Column("f0", X[:, 0]), Column("y", X[:, 1])])
    with pytest.raises(ValueError, match="positive class of a binary"):
        XGBoost(response_column="y", scale_pos_weight=2.0, **BASE).train(reg)


# ---------------------------------------------------------------------------
# the compiled programs


def xgb_block_text(n_devices: int, **fields) -> str:
    """The training block of the XGBoost builder's default parameters, the
    chip's flow (Pallas, subtraction), at 13 features over ``n_devices``."""
    p = XGBoostParameters(response_column="y")
    tp = booster.TreeParams(
        ntrees=0, seed=0, max_depth=p.max_depth, learn_rate=p.learn_rate, nbins=p.nbins,
        min_rows=p.min_rows, min_split_improvement=p.min_split_improvement,
        reg_lambda=p.reg_lambda, reg_alpha=p.reg_alpha, gamma=p.gamma,
        sample_rate=p.sample_rate, col_sample_rate_per_tree=p.col_sample_rate_per_tree,
        **fields)
    n, F, block = 2048 * n_devices, 13, 2
    S = jax.ShapeDtypeStruct
    fn = booster._make_block_fn("bernoulli", 1, block, tp,
                                default_mesh(n_devices=n_devices), subtract=True)
    return fn.lower(
        S((n, F), jnp.int32), S((n,), jnp.float32), S((n,), jnp.bool_),
        S((n, 1), jnp.float32), S((block, 2), jnp.uint32),
        S((F + (-F) % min(8, F), n), jnp.int32), None, None).as_text()


#: sha256 of the StableHLO text of that block over one device and over four,
#: recorded from commit 037be79 (the parent of ISSUE 36) BEFORE the change
PARENT_XGB_BLOCKS = {
    1: "5c94991dac36f5e2c27b85ae3beaf492f7403b7c3a603887826e527be72e3f95",
    4: "0192e23fa8e510e97c94142af30ba8e25d8013edf5c0333fdf9a6d08fa1e07d0",
}


@pytest.mark.parametrize("n_devices", sorted(PARENT_XGB_BLOCKS))
def test_with_the_new_fields_at_their_defaults_the_block_is_the_parents(
        n_devices, monkeypatch):
    """``min_child_weight`` unset and ``scale_pos_weight`` 1 trace nothing:
    text for text the parent's program, sharded or not (the three cells'
    GBM blocks are held by ``test_tree_train_metrics.py``); either field
    set is another program."""
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "pallas")
    text = xgb_block_text(n_devices)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_XGB_BLOCKS[n_devices]
    assert "all_reduce" in text  # the levels' sum, over one device too
    assert xgb_block_text(n_devices, min_child_weight=1.0) != text
    assert xgb_block_text(n_devices, scale_pos_weight=2.0) != text


def test_the_hessian_floor_lowers_under_the_split_scope(monkeypatch):
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "pallas")
    tp = booster.TreeParams(ntrees=0, seed=0, max_depth=2, nbins=16, min_child_weight=1.0)
    n, F = 2048 * 4, 13
    S = jax.ShapeDtypeStruct
    fn = booster._make_block_fn("bernoulli", 1, 1, tp, default_mesh(n_devices=4),
                                subtract=True)
    text = fn.lower(
        S((n, F), jnp.int32), S((n,), jnp.float32), S((n,), jnp.bool_),
        S((n, 1), jnp.float32), S((1, 2), jnp.uint32), S((16, n), jnp.int32),
        None, None).as_text(debug_info=True)
    assert "L00/split" in text and "hist_psum" in text


# ---------------------------------------------------------------------------
# what a fit over several devices says of its shards


def events_since(t0_ns):
    return [e for e in timeline.snapshot(timeline.CAPACITY)
            if e["ns"] >= t0_ns and "parent_id" in e]


def test_a_fit_over_the_mesh_says_its_shards_and_what_it_summed():
    """The builder's fit runs over the default mesh (the suite's eight
    devices): the spans of what is placed on, summed over and fetched from
    the devices say how many shards and how many bytes a shard, the counter
    and ``fit_profile`` hold the bytes handed to the levels' psums."""
    X, y = table(n=2000)
    psum = telemetry.REGISTRY.get("tree_hist_psum_bytes_total")
    before, t0 = psum.value(), time.time_ns()
    model = XGBoost(response_column="y", seed=3, max_runtime_secs=600.0,
                    **BASE).train(frame_of(X, y))
    by_kind = {}
    for e in events_since(t0):
        by_kind.setdefault(e["kind"], []).append(e)
    for kind in ("bins_upload", "state_upload"):
        (e,) = by_kind[kind]
        assert e["shards"] == 8 and e["bytes_per_shard"] == e["bytes"] // 8
    assert all(e["shards"] == 8 for e in by_kind["tree_block"] + by_kind["budget_check"])
    # scatter path, depth 4: four histogram levels of 1, 2, 4, 8 slots over
    # 5 features x 17 bins and the leaves' totals of 16 slots, float32 x 3
    a_tree = 12 * (15 * 5 * 17 + 16)
    assert [e["bytes_psummed"] for e in by_kind["tree_block"]] == [a_tree * e["trees"]
                                                               for e in by_kind["tree_block"]]
    assert psum.value() - before == a_tree * 3
    assert model.fit_profile["tree_block"]["bytes_psummed"] == a_tree * 3
    assert model.fit_profile["bins_upload"]["bytes_per_shard"] == by_kind["bins_upload"][0]["bytes_per_shard"]


def test_a_fit_on_one_device_says_nothing_new():
    from h2o3_tpu.models.tree.common import init_margin

    X, y = table(n=600)
    psum = telemetry.REGISTRY.get("tree_hist_psum_bytes_total")
    before, t0 = psum.value(), time.time_ns()
    booster.train_boosted(
        X.astype(np.float32), "bernoulli", y.astype(np.float64), 1,
        init_margin("bernoulli", y.astype(np.float64), 1),
        booster.TreeParams(ntrees=2, max_depth=3, nbins=8, seed=0),
        mesh=default_mesh(n_devices=1), fit_eval={"frame": None, "y": y, "w": None})
    events = [e for e in events_since(t0) if e["kind"] in (
        "bins_upload", "state_upload", "tree_block", "margin_readback")]
    assert {e["kind"] for e in events} == {
        "bins_upload", "state_upload", "tree_block", "margin_readback"}
    for e in events:
        assert not {"shards", "bytes_per_shard", "bytes_psummed"} & set(e), e
    assert psum.value() == before
