"""The K class trees of a boosting round grown as one level program
(``booster._grow_trees``): a level of K trees is one histogram launch over
its (class, node) slots, the trees are judged by the plain reference of the
``gbm-kdd99-5class-d5`` configuration (``benchmark/references/
hist-gbm-classes.py``, H2O's bound on a multinomial leaf) within that configuration's
limits, DRF and XGBoost multinomial fits go the same way, a K-class model
scores as the device did (predict, MOJO, checkpoint), and **the gate**: a fit
of one class tree a round lowers to the programs of the commit before the
change (a573c2c), text for text."""

import hashlib
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.frame.frame import ColType, Column, Frame
from h2o3_tpu.models.tree import DRF, GBM, booster
from h2o3_tpu.models.tree.booster import TreeParams, level_plan, plan_launches
from h2o3_tpu.models.tree.xgboost import XGBoost
from h2o3_tpu.parallel.mesh import default_mesh
from h2o3_tpu.util import timeline

pytestmark = pytest.mark.leaks_keys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    path = os.path.join(ROOT, "benchmark", "references", name + ".py")
    spec = importlib.util.spec_from_file_location("references_t_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = load("hist-gbm-classes")
with open(os.path.join(ROOT, "benchmark", "configs", "gbm-kdd99-5class-d5.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmark", "limits", "gbm-kdd99-5class-d5.json")) as f:
    LIMITS = json.load(f)["limits"]

PARAMS = {k: v for k, v in CONFIG["params"].items() if k != "distribution"}
SEED = 2147483659
N = 20_000


def table(classes, n=N, seed=SEED):
    """Four numeric features (one with NA) and one categorical of 12 levels;
    the classes overlap, so no class's rows saturate in a few rounds, and
    the last class is rare (1.5% of the rows)."""
    rng = np.random.default_rng(seed % (2**32))
    X = rng.normal(size=(n, 5)).astype(np.float32)
    X[:, 4] = rng.integers(0, 12, n)
    effect = rng.normal(size=(12, classes))
    w = rng.normal(size=(4, classes))
    logit = X[:, :4] @ w + effect[X[:, 4].astype(np.int64)]
    logit[:, -1] -= 3.0
    p = np.exp(logit - logit.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    y = (rng.random(n)[:, None] > np.cumsum(p, axis=1)).sum(axis=1).astype(np.int32)
    X[rng.random(n) < 0.05, 2] = np.nan
    return X, y


def columns():
    return ([{"name": f"x{i}", "type": "num"} for i in range(4)]
            + [{"name": "lvl", "type": "cat", "domain": [f"l{i:02d}" for i in range(12)]}])


def frame_of(X, y, classes):
    cols = [Column(f"x{i}", X[:, i].astype(np.float64)) for i in range(4)]
    cols.append(Column("lvl", X[:, 4].astype(np.int32), ColType.CAT,
                       [f"l{i:02d}" for i in range(12)]))
    cols.append(Column("label", y, ColType.CAT, [str(c) for c in range(classes)]))
    return Frame(cols)


def fit(classes, ntrees=6, **kw):
    X, y = table(classes)
    fr = frame_of(X, y, classes)
    params = dict(PARAMS, ntrees=ntrees, **kw)
    model = GBM(response_column="label", seed=SEED, distribution="multinomial",
                **params).train(fr)
    return model, fr, X, y


@pytest.fixture(scope="module", params=[3, 5])
def fitted(request):
    return fit(request.param)


# ---------------------------------------------------------------------------
# judged by the configuration's reference, within its limits


def test_a_k_class_fit_is_within_the_configurations_limits(fitted):
    model, _, X, y = fitted
    classes = model.nclasses
    config = dict(CONFIG, params=dict(CONFIG["params"], ntrees=6))
    answer = ref.extract(model, list(LIMITS))
    got = ref.compare(config, SEED, {"X": X, "y": y, "classes": classes,
                                     "columns": columns()}, [answer], 2, list(LIMITS))
    assert len(answer["trees"]) == classes and len(answer["trees"][0]) == 6
    over = {k: (v, LIMITS[k]) for k, v in got.items() if not v <= LIMITS[k]}
    assert not over, over


def test_the_leaves_are_the_newton_step_held_to_h2os_bound(fitted):
    """A leaf is the learn-rate scaled Newton step over the rows it holds,
    within +-1e4: round 0's leaves read against the reference's."""
    model, _, X, y = fitted
    K = model.nclasses
    answer = ref.extract(model, [])
    p = ref.RefParams.from_config(CONFIG["params"], SEED)
    cat_levels = ref.cat_levels_of(columns(), X.shape[1])
    codes = ref.bin_codes(X, answer["edges"], cat_levels)
    yf = y.astype(np.float64)
    f0 = ref.init_margin("multinomial", yf, K)
    g, h = ref.base.grad_hess("multinomial", yf, np.tile(f0, (len(y), 1)))
    rows = np.ones(len(y), bool)
    for c in range(K):
        tree = answer["trees"][c][0]
        _, pos, _ = ref.sets.grow(codes, g[:, c], h[:, c], rows, rows[:X.shape[1]], p,
                                  cat_levels, follow=tree)
        assert ref.leaf_gaps(tree, pos, g[:, c], h[:, c], rows, p, K)["leaf_gap"] < 1e-3
    assert model.booster.params.max_abs_leaf == booster.MULTINOMIAL_LEAF_MAX
    for trees in model.booster.trees_per_class:
        assert np.abs(np.concatenate(trees.leaf)).max() <= booster.MULTINOMIAL_LEAF_MAX


def test_a_rare_class_is_clamped_not_unbounded():
    """A class of 3 rows in 20,000 (prior 1.5e-4) that a leaf isolates takes
    a Newton step of ~lr/p: held at 1e4, not 1e12 a round later."""
    X, y = table(3)
    y = np.where(np.arange(N) % 2, y % 2, y % 2)
    rare = np.flatnonzero(X[:, 4] == 7)[:12]
    y[rare] = 2
    X[rare, 0] = 50.0
    model = GBM(response_column="label", seed=SEED, distribution="multinomial",
                **dict(PARAMS, ntrees=3)).train(frame_of(X, y, 3))
    leaves = np.concatenate(model.booster.trees_per_class[2].leaf)
    assert np.abs(leaves).max() == booster.MULTINOMIAL_LEAF_MAX
    assert np.isfinite(model.training_metrics.logloss)


def test_levels_of_saturated_rows_are_ordered_by_their_ratio():
    """A categorical's levels whose Σh is under 1e-12 (rows at the hessian's
    1e-16 floor) are ordered by Σg/Σh in a K-class tree's search, so its
    set is the reference's best prefix; floored at 1e-12, as a one-class
    tree's order key still is, they order by Σg and the set is another."""
    cnt = np.full(6, 100.0)
    G = np.array([26.0, 40.0, 10.0, 47.0, 48.0, 56.0])
    H = np.array([6e-14, 6e-15, 1e-14, 2e-14, 1e-14, 7e-15])
    hist = np.zeros((1, 1, 7, 3))
    hist[0, 0, :6] = np.stack([G, H, cnt], axis=1)
    gains, _, order, _ = ref._all_candidates(hist, np.array([True]), 10.0)
    _, j, _ = np.unravel_index(np.argmax(gains[0]), gains.shape[1:])
    want = np.isin(np.arange(6), order[0, 0, :j + 1])

    def left(tiny):
        out = booster._split_search(
            jnp.asarray(hist, jnp.float32), jnp.float32(0), jnp.float32(0), jnp.float32(0),
            jnp.float32(0.1), jnp.ones(1, bool), min_rows=10.0, n_bins1=7,
            cat_levels=(6,), tiny_hessians=tiny)
        return np.asarray(out[-1])[0, :6]

    np.testing.assert_array_equal(left(True), want)
    assert not np.array_equal(left(False), want)


def test_a_choice_only_the_stated_precision_makes_is_not_held_short():
    """Four groups of 1,000 rows whose gradients sit by bfloat16's rounding
    midpoints: the exact sums split on feature 0, the sums of the gradients
    rounded to bfloat16 on feature 1. ``held`` reads feature 1's tree
    against both and holds it short by neither; a tree that does not split
    is short by both."""
    p = ref.RefParams.from_config(dict(CONFIG["params"], max_depth=1), SEED)
    i = np.arange(4000)
    codes = np.stack([i % 2, (i // 2) % 2]).astype(np.uint8)
    g = np.array([0.33315915, -0.333048, -0.33326046, 0.33283128])[codes[0] * 2 + codes[1]]
    h = np.full(4000, 0.25)
    rows, cols = np.ones(4000, bool), np.ones(2, bool)
    B = ref.sets.na_code(p.nbins, [0, 0])

    def tree(feat):
        t = ref.Tree(np.zeros(3, np.int32), np.zeros(3, np.int32), np.zeros(3, bool),
                     np.zeros(3, bool), np.zeros(3), np.zeros((3, B), bool))
        if feat is not None:
            t.feat[0], t.is_split[0], t.left[0] = feat, True, np.arange(B) < 1
        return t

    def exact(t):
        return ref.sets.grow(codes, g, h, rows, cols, p, [0, 0], follow=t)[2]["split_gap"]

    def held(t):
        return ref.held(codes, g, h, rows, cols, p, [0, 0], t, "bfloat16")[1]["split_gap"]

    assert exact(tree(0)) == 0 and exact(tree(1)) > LIMITS["split_gap"]
    assert held(tree(0)) == 0 and held(tree(1)) == 0
    assert held(tree(None)) > LIMITS["split_gap"]


def test_a_split_missed_on_a_small_side_the_judge_keeps_is_held_short():
    """The judge drops a candidate whose side's Σh is under 2^-20 of its
    node's (``RESOLUTION``), no more: a builder blind to sides under 2^-12
    (``small_sides_blind``) on the cell's own table is outside its limits,
    and the same builder with sight is inside them."""
    path = os.path.join(ROOT, "benchmark", "tables", "kdd99-synth.py")
    spec = importlib.util.spec_from_file_location("tables_t_kdd99_synth", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    X, y = gen.make(CONFIG["table"], N, SEED)
    yf = y.astype(np.float64)
    p = ref.RefParams.from_config(CONFIG["params"], SEED)
    cat_levels = ref.cat_levels_of(gen.columns(CONFIG["table"]), X.shape[1])
    codes = ref.bin_codes(X, ref.quantile_edges(X, p.nbins, cat_levels), cat_levels)
    read = {}
    for fault in (None, "small_sides_blind"):
        model = ref.boost(codes, yf, p, 5, cat_levels, 5, fault=fault)
        read[fault] = ref.judge(codes, yf, p, model, [0, 2, 4], cat_levels, 5,
                                stated="bfloat16")
    assert ref.BLIND > ref.RESOLUTION and "small_sides_blind" in ref.FAULTS
    assert all(read[None][k] <= LIMITS[k] for k in ref.JUDGED), read[None]
    assert any(read["small_sides_blind"][k] > LIMITS[k] for k in ref.JUDGED), read


# ---------------------------------------------------------------------------
# one launch a level


@pytest.mark.parametrize("depth,classes,launches", [
    (5, 5, [1, 1, 1, 1, 1]),        # the cell: at most 5 x 16 x 4 = 320 lanes
    (7, 5, [1, 1, 1, 1, 1, 5, 5]),  # levels 5-6: 5 x 32 x 4 = 640 > 512, one a class
    (5, 1, [1, 1, 1, 1, 1]),
])
def test_a_k_class_level_is_one_histogram_launch(depth, classes, launches):
    """K class trees build every node of a level (no subtraction) and take
    their leaves from the last split (no totals level)."""
    p = TreeParams(max_depth=depth, nbins=20)
    plan = level_plan(p, True, impl="pallas", classes=classes)
    assert [built for built, *_ in plan] == [
        classes * booster._built_nodes(d, classes == 1) for d in range(depth)]
    assert [booster._histogram.class_launches(b // classes, classes, "pallas")
            for b, *_ in plan] == launches
    assert sum(plan_launches(plan, classes, "pallas").values()) == sum(launches)
    one = level_plan(p, True, impl="pallas")
    assert plan_launches(one, 1, "pallas") == {"nodematmul": depth} or depth > 6


def test_the_block_states_its_class_trees_and_launches(fitted):
    """``tree_block`` says ``class_trees`` and ``hist_launches`` (the scatter
    on the CPU: one a level, whatever K), ``fit_profile`` sums the launches
    and ``tree_hist_launches_total`` counts them."""
    model, *_ = fitted
    K = model.nclasses
    blocks = [e for e in timeline.snapshot(timeline.CAPACITY) if e["kind"] == "tree_block"
              and e.get("class_trees") == K and e.get("first_tree") == 0]
    assert blocks and blocks[-1]["hist_launches"] == blocks[-1]["trees"] * 5
    assert [tuple(lv[:2]) for lv in blocks[-1]["hist_slots"]] == [
        (K * 2**d, K * 2**d) for d in range(5)]  # leaves from the last split
    prof = model.fit_profile["tree_block"]
    assert prof["hist_launches"] == 6 * 5
    score = model.fit_profile["score/score_metrics"] if "score/score_metrics" in \
        model.fit_profile else None
    assert score is not None
    assert booster.HIST_LAUNCHES.value(kernel="scatter") >= 30


def test_a_level_of_five_trees_is_one_pallas_launch_in_the_program(monkeypatch):
    """The interpreted Pallas path traces one ``_build_histogram_pallas_jit``
    a level for a round of five trees (and ``_build_classes_nodematmul``
    inside it), and the histograms are the scatter's."""
    from h2o3_tpu.ops import histogram, pallas_histogram

    rng = np.random.default_rng(7)
    n, F, K, nodes, B1 = 1536, 5, 5, 4, 13
    bins = jnp.asarray(rng.integers(0, B1, (n, F)), jnp.int32)
    nd = jnp.asarray(rng.integers(-1, nodes, (K, n)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(K, n)), jnp.float32)
    h = jnp.asarray(rng.random((K, n)), jnp.float32)
    calls = []
    real = pallas_histogram._build_classes_nodematmul
    monkeypatch.setattr(pallas_histogram, "_build_classes_nodematmul",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    pallas_histogram._build_histogram_pallas_jit.clear_cache()
    got = histogram.build_histogram_sharded(bins, nd, g, h, nodes, B1, impl="pallas")
    want = histogram.build_histogram_sharded(bins, nd, g, h, nodes, B1, impl="scatter")
    assert got.shape == (K * nodes, F, B1, 3) and calls == [1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)
    # one class tree at a time gives the same slots
    for c in range(K):
        one = histogram.build_histogram_sharded(bins, nd[c], g[c], h[c], nodes, B1,
                                                impl="scatter")
        np.testing.assert_allclose(np.asarray(got[c * nodes:(c + 1) * nodes]),
                                   np.asarray(one), rtol=1e-5, atol=1e-4)
    pallas_histogram._build_histogram_pallas_jit.clear_cache()


# ---------------------------------------------------------------------------
# DRF and XGBoost multinomial through the same path


@pytest.mark.parametrize("builder,kw", [
    (DRF, dict(ntrees=4, max_depth=6, nbins=20)),
    (XGBoost, dict(ntrees=4, max_depth=4)),
])
def test_drf_and_xgboost_multinomial_grow_their_class_trees_together(builder, kw):
    X, y = table(3)
    fr = frame_of(X, y, 3)
    model = builder(response_column="label", seed=SEED, **kw).train(fr)
    assert model.booster.nclasses_trees == 3
    block = [e for e in timeline.snapshot(timeline.CAPACITY) if e["kind"] == "tree_block"][-1]
    assert block["class_trees"] == 3
    assert block["hist_launches"] == block["trees"] * kw["max_depth"]
    # the fit's own margin (metrics from it) is the walk's of the same trees
    walked = model.model_performance(fr)
    assert walked.logloss == pytest.approx(model.training_metrics.logloss, rel=1e-5)


# ---------------------------------------------------------------------------
# a K-class model scores as the device did


def test_predict_scores_as_the_fit_did(fitted):
    model, fr, *_ = fitted
    walked = model.model_performance(fr)  # the fit's margin is gone: a walk
    assert walked.logloss == pytest.approx(model.training_metrics.logloss, rel=1e-6)
    pred = model.predict(fr)
    assert pred.ncols == model.nclasses + 1


def test_the_reference_format_mojo_scores_as_the_device_does(fitted, tmp_path):
    from h2o3_tpu.models import mojo_ref
    from h2o3_tpu.models.tree.common import tree_matrix

    model, fr, *_ = fitted
    mojo = mojo_ref.read_mojo(mojo_ref.write_mojo(model, str(tmp_path / "k.zip")))
    X = tree_matrix(model.data_info, fr, encoding=model.tree_encoding).astype(np.float64)
    want = model._predict_raw(fr)
    rows = list(range(0, 2000, 37))
    got = np.array([mojo.score0(X[i]) for i in rows])
    np.testing.assert_allclose(got, want[rows], atol=2e-6)


def test_a_continued_fit_scores_as_its_device_margin_did():
    """Checkpoint-continue: the earlier rounds are the checkpoint's trees,
    and the whole model's walk reads the metrics of the margin the device
    summed (a straight fit's trees differ only by the host's float64 margin
    a continuation starts from)."""
    X, y = table(3)
    fr = frame_of(X, y, 3)
    kw = dict(response_column="label", seed=SEED, distribution="multinomial")
    first = GBM(**kw, **dict(PARAMS, ntrees=3)).train(fr)
    more = GBM(**kw, checkpoint=first.key, **dict(PARAMS, ntrees=6)).train(fr)
    for a, b in zip(first.booster.trees_per_class, more.booster.trees_per_class):
        assert (a.ntrees, b.ntrees) == (3, 6)
        for t in range(3):
            np.testing.assert_array_equal(a.leaf[t], b.leaf[t])
    walked = more.model_performance(fr)
    assert walked.logloss == pytest.approx(more.training_metrics.logloss, rel=1e-5)


# ---------------------------------------------------------------------------
# the gate: one class tree a round is the parent's program


def block_text(objective, p, impl, subtract, monkeypatch, C=1, weighted=False,
               monotone=False, n=1024, F=5):
    """StableHLO text (no debug locations) of a block of two rounds, lowered
    with the eight arguments of ``benchmark/lib/programs.py``."""
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", impl)
    S = jax.ShapeDtypeStruct
    fn = booster._make_block_fn(objective, C, 2, p, default_mesh(n_devices=1),
                                weighted=weighted, monotone=monotone, subtract=subtract)
    fp = F + (-F) % min(8, F)
    return fn.lower(
        S((n, F), jnp.int32),
        S((n, C), jnp.float32) if objective == "fixed" else S((n,), jnp.float32),
        S((n,), jnp.bool_), S((n, C), jnp.float32), S((2, 2), jnp.uint32),
        S((fp, n), jnp.int32) if impl == "pallas" else None,
        S((n,), jnp.float32) if weighted else None,
        S((F,), jnp.int32) if monotone else None).as_text()


TP = TreeParams
#: one class tree a round, each program of a kind the builders lower:
#: (objective, params, impl, subtract, weighted, monotone)
GATED = {
    "gbm_bernoulli_pallas": ("bernoulli", TP(ntrees=0, seed=0, max_depth=3, nbins=16,
                             min_rows=2.0, reg_lambda=0.0, sample_rate=0.8,
                             col_sample_rate_per_tree=0.8), "pallas", True, False, False),
    "gbm_gaussian_scatter": ("gaussian", TP(ntrees=0, seed=0, max_depth=3, nbins=16,
                             min_rows=2.0, reg_lambda=0.0), "scatter", False, False, False),
    "gbm_sets_pallas": ("bernoulli", TP(ntrees=0, seed=0, max_depth=3, nbins=16,
                        min_rows=2.0, reg_lambda=0.0, cat_levels=(20, 0, 0, 0, 0)),
                        "pallas", True, False, False),
    "gbm_weighted_monotone": ("bernoulli", TP(ntrees=0, seed=0, max_depth=3, nbins=16,
                              min_rows=2.0, reg_lambda=0.0), "scatter", False, True, True),
    "xgb_bernoulli_pallas": ("bernoulli", TP(ntrees=0, seed=0, max_depth=3, nbins=16,
                             reg_lambda=1.0, gamma=0.5, min_child_weight=1.0,
                             scale_pos_weight=2.0), "pallas", True, False, False),
    "drf_fixed_scatter": ("fixed", TP(ntrees=0, seed=0, max_depth=3, nbins=16,
                          learn_rate=1.0, reg_lambda=0.0, sample_rate=0.632, mtries=2),
                          "scatter", False, False, False),
    "drf_frontier_scatter": ("fixed", TP(ntrees=0, seed=0, max_depth=11, nbins=8,
                             learn_rate=1.0, reg_lambda=0.0, mtries=2, min_rows=1.0),
                             "scatter", False, False, False),
}

#: sha256 of each program's text, recorded from commit a573c2c (the parent:
#: the unrolled loop of one ``_build_one_tree`` a class) before the change
PARENT_DIGESTS = {
    "drf_fixed_scatter": "b37c8bf1ce69b04d522e6b0d4062b55fcf807e10e4a20b3f606893830a9db96d",
    "drf_frontier_scatter": "1c6cbc81ccb626d657d5e27e54969ea9a95e9efea0abb5b13e2fee772f8611cc",
    "gbm_bernoulli_pallas": "64e4109bd333737b1a15459c22cd594a1e5730b639418274f7cd4f27555c1445",
    "gbm_gaussian_scatter": "4df82451eee3dab67490c82fe12fd23dd2ab566d4c5fc6332d95a86bf716fe89",
    "gbm_sets_pallas": "c10d6406de452304fca7a241272fb02d26a070f4628e70d022cc9461c1393f10",
    "gbm_weighted_monotone": "baef0df3c3ca85b7275930ae0a2233053c76e4ee57339dec6ae5a9bb1e2d0e19",
    "xgb_bernoulli_pallas": "e446015aefddb512470a0a274b64569dc4b9abab0def0bd7eebd13bf320d2970",
}


@pytest.mark.parametrize("program", sorted(GATED))
def test_the_gate_one_class_tree_a_round_lowers_to_the_parents_program(
        program, monkeypatch):
    objective, p, impl, subtract, weighted, monotone = GATED[program]
    booster._make_block_fn.cache_clear()
    text = block_text(objective, p, impl, subtract, monkeypatch, weighted=weighted,
                      monotone=monotone)
    booster._make_block_fn.cache_clear()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_DIGESTS[program]
