"""Set-valued splits on categorical columns (``categorical_encoding="enum"``):
binning a bin a level, the split search over prefixes of the G/H order, the
routing and the scoring walk by membership, export and re-scoring, and the
paths that cannot carry a set refusing it by name, and the gate: a fit with
no ``enum`` column lowers to the programs the parent of this change lowered.
The plain reference ``benchmark/references/hist-gbm-sets.py`` (numpy float64,
independent of the program) judges the fitted trees on seeded tables.
"""

import hashlib
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.frame.frame import NA_CAT, ColType, Column, Frame
from h2o3_tpu.models.tree import booster
from h2o3_tpu.models.tree.booster import TreeParams, _split_search, train_boosted
from h2o3_tpu.models.tree.drf import DRF
from h2o3_tpu.models.tree.gbm import GBM
from h2o3_tpu.models.tree.xgboost import XGBoost
from h2o3_tpu.ops.histogram import apply_bins, make_bins, na_code
from h2o3_tpu.parallel.mesh import default_mesh

# fitted models stay in the DKV (a module fixture shares one); the module
# sweeper removes them at module end
pytestmark = pytest.mark.leaks_keys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    path = os.path.join(ROOT, "benchmark", "references", "hist-gbm-sets.py")
    spec = importlib.util.spec_from_file_location("references_hist_gbm_sets_t", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def set_splits(trees) -> int:
    """How many splits of an ensemble test a set of levels."""
    cat = np.asarray(trees.cat_levels, bool)
    return int(sum((sp & cat[f]).sum() for f, sp in zip(trees.feat, trees.is_split)))


def table(seed, n=4000, levels=40, na_every=37):
    """Two categorical columns whose effect does not follow the level's
    index, one numeric, a binary response; NA in every column's own rows."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, levels, n)
    b = rng.integers(0, 7, n)
    x = rng.normal(size=n)
    eff_a, eff_b = rng.normal(size=levels), rng.normal(0, 0.5, 7)
    y = (rng.random(n) < 1 / (1 + np.exp(-(eff_a[a] + eff_b[b] + 0.5 * x)))).astype(np.int32)
    a_codes = a.astype(np.int32)
    a_codes[::na_every] = NA_CAT
    x = x.copy()
    x[5::na_every] = np.nan
    return a_codes, b.astype(np.int32), x, y


def frame_of(a, b, x, y, levels=40):
    return Frame([
        Column("a", a, ColType.CAT, [f"a{i}" for i in range(levels)]),
        Column("b", b, ColType.CAT, [f"b{i}" for i in range(7)]),
        Column("x", x),
        Column("y", y, ColType.CAT, ["0", "1"]),
    ])


PARAMS = dict(response_column="y", ntrees=4, max_depth=4, nbins=16, min_rows=5.0,
              learn_rate=0.3, seed=3)


@pytest.fixture(scope="module")
def fitted():
    a, b, x, y = table(11)
    fr = frame_of(a, b, x, y)
    return GBM(categorical_encoding="enum", **PARAMS).train(fr), fr, (a, b, x, y)


# ---------------------------------------------------------------------------
# binning


def test_bins_a_level_a_bin_na_last():
    X = np.array([[0.0, 0.1], [2.0, 0.5], [np.nan, np.nan], [7.0, 0.9], [-1.0, 0.3]],
                 np.float32)
    cat_levels = (5, 0)
    edges = make_bins(X, nbins=4, cat_levels=cat_levels)
    assert np.isinf(edges[0]).all() and np.isfinite(edges[1][:3]).all()
    codes = apply_bins(X, edges, cat_levels)
    na = na_code(4, cat_levels)
    assert na == 5
    # a level's code is the level; NA, a level past the known ones and a
    # negative code take the last bucket
    assert codes[:, 0].tolist() == [0, 2, na, na, na]
    assert codes[2, 1] == na and (codes[[0, 1, 3, 4], 1] < 4).all()
    # with no categorical the NA bucket is nbins, as it was
    assert apply_bins(X[:, 1:], edges[1:])[2, 0] == 4


def test_more_levels_than_nbins_cats_is_refused_by_name():
    a, b, x, y = table(5, n=500)
    with pytest.raises(ValueError, match=r"'a' has 40 levels.*nbins_cats=32"):
        GBM(categorical_encoding="enum", nbins_cats=32, **PARAMS).train(frame_of(a, b, x, y))


def test_encoding_names():
    from h2o3_tpu.models.tree.common import resolve_tree_encoding

    assert resolve_tree_encoding("enum") == "enum"
    assert resolve_tree_encoding("label_encoder") == "label_encoder"
    assert resolve_tree_encoding("one_hot_explicit") == "one_hot_explicit"
    with pytest.raises(ValueError, match="not supported"):
        resolve_tree_encoding("binary")


# ---------------------------------------------------------------------------
# the split search


def hand_hist(g, h, c, n_bins1):
    """[1, 1, n_bins1, 3] from per-code sums (the NA bucket last)."""
    hist = np.zeros((1, 1, n_bins1, 3), np.float32)
    hist[0, 0, :len(g), 0], hist[0, 0, :len(h), 1], hist[0, 0, :len(c), 2] = g, h, c
    return jnp.asarray(hist)


def search(hist, cat_levels, n_bins1, min_rows=1.0):
    return _split_search(
        hist, jnp.float32(0), jnp.float32(0), jnp.float32(0), jnp.float32(1.0),
        jnp.ones((hist.shape[1],), bool), min_rows=min_rows, n_bins1=n_bins1,
        cat_levels=cat_levels)


def test_best_set_is_not_a_range_of_codes():
    # levels 0 and 2 pull one way, 1 and 3 the other: no threshold on the
    # codes separates them, the G/H order does
    g = [-4.0, 4.0, -3.0, 5.0]
    hist = hand_hist(g, [2.0] * 4, [10.0] * 4, n_bins1=6)
    f, j, dl, gain, leaf, left = search(hist, (4,), 6)
    assert left.shape == (1, 5)
    assert left[0, :4].tolist() == [True, False, True, False]
    assert int(j[0]) == 1  # a prefix of two levels of the order 0, 2, 1, 3
    exact = 0.5 * (49 / 4 + 81 / 4 - 4 / 8)
    assert float(gain[0]) == pytest.approx(exact, rel=1e-6)
    # the same sums split by a threshold on the codes reach less
    _, _, _, gain_thr, _ = _split_search(
        hist, jnp.float32(0), jnp.float32(0), jnp.float32(0), jnp.float32(1.0),
        jnp.ones((1,), bool), min_rows=1.0, n_bins1=6)
    assert float(gain_thr[0]) < 0.5 * exact


def test_levels_without_rows_follow_the_na_side_and_are_no_candidate():
    # level 1 and level 4 hold no row; NA rows pull with level 3
    g = [-4.0, 0.0, -3.0, 5.0, 0.0]
    h = [2.0, 0.0, 2.0, 2.0, 0.0]
    c = [10.0, 0.0, 10.0, 10.0, 0.0]
    hist = np.array(hand_hist(g, h, c, n_bins1=6))
    hist[0, 0, 5] = (6.0, 2.0, 10.0)  # the NA bucket
    f, j, dl, gain, leaf, left = search(jnp.asarray(hist), (5,), 6)
    assert bool(dl[0]) is False  # NA goes right, with level 3
    assert left[0].tolist() == [True, False, True, False, False]
    # the NA side to the left: unseen levels follow it
    hist[0, 0, 5] = (-6.0, 2.0, 10.0)
    f, j, dl, gain, leaf, left = search(jnp.asarray(hist), (5,), 6)
    assert bool(dl[0]) is True
    assert left[0].tolist() == [True, True, True, False, True]
    # one present level alone: nothing to split on
    one = hand_hist([3.0], [2.0], [10.0], n_bins1=6)
    assert not np.isfinite(float(search(one, (5,), 6)[3][0]))


def test_ties_go_by_level_and_a_numeric_beside_keeps_its_thresholds():
    # feature 0 categorical with all ratios equal but one; feature 1 numeric
    hist = np.zeros((1, 2, 5, 3), np.float32)
    hist[0, 0, :4] = [(-2, 2, 10), (-2, 2, 10), (6, 2, 10), (-2, 2, 10)]
    hist[0, 1, :4] = [(-4, 2, 10), (-2, 2, 10), (2, 2, 10), (4, 2, 10)]
    f, j, dl, gain, leaf, left = search(jnp.asarray(hist), (4, 0), 5)
    # best of all: levels {0, 1, 3} of the categorical against level 2
    assert int(f[0]) == 0 and left[0].tolist() == [True, True, False, True]
    # mask the categorical out: the numeric's best threshold, as a range
    out = _split_search(
        jnp.asarray(hist), jnp.float32(0), jnp.float32(0), jnp.float32(0),
        jnp.float32(1.0), jnp.asarray([False, True]), min_rows=1.0, n_bins1=5,
        cat_levels=(4, 0))
    assert int(out[0][0]) == 1 and int(out[1][0]) == 1
    assert out[-1][0].tolist() == [True, True, False, False]


def test_min_rows_holds_for_sets():
    g = [-4.0, 4.0, -3.0, 5.0]
    hist = hand_hist(g, [2.0] * 4, [3.0, 30.0, 3.0, 30.0], n_bins1=6)
    f, j, dl, gain, leaf, left = search(hist, (4,), 6, min_rows=10.0)
    # {0, 2} holds 6 rows: not allowed; {0, 2, 1} against {3} is
    assert left[0, :4].tolist() == [True, True, True, False]


def test_set_words_round_trip():
    rng = np.random.default_rng(0)
    left = rng.random((9, 301)) < 0.5
    words = booster._pack_words(jnp.asarray(left))
    assert words.shape == (9, 10) and words.dtype == jnp.uint32
    assert (ref.unpack_words(np.asarray(words), 301) == left).all()
    by = booster._word_bytes(words)
    assert by.shape == (9, 40) and int(by.max()) <= 255
    # the lookup by one matmul is exact for bytes, for any node
    k = jnp.asarray(rng.integers(0, 9, 500).astype(np.int32))
    got = np.asarray(booster._byte_lookup(by.T, k)).astype(np.int64)
    assert (got == np.asarray(by).T[:, np.asarray(k)]).all()


# ---------------------------------------------------------------------------
# a fit: against the reference, route = predict, export


@pytest.mark.parametrize("seed", [11, 12])
def test_program_against_the_reference(seed):
    a, b, x, y = table(seed)
    fr = frame_of(a, b, x, y)
    model = GBM(categorical_encoding="enum", **dict(PARAMS, seed=seed)).train(fr)
    numbers = [k for k in ref.NUMBERS if k not in ("mse_gap", "rmse_gap")]
    answer = ref.extract(model, numbers)
    assert answer["cat_levels"] == (40, 7, 0)
    columns = [{"name": "a", "type": "cat", "domain": [f"a{i}" for i in range(40)]},
               {"name": "b", "type": "cat", "domain": [f"b{i}" for i in range(7)]},
               {"name": "x", "type": "num"}]
    X = np.stack([np.where(a < 0, np.nan, a), b.astype(np.float64), x], axis=1)
    config = {"params": {"distribution": "bernoulli", "max_depth": 4, "nbins": 16,
                         "learn_rate": 0.3, "min_rows": 5.0}}
    got = ref.compare(config, seed, {"X": X, "y": y, "classes": 2, "columns": columns},
                      [answer], block=4, numbers=numbers)
    assert got["init_margin_gap"] < 1e-9
    assert got["split_gap"] < 1e-3 and got["gain_forgone"] < 1e-5, got
    assert got["leaf_gap"] < 1e-3 and got["logloss_gap"] < 1e-6 and got["auc_gap"] < 1e-6, got


def test_sets_are_used_and_beat_thresholds_on_codes(fitted):
    model, fr, _ = fitted
    trees = model.booster.trees_per_class[0]
    assert trees.cat_levels == (40, 7, 0) and trees.n_bins1 == 41
    assert trees.split_set[0].shape == (31, 2) and trees.split_set[0].dtype == np.uint32
    assert set_splits(trees) > 0
    by_codes = GBM(categorical_encoding="label_encoder", **PARAMS).train(fr)
    assert not by_codes.booster.trees_per_class[0].cat_levels
    assert model.training_metrics.logloss < by_codes.training_metrics.logloss - 0.02


def test_predict_is_route():
    """The fit's own per-row leaves (the margin the blocks hand back) equal
    the scoring walk of the finished trees."""
    a, b, x, y = table(21, n=3000)
    X = np.stack([np.where(a < 0, np.nan, a), b, x], axis=1).astype(np.float32)
    seen = {}

    def monitor(t, margin):
        seen["margin"] = margin.copy()
        return False

    p = TreeParams(ntrees=6, max_depth=5, nbins=16, min_rows=5.0, learn_rate=0.3,
                   reg_lambda=0.0, seed=4, cat_levels=(40, 7, 0))
    bt = train_boosted(X, "bernoulli", y.astype(np.float64), 1, np.array([0.1]), p,
                       monitor=monitor, score_interval=3)
    np.testing.assert_allclose(bt.predict_margin(X)[:, 0], seen["margin"][:, 0],
                               rtol=0, atol=2e-6)


def test_na_and_unseen_levels_follow_default_left(fitted):
    model, fr, (a, b, x, y) = fitted
    n = 64
    na = frame_of(np.full(n, NA_CAT, np.int32), b[:n], x[:n], y[:n])
    # a domain with a level the fit never saw: its rows score as NA rows do
    wider = Frame([
        Column("a", np.full(n, 40, np.int32), ColType.CAT, [f"a{i}" for i in range(40)] + ["new"]),
        Column("b", b[:n], ColType.CAT, [f"b{i}" for i in range(7)]),
        Column("x", x[:n]),
        Column("y", y[:n], ColType.CAT, ["0", "1"]),
    ])
    np.testing.assert_array_equal(model._predict_raw(na), model._predict_raw(wider))


def test_mojo_scores_as_the_device_does(fitted, tmp_path):
    from h2o3_tpu.genmodel import load_mojo

    model, fr, _ = fitted
    path = str(tmp_path / "enum.mojo")
    model.download_mojo(path)
    mm = load_mojo(path)
    rows = fr.to_pandas().to_dict(orient="records")
    np.testing.assert_allclose(np.asarray(mm.score(rows), np.float64),
                               model._predict_raw(fr), atol=1e-6)
    rows[0]["a"] = "never seen"
    rows[1]["a"] = None
    two = np.asarray(mm.score(rows[:2]))
    assert np.isfinite(two).all()


def test_saved_model_scores_the_same(fitted, tmp_path):
    from h2o3_tpu.models import persist

    model, fr, _ = fitted
    again = persist.load_model(persist.save_model(model, str(tmp_path / "m.bin")))
    assert again.booster.trees_per_class[0].cat_levels == (40, 7, 0)
    np.testing.assert_array_equal(again._predict_raw(fr), model._predict_raw(fr))


def test_checkpoint_continues_with_sets():
    a, b, x, y = table(31, n=2000)
    fr = frame_of(a, b, x, y)
    kw = dict(PARAMS, categorical_encoding="enum")
    whole = GBM(**dict(kw, ntrees=6)).train(fr)
    first = GBM(**dict(kw, ntrees=3)).train(fr)
    more = GBM(**dict(kw, ntrees=6, checkpoint=first.key)).train(fr)
    np.testing.assert_allclose(more._predict_raw(fr), whole._predict_raw(fr), atol=1e-6)
    with pytest.raises(ValueError, match="categorical_encoding"):
        GBM(**dict(PARAMS, ntrees=6, checkpoint=first.key,
                   categorical_encoding="label_encoder")).train(fr)


@pytest.mark.parametrize("builder", [DRF, XGBoost])
def test_other_builders_split_on_sets(builder):
    a, b, x, y = table(41, n=2000)
    fr = frame_of(a, b, x, y)
    model = builder(response_column="y", ntrees=4, max_depth=4, nbins=16, seed=2,
                    categorical_encoding="enum").train(fr)
    trees = model.booster.trees_per_class[0]
    assert trees.cat_levels == (40, 7, 0) and set_splits(trees) > 0
    assert model.training_metrics.auc > 0.7


def test_pallas_kernels_build_the_same_sets(monkeypatch):
    a, b, x, y = table(51, n=1500)
    fr = frame_of(a, b, x, y)
    kw = dict(PARAMS, ntrees=2, categorical_encoding="enum")
    by_scatter = GBM(**kw).train(fr)
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "pallas")
    from h2o3_tpu.frame import devcache

    devcache.DEVCACHE.clear()
    by_kernels = GBM(**kw).train(fr)
    np.testing.assert_allclose(by_kernels._predict_raw(fr), by_scatter._predict_raw(fr),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# what cannot carry a set says so


@pytest.mark.parametrize("reader", ["pojo", "shap", "rulefit", "tree_route"])
def test_threshold_readers_refuse_a_set_valued_model_by_name(fitted, reader):
    from h2o3_tpu.models import pojo, rulefit

    model, fr, _ = fitted
    with pytest.raises((NotImplementedError, Exception), match=r"set-valued splits") as err:
        if reader == "pojo":
            pojo._tree_tables(model)
        elif reader == "shap":
            model.predict_contributions(fr)
        elif reader == "rulefit":
            rulefit._extract_rules(model, model.data_info)
        else:
            tree_route(model)
    assert reader in str(err.value) or "/3/Tree" in str(err.value)


def tree_route(model):
    """GET /3/Trees of ``model`` through the registered handler, as
    ``client/tree.py``'s H2OTree asks for it."""
    from h2o3_tpu.api import handlers
    from h2o3_tpu.api.server import RequestServer

    registry = RequestServer()
    handlers.register_all(registry, None)
    return registry.dispatch("GET", f"/3/Trees/{model.key}/0", {})


def test_reference_format_mojo_scores_sets_as_the_device_does(fitted, tmp_path):
    """H2O's bitset split, written and read back by the independent decoder."""
    from h2o3_tpu.models import mojo_ref
    from h2o3_tpu.models.tree.common import tree_matrix

    model, fr, _ = fitted
    mojo = mojo_ref.read_mojo(mojo_ref.write_mojo(model, str(tmp_path / "sets.zip")))
    assert mojo.info["_genmodel_encoding"] == "Enum" and len(mojo.domains) == 3
    X = tree_matrix(model.data_info, fr, encoding=model.tree_encoding).astype(np.float64)
    want = model._predict_raw(fr)
    rows = list(range(0, 400, 7)) + [0, 37, 74]  # with NA rows of the categorical
    got = np.array([mojo.score0(X[i]) for i in rows])
    np.testing.assert_allclose(got, want[rows], atol=2e-6)
    # a level past the domain follows the NA side, as an NA does
    past, na = X[1].copy(), X[1].copy()
    past[0], na[0] = 40.0, np.nan
    np.testing.assert_array_equal(mojo.score0(past), mojo.score0(na))


def test_dist_hist_refuses_sets_by_name():
    class Homed:
        is_dist_hist = True

    p = TreeParams(ntrees=1, cat_levels=(3, 0))
    with pytest.raises(NotImplementedError, match=r"^dist_hist .*set-valued splits"):
        train_boosted(Homed(), "bernoulli", np.zeros(4), 1, np.zeros(1), p)


def test_a_model_without_sets_is_not_refused():
    a, b, x, y = table(61, n=800)
    fr = frame_of(a, b, x, y)
    from h2o3_tpu.models import pojo

    model = GBM(**dict(PARAMS, ntrees=2)).train(fr)  # auto: label codes
    assert model.tree_encoding == "label_encoder" and model.cat_levels == ()
    assert pojo._tree_tables(model)
    assert model.predict_contributions(fr).nrows == 800


def test_an_enum_model_without_a_set_is_refused_too():
    """A categorical with more levels than ``nbins`` whose rows all hold one
    level offers no set to split on, but widens the bin axis: NA of the
    numeric column is code 40 here, not ``nbins``, so a reader that bins by
    the edges alone would route it wrong.  It is refused like any other."""
    rng = np.random.default_rng(5)
    n = 600
    x = rng.normal(size=n)
    y = (rng.random(n) < 1 / (1 + np.exp(-2 * x))).astype(np.int32)
    x[::4] = np.nan
    fr = Frame([Column("a", np.full(n, 17, np.int32), ColType.CAT, [f"a{i}" for i in range(40)]),
                Column("x", x), Column("y", y, ColType.CAT, ["0", "1"])])
    model = GBM(categorical_encoding="enum", **dict(PARAMS, ntrees=3)).train(fr)
    trees = model.booster.trees_per_class[0]
    assert trees.cat_levels == (40, 0) and trees.n_bins1 == 41 and set_splits(trees) == 0
    with pytest.raises(NotImplementedError, match="set-valued splits"):
        model.predict_contributions(fr)


# ---------------------------------------------------------------------------
# the block program: unchanged with no categorical, scoped with one


def lowered(p, block=2, n=1024, F=3, debug=False):
    fn = booster._make_block_fn("bernoulli", 1, block, p, default_mesh(), subtract=False)
    S = jax.ShapeDtypeStruct
    low = fn.lower(S((n, F), jnp.int32), S((n,), jnp.float32), S((n,), jnp.bool_),
                   S((n, 1), jnp.float32), S((block, 2), jnp.uint32), None, None, None)
    return low.as_text(debug_info=True) if debug else low.as_text()


def numeric_block_text(objective, C, block, p, impl, subtract, monkeypatch, n=1024, F=5):
    """StableHLO text (no debug locations) of the block of a numeric fit,
    lowered with the eight arguments of ``benchmark/lib/programs.py``."""
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", impl)
    S = jax.ShapeDtypeStruct
    fn = booster._make_block_fn(objective, C, block, p, default_mesh(n_devices=1),
                                subtract=subtract)
    fp = F + (-F) % min(8, F)
    return fn.lower(
        S((n, F), jnp.int32),
        S((n, C), jnp.float32) if objective == "fixed" else S((n,), jnp.float32),
        S((n,), jnp.bool_), S((n, C), jnp.float32), S((block, 2), jnp.uint32),
        S((fp, n), jnp.int32) if impl == "pallas" else None, None, None).as_text()


#: sha256 of the StableHLO text of a numeric fit's programs at fixed small
#: shapes, recorded from commit 811397d (the parent of the set-valued splits)
#: BEFORE the change: the scatter block, the Pallas block with subtraction
#: and sampling (the chip's flow, interpreted), a DRF block (fixed targets,
#: mtries), and ``_predict_stacked`` as ``programs.build_scoring_programs``
#: lowers it. The DRF block's was recorded again when a node's mtries draw
#: came to be keyed by its heap id (``booster._node_candidates``): the one
#: thing that draw changed in its program. Its two class trees a round came
#: to be grown as one level program (``booster._grow_trees``), so it was
#: recorded once more then; a block of one class tree a round is held to the
#: parent of that change in ``tests/test_tree_classes.py``
PARENT_PROGRAMS = {
    "block_scatter": "3a50c57ac37cb62af4195df6a9fb9e1a45e227b50424d43b9a14542bb9c4a396",
    "block_pallas_subtract": "dde7eaf032dd91a0b2a2101646c6cdac1d5e0f0524496e52a4e7c6434c61072e",
    "block_drf": "fa4e2263fc7a1aed789507287a64510fa0762a9aadf7e04873909b63910f0ca6",
    "predict_stacked": "65fd78c589b300f01a4dcaa7e757aab45a4deccda6f745f4d125e892cf5d66db",
    # a sorted level (256 node slots), which no block above reaches at depth 3:
    # recorded from commit b201c72, before the level plan moved into one place
    "hist_sorted_level": "d818051a38ba4a5e256bd6d815b887495653c4f2e07ffa133152f54bef32bc42",
}


@pytest.mark.parametrize("program", sorted(PARENT_PROGRAMS))
def test_the_gate_a_numeric_fit_lowers_to_the_parents_programs(
        program, monkeypatch, parents_level_plan):
    """With no ``enum`` column the set search, the sixth array and the set
    routing are not traced at all: text for text the parent's programs
    (traced with the parents' node ladder and without the barrier on the
    node-matmul kernel's operands, both of ISSUE 35: the fixture)."""
    TP = TreeParams
    S = jax.ShapeDtypeStruct
    if program == "block_scatter":
        text = numeric_block_text("bernoulli", 1, 2, TP(
            ntrees=0, seed=0, max_depth=3, nbins=16, min_rows=2.0, reg_lambda=0.0),
            "scatter", False, monkeypatch)
    elif program == "block_pallas_subtract":
        text = numeric_block_text("bernoulli", 1, 2, TP(
            ntrees=0, seed=0, max_depth=3, nbins=16, min_rows=2.0, reg_lambda=0.0,
            sample_rate=0.8, col_sample_rate_per_tree=0.8), "pallas", True, monkeypatch)
    elif program == "block_drf":
        text = numeric_block_text("fixed", 2, 2, TP(
            ntrees=0, seed=0, max_depth=3, nbins=16, learn_rate=1.0, reg_lambda=0.0,
            sample_rate=0.632, mtries=2), "scatter", False, monkeypatch)
    elif program == "hist_sorted_level":
        from h2o3_tpu.ops.pallas_histogram import _build_histogram_pallas_jit

        text = _build_histogram_pallas_jit.lower(
            S((4096, 5), jnp.int32), S((4096,), jnp.int32), S((4096,), jnp.float32),
            S((4096,), jnp.float32), n_nodes=256, n_bins1=21, row_tile=None,
            interpret=True, vma=(), kernel="auto", bins_fm=None, rw=None,
            dtype="f32").as_text()
        assert "stablehlo.sort" in text
    else:
        text = booster._predict_stacked.lower(
            S((1000, 5), jnp.int32), S((3, 15), jnp.int32), S((3, 15), jnp.int32),
            S((3, 15), jnp.bool_), S((3, 15), jnp.bool_), S((3, 15), jnp.float32),
            max_depth=3, n_bins1_arr=S((), jnp.int32)).as_text()
    assert "stablehlo.sort" not in text or program != "block_scatter"
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PROGRAMS[program]


def test_enum_on_a_numeric_frame_is_the_numeric_fit():
    """No categorical column: default TreeParams, five arrays a tree, and the
    scoring program of today."""
    rng = np.random.default_rng(0)
    fr = Frame([Column("u", rng.normal(size=300)), Column("v", rng.normal(size=300)),
                Column("y", rng.integers(0, 2, 300).astype(np.int32), ColType.CAT, ["0", "1"])])
    model = GBM(response_column="y", ntrees=1, max_depth=2, seed=1,
                categorical_encoding="enum").train(fr)
    assert model.cat_levels == () and model.booster.params.cat_levels == ()
    trees = model.booster.trees_per_class[0]
    assert trees.split_set is None and len(trees.stacked()) == 5
    assert model.booster.params.n_bins1 == model.booster.params.nbins + 1


def test_block_with_a_categorical_carries_the_scopes():
    p = TreeParams(ntrees=0, seed=0, max_depth=3, nbins=20, min_rows=10, cat_levels=(0, 33, 0))
    text = lowered(p, debug=True)
    for scope in ("L00/split/sets", "L02/split/sets", "L00/route/sets", "L02/route/sets"):
        assert scope in text, scope
    assert "L03/split" not in text  # the last level holds leaves only


def test_spans_and_counter_name_the_categorical_features(fitted):
    from h2o3_tpu.util import timeline

    before = {k: booster.TREE_SPLITS.value(kind=k) for k in ("set", "threshold")}
    a, b, x, y = table(71, n=1000)
    # the validation frame is what the fit walks (its own frame it scores
    # from the margin it holds)
    model = GBM(**dict(PARAMS, ntrees=2, categorical_encoding="enum")).train(
        frame_of(a, b, x, y), frame_of(a, b, x, y))
    events = [e for e in timeline.snapshot(4096)]
    assert [e for e in events if e["kind"] == "make_bins"][-1]["cat_features"] == 2
    trees = model.booster.trees_per_class[0]
    n_set = set_splits(trees)
    n_all = sum(int(sp.sum()) for sp in trees.is_split)
    assert booster.TREE_SPLITS.value(kind="set") - before["set"] == n_set > 0
    assert booster.TREE_SPLITS.value(kind="threshold") - before["threshold"] == n_all - n_set
    readback = [e for e in events if e["kind"] == "tree_readback"][-1]
    assert (readback["splits"], readback["set_splits"]) == (n_all, n_set)
    walk = [e for e in events if e["kind"] == "score_traverse"][-1]
    assert walk["sets"] is True and walk["chunks"] == 1
    # the fit's own profile and its `train done` line carry the counts
    assert model.fit_profile["tree_readback"]["set_splits"] == n_set
    assert model.fit_profile["score/score_traverse"]["chunks"] == 1
    assert model.fit_profile["make_bins"]["cat_features"] == 2
