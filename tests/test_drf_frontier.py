"""Trees past the dense node ladder: frontier levels, per-node ``mtries``,
the forest's list of nodes, the budget. A DRF at depth 14 (its levels 10-13
are frontier levels on the CPU, which builds every level without
subtraction) is judged by the plain reference
``benchmark/references/hist-drf.py`` within the limits of the
``drf-higgs-d20`` configuration; a GBM at depth 12 by ``hist-gbm``. What a
level costs is held to the rows: the depth-20 block's temporaries at N and
2N rows, and no tensor of rows x nodes in its text."""

import importlib.util
import json
import os
import re
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.frame.frame import ColType, Column, Frame
from h2o3_tpu.models.tree import DRF, GBM, booster
from h2o3_tpu.models.tree.booster import TreeParams, level_plan, train_boosted
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


ref = load("hist-drf")
with open(os.path.join(ROOT, "benchmark", "configs", "drf-higgs-d20.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmark", "limits", "drf-higgs-d20.json")) as f:
    LIMITS = json.load(f)["limits"]

#: the configuration's parameters at depth 14 and a handful of trees
PARAMS = dict(CONFIG["params"], max_depth=14, ntrees=3)
SEED = 2147490777
N = 4000


def table(n=N, seed=SEED):
    """The configuration's table (higgs-synth: 28 numeric features), with
    NA in one feature so the NA bucket is on both sides of a split."""
    rng = np.random.default_rng(seed % (2**32))
    X = rng.normal(size=(n, 28)).astype(np.float32)
    w = rng.normal(size=28) / np.sqrt(28)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w + 0.5 * X[:, 0] * X[:, 1])))).astype(np.int32)
    X[rng.random(n) < 0.05, 3] = np.nan
    return X, y


def frame_of(X, y):
    return Frame([Column(f"f{i}", X[:, i].astype(np.float64)) for i in range(X.shape[1])]
                 + [Column("y", y, ColType.CAT, ["0", "1"])])


@pytest.fixture(scope="module")
def deep():
    X, y = table()
    fr = frame_of(X, y)
    model = DRF(response_column="y", seed=SEED, **PARAMS).train(fr)
    return model, fr, X, y


def judge(model, X, y, block=2):
    config = {"params": PARAMS}
    answer = ref.extract(model, list(LIMITS))
    return ref.compare(config, SEED, {"X": X, "y": y, "classes": 2}, [answer], block,
                       list(LIMITS))


# ---------------------------------------------------------------------------
# the forest against the reference


def test_a_depth_14_forest_is_the_references_within_the_limits(deep):
    model, fr, X, y = deep
    trees = model.booster.trees_per_class[0]
    assert trees.deep and trees.ntrees == 3
    # frontier levels were reached: nodes below level 10
    assert max(int(t.max()) for t in trees.node) >= 2**11 - 1
    got = judge(model, X, y)
    bad = {k: (v, LIMITS[k]) for k, v in got.items() if not v <= LIMITS[k]}
    assert not bad, bad


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_fails_a_limit(deep, fault):
    """(a) every feature a candidate, (b) trees cut at depth 12, (c) trees
    summed, (d) every row in every tree: each reads past a limit; the
    reference's own forest reads within them."""
    model, fr, X, y = deep
    p = ref.RefParams.from_config(PARAMS, SEED, 28, 2)
    edges = np.asarray(model.booster.trees_per_class[0].edges)
    codes = ref.base.bin_codes(X, edges)
    answer = dict(ref.forest(codes, y.astype(np.float64), p, 2, fault=fault), edges=edges)
    got = ref.compare({"params": PARAMS}, SEED, {"X": X, "y": y, "classes": 2},
                      [answer], 2, list(LIMITS))
    assert any(v > LIMITS[k] for k, v in got.items()), got


def test_the_references_own_forest_is_within_the_limits(deep):
    model, fr, X, y = deep
    p = ref.RefParams.from_config(PARAMS, SEED, 28, 2)
    edges = np.asarray(model.booster.trees_per_class[0].edges)
    answer = dict(ref.forest(ref.base.bin_codes(X, edges), y.astype(np.float64), p, 2),
                  edges=edges)
    got = ref.compare({"params": PARAMS}, SEED, {"X": X, "y": y, "classes": 2},
                      [answer], 2, list(LIMITS))
    assert all(v <= LIMITS[k] for k, v in got.items()), got


def test_a_depth_12_gbm_grows_its_frontier_levels_as_the_reference_does():
    """Boosting through the frontier: every feature a candidate, the
    learn-rate-scaled Newton leaves, judged by ``hist-gbm`` on the trees
    laid out as heaps."""
    gbm_ref = load("hist-gbm")
    X, y = table(3000)
    fr = frame_of(X, y)
    params = dict(distribution="bernoulli", ntrees=3, max_depth=12, nbins=20,
                  learn_rate=0.3, min_rows=2.0, sample_rate=0.8)
    model = GBM(response_column="y", seed=SEED, **params).train(fr)
    trees = model.booster.trees_per_class[0]
    assert trees.deep and max(int(t.max()) for t in trees.node) >= 2**11 - 1
    M = 2**13 - 1
    heaps = []
    for i in range(trees.ntrees):
        heap = [np.zeros(M, a.dtype) for a in (trees.feat[i], trees.split_bin[i],
                                               trees.default_left[i], trees.is_split[i],
                                               trees.leaf[i])]
        for a, src in zip(heap, (trees.feat[i], trees.split_bin[i], trees.default_left[i],
                                 trees.is_split[i], trees.leaf[i])):
            a[trees.node[i]] = src
        heaps.append(gbm_ref.Tree(*heap[:4], heap[4].astype(np.float64)))
    p = gbm_ref.RefParams.from_config(params, SEED)
    codes = gbm_ref.bin_codes(X, np.asarray(trees.edges))
    answer = {"init_margin": np.asarray(model.booster.init_margin), "trees": [heaps]}
    got = gbm_ref.judge(codes, y.astype(np.float64), p, answer, [0, 1, 2])
    assert got["split_gap"] < 1e-4 and got["leaf_gap"] < 1e-4, got
    # the walk of the list of nodes is the fit's own margin, to float32's
    # order of summation
    np.testing.assert_allclose(model.model_performance(fr).auc, model.training_metrics.auc,
                               atol=1e-6)


def test_monotone_constraints_hold_through_the_frontier_levels():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(N, 3))
    y = X[:, 0] + np.sin(3 * X[:, 1]) + 0.3 * rng.normal(size=N)
    fr = Frame([Column(n, X[:, i]) for i, n in enumerate("abc")] + [Column("y", y)])
    model = GBM(response_column="y", ntrees=3, max_depth=12, min_rows=1, seed=1,
                monotone_constraints={"a": 1}).train(fr)
    assert model.booster.trees_per_class[0].deep
    grid = np.linspace(-3, 3, 200)
    for b in (-1.0, 0.0, 0.7):
        g = Frame([Column("a", grid), Column("b", np.full(200, b)), Column("c", np.zeros(200))])
        assert np.all(np.diff(model.predict(g).col("predict").numeric_view()) >= -1e-6)


# ---------------------------------------------------------------------------
# the list of nodes: predict, persistence, checkpoint


def test_predict_of_a_deep_forest_is_the_fits_averaged_margin():
    X, y = table(2000)
    p = TreeParams(ntrees=4, max_depth=16, nbins=20, learn_rate=1.0, reg_lambda=0.0,
                   sample_rate=0.632, mtries=5, seed=3)
    bt = train_boosted(X.astype(np.float64), "fixed", y[:, None].astype(np.float64), 1,
                       np.zeros(1), p, average=True, fit_eval={"frame": None, "y": y, "w": None})
    assert bt.trees_per_class[0].deep
    np.testing.assert_allclose(bt.predict_margin(X.astype(np.float64)),
                               bt.fit_eval["margin"], rtol=0, atol=1e-6)


def test_a_deep_tree_is_its_nodes_not_a_heap(deep):
    model, fr, X, y = deep
    trees = model.booster.trees_per_class[0]
    for i in range(trees.ntrees):
        node, child, sp = trees.node[i], trees.child[i], trees.is_split[i]
        assert len(node) < 2 * N and np.all(np.diff(node) > 0)
        assert np.array_equal(node[child[sp]], 2 * node[sp] + 1)
        assert np.array_equal(node[child[sp] + 1], 2 * node[sp] + 2)
        assert np.all(child[~sp] == -1)


def test_a_deep_forest_saves_loads_and_continues(deep, tmp_path):
    from h2o3_tpu.models.persist import load_model, save_model

    model, fr, X, y = deep
    path = str(tmp_path / "drf.bin")
    save_model(model, path)
    back = load_model(path)
    np.testing.assert_array_equal(back._predict_raw(fr), model._predict_raw(fr))
    first = DRF(response_column="y", seed=SEED, **dict(PARAMS, ntrees=2)).train(fr)
    more = DRF(response_column="y", seed=SEED,
               **dict(PARAMS, ntrees=3, checkpoint=first.key)).train(fr)
    a, b = more.booster.trees_per_class[0], model.booster.trees_per_class[0]
    for name in ("node", "feat", "split_bin", "leaf", "child"):
        for u, v in zip(getattr(a, name), getattr(b, name)):
            np.testing.assert_array_equal(u, v)
    np.testing.assert_allclose(more._predict_raw(fr), model._predict_raw(fr), atol=1e-6)


# ---------------------------------------------------------------------------
# the budget


def test_max_runtime_secs_bounds_a_forest(deep):
    model, fr, X, y = deep
    t0 = time.time()
    m = DRF(response_column="y", seed=SEED, **dict(PARAMS, ntrees=10000, max_depth=6,
                                                   max_runtime_secs=3.0)).train(fr)
    assert time.time() - t0 < 60 and 0 < m.ntrees_built < 10000
    assert m.booster.trees_per_class[0].ntrees == m.ntrees_built


def test_stopping_rounds_stop_a_forest_on_its_averaged_margin(deep):
    model, fr, X, y = deep
    m = DRF(response_column="y", seed=SEED, **dict(
        PARAMS, ntrees=400, max_depth=4, stopping_rounds=2, stopping_tolerance=0.05,
        score_tree_interval=2)).train(fr)
    assert m.ntrees_built < 400 and len(m.scoring_history) >= 3
    # the stopping metric is the logloss of the forest's probabilities
    assert all(0.0 < h["score"] < 1.0 for h in m.scoring_history)


# ---------------------------------------------------------------------------
# what a level costs


def block_of(rows, depth=20):
    p = TreeParams(ntrees=0, seed=0, max_depth=depth, nbins=20, learn_rate=1.0,
                   reg_lambda=0.0, sample_rate=0.632, mtries=5)
    fn = booster._make_block_fn("fixed", 1, 1, p, default_mesh(n_devices=1), subtract=False)
    S = jax.ShapeDtypeStruct
    return fn.lower(S((rows, 28), jnp.int32), S((rows, 1), jnp.float32), S((rows,), jnp.bool_),
                    S((rows, 1), jnp.float32), S((1, 2), jnp.uint32), None, None, None)


def test_a_depth_20_block_grows_with_its_rows_not_with_its_nodes(monkeypatch):
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "scatter")
    low = [block_of(n) for n in (2048, 4096)]
    temps = [lw.compile().memory_analysis().temp_size_in_bytes for lw in low]
    assert temps[1] < 2.3 * temps[0], temps
    # a level of 2^19 nodes would hold 2^19 x 5 x 21 x 3 floats alone
    assert temps[1] < (2**19 * 5 * 21 * 3 * 4) / 4, temps
    # no tensor of the frontier levels spans rows x nodes: every shape of the
    # text holds fewer elements than rows x 1024 (the first frontier level's
    # nodes); the dense levels reach rows x 512
    rows = 4096
    shapes = re.findall(r"tensor<([0-9x]+)x[a-z]", low[1].as_text())
    biggest = max(int(np.prod([int(v) for v in s.split("x")])) for s in shapes)
    assert biggest < rows * 1024, biggest


def test_level_plan_marks_the_frontier_levels_with_their_slots():
    p = TreeParams(max_depth=20, min_rows=1.0)
    plan = level_plan(p, subtract=True, impl="pallas", rows=8_000_256)
    assert [lv[2] for lv in plan[:11]] == ["nodematmul"] * 8 + ["sorted"] * 3
    # every frontier level launches the deepest one's slots (one scanned level)
    assert plan[11:] == tuple((2**d, 2**19, "frontier") for d in range(11, 20))
    plan = level_plan(p, subtract=False, impl="scatter", rows=4000)
    assert [lv[2] for lv in plan].count("frontier") == 10 and len(plan) == 20
    assert plan[10] == (2**10, 4000, "frontier") and plan[-1] == (2**19, 4000, "frontier")
    assert level_plan(TreeParams(max_depth=20, min_rows=5.0), False, "scatter",
                      rows=4000)[-1] == (2**19, 800, "frontier")
    # the dense trees' plans are as they were
    assert level_plan(TreeParams(max_depth=10), True, "pallas", rows=10**6) == level_plan(
        TreeParams(max_depth=10), True, "pallas")


def test_the_fit_says_its_frontier(deep):
    model, fr, X, y = deep
    blocks = [e for e in timeline.snapshot(4096) if e["kind"] == "tree_block"
              and e.get("hist_slots") and len(e["hist_slots"]) == 14]
    assert blocks and [lv[2] for lv in blocks[-1]["hist_slots"]][10:] == ["frontier"] * 4
    reads = [e for e in timeline.snapshot(4096) if e["kind"] == "tree_readback"
             and "frontier_nodes" in e]
    assert reads and reads[-1]["frontier_nodes"] > 0
    from h2o3_tpu.models.tree.booster import TREE_FRONTIER_NODES

    assert TREE_FRONTIER_NODES.value() > 0


def test_the_frontier_kernel_interpreted_is_its_xla_form():
    from h2o3_tpu.ops import histogram as H, pallas_histogram as PH

    rng = np.random.default_rng(1)
    for n, S, m, B1 in [(3000, 700, 5, 21), (1024, 3, 2, 21), (2500, 600, 4, 9)]:
        codes = jnp.asarray(rng.integers(0, B1, size=(n, m)), jnp.int32)
        slots = jnp.asarray(rng.integers(0, S + 1, size=n), jnp.int32)  # S: no slot
        g = jnp.asarray(rng.normal(size=n), jnp.float32)
        h = jnp.asarray(rng.random(n), jnp.float32)
        want = H._shard_histogram(codes, jnp.where(slots < S, slots, -1), g, h, S, B1)
        got = PH.build_frontier_histogram_pallas(codes, slots, g, h, S, B1, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-5)


def test_a_deep_fit_through_the_kernels_interpreted_is_the_scatter_fit(monkeypatch):
    X, y = table(800)
    fr = frame_of(X, y)
    kw = dict(PARAMS, max_depth=12, ntrees=1)
    by_scatter = DRF(response_column="y", seed=SEED, **kw).train(fr)
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "pallas")
    from h2o3_tpu.frame import devcache

    devcache.DEVCACHE.clear()
    by_kernels = DRF(response_column="y", seed=SEED, **kw).train(fr)
    a, b = by_kernels.booster.trees_per_class[0], by_scatter.booster.trees_per_class[0]
    np.testing.assert_array_equal(a.node[0], b.node[0])
    np.testing.assert_allclose(a.leaf[0], b.leaf[0], atol=1e-6)


# ---------------------------------------------------------------------------
# what cannot read a deep tree says so


def tree_route(model):
    from h2o3_tpu.api import handlers
    from h2o3_tpu.api.server import RequestServer

    registry = RequestServer()
    handlers.register_all(registry, None)
    return registry.dispatch("GET", f"/3/Trees/{model.key}/0", {})


@pytest.mark.parametrize("reader", ["shap", "pojo", "mojo", "mojo_ref", "rulefit",
                                    "tree_route", "enum_on_frontier"])
def test_what_cannot_read_a_deep_tree_refuses_it_by_name(deep, reader, tmp_path):
    from h2o3_tpu.models import mojo_ref, pojo, rulefit

    model, fr, X, y = deep
    name = {"shap": "shap", "pojo": "pojo", "mojo": "mojo", "mojo_ref": "mojo",
            "rulefit": "rulefit", "tree_route": "/3/Tree", "enum_on_frontier": "set-valued"}[reader]
    with pytest.raises(Exception, match=r"frontier|past the dense") as err:
        if reader == "shap":
            model.predict_contributions(fr)
        elif reader == "pojo":
            pojo._tree_tables(model)
        elif reader == "mojo":
            model.download_mojo(str(tmp_path / "m.zip"))
        elif reader == "mojo_ref":
            mojo_ref.write_mojo(model, str(tmp_path / "r.zip"))
        elif reader == "rulefit":
            rulefit._extract_rules(model, model.data_info)
        elif reader == "tree_route":
            tree_route(model)
        else:
            rng = np.random.default_rng(3)
            cat = Column("c", rng.integers(0, 6, size=N).astype(np.int32), ColType.CAT,
                         [f"l{i}" for i in range(6)])
            DRF(response_column="y", seed=SEED, categorical_encoding="enum",
                **dict(PARAMS, ntrees=1)).train(Frame([cat] + [fr.col(n) for n in fr.names]))
    assert name in str(err.value)


@pytest.mark.parametrize("max_depth,homed", [(10, True), (11, False), (12, False), (20, False)])
def test_a_chunk_homed_frame_past_the_dense_ladder_is_materialized(max_depth, homed):
    """The chunk-homed engine (``dist_hist``) builds every level as a dense
    histogram of 2^d nodes; a tree with frontier levels is fitted on the
    materialized frame instead, as every feature that engine lacks is."""
    from h2o3_tpu.models.tree import dist_hist
    from h2o3_tpu.models.tree.drf import DRFParameters

    class Homed:
        chunk_layout = {"groups": []}

    p = DRFParameters(response_column="y", max_depth=max_depth)
    assert dist_hist.use_dist(Homed(), p, "label_encoder") is homed
