"""`correct` fails what it must.

* The control — the reference in the program's place with gradients and
  hessians rounded to fp8, the precision below the configuration's bfloat16
  — is not correct, while the same builder at bfloat16 and at float64 is.
* A run of the harness (the look for a chip skipped: ``--rehearse``) with
  the timed path broken underneath comes out with ``correct`` false, once
  for each fault a one-chip training cell can have: a block that returns
  its state unchanged, half of the batch left out of the histograms, an
  answer (the leaf values) altered where it is produced.
"""

import json
import os

import numpy as np
import pytest

from lib import checks, harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELLS = ["gbm-higgs-d6-b256", "gbm-higgs-automl-d10"]
ref = harness.load_named(ROOT, "references", "hist-gbm")


def higgs_synth(rows, features, seed):
    make = harness.load_named(ROOT, "tables", "higgs-synth").make
    return make({"features": features, "classes": 2}, rows, seed)


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def quantile_edges(X, nbins):
    qs = np.linspace(0, 1, nbins + 1)[1:-1]
    return np.stack([np.quantile(X[:, f].astype(np.float64), qs)
                     for f in range(X.shape[1])])


@pytest.fixture(scope="module", params=CELLS)
def small(request):
    config = load("configs", request.param)
    X, y = higgs_synth(60_000, 28, 5)
    p = ref.RefParams.from_config(config["params"], 5)
    codes = ref.bin_codes(X, quantile_edges(X, p.nbins))
    return request.param, p, codes, y.astype(np.float64)


JUDGED = ref.JUDGED


def test_control_precision(small):
    """float64 is correct, the fp8 control is not, and the stated precision
    (bfloat16) reads at least three times below the control on a number the
    control fails.  (At this size a leaf holds some hundred rows, so
    bfloat16 itself reads higher than at the cell's size, where the limits
    were set: it is held against the control here, not against the limits.)"""
    name, p, codes, y = small
    limits = checks.load_limits(ROOT, name)
    read = {prec: ref.judge(codes, y, p, ref.boost(codes, y, p, 2, precision=prec), [0, 1])
            for prec in ("float64", "bfloat16", "fp8")}
    assert all(read["float64"][k] <= limits[k] for k in JUDGED), read["float64"]
    failed = [k for k in JUDGED if read["fp8"][k] > limits[k]]
    assert failed, read["fp8"]
    assert any(read["fp8"][k] >= 3 * read["bfloat16"][k] for k in failed), read


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "leaf_altered"])
def test_planted_fault_in_the_reference(small, fault):
    name, p, codes, y = small
    limits = checks.load_limits(ROOT, name)
    judged = ref.judge(codes, y, p, ref.boost(codes, y, p, 3, fault=fault), [0, 1, 2])
    assert any(judged[k] > limits[k] for k in JUDGED), judged


def test_unequal_bins_fail_the_rank_gap():
    X, _ = higgs_synth(60_000, 28, 5)
    lo, hi = X.min(axis=0), X.max(axis=0)
    width = np.stack([np.linspace(lo[f], hi[f], 22)[1:-1] for f in range(28)])
    limit = checks.load_limits(ROOT, CELLS[1])["bin_rank_gap"]
    assert ref.bin_rank_gap(ref.bin_codes(X, width), 20) > limit
    assert ref.bin_rank_gap(ref.bin_codes(X, quantile_edges(X, 20)), 20) < limit


# -- the harness, with the program broken underneath ---------------------------


def drive(seconds=3.0):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][0]
    return harness.run(
        cell=cell, config=load("configs", cell["config"]),
        traffic=load("traffic", cell["traffic"]), seed=11, seconds=seconds,
        trace=False, rehearse=True, t_start=0.0, root=ROOT, metrics=[])


def test_sound_program_is_correct():
    out = drive()
    assert out["correct"] is True and out["failed"] == 0, out["checks"]
    assert out["window"]["blocks"] >= 2  # the block-to-block state was judged


def test_state_unchanged(monkeypatch):
    import jax.numpy as jnp
    from h2o3_tpu.models.tree import booster

    make = booster._make_block_fn

    def broken(*a, **k):
        step = make(*a, **k)

        def stale(bins, y, valid, margin, *rest):
            kept = jnp.array(margin, copy=True)
            _, trees = step(bins, y, valid, margin, *rest)
            return kept, trees

        return stale

    monkeypatch.setattr(booster, "_make_block_fn", broken)
    out = drive()
    assert out["window"]["blocks"] >= 2
    assert out["correct"] is False, out["checks"]


def test_half_of_the_batch_left_out(monkeypatch):
    import jax.numpy as jnp
    from h2o3_tpu.models.tree import booster

    build = booster.build_histogram_sharded

    def half(bins, nodes, *a, **k):
        odd = jnp.arange(nodes.shape[0]) % 2 == 1
        return build(bins, jnp.where(odd, -1, nodes), *a, **k)

    monkeypatch.setattr(booster, "build_histogram_sharded", half)
    booster._make_block_fn.cache_clear()
    try:
        out = drive()
    finally:
        booster._make_block_fn.cache_clear()
    assert out["correct"] is False, out["checks"]


def test_answer_altered_where_it_is_produced(monkeypatch):
    from h2o3_tpu.models.tree import booster

    append = booster.Trees.append

    def altered(self, feat, split_bin, default_left, is_split, leaf):
        append(self, feat, split_bin, default_left, is_split, np.asarray(leaf) * 1.1)

    monkeypatch.setattr(booster.Trees, "append", altered)
    out = drive()
    assert out["correct"] is False, out["checks"]


def test_reported_metric_altered(monkeypatch):
    from h2o3_tpu.models import metrics

    real = metrics.binomial_metrics

    def half_rows(actual, prob, *a, **k):
        return real(actual[::2], prob[::2], *a, **k)

    monkeypatch.setattr(metrics, "binomial_metrics", half_rows)
    out = drive()
    assert out["correct"] is False, out["checks"]
    v, lim = out["checks"]["logloss_gap"]
    assert v > lim


def test_init_margin_from_half_of_the_rows(monkeypatch):
    from h2o3_tpu.models.tree import common

    real = common.init_margin

    def half(distribution, y, nclasses, weights=None):
        return real(distribution, y[::2], nclasses, weights=weights)

    monkeypatch.setattr(common, "init_margin", half)
    out = drive()
    v, lim = out["checks"]["init_margin_gap"]
    assert out["correct"] is False and v > lim, out["checks"]


# -- other shapes as data: K classes, a regression -----------------------------


@pytest.mark.parametrize("distribution,classes,metrics", [
    ("multinomial", 3, ["logloss"]), ("gaussian", 1, ["mse", "rmse"])])
def test_other_distributions_are_data(distribution, classes, metrics, monkeypatch):
    """A table of K classes or a numeric response goes through the same
    generator look-up, frame, entry, extraction and comparison, the limits'
    keys naming the metrics; and a fault planted in the program is seen."""
    config = {"builder": "h2o3_tpu.models.tree.gbm:GBM", "response_column": "y",
              "reference": "hist-gbm",
              "table": {"generator": "linear-synth", "features": 10, "classes": classes},
              "params": {"distribution": distribution, "max_depth": 4, "nbins": 32,
                         "learn_rate": 0.1, "min_rows": 5.0, "min_split_improvement": 1e-5,
                         "sample_rate": 0.8, "col_sample_rate_per_tree": 0.8}}
    numbers = ["bin_rank_gap", "init_margin_gap", *JUDGED, *(m + "_gap" for m in metrics)]
    table = harness.make_table(ROOT, config, 20_000, 7)
    X, y = table["X"], table["y"]
    builder = harness.load_builder(config["builder"])
    named = checks.load_reference(ROOT, config, dict.fromkeys(numbers, 1.0))

    def read():
        served = harness.fit(builder, config, harness.make_frame(X, y, config), 7,
                             {"ntrees": 20})
        answer = named.extract(served["model"], numbers)
        assert len(answer["trees"]) == max(classes, 1) and sorted(answer["reported"]) == metrics
        return named.compare(config, 7, table, [answer], 16, numbers)

    sound = read()
    assert max(sound.values()) < 1e-3 and sound[metrics[0] + "_gap"] < 1e-6, sound
    from h2o3_tpu.models.tree import booster

    append = booster.Trees.append
    monkeypatch.setattr(
        booster.Trees, "append",
        lambda self, f, b, dl, sp, leaf: append(self, f, b, dl, sp, np.asarray(leaf) * 1.1))
    broken = read()
    assert broken["leaf_gap"] > 0.05, broken
