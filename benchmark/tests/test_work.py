"""The work-and-bytes function against hand-worked values for both
configurations, and the readers that must never pass 100%."""

import json
import os

import pytest

from lib import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(HERE, "lib", "peaks.json")) as f:
        return json.load(f)["TPU v5 lite"]


def test_depth6_256_bins():
    c = load("gbm-higgs-d6-b256")
    w = work.tree_work(c["table"]["rows"], 28, 2, c["params"])
    # a level: 4,000,000 rows x (28 codes of 2 bytes + g, h, node id) = 272 MB
    assert w["hist_bytes"] == 6 * 4_000_000 * (28 * 2 + 12) == 1_632_000_000
    assert w["hist_ops"] == 6 * 2 * 4_000_000 * 28
    assert w["bytes"] == 1_632_000_000 + 4_000_000 * 28
    t = work.least_seconds(w["ops"], w["bytes"], peaks())
    assert t["bound"] == "bytes"
    assert t["seconds"] == pytest.approx(1.744e9 / 819e9)  # 2.13 ms a tree


def test_depth10_20_bins_sampled():
    c = load("gbm-higgs-automl-d10")
    w = work.tree_work(c["table"]["rows"], 28, 2, c["params"])
    # 80% of 6,000,000 rows, 22 of 28 columns, codes of one byte
    assert w["hist_bytes"] == pytest.approx(10 * 4_800_000 * (22 + 12))
    assert w["hist_ops"] == pytest.approx(10 * 2 * 4_800_000 * 22)
    assert w["bytes"] == pytest.approx(1_632_000_000 + 6_000_000 * 28)
    assert work.least_seconds(w["ops"], w["bytes"], peaks())["bound"] == "bytes"


def test_code_width_and_class_trees():
    assert [work.code_bytes(n) for n in (20, 255, 256, 65535, 65536)] == [1, 1, 2, 2, 4]
    p = {"max_depth": 4, "nbins": 20, "distribution": "multinomial"}
    one = work.tree_work(1000, 10, 1, dict(p, distribution="gaussian"))
    seven = work.tree_work(1000, 10, 7, p)
    assert seven["bytes"] == pytest.approx(7 * one["bytes"])


def test_a_feature_is_as_wide_as_its_own_bins():
    """A column the generator types ``cat`` has a bin a level, capped by
    ``nbins_cats``; every other feature, and every feature of a generator
    that states no columns, has ``nbins``."""
    p = {"max_depth": 4, "nbins": 20, "distribution": "gaussian"}
    cols = [{"name": "a", "type": "num"},
            {"name": "origin", "type": "cat", "domain": [str(i) for i in range(300)]},
            {"name": "carrier", "type": "cat", "domain": [str(i) for i in range(22)]}]
    assert work.feature_bins(3, p, cols) == [20, 300, 22]
    assert work.feature_bins(3, dict(p, nbins_cats=64), cols) == [20, 64, 22]
    assert work.feature_bins(3, p) == [20, 20, 20]
    wide = work.tree_work(1000, 3, 1, p, cols)
    assert wide["hist_bytes"] == 4 * 1000 * ((1 + 2 + 1) + 12)
    assert wide["hist_ops"] == work.tree_work(1000, 3, 1, p)["hist_ops"]
    for name in ("gbm-higgs-d6-b256", "gbm-higgs-automl-d10"):
        c = load(name)
        numeric = [{"name": f"f{i}", "type": "num"} for i in range(28)]
        assert (work.tree_work(c["table"]["rows"], 28, 2, c["params"], numeric)
                == work.tree_work(c["table"]["rows"], 28, 2, c["params"]))


def test_work_ignores_what_the_program_chose():
    """Only shapes go in: there is no argument for a kernel, a padding, a
    subtraction flag or a stored dtype."""
    import inspect

    assert list(inspect.signature(work.tree_work).parameters) == [
        "rows", "features", "classes", "params", "columns"]
    c = load("gbm-higgs-d6-b256")
    base = work.tree_work(4_000_000, 28, 2, c["params"])
    assert work.tree_work(4_000_000, 28, 2, dict(c["params"], ntrees=7, learn_rate=0.5)) == base
