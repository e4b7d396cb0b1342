"""The KDD Cup 1999 deployment: the generator's columns in the published
order with their types and level counts, the five classes at the published
counts, the share of exact duplicate rows, and the cell with its metrics in
``BENCHMARK.json``, each looked up by name.  The reference of the cell
(``hist-gbm-classes``) in the program's place must be held correct, the fp8
control and a planted fault must not.  All on the CPU at small sizes.
"""

import json
import os

import numpy as np
import pytest

from lib import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CONFIG = "gbm-kdd99-5class-d5"
CELL = CONFIG + ".budget-fit"
gen = harness.load_named(ROOT, "tables", "kdd99-synth")
ref = harness.load_named(ROOT, "references", "hist-gbm-classes")


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def test_the_columns_are_the_published_ones_in_order():
    cols = gen.columns({})
    assert len(cols) == 41
    names = [c["name"] for c in cols]
    assert names[:6] == ["duration", "protocol_type", "service", "flag", "src_bytes",
                         "dst_bytes"]
    assert names[-1] == "dst_host_srv_rerror_rate" and names[19] == "num_outbound_cmds"
    cats = {c["name"]: c["domain"] for c in cols if c["type"] == "cat"}
    assert {k: len(v) for k, v in cats.items()} == {
        "protocol_type": 3, "service": 70, "flag": 11}
    for domain in cats.values():  # codes in the order of the sorted names
        assert domain == sorted(domain) and len(set(domain)) == len(domain)


def test_the_classes_come_at_the_published_counts():
    counts, distinct = gen.class_counts(gen.ROWS)
    assert gen.ROWS == 4_898_431
    assert dict(zip(gen.CLASSES, counts)) == {
        "normal": 972_781, "dos": 3_883_370, "probe": 41_102, "r2l": 1_126, "u2r": 52}
    assert sum(distinct) == 1_074_992
    small, small_distinct = gen.class_counts(50_000)
    assert sum(small) == 50_000 and min(small) >= 1
    assert all(d <= c for d, c in zip(small_distinct, small))


@pytest.fixture(scope="module")
def table():
    config = load(f"benchmark/configs/{CONFIG}.json")
    return harness.make_table(ROOT, config, 50_000, 2**33 + 11), config


def test_the_rows_and_their_duplicates(table):
    t, config = table
    X, y = t["X"], t["y"]
    assert X.shape == (50_000, 41) and X.dtype == np.float32
    counts, distinct = gen.class_counts(50_000)
    assert np.bincount(y, minlength=5).tolist() == counts
    rows = np.unique(X.view(np.dtype((np.void, 4 * 41))).ravel()).size
    # each class's distinct rows (prototypes), none shared between classes
    assert rows == sum(distinct)
    assert 1 - rows / len(y) == pytest.approx(1 - 1_074_992 / 4_898_431, abs=2e-3)
    assert not np.isnan(X).any() and (X[:, 19] == 0).all()
    for j in (1, 2, 3):  # level codes within their domains
        assert X[:, j].min() >= 0 and X[:, j].max() < len(t["columns"][j]["domain"])
    again = harness.make_table(ROOT, config, 50_000, 2**33 + 11)
    np.testing.assert_array_equal(again["X"], X)


def test_the_cell_and_its_metrics_by_name():
    bench = load("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    cell = cells[CELL]
    assert cell["config"] == CONFIG and cell["chips"] == 1 and cell["traffic"] == "budget-fit"
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    for name in ("class_hist_roofline", "multiclass_step_mfu", "hist_launches_per_round"):
        assert metrics[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))
    # the accepted metrics that count the codes once a class tree, and the
    # ones no cell reports, do not list it
    for name in ("hist_kernel_roofline", "train_step_mfu", "score_matrix_s", "score_bin_s",
                 "score_traverse_s", "sharded_step_mfu", "frontier_hist_roofline"):
        assert CELL not in metrics[name]["workloads"]
    config = load(configs[CONFIG]["file"])
    assert config["reference"] == "hist-gbm-classes"
    limits = load(f"benchmark/limits/{CONFIG}.json")["limits"]
    assert set(limits) <= set(ref.NUMBERS)


def test_the_class_work_counts_the_codes_once_a_level():
    from lib import work, work_classes

    config = load(f"benchmark/configs/{CONFIG}.json")
    params = config["params"]
    rows, F = 4_898_431, 41
    level = work_classes.level_work(rows, F, 5, params)
    assert level["bytes"] == rows * (F * 1 + 12 * 5)
    assert level["ops"] == 2.0 * rows * F * 5
    whole = work_classes.round_work(rows, F, 5, params)
    per_tree = work.tree_work(rows, F, 5, params)
    # lib/work reads the codes once a class tree: K times here
    assert per_tree["hist_bytes"] > whole["hist_bytes"]
    assert whole["bytes"] - whole["hist_bytes"] == 5 * rows * 28.0


@pytest.fixture(scope="module")
def boosted(table):
    t, config = table
    X, y = t["X"], t["y"].astype(np.float64)
    p = ref.RefParams.from_config(config["params"], 7)
    cat_levels = ref.cat_levels_of(t["columns"], X.shape[1])
    codes = ref.bin_codes(X, ref.quantile_edges(X, p.nbins, cat_levels), cat_levels)
    return codes, y, p, cat_levels, config


def judged(boosted, **kw):
    codes, y, p, cat_levels, config = boosted
    model = ref.boost(codes, y, p, 3, cat_levels, 5, **kw)
    return ref.judge(codes, y, p, model, [0, 2], cat_levels, 5,
                     stated=ref.stated_precision(config))


def test_the_reference_in_the_programs_place_is_correct(boosted):
    limits = load(f"benchmark/limits/{CONFIG}.json")["limits"]
    got = judged(boosted, precision="bfloat16")
    for k in ("split_gap", "gain_forgone", "leaf_gap", "leaf_gap_mean"):
        assert got[k] <= limits[k], (k, got[k], limits[k])


@pytest.mark.parametrize("variant", [{"precision": "fp8"}, {"fault": "state_unchanged"},
                                     {"fault": "leaf_altered"}])
def test_the_control_and_a_planted_fault_are_not(boosted, variant):
    limits = load(f"benchmark/limits/{CONFIG}.json")["limits"]
    got = judged(boosted, **variant)
    assert any(got[k] > limits[k] for k in ("split_gap", "gain_forgone", "leaf_gap",
                                            "leaf_gap_mean")), got
