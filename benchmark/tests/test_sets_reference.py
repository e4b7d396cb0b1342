"""The reference for set-valued splits, and what ``correct`` must fail in the
cell that names it: the fp8 control, the planted faults — thresholds on label
codes under the name ``enum`` (the parent's behaviour) and one flipped bit of
a set at scoring among them — a level map that is not the table's, and a
model that holds no set at all.  The new cell's rehearsal on the CPU ends
``correct``.  All host numpy or CPU, small sizes.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lib import checks, harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CONFIG = "gbm-airline-10m-d10"
CELL = CONFIG + ".budget-fit"
ref = harness.load_named(ROOT, "references", "hist-gbm-sets")


def load_config():
    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


#: the precision the configuration states: what a judge rounds gradients to
STATED = ref.stated_precision(load_config())


@pytest.fixture(scope="module")
def small():
    """60,000 rows of the cell's own table, binned by its columns."""
    config = load_config()
    table = harness.make_table(ROOT, config, 60_000, 5)
    p = ref.RefParams.from_config(config["params"], 5)
    cat_levels = ref.cat_levels_of(table["columns"], 8)
    codes = ref.bin_codes(table["X"], ref.quantile_edges(table["X"], p.nbins, cat_levels),
                          cat_levels)
    return p, codes, table["y"].astype(np.float64), cat_levels, table


def test_the_table_is_the_published_shape(small):
    p, codes, y, cat_levels, table = small
    assert cat_levels == [12, 31, 7, 0, 22, 300, 300, 0]
    assert [c["name"] for c in table["columns"]] == [
        "Month", "DayofMonth", "DayOfWeek", "DepTime", "UniqueCarrier", "Origin",
        "Dest", "Distance"]
    assert 0.17 < y.mean() < 0.21 and not np.isnan(table["X"]).any()
    # a few hubs hold most flights, and a busy airport may have any code
    counts = np.bincount(table["X"][:, 5].astype(int), minlength=300)
    assert np.sort(counts)[-10:].sum() > 0.4 * len(y)
    assert abs(np.corrcoef(np.arange(300), counts)[0, 1]) < 0.3
    assert codes.dtype == np.uint16 and codes.max() <= 300


def test_control_precision(small):
    """float64 is correct by the cell's limits, the fp8 control is not, and
    bfloat16 reads at least three times below the control on a number the
    control fails (as test_correct.py holds the Higgs cells)."""
    p, codes, y, cat_levels, _ = small
    limits = checks.load_limits(ROOT, CONFIG)
    read = {prec: ref.judge(codes, y, p, ref.boost(codes, y, p, 2, cat_levels, precision=prec),
                            [0, 1], cat_levels, stated=STATED)
            for prec in ("float64", "bfloat16", "fp8")}
    assert all(read["float64"][k] <= limits[k] for k in ref.JUDGED), read["float64"]
    failed = [k for k in ref.JUDGED if read["fp8"][k] > limits[k]]
    assert failed, read["fp8"]
    assert any(read["fp8"][k] >= 3 * read["bfloat16"][k] for k in failed), read


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_planted_fault_in_the_reference(small, fault):
    p, codes, y, cat_levels, _ = small
    limits = checks.load_limits(ROOT, CONFIG)
    judged = ref.judge(codes, y, p, ref.boost(codes, y, p, 3, cat_levels, fault=fault),
                       [0, 1, 2], cat_levels, stated=STATED)
    assert any(judged[k] > limits[k] for k in ref.JUDGED), judged
    if fault == "label_codes":
        # today's behaviour under the name enum: trees that are consistent in
        # themselves (leaves exact) and forgo the gain a set takes
        assert judged["gain_forgone"] > limits["gain_forgone"] \
            or judged["split_gap"] > limits["split_gap"], judged
        assert judged["leaf_gap"] <= limits["leaf_gap"]


def test_a_judge_keeps_runs_of_equal_ratios_whole():
    # levels 0..3 of one node: 0 and 1 tie; a prefix may not end between them
    hist = np.zeros((1, 1, 5, 3))
    hist[0, 0, :4] = [(-2, 2, 10), (-2, 2, 10), (1, 2, 10), (3, 2, 10)]
    cat = np.array([True])
    free, _, order, present = ref._candidates(hist, cat, 1.0)
    whole, _, _, _ = ref._candidates(hist, cat, 1.0, whole_ties=True)
    assert np.isfinite(free[0, 0, :3, 0]).all() and not np.isfinite(free[0, 0, 3, 0])
    assert not np.isfinite(whole[0, 0, 0, 0]) and np.isfinite(whole[0, 0, 1:3, 0]).all()
    assert order[0, 0].tolist() == [0, 1, 2, 3] and present.all()


def test_a_judge_keeps_the_prefixes_the_stated_precision_holds_too():
    # by the exact sums level 1 comes before level 2; by the sums of the
    # rounded gradients after it: {0, 1} is a prefix of one order only
    hist = np.zeros((1, 1, 5, 3))
    hist[0, 0, :4] = [(-4, 2, 10), (1.00, 2, 10), (1.01, 2, 10), (3, 2, 10)]
    stated = hist.copy()
    stated[0, 0, 1, 0], stated[0, 0, 2, 0] = 1.02, 1.0
    cat = np.array([True])
    exact, _, _, _ = ref._candidates(hist, cat, 1.0, whole_ties=True)
    both, _, _, _ = ref._candidates(hist, cat, 1.0, whole_ties=True, stated=stated)
    assert np.isfinite(exact[0, 0, :3, 0]).all()
    assert np.isfinite(both[0, 0, [0, 2], 0]).all() and not np.isfinite(both[0, 0, 1, 0])
    # rounded to one value, the two may come either way: no prefix between them
    stated[0, 0, 1, 0] = stated[0, 0, 2, 0] = 1.0
    tied, _, _, _ = ref._candidates(hist, cat, 1.0, whole_ties=True, stated=stated)
    assert np.isfinite(tied[0, 0, [0, 2], 0]).all() and not np.isfinite(tied[0, 0, 1, 0])
    # a numeric feature's thresholds are what they were
    num, _, _, _ = ref._candidates(hist, ~cat, 1.0, whole_ties=True, stated=stated)
    assert np.isfinite(num[0, 0, :3, 0]).all()


def test_one_flipped_bit_of_a_set_at_scoring_fails_logloss_gap(small):
    """Metrics reported from sets with one bit flipped (a scoring walk that
    misreads one level of one node) against the walk of the true sets."""
    p, codes, y, cat_levels, _ = small
    limits = checks.load_limits(ROOT, CONFIG)
    model = ref.boost(codes, y, p, 2, cat_levels)
    mine = ref.score(codes, y, p, model)
    theirs = ref.score(codes, y, p, ref.flip_one_bit(model))
    assert abs(theirs["logloss"] - mine["logloss"]) / mine["logloss"] > limits["logloss_gap"]
    assert ref.score(codes, y, p, model)["logloss"] == mine["logloss"]


def test_the_limits_are_numbers_of_the_reference():
    limits = checks.load_limits(ROOT, CONFIG)
    assert set(limits) <= set(ref.NUMBERS) and ref.NUMBERS == ref.base.NUMBERS
    assert checks.load_reference(ROOT, load_config(), limits).NUMBERS == ref.NUMBERS


def test_a_level_map_that_is_not_the_tables_is_an_error(small):
    _, _, _, _, table = small
    domains = {c["name"]: list(c["domain"]) for c in table["columns"] if c["type"] == "cat"}
    assert ref.foreign_level_maps(table["columns"], domains) == []
    domains["Origin"] = domains["Origin"][::-1]
    assert ref.foreign_level_maps(table["columns"], domains) == ["Origin"]


def test_a_model_without_sets_is_an_error():
    """What the parent of the set-valued splits fitted under the name enum
    (label codes): extraction stops the run."""
    config = load_config()
    table = harness.make_table(ROOT, config, 2000, 3)
    frame = harness.make_frame(table["X"], table["y"], config, table["columns"])
    served = harness.fit(harness.load_builder(config["builder"]), config, frame, 3,
                         {"ntrees": 1, "max_depth": 3, "categorical_encoding": "label_encoder"})
    with pytest.raises(SystemExit, match="holds no set of levels"):
        ref.extract(served["model"], list(checks.load_limits(ROOT, CONFIG)))


def test_rehearsal_of_the_new_cell_is_correct():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147483659",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearse"] is True and line["metrics"] == {}
    assert set(line["checks"]) == set(checks.load_limits(ROOT, CONFIG))
    assert "check gain_forgone" in done.stderr


def test_the_cell_is_listed_with_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["workloads"][-1]["name"] == CELL and bench["workloads"][-1]["chips"] == 1
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == ["set_split_ms_per_tree", "set_route_ms_per_tree"]
    # every accepted per-layer metric is read in the new cell too
    assert all(CELL in m["workloads"] for m in bench["per_layer"])
    for m in mine:
        assert os.path.exists(os.path.join(HERE, "metrics", m["name"] + ".py"))


def test_a_scope_inside_a_phase_is_summed_by_its_pattern(monkeypatch):
    run = {"cell": "c", "trace": {"busy_s": 1.0}, "served": [{"blocks": [{"trees": 4}]}]}
    ops = [("jit(block_fn)/L09/split/sets/sort", 0.008),
           ("jit(block_fn)/L09/split/reduce_max", 0.002),
           ("jit(block_fn)/L03/route/sets/dot_general", 0.012),
           ("", 0.5)]
    split = harness.load_named(ROOT, "metrics", "set_split_ms_per_tree")
    monkeypatch.setattr(split, "block_ops", lambda run: (ops, [name for name, _ in ops]))
    assert split.read(run) == pytest.approx(2.0)
    route = harness.load_named(ROOT, "metrics", "set_route_ms_per_tree")
    assert route.read(run) == pytest.approx(3.0)  # through the module loaded above
    # a program whose block has no such scope (the parent): nothing to read
    monkeypatch.setattr(split, "block_ops", lambda run: (ops[1:2], [ops[1][0]]))
    assert split.read(run) is None and route.read(run) is None
    monkeypatch.undo()
    assert split.read(dict(run, trace=None)) is None  # untraced
