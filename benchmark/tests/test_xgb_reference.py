"""The reference for XGBoost's parameters, and what ``correct`` must fail in
the cell that names it: the fp8 control and the planted faults — a builder
without lambda, without gamma, with the floor on row counts, without the
class weight, and one shard of four left out of every histogram's sum among
them.  The new cell's rehearsal over four CPU devices ends ``correct``.  All
host numpy or CPU, small sizes.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lib import checks, harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CONFIG = "xgb-airline-4chip-d6"
CELL = CONFIG + ".budget-fit"
ref = harness.load_named(ROOT, "references", "hist-xgb")

#: a fault of a parameter that the deployment's own value cannot show at
#: 60,000 rows is planted against a value that binds there: the judge's
#: side of the mechanism is the same code
BINDING = {"gamma_zero": {"gamma": 30.0}, "lambda_zero": {"reg_lambda": 50.0},
           "count_floor": {"min_child_weight": 50.0}}


def load_config():
    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    """60,000 rows of the cell's own table, binned by quantiles that skip NA."""
    config = load_config()
    table = harness.make_table(ROOT, config, 60_000, 5)
    p = ref.RefParams.from_config(config["params"], 5)
    X = table["X"]
    qs = np.linspace(0, 1, p.nbins + 1)[1:-1]
    edges = np.stack([np.nanquantile(X[:, f].astype(np.float64), qs)
                      for f in range(X.shape[1])])
    return p, ref.bin_codes(X, edges), table["y"].astype(np.float64), table


def test_the_table_is_the_benchmarks_shape(small):
    p, codes, y, table = small
    X = table["X"]
    assert [c["name"] for c in table["columns"]] == [
        "Year", "Month", "DayofMonth", "DayOfWeek", "CRSDepTime", "CRSArrTime",
        "UniqueCarrier", "FlightNum", "ActualElapsedTime", "Origin", "Dest",
        "Distance", "Diverted"]
    assert all(c["type"] == "num" for c in table["columns"])
    distinct = [len(np.unique(X[~np.isnan(X[:, f]), f])) for f in range(13)]
    few = [f for f in range(13) if distinct[f] <= 31]
    assert few == [0, 1, 2, 3, 6, 12] and min(distinct) == 2
    assert all(distinct[f] > 256 for f in (4, 5, 7, 8, 11))
    na = np.isnan(X).mean(axis=0)
    assert 0.015 < na[8] < 0.025 and na[[f for f in range(13) if f != 8]].sum() == 0
    assert np.isnan(X[X[:, 12] == 1, 8]).all()
    assert 0.45 < y.mean() < 0.49
    # a few hubs hold most flights, and a busy airport may have any code
    counts = np.bincount(X[:, 9].astype(int), minlength=340)
    assert np.sort(counts)[-10:].sum() > 0.4 * len(y)
    assert abs(np.corrcoef(np.arange(340), counts)[0, 1]) < 0.3
    assert (p.reg_lambda, p.gamma, p.min_child_weight, p.scale_pos_weight) == (1.0, 0.1, 1.0, 2.0)
    assert (p.max_depth, p.nbins, p.learn_rate) == (6, 256, 0.1) and codes.shape == (13, 60_000)


def test_the_same_seed_gives_the_same_table_and_a_large_one_is_taken():
    config = load_config()
    a = harness.make_table(ROOT, config, 500, 2147489001)
    b = harness.make_table(ROOT, config, 500, 2147489001)
    c = harness.make_table(ROOT, config, 500, 2147489002)
    assert np.array_equal(a["X"], b["X"], equal_nan=True) and np.array_equal(a["y"], b["y"])
    assert not np.array_equal(a["X"], c["X"], equal_nan=True)


@pytest.mark.parametrize("sample_rate", [1.0, 0.6])
def test_the_passes_in_runs_of_rows_are_hist_gbms_arithmetic(small, monkeypatch, sample_rate):
    """The passes this file makes in runs of rows on the threads (binning,
    level histograms, routing, walk, logloss, AUC) against ``hist-gbm``'s
    over whole columns: with lambda and gamma zero and the floor on counts the
    two references are one, so each judges the other's trees as its own and
    reads the same metrics; runs of 7,001 and 3,001 rows make every pass
    cross runs."""
    p, codes, y, table = small
    monkeypatch.setattr(ref, "STEP", 7_001)
    monkeypatch.setattr(ref, "WALK_STEP", 3_001)
    base = ref.base
    qs = np.linspace(0, 1, p.nbins + 1)[1:-1]
    edges = np.stack([np.nanquantile(table["X"][:, f].astype(np.float64), qs)
                      for f in range(13)])
    assert np.array_equal(ref.bin_codes(table["X"], edges), base.bin_codes(table["X"], edges))
    plain = dataclasses.replace(p, reg_lambda=0.0, gamma=0.0, min_child_weight=None,
                                min_rows=20.0, scale_pos_weight=1.0,
                                sample_rate=sample_rate)
    theirs = base.RefParams(
        distribution="bernoulli", max_depth=p.max_depth, nbins=p.nbins,
        learn_rate=p.learn_rate, min_rows=20.0, sample_rate=sample_rate, seed=p.seed)
    mine, his = ref.boost(codes, y, plain, 4, 2), base.boost(codes, y, theirs, 4, 2)
    B1 = p.nbins + 1
    np.testing.assert_array_equal(ref.walk(codes, his["trees"][0], B1),
                                  base.walk(codes, his["trees"][0], B1))
    got, want = ref.score(codes, y, plain, his, 2), base.score(codes, y, theirs, his, 2)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-13), k
    ties = np.round(ref.walk(codes, his["trees"][0], B1), 1)  # many tied scores
    assert ref.auc(y, ties) == pytest.approx(base.auc(y, ties), rel=1e-13)
    # either judge reads the same of the other's trees, and nothing to forgo
    # in them (two candidates may tie, so the trees themselves may differ)
    for model in (mine, his):
        here = ref.judge(codes, y, plain, model, [0, 2, 3], 2)
        there = base.judge(codes, y, theirs, model, [0, 2, 3], 2)
        for k in ref.JUDGED:
            assert here[k] == pytest.approx(there[k], rel=1e-9, abs=1e-12), k
            assert here[k] < 1e-9, k


def test_control_precision(small):
    """float64 is correct by the cell's limits, the fp8 control is not, and
    bfloat16 reads at least three times below the control on a number the
    control fails (as test_correct.py holds the Higgs cells)."""
    p, codes, y, _ = small
    limits = checks.load_limits(ROOT, CONFIG)
    read = {prec: ref.judge(codes, y, p, ref.boost(codes, y, p, 3, precision=prec), [0, 2])
            for prec in ("float64", "bfloat16", "fp8")}
    assert all(read["float64"][k] <= limits[k] for k in ref.JUDGED), read["float64"]
    failed = [k for k in ref.JUDGED if read["fp8"][k] > limits[k]]
    assert failed, read["fp8"]
    assert any(read["fp8"][k] >= 3 * read["bfloat16"][k] for k in failed), read


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_planted_fault_in_the_reference(small, fault):
    p, codes, y, _ = small
    p = dataclasses.replace(p, **BINDING.get(fault, {}))
    limits = checks.load_limits(ROOT, CONFIG)
    clean = ref.judge(codes, y, p, ref.boost(codes, y, p, 3), [0, 1, 2])
    assert all(clean[k] <= limits[k] for k in ref.JUDGED), clean
    judged = ref.judge(codes, y, p, ref.boost(codes, y, p, 3, fault=fault), [0, 1, 2])
    assert any(judged[k] > limits[k] for k in ref.JUDGED), judged
    if fault in ("gamma_zero", "count_floor"):
        # trees that are consistent in themselves (leaves exact) with a
        # split the reference holds impossible
        assert judged["split_gap"] == 1.0 and judged["leaf_gap"] <= limits["leaf_gap"], judged
    if fault == "no_class_weight":
        assert judged["leaf_gap_mean"] > 100 * limits["leaf_gap_mean"], judged


def test_an_unknown_fault_is_an_error(small):
    p, codes, y, _ = small
    with pytest.raises(ValueError, match="no planted fault"):
        ref.boost(codes, y, p, 1, fault="typo")


def test_the_shard_left_out_is_a_quarter_of_the_rows(small):
    """What the fault leaves out is what a device of four holds: the second
    of four equal runs of rows, in every histogram of every level."""
    p, codes, y, _ = small
    n = len(y)
    kept = np.arange(n) * ref.SHARDS // n != 1
    assert kept.sum() == n - n // 4 and not kept[n // 4:n // 2].any()
    assert ref.faulty_params(p, "shard_dropped") == p
    assert ref.faulty_params(p, "count_floor").min_child_weight is None
    assert ref.faulty_params(p, "count_floor").min_rows == p.min_child_weight


def test_the_judge_gives_the_floor_the_room_of_the_stated_precision():
    # one node: the best threshold's left child sums hessians of 0.995
    hist = np.zeros((1, 1, 4, 3))
    hist[0, 0, :3] = [(-3.0, 0.995, 9), (1.0, 3.0, 9), (2.5, 3.0, 9)]
    p = ref.RefParams(distribution="bernoulli", max_depth=1, nbins=3, learn_rate=1.0,
                      min_child_weight=1.0)
    strict = ref._gains(hist, p)[0]
    lawful = ref._gains(hist, p, slack=ref.FLOOR_SLACK)[0]
    assert not np.isfinite(strict[0, 0, 0, 0]) and np.isfinite(lawful[0, 0, 0, 0])
    assert np.isfinite(strict[0, 0, 1, 0]) and strict[0, 0, 1, 0] == lawful[0, 0, 1, 0]
    # without a floor the rows are counted, as hist-gbm counts them
    rows = ref._gains(hist, dataclasses.replace(p, min_child_weight=None, min_rows=10.0))[0]
    assert not np.isfinite(rows[0, 0, 0, 0]) and np.isfinite(
        ref._gains(hist, dataclasses.replace(p, min_child_weight=None))[0][0, 0, 0, 0])


def test_the_limits_are_numbers_of_the_reference():
    limits = checks.load_limits(ROOT, CONFIG)
    assert set(limits) <= set(ref.NUMBERS) and ref.NUMBERS == ref.base.NUMBERS
    assert checks.load_reference(ROOT, load_config(), limits).NUMBERS == ref.NUMBERS


def test_rehearsal_of_the_new_cell_over_four_devices_is_correct():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147489659",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearse"] is True and line["metrics"] == {}
    assert line["device"]["count"] == 4
    assert set(line["checks"]) == set(checks.load_limits(ROOT, CONFIG))
    assert "check gain_forgone" in done.stderr


def test_the_cell_is_listed_with_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "budget-fit"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["rows", "ntrees"] == list(load_config()["reduced"])
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == ["hist_psum_ms_per_tree", "sharded_step_mfu"]
    # what divides the whole table's work by ONE chip's peak, what has been
    # silent since PR 33 and what reads sets does not list the cell
    without = {m["name"] for m in bench["per_layer"] if CELL not in m["workloads"]}
    assert without == {"train_step_mfu", "hist_kernel_roofline", "score_matrix_s",
                       "score_bin_s", "score_traverse_s", "set_split_ms_per_tree",
                       "set_route_ms_per_tree"}
    for name in mine:
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))


def test_the_new_readers_on_a_run_without_their_names():
    import jax

    psum = harness.load_named(ROOT, "metrics", "hist_psum_ms_per_tree")
    sharded = harness.load_named(ROOT, "metrics", "sharded_step_mfu")
    whole = harness.load_named(ROOT, "metrics", "train_step_mfu")
    blocks = [{"start_ns": 0, "end_ns": int(6.4e9), "trees": 16}]
    run = {"cell": CELL, "trace": None, "served": [{"blocks": blocks}],
           "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
           "work": {"ops": 1e9, "bytes": 5.7e9, "hist_ops": 0.0, "hist_bytes": 0.0}}
    assert psum.read(run) is None  # untraced: nothing to read, nothing raised
    chips = len(jax.devices())
    assert sharded.read(run) == pytest.approx(whole.read(run) / chips)
    assert 0.0 < sharded.read(run) < 100.0
    assert sharded.read(dict(run, served=[{"blocks": []}])) is None
    assert sharded.read(dict(run, peak=None)) is None
