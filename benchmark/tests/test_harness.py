"""The data-driven parts: traffic expansion, judged rounds, metric readers."""

import json
import os

import pytest

from lib import checks, harness, work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def test_requests_expand_templates_and_named_values():
    traffic = {"requests": [
        {"op": "train", "repeat": 2, "params": {"max_runtime_secs": "window"}},
        {"op": "train", "params": {"ntrees": "one_block"}}]}
    assert harness.generate_requests(traffic, 32.0, 5) == [
        {"op": "train", "params": {"max_runtime_secs": 32.0}},
        {"op": "train", "params": {"max_runtime_secs": 32.0}},
        {"op": "train", "params": {"ntrees": 5}}]
    with pytest.raises(SystemExit, match="no driver"):
        harness.generate_requests({"requests": [{"op": "score"}]}, 32.0, 16)


def test_budget_fit_is_one_budgeted_call():
    with open(os.path.join(HERE, "traffic", "budget-fit.json")) as f:
        traffic = json.load(f)
    assert harness.generate_requests(traffic, 32.0, 16) == [
        {"op": "train", "params": {"max_runtime_secs": 32.0}}]


def test_a_table_generator_is_a_file_found_by_name():
    spec = {"generator": "higgs-synth", "features": 28, "classes": 2}
    make = harness.load_named(ROOT, "tables", "higgs-synth").make
    X, y = make(spec, 1000, 2**31 + 11)
    X2, y2 = make(spec, 1000, 2**31 + 11)
    assert X.shape == (1000, 28) and set(y) == {0, 1}
    assert (X == X2).all() and (y == y2).all()
    with pytest.raises(SystemExit, match="binary response"):
        make(dict(spec, classes=7), 1000, 1)
    with pytest.raises(SystemExit, match="no benchmark/tables/covtype.py"):
        harness.load_named(ROOT, "tables", "covtype")


def load_config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_the_limits_file_names_the_numbers_compared():
    limits = checks.load_limits(ROOT, "gbm-higgs-d6-b256")
    ref = checks.load_reference(ROOT, load_config("gbm-higgs-d6-b256"), limits)
    assert list(limits)[:2] == ["bin_rank_gap", "init_margin_gap"]
    assert ref.reported_metrics(limits) == ["logloss", "auc"]
    assert ref.reported_metrics(["leaf_gap", "mse_gap"]) == ["mse"]


@pytest.mark.parametrize("built,block,rounds", [
    (64, 16, [0, 8, 16]), (17, 16, [0, 8, 16]), (16, 16, [0, 8]), (5, 16, [0]),
    (1, 16, [0]), (10, 5, [0, 2, 5]), (5, 5, [0, 2])])
def test_judged_rounds(built, block, rounds):
    ref = harness.load_named(ROOT, "references", "hist-gbm")
    assert ref.judged_rounds(built, block) == rounds


def run_ctx(trace):
    params = {"max_depth": 6, "nbins": 256, "distribution": "bernoulli"}
    blocks = [{"start_ns": 2_000_000_000 + i * 9_000_000_000,
               "end_ns": 2_000_000_000 + i * 9_000_000_000 + 8_000_000_000,
               "trees": 16} for i in range(2)]
    with open(os.path.join(HERE, "lib", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    return {"rows": 4_000_000, "trees_built": 32, "wall_s": 25.0, "setup_s": 60.0,
            "setup_compile": {"seconds": 1.5},
            "warmup": {"t0_ns": 0, "blocks": [{"start_ns": 17_000_000_000,
                                               "end_ns": 26_000_000_000, "trees": 16}]},
            "served": [{"t0_ns": 0, "t1_ns": 25_000_000_000, "blocks": blocks}],
            "trace": trace, "peak": peak,
            "work": work.tree_work(4_000_000, 28, 2, params)}


def read(name, run):
    return harness.load_reader(ROOT, name)(run)


def test_readers_on_hand_worked_spans():
    traced = {"busy_s": 20.0, "window_s": 25.0, "hist_kernel_s": 14.0}
    run = run_ctx(traced)
    assert read("train_rows_per_s", run) == pytest.approx(4_000_000 * 32 / 25.0)
    assert read("setup_s", run) == 60.0 and read("compile_s", run) == 1.5
    assert read("bin_upload_s", run) == pytest.approx(17.0)
    assert read("block_ms_per_tree", run) == pytest.approx(500.0)
    assert read("host_between_blocks_pct", run) == pytest.approx(100 * 1.0 / 25.0)
    assert read("post_fit_scoring_s", run) == pytest.approx(25.0 - 19.0)
    assert read("device_idle_pct", run) == pytest.approx(20.0)
    assert read("hist_kernel_busy_share_pct", run) == pytest.approx(70.0)
    # 32 trees x 6 levels x 272 MB at 819 GB/s over 14 s of kernel time
    assert read("hist_kernel_roofline", run) == pytest.approx(
        100 * 32 * 1.632e9 / 819e9 / 14.0)
    assert read("train_step_mfu", run) == pytest.approx(100 * (1.744e9 / 819e9) / 0.5)


def test_readers_with_nothing_to_read_return_nothing():
    run = run_ctx(None)
    for name in ("hist_kernel_roofline", "hist_kernel_busy_share_pct", "device_idle_pct"):
        assert read(name, run) is None
    run["served"][0]["blocks"] = run["served"][0]["blocks"][:1]
    assert read("host_between_blocks_pct", run) is None
    run["trace"] = {"busy_s": 1.0, "window_s": 2.0, "hist_kernel_s": 0.0}
    assert read("hist_kernel_roofline", run) is None  # never a roofline of 0


def test_every_metric_of_the_benchmark_has_a_reader_and_every_cell_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(ROOT, m["name"]))
    for cell in bench["workloads"]:
        assert os.path.exists(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
        limits = checks.load_limits(ROOT, cell["config"])
        ref = checks.load_reference(ROOT, load_config(cell["config"]), limits)
        assert set(limits) <= set(ref.NUMBERS)
