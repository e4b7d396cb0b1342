"""What is particular to a deployment is data and files found by name:
typed columns from the table generator, the reference that decides
``correct``, the block's memory from the model that was fitted (any tree
builder), scoring programs for a fixed tree count.  All on the CPU at tiny
sizes; the files a later PR would add are written into a temporary root.
"""

import json
import os
import shutil
import textwrap

import numpy as np
import pytest

from lib import checks, harness, programs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

AIRLINE_TOY = '''
    """Four columns of the airline on-time table's shape: two numeric, two
    categorical (level codes; NaN is the NA)."""
    import numpy as np

    CARRIERS = [f"C{i:02d}" for i in range(22)]


    def columns(spec):
        origins = [f"A{i:03d}" for i in range(int(spec["origins"]))]
        return [{"name": "DepTime", "type": "num"},
                {"name": "Origin", "type": "cat", "domain": origins},
                {"name": "UniqueCarrier", "type": "cat", "domain": CARRIERS},
                {"name": "Distance", "type": "num"}]


    def make(spec, rows, seed):
        rng = np.random.default_rng(seed)
        X = np.empty((rows, 4), np.float32)
        X[:, 0] = rng.uniform(0, 2400, rows)
        X[:, 1] = rng.integers(0, int(spec["origins"]), rows)
        X[:, 2] = rng.integers(0, len(CARRIERS), rows)
        X[:, 3] = rng.gamma(2.0, 400.0, rows)
        X[::97, 1] = np.nan
        late = X[:, 0] / 2400 + (X[:, 2] % 3 == 0) + rng.normal(size=rows)
        return X, (late > 1.0).astype(np.int32)
'''

TOY_REFERENCE = '''
    """A reference of one number: how many trees short of the configuration's
    ``expect_trees`` the worst answer of the window is."""
    NUMBERS = ("trees_short",)


    def extract(model, numbers):
        return {"built": int(model.ntrees_built)}


    def compare(config, seed, table, answers, block, numbers):
        assert table["X"].shape[0] == len(table["y"]) and "columns" in table
        return {"trees_short": max(config["expect_trees"] - a["built"] for a in answers)}
'''


def write(root, kind, name, text):
    os.makedirs(os.path.join(root, "benchmark", kind), exist_ok=True)
    with open(os.path.join(root, "benchmark", kind, name), "w") as f:
        f.write(textwrap.dedent(text).lstrip("\n"))


@pytest.fixture
def deployment(tmp_path):
    """A root that holds what a later PR adds for a deployment: a generator
    with typed columns, a configuration, its limits, a reference, a traffic
    mix of a fixed tree count; and the benchmark's table of peaks."""
    root = str(tmp_path)
    write(root, "tables", "airline-toy.py", AIRLINE_TOY)
    write(root, "references", "toy.py", TOY_REFERENCE)
    write(root, "limits", "gbm-airline-toy.json", '{"limits": {"trees_short": 0}}')
    os.makedirs(os.path.join(root, "benchmark", "lib"))
    shutil.copy(os.path.join(HERE, "lib", "peaks.json"),
                os.path.join(root, "benchmark", "lib", "peaks.json"))
    config = {"name": "gbm-airline-toy", "builder": "h2o3_tpu.models.tree.gbm:GBM",
              "response_column": "late", "reference": "toy", "expect_trees": 8,
              "table": {"generator": "airline-toy", "features": 4, "classes": 2,
                        "origins": 300},
              "params": {"distribution": "bernoulli", "max_depth": 3, "nbins": 16,
                         "learn_rate": 0.1, "min_rows": 5.0},
              "rehearse": {"rows": 2000}}
    traffic = {"warmup": {"op": "train", "params": {"ntrees": "one_block"}},
               "requests": [{"op": "train", "params": {"ntrees": 8}}]}
    cell = {"name": "gbm-airline-toy.fixed-trees", "config": "gbm-airline-toy",
            "traffic": "fixed-trees", "chips": 1}
    return root, cell, config, traffic


# -- (a) typed columns ----------------------------------------------------------


def test_typed_columns_reach_train_as_categorical_columns(deployment):
    from h2o3_tpu.frame.frame import ColType

    root, _, config, _ = deployment
    table = harness.make_table(root, config, 2000, 2**31 + 5)
    assert [c["type"] for c in table["columns"]] == ["num", "cat", "cat", "num"]
    seen = []
    real = harness.load_builder(config["builder"])

    class Spy(real):
        def train(self, frame, *a, **k):
            seen.append(frame)
            return super().train(frame, *a, **k)

    frame = harness.make_frame(table["X"], table["y"], config, table["columns"])
    served = harness.fit(Spy, config, frame, 7, {"ntrees": 2})
    assert served["trees_built"] == 2
    cols = {c.name: c for c in seen[0].columns}
    assert list(cols) == ["DepTime", "Origin", "UniqueCarrier", "Distance", "late"]
    assert cols["Origin"].type is ColType.CAT and len(cols["Origin"].domain) == 300
    assert cols["UniqueCarrier"].type is ColType.CAT
    assert cols["UniqueCarrier"].domain == [f"C{i:02d}" for i in range(22)]
    assert cols["DepTime"].type is ColType.NUM and cols["DepTime"].data.dtype == np.float64
    origin = cols["Origin"].data
    assert origin.dtype == np.int32 and (origin[::97] == -1).all()  # NaN is the NA
    assert (origin[1:97] == table["X"][1:97, 1].astype(np.int32)).all()
    assert served["model"].data_info.cat_domains["Origin"] == cols["Origin"].domain


def test_a_generator_without_columns_gives_the_frame_as_before():
    """``higgs-synth`` states no columns: ``f0..`` float64 features and the
    categorical response, value for value what ``make_frame`` built before
    columns could be typed."""
    from h2o3_tpu.frame.frame import ColType, Column, Frame

    with open(os.path.join(HERE, "configs", "gbm-higgs-d6-b256.json")) as f:
        config = json.load(f)
    table = harness.make_table(ROOT, config, 500, 3)
    assert table["columns"] is None and table["classes"] == 2
    X, y = table["X"], table["y"]
    before = Frame([Column(f"f{i}", X[:, i].astype(np.float64)) for i in range(28)]
                   + [Column("y", y.astype(np.int32), ColType.CAT, ["0", "1"])])
    now = harness.make_frame(X, y, config, table["columns"])
    assert now.names == before.names
    for a, b in zip(now.columns, before.columns):
        assert (a.type, a.domain, a.data.dtype) == (b.type, b.domain, b.data.dtype)
        assert a.data.tobytes() == b.data.tobytes()


@pytest.mark.parametrize("columns,complaint", [
    ([{"name": "a", "type": "num"}], "states 1 columns and makes 4"),
    ([{"name": "a", "type": "enum"}] * 4, "the type is"),
    ([{"name": "a", "type": "cat"}] * 4, "with its domain")])
def test_columns_that_do_not_describe_the_table_are_an_error(deployment, columns, complaint):
    root, _, config, _ = deployment
    write(root, "tables", "mistyped.py", AIRLINE_TOY.replace(
        "return [{", "return %r\n        return [{" % (columns,)))
    config["table"]["generator"] = "mistyped"
    with pytest.raises(SystemExit, match=complaint):
        harness.make_table(root, config, 100, 1)


# -- (b) the reference found by name --------------------------------------------


def drive(deployment, monkeypatch, **changes):
    root, cell, config, traffic = deployment
    monkeypatch.setenv("H2O3_TPU_TREE_BLOCK", "4")
    return harness.run(cell=cell, config=dict(config, **changes), traffic=traffic,
                       seed=2**31 + 9, seconds=1.0, trace=False, rehearse=True,
                       t_start=0.0, root=root, metrics=[])


def test_a_reference_found_by_name_decides_correct(deployment, monkeypatch):
    """The toy reference's one number is the run's ``checks``, and it alone
    says whether the run is correct.  The window's fit of a fixed 8 trees
    (two blocks of 4) ends on a tree count the warm-up did not score: that
    scoring program was built in set-up, or ``failed`` would count it."""
    out = drive(deployment, monkeypatch)
    assert out["checks"] == {"trees_short": (0.0, 0.0)}
    assert out["correct"] is True and out["failed"] == 0, out
    assert out["window"]["trees_built"] == 8 and out["window"]["blocks"] == 2
    out = drive(deployment, monkeypatch, expect_trees=9)
    assert out["checks"] == {"trees_short": (1.0, 0.0)}
    assert out["correct"] is False and out["failed"] == 0


def test_what_cannot_decide_correct_is_an_error_not_a_pass(deployment, monkeypatch):
    root, _, config, _ = deployment
    limits = checks.load_limits(root, "gbm-airline-toy")
    assert checks.load_reference(root, config, limits).NUMBERS == ("trees_short",)
    with pytest.raises(SystemExit, match="no benchmark/references/hist-gbm.py"):
        checks.load_reference(root, dict(config, reference="hist-gbm"), limits)
    nameless = {k: v for k, v in config.items() if k != "reference"}
    with pytest.raises(SystemExit, match='names no "reference"'):
        checks.load_reference(root, nameless, limits)
    with pytest.raises(SystemExit, match=r"cannot compute the limits' numbers \['leaf_gap'\]"):
        checks.load_reference(root, config, dict(limits, leaf_gap=0.1))
    write(root, "references", "half.py", "NUMBERS = ('trees_short',)\n")
    with pytest.raises(SystemExit, match=r"offers no \['extract', 'compare'\]"):
        checks.load_reference(root, dict(config, reference="half"), limits)
    # and a run refuses such a configuration before it sets anything up
    with pytest.raises(SystemExit, match='names no "reference"'):
        drive(deployment, monkeypatch, reference=None)
    silent = type("Ref", (), {"compare": staticmethod(lambda *a: {})})
    with pytest.raises(SystemExit, match="returned no"):
        checks.decide(silent, limits, config, 1, {}, [], 4)


def test_both_cells_name_hist_gbm_and_their_limits_are_its_numbers():
    ref = harness.load_named(ROOT, "references", "hist-gbm")
    for name in ("gbm-higgs-d6-b256", "gbm-higgs-automl-d10"):
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            config = json.load(f)
        assert config["reference"] == "hist-gbm"
        limits = checks.load_limits(ROOT, name)
        assert checks.load_reference(ROOT, config, limits).NUMBERS == ref.NUMBERS
        assert set(limits) <= set(ref.NUMBERS)


# -- (c) the block's memory from the model that was fitted ------------------------


def old_gbm_mapping(config):
    """The mapping ``lib/programs.py`` kept up to PR 29: a copy of ``GBM._fit``'s mapping."""
    from h2o3_tpu.models.tree.booster import TreeParams

    p = config["params"]
    return TreeParams(
        ntrees=0, max_depth=int(p["max_depth"]), learn_rate=float(p["learn_rate"]),
        nbins=int(p["nbins"]), min_rows=float(p["min_rows"]),
        min_split_improvement=float(p.get("min_split_improvement", 1e-5)),
        reg_lambda=0.0, reg_alpha=0.0,
        sample_rate=float(p.get("sample_rate", 1.0)),
        col_sample_rate_per_tree=float(p.get("col_sample_rate_per_tree", 1.0)),
        seed=0)


BUILDERS = {
    "gbm": ("h2o3_tpu.models.tree.gbm:GBM", "bernoulli", {"learn_rate": 0.1}),
    "xgboost": ("h2o3_tpu.models.tree.xgboost:XGBoost", "bernoulli",
                {"learn_rate": 0.3, "reg_lambda": 1.0, "gamma": 0.1}),
    "drf": ("h2o3_tpu.models.tree.drf:DRF", "fixed", {"mtries": 3}),
}


@pytest.mark.parametrize("name", list(BUILDERS))
def test_block_footprint_from_the_fitted_model(name):
    import jax

    builder, objective, extra = BUILDERS[name]
    config = {"builder": builder, "response_column": "y",
              "table": {"generator": "higgs-synth", "features": 10, "classes": 2},
              "params": {"max_depth": 4, "nbins": 32, "min_rows": 5.0,
                         "sample_rate": 0.8, **extra}}
    if name != "drf":
        config["params"]["distribution"] = "bernoulli"
    spec = programs.tiny_fit_spec(config, ROOT)
    assert spec["objective"] == objective and spec["class_trees"] == 1
    assert (spec["params"].ntrees, spec["params"].seed) == (0, 0)
    assert spec["params"].max_depth == 4 and spec["params"].sample_rate == 0.8
    mem = programs.block_footprint(spec, 2000, 10, 4, jax.devices()[:1])
    assert mem["temp"] > 0 and mem["argument"] > 0 and mem["padded_rows"] >= 2000
    assert mem["total"] == mem["temp"] + mem["argument"] + mem["output"] - mem["alias"]
    if name == "gbm":
        old = dict(spec, params=old_gbm_mapping(config))
        assert spec["params"] == old["params"]
        assert programs.block_footprint(old, 2000, 10, 4, jax.devices()[:1]) == mem
    if name == "xgboost":
        assert spec["params"].reg_lambda == 1.0 and spec["params"].gamma == 0.1
    if name == "drf":
        assert spec["params"].mtries == 3 and spec["params"].learn_rate == 1.0


@pytest.mark.parametrize("name", ["gbm-higgs-d6-b256", "gbm-higgs-automl-d10"])
def test_both_cells_lower_from_the_parameters_they_lowered_from_before(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        config = json.load(f)
    spec = programs.tiny_fit_spec(config, ROOT)
    assert spec == {"objective": "bernoulli", "class_trees": 1,
                    "params": old_gbm_mapping(config)}


def test_the_objective_is_the_models_word_and_a_model_that_says_nothing_is_an_under_read(capsys):
    from h2o3_tpu.models.tree.booster import TreeParams

    # no table of builders: a booster that averages fits fixed targets,
    # whatever class built it; any other the configuration's distribution
    booster = type("B", (), {"params": TreeParams(), "nclasses_trees": 1, "average": True})
    model = type("M", (), {"booster": booster})
    config = {"builder": "pkg.mod:ExtraTrees", "params": {"distribution": "gaussian"}}
    assert programs.block_spec(model, config)["objective"] == "fixed"
    booster.average = False
    assert programs.block_spec(model, config)["objective"] == "gaussian"
    # a model with no booster to read: a note, and no block to lower
    assert harness.warm_block_program(type("M", (), {}), config) is None
    assert "does not say what its block was compiled from" in capsys.readouterr().err
    # and the run's line then says that its memory is an under-read
    assert programs.attached_footprint(None, 2000, 10, 4) is None
    assert "allocator's peak alone" in capsys.readouterr().err


# -- (d) scoring programs for a fixed tree count ----------------------------------


def asked_of_build_scoring_programs(monkeypatch, traffic, warm, block=16):
    asked = []
    monkeypatch.setattr(programs, "build_scoring_programs",
                        lambda model, rows, features, counts: asked.append(counts))
    requests = harness.generate_requests(traffic, 32.0, block)
    built = harness.prewarm_scoring(None, warm, 1.0, requests, 2000, 10, block)
    assert [built] == asked or (built == [] and asked == [])
    return asked


def test_a_fixed_tree_count_is_prewarmed(monkeypatch, tmp_path):
    warm = {"trees_built": 8,
            "blocks": [{"start_ns": 0, "end_ns": 10_000_000_000, "trees": 8}]}
    write(str(tmp_path), "traffic", "fixed-trees.json", json.dumps({
        "warmup": {"op": "train", "params": {"ntrees": "one_block"}},
        "requests": [{"op": "train", "repeat": 2, "params": {"ntrees": 24}}]}))
    with open(tmp_path / "benchmark" / "traffic" / "fixed-trees.json") as f:
        traffic = json.load(f)
    assert asked_of_build_scoring_programs(monkeypatch, traffic, warm, block=8) == [[24]]
    # the warm-up's own count is built already; a budget beside ntrees sets the end
    traffic = {"requests": [{"op": "train", "params": {"ntrees": 32}},
                            {"op": "train", "params": {"ntrees": "one_block"}}]}
    assert asked_of_build_scoring_programs(monkeypatch, traffic, warm, block=8) == [[32]]
    assert asked_of_build_scoring_programs(
        monkeypatch, {"requests": [{"op": "train", "params": {"ntrees": 8}}]}, warm,
        block=8) == []
    # a fixed count that ends on a shorter block would compile in its window
    with pytest.raises(SystemExit, match="whole number of blocks of 16"):
        asked_of_build_scoring_programs(monkeypatch, traffic | {"requests": [
            {"op": "train", "params": {"ntrees": 24}}]}, warm, block=16)
    budgeted = {"requests": [{"op": "train", "params": {
        "ntrees": 24, "max_runtime_secs": "window"}}]}
    assert harness.generate_requests(budgeted, 32.0, 16)[0]["params"]["ntrees"] == 24


def test_a_budget_is_prewarmed_as_before(monkeypatch):
    """A 10 s block of which 1 s was compiling: a 32 s budget can end on 4
    blocks, and the counts of 2 to 10 blocks are built, less the warm-up's."""
    with open(os.path.join(HERE, "traffic", "budget-fit.json")) as f:
        traffic = json.load(f)
    warm = {"trees_built": 16,
            "blocks": [{"start_ns": 0, "end_ns": 10_000_000_000, "trees": 16}]}
    assert asked_of_build_scoring_programs(monkeypatch, traffic, warm) == [
        [16 * j for j in range(2, 11)]]
    assert asked_of_build_scoring_programs(monkeypatch, traffic, dict(warm, blocks=[])) == []


def test_the_block_time_leaves_out_what_was_not_execution(monkeypatch):
    """Depth 10 with every program in the cache: a warm-up block of 14 s of
    which 9 s were tracing, lowering and the cache's load.  The budget ends
    on 7 blocks; the ladder is 3 to 16 blocks round it, not 1 to 8."""
    meter = harness.CompileMeter()
    s = 1_000_000_000
    # an inner jit's spells lie inside its caller's; a later build follows
    meter.spells = [(1 * s, 8 * s), (2 * s, 3 * s), (4 * s, 9 * s), (9 * s, 10 * s), (20 * s, 21 * s)]
    assert meter.building_s(0, 14 * s) == 9.0
    assert meter.building_s(5 * s, 9 * s + s // 2) == 4.5
    assert meter.building_s(11 * s, 14 * s) == 0.0
    with open(os.path.join(HERE, "traffic", "budget-fit.json")) as f:
        traffic = json.load(f)
    warm = {"trees_built": 5, "blocks": [{"start_ns": 0, "end_ns": 14 * s, "trees": 5}]}
    monkeypatch.setattr(programs, "build_scoring_programs", lambda *a: None)
    requests = harness.generate_requests(traffic, 32.0, 5)
    assert harness.prewarm_scoring(None, warm, meter.building_s(0, 14 * s), requests,
                                   2000, 10, 5) == [5 * j for j in range(3, 17)]
    # a span that says nothing (a step that returns before its work is done)
    # asks for the nearest rungs only, not for thousands of programs
    hollow = {"trees_built": 5, "blocks": [{"start_ns": 0, "end_ns": 14_000_000, "trees": 5}]}
    built = harness.prewarm_scoring(None, hollow, 0.012, requests, 2000, 10, 5)
    assert len(built) == harness.LADDER_MAX and all(c % 5 == 0 for c in built)

