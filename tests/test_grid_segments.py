"""Grid search (hex/grid/GridSearch.java) and segment models (hex/segments/)."""

import numpy as np
import pytest

from h2o3_tpu.frame.frame import ColType, Column, Frame
from h2o3_tpu.models.glm import GLM, GLMParameters
from h2o3_tpu.models.grid import Grid, GridSearch, SearchCriteria, metric_value
from h2o3_tpu.models.segments import SegmentModelsBuilder


# legacy module predating the CheckKeysTask fixture: tests here
# share/train keys without per-test cleanup; the module-level
# sweeper still removes everything at module end
pytestmark = pytest.mark.leaks_keys


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def _binomial_frame(rng, n=600):
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.normal(size=n) * 0.5 > 0).astype(np.int32)
    cols = [Column(f"x{i}", X[:, i]) for i in range(3)]
    cols.append(Column("y", y, ColType.CAT, ["0", "1"]))
    return Frame(cols)


class TestGridSearch:
    def test_cartesian_covers_product(self, rng):
        fr = _binomial_frame(rng)
        gs = GridSearch(
            GLM,
            GLMParameters(response_column="y", family="binomial"),
            {"alpha": [0.0, 0.5, 1.0], "lambda_": [0.0, 0.01]},
        )
        grid = gs.train(fr)
        assert len(grid.models) + len(grid.failures) == 6
        assert len(grid.models) == 6
        combos = {(h["alpha"], h["lambda_"]) for h in grid.hyper_params}
        assert len(combos) == 6

    def test_sorted_leaderboard(self, rng):
        fr = _binomial_frame(rng)
        gs = GridSearch(
            GLM,
            GLMParameters(response_column="y", family="binomial"),
            {"lambda_": [0.0, 0.5, 5.0]},
        )
        g = gs.train(fr).get_grid(sort_by="auc")
        aucs = [metric_value(m, "auc")[0] for m in g.models]
        assert aucs == sorted(aucs, reverse=True)
        # heavy shrinkage must hurt AUC
        assert g.hyper_params[0]["lambda_"] < 5.0

    def test_random_discrete_max_models_and_seed(self, rng):
        fr = _binomial_frame(rng)
        crit = SearchCriteria(strategy="RandomDiscrete", max_models=4, seed=7)
        gs = GridSearch(
            GLM,
            GLMParameters(response_column="y", family="binomial"),
            {"alpha": [0.0, 0.25, 0.5, 0.75, 1.0], "lambda_": [0.0, 0.01, 0.1]},
            search_criteria=crit,
        )
        g1 = gs.train(fr)
        assert len(g1.models) == 4
        g2 = GridSearch(
            GLM,
            GLMParameters(response_column="y", family="binomial"),
            {"alpha": [0.0, 0.25, 0.5, 0.75, 1.0], "lambda_": [0.0, 0.01, 0.1]},
            search_criteria=crit,
        ).train(fr)
        assert g1.hyper_params == g2.hyper_params  # seeded order reproducible

    def test_failures_recorded_not_fatal(self, rng):
        fr = _binomial_frame(rng)
        gs = GridSearch(
            GLM,
            GLMParameters(response_column="y", family="binomial"),
            {"alpha": [0.5, -123.0]},  # invalid alpha -> failure
        )
        grid = gs.train(fr)
        assert len(grid.models) + len(grid.failures) == 2
        assert len(grid.failures) >= 1

    def test_unknown_hyperparam_rejected(self):
        with pytest.raises(ValueError, match="unknown hyperparameter"):
            GridSearch(GLM, GLMParameters(), {"nope": [1]})

    def test_save_load_roundtrip(self, rng, tmp_path):
        fr = _binomial_frame(rng)
        grid = GridSearch(
            GLM,
            GLMParameters(response_column="y", family="binomial"),
            {"lambda_": [0.0, 0.1]},
        ).train(fr)
        p = str(tmp_path / "grid.bin")
        grid.save(p)
        g2 = Grid.load(p)
        assert g2.model_ids == grid.model_ids
        assert len(g2.models) == 2
        # loaded models still score
        assert g2.models[0].predict(fr).nrows == fr.nrows

    def test_parallel_matches_serial(self, rng):
        fr = _binomial_frame(rng)
        hp = {"lambda_": [0.0, 0.01, 0.1, 1.0]}
        base = GLMParameters(response_column="y", family="binomial")
        serial = GridSearch(GLM, base, hp).train(fr)
        par = GridSearch(GLM, base, hp, parallelism=4).train(fr)
        a = sorted(metric_value(m, "auc")[0] for m in serial.models)
        b = sorted(metric_value(m, "auc")[0] for m in par.models)
        np.testing.assert_allclose(a, b, rtol=1e-6)


class TestSegmentModels:
    def test_per_segment_models(self, rng):
        n = 900
        seg = rng.integers(0, 3, size=n)
        x = rng.normal(size=n)
        # different slope per segment
        y = x * np.array([1.0, -2.0, 0.5])[seg] + rng.normal(size=n) * 0.1
        fr = Frame(
            [
                Column("g", seg.astype(np.int32), ColType.CAT, ["a", "b", "c"]),
                Column("x", x),
                Column("y", y),
            ]
        )
        sb = SegmentModelsBuilder(
            GLM,
            GLMParameters(response_column="y", family="gaussian", lambda_=0.0),
            segment_columns=["g"],
        )
        sm = sb.train(fr)
        assert len(sm.segments) == 3
        assert all(e is None for e in sm.errors)
        slopes = {
            s["g"]: sm.model_for(g=s["g"]).coefficients["x"] for s in sm.segments
        }
        assert abs(slopes["a"] - 1.0) < 0.05
        assert abs(slopes["b"] + 2.0) < 0.05
        assert abs(slopes["c"] - 0.5) < 0.05

    def test_results_frame(self, rng):
        n = 300
        seg = rng.integers(0, 2, size=n)
        x = rng.normal(size=n)
        y = x + rng.normal(size=n) * 0.1
        fr = Frame(
            [
                Column("g", seg.astype(np.int32), ColType.CAT, ["u", "v"]),
                Column("x", x),
                Column("y", y),
            ]
        )
        sm = SegmentModelsBuilder(
            GLM, GLMParameters(response_column="y"), segment_columns=["g"]
        ).train(fr)
        out = sm.as_frame()
        assert out.nrows == 2
        assert set(out.names) == {"g", "status", "model", "errors"}
        st = out.col("status")
        assert all(st.domain[v] == "succeeded" for v in st.data)


class TestGridSegmentsReviewFixes:
    def test_parallel_minimize_metric_does_not_stop_while_improving(self, rng):
        n = 400
        x = rng.normal(size=n)
        y = 2.0 * x + rng.normal(size=n) * 0.1
        fr = Frame([Column("x", x), Column("y", y)])
        # lambdas from heavy to none: rmse strictly improves
        hp = {"lambda_": [1.0, 0.3, 0.1, 0.03, 0.0]}
        crit = SearchCriteria(stopping_rounds=1, stopping_tolerance=1e-3)
        grid = GridSearch(
            GLM, GLMParameters(response_column="y"), hp,
            search_criteria=crit, parallelism=2,
        ).train(fr)
        # with the direction bug this stopped after 2 models
        assert len(grid.models) == 5

    def test_segment_nan_numeric_column(self, rng):
        n = 200
        seg = rng.integers(0, 2, size=n).astype(np.float64)
        seg[:30] = np.nan
        x = rng.normal(size=n)
        y = x * np.where(np.nan_to_num(seg, nan=2.0) == 0, 1.0, -1.0)
        fr = Frame([Column("g", seg), Column("x", x), Column("y", y)])
        sm = SegmentModelsBuilder(
            GLM, GLMParameters(response_column="y"), segment_columns=["g"]
        ).train(fr)
        # NaN rows form ONE segment, not one per row
        assert len(sm.segments) == 3
        assert sum(s["g"] is None for s in sm.segments) == 1
        assert all(e is None for e in sm.errors)

    def test_grid_export_is_not_pickle(self, rng, tmp_path):
        """Grid.save uses the allowlisted zip format, never pickle
        (round-1/2 ADVICE item; pickle loads arbitrary code)."""
        import zipfile

        fr = _binomial_frame(rng)
        grid = GridSearch(
            GLM,
            GLMParameters(response_column="y", family="binomial"),
            {"lambda_": [0.0]},
        ).train(fr)
        p = str(tmp_path / "grid.bin")
        grid.save(p)
        assert zipfile.is_zipfile(p)
        with zipfile.ZipFile(p) as z:
            assert {"meta.json", "model.json", "arrays.npz"} <= set(z.namelist())

    def test_no_pickle_in_the_modules_that_read_a_users_files(self):
        """No `import pickle` where the package reads what a user hands in
        (a saved model, grid or frame, a MOJO, a request body, a Rapids
        expression): pickle loads arbitrary code. The RPC plane between the
        members of one cloud is out of scope: its wire codec is pickle by
        design (`cluster/rpc._encode`), and `rapids/dist_exec.py` sizes a
        payload with it (ROADMAP D12)."""
        import pathlib

        import h2o3_tpu

        root = pathlib.Path(h2o3_tpu.__file__).parent
        files = [root / "recovery.py"]
        for sub in ("models", "genmodel", "automl", "frame", "api", "client",
                    "rapids"):
            assert (root / sub).is_dir(), sub
            files += (root / sub).rglob("*.py")
        offenders = [
            str(f.relative_to(root))
            for f in files
            if f != root / "rapids" / "dist_exec.py" and any(
                line.strip().startswith(("import pickle", "from pickle"))
                for line in f.read_text().splitlines()
            )
        ]
        assert offenders == []
