"""MOJO export/offline-scoring parity — the "same answer everywhere"
guarantee (reference tier: testdir_javapredict cross-language consistency,
SURVEY.md §4 item 6): in-cluster predictions must equal genmodel scoring.
"""

import numpy as np
import pytest

from h2o3_tpu.frame.frame import ColType, Column, Frame
from h2o3_tpu.genmodel import EasyPredictModelWrapper, load_mojo


# legacy module predating the CheckKeysTask fixture: tests here
# share/train keys without per-test cleanup; the module-level
# sweeper still removes everything at module end
pytestmark = pytest.mark.leaks_keys


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def _frame_rows(frame: Frame):
    """Frame -> list of row dicts with domain strings for cats."""
    df = frame.to_pandas()
    return df.to_dict(orient="records")


def _mixed_frame(rng, n=400, classify=True):
    x0 = rng.normal(size=n)
    x1 = rng.normal(size=n) * 2 + 1
    g = rng.integers(0, 3, size=n)
    logit = x0 - 0.8 * x1 + np.array([0.5, -0.5, 1.0])[g]
    if classify:
        y = (logit + rng.normal(size=n) * 0.5 > 0).astype(np.int32)
        ycol = Column("y", y, ColType.CAT, ["no", "yes"])
    else:
        ycol = Column("y", logit + rng.normal(size=n) * 0.1)
    return Frame(
        [
            Column("x0", x0),
            Column("x1", x1),
            Column("g", g.astype(np.int32), ColType.CAT, ["a", "b", "c"]),
            ycol,
        ]
    )


def _assert_parity(model, frame, mojo_path, atol=1e-5):
    model.download_mojo(mojo_path)
    mm = load_mojo(mojo_path)
    ours = model._predict_raw(frame)
    theirs = mm.score(_frame_rows(frame))
    np.testing.assert_allclose(
        np.asarray(theirs, dtype=np.float64),
        np.asarray(ours, dtype=np.float64),
        atol=atol, rtol=1e-4,
    )
    return mm


class TestMojoParity:
    def test_glm_binomial(self, rng, tmp_path):
        from h2o3_tpu.models.glm import GLM

        fr = _mixed_frame(rng)
        m = GLM(response_column="y", family="binomial", lambda_=0.01).train(fr)
        mm = _assert_parity(m, fr, str(tmp_path / "glm.mojo"))
        pred = EasyPredictModelWrapper(mm).predict(_frame_rows(fr)[0])
        assert pred.label in ("no", "yes")
        assert len(pred.class_probabilities) == 2

    def test_glm_regression(self, rng, tmp_path):
        from h2o3_tpu.models.glm import GLM

        fr = _mixed_frame(rng, classify=False)
        m = GLM(response_column="y", family="gaussian").train(fr)
        mm = _assert_parity(m, fr, str(tmp_path / "glm_reg.mojo"))
        val = EasyPredictModelWrapper(mm).predict(_frame_rows(fr)[0]).value
        assert np.isfinite(val)

    def test_gbm(self, rng, tmp_path):
        from h2o3_tpu.models.tree.gbm import GBM

        fr = _mixed_frame(rng)
        m = GBM(response_column="y", ntrees=10, max_depth=3, seed=1).train(fr)
        _assert_parity(m, fr, str(tmp_path / "gbm.mojo"))

    def test_drf_multinomial(self, rng, tmp_path):
        from h2o3_tpu.models.tree.drf import DRF

        n = 500
        X = rng.normal(size=(n, 3))
        y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0.5).astype(int)
        fr = Frame(
            [Column(f"x{i}", X[:, i]) for i in range(3)]
            + [Column("y", y.astype(np.int32), ColType.CAT, ["l", "m", "h"])]
        )
        m = DRF(response_column="y", ntrees=8, max_depth=4, seed=3).train(fr)
        mm = _assert_parity(m, fr, str(tmp_path / "drf.mojo"))
        pred = EasyPredictModelWrapper(mm).predict(_frame_rows(fr)[0])
        assert pred.label in ("l", "m", "h")

    def test_kmeans(self, rng, tmp_path):
        from h2o3_tpu.models.kmeans import KMeans

        fr = _mixed_frame(rng, classify=False)
        m = KMeans(k=3, seed=5, ignored_columns=["y"]).train(fr)
        mm = _assert_parity(m, fr, str(tmp_path / "km.mojo"))
        pred = EasyPredictModelWrapper(mm).predict(_frame_rows(fr)[0])
        assert 0 <= pred.cluster < 3
        assert len(pred.distances) == 3

    def test_deeplearning(self, rng, tmp_path):
        from h2o3_tpu.models.deeplearning import DeepLearning

        fr = _mixed_frame(rng)
        m = DeepLearning(
            response_column="y", hidden=[8, 8], epochs=3, seed=2
        ).train(fr)
        _assert_parity(m, fr, str(tmp_path / "dl.mojo"), atol=1e-4)

    def test_naive_bayes(self, rng, tmp_path):
        from h2o3_tpu.models.naive_bayes import NaiveBayes

        fr = _mixed_frame(rng)
        m = NaiveBayes(response_column="y").train(fr)
        _assert_parity(m, fr, str(tmp_path / "nb.mojo"))

    def test_isolation_forest(self, rng, tmp_path):
        from h2o3_tpu.models.isolation_forest import IsolationForest

        fr = _mixed_frame(rng, classify=False)
        m = IsolationForest(
            ntrees=10, max_depth=6, seed=4, ignored_columns=["y"]
        ).train(fr)
        mm = _assert_parity(m, fr, str(tmp_path / "if.mojo"))
        pred = EasyPredictModelWrapper(mm).predict(_frame_rows(fr)[0])
        assert 0.0 <= pred.score <= 1.0

    def test_pca(self, rng, tmp_path):
        from h2o3_tpu.models.pca import PCA

        fr = _mixed_frame(rng, classify=False)
        m = PCA(k=2, ignored_columns=["y"]).train(fr)
        mm = _assert_parity(m, fr, str(tmp_path / "pca.mojo"))
        dims = EasyPredictModelWrapper(mm).predict(_frame_rows(fr)[0]).dimensions
        assert len(dims) == 2

    def test_unseen_level_and_missing_values(self, rng, tmp_path):
        """adaptTestForTrain semantics survive export: unseen level -> NA."""
        from h2o3_tpu.models.glm import GLM

        fr = _mixed_frame(rng)
        m = GLM(response_column="y", family="binomial").train(fr)
        p = str(tmp_path / "glm2.mojo")
        m.download_mojo(p)
        mm = load_mojo(p)
        row = {"x0": 0.5, "x1": None, "g": "NEVER_SEEN"}
        probs = mm.score0(row)
        assert np.all(np.isfinite(probs))
        assert abs(probs.sum() - 1.0) < 1e-9

    def test_genmodel_has_no_jax_dependency(self):
        """The genmodel package must stay numpy-only (dependency-light jar).

        Checked in a fresh interpreter whose PYTHONPATH holds the repo and
        nothing else: the check is what *genmodel* imports."""
        import os
        import subprocess
        import sys

        code = (
            "import sys\n"
            "import h2o3_tpu.genmodel\n"
            "assert 'jax' not in sys.modules, 'genmodel imported jax'\n"
            "assert 'h2o3_tpu.models' not in sys.modules\n"
            "assert 'h2o3_tpu.frame' not in sys.modules\n"
            "print('clean')\n"
        )
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        env["PYTHONPATH"] = "/root/repo"
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd="/root/repo", env=env,
        )
        assert out.returncode == 0, out.stderr
        assert "clean" in out.stdout


class TestMojoReviewFixes:
    def test_glm_offset_parity(self, rng, tmp_path):
        from h2o3_tpu.models.glm import GLM

        n = 300
        x = rng.normal(size=n)
        off = rng.uniform(0.0, 2.0, size=n)
        y = rng.poisson(np.exp(0.4 * x + off)).astype(np.float64)
        fr = Frame([Column("x", x), Column("exposure", off), Column("y", y)])
        m = GLM(
            response_column="y", family="poisson", offset_column="exposure",
            ignored_columns=["exposure"],
        ).train(fr)
        p = str(tmp_path / "glm_off.mojo")
        m.download_mojo(p)
        from h2o3_tpu.genmodel import load_mojo

        mm = load_mojo(p)
        rows = [{"x": float(x[i]), "exposure": float(off[i])} for i in range(50)]
        theirs = mm.score(rows)
        ours = m._predict_raw(fr.head(50))
        np.testing.assert_allclose(theirs, ours, rtol=1e-6)

    def test_binomial_label_threshold_matches_in_cluster(self, rng, tmp_path):
        from h2o3_tpu.models.tree.gbm import GBM

        # imbalanced so max-F1 threshold is far from 0.5
        n = 800
        X = rng.normal(size=(n, 3))
        y = ((X[:, 0] + rng.normal(size=n)) > 1.6).astype(np.int32)
        fr = Frame(
            [Column(f"x{i}", X[:, i]) for i in range(3)]
            + [Column("y", y, ColType.CAT, ["neg", "pos"])]
        )
        m = GBM(response_column="y", ntrees=10, max_depth=3, seed=1).train(fr)
        p = str(tmp_path / "imb.mojo")
        m.download_mojo(p)
        from h2o3_tpu.genmodel import load_mojo

        mm = load_mojo(p)
        w = EasyPredictModelWrapper(mm)
        online = m.predict(fr)
        pc = online.col("predict")
        rows = _frame_rows(fr)
        for i in range(0, n, 37):
            r = dict(rows[i]); r.pop("y", None)
            assert w.predict(r).label == pc.domain[pc.data[i]]

    def test_autoencoder_easy_predict(self, rng, tmp_path):
        from h2o3_tpu.models.deeplearning import DeepLearning

        fr = _mixed_frame(rng, classify=False)
        m = DeepLearning(
            autoencoder=True, hidden=[4], epochs=2, seed=1, ignored_columns=["y"]
        ).train(fr)
        p = str(tmp_path / "ae.mojo")
        m.download_mojo(p)
        from h2o3_tpu.genmodel import load_mojo

        mm = load_mojo(p)
        pred = EasyPredictModelWrapper(mm).predict(_frame_rows(fr)[0])
        assert hasattr(pred, "reconstructed")
        assert pred.reconstruction_error is not None
        assert np.isfinite(pred.reconstruction_error)


class TestMojoGlmR3:
    """Round-3 GLM families through the MOJO (multinomial softmax + ordinal
    thresholds; reference scorer hex/genmodel/algos/glm/GlmMojoModel.java and
    GlmOrdinalMojoModel.java)."""

    def test_glm_multinomial(self, rng, tmp_path):
        from h2o3_tpu.models.glm import GLM

        n = 300
        X = rng.normal(size=(n, 3))
        y = np.array(["a", "b", "c"])[
            np.argmax(X @ rng.normal(size=(3, 3)), axis=1)
        ]
        fr = Frame(
            [Column(f"x{i}", X[:, i]) for i in range(3)]
            + [Column("y", np.searchsorted(["a", "b", "c"], y).astype(np.int32),
                      ColType.CAT, ["a", "b", "c"])]
        )
        m = GLM(response_column="y", family="multinomial", lambda_=0.01).train(fr)
        mm = _assert_parity(m, fr, str(tmp_path / "glm_mn.mojo"))
        pred = EasyPredictModelWrapper(mm).predict(_frame_rows(fr)[0])
        assert pred.label in ("a", "b", "c")
        assert len(pred.class_probabilities) == 3

    def test_glm_ordinal(self, rng, tmp_path):
        from h2o3_tpu.models.glm import GLM

        n = 500
        X = rng.normal(size=(n, 2))
        eta = X @ np.array([1.0, -0.8])
        u = rng.random(n)
        c0 = 1 / (1 + np.exp(-(-0.5 - eta)))
        c1 = 1 / (1 + np.exp(-(1.0 - eta)))
        codes = np.where(u < c0, 0, np.where(u < c1, 1, 2)).astype(np.int32)
        fr = Frame(
            [Column("x0", X[:, 0]), Column("x1", X[:, 1]),
             Column("y", codes, ColType.CAT, ["lo", "mid", "hi"])]
        )
        m = GLM(response_column="y", family="ordinal", lambda_=0.0).train(fr)
        _assert_parity(m, fr, str(tmp_path / "glm_ord.mojo"))


def test_pca_demean_descale_mojo_roundtrip(rng, tmp_path):
    """The native MOJO must carry demean/descale statistics — without
    them the offline scorer projects un-transformed rows onto
    transformed-space eigenvectors."""
    import numpy as np

    from h2o3_tpu.frame.frame import Column, Frame
    from h2o3_tpu.genmodel.mojo_model import MojoModel
    from h2o3_tpu.models.mojo_export import write_mojo as write_native
    from h2o3_tpu.models.pca import PCA

    X = rng.normal(size=(250, 4)) + 5.0
    X[:, 0] *= 10.0
    fr = Frame([Column(f"x{i}", X[:, i]) for i in range(4)])
    for transform in ("demean", "descale"):
        m = PCA(k=2, transform=transform, seed=1).train(fr)
        path = str(tmp_path / f"pca_{transform}.mojo")
        write_native(m, path)
        mojo = MojoModel.load(path)
        got = mojo.score({f"x{i}": X[:, i] for i in range(4)})
        want = m._predict_raw(fr)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
