"""Secondary benchmarks: GLM / DeepLearning / KMeans training throughput.

BASELINE.json's to-measure configs go beyond the flagship tpu_hist number
(GLM prostate-shaped smoke, DL MNIST-shaped, AutoML airlines-shaped —
SURVEY.md §6). This runner measures the single-chip training throughput of
the three dense-algebra algos on synthetic data of those shapes and writes
BENCH_EXTRA.json, naming the device it ran on.

Timing: a warmup fit (compiles) on one frame, then a timed fit on a second
frame of the same data (fresh column versions, so the device frame cache
serves nothing), per algo. Each train's own device-sync boundaries make
per-train wall time honest (the host blocks on fetching the fitted
parameters).

Usage:  python scripts/bench_extra.py [out.json]
(BENCH_EXTRA_SCALE=0.01 shrinks every config for a CPU smoke run.)
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from h2o3_tpu.util import compile_cache  # noqa: E402

compile_cache.configure()

_SCALE = float(os.environ.get("BENCH_EXTRA_SCALE", "1.0"))


def _n(base: int) -> int:
    return max(1000, int(base * _SCALE))


def _bench_glm():
    """Binomial IRLSM on a prostate-shaped but larger design (1M x 16)."""
    from h2o3_tpu.frame.frame import Column, ColType, Frame
    from h2o3_tpu.models.glm import GLM, GLMParameters

    rng = np.random.default_rng(0)
    n, d = _n(1_000_000), 16
    X = rng.normal(size=(n, d)).astype(np.float64)
    w = rng.normal(size=d) / np.sqrt(d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.int32)

    def make_frame():
        cols = [Column(f"x{i}", X[:, i]) for i in range(d)]
        cols.append(Column("y", y, ColType.CAT, ["n", "p"]))
        return Frame(cols)

    GLM(GLMParameters(response_column="y", family="binomial")).train(
        make_frame())  # warmup/compile
    fr = make_frame()
    t0 = time.time()
    m = GLM(GLMParameters(response_column="y", family="binomial")).train(fr)
    dt = time.time() - t0
    return {
        "metric": "glm_binomial_train_rows_per_sec",
        "value": round(n * m.iterations / dt, 1),
        "unit": f"row-passes/sec ({n} rows x {m.iterations} IRLSM iters)",
        "train_s": round(dt, 3),
    }


def _bench_dl():
    """MNIST-shaped MLP (60k x 784, 128-64 hidden, 10 classes)."""
    from h2o3_tpu.frame.frame import Column, ColType, Frame
    from h2o3_tpu.models.deeplearning import DeepLearning

    rng = np.random.default_rng(0)
    n, d, C = _n(60_000), 784 if _SCALE >= 1 else 64, 10
    X = rng.random((n, d)).astype(np.float32)
    y = rng.integers(0, C, n).astype(np.int32)
    epochs = 2

    def make_frame():
        cols = [Column(f"p{i}", X[:, i].astype(np.float64)) for i in range(d)]
        cols.append(Column("y", y, ColType.CAT, [str(c) for c in range(C)]))
        return Frame(cols)

    DeepLearning(hidden=[128, 64], epochs=epochs, response_column="y",
                 seed=1).train(make_frame())
    fr = make_frame()
    t0 = time.time()
    DeepLearning(hidden=[128, 64], epochs=epochs, response_column="y",
                 seed=2).train(fr)
    dt = time.time() - t0
    return {
        "metric": "dl_mnist_shape_train_samples_per_sec",
        "value": round(n * epochs / dt, 1),
        "unit": f"sample-passes/sec ({n} rows x {epochs} epochs, "
                f"{d}-128-64-10)",
        "train_s": round(dt, 3),
    }


def _bench_kmeans():
    """Lloyd iterations on 2M x 16, k=8."""
    from h2o3_tpu.frame.frame import Column, Frame
    from h2o3_tpu.models.kmeans import KMeans

    rng = np.random.default_rng(0)
    n, d, k = _n(2_000_000), 16, 8
    X = rng.normal(size=(n, d)).astype(np.float64)
    X[: n // 8] += 3.0

    def make_frame():
        return Frame([Column(f"x{i}", X[:, i]) for i in range(d)])

    KMeans(k=k, max_iterations=5, seed=1).train(make_frame())
    fr = make_frame()
    t0 = time.time()
    m = KMeans(k=k, max_iterations=5, seed=2).train(fr)
    dt = time.time() - t0
    iters = getattr(m, "iterations", 5) or 5
    return {
        "metric": "kmeans_train_rows_per_sec",
        "value": round(n * iters / dt, 1),
        "unit": f"row-iterations/sec ({n} rows x {iters} Lloyd iters, k={k})",
        "train_s": round(dt, 3),
    }


def main() -> None:
    results = []
    for fn in (_bench_glm, _bench_kmeans, _bench_dl):
        results.append(fn())
        print(json.dumps(results[-1]), flush=True)
    import jax

    dev = jax.devices()[0]
    artifact = {
        "device": {"platform": dev.platform, "device_kind": dev.device_kind,
                   "device_count": len(jax.devices())},
        "results": results,
    }
    out = sys.argv[1] if len(sys.argv) > 1 else "BENCH_EXTRA.json"
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
