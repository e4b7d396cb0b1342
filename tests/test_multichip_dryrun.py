"""The driver-facing multi-chip gate, run in-tier.

Covers both driver environments: (a) this process, where conftest already
bootstrapped the 8-device CPU mesh (config route); (b) a process whose
backend initialized with too few devices, forcing the subprocess re-exec
path (a driver that probed a one-chip backend first).  What the dry run
only shows to finish — a fit whose rows are dealt over a mesh — is held to
the same fit on one device here, case by case (ISSUE 36: the deployment
over the four chips of a host owes the answers of one chip).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_in_process():
    from __graft_entry__ import dryrun_multichip

    assert len(jax.devices()) == 8
    dryrun_multichip(8)


def test_dryrun_multichip_from_initialized_backend():
    # Simulate the driver: backend comes up with 1 CPU device *before*
    # dryrun_multichip is called, so the config route is closed and the
    # subprocess re-exec must kick in.
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "jax.config.update('jax_num_cpu_devices', 1)\n"
        "assert len(jax.devices()) == 1\n"
        "from __graft_entry__ import dryrun_multichip\n"
        "dryrun_multichip(8)\n"
    )
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "one tpu_hist boosting round OK" in proc.stdout


def test_entry_compiles():
    from __graft_entry__ import entry

    fn, args = entry()
    res = jax.jit(fn)(*args)
    assert res.shape == (256,)


#: name -> (devices of the mesh, TreeParams fields, H2O3_TPU_TREE_SUBTRACT).
#: The dry run above is GBM's parameters over 8 devices with and without
#: subtraction, against nothing; these are the cases it does not repeat
MESH_FITS = {
    "gbm-4": (4, {}, "0"),
    "gbm-4-subtract": (4, {}, "1"),
    "xgb-4": (4, dict(reg_lambda=1.0, gamma=0.1, min_child_weight=1.0,
                      scale_pos_weight=2.0, min_split_improvement=0.0), "0"),
    "xgb-4-subtract": (4, dict(reg_lambda=1.0, gamma=0.1, min_child_weight=1.0,
                               scale_pos_weight=2.0, min_split_improvement=0.0), "1"),
    "xgb-8-sampled": (8, dict(reg_lambda=1.0, gamma=0.1, min_child_weight=5.0,
                              scale_pos_weight=2.0, sample_rate=0.7,
                              col_sample_rate_per_tree=0.75), "0"),
}


@pytest.mark.parametrize("case", sorted(MESH_FITS))
def test_a_fit_over_a_mesh_is_the_fit_on_one_device(case, monkeypatch):
    """Same seed, same rows (a count no mesh divides, NA in a column): the
    trees of the mesh's fit are those of one device's node for node, leaves
    and final margins equal to float32 rounding of the sums."""
    from h2o3_tpu.models.tree import booster
    from h2o3_tpu.models.tree.common import init_margin
    from h2o3_tpu.parallel.mesh import default_mesh

    n_devices, fields, subtract = MESH_FITS[case]
    monkeypatch.setenv("H2O3_TPU_TREE_SUBTRACT", subtract)
    rng = np.random.default_rng(5)
    n = 3001
    X = rng.normal(size=(n, 6)).astype(np.float32)
    X[rng.random(n) < 0.04, 2] = np.nan
    y = (X[:, 0] + X[:, 1] * np.nan_to_num(X[:, 2]) + rng.normal(size=n) * 0.3 > 0.4
         ).astype(np.float64)
    params = booster.TreeParams(ntrees=3, max_depth=4, nbins=16, learn_rate=0.3,
                                seed=9, **fields)

    def fit(k):
        b = booster.train_boosted(
            X, "bernoulli", y, 1, init_margin("bernoulli", y, 1), params,
            mesh=default_mesh(n_devices=k), fit_eval={"frame": None, "y": y, "w": None})
        return b.trees_per_class[0], b.fit_eval["margin"]

    (one, margin_one), (many, margin_many) = fit(1), fit(n_devices)
    for t in range(3):
        split = one.is_split[t]
        assert (many.is_split[t] == split).all()
        assert (many.feat[t][split] == one.feat[t][split]).all()
        assert (many.split_bin[t][split] == one.split_bin[t][split]).all()
        assert (many.default_left[t][split] == one.default_left[t][split]).all()
        np.testing.assert_allclose(many.leaf[t], one.leaf[t], rtol=1e-4, atol=2e-5)
    assert margin_many.shape == (n, 1)
    np.testing.assert_allclose(margin_many, margin_one, rtol=1e-4, atol=5e-5)
