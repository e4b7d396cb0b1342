"""Kernel experiment lab: time tpu_hist variants and isolate per-level cost.

Measures, per tree level K in (1, 2, 4, 8, 16, 32):
  * the production node-matmul kernel (h2o3_tpu/ops/pallas_histogram.py);
  * a full "level step" (hist + split search + routing) to expose the glue
    residual between the kernel and the end-to-end tree time;
  * candidate variants (row-tile 1024, factorized hi/lo one-hot) before
    they are promoted into the production kernel.

Timing is scripts/bench_hist_kernel.py's ``timed_chain`` (scan-chained REPS
applications, warmed, timed to ``block_until_ready``).

Usage:
  python scripts/kernel_lab.py                # full lab on TPU
  python scripts/kernel_lab.py --parity       # interpreter-mode parity (CPU)
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PARITY = "--parity" in sys.argv
if PARITY:
    os.environ["JAX_PLATFORMS"] = "cpu"

from bench_hist_kernel import timed_chain  # noqa: E402  (sets the cache up)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from h2o3_tpu.ops.pallas_histogram import (  # noqa: E402
    build_histogram_pallas,
    _build_histogram_nodematmul,
    _resolve_hist_dtype,
)

N = 2_000_000 if not PARITY else 4096
F, B1 = 28, 257
REPS = 4
LEVEL_KS = (1, 2, 4, 8, 16, 32)


def parity_main():
    rng = np.random.default_rng(0)
    bins = rng.integers(0, B1, size=(N, F)).astype(np.int32)
    nodes = rng.integers(-1, 8, size=N).astype(np.int32)
    g = rng.normal(size=N).astype(np.float32)
    h = rng.random(N).astype(np.float32)

    from h2o3_tpu.ops.histogram import _shard_histogram

    want = np.asarray(_shard_histogram(
        jnp.asarray(bins), jnp.asarray(nodes), jnp.asarray(g),
        jnp.asarray(h), 8, B1))

    fb = 4
    Fp = F + (-F) % fb
    bfm = np.zeros((Fp, N), np.int32)
    bfm[:F] = bins.T
    got = np.asarray(build_histogram_pallas(
        jnp.asarray(bins), jnp.asarray(nodes), jnp.asarray(g),
        jnp.asarray(h), 8, B1, row_tile=512, interpret=True,
        kernel="factorized"))
    err = np.max(np.abs(want - got))
    print(f"factorized parity max_abs_err = {err:.3e}")
    assert err < 1e-2, err
    print("PARITY OK")


def lab_main():
    rng = np.random.default_rng(0)
    bins = rng.integers(0, B1, size=(N, F)).astype(np.int32)
    fb = 8
    Fp = F + (-F) % fb
    bfm_host = np.zeros((Fp, N), np.int32)
    bfm_host[:F] = bins.T
    bins_d = jax.device_put(bins)
    bfm = jax.device_put(bfm_host)
    gs = jnp.stack([jax.device_put(rng.normal(size=N).astype(np.float32))
                    for _ in range(REPS)])
    h = jax.device_put(rng.random(N).astype(np.float32))

    rows = []

    dt_bf16 = jnp.bfloat16 if _resolve_hist_dtype("auto") == jnp.bfloat16 \
        else jnp.float32

    for K in LEVEL_KS:
        nodes = jax.device_put(rng.integers(0, K, size=N).astype(np.int32))
        row = {"K": K}

        # production kernel (row_tile 512)
        row["prod_ms"] = round(timed_chain(
            lambda g: build_histogram_pallas(
                bins_d, nodes, g, h, K, B1, bins_fm=bfm),
            gs) * 1e3, 2)

        # row-tile 1024 variant of the production kernel
        try:
            row["rt1024_ms"] = round(timed_chain(
                lambda g: _build_histogram_nodematmul(
                    bins_d, nodes, g, h, K, B1, row_tile=1024, feat_block=fb,
                    interpret=False, vma=(), bins_fm=None, dtype=dt_bf16),
                gs) * 1e3, 2)
        except Exception as e:
            row["rt1024_ms"] = f"ERR {type(e).__name__}"

        # factorized hi/lo variant (production kernel)
        try:
            row["fact_ms"] = round(timed_chain(
                lambda g: build_histogram_pallas(
                    bins_d, nodes, g, h, K, B1, bins_fm=bfm,
                    kernel="factorized"),
                gs) * 1e3, 2)
        except Exception as e:
            row["fact_ms"] = f"ERR {type(e).__name__}"

        # factorized at row-tile 1024
        try:
            row["fact1024_ms"] = round(timed_chain(
                lambda g: build_histogram_pallas(
                    bins_d, nodes, g, h, K, B1, row_tile=1024,
                    kernel="factorized"),
                gs) * 1e3, 2)
        except Exception as e:
            row["fact1024_ms"] = f"ERR {type(e).__name__}"

        rows.append(row)
        print(row, flush=True)

    # glue residual: one full level step (hist + split search + route)
    from h2o3_tpu.models.tree.booster import _split_search, _sel_tables, _sel_cols

    K = 32
    nodes_l = jax.device_put(rng.integers(0, K, size=N).astype(np.int32))

    def level_step(g):
        hist = build_histogram_pallas(bins_d, nodes_l, g, h, K, B1, bins_fm=bfm)
        out = _split_search(
            hist, jnp.float32(1.0), jnp.float32(0.0), jnp.float32(0.0),
            jnp.float32(0.1), jnp.ones((F,), bool), min_rows=1.0, n_bins1=B1)
        bf, bb, dl, gain, leaf = out
        f, sb, dlk, cank = _sel_tables(
            (bf, bb, dl, gain > 0), jnp.clip(nodes_l, 0, K - 1))
        b = _sel_cols(bins_d, f)
        go_left = jnp.where(b >= B1 - 1, dlk, b <= sb)
        child = 2 * nodes_l + jnp.where(go_left, 1, 2)
        return child.astype(jnp.float32).sum() + leaf.sum()

    t = timed_chain(level_step, gs)
    print({"level_step_K32_ms": round(t * 1e3, 2)}, flush=True)
    rows.append({"level_step_K32_ms": round(t * 1e3, 2)})

    with open("KERNEL_LAB.json", "w") as f:
        json.dump(rows, f, indent=1)
    print("wrote KERNEL_LAB.json")


if __name__ == "__main__":
    if PARITY:
        parity_main()
    else:
        lab_main()
