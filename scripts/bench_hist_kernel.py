"""Probe the Pallas tpu_hist kernel vs the XLA scatter path on the chip.

Writes KERNEL_PROBE.json (per-K ms, rows/sec, achieved-vs-peak MXU FLOPs):
kernel-level evidence that is independent of the end-to-end bench.

Timing: REPS kernel applications, each on its own gradient vector, are
folded into ONE program (``lax.scan``) whose checksum is waited for with
``block_until_ready``; the program runs once to compile and warm, then once
timed, and the wall is divided by REPS.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from h2o3_tpu.util import compile_cache  # noqa: E402

compile_cache.configure()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import PEAK_BF16_TFLOPS  # noqa: E402
from h2o3_tpu.ops.histogram import _shard_histogram  # noqa: E402
from h2o3_tpu.ops.pallas_histogram import _C, build_histogram_pallas  # noqa: E402

N, F, B1 = 2_000_000, 28, 257
#: the XLA scatter baseline runs on this many rows and is scaled linearly
#: to N — TPU scatter-adds are serialized per element, so a full-N baseline
#: both risks the probe's time budget and adds nothing (it is the *slow*
#: side of the comparison)
N_SCATTER = 200_000
REPS = 4


def timed_chain(make_fn, gs) -> float:
    """Seconds per application of ``make_fn`` over the stacked ``gs``."""
    @jax.jit
    def chained(gs):
        def body(tot, g):
            return tot + make_fn(g).sum(), None
        tot, _ = jax.lax.scan(body, jnp.float32(0.0), gs)
        return tot

    chained(gs).block_until_ready()  # compile + first run
    t0 = time.perf_counter()
    chained(gs).block_until_ready()
    return (time.perf_counter() - t0) / gs.shape[0]


def main() -> None:
    rng = np.random.default_rng(0)
    bins = jax.device_put(rng.integers(0, B1, size=(N, F)).astype(np.int32))
    gs = jnp.stack([
        jax.device_put(rng.normal(size=N).astype(np.float32))
        for _ in range(REPS)
    ])
    h = jax.device_put(rng.random(N).astype(np.float32))
    scatter = jax.jit(_shard_histogram, static_argnums=(4, 5))

    dev = jax.devices()[0]
    peak = PEAK_BF16_TFLOPS[dev.device_kind]  # an unknown kind is an error

    results = []
    for K in (1, 8, 64):
        nodes = jax.device_put(rng.integers(0, K, size=N).astype(np.int32))

        t_p = timed_chain(
            lambda g: build_histogram_pallas(bins, nodes, g, h, K, B1), gs)
        t_xs = timed_chain(
            lambda g: scatter(bins[:N_SCATTER], nodes[:N_SCATTER],
                              g[:N_SCATTER], h[:N_SCATTER], K, B1),
            gs[:, :N_SCATTER])
        t_x = t_xs * (N / N_SCATTER)  # scatter cost is linear in rows

        # parity at the subsample size (full-size oracle OOMs: its scatter
        # operand lane-pads 3 -> 128); dtype pinned to f32 so this measures
        # kernel correctness, not bf16 input rounding — note the TPU MXU's
        # DEFAULT precision still multiplies in bf16 either way
        out_x = scatter(bins[:N_SCATTER], nodes[:N_SCATTER],
                        gs[0, :N_SCATTER], h[:N_SCATTER], K, B1)
        out_p = build_histogram_pallas(
            bins[:N_SCATTER], nodes[:N_SCATTER], gs[0, :N_SCATTER],
            h[:N_SCATTER], K, B1, dtype="f32")
        err = float(np.max(np.abs(np.asarray(out_x) - np.asarray(out_p))))

        # dense-matmul FLOPs actually ISSUED: the kernel pads features to
        # a _FEAT_BLOCK multiple and rows to a _ROW_TILE multiple
        from h2o3_tpu.ops.pallas_histogram import _FEAT_BLOCK, _ROW_TILE

        f_pad = F + (-F) % _FEAT_BLOCK
        n_pad = N + (-N) % _ROW_TILE
        flops = 2.0 * n_pad * (f_pad * B1) * (K * _C)
        achieved = flops / t_p / 1e12
        row = {
            "K": K,
            "xla_scatter_ms": round(t_x * 1e3, 2),
            "xla_scatter_n": N_SCATTER,  # measured rows; ms scaled to N
            "pallas_ms": round(t_p * 1e3, 2),
            "speedup": round(t_x / t_p, 2),
            "pallas_rows_per_sec": round(N / t_p, 0),
            "achieved_tflops": round(achieved, 2),
            "pct_of_bf16_peak": round(100 * achieved / peak, 1),
            "max_abs_err": err,
        }
        results.append(row)
        print(row, flush=True)

    from h2o3_tpu.ops.pallas_histogram import _resolve_hist_dtype

    artifact = {
        "config": {"n_rows": N, "n_feat": F, "n_bins1": B1,
                   "platform": dev.platform,
                   "device_kind": dev.device_kind,
                   "hist_dtype": (
                       "bf16" if _resolve_hist_dtype("auto") == jnp.bfloat16
                       else "f32"),
                   "reps": REPS,
                   "method": "scan-chained kernel apps, warmed, timed to "
                             "block_until_ready"},
        "results": results,
    }
    out_path = sys.argv[1] if len(sys.argv) > 1 else "KERNEL_PROBE.json"
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
