"""Benchmark: tpu_hist booster training throughput on Higgs-like data.

``python bench.py`` measures the flagship cell (BASELINE.json configs[2]):
XGBoost-style ``tree_method=tpu_hist`` training rows/sec/chip on a synthetic
Higgs-shaped dataset (28 numeric features, binary response — the real
Higgs-11M is not bundled in this zero-egress image, so shapes/statistics are
simulated). It runs in this one process, needs an accelerator, and prints ONE
JSON line that names the device it ran on (``platform``, ``device_kind``,
``device_count``) next to the number. Without an accelerator it exits
non-zero and prints no value: a number from a CPU is not a device metric.

The ``--*-bench`` modes are CPU-side microbenches of the host planes (cache,
parse, cluster, chaos, serving, rapids, histogram plans, observability,
codecs); each pins itself to the CPU backend.
"""

import json
import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))

#: peak dense bf16 TFLOP/s per chip, keyed by ``jax.devices()[0].device_kind``
#: (Google Cloud documentation, "TPU v5e"). A kind that is not here is an
#: error, not a default.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def synth_higgs(n_rows: int, n_feat: int = 28, seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    w = rng.normal(size=n_feat) / np.sqrt(n_feat)
    logit = X @ w + 0.5 * X[:, 0] * X[:, 1]
    y = (rng.random(n_rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float64)
    return X, y


def main() -> None:
    """The flagship measurement; prints the result JSON as its last line."""
    n_rows = int(os.environ.get("BENCH_ROWS", 2_000_000))
    ntrees = int(os.environ.get("BENCH_TREES", 10))
    max_depth = int(os.environ.get("BENCH_DEPTH", 6))

    from h2o3_tpu.util import compile_cache, telemetry

    compile_cache.configure()

    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        sys.exit(f"bench.py: no accelerator (jax.devices()[0] is {dev}); "
                 "this mode measures the chip and reports nothing without one")
    if dev.device_kind not in PEAK_BF16_TFLOPS:
        sys.exit(f"bench.py: no peak FLOP/s on record for device kind "
                 f"{dev.device_kind!r}; add it to PEAK_BF16_TFLOPS with its "
                 "source")
    peak = PEAK_BF16_TFLOPS[dev.device_kind]

    from h2o3_tpu.models.tree.booster import TreeParams, train_boosted
    from h2o3_tpu.models.tree.common import init_margin

    # count XLA compiles from the first warmup program on, so the artifact
    # records how much of this run was compilation vs steady-state training
    telemetry.install_jax_compile_listener()

    X, y = synth_higgs(n_rows)
    params = TreeParams(
        ntrees=ntrees, max_depth=max_depth, learn_rate=0.1, nbins=256,
        min_rows=1.0, reg_lambda=1.0, seed=0,
    )
    f0 = init_margin("bernoulli", y, 1)

    # warmup run at full shape: compiles the training-block executable(s);
    # the timed run below hits the jit cache
    from dataclasses import replace as _dc_replace
    t0 = time.time()
    train_boosted(X, "bernoulli", y, 1, f0, _dc_replace(params, seed=12345))
    warmup_s = time.time() - t0
    print(f"# warmup done in {warmup_s:.1f}s", file=sys.stderr)

    # steady-state training throughput: the fit's tree_block spans separate
    # the on-chip boosting loop from the one-time host prep (binning +
    # device transfer), the same split the reference's benchmarks use
    # (DMatrix build excluded from the gpu_hist training timer)
    from h2o3_tpu.util import timeline

    t_fit = time.time_ns()
    train_boosted(X, "bernoulli", y, 1, f0, params)
    dt = sum(e["duration_ms"] for e in timeline.snapshot(timeline.CAPACITY)
             if e["kind"] == "tree_block" and e["start_ns"] >= t_fit) / 1e3

    # record which level flow produced this number
    from h2o3_tpu.models.tree.booster import _tree_subtract_enabled
    _subtract_on = _tree_subtract_enabled()

    rows_per_sec = n_rows * ntrees / dt  # row-scans per second per chip

    # MFU accounting: the histogram build is the FLOP budget — per level
    # the node-matmul kernel contracts one_hot(bins)[R, F*B1] against
    # node-masked vals [R, K*C] (C=4 channels), so FLOPs = 2*R*F*B1*K*C
    # summed over levels (K = 2**d nodes; subtraction builds only the
    # smaller child, ~halving K past the root).  Achieved TFLOP/s over the
    # device kind's bf16 peak gives MFU.
    n_bins1, chans, n_feat = 257, 4, X.shape[1]
    level_nodes = sum(
        max(1, 2 ** d // (2 if _subtract_on and d > 0 else 1))
        for d in range(max_depth)
    )
    flops = 2.0 * n_rows * n_feat * n_bins1 * chans * level_nodes * ntrees
    tflops = flops / dt / 1e12
    target = 8_000_000.0

    # telemetry ride-along: jit-miss / dispatch / shard-byte totals travel
    # with the number so regressions in compile count or dispatch volume
    # are visible next to the throughput
    tel = {k: (round(v, 3) if isinstance(v, float) else v)
           for k, v in telemetry.REGISTRY.summary().items() if v}

    print(json.dumps({
        "metric": "tpu_hist_train_rows_per_sec_per_chip",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec (n_rows*ntrees/train_time, Higgs-shaped 28f)",
        "vs_baseline": round(rows_per_sec / target, 3),
        "device": {"platform": dev.platform, "device_kind": dev.device_kind,
                   "device_count": len(jax.devices())},
        "detail": {"n_rows": n_rows, "ntrees": ntrees,
                   "max_depth": max_depth, "train_s": round(dt, 3),
                   "warmup_s": round(warmup_s, 1),
                   "subtract": _subtract_on,
                   "vs_baseline_is": "value / 8M rows/sec round target",
                   "vs_north_star_25M": round(rows_per_sec / 25e6, 3),
                   "achieved_tflops": round(tflops, 2),
                   "peak_bf16_tflops": peak,
                   "mfu_vs_bf16_peak": round(tflops / peak, 4)},
        "telemetry": tel,
    }))


def _cache_bench() -> None:
    """CPU-runnable warm-vs-cold devcache microbench (PR 3 acceptance).

    N repeat fits of GLM+GBM on ONE frame plus repeat map_reduce
    dispatches; reports upload bytes and dispatch/fit wall cold vs warm,
    and the ``mapreduce_jit_cache_total`` hit ratio. Prints ONE JSON line.
    Runs entirely on the host CPU backend — the caching win is provable
    without TPU access (`python bench.py --cache-bench`).
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.compute.mapreduce import FrameTable, map_reduce
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.util import telemetry

    telemetry.install_jax_compile_listener()
    n_rows = int(os.environ.get("BENCH_CACHE_ROWS", 100_000))
    n_fits = int(os.environ.get("BENCH_CACHE_FITS", 3))

    import numpy as np
    X, y = synth_higgs(n_rows, n_feat=8)
    fr = Frame.from_dict(
        {f"f{i}": X[:, i].astype(np.float64) for i in range(X.shape[1])}
        | {"y": y}
    )
    shard_bytes = telemetry.REGISTRY.get("shard_bytes_total")
    jit_cache = telemetry.REGISTRY.get("mapreduce_jit_cache_total")

    def fit_once():
        t0 = time.time()
        GLM(response_column="y", family="binomial", lambda_=0.0).train(fr)
        GBM(response_column="y", ntrees=3, max_depth=3, seed=0).train(fr)
        return time.time() - t0

    def dispatch_once():
        tbl = FrameTable.from_frame(fr, columns=["f0", "f1"])
        t0 = time.time()
        map_reduce(
            _cache_bench_stat, tbl)
        return time.time() - t0

    b0 = shard_bytes.total()
    cold_fit = fit_once()
    cold_dispatch = dispatch_once()
    cold_bytes = shard_bytes.total() - b0

    warm_fit, warm_dispatch, warm_bytes = [], [], []
    for _ in range(max(1, n_fits - 1)):
        b1 = shard_bytes.total()
        warm_fit.append(fit_once())
        warm_dispatch.append(dispatch_once())
        warm_bytes.append(shard_bytes.total() - b1)

    hits = sum(
        s["value"] for s in jit_cache.snapshot()["series"]
        if s["labels"]["result"] == "hit"
    )
    total = sum(s["value"] for s in jit_cache.snapshot()["series"])
    summary = {
        k: v for k, v in telemetry.REGISTRY.summary().items()
        if k.startswith(("devcache", "mapreduce", "shard"))
    }
    print(json.dumps({
        "metric": "devcache_warm_vs_cold",
        "unit": "seconds / bytes (lower warm is the win)",
        "n_rows": n_rows,
        "fits_per_phase": "1 GLM + 1 GBM",
        "cold": {"fit_s": round(cold_fit, 3),
                 "dispatch_s": round(cold_dispatch, 4),
                 "upload_bytes": cold_bytes},
        "warm": {"fit_s": round(min(warm_fit), 3),
                 "dispatch_s": round(min(warm_dispatch), 4),
                 "upload_bytes": max(warm_bytes)},
        "warm_beats_cold": bool(
            min(warm_fit) < cold_fit
            and min(warm_dispatch) < cold_dispatch
            and max(warm_bytes) < cold_bytes
        ),
        "jit_cache_hit_ratio": round(hits / total, 3) if total else None,
        "telemetry": summary,
    }))


def _dist_rapids_cell() -> dict:
    """The distributed-Rapids cell of ``--rapids-bench``: one fused
    ``:=``/filter/reduce pipeline run caller-local over a materialized
    frame (1-node, the bit-identity reference) and again over a
    chunk-homed ``DistFrame`` on a 3-node in-process cloud
    (``rapids/dist_exec.py``), where each region ships as a canonical
    sexpr and the derived/filtered columns stay home-resident.  Reports
    warm pipeline wall and per-op wall for both modes, the bytes that
    actually moved (dtask payloads + ring reads, pinned so gossip noise
    cannot pollute the cell) vs the f64 frame body a gather would move,
    and asserts ``bit_identical`` + ``partials_only`` + a
    zero-plan-compile warm path in-run."""
    import numpy as np

    from h2o3_tpu.cluster import dkv as cdkv
    from h2o3_tpu.cluster import tasks as ctasks
    from h2o3_tpu.cluster.membership import Cloud, set_local_cloud
    from h2o3_tpu.frame.parse import _iter_body_chunks, parse_csv, \
        parse_setup
    from h2o3_tpu.keyed import KeyedStore
    from h2o3_tpu.rapids.runtime import Session, exec_rapids
    from h2o3_tpu.util import telemetry

    n = int(os.environ.get("BENCH_DIST_RAPIDS_ROWS", 30_000))
    reps = 3

    def _meter(name, **labels):
        c = telemetry.REGISTRY.get(name)
        if c is None:
            return 0.0
        return sum(s["value"] for s in c.snapshot()["series"]
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    # integer-valued floats: reducer partials are exact in f64 under
    # any chunk partitioning, so merge order cannot move bits
    xs = np.arange(n) % 97
    ys = (np.arange(n) * 7) % 31
    text = "x,y\n" + "".join(f"{xs[i]},{ys[i]}\n" for i in range(n))

    clouds = []
    for i in range(3):
        c = Cloud("rapbench", f"rb{i}", hb_interval=0.05)
        cdkv.install(c, KeyedStore())
        ctasks.install(c)
        clouds.append(c)
    seeds = [c.info.addr for c in clouds]
    for c in clouds:
        c.start([a for a in seeds if a != c.info.addr])
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline and not all(
            c.size() == 3 for c in clouds):
        time.sleep(0.02)

    saved = os.environ.get("H2O3_TPU_RAPIDS_FUSION")
    try:
        set_local_cloud(clouds[0])
        os.environ["H2O3_TPU_RAPIDS_FUSION"] = "1"
        setup = parse_setup(text)
        chunks = list(_iter_body_chunks(
            [text.encode()], 16384, setup.header,
            setup.skip_blank_lines))
        fr = ctasks.distributed_parse_chunks(
            chunks, setup, cloud=clouds[0], key="bench_dist_rapids_df")
        n_homes = len({g["home_name"]
                       for g in fr.chunk_layout["groups"]})

        session = Session()
        session.assign("db", fr)
        session.assign("lb", parse_csv(text))

        # :=-derive onto the homes, filter through a shipped mask,
        # reduce to partials — three regions, ~4 fused prims
        n_ops = 4

        def _pipeline(v):
            exec_rapids(
                f"(tmp= {v}d (:= {v}b (* (cols_py {v}b 0) 2) 1 _))",
                session)
            exec_rapids(
                f"(tmp= {v}f (rows {v}d (< (cols_py {v}d 0) 48)))",
                session)
            out = exec_rapids(
                f"(sum (* (cols_py {v}f 0) (cols_py {v}f 1)))", session)
            return int(np.float64(out.value).view(np.uint64))

        def _timed(v):
            sig = _pipeline(v)  # cold: compiles, probes, caches
            w0 = _meter("rpc_payload_bytes_total",
                        direction="sent", method="dtask")
            g0 = _meter("rpc_payload_bytes_total", method="dkv_get")
            pb0 = _meter("rapids_dist_partial_bytes_total")
            dd0 = _meter("rapids_dist_total", result="dist")
            pm0 = (_meter("mapreduce_plan_cache_total",
                          op="rapids_dist", result="miss")
                   + _meter("mapreduce_plan_cache_total",
                            op="rapids_fusion", result="miss"))
            t = time.perf_counter()
            sig = _pipeline(v)
            wall = time.perf_counter() - t
            meters = {
                "moved_bytes": (
                    _meter("rpc_payload_bytes_total",
                           direction="sent", method="dtask") - w0
                    + _meter("rpc_payload_bytes_total",
                             method="dkv_get") - g0),
                "partial_bytes": (
                    _meter("rapids_dist_partial_bytes_total") - pb0),
                "dist_regions": (
                    _meter("rapids_dist_total", result="dist") - dd0),
                "plan_misses": (
                    _meter("mapreduce_plan_cache_total",
                           op="rapids_dist", result="miss")
                    + _meter("mapreduce_plan_cache_total",
                             op="rapids_fusion", result="miss") - pm0),
            }
            for _ in range(reps - 1):
                t = time.perf_counter()
                _pipeline(v)
                wall = min(wall, time.perf_counter() - t)
            return {"sig": sig, "wall": wall, **meters}

        local = _timed("l")
        dist = _timed("d")

        frame_bytes = 8 * n * 2
        partials_only = dist["moved_bytes"] < frame_bytes / 4
        return {
            "rows": n,
            "homes": n_homes,
            "pipeline": ":= derive -> mask filter -> sum reduce",
            "pipeline_ops": n_ops,
            "warm_wall_1node_ms": round(local["wall"] * 1e3, 2),
            "warm_wall_3node_ms": round(dist["wall"] * 1e3, 2),
            "warm_per_op_ms_1node": round(
                local["wall"] * 1e3 / n_ops, 3),
            "warm_per_op_ms_3node": round(
                dist["wall"] * 1e3 / n_ops, 3),
            "dist_regions_per_run": int(dist["dist_regions"]),
            "wire_moved_bytes": int(dist["moved_bytes"]),
            "partial_bytes": int(dist["partial_bytes"]),
            "frame_body_bytes": frame_bytes,
            "wire_vs_frame_ratio": round(
                dist["moved_bytes"] / max(frame_bytes, 1), 4),
            "bit_identical": local["sig"] == dist["sig"],
            "partials_only": bool(partials_only),
            "warm_zero_plan_compile": dist["plan_misses"] == 0.0,
        }
    finally:
        if saved is None:
            os.environ.pop("H2O3_TPU_RAPIDS_FUSION", None)
        else:
            os.environ["H2O3_TPU_RAPIDS_FUSION"] = saved
        set_local_cloud(None)
        for c in clouds:
            try:
                c.stop()
            except Exception:
                pass


def _rapids_bench() -> None:
    """CPU-runnable rapids query-fusion bench (fusion PR acceptance).

    One ~20-op munging pipeline (column selects, scale, abs-clip via
    ifelse, sqrt, floor, modulo, compare, sum reduce) over a generated
    2-column frame, three ways: op-at-a-time interpreter
    (H2O3_TPU_RAPIDS_FUSION=0), fused cold (first dispatch: lowering +
    trace + compile + upload), fused warm (plan cache + devcache hits).
    Asserts fused/interpreted bit-identity in-run and a zero-recompile,
    zero-upload warm path; a second pipeline with a non-fusible log1p in
    the middle pins fallback-at-the-boundary parity. Writes
    RAPIDS_BENCH.json and prints the same JSON (`--rapids-bench`)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from h2o3_tpu.frame.frame import Column, ColType, Frame
    from h2o3_tpu.rapids.runtime import Session, exec_rapids
    from h2o3_tpu.util import telemetry

    n_rows = int(os.environ.get("BENCH_RAPIDS_ROWS", 2_000_000))
    reps = int(os.environ.get("BENCH_RAPIDS_REPS", 5))
    rng = np.random.default_rng(7)
    session = Session()
    fr = Frame([
        Column("x", rng.standard_normal(n_rows), ColType.NUM),
        Column("y", rng.standard_normal(n_rows), ColType.NUM),
    ])
    session.assign("rb", fr)

    # all-fusible ~20-op pipeline, one scalar out (sum-reduce root)
    pipeline = (
        "(sum (* (+ (sqrt (abs (+ (cols_py rb 0) (cols_py rb 1)))) "
        "(ifelse (> (cols_py rb 0) 0) (cols_py rb 0) (- 0 (cols_py rb 0)))) "
        "(+ (* (floor (cols_py rb 1)) 0.25) (% (cols_py rb 0) 3))))"
    )
    # same shape with a non-fusible log1p inside: the region fractures at
    # the boundary and must still be bit-identical
    mixed = (
        "(sum (* (log1p (abs (+ (cols_py rb 0) (cols_py rb 1)))) "
        "(+ (* (floor (cols_py rb 1)) 0.25) (% (cols_py rb 0) 3))))"
    )

    def bits(v: float) -> int:
        return int(np.float64(v).view(np.uint64))

    def run(expr, fusion: bool) -> tuple:
        os.environ["H2O3_TPU_RAPIDS_FUSION"] = "1" if fusion else "0"
        t0 = time.perf_counter()
        out = exec_rapids(expr, session)
        return time.perf_counter() - t0, float(out.value)

    def counters():
        def val(name, **labels):
            c = telemetry.REGISTRY.get(name)
            return float(c.value(**labels)) if c is not None else 0.0

        return {
            "jit_miss": val("mapreduce_jit_cache_total",
                            op="map_batches", result="miss"),
            "plan_miss": val("mapreduce_plan_cache_total",
                             op="rapids_fusion", result="miss"),
            "upload_bytes": val("shard_bytes_total"),
            "devcache_miss": val("devcache_requests_total",
                                 kind="frame_table", result="miss"),
        }

    interp_s, interp_v = zip(*(run(pipeline, fusion=False)
                               for _ in range(reps)))
    cold_s, cold_v = run(pipeline, fusion=True)
    snap = counters()
    warm = [run(pipeline, fusion=True) for _ in range(reps)]
    warm_s = [t for t, _ in warm]
    warm_deltas = {k: counters()[k] - snap[k] for k in snap}

    mixed_interp = run(mixed, fusion=False)[1]
    mixed_fused = run(mixed, fusion=True)[1]

    values = {interp_v[0], cold_v} | {v for _, v in warm}
    bit_identical = len({bits(v) for v in values}) == 1
    mixed_identical = bits(mixed_interp) == bits(mixed_fused)
    warm_clean = all(v == 0.0 for v in warm_deltas.values())

    interp_best = min(interp_s)
    warm_best = min(warm_s)
    fusion_counter = telemetry.REGISTRY.get("rapids_fusion_total")
    result = {
        "metric": "rapids_fusion_warm_speedup",
        "unit": "x (interpreted wall / fused warm wall, same pipeline)",
        "n_rows": n_rows,
        "pipeline_ops": 20,
        "interpreted_s": round(interp_best, 4),
        "fused_cold_s": round(cold_s, 4),
        "fused_warm_s": round(warm_best, 4),
        "speedup_warm": round(interp_best / warm_best, 2),
        "rows_per_sec": {
            "interpreted": int(n_rows / interp_best),
            "fused_warm": int(n_rows / warm_best),
        },
        "bit_identical": bit_identical,
        "mixed_fallback_bit_identical": mixed_identical,
        "warm_zero_recompile_zero_upload": warm_clean,
        "warm_deltas": warm_deltas,
        "fused_regions": fusion_counter.value(result="fused"),
        "fallback_regions": fusion_counter.value(result="fallback"),
    }
    dist_cell = _dist_rapids_cell()
    result["dist_rapids"] = dist_cell
    with open(os.path.join(_HERE, "RAPIDS_BENCH.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    if not (bit_identical and mixed_identical and warm_clean
            and dist_cell["bit_identical"] and dist_cell["partials_only"]
            and dist_cell["warm_zero_plan_compile"]):
        sys.exit(1)


def _parse_bench_csv(target_mb: float) -> str:
    """Deterministic mixed NUM/CAT/TIME/STR/NUM CSV of ~target_mb MB —
    the column mix routes every chunk through every native primitive
    (float parse, dict encode, time parse, string gather)."""
    cats = ("alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta")
    row_bytes = 62  # measured mean of the format below
    n = max(1000, int(target_mb * 1e6 / row_bytes))
    rows = ["num,cat,time,str,count"]
    for i in range(n):
        num = "NA" if i % 97 == 0 else f"{i * 0.75 - 17.0:.4f}"
        tim = (f"2021-{(i % 12) + 1:02d}-{(i % 27) + 1:02d}"
               f" 10:{i % 60:02d}:{(i * 7) % 60:02d}")
        rows.append(f"{num},{cats[i % 7]},{tim},free text {i % 5000},{i}")
    rows.append("")
    return "\n".join(rows)


def _frames_identical(a, b) -> bool:
    import numpy as np

    if a.names != b.names or a.nrows != b.nrows:
        return False
    for n in a.names:
        ca, cb = a.col(n), b.col(n)
        if ca.type != cb.type or ca.domain != cb.domain:
            return False
        if ca.data.dtype == object:
            if list(ca.data) != list(cb.data):
                return False
        elif not np.array_equal(ca.data, cb.data, equal_nan=True):
            return False
    return True


def _parse_bench() -> None:
    """CPU parse-pipeline bench (chunk-parallel two-phase ingest).

    Rows/sec at 1/2/4/8 workers on a generated mixed NUM/CAT/TIME/STR
    CSV (~BENCH_PARSE_MB, default 100), plain and gzipped, with scaling
    efficiency and a serial-vs-parallel bit-identity check in the same
    run.  Prints ONE JSON line and mirrors it to PARSE_BENCH.json.
    Worker scaling is an OS-scheduling property: on a single-core host
    (host_cpus=1) throughput is flat across worker counts by physics —
    the pipeline-vs-serial-python speedup is the portable number there.
    """
    import gzip
    import io

    from h2o3_tpu.frame.parse import parse_csv, parse_csv_stream
    from h2o3_tpu.frame.ingest import parse_bytes
    from h2o3_tpu.util import telemetry

    size_mb = float(os.environ.get("BENCH_PARSE_MB", 100))
    repeats = int(os.environ.get("BENCH_PARSE_REPEATS", 2))
    worker_counts = (1, 2, 4, 8)
    # 2 MiB chunks: ~50 chunks at 100 MB, enough scheduling granularity
    # for 8 workers without drowning in per-chunk overhead
    os.environ.setdefault("H2O3_TPU_PARSE_CHUNK_BYTES", str(2 << 20))
    chunk_bytes = int(os.environ["H2O3_TPU_PARSE_CHUNK_BYTES"])

    t0 = time.time()
    text = _parse_bench_csv(size_mb)
    raw = text.encode("utf-8")
    nbytes = len(raw)
    gen_s = time.time() - t0
    print(f"# generated {nbytes / 1e6:.1f} MB csv in {gen_s:.1f}s",
          file=sys.stderr)

    def timed_parse(workers):
        best, fr = None, None
        for _ in range(max(1, repeats)):
            t = time.time()
            f = parse_csv_stream(io.BytesIO(raw), workers=workers)
            dt = time.time() - t
            if best is None or dt < best:
                best, fr = dt, f
        return best, fr

    # warmup outside the timers: builds/loads the native lib (a stale
    # .so recompiles on first use) and faults the page cache
    w_end = raw.find(b"\n", min(3 << 20, len(raw) // 2)) + 1
    parse_csv_stream(io.BytesIO(raw[:w_end] if w_end > 0 else raw),
                     workers=2)

    plain = {}
    frames = {}
    for w in worker_counts:
        dt, fr = timed_parse(w)
        plain[w] = {"seconds": round(dt, 3),
                    "rows_per_sec": round(fr.nrows / dt, 1),
                    "mb_per_sec": round(nbytes / 1e6 / dt, 1)}
        if w in (1, worker_counts[-1]):
            frames[w] = fr
        print(f"# workers={w}: {dt:.2f}s "
              f"({fr.nrows / dt / 1e6:.2f}M rows/s)", file=sys.stderr)
    nrows = frames[1].nrows

    # gzipped source through the streamed-decompression ingest path
    gz = gzip.compress(raw, compresslevel=1)
    gz_res = {}
    gz_identical = True
    for w in (1, worker_counts[-1]):
        best, fr = None, None
        for _ in range(max(1, repeats)):
            t = time.time()
            f = parse_bytes("bench.csv.gz", gz, workers=w)
            dt = time.time() - t
            if best is None or dt < best:
                best, fr = dt, f
        gz_res[w] = {"seconds": round(best, 3),
                     "rows_per_sec": round(fr.nrows / best, 1)}
        gz_identical = gz_identical and _frames_identical(frames[1], fr)
        print(f"# gz workers={w}: {best:.2f}s", file=sys.stderr)

    # bit-identity, same run: parallel vs workers=1 on the full input,
    # plus the serial whole-text oracle on a record-aligned prefix small
    # enough to take the serial path (it is pure-python and ~25x slower)
    wmax = worker_counts[-1]
    identical = _frames_identical(frames[1], frames[wmax])
    serial_mb = float(os.environ.get("BENCH_PARSE_SERIAL_MB", 8))
    cut = raw.rfind(b"\n", 0, int(serial_mb * 1e6)) + 1
    slice_text = raw[:cut].decode()
    # chunk threshold above the slice size forces the true serial
    # whole-text path (parse_csv routes anything larger to the pipeline)
    os.environ["H2O3_TPU_PARSE_CHUNK_BYTES"] = str(1 << 28)
    t = time.time()
    serial_fr = parse_csv(slice_text)
    serial_s = time.time() - t
    os.environ["H2O3_TPU_PARSE_CHUNK_BYTES"] = str(256 << 10)
    par_slice = parse_csv(slice_text, workers=wmax)
    os.environ["H2O3_TPU_PARSE_CHUNK_BYTES"] = str(chunk_bytes)
    serial_identical = _frames_identical(serial_fr, par_slice)
    serial_rps = serial_fr.nrows / serial_s

    rps1, rpsN = plain[1]["rows_per_sec"], plain[wmax]["rows_per_sec"]
    tel = {
        k: v for k, v in telemetry.REGISTRY.summary().items()
        if k.startswith("parse")
    }
    result = {
        "metric": "parse_rows_per_sec",
        "value": rpsN,
        "unit": f"rows/sec ({wmax} workers, mixed NUM/CAT/TIME/STR csv)",
        "vs_baseline": round(rpsN / serial_rps, 2),
        "detail": {
            "csv_mb": round(nbytes / 1e6, 1),
            "n_rows": nrows,
            "chunk_bytes": chunk_bytes,
            "host_cpus": os.cpu_count(),
            "workers": plain,
            "gz": gz_res,
            "scaling_efficiency": {
                w: round(plain[w]["rows_per_sec"] / (w * rps1), 3)
                for w in worker_counts
            },
            "speedup_8w_vs_1w": round(rpsN / rps1, 2),
            "serial_python_rows_per_sec": round(serial_rps, 1),
            "speedup_pipeline_vs_serial_python": round(rpsN / serial_rps, 2),
            "bit_identical_1w_vs_8w_full": identical,
            "bit_identical_serial_vs_parallel_slice": serial_identical,
            "bit_identical_gz_vs_plain": gz_identical,
            "vs_baseline_is": "pipeline rows/sec / serial-python rows/sec",
        },
        "telemetry": {k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in tel.items()},
    }
    with open(os.path.join(_HERE, "PARSE_BENCH.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


def _cache_bench_stat(cols, mask):
    """Module-level map fn so repeat dispatches share one plan-cache key."""
    import jax.numpy as jnp

    return jnp.sum(jnp.where(mask, cols["f0"] * cols["f1"], 0.0))


def _dist_hist_cell() -> dict:
    """The distributed-training cell of ``--hist-bench``: one GBM fit run
    1-node (``H2O3_TPU_DIST_HIST=local`` — the same engine with every
    histogram op executed caller-side, the bit-identity reference) and
    again against a 3-node in-process cloud with the frame parsed onto
    chunk homes (``models/tree/dist_hist.py``).  Reports fit wall and
    mean per-level wall for both modes, the partials-vs-rows wire ratio
    — histogram-partial bytes actually shipped vs the f64 frame body the
    move-the-data path would ship — and the bit-identity flag.  The
    partials bound is asserted in-run (``partials_bounded``): per level
    at most ``n_nodes x n_features x (nbins+1) x 3 x 8`` bytes per home.
    Fit walls are min-of-3 warm repeats (scheduler jitter at the ~200ms
    scale otherwise swamps mode deltas); wire/cache meters are deltas
    around the first warm repeat only.
    """
    import pickle

    import numpy as np

    from h2o3_tpu.cluster import dkv as cdkv
    from h2o3_tpu.cluster import tasks as ctasks
    from h2o3_tpu.cluster.membership import Cloud, set_local_cloud
    from h2o3_tpu.frame.parse import _iter_body_chunks, parse_setup
    from h2o3_tpu.keyed import KeyedStore
    from h2o3_tpu.models.grid import metric_value
    from h2o3_tpu.models.tree.gbm import GBM, GBMParameters
    from h2o3_tpu.util import telemetry

    n = int(os.environ.get("BENCH_DIST_HIST_ROWS", 30_000))
    nbins, depth, ntrees = 16, 3, 4

    def _meter(name, **labels):
        c = telemetry.REGISTRY.get(name)
        if c is None:
            return 0.0
        return sum(s["value"] for s in c.snapshot()["series"]
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    cats = ("lo", "mid", "hi")
    yes_no = ("no", "yes")
    lines = ["x,y,z,c,resp"]
    for i in range(n):
        x, y, z = i % 97, (i * 7) % 31, (i * 13) % 53
        lines.append(f"{x},{y},{z},{cats[i % 3]},"
                     f"{yes_no[int((x * 3 + y) % 11 < 5)]}")
    text = "\n".join(lines) + "\n"

    clouds = []
    for i in range(3):
        c = Cloud("histbench", f"hb{i}", hb_interval=0.05)
        cdkv.install(c, KeyedStore())
        ctasks.install(c)
        clouds.append(c)
    seeds = [c.info.addr for c in clouds]
    for c in clouds:
        c.start([a for a in seeds if a != c.info.addr])
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline and not all(
            c.size() == 3 for c in clouds):
        time.sleep(0.02)

    saved = os.environ.get("H2O3_TPU_DIST_HIST")
    try:
        set_local_cloud(clouds[0])
        setup = parse_setup(text)
        chunks = list(_iter_body_chunks(
            [text.encode()], 32768, setup.header, setup.skip_blank_lines))
        fr = ctasks.distributed_parse_chunks(
            chunks, setup, cloud=clouds[0], key="bench_dist_hist_df")
        n_homes = len({g["home_name"]
                       for g in fr.chunk_layout["groups"]})

        def _fit():
            m = GBM(GBMParameters(
                response_column="resp", ntrees=ntrees, max_depth=depth,
                nbins=nbins, min_rows=1.0, seed=17)).train(fr)
            arrays = [
                np.stack(getattr(t, f))
                for t in m.booster.trees_per_class
                for f in ("feat", "split_bin", "default_left",
                          "is_split", "leaf")]
            return pickle.dumps([arrays,
                                 np.asarray(m.booster.init_margin),
                                 metric_value(m, "auto")[0]])

        def _timed_fit(mode):
            os.environ["H2O3_TPU_DIST_HIST"] = mode
            _fit()  # warms the mode's jit / binned contexts
            lv0 = _meter("dist_hist_levels_total")
            pb0 = _meter("dist_hist_partial_bytes_total")
            w0 = _meter("rpc_payload_bytes_total", direction="sent")
            # the timed fit IS the warm repeat fit on the unmutated
            # DistFrame: its hist_bind rounds must serve every group's
            # binned codes from the device cache — a miss is the only
            # path that decodes (apply_bins) or uploads, so miss == 0
            # is the zero-decode / zero-upload-bytes proof
            bm0 = _meter("dist_hist_bind_cache_total", result="miss")
            bh0 = _meter("dist_hist_bind_cache_total", result="hit")
            dm0 = _meter("devcache_requests_total",
                         kind="hist_bins_home", result="miss")
            t = time.perf_counter()
            sig = _fit()
            wall = time.perf_counter() - t
            meters = {
                "levels": _meter("dist_hist_levels_total") - lv0,
                "partial_bytes": (
                    _meter("dist_hist_partial_bytes_total") - pb0),
                "sent_bytes": (
                    _meter("rpc_payload_bytes_total",
                           direction="sent") - w0),
                "bind_decodes": (
                    _meter("dist_hist_bind_cache_total", result="miss")
                    - bm0),
                "bind_cache_hits": (
                    _meter("dist_hist_bind_cache_total", result="hit")
                    - bh0),
                "bind_upload_misses": (
                    _meter("devcache_requests_total",
                           kind="hist_bins_home", result="miss") - dm0),
            }
            # min-of-k warm walls (same rationale as the level rows):
            # one fit is ~200ms of mostly-idle RPC turnarounds, exactly
            # the scale at which scheduler jitter swamps a real delta
            for _ in range(2):
                t = time.perf_counter()
                _fit()
                wall = min(wall, time.perf_counter() - t)
            return {
                "sig": sig,
                "wall": wall,
                **meters,
            }

        local = _timed_fit("local")
        dist = _timed_fit("1")

        # the per-level arithmetic from the README: worst case
        # 2^(depth-1) sibling nodes x F features x (nbins + 1 NA
        # bucket) x {sum_g, sum_h, sum_w} x f64, per home
        F, n_bins1 = 4, nbins + 1
        per_level_cap = (1 << max(depth - 1, 0)) * F * n_bins1 * 3 * 8
        frame_bytes = 8 * n * 5
        partials_bounded = (
            dist["levels"] > 0
            and dist["partial_bytes"]
            <= dist["levels"] * per_level_cap * n_homes)
        return {
            "rows": n,
            "homes": n_homes,
            "ntrees": ntrees,
            "max_depth": depth,
            "nbins": nbins,
            "fit_wall_1node_ms": round(local["wall"] * 1e3, 1),
            "fit_wall_3node_ms": round(dist["wall"] * 1e3, 1),
            "level_ops_3node": int(dist["levels"]),
            "mean_level_ms_1node": round(
                local["wall"] * 1e3 / max(local["levels"], 1), 2),
            "mean_level_ms_3node": round(
                dist["wall"] * 1e3 / max(dist["levels"], 1), 2),
            "partial_bytes": int(dist["partial_bytes"]),
            "frame_body_bytes": frame_bytes,
            "partials_vs_rows_ratio": round(
                dist["partial_bytes"] / max(frame_bytes, 1), 4),
            "wire_sent_bytes": int(dist["sent_bytes"]),
            "partials_bounded": bool(partials_bounded),
            "wire_under_frame": bool(dist["sent_bytes"] < frame_bytes),
            "bit_identical": local["sig"] == dist["sig"],
            "warm_bind_decodes": int(dist["bind_decodes"]),
            "warm_bind_cache_hits": int(dist["bind_cache_hits"]),
            "warm_binned_upload_zero": bool(
                dist["bind_upload_misses"] == 0
                and dist["bind_cache_hits"] > 0),
        }
    finally:
        if saved is None:
            os.environ.pop("H2O3_TPU_DIST_HIST", None)
        else:
            os.environ["H2O3_TPU_DIST_HIST"] = saved
        set_local_cloud(None)
        for c in clouds:
            try:
                c.stop()
            except Exception:
                pass


def _hist_bench() -> None:
    """CPU booster-histogram microbench (the XLA scatter path).

    Times ``build_histogram_sharded`` — the per-level inner loop of the
    tree booster — on synthetic Higgs-shaped data quantized once with
    ``make_bins``/``apply_bins``, at node counts matching tree levels
    0..depth (2^level histogram nodes).  Per level it reports the cold
    wall (first call; plan compile included only when the node-bucket
    ladder misses), the warm wall (min of repeat calls on the cached plan
    — min-of-k, not median: the compile question is "is there a plan", so
    the best warm rep is the signal and the rest is scheduler noise), the
    warm-plan delta between them, rows/s from the warm wall, and the
    plan-cache hit/miss counts (``hist_plan_cache_total``) so compile-free
    warm levels are asserted, not inferred from walls.  The ``plan_churn``
    cell aggregates those per-level compile deltas and bucket hits; the
    run FAILS if any warm rep misses the plan cache.  The ``dist_hist``
    cell then prices map-side training over chunk homes (see
    :func:`_dist_hist_cell`).
    Prints ONE JSON line and mirrors it
    to HIST_BENCH.json.  CPU-only by construction: ``H2O3_TPU_HIST_IMPL``
    is pinned to ``scatter`` so numbers compare across hosts without a
    TPU in the loop (the Pallas kernel tier is scripts/bench_hist_kernel
    on real hardware)."""
    import platform

    os.environ["H2O3_TPU_HIST_IMPL"] = "scatter"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from h2o3_tpu.ops.histogram import (
        apply_bins,
        build_histogram_sharded,
        make_bins,
    )

    n = int(os.environ.get("BENCH_HIST_ROWS", 200_000))
    nfeat = int(os.environ.get("BENCH_HIST_FEATS", 28))
    nbins = int(os.environ.get("BENCH_HIST_BINS", 64))
    depth = int(os.environ.get("BENCH_HIST_DEPTH", 6))
    reps = int(os.environ.get("BENCH_HIST_REPS", 5))

    X, _y = synth_higgs(n, nfeat, seed=0)
    t = time.perf_counter()
    edges = make_bins(X, nbins=nbins, seed=0)
    make_bins_ms = (time.perf_counter() - t) * 1e3
    t = time.perf_counter()
    codes = apply_bins(X, edges)
    apply_bins_ms = (time.perf_counter() - t) * 1e3

    bins = jnp.asarray(codes, dtype=jnp.int32)
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    n_bins1 = nbins + 1  # + the NA bucket at the end

    from h2o3_tpu.ops.histogram import node_buckets, pad_nodes
    from h2o3_tpu.util import telemetry

    def _plan(result):
        c = telemetry.REGISTRY.get("hist_plan_cache_total")
        if c is None:
            return 0.0
        return sum(s["value"] for s in c.snapshot()["series"]
                   if s["labels"].get("result") == result)

    levels = []
    for lvl in range(depth + 1):
        k = 2 ** lvl
        nodes = jnp.asarray(rng.integers(0, k, size=n).astype(np.int32))
        m0 = _plan("miss")
        t = time.perf_counter()
        jax.block_until_ready(build_histogram_sharded(
            bins, nodes, g, h, k, n_bins1))
        cold = time.perf_counter() - t
        cold_miss = int(_plan("miss") - m0)
        m1, h1 = _plan("miss"), _plan("hit")
        walls = []
        for _ in range(reps):
            t = time.perf_counter()
            jax.block_until_ready(build_histogram_sharded(
                bins, nodes, g, h, k, n_bins1))
            walls.append(time.perf_counter() - t)
        warm = min(walls)  # min-of-k: any rep on the cached plan is proof
        warm_miss = int(_plan("miss") - m1)
        warm_hits = int(_plan("hit") - h1)
        levels.append({
            "level": lvl,
            "n_nodes": k,
            "node_bucket": pad_nodes(k),
            "cold_ms": round(cold * 1e3, 2),
            "warm_ms": round(warm * 1e3, 2),
            "warm_plan_delta_ms": round((cold - warm) * 1e3, 2),
            "rows_per_sec": round(n / max(warm, 1e-9), 1),
            "plan_cache": {"cold_miss": cold_miss,
                           "warm_hits": warm_hits,
                           "warm_miss": warm_miss},
        })
    # warm tree levels must compile nothing: every warm rep a plan-cache
    # hit, and within a node bucket only the FIRST level's cold call may
    # compile — asserted on the counters, not inferred from wall noise
    compile_free = all(lv["plan_cache"]["warm_miss"] == 0 for lv in levels)
    bucket_first = {}
    for lv in levels:
        bucket_first.setdefault(lv["node_bucket"], lv["level"])
    warm_bucket_levels = [lv for lv in levels
                          if bucket_first[lv["node_bucket"]] != lv["level"]]
    bucket_hits = all(lv["plan_cache"]["cold_miss"] == 0
                      for lv in warm_bucket_levels)
    if not (compile_free and bucket_hits):
        raise AssertionError(
            f"plan churn on warm levels: {[lv['plan_cache'] for lv in levels]}")
    plan_churn = {
        "node_buckets": list(node_buckets()),
        "plan_misses": sum(lv["plan_cache"]["cold_miss"] for lv in levels),
        "bucket_hit_levels": len(warm_bucket_levels),
        "per_level": [
            {"level": lv["level"], "n_nodes": lv["n_nodes"],
             "node_bucket": lv["node_bucket"],
             "compile_delta_ms": (lv["warm_plan_delta_ms"]
                                  if lv["plan_cache"]["cold_miss"] else 0.0),
             "plan_cache": lv["plan_cache"]}
            for lv in levels],
        "warm_levels_compile_free": bool(compile_free and bucket_hits),
    }
    deepest = levels[-1]
    dist_cell = _dist_hist_cell()
    result = {
        "metric": "cpu_hist_scatter_rows_per_sec",
        "value": deepest["rows_per_sec"],
        "unit": (f"rows/sec (warm scatter histogram, level {depth}: "
                 f"{deepest['n_nodes']} nodes, {nfeat} features, "
                 f"{nbins} bins)"),
        "vs_baseline": round(
            levels[0]["rows_per_sec"]
            / max(deepest["rows_per_sec"], 1e-9), 2),
        "detail": {
            "host_cpus": os.cpu_count(),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "impl": "scatter",
            "rows": n,
            "features": nfeat,
            "nbins": nbins,
            "make_bins_ms": round(make_bins_ms, 1),
            "apply_bins_ms": round(apply_bins_ms, 1),
            "per_level": levels,
            "plan_churn": plan_churn,
            "dist_hist": dist_cell,
            "vs_baseline_is": "level-0 rows/s / deepest-level rows/s",
        },
    }
    with open(os.path.join(_HERE, "HIST_BENCH.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


def _cluster_bench() -> None:
    """2-node localhost cloud microbench (application-plane cluster).

    Boots this process as node 0 and a ``h2o3_tpu.cluster.nodeproc``
    subprocess as node 1 (port 0 + address-file rendezvous, exactly the
    multi-process tests' harness), then measures the control plane: RPC
    round-trip latency percentiles, RPC throughput by payload size, and
    DKV put/get on keys homed locally vs on the remote node, plus a
    ``dist_frame`` cell: chunk-homed parse wall, chunk-homed vs local
    ``map_reduce`` wall, and partials-vs-frame bytes on the wire.  Prints
    ONE JSON line and mirrors it to CLUSTER_BENCH.json.  The control
    plane itself stays jax-free; only the dist_frame cell jits.
    """
    import platform
    import tempfile

    from h2o3_tpu.cluster.membership import boot_node, set_local_cloud
    from h2o3_tpu.keyed import KeyedStore
    from h2o3_tpu.util import telemetry

    rounds = int(os.environ.get("BENCH_CLUSTER_ROUNDS", 300))
    store = KeyedStore()
    cloud = boot_node("cluster-bench", "bench-n0",
                      hb_interval=0.2, store=store)
    router = store.router
    tmp = tempfile.mkdtemp(prefix="cluster_bench_")
    flat = os.path.join(tmp, "flatfile")
    addr1 = os.path.join(tmp, "n1.addr")
    with open(flat, "w") as f:
        f.write(f"{cloud.info.host}:{cloud.info.port}\n")
    child = subprocess.Popen(
        [sys.executable, "-m", "h2o3_tpu.cluster.nodeproc",
         "--cluster-name", "cluster-bench", "--node-name", "bench-n1",
         "--flatfile", flat, "--address-file", addr1,
         "--hb-interval", "0.2"],
        stdin=subprocess.PIPE, stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT, cwd=_HERE,
    )
    try:
        t0 = time.time()
        while time.time() - t0 < 30:
            if cloud.size() == 2 and cloud.consensus():
                break
            time.sleep(0.05)
        else:
            raise RuntimeError("2-node bench cloud never formed")
        peer = next(m for m in cloud.members_sorted()
                    if m.info.name == "bench-n1")

        def _pct(samples, q):
            s = sorted(samples)
            return s[min(len(s) - 1, int(q * len(s)))]

        # RPC round-trip latency (echo, tiny payload) — interleaved with
        # the traced variant in alternating blocks so scheduler/cache
        # drift between sections cancels out of the comparison
        lat = []
        lat_traced = []
        block = max(1, rounds // 4)
        for _ in range(4):
            for _ in range(block):
                t = time.perf_counter()
                cloud.client.call(peer.info.addr, "echo", b"x", timeout=5.0,
                                  target=peer.info.ident)
                lat.append(time.perf_counter() - t)
            with telemetry.Span("cluster_bench_traced"):
                for _ in range(block):
                    t = time.perf_counter()
                    cloud.client.call(peer.info.addr, "echo", b"x",
                                      timeout=5.0, target=peer.info.ident)
                    lat_traced.append(time.perf_counter() - t)
        rtt = {
            "p50_us": round(_pct(lat, 0.50) * 1e6, 1),
            "p90_us": round(_pct(lat, 0.90) * 1e6, 1),
            "p99_us": round(_pct(lat, 0.99) * 1e6, 1),
            "rounds": len(lat),
        }
        # telemetry overhead: the same echo RTT with tracing ACTIVE (an
        # open span makes the client inject trace context, open an
        # rpc_client span, and the server open its dispatch span) vs the
        # untraced blocks above.  Documented budget: <5% p50 regression on
        # a production control plane — operationalized at a 500us
        # reference RTT (cross-host LAN), i.e. <=25us absolute per traced
        # call.  The loopback percentage is also reported but is
        # pessimistic by construction: a sub-100us loopback RTT amplifies
        # a fixed ~20us span cost into a large-looking ratio.
        on_p50 = _pct(lat_traced, 0.50) * 1e6
        off_p50 = rtt["p50_us"]
        overhead_us = on_p50 - off_p50
        ref_rtt_us = 500.0
        budget_us = ref_rtt_us * 0.05
        trace_overhead = {
            "tracing_off_p50_us": off_p50,
            "tracing_on_p50_us": round(on_p50, 1),
            "overhead_us_p50": round(overhead_us, 1),
            "overhead_pct_p50_loopback": round(
                overhead_us / max(off_p50, 1e-9) * 100, 1),
            "budget": {
                "pct_p50": 5.0,
                "reference_rtt_us": ref_rtt_us,
                "overhead_budget_us": budget_us,
            },
            "within_budget": overhead_us <= budget_us,
        }
        # throughput by payload size (echo both ways: 2x bytes per RTT)
        thru = {}
        for sz in (64 << 10, 1 << 20, 4 << 20):
            payload = b"\0" * sz
            n = max(8, min(64, (64 << 20) // sz))
            t = time.perf_counter()
            for _ in range(n):
                cloud.client.call(peer.info.addr, "echo", payload,
                                  timeout=30.0, target=peer.info.ident)
            dt = time.perf_counter() - t
            thru[sz] = {"mb_per_sec": round(2 * sz * n / dt / 1e6, 1),
                        "calls": n}
        # DKV put/get: one key homed here, one homed on the peer
        local_key = next(k for k in (f"bench_local_{i}" for i in range(4096))
                         if router.home_name(k) == "bench-n0")
        remote_key = next(k for k in (f"bench_remote_{i}" for i in range(4096))
                          if router.home_name(k) == "bench-n1")
        value = list(range(1000))
        dkv = {}
        for label, key in (("local", local_key), ("remote", remote_key)):
            puts, gets = [], []
            for _ in range(rounds):
                t = time.perf_counter()
                store.put(key, value)
                puts.append(time.perf_counter() - t)
                t = time.perf_counter()
                got = store.get(key)
                gets.append(time.perf_counter() - t)
            assert got == value, f"{label} DKV roundtrip corrupted"
            store.remove(key)
            dkv[label] = {
                "put_p50_us": round(_pct(puts, 0.5) * 1e6, 1),
                "get_p50_us": round(_pct(gets, 0.5) * 1e6, 1),
            }
        # chunk-homed distributed Frame: parse-to-homes wall, chunk-homed
        # vs local map_reduce wall, and partials-vs-frame bytes on the
        # wire (the one jax user in this bench: the map side jits on
        # both members)
        import numpy as np

        from h2o3_tpu.cluster import frames as cframes
        from h2o3_tpu.cluster import tasks as ctasks
        from h2o3_tpu.frame.parse import _iter_body_chunks, parse_setup

        n = 60000
        xs = np.arange(n) % 97
        ys = (np.arange(n) * 7) % 31
        text = "x,y\n" + "".join(f"{xs[i]},{ys[i]}\n" for i in range(n))
        setup = parse_setup(text)
        chunks_in = list(_iter_body_chunks(
            [text.encode()], 32768, setup.header, setup.skip_blank_lines))
        t = time.perf_counter()
        fr = ctasks.distributed_parse_chunks(
            chunks_in, setup, cloud=cloud, key="bench_dist_frame")
        parse_wall = time.perf_counter() - t
        host = {"x": xs.astype(np.float64), "y": ys.astype(np.float64)}
        local_mr = ctasks.distributed_map_reduce(
            cframes.mr_sum_xy, host, cloud=None)  # warms the local jit
        t = time.perf_counter()
        ctasks.distributed_map_reduce(cframes.mr_sum_xy, host, cloud=None)
        local_wall = time.perf_counter() - t

        def _sent_bytes():
            c = telemetry.REGISTRY.get("rpc_payload_bytes_total")
            if c is None:
                return 0.0
            # sum over the method label: this cell wants total egress
            return sum(s["value"] for s in c.snapshot()["series"]
                       if s["labels"].get("direction") == "sent")

        ctasks.distributed_map_reduce(
            cframes.mr_sum_xy, fr, cloud=cloud)  # warms the remote jit
        s0 = _sent_bytes()
        t = time.perf_counter()
        dist_mr = ctasks.distributed_map_reduce(
            cframes.mr_sum_xy, fr, cloud=cloud)
        homed_wall = time.perf_counter() - t
        mr_sent = _sent_bytes() - s0
        frame_bytes = 2 * 8 * n
        import jax as _jax

        bit_identical = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(_jax.tree.leaves(local_mr),
                            _jax.tree.leaves(dist_mr)))
        # distributed model search: the same 6-cell GLM grid walked
        # single-node vs fanned across both members (cluster/search.py).
        # Each path runs once untimed to warm its jit caches, then once
        # timed; the leaderboards must be bit-identical either way (the
        # subsystem's determinism contract).  Runs BEFORE the dead-home
        # cell below: it needs the peer alive.
        from h2o3_tpu.frame.frame import ColType, Column, Frame
        from h2o3_tpu.models.glm import GLM, GLMParameters
        from h2o3_tpu.models.grid import GridSearch, cell_key, metric_value

        srng = np.random.default_rng(5)
        sn = 400
        sX = srng.normal(size=(sn, 3))
        slogit = sX @ np.array([1.0, -2.0, 0.5])
        sy = (srng.random(sn)
              < 1.0 / (1.0 + np.exp(-slogit))).astype(np.float64)
        scols = [Column(f"x{i}", sX[:, i]) for i in range(3)]
        scols.append(Column("y", sy, ColType.CAT, ["n", "p"]))
        sfr = Frame(scols)

        def _grid():
            return GridSearch(
                GLM,
                GLMParameters(response_column="y", family="binomial",
                              seed=7, nfolds=2),
                {"alpha": [0.0, 0.5, 1.0], "lambda_": [0.01, 0.1]})

        def _srows(grid):
            return [(cell_key(hp), metric_value(m, "auto")[0])
                    for hp, m in zip(grid.hyper_params, grid.models)]

        os.environ["H2O3_TPU_SEARCH_DIST"] = "0"
        try:
            _grid().train(sfr)  # warms the local jit
            t = time.perf_counter()
            sg1 = _grid().train(sfr)
            search_1node = time.perf_counter() - t
        finally:
            os.environ.pop("H2O3_TPU_SEARCH_DIST", None)
        _grid().train(sfr)  # warms the peer's jit + its frame transfer
        t = time.perf_counter()
        sg2 = _grid().train(sfr)
        search_2node = time.perf_counter() - t
        search_speedup = search_1node / max(search_2node, 1e-9)
        dist_search = {
            "cells": 6,
            "grid_wall_1node_ms": round(search_1node * 1e3, 1),
            "grid_wall_2node_ms": round(search_2node * 1e3, 1),
            "speedup": round(search_speedup, 2),
            "scaling_efficiency": round(search_speedup / 2.0, 2),
            "leaderboard_bit_identical": _srows(sg1) == _srows(sg2),
        }
        # one-home-dead recovery wall: SIGKILL the peer (this cell runs
        # last, nothing downstream needs it) and re-run the chunk-homed
        # map_reduce — the caller holds the dead home's replica chunks,
        # so the ladder recovers path=replica without a re-parse
        rec = telemetry.REGISTRY.get("cluster_fanout_recovered_total")
        rep0 = rec.value(path="replica") if rec is not None else 0.0
        child.kill()
        t = time.perf_counter()
        dead_mr = ctasks.distributed_map_reduce(
            cframes.mr_sum_xy, fr, cloud=cloud)
        dead_wall = time.perf_counter() - t
        dead_identical = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(_jax.tree.leaves(local_mr),
                            _jax.tree.leaves(dead_mr)))
        rep1 = rec.value(path="replica") if rec is not None else 0.0
        lay = getattr(fr, "chunk_layout", None) or {}
        dist_frame = {
            "rows": n,
            "chunks": len(chunks_in),
            "groups": len(lay.get("groups", ())),
            "parse_to_homes_ms": round(parse_wall * 1e3, 1),
            "map_reduce_local_ms": round(local_wall * 1e3, 1),
            "map_reduce_chunk_homed_ms": round(homed_wall * 1e3, 1),
            "map_reduce_one_home_dead_ms": round(dead_wall * 1e3, 1),
            "recovered_path_replica": int(rep1 - rep0),
            "mr_sent_bytes": int(mr_sent),
            "frame_bytes": frame_bytes,
            "partials_only": bool(mr_sent < frame_bytes / 4),
            "bit_identical": bit_identical and dead_identical,
        }
        tel = {k: v for k, v in telemetry.REGISTRY.summary().items()
               if k.startswith(("rpc_", "cluster_"))}
        result = {
            "metric": "rpc_roundtrip_p50_us",
            "value": rtt["p50_us"],
            "unit": "microseconds (2-node localhost cloud, echo RPC)",
            "vs_baseline": round(
                dkv["remote"]["get_p50_us"]
                / max(dkv["local"]["get_p50_us"], 1e-9), 2),
            "detail": {
                "host_cpus": os.cpu_count(),
                "platform": platform.platform(),
                "python": platform.python_version(),
                "rpc_roundtrip": rtt,
                "telemetry_overhead": trace_overhead,
                "rpc_throughput_by_bytes": thru,
                "dkv": dkv,
                "dist_frame": dist_frame,
                "dist_search": dist_search,
                "vs_baseline_is": "remote get p50 / local get p50",
            },
            "telemetry": {k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in tel.items()},
        }
        with open(os.path.join(_HERE, "CLUSTER_BENCH.json"), "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
    finally:
        try:
            child.stdin.close()
            child.wait(timeout=10)
        except Exception:
            child.kill()
        cloud.stop()
        set_local_cloud(None)


def _obs_bench() -> None:
    """Cost-ledger overhead + end-to-end attribution bench (--obs-bench).

    Two A/B cells, ledger charging ON vs OFF in alternating blocks (so
    scheduler/cache drift cancels out of the comparison):

    * **warm fused Rapids dispatch** — plan-cache + devcache hits, the
      hot serving path; the ledger's design puts zero charge events on
      it, and this cell is the proof
    * **traced RPC echo** on a 2-node in-process cloud — every call pays
      two real charge events (sent + received bytes), the worst per-call
      ledger tax in the system.  Like the --cluster-bench telemetry
      cell, the <5% p50 budget is operationalized at a 500us reference
      RTT (the loopback percentage is reported but pessimistic: a
      sub-100us RTT amplifies a ~2us fixed cost)

    The same two workloads are re-run as ``flight`` cells with the
    flight recorder ON vs OFF (ledger left on in both arms), proving the
    always-on ring stays inside the same <5% p50 budget.

    Then the in-run attribution assertion: a REST request (bench-local
    route) whose handler runs ``distributed_map_reduce`` must leave a
    ledger on its trace carrying BOTH client-side categories (RPC bytes)
    and remote-side categories (the peer's shard wall).  Writes
    OBS_BENCH.json and prints the same JSON; exits 1 when over budget or
    when attribution came back empty.
    """
    import urllib.request

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from h2o3_tpu.api import start_server
    from h2o3_tpu.cluster import frames as cframes
    from h2o3_tpu.cluster import tasks as ctasks
    from h2o3_tpu.cluster.membership import Cloud, set_local_cloud
    from h2o3_tpu.frame.frame import Column, ColType, Frame
    from h2o3_tpu.rapids.runtime import Session, exec_rapids
    from h2o3_tpu.util import flight as flight_mod
    from h2o3_tpu.util import ledger as ledger_mod
    from h2o3_tpu.util import telemetry

    n_rows = int(os.environ.get("BENCH_OBS_ROWS", 200_000))
    reps = int(os.environ.get("BENCH_OBS_REPS", 40))

    def _pct(samples, q):
        s = sorted(samples)
        return s[min(len(s) - 1, int(q * len(s)))]

    def _ab(fn, n, warmup=3, toggle=None):
        """Alternating-block A/B: returns (on_samples, off_samples).
        ``toggle`` flips the subsystem under test (default: the cost
        ledger; the flight cells pass the recorder's switch)."""
        toggle = toggle or ledger_mod.set_enabled
        for _ in range(warmup):
            fn()
        on, off = [], []
        block = max(1, n // 4)
        for _ in range(4):
            for enabled, sink in ((True, on), (False, off)):
                toggle(enabled)
                for _ in range(block):
                    t = time.perf_counter()
                    fn()
                    sink.append(time.perf_counter() - t)
        toggle(True)
        return on, off

    # -- cell 1: warm fused Rapids dispatch --------------------------------
    rng = np.random.default_rng(7)
    session = Session()
    session.assign("ob", Frame([
        Column("x", rng.standard_normal(n_rows), ColType.NUM),
        Column("y", rng.standard_normal(n_rows), ColType.NUM),
    ]))
    expr = ("(sum (* (sqrt (abs (+ (cols_py ob 0) (cols_py ob 1)))) "
            "(+ (* (floor (cols_py ob 1)) 0.25) (% (cols_py ob 0) 3))))")
    os.environ["H2O3_TPU_RAPIDS_FUSION"] = "1"
    rap_on, rap_off = _ab(lambda: exec_rapids(expr, session), reps)
    rap_on_ms = _pct(rap_on, 0.5) * 1e3
    rap_off_ms = _pct(rap_off, 0.5) * 1e3
    rap_pct = (rap_on_ms - rap_off_ms) / max(rap_off_ms, 1e-9) * 100
    rapids_cell = {
        "ledger_off_p50_ms": round(rap_off_ms, 3),
        "ledger_on_p50_ms": round(rap_on_ms, 3),
        "overhead_pct_p50": round(rap_pct, 2),
        "budget": {"pct_p50": 5.0},
        "within_budget": rap_pct <= 5.0,
    }

    # -- flight cell 1: same warm dispatch, recorder ON vs OFF ------------
    # the hot serving path has no flight choke points (only evictions and
    # shed record), so this cell proves the always-on default costs nothing
    # where latency matters most
    frap_on, frap_off = _ab(lambda: exec_rapids(expr, session), reps,
                            toggle=flight_mod.set_enabled)
    frap_on_ms = _pct(frap_on, 0.5) * 1e3
    frap_off_ms = _pct(frap_off, 0.5) * 1e3
    frap_pct = (frap_on_ms - frap_off_ms) / max(frap_off_ms, 1e-9) * 100
    flight_rapids_cell = {
        "flight_off_p50_ms": round(frap_off_ms, 3),
        "flight_on_p50_ms": round(frap_on_ms, 3),
        "overhead_pct_p50": round(frap_pct, 2),
        "budget": {"pct_p50": 5.0},
        "within_budget": frap_pct <= 5.0,
    }

    # -- cell 2 + attribution: 2-node cloud, REST front -------------------
    a = Cloud("obs-bench", "obs-n0", hb_interval=0.2)
    b = Cloud("obs-bench", "obs-n1", hb_interval=0.2)
    srv = None
    try:
        a.start([])
        b.start([a.info.addr])
        t0 = time.time()
        while time.time() - t0 < 30:
            if a.size() == 2 and a.consensus() and b.consensus():
                break
            time.sleep(0.05)
        else:
            raise RuntimeError("2-node obs-bench cloud never formed")
        ctasks.install(a)
        ctasks.install(b)
        peer = next(m for m in a.members_sorted()
                    if m.info.name == "obs-n1")

        def _echo():
            with telemetry.Span("obs_bench_echo"):
                a.client.call(peer.info.addr, "echo", b"x", timeout=5.0,
                              target=peer.info.ident)

        echo_on, echo_off = _ab(_echo, reps * 4)
        on_us = _pct(echo_on, 0.5) * 1e6
        off_us = _pct(echo_off, 0.5) * 1e6
        overhead_us = on_us - off_us
        ref_rtt_us, budget_us = 500.0, 500.0 * 0.05
        echo_cell = {
            "ledger_off_p50_us": round(off_us, 1),
            "ledger_on_p50_us": round(on_us, 1),
            "overhead_us_p50": round(overhead_us, 1),
            "overhead_pct_p50_loopback": round(
                overhead_us / max(off_us, 1e-9) * 100, 1),
            "budget": {
                "pct_p50": 5.0,
                "reference_rtt_us": ref_rtt_us,
                "overhead_budget_us": budget_us,
            },
            "within_budget": overhead_us <= budget_us,
        }

        # -- flight cell 2: traced echo, recorder ON vs OFF ---------------
        # every successful non-heartbeat call appends one structured event
        # to the ring — the recorder's worst per-call tax; same 500us
        # reference-RTT budget as the ledger cell
        fecho_on, fecho_off = _ab(_echo, reps * 4,
                                  toggle=flight_mod.set_enabled)
        f_on_us = _pct(fecho_on, 0.5) * 1e6
        f_off_us = _pct(fecho_off, 0.5) * 1e6
        f_overhead_us = f_on_us - f_off_us
        flight_echo_cell = {
            "flight_off_p50_us": round(f_off_us, 1),
            "flight_on_p50_us": round(f_on_us, 1),
            "overhead_us_p50": round(f_overhead_us, 1),
            "overhead_pct_p50_loopback": round(
                f_overhead_us / max(f_off_us, 1e-9) * 100, 1),
            "budget": {
                "pct_p50": 5.0,
                "reference_rtt_us": ref_rtt_us,
                "overhead_budget_us": budget_us,
            },
            "within_budget": f_overhead_us <= budget_us,
        }

        # REST -> distributed_map_reduce attribution, through the full
        # middleware (the REST span is the trace root the remote shard
        # execution must fold back into)
        set_local_cloud(a)
        srv = start_server(port=0)
        host = {"x": np.arange(50_000, dtype=np.float64),
                "y": (np.arange(50_000, dtype=np.float64) * 3) % 17}

        def bench_dmr(params):
            out = ctasks.distributed_map_reduce(
                cframes.mr_sum_xy, host, cloud=a)
            return {"leaves": [float(v) for v in jax.tree.leaves(out)]}

        srv.registry.register("GET", "/3/BenchDMR", bench_dmr,
                              "bench-only: REST-rooted distributed mr")
        with urllib.request.urlopen(srv.url + "/3/BenchDMR") as resp:
            assert resp.status == 200
            tid = resp.headers["X-H2O3-Trace-Id"]
        entry = ledger_mod.LEDGER.get(tid)
        assert entry is not None, "REST dmr trace has no ledger entry"
        total = entry["total"]
        client_ok = (total.get(ledger_mod.RPC_SENT_BYTES, 0) > 0
                     and total.get(ledger_mod.RPC_RECV_BYTES, 0) > 0)
        remote = entry["nodes"].get("obs-n1", {})
        remote_ok = remote.get(ledger_mod.SHARD_WALL_SECONDS, 0) > 0
        attribution = {
            "trace_id": tid,
            "client_categories_nonempty": client_ok,
            "remote_categories_nonempty": remote_ok,
            "nodes": sorted(entry["nodes"]),
            "total": {k: round(v, 6) for k, v in sorted(total.items())},
        }
    finally:
        if srv is not None:
            srv.stop()
        set_local_cloud(None)
        a.stop()
        b.stop()

    ok = (rapids_cell["within_budget"] and echo_cell["within_budget"]
          and flight_rapids_cell["within_budget"]
          and flight_echo_cell["within_budget"]
          and client_ok and remote_ok)
    result = {
        "metric": "ledger_overhead_pct_p50_warm_rapids",
        "value": rapids_cell["overhead_pct_p50"],
        "unit": "% (ledger on vs off, warm fused Rapids dispatch p50)",
        "detail": {
            "n_rows": n_rows,
            "rapids_warm_dispatch": rapids_cell,
            "rpc_echo_traced": echo_cell,
            "flight": {
                "rapids_warm_dispatch": flight_rapids_cell,
                "rpc_echo_traced": flight_echo_cell,
            },
            "rest_dmr_attribution": attribution,
        },
    }
    with open(os.path.join(_HERE, "OBS_BENCH.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    if not ok:
        sys.exit(1)


def _chaos_bench() -> None:
    """Chaos recovery microbench (the failure model's price tags).

    Boots a 3-node localhost cloud (this process + two nodeproc
    children), replicates keys across it, then SIGKILLs one child and
    measures what recovery actually costs: how long until the first
    replica-served read of a key the victim homed (the read-repair
    path), what fraction of replicated keys stay readable through the
    death, distributed map_reduce wall clock with a rescheduled range
    vs healthy, and the time for membership to reconverge on the
    survivors.  Prints ONE JSON line and mirrors it to
    CHAOS_BENCH.json.  CPU-only: the fan-out payloads are tiny."""
    import platform
    import signal as _signal
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from h2o3_tpu.cluster import tasks as ctasks
    from h2o3_tpu.cluster.membership import boot_node, set_local_cloud
    from h2o3_tpu.keyed import KeyedStore
    from h2o3_tpu.util import telemetry

    import numpy as np

    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    with open(os.path.join(tmp, "chaos_bench_mrfns.py"), "w") as f:
        f.write(
            "import jax.numpy as jnp\n"
            "def stat(cols, mask):\n"
            "    return {'s': jnp.sum(jnp.where(mask, cols['x'], 0.0)),\n"
            "            'n': jnp.sum(mask.astype(jnp.float32))}\n")
    sys.path.insert(0, tmp)
    import chaos_bench_mrfns as mrfns

    store = KeyedStore()
    cloud = boot_node("chaos-bench", "cb-n0", hb_interval=0.1, store=store)
    router = store.router
    flat = os.path.join(tmp, "flatfile")
    with open(flat, "w") as f:
        f.write(f"{cloud.info.host}:{cloud.info.port}\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = tmp + os.pathsep + _HERE + os.pathsep + \
        env.get("PYTHONPATH", "")
    children = {}
    for name in ("cb-n1", "cb-n2"):
        children[name] = subprocess.Popen(
            [sys.executable, "-m", "h2o3_tpu.cluster.nodeproc",
             "--cluster-name", "chaos-bench", "--node-name", name,
             "--flatfile", flat, "--hb-interval", "0.1"],
            stdin=subprocess.PIPE, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT, cwd=tmp, env=env)
    try:
        t_form = time.perf_counter()
        t0 = time.time()
        while time.time() - t0 < 60:
            if cloud.size() == 3 and cloud.consensus():
                break
            time.sleep(0.02)
        else:
            raise RuntimeError("3-node chaos-bench cloud never formed")
        formation_s = time.perf_counter() - t_form

        victim = "cb-n2"
        keys = {f"chaos-bench/k{i}": [i, i * 2] for i in range(32)}
        for k, v in sorted(keys.items()):
            store.put(k, v, replicas=3)
        victim_keys = [k for k in sorted(keys)
                       if router.home_name(k) == victim]

        cols = {"x": (np.arange(30011) % 97).astype(np.float32)}
        baseline = ctasks.distributed_map_reduce(
            mrfns.stat, cols, cloud=None)
        healthy = []
        for _ in range(3):
            t = time.perf_counter()
            out = ctasks.distributed_map_reduce(mrfns.stat, cols,
                                                cloud=cloud)
            healthy.append(time.perf_counter() - t)
        assert float(out["s"]) == float(baseline["s"])
        healthy_s = sorted(healthy)[1]  # median of 3

        # -- nemesis: SIGKILL one child, then price the recovery paths
        children[victim].send_signal(_signal.SIGKILL)
        children[victim].wait(timeout=10)
        t_kill = time.perf_counter()

        first_read_us = None
        readable = 0
        for k in victim_keys + [k for k in sorted(keys)
                                if k not in victim_keys]:
            t = time.perf_counter()
            ok = store.get(k) == keys[k]
            dt = time.perf_counter() - t
            readable += bool(ok)
            if first_read_us is None and k in victim_keys:
                first_read_us = round(dt * 1e6, 1)

        t = time.perf_counter()
        recovered = ctasks.distributed_map_reduce(mrfns.stat, cols,
                                                  cloud=cloud)
        recovered_s = time.perf_counter() - t
        bit_identical = (float(recovered["s"]) == float(baseline["s"])
                         and float(recovered["n"]) == float(baseline["n"]))

        while time.time() - t0 < 120:
            if cloud.size() == 2:
                break
            time.sleep(0.02)
        reconverge_s = time.perf_counter() - t_kill

        tel = {k: v for k, v in telemetry.REGISTRY.summary().items()
               if k.startswith(("cluster_fanout", "cluster_dkv",
                                "cluster_removals", "rpc_retries"))}
        result = {
            "metric": "chaos_reconverge_seconds",
            "value": round(reconverge_s, 3),
            "unit": ("seconds from SIGKILL to survivor membership "
                     "(3->2 nodes, hb 0.1s)"),
            "vs_baseline": round(recovered_s / max(healthy_s, 1e-9), 2),
            "detail": {
                "host_cpus": os.cpu_count(),
                "platform": platform.platform(),
                "formation_s": round(formation_s, 3),
                "mr_healthy_p50_s": round(healthy_s, 4),
                "mr_recovered_s": round(recovered_s, 4),
                "mr_recovered_bit_identical": bit_identical,
                "keys_replicated": len(keys),
                "keys_homed_on_victim": len(victim_keys),
                "keys_readable_after_kill": readable,
                "first_victim_key_read_us": first_read_us,
                "vs_baseline_is": "recovered map_reduce / healthy p50",
            },
            "telemetry": {k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in tel.items()},
        }
        with open(os.path.join(_HERE, "CHAOS_BENCH.json"), "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
    finally:
        for child in children.values():
            try:
                child.stdin.close()
                child.wait(timeout=10)
            except Exception:
                child.kill()
        cloud.stop()
        set_local_cloud(None)


def _serve_bench_multinode(model, score_fr, smoke, *, client,
                           read_response):
    """The cluster-wide serving cell: three REAL node processes (REST +
    cluster plane each), the model imported on ONE of them and homed
    onto the DKV ring by the serving plane.  Measures

    * ``one_door_rps`` — every client through the single door that holds
      the model (the 1-node serving baseline);
    * ``three_door_rps`` — the same closed-loop load spread across ALL
      front doors: two of them forward over ``predict_remote`` to the
      model's ring home, where bundles coalesce (dispatches < forwarded
      requests, proven from the home's ``/3/Metrics``);
    * ``replica_spill_rps`` — a second topology whose ring home is
      spawned with ``H2O3_TPU_SERVE_BUDGET=0``: every forwarded request
      sheds 429 at the home and must SPILL to the ring replica.

    ``overload_clean`` (nothing outside 2xx/408/413/429 anywhere) and
    ``bit_identical`` (a forwarded/spilled prediction CSV byte-equal to
    the home-door's local one) are asserted IN-RUN — a violation raises
    and fails the bench."""
    import asyncio
    import shutil
    import socket
    import tempfile
    import urllib.parse
    import urllib.request

    from h2o3_tpu.cluster.dkv import HashRing
    from h2o3_tpu.frame.persist import save_frame
    from h2o3_tpu.models.persist import save_model

    mn_duration = 0.35 if smoke else 2.0
    one_door_clients = 6 if smoke else 24
    three_door_clients = 6 if smoke else 24
    overload_total = 0 if smoke else 384
    spill_clients = 4 if smoke else 16

    mkey, fkey = "sb_multi", "sb_score.hex"
    mpath = f"/3/Predictions/models/{mkey}/frames/{fkey}"
    tmp = tempfile.mkdtemp(prefix="serve-bench-mn-")
    frame_path = save_frame(score_fr, os.path.join(tmp, "score.h2f"))
    model_path = save_model(model, os.path.join(tmp, "model.bin"))

    def _ctl(base, method, path, data=None, retries=40):
        body = json.dumps(data).encode() if data is not None else None
        hdrs = {"Content-Type": "application/json"} if body else {}
        last = None
        for _ in range(retries):
            req = urllib.request.Request(
                base + path, data=body, headers=hdrs, method=method)
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return json.loads(r.read())
            except Exception as e:  # noqa: BLE001  (node still booting)
                last = e
                time.sleep(0.25)
        raise RuntimeError(f"{method} {path} on {base} failed: {last}")

    def _metric(base, name, **labels):
        fam = _ctl(base, "GET", "/3/Metrics")["metrics"].get(name)
        if not fam:
            return 0.0
        return sum(s["value"] for s in fam["series"]
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    def _hist(base, name):
        fam = _ctl(base, "GET", "/3/Metrics")["metrics"].get(name)
        if not fam:
            return 0.0, 0.0
        return (float(sum(s["count"] for s in fam["series"])),
                float(sum(s["sum"] for s in fam["series"])))

    def _csv(base, frame_id):
        url = (base + "/3/DownloadDataset?frame_id="
               + urllib.parse.quote(frame_id))
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.read()

    def _free_ports(n):
        socks = [socket.socket() for _ in range(n)]
        try:
            for s in socks:
                s.bind(("127.0.0.1", 0))
            return [s.getsockname()[1] for s in socks]
        finally:
            for s in socks:
                s.close()

    def _boot(tag, home_env=None):
        """Spawn + form a 3-node cloud; returns (procs, REST bases,
        home door index for ``mkey``).  Ports are parent-picked so ring
        idents — and therefore the model's home — are known up front."""
        rpc, rest = _free_ports(3), _free_ports(3)
        names = [f"sb{tag}{i}" for i in range(3)]
        idents = [f"{names[i]}@127.0.0.1:{rpc[i]}" for i in range(3)]
        home_i = idents.index(HashRing(idents).homes(mkey, 1)[0])
        procs = []
        for i in range(3):
            ff = os.path.join(tmp, f"flatfile_{tag}{i}")
            with open(ff, "w") as f:
                f.write("".join(f"127.0.0.1:{p}\n"
                                for j, p in enumerate(rpc) if j != i))
            env = dict(os.environ)
            env.pop("BENCH_SERVE_SMOKE", None)
            env.update(JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1",
                       H2O3_TPU_HB_INTERVAL="0.1",
                       H2O3_TPU_SERVE_REPLICAS="1",
                       H2O3_TPU_BATCH_WINDOW_MS="6.0")
            if home_env and i == home_i:
                env.update(home_env)
            log = open(os.path.join(tmp, f"{names[i]}.log"), "wb")
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "h2o3_tpu",
                 "--name", names[i], "--port", str(rest[i]),
                 "--cluster-name", f"sbench{tag}",
                 "--node-name", names[i],
                 "--cluster-port", str(rpc[i]), "--flatfile", ff],
                stdout=log, stderr=log, env=env, cwd=_HERE))
        bases = [f"http://127.0.0.1:{p}" for p in rest]
        deadline = time.time() + 90
        sizes = []
        while time.time() < deadline:
            sizes = [len(_ctl(b, "GET", "/3/Cloud").get("nodes", []))
                     for b in bases]
            if sizes == [3, 3, 3]:
                return procs, bases, home_i
            time.sleep(0.2)
        raise RuntimeError(f"multinode cloud never formed: {sizes}")

    def _seed(bases, import_door):
        for b in bases:
            _ctl(b, "POST", "/3/Frames/load",
                 {"dir": frame_path, "frame_id": fkey})
        _ctl(bases[import_door], "POST", "/99/Models.bin",
             {"dir": model_path, "model_id": mkey})

    def _halt(procs):
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()

    def _mn_req(door, i):
        body = json.dumps(
            {"predictions_frame": f"sb_pred_{door}_{i % 8}"}).encode()
        return (f"POST {mpath} HTTP/1.1\r\nHost: localhost\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode() + body

    async def _mn_cell(doors, n_clients):
        """doors: list of (host, port, door index); clients round-robin
        across them, closed-loop for ``mn_duration``."""
        for host, port, d in doors:
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(_mn_req(d, 0))
            await writer.drain()
            st, _ = await read_response(reader)
            writer.close()
            if st != 200:
                raise RuntimeError(
                    f"multinode cold request on door {d} answered {st}")
        lat, statuses, errors = [], {}, [0]
        stop_t = time.perf_counter() + mn_duration + 0.25
        await asyncio.gather(*(
            client(doors[i % len(doors)][0], doors[i % len(doors)][1],
                   _mn_req(doors[i % len(doors)][2], i), stop_t, lat,
                   statuses, errors, stagger=0.25 * i / n_clients)
            for i in range(n_clients)))
        lat.sort()
        n_ok = len(lat)
        return {
            "p50_ms": round(lat[n_ok // 2] * 1e3, 3) if n_ok else None,
            "rps": round(n_ok / mn_duration, 1),
            "statuses": {str(k): v for k, v in sorted(statuses.items())},
            "conn_errors": errors[0],
        }

    def _doors(bases, idx):
        out = []
        for i in idx:
            host, port = bases[i][len("http://"):].split(":")
            out.append((host, int(port), i))
        return out

    cells = {}
    all_statuses = []
    try:
        # -- topology A: normal budgets.  Import the model on a door
        # that is NOT the ring home: the importing door scores its own
        # copy locally (the 1-node baseline), while the OTHER doors miss
        # in DKV (the model object is node-local and the ring home holds
        # only the serving blob) and must forward through the ring -----
        procs, bases, home_i = _boot("a")
        imp = (home_i + 1) % 3
        third = 3 - home_i - imp
        try:
            _seed(bases, import_door=imp)
            cells["one_door"] = asyncio.run(
                _mn_cell(_doors(bases, [imp]), one_door_clients))
            fwd0 = sum(_metric(b, "serve_forward_total") for b in bases)
            disp0, req0 = _hist(bases[home_i], "predict_batch_size")
            cells["three_door"] = asyncio.run(
                _mn_cell(_doors(bases, [0, 1, 2]), three_door_clients))
            forwarded = sum(_metric(b, "serve_forward_total")
                            for b in bases) - fwd0
            disp1, req1 = _hist(bases[home_i], "predict_batch_size")
            dispatches, coalesced = disp1 - disp0, req1 - req0
            if overload_total:
                cells["three_door_overload"] = asyncio.run(
                    _mn_cell(_doors(bases, [0, 1, 2]), overload_total))
            # bit-identity: a forwarded door's prediction CSV byte-equal
            # to the model-holding door's locally scored one
            ref_csv = _csv(bases[imp], f"sb_pred_{imp}_0")
            fwd_csv = _csv(bases[third], f"sb_pred_{third}_0")
        finally:
            _halt(procs)

        # -- topology B: the ring home sheds EVERYTHING; forwarded load
        # must spill to the ring replica.  Import on a non-home door
        # again and aim the client load at the THIRD door, which holds
        # nothing locally — every request must forward, shed, spill ----
        procs, bases, home_i = _boot(
            "b", home_env={"H2O3_TPU_SERVE_BUDGET": "0"})
        imp = (home_i + 1) % 3
        front = 3 - home_i - imp
        try:
            _seed(bases, import_door=imp)
            spill0 = sum(_metric(b, "serve_replica_spill_total")
                         for b in bases)
            cells["replica_spill"] = asyncio.run(
                _mn_cell(_doors(bases, [front]), spill_clients))
            spilled = sum(_metric(b, "serve_replica_spill_total")
                          for b in bases) - spill0
            spill_csv = _csv(bases[front], f"sb_pred_{front}_0")
        finally:
            _halt(procs)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    for c in cells.values():
        all_statuses.extend(c["statuses"])
    overload_clean = not [
        s for s in all_statuses
        if not (200 <= int(s) < 300 or int(s) in (408, 413, 429))]
    bit_identical = bool(ref_csv) and ref_csv == fwd_csv == spill_csv
    out = {
        "nodes": 3,
        "one_door_rps": cells["one_door"]["rps"],
        "three_door_rps": cells["three_door"]["rps"],
        "three_vs_one": round(
            cells["three_door"]["rps"] / cells["one_door"]["rps"], 2)
        if cells["one_door"]["rps"] else 0.0,
        "replica_spill_rps": cells["replica_spill"]["rps"],
        "forwarded_requests": forwarded,
        "home_dispatches": dispatches,
        "home_coalesced_requests": coalesced,
        "replica_spilled": spilled,
        "overload_clean": overload_clean,
        "bit_identical": bit_identical,
        "cells": cells,
    }
    # the in-run contract: violations FAIL the bench, they don't just
    # dent a number in the JSON
    if not overload_clean:
        raise RuntimeError(f"multinode serving answered outside "
                           f"2xx/408/413/429: {out}")
    if not bit_identical:
        raise RuntimeError("forwarded/spilled predictions are not "
                           "byte-identical to home-door scoring")
    if not (forwarded > 0 and spilled > 0):
        raise RuntimeError(f"serving ring never exercised: {out}")
    if not dispatches < coalesced:
        raise RuntimeError(
            f"forwarded requests did not coalesce at the home: "
            f"{dispatches} dispatches for {coalesced} requests")
    return out


def _serve_bench():
    """Serving-plane microbench (the async front-end's price tags).

    Trains one GBM in-process, parks a scoring frame in DKV, then runs
    closed-loop keep-alive HTTP clients (asyncio, one loop — 4096 real
    client threads would measure the client, not the server) against three
    transports: the thread-per-connection baseline (server_threaded.py),
    the event loop with coalescing off, and the event loop with the
    scoring coalescer on.  Per cell: first-request (cold) latency, warm
    p50/p99, RPS, status mix.  The headline is warm scoring RPS of the
    coalescing event loop vs the threaded baseline at the reference
    client count; the overload cell (4096 clients) must answer with
    nothing outside 2xx/408/413/429.  Prints ONE JSON line and mirrors
    it to SERVE_BENCH.json.  CPU-only: scoring programs are tiny.
    BENCH_SERVE_SMOKE=1 shrinks everything for the tier-1 test."""
    import asyncio
    import platform
    import threading  # noqa: F401  (server machinery: imported for clarity)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from h2o3_tpu import Frame
    from h2o3_tpu.api.server import H2OServer
    from h2o3_tpu.api.server_threaded import ThreadedH2OServer
    from h2o3_tpu.keyed import DKV
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.util import telemetry

    smoke = bool(os.environ.get("BENCH_SERVE_SMOKE"))
    n_train = 1500 if smoke else 20000
    n_score = 256 if smoke else 2048
    ntrees = 3 if smoke else 8
    duration = 0.4 if smoke else 3.0
    client_counts = [4] if smoke else [16, 256, 4096]
    ref_clients = 4 if smoke else 256
    overload_clients = 4 if smoke else 4096
    # thread-per-connection cannot field the overload cell: 4096 clients
    # would need 4096 server threads on this host
    threaded_max_clients = 256
    pred_keyspace = 64  # predictions_frame targets cycle: DKV stays bounded

    Xtr, ytr = synth_higgs(n_train, seed=1)
    names = [f"x{i}" for i in range(Xtr.shape[1])]
    train_fr = Frame.from_dict(
        {n: Xtr[:, i] for i, n in enumerate(names)} | {"y": ytr})
    model = GBM(response_column="y", ntrees=ntrees, max_depth=4,
                seed=7).train(train_fr)
    Xs, _ = synth_higgs(n_score, seed=2)
    score_fr = Frame.from_dict({n: Xs[:, i] for i, n in enumerate(names)})
    score_fr.key = "serve_bench.hex"
    DKV.put(score_fr.key, score_fr)
    path = f"/3/Predictions/models/{model.key}/frames/{score_fr.key}"

    def _request_bytes(i):
        body = json.dumps(
            {"predictions_frame": f"serve_bench_pred_{i % pred_keyspace}"}
        ).encode()
        return (f"POST {path} HTTP/1.1\r\nHost: localhost\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n"
                ).encode() + body

    async def _read_response(reader):
        line = await reader.readline()
        if not line:
            raise ConnectionError("server closed")
        parts = line.split()
        status = int(parts[1])
        # the threaded baseline answers HTTP/1.0: close-per-response
        # unless it says keep-alive (reconnect cost is part of its price)
        length, keep = 0, parts[0] != b"HTTP/1.0"
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin-1").partition(":")
            k = k.strip().lower()
            if k == "content-length":
                length = int(v)
            elif k == "connection" and "close" in v.lower():
                keep = False
        if length:
            await reader.readexactly(length)
        return status, keep

    async def _client(host, port, req, stop_t, lat, statuses, errors,
                      stagger):
        await asyncio.sleep(stagger)
        reader = writer = None
        try:
            while time.perf_counter() < stop_t:
                if writer is None:
                    try:
                        reader, writer = await asyncio.open_connection(
                            host, port)
                    except OSError:
                        errors[0] += 1
                        await asyncio.sleep(0.01)
                        continue
                t0 = time.perf_counter()
                try:
                    writer.write(req)
                    await writer.drain()
                    status, keep = await _read_response(reader)
                except (OSError, ConnectionError,
                        asyncio.IncompleteReadError):
                    errors[0] += 1
                    writer.close()
                    writer = None
                    continue
                lat.append(time.perf_counter() - t0)
                statuses[status] = statuses.get(status, 0) + 1
                if status < 200 or status >= 300:
                    lat.pop()  # RPS/latency count successes only
                if not keep:
                    writer.close()
                    writer = None
                if status == 429:
                    await asyncio.sleep(0.005)  # shed: back off, retry
        finally:
            if writer is not None:
                writer.close()

    async def _run_cell(host, port, n_clients):
        # cold: the first request a fresh transport serves (process-wide
        # jit caches persist across cells, so only the first cell pays
        # the compile — recorded as-is, the matrix shows it)
        t0 = time.perf_counter()
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(_request_bytes(0))
        await writer.drain()
        st, _ = await _read_response(reader)
        cold_ms = (time.perf_counter() - t0) * 1e3
        writer.close()
        if st != 200:
            raise RuntimeError(f"cold request answered {st}")
        lat, statuses, errors = [], {}, [0]
        stop_t = time.perf_counter() + duration + 0.25
        await asyncio.gather(*(
            _client(host, port, _request_bytes(i), stop_t, lat, statuses,
                    errors, stagger=0.25 * i / n_clients)
            for i in range(n_clients)))
        lat.sort()
        n_ok = len(lat)
        return {
            "cold_ms": round(cold_ms, 2),
            "p50_ms": round(lat[n_ok // 2] * 1e3, 3) if n_ok else None,
            "p99_ms": round(lat[min(n_ok - 1, int(n_ok * 0.99))] * 1e3,
                            3) if n_ok else None,
            "rps": round(n_ok / duration, 1),
            "statuses": {str(k): v for k, v in sorted(statuses.items())},
            "conn_errors": errors[0],
        }

    servers = [
        ("threaded", lambda: ThreadedH2OServer(port=0)),
        ("event_loop", lambda: H2OServer(
            port=0, http=dict(batch_window_ms=0))),
        ("event_loop_coalesce", lambda: H2OServer(
            port=0, http=dict(batch_window_ms=4.0))),
    ]
    cells = []
    warm_rps = {}
    try:
        for sname, mk in servers:
            for n_clients in client_counts:
                if sname == "threaded" and n_clients > threaded_max_clients:
                    cells.append({"server": sname, "clients": n_clients,
                                  "skipped": "thread-per-connection "
                                             "cannot field this load"})
                    continue
                srv = mk().start()
                try:
                    cell = asyncio.run(
                        _run_cell("127.0.0.1", srv.port, n_clients))
                finally:
                    srv.stop()
                cell.update(server=sname, clients=n_clients)
                cells.append(cell)
                warm_rps[(sname, n_clients)] = cell["rps"]

        # bit-identity: what the coalesced path left in DKV == serial
        serial = model.predict(score_fr)
        got = DKV.get(f"serve_bench_pred_{0}")
        bit_identical = bool(got is not None and all(
            np.array_equal(np.asarray(a.data, dtype=np.float64),
                           np.asarray(b.data, dtype=np.float64))
            for a, b in zip(serial.columns, got.columns)))

        overload = next(
            (c for c in cells if c.get("server") == "event_loop_coalesce"
             and c.get("clients") == overload_clients), None)
        overload_clean = overload is not None and not [
            s for s in overload["statuses"]
            if not (200 <= int(s) < 300 or int(s) in (408, 413, 429))]

        multinode = _serve_bench_multinode(
            model, score_fr, smoke,
            client=_client, read_response=_read_response)
        base = warm_rps.get(("threaded", ref_clients), 0.0)
        coal = warm_rps.get(("event_loop_coalesce", ref_clients), 0.0)
        speedup = round(coal / base, 2) if base else 0.0
        tel = {k: v for k, v in telemetry.REGISTRY.summary().items()
               if k.startswith(("http_", "predict_batch_size"))}
        result = {
            "metric": "serve_warm_rps_speedup",
            "value": speedup,
            "unit": (f"x warm scoring RPS at {ref_clients} clients, "
                     "coalescing event loop vs thread-per-connection"),
            "vs_baseline": speedup,
            "detail": {
                "host_cpus": os.cpu_count(),
                "platform": platform.platform(),
                "model": f"GBM ntrees={ntrees} depth=4 on "
                         f"{n_train}x28 synth-higgs",
                "score_rows": n_score,
                "duration_s": duration,
                "matrix": cells,
                "bit_identical": bit_identical,
                "overload_clean": overload_clean,
                "multinode": multinode,
                "smoke": smoke,
            },
            "telemetry": {k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in tel.items()},
        }
        if not smoke:
            with open(os.path.join(_HERE, "SERVE_BENCH.json"), "w") as f:
                json.dump(result, f, indent=1)
        print(json.dumps(result))
        return result
    finally:
        DKV.remove(score_fr.key)
        for i in range(pred_keyspace):
            try:
                DKV.remove(f"serve_bench_pred_{i}")
            except Exception:
                pass
        try:
            DKV.remove(model.key)
        except Exception:
            pass


def _codec_bench() -> None:
    """CPU chunk-codec bench (codec-layer PR acceptance).

    Parses the mixed NUM/CAT/TIME/STR/NUM CSV (~BENCH_CODEC_MB, default
    96) onto a 2-node in-process cloud twice — codecs on (the default
    data plane) and ``H2O3_TPU_CODECS=0`` — and prices the layer:
    resident (ring wire) bytes/row and replica fan-out bytes encoded vs
    dense, the warm fused Rapids pipeline wall over encoded vs dense
    chunks, and the parse→fit working set (frame wire bytes + decoded
    devcache bytes by kind + peak RSS) for a distributed tree fit on the
    encoded frame.  Asserts IN-RUN that both parses materialize every
    column bit-identically (uint64 views) and that the encoded resident
    footprint is at most half the dense one.  Prints ONE JSON line and
    mirrors it to CODEC_BENCH.json (`--codec-bench`).
    """
    import resource

    import numpy as np

    from h2o3_tpu.cluster import dkv as cdkv
    from h2o3_tpu.cluster import tasks as ctasks
    from h2o3_tpu.cluster.membership import Cloud, set_local_cloud
    from h2o3_tpu.frame import codecs as _codecs  # noqa: F401  registers
    from h2o3_tpu.frame import devcache as _devcache  # the codec meters
    from h2o3_tpu.frame.frame import ColType
    from h2o3_tpu.frame.parse import _iter_body_chunks, parse_setup
    from h2o3_tpu.keyed import KeyedStore
    from h2o3_tpu.models.tree.gbm import GBM, GBMParameters
    from h2o3_tpu.rapids.runtime import Session, exec_rapids
    from h2o3_tpu.util import telemetry

    size_mb = float(os.environ.get("BENCH_CODEC_MB", 96))
    reps = int(os.environ.get("BENCH_CODEC_REPS", 3))
    chunk_bytes = int(os.environ.get("H2O3_TPU_PARSE_CHUNK_BYTES",
                                     2 << 20))

    def _meter(name, **labels):
        c = telemetry.REGISTRY.get(name)
        if c is None:
            return 0.0
        return sum(s["value"] for s in c.snapshot()["series"]
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    t0 = time.time()
    text = _parse_bench_csv(size_mb)
    raw_mb = len(text.encode()) / 1e6
    print(f"# generated {raw_mb:.1f} MB csv in {time.time() - t0:.1f}s",
          file=sys.stderr)

    clouds = []
    for i in range(2):
        c = Cloud("codecbench", f"cb{i}", hb_interval=0.1)
        cdkv.install(c, KeyedStore())
        ctasks.install(c)
        clouds.append(c)
    seeds = [c.info.addr for c in clouds]
    for c in clouds:
        c.start([a for a in seeds if a != c.info.addr])
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline and not all(
            c.size() == 2 for c in clouds):
        time.sleep(0.02)

    saved = os.environ.get("H2O3_TPU_CODECS")
    try:
        set_local_cloud(clouds[0])
        setup = parse_setup(text)
        chunks = list(_iter_body_chunks(
            [text.encode()], chunk_bytes, setup.header,
            setup.skip_blank_lines))

        def _parse(key, codecs_on):
            os.environ["H2O3_TPU_CODECS"] = "1" if codecs_on else "0"
            mix0 = {s["labels"]["codec"]: s["value"] for s in
                    telemetry.REGISTRY.get("chunk_codec_total")
                    .snapshot()["series"]} if codecs_on else {}
            r0 = _meter("cluster_chunk_replica_bytes")
            t = time.perf_counter()
            fr = ctasks.distributed_parse_chunks(
                chunks, setup, cloud=clouds[0], key=key)
            wall = time.perf_counter() - t
            mix = {}
            if codecs_on:
                for s in (telemetry.REGISTRY.get("chunk_codec_total")
                          .snapshot()["series"]):
                    codec = s["labels"]["codec"]
                    d = s["value"] - mix0.get(codec, 0.0)
                    if d:
                        mix[codec] = int(d)
            return fr, wall, _meter("cluster_chunk_replica_bytes") - r0, mix

        enc, enc_wall, enc_replica, codec_mix = _parse("codec_enc", True)
        dense, dense_wall, dense_replica, _ = _parse("codec_dense", False)
        os.environ["H2O3_TPU_CODECS"] = "1"
        nrows = enc.nrows

        # bit-identity: both chunk-homed parses must materialize every
        # column to the same bits (uint64 views for numeric, exact codes
        # + domains for CAT, element equality for STR)
        bit_identical = True
        for name in enc.names:
            a, b = enc.col(name), dense.col(name)
            if a.type != b.type or a.domain != b.domain:
                bit_identical = False
            elif a.data.dtype == object:
                bit_identical &= all(
                    x == y for x, y in zip(a.data, b.data))
            elif a.type in (ColType.NUM, ColType.TIME):
                bit_identical &= bool(np.array_equal(
                    a.numeric_view().view(np.uint64),
                    b.numeric_view().view(np.uint64)))
            else:
                bit_identical &= bool(np.array_equal(a.data, b.data))

        # warm fused pipeline over encoded vs dense chunks: drop the
        # materialized copies so the dist path (group reps + in-program
        # decode) is what actually runs
        session = Session()
        session.assign("ce", enc)
        session.assign("cd", dense)

        def _pipeline(v):
            out = exec_rapids(
                f"(sumNA (* (cols_py {v} 0) (cols_py {v} 4)))", session)
            return int(np.float64(out.value).view(np.uint64))

        def _warm(v, fr):
            fr._materialized = None
            sig = _pipeline(v)  # cold: compiles + uploads + caches
            best = None
            for _ in range(max(1, reps)):
                t = time.perf_counter()
                assert _pipeline(v) == sig
                dt = time.perf_counter() - t
                best = dt if best is None else min(best, dt)
            return best, sig

        warm_enc_s, sig_enc = _warm("ce", enc)
        warm_dense_s, sig_dense = _warm("cd", dense)
        pipeline_identical = sig_enc == sig_dense

        # parse→fit working set: a distributed tree fit straight off the
        # encoded chunks — what stays resident is the encoded ring copy
        # plus the byte-budgeted devcache entries, not a dense frame
        enc._materialized = None
        t = time.perf_counter()
        model = GBM(GBMParameters(
            nbins=16, response_column="count", ntrees=2, max_depth=3,
            min_rows=10.0, seed=11,
            ignored_columns=["str"])).train(enc)
        fit_wall = time.perf_counter() - t
        assert model is not None
        fit_cell = {
            "fit_wall_s": round(fit_wall, 3),
            "frame_wire_bytes": int(enc.nbytes_wire),
            "devcache_bytes_by_kind": {
                k: int(v) for k, v in sorted(
                    _devcache.DEVCACHE.kind_bytes().items())},
            "peak_rss_mb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
                1),
        }

        resident_ratio = enc.nbytes_wire / max(dense.nbytes_wire, 1)
        replica_ratio = enc_replica / max(dense_replica, 1.0)
        result = {
            "metric": "chunk_codec_resident_ratio",
            "unit": "x (encoded ring bytes / dense ring bytes, same frame)",
            "csv_mb": round(raw_mb, 1),
            "n_rows": nrows,
            "n_cols": len(enc.names),
            "resident": {
                "encoded_bytes_per_row": round(enc.nbytes_wire / nrows, 2),
                "dense_bytes_per_row": round(dense.nbytes_wire / nrows, 2),
                "ratio": round(resident_ratio, 4),
            },
            "replicas": {
                "encoded_replica_bytes": int(enc_replica),
                "dense_replica_bytes": int(dense_replica),
                "ratio": round(replica_ratio, 4),
            },
            "codec_mix": codec_mix,
            "parse_wall": {"encoded_s": round(enc_wall, 3),
                           "dense_s": round(dense_wall, 3)},
            "fused_pipeline": {
                "warm_encoded_s": round(warm_enc_s, 4),
                "warm_dense_s": round(warm_dense_s, 4),
                "bit_identical": pipeline_identical,
            },
            "fit_working_set": fit_cell,
            "bit_identical": bit_identical and pipeline_identical,
            "resident_ratio_within_half": resident_ratio <= 0.5,
        }
        with open(os.path.join(_HERE, "CODEC_BENCH.json"), "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
        if not (result["bit_identical"]
                and result["resident_ratio_within_half"]):
            sys.exit(1)
    finally:
        if saved is None:
            os.environ.pop("H2O3_TPU_CODECS", None)
        else:
            os.environ["H2O3_TPU_CODECS"] = saved
        set_local_cloud(None)
        for c in clouds:
            try:
                c.stop()
            except Exception:
                pass


if __name__ == "__main__":
    if "--cache-bench" in sys.argv:
        _cache_bench()
    elif "--parse-bench" in sys.argv:
        _parse_bench()
    elif "--cluster-bench" in sys.argv:
        _cluster_bench()
    elif "--chaos-bench" in sys.argv:
        _chaos_bench()
    elif "--serve-bench" in sys.argv:
        _serve_bench()
    elif "--rapids-bench" in sys.argv:
        _rapids_bench()
    elif "--hist-bench" in sys.argv:
        _hist_bench()
    elif "--obs-bench" in sys.argv:
        _obs_bench()
    elif "--codec-bench" in sys.argv:
        _codec_bench()
    else:
        main()
