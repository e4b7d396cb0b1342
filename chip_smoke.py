#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Default run (one chip): starts one node through the launcher
(``python -m h2o3_tpu --port 0``), the only process that touches the chip, and
drives it over REST as an H2O client would: ``/3/Cloud`` -> PostFile + Parse of
a Higgs-shaped CSV made from ``--seed`` -> ``ModelBuilders/gbm`` at the
flagship width (28 features, 256 bins, depth 6, 10 trees, bernoulli) ->
``Predictions`` on the full frame, twice. This parent never imports jax.

``--chips 4`` runs only the mesh phase, in this one process (one process can
drive four chips): the same fit through the GBM estimator on the default mesh
of all four devices, and through ``train_boosted`` on ``default_mesh(1)``,
compared.

What decides ``ok``: every request 2xx; the platform is a TPU; training AUC
within 0.02 of sklearn's HistGradientBoostingClassifier with the same settings
on the same data; ``/3/Metrics`` says the Pallas histogram plans were the ones
traced; no XLA compile across the second prediction. Every earlier line of
output is an observation (free-form JSON, one per step), not a metric. The
last line is ``{"ok": ..., "device": {"platform", "kind", "count"}}`` and the
exit code is 0 only when ``ok`` is true.

``--rehearse`` is for a machine without the chip: a platform that is not a
TPU is still recorded as a failed check (so ``ok`` stays false and the exit
code non-zero), but the remaining steps run, at ``--rows`` small enough for a
CPU. The rehearsal passed when ``"failed": ["platform"]`` is all that failed:

    JAX_PLATFORMS=cpu H2O3_TPU_HIST_IMPL=pallas \\
        python chip_smoke.py --rehearse --rows 4096
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(HERE, ".chip_smoke")  # git-ignored
NODE_LOG = os.path.join(SCRATCH, "node.log")
ROWS = 2_000_000
N_FEAT = 28
COLUMNS = [f"f{i}" for i in range(N_FEAT)] + ["y"]
GBM_PARAMS = dict(distribution="bernoulli", ntrees=10, max_depth=6, nbins=256,
                  learn_rate=0.1, min_rows=1)
#: the CSV goes up in parts: ~310 bytes a row, and one request body may hold
#: 256 MiB (api/server.py max_body_bytes)
UPLOAD_PARTS = 4
AUC_TOLERANCE = 0.02


def say(**obs) -> None:
    print(json.dumps(obs), flush=True)


class Checks:
    """The comparisons that decide ``ok``; each prints as it is made."""

    def __init__(self) -> None:
        self.failed: list = []

    def record(self, name: str, ok: bool, **detail) -> bool:
        say(check=name, ok=bool(ok), **detail)
        if not ok:
            self.failed.append(name)
        return bool(ok)


def require_tpu(checks: Checks, platform: str, rehearse: bool) -> None:
    if not checks.record("platform", platform == "tpu", platform=platform):
        if not rehearse:
            raise RuntimeError(f"platform is {platform!r}, not 'tpu'")


def training_table(rows: int, seed: int):
    """The benchmark's Higgs-shaped table (``benchmark/tables/higgs-synth.py``,
    loaded by path: its name has a hyphen): float32 [rows, 28] and a 0/1
    float64 response."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tables_higgs_synth",
        os.path.join(HERE, "benchmark", "tables", "higgs-synth.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    X, y = gen.make({"features": N_FEAT, "classes": 2}, rows, seed)
    return X, y.astype("float64")


def reference_auc(X, y, seed: int) -> float:
    """The plain reference: sklearn's histogram GBDT, same settings, same
    data (255 is the most bins it takes)."""
    from sklearn.ensemble import HistGradientBoostingClassifier
    from sklearn.metrics import roc_auc_score

    ref = HistGradientBoostingClassifier(
        max_iter=GBM_PARAMS["ntrees"], max_depth=GBM_PARAMS["max_depth"],
        learning_rate=GBM_PARAMS["learn_rate"], max_bins=255,
        min_samples_leaf=GBM_PARAMS["min_rows"], max_leaf_nodes=None,
        l2_regularization=0.0, early_stopping=False, random_state=seed,
    ).fit(X, y)
    return float(roc_auc_score(y, ref.predict_proba(X)[:, 1]))


# ---------------------------------------------------------------------------
# default run: one node process owns the chip, this parent speaks REST


@contextlib.contextmanager
def node(log_path: str):
    """Boot ``python -m h2o3_tpu --port 0``; yields (process, base url).
    The child is terminated and waited for on every exit path."""
    env = dict(os.environ, PYTHONUNBUFFERED="1",
               PYTHONPATH=HERE + os.pathsep + os.environ.get("PYTHONPATH", ""))
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "h2o3_tpu", "--port", "0",
             "--name", "chip-smoke"],
            cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT)
        try:
            yield proc, _wait_for_url(proc, log_path)
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()


def _wait_for_url(proc: subprocess.Popen, log_path: str) -> str:
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        with open(log_path, "r", errors="replace") as f:
            text = f.read()
        for line in text.splitlines():
            if "up at http" in line:
                return line.strip().rsplit(" ", 1)[-1]
        if proc.poll() is not None:
            raise RuntimeError(
                f"node exited with {proc.returncode} before it was up:\n"
                + text[-4000:])
        time.sleep(0.2)
    raise RuntimeError("node was not up within 300 s:\n" + text[-4000:])


def call(base: str, method: str, path: str, body=None, timeout: float = 1000):
    """One REST request; a non-2xx answer raises with the server's message."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        base + path, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise RuntimeError(
            f"{method} {path} answered {e.code}: "
            + e.read().decode(errors="replace")[-4000:]) from e


def metric(snapshot: dict, name: str, **labels) -> float:
    """Sum of a metric's series that carry ``labels`` (0 if never touched)."""
    series = snapshot["metrics"].get(name, {}).get("series", [])
    return sum(s["value"] for s in series
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _device_memory(base: str):
    """In-use, peak and limit bytes of the node's first device."""
    stats = call(base, "GET", "/3/Cloud")["nodes"][0]["device_memory"] or {}
    return {k: stats.get(k)
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def write_csv_parts(X, y, tmp: str) -> list:
    """The table as UPLOAD_PARTS CSV files, each with its header."""
    import numpy as np
    import pyarrow as pa
    from pyarrow import csv as pa_csv

    paths = []
    for i, idx in enumerate(np.array_split(np.arange(len(y)), UPLOAD_PARTS)):
        cols = [pa.array(X[idx, j]) for j in range(N_FEAT)]
        cols.append(pa.array(y[idx].astype(np.int8)))
        paths.append(os.path.join(tmp, f"higgs_{i}.csv"))
        pa_csv.write_csv(pa.table(cols, names=COLUMNS), paths[-1])
    return paths


def run_node(args, checks: Checks) -> dict:
    os.makedirs(SCRATCH, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=SCRATCH) as tmp, \
            node(NODE_LOG) as (proc, base):
        node_info = call(base, "GET", "/3/Cloud")["nodes"][0]
        device = {"platform": node_info["platform"],
                  "kind": node_info["device_kind"],
                  "count": node_info["device_count"]}
        say(step="cloud", devices=node_info["devices"],
            native_available=node_info["native"],
            compile_cache_dir=node_info["compile_cache_dir"], **device)
        require_tpu(checks, device["platform"], args.rehearse)
        checks.record("one_device", device["count"] == 1,
                      count=device["count"])

        t0 = time.monotonic()
        X, y = training_table(args.rows, args.seed)
        paths = write_csv_parts(X, y, tmp)
        say(step="generate", rows=args.rows, features=N_FEAT,
            csv_bytes=sum(os.path.getsize(p) for p in paths),
            wall_s=time.monotonic() - t0)

        t0 = time.monotonic()
        keys = []
        for p in paths:
            with open(p, "r") as f:
                up = call(base, "POST", "/3/PostFile", {"data": f.read()})
            keys.append(up["destination_frame"])
        t_up = time.monotonic() - t0
        parsed = call(base, "POST", "/3/Parse", {
            "source_frames": keys, "destination_frame": "higgs.hex",
            "check_header": 1, "column_names": COLUMNS,
            "column_types": ["numeric"] * N_FEAT + ["enum"]})
        frame = call(base, "GET", "/3/Frames/higgs.hex")["frames"][0]
        say(step="parse", upload_wall_s=t_up,
            parse_wall_s=time.monotonic() - t0 - t_up,
            rows=frame["rows"], columns=frame["num_columns"])
        checks.record(
            "parsed_frame",
            parsed["job"]["status"] == "DONE" and frame["rows"] == args.rows
            and frame["num_columns"] == N_FEAT + 1
            and frame["columns"][-1]["domain"] == ["0", "1"],
            job=parsed["job"]["status"])

        t0 = time.monotonic()
        built = call(base, "POST", "/3/ModelBuilders/gbm", dict(
            GBM_PARAMS, training_frame="higgs.hex", response_column="y",
            seed=args.seed, model_id="smoke_gbm"))
        job = built["job"]
        while job["status"] not in ("DONE", "FAILED", "CANCELLED"):
            time.sleep(0.5)
            job = call(base, "GET",
                       "/3/Jobs/" + job["key"]["name"])["jobs"][0]
        fit_wall = time.monotonic() - t0
        m_fit = call(base, "GET", "/3/Metrics")
        say(step="fit", first_fit_wall_s_including_compile=fit_wall,
            xla_compiles=metric(m_fit, "jit_compiles_total"),
            xla_compile_s=metric(m_fit, "jit_compile_seconds_total"),
            device_memory=_device_memory(base))
        checks.record("fit_job", job["status"] == "DONE",
                      status=job["status"], exception=job["exception"])
        model = call(base, "GET", "/3/Models/smoke_gbm")["models"][0]
        auc = model["output"]["training_metrics"]["auc"]

        plans = {impl: metric(m_fit, "hist_plan_cache_total", impl=impl)
                 for impl in ("pallas", "scatter")}
        # the block program is traced once: each level asks for a Pallas
        # histogram plan; with subtraction on (the Pallas default) no
        # scatter node-totals plan is asked for at all
        checks.record("pallas_plans_traced", plans["pallas"] > 0, **plans)

        walls, compiles = [], [metric(m_fit, "jit_compiles_total")]
        for i in range(2):
            t0 = time.monotonic()
            scored = call(
                base, "POST", "/3/Predictions/models/smoke_gbm/frames/higgs.hex",
                {"predictions_frame": f"smoke_pred_{i}"})["model_metrics"][0]
            walls.append(time.monotonic() - t0)
            compiles.append(metric(call(base, "GET", "/3/Metrics"),
                                   "jit_compiles_total"))
        say(step="predict", cold_wall_s=walls[0], warm_wall_s=walls[1],
            xla_compiles_after=compiles, device_memory=_device_memory(base))
        checks.record("warm_predict_compiles_nothing",
                      compiles[2] == compiles[1], compiles=compiles)
        pred = call(base, "GET", "/3/Frames/smoke_pred_1/summary")["frames"][0]
        p1 = pred["columns"][-1]
        checks.record(
            "prediction_frame",
            pred["rows"] == args.rows and p1["missing_count"] == 0
            and 0.0 <= p1["mins"][0] <= p1["mean"] <= p1["maxs"][0] <= 1.0
            and abs(scored["auc"] - auc) < 1e-9,
            rows=pred["rows"], columns=pred["column_names"],
            p1_mean=p1["mean"], scored_auc=scored["auc"])

        t0 = time.monotonic()
        ref = reference_auc(X, y, args.seed)
        say(step="reference", wall_s=time.monotonic() - t0,
            what="sklearn HistGradientBoostingClassifier, same settings")
        checks.record("auc_vs_reference", abs(auc - ref) <= AUC_TOLERANCE,
                      training_auc=auc, reference_auc=ref,
                      tolerance=AUC_TOLERANCE)
        checks.record("node_alive", proc.poll() is None)
    checks.record("parent_is_jax_free", "jax" not in sys.modules)
    return device


# ---------------------------------------------------------------------------
# --chips 4: the mesh phase, one process driving all four chips


def run_mesh(args, checks: Checks) -> dict:
    import numpy as np

    from h2o3_tpu.util import compile_cache, telemetry

    say(step="compile_cache", dir=compile_cache.configure())
    telemetry.install_jax_compile_listener()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from sklearn.metrics import roc_auc_score

    from h2o3_tpu.frame.frame import ColType, Column, Frame
    from h2o3_tpu.models.tree import booster
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.ops.pallas_histogram import _FEAT_BLOCK, _ROW_TILE
    from h2o3_tpu.parallel.mesh import DATA_AXIS, default_mesh

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(step="devices", devices=[str(d) for d in devs], **device)
    require_tpu(checks, device["platform"], args.rehearse)
    if not checks.record("four_devices", len(devs) == 4, count=len(devs)):
        raise RuntimeError(f"--chips 4 needs four devices, found {len(devs)}")

    X, y = training_table(args.rows, args.seed)
    frame = Frame(
        [Column(f"f{i}", X[:, i].astype(np.float64)) for i in range(N_FEAT)]
        + [Column("y", y.astype(np.int32), ColType.CAT, ["0", "1"])])

    # four chips: the estimator, on the default mesh of every device
    mesh4 = default_mesh()
    t0 = time.monotonic()
    model = GBM(response_column="y", seed=args.seed, **GBM_PARAMS).train(frame)
    say(step="fit_4", wall_s_including_compile=time.monotonic() - t0,
        xla_compiles=telemetry.jit_compile_count())
    b4 = model.booster
    n_pad = args.rows + (-args.rows) % (4 * _ROW_TILE)
    f_pad = N_FEAT + (-N_FEAT) % _FEAT_BLOCK
    codes = {
        str(a.shape): {
            "devices": len(a.sharding.device_set),
            "shard_shapes": sorted({str(s.data.shape)
                                    for s in a.addressable_shards})}
        for a in jax.live_arrays()
        if a.dtype == jnp.int32
        and a.shape in ((n_pad, N_FEAT), (f_pad, n_pad))}
    checks.record(
        "binned_codes_span_four_devices",
        len(codes) == 2 and all(
            c["devices"] == 4 and len(c["shard_shapes"]) == 1
            for c in codes.values()),
        resident=codes)

    # the block program the fit ran, compiled again from shapes for its text
    row = NamedSharding(mesh4, P(DATA_AXIS))
    row2 = NamedSharding(mesh4, P(DATA_AXIS, None))
    S = jax.ShapeDtypeStruct
    ntrees = GBM_PARAMS["ntrees"]
    block = booster._make_block_fn(
        "bernoulli", 1, ntrees,
        dataclasses.replace(b4.params, ntrees=0, seed=0), mesh4,
        weighted=False, monotone=False,
        subtract=booster._tree_subtract_enabled())
    text = block.lower(
        S((n_pad, N_FEAT), jnp.int32, sharding=row2),
        S((n_pad,), jnp.float32, sharding=row),
        S((n_pad,), jnp.bool_, sharding=row),
        S((n_pad, 1), jnp.float32, sharding=row2),
        S((ntrees, 2), jnp.uint32, sharding=NamedSharding(mesh4, P())),
        S((f_pad, n_pad), jnp.int32,
          sharding=NamedSharding(mesh4, P(None, DATA_AXIS))),
        None, None).compile().as_text()
    checks.record("block_program_all_reduces", "all-reduce" in text,
                  tpu_custom_call="tpu_custom_call" in text)

    # one chip: the estimator takes no mesh, train_boosted does — same
    # matrix, response, init margin and TreeParams as the fit above
    t0 = time.monotonic()
    b1 = booster.train_boosted(
        X, "bernoulli", y, 1, b4.init_margin, b4.params,
        mesh=default_mesh(n_devices=1))
    say(step="fit_1", wall_s_including_compile=time.monotonic() - t0,
        xla_compiles=telemetry.jit_compile_count())

    m4 = b4.predict_margin(X)[:, 0]
    m1 = b1.predict_margin(X)[:, 0]
    auc4, auc1 = float(roc_auc_score(y, m4)), float(roc_auc_score(y, m1))
    checks.record("auc_4_vs_1", abs(auc4 - auc1) <= 1e-3, auc_4=auc4,
                  auc_1=auc1, estimator_training_auc=model.training_metrics.auc)
    # Tolerance: both meshes round g/h to bf16 row by row, identically, and
    # feed the MXU the same 512-row tiles (shard boundaries fall on tile
    # boundaries), so histograms differ only in the order f32 tile partials
    # are added: ~1e-6 relative. A leaf is -G/H*0.1 and a margin ten leaves,
    # so 1e-3 in log-odds is a thousand times that (PR 24's run: 0.0).
    diff = np.abs(m4 - m1)
    t4, t1 = b4.trees_per_class[0], b1.trees_per_class[0]
    split_nodes_differ = int(np.sum(
        (np.stack(t4.is_split) != np.stack(t1.is_split))
        | (np.stack(t4.is_split)
           & ((np.stack(t4.feat) != np.stack(t1.feat))
              | (np.stack(t4.split_bin) != np.stack(t1.split_bin))))))
    checks.record("margins_4_vs_1",
                  bool(np.allclose(m4, m1, rtol=0.0, atol=1e-3)),
                  atol=1e-3, max_abs_diff=float(diff.max()),
                  split_nodes_differ=split_nodes_differ)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated table")
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase, in this process")
    ap.add_argument("--rehearse", action="store_true",
                    help="keep going on a platform that is not a TPU "
                         "(still ok: false)")
    args = ap.parse_args(argv)
    checks = Checks()
    try:
        device = (run_mesh if args.chips == 4 else run_node)(args, checks)
    except Exception as e:  # a failed step ends the run: ok false, exit 1
        traceback.print_exc()
        if os.path.exists(NODE_LOG):
            with open(NODE_LOG, "r", errors="replace") as f:
                print("---- node.log (tail) ----\n" + f.read()[-6000:],
                      file=sys.stderr)
        print(json.dumps({"ok": False, "failed": checks.failed,
                          "error": f"{type(e).__name__}: {e}"}), flush=True)
        return 1
    ok = not checks.failed
    print(json.dumps({"ok": ok, "device": device,
                      **({} if ok else {"failed": checks.failed})}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
