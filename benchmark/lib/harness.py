"""One run of one cell: set-up, the timed window, the readings, `correct`.

The window drives the program's normal Python entry — the builder class the
configuration names, ``Builder(**params, response_column=..., seed=...)
.train(frame)`` — for each request the traffic mix generates.  From the
program the harness takes that entry, ``util/compile_cache.configure``, the
``tree_block`` timeline spans, the ``hist_plan_cache_total`` counter and
``tree_block_size()``; everything else (table, work counts, peaks, trace
reduction, reference, comparison) is the benchmark's own.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from . import checks as checks_mod
from . import trace as trace_mod
from . import work as work_mod

WINDOW_MARKER = "timed_window"


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring)


class CompileMeter:
    """Counts program builds that reached the backend (``builds``), how many
    of them the persistent cache served (``cache_hits``) and the seconds
    spent; ``compiles`` = builds the cache did not serve."""

    def __init__(self) -> None:
        self.builds = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def install(self) -> None:
        from jax import monitoring

        def on_duration(name: str, secs: float, **kw) -> None:
            if name.endswith("backend_compile_duration"):
                self.builds += 1
                self.seconds += secs

        def on_event(name: str, **kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, float]:
        return {"builds": self.builds, "cache_hits": self.cache_hits,
                "compiles": self.builds - self.cache_hits,
                "seconds": self.seconds}

    @staticmethod
    def delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
        return {k: b[k] - a[k] for k in a}


# ---------------------------------------------------------------------------
# traffic: one general generator over a data file


def generate_requests(traffic: dict, seconds: float, block: int) -> List[dict]:
    """Expand a traffic file into the list of requests of one run, the same
    for every seed.  ``"window"`` stands for ``--seconds`` and
    ``"one_block"`` for the program's tree block size."""

    def resolve(params: dict) -> dict:
        named = {"window": seconds, "one_block": block}
        return {k: named.get(v, v) if isinstance(v, str) else v
                for k, v in params.items()}

    out = []
    for req in traffic["requests"]:
        if req["op"] != "train":
            raise SystemExit(f"traffic op {req['op']!r} has no driver yet")
        out.extend({"op": req["op"], "params": resolve(req.get("params", {}))}
                   for _ in range(int(req.get("repeat", 1))))
    return out


# ---------------------------------------------------------------------------
# pieces of a run


def load_builder(path: str):
    module, _, cls = path.partition(":")
    return getattr(importlib.import_module(module), cls)


def make_frame(X: np.ndarray, y: np.ndarray, config: dict):
    from h2o3_tpu.frame.frame import ColType, Column, Frame

    classes = int(config["table"]["classes"])
    cols = [Column(f"f{i}", X[:, i].astype(np.float64)) for i in range(X.shape[1])]
    if classes >= 2:
        cols.append(Column(config["response_column"], y.astype(np.int32),
                           ColType.CAT, [str(c) for c in range(classes)]))
    else:
        cols.append(Column(config["response_column"], y.astype(np.float64)))
    return Frame(cols)


def tree_blocks(t0_ns: int, t1_ns: int) -> List[dict]:
    """The program's ``tree_block`` spans that ended inside [t0, t1]."""
    from h2o3_tpu.util import timeline

    out = []
    for ev in timeline.snapshot(8192):
        if ev["kind"] == "tree_block" and t0_ns <= ev["ns"] <= t1_ns:
            dur = int(ev["duration_ms"] * 1e6)
            out.append({"start_ns": ev["ns"] - dur, "end_ns": ev["ns"],
                        "trees": int(ev["trees"])})
    return out


def hist_plans() -> Dict[str, float]:
    """``hist_plan_cache_total`` summed per implementation."""
    from h2o3_tpu.ops.histogram import PLAN_CACHE

    out: Dict[str, float] = {}
    for impl in ("pallas", "scatter"):
        out[impl] = sum(PLAN_CACHE.value(impl=impl, result=r) for r in ("hit", "miss"))
    return out


def fit(builder, config: dict, frame, seed: int, overrides: dict) -> dict:
    """One ``train()`` with its wall on the host clock and its spans."""
    params = {**config["params"], **overrides}
    t0 = time.time_ns()
    model = builder(response_column=config["response_column"], seed=seed,
                    **params).train(frame)
    t1 = time.time_ns()
    blocks = tree_blocks(t0, t1)
    return {"model": model, "t0_ns": t0, "t1_ns": t1,
            "wall_s": (t1 - t0) / 1e9, "blocks": blocks,
            "trees_built": int(model.ntrees_built)}


def timed_window(builder, config: dict, frame, seed: int, requests: List[dict]) -> List[dict]:
    """The measured window: the requests, one after the other (closed loop,
    one caller).  Its name is the marker the trace reduction looks for."""
    return [fit(builder, config, frame, seed, r["params"]) for r in requests]


def extract_model(model, ref, reported: List[str]) -> dict:
    """The program's answer as plain arrays: init margin, bin edges, trees,
    and the ``training_metrics`` entries named in ``reported``."""
    b = model.booster
    trees = []
    for tpc in b.trees_per_class:
        trees.append([
            ref.Tree(np.asarray(tpc.feat[i]), np.asarray(tpc.split_bin[i]),
                     np.asarray(tpc.default_left[i]), np.asarray(tpc.is_split[i]),
                     np.asarray(tpc.leaf[i], np.float64))
            for i in range(tpc.ntrees)])
    tm = model.training_metrics
    return {"init_margin": np.asarray(b.init_margin, np.float64),
            "edges": np.asarray(b.trees_per_class[0].edges, np.float64),
            "trees": trees,
            "reported": {k: float(getattr(tm, k)) for k in reported
                         if getattr(tm, k, None) is not None}}


def span_summary(served: dict) -> dict:
    """Where one fit's wall went, by the program's spans (for the reader of
    a result line; the per-layer metrics read the same spans)."""
    b = served["blocks"]
    if not b:
        return {"wall_s": served["wall_s"]}
    return {"entry_s": (b[0]["start_ns"] - served["t0_ns"]) / 1e9,
            "block_s": [(x["end_ns"] - x["start_ns"]) / 1e9 for x in b],
            "between_s": [(b[i + 1]["start_ns"] - b[i]["end_ns"]) / 1e9
                          for i in range(len(b) - 1)],
            "tail_s": (served["t1_ns"] - b[-1]["end_ns"]) / 1e9}


def device_info(devices, peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": int(peak_bytes)}


def allocator_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def check_devices(devices, peaks: dict, chips: int, rehearse: bool) -> Optional[dict]:
    """The peaks of the attached device kind, or the reason this machine
    cannot run the cell.  A rehearsal wants the CPU and gets no peaks."""
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            raise SystemExit("--rehearse is for the CPU; run without it on the chip")
        return None
    if platform == "cpu":
        raise SystemExit("no accelerator: jax.devices() is the CPU; this "
                         "command measures the chip and prints nothing without one")
    if devices[0].device_kind not in peaks:
        raise SystemExit(f"no peaks on record for device kind "
                         f"{devices[0].device_kind!r}: add it to "
                         "benchmark/lib/peaks.json with its source")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return peaks[devices[0].device_kind]


def load_named(root: str, kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``: a per-layer metric's reader
    or a table generator, found by the name the data gives."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no benchmark/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        kind + "_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, name: str):
    return load_named(root, "metrics", name).read


# ---------------------------------------------------------------------------
# the run


def run(*, cell: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace: bool, rehearse: bool, t_start: float, root: str,
        metrics: List[dict]) -> dict:
    from h2o3_tpu.util import compile_cache

    cache_dir = compile_cache.configure()
    import jax

    # every program goes to the persistent cache, not only the slow compiles:
    # the second run of a cell must find all of them there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    meter = CompileMeter()
    meter.install()

    devices = jax.devices()
    with open(os.path.join(root, "benchmark", "lib", "peaks.json")) as f:
        peak = check_devices(devices, json.load(f), int(cell["chips"]), rehearse)

    from h2o3_tpu.models.tree.booster import tree_block_size

    rows = int(config["rehearse"]["rows"] if rehearse else config["table"]["rows"])
    features = int(config["table"]["features"])
    classes = int(config["table"]["classes"])
    builder = load_builder(config["builder"])
    block = tree_block_size()

    # -- set-up: table, frame, warm-up fit of one block -----------------------
    X, y = load_named(root, "tables", config["table"]["generator"]).make(
        config["table"], rows, seed)
    frame = make_frame(X, y, config)
    warm_req = generate_requests(
        {"requests": [dict(traffic["warmup"], repeat=1)]}, seconds, block)[0]
    before_warm = meter.snapshot()
    warm = fit(builder, config, frame, seed, warm_req["params"])
    warm_model = warm.pop("model")
    requests = generate_requests(traffic, seconds, block)
    prewarm_scoring(warm_model, warm, meter.snapshot()["seconds"] - before_warm["seconds"],
                    requests, rows, features, block)
    del warm_model
    gc.collect()
    setup_compile = meter.snapshot()

    # -- the window ------------------------------------------------------------
    trace_dir = os.path.join(root, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.time() - t_start
    try:
        with jax.profiler.TraceAnnotation(WINDOW_MARKER):
            served = timed_window(builder, config, frame, seed, requests)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compile = CompileMeter.delta(setup_compile, meter.snapshot())
    # level plans are counted when a program is traced, so the window of a
    # warm program adds none: the run's whole count says which one it ran
    plans = hist_plans()
    alloc_peak = allocator_peak(devices)

    # -- after the window: memory, trace, then free the program and compare ---
    from . import reference as ref

    limits = checks_mod.load_limits(root, cell["config"])
    answers = [extract_model(s.pop("model"), ref, checks_mod.reported_metrics(limits))
               for s in served]
    program_bytes = None
    if not rehearse:
        from . import programs

        program_bytes = programs.attached_footprint(config, rows, features, classes, block)
    reduced = None
    if trace:
        device_ev, host_ev = trace_mod.read_xplane(trace_dir, WINDOW_MARKER)
        reduced = trace_mod.reduce(device_ev, host_ev, WINDOW_MARKER)
        if not rehearse and (reduced is None or reduced["busy_s"] <= 0):
            raise SystemExit(
                "the traced window holds no device operation: device planes "
                f"{ {k: len(v) for k, v in device_ev.items()} }, {len(host_ev)} host "
                f"spans, {sum(WINDOW_MARKER in e[0] for e in host_ev)} of them the "
                f"window's marker; the trace is kept in {trace_dir}")
    del frame
    from h2o3_tpu.frame import devcache

    devcache.DEVCACHE.clear()
    gc.collect()

    failed = 0
    problems: List[str] = []
    if window_compile["compiles"] > 0:
        failed += 1
        problems.append(f"{window_compile['compiles']} program(s) compiled inside the window")
    if not rehearse and plans.get("scatter", 0) > 0:
        failed += 1
        problems.append("this run planned the scatter histogram: not the path the cell is about")
    t_check = time.time()
    compared = checks_mod.compare(ref, config, seed, X, y, classes, answers, block,
                                  list(limits))
    check_s = time.time() - t_check
    checks = {k: (v, limits[k]) for k, v in compared.items()}
    correct = not problems and all(v <= lim for v, lim in checks.values())
    for msg in problems:
        print("FAILED: " + msg, file=sys.stderr)

    # -- metrics through their readers ----------------------------------------
    trees_built = sum(s["trees_built"] for s in served)
    run_ctx = {
        "cell": cell["name"], "rows": rows, "features": features,
        "classes": classes, "params": config["params"], "seconds": seconds,
        "setup_s": setup_s, "setup_compile": setup_compile,
        "window_compile": window_compile, "warmup": warm, "served": served,
        "trees_built": trees_built,
        "wall_s": sum(s["wall_s"] for s in served),
        "trace": reduced, "peak": peak,
        "work": work_mod.tree_work(rows, features, classes, config["params"]),
    }
    out_metrics = {}
    if not rehearse:
        for m in metrics:
            value = load_reader(root, m["name"])(run_ctx)
            if value is not None:
                out_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    footprint = max(alloc_peak, program_bytes["total"] if program_bytes else 0)
    result = {
        "correct": bool(correct),
        "attempted": len(requests),
        "failed": failed,
        "metrics": out_metrics,
        "device": device_info(devices, footprint),
        "workload": cell["name"], "seed": seed, "seconds": seconds,
        "rehearse": rehearse, "compile_cache_dir": cache_dir, "check_s": check_s,
        "window": {"wall_s": run_ctx["wall_s"], "trees_built": trees_built,
                   "blocks": sum(len(s["blocks"]) for s in served),
                   "spans_s": [span_summary(s) for s in served],
                   "builds": window_compile["builds"],
                   "cache_hits": window_compile["cache_hits"],
                   "hist_plans": plans},
        "memory": {"allocator_peak_bytes": alloc_peak, "program": program_bytes},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks  # last: each number compared beside its limit
    return result


def prewarm_scoring(model, warm: dict, warm_compile_s: float, requests: List[dict],
                    rows: int, features: int, block: int) -> None:
    """The post-fit scoring program has the number of trees in its shapes,
    so a budgeted fit that ends on another count than the warm-up's would
    compile inside the window.  The warm-up's block time (less what it spent
    compiling) says which counts the budget can end on; the programs from
    half to twice that many blocks are built here, in set-up."""
    budgets = [r["params"]["max_runtime_secs"] for r in requests
               if "max_runtime_secs" in r["params"]]
    if not budgets or not warm["blocks"]:
        return
    b = warm["blocks"][-1]
    span = (b["end_ns"] - b["start_ns"]) / 1e9
    block_s = max(span - warm_compile_s, 0.2 * span)
    from . import programs

    counts = set()
    for budget in budgets:
        k = int(budget // block_s) + 1
        counts.update(block * j for j in range(max(1, k // 2), 2 * k + 3))
    counts.discard(warm["trees_built"])
    programs.build_scoring_programs(model, rows, features, sorted(counts))
