"""One run of one cell: set-up, the timed window, the readings, `correct`.

The window drives the program's normal Python entry — the builder class the
configuration names, ``Builder(**params, response_column=..., seed=...)
.train(frame)`` — for each request the traffic mix generates.  From the
program the harness takes that entry, ``frame.Frame`` / ``Column`` /
``ColType`` to hand it a table, ``util/compile_cache.configure``, the
``tree_block`` timeline spans, the ``hist_plan_cache_total`` counter,
``tree_block_size()`` and ``devcache.DEVCACHE.clear``; everything else
(table, work counts, peaks, trace reduction, reference, comparison) is the
benchmark's own.

What is particular to a deployment is data, found by the names the
configuration gives: the table generator and its typed columns
(``benchmark/tables/``), the traffic mix (``benchmark/traffic/``), the
reference with its extraction and comparison (``benchmark/references/``,
through ``lib/checks.py``), the limits, the metric readers.  This file no
longer knows what a tree's arrays are or which reference judges them; what
is still reached for inside ``models/tree/booster`` (``_make_block_fn``,
``_predict_stacked``, ``BoostedTrees.params``) is reached for in
``lib/programs.py`` alone.
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
#: scoring programs built ahead for one budgeted request, at most
LADDER_MAX = 32


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring)


class CompileMeter:
    """Counts program builds that reached the backend (``builds``), how many
    of them the persistent cache served (``cache_hits``) and the seconds
    spent; ``compiles`` = builds the cache did not serve.  ``names`` keeps
    the name of every build in order, so a run can say which program was
    built inside its window; ``spells`` keeps when a program was being
    traced, lowered or built, so a span can say how much of it was not
    execution (``building_s``)."""

    #: jax.monitoring's three stages of making a program
    STAGES = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
              "backend_compile_duration")

    def __init__(self) -> None:
        self.builds = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names: List[str] = []
        self.spells: List[tuple] = []  # (start_ns, end_ns) on the host clock

    def install(self) -> None:
        from jax import monitoring

        def on_duration(name: str, secs: float, **kw) -> None:
            if name.endswith(self.STAGES):
                end = time.time_ns()
                self.spells.append((end - int(secs * 1e9), end))
            if name.endswith("backend_compile_duration"):
                self.builds += 1
                self.seconds += secs
                self.names.append(str(kw.get("fun_name")))

        def on_event(name: str, **kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def building_s(self, t0_ns: int, t1_ns: int) -> float:
        """Seconds of [t0, t1] in which some program was being traced,
        lowered or built: the union of the spells, because an inner jit's
        lie inside its caller's."""
        total, reach = 0, t0_ns
        for a, b in sorted(self.spells):
            a, b = max(a, reach), min(b, t1_ns)
            if b > a:
                total += b - a
                reach = b
        return total / 1e9

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
    ``"one_block"`` for the program's tree block size.  A fixed ``ntrees``
    (no budget beside it) is a whole number of blocks: a shorter last block
    is a training program of its own, which set-up builds for no one, and it
    would compile inside the window."""

    def resolve(params: dict) -> dict:
        named = {"window": seconds, "one_block": block}
        return {k: named.get(v, v) if isinstance(v, str) else v
                for k, v in params.items()}

    out = []
    for req in traffic["requests"]:
        if req["op"] != "train":
            raise SystemExit(f"traffic op {req['op']!r} has no driver yet")
        params = resolve(req.get("params", {}))
        if ("ntrees" in params and "max_runtime_secs" not in params
                and int(params["ntrees"]) % block):
            raise SystemExit(f"traffic asks for a fixed ntrees of {params['ntrees']}: "
                             f"give a whole number of blocks of {block} trees")
        out.extend({"op": req["op"], "params": dict(params)}
                   for _ in range(int(req.get("repeat", 1))))
    return out


# ---------------------------------------------------------------------------
# pieces of a run


def load_builder(path: str):
    module, _, cls = path.partition(":")
    return getattr(importlib.import_module(module), cls)


def make_table(root: str, config: dict, rows: int, seed: int) -> dict:
    """The configuration's table from the seed, by its generator: ``X``,
    ``y``, ``classes`` and ``columns`` — the generator's own
    ``columns(spec)``, one ``{"name", "type": "num"|"cat", "domain"}`` a
    feature, or None where it states none (then every feature is numeric)."""
    spec = config["table"]
    gen = load_named(root, "tables", spec["generator"])
    X, y = gen.make(spec, rows, seed)
    columns = gen.columns(spec) if hasattr(gen, "columns") else None
    if columns is not None:
        if len(columns) != X.shape[1]:
            raise SystemExit(f"generator {spec['generator']} states {len(columns)} "
                             f"columns and makes {X.shape[1]}")
        for col in columns:
            if col.get("type") not in ("num", "cat") or (
                    col["type"] == "cat" and not col.get("domain")):
                raise SystemExit(f"column {col.get('name')!r} of generator "
                                 f"{spec['generator']}: the type is \"num\", or "
                                 "\"cat\" with its domain")
    return {"X": X, "y": y, "classes": int(spec["classes"]), "columns": columns}


def make_frame(X: np.ndarray, y: np.ndarray, config: dict,
               columns: Optional[List[dict]] = None):
    """The ``Frame`` handed to ``train()``.  A feature's type comes from
    ``columns`` alone (a categorical's values are its level codes, NaN its
    NA); with none stated the features are ``f0..``, float64.  The response
    is categorical where the table has classes."""
    from h2o3_tpu.frame.frame import NA_CAT, ColType, Column, Frame

    classes = int(config["table"]["classes"])
    if columns is None:
        cols = [Column(f"f{i}", X[:, i].astype(np.float64)) for i in range(X.shape[1])]
    else:
        cols = []
        for i, col in enumerate(columns):
            if col["type"] == "cat":
                codes = np.where(np.isnan(X[:, i]), NA_CAT, X[:, i]).astype(np.int32)
                cols.append(Column(col["name"], codes, ColType.CAT, list(col["domain"])))
            else:
                cols.append(Column(col["name"], X[:, i].astype(np.float64)))
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
    """The module ``benchmark/<kind>/<name>.py``: a per-layer metric's
    reader, a table generator or a reference, found by the name the data
    gives."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no benchmark/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        kind + "_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks its module up by name
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
    # a configuration whose limits or reference cannot decide `correct` is
    # refused here, not after the window
    limits = checks_mod.load_limits(root, cell["config"])
    ref = checks_mod.load_reference(root, config, limits)

    # -- set-up: table, frame, warm-up fit of one block -----------------------
    table = make_table(root, config, rows, seed)
    frame = make_frame(table["X"], table["y"], config, table["columns"])
    warm_req = generate_requests(
        {"requests": [dict(traffic["warmup"], repeat=1)]}, seconds, block)[0]
    warm = fit(builder, config, frame, seed, warm_req["params"])
    warm_model = warm.pop("model")
    requests = generate_requests(traffic, seconds, block)
    block_program = warm_block_program(warm_model, config)
    last = warm["blocks"][-1] if warm["blocks"] else None
    prewarmed = prewarm_scoring(
        warm_model, warm, meter.building_s(last["start_ns"], last["end_ns"]) if last else 0.0,
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
    window_built = meter.names[int(setup_compile["builds"]):]
    # level plans are counted when a program is traced, so the window of a
    # warm program adds none: the run's whole count says which one it ran
    plans = hist_plans()
    alloc_peak = allocator_peak(devices)

    # -- after the window: memory, trace, then free the program and compare ---
    answers = [ref.extract(s.pop("model"), list(limits)) for s in served]
    program_bytes = None
    if not rehearse:
        from . import programs

        program_bytes = programs.attached_footprint(block_program, rows, features, block)
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
        problems.append(f"{window_compile['compiles']} program(s) compiled inside the "
                        f"window, of those built there: {window_built}")
    if not rehearse and plans.get("scatter", 0) > 0:
        failed += 1
        problems.append("this run planned the scatter histogram: not the path the cell is about")
    t_check = time.time()
    checks = checks_mod.decide(ref, limits, config, seed, table, answers, block)
    check_s = time.time() - t_check
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
        "trace": reduced, "peak": peak, "block_program": block_program,
        "work": work_mod.tree_work(rows, features, classes, config["params"],
                                   table["columns"]),
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
                   "builds": window_compile["builds"], "built": window_built,
                   "cache_hits": window_compile["cache_hits"],
                   "prewarmed": prewarmed, "hist_plans": plans},
        # "allocator_only" under-reads: it leaves out a program's temporaries
        "memory": {"allocator_peak_bytes": alloc_peak, "program": program_bytes,
                   "source": "block_program" if program_bytes else "allocator_only"},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks  # last: each number compared beside its limit
    return result


def warm_block_program(model, config: dict) -> Optional[dict]:
    """What the training block was compiled from, off the warm-up's model
    (``programs.block_spec``); None, with a note, where it cannot be read."""
    from . import programs

    try:
        return programs.block_spec(model, config)
    except Exception as e:  # the program's internals moved, or a new builder
        print(f"note: the fitted model does not say what its block was "
              f"compiled from ({e!r})", file=sys.stderr)
        return None


def prewarm_scoring(model, warm: dict, warm_building_s: float, requests: List[dict],
                    rows: int, features: int, block: int) -> List[int]:
    """The post-fit scoring program has the number of trees in its shapes,
    so a fit that ends on another count than the warm-up's would compile
    inside the window.  A request with a literal ``ntrees`` and no budget
    ends on that count (a whole number of blocks: ``generate_requests``
    refuses another).  For a budgeted request the warm-up's block time says
    which counts the budget can end on: from half to twice that many blocks.
    The block time is the warm-up block's span less ``warm_building_s``, the
    part of it in which its program was traced, lowered and built or loaded
    (8-9 of 14 s at depth 10 with every program in the cache: left in, the
    ladder ended one block above the count the window really ends on).  All
    are built here, in set-up, at most ``LADDER_MAX`` a budget (the nearest
    to the estimate: a block span that says nothing, as of a step that
    returns before its work is done, must not ask for thousands); returns
    the counts."""
    counts = {int(r["params"]["ntrees"]) for r in requests
              if "ntrees" in r["params"] and "max_runtime_secs" not in r["params"]}
    budgets = [r["params"]["max_runtime_secs"] for r in requests
               if "max_runtime_secs" in r["params"]]
    if budgets and warm["blocks"]:
        b = warm["blocks"][-1]
        span = (b["end_ns"] - b["start_ns"]) / 1e9
        block_s = max(span - warm_building_s, 0.05 * span)
        for budget in budgets:
            k = int(budget // block_s) + 1
            rungs = sorted(range(max(1, k // 2), 2 * k + 3), key=lambda j: abs(j - k))
            counts.update(block * j for j in rungs[:LADDER_MAX])
    counts.discard(warm["trees_built"])
    if counts:
        from . import programs

        programs.build_scoring_programs(model, rows, features, sorted(counts))
    return sorted(counts)
