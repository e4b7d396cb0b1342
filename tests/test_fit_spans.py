"""The fit's spans, their twins in the profiler's trace, the named scopes
of the block program and the compile counts of a span (ISSUE 28)."""

import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu import Frame
from h2o3_tpu.models.tree import GBM
from h2o3_tpu.util import telemetry, timeline

pytestmark = pytest.mark.leaks_keys

#: every kind of ISSUE 28's table B that a single-host GBM fit with a
#: validation frame passes through, and the two that close its gaps
#: (``tree_rows``, ``score_link``); since ISSUE 33 the training frame is
#: scored from the margin the fit holds, so the walk's kinds are the
#: validation frame's
FIT_KINDS = (
    "train", "tree_setup", "tree_matrix", "tree_rows", "train_boosted",
    "make_bins",
    "bins_resident", "apply_bins", "bins_upload", "state_upload",
    "tree_block", "tree_readback", "budget_check", "model_performance",
    "score_traverse", "score_link", "score_metrics",
)


def _frame(rng, n=1500):
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] - X[:, 1] * X[:, 2] > 0).astype(int)
    d = {f"x{i}": X[:, i] for i in range(4)}
    d["y"] = np.where(y > 0, "yes", "no")
    return Frame.from_dict(d)


def _fit(frame, valid=None):
    """One budgeted GBM fit (the budget makes the builder check it after
    every block) and the ring events of its trace."""
    t0 = time_ns()
    model = GBM(response_column="y", ntrees=4, max_depth=3, seed=3,
                max_runtime_secs=600.0).train(frame, valid)
    events = [e for e in timeline.snapshot(timeline.CAPACITY)
              if e["ns"] >= t0 and "parent_id" in e]
    train = [e for e in events if e["kind"] == "train"][-1]
    return model, train, [e for e in events if e["trace_id"] == train["trace_id"]]


def time_ns():
    import time

    return time.time_ns()


@pytest.fixture(scope="module")
def fitted():
    frame = _frame(np.random.default_rng(7))
    return (frame,) + _fit(frame, _frame(np.random.default_rng(8), n=700))


@pytest.mark.parametrize("kind", FIT_KINDS)
def test_fit_yields_every_kind(fitted, kind):
    _, _, _, events = fitted
    assert kind in {e["kind"] for e in events}


def test_one_trace_and_children_inside_parents(fitted):
    _, _, train, events = fitted
    by_id = {e["span_id"]: e for e in events}
    assert {e["trace_id"] for e in events} == {train["trace_id"]}
    for e in events:
        assert e["start_ns"] <= e["ns"]
        if e is train:
            continue
        parent = by_id[e["parent_id"]]  # every parent is of the same fit
        assert parent["start_ns"] <= e["start_ns"]
        assert e["ns"] <= parent["ns"]


def test_tree_block_keeps_the_fields_the_harness_reads(fitted):
    _, _, _, events = fitted
    blocks = [e for e in events if e["kind"] == "tree_block"]
    assert blocks and sum(b["trees"] for b in blocks) == 4
    for b in blocks:
        assert {"ns", "duration_ms", "trees", "span_id", "parent_id"} <= set(b)
        # the start the harness derives lies at the span's own start, to 2 ms
        assert abs(b["ns"] - b["duration_ms"] * 1e6 - b["start_ns"]) < 2e6


def test_entry_and_scoring_share_kinds_under_different_parents(fitted):
    _, _, _, events = fitted
    by_id = {e["span_id"]: e for e in events}
    parents = {k: {by_id[e["parent_id"]]["kind"] for e in events if e["kind"] == k}
               for k in ("tree_matrix", "apply_bins")}
    # the fixture's fit is a miss: its training rows are built as a matrix
    # for the codes' placement, and the validation frame's for its walk
    assert parents["tree_matrix"] == {"bins_resident", "model_performance"}
    assert parents["apply_bins"] == {"bins_resident", "model_performance"}
    resident = [e for e in events if e["kind"] == "bins_resident"]
    assert [e["hit"] for e in resident] == [False]


def test_second_fit_hits_the_resident_bins(fitted):
    frame = fitted[0]
    _, _, events = _fit(frame)
    assert [e["hit"] for e in events if e["kind"] == "bins_resident"] == [True]
    assert "bins_upload" not in {e["kind"] for e in events}
    # and no matrix of the training rows: a tree_matrix is a walk's
    by_id = {e["span_id"]: e for e in events}

    def scoring(e):
        while e is not None and e["kind"] != "model_performance":
            e = by_id.get(e["parent_id"])
        return e is not None

    assert all(scoring(e) for e in events if e["kind"] in ("tree_matrix", "tree_rows"))


def test_fit_profile_rides_the_model_and_the_log(fitted):
    from h2o3_tpu.util import log

    _, model, _, events = fitted
    prof = model.fit_profile
    assert prof["tree_block"]["n"] == len(
        [e for e in events if e["kind"] == "tree_block"])
    # the walk's kinds once, for the validation frame; link and metrics
    # for the training frame's margin too
    for key, n in (("tree_setup", 1), ("tree_readback", 1), ("score/tree_matrix", 1),
                   ("score/apply_bins", 1), ("score/score_traverse", 1),
                   ("score/score_link", 2), ("score/score_metrics", 2)):
        assert prof[key]["n"] == n and prof[key]["s"] >= 0.0
    assert prof["model_performance"]["n"] == 2
    assert prof["model_performance"]["fit_margin_device"] == 1
    assert "score/tree_block" not in prof
    done = [ln for ln in log.recent(500)
            if "gbm train done" in ln and str(model.key) in ln]
    assert done and "tree_block" in done[-1] and "score/apply_bins" in done[-1]


def test_spans_are_annotations_of_the_profilers_trace(tmp_path):
    from jax.profiler import ProfileData

    frame = _frame(np.random.default_rng(11), n=600)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, _, events = _fit(frame)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = ProfileData.from_file(files[-1])
    start = None
    annotated = {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats)["profile_start_time"])
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "span_id" in stats:
                    annotated[stats["span_id"]] = (ev.name, ev.start_ns, ev.duration_ns)
    assert start is not None
    for e in events:
        if e["kind"].startswith("jit_"):
            continue  # a program's trace, lowering or build: heard after it, no annotation
        name, ev_start, ev_dur = annotated[e["span_id"]]
        assert name == e["kind"]
        # one clock: the ring's stamps are the trace's time plus its
        # profile_start_time, to well under a millisecond
        assert abs(start + ev_start - e["start_ns"]) < 1e6
        assert abs(start + ev_start + ev_dur - e["ns"]) < 1e6


SCOPES = ("L00/hist_nodes", "L00/hist", "L00/split", "L00/route", "L01/hist",
          "L01/route", "grad", "sample", "margin", "leaf")


@pytest.fixture(scope="module")
def lowered_block(mesh):
    from h2o3_tpu.models.tree import booster

    p = booster.TreeParams(ntrees=0, max_depth=2, nbins=8, seed=0)
    fn = booster._make_block_fn("bernoulli", 1, 2, p, mesh, subtract=True)
    S = jax.ShapeDtypeStruct
    n, F = 64, 3
    return fn.lower(
        S((n, F), jnp.int32), S((n,), jnp.float32), S((n,), jnp.bool_),
        S((n, 1), jnp.float32), S((2, 2), jnp.uint32), None, None, None,
    ).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES + ("L01/subtract", "hist_psum"))
def test_lowered_block_names_every_level_and_phase(lowered_block, scope):
    assert re.search(r'loc\("[^"]*\b' + re.escape(scope) + r'[/"]', lowered_block)


def test_lowered_scoring_program_is_scoped():
    from h2o3_tpu.models.tree import booster

    S = jax.ShapeDtypeStruct
    t, m = 2, 7
    text = booster._predict_stacked.lower(
        S((32, 3), jnp.int32), S((t, m), jnp.int32), S((t, m), jnp.int32),
        S((t, m), jnp.bool_), S((t, m), jnp.bool_), S((t, m), jnp.float32),
        max_depth=2, n_bins1_arr=S((), jnp.int32)).as_text(debug_info=True)
    assert "score_traverse" in text


def test_span_reports_the_compiles_of_its_thread():
    assert telemetry.install_jax_compile_listener()
    t0 = time_ns()
    salt = float(t0 % 9973)  # a constant no earlier test compiled

    @jax.jit
    def fresh(x):
        return jnp.sin(x) * salt + 3.0

    with telemetry.Span("outer_for_test"):
        with telemetry.Span("compiles_here"):
            fresh(jnp.ones((5,))).block_until_ready()
        with telemetry.Span("sibling"):
            fresh(jnp.ones((5,))).block_until_ready()
    by_kind = {e["kind"]: e for e in timeline.snapshot(50) if e["ns"] >= t0}
    assert by_kind["compiles_here"]["compiles"] >= 1
    assert by_kind["compiles_here"]["compile_s"] > 0
    assert by_kind["outer_for_test"]["compiles"] >= 1
    assert "compiles" not in by_kind["sibling"]
    assert "cache_loads" not in by_kind["sibling"]


def test_cache_load_is_not_a_compile():
    """What the listener hears when the persistent cache serves a program:
    the cache's hit event, then the duration of the load."""
    from jax import monitoring

    assert telemetry.install_jax_compile_listener()
    builds0 = telemetry.jit_compile_count()
    loads0 = telemetry.REGISTRY.get("jit_cache_loads_total").total()
    seen0 = telemetry.thread_compile_count()
    t0 = time_ns()
    with telemetry.Span("loads_here"):
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.25)
    ev = [e for e in timeline.snapshot(20)
          if e["ns"] >= t0 and e["kind"] == "loads_here"][-1]
    assert ev["cache_loads"] == 1 and "compiles" not in ev
    assert ev["compile_s"] == pytest.approx(0.25)
    assert telemetry.jit_compile_count() == builds0
    assert telemetry.REGISTRY.get("jit_cache_loads_total").total() == loads0 + 1
    # either way the in-process jit cache missed: plan accounting sees it
    assert telemetry.thread_compile_count() == seen0 + 1


def test_span_opens_no_annotation_without_jax(monkeypatch):
    import sys

    monkeypatch.delitem(sys.modules, "jax.profiler")
    with telemetry.Span("host_only") as sp:
        assert sp._ann is None
    assert "jax.profiler" not in sys.modules
