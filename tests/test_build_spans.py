"""What a first fit pays before its first tree, under the program's own
spans: JAX's trace, lowering and build of each program as leaf events of
the ring (``jit_trace``, ``jit_lower``, ``jit_build``) with ``trace_s`` /
``lower_s`` on the spans that made them; the host steps ``data_info`` and
``margin_download``; and a bound on the ring events a fit records (the one
clock of the ring and the profiler's trace: ``test_fit_spans.py``)."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import monitoring

from h2o3_tpu import Frame
from h2o3_tpu.models.tree import GBM
from h2o3_tpu.models.tree.drf import DRF
from h2o3_tpu.models.tree.xgboost import XGBoost
from h2o3_tpu.util import telemetry, timeline

pytestmark = pytest.mark.leaks_keys

JIT_KINDS = {"jit_trace", "jit_lower", "jit_build"}
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: what a fit may add to the ring, at most: a first fit records tens of
#: events, so eight such fits and a window still leave the warm-up on record
RING_SHARE = 1 / 8


def _frame(n, seed, cats=False):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4))
    d = {f"x{i}": X[:, i] for i in range(4)}
    if cats:
        d["c"] = np.array(list("pqrst"))[rng.integers(0, 5, n)]
    d["y"] = np.where(X[:, 0] - X[:, 1] * X[:, 2] > 0, "yes", "no")
    return Frame.from_dict(d)


def _fit(builder, frame, **params):
    """One budgeted fit (a budget check after every block) and the ring
    events of its trace, with how many events the ring took meanwhile."""
    n0 = timeline.total_events()
    t0 = time.time_ns()
    model = builder(response_column="y", ntrees=4, max_depth=3, seed=3,
                    max_runtime_secs=600.0, **params).train(frame)
    added = timeline.total_events() - n0
    events = [e for e in timeline.snapshot(timeline.CAPACITY)
              if e["ns"] >= t0 and "parent_id" in e]
    train = [e for e in events if e["kind"] == "train"][-1]
    return model, train, [e for e in events if e["trace_id"] == train["trace_id"]], added


@pytest.fixture(scope="module")
def first_and_second():
    """A process's first fit on shapes no other test builds (nbins 11 and
    an odd row count), then a second fit of the same program."""
    frame = _frame(1733, 21)
    return _fit(GBM, frame, nbins=11), _fit(GBM, frame, nbins=11)


def test_a_first_fit_records_its_programs_as_leaves(first_and_second):
    (_, train, events, _), _ = first_and_second
    jit = [e for e in events if e["kind"] in JIT_KINDS]
    assert {e["kind"] for e in jit} >= {"jit_build"}
    by_id = {e["span_id"]: e for e in events}
    for e in jit:
        parent = by_id[e["parent_id"]]  # an open span of the same fit
        assert parent["kind"] not in JIT_KINDS
        assert parent["start_ns"] <= e["start_ns"] <= e["ns"] <= parent["ns"]
        assert not any(x["parent_id"] == e["span_id"] for x in events)  # a leaf
        # a step of its own lasted JIT_LEAF_S; a merged run says how many
        assert e["ns"] - e["start_ns"] >= telemetry.JIT_LEAF_S * 1e9 - 1e3
    # the block program was made under its tree_block span
    blocks = {e["span_id"] for e in events if e["kind"] == "tree_block"}
    assert any(e["parent_id"] in blocks for e in jit)


def test_a_build_says_whether_it_was_a_cache_load(first_and_second):
    (_, _, events, _), _ = first_and_second
    for e in events:
        if e["kind"] == "jit_build":
            assert e.get("cache_load", True) is True  # absent, or true
        elif e["kind"] in JIT_KINDS:
            assert "cache_load" not in e


def test_a_second_fit_of_the_same_program_records_none(first_and_second):
    _, (_, train, events, _) = first_and_second
    assert not [e for e in events if e["kind"] in JIT_KINDS]
    assert not [e for e in events if "lower_s" in e or "compiles" in e]
    # the block keys' eager vmap of fold_in traces two small jaxprs a block,
    # each fit (tens of microseconds); nothing is lowered or built
    assert train.get("trace_s", 0.0) < telemetry.JIT_LEAF_S


def test_trace_and_lower_seconds_ride_the_spans_that_made_programs(first_and_second):
    (_, train, events, _), _ = first_and_second
    assert train["trace_s"] > 0 and train["lower_s"] > 0
    block = [e for e in events if e["kind"] == "tree_block"][0]
    assert block["trace_s"] > 0 and block["lower_s"] > 0 and block["compiles"] >= 1
    # a parent holds its children's
    for e in events:
        if "trace_s" in e and e is not train:
            assert e["trace_s"] <= train["trace_s"] + 1e-6


def test_a_fits_ring_events_stay_under_a_share_of_the_ring(first_and_second):
    (_, _, events, added), (_, _, events2, added2) = first_and_second
    assert len(events) <= added < RING_SHARE * timeline.CAPACITY
    assert added2 < added


def test_a_nested_trace_is_counted_once():
    """An inner jit traced inside an outer one's trace: JAX reports both,
    the outer's duration holds the inner's."""
    assert telemetry.install_jax_compile_listener()
    t0 = time.time_ns()
    with telemetry.Span("nest_for_test"):
        time.sleep(0.06)
        monitoring.record_scalar(TRACE, time.time())  # the outer opens
        monitoring.record_scalar(TRACE, time.time())  # the inner opens
        monitoring.record_event_duration_secs(TRACE, 0.02)  # the inner closes
        monitoring.record_event_duration_secs(TRACE, 0.05)  # the outer closes
        monitoring.record_scalar(LOWER, time.time())
        monitoring.record_event_duration_secs(LOWER, 0.001)  # short: fields only
    events = [e for e in timeline.snapshot(20) if e["ns"] >= t0]
    span = [e for e in events if e["kind"] == "nest_for_test"][-1]
    jit = [e for e in events if e["kind"] in JIT_KINDS]
    assert span["trace_s"] == pytest.approx(0.05)
    assert span["lower_s"] == pytest.approx(0.001)
    assert [e["kind"] for e in jit] == ["jit_trace"]
    assert jit[0]["parent_id"] == span["span_id"]
    assert (jit[0]["ns"] - jit[0]["start_ns"]) / 1e9 == pytest.approx(0.05, abs=2e-3)


def test_a_real_nested_jit_is_one_trace():
    assert telemetry.install_jax_compile_listener()
    salt = float(time.time_ns() % 7919)

    @jax.jit
    def inner(x):
        return jnp.cos(x) * salt

    @jax.jit
    def outer(x):
        return inner(x) + 1.0

    heard = []

    def listen(name, secs, **kw):
        if name == TRACE:
            heard.append(secs)

    x = jnp.ones((3,))
    monitoring.register_event_duration_secs_listener(listen)
    try:
        with telemetry.Span("real_nest_for_test"):
            outer(x).block_until_ready()
    finally:
        monitoring.unregister_event_duration_listener(listen)
    span = [e for e in timeline.snapshot(50) if e["kind"] == "real_nest_for_test"][-1]
    assert len(heard) >= 2  # the outer's trace and the inner's, inside it
    # the outer's seconds hold the inner's, which are not added again
    assert max(heard) - 1e-6 <= span["trace_s"] < sum(heard)


def test_steps_outside_any_span_record_nothing():
    assert telemetry.install_jax_compile_listener()
    n0 = timeline.total_events()
    monitoring.record_scalar(TRACE, time.time())
    monitoring.record_event_duration_secs(TRACE, 0.5)
    assert timeline.total_events() == n0


@pytest.mark.parametrize("builder", [GBM, XGBoost, DRF], ids=["gbm", "xgboost", "drf"])
def test_data_info_sits_under_tree_setup(builder):
    frame = _frame(900, 22, cats=True)
    _, _, events, _ = _fit(builder, frame)
    by_id = {e["span_id"]: e for e in events}
    info = [e for e in events if e["kind"] == "data_info"]
    assert len(info) == 1
    assert by_id[info[0]["parent_id"]]["kind"] == "tree_setup"
    assert info[0]["cat_columns"] == 1 and info[0]["rows"] == 900


@pytest.mark.parametrize("builder", [GBM, DRF], ids=["gbm", "drf"])
def test_margin_download_sits_under_budget_check(builder):
    frame = _frame(700, 23)
    _, _, events, _ = _fit(builder, frame)
    by_id = {e["span_id"]: e for e in events}
    checks = [e for e in events if e["kind"] == "budget_check"]
    downloads = [e for e in events if e["kind"] == "margin_download"]
    assert checks and len(downloads) == len(checks)
    for d in downloads:
        assert by_id[d["parent_id"]]["kind"] == "budget_check"
        assert d["bytes"] > 0 and d.get("shards", 1) == jax.device_count()
