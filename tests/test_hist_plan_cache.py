"""The node ladder: how many node slots a level's launch has.

Contract (h2o3_tpu/ops/histogram.py): every histogram/totals launch pads
its node dimension up the node ladder (1/2/4/8/16/32/64/512: a level pays
for the slots it launches, so up to 64 the ladder follows the node count,
and the 512 rung keeps 65 to 512 nodes on the sorted kernel); the real node
rows are sliced back out and the result is BIT-identical to the unpadded
build, because the scatter-add accumulation order does not depend on the
destination capacity. ``hist_plan_cache_total{impl,result}`` meters lookups
against the padded-shape plan cache — a warm fit must record zero misses.
``booster.level_plan`` says what every level of a fit launches, and the
``tree_block`` span and ``fit_profile`` carry it as ``hist_slots``.
"""

import pickle
import time

import numpy as np
import pytest

import jax.numpy as jnp

from h2o3_tpu import Frame
from h2o3_tpu.models.grid import metric_value
from h2o3_tpu.models.tree import DRF, GBM, XGBoost, booster
from h2o3_tpu.ops import histogram as H

pytestmark = pytest.mark.leaks_keys


# ---------------------------------------------------------------------------
# the ladder itself


@pytest.fixture
def no_ladder(monkeypatch):
    """Call it to take the ladder away for the rest of the test. A fit's
    block is cached by its parameters (``_make_block_fn``) and its trace
    holds the padding it was traced with, so the cache is emptied on both
    sides: the next fit traces unpadded, and no later test inherits that
    trace."""
    def off():
        monkeypatch.setattr(H, "_NODE_BUCKETS", ())
        booster._make_block_fn.cache_clear()
    yield off
    booster._make_block_fn.cache_clear()


def test_pad_nodes_default_ladder():
    assert H._NODE_BUCKETS == (1, 2, 4, 8, 16, 32, 64, 512)
    # bucket edges: at the edge stays, one past jumps to the next rung,
    # past the top rung runs unpadded
    for n, want in [(1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (7, 8), (8, 8),
                    (9, 16), (16, 16), (17, 32), (32, 32), (33, 64), (64, 64),
                    (65, 512), (512, 512), (513, 513), (4096, 4096)]:
        assert H.pad_nodes(n) == want, (n, want)


def test_the_ladder_follows_the_node_count_up_to_64():
    """Up to 64 a launch has fewer than twice the slots its nodes need (the
    next power of two), and the powers of two a tree's levels build are not
    padded at all."""
    for n in range(1, 65):
        slots = H.pad_nodes(n)
        assert slots == 1 << (n - 1).bit_length(), n
        assert n <= slots < 2 * n, n
    for d in range(7):
        assert H.pad_nodes(2**d) == 2**d


# ---------------------------------------------------------------------------
# bit-identity of padded launches, across the bucket boundaries


def _level_inputs(rng, n, k, f=3, b=6):
    bins = jnp.asarray(rng.integers(0, b + 1, size=(n, f)).astype(np.int32))
    nodes = jnp.asarray(rng.integers(-1, k, size=n).astype(np.int32))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.random(n).astype(np.float32))
    rw = jnp.asarray((1.0 + rng.random(n)).astype(np.float32))
    return bins, nodes, g, h, rw, b + 1


@pytest.mark.parametrize("k", [1, 3, 7, 8, 9, 16, 17, 32, 33, 64, 65])
@pytest.mark.parametrize("with_rw", [False, True])
def test_padded_bit_identical(no_ladder, rng, k, with_rw):
    bins, nodes, g, h, rw, n_bins1 = _level_inputs(rng, 1024, k)
    rw = rw if with_rw else None
    hist = np.asarray(H.build_histogram_sharded(
        bins, nodes, g, h, n_nodes=k, n_bins1=n_bins1, rw=rw))
    tot = np.asarray(H.node_totals_sharded(nodes, g, h, n_nodes=k, rw=rw))
    no_ladder()  # unpadded ref
    ref_h = np.asarray(H.build_histogram_sharded(
        bins, nodes, g, h, n_nodes=k, n_bins1=n_bins1, rw=rw))
    ref_t = np.asarray(H.node_totals_sharded(nodes, g, h, n_nodes=k, rw=rw))
    assert hist.shape == ref_h.shape == (k, 3, n_bins1, 3)
    assert hist.tobytes() == ref_h.tobytes(), f"histogram drift at k={k}"
    assert tot.tobytes() == ref_t.tobytes(), f"totals drift at k={k}"


def test_pad_rows_are_exact_zero(rng):
    # node ids never reach the pad rows, so the padded capacity beyond the
    # real node count accumulates exact 0.0 — assert via the full padded
    # build with the ladder forced to a single oversized bucket
    bins, nodes, g, h, _, n_bins1 = _level_inputs(rng, 512, 3)
    full = np.asarray(H._build_histogram_jit(
        bins, nodes, g, h, None, None, 8, n_bins1, None, "scatter"))
    assert full.shape[0] == 8
    assert not full[3:].any(), "pad rows picked up mass"


# ---------------------------------------------------------------------------
# plan-cache accounting: one miss per bucket, hits for every level after


def _plan(result):
    from h2o3_tpu.util import telemetry

    c = telemetry.REGISTRY.get("hist_plan_cache_total")
    if c is None:
        return 0.0
    return sum(s["value"] for s in c.snapshot()["series"]
               if s["labels"]["result"] == result)


def test_one_plan_per_bucket(rng):
    bins, nodes, g, h, _, n_bins1 = _level_inputs(rng, 2048, 8)
    miss0, hit0 = _plan("miss"), _plan("hit")
    for k in (5, 6, 7, 8):  # one bucket: four node counts, one plan
        nk = jnp.asarray(rng.integers(-1, k, size=2048).astype(np.int32))
        H.build_histogram_sharded(bins, nk, g, h, n_nodes=k, n_bins1=n_bins1)
    miss = _plan("miss") - miss0
    hit = _plan("hit") - hit0
    assert miss <= 1, f"plan churn inside one bucket: {miss} misses"
    assert miss + hit == 4


@pytest.mark.parametrize("rung,ks", [(4, (3, 4)), (16, (9, 12, 16)),
                                     (32, (17, 24, 32)), (64, (33, 48, 64)),
                                     (512, (65, 128, 256, 512))])
def test_one_plan_inside_each_rung(rng, rung, ks):
    bins, _, g, h, _, n_bins1 = _level_inputs(rng, 2048, 8, f=2, b=5)
    miss0, hit0 = _plan("miss"), _plan("hit")
    for k in ks:
        assert H.pad_nodes(k) == rung
        nk = jnp.asarray(rng.integers(-1, k, size=2048).astype(np.int32))
        H.build_histogram_sharded(bins, nk, g, h, n_nodes=k, n_bins1=n_bins1)
    miss = _plan("miss") - miss0
    assert miss <= 1, f"plan churn inside the {rung} rung: {miss} misses"
    assert miss + _plan("hit") - hit0 == len(ks)


# ---------------------------------------------------------------------------
# whole-fit bit-identity: the ladder must never change a model


def _frames(seed=7, n=3000):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5))
    reg = 3 * X[:, 0] + np.sin(3 * X[:, 1]) * 2 + X[:, 2] * X[:, 3]
    cls = np.where(reg + 0.3 * rng.normal(size=n) > 0, "yes", "no")
    cols = {f"x{i}": X[:, i] for i in range(5)}
    return (Frame.from_dict(cols | {"y": reg}),
            Frame.from_dict(cols | {"y": cls}))


def _sig(model):
    bt = model.booster
    arrays = [
        np.stack(getattr(t, f))
        for t in bt.trees_per_class
        for f in ("feat", "split_bin", "default_left", "is_split", "leaf")
    ]
    return pickle.dumps([arrays, np.asarray(bt.init_margin),
                         metric_value(model, "auto")[0]])


def _model(algo, depth=4):
    kw = dict(response_column="y", ntrees=3, max_depth=depth, seed=11)
    if algo == "gbm":
        return GBM(**kw)
    if algo == "drf":
        return DRF(sample_rate=0.7, **kw)
    return XGBoost(**kw)


@pytest.mark.parametrize("algo", ["gbm", "drf", "xgb"])
@pytest.mark.parametrize("resp", ["reg", "bin"])
def test_fit_matrix_padded_vs_unpadded(monkeypatch, no_ladder, algo, resp):
    fr_reg, fr_bin = _frames()
    fr = fr_reg if resp == "reg" else fr_bin
    slots = []  # the node slots of every level plan a fit asks for
    note = H._note_plan

    def spy(key, impl):
        slots.append(key[1])
        note(key, impl)

    monkeypatch.setattr(H, "_note_plan", spy)
    # depth 7: the one launch of a scatter fit that is padded at all is the
    # leaves' totals (128 nodes in the 512 rung); up to 64 the ladder is the
    # node count of a level
    padded = _model(algo, depth=7).train(fr)
    assert set(slots) == {1, 2, 4, 8, 16, 32, 64, 512}, slots
    # what the fit asked for is what its plan says, level by level
    plan = booster.level_plan(padded.booster.params, subtract=False)
    assert sorted(set(slots)) == sorted({lv[1] for lv in plan})
    no_ladder()
    del slots[:]
    unpadded = _model(algo, depth=7).train(fr)
    # traced afresh, every level at its own node count: not the padded
    # program compared with itself
    assert set(slots) == {1, 2, 4, 8, 16, 32, 64, 128}, slots
    assert _sig(padded) == _sig(unpadded), f"{algo}/{resp} drifts under padding"


def test_warm_fit_compiles_no_plans():
    fr_reg, _ = _frames()
    _model("gbm").train(fr_reg)  # cold: traces this shape family once
    miss0 = _plan("miss")
    _model("gbm").train(fr_reg)  # warm: every level must hit
    assert _plan("miss") == miss0, "warm fit missed the plan cache"


# ---------------------------------------------------------------------------
# the level plan: what every level launches, stated without a trace


D6 = ([1, 1, 2, 4, 8, 16], [1, 1, 2, 4, 8, 16])
D10 = ([1, 1, 2, 4, 8, 16, 32, 64, 128, 256],
       [1, 1, 2, 4, 8, 16, 32, 64, 512, 512])


@pytest.mark.parametrize("depth,built,slots", [(6, *D6), (10, *D10)])
def test_level_plan_with_subtraction(depth, built, slots):
    """A level builds each parent's smaller child alone: half the level's
    nodes; the leaves come from the last split's child stats, no launch."""
    p = booster.TreeParams(max_depth=depth)
    plan = booster.level_plan(p, subtract=True, impl="pallas")
    assert [lv[0] for lv in plan] == built
    assert [lv[1] for lv in plan] == slots
    # node-matmul up to 128 slots, the sorted kernel past it
    assert [lv[2] for lv in plan] == [
        "nodematmul" if s <= 128 else "sorted" for s in slots]
    assert [lv[2] for lv in booster.level_plan(p, True, impl="scatter")] == [
        "scatter"] * depth


@pytest.mark.parametrize("depth", [0, 1, 4, 6])
def test_level_plan_without_subtraction(depth):
    """Every node of a level, then the per-node totals of the leaves."""
    p = booster.TreeParams(max_depth=depth)
    plan = booster.level_plan(p, subtract=False, impl="scatter")
    assert [lv[0] for lv in plan] == [2**d for d in range(depth + 1)]
    assert [lv[1] for lv in plan] == [H.pad_nodes(2**d) for d in range(depth + 1)]
    assert [lv[2] for lv in plan] == ["scatter"] * depth + ["totals"]
    # with subtraction a stump still has its leaf's totals to sum
    if depth == 0:
        assert booster.level_plan(p, subtract=True, impl="scatter") == plan


@pytest.mark.parametrize("depth,built,slots", [(6, *D6), (10, *D10)])
def test_tree_block_span_and_fit_profile_carry_hist_slots(
        monkeypatch, depth, built, slots):
    from h2o3_tpu.util import timeline

    monkeypatch.setenv("H2O3_TPU_TREE_SUBTRACT", "1")
    asked = []  # the node slots of every plan the trace asks for
    note = H._note_plan

    def spy(key, impl):
        asked.append(key[1])
        note(key, impl)

    monkeypatch.setattr(H, "_note_plan", spy)
    _, fr_bin = _frames(n=600)
    t0 = time.time_ns()
    model = GBM(response_column="y", ntrees=2, max_depth=depth, nbins=8,
                seed=5).train(fr_bin)
    want = [[b, s, "scatter"] for b, s in zip(built, slots)]
    blocks = [e for e in timeline.snapshot(timeline.CAPACITY)
              if e["kind"] == "tree_block" and e["ns"] >= t0][-1:]
    assert [list(map(list, e["hist_slots"])) for e in blocks] == [want]
    prof = model.fit_profile["tree_block"]
    assert list(map(list, prof["hist_slots"])) == want
    assert prof["n"] == 1 and prof["s"] >= 0.0
    # the plan is what the trace launched: one block of two trees traces
    # the tree once, a level a launch
    assert asked == slots
    # and the log line still reads (a plan is not a count)
    from h2o3_tpu.models.framework import _profile_text

    assert "tree_block" in _profile_text(model.fit_profile)
    assert "hist_slots" not in _profile_text(model.fit_profile)
