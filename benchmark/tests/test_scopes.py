"""The by-scope reduction on a small recorded block, against hand-worked
values, on both routes: the compiled text and an op_name stat."""

import json
import os

import pytest

from lib import harness, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
P = "jit(block_fn)/while/body/closed_call/"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "recorded_scopes.json")) as f:
        d = json.load(f)
    d["ops"] = [tuple(op) for op in d["ops"]]
    d["blocks"] = [tuple(b) for b in d["blocks"]]
    return d


@pytest.mark.parametrize("op_name,want", [
    (P + "L03/split/reduce_max", (3, "split")),
    (P + "L00/hist_nodes/select_n", (0, "hist_nodes")),
    (P + "L09/route/jit(_where)/select_n", (9, "route")),
    (P + "L05/subtract/sub", (5, "subtract")),
    (P + "L08/hist/jit(_build_histogram_jit)/hist_sorted/jit(_build_histogram_pallas_jit)"
         "/sorted_prep/sort", (8, "sorted_prep")),
    (P + "L08/hist/jit(_build_histogram_jit)/hist_sorted/jit(_build_histogram_pallas_jit)"
         "/pallas_call", (8, "kernel")),
    (P + "L02/hist/jit(_build_histogram_jit)/hist_nodematmul/jit(_build_histogram_pallas_jit)"
         "/transpose", (2, "hist_prep")),
    (P + "L02/hist/jit(_build_histogram_jit)/shard_map/hist_psum/psum", (2, "hist_psum")),
    (P + "L02/hist/slice", (2, "hist_prep")),
    (P + "grad/logistic", (None, "grad")),
    (P + "sample/jit(_uniform)/shift_right_logical", (None, "sample")),
    (P + "margin/scatter-add", (None, "margin")),
    (P + "leaf/reduce_sum", (None, "leaf")),
    ("jit(block_fn)/while", (None, "unscoped")),
    ("jit(block_fn)/while/body/dynamic_update_slice", (None, "unscoped")),
    (P + "gradient_of_something/add", (None, "unscoped")),  # a component, not a prefix
    ("margin", (None, "unscoped")),  # the block's parameter of that name, no scope
    ("margin/add", (None, "margin")),  # inside a call XLA did not inline: a relative path
    (P + "leaf", (None, "leaf")),
    (None, (None, "unscoped")),
])
def test_phase_of(op_name, want):
    assert scopes.phase_of(op_name) == want


def test_text_gives_every_instruction_its_scope(recorded):
    names = scopes.scopes_from_text(recorded["hlo"])
    assert names["fusion.928"].endswith("sorted_prep/scatter")
    assert scopes.phase_of(names["_build_histogram_pallas_jit.48"]) == (8, "kernel")
    # a fusion with no metadata: the scope of what it calls
    assert scopes.phase_of(names["fusion.771"]) == (3, "split")
    assert scopes.phase_of(names["fusion.801"]) == (3, "route")
    # an instruction the compiler added: the scope of the operand it reads
    assert scopes.phase_of(names["copy.1586"]) == (3, "split")
    # nothing to inherit
    assert scopes.phase_of(names.get("copy-done.7")) == (None, "unscoped")
    assert scopes.phase_of(names["while.29"]) == (None, "unscoped")


def test_sums_by_the_compiled_text(recorded):
    names = scopes.scopes_from_text(recorded["hlo"])
    got = scopes.by_scope(recorded["ops"], recorded["blocks"], names)
    ns = {k: round(v * 1e9) for k, v in got["phases"].items()}
    # the while keeps 10000 - 8400 nested = 1600 ns of its own; copy-done 100
    assert ns == {"grad": 400, "sorted_prep": 3100, "hist_prep": 200, "kernel": 2500,
                  "subtract": 100, "split": 500, "route": 600, "leaf": 900,
                  "unscoped": 1700}
    assert got["busy_s"] == pytest.approx(10000e-9)
    assert got["blocks"] == 1
    l3 = {k: round(v * 1e9) for k, v in got["levels"]["L03"].items()}
    assert l3 == {"hist_prep": 200, "kernel": 1000, "subtract": 100, "split": 500,
                  "route": 600}
    assert round(got["levels"]["L08"]["kernel"] * 1e9) == 1500
    assert [n for n, _ in got["unscoped_ops"]] == ["while.29", "copy-done.7"]


def test_sums_by_an_op_name_stat(recorded):
    """Where the plane gives the name itself the text is not asked."""
    names = scopes.scopes_from_text(recorded["hlo"])
    ops = [(i, s, d, names.get(i)) for i, s, d, _ in recorded["ops"]]
    by_stat = scopes.by_scope(ops, recorded["blocks"], {})
    by_text = scopes.by_scope(recorded["ops"], recorded["blocks"], names)
    assert by_stat["phases"] == by_text["phases"]
    assert by_stat["levels"] == by_text["levels"]


def test_a_program_without_scopes_reads_nothing(recorded):
    bare = {k: "jit(block_fn)/while/body/closed_call/mul"
            for k in scopes.scopes_from_text(recorded["hlo"])}
    assert scopes.by_scope(recorded["ops"], recorded["blocks"], bare) is None
    assert scopes.by_scope(recorded["ops"], recorded["blocks"], {}) is None
    # the parent's block has parameters named like scopes, and copies of them
    bare["copy-done.7"] = "margin"
    assert scopes.by_scope(recorded["ops"], recorded["blocks"], bare) is None


def test_operations_outside_the_blocks_are_left_out(recorded):
    names = scopes.scopes_from_text(recorded["hlo"])
    moved = [(1000.0, 5000.0)]  # cuts the while and everything from the kernels on
    got = scopes.by_scope(recorded["ops"], moved, names)
    assert set(got["phases"]) == {"grad", "sorted_prep", "hist_prep"}


@pytest.mark.parametrize("name,value", [
    ("sorted_prep_ms_per_tree", 3100e-6 / 2),
    ("split_ms_per_tree", 500e-6 / 2),
    ("route_ms_per_tree", (600 + 900) * 1e-6 / 2),
    ("grad_margin_ms_per_tree", 400e-6 / 2),
    ("block_unscoped_pct", 17.0),
])
def test_readers(recorded, monkeypatch, name, value):
    scoped = scopes.by_scope(recorded["ops"], recorded["blocks"],
                             scopes.scopes_from_text(recorded["hlo"]))
    scoped["trees"] = recorded["trees"]
    monkeypatch.setattr(scopes, "window_scopes", lambda run: scoped)
    assert harness.load_reader(ROOT, name)({}) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "sorted_prep_ms_per_tree", "split_ms_per_tree", "route_ms_per_tree",
    "grad_margin_ms_per_tree", "block_unscoped_pct"])
def test_readers_read_nothing_without_a_trace_or_scopes(monkeypatch, name):
    assert harness.load_reader(ROOT, name)({"trace": None}) is None
    monkeypatch.setattr(scopes, "window_scopes", lambda run: None)
    assert harness.load_reader(ROOT, name)({"trace": {"busy_s": 1.0}}) is None


def test_no_sorted_level_reads_zero_not_nothing(recorded, monkeypatch):
    names = {k: v for k, v in scopes.scopes_from_text(recorded["hlo"]).items()
             if "sorted_prep" not in v}
    scoped = scopes.by_scope(recorded["ops"], recorded["blocks"], names)
    scoped["trees"] = 2
    monkeypatch.setattr(scopes, "window_scopes", lambda run: scoped)
    assert harness.load_reader(ROOT, "sorted_prep_ms_per_tree")({}) == 0.0


def test_self_times_of_nested_operations():
    ops = [("outer", 0.0, 100.0, None), ("a", 10.0, 30.0, None),
           ("inner", 50.0, 40.0, None), ("b", 60.0, 10.0, None)]
    got = {op[0]: t for op, t in scopes.self_times(ops)}
    assert got == {"outer": 30.0, "a": 30.0, "inner": 30.0, "b": 10.0}
