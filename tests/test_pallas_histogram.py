"""Parity: Pallas tpu_hist kernels vs the portable XLA scatter oracle.

Runs the kernels in Pallas interpreter mode (CPU-safe); on a real TPU the
same code paths compile to Mosaic. Oracle: ops/histogram.py
(_shard_histogram), itself validated against the reference semantics of
hex/tree/DHistogram.java:433.

Two kernels are covered explicitly: the fixed-layout node-matmul kernel
(bf16 operands, f32 accumulation — tolerance reflects the bf16 rounding of
g/h inputs; counts are exact because 0/1 are exact in bf16) and the sorted
tile-per-node fallback used for deep levels (f32 throughout).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.ops.histogram import _shard_histogram
from h2o3_tpu.ops.pallas_histogram import (
    _build_histogram_pallas_jit,
    _code_words,
    _kernel_choice,
    _pack_row,
    _unpack_row,
    build_histogram_pallas,
)

INTERPRET = jax.default_backend() != "tpu"

# (kernel, rtol, atol): node-matmul carries bf16 operand rounding (~2^-8
# relative per element); sorted kernel is f32 end-to-end; auto is the
# selection a fit runs (``_kernel_choice``), the node-matmul kernel at every
# node count of these cases
KERNELS = [
    ("nodematmul", 2e-2, 5e-2),
    ("sorted", 1e-5, 1e-4),
    ("auto", 2e-2, 5e-2),
]


@pytest.mark.parametrize("kernel,dtype,n_nodes,want", [
    ("auto", "auto", 1, ("nodematmul", "f32")),
    ("auto", "auto", 128, ("nodematmul", "f32")),  # K*C = 512: the last that fits
    ("auto", "auto", 129, ("sorted", "f32")),
    ("auto", "bf16", 512, ("sorted", "bf16")),
    ("sorted", "auto", 4, ("sorted", "f32")),  # a name asked for is kept
    ("nodematmul", "f32", 256, ("nodematmul", "f32")),
])
def test_kernel_choice_is_the_level_plan(kernel, dtype, n_nodes, want):
    """The one place that decides kernel and operand precision: by the node
    slots and the platform (interpreted here, so f32)."""
    assert INTERPRET
    assert _kernel_choice(kernel, dtype, n_nodes) == want
    assert _kernel_choice(*want, n_nodes) == want  # resolving twice is no change


def test_kernel_choice_on_a_tpu_is_bf16(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _kernel_choice("auto", "auto", 8) == ("nodematmul", "bf16")
    assert _kernel_choice("auto", "f32", 8) == ("nodematmul", "f32")


@pytest.mark.parametrize("kernel,dtype", [("factorized", "auto"), ("auto", "f16")])
def test_kernel_choice_refuses_a_name_it_does_not_know(kernel, dtype):
    with pytest.raises(ValueError):
        _kernel_choice(kernel, dtype, 8)


def _mk(n, f, k, b1, seed, frac_inactive=0.0, empty_node=None):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, b1, size=(n, f)).astype(np.int32)
    nodes = rng.integers(0, k, size=n).astype(np.int32)
    if empty_node is not None:
        nodes[nodes == empty_node] = (empty_node + 1) % k
    if frac_inactive:
        nodes[rng.random(n) < frac_inactive] = -1
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.1
    return bins, nodes, g, h


@pytest.mark.parametrize("kernel,rtol,atol", KERNELS)
@pytest.mark.parametrize(
    "n,f,k,b1,row_tile",
    [
        (1000, 5, 4, 17, 128),
        (513, 3, 1, 9, 256),      # single node, non-divisible rows
        (2048, 7, 8, 33, 512),
        (900, 11, 4, 17, 128),    # features not a multiple of the 8-wide block
    ],
)
def test_parity(n, f, k, b1, row_tile, kernel, rtol, atol):
    bins, nodes, g, h = _mk(n, f, k, b1, seed=n)
    want = np.asarray(_shard_histogram(bins, nodes, g, h, k, b1))
    got = np.asarray(
        build_histogram_pallas(
            bins, nodes, g, h, k, b1, row_tile=row_tile, interpret=INTERPRET,
            kernel=kernel,
        )
    )
    assert got.shape == want.shape == (k, f, b1, 3)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)


@pytest.mark.parametrize("dtype,rtol,atol", [("f32", 1e-5, 1e-4),
                                             ("bf16", 2e-2, 5e-2)])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("built,slots", [(1, 1), (2, 2), (3, 4), (16, 16),
                                         (9, 16), (32, 32), (17, 32)])
def test_nodematmul_at_the_ladders_new_rungs(
        built, slots, weighted, dtype, rtol, atol):
    """The launches the node ladder gained at ISSUE 35 (1, 2, 4, 16 and 32
    slots: K*C = 4 to 128 operand columns): ``built`` nodes in ``slots``
    slots, as ``pad_nodes`` pads
    them, against the scatter oracle at the built node count; the empty
    slots hold exact zeros. f32 as the interpreter runs a fit, bf16 as the
    chip does."""
    from h2o3_tpu.ops.histogram import pad_nodes

    assert pad_nodes(built) == slots
    bins, nodes, g, h = _mk(1500, 5, built, 17, seed=slots + built,
                            frac_inactive=0.2)
    rw = None
    if weighted:
        rw = (1.0 + np.random.default_rng(built).random(1500)).astype(np.float32)
    want = np.asarray(_shard_histogram(bins, nodes, g, h, built, 17, rw=rw))
    got = np.asarray(build_histogram_pallas(
        bins, nodes, g, h, slots, 17, row_tile=256, interpret=INTERPRET,
        kernel="nodematmul", dtype=dtype, rw=rw))
    assert got.shape == (slots, 5, 17, 3)
    np.testing.assert_allclose(got[:built], want, rtol=rtol, atol=atol)
    assert not got[built:].any(), "an empty slot picked up mass"


def test_nodematmul_operands_sit_behind_a_barrier():
    """The node ids, g, h (and the row weights) reach the kernel's [N, 1] and
    [N, C] operands through one ``optimization_barrier``, so XLA cannot hoist
    those reshapes into the vectors' producers (ISSUE 35); the sorted
    kernel's operands are gathered rows and have none."""
    S = jax.ShapeDtypeStruct
    args = (S((2048, 5), jnp.int32), S((2048,), jnp.int32),
            S((2048,), jnp.float32), S((2048,), jnp.float32))

    def text(kernel, rw):
        return _build_histogram_pallas_jit.lower(
            *args, n_nodes=16, n_bins1=9, row_tile=None, interpret=True, vma=(),
            kernel=kernel, bins_fm=None, rw=rw, dtype="f32").as_text()

    assert text("nodematmul", None).count("optimization_barrier") == 1
    weighted = text("nodematmul", S((2048,), jnp.float32))
    assert weighted.count("optimization_barrier") == 1
    barrier = next(ln for ln in weighted.splitlines() if "optimization_barrier" in ln)
    assert barrier.count("tensor<2048x") >= 4  # ids, g, h, w in, and out
    assert "optimization_barrier" not in text("sorted", None)


@pytest.mark.parametrize("kernel,rtol,atol", KERNELS)
def test_inactive_rows_and_empty_nodes(kernel, rtol, atol):
    bins, nodes, g, h = _mk(
        1500, 4, 6, 13, seed=7, frac_inactive=0.3, empty_node=2
    )
    want = np.asarray(_shard_histogram(bins, nodes, g, h, 6, 13))
    got = np.asarray(
        build_histogram_pallas(
            bins, nodes, g, h, 6, 13, row_tile=128, interpret=INTERPRET,
            kernel=kernel,
        )
    )
    # empty node's slab must be exactly zero, not garbage
    assert np.all(got[2] == 0)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)


@pytest.mark.parametrize("kernel,rtol,atol", KERNELS)
def test_counts_are_exact_integers(kernel, rtol, atol):
    bins, nodes, g, h = _mk(700, 2, 3, 5, seed=3)
    got = np.asarray(
        build_histogram_pallas(bins, nodes, g, h, 3, 5, row_tile=128,
                               interpret=INTERPRET, kernel=kernel)
    )
    counts = got[..., 2]
    np.testing.assert_allclose(counts, np.round(counts))
    assert counts.sum() == 700 * 2


# ---------------------------------------------------------------------------
# the sorted kernel's preparation (``_prep_gathered``): the regime a deep
# level of a sampled fit runs (many node slots, few of them built, most rows
# inactive) and the edges of its per-tile index arithmetic. Each case:
# (n, f, k, b1, row_tile, nodes(rng, n, k), row weights?)


def _few_of_512(rng, n, k):
    """128 built nodes of 512 slots, 65% of the rows inactive."""
    nodes = rng.integers(0, 128, size=n).astype(np.int32)
    nodes[rng.random(n) < 0.65] = -1
    return nodes


def _mostly_inactive(rng, n, k):
    nodes = rng.integers(0, k, size=n).astype(np.int32)
    nodes[rng.random(n) < 0.8] = -1
    return nodes


def _tile_edges(rng, n, k):
    """Node 0 fills exactly two tiles, node 1 exactly one, node 2 has one
    row, node 3 one row more than a tile, node 4 none; the rest inactive."""
    nodes = np.full(n, -1, np.int32)
    at = rng.permutation(n)
    lo = 0
    for node, count in ((0, 256), (1, 128), (2, 1), (3, 129), (5, 127)):
        nodes[at[lo:lo + count]] = node
        lo += count
    return nodes


def _all_inactive(rng, n, k):
    return np.full(n, -1, np.int32)


def _all_active_last_node_short(rng, n, k):
    """Every row active and the last node's last tile short of the end of
    the row order: its window of the order runs into the slack."""
    nodes = rng.integers(0, k, size=n).astype(np.int32)
    nodes[:3] = k - 1
    return nodes


SORTED_CASES = {
    "512_slots_128_built_most_rows_inactive": (3000, 4, 512, 7, 128, _few_of_512, False),
    "over_half_inactive": (2500, 5, 140, 9, 128, _mostly_inactive, False),
    "count_multiple_of_tile_and_single_row": (1100, 3, 130, 6, 128, _tile_edges, False),
    "every_row_inactive": (700, 3, 130, 5, 128, _all_inactive, False),
    "row_weights": (1500, 4, 130, 8, 128, _mostly_inactive, True),
    "rows_not_a_multiple_of_the_tile": (1237, 3, 131, 6, 256, _mostly_inactive, True),
    "all_active_window_reaches_the_slack": (1000, 3, 129, 5, 128,
                                            _all_active_last_node_short, False),
}


@pytest.mark.parametrize("case", sorted(SORTED_CASES))
def test_sorted_preparation(case):
    n, f, k, b1, row_tile, mk_nodes, weighted = SORTED_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    bins = rng.integers(0, b1, size=(n, f)).astype(np.int32)
    nodes = mk_nodes(rng, n, k)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.1
    rw = (rng.integers(1, 4, size=n).astype(np.float32) if weighted else None)
    want = np.asarray(_shard_histogram(bins, nodes, g, h, k, b1, rw=rw))
    got = np.asarray(build_histogram_pallas(
        bins, nodes, g, h, k, b1, row_tile=row_tile, interpret=INTERPRET,
        kernel="sorted", rw=rw))
    assert got.shape == want.shape == (k, f, b1, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    # a node without rows: its slab exactly zero, never left undefined
    empty = np.setdiff1d(np.arange(k), nodes[nodes >= 0])
    assert empty.size or case.startswith("all_active")
    assert np.all(got[empty] == 0)
    # counts (sums of the integer weights) are exact integers, and whole
    counts = got[..., 2]
    assert np.array_equal(counts, np.round(counts))
    active = nodes >= 0
    assert counts.sum() == f * (rw[active].sum() if weighted else active.sum())


def test_sorted_preparation_moves_rows_once_and_never_scatters():
    """The lowered sorted level holds no scatter and no more row gathers
    than operands: the padded layout is read, not written, and every other
    lookup is per tile or per node, never per row."""
    n, f, k, b1, r = 8192, 6, 130, 9, 128
    t_max = n // r + k
    S = jax.ShapeDtypeStruct
    text = _build_histogram_pallas_jit.trace(
        S((n, f), jnp.int32), S((n,), jnp.int32), S((n,), jnp.float32),
        S((n,), jnp.float32), k, b1, r, False, (), "sorted", None,
        S((n,), jnp.float32), "bf16",
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert "scatter" not in text
    gathers = re.findall(
        r'"stablehlo\.gather".*slice_sizes = array<i64: ([\d, ]+)>.*'
        r': \(tensor<[^>]*>, tensor<(\d+)x1xi32>\) -> tensor<([^>]*)>', text)
    assert gathers
    per_row = [(sizes, out) for sizes, n_idx, out in gathers if int(n_idx) >= n]
    # 9 bin values: 4 bits a code, the 6 codes share a word; g|h and w
    assert per_row == [("1, 3", "%dx3xi32" % (t_max * r))]
    # the rest read small tables, at most once a tile
    assert all(int(n_idx) <= t_max for _, n_idx, _ in gathers
               if int(n_idx) < n)


@pytest.mark.parametrize("weighted", [False, True])
def test_sorted_level_per_shard_under_shard_map(mesh, weighted):
    """The four-chip path: each shard prepares and builds its own sorted
    level inside ``shard_map`` (``vma``), then the psum merges them."""
    from h2o3_tpu.ops.histogram import build_histogram_sharded

    n, f, k, b1 = 4096, 5, 130, 9
    rng = np.random.default_rng(11)
    bins = rng.integers(0, b1, size=(n, f)).astype(np.int32)
    nodes = _mostly_inactive(rng, n, k)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.1
    rw = rng.integers(1, 4, size=n).astype(np.float32) if weighted else None
    want = np.asarray(_shard_histogram(bins, nodes, g, h, k, b1, rw=rw))
    got = np.asarray(build_histogram_sharded(
        jnp.asarray(bins), jnp.asarray(nodes), jnp.asarray(g), jnp.asarray(h),
        k, b1, mesh=mesh, impl="pallas",
        rw=None if rw is None else jnp.asarray(rw)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert np.array_equal(got[..., 2], np.round(got[..., 2]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_bins1,f", [(2, 3), (21, 28), (32, 7), (256, 9),
                                       (257, 28), (1025, 4)])
def test_packed_row_round_trip(n_bins1, f, dtype):
    """The gathered row carries codes and g, h, w bit for bit at every bin
    count's code width, and a code out of range stays one that matches no
    bin (the kernel's one-hot then adds nothing for it, as before)."""
    n = 300
    rng = np.random.default_rng(n_bins1)
    bins = rng.integers(0, n_bins1, size=(n, f)).astype(np.int32)
    bins[0, 0], bins[1, f - 1], bins[2, 0] = -1, n_bins1, n_bins1 + 1000
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.1
    w = rng.integers(1, 5, size=n).astype(np.float32)
    rows = _pack_row(jnp.asarray(bins), g, h, w, n_bins1, dtype)
    bits, per, n_words = _code_words(n_bins1, f)
    assert n_bins1 < 1 << bits and per * bits <= 32 and n_words * per >= f
    assert rows.shape == (n, n_words + (2 if dtype == jnp.bfloat16 else 3))
    codes, vals = _unpack_row(rows, f, n_bins1, dtype)
    codes = np.asarray(codes)
    in_range = (bins >= 0) & (bins < n_bins1)
    assert np.array_equal(codes[in_range], bins[in_range])
    assert in_range.sum() == bins.size - 3 and np.all(codes[~in_range] >= n_bins1)
    want = jnp.stack([g, h, w, jnp.zeros_like(g)], axis=1).astype(dtype)
    assert vals.dtype == dtype and np.array_equal(np.asarray(vals), np.asarray(want))
