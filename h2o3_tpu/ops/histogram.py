"""Gradient-histogram construction — the tree-training hot kernel.

Reference: ``hex/tree/DHistogram.java:433`` (updateHisto: accumulate
{Σw, Σwy, Σwy²} per (leaf, column, bin) in a flat double[]), built per node
tree-level by ``ScoreBuildHistogram2`` (``tree/ScoreBuildHistogram2.java:
273-280,385-396``) as a two-stage pass: per-thread private histograms, then a
shared atomic merge, then a cross-node MRTask reduce. The XGBoost extension
does the same thing on GPU inside ``grow_gpu_hist`` (native, §2.3 of
SURVEY.md).

TPU-native redesign (the "tpu_hist" kernel):
  * features are pre-quantized to int bin codes (global quantile binning like
    XGBoost hist / H2O ``histogram_type=QuantilesGlobal``) — static shapes,
    uint8-sized codes, NA gets a dedicated trailing bin;
  * per device shard, the (node, feature, bin) histogram of (grad, hess,
    count) is ONE fused scatter-add into a zeros array — the shard-private
    histogram, exactly ScoreBuildHistogram2's private stage;
  * the cross-device merge is ``lax.psum`` over the data axis — the MRTask
    reduce, emitted by XLA as a log-depth ICI collective.

A Pallas VMEM-resident variant lives in h2o3_tpu/ops/pallas_histogram.py;
this module is the portable XLA path and the correctness oracle.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Optional, Tuple  # noqa: F401

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from h2o3_tpu.parallel.mesh import DATA_AXIS
from h2o3_tpu.util import telemetry


# ---------------------------------------------------------------------------
# the node ladder: how many node slots a level's call launches
#
# Every call sits inside the traced block of trees, where a level pays for
# the slots it launches and not for the nodes it builds: on a v5e the
# node-matmul kernel took 47.6 / 49.2 / 52.5 / 60.0 / 67.9 / 152.3 ms at
# 1 / 2 / 4 / 8 / 16 / 64 slots of 256 bins (PERF.md section 6, PR 35). So
# up to 64 nodes the ladder is the node count itself, rounded up to a power
# of two (a tree's levels build powers of two, so nothing of theirs is
# padded), and one rung remains above it, 512: levels of 65 to 512 nodes
# (levels 8 and 9 of a depth-10 tree build 128 and 256) run the sorted
# kernel, whose cost is its preparation and hardly its slots (un-padding
# drops at most 3% of the kernel: PR 29). A rung of 128 would move level 8
# to the node-matmul kernel: which kernel a level gets is
# ``pallas_histogram._kernel_choice``'s question and waits for the sorted
# levels' own work (ROADMAP S2, S3).
# Pad rows are zero-filled (a scatter-add / one-hot contraction never
# touches a node id beyond the real range) and the real ``n_nodes`` rows are
# sliced back out, so the result is bit-identical to the unpadded build.
# Past 512 a call runs unpadded; no tree level builds more nodes than that:
# a level past it is a frontier level (``booster.frontier_start``).

#: the top of the node ladder, the most nodes a dense level builds
MAX_DENSE_NODES = 512
_NODE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, MAX_DENSE_NODES)

PLAN_CACHE = telemetry.counter(
    "hist_plan_cache_total",
    "histogram level-plan lookups against the padded-bucket jit cache",
    labels=("impl", "result"),
)

_PLAN_LOCK = threading.Lock()
_PLAN_KEYS: set = set()


def pad_nodes(n_nodes: int) -> int:
    """Smallest ladder bucket >= ``n_nodes`` (identity above the ladder)."""
    for b in _NODE_BUCKETS:
        if n_nodes <= b:
            return b
    return n_nodes


def _shape_sig(arrays) -> Tuple:
    return tuple(
        None if a is None else (tuple(a.shape), str(a.dtype)) for a in arrays
    )


def _note_plan(key: Tuple, impl: str) -> None:
    """Meter a plan-cache lookup: ``miss`` the first time a jit cache key
    is seen by this process, ``hit`` after — the bench asserts warm tree
    levels are all hits (compile-free) instead of inferring it from walls.
    ``impl`` names the implementation the plan was traced with, so a run
    can prove which one it used."""
    with _PLAN_LOCK:
        seen = key in _PLAN_KEYS
        if not seen:
            _PLAN_KEYS.add(key)
    PLAN_CACHE.inc(impl=impl, result="hit" if seen else "miss")


# ---------------------------------------------------------------------------
# quantile binning (GlobalQuantilesCalc / XGBoost sketch analogue)


def na_code(nbins: int, cat_levels=()) -> int:
    """The NA bucket's code, the last of the bin axis: ``nbins`` for a
    frame of numeric features, and the widest feature's bin count where a
    categorical column has more levels than that (every feature is padded
    to the widest, so one code stands for NA in all of them)."""
    return max([int(nbins), *(int(v) for v in cat_levels)])


def make_bins(
    X, nbins: int = 256, sample: int = 200_000, seed: int = 0,
    cat_levels=(),
) -> np.ndarray:
    """Per-feature bin edges from (sampled) quantiles. Returns [F, nbins-1]
    interior edges; value -> bin = searchsorted(edges, v, 'right').

    X: an ``[N, F]`` array, or deferred rows with ``shape`` and
    ``rows(idx)`` (``models/tree/common.TreeRows``), of which only the
    ``sample`` rows drawn are read; the draw and the edges are the same.
    cat_levels: per feature, the number of levels of a categorical column
    that is binned a bin a level (``categorical_encoding="enum"``) and 0 for
    a numeric one; empty for a frame with none.  A categorical feature has
    no sketch: its code is its level, and its row of edges is never read
    (+inf throughout)."""
    n, F = X.shape
    idx = None  # every row
    if n > sample:
        idx = np.random.default_rng(seed).choice(n, sample, replace=False)
    if hasattr(X, "rows"):
        Xs = X.rows(idx)
    else:
        Xs = X if idx is None else X[idx]
    qs = np.linspace(0, 1, nbins + 1)[1:-1]
    edges = np.empty((F, nbins - 1), dtype=np.float64)
    for f in range(F):
        if len(cat_levels) and cat_levels[f]:
            edges[f] = np.inf
            continue
        col = Xs[:, f]
        col = col[~np.isnan(col)]
        if col.size == 0:
            edges[f] = np.arange(nbins - 1, dtype=np.float64)
            continue
        distinct = np.unique(col)
        if len(distinct) <= nbins:
            # low-cardinality (incl. one-hot indicators): exact midpoint
            # edges give every distinct value its own bin — data quantiles
            # would collapse rare values (e.g. a 3%-frequency indicator)
            # into their neighbor's bin and make them unsplittable
            mids = (distinct[:-1] + distinct[1:]) / 2.0
            e = np.full(nbins - 1, np.inf)  # inf pad: never <= any value
            e[: len(mids)] = mids
            edges[f] = e
            continue
        e = np.quantile(col, qs)
        # de-duplicate while keeping monotonicity (constant-ish features)
        e = np.maximum.accumulate(e)
        edges[f] = e
    return edges


def _apply_bins_batched(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Vectorized per-row searchsorted (no Python loop over features).

    One stable argsort of the per-feature ``[edges | values]`` concatenation
    ranks every value against its own feature's edges in a single batched
    pass: with edges FIRST and the sort stable, an equal edge sorts before
    the value, so the running edge count at a value's sorted position is
    exactly ``searchsorted(edges[f], x, side="right")`` — float64-exact
    (ties, ±inf and NaN-last included). Row chunks bound the workspace.
    """
    n, F = X.shape
    E = edges.shape[1]
    out = np.empty((n, F), dtype=np.int32)
    rows = np.arange(F)[:, None]
    chunk = max(1, 4_000_000 // max(F, 1))
    for s in range(0, n, chunk):
        xb = X[s:s + chunk].T  # [F, m]
        comb = np.concatenate([edges, xb], axis=1)  # [F, E+m]
        order = np.argsort(comb, axis=1, kind="stable")
        is_val = order >= E
        edges_before = np.cumsum(~is_val, axis=1)  # edges at/before position
        blk = np.empty(xb.shape, dtype=np.int32)
        blk[np.broadcast_to(rows, order.shape)[is_val],
            order[is_val] - E] = edges_before[is_val]
        out[s:s + chunk] = blk.T
    return out


#: ``apply_bins`` bins a feature a thread, up to this many, from this many cells
_BIN_THREADS = 16
_BIN_THREADS_MIN_CELLS = 1 << 22


def apply_bins(X: np.ndarray, edges: np.ndarray, cat_levels=()) -> np.ndarray:
    """Quantize raw features to bin codes [N, F] int8-range; NA -> the last
    bucket (``na_code``: ``nbins`` where no feature is categorical).

    cat_levels (see ``make_bins``): a categorical feature's code is its
    level; NA, and a level the fit did not know (a code outside
    ``0..levels-1``), take the NA bucket.

    Implementation is measurement-dispatched (single-core CPU numbers, see
    PR notes): for tall matrices — the booster shape, e.g. 1M x 28 — the
    per-feature ``np.searchsorted`` loop IS the fastest exact kernel
    (binary search over L1-resident edges beats every batched formulation:
    argsort ~0.7x, pooled-rank ~0.6x, broadcast-count ~0.3x, grid-bucketed
    ~0.7x, jnp/f32 ~0.7x AND inexact), while for wide-short matrices the
    per-call overhead of F tiny searchsorteds dominates and the batched
    argsort path wins (n=8, F=5000: ~1.8x). Both paths are bit-exact
    against the per-feature formulation; the hot repeat-fit case no longer
    reaches either — the device frame cache serves the bin codes resident.
    """
    X = np.asarray(X)
    n, F = X.shape
    cats = {f for f in range(F) if len(cat_levels) and cat_levels[f]}
    na = na_code(edges.shape[1] + 1, cat_levels)
    if n == 0 or F == 0:
        return np.empty((n, F), dtype=np.int32)
    with telemetry.Span("apply_bins", rows=n, features=F):
        if F > 32 * max(n, 1) and not cats:  # wide-short: loop overhead dominates
            out = _apply_bins_batched(X, edges)
            out[np.isnan(X)] = na  # NA bucket (DHistogram NA bin at end)
        else:
            out = np.empty((n, F), dtype=np.int32)

            def one(f):
                col = X[:, f]
                if f in cats:
                    known = (col >= 0) & (col < cat_levels[f])  # NaN: False
                    out[:, f] = np.where(known, col, na)
                else:
                    out[:, f] = np.searchsorted(edges[f], col, side="right")
                out[np.isnan(col), f] = na  # NA bucket (DHistogram NA bin at end)

            # a feature's pass touches no other's cells, and searchsorted and
            # the copies release the interpreter: a matrix large enough to pay
            # for the threads takes them (32M x 13 on one: 30 s of a fit's
            # first touch of a frame)
            workers = min(F, os.cpu_count() or 1, _BIN_THREADS)
            if workers < 2 or n * F < _BIN_THREADS_MIN_CELLS:
                for f in range(F):
                    one(f)
            else:
                with ThreadPoolExecutor(workers) as pool:
                    list(pool.map(one, range(F)))
    return out


# ---------------------------------------------------------------------------
# histogram-partial payload guard (distributed training)


class HistPartialTooLargeError(ValueError):
    """A histogram partial would exceed the RPC frame limit.

    Raised caller-side before a distributed fit starts (worst level of the
    planned tree) and home-side before a partial ships, so the operator
    sees the arithmetic and the remediation instead of a transport
    ``MAX_FRAME_BYTES`` failure mid-level."""

    def __init__(self, what: str, nbytes: int, limit: int,
                 n_classes: int, n_nodes: int, n_features: int,
                 n_bins1: int) -> None:
        self.nbytes = int(nbytes)
        self.limit = int(limit)
        super().__init__(
            f"histogram partial for {what} is {nbytes} bytes "
            f"({n_classes} classes x {n_nodes} nodes x {n_features} "
            f"features x {n_bins1} bins x 3 channels x 8 bytes) "
            f"but the RPC frame limit leaves {limit}; lower "
            f"H2O3_TPU_TREE_BLOCK to ship fewer class trees per level, "
            f"or reduce max_depth / nbins")


def guard_hist_payload(what: str, n_classes: int, n_nodes: int,
                       n_features: int, n_bins1: int) -> int:
    """Raise :class:`HistPartialTooLargeError` if a ``(classes, nodes,
    features, bins, 3)`` float64 partial cannot fit one RPC frame.
    Returns the payload size in bytes."""
    nbytes = int(n_classes) * int(n_nodes) * int(n_features) \
        * int(n_bins1) * 3 * 8
    # lazy: ops must stay importable without the cluster package loaded
    from h2o3_tpu.cluster import transport

    limit = max(0, int(transport.MAX_FRAME_BYTES) - (1 << 16))
    if nbytes > limit:
        raise HistPartialTooLargeError(
            what, nbytes, limit, n_classes, n_nodes, n_features, n_bins1)
    return nbytes


# ---------------------------------------------------------------------------
# the scatter-add histogram


def _shard_histogram(bins, nodes, g, h, n_nodes: int, n_bins1: int, rw=None):
    """Shard-private histogram: [K, F, B+1, 3] of (Σg, Σh, Σw); of the class
    trees of a round ([C, N] nodes, g, h), [C * K, F, B+1, 3], class-major.

    rw: optional [N] per-row count weight — the third channel becomes the
    weighted observation count (DHistogram Σw), so min_rows sees weighted
    counts under a weights_column. None keeps raw row counts."""
    if nodes.ndim == 2:
        # K class trees ([K, N] nodes, g, h): class k's node j is slot
        # k * n_nodes + j of one scatter over the rows of every class
        k = nodes.shape[0]
        offset = n_nodes * jnp.arange(k, dtype=nodes.dtype)[:, None]
        nodes = jnp.where(nodes >= 0, nodes + offset, -1).reshape(-1)
        bins = jnp.tile(bins, (k, 1))
        g, h = g.reshape(-1), h.reshape(-1)
        rw = None if rw is None else jnp.tile(rw, k)
        n_nodes = k * n_nodes
    n, F = bins.shape
    valid = nodes >= 0
    node = jnp.where(valid, nodes, 0)
    flat = (node[:, None] * F + jnp.arange(F, dtype=jnp.int32)[None, :]) * n_bins1 + bins
    w = valid.astype(g.dtype)
    cw = w if rw is None else w * rw
    # one 1-D scatter per channel: scatter updates must stay 1-D — any
    # [N*F, 3] (or batched [3, N*F]) update tensor gets canonicalized by
    # XLA:TPU into a copy whose 3-lane axis pads to 128 (≈42x HBM blowup;
    # observed as a 28.6 GB allocation at N=2M, F=28)
    flat = flat.reshape(-1)
    size = n_nodes * F * n_bins1
    chans = []
    for v in (g * w, h * w, cw):
        upd = jnp.broadcast_to(v[:, None], (n, F)).reshape(-1)
        chans.append(jnp.zeros(size, g.dtype).at[flat].add(upd))
    hist = jnp.stack(chans, axis=0)
    return jnp.moveaxis(hist.reshape(3, n_nodes, F, n_bins1), 0, -1)


def _shard_node_totals(nodes, g, h, n_nodes: int, rw=None):
    """Per-node (Σg, Σh, Σw) [K, 3] — one masked 1-D scatter-add per channel.

    The terminal tree level needs only these totals (leaf values), not the
    full per-(feature, bin) histogram: splitting is impossible at max
    depth, so the [K, F, B+1, 3] build there would be pure waste — and it
    is the widest (most expensive) level of the whole tree.

    Scatter (not a one-hot contraction): a scatter-add accumulates per
    destination index in a capacity-independent order, so a node dimension
    padded to the bucket ladder stays bit-identical to the unpadded build —
    a dot_general's blocking (and with it the float accumulation order)
    shifts with the padded K."""
    valid = nodes >= 0
    node = jnp.where(valid, nodes, 0)  # masked rows add an exact 0.0 below
    w = valid.astype(g.dtype)
    cw = w if rw is None else w * rw
    chans = [
        jnp.zeros(n_nodes, g.dtype).at[node].add(v)
        for v in (g * w, h * w, cw)
    ]
    return jnp.stack(chans, axis=1)  # [K, 3]


def node_totals_sharded(nodes, g, h, n_nodes: int, mesh=None, rw=None):
    """Distributed per-node totals: shard-private contraction + psum.

    The node dimension is padded up the node ladder (``pad_nodes``); node
    ids never reach the pad columns, so slicing the real rows back out is
    bit-identical."""
    k_pad = pad_nodes(n_nodes)
    _note_plan(
        ("totals", k_pad, _shape_sig((nodes, g, h, rw)), mesh), "scatter")
    if mesh is None:
        out = _shard_node_totals(nodes, g, h, k_pad, rw=rw)
        return out[:n_nodes] if k_pad != n_nodes else out

    extras = [] if rw is None else [rw]

    def fn(nd, gg, hh, *rest):
        part = _shard_node_totals(
            nd, gg, hh, k_pad, rw=rest[0] if rest else None
        )
        return jax.lax.psum(part, DATA_AXIS)

    out = _shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS))
        + tuple(P(DATA_AXIS) for _ in extras),
        out_specs=P(),
    )(nodes, g, h, *extras)
    return out[:n_nodes] if k_pad != n_nodes else out


def _hist_impl(impl: Optional[str]) -> str:
    """Resolve histogram implementation: Pallas MXU kernel on TPU, XLA
    scatter elsewhere. Override with H2O3_TPU_HIST_IMPL=scatter|pallas."""
    impl = impl or os.environ.get("H2O3_TPU_HIST_IMPL") or (
        "pallas" if jax.default_backend() == "tpu" else "scatter"
    )
    if impl not in ("scatter", "pallas"):
        raise ValueError(
            f"H2O3_TPU_HIST_IMPL must be 'scatter' or 'pallas', got {impl!r}"
        )
    return impl


def _one_shard_histogram(
    bins, nodes, g, h, n_nodes, n_bins1, impl, vma=(), bins_fm=None, rw=None,
):
    if impl == "pallas":
        from h2o3_tpu.ops.pallas_histogram import build_histogram_pallas

        # kernel and operand precision are the Pallas module's to choose
        return build_histogram_pallas(
            bins, nodes, g, h, n_nodes, n_bins1,
            interpret=jax.default_backend() != "tpu", vma=vma, bins_fm=bins_fm,
            rw=rw,
        )
    return _shard_histogram(bins, nodes, g, h, n_nodes, n_bins1, rw=rw)


def build_histogram_sharded(
    bins, nodes, g, h, n_nodes: int, n_bins1: int, mesh=None,
    impl: Optional[str] = None, bins_fm=None, rw=None,
):
    """Full distributed histogram: private scatter-add per shard, psum merge.

    bins:[N,F] int32 row-sharded; nodes:[N] int32 (-1 = inactive row);
    g,h:[N] float32; or nodes, g, h [K, N] of the K class trees of a round,
    one histogram of their (class, node) slots, [K * n_nodes, ...]
    class-major (``class_launches`` says how many launches it takes).
    bins_fm: optional feature-major [F, N] copy of bins
    (already padded to the kernel row tile) — callers in a training loop pass
    it so the pallas path skips a per-call transpose. rw: optional [N]
    per-row count weight (weights_column: the count channel reports Σw).
    Returns replicated [n_nodes, F, n_bins1, 3].

    The node dimension is padded up the node ladder (``pad_nodes``: the
    powers of two up to 64, then 512) before the jit call and the real
    ``n_nodes`` rows are sliced back out. A level pays for the slots it
    launches, so up to 64 the ladder follows the node count (no floor: one
    slot costs a fifth less than eight); the 512 rung keeps levels of 65 to
    512 nodes on the sorted kernel, which hardly pays for its slots (the
    comment at ``_NODE_BUCKETS`` has the readings).
    """
    impl = _hist_impl(impl)
    k_pad = pad_nodes(n_nodes)
    _note_plan((
        "hist", k_pad, n_bins1, _shape_sig((bins, nodes, g, h, bins_fm, rw)),
        mesh, impl,
    ), impl)
    out = _build_histogram_jit(
        bins, nodes, g, h, bins_fm, rw, k_pad, n_bins1, mesh, impl)
    if k_pad == n_nodes:
        return out
    if nodes.ndim == 2:  # each class tree's real nodes
        return out.reshape(nodes.shape[0], k_pad, *out.shape[1:])[:, :n_nodes].reshape(
            -1, *out.shape[1:])
    return out[:n_nodes]


def class_launches(n_nodes: int, n_classes: int, impl: Optional[str] = None) -> int:
    """Histogram launches of a level that builds ``n_nodes`` nodes in each of
    ``n_classes`` class trees (``build_histogram_sharded`` with [K, N] node
    ids): one, the scatter over every class's rows or the node-matmul kernel
    over the (class, node) slots of all, while that kernel holds them
    (``pallas_histogram._kernel_choice``); past it, one a class."""
    if n_classes == 1 or _hist_impl(impl) != "pallas":
        return 1
    from h2o3_tpu.ops.pallas_histogram import _kernel_choice

    one = _kernel_choice("auto", "auto", n_classes * pad_nodes(n_nodes))[0] == "nodematmul"
    return 1 if one else n_classes


def build_frontier_histogram_sharded(
    codes, slots, g, h, n_slots: int, n_bins1: int, mesh=None,
    impl: Optional[str] = None, rw=None,
):
    """The histogram of a frontier level (a tree level past the node
    ladder): [n_slots, m, n_bins1, 3] of (Σg, Σh, Σw), where each node is
    histogrammed over its own m features alone. codes: [N, m] int32, a row's
    codes of its node's features; slots: [N] int32, the row's node's slot
    (``n_slots``: the row adds nothing). Shard-private, then psum.

    Pallas: ``pallas_histogram.build_frontier_histogram_pallas`` (rows in
    slot order, tiles over runs of slots, no padding a node); the XLA
    scatter path is the oracle: the dense scatter with a row's m codes in
    place of its F."""
    impl = _hist_impl(impl)
    _note_plan(("frontier", n_slots, n_bins1,
                _shape_sig((codes, slots, g, h, rw)), mesh, impl), impl)
    with jax.named_scope("hist_frontier"):
        return _build_frontier_jit(codes, slots, g, h, rw, n_slots, n_bins1, mesh, impl)


def _one_shard_frontier(codes, slots, g, h, n_slots, n_bins1, impl, vma=(), rw=None):
    if impl == "pallas":
        from h2o3_tpu.ops.pallas_histogram import build_frontier_histogram_pallas

        return build_frontier_histogram_pallas(
            codes, slots, g, h, n_slots, n_bins1,
            interpret=jax.default_backend() != "tpu", vma=vma, rw=rw)
    nodes = jnp.where(slots < n_slots, slots, -1)
    return _shard_histogram(codes, nodes, g, h, n_slots, n_bins1, rw=rw)


@partial(jax.jit, static_argnames=("n_slots", "n_bins1", "mesh", "impl"))
def _build_frontier_jit(codes, slots, g, h, rw, n_slots: int, n_bins1: int,
                        mesh, impl: str):
    if mesh is None:
        return _one_shard_frontier(codes, slots, g, h, n_slots, n_bins1, impl, rw=rw)
    extras = [] if rw is None else [rw]

    def fn(c, s, gg, hh, *rest):
        part = _one_shard_frontier(c, s, gg, hh, n_slots, n_bins1, impl,
                                   vma=(DATA_AXIS,), rw=rest[0] if rest else None)
        with jax.named_scope("hist_psum"):
            return jax.lax.psum(part, DATA_AXIS)

    interpreted = impl == "pallas" and jax.default_backend() != "tpu"
    return _shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS))
        + tuple(P(DATA_AXIS) for _ in extras),
        out_specs=P(),
        check_vma=not interpreted,
    )(codes, slots, g, h, *extras)


@partial(jax.jit, static_argnames=("n_nodes", "n_bins1", "mesh", "impl"))
def _build_histogram_jit(
    bins, nodes, g, h, bins_fm, rw, n_nodes: int, n_bins1: int, mesh,
    impl: str,
):
    if mesh is None:
        return _one_shard_histogram(
            bins, nodes, g, h, n_nodes, n_bins1, impl, bins_fm=bins_fm, rw=rw,
        )

    # optional row-sharded / feature-major extras enter the shard_map only
    # when present so the base program is unchanged without them
    extras = []
    if bins_fm is not None:
        extras.append(("bins_fm", bins_fm, P(None, DATA_AXIS)))
    if rw is not None:
        extras.append(("rw", rw, P(DATA_AXIS)))

    def fn(b, nd, gg, hh, *rest):
        kw = dict(zip([name for name, _, _ in extras], rest))
        part = _one_shard_histogram(
            b, nd, gg, hh, n_nodes, n_bins1, impl, vma=(DATA_AXIS,), **kw
        )
        with jax.named_scope("hist_psum"):
            return jax.lax.psum(part, DATA_AXIS)

    # interpreter-mode pallas lowers VMEM scratch to plain arrays whose
    # varying-axis metadata can't match the shard-varying values written
    # into them; the check only exists to validate collective placement,
    # which the real-TPU path still enforces
    interpreted = impl == "pallas" and jax.default_backend() != "tpu"
    # a row's node ids and channels: [N], or [K, N] of K class trees
    row = P(DATA_AXIS) if nodes.ndim == 1 else P(None, DATA_AXIS)
    return _shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), row, row, row)
        + tuple(spec for _, _, spec in extras),
        out_specs=P(),
        check_vma=not interpreted,
    )(bins, nodes, g, h, *[a for _, a, _ in extras])
