"""The tpu_hist booster core — histogram GBDT shared by GBM/DRF/XGBoost.

Reference architecture being re-designed (not translated):
  * driver loop: ``hex/tree/SharedTree.java:208-210,440-469`` (iterate trees ×
    scoreAndBuildTrees, k trees per class);
  * per-level fused pass: ``hex/tree/ScoreBuildHistogram2.java`` (re-assign
    rows to new leaves + accumulate histograms);
  * split search over bins: ``hex/tree/DTree.java`` (UndecidedNode.bestCol);
  * XGBoost-style second-order machinery: ``h2o-extensions/xgboost``'s native
    ``grow_gpu_hist`` updater (``XGBoostModel.java:382-394``), Rabit allreduce
    replaced by ``lax.psum`` (SURVEY.md §2.3).

TPU-native design decisions (device-resident, round 2 rewrite):
  * global quantile binning once per training run (static uint8-range codes)
    — the reference's ``histogram_type=QuantilesGlobal`` made the default,
    because per-leaf re-binning (UniformAdaptive) implies dynamic shapes;
  * the ENTIRE tree build is one traced program: levels are unrolled inside
    the trace with per-level static node capacity (level d has exactly 2^d
    slots), so histogram/split/route for a whole tree — and a whole block of
    trees via ``lax.scan`` — compile to a single XLA executable.  Bins, g/h,
    row→node assignment and the margin never leave the device; the host sees
    tree arrays only at block boundaries (score_tree_interval granularity),
    exactly where the reference's driver scores (``SharedTree.java:440``);
  * gradients/hessians are computed on device from the distribution family
    (``hex/Distribution.java`` analogue) inside the same program;
  * row/column subsampling and per-node mtries draw from ``jax.random`` keys
    folded per (block, tree, level) — reproducible under jit;
  * the histogram is a shard-private scatter-add (or Pallas MXU kernel on
    TPU) + psum (h2o3_tpu/ops/histogram.py);
  * NA routing learns a per-split default direction by evaluating the NA
    bucket on both sides (DHistogram's trailing NA bin, XGBoost default-dir).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Callable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from h2o3_tpu.ops import histogram as _histogram
from h2o3_tpu.ops.bitpack import field_bits, pack_words, unpack_words, word_layout
from h2o3_tpu.ops.histogram import (
    _hist_impl,
    apply_bins,
    build_frontier_histogram_sharded,
    build_histogram_sharded,
    make_bins,
    na_code,
    pad_nodes,
)
from h2o3_tpu.parallel.mesh import default_mesh, row_sharding
from h2o3_tpu.util import telemetry
from h2o3_tpu.util.telemetry import Span

TREE_FRONTIER_NODES = telemetry.counter(
    "tree_frontier_nodes_total",
    "live nodes of the frontier levels (levels past the dense node ladder, "
    "each node histogrammed over its own mtries features) of the trees read "
    "back from the device",
)

TREE_FRONTIER_ROW_GATHERS = telemetry.counter(
    "tree_frontier_row_gathers_total",
    "row-sized gathers the frontier levels of the tree blocks made: the "
    "levels of every tree times the gathers a level (the tree_block span's "
    "frontier_row_gathers)",
)

TREE_SPLITS = telemetry.counter(
    "tree_splits_total",
    "split nodes of the trees read back from the device, by what the split "
    "tests: membership in a set of a categorical's levels, or a threshold",
    labels=("kind",),
)

HIST_LAUNCHES = telemetry.counter(
    "tree_hist_launches_total",
    "histogram kernel launches of the tree blocks run, as their level plans "
    "state them (a level of K class trees is one launch while one kernel "
    "holds its class x node slots)",
    labels=("kernel",),
)

HIST_PSUM_BYTES = telemetry.counter(
    "tree_hist_psum_bytes_total",
    "bytes of level histograms (and leaf totals) a device of a mesh of "
    "several handed to the per-level psum of the tree blocks it ran; a "
    "one-device mesh sums nothing and adds nothing",
)

#: boosting rounds fused into one XLA program when no monitor is active
#: (overridable via H2O3_TPU_TREE_BLOCK); also the deadline-check cadence
DEFAULT_TREE_BLOCK = 16


def tree_block_size() -> int:
    import os

    return int(os.environ.get("H2O3_TPU_TREE_BLOCK", str(DEFAULT_TREE_BLOCK)))


@dataclass(frozen=True)
class TreeParams:
    ntrees: int = 50
    max_depth: int = 6
    learn_rate: float = 0.1
    nbins: int = 256
    min_rows: float = 1.0
    min_split_improvement: float = 1e-5
    reg_lambda: float = 1.0  # L2 on leaf values (xgboost lambda; GBM uses 0)
    reg_alpha: float = 0.0  # L1 on leaf values
    gamma: float = 0.0  # min loss reduction (xgboost gamma)
    sample_rate: float = 1.0  # row subsample per tree
    col_sample_rate_per_tree: float = 1.0
    mtries: int = -1  # features per split; -1 = all (DRF uses sqrt/thirds)
    seed: int = 42
    #: per tree feature, the levels of a categorical column that splits on
    #: sets of its levels (categorical_encoding="enum") and 0 for a feature
    #: that splits on a threshold; () where no feature is categorical
    cat_levels: Tuple[int, ...] = ()
    #: xgboost's ``min_child_weight``: a split needs Σh >= it on both
    #: children, in place of the count test of ``min_rows``; None keeps the
    #: count test
    min_child_weight: Optional[float] = None
    #: xgboost's ``scale_pos_weight``: g and h of a bernoulli fit's positive
    #: rows times it
    scale_pos_weight: float = 1.0
    #: a bound on every leaf's value, None for none: H2O GBM bounds a
    #: multinomial fit's leaves (``MULTINOMIAL_LEAF_MAX``), whose margins are
    #: exponentiated
    max_abs_leaf: Optional[float] = None

    @property
    def n_bins1(self) -> int:
        """Width of every feature's bin axis, the NA bucket included: all
        features are padded to the widest (``nbins``, or a categorical's
        levels where it has more)."""
        return na_code(self.nbins, self.cat_levels) + 1


class Trees:
    """Heap-layout tree arrays. Node i's children are 2i+1 / 2i+2.

    Per tree: feat[M] int32, split_bin[M] int32, default_left[M] bool,
    is_split[M] bool, leaf[M] f32 (learn-rate scaled), with
    M = 2^(max_depth+1)-1. Stored stacked: [T, M] per field.

    Where a feature is categorical (``cat_levels``, as ``TreeParams`` has
    it) every node also holds ``split_set`` [M, W] uint32, W = ceil(B/32):
    bit ``b & 31`` of word ``b >> 5`` is set iff a row whose code of the
    split feature is b goes left. For a split on a categorical that is the
    set of levels the split search chose, with the NA side's bit for a level
    no row of the node had; for a threshold split it is the range
    ``0..split_bin``; the NA bucket itself follows ``default_left``.
    ``split_bin`` of a set-valued split is the length of the chosen prefix of
    the node's level order, less one, and says nothing without that order:
    read the set. A numeric ensemble holds the five arrays and no sets.

    A tree deep enough to have frontier levels (``frontier_start``) is kept
    as a list of its nodes instead (``deep``): per tree, the nodes that exist
    in heap order — a split node's children side by side — with the five
    fields, ``node`` [L] int32 their heap ids and ``child`` [L] int32 the
    position of a split node's left child (its right child is the next one),
    -1 for a leaf. L is the tree's own size, not 2^(max_depth+1)-1, so the
    trees of an ensemble differ in length and are not stacked.
    """

    #: an ensemble saved before there were sets has neither field
    cat_levels: Tuple[int, ...] = ()
    split_set: Optional[List[np.ndarray]] = None
    #: an ensemble saved before there were frontier levels has none
    node: Optional[List[np.ndarray]] = None
    child: Optional[List[np.ndarray]] = None

    def __init__(self, max_depth: int, n_bins1: int, edges: np.ndarray,
                 cat_levels: Tuple[int, ...] = (), deep: bool = False):
        self.max_depth = max_depth
        self.n_bins1 = n_bins1
        self.edges = edges  # [F, B-1] for re-binning at predict time
        self.cat_levels = tuple(int(v) for v in cat_levels)
        self.feat: List[np.ndarray] = []
        self.split_bin: List[np.ndarray] = []
        self.default_left: List[np.ndarray] = []
        self.is_split: List[np.ndarray] = []
        self.leaf: List[np.ndarray] = []
        if self.cat_levels:
            self.split_set: List[np.ndarray] = []
        if deep:
            self.node: List[np.ndarray] = []
            self.child: List[np.ndarray] = []

    @property
    def deep(self) -> bool:
        return self.child is not None

    def _fields(self) -> Tuple[str, ...]:
        return ("feat", "split_bin", "default_left", "is_split", "leaf") + (
            ("split_set",) if self.cat_levels else ()) + (
            ("node", "child") if self.deep else ())

    def append(self, feat, split_bin, default_left, is_split, leaf,
               split_set=None, node=None) -> None:
        """One tree as the block returned it: heap arrays [M], or for a
        deep tree the heap arrays of its dense levels followed by the slots
        of its frontier levels and ``node``, the slots' heap ids."""
        if self.deep:
            fields = _node_list(*(np.asarray(a) for a in (
                feat, split_bin, default_left, is_split, leaf, node)))
            for name, a in zip(self._fields(), fields):
                getattr(self, name).append(a)
            return
        self.feat.append(np.asarray(feat))
        self.split_bin.append(np.asarray(split_bin))
        self.default_left.append(np.asarray(default_left))
        self.is_split.append(np.asarray(is_split))
        self.leaf.append(np.asarray(leaf))
        if self.cat_levels:
            self.split_set.append(np.asarray(split_set))

    def extend(self, other: "Trees") -> None:
        """Take over another ensemble's trees (checkpoint-continue)."""
        if other.cat_levels != self.cat_levels:
            raise ValueError("checkpoint categorical levels mismatch")
        if other.deep != self.deep:
            raise ValueError("checkpoint tree layout mismatch (frontier levels)")
        for name in self._fields():
            getattr(self, name).extend(getattr(other, name))

    @property
    def ntrees(self) -> int:
        return len(self.feat)

    def bin(self, X: np.ndarray) -> np.ndarray:
        """Raw features to this ensemble's bin codes."""
        return apply_bins(X, self.edges, self.cat_levels)

    def stacked(self):
        return (
            jnp.asarray(np.stack(self.feat)),
            jnp.asarray(np.stack(self.split_bin)),
            jnp.asarray(np.stack(self.default_left)),
            jnp.asarray(np.stack(self.is_split)),
            jnp.asarray(np.stack(self.leaf)),
        )


def no_sets(what: str) -> NotImplementedError:
    """The refusal of a path that reads a split as a threshold."""
    return NotImplementedError(
        f"{what} does not support set-valued splits on categorical "
        "columns (categorical_encoding='enum'); train with "
        "categorical_encoding='label_encoder' or 'one_hot_explicit'")


def refuse_sets(trees: "Trees", what: str) -> None:
    """``what`` reads ``split_bin`` as a threshold of a heap of nodes and
    bins by the edges alone: it can carry neither a split on a set of a
    categorical's levels, nor the codes of a fit that binned a level a bin,
    nor a deep tree's list of nodes, and says so instead of scoring it
    wrong."""
    if getattr(trees, "cat_levels", ()):
        raise no_sets(what)
    refuse_deep(trees, what)


def refuse_deep(trees: "Trees", what: str) -> None:
    """``what`` reads a tree as a heap of 2^(max_depth+1)-1 nodes: a deep
    tree (``Trees.deep``) is a list of its nodes, and is refused by name."""
    if getattr(trees, "child", None) is not None:
        raise NotImplementedError(
            f"{what} does not support trees with frontier levels (max_depth "
            f"{trees.max_depth}: levels past the dense node ladder are kept "
            "as a list of nodes, not a heap); train with max_depth <= 10")


def _node_list(feat, split_bin, default_left, is_split, leaf, node):
    """A deep tree as the block returned it — heap arrays of its dense
    levels, then the slots of its frontier levels with their heap ids in
    ``node`` (-1: an empty slot) — as the nodes that exist, in heap order:
    the five fields, the heap ids and every split node's left child's
    position. A node of the dense levels exists when its parent split."""
    m_dense = len(feat) - len(node)
    reach = np.zeros(m_dense, bool)
    reach[0] = True
    for d in range(1, int(np.log2(m_dense + 1))):
        idx = np.arange(2**d - 1, 2 ** (d + 1) - 1)
        up = (idx - 1) // 2
        reach[idx] = reach[up] & is_split[up]
    live = node >= 0

    def keep(a):
        return np.concatenate([a[:m_dense][reach], a[m_dense:][live]])

    # levels follow one another and a level's slots follow their parents'
    # order, so the ids come in heap order
    ids = np.concatenate([np.flatnonzero(reach), node[live]]).astype(np.int32)
    if np.any(np.diff(ids) <= 0):
        raise RuntimeError("the nodes of a deep tree are not in heap order")
    fields = [keep(a) for a in (feat, split_bin, default_left, is_split, leaf)]
    sp = fields[3].astype(bool)
    child = np.where(sp, np.searchsorted(ids, 2 * ids + 1), -1).astype(np.int32)
    if sp.any() and not (np.array_equal(ids[child[sp]], 2 * ids[sp] + 1)
                         and np.array_equal(ids[child[sp] + 1], 2 * ids[sp] + 2)):
        raise RuntimeError("a split node of a deep tree lacks a child")
    return (*fields, ids, child)


# ---------------------------------------------------------------------------
# device-side objective families (hex/Distribution.java analogue)


def grad_hess_device(objective: str, y, margin):
    """Per-row (g, h) of the loss wrt the margin, traced on device.

    y: [N] labels/targets, or [N, C] fixed targets for objective='fixed'
    (DRF: each tree independently fits the raw targets, so g=-y, h=1 gives a
    Newton leaf equal to the in-leaf target mean). margin: [N, C] f32.

    Parameterized families (hex/Distribution.java analogues) encode their
    parameter in the objective string: ``tweedie:1.5``, ``quantile:0.9``,
    ``huber:<delta>`` — the string is the jit/compile cache key, so each
    parameter value compiles its own program with the constant folded in.
    """
    name, _, arg = objective.partition(":")
    if name == "custom":
        # user objective (udf.register_distribution — the
        # CDistributionFunc analogue): written with jnp ops, so it traces
        # straight into this device program
        from h2o3_tpu.udf import get_distribution

        g, h = get_distribution(arg)["grad_hess"](y, margin[:, 0])
        return (jnp.asarray(g, jnp.float32)[:, None],
                jnp.maximum(jnp.asarray(h, jnp.float32), 1e-16)[:, None])
    if name == "fixed":
        t = y if y.ndim == 2 else y[:, None]
        return -t.astype(jnp.float32), jnp.ones_like(t, dtype=jnp.float32)
    if name == "gaussian":
        g = margin[:, 0] - y
        return g[:, None], jnp.ones_like(g)[:, None]
    if name == "bernoulli":
        p = jax.nn.sigmoid(margin[:, 0])
        return (p - y)[:, None], jnp.maximum(p * (1 - p), 1e-16)[:, None]
    if name == "multinomial":
        p = jax.nn.softmax(margin, axis=1)
        onehot = (y.astype(jnp.int32)[:, None] == jnp.arange(margin.shape[1])[None, :]).astype(
            jnp.float32
        )
        return p - onehot, jnp.maximum(p * (1 - p), 1e-16)
    if name == "poisson":
        mu = jnp.exp(margin[:, 0])
        return (mu - y)[:, None], jnp.maximum(mu, 1e-16)[:, None]
    if name == "gamma":
        # deviance with log link: L = 2(y e^{-f} + f - log y - 1)
        ymf = y * jnp.exp(-margin[:, 0])
        return (1.0 - ymf)[:, None], jnp.maximum(ymf, 1e-16)[:, None]
    if name == "tweedie":
        # log link, 1<p<2: L = -y e^{(1-p)f}/(1-p) + e^{(2-p)f}/(2-p)
        pw = float(arg)
        a = y * jnp.exp((1.0 - pw) * margin[:, 0])
        b = jnp.exp((2.0 - pw) * margin[:, 0])
        g = b - a
        h = (pw - 1.0) * a + (2.0 - pw) * b
        return g[:, None], jnp.maximum(h, 1e-16)[:, None]
    if name == "huber":
        delta = float(arg)
        r = margin[:, 0] - y
        return jnp.clip(r, -delta, delta)[:, None], jnp.ones_like(r)[:, None]
    if name == "laplace":
        g = jnp.sign(margin[:, 0] - y)
        return g[:, None], jnp.ones_like(g)[:, None]
    if name == "quantile" or objective == "quantile_0.5":
        alpha = float(arg) if arg else 0.5
        g = jnp.where(margin[:, 0] < y, -alpha, 1.0 - alpha)
        return g[:, None], jnp.ones_like(g)[:, None]
    raise ValueError(f"unknown objective {objective!r}")


def grad_hess_classes(objective: str, y, margin):
    """(g, h) [C, N] of a fit of C class trees, from its class-major margin
    [C, N]: the multinomial's softmax gradient g = p_k - 1[y = k], h =
    p_k(1 - p_k) (floored at 1e-16), 1 - p_k taken as the other classes'
    share; or the fixed targets [N, C] of a forest (g = -t, h = 1).
    Class-major, so every class's [N] row is lane-dense."""
    if objective == "multinomial":
        C = margin.shape[0]
        k = jnp.arange(C)[:, None]
        e = jnp.exp(margin - margin.max(axis=0, keepdims=True))  # the top class's is 1
        top = jnp.argmax(margin, axis=0)[None, :]
        s = e.sum(axis=0, keepdims=True)
        # 1 - p_k as the other classes' share, summed as such for the top
        # class: a confident row's p is 1 to float32's rounding (6e-8), and
        # 1 - p, its hessian and a correct row's gradient would read 0 where
        # float64 reads 1e-9
        rest = jnp.where(k == top, 0.0, e).sum(axis=0, keepdims=True)
        p = e / s
        q = jnp.where(k == top, rest, s - e) / s
        g = jnp.where(y.astype(jnp.int32)[None, :] == k, -q, p)
        return g, jnp.maximum(p * q, 1e-16)
    if objective == "fixed":
        t = y.T.astype(jnp.float32)
        return -t, jnp.ones_like(t)
    raise ValueError(f"objective {objective!r} has one class tree a round")


# ---------------------------------------------------------------------------
# traced level-step pieces


#: float32's least normal number: a floor on a divisor under which no Σh of
#: a float32 sum falls (a TPU flushes subnormals to zero)
_F32_TINY = float(np.finfo(np.float32).tiny)


def _order_levels(real, cat_idx: Tuple[int, ...], lam, h_floor: float = 1e-12):
    """The categorical features' bins of ``real`` [K, F, B, 3] put in the
    order their prefixes are searched in: levels that hold a row by
    Σg/max(Σh+λ, h_floor) ascending, ties by level, levels with no row
    last. One sort carries the sums along (no gather by the order), over the
    categoricals' slices alone. Returns ``real`` with those slices
    reordered, and for the categoricals [K, Fc, B]: every level's key, the
    sorted keys, the sorted levels, and whether a level holds a row."""
    F = real.shape[1]
    rc = jnp.stack([real[:, f] for f in cat_idx], axis=1)  # [K, Fc, B, 3]
    code = jnp.broadcast_to(jnp.arange(rc.shape[2], dtype=jnp.int32), rc.shape[:3])
    present = rc[..., 2] > 0
    ratio = rc[..., 0] / jnp.maximum(rc[..., 1] + lam, h_floor)
    key = jnp.where(present, jnp.clip(ratio, -3e38, 3e38), jnp.inf)
    key_s, code_s, g_s, h_s, c_s = jax.lax.sort(
        (key, code, rc[..., 0], rc[..., 1], rc[..., 2]), dimension=2, num_keys=2)
    rc = jnp.stack([g_s, h_s, c_s], axis=-1)
    # back beside the numeric features' slices, which keep their code order
    pieces, start = [], 0
    for j, f in enumerate(cat_idx):
        if f > start:
            pieces.append(real[:, start:f])
        pieces.append(rc[:, j:j + 1])
        start = f + 1
    if start < F:
        pieces.append(real[:, start:])
    return jnp.concatenate(pieces, axis=1), key, key_s, code_s, present


def _winner_set(best_f, best_b, dl, cat_idx, key, key_s, code_s, present):
    """left [K, B] bool of the chosen split of every node: does a row whose
    code of the chosen feature is b go left. A level is in the chosen prefix
    iff its (key, level) is at or before the prefix's last, so no inverse of
    the order is needed; the chosen feature's rows are taken by masked sums
    over the few categoricals, not by a gather."""
    B = key.shape[2]
    mine = best_f[:, None] == jnp.asarray(cat_idx, jnp.int32)[None, :]  # [K, Fc]
    is_set = jnp.any(mine, axis=1)
    at_b = jnp.arange(B, dtype=best_b.dtype)[None, :] == best_b[:, None]  # [K, B]

    def of_best(a):  # [K, Fc, B] -> the chosen feature's [K, B]
        return jnp.sum(jnp.where(mine[:, :, None], a, jnp.zeros((), a.dtype)), axis=1)

    def at_best(a):  # [K, B] -> the chosen position's [K, 1]
        return jnp.sum(jnp.where(at_b, a, jnp.zeros((), a.dtype)), axis=1,
                       keepdims=True)

    key_f = of_best(key)
    last_key, last_code = at_best(of_best(key_s)), at_best(of_best(code_s))
    code = jnp.arange(B, dtype=jnp.int32)[None, :]
    in_prefix = (key_f < last_key) | ((key_f == last_key) & (code <= last_code))
    absent = of_best((~present).astype(jnp.int32)) > 0
    in_set = jnp.where(absent, dl[:, None], in_prefix)
    return jnp.where(is_set[:, None], in_set, code <= best_b[:, None])


def _split_search(
    hist, lam, alpha, gamma, lr, feat_mask, min_rows: float, n_bins1: int,
    constraints=None, node_lo=None, node_hi=None, child_stats: bool = False,
    cat_levels: Tuple[int, ...] = (), min_child_weight: Optional[float] = None,
    deep: bool = False, stable_gain: bool = False, tiny_hessians: bool = False,
):
    """Per-node best split over (feature, bin, NA-direction).

    hist: [K, F, B+1, 3] (Σg, Σh, count). Returns per-node arrays:
    feat, bin, default_left, gain, leaf_value (lr-scaled) — plus, in
    monotone mode, the best split's unscaled (left, right) child values.

    child_stats=True additionally returns (wl, wr, left_small): the chosen
    split's unscaled child leaf values and whether the LEFT child holds no
    more rows than the right — the inputs the histogram-subtraction level
    flow needs (build the smaller sibling, derive the larger by
    subtraction; terminal leaves come straight from wl/wr with no extra
    totals pass).

    Monotone mode (constraints: [F] in {-1,0,+1}, node_lo/node_hi: [K]
    per-node leaf-value bounds): candidates whose child values violate the
    feature's direction are masked out, and the terminal leaf value is
    clipped into the node's inherited bounds — the same two-sided design as
    the reference's GBM monotone path (hex/tree/gbm/GBM.java) and XGBoost's
    monotone_constraints.

    Set-valued mode (cat_levels, as ``TreeParams`` has it, with a
    categorical among them): a categorical's levels that hold a row are
    ordered by Σg/(Σh+λ) ascending, ties by level (for the second-order
    objective the best two-way partition of the levels is a prefix of this
    order), and its candidates are the prefixes of that order, with the NA
    bucket on either side, beside the thresholds of the numeric features.
    Only the categoricals' slices are sorted. ``bin`` of such a winner is
    the chosen prefix's length less one, and one more array is returned
    last: left [K, B] bool, whether a row with code b of the chosen feature
    goes left — the prefix's levels, ``default_left`` for a level of a
    categorical that no row of the node has, and ``0..bin`` for a threshold.

    A child must hold ``min_rows`` rows (the count channel), or, where
    ``min_child_weight`` is given, a Σh of at least that much in its place
    (xgboost's floor on the hessian).

    ``deep`` (the levels of a deep tree, up to 2^19 nodes): the winner's
    entries are taken by masked sums, not by gathers along the node axis
    (a TPU gather of [2^19, 1, B, 3] took 348 ms a level). ``stable_gain``
    (only where lambda = alpha = 0) computes a candidate's gain as
    ``HL HR (GL/HL - GR/HR)^2 / H``, the three-term form without its
    cancellation: float32 squares of sums over more than 4,096 rows round,
    and a pure node's candidates would read a gain of +-1e-4 instead of 0.

    ``tiny_hessians`` (the class trees of a multinomial round, whose rows'
    hessians go down to the floor of 1e-16): a candidate's right side is
    summed from the right, over its own bins, as H2O's DTree sums from both
    ends, not taken as the node's total less the left; a candidate's parent
    is its own feature's total; and a categorical's levels are ordered by
    Σg/Σh floored at float32's least normal, not at 1e-12. A rare class
    (Σh 1e-5 a row) or a saturated one (1e-16) puts a side's Σh under the
    float32 rounding of the node's sums, and the difference of two such
    sums, taken over another feature's total, turned a split of no gain into
    the best (gain 9,067 for 5.5e-5 on a 5-class root of 100,000 rows); and
    levels whose Σh is under 1e-12 all read G x 1e12, an order of their
    counts, so a 4.9M-row fit's rare-class tree took prefixes 28% short of
    the best's gain.
    """
    B = n_bins1 - 1
    total = hist.sum(axis=2)  # [K, F, 3] — identical across F
    G = total[:, 0, 0]
    H = total[:, 0, 1]
    CNT = total[:, 0, 2]

    real = hist[:, :, :B, :]
    na = hist[:, :, B, :]  # [K, F, 3]
    cat_idx = tuple(f for f, v in enumerate(cat_levels) if v)
    if cat_idx:
        with jax.named_scope("sets"):
            real, key, key_s, code_s, present = _order_levels(
                real, cat_idx, lam, _F32_TINY if tiny_hessians else 1e-12)
    cum = jnp.cumsum(real, axis=2)  # bins <= b (the order's first b+1) on the left

    def thresh(g):
        return jnp.sign(g) * jnp.maximum(jnp.abs(g) - alpha, 0.0)

    def side_score(g, h):
        # optimal leaf objective with L1/L2: 0.5 * T(g)^2 / (h + lam)
        t = thresh(g)
        return t * t / jnp.maximum(h + lam, 1e-12)

    def opt_w(g, h):
        # unscaled optimal leaf value
        return -thresh(g) / jnp.maximum(h + lam, 1e-12)

    parent = side_score(G, H)  # [K]
    if tiny_hessians:
        # Σ of the bins past b, a candidate's right side less the NA bucket
        after = jnp.flip(jnp.cumsum(jnp.flip(real, axis=2), axis=2), axis=2)
        after = jnp.concatenate([after[:, :, 1:], jnp.zeros_like(after[:, :, :1])], axis=2)
        parent_f = side_score(total[:, :, 0], total[:, :, 1])[:, :, None]  # [K, F, 1]
        H_f = total[:, :, 1][:, :, None]

    def dir_gain(gl, hl, cl, right=None):
        # constraints mode materializes per-candidate child values (the
        # directional mask needs them); otherwise child stats for the ONE
        # winning candidate are gathered later — full [K, F, B] wl/wr
        # arrays would be pure waste on the default subtract path
        if right is None:
            gr = G[:, None, None] - gl
            hr = H[:, None, None] - hl
            cr = CNT[:, None, None] - cl
        else:
            gr, hr, cr = right
        # (the node's total and score sliced where the one-tree program did)
        if stable_gain:
            diff = gl / jnp.maximum(hl, 1e-12) - gr / jnp.maximum(hr, 1e-12)
            gain = 0.5 * (hl * hr / jnp.maximum(
                H[:, None, None] if right is None else H_f, 1e-12)) * diff * diff - gamma
        else:
            gain = 0.5 * (side_score(gl, hl) + side_score(gr, hr) - (
                parent[:, None, None] if right is None else parent_f)) - gamma
        if min_child_weight is None:
            ok = (cl >= min_rows) & (cr >= min_rows)
        else:
            ok = (hl >= min_child_weight) & (hr >= min_child_weight)
        gain = jnp.where(ok, gain, -jnp.inf)
        if constraints is not None:
            wl = opt_w(gl, hl)
            wr = opt_w(gr, hr)
            # [F] a feature, or [K, F] of a frontier level's own features
            c = (constraints[None, :, None] if constraints.ndim == 1
                 else constraints[:, :, None]).astype(gl.dtype)
            gain = jnp.where((c != 0) & (c * (wr - wl) < 0), -jnp.inf, gain)
        return gain

    # NA right (default_left=False): left stats = cum; NA left: left += NA bucket
    if tiny_hessians:
        gain_r = dir_gain(cum[..., 0], cum[..., 1], cum[..., 2],
                          tuple(after[..., i] + na[..., i][:, :, None] for i in range(3)))
        gain_l = dir_gain(
            *(cum[..., i] + na[..., i][:, :, None] for i in range(3)),
            tuple(after[..., i] for i in range(3)))
    else:
        gain_r = dir_gain(cum[..., 0], cum[..., 1], cum[..., 2])
        gain_l = dir_gain(
            cum[..., 0] + na[..., 0][:, :, None],
            cum[..., 1] + na[..., 1][:, :, None],
            cum[..., 2] + na[..., 2][:, :, None],
        )

    go_left_better = gain_l > gain_r
    gain_fb = jnp.where(go_left_better, gain_l, gain_r)  # [K, F, B]
    # feat_mask: [F] global or [K, F] per-node (DRF mtries per split)
    fm = feat_mask[None, :, None] if feat_mask.ndim == 1 else feat_mask[:, :, None]
    gain_fb = jnp.where(fm, gain_fb, -jnp.inf)

    flat = gain_fb.reshape(gain_fb.shape[0], -1)
    best = jnp.argmax(flat, axis=1)
    if deep:
        best_gain = jnp.max(flat, axis=1)
    else:
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    best_f = (best // B).astype(jnp.int32)
    best_b = (best % B).astype(jnp.int32)
    if deep:
        at_best = jnp.arange(flat.shape[1], dtype=best.dtype)[None, :] == best[:, None]
        dl = jnp.any(at_best & go_left_better.reshape(at_best.shape), axis=1)
    else:
        dl = jnp.take_along_axis(
            go_left_better.reshape(go_left_better.shape[0], -1), best[:, None], axis=1
        )[:, 0]

    sets = ()
    if cat_idx:
        with jax.named_scope("sets"):
            sets = (_winner_set(best_f, best_b, dl, cat_idx, key, key_s, code_s,
                                present),)

    # leaf value if this node terminates (Newton step, L1-thresholded, lr-scaled)
    raw_leaf = opt_w(G, H)
    if constraints is not None:
        raw_leaf = jnp.clip(raw_leaf, node_lo, node_hi)
    if constraints is not None or child_stats:
        # gather the winning candidate's (Σg, Σh, Σw) left-side stats from
        # cum/na — K-sized gathers, not full [K, F, B] re-materialization
        K = hist.shape[0]
        if deep:
            of_f = (jnp.arange(cum.shape[1], dtype=best_f.dtype)[None, :]
                    == best_f[:, None])[:, :, None]  # [K, F, 1]
            of_b = (jnp.arange(B, dtype=best_b.dtype)[None, :]
                    == best_b[:, None])[:, None, :, None]  # [K, 1, B, 1]
            stats_l = jnp.sum(jnp.where(of_f[..., None] & of_b, cum, 0.0), axis=(1, 2))
            na_f = jnp.sum(jnp.where(of_f, na, 0.0), axis=1)
        else:
            idx_f = jnp.broadcast_to(best_f[:, None, None, None], (K, 1, B, 3))
            cum_f = jnp.take_along_axis(cum, idx_f, axis=1)[:, 0]  # [K, B, 3]
            stats_l = jnp.take_along_axis(
                cum_f, jnp.broadcast_to(best_b[:, None, None], (K, 1, 3)), axis=1
            )[:, 0]  # [K, 3]
            na_f = jnp.take_along_axis(
                na, jnp.broadcast_to(best_f[:, None, None], (K, 1, 3)), axis=1
            )[:, 0]  # [K, 3]
        stats_l = stats_l + dl[:, None].astype(stats_l.dtype) * na_f
        gl_b, hl_b, cl_b = stats_l[:, 0], stats_l[:, 1], stats_l[:, 2]
        best_wl = opt_w(gl_b, hl_b)
        if tiny_hessians:
            # the chosen candidate's right side as its gain saw it
            if deep:
                stats_r = jnp.sum(jnp.where(of_f[..., None] & of_b, after, 0.0), axis=(1, 2))
            else:
                after_f = jnp.take_along_axis(after, idx_f, axis=1)[:, 0]
                stats_r = jnp.take_along_axis(
                    after_f, jnp.broadcast_to(best_b[:, None, None], (K, 1, 3)), axis=1
                )[:, 0]
            stats_r = stats_r + (~dl)[:, None].astype(stats_r.dtype) * na_f
            best_wr = opt_w(stats_r[:, 0], stats_r[:, 1])
        else:
            best_wr = opt_w(G - gl_b, H - hl_b)
        left_small = 2.0 * cl_b <= CNT
        return (best_f, best_b, dl, best_gain, lr * raw_leaf,
                best_wl, best_wr, left_small) + sets
    return (best_f, best_b, dl, best_gain, lr * raw_leaf) + sets


#: H2O GBM's bound on a multinomial leaf, whose margins are exponentiated
#: (hex/tree/gbm/GBM.java: "check for very large values (which will get
#: exponentiated later)"). A class whose prior is tiny (a few rows in
#: millions) takes Newton steps of lr/p on its first round, and a row whose
#: class probability has underflowed sums its gradient over a hessian at its
#: 1e-16 floor: unbounded, such leaves reach 1e12 within a few rounds and the
#: margins lose every smaller term
MULTINOMIAL_LEAF_MAX = 1e4


def _sel_table(table, idx):
    """table[idx] for a small table [K] and big idx [N] — as a masked
    reduction, NOT a gather (XLA TPU gathers are scalar-serialized: ~250ns
    per element; this is one fused VPU pass)."""
    K = table.shape[0]
    mask = idx[:, None] == jnp.arange(K, dtype=idx.dtype)[None, :]
    zero = jnp.zeros((), dtype=table.dtype)
    return jnp.sum(jnp.where(mask, table[None, :], zero), axis=1)


def _sel_tables(tables, idx):
    """Select from several same-length small tables sharing one mask."""
    K = tables[0].shape[0]
    mask = idx[:, None] == jnp.arange(K, dtype=idx.dtype)[None, :]
    outs = []
    for t in tables:
        zero = jnp.zeros((), dtype=t.dtype)
        outs.append(jnp.sum(jnp.where(mask, t[None, :], zero), axis=1))
    return outs


def _sel_cols(bins, f_idx):
    """bins[i, f_idx[i]] — per-row column select as a masked reduction."""
    F = bins.shape[1]
    mask = f_idx[:, None] == jnp.arange(F, dtype=f_idx.dtype)[None, :]
    return jnp.sum(jnp.where(mask, bins, 0), axis=1)


def _tree_walk(bins, feat, split_bin, default_left, is_split, leaf, max_depth: int, n_bins1):
    """Heap-walk a single tree (arrays [M]); returns per-row leaf values."""
    idx = jnp.zeros(bins.shape[0], dtype=jnp.int32)

    def body(_, idx):
        f, sb, dl, sp = _sel_tables((feat, split_bin, default_left, is_split), idx)
        b = _sel_cols(bins, f)
        is_na = b >= n_bins1 - 1
        go_left = jnp.where(is_na, dl, b <= sb)
        nxt = 2 * idx + jnp.where(go_left, 1, 2)
        return jnp.where(sp, nxt, idx)

    idx = jax.lax.fori_loop(0, max_depth, body, idx)
    return _sel_table(leaf, idx)


@partial(jax.jit, static_argnames=("max_depth",))
def _predict_stacked(bins, feat, split_bin, default_left, is_split, leaf, max_depth: int, n_bins1_arr):
    """Sum of all trees' outputs for each row. Tree arrays: [T, M]."""
    if frontier_start(max_depth, subtract=True) is not None:
        # no fit keeps such a tree as a heap (``Trees.deep``), and the walk
        # would reduce over [N, 2^(max_depth+1)] at every step
        raise ValueError(
            f"a tree of depth {max_depth} has frontier levels and is walked "
            "by its list of nodes (_predict_deep), not as a heap")

    def one_tree(carry, tree):
        tf, tb, tdl, tsp, tlf = tree
        return carry + _tree_walk(bins, tf, tb, tdl, tsp, tlf, max_depth, n_bins1_arr), None

    with jax.named_scope("score_traverse"):
        out, _ = jax.lax.scan(
            one_tree,
            jnp.zeros(bins.shape[0], jnp.float32),
            (feat, split_bin, default_left, is_split, leaf),
        )
    return out


# -- set-valued splits: a node's fields and its set of codes in one lookup ---
#
# A row needs, of its node, the split feature, default_left, is_split and
# ONE bit of the node's set: the bit of the row's own code. The masked sums
# above would pay one pass over [N, K] for every word of the set (ten for
# 300 levels). So the node's fields go into a [rows, K] table of bytes and
# every row's column of it is fetched by one matmul with the one-hot of the
# row's node: bytes are exact in bfloat16, the one-hot is fused into the
# product, and the MXU does in one pass what the VPU would do in forty.


def _pack_words(left):
    """left [K, B] bool -> the node's set [K, ceil(B/32)] uint32, bit
    ``b & 31`` of word ``b >> 5`` standing for code b."""
    K, B = left.shape
    W = -(-B // 32)
    bits = jnp.pad(left, ((0, 0), (0, W * 32 - B))).reshape(K, W, 32)
    return jnp.sum(bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32),
                   axis=2, dtype=jnp.uint32)


def _word_bytes(words):
    """uint32 [..., W] -> its bytes, low first, int32 in 0..255 [..., 4W]:
    byte ``b >> 3`` holds code b's bit at ``b & 7``."""
    by = (words[..., None] >> (8 * jnp.arange(4, dtype=jnp.uint32))) & 0xFF
    return by.reshape(*words.shape[:-1], 4 * words.shape[-1]).astype(jnp.int32)


def _byte_lookup(table, idx):
    """table[:, idx] for a table [R, K] of bytes (int32 in 0..255) and a big
    idx [N]: [R, N] bfloat16, exact. One matmul with the one-hot of idx."""
    K = table.shape[1]
    onehot = (jnp.arange(K, dtype=idx.dtype)[:, None] == idx[None, :])
    return jnp.dot(table.astype(jnp.bfloat16), onehot.astype(jnp.bfloat16),
                   preferred_element_type=jnp.bfloat16)


def _set_route(bins, k, feat, default_left, is_split, split_set, n_bins1):
    """(go_left, is_split) [N] of rows at nodes ``k`` of a level whose K
    nodes have the fields given ([K] each, split_set [K, W] uint32)."""
    set_bytes = _word_bytes(split_set)  # [K, 4W]
    nb = set_bytes.shape[1]
    table = jnp.concatenate([
        jnp.stack([feat & 0xFF, feat >> 8, default_left.astype(jnp.int32),
                   is_split.astype(jnp.int32)]),
        set_bytes.T])  # [4 + nb, K]
    r = _byte_lookup(table, k)
    f = r[0].astype(jnp.int32) + 256 * r[1].astype(jnp.int32)
    b = _sel_cols(bins, f)
    mine = (b >> 3)[None, :] == jnp.arange(nb, dtype=b.dtype)[:, None]
    byte = jnp.sum(jnp.where(mine, r[4:], jnp.zeros((), r.dtype)),
                   axis=0).astype(jnp.int32)
    in_set = ((byte >> (b & 7)) & 1) == 1
    # the NA bucket, and any code past the set (none after binning)
    go_left = jnp.where(b >= n_bins1 - 1, r[2] > 0, in_set)
    return go_left, r[3] > 0


def _tree_walk_sets(bins, feat, default_left, is_split, leaf, split_set,
                    max_depth: int, n_bins1: int):
    """``_tree_walk`` for trees with set-valued splits (arrays [M], split_set
    [M, W]): level by level, so a row's node is looked up among the 2^d of
    its level and not among the whole heap."""
    idx = jnp.zeros(bins.shape[0], dtype=jnp.int32)
    for d in range(max_depth):
        K, lo = 2**d, 2**d - 1
        lvl = slice(lo, lo + K)
        local = idx - lo  # rows that stopped above stay below lo
        go_left, sp = _set_route(
            bins, jnp.clip(local, 0, K - 1), feat[lvl], default_left[lvl],
            is_split[lvl], split_set[lvl], n_bins1)
        nxt = 2 * idx + jnp.where(go_left, 1, 2)
        idx = jnp.where((local >= 0) & sp, nxt, idx)
    # the leaf's four bytes by the same lookup, over the whole heap
    u = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
    r = _byte_lookup(_word_bytes(u[:, None]).T, idx).astype(jnp.uint32)
    bits = r[0] | (r[1] << 8) | (r[2] << 16) | (r[3] << 24)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@partial(jax.jit, static_argnames=("max_depth", "n_bins1"), donate_argnums=(0,))
def _predict_chunk_sets(acc, bins, feat, default_left, is_split, leaf,
                        split_set, max_depth: int, n_bins1: int):
    """``acc`` plus the outputs of a chunk of trees with set-valued splits
    (arrays [T, M], split_set [T, M, W]). The caller hands over the trees a
    chunk at a time, so the program does not depend on how many a model has."""

    def one_tree(carry, tree):
        return carry + _tree_walk_sets(bins, *tree, max_depth, n_bins1), None

    with jax.named_scope("score_traverse"):
        out, _ = jax.lax.scan(
            one_tree, acc, (feat, default_left, is_split, leaf, split_set))
    return out


# -- deep trees: a walk over each tree's list of nodes -----------------------


@partial(jax.jit, static_argnames=("max_depth", "n_bins1"), donate_argnums=(0,))
def _predict_chunk_deep(acc, bins, table, leaf, max_depth: int, n_bins1: int):
    """``acc`` plus the outputs of a chunk of deep trees: ``table`` [T, L, 4]
    int32 of (feature, split bin, default_left | is_split << 1, left child's
    position) a node, ``leaf`` [T, L]. A row's node is looked up by a gather
    a level, so a tree costs rows x depth whatever its size."""

    def one_tree(carry, tree):
        tab, lf = tree

        def step(_, idx):
            r = tab[idx]
            b = _sel_cols(bins, r[:, 0])
            go_left = jnp.where(b >= n_bins1 - 1, (r[:, 2] & 1) > 0, b <= r[:, 1])
            return jnp.where((r[:, 2] & 2) > 0, r[:, 3] + jnp.where(go_left, 0, 1), idx)

        idx = jax.lax.fori_loop(
            0, max_depth, step, jnp.zeros(bins.shape[0], jnp.int32))
        return carry + lf[idx], None

    with jax.named_scope("score_traverse"):
        out, _ = jax.lax.scan(one_tree, acc, (table, leaf))
    return out


def _predict_deep(bins, trees: "Trees"):
    """Sum of the outputs of deep trees, and the number of chunks it took:
    a tree block's worth of trees a call, each tree's nodes padded to the
    next power of two of the chunk's largest (the chunk's last trees, where
    short, are one leaf of 0)."""
    chunk = tree_block_size()
    acc = jnp.zeros(bins.shape[0], jnp.float32)
    starts = range(0, trees.ntrees, chunk)
    for t in starts:
        ids = range(t, min(t + chunk, trees.ntrees))
        size = 1 << max(max(len(trees.feat[i]) for i in ids) - 1, 1).bit_length()
        table = np.zeros((chunk, size, 4), np.int32)
        leaf = np.zeros((chunk, size), np.float32)
        for j, i in enumerate(ids):
            L = len(trees.feat[i])
            table[j, :L, 0] = trees.feat[i]
            table[j, :L, 1] = trees.split_bin[i]
            table[j, :L, 2] = (trees.default_left[i].astype(np.int32)
                               | (trees.is_split[i].astype(np.int32) << 1))
            table[j, :L, 3] = trees.child[i]
            leaf[j, :L] = trees.leaf[i]
        acc = _predict_chunk_deep(acc, bins, jnp.asarray(table), jnp.asarray(leaf),
                                  max_depth=trees.max_depth, n_bins1=trees.n_bins1)
    return acc, len(starts)


# ---------------------------------------------------------------------------
# the device-resident training block


def _tree_subtract_enabled() -> bool:
    """Histogram-subtraction level flow: build only the SMALLER sibling of
    each split and derive the larger by subtraction from the retained
    parent histogram (the standard hist-GBDT trick — LightGBM, XGBoost
    ``hist`` and the reference's ``grow_gpu_hist`` all do this); terminal
    leaves come from the last split's child stats with no totals pass.

    Env H2O3_TPU_TREE_SUBTRACT: '1' on, '0' off, unset/'auto' = on for the
    Pallas TPU path, off for the XLA scatter path (keeps the CPU oracle
    tier bit-stable). Read at trace time of the training block.
    """
    import os

    v = os.environ.get("H2O3_TPU_TREE_SUBTRACT", "auto")
    if v in ("0", "1"):
        return v == "1"
    return _hist_impl(None) == "pallas"


def _built_nodes(d: int, subtract: bool) -> int:
    """The nodes whose histogram level ``d`` builds: all 2^d of the level,
    or with subtraction each parent's smaller child alone."""
    return 2 ** (d - 1) if subtract and d > 0 else 2**d


def frontier_start(max_depth: int, subtract: bool) -> Optional[int]:
    """The first frontier level of a tree of ``max_depth``: the first level
    whose histogram would build more nodes than the top of the node ladder
    (``histogram.MAX_DENSE_NODES``), 10 without subtraction and 11 with it;
    None for a tree that has none. The tree's shape alone decides it."""
    for d in range(max_depth):
        if _built_nodes(d, subtract) > _histogram.MAX_DENSE_NODES:
            return d
    return None


def frontier_slots(p: TreeParams, rows: Optional[int], weighted: bool = False) -> int:
    """The slots of every frontier level over ``rows`` (padded) rows: one a
    node that can exist on the deepest, level ``max_depth - 1``. A node
    exists where its parent split, and a split leaves each child
    ``min_rows`` sampled rows: unweighted, at least ceil(min_rows) rows
    each, so a level holds at most 2 * (rows // (2 * ceil(min_rows)))
    nodes; under weights or xgboost's hessian floor, one row each. ``rows``
    None, or no floor to count by, gives 2^(max_depth - 1)."""
    K = 2 ** (p.max_depth - 1)
    if rows is None:
        return K
    if p.min_child_weight is None and not weighted and p.min_rows >= 1:
        per = 2 * int(np.ceil(p.min_rows))
        return max(2, min(K, 2 * (int(rows) // per)))
    floor = p.min_rows if p.min_child_weight is None else p.min_child_weight
    return max(2, min(K, int(rows))) if floor > 0 else K


def _exact_gain(p: TreeParams) -> bool:
    """Where a split's gain has the cancellation-free form (no lambda, no
    alpha: ``_split_search``'s ``stable_gain``)."""
    return p.reg_lambda == 0 and p.reg_alpha == 0


def _mtries(p: TreeParams, F: int) -> int:
    """Candidate features of a node: ``mtries``, or all F where it is <= 0."""
    return min(p.mtries, F) if p.mtries > 0 else F


def _node_candidates(key, node_ids, F: int, m: int):
    """[K, m] int32: the mtries features of the nodes of heap ids
    ``node_ids``, the m of lowest uniform(fold_in(the tree's key, heap id),
    (F,)), ties to the lower feature, listed in feature order (so a tie of
    gains goes to the lower feature, as over all F). A node's draw does not
    depend on how a level numbers its slots, and the order is a two-key
    sort, the same on every backend (``lax.top_k`` may break a tie either
    way)."""
    r = jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(key, i), (F,)))(
        node_ids.astype(jnp.uint32))
    feats = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), r.shape)
    return jnp.sort(jax.lax.sort((r, feats), dimension=1, num_keys=2)[1][:, :m], axis=1)


def level_plan(p: TreeParams, subtract: bool, impl: Optional[str] = None,
               rows: Optional[int] = None, weighted: bool = False,
               classes: int = 1):
    """What every level of a round of ``classes`` trees of ``p`` launches,
    one ``(nodes built, node slots launched, kernel)`` a level in the order
    the block runs them: the histogram of levels 0 to ``max_depth - 1`` and,
    without subtraction, the per-node totals of the leaves (``totals``). The
    padding a level pays is ``slots / built``. A level of K class trees
    counts its (class, node) slots; it is one launch of the kernel named
    while that kernel holds them all and one a class past it
    (``plan_launches``); K class trees build every node of a level and take
    their leaves from the last split. A frontier level (``frontier_start``) says
    ``(2^d, slots, "frontier")``, every one of them the slots of the deepest
    (``_frontier_levels``), and a tree with one takes its leaves from the
    last split (no ``totals``). Pure Python over the
    functions the trace itself asks (``pad_nodes``, ``_hist_impl``,
    ``_kernel_choice``, ``frontier_slots``, ``class_launches``); ``impl`` as
    ``build_histogram_sharded`` takes it."""
    impl = _hist_impl(impl)
    # K class trees build every node and take the leaves from the last split
    # (``_grow_trees``)
    stats = subtract or classes > 1
    subtract = subtract and classes == 1
    if impl == "pallas":
        from h2o3_tpu.ops.pallas_histogram import _kernel_choice

        def kernel(built, slots):
            if _histogram.class_launches(built, classes, impl) == 1:
                slots = classes * slots
            return _kernel_choice("auto", "auto", slots)[0]
    else:
        def kernel(built, slots):
            return impl

    d_f = frontier_start(p.max_depth, subtract)
    dense = p.max_depth if d_f is None else d_f
    built = [_built_nodes(d, subtract) for d in range(dense)]
    plan = [(classes * k, classes * pad_nodes(k), kernel(k, pad_nodes(k))) for k in built]
    plan += [(classes * 2**d, classes * frontier_slots(p, rows, weighted), "frontier")
             for d in range(dense, p.max_depth)]
    if d_f is None and not (stats and p.max_depth > 0):
        leaves = 2**p.max_depth
        plan.append((classes * leaves, classes * pad_nodes(leaves), "totals"))
    return tuple(plan)


def plan_launches(plan, classes: int = 1, impl: Optional[str] = None) -> dict:
    """Histogram kernel launches of one round of a ``level_plan``, by
    kernel: a level of K class trees is one launch while one kernel holds
    its (class, node) slots (``histogram.class_launches``), K past it; a
    frontier level is a launch a class tree, the leaves' totals none."""
    out: dict = {}
    for built, _, kernel in plan:
        if kernel == "totals":
            continue
        n = classes if kernel == "frontier" else _histogram.class_launches(
            built // classes, classes, impl)
        out[kernel] = out.get(kernel, 0) + n
    return out


def _grow_trees(
    bins, g, h, sample, feat_mask, keys, p: TreeParams, mesh, bins_fm=None,
    constraints=None, rw=None, subtract: bool = False,
):
    """Grow the class trees of one boosting round to max_depth together,
    fully traced. Levels are unrolled with per-level static node capacity
    2^d (the fixed-capacity redesign of the reference's dynamic DTree node
    growth).

    ``keys`` holds one PRNG key a class tree; ``g``, ``h`` are a class's
    [N] gradient and hessian where there is one class tree and the [K, N]
    class-major stack where there are K (never [N, K], whose minor axis XLA
    pads to 128 lanes). Every row sits in one node of each class tree: a
    level's slots are its (class, node) pairs, one histogram launch builds
    all of them (``build_histogram_sharded`` takes the K rows of node ids),
    and split search, subtraction and the leaves run on that flattened
    class-major axis, each node on its own. A row's node in each class tree
    is a lane-dense [N] vector of its own, routed by that tree's splits. One
    class tree traces the very program a single tree always traced.

    Every row (sampled or not) is routed so its leaf is known at the end —
    the margin update is then a single small-table select, with no separate
    prediction walk over the finished tree. Only ``sample`` rows contribute
    to histograms (row-subsampling semantics of GBM/DRF).

    constraints: optional [F] monotone directions; when set, per-node
    leaf-value bounds [lo, hi] are carried down the levels (children of a
    split on a constrained feature inherit the split's midpoint as the
    shared bound) and leaf values are clipped into them.

    Where a feature is categorical (``p.cat_levels``) a sixth heap array
    holds every node's set of codes that go left (``Trees.split_set``) and
    rows are routed by membership in it; with none the program is the one
    it was.

    A tree deep enough to have frontier levels (``frontier_start``) grows
    its dense levels as above and the rest by ``_frontier_levels``, a class
    tree at a time; every row then carries its node's leaf value down the
    levels, so no lookup over the heap is made at the end.

    Where ``p.max_abs_leaf`` is set (H2O GBM's multinomial fit) every leaf,
    and so each row's value, is clamped to it.

    Returns (heap arrays, per-row leaf value of each class tree): with one
    class tree the arrays are [M] and the values one [N] in a list, with K
    they are [K, M] and K of [N]. For a deep tree the arrays are the dense
    levels' heap followed by the frontier levels' slots, and a sixth array
    holds the slots' heap ids.
    """
    nc = len(keys)  # class trees of the round
    # K class trees build every node of a level: subtraction's larger child
    # (the parent less the smaller) loses a side whose Σh is a saturated or
    # rare class's (1e-16 or 1e-5 a row) to the float32 rounding of the
    # parent's sums; the leaves below the last split still come from its
    # child stats, not from a totals pass
    stats = subtract or nc > 1
    subtract = subtract and nc == 1
    D = p.max_depth
    n_bins1 = p.n_bins1
    sets = any(p.cat_levels)
    n_words = -(-(n_bins1 - 1) // 32)
    F = bins.shape[1]

    def of(a, c):
        """Class c's nodes of a flattened (class, node) axis; the value
        itself where there is one class tree."""
        return a if nc == 1 else a.reshape(nc, -1, *a.shape[1:])[c]

    def rows_of(a, c):  # class c's [N] row of a [K, N] stack
        return a if nc == 1 else a[c]

    def stacked(vs):  # K per-class [N] vectors as one [K, N] operand
        return vs[0] if nc == 1 else jnp.stack(vs)

    def joined(vs):  # K per-class node tables as one flattened axis
        return vs[0] if nc == 1 else jnp.concatenate(vs)

    # absolute heap position of each row in each class tree
    pos = [jnp.zeros(bins.shape[0], dtype=jnp.int32) for _ in range(nc)]
    mono = constraints is not None
    if mono:
        b_lo = jnp.full((nc,), -jnp.inf, jnp.float32)
        b_hi = jnp.full((nc,), jnp.inf, jnp.float32)
    d_f = frontier_start(D, subtract)
    deep = d_f is not None
    if deep:
        # each row's node's leaf
        val = [jnp.zeros(bins.shape[0], jnp.float32) for _ in range(nc)]

    tf_l, tb_l, tdl_l, tsp_l, tlf_l, tset_l = [], [], [], [], [], []
    prev_hist = prev_can = prev_left_small = prev_wl = prev_wr = None
    # every level and phase carries a named scope (metadata only, no
    # instruction): the profiler's device operations are summed by them
    for d in range(D if deep else D + 1):
        if deep and d == d_f:
            break
        K = 2**d
        lo = K - 1
        lvl = f"L{d:02d}"
        with jax.named_scope(f"{lvl}/hist_nodes"):
            local, in_lvl, hist_nodes = [], [], []
            for c in range(nc):
                local.append(pos[c] - lo)
                in_lvl.append((local[c] >= 0) & (local[c] < K))
                hist_nodes.append(
                    jnp.where(in_lvl[c] & sample, local[c], -1).astype(jnp.int32))
        if d == D:
            with jax.named_scope("leaf"):
                if stats and prev_wl is not None:  # D=0 has no parent split
                    # terminal leaves straight from the parent split's child
                    # stats: child(2k+0) = wl[k], child(2k+1) = wr[k] — the
                    # level-(D-1) cumsum stats cover exactly the rows each
                    # child receives, so no totals pass is needed at all
                    raw_leaf = jnp.stack([prev_wl, prev_wr], axis=1).reshape(nc * K)
                else:
                    # terminal level: no split is possible, so the full
                    # [K, F, B+1, 3] histogram (the widest of the tree) is
                    # pure waste — per-node (Σg, Σh) totals give the leaf
                    # values
                    from h2o3_tpu.ops.histogram import node_totals_sharded

                    tot = joined([node_totals_sharded(
                        hist_nodes[c], rows_of(g, c), rows_of(h, c), K, mesh=mesh,
                        rw=rw) for c in range(nc)])
                    G, H = tot[:, 0], tot[:, 1]
                    t = jnp.sign(G) * jnp.maximum(
                        jnp.abs(G) - jnp.float32(p.reg_alpha), 0.0
                    )
                    raw_leaf = -t / jnp.maximum(
                        H + jnp.float32(p.reg_lambda), 1e-12)
                if mono:
                    raw_leaf = jnp.clip(raw_leaf, b_lo, b_hi)
                tf_l.append(jnp.zeros(nc * K, jnp.int32))
                tb_l.append(jnp.zeros(nc * K, jnp.int32))
                tdl_l.append(jnp.zeros(nc * K, bool))
                tsp_l.append(jnp.zeros(nc * K, bool))
                tlf_l.append(jnp.float32(p.learn_rate) * raw_leaf)
                if sets:
                    tset_l.append(jnp.zeros((nc * K, n_words), jnp.uint32))
            break
        if subtract and d > 0:
            # build ONLY each parent's smaller child (one kernel slot per
            # parent, K/2 nodes); the larger sibling = parent − smaller.
            # Children of non-split parents hold no rows: their small
            # half is all-zero by the in_lvl mask and their big half is
            # masked to zero by prev_can.
            Kp = _built_nodes(d, subtract)
            with jax.named_scope(f"{lvl}/hist_nodes"):
                half_nodes = []
                for c in range(nc):
                    par = jnp.clip(local[c] // 2, 0, Kp - 1)
                    parity = local[c] % 2
                    small_parity = jnp.where(of(prev_left_small, c), 0, 1)  # [Kp]
                    sp_row = _sel_table(small_parity.astype(jnp.int32), par)
                    half_nodes.append(jnp.where(
                        in_lvl[c] & sample & (parity == sp_row), par, -1
                    ).astype(jnp.int32))
            with jax.named_scope(f"{lvl}/hist"):
                hist_small = build_histogram_sharded(
                    bins, stacked(half_nodes), g, h, n_nodes=Kp, n_bins1=n_bins1,
                    mesh=mesh, bins_fm=bins_fm, rw=rw,
                )
            with jax.named_scope(f"{lvl}/subtract"):
                can_m = prev_can[:, None, None, None]
                hist_big = jnp.where(can_m, prev_hist - hist_small, 0.0)
                ls_m = prev_left_small[:, None, None, None]
                left = jnp.where(ls_m, hist_small, hist_big)
                right = jnp.where(ls_m, hist_big, hist_small)
                hist = jnp.stack([left, right], axis=1).reshape(
                    nc * K, *hist_small.shape[1:]
                )
        else:
            with jax.named_scope(f"{lvl}/hist"):
                hist = build_histogram_sharded(
                    bins, stacked(hist_nodes), g, h, n_nodes=K, n_bins1=n_bins1,
                    mesh=mesh, bins_fm=bins_fm, rw=rw,
                )
        with jax.named_scope(f"{lvl}/split"):
            if p.mtries > 0:
                # the node's mtries features, drawn by its heap id
                node_feat_mask = []
                for c in range(nc):
                    pick = _node_candidates(
                        keys[c], lo + jnp.arange(K, dtype=jnp.int32), F, _mtries(p, F))
                    chosen = jnp.any(
                        pick[:, :, None] == jnp.arange(F, dtype=pick.dtype)[None, None, :],
                        axis=1)
                    node_feat_mask.append(chosen & feat_mask[None, :])
                node_feat_mask = joined(node_feat_mask)
            else:
                node_feat_mask = feat_mask
            out = _split_search(
                hist,
                jnp.float32(p.reg_lambda),
                jnp.float32(p.reg_alpha),
                jnp.float32(p.gamma),
                jnp.float32(p.learn_rate),
                node_feat_mask,
                min_rows=float(p.min_rows),
                n_bins1=n_bins1,
                constraints=constraints if mono else None,
                node_lo=b_lo if mono else None,
                node_hi=b_hi if mono else None,
                child_stats=stats,
                cat_levels=p.cat_levels,
                min_child_weight=p.min_child_weight,
                deep=deep,
                stable_gain=deep and _exact_gain(p),
                tiny_hessians=nc > 1,
            )
            if sets:
                with jax.named_scope("sets"):
                    node_set = _pack_words(out[-1])
                    tset_l.append(node_set)
                out = out[:-1]
            if mono or stats:
                bf, bb, dl, gain, leaf, bwl, bwr, left_small = out
            else:
                bf, bb, dl, gain, leaf = out
            can = (gain > max(p.min_split_improvement, 0.0)) & jnp.isfinite(gain) & (d < D)
        tf_l.append(bf)
        tb_l.append(bb)
        tdl_l.append(dl)
        tsp_l.append(can)
        tlf_l.append(leaf)
        if subtract:
            prev_hist, prev_can, prev_left_small = hist, can, left_small
        if stats:
            prev_wl, prev_wr = bwl, bwr
        with jax.named_scope(f"{lvl}/route"):
            if deep and d == d_f - 1:
                slot = [None] * nc
            for c in range(nc):
                k = jnp.clip(local[c], 0, K - 1)
                if sets:
                    with jax.named_scope("sets"):
                        go_left, cank = _set_route(
                            bins, k, of(bf, c), of(dl, c), of(can, c), of(node_set, c),
                            n_bins1)
                elif deep:
                    # a deep tree's rows carry their node's leaf, and at the
                    # last dense level the rank of their node among those
                    # that split (its children's slots on the first frontier
                    # level)
                    last = d == d_f - 1
                    rank = jnp.cumsum(of(can, c).astype(jnp.int32)) - 1
                    f, sb, dlk, cank, lfk, *rk = _sel_tables(
                        (of(bf, c), of(bb, c), of(dl, c), of(can, c), of(leaf, c))
                        + ((rank,) if last else ()), k)
                    val[c] = jnp.where(in_lvl[c], lfk, val[c])
                    b = _sel_cols(bins, f)
                    go_left = jnp.where(b >= n_bins1 - 1, dlk, b <= sb)
                    if last:
                        slot[c] = jnp.where(
                            in_lvl[c] & cank, 2 * rk[0] + jnp.where(go_left, 0, 1),
                            frontier_slots(p, bins.shape[0], rw is not None))
                else:
                    f, sb, dlk, cank = _sel_tables(
                        (of(bf, c), of(bb, c), of(dl, c), of(can, c)), k)
                    b = _sel_cols(bins, f)
                    go_left = jnp.where(b >= n_bins1 - 1, dlk, b <= sb)
                child = 2 * (lo + k) + jnp.where(go_left, 1, 2)
                pos[c] = jnp.where(in_lvl[c] & cank, child, pos[c]).astype(jnp.int32)
            if mono:
                # propagate bounds: split midpoint caps the monotone side
                c_best = jnp.take(constraints, bf).astype(jnp.float32)  # [K]
                mid = jnp.clip(0.5 * (bwl + bwr), b_lo, b_hi)
                lo_left = jnp.where(c_best < 0, jnp.maximum(b_lo, mid), b_lo)
                hi_left = jnp.where(c_best > 0, jnp.minimum(b_hi, mid), b_hi)
                lo_right = jnp.where(c_best > 0, jnp.maximum(b_lo, mid), b_lo)
                hi_right = jnp.where(c_best < 0, jnp.minimum(b_hi, mid), b_hi)
                b_lo = jnp.stack([lo_left, lo_right], axis=1).reshape(nc * 2 * K)
                b_hi = jnp.stack([hi_left, hi_right], axis=1).reshape(nc * 2 * K)

    def heap(levels):
        """A field's levels as each class tree's heap: per-level
        concatenation IS the heap layout, node (d, i) -> 2^d - 1 + i."""
        if nc == 1:
            return jnp.concatenate(levels)
        return jnp.concatenate([a.reshape(nc, -1, *a.shape[1:]) for a in levels], axis=1)

    if deep:
        dense = tuple(heap(a) for a in (tf_l, tb_l, tdl_l, tsp_l, tlf_l))
        trees, preds = [], []
        for c in range(nc):
            fr, v = _frontier_levels(
                bins, rows_of(g, c), rows_of(h, c), sample, feat_mask, keys[c], p, mesh,
                rw, slot[c], val[c], of(can, c),
                (constraints, of(b_lo, c), of(b_hi, c)) if mono else None, d_f)
            tree = tuple(jnp.concatenate([rows_of(a, c), b])
                         for a, b in zip(dense, fr[:5])) + (fr[5],)
            if p.max_abs_leaf is not None:
                bound = jnp.float32(p.max_abs_leaf)
                tree = tree[:4] + (jnp.clip(tree[4], -bound, bound),) + tree[5:]
                v = jnp.clip(v, -bound, bound)
            trees.append(tree)
            preds.append(v)
        if nc == 1:
            return trees[0], preds
        return tuple(jnp.stack(a) for a in zip(*trees)), preds

    with jax.named_scope("leaf"):
        tree = (
            heap(tf_l),
            heap(tb_l),
            heap(tdl_l),
            heap(tsp_l),
            heap(tlf_l),
        ) + ((heap(tset_l),) if sets else ())
        if p.max_abs_leaf is not None:
            bound = jnp.float32(p.max_abs_leaf)
            tree = tree[:4] + (jnp.clip(tree[4], -bound, bound),) + tree[5:]
        preds = [_sel_table(rows_of(tree[4], c), pos[c]) for c in range(nc)]
    return tree, preds


def _feature_words(key, node_ids, F: int, m: int):
    """The features of the nodes of heap ids ``node_ids``
    (``_node_candidates``) packed at ``ceil(log2 F)`` bits an id: their
    [K] uint32 words (``bitpack``)."""
    fidx = _node_candidates(key, node_ids, F, m)
    return pack_words((fidx[:, j] for j in range(m)), (field_bits(F),) * m)


def _kids_words(key, node, F: int, m: int):
    """The feature words of each child of the nodes of heap ids ``node``,
    drawn by the child's heap id, in its parent's place: ((left, right)
    [K]) a word."""
    return tuple(zip(_feature_words(key, 2 * node + 1, F, m),
                     _feature_words(key, 2 * node + 2, F, m)))


def _gather_rows(cols, idx):
    """``[c[idx] for c in cols]``, the [K] columns moved by ONE row gather
    of their [K, len(cols)] stack (a single column by itself: an [N, 1]
    result may be laid out padded to 128 lanes)."""
    if len(cols) == 1:
        return [cols[0][idx]]
    got = jnp.stack(cols, axis=1)[idx]
    return [got[:, i] for i in range(len(cols))]


def _children(can, parent_node, slots: int, pairs=()):
    """The next level's ``slots`` slots from the nodes of this one that
    split (``can``, heap ids ``parent_node``): the parent of rank r among
    them holds slots 2r (left child) and 2r + 1 (right child). ``pairs``:
    (left, right) [K] float32 arrays of what each child takes from its
    parent. Returns the slots' heap ids (-1: empty) and, a pair, the
    children's values [slots]. One stable sort puts the parents that split
    first, in order, and one row gather takes their fields: a binary search
    of the slots in the ranks took 59 ms a level at 2^19 slots on a v5e."""
    K = can.shape[0]
    i32 = jnp.int32
    order = jax.lax.sort(((~can).astype(i32), jnp.arange(K, dtype=i32)),
                         num_keys=1, is_stable=True)[1]
    cols = [parent_node] + [jax.lax.bitcast_convert_type(v, i32) for pr in pairs for v in pr]
    took = _gather_rows(cols, order)  # 1 + 2 pairs, [K] each

    def interleave(left, right):
        v = jnp.stack([left, right], axis=1).reshape(2 * K)
        if 2 * K >= slots:
            return v[:slots]
        return jnp.concatenate([v, jnp.zeros(slots - 2 * K, v.dtype)])

    node = interleave(2 * took[0] + 1, 2 * took[0] + 2)
    node = jnp.where(jnp.arange(slots, dtype=i32) // 2 < jnp.sum(can.astype(i32)), node, -1)
    vals = [jax.lax.bitcast_convert_type(
        interleave(took[1 + 2 * i], took[2 + 2 * i]), jnp.float32)
        for i in range(len(pairs))]
    return node, vals


def frontier_row_gathers(impl: str) -> int:
    """The row-sized gathers one frontier level makes (``_frontier_levels``):
    the level's slot table by each row's slot and, where the histogram is
    the Pallas kernel's, the rows sorted by slot into its tile layout
    (``pallas_histogram._prep_frontier``). ``tests/test_frontier_gathers.py``
    counts them in the traced level."""
    return 1 + (impl == "pallas")


def _frontier_levels(bins, g, h, sample, feat_mask, key, p: TreeParams, mesh,
                     rw, slot, val, can_dense, mono, d_f: int):
    """Levels ``d_f`` .. ``max_depth`` of a deep tree: the frontier levels
    and the leaves below them. A level's nodes sit in slots, each carrying
    its heap id; a row carries the slot of its node (``slot``; the slot
    count: none) and its node's leaf value (``val``), and finds its node's
    fields by a gather: what a level costs grows with the rows, not with its
    nodes. Each node is histogrammed over its own ``mtries`` features alone
    (all F where mtries <= 0), ``[slots, mtries, B+1, 3]``, with no
    subtraction (a child's features are not its parent's); the leaves come
    from the last split's child stats.

    A row's slot fetches its node's split fields and, where a node has
    fewer features than F, the feature lists of both of its children, in
    ONE gather of the level's slot table a level (the chip prices a row
    gather by the row and by each group of 8 int32 columns, so fields and
    feature ids are packed into words, ``bitpack.pack_words``): the row
    carries the words of the node it goes to (``fw``) into the next level,
    whose codes are then selected with no gather. A level draws its
    children's features by their heap ids in its own slots for the table,
    and its own nodes' for the split search: a draw costs ~2.3 ms a level at
    2^19 slots on a v5e, a gather of the children's words by rank 8.7 ms.
    The first frontier level's words come by one gather before the levels;
    with every feature a node's the codes are the row's own.

    Every frontier level has the slots of the deepest (``frontier_slots``),
    so the levels are one ``lax.scan`` over one
    compiled level: unrolled, a depth-20 block took 145 s to compile on a
    v5e, 9 s a frontier level. Their operations carry the first frontier
    level's scopes (``L<d_f>/hist_nodes`` ... ``/route``).

    ``slot`` on entry is the first frontier level's (the last dense level's
    routing made it), ``can_dense`` that level's splits, ``mono`` (monotone
    directions [F], and the bounds of the dense children by their index) or
    None. Returns the slots' (feat, split_bin, default_left, is_split, leaf,
    heap id), each the levels' slots one after the other, and ``val``."""
    D = p.max_depth
    n_bins1 = p.n_bins1
    N, F = bins.shape
    i32 = jnp.int32
    m = _mtries(p, F)
    every = m == F  # every feature a node's: the codes are the row's own
    msi = max(p.min_split_improvement, 0.0)
    lr = jnp.float32(p.learn_rate)
    Kp = can_dense.shape[0]
    S = frontier_slots(p, N, rw is not None)
    lvl = f"L{d_f:02d}"
    # the slot table's uint32 words: the leaf's bits; the split feature,
    # bin, NA direction and 1 + the rank among the level's splits (0: no
    # split); then each child's feature words
    route_bits = (field_bits(F), field_bits(n_bins1), 1, field_bits(S + 1))
    n_rw = 1 + word_layout(route_bits)[1]
    feat_bits = (field_bits(F),) * m

    def padded(v):  # [S] -> [S + 1], the last entry that of no slot
        return jnp.concatenate([v, jnp.zeros((1,), v.dtype)])

    with jax.named_scope(f"L{d_f - 1:02d}/route"):
        # the dense levels' row state, made whole before the frontier reads
        # it: with the first level's feature gather reading ``slot``, XLA
        # fused the chain of every dense level's routing into it, so each
        # level's [N] routing values stayed live up to the last one (+495 MB
        # of temporaries at 6M rows, for a described v5e)
        slot, val = jax.lax.optimization_barrier((slot.astype(i32), val))
        dense_ids = Kp - 1 + jnp.arange(Kp, dtype=i32)
        if mono:
            # the dense children's bounds, by the dense child index 2k + side
            constraints, b_lo, b_hi = mono
            node, bounds = _children(can_dense, dense_ids, S, (
                (b_lo[0::2], b_lo[1::2]), (b_hi[0::2], b_hi[1::2])))
            bounds = tuple(bounds)
        else:
            node, _ = _children(can_dense, dense_ids, S)
            bounds = (jnp.zeros(S, jnp.float32),) * 2  # unread
    fw = None
    if not every:
        with jax.named_scope(f"{lvl}/hist_nodes"):
            # the first frontier level's nodes' feature words, each row its own
            fw = _gather_rows([padded(v) for v in _feature_words(key, node, F, m)],
                              jnp.minimum(slot, S))

    def level(carry, _):
        slot, val, node, _w, (lo_s, hi_s), fw = carry
        at = slot < S
        with jax.named_scope(f"{lvl}/hist_nodes"):
            if every:
                fidx = jnp.broadcast_to(jnp.arange(F, dtype=i32), (S, F))
                codes = bins
            else:
                fidx = _node_candidates(key, node, F, m)
                # each row's codes of its node's features, [N, m]
                row_f = unpack_words(lambda i: fw[i], feat_bits)
                codes = jnp.stack([_sel_cols(bins, f) for f in row_f], axis=1)
            cand = feat_mask[fidx] & (node >= 0)[:, None]
            hslot = jnp.where(at & sample, slot, S).astype(i32)
        with jax.named_scope(f"{lvl}/hist"):
            hist = build_frontier_histogram_sharded(
                codes, hslot, g, h, n_slots=S, n_bins1=n_bins1, mesh=mesh, rw=rw)
        with jax.named_scope(f"{lvl}/split"):
            bj, bb, dl, gain, leaf, bwl, bwr, _ = _split_search(
                hist,
                jnp.float32(p.reg_lambda),
                jnp.float32(p.reg_alpha),
                jnp.float32(p.gamma),
                lr,
                cand,
                min_rows=float(p.min_rows),
                n_bins1=n_bins1,
                constraints=constraints[fidx] if mono else None,
                node_lo=lo_s if mono else None,
                node_hi=hi_s if mono else None,
                child_stats=True,
                min_child_weight=p.min_child_weight,
                deep=True,
                stable_gain=_exact_gain(p),
            )
            bf = jnp.sum(jnp.where(jnp.arange(m, dtype=i32)[None, :] == bj[:, None],
                                   fidx, 0), axis=1)
            can = (gain > msi) & jnp.isfinite(gain) & (node >= 0)
        with jax.named_scope(f"{lvl}/route"):
            rank = jnp.cumsum(can.astype(i32)) - 1
            kids = () if every else _kids_words(key, node, F, m)
            if mono:
                c_best = constraints[bf].astype(jnp.float32)
                mid = jnp.clip(0.5 * (bwl + bwr), lo_s, hi_s)
                lo_l = jnp.where(c_best < 0, jnp.maximum(lo_s, mid), lo_s)
                hi_l = jnp.where(c_best > 0, jnp.minimum(hi_s, mid), hi_s)
                lo_r = jnp.where(c_best > 0, jnp.maximum(lo_s, mid), lo_s)
                hi_r = jnp.where(c_best < 0, jnp.minimum(hi_s, mid), hi_s)
                node_next, (w_next, lo_s, hi_s) = _children(
                    can, node, S, ((bwl, bwr), (lo_l, lo_r), (hi_l, hi_r)))
            else:
                node_next, (w_next,) = _children(can, node, S, ((bwl, bwr),))
            # the table a row's slot reads, [S + 1, words]: its node's split
            # and both children's feature words
            cols = [jax.lax.bitcast_convert_type(leaf, jnp.uint32)] + pack_words(
                (bf, bb, dl, jnp.where(can, rank + 1, 0)), route_bits)
            cols += [lw for lw, _ in kids] + [rw_ for _, rw_ in kids]
            r = _gather_rows([padded(v) for v in cols], jnp.minimum(slot, S))
            rf, rb, rdl, rk = (v.astype(i32) for v in
                               unpack_words(lambda i: r[1 + i], route_bits))
            b = _sel_cols(bins, rf)
            go_left = jnp.where(b >= n_bins1 - 1, rdl > 0, b <= rb)
            val = jnp.where(at, jax.lax.bitcast_convert_type(r[0], jnp.float32), val)
            slot = jnp.where(at & (rk > 0), 2 * (rk - 1) + jnp.where(go_left, 0, 1),
                             S).astype(i32)
            if not every:
                n_fw = len(kids)
                fw = [jnp.where(go_left, lw, rw_) for lw, rw_ in
                      zip(r[n_rw:n_rw + n_fw], r[n_rw + n_fw:])]
        return ((slot, val, node_next, w_next, (lo_s, hi_s), fw),
                (bf, bb, dl, can, leaf, node))

    carry = (slot, val, node, jnp.zeros(S, jnp.float32), bounds, fw)
    (slot, val, node, w_next, (lo_s, hi_s), _), levels = jax.lax.scan(
        level, carry, None, length=D - d_f)
    with jax.named_scope("leaf"):
        raw = jnp.clip(w_next, lo_s, hi_s) if mono else w_next
        leaf = jnp.where(node >= 0, lr * raw, 0.0)
        val = jnp.where(slot < S, jnp.concatenate([leaf, jnp.zeros((1,), jnp.float32)])[
            jnp.minimum(slot, S)], val)
        zero = jnp.zeros(S, i32)
        last = (zero, zero, zero.astype(bool), zero.astype(bool), leaf, node)
        fr = tuple(jnp.concatenate([a.reshape(-1), b]) for a, b in zip(levels, last))
    return fr, val


@lru_cache(maxsize=64)
def _make_block_fn(
    objective: str,
    n_class_trees: int,
    block: int,
    p: TreeParams,
    mesh,
    weighted: bool = False,
    monotone: bool = False,
    subtract: bool = False,
):
    """Compile one training block: scan over `block` boosting rounds, the
    whole thing one XLA program. Returns f(bins, y, valid, margin, keys,
    bins_fm, w, mono) -> (margin', tree arrays [block, C, M]).
    `weighted`/`monotone` are compile-time flags so the unweighted /
    unconstrained program is byte-identical to before (w/mono are passed as
    None and never touched).

    A round grows its C class trees together (``_grow_trees``). With C > 1
    the rounds carry the margin class-major, [C, N], and the gradients are
    computed so (``grad_hess_classes``): the [N, C] margin handed in and out
    is transposed once a block."""
    C = n_class_trees

    @partial(jax.jit, donate_argnums=(3,))
    def block_fn(bins, y, valid, margin, keys, bins_fm, w, mono):
        def one_round(margin, key_t):
            with jax.named_scope("grad"):
                if C == 1:
                    g_all, h_all = grad_hess_device(objective, y, margin)
                else:
                    g_all, h_all = grad_hess_classes(objective, y, margin)
                # a row's weight along its axis: [N, 1] rows, or [C, N] classes
                row = (slice(None), None) if C == 1 else (None, slice(None))
                if weighted:
                    # fold row weights into (g, h): every Σg/Σh a histogram
                    # sees becomes the weighted sum (DHistogram's Σw-scaled
                    # stats)
                    g_all = g_all * w[row]
                    h_all = h_all * w[row]
                if p.scale_pos_weight != 1.0:
                    # the class weight: a positive row's g and h count so
                    # many times (the counts stay rows)
                    cw = jnp.where(y > 0.5, jnp.float32(p.scale_pos_weight), 1.0)
                    g_all = g_all * cw[row]
                    h_all = h_all * cw[row]
            with jax.named_scope("sample"):
                kr, kc, kt = jax.random.split(key_t, 3)
                active = valid
                if p.sample_rate < 1.0:
                    active = active & (
                        jax.random.uniform(kr, active.shape) < p.sample_rate
                    )
                F = bins.shape[1]
                if p.col_sample_rate_per_tree < 1.0:
                    ncols = max(1, int(round(p.col_sample_rate_per_tree * F)))
                    r = jax.random.uniform(kc, (F,))
                    thresh = jnp.sort(r)[ncols - 1]
                    feat_mask = r <= thresh
                else:
                    feat_mask = jnp.ones((F,), bool)

            if C == 1:
                with jax.named_scope("grad"):
                    g_all = g_all[:, 0].astype(jnp.float32)
                    h_all = h_all[:, 0].astype(jnp.float32)
            tree, preds = _grow_trees(
                bins,
                g_all,
                h_all,
                active,
                feat_mask,
                [jax.random.fold_in(kt, c) for c in range(C)],
                p,
                mesh,
                bins_fm=bins_fm,
                constraints=mono if monotone else None,
                rw=w if weighted else None,
                subtract=subtract,
            )
            # margin update from the round's trees (full data, not just the
            # sample); each field [C, M], with set-valued splits a sixth,
            # [C, M, W]
            with jax.named_scope("margin"):
                if C == 1:
                    margin = margin.at[:, 0].add(preds[0])
                else:
                    margin = margin + jnp.stack(preds)
            if C == 1:
                tree = tuple(jnp.stack([a]) for a in tree)
            return margin, tree

        if C > 1:
            margin = margin.T
        margin, trees = jax.lax.scan(one_round, margin, keys)
        return (margin if C == 1 else margin.T), trees

    return block_fn


# ---------------------------------------------------------------------------
# training driver


class BoostedTrees:
    """Trained ensemble: per-class Trees + binning spec + init margin."""

    def __init__(
        self,
        trees_per_class: List[Trees],
        init_margin: np.ndarray,  # [C]
        params: TreeParams,
        average: bool = False,  # DRF averages instead of summing margins
    ):
        self.trees_per_class = trees_per_class
        self.init_margin = init_margin
        self.params = params
        self.average = average
        #: what a fit leaves for its own training metrics and the call
        #: drops: {"frame", "y", "w", "margin"}, the margin
        #: ``predict_margin`` would return for the rows of ``frame`` the
        #: fit kept (``common.TreeModelBase.model_performance``); where that
        #: margin is the one the blocks summed on the device, "device" too:
        #: {"margin", "y", "valid", "mesh"} as the blocks held them
        self.fit_eval: Optional[dict] = None

    @property
    def nclasses_trees(self) -> int:
        return len(self.trees_per_class)

    def predict_margin(self, X: np.ndarray) -> np.ndarray:
        """Raw margins [N, C] from raw features (re-binned with stored edges)."""
        t0 = self.trees_per_class[0]
        codes = t0.bin(X)
        cols = []
        # the codes' upload, every class's traversal and its read-back
        with Span("score_traverse", rows=X.shape[0], trees=t0.ntrees) as span:
            bins = jnp.asarray(codes)
            for c, trees in enumerate(self.trees_per_class):
                if trees.ntrees == 0:
                    cols.append(np.full(X.shape[0], self.init_margin[c], dtype=np.float64))
                    continue
                if trees.cat_levels:
                    s, chunks = _predict_sets(bins, trees)
                    span.set(sets=True, chunks=chunks)
                elif trees.deep:
                    s, chunks = _predict_deep(bins, trees)
                    span.set(deep=True, chunks=chunks)
                else:
                    s = _predict_stacked(
                        bins, *trees.stacked(), max_depth=trees.max_depth,
                        n_bins1_arr=jnp.int32(trees.n_bins1),
                    )
                s = np.asarray(jax.device_get(s), dtype=np.float64)
                if self.average:
                    s = s / trees.ntrees
                cols.append(self.init_margin[c] + s)
            return np.stack(cols, axis=1)


def _predict_sets(bins, trees: Trees):
    """Sum of the outputs of trees with set-valued splits, and the number of
    chunks it took: a tree block's worth of trees a call, the last chunk
    filled up with trees that are one leaf of 0, so one program serves a
    model of any number of trees (and the one a fit's first scoring built
    serves every later one)."""
    chunk = tree_block_size()
    fields = [np.stack(a) for a in (trees.feat, trees.default_left,
                                    trees.is_split, trees.leaf, trees.split_set)]
    short = (-trees.ntrees) % chunk
    if short:
        fields = [np.concatenate([a, np.zeros((short,) + a.shape[1:], a.dtype)])
                  for a in fields]
    acc = jnp.zeros(bins.shape[0], jnp.float32)
    starts = range(0, trees.ntrees, chunk)
    for t in starts:
        acc = _predict_chunk_sets(
            acc, bins, *(jnp.asarray(a[t:t + chunk]) for a in fields),
            max_depth=trees.max_depth, n_bins1=trees.n_bins1)
    return acc, len(starts)


def _count_splits(feat, is_split, cat_levels) -> Tuple[int, int]:
    """(splits, set-valued splits) of a block's read-back trees, ticked into
    ``tree_splits_total``."""
    n_split = int(is_split.sum())
    n_set = int((is_split & np.asarray(cat_levels, bool)[feat]).sum()
                ) if cat_levels else 0
    TREE_SPLITS.inc(n_set, kind="set")
    TREE_SPLITS.inc(n_split - n_set, kind="threshold")
    return n_split, n_set


def train_boosted(
    X,
    objective: str,
    y: np.ndarray,
    n_class_trees: int,
    init_margin: np.ndarray,
    params: TreeParams,
    average: bool = False,
    monitor: Optional[Callable[[int, np.ndarray], bool]] = None,
    score_interval: int = 1,
    mesh=None,
    resume_from: Optional["BoostedTrees"] = None,
    weights: Optional[np.ndarray] = None,
    offset: Optional[np.ndarray] = None,
    monotone: Optional[np.ndarray] = None,
    cache_token=None,
    cache_frame_key: Optional[str] = None,
    fit_eval: Optional[dict] = None,
) -> BoostedTrees:
    """Device-resident booster loop.

    X: the training rows, an [N, F] float array or deferred rows
    (``common.TreeRows``). Deferred rows are read only by the quantile
    sketch's sample while the bin codes are resident (``cache_token``'s
    devcache entry); they are built as a matrix for the codes' placement on
    a miss, so a first fit under ``max_runtime_secs`` pays that matrix
    inside its budget, beside ``apply_bins`` and the upload, and for a
    checkpoint's margin. The ``train_boosted`` span says which
    (``matrix_built`` / ``matrix_resident``; ``tree_entry_matrix_total``).
    objective: a grad_hess_device family name ('gaussian', 'bernoulli',
    'multinomial', 'poisson', 'gamma', 'laplace', 'tweedie:<p>',
    'huber:<delta>', 'quantile:<alpha>') or 'fixed' with y = targets [N, C]
    (DRF bagging semantics, average=True).
    monitor(tree_idx, margin[N, C]) -> True to stop early (ScoreKeeper hook);
    called every `score_interval` trees, which is also the device-block size —
    between calls nothing crosses the host boundary.
    resume_from: checkpoint-continue (SharedTree.java:131-136): start from an
    existing ensemble's trees + margin and train ``ntrees`` MORE trees. The
    per-tree RNG is keyed by absolute tree index, so k trees then k more
    reproduces a single 2k-tree run exactly.
    weights: [N] per-row observation weights (weights_column,
    hex/tree/SharedTree.java weights plumbing) folded into (g, h) on device.
    offset: [N] per-row margin offset (offset_column) added to the initial
    margin; single-margin objectives only. The caller owns adding the offset
    back at scoring time (Model.score semantics).
    monotone: [F] per-feature direction in {-1, 0, +1} (monotone_constraints).
    cache_token: hashable identity of X's provenance (frame column versions
    + encoding; see models/tree/common.tree_cache_token). When set, the
    quantize-and-place block (apply_bins + bin-code/validity/feature-major
    device_put) is memoized in the process-wide device frame cache, so
    repeat GBM/DRF/XGBoost fits on the same unmutated frame — and every
    tree of every fit — reuse the resident bin codes instead of re-binning
    and re-uploading. cache_frame_key links the entry to a DKV frame for
    lifecycle eviction. None bypasses the cache entirely.
    fit_eval: {"frame", "y", "w"}: the frame X's rows came from and the
    response and weights its training metrics are over. The ensemble then
    comes back with them and the fit's final margin of those rows as its
    ``fit_eval``, wherever that margin is the whole ensemble's.
    """
    if getattr(X, "is_dist_hist", False):
        if params.cat_levels:
            raise no_sets("dist_hist (tree training on a chunk-homed frame)")
        # chunk-homed training: the level loop fans hist_level ctx-DTasks
        # to the chunk homes and only histogram partials cross the wire
        from h2o3_tpu.models.tree import dist_hist as _dist_hist

        return _dist_hist.train_boosted_dist(
            X, objective, y, n_class_trees, init_margin, params,
            average=average, monitor=monitor,
            score_interval=score_interval,
            weights=weights, offset=offset, fit_eval=fit_eval)

    if mesh is None:
        mesh = default_mesh()
    with Span("train_boosted", objective=objective, rows=X.shape[0],
              nshards=mesh.devices.size) as span:
        bt = _train_boosted(
            X, objective, y, n_class_trees, init_margin, params, average,
            monitor, score_interval, mesh, resume_from, weights, offset,
            monotone, cache_token, cache_frame_key, fit_eval)
        if hasattr(X, "materialize"):  # deferred rows: were they built?
            span.set(**{"matrix_" + X.count(): 1})
        return bt


def _dense(X) -> np.ndarray:
    """X, or the whole float matrix of deferred rows (``common.TreeRows``)."""
    return X.materialize() if hasattr(X, "materialize") else X


def _train_boosted(
    X, objective, y, n_class_trees, init_margin, params, average, monitor,
    score_interval, mesh, resume_from, weights, offset, monotone,
    cache_token, cache_frame_key, fit_eval,
) -> BoostedTrees:
    """The single-host body of :func:`train_boosted`, under its span."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from h2o3_tpu.ops.histogram import _hist_impl
    from h2o3_tpu.parallel.mesh import DATA_AXIS

    n, F = X.shape
    p = params
    nshards = mesh.devices.size

    n_bins1 = p.n_bins1
    n_cat = sum(1 for v in p.cat_levels if v)
    subtract_on = _tree_subtract_enabled()
    # K class trees build every node (``_grow_trees``)
    deep = frontier_start(p.max_depth, subtract_on and n_class_trees == 1) is not None
    if deep and n_cat:
        raise NotImplementedError(
            f"set-valued splits on categorical columns (categorical_encoding="
            f"'enum') are not carried to the frontier levels of a tree of "
            f"max_depth {p.max_depth} (levels past the dense node ladder); "
            "train with max_depth <= 10 or categorical_encoding='label_encoder'")

    def sharded(nbytes=None, **more) -> dict:
        """What a span of something placed on, summed over or fetched from
        a mesh of several devices says besides: the shards, the bytes a
        shard (rows are dealt evenly) and ``more``. Nothing on one device."""
        if nshards == 1:
            return {}
        if nbytes is not None:
            more["bytes_per_shard"] = int(nbytes) // nshards
        return {"shards": nshards, **more}

    if resume_from is not None:
        # continue training: reuse the checkpoint's binning + f0 exactly
        init_margin = resume_from.init_margin
        edges = resume_from.trees_per_class[0].edges
        if resume_from.trees_per_class[0].n_bins1 != n_bins1:
            raise ValueError("checkpoint nbins mismatch")
    else:
        with Span("make_bins", nbins=p.nbins, cat_features=n_cat):
            edges = make_bins(X, p.nbins, seed=p.seed, cat_levels=p.cat_levels)

    def _place_bins():
        resident.set(hit=False)
        bins_host = apply_bins(_dense(X), edges, p.cat_levels)
        # the codes padded to the row tile, and their feature-major copy
        with Span("bins_layout") as layout:
            padn = (-n) % mult
            if padn:
                bh = np.concatenate(
                    [bins_host, np.zeros((padn, F), dtype=np.int32)], axis=0
                )
            else:
                bh = bins_host
            n_pad = bh.shape[0]
            valid_h = np.arange(n_pad) < n
            bfm_host = None
            if use_pallas:
                from h2o3_tpu.ops.pallas_histogram import _FEAT_BLOCK

                fb = min(_FEAT_BLOCK, F)
                Fp = F + (-F) % fb
                bfm_host = np.zeros((Fp, n_pad), dtype=np.int32)
                bfm_host[:F] = bh.T
            nbytes = bh.nbytes + valid_h.nbytes + (
                bfm_host.nbytes if bfm_host is not None else 0)
            layout.set(bytes=nbytes)
        with Span("bins_upload", bytes=nbytes, **sharded(nbytes)):
            bins_d = jax.device_put(bh, row_sharding(mesh, 2))
            valid_d = jax.device_put(valid_h, row_sharding(mesh, 1))
            bins_fm_d = None
            if bfm_host is not None:
                bins_fm_d = jax.device_put(
                    bfm_host, NamedSharding(mesh, P(None, DATA_AXIS))
                )
            jax.block_until_ready((bins_d, valid_d, bins_fm_d))
        return bins_d, valid_d, bins_fm_d, n_pad

    # bin codes are a pure function of (X provenance, edges, padding
    # layout) — reusable across ntrees, checkpoint-continues, and
    # GBM/DRF/XGBoost fits sharing a frame + binning spec
    import hashlib

    from h2o3_tpu.frame import devcache as _devcache

    # the span holds the whole lookup: the histogram implementation and the
    # padding it asks for, the edges' digest and the cache's answer
    with Span("bins_resident", hit=True) as resident:
        # pallas path: pad every shard to the kernel row tile so the
        # prepared feature-major copy needs no per-level realignment; a
        # process's first fit imports the Pallas modules here
        with Span("hist_impl"):
            use_pallas = _hist_impl(None) == "pallas"
            if use_pallas:
                from h2o3_tpu.ops.pallas_histogram import _ROW_TILE

                mult = nshards * _ROW_TILE
            else:
                mult = nshards
        edges_digest = hashlib.sha1(
            np.ascontiguousarray(edges).tobytes()
        ).hexdigest()
        bins_d, valid_d, bins_fm_d, n_pad = _devcache.cached(
            "tree_bins", cache_token,
            (edges_digest, p.nbins, mult) + ((p.cat_levels,) if n_cat else ()),
            mesh,
            _place_bins, frame_key=cache_frame_key,
        )

    C = n_class_trees
    with Span("state_upload") as upload:
        if objective == "fixed":
            targets = np.asarray(y, dtype=np.float32)
            if targets.ndim == 1:
                targets = targets[:, None]
            y_host = np.zeros((n_pad, targets.shape[1]), np.float32)
            y_host[:n] = targets
            y_d = jax.device_put(y_host, row_sharding(mesh, 2))
        else:
            y_host = np.zeros(n_pad, np.float32)
            y_host[:n] = np.asarray(y, dtype=np.float32)
            y_d = jax.device_put(y_host, row_sharding(mesh, 1))

        if resume_from is not None and objective != "fixed":
            m0 = resume_from.predict_margin(_dense(X)).astype(np.float32)  # [n, C]
            margin_host = np.tile(
                np.asarray(init_margin, dtype=np.float32), (n_pad, 1)
            )
            margin_host[:n] = m0
        else:
            margin_host = np.tile(
                np.asarray(init_margin, dtype=np.float32), (n_pad, 1)
            )
        if offset is not None:
            if C != 1:
                raise ValueError("offset_column requires a single-margin objective")
            margin_host[:n, 0] += np.asarray(offset, dtype=np.float32)
        margin = jax.device_put(margin_host, row_sharding(mesh, 2))

        w_d = None
        if weights is not None:
            w_host = np.zeros(n_pad, np.float32)
            w_host[:n] = np.asarray(weights, dtype=np.float32)
            w_d = jax.device_put(w_host, row_sharding(mesh, 1))
        jax.block_until_ready((y_d, margin, w_d))
        state_bytes = y_host.nbytes + margin_host.nbytes + (
            w_host.nbytes if w_d is not None else 0)
        upload.set(bytes=state_bytes, **sharded(state_bytes))
    mono_d = None
    if monotone is not None and np.any(np.asarray(monotone) != 0):
        mono_d = jnp.asarray(np.asarray(monotone, dtype=np.int32))

    trees_per_class = [Trees(p.max_depth, n_bins1, edges, p.cat_levels, deep=deep)
                       for _ in range(C)]
    tree_offset = 0
    if resume_from is not None:
        tree_offset = resume_from.trees_per_class[0].ntrees
        for c in range(C):
            trees_per_class[c].extend(resume_from.trees_per_class[c])
    key = None  # the fit's PRNG key, made under the first block's tree_keys

    # the block program depends on neither ntrees nor seed — normalize them
    # out of the compile-cache key
    from dataclasses import replace as _dc_replace

    p_key = _dc_replace(p, ntrees=0, seed=0)

    built = 0
    final_host = None  # the last budget check's copy of the margin
    default_block = tree_block_size()
    # what the block's levels launch, (built, slots, kernel) a level: the
    # span states it, so padding reads as slots / built without a trace
    hist_slots = level_plan(p_key, subtract_on, rows=n_pad,
                            weighted=weights is not None, classes=C)
    # the histogram launches of a round, by kernel: the span states their sum
    launches = plan_launches(hist_slots, C)
    # what one tree's levels hand to their psums, on each device: float32
    # [slots, F, B+1, 3] a histogram level ([slots, mtries, B+1, 3] a
    # frontier level), [slots, 3] the leaf totals
    width = {"totals": 1, "frontier": _mtries(p, F) * n_bins1}
    psum_tree = (nshards > 1) * 12 * sum(
        slots * width.get(kernel, F * n_bins1) for _, slots, kernel in hist_slots)
    # what one frontier level gathers of every row, stated by the span, and
    # what a tree's frontier levels gather
    frontier = {}
    if deep:
        frontier["frontier_row_gathers"] = frontier_row_gathers(
            "pallas" if use_pallas else "scatter")
    gathers_tree = frontier.get("frontier_row_gathers", 0) * sum(
        kernel == "frontier" for *_, kernel in hist_slots)
    while built < p.ntrees:
        block = (
            min(score_interval, p.ntrees - built)
            if monitor is not None
            else min(default_block, p.ntrees - built)
        )
        fn = _make_block_fn(
            objective, C, block, p_key, mesh,
            weighted=w_d is not None, monotone=mono_d is not None,
            subtract=subtract_on,
        )
        # one key per ABSOLUTE tree index: blocking and checkpoints never
        # change the random stream a given tree sees
        # replicated over the mesh, as every other argument is placed on it:
        # the block is then the program its shapes alone lower to
        # (``block_fn.lower``), one entry of the persistent compile cache
        with Span("tree_keys", trees=block):
            if key is None:
                key = jax.random.PRNGKey(p.seed)
            keys = jax.device_put(
                jax.vmap(lambda t: jax.random.fold_in(key, t))(
                    jnp.arange(tree_offset + built, tree_offset + built + block)),
                NamedSharding(mesh, P()))
        with Span(
            "tree_block", objective=objective, trees=block, rows=n,
            first_tree=tree_offset + built, hist_slots=hist_slots,
            class_trees=C, hist_launches=block * sum(launches.values()), **frontier,
            **sharded(bytes_psummed=block * psum_tree),
        ):
            margin, trees_dev = fn(
                bins_d, y_d, valid_d, margin, keys, bins_fm_d, w_d, mono_d
            )
            jax.block_until_ready(margin)
        HIST_PSUM_BYTES.inc(block * psum_tree)
        for kernel, k in launches.items():
            HIST_LAUNCHES.inc(block * k, kernel=kernel)
        if gathers_tree:
            TREE_FRONTIER_ROW_GATHERS.inc(block * C * gathers_tree)
        with Span("tree_readback", trees=block) as readback:
            # [block, C, M] each; with set-valued splits a sixth, [block, C,
            # M, W]; a deep tree's sixth is its frontier slots' heap ids
            fields = jax.device_get(trees_dev)
            for t in range(block):
                for c in range(C):
                    if deep:
                        trees_per_class[c].append(*(a[t, c] for a in fields[:5]),
                                                  node=fields[5][t, c])
                    else:
                        trees_per_class[c].append(*(a[t, c] for a in fields))
            n_split, n_set = _count_splits(fields[0], fields[3], p.cat_levels)
            readback.set(splits=n_split, set_splits=n_set)
            if deep:
                n_frontier = int((fields[5] >= 0).sum())
                TREE_FRONTIER_NODES.inc(n_frontier)
                readback.set(frontier_nodes=n_frontier)
        built += block
        if monitor is not None:
            with Span("budget_check", **sharded()) as check:
                with Span("margin_download", bytes=margin.nbytes, **sharded()):
                    final_host = np.asarray(jax.device_get(margin), np.float64)[:n]
                seen = final_host
                if average:
                    # what the forest predicts: the mean of its trees
                    f0 = np.asarray(init_margin, np.float64)[None, :]
                    seen = f0 + (final_host - f0) / built
                stop = bool(monitor(built - 1, seen))
                check.set(stop=stop)
            if stop:
                break

    bt = BoostedTrees(trees_per_class, np.asarray(init_margin, np.float64), p, average=average)
    # every block added its trees' leaves to the margin of every row, so
    # the device holds what a walk of the ensemble over X would sum; not
    # so for an averaged ensemble continued from a checkpoint, whose
    # margin starts without the earlier trees
    if fit_eval is not None and not (average and resume_from is not None):
        if final_host is None:
            with Span("margin_readback", bytes=margin.nbytes,
                      **sharded(margin.nbytes)):
                final_host = np.asarray(jax.device_get(margin))[:n]
        if average and built:
            f0 = bt.init_margin[None, :]
            final_host = f0 + (final_host - f0) / built
        bt.fit_eval = dict(fit_eval, margin=final_host)
        if not average:
            # the ensemble's own margin where it lives, float32 and padded,
            # beside the response and the mask of the rows that are real
            bt.fit_eval["device"] = {"margin": margin, "y": y_d,
                                     "valid": valid_d, "mesh": mesh}
    return bt
