"""The plain reference ``hist-gbm``: histogram gradient boosting in numpy
float64, with the extraction and the comparison that decide ``correct`` for
a configuration that names it (``"reference": "hist-gbm"``).

It imports nothing of the program.  What the harness asks of a reference
file, and finds here: ``NUMBERS`` (every number it can compute),
``extract(model, numbers)`` (the program's answer as plain arrays) and
``compare(config, seed, table, answers, block, numbers)`` (the worst reading
of each number over the window's answers).  Which of ``NUMBERS`` a
configuration is held to is data: the keys of its limits file
(``benchmark/limits/<config>.json``; PERF.md gives the readings each limit
was set from).

Compared, for every ``train()`` the window served, at the timed size:

* ``bin_rank_gap`` — the model's bin edges are quantile edges of the data;
* ``init_margin_gap`` — the model's starting margin against the reference's
  own, from the response alone;
* ``split_gap``, ``gain_forgone``, ``leaf_gap``, ``leaf_gap_mean`` — the
  trees of up to three boosting rounds (``judged_rounds``), judged by
  teacher forcing (``judge``);
* ``<metric>_gap`` (``logloss_gap``, ``auc_gap``, ``mse_gap``, ``rmse_gap``)
  — a ``training_metrics`` entry the fit reported (the program's own scoring
  traversal over every tree it built) against the reference's float64 walk
  of the same trees over every row: relative, but for ``auc``, which is
  absolute.

The arithmetic is used in two ways:

* ``grow(..., follow=None)`` BUILDS trees from its own argmax — the control
  (gradients rounded to a lower precision first) and the planted faults run
  through this, "the reference put in the program's place";
* ``grow(..., follow=tree)`` JUDGES a tree somebody else built (the program,
  the control, a fault): it routes the rows by that tree's splits, and at
  every node computes, in float64 from exact gradients, the gain of every
  candidate split and the leaf value, and records how far the judged tree's
  choice lies below the reference's best (``split_gap``) and how far its
  leaf values lie from the reference's (``leaf_gap``).  This is teacher
  forcing: near-tied splits flip under any rounding, so trees are never
  compared node for node, only choice against the reference's ranking.

Semantics implemented (the configuration files state them): threshold
splits on ordered bin codes (a categorical column is its level codes, as
the program's ``label_encoder`` has it; set-valued splits need a reference
of their own); quantile bin codes ``code = #edges <= x`` with NaN ->
``nbins``; per boosting round t the row sample ``uniform(k_r) <
sample_rate`` and the column sample ``rank(uniform(k_c)) < round(rate * F)``
with ``k_r, k_c, _ = split(fold_in(PRNGKey(seed), t), 3)``;
bernoulli/gaussian/multinomial
gradients; gain ``0.5 * (GL^2/HL + GR^2/HR - G^2/H)`` with both children
holding at least ``min_rows`` sampled rows and the NA bucket tried on both
sides; a node splits while ``gain > min_split_improvement`` and depth is
left; Newton leaves ``-learn_rate * G / H`` over the sampled rows; every row
(sampled or not) is routed and gets the leaf added to its margin.
"""

from __future__ import annotations

import sys
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

_THREADS = 8

JUDGED = ("split_gap", "gain_forgone", "leaf_gap", "leaf_gap_mean")
#: the ``training_metrics`` entries ``score`` computes, each compared as
#: ``<metric>_gap``: a share of the reference's value, but for those of
#: ``ABSOLUTE``, compared as a difference
REPORTED = ("logloss", "auc", "mse", "rmse")
ABSOLUTE = ("auc",)
NUMBERS = ("bin_rank_gap", "init_margin_gap") + JUDGED + tuple(
    m + "_gap" for m in REPORTED)


@dataclass(frozen=True)
class RefParams:
    distribution: str
    max_depth: int
    nbins: int
    learn_rate: float
    min_rows: float
    min_split_improvement: float = 1e-5
    sample_rate: float = 1.0
    col_sample_rate_per_tree: float = 1.0
    seed: int = 0

    @staticmethod
    def from_config(params: dict, seed: int) -> "RefParams":
        keys = ("distribution", "max_depth", "nbins", "learn_rate", "min_rows",
                "min_split_improvement", "sample_rate",
                "col_sample_rate_per_tree")
        return RefParams(seed=seed, **{k: params[k] for k in keys if k in params})


@dataclass
class Tree:
    """Heap layout, node i's children 2i+1 / 2i+2; arrays of 2^(D+1)-1."""

    feat: np.ndarray
    split_bin: np.ndarray
    default_left: np.ndarray
    is_split: np.ndarray
    leaf: np.ndarray  # learn-rate scaled


# ---------------------------------------------------------------------------
# binning


def bin_codes(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Feature-major codes [F, n]: number of edges <= x; NaN -> nbins."""
    n, F = X.shape
    nbins = edges.shape[1] + 1
    dtype = np.uint8 if nbins + 1 <= 256 else np.uint16
    out = np.empty((F, n), dtype)

    def one(f):
        col = X[:, f].astype(np.float64)
        c = np.searchsorted(edges[f], col, side="right")
        c[np.isnan(col)] = nbins
        out[f] = c

    with ThreadPoolExecutor(_THREADS) as ex:
        list(ex.map(one, range(F)))
    return out


def bin_rank_gap(codes: np.ndarray, nbins: int) -> float:
    """Quantile binning puts (b+1)/nbins of a feature's rows at or below bin
    b.  Widest distance from that over features and bins; features with no
    more distinct codes than bins/2 (categorical-like) are left out."""
    worst = 0.0
    for f in range(codes.shape[0]):
        cnt = np.bincount(codes[f], minlength=nbins + 1)[:nbins].astype(np.float64)
        if (cnt > 0).sum() <= nbins // 2 or cnt.sum() == 0:
            continue
        cdf = np.cumsum(cnt) / cnt.sum()
        target = np.arange(1, nbins + 1) / nbins
        worst = max(worst, float(np.abs(cdf - target).max()))
    return worst


# ---------------------------------------------------------------------------
# the model's arithmetic


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def init_margin(distribution: str, y: np.ndarray, nclasses: int) -> np.ndarray:
    if distribution == "bernoulli":
        p = min(max(float(np.mean(y)), 1e-10), 1 - 1e-10)
        return np.array([np.log(p / (1 - p))])
    if distribution == "gaussian":
        return np.array([float(np.mean(y))])
    if distribution == "multinomial":
        pri = np.bincount(y.astype(np.int64), minlength=nclasses) / len(y)
        return np.log(np.maximum(pri, 1e-10))
    raise ValueError(f"reference has no distribution {distribution!r}")


def grad_hess(distribution: str, y: np.ndarray, margin: np.ndarray):
    """(g, h) [n, C] of the loss with respect to the margin [n, C]."""
    if distribution == "bernoulli":
        p = sigmoid(margin[:, 0])
        return (p - y)[:, None], np.maximum(p * (1 - p), 1e-16)[:, None]
    if distribution == "gaussian":
        return (margin[:, 0] - y)[:, None], np.ones((len(y), 1))
    if distribution == "multinomial":
        z = margin - margin.max(axis=1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=1, keepdims=True)
        onehot = y.astype(np.int64)[:, None] == np.arange(margin.shape[1])[None, :]
        return p - onehot, np.maximum(p * (1 - p), 1e-16)
    raise ValueError(f"reference has no distribution {distribution!r}")


def logloss(distribution: str, y: np.ndarray, margin: np.ndarray) -> float:
    eps = 1e-15
    if distribution == "bernoulli":
        p = np.clip(sigmoid(margin[:, 0]), eps, 1 - eps)
        return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))
    if distribution == "multinomial":
        z = margin - margin.max(axis=1, keepdims=True)
        lp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
        return float(-np.mean(lp[np.arange(len(y)), y.astype(np.int64)]))
    raise ValueError(f"no logloss for {distribution!r}")


def round_sample(seed: int, t: int, n: int, F: int, p: RefParams):
    """Row and column sample of boosting round t (see module docstring)."""
    rows = np.ones(n, bool)
    cols = np.ones(F, bool)
    if p.sample_rate >= 1.0 and p.col_sample_rate_per_tree >= 1.0:
        return rows, cols
    import jax

    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("the sampling stream is defined for partitionable threefry")
    with jax.default_device(jax.devices("cpu")[0]):
        kr, kc, _ = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(seed), t), 3)
        if p.sample_rate < 1.0:
            rows = np.asarray(jax.random.uniform(kr, (n,)) < p.sample_rate)
        if p.col_sample_rate_per_tree < 1.0:
            ncols = max(1, int(round(p.col_sample_rate_per_tree * F)))
            r = np.asarray(jax.random.uniform(kc, (F,)))
            cols = r <= np.sort(r)[ncols - 1]
    return rows, cols


# ---------------------------------------------------------------------------
# one tree, level by level


def _level_hist(codes, idx, local, g, h, K: int, B1: int, feats) -> np.ndarray:
    """[K, F, B1, 3] (sum g, sum h, count) over rows ``idx`` whose node at
    this level is ``local``; features outside ``feats`` stay zero."""
    F = codes.shape[0]
    hist = np.zeros((K, F, B1, 3))
    base = local.astype(np.int64) * B1

    def one(f):
        flat = base + codes[f, idx]
        for c, w in enumerate((g, h, None)):
            hist[:, f, :, c] = np.bincount(
                flat, weights=w, minlength=K * B1).reshape(K, B1)

    # threads overlap the gathers and adds; the bincounts themselves hold the lock
    with ThreadPoolExecutor(_THREADS) as ex:
        list(ex.map(one, feats))
    return hist


def _gains(hist: np.ndarray, min_rows: float):
    """Candidate gains [K, F, B, 2] (NA right, NA left) and node totals."""
    B = hist.shape[2] - 1
    tot = hist.sum(axis=2)  # [K, F, 3]
    f0 = int(np.argmax(tot[..., 2].sum(axis=0)))  # any built feature: totals agree
    G, H, CNT = tot[:, f0, 0], tot[:, f0, 1], tot[:, f0, 2]
    cum = np.cumsum(hist[:, :, :B, :], axis=2)
    na = hist[:, :, B, :]

    def score(g, h):
        return g * g / np.maximum(h, 1e-12)

    parent = score(G, H)[:, None, None]
    out = []
    for left in (cum, cum + na[:, :, None, :]):
        gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
        gr, hr, cr = G[:, None, None] - gl, H[:, None, None] - hl, CNT[:, None, None] - cl
        gain = 0.5 * (score(gl, hl) + score(gr, hr) - parent)
        out.append(np.where((cl >= min_rows) & (cr >= min_rows), gain, -np.inf))
    return np.stack(out, axis=-1), G, H, CNT


def grow(
    codes: np.ndarray, g: np.ndarray, h: np.ndarray, rows: np.ndarray,
    cols: np.ndarray, p: RefParams, follow: Optional[Tree] = None,
    g_hist: Optional[np.ndarray] = None, h_hist: Optional[np.ndarray] = None,
    hist_rows: Optional[np.ndarray] = None,
):
    """Build (``follow`` None) or judge (``follow`` a tree) one tree.

    g/h are exact per-row gradients.  A builder may be handed ``g_hist`` /
    ``h_hist`` (what its histograms sum instead: the control's rounded
    values) and ``hist_rows`` (the rows its histograms see instead of
    ``rows``: a planted fault).  Returns (tree, pos, report): ``pos`` the heap
    node of every row, ``report`` the judged gaps when following.
    """
    F, n = codes.shape
    D, B1 = p.max_depth, p.nbins + 1
    M = 2 ** (D + 1) - 1
    msi = max(p.min_split_improvement, 0.0)
    gq = g if g_hist is None else g_hist
    hq = h if h_hist is None else h_hist
    sample = rows if hist_rows is None else hist_rows
    feats = np.flatnonzero(cols)
    tree = Tree(np.zeros(M, np.int32), np.zeros(M, np.int32), np.zeros(M, bool),
                np.zeros(M, bool), np.zeros(M))
    pos = np.zeros(n, np.int32)
    split_gaps: List[float] = []
    short_sum = best_sum = 0.0
    ref_leaf = np.zeros(M)
    nrows = np.zeros(M)  # rows (sampled or not) that pass through each node
    for d in range(D + 1):
        K, lo = 2 ** d, 2 ** d - 1
        at = pos >= lo  # rows stopped at shallower leaves stay below lo
        idx = np.flatnonzero(at & sample)
        local = pos[idx] - lo
        hist = _level_hist(codes, idx, local, gq[idx], hq[idx], K, B1,
                           feats if d < D else feats[:1])
        gains, G, H, CNT = _gains(hist, p.min_rows)
        leaf = -p.learn_rate * G / np.maximum(H, 1e-12)
        nrows[lo:lo + K] = np.bincount(pos[at] - lo, minlength=K)
        ref_leaf[lo:lo + K] = leaf
        if d == D:
            break
        gains[:, ~cols] = -np.inf
        flat = gains.reshape(K, -1)
        arg = flat.argmax(axis=1)
        best = flat[np.arange(K), arg]
        if follow is None:
            bf, bb, bdl = np.unravel_index(arg, gains.shape[1:])
            can = (best > msi) & np.isfinite(best)
            tree.feat[lo:lo + K] = bf
            tree.split_bin[lo:lo + K] = bb
            tree.default_left[lo:lo + K] = bdl.astype(bool)
            tree.is_split[lo:lo + K] = can
        else:
            sl = slice(lo, lo + K)
            reach = nrows > 0
            chosen = gains[np.arange(K), follow.feat[sl], follow.split_bin[sl],
                           follow.default_left[sl].astype(np.int64)]
            chosen = np.where(follow.is_split[sl], chosen, np.minimum(best, msi))
            live = reach[sl] & np.isfinite(best) & (best > msi)
            gap = np.where(
                live, (best - np.where(np.isfinite(chosen), chosen, 0.0))
                / np.where(live, best, 1.0), 0.0)
            # a split the reference holds impossible (min_rows, no gain)
            gap = np.where(reach[sl] & follow.is_split[sl] & ~live, 1.0, gap)
            split_gaps.extend(gap[reach[sl]].tolist())
            short_sum += float((gap * np.where(live, best, 0.0)).sum())
            best_sum += float(np.where(live, best, 0.0).sum())
        t = tree if follow is None else follow
        k = pos[at] - lo
        node = lo + k
        f = t.feat[node]
        code = codes[f, np.flatnonzero(at)]
        go_left = np.where(code >= B1 - 1, t.default_left[node],
                           code <= t.split_bin[node])
        child = 2 * node + np.where(go_left, 1, 2)
        pos[at] = np.where(t.is_split[node], child, node).astype(np.int32)
    if follow is None:
        tree.leaf = ref_leaf
        return tree, pos, None
    term = (nrows > 0) & ~follow.is_split
    scale = np.median(np.abs(ref_leaf[term])) if term.any() else 1.0
    den = np.maximum(np.abs(ref_leaf[term]), scale)
    lg = np.abs(follow.leaf[term] - ref_leaf[term]) / np.where(den > 0, den, 1.0)
    return follow, pos, {
        "split_gap": float(max(split_gaps, default=0.0)),
        "gain_forgone": short_sum / best_sum if best_sum > 0 else 0.0,
        "leaf_gap": float(lg.max(initial=0.0)),
        "leaf_gap_mean": float((lg * nrows[term]).sum() / max(nrows[term].sum(), 1.0)),
    }


def walk(codes: np.ndarray, trees: Sequence[Tree], B1: int) -> np.ndarray:
    """Sum of the trees' leaf values for every row, float64."""
    F, n = codes.shape
    out = np.zeros(n)
    if not trees:
        return out
    D = int(np.log2(len(trees[0].feat) + 1)) - 1
    step = max(1, -(-n // (4 * _THREADS)))

    def chunk(s):
        e = min(n, s + step)
        r = np.arange(s, e)
        acc = np.zeros(e - s)
        for t in trees:
            pos = np.zeros(e - s, np.int32)
            for _ in range(D):
                code = codes[t.feat[pos], r]
                go_left = np.where(code >= B1 - 1, t.default_left[pos],
                                   code <= t.split_bin[pos])
                pos = np.where(t.is_split[pos], 2 * pos + np.where(go_left, 1, 2), pos)
            acc += t.leaf[pos]
        out[s:e] = acc

    with ThreadPoolExecutor(_THREADS) as ex:
        list(ex.map(chunk, range(0, n, step)))
    return out


# ---------------------------------------------------------------------------
# lower precisions, for the control


def round_to(x: np.ndarray, precision: str) -> np.ndarray:
    """x rounded to the named storage precision, back in float64."""
    if precision == "float64":
        return x
    import ml_dtypes

    dt = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16,
          "fp8": ml_dtypes.float8_e4m3fn}[precision]
    return x.astype(np.float32).astype(dt).astype(np.float64)


# ---------------------------------------------------------------------------
# whole-model drivers


def boost(codes, y, p: RefParams, rounds: int, nclasses: int = 1,
          precision: str = "float64", fault: Optional[str] = None) -> Dict:
    """The reference in the program's place: ``rounds`` boosting rounds from
    its own argmax.  ``precision`` rounds g/h before they are summed (the
    control); ``fault`` plants one of ``state_unchanged`` (the margin is
    never updated), ``half_batch`` (histograms see every other sampled
    row), ``leaf_altered`` (every tree's leaf values 10% off)."""
    F, n = codes.shape
    C = nclasses if p.distribution == "multinomial" else 1
    f0 = init_margin(p.distribution, y, nclasses)
    margin = np.tile(f0, (n, 1))
    model: List[List[Tree]] = [[] for _ in range(C)]
    for t in range(rounds):
        g, h = grad_hess(p.distribution, y, margin)
        rows, cols = round_sample(p.seed, t, n, F, p)
        hist_rows = rows & (np.arange(n) % 2 == 0) if fault == "half_batch" else None
        for c in range(C):
            tree, pos, _ = grow(
                codes, g[:, c], h[:, c], rows, cols, p,
                g_hist=round_to(g[:, c], precision),
                h_hist=round_to(h[:, c], precision), hist_rows=hist_rows)
            if fault == "leaf_altered":
                tree.leaf *= 1.1
            if fault != "state_unchanged":
                margin[:, c] += tree.leaf[pos]
            model[c].append(tree)
    return {"init_margin": f0, "trees": model}


#: from this boosting round on the gradients take many distinct values, so
#: their rounding errors average out over a leaf's rows; in rounds 0 and 1
#: they take 2 and 2 * leaves values, every row of a class errs alike, and
#: a leaf's error is the precision's own, not its square root's share
LATE_ROUND = 2


def judge(codes, y, p: RefParams, model: Dict, rounds: Sequence[int],
          nclasses: int = 1) -> Dict[str, float]:
    """Teacher-forced judgement of ``model`` ({"init_margin", "trees":
    [class][round] Tree}) at the boosting rounds listed; the margin before a
    judged round comes from walking the model's own earlier trees.
    ``split_gap`` and ``leaf_gap`` are the widest over every judged node and
    leaf, ``gain_forgone`` the mean over the judged trees, and
    ``leaf_gap_mean`` the row-weighted mean leaf gap of the judged rounds
    from ``LATE_ROUND`` on (0 where none was judged); ``by_round`` keeps each
    judged tree's own readings, for the reader of a run."""
    F, n = codes.shape
    B1 = p.nbins + 1
    C = len(model["trees"])
    f0 = init_margin(p.distribution, y, nclasses)
    out = {"split_gap": 0.0, "leaf_gap": 0.0, "leaf_gap_mean": 0.0,
           "gain_forgone": 0.0, "by_round": {}}
    margin = np.tile(f0, (n, 1))
    done = 0
    short = []
    for t in sorted(rounds):
        for c in range(C):
            margin[:, c] += walk(codes, model["trees"][c][done:t], B1)
        done = t
        g, h = grad_hess(p.distribution, y, margin)
        rows, cols = round_sample(p.seed, t, n, F, p)
        for c in range(C):
            _, _, rep = grow(codes, g[:, c], h[:, c], rows, cols, p,
                             follow=model["trees"][c][t])
            out["by_round"][f"{t}.{c}"] = rep
            out["split_gap"] = max(out["split_gap"], rep["split_gap"])
            out["leaf_gap"] = max(out["leaf_gap"], rep["leaf_gap"])
            if t >= LATE_ROUND:
                out["leaf_gap_mean"] = max(out["leaf_gap_mean"], rep["leaf_gap_mean"])
            short.append(rep["gain_forgone"])
    out["gain_forgone"] = float(np.mean(short)) if short else 0.0
    return out


def score(codes, y, p: RefParams, model: Dict, nclasses: int = 1) -> Dict[str, float]:
    """Training metrics of the whole model, float64 throughout: logloss (and
    AUC, for two classes) of a classifier, mse and rmse of a regression."""
    C = len(model["trees"])
    B1 = p.nbins + 1
    margin = np.tile(np.asarray(model["init_margin"], np.float64), (len(y), 1))
    for c in range(C):
        margin[:, c] += walk(codes, model["trees"][c], B1)
    if p.distribution == "gaussian":
        mse = float(np.mean((margin[:, 0] - y) ** 2))
        return {"mse": mse, "rmse": mse ** 0.5}
    out = {"logloss": logloss(p.distribution, y, margin)}
    if p.distribution == "bernoulli":
        out["auc"] = auc(y, margin[:, 0])
    return out


def auc(y: np.ndarray, s: np.ndarray) -> float:
    """Mann-Whitney AUC, ties counted half."""
    order = np.argsort(s, kind="stable")
    ss = s[order]
    ranks = np.empty(len(s))
    bounds = np.flatnonzero(np.r_[True, ss[1:] != ss[:-1], True])
    avg = (bounds[:-1] + bounds[1:] + 1) / 2.0  # mean 1-based rank of each tie group
    ranks[order] = np.repeat(avg, np.diff(bounds))
    pos = y > 0.5
    n1, n0 = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


# ---------------------------------------------------------------------------
# what the harness calls: the program's answer, and its comparison


def reported_metrics(numbers) -> List[str]:
    """Names of the ``training_metrics`` entries that ``numbers`` ask for."""
    return [m for m in REPORTED if m + "_gap" in numbers]


def extract(model, numbers) -> dict:
    """The program's answer as plain arrays: init margin, bin edges, trees,
    and the ``training_metrics`` entries that ``numbers`` ask for."""
    b = model.booster
    trees = []
    for tpc in b.trees_per_class:
        trees.append([
            Tree(np.asarray(tpc.feat[i]), np.asarray(tpc.split_bin[i]),
                 np.asarray(tpc.default_left[i]), np.asarray(tpc.is_split[i]),
                 np.asarray(tpc.leaf[i], np.float64))
            for i in range(tpc.ntrees)])
    tm = model.training_metrics
    return {"init_margin": np.asarray(b.init_margin, np.float64),
            "edges": np.asarray(b.trees_per_class[0].edges, np.float64),
            "trees": trees,
            "reported": {k: float(getattr(tm, k)) for k in reported_metrics(numbers)
                         if getattr(tm, k, None) is not None}}


def judged_rounds(built: int, block: int) -> List[int]:
    """Round 0, a round in the middle of the first block (the state handed
    from tree to tree inside a block, at a round where gradients are no
    longer two-valued) and, where a second block was built, its first round
    (the state handed from block to block).  Two rounds for three where
    only one block was built keeps the check shorter than the window."""
    rounds = [0, block // 2] + ([block] if built > block else [])
    return [r for r in rounds if r < built] or [0]


def compare_one(config: dict, seed: int, X, y, classes: int,
                answer: dict, block: int, numbers) -> Dict[str, float]:
    p = RefParams.from_config(config["params"], seed)
    yf = y.astype(np.float64)
    built = len(answer["trees"][0])
    if built == 0:
        return {k: float("inf") for k in numbers}
    codes = bin_codes(X, answer["edges"])
    f0 = init_margin(p.distribution, yf, classes)
    out = {"bin_rank_gap": bin_rank_gap(codes, p.nbins),
           "init_margin_gap": float(np.abs(answer["init_margin"] - f0).max())}
    judged = judge(codes, yf, p, answer, judged_rounds(built, block), classes)
    for key, rep in judged["by_round"].items():
        print(f"judged round.class {key}: " + " ".join(
            f"{k}={v:.4g}" for k, v in rep.items()), file=sys.stderr)
    out.update({k: judged[k] for k in JUDGED})
    mine = score(codes, yf, p, answer, classes)
    theirs = answer["reported"]
    for name in reported_metrics(numbers):
        if name not in mine:
            raise SystemExit(f"the reference computes no {name!r} for "
                             f"{p.distribution}: it has {sorted(mine)}")
        gap = abs(theirs.get(name, float("inf")) - mine[name])
        out[name + "_gap"] = gap if name in ABSOLUTE else gap / abs(mine[name])
    unknown = [k for k in numbers if k not in out]
    if unknown:
        raise SystemExit(f"no way to compute the limits' numbers {unknown}")
    return {k: float(out[k]) if np.isfinite(out[k]) else float("inf") for k in numbers}


def compare(config: dict, seed: int, table: dict, answers: List[dict],
            block: int, numbers) -> Dict[str, float]:
    """Worst reading of each of ``numbers`` over the window's answers;
    ``table`` holds ``X``, ``y``, ``classes`` and ``columns``."""
    worst = {k: 0.0 for k in numbers}
    for answer in answers:
        one = compare_one(config, seed, table["X"], table["y"], table["classes"],
                          answer, block, numbers)
        for k, v in one.items():
            worst[k] = max(worst[k], v)
    return worst
