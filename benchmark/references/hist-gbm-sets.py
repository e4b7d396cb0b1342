"""The plain reference ``hist-gbm-sets``: histogram gradient boosting with
set-valued splits on categorical columns, numpy float64, with the extraction
and the comparison that decide ``correct`` for a configuration that names it.

It imports nothing of the program.  The arithmetic it shares with
``hist-gbm`` (gradients, the starting margin, the sampling stream, the
rounding of the control, logloss and AUC, the quantile check of the numeric
bins, which rounds are judged) is loaded from that file, beside this one;
what a set-valued split changes is here: the codes, the candidates of a
node, the routing, the walk.  Same interface (``NUMBERS``, ``extract``,
``compare``; ``boost`` / ``judge`` for the control tool) and the same teacher
forcing: the rows are binned with the model's own edges and level maps, the
margin before a judged round is recomputed from the model's earlier trees,
rows are routed by the judged tree's own splits (its sets included), and at
every node every candidate's gain and the Newton leaf are computed in
float64 from exact gradients.

Semantics (the configuration's ``guarantees.split`` states them).  For a node
with sums (G_l, H_l, C_l) over the levels l of a categorical feature, the NA
bucket, and totals G, H, C:

1. the levels with C_l > 0 are ordered by G_l / H_l ascending, ties by l;
2. the candidates are the prefixes S_j of that order, j = 1..(levels
   present), each with the NA bucket on the left or on the right (the whole
   order with NA on the right is the split of NA against the rest); the
   gain is ``0.5 * (GL^2/HL + GR^2/HR - G^2/H)`` and both children hold at
   least ``min_rows`` rows;
3. the winner over every feature — thresholds of the numeric ones, prefixes
   of the categorical ones — is the split; a categorical's is kept as the set
   of codes that go left;
4. a row goes left iff its code is in the set; NA, a level that no row of the
   node had at fit and a code past the levels known at fit follow
   ``default_left``;
5. a categorical's code is its level's index, NA the last bucket; a numeric
   column keeps its quantile bins.  Every feature's bin axis has the width of
   the widest (``max(nbins, most levels) + 1``).

A departure from H2O-3, noted here as the issue asks: H2O's ``DTree`` orders
a categorical's bins by their mean response.  For a gaussian fit that is this
order (H_l = C_l); for any other family this file orders by the second-order
ratio G_l / H_l, of which the mean response is the first-order form, because
for the Newton objective the best two-way partition of the levels is a
prefix of the G/H order.

What a judge holds a choice against.  The order of point 1 is only as sharp
as the arithmetic that made the sums: where two levels' ratios are one value,
or nearer than the rounding of the gradients to the configuration's stated
precision can tell apart, a faithful split search may hold either order, and
with ``min_rows`` at work two orders of the same levels reach different
prefixes.  So a judge's best is taken over the prefixes that every such order
holds: those that end at no run of equal ratios, and that are prefixes of the
order of the exact sums AND of the order of the sums of gradients rounded to
``precision.stated``.  A choice whose exact gain reaches that best forgoes
nothing; one that does not is short by what ``split_gap`` and
``gain_forgone`` say.  Where rounding reorders nothing (every node with many
rows a level) the two orders are one and the judge's best is the best prefix.

The numbers are ``hist-gbm``'s eight and no other.  A model whose level
map is not the table's (a categorical's code is the level's index in the
table's domain) is an error of ``compare``, not a reading.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


def _load_base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "hist-gbm.py")
    spec = importlib.util.spec_from_file_location("references_hist_gbm_shared", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks its module up by name
    spec.loader.exec_module(mod)
    return mod


base = _load_base()

JUDGED = base.JUDGED
NUMBERS = base.NUMBERS
RefParams = base.RefParams
init_margin = base.init_margin
judged_rounds = base.judged_rounds


@dataclass
class Tree:
    """Heap layout as ``hist-gbm``'s, and ``left`` [M, B] bool: whether a
    row with code b of the node's feature goes left."""

    feat: np.ndarray
    split_bin: np.ndarray
    default_left: np.ndarray
    is_split: np.ndarray
    leaf: np.ndarray  # learn-rate scaled
    left: np.ndarray


def na_code(nbins: int, cat_levels: Sequence[int]) -> int:
    return max([int(nbins), *(int(v) for v in cat_levels)])


def unpack_words(words: np.ndarray, B: int) -> np.ndarray:
    """[M, ceil(B/32)] uint32, bit b & 31 of a node's word b >> 5 standing
    for code b -> [M, B] bool."""
    w = np.ascontiguousarray(np.asarray(words).astype("<u4"))
    return np.unpackbits(w.view(np.uint8), axis=1, bitorder="little")[:, :B].astype(bool)


# ---------------------------------------------------------------------------
# binning


def bin_codes(X: np.ndarray, edges: np.ndarray, cat_levels: Sequence[int]) -> np.ndarray:
    """Feature-major codes [F, n]: a numeric feature's number of edges <= x,
    a categorical's level index; NaN, and a level outside the levels known,
    take the last bucket."""
    n, F = X.shape
    na = na_code(edges.shape[1] + 1, cat_levels)
    out = np.empty((F, n), np.uint8 if na + 1 <= 256 else np.uint16)
    for f in range(F):
        col = X[:, f].astype(np.float64)
        if cat_levels[f]:
            known = (col >= 0) & (col < cat_levels[f])
            out[f] = np.where(known, col, na)
        else:
            c = np.searchsorted(edges[f], col, side="right")
            c[np.isnan(col)] = na
            out[f] = c
    return out


def quantile_edges(X: np.ndarray, nbins: int, cat_levels: Sequence[int]) -> np.ndarray:
    """Exact quantile edges [F, nbins-1] of the numeric columns (a
    categorical's row is never read): what a builder that is not the program
    bins with."""
    qs = np.linspace(0, 1, nbins + 1)[1:-1]
    return np.stack([np.full(nbins - 1, np.inf) if cat_levels[f] else
                     np.quantile(X[:, f].astype(np.float64), qs)
                     for f in range(X.shape[1])])


def foreign_level_maps(columns: List[dict], model_domains: Dict[str, list]) -> List[str]:
    """Names of the categorical columns whose levels the model numbers
    otherwise than the table does (a code is the level's index in the
    table's domain)."""
    return [c["name"] for c in columns
            if c["type"] == "cat" and list(model_domains.get(c["name"], ())) != list(c["domain"])]


# ---------------------------------------------------------------------------
# one tree, level by level


def _score(g, h):
    return g * g / np.maximum(h, 1e-12)


#: ratios nearer than this share of the larger are one value to a judge: no
#: float32 sum of bfloat16 gradients tells them apart
TIE = 1e-6


def _ratios(hist: np.ndarray):
    """G_l / H_l of the levels present [K, F, B] (inf elsewhere), and which
    are present."""
    real = hist[:, :, :-1, :]
    present = real[..., 2] > 0
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(present, real[..., 0] / np.where(present, real[..., 1], 1.0), np.inf)
    return ratio, present


def _candidates(hist: np.ndarray, cat: np.ndarray, min_rows: float,
                whole_ties: bool = False, stated: Optional[np.ndarray] = None):
    """Gains [K, F, B, 2] (NA right, NA left) of every candidate, the node
    totals, and, for reading a categorical's candidate back, the order of
    its levels [K, F, B] and which are present.  Position j of a numeric
    feature is the threshold ``code <= j``; of a categorical, the first j+1
    levels of its order.

    ``whole_ties`` (a judge's): a categorical's prefix may not end inside a
    run of levels with one ratio.  Levels with few rows tie in exact
    arithmetic (in round 0 every level with the same share of positives),
    any rounding orders such a run its own way, and with ``min_rows`` at
    work two orders of one run reach different prefixes; the prefixes that
    keep every run whole are the candidates that every order holds, so their
    best is what any faithful split search reaches or passes.  ``stated``
    (a judge's too) is the histogram of the gradients rounded to the
    configuration's stated precision: a prefix must also be one of ITS order,
    with no level outside it at or under the prefix's largest ratio."""
    B = hist.shape[2] - 1
    tot = hist.sum(axis=2)
    f0 = int(np.argmax(tot[..., 2].sum(axis=0)))  # any built feature: totals agree
    G, H, CNT = tot[:, f0, 0], tot[:, f0, 1], tot[:, f0, 2]
    real = hist[:, :, :B, :]
    na = hist[:, :, B, :]
    ratio, present = _ratios(hist)
    key = np.where(cat[None, :, None], ratio, np.arange(B, dtype=np.float64)[None, None, :])
    order = np.argsort(key, axis=2, kind="stable")  # ties by level
    ordered = np.take_along_axis(real, order[..., None], axis=2)
    cum = np.cumsum(ordered, axis=2)
    n_present = present.sum(axis=2)
    # a categorical's prefix holds levels that are present: at most all of
    # them (then the NA bucket alone is the other side)
    allowed = ~cat[None, :, None] | (np.arange(B)[None, None, :] < n_present[..., None])
    if whole_ties:
        ks = np.take_along_axis(key, order, axis=2)
        with np.errstate(invalid="ignore"):
            apart = (ks[..., 1:] - ks[..., :-1]) > TIE * np.maximum(
                np.abs(ks[..., 1:]), np.abs(ks[..., :-1]))
        allowed[..., :-1] &= apart | ~cat[None, :, None]
    if stated is not None:
        # along the exact order: the largest rounded ratio of the prefix
        # against the smallest of the rest
        rs = np.take_along_axis(_ratios(stated)[0], order, axis=2)
        top = np.maximum.accumulate(rs, axis=2)[..., :-1]
        rest = np.minimum.accumulate(rs[..., ::-1], axis=2)[..., ::-1][..., 1:]
        with np.errstate(invalid="ignore"):
            apart = (rest - top) > TIE * np.maximum(np.abs(rest), np.abs(top))
        allowed[..., :-1] &= apart | ~cat[None, :, None]
    parent = _score(G, H)[:, None, None]
    out = []
    for left in (cum, cum + na[:, :, None, :]):
        gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
        gr, hr, cr = G[:, None, None] - gl, H[:, None, None] - hl, CNT[:, None, None] - cl
        gain = 0.5 * (_score(gl, hl) + _score(gr, hr) - parent)
        out.append(np.where((cl >= min_rows) & (cr >= min_rows) & allowed, gain, -np.inf))
    return np.stack(out, axis=-1), (G, H, CNT), order, present


def _set_gain(hist_kf: np.ndarray, left: np.ndarray, dl: np.ndarray, totals,
              min_rows: float) -> np.ndarray:
    """Gain of the partition a tree holds for each node: hist_kf [K, B+1, 3]
    of the node's feature, left [K, B] the codes that go left, dl [K]."""
    G, H, CNT = totals
    B = hist_kf.shape[1] - 1
    side = (hist_kf[:, :B, :] * left[:, :, None]).sum(axis=1) + hist_kf[:, B, :] * dl[:, None]
    gl, hl, cl = side[:, 0], side[:, 1], side[:, 2]
    gain = 0.5 * (_score(gl, hl) + _score(G - gl, H - hl) - _score(G, H))
    return np.where((cl >= min_rows) & (CNT - cl >= min_rows), gain, -np.inf)


def grow(
    codes: np.ndarray, g: np.ndarray, h: np.ndarray, rows: np.ndarray,
    cols: np.ndarray, p, cat_levels: Sequence[int], follow: Optional[Tree] = None,
    g_hist: Optional[np.ndarray] = None, h_hist: Optional[np.ndarray] = None,
    hist_rows: Optional[np.ndarray] = None, build_cat: Optional[Sequence[int]] = None,
    stated: str = "float64",
):
    """Build (``follow`` None) or judge (``follow`` a tree) one tree, as
    ``hist-gbm``'s ``grow`` does.  ``build_cat`` is a builder's own idea of
    which features are categorical (a planted fault: none are, and the level
    codes are cut by thresholds); a judge goes by ``cat_levels``, and by
    ``stated``, the precision the configuration states for the gradients
    that are summed."""
    F, n = codes.shape
    D = p.max_depth
    B = na_code(p.nbins, cat_levels)
    B1 = B + 1
    M = 2 ** (D + 1) - 1
    msi = max(p.min_split_improvement, 0.0)
    cat = np.asarray(cat_levels if build_cat is None else build_cat) > 0
    gq = g if g_hist is None else g_hist
    hq = h if h_hist is None else h_hist
    sample = rows if hist_rows is None else hist_rows
    feats = np.flatnonzero(cols)
    rounded = follow is not None and stated != "float64" and cat.any()
    if rounded:
        g_st, h_st = base.round_to(g, stated), base.round_to(h, stated)
    tree = Tree(np.zeros(M, np.int32), np.zeros(M, np.int32), np.zeros(M, bool),
                np.zeros(M, bool), np.zeros(M), np.zeros((M, B), bool))
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
        hist = base._level_hist(codes, idx, local, gq[idx], hq[idx], K, B1,
                                feats if d < D else feats[:1])
        hist_st = base._level_hist(
            codes, idx, local, g_st[idx], h_st[idx], K, B1,
            feats[cat[feats]]) if rounded and d < D else None
        gains, totals, order, present = _candidates(
            hist, cat, p.min_rows, whole_ties=follow is not None, stated=hist_st)
        G, H, _ = totals
        leaf = -p.learn_rate * G / np.maximum(H, 1e-12)
        nrows[lo:lo + K] = np.bincount(pos[at] - lo, minlength=K)
        ref_leaf[lo:lo + K] = leaf
        if d == D:
            break
        gains[:, ~cols] = -np.inf
        flat = gains.reshape(K, -1)
        arg = flat.argmax(axis=1)
        best = flat[np.arange(K), arg]
        sl = slice(lo, lo + K)
        ks = np.arange(K)
        if follow is None:
            bf, bj, bdl = np.unravel_index(arg, gains.shape[1:])
            can = (best > msi) & np.isfinite(best)
            # the codes that go left: the first bj+1 of the feature's order,
            # and the NA side for a categorical's level the node has no row of
            rank = np.argsort(order[ks, bf], axis=1)  # code -> position
            left = rank <= bj[:, None]
            unseen = cat[bf][:, None] & ~present[ks, bf]
            tree.feat[sl], tree.split_bin[sl] = bf, bj
            tree.default_left[sl] = bdl.astype(bool)
            tree.is_split[sl] = can
            tree.left[sl] = np.where(unseen, bdl.astype(bool)[:, None], left)
        else:
            reach = nrows > 0
            f_k = follow.feat[sl]
            chosen = _set_gain(hist[ks, f_k], follow.left[sl] & (present[ks, f_k] | ~cat[f_k][:, None]),
                               follow.default_left[sl], totals, p.min_rows)
            # a set that sends no present level left is no candidate
            n_left = (follow.left[sl] & present[ks, f_k]).sum(axis=1)
            chosen = np.where(cat[f_k] & (n_left == 0), -np.inf, chosen)
            # a level the node has no row of that does not follow the NA side
            stray = (cat[f_k][:, None] & ~present[ks, f_k]
                     & (follow.left[sl] != follow.default_left[sl][:, None])
                     & (np.arange(B)[None, :] < np.asarray(cat_levels)[f_k][:, None])).any(axis=1)
            chosen = np.where(cols[f_k] & ~stray, chosen, -np.inf)
            chosen = np.where(follow.is_split[sl], chosen, np.minimum(best, msi))
            live = reach[sl] & np.isfinite(best) & (best > msi)
            # what float32 rounds off the gain's three terms: a gain is not
            # known more closely to a program whose sums are float32
            slack = 2.0 ** -20 * _score(G, H)
            # a choice that passes the best of the candidates every order
            # holds (it cut a run of equal ratios well) forgoes nothing
            short = best - np.where(np.isfinite(chosen), chosen + slack, 0.0)
            gap = np.where(live, np.maximum(short, 0.0) / np.where(live, best, 1.0), 0.0)
            # a split the reference holds impossible: min_rows, an unseen
            # level astray, or a gain under min_split_improvement by more
            # than the rounding
            no_gain = ~np.isfinite(chosen) | (~live & (chosen + slack <= msi))
            gap = np.where(reach[sl] & follow.is_split[sl] & no_gain, 1.0, gap)
            split_gaps.extend(gap[reach[sl]].tolist())
            short_sum += float((gap * np.where(live, best, 0.0)).sum())
            best_sum += float(np.where(live, best, 0.0).sum())
        t = tree if follow is None else follow
        node = lo + (pos[at] - lo)
        code = codes[t.feat[node], np.flatnonzero(at)].astype(np.int64)
        go_left = np.where(code >= B, t.default_left[node],
                           t.left[node, np.minimum(code, B - 1)])
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


def walk(codes: np.ndarray, trees: Sequence[Tree]) -> np.ndarray:
    """Sum of the trees' leaf values for every row, float64."""
    from concurrent.futures import ThreadPoolExecutor

    F, n = codes.shape
    out = np.zeros(n)
    if not trees:
        return out
    D = int(np.log2(len(trees[0].feat) + 1)) - 1
    B = trees[0].left.shape[1]
    step = max(1, -(-n // (4 * base._THREADS)))

    def chunk(s):
        e = min(n, s + step)
        r = np.arange(s, e)
        acc = np.zeros(e - s)
        for t in trees:
            pos = np.zeros(e - s, np.int32)
            for _ in range(D):
                code = codes[t.feat[pos], r].astype(np.int64)
                go_left = np.where(code >= B, t.default_left[pos],
                                   t.left[pos, np.minimum(code, B - 1)])
                pos = np.where(t.is_split[pos], 2 * pos + np.where(go_left, 1, 2), pos)
            acc += t.leaf[pos]
        out[s:e] = acc

    with ThreadPoolExecutor(base._THREADS) as ex:
        list(ex.map(chunk, range(0, n, step)))
    return out


# ---------------------------------------------------------------------------
# whole-model drivers

#: the planted faults ``boost`` knows: ``hist-gbm``'s three, and the one this
#: reference is for — thresholds on label codes under the name ``enum``
FAULTS = ("state_unchanged", "half_batch", "leaf_altered", "label_codes")


def flip_one_bit(model: Dict) -> Dict:
    """A planted fault of the scoring side: ``model`` with ONE bit of ONE
    set flipped, in the first tree whose root splits on a set: the first
    level of that set goes right.  A walk of the true sets then disagrees
    with metrics reported from the flipped ones."""
    import copy

    out = {"init_margin": model["init_margin"], "trees": copy.deepcopy(model["trees"])}
    for t in out["trees"][0]:
        if t.is_split[0] and t.left[0].any() and not t.left[0].all():
            t.left[0, int(np.argmax(t.left[0]))] = False
            return out
    raise ValueError("no tree splits on a set at its root")


def boost(codes, y, p, rounds: int, cat_levels: Sequence[int], nclasses: int = 1,
          precision: str = "float64", fault: Optional[str] = None) -> Dict:
    """The reference in the program's place, as ``hist-gbm``'s ``boost``.
    ``fault="label_codes"`` builds every tree as the program did before it
    knew sets: a categorical's level codes are ordinals, cut by thresholds."""
    F, n = codes.shape
    C = nclasses if p.distribution == "multinomial" else 1
    f0 = init_margin(p.distribution, y, nclasses)
    margin = np.tile(f0, (n, 1))
    model: List[List[Tree]] = [[] for _ in range(C)]
    for t in range(rounds):
        g, h = base.grad_hess(p.distribution, y, margin)
        rows, cols = base.round_sample(p.seed, t, n, F, p)
        hist_rows = rows & (np.arange(n) % 2 == 0) if fault == "half_batch" else None
        for c in range(C):
            tree, pos, _ = grow(
                codes, g[:, c], h[:, c], rows, cols, p, cat_levels,
                g_hist=base.round_to(g[:, c], precision),
                h_hist=base.round_to(h[:, c], precision), hist_rows=hist_rows,
                build_cat=[0] * F if fault == "label_codes" else None)
            if fault == "leaf_altered":
                tree.leaf *= 1.1
            if fault != "state_unchanged":
                margin[:, c] += tree.leaf[pos]
            model[c].append(tree)
    return {"init_margin": f0, "trees": model}


def judge(codes, y, p, model: Dict, rounds: Sequence[int], cat_levels: Sequence[int],
          nclasses: int = 1, stated: str = "float64") -> Dict[str, float]:
    """Teacher-forced judgement of ``model`` at the boosting rounds listed,
    as ``hist-gbm``'s ``judge``; ``stated`` is the configuration's
    ``precision.stated`` (see the header: what a judge holds a choice
    against)."""
    F, n = codes.shape
    C = len(model["trees"])
    f0 = init_margin(p.distribution, y, nclasses)
    out = {"split_gap": 0.0, "leaf_gap": 0.0, "leaf_gap_mean": 0.0,
           "gain_forgone": 0.0, "by_round": {}}
    margin = np.tile(f0, (n, 1))
    done = 0
    short = []
    for t in sorted(rounds):
        for c in range(C):
            margin[:, c] += walk(codes, model["trees"][c][done:t])
        done = t
        g, h = base.grad_hess(p.distribution, y, margin)
        rows, cols = base.round_sample(p.seed, t, n, F, p)
        for c in range(C):
            _, _, rep = grow(codes, g[:, c], h[:, c], rows, cols, p, cat_levels,
                             follow=model["trees"][c][t], stated=stated)
            out["by_round"][f"{t}.{c}"] = rep
            out["split_gap"] = max(out["split_gap"], rep["split_gap"])
            out["leaf_gap"] = max(out["leaf_gap"], rep["leaf_gap"])
            if t >= base.LATE_ROUND:
                out["leaf_gap_mean"] = max(out["leaf_gap_mean"], rep["leaf_gap_mean"])
            short.append(rep["gain_forgone"])
    out["gain_forgone"] = float(np.mean(short)) if short else 0.0
    return out


def score(codes, y, p, model: Dict, nclasses: int = 1) -> Dict[str, float]:
    """Training metrics of the whole model, float64 throughout."""
    C = len(model["trees"])
    margin = np.tile(np.asarray(model["init_margin"], np.float64), (len(y), 1))
    for c in range(C):
        margin[:, c] += walk(codes, model["trees"][c])
    if p.distribution == "gaussian":
        mse = float(np.mean((margin[:, 0] - y) ** 2))
        return {"mse": mse, "rmse": mse ** 0.5}
    out = {"logloss": base.logloss(p.distribution, y, margin)}
    if p.distribution == "bernoulli":
        out["auc"] = base.auc(y, margin[:, 0])
    return out


# ---------------------------------------------------------------------------
# what the harness calls: the program's answer, and its comparison


def extract(model, numbers) -> dict:
    """The program's answer as plain arrays: init margin, bin edges, level
    maps, trees with their sets, and the ``training_metrics`` entries that
    ``numbers`` ask for.  A model of a frame with categorical columns that
    holds no set for them did not fit what this reference judges: an error."""
    b = model.booster
    t0 = b.trees_per_class[0]
    cat_levels = tuple(int(v) for v in getattr(t0, "cat_levels", ()) or ())
    domains = {k: list(v) for k, v in model.data_info.cat_domains.items()}
    if domains and not any(cat_levels):
        raise SystemExit(
            "hist-gbm-sets: the model holds no set of levels for its categorical "
            f"columns {sorted(domains)}: it split their codes by thresholds "
            "(categorical_encoding was not taken as \"enum\")")
    B = int(t0.n_bins1) - 1
    trees = []
    for tpc in b.trees_per_class:
        trees.append([
            Tree(np.asarray(tpc.feat[i]), np.asarray(tpc.split_bin[i]),
                 np.asarray(tpc.default_left[i]), np.asarray(tpc.is_split[i]),
                 np.asarray(tpc.leaf[i], np.float64),
                 unpack_words(tpc.split_set[i], B))
            for i in range(tpc.ntrees)])
    tm = model.training_metrics
    return {"init_margin": np.asarray(b.init_margin, np.float64),
            "edges": np.asarray(t0.edges, np.float64),
            "cat_levels": cat_levels, "cat_domains": domains,
            "trees": trees,
            "reported": {k: float(getattr(tm, k)) for k in base.reported_metrics(numbers)
                         if getattr(tm, k, None) is not None}}


def cat_levels_of(columns: Optional[List[dict]], F: int) -> List[int]:
    """The table's own word on its columns: levels of a categorical, 0 else."""
    if columns is None:
        return [0] * F
    return [len(c["domain"]) if c["type"] == "cat" else 0 for c in columns]


def stated_precision(config: dict) -> str:
    """The precision the configuration states for the summed gradients."""
    return (config.get("precision") or {}).get("stated", "float64")


def compare_one(config: dict, seed: int, table: dict, answer: dict, block: int,
                numbers) -> Dict[str, float]:
    X, y, classes = table["X"], table["y"], table["classes"]
    p = RefParams.from_config(config["params"], seed)
    yf = y.astype(np.float64)
    built = len(answer["trees"][0])
    if built == 0:
        return {k: float("inf") for k in numbers}
    cat_levels = cat_levels_of(table["columns"], X.shape[1])
    if tuple(answer["cat_levels"]) != tuple(cat_levels):
        raise SystemExit(f"hist-gbm-sets: the model's levels {answer['cat_levels']} "
                         f"are not the table's {cat_levels}")
    codes = bin_codes(X, answer["edges"], cat_levels)
    numeric = [f for f in range(X.shape[1]) if not cat_levels[f]]
    f0 = init_margin(p.distribution, yf, classes)
    foreign = foreign_level_maps(table["columns"] or [], answer["cat_domains"])
    if foreign:
        raise SystemExit(f"hist-gbm-sets: the model numbers the levels of {foreign} "
                         "otherwise than the table does")
    out = {"bin_rank_gap": base.bin_rank_gap(codes[numeric], p.nbins) if numeric else 0.0,
           "init_margin_gap": float(np.abs(answer["init_margin"] - f0).max())}
    judged = judge(codes, yf, p, answer, judged_rounds(built, block), cat_levels, classes,
                   stated=stated_precision(config))
    for key, rep in judged["by_round"].items():
        print(f"judged round.class {key}: " + " ".join(
            f"{k}={v:.4g}" for k, v in rep.items()), file=sys.stderr)
    out.update({k: judged[k] for k in JUDGED})
    mine = score(codes, yf, p, answer, classes)
    theirs = answer["reported"]
    for name in base.reported_metrics(numbers):
        if name not in mine:
            raise SystemExit(f"the reference computes no {name!r} for "
                             f"{p.distribution}: it has {sorted(mine)}")
        gap = abs(theirs.get(name, float("inf")) - mine[name])
        out[name + "_gap"] = gap if name in base.ABSOLUTE else gap / abs(mine[name])
    unknown = [k for k in numbers if k not in out]
    if unknown:
        raise SystemExit(f"no way to compute the limits' numbers {unknown}")
    return {k: float(out[k]) if np.isfinite(out[k]) else float("inf") for k in numbers}


def compare(config: dict, seed: int, table: dict, answers: List[dict],
            block: int, numbers) -> Dict[str, float]:
    """Worst reading of each of ``numbers`` over the window's answers."""
    worst = {k: 0.0 for k in numbers}
    for answer in answers:
        for k, v in compare_one(config, seed, table, answer, block, numbers).items():
            worst[k] = max(worst[k], v)
    return worst
