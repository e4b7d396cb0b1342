"""The plain reference ``hist-gbm-classes``: ``hist-gbm-sets`` (set-valued
splits on categorical columns, numpy float64) for a multinomial fit of K
class trees a round, with H2O GBM's bound on a multinomial leaf and its
logloss, judged where a float32 sum can see.

It imports nothing of the program.  Everything but four rules is loaded
from ``hist-gbm-sets`` beside this file (and through it ``hist-gbm``): the
codes, the candidates of a node, its split gaps, the routing, the walk, the
gradients, the sampling stream and the control's rounding.  What a K-class
fit of H2O's GBM adds, and what its saturated rows ask of a judge, is here:

1. the leaf.  For class tree k the learn-rate scaled Newton step -lr G/H of
   a node's rows (g = p_k - 1[y = k], h = max(p_k(1 - p_k), 1e-16)) is
   clamped to +-1e4 (hex/tree/gbm/GBM.java clamps a multinomial leaf,
   whose margin is exponentiated).  A class of a few rows in millions takes
   steps of lr/p_k on its first round, and a row whose class probability
   underflowed sums its gradient over the hessian's floor: without the
   clamp such a leaf reaches 1e12 and the comparison is between two
   roundings of that, not between two fits.  The split gaps are
   ``hist-gbm-sets``' grow's; the leaf gaps are read here, from the rows each
   terminal node of the judged tree holds, against the clamped leaf;
2. the logloss: -log of the true class's softmax probability, that
   probability no less than 1e-15 (H2O's ``ModelMetricsMultinomial``).  A
   row the model holds impossible weighs 34.5, not its margin;
3. the candidates a judge holds a split against: those whose sides' Σh a
   float32 sum of the node's rows can tell from zero (``_resolvable``); the
   planted fault ``small_sides_blind``, a builder that sees no side under
   2^-12 of its node's Σh, shows that a split missed on a side the rule
   keeps is still held short;
4. the sums a judge holds a tree's choices against: those of the exact
   gradients and, where those find a choice short, those of the gradients
   rounded to the configuration's stated precision; a tree is short by the
   lesser reading.  The table's rows are 78% exact copies and a round's
   rows share few gradient values, so rounding them to bfloat16 moves a
   node's sums by one systematic 2^-9, not by a mean of independent
   roundings: at a node of 3.9M rows whose best split gains 1e-5 of its
   G^2/H, that alone picks another threshold (``hist-gbm-sets``'
   ``stated`` allows the same for the order of a categorical's levels).

And one economy: a fit, a judge and a walk run over the table's distinct
(codes, response) rows, each counted as the rows it stands for
(``distinct``; the KDD table is 78% exact duplicates), where every row is
sampled.

The numbers are ``hist-gbm``'s eight and no other; ``compare``, ``boost``
and ``judge`` take what ``hist-gbm-sets``' take.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence

import numpy as np


def _load_sets():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "hist-gbm-sets.py")
    spec = importlib.util.spec_from_file_location("references_hist_gbm_sets_shared", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks its module up by name
    spec.loader.exec_module(mod)
    return mod


sets = _load_sets()
base = sets.base

#: a side of a candidate whose hessian sum is under this share of its
#: node's is not told from zero by a float32 sum of the node's rows: the
#: judge's own ``slack`` on a gain (``hist-gbm-sets``' grow), 2^-20 of the
#: node's G^2/H, is the same share, 16 float32 roundings (2^-24) for the
#: bfloat16 operands and the sums of a level
RESOLUTION = 2.0 ** -20
#: the share under which the planted fault ``small_sides_blind`` sees no
#: side: above ``RESOLUTION``, so the splits it misses are ones a judge keeps
BLIND = 2.0 ** -12
_all_candidates = sets._candidates


def _without_small_sides(share: float):
    """``hist-gbm-sets``' candidates less those with a side whose Σh is
    under ``share`` of the node's."""
    def candidates(hist, cat, min_rows, whole_ties=False, stated=None):
        gains, totals, order, present = _all_candidates(hist, cat, min_rows, whole_ties,
                                                        stated)
        B = hist.shape[2] - 1
        H = totals[1][:, None, None]
        hl = np.cumsum(np.take_along_axis(hist[:, :, :B, 1], order, axis=2), axis=2)
        floor = share * np.abs(H)
        for side, hl_s in enumerate((hl, hl + hist[:, :, B, 1][:, :, None])):
            gains[..., side] = np.where((hl_s < floor) | (H - hl_s < floor), -np.inf,
                                        gains[..., side])
        return gains, totals, order, present
    return candidates


#: the candidates a judge holds a split against.  Rows whose class
#: probability has underflowed carry the hessian's floor, 1e-16; a side of
#: only such rows has a Σh under the rounding of the node's sum, and the gain
#: of the other side against the node's, GR^2/HR - G^2/H, is that
#: rounding's.  Such a candidate is no split a judge can hold the program
#: to; a program that takes one anyway is not held short (the gain of its
#: choice is read by ``_set_gain`` and passes the best)
_resolvable = _without_small_sides(RESOLUTION)


@contextmanager
def _searching(candidates):
    """``sets.grow`` searches ``candidates`` while this holds."""
    sets._candidates = candidates
    try:
        yield
    finally:
        sets._candidates = _resolvable


#: how many rows of the table each row handed to ``sets.grow`` stands for,
#: while ``counted`` holds it: the distinct rows of a table of duplicates
_MULT: Optional[np.ndarray] = None
_all_level_hist = base._level_hist


def _level_hist(codes, idx, local, g, h, K: int, B1: int, feats) -> np.ndarray:
    """``hist-gbm``'s level histogram, each row counted ``_MULT`` times: its
    g and h (as the caller rounded them) times its multiplicity, and the
    multiplicity in the count channel."""
    if _MULT is None:
        return _all_level_hist(codes, idx, local, g, h, K, B1, feats)
    F = codes.shape[0]
    hist = np.zeros((K, F, B1, 3))
    m = _MULT[idx]
    weights = (g * m, h * m, m)
    flat0 = local.astype(np.int64) * B1

    def one(f):
        flat = flat0 + codes[f, idx]
        for c, w in enumerate(weights):
            hist[:, f, :, c] = np.bincount(flat, weights=w, minlength=K * B1).reshape(K, B1)

    with ThreadPoolExecutor(base._THREADS) as ex:
        list(ex.map(one, feats))
    return hist


# this module's own copies of hist-gbm-sets and hist-gbm (not the ones a
# configuration that names either loads) search the resolvable candidates
# and count a distinct row as the rows it stands for
sets._candidates = _resolvable
base._level_hist = _level_hist


@contextmanager
def counted(mult: Optional[np.ndarray]):
    global _MULT
    _MULT = mult
    try:
        yield
    finally:
        _MULT = None


#: the table ``distinct`` was last asked for, and its answer: the judge and
#: the score of one answer ask for the same one
_LAST: list = []


def distinct(codes: np.ndarray, y: np.ndarray):
    """The distinct (codes, response) rows of a table: (codes [F, U], y [U],
    multiplicity [U] float64, each row's distinct row [N]).  Rows alike in
    both are alike in every margin, gradient and route, so a fit or a judge
    over the distinct rows, each counted as many times as it stands for, is
    the one over all rows (float64 sums in another order): the KDD table's
    4,898,431 rows are 1,074,992 distinct ones."""
    if _LAST and _LAST[0] is codes and _LAST[1] is y:
        return _LAST[2]
    F, n = codes.shape
    small = max(int(codes.max(initial=0)), int(y.max(initial=0))) < 256
    rows = np.empty((n, F + 1), np.uint8 if small else np.uint16)
    rows[:, :F] = codes.T
    rows[:, F] = y
    key = rows.view(np.dtype((np.void, rows.itemsize * (F + 1)))).ravel()
    _, first, inverse, count = np.unique(key, return_index=True, return_inverse=True,
                                         return_counts=True)
    out = (np.ascontiguousarray(codes[:, first]), y[first], count.astype(np.float64),
           inverse.ravel())
    _LAST[:] = [codes, y, out]
    return out


def rows_of(codes, y, p):
    """(codes, y, multiplicity) a fit or judge runs over: the distinct rows
    where every row is sampled (the sample is drawn a row at a time, so it
    would tell duplicates apart), else all rows once."""
    if p.sample_rate < 1.0:
        return codes, y, None
    return distinct(codes, y)[:3]

JUDGED = base.JUDGED
NUMBERS = base.NUMBERS
FAULTS = sets.FAULTS + ("small_sides_blind",)
RefParams = base.RefParams
Tree = sets.Tree
init_margin = base.init_margin
judged_rounds = base.judged_rounds
bin_codes = sets.bin_codes
quantile_edges = sets.quantile_edges
cat_levels_of = sets.cat_levels_of
stated_precision = sets.stated_precision
flip_one_bit = sets.flip_one_bit
extract = sets.extract
walk = sets.walk

#: H2O GBM's bound on a multinomial leaf
LEAF_MAX = 1e4
#: H2O's floor on a probability inside the logloss
EPS = 1e-15


def clamp(leaf: np.ndarray, p, C: int) -> np.ndarray:
    """A multinomial fit's leaves held to +-``LEAF_MAX``."""
    if p.distribution == "multinomial" and C > 1:
        return np.clip(leaf, -LEAF_MAX, LEAF_MAX)
    return leaf


def leaf_gaps(tree, pos: np.ndarray, g: np.ndarray, h: np.ndarray,
              rows: np.ndarray, p, C: int, mult: Optional[np.ndarray] = None
              ) -> Dict[str, float]:
    """``leaf_gap`` and ``leaf_gap_mean`` of a judged tree, as
    ``hist-gbm``'s grow reads them, against the clamped leaf of the rows
    that each terminal node holds (``pos``, after the tree's own routing),
    a row counted ``mult`` times."""
    M = len(tree.leaf)
    m = np.ones(len(pos)) if mult is None else mult
    nrows = np.bincount(pos, weights=m, minlength=M)
    G = np.bincount(pos[rows], weights=(g * m)[rows], minlength=M)
    H = np.bincount(pos[rows], weights=(h * m)[rows], minlength=M)
    ref = clamp(-p.learn_rate * G / np.maximum(H, 1e-12), p, C)
    term = (nrows > 0) & ~tree.is_split
    scale = np.median(np.abs(ref[term])) if term.any() else 1.0
    den = np.maximum(np.abs(ref[term]), scale)
    lg = np.abs(np.asarray(tree.leaf, np.float64)[term] - ref[term]) / np.where(
        den > 0, den, 1.0)
    return {"leaf_gap": float(lg.max(initial=0.0)),
            "leaf_gap_mean": float((lg * nrows[term]).sum()
                                   / max(nrows[term].sum(), 1.0))}


def boost(codes, y, p, rounds: int, cat_levels: Sequence[int], nclasses: int = 1,
          precision: str = "float64", fault=None) -> Dict:
    """``hist-gbm-sets``' boost with the multinomial leaf, over the
    distinct rows (``rows_of``)."""
    C = nclasses if p.distribution == "multinomial" else 1
    f0 = init_margin(p.distribution, y, nclasses)
    codes, y, mult = rows_of(codes, y, p)
    with counted(mult):
        return _boost(codes, y, p, f0, rounds, cat_levels, C, precision, fault)


def _boost(codes, y, p, f0, rounds, cat_levels, C, precision, fault) -> Dict:
    F, n = codes.shape
    margin = np.tile(f0, (n, 1))
    model: List[List] = [[] for _ in range(C)]
    for t in range(rounds):
        g, h = base.grad_hess(p.distribution, y, margin)
        rows, cols = base.round_sample(p.seed, t, n, F, p)
        hist_rows = rows & (np.arange(n) % 2 == 0) if fault == "half_batch" else None
        search = _without_small_sides(BLIND) if fault == "small_sides_blind" else _resolvable
        for c in range(C):
            with _searching(search):
                tree, pos, _ = sets.grow(
                    codes, g[:, c], h[:, c], rows, cols, p, cat_levels,
                    g_hist=base.round_to(g[:, c], precision),
                    h_hist=base.round_to(h[:, c], precision), hist_rows=hist_rows,
                    build_cat=[0] * F if fault == "label_codes" else None)
            tree.leaf = clamp(tree.leaf, p, C)
            if fault == "leaf_altered":
                tree.leaf *= 1.1
            if fault != "state_unchanged":
                margin[:, c] += tree.leaf[pos]
            model[c].append(tree)
    return {"init_margin": f0, "trees": model}


def judge(codes, y, p, model: Dict, rounds: Sequence[int], cat_levels: Sequence[int],
          nclasses: int = 1, stated: str = "float64") -> Dict[str, float]:
    """``hist-gbm-sets``' judge with the multinomial leaf: split gaps from
    its grow, leaf gaps from ``leaf_gaps``, over the distinct rows."""
    C = len(model["trees"])
    f0 = init_margin(p.distribution, y, nclasses)
    codes, y, mult = rows_of(codes, y, p)
    with counted(mult):
        return _judge(codes, y, p, f0, model, rounds, cat_levels, C, stated, mult)


def held(codes, g, h, rows, cols, p, cat_levels, tree, stated: str):
    """Each row's terminal node in ``tree`` and the tree's split gaps
    (``hist-gbm-sets``' grow's) against the sums of the exact g and h and,
    where those find a choice short, against the sums of g and h rounded to
    ``stated``: the lesser reading of each (point 4 of the header)."""
    _, pos, rep = sets.grow(codes, g, h, rows, cols, p, cat_levels, follow=tree,
                            stated=stated)
    if stated != "float64" and (rep["split_gap"] > 0 or rep["gain_forgone"] > 0):
        _, _, rounded = sets.grow(codes, g, h, rows, cols, p, cat_levels, follow=tree,
                                  stated=stated, g_hist=base.round_to(g, stated),
                                  h_hist=base.round_to(h, stated))
        for k in ("split_gap", "gain_forgone"):
            rep[k] = min(rep[k], rounded[k])
    return pos, rep


def _judge(codes, y, p, f0, model, rounds, cat_levels, C, stated, mult):
    F, n = codes.shape
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
            tree = model["trees"][c][t]
            pos, rep = held(codes, g[:, c], h[:, c], rows, cols, p, cat_levels, tree, stated)
            rep.update(leaf_gaps(tree, pos, g[:, c], h[:, c], rows, p, C, mult))
            out["by_round"][f"{t}.{c}"] = rep
            out["split_gap"] = max(out["split_gap"], rep["split_gap"])
            out["leaf_gap"] = max(out["leaf_gap"], rep["leaf_gap"])
            if t >= base.LATE_ROUND:
                out["leaf_gap_mean"] = max(out["leaf_gap_mean"], rep["leaf_gap_mean"])
            short.append(rep["gain_forgone"])
    out["gain_forgone"] = float(np.mean(short)) if short else 0.0
    return out


def score(codes, y, p, model: Dict, nclasses: int = 1) -> Dict[str, float]:
    """Training metrics of the whole model, float64: a multinomial's
    logloss with the true class's probability floored at 1e-15."""
    C = len(model["trees"])
    if not (p.distribution == "multinomial" and C > 1):
        return sets.score(codes, y, p, model, nclasses)
    codes, y, mult = distinct(codes, y)[:3]  # a walk is the codes' alone
    margin = np.tile(np.asarray(model["init_margin"], np.float64), (len(y), 1))
    for c in range(C):
        margin[:, c] += walk(codes, model["trees"][c])
    z = margin - margin.max(axis=1, keepdims=True)
    prob = np.exp(z) / np.exp(z).sum(axis=1, keepdims=True)
    py = prob[np.arange(len(y)), y.astype(np.int64)]
    loss = -np.log(np.clip(py, EPS, 1.0))
    return {"logloss": float((loss * mult).sum() / mult.sum())}


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
        raise SystemExit(f"hist-gbm-classes: the model's levels {answer['cat_levels']} "
                         f"are not the table's {cat_levels}")
    foreign = sets.foreign_level_maps(table["columns"] or [], answer["cat_domains"])
    if foreign:
        raise SystemExit(f"hist-gbm-classes: the model numbers the levels of {foreign} "
                         "otherwise than the table does")
    codes = bin_codes(X, answer["edges"], cat_levels)
    numeric = [f for f in range(X.shape[1]) if not cat_levels[f]]
    f0 = init_margin(p.distribution, yf, classes)
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
