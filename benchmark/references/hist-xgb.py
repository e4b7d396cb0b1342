"""The plain reference ``hist-xgb``: histogram gradient boosting with
XGBoost's regularised objective, numpy float64, with the extraction and the
comparison that decide ``correct`` for a configuration that names it.

It imports nothing of the program and knows nothing of chips: a fit whose
rows are dealt over several devices owes the same answers as one that is
not.  The arithmetic it shares with ``hist-gbm`` (binning and its quantile
check, the starting margin, the bernoulli gradients, the sampling stream,
the rounding of the control, which rounds are judged, the extraction) is
loaded from that file, beside this one; what XGBoost's parameters change is
here.  Same interface (``NUMBERS``, ``extract``, ``compare``; ``boost`` /
``judge`` for the control tool and the tests), the same eight numbers, the
same teacher forcing (``hist-gbm``'s header).

The passes over every row (the level histograms, the routing, the walk,
logloss and AUC) are this file's own, the same arithmetic as ``hist-gbm``'s
in runs of ``STEP`` rows on the threads: its table has 32M rows, the check
of a run has to end minutes before the run's time limit, and
``np.bincount`` holds the interpreter, so everything beside it is kept off
the one thread that sums.

Semantics (the configuration's ``guarantees`` state them).  With G, H the
sums of g and h over a node's sampled rows and GL, HL, GR, HR those of a
candidate's children (the NA bucket tried on both sides):

* ``scale_pos_weight`` s: g and h of a row of the positive class are s times
  the bernoulli gradient and hessian; counts stay rows; the starting margin
  and the reported metrics are unweighted;
* gain ``0.5 * (GL^2/(HL+lambda) + GR^2/(HR+lambda) - G^2/(H+lambda)) -
  gamma``, a node splits while ``gain > max(min_split_improvement, 0)`` and
  depth is left;
* ``min_child_weight`` w: a candidate needs ``HL >= w`` and ``HR >= w``
  (the sums of hessians, weighted as above); where the configuration gives
  none, both children hold at least ``min_rows`` rows instead;
* leaf ``-learn_rate * G / (H + lambda)``; every row is routed and gets the
  leaf added to its margin.

A judge holds the tree's own choice to the floor with the room of the
stated precision (``FLOOR_SLACK``): a child whose exact sum of hessians is
the floor to bfloat16's rounding was a lawful candidate to a program that
summed bfloat16 hessians.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


def _load_base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "hist-gbm.py")
    spec = importlib.util.spec_from_file_location("references_hist_gbm_for_xgb", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks its module up by name
    spec.loader.exec_module(mod)
    return mod


base = _load_base()
# this copy of the shared arithmetic is this file's own: on a host with many
# cores the passes over every row take them (a 32M-row table)
base._THREADS = max(base._THREADS, min(32, (os.cpu_count() or 8) - 2))

JUDGED = base.JUDGED
NUMBERS = base.NUMBERS
Tree = base.Tree
extract = base.extract
init_margin = base.init_margin
judged_rounds = base.judged_rounds

#: the share of the floor a judged tree's own choice may fall short of it:
#: bfloat16 keeps 8 bits of a hessian
FLOOR_SLACK = 2.0 ** -7
#: rows a run: a pass over every row is made in runs of this many, on the threads
STEP = 1 << 20
#: rows a run of the walk, whose temporaries then stay in a core's cache
WALK_STEP = 1 << 18


def _runs(n: int, fn, step: int = STEP) -> list:
    """``fn(s, e)`` over the runs of ``step`` rows of ``n``, on the threads,
    in order."""
    with ThreadPoolExecutor(base._THREADS) as ex:
        return list(ex.map(lambda s: fn(s, min(n, s + step)), range(0, n, step)))


def bin_codes(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Feature-major codes [F, n]: number of edges <= x; NaN -> nbins
    (``hist-gbm``'s, in runs of rows)."""
    n, F = X.shape
    nbins = edges.shape[1] + 1
    out = np.empty((F, n), np.uint8 if nbins + 1 <= 256 else np.uint16)

    def run(s, e):
        for f in range(F):
            col = X[s:e, f].astype(np.float64)
            c = np.searchsorted(edges[f], col, side="right")
            c[np.isnan(col)] = nbins
            out[f, s:e] = c

    _runs(n, run)
    return out


def _step_table(t: Tree, B1: int) -> np.ndarray:
    """[M * B1] heap node a row at node m with code c stands at one level
    on: the child its split sends it to (the NA code by ``default_left``),
    m itself where m does not split."""
    node = np.arange(len(t.feat))[:, None]
    code = np.arange(B1)[None, :]
    left = np.where(code >= B1 - 1, t.default_left[:, None], code <= t.split_bin[:, None])
    child = 2 * node + np.where(left, 1, 2)
    return np.where(t.is_split[:, None], child, node).astype(np.intp).ravel()


def _route(codes: np.ndarray, pos: np.ndarray, t: Tree, B1: int) -> None:
    """Every row one level down tree ``t``, in place."""
    table, feat = _step_table(t, B1), t.feat.astype(np.intp)

    def run(s, e):
        at = pos[s:e].astype(np.intp)
        pos[s:e] = table[at * B1 + codes[feat[at], np.arange(s, e)]]

    _runs(len(pos), run)


@dataclass(frozen=True)
class RefParams:
    distribution: str
    max_depth: int
    nbins: int
    learn_rate: float
    min_rows: float = 1.0
    min_split_improvement: float = 0.0
    reg_lambda: float = 1.0
    gamma: float = 0.0
    min_child_weight: Optional[float] = None
    scale_pos_weight: float = 1.0
    sample_rate: float = 1.0
    col_sample_rate_per_tree: float = 1.0
    seed: int = 0

    @staticmethod
    def from_config(params: dict, seed: int) -> "RefParams":
        keys = {f.name for f in dataclasses.fields(RefParams)} - {"seed"}
        return RefParams(seed=seed, **{k: params[k] for k in keys if k in params})


def grad_hess(p: RefParams, y: np.ndarray, margin: np.ndarray):
    """(g, h) [n, 1]: ``hist-gbm``'s, a positive row's times the class weight."""
    if p.scale_pos_weight != 1.0 and p.distribution != "bernoulli":
        raise ValueError("scale_pos_weight weighs the positive class of a binary response")
    g, h = np.empty(margin.shape), np.empty(margin.shape)

    def run(s, e):
        gs, hs = base.grad_hess(p.distribution, y[s:e], margin[s:e])
        w = np.where(y[s:e] > 0.5, p.scale_pos_weight, 1.0)[:, None]
        g[s:e], h[s:e] = gs * w, hs * w

    _runs(len(y), run)
    return g, h


def _gains(hist: np.ndarray, p: RefParams, slack: float = 0.0):
    """Candidate gains [K, F, B, 2] (NA right, NA left), gamma taken off,
    and the node totals; ``slack`` is the share of the hessian floor a child
    may fall short of it."""
    B = hist.shape[2] - 1
    tot = hist.sum(axis=2)  # [K, F, 3]
    f0 = int(np.argmax(tot[..., 1].sum(axis=0)))  # any built feature: totals agree
    G, H, CNT = tot[:, f0, 0], tot[:, f0, 1], tot[:, f0, 2]
    cum = np.cumsum(hist[:, :, :B, :], axis=2)
    na = hist[:, :, B, :]
    lam = p.reg_lambda

    def score(g, h):
        return g * g / np.maximum(h + lam, 1e-12)

    parent = score(G, H)[:, None, None]
    out = []
    for left in (cum, cum + na[:, :, None, :]):
        gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
        gr, hr, cr = G[:, None, None] - gl, H[:, None, None] - hl, CNT[:, None, None] - cl
        gain = 0.5 * (score(gl, hl) + score(gr, hr) - parent) - p.gamma
        if p.min_child_weight is None:
            ok = (cl >= p.min_rows) & (cr >= p.min_rows)
        else:
            floor = p.min_child_weight * (1.0 - slack)
            ok = (hl >= floor) & (hr >= floor)
        out.append(np.where(ok, gain, -np.inf))
    return np.stack(out, axis=-1), G, H, CNT


def _level_hist(codes, pos, sample, g, h, lo: int, K: int, B1: int, feats,
                counted: bool):
    """``hist-gbm``'s level histogram [K, F, B1, 3] (sum g, sum h, count)
    of the level whose first heap node is ``lo``, over the rows that stand
    on it (``pos >= lo``) and are in ``sample`` (None: every row), and the
    rows, sampled or not, that stand on each of its nodes.  With what a table
    of tens of millions of rows asks for: no gather (a row that is not
    summed goes to a slot past the level's last), and no count where nothing
    tests one (``counted`` False leaves the third channel zero: a hessian
    floor is in force)."""
    F, n = codes.shape
    slots = (K + 1) * B1

    def run(s, e):
        local = pos[s:e].astype(np.intp) - lo
        here = local >= 0
        standing = np.bincount(np.where(here, local, K), minlength=K + 1)[:K]
        if sample is not None:
            here &= sample[s:e]
        node = np.where(here, local, K) * B1
        part = np.zeros((len(feats), slots, 3))
        for i, f in enumerate(feats):
            flat = node + codes[f, s:e]
            for c, w in enumerate((g[s:e], h[s:e], None) if counted else (g[s:e], h[s:e])):
                part[i, :, c] = np.bincount(flat, weights=w, minlength=slots)
        return part, standing

    parts = _runs(n, run)
    hist = np.zeros((K, F, B1, 3))
    hist[:, feats] = sum(p for p, _ in parts).reshape(
        len(feats), K + 1, B1, 3)[:, :K].transpose(1, 0, 2, 3)
    return hist, sum(s for _, s in parts)


def grow(
    codes: np.ndarray, g: np.ndarray, h: np.ndarray, rows: np.ndarray,
    cols: np.ndarray, p: RefParams, follow: Optional[Tree] = None,
    g_hist: Optional[np.ndarray] = None, h_hist: Optional[np.ndarray] = None,
    hist_rows: Optional[np.ndarray] = None, build: Optional[RefParams] = None,
):
    """Build (``follow`` None) or judge (``follow`` a tree) one tree, as
    ``hist-gbm``'s ``grow`` does and with its arguments.  ``build`` is a
    builder's own idea of the parameters (a planted fault: another lambda,
    gamma, floor); a judge goes by ``p``."""
    F, n = codes.shape
    q = p if follow is not None or build is None else build
    D, B1 = q.max_depth, q.nbins + 1
    M = 2 ** (D + 1) - 1
    msi = max(q.min_split_improvement, 0.0)
    gq = g if g_hist is None else g_hist
    hq = h if h_hist is None else h_hist
    sample = rows if hist_rows is None else hist_rows
    sample = None if sample.all() else sample
    feats = np.flatnonzero(cols)
    tree = Tree(np.zeros(M, np.int32), np.zeros(M, np.int32), np.zeros(M, bool),
                np.zeros(M, bool), np.zeros(M))
    pos = np.zeros(n, np.int32)
    split_gaps: List[float] = []
    short_sum = best_sum = 0.0
    ref_leaf = np.zeros(M)
    nrows = np.zeros(M)  # rows (sampled or not) that pass through each node
    for d in range(D + 1):
        K, lo = 2 ** d, 2 ** d - 1  # rows stopped at shallower leaves stay below lo
        hist, nrows[lo:lo + K] = _level_hist(
            codes, pos, sample, gq, hq, lo, K, B1,
            feats if d < D else feats[:1], q.min_child_weight is None)
        gains, G, H, _ = _gains(hist, q)
        leaf = -q.learn_rate * G / np.maximum(H + q.reg_lambda, 1e-12)
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
            lawful = gains if q.min_child_weight is None else _gains(
                hist, q, slack=FLOOR_SLACK)[0]
            chosen = lawful[np.arange(K), follow.feat[sl], follow.split_bin[sl],
                            follow.default_left[sl].astype(np.int64)]
            chosen = np.where(follow.is_split[sl], chosen, np.minimum(best, msi))
            live = reach[sl] & np.isfinite(best) & (best > msi)
            gap = np.where(
                live, np.maximum(best - np.where(np.isfinite(chosen), chosen, 0.0), 0.0)
                / np.where(live, best, 1.0), 0.0)
            # a split the reference holds impossible (the floor, no gain left
            # after gamma)
            gap = np.where(reach[sl] & follow.is_split[sl]
                           & (~live | ~np.isfinite(chosen)), 1.0, gap)
            split_gaps.extend(gap[reach[sl]].tolist())
            short_sum += float((gap * np.where(live, best, 0.0)).sum())
            best_sum += float(np.where(live, best, 0.0).sum())
        _route(codes, pos, tree if follow is None else follow, B1)
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


# ---------------------------------------------------------------------------
# whole-model drivers

#: the planted faults ``boost`` knows: ``hist-gbm``'s three, a builder that
#: holds another value of one of XGBoost's parameters than the configuration
#: gives (no lambda, no gamma, the floor on row counts, no class weight), and
#: one shard of ``SHARDS`` equal runs of rows left out of every histogram's sum
FAULTS = ("state_unchanged", "half_batch", "leaf_altered", "lambda_zero",
          "gamma_zero", "count_floor", "no_class_weight", "shard_dropped")
SHARDS = 4


def faulty_params(p: RefParams, fault: Optional[str]) -> RefParams:
    """The parameters a builder with ``fault`` goes by."""
    change = {"lambda_zero": {"reg_lambda": 0.0}, "gamma_zero": {"gamma": 0.0},
              "count_floor": {"min_child_weight": None,
                              "min_rows": p.min_child_weight or p.min_rows},
              "no_class_weight": {"scale_pos_weight": 1.0}}.get(fault, {})
    return dataclasses.replace(p, **change)


def boost(codes, y, p: RefParams, rounds: int, nclasses: int = 1,
          precision: str = "float64", fault: Optional[str] = None) -> Dict:
    """The reference in the program's place, as ``hist-gbm``'s ``boost``:
    ``rounds`` boosting rounds from its own argmax, g and h rounded to
    ``precision`` before they are summed (the control), one of ``FAULTS``
    planted."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}: there are {FAULTS}")
    F, n = codes.shape
    q = faulty_params(p, fault)
    f0 = init_margin(p.distribution, y, nclasses)
    margin = np.tile(f0, (n, 1))
    trees: List[Tree] = []
    hist_rows = None
    for t in range(rounds):
        g, h = grad_hess(q, y, margin)
        rows, cols = base.round_sample(p.seed, t, n, F, p)
        if fault == "half_batch":
            hist_rows = rows & (np.arange(n) % 2 == 0)
        elif fault == "shard_dropped":  # the second of the equal runs of rows
            hist_rows = rows & (np.arange(n) * SHARDS // n != 1)
        tree, pos, _ = grow(
            codes, g[:, 0], h[:, 0], rows, cols, p,
            g_hist=base.round_to(g[:, 0], precision),
            h_hist=base.round_to(h[:, 0], precision), hist_rows=hist_rows, build=q)
        if fault == "leaf_altered":
            tree.leaf *= 1.1
        if fault != "state_unchanged":
            margin[:, 0] += tree.leaf[pos]
        trees.append(tree)
    return {"init_margin": f0, "trees": [trees]}


def judge(codes, y, p: RefParams, model: Dict, rounds: Sequence[int],
          nclasses: int = 1) -> Dict[str, float]:
    """Teacher-forced judgement of ``model`` at the boosting rounds listed,
    as ``hist-gbm``'s ``judge`` (one class tree a round)."""
    F, n = codes.shape
    B1 = p.nbins + 1
    trees = model["trees"][0]
    out = {"split_gap": 0.0, "leaf_gap": 0.0, "leaf_gap_mean": 0.0,
           "gain_forgone": 0.0, "by_round": {}}
    margin = np.tile(init_margin(p.distribution, y, nclasses), (n, 1))
    done = 0
    short = []
    for t in sorted(rounds):
        margin[:, 0] += walk(codes, trees[done:t], B1)
        done = t
        g, h = grad_hess(p, y, margin)
        rows, cols = base.round_sample(p.seed, t, n, F, p)
        _, _, rep = grow(codes, g[:, 0], h[:, 0], rows, cols, p, follow=trees[t])
        out["by_round"][f"{t}.0"] = rep
        out["split_gap"] = max(out["split_gap"], rep["split_gap"])
        out["leaf_gap"] = max(out["leaf_gap"], rep["leaf_gap"])
        if t >= base.LATE_ROUND:
            out["leaf_gap_mean"] = max(out["leaf_gap_mean"], rep["leaf_gap_mean"])
        short.append(rep["gain_forgone"])
    out["gain_forgone"] = float(np.mean(short)) if short else 0.0
    return out


def walk(codes: np.ndarray, trees: Sequence[Tree], B1: int) -> np.ndarray:
    """Sum of the trees' leaf values for every row, float64."""
    F, n = codes.shape
    out = np.zeros(n)
    if not trees:
        return out
    D = int(np.log2(len(trees[0].feat) + 1)) - 1
    steps = [(_step_table(t, B1), t.feat.astype(np.intp), t.leaf) for t in trees]

    def run(s, e):
        r = np.arange(s, e)
        acc = np.zeros(e - s)
        for table, feat, leaf in steps:
            pos = np.zeros(e - s, np.intp)
            for _ in range(D):
                pos = table[pos * B1 + codes[feat[pos], r]]
            acc += leaf[pos]
        out[s:e] = acc

    _runs(n, run, WALK_STEP)
    return out


def auc(y: np.ndarray, s: np.ndarray) -> float:
    """Mann-Whitney AUC, ties counted half: of the pairs of a positive and a
    negative row, the share in which the positive has the higher score,
    counted for each positive among the sorted negatives."""
    is_pos = y > 0.5
    pos, neg = np.sort(s[is_pos]), np.sort(s[~is_pos])
    # a negative under the positive counts twice, one tied with it once
    twice = sum(_runs(len(pos), lambda a, b: int(
        np.searchsorted(neg, pos[a:b], side="left").sum()
        + np.searchsorted(neg, pos[a:b], side="right").sum())))
    return twice / 2.0 / (len(pos) * len(neg))


def score(codes, y, p: RefParams, model: Dict, nclasses: int = 1) -> Dict[str, float]:
    """Training metrics of the whole model, float64 throughout: logloss and
    AUC of the binary fit, as ``hist-gbm`` defines them."""
    margin = float(np.asarray(model["init_margin"], np.float64)[0]) + walk(
        codes, model["trees"][0], p.nbins + 1)
    eps = 1e-15

    def run(s, e):
        pr = np.clip(base.sigmoid(margin[s:e]), eps, 1 - eps)
        return float(np.sum(y[s:e] * np.log(pr) + (1 - y[s:e]) * np.log(1 - pr)))

    return {"logloss": -sum(_runs(len(y), run)) / len(y), "auc": auc(y, margin)}


# ---------------------------------------------------------------------------
# what the harness calls: the program's comparison (``extract`` is hist-gbm's)


def compare_one(config: dict, seed: int, X, y, classes: int,
                answer: dict, block: int, numbers) -> Dict[str, float]:
    p = RefParams.from_config(config["params"], seed)
    if p.distribution != "bernoulli":
        raise SystemExit(f"hist-xgb judges a binary fit; the configuration's "
                         f"distribution is {p.distribution!r}")
    yf = y.astype(np.float64)
    built = len(answer["trees"][0])
    if built == 0:
        return {k: float("inf") for k in numbers}
    codes = bin_codes(X, answer["edges"])
    f0 = init_margin(p.distribution, yf, classes)
    out = {"bin_rank_gap": base.bin_rank_gap(codes, p.nbins),
           "init_margin_gap": float(np.abs(answer["init_margin"] - f0).max())}
    judged = judge(codes, yf, p, answer, judged_rounds(built, block), classes)
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
    """Worst reading of each of ``numbers`` over the window's answers;
    ``table`` holds ``X``, ``y``, ``classes`` and ``columns``."""
    worst = {k: 0.0 for k in numbers}
    for answer in answers:
        one = compare_one(config, seed, table["X"], table["y"], table["classes"],
                          answer, block, numbers)
        for k, v in one.items():
            worst[k] = max(worst[k], v)
    return worst
