"""The plain reference ``hist-drf``: H2O's distributed random forest on
histograms, numpy float64, with the extraction and the comparison that
decide ``correct`` for a configuration that names it.

It imports nothing of the program; ``jax.random`` (on the CPU) serves the
random streams alone, as in ``hist-gbm``, whose binning, quantile check and
AUC it loads from that file beside this one.  Same interface (``NUMBERS``,
``extract``, ``compare``; ``forest`` / ``judge`` for the tests and tools).

Semantics (the configuration's ``guarantees`` state them), for a binary
response t in {0, 1}:

* tree t samples row i iff ``uniform(k_r)[i] < sample_rate`` with
  ``k_r, _, k_t = split(fold_in(PRNGKey(seed), t), 3)``;
* a node of heap id i (root 0, children 2i+1 and 2i+2) has for candidates
  the ``mtries`` features of lowest ``uniform(fold_in(fold_in(k_t, 0), i),
  (F,))``, ties to the lower feature; ``mtries`` -1 is floor(sqrt(F)) for a
  classifier, F/3 for a regression;
* gain ``0.5 * (GL^2/HL + GR^2/HR - G^2/H)`` with g = -t and h = 1 over the
  node's sampled rows (the squared-error reduction of the class
  indicator), the NA bucket tried on both sides, each child holding at
  least ``min_rows`` sampled rows; a node splits while ``gain >
  min_split_improvement`` and depth is left;
* leaf: the mean of t over the node's sampled rows; every row is routed;
* the forest's margin is the mean of its trees' leaves; the reported
  logloss and AUC are of ``clip(margin, 0, 1)`` as P(t = 1), in-bag, over
  every row.

A tree is judged by teacher forcing (``hist-gbm``'s header): the rows are
routed by the judged tree's own splits, and at every node the reference's
best candidate, in float64 from exact sums, is set against the judged
tree's choice (``split_gap``, ``gain_forgone``) and the reference's leaf
against its leaf (``leaf_gap``, ``leaf_gap_mean``).  A chosen feature that is
not among the node's candidates, and a split the reference holds
impossible, read 1.  A tree is a list of nodes in heap order (``node`` heap
ids, the five fields, ``child``: a split node's left child's position, the
right child next to it), as the program keeps a deep tree; a heap of the
program's dense trees is read into the same form.

Every pass over the rows is made in runs of ``STEP`` rows on the threads
(the walk of every tree, the routing); ``np.bincount`` holds the
interpreter, so the level histograms are summed on one thread, a candidate
feature at a time.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


def _load_base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "hist-gbm.py")
    spec = importlib.util.spec_from_file_location("references_hist_gbm_for_drf", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks its module up by name
    spec.loader.exec_module(mod)
    return mod


base = _load_base()
base._THREADS = max(base._THREADS, min(32, (os.cpu_count() or 8) - 2))

JUDGED = ("split_gap", "gain_forgone", "leaf_gap", "leaf_gap_mean")
REPORTED = ("logloss", "auc")
NUMBERS = ("bin_rank_gap",) + JUDGED + tuple(m + "_gap" for m in REPORTED)
#: rows a run of a pass over every row
STEP = 1 << 20
#: the planted faults ``forest`` knows: every feature a candidate, trees cut
#: at depth 12, trees summed and not averaged, every row in every tree
FAULTS = ("mtries_ignored", "depth_12", "trees_summed", "sample_ignored")


def _runs(n: int, fn, step: int = STEP) -> list:
    with ThreadPoolExecutor(base._THREADS) as ex:
        return list(ex.map(lambda s: fn(s, min(n, s + step)), range(0, n, step)))


@dataclass(frozen=True)
class RefParams:
    max_depth: int
    nbins: int
    mtries: int
    min_rows: float = 1.0
    min_split_improvement: float = 1e-5
    sample_rate: float = 0.632
    seed: int = 0

    @staticmethod
    def from_config(params: dict, seed: int, features: int, classes: int) -> "RefParams":
        m = int(params.get("mtries", -1))
        if m <= 0:
            m = max(1, int(np.sqrt(features))) if classes > 1 else max(1, features // 3)
        return RefParams(
            max_depth=int(params["max_depth"]), nbins=int(params["nbins"]),
            mtries=min(m, features), min_rows=float(params.get("min_rows", 1.0)),
            min_split_improvement=float(params.get("min_split_improvement", 1e-5)),
            sample_rate=float(params.get("sample_rate", 0.632)), seed=seed)


@dataclass
class Tree:
    """A tree as its list of nodes in heap order."""

    node: np.ndarray  # heap ids, ascending
    feat: np.ndarray
    split_bin: np.ndarray
    default_left: np.ndarray
    is_split: np.ndarray
    leaf: np.ndarray
    child: np.ndarray  # position of a split node's left child, -1 for a leaf


def tree_from_lists(node, feat, split_bin, default_left, is_split, leaf) -> Tree:
    """A tree from its nodes (any order): sorted, with child positions."""
    order = np.argsort(node, kind="stable")
    ids = np.asarray(node, np.int64)[order]
    sp = np.asarray(is_split, bool)[order]
    child = np.where(sp, np.searchsorted(ids, 2 * ids + 1), -1)
    return Tree(ids, np.asarray(feat, np.int64)[order],
                np.asarray(split_bin, np.int64)[order],
                np.asarray(default_left, bool)[order], sp,
                np.asarray(leaf, np.float64)[order], child.astype(np.int64))


def tree_from_heap(feat, split_bin, default_left, is_split, leaf) -> Tree:
    """A heap of 2^(D+1)-1 nodes as the list of the nodes a row can reach."""
    M = len(feat)
    reach = np.zeros(M, bool)
    reach[0] = True
    for i in range(1, M):
        up = (i - 1) // 2
        reach[i] = reach[up] and bool(is_split[up])
    ids = np.flatnonzero(reach)
    return tree_from_lists(ids, *(np.asarray(a)[ids] for a in (
        feat, split_bin, default_left, is_split, leaf)))


# ---------------------------------------------------------------------------
# streams


def _threefry():
    import jax

    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("the streams are defined for partitionable threefry")
    return jax


def tree_sample(p: RefParams, t: int, n: int, all_rows: bool = False):
    """Rows of tree t, and the tree's key for the node draws."""
    jax = _threefry()
    with jax.default_device(jax.devices("cpu")[0]):
        kr, _, kt = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(p.seed), t), 3)
        rows = (np.ones(n, bool) if all_rows or p.sample_rate >= 1.0
                else np.asarray(jax.random.uniform(kr, (n,)) < p.sample_rate))
        return rows, jax.random.fold_in(kt, 0)


#: nodes a call of the draw: every call has this shape, so it compiles once
DRAW_STEP = 1 << 18


@functools.lru_cache(maxsize=None)
def _draw(F: int):
    """uniform(fold_in(key, i), (F,)) for a run of heap ids, jitted once."""
    jax = _threefry()
    return jax.jit(jax.vmap(
        lambda key, i: jax.random.uniform(jax.random.fold_in(key, i), (F,)),
        in_axes=(None, 0)))


def node_candidates(key, node_ids: np.ndarray, F: int, m: int) -> np.ndarray:
    """[K, m] candidate features of the nodes of heap ids ``node_ids``."""
    jax = _threefry()
    out = np.empty((len(node_ids), m), np.int64)
    draw = _draw(F)
    with jax.default_device(jax.devices("cpu")[0]):
        for s in range(0, len(node_ids), DRAW_STEP):
            ids = np.zeros(DRAW_STEP, np.uint32)
            part = node_ids[s:s + DRAW_STEP]
            ids[:len(part)] = part
            r = np.asarray(draw(key, ids))[:len(part)]
            out[s:s + len(part)] = np.argsort(r, axis=1, kind="stable")[:, :m]
    return out


# ---------------------------------------------------------------------------
# one tree, level by level


def _gains(hg: np.ndarray, hc: np.ndarray, min_rows: float) -> np.ndarray:
    """Gains [K, B, 2] (NA right, NA left) from a feature's sums of g and
    counts [K, B1] of each node."""
    B = hg.shape[1] - 1
    G, CNT = hg.sum(axis=1), hc.sum(axis=1)
    cg, cc = np.cumsum(hg[:, :B], axis=1), np.cumsum(hc[:, :B], axis=1)

    def score(g, c):
        return g * g / np.maximum(c, 1e-12)

    parent = score(G, CNT)[:, None]
    out = []
    for gl, cl in ((cg, cc), (cg + hg[:, B:], cc + hc[:, B:])):
        gr, cr = G[:, None] - gl, CNT[:, None] - cl
        gain = 0.5 * (score(gl, cl) + score(gr, cr) - parent)
        out.append(np.where((cl >= min_rows) & (cr >= min_rows), gain, -np.inf))
    return np.stack(out, axis=-1)


def _candidate(codes, rows_s, local_s, t_s, feats, K: int, B1: int,
               min_rows: float, judged=None):
    """Of each node's candidate ``feats`` [K] (one a node): the best gain
    [K], its (bin, NA left) flat index [K] and, with ``judged`` = (bin [K],
    NA left [K]), the gain of that candidate [K]."""
    flat = np.empty(len(rows_s), np.int64)

    def rows_run(s, e):
        ls = local_s[s:e]
        flat[s:e] = ls * B1 + codes[feats[ls], rows_s[s:e]]

    _runs(len(rows_s), rows_run)
    hg = -np.bincount(flat, weights=t_s, minlength=K * B1).reshape(K, B1)
    hc = np.bincount(flat, minlength=K * B1).reshape(K, B1).astype(np.float64)
    best, arg = np.empty(K), np.empty(K, np.int64)
    at = None if judged is None else np.empty(K)

    def nodes_run(s, e):
        g = _gains(hg[s:e], hc[s:e], min_rows)
        f = g.reshape(e - s, -1)
        arg[s:e] = f.argmax(axis=1)
        best[s:e] = f[np.arange(e - s), arg[s:e]]
        if judged is not None:
            at[s:e] = g[np.arange(e - s), judged[0][s:e], judged[1][s:e]]

    _runs(K, nodes_run, 1 << 15)
    return best, arg, at


def grow(codes: np.ndarray, t: np.ndarray, rows: np.ndarray, key, p: RefParams,
         follow: Optional[Tree] = None, every_feature: bool = False):
    """Build (``follow`` None) or judge (``follow`` a tree) one tree.
    Returns (tree, each row's position in it, report)."""
    F, n = codes.shape
    B1 = p.nbins + 1
    msi = max(p.min_split_improvement, 0.0)
    m = F if every_feature else p.mtries
    # the tree being built, a level at a time: (ids, feat, bin, dl, split, leaf)
    built: List[tuple] = []
    ids = np.zeros(1, np.int64)  # this level's nodes (building)
    pos = np.zeros(n, np.int64)  # rows' position: in the level (building) or the tree
    alive = np.ones(n, bool)  # rows whose node is on this level
    gaps: List[float] = []
    short = best_sum = 0.0
    leaf_gaps: List[np.ndarray] = []
    leaf_rows: List[np.ndarray] = []
    for d in range(p.max_depth + 1):
        if follow is not None:
            lo, hi = np.searchsorted(follow.node, [2**d - 1, 2 ** (d + 1) - 1])
            ids = follow.node[lo:hi]
        else:
            lo = 0
        K = len(ids)
        if K == 0:
            break
        r_at = np.flatnonzero(alive)
        local = pos[r_at] - lo
        nrows = np.bincount(local, minlength=K).astype(np.float64)
        s = rows[r_at]
        rows_s, local_s, t_s = r_at[s], local[s], t[r_at[s]]
        cnt = np.bincount(local_s, minlength=K).astype(np.float64)
        ref_leaf = np.bincount(local_s, weights=t_s, minlength=K) / np.maximum(cnt, 1.0)
        sl = slice(lo, lo + K)
        if d == p.max_depth:
            split = np.zeros(K, bool)
            if follow is None:
                built.append((ids, np.zeros(K, np.int64), np.zeros(K, np.int64),
                              np.zeros(K, bool), split, ref_leaf))
            else:
                term = nrows > 0
                leaf_gaps.append(np.abs(follow.leaf[sl] - ref_leaf)[term])
                leaf_rows.append(nrows[term])
            break
        cand = (np.broadcast_to(np.arange(F), (K, F)) if every_feature
                else node_candidates(key, ids, F, m))
        best = np.full(K, -np.inf)
        best_f = np.zeros(K, np.int64)
        best_b = np.zeros(K, np.int64)
        best_dl = np.zeros(K, bool)
        chosen = np.full(K, -np.inf)
        judged = None if follow is None else (
            np.minimum(follow.split_bin[sl], B1 - 2), follow.default_left[sl].astype(np.int64))
        for j in range(m):
            g, arg, at = _candidate(codes, rows_s, local_s, t_s, np.ascontiguousarray(cand[:, j]),
                                    K, B1, p.min_rows, judged)
            better = g > best
            best = np.where(better, g, best)
            best_f = np.where(better, cand[:, j], best_f)
            best_b = np.where(better, arg // 2, best_b)
            best_dl = np.where(better, (arg % 2).astype(bool), best_dl)
            if follow is not None:
                mine = follow.is_split[sl] & (follow.feat[sl] == cand[:, j])
                chosen = np.where(mine, at, chosen)
        live = np.isfinite(best) & (best > msi)
        if follow is None:
            split = live
            built.append((ids, best_f, best_b, best_dl, split, ref_leaf))
            f_node, b_node, dl_node, sp_node = best_f, best_b, best_dl, split
        else:
            reach = nrows > 0
            sp = follow.is_split[sl]
            chosen = np.where(sp, chosen, np.minimum(best, msi))
            gap = np.where(live, np.maximum(best - np.where(np.isfinite(chosen), chosen, 0.0),
                                            0.0) / np.where(live, best, 1.0), 0.0)
            gap = np.where(reach & sp & (~live | ~np.isfinite(chosen)), 1.0, gap)
            gaps.append(float(gap[reach].max(initial=0.0)))
            short += float((gap * np.where(live, best, 0.0)).sum())
            best_sum += float(np.where(live, best, 0.0).sum())
            term = reach & ~sp
            leaf_gaps.append(np.abs(follow.leaf[sl] - ref_leaf)[term])
            leaf_rows.append(nrows[term])
            f_node, b_node, dl_node, sp_node = (follow.feat[sl], follow.split_bin[sl],
                                                follow.default_left[sl], sp)
        if follow is None:
            # the next level's nodes: the children of the nodes that split,
            # in heap order, and each row's position among them
            base = 2 * (np.cumsum(split) - 1)
            ids = np.stack([2 * ids[split] + 1, 2 * ids[split] + 2], axis=1).ravel()
        else:
            base = follow.child[sl]

        def route(a, e):
            r, ls = r_at[a:e], local[a:e]
            code = codes[f_node[ls], r]
            go_left = np.where(code >= B1 - 1, dl_node[ls], code <= b_node[ls])
            still = sp_node[ls]
            alive[r[~still]] = False
            pos[r[still]] = (base[ls] + np.where(go_left, 0, 1))[still]

        _runs(len(r_at), route)
    if follow is None:
        tree = tree_from_lists(*(np.concatenate([lv[i] for lv in built]) for i in range(6)))
        return tree, None, None
    lg = np.concatenate(leaf_gaps) if leaf_gaps else np.zeros(0)
    lr = np.concatenate(leaf_rows) if leaf_rows else np.zeros(0)
    return follow, None, {
        "split_gap": max(gaps, default=0.0),
        "gain_forgone": short / best_sum if best_sum > 0 else 0.0,
        "leaf_gap": float(lg.max(initial=0.0)),
        "leaf_gap_mean": float((lg * lr).sum() / max(lr.sum(), 1.0)),
    }


def walk(codes: np.ndarray, trees: Sequence[Tree], B1: int) -> np.ndarray:
    """Sum of the trees' leaves for every row, float64."""
    F, n = codes.shape
    out = np.zeros(n)

    def run(s, e):
        r = np.arange(s, e)
        acc = np.zeros(e - s)
        for t in trees:
            idx = np.zeros(e - s, np.int64)
            while True:
                sp = t.is_split[idx]
                if not sp.any():
                    break
                code = codes[t.feat[idx], r]
                left = np.where(code >= B1 - 1, t.default_left[idx], code <= t.split_bin[idx])
                idx = np.where(sp, t.child[idx] + np.where(left, 0, 1), idx)
            acc += t.leaf[idx]
        out[s:e] = acc

    _runs(n, run, 1 << 18)
    return out


def report(codes, y, trees: Sequence[Tree], B1: int, summed: bool = False) -> Dict[str, float]:
    """Logloss and AUC of the forest's P(t = 1), float64."""
    total = walk(codes, trees, B1)
    p1 = np.clip(total if summed else total / max(len(trees), 1), 0.0, 1.0)
    pc = np.clip(p1, 1e-15, 1 - 1e-15)
    logloss = float(-np.mean(y * np.log(pc) + (1 - y) * np.log(1 - pc)))
    return {"logloss": logloss, "auc": base.auc(y, p1)}


# ---------------------------------------------------------------------------
# the reference in the program's place (faults), and its judge


def forest(codes, y, p: RefParams, ntrees: int, fault: Optional[str] = None) -> Dict:
    """``ntrees`` trees built from the reference's own argmax, one of
    ``FAULTS`` planted; an answer as ``extract`` gives one."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}: there are {FAULTS}")
    F, n = codes.shape
    q = dataclasses.replace(p, max_depth=min(p.max_depth, 12)) if fault == "depth_12" else p
    trees = []
    for t in range(ntrees):
        rows, key = tree_sample(p, t, n, all_rows=fault == "sample_ignored")
        trees.append(grow(codes, y, rows, key, q, every_feature=fault == "mtries_ignored")[0])
    return {"trees": [trees], "reported": report(codes, y, trees, p.nbins + 1,
                                                 summed=fault == "trees_summed")}


def judge(codes, y, p: RefParams, trees: Sequence[Tree], judged: Sequence[int]) -> Dict:
    """Teacher-forced judgement of the trees listed."""
    F, n = codes.shape
    out = {"split_gap": 0.0, "leaf_gap": 0.0, "leaf_gap_mean": 0.0,
           "gain_forgone": 0.0, "by_tree": {}}
    short = []
    for t in judged:
        rows, key = tree_sample(p, t, n)
        rep = grow(codes, y, rows, key, p, follow=trees[t])[2]
        out["by_tree"][t] = rep
        for k in ("split_gap", "leaf_gap", "leaf_gap_mean"):
            out[k] = max(out[k], rep[k])
        short.append(rep["gain_forgone"])
    out["gain_forgone"] = float(np.mean(short)) if short else 0.0
    return out


# ---------------------------------------------------------------------------
# what the harness calls


def extract(model, numbers) -> dict:
    """The program's answer as plain arrays: bin edges, every tree as its
    list of nodes, the ``training_metrics`` entries ``numbers`` ask for."""
    b = model.booster
    if b.nclasses_trees != 1 or not b.average:
        raise SystemExit("hist-drf judges a forest of one averaged tree a round "
                         "(a binary or regression DRF)")
    tpc = b.trees_per_class[0]
    trees = []
    for i in range(tpc.ntrees):
        fields = [np.asarray(getattr(tpc, f)[i]) for f in (
            "feat", "split_bin", "default_left", "is_split", "leaf")]
        if getattr(tpc, "child", None) is not None:
            trees.append(tree_from_lists(np.asarray(tpc.node[i]), *fields))
        else:
            trees.append(tree_from_heap(*fields))
    tm = model.training_metrics
    return {"edges": np.asarray(tpc.edges, np.float64), "trees": [trees],
            "reported": {k: float(getattr(tm, k)) for k in REPORTED
                         if k + "_gap" in numbers and getattr(tm, k, None) is not None}}


def compare_one(config: dict, seed: int, X, y, classes: int, answer: dict,
                block: int, numbers) -> Dict[str, float]:
    if classes != 2:
        raise SystemExit("hist-drf judges a binary forest")
    F = X.shape[1]
    p = RefParams.from_config(config["params"], seed, F, classes)
    yf = y.astype(np.float64)
    trees = answer["trees"][0]
    if not trees:
        return {k: float("inf") for k in numbers}
    codes = base.bin_codes(X, answer["edges"])
    out = {"bin_rank_gap": base.bin_rank_gap(codes, p.nbins)}
    # a block of one tree lists tree 0 twice
    judged = judge(codes, yf, p, trees, sorted(set(base.judged_rounds(len(trees), block))))
    for t, rep in judged["by_tree"].items():
        print(f"judged tree {t}: " + " ".join(f"{k}={v:.4g}" for k, v in rep.items()),
              file=sys.stderr)
    out.update({k: judged[k] for k in JUDGED})
    mine = report(codes, yf, trees, p.nbins + 1)
    for name in REPORTED:
        if name + "_gap" in numbers:
            gap = abs(answer["reported"].get(name, float("inf")) - mine[name])
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
        one = compare_one(config, seed, table["X"], table["y"], table["classes"],
                          answer, block, numbers)
        for k, v in one.items():
            worst[k] = max(worst[k], v)
    return worst
