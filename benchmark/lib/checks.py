"""The comparison that decides ``correct``: the program's answers of the
timed window against the plain reference, each number beside a limit of its
own (``benchmark/limits/<config>.json``; PERF.md gives the readings each
limit was set from).

Compared, for every ``train()`` the window served, at the timed size:

* ``bin_rank_gap`` — the model's bin edges are quantile edges of the data;
* ``init_margin_gap`` — the model's starting margin against the reference's
  own, from the response alone;
* ``split_gap``, ``gain_forgone``, ``leaf_gap``, ``leaf_gap_mean`` — the
  trees of up to three boosting rounds (``judged_rounds``), judged by
  teacher forcing (``reference.judge``);
* ``<metric>_gap`` (``logloss_gap``, ``auc_gap``...) — a ``training_metrics``
  entry the fit reported (the program's own scoring traversal over every
  tree it built) against the reference's float64 walk of the same trees
  over every row: relative, but for ``auc``, which is absolute.

Which of them a configuration is held to is data: the keys of its limits
file.  A key this module cannot compute is an error, not a pass.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List

import numpy as np

JUDGED = ("split_gap", "gain_forgone", "leaf_gap", "leaf_gap_mean")
ABSOLUTE = ("auc",)  # compared as a difference; every other metric as a share


def load_limits(root: str, config_name: str) -> Dict[str, float]:
    """The numbers a configuration is held to, each with its limit."""
    with open(os.path.join(root, "benchmark", "limits", config_name + ".json")) as f:
        limits = json.load(f)["limits"]
    if not limits:
        raise SystemExit(f"limits of {config_name} name no number to compare")
    return {k: float(v) for k, v in limits.items()}


def reported_metrics(numbers) -> List[str]:
    """Names of the ``training_metrics`` entries the limits ask for."""
    fixed = ("bin_rank_gap", "init_margin_gap") + JUDGED
    return [k[:-len("_gap")] for k in numbers if k not in fixed and k.endswith("_gap")]


def judged_rounds(built: int, block: int) -> List[int]:
    """Round 0, a round in the middle of the first block (the state handed
    from tree to tree inside a block, at a round where gradients are no
    longer two-valued) and, where a second block was built, its first round
    (the state handed from block to block).  Two rounds for three where
    only one block was built keeps the check shorter than the window."""
    rounds = [0, block // 2] + ([block] if built > block else [])
    return [r for r in rounds if r < built] or [0]


def compare_one(ref, config: dict, seed: int, X, y, classes: int,
                answer: dict, block: int, numbers) -> Dict[str, float]:
    p = ref.RefParams.from_config(config["params"], seed)
    yf = y.astype(np.float64)
    built = len(answer["trees"][0])
    if built == 0:
        return {k: float("inf") for k in numbers}
    codes = ref.bin_codes(X, answer["edges"])
    f0 = ref.init_margin(p.distribution, yf, classes)
    out = {"bin_rank_gap": ref.bin_rank_gap(codes, p.nbins),
           "init_margin_gap": float(np.abs(answer["init_margin"] - f0).max())}
    judged = ref.judge(codes, yf, p, answer, judged_rounds(built, block), classes)
    for key, rep in judged["by_round"].items():
        print(f"judged round.class {key}: " + " ".join(
            f"{k}={v:.4g}" for k, v in rep.items()), file=sys.stderr)
    out.update({k: judged[k] for k in JUDGED})
    mine = ref.score(codes, yf, p, answer, classes)
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


def compare(ref, config: dict, seed: int, X, y, classes: int,
            answers: List[dict], block: int, numbers) -> Dict[str, float]:
    """Worst reading of each of ``numbers`` over the window's answers."""
    worst = {k: 0.0 for k in numbers}
    for answer in answers:
        one = compare_one(ref, config, seed, X, y, classes, answer, block, numbers)
        for k, v in one.items():
            worst[k] = max(worst[k], v)
    return worst
