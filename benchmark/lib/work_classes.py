"""Operations and bytes a boosting round of K class trees needs when its
levels are built together — from the configuration's shapes only
(``lib/work.py``'s rule: nothing here looks at the kernel the program chose,
its slots, its padding or a dtype it stores).

``lib/work.py`` counts a round as K trees, each level reading every row's
codes once a class tree: a round whose K trees share a level's pass over the
codes would read up to K times too high there.  Here a level of the round
reads each sampled row's codes once, at the narrowest integer width that
holds a feature's bins and the NA bucket (``work.feature_bins``), and per
class tree the row's gradient, hessian and node id (12 bytes); each (row,
feature, class tree) makes two additions.  The per-tree gradient and margin
passes are ``lib/work.py``'s (28 bytes, 9 operations a row a class tree).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .work import class_trees, code_bytes, feature_bins


def level_work(rows: int, features: int, classes: int, params: dict,
               columns: Optional[List[dict]] = None) -> Dict[str, float]:
    """One level of a round's K class trees, built together."""
    k = class_trees(params.get("distribution", "gaussian"), classes)
    widths = [code_bytes(b) for b in feature_bins(features, params, columns)]
    sampled = rows * float(params.get("sample_rate", 1.0))
    rate = float(params.get("col_sample_rate_per_tree", 1.0))
    feats = features if rate >= 1.0 else max(1, int(round(rate * features)))
    return {"bytes": sampled * (feats * (sum(widths) / len(widths)) + 12.0 * k),
            "ops": 2.0 * sampled * feats * k}


def round_work(rows: int, features: int, classes: int, params: dict,
               columns: Optional[List[dict]] = None) -> Dict[str, float]:
    """One round: ``max_depth`` levels and each class tree's passes."""
    level = level_work(rows, features, classes, params, columns)
    depth = int(params["max_depth"])
    k = class_trees(params.get("distribution", "gaussian"), classes)
    return {"hist_bytes": depth * level["bytes"], "hist_ops": depth * level["ops"],
            "bytes": depth * level["bytes"] + k * rows * 28.0,
            "ops": depth * level["ops"] + k * rows * 9.0}
