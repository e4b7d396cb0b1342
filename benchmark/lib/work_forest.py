"""Operations and bytes a random forest's tree needs — from the
configuration's shapes only (``lib/work.py``'s rule: nothing here looks at
the kernel the program chose, its slots, its padding or a dtype it stores).

A DRF node searches its own ``mtries`` features (``floor(sqrt(F))`` for a
classifier, ``F // 3`` for a regression where ``mtries`` is -1), so at every
level that searches splits (``max_depth`` of them) every sampled row is read
once with: its node's id (4 bytes), its target (4 bytes) and its ``mtries``
codes at the narrowest integer width that holds ``nbins`` bins and the NA
bucket; and each (row, candidate feature) makes two additions (the target
and the count into its bin).  Per tree the target and margin passes read
and write what ``lib/work.py`` counts (28 bytes, 9 operations a row).
"""

from __future__ import annotations

import math
from typing import Dict

from .work import code_bytes


def mtries(features: int, classes: int, params: dict) -> int:
    m = int(params.get("mtries", -1))
    if m <= 0:
        m = max(1, int(math.sqrt(features))) if classes > 1 else max(1, features // 3)
    return min(m, features)


def level_work(rows: int, features: int, classes: int, params: dict) -> Dict[str, float]:
    """One level of one tree: every sampled row's node id, target and
    ``mtries`` codes, two additions a (row, candidate feature)."""
    m = mtries(features, classes, params)
    sampled = rows * float(params.get("sample_rate", 1.0))
    return {"bytes": sampled * (8.0 + m * code_bytes(int(params["nbins"]))),
            "ops": 2.0 * sampled * m}


def tree_work(rows: int, features: int, classes: int, params: dict) -> Dict[str, float]:
    """One tree: ``max_depth`` levels and the per-tree passes."""
    level = level_work(rows, features, classes, params)
    depth = int(params["max_depth"])
    return {"bytes": depth * level["bytes"] + 28.0 * rows,
            "ops": depth * level["ops"] + 9.0 * rows}
