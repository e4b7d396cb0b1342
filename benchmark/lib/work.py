"""Operations and bytes a tree fit needs — from the configuration's shapes only.

Nothing here looks at the kernel the program chose, its node padding, the
subtraction flow or a dtype it happens to store: a change of implementation
must not change the count, or a share of the roofline could rise while the
fit slows.

Per tree level that searches splits (``max_depth`` of them) every sampled
row is read once: its sampled features' bin codes, each at the narrowest
integer width that holds the feature's bins and the NA bucket (``nbins``
bins, or, for a column its generator types ``cat``, a bin a level up to
``nbins_cats``), its gradient, hessian and node id (4 bytes each), and each
(row, feature) makes two additions (gradient and hessian into its bin).
Per tree the gradient pass reads response and margin and writes gradient
and hessian (16 bytes, 8 operations a row), and the margin pass reads margin
and leaf id and writes the margin (12 bytes, 1 operation a row).  A round
builds one tree per class tree.
"""

from __future__ import annotations

from typing import Dict, List, Optional

#: H2O's default cap on the bins of a categorical column
NBINS_CATS = 1024


def code_bytes(nbins: int) -> int:
    for width in (1, 2, 4):
        if nbins + 1 <= 2 ** (8 * width):
            return width
    raise ValueError(f"nbins {nbins} does not fit an integer code")


def class_trees(distribution: str, classes: int) -> int:
    return classes if distribution == "multinomial" else 1


def feature_bins(features: int, params: dict,
                 columns: Optional[List[dict]] = None) -> List[int]:
    """Bins of each feature: ``nbins``, or a categorical's levels capped by
    ``nbins_cats`` where the table's ``columns`` type it so."""
    nbins = int(params["nbins"])
    if columns is None:
        return [nbins] * features
    cap = int(params.get("nbins_cats", NBINS_CATS))
    return [min(len(c["domain"]), cap) if c["type"] == "cat" else nbins
            for c in columns]


def tree_work(rows: int, features: int, classes: int, params: dict,
              columns: Optional[List[dict]] = None) -> Dict[str, float]:
    """Operations and bytes of ONE boosting round (all its class trees),
    split into the histogram levels and the per-tree passes.  A column
    sample takes the mean code width of the features."""
    depth = int(params["max_depth"])
    widths = [code_bytes(b) for b in feature_bins(features, params, columns)]
    sampled_rows = rows * float(params.get("sample_rate", 1.0))
    rate = float(params.get("col_sample_rate_per_tree", 1.0))
    sampled_feats = features if rate >= 1.0 else max(1, int(round(rate * features)))
    c = class_trees(params.get("distribution", "gaussian"), classes)
    level_bytes = sampled_rows * (sampled_feats * (sum(widths) / len(widths)) + 12)
    level_ops = 2.0 * sampled_rows * sampled_feats
    hist_bytes = c * depth * level_bytes
    hist_ops = c * depth * level_ops
    pass_bytes = c * rows * 28.0
    pass_ops = c * rows * 9.0
    return {
        "hist_bytes": hist_bytes, "hist_ops": hist_ops,
        "bytes": hist_bytes + pass_bytes, "ops": hist_ops + pass_ops,
    }


def least_seconds(ops: float, nbytes: float, peak: dict) -> Dict[str, object]:
    """Roofline: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two binds."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "bytes" if t_bytes >= t_ops else "flops"}
