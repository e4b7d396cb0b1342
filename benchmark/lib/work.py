"""Operations and bytes a tree fit needs — from the configuration's shapes only.

Nothing here looks at the kernel the program chose, its node padding, the
subtraction flow or a dtype it happens to store: a change of implementation
must not change the count, or a share of the roofline could rise while the
fit slows.

Per tree level that searches splits (``max_depth`` of them) every sampled
row is read once: its sampled features' bin codes at the narrowest integer
width that holds ``nbins + 1`` values, its gradient, hessian and node id
(4 bytes each), and each (row, feature) makes two additions (gradient and
hessian into its bin).  Per tree the gradient pass reads response and
margin and writes gradient and hessian (16 bytes, 8 operations a row), and
the margin pass reads margin and leaf id and writes the margin (12 bytes, 1
operation a row).  A round builds one tree per class tree.
"""

from __future__ import annotations

from typing import Dict


def code_bytes(nbins: int) -> int:
    for width in (1, 2, 4):
        if nbins + 1 <= 2 ** (8 * width):
            return width
    raise ValueError(f"nbins {nbins} does not fit an integer code")


def class_trees(distribution: str, classes: int) -> int:
    return classes if distribution == "multinomial" else 1


def tree_work(rows: int, features: int, classes: int, params: dict) -> Dict[str, float]:
    """Operations and bytes of ONE boosting round (all its class trees),
    split into the histogram levels and the per-tree passes."""
    depth = int(params["max_depth"])
    nbins = int(params["nbins"])
    sampled_rows = rows * float(params.get("sample_rate", 1.0))
    rate = float(params.get("col_sample_rate_per_tree", 1.0))
    sampled_feats = features if rate >= 1.0 else max(1, int(round(rate * features)))
    c = class_trees(params.get("distribution", "gaussian"), classes)
    level_bytes = sampled_rows * (sampled_feats * code_bytes(nbins) + 12)
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
