"""The whole forest step against the chip: least time the chip needs for
one DRF tree's work (lib/work_forest.py: every level over each node's
mtries features, and the per-tree passes; the share is of the binding
peak, bytes/s on the v5e) over block_ms_per_tree."""
from lib.work import least_seconds
from lib.work_forest import tree_work


def read(run):
    peak = run["peak"]
    blocks = [b for s in run["served"] for b in s["blocks"]]
    trees = sum(b["trees"] for b in blocks)
    if not peak or not trees:
        return None
    per_tree = sum(b["end_ns"] - b["start_ns"] for b in blocks) / 1e9 / trees
    w = tree_work(run["rows"], run["features"], run["classes"], run["params"])
    return 100.0 * least_seconds(w["ops"], w["bytes"], peak)["seconds"] / per_tree
