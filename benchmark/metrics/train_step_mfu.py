"""The whole boosting step against the chip: least time the chip needs for
one tree's work (histogram levels + gradient and margin passes; the share
is of the binding peak, bytes/s on the v5e) over block_ms_per_tree."""
from lib.work import least_seconds


def read(run):
    peak = run["peak"]
    blocks = [b for s in run["served"] for b in s["blocks"]]
    trees = sum(b["trees"] for b in blocks)
    if not peak or not trees:
        return None
    per_tree = sum(b["end_ns"] - b["start_ns"] for b in blocks) / 1e9 / trees
    w = run["work"]
    return 100.0 * least_seconds(w["ops"], w["bytes"], peak)["seconds"] / per_tree
