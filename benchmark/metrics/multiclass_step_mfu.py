"""The whole boosting round of K class trees against the chip: least time
the chip needs for one round's work (lib/work_classes.py: the levels of the
K trees built together, and each tree's gradient and margin passes; the
share is of the binding peak, bytes/s on the v5e) over block_ms_per_tree,
the ms a round."""
from lib.work import least_seconds
from lib.work_classes import round_work


def read(run):
    peak = run["peak"]
    blocks = [b for s in run["served"] for b in s["blocks"]]
    rounds = sum(b["trees"] for b in blocks)
    if not peak or not rounds:
        return None
    per_round = sum(b["end_ns"] - b["start_ns"] for b in blocks) / 1e9 / rounds
    w = round_work(run["rows"], run["features"], run["classes"], run["params"],
                   run.get("columns"))
    return 100.0 * least_seconds(w["ops"], w["bytes"], peak)["seconds"] / per_round
