"""Least time the chip needs for the frontier levels' histogram work of the
window's trees (lib/work_forest.py: a level's sampled rows, each with its
node id, target and mtries codes; bytes bind on the v5e) over the device
time under `L<dd>/hist/hist_frontier` of those levels: the frontier kernel
and its preparation (the sort by slot, the gather of the packed rows).
Device trace (lib/frontier.py)."""
from lib import frontier
from lib.work import least_seconds
from lib.work_forest import level_work


def read(run):
    got, peak = frontier.level_phases(run), run["peak"]
    if got is None or not peak:
        return None
    spent = got["phases"].get("hist_prep", 0.0) + got["phases"].get("kernel", 0.0)
    if spent <= 0:
        return None
    w = level_work(run["rows"], run["features"], run["classes"], run["params"])
    least = least_seconds(w["ops"], w["bytes"], peak)["seconds"]
    return 100.0 * least * len(got["levels"]) * got["trees"] / spent
