"""Least time the chip needs for the histogram levels of the traced
window's rounds, a round's K class trees built together
(lib/work_classes.py: a level reads each row's codes once and 12 bytes a
class tree; bytes bind on the v5e) over the device time of the histogram
kernels' events.  Device trace."""
from lib.work import least_seconds
from lib.work_classes import round_work


def read(run):
    tr, peak = run["trace"], run["peak"]
    if not tr or not peak or tr["hist_kernel_s"] <= 0:
        return None
    w = round_work(run["rows"], run["features"], run["classes"], run["params"],
                   run.get("columns"))
    least = least_seconds(w["hist_ops"], w["hist_bytes"], peak)["seconds"]
    return 100.0 * least * run["trees_built"] / tr["hist_kernel_s"]
