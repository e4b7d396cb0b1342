"""Least time the chip needs for the histogram work of the traced window's
trees (lib/work.py: bytes bind on the v5e) over the device time of the
histogram kernels' events.  Device trace."""
from lib.work import least_seconds


def read(run):
    tr, peak = run["trace"], run["peak"]
    if not tr or not peak or tr["hist_kernel_s"] <= 0:
        return None
    w = run["work"]
    least = least_seconds(w["hist_ops"], w["hist_bytes"], peak)["seconds"]
    return 100.0 * least * run["trees_built"] / tr["hist_kernel_s"]
