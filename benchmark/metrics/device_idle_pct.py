"""1 - union of the device operations' intervals over the traced window
(the whole train() call: entry, every block, the gaps between, the post-fit
scoring).  Device trace."""


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
