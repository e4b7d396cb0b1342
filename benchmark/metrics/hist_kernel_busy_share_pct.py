"""Device time of the histogram kernels' events over the device's busy time
in the traced window.  Device trace."""


def read(run):
    tr = run["trace"]
    if not tr or tr["hist_kernel_s"] <= 0:
        return None
    return 100.0 * tr["hist_kernel_s"] / tr["busy_s"]
