"""Post-fit scoring, host binning: the `apply_bins` span under
`model_performance` in the window's fit (the codes are resident on the device;
the predict path bins the frame again on the host)."""
from lib import spans


def read(run):
    return spans.window_kind_seconds(run, "apply_bins", under="model_performance")
