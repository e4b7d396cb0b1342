"""Host binning of the training frame: the `apply_bins` span under
`bins_resident` of the warm-up fit (one searchsorted a feature over every
row; the window's fit finds the codes resident and has no such span)."""
from lib import spans


def read(run):
    return spans.kind_seconds(spans.warmup_tree(run), "apply_bins", under="bins_resident")
