"""The quantile sketch of the first fit on a new frame: the `make_bins` span
of the warm-up fit (a sample of 200,000 rows, one quantile pass a feature)."""
from lib import spans


def read(run):
    return spans.kind_seconds(spans.warmup_tree(run), "make_bins")
