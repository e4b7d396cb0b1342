"""The bin codes to the device: the `bins_upload` span of the warm-up fit
(row-major codes, the validity mask and the feature-major copy, to the end
of `block_until_ready`)."""
from lib import spans


def read(run):
    return spans.kind_seconds(spans.warmup_tree(run), "bins_upload")
