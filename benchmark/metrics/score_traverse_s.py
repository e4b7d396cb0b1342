"""Post-fit scoring on the device: the `score_traverse` span of the window's
fit (the codes' upload, the walk of every tree, the read-back of the
margin)."""
from lib import spans


def read(run):
    return spans.window_kind_seconds(run, "score_traverse")
