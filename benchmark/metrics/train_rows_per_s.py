"""Rows of the training frame x trees built, over the whole wall of the
window's train() calls: binning the call does, every block, every read-back
and budget check, and the post-fit scoring.  Host clock."""


def read(run):
    return run["rows"] * run["trees_built"] / run["wall_s"]
