"""Device ms a tree under the scopes `grad`, `sample` and `margin`: gradient
and hessian of every row, the row and column draws, the margin update.
Device trace, by the compiled block's scopes (lib/scopes.py)."""
from lib import scopes


def read(run):
    return scopes.ms_per_tree(run, ("grad", "sample", "margin"))
