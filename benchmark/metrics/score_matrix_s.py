"""Post-fit scoring, frame to matrix: the `tree_matrix` span under
`model_performance` in the window's fit."""
from lib import spans


def read(run):
    return spans.window_kind_seconds(run, "tree_matrix", under="model_performance")
