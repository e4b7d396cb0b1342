"""Device ms a tree under the scopes `L<dd>/route`, `L<dd>/hist_nodes` and
`leaf`: every row to its child, the selection of the rows a level builds,
the leaf values and the row's own leaf.  Device trace, by the compiled
block's scopes (lib/scopes.py)."""
from lib import scopes


def read(run):
    return scopes.ms_per_tree(run, ("route", "hist_nodes", "leaf"))
