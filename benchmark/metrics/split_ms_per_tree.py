"""Device ms a tree under the scopes `L<dd>/split`: the split search over
every level's histogram.  Device trace, by the compiled block's scopes
(lib/scopes.py)."""
from lib import scopes


def read(run):
    return scopes.ms_per_tree(run, ("split",))
