"""Device ms a tree under the scope `sorted_prep`: the argsort of the rows by
node and the gathers of codes and of g, h into node-sorted order, which the
sorted tile-per-node kernel needs on the levels past the node-matmul
kernel's reach.  0 where no level is sorted.  Device trace, by the compiled
block's scopes (lib/scopes.py)."""
from lib import scopes


def read(run):
    return scopes.ms_per_tree(run, ("sorted_prep",))
