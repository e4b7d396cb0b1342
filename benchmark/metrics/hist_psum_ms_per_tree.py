"""Device ms a tree under the scopes `L<dd>/hist/hist_psum`: the sum of every
level's histogram over the devices of the mesh (an all-reduce of float32
`[node slots, features, bins + 1, 3]`), on the first device's plane.  Device
trace, by the compiled block's scopes (lib/scopes.py); 0 where the block sums
over one device."""
from lib import scopes


def read(run):
    return scopes.ms_per_tree(run, ("hist_psum",))
