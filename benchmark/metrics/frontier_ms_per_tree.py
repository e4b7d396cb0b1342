"""Device ms a tree under the scopes `L<dd>/` of the levels the window's
`tree_block` spans mark `frontier` in `hist_slots`: every phase of the
levels past the node ladder.  Device trace, by the compiled block's scopes
(lib/frontier.py)."""
from lib import frontier


def read(run):
    got = frontier.level_phases(run)
    if got is None:
        return None
    return 1e3 * sum(got["phases"].values()) / got["trees"]
