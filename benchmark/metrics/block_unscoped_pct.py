"""Share of the device's busy time inside the window's blocks that lies under
no scope of the block program and is no kernel: the loop's own time between
the operations of a tree, and what the compiler added with no name.  Device
trace, by the compiled block's scopes (lib/scopes.py)."""
from lib import scopes


def read(run):
    scoped = scopes.window_scopes(run)
    if scoped is None or scoped["busy_s"] <= 0:
        return None
    return 100.0 * scoped["phases"].get(scopes.UNSCOPED, 0.0) / scoped["busy_s"]
