"""What a fit on a resident frame pays before its first device block: the
start of the window's `train` span to the start of its first `tree_block`
span (frame to matrix, quantile sketch, resident-bins lookup, state upload)."""
from lib import spans


def read(run):
    total, found = 0.0, False
    for tree in spans.window_trees(run):
        blocks = [e for e in tree["spans"] if e["kind"] == "tree_block"]
        if blocks:
            first = min(int(e["start_ns"]) for e in blocks)
            total += (first - int(tree["train"]["start_ns"])) / 1e9
            found = True
    return total if found else None
