"""Share of the wall of the window's `train` span that lies under none of
its leaf spans: what the program's spans do not explain."""
from lib import spans


def read(run):
    trees = spans.window_trees(run)
    if not trees:
        return None
    wall = sum(spans.seconds(t["train"]) for t in trees)
    return 100.0 * sum(spans.uncovered_seconds(t) for t in trees) / wall
