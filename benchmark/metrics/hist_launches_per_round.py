"""Histogram kernel launches a boosting round in the window: the
``tree_block`` spans' ``hist_launches`` (a block's launches as its level
plan states them) over their rounds (``trees``).  A round of K class trees
whose levels are each one launch reads its depth.  Program span; None for a
program whose spans state no launches."""
from lib import spans


def read(run):
    launches = rounds = 0
    for tree in spans.window_trees(run):
        for e in tree["spans"]:
            if e["kind"] != "tree_block":
                continue
            if "hist_launches" not in e:
                return None
            launches += int(e["hist_launches"])
            rounds += int(e["trees"])
    return launches / rounds if rounds else None
