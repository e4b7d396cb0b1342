"""Sum of the window's tree_block spans over the trees they built."""


def read(run):
    blocks = [b for s in run["served"] for b in s["blocks"]]
    trees = sum(b["trees"] for b in blocks)
    if not trees:
        return None
    return sum(b["end_ns"] - b["start_ns"] for b in blocks) / 1e6 / trees
