"""End of a fit's last tree_block span to the return of train(): the last
read-back and model_performance over the training frame (apply_bins,
traversal, metrics)."""


def read(run):
    tails = [(s["t1_ns"] - s["blocks"][-1]["end_ns"]) / 1e9
             for s in run["served"] if s["blocks"]]
    return sum(tails) if tails else None
