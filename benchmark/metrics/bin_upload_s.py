"""What the first fit on a new frame pays before its first device block:
frame to matrix, quantile sketch, apply_bins, upload.  Read from the warm-up
fit: its train() call to the start of its first tree_block span."""


def read(run):
    warm = run["warmup"]
    if not warm["blocks"]:
        return None
    return (warm["blocks"][0]["start_ns"] - warm["t0_ns"]) / 1e9
