"""The whole boosting step against ALL the chips it runs on: least time the
attached devices together need for one tree's work (lib/work.py, over
`device.count` times one chip's peak; bytes bind on the v5e) over
block_ms_per_tree.  `train_step_mfu` divides the whole table's work by one
chip's peak, so on four chips it reads four times a chip's share; this is
the share of what the cell was given."""
from lib.work import least_seconds


def read(run):
    import jax

    peak = run["peak"]
    blocks = [b for s in run["served"] for b in s["blocks"]]
    trees = sum(b["trees"] for b in blocks)
    if not peak or not trees:
        return None
    chips = len(jax.devices())
    all_chips = {k: v * chips for k, v in peak.items() if k.endswith("_per_s")}
    per_tree = sum(b["end_ns"] - b["start_ns"] for b in blocks) / 1e9 / trees
    w = run["work"]
    return 100.0 * least_seconds(w["ops"], w["bytes"], all_chips)["seconds"] / per_tree
