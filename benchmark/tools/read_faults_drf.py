"""Upper readings for the limits of a configuration judged by ``hist-drf``:
the planted faults at the cell's own size, through the plain reference alone.

    python benchmark/tools/read_faults_drf.py <config> --seeds 31 [--rows N] [--trees 2]

The reference is put in the program's place (``forest``, from its own
argmax) once a fault it names (``FAULTS``: every feature a candidate, trees
cut at depth 12, trees summed and not averaged, every row in every tree) and
once with none, and judged as a run's answer is (trees 0 and 1, the cell's
blocks of one tree). A DRF's g in {0, -1} and h = 1 are exact in every
precision a kernel may use, so there is no rounding control: the faults are
the upper readings. Bins are the program's quantile edges (``make_bins``).
Host numpy float64; it never touches a device. One JSON line a seed and
variant; PERF.md section 6 keeps the smallest of each.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--trees", type=int, default=2)
    args = ap.parse_args()

    from h2o3_tpu.ops.histogram import make_bins
    from lib import checks, harness

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    root = os.path.dirname(HERE)
    limits = checks.load_limits(root, args.config)
    ref = checks.load_reference(root, config, limits)
    rows = args.rows or int(config["table"]["rows"])
    for seed in args.seeds:
        table = harness.make_table(root, config, rows, seed)
        X, y = table["X"], table["y"]
        edges = make_bins(X, int(config["params"]["nbins"]), seed=seed)
        codes = ref.base.bin_codes(X, edges)
        p = ref.RefParams.from_config(config["params"], seed, X.shape[1], table["classes"])
        for fault in (None,) + tuple(ref.FAULTS):
            t0 = time.time()
            answer = dict(ref.forest(codes, y.astype(np.float64), p, args.trees, fault=fault),
                          edges=edges)
            got = ref.compare(config, seed, table, [answer], 1, list(limits))
            print(json.dumps({"seed": seed, "rows": rows, "fault": fault,
                              "seconds": round(time.time() - t0, 1), **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
