"""Upper readings for a configuration's limits: the control and the planted
faults, at the cell's own size, through the plain reference alone.

    python benchmark/tools/read_control.py <config> --seeds 31 32 33 --rounds 0 2 5 [--rows N]

The reference the configuration names (``benchmark/references/hist-gbm.py``
or another that offers the same ``boost`` / ``judge``) is put in the
program's place (``boost``, from its own argmax) once per variant —
gradients and hessians rounded to the stated precision (bfloat16, for
comparison), to the control's (fp8), and float64 with one fault planted —
and judged at ``--rounds`` as a run's answer is.
It is host numpy float64 and never touches a device.  One JSON line a seed
and variant; PERF.md section 6 keeps the smallest of each.
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

VARIANTS = [("bfloat16", None), ("fp8", None), ("float64", "state_unchanged"),
            ("float64", "half_batch"), ("float64", "leaf_altered")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rounds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int)
    args = ap.parse_args()

    from lib import harness

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    root = os.path.dirname(HERE)
    ref = harness.load_named(root, "references", config["reference"])
    rows = args.rows or int(config["table"]["rows"])
    classes = int(config["table"]["classes"])
    make = harness.load_named(root, "tables", config["table"]["generator"]).make
    for seed in args.seeds:
        X, y = make(config["table"], rows, seed)
        yf = y.astype(np.float64)
        p = ref.RefParams.from_config(config["params"], seed)
        qs = np.linspace(0, 1, p.nbins + 1)[1:-1]
        edges = np.stack([np.quantile(X[:, f].astype(np.float64), qs)
                          for f in range(X.shape[1])])
        codes = ref.bin_codes(X, edges)
        half = ref.init_margin(p.distribution, yf[::2], classes)
        print(json.dumps({"config": args.config, "seed": seed, "rows": rows,
                          "variant": "init_margin from half the rows",
                          "init_margin_gap": float(np.abs(
                              half - ref.init_margin(p.distribution, yf, classes)).max())}),
              flush=True)
        for precision, fault in VARIANTS:
            t0 = time.time()
            model = ref.boost(codes, yf, p, max(args.rounds) + 1, classes,
                              precision=precision, fault=fault)
            judged = ref.judge(codes, yf, p, model, args.rounds, classes)
            print(json.dumps({"config": args.config, "seed": seed, "rows": rows,
                              "variant": fault or precision, "rounds": args.rounds,
                              **{k: judged[k] for k in ref.JUDGED},
                              "seconds": round(time.time() - t0, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
