"""Upper readings for the limits of a configuration judged by ``hist-xgb``:
the control and the planted faults, at the cell's own size, through the plain
reference alone.

    python benchmark/tools/read_control_xgb.py <config> --seeds 31 32 33 --rounds 0 2 5 [--rows N] [--variants ...]

``read_control.py`` beside this file knows neither NA in a column nor the
faults of XGBoost's parameters; this one bins by quantiles that skip NaN and
runs every fault the reference names (``FAULTS``: a builder without lambda,
without gamma, with the floor on row counts, without the class weight, with
one shard of four left out of every histogram's sum, beside ``hist-gbm``'s
three).  The reference is put in the program's place (``boost``, from its own
argmax) once per variant — gradients and hessians rounded to the stated
precision (bfloat16, for comparison), to the control's (fp8), and float64
with one fault planted — and judged at ``--rounds`` as a run's answer is;
``bin_rank_gap`` is read with equal-width bins in the quantile bins' place,
``init_margin_gap`` with a start from half the rows, ``logloss_gap`` and
``auc_gap`` with metrics reported over half the rows.  Host numpy float64; it
never touches a device.  One JSON line a seed and variant; PERF.md section 6
keeps the smallest of each.
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
    ap.add_argument("--rounds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--variants", nargs="+",
                    help="of: bfloat16 fp8 half_rows, or a fault's name (default: all)")
    args = ap.parse_args()

    from lib import harness

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    root = os.path.dirname(HERE)
    ref = harness.load_named(root, "references", config["reference"])
    rows = args.rows or int(config["table"]["rows"])
    variants = [("bfloat16", None), ("fp8", None)] + [("float64", f) for f in ref.FAULTS]
    if args.variants:
        variants = [v for v in variants if (v[1] or v[0]) in args.variants]

    def say(seed, variant, **readings):
        print(json.dumps({"config": args.config, "seed": seed, "rows": rows,
                          "variant": variant, **readings}), flush=True)

    for seed in args.seeds:
        table = harness.make_table(root, config, rows, seed)
        X, yf, classes = table["X"], table["y"].astype(np.float64), table["classes"]
        p = ref.RefParams.from_config(config["params"], seed)
        qs = np.linspace(0, 1, p.nbins + 1)[1:-1]
        sample = X[:: max(1, rows // 200_000)].astype(np.float64)
        codes = ref.bin_codes(X, np.stack(
            [np.nanquantile(sample[:, f], qs) for f in range(X.shape[1])]))
        say(seed, "init_margin from half the rows", init_margin_gap=float(np.abs(
            ref.init_margin(p.distribution, yf[::2], classes)
            - ref.init_margin(p.distribution, yf, classes)).max()))
        width = np.stack([np.linspace(np.nanmin(X[:, f]), np.nanmax(X[:, f]), p.nbins + 1)[1:-1]
                          for f in range(X.shape[1])])
        say(seed, "equal-width bins",
            bin_rank_gap=ref.base.bin_rank_gap(ref.bin_codes(X, width), p.nbins))
        if not args.variants or "half_rows" in args.variants:
            # metrics reported over every other row against the walk of all
            t0 = time.time()
            model = ref.boost(codes, yf, p, 2, classes)
            mine = ref.score(codes, yf, p, model, classes)
            theirs = ref.score(codes[:, ::2], yf[::2], p, model, classes)
            say(seed, "metrics over half the rows",
                logloss_gap=abs(theirs["logloss"] - mine["logloss"]) / mine["logloss"],
                auc_gap=abs(theirs["auc"] - mine["auc"]), seconds=round(time.time() - t0, 1))
        for precision, fault in variants:
            t0 = time.time()
            model = ref.boost(codes, yf, p, max(args.rounds) + 1, classes,
                              precision=precision, fault=fault)
            judged = ref.judge(codes, yf, p, model, args.rounds, classes)
            say(seed, fault or precision, rounds=args.rounds,
                **{k: judged[k] for k in ref.JUDGED}, seconds=round(time.time() - t0, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
