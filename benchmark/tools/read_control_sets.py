"""Upper readings for the limits of a configuration with categorical columns:
the control and the planted faults, at the cell's own size, through the plain
reference alone.

    python benchmark/tools/read_control_sets.py <config> --seeds 31 32 33 --rounds 0 2 5 [--rows N]

``read_control.py`` beside this file bins every column by quantiles of
``nbins``; this one bins by the table's ``columns``: a numeric column by
quantiles, a categorical a bin a level.  The reference the configuration
names (``benchmark/references/hist-gbm-sets.py``, or another that offers the
same ``boost`` / ``judge`` / ``FAULTS``) is put in the program's place
(``boost``, from its own argmax) once per variant — gradients and hessians
rounded to the stated precision (bfloat16, for comparison), to the control's
(fp8), and float64 with one fault planted, ``label_codes`` (thresholds on a
categorical's level codes, what ``enum`` meant before sets) among them, and
``flipped_bit`` (one bit of one set misread at scoring: ``logloss_gap``,
``auc_gap``) — and
judged at ``--rounds`` as a run's answer is; ``bin_rank_gap`` is read with
equal-width bins of the numeric columns in the quantile bins' place.  Host numpy float64; it never
touches a device.  One JSON line a seed and variant; PERF.md section 6 keeps
the smallest of each.
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
                    help="of: bfloat16 fp8, or a fault's name (default: all)")
    args = ap.parse_args()

    from lib import harness

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    root = os.path.dirname(HERE)
    ref = harness.load_named(root, "references", config["reference"])
    rows = args.rows or int(config["table"]["rows"])
    variants = [("bfloat16", None), ("fp8", None)] + [
        ("float64", f) for f in ref.FAULTS + ("flipped_bit",)]
    if args.variants:
        variants = [v for v in variants if (v[1] or v[0]) in args.variants]
    for seed in args.seeds:
        table = harness.make_table(root, config, rows, seed)
        X, yf, classes = table["X"], table["y"].astype(np.float64), table["classes"]
        p = ref.RefParams.from_config(config["params"], seed)
        cat_levels = ref.cat_levels_of(table["columns"], X.shape[1])
        codes = ref.bin_codes(X, ref.quantile_edges(X, p.nbins, cat_levels), cat_levels)
        half = ref.init_margin(p.distribution, yf[::2], classes)
        print(json.dumps({"config": args.config, "seed": seed, "rows": rows,
                          "variant": "init_margin from half the rows",
                          "init_margin_gap": float(np.abs(
                              half - ref.init_margin(p.distribution, yf, classes)).max())}),
              flush=True)
        numeric = [f for f in range(X.shape[1]) if not cat_levels[f]]
        width = np.stack([np.linspace(np.nanmin(X[:, f]), np.nanmax(X[:, f]), p.nbins + 1)[1:-1]
                          for f in range(X.shape[1])])
        print(json.dumps({"config": args.config, "seed": seed, "rows": rows,
                          "variant": "equal-width bins of the numeric columns",
                          "bin_rank_gap": ref.base.bin_rank_gap(
                              ref.bin_codes(X, width, cat_levels)[numeric], p.nbins)}),
              flush=True)
        for precision, fault in variants:
            t0 = time.time()
            if fault == "flipped_bit":
                # metrics reported from sets with one bit flipped against
                # the walk of the true sets: the scoring side's numbers
                model = ref.boost(codes, yf, p, 2, cat_levels, classes)
                mine = ref.score(codes, yf, p, model, classes)
                theirs = ref.score(codes, yf, p, ref.flip_one_bit(model), classes)
                print(json.dumps({"config": args.config, "seed": seed, "rows": rows,
                                  "variant": fault,
                                  "logloss_gap": abs(theirs["logloss"] - mine["logloss"])
                                  / mine["logloss"],
                                  "auc_gap": abs(theirs["auc"] - mine["auc"]),
                                  "seconds": round(time.time() - t0, 1)}), flush=True)
                continue
            model = ref.boost(codes, yf, p, max(args.rounds) + 1, cat_levels, classes,
                              precision=precision, fault=fault)
            judged = ref.judge(codes, yf, p, model, args.rounds, cat_levels, classes,
                               stated=ref.stated_precision(config))
            print(json.dumps({"config": args.config, "seed": seed, "rows": rows,
                              "variant": fault or precision, "rounds": args.rounds,
                              **{k: judged[k] for k in ref.JUDGED},
                              "seconds": round(time.time() - t0, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
