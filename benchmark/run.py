"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by the names in
``BENCHMARK.json``: the configuration (``benchmark/configs/<config>.json``),
the traffic mix (``benchmark/traffic/<traffic>.json``) and one reader per
metric (``benchmark/metrics/<metric>.py``).  The last line of standard
output is the result object; with no accelerator (or in a directory that
holds only the benchmark) the command exits non-zero and prints no result.
``--rehearse`` drives the same control flow at a tiny size on the CPU and
prints no metric.
"""

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def load_cell(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"there are: {', '.join(sorted(cells))}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def metric_names(bench: dict, cell: dict, group: str):
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, control flow only: prints no metric")
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload)
    # settings of the deployment that the program reads from its environment
    os.environ.update({k: str(v) for k, v in config.get("env", {}).items()})
    try:
        import h2o3_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"the program is not in this checkout ({e}); "
                         "the benchmark measures it and nothing else")

    from lib import harness

    result = harness.run(
        cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse, t_start=T_START, root=ROOT,
        metrics=metric_names(
            bench, cell, "per_layer" if args.trace else "end_to_end"),
    )
    checks = result["checks"]
    for name, (value, limit) in checks.items():
        print(f"check {name} = {value:.6g} (limit {limit:.6g}) "
              f"{'ok' if value <= limit else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
