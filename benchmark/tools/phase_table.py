"""Where a traced window's time went, by the program's own names.

    python benchmark/tools/phase_table.py <workload> [--trace-dir .bench_trace]
        [--hlo FILE | --save-hlo FILE] [--ops-json FILE]

Reads the trace a ``--trace 1`` run kept (``.bench_trace/``) and prints, for
PERF.md section 5: the level x phase table of device ms a tree inside the
window's training blocks (``lib/scopes.py``: the compiled block's named
scopes), the operations no scope reaches, and the window's host spans in
order (the ``telemetry.Span`` annotations of the trace).  The block's
compiled text is lowered again for the attached devices, as a run does; with
``--hlo`` a saved text is read instead, so a trace brought back from the chip
can be read where no chip is (``--save-hlo`` writes the text for that).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def level_table(scoped: dict, trees: int) -> str:
    from lib import scopes

    cols = list(scopes.LEVEL_PHASES)
    rows = ["| level | " + " | ".join(cols) + " | all |",
            "| --- |" + " ---: |" * (len(cols) + 1)]
    total = {c: 0.0 for c in cols}
    for level in sorted(scoped["levels"]):
        row = scoped["levels"][level]
        cells = [1e3 * row.get(c, 0.0) / trees for c in cols]
        for c, v in zip(cols, cells):
            total[c] += v
        rows.append(f"| {level} | " + " | ".join(f"{v:.1f}" for v in cells)
                    + f" | {sum(cells):.1f} |")
    rows.append("| all levels | " + " | ".join(f"{total[c]:.1f}" for c in cols)
                + f" | {sum(total.values()):.1f} |")
    return "\n".join(rows)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--trace-dir", default=os.path.join(ROOT, ".bench_trace"))
    ap.add_argument("--hlo", help="read the block's compiled text from this file")
    ap.add_argument("--save-hlo", help="write the lowered block's compiled text here")
    ap.add_argument("--ops-json", help="write every block operation's self seconds and scope here")
    args = ap.parse_args()

    from lib import scopes, spans

    config = scopes._config_of(ROOT, args.workload)
    os.environ.update({k: str(v) for k, v in config.get("env", {}).items()})
    traced = scopes.read_trace(args.trace_dir, "timed_window")
    if traced is None or not traced["blocks"]:
        raise SystemExit(f"no traced window with training blocks under {args.trace_dir}")

    if args.hlo:
        with open(args.hlo) as f:
            text = f.read()
    else:
        import jax
        from h2o3_tpu.models.tree.booster import tree_block_size

        from lib import programs

        text = scopes.block_text(
            programs.tiny_fit_spec(config, ROOT), int(config["table"]["rows"]),
            int(config["table"]["features"]), tree_block_size(), jax.devices())
        if args.save_hlo:
            with open(args.save_hlo, "w") as f:
                f.write(text)
    op_names = scopes.scopes_from_text(text)
    scoped = scopes.by_scope(traced["ops"], traced["blocks"], op_names)
    if scoped is None:
        raise SystemExit("the block program carries no named scope")

    block = int(config.get("env", {}).get("H2O3_TPU_TREE_BLOCK", 16))
    trees = len(traced["blocks"]) * block
    lo, hi = traced["window"]
    print(f"## {args.workload}: {len(traced['blocks'])} blocks of {block} trees, "
          f"window {(hi - lo) / 1e9:.2f} s, device busy in the blocks "
          f"{scoped['busy_s']:.2f} s ({1e3 * scoped['busy_s'] / trees:.1f} ms a tree)\n")
    print("Device ms a tree by level and phase:\n")
    print(level_table(scoped, trees))
    print("\nPer tree, outside the levels (ms): " + ", ".join(
        f"{p} {1e3 * scoped['phases'].get(p, 0.0) / trees:.1f}"
        for p in scopes.TREE_PHASES + (scopes.UNSCOPED,)))
    print(f"\nUnscoped {100 * scoped['phases'].get(scopes.UNSCOPED, 0.0) / scoped['busy_s']:.2f}% "
          "of the blocks' busy time; the operations with most of it (ms a tree):")
    for name, s in scoped["unscoped_ops"][:10]:
        print(f"  {name}  {1e3 * s / trees:.2f}")

    if args.ops_json:
        rows = {}
        inside = [op for op in traced["ops"]
                  if any(a <= op[1] and op[1] + op[2] <= b for a, b in traced["blocks"])]
        for op, self_ns in scopes.self_times(inside):
            name = op[3] or op_names.get(op[0])
            slot = rows.setdefault(op[0], {"self_s": 0.0, "n": 0, "op_name": name,
                                           "phase": scopes.phase_of(name)})
            slot["self_s"] += self_ns / 1e9
            slot["n"] += 1
        with open(args.ops_json, "w") as f:
            json.dump(rows, f)

    fits = [e for e in traced["spans"] if e["kind"] == "train"
            and lo <= e["start_ns"] and e["ns"] <= hi]
    for fit in fits:
        tree = spans.fit_tree(traced["spans"], fit["start_ns"], fit["ns"])
        print(f"\nHost spans of the window's fit (s after `train` starts, seconds; "
              f"under no leaf span {spans.uncovered_seconds(tree):.3f} s):\n")
        for row in spans.in_order(tree):
            print(f"  {'  ' * row['depth']}{row['kind']:<{24 - 2 * row['depth']}}"
                  f"{row['start_s']:>9.3f} {row['seconds']:>9.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
