"""Device ms a tree under the scopes `L<dd>/split/sets`: the ordering of a
node's categorical levels by their ratio of sums (one sort over the
categoricals' slices), and the chosen prefix read back as a set of codes.
Part of `split_ms_per_tree`.  Device trace, by the compiled block's scopes:
the trace and the block's text through lib/scopes.py's own functions, with a
pattern of this file's; left out by a program whose block has no such scope."""
import os
import re
import sys

from lib import scopes

_MEMO = {}


def block_ops(run, marker="timed_window"):
    """(op_name, self seconds) of the operations inside the window's blocks,
    and every op_name of the block's compiled text; None with no trace, no
    block, or a block that cannot be lowered again."""
    if not run.get("trace"):
        return None
    trace_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(scopes.__file__)))), ".bench_trace")
    key = trace_dir + "|" + run["cell"]
    if key not in _MEMO:
        _MEMO[key] = None
        traced = scopes.read_trace(trace_dir, marker)
        if traced and traced["blocks"]:
            try:
                import jax
                from h2o3_tpu.models.tree.booster import tree_block_size

                names = scopes.scopes_from_text(scopes.block_text(
                    run["block_program"], run["rows"], run["features"],
                    tree_block_size(), jax.devices()))
            except Exception as e:  # the program's internals moved
                print(f"note: the training block could not be lowered again ({e!r}); "
                      "the metrics of single scopes are left out", file=sys.stderr)
                return None
            inside = [op for op in traced["ops"]
                      if any(lo <= op[1] and op[1] + op[2] <= hi
                             for lo, hi in traced["blocks"])]
            _MEMO[key] = ([(op[3] or names.get(op[0]) or "", self_ns / 1e9)
                           for op, self_ns in scopes.self_times(inside)],
                          sorted(set(names.values())))
    return _MEMO[key]


def ms_per_tree(run, pattern):
    """Device ms a tree, in the window's blocks, of the operations whose
    op_name matches ``pattern``; None where the block's text has none."""
    found = block_ops(run)
    trees = sum(b["trees"] for s in run["served"] for b in s["blocks"])
    if found is None or not trees:
        return None
    ops, names = found
    if not any(pattern.search(name) for name in names):
        return None
    return 1e3 * sum(s for name, s in ops if pattern.search(name)) / trees


PATTERN = re.compile(r"(?:^|/)L\d\d/split/sets(?:/|$)")


def read(run):
    return ms_per_tree(run, PATTERN)
