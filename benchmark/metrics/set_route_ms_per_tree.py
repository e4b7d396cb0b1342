"""Device ms a tree under the scopes `L<dd>/route/sets`: a row's node fields
and its node's set fetched by one lookup, and the test of the row's own bit.
Part of `route_ms_per_tree`.  Device trace, by the compiled block's scopes,
read as `set_split_ms_per_tree` reads its own (that file's functions, this
file's pattern); left out by a program whose block has no such scope."""
import os
import re
import sys

from lib import harness

PATTERN = re.compile(r"(?:^|/)L\d\d/route/sets(?:/|$)")


def read(run):
    # the module a run has loaded already keeps the parsed trace
    split = sys.modules.get("metrics_set_split_ms_per_tree")
    if split is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        split = harness.load_named(root, "metrics", "set_split_ms_per_tree")
    return split.ms_per_tree(run, PATTERN)
