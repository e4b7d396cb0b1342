"""Share of the device's idle time in the traced window (the first device's
plane) during which no leaf span of the window's fits was open: the chip
waiting on host work that has no name.  The leaves are the ring's; their
intervals are their annotations in the trace, found by `span_id`."""
from lib import build_spans, harness, spans


def read(run):
    if not run["trace"]:
        return None
    trees = [t for t in spans.window_trees(run) if build_spans.named(t)]
    if not trees:
        return None
    ids = {e["span_id"]: e["kind"] for t in trees for e in build_spans.leaves(t)}
    traced = build_spans.read_trace(build_spans.TRACE_DIR, harness.WINDOW_MARKER,
                                    set(ids.values()))
    if traced is None:
        return None
    ops, window, annotated = traced
    return build_spans.idle_unspanned_share(
        ops, window, [annotated[i] for i in ids if i in annotated])
