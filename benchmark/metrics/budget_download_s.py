"""Seconds the window's budget checks spent reading the margin back to the
host: its `margin_download` spans under `budget_check`, summed (what is left
of a check is the monitor)."""
from lib import build_spans, spans


def read(run):
    trees = spans.window_trees(run)
    if not any(build_spans.named(t) for t in trees):
        return None
    return spans.window_kind_seconds(run, "margin_download", under="budget_check")
