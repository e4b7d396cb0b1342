"""Post-fit scoring, the metrics: the `score_metrics` span of the window's
fit (response vector and the `models/metrics` call over every row)."""
from lib import spans


def read(run):
    return spans.window_kind_seconds(run, "score_metrics")
