"""A table of any shape from the seed: ``features`` standard normal columns
and a response that is linear in them with one interaction — a class code
drawn from the softmax of ``classes`` such scores, or, with ``classes`` 1,
the first score plus unit noise (regression).  For a configuration whose
published table cannot be fetched and whose shape is all that speed
depends on (covtype: 54 features, 7 classes).
"""

import numpy as np


def make(spec: dict, rows: int, seed: int):
    """(X float32 [rows, features], y: int32 class codes, or float64)."""
    features, classes = int(spec["features"]), int(spec["classes"])
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    w = rng.normal(size=(features, max(classes, 1))) / np.sqrt(features)
    score = X @ w
    score[:, 0] += 0.5 * X[:, 0] * X[:, 1]
    if classes < 2:
        return X, score[:, 0] + rng.normal(size=rows)
    # Gumbel-max: a draw from the softmax of the scores
    return X, np.argmax(score + rng.gumbel(size=score.shape), axis=1).astype(np.int32)
