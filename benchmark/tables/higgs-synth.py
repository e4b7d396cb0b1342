"""UCI HIGGS's shape from the seed: numeric features and a binary response.

A copy of the generator the repo's smoke test proved on the chip
(``bench.synth_higgs``): standard normal features and a logistic response
with one interaction, so a tree model has signal to find at every depth.
Speed depends on the shape only; the values stand in for the HIGGS rows,
which a sealed machine cannot fetch.
"""

import numpy as np


def make(spec: dict, rows: int, seed: int):
    """(X float32 [rows, features], y int32 class codes) for a
    configuration's ``table`` entry."""
    if int(spec["classes"]) != 2:
        raise SystemExit("higgs-synth makes a binary response; a table of "
                         f"{spec['classes']} classes needs a generator of its own")
    features = int(spec["features"])
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    w = rng.normal(size=features) / np.sqrt(features)
    logit = X @ w + 0.5 * X[:, 0] * X[:, 1]
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int32)
    return X, y
