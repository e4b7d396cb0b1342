"""The airline on-time table's shape from the seed (szilard/GBM-perf's
training set): six categorical and two numeric predictors and the binary
``dep_delayed_15min``.

The CSV cannot be fetched on a sealed machine, so the rows are drawn: the
columns, their types and their level counts are the published table's, the
values stand in for it.  What a tree fit's speed and its choice of splits
depend on is kept: three small calendar columns, a carrier column, two
airport columns of some 300 levels whose frequencies fall off steeply (a
few hubs hold most flights), and a response that depends on WHICH level a
row has and not on the level's index.  Every level's effect is drawn from
the seed independently of its index, so the order of the codes carries
nothing: a threshold on codes can only cut the levels into two runs of
indices and forgoes gain that a set of levels takes.

What the table IS comes from the configuration's ``table`` entry alone and
is the same for every seed: the level counts, which level is how busy and
what each level does to the response (drawn once from ``effects_seed``).
The rows come from ``--seed``.  A level's code is its place in the lexical
order of the level names, as a parser gives them.

Values the source does not give, each an assumption (the configuration's
``assumed`` lists them): the level counts of Origin and Dest (``origins``,
``dests``, 300 each), the Zipf exponents, the effects' spread, the shape of
the rise over DepTime, the Distance term, the Origin x DepTime interaction
(flights out of the busiest tenth of the origins are delayed more as the
day goes on), and the share of positives (``positives``, 0.19).
No NA: the source has none.
"""

import numpy as np

#: calendar and carrier columns: the published table's level counts
MONTHS, DAYS, WEEKDAYS, CARRIERS = 12, 31, 7, 22


#: what a ``table`` entry may leave out
DEFAULTS = {"origins": 300, "dests": 300, "positives": 0.19, "effects_seed": 20071}


def _named(prefix: str, n: int):
    """n level names in their lexical order (``c-1, c-10, c-11, c-12, c-2``)."""
    return sorted(f"{prefix}{i + 1}" for i in range(n))


def _spec(spec: dict) -> dict:
    return {**DEFAULTS, **spec}


def columns(spec: dict):
    """The published column order; a categorical's values are its level
    codes, in the order of its domain."""
    spec = _spec(spec)
    return [
        {"name": "Month", "type": "cat", "domain": _named("c-", MONTHS)},
        {"name": "DayofMonth", "type": "cat", "domain": _named("c-", DAYS)},
        {"name": "DayOfWeek", "type": "cat", "domain": _named("c-", WEEKDAYS)},
        {"name": "DepTime", "type": "num"},
        {"name": "UniqueCarrier", "type": "cat", "domain": _named("K", CARRIERS)},
        {"name": "Origin", "type": "cat", "domain": _named("A", int(spec["origins"]))},
        {"name": "Dest", "type": "cat", "domain": _named("A", int(spec["dests"]))},
        {"name": "Distance", "type": "num"},
    ]


def _zipf(rng, rank_of_code, exponent: float, rows: int):
    """Level codes with frequencies ~ rank^-exponent, by the ranks the table
    dealt to the codes: a busy airport may have any code."""
    levels = len(rank_of_code)
    p = np.arange(1, levels + 1, dtype=np.float64) ** -exponent
    p /= p.sum()
    return np.argsort(rank_of_code)[rng.choice(levels, size=rows, p=p)]


def _shape(spec: dict) -> dict:
    """What every seed's rows share: each level's rank by traffic and its
    effect on the response, drawn independently of the level's code."""
    rng = np.random.default_rng(int(spec["effects_seed"]))
    sizes = {"month": MONTHS, "day": DAYS, "weekday": WEEKDAYS, "carrier": CARRIERS,
             "origin": int(spec["origins"]), "dest": int(spec["dests"])}
    spread = {"month": 0.15, "day": 0.05, "weekday": 0.1, "carrier": 0.3,
              "origin": 0.45, "dest": 0.3}
    return {"rank": {k: rng.permutation(sizes[k]) for k in ("carrier", "origin", "dest")},
            "effect": {k: rng.normal(0.0, spread[k], n) for k, n in sizes.items()}}


def make(spec: dict, rows: int, seed: int):
    """(X float32 [rows, 8], y int32 in {0, 1}) for a configuration's
    ``table`` entry."""
    if int(spec["classes"]) != 2 or int(spec["features"]) != 8:
        raise SystemExit("airline-synth makes 8 predictors and a binary response")
    spec = _spec(spec)
    shape = _shape(spec)
    rank, effect = shape["rank"], shape["effect"]
    rng = np.random.default_rng(seed)
    origins = int(spec["origins"])
    X = np.empty((rows, 8), np.float32)
    X[:, 0] = rng.integers(0, MONTHS, rows)
    X[:, 1] = rng.integers(0, DAYS, rows)
    X[:, 2] = rng.integers(0, WEEKDAYS, rows)
    # hhmm, more departures by day than by night
    hour = np.clip(rng.normal(13.5, 4.5, rows), 0.0, 23.99)
    X[:, 3] = np.floor(hour) * 100 + np.floor((hour % 1.0) * 60)
    carrier = _zipf(rng, rank["carrier"], 0.8, rows)
    origin = _zipf(rng, rank["origin"], 1.1, rows)
    dest = _zipf(rng, rank["dest"], 1.1, rows)
    X[:, 4], X[:, 5], X[:, 6] = carrier, origin, dest
    X[:, 7] = np.round(np.clip(rng.lognormal(6.4, 0.7, rows), 30.0, 5000.0))

    late = 1.0 / (1.0 + np.exp(-(hour - 14.0) / 3.0))  # a smooth rise over the day
    hub = rank["origin"] < max(1, origins // 10)
    logit = (effect["month"][X[:, 0].astype(np.int64)]
             + effect["day"][X[:, 1].astype(np.int64)]
             + effect["weekday"][X[:, 2].astype(np.int64)]
             + effect["carrier"][carrier] + effect["origin"][origin]
             + effect["dest"][dest]
             + 1.6 * late + 0.8 * hub[origin] * late
             - 0.1 * np.log(X[:, 7].astype(np.float64) / 600.0))
    # the intercept that gives the stated share of positives, by bisection
    # on a sample (the same for a seed)
    target = float(spec["positives"])
    sample = logit[:: max(1, rows // 200_000)]
    lo, hi = -20.0, 20.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(sample + mid)))) < target:
            lo = mid
        else:
            hi = mid
    p = 1.0 / (1.0 + np.exp(-(logit + 0.5 * (lo + hi))))
    return X, (rng.random(rows) < p).astype(np.int32)
