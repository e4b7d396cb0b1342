"""The airline on-time table as the GPU boosting benchmarks cut it (Mitchell
et al. 2018, arXiv:1806.11248; RAMitchell/GBM-Benchmarks): 13 numeric
predictors and the binary response ``ArrDelay > 0``, from the seed.

That benchmark reads the 1987-2008 on-time records, keeps 13 columns, turns
the text columns (carrier, origin, destination) into integer codes and hands
every column to the booster as a number; so every column here is ``num`` and
a split is a threshold, on a code too.  The CSV cannot be fetched on a sealed
machine, so the rows are drawn.  What a histogram fit's speed and its choice
of splits depend on is kept: six columns of 2 to 31 distinct values (Year,
Month, DayofMonth, DayOfWeek, UniqueCarrier, Diverted), whose quantile edges
tie and who fill far fewer than 256 bins; two clock columns and two of
minutes and miles that fill all of them; two airport columns of some 340
codes whose traffic falls off steeply; a flight number; NA in
ActualElapsedTime (a cancelled or diverted flight has none); and a response
that depends on which carrier and airport a row has, on the hour, and on the
minutes a flight took against the miles it flew, which no single column
gives away.

What the table IS comes from the configuration's ``table`` entry alone and is
the same for every seed: the code counts, which code is how busy and what
each code does to the response (drawn once from ``effects_seed``).  The rows
come from ``--seed``, in runs of a million rows with a stream each (children
of the seed), so several threads draw a large table.  Every count and
distribution below is an assumption (the configuration's ``assumed`` lists
them).
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = max(1, min(16, (os.cpu_count() or 2) - 2))
YEARS = np.arange(1987, 2009)
#: what a ``table`` entry may leave out
DEFAULTS = {"carriers": 29, "airports": 340, "positives": 0.47,
            "elapsed_na": 0.018, "diverted": 0.0023, "effects_seed": 20081}

NAMES = ("Year", "Month", "DayofMonth", "DayOfWeek", "CRSDepTime", "CRSArrTime",
         "UniqueCarrier", "FlightNum", "ActualElapsedTime", "Origin", "Dest",
         "Distance", "Diverted")


def _spec(spec: dict) -> dict:
    return {**DEFAULTS, **spec}


def columns(spec: dict):
    """The benchmark's column order; every column is a number."""
    return [{"name": name, "type": "num"} for name in NAMES]


def _shape(spec: dict) -> dict:
    """What every seed's rows share: each code's rank by traffic and its
    effect on the response, drawn independently of the code."""
    rng = np.random.default_rng(int(spec["effects_seed"]))
    sizes = {"year": len(YEARS), "month": 12, "weekday": 7,
             "carrier": int(spec["carriers"]), "origin": int(spec["airports"]),
             "dest": int(spec["airports"])}
    spread = {"year": 0.2, "month": 0.15, "weekday": 0.1, "carrier": 0.3,
              "origin": 0.35, "dest": 0.25}
    return {"rank": {k: rng.permutation(sizes[k]) for k in ("carrier", "origin", "dest")},
            "effect": {k: rng.normal(0.0, spread[k], n) for k, n in sizes.items()}}


def _zipf(rng, rank_of_code, exponent: float, rows: int):
    """Codes with frequencies ~ rank^-exponent, by the ranks the table dealt
    to the codes: a busy airport may have any code."""
    levels = len(rank_of_code)
    p = np.arange(1, levels + 1, dtype=np.float64) ** -exponent
    return np.argsort(rank_of_code)[rng.choice(levels, size=rows, p=p / p.sum())]


def _hhmm(minutes):
    m = np.mod(minutes, 1440.0)
    return np.floor(m / 60.0) * 100 + np.floor(np.mod(m, 60.0))


#: rows a stream: a table is drawn in runs of this many rows, each from its
#: own child of the seed, so that a large one is made by several threads
RUN = 1_000_000


def _run(spec: dict, shape: dict, rng, rows: int):
    """(X [rows, 13], logit [rows] without the intercept) of one run of rows."""
    rank, effect = shape["rank"], shape["effect"]
    X = np.empty((rows, len(NAMES)), np.float32)
    # more flights in the later years
    year = rng.choice(len(YEARS), size=rows, p=(w := np.linspace(1.0, 1.6, len(YEARS))) / w.sum())
    month = rng.integers(0, 12, rows)
    weekday = rng.integers(0, 7, rows)
    X[:, 0], X[:, 1], X[:, 3] = YEARS[year], month + 1, weekday + 1
    X[:, 2] = rng.integers(1, 32, rows)
    # more departures by day than by night
    dep = np.clip(rng.normal(13.5, 4.5, rows), 0.0, 23.99) * 60.0
    miles = np.round(np.clip(rng.lognormal(6.4, 0.7, rows), 30.0, 5000.0))
    planned = np.round(miles / 7.5 + 35.0 + rng.normal(0.0, 6.0, rows))
    took = planned + rng.gumbel(-5.0, 9.0, rows)  # a long right tail
    X[:, 4], X[:, 5] = _hhmm(dep), _hhmm(dep + planned)
    carrier = _zipf(rng, rank["carrier"], 0.8, rows)
    origin = _zipf(rng, rank["origin"], 1.1, rows)
    dest = _zipf(rng, rank["dest"], 1.1, rows)
    X[:, 6], X[:, 9], X[:, 10] = carrier, origin, dest
    # a carrier numbers its flights from 1; short numbers are the busy ones
    X[:, 7] = np.floor(np.clip(rng.lognormal(6.6, 1.0, rows), 1.0, 7999.0))
    X[:, 11] = miles
    diverted = rng.random(rows) < float(spec["diverted"])
    X[:, 12] = diverted
    X[:, 8] = np.round(np.maximum(took, 15.0))
    X[diverted | (rng.random(rows) < float(spec["elapsed_na"])), 8] = np.nan

    late = 1.0 / (1.0 + np.exp(-(dep / 60.0 - 14.0) / 3.0))  # a smooth rise over the day
    hub = rank["origin"] < max(1, int(spec["airports"]) // 10)
    logit = (effect["year"][year] + effect["month"][month] + effect["weekday"][weekday]
             + effect["carrier"][carrier] + effect["origin"][origin]
             + effect["dest"][dest] + 1.2 * late + 0.6 * hub[origin] * late
             + 0.06 * (took - miles / 7.5 - 35.0))
    return X, logit


def make(spec: dict, rows: int, seed: int):
    """(X float32 [rows, 13], y int32 in {0, 1}) for a configuration's
    ``table`` entry."""
    if int(spec["classes"]) != 2 or int(spec["features"]) != len(NAMES):
        raise SystemExit("airline13-synth makes 13 predictors and a binary response")
    spec = _spec(spec)
    shape = _shape(spec)
    starts = range(0, rows, RUN)
    # two streams a run of rows: the predictors', the response's
    streams = [np.random.default_rng(s) for s in
               np.random.SeedSequence(seed).spawn(2 * len(starts))]
    X = np.empty((rows, len(NAMES)), np.float32)
    logit = np.empty(rows)

    def fill(i):
        lo, hi = starts[i], min(rows, starts[i] + RUN)
        X[lo:hi], logit[lo:hi] = _run(spec, shape, streams[2 * i], hi - lo)

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fill, range(len(starts))))
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
    y = np.empty(rows, np.int32)

    def respond(i):
        a, b = starts[i], min(rows, starts[i] + RUN)
        p = 1.0 / (1.0 + np.exp(-(logit[a:b] + 0.5 * (lo + hi))))
        y[a:b] = streams[2 * i + 1].random(b - a) < p

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(respond, range(len(starts))))
    return X, y
