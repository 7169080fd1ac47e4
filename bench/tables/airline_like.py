"""The airline on-time table's shape, made from ``--seed`` on the host.

The real table (szilard/benchm-ml's preparation of the ASA airline
data, flights of 2005-2006) has eight predictors: `Month`,
`DayofMonth`, `DayOfWeek`, `UniqueCarrier`, `Origin`, `Dest`
(categorical) and `DepTime`, `Distance` (numeric), and the response
`dep_delayed_15min`, about a fifth positive. This file keeps the
columns, their kinds and level counts and the skew of the airports, not
the rows: a categorical column is handed over as integer level codes
with its domain (what parsing a CSV leaves), `Origin` and `Dest` are
drawn Zipf(0.8) with the rank-to-code map shuffled from the seed, and
the response hangs on a random effect of every level of every
categorical — drawn from the seed and unrelated to the level's code, so
that no ordering of the codes is of any use — plus an hour-of-day and a
distance term and logistic noise.

Made in fixed row chunks, as `datasets.higgs_like` is: a chunk's rows
depend on (seed, chunk index) alone, and the table never needs a second
full host copy."""

from __future__ import annotations

import numpy as np

CHUNK_ROWS = 1 << 20

# (name, levels: 0 = numeric), in the real table's column order
COLUMNS = (("Month", 12), ("DayofMonth", 31), ("DayOfWeek", 7),
           ("DepTime", 0), ("UniqueCarrier", 22), ("Origin", 300),
           ("Dest", 300), ("Distance", 0))
NAMES = tuple(n for n, _ in COLUMNS)
LEVELS = np.array([lv for _, lv in COLUMNS], dtype=np.int64)
N_FEATURES = len(COLUMNS)

ZIPF_S = 0.8                     # airports: hubs dominate
# standard deviation of a level's effect on the logit, by column
EFFECT_SD = {"Month": 0.35, "DayofMonth": 0.20, "DayOfWeek": 0.25,
             "UniqueCarrier": 0.45, "Origin": 0.60, "Dest": 0.50}
INTERCEPT = -1.95                # about a fifth of the flights positive


def domains() -> dict[str, list[str]]:
    """The level names of the categorical columns, code = position."""
    return {name: [f"{name[:3].lower()}{i:03d}" for i in range(lv)]
            for name, lv in COLUMNS if lv}


def _plan(seed: int):
    """What every chunk of one table shares: the level effects, and the
    airports' cumulative popularity by code."""
    rng = np.random.default_rng([int(seed), 0xA121])
    effects = {name: rng.normal(0.0, EFFECT_SD[name], lv)
               for name, lv in COLUMNS if lv}
    cum = {}
    for name in ("Origin", "Dest"):
        lv = dict(COLUMNS)[name]
        p = np.arange(1, lv + 1, dtype=np.float64) ** -ZIPF_S
        code_of_rank = rng.permutation(lv)
        by_code = np.empty(lv)
        by_code[code_of_rank] = p / p.sum()
        cum[name] = np.cumsum(by_code)
    return effects, cum


def _chunk(rows: int, seed: int, chunk: int, plan):
    effects, cum = plan
    rng = np.random.default_rng([int(seed), chunk])
    X = np.empty((N_FEATURES, rows), dtype=np.float32)
    logit = np.full(rows, INTERCEPT)
    for j, (name, lv) in enumerate(COLUMNS):
        if not lv:
            continue
        if name in cum:
            code = np.minimum(np.searchsorted(cum[name], rng.random(rows)),
                              lv - 1)
        else:
            code = rng.integers(0, lv, rows)
        X[j] = code
        logit += effects[name][code]
    # departures peak in the day; delays build up through it
    hour = np.clip(rng.normal(13.5, 4.5, rows), 0.0, 23.99)
    X[NAMES.index("DepTime")] = np.floor(hour) * 100 \
        + rng.integers(0, 60, rows)
    dist = 60.0 + rng.gamma(2.0, 350.0, rows)
    X[NAMES.index("Distance")] = dist
    logit += 0.9 * (hour - 13.5) / 4.5 * (hour > 6) \
        - 0.15 * (np.log(dist) - 6.4)
    y = logit + rng.logistic(size=rows) > 0
    return X, y


def airline_like(rows: int, seed: int):
    """(X [8, rows] float32, one contiguous row per column, the
    categorical columns' level codes as numbers; y [rows] bool). The
    same (rows, seed) gives the same table."""
    plan = _plan(seed)
    X = np.empty((N_FEATURES, rows), dtype=np.float32)
    y = np.empty(rows, dtype=bool)
    for c, lo in enumerate(range(0, rows, CHUNK_ROWS)):
        hi = min(lo + CHUNK_ROWS, rows)
        X[:, lo:hi], y[lo:hi] = _chunk(hi - lo, seed, c, plan)
    return X, y


def as_columns(X: np.ndarray, y: np.ndarray) -> dict[str, np.ndarray]:
    """The table as a user hands it to `Frame.from_arrays` beside
    `domains()`: the categorical columns as int32 codes, the numeric
    ones float32, the response as strings."""
    cols = {name: X[j].astype(np.int32) if lv else X[j]
            for j, (name, lv) in enumerate(COLUMNS)}
    cols["y"] = np.where(y, "Y", "N")
    return cols
