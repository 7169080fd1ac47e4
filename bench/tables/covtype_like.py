"""The UCI Covertype table's shape, made from ``--seed`` on the host.

The real table (Blackard and Dean 1999; 581,012 cells of 30 x 30 m of
the Roosevelt National Forest) has 54 predictors — ten quantitative
(elevation, aspect, slope, three horizontal distances and one signed
vertical one, three hillshade indices), a 4-way one-hot wilderness area
and a 40-way one-hot soil type — and a forest cover type of 7 classes
at about 36.5 / 48.8 / 6.2 / 0.47 / 1.6 / 3.0 / 3.5 %. This file keeps
the columns, their kinds and ranges, the one-hot groups with exactly
one 1 a row and their skew (the three rarest soil types hold under a
hundred rows of 581,012), and the classes' shares; not the rows. A
row's class is the largest of seven latent scores: a bell over the
elevation about the class's own altitude (cottonwood lowest, krummholz
highest, as in the forest), an effect of its wilderness area (some
classes grow in some areas only) and of its soil type, a little of two
other columns, the class's offset, and Gumbel noise. The offsets are
found from the seed so that the shares come out as published: trees
find real splits, and class 4 (0.47%, ~2,750 rows) is learnable.

Made in fixed row chunks, as `datasets.higgs_like` is: a chunk's rows
depend on (seed, chunk index) alone."""

from __future__ import annotations

import numpy as np

CHUNK_ROWS = 1 << 18

QUANT = ("Elevation", "Aspect", "Slope",
         "Horizontal_Distance_To_Hydrology",
         "Vertical_Distance_To_Hydrology",
         "Horizontal_Distance_To_Roadways", "Hillshade_9am",
         "Hillshade_Noon", "Hillshade_3pm",
         "Horizontal_Distance_To_Fire_Points")
N_WILD, N_SOIL, CLASSES = 4, 40, 7
NAMES = QUANT + tuple(f"Wilderness_Area{i + 1}" for i in range(N_WILD)) \
    + tuple(f"Soil_Type{i + 1}" for i in range(N_SOIL))
N_FEATURES = len(NAMES)                       # 54
LABELS = np.array(list("1234567"))            # the response's 7 levels

SHARES = np.array([0.365, 0.488, 0.062, 0.0047, 0.016, 0.030, 0.035])
SHARES = SHARES / SHARES.sum()
WILD_P = np.array([0.449, 0.051, 0.436, 0.064])
# soil types: rank r holds a share that falls off as exp(-r / 5.3) —
# the commonest a sixth of the rows, the three rarest under a hundred
# of 581,012 — and a fixed shuffle says which type has which rank
_FIXED = np.random.default_rng(0xC07E)
SOIL_P = np.exp(-np.arange(N_SOIL) / 5.3)
SOIL_P = (SOIL_P / SOIL_P.sum())[_FIXED.permutation(N_SOIL)]
# metres a wilderness area and a soil type lie above or below the rest
WILD_ELEV = np.array([3000.0, 3300.0, 2900.0, 2250.0])
SOIL_ELEV = _FIXED.normal(0.0, 120.0, N_SOIL)
# the classes: altitude and its spread, where they grow, on what soil
CLASS_ELEV = np.array([3130., 2930., 2400., 2220., 2790., 2420., 3360.])
CLASS_SPREAD = np.array([160., 190., 190., 100., 120., 170., 110.])
NOT_HERE = -6.0
CLASS_WILD = np.array([[0.3, 0.6, 0.0, NOT_HERE],
                       [0.4, -0.5, 0.2, -1.0],
                       [NOT_HERE, NOT_HERE, 0.0, 1.0],
                       [NOT_HERE, NOT_HERE, NOT_HERE, 1.5],
                       [0.3, NOT_HERE, 0.5, NOT_HERE],
                       [NOT_HERE, NOT_HERE, 0.2, 0.8],
                       [0.2, 0.8, 0.4, NOT_HERE]])
CLASS_SOIL = _FIXED.normal(0.0, 0.8, (CLASSES, N_SOIL))
CALIBRATION_ROWS = 1 << 17


def _scores(rows: int, rng):
    """(X [54, rows] float32, scores [rows, 7] without the offsets)."""
    X = np.zeros((N_FEATURES, rows), dtype=np.float32)
    wild = np.minimum(np.searchsorted(np.cumsum(WILD_P), rng.random(rows)),
                      N_WILD - 1)
    soil = np.minimum(np.searchsorted(np.cumsum(SOIL_P), rng.random(rows)),
                      N_SOIL - 1)
    at = np.arange(rows)
    X[len(QUANT) + wild, at] = 1.0
    X[len(QUANT) + N_WILD + soil, at] = 1.0
    elev = np.rint(np.clip(WILD_ELEV[wild] + SOIL_ELEV[soil]
                           + rng.normal(0.0, 180.0, rows), 1860., 3860.))
    aspect = rng.integers(0, 361, rows).astype(np.float64)
    slope = np.rint(np.clip(rng.gamma(3.0, 4.7, rows), 0.0, 66.0))
    hydro = np.rint(np.clip(rng.gamma(1.6, 170.0, rows), 0.0, 1397.0))
    vert = np.rint(np.clip(rng.normal(0.15 * hydro, 25.0 + 0.1 * hydro),
                           -173.0, 601.0))
    road = np.rint(np.clip(rng.gamma(2.0, 1150.0, rows), 0.0, 7117.0))
    fire = np.rint(np.clip(rng.gamma(2.0, 990.0, rows), 0.0, 7173.0))
    X[0], X[1], X[2], X[3], X[4], X[5], X[9] = \
        elev, aspect, slope, hydro, vert, road, fire
    # the hillshade index of a slope facing `aspect` under the summer
    # sun at 9 am, noon and 3 pm (its azimuth and zenith angle)
    sl, az = np.radians(slope), np.radians(aspect)
    for j, (sun_az, zen) in zip((6, 7, 8), ((135., 45.), (180., 25.),
                                            (225., 45.))):
        z = np.radians(zen)
        X[j] = np.rint(np.clip(255.0 * (
            np.cos(z) * np.cos(sl) + np.sin(z) * np.sin(sl)
            * np.cos(np.radians(sun_az) - az)), 0.0, 255.0))
    score = -0.5 * ((elev[:, None] - CLASS_ELEV) / CLASS_SPREAD) ** 2 \
        + CLASS_WILD[:, wild].T + CLASS_SOIL[:, soil].T
    score[:, 3] -= hydro / 150.0          # cottonwood and willow: by water
    score[:, 5] += (X[7] - 220.0) / 40.0  # Douglas-fir: the sunny slopes
    score += rng.gumbel(0.0, 1.0, (rows, CLASSES))
    return X, score


def offsets(seed: int) -> np.ndarray:
    """The classes' offsets that give `SHARES` on a sample drawn from
    the seed: a fixed point, found by moving each offset by the log of
    the share it misses by."""
    _, score = _scores(CALIBRATION_ROWS,
                       np.random.default_rng([int(seed), 0xCA11B]))
    off = np.log(SHARES)
    for _ in range(60):
        got = np.bincount(np.argmax(score + off, axis=1),
                          minlength=CLASSES) / len(score)
        off = off + 0.7 * np.log(SHARES / np.maximum(got, 1e-6))
    return off - off.mean()


def covtype_like(rows: int, seed: int):
    """(X [54, rows] float32, one contiguous row per column, the
    integers held as float32; y [rows] int8 class 0-6, label `k + 1`).
    The same (rows, seed) gives the same table."""
    off = offsets(seed)
    X = np.empty((N_FEATURES, rows), dtype=np.float32)
    y = np.empty(rows, dtype=np.int8)
    for c, lo in enumerate(range(0, rows, CHUNK_ROWS)):
        hi = min(lo + CHUNK_ROWS, rows)
        X[:, lo:hi], score = _scores(
            hi - lo, np.random.default_rng([int(seed), c]))
        y[lo:hi] = np.argmax(score + off, axis=1)
    return X, y


def as_columns(X: np.ndarray, y: np.ndarray) -> dict[str, np.ndarray]:
    """The table as a user hands it to `Frame.from_arrays`: 54 float32
    columns and the cover type as one-character strings "1".."7"."""
    cols = {name: X[j] for j, name in enumerate(NAMES)}
    cols["y"] = LABELS[y]
    return cols
