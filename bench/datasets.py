"""The benchmark's tables, made from ``--seed`` on the host.

`higgs_like` is a copy of `chip_smoke.py::higgs_like` (the yardstick may
not move with the program), made in fixed row chunks so that a 16.8 M-row
table never needs a second full host copy and so that a chunk's rows
depend on (seed, chunk index) alone."""

from __future__ import annotations

import numpy as np

N_FEATURES = 28            # HIGGS: 21 low-level + 7 derived features
CHUNK_ROWS = 1 << 20


def _higgs_chunk(rows: int, seed: int, chunk: int):
    """[28, rows] float32 features (the last 7 derived from the first
    21, as the real set's invariant masses are) and a 0/1 response that
    depends on them non-linearly."""
    rng = np.random.default_rng([int(seed), chunk])
    X = rng.standard_normal((N_FEATURES, rows), dtype=np.float32)
    for j in range(7):
        X[21 + j] = np.sqrt(X[3 * j] ** 2 + X[3 * j + 1] ** 2
                            + 0.5 * X[3 * j + 2] ** 2)
    logit = (X[0] - 0.8 * X[1] + 0.6 * X[2] * X[3]
             + 0.9 * (np.abs(X[4]) - 0.8) + 0.7 * (X[21] - 1.2)
             - 0.5 * (X[24] - 1.2) * X[5])
    y = logit + 0.7 * rng.logistic(size=rows).astype(np.float32) > 0
    return X, y


def higgs_like(rows: int, seed: int):
    """(X [28, rows] float32, one contiguous row per feature; y [rows]
    bool). The same (rows, seed) gives the same table."""
    X = np.empty((N_FEATURES, rows), dtype=np.float32)
    y = np.empty(rows, dtype=bool)
    for c, lo in enumerate(range(0, rows, CHUNK_ROWS)):
        hi = min(lo + CHUNK_ROWS, rows)
        X[:, lo:hi], y[lo:hi] = _higgs_chunk(hi - lo, seed, c)
    return X, y


def as_columns(X: np.ndarray, y: np.ndarray) -> dict[str, np.ndarray]:
    """The table as a user hands it to `Frame.from_arrays`: 28 float32
    columns f0..f27 and the response as signal / background labels."""
    cols = {f"f{j}": X[j] for j in range(X.shape[0])}
    cols["y"] = np.where(y, "s", "b")
    return cols


TABLES = {"higgs_like": higgs_like}
