"""Fixed-width string column → enum codes through a table, not a sort.

`frame._factorize` finds a string column's levels with `np.unique`,
which sorts every row: 0.5 s for 4.2 M one-character strings to find
two levels. A fixed-width numpy string is `k` words a row (`<Uk`:
uint32 code points; `Sk`: bytes), NUL-padded, and numpy orders such
strings word by word — so a flag table over the words seen at each
position gives every word its rank, the ranks of a row's positions
combine into one mixed-radix key whose order IS numpy's string order,
and a second flag table over the keys seen gives every row its code.
Two or three passes over the rows, no sort, no copy of the strings, and
the same codes and domain as the sort gives, bitwise.
"""

from __future__ import annotations

import numpy as np

from .frame import NA_ENUM

# most entries a key table may have (1 MB of flags, 4 MB of codes):
# past it — wide strings of many distinct characters — the sort serves
_TABLE_CAP = 1 << 20
# rows a pass takes at a time: numpy indexes through intp, and a block's
# cast stays in cache where a column's would be a fresh 8 bytes a row
_BLOCK = 1 << 16
# the largest word the sort path can name: a Unicode code point, and for
# bytes ASCII (`astype(str)` refuses the rest)
_WORD_LIMIT = {"U": (np.uint32, 0x10FFFF), "S": (np.uint8, 0x7F)}


def _blocks(words: np.ndarray):
    """(rows, their words as indices: `[k, rows]`, a position's
    together) for every block of `words`' rows."""
    for lo in range(0, len(words), _BLOCK):
        rows = slice(lo, lo + _BLOCK)
        yield rows, np.ascontiguousarray(words[rows].T, dtype=np.intp)


def _keys(words: np.ndarray, tables: list[np.ndarray]) -> np.ndarray:
    """Every row's sum over its positions of `tables[j][word j]`, int32.
    (mode="clip": no bounds check; a word is under its table's end.)"""
    keys = np.empty(len(words), dtype=np.int32)
    for rows, idx in _blocks(words):
        np.take(tables[0], idx[0], out=keys[rows], mode="clip")
        for j in range(1, len(tables)):
            keys[rows] += np.take(tables[j], idx[j], mode="clip")
    return keys


def factorize_table(arr: np.ndarray) -> tuple[np.ndarray, list[str]] | None:
    """`frame._factorize` of a `U` or `S` array with no given domain:
    (int32 codes, sorted vocab), `""` → NA_ENUM — or None where the
    table does not serve (not native fixed-width strings, a word out of
    range, key space past `_TABLE_CAP`) and the sort has to."""
    word, limit = _WORD_LIMIT.get(arr.dtype.kind, (None, 0))
    if word is None or arr.ndim != 1 or not arr.dtype.isnative \
            or arr.dtype.itemsize == 0:
        return None
    n, k = len(arr), arr.dtype.itemsize // np.dtype(word).itemsize
    if n == 0:
        return np.empty(0, dtype=np.int32), []
    words = np.ascontiguousarray(arr).view(word).reshape(n, k)
    tops = words.max(axis=0)
    if int(tops.max()) > limit:
        return None
    # the words seen at each position. NUL ("the string ended here") is
    # always among them: it sorts first, as it does for numpy, so rank 0
    # is NUL and key 0 the empty string alone
    seen = [np.zeros(int(top) + 1, dtype=bool) for top in tops]
    for _, idx in _blocks(words):
        for j in range(k):
            seen[j][idx[j]] = True
    # per position: its words in order, and rank x the position's
    # weight (the product of the sizes of the positions after it)
    points, tables, total = [None] * k, [None] * k, 1
    for j in reversed(range(k)):
        seen[j][0] = True
        points[j] = np.flatnonzero(seen[j])
        tables[j] = ((np.cumsum(seen[j]) - 1) * total).astype(np.int32)
        total *= len(points[j])
        if total > _TABLE_CAP:
            return None
    if k == 1:     # every key was seen: the rank, shifted past "", is the code
        levels = np.arange(1, total)
        code_of = tables[0] - 1
        code_of[0] = NA_ENUM
        codes = _keys(words, [code_of])
    else:
        keys = _keys(words, tables)
        used = np.zeros(total, dtype=bool)
        used[keys] = True
        used[0] = False            # the empty string is NA, not a level
        levels = np.flatnonzero(used)
        code_of = np.full(total, NA_ENUM, dtype=np.int32)
        code_of[levels] = np.arange(len(levels), dtype=np.int32)
        codes = np.take(code_of, keys, mode="clip")
    # the levels' strings back from their keys, digit by digit
    text = np.empty((len(levels), k), dtype=word)
    for j in reversed(range(k)):
        levels, digit = np.divmod(levels, len(points[j]))
        text[:, j] = points[j][digit]
    domain = text.view(arr.dtype).reshape(len(text))
    if arr.dtype.kind == "S":
        domain = domain.astype(str)
    return codes, [str(d) for d in domain]
