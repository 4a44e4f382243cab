"""Exact arithmetic the oracles share: integer group sums over numpy
columns, and the text the wire gives a DECIMAL.  No JAX, nothing from the
program."""

from __future__ import annotations

import datetime

import numpy as np

# Rows an oracle handles at a time.  Temporaries of this size come back
# from the allocator's free lists; whole-column temporaries at SF10 are
# fresh 480 MB mappings whose first touch costs more than the arithmetic.
CHUNK_ROWS = 1 << 21

_EPOCH = datetime.date(1970, 1, 1)


def days(date: datetime.date) -> int:
    return (date - _EPOCH).days


def chunks(n: int):
    for lo in range(0, n, CHUNK_ROWS):
        yield slice(lo, min(lo + CHUNK_ROWS, n))


def group_sums(keys: np.ndarray, values: np.ndarray, n: int) -> np.ndarray:
    """Exact int64 SUM(values) per key in [0, n), for values in [0, 2^48).
    ``bincount`` accumulates in float64, so the values go through in 24-bit
    halves: a half's group sum stays below 2^53 for up to 2^29 rows."""
    if len(keys) >= 1 << 29:
        raise ValueError("group_sums is exact for fewer than 2^29 rows a call")
    lo = np.bincount(keys, weights=(values & 0xFFFFFF), minlength=n)
    hi = np.bincount(keys, weights=(values >> 24), minlength=n)
    return (hi.astype(np.int64) << 24) + lo.astype(np.int64)


def dec_text(raw: int, scale: int) -> str:
    """The wire's text for the DECIMAL ``raw / 10**scale``."""
    raw = int(raw)
    sign, raw = ("-" if raw < 0 else ""), abs(raw)
    if scale == 0:
        return f"{sign}{raw}"
    whole, frac = divmod(raw, 10 ** scale)
    return f"{sign}{whole}.{frac:0{scale}d}"


def avg_text(total_raw: int, count: int, scale: int) -> str:
    """AVG of a DECIMAL column at ``scale``: MySQL adds four digits and
    rounds half away from zero.  ``total_raw`` is not negative here."""
    if total_raw < 0:
        raise ValueError("avg_text rounds non-negative sums only")
    num, den = int(total_raw) * 10 ** 4, int(count)
    return dec_text((2 * num + den) // (2 * den), scale + 4)


def scan_bytes(reads: dict, rows: dict, width: dict) -> int:
    """Bytes a statement has to read: each column of ``reads`` (``{table:
    [column, ...]}``) once, over all rows, at ``width[table][column]``
    bytes, the narrowest integer that holds the column's values."""
    return sum(rows[t] * width[t][c] for t, cols in reads.items()
               for c in cols)
