"""A plain reference for a grouped COUNT / SUM: sort the rows by key,
find where the key changes, `np.add.reduceat`.  int64 numpy and Python
ints only, no float accumulation, and nothing of `copr/`: what the TPU's
lowering of a high-NDV GROUP BY (`copr/runagg`) is compared with, and
the second computation the benchmark's `hndv_*` oracles are checked by.

Semantics are SQL's: rows are grouped by the tuple of their keys, every
NULL of a key being one value; COUNT(x) and SUM(x) skip a NULL x; the
SUM of no value is NULL (None here).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def _halves_sum(values: np.ndarray, starts: np.ndarray) -> list:
    """Exact per-run sums of int64 `values` as Python ints: the two
    32-bit halves summed apart (neither can wrap for < 2^31 rows)."""
    v = values.astype(np.int64)
    lo = np.add.reduceat(v & 0xFFFFFFFF, starts)
    hi = np.add.reduceat(v >> 32, starts)
    return [(int(h) << 32) + int(x) for h, x in zip(hi, lo)]


def group_by(keys: Sequence[Column], live: Optional[np.ndarray],
             aggs: Sequence[tuple]) -> dict:
    """{key tuple (None for NULL): [one value an aggregate]} over the
    rows `live` (None: all).  An aggregate is ("count", None) for
    COUNT(*), ("count", column) or ("sum", column); a column is
    (integer values, validity | None)."""
    n = len(keys[0][0])
    rows = np.arange(n) if live is None else np.nonzero(live)[0]
    if not len(rows):
        return {}
    lanes = []
    for v, valid in keys:
        v = np.asarray(v)[rows]
        ok = np.ones(len(rows), bool) if valid is None \
            else np.asarray(valid, bool)[rows]
        lanes += [np.where(ok, v, np.zeros((), v.dtype)), ~ok]
    order = np.lexsort(lanes[::-1])
    lanes = [x[order] for x in lanes]
    change = np.zeros(len(rows), bool)
    change[0] = True
    for x in lanes:
        change[1:] |= x[1:] != x[:-1]
    starts = np.nonzero(change)[0]
    out_keys = list(zip(*[
        [None if null else v.item() for v, null
         in zip(lanes[2 * j][starts], lanes[2 * j + 1][starts])]
        for j in range(len(keys))]))
    cols = []
    for func, col in aggs:
        if col is None:
            cols.append([int(c) for c in np.add.reduceat(
                np.ones(len(rows), np.int64), starts)])
            continue
        v = np.asarray(col[0])[rows][order]
        ok = np.ones(len(rows), bool) if col[1] is None \
            else np.asarray(col[1], bool)[rows][order]
        cnt = np.add.reduceat(ok.astype(np.int64), starts)
        if func == "count":
            cols.append([int(c) for c in cnt])
        else:
            sums = _halves_sum(np.where(ok, v, 0), starts)
            cols.append([s if c else None for s, c in zip(sums, cnt)])
    return {k: [c[g] for c in cols] for g, k in enumerate(out_keys)}


__all__ = ["group_by"]
