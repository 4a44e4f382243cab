"""A dimension-table aggregate: brands of the parts above a size, after
TPC-H Q16's grouping of ``part`` and without its ``partsupp`` join (an
assumption of this benchmark, listed in the configuration).  SIZE is
1..49.  No ORDER BY, so the rows compare as a set."""

from __future__ import annotations

import numpy as np

from harness import exact

NAME = "part_agg"
POOL = 4
ORDERED = False
READS = {"part": ["p_brand", "p_size"]}
_MAX_SIZE = 50


def draw(rng) -> dict:
    return {"size": int(rng.integers(1, 50))}


def sql(p: dict) -> str:
    return (f"select p_brand, count(*) from part where p_size > {p['size']} "
            "group by p_brand")


def prepare(data: dict):
    codes, brands = data["part"]["p_brand"]
    size = data["part"]["p_size"]
    nb = len(brands) * (_MAX_SIZE + 1)
    table = np.bincount(codes.astype(np.int64) * (_MAX_SIZE + 1) + size,
                        minlength=nb)
    return brands, table.reshape(len(brands), _MAX_SIZE + 1)


def answer(state, p: dict) -> list[tuple]:
    brands, table = state
    count = table[:, p["size"] + 1:].sum(axis=1)
    return [(b, str(int(n))) for b, n in zip(brands, count) if n]


def bytes_read(rows: dict, width: dict) -> int:
    return exact.scan_bytes(READS, rows, width)
