"""TPC-H Q6 (forecasting revenue change), TPC-H v3 section 2.4.6, in the
literal form of the program's plan corpus (``TPCH_PLAN_QUERIES[0]``).

Substitution parameters as the spec draws them: DATE is 1 January of
1993..1997, DISCOUNT 0.02..0.09, QUANTITY 24 or 25.
"""

from __future__ import annotations

import datetime

import numpy as np

from harness import exact

NAME = "q6"
POOL = 4
ORDERED = True
READS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                      "l_extendedprice"]}

_YEARS = list(range(1992, 2000))           # ship dates fall in 1992..1998
_N_DISC, _N_QTY = 11, 50


def draw(rng) -> dict:
    return {"year": int(rng.integers(1993, 1998)),
            "discount": int(rng.integers(2, 10)),
            "quantity": int(rng.integers(24, 26))}


def sql(p: dict) -> str:
    d = p["discount"]
    return (
        "select sum(l_extendedprice * l_discount) as revenue from lineitem "
        f"where l_shipdate >= date '{p['year']}-01-01' "
        f"and l_shipdate < date '{p['year'] + 1}-01-01' "
        f"and l_discount between 0.{d - 1:02d} and 0.{d + 1:02d} "
        f"and l_quantity < {p['quantity']}")


def prepare(data: dict):
    """One pass: exact sum of price*discount per (ship year, discount,
    quantity).  Every member of a pool is answered from this table."""
    li = data["lineitem"]
    ship, disc = li["l_shipdate"], li["l_discount"]
    qty, price = li["l_quantity"], li["l_extendedprice"]
    starts = np.array([exact.days(datetime.date(y, 1, 1)) for y in _YEARS])
    if len(ship) and not (starts[0] <= ship.min() and ship.max() < starts[-1]):
        raise ValueError("ship dates outside 1992..1998")
    nb = (len(_YEARS) - 1) * _N_DISC * _N_QTY
    table = np.zeros(nb, np.int64)
    for s in exact.chunks(len(ship)):
        year = np.searchsorted(starts, ship[s], side="right") - 1
        key = (year * _N_DISC + disc[s]) * _N_QTY + (qty[s] // 100 - 1)
        table += exact.group_sums(key, price[s] * disc[s], nb)
    return table.reshape(len(_YEARS) - 1, _N_DISC, _N_QTY)


def answer(table, p: dict) -> list[tuple]:
    d = p["discount"]
    total = table[p["year"] - _YEARS[0], d - 1:d + 2,
                  :p["quantity"] - 1].sum()
    # SUM over no rows is NULL; with these parameters that does not occur
    return [(exact.dec_text(total, 4),)]


def bytes_read(rows: dict, width: dict) -> int:
    return exact.scan_bytes(READS, rows, width)
