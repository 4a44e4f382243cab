"""TPC-H Q14 (promotion effect), TPC-H v3 section 2.4.14, in the spec's
own text: ``lineitem`` joined to ``part`` by part key, one month of ship
dates, the share of revenue that came from promotional parts.

Substitution parameter as the spec draws it: DATE is the first day of a
month of 1993..1997.

The oracle joins by plain fancy indexing on ``l_partkey - 1`` (PART's key
is dense from 1) and gives MySQL's text for the quotient: the product
``100.00 * SUM`` has scale 6, a division adds four digits and rounds half
away from zero."""

from __future__ import annotations

import datetime

import numpy as np

from harness import exact

NAME = "q14"
POOL = 4
ORDERED = True
READS = {"LINEITEM": ["l_partkey", "l_extendedprice", "l_discount",
                      "l_shipdate"],
         "PART": ["p_partkey", "p_type"]}

_FIRST = (1992, 1)                  # ship dates fall in 1992-01..1998-12
_MONTHS = 7 * 12


def _month_start(k: int) -> datetime.date:
    """First day of the ``k``-th month counted from January 1992."""
    y, m = divmod(_FIRST[1] - 1 + k, 12)
    return datetime.date(_FIRST[0] + y, m + 1, 1)


def draw(rng) -> dict:
    return {"year": int(rng.integers(1993, 1998)),
            "month": int(rng.integers(1, 13))}


def sql(p: dict) -> str:
    date = f"{p['year']}-{p['month']:02d}-01"
    return (
        "select 100.00 * sum(case when p_type like 'PROMO%' "
        "then l_extendedprice * (1 - l_discount) else 0 end) "
        "/ sum(l_extendedprice * (1 - l_discount)) as promo_revenue "
        "from lineitem, part where l_partkey = p_partkey "
        f"and l_shipdate >= date '{date}' "
        f"and l_shipdate < date '{date}' + interval '1' month")


def prepare(data: dict):
    """Exact sums of price * (1 - discount), at scale 4, per ship month:
    all parts, and promotional parts."""
    li, part = data["LINEITEM"], data["PART"]
    if not np.array_equal(part["p_partkey"],
                          np.arange(1, len(part["p_partkey"]) + 1)):
        raise ValueError("PART's key is not dense from 1")
    codes, types = part["p_type"]
    promo = np.array([t.startswith("PROMO") for t in types])[codes]
    starts = np.array([exact.days(_month_start(k))
                       for k in range(_MONTHS + 1)])
    ship = li["l_shipdate"]
    if len(ship) and not (starts[0] <= ship.min() and ship.max() < starts[-1]):
        raise ValueError("ship dates outside 1992..1998")
    sums = np.zeros((2, _MONTHS), np.int64)
    for s in exact.chunks(len(ship)):
        month = np.searchsorted(starts, ship[s], side="right") - 1
        revenue = li["l_extendedprice"][s] * (100 - li["l_discount"][s])
        flag = promo[li["l_partkey"][s] - 1]
        sums[0] += exact.group_sums(month, revenue, _MONTHS)
        sums[1] += exact.group_sums(month[flag], revenue[flag], _MONTHS)
    return sums


def answer(sums, p: dict) -> list[tuple]:
    k = (p["year"] - _FIRST[0]) * 12 + p["month"] - 1
    total, promo = int(sums[0, k]), int(sums[1, k])
    if total == 0:
        return [(None,)]            # x / 0 is NULL, and so is SUM of no row
    # 100.00 * promo / total at scale 6 + 4, half away from zero
    num = 100 * promo * 10 ** 10
    return [(exact.dec_text((2 * num + total) // (2 * total), 10),)]


def bytes_read(rows: dict, width: dict) -> int:
    """Probe columns at their narrow widths once, build columns once,
    nothing for the gather: the least a memory-bound probe could read."""
    return exact.scan_bytes(READS, rows, width)
