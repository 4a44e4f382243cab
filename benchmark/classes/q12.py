"""TPC-H Q12 (shipping modes and order priority), TPC-H v3 section
2.4.12, in the spec's own text: of the lines of two ship modes received
in one year, late against their commit date though shipped before it, how
many belong to orders of high and of other priority.  ``lineitem``
filtered by two column-to-column comparisons, an ``IN`` and a year (one
row in two hundred), looked up in all of ``orders`` by the order key,
grouped by ship mode.

Substitution parameters as the spec draws them: SHIPMODE1 and SHIPMODE2
two different modes of the seven, DATE the first of January of
1993..1997.

The oracle filters with numpy, joins by fancy indexing on a key -> row
map and counts with ``bincount``.

Loads only against a program that supports the deployment, as
``q3.py`` says."""

from __future__ import annotations

import datetime

import numpy as np

from harness import exact
from tidb_tpu.copr import facts as _facts

if "join_direct_launches" not in _facts.counter_names():
    raise SystemExit(
        "benchmark: this program does not support the deployment "
        "tpch_sf1_orders_x1: it keeps no join_direct_launches counter")

NAME = "q12"
POOL = 4
ORDERED = True
# join launches a statement of this class takes (`orderjoin_device_share`):
# `lineitem` looked up in all of `orders`, whose build is kept with the
# table's snapshot
JOIN_LAUNCHES = 1
READS = {"ORDERS": ["o_orderkey", "o_orderpriority"],
         "LineItem": ["l_orderkey", "l_shipmode", "l_commitdate",
                      "l_receiptdate", "l_shipdate"]}
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
HIGH = ("1-URGENT", "2-HIGH")
YEARS = range(1993, 1998)


def draw(rng) -> dict:
    a, b = rng.choice(len(SHIPMODES), 2, replace=False)
    return {"mode1": SHIPMODES[int(a)], "mode2": SHIPMODES[int(b)],
            "year": int(rng.integers(YEARS.start, YEARS.stop))}


def sql(p: dict) -> str:
    date = f"{p['year']}-01-01"
    return (
        "select l_shipmode, "
        "sum(case when o_orderpriority ='1-URGENT' "
        "or o_orderpriority ='2-HIGH' then 1 else 0 end) as high_line_count, "
        "sum(case when o_orderpriority <> '1-URGENT' "
        "and o_orderpriority <> '2-HIGH' then 1 else 0 end) "
        "as low_line_count "
        "from orders, lineitem where o_orderkey = l_orderkey "
        f"and l_shipmode in ('{p['mode1']}', '{p['mode2']}') "
        "and l_commitdate < l_receiptdate and l_shipdate < l_commitdate "
        f"and l_receiptdate >= date '{date}' "
        f"and l_receiptdate < date '{date}' + interval '1' year "
        "group by l_shipmode order by l_shipmode")


def prepare(data: dict):
    """``counts[year, mode, high?]``: the late lines received in each
    year a parameter can name, by ship mode and by whether their order's
    priority is high."""
    orders, li = data["ORDERS"], data["LineItem"]
    okey = orders["o_orderkey"]
    if len(np.unique(okey)) != len(okey):
        raise ValueError("ORDERS' key is not unique")
    codes, names = orders["o_orderpriority"]
    high_of = np.zeros(int(okey.max()) + 2, np.int8) - 1   # -1: no order
    high_of[okey] = np.array([s in HIGH for s in names])[codes]
    mcodes, modes = li["l_shipmode"]
    starts = np.array([exact.days(datetime.date(y, 1, 1))
                       for y in range(YEARS.start, YEARS.stop + 1)])
    counts = np.zeros((len(YEARS), len(modes), 2), np.int64)
    for s in exact.chunks(len(mcodes)):
        commit, receipt = li["l_commitdate"][s], li["l_receiptdate"][s]
        year = np.searchsorted(starts, receipt, side="right") - 1
        high = high_of[np.minimum(li["l_orderkey"][s], len(high_of) - 1)]
        m = (commit < receipt) & (li["l_shipdate"][s] < commit) \
            & (year >= 0) & (year < len(YEARS)) & (high >= 0)
        flat = (year[m] * len(modes) + mcodes[s][m]) * 2 + high[m]
        counts += np.bincount(flat, minlength=counts.size).reshape(
            counts.shape)
    return {"counts": counts, "modes": list(modes)}


def answer(state, p: dict) -> list[tuple]:
    out = []
    for mode in sorted({p["mode1"], p["mode2"]}):
        low, high = state["counts"][p["year"] - YEARS.start,
                                    state["modes"].index(mode)]
        if low + high:              # a group no row is in is no group
            out.append((mode, str(int(high)), str(int(low))))
    return out


def bytes_read(rows: dict, width: dict) -> int:
    """Every column the statement reads, of both tables, once, at its
    narrow width; nothing for the lookup."""
    return exact.scan_bytes(READS, rows, width)
