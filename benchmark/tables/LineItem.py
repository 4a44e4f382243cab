"""TPC-H ``LINEITEM`` as the orders-lineitem queries (Q3, Q12) read it, as
plain numpy arrays made from the seed: every row belongs to an order of
``tables/ORDERS.py``.

A third generator beside ``lineitem.py`` and ``LINEITEM.py``, which may
not change and whose rows belong to no order (their ``l_orderkey`` is
random or absent, and they have no ``l_commitdate`` / ``l_receiptdate``).
``run.py`` finds a table's generator by the key of a class's ``READS``
and that key is the catalog name; table names compare without case, so
the third spelling names the same table ``lineitem`` the queries read.

The spec's 4.2.3: each order has 1..7 lines, uniform (about 4 x 1,500,000
x SF rows: 6,000,000 at SF1, as the spec's table of cardinalities says
"about"); ``l_orderkey`` its order's key; ``l_shipdate`` = order date +
1..121 days, ``l_commitdate`` = order date + 30..90, ``l_receiptdate`` =
ship date + 1..30; seven ship modes; quantity 1..50, part key uniform,
extended price = quantity x the part's retail price and discount
0.00..0.10 as ``LINEITEM.py`` makes them.  Stored by ``l_orderkey``.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

NAME = "LineItem"
LOAD = "bulk"
PARTS_PER_SF = 200_000
TYPES = {
    "l_orderkey": "bigint", "l_extendedprice": "decimal(15,2)",
    "l_discount": "decimal(15,2)", "l_shipdate": "date",
    "l_commitdate": "date", "l_receiptdate": "date", "l_shipmode": "dict",
}
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]


def _orders():
    """``tables/ORDERS.py``, found beside this file as the harness finds
    it (no package: ``tables`` is also the name of a library)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ORDERS.py")
    spec = importlib.util.spec_from_file_location("bench_tables_ORDERS_li",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def lines(scale: float, seed: int) -> np.ndarray:
    """Lines of each order, 1..7."""
    return np.random.default_rng([seed, 50]).integers(
        1, 8, _orders().rows(scale))


def rows(scale: float, seed: int) -> int:
    return int(lines(scale, seed).sum())


def generate(scale: float, seed: int, columns: list[str]) -> dict:
    """``{column: int64 array | (int32 codes, dictionary)}``; decimals
    are raw integers at scale 2, dates are days since 1970-01-01."""
    unknown = set(columns) - set(TYPES)
    if unknown:
        raise ValueError(f"LineItem has no generator for {sorted(unknown)}")
    orders = _orders()
    per_order = lines(scale, seed)
    n = int(per_order.sum())
    want = set(columns)
    out = {}
    if "l_orderkey" in want:
        out["l_orderkey"] = np.repeat(orders.orderkeys(scale), per_order)
    if want & {"l_shipdate", "l_commitdate", "l_receiptdate"}:
        odate = np.repeat(orders.orderdates(scale, seed), per_order)
        ship = odate + np.random.default_rng([seed, 51]).integers(1, 122, n)
        out["l_shipdate"] = ship
        out["l_commitdate"] = odate + np.random.default_rng(
            [seed, 52]).integers(30, 91, n)
        out["l_receiptdate"] = ship + np.random.default_rng(
            [seed, 53]).integers(1, 31, n)
    if "l_extendedprice" in want:
        parts = max(int(PARTS_PER_SF * scale), 1)
        partkey = np.random.default_rng([seed, 54]).integers(
            1, parts + 1, n, dtype=np.int32)
        key = np.arange(parts + 1)
        price = (90000 + (key % 20001) + 100 * (key % 1000))[partkey]
        price *= np.random.default_rng([seed, 55]).integers(1, 51, n)
        out["l_extendedprice"] = price
    if "l_discount" in want:
        out["l_discount"] = np.random.default_rng([seed, 56]).integers(
            0, 11, n)
    if "l_shipmode" in want:
        out["l_shipmode"] = (np.random.default_rng([seed, 57]).integers(
            0, len(SHIPMODES), n).astype(np.int32), SHIPMODES)
    return {c: out[c] for c in columns}
