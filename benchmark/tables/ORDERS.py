"""TPC-H ``ORDERS`` as the orders-lineitem queries (Q3, Q12) read it: key,
customer, date and the two priorities, as plain numpy arrays made from the
seed.  The spec's 4.2.3: 1,500,000 x SF orders; ``o_orderkey`` sparse, the
first 8 keys of every 32 (so a range four times the count);
``o_custkey`` uniform over the customers whose key is no multiple of 3
(a third of the customers have no order); ``o_orderdate`` uniform in
STARTDATE .. ENDDATE - 151 days (1992-01-01 .. 1998-08-02);
``o_orderpriority`` one of five; ``o_shippriority`` 0.  Stored by key.

``tables/LineItem.py`` makes its rows from the same orders: ``orderkeys``
and ``orderdates`` are the streams both read."""

from __future__ import annotations

import datetime

import numpy as np

NAME = "ORDERS"
LOAD = "bulk"
ROWS_PER_SF = 1_500_000
CUSTOMERS_PER_SF = 150_000
TYPES = {"o_orderkey": "bigint", "o_custkey": "bigint",
         "o_orderdate": "date", "o_orderpriority": "dict",
         "o_shippriority": "bigint"}
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]

_EPOCH = datetime.date(1970, 1, 1)
STARTDATE = (datetime.date(1992, 1, 1) - _EPOCH).days
ORDER_DAYS = 2406               # 1992-01-01 .. 1998-08-02, both ends


def rows(scale: float) -> int:
    return int(ROWS_PER_SF * scale)


def orderkeys(scale: float) -> np.ndarray:
    """The first 8 keys of every 32, ascending from 1."""
    i = np.arange(rows(scale), dtype=np.int64)
    return (i >> 3 << 5) + (i & 7) + 1


def orderdates(scale: float, seed: int) -> np.ndarray:
    return np.random.default_rng([seed, 31]).integers(
        STARTDATE, STARTDATE + ORDER_DAYS, rows(scale))


def custkeys(scale: float, seed: int) -> np.ndarray:
    """Uniform over 1 .. 150,000 x SF less the multiples of 3."""
    customers = max(int(CUSTOMERS_PER_SF * scale), 2)
    j = np.random.default_rng([seed, 32]).integers(
        0, customers - customers // 3, rows(scale))
    return j + j // 2 + 1       # 1, 2, 4, 5, 7, 8, ...


def generate(scale: float, seed: int, columns: list[str]) -> dict:
    """``{column: int64 array | (int32 codes, dictionary)}``; dates are
    days since 1970-01-01."""
    unknown = set(columns) - set(TYPES)
    if unknown:
        raise ValueError(f"ORDERS has no generator for {sorted(unknown)}")
    n = rows(scale)
    make = {
        "o_orderkey": lambda: orderkeys(scale),
        "o_custkey": lambda: custkeys(scale, seed),
        "o_orderdate": lambda: orderdates(scale, seed),
        "o_orderpriority": lambda: (np.random.default_rng(
            [seed, 33]).integers(0, len(PRIORITIES), n).astype(np.int32),
            PRIORITIES),
        "o_shippriority": lambda: np.zeros(n, np.int64),
    }
    return {c: make[c]() for c in columns}
