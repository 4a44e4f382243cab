"""TPC-H ``lineitem``: the columns the benchmark's classes read, as plain
numpy arrays made from the seed.

The distributions are those of ``tidb_tpu/testing/tpch.py gen_lineitem``
(the spec's for quantity, discount, tax, ship date, return flag and line
status; ``l_orderkey`` random in the spec's key range and sorted, with no
``orders`` table behind it), drawn with fewer and narrower temporaries:
on these machines first-touching a fresh 480 MB array costs more than
filling it.  It lives here so that no later PR can change the data a cell
runs on.  Each block of columns draws from its
own stream ``[seed, tag]``, so the blocks can be made side by side and a
column's values do not depend on which other columns were asked for.
"""

from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor

import numpy as np

NAME = "lineitem"
LOAD = "bulk"
ROWS_PER_SF = 6_000_000
# logical type of each column the harness may be asked for
TYPES = {
    "l_orderkey": "bigint", "l_quantity": "decimal(15,2)",
    "l_extendedprice": "decimal(15,2)", "l_discount": "decimal(15,2)",
    "l_tax": "decimal(15,2)", "l_returnflag": "dict",
    "l_linestatus": "dict", "l_shipdate": "date",
}
RETURNFLAGS = ["A", "N", "R"]
LINESTATUSES = ["F", "O"]

_EPOCH = datetime.date(1970, 1, 1)
STARTDATE = (datetime.date(1992, 1, 1) - _EPOCH).days
CURRENTDATE = (datetime.date(1995, 6, 17) - _EPOCH).days


def rows(scale: float) -> int:
    return int(ROWS_PER_SF * scale)


def _orderkey(n, scale, seed):
    keys = np.random.default_rng([seed, 1]).integers(
        1, max(int(1_500_000 * scale), 1) * 4 + 1, n)
    keys.sort()
    return {"l_orderkey": keys}


def _quantity_price(n, scale, seed):
    parts = max(int(200_000 * scale), 1)
    partkey = np.random.default_rng([seed, 2]).integers(
        1, parts + 1, n, dtype=np.int32)
    qty = np.random.default_rng([seed, 5]).integers(1, 51, n)
    # extendedprice = quantity * p_retailprice(partkey), in cents; the
    # retail price is looked up per part and not computed per row
    key = np.arange(parts + 1)
    price = (90000 + (key % 20001) + 100 * (key % 1000))[partkey]
    price *= qty
    qty *= 100
    return {"l_quantity": qty, "l_extendedprice": price}


def _discount(n, scale, seed):
    return {"l_discount": np.random.default_rng([seed, 6]).integers(0, 11, n)}


def _tax(n, scale, seed):
    return {"l_tax": np.random.default_rng([seed, 7]).integers(0, 9, n)}


def _ship(n, scale, seed):
    rng = np.random.default_rng([seed, 8])
    # order date + 1..121 days over the spec's range of order dates
    ship = rng.integers(STARTDATE + 1, STARTDATE + 122 + 2406, n)
    lag = rng.integers(1, 31, n, dtype=np.int8)         # receipt - ship
    # R or A (50/50) where the item was received by CURRENTDATE, else N
    returned = ship <= np.int16(CURRENTDATE) - lag
    r_not_a = rng.integers(0, 2, n, dtype=np.int8)
    flag = np.where(returned, r_not_a << 1, np.int8(1)).astype(np.int32)
    status = (ship > CURRENTDATE).astype(np.int32)
    return {"l_shipdate": ship, "l_returnflag": (flag, RETURNFLAGS),
            "l_linestatus": (status, LINESTATUSES)}


_BLOCKS = [
    ({"l_orderkey"}, _orderkey),
    ({"l_quantity", "l_extendedprice"}, _quantity_price),
    ({"l_discount"}, _discount),
    ({"l_tax"}, _tax),
    ({"l_shipdate", "l_returnflag", "l_linestatus"}, _ship),
]


def generate(scale: float, seed: int, columns: list[str]) -> dict:
    """``{column: int64 array | (int32 codes, dictionary)}`` for the
    columns asked for; decimals are raw integers at scale 2, dates are
    days since 1970-01-01."""
    unknown = set(columns) - set(TYPES)
    if unknown:
        raise ValueError(f"lineitem has no generator for {sorted(unknown)}")
    n = rows(scale)
    todo = [fn for makes, fn in _BLOCKS if makes & set(columns)]
    out = {}
    with ThreadPoolExecutor(max_workers=len(todo)) as pool:
        for made in pool.map(lambda fn: fn(n, scale, seed), todo):
            out.update(made)
    return {c: out[c] for c in columns}
