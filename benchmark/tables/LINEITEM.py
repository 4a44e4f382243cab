"""TPC-H ``LINEITEM`` as the part-lineitem queries (Q14, Q19) read it, as
plain numpy arrays made from the seed.  The spec's schema (1.4.1) writes
the table's name in capitals and its queries write ``lineitem``; the
program compares table names without case, as TiDB does.

A second generator beside ``lineitem.py``, which may not change: this one
has ``l_partkey``, ``l_shipmode`` and ``l_shipinstruct`` and leaves out
what Q14 and Q19 do not read.  The arithmetic is that of
``tidb_tpu/testing/tpch.py gen_lineitem`` (the spec's 4.2.3: part key
uniform in 1..200,000 x SF, quantity 1..50, discount 0.00..0.10, ship
date = order date + 1..121 days, seven ship modes, four instructions,
extended price = quantity x the part's retail price), and a column that
both files make has the same values in both: each block of columns draws
from its own stream ``[seed, tag]``.
"""

from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor

import numpy as np

NAME = "LINEITEM"
LOAD = "bulk"
ROWS_PER_SF = 6_000_000
PARTS_PER_SF = 200_000
TYPES = {
    "l_partkey": "bigint", "l_quantity": "decimal(15,2)",
    "l_extendedprice": "decimal(15,2)", "l_discount": "decimal(15,2)",
    "l_shipdate": "date", "l_shipmode": "dict", "l_shipinstruct": "dict",
}
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
SHIPINSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE",
                "TAKE BACK RETURN"]

_EPOCH = datetime.date(1970, 1, 1)
STARTDATE = (datetime.date(1992, 1, 1) - _EPOCH).days


def rows(scale: float) -> int:
    return int(ROWS_PER_SF * scale)


def _partkey_quantity_price(n, scale, seed):
    parts = max(int(PARTS_PER_SF * scale), 1)
    partkey = np.random.default_rng([seed, 2]).integers(
        1, parts + 1, n, dtype=np.int32)
    qty = np.random.default_rng([seed, 5]).integers(1, 51, n)
    # extendedprice = quantity * p_retailprice(partkey), in cents
    key = np.arange(parts + 1)
    price = (90000 + (key % 20001) + 100 * (key % 1000))[partkey]
    price *= qty
    qty *= 100
    return {"l_partkey": partkey.astype(np.int64), "l_quantity": qty,
            "l_extendedprice": price}


def _discount(n, scale, seed):
    return {"l_discount": np.random.default_rng([seed, 6]).integers(0, 11, n)}


def _shipdate(n, scale, seed):
    # order date + 1..121 days over the spec's range of order dates
    return {"l_shipdate": np.random.default_rng([seed, 8]).integers(
        STARTDATE + 1, STARTDATE + 122 + 2406, n)}


def _shipinstruct(n, scale, seed):
    codes = np.random.default_rng([seed, 9]).integers(
        0, len(SHIPINSTRUCT), n).astype(np.int32)
    return {"l_shipinstruct": (codes, SHIPINSTRUCT)}


def _shipmode(n, scale, seed):
    codes = np.random.default_rng([seed, 10]).integers(
        0, len(SHIPMODES), n).astype(np.int32)
    return {"l_shipmode": (codes, SHIPMODES)}


_BLOCKS = [
    ({"l_partkey", "l_quantity", "l_extendedprice"}, _partkey_quantity_price),
    ({"l_discount"}, _discount),
    ({"l_shipdate"}, _shipdate),
    ({"l_shipinstruct"}, _shipinstruct),
    ({"l_shipmode"}, _shipmode),
]


def generate(scale: float, seed: int, columns: list[str]) -> dict:
    """``{column: int64 array | (int32 codes, dictionary)}`` for the
    columns asked for; decimals are raw integers at scale 2, dates are
    days since 1970-01-01."""
    unknown = set(columns) - set(TYPES)
    if unknown:
        raise ValueError(f"LINEITEM has no generator for {sorted(unknown)}")
    n = rows(scale)
    todo = [fn for makes, fn in _BLOCKS if makes & set(columns)]
    out = {}
    with ThreadPoolExecutor(max_workers=len(todo)) as pool:
        for made in pool.map(lambda fn: fn(n, scale, seed), todo):
            out.update(made)
    return {c: out[c] for c in columns}
