"""TPC-H ``CUSTOMER`` as Q3 reads it: key and market segment, as plain
numpy arrays made from the seed.  The spec's 4.2.3: 150,000 x SF
customers, ``c_custkey`` dense from 1, ``c_mktsegment`` one of five,
uniform.  The dictionary is sorted."""

from __future__ import annotations

import numpy as np

NAME = "CUSTOMER"
LOAD = "bulk"
ROWS_PER_SF = 150_000
TYPES = {"c_custkey": "bigint", "c_mktsegment": "dict"}
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]


def rows(scale: float) -> int:
    return max(int(ROWS_PER_SF * scale), 2)    # as ORDERS draws o_custkey


def generate(scale: float, seed: int, columns: list[str]) -> dict:
    unknown = set(columns) - set(TYPES)
    if unknown:
        raise ValueError(f"CUSTOMER has no generator for {sorted(unknown)}")
    n = rows(scale)
    out = {"c_custkey": np.arange(1, n + 1),
           "c_mktsegment": (np.random.default_rng([seed, 40]).integers(
               0, len(SEGMENTS), n).astype(np.int32), SEGMENTS)}
    return {c: out[c] for c in columns}
