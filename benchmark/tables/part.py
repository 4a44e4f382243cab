"""TPC-H ``part``: key, brand and size, as plain numpy arrays from the seed
(a copy of the arithmetic of ``tidb_tpu/testing/tpch.py gen_part``)."""

from __future__ import annotations

import numpy as np

NAME = "part"
LOAD = "bulk"
ROWS_PER_SF = 200_000
TYPES = {"p_partkey": "bigint", "p_brand": "dict", "p_size": "bigint"}
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]


def rows(scale: float) -> int:
    return int(ROWS_PER_SF * scale)


def generate(scale: float, seed: int, columns: list[str]) -> dict:
    unknown = set(columns) - set(TYPES)
    if unknown:
        raise ValueError(f"part has no generator for {sorted(unknown)}")
    n = rows(scale)
    rng = np.random.default_rng([seed, 20])
    out = {"p_partkey": np.arange(1, n + 1),
           "p_brand": (rng.integers(0, len(BRANDS), n).astype(np.int32),
                       BRANDS),
           "p_size": rng.integers(1, 51, n)}
    return {c: out[c] for c in columns}
