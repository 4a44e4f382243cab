"""TPC-H ``PART`` as the part-lineitem queries (Q14, Q19) read it: key,
brand, type, size and container, as plain numpy arrays from the seed.  A
second generator beside ``part.py``, which may not change; key, brand and
size are drawn as there (``tidb_tpu/testing/tpch.py gen_part``'s
arithmetic), type and container from streams of their own.

The spec's 4.2.3: ``p_partkey`` dense from 1, ``p_brand`` Brand#MN with M
and N in 1..5, ``p_type`` three syllables (6 x 5 x 5 = 150 values),
``p_size`` 1..50, ``p_container`` two syllables (5 x 8 = 40 values), all
uniform.  Dictionaries are sorted."""

from __future__ import annotations

import numpy as np

NAME = "PART"
LOAD = "bulk"
ROWS_PER_SF = 200_000
TYPES = {"p_partkey": "bigint", "p_brand": "dict", "p_type": "dict",
         "p_size": "bigint", "p_container": "dict"}
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]
PART_TYPES = sorted(
    f"{a} {b} {c}"
    for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
    for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
    for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER"))
CONTAINERS = sorted(
    f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
    for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"))


def rows(scale: float) -> int:
    return int(ROWS_PER_SF * scale)


def generate(scale: float, seed: int, columns: list[str]) -> dict:
    unknown = set(columns) - set(TYPES)
    if unknown:
        raise ValueError(f"PART has no generator for {sorted(unknown)}")
    n = rows(scale)
    rng = np.random.default_rng([seed, 20])

    def codes(tag, values):
        return (np.random.default_rng([seed, tag]).integers(
            0, len(values), n).astype(np.int32), values)
    out = {"p_partkey": np.arange(1, n + 1),
           "p_brand": (rng.integers(0, len(BRANDS), n).astype(np.int32),
                       BRANDS),
           "p_size": rng.integers(1, 51, n),
           "p_type": codes(21, PART_TYPES),
           "p_container": codes(22, CONTAINERS)}
    return {c: out[c] for c in columns}
