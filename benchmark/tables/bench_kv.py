"""``bench_kv``: a 2,000-row table that goes the long way in: CREATE TABLE
and INSERT over the wire, into the KV store, columnarized lazily on the
first read.  Its size does not scale: it stands for the small tables
beside a warehouse's large ones."""

from __future__ import annotations

import numpy as np

NAME = "bench_kv"
LOAD = "wire"
ROWS = 2000
INSERT_BATCH = 500
TYPES = {"id": "bigint", "grp": "bigint", "v": "bigint"}
DDL = "create table bench_kv (id bigint primary key, grp bigint, v bigint)"


def rows(scale: float) -> int:
    return ROWS


def generate(scale: float, seed: int, columns: list[str]) -> dict:
    rng = np.random.default_rng([seed, 99])
    out = {"id": np.arange(ROWS), "grp": rng.integers(0, 10, ROWS),
           "v": rng.integers(-1000, 1000, ROWS)}
    return {c: out[c] for c in columns}


def inserts(data: dict) -> list[str]:
    """The INSERT statements that load ``data``, in batches."""
    names = list(data)
    table = np.stack([data[c] for c in names], axis=1)
    return [f"insert into bench_kv ({','.join(names)}) values " + ",".join(
        "(" + ",".join(str(int(x)) for x in row) + ")"
        for row in table[lo:lo + INSERT_BATCH])
        for lo in range(0, len(table), INSERT_BATCH)]
