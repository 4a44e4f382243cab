"""ORDER BY ... LIMIT 10 over ``lineitem`` on two keys: the TopN core of
TPC-H Q3, Q10 and Q18, on one table because ``orders`` and ``customer``
have no generator at size (``TPCH_PLAN_QUERIES[6]`` of the program's plan
corpus).  No parameters."""

from __future__ import annotations

import numpy as np

from harness import exact

NAME = "topn"
POOL = 1
ORDERED = True
READS = {"lineitem": ["l_orderkey", "l_extendedprice"]}
LIMIT = 10


def draw(rng) -> dict:
    return {}


def sql(p: dict) -> str:
    return ("select l_orderkey, l_extendedprice from lineitem "
            f"order by l_extendedprice desc, l_orderkey limit {LIMIT}")


def prepare(data: dict):
    li = data["lineitem"]
    key, price = li["l_orderkey"], li["l_extendedprice"]
    cand_key, cand_price = [], []
    for s in exact.chunks(len(price)):
        p, k = price[s], key[s]
        if len(p) > LIMIT:
            kth = np.partition(p, len(p) - LIMIT)[len(p) - LIMIT]
            keep = p >= kth                 # every tie of the 10th price
            p, k = p[keep], k[keep]
        cand_key.append(k)
        cand_price.append(p)
    k, p = np.concatenate(cand_key), np.concatenate(cand_price)
    order = np.lexsort((k, -p))[:LIMIT]
    return [(str(int(k[i])), exact.dec_text(p[i], 2)) for i in order]


def answer(state, p: dict) -> list[tuple]:
    return state


def bytes_read(rows: dict, width: dict) -> int:
    return exact.scan_bytes(READS, rows, width)
