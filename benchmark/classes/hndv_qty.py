"""Per-part quantity, ranked: ``chip_smoke.py``'s ``HNDV_SQL`` letter for
letter.  The aggregate TPC-H v3 computes per part in Q17 (2.4.17) and Q20
(2.4.20), over every row of ``lineitem``, ranked as Q3, Q10 and Q18 rank
their groups (ORDER BY an aggregate DESC, LIMIT); ties broken by the part
key, so the order is total.  Not a spec query text; no parameters: every
row is live and every part (200,000 x SF) is a group.

The deployment is one the program supports only since it reduces a
high-NDV GROUP BY without a gather or a scatter a slot (PR 29): against a
program without that (no ``hndv_agg_launches`` counter) this class
refuses to load, at once, rather than time statements of seconds."""

from __future__ import annotations

import numpy as np

from harness import exact
from tidb_tpu.copr import facts as _facts

if "hndv_agg_launches" not in _facts.counter_names():
    raise SystemExit(
        "benchmark: this program does not support the deployment "
        "tpch_sf1_hndv_x1: it keeps no hndv_agg_launches counter (its "
        "high-NDV GROUP BY takes seconds a statement)")

NAME = "hndv_qty"
POOL = 1
ORDERED = True
READS = {"LINEITEM": ["l_partkey", "l_quantity"]}
LIMIT = 10


def draw(rng) -> dict:
    return {}


def sql(p: dict) -> str:
    return ("select l_partkey, sum(l_quantity) from lineitem "
            "group by l_partkey order by 2 desc, 1 limit 10")


def prepare(data: dict):
    li = data["LINEITEM"]
    part, qty = li["l_partkey"], li["l_quantity"]
    n = int(part.max()) + 1 if len(part) else 1
    totals = np.zeros(n, np.int64)
    rows = np.zeros(n, np.int64)
    for s in exact.chunks(len(part)):
        totals += exact.group_sums(part[s], qty[s], n)
        rows += np.bincount(part[s], minlength=n)
    held = np.nonzero(rows > 0)[0]              # ascending: ties by key
    first = held[np.argsort(-totals[held], kind="stable")[:LIMIT]]
    return [(str(int(k)), exact.dec_text(totals[k], 2)) for k in first]


def answer(state, p: dict) -> list[tuple]:
    return state


def bytes_read(rows: dict, width: dict) -> int:
    """The two columns once, at their narrow widths: what a grouped
    reduction bound by memory would read.  (It is not: a sort orders the
    rows, PERF.md section 5.)"""
    return exact.scan_bytes(READS, rows, width)
