"""Per-part revenue and order lines of one ship year, ranked:

    select l_partkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           count(*)
    from lineitem
    where l_shipdate >= date '[DATE]'
      and l_shipdate < date '[DATE]' + interval '1' year
    group by l_partkey order by revenue desc, l_partkey limit 10

The aggregate TPC-H v3 computes per part and year in Q20 (2.4.20), with
Q3's and Q10's revenue expression (2.4.3), ranked as they rank their
groups.  Not a spec query text.  DATE is Q20's parameter, the first of
January of a year in 1993..1997.  The year keeps one row in seven and
nearly every part (about 197,000 x SF groups); two aggregates, one of them
a DECIMAL sum of 64-bit products.

Loads only against a program that supports the deployment, as
``hndv_qty.py`` says."""

from __future__ import annotations

import datetime

import numpy as np

from harness import exact
from tidb_tpu.copr import facts as _facts

if "hndv_agg_launches" not in _facts.counter_names():
    raise SystemExit(
        "benchmark: this program does not support the deployment "
        "tpch_sf1_hndv_x1: it keeps no hndv_agg_launches counter")

NAME = "hndv_rev"
POOL = 4
ORDERED = True
READS = {"LINEITEM": ["l_partkey", "l_extendedprice", "l_discount",
                      "l_shipdate"]}
YEARS = range(1993, 1998)
LIMIT = 10


def draw(rng) -> dict:
    return {"year": int(rng.integers(YEARS.start, YEARS.stop))}


def sql(p: dict) -> str:
    date = f"{p['year']}-01-01"
    return (
        "select l_partkey, sum(l_extendedprice * (1 - l_discount)) as "
        "revenue, count(*) from lineitem "
        f"where l_shipdate >= date '{date}' "
        f"and l_shipdate < date '{date}' + interval '1' year "
        "group by l_partkey order by revenue desc, l_partkey limit 10")


def prepare(data: dict):
    """``{year: rows}``: the answer for every year a parameter can name."""
    li = data["LINEITEM"]
    part, ship = li["l_partkey"], li["l_shipdate"]
    n = int(part.max()) + 1 if len(part) else 1
    starts = np.array([exact.days(datetime.date(y, 1, 1))
                       for y in range(YEARS.start, YEARS.stop + 1)])
    revenue = np.zeros((len(YEARS), n), np.int64)
    lines = np.zeros((len(YEARS), n), np.int64)
    for s in exact.chunks(len(part)):
        year = np.searchsorted(starts, ship[s], side="right") - 1
        value = li["l_extendedprice"][s] * (100 - li["l_discount"][s])
        for y in range(len(YEARS)):
            m = year == y
            revenue[y] += exact.group_sums(part[s][m], value[m], n)
            lines[y] += np.bincount(part[s][m], minlength=n)
    out = {}
    for y in range(len(YEARS)):
        held = np.nonzero(lines[y] > 0)[0]      # ascending: ties by key
        first = held[np.argsort(-revenue[y][held], kind="stable")[:LIMIT]]
        out[YEARS.start + y] = [
            (str(int(k)), exact.dec_text(revenue[y][k], 4),
             str(int(lines[y][k]))) for k in first]
    return out


def answer(state, p: dict) -> list[tuple]:
    return state[p["year"]]


def bytes_read(rows: dict, width: dict) -> int:
    """The four columns once, at their narrow widths."""
    return exact.scan_bytes(READS, rows, width)
