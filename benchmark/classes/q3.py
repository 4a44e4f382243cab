"""TPC-H Q3 (shipping priority), TPC-H v3 section 2.4.3, in the spec's own
text: the ten unshipped orders of highest value of one market segment.
``customer`` filtered by segment, joined to ``orders`` before a date,
joined to ``lineitem`` shipped after it, grouped by the order, ranked.

Substitution parameters as the spec draws them: SEGMENT one of the five,
DATE a day of March 1995.  The spec's "first 10 rows" is ``limit 10``.

The oracle joins by plain fancy indexing on a key -> row map (numpy, no
hash table), sums ``price * (100 - discount)`` exactly per order
(``exact.group_sums``) and ranks with ``np.lexsort``.  The order of the
ten rows is the spec's (revenue descending, then order date) and is total
only if no two of the first eleven groups agree on both: a parameter set
whose first eleven hold such a tie is not drawn (``draw`` draws again).

Loads only against a program that supports the deployment
``tpch_sf1_orders_x1``: one that says of a join launch which form each
lookup took (the ``join_direct_launches`` counter).  A program without it
searches a sorted build for every slot of ``lineitem`` and compacts its
row results by scatter, seconds a statement, and would be timed for
minutes before the first answer: the harness has no other way to fail
early on a parent it is laid over."""

from __future__ import annotations

import datetime

import numpy as np

from harness import exact
from tidb_tpu.copr import facts as _facts

if "join_direct_launches" not in _facts.counter_names():
    raise SystemExit(
        "benchmark: this program does not support the deployment "
        "tpch_sf1_orders_x1: it keeps no join_direct_launches counter")

NAME = "q3"
POOL = 4
ORDERED = True
# join launches a statement of this class takes (`orderjoin_device_share`):
# `orders` looked up in the segment's customers, rows back; `lineitem`
# looked up in those orders, grouped.  The customers' own rows are a
# plain scan, and kept with the table's snapshot after the first
JOIN_LAUNCHES = 2
READS = {"CUSTOMER": ["c_custkey", "c_mktsegment"],
         "ORDERS": ["o_orderkey", "o_custkey", "o_orderdate",
                    "o_shippriority"],
         "LineItem": ["l_orderkey", "l_extendedprice", "l_discount",
                      "l_shipdate"]}
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
LIMIT = 10

_EPOCH = datetime.date(1970, 1, 1)
_DATA: dict = {}            # what `prepare` was given: `draw` checks ties


def _draw(rng) -> dict:
    return {"segment": SEGMENTS[int(rng.integers(0, len(SEGMENTS)))],
            "day": int(rng.integers(1, 32))}


def draw(rng) -> dict:
    for _ in range(64):
        p = _draw(rng)
        if not _DATA or not _ranked(_DATA["state"], p)[1]:
            return p
    raise RuntimeError("q3: every drawn parameter set ties in its first "
                       "eleven groups")


def sql(p: dict) -> str:
    date = f"1995-03-{p['day']:02d}"
    return (
        "select l_orderkey, sum(l_extendedprice*(1-l_discount)) as revenue, "
        "o_orderdate, o_shippriority "
        "from customer, orders, lineitem "
        f"where c_mktsegment = '{p['segment']}' and c_custkey = o_custkey "
        "and l_orderkey = o_orderkey "
        f"and o_orderdate < date '{date}' and l_shipdate > date '{date}' "
        "group by l_orderkey, o_orderdate, o_shippriority "
        "order by revenue desc, o_orderdate limit 10")


def prepare(data: dict):
    """The three tables and the two key -> row maps; the answers are
    worked out a parameter set at a time (`answer`) and kept."""
    cust, orders, li = data["CUSTOMER"], data["ORDERS"], data["LineItem"]
    okey, ckey = orders["o_orderkey"], cust["c_custkey"]
    if len(np.unique(okey)) != len(okey) or len(np.unique(ckey)) != len(ckey):
        raise ValueError("a primary key is not unique")
    order_of = np.full(int(okey.max()) + 2, -1, np.int64)
    order_of[okey] = np.arange(len(okey))
    cust_of = np.full(int(max(ckey.max(), orders["o_custkey"].max())) + 2,
                      -1, np.int64)
    cust_of[ckey] = np.arange(len(ckey))
    lkey = li["l_orderkey"]
    line_order = order_of[np.minimum(lkey, len(order_of) - 1)]
    state = {"cust": cust, "orders": orders, "li": li, "cust_of": cust_of,
             "line_order": line_order, "answers": {}}
    _DATA["state"] = state
    return state


def _ranked(state, p: dict):
    """(the first ten rows, whether the first eleven groups tie on
    (revenue, o_orderdate))."""
    key = (p["segment"], p["day"])
    if key in state["answers"]:
        return state["answers"][key]
    cust, orders, li = state["cust"], state["orders"], state["li"]
    date = exact.days(datetime.date(1995, 3, p["day"]))
    codes, names = cust["c_mktsegment"]
    in_segment = np.array([s == p["segment"] for s in names])[codes]
    crow = state["cust_of"][orders["o_custkey"]]
    keep_order = (crow >= 0) & in_segment[np.maximum(crow, 0)] \
        & (orders["o_orderdate"] < date)
    n = len(keep_order)
    revenue = np.zeros(n, np.int64)
    lines = np.zeros(n, np.int64)
    for s in exact.chunks(len(li["l_shipdate"])):
        orow = state["line_order"][s]
        m = (li["l_shipdate"][s] > date) & (orow >= 0)
        m[m] = keep_order[orow[m]]
        k = orow[m]
        if not len(k):
            continue
        # summed and counted over the order rows this chunk's live lines
        # touch, not over all orders (15M at SF10, 29 chunks): `lineitem`
        # is stored by `l_orderkey`, so the range is narrow and a pass
        # costs its rows.  The same integers whatever the storage order:
        # rows in any order make the range wide and the pass slow, never
        # wrong
        lo, hi = int(k.min()), int(k.max()) + 1
        k -= lo
        value = li["l_extendedprice"][s][m] * (100 - li["l_discount"][s][m])
        revenue[lo:hi] += exact.group_sums(k, value, hi - lo)
        lines[lo:hi] += np.bincount(k, minlength=hi - lo)
    held = np.nonzero(lines > 0)[0]
    # the group is (l_orderkey, o_orderdate, o_shippriority): the last
    # two are functions of the first, the orders' key being unique
    odate = orders["o_orderdate"][held]
    first = held[np.lexsort((odate, -revenue[held]))[:LIMIT + 1]]
    pairs = [(int(revenue[k]), int(orders["o_orderdate"][k])) for k in first]
    rows = [(str(int(orders["o_orderkey"][k])),
             exact.dec_text(revenue[k], 4),
             str(_EPOCH + datetime.timedelta(int(orders["o_orderdate"][k]))),
             str(int(orders["o_shippriority"][k]))) for k in first[:LIMIT]]
    state["answers"][key] = rows, len(set(pairs)) != len(pairs)
    return state["answers"][key]


def answer(state, p: dict) -> list[tuple]:
    return _ranked(state, p)[0]


def bytes_read(rows: dict, width: dict) -> int:
    """Every column the statement reads, of all three tables, once, at its
    narrow width; nothing for the two lookups, the GROUP BY or the rank."""
    return exact.scan_bytes(READS, rows, width)
