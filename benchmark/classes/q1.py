"""TPC-H Q1 (pricing summary report), the spec's full text: TPC-H v3
section 2.4.1.  Eight aggregate columns over nearly the whole table,
grouped by return flag and line status.  DELTA is 60..120 days.
"""

from __future__ import annotations

import datetime

import numpy as np

from harness import exact

NAME = "q1"
POOL = 4
ORDERED = True
READS = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                      "l_quantity", "l_extendedprice", "l_discount",
                      "l_tax"]}

_END = datetime.date(1998, 12, 1)
# raw sums kept per (group, ship day): quantity, price, discount at scale
# 2, price*(1-discount) at scale 4, price*(1-discount)*(1+tax) at scale 6
_SUMS = 5


def draw(rng) -> dict:
    return {"delta": int(rng.integers(60, 121))}


def sql(p: dict) -> str:
    return (
        "select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
        "sum(l_extendedprice) as sum_base_price, "
        "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
        "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
        "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
        "avg(l_discount) as avg_disc, count(*) as count_order "
        "from lineitem "
        f"where l_shipdate <= date '1998-12-01' - interval '{p['delta']}' day "
        "group by l_returnflag, l_linestatus "
        "order by l_returnflag, l_linestatus")


def prepare(data: dict):
    """One pass: exact counts and raw sums per (group, ship day)."""
    li = data["lineitem"]
    ship = li["l_shipdate"]
    (flag, flags), (status, statuses) = li["l_returnflag"], li["l_linestatus"]
    qty, price = li["l_quantity"], li["l_extendedprice"]
    disc, tax = li["l_discount"], li["l_tax"]
    day0 = int(ship.min()) if len(ship) else 0
    ndays = (int(ship.max()) - day0 + 1) if len(ship) else 1
    ngroups = len(flags) * len(statuses)
    nb = ngroups * ndays
    count = np.zeros(nb, np.int64)
    sums = np.zeros((_SUMS, nb), np.int64)
    for s in exact.chunks(len(ship)):
        gid = flag[s].astype(np.int64) * len(statuses) + status[s]
        key = gid * ndays + (ship[s] - day0)
        count += np.bincount(key, minlength=nb)
        disc_price = price[s] * (100 - disc[s])
        for i, v in enumerate((qty[s], price[s], disc[s], disc_price,
                               disc_price * (100 + tax[s]))):
            sums[i] += exact.group_sums(key, v, nb)
    return {"day0": day0, "count": count.reshape(ngroups, ndays),
            "sums": sums.reshape(_SUMS, ngroups, ndays),
            "names": [(f, s) for f in flags for s in statuses]}


def answer(state, p: dict) -> list[tuple]:
    cutoff = exact.days(_END - datetime.timedelta(days=p["delta"]))
    upto = max(cutoff - state["day0"] + 1, 0)
    count = state["count"][:, :upto].sum(axis=1)
    sums = state["sums"][:, :, :upto].sum(axis=2)
    out = []
    for g, (flag, status) in enumerate(state["names"]):   # sorted already
        n = int(count[g])
        if not n:
            continue
        qty, price, disc, disc_price, charge = (int(x) for x in sums[:, g])
        out.append((flag, status, exact.dec_text(qty, 2),
                    exact.dec_text(price, 2), exact.dec_text(disc_price, 4),
                    exact.dec_text(charge, 6), exact.avg_text(qty, n, 2),
                    exact.avg_text(price, n, 2), exact.avg_text(disc, n, 2),
                    str(n)))
    return out


def bytes_read(rows: dict, width: dict) -> int:
    return exact.scan_bytes(READS, rows, width)
