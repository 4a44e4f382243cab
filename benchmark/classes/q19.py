"""TPC-H Q19 (discounted revenue), TPC-H v3 section 2.4.19, in the spec's
own text: ``lineitem`` joined to ``part``, three OR branches that each
repeat the join condition and name a brand, four containers, a range of
quantities and of sizes, and the same ship mode and instruction.

Substitution parameters as the spec draws them: QUANTITY1 1..10,
QUANTITY2 10..20, QUANTITY3 20..30, BRAND1..3 ``Brand#MN`` with M and N in
1..5.  ``l_shipmode in ('AIR', 'AIR REG')`` is the spec's text; the
generator's mode is ``REG AIR``, so only ``AIR`` ever matches (the spec's
own quirk, kept).

The branches name disjoint containers (SM, MED, LG), so no row satisfies
two of them and the OR's sum is the sum over the branches.  The oracle
joins by plain fancy indexing on ``l_partkey - 1``."""

from __future__ import annotations

import numpy as np

from harness import exact

NAME = "q19"
POOL = 4
ORDERED = True
READS = {"LINEITEM": ["l_partkey", "l_quantity", "l_extendedprice",
                      "l_discount", "l_shipmode", "l_shipinstruct"],
         "PART": ["p_partkey", "p_brand", "p_container", "p_size"]}

# per branch: containers, largest size; quantities are [Q, Q + 10]
BRANCHES = (
    (("SM CASE", "SM BOX", "SM PACK", "SM PKG"), 5),
    (("MED BAG", "MED BOX", "MED PKG", "MED PACK"), 10),
    (("LG CASE", "LG BOX", "LG PACK", "LG PKG"), 15),
)
_QUANTITIES = ((1, 10), (10, 20), (20, 30))
_N_QTY = 50


def draw(rng) -> dict:
    return {"quantity": [int(rng.integers(lo, hi + 1))
                         for lo, hi in _QUANTITIES],
            "brand": [f"Brand#{int(rng.integers(1, 6))}"
                      f"{int(rng.integers(1, 6))}" for _ in BRANCHES]}


def sql(p: dict) -> str:
    def branch(k):
        containers, size = BRANCHES[k]
        q = p["quantity"][k]
        return (
            "( p_partkey = l_partkey "
            f"and p_brand = '{p['brand'][k]}' "
            "and p_container in ("
            + ", ".join(f"'{c}'" for c in containers) + ") "
            f"and l_quantity >= {q} and l_quantity <= {q} + 10 "
            f"and p_size between 1 and {size} "
            "and l_shipmode in ('AIR', 'AIR REG') "
            "and l_shipinstruct = 'DELIVER IN PERSON' )")
    return ("select sum(l_extendedprice* (1 - l_discount)) as revenue "
            "from lineitem, part where "
            + " or ".join(branch(k) for k in range(len(BRANCHES))))


def prepare(data: dict):
    """Per branch, brand and quantity: the exact sum of price * (1 -
    discount), at scale 4, and the count, over the rows shipped by AIR
    and delivered in person whose part has one of the branch's containers
    and a size the branch admits."""
    li, part = data["LINEITEM"], data["PART"]
    if not np.array_equal(part["p_partkey"],
                          np.arange(1, len(part["p_partkey"]) + 1)):
        raise ValueError("PART's key is not dense from 1")
    brand, brands = part["p_brand"]
    container, containers = part["p_container"]
    size = part["p_size"]
    # 0: in no branch; k + 1: the part passes branch k's container and size
    of_part = np.zeros(len(size), np.int64)
    for k, (names, largest) in enumerate(BRANCHES):
        named = np.isin(container, [containers.index(c) for c in names])
        of_part[named & (size >= 1) & (size <= largest)] = k + 1
    mode, modes = li["l_shipmode"]
    instruct, instructs = li["l_shipinstruct"]
    air = [modes.index(m) for m in ("AIR", "AIR REG") if m in modes]
    in_person = instructs.index("DELIVER IN PERSON")
    nb = (len(BRANCHES) + 1) * len(brands) * _N_QTY
    sums, counts = np.zeros(nb, np.int64), np.zeros(nb, np.int64)
    for s in exact.chunks(len(mode)):
        keep = np.isin(mode[s], air) & (instruct[s] == in_person)
        row = li["l_partkey"][s][keep] - 1
        qty = li["l_quantity"][s][keep] // 100 - 1
        key = (of_part[row] * len(brands) + brand[row]) * _N_QTY + qty
        revenue = li["l_extendedprice"][s][keep] \
            * (100 - li["l_discount"][s][keep])
        sums += exact.group_sums(key, revenue, nb)
        counts += np.bincount(key, minlength=nb)
    shape = (len(BRANCHES) + 1, len(brands), _N_QTY)
    return brands, sums.reshape(shape), counts.reshape(shape)


def answer(state, p: dict) -> list[tuple]:
    brands, sums, counts = state
    total = n = 0
    for k in range(len(BRANCHES)):
        b, q = brands.index(p["brand"][k]), p["quantity"][k]
        total += int(sums[k + 1, b, q - 1:q + 10].sum())
        n += int(counts[k + 1, b, q - 1:q + 10].sum())
    # SUM over no rows is NULL
    return [(exact.dec_text(total, 4) if n else None,)]


def bytes_read(rows: dict, width: dict) -> int:
    """Probe columns at their narrow widths once, build columns once,
    nothing for the gather: the least a memory-bound probe could read."""
    return exact.scan_bytes(READS, rows, width)
