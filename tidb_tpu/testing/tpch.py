"""TPC-H lineitem/part generator (numpy, vectorized).

Distribution-faithful for the columns Q1/Q6/Q19 touch (quantity, discount,
tax, shipdate ranges, returnflag/linestatus derivation); other columns are
uniform fillers.  SF=1 ≈ 6M lineitem rows, as in the spec.

Reference analog: the reference benchmarks against TPC-H via external
tooling (BASELINE.md); this in-repo generator plays the role of the
reference's benchdb data loaders (cmd/benchdb).
"""

from __future__ import annotations

import numpy as np

from ..chunk.column import Column, StringDict
from ..types import dtypes as dt
from ..types.temporal import parse_date

DEC2 = dt.decimal(15, 2)

LINEITEM_NAMES = [
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
    "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus",
    "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct",
    "l_shipmode",
]

SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
SHIPINSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]

_STARTDATE = parse_date("1992-01-01")
_CURRENTDATE = parse_date("1995-06-17")
_ENDDATE = parse_date("1998-12-01")


def gen_lineitem(sf: float = 1.0, seed: int = 0,
                 columns: list[str] | None = None) -> tuple[list[str], list[Column]]:
    """Generate lineitem columns; `columns` restricts output (saves RAM)."""
    n = int(6_000_000 * sf)
    want = set(columns or LINEITEM_NAMES)
    out_names, out_cols = [], []

    def emit(name, col):
        if name in want:
            out_names.append(name)
            out_cols.append(col)

    # each block draws from its own seeded child stream, so restricting
    # `columns` skips unwanted work (the SF=100 bench wants 4 of 15
    # columns — no 600M-row orderkey sort) without changing the values
    # of the columns that ARE produced
    def crng(tag: int):
        return np.random.default_rng([seed, tag])

    if "l_orderkey" in want:
        orderkey = np.sort(
            crng(1).integers(1, max(int(1_500_000 * sf), 1) * 4 + 1, n))
        emit("l_orderkey", Column.from_numpy(dt.bigint(False), orderkey))
    if {"l_partkey", "l_extendedprice"} & want:
        partkey = crng(2).integers(1, max(int(200_000 * sf), 1) + 1, n)
        emit("l_partkey", Column.from_numpy(dt.bigint(False), partkey))
    if "l_suppkey" in want:
        emit("l_suppkey", Column.from_numpy(
            dt.bigint(False),
            crng(3).integers(1, max(int(10_000 * sf), 1) + 1, n)))
    if "l_linenumber" in want:
        emit("l_linenumber", Column.from_numpy(
            dt.bigint(False), crng(4).integers(1, 8, n)))

    if {"l_quantity", "l_extendedprice"} & want:
        qty = crng(5).integers(1, 51, n)
        emit("l_quantity", Column.from_numpy(DEC2, qty * 100))
        if "l_extendedprice" in want:
            # extendedprice = qty * p_retailprice(partkey), in cents
            retail = 90000 + (partkey % 20001) + 100 * (partkey % 1000)
            emit("l_extendedprice", Column.from_numpy(DEC2, qty * retail))

    if "l_discount" in want:
        emit("l_discount", Column.from_numpy(DEC2, crng(6).integers(0, 11, n)))
    if "l_tax" in want:
        emit("l_tax", Column.from_numpy(DEC2, crng(7).integers(0, 9, n)))

    if {"l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
            "l_receiptdate"} & want:
        rng = crng(8)
        ship = _STARTDATE + rng.integers(1, 122 + 2406, n)  # orderdate+1..121
        receipt = ship + rng.integers(1, 31, n)
        # returnflag: R or A (50/50) if receipt <= currentdate else N.
        # Codes computed numerically (dict order A=0, N=1, R=2): the
        # per-row python encode loop took minutes at SF>=10.
        returned = receipt <= _CURRENTDATE
        ra = rng.random(n) < 0.5
        fdict = StringDict(["A", "N", "R"])
        codes = np.where(returned, np.where(ra, 2, 0), 1).astype(np.int32)
        emit("l_returnflag", Column(dt.varchar(False), codes,
                                    np.ones(n, bool), fdict))
        sdict = StringDict(["F", "O"])   # F=0, O=1
        scodes = (ship > _CURRENTDATE).astype(np.int32)
        emit("l_linestatus", Column(dt.varchar(False), scodes,
                                    np.ones(n, bool), sdict))
        emit("l_shipdate", Column.from_numpy(dt.date(False), ship))
        emit("l_commitdate", Column.from_numpy(dt.date(False),
                                               ship + rng.integers(-30, 31, n)))
        emit("l_receiptdate", Column.from_numpy(dt.date(False), receipt))

    if "l_shipinstruct" in want:
        d = StringDict(SHIPINSTRUCT)
        emit("l_shipinstruct",
             Column(dt.varchar(False),
                    crng(9).integers(0, len(d), n).astype(np.int32),
                    np.ones(n, bool), d))
    if "l_shipmode" in want:
        d = StringDict(SHIPMODES)
        emit("l_shipmode",
             Column(dt.varchar(False),
                    crng(10).integers(0, len(d), n).astype(np.int32),
                    np.ones(n, bool), d))
    return out_names, out_cols


PART_NAMES = ["p_partkey", "p_brand", "p_size", "p_container"]

CONTAINERS = [f"{a} {b}" for a in ["SM", "LG", "MED", "JUMBO", "WRAP"]
              for b in ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]]


def gen_part(sf: float = 1.0, seed: int = 1) -> tuple[list[str], list[Column]]:
    n = int(200_000 * sf)
    rng = np.random.default_rng(seed)
    partkey = np.arange(1, n + 1)
    bdict = StringDict([f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)])
    brands = rng.integers(0, len(bdict), n).astype(np.int32)
    cdict = StringDict(CONTAINERS)
    containers = rng.integers(0, len(cdict), n).astype(np.int32)
    cols = [
        Column.from_numpy(dt.bigint(False), partkey),
        Column(dt.varchar(False), brands, np.ones(n, bool), bdict),
        Column.from_numpy(dt.bigint(False), rng.integers(1, 51, n)),
        Column(dt.varchar(False), containers, np.ones(n, bool), cdict),
    ]
    return PART_NAMES, cols


ORDERS_MINI_NAMES = ["o_orderkey", "o_custkey", "o_totalprice"]


def gen_orders_mini(n: int = 1024, seed: int = 7) -> tuple[list[str], list[Column]]:
    """Small orders table keyed to lineitem's l_orderkey domain — enough
    for multi-join fragment validation (dryrun/Q3 shape)."""
    rng = np.random.default_rng(seed)
    okey = np.arange(1, n + 1)
    cols = [
        Column.from_numpy(dt.bigint(False), okey),
        Column.from_numpy(dt.bigint(False), rng.integers(1, n // 4 + 2, n)),
        Column.from_numpy(DEC2, rng.integers(1000, 500000, n)),
    ]
    return ORDERS_MINI_NAMES, cols


# ------------------------------------------------------------------ #
# plan corpus: the TPC-H-shaped statements every static-analysis gate
# run and tests/test_analysis.py push through analysis.verify_plan.
# Shapes covered: dense scalar/keyed agg, SORT (high-NDV) agg, rollup,
# TopN/Limit, row-returning projections, broadcast lookup join (rows +
# agg + multi-level), semi/anti join, host sort/setop, device window.
# ------------------------------------------------------------------ #

TPCH_PLAN_QUERIES = [
    # Q6: dense scalar aggregation over scan+filter
    """select sum(l_extendedprice * l_discount) as revenue from lineitem
       where l_shipdate >= date '1994-01-01'
         and l_shipdate < date '1995-01-01'
         and l_discount between 0.05 and 0.07 and l_quantity < 24""",
    # Q1: dense keyed aggregation (dict-coded group keys)
    """select l_returnflag, l_linestatus, sum(l_quantity),
              sum(l_extendedprice), avg(l_discount), count(*)
       from lineitem where l_shipdate <= date '1998-09-02'
       group by l_returnflag, l_linestatus
       order by l_returnflag, l_linestatus""",
    # high-NDV group-by: SORT-strategy aggregation (single key)
    """select l_orderkey, sum(l_extendedprice) from lineitem
       group by l_orderkey""",
    # very-high-NDV group-bys: the per-key stats NDV PRODUCT seeds a
    # SORT group table at the capacity ceiling (tpch_plan_session
    # ANALYZEs lineitem so the estimates exist at plan time) — the gate
    # keeps them contract-clean and rc-pricing-finite like every other
    # corpus shape
    """select l_orderkey, l_partkey, count(*), sum(l_quantity)
       from lineitem group by l_orderkey, l_partkey""",
    """select l_orderkey, l_suppkey, max(l_extendedprice) from lineitem
       where l_quantity < 45 group by l_orderkey, l_suppkey""",
    # rollup: Expand + grouping sets
    """select l_returnflag, l_linestatus, sum(l_quantity) from lineitem
       group by l_returnflag, l_linestatus with rollup""",
    # device TopN (multi-key) and plain Limit
    """select l_orderkey, l_extendedprice from lineitem
       order by l_extendedprice desc, l_orderkey limit 10""",
    "select l_partkey from lineitem limit 5",
    # row-returning scan chain with projection arithmetic
    """select l_orderkey, l_extendedprice * (1 - l_discount)
       from lineitem where l_quantity < 5""",
    # broadcast lookup join with a group-by (lineitem probes, part builds)
    """select p_brand, sum(l_extendedprice) from lineitem, part
       where l_partkey = p_partkey and l_quantity < 10
       group by p_brand""",
    # broadcast lookup join, row-returning
    """select l_orderkey, p_brand from lineitem, part
       where l_partkey = p_partkey and p_size > 40 limit 20""",
    # semi join (IN subquery)
    """select l_orderkey from lineitem
       where l_partkey in (select p_partkey from part where p_size > 45)
       limit 10""",
    # anti join (NOT IN subquery)
    """select count(*) from lineitem
       where l_suppkey not in (select o_custkey from orders)""",
    # multi-table chain: lineitem x orders x part
    """select o_totalprice, p_brand, l_quantity from lineitem, orders, part
       where l_orderkey = o_orderkey and l_partkey = p_partkey
       limit 10""",
    # host sort over join output
    """select o_orderkey, sum(l_extendedprice) as rev from lineitem, orders
       where l_orderkey = o_orderkey
       group by o_orderkey order by rev desc limit 5""",
    # set operation
    """select l_partkey from lineitem where l_quantity < 2
       union select p_partkey from part where p_size = 1""",
    # window function over the sharded table
    """select l_orderkey,
              row_number() over (partition by l_returnflag
                                 order by l_extendedprice desc) as rn
       from lineitem limit 10""",
    # scalar-subquery-free HAVING residue (host filter over agg)
    """select l_returnflag, count(*) as c from lineitem
       group by l_returnflag having count(*) > 1""",
]


def tpch_plan_session(sf: float = 0.001, n_orders: int = 512):
    """In-memory Domain+Session with lineitem/part/orders registered from
    the generators above — the fixture both the analysis gate and the
    verifier tests plan TPCH_PLAN_QUERIES against."""
    from ..session import Domain, Session
    from ..session.catalog import TableInfo
    dom = Domain()
    for name, (names, cols) in (
            ("lineitem", gen_lineitem(sf=sf, seed=42)),
            ("part", gen_part(sf=max(sf * 10, 0.005), seed=7)),
            ("orders", gen_orders_mini(n_orders))):
        t = TableInfo(name, list(names), [c.dtype for c in cols])
        t.register_columns(list(cols))
        dom.catalog.create_table("test", t)
    sess = Session(dom)
    # stats NDV seeds the SORT group-table capacity
    # (executor/plan._ndv_capacity)
    sess.execute("analyze table lineitem")
    return sess


# planned with the broadcast threshold forced to 0 so the repartition
# (all_to_all shuffle) join path is exercised by the gate too
# (a key that comes twice on both sides: no table of the build to own,
# so under a zeroed broadcast cap both sides are re-bucketed; a unique
# build key past the cap stays sharded instead: plan.which_side_moves)
TPCH_SHUFFLE_QUERIES = [
    """select count(*), sum(l_quantity + o_totalprice) from lineitem
       join orders on l_linenumber = o_custkey""",
    """select o_custkey, sum(l_quantity) from lineitem join orders
       on l_linenumber = o_custkey group by o_custkey""",
]


# the MULTICHIP dryrun's plan shapes (__graft_entry__.dryrun_multichip):
# every distributed step the dry run executes on the 8-vdev mesh, as
# plannable SQL — the shardflow gate pass must analyze each clean with
# finite per-link transfer bytes (the pod-scale exchange shapes the
# multi-host runtime PR will inherit)
MULTICHIP_PLAN_QUERIES = [
    # Q1 psum step: dense keyed agg merged in-program
    """select l_returnflag, l_linestatus, sum(l_quantity), count(*)
       from lineitem where l_shipdate <= date '1998-09-02'
       group by l_returnflag, l_linestatus""",
    # TopN shard-merge step
    """select l_extendedprice from lineitem
       order by l_extendedprice desc limit 5""",
    # broadcast-join step (LookupJoin + psum agg)
    """select count(*), sum(l_extendedprice) from lineitem, part
       where p_partkey = l_partkey and p_size < 25""",
    # rollup Expand fragment
    """select l_returnflag, l_linestatus, count(*) from lineitem
       group by l_returnflag, l_linestatus with rollup""",
    # window repartition (all_to_all on PARTITION BY)
    """select l_linestatus, row_number() over
       (partition by l_linestatus order by l_extendedprice desc)
       from lineitem""",
    # window-over-join fragment
    """select l_linestatus, row_number() over
       (partition by l_linestatus order by l_extendedprice desc)
       from lineitem, part
       where p_partkey = l_partkey and p_size < 25""",
]


def built_multichip_plans(session):
    """Plan the MULTICHIP dryrun shapes: the broadcast forms above plus
    the same join re-planned as a repartition shuffle (threshold 0) —
    the all_to_all exchange step of the dry run."""
    yield from built_tpch_plans(session, MULTICHIP_PLAN_QUERIES)
    from ..executor import plan as planmod
    saved = planmod.BROADCAST_BUILD_MAX_ROWS
    planmod.BROADCAST_BUILD_MAX_ROWS = 0
    try:
        yield from built_tpch_plans(
            session, ["""select count(*), sum(l_extendedprice)
                         from lineitem, part
                         where p_size = l_linenumber and p_size < 25"""])
    finally:
        planmod.BROADCAST_BUILD_MAX_ROWS = saved


def built_tpch_plans(session, queries=None):
    """Plan (without executing) each corpus statement; yields
    (sql, physical plan) pairs for analysis.verify_plan.  With the
    default corpus, also plans TPCH_SHUFFLE_QUERIES under a zeroed
    broadcast threshold to cover the exchange (shuffle-join) path."""
    from ..sql.parser import parse_one

    def plan(sql):
        _built, phys = session._plan_select(parse_one(sql))
        return phys

    for sql in (queries if queries is not None else TPCH_PLAN_QUERIES):
        yield sql, plan(sql)
    if queries is None:
        from ..executor import plan as planmod
        saved = planmod.BROADCAST_BUILD_MAX_ROWS
        planmod.BROADCAST_BUILD_MAX_ROWS = 0
        try:
            for sql in TPCH_SHUFFLE_QUERIES:
                yield sql, plan(sql)
        finally:
            planmod.BROADCAST_BUILD_MAX_ROWS = saved


__all__ = ["gen_lineitem", "gen_part", "gen_orders_mini", "LINEITEM_NAMES",
           "PART_NAMES", "DEC2", "TPCH_PLAN_QUERIES",
           "TPCH_SHUFFLE_QUERIES", "MULTICHIP_PLAN_QUERIES",
           "tpch_plan_session", "built_tpch_plans",
           "built_multichip_plans"]
