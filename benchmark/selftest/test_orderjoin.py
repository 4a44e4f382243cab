"""The orders-lineitem cell ``tpch1x1.orderjoin``: its three generators'
referential integrity and distributions, its two oracles against a second
independent computation (sqlite, at a small scale), the new readers on
made-up ``/sched`` documents and span trees, and the cell rehearsed on the
CPU."""

import datetime
import sqlite3

import numpy as np
import pytest

from conftest import load_run_py
from harness import exact
from harness.context import Run

run_py = load_run_py()
EPOCH = datetime.date(1970, 1, 1)
CELL = "tpch1x1.orderjoin"


def _table(name, scale, seed=7, columns=None):
    table = run_py.load_module("tables", name)
    return table, table.generate(scale, seed, columns or list(table.TYPES))


def _three(scale, seed=7):
    return {name: _table(name, scale, seed)[1]
            for name in ("CUSTOMER", "ORDERS", "LineItem")}


# --------------------------------------------------------------------- #
# the generators
# --------------------------------------------------------------------- #

def test_referential_integrity_and_the_spec_s_key_patterns():
    data = _three(0.05)
    cust, orders, li = data["CUSTOMER"], data["ORDERS"], data["LineItem"]
    n_c, n_o, n_l = 7_500, 75_000, len(li["l_orderkey"])
    assert len(cust["c_custkey"]) == n_c and len(orders["o_orderkey"]) == n_o
    assert (cust["c_custkey"] == np.arange(1, n_c + 1)).all()
    # o_orderkey: the first 8 keys of every 32, ascending from 1
    okey = orders["o_orderkey"]
    assert okey[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33]
    assert ((okey - 1) % 32 < 8).all() and (np.diff(okey) > 0).all()
    assert okey[-1] - okey[0] + 1 > 3.9 * n_o          # a range 4x the keys
    # o_custkey: a customer, never a multiple of 3, two thirds of them
    ck = orders["o_custkey"]
    assert ck.min() >= 1 and ck.max() <= n_c and (ck % 3 != 0).all()
    assert len(np.unique(ck)) > 0.99 * (n_c - n_c // 3)
    # every line belongs to an order, 1..7 lines an order, stored by key
    lkey = li["l_orderkey"]
    assert np.isin(lkey, okey).all() and (np.diff(lkey) >= 0).all()
    keys, lines = np.unique(lkey, return_counts=True)
    assert (keys == okey).all()
    assert lines.min() == 1 and lines.max() == 7
    assert abs(lines.mean() - 4) < 0.05 and abs(n_l - 4 * n_o) < n_o // 25
    share = np.bincount(lines)[1:] / n_o
    assert abs(share - 1 / 7).max() < 0.01
    # the dates hang on the order's
    odate = orders["o_orderdate"][np.searchsorted(okey, lkey)]
    ship, commit, receipt = (li[c] - odate if c != "l_receiptdate"
                             else li[c] - li["l_shipdate"] for c in (
        "l_shipdate", "l_commitdate", "l_receiptdate"))
    assert (ship.min(), ship.max()) == (1, 121)
    assert (commit.min(), commit.max()) == (30, 90)
    assert (receipt.min(), receipt.max()) == (1, 30)
    lo, hi = (EPOCH + datetime.timedelta(int(orders["o_orderdate"].min())),
              EPOCH + datetime.timedelta(int(orders["o_orderdate"].max())))
    assert datetime.date(1992, 1, 1) <= lo <= datetime.date(1992, 1, 3)
    assert datetime.date(1998, 7, 31) <= hi <= datetime.date(1998, 8, 2)
    assert (orders["o_shippriority"] == 0).all()


def test_distributions_and_dictionaries():
    data = _three(0.05)
    mods = {n: run_py.load_module("tables", n)
            for n in ("CUSTOMER", "ORDERS", "LineItem")}
    for table, col, values in (
            ("CUSTOMER", "c_mktsegment", mods["CUSTOMER"].SEGMENTS),
            ("ORDERS", "o_orderpriority", mods["ORDERS"].PRIORITIES),
            ("LineItem", "l_shipmode", mods["LineItem"].SHIPMODES)):
        codes, names = data[table][col]
        assert names == values == sorted(values)
        share = np.bincount(codes, minlength=len(values)) / len(codes)
        assert abs(share - 1 / len(values)).max() < 0.015
    assert len(mods["CUSTOMER"].SEGMENTS) == 5
    assert len(mods["ORDERS"].PRIORITIES) == 5
    assert mods["LineItem"].SHIPMODES == run_py.load_module(
        "tables", "LINEITEM").SHIPMODES
    li = data["LineItem"]
    assert set(np.unique(li["l_discount"])) == set(range(11))
    price = li["l_extendedprice"]
    assert price.min() >= 90_000 and price.max() <= 50 * 210_000


def test_the_seed_makes_the_data_and_may_be_large():
    a, b = _three(0.002, 2 ** 31 + 11), _three(0.002, 2 ** 31 + 11)
    c = _three(0.002, 2 ** 31 + 12)
    same = differs = 0
    for t in a:
        for col in a[t]:
            x, y, z = (v[col][0] if isinstance(v[col], tuple) else v[col]
                       for v in (a[t], b[t], c[t]))
            assert (x == y).all()
            same += 1
            differs += len(x) != len(z) or not (x == z).all()
    assert same == 14 and differs >= 9     # keys and constants do not move
    # a column asked for alone is the column of the whole table
    _, only = _table("LineItem", 0.002, 2 ** 31 + 11, ["l_receiptdate"])
    assert (only["l_receiptdate"] == a["LineItem"]["l_receiptdate"]).all()
    assert run_py.load_module("tables", "LineItem").NAME.lower() == "lineitem"


# --------------------------------------------------------------------- #
# the oracles against sqlite
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def small():
    """(data, a sqlite database of the same rows; dates as day numbers,
    decimals as their raw integers, strings spelt out)."""
    data = _three(0.004, 2147483659)       # 600 x 6,000 x 24,000 rows
    db = sqlite3.connect(":memory:")
    for table, cols in data.items():
        names = list(cols)
        db.execute(f"create table {table.lower()} ({', '.join(names)})")
        arrays = [[v[1][i] for i in v[0].tolist()] if isinstance(v, tuple)
                  else v.tolist() for v in cols.values()]
        db.executemany(
            f"insert into {table.lower()} values "
            f"({', '.join('?' * len(names))})", list(zip(*arrays)))
    db.execute("create index o_k on orders (o_orderkey)")
    db.execute("create index c_k on customer (c_custkey)")
    return data, db


def _day(date: datetime.date) -> int:
    return (date - EPOCH).days


def test_q3_oracle_against_sqlite(small):
    data, db = small
    cls = run_py.load_module("classes", "q3")
    state = cls.prepare(data)
    rng = np.random.default_rng(3)
    seen = set()
    for p in [cls.draw(rng) for _ in range(12)]:
        date = _day(datetime.date(1995, 3, p["day"]))
        got = db.execute(
            "select l_orderkey, sum(l_extendedprice * (100 - l_discount)) "
            "as revenue, o_orderdate, o_shippriority "
            "from customer, orders, lineitem where c_mktsegment = ? "
            "and c_custkey = o_custkey and l_orderkey = o_orderkey "
            "and o_orderdate < ? and l_shipdate > ? "
            "group by l_orderkey, o_orderdate, o_shippriority "
            "order by revenue desc, o_orderdate limit 10",
            (p["segment"], date, date)).fetchall()
        want = [(str(k), exact.dec_text(rev, 4),
                 str(EPOCH + datetime.timedelta(d)), str(sp))
                for k, rev, d, sp in got]
        assert cls.answer(state, p) == want and len(want) == 10
        seen.add(tuple(want))
        sql = cls.sql(p)
        assert " from customer, orders, lineitem where c_mktsegment = " in sql
        assert "sum(l_extendedprice*(1-l_discount)) as revenue" in sql
        assert sql.endswith("order by revenue desc, o_orderdate limit 10")
        assert f"date '1995-03-{p['day']:02d}'" in sql
    assert len(seen) >= 8
    # no drawn set ties in its first eleven groups, so the order is total
    assert not any(cls._ranked(state, p)[1]
                   for p in [cls.draw(rng) for _ in range(8)])


def test_q3_draws_again_where_the_first_eleven_tie(small):
    """Two orders of one revenue and one date at the top: the set is
    not drawn."""
    data, _db = small
    cls = run_py.load_module("classes", "q3")
    state = cls.prepare(data)
    p = {"segment": "BUILDING", "day": 15}
    rows, tie = cls._ranked(state, p)
    assert not tie and len(rows) == 10
    state["answers"][p["segment"], p["day"]] = (rows, True)

    class OneThenOthers:
        """A generator that proposes `p` first."""
        def __init__(self):
            self.rng, self.calls = np.random.default_rng(1), 0

        def integers(self, lo, hi):
            self.calls += 1
            if self.calls == 1:
                return cls.SEGMENTS.index("BUILDING")
            if self.calls == 2:
                return 15
            return self.rng.integers(lo, hi)
    drawn = cls.draw(OneThenOthers())
    assert drawn != p and not cls._ranked(state, drawn)[1]


def test_q12_oracle_against_sqlite(small):
    data, db = small
    cls = run_py.load_module("classes", "q12")
    state = cls.prepare(data)
    rng = np.random.default_rng(4)
    counted = 0
    for p in [cls.draw(rng) for _ in range(12)]:
        assert p["mode1"] != p["mode2"]
        lo = _day(datetime.date(p["year"], 1, 1))
        hi = _day(datetime.date(p["year"] + 1, 1, 1))
        got = db.execute(
            "select l_shipmode, "
            "sum(case when o_orderpriority = '1-URGENT' "
            "or o_orderpriority = '2-HIGH' then 1 else 0 end), "
            "sum(case when o_orderpriority <> '1-URGENT' "
            "and o_orderpriority <> '2-HIGH' then 1 else 0 end) "
            "from orders, lineitem where o_orderkey = l_orderkey "
            "and l_shipmode in (?, ?) and l_commitdate < l_receiptdate "
            "and l_shipdate < l_commitdate and l_receiptdate >= ? "
            "and l_receiptdate < ? group by l_shipmode order by l_shipmode",
            (p["mode1"], p["mode2"], lo, hi)).fetchall()
        want = [(m, str(h), str(l)) for m, h, l in got]
        assert cls.answer(state, p) == want and len(want) == 2
        counted += sum(int(h) + int(l) for _m, h, l in want)
        sql = cls.sql(p)
        assert " from orders, lineitem where o_orderkey = l_orderkey " in sql
        assert "+ interval '1' year" in sql
        assert f"in ('{p['mode1']}', '{p['mode2']}')" in sql
    assert counted > 400


@pytest.mark.parametrize("name,tables", [
    ("q3", {"CUSTOMER": 2, "ORDERS": 4, "LineItem": 4}),
    ("q12", {"ORDERS": 2, "LineItem": 5})])
def test_bytes_read_counts_each_column_once(name, tables):
    cls = run_py.load_module("classes", name)
    assert {t: len(c) for t, c in cls.READS.items()} == tables
    rows = {"CUSTOMER": 10, "ORDERS": 100, "LineItem": 1000}
    width = {t: {c: 2 for c in cols} for t, cols in cls.READS.items()}
    assert cls.bytes_read(rows, width) == sum(
        2 * rows[t] * n for t, n in tables.items())
    assert cls.POOL == 4 and cls.ORDERED
    assert cls.JOIN_LAUNCHES == (2 if name == "q3" else 1)


# --------------------------------------------------------------------- #
# the readers
# --------------------------------------------------------------------- #

direct = run_py.load_module("layer_metrics", "join_direct_share")
device = run_py.load_module("layer_metrics", "orderjoin_device_share")
build_ms = run_py.load_module("layer_metrics", "join_build_ms")


class _Class:
    def __init__(self, launches):
        self.JOIN_LAUNCHES = launches


def _run(before, after, q3=20, q12=20):
    records = [{"ok": True, "class": "q3"}] * q3 \
        + [{"ok": True, "class": "q12"}] * q12
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1},
               classes={"q3": _Class(2), "q12": _Class(1)},
               sched_before=before, sched_after=after, records=records)


ZERO = {k: 0 for k in device.RERUNS + (
    "join_launches", "join_direct_launches")}
ZERO["client"] = {"degraded": 0, "oom_recovered": 0}


@pytest.mark.parametrize("before,after,want", [
    (ZERO, dict(ZERO, join_launches=60, join_direct_launches=60), 100.0),
    (dict(ZERO, join_launches=9, join_direct_launches=3),
     dict(ZERO, join_launches=69, join_direct_launches=63), 100.0),
    # a launch in four searched a sorted build
    (ZERO, dict(ZERO, join_launches=60, join_direct_launches=45), 75.0),
    # no join launched: nothing to read
    (ZERO, ZERO, None),
    # the parent keeps no such counter: nothing, no KeyError
    ({"join_launches": 0}, {"join_launches": 60}, None),
])
def test_join_direct_share(before, after, want):
    assert direct.read(_run(before, after)) == want


@pytest.mark.parametrize("after,want", [
    # 20 x 2 + 20 x 1 join launches: each statement its plan's
    (dict(ZERO, join_launches=60), 100.0),
    # a kept build that was made again; a statement that launched less
    (dict(ZERO, join_launches=66), 100.0 * 60 / 66),
    (dict(ZERO, join_launches=45), 75.0),
    # anything rerun, fallen back or repartitioned in the window: 0
    (dict(ZERO, join_launches=60, rows_regrows=1), 0.0),
    (dict(ZERO, join_launches=60, join_compact_overflows=2), 0.0),
    (dict(ZERO, join_launches=60, hndv_agg_regrows=1), 0.0),
    (dict(ZERO, join_launches=60, join_regrows=1), 0.0),
    (dict(ZERO, join_launches=60, join_host_fallbacks=1), 0.0),
    (dict(ZERO, join_launches=60, join_shuffle_launches=1), 0.0),
    (dict(ZERO, join_launches=60,
          client={"degraded": 1, "oom_recovered": 0}), 0.0),
])
def test_orderjoin_device_share(after, want):
    assert device.read(_run(ZERO, after)) == pytest.approx(want)


def test_orderjoin_device_share_finds_nothing_on_the_parent():
    parent = {k: 0 for k in ZERO if k != "rows_regrows"}
    assert device.read(_run(parent, dict(parent, join_launches=60))) is None
    assert device.read(_run(ZERO, dict(ZERO, join_launches=60), 0, 0)) is None


def _tree(cls, builds):
    """A statement's tree with `cop.join_build` spans of (start, length,
    [(child start, child length)]) microseconds."""
    spans, n = [{"id": 1, "parent": None, "name": "session.ExecuteStmt",
                 "start_us": 0, "duration_us": 10_000}], 1
    for start, length, children in builds:
        n += 1
        mine = n
        spans.append({"id": mine, "parent": 1, "name": "cop.join_build",
                      "start_us": start, "duration_us": length})
        for c_start, c_len in children:
            n += 1
            spans.append({"id": n, "parent": mine, "name": "cop.dispatch",
                          "start_us": c_start, "duration_us": c_len})
    return {"class": cls, "spans": spans}


def test_join_build_ms_is_the_span_s_self_time():
    run = _run(ZERO, ZERO)
    # q3: 30 ms of which a launch and its transfer take 18 -> 12 ms, and a
    # kept build's lookup 0.06; q12: the lookup alone
    run.trees = [_tree("q3", [(100, 30_000, [(200, 15_000), (16_000, 3_000)]),
                              (40_000, 60, [])]),
                 _tree("q3", [(100, 20_000, [(200, 10_000)]),
                              (40_000, 40, [])]),
                 _tree("q3", [(100, 24_000, [(200, 10_000)]),
                              (40_000, 50, [])]),
                 _tree("q12", [(100, 50, [])]),
                 _tree("q12", [(100, 70, [])])]
    got = build_ms.read(run)
    assert got == pytest.approx((12.06 * 0.06) ** 0.5)
    run.trees = []
    assert build_ms.read(run) is None


def test_the_rooflines_read_nothing_without_a_trace():
    run = _run(ZERO, ZERO)
    for name in ("q3_join_roofline", "q12_probe_roofline"):
        assert run_py.load_module("layer_metrics", name).read(run) is None


def test_the_cell_lists_what_it_reports():
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, CELL)
    assert (cell["chips"], config["scale"], mix["clients"]) == (1, 1, 1)
    assert mix["mix"] == {"q3": 1, "q12": 1} and mix["pool_seed"] == 31
    assert (mix["loop"], mix["order"], mix["cycles"]) \
        == ("closed", "shuffled", 16)
    assert config["architecture"] is None
    assert {t: len(v["columns"]) for t, v in config["tables"].items()} \
        == {"CUSTOMER": 2, "ORDERS": 5, "LineItem": 7}
    assert all(v.get("analyze") for v in config["tables"].values())
    e2e = {m["name"] for m in run_py.cell_metrics(bench, "end_to_end", CELL)}
    assert e2e == {"stmt_ms_geomean", "stmt_p95_x", "peak_hbm_gb", "setup_s"}
    # what the cell lists, not the whole set or its place: later PRs
    # append entries that list this cell, alone or beside others
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine >= {
        "device_ms.q3", "device_ms.q12", "program_ms.solo_join_rows",
        "program_ms.solo_join_agg_sort", "program_ms.solo_join_agg_dense",
        "q3_join_roofline", "q12_probe_roofline", "join_direct_share",
        "orderjoin_device_share", "join_build_ms"}
    wanted = {m["name"] for m in
              run_py.cell_metrics(bench, "per_layer", CELL)}
    assert mine <= wanted and "device_idle_share" in wanted
    assert not {"device_ms.q14", "join_compact_share",
                "hndv_device_share"} & wanted


def test_cell_rehearsed_on_the_cpu():
    """The whole cell at SF0.01 for three seconds: every warm-up and
    window answer equals the oracle's, every lookup was direct-addressed,
    every statement took the join launches its plan has and nothing was
    rerun in the window."""
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, CELL)
    run = run_py.run_cell(cell, config, mix, seed=2147483659, seconds=3.0,
                          trace=False, scale=0.01)
    assert run.records and all(r["ok"] for r in run.records)
    assert set(run.ms_by_class()) == {"q3", "q12"}
    assert run.rows["CUSTOMER"] == 1_500 and run.rows["ORDERS"] == 15_000
    assert 57_000 < run.rows["LineItem"] < 63_000
    got = run_py.read_metrics(run, "layer_metrics", [
        m for m in bench["per_layer"]
        if m["name"] in ("join_direct_share", "orderjoin_device_share",
                         "launches_per_stmt")])
    assert got["join_direct_share"]["value"] == 100.0
    assert got["orderjoin_device_share"]["value"] == 100.0
    # two launches a `q3` (its customers' rows are kept), one a `q12`
    by_class = {c: len(v) for c, v in run.ms_by_class().items()}
    assert got["launches_per_stmt"]["value"] * len(run.records) \
        == pytest.approx(2 * by_class["q3"] + by_class["q12"])
    for k in ("rows_regrows", "hndv_agg_regrows", "join_compact_overflows",
              "join_host_fallbacks"):
        assert run.sched_delta(k) == 0
