"""The part-lineitem cell ``tpch1x1.partjoin``: its two oracles against a
nested loop in Python integers and ``Decimal``, its tables' distributions,
the cell rehearsed on the CPU, and ``join_device_share`` on made-up
``/sched`` documents."""

import datetime
from decimal import ROUND_HALF_UP, Decimal

import numpy as np
import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
EPOCH = datetime.date(1970, 1, 1)
ROWS = 400                  # lineitem rows of the nested loop
PARTS = 40


def _table(name, scale, seed=7):
    table = run_py.load_module("tables", name)
    return table, table.generate(scale, seed, list(table.TYPES))


@pytest.fixture(scope="module")
def small():
    """A few hundred LINEITEM rows over 40 parts: small enough for a
    nested loop, with the part keys drawn inside PART's range."""
    _, part = _table("PART", PARTS / 200_000)
    _, li = _table("LINEITEM", ROWS / 6_000_000)
    assert len(part["p_partkey"]) == PARTS and len(li["l_partkey"]) == ROWS
    li["l_partkey"] = np.random.default_rng(3).integers(1, PARTS + 1, ROWS)
    # AIR, in person, on every other row: both branches of that test run
    for col, name in (("l_shipmode", "AIR"),
                      ("l_shipinstruct", "DELIVER IN PERSON")):
        codes, names = li[col]
        codes[::2] = names.index(name)
    return {"LINEITEM": li, "PART": part}


def _rows(table: dict) -> list[dict]:
    cols = {c: ([v[1][i] for i in v[0].tolist()] if isinstance(v, tuple)
                else v.tolist()) for c, v in table.items()}
    n = len(next(iter(cols.values())))
    return [{c: cols[c][i] for c in cols} for i in range(n)]


def _params(cls, n=12):
    rng = np.random.default_rng(11)
    return [cls.draw(rng) for _ in range(n)]


def _revenue(l) -> Decimal:
    return (Decimal(l["l_extendedprice"]).scaleb(-2)
            * (1 - Decimal(l["l_discount"]).scaleb(-2)))


def test_q14_oracle_against_a_nested_loop(small):
    cls = run_py.load_module("classes", "q14")
    state = cls.prepare(small)
    li, part = _rows(small["LINEITEM"]), _rows(small["PART"])
    shares = set()
    for p in _params(cls, 30):
        lo = datetime.date(p["year"], p["month"], 1)
        hi = datetime.date(p["year"] + p["month"] // 12,
                           p["month"] % 12 + 1, 1)
        promo = total = Decimal(0)
        for l in li:
            for pt in part:
                if l["l_partkey"] == pt["p_partkey"] and (
                        lo - EPOCH).days <= l["l_shipdate"] < (hi - EPOCH).days:
                    total += _revenue(l)
                    if pt["p_type"].startswith("PROMO"):
                        promo += _revenue(l)
        want = None if not total else format(
            (Decimal("100.00") * promo / total).quantize(
                Decimal("1e-10"), ROUND_HALF_UP), "f")
        assert cls.answer(state, p) == [(want,)]
        shares.add(want)
        assert "from lineitem, part where l_partkey = p_partkey" in cls.sql(p)
        assert "+ interval '1' month" in cls.sql(p)
    # months with promotional revenue, months with none, not all alike
    assert len(shares - {None, "0.0000000000"}) >= 3


def test_q19_oracle_against_a_nested_loop(small):
    cls = run_py.load_module("classes", "q19")
    state = cls.prepare(small)
    li, part = _rows(small["LINEITEM"]), _rows(small["PART"])
    answered = 0
    # few rows: draw brands until some parameter sets select something
    for p in _params(cls, 200):
        total, n = Decimal(0), 0
        for l in li:
            for pt in part:
                if l["l_partkey"] != pt["p_partkey"]:
                    continue
                qty = Decimal(l["l_quantity"]).scaleb(-2)
                if any(pt["p_brand"] == p["brand"][k]
                       and pt["p_container"] in containers
                       and p["quantity"][k] <= qty <= p["quantity"][k] + 10
                       and 1 <= pt["p_size"] <= size
                       and l["l_shipmode"] in ("AIR", "AIR REG")
                       and l["l_shipinstruct"] == "DELIVER IN PERSON"
                       for k, (containers, size) in enumerate(cls.BRANCHES)):
                    total += _revenue(l)
                    n += 1
        want = format(total.quantize(Decimal("1e-4")), "f") if n else None
        assert cls.answer(state, p) == [(want,)]
        answered += n > 0
    assert answered >= 3
    sql = cls.sql(_params(cls, 1)[0])
    assert sql.count("p_partkey = l_partkey") == 3      # in every branch
    assert sql.count("l_shipmode in ('AIR', 'AIR REG')") == 3
    assert " from lineitem, part where " in sql


def test_table_distributions():
    part_mod, part = _table("PART", 0.05)
    li_mod, li = _table("LINEITEM", 0.05)
    n = len(part["p_partkey"])
    assert n == 10_000 and len(li["l_partkey"]) == 300_000
    assert (part["p_partkey"] == np.arange(1, n + 1)).all()
    assert len(part_mod.PART_TYPES) == 150 and len(part_mod.CONTAINERS) == 40
    assert sum(t.startswith("PROMO") for t in part_mod.PART_TYPES) == 25
    for col, values in (("p_brand", part_mod.BRANDS),
                        ("p_type", part_mod.PART_TYPES),
                        ("p_container", part_mod.CONTAINERS)):
        codes, names = part[col]
        assert names == values == sorted(values)
        share = np.bincount(codes, minlength=len(values)) / n
        assert share.min() > 0.4 / len(values)
        assert share.max() < 1.8 / len(values)
    assert set(np.unique(part["p_size"])) == set(range(1, 51))
    pk = li["l_partkey"]
    assert pk.min() == 1 and pk.max() == n
    assert abs(pk.mean() - (n + 1) / 2) < n / 100
    for col, values in (("l_shipmode", li_mod.SHIPMODES),
                        ("l_shipinstruct", li_mod.SHIPINSTRUCT)):
        codes, names = li[col]
        assert names == values == sorted(values)
        share = np.bincount(codes, minlength=len(values)) / len(codes)
        assert abs(share - 1 / len(values)).max() < 0.01
    assert "REG AIR" in li_mod.SHIPMODES and "AIR REG" not in li_mod.SHIPMODES
    ship = li["l_shipdate"]
    assert datetime.date(1992, 1, 2) <= EPOCH + datetime.timedelta(
        int(ship.min()))
    assert EPOCH + datetime.timedelta(int(ship.max())) \
        <= datetime.date(1998, 12, 31)
    # a column both generators make has the same values in both
    old_mod, old = _table("lineitem", 0.05)
    for col in ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate"):
        assert (old[col] == li[col]).all()
    _, old_part = _table("part", 0.05)
    for col in ("p_partkey", "p_size"):
        assert (old_part[col] == part[col]).all()
    assert (old_part["p_brand"][0] == part["p_brand"][0]).all()


def test_cell_rehearsed_on_the_cpu():
    """The whole cell at SF0.01 for three seconds: every warm-up and
    window answer equals the oracle's, every statement was one launch of
    a join-carrying device program, and the cell's metrics are found."""
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, "tpch1x1.partjoin")
    assert (cell["chips"], config["scale"], mix["clients"]) == (1, 1, 1)
    assert mix["mix"] == {"q14": 1, "q19": 1} and mix["pool_seed"] == 25
    run = run_py.run_cell(cell, config, mix, seed=2147483659, seconds=3.0,
                          trace=False, scale=0.01)
    assert run.records and all(r["ok"] for r in run.records)
    assert set(run.ms_by_class()) == {"q14", "q19"}
    assert run.rows == {"LINEITEM": 60_000, "PART": 2_000}
    wanted = {m["name"] for m in
              run_py.cell_metrics(bench, "per_layer", "tpch1x1.partjoin")}
    assert {"device_ms.q14", "device_ms.q19", "join_device_share",
            "program_ms.solo_join_agg_scalar", "q14_probe_roofline",
            "q19_probe_roofline", "span_self_ms.cop.join_build",
            "wire_ms", "device_idle_share"} <= wanted
    assert not {"device_ms.q6", "topn_pruned_share"} & wanted
    got = run_py.read_metrics(run, "layer_metrics", [
        m for m in bench["per_layer"]
        if m["name"] in ("join_device_share", "launches_per_stmt")])
    assert got["join_device_share"]["value"] == 100.0
    assert got["launches_per_stmt"]["value"] == 1.0
    assert run.sched_delta("join_regrows") == 0


share = run_py.load_module("layer_metrics", "join_device_share")


def _run(before, after, answered):
    records = [{"ok": True}] * answered
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1}, classes={},
               sched_before=before, sched_after=after, records=records)


ZERO = {"join_launches": 0, "join_shuffle_launches": 0,
        "join_host_fallbacks": 0}


@pytest.mark.parametrize("before,after,answered,want", [
    (ZERO, dict(ZERO, join_launches=40), 40, 100.0),
    (dict(ZERO, join_launches=16), dict(ZERO, join_launches=56), 40, 100.0),
    # a statement in four answered by something that is no join program
    (ZERO, dict(ZERO, join_launches=30), 40, 75.0),
    # a repartition join, or a host fallback, in the window: not proven
    (ZERO, dict(ZERO, join_launches=40, join_shuffle_launches=1), 40, 0.0),
    (ZERO, dict(ZERO, join_launches=40, join_host_fallbacks=2), 40, 0.0),
    # counters that started with the window
    ({}, dict(ZERO, join_launches=8), 8, 100.0),
    # nothing answered: nothing to read
    (ZERO, ZERO, 0, None),
    # a program without the counters (the parent): nothing, no KeyError
    ({"launches": 3}, {"launches": 90}, 40, None),
])
def test_join_device_share(before, after, answered, want):
    assert share.read(_run(before, after, answered)) == want


@pytest.mark.parametrize("name", ["q14", "q19"])
def test_bytes_read_counts_each_column_once(name):
    cls = run_py.load_module("classes", name)
    rows = {"LINEITEM": 1000, "PART": 10}
    width = {"LINEITEM": {c: 4 for c in cls.READS["LINEITEM"]},
             "PART": {c: 2 for c in cls.READS["PART"]}}
    assert cls.bytes_read(rows, width) == (
        1000 * 4 * len(cls.READS["LINEITEM"]) + 10 * 2 * len(cls.READS["PART"]))
    assert cls.POOL == 4
