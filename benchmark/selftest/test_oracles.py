"""Every class's oracle against a second, plain computation at SF0.01:
row by row in Python integers and ``Decimal``, no numpy tricks."""

import datetime
from decimal import ROUND_HALF_UP, Decimal

import numpy as np
import pytest

SCALE, SEED = 0.01, 5
EPOCH = datetime.date(1970, 1, 1)


@pytest.fixture(scope="module")
def data(run_py):
    out = {}
    for name in ("lineitem", "part", "bench_kv"):
        table = run_py.load_module("tables", name)
        out[name] = table.generate(SCALE, SEED, list(table.TYPES))
    return out


def rows_of(table: dict) -> list[dict]:
    cols = {}
    for c, v in table.items():
        if isinstance(v, tuple):
            codes, names = v
            cols[c] = [names[i] for i in codes.tolist()]
        else:
            cols[c] = v.tolist()
    n = len(next(iter(cols.values())))
    return [{c: cols[c][i] for c in cols} for i in range(n)]


def dec(raw: int, scale: int) -> Decimal:
    return Decimal(raw).scaleb(-scale)


def params(cls, n=6):
    rng = np.random.default_rng(11)
    return [cls.draw(rng) for _ in range(n)]


def test_lineitem_distributions(data):
    li = data["lineitem"]
    n = len(li["l_shipdate"])
    assert n == 60_000
    assert (np.diff(li["l_orderkey"]) >= 0).all()
    assert set(np.unique(li["l_quantity"])) == {q * 100 for q in range(1, 51)}
    assert set(np.unique(li["l_discount"])) == set(range(11))
    assert set(np.unique(li["l_tax"])) == set(range(9))
    ship, (flag, _), (status, _) = (li["l_shipdate"], li["l_returnflag"],
                                    li["l_linestatus"])
    current = (datetime.date(1995, 6, 17) - EPOCH).days
    assert ((status == 1) == (ship > current)).all()
    assert (flag[ship > current] == 1).all()        # not yet shipped: N
    assert 0.2 < (flag == 0).mean() < 0.3 and 0.2 < (flag == 2).mean() < 0.3


def test_q6(run_py, data):
    cls = run_py.load_module("classes", "q6")
    state = cls.prepare(data)
    rows = rows_of(data["lineitem"])
    for p in params(cls):
        lo = (datetime.date(p["year"], 1, 1) - EPOCH).days
        hi = (datetime.date(p["year"] + 1, 1, 1) - EPOCH).days
        total = sum(r["l_extendedprice"] * r["l_discount"] for r in rows
                    if lo <= r["l_shipdate"] < hi
                    and p["discount"] - 1 <= r["l_discount"] <= p["discount"] + 1
                    and r["l_quantity"] < p["quantity"] * 100)
        assert cls.answer(state, p) == [(str(dec(total, 4)),)]


def test_q1(run_py, data):
    cls = run_py.load_module("classes", "q1")
    state = cls.prepare(data)
    rows = rows_of(data["lineitem"])
    six = Decimal("0.000001")
    for p in params(cls, 3):
        cutoff = (datetime.date(1998, 12, 1) - EPOCH).days - p["delta"]
        groups: dict = {}
        for r in rows:
            if r["l_shipdate"] <= cutoff:
                groups.setdefault((r["l_returnflag"], r["l_linestatus"]),
                                  []).append(r)
        want = []
        for (flag, status), g in sorted(groups.items()):
            qty = sum(dec(r["l_quantity"], 2) for r in g)
            price = sum(dec(r["l_extendedprice"], 2) for r in g)
            disc = sum(dec(r["l_discount"], 2) for r in g)
            disc_price = sum(dec(r["l_extendedprice"], 2)
                             * (1 - dec(r["l_discount"], 2)) for r in g)
            charge = sum(dec(r["l_extendedprice"], 2)
                         * (1 - dec(r["l_discount"], 2))
                         * (1 + dec(r["l_tax"], 2)) for r in g)
            n = len(g)
            want.append((flag, status, str(qty), str(price),
                         str(disc_price.quantize(Decimal("0.0001"))),
                         str(charge.quantize(six)),
                         str((qty / n).quantize(six, ROUND_HALF_UP)),
                         str((price / n).quantize(six, ROUND_HALF_UP)),
                         str((disc / n).quantize(six, ROUND_HALF_UP)),
                         str(n)))
        assert cls.answer(state, p) == want


def test_topn(run_py, data):
    cls = run_py.load_module("classes", "topn")
    rows = rows_of(data["lineitem"])
    rows.sort(key=lambda r: (-r["l_extendedprice"], r["l_orderkey"]))
    want = [(str(r["l_orderkey"]), str(dec(r["l_extendedprice"], 2)))
            for r in rows[:cls.LIMIT]]
    assert cls.answer(cls.prepare(data), {}) == want


def test_part_agg(run_py, data):
    cls = run_py.load_module("classes", "part_agg")
    state = cls.prepare(data)
    rows = rows_of(data["part"])
    for p in params(cls):
        count: dict = {}
        for r in rows:
            if r["p_size"] > p["size"]:
                count[r["p_brand"]] = count.get(r["p_brand"], 0) + 1
        assert sorted(cls.answer(state, p)) == sorted(
            (b, str(n)) for b, n in count.items())


def test_kv_agg(run_py, data):
    cls = run_py.load_module("classes", "kv_agg")
    state = cls.prepare(data)
    rows = rows_of(data["bench_kv"])
    for p in params(cls):
        hit = [r["v"] for r in rows if r["grp"] < p["grp"]]
        assert cls.answer(state, p) == [(str(len(hit)), str(sum(hit)))]


def test_dec_text_and_avg_text():
    from harness import exact
    assert exact.dec_text(5, 2) == "0.05"
    assert exact.dec_text(-12345, 2) == "-123.45"
    assert exact.dec_text(7, 0) == "7"
    assert exact.avg_text(10, 3, 2) == "0.033333"       # 0.10 / 3
    assert exact.avg_text(5, 2000000, 2) == "0.000000"  # rounds down
    assert exact.avg_text(1, 2000000, 2) == "0.000000"
    assert exact.avg_text(3, 2000000, 2) == "0.000000"
    assert exact.avg_text(1, 20000, 2) == "0.000001"    # exactly half: up


def test_group_sums_is_exact_beyond_float53():
    from harness import exact
    keys = np.zeros(1000, np.int64)
    values = np.full(1000, (1 << 47) + 1, np.int64)
    assert exact.group_sums(keys, values, 1)[0] == 1000 * ((1 << 47) + 1)
