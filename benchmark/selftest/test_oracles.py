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


# --------------------------------------------------------------------- #
# Q3's pass over `lineitem`: a chunk sums over the range of order rows
# its live lines touch (PR 46), whatever order the rows are stored in
# --------------------------------------------------------------------- #

Q3_SEED = 2147483659
Q3_PARAMS = [{"segment": "BUILDING", "day": 15},
             {"segment": "AUTOMOBILE", "day": 1},
             {"segment": "MACHINERY", "day": 31},
             {"segment": "HOUSEHOLD", "day": 9}]


@pytest.fixture(scope="module")
def orders_data(run_py):
    """CUSTOMER, ORDERS and LineItem at SF0.01: 1,500 x 15,000 x about
    60,000 rows."""
    out = {}
    for name in ("CUSTOMER", "ORDERS", "LineItem"):
        table = run_py.load_module("tables", name)
        out[name] = table.generate(SCALE, Q3_SEED, list(table.TYPES))
    return out


def permuted(table: dict, seed: int) -> dict:
    """The table's rows in an order drawn from the seed."""
    n = len(next(iter(table.values())))
    order = np.random.default_rng(seed).permutation(n)
    return {c: (v[0][order], v[1]) if isinstance(v, tuple) else v[order]
            for c, v in table.items()}


def q3_brute(data: dict, p: dict):
    """(the first ten rows, whether the first eleven groups tie on
    (revenue, o_orderdate)): a ``dict`` of ``int`` sums over the joined
    rows, a row at a time."""
    date = (datetime.date(1995, 3, p["day"]) - EPOCH).days
    segment = {r["c_custkey"] for r in rows_of(data["CUSTOMER"])
               if r["c_mktsegment"] == p["segment"]}
    order = {r["o_orderkey"]: r for r in rows_of(data["ORDERS"])
             if r["o_custkey"] in segment and r["o_orderdate"] < date}
    revenue: dict = {}
    for r in rows_of(data["LineItem"]):
        if r["l_shipdate"] > date and r["l_orderkey"] in order:
            revenue[r["l_orderkey"]] = revenue.get(r["l_orderkey"], 0) \
                + r["l_extendedprice"] * (100 - r["l_discount"])
    first = sorted(revenue, key=lambda k: (-revenue[k],
                                           order[k]["o_orderdate"]))[:11]
    pairs = [(revenue[k], order[k]["o_orderdate"]) for k in first]
    rows = [(str(k), str(dec(revenue[k], 4)),
             str(EPOCH + datetime.timedelta(order[k]["o_orderdate"])),
             str(order[k]["o_shippriority"])) for k in first[:10]]
    return rows, len(set(pairs)) != len(pairs)


@pytest.mark.parametrize("chunk_rows", [1 << 21, 4096, 4])
@pytest.mark.parametrize("stored", ["by_key", "permuted"])
@pytest.mark.parametrize("p", Q3_PARAMS,
                         ids=lambda p: f"{p['segment']}-{p['day']}")
def test_q3_ranked_against_a_dict_of_int_sums(run_py, orders_data,
                                              monkeypatch, p, stored,
                                              chunk_rows):
    from harness import exact
    data = orders_data if stored == "by_key" else dict(
        orders_data, LineItem=permuted(orders_data["LineItem"], 7))
    monkeypatch.setattr(exact, "CHUNK_ROWS", chunk_rows)
    widths = []                 # the order rows each chunk summed over
    group_sums = exact.group_sums

    def recorded(keys, values, n):
        widths.append(n)
        assert len(keys) and keys.min() == 0 and keys.max() == n - 1
        return group_sums(keys, values, n)
    monkeypatch.setattr(exact, "group_sums", recorded)
    cls = run_py.load_module("classes", "q3")
    rows, tie = cls._ranked(cls.prepare(data), p)
    assert (rows, tie) == q3_brute(orders_data, p)
    assert len(rows) == 10 and not tie
    lines, orders = len(data["LineItem"]["l_shipdate"]), 15_000
    chunks = -(-lines // chunk_rows)
    if chunk_rows == 4:
        # a chunk with no live line is skipped, a chunk of one order sums
        # over that one row
        assert len(widths) < chunks and min(widths) == 1
    else:
        assert len(widths) == chunks
    if stored == "by_key":      # an order has a line or more: c rows, <= c orders
        assert max(widths) <= min(chunk_rows, orders)
    elif chunk_rows > 4:        # rows anywhere: the range is wide, not wrong
        assert max(widths) > orders // 2


def test_q3_ranked_sees_a_tie_among_the_first_eleven(run_py, monkeypatch):
    """Made-up tables: two orders of one revenue and one date at the top,
    their lines in chunks of their own and a dead chunk between them."""
    from harness import exact
    monkeypatch.setattr(exact, "CHUNK_ROWS", 2)
    day = (datetime.date(1995, 3, 1) - EPOCH).days
    data = {
        "CUSTOMER": {"c_custkey": np.array([1, 2]),
                     "c_mktsegment": (np.array([0, 1], np.int32),
                                      ["BUILDING", "MACHINERY"])},
        "ORDERS": {"o_orderkey": np.array([1, 2, 3, 33]),
                   "o_custkey": np.array([1, 1, 2, 1]),
                   "o_orderdate": np.array([day, day, day, day]),
                   "o_shippriority": np.zeros(4, np.int64)},
        "LineItem": {
            "l_orderkey": np.array([1, 1, 3, 3, 2, 33]),
            "l_extendedprice": np.array([600, 400, 900, 900, 1000, 500]),
            "l_discount": np.array([0, 0, 0, 0, 0, 10]),
            "l_shipdate": np.full(6, day + 30)}}
    p = {"segment": "BUILDING", "day": 15}
    cls = run_py.load_module("classes", "q3")
    rows, tie = cls._ranked(cls.prepare(data), p)
    assert tie and q3_brute(data, p)[1]
    assert sorted(rows) == sorted(q3_brute(data, p)[0]) == [("1", "10.0000", "1995-03-01", "0"),
                            ("2", "10.0000", "1995-03-01", "0"),
                            ("33", "4.5000", "1995-03-01", "0")]
