"""Statistics subsystem: device-built ANALYZE, estimation, cost-based
access paths (reference: pkg/statistics + pkg/planner/cardinality)."""

import numpy as np
import pytest

from tidb_tpu.session.session import Domain, Session
from tidb_tpu.stats.build import build_column_stats, sortable_f64
from tidb_tpu.stats.histogram import Histogram
from tidb_tpu.stats.sketch import FMSketch, TopN


def make_session():
    return Session(Domain())


def test_kernel_counts_ndv_nulls(rng):
    x = rng.integers(0, 1000, size=5000)
    valid = rng.random(5000) > 0.1
    out = build_column_stats(x.astype(np.int64), valid)
    assert int(out["count"]) == int(valid.sum())
    assert int(out["null_count"]) == int((~valid).sum())
    assert int(out["ndv"]) == len(np.unique(x[valid]))


def test_kernel_topn_exact(rng):
    # skewed: value 7 appears 3000 times, rest uniform
    x = np.concatenate([np.full(3000, 7), rng.integers(100, 200, 2000)])
    out = build_column_stats(x.astype(np.int64), np.ones(len(x), bool))
    top = dict(zip(out["top_vals"].tolist(), out["top_counts"].tolist()))
    assert top[7] == 3000


def test_histogram_range_estimates(rng):
    x = rng.integers(0, 10000, size=20000).astype(np.int64)
    out = build_column_stats(x, np.ones(len(x), bool))
    h = Histogram(out["bounds"], out["cum_counts"], out["repeats"],
                  ndv=int(out["ndv"]))
    true_lt = int((x < 2500).sum())
    est = h.less_row_count(2500)
    assert abs(est - true_lt) / len(x) < 0.02
    true_rng = int(((x >= 1000) & (x <= 3000)).sum())
    est = h.range_row_count(1000, True, 3000, True)
    assert abs(est - true_rng) / len(x) < 0.03


def test_float_encoding_order(rng):
    a = rng.normal(size=1000) * 100
    enc = sortable_f64(a)
    assert np.array_equal(np.argsort(enc, kind="stable"),
                          np.argsort(a, kind="stable"))


def test_fmsketch_ndv(rng):
    x = rng.integers(0, 50000, size=100000).astype(np.int64)
    out = build_column_stats(x, np.ones(len(x), bool))
    est = FMSketch(out["kmv"].astype(np.uint64)).ndv()
    true = len(np.unique(x))
    assert abs(est - true) / true < 0.35   # KMV with k=64


def test_analyze_and_show(rng):
    s = make_session()
    s.execute("create table t (a bigint, b double, c varchar(10))")
    vals = ",".join(f"({i % 7}, {i * 0.5}, 'v{i % 3}')" for i in range(500))
    s.execute(f"insert into t values {vals}")
    s.execute("analyze table t")
    meta = s.must_query("show stats_meta")
    assert ("test", "t", 0, 500) in meta
    hist = s.must_query("show stats_histograms")
    row = [r for r in hist if r[2] == "a"][0]
    assert row[3] == 7          # ndv of a
    topn = s.must_query("show stats_topn")
    assert any(r[2] == "a" for r in topn)


def test_selectivity_drives_index_choice(rng):
    """After ANALYZE, a non-selective predicate should NOT use the index
    (full device scan is cheaper than 50% random lookups)."""
    from tidb_tpu.planner.ranger import choose_index
    s = make_session()
    s.execute("create table t (a bigint, b bigint)")
    rows = ",".join(f"({i % 2}, {i})" for i in range(2000))
    s.execute(f"insert into t values {rows}")
    s.execute("create index ia on t (a)")
    s.execute("analyze table t")

    from tidb_tpu.planner.build import build_query
    from tidb_tpu.planner.logical import DataSource
    from tidb_tpu.planner.optimize import optimize_plan
    from tidb_tpu.planner.ranger import apply_index_paths, LogicalIndexScan
    from tidb_tpu.sql.parser import parse_sql

    def planned_access(sql):
        built = build_query(parse_sql(sql)[0], s.domain.catalog, s.db)
        plan = optimize_plan(built.plan)
        plan = apply_index_paths(plan, s.domain.stats)
        found = []
        stack = [plan]
        while stack:
            p = stack.pop()
            stack.extend(p.children)
            if isinstance(p, LogicalIndexScan):
                found.append(p)
        return found

    # a = 0 matches ~1000 of 2000 rows -> index rejected by cost
    assert planned_access("select b from t where a = 0") == []
    # correctness either way
    assert s.must_query("select count(*) from t where a = 0") == [(1000,)]


def test_selective_index_still_used(rng):
    s = make_session()
    s.execute("create table t (a bigint, b bigint)")
    rows = ",".join(f"({i}, {i})" for i in range(2000))
    s.execute(f"insert into t values {rows}")
    s.execute("create index ia on t (a)")
    s.execute("analyze table t")
    assert s.must_query("select b from t where a = 77") == [(77,)]


def test_auto_analyze_triggers(rng):
    s = make_session()
    s.execute("create table t (a bigint)")
    rows = ",".join(f"({i})" for i in range(1500))
    s.execute(f"insert into t values {rows}")
    # planning any select should auto-analyze (>= 1000 rows, no stats)
    s.execute("select count(*) from t where a > 10")
    assert s.domain.stats.get(s.domain.catalog.get_table("test", "t")) is not None


def test_topn_merge_and_fms_merge():
    t1 = TopN({1: 10, 2: 5})
    t2 = TopN({2: 7, 3: 1})
    m = t1.merge(t2)
    assert m.values[2] == 12
    f1 = FMSketch(np.array([1, 5, 9], np.uint64))
    f2 = FMSketch(np.array([5, 7], np.uint64))
    assert f1.merge(f2).ndv() == 4


def test_auto_analyze_feeds_sort_agg_capacity():
    """Consumer half of auto-analyze (VERDICT r2 #8): fresh column NDV
    seeds the SORT-strategy group-table capacity, so the client skips the
    grow-from-default regrow; before ANALYZE the capacity is the planner
    default (0 -> client default)."""
    from tidb_tpu.copr import dag as D
    from tidb_tpu.session import Domain, Session

    s = Session(Domain())
    s.execute("create table nd (k bigint not null, v bigint)")
    s.execute("insert into nd values " +
              ",".join(f"({i % 1500}, {i})" for i in range(3000)))

    def sort_capacity(sess):
        built, phys = sess._plan_select(
            __import__("tidb_tpu.sql.parser", fromlist=["parse_sql"])
            .parse_sql("select k, count(*) from nd group by k")[0])
        stack = [phys]
        while stack:
            op = stack.pop()
            dag = getattr(op, "dag", None)
            if isinstance(dag, D.Aggregation) \
                    and dag.strategy == D.GroupStrategy.SORT:
                return dag.group_capacity
            stack.extend(getattr(op, "children", []))
        raise AssertionError("no SORT aggregation in plan")

    s.domain.stats.auto_analyze_enabled = False
    assert sort_capacity(s) == 0          # no stats: client default path
    s.domain.stats.analyze_table(s.domain.catalog.get_table("test", "nd"))
    cap = sort_capacity(s)
    assert cap >= 1500                    # NDV(k)=1500 with headroom
    assert cap <= 8192


def test_ndv_capacity_not_seeded_through_projection():
    """Review r3: group keys bound over a Projection reference the
    PROJECTED schema — seeding from the scan schema picked the wrong
    column's NDV.  Such plans must leave capacity to the client regrow."""
    from tidb_tpu.copr import dag as D
    from tidb_tpu.session import Domain, Session
    from tidb_tpu.sql.parser import parse_sql

    s = Session(Domain())
    s.execute("create table pj (a bigint not null, b bigint not null)")
    s.execute("insert into pj values " +
              ",".join(f"({i}, {i % 3})" for i in range(1200)))
    s.domain.stats.analyze_table(s.domain.catalog.get_table("test", "pj"))

    built, phys = s._plan_select(
        parse_sql("select distinct b + 0 from pj where a >= 0")[0])
    stack = [phys]
    caps = []
    while stack:
        op = stack.pop()
        dag = getattr(op, "dag", None)
        if isinstance(dag, D.Aggregation) \
                and dag.strategy == D.GroupStrategy.SORT:
            caps.append(dag.group_capacity)
        stack.extend(getattr(op, "children", []))
    assert caps and all(c == 0 for c in caps), caps
    assert sorted(s.must_query("select distinct b + 0 from pj")) == \
        [(0,), (1,), (2,)]


def test_decimal_column_selectivity_scales_the_constant():
    """A DECIMAL column's histogram holds scaled ints; `l_quantity < 10`
    compares an unscaled integer literal.  Estimated against the raw
    histogram it matched no row (selectivity 1e-9), which made the
    60M-row table the smaller join input and sent chip_smoke's broadcast join
    to a shuffle with lineitem as its sorted build side."""
    from tidb_tpu.testing.tpch import TPCH_PLAN_QUERIES, tpch_plan_session
    s = tpch_plan_session(sf=0.01)
    n = s.must_query("select count(*) from lineitem")[0][0]
    true = s.must_query(
        "select count(*) from lineitem where l_quantity < 10")[0][0]
    from tidb_tpu.planner import cardinality as card
    seen = []
    orig = card.cond_selectivity

    def spy(stats, cond, ds):
        r = orig(stats, cond, ds)
        seen.append((str(cond), r))
        return r
    card.cond_selectivity = spy
    try:
        s.must_query("explain " + TPCH_PLAN_QUERIES[9])
    finally:
        card.cond_selectivity = orig
    sel = [r for c, r in seen if "l_quantity" in c]
    assert sel and all(abs(r - true / n) < 0.03 for r in sel), (sel, true / n)


@pytest.fixture(scope="module")
def flags():
    """A table whose columns are what a star schema's fact table filters
    on: a date-like range column, two low-NDV string flags, a quantity."""
    s = make_session()
    s.execute("create table f (d bigint, mode varchar(10), "
              "instr varchar(20), q bigint)")
    rng = np.random.default_rng(5)
    n = 20000
    modes = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
    instrs = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
    d, m, i, q = (rng.integers(8000, 10500, n), rng.integers(0, 7, n),
                  rng.integers(0, 4, n), rng.integers(1, 51, n))
    for at in range(0, n, 2000):
        s.execute("insert into f values " + ", ".join(
            f"({d[k]}, '{modes[m[k]]}', '{instrs[i[k]]}', {q[k]})"
            for k in range(at, at + 2000)))
    s.execute("analyze table f")
    return s, n


def _estimate(s, where):
    """The planner's estimate of the rows `where` leaves of `f`."""
    from tidb_tpu.planner import join_reorder
    seen = []
    orig = join_reorder.est_scan_rows

    def spy(stats, conds, ds):
        seen.append(orig(stats, conds, ds))
        return seen[-1]
    join_reorder.est_scan_rows = spy
    try:
        s.must_query("explain select count(*) from f, f g where f.q = g.q "
                     "and g.d = 8000 and " + where)
    finally:
        join_reorder.est_scan_rows = orig
    return max(seen)


@pytest.mark.parametrize("where,true_where", [
    # both ends of one column: one interval, not two independent halves
    ("f.d >= 9000 and f.d < 9030", None),
    ("f.d > 8100 and f.d <= 8200 and f.d >= 8150", "f.d >= 8150 and f.d <= 8200"),
    # an IN list sums its values; one the dictionary does not hold is none
    ("f.mode in ('AIR', 'AIR REG')", None),
    ("f.mode in ('AIR', 'SHIP', 'TRUCK')", None),
    # a flag's every value is counted exactly (no more values than TopN slots)
    ("f.instr = 'DELIVER IN PERSON'", None),
    ("f.instr = 'DELIVER IN PERSON' and f.mode in ('AIR', 'AIR REG')", None),
    ("f.instr = 'NO SUCH INSTRUCTION'", None)])
def test_filter_estimates_of_a_fact_table(flags, where, true_where):
    s, n = flags
    true = s.must_query("select count(*) from f where "
                        + (true_where or where))[0][0]
    est = _estimate(s, where)
    assert abs(est - true) <= max(0.2 * true, 0.005 * n), (est, true)


def test_equal_row_count_of_a_value_that_bounds_several_buckets():
    """Four values in 64 buckets: every bucket's bound repeats; the count
    of a value is its last bucket's repeat, not its first's."""
    x = np.repeat(np.arange(4), [3000, 1000, 5000, 1000]).astype(np.int64)
    out = build_column_stats(x, np.ones(len(x), bool))
    h = Histogram(out["bounds"], out["cum_counts"], out["repeats"],
                  ndv=int(out["ndv"]))
    for v, want in enumerate([3000, 1000, 5000, 1000]):
        assert abs(h.equal_row_count(v) - want) <= len(x) / 64 + 1, v
    assert h.equal_row_count(7) == 0.0
