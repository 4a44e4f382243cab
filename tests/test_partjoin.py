"""TPC-H's part-lineitem queries, Q14 and Q19 in the spec's own text,
against the benchmark's plain references (``benchmark/classes/q14.py``,
``q19.py``: numpy and ``Decimal`` arithmetic that imports nothing of the
program), and the lookup join's edge cases against a nested loop.

The tolerance is equality: the answers are DECIMAL text."""

import importlib.util
import os
import sys

import numpy as np
import pytest

from tidb_tpu.session import Domain, Session
from tidb_tpu.session.catalog import TableInfo

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SCALE, SEED = 0.05, 2147483659        # 300,000 x 10,000 rows


def _bench(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, as the harness loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)       # harness.exact
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"pj_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _broadcast_cap_put_back(monkeypatch):
    """SET tidb_tpu_broadcast_build_max_rows moves the planner's module
    value for the whole process, and its -1 means "leave it": a test
    that SETs it would hand its value to every later test of the worker."""
    from tidb_tpu.executor import plan
    monkeypatch.setattr(plan, "BROADCAST_BUILD_MAX_ROWS",
                        plan.BROADCAST_BUILD_MAX_ROWS)


@pytest.fixture(scope="module")
def tpch():
    """(session, {class: (module, oracle state)}): LINEITEM and PART from
    the benchmark's generators, registered as ``lineitem`` and ``part``,
    ``lineitem`` ANALYZEd as the configuration does."""
    run_py = _bench("", "run")          # the harness's own column wrapper
    tables = {"lineitem": _bench("tables", "LINEITEM"),
              "part": _bench("tables", "PART")}
    data = {name: t.generate(SCALE, SEED, list(t.TYPES))
            for name, t in tables.items()}
    dom = Domain()
    for name, t in tables.items():
        valid = np.ones(t.rows(SCALE), bool)
        cols = [run_py._column(t.TYPES[c], v, valid)
                for c, v in data[name].items()]
        info = TableInfo(name, list(data[name]), [c.dtype for c in cols])
        info.register_columns(cols)
        dom.catalog.create_table("test", info)
    sess = Session(dom)
    sess.execute("analyze table lineitem")
    oracle_data = {"LINEITEM": data["lineitem"], "PART": data["part"]}
    classes = {}
    for name in ("q14", "q19"):
        mod = _bench("classes", name)
        classes[name] = (mod, mod.prepare(oracle_data))
    return sess, classes


def _params(mod, n=6, seed=25):
    rng = np.random.default_rng(seed)
    return [mod.draw(rng) for _ in range(n)]


def _text(rows):
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


def _plan(sess, sql):
    return "\n".join(r[0] for r in sess.execute("explain " + sql).rows)


def _swap_from(sql):
    assert "from lineitem, part" in sql
    return sql.replace("from lineitem, part", "from part, lineitem")


@pytest.mark.parametrize("name", ["q14", "q19"])
@pytest.mark.parametrize("swapped", [False, True], ids=["spec", "part-first"])
def test_spec_text_equals_the_reference(tpch, name, swapped):
    """Both FROM orders: the reference's text, `lineitem` probes and
    `part` is broadcast-built."""
    sess, classes = tpch
    mod, state = classes[name]
    answered = 0
    for p in _params(mod):
        sql = _swap_from(mod.sql(p)) if swapped else mod.sql(p)
        want = mod.answer(state, p)
        assert _text(sess.execute(sql).rows) == want, sql
        answered += want[0][0] is not None
        plan = _plan(sess, sql)
        assert "probe=lineitem broadcast-build" in plan, plan
        assert "table=part" in plan and "ShuffleJoin" not in plan, plan
    assert answered, "every drawn parameter set gave NULL: nothing compared"


@pytest.mark.parametrize("name", ["q14", "q19"])
def test_lineitem_over_the_broadcast_cap_still_probes(tpch, name):
    """`lineitem` larger than a broadcast build may be: the unique side
    still builds, no repartition join is planned, the answer holds."""
    sess, classes = tpch
    mod, state = classes[name]
    p = _params(mod, 1)[0]
    sess.execute("set global tidb_tpu_broadcast_build_max_rows = 50000")
    try:
        for sql in (mod.sql(p), _swap_from(mod.sql(p))):
            plan = _plan(sess, sql)
            assert "probe=lineitem broadcast-build" in plan, plan
            assert "ShuffleJoin" not in plan, plan
            assert _text(sess.execute(sql).rows) == mod.answer(state, p)
    finally:
        sess.execute("set global tidb_tpu_broadcast_build_max_rows = -1")


def test_table_names_compare_without_case():
    """The spec's schema writes LINEITEM, its queries `lineitem`."""
    sess = Session()
    sess.execute("create table PART (p_partkey bigint, p_size bigint)")
    sess.execute("insert into PART values (1, 7), (2, 9)")
    assert sess.execute("select p_size from part where p_partkey = 2"
                        ).rows == [(9,)]
    assert sess.execute("select Part.p_size from Part order by 1"
                        ).rows == [(7,), (9,)]
    with pytest.raises(Exception, match="exists"):
        sess.execute("create table part (a bigint)")
    sess.execute("drop table part")
    with pytest.raises(Exception, match="doesn't exist"):
        sess.execute("select * from PART")


# --------------------------------------------------------------------- #
# the lookup join's edge cases, against a nested loop
# --------------------------------------------------------------------- #

FACT = [(1, 10), (2, 20), (3, 30), (None, 40), (7, 50), (-5, 60),
        (1000, 70), (2, 80), (5, 90), (6, 100)]


def _edge_session(dim_rows, dim_name="dim"):
    sess = Session()
    sess.execute("create table fact (k bigint, v bigint)")
    sess.execute("insert into fact values " + ", ".join(
        f"({'null' if k is None else k}, {v})" for k, v in FACT))
    sess.execute(f"create table {dim_name} (k bigint, w bigint, s bigint)")
    sess.execute(f"insert into {dim_name} values " + ", ".join(
        "(" + ", ".join("null" if x is None else str(x) for x in r) + ")"
        for r in dim_rows))
    return sess


def _nested_loop(dim_rows, keep=lambda k, w, s: True):
    out = []
    for fk, fv in FACT:
        for k, w, s in dim_rows:
            if fk is not None and k is not None and fk == k \
                    and keep(k, w, s):
                out.append((fv, w, s))
    return sorted(out, key=repr)


def _join_build_spans(sess):
    return [sp.attrs for sp in sess.last_trace.spans
            if sp.name == "cop.join_build"]


DENSE_DIM = [(k, 100 + k, None if k == 3 else k * k) for k in range(1, 9)]
# a range no table can span (copr/joinbuild.build_form): the sorted form
SPARSE_DIM = [(k, 100 + k, k + 1) for k in (2, 5, 1000, 70_000,
                                             9_000_000_000)]
DUP_DIM = DENSE_DIM + [(2, 777, 4), (5, 778, None)]


@pytest.mark.parametrize("dim,where,keep,shape", [
    # an unfiltered dense key: direct addressing, every key present
    (DENSE_DIM, "", None, {"unique": True, "dense": True}),
    # a filtered build: keys absent from the range, NULL build values
    (DENSE_DIM, " and w <> 102 and w < 107",
     lambda k, w, s: w != 102 and w < 107, {"unique": True, "dense": True}),
    # a key that is no dense range: the sorted-search form, and agrees
    (SPARSE_DIM, "", None, {"unique": True, "dense": False}),
    # a duplicate build key: the expanding (multimatch) join, and agrees
    (DUP_DIM, "", None, {"unique": False, "dense": False}),
], ids=["dense", "filtered-absent-keys", "sparse-sorted", "duplicate-keys"])
def test_lookup_join_edge_cases(dim, where, keep, shape):
    """NULL probe keys, a probe key below and one above the build's
    range, NULL build values: each form equals the nested loop."""
    sess = _edge_session(dim)
    sql = ("select v, w, s from fact, dim where fact.k = dim.k" + where)
    want = _nested_loop(dim, keep or (lambda k, w, s: True))
    plan = _plan(sess, sql)
    assert "CopJoinTask" in plan and "probe=fact" in plan, plan
    assert sorted(sess.execute(sql).rows, key=repr) == want
    (attrs,) = _join_build_spans(sess)
    assert {k: attrs[k] for k in shape} == shape
    assert attrs["rows"] == len([r for r in dim if keep is None
                                 or keep(*r)]) and not attrs["cached"]
    # the aggregate over the join, and the prepared build kept with the
    # snapshot: the repeat's span is a lookup
    total = sess.execute("select sum(v * w), count(s) from fact, dim "
                         "where fact.k = dim.k" + where).rows
    assert total == [(sum(v * w for v, w, _ in want) if want else None,
                      sum(s is not None for _, _, s in want))]
    sess.execute(sql)
    (again,) = _join_build_spans(sess)
    assert again["cached"] and again["rows"] == attrs["rows"]
    # a write to the build table is a new snapshot: prepared again
    sess.execute("insert into dim values (6000, 1, 1)")
    sess.execute(sql)
    assert not _join_build_spans(sess)[0]["cached"]


def test_join_counters_and_launch_span():
    """`/sched` counts join launches, host fallbacks and regrows, and a
    join-carrying launch says what it joined."""
    sess = _edge_session(DUP_DIM)
    sched = sess.domain.client._scheduler()
    before = sched.stats()
    for k in ("join_launches", "join_shuffle_launches",
              "join_host_fallbacks", "join_regrows"):
        assert k in before
    sql = "select sum(v), count(*) from fact, dim where fact.k = dim.k"
    assert sess.execute(sql).rows == [
        (sum(v for v, _, _ in _nested_loop(DUP_DIM)),
         len(_nested_loop(DUP_DIM)))]
    launch = [sp.attrs for sp in sess.last_trace.spans
              if sp.name == "sched.launch" and "join" in sp.attrs]
    assert launch and launch[-1]["join"] == "multimatch"
    assert launch[-1]["build_rows"] == len(DUP_DIM)
    assert "_join_" in launch[-1]["program"]
    after = sched.stats()
    assert after["join_launches"] > before["join_launches"]
    assert after["join_shuffle_launches"] == before["join_shuffle_launches"]
    # an empty build side is the one host fallback left
    sess.execute("delete from dim")
    assert sess.execute(sql).rows == [(None, 0)]
    assert sched.stats()["join_host_fallbacks"] \
        == before["join_host_fallbacks"] + 1


def test_unique_launch_names_its_join():
    sess = _edge_session(DENSE_DIM)
    sess.execute("select sum(v * w) from fact, dim where fact.k = dim.k")
    (launch,) = [sp.attrs for sp in sess.last_trace.spans
                 if sp.name == "sched.launch" and "join" in sp.attrs]
    assert launch["join"] == "unique" and launch["build_rows"] == 8
    assert launch["program"].startswith("cop_solo_join_agg_")


def test_repartition_join_is_counted_apart():
    """With nothing broadcastable the join is the all_to_all program:
    `/sched` counts it as a shuffle launch, not as a lookup join."""
    sess = _edge_session(DUP_DIM)
    sched = sess.domain.client._scheduler()
    sess.execute("set global tidb_tpu_broadcast_build_max_rows = 0")
    try:
        sql = "select sum(v), count(*) from fact join dim on fact.k = dim.k"
        assert "CopShuffleJoin" in _plan(sess, sql)
        before = sched.stats()
        assert sess.execute(sql).rows == [
            (sum(v for v, _, _ in _nested_loop(DUP_DIM)),
             len(_nested_loop(DUP_DIM)))]
        after = sched.stats()
    finally:
        sess.execute("set global tidb_tpu_broadcast_build_max_rows = -1")
    assert after["join_shuffle_launches"] > before["join_shuffle_launches"]
    assert after["join_launches"] == before["join_launches"]
