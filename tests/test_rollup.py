"""GROUP BY ... WITH ROLLUP -> Expand (grouping sets).

Reference analog: logical Expand
(pkg/planner/core/operator/logicalop/logical_expand.go:32) executed by the
engine Expand executor (unistore/cophandler/mpp.go:638); MySQL 8 ROLLUP +
GROUPING() semantics (https://dev.mysql.com/doc/refman/8.0/en/group-by-modifiers.html).

Differential strategy: sqlite has no ROLLUP, so the oracle is the UNION ALL
of the per-level GROUP BYs with rolled keys replaced by NULL — exactly the
grouping-sets definition.
"""

import sqlite3

import pytest

from tidb_tpu.session import Session


@pytest.fixture
def sess():
    s = Session()
    s.execute("CREATE TABLE t (a INT, b VARCHAR(10), v INT)")
    s.execute("INSERT INTO t VALUES (1,'x',10),(1,'y',20),(2,'x',30),"
              "(2,NULL,40),(NULL,'x',50),(1,'x',60)")
    return s


def _norm(rows):
    def key(r):
        return tuple((x is None, str(x)) for x in r)
    return sorted([tuple(float(x) if hasattr(x, "quantize") else x
                         for x in r) for r in rows], key=key)


def test_rollup_two_keys_vs_sqlite(sess):
    got = sess.execute(
        "SELECT a, b, SUM(v), COUNT(*) FROM t GROUP BY a, b WITH ROLLUP")
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE t (a INT, b TEXT, v INT)")
    con.executemany("INSERT INTO t VALUES (?,?,?)",
                    [(1, 'x', 10), (1, 'y', 20), (2, 'x', 30),
                     (2, None, 40), (None, 'x', 50), (1, 'x', 60)])
    exp = con.execute(
        "SELECT a, b, SUM(v), COUNT(*) FROM t GROUP BY a, b "
        "UNION ALL SELECT a, NULL, SUM(v), COUNT(*) FROM t GROUP BY a "
        "UNION ALL SELECT NULL, NULL, SUM(v), COUNT(*) FROM t").fetchall()
    assert _norm(got.rows) == _norm(exp)


def test_rollup_distinguishes_natural_null(sess):
    rows = _norm(sess.execute(
        "SELECT a, b, COUNT(*) FROM t GROUP BY a, b WITH ROLLUP").rows)
    # a=2 has a natural b-NULL group (count 1) AND a rollup subtotal
    # (count 2): both rows must exist separately
    two_null = [r for r in rows if r[0] == 2 and r[1] is None]
    assert sorted(c for _, _, c in two_null) == [1, 2]


def test_grouping_function(sess):
    got = sess.execute("SELECT a, b, GROUPING(a), GROUPING(b), "
                       "GROUPING(a,b) FROM t GROUP BY a, b WITH ROLLUP")
    rows = _norm(got.rows)
    # grand total: both rolled, bitmask a<<1 | b = 3
    gt = [r for r in rows if r[2] == 1]
    assert gt == [(None, None, 1, 1, 3)]
    # natural NULLs report GROUPING()=0
    nat = [r for r in rows if r[0] is None and r[2] == 0 and r[1] == 'x']
    assert len(nat) == 1
    for r in rows:
        assert r[4] == r[2] * 2 + r[3]


def test_grouping_requires_rollup(sess):
    from tidb_tpu.planner.build import PlanError
    with pytest.raises(PlanError):
        sess.execute("SELECT a, GROUPING(a) FROM t GROUP BY a")


def test_rollup_expand_visible_in_explain(sess):
    plan = "\n".join(r[0] for r in sess.execute(
        "EXPLAIN SELECT a, SUM(v) FROM t GROUP BY a WITH ROLLUP").rows)
    assert "Expand" in plan, plan
    assert "CopTask[agg]" in plan, plan    # fused device fragment


def test_rollup_having_order_limit(sess):
    got = sess.execute(
        "SELECT a, SUM(v) AS sv FROM t GROUP BY a WITH ROLLUP "
        "HAVING sv >= 70 ORDER BY sv DESC LIMIT 2")
    vals = [float(r[1]) for r in got.rows]
    assert vals == [210.0, 90.0]


def test_rollup_grouping_in_having(sess):
    got = sess.execute("SELECT a, SUM(v) FROM t GROUP BY a WITH ROLLUP "
                       "HAVING GROUPING(a) = 1")
    assert _norm(got.rows) == [(None, 210.0)]


def test_rollup_over_join_host_path(sess):
    sess.execute("CREATE TABLE u (a INT, w INT)")
    sess.execute("INSERT INTO u VALUES (1,100),(2,200)")
    got = sess.execute("SELECT t.a, SUM(u.w) FROM t JOIN u ON t.a=u.a "
                       "GROUP BY t.a WITH ROLLUP")
    assert _norm(got.rows) == [(1, 300.0), (2, 400.0), (None, 700.0)]


def test_rollup_distinct_agg_host_fallback(sess):
    got = sess.execute(
        "SELECT a, COUNT(DISTINCT b) FROM t GROUP BY a WITH ROLLUP")
    rows = _norm(got.rows)
    assert (None, 2) in rows          # grand total: distinct {x, y}
    assert (1, 2) in rows and (2, 1) in rows


def test_rollup_expression_key(sess):
    got = sess.execute("SELECT a+1, COUNT(*) FROM t GROUP BY a+1 WITH ROLLUP")
    rows = _norm(got.rows)
    assert (None, 6) in rows          # grand total over 6 rows


def test_rollup_single_key_dict_string(sess):
    got = sess.execute(
        "SELECT b, SUM(v) FROM t GROUP BY b WITH ROLLUP")
    rows = _norm(got.rows)
    assert (None, 210.0) in rows      # grand total
    assert ('x', 150.0) in rows and ('y', 20.0) in rows
    # natural b-NULL group and the grand total are distinct rows
    assert sorted(r[1] for r in rows if r[0] is None) == [40.0, 210.0]


def test_rollup_parse_error_without_rollup_word():
    from tidb_tpu.sql.parser import ParseError
    s = Session()
    s.execute("CREATE TABLE p (a INT)")
    with pytest.raises(ParseError):
        s.execute("SELECT a FROM p GROUP BY a WITH CUBE")


def test_rollup_level_by_level_states_match(sess, monkeypatch):
    """The TPU per-level Expand aggregation (copr/exec.py agg_states)
    must produce identical results to the fused materialized expand:
    the statement's program over the CPU mesh, lowered as for a TPU,
    aggregates level by level.  (Grouped by the dictionary column: a
    DENSE aggregation, the only strategy that has the form.)"""
    from tidb_tpu.compilecache import compile_cache
    from tidb_tpu.copr import exec as X
    from tidb_tpu.parallel import spmd
    q = ("SELECT b, COUNT(*), SUM(v), MIN(v), MAX(v) FROM t "
         "GROUP BY b WITH ROLLUP")

    def norm(rows):
        return sorted((tuple((x is None, x) for x in r) for r in rows))
    sess.execute("set global tidb_tpu_result_cache_entries = 0")
    want = norm(sess.execute(q).rows)               # the host engine's
    sess.domain.client._platform = lambda: "tpu"    # the device path
    forms = []
    monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
    monkeypatch.setattr(
        X, "_expand_level_states",
        lambda *a, real=X._expand_level_states:
        forms.append("levels") or real(*a))
    # no program object and no executable an earlier statement traced
    # for the CPU
    spmd._cached.cache_clear()
    compile_cache().clear_pool()
    try:
        got = norm(Session(sess.domain).execute(q).rows)
    finally:
        spmd._cached.cache_clear()
        compile_cache().clear_pool()
    assert got == want and forms == ["levels"]
