"""The statement memo (``tidb_tpu/session/stmt_memo.py``): what it keeps
of a text, when it keeps an AST, and what it could get wrong.

A text is a ``miss`` at first sight (one parse, the statement's own AST),
a ``bypass`` at the second (one parse, the memo's own AST from here on)
and a ``hit`` from the third on (no parse).  The kept AST is shared by
every session and nobody writes to it: the tests below take a structural
fingerprint of it before and after everything a session does with it.
Answers and counts only: nothing here is a device number."""

import importlib.util
import os
import sys
import threading

import numpy as np
import pytest

from tests.helpers import memo_outcomes as outcomes
from tidb_tpu.privilege import PrivilegeError
from tidb_tpu.session import Domain, Session
from tidb_tpu.session.stmt_memo import StmtMemo, describe
from tidb_tpu.sql.parser import ParseError, parse_sql
from tidb_tpu.utils.stmtsummary import StmtSummary, normalize_sql

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CLASSES = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, "classes"))
                 if f.endswith(".py"))
SCALE, SEED = 0.002, 2147483659


def _bench(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, as the harness loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)       # harness.exact
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"sm_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fingerprint(node) -> str:
    """Every field of every node (the dataclasses' own ``repr``) and the
    span the parser stamped on the statement."""
    return f"{node!r} @ {getattr(node, 'text_span', None)}"


def kept(dom, sql: str) -> tuple:
    """The ASTs the memo keeps for ``sql`` (None: not a query)."""
    return dom.stmt_memo._lru[sql][1]


def moved(before: dict) -> dict:
    return {o: n - before[o] for o, n in outcomes().items()
            if n != before[o]}


# --------------------------------------------------------------------- #
# the memo by itself
# --------------------------------------------------------------------- #

def test_miss_then_the_memos_own_parse_then_hits():
    memo, sql = StmtMemo(), "select a from t where b = 3"
    first, = memo.resolve(sql)
    assert (first.outcome, first.shared) == ("miss", False)
    assert sql in memo and memo._lru[sql][1] is None     # no AST kept yet
    second, = memo.resolve(sql)
    # the text came back: parsed once more, and that parse is kept
    assert (second.outcome, second.shared) == ("bypass", True)
    assert second.stmt is not first.stmt
    third, = memo.resolve(sql)
    fourth, = memo.resolve(sql)
    assert (third.outcome, third.shared) == ("hit", True)
    assert third.stmt is second.stmt is fourth.stmt
    assert first.rec == second.rec == third.rec
    assert first.rec.text == sql
    assert first.rec.digest == first.rec.bind_digest == normalize_sql(sql)
    assert first.rec.tables == ((None, "t"),)
    assert fingerprint(third.stmt) == fingerprint(parse_sql(sql)[0])


def test_a_statement_that_is_no_query_is_parsed_afresh_every_time():
    memo, sql = StmtMemo(), "insert into t values (1, 2)"
    seen = [memo.resolve(sql)[0] for _ in range(4)]
    assert [r.outcome for r in seen] == ["miss"] + ["bypass"] * 3
    assert not any(r.shared for r in seen)
    assert len({id(r.stmt) for r in seen}) == 4
    assert seen[0].rec.tables is None
    assert memo._lru[sql][1] == (None,)


def test_lru_eviction():
    memo = StmtMemo(capacity=2)
    a, b, c = ("select 1 from ta", "select 1 from tb", "select 1 from tc")
    for sql in (a, a, b, b):
        memo.resolve(sql)
    assert memo.resolve(a)[0].outcome == "hit"      # a is the newer now
    memo.resolve(c)                                 # b leaves
    assert len(memo) == 2 and a in memo and c in memo and b not in memo
    assert memo.resolve(b)[0].outcome == "miss"     # and a left for it
    assert a not in memo


def test_a_text_over_the_cap_is_never_kept():
    memo = StmtMemo(max_text=40)
    short = "select a from t where a in (1, 2)"
    long = "insert into t values " + ", ".join(f"({i})" for i in range(40))
    for _ in range(3):
        r, = memo.resolve(long)
        assert (r.outcome, r.shared) == ("bypass", False)
        assert r.rec.digest == normalize_sql(long)
    assert long not in memo and len(memo) == 0
    assert [memo.resolve(short)[0].outcome for _ in range(3)] \
        == ["miss", "bypass", "hit"]


def test_a_text_that_does_not_parse_is_not_kept():
    memo = StmtMemo()
    for _ in range(2):
        with pytest.raises(ParseError):
            memo.resolve("select from where")
    assert len(memo) == 0


PACKET = ("select a from t where s = 'x;y' ;\n"
          "  insert into t values (1, 'p;q');"
          "explain select b from u /* ; */ where b < 2 ;; "
          "select max(a) from t")


def test_multi_statement_packet_each_statement_its_text_and_span():
    memo = StmtMemo()
    fresh = parse_sql(PACKET)
    first = memo.resolve(PACKET)
    assert [r.rec.text for r in first] == [
        "select a from t where s = 'x;y'",
        "insert into t values (1, 'p;q')",
        "explain select b from u /* ; */ where b < 2",
        "select max(a) from t"]
    for r, stmt in zip(first, fresh):
        lo, hi = stmt.text_span
        assert r.rec.text == PACKET[lo:hi].strip()
        assert r.rec.digest == normalize_sql(r.rec.text)
        # a statement's own text parses to the statement
        assert repr(parse_sql(r.rec.text)[0]) == repr(stmt)
    assert [r.rec.tables for r in first] == [
        ((None, "t"),), None, ((None, "u"),), ((None, "t"),)]
    memo.resolve(PACKET)
    third = memo.resolve(PACKET)
    # the queries are the memo's, the INSERT and the EXPLAIN fresh
    assert [(r.outcome, r.shared) for r in third] == [
        ("hit", True), ("bypass", False), ("bypass", False), ("hit", True)]
    assert [repr(r.stmt) for r in third] == [repr(s) for s in fresh]
    assert third[0].stmt is memo.resolve(PACKET)[0].stmt
    assert third[1].stmt is not memo.resolve(PACKET)[1].stmt


@pytest.mark.parametrize("prefix", ["explain", "EXPLAIN  ANALYZE", "trace",
                                    " Explain\n"])
def test_explain_and_trace_prefixes(prefix):
    inner = "select a, count(*) from t where b > 10 group by a"
    rec, = describe(f"{prefix} {inner}", parse_sql(f"{prefix} {inner}"))
    assert rec.text == f"{prefix} {inner}".strip()
    assert rec.digest == normalize_sql(rec.text)
    assert rec.bind_digest == normalize_sql(inner)      # a binding's digest
    assert rec.tables == ((None, "t"),)


# --------------------------------------------------------------------- #
# the benchmark's thirteen class texts and a sample of the suite's
# --------------------------------------------------------------------- #

def class_deployment(name: str):
    """(session, a statement of the class): the tables the class reads,
    from the benchmark's generators, loaded as ``run.py`` loads them."""
    run_py, cls = _bench("", "run"), _bench("classes", name)
    dom = Domain()
    sess = Session(dom)

    class Admin:
        query = staticmethod(lambda q: sess.execute(q).rows)

    for table in cls.READS:
        t = _bench("tables", table)
        run_py._load_table(dom, Admin, t,
                           t.generate(SCALE, SEED, list(t.TYPES)))
    return sess, cls.sql(cls.draw(np.random.default_rng(SEED)))


SUITE = {
    "filter": "select a, b from t where b > 2 and s <> 'x' order by a",
    "group": ("select s, count(*), sum(b) from t where a < 90 group by s "
              "having count(*) > 1 order by s"),
    "join": ("select t.a, u.d from t join u on t.a = u.a "
             "where u.d > 1 order by t.a, u.d"),
    "in_subquery": ("select a from t where a in (select a from u "
                    "where d > 2) order by a"),
    "exists": ("select a from t where exists (select 1 from u "
               "where u.a = t.a) order by a"),
    # the three the builder rewrites in place (planner/build.build_select)
    "apply_where": ("select a from t where b > (select avg(d) from u "
                    "where u.a = t.a) order by a"),
    "apply_item": ("select a, (select max(d) from u where u.a = t.a) "
                   "from t order by a"),
    "apply_order": ("select a from t order by "
                    "(select max(d) from u where u.a = t.a), a"),
    "scalar_subquery": "select a from t where b >= (select max(d) from u)",
    "cte": ("with w as (select a, sum(d) as sd from u group by a) "
            "select t.a, w.sd from t join w on t.a = w.a order by t.a"),
    "union": "select a from t union select a from u order by 1",
    "window": "select a, row_number() over (order by b, a) from t",
    "hinted": "select /*+ HASH_JOIN(u) */ t.a from t join u on t.a = u.a",
    "for_update": "select a from t where b = 2 for update",
}


def suite_deployment():
    sess = Session(Domain())
    sess.execute("create table t (a bigint primary key, b bigint, "
                 "s varchar(8))")
    sess.execute("create table u (a bigint, d bigint)")
    sess.execute("insert into t values " + ", ".join(
        f"({i}, {i % 5}, '{'xyz'[i % 3]}')" for i in range(40)))
    sess.execute("insert into u values " + ", ".join(
        f"({i % 25}, {i % 7})" for i in range(60)))
    return sess


def deployment(case: str):
    if case in SUITE:
        return suite_deployment(), SUITE[case]
    return class_deployment(case)


def summary(dom) -> dict:
    return {row[0]: row[1] for row in dom.stmt_summary.summary_rows()}


@pytest.mark.parametrize("case", CLASSES + sorted(SUITE))
def test_digest_is_normalize_sql_and_the_summary_reads_the_same(case):
    """The memo's digest is ``normalize_sql(text)``, and
    ``statements_summary`` files the statement under the same digest,
    the same number of times, as under a memo that never hits."""
    sess, sql = deployment(case)
    dom = sess.domain
    rows = [sess.execute(sql).rows for _ in range(4)]
    assert rows[0] == rows[1] == rows[2] == rows[3]
    rec, = dom.stmt_memo._lru[sql][0]
    assert rec.digest == normalize_sql(sql) and rec.text == sql
    with_memo = summary(dom)
    assert with_memo[rec.digest] == 4
    dom.stmt_summary, dom.stmt_memo = StmtSummary(), StmtMemo(capacity=0)
    before = outcomes()
    assert [sess.execute(sql).rows for _ in range(4)] == rows
    assert moved(before) == {"miss": 4}
    assert summary(dom) == {rec.digest: 4}
    assert sess.must_query(
        "select digest_text, exec_count from "
        "information_schema.statements_summary where exec_count = 4") \
        == [(rec.digest, 4)]


@pytest.mark.parametrize("case", CLASSES + sorted(SUITE))
def test_the_kept_ast_is_not_written_to(case):
    """The memo's AST reads the same before and after a plan-cache hit,
    a plan-cache miss (a row written, a column added, ANALYZE) and every
    execution; where the builder needs an AST it gets a parse of its
    own."""
    sess, sql = deployment(case)
    dom = sess.domain
    table = sorted({t for _db, t in dom.stmt_memo.resolve(sql)[0].rec.tables
                    })[0]
    dom.stmt_memo = StmtMemo()
    want = sess.execute(sql).rows
    sess.execute(sql)
    ast, = kept(dom, sql)
    before = fingerprint(ast)
    assert before == fingerprint(parse_sql(sql)[0])

    def again(outcome):
        seen = outcomes()
        rows = sess.execute(sql).rows
        assert moved(seen) == {outcome: 1}, (outcome, moved(seen))
        assert kept(dom, sql)[0] is ast and fingerprint(ast) == before
        return rows

    planned = dom.plan_cache.get(sql, sess.db, dom.sysvars,
                                 dom.catalog) is not None
    # a plan the cache holds: nothing is parsed; one it cannot hold (a
    # scalar subquery folded at plan time): parsed afresh every time
    steady = "hit" if planned else "bypass"
    assert again(steady) == want
    if case == "for_update":                # the locking read's own path
        sess.execute("begin")
        assert again(steady) == want
        sess.execute("commit")
    sess.execute(f"analyze table {table}")
    assert again(steady) == want
    row = {"bench_kv": "values (100000, 3, 7, null)",
           "t": "select a + 1000, b, s, null from t limit 1"}.get(
        table, f"select * from {table} limit 1")
    for write in (f"alter table {table} add column memo_extra bigint",
                  f"insert into {table} {row}"):
        sess.execute(write)
        again("bypass")                     # the plan cache missed
        assert again(steady) == again(steady)
    sess.execute(f"explain {sql}")
    assert fingerprint(ast) == before


# --------------------------------------------------------------------- #
# what a memo could get wrong
# --------------------------------------------------------------------- #

Q = "select b.v, sm.w from big b join small sm on b.k = sm.k"
HINTED = ("select /*+ MERGE_JOIN(sm) */ b.v, sm.w from big b "
          "join small sm on b.k = sm.k")


def _join_line(sess, q):
    plan = "\n".join(r[0] for r in sess.must_query("explain " + q))
    return next(ln.strip() for ln in plan.splitlines() if "Join" in ln)


@pytest.mark.parametrize("scope", ["global", "session"])
def test_a_binding_made_for_a_kept_text_applies_and_drops(scope):
    sess = Session(Domain())
    sess.execute("create table big (k bigint, v bigint)")
    sess.execute("create table small (k bigint, w bigint)")
    sess.execute("insert into big values "
                 + ",".join(f"({i % 50},{i})" for i in range(500)))
    sess.execute("insert into small values (3,30),(7,70)")
    base = [sorted(sess.must_query(Q)) for _ in range(4)][0]
    plain = [_join_line(sess, Q) for _ in range(3)][0]
    ast, = kept(sess.domain, Q)
    before = fingerprint(ast)
    assert "HostMergeJoin" not in plain and not ast.hints
    sess.execute(f"create {scope} binding for {Q} using {HINTED}")
    seen = outcomes()
    assert sorted(sess.must_query(Q)) == base
    assert moved(seen) == {"bypass": 1}     # hinted on a parse of its own
    assert "HostMergeJoin" in _join_line(sess, Q)
    assert not ast.hints and fingerprint(ast) == before
    sess.execute(f"drop {scope} binding for {Q}")
    seen = outcomes()
    assert sorted(sess.must_query(Q)) == base
    # the bound plan was never cached, the unbound one still is
    assert moved(seen) == {"hit": 1}
    assert _join_line(sess, Q) == plain
    assert kept(sess.domain, Q)[0] is ast and fingerprint(ast) == before


def test_revoke_takes_effect_on_a_kept_text():
    dom = Domain()
    root = Session(dom)
    root.execute("create user 'alice'@'%' identified by 'secret'")
    root.execute("create table t (a bigint)")
    root.execute("insert into t values (1),(2)")
    root.execute("grant select on test.t to 'alice'@'%'")
    alice = Session(dom, user="alice")
    sql = "select count(*) from t"
    for _ in range(4):
        assert alice.must_query(sql) == [(2,)]
    root.execute("revoke select on test.t from 'alice'@'%'")
    seen = outcomes()
    with pytest.raises(PrivilegeError):
        alice.execute(sql)
    assert moved(seen) == {"hit": 1}        # the list is kept, not the verdict
    assert root.must_query(sql) == [(2,)]
    root.execute("grant select on test.t to 'alice'@'%'")
    assert alice.must_query(sql) == [(2,)]


def test_use_between_two_executions_resolves_the_other_database():
    sess = Session(Domain())
    sess.execute("create database other")
    sess.execute("create table t (a bigint)")
    sess.execute("create table other.t (a bigint)")
    sess.execute("insert into t values (1)")
    sess.execute("insert into other.t values (10), (20)")
    sql = "select sum(a), count(*) from t"
    for _ in range(3):
        assert [tuple(map(int, r)) for r in sess.must_query(sql)] == [(1, 1)]
    sess.execute("use other")
    for _ in range(3):
        assert [tuple(map(int, r)) for r in sess.must_query(sql)] \
            == [(30, 2)]
    sess.execute("use test")
    assert [tuple(map(int, r)) for r in sess.must_query(sql)] == [(1, 1)]


def test_eight_sessions_share_three_kept_texts():
    sess = suite_deployment()
    dom = sess.domain
    # two whose plans the cache holds and one it cannot (a scalar
    # subquery folded at plan time: parsed afresh for the builder)
    texts = [SUITE["group"], "select count(*), sum(d) from u where d < 4",
             SUITE["scalar_subquery"]]
    want = [sess.execute(q).rows for q in texts]
    for q in texts:
        sess.execute(q)
    asts = [kept(dom, q)[0] for q in texts]
    before = [fingerprint(a) for a in asts]
    wrong, errors = [], []

    def stream(k):
        s = Session(dom)
        try:
            for i in range(200 * len(texts)):
                j = (i + k) % len(texts)
                if s.execute(texts[j]).rows != want[j]:
                    wrong.append((k, i, j))
        except Exception as e:      # noqa: BLE001 surfaced via assert
            errors.append(e)

    seen = outcomes()
    threads = [threading.Thread(target=stream, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors and not wrong, (errors[:1], wrong[:3])
    assert [kept(dom, q)[0] for q in texts] == asts
    assert [fingerprint(a) for a in asts] == before
    got = moved(seen)
    assert sum(got.values()) == 8 * 200 * 3 and "miss" not in got
    assert got == {"hit": 8 * 200 * 2, "bypass": 8 * 200}
