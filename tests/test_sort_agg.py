"""SORT-strategy device group-by (high/arbitrary NDV) tests.

Reference analog: the parallel HashAgg over arbitrary key domains
(pkg/executor/aggregate/agg_hash_executor.go:94) — redesigned as device
sort + segment-reduce (SURVEY.md §7 hard part 4).  VERDICT r1 item 2.

Whole statements first (on the CPU mesh the host engine answers them).
Then SORT's two device lowerings, each through a `ShardedCopProgram` with
the host engine off: `exec._agg_sort_states` (the CPU mesh's, and the
only one of a MIN, a MAX or a float SUM) and `copr/runagg` (a TPU's, for
COUNTs and integer or DECIMAL SUMs: the mesh is said to be a TPU's, as
tests/test_run_agg.py does).  SORT is the one strategy of an unbounded
key domain: what the planner, the contracts and EXPLAIN say of it is
pinned at the end.
"""

import dataclasses

import jax
import numpy as np
import pytest

from tidb_tpu import copr
from tidb_tpu.analysis.contracts import (PlanContractError,
                                         fusion_signature,
                                         verify_fusion_group)
from tidb_tpu.chunk.column import Column, StringDict
from tidb_tpu.copr import dag as D
from tidb_tpu.copr.aggregate import (GroupKeyMeta, finalize,
                                     finalize_sorted, merge_sorted_states,
                                     merge_states)
from tidb_tpu.copr.runagg import run_form
from tidb_tpu.expr.ir import ColumnRef
from tidb_tpu.parallel import spmd
from tidb_tpu.parallel.mesh import get_mesh
from tidb_tpu.session import Domain, Session
from tidb_tpu.session.catalog import TableInfo
from tidb_tpu.store import CopClient, snapshot_from_columns
from tidb_tpu.testing.tpch import built_tpch_plans
from tidb_tpu.types import dtypes as dt


def _table(dom, name, cols):
    names = [c[0] for c in cols]
    columns = [c[1] for c in cols]
    ti = TableInfo(name, names, [c.dtype for c in columns])
    ti.register_columns(columns)
    dom.catalog.create_table("test", ti)
    return ti


@pytest.fixture()
def dom():
    return Domain()


def _explain_has_coptask(sess, sql):
    plan = "\n".join(r[0] for r in sess.must_query("explain " + sql))
    return "CopTask[agg]" in plan


def test_high_ndv_int_group_by_on_device(dom):
    sess = Session(dom)
    rng = np.random.default_rng(1)
    n = 60_000
    k = rng.integers(0, 40_000, n).astype(np.int64)
    v = rng.integers(-500, 500, n).astype(np.int64)
    _table(dom, "g1", [
        ("k", Column(dt.bigint(), k, np.ones(n, bool))),
        ("v", Column(dt.bigint(), v, np.ones(n, bool)))])
    sql = "select k, count(*), sum(v) from g1 group by k"
    assert _explain_has_coptask(sess, sql)
    rows = sess.must_query(sql)
    uk, inv = np.unique(k, return_inverse=True)
    assert len(rows) == len(uk)
    cnt = np.bincount(inv)
    sv = np.bincount(inv, weights=v).astype(np.int64)
    exp = {int(u): (int(c), int(s)) for u, c, s in zip(uk, cnt, sv)}
    for rk, rc, rs in rows:
        assert exp[rk] == (rc, int(rs))


def test_million_ndv_matches_oracle(dom):
    """VERDICT done-criterion: 1M-NDV int key agg matches the numpy
    oracle through the device SORT path."""
    sess = Session(dom)
    rng = np.random.default_rng(2)
    n = 1_000_000
    k = rng.integers(0, 1_000_000, n).astype(np.int64)
    _table(dom, "gm", [("k", Column(dt.bigint(), k, np.ones(n, bool)))])
    sql = "select k, count(*) from gm group by k"
    assert _explain_has_coptask(sess, sql)
    rows = sess.must_query(sql)
    uk, cnt = np.unique(k, return_counts=True)
    assert len(rows) == len(uk)
    got = dict(rows)
    for i in range(0, len(uk), 104729):
        assert got[int(uk[i])] == int(cnt[i])
    assert sum(got.values()) == n


def test_group_by_nullable_key_groups_nulls_together(dom):
    sess = Session(dom)
    sess.execute("create table gn (k bigint, v bigint)")
    sess.execute("insert into gn values (1, 10), (null, 5), (1, 1), "
                 "(null, 7), (2, 3)")
    rows = sess.must_query(
        "select k, sum(v), count(*) from gn group by k")
    by_key = {r[0]: (int(r[1]), r[2]) for r in rows}
    assert by_key[None] == (12, 2)
    assert by_key[1] == (11, 2)
    assert by_key[2] == (3, 1)
    # NULL key group distinct from value-0 group
    sess.execute("insert into gn values (0, 100)")
    rows = sess.must_query("select k, sum(v) from gn group by k")
    by_key = {r[0]: int(r[1]) for r in rows}
    assert by_key[0] == 100 and by_key[None] == 12


def test_multi_key_int_and_float(dom):
    sess = Session(dom)
    rng = np.random.default_rng(3)
    n = 5_000
    a = rng.integers(0, 50, n).astype(np.int64)
    b = rng.integers(0, 40, n).astype(np.float64) / 4.0
    v = rng.integers(0, 100, n).astype(np.int64)
    _table(dom, "g2", [
        ("a", Column(dt.bigint(), a, np.ones(n, bool))),
        ("b", Column(dt.double(), b, np.ones(n, bool))),
        ("v", Column(dt.bigint(), v, np.ones(n, bool)))])
    rows = sess.must_query(
        "select a, b, sum(v), max(v) from g2 group by a, b")
    exp = {}
    for i in range(n):
        key = (int(a[i]), float(b[i]))
        s, m = exp.get(key, (0, -1))
        exp[key] = (s + int(v[i]), max(m, int(v[i])))
    assert len(rows) == len(exp)
    for ra, rb, rs, rm in rows:
        assert exp[(ra, rb)] == (int(rs), rm)


def test_string_dict_key_falls_to_sort_when_domain_large(dom):
    """A dict-encoded string key beyond MAX_DENSE_GROUPS still runs on
    device via SORT and decodes back through the dictionary."""
    sess = Session(dom)
    n = 20_000
    rng = np.random.default_rng(4)
    codes = rng.integers(0, 5_000, n).astype(np.int64)
    words = [f"w{i:05d}" for i in range(5_000)]
    sd = StringDict(words)
    _table(dom, "g3", [
        ("s", Column(dt.varchar(), codes, np.ones(n, bool), sd)),
        ("v", Column(dt.bigint(), np.ones(n, np.int64), np.ones(n, bool)))])
    rows = sess.must_query("select s, count(*) from g3 group by s")
    uk, cnt = np.unique(codes, return_counts=True)
    got = dict(rows)
    assert len(got) == len(uk)
    assert got[words[int(uk[0])]] == int(cnt[0])


def test_decimal_sum_group_by_high_ndv_exact(dom):
    sess = Session(dom)
    sess.execute("create table gd (k bigint, d decimal(12,2))")
    vals = [(i % 700, f"{(i * 7 % 1000)}.{i % 100:02d}") for i in range(3000)]
    for off in range(0, len(vals), 500):
        sess.execute("insert into gd values " + ",".join(
            f"({k}, {d})" for k, d in vals[off:off + 500]))
    rows = sess.must_query("select k, sum(d) from gd group by k")
    import decimal
    exp = {}
    for k, d in vals:
        exp[k] = exp.get(k, decimal.Decimal(0)) + decimal.Decimal(d)
    assert len(rows) == len(exp)
    for rk, rs in rows:
        assert decimal.Decimal(str(rs)) == exp[rk], (rk, rs, exp[rk])


def test_group_capacity_regrow(dom):
    """More distinct groups than the initial capacity triggers the regrow
    loop (paging analog) and still returns every group."""
    from tidb_tpu.store import client as client_mod
    sess = Session(dom)
    n = 30_000
    k = np.arange(n, dtype=np.int64)  # all distinct
    _table(dom, "g4", [("k", Column(dt.bigint(), k, np.ones(n, bool)))])
    old = client_mod.DEFAULT_GROUP_CAPACITY
    client_mod.DEFAULT_GROUP_CAPACITY = 64
    try:
        rows = sess.must_query("select k, count(*) from g4 group by k")
    finally:
        client_mod.DEFAULT_GROUP_CAPACITY = old
    assert len(rows) == n
    assert all(c == 1 for _, c in rows)


def test_min_max_date_group_by(dom):
    """Regression: MIN/MAX sentinel must be built in the state array's own
    dtype (int64 sentinel astype int32 wraps to -1 and wins every min)."""
    sess = Session(dom)
    sess.execute("create table gdt (k bigint, d date)")
    sess.execute("insert into gdt values (1, '2020-05-01'), "
                 "(1, '2021-06-02'), (1, '1999-01-03'), (2, '2010-07-04')")
    import datetime
    rows = sess.must_query("select k, min(d), max(d) from gdt group by k")
    by_key = {r[0]: (r[1], r[2]) for r in rows}
    assert by_key[1] == (datetime.date(1999, 1, 3), datetime.date(2021, 6, 2))
    assert by_key[2] == (datetime.date(2010, 7, 4),) * 2


def test_negative_zero_groups_with_zero(dom):
    """Regression: -0.0 and +0.0 are SQL-equal and must form one group."""
    sess = Session(dom)
    n = 4
    b = np.array([0.0, -0.0, 0.0, -0.0])
    _table(dom, "gz", [
        ("b", Column(dt.double(), b, np.ones(n, bool))),
        ("v", Column(dt.bigint(), np.arange(n, dtype=np.int64),
                     np.ones(n, bool)))])
    rows = sess.must_query("select b, count(*) from gz group by b")
    assert len(rows) == 1 and rows[0][1] == 4


# ------------------------------------------------------------------ #
# the two device lowerings of SORT, host engine off
# ------------------------------------------------------------------ #

N_DEV = 8
I64, I64N = dt.bigint(False), dt.bigint()
LOWERINGS = ("sort_states", "runagg")
COUNT_ALL = copr.AggDesc(copr.AggFunc.COUNT, None, I64)


@pytest.fixture(scope="module")
def mesh():
    return get_mesh()


@pytest.fixture()
def lowered(request, monkeypatch):
    """-> the lowering's name, with the mesh said to be a TPU's for
    `runagg` (spmd traces a program for its mesh's platform) and no
    program of the other lowering left in a cache."""
    from tidb_tpu.compilecache import compile_cache
    if request.param == "runagg":
        monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
    spmd._cached.cache_clear()
    compile_cache().clear_pool()
    yield request.param
    spmd._cached.cache_clear()
    compile_cache().clear_pool()


def _snap(names, cols):
    return snapshot_from_columns(names, cols, n_shards=N_DEV)


def _col(dtype, data, valid=None):
    return Column(dtype, data, np.ones(len(data), bool) if valid is None
                  else valid)


def _sort_agg(scan, keys, aggs, cap, lowering):
    """A SORT aggregation with the wide record (`pack_words` 0: it
    always fits), of aggregates the lowering computes."""
    agg = D.Aggregation(scan, keys, aggs, D.GroupStrategy.SORT,
                        group_capacity=cap)
    assert run_form(agg) or lowering == "sort_states"
    return agg


def _run_host_merged(agg, snap, key_meta, mesh, lowering):
    """Run the device program and merge the per-device group tables on
    the host: the client's path without its host engine.  As the client
    does, a table some device says was too small (`__ngroups__`: the
    groups it saw, or the slots `runagg`'s compaction of the run ends
    wanted) is run again at that size."""
    cols, counts = snap.device_cols(mesh)
    for _ in range(4):
        prog = spmd.ShardedCopProgram(agg, mesh)
        assert prog.host_merge
        assert prog.platform == ("tpu" if lowering == "runagg" else "cpu")
        # `runagg` alone says how many limb lanes its prefix sums took
        assert ("scan_limbs" in prog.facts(cols, counts)) \
            == (lowering == "runagg")
        states = jax.device_get(prog(cols, counts))
        need = int(np.max(states["__ngroups__"]))
        if need <= agg.group_capacity:
            break
        agg = dataclasses.replace(
            agg, group_capacity=1 << (need - 1).bit_length())
    else:
        raise AssertionError("the group table did not converge")
    per_dev = [jax.tree_util.tree_map(lambda a, d=d: np.asarray(a)[d],
                                      states) for d in range(N_DEV)]
    merged = merge_sorted_states(agg, per_dev)
    return finalize_sorted(agg, merged, key_meta)


def _as_map(key_cols, agg_cols):
    out = {}
    for i in range(len(agg_cols[0]) if agg_cols else 0):
        key = tuple((int(kc.data[i]) if kc.validity[i] else None)
                    for kc in key_cols)
        out[key] = tuple(
            (int(c.data[i]) if c.validity[i] else None) for c in agg_cols)
    return out


@pytest.mark.parametrize("lowered", LOWERINGS, indirect=True)
def test_small_domain_bit_identical_to_dense_and_numpy(mesh, lowered):
    """COUNT and SUM (with MIN and MAX where the lowering has them) over
    a small-domain key: the SORT program's groups and values equal the
    DENSE program's and numpy's, bit for bit; AVG is SUM / COUNT."""
    rng = np.random.default_rng(11)
    n, dom = 120_000, 500
    k = rng.integers(0, dom, n).astype(np.int64)
    v = rng.integers(-10_000, 10_000, n).astype(np.int64)
    snap = _snap(["k", "v"], [_col(I64, k), _col(I64, v)])
    kref, vref = ColumnRef(I64, 0, "k"), ColumnRef(I64, 1, "v")
    aggs = (COUNT_ALL,
            copr.AggDesc(copr.AggFunc.SUM, vref, copr.sum_out_dtype(I64)))
    if lowered == "sort_states":
        aggs += (copr.AggDesc(copr.AggFunc.MIN, vref, I64N),
                 copr.AggDesc(copr.AggFunc.MAX, vref, I64N))
    scan = D.TableScan((0, 1), (I64, I64))
    srt = _sort_agg(scan, (kref,), aggs, 1024, lowered)
    m_srt = _as_map(*_run_host_merged(
        srt, snap, [GroupKeyMeta(I64, 0)], mesh, lowered))

    den = D.Aggregation(scan, (kref,), aggs, D.GroupStrategy.DENSE,
                        domain_sizes=(dom,))
    prog = spmd.ShardedCopProgram(den, mesh)
    assert not prog.host_merge
    states = jax.device_get(prog(*snap.device_cols(mesh)))
    m_den = _as_map(*finalize(den, merge_states([states]),
                              [GroupKeyMeta(I64, dom)]))
    assert m_srt == m_den

    exp = {}
    for u in np.unique(k):
        m = k == u
        exp[(int(u),)] = (int(m.sum()), int(v[m].sum()), int(v[m].min()),
                          int(v[m].max()))[:len(aggs)]
    assert m_srt == exp


@pytest.mark.parametrize("lowered", LOWERINGS, indirect=True)
def test_null_keys_a_group_of_their_own_and_multicolumn_keys(mesh,
                                                             lowered):
    """NULL keys form their own group (not zero's), two columns group
    by the tuple: against a Python reference."""
    rng = np.random.default_rng(13)
    n = 50_000
    a = rng.integers(0, 4000, n).astype(np.int64)
    av = rng.random(n) < 0.9            # one key in ten NULL
    b = rng.integers(-5, 5, n).astype(np.int64)
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    snap = _snap(["a", "b", "v"],
                 [_col(I64N, a, av), _col(I64, b), _col(I64, v)])
    aref, bref = ColumnRef(I64N, 0, "a"), ColumnRef(I64, 1, "b")
    vref = ColumnRef(I64, 2, "v")
    aggs = (COUNT_ALL,
            copr.AggDesc(copr.AggFunc.SUM, vref, copr.sum_out_dtype(I64)))
    if lowered == "sort_states":
        aggs += (copr.AggDesc(copr.AggFunc.MIN, vref, I64N),
                 copr.AggDesc(copr.AggFunc.MAX, vref, I64N))
    scan = D.TableScan((0, 1, 2), (I64N, I64, I64))
    agg = _sort_agg(scan, (aref, bref), aggs, 1 << 16, lowered)
    got = _as_map(*_run_host_merged(
        agg, snap, [GroupKeyMeta(I64N, 0), GroupKeyMeta(I64, 0)], mesh,
        lowered))

    exp: dict = {}
    for i in range(n):
        key = (int(a[i]) if av[i] else None, int(b[i]))
        c, sm, mn, mx = exp.get(key, (0, 0, None, None))
        vi = int(v[i])
        exp[key] = (c + 1, sm + vi, vi if mn is None else min(mn, vi),
                    vi if mx is None else max(mx, vi))
    assert got == {key: val[:len(aggs)] for key, val in exp.items()}
    assert any(key[0] is None for key in got)     # the NULL group


def _decimal_sums(mesh, lowering, val):
    rng = np.random.default_rng(17)
    k = rng.integers(0, 4, len(val)).astype(np.int64)
    dec_t = dt.decimal(18, 2)
    snap = _snap(["k", "d"], [_col(I64, k), _col(dec_t, val)])
    aggs = (copr.AggDesc(copr.AggFunc.SUM, ColumnRef(dec_t, 1, "d"),
                         copr.sum_out_dtype(dec_t)), COUNT_ALL)
    agg = _sort_agg(D.TableScan((0, 1), (I64, dec_t)),
                    (ColumnRef(I64, 0, "k"),), aggs, 1024, lowering)
    key_cols, agg_cols = _run_host_merged(
        agg, snap, [GroupKeyMeta(I64, 0)], mesh, lowering)
    got = {int(key_cols[0].data[i]): int(agg_cols[0].data[i])
           for i in range(len(key_cols[0]))}
    exp = {int(u): int(val[k == u].astype(object).sum())
           for u in np.unique(k)}
    assert got == exp
    return exp


@pytest.mark.parametrize("lowered", LOWERINGS, indirect=True)
def test_decimal_sum_with_high_limbs_at_the_limb_fence(mesh, lowered):
    """Scaled DECIMAL values on both sides of 2^31 and 2^32, negative
    ones too: a row's high limb is not zero, a group's low limbs add up
    past 2^31 many times over, and the (hi, lo) words still recombine to
    the exact total."""
    rng = np.random.default_rng(19)
    n = 40_000
    fence = rng.choice(np.array([1 << 31, 1 << 32], np.int64), n)
    val = (fence + rng.integers(-3, 4, n)) * rng.choice(
        np.array([1, 1, 1, -1], np.int64), n)
    exp = _decimal_sums(mesh, lowered, val)
    assert (np.abs(val) >> 32).any() and min(exp.values()) > 1 << 40


@pytest.mark.parametrize("lowered", LOWERINGS, indirect=True)
def test_decimal_sum_whose_totals_pass_int64(mesh, lowered):
    """DECIMAL SUMs whose group totals overflow int64 recombine exactly
    (Python ints through the host merge)."""
    rng = np.random.default_rng(17)
    n = 40_000
    val = (rng.integers(1 << 40, (1 << 40) + (1 << 20), n)
           * 1000).astype(np.int64)
    exp = _decimal_sums(mesh, lowered, val)
    assert max(abs(t) for t in exp.values()) > 2 ** 63


@pytest.mark.parametrize("lowered", LOWERINGS, indirect=True)
def test_two_million_distinct_groups_bit_identical(mesh, lowered):
    """Two million distinct keys through the device program, COUNT and
    SUM equal to numpy's for every one of them."""
    rng = np.random.default_rng(7)
    n = 2_000_000
    k = rng.permutation(n).astype(np.int64)
    v = rng.integers(0, 1000, n).astype(np.int64)
    snap = _snap(["k", "v"], [_col(I64, k), _col(I64, v)])
    vref = ColumnRef(I64, 1, "v")
    aggs = (COUNT_ALL,
            copr.AggDesc(copr.AggFunc.SUM, vref, copr.sum_out_dtype(I64)))
    agg = _sort_agg(D.TableScan((0, 1), (I64, I64)),
                    (ColumnRef(I64, 0, "k"),), aggs, 1 << 18, lowered)
    key_cols, agg_cols = _run_host_merged(
        agg, snap, [GroupKeyMeta(I64, 0)], mesh, lowered)
    assert len(key_cols[0]) == n                 # every group distinct
    order = np.argsort(key_cols[0].data)
    assert (key_cols[0].data[order] == np.arange(n)).all()
    assert (np.asarray(agg_cols[0].data).astype(np.int64) == 1).all()
    got = np.asarray([int(x) for x in agg_cols[1].data], dtype=np.int64)
    exp = np.zeros(n, np.int64)
    exp[k] = v
    assert (got[order] == exp).all()


@pytest.mark.parametrize("lowered", LOWERINGS, indirect=True)
def test_more_groups_than_capacity_regrown_once_from_the_count(mesh,
                                                               lowered):
    """Thirty thousand distinct keys into 1,024 slots: the device says
    how many groups it saw (`__ngroups__`), the client regrows the table
    to that, once, and every group comes back."""
    n = 30_000
    k = np.arange(n, dtype=np.int64) + (7_000_000 if lowered == "runagg"
                                        else 0)
    snap = _snap(["k"], [_col(I64, k)])
    agg = _sort_agg(D.TableScan((0,), (I64,)), (ColumnRef(I64, 0, "k"),),
                    (COUNT_ALL,), 1024, lowered)
    client = CopClient(mesh)
    client._platform = lambda: "tpu"      # the host engine off
    before = client._scheduler().stats()["hndv_agg_regrows"]
    res = client.execute_agg(agg, snap, [GroupKeyMeta(I64, 0)])
    assert client._scheduler().stats()["hndv_agg_regrows"] - before == 1
    assert sorted(int(x) for x in res.key_columns[0].data) == list(k)
    assert all(int(c) == 1 for c in res.columns[0].data)


def test_same_capacity_sort_tasks_fuse_into_one_launch(mesh):
    """Two SORT aggregations (same pow2 capacity, different payloads)
    over one scan run as ONE fused launch with host-merged per-member
    leaves, each bit-identical to its solo run — SORT chains finally
    fuse (ROADMAP fusion-breadth carried follow-on)."""
    from tidb_tpu.copr.dag import FusedDag
    from tidb_tpu.parallel.spmd import (get_fused_program,
                                        get_sharded_program)

    rng = np.random.default_rng(29)
    n = 20_000
    k = rng.integers(0, 5_000, n).astype(np.int64)
    v = rng.integers(0, 100, n).astype(np.int64)
    snap = _snap(["k", "v"], [_col(I64, k), _col(I64, v)])
    kref, vref = ColumnRef(I64, 0, "k"), ColumnRef(I64, 1, "v")
    scan = D.TableScan((0, 1), (I64, I64))
    a = D.Aggregation(scan, (kref,), (COUNT_ALL,),
                      D.GroupStrategy.SORT, group_capacity=8192)
    b = D.Aggregation(scan, (kref,),
                      (copr.AggDesc(copr.AggFunc.MAX, vref, I64N),),
                      D.GroupStrategy.SORT, group_capacity=8192)
    cols, counts = snap.device_cols(mesh)
    fprog = get_fused_program(FusedDag((a, b)), mesh)
    out_a, out_b = jax.device_get(fprog(cols, counts))
    for agg, out in ((a, out_a), (b, out_b)):
        solo = jax.device_get(get_sharded_program(agg, mesh)(cols, counts))
        flat_f, _ = jax.tree_util.tree_flatten(out)
        flat_s, _ = jax.tree_util.tree_flatten(solo)
        assert all((np.asarray(x) == np.asarray(y)).all()
                   for x, y in zip(flat_f, flat_s))


# ------------------------------------------------------------------ #
# SORT is the one strategy of an unbounded key domain
# ------------------------------------------------------------------ #

class _FakeTask:
    """Just enough of CopTask for verify_fusion_group."""

    def __init__(self, dag, fp=("x",), sig=(("s", "i8"),),
                 token=(1, 2, 3), aux=()):
        self.key = (D.dag_digest(dag), fp, 0, sig)
        self.dag = dag
        self.input_token = token
        self.aux = aux


def _count_by_key(cap, func=D.AggFunc.COUNT):
    kref = ColumnRef(I64, 0)
    return D.Aggregation(
        D.TableScan((0,), (I64,)), (kref,),
        (COUNT_ALL if func is D.AggFunc.COUNT
         else D.AggDesc(func, kref, I64N),),
        D.GroupStrategy.SORT, group_capacity=cap)


def test_sort_fusion_class_refuses_mismatched_capacities():
    """('sort-agg', cap) — the capacity-bucketed SORT class
    (fusion-breadth satellite) — refuses mismatched capacities at the
    class level, and fuses matching ones."""
    s4, s8 = _count_by_key(4096), _count_by_key(8192)
    assert fusion_signature(s4) == ("sort-agg", 4096)
    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group([_FakeTask(s4), _FakeTask(s8)])
    assert ei.value.rule == "fusion-class"
    # same capacity, different aggregates: a valid group
    verify_fusion_group([_FakeTask(s4),
                         _FakeTask(_count_by_key(4096, D.AggFunc.MAX))])


def test_group_strategy_has_three_members():
    assert [s.name for s in D.GroupStrategy] == ["SCALAR", "DENSE", "SORT"]
    assert [a for a in (D.Aggregation(strategy=s) for s in D.GroupStrategy)
            if a.host_merged] == [D.Aggregation(strategy=D.GroupStrategy.SORT)]


def test_an_aggregation_has_no_field_of_another_strategy():
    """What an `Aggregation` can say is these fields, and one capacity
    (`group_capacity`): a keyword of another strategy is a TypeError,
    not a silent second way to size a table."""
    assert [f.name for f in dataclasses.fields(D.Aggregation)] == [
        "child", "group_by", "aggs", "strategy", "domain_sizes",
        "group_capacity", "narrow_sums", "pack_words", "topn", "dependent"]
    for other in ("buckets", "hashed", "passes"):
        with pytest.raises(TypeError):
            dataclasses.replace(_count_by_key(1024), **{other: 1})


def test_fusion_signature_of_a_sort_aggregation_is_what_it_was():
    """Sized to a power of two: its class; unsized, lopsided or with an
    exact record (each member sorts its own): none.  And never an
    in-program aggregation's class."""
    assert fusion_signature(_count_by_key(4096)) == ("sort-agg", 4096)
    assert fusion_signature(_count_by_key(0)) is None
    assert fusion_signature(_count_by_key(1000)) is None
    assert fusion_signature(dataclasses.replace(
        _count_by_key(4096), pack_words=1)) is None
    scalar = D.Aggregation(D.TableScan((0,), (I64,)), (), (COUNT_ALL,),
                           D.GroupStrategy.SCALAR)
    assert fusion_signature(scalar) == ("inprog-agg",)
    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group([_FakeTask(scalar),
                             _FakeTask(_count_by_key(4096))])
    assert ei.value.rule == "fusion-class"


# a GROUP BY of sixty thousand distinct keys with an aggregate only
# `_agg_sort_states` computes: above the 32,768 estimated groups where
# two more strategies were once priced against SORT
HIGH_NDV_SHAPES = {
    "min": "select k, min(v) from hi group by k",
    "max": "select k, max(v), count(*) from hi group by k",
    "float_sum": "select k, sum(f) from hi group by k",
}


@pytest.fixture(scope="module")
def high_ndv():
    dom = Domain()
    sess = Session(dom)
    rng = np.random.default_rng(3)
    n = 60_000
    k = rng.permutation(100_000)[:n].astype(np.int64)
    _table(dom, "hi", [
        ("k", _col(I64, k)),
        ("v", _col(I64, rng.integers(0, 50, n).astype(np.int64))),
        ("f", _col(dt.double(False), rng.random(n)))])
    sess.execute("analyze table hi")
    return sess


def _planned_as(sess, platform, fn):
    """fn() with the programs' mesh said to be `platform`'s (what the
    planner reads: `executor/plan._mesh_platform`)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spmd, "mesh_platform", lambda _mesh: platform)
        return fn()


def _root_agg(phys):
    stack = [phys]
    while stack:
        op = stack.pop()
        if isinstance(getattr(op, "dag", None), D.Aggregation):
            return op.dag
        stack.extend(c for c in getattr(op, "children", []) or [] if c)
    raise AssertionError("no pushed aggregation")


@pytest.mark.parametrize("shape", list(HIGH_NDV_SHAPES))
def test_min_max_and_float_sum_at_high_ndv_plan_as_sort(high_ndv, shape):
    """On a TPU and on the CPU mesh alike: SORT, its table seeded from
    the NDV ANALYZE found (60,000 and a quarter more, to a power of
    two), no record form (`runagg` does not compute these)."""
    sql = HIGH_NDV_SHAPES[shape]
    for platform in ("tpu", "cpu"):
        (_sql, phys), = _planned_as(
            high_ndv, platform,
            lambda: list(built_tpch_plans(high_ndv, [sql])))
        agg = _root_agg(phys)
        assert agg.strategy is D.GroupStrategy.SORT, (platform, agg)
        assert (agg.group_capacity, agg.pack_words) == (1 << 17, 0)
        assert not run_form(agg)


@pytest.mark.parametrize("shape", list(HIGH_NDV_SHAPES))
def test_explain_of_a_high_ndv_group_by_says_sort(high_ndv, shape):
    sql = "explain " + HIGH_NDV_SHAPES[shape]
    for platform in ("tpu", "cpu"):
        plan = [r[0] for r in _planned_as(
            high_ndv, platform, lambda: high_ndv.must_query(sql))]
        assert "agg strategy: sort (capacity 131072)" in plan, plan
        assert any("Aggregation[sort]" in line for line in plan), plan
