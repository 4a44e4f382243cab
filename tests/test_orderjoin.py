"""The orders-lineitem join deployment (``tpch_sf1_orders_x1``): TPC-H Q3
and Q12 in the spec's own text against the benchmark's plain references
(``benchmark/classes/q3.py``, ``q12.py``: numpy on a key -> row map, exact
integer sums, ``np.lexsort``; nothing of the program), and what the
deployment forced, each against a nested loop or the form it replaced:
the build-form rule, a sparse direct-addressed build, build columns
nothing reads, a build side that is a join's result, the rows root's
compaction, the compaction of a join's matched rows, the GROUP BY above
the join.  Programs are lowered as for a TPU on the CPU mesh, as
``tests/test_join_compact.py`` does.

The tolerance is equality: the answers are DECIMAL text."""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.copr import dag as D
from tidb_tpu.copr import exec as X
from tidb_tpu.copr import facts as F
from tidb_tpu.copr import joinbuild as JB
from tidb_tpu.expr import ColumnRef, Const, Func
from tidb_tpu.expr.compile import Evaluator
from tidb_tpu.parallel import get_mesh, spmd
from tidb_tpu.sched import scheduler_for
from tidb_tpu.session import Domain, Session
from tidb_tpu.session.catalog import TableInfo
from tidb_tpu.types import dtypes as dt

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SCALE, SEED = 0.02, 2147483659      # 3,000 x 30,000 x 120,000 rows
I64, I64N = dt.bigint(False), dt.bigint(True)
COLS = D.COMPACT_COLUMNS
N, C = 4096, 512                    # slots, a compaction's capacity


def _bench(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, as the harness loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)       # harness.exact
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"oj_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _forget_programs():
    from tidb_tpu.compilecache import compile_cache
    for cache in (spmd._cached, spmd._cached_fused, spmd._cached_fused_rows,
                  spmd._cached_batched, spmd._cached_batched_rows):
        cache.cache_clear()
    compile_cache().clear_pool()


@pytest.fixture
def lowered_for(monkeypatch):
    """lowered_for(platform): every program built from then on, until
    the test ends, is lowered as for a mesh of that platform."""
    def steer(platform):
        monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: platform)
        _forget_programs()
    yield steer
    _forget_programs()


# --------------------------------------------------------------------- #
# the build-form rule, a pure function
# --------------------------------------------------------------------- #

V5E = 16 << 30


@pytest.mark.parametrize("rows,span,columns,memory,want", [
    # every TPC-H primary key: a range as long as its count
    (200_000, 200_000, 4, V5E, "direct"),
    # o_orderkey, the first 8 of every 32: all of orders, spread 4 ...
    (1_500_000, 6_000_000, 1, V5E, "direct"),
    # ... and one segment's orders before a date over the same range,
    # spread 41, which the parent searched
    (145_000, 6_000_000, 2, V5E, "direct"),
    # one segment's customers, spread 5, the key alone
    (30_000, 150_000, 0, V5E, "direct"),
    # a handful of keys over a wide range: a table still costs its range
    (6, 9_000_000, 2, V5E, "direct"),
    # 67M slots of one word are the budget (1/64 of the device) ...
    (1_000, 1 << 26, 1, V5E, "direct"),
    (1_000, (1 << 26) + 1, 1, V5E, "sorted"),
    # ... of four words a quarter of that, of a smaller device less
    (1_000, 1 << 24, 4, V5E, "direct"),
    (1_000, (1 << 24) + 1, 4, V5E, "sorted"),
    (1_000, 1 << 24, 1, V5E // 8, "sorted"),
    # no table spans a range an int32 cannot index
    (10, 9_000_000_000, 1, 1 << 50, "sorted"),
])
def test_build_form(rows, span, columns, memory, want):
    assert JB.build_form(rows, span, columns, memory) == want
    # how sparse the range is does not enter
    assert JB.build_form(span, span, columns, memory) == want


# --------------------------------------------------------------------- #
# a sparse unique key, direct-addressed with holes
# --------------------------------------------------------------------- #

SPARSE_KEYS = [(i >> 3 << 5) + (i & 7) + 1 for i in range(96)]   # 8 of 32


def _side(with_columns: bool, read=None, keys=None, **kw):
    """A build side over TPC-H's order-key pattern: (side, rows)."""
    keys = np.array(SPARSE_KEYS if keys is None else keys, np.int64)
    n = len(keys)
    rows = [(int(k), 1000 + int(k), None if k % 5 == 0 else int(k) * 3)
            for k in keys]
    cols = [(keys, np.ones(n, bool))]
    if with_columns:
        cols += [(np.array([r[1] for r in rows], np.int64), np.ones(n, bool)),
                 (np.array([r[2] or 0 for r in rows], np.int64),
                  np.array([r[2] is not None for r in rows]))]
    else:
        rows = [r[:1] for r in rows]
    return JB.prepare_build(keys, cols, key_col=0, read=read, **kw), rows


def _probe_keys():
    rng = np.random.default_rng(1)
    key = rng.integers(-3, 400, N).astype(np.int32)     # holes, both ends
    key[::31] = 2_000_000_000
    key[7::37] = -2_000_000_000
    return key, rng.random(N) > 0.1


def _lookup(side, n_build, kind="inner", match_capacity=0, sel=None):
    """The join lowered as for a TPU -> (live output rows, extras)."""
    key, kvalid = _probe_keys()
    sel = np.ones(N, bool) if sel is None else sel
    scan = D.TableScan((0, 1), (I64N, I64))
    node = D.LookupJoin(scan, probe_key=ColumnRef(I64N, 0), kind=kind,
                        build_dtypes=(I64, I64, I64N)[:n_build],
                        dense=side.dense, packing=side.packing,
                        match_capacity=match_capacity)

    def fn(cols, sel, aux):
        cols = [(v, True if m is None else m) for v, m in cols]
        aux = tuple(tuple((v, True if m is None else m) for v, m in g)
                    for g in aux)
        batch = X._exec_node(node, cols, sel, Evaluator(jnp, platform="tpu"),
                             aux, 1)
        n = len(batch.cols[0][0])
        return ([(X._ensure_array(v, n), X._sel_array(m, n))
                 for v, m in batch.cols], X._sel_array(batch.sel, n),
                batch.extras)
    out, osel, extras = jax.tree_util.tree_map(np.asarray, jax.jit(fn)(
        [(key, kvalid), (np.arange(N), None)], sel, (side.aux,)))
    rows = [tuple(v[i].item() if m[i] else None for v, m in out)
            for i in np.nonzero(osel)[0]]
    return sorted(rows, key=repr), extras


def _nested_loop(build_rows, kind="inner", sel=None):
    key, kvalid = _probe_keys()
    out = []
    for i in range(N):
        if sel is not None and not sel[i]:
            continue
        probe = (int(key[i]) if kvalid[i] else None, i)
        hit = [b for b in build_rows if probe[0] is not None
               and probe[0] == b[0]]
        if hit:
            out.append(probe + hit[0])
        elif kind == "left":
            out.append(probe + (None,) * len(build_rows[0]))
    return sorted(out, key=repr)


@pytest.mark.parametrize("kind", ["inner", "left"])
@pytest.mark.parametrize("with_columns", [True, False],
                         ids=["columns", "key-only"])
def test_a_sparse_key_is_addressed_directly(with_columns, kind):
    """1.5M-orders' key pattern in small: a range four times the keys,
    holes in it, NULL and out-of-range probe keys, a NULL build value;
    with the key as the build's only column the table is the presence
    word alone."""
    side, rows = _side(with_columns)
    assert side.form == "direct" and side.unique
    span = SPARSE_KEYS[-1] - SPARSE_KEYS[0] + 1
    assert span == 4 * 96 - 24 and side.slots == JB.table_slots(span) == 1024
    assert side.packing[0] == 1 and side.packing[1] == 0     # presence bit
    got, extras = _lookup(side, len(rows[0]), kind)
    assert got == _nested_loop(rows, kind) and len(got) > 500
    assert "join_need" not in extras


@pytest.mark.parametrize("span,slots", [
    # one segment's orders before a date, by segment, date and seed: one
    # length, so one program
    (5_999_777, 6_029_312), (5_999_968, 6_029_312), (5_999_976, 6_029_312),
    (149_990, 155_648), (149_994, 155_648),
    # at most a sixteenth more; a power of two stays; never under 1,024
    (1 << 26, 1 << 26), ((1 << 26) + 1, 17 << 22), (360, 1_024), (1, 1_024)])
def test_a_table_s_length_does_not_follow_the_data(span, slots):
    assert JB.table_slots(span) == slots >= span
    assert slots <= max(span + span // 16 + 1, 1_024)


def test_a_spread_the_parent_searched_is_now_a_table():
    """One key in 41 of its range (Q3's build: a segment's orders before
    a date): the parent's rule (4 times the keys) sorted it."""
    keys = SPARSE_KEYS[::10]
    side, rows = _side(True, keys=keys)
    assert side.form == "direct" and side.slots > 30 * len(keys)
    assert _lookup(side, 3)[0] == _nested_loop(rows)
    # a device too small for the table: the sorted form, the same rows
    small, _ = _side(True, keys=keys, device_bytes=1 << 12)
    assert small.form == "sorted" and small.slots == len(keys)
    assert _lookup(small, 3)[0] == _nested_loop(rows)


def test_columns_nothing_reads_ride_in_no_word():
    """A join's result comes with the keys it was joined on: told which
    columns the program reads, the build carries no bit of the others,
    and what it gives for them is never looked at."""
    wide = np.array(SPARSE_KEYS, np.int64) << 20          # 27 bits
    keys = np.array(SPARSE_KEYS, np.int64)
    ones = np.ones(len(keys), bool)
    cols = [(keys, ones), (wide, ones), (keys * 7, ones), (wide + 1, ones)]
    full = JB.prepare_build(keys, cols, key_col=0)
    lean = JB.prepare_build(keys, cols, key_col=0,
                            read=(True, False, True, False))
    assert full.packing[0] == 3 and lean.packing[0] == 1
    assert [e[0] for e in lean.packing[2]] == [JB.KEY_ITSELF, JB.UNREAD, 0,
                                               JB.UNREAD]
    from tidb_tpu.copr.join import direct_lookup
    probe = jnp.asarray(np.array([1, 2, 9, 33, 500], np.int32))
    for side in (full, lean):
        grp = [(v, True if m is None else m) for v, m in side.aux]
        matched, out, _miss = jax.jit(
            lambda kv, grp, p=side.packing: direct_lookup(kv, grp, p))(
                probe, grp)
        assert np.asarray(matched).tolist() == [True, True, False, True,
                                                False]
        assert np.asarray(out[2][0])[[0, 1, 3]].tolist() == [7, 14, 231]
    # the contract knows the marker
    from tidb_tpu.analysis.contracts import verify_dag
    scan = D.TableScan((0,), (I64,))
    join = D.LookupJoin(scan, probe_key=ColumnRef(I64, 0), kind="inner",
                        build_dtypes=(I64,) * 4, dense=True,
                        packing=lean.packing)
    verify_dag(D.Aggregation(join, (), (D.AggDesc(
        D.AggFunc.SUM, ColumnRef(I64, 3), I64),), D.GroupStrategy.SCALAR))


def test_which_build_columns_a_program_reads():
    """Liveness from the aggregation at the root down to the join,
    through the projection that puts a swapped join's columns back."""
    scan = D.TableScan((0, 1), (I64, I64))
    join = D.LookupJoin(scan, probe_key=ColumnRef(I64, 0), kind="inner",
                        build_dtypes=(I64, I64, I64))

    def agg(child, key, arg):
        return D.Aggregation(child, (ColumnRef(I64, key),), (D.AggDesc(
            D.AggFunc.SUM, ColumnRef(I64, arg), I64),), D.GroupStrategy.SORT)
    cond = D.Selection(join, (ColumnRef(I64, 3),))       # build column 1
    assert D.build_columns_read(agg(cond, 0, 4), join) == (False, True, True)
    assert D.build_columns_read(agg(join, 1, 2), join) == (True, False, False)
    # a projection of every column, of which the root reads two
    restore = D.Projection(join, tuple(ColumnRef(I64, i)
                                       for i in (2, 3, 4, 0, 1)))
    assert D.build_columns_read(agg(restore, 3, 1), join) \
        == (False, True, False)
    # rows that leave the program as they are: everything is read
    assert D.build_columns_read(cond, join) is None
    assert D.build_columns_read(join, join) is None
    assert D.build_columns_read(D.TopN(restore, limit=3, sort_keys=(
        (ColumnRef(I64, 0), False),)), join) is None


# --------------------------------------------------------------------- #
# the rows root's compaction against exec.compact
# --------------------------------------------------------------------- #

LIVES = {"none": 0, "one": 1, "C-1": C - 1, "C": C, "C+1": C + 1,
         "run": C, "all": N}


# --------------------------------------------------------------------- #
# which group keys the others determine: a pure function of the DAG
# --------------------------------------------------------------------- #

def _c(i, t=I64, name=""):
    return ColumnRef(t, i, name)


def _fact_scan():
    return D.TableScan((0, 1, 2), (I64N, I64, I64))     # k, a, v


def _lookup_node(child, key, kind="inner", unique=True, slot=0):
    """A join that brings two build columns (none for a semi join)."""
    semi = kind in ("semi", "anti")
    t = I64N if kind == "left" else I64
    return D.LookupJoin(child, probe_key=key, kind=kind, unique=unique,
                        build_dtypes=() if semi else (t, t), aux_slot=slot)


def _sum(i=2):
    return (D.AggDesc(D.AggFunc.SUM, _c(i), dt.decimal(38, 0)),)


def _grouped(child, keys):
    return D.Aggregation(child, tuple(keys), _sum(), D.GroupStrategy.SORT,
                         group_capacity=1024)


_K1000A = Func(I64, "add", (Func(I64, "mul", (_c(0, I64N), Const(I64, 1000))),
                            _c(1)))
_ONE = _lookup_node(_fact_scan(), _c(0, I64N))
_CHAIN = _lookup_node(_ONE, _c(1), slot=1)          # builds: 3, 4 then 5, 6
_CHAIN_ON_BUILD = _lookup_node(_ONE, _c(3), slot=1)

RULE = {
    "a unique inner join": (_ONE, [_c(0, I64N), _c(3), _c(4)], (1, 2)),
    "a unique left join": (
        _lookup_node(_fact_scan(), _c(0, I64N), "left"),
        [_c(0, I64N), _c(3, I64N), _c(4, I64N)], (1, 2)),
    "the names of the columns do not matter": (
        _ONE, [_c(0, I64N, "fact.k"), _c(4, I64, "head.g")], (1,)),
    "a multimatch build": (
        _lookup_node(_fact_scan(), _c(0, I64N), unique=False),
        [_c(0, I64N), _c(3), _c(4)], ()),
    "a semi join brings no column": (
        _lookup_node(_fact_scan(), _c(0, I64N), "semi"),
        [_c(0, I64N), _c(1)], ()),
    "a probe key that is not grouped": (_ONE, [_c(1), _c(3)], ()),
    "a composite key half grouped": (
        _lookup_node(_fact_scan(), _K1000A), [_c(0, I64N), _c(3)], ()),
    "a computed key grouped as it is probed": (
        _lookup_node(_fact_scan(), _K1000A), [_K1000A, _c(3), _c(1)], (1,)),
    "an expression over build columns": (
        _ONE, [_c(0, I64N), Func(I64, "add", (_c(3), _c(4)))], (1,)),
    "an expression over a build and a probe column": (
        _ONE, [_c(0, I64N), Func(I64, "add", (_c(3), _c(1)))], ()),
    "a constant": (_ONE, [_c(0, I64N), Const(I64, 7), _c(3)], (2,)),
    "under a filter and a projection": (
        D.Projection(D.Selection(_ONE, (Func(I64, "lt", (
            _c(3), Const(I64, 9))),)), (_c(4), _c(2), _c(0, I64N))),
        [_c(2, I64N), _c(0)], (1,)),
    "a chain, each level's key grouped": (
        _CHAIN, [_c(0, I64N), _c(1), _c(3), _c(6)], (2, 3)),
    "a chain, one level's key not grouped": (
        _CHAIN, [_c(0, I64N), _c(3), _c(6)], (1,)),
    "a chain over both levels' columns": (
        _CHAIN, [_c(0, I64N), _c(1), Func(I64, "add", (_c(4), _c(5)))],
        (2,)),
    "a chain probed with a dependent key": (
        _CHAIN_ON_BUILD, [_c(0, I64N), _c(3), _c(5)], (1,)),
    "a chain probed with a build column of a multimatch level": (
        _lookup_node(_lookup_node(_fact_scan(), _c(0, I64N), unique=False),
                     _c(3), slot=1),
        [_c(0, I64N), _c(3), _c(5)], (2,)),
    "a limit between": (D.Limit(_ONE, 5), [_c(0, I64N), _c(3)], ()),
    "no join": (_fact_scan(), [_c(0, I64N), _c(1)], ()),
}


@pytest.mark.parametrize("case", list(RULE))
def test_which_group_keys_the_others_determine(case):
    """`dag.with_dependent_keys`: a key is dependent when all it reads
    are build columns of unique inner or left joins whose probe keys are
    group keys that are not dependent themselves; whatever else leaves
    the aggregation as it was given, the same object."""
    child, keys, want = RULE[case]
    agg = _grouped(child, keys)
    got = D.with_dependent_keys(agg)
    assert got.dependent == want
    assert dataclasses.replace(got, dependent=()) == agg
    if not want:
        assert got is agg
    assert D.with_dependent_keys(got) is got
    from tidb_tpu.analysis.compilekey import stable_digest
    assert (stable_digest(got) == stable_digest(agg)) == (not want)


def test_dependence_is_asked_of_grouped_aggregations_alone():
    scalar = D.Aggregation(_ONE, (), _sum(), D.GroupStrategy.SCALAR)
    assert D.with_dependent_keys(scalar) is scalar
    assert D.with_dependent_keys(_ONE) is _ONE
    from tidb_tpu.analysis.contracts import PlanContractError, verify_dag
    flat = _grouped(_fact_scan(), [_c(0, I64N), _c(1)])
    verify_dag(dataclasses.replace(flat, dependent=(1,)))
    for bad in ((0, 1), (2,), (1, 1)):
        with pytest.raises(PlanContractError):
            verify_dag(dataclasses.replace(flat, dependent=bad))


def _sel(case: str, seed=1):
    sel = np.zeros(N, bool)
    grid = sel.reshape(N // COLS, COLS)
    rng = np.random.default_rng(seed)
    if case == "one":
        sel[777] = True
    elif case == "run":
        sel[1000:1000 + C] = True
    elif case == "all":
        sel[:] = True
    elif case != "none":
        for t in range(COLS):           # every column its share exactly
            grid[rng.permutation(N // COLS)[:C // COLS], t] = True
        if case == "C-1":
            sel[np.nonzero(sel)[0][17]] = False
        elif case == "C+1":
            grid[np.nonzero(~grid[:, 5])[0][3], 5] = True
    assert sel.sum() == LIVES[case]
    return sel


def _root_rows(sel, capacity, platform, stacked=1):
    """`compact_root` -> (the rows its mask says, need, its facts)."""
    rng = np.random.default_rng(4)
    cols = [(rng.integers(-2 ** 40, 2 ** 40, N), None),
            (rng.integers(-128, 128, N).astype(np.int8), rng.random(N) > 0.3),
            (rng.random(N) > 0.5, None)]
    facts = {}

    def fn(cols, sel):
        batch = X.DeviceBatch([(v, True if m is None else m)
                               for v, m in cols], sel, stacked=stacked)
        out, need = X.compact_root(batch, capacity, platform)
        facts.update(batch.facts)
        return out, need
    out, need = jax.tree_util.tree_map(np.asarray, jax.jit(fn)(cols, sel))
    live = out[-1][0]
    rows = sorted((tuple(v[i].item() if m[i] else None for v, m in out[:-1])
                   for i in np.nonzero(live)[0]), key=repr)
    want = sorted((tuple(v[i].item() if m is None or m[i] else None
                         for v, m in cols) for i in np.nonzero(sel)[0]),
                  key=repr)
    return rows, int(need), facts, want


@pytest.mark.parametrize("stacked", [1, 8])
@pytest.mark.parametrize("case", list(LIVES))
def test_the_rows_root_compacts_by_the_column_sort(case, stacked):
    """Every live/dead mix, none and all included: where the rows fit,
    the slots the mask names hold exactly the live rows, as
    `exec.compact`'s first `count` slots do; past the capacity `need`
    says so and the rows there are some of the live ones."""
    sel = _sel(case)
    rows, need, facts, want = _root_rows(sel, C, "tpu", stacked)
    assert facts == {"rows_capacity": C, "rows_compact": 1}
    assert need >= LIVES[case] and need % COLS == 0
    if LIVES[case] <= C and case != "C+1":
        assert need <= C and rows == want
    else:
        assert need > C and set(rows) <= set(want) and len(rows) < len(want)
    # the scatter, where a scatter is cheap: the same rows, at the front
    rows_cpu, need_cpu, facts_cpu, _ = _root_rows(sel, C, "cpu", stacked)
    assert facts_cpu == {"rows_capacity": C, "rows_compact": 0}
    assert need_cpu == LIVES[case]
    assert rows_cpu == want if need_cpu <= C else len(rows_cpu) == C


def test_the_rows_root_keeps_the_scatter_where_the_sort_cannot():
    """Slots that are no whole rows of the column view, a capacity that
    holds every slot: `compact`, and the mask is its first `count`."""
    sel = _sel("C-1")
    rows, need, facts, want = _root_rows(sel, N, "tpu")
    assert facts["rows_compact"] == 0 and need == C - 1 and rows == want
    rows, need, facts, want = _root_rows(sel, C + 64, "tpu")
    assert facts["rows_compact"] == 0 and rows == want


# --------------------------------------------------------------------- #
# the compaction of a join's matched rows
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("capacity,fits", [(2048, True), (256, False)])
def test_matched_rows_compacted_after_the_lookup(capacity, fits):
    """Every slot is looked up; what is above the join runs on the
    matched rows alone.  The live count and the capacity they take are
    reported, and a capacity that falls short gives some of the rows."""
    side, rows = _side(True)
    sel = np.random.default_rng(2).random(N) > 0.4
    want = _nested_loop(rows, sel=sel)
    plain, _ = _lookup(side, 3, sel=sel)
    got, extras = _lookup(side, 3, match_capacity=capacity, sel=sel)
    assert plain == want and int(extras["join_live"]) == len(want)
    assert int(extras["join_need"]) >= len(want)
    if fits:
        assert got == want and int(extras["join_need"]) <= capacity
    else:
        assert int(extras["join_need"]) > capacity
        assert set(got) < set(want)


def test_the_contract_of_the_match_compaction():
    from tidb_tpu.analysis.contracts import PlanContractError, verify_dag
    side, _ = _side(True)
    scan = D.TableScan((0, 1), (I64N, I64))
    join = D.LookupJoin(scan, probe_key=ColumnRef(I64N, 0), kind="inner",
                        build_dtypes=(I64, I64, I64N), dense=True,
                        packing=side.packing, match_capacity=C)

    def agg(node):
        return D.Aggregation(node, (), (D.AggDesc(D.AggFunc.COUNT, None,
                                                  I64),),
                             D.GroupStrategy.SCALAR)
    verify_dag(agg(join))
    assert D.compacting_join(agg(join)) is join and D.has_extras(agg(join))
    assert D.compact_capacity(join) == C
    exact = D.uncompacted(agg(join))
    assert D.compacting_join(exact) is None and not D.has_extras(exact)
    for broken, why in (
            (dataclasses.replace(join, match_capacity=100), "multiple"),
            (dataclasses.replace(join, kind="left"), "inner"),
            (dataclasses.replace(join, probe_capacity=C), "one join")):
        with pytest.raises(PlanContractError, match=why):
            verify_dag(agg(broken))
    with pytest.raises(PlanContractError, match="order"):
        verify_dag(join)
    # a program that does not use it keeps the name it had
    from tidb_tpu.analysis.compilekey import stable_digest
    bare = dataclasses.replace(join, match_capacity=0)
    assert stable_digest(bare) != stable_digest(join)
    fields = {f.name: f for f in dataclasses.fields(D.LookupJoin)}
    assert fields["match_capacity"].metadata == D.DIGEST_IF_SET


# --------------------------------------------------------------------- #
# whole statements: the spec's text against the reference
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tpch():
    """(domain, {class: (module, oracle state)}): CUSTOMER, ORDERS and
    LineItem from the benchmark's generators, ANALYZEd as the
    configuration does, the engine pinned to the device path."""
    run_py = _bench("", "run")
    tables = {n: _bench("tables", n)
              for n in ("CUSTOMER", "ORDERS", "LineItem")}
    data = {n: t.generate(SCALE, SEED, list(t.TYPES))
            for n, t in tables.items()}
    dom = Domain()
    for n, t in tables.items():
        valid = np.ones(len(next(run_py._arrays(data[n]))), bool)
        cols = [run_py._column(t.TYPES[c], v, valid)
                for c, v in data[n].items()]
        info = TableInfo(t.NAME, list(data[n]), [c.dtype for c in cols])
        info.register_columns(cols)
        dom.catalog.create_table("test", info)
    sess = Session(dom)
    for n in tables:
        sess.execute(f"analyze table {n}")
    sess.execute("set global tidb_tpu_result_cache_entries = 0")
    sess.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    classes = {}
    for name in ("q3", "q12"):
        mod = _bench("classes", name)
        classes[name] = (mod, mod.prepare(data))
    yield dom, classes
    _forget_programs()


def _text(rows):
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


def _spans(sess, name):
    return [sp.attrs for sp in sess.last_trace.spans if sp.name == name]


COUNTERS = ("join_launches", "join_direct_launches", "rows_launches",
            "rows_compact_launches", "rows_regrows", "join_host_fallbacks",
            "join_shuffle_launches", "join_compact_overflows",
            "join_match_compact_launches", "hndv_agg_regrows",
            "hndv_agg_launches", "agg_dependent_key_launches",
            "group_topn_device_launches", "hndv_host_topn_launches")
Q3_FORM = ("one sort of 2-word records, o_orderdate, o_shippriority riding "
           "as dependents of the join's key, first 10 groups ranked on the ")


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", ["q3", "q12"])
def test_spec_text_equals_the_reference(tpch, lowered_for, name, devices):
    """Both spec texts, on one and on four devices, every program lowered
    as for a TPU: the oracle's rows text for text (Q3's ten in its
    order); every lookup direct-addressed; no fallback, no repartition
    join.  Q3's GROUP BY keeps `o_orderdate` and `o_shippriority`, which
    the unique `orders` build brings, out of its two-word record; one
    device ranks its groups, four leave it to the host, which merges
    their tables; Q12's DENSE root knows none of it."""
    dom, classes = tpch
    mod, state = classes[name]
    lowered_for("tpu")
    mesh = dom.client.mesh
    dom.client.mesh = get_mesh(devices)
    sched = dom.client._scheduler()
    try:
        before = sched.stats()
        assert set(COUNTERS) <= set(before)
        sess = Session(dom)
        rng = np.random.default_rng(31)
        for p in [mod.draw(rng) for _ in range(3)]:
            want = mod.answer(state, p)
            assert _text(sess.execute(mod.sql(p)).rows) == want, p
            assert len(want) == (10 if name == "q3" else 2)
            launches = [a for a in _spans(sess, "sched.launch")
                        if "join" in a]
            assert launches and all(a["join_form"] == "direct"
                                    for a in launches)
            for b in _spans(sess, "cop.join_build"):
                assert b["form"] == "direct" and b["slots"] >= b["rows"]
            grouped = [a for a in launches if "agg_strategy" in a]
            if name == "q3":
                assert [(a["dependent_keys"], a["group_topn"])
                        for a in grouped] \
                    == [(2, "device" if devices == 1 else "host")]
                (form,) = [r[0] for r in sess.execute(
                    "explain " + mod.sql(p)).rows
                    if r[0].startswith("agg strategy")]
                assert Q3_FORM + ("device" if devices == 1 else "host") \
                    in form
            else:
                assert not grouped and not any(
                    "dependent_keys" in a for a in launches)
        after = sched.stats()
    finally:
        dom.client.mesh = mesh
    moved = {k: after[k] - before[k] for k in COUNTERS}
    assert moved["join_direct_launches"] == moved["join_launches"] > 0
    assert not moved["join_host_fallbacks"] + moved["join_shuffle_launches"]
    n = moved["hndv_agg_launches"]
    assert (n > 0) == (name == "q3")
    assert moved["agg_dependent_key_launches"] == n
    assert (moved["group_topn_device_launches"],
            moved["hndv_host_topn_launches"]) \
        == ((n, 0) if devices == 1 else (0, n))


def test_q3_makes_its_orders_build_anew_with_every_statement(tpch,
                                                             lowered_for):
    """A build side that is a join's result and carries the statement's
    parameters: different parameters in turn, each answer its own (no
    stale build), never kept; the customers' constant-filtered rows are
    kept with the table's snapshot, as the parent keeps them.  The
    `orders` launch's rows leave by the column sort; the joined rows are
    compacted before the GROUP BY; a group table regrown once is not
    regrown again."""
    dom, classes = tpch
    mod, state = classes["q3"]
    lowered_for("tpu")
    mesh = dom.client.mesh
    dom.client.mesh = get_mesh(1)
    sched = dom.client._scheduler()
    try:
        sess = Session(dom)
        params = [{"segment": s, "day": d} for s, d in (
            ("BUILDING", 4), ("MACHINERY", 4), ("BUILDING", 29),
            ("BUILDING", 4), ("MACHINERY", 4))]
        answers, builds = [], []
        before = sched.stats()
        for p in params:
            answers.append(_text(sess.execute(mod.sql(p)).rows))
            assert answers[-1] == mod.answer(state, p)
            joined, table = sorted(_spans(sess, "cop.join_build"),
                                   key=lambda b: b["source"])
            assert (joined["source"], table["source"]) == ("join", "table")
            assert not joined["cached"] and joined["form"] == "direct"
            builds.append((joined["rows"], table["cached"]))
            rows_root, grouped = [a for a in _spans(sess, "sched.launch")
                                  if "join" in a][-2:]
            assert rows_root["rows_capacity"] % COLS == 0
            assert rows_root["program"].startswith("cop_solo_join_rows_")
            assert grouped["program"].startswith("cop_solo_join_agg_sort_")
            assert grouped["match_capacity"] > 0
            assert grouped["match_capacity"] * 8 <= grouped["probe_rows"]
            assert (grouped["dependent_keys"], grouped["group_topn"]) \
                == (2, "device")
        after = sched.stats()
    finally:
        dom.client.mesh = mesh
    assert answers[0] != answers[1] != answers[2] != answers[0]
    assert answers[3:] == answers[:2]
    # the orders that qualify differ with the parameters, and come again
    assert len({b[0] for b in builds}) == 3 and builds[3][0] == builds[0][0]
    # each segment's customers fetched once
    assert [b[1] for b in builds] == [False, False, True, True, True]
    assert after["join_match_compact_launches"] \
        - before["join_match_compact_launches"] == len(params)
    assert after["rows_compact_launches"] > before["rows_compact_launches"]
    assert after["join_compact_overflows"] == before["join_compact_overflows"]
    # the repeats start with the table and the capacity the first found
    seen = after["hndv_agg_regrows"] - before["hndv_agg_regrows"]
    assert seen <= 3, "a repeated statement regrew its group table again"
    # every statement's groups ranked by the device: ten slots crossed
    assert after["group_topn_device_launches"] \
        - before["group_topn_device_launches"] >= len(params)
    assert after["hndv_host_topn_launches"] \
        == before["hndv_host_topn_launches"]


def test_q12_compacts_its_probe_and_keeps_its_build(tpch, lowered_for):
    """Two column-to-column comparisons, an IN and a year: the estimate
    multiplies their selectivities and finds a capacity; all of `orders`
    is a 4x-sparse direct-addressed table kept with its snapshot."""
    dom, classes = tpch
    mod, state = classes["q12"]
    lowered_for("tpu")
    mesh = dom.client.mesh
    dom.client.mesh = get_mesh(1)   # a device's share holds a capacity
    try:
        sess = Session(dom)
        rng = np.random.default_rng(5)
        cached = []
        for p in [mod.draw(rng) for _ in range(3)]:
            assert _text(sess.execute(mod.sql(p)).rows) \
                == mod.answer(state, p)
            (build,) = _spans(sess, "cop.join_build")
            assert build["source"] == "table" and build["form"] == "direct"
            assert build["rows"] * 3 < build["slots"]        # 8 keys of 32
            cached.append(build["cached"])
            (launch,) = [a for a in _spans(sess, "sched.launch")
                         if "join" in a]
            assert launch["probe_capacity"] > 0 and launch["agg_limbs"] > 0
            assert launch["program"].startswith("cop_solo_join_agg_dense_")
    finally:
        dom.client.mesh = mesh
    assert cached[1:] == [True, True]


def test_explain_names_the_form_of_every_build_side(tpch):
    dom, classes = tpch
    sess = Session(dom)
    for name, want in (
            ("q3", "join forms: ORDERS.o_orderkey direct (122880 slots), "
                   "CUSTOMER.c_custkey direct (3000 slots)"),
            ("q12", "join forms: ORDERS.o_orderkey direct (122880 slots)")):
        mod = classes[name][0]
        plan = [r[0] for r in sess.execute(
            "explain " + mod.sql(mod.draw(np.random.default_rng(1)))).rows]
        assert want in plan, plan
    sess.execute("create table dup (k bigint, w bigint)")
    sess.execute("insert into dup values (1, 1), (1, 2), (9000000000, 3)")
    sess.execute("create table far (k bigint, w bigint)")
    sess.execute("insert into far values (1, 1), (9000000000, 3)")
    for table, form in (("dup", "expanding"), ("far", "sorted")):
        plan = [r[0] for r in sess.execute(
            f"explain select sum(w) from lineitem, {table} "
            f"where l_orderkey = {table}.k").rows]
        assert f"join forms: {table}.k {form}" in plan, plan
    plan = [r[0] for r in sess.execute(
        "explain select count(*) from lineitem").rows]
    assert not any(line.startswith("join forms") for line in plan)


def test_explain_names_the_dependent_keys_once_a_statement_has_run(
        tpch, lowered_for):
    """Which keys ride as dependents is a run's finding (a build side's
    uniqueness is counted, not declared): before a statement of the
    digest has run the aggregation's line has the planner's form (a
    record above a join is hashed, its groups the host's to rank);
    after, what that run launched."""
    dom, classes = tpch
    mod, _state = classes["q3"]
    lowered_for("tpu")
    mesh = dom.client.mesh
    dom.client.mesh = get_mesh(1)
    try:
        sess = Session(dom)
        sql = mod.sql({"segment": "FURNITURE", "day": 11})

        def line():
            (said,) = [r[0] for r in sess.execute("explain " + sql).rows
                       if r[0].startswith("agg strategy")]
            return said
        assert line() == ("agg strategy: sort (capacity auto; one sort of "
                          "hashed records, first 10 groups ranked on the "
                          "host)")
        sess.execute(sql)
        assert line() == ("agg strategy: sort (capacity auto; "
                          + Q3_FORM + "device)")
        # another digest (another literal) has not run
        assert "hashed" in [r[0] for r in sess.execute(
            "explain " + mod.sql({"segment": "FURNITURE", "day": 12})).rows
            if r[0].startswith("agg strategy")][0]
    finally:
        dom.client.mesh = mesh


# --------------------------------------------------------------------- #
# smaller statements: the edges, against a nested loop
# --------------------------------------------------------------------- #

ROWS = 8 * 8192


@pytest.fixture(scope="module")
def star():
    """`fact` (k nullable, a, v) ANALYZEd; `head` whose key is the first
    8 of every 32 (k, g = k // 32, c); `cust` (c, seg)."""
    rng = np.random.default_rng(7)
    k = rng.integers(-2, 420, ROWS)
    kvalid = rng.random(ROWS) > 0.05
    a = rng.integers(0, 1000, ROWS)
    v = rng.permutation(ROWS) - ROWS // 2
    from tidb_tpu.chunk.column import Column
    dom = Domain()
    cols = [Column(dt.bigint(True), k.astype(np.int64), kvalid),
            Column(dt.bigint(False), a.astype(np.int64), np.ones(ROWS, bool)),
            Column(dt.bigint(False), v.astype(np.int64), np.ones(ROWS, bool))]
    info = TableInfo("fact", ["k", "a", "v"], [c.dtype for c in cols])
    info.register_columns(cols)
    dom.catalog.create_table("test", info)
    s = Session(dom)
    head = [(key, key // 32, key % 7) for key in SPARSE_KEYS]
    cust = [(c, c % 3) for c in range(7)]
    s.execute("create table head (k bigint, g bigint, c bigint)")
    s.execute("insert into head values " + ", ".join(map(str, head)))
    s.execute("create table cust (c bigint, seg bigint)")
    s.execute("insert into cust values " + ", ".join(map(str, cust)))
    s.execute("create table none (k bigint, g bigint, c bigint)")
    for t in ("fact", "head", "cust"):
        s.execute(f"analyze table {t}")
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    s.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    dom.client._scheduler()     # this mesh's, whatever a test swaps in
    yield dom, (k, kvalid, a, v), head, cust
    _forget_programs()


def _chain_rows(fact, head, cust, seg, below, keep):
    """fact x (head x cust filtered): (k, v, g) of the joined rows."""
    k, kvalid, a, v = fact
    heads = {h[0]: h for h in head
             if dict(cust)[h[2]] == seg and h[0] < below}
    return [(int(k[i]), int(v[i]), heads[int(k[i])][1])
            for i in np.nonzero(kvalid & keep(a))[0] if int(k[i]) in heads]


@pytest.mark.parametrize("seg,below", [(0, 400), (1, 200), (2, 400),
                                       (0, 100), (1, 0)])
def test_a_chained_build_under_a_group_by_with_dependent_keys(
        star, lowered_for, monkeypatch, seg, below):
    """Q3's shape in small: the build is `head` joined to a segment of
    `cust`, the probe keeps over an eighth (no probe compaction), the
    GROUP BY has a key of the probe and a key that depends on it through
    the unique build.  Each parameter set its own answer; an empty build
    (nothing below 0) is the host fallback's and is counted.  On one
    device: `g` rides as a dependent of `fact.k` beside an exact record
    (one word: the statistics of `fact` say so), the device ranks the
    groups, the host ranks none."""
    dom, fact, head, cust = star
    lowered_for("tpu")
    monkeypatch.setattr(dom.client, "mesh", get_mesh(1))
    sess = Session(dom)
    sched = dom.client._scheduler()
    sql = ("select fact.k, sum(v), g from cust, head, fact "
           f"where seg = {seg} and cust.c = head.c and fact.k = head.k "
           f"and head.k < {below} and a >= 300 "
           "group by fact.k, g order by 2 desc, 1 limit 10")
    groups: dict = {}
    for key, val, g in _chain_rows(fact, head, cust, seg, below,
                                   lambda a: a >= 300):
        groups[key, g] = groups.get((key, g), 0) + val
    want = sorted(((k, s, g) for (k, g), s in groups.items()),
                  key=lambda r: (-r[1], r[0]))[:10]
    before = sched.stats()
    assert sess.execute(sql).rows == want
    after = sched.stats()
    assert (len(want) == 10) == (below > 0)
    assert after["join_host_fallbacks"] - before["join_host_fallbacks"] \
        == (below == 0)
    if below:
        joined = [b for b in _spans(sess, "cop.join_build")
                  if b["source"] == "join"]
        assert joined and joined[0]["form"] == "direct" \
            and not joined[0]["cached"]
        (grouped,) = [a for a in _spans(sess, "sched.launch")
                      if "agg_strategy" in a]
        assert (grouped["dependent_keys"], grouped["group_topn"]) \
            == (1, "device")
        assert "one sort of 1-word records, g riding as dependents" \
            in "\n".join(r[0] for r in sess.execute("explain " + sql).rows)
    moved = {k: after[k] - before[k] for k in (
        "agg_dependent_key_launches", "group_topn_device_launches",
        "hndv_host_topn_launches", "hndv_agg_regrows")}
    assert moved == {"agg_dependent_key_launches": int(below > 0),
                     "group_topn_device_launches": int(below > 0),
                     "hndv_host_topn_launches": 0, "hndv_agg_regrows": 0}, \
        moved


LEFT_SQL = ("select fact.k, g, c, sum(v), count(*) from fact left join head "
            "on fact.k = head.k where a >= {a} group by fact.k, g, c "
            "order by 4 desc, 1 limit {limit}")


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("a,limit", [(300, 10), (900, 10), (300, 70)])
def test_a_left_join_s_unmatched_rows_group_under_a_null_dependent(
        star, lowered_for, monkeypatch, devices, a, limit):
    """A unique LEFT join under the GROUP BY: a probe key the build does
    not hold is one group, its dependent keys NULL; the NULL probe keys
    are one group too.  Ranked by the device where it holds its groups
    whole and the LIMIT is at most `GROUP_TOPN_MAX`, else by the host
    (four devices: which merges their tables by true key)."""
    dom, (k, kvalid, av, v), head, _cust = star
    lowered_for("tpu")
    monkeypatch.setattr(dom.client, "mesh", get_mesh(devices))
    sess = Session(dom)
    sched = dom.client._scheduler()
    heads = {h[0]: h for h in head}
    groups: dict = {}
    for i in np.nonzero(av >= a)[0]:
        key = int(k[i]) if kvalid[i] else None
        h = heads.get(key, (None, None, None))
        s, n = groups.get((key, h[1], h[2]), (0, 0))
        groups[key, h[1], h[2]] = (s + int(v[i]), n + 1)
    want = sorted(((key, g, c, s, n) for (key, g, c), (s, n)
                   in groups.items()),
                  key=lambda r: (-r[3], r[0] is not None, r[0] or 0))[:limit]
    assert any(r[0] is not None and r[1] is None for r in want)
    before = sched.stats()
    assert sess.execute(LEFT_SQL.format(a=a, limit=limit)).rows == want
    after = sched.stats()
    (grouped,) = [s for s in _spans(sess, "sched.launch")
                  if "agg_strategy" in s]
    on_device = devices == 1 and limit <= D.GROUP_TOPN_MAX
    assert (grouped["dependent_keys"], grouped["group_topn"]) \
        == (2, "device" if on_device else "host")
    assert (after["group_topn_device_launches"]
            - before["group_topn_device_launches"],
            after["hndv_host_topn_launches"]
            - before["hndv_host_topn_launches"]) \
        == ((1, 0) if on_device else (0, 1))


def test_a_multimatch_build_leaves_the_group_by_as_it_was(star, lowered_for,
                                                         monkeypatch):
    """Dependence is used only where this run found the build unique: a
    build key held twice switches the join to the expanding form and
    the GROUP BY keeps all its keys in the record."""
    dom, (k, kvalid, av, v), _head, _cust = star
    lowered_for("tpu")
    monkeypatch.setattr(dom.client, "mesh", get_mesh(1))
    sess = Session(dom)
    sess.execute("create table twice (k bigint, g bigint)")
    sess.execute("insert into twice values (1, 10), (1, 11), (2, 20), "
                 "(400, 30)")
    try:
        sql = ("select fact.k, g, sum(v) from fact, twice where fact.k = "
               "twice.k group by fact.k, g order by 3 desc, 1, 2 limit 5")
        want: dict = {}
        for key, g in ((1, 10), (1, 11), (2, 20), (400, 30)):
            rows = kvalid & (k == key)
            if rows.any():
                want[key, g] = int(v[rows].sum())
        assert sess.execute(sql).rows == sorted(
            ((key, g, s) for (key, g), s in want.items()),
            key=lambda r: (-r[2], r[0], r[1]))[:5]
        (grouped,) = [s for s in _spans(sess, "sched.launch")
                      if "agg_strategy" in s]
        assert grouped["join"] == "multimatch"
        assert "dependent_keys" not in grouped
    finally:
        sess.execute("drop table twice")


def test_the_record_s_words_are_a_guess_the_device_corrects(star, lowered_for,
                                                            monkeypatch):
    """Tables nobody ANALYZEd: two words are guessed for the record
    without its dependent key.  A probe key 2^40 wide does not fit them:
    the device says so, the statement is rerun once with hashed records
    (the dependent key still out of the hash; the host ranks what a
    hash may have split), the digest is remembered and the repeat starts
    there; the answers are the same."""
    dom, _fact, _head, _cust = star
    lowered_for("tpu")
    monkeypatch.setattr(dom.client, "mesh", get_mesh(1))
    sess = Session(dom)
    sched = dom.client._scheduler()
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 50, 600)
    vals = rng.integers(-1000, 1000, 600)
    sess.execute("create table raw_head (k bigint, g bigint)")
    sess.execute("create table raw_fact (k bigint, v bigint)")
    try:
        for scale, regrows in ((1, 0), (1 << 34, 1)):
            sess.execute("delete from raw_head")
            sess.execute("delete from raw_fact")
            sess.execute("insert into raw_head values " + ", ".join(
                f"({k * scale}, {k % 7})" for k in range(0, 50, 2)))
            sess.execute("insert into raw_fact values " + ", ".join(
                f"({k * scale}, {v})" for k, v in zip(keys, vals)))
            sql = ("select raw_fact.k, g, sum(v) from raw_fact, raw_head "
                   "where raw_fact.k = raw_head.k group by raw_fact.k, g "
                   "order by 3 desc, 1 limit 10")
            sums: dict = {}
            for k, v in zip(keys, vals):
                if k % 2 == 0:
                    sums[k] = sums.get(k, 0) + int(v)
            want = sorted(((k * scale, k % 7, s) for k, s in sums.items()),
                          key=lambda r: (-r[2], r[0]))[:10]
            for again in (False, True):
                before = sched.stats()
                assert sess.execute(sql).rows == want
                after = sched.stats()
                assert after["hndv_agg_regrows"] \
                    - before["hndv_agg_regrows"] \
                    == (0 if again else regrows)
                *_first, last = [a for a in _spans(sess, "sched.launch")
                                 if "agg_strategy" in a]
                assert last["dependent_keys"] == 1
                assert last["group_topn"] \
                    == ("device" if regrows == 0 else "host")
            # EXPLAIN says what the executor handed the dispatcher
            (said,) = [r[0] for r in sess.execute("explain " + sql).rows
                       if r[0].startswith("agg strategy")]
            assert "2-word records, g riding as dependents" in said
    finally:
        sess.execute("drop table raw_head")
        sess.execute("drop table raw_fact")


def test_a_rows_root_past_its_capacity_is_rerun(star, lowered_for):
    """The paging loop: a first capacity that falls short is counted
    (`rows_regrows`), the statement rerun with what the rows take, and
    the repeat starts there."""
    dom, fact, _head, _cust = star
    lowered_for("tpu")
    k, kvalid, a, v = fact
    sess = Session(dom)
    sched = scheduler_for(dom.client.mesh)
    sql = "select v, a from fact where a < 300"
    want = sorted((int(v[i]), int(a[i])) for i in np.nonzero(a < 300)[0])
    before = sched.stats()
    assert sorted(sess.execute(sql).rows) == want
    first = sched.stats()
    short, *grown = _spans(sess, "sched.launch")
    assert short["rows_compact"] == 1 and grown
    assert grown[-1]["rows_capacity"] > short["rows_capacity"] \
        >= D.COMPACT_COLUMNS
    assert sorted(sess.execute(sql).rows) == want
    again = sched.stats()
    assert first["rows_regrows"] - before["rows_regrows"] == len(grown)
    assert again["rows_regrows"] == first["rows_regrows"]
    assert first["rows_compact_launches"] > before["rows_compact_launches"]
    (launch,) = _spans(sess, "sched.launch")
    assert launch["rows_capacity"] >= len(want) // 8


def test_the_new_facts_are_rows_of_the_table():
    """`join_form`, `match_capacity`, `rows_capacity` and `rows_compact`
    are rows of copr/facts.py and nothing of `sched/`."""
    assert {"join_direct_launches", "join_match_compact_launches",
            "rows_launches", "rows_compact_launches",
            "rows_regrows"} <= set(F.counter_names())
    assert F.counters({"join": "unique", "join_form": "direct,direct"}) \
        == ["join_launches", "join_direct_launches"]
    assert F.counters({"join": "unique", "join_form": "direct,sorted"}) \
        == ["join_launches"]
    assert F.counters({"join": "multimatch", "join_form": "expanding"}) \
        == ["join_launches"]
    assert F.counters({"rows_capacity": 256, "rows_compact": 1}) \
        == ["rows_launches", "rows_compact_launches"]
    assert F.counters({"rows_capacity": 256, "rows_compact": 0}) \
        == ["rows_launches"]
    assert F.counters({"match_capacity": 1024}) \
        == ["join_match_compact_launches"]
    assert F.counters({"match_capacity": 0}) == []
    # PR 32: the keys that rode as dependents; where the groups were ranked
    assert {"agg_dependent_key_launches", "group_topn_device_launches",
            "hndv_host_topn_launches"} <= set(F.counter_names())
    assert F.counters({"dependent_keys": 2}) \
        == ["agg_dependent_key_launches"]
    assert F.counters({"group_topn": "device"}) \
        == ["group_topn_device_launches"]
    assert F.counters({"group_topn": "host"}) == ["hndv_host_topn_launches"]
    assert F.span_attrs({"dependent_keys": 2, "group_topn": "device"}) \
        == {"dependent_keys": 2, "group_topn": "device"}
    grouped = _grouped(_ONE, [_c(0, I64N), _c(3)])
    assert F.of_program({"dependent_keys": 1}, grouped) \
        == {"dependent_keys": 1}
    assert F.of_program({"dependent_keys": 1}, _ONE) == {}
    assert F.span_attrs({"join_form": "direct", "rows_capacity": 256,
                         "rows_compact": 0, "match_capacity": 0}) \
        == {"join_form": "direct", "rows_capacity": 256}
    scan = D.TableScan((0,), (I64,))
    assert F.of_program({"rows_capacity": 8, "rows_compact": 1}, scan) \
        == {"rows_capacity": 8, "rows_compact": 1}
    top = D.TopN(scan, limit=3, sort_keys=((ColumnRef(I64, 0), False),))
    assert F.of_program({"rows_capacity": 8}, top) == {}
    here = os.path.dirname(os.path.abspath(F.__file__))
    sched_dir = os.path.join(os.path.dirname(here), "sched")
    for f in os.listdir(sched_dir):
        if f.endswith(".py"):
            text = open(os.path.join(sched_dir, f)).read()
            assert "join_form" not in text and "rows_compact" not in text
            assert "dependent" not in text and "group_topn" not in text
