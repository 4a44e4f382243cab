"""The TPU's dense grouped SUM/COUNT reduction (copr/exec._dense_limb_states),
which no statement reaches on the CPU mesh (there `_reduce` scatters): lowered
for `Evaluator(jnp, platform="tpu")`, its states have to equal, word for word
after recombination, the scatter branch's and a Python-int oracle's."""

import datetime
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from tidb_tpu.copr import dag as D
from tidb_tpu.copr import exec as X
from tidb_tpu.copr.aggregate import sum_out_dtype
from tidb_tpu.expr import ColumnRef, Func
from tidb_tpu.expr.compile import Evaluator
from tidb_tpu.parallel import spmd
from tidb_tpu.parallel.mesh import SHARD_AXIS, shard_map
from tidb_tpu.types import dtypes as dt

I64, I64N, F64 = dt.bigint(False), dt.bigint(True), dt.double()
SUM, COUNT, MIN, MAX = (D.AggFunc.SUM, D.AggFunc.COUNT, D.AggFunc.MIN,
                        D.AggFunc.MAX)

# the group keys of a G-group aggregation: (domain size, nullable) a key;
# a nullable key's domain holds its NULL slot
KEYS = {1: ((1, False),), 2: ((2, True),), 6: ((3, False), (2, False)),
        25: ((25, False),), 64: ((8, True), (8, False))}


def _states(agg, cols, sel, platform, stacked=1):
    """`_agg_partial_states` traced for `platform`; -> (states, agg_limbs)."""
    limbs = []

    def fn(cols, sel):
        batch = X.DeviceBatch(
            [(v, True if m is None else m) for v, m in cols], sel,
            stacked=stacked)
        out = X._agg_partial_states(
            agg, batch, Evaluator(jnp, platform=platform), {})
        limbs.append(batch.facts["agg_limbs"])
        return out
    out = jax.jit(fn)(cols, sel)
    return jax.tree_util.tree_map(np.asarray, out), limbs[0]


def _total(state) -> list:
    """A SUM state's exact totals a group, as Python ints."""
    if "hi" in state:
        return [(int(h) << 32) + int(lo)
                for h, lo in zip(state["hi"], state["lo"])]
    return [int(x) for x in state["sum"]]


def _assert_same(got, want, aggs):
    """Two state dicts hold the same totals (float sums to rounding)."""
    assert got.keys() == want.keys()
    assert got["__rows__"].tolist() == want["__rows__"].tolist()
    for i, a in enumerate(aggs):
        g, w = got[f"a{i}"], want[f"a{i}"]
        assert g.keys() == w.keys(), (i, str(a))
        for f in g:
            assert g[f].dtype == w[f].dtype, (i, f)
        if a.func == SUM and a.arg.dtype.kind == dt.TypeKind.FLOAT64:
            np.testing.assert_allclose(g["sum"], w["sum"], rtol=1e-6)
            assert g["cnt"].tolist() == w["cnt"].tolist()
        elif a.func == SUM:
            assert _total(g) == _total(w), (i, str(a))
            assert g["cnt"].tolist() == w["cnt"].tolist()
        else:
            for f in g:
                assert g[f].tolist() == w[f].tolist(), (i, str(a), f)


def _table(n, G, seed=0):
    """Columns (keys first), the selection and the aggregation of a mixed
    case: NULL keys and arguments, negatives, every physical width, an
    expression, one argument under two aggregates, narrow and limb slots,
    COUNT(x), MIN, MAX and a float SUM beside the integer lanes."""
    rng = np.random.default_rng(seed + 131 * G + n)
    cols, group_by, sizes = [], [], []
    for size, nullable in KEYS[G]:
        codes = rng.integers(0, size - nullable, n).astype(np.int8)
        valid = rng.random(n) > 0.1 if nullable else None
        group_by.append(ColumnRef(I64N if nullable else I64, len(cols)))
        cols.append((codes, valid))
        sizes.append(size)
    k = len(cols)
    a8 = rng.integers(-128, 128, n).astype(np.int8)
    a16 = rng.integers(-2 ** 15, 2 ** 15, n).astype(np.int16)
    a32 = rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)
    a64 = rng.integers(-2 ** 40, 2 ** 40, n).astype(np.int64)
    f = rng.random(n)
    cols += [(a8, rng.random(n) > 0.2), (a16, None),
             (a32, rng.random(n) > 0.5), (a64, None), (f, None)]
    r8, r16, r32, r64 = (ColumnRef(I64N, k), ColumnRef(I64, k + 1),
                         ColumnRef(I64N, k + 2), ColumnRef(I64, k + 3))
    prod = Func(I64N, "mul", (r32, r16))
    out = sum_out_dtype(I64)
    aggs = (D.AggDesc(COUNT, None, I64), D.AggDesc(SUM, r8, out),
            D.AggDesc(COUNT, r8, I64), D.AggDesc(SUM, r16, out),
            D.AggDesc(SUM, r32, out), D.AggDesc(SUM, r64, out),
            D.AggDesc(SUM, r16, out), D.AggDesc(SUM, prod, out),
            D.AggDesc(MIN, r32, I64N), D.AggDesc(MAX, r16, I64),
            D.AggDesc(SUM, ColumnRef(F64, k + 4), F64))
    scan = D.TableScan(tuple(range(len(cols))), tuple(
        [g.dtype for g in group_by] + [I64N, I64, I64N, I64, F64]))
    agg = D.Aggregation(scan, tuple(group_by), aggs, D.GroupStrategy.DENSE,
                        domain_sizes=tuple(sizes), narrow_sums=(3, 4))
    sel = rng.random(n) > 0.15
    return cols, sel, agg


def _oracle(cols, sel, agg):
    """Exact {agg index: totals a group} of the integer SUMs and COUNTs of
    `_table`, in Python ints."""
    G, n = agg.num_groups, len(sel)
    gid = np.zeros(n, np.int64)
    for (codes, valid), size, e in zip(cols, agg.domain_sizes, agg.group_by):
        code = codes.astype(np.int64)
        if e.dtype.nullable:
            code = np.where(valid, code + 1, 0) if valid is not None \
                else code + 1
        gid = gid * size + code
    k = len(agg.group_by)

    def arg(e):
        if isinstance(e, ColumnRef):
            v, m = cols[e.index]
            return [int(x) for x in v], (np.ones(n, bool) if m is None else m)
        (av, am), (bv, bm) = arg(e.args[0]), arg(e.args[1])
        return [x * y for x, y in zip(av, bv)], am & bm
    out = {}
    for i, a in enumerate(agg.aggs):
        if a.func == COUNT and a.arg is None:
            out[i] = [int((sel & (gid == g)).sum()) for g in range(G)]
        elif a.func in (SUM, COUNT) and a.arg.index != k + 4 \
                if isinstance(a.arg, ColumnRef) else True:
            v, m = arg(a.arg)
            live = sel & m
            out[i] = [(sum(v[r] for r in np.flatnonzero(live & (gid == g)))
                       if a.func == SUM else int((live & (gid == g)).sum()))
                      for g in range(G)]
    return out


SHAPES = [(8 * 1024, 8), (1000, 1), (70001, 1), (2 * 2 ** 17, 2)]


@pytest.mark.parametrize("G", sorted(KEYS))
@pytest.mark.parametrize("n,stacked", SHAPES)
def test_limb_states_equal_scatter_and_oracle(G, n, stacked):
    cols, sel, agg = _table(n, G)
    got, limbs = _states(agg, cols, sel, "tpu", stacked)
    want, none = _states(agg, cols, sel, "cpu", stacked)
    # lanes a row: two NULL masks and the selection's count, int8 and int16
    # one limb, int32 two, int64 and the int64 product three
    assert (limbs, none) == (3 + 1 + 1 + 2 + 3 + 3, 0)
    _assert_same(got, want, agg.aggs)
    for i, totals in _oracle(cols, sel, agg).items():
        a, state = agg.aggs[i], got[f"a{i}"]
        if a.func == COUNT:
            assert state["count"].tolist() == totals
        elif a.arg.dtype.kind != dt.TypeKind.FLOAT64:
            assert _total(state) == totals, (i, str(a))
    # narrow slots hold one word, the others two
    assert set(got["a3"]) == {"sum", "cnt"} == set(got["a4"])
    assert set(got["a5"]) == {"hi", "lo", "cnt"}


@pytest.mark.parametrize("n,stacked,view,pad", [
    (8 * 2 ** 23, 8, (8, 128, 512, 128), 0),      # SF10 on one chip
    (2 * 2 ** 23, 2, (2, 128, 512, 128), 0),      # a device of four
    (8 * 1024, 8, (8, 1, 8, 128), 0),
    (2 ** 21, 1, (1, 32, 512, 128), 0),
    (1000, 1, (1, 1, 8, 128), 24),                # less than a tile row
    (70001, 1, (1, 2, 512, 128), 2 * 512 * 128 - 70001),
    (3 * 1024, 2, (2, 1, 12, 128), 0),
    (3 * 1000, 3, (1, 1, 24, 128), 72),           # runs of no whole tiles
    (5 * 600 * 128, 5, (1, 6, 512, 128), 72 * 128),   # tiles that do not divide
    (0, 1, (1, 1, 1, 128), 128),
])
def test_dense_view(n, stacked, view, pad):
    assert X.dense_view(n, stacked) == (view, pad)
    (s, blocks, tiles, lanes), pad = X.dense_view(n, stacked)
    assert s * blocks * tiles * lanes == n + pad and tiles <= X.ACC_RUN


@pytest.mark.parametrize("dtype,k", [
    (np.bool_, 1), (np.int8, 1), (np.uint8, 1), (np.int16, 1),
    (np.uint16, 1), (np.int32, 2), (np.uint32, 3), (np.int64, 3)])
def test_limbs_recombine_at_the_extremes(dtype, k):
    info = None if dtype is np.bool_ else np.iinfo(dtype)
    vals = [False, True] if info is None else \
        [info.min, info.min + 1, -1 if info.min else 0, 0, 1, info.max - 1,
         info.max]
    limbs = [np.asarray(x) for x in X._limbs(jnp.asarray(vals, dtype))]
    assert len(limbs) == k and all(x.dtype == np.int32 for x in limbs)
    top = 32 if k == 1 else 8 * max(np.dtype(dtype).itemsize, 4) \
        - X.LIMB_BITS * (k - 1) if dtype is not np.uint32 else 20
    for i, x in enumerate(limbs):
        lo, hi = (0, 2 ** X.LIMB_BITS) if i < k - 1 \
            else (-2 ** (top - 1), 2 ** (top - 1))
        assert ((x >= lo) & (x < hi)).all()
    back = [sum(int(x[r]) << (X.LIMB_BITS * i) for i, x in enumerate(limbs))
            for r in range(len(vals))]
    assert back == [int(v) for v in vals]


@pytest.mark.parametrize("dtype", [np.int16, np.int32, np.int64])
@pytest.mark.parametrize("end", ["min", "max"])
def test_accumulator_bound_whole_run_in_one_group(dtype, end):
    """Every row of two accumulation runs in one group at the dtype's
    extreme: the largest sums an int32 accumulator is ever handed."""
    n = 2 * X.ACC_RUN * X.LANES
    v = np.full(n, getattr(np.iinfo(dtype), end), dtype)
    cols = [(np.ones(n, np.int8), None), (v, None)]
    agg = D.Aggregation(
        D.TableScan((0, 1), (I64, I64)), (ColumnRef(I64, 0),),
        (D.AggDesc(SUM, ColumnRef(I64, 1), sum_out_dtype(I64)),
         D.AggDesc(COUNT, None, I64)),
        D.GroupStrategy.DENSE, domain_sizes=(2,))
    got, limbs = _states(agg, cols, np.ones(n, bool), "tpu")
    assert limbs == 1 + len(X._limbs(jnp.zeros(1, dtype)))
    assert _total(got["a0"]) == [0, n * int(v[0])]
    assert got["a1"]["count"].tolist() == [0, n]


@pytest.mark.parametrize("narrow", [False, True])
def test_int64_extremes(narrow):
    """+-2^62 and the ends of int64: three limbs carry any int64.  The limb
    slot's total leaves int64; the narrow slot's wraps back into it, as
    the proof that stamps a slot narrow says it may."""
    vals = [2 ** 62, 2 ** 62, 2 ** 62, -2 ** 62, 2 ** 63 - 1, -2 ** 63,
            -2 ** 62, 2 ** 62 - 1] * 40
    keys = [i % 3 for i in range(len(vals))]
    if narrow:
        vals = [v if k else -v - 1 for v, k in zip(vals, keys)]
        vals = [v if abs(v) < 2 ** 61 or k != 1 else v // 4
                for v, k in zip(vals, keys)]
    n = len(vals)
    cols = [(np.array(keys, np.int8), None), (np.array(vals, np.int64), None)]
    agg = D.Aggregation(
        D.TableScan((0, 1), (I64, I64)), (ColumnRef(I64, 0),),
        (D.AggDesc(SUM, ColumnRef(I64, 1), sum_out_dtype(I64)),),
        D.GroupStrategy.DENSE, domain_sizes=(3,),
        narrow_sums=(0,) if narrow else ())
    got, _ = _states(agg, cols, np.ones(n, bool), "tpu")
    want = [sum(v for v, k in zip(vals, keys) if k == g) for g in range(3)]
    if narrow:
        want = [(t + 2 ** 63) % 2 ** 64 - 2 ** 63 for t in want]
    assert _total(got["a0"]) == want
    assert max(abs(t) for t in want) > 2 ** 62


def test_two_threads_lowering_for_two_platforms_get_their_own_forms():
    """One DENSE aggregation lowered for "tpu" on one thread and for
    "cpu" on another, in step (both are inside their trace, evaluator in
    hand, before either lowers): each gets its own platform's form every
    time.  A platform kept beside the lowering, for the whole process,
    could not promise that."""
    import threading
    cols, sel, agg = _table(1024, 6, seed=3)
    rounds, in_step = 6, threading.Barrier(2)
    seen, errors = {"tpu": [], "cpu": []}, []

    def lower(platform):
        def fn(cols, sel):
            ev = Evaluator(jnp, platform=platform)
            batch = X.DeviceBatch(
                [(v, True if m is None else m) for v, m in cols], sel)
            in_step.wait(timeout=60)
            out = X._agg_partial_states(agg, batch, ev, {})
            seen[platform].append(batch.facts["agg_limbs"])
            return out
        try:
            for _ in range(rounds):
                # a new function object a round: traced anew
                jax.eval_shape(lambda c, s: fn(c, s), cols, sel)
        except Exception as e:  # noqa: BLE001 - surfaced by the assert
            in_step.abort()
            errors.append(e)
    threads = [threading.Thread(target=lower, args=(p,)) for p in seen]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    assert seen == {"tpu": [13] * rounds, "cpu": [0] * rounds}


def test_under_vmap_as_the_batched_program_runs_it():
    n, slots = 4 * 1024, 3
    tables = [_table(n, 6, seed=s) for s in range(slots)]
    agg = tables[0][2]
    cols = [(np.stack([t[0][j][0] for t in tables]),
             None if tables[0][0][j][1] is None
             else np.stack([t[0][j][1] for t in tables]))
            for j in range(len(tables[0][0]))]
    sel = np.stack([t[1] for t in tables])

    def one(cols, sel):
        batch = X.DeviceBatch(
            [(v, True if m is None else m) for v, m in cols], sel, stacked=4)
        return X._agg_partial_states(
            agg, batch, Evaluator(jnp, platform="tpu"), {})
    got = jax.jit(jax.vmap(one))(cols, sel)
    got = jax.tree_util.tree_map(np.asarray, got)
    for s, (c, sl, _agg) in enumerate(tables):
        want, _ = _states(agg, c, sl, "cpu")
        _assert_same(jax.tree_util.tree_map(lambda a: a[s], got), want,
                     agg.aggs)


def test_over_a_four_device_mesh_with_psum():
    """Each device pins its stacked (2, C) shards to the view
    (`_flatten_block`), reduces them, and the states merge by psum."""
    devs, s_local, cap = 4, 2, 2048
    n = devs * s_local * cap
    cols, sel, agg = _table(n, 6, seed=5)
    counts = np.array([cap - 7 * i for i in range(devs * s_local)], np.int64)
    live = (np.arange(cap)[None, :] < counts[:, None]).reshape(-1)
    stacked = [(v.reshape(-1, cap), None if m is None else m.reshape(-1, cap))
               for v, m in cols]
    # the selection as a column of the scan, so that it is sharded too
    stacked.append((sel.reshape(-1, cap), None))
    mesh = Mesh(np.array(jax.devices()[:devs]), (SHARD_AXIS,))
    seen = {}

    def device_fn(cols, counts):
        view, pad = X.dense_view(s_local * cap, s_local)
        assert not pad
        flat, base = spmd._flatten_block(list(cols), counts, view)
        batch = X.DeviceBatch(
            [(v, True if m is None else m) for v, m in flat[:-1]],
            base & flat[-1][0], stacked=s_local)
        states = X._agg_partial_states(
            agg, batch, Evaluator(jnp, platform="tpu"), {})
        seen["limbs"] = batch.facts["agg_limbs"]
        return spmd._collective_merge(states, SHARD_AXIS, devs)
    got = jax.jit(shard_map(
        device_fn, mesh=mesh, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P()))(stacked, counts)
    assert seen["limbs"] == 13
    want, _ = _states(agg, cols, sel & live, "cpu")
    _assert_same(jax.tree_util.tree_map(np.asarray, got), want, agg.aggs)


@pytest.mark.parametrize("view", [False, True])
def test_flatten_block_pins_without_changing_a_live_row(view):
    s, cap = 4, 1024
    rng = np.random.default_rng(3)
    cols = [(rng.integers(-100, 100, (s, cap)).astype(np.int16), None),
            (rng.integers(0, 9, (s, cap)).astype(np.int64),
             rng.random((s, cap)) > 0.3)]
    counts = np.array([cap, 17, 0, cap - 1], np.int64)
    shape = X.dense_view(s * cap, s)[0] if view else None
    flat, live = jax.jit(
        lambda c, k: spmd._flatten_block(c, k, shape))(cols, counts)
    want = (np.arange(cap)[None, :] < counts[:, None]).reshape(-1)
    assert np.asarray(live).tolist() == want.tolist()
    for (v, m), (fv, fm) in zip(cols, flat):
        assert fv.dtype == v.dtype
        assert np.asarray(fv)[want].tolist() == v.reshape(-1)[want].tolist()
        if m is not None:
            assert np.asarray(fm)[want].tolist() \
                == m.reshape(-1)[want].tolist()


def test_rollup_levels():
    """WITH ROLLUP level by level (`_expand_level_states`, the TPU's form)
    against the materialised Expand of the CPU's."""
    n = 3000
    rng = np.random.default_rng(11)
    a = rng.integers(0, 3, n).astype(np.int8)
    b = rng.integers(0, 2, n).astype(np.int8)
    v = rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)
    cols = [(a, None), (b, rng.random(n) > 0.2), (v, rng.random(n) > 0.1)]
    scan = D.TableScan((0, 1, 2), (I64, I64N, I64N))
    exp = D.Expand(scan, (ColumnRef(I64, 0), ColumnRef(I64N, 1)), 3)
    vref = ColumnRef(I64N, 2)
    aggs = (D.AggDesc(COUNT, None, I64),
            D.AggDesc(SUM, vref, sum_out_dtype(I64)),
            D.AggDesc(MIN, vref, I64N), D.AggDesc(COUNT, vref, I64))
    agg = D.Aggregation(
        exp, (ColumnRef(I64N, 3), ColumnRef(I64N, 4), ColumnRef(I64, 5)),
        aggs, D.GroupStrategy.DENSE, domain_sizes=(4, 3, 3))
    sel = rng.random(n) > 0.1

    def run(platform):
        limbs = []

        def fn(cols, sel):
            scan_cols = [(v, True if m is None else m) for v, m in cols]
            states, batch = X.agg_states(
                agg, scan_cols, sel, Evaluator(jnp, platform=platform), ())
            limbs.append(batch.facts["agg_limbs"])
            return states
        return (jax.tree_util.tree_map(np.asarray, jax.jit(fn)(cols, sel)),
                limbs[0])
    (got, limbs), (want, none) = run("tpu"), run("cpu")
    assert (limbs, none) == (2 + 2, 0)
    _assert_same(got, want, aggs)
    assert int(got["__rows__"].sum()) == 3 * int(sel.sum())


def test_tpch_q1_against_a_python_int_oracle():
    """TPC-H Q1's eight aggregates over `gen_lineitem` at toy size, through
    the plan the session builds (DENSE, six groups, narrow slots)."""
    from tidb_tpu.testing.tpch import (built_tpch_plans, gen_lineitem,
                                       tpch_plan_session)
    sql = ("select l_returnflag, l_linestatus, sum(l_quantity), "
           "sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)), "
           "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)), "
           "avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*) "
           "from lineitem where l_shipdate <= date '1998-09-02' "
           "group by l_returnflag, l_linestatus")
    sess = tpch_plan_session(0.001)
    (_sql, phys), = built_tpch_plans(sess, [sql])
    op = phys
    while not hasattr(op, "dag"):
        op = op.children[0]
    agg = op.dag
    assert agg.strategy == D.GroupStrategy.DENSE and agg.num_groups == 6
    assert agg.narrow_sums

    names, table = gen_lineitem(sf=0.001, seed=42)
    col = {name: c.narrowed() for name, c in zip(names, table)}
    n = len(col["l_quantity"])
    pad = -n % 1024
    scan_cols = [(np.pad(c.narrowed(), (0, pad)), None)
                 if c.narrowed().dtype != object
                 else (np.zeros(n + pad, np.int8), None) for c in table]

    def run(platform):
        limbs = []

        def fn(scan_cols):
            states, batch = X.agg_states(
                agg, [(v, True) for v, _m in scan_cols], jnp.int64(n),
                Evaluator(jnp, platform=platform), (), 2)
            limbs.append(batch.facts["agg_limbs"])
            return states
        return (jax.tree_util.tree_map(
            np.asarray, jax.jit(fn)(scan_cols)), limbs[0])
    (got, limbs), (want, _none) = run("tpu"), run("cpu")
    # quantity 1 limb, price 2, discount 1, the two products 3 each, count
    assert limbs == 1 + 2 + 1 + 3 + 3 + 1
    assert col["l_quantity"].dtype == np.int16 \
        and col["l_extendedprice"].dtype == np.int32 \
        and col["l_discount"].dtype == np.int8
    _assert_same(got, want, agg.aggs)

    cutoff = (datetime.date(1998, 9, 2) - datetime.date(1970, 1, 1)).days
    live = col["l_shipdate"].astype(np.int64) <= cutoff
    gid = col["l_returnflag"].astype(np.int64) * 2 + col["l_linestatus"]
    qty, price, disc, tax = ([int(x) for x in col[c]] for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    args = [qty, price,
            [p * (100 - d) for p, d in zip(price, disc)],
            [p * (100 - d) * (100 + t) for p, d, t in zip(price, disc, tax)],
            qty, None, price, None, disc, None, None]
    assert len(args) == len(agg.aggs)
    for i, (a, vals) in enumerate(zip(agg.aggs, args)):
        rows = [np.flatnonzero(live & (gid == g)) for g in range(6)]
        if a.func == COUNT:
            assert got[f"a{i}"]["count"].tolist() == [len(r) for r in rows]
        else:
            assert _total(got[f"a{i}"]) == [
                sum(vals[r] for r in rs) for rs in rows], str(a)
            assert got[f"a{i}"]["cnt"].tolist() == [len(r) for r in rows]
    assert math.prod(agg.domain_sizes) == 6


Q1_SQL = ("select l_returnflag, l_linestatus, sum(l_quantity), "
          "sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)), "
          "avg(l_discount), count(*) from lineitem "
          "where l_shipdate <= date '1998-09-02' "
          "group by l_returnflag, l_linestatus")
JOIN_SQL = ("select p_brand, sum(l_extendedprice), count(*) from lineitem, "
            "part where l_partkey = p_partkey and l_quantity < 10 "
            "group by p_brand")


@pytest.mark.parametrize("sql,platform,pinned,limbs", [
    (Q1_SQL, "tpu", True, 1 + 1 + 2 + 3 + 1),
    (JOIN_SQL, "tpu", False, 1 + 2),
    (Q1_SQL, "cpu", False, 0)])
def test_whole_statement_through_the_sharded_program(monkeypatch, sql,
                                                     platform, pinned, limbs):
    """The statement over the CPU mesh with every program traced as for
    `platform`: the answer equals the host engine's; `/sched` counts the
    launch of a DENSE aggregation and whether it took the limb form, and
    the `sched.launch` span says its lanes a row; only a program that
    joins nothing has its columns pinned to the view
    (`ShardedCopProgram._device_fn`)."""
    from tidb_tpu.parallel import get_mesh
    from tidb_tpu.sched import scheduler_for
    from tidb_tpu.testing.tpch import tpch_plan_session
    want = sorted(tpch_plan_session(0.001).execute(sql).rows)

    sess = tpch_plan_session(0.001)
    dom = sess.domain
    dom.client._platform = lambda: "tpu"    # the device path
    sess.execute("set global tidb_tpu_trace_sample = 1")
    monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: platform)
    views = []
    monkeypatch.setattr(
        spmd, "_flatten_block",
        lambda cols, counts, view=None, real=spmd._flatten_block:
        views.append(view) or real(cols, counts, view))
    spmd._cached.cache_clear()
    sched = scheduler_for(get_mesh())
    try:
        before = sched.stats()
        got = sorted(sess.execute(sql).rows)
        after = sched.stats()
    finally:
        spmd._cached.cache_clear()
    assert got == want
    assert views and {v is not None for v in views} == {pinned}
    assert [after[k] - before[k] for k in (
        "dense_agg_launches", "dense_agg_limb_launches")] == [1, int(limbs > 0)]
    launches = [sp.attrs for ent in dom.flight_recorder.index()
                for sp in dom.flight_recorder.get(ent["trace_id"]).spans
                if sp.name == "sched.launch"
                and "_agg_dense_" in sp.attrs.get("program", "")]
    assert [a.get("agg_limbs", 0) for a in launches] == [limbs]


def test_batched_and_fused_programs_equal_solo(monkeypatch):
    """The vmapped batched program and a fused program, traced as for a
    TPU over the 8-device CPU mesh (two stacked shards a device, every
    column pinned to the view): each slot and member equals the solo
    program's states, and those the scatter branch's."""
    from tidb_tpu.parallel import get_mesh
    from tidb_tpu.parallel.mesh import sharded
    mesh = get_mesh()
    s, cap = 2 * len(mesh.devices.reshape(-1)), 1024
    monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
    inputs, want = [], []
    for seed in (1, 2):
        cols, _sel, agg = _table(s * cap, 6, seed=seed)
        counts = np.array([cap - 5 * i for i in range(s)], np.int64)
        live = (np.arange(cap)[None, :] < counts[:, None]).reshape(-1)
        want.append(_states(agg, cols, live, "cpu")[0])
        put = lambda a: jax.device_put(a.reshape(s, cap), sharded(mesh))  # noqa: E731
        inputs.append(([(put(v), None if m is None else put(m))
                        for v, m in cols],
                       jax.device_put(counts, sharded(mesh))))
    other = D.Aggregation(agg.child, agg.group_by, agg.aggs[:2],
                          D.GroupStrategy.DENSE,
                          domain_sizes=agg.domain_sizes)
    try:
        solo = spmd.ShardedCopProgram(agg, mesh)
        got = [jax.tree_util.tree_map(np.asarray, solo(c, k))
               for c, k in inputs]
        assert solo.facts(*inputs[0]) == {"agg_limbs": 13}
        batched = spmd.BatchedCopProgram(agg, mesh, 2)(
            [c for c, _k in inputs], [k for _c, k in inputs])
        fused = spmd.FusedCopProgram(D.FusedDag((agg, other)), mesh)
        members = fused(*inputs[0])
        # the second member: the row count, an argument's count, one limb
        assert fused.facts(*inputs[0]) == {"agg_limbs": 13 + 3}
    finally:
        spmd._cached.cache_clear()
    for g, w, b in zip(got, want, batched):
        _assert_same(g, w, agg.aggs)
        _assert_same(jax.tree_util.tree_map(np.asarray, b), g, agg.aggs)
    first = jax.tree_util.tree_map(np.asarray, members[0])
    _assert_same(first, got[0], agg.aggs)
    assert np.asarray(members[1]["a1"]["lo"]).tolist() \
        == got[0]["a1"]["lo"].tolist()
