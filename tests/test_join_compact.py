"""The lookup join's probe compaction (dag.LookupJoin `probe_capacity`,
copr/exec `_compact_probe`, copr/join `live_rows` / `gather_rows`), which
no statement reaches on the CPU mesh (the executor engages it only where a
gather costs its indices): the lowering itself against the uncompacted
join and a nested loop, then whole statements over the CPU mesh with every
program lowered as for a TPU, as tests/test_dense_agg.py does."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tidb_tpu.chunk.column import Column
from tidb_tpu.copr import dag as D
from tidb_tpu.copr import exec as X
from tidb_tpu.copr import join as J
from tidb_tpu.copr.joinbuild import prepare_build
from tidb_tpu.expr import ColumnRef, Func
from tidb_tpu.expr.ir import Const
from tidb_tpu.expr.compile import Evaluator
from tidb_tpu.parallel import get_mesh, spmd
from tidb_tpu.parallel.mesh import SHARD_AXIS, sharded
from tidb_tpu.sched import scheduler_for
from tidb_tpu.session import Domain, Session
from tidb_tpu.session.catalog import TableInfo
from tidb_tpu.types import dtypes as dt

I64, I64N, F64 = dt.bigint(False), dt.bigint(True), dt.double()
N, C = 4096, 512        # probe slots, the compaction's capacity

# build sides: name -> (key, w, s | None) rows
BUILDS = {
    # every key of a range: direct addressing, no presence bit
    "dense": [(k, 100 + k, None if k % 5 == 0 else k * k)
              for k in range(1, 41)],
    # keys missing from the range: direct addressing with holes
    "holes": [(k, 100 + k, k + 7) for k in range(1, 41) if k % 3],
    # a range no table can span: the sorted, binary-searched form
    "sparse": [(k, 100 + k, k + 1) for k in (2, 5, 17, 1000, 70_000,
                                             9_000_000_000)],
}


def _probe(seed=0):
    """Probe columns of every physical width, NULL and out-of-range keys:
    (key int32 nullable, int64, int8 nullable, int16, bool, float32,
    float64) and the rows as Python tuples (None for NULL)."""
    rng = np.random.default_rng(seed)
    key = rng.integers(1, 45, N).astype(np.int32)
    key[::17] = -5                      # below every build range
    key[5::23] = 2_000_000_000          # above it
    key[3::29] = 70_000
    kvalid = rng.random(N) > 0.1
    v64 = rng.integers(-2 ** 40, 2 ** 40, N).astype(np.int64)
    v8 = rng.integers(-128, 128, N).astype(np.int8)
    v8valid = rng.random(N) > 0.3
    v16 = rng.integers(-2 ** 15, 2 ** 15, N).astype(np.int16)
    flag = rng.random(N) > 0.5
    f32 = rng.random(N).astype(np.float32)
    f64 = rng.random(N)
    cols = [(key, kvalid), (v64, None), (v8, v8valid), (v16, None),
            (flag, None), (f32, None), (f64, None)]
    rows = [tuple(None if m is not None and not m[i] else v[i].item()
                  for v, m in cols) for i in range(N)]
    return cols, rows


COLS = D.COMPACT_COLUMNS

# selection: (live rows, the capacity they take: the fullest of the
# compaction's interleaved columns times the columns)
LIVES = {"0": (0, 0), "1": (1, COLS), "C-1": (C - 1, C), "C": (C, C),
         "C+1": (C + 1, C + COLS), "run": (C, C), "all": (N, N)}


def _sel(case: str, seed=1):
    """`C`: every column holds exactly its share of the capacity, at
    random rows; `C-1` / `C+1`: one row fewer / one column one row over;
    `run`: C consecutive rows, which the interleaving spreads evenly."""
    sel = np.zeros(N, bool)
    grid = sel.reshape(N // COLS, COLS)         # a view: slot i, column i % COLS
    rng = np.random.default_rng(seed)
    if case == "1":
        sel[777] = True
    elif case == "run":
        sel[1000:1000 + C] = True
    elif case == "all":
        sel[:] = True
    elif case != "0":
        for t in range(COLS):
            grid[rng.permutation(N // COLS)[:C // COLS], t] = True
        if case == "C-1":
            sel[np.nonzero(sel)[0][17]] = False
        elif case == "C+1":
            grid[np.nonzero(~grid[:, 5])[0][3], 5] = True
    assert sel.sum() == LIVES[case][0]
    return sel


def _build(name):
    rows = BUILDS[name]
    keys = np.array([k for k, _w, _s in rows], np.int64)
    cols = [(keys, np.ones(len(rows), bool)),
            (np.array([w for _k, w, _s in rows], np.int64),
             np.ones(len(rows), bool)),
            (np.array([0 if s is None else s for _k, _w, s in rows],
                      np.int64),
             np.array([s is not None for _k, _w, s in rows]))]
    side = prepare_build(keys, cols, key_col=0)
    assert side.unique and side.dense == (name != "sparse")
    return side


def _join(side, kind, capacity):
    scan = D.TableScan(tuple(range(7)),
                       (I64N, I64, I64N, I64, I64, F64, F64))
    return D.LookupJoin(scan, probe_key=ColumnRef(I64N, 0), kind=kind,
                        build_dtypes=(I64, I64, I64N), dense=side.dense,
                        packing=side.packing, probe_capacity=capacity)


def _run(node, cols, sel, aux, stacked=1):
    """`_exec_node` traced as for a TPU -> (the live output rows as
    tuples, None for NULL, sorted; extras)."""
    def fn(cols, sel, aux):
        cols = [(v, True if m is None else m) for v, m in cols]
        aux = tuple(tuple((v, True if m is None else m) for v, m in g)
                    for g in aux)
        batch = X._exec_node(node, cols, sel, Evaluator(jnp, platform="tpu"),
                             aux, stacked)
        n = len(batch.cols[0][0])
        return ([(X._ensure_array(v, n), X._sel_array(m, n))
                 for v, m in batch.cols], X._sel_array(batch.sel, n),
                batch.extras)
    out, osel, extras = jax.tree_util.tree_map(
        np.asarray, jax.jit(fn)(cols, sel, aux))
    rows = [tuple(v[i].item() if m[i] else None for v, m in out)
            for i in np.nonzero(osel)[0]]
    return sorted(rows, key=repr), extras


def _nested_loop(rows, sel, build, kind):
    out = []
    for i in np.nonzero(sel)[0]:
        hit = [b for b in build if rows[i][0] is not None
               and rows[i][0] == b[0]]
        if hit:
            out.append(rows[i] + hit[0])
        elif kind == "left":
            out.append(rows[i] + (None, None, None))
    return sorted(out, key=repr)


@pytest.mark.parametrize("live", list(LIVES))
@pytest.mark.parametrize("build", list(BUILDS))
@pytest.mark.parametrize("kind", ["inner", "left"])
def test_compacted_equals_uncompacted_and_a_nested_loop(kind, build, live):
    """The same rows, NULL and out-of-range probe keys and NULL build
    values included; the live count and the capacity it takes are
    reported exactly, and where that exceeds the join's capacity the
    launch holds some of the rows and is good for nothing else."""
    cols, rows = _probe()
    sel = _sel(live)
    side = _build(build)
    want = _nested_loop(rows, sel, BUILDS[build], kind)
    plain, extras = _run(_join(side, kind, 0), cols, sel, (side.aux,))
    assert plain == want and "join_need" not in extras
    # the integer columns packed into the one gather, the doubles apart;
    # the slots one run, and four stacked shards read tile by tile
    for stacked in (1, 4):
        got, extras = _run(_join(side, kind, C), cols, sel, (side.aux,),
                           stacked)
        assert (int(extras["join_live"]), int(extras["join_need"])) \
            == LIVES[live]
        if LIVES[live][1] <= C:
            assert got == want, stacked
        else:
            # a left join puts out every live row: some are missing
            assert set(got) <= set(want)
            assert kind == "inner" or len(got) < len(want)


@pytest.mark.parametrize("est,rows,want", [
    # no estimate: nothing is sized from a guess
    (0, 1 << 23, 0), (-1.0, 1 << 23, 0),
    # TPC-H SF1 on one chip: Q14 keeps one row in 84, Q19 one in 28
    (71_400, 6_001_215, 114_688), (214_000, 6_001_215, 327_680),
    # the same queries at SF10
    (714_000, 60_012_150, 1_048_576),
    # a filter that keeps a tenth, or 18 % (chip_smoke's join): no
    (600_000, 6_001_215, 0), (1_080_000, 6_001_215, 0),
    # a device's share of an 8-device mesh; the least capacity; a table
    # too small for it
    (655.4, 65_536, 3_072), (1, 1 << 20, 1_024), (1, 8_191, 0)])
def test_probe_capacity_for(est, rows, want):
    cap = D.probe_capacity_for(est, rows)
    assert cap == want and cap % D.COMPACT_COLUMNS == 0
    if cap:
        # room for the estimate's error and for the fullest column
        assert 1.25 * est < cap <= rows // 8


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32, np.int64,
                                   np.uint8, np.uint16, np.uint32,
                                   np.uint64, np.bool_, np.float32])
def test_gather_rows_keeps_every_bit(dtype):
    """The packed words give back a column's extremes, its NULL mask and
    its neighbours' bits untouched, whatever shares its word."""
    rng = np.random.default_rng(3)
    if dtype == np.bool_:
        v = rng.random(N) > 0.5
    elif dtype == np.float32:
        v = rng.standard_normal(N).astype(np.float32)
        v[:3] = [np.inf, -0.0, np.finfo(np.float32).max]
    else:
        info = np.iinfo(dtype)
        v = rng.integers(info.min, info.max, N, dtype=dtype, endpoint=True)
        v[:2] = [info.min, info.max]
    m = rng.random(N) > 0.4
    other = rng.integers(-128, 128, N).astype(np.int8)
    rows = rng.permutation(N)[:C].astype(np.int32)
    got = jax.jit(lambda cols, rows: [
        (gv, None if gm is True else gm) for gv, gm in J.gather_rows(
            [(a, True if b is None else b) for a, b in cols], rows)])(
        [(other, None), (v, m), (other, m)], rows)
    for (gv, gm), (wv, wm) in zip(got, [(other, None), (v, m), (other, m)]):
        assert np.asarray(gv).dtype == wv.dtype
        assert np.array_equal(np.asarray(gv), wv[rows])
        assert (gm is None) if wm is None \
            else np.array_equal(np.asarray(gm), wm[rows])


@pytest.mark.parametrize("stacked", [1, 8, 3])
@pytest.mark.parametrize("case", list(LIVES))
def test_live_rows_holds_every_live_row_where_it_fits(case, stacked):
    """Whatever runs the slots are viewed as (eight stacked shards; three,
    which do not divide them and are read as one), a column holds the
    same slots.  What comes back are places in `_tile_order`, the order
    `gather_rows` reads the columns in."""
    sel = _sel(case, seed=3)
    places, ok, need = jax.tree_util.tree_map(
        np.asarray, jax.jit(J.live_rows, static_argnums=(1, 2))(
            sel, C, stacked))
    rows = np.asarray(J._tile_order(jnp.arange(N), stacked))[places]
    assert int(need) == LIVES[case][1]
    assert ((0 <= rows) & (rows < N)).all() and sel[rows[ok]].all()
    assert len(set(rows[ok].tolist())) == ok.sum()
    if need <= C:
        assert sorted(rows[ok].tolist()) == np.nonzero(sel)[0].tolist()
    # within one of the interleaved columns the rows keep their order
    grid = np.where(ok, places, N).reshape(C // COLS, COLS)
    assert (np.diff(grid, axis=0) >= 0).all()
    assert (rows[ok] % COLS == np.nonzero(ok)[0] % COLS).all()


def test_under_vmap_each_slot_compacts_its_own_rows():
    """As a batched program would run it: slots do not mix."""
    cols, rows = _probe()
    side = _build("holes")
    node = _join(side, "inner", C)
    sels = np.stack([_sel("1"), _sel("C", 6), _sel("0")])

    def one(sel):
        batch = X._exec_node(
            node, [(jnp.asarray(v), True if m is None else jnp.asarray(m))
                   for v, m in cols], sel, Evaluator(jnp, platform="tpu"),
            (tuple((v, True if m is None else m) for v, m in side.aux),))
        w = batch.cols[8][0]
        return jnp.sum(jnp.where(batch.sel, w, 0)), batch.extras["join_live"]
    sums, lives = jax.jit(jax.vmap(one))(sels)
    assert lives.tolist() == [1, C, 0]
    for k, sel in enumerate(sels):
        want = _nested_loop(rows, sel, BUILDS["holes"], "inner")
        assert int(sums[k]) == sum(r[8] for r in want)


def test_chained_joins_compact_once_at_the_lowest():
    """The join above runs on the lowest join's slots; the contract
    refuses a compaction above another join."""
    from tidb_tpu.analysis.contracts import PlanContractError, verify_dag
    cols, rows = _probe()
    low, high = _build("dense"), _build("holes")
    first = _join(low, "inner", C)
    # the second level probes with the first's `w - 100`, the key again
    key2 = Func(I64, "sub", (ColumnRef(I64, 8), Const(I64, 100)))
    second = D.LookupJoin(first, probe_key=key2, kind="inner",
                          build_dtypes=(I64, I64, I64N), dense=high.dense,
                          packing=high.packing, aux_slot=1)
    sel = _sel("C-1")
    got, extras = _run(second, cols, sel, (low.aux, high.aux))
    assert int(extras["join_live"]) == C - 1
    want = sorted((r + b for r in _nested_loop(rows, sel, BUILDS["dense"],
                                               "inner")
                   for b in BUILDS["holes"] if b[0] == r[8] - 100), key=repr)
    assert got == want and len(want) > 50
    assert D.compacting_join(second) is first and D.has_extras(second)
    import dataclasses

    def agg(join):
        return D.Aggregation(join, (), (D.AggDesc(D.AggFunc.COUNT, None,
                                                  I64),),
                             D.GroupStrategy.SCALAR)
    verify_dag(agg(second))
    for broken, why in (
            (dataclasses.replace(second, probe_capacity=128), "lowest"),
            (dataclasses.replace(second, child=dataclasses.replace(
                first, kind="semi")), "unique"),
            (dataclasses.replace(second, child=dataclasses.replace(
                first, probe_capacity=100)), "multiple")):
        with pytest.raises(PlanContractError, match=why):
            verify_dag(agg(broken))
    # the kept rows come in no order: only an aggregation may sit above
    with pytest.raises(PlanContractError, match="order"):
        verify_dag(second)
    with pytest.raises(PlanContractError, match="order"):
        verify_dag(D.TopN(first, sort_keys=((ColumnRef(I64, 1), False),),
                          limit=5))


# --------------------------------------------------------------------- #
# whole statements over the CPU mesh, lowered as for a TPU
# --------------------------------------------------------------------- #

ROWS = 8 * 65536        # 65536 rows a device of the 8-device CPU mesh
DIM = [(k, 100 + k, None if k % 5 == 0 else k * k) for k in range(1, 41)]
DIM2 = [(k, 3 * k + 1) for k in range(1, 41) if k % 7]     # a second dimension


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


def _fact(rng):
    k = rng.integers(1, 48, ROWS)           # 41..47 match nothing
    k[::19] = -3
    a = rng.integers(0, 1000, ROWS)
    v = rng.permutation(ROWS) - ROWS // 2   # distinct: a total order
    return {"k": (k, rng.random(ROWS) > 0.05), "a": (a, None),
            "b": (a.copy(), None), "v": (v, None)}


@pytest.fixture(scope="module")
def star():
    """(domain, fact columns): `fact` (k nullable, a, b = a, v) ANALYZEd,
    `dim` (k 1..40, w, s nullable), `dim2` (k2, z), the engine pinned to
    the device path."""
    dom = Domain()
    fact = _fact(np.random.default_rng(7))
    cols = [Column(dt.bigint(m is not None), d.astype(np.int64),
                   np.ones(ROWS, bool) if m is None else m)
            for d, m in fact.values()]
    info = TableInfo("fact", list(fact), [c.dtype for c in cols])
    info.register_columns(cols)
    dom.catalog.create_table("test", info)
    s = Session(dom)
    s.execute("create table dim (k bigint, w bigint, s bigint)")
    s.execute("insert into dim values " + ", ".join(
        f"({k}, {w}, {'null' if x is None else x})" for k, w, x in DIM))
    s.execute("create table dim2 (k2 bigint, z bigint)")
    s.execute("insert into dim2 values " + ", ".join(
        f"({k}, {z})" for k, z in DIM2))
    s.execute("analyze table fact")
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    s.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    yield dom, fact
    _forget_programs()


def _joined(fact, keep):
    """The join's rows in `fact` order: (v, w, s) of every kept fact row
    whose key is a dim key."""
    (k, kv), (v, _) = fact["k"], fact["v"]
    dim = {d[0]: d for d in DIM}
    return [(int(v[i]), dim[int(k[i])][1], dim[int(k[i])][2])
            for i in np.nonzero(keep & kv)[0] if int(k[i]) in dim]


def _statement(dom, sql):
    """-> (rows, the join launch's span attributes, `/sched` deltas of
    the join counters)."""
    sched = scheduler_for(get_mesh())
    names = ("join_launches", "join_compact_launches",
             "join_compact_overflows", "join_host_fallbacks")
    before = sched.stats()
    sess = Session(dom)
    rows = sess.execute(sql).rows
    after = sched.stats()
    spans = [sp.attrs for sp in sess.last_trace.spans
             if sp.name == "sched.launch" and "join" in sp.attrs]
    live = [sp.attrs["probe_live"] for sp in sess.last_trace.spans
            if sp.name == "cop.transfer" and "probe_live" in sp.attrs]
    return rows, spans, live, [after[n] - before[n] for n in names]


AGG = "select sum(v * w), count(*), count(s) from fact, dim " \
      "where fact.k = dim.k and "


def _agg_of(rows):
    return [(sum(v * w for v, w, _s in rows) if rows else None, len(rows),
             sum(s is not None for _v, _w, s in rows))]


def test_a_filtered_probe_side_compacts(star, lowered_for):
    dom, fact = star
    lowered_for("tpu")
    rows, (span,), live, delta = _statement(dom, AGG + "a < 20")
    want = _joined(fact, fact["a"][0] < 20)
    assert rows == _agg_of(want) and len(want) > 3000
    # the estimate: 2 % of 524,288 rows, a device's share, a quarter more
    # and six deviations of an interleaved column's share
    assert span["probe_capacity"] == 4096 and span["join"] == "unique"
    assert live == [int((fact["a"][0] < 20).sum())]
    assert delta == [1, 1, 0, 0]


def test_an_overflow_is_exact_counted_and_remembered(star, lowered_for):
    """`b` is `a` again: the planner multiplies the two selectivities (1 %)
    and the filter keeps 10 %, twice the capacity.  The statement's
    answer is the exact program's; the digest overflows once."""
    dom, fact = star
    lowered_for("tpu")
    sql = AGG + "a < 100 and b < 100"
    want = _agg_of(_joined(fact, fact["a"][0] < 100))
    rows, spans, live, delta = _statement(dom, sql)
    assert rows == want
    assert [s.get("probe_capacity", 0) for s in spans] == [3072, 0]
    assert live == [int((fact["a"][0] < 100).sum())]
    assert delta == [2, 1, 1, 0]
    rows, spans, live, delta = _statement(dom, sql)
    assert rows == want and not live
    assert [s.get("probe_capacity", 0) for s in spans] == [0]
    assert delta == [1, 0, 0, 0]


def test_a_chain_that_overflows_is_exact_counted_and_remembered(
        star, lowered_for):
    """Two dimensions: the lowest join of the chain carries the capacity,
    and it is that join an overflow switches to the exact form, for this
    statement and for the digest."""
    dom, fact = star
    lowered_for("tpu")
    sql = "select sum(v * z), count(*), count(s) from fact, dim, dim2 " \
          "where fact.k = dim.k and fact.k = dim2.k2 and a < 100 and b < 100"
    z = dict(DIM2)
    (k, _kv) = fact["k"]
    keep = (fact["a"][0] < 100) & np.isin(k, list(z))
    rows = [(v * z[int(k[i])], s) for i, (v, _w, s) in zip(
        np.nonzero(keep & fact["k"][1] & np.isin(k, [d[0] for d in DIM]))[0],
        _joined(fact, keep))]
    want = [(sum(vz for vz, _s in rows), len(rows),
             sum(s is not None for _vz, s in rows))]
    assert len(rows) > 3072
    got, spans, live, delta = _statement(dom, sql)
    assert got == want
    assert [s.get("probe_capacity", 0) for s in spans] == [3072, 0]
    assert live == [int((fact["a"][0] < 100).sum())]
    assert delta == [2, 1, 1, 0]
    got, spans, live, delta = _statement(dom, sql)
    assert got == want and not live
    assert [s.get("probe_capacity", 0) for s in spans] == [0]
    assert delta == [1, 0, 0, 0]


@pytest.mark.parametrize("overflows", [False, True])
def test_a_grouped_aggregation_above_a_compacted_join(star, lowered_for,
                                                      overflows):
    """GROUP BY a build column: the grouped states are built from the
    capacity's slots; where they do not fit, from the exact program's."""
    dom, fact = star
    lowered_for("tpu")
    cond = "a < 120 and b < 120" if overflows else "a < 20"
    sql = "select w, sum(v), count(s) from fact, dim where fact.k = dim.k " \
          f"and {cond} group by w order by w"
    groups: dict = {}
    for v, w, s in _joined(fact, fact["a"][0] < (120 if overflows else 20)):
        g = groups.setdefault(w, [0, 0])
        g[0] += v
        g[1] += s is not None
    got, spans, _live, delta = _statement(dom, sql)
    assert got == [(w, g[0], g[1]) for w, g in sorted(groups.items())]
    assert len(got) == 40
    assert [s.get("probe_capacity", 0) > 0 for s in spans] \
        == ([True, False] if overflows else [True])
    assert delta == ([2, 1, 1, 0] if overflows else [1, 1, 0, 0])


@pytest.mark.parametrize("root,sql", [
    ("topn", "select v, w, s from fact, dim where fact.k = dim.k "
             "and a < 20 order by w, v desc limit 25"),
    ("rows", "select v, w, s from fact, dim where fact.k = dim.k "
             "and a < 20"),
    ("limit", "select v, w, s from fact, dim where fact.k = dim.k "
              "and a < 20 limit 7")])
def test_a_root_that_reads_row_order_keeps_todays_program(star, lowered_for,
                                                          root, sql):
    """The compacted slots come in no order, so a TopN (it ties on the
    row index), a LIMIT and a plain row stream above the join are not
    compacted: the program, its name and its rows are the CPU mesh's.
    (A plain row stream's rows leave a TPU's program in no order either,
    by the same column sort: exec.compact_root.)"""
    dom, fact = star
    lowered_for("cpu")
    plain, (cpu,), _live, _delta = _statement(dom, sql)
    lowered_for("tpu")
    # (a rows root lowered for a TPU may page once more: the column sort
    # takes its fullest column's share of the capacity, not the rows')
    got, (*_paged, span), live, delta = _statement(dom, sql)
    assert "probe_capacity" not in span and not live and delta[1:] == [0] * 3
    assert span["program"] == cpu["program"]
    assert got == plain if root != "rows" else sorted(got) == sorted(plain)
    want = _joined(fact, fact["a"][0] < 20)
    if root == "topn":
        assert got == sorted(want, key=lambda r: (r[1], -r[0]))[:25]
    elif root == "rows":
        assert sorted(got) == sorted(want)
    else:
        assert len(got) == 7 and set(got) <= set(want)


def test_an_unfiltered_probe_and_the_cpu_mesh_keep_todays_program(
        star, lowered_for):
    """No filter beneath the join: nothing to compact, the DAG, its digest
    and the program's name are what they are when lowered for the CPU.
    A filtered statement lowered for the CPU keeps them too."""
    dom, fact = star
    names = {}
    for platform in ("cpu", "tpu"):
        lowered_for(platform)
        for sql in (AGG + "1 = 1", AGG + "a < 20"):
            rows, (span,), _live, delta = _statement(dom, sql)
            names[platform, sql] = span["program"]
            assert ("probe_capacity" in span) \
                == (platform == "tpu" and "a < 20" in sql)
    assert names["cpu", AGG + "1 = 1"] == names["tpu", AGG + "1 = 1"]
    assert names["cpu", AGG + "a < 20"] != names["tpu", AGG + "a < 20"]


def test_no_statistics_no_compaction(lowered_for):
    """Without ANALYZE (and with the automatic one off) there is no
    estimate: a guess sizes nothing."""
    dom = Domain()
    dom.stats.auto_analyze_enabled = False
    fact = _fact(np.random.default_rng(8))
    cols = [Column(dt.bigint(m is not None), d.astype(np.int64),
                   np.ones(ROWS, bool) if m is None else m)
            for d, m in fact.values()]
    info = TableInfo("fact", list(fact), [c.dtype for c in cols])
    info.register_columns(cols)
    dom.catalog.create_table("test", info)
    s = Session(dom)
    s.execute("create table dim (k bigint, w bigint, s bigint)")
    s.execute("insert into dim values (1, 2, 3), (2, 3, 4)")
    s.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    lowered_for("tpu")
    _rows, (span,), live, delta = _statement(dom, AGG + "a < 20")
    assert "probe_capacity" not in span and not live and delta[1] == 0


def _scalar_sum(child):
    from tidb_tpu.copr.aggregate import sum_out_dtype
    prod = Func(I64N, "mul", (ColumnRef(I64, 1), ColumnRef(I64, 8)))
    return D.Aggregation(
        child, (), (D.AggDesc(D.AggFunc.SUM, prod, sum_out_dtype(I64)),
                    D.AggDesc(D.AggFunc.COUNT, None, I64),
                    D.AggDesc(D.AggFunc.COUNT, ColumnRef(I64N, 9), I64)),
        D.GroupStrategy.SCALAR)


def _total(state):
    return (int(np.asarray(state["hi"]).reshape(-1)[0]) << 32) \
        + int(np.asarray(state["lo"]).reshape(-1)[0])


def test_over_a_four_device_mesh_each_device_compacts_its_own_shard(
        monkeypatch):
    """Two stacked shards a device; the live counts come back a device;
    the psum above the join is untouched."""
    monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
    mesh = Mesh(np.array(jax.devices()[:4]), (SHARD_AXIS,))
    cols, rows = _probe(seed=4)
    side = _build("holes")
    s, cap = 8, N // 8
    counts = np.array([cap - 7 * i for i in range(s)], np.int64)
    keep = (np.arange(cap)[None, :] < counts[:, None]).reshape(-1)
    # flag and k < 12: one row in eight, NULL keys filtered
    live = cols[4][0] & cols[0][1] & (cols[0][0] < 12)
    conds = (ColumnRef(I64, 4),
             Func(I64, "lt", (ColumnRef(I64N, 0), Const(I64, 12))))

    def put(a):
        return jax.device_put(a.reshape(s, cap), sharded(mesh))
    args = ([(put(v), None if m is None else put(m)) for v, m in cols],
            jax.device_put(counts, sharded(mesh)), (side.aux,))
    want = _nested_loop(rows, keep & live, BUILDS["holes"], "inner")
    got = {}
    for capacity in (0, 896):
        join = _join(side, "inner", capacity)
        join = D.LookupJoin(
            D.Selection(join.child, conds),
            **{f: getattr(join, f) for f in (
                "probe_key", "kind", "build_dtypes", "dense", "packing",
                "probe_capacity")})
        prog = spmd.ShardedCopProgram(_scalar_sum(join), mesh)
        out = prog(*args)
        facts = prog.facts(*args)
        assert facts["probe_capacity"] == capacity
        if capacity:
            assert prog.has_extras
            out, extras = out
            per_dev = (keep & live).reshape(4, -1).sum(axis=1)
            assert np.asarray(extras["join_live"]).tolist() \
                == per_dev.tolist()
            assert 0 < np.asarray(extras["join_need"]).max() <= 896
        got[capacity] = jax.tree_util.tree_map(np.asarray, out)
        assert _total(got[capacity]["a0"]) == sum(r[1] * r[8] for r in want)
        assert int(got[capacity]["a1"]["count"]) == len(want)
        assert int(got[capacity]["a2"]["count"]) \
            == sum(r[9] is not None for r in want)
    spmd._cached.cache_clear()


def test_a_compacting_program_is_launched_alone(monkeypatch):
    """Its extras (the live count) keep it out of a fused launch, as an
    expanding join's do; join programs take aux inputs and are launched
    alone anyway (copr/facts.py)."""
    monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
    side = _build("dense")
    mesh = get_mesh()
    try:
        exact, compact = (_scalar_sum(_join(side, "inner", c))
                          for c in (0, 256))
        spmd.FusedCopProgram(D.FusedDag((exact, exact)), mesh)
        with pytest.raises(ValueError, match="extras"):
            spmd.FusedCopProgram(D.FusedDag((exact, compact)), mesh)
    finally:
        spmd._cached.cache_clear()
        spmd._cached_fused.cache_clear()


# --------------------------------------------------------------------- #
# TPC-H Q14 and Q19 in the spec's text, against the benchmark's oracles
# --------------------------------------------------------------------- #

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _bench(kind: str, name: str):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)       # harness.exact
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"jc_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tpch():
    """LINEITEM and PART from the benchmark's generators at SF0.1
    (600,000 x 20,000 rows), ANALYZEd as the configuration does."""
    run_py = _bench("", "run")
    tables = {"lineitem": _bench("tables", "LINEITEM"),
              "part": _bench("tables", "PART")}
    data = {name: t.generate(0.1, 2147483659, list(t.TYPES))
            for name, t in tables.items()}
    dom = Domain()
    for name, t in tables.items():
        valid = np.ones(t.rows(0.1), bool)
        cols = [run_py._column(t.TYPES[c], v, valid)
                for c, v in data[name].items()]
        info = TableInfo(name, list(data[name]), [c.dtype for c in cols])
        info.register_columns(cols)
        dom.catalog.create_table("test", info)
    s = Session(dom)
    s.execute("analyze table lineitem")
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    s.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    oracle = {"LINEITEM": data["lineitem"], "PART": data["part"]}
    yield dom, {n: (m, m.prepare(oracle)) for n, m in (
        (n, _bench("classes", n)) for n in ("q14", "q19"))}
    _forget_programs()


@pytest.mark.parametrize("name", ["q14", "q19"])
def test_spec_text_equals_the_reference(tpch, lowered_for, name):
    """Each drawn parameter set: the oracle's text, one compacted launch,
    no overflow; `probe_capacity` is on the span and the live rows the
    device found fit it."""
    dom, classes = tpch
    mod, state = classes[name]
    lowered_for("tpu")
    rng = np.random.default_rng(25)
    answered = 0
    for _ in range(3):
        p = mod.draw(rng)
        rows, (span,), (live,), delta = _statement(dom, mod.sql(p))
        want = mod.answer(state, p)
        assert [tuple(None if v is None else str(v) for v in r)
                for r in rows] == want
        answered += want[0][0] is not None
        # a capacity a device: whole rows of the column view, at most an
        # eighth of its 75,000 rows; the live rows of all eight fit it
        capacity = span["probe_capacity"]
        assert capacity % 1024 == 0 and 0 < capacity <= 75_000 // 8
        assert delta == [1, 1, 0, 0] and 0 < live <= 8 * capacity
    assert answered
