"""The window form of the direct-addressed lookup (dag.LookupJoin
`probe_window`, copr/join `_window_reader`): a probe whose key lies in
key order reads its table by windows, one a block of 128 probe rows,
not by an index a row.  No statement reaches it on the CPU mesh (the
executor engages it only where a gather costs its indices): the
lowering itself against the gather form bit for bit, the pure rule, the
ANALYZE fact, then whole statements over the CPU mesh with every program
lowered as for a TPU, as tests/test_join_compact.py does, and one
ahead-of-time compile of the lowering at TPC-H SF1's shapes for a v5e."""

import dataclasses
import hashlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.chunk.column import Column
from tidb_tpu.copr import dag as D
from tidb_tpu.copr import exec as X
from tidb_tpu.copr import join as J
from tidb_tpu.copr.joinbuild import APART, prepare_build
from tidb_tpu.expr import ColumnRef, Func
from tidb_tpu.expr.compile import Evaluator
from tidb_tpu.expr.ir import Const
from tidb_tpu.parallel import get_mesh, spmd
from tidb_tpu.session import Domain, Session
from tidb_tpu.session.catalog import TableInfo
from tidb_tpu.stats.handle import never_decreases
from tidb_tpu.types import dtypes as dt

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
I64, I64N, F64N = dt.bigint(False), dt.bigint(True), dt.double(True)
COLS = D.COMPACT_COLUMNS
N = 8192                # probe slots: 64 blocks, 8 a stacked run of eight


def _bench(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, as the harness loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)       # harness.exact
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"jw_{kind}_{name}", path)
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
# the lowering against the gather form
# --------------------------------------------------------------------- #

def _order_keys(n):
    """TPC-H's order keys: the first 8 of every 32."""
    i = np.arange(n, dtype=np.int64)
    return (i >> 3 << 5) + (i & 7) + 1


# probe keys in key order -> (keys of the build side, the probe's keys)
def _dense(rng):
    return np.arange(1, 3000), np.sort(rng.integers(1, 3000, N))


def _sparse(rng):
    keys = _order_keys(2000)
    return keys, np.sort(rng.choice(keys, N))


def _runs(rng):
    """`lineitem`'s shape: 1..7 rows an order, and an order in ten built."""
    keys = _order_keys(2100)
    probe = np.repeat(keys, rng.integers(1, 8, len(keys)))[:N]
    assert len(probe) == N
    return keys[rng.random(len(keys)) < 0.1], probe


PATTERNS = {"dense": _dense, "sparse": _sparse, "runs": _runs}
# the windows each is read by: a block of 128 rows spans 47 slots of the
# dense table, 128 on average of the other two (`runs`: 191 at most)
WINDOWS = {"dense": (128, 256), "sparse": (256, 384), "runs": (256, 512)}


def _side(keys, rng):
    """A direct-addressed build side over `keys`: a value of 20 bits, a
    nullable one of 28 (a second word), a nullable one of 40 and a
    nullable double (each carried APART)."""
    n = len(keys)
    cols = [(keys.astype(np.int64), np.ones(n, bool)),
            (rng.integers(0, 1 << 20, n), np.ones(n, bool)),
            (rng.integers(-2 ** 27, 2 ** 27, n), rng.random(n) > 0.2),
            (rng.integers(-2 ** 39, 2 ** 39, n), rng.random(n) > 0.2),
            (rng.standard_normal(n), rng.random(n) > 0.3)]
    cols[4][0][:3] = [-0.0, np.inf, np.nan]
    side = prepare_build(keys.astype(np.int64), cols, key_col=0)
    assert side.unique and side.dense and side.packing[0] == 2
    assert [f[0] for f in side.packing[2]].count(APART) == 2
    return side


def _join(side, kind, window, capacity=0):
    scan = D.TableScan((0, 1), (I64N, I64))
    return D.LookupJoin(scan, probe_key=ColumnRef(I64N, 0), kind=kind,
                        build_dtypes=(I64, I64, I64N, I64N, F64N),
                        dense=side.dense, packing=side.packing,
                        probe_window=window, probe_capacity=capacity)


def _run(node, cols, sel, aux, stacked=1):
    """`_exec_node` traced as for a TPU -> ([(values, mask)] of every
    output column, the output selection, extras), numpy."""
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
    return jax.tree_util.tree_map(np.asarray, jax.jit(fn)(cols, sel, aux))


def _same_rows(got, want):
    """Bit for bit at every live row: validity everywhere, values where
    valid (a double by its bytes: -0.0 and NaN too)."""
    (gcols, gsel, _), (wcols, wsel, _) = got, want
    assert np.array_equal(gsel, wsel)
    for (gv, gm), (wv, wm) in zip(gcols, wcols):
        assert gv.dtype == wv.dtype
        assert np.array_equal(gm[wsel], wm[wsel])
        keep = wsel & wm
        assert gv[keep].tobytes() == wv[keep].tobytes()


@pytest.mark.parametrize("stacked", [1, 8])
@pytest.mark.parametrize("kind", ["inner", "left"])
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_windows_equal_the_gather(pattern, kind, stacked):
    """`matched`, every packed field, the column carried apart and a LEFT
    join's NULLs, on keys in key order: NULL and filtered probe rows
    everywhere, and in every stacked run a dead tail whose keys are not
    in order (what a shard's padding holds)."""
    rng = np.random.default_rng(5)
    keys, probe = PATTERNS[pattern](rng)
    side = _side(keys, rng)
    run = N // stacked
    tail = (np.arange(N) % run) >= run - 37 - (np.arange(N) // run)
    probe = np.where(tail, rng.integers(-5, 70_000, N), probe)
    sel = ~tail & (rng.random(N) < 0.54)
    cols = [(probe.astype(np.int32), rng.random(N) > 0.05),
            (rng.integers(0, 9, N), None)]
    want = _run(_join(side, kind, 0), cols, sel, (side.aux,), stacked)
    assert "join_window_miss" not in want[2]
    assert want[1].sum() > (200 if kind == "inner" else 4000)
    for window in WINDOWS[pattern]:
        got = _run(_join(side, kind, window), cols, sel, (side.aux,),
                   stacked)
        assert int(got[2]["join_window_miss"]) == 0, window
        _same_rows(got, want)


def _live_rows(out):
    cols, sel, _extras = out
    return sorted(tuple(v[i].tobytes() if m[i] else None for v, m in cols)
                  for i in np.nonzero(sel)[0])


@pytest.mark.parametrize("stacked", [1, 8])
def test_matched_rows_compacted_after_a_window_lookup(stacked):
    """`q3`'s `lineitem` level: the filter keeps half, the build a tenth,
    so the rows are compacted AFTER the lookup (`match_capacity`): the
    same rows as the gather form's, in no order, and both reports."""
    rng = np.random.default_rng(9)
    keys, probe = _runs(rng)
    side = _side(keys, rng)
    sel = rng.random(N) < 0.54
    cols = [(probe.astype(np.int32), None), (rng.integers(0, 9, N), None)]
    want = _run(_join(side, "inner", 0), cols, sel, (side.aux,), stacked)
    node = dataclasses.replace(_join(side, "inner", 256),
                               match_capacity=1024)
    got = _run(node, cols, sel, (side.aux,), stacked)
    assert int(got[2]["join_window_miss"]) == 0
    assert int(got[2]["join_live"]) == want[1].sum() > 300
    assert int(got[2]["join_need"]) <= 1024 == len(got[1])
    assert _live_rows(got) == _live_rows(want)


@pytest.mark.parametrize("stacked", [1, 8])
def test_a_block_wider_than_its_window_is_counted(stacked):
    """Keys in order that jump inside a block: the rows beyond the
    window are counted (the live ones only), and the launch is good for
    nothing else."""
    rng = np.random.default_rng(6)
    keys = np.arange(1, 40_000)
    side = _side(keys, rng)
    probe = np.sort(rng.integers(1, 3000, N))
    order = J._tile_order(jnp.arange(N), stacked)
    block = np.asarray(order).reshape(N // COLS, COLS)[11]
    far = block[100:]                    # 28 rows of one block, 20,000 on
    probe[far] += 20_000
    probe[block[-1] + 1:] += 20_000      # the key order holds
    assert (np.diff(probe.reshape(stacked, -1), axis=1) >= 0).all()
    sel = np.ones(N, bool)
    sel[far[:5]] = False
    cols = [(probe.astype(np.int32), None), (rng.integers(0, 9, N), None)]
    want = _run(_join(side, "inner", 0), cols, sel, (side.aux,), stacked)
    got = _run(_join(side, "inner", 256), cols, sel, (side.aux,), stacked)
    assert int(got[2]["join_window_miss"]) == len(far) - 5
    # with the block's stragglers filtered out nothing is missed
    sel[far] = False
    want = _run(_join(side, "inner", 0), cols, sel, (side.aux,), stacked)
    got = _run(_join(side, "inner", 256), cols, sel, (side.aux,), stacked)
    assert int(got[2]["join_window_miss"]) == 0
    _same_rows(got, want)


def test_a_table_or_a_probe_too_short_for_windows_keeps_the_gather():
    """Fewer slots than a window and a lane, or slots that are not whole
    blocks: `direct_lookup` gathers, and reports no miss."""
    rng = np.random.default_rng(7)
    side = _side(np.arange(1, 300), rng)
    probe = np.sort(rng.integers(1, 300, N))
    for window, n in ((256, N), (128, N - 5)):
        cols = [(probe[:n].astype(np.int32), None),
                (rng.integers(0, 9, n), None)]
        sel = np.ones(n, bool)
        want = _run(_join(side, "left", 0), cols, sel, (side.aux,))
        got = _run(_join(side, "left", window), cols, sel, (side.aux,))
        assert int(got[2]["join_window_miss"]) == 0
        _same_rows(got, want)


# --------------------------------------------------------------------- #
# the rule, a pure function
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("rows,span,ordered,want", [
    # TPC-H `l_orderkey` at SF1 and SF10: as many rows as the range
    (6_001_215, 6_000_000, True, 256), (59_986_052, 60_000_000, True, 256),
    # the same column, give or take the data: one window, one program
    (5_999_000, 6_000_000, True, 256), (6_003_000, 6_000_000, True, 256),
    # a primary key probed in its own order; `o_orderkey` (spread 4)
    (200_000, 200_000, True, 256), (1_500_000, 6_000_000, True, 0),
    # a key twice as sparse as its rows: the cap; just past it: the gather
    (3_000_000, 6_000_000, True, 512), (2_900_000, 6_000_000, True, 0),
    # many rows a key: the least window
    (6_000_000, 200_000, True, 128),
    # not in key order (`l_partkey`), no statistics, an empty table
    (6_001_215, 200_000, False, 0), (6_001_215, 6_000_000, False, 0),
    (0, 0, True, 0), (100, 0, True, 0)])
def test_probe_window_for(rows, span, ordered, want):
    window = D.probe_window_for(rows, span, ordered)
    assert window == want and window % COLS == 0
    assert window <= D.PROBE_WINDOW_MAX
    if window:
        # room for twice a block's mean span, less the sixty-fourth
        assert window >= 2 * COLS * span / rows * (1 - 1 / 64)


def _scalar(child):
    return D.Aggregation(child, (), (D.AggDesc(D.AggFunc.COUNT, None, I64),),
                         D.GroupStrategy.SCALAR)


@pytest.mark.parametrize("case,ok", [
    ("plain", True), ("left", True), ("under a selection", True),
    ("matched rows compacted after", True),
    ("compacted probe", False), ("above a compacting join", False),
    ("not unique", False), ("sorted form", False), ("semi", False),
    ("computed key", False), ("a build column as key", False)])
def test_which_lookups_may_read_by_windows(case, ok):
    """`dag.window_ok`: unique, direct-addressed, inner or left, probed
    with a bare column of the scan whose rows nothing has compacted out
    of their order; the contract refuses `probe_window` anywhere else."""
    from tidb_tpu.analysis.contracts import PlanContractError, verify_dag
    side = _side(np.arange(1, 300), np.random.default_rng(8))
    base = _join(side, "inner", 0)
    scan = base.child
    join = {
        "plain": base,
        "left": dataclasses.replace(base, kind="left"),
        "under a selection": dataclasses.replace(base, child=D.Selection(
            scan, (Func(I64, "lt", (ColumnRef(I64, 1), Const(I64, 5))),))),
        "matched rows compacted after": dataclasses.replace(
            base, match_capacity=1024),
        "compacted probe": dataclasses.replace(base, probe_capacity=1024),
        "above a compacting join": dataclasses.replace(
            base, aux_slot=1, child=dataclasses.replace(
                base, probe_capacity=1024)),
        "not unique": dataclasses.replace(base, unique=False,
                                          out_capacity=4096),
        "sorted form": dataclasses.replace(base, dense=False, packing=None),
        "semi": dataclasses.replace(base, kind="semi", build_dtypes=()),
        "computed key": dataclasses.replace(base, probe_key=Func(
            I64N, "add", (ColumnRef(I64N, 0), Const(I64, 1)))),
        "a build column as key": dataclasses.replace(
            base, aux_slot=1, probe_key=ColumnRef(I64, 3), child=base),
    }[case]
    assert D.window_ok(join) == ok
    windowed = _scalar(dataclasses.replace(join, probe_window=256))
    if ok:
        verify_dag(windowed)
        assert D.windowed_join(windowed) is not None
        assert D.has_extras(windowed)
        assert D.unwindowed(windowed) == _scalar(join)
        for bad in (100, 640, -128):
            with pytest.raises(PlanContractError, match="probe_window"):
                verify_dag(_scalar(dataclasses.replace(join,
                                                       probe_window=bad)))
    else:
        with pytest.raises(PlanContractError):
            verify_dag(windowed)


# --------------------------------------------------------------------- #
# ANALYZE's fact
# --------------------------------------------------------------------- #

def _col(values, valid=None):
    values = np.asarray(values)
    return Column(dt.bigint(valid is not None), values.astype(np.int64),
                  np.ones(len(values), bool) if valid is None
                  else np.asarray(valid))


@pytest.mark.parametrize("case,want", [
    ("rising", True), ("with ties", True), ("one value", True),
    ("one row", True), ("nulls out of order", True),
    ("one row out of order", False), ("the last row out of order", False),
    ("falling", False), ("empty", False), ("a double", False)])
def test_never_decreases(case, want):
    n = 10_000
    rising = np.arange(n) * 3
    col = {
        "rising": lambda: _col(rising),
        "with ties": lambda: _col(np.repeat(np.arange(n // 4), 4)),
        "one value": lambda: _col(np.full(n, 7)),
        "one row": lambda: _col([5]),
        "nulls out of order": lambda: _col(
            np.where(np.arange(n) % 7 == 0, -1, rising),
            np.arange(n) % 7 != 0),
        "one row out of order": lambda: _col(
            np.where(np.arange(n) == 6000, 2, rising)),
        "the last row out of order": lambda: _col(
            np.where(np.arange(n) == n - 1, 0, rising)),
        "falling": lambda: _col(rising[::-1]),
        "empty": lambda: _col([]),
        "a double": lambda: Column(dt.double(), rising.astype(np.float64),
                                   np.ones(n, bool)),
    }[case]()
    assert never_decreases(col) is want


def test_analyze_records_the_order_and_an_insert_out_of_order_unsets_it():
    dom = Domain()
    dom.stats.auto_analyze_enabled = False
    sess = Session(dom)
    sess.execute("create table t (k bigint, v bigint)")
    sess.execute("insert into t values " + ", ".join(
        f"({k // 3}, {(k * 7919) % 1000})" for k in range(3000)))
    sess.execute("analyze table t")
    table = dom.catalog.get_table("test", "t")
    stats = dom.stats.get(table)
    assert stats.col("k").ordered and not stats.col("v").ordered
    assert stats.col("k").span == 1000 and stats.count == 3000
    sess.execute("insert into t values (5, 1)")
    sess.execute("analyze table t")
    assert not dom.stats.get(table).col("k").ordered


def test_the_sort_kernel_is_the_parent_s_program():
    """The order is found on the host, beside the device's sort kernel:
    that program (a minute's compile a row count on a v5e, 17.6 MB of
    every cell's `peak_hbm_gb`) is the text it was before."""
    from tidb_tpu.stats import build
    text = build._stats_kernel.lower(
        jax.ShapeDtypeStruct((4096,), jnp.int64),
        jax.ShapeDtypeStruct((4096,), jnp.bool_), 64, 16).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "8af455940fa73ce6"


# --------------------------------------------------------------------- #
# whole statements over the CPU mesh, lowered as for a TPU
# --------------------------------------------------------------------- #

ROWS = 8 * 16384        # 16,384 rows a device of the 8-device CPU mesh
HEAD = [(k, 100 + k, None if k % 5 == 0 else k * k)
        for k in _order_keys(ROWS // 16).tolist()]
COUNTERS = ("join_launches", "join_direct_launches", "join_window_launches",
            "join_window_overflows", "join_compact_launches",
            "join_host_fallbacks")


def _domain(fact: dict, analyze=True):
    """`fact` (its columns as given) and `head` (HEAD's rows: k, w, s
    nullable), the engine pinned to the device path."""
    dom = Domain()
    dom.stats.auto_analyze_enabled = False
    cols = [Column(I64, d.astype(np.int64), np.ones(len(d), bool))
            for d in fact.values()]
    info = TableInfo("fact", list(fact), [c.dtype for c in cols])
    info.register_columns(cols)
    dom.catalog.create_table("test", info)
    sess = Session(dom)
    sess.execute("create table head (k bigint, w bigint, s bigint)")
    for at in range(0, len(HEAD), 2048):
        sess.execute("insert into head values " + ", ".join(
            f"({k}, {w}, {'null' if s is None else s})"
            for k, w, s in HEAD[at:at + 2048]))
    if analyze:
        sess.execute("analyze table fact")
    sess.execute("set global tidb_tpu_result_cache_entries = 0")
    sess.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    return dom


def _fact(rng, jump_at=None):
    """Rows in `k` order, 4 a key of HEAD's on average (1..7), `p` the
    same keys in no order, `a` uniform in 0..999; `jump_at`: the keys
    from that row on lie 1,000 further (still in order)."""
    keys = _order_keys(ROWS // 4 + 2048)
    k = np.repeat(keys, rng.integers(1, 8, len(keys)))[:ROWS]
    assert len(k) == ROWS
    if jump_at is not None:
        k[jump_at:] += 1000
    return {"k": k, "p": rng.permutation(k), "a": rng.integers(0, 1000, ROWS),
            "v": rng.integers(-1000, 1000, ROWS)}


def _joined(fact, key, keep):
    head = {r[0]: r for r in HEAD}
    return [(int(fact["v"][i]),) + head[int(fact[key][i])][1:]
            for i in np.nonzero(keep)[0] if int(fact[key][i]) in head]


def _agg_of(rows):
    return [(sum(v * w for v, w, _s in rows) if rows else None, len(rows),
             sum(s is not None for _v, _w, s in rows))]


def _statement(dom, sql):
    """-> (rows, the join launches' span attributes, `/sched` deltas of
    COUNTERS)."""
    sched = dom.client._scheduler()
    before = sched.stats()
    sess = Session(dom)
    rows = sess.execute(sql).rows
    after = sched.stats()
    spans = [sp.attrs for sp in sess.last_trace.spans
             if sp.name == "sched.launch" and "join" in sp.attrs]
    return rows, spans, [after[n] - before[n] for n in COUNTERS]


AGG = "select sum(v * w), count(*), count(s) from fact, head where "


@pytest.fixture(scope="module")
def ordered():
    fact = _fact(np.random.default_rng(11))
    yield _domain(fact), fact
    _forget_programs()


def test_a_probe_in_key_order_reads_by_windows(ordered, lowered_for):
    """The lookup of a statement whose filter keeps half its probe rows
    reads by windows, is counted as a direct-addressed launch as before,
    and EXPLAIN names the fact; the same statement probed with the
    shuffled key, or lowered for the CPU, is today's program."""
    dom, fact = ordered
    lowered_for("tpu")
    sql = AGG + "fact.k = head.k and a < 540"
    rows, (span,), delta = _statement(dom, sql)
    assert rows == _agg_of(_joined(fact, "k", fact["a"] < 540))
    assert rows[0][1] > 10_000
    assert span["probe_window"] == 256 and span["join_form"] == "direct"
    assert "probe_capacity" not in span
    assert delta == [1, 1, 1, 0, 0, 0]
    plan = [r[0] for r in Session(dom).execute("explain " + sql).rows]
    assert "probe order: fact.k in key order (windows of 256 slots)" in plan
    # a LEFT join's unmatched rows
    left = "select count(*), count(w), count(s), sum(v * w) from fact " \
           "left join head on fact.k = head.k where a < 540"
    got, (span,), delta = _statement(dom, left)
    hits = _joined(fact, "k", fact["a"] < 540)
    assert got == [(int((fact["a"] < 540).sum()), len(hits),
                    sum(s is not None for _v, _w, s in hits),
                    sum(v * w for v, w, _s in hits))]
    assert span["probe_window"] == 256 and delta[2:4] == [1, 0]
    # the shuffled key: ANALYZE found no order
    names = {}
    shuffled = AGG + "fact.p = head.k and a < 540"
    rows, (span,), delta = _statement(dom, shuffled)
    assert rows == _agg_of(_joined(fact, "p", fact["a"] < 540))
    assert "probe_window" not in span and delta == [1, 1, 0, 0, 0, 0]
    plan = [r[0] for r in Session(dom).execute("explain " + shuffled).rows]
    assert not any(line.startswith("probe order") for line in plan)
    names["tpu"] = span["program"]
    lowered_for("cpu")
    for text in (sql, shuffled):
        _rows, (span,), delta = _statement(dom, text)
        assert "probe_window" not in span and delta[2] == 0
        names[text] = span["program"]
    # `probe_window` 0 is the parent's DAG, digest and program name
    assert names["tpu"] == names[shuffled] != names[sql]


def test_a_compacted_probe_keeps_the_gather(ordered, lowered_for):
    """A filter that keeps one row in fifty: the probe rows are compacted
    first (`probe_capacity`), in no order, so the lookup gathers."""
    dom, fact = ordered
    lowered_for("tpu")
    rows, (span,), delta = _statement(
        dom, AGG + "fact.k = head.k and a < 20")
    assert rows == _agg_of(_joined(fact, "k", fact["a"] < 20))
    assert span["probe_capacity"] > 0 and "probe_window" not in span
    assert delta == [1, 1, 0, 0, 1, 0]


def test_no_statistics_no_windows(lowered_for):
    dom = _domain(_fact(np.random.default_rng(12)), analyze=False)
    lowered_for("tpu")
    _rows, (span,), delta = _statement(
        dom, AGG + "fact.k = head.k and a < 540")
    assert "probe_window" not in span and delta[2] == 0


def test_a_miss_is_exact_counted_and_remembered(lowered_for):
    """The keys jump in the middle of a block: ANALYZE's fact holds, the
    block spans 1,100 slots, its rows beyond the window are counted
    and the statement is answered by the gather form, once for the
    digest; the next statement of the digest launches that form at
    once."""
    fact = _fact(np.random.default_rng(13), jump_at=16384 + 1000)
    dom = _domain(fact)
    lowered_for("tpu")
    sql = AGG + "fact.k = head.k and a < 540"
    want = _agg_of(_joined(fact, "k", fact["a"] < 540))
    rows, spans, delta = _statement(dom, sql)
    assert rows == want and want[0][1] > 5_000
    assert [s.get("probe_window", 0) for s in spans] == [256, 0]
    assert delta == [2, 2, 1, 1, 0, 0]
    rows, spans, delta = _statement(dom, sql)
    assert rows == want
    assert [s.get("probe_window", 0) for s in spans] == [0]
    assert delta == [1, 1, 0, 0, 0, 0]
    # a rows root is rerun alike
    sql = "select v, w from fact, head where fact.k = head.k and a < 540"
    rows, spans, delta = _statement(dom, sql)
    assert sorted(rows) == sorted((v, w) for v, w, _s in _joined(
        fact, "k", fact["a"] < 540))
    # (a rows root lowered for a TPU may page once more: test_join_compact)
    windows = [s.get("probe_window", 0) for s in spans]
    assert windows[0] == 256 and not any(windows[1:]) and len(windows) > 1
    assert delta[2:4] == [1, 1]
    _forget_programs()


# --------------------------------------------------------------------- #
# the parent's programs keep their names
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def partjoin():
    """tests/test_partjoin.py's tables: `lineitem` and `part` at scale
    0.05 from the benchmark's generators, `lineitem` ANALYZEd."""
    run_py = _bench("", "run")
    tables = {"lineitem": _bench("tables", "LINEITEM"),
              "part": _bench("tables", "PART")}
    data = {name: t.generate(0.05, 2147483659, list(t.TYPES))
            for name, t in tables.items()}
    dom = Domain()
    for name, t in tables.items():
        valid = np.ones(t.rows(0.05), bool)
        cols = [run_py._column(t.TYPES[c], v, valid)
                for c, v in data[name].items()]
        info = TableInfo(name, list(data[name]), [c.dtype for c in cols])
        info.register_columns(cols)
        dom.catalog.create_table("test", info)
    sess = Session(dom)
    sess.execute("analyze table lineitem")
    sess.execute("set global tidb_tpu_result_cache_entries = 0")
    sess.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    yield dom
    _forget_programs()


@pytest.mark.parametrize("name,program", [
    ("q14", "cop_solo_join_agg_scalar_1addb69e3d59"),
    ("q19", "cop_solo_join_agg_scalar_051d00ce9053")])
def test_a_join_that_does_not_qualify_keeps_the_parent_s_program(
        partjoin, lowered_for, name, program):
    """`l_partkey` is in no order: Q14's and Q19's DAGs, lowered as for a
    TPU, carry no `probe_window`, and their restart-stable digests are
    what they were before the window form (the literals are PR 44's,
    where every `Aggregation` lost two fields and so changed its name
    once: the programs' text did not)."""
    lowered_for("tpu")
    mod = _bench("classes", name)
    sess = Session(partjoin)
    sess.execute(mod.sql(mod.draw(np.random.default_rng(25))))
    (span,) = [sp.attrs for sp in sess.last_trace.spans
               if sp.name == "sched.launch" and "join" in sp.attrs]
    assert span["program"] == program and "probe_window" not in span


# --------------------------------------------------------------------- #
# TPC-H Q3 over four devices
# --------------------------------------------------------------------- #

def test_q3_over_a_four_device_mesh_reads_by_windows(lowered_for):
    """Each device holds whole row ranges of `LineItem`, so the key
    order holds a shard: the `lineitem` launch of the spec's Q3 reads
    `orders`' table by windows on every device, misses nothing and
    answers as the benchmark's reference does; its `orders` launch
    (probed with `o_custkey`, in no order) gathers."""
    run_py = _bench("", "run")
    tables = {n: _bench("tables", n)
              for n in ("CUSTOMER", "ORDERS", "LineItem")}
    data = {n: t.generate(0.02, 2147483659, list(t.TYPES))
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
    lowered_for("tpu")
    dom.client.mesh = get_mesh(4)
    mod = _bench("classes", "q3")
    state = mod.prepare(data)
    sched = dom.client._scheduler()
    before = sched.stats()
    rng = np.random.default_rng(34)
    for p in [mod.draw(rng) for _ in range(2)]:
        sess = Session(dom)
        rows = sess.execute(mod.sql(p)).rows
        assert [tuple(str(v) for v in r) for r in rows] \
            == mod.answer(state, p) and len(rows) == 10
        launches = [sp.attrs for sp in sess.last_trace.spans
                    if sp.name == "sched.launch" and "join" in sp.attrs]
        # (the `orders` launch, a rows root, may page once more)
        *orders, lineitem = [a.get("probe_window", 0) for a in launches]
        assert orders and not any(orders) and lineitem == 256
    after = sched.stats()
    joins, direct, windows, overflows = [after[n] - before[n]
                                         for n in COUNTERS[:4]]
    assert joins == direct >= 4 and (windows, overflows) == (2, 0)


# --------------------------------------------------------------------- #
# the form a v5e's compiler gives it, at TPC-H SF1's shapes
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:     # noqa: BLE001 - whatever says "no compiler"
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_a_v5e_materialises_no_window_a_row(one_chip):
    """`q3`'s lookup at SF1 (2^23 slots in eight stacked runs, a one-word
    table of 6,029,312 slots), compiled ahead of time for a v5e: the
    select-reduce is fused, so the program's temporaries are the fetched
    table rows (65,536 blocks x 3 x 128 words, 100 MB) and the slots'
    own arrays, nowhere near the 12.9 GB of a (65536, 128, 384) array;
    and no `while` steps through the blocks."""
    stacked, n, slots = 8, 1 << 23, 6_029_312
    packing = (1, 0, ((-2, 0, 0, -1, False), (0, 1, 20, -1, False)))

    def lookup(kv, sel, meta, mins, table):
        grp = [(meta, True), (mins, True), (table, True)]
        matched, (_key, (v, _)), miss = J.direct_lookup(
            kv.reshape(-1), grp, packing, 256, sel.reshape(-1), stacked)
        return jnp.sum(jnp.where(matched, v, 0)), miss

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(lookup).lower(
        arg((stacked, n // stacked), jnp.int32),
        arg((stacked, n // stacked), jnp.bool_), arg((2,), jnp.int32),
        arg((2,), jnp.int64), arg((slots,), jnp.uint32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 400 << 20, temp
    text = compiled.as_text()
    assert "u32[196608,128]" in text and " while(" not in text
