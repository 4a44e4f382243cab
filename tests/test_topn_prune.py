"""Device TopN by block-minimum pruning (copr/exec.topn_head): the pruned
permutation head against the full sort (one block) on the same lanes,
the block-length chooser, and the sharded path at a size that prunes."""

import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.copr import dag as D
from tidb_tpu.copr import exec as X
from tidb_tpu.expr import ColumnRef
from tidb_tpu.expr.compile import Evaluator
from tidb_tpu.types import dtypes as dt

I64, U64 = dt.bigint(), dt.ubigint()
IMIN, IMAX, UMAX = -(2 ** 63), 2 ** 63 - 1, 2 ** 64 - 1
N, L = 64, 8                       # 8 blocks of 8 rows


def _col(values, dtype=I64):
    """(values, valid) with None as NULL; valid is True when none is."""
    valid = np.array([v is not None for v in values])
    data = np.array([0 if v is None else v for v in values],
                    dtype=dtype.np_dtype())
    return jnp.asarray(data), (True if valid.all() else jnp.asarray(valid))


def _keys(*descs, dtypes=None):
    dtypes = dtypes or [I64] * len(descs)
    return tuple((ColumnRef(t, i), d)
                 for i, (t, d) in enumerate(zip(dtypes, descs)))


def _case(name):
    """-> (cols, sel, sort_keys, limit) of one adversarial input."""
    rng = np.random.default_rng(7)
    rand = [int(x) for x in rng.integers(-50, 50, N)]
    all_live = np.ones(N, bool)
    if name == "all_tied_on_first_key":
        return [_col([5] * N), _col(rand)], all_live, _keys(False, False), 10
    if name == "sorted_ascending":      # the whole answer in block 0
        return [_col(list(range(N)))], all_live, _keys(False), 6
    if name == "sorted_descending":     # ... in the last block
        return [_col(list(range(N, 0, -1)))], all_live, _keys(False), 6
    if name == "fewer_live_than_k":
        live = np.zeros(N, bool)
        live[[3, 17, 18, 40, 63]] = True
        return [_col(rand)], live, _keys(True), 9
    if name == "none_live":
        return [_col(rand)], np.zeros(N, bool), _keys(False), 5
    if name == "nulls_first_asc":
        vals = [None if i % 5 == 0 else rand[i] for i in range(N)]
        return [_col(vals)], all_live, _keys(False), 20
    if name == "nulls_last_desc":
        vals = [None if i % 9 else rand[i] for i in range(N)]
        return [_col(vals)], all_live, _keys(True), 12   # 8 non-NULL rows
    if name == "int64_extremes":
        vals = list(rand)
        vals[7], vals[8], vals[33], vals[62] = IMIN, IMIN + 1, IMAX, IMAX - 1
        vals[20] = IMIN                 # a tie of the minimum, another block
        return [_col(vals)], all_live, _keys(False), 3
    if name == "int64_extremes_desc":
        vals = list(rand)
        vals[7], vals[8], vals[33], vals[62] = IMIN, IMIN + 1, IMAX, IMAX - 1
        return [_col(vals)], all_live, _keys(True), 2
    if name == "uint64_extremes":
        vals = [int(x) for x in rng.integers(1, 1000, N)]
        vals[15], vals[16], vals[50], vals[51] = 0, UMAX, UMAX - 1, 1
        return ([_col(vals, U64)], all_live,
                _keys(True, dtypes=[U64]), 3)
    if name == "duplicates_across_block_edge":
        vals = list(range(100, 100 + N))
        vals[L - 2:L + 3] = [1] * 5     # rows 6..10 straddle blocks 0 and 1
        return [_col(vals)], all_live, _keys(False), 4
    if name == "two_keys_mixed":
        a = [int(x) for x in rng.integers(0, 4, N)]
        return [_col(a), _col(rand)], all_live, _keys(False, True), 11
    if name == "three_keys_mixed":
        a = [int(x) for x in rng.integers(0, 3, N)]
        b = [None if i % 7 == 0 else int(x)
             for i, x in enumerate(rng.integers(0, 3, N))]
        live = rng.random(N) < 0.8
        return ([_col(a), _col(b), _col(rand)], live,
                _keys(True, False, True), 13)
    raise KeyError(name)


CASES = ["all_tied_on_first_key", "sorted_ascending", "sorted_descending",
         "fewer_live_than_k", "none_live", "nulls_first_asc",
         "nulls_last_desc", "int64_extremes", "int64_extremes_desc",
         "uint64_extremes", "duplicates_across_block_edge",
         "two_keys_mixed", "three_keys_mixed"]


def _run(monkeypatch, cols, sel, keys, limit, block_len, stacked=1):
    node = D.TopN(D.TableScan(tuple(range(len(cols))),
                              tuple(e.dtype for e, _ in keys)),
                  sort_key=keys[0][0], desc=keys[0][1], limit=limit,
                  sort_keys=keys)
    monkeypatch.setattr(D, "topn_block_len", lambda n, k: block_len)
    out = X._exec_topn(node, X.DeviceBatch(list(cols), jnp.asarray(sel),
                                           stacked=stacked),
                       Evaluator(jnp))
    assert out.facts == {"topn_blocks": N // block_len} and out.stacked == 1
    return ([(np.asarray(v), True if m is True else np.asarray(m))
             for v, m in out.cols], np.asarray(out.sel))


@pytest.mark.parametrize("limit", [None, L, N // L, N, N + 5],
                         ids=["k", "k=L", "k=M", "k=n", "k>n"])
@pytest.mark.parametrize("case", CASES)
def test_pruned_head_equals_full_sort(monkeypatch, case, limit):
    cols, sel, keys, k = _case(case)
    k = k if limit is None else limit
    full = _run(monkeypatch, cols, sel, keys, k, N)
    # contiguous blocks, then blocks across 4 and 8 stacked runs (a
    # stacking that does not divide the block falls back to one run)
    for block_len, stacked in ((L, 1), (2, 1), (N // 2, 1), (L, 4),
                               (2 * L, 8), (2, 4)):
        got = _run(monkeypatch, cols, sel, keys, k, block_len, stacked)
        np.testing.assert_array_equal(got[1], full[1])
        for (gv, gm), (fv, fm) in zip(got[0], full[0]):
            np.testing.assert_array_equal(gv, fv)
            np.testing.assert_array_equal(gm, fm)


def test_full_sort_head_is_the_sql_order(monkeypatch):
    """The anchor the property test leans on: one block against numpy."""
    cols, sel, keys, k = _case("three_keys_mixed")
    out, out_sel = _run(monkeypatch, cols, sel, keys, k, L)
    a, b, c = (np.asarray(v) for v, _ in cols)
    b_null = ~np.asarray(cols[1][1])
    # a DESC, b ASC with NULLs first, c DESC, then the row index
    order = np.lexsort((np.arange(N), -c, np.where(b_null, -1, b), -a, ~sel))
    assert out_sel.all()
    np.testing.assert_array_equal(out[0][0], a[order[:k]])
    np.testing.assert_array_equal(out[2][0], c[order[:k]])
    np.testing.assert_array_equal(out[1][1], ~b_null[order[:k]])


@pytest.mark.parametrize("n,k,want", [
    (2 ** 26, 10, 16384),           # one chip's lineitem: M = 4 K
    (2 ** 26, 16, 16384),
    (2 ** 24, 10, 8192),            # a shard on four chips: M = 2 K
    (2 ** 26, 4096, 1024),          # floored at the lane tile
    (2 ** 16, 10, 1024),            # smallest table that prunes at k = 10
    (2 ** 15, 10, 2 ** 15),         # small: the sorts are no quarter of n
    (512, 7, 512),                  # toy table
    (0, 10, 0),
    (60_000_000, 10, 60_000_000),   # not divisible into blocks
    (3 * 2 ** 20, 10, 4096),        # divisible is enough: no power of two
    (3 * 2 ** 20 + 8, 10, 3 * 2 ** 20 + 8),
    (2 ** 20, 2 ** 19, 2 ** 20),    # a large LIMIT
    (2 ** 26, 2 ** 14, 2 ** 26),
    (2 ** 20, 2 ** 21, 2 ** 20),    # k > n
    (2 ** 20, 0, 2 ** 20),
])
def test_block_len_chooser(n, k, want):
    got = D.topn_block_len(n, k)
    assert got == want
    if got != n:
        m = n // got
        assert n % got == 0 and got >= D.TOPN_MIN_BLOCK
        assert m + min(k, m) * got <= n // 4


def test_layout_and_blocks_ride_the_batch():
    """`stacked` comes in with the scan and stays while the slot axis
    does (Selection, Projection, Limit); Expand builds a new axis and
    drops it.  The TopN says how many blocks it viewed ITS input as —
    the Expand's doubled slots, not the scan's."""
    from tidb_tpu.expr import builders as B
    scan = D.TableScan((0,), (I64,))
    r = ColumnRef(I64, 0)
    n = 2 ** 16
    flat = [(jnp.arange(n, dtype=jnp.int64), True)]
    live = jnp.ones(n, bool)

    def run(node):
        return X._exec_node(node, flat, live, Evaluator(jnp), (), stacked=8)
    kept = D.Limit(D.Projection(D.Selection(
        scan, (B.compare("ge", r, B.lit(5, I64)),)), (r,)), 100)
    assert run(kept).stacked == 8 and run(kept).facts == {}
    rolled = D.Expand(scan, (r,), 2)
    assert run(rolled).stacked == 1
    assert run(D.TopN(scan, sort_key=r, limit=10)).facts["topn_blocks"] \
        == n // D.topn_block_len(n, 10) == 64
    assert run(D.TopN(rolled, sort_key=r, limit=10)).facts["topn_blocks"] \
        == 2 * n // D.topn_block_len(2 * n, 10) == 128
    assert run(D.TopN(scan, sort_key=r, limit=n)).facts["topn_blocks"] == 1


# ------------------------------------------------------------------ #
# the real programs at a size that prunes (block length >= 1024)
# ------------------------------------------------------------------ #

DEC2 = dt.decimal(15, 2)
ROWS = 8 * 65_536                   # 8 shards of 65,536 slots: M = 64 each


@pytest.fixture(scope="module")
def big():
    """(columns, TopN dag, the 10 rows the SQL order puts first)."""
    from tidb_tpu.chunk import Column
    from tidb_tpu.expr import builders as B
    rng = np.random.default_rng(11)
    okey = np.sort(rng.integers(1, 6_000_000, ROWS))
    price = rng.integers(90_000, 10_000_000, ROWS)
    price[rng.integers(0, ROWS, 40)] = 9_999_999    # ties across shards
    cols = [Column.from_numpy(I64, okey), Column.from_numpy(DEC2, price)]
    scan = D.TableScan((0, 1), (I64, DEC2))
    ro, rp = ColumnRef(I64, 0), ColumnRef(DEC2, 1)
    live = D.Selection(scan, (B.compare("ge", ro, B.lit(1000, I64)),))
    top = D.TopN(live, sort_key=rp, desc=True, limit=10,
                 sort_keys=((rp, True), (ro, False)))
    keep = okey >= 1000
    order = np.lexsort((okey[keep], -price[keep]))[:10]
    return cols, top, list(zip(price[keep][order], okey[keep][order]))


def test_single_device_program_prunes_and_is_exact(big):
    from tests.test_copr import dev_cols
    from tidb_tpu import copr
    cols, top, want = big
    assert D.topn_block_len(ROWS, 10) == 2048       # M = 256
    out, cnt = copr.get_program(top, row_capacity=16)(
        dev_cols(cols), jnp.int64(ROWS))
    assert int(cnt) == 10
    got = list(zip(np.asarray(out[1][0])[:10], np.asarray(out[0][0])[:10]))
    assert got == want


def _sharded(cols, top, snaps):
    """Run `top` over each snapshot on the CPU mesh as the chip would
    (device path) under one span tree -> (results, what `/sched`
    counted meanwhile, the `sched.launch` spans' attrs).  Both counter
    readings come from the mesh's one scheduler, which other tests of
    the process have driven too."""
    from tidb_tpu.obs.trace import TRACE_CTX, SpanTree, TraceCtx
    from tidb_tpu.parallel import get_mesh
    from tidb_tpu.sched import scheduler_for
    from tidb_tpu.store import CopClient
    client = CopClient(get_mesh())
    client._platform = lambda: "tpu"
    sched = scheduler_for(get_mesh())
    tree = SpanTree(trace_id="topn-1", sql="topn")
    root = tree.begin("stmt")
    tok = TRACE_CTX.set(TraceCtx(tree, root))
    try:
        before = sched.stats()
        outs = [client.execute_rows(top, snap, (I64, DEC2))
                for snap in snaps]
        after = sched.stats()
    finally:
        TRACE_CTX.reset(tok)
        tree.end(root)
    counted = {k: after[k] - before[k]
               for k in ("topn_launches", "topn_pruned_launches")}
    return outs, counted, [sp.attrs for sp in tree.spans
                           if sp.name == "sched.launch"]


def _snap(cols, rows=None):
    from tidb_tpu.store import snapshot_from_columns
    return snapshot_from_columns(
        ["o", "p"], [c if rows is None else c.slice(0, rows) for c in cols],
        n_shards=8, min_capacity=64)


def test_sharded_topn_prunes_counts_and_equals_single_device(big):
    """Each device prunes its own shard; the union of the shard tops
    holds the single-device answer; `/sched` counts the launch as a
    pruned TopN and the `sched.launch` span says by how many blocks."""
    cols, top, want = big
    (out, small), counted, launches = _sharded(
        cols, top, [_snap(cols), _snap(cols, 4000)])
    assert len(out[0]) == 80 and len(small[0]) == 80    # 8 shard tops
    union = sorted(zip(-out[1].data, out[0].data))[:10]
    assert [(-p, o) for p, o in union] == want
    # the big table's launch pruned, the toy table's sorted in full
    assert counted == {"topn_launches": 2, "topn_pruned_launches": 1}
    assert [a["topn_blocks"] for a in launches] == [64, 1]
    assert all(a["program"].startswith("cop_solo_topn_") for a in launches)


def test_blocks_of_an_untraced_executable_come_from_an_abstract_trace(
        big, monkeypatch):
    """What a launch reports is what the program's trace recorded.  A
    program object whose executable copforge serves from its pool (or
    its disk store) is never traced by the launch: it is traced
    abstractly instead, once, with the same answer."""
    from tidb_tpu.parallel import spmd
    from tidb_tpu.compilecache import compile_cache
    cols, top, _ = big
    snap = _snap(cols)
    _sharded(cols, top, [snap])
    spmd._cached.cache_clear()          # new program objects, same key
    traces = []
    real = spmd.ShardedCopProgram._device_fn
    monkeypatch.setattr(
        spmd.ShardedCopProgram, "_device_fn",
        lambda self, *a: traces.append(compile_cache().stats()["misses"])
        or real(self, *a))
    misses = compile_cache().stats()["misses"]
    _, counted, launches = _sharded(cols, top, [snap, snap])
    assert counted == {"topn_launches": 2, "topn_pruned_launches": 2}
    assert [a["topn_blocks"] for a in launches] == [64, 64]
    # one trace, and no compile: it was the abstract one
    assert traces == [misses] and compile_cache().stats()["misses"] == misses


def test_admission_cost_describes_the_pruning_program():
    """copcost prices what runs: a pruning TopN holds buffers of M and
    k * L comparator tuples, not of every row; a toy table's TopN (one
    block) is priced as the full sort it is."""
    from tidb_tpu.analysis.copcost import Layout, dag_cost
    scan = D.TableScan((0, 1), (I64, I64))
    ro, rp = ColumnRef(I64, 0), ColumnRef(I64, 1)
    top = D.TopN(scan, sort_key=rp, desc=True, limit=10,
                 sort_keys=((rp, True), (ro, False)))
    tuple_bytes = (2 + 1) * 8
    big = dag_cost(top, Layout(1, 2 ** 26, 1, 2 ** 26))
    parts = dict(big.breakdown)
    length = D.topn_block_len(2 ** 26, 10)
    assert parts["TopN:sort"] == 10 * length * tuple_bytes
    assert parts["TopN:block-min"] == 2 ** 26 // length * tuple_bytes
    toy = dag_cost(top, Layout(1, 4096, 1, 4096))
    parts = dict(toy.breakdown)
    assert parts["TopN:sort"] == 4096 * tuple_bytes
    assert "TopN:block-min" not in parts
    # one pass over the rows and two small sorts, not n log n
    full_sort_flops = 2 ** 26 * 26 * 2
    assert big.flops < full_sort_flops // 4
