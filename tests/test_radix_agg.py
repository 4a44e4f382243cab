"""SCATTER-strategy device group-by (multi-pass scatter radix
partition, ISSUE 11).

Layers under test:

- kernel exactness: the SCATTER device program is bit-identical to the
  SEGMENT and SORT programs and the numpy oracle on the 8-vdev CPU mesh
  (NULL keys, multi-column keys, decimal limb sums past int64),
- partition exactness: the XLA 1-bit lowering returns the stable
  bucket-major permutation numpy's stable argsort gives,
- capacity discipline: the client regrows num_buckets from observed
  __ngroups__ (paging analog) on the SCATTER path too,
- prehash hoist (satellite): a regrow sequence traces the avalanche
  key hash exactly ONCE (the hoisted hash program), not once per
  capacity re-entry,
- contracts/copcost: malformed bucket counts and pass blow-ups are
  rejected pre-trace with structured errors (get_sharded_program
  monkeypatched to fail on touch); COST-RADIX-PASSES gate finding,
- calibration arbitration: a digest whose measured SEGMENT time_factor
  beats SCATTER's flips planner strategy selection with NO code change,
- fusion: ('scatter-agg', B, passes) signature refuses mismatched
  bucket spaces; the SORT capacity-bucketed class refuses mismatched
  capacities (fusion-breadth satellite).
"""

import jax
import numpy as np
import pytest

from tidb_tpu import copr
from tidb_tpu.analysis.calibrate import correction_store
from tidb_tpu.analysis.compilekey import stable_digest
from tidb_tpu.analysis.contracts import (PlanContractError,
                                         fusion_signature, verify_dag,
                                         verify_fusion_group)
from tidb_tpu.analysis.copcost import cost_findings
from tidb_tpu.chunk.column import Column
from tidb_tpu.copr import dag as D
from tidb_tpu.copr import radix, segment
from tidb_tpu.copr.aggregate import (GroupKeyMeta, finalize_sorted,
                                     merge_sorted_states)
from tidb_tpu.expr.ir import ColumnRef
from tidb_tpu.parallel.mesh import get_mesh
from tidb_tpu.parallel.spmd import get_sharded_program
from tidb_tpu.store import snapshot_from_columns
from tidb_tpu.types import dtypes as dt

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    return get_mesh()


def _snap(names, cols, n_shards=8):
    return snapshot_from_columns(names, cols, n_shards=n_shards)


def _run_host_merged(agg, snap, key_meta, mesh):
    prog = get_sharded_program(agg, mesh)
    assert prog.host_merge
    cols, counts = snap.device_cols(mesh)
    states = jax.device_get(prog(cols, counts))
    per_dev = [jax.tree_util.tree_map(lambda a, d=d: np.asarray(a)[d],
                                      states) for d in range(N_DEV)]
    merged = merge_sorted_states(agg, per_dev)
    key_cols, agg_cols = finalize_sorted(agg, merged, key_meta)
    return key_cols, agg_cols


def _as_map(key_cols, agg_cols):
    out = {}
    n = len(agg_cols[0]) if agg_cols else 0
    for i in range(n):
        key = tuple((int(kc.data[i]) if kc.validity[i] else None)
                    for kc in key_cols)
        out[key] = tuple(
            (int(c.data[i]) if c.validity[i] else None) for c in agg_cols)
    return out


def _scatter_dag(num_buckets, keys=True, scan=None, aggs=None,
                 group_by=None, prehashed=False):
    scan = scan or D.TableScan((0,), (dt.bigint(False),))
    return D.Aggregation(
        scan,
        group_by if group_by is not None else
        ((ColumnRef(dt.bigint(False), 0),) if keys else ()),
        aggs or (D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
        D.GroupStrategy.SCATTER, num_buckets=num_buckets,
        prehashed=prehashed)


# ------------------------------------------------------------------ #
# kernel exactness: SCATTER vs SEGMENT vs SORT vs numpy
# ------------------------------------------------------------------ #

def test_scatter_bit_identical_null_and_multicolumn_keys(mesh):
    """NULL keys form their own group, multi-column keys group by the
    tuple — SCATTER vs SEGMENT vs SORT vs a python oracle, for
    COUNT/SUM/MIN/MAX."""
    rng = np.random.default_rng(13)
    n = 50_000
    a = rng.integers(0, 4000, n).astype(np.int64)
    av = rng.random(n) < 0.9            # ~10% NULL keys
    b = rng.integers(-5, 5, n).astype(np.int64)
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    snap = _snap(["a", "b", "v"], [
        Column(dt.bigint(), a, av),
        Column(dt.bigint(False), b, np.ones(n, bool)),
        Column(dt.bigint(False), v, np.ones(n, bool))])
    aref = ColumnRef(dt.bigint(), 0, "a")
    bref = ColumnRef(dt.bigint(False), 1, "b")
    vref = ColumnRef(dt.bigint(False), 2, "v")
    aggs = (copr.AggDesc(copr.AggFunc.COUNT, None, dt.bigint(False)),
            copr.AggDesc(copr.AggFunc.SUM, vref,
                         copr.sum_out_dtype(vref.dtype)),
            copr.AggDesc(copr.AggFunc.MIN, vref, dt.bigint()),
            copr.AggDesc(copr.AggFunc.MAX, vref, dt.bigint()))
    scan = D.TableScan((0, 1, 2),
                       (dt.bigint(), dt.bigint(False), dt.bigint(False)))
    meta = [GroupKeyMeta(dt.bigint(), 0), GroupKeyMeta(dt.bigint(False), 0)]

    maps = {}
    for strat, kw in (
            (D.GroupStrategy.SCATTER, {"num_buckets": 1 << 16}),
            (D.GroupStrategy.SEGMENT, {"num_buckets": 1 << 16}),
            (D.GroupStrategy.SORT, {"group_capacity": 1 << 16})):
        agg = D.Aggregation(scan, (aref, bref), aggs, strat, **kw)
        maps[strat] = _as_map(*_run_host_merged(agg, snap, meta, mesh))
    assert maps[D.GroupStrategy.SCATTER] == maps[D.GroupStrategy.SEGMENT]
    assert maps[D.GroupStrategy.SCATTER] == maps[D.GroupStrategy.SORT]

    exp: dict = {}
    for i in range(n):
        key = (int(a[i]) if av[i] else None, int(b[i]))
        c, s, mn, mx = exp.get(key, (0, 0, None, None))
        vi = int(v[i])
        exp[key] = (c + 1, s + vi,
                    vi if mn is None else min(mn, vi),
                    vi if mx is None else max(mx, vi))
    assert maps[D.GroupStrategy.SCATTER] == exp
    assert any(key[0] is None for key in exp)     # NULL group exists


def test_scatter_decimal_sum_past_int64(mesh):
    """Decimal SUMs whose group totals overflow int64 recombine exactly
    through the (hi, lo) limb states on the SCATTER path."""
    rng = np.random.default_rng(17)
    n = 40_000
    k = rng.integers(0, 4, n).astype(np.int64)
    base = rng.integers(1 << 40, (1 << 40) + (1 << 20), n)
    val = (base * 1000).astype(np.int64)
    dec_t = dt.decimal(18, 2)
    snap = _snap(["k", "d"], [
        Column(dt.bigint(False), k, np.ones(n, bool)),
        Column(dec_t, val, np.ones(n, bool))])
    kref = ColumnRef(dt.bigint(False), 0, "k")
    dref = ColumnRef(dec_t, 1, "d")
    aggs = (copr.AggDesc(copr.AggFunc.SUM, dref, copr.sum_out_dtype(dec_t)),
            copr.AggDesc(copr.AggFunc.COUNT, None, dt.bigint(False)))
    scan = D.TableScan((0, 1), (dt.bigint(False), dec_t))
    sca = D.Aggregation(scan, (kref,), aggs, D.GroupStrategy.SCATTER,
                        num_buckets=1024)
    key_cols, agg_cols = _run_host_merged(
        sca, snap, [GroupKeyMeta(dt.bigint(False), 0)], mesh)
    got = {int(key_cols[0].data[i]): int(agg_cols[0].data[i])
           for i in range(len(key_cols[0]))}
    exp = {}
    for u in np.unique(k):
        exp[int(u)] = int(val[k == u].astype(object).sum())
    assert got == exp
    assert max(abs(t) for t in exp.values()) > 2 ** 63  # past int64


# ------------------------------------------------------------------ #
# the partition itself
# ------------------------------------------------------------------ #

def test_scatter_permutation_is_stable_bucket_major_order():
    """The partition is a stable LSD radix sort of the partition key:
    exactly numpy's stable argsort of it (single device, no mesh)."""
    rng = np.random.default_rng(5)
    n = 10_000
    h = jax.numpy.asarray(
        rng.integers(0, 1 << 63, n, dtype=np.uint64), dtype=jax.numpy.uint64)
    sel = jax.numpy.asarray(rng.random(n) < 0.95)
    for num_buckets in (1024, 1 << 15):
        perm = np.asarray(radix.scatter_permutation(h, sel, num_buckets, n))
        bits = D.radix_key_bits(num_buckets) - 1
        keys = np.asarray(h >> np.uint64(64 - bits)).astype(np.int64)
        keys[~np.asarray(sel)] = 1 << bits
        assert (perm == np.argsort(keys, kind="stable")).all()


# ------------------------------------------------------------------ #
# bucket regrow + prehash hoist
# ------------------------------------------------------------------ #

def test_scatter_bucket_regrow_from_observed_groups(mesh):
    """More distinct groups than num_buckets: the client regrows the
    SCATTER bucket space from __ngroups__ and still returns every
    group — device path pinned open (host fallback disabled)."""
    from tidb_tpu.store import CopClient
    n = 30_000
    k = np.arange(n, dtype=np.int64)           # all distinct
    snap = _snap(["k"], [Column(dt.bigint(False), k, np.ones(n, bool))])
    agg = _scatter_dag(1024)                   # far too small
    client = CopClient(mesh)
    client._host_sort_agg = lambda *a, **kw: None    # force device path
    res = client.execute_agg(agg, snap, [GroupKeyMeta(dt.bigint(False), 0)])
    assert len(res.key_columns[0]) == n
    assert all(int(c) == 1 for c in res.columns[0].data)


def test_regrow_reuses_hoisted_key_hash(mesh):
    """Prehash satellite pin: a SCATTER regrow sequence traces the
    avalanche key hash exactly ONCE (inside the hoisted hash program);
    every capacity re-entry reuses the hashed column instead of
    re-hashing the key tuple.  Applies to SEGMENT too."""
    from tidb_tpu.store import CopClient
    from tidb_tpu.compilecache import compile_cache
    for strat in (D.GroupStrategy.SCATTER, D.GroupStrategy.SEGMENT):
        # the hash program is cached per (scan, keys, mesh) AND warms
        # through the copforge pool — clear both so each strategy round
        # pays (and counts) exactly one cold trace
        radix.get_hash_program.cache_clear()
        compile_cache().clear_pool()
        n = 20_000
        # unique per-strategy data so no program/result cache interferes
        off = 0 if strat is D.GroupStrategy.SCATTER else 7_000_000
        k = np.arange(n, dtype=np.int64) + off
        snap = _snap(["k"], [Column(dt.bigint(False), k, np.ones(n, bool))])
        agg = D.Aggregation(
            D.TableScan((0,), (dt.bigint(False),)),
            (ColumnRef(dt.bigint(False), 0),),
            (D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
            strat, num_buckets=1024)           # forces >= 1 regrow
        client = CopClient(mesh)
        client._host_sort_agg = lambda *a, **kw: None
        before = segment.HASH_TRACES[0]
        res = client.execute_agg(agg, snap,
                                 [GroupKeyMeta(dt.bigint(False), 0)])
        assert len(res.key_columns[0]) == n
        traces = segment.HASH_TRACES[0] - before
        assert traces == 1, \
            f"{strat}: key hash traced {traces}x across regrow (want 1)"


def test_prehashed_dag_contract_rules():
    """prehashed contracts: well-formed passes; non-radix strategy,
    non-scan chain, and a group key reading the hash column are all
    rejected pre-trace."""
    scan2 = D.TableScan((0, 1), (dt.bigint(False), dt.bigint(False)))
    ok = _scatter_dag(1024, scan=scan2, prehashed=True)
    verify_dag(ok)
    with pytest.raises(PlanContractError):
        verify_dag(D.Aggregation(
            scan2, (ColumnRef(dt.bigint(False), 0),),
            (D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
            D.GroupStrategy.SORT, group_capacity=64, prehashed=True))
    with pytest.raises(PlanContractError) as ei:
        verify_dag(_scatter_dag(
            1024, scan=scan2, prehashed=True,
            group_by=(ColumnRef(dt.bigint(False), 1),)))
    assert ei.value.rule == "column-ref"


# ------------------------------------------------------------------ #
# contracts / copcost: malformed shapes rejected pre-trace
# ------------------------------------------------------------------ #

def test_malformed_buckets_and_passes_rejected_pre_trace(mesh,
                                                         monkeypatch):
    """Malformed SCATTER bucket/pass shapes raise structured contract
    errors BEFORE any trace: get_sharded_program is monkeypatched to
    fail on touch and submission still rejects cleanly."""
    import tidb_tpu.parallel.spmd as spmd
    from tidb_tpu.sched import CopTask, DeviceScheduler

    verify_dag(_scatter_dag(4096))                   # well-formed passes
    for bad in (0, -8, 3, 1000):                     # zero/neg/non-pow2
        with pytest.raises(PlanContractError) as ei:
            verify_dag(_scatter_dag(bad))
        assert ei.value.rule == "capacity-shape", bad
    with pytest.raises(PlanContractError) as ei:
        verify_dag(_scatter_dag(4096, keys=False))
    assert ei.value.rule == "capacity-shape"
    # pass blow-up: a bucket space pricing > MAX_RADIX_PASSES passes
    absurd = 1 << 60
    assert D.radix_passes(absurd) > D.MAX_RADIX_PASSES
    with pytest.raises(PlanContractError) as ei:
        verify_dag(_scatter_dag(absurd))
    assert ei.value.rule == "capacity-shape"
    assert "passes" in ei.value.detail

    n = 4096
    snap = _snap(["k"], [Column(
        dt.bigint(False), np.arange(n, dtype=np.int64), np.ones(n, bool))])
    cols, counts = snap.device_cols(mesh)

    def boom(*_a, **_k):
        raise AssertionError("reached tracing/compilation")
    monkeypatch.setattr(spmd, "get_sharded_program", boom)
    monkeypatch.setattr(spmd, "get_batched_program", boom)
    monkeypatch.setattr(spmd, "get_fused_program", boom)

    sched = DeviceScheduler()
    task = CopTask.structured(_scatter_dag(absurd), mesh, 0, cols,
                              counts, ())
    with pytest.raises(PlanContractError):
        sched.submit(task)


def test_cost_radix_passes_gate_finding():
    """cost_findings reports COST-RADIX-PASSES for a degenerate SCATTER
    corpus plan (seeded via a fake physical op, bypassing verify)."""
    n = 1024
    snap = _snap(["k"], [Column(
        dt.bigint(False), np.arange(n, dtype=np.int64),
        np.ones(n, bool))])

    class _FakeExec:
        table = type("T", (), {"snapshot": staticmethod(lambda: snap)})()
        children = ()
        dag = _scatter_dag(1 << 60)
    _FakeExec.__name__ = "CopTaskExec"

    finds = cost_findings([("select 1", _FakeExec())], n_devices=N_DEV)
    assert any(f.rule == "COST-RADIX-PASSES" for f in finds), finds


def test_scatter_partition_prices_below_segment_sort():
    """Acceptance criterion: at the 2M-group shape the SCATTER
    partition pass prices measurably fewer FLOPs AND fewer partition-
    buffer bytes than SEGMENT's lax.sort pass in the copcost
    breakdown."""
    from tidb_tpu.analysis.copcost import Layout, dag_cost
    cap = 1 << 21                                 # 2M-group bucket space
    layout = Layout(8, 1 << 18, 8, 1 << 21)       # 2M rows over 8 devices
    scan = D.TableScan((0,), (dt.bigint(False),))
    kref = ColumnRef(dt.bigint(False), 0)
    count = (D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),)
    sca = dag_cost(D.Aggregation(scan, (kref,), count,
                                 D.GroupStrategy.SCATTER, num_buckets=cap),
                   layout)
    seg = dag_cost(D.Aggregation(scan, (kref,), count,
                                 D.GroupStrategy.SEGMENT, num_buckets=cap),
                   layout)
    assert sca.flops < seg.flops
    part = {lbl.rsplit(":", 1)[-1]: b for lbl, b in sca.breakdown}
    seg_part = {lbl.rsplit(":", 1)[-1]: b for lbl, b in seg.breakdown}
    sca_bytes = sum(v for k, v in part.items() if k.startswith("radix"))
    assert sca_bytes < seg_part["radix"]          # SEGMENT's sort buffer
    assert not sca.radix_blowups and not sca.unbounded


# ------------------------------------------------------------------ #
# calibration arbitration
# ------------------------------------------------------------------ #

def test_measured_time_factor_flips_strategy_selection():
    """A digest whose measured SEGMENT time beats SCATTER's flips
    planner selection to SEGMENT with NO code change; clearing the
    corrections flips it back (test-pinned acceptance criterion)."""
    from tidb_tpu.session import Domain, Session
    from tidb_tpu.session.catalog import TableInfo

    def _plan(sess):
        return "\n".join(r[0] for r in sess.must_query(
            "explain select k, count(*) from arb group by k"))

    dom = Domain()
    sess = Session(dom)
    rng = np.random.default_rng(31)
    n = 60_000
    big = rng.permutation(100_000)[:n].astype(np.int64)
    ti = TableInfo("arb", ["k"], [dt.bigint(False)])
    ti.register_columns([Column(dt.bigint(False), big, np.ones(n, bool))])
    dom.catalog.create_table("test", ti)
    sess.execute("analyze table arb")

    store = correction_store()
    try:
        plan0 = _plan(sess)
        assert "agg strategy: scatter" in plan0, plan0

        # reconstruct the candidate dags the planner arbitrates and
        # seed measured factors: SCATTER slow (8x), SEGMENT fast (1/8)
        from tidb_tpu.analysis.copcost import LaunchCost
        import re
        m = re.search(r"scatter \((\d+) buckets", plan0)
        cap = int(m.group(1))
        scan = D.TableScan((0,), (dt.bigint(False),))
        kref = ColumnRef(dt.bigint(False), 0, "k")
        count = (D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),)
        sca = D.Aggregation(scan, (kref,), count,
                            D.GroupStrategy.SCATTER, num_buckets=cap)
        seg = D.Aggregation(scan, (kref,), count,
                            D.GroupStrategy.SEGMENT, num_buckets=cap)
        ref = LaunchCost(flops=1_000_000, output_bytes=1 << 20)
        for _ in range(16):     # converge the clamped EWMA factors
            store.observe(stable_digest(sca), ref,
                          int(8 * 1e9))          # measured SLOW
            store.observe(stable_digest(seg), ref,
                          int(0.001 * 1e6))      # measured FAST
        plan1 = _plan(sess)
        assert "agg strategy: segment" in plan1, plan1
    finally:
        store.purge(stable_digest(sca))
        store.purge(stable_digest(seg))
    assert "agg strategy: scatter" in _plan(sess)


# ------------------------------------------------------------------ #
# fusion classes
# ------------------------------------------------------------------ #

class _FakeTask:
    def __init__(self, dag, fp=("x",), sig=(("s", "i8"),),
                 token=(1, 2, 3), aux=()):
        self.key = (D.dag_digest(dag), fp, 0, sig)
        self.dag = dag
        self.input_token = token
        self.aux = aux


def test_scatter_and_sort_fusion_classes_refuse_mismatches():
    """('scatter-agg', B, passes) refuses mismatched bucket spaces at
    the class level; ('sort-agg', cap) — the capacity-bucketed SORT
    class (fusion-breadth satellite) — refuses mismatched capacities
    the same way, and fuses matching ones."""
    a, b = _scatter_dag(4096), _scatter_dag(8192)
    assert fusion_signature(a) == ("scatter-agg", 4096,
                                   D.radix_passes(4096))
    assert fusion_signature(a) != fusion_signature(b)
    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group([_FakeTask(a), _FakeTask(b)])
    assert ei.value.rule == "fusion-class"

    scan = D.TableScan((0,), (dt.bigint(False),))
    kref = ColumnRef(dt.bigint(False), 0)
    count = (D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),)

    def sort_dag(cap, func=D.AggFunc.COUNT):
        return D.Aggregation(scan, (kref,), count if func is
                             D.AggFunc.COUNT else
                             (D.AggDesc(func, kref, dt.bigint()),),
                             D.GroupStrategy.SORT, group_capacity=cap)
    s4, s8 = sort_dag(4096), sort_dag(8192)
    assert fusion_signature(s4) == ("sort-agg", 4096)
    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group([_FakeTask(s4), _FakeTask(s8)])
    assert ei.value.rule == "fusion-class"
    # same capacity, different aggregates: a valid group
    verify_fusion_group([_FakeTask(s4),
                         _FakeTask(sort_dag(4096, D.AggFunc.MAX))])


def test_same_capacity_sort_tasks_fuse_into_one_launch(mesh):
    """Two SORT aggregations (same pow2 capacity, different payloads)
    over one scan run as ONE fused launch with host-merged per-member
    leaves, each bit-identical to its solo run — SORT chains finally
    fuse (ROADMAP fusion-breadth carried follow-on)."""
    from tidb_tpu.copr.dag import FusedDag
    from tidb_tpu.parallel.spmd import get_fused_program

    rng = np.random.default_rng(29)
    n = 20_000
    k = rng.integers(0, 5_000, n).astype(np.int64)
    v = rng.integers(0, 100, n).astype(np.int64)
    snap = _snap(["k", "v"], [
        Column(dt.bigint(False), k, np.ones(n, bool)),
        Column(dt.bigint(False), v, np.ones(n, bool))])
    kref = ColumnRef(dt.bigint(False), 0, "k")
    vref = ColumnRef(dt.bigint(False), 1, "v")
    scan = D.TableScan((0, 1), (dt.bigint(False), dt.bigint(False)))
    a = D.Aggregation(scan, (kref,),
                      (copr.AggDesc(copr.AggFunc.COUNT, None,
                                    dt.bigint(False)),),
                      D.GroupStrategy.SORT, group_capacity=8192)
    b = D.Aggregation(scan, (kref,),
                      (copr.AggDesc(copr.AggFunc.MAX, vref, dt.bigint()),),
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
