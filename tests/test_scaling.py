"""Multi-chip scaling-shape assertions at N=8 (SURVEY §2.10 P1/P2/P7).

These tests pin the properties that make the single-chip bench + mesh
evidence support the pod story: per-device work is ~1/N, one compile per
(dag digest, capacity) shape, and the agg merge crosses devices via
psum-family all-reduce ONLY (no all-to-all / unexpected collectives) —
the reference's fan-out+merge contract (pkg/store/copr/coprocessor.go:337,
agg_hash_final_worker.go) restated as compiled-program facts.
"""

import jax
import numpy as np
import pytest

from tests.test_copr import DEC2, make_lineitem, q6_dag
from tidb_tpu import copr
from tidb_tpu.copr import dag as D
from tidb_tpu.expr import ColumnRef
from tidb_tpu.parallel import get_mesh
from tidb_tpu.parallel.mesh import SHARD_AXIS
from tidb_tpu.parallel.spmd import get_sharded_program
from tidb_tpu.store import CopClient, snapshot_from_columns
from tidb_tpu.types import dtypes as dt

NAMES = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate",
         "l_returnflag", "l_linestatus"]


@pytest.fixture(scope="module")
def mesh():
    return get_mesh()


def _lowered(prog, snap, mesh):
    cols, counts = snap.device_cols(mesh)
    return prog._fn.lower(tuple(cols), counts, ()), (cols, counts)


def test_input_sharding_per_device_slice(mesh):
    cols = make_lineitem(8_000, seed=0)
    snap = snapshot_from_columns(NAMES, cols, n_shards=8, min_capacity=64)
    dcols, counts = snap.device_cols(mesh)
    n_dev = mesh.devices.size
    for data, _valid in dcols:
        s, c = data.shape
        assert s % n_dev == 0
        # each device must hold exactly S/N shards — dp over the shard axis
        shard_shapes = {tuple(sh.data.shape)
                        for sh in data.addressable_shards}
        assert shard_shapes == {(s // n_dev, c)}


def test_one_compile_per_dag_shape(mesh):
    agg = q6_dag()
    p1 = get_sharded_program(agg, mesh)
    p2 = get_sharded_program(agg, mesh)
    assert p1 is p2     # digest-keyed cache: second query reuses the jit


def test_agg_merge_is_allreduce_only(mesh):
    cols = make_lineitem(8_000, seed=1)
    snap = snapshot_from_columns(NAMES, cols, n_shards=8, min_capacity=64)
    prog = get_sharded_program(q6_dag(), mesh)
    lowered, _ = _lowered(prog, snap, mesh)
    txt = lowered.compile().as_text()
    assert "all-reduce" in txt
    assert "all-to-all" not in txt
    # replicated output: merged states identical on every device
    assert not prog.host_merge


def test_minmax_merge_in_program(mesh):
    """MIN/MAX now merge on device via the psum-gather trick — no
    host-side per-device reduce, and still no all-to-all."""
    cols = make_lineitem(4_000, seed=2)
    snap = snapshot_from_columns(NAMES, cols, n_shards=8, min_capacity=64)
    rq = ColumnRef(DEC2, 0)
    scan = D.TableScan((0,), (DEC2,))
    agg = D.Aggregation(scan, (), (
        copr.AggDesc(copr.AggFunc.MIN, rq, DEC2),
        copr.AggDesc(copr.AggFunc.MAX, rq, DEC2),
        copr.AggDesc(copr.AggFunc.COUNT, None, dt.bigint(False)),
    ), D.GroupStrategy.DENSE, domain_sizes=())
    client = CopClient(mesh)
    prog = get_sharded_program(agg, mesh)
    assert not prog.host_merge
    lowered, _ = _lowered(prog, snap, mesh)
    txt = lowered.compile().as_text()
    assert "all-reduce" in txt and "all-to-all" not in txt
    res = client.execute_agg(agg, snap, [])
    assert int(res.columns[0].data[0]) == int(cols[0].data.min())
    assert int(res.columns[1].data[0]) == int(cols[0].data.max())
    assert int(res.columns[2].data[0]) == len(cols[0])


def test_per_device_flops_scale(mesh):
    """Per-device FLOPs of the 8-way program ~ 1/8 of the single-device
    program over the same table (work really is partitioned, not
    replicated)."""
    import jax.numpy as jnp

    from tests.test_copr import dev_cols
    cols = make_lineitem(65_536, seed=3)
    snap = snapshot_from_columns(NAMES, cols, n_shards=8,
                                 min_capacity=8192)
    agg = q6_dag()
    prog8 = get_sharded_program(agg, mesh)
    lowered, _ = _lowered(prog8, snap, mesh)
    fl8 = lowered.compile().cost_analysis()
    prog1 = copr.get_program(agg)
    single = jax.jit(prog1._trace).lower(
        dev_cols(cols), jnp.int64(len(cols[0]))).compile().cost_analysis()
    f8, f1 = fl8.get("flops", 0.0), single.get("flops", 0.0)
    if not f8 or not f1:
        pytest.skip("backend reports no flops estimate")
    # cost_analysis on SPMD programs reports per-device flops
    assert f8 < f1 / 4, (f8, f1)


def test_rows_output_stays_sharded(mesh):
    cols = make_lineitem(4_000, seed=4)
    snap = snapshot_from_columns(NAMES, cols, n_shards=8, min_capacity=64)
    scan = D.TableScan((1,), (DEC2,))
    prog = get_sharded_program(scan, mesh, row_capacity=1024)
    dcols, counts = snap.device_cols(mesh)
    out_cols, out_counts = prog(dcols, counts, ())
    # per-device compacted outputs ride the shard axis — the host
    # concatenates N local blocks, it never receives a replicated copy
    data = out_cols[0][0]
    n_dev = mesh.devices.size
    assert data.shape[0] == n_dev
    shard_shapes = {tuple(sh.data.shape) for sh in data.addressable_shards}
    assert shard_shapes == {(1, data.shape[1])}


def test_device_multikey_topn(mesh):
    """Multi-column ORDER BY ... LIMIT runs on device: one lax.sort with
    all keys (cophandler/topn.go multi-ByItem analog)."""
    cols = make_lineitem(4_000, seed=5)
    snap = snapshot_from_columns(NAMES, cols, n_shards=8, min_capacity=64)
    client = CopClient(mesh)
    scan = D.TableScan((1, 2), (DEC2, DEC2))   # price, disc
    k1, k2 = ColumnRef(DEC2, 1), ColumnRef(DEC2, 0)   # disc asc, price desc
    topn = D.TopN(scan, sort_key=k1, desc=False, limit=12,
                  sort_keys=((k1, False), (k2, True)))
    out = client.execute_rows(topn, snap, (DEC2, DEC2))
    # oracle: global 12 best under (disc asc, price desc); per-device
    # top-12 must contain the global top-12
    order = np.lexsort((-cols[1].data, cols[2].data))[:12]
    exp = sorted(zip(cols[2].data[order], -cols[1].data[order]))
    got = sorted(zip(out[1].data, -out[0].data))
    for row in exp:
        assert row in got


def test_sql_multikey_topn_pushes_to_device(mesh):
    from tidb_tpu.session.session import Domain, Session
    s = Session(Domain())
    s.execute("create table t (a bigint, b bigint, c bigint)")
    vals = ",".join(f"({i % 7}, {-i % 11}, {i})" for i in range(400))
    s.execute(f"insert into t values {vals}")
    plan = "\n".join(r[0] for r in s.must_query(
        "explain select a, b, c from t order by a, b desc limit 5"))
    assert "CopTask[rows]" in plan, plan
    got = s.must_query("select a, b, c from t order by a, b desc limit 5")
    exp = sorted(((i % 7, -i % 11, i) for i in range(400)),
                 key=lambda r: (r[0], -r[1]))[:5]
    assert [tuple(r) for r in got] == exp


def test_paging_feedback_adapts(mesh):
    """Second run of the same selective plan starts at the observed
    capacity: no regrow passes (adaptive paging, pkg/util/paging)."""
    from tidb_tpu.expr import builders as B
    cols = make_lineitem(40_000, seed=6)
    snap = snapshot_from_columns(NAMES, cols, n_shards=8,
                                 min_capacity=4096)
    client = CopClient(mesh)
    rq = ColumnRef(DEC2, 0)
    scan = D.TableScan((0,), (DEC2,))
    # ~96% selectivity: the constant 1/4 first guess must regrow
    sel = D.Selection(scan, (B.compare("ge", rq, B.decimal_lit("2")),))
    out1 = client.execute_rows(sel, snap, (DEC2,))
    assert client.last_page_iters > 1
    out2 = client.execute_rows(sel, snap, (DEC2,))
    assert client.last_page_iters == 1
    assert len(out1[0]) == len(out2[0])
