"""shardflow: sharding-layout & collective-transfer abstract
interpretation (ISSUE 12).

Layers under test:

- topology model: single-host meshes degenerate to all-ICI, the
  (host=2, device=4) view splits collective traffic exactly, uneven
  factorizations refuse,
- corpus acceptance: every TPC-H corpus plan (incl. the shuffle
  queries) and every MULTICHIP dryrun plan shape flows clean under
  both views with finite per-link bytes,
- seeded violations: an undeclared reshard, an unknown mesh axis, a
  coordinator-routed host merge on a 2-host view, and a DCI-blowup
  join each reject PRE-TRACE with structured rule ids
  (get_sharded_program monkeypatched to fail on touch — the
  PR 2/4/7 pattern),
- pricing: DCI bytes price at a strictly higher RU rate than ICI, and
  the same plan prices more under the 2-host view (test-pinned),
- validation: predicted per-link exchange bytes of the shuffle-join
  path match the traced program's live send buffers on the 8-vdev
  mesh within SHARD_TOLERANCE (the copcost exact-resident-bytes
  precedent),
- single-source boundary checks: contracts' shuffle-spec pass and
  shardflow's report the same rule id,
- surfacing: /sched counters + prometheus metrics, EXPLAIN transfer
  footer under a declared host view, TPU-SHARD-CONST lint rule.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.analysis import shardflow as SF
from tidb_tpu.analysis.contracts import PlanContractError
from tidb_tpu.analysis.copcost import shuffle_exchange_buckets, task_cost
from tidb_tpu.copr import dag as D
from tidb_tpu.expr.ir import ColumnRef
from tidb_tpu.parallel import topology as T
from tidb_tpu.parallel.mesh import get_mesh
from tidb_tpu.sched import CopTask, DeviceScheduler
from tidb_tpu.testing.tpch import (TPCH_SHUFFLE_QUERIES,
                                   built_multichip_plans, built_tpch_plans,
                                   tpch_plan_session)
from tidb_tpu.types import dtypes as dt

N_DEV = 8


@pytest.fixture(scope="module")
def corpus():
    s = tpch_plan_session(sf=0.0005)
    return s, list(built_tpch_plans(s))


@pytest.fixture(scope="module")
def mesh():
    return get_mesh()


@pytest.fixture()
def host_view():
    """Declared 2-host view, reset afterwards (module-global state)."""
    T.set_host_view(2)
    try:
        yield T.topology_for(n_devices=N_DEV, n_hosts=2)
    finally:
        T.set_host_view(None)


def _find(op, name):
    if type(op).__name__ == name:
        return op
    for c in getattr(op, "children", []) or []:
        r = _find(c, name) if c is not None else None
        if r is not None:
            return r
    return None


def _no_trace(monkeypatch):
    """Fail the test if anything reaches program build/trace."""
    import tidb_tpu.parallel.spmd as spmd

    def boom(*_a, **_k):
        raise AssertionError("reached tracing/compilation")
    monkeypatch.setattr(spmd, "get_sharded_program", boom)
    monkeypatch.setattr(spmd, "get_batched_program", boom)
    monkeypatch.setattr(spmd, "get_fused_program", boom)


def _device_inputs(n_shards=8, cap=16):
    cols = [(jnp.zeros((n_shards, cap), jnp.int64), None)]
    counts = jnp.full((n_shards,), cap, jnp.int64)
    return cols, counts


def _scalar_agg():
    scan = D.TableScan((0,), (dt.bigint(False),))
    return D.Aggregation(
        child=scan,
        aggs=(D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
        strategy=D.GroupStrategy.SCALAR)


def _sort_agg(cap=1024):
    scan = D.TableScan((0,), (dt.bigint(False),))
    return D.Aggregation(
        child=scan, group_by=(ColumnRef(dt.bigint(False), 0, "k"),),
        aggs=(D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
        strategy=D.GroupStrategy.SORT, group_capacity=cap)


# ------------------------------------------------------------------ #
# topology model
# ------------------------------------------------------------------ #

def test_single_host_degenerates_to_all_ici():
    t = T.topology_for(n_devices=8)
    assert t.n_hosts == 1 and not t.multi_host
    bd = t.split_all_to_all(100)
    assert bd.dci == 0
    assert bd.intra == 8 * 100          # every device keeps its bucket
    assert bd.ici == 8 * 7 * 100        # and ships 7 over ICI
    assert t.split_psum(10).dci == 0
    assert t.link_of(0, 7) == T.LINK_ICI
    assert t.link_of(3, 3) == T.LINK_INTRA


def test_two_host_view_splits_links_exactly():
    t = T.MeshTopology((T.SHARD_AXIS,), 8, 2)
    assert t.devices_per_host == 4
    assert t.link_of(0, 3) == T.LINK_ICI      # same host block
    assert t.link_of(0, 4) == T.LINK_DCI      # crosses the host cut
    bd = t.split_all_to_all(100)
    assert bd.intra == 8 * 100
    assert bd.ici == 8 * 3 * 100              # 3 same-host peers
    assert bd.dci == 8 * 4 * 100              # 4 cross-host peers
    g = t.split_all_gather(10)
    assert (g.ici, g.dci) == (8 * 3 * 10, 8 * 4 * 10)
    # host-merge routing: per-host stays intra, the coordinator
    # anti-route ships every remote device's states over DCI
    assert t.split_host_merge(10).dci == 0
    assert t.split_host_merge(10, T.MERGE_COORDINATOR).dci == 4 * 10


def test_uneven_host_factorization_refuses():
    with pytest.raises(ValueError):
        T.MeshTopology((T.SHARD_AXIS,), 8, 3)
    # topology_for falls back to single-host instead of poisoning
    # every analysis with a structural error
    assert T.topology_for(n_devices=8, n_hosts=3).n_hosts == 1


def test_declared_host_view_feeds_topology_for():
    T.set_host_view(2)
    try:
        assert T.topology_for(n_devices=8).n_hosts == 2
    finally:
        T.set_host_view(None)
    assert T.topology_for(n_devices=8).n_hosts == 1


# ------------------------------------------------------------------ #
# corpus + MULTICHIP acceptance (finite per-link bytes, clean flows)
# ------------------------------------------------------------------ #

def test_corpus_flows_clean_under_both_views(corpus):
    _s, plans = corpus
    topo1 = T.topology_for(n_devices=N_DEV)
    topo2 = T.MeshTopology((T.SHARD_AXIS,), N_DEV, 2)
    assert SF.shard_findings(plans, n_devices=N_DEV) == []
    saw_dci = False
    for sql, phys in plans:
        SF.verify_plan_sharding(phys, topo1)
        SF.verify_plan_sharding(phys, topo2)
        bd = SF.plan_transfer(phys, topo2)
        assert bd.intra >= 0 and bd.ici >= 0 and bd.dci >= 0, sql
        saw_dci = saw_dci or bd.dci > 0
    assert saw_dci       # the corpus really exercises the DCI tier


def test_multichip_dryrun_shapes_flow_clean(corpus):
    s, _plans = corpus
    multichip = list(built_multichip_plans(s))
    assert len(multichip) == 7
    topo2 = T.MeshTopology((T.SHARD_AXIS,), N_DEV, 2)
    kinds = set()
    for _sql, phys in multichip:
        assert SF.verify_plan_sharding(phys, topo2) >= 1
        for n in ("CopTaskExec", "CopJoinTaskExec", "CopShuffleJoinExec",
                  "CopWindowExec"):
            if _find(phys, n) is not None:
                kinds.add(n)
    assert kinds == {"CopTaskExec", "CopJoinTaskExec",
                     "CopShuffleJoinExec", "CopWindowExec"}, kinds


def test_shuffle_plan_dci_dominates_ici_under_two_host_view(corpus):
    """Uniform all_to_all over a (2, 4) view: 4 of 7 peer hops cross
    hosts, so exchange dci/ici is exactly 4/3 — the attribution really
    is per-link, not a relabeled total."""
    _s, plans = corpus
    topo2 = T.MeshTopology((T.SHARD_AXIS,), N_DEV, 2)
    shuffle = next(p for q, p in plans if "o_custkey" in q
                   and _find(p, "CopShuffleJoinExec") is not None)
    bd = SF.plan_transfer(shuffle, topo2)
    assert bd.ici > 0 and bd.dci > 0
    op = _find(shuffle, "CopShuffleJoinExec")
    ex = SF.shuffle_transfer(
        op.spec,
        SF.C.snapshot_layout(op.left_table.snapshot(), N_DEV),
        SF.C.snapshot_layout(op.right_table.snapshot(), N_DEV),
        SF.C.snapshot_scan_widths(op.left_table.snapshot()),
        SF.C.snapshot_scan_widths(op.right_table.snapshot()), topo2)
    assert ex.dci * 3 == ex.ici * 4


# ------------------------------------------------------------------ #
# seeded violations: rejected pre-trace with structured rule ids
# ------------------------------------------------------------------ #

def test_seeded_implicit_reshard_rejected_at_admission(mesh, monkeypatch):
    """A row-wise operator consuming post-psum replicated states is the
    hidden reshard XLA would silently insert — rejected at sched submit
    before any trace."""
    _no_trace(monkeypatch)
    bad = D.Selection(child=_scalar_agg(),
                      conditions=(ColumnRef(dt.bigint(False), 0, "c"),))
    cols, counts = _device_inputs()
    task = CopTask.structured(bad, mesh, 1024, cols, counts, ())
    with pytest.raises(PlanContractError) as ei:
        DeviceScheduler().submit(task)
    assert ei.value.rule == SF.RULE_IMPLICIT_RESHARD
    # and the same dag rejects at the flow level directly
    with pytest.raises(PlanContractError):
        SF.verify_dag_sharding(bad, T.topology_for(n_devices=N_DEV))


def test_seeded_unknown_mesh_axis_rejected_at_admission(monkeypatch):
    """A mesh whose axes do not carry the exchange axis: the program
    would fail at trace (or bind the wrong axis) — rejected at submit,
    pre-trace."""
    from jax.sharding import Mesh
    _no_trace(monkeypatch)
    weird = Mesh(np.array(jax.devices()), ("ring",))
    cols, counts = _device_inputs()
    task = CopTask.structured(_scalar_agg(), weird, 1024, cols, counts, ())
    with pytest.raises(PlanContractError) as ei:
        DeviceScheduler().submit(task)
    assert ei.value.rule == SF.RULE_AXIS_UNKNOWN


def test_seeded_coordinator_merge_rejected_on_two_host_view(monkeypatch):
    """A host-merged group table routed through one coordinator on a
    2-host topology view — the per-host discipline is the contract."""
    _no_trace(monkeypatch)
    topo2 = T.MeshTopology((T.SHARD_AXIS,), N_DEV, 2)
    sort_dag = _sort_agg()
    # per-host routing (the declared discipline) flows clean
    out = SF.verify_dag_sharding(sort_dag, topo2)
    assert out.row_sharded                     # per-device state tables
    with pytest.raises(PlanContractError) as ei:
        SF.verify_dag_sharding(sort_dag, topo2,
                               merge_route=T.MERGE_COORDINATOR)
    assert ei.value.rule == SF.RULE_MERGE_COORDINATOR
    # single-host topologies have no coordinator to reject
    SF.verify_dag_sharding(sort_dag, T.topology_for(n_devices=N_DEV),
                           merge_route=T.MERGE_COORDINATOR)


def _blowup_spec(levels=512):
    """Hand-built shuffle spec whose left chain Expands every scanned
    row `levels`x before the exchange: the repartition ships the table
    across DCI hundreds of times over."""
    key_t = dt.bigint(False)
    lscan = D.TableScan((0,), (key_t,))
    left = D.Expand(child=lscan, keys=(ColumnRef(key_t, 0, "k"),),
                    levels=levels)
    right = D.TableScan((0,), (key_t,))
    ldt = D.output_dtypes(left)
    top = D.Aggregation(
        child=D.TableScan((0,), (key_t,)),
        aggs=(D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
        strategy=D.GroupStrategy.SCALAR)
    return D.ShuffleJoinSpec(
        left=left, right=right,
        left_key=ColumnRef(key_t, 0, "lk"),
        right_key=ColumnRef(key_t, 0, "rk"),
        kind="inner", left_dtypes=ldt, right_dtypes=(key_t,), top=top)


def test_seeded_dci_blowup_join_rejected(monkeypatch):
    _no_trace(monkeypatch)
    from tidb_tpu.analysis.copcost import Layout
    spec = _blowup_spec()
    topo2 = T.MeshTopology((T.SHARD_AXIS,), N_DEV, 2)
    lay = Layout(8, 1024, N_DEV, 8 * 1024)
    with pytest.raises(PlanContractError) as ei:
        SF.verify_spec_sharding(spec, topo2, llayout=lay, rlayout=lay)
    assert ei.value.rule == SF.RULE_DCI_BLOWUP
    # the same spec without the Expand blow-up flows clean
    sane = dataclasses.replace(spec, left=spec.right,
                               left_dtypes=(dt.bigint(False),))
    bd = SF.verify_spec_sharding(sane, topo2, llayout=lay, rlayout=lay)
    assert bd.dci > 0
    # and single-host views never price a DCI blow-up
    SF.verify_spec_sharding(spec, T.topology_for(n_devices=N_DEV),
                            llayout=lay, rlayout=lay)


def test_psum_limb_fence_bound_proven_pre_trace():
    """The runtime OverflowError fence (spmd/shuffle), proven from the
    layout's global capacity before any trace."""
    scan = D.TableScan((0,), (dt.bigint(False),))
    int_sum = D.Aggregation(
        child=scan,
        aggs=(D.AggDesc(D.AggFunc.SUM, ColumnRef(dt.bigint(False), 0, "x"),
                        dt.bigint(False)),),
        strategy=D.GroupStrategy.SCALAR)
    topo = T.topology_for(n_devices=N_DEV)
    SF.verify_dag_sharding(int_sum, topo, global_rows=2 ** 30)
    with pytest.raises(PlanContractError) as ei:
        SF.verify_dag_sharding(int_sum, topo, global_rows=2 ** 31)
    assert ei.value.rule == SF.RULE_PSUM_FENCE


# ------------------------------------------------------------------ #
# pricing: DCI bytes are dearer than ICI (test-pinned)
# ------------------------------------------------------------------ #

def test_dci_bytes_price_above_ici():
    from tidb_tpu.analysis.copcost import LaunchCost
    from tidb_tpu.rc.pricing import (RU_PER_DCI_BYTE, RU_PER_ICI_BYTE,
                                     cost_rus)
    assert RU_PER_DCI_BYTE > RU_PER_ICI_BYTE
    n = 64 << 20
    ici_only = LaunchCost(transfer_breakdown=(0, n, 0))
    dci_only = LaunchCost(transfer_breakdown=(0, 0, n))
    assert cost_rus(dci_only) > cost_rus(ici_only)
    assert cost_rus(dci_only) == pytest.approx(
        cost_rus(ici_only) * RU_PER_DCI_BYTE / RU_PER_ICI_BYTE)


def test_two_host_view_prices_plan_higher(corpus):
    """The same shuffle plan costs strictly more RUs under the 2-host
    view: the bytes that crossed the host cut re-price at the DCI
    rate — admission and fairness stay honest when the mesh splits."""
    from tidb_tpu.analysis.copcost import plan_cost
    from tidb_tpu.rc.pricing import cost_rus
    _s, plans = corpus
    shuffle = next(p for q, p in plans
                   if _find(p, "CopShuffleJoinExec") is not None)
    topo1 = T.MeshTopology((T.SHARD_AXIS,), N_DEV, 1)
    topo2 = T.MeshTopology((T.SHARD_AXIS,), N_DEV, 2)
    rus1 = cost_rus(plan_cost(shuffle, N_DEV, topology=topo1))
    rus2 = cost_rus(plan_cost(shuffle, N_DEV, topology=topo2))
    assert rus2 > rus1


def test_task_cost_breakdown_honors_declared_host_view(corpus, mesh,
                                                       host_view):
    _s, plans = corpus
    phys = next(p for q, p in plans if "revenue" in q)
    cop = _find(phys, "CopTaskExec")
    cols, counts = _device_inputs()
    task = CopTask.structured(cop.dag, mesh, 0, cols, counts, ())
    cost = task_cost(task)
    assert cost.ici_bytes > 0 and cost.dci_bytes > 0   # view declared
    T.set_host_view(None)
    cost1 = task_cost(task)
    assert cost1.dci_bytes == 0 and cost1.ici_bytes > 0
    # single-host ici = everything the psum exchanges; the 2-host view
    # reclassifies part of it, it never invents traffic
    assert cost.ici_bytes + cost.dci_bytes == cost1.ici_bytes


# ------------------------------------------------------------------ #
# scheduler surfacing: per-link counters + prometheus metrics
# ------------------------------------------------------------------ #

def test_sched_transfer_counters_and_metrics(mesh):
    sched = DeviceScheduler()
    sched._serve = lambda batch: [t.finish(("prog", "out")) for t in batch]
    cols, counts = _device_inputs()
    task = CopTask.structured(_scalar_agg(), mesh, 0, cols, counts, ())
    sched.submit(task)
    task.wait()
    for _ in range(200):                   # _account runs on the drain
        if sched.stats()["transfer_ici_bytes"] > 0:
            break
        import time
        time.sleep(0.01)
    st = sched.stats()
    assert st["transfer_ici_bytes"] > 0
    assert st["transfer_dci_bytes"] == 0   # single host: no DCI tier
    from tidb_tpu.utils.metrics import global_registry
    text = global_registry().prometheus_text()
    assert "tidb_tpu_sched_transfer_ici_bytes_total" in text
    assert "tidb_tpu_sched_transfer_dci_bytes_total" in text


# ------------------------------------------------------------------ #
# validation: predicted per-link bytes vs the traced exchange buffers
# ------------------------------------------------------------------ #

def test_predicted_shuffle_link_bytes_match_traced_exchange():
    """The copcost exact-resident-bytes precedent, for the wire: the
    static per-link prediction of the shuffle-join exchange must land
    within SHARD_TOLERANCE of the LIVE send-buffer bytes the traced
    program actually swaps on the 8-vdev mesh."""
    import tidb_tpu.parallel.shuffle as shuffle_mod
    from tidb_tpu.executor import plan as planmod
    from tidb_tpu.parallel.exchange import record_exchange
    from tidb_tpu.sql.parser import parse_one

    s = tpch_plan_session(sf=0.0005)
    saved = planmod.BROADCAST_BUILD_MAX_ROWS
    planmod.BROADCAST_BUILD_MAX_ROWS = 0
    shuffle_mod._cached.cache_clear()      # force a fresh trace
    records = record_exchange(True)
    try:
        _b, phys = s._plan_select(parse_one(TPCH_SHUFFLE_QUERIES[0]))
        op = _find(phys, "CopShuffleJoinExec")
        assert op is not None
        rows = s.must_query(TPCH_SHUFFLE_QUERIES[0])
        assert rows[0][0] > 0
    finally:
        record_exchange(False)
        planmod.BROADCAST_BUILD_MAX_ROWS = saved
    # first program trace: one record per exchange side, per device
    assert len(records) >= 2, records
    n_dev = records[0][0]
    assert n_dev == N_DEV
    measured_total = sum(p for _d, _c, p in records[:2]) * n_dev
    lsnap, rsnap = op.left_table.snapshot(), op.right_table.snapshot()
    lb, rb = shuffle_exchange_buckets(
        op.spec,
        SF.C.snapshot_layout(lsnap, N_DEV),
        SF.C.snapshot_layout(rsnap, N_DEV),
        SF.C.snapshot_scan_widths(lsnap),
        SF.C.snapshot_scan_widths(rsnap), N_DEV)
    topo = T.topology_for(n_devices=N_DEV)
    predicted = topo.split_all_to_all(lb).combined(
        topo.split_all_to_all(rb))
    assert measured_total / SF.SHARD_TOLERANCE <= predicted.total \
        <= measured_total * SF.SHARD_TOLERANCE, \
        (predicted.total, measured_total)
    # per-link: the same band holds for the classified tiers (the
    # split is exact per-pair arithmetic over the measured total)
    measured = topo.split_all_to_all(measured_total // (n_dev * n_dev))
    for pred, meas in ((predicted.ici, measured.ici),
                      (predicted.intra, measured.intra)):
        assert meas / SF.SHARD_TOLERANCE <= pred \
            <= meas * SF.SHARD_TOLERANCE, (pred, meas)


def test_program_transfer_breakdown_methods(corpus, mesh):
    """Runtime programs expose the same typed-link attribution their
    static twins predict (shuffle caps / window capacity), and spmd
    programs surface their merge collective for introspection."""
    from tidb_tpu.parallel.shuffle import ShuffleCaps, get_shuffle_program
    from tidb_tpu.parallel.spmd import get_sharded_program
    _s, plans = corpus
    shuffle = next(p for q, p in plans
                   if _find(p, "CopShuffleJoinExec") is not None)
    op = _find(shuffle, "CopShuffleJoinExec")
    prog = get_shuffle_program(op.spec, mesh, ShuffleCaps(1024, 1024, 2048))
    topo2 = T.MeshTopology((T.SHARD_AXIS,), N_DEV, 2)
    bd = prog.transfer_breakdown(topo2)
    assert bd.ici > 0 and bd.dci > 0
    assert prog.transfer_breakdown(T.topology_for(n_devices=N_DEV)).dci == 0
    q6 = next(p for q, p in plans if "revenue" in q)
    sprog = get_sharded_program(_find(q6, "CopTaskExec").dag, mesh)
    assert sprog.collective_axis == T.SHARD_AXIS
    assert sprog.merge_kind == "psum"


# ------------------------------------------------------------------ #
# single-source boundary checks + EXPLAIN + lint
# ------------------------------------------------------------------ #

def test_shuffle_boundary_single_source_same_rule(corpus):
    """The exchange-boundary checks were deduped into shardflow; the
    contracts pass delegates — both report the SAME rule id on the
    same defect, so the passes cannot drift."""
    from tidb_tpu.analysis.contracts import _verify_shuffle_spec
    _s, plans = corpus
    shuffle = next(p for q, p in plans
                   if _find(p, "CopShuffleJoinExec") is not None)
    spec = _find(shuffle, "CopShuffleJoinExec").spec
    bad = dataclasses.replace(
        spec, left_dtypes=spec.left_dtypes + (dt.bigint(False),))
    rules = []
    for entry in (lambda: _verify_shuffle_spec(bad, ()),
                  lambda: SF.verify_shuffle_boundary(bad, ())):
        with pytest.raises(PlanContractError) as ei:
            entry()
        rules.append(ei.value.rule)
    assert rules == ["exchange-mismatch", "exchange-mismatch"]


def test_explain_transfer_footer_reflects_host_view(corpus):
    s, _plans = corpus
    q = "explain select count(*) from lineitem where l_quantity < 5"
    rows = [r[0] for r in s.must_query(q)]
    line = next(r for r in rows if r.startswith("transfer: "))
    assert "/ 0B dci" in line          # single host: DCI tier is empty
    s.execute("set global tidb_tpu_topology_hosts = 2")
    try:
        rows2 = [r[0] for r in s.must_query(q)]
        line2 = next(r for r in rows2 if r.startswith("transfer: "))
        assert "/ 0B dci" not in line2, line2
    finally:
        s.execute("set global tidb_tpu_topology_hosts = -1")
        T.set_host_view(None)


def _rules(src, rel):
    from tidb_tpu.analysis.lint import lint_source
    return [f.rule for f in lint_source(src, rel)]


def test_lint_shard_const():
    """TPU-SHARD-CONST: collective axis names in traced modules must
    reference the topology symbol, never a string literal."""
    lit = ("from jax import lax\n\ndef f(x):\n"
           "    return lax.all_gather(x, 'shard')\n")
    assert _rules(lit, "parallel/exchange.py") == ["TPU-SHARD-CONST"]
    # keyword spelling flags too
    kw = ("from jax import lax\n\ndef f(x):\n"
          "    return lax.all_gather(x, axis_name='shard')\n")
    assert _rules(kw, "parallel/spmd.py") == ["TPU-SHARD-CONST"]
    # PartitionSpec literals flag
    ps = ("from jax.sharding import PartitionSpec as P\n\n"
          "def f():\n    return P('shard')\n")
    assert _rules(ps, "parallel/window.py") == ["TPU-SHARD-CONST"]
    # referencing the symbol passes
    ok = ("from jax import lax\nfrom .topology import SHARD_AXIS\n\n"
          "def f(x, axis=SHARD_AXIS):\n"
          "    return lax.all_gather(x, axis)\n")
    assert _rules(ok, "parallel/exchange.py") == []
    # outside traced modules: silent
    assert _rules(lit, "store/client.py") == []
    # inline waiver works like every other rule
    waived = lit.replace("'shard')", "'shard')  # planlint: ok - test rig")
    assert _rules(waived, "parallel/exchange.py") == []
    # repo sweep: the traced modules are literal-free
    import os

    import tidb_tpu
    from tidb_tpu.analysis.lint import TRACED_MODULES
    root = os.path.dirname(tidb_tpu.__file__)
    for rel in sorted(TRACED_MODULES):
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            found = [r for r in _rules(f.read(), rel)
                     if r == "TPU-SHARD-CONST"]
        assert not found, (rel, found)
