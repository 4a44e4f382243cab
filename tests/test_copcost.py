"""copcost: the static shape/memory abstract interpreter and its
HBM-budget admission gate (ISSUE 4).

Three layers under test:

- model validation: predicted resident input bytes must match the LIVE
  device buffers exactly, and predicted peak HBM must stay within the
  pinned COST_TOLERANCE band of the compiled program's measured
  argument/output/temp sizes on the 8-vdev CPU mesh,
- gate rules: the TPC-H corpus is clean; seeded capacity blow-ups and
  unboundable nodes are rejected PRE-TRACE (get_sharded_program
  monkeypatched to fail on touch),
- sched admission: a budget below a query's footprint rejects at
  submit with a structured CostError, the deferred counter moves when
  a fused group overflows the summed-footprint cap, and the window
  hit-rate feedback decays a never-paying key's hold toward zero.
"""

import dataclasses
import threading
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.analysis.copcost import (CAP_BLOWUP_MAX, COST_TOLERANCE,
                                       DENSE_BLOWUP_MIN_GROUPS,
                                       CostError, cost_findings,
                                       cost_report, dag_cost, plan_cost,
                                       snapshot_input_bytes,
                                       snapshot_layout,
                                       snapshot_scan_widths, task_cost)
from tidb_tpu.copr import dag as D
from tidb_tpu.expr.ir import ColumnRef
from tidb_tpu.parallel.mesh import get_mesh
from tidb_tpu.sched import CopTask, DeviceScheduler
from tidb_tpu.sched.scheduler import WINDOW_HIT_INIT
from tidb_tpu.testing.tpch import built_tpch_plans, tpch_plan_session
from tidb_tpu.types import dtypes as dt

N_DEV = 8


@pytest.fixture(scope="module")
def corpus():
    s = tpch_plan_session()
    return s, list(built_tpch_plans(s))


@pytest.fixture(scope="module")
def mesh():
    return get_mesh()


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


# ------------------------------------------------------------------ #
# model validation against live buffers / compiled memory analysis
# ------------------------------------------------------------------ #

def test_input_bytes_match_live_device_buffers(corpus, mesh):
    """The resident-input half of the model mirrors ColumnarSnapshot
    placement arithmetic exactly: predicted bytes == live device buffer
    nbytes, no tolerance."""
    _s, plans = corpus
    checked = 0
    for _sql, phys in plans:
        cop = _find(phys, "CopTaskExec")
        if cop is None:
            continue
        snap = cop.table.snapshot()
        layout = snapshot_layout(snap, N_DEV)
        widths = snapshot_scan_widths(snap)
        predicted = snapshot_input_bytes(snap, layout, widths)
        cols, counts = snap.device_cols(mesh)
        measured = sum(
            int(v.nbytes) + (int(m.nbytes) if m is not None else 0)
            for v, m in cols) + int(counts.nbytes)
        assert predicted == measured, (_sql, predicted, measured)
        checked += 1
    assert checked >= 8         # the corpus really exercises the model


def _measured_mesh_bytes(prog, cols, counts, input_bytes):
    """Resident inputs + D x compiled per-device output/temp sizes, from
    jax.stages.Compiled memory analysis (None when the backend reports
    nothing useful)."""
    ma = prog._fn.lower(tuple(cols), counts, ()).compile().memory_analysis()
    if ma is None:
        return None
    try:
        out = int(ma.output_size_in_bytes)
        tmp = int(ma.temp_size_in_bytes)
    except (AttributeError, TypeError):
        return None
    if out + tmp <= 0:
        return None
    return input_bytes + N_DEV * (out + tmp)


def test_peak_hbm_within_pinned_tolerance(corpus, mesh):
    """On the 8-vdev CPU mesh, LaunchCost.peak_hbm_bytes stays within
    COST_TOLERANCE of (live input buffers + D x compiled output/temp
    bytes) for every plain CopTask corpus plan — the acceptance band
    the ISSUE pins.  (The model's intermediate term is a deliberate
    no-fusion upper bound, hence a band rather than equality.)"""
    from tidb_tpu.parallel.spmd import get_sharded_program
    _s, plans = corpus
    checked = 0
    for sql, phys in plans:
        cop = _find(phys, "CopTaskExec")
        if cop is None or not isinstance(cop.dag, D.Aggregation):
            continue
        if cop.dag.strategy == D.GroupStrategy.SORT:
            continue            # host-merge outputs skew per-device sizes
        snap = cop.table.snapshot()
        layout = snapshot_layout(snap, N_DEV)
        widths = snapshot_scan_widths(snap)
        input_bytes = snapshot_input_bytes(snap, layout, widths)
        cols, counts = snap.device_cols(mesh)
        prog = get_sharded_program(cop.dag, mesh)
        measured = _measured_mesh_bytes(prog, cols, counts, input_bytes)
        if measured is None:
            pytest.skip("backend reports no compiled memory analysis")
        predicted = dag_cost(cop.dag, layout, widths,
                             input_bytes=input_bytes).peak_hbm_bytes
        assert measured / COST_TOLERANCE <= predicted \
            <= measured * COST_TOLERANCE, (sql, predicted, measured)
        checked += 1
    assert checked >= 3


def test_corpus_is_cost_clean_and_reportable(corpus):
    _s, plans = corpus
    assert cost_findings(plans, n_devices=N_DEV) == []
    report = cost_report(plans, n_devices=N_DEV)
    lines = report.splitlines()
    assert len(lines) == len(plans) + 1          # header + one per query
    assert "peak" in lines[0] and "pad" in lines[0]


# ------------------------------------------------------------------ #
# seeded violations: rejected pre-trace
# ------------------------------------------------------------------ #

@pytest.fixture()
def q6_cop(corpus):
    _s, plans = corpus
    phys = next(p for q, p in plans if "revenue" in q)
    cop = _find(phys, "CopTaskExec")
    assert cop is not None
    return phys, cop


def _device_inputs(n_shards=8, cap=16):
    cols = [(jnp.zeros((n_shards, cap), jnp.int64), None)]
    counts = jnp.full((n_shards,), cap, jnp.int64)
    return cols, counts


def test_seeded_cap_blowup_rejected_at_admission(q6_cop, mesh,
                                                 monkeypatch):
    """A corpus DAG mutated to an expanding join whose out_capacity
    dwarfs its probe rows blows the static footprint: the scheduler
    rejects it at submit, before any trace (COST-CAP-BLOWUP's admission
    twin via the HBM budget)."""
    _no_trace(monkeypatch)
    _phys, cop = q6_cop
    scan = cop.dag
    while not isinstance(scan, D.TableScan):
        scan = scan.child
    blown = D.LookupJoin(
        child=scan, probe_key=ColumnRef(scan.col_dtypes[0], 0, "k"),
        kind="inner", build_dtypes=(dt.bigint(False),), unique=False,
        out_capacity=1 << 34)           # 16Gi rows x 18B >> any budget
    cols, counts = _device_inputs()
    task = CopTask.structured(blown, mesh, 1024, cols, counts, ())
    sched = DeviceScheduler()
    with pytest.raises(CostError) as ei:
        sched.submit(task)
    assert ei.value.rule == "hbm-budget"
    assert sched.budget_rejects == 1


def test_seeded_cap_blowup_is_a_gate_finding(q6_cop):
    """The same blow-up planned (not submitted) trips COST-CAP-BLOWUP
    in the gate's corpus pass."""
    _phys, cop = q6_cop
    scan = cop.dag
    while not isinstance(scan, D.TableScan):
        scan = scan.child
    rows_pd = snapshot_layout(cop.table.snapshot(), N_DEV).rows_per_device
    blown = D.LookupJoin(
        child=scan, probe_key=ColumnRef(scan.col_dtypes[0], 0, "k"),
        kind="inner", build_dtypes=(dt.bigint(False),), unique=False,
        out_capacity=int(rows_pd * CAP_BLOWUP_MAX * 4))
    bad = dataclasses.replace(cop, dag=blown)
    findings = cost_findings([("select seeded", bad)], n_devices=N_DEV)
    assert [f.rule for f in findings] == ["COST-CAP-BLOWUP"]


@dataclass(frozen=True)
class _AlienNode(D.CopNode):
    """A device node the interpreter has no size algebra for."""
    child: D.CopNode = None

    def children(self):
        return (self.child,)


def test_seeded_unbounded_node_rejected_at_admission(q6_cop, mesh,
                                                     monkeypatch):
    _no_trace(monkeypatch)
    _phys, cop = q6_cop
    scan = cop.dag
    while not isinstance(scan, D.TableScan):
        scan = scan.child
    cols, counts = _device_inputs()
    task = CopTask.structured(_AlienNode(child=scan), mesh, 1024,
                              cols, counts, ())
    with pytest.raises(CostError) as ei:
        DeviceScheduler().submit(task)
    assert ei.value.rule == "cost-unbounded"
    assert "_AlienNode" in ei.value.detail


def test_seeded_unbounded_node_is_a_gate_finding(q6_cop):
    _phys, cop = q6_cop
    scan = cop.dag
    while not isinstance(scan, D.TableScan):
        scan = scan.child
    bad = dataclasses.replace(cop, dag=_AlienNode(child=scan))
    findings = cost_findings([("select seeded", bad)], n_devices=N_DEV)
    assert [f.rule for f in findings] == ["COST-UNBOUNDED"]


def _count_by_key(strategy, **sizes):
    return D.Aggregation(
        D.TableScan((0,), (dt.bigint(False),)),
        (ColumnRef(dt.bigint(False), 0),),
        (D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
        strategy, **sizes)


def _key_snapshot(n):
    from tidb_tpu.chunk.column import Column
    from tidb_tpu.store import snapshot_from_columns
    return snapshot_from_columns(["k"], [Column(
        dt.bigint(False), np.arange(n, dtype=np.int64),
        np.ones(n, bool))], n_shards=N_DEV)


def test_degenerate_dense_rejected_at_admission(mesh, monkeypatch):
    """The large-NDV DENSE plan (the sf>=10 TPU-worker crash shape) is
    priced as a dense-blowup and rejected with CostError at submit,
    BEFORE anything traces — such keys group by SORT."""
    cols, counts = _key_snapshot(4096).device_cols(mesh)
    # past BOTH fences: the planner's dense ceiling AND the
    # states-vs-rows ratio (states >> rows)
    dom_size = 2 * DENSE_BLOWUP_MIN_GROUPS
    dense = _count_by_key(D.GroupStrategy.DENSE, domain_sizes=(dom_size,))
    _no_trace(monkeypatch)

    sched = DeviceScheduler()
    task = CopTask.structured(dense, mesh, 0, cols, counts, ())
    r0 = sched.budget_rejects
    with pytest.raises(CostError) as ei:
        sched.submit(task)
    assert ei.value.rule == "dense-blowup"
    assert sched.budget_rejects == r0 + 1
    # the cost model itself flags it too (gate-finding twin)
    cost = task_cost(task)
    assert cost.dense_blowups
    # the equivalent SORT plan prices clean and admits
    srt = _count_by_key(D.GroupStrategy.SORT,
                        group_capacity=1 << (dom_size - 1).bit_length())
    srt_cost = task_cost(CopTask.structured(srt, mesh, 0, cols, counts, ()))
    assert not srt_cost.dense_blowups and not srt_cost.unbounded
    assert srt_cost.peak_hbm_bytes > 0


def test_dense_blowup_gate_finding():
    """cost_findings reports COST-DENSE-BLOWUP for a degenerate dense
    corpus plan (seeded via a fake physical op)."""
    snap = _key_snapshot(1024)

    class _FakeExec:
        table = type("T", (), {"snapshot": staticmethod(lambda: snap)})()
        children = ()
        dag = _count_by_key(D.GroupStrategy.DENSE,
                            domain_sizes=(4 * DENSE_BLOWUP_MIN_GROUPS,))
    _FakeExec.__name__ = "CopTaskExec"

    finds = cost_findings([("select 1", _FakeExec())], n_devices=N_DEV)
    assert any(f.rule == "COST-DENSE-BLOWUP" for f in finds), finds


def test_seeded_padding_waste_is_a_gate_finding():
    """A near-empty table under the pow2 + min_capacity stacking pads
    thousands of cells per live row — COST-PAD-WASTE."""
    from tidb_tpu.session import Domain, Session
    dom = Domain()
    s = Session(dom)
    s.execute("create table tiny (a bigint)")
    s.execute("insert into tiny values (1),(2),(3)")
    from tidb_tpu.sql.parser import parse_one
    _built, phys = s._plan_select(parse_one("select count(*) from tiny"))
    findings = cost_findings([("select count tiny", phys)],
                             n_devices=N_DEV)
    assert [f.rule for f in findings] == ["COST-PAD-WASTE"]


# ------------------------------------------------------------------ #
# sched admission: budget + deferral + window feedback
# ------------------------------------------------------------------ #

def test_auto_budget_has_no_default_on_a_tpu_mesh():
    """The auto budget comes from the device's own bytes_limit.  A CPU
    mesh reports none and takes the host constant; a TPU mesh that
    reports none raises instead of admitting against a guess."""
    from types import SimpleNamespace

    from tidb_tpu.analysis.copcost import (DEFAULT_CPU_HBM_BUDGET,
                                           HBM_BUDGET_FRACTION,
                                           mesh_hbm_budget)

    def fake_mesh(platform, stats, n=4):
        dev = SimpleNamespace(platform=platform,
                              memory_stats=lambda: stats)
        return SimpleNamespace(devices=np.array([dev] * n, dtype=object))

    assert mesh_hbm_budget(fake_mesh("cpu", None)) == DEFAULT_CPU_HBM_BUDGET
    limit = 16 << 30
    assert mesh_hbm_budget(fake_mesh("tpu", {"bytes_limit": limit})) \
        == int(HBM_BUDGET_FRACTION * limit) * 4
    for stats in (None, {}, {"bytes_in_use": 1}):
        with pytest.raises(RuntimeError, match="bytes_limit"):
            mesh_hbm_budget(fake_mesh("tpu", stats))


def test_budget_rejects_pre_trace_and_query_errors_cleanly(monkeypatch):
    """Integration: tidb_tpu_sched_hbm_budget below the query footprint
    => the statement fails with a structured planner-style error BEFORE
    any trace, the reject counter is visible on the /sched payload, and
    lifting the budget lets the same query complete."""
    from tidb_tpu.planner.build import PlanError
    from tidb_tpu.session import Domain, Session
    dom = Domain()
    s = Session(dom)
    s.execute("create table t (q bigint, p bigint)")
    s.execute("insert into t values " + ",".join(
        f"({i % 50}, {i})" for i in range(1000)))
    # pin the device path open (the CPU engine choice would bypass the
    # scheduler entirely) and disable the result cache
    monkeypatch.setattr(type(dom.client), "_platform",
                        lambda self: "tpu")
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    try:
        s.execute("set global tidb_tpu_sched_hbm_budget = 4096")
        import tidb_tpu.parallel.spmd as spmd
        real = spmd.get_sharded_program

        def boom(*_a, **_k):
            raise AssertionError("traced an over-budget program")
        monkeypatch.setattr(spmd, "get_sharded_program", boom)
        with pytest.raises(PlanError) as ei:
            s.must_query("select sum(p) from t where q < 10")
        assert isinstance(ei.value, CostError)
        assert ei.value.rule == "hbm-budget"
        stats = dom.client.sched_stats()     # the /sched payload
        assert stats["budget_rejects"] >= 1
        assert stats["hbm_budget"] == 4096
        # lift the budget: the same statement completes
        monkeypatch.setattr(spmd, "get_sharded_program", real)
        s.execute("set global tidb_tpu_sched_hbm_budget = 0")
        rows = s.must_query("select sum(p) from t where q < 10")
        assert rows[0][0] == sum(i for i in range(1000) if i % 50 < 10)
    finally:
        s.execute("set global tidb_tpu_sched_hbm_budget = -1")
        s.execute("set global tidb_tpu_result_cache_entries = -1")


def test_fusion_drain_caps_group_by_summed_footprint(mesh):
    """Two compatible tasks whose summed footprint overflows the budget
    launch apart: the rider is deferred (counter moves) and still
    completes on its own later drain round."""
    sched = DeviceScheduler()
    sched.pause()
    served: list = []

    def fake_serve(batch):
        served.append(list(batch))
        for t in batch:
            t.finish(("prog", "out"))
    sched._serve = fake_serve

    agg = D.Aggregation(
        child=D.TableScan((0,), (dt.bigint(False),)),
        aggs=(D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
        strategy=D.GroupStrategy.SCALAR)
    t1_cols, t1_counts = [(jnp.zeros((8, 64), jnp.int64), None)], \
        jnp.full((8,), 64, jnp.int64)
    t2_cols, t2_counts = [(jnp.ones((8, 64), jnp.int64), None)], \
        jnp.full((8,), 64, jnp.int64)
    t1 = CopTask.structured(agg, mesh, 0, t1_cols, t1_counts, ())
    t2 = CopTask.structured(agg, mesh, 0, t2_cols, t2_counts, ())
    one = task_cost(t1).peak_hbm_bytes
    # room for one task plus half another: the rider must defer
    sched.configure(hbm_budget=int(one * 1.5))
    sched.submit(t1)
    sched.submit(t2)
    assert sched.budget_admitted == 2        # both fit solo
    sched.resume()
    t1.wait()
    t2.wait()
    assert sched.budget_deferrals >= 1
    assert all(len(b) == 1 for b in served), served
    stats = sched.stats()
    assert stats["budget_deferrals"] >= 1
    assert stats["last_launch_bytes"] > 0


def test_window_feedback_decays_unpaying_key_to_zero():
    """ROADMAP window-feedback item: a key whose holds never yield
    riders loses its micro-batch window entirely; one hit recovers it."""
    sched = DeviceScheduler()
    lead = CopTask(key=("k",), fusion_key=None, fn=None)
    fk = lead.key
    sched._fk_gap[fk] = 100_000           # 100us EWMA arrival gap
    assert sched._window_ns(lead) == 200_000   # optimistic prior: 2x gap
    for _ in range(40):
        sched._note_window_outcome(lead, False)
    assert sched._window_ns(lead) == 0    # decayed below the floor
    for _ in range(6):
        sched._note_window_outcome(lead, True)
    assert sched._window_ns(lead) > 0     # hits recover the hold
    assert sched.window_hits == 6
    # the prior really is optimistic full-window
    assert sched._fk_hit.get("fresh", WINDOW_HIT_INIT) == WINDOW_HIT_INIT


def test_task_cost_never_syncs_device(q6_cop, mesh, monkeypatch):
    """task_cost reads array metadata only — a device_get anywhere in
    the admission path would serialize the launch pipeline."""
    _phys, cop = q6_cop
    cols, counts = _device_inputs()
    task = CopTask.structured(cop.dag, mesh, 0, cols, counts, ())

    def boom(*_a, **_k):
        raise AssertionError("admission path synced the device")
    monkeypatch.setattr(jax, "device_get", boom)
    cost = task_cost(task)
    assert cost is not None and cost.peak_hbm_bytes > 0
    assert cost.input_bytes == sum(
        int(v.nbytes) for v, _m in cols) + int(counts.nbytes)
