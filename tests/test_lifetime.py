"""coplife (analysis/lifetime, ISSUE 7): static buffer-lifetime
classification, DonationPlan-derived donate_argnums in the spmd
builders, donation-safe launches on the 8-vdev CPU mesh, and the
DONATE-* gate rules.

Four layers under test:

- classification: the regrow disciplines of store/client.py map to the
  right lifetime classes (paging rows / group regrow / join regrow =
  LOOP-CARRIED, in-program aggs = EPHEMERAL) and each program shape
  derives the right donate_argnums,
- safety: a seeded unsafe plan is rejected PRE-TRACE at the builder and
  a donating task over a live snapshot resident (or a loop-carried
  program) is rejected at sched admission,
- execution: donation-on and donation-off launches are bit-identical
  across solo/batched/fused shapes, the streamed paging loop donates
  its batches, and the PERSISTENT snapshot residents survive it all,
- cost/gate: donated_bytes strictly tightens peak_hbm_bytes, the TPC-H
  corpus is donation-clean with finite plans, seeded DONATE-UNSAFE /
  DONATE-MISSED findings fire, and the TPU-DONATE lint rule holds.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.analysis import lifetime as L
from tidb_tpu.analysis.copcost import dag_cost, snapshot_layout, task_cost
from tidb_tpu.analysis.lifetime import (BufferClass, DonationError,
                                        donation_findings, donation_plan,
                                        donation_report, is_resident,
                                        scan_lifetime, verify_donation)
from tidb_tpu.copr import dag as D
from tidb_tpu.expr.ir import ColumnRef
from tidb_tpu.parallel import spmd
from tidb_tpu.parallel.mesh import get_mesh, sharded
from tidb_tpu.sched import CopTask, DeviceScheduler
from tidb_tpu.store import CopClient, snapshot_from_columns
from tidb_tpu.testing.tpch import built_tpch_plans, tpch_plan_session
from tidb_tpu.types import dtypes as dt

N_DEV = 8
BIG = dt.bigint(True)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8
    return get_mesh()


@pytest.fixture(scope="module")
def corpus():
    s = tpch_plan_session()
    return s, list(built_tpch_plans(s))


def _scan():
    return D.TableScan((0,), (BIG,))


def _scalar_agg(func=D.AggFunc.SUM):
    from tidb_tpu.copr.aggregate import sum_out_dtype
    arg = None if func is D.AggFunc.COUNT else ColumnRef(BIG, 0)
    out = dt.bigint(False) if func is D.AggFunc.COUNT \
        else sum_out_dtype(BIG) if func is D.AggFunc.SUM else BIG
    return D.Aggregation(child=_scan(),
                         aggs=(D.AggDesc(func, arg, out),),
                         strategy=D.GroupStrategy.SCALAR)


def _sort_agg():
    return D.Aggregation(
        child=_scan(), group_by=(ColumnRef(BIG, 0),),
        aggs=(D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
        strategy=D.GroupStrategy.SORT, group_capacity=64)


def _join_agg():
    join = D.LookupJoin(child=_scan(), probe_key=ColumnRef(BIG, 0),
                        kind="inner", build_dtypes=(BIG,), unique=False,
                        out_capacity=256)
    return D.Aggregation(child=join,
                         aggs=(D.AggDesc(D.AggFunc.COUNT, None,
                                         dt.bigint(False)),),
                         strategy=D.GroupStrategy.SCALAR)


def _mk_inputs(mesh, seed=0, s=8, cap=64):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 1000, (s, cap)).astype(np.int64)
    valid = rng.random((s, cap)) > 0.1
    counts = rng.integers(1, cap + 1, s).astype(np.int64)
    sh = sharded(mesh)
    cols = [(jax.device_put(data, sh), jax.device_put(valid, sh))]
    return cols, jax.device_put(counts, sh)


def _tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _no_trace(monkeypatch):
    import tidb_tpu.parallel.spmd as sp

    def boom(*_a, **_k):
        raise AssertionError("reached tracing/compilation")
    monkeypatch.setattr(sp, "get_sharded_program", boom)
    monkeypatch.setattr(sp, "get_batched_program", boom)
    monkeypatch.setattr(sp, "get_fused_program", boom)


# ------------------------------------------------------------------ #
# classification + plan derivation
# ------------------------------------------------------------------ #

def test_scan_lifetime_classes():
    assert scan_lifetime(_scalar_agg())[0] is BufferClass.EPHEMERAL
    # every client regrow discipline pins its inputs across launches
    cls, why = scan_lifetime(_scan())
    assert cls is BufferClass.LOOP_CARRIED and "paging" in why
    cls, why = scan_lifetime(_sort_agg())
    assert cls is BufferClass.LOOP_CARRIED and "regrow" in why
    cls, why = scan_lifetime(_join_agg())
    assert cls is BufferClass.LOOP_CARRIED and "join" in why
    unsized = dataclasses.replace(_sort_agg(), group_capacity=0)
    assert scan_lifetime(unsized)[0] is BufferClass.LOOP_CARRIED


def test_donation_plan_argnums_per_program_shape():
    agg = _scalar_agg()
    assert donation_plan(agg, "solo").donate_argnums == (0, 1, 2)
    assert donation_plan(_scan(), "solo").donate_argnums == ()
    assert donation_plan(_sort_agg(), "solo").donate_argnums == ()
    assert donation_plan(_join_agg(), "solo").donate_argnums == ()
    # stacked copies are ephemeral by construction, whatever the dag
    assert donation_plan(agg, "batched").donate_argnums == (0, 1, 2)
    assert donation_plan(_scan(), "batched-rows").donate_argnums \
        == (0, 1, 2)
    fused = D.FusedDag((agg, _scalar_agg(D.AggFunc.COUNT)))
    assert donation_plan(fused, "fused").donate_argnums == (0, 1, 2)
    assert donation_plan(fused, "fused-rows").donate_argnums == ()
    with pytest.raises(ValueError):
        donation_plan(agg, "warp")


def test_fused_shared_aux_slot_refuses_aux_donation():
    """Two fused members reading ONE aux slot: the unfused fallback
    serves them as sequential solo launches over the same aux arrays,
    so the slot must survive — cols/counts stay donatable."""
    def member(slot):
        join = D.LookupJoin(child=_scan(), probe_key=ColumnRef(BIG, 0),
                            kind="inner", build_dtypes=(BIG,),
                            unique=True, aux_slot=slot)
        return D.Aggregation(
            child=join,
            aggs=(D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
            strategy=D.GroupStrategy.SCALAR)
    shared = D.FusedDag((member(0), member(0)))
    plan = donation_plan(shared, "fused")
    assert plan.donate_argnums == (0, 1)
    assert plan.slot(L.ARG_AUX).cls is BufferClass.PERSISTENT
    distinct = D.FusedDag((member(0), member(1)))
    assert donation_plan(distinct, "fused").donate_argnums == (0, 1, 2)


# ------------------------------------------------------------------ #
# safety: seeded unsafe plans rejected pre-trace
# ------------------------------------------------------------------ #

def test_verify_donation_rejects_unsafe_slots():
    with pytest.raises(DonationError) as ei:
        verify_donation(_scan(), (0,), "solo")
    assert ei.value.rule == "donate-unsafe"
    assert "loop-carried" in ei.value.detail
    with pytest.raises(DonationError):
        verify_donation(_scalar_agg(), (7,), "solo")   # not a slot
    verify_donation(_scalar_agg(), (0, 1, 2), "solo")  # derived = ok


def test_builder_rejects_seeded_unsafe_plan_pre_trace(mesh, monkeypatch):
    """A ShardedCopProgram constructed with a donate_argnums override
    the DonationPlan forbids must raise BEFORE anything is handed to
    jax.jit (jit monkeypatched to prove it is never reached)."""
    def boom(*_a, **_k):
        raise AssertionError("reached jax.jit")
    monkeypatch.setattr(spmd.jax, "jit", boom)
    with pytest.raises(DonationError):
        spmd.ShardedCopProgram(_scan(), mesh, 64, donate_argnums=(0,))
    with pytest.raises(DonationError):
        spmd.FusedRowsProgram(
            D.FusedDag((_scan(), D.Limit(_scan(), 4))), mesh, (16, 16),
            donate_argnums=(0, 1))


def test_sched_rejects_donating_task_over_resident(mesh, monkeypatch):
    """The runtime backstop: snapshot residents register PERSISTENT, so
    a donating task carrying them is refused at submit, pre-trace."""
    _no_trace(monkeypatch)
    rng = np.random.default_rng(3)
    from tidb_tpu.chunk.column import Column
    col = Column(BIG, rng.integers(0, 99, 512).astype(np.int64),
                 np.ones(512, bool))
    snap = snapshot_from_columns(["a"], [col], n_shards=8,
                                 min_capacity=64)
    cols, counts = snap.device_cols(mesh)
    assert is_resident(counts)
    task = CopTask.structured(_scalar_agg(), mesh, 0, cols, counts, (),
                              donate=True)
    with pytest.raises(DonationError) as ei:
        DeviceScheduler().submit(task)
    assert ei.value.rule == "donate-unsafe"
    assert "resident" in ei.value.detail
    # the same arrays WITHOUT donation admit fine (cost gate only)
    ok = CopTask.structured(_scalar_agg(), mesh, 0, cols, counts, ())
    assert ok.donate is False and ok.key != task.key


def test_sched_rejects_donating_loop_carried_task(mesh, monkeypatch):
    _no_trace(monkeypatch)
    cols, counts = _mk_inputs(mesh, seed=5)
    task = CopTask.structured(_scan(), mesh, 64, cols, counts, (),
                              donate=True)
    with pytest.raises(DonationError) as ei:
        DeviceScheduler().submit(task)
    assert ei.value.rule == "donate-unsafe"


# ------------------------------------------------------------------ #
# execution: donation on vs off is bit-identical
# ------------------------------------------------------------------ #

def test_solo_donating_launch_bit_identical(mesh):
    for func in (D.AggFunc.SUM, D.AggFunc.COUNT, D.AggFunc.MAX):
        agg = _scalar_agg(func)
        cols_a, counts_a = _mk_inputs(mesh, seed=7)
        cols_b, counts_b = _mk_inputs(mesh, seed=7)   # same values
        off = spmd.ShardedCopProgram(agg, mesh)
        on = spmd.ShardedCopProgram(agg, mesh, donate=True)
        assert on._donate_argnums == (0, 1, 2)
        _tree_equal(off(cols_a, counts_a), on(cols_b, counts_b))


def test_batched_donating_launch_bit_identical(mesh):
    """The stacked copies are donated, the MEMBER arrays are not: the
    same member inputs run through both variants untouched."""
    agg = _scalar_agg()
    in1 = _mk_inputs(mesh, seed=11)
    in2 = _mk_inputs(mesh, seed=12)
    off = spmd.BatchedCopProgram(agg, mesh, 2, donate=False)
    on = spmd.BatchedCopProgram(agg, mesh, 2)
    assert off._donate_argnums == () and on._donate_argnums == (0, 1, 2)
    outs_off = off([in1[0], in2[0]], [in1[1], in2[1]])
    outs_on = on([in1[0], in2[0]], [in1[1], in2[1]])
    _tree_equal(outs_off, outs_on)
    # member arrays survived both launches (only the stacks died)
    assert not in1[0][0][0].is_deleted() and not in1[1].is_deleted()


def test_batched_rows_donating_launch_bit_identical(mesh):
    scan = _scan()
    in1 = _mk_inputs(mesh, seed=13)
    in2 = _mk_inputs(mesh, seed=14)
    off = spmd.BatchedRowsProgram(scan, mesh, 64, 2, donate=False)
    on = spmd.BatchedRowsProgram(scan, mesh, 64, 2)
    outs_off = off([in1[0], in2[0]], [in1[1], in2[1]])
    outs_on = on([in1[0], in2[0]], [in1[1], in2[1]])
    _tree_equal(outs_off, outs_on)
    assert not in2[0][0][0].is_deleted()


def test_fused_donating_launch_bit_identical(mesh):
    fused = D.FusedDag((_scalar_agg(D.AggFunc.SUM),
                        _scalar_agg(D.AggFunc.COUNT)))
    cols_a, counts_a = _mk_inputs(mesh, seed=21)
    cols_b, counts_b = _mk_inputs(mesh, seed=21)
    off = spmd.FusedCopProgram(fused, mesh)
    on = spmd.FusedCopProgram(fused, mesh, donate=True)
    assert on._donate_argnums == (0, 1, 2)
    _tree_equal(off(cols_a, counts_a), on(cols_b, counts_b))


def _donated_more(sched, donated0, timeout=10.0) -> bool:
    """Did a launch since `donated0` donate?  The drain thread counts a
    launch (`_account`) AFTER it has answered the statement's thread
    (`finish()`), so the answer can be here before the count: wait for
    it (one streamed batch is one launch, and a loaded machine took the
    interpreter from the drain in between: PR 44's full run)."""
    import time
    deadline = time.monotonic() + timeout
    while sched.donated_tasks <= donated0 and time.monotonic() < deadline:
        time.sleep(0.01)
    return sched.donated_tasks > donated0


def test_streamed_paging_loop_donates_and_residents_survive(mesh):
    """The acceptance shape: a paging-loop (streamed HBM batches) query
    donates its ephemeral batches — bit-identical to the resident run —
    while the snapshot's PERSISTENT device_cols stay live and reusable
    afterwards."""
    from tidb_tpu.chunk.column import Column
    from tidb_tpu.sched import scheduler_for
    rng = np.random.default_rng(17)
    n = 6000
    vals = rng.integers(0, 50_000, n).astype(np.int64)
    col = Column(BIG, vals, np.ones(n, bool))
    snap = snapshot_from_columns(["a"], [col], n_shards=8,
                                 min_capacity=64)
    client = CopClient(mesh)
    client._platform = lambda: "tpu"      # pin the device path open
    client._result_cache_cap = 0          # every run really launches
    agg = _scalar_agg()
    resident = client.execute_agg(agg, snap, [])
    cols, counts = snap.device_cols(mesh)
    sched = scheduler_for(mesh)
    donated0 = sched.donated_tasks
    client.device_mem_cap = 4096          # force multi-batch streaming
    streamed = client.execute_agg(agg, snap, [])
    assert [c.to_python() for c in streamed.columns] \
        == [c.to_python() for c in resident.columns]
    assert int(streamed.columns[0].data[0]) == int(vals.sum())
    assert _donated_more(sched, donated0)         # batches donated
    assert sched.donated_bytes >= 0
    # PERSISTENT residents survived every donating launch...
    assert not counts.is_deleted()
    assert all(not v.is_deleted() for v, _m in cols)
    assert is_resident(counts)
    # ...and are still usable by a fresh resident launch
    client.device_mem_cap = 0
    again = client.execute_agg(agg, snap, [])
    assert int(again.columns[0].data[0]) == int(vals.sum())


def test_corpus_query_paging_loop_donates(corpus, mesh):
    """Acceptance pin: a TPC-H corpus query (Q6-shaped revenue agg) run
    through the streamed paging loop donates its ephemeral batches, its
    copcost peak under donation is STRICTLY below the pre-donation
    bound, and the corpus snapshot's residents stay live."""
    from tidb_tpu.sched import scheduler_for
    _s, plans = corpus
    phys = next(p for q, p in plans if "revenue" in q)

    def find_cop(op):
        if type(op).__name__ == "CopTaskExec":
            return op
        for c in getattr(op, "children", []) or []:
            r = find_cop(c) if c is not None else None
            if r is not None:
                return r
        return None
    cop = find_cop(phys)
    assert isinstance(cop.dag, D.Aggregation)
    plan = donation_plan(cop.dag, "solo")
    assert plan.donate_argnums           # ephemeral: the plan donates
    snap = cop.table.snapshot()
    layout = snapshot_layout(snap, N_DEV)
    plain = dag_cost(cop.dag, layout, None, input_bytes=1 << 20)
    tight = dag_cost(cop.dag, layout, None, input_bytes=1 << 20,
                     donation=plan)
    assert tight.donated_bytes >= 1      # >= one donated buffer's bytes
    assert tight.peak_hbm_bytes < plain.peak_hbm_bytes
    client = CopClient(mesh)
    client._platform = lambda: "tpu"
    client._result_cache_cap = 0
    resident = client.execute_agg(cop.dag, snap, [])
    cols, counts = snap.device_cols(mesh)
    sched = scheduler_for(mesh)
    donated0 = sched.donated_tasks
    client.device_mem_cap = 2048
    streamed = client.execute_agg(cop.dag, snap, [])
    assert [c.to_python() for c in streamed.columns] \
        == [c.to_python() for c in resident.columns]
    assert _donated_more(sched, donated0)
    assert not counts.is_deleted()
    assert all(not v.is_deleted() for v, _m in cols)


# ------------------------------------------------------------------ #
# copcost: donation tightens the admission bound
# ------------------------------------------------------------------ #

def test_donated_bytes_strictly_tighten_peak(mesh):
    agg = _scalar_agg()
    layout = snapshot_layout(
        snapshot_from_columns(
            ["a"], [__import__("tidb_tpu.chunk.column",
                               fromlist=["Column"]).Column(
                BIG, np.arange(4096, dtype=np.int64),
                np.ones(4096, bool))], n_shards=8), N_DEV)
    plain = dag_cost(agg, layout, None, input_bytes=1 << 20)
    donated = dag_cost(agg, layout, None, input_bytes=1 << 20,
                       donation=donation_plan(agg, "solo"))
    assert donated.donated_bytes > 0
    assert donated.peak_hbm_bytes < plain.peak_hbm_bytes
    # loop-carried plans never tighten
    rows = dag_cost(_scan(), layout, None, input_bytes=1 << 20,
                    donation=donation_plan(_scan(), "solo"))
    assert rows.donated_bytes == 0


def test_task_cost_honors_donate_flag(mesh):
    cols, counts = _mk_inputs(mesh, seed=23)
    t_off = CopTask.structured(_scalar_agg(), mesh, 0, cols, counts, ())
    t_on = CopTask.structured(_scalar_agg(), mesh, 0, cols, counts, (),
                              donate=True)
    c_off, c_on = task_cost(t_off), task_cost(t_on)
    assert c_on.donated_bytes > 0
    assert c_on.peak_hbm_bytes < c_off.peak_hbm_bytes


# ------------------------------------------------------------------ #
# gate rules + corpus + report
# ------------------------------------------------------------------ #

def test_corpus_donation_clean_with_finite_plans(corpus):
    _s, plans = corpus
    assert donation_findings(plans, n_devices=N_DEV) == []
    planned = 0
    for _sql, phys in plans:
        for _op, dag in L._plan_cop_ops(phys):
            plan = donation_plan(dag, "solo")
            assert isinstance(plan.donate_argnums, tuple)
            planned += 1
    assert planned >= 8
    report = donation_report(plans, n_devices=N_DEV)
    lines = report.splitlines()
    assert len(lines) == len(plans) + 2        # header + rows + summary
    assert f"donation: {len(plans)}/{len(plans)}" in lines[-1]
    assert "ephemeral" in report and "loop-carried" in report


def test_seeded_donate_unsafe_is_a_gate_finding(corpus, monkeypatch):
    """A rotted plan derivation (donating a loop-carried rows slot)
    must surface as DONATE-UNSAFE on the corpus walk."""
    _s, plans = corpus
    phys = next(p for q, p in plans if "limit 5" in q)     # rows plan
    bad = L.DonationPlan(
        "solo",
        (L.SlotLife("cols", 0, BufferClass.LOOP_CARRIED, "paging"),
         L.SlotLife("counts", 1, BufferClass.LOOP_CARRIED, "paging"),
         L.SlotLife("aux", 2, BufferClass.LOOP_CARRIED, "paging")),
        (0,))
    monkeypatch.setattr(L, "donation_plan", lambda *_a, **_k: bad)
    findings = donation_findings([("select seeded", phys)],
                                 n_devices=N_DEV)
    assert [f.rule for f in findings] == ["DONATE-UNSAFE"]


def test_seeded_donate_missed_is_a_gate_finding(corpus, monkeypatch):
    """An EPHEMERAL scan slot above the floor left undonated fires
    DONATE-MISSED (floor shrunk so the toy corpus tables qualify)."""
    _s, plans = corpus
    sql, phys = next(
        (q, p) for q, p in plans
        if L._plan_cop_ops(p)
        and all(scan_lifetime(d)[0] is BufferClass.EPHEMERAL
                for _o, d in L._plan_cop_ops(p)))
    opted_out = L.DonationPlan(
        "solo",
        (L.SlotLife("cols", 0, BufferClass.EPHEMERAL, "one-shot"),
         L.SlotLife("counts", 1, BufferClass.EPHEMERAL, "one-shot"),
         L.SlotLife("aux", 2, BufferClass.EPHEMERAL, "one-shot")),
        ())
    monkeypatch.setattr(L, "donation_plan", lambda *_a, **_k: opted_out)
    monkeypatch.setattr(L, "DONATE_MISSED_MIN_BYTES", 1)
    findings = donation_findings([(sql, phys)], n_devices=N_DEV)
    assert findings and all(f.rule == "DONATE-MISSED" for f in findings)


# ------------------------------------------------------------------ #
# TPU-DONATE lint rule
# ------------------------------------------------------------------ #

def test_tpu_donate_lint_literal_fails():
    from tidb_tpu.analysis.lint import lint_source
    src = "f = jax.jit(fn, donate_argnums=(0, 1))\n"
    rules = [f.rule for f in lint_source(src, "copr/exec.py")]
    assert "TPU-DONATE" in rules
    src2 = "f = jax.jit(fn, donate_argnums=0)\n"
    assert "TPU-DONATE" in [f.rule for f in
                            lint_source(src2, "parallel/spmd.py")]
    # a name that is not plan-derived fails too
    src3 = "f = jax.jit(fn, donate_argnums=nums)\n"
    assert "TPU-DONATE" in [f.rule for f in
                            lint_source(src3, "copr/exec.py")]


def test_tpu_donate_lint_plan_derived_passes():
    from tidb_tpu.analysis.lint import lint_source
    ok = ("f = jax.jit(fn, donate_argnums=self._donate_argnums)\n"
          "g = jax.jit(fn, donate_argnums=())\n"
          "h = jax.jit(fn, donate_argnums=plan.donate_argnums)\n")
    assert [f for f in lint_source(ok, "parallel/spmd.py")
            if f.rule == "TPU-DONATE"] == []
    # untracked modules are out of scope
    lit = "f = jax.jit(fn, donate_argnums=(0,))\n"
    assert [f for f in lint_source(lit, "utils/poolmgr.py")
            if f.rule == "TPU-DONATE"] == []


def test_repo_sweep_has_zero_tpu_donate_findings():
    from tidb_tpu.analysis.lint import lint_tree
    assert [str(f) for f in lint_tree() if f.rule == "TPU-DONATE"] == []


# ------------------------------------------------------------------ #
# registry + surfacing
# ------------------------------------------------------------------ #

def test_resident_registry_tracks_exact_objects(mesh):
    batch = jnp.arange(8, dtype=jnp.int64)
    assert not is_resident(batch)
    L.register_resident(batch)
    assert is_resident(batch)
    other = jnp.arange(8, dtype=jnp.int64)
    assert not is_resident(other)


def test_explain_footer_reports_donation():
    from tidb_tpu.session import Domain, Session
    dom = Domain()
    s = Session(dom)
    s.execute("create table lt (a bigint, b bigint)")
    s.execute("insert into lt values " + ",".join(
        f"({i},{i % 7})" for i in range(256)))
    r = s.execute("explain select sum(a*b) from lt where a > 3")
    text = "\n".join(row[0] for row in r.rows)
    assert "contract: ok" in text
    assert "donate:" in text and "bufs" in text
