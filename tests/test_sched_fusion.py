"""Cross-query kernel fusion in the admission scheduler (sched/):
one scan, many payloads.

Concurrent sessions scanning the SAME table but computing DIFFERENT
aggregates fuse into ONE device program (spmd.FusedCopProgram) whose
output carries each member's payload as a separate leaf; the fusion key
is contract-aware (analysis.contracts.fusion_signature — no tracing)
and incompatible pairs are REFUSED pre-launch by verify_fusion_group.
Also covers the two launch-shape follow-ons landed with it: rows-kind
batched (vmapped) launches and the adaptive micro-batch window.

The drain thread never compiles a group program, and nothing compiles
one by itself: a member set whose fused (or batched) program is not
loaded is served apart, by each member's solo program, until an
explicit `warm_groups()` has compiled it on a background thread; from
then on the set shares ONE launch.  So every test of a group launch
here runs its queries once, warms (`_land`), and reads the group form
off the second round.

Like tests/test_sched.py, concurrency tests pin the device path open
(`_platform` -> "tpu") and pause the drain loop so queue buildup is
deterministic.
"""

import threading
import time

import numpy as np
import pytest

from tidb_tpu.analysis.contracts import (PlanContractError,
                                         fusion_signature,
                                         verify_fusion_group)
from tidb_tpu.copr import dag as D
from tidb_tpu.expr.ir import ColumnRef
from tidb_tpu.parallel import spmd
from tidb_tpu.sched import CopTask, DeviceScheduler
from tidb_tpu.session import Domain, Session
from tidb_tpu.types import dtypes as dt


def _wait_until(pred, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


def _land(sched, timeout=120.0):
    """The explicit warm, and its background threads done: every group
    program of a set that was served apart is loaded."""
    sched.warm_groups()
    _wait_until(lambda: not sched._groups_pending
                and not sched._groups_inflight and not sched._warm_alive,
                timeout, "the background group compiles")


def _mk_table(s: Session, name: str = "t", n: int = 4000, seed: int = 0):
    rng = np.random.default_rng(seed)
    q = rng.integers(1, 50, n)
    d = rng.integers(0, 10, n)
    p = rng.integers(100, 10_000, n)
    s.execute(f"create table {name} (q bigint, d bigint, p bigint)")
    s.execute(f"insert into {name} values "
              + ",".join(f"({a},{b},{c})" for a, b, c in zip(q, d, p)))
    return q, d, p


# one query per device aggregate op kind (COUNT / SUM / MIN / MAX), all
# over one shared scan, each with its own filter.  The SUMs prove
# narrow under copnum (single-word int64 states) and fuse under their
# own ('agg-narrow', ...) class, apart from the limb aggs — two SUMs so
# that class also gets a real (>=2 member) fused launch.
FUSION_QUERIES = [
    "select count(*) from t where d >= 5",
    "select sum(p * d) from t where q < 24",
    "select min(p) from t where q > 10",
    "select max(p) from t where d < 8",
    "select sum(p) from t where q > 5",
]


def _fusion_domain():
    dom = Domain()
    s = Session(dom)
    data = _mk_table(s)
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    # schedulers are process-wide per mesh fingerprint: pin the knobs a
    # previous test may have tightened (max_coalesce etc.)
    s.execute("set global tidb_tpu_sched_max_coalesce = 8")
    s.execute("set global tidb_tpu_sched_fusion = 1")
    s.execute("set global tidb_tpu_sched_window_us = -1")
    dom.client._platform = lambda: "tpu"
    return dom, s, data


def _run_concurrent(dom, sched, queries):
    """Queue `queries` from concurrent sessions while the drain is
    paused, then release and collect results."""
    out, errors = {}, []

    def run(i, q):
        try:
            out[i] = Session(dom).must_query(q)
        except Exception as e:  # noqa: BLE001 surfaced via assert
            errors.append(e)
    sched.pause()
    try:
        threads = [threading.Thread(target=run, args=(i, q))
                   for i, q in enumerate(queries)]
        for t in threads:
            t.start()
        _wait_until(lambda: sched.depth >= len(queries),
                    msg=f"{len(queries)} queued cop tasks")
    finally:
        sched.resume()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    return out


def test_first_seen_sets_are_served_apart_and_fuse_the_next_time():
    """N sessions x N DIFFERENT aggregates over one table.  The first
    time the two member sets turn up (the limb aggs, the proven-narrow
    SUMs) their fused programs are not loaded: the drain serves the
    members apart (`groups_apart_unloaded`, NOT a refusal);
    `warm_groups` compiles what co-occurred on a background thread.
    The second time each set is ONE launch (fewer launches than tasks, every member fused),
    no new solo-program compiles, answers exact both times."""
    from tidb_tpu.compilecache import simulate_restart
    dom, s, _data = _fusion_domain()
    simulate_restart()        # no group program of an earlier test
    # warm-up: compiles each member program once, starts the scheduler
    solo = [Session(dom).must_query(q) for q in FUSION_QUERIES]
    sched = dom.client._sched_obj
    assert sched is not None, "launch did not route through the scheduler"
    _land(sched)
    misses0 = spmd._cached.cache_info().misses
    f0, r0 = sched.fused_launches, sched.fused_refused
    a0, c0 = sched.groups_apart_unloaded, sched.group_compiles_bg

    out = _run_concurrent(dom, sched, FUSION_QUERIES)
    assert [out[i] for i in range(len(FUSION_QUERIES))] == solo
    assert sched.fused_launches == f0, "a first-seen set fused at once"
    assert sched.groups_apart_unloaded == a0 + 2
    assert sched.fused_refused == r0
    _land(sched)
    assert sched.group_compiles_bg == c0 + 2
    assert sched.warm_failures == 0

    l0, ft0, t0 = sched.launches, sched.fused_tasks, sched.tasks_done
    out = _run_concurrent(dom, sched, FUSION_QUERIES)
    # every session got the same answer a solo run produces...
    assert [out[i] for i in range(len(FUSION_QUERIES))] == solo
    # ...both classes fused: fewer launches than tasks, fused launches
    # seen, and EVERY member (limb and narrow alike) rode a fusion
    assert sched.fused_launches == f0 + 2
    assert sched.launches - l0 < sched.tasks_done - t0
    assert sched.fused_tasks - ft0 >= len(FUSION_QUERIES)
    assert sched.groups_apart_unloaded == a0 + 2
    assert sched.group_compiles_bg == c0 + 2
    # ...and the compile count stayed flat vs the warmed single-session
    # programs (the fused program caches separately on the FusedDag)
    assert spmd._cached.cache_info().misses == misses0


def test_fused_results_bit_identical_across_op_kinds():
    """Each device agg op kind (COUNT/SUM/MIN/MAX) returns EXACTLY the
    solo-run value whatever launch served it: apart while the fused
    program is not loaded, fused afterwards — run three times so the
    apart round and two fused rounds are covered."""
    dom, s, _data = _fusion_domain()
    solo = [Session(dom).must_query(q) for q in FUSION_QUERIES]
    sched = dom.client._sched_obj
    for _round in range(3):
        out = _run_concurrent(dom, sched, FUSION_QUERIES)
        for i, exp in enumerate(solo):
            assert out[i] == exp, (FUSION_QUERIES[i], out[i], exp)
        _land(sched)
    assert sched.fused_launches >= 1


def _mk_agg_dag(strategy=D.GroupStrategy.SCALAR,
                func=D.AggFunc.COUNT, arg=None):
    scan = D.TableScan((0,), (dt.bigint(False),))
    return D.Aggregation(
        child=scan, aggs=(D.AggDesc(func, arg, dt.bigint(False)),),
        strategy=strategy,
        group_by=(ColumnRef(dt.bigint(False), 0),)
        if strategy == D.GroupStrategy.SORT else (),
        group_capacity=64 if strategy == D.GroupStrategy.SORT else 0)


class _FakeTask:
    """Just enough of CopTask for verify_fusion_group."""

    def __init__(self, dag, fp=("x",), sig=(("s", "i8"),), token=(1, 2, 3),
                 aux=()):
        self.key = (D.dag_digest(dag), fp, 0, sig)
        self.dag = dag
        self.input_token = token
        self.aux = aux


def test_fusion_signature_contract_class():
    """Fusable classes: in-program agg chains ('inprog-agg'),
    extras-free rows chains ('rows'), and — the ISSUE 11 fusion-breadth
    satellite — SORT aggs with a concrete pow2 capacity
    ('sort-agg', cap); a SORT agg the planner left unsized (capacity 0:
    the client owns sizing) still has no static shape class."""
    assert fusion_signature(_mk_agg_dag()) == ("inprog-agg",)
    # capacity-bucketed SORT shape class (pow2 capacities, which is all
    # the planner/regrow discipline ever produces)
    assert fusion_signature(
        _mk_agg_dag(strategy=D.GroupStrategy.SORT)) == ("sort-agg", 64)
    import dataclasses
    unsized = dataclasses.replace(
        _mk_agg_dag(strategy=D.GroupStrategy.SORT), group_capacity=0)
    assert fusion_signature(unsized) is None
    lopsided = dataclasses.replace(
        _mk_agg_dag(strategy=D.GroupStrategy.SORT), group_capacity=100)
    assert fusion_signature(lopsided) is None      # non-pow2: no class
    scan = D.TableScan((0,), (dt.bigint(False),))
    # rows chains fuse now, with per-member output capacities
    assert fusion_signature(D.Limit(scan, 5)) == ("rows",)
    assert fusion_signature(scan) == ("rows",)
    srt = D.Aggregation(
        child=scan, group_by=(ColumnRef(dt.bigint(False), 0),),
        aggs=(D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False)),),
        strategy=D.GroupStrategy.SORT, group_capacity=4096)
    assert fusion_signature(srt) == ("sort-agg", 4096)


def test_rows_plans_sharing_scan_fuse_with_per_member_capacities():
    """Fusion-breadth follow-on (ROADMAP): two DIFFERENT row-returning
    plans over ONE table share the scan in a single FusedRowsProgram,
    each keeping its own output capacity (a TopN's limit-sized buffer
    next to a selection's paging capacity), results exact."""
    dom, s, _data = _fusion_domain()
    qa = "select p from t where d = 3"
    qb = "select q from t order by q desc, p desc limit 7"
    solo = [sorted(Session(dom).must_query(qa)),
            Session(dom).must_query(qb)]
    sched = dom.client._sched_obj
    out = _run_concurrent(dom, sched, [qa, qb])     # apart, or loaded
    assert sorted(out[0]) == solo[0]
    assert out[1] == solo[1]
    _land(sched)
    f0, l0 = sched.fused_launches, sched.launches
    t0 = sched.tasks_done
    out = _run_concurrent(dom, sched, [qa, qb])
    assert sorted(out[0]) == solo[0]
    assert out[1] == solo[1]
    assert sched.fused_launches > f0
    assert sched.launches - l0 < sched.tasks_done - t0


def test_fusion_refused_for_contract_incompatible_pairs():
    """Mesh / capacity(dtype) / scan-input mismatches are REFUSED with a
    structured PlanContractError before anything launches."""
    a = _mk_agg_dag()
    b = _mk_agg_dag(func=D.AggFunc.SUM, arg=ColumnRef(dt.bigint(False), 0))
    ok = [_FakeTask(a), _FakeTask(b)]
    verify_fusion_group(ok)        # compatible pair passes

    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group([_FakeTask(a), _FakeTask(b, fp=("y",))])
    assert ei.value.rule == "mesh-mismatch"

    # capacity signature carries shapes AND dtypes: either mismatch kills
    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group(
            [_FakeTask(a), _FakeTask(b, sig=(("s", "f8"),))])
    assert ei.value.rule == "capacity-shape"

    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group([_FakeTask(a), _FakeTask(b, token=(9, 9, 9))])
    assert ei.value.rule == "fusion-input"

    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group([_FakeTask(a), _FakeTask(b, aux=(((1,),),))])
    assert ei.value.rule == "fusion-input"

    with pytest.raises(PlanContractError) as ei:
        verify_fusion_group(
            [_FakeTask(a),
             _FakeTask(_mk_agg_dag(strategy=D.GroupStrategy.SORT))])
    assert ei.value.rule == "fusion-class"

    with pytest.raises(PlanContractError):
        verify_fusion_group([_FakeTask(a)])      # no solo "groups"


def test_incompatible_tables_do_not_fuse_end_to_end():
    """Two sessions over DIFFERENT tables (different snapshot scans and
    capacity signatures -> different fusion keys) never group: both
    answers stay correct and no fused launch happens."""
    dom = Domain()
    s = Session(dom)
    _mk_table(s, "t", n=4000, seed=1)
    _mk_table(s, "u", n=100, seed=2)     # different capacity bucket
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    dom.client._platform = lambda: "tpu"
    qa = "select sum(p) from t where q < 24"
    qb = "select count(*) from u where d >= 5"
    solo = [Session(dom).must_query(qa), Session(dom).must_query(qb)]
    sched = dom.client._sched_obj
    f0 = sched.fused_launches
    out = _run_concurrent(dom, sched, [qa, qb])
    assert [out[0], out[1]] == solo
    assert sched.fused_launches == f0


def test_refused_fused_launch_is_counted_and_results_unchanged(
        monkeypatch, caplog):
    """A fused launch that raises is served apart with the same answers;
    what says it happened is `fused_refused` on /sched and one warning."""
    dom, s, _data = _fusion_domain()
    queries = FUSION_QUERIES[:1] + FUSION_QUERIES[2:4]   # one fusion class
    solo = [Session(dom).must_query(q) for q in queries]
    sched = dom.client._sched_obj

    def boom(*_a, **_kw):
        raise RuntimeError("fused program refused by the backend")
    monkeypatch.setattr(spmd, "get_fused_program", boom)
    r0, f0 = sched.fused_refused, sched.fused_launches
    sched._refusals_logged.clear()       # logged once per digest, ever
    with caplog.at_level("WARNING", logger="tidb_tpu.sched.scheduler"):
        out = _run_concurrent(dom, sched, queries)
    assert [out[i] for i in range(len(queries))] == solo
    assert sched.fused_launches == f0
    assert sched.fused_refused == r0 + 1
    st = dom.client.sched_stats()
    assert st["fused_refused"] == sched.fused_refused
    assert st["batched_refused"] == sched.batched_refused
    assert any("fused launch refused" in r.getMessage()
               and "refused by the backend" in r.getMessage()
               for r in caplog.records)


def test_rows_kind_batched_launch_splits_rows_per_task():
    """Same row-returning program, DIFFERENT snapshots: the scheduler
    stacks the inputs along a batch slot dim and runs ONE vmapped rows
    launch (per-slot capacity + counts), splitting rows back per task."""
    dom = Domain()
    s = Session(dom)
    _mk_table(s, "r1", n=3000, seed=3)
    _mk_table(s, "r2", n=3000, seed=4)
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    dom.client._platform = lambda: "tpu"
    qa = "select p from r1 where d = 3"
    qb = "select p from r2 where d = 3"
    solo = [sorted(Session(dom).must_query(qa)),
            sorted(Session(dom).must_query(qb))]
    sched = dom.client._sched_obj
    r0 = sched.batched_refused
    out = _run_concurrent(dom, sched, [qa, qb])     # apart, or loaded
    assert sorted(out[0]) == solo[0] and sorted(out[1]) == solo[1]
    _land(sched)
    br0 = sched.batched_rows_launches
    out = _run_concurrent(dom, sched, [qa, qb])
    assert sorted(out[0]) == solo[0] and sorted(out[1]) == solo[1]
    assert sched.batched_rows_launches > br0
    assert sched.batched_refused == r0


def test_adaptive_window_ewma_and_clamp():
    """The micro-batch window is per-key EWMA-tuned: bursty keys earn a
    bounded hold, slow keys never delay their own launch."""
    sched = DeviceScheduler()
    lead = CopTask(fn=lambda: None)
    lead.key = ("k",)
    lead.fusion_key = ("fk",)
    # no history -> no hold
    assert sched._window_ns(lead) == 0
    # bursty arrivals 100us apart -> window ~2x gap, positive + bounded
    t0 = lead.submit_ns
    for i in range(4):
        t = CopTask(fn=lambda: None)
        t.fusion_key = ("fk",)
        t.submit_ns = t0 + i * 100_000
        sched._note_arrival(t)
    w = sched._window_ns(lead)
    assert 0 < w <= 1_000_000 * 2, w      # <= WINDOW_CAP_US * 1000 * 2
    # a long lull clamps before feeding the EWMA, and a slow key (EWMA
    # beyond the cap) disables the hold instead of stalling every launch
    slow = CopTask(fn=lambda: None)
    slow.fusion_key = ("fk",)
    slow.submit_ns = t0 + 10_000_000_000
    sched._note_arrival(slow)
    for i in range(6):
        t = CopTask(fn=lambda: None)
        t.fusion_key = ("fk",)
        t.submit_ns = slow.submit_ns + (i + 1) * 40_000_000
        sched._note_arrival(t)
    assert sched._window_ns(lead) == 0
    # fixed sysvar value overrides the EWMA entirely
    sched.configure(window_us=250)
    assert sched._window_ns(lead) == 250_000
    sched.configure(window_us=0)
    assert sched._window_ns(lead) == 0
    # opaque tasks (no key) never hold
    sched.configure(window_us=250)
    assert sched._window_ns(CopTask(fn=lambda: None)) == 0


def test_window_holds_drain_for_straggler():
    """With a fixed window, a straggler submitted shortly after the lead
    coalesces into the lead's launch instead of launching apart — no
    pause/resume needed (the open-loop bursty-arrival shape)."""
    dom, s, _data = _fusion_domain()
    s.execute("set global tidb_tpu_sched_window_us = 100000")  # 100ms
    q = FUSION_QUERIES[1]
    exp = Session(dom).must_query(q)
    sched = dom.client._sched_obj
    assert sched.window_us == 100_000
    c0, w0 = sched.coalesced_launches, sched.window_waits
    out, errors = {}, []

    def run(i):
        try:
            out[i] = Session(dom).must_query(q)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
    try:
        t1 = threading.Thread(target=run, args=(1,))
        t2 = threading.Thread(target=run, args=(2,))
        t1.start()
        time.sleep(0.02)       # straggler lands inside the 100ms window
        t2.start()
        t1.join(timeout=60)
        t2.join(timeout=60)
    finally:
        # schedulers are shared per mesh fingerprint: put the adaptive
        # window back so later tests don't pay a 100ms hold per launch
        s.execute("set global tidb_tpu_sched_window_us = -1")
        sched.configure(window_us=-1)
    assert not errors, errors
    assert out[1] == exp and out[2] == exp
    assert sched.window_waits > w0
    assert sched.coalesced_launches > c0


def test_fusion_sysvar_disables_fusion():
    dom, s, _data = _fusion_domain()
    solo = [Session(dom).must_query(q) for q in FUSION_QUERIES[:2]]
    s.execute("set global tidb_tpu_sched_fusion = 0")
    sched = dom.client._sched_obj
    f0 = sched.fused_launches
    out = _run_concurrent(dom, sched, FUSION_QUERIES[:2])
    assert [out[0], out[1]] == solo
    assert sched.fused_launches == f0
    assert sched.fusion_enable is False
    s.execute("set global tidb_tpu_sched_fusion = 1")
    Session(dom).must_query(FUSION_QUERIES[0])
    assert sched.fusion_enable is True


def test_explain_analyze_reports_fused_count():
    dom, s, _data = _fusion_domain()
    res = s.execute("explain analyze " + FUSION_QUERIES[1])
    text = "\n".join(r[0] for r in res.rows)
    assert "schedWait" in text and "fused:" in text, text


def test_sched_status_surfaces_fusion_and_client_stats():
    dom, s, _data = _fusion_domain()
    s.must_query(FUSION_QUERIES[0])
    st = dom.client.sched_stats()
    for field in ("fused_launches", "fused_tasks", "window_waits",
                  "batched_rows_launches", "wait_p50_ms", "wait_p99_ms",
                  "fusion", "window_us"):
        assert field in st, field
    # shared-client counters ride along for the status route
    assert "client" in st
    for field in ("result_cache_hits", "result_cache_misses",
                  "last_page_iters", "last_retries"):
        assert field in st["client"], field
