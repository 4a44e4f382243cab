"""copmeter (ISSUE 10): closed-loop cost calibration + OOM-graceful
admission.

Covers the calibration invariants (corrections clamped and monotone
under synthetic drift, the RU floor never undercut, quarantined
digests' corrections purged with the manifest entry), manifest
persistence, the bounded-LRU attribution map satellite, the
TPU-CALIB-CLAMP lint rule, deadline-aware early shedding, the EXPLAIN
``cost:`` verdict, and the OOM recovery path (injected ``oom`` launch
fault recovers bit-identically WITHOUT opening the poison breaker).
"""

import time

import numpy as np
import pytest

from tidb_tpu import faults
from tidb_tpu.analysis.calibrate import (CALIB_CLAMP_MAX, CALIB_CLAMP_MIN,
                                         BoundedLRU, CorrectionStore,
                                         clamp_factor, correction_store,
                                         predict_ms)
from tidb_tpu.analysis.copcost import LaunchCost
from tidb_tpu.compilecache.manifest import WarmManifest
from tidb_tpu.faults import FaultPlan, FaultRule, MemoryFault, is_oom_error
from tidb_tpu.session import Domain, Session

COST = LaunchCost(input_bytes=1 << 20, aux_bytes=0, inter_bytes=1 << 20,
                  output_bytes=1 << 16, flops=10_000_000)


def _feed(store, digest, drift, rounds, cost=COST):
    true_ns = int(predict_ms(cost) * drift * 1e6)
    for _ in range(rounds):
        store.observe(digest, cost, true_ns)


# ------------------------------------------------------------------ #
# correction store invariants
# ------------------------------------------------------------------ #

def test_corrections_monotone_and_convergent_under_drift():
    """Constant drift inside the clamp: the time factor approaches it
    monotonically (EWMA toward a fixed point) and the tracked error
    decays under the 25% acceptance bound."""
    store = CorrectionStore()
    prev = 1.0
    for i in range(24):
        _feed(store, "d1", 3.0, 1)
        f = store.get("d1").time_factor
        assert prev - 1e-9 <= f <= 3.0 + 1e-9, (i, prev, f)
        prev = f
    ent = store.get("d1")
    assert abs(ent.time_factor - 3.0) < 0.05
    assert ent.err < 0.25


def test_corrections_hard_clamped_at_both_extremes():
    store = CorrectionStore()
    _feed(store, "hi", 1e5, 40)       # drift far past the clamp
    _feed(store, "lo", 1e-5, 40)
    assert store.get("hi").time_factor <= CALIB_CLAMP_MAX
    assert store.get("hi").time_factor > CALIB_CLAMP_MAX - 1e-3
    assert store.get("lo").time_factor >= CALIB_CLAMP_MIN
    assert store.get("lo").time_factor < CALIB_CLAMP_MIN + 1e-3
    # the oom bump clamps too: repeated bumps saturate, never explode
    for _ in range(10):
        store.observe_oom("hi")
    assert store.get("hi").mem_factor == CALIB_CLAMP_MAX
    assert clamp_factor(1e9) == CALIB_CLAMP_MAX
    assert clamp_factor(0.0) == CALIB_CLAMP_MIN


def test_corrected_cost_scales_modeled_terms_only():
    store = CorrectionStore()
    _feed(store, "d1", 2.0, 20)
    store.observe_oom("d1")
    cc = store.corrected_cost("d1", COST)
    # exact admission metadata is never corrected
    assert cc.input_bytes == COST.input_bytes
    # time factor scales the work term, mem factor the modeled bytes
    assert cc.flops > COST.flops
    assert cc.inter_bytes == int(COST.inter_bytes * 2.0)
    assert cc.peak_hbm_bytes > COST.peak_hbm_bytes
    # unknown digests pass through untouched (the static model)
    assert store.corrected_cost("nope", COST) is COST


def test_ru_floor_never_undercut_by_corrections():
    """Even with every factor pinned at the minimum clamp, pricing
    never drops below the per-task RU floor."""
    from tidb_tpu.rc.pricing import MIN_TASK_RU, cost_rus
    store = CorrectionStore()
    tiny = LaunchCost(input_bytes=64, inter_bytes=64, output_bytes=8,
                      flops=10)
    _feed(store, "t", 1e-5, 40, cost=tiny)   # factor -> CALIB_CLAMP_MIN
    corrected = store.corrected_cost("t", tiny)
    assert cost_rus(corrected) >= MIN_TASK_RU
    big = store.corrected_cost("t", COST)
    assert cost_rus(big) >= MIN_TASK_RU


def test_calibration_persists_through_manifest_and_purges(tmp_path):
    store = CorrectionStore()
    _feed(store, "aaaa000011112222", 2.5, 8)
    m = WarmManifest(str(tmp_path))
    m.save_calibration(store.entries_payload())
    # a fresh process (new manifest object off the same dir) restores
    m2 = WarmManifest(str(tmp_path))
    s2 = CorrectionStore()
    assert s2.restore(m2) == 1
    # payloads round to 4 decimals on the way to JSON
    assert abs(s2.get("aaaa000011112222").time_factor
               - store.get("aaaa000011112222").time_factor) < 1e-3
    # quarantine purge drops the persisted corrections with the entry
    m2.purge_digest("aaaa000011112222")
    m3 = WarmManifest(str(tmp_path))
    assert m3.load_calibration() == {}
    s3 = CorrectionStore()
    assert s3.restore(m3) == 0


def test_quarantine_purges_live_corrections(tmp_path):
    """compile_cache().quarantine drops the digest's live corrections
    (and the manifest twin) — no stale feedback laundering."""
    from tidb_tpu.compilecache import compile_cache, configure
    cc = compile_cache()
    old_dir, old_enable = cc.cache_dir, cc.enable
    store = correction_store()
    try:
        configure(enable=True, cache_dir=str(tmp_path))
        _feed(store, "feedbeef00000001", 2.0, 4)
        assert store.get("feedbeef00000001") is not None
        cc.quarantine("feedbeef00000001")
        assert store.get("feedbeef00000001") is None
        assert cc.manifest.load_calibration().get(
            "feedbeef00000001") is None
    finally:
        configure(enable=old_enable, cache_dir=old_dir)
        store.purge("feedbeef00000001")


# ------------------------------------------------------------------ #
# BoundedLRU (satellite: shared eviction policy)
# ------------------------------------------------------------------ #

def test_bounded_lru_caps_and_evicts_lru():
    lru = BoundedLRU(cap=4)
    for i in range(8):
        lru.bump(f"k{i}", i)
    assert len(lru) == 4
    assert "k0" not in lru and "k7" in lru
    lru.get("k4")                     # touch: k4 becomes MRU
    lru.bump("k9", 1)
    assert "k4" in lru and "k5" not in lru
    assert lru.evictions == 5


def test_scheduler_digest_map_is_bounded():
    """Satellite: the per-digest device-time attribution map no longer
    grows per digest for the life of the process."""
    from tidb_tpu.sched.scheduler import RC_DIGEST_CAP, DeviceScheduler
    sched = DeviceScheduler()
    for i in range(RC_DIGEST_CAP * 3):
        sched._digest_ns.bump(f"{i:016x}", 1_000_000)
    assert len(sched._digest_ns) <= RC_DIGEST_CAP
    # stats still renders the top-8 view off the bounded map
    top = sched.stats()["digest_dispatch_ms"]
    assert len(top) == 8


# ------------------------------------------------------------------ #
# TPU-CALIB-CLAMP lint rule (satellite)
# ------------------------------------------------------------------ #

_BAD_MULT = """
def corrected(cost, corr):
    return cost.flops * corr.time_factor
"""

_BAD_AUG = """
def bump(cost, corr):
    x = cost.inter_bytes
    x *= corr.mem_factor
    return x
"""

_GOOD = """
def corrected(cost, corr):
    tf = clamp_factor(corr.time_factor)
    return cost.flops * tf
"""


def test_calib_clamp_rule_flags_unclamped_feedback():
    from tidb_tpu.analysis.lint import lint_source
    found = lint_source(_BAD_MULT, "analysis/foo.py")
    assert any(f.rule == "TPU-CALIB-CLAMP" for f in found), found
    found = lint_source(_BAD_AUG, "sched/foo.py")
    assert any(f.rule == "TPU-CALIB-CLAMP" for f in found), found


def test_calib_clamp_rule_accepts_clamped_feedback():
    from tidb_tpu.analysis.lint import lint_source
    found = lint_source(_GOOD, "analysis/foo.py")
    assert not [f for f in found if f.rule == "TPU-CALIB-CLAMP"], found


def test_calib_clamp_repo_sweep_zero_findings():
    from tidb_tpu.analysis.lint import lint_tree
    bad = [f for f in lint_tree() if f.rule == "TPU-CALIB-CLAMP"]
    assert not bad, bad


# ------------------------------------------------------------------ #
# deadline-aware early shedding
# ------------------------------------------------------------------ #

def test_shed_at_submit_8252_and_9003():
    from tidb_tpu.rc.controller import ResourceExhaustedError, ResourceGroup
    from tidb_tpu.sched.scheduler import SHED_MAX_BACKLOG_S, DeviceScheduler
    from tidb_tpu.sched.task import CopTask, ServerBusyError
    sched = DeviceScheduler()
    sched.pause()
    sched.calibration_enable = True
    # a measured backlog the drain provably cannot clear in time
    sched._backlog_ns = int((SHED_MAX_BACKLOG_S + 5) * 1e9)
    # rc-limited waiter: backlog > its max-queue deadline -> 8252 HERE
    g = ResourceGroup("shed_t", ru_per_sec=10)
    t = CopTask(fn=lambda: None, group="shed_t", weight=1.0, rc_group=g)
    with pytest.raises(ResourceExhaustedError):
        sched.submit(t)
    assert sched.shed_rejects == 1
    # unlimited waiter: backlog > the busy ceiling -> 9003
    t2 = CopTask(fn=lambda: None)
    with pytest.raises(ServerBusyError):
        sched.submit(t2)
    assert sched.shed_rejects == 2
    assert sched.depth == 0           # nothing queued by a shed submit
    # calibration off: the static path never sheds
    sched.calibration_enable = False
    t3 = CopTask(fn=lambda: None, rc_group=g, group="shed_t", weight=1.0)
    sched.submit(t3)
    assert sched.depth == 1


# ------------------------------------------------------------------ #
# end-to-end: OOM recovery + EXPLAIN verdict (CPU mesh, pinned device
# path — the faultline fixture idiom)
# ------------------------------------------------------------------ #

OOMQ = "select sum(p), count(*) from oomt where d >= 3"


@pytest.fixture()
def odom():
    dom = Domain()
    s = Session(dom)
    rng = np.random.default_rng(2)
    n = 20_000
    d = rng.integers(0, 10, n)
    p = rng.integers(100, 10_000, n)
    s.execute("create table oomt (d bigint, p bigint)")
    step = 10_000
    for lo in range(0, n, step):
        s.execute("insert into oomt values " + ",".join(
            f"({a},{b})" for a, b in zip(d[lo:lo + step],
                                         p[lo:lo + step])))
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    dom.client._platform = lambda: "tpu"
    s.must_query("select count(*) from oomt")     # start the scheduler
    sched = dom.client._sched_obj
    assert sched is not None
    saved_sleep = sched._retry_sleep
    sched._retry_sleep = lambda sec: None
    try:
        yield dom, s, sched
    finally:
        sched._retry_sleep = saved_sleep
        sched.breaker.reset()
        faults.clear()
        correction_store().reset()


def _digest_of(dom, sched, query) -> str:
    sched._digest_ns.clear()
    Session(dom).must_query(query)
    digs = list(sched._digest_ns)
    assert len(digs) == 1, digs
    return digs[0]


def test_is_oom_error_classification():
    assert is_oom_error(MemoryFault("launch", 1))
    assert is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate"))
    assert not is_oom_error(RuntimeError("some other crash"))
    assert not is_oom_error(faults.TransientFault("launch", 1))
    # grammar: the oom kind parses with rate/match/times
    plan = FaultPlan.parse("seed=3,launch:oom:0.5:times=2")
    assert plan.rules[0].kind == "oom"
    with pytest.raises(ValueError):
        FaultPlan.parse("launch:bogus")


def test_injected_oom_recovers_bit_identical_without_breaker(odom):
    """Acceptance: an injected ``oom`` launch fault recovers — the
    waiter sees a bit-identical result via the recovery ladder — the
    poison breaker NEVER opens, and the digest's memory correction is
    bumped so future admission prices the bigger footprint."""
    dom, s, sched = odom
    solo = s.must_query(OOMQ)
    dig = _digest_of(dom, sched, OOMQ)
    store = correction_store()
    q0, o0 = sched.quarantined, sched.oom_faults
    oe0 = store.stats()["oom_events"]
    r0 = dom.client.oom_recovered
    faults.install(FaultPlan(
        [FaultRule("launch", "oom", match=dig, times=1)], seed=5))
    got = s.must_query(OOMQ)
    faults.clear()
    assert got == solo                         # bit-identical
    assert sched.oom_faults == o0 + 1
    assert sched.quarantined == q0             # no fail-fast ever
    assert dig not in (sched.stats()["breaker"] or {})
    assert dom.client.oom_recovered == r0 + 1
    assert store.stats()["oom_events"] == oe0 + 1
    ent = [e for d, e in store._entries.items() if e.oom_bumps]
    assert ent and ent[0].mem_factor > 1.0
    # and the SAME statement keeps serving normally afterwards
    assert s.must_query(OOMQ) == solo


def test_persistent_oom_degrades_to_host_oracle(odom):
    """A program that OOMs at EVERY size (rate-1.0 oom rule, so the
    streamed retry fails too) still serves correct results through the
    host oracle — and still never charges the breaker."""
    dom, s, sched = odom
    solo = s.must_query(OOMQ)
    dig = _digest_of(dom, sched, OOMQ)
    d0 = dom.client.degraded
    q0 = sched.quarantined
    faults.install(FaultPlan(
        [FaultRule("launch", "oom", match=dig)], seed=5))
    got = s.must_query(OOMQ)
    faults.clear()
    assert got == solo
    assert dom.client.degraded == d0 + 1
    assert sched.quarantined == q0
    assert dig not in (sched.stats()["breaker"] or {})


def test_explain_cost_verdict_static_then_calibrated(odom):
    """EXPLAIN surfaces the calibration verdict: ``cost: static``
    before any measurement (and whenever the sysvar is off),
    ``cost: calibrated (err N%)`` once the digest has measured
    corrections."""
    dom, s, sched = odom
    store = correction_store()
    store.reset()
    text0 = "\n".join(str(r) for r in s.must_query("explain " + OOMQ))
    assert "cost: static" in text0, text0
    # run twice: the first launch compiles (cold launches never feed
    # the loop), the second is warm and observes; observation happens
    # on the drain thread after finish, so poll briefly
    s.must_query(OOMQ)
    s.must_query(OOMQ)
    deadline = time.monotonic() + 5.0
    while store.stats()["observed"] == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert store.stats()["observed"] > 0
    text1 = "\n".join(str(r) for r in s.must_query("explain " + OOMQ))
    assert "cost: calibrated (err" in text1, text1
    # sysvar off: the static model, untouched
    s.execute("set global tidb_tpu_cost_calibration = 0")
    try:
        text2 = "\n".join(str(r) for r in
                          s.must_query("explain " + OOMQ))
        assert "cost: static" in text2, text2
        s.must_query(OOMQ)
        assert sched.calibration_enable is False
    finally:
        s.execute("set global tidb_tpu_cost_calibration = 1")
        s.must_query(OOMQ)
        assert sched.calibration_enable is True


def test_calibration_visible_on_sched_stats(odom):
    dom, s, sched = odom
    s.must_query(OOMQ)
    s.must_query(OOMQ)
    st = sched.stats()
    assert st["calibration"]["enabled"] is True
    assert "oom_faults" in st and "shed_rejects" in st
    assert "backlog_ms" in st
