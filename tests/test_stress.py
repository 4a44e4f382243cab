"""Chaos stress harness (ISSUE 10): the tier-1 64-session smoke and
the slow/bench-only ~1k-session rung.

The smoke proves the whole vertical on every CI run: 64 open-loop
sessions over the mixed corpus (dense/SORT at low and high NDV/rows/shuffle),
4 resource groups, PR 8 chaos armed — completion 1.0 and ZERO wrong
results, with the copmeter metrics (p50/p99 wait, fusion rate, RU
fairness, calibrated-pricing error) present as first-class fields.
The full rung is @slow + bench-only (BENCH_MODE=sched ``stress``)."""

import pytest

from tidb_tpu.analysis.calibrate import correction_store
from tidb_tpu.testing.stress import (STRESS_QUERIES, build_stress_domain,
                                     run_stress_harness)


def _run(n_sessions, n_rows, rate=400.0):
    dom, _s = build_stress_domain(n_rows=n_rows)
    sched = dom.client._scheduler()
    assert sched is not None
    saved_sleep = sched._retry_sleep
    sched._retry_sleep = lambda sec: None     # fast transient retries
    try:
        return run_stress_harness(dom, n_sessions=n_sessions,
                                  rate_per_s=rate)
    finally:
        sched._retry_sleep = saved_sleep
        sched.breaker.reset()
        correction_store().reset()


def test_stress_smoke_64_sessions_completion_and_zero_wrong():
    out = _run(n_sessions=64, n_rows=30_000)
    assert out["completion_rate"] == 1.0, out
    assert out["wrong_results"] == 0, out
    assert out["failed"] == 0, out
    # every corpus shape was exercised and completed
    tags = {tag for tag, _sql in STRESS_QUERIES}
    assert set(out["per_shape"]) == tags, out["per_shape"]
    for tag, v in out["per_shape"].items():
        assert v["ok"] == v["submitted"], (tag, v)
    # the copmeter metrics land as first-class fields
    assert out["sched_wait_p99_ms"] >= out["sched_wait_p50_ms"] >= 0
    assert 0.0 <= out["fusion_rate"] <= 1.0
    assert out["ru_fairness"] == 1.0          # all groups fully served
    assert out["calibration_entries"] > 0
    assert out["calibration_observed"] >= 0
    assert out["launches"] <= out["tasks"]
    # copnum watermark check ran at every sched admit: the declared
    # ANALYZE intervals contain everything the harness actually scanned
    assert out["value_drifts"] == 0, out


@pytest.mark.slow
def test_stress_full_1k_sessions():
    """The full ~1k-session rung (bench ``stress`` twin): ZERO wrong
    results is absolute; completion holds near 1.0 through the
    busy-retry ladder even though arrivals overrun the bounded queue
    (the residual slack absorbs CI-host timing jitter — a session that
    exhausts its whole retry budget is overload, not wrongness)."""
    out = _run(n_sessions=1000, n_rows=60_000, rate=200.0)
    assert out["wrong_results"] == 0, out
    assert out["completion_rate"] >= 0.98, out
    assert out["ru_fairness"] is not None and out["ru_fairness"] < 1.5


def test_stress_ledger_conserves_under_concurrency():
    """copgauge invariant (ISSUE 14 satellite): run the mixed-corpus
    smoke with the HBM ledger armed and assert it CONSERVES — launch
    bytes drain back out (no in-flight residue, no negative balances),
    residency returns to its post-warm baseline after a second wave,
    and the watermark dominates every per-launch measured peak."""
    import time

    dom, _s = build_stress_domain(n_rows=20_000)
    sched = dom.client._scheduler()
    assert sched is not None and sched.hbm_enable
    saved_sleep = sched._retry_sleep
    sched._retry_sleep = lambda sec: None
    try:
        out = _harness_out = run_stress_harness(dom, n_sessions=32,
                                                rate_per_s=400.0)
        assert out["wrong_results"] == 0, out
        led = sched._ledger_obj
        assert led is not None, "ledger never engaged"
        deadline = time.monotonic() + 10.0
        while led.inflight_bytes and time.monotonic() < deadline:
            time.sleep(0.02)
        assert led.inflight_bytes == 0            # drained launch bytes
        assert led.negative_events == 0           # no negative balances
        baseline = led.persistent_bytes           # post-warm residency
        assert baseline > 0
        out2 = run_stress_harness(dom, n_sessions=16, rate_per_s=400.0)
        assert out2["wrong_results"] == 0, out2
        deadline = time.monotonic() + 10.0
        while led.inflight_bytes and time.monotonic() < deadline:
            time.sleep(0.02)
        # conservation: the second wave adds NO residency — the same
        # snapshot residents serve it, launch bytes all returned
        assert led.inflight_bytes == 0
        assert led.persistent_bytes == baseline
        assert led.negative_events == 0
        # the watermark dominates every measured launch peak
        assert led.watermark_bytes >= led.max_measured_bytes
        assert led.watermark_bytes >= led.persistent_bytes
        assert led.measured_launches > 0
        del _harness_out
    finally:
        sched._retry_sleep = saved_sleep
        sched.breaker.reset()
        correction_store().reset()
