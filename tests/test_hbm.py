"""copgauge (obs/hbm, ISSUE 14): the live HBM ledger, measured memory
watermarks feeding continuous mem_factor calibration, the /hbm +
/profile routes, the
TPU-MEM-SOURCE lint rule, and the prometheus label-escaping satellite.

Device-path tests pin `_platform` -> "tpu" (the tests/test_copcost.py
discipline) so the CPU engine choice cannot bypass the scheduler, and
zero the result cache so every statement really launches.
"""

import gc
import json
import time
import urllib.request

import pytest

from tidb_tpu.analysis.calibrate import (CALIB_CLAMP_MAX,
                                         CALIB_CLAMP_MIN,
                                         CorrectionStore,
                                         correction_store)
from tidb_tpu.analysis.copcost import COST_TOLERANCE, LaunchCost
from tidb_tpu.obs.hbm import HbmLedger, ledger_for, profiler_gate
from tidb_tpu.session import Domain, Session


def _device_session(monkeypatch, rows=4000, name="t"):
    dom = Domain()
    s = Session(dom)
    s.execute(f"create table {name} (a bigint, b bigint)")
    s.execute(f"insert into {name} values " + ",".join(
        f"({i % 13}, {i})" for i in range(rows)))
    monkeypatch.setattr(type(dom.client), "_platform",
                        lambda self: "tpu")
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    return dom, s


def _drain_idle(sched, timeout=5.0):
    """Wait until the drain finished post-launch bookkeeping."""
    led = sched._ledger_obj
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if sched.depth == 0 and (led is None
                                 or led.inflight_bytes == 0):
            return
        time.sleep(0.01)


# ------------------------------------------------------------------ #
# unit: ledger accounting
# ------------------------------------------------------------------ #

def test_ledger_resident_register_unregister_via_weakref():
    led = HbmLedger("fp-test")

    class Token:
        pass

    t1 = Token()
    led.add_resident(t1, 1000)
    led.add_resident(t1, 1000)           # same live object: no double
    assert led.persistent_bytes == 1000
    t2 = Token()
    led.add_resident(t2, 500)
    assert led.persistent_bytes == 1500
    del t1
    gc.collect()
    assert led.persistent_bytes == 500   # death callback debited
    assert led.unregistered == 1
    assert led.negative_events == 0
    assert led.residents() == [(500, True)]


def test_ledger_launch_scoped_bytes_conserve():
    led = HbmLedger("fp-test2")
    led.launch_begin(4096)
    assert led.inflight_bytes == 4096
    assert led.watermark_bytes >= 4096
    led.launch_end(4096)
    assert led.inflight_bytes == 0
    # drift can never wedge the account: clamped + counted
    led.launch_end(1)
    assert led.inflight_bytes == 0
    assert led.negative_events == 1


def test_ledger_watermark_dominates_measured_peaks():
    led = HbmLedger("fp-test3")
    for n in (100, 900, 300):
        led.note_measured(n)
    assert led.max_measured_bytes == 900
    assert led.watermark_bytes >= led.max_measured_bytes
    assert led.last_measured_bytes == 300
    assert led.measured_launches == 3


# ------------------------------------------------------------------ #
# unit: continuous mem_factor calibration (the drift acceptance)
# ------------------------------------------------------------------ #

def test_observe_mem_converges_monotonically_within_clamp():
    """Seeded inflated/deflated measured peaks drive mem_factor
    monotonically to each clamp edge — never past it."""
    store = CorrectionStore()
    cost = LaunchCost(input_bytes=1 << 20, inter_bytes=2 << 20,
                      output_bytes=1 << 20)
    digest = "gauge/drift"
    prev = 1.0
    for _ in range(60):                       # inflated: rise to max
        store.observe_mem(digest, cost, measured_bytes=256 << 20)
        f = store.get(digest).mem_factor
        assert prev - 1e-12 <= f <= CALIB_CLAMP_MAX
        prev = f
    assert prev == pytest.approx(CALIB_CLAMP_MAX, rel=1e-3)
    for _ in range(120):                      # deflated: fall to min
        store.observe_mem(digest, cost, measured_bytes=1)
        f = store.get(digest).mem_factor
        assert CALIB_CLAMP_MIN <= f <= prev + 1e-12
        prev = f
    assert prev == pytest.approx(CALIB_CLAMP_MIN, rel=1e-3)
    ent = store.get(digest)
    assert ent.mem_samples == 180
    assert store.mem_observed == 180


def test_observe_mem_target_solves_modeled_terms():
    """The EWMA target solves exact + f*modeled == measured: exact
    resident-input bytes are never corrected (the copcost pin)."""
    store = CorrectionStore()
    cost = LaunchCost(input_bytes=10_000, inter_bytes=4_000,
                      output_bytes=1_000)
    # measured == exact + 2x modeled -> target factor 2.0
    measured = 10_000 + 2 * 5_000
    for _ in range(200):
        store.observe_mem("gauge/solve", cost, measured)
    assert store.get("gauge/solve").mem_factor == pytest.approx(2.0,
                                                                rel=1e-3)
    corrected = store.corrected_cost("gauge/solve", cost)
    assert corrected.input_bytes == cost.input_bytes
    assert corrected.peak_hbm_bytes == pytest.approx(measured, rel=0.01)
    assert store.get("gauge/solve").mem_err < 0.05


def test_corrected_cost_flips_admission_decision_both_ways():
    """The budget comparison provably changes from measured evidence:
    a budget between the deflated and inflated corrected peaks admits
    under one factor and rejects under the other."""
    store = CorrectionStore()
    cost = LaunchCost(input_bytes=1 << 20, inter_bytes=4 << 20,
                      output_bytes=1 << 20)
    budget = cost.peak_hbm_bytes * 2
    assert cost.peak_hbm_bytes <= budget            # static: admit
    for _ in range(80):
        store.observe_mem("gauge/flip", cost, measured_bytes=256 << 20)
    hi = store.corrected_cost("gauge/flip", cost).peak_hbm_bytes
    assert hi > budget                              # inflated: reject
    for _ in range(200):
        store.observe_mem("gauge/flip", cost, measured_bytes=1)
    lo = store.corrected_cost("gauge/flip", cost).peak_hbm_bytes
    assert lo <= budget                             # deflated: admit


# ------------------------------------------------------------------ #
# integration: the live pipeline on the 8-vdev mesh
# ------------------------------------------------------------------ #

def test_ledger_accuracy_resident_bytes_exact(monkeypatch):
    """Acceptance: ledger resident bytes equal live device buffer
    nbytes EXACTLY after a query drains (the copcost validation
    discipline, as a conservation delta against the shared ledger)."""
    from tidb_tpu.sched.task import mesh_fingerprint
    dom, s = _device_session(monkeypatch, rows=4000, name="tacc")
    mesh = dom.client.mesh
    led = ledger_for(mesh_fingerprint(mesh))
    registered0 = led.registered
    assert s.must_query("select sum(b) from tacc where a > 3")
    sched = dom.client._sched_obj
    assert sched is not None
    _drain_idle(sched)
    snap = dom.catalog.get_table(s.db, "tacc").snapshot()
    cols, counts = snap.device_cols(mesh)    # cached resident arrays
    expected = sum(
        int(v.nbytes) + (int(m.nbytes) if m is not None else 0)
        for v, m in cols) + int(counts.nbytes)
    # the query registered THIS table's residents with EXACTLY the live
    # device buffer nbytes (the ledger is process-shared across tests,
    # so assert on the entry, not a global delta another test's dying
    # snapshot could skew mid-test)
    assert led.registered > registered0
    live = [n for n, alive in led.residents() if alive]
    assert expected in live, (expected, live)
    # internal conservation: the account equals its live entries
    assert led.persistent_bytes == sum(n for n, a in led.residents()
                                       if a)
    assert led.inflight_bytes == 0
    assert led.negative_events == 0


def test_ledger_falls_when_snapshot_dropped():
    """Satellite regression: dropping a registered resident debits the
    ledger (weakref death = unregister) and the swept registry never
    reports the dead entry — exercised through the REAL registration
    seam (lifetime.register_resident with bytes + fingerprint, exactly
    what ColumnarSnapshot.device_cols calls) over live device arrays."""
    import jax
    import numpy as np

    from tidb_tpu.analysis import lifetime
    counts = jax.device_put(np.arange(64, dtype=np.int64))
    led = ledger_for("fp-drop-test")
    assert led.persistent_bytes == 0
    lifetime.register_resident(counts, nbytes=8192,
                               fingerprint="fp-drop-test")
    assert led.persistent_bytes == 8192
    assert lifetime.is_resident(counts)
    live_before = len(lifetime.residents())
    assert live_before >= 1
    del counts
    gc.collect()
    deadline = time.monotonic() + 5.0
    while led.persistent_bytes > 0 and time.monotonic() < deadline:
        gc.collect()
        time.sleep(0.05)
    assert led.persistent_bytes == 0          # the ledger fell
    assert led.unregistered == 1
    assert led.negative_events == 0
    # sweep-on-registration: residents() never returns a dead entry
    assert len(lifetime.residents()) < live_before
    assert all(a is not None for a in lifetime.residents())


def test_measured_watermark_within_tolerance_of_memory_analysis(
        monkeypatch):
    """Acceptance: the drain's measured launch peak (compiled memory
    analysis of the actually-served executable) stays within the
    pinned COST_TOLERANCE of an independently lowered twin."""
    dom, s = _device_session(monkeypatch, rows=4000, name="twm")
    assert s.must_query("select sum(b) from twm where a > 3")
    sched = dom.client._sched_obj
    _drain_idle(sched)
    led = sched._ledger_obj
    assert led is not None
    measured = led.last_measured_bytes
    if measured <= 0:
        pytest.skip("backend reports no compiled memory analysis")
    from tidb_tpu.copr import dag as D
    from tidb_tpu.parallel.spmd import get_sharded_program
    snap = dom.catalog.get_table(s.db, "twm").snapshot()
    mesh = dom.client.mesh
    cols, counts = snap.device_cols(mesh)
    # rebuild the same dag the session launched via the plan path
    built, phys = s._plan_select(_parse_select(
        "select sum(b) from twm where a > 3"))
    cop = _find_op(phys, "CopTaskExec")
    assert cop is not None and isinstance(cop.dag, D.Aggregation)
    ma = get_sharded_program(cop.dag, mesh)._fn.lower(
        tuple(cols), counts, ()).compile().memory_analysis()
    n_dev = int(mesh.devices.size)
    twin = n_dev * (int(ma.argument_size_in_bytes)
                    + int(ma.output_size_in_bytes)
                    + int(ma.temp_size_in_bytes))
    assert twin / COST_TOLERANCE <= measured <= twin * COST_TOLERANCE
    assert led.watermark_bytes >= measured


def _parse_select(sql):
    from tidb_tpu.sql.parser import parse_one
    return parse_one(sql)


def _find_op(op, name):
    if type(op).__name__ == name:
        return op
    for c in getattr(op, "children", []) or []:
        r = _find_op(c, name) if c is not None else None
        if r is not None:
            return r
    return None


def test_launch_span_carries_hbm_attrs_and_flip_end_to_end(monkeypatch):
    """Acceptance: launch spans carry hbm_predicted/hbm_measured, and
    a budget between the deflated and inflated corrected peaks flips a
    REAL submit's admission decision both ways."""
    from tidb_tpu.analysis.copcost import CostError
    from tidb_tpu.planner.build import PlanError
    dom, s = _device_session(monkeypatch, rows=4000, name="tflip")
    s.execute("set global tidb_tpu_trace_sample = 1")
    q = "select sum(b) from tflip where a > 5"
    store = correction_store()
    store.reset()
    try:
        assert s.must_query(q)
        _drain_idle(dom.client._sched_obj)

        def launch_span():
            for ent in dom.flight_recorder.index():
                tree = dom.flight_recorder.get(ent["trace_id"])
                for sp in tree.spans:
                    if sp.name == "sched.launch" and \
                            "hbm_predicted" in sp.attrs:
                        return sp
            return None

        sp = launch_span()
        assert sp is not None, "no launch span carried hbm attrs"
        assert sp.attrs["hbm_predicted"] > 0
        assert sp.attrs["hbm_measured"] > 0
        # the one digest the fresh store observed is the query's
        digests = [d for d, p in store.entries_payload().items()
                   if p.get("mem_samples", 0) > 0]
        assert len(digests) == 1, digests
        digest = digests[0]
        p1 = sp.attrs["hbm_predicted"]
        # inflate the measured watermark: the corrected peak grows
        ent = store.get(digest)
        static = _static_cost_of(dom, s, q)
        for _ in range(80):
            store.observe_mem(digest, static, measured_bytes=p1 * 64)
        assert store.get(digest).mem_factor > ent.mem_factor
        # budget between static and inflated corrected peak:
        # admit -> reject pinned
        s.execute(f"set global tidb_tpu_sched_hbm_budget = {p1 * 2}")
        with pytest.raises(PlanError) as ei:
            s.must_query(q)
        assert isinstance(ei.value, CostError)
        assert ei.value.rule == "hbm-budget"
        # deflate back: reject -> admit pinned, same budget
        for _ in range(300):
            store.observe_mem(digest, static, measured_bytes=1)
        assert s.must_query(q)
    finally:
        s.execute("set global tidb_tpu_sched_hbm_budget = -1")
        s.execute("set global tidb_tpu_trace_sample = 16")
        store.reset()


def _static_cost_of(dom, s, sql):
    """The admission-time static LaunchCost of the single cop task a
    statement launches (task_cost over the resident arrays)."""
    from tidb_tpu.analysis.copcost import dag_cost, Layout
    from tidb_tpu.analysis.copcost import (snapshot_input_bytes,
                                           snapshot_layout,
                                           snapshot_scan_widths)
    built, phys = s._plan_select(_parse_select(sql))
    cop = _find_op(phys, "CopTaskExec")
    snap = cop.table.snapshot()
    n_dev = int(dom.client.mesh.devices.size)
    layout = snapshot_layout(snap, n_dev)
    widths = snapshot_scan_widths(snap)
    return dag_cost(cop.dag, layout, widths,
                    input_bytes=snapshot_input_bytes(
                        snap, layout, widths))


def test_ledger_off_is_byte_identical_static_model(monkeypatch):
    """Acceptance: with tidb_tpu_hbm_ledger=0 nothing feeds the memory
    loop — no measured watermarks, no mem_factor motion, no hbm
    EXPLAIN detail; the static model behaves exactly as before
    copgauge (mem_factor moves only on OOM)."""
    dom, s = _device_session(monkeypatch, rows=3000, name="toff")
    store = correction_store()
    store.reset()
    sched0 = dom.client._scheduler()
    led_launches0 = sched0._ledger_obj.launches \
        if sched0 is not None and sched0._ledger_obj is not None else 0
    mem_observed0 = store.mem_observed    # lifetime counter survives
                                          # reset(); assert the delta
    s.execute("set global tidb_tpu_hbm_ledger = 0")
    try:
        q = "select sum(b) from toff where a > 4"
        assert s.must_query(q)
        assert s.must_query(q)
        sched = dom.client._sched_obj
        _drain_idle(sched)
        assert sched.hbm_enable is False
        # the (process-shared) ledger saw no traffic from these launches
        led = sched._ledger_obj
        if led is not None:
            assert led.launches == led_launches0
        assert store.mem_observed == mem_observed0
        for _d, p in store.entries_payload().items():
            assert p["mem_factor"] == 1.0
            assert p["mem_samples"] == 0
        rows = s.must_query("explain analyze " + q)
        assert not any("hbm:" in str(r) for r in rows)
    finally:
        s.execute("set global tidb_tpu_hbm_ledger = 1")
        store.reset()


def test_explain_analyze_reports_hbm_detail(monkeypatch):
    dom, s = _device_session(monkeypatch, rows=3000, name="texp")
    rows = s.must_query(
        "explain analyze select sum(b) from texp where a > 4")
    joined = "\n".join(str(r) for r in rows)
    assert "hbm:" in joined and "measured" in joined \
        and "predicted" in joined


def test_hbm_and_profile_routes(monkeypatch):
    """/hbm serves the ledger payload; /profile is gated by
    the sysvar and refuses while a capture is active."""
    from tidb_tpu.server.status import StatusServer
    dom, s = _device_session(monkeypatch, rows=3000, name="troute")
    assert s.must_query("select sum(b) from troute where a > 2")
    _drain_idle(dom.client._sched_obj)
    srv = StatusServer(dom)
    port = srv.start()
    try:
        out = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/hbm").read())
        assert out["enabled"] is True
        assert out["budget_bytes"] >= 0
        assert out["resident_bytes"] > 0
        assert out["watermark_bytes"] >= out["resident_bytes"] \
            or out["watermark_bytes"] > 0
        assert "roofline" not in out and "calibration" in out
        assert isinstance(out["ledgers"], list) and out["ledgers"]
        # /profile: sysvar-gated
        ref = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/profile?ms=50").read())
        assert "refused" in ref and "tidb_tpu_profile" in ref["refused"]
        s.execute("set global tidb_tpu_profile = 1")
        one = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/profile?ms=400").read())
        if one.get("started"):
            # a second capture while one is active is refused
            two = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/profile?ms=400").read())
            assert "refused" in two
            deadline = time.monotonic() + 5.0
            while profiler_gate().stats()["active"] and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert not profiler_gate().stats()["active"]
        else:
            assert "refused" in one       # profiler-less build: typed
    finally:
        s.execute("set global tidb_tpu_profile = 0")
        srv.close()


def test_hbm_gauges_in_prometheus_text(monkeypatch):
    from tidb_tpu.utils.metrics import global_registry
    dom, s = _device_session(monkeypatch, rows=3000, name="tgauge")
    assert s.must_query("select sum(b) from tgauge where a > 1")
    assert s.must_query("select sum(b) from tgauge where a > 1")
    _drain_idle(dom.client._sched_obj)
    text = global_registry().prometheus_text()
    assert "tidb_tpu_hbm_resident_bytes" in text
    assert "tidb_tpu_hbm_watermark_bytes" in text
    assert "tidb_tpu_hbm_budget_bytes" in text
    # the per-digest roofline gauges went with obs/roofline.py (PR 33)
    assert "tidb_tpu_roofline" not in text


# ------------------------------------------------------------------ #
# satellite: prometheus label-value escaping
# ------------------------------------------------------------------ #

def test_prometheus_label_values_escaped():
    from tidb_tpu.utils.metrics import Registry, escape_label
    assert escape_label('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    reg = Registry()
    c = reg.counter("esc_total", "t", labels=("digest",))
    c.inc(digest='we"ird\\label\nx')
    h = reg.histogram("esc_ms", "t", buckets=(1, 10),
                      labels=("strategy",))
    h.observe(2.0, strategy='s"1\\')
    text = reg.prometheus_text()
    assert 'digest="we\\"ird\\\\label\\nx"' in text
    assert 'strategy="s\\"1\\\\"' in text
    # no raw quote/backslash/newline survives inside a label value
    for line in text.splitlines():
        if "esc_" not in line or "{" not in line:
            continue
        body = line[line.index("{") + 1:line.rindex("}")]
        assert "\n" not in body
        i = 0
        while i < len(body):
            if body[i] == "\\":
                assert body[i + 1] in '\\"n'
                i += 2
                continue
            i += 1


# ------------------------------------------------------------------ #
# satellite: TPU-MEM-SOURCE lint rule
# ------------------------------------------------------------------ #

def test_lint_mem_source_flags_stray_calls():
    from tidb_tpu.analysis.lint import lint_source
    src = ("def probe(dev):\n"
           "    return dev.memory_stats()\n")
    rules = [f.rule for f in lint_source(src, "sched/scheduler.py")]
    assert "TPU-MEM-SOURCE" in rules
    src2 = ("def probe(exe):\n"
            "    return exe.memory_analysis()\n")
    rules2 = [f.rule for f in lint_source(src2, "analysis/copcost.py")]
    assert "TPU-MEM-SOURCE" in rules2


def test_lint_mem_source_allows_ledger_and_compilecache():
    from tidb_tpu.analysis.lint import lint_source
    src = ("def probe(dev):\n"
           "    return dev.memory_stats()\n")
    assert not [f for f in lint_source(src, "obs/hbm.py")
                if f.rule == "TPU-MEM-SOURCE"]
    src2 = ("def probe(exe):\n"
            "    return exe.memory_analysis()\n")
    assert not [f for f in lint_source(src2, "compilecache/cache.py")
                if f.rule == "TPU-MEM-SOURCE"]


def test_lint_mem_source_repo_sweep_clean():
    """Zero findings over the live tree: every memory poll routes
    through obs/hbm.py or the compile cache seam."""
    from tidb_tpu.analysis.lint import lint_tree
    assert not [f for f in lint_tree() if f.rule == "TPU-MEM-SOURCE"]
