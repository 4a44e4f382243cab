"""Resource control plane (rc/): LaunchCost-priced RU admission, group
isolation at the device drain, bounded overdraft, max-queue deadline,
runaway actions (KILL/COOLDOWN/SWITCH_GROUP), and surfacing (/resource,
EXPLAIN ANALYZE `ru:`, Avg_ru, tidb_tpu_rc_* metrics).

Like tests/test_sched.py, concurrency tests pin the device path open
(`_platform` -> "tpu") so the CPU host-agg engine choice doesn't bypass
the launch seam; the scheduler is process-wide per mesh, so tests
assert on DELTAS and restore every knob they touch.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from tidb_tpu.rc import (ResourceExhaustedError, TokenBucket, cost_rus,
                         task_rus)
from tidb_tpu.rc.pricing import MIN_TASK_RU, split_device_time
from tidb_tpu.session import Domain, Session


def _wait_until(pred, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


def _mk_table(s: Session, name: str = "t", n: int = 3000, seed: int = 0):
    rng = np.random.default_rng(seed)
    a = rng.integers(1, 50, n)
    b = rng.integers(0, 10, n)
    s.execute(f"create table {name} (a bigint, b bigint)")
    s.execute(f"insert into {name} values "
              + ",".join(f"({x},{y})" for x, y in zip(a, b)))
    return a, b


def _device_domain(n: int = 3000):
    """Domain with the launch seam pinned open + result cache off."""
    dom = Domain()
    s = Session(dom)
    data = _mk_table(s, n=n)
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    dom.client._platform = lambda: "tpu"
    return dom, s, data


Q = "select sum(a*b) from t where b < 7"


def _expected(a, b):
    m = b < 7
    return int((a[m] * b[m]).sum())


# ------------------------------------------------------------------ #
# pricing + bucket units
# ------------------------------------------------------------------ #

def test_pricing_floor_monotonic_and_marginal():
    from tidb_tpu.analysis.copcost import LaunchCost
    tiny = LaunchCost(input_bytes=8, output_bytes=8)
    assert cost_rus(tiny) == MIN_TASK_RU
    big = LaunchCost(input_bytes=512 << 20, inter_bytes=64 << 20,
                     output_bytes=1 << 20, flops=10**9)
    bigger = LaunchCost(input_bytes=1 << 30, inter_bytes=64 << 20,
                        output_bytes=1 << 20, flops=10**9)
    assert MIN_TASK_RU < cost_rus(big) < cost_rus(bigger)
    # a rider sharing the resident scan pays only its marginal bytes
    assert cost_rus(big, shared_scan=True) < cost_rus(big)
    # floor survives the marginal discount
    assert cost_rus(tiny, shared_scan=True) == MIN_TASK_RU


def test_task_rus_opaque_fallback_and_shared_scan():
    from tidb_tpu.sched import CopTask
    op = CopTask(fn=lambda: None, est_rows=500)
    assert task_rus(op) == pytest.approx(6.0)   # 500/100 + 1
    from tidb_tpu.analysis.copcost import LaunchCost
    lead = CopTask(fn=None, key=("k",))
    lead.cost = LaunchCost(input_bytes=256 << 20, output_bytes=1 << 20)
    lead.input_token = (1, 2, 3)
    rider = CopTask(fn=None, key=("k",))
    rider.cost = lead.cost
    rider.input_token = (1, 2, 3)
    assert task_rus(rider, lead) < task_rus(rider)


def test_bucket_refill_burst_overdraft():
    b = TokenBucket(100, burstable=False)
    assert b.can_cover(100) and not b.can_cover(101)
    assert b.can_cover(120, overdraft=50)      # bounded debt admits
    b.debit(150)
    assert b.debt > 0 and not b.can_cover(1)
    assert b.can_cover(1, overdraft=100)
    b.credit(1000)                              # clamped to burst cap
    assert 0 < b.balance <= 100
    # burstable banks 10x
    bb = TokenBucket(100, burstable=True)
    assert bb.can_cover(1000) and not bb.can_cover(1001)
    # unlimited always covers
    assert TokenBucket(0).can_cover(1e12)


def test_split_device_time_by_marginal_bytes():
    # lead carries the shared scan (weight 100), riders marginal 10/30
    parts = split_device_time([100, 10, 30], 14_000)
    assert sum(parts) == 14_000
    assert parts[0] > parts[2] > parts[1] > 0
    # unknown weights split evenly, still exact
    parts = split_device_time([0, 0], 999)
    assert sum(parts) == 999 and min(parts) > 0


# ------------------------------------------------------------------ #
# admission-time enforcement (acceptance criterion)
# ------------------------------------------------------------------ #

def test_rc_isolation_identical_query_held_at_drain():
    """With rc enabled and a group's bucket exhausted, its structured
    task HOLDS at the drain — zero launches served for that group, and
    it may not hitch as a rider either — while a sibling group's
    IDENTICAL query completes.  Crediting the bucket releases the held
    waiter (held, not dead)."""
    dom, s, data = _device_domain()
    exp = _expected(*data)
    assert s.must_query(Q) == [(exp,)]          # warm + engage scheduler
    sched = dom.client._sched_obj
    assert sched is not None
    s.execute("create resource group starved RU_PER_SEC = 1")
    s.execute("create resource group sibling RU_PER_SEC = 0")
    g = dom.resource_groups.get("starved")
    g.bucket.force_debit(1e9)                   # exhausted for the test
    saved = sched.rc_max_queue_s
    sched.rc_max_queue_s = 60.0                 # no deadline interference
    out = {}

    def run(grp, tag):
        sess = Session(dom)
        sess.execute(f"set resource group {grp}")
        try:
            out[tag] = ("ok", sess.must_query(Q))
        except Exception as e:  # noqa: BLE001 surfaced via assert
            out[tag] = (type(e).__name__, str(e))

    t_starved = threading.Thread(target=run, args=("starved", "s"))
    t_free = threading.Thread(target=run, args=("sibling", "f"))
    try:
        t_starved.start()
        _wait_until(lambda: (sched.stats()["groups"].get("starved") or
                             {}).get("queued", 0) >= 1,
                    msg="starved task queued")
        served0 = sched.stats()["groups"]["starved"]["tasks"]
        t_free.start()
        t_free.join(timeout=60)
        assert out["f"] == ("ok", [(exp,)])     # sibling sailed through
        st = sched.stats()["groups"]["starved"]
        assert st["queued"] >= 1, st            # still held at the drain
        assert st["tasks"] == served0 == 0, st  # zero launches served
        assert st["throttled"] > 0, st          # drain skipped the group
    finally:
        g.bucket.credit(2e9)                    # release the waiter
        t_starved.join(timeout=60)
        sched.rc_max_queue_s = saved
    assert out["s"] == ("ok", [(exp,)])
    assert sched.stats()["groups"]["starved"]["tasks"] >= 1


def test_rc_exhausted_group_never_traced_and_deadline(monkeypatch):
    """Satellite: two sessions in an RU-exhausted group + one session
    in an unlimited group submitting simultaneously.  The unlimited
    group's launches proceed; the exhausted group's tasks stay queued —
    get_sharded_program is monkeypatched to FAIL on touch for their
    dags — and the deadline path raises the MySQL-compatible
    resource-exhausted error with `throttled` visible on /resource."""
    import tidb_tpu.parallel.spmd as spmd
    from tidb_tpu.copr.dag import dag_digest
    from tidb_tpu.server.status import StatusServer

    dom, s, data = _device_domain()
    # distinct query shapes so the starved dag is its own program
    q_starved = "select min(a) from t where b = 3"
    q_free = "select max(a) from t where b = 4"
    a, b = data
    exp_free = int(a[b == 4].max())
    assert s.must_query(q_free) is not None     # warm + engage
    sched = dom.client._sched_obj
    s.execute("create resource group starved2 RU_PER_SEC = 1")
    s.execute("create resource group free2 RU_PER_SEC = 0")
    dom.resource_groups.get("starved2").bucket.force_debit(1e9)
    saved = sched.rc_max_queue_s
    monkeypatch.setattr(sched, "rc_max_queue_s", 0.5)

    forbidden = set()
    orig_submit = sched.submit

    def submit_spy(task):
        if task.group == "starved2" and task.dag is not None:
            forbidden.add(dag_digest(task.dag))
        return orig_submit(task)

    monkeypatch.setattr(sched, "submit", submit_spy)
    real_get = spmd.get_sharded_program

    def guarded(dag, mesh, row_capacity=0, donate=False):
        assert dag_digest(dag) not in forbidden, \
            "RU-exhausted group's dag reached trace/compile"
        return real_get(dag, mesh, row_capacity, donate)

    monkeypatch.setattr(spmd, "get_sharded_program", guarded)

    results, errors = [], []

    def run(grp, sql, sink):
        sess = Session(dom)
        sess.execute(f"set resource group {grp}")
        try:
            sink.append(sess.must_query(sql))
        except Exception as e:  # noqa: BLE001 surfaced via assert
            errors.append(e)

    threads = [threading.Thread(target=run,
                                args=("starved2", q_starved, results)),
               threading.Thread(target=run,
                                args=("starved2", q_starved, results)),
               threading.Thread(target=run,
                                args=("free2", q_free, results))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sched.rc_max_queue_s = saved
    assert [(exp_free,)] in results             # unlimited group ran
    assert len(errors) == 2, (results, errors)  # both starved waiters
    for e in errors:
        assert isinstance(e, ResourceExhaustedError), e
        assert e.errno == 8252
        assert "quota" in str(e)
    # the wire layer maps the typed errno
    from tidb_tpu.server.mysql_server import _errno_for
    assert _errno_for(errors[0]) == 8252
    srv = StatusServer(dom)
    port = srv.start()
    try:
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/resource", timeout=5).read())
    finally:
        srv.close()
    assert body["groups"]["starved2"]["throttled"] > 0, body
    assert body["rc_exhausted"] >= 2
    assert body["groups"]["starved2"]["debt"] > 0


def test_rc_disable_reverts_to_postpaid(monkeypatch):
    """tidb_tpu_rc_enable = 0: an exhausted group's device query is NOT
    held at the drain (legacy post-paid accounting)."""
    dom, s, data = _device_domain(n=800)
    exp = _expected(*data)
    assert s.must_query(Q) == [(exp,)]
    sched = dom.client._sched_obj
    s.execute("create resource group nolimit_off RU_PER_SEC = 1")
    dom.resource_groups.get("nolimit_off").bucket.force_debit(1e9)
    s.execute("set global tidb_tpu_rc_enable = 0")
    try:
        sess = Session(dom)
        sess.execute("set resource group nolimit_off")
        assert sess.must_query(Q) == [(exp,)]   # launches immediately
        assert sched.rc_enable is False
    finally:
        s.execute("set global tidb_tpu_rc_enable = 1")
        s.must_query("select count(*) from t")  # re-plumb the knob
        assert sched.rc_enable is True


def test_rc_overdraft_sysvar_plumbs():
    dom, s, _data = _device_domain(n=400)
    s.execute("set global tidb_tpu_rc_overdraft_ru = 500")
    s.must_query("select count(*) from t")
    sched = dom.client._sched_obj
    try:
        assert sched.rc_overdraft_ru == 500.0
        from tidb_tpu.utils.metrics import global_registry
        m = global_registry().metrics["tidb_tpu_rc_overdraft_ru"]
        assert m.get() == 500.0
    finally:
        from tidb_tpu.rc.controller import DEFAULT_OVERDRAFT_RU
        sched.rc_overdraft_ru = DEFAULT_OVERDRAFT_RU


# ------------------------------------------------------------------ #
# runaway actions
# ------------------------------------------------------------------ #

def test_runaway_switch_group_reprices():
    dom = Domain()
    s = Session(dom)
    _mk_table(s, n=400)
    s.execute("create resource group batch RU_PER_SEC = 1000")
    s.execute("create resource group hot RU_PER_SEC = 1000 "
              "QUERY_LIMIT = (EXEC_ELAPSED = '1ms' "
              "ACTION = SWITCH_GROUP(batch))")
    s.execute("set resource group hot")
    batch = dom.resource_groups.get("batch")
    debited0 = batch.bucket.debited
    assert s.must_query("select count(*) from t where a > 1") is not None
    assert dom.resource_groups.get("hot").runaway_count >= 1
    assert batch.bucket.debited > debited0      # statement paid there
    recs = dom.resource_groups.runaway_ring.records()
    assert recs and recs[-1]["action"] == "switch_group"
    assert recs[-1]["target"] == "batch"
    assert recs[-1]["group"] == "hot"
    # infoschema surfaces the armed target
    rows = s.must_query("select runaway_action from "
                        "information_schema.resource_groups "
                        "where name = 'hot'")
    assert rows == [("SWITCH_GROUP(batch)",)]


def test_runaway_switch_group_requires_existing_target():
    from tidb_tpu.planner.build import PlanError
    s = Session(Domain())
    with pytest.raises(PlanError):
        s.execute("create resource group bad RU_PER_SEC = 1 "
                  "QUERY_LIMIT = (EXEC_ELAPSED = '1s' "
                  "ACTION = SWITCH_GROUP(nope))")
    # dropping an armed target disarms the watcher to cooldown
    s.execute("create resource group tgt RU_PER_SEC = 1")
    s.execute("create resource group watcher RU_PER_SEC = 1 "
              "QUERY_LIMIT = (EXEC_ELAPSED = '1s' "
              "ACTION = SWITCH_GROUP(tgt))")
    s.execute("drop resource group tgt")
    g = s.domain.resource_groups.get("watcher")
    assert g.runaway_action == "cooldown" and g.switch_target == ""


def test_runaway_cooldown_records_and_double_charges():
    dom = Domain()
    s = Session(dom)
    _mk_table(s, n=400)
    s.execute("create resource group cd2 RU_PER_SEC = 100000 "
              "QUERY_LIMIT = (EXEC_ELAPSED = '1ms' ACTION = COOLDOWN)")
    s.execute("set resource group cd2")
    g = dom.resource_groups.get("cd2")
    d0 = g.bucket.debited
    assert s.must_query("select count(*) from t") == [(400,)]
    # cooldown demotion: the statement paid double the base charge
    # (host path: 1 result row -> 1.01 RU, doubled)
    assert g.bucket.debited - d0 == pytest.approx(
        2 * (1 / 100.0 + 1.0), abs=1e-6)
    recs = dom.resource_groups.runaway_ring.records()
    assert recs[-1]["action"] == "cooldown"
    assert recs[-1]["elapsed_s"] > 0


def test_runaway_kill_still_raises():
    """The pre-rc KILL semantics survive the move to rc/ (back-compat
    import path included)."""
    from tidb_tpu.utils.resourcegroup import RunawayError
    dom = Domain()
    s = Session(dom)
    _mk_table(s, n=300)
    s.execute("create resource group tight2 RU_PER_SEC = 0 "
              "QUERY_LIMIT = (EXEC_ELAPSED = '1ms' ACTION = KILL)")
    s.execute("set resource group tight2")
    with pytest.raises(RunawayError) as ei:
        s.must_query("select count(*) from t where a > 1")
    assert ei.value.errno == 8253
    assert dom.resource_groups.runaway_ring.records()[-1]["action"] \
        == "kill"


# ------------------------------------------------------------------ #
# surfacing + accounting honesty
# ------------------------------------------------------------------ #

def test_explain_analyze_and_summary_report_ru():
    dom, s, _data = _device_domain(n=600)
    res = s.execute("explain analyze " + Q)
    text = "\n".join(r[0] for r in res.rows)
    assert "schedWait" in text and "ru:" in text, text
    rows = s.must_query("show statements_summary")
    hdr_rows = s.execute("show statements_summary")
    assert hdr_rows.names[-1] == "Avg_ru"
    # index by name: copscope (ISSUE 13) inserted Sum_sched_tasks /
    # Sum_fused between Avg_compile_ms and Avg_ru
    i_ru = hdr_rows.names.index("Avg_ru")
    assert any(len(r) > i_ru and r[i_ru] and r[i_ru] >= 1.0
               for r in rows), rows
    rows = s.must_query(
        "select avg_ru from information_schema.statements_summary "
        "where digest_text like '%sum(a%'")
    assert rows and rows[0][0] >= 1.0


def test_priced_ru_replaces_estrows_keeps_counter_name():
    """Satellite: the est_rows/100+1 drain charge is retired; the
    tidb_tpu_sched_ru_total counter name and the per-group `rus` stat
    survive for /sched consumers, now carrying PRICED values."""
    from tidb_tpu.utils.metrics import global_registry
    dom, s, _data = _device_domain(n=600)
    reg = global_registry()
    c = reg.counter("tidb_tpu_sched_ru_total", "", labels=("group",))
    before = c.get(group="default")
    s.must_query(Q)
    sched = dom.client._sched_obj
    assert sched is not None
    st = sched.stats()
    assert c.get(group="default") > before
    assert st["groups"]["default"]["rus"] > 0
    assert st["rc_enable"] is True
    # priced from LaunchCost: the serving task carried a cost model
    # value, not the retired row formula (floor still applies)
    assert st["groups"]["default"]["rus"] >= MIN_TASK_RU


def test_device_time_attribution_per_group_and_digest():
    """Fused-launch attribution satellite: measured launch wall time
    lands on the groups whose members rode the launch (split by
    marginal bytes) and on the per-program-digest map — not wholesale
    on whichever group drained the batch."""
    dom, s, data = _device_domain()
    exp = _expected(*data)
    s.execute("create resource group ga RU_PER_SEC = 0 PRIORITY = HIGH")
    s.execute("create resource group gb RU_PER_SEC = 0 PRIORITY = LOW")
    assert s.must_query(Q) == [(exp,)]
    q2 = "select count(*) from t where b < 7"
    exp2 = int((data[1] < 7).sum())
    assert s.must_query(q2) == [(exp2,)]
    sched = dom.client._sched_obj
    sched.pause()
    out, errors = {}, []

    def run(grp, sql, tag):
        sess = Session(dom)
        sess.execute(f"set resource group {grp}")
        try:
            out[tag] = sess.must_query(sql)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=run, args=("ga", Q, "a")),
               threading.Thread(target=run, args=("gb", q2, "b"))]
    try:
        for t in threads:
            t.start()
        _wait_until(lambda: sched.depth >= 2, msg="2 queued cop tasks")
    finally:
        sched.resume()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert out["a"] == [(exp,)] and out["b"] == [(exp2,)]
    st = sched.stats()
    for grp in ("ga", "gb"):
        assert st["groups"][grp]["device_ms"] > 0, st["groups"][grp]
        assert st["groups"][grp]["rus"] >= MIN_TASK_RU
    assert st["digest_dispatch_ms"], st


def test_resource_route_lists_groups_and_balances():
    dom, s, _data = _device_domain(n=400)
    s.execute("create resource group viewme RU_PER_SEC = 777")
    s.must_query("select count(*) from t")
    from tidb_tpu.server.status import StatusServer
    srv = StatusServer(dom)
    port = srv.start()
    try:
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/resource", timeout=5).read())
    finally:
        srv.close()
    assert body["groups"]["viewme"]["ru_per_sec"] == 777
    assert body["groups"]["viewme"]["balance"] > 0
    assert "runaway" in body and "rc_overdraft_ru" in body
    # prometheus rc metrics exist on /metrics
    from tidb_tpu.utils.metrics import global_registry
    text = global_registry().prometheus_text()
    assert "tidb_tpu_rc_ru_debited_total" in text
    assert "tidb_tpu_rc_overdraft_ru" in text


def test_switch_group_parse_errors():
    from tidb_tpu.sql.parser import ParseError, parse_sql
    with pytest.raises(ParseError):
        parse_sql("create resource group x QUERY_LIMIT = "
                  "(EXEC_ELAPSED = '1s' ACTION = SWITCH_GROUP)")
    stmt = parse_sql("create resource group x QUERY_LIMIT = "
                     "(EXEC_ELAPSED = '1s' ACTION = "
                     "SWITCH_GROUP(other))")[0]
    assert stmt.action == "switch_group"
    assert stmt.switch_target == "other"
