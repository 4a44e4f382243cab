"""Device admission scheduler (sched/): continuous micro-batching of
concurrent cop tasks — in-flight dedup, batched vmap launches,
weighted-fair ordering, bounded-queue backpressure, schedWait surfacing.

The concurrency tests pin the device path open (`_platform` -> "tpu")
so the CPU host-agg engine choice doesn't bypass the launch seam, and
pause the drain loop to make queue buildup deterministic.
"""

import threading
import time

import numpy as np
import pytest

from tidb_tpu.parallel import spmd
from tidb_tpu.sched import CopTask, DeviceScheduler, ServerBusyError
from tidb_tpu.session import Domain, Session


def _wait_until(pred, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


def _mk_lineitem(s: Session, name: str = "lineitem", n: int = 4000,
                 seed: int = 0):
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 50, n)
    disc = rng.integers(0, 10, n)          # discount in percent
    price = rng.integers(100, 10_000, n)
    ship = rng.integers(0, 2000, n)        # days since 1992-01-01
    s.execute(f"create table {name} (l_quantity bigint, l_discount bigint,"
              " l_extendedprice bigint, l_shipdays bigint)")
    rows = ",".join(f"({q},{d},{p},{sd})"
                    for q, d, p, sd in zip(qty, disc, price, ship))
    s.execute(f"insert into {name} values {rows}")
    return qty, disc, price, ship


Q6 = ("select sum(l_extendedprice * l_discount) from lineitem "
      "where l_shipdays >= 730 and l_shipdays < 1095 "
      "and l_discount between 5 and 7 and l_quantity < 24")


def _q6_expected(qty, disc, price, ship):
    m = ((ship >= 730) & (ship < 1095) & (disc >= 5) & (disc <= 7)
         & (qty < 24))
    return int((price[m] * disc[m]).sum())


def test_concurrent_identical_q6_coalesces_without_recompiling():
    """8 sessions x identical Q6 over one snapshot: the in-flight tasks
    coalesce into shared launches, the sharded-program compile count
    stays at the single-session count, and every session gets the right
    answer."""
    dom = Domain()
    s = Session(dom)
    data = _mk_lineitem(s)
    exp = _q6_expected(*data)
    # keep every session dispatching: no result-cache short circuit, and
    # the device path pinned open on the CPU test mesh
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    dom.client._platform = lambda: "tpu"
    # warm-up: compiles the Q6 program once, starts the scheduler
    assert s.must_query(Q6) == [(exp,)]
    sched = dom.client._sched_obj
    assert sched is not None, "launch did not route through the scheduler"
    misses0 = spmd._cached.cache_info().misses
    coalesced0 = sched.coalesced_launches

    sched.pause()
    try:
        results, errors = [], []

        def run():
            try:
                results.append(Session(dom).must_query(Q6))
            except Exception as e:  # noqa: BLE001 surfaced via assert
                errors.append(e)
        threads = [threading.Thread(target=run) for _ in range(8)]
        for t in threads:
            t.start()
        _wait_until(lambda: sched.depth >= 8, msg="8 queued cop tasks")
    finally:
        sched.resume()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert results == [[(exp,)]] * 8
    # identical in-flight tasks shared launches...
    assert sched.coalesced_launches > coalesced0
    # ...and nobody compiled a new program
    assert spmd._cached.cache_info().misses == misses0


def test_batched_launch_splits_states_per_task():
    """Same program, DIFFERENT snapshots: the scheduler stacks the
    inputs along a batch slot dim and runs ONE vmapped launch, splitting
    the partial-agg states back per task."""
    dom = Domain()
    s = Session(dom)
    d1 = _mk_lineitem(s, "lineitem", seed=1)
    s2 = Session(dom)
    d2 = _mk_lineitem(s2, "lineitem2", seed=2)
    dom.client._platform = lambda: "tpu"
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    q2 = Q6.replace("from lineitem ", "from lineitem2 ")
    exp1, exp2 = _q6_expected(*d1), _q6_expected(*d2)
    # warm-up resolves snapshots + scheduler (separate single launches)
    assert s.must_query(Q6) == [(exp1,)]
    assert s2.must_query(q2) == [(exp2,)]
    sched = dom.client._sched_obj

    def both():
        sched.pause()
        try:
            out, errors = {}, []

            def run(sql, tag):
                try:
                    out[tag] = Session(dom).must_query(sql)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
            threads = [threading.Thread(target=run, args=(Q6, 1)),
                       threading.Thread(target=run, args=(q2, 2))]
            for t in threads:
                t.start()
            _wait_until(lambda: sched.depth >= 2, msg="2 queued cop tasks")
        finally:
            sched.resume()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert out[1] == [(exp1,)] and out[2] == [(exp2,)]

    # the drain compiles no group program: the first time the two slots
    # turn up they launch apart (unless an earlier test loaded the
    # vmapped program); the explicit warm compiles it on a background
    # thread
    both()
    sched.warm_groups()
    _wait_until(lambda: not sched._groups_pending
                and not sched._groups_inflight and not sched._warm_alive,
                timeout=120, msg="the background group compile")
    batched0, refused0 = sched.batched_launches, sched.batched_refused
    both()
    assert sched.batched_launches > batched0
    assert sched.batched_refused == refused0


def test_weighted_fair_order_across_groups():
    """Stride scheduling: a high-priority group's tasks drain ahead of a
    low-priority group's at the weight ratio (resource-group PRIORITY)."""
    sched = DeviceScheduler()
    sched.pause()
    order: list = []
    tasks = []
    for i in range(8):
        tasks.append(sched.submit(CopTask(
            fn=lambda: order.append("g"), group="gold", weight=16.0)))
    for i in range(8):
        tasks.append(sched.submit(CopTask(
            fn=lambda: order.append("l"), group="lead", weight=1.0)))
    sched.resume()
    for t in tasks:
        t.wait()
    gold_pos = [i for i, tag in enumerate(order) if tag == "g"]
    # all 16x-weight tasks land in the first 9 slots (one lead slips in
    # when its virtual time is still behind gold's first charge)
    assert max(gold_pos) <= 8, order
    st = sched.stats()
    assert st["groups"]["gold"]["tasks"] == 8
    assert st["groups"]["lead"]["tasks"] == 8
    assert st["groups"]["gold"]["rus"] > 0     # per-group RU accounting


def test_queue_overflow_raises_mysql_busy_error():
    sched = DeviceScheduler(max_depth=4)
    sched.pause()
    tasks = [sched.submit(CopTask(fn=lambda: None)) for _ in range(4)]
    with pytest.raises(ServerBusyError) as ei:
        sched.submit(CopTask(fn=lambda: None))
    assert ei.value.errno == 9003
    assert "busy" in str(ei.value)
    # the wire layer maps it to the TiDB busy error number
    from tidb_tpu.server.mysql_server import _errno_for
    assert _errno_for(ei.value) == 9003
    assert sched.busy_rejects == 1
    sched.resume()
    for t in tasks:
        t.wait()
    assert sched.stats()["queue_depth"] == 0


def test_explain_analyze_reports_sched_wait():
    dom = Domain()
    s = Session(dom)
    _mk_lineitem(s, n=500)
    dom.client._platform = lambda: "tpu"
    res = s.execute("explain analyze " + Q6)
    text = "\n".join(r[0] for r in res.rows)
    assert "schedWait" in text, text
    # ...and the statement summary aggregates the admission wait column
    rows = s.must_query("show statements_summary")
    assert any(len(r) >= 7 and r[6] is not None for r in rows)


def test_sched_knobs_and_status_surface():
    dom = Domain()
    s = Session(dom)
    _mk_lineitem(s, n=300)
    dom.client._platform = lambda: "tpu"
    s.execute("set global tidb_tpu_sched_queue_depth = 17")
    s.execute("set global tidb_tpu_sched_max_coalesce = 3")
    s.must_query(Q6)
    sched = dom.client._sched_obj
    assert sched.max_depth == 17 and sched.max_coalesce == 3
    st = dom.client.sched_stats()
    assert st["started"] and st["launches"] >= 1
    # /sched status route serves the same snapshot
    import json
    import urllib.request
    from tidb_tpu.server.status import StatusServer
    srv = StatusServer(dom)
    port = srv.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/sched", timeout=5).read()
        payload = json.loads(body)
        assert payload["launches"] >= 1
        assert "groups" in payload
    finally:
        srv.close()


def test_resource_group_priority_feeds_sched_weight():
    dom = Domain()
    s = Session(dom)
    s.execute("create resource group express RU_PER_SEC = 1000 "
              "PRIORITY = HIGH")
    g = dom.resource_groups.get("express")
    assert g.priority == "high" and g.sched_weight == 16.0
    rows = s.must_query("select name, priority from "
                        "information_schema.resource_groups "
                        "where name = 'express'")
    assert rows == [("express", "HIGH")]
    s.execute("alter resource group express PRIORITY = LOW")
    assert dom.resource_groups.get("express").sched_weight == 1.0
