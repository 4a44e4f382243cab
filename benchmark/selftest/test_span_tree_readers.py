"""``server_unnamed_ms`` and ``client_outside_ms``: on a made-up tree whose
arithmetic can be done by hand, on trees recorded on the chip
(``data/served_trees.json``: three ``kv_agg`` and three ``part_agg``
statements of ``tpch10x1.small`` with the client's times of the same run,
PR 33), and on the parent's trees, which have no ``wire.stmt``: nothing to
read, no error."""

import json
import os

import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
unnamed = run_py.load_module("layer_metrics", "server_unnamed_ms")
outside = run_py.load_module("layer_metrics", "client_outside_ms")
self_ms = run_py.load_module("layer_metrics", "span_self_ms")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def span(i, parent, name, start, dur):
    return {"id": i, "parent": parent, "name": name, "start_us": start,
            "duration_us": dur}


def made_up_tree(cls="kv_agg", shift=0.0):
    """wire.stmt 0..1000: its children cover all but 60; ExecuteStmt
    100..800 leaves 40; cop.dispatch 250..550 leaves 20 (its queue and
    its admit overlap on two threads: counted once); cop.transfer
    560..760 leaves 15.  135 in all, and ``shift`` more where the root
    outlasts its last child."""
    return {"class": cls, "spans": [
        span(1, None, "wire.stmt", 0.0, 1000.0 + shift),
        span(2, 1, "session.parse", 10.0, 80.0),
        span(3, 1, "session.ExecuteStmt", 100.0, 700.0),
        span(4, 3, "session.plan", 110.0, 60.0),
        span(5, 3, "session.inputs", 170.0, 80.0),
        span(6, 3, "cop.dispatch", 250.0, 300.0),
        span(7, 6, "sched.admit", 260.0, 100.0),
        span(8, 6, "sched.queue", 340.0, 60.0),
        span(9, 6, "sched.launch", 400.0, 80.0),
        span(10, 6, "sched.epilogue", 480.0, 20.0),
        span(11, 6, "sched.wake", 500.0, 40.0),
        span(12, 3, "cop.transfer", 560.0, 200.0),
        span(13, 12, "cop.d2h_issue", 565.0, 25.0),
        span(14, 12, "cop.device_wait", 590.0, 100.0),
        span(15, 12, "cop.d2h", 695.0, 60.0),
        span(16, 3, "session.resultset", 770.0, 20.0),
        span(17, 1, "session.finish", 800.0, 90.0),
        span(18, 1, "wire.write", 900.0, 70.0)]}


def _run(trees, client_ms=None):
    run = Run(cell={"chips": 1}, config={}, mix={"clients": 1}, classes={})
    run.trees = trees
    for cls, values in (client_ms or {}).items():
        run.records += [{"class": cls, "stmt": 0, "due": 0.0, "sent": 0.0,
                         "done": ms / 1e3, "ok": True, "err": None}
                        for ms in values]
    return run


def test_unnamed_is_the_containers_self_time():
    run = _run([made_up_tree()])
    assert unnamed.read(run) == pytest.approx(0.135)
    # each container's part, by the reader the entries name
    assert self_ms.read(run, "wire.stmt") == pytest.approx(0.060)
    assert self_ms.read(run, "session.ExecuteStmt") == pytest.approx(0.040)
    assert self_ms.read(run, "cop.dispatch") == pytest.approx(0.020)
    assert self_ms.read(run, "cop.transfer") == pytest.approx(0.015)
    # the new entries read their spans by name
    for name, ms in (("session.finish", 0.090), ("session.inputs", 0.080),
                     ("sched.epilogue", 0.020), ("sched.wake", 0.040),
                     ("cop.d2h_issue", 0.025)):
        assert self_ms.read(run, name) == pytest.approx(ms)


def test_median_per_class_then_geomean():
    # kv_agg: 135, 135, 335 -> 135; part_agg: 540 -> geomean 270
    trees = [made_up_tree(), made_up_tree(), made_up_tree(shift=200.0),
             made_up_tree("part_agg", shift=405.0)]
    assert unnamed.read(_run(trees)) == pytest.approx(0.270)
    # the client saw 1.5 and 2.205 ms; the server 1.0 and 1.405 inside
    run = _run(trees, {"kv_agg": [1.4, 1.5, 1.6], "part_agg": [2.205]})
    assert outside.read(run) == pytest.approx((0.5 + 0.8) / 2)


def test_nothing_to_read_on_the_parents_trees():
    """No ``wire.stmt`` (``wire.write`` a root of its own), or one that
    never ended: both readers return None and raise nothing."""
    tree = made_up_tree()
    parent = {"class": "kv_agg", "spans": [
        dict(s, parent=None if s["parent"] == 1 else s["parent"])
        for s in tree["spans"] if s["name"] != "wire.stmt"]}
    run = _run([parent], {"kv_agg": [1.5]})
    assert unnamed.read(run) is None and outside.read(run) is None
    still_open = made_up_tree()
    still_open["spans"][0]["duration_us"] = 0.0
    run = _run([still_open], {"kv_agg": [1.5]})
    assert unnamed.read(run) is None and outside.read(run) is None
    assert unnamed.read(_run([])) is None and outside.read(_run([])) is None
    # a sampled median a hair over the client's reads as nothing outside
    assert outside.read(_run([made_up_tree()], {"kv_agg": [0.99]})) == 0.0


def test_on_the_trees_recorded_on_the_chip():
    """Six trees of ``tpch10x1.small`` as ``/trace/<id>`` gave them (PR 33)
    beside the client's times: the readers' values pinned, every tree
    closed (all self-times sum to the root's duration), every new span
    there, the older readers unmoved by the new root."""
    with open(os.path.join(DATA, "served_trees.json")) as f:
        data = json.load(f)
    run = _run(data["trees"], data["client_ms"])
    assert unnamed.read(run) == pytest.approx(0.18183, abs=1e-5)
    assert outside.read(run) == pytest.approx(0.11395, abs=1e-5)
    for name, ms in (("session.finish", 0.12292), ("session.inputs", 0.24794),
                     ("sched.epilogue", 0.05472), ("sched.wake", 0.21859),
                     ("cop.d2h_issue", 0.14126), ("session.begin", 0.13901),
                     ("session.outputs", 0.23293), ("sched.task", 0.07371),
                     ("sched.pickup", 0.08004), ("wire.stmt", 0.04988)):
        assert self_ms.read(run, name) == pytest.approx(ms, abs=1e-5), name
    host_plan = run_py.load_module("layer_metrics", "host_plan_ms")
    assert host_plan.read(run) == pytest.approx(0.71540, abs=1e-5)
    for tree in data["trees"]:
        spans = tree["spans"]
        (root,) = [s for s in spans if s["parent"] is None]
        assert root["name"] == "wire.stmt"
        total = sum(self_ms.self_us(s, spans) for s in spans)
        assert total == pytest.approx(root["duration_us"], rel=2e-3)
        lo, hi = root["start_us"], root["start_us"] + root["duration_us"]
        assert all(lo - 0.2 <= s["start_us"]
                   and s["start_us"] + s["duration_us"] <= hi + 0.2
                   for s in spans)


def test_the_entries_list_every_cell():
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    layers = {"span_self_ms.session.finish": "session, planner, gates",
              "span_self_ms.session.inputs": "session, planner, gates",
              "span_self_ms.sched.epilogue": "admission scheduler",
              "span_self_ms.sched.wake": "admission scheduler",
              "span_self_ms.cop.d2h_issue": "launch and transfer",
              "span_self_ms.session.begin": "session, planner, gates",
              "span_self_ms.session.outputs": "session, planner, gates",
              "span_self_ms.sched.task": "admission scheduler",
              "span_self_ms.sched.pickup": "admission scheduler",
              "server_unnamed_ms": "session, planner, gates",
              "client_outside_ms": "wire"}
    for name, layer in layers.items():
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry == {"name": name, "unit": "ms", "better": "lower",
                         "source": "program_span", "layer": layer,
                         "moves": "stmt_ms_geomean",
                         "workloads": cells[:6]}
