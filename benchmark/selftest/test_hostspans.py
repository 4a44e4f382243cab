"""The program's spans in the profiler's trace (``harness/hostspans.py``)
and the readers built on them (``idle_ms``, ``launch_latency_ms``,
``ready_latency_ms``, ``launch_to_ready_ms``, ``program_ms``,
``span_self_ms``): first on made-up
spans, where the right answer is plain, then pinned on a slice recorded on
the chip, so that every later PR splits idle time and finds programs the
same way.  The slice is one second of the traced window of one
``tpch10x1.small`` run on one TPU v5 lite (PR 23, chip call 1), cut to the
lines the readers use; ``small_slice.json`` holds the harness's sync marks,
the slice's bounds and the load generator's statement intervals."""

import json
import os

import pytest

from conftest import ROOT
from harness import hostspans, xplane
from harness.context import Run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6


def span(name, start_ms, end_ms, line=0, trace_id="t", program=None):
    return {"name": name, "start": start_ms * MS, "end": end_ms * MS,
            "line": line, "trace_id": trace_id, "program": program}


def module(name, start_ms, end_ms):
    return (start_ms * MS, end_ms * MS, f"jit_{name}(123)")


# one statement, 0..10 ms: the root on the statement thread with its
# children, the launch on the drain thread, and the device busy 4..6 ms
STATEMENT = [
    span("session.ExecuteStmt", 0, 9),
    span("session.plan", 0.5, 1),
    span("cop.dispatch", 2, 4.5),
    span("sched.admit", 2, 2.5),
    span("sched.launch", 3, 3.8, line=1, program="cop_solo_agg_scalar_ab"),
    span("cop.transfer", 4.5, 7),
    span("cop.device_wait", 4.5, 6.5),
    span("cop.d2h", 6.5, 7),
    span("cop.host_merge", 7, 8),
    span("session.resultset", 8.5, 9),
    span("wire.write", 9.5, 10),
]
TRACE = {"devices": {0: {
    "ops": [(4 * MS, 6 * MS, "%fusion = ...")],
    "modules": [module("cop_solo_agg_scalar_ab", 4, 6)]}}}


def test_phase_of_a_span():
    assert [hostspans.phase(n) for n in (
        "wire.write", "session.ExecuteStmt", "session.parse", "plan.gates",
        "cop.dispatch", "sched.admit", "sched.launch", "sched.compile",
        "cop.transfer", "cop.device_wait", "cop.d2h", "cop.host_merge",
        "session.resultset")] == [
        "wire", "session", "session", "session", "sched", "sched", "sched",
        "sched", "transfer", "transfer", "transfer", "merge", "merge"]


def test_idle_goes_to_the_innermost_span_in_flight():
    idle = hostspans.idle_by_phase(TRACE, STATEMENT, -1 * MS, 11 * MS)
    # before the root and after it, and in the write: wire (1 + 2 ms);
    # root alone or planning: session (2 + 0.5 ms); dispatch, admit and the
    # drain's launch: sched (2 ms, the device starts at 4); waiting and
    # copying: transfer (0.5 + 0.5 busy ends at 6; 6..7); merge 1 + 0.5
    assert {p: v / MS for p, v in idle.items()} == pytest.approx({
        "wire": 1 + 2, "session": 2 + 0.5, "sched": 2.0,
        "transfer": 1.0, "merge": 1.5})
    busy = xplane.busy(TRACE, -1 * MS, 11 * MS)
    assert sum(idle.values()) / 1e9 == pytest.approx(
        busy["idle_share"] * busy["window_s"])


def test_a_program_without_spans_reads_nothing():
    assert hostspans.idle_by_phase(TRACE, [], 0, 10 * MS) == {}
    assert hostspans.launches(TRACE, [], 0, 10 * MS) == []
    assert hostspans.idle_by_phase({"devices": {}}, STATEMENT, 0, 1) == {}


def test_launches_pair_with_the_nearest_execution_of_their_program():
    spans = [span("sched.launch", t, t + 0.2, line=1, trace_id=f"s{k}",
                  program="cop_solo_agg_scalar_ab")
             for k, t in enumerate((1.0, 3.0, 5.0, 7.0))]
    spans.append(span("sched.launch", 2.0, 2.2, line=1, trace_id="other",
                      program="cop_solo_topn_cd"))
    spans.append(span("cop.device_wait", 1.2, 1.9, trace_id="s0"))
    devs = {d: {"ops": [], "modules": [
        # the first execution belongs to a launch before the slice
        module("cop_solo_agg_scalar_ab", 0.2, 0.4),
        module("cop_solo_agg_scalar_ab", 1.3 + d / 10, 1.5 + d / 10),
        module("cop_solo_topn_cd", 2.5, 2.9),
        module("cop_solo_agg_scalar_ab", 3.4, 3.6 + d / 10),
        module("cop_solo_agg_scalar_ab", 5.3, 5.5),
        # the device's clock behind the host's: it starts "before" its
        # launch, and is still that launch's execution
        module("cop_solo_agg_scalar_ab", 6.4, 6.6)]} for d in (0, 1)}
    got = hostspans.launches({"devices": devs}, spans, 0.5 * MS, 8 * MS)
    by_id = {x["trace_id"]: x for x in got}
    assert set(by_id) == {"s0", "s1", "s2", "s3", "other"}
    # first device to start, last device to finish
    assert by_id["s0"]["start"] == pytest.approx(1.3 * MS)
    assert by_id["s0"]["end"] == pytest.approx(1.6 * MS)
    assert by_id["s0"]["ready"] == pytest.approx(1.9 * MS)
    assert by_id["s1"]["end"] == pytest.approx(3.7 * MS)
    assert by_id["s1"]["ready"] is None         # no wait recorded
    assert by_id["other"]["start"] == pytest.approx(2.5 * MS)
    assert [round((x["start"] - x["launch"]) / MS, 3) for x in got
            if x["program"].endswith("_ab")] == [0.3, 0.4, 0.3, -0.6]
    assert hostspans.module_ms({"devices": devs}, "jit_cop_solo_agg_scalar_",
                               0.5 * MS, 6 * MS) == pytest.approx(
        [0.2, 0.3, 0.2])                # device 1, which spent longest


def reader(run_py, name):
    return run_py.load_module("layer_metrics", name)


def made_up_run(run_py):
    bench = run_py.load_json(ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, "tpch10x1.small")
    run = Run(cell=cell, config=config, mix=mix, classes={})
    run.trace, run.trace_lo_ns, run.trace_hi_ns = TRACE, -1 * MS, 11 * MS
    run.records = [{"class": "kv_agg", "stmt": 0, "due": 0.0, "sent": 0.0,
                    "done": 0.010, "ok": True, "err": None}]
    run.__dict__["_hostspans"] = STATEMENT
    return run


def test_readers_on_one_made_up_statement(run_py):
    run = made_up_run(run_py)
    idle = {p: reader(run_py, "idle_ms").read(run, p)
            for p in hostspans.PHASES}
    assert idle == pytest.approx({"wire": 3.0, "session": 2.5, "sched": 2.0,
                                  "transfer": 1.0, "merge": 1.5})
    assert reader(run_py, "launch_latency_ms").read(run) == pytest.approx(1.0)
    assert reader(run_py, "ready_latency_ms").read(run) == pytest.approx(0.5)
    # their sum, both ends on the host's clock: 6.5 - 3 less 2 ms of device
    assert reader(run_py, "launch_to_ready_ms").read(run) == pytest.approx(1.5)
    assert reader(run_py, "program_ms").read(
        run, "solo_agg_scalar") == pytest.approx(2.0)
    assert reader(run_py, "program_ms").read(run, "solo_topn") is None
    # the parent commit's program: no annotation, nothing to read, no error
    run.__dict__["_hostspans"] = []
    run.__dict__.pop("_idle_by_phase")
    assert reader(run_py, "idle_ms").read(run, "wire") is None
    assert reader(run_py, "launch_latency_ms").read(run) is None
    assert reader(run_py, "ready_latency_ms").read(run) is None
    assert reader(run_py, "launch_to_ready_ms").read(run) is None


def test_span_self_time_is_what_no_child_covers(run_py):
    run = made_up_run(run_py)
    tree = {"class": "kv_agg", "spans": [
        {"id": 1, "parent": None, "name": "session.ExecuteStmt",
         "start_us": 0.0, "duration_us": 900.0},
        {"id": 2, "parent": 1, "name": "session.parse",
         "start_us": -300.0, "duration_us": 200.0},
        {"id": 3, "parent": 1, "name": "session.plan",
         "start_us": 50.0, "duration_us": 400.0},
        {"id": 4, "parent": 3, "name": "plan.gates",
         "start_us": 300.0, "duration_us": 100.0},
        {"id": 5, "parent": 1, "name": "cop.transfer",
         "start_us": 500.0, "duration_us": 300.0},
        {"id": 6, "parent": 5, "name": "cop.device_wait",
         "start_us": 510.0, "duration_us": 200.0},
        {"id": 7, "parent": None, "name": "wire.write",
         "start_us": 950.0, "duration_us": 120.0}]}
    run.trees = [tree, dict(tree, **{"class": "part_agg"})]
    read = reader(run_py, "span_self_ms").read
    assert read(run, "session.plan") == pytest.approx(0.3)
    assert read(run, "plan.gates") == pytest.approx(0.1)
    assert read(run, "session.parse") == pytest.approx(0.2)
    assert read(run, "cop.device_wait") == pytest.approx(0.2)
    assert read(run, "wire.write") == pytest.approx(0.12)
    assert read(run, "cop.d2h") is None         # no such span: left out


# --------------------------------------------------------------------- #
# pinned on the slice recorded on the chip
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small_slice.json")) as f:
        meta = json.load(f)
    path = os.path.join(DATA, "small_slice.xplane.pb.gz")
    trace, spans = xplane.read(path), hostspans.load(path)
    off = xplane.clock_offset_ns(trace["sync"], meta["marks"])
    statements = [(c, s * 1e9 + off, e * 1e9 + off)
                  for c, s, e in meta["statements"]]
    return trace, spans, statements, meta["lo"] + off, meta["hi"] + off


def test_recorded_spans_and_program_names(recorded):
    trace, spans, _, lo, hi = recorded
    count: dict = {}
    for s in spans:
        count[s["name"]] = count.get(s["name"], 0) + 1
    assert count == {
        "session.ExecuteStmt": 241, "cop.transfer": 241, "cop.d2h": 241,
        "cop.host_merge": 240, "session.resultset": 240, "wire.write": 240,
        "session.parse": 240, "session.plan": 240, "cop.dispatch": 240,
        "sched.admit": 240, "sched.launch": 240, "cop.device_wait": 240}
    # every span but the parse carries its statement's trace id, and a
    # statement's spans share it
    assert all(s["trace_id"] for s in spans if s["name"] != "session.parse")
    launches = [s for s in spans if s["name"] == "sched.launch"]
    one = [s for s in spans if s["trace_id"] == launches[100]["trace_id"]]
    assert sorted(s["name"] for s in one) == sorted(
        set(count) - {"session.parse"})
    # the drain thread's launches are on a line of their own and name
    # their programs; no module is an anonymous jit__device_fn
    assert {s["line"] for s in launches}.isdisjoint(
        s["line"] for s in spans if s["name"] == "cop.dispatch")
    modules = {m[2].split("(")[0] for m in trace["devices"][0]["modules"]}
    assert {"jit_" + s["program"] for s in launches} == modules
    assert sorted(m.rsplit("_", 1)[0] for m in modules) == \
        ["jit_cop_solo_agg_dense"] * 4 + ["jit_cop_solo_agg_scalar"] * 4


def test_recorded_idle_by_host_phase(recorded):
    trace, spans, _, lo, hi = recorded
    idle = hostspans.idle_by_phase(trace, spans, lo, hi)
    assert {p: v / MS for p, v in idle.items()} == pytest.approx({
        "wire": 248.208483, "session": 190.723853, "sched": 294.712641,
        "transfer": 223.631643, "merge": 26.277762}, abs=1e-5)
    busy = xplane.busy(trace, lo, hi)
    assert busy["idle_share"] == pytest.approx(0.983554382, abs=1e-8)
    # the five parts are the device's idle time, all of it
    assert sum(idle.values()) / 1e9 == pytest.approx(
        busy["idle_share"] * busy["window_s"], rel=1e-6)


def test_recorded_launches_and_programs(recorded):
    trace, spans, statements, lo, hi = recorded
    got = hostspans.launches(trace, spans, lo, hi)
    assert len(got) == 218 and all(x["ready"] for x in got)
    from statistics import median
    # the device's clock ran 0.6 ms behind the host's in this trace: the
    # 7 us programs "start" before the annotation that dispatches them
    assert median((x["start"] - x["launch"]) / MS for x in got) == \
        pytest.approx(-0.6370965, abs=1e-6)
    assert median((x["ready"] - x["end"]) / MS for x in got) == \
        pytest.approx(1.7539325, abs=1e-6)
    assert median((x["ready"] - x["launch"] - (x["end"] - x["start"])) / MS
                  for x in got) == pytest.approx(1.0891935, abs=1e-6)
    scalar = hostspans.module_ms(trace, "jit_cop_solo_agg_scalar_", lo, hi)
    dense = hostspans.module_ms(trace, "jit_cop_solo_agg_dense_", lo, hi)
    assert (len(scalar), len(dense)) == (108, 110)
    assert median(scalar) == pytest.approx(0.00655, abs=1e-6)
    assert median(dense) == pytest.approx(0.1468205, abs=1e-6)
    assert hostspans.module_ms(trace, "jit_cop_solo_topn_", lo, hi) == []
    # by program name and by which statement was in flight: the same
    # executions (a module event is a few microseconds longer than the
    # union of its operations)
    per = xplane.per_statement(trace, statements, lo, hi)
    assert (len(per["kv_agg"]), len(per["part_agg"])) == (108, 109)
    assert median(per["part_agg"]) == pytest.approx(0.146521, abs=1e-6)


def test_a_traced_rehearsal_finds_its_own_spans(run_py):
    """The path from a run to its trace file (``trace_meta.json``), on the
    CPU mesh at SF0.01: the host engine answers, so the statement threads'
    spans are there and no device plane is; counts only."""
    bench = run_py.load_json(ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, "tpch10x1.small")
    run = run_py.run_cell(cell, config, mix, seed=3, seconds=6.0,
                          trace=True, scale=0.01)
    names = {s["name"] for s in hostspans.of(run)}
    assert {"session.parse", "session.ExecuteStmt", "session.plan",
            "session.resultset", "wire.write"} <= names
    assert run.trees and run.trace["devices"] == {}
    for span_name in ("session.parse", "session.plan", "wire.write"):
        assert reader(run_py, "span_self_ms").read(run, span_name) > 0
    assert reader(run_py, "idle_ms").read(run, "wire") is None
    assert reader(run_py, "launch_to_ready_ms").read(run) is None
