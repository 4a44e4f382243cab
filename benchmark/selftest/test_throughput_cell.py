"""The cell ``tpch10x1.throughput`` (PR 48): its entries, its readers on
made-up ``/sched`` documents and span trees, and the whole cell by its
real entry at SF0.01 on the CPU.  A reader finds nothing (and does not
raise) in a program that keeps no such counter or span, as the parent of
the PR that added it does not."""

import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
CELL = "tpch10x1.throughput"
READERS = {name: run_py.load_module("layer_metrics", name) for name in (
    "stmts_per_s", "dedup_share", "fused_task_share", "group_apart_share",
    "window_hold_ms", "span_self_ms", "device_busy_ms_per_stmt")}


def _run(before=None, after=None, trees=(), clients=8):
    run = Run(cell={"chips": 1, "name": CELL}, config={},
              mix={"clients": clients}, classes={},
              sched_before=before or {}, sched_after=after or {})
    run.trees = list(trees)
    return run


# --------------------------------------------------------------------- #
# the entries
# --------------------------------------------------------------------- #

def test_the_cell_lists_what_it_reports():
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], config["chips"],
            config["scale"], config["streams"], mix["clients"]) \
        == (1, "throughput8", 1, 10, 8, 8)
    assert mix["mix"] == {"q6": 4, "q1": 2, "part_agg": 2}
    assert (mix["loop"], mix["order"], mix["cycles"], mix["pool_seed"]) \
        == ("closed", "shuffled", 16, 22)
    # the tables, columns and settings of the one-client cells: the same
    # data for a seed, the same solo programs
    base = run_py.load_json(run_py.HERE, "configs", "tpch_sf10_x1.json")
    assert config["tables"] == {t: base["tables"][t]
                                for t in ("lineitem", "part")}
    assert config["server"] == base["server"]
    assert config["architecture"] is None
    assert config["reference"] == [f"benchmark/classes/{c}.py"
                                   for c in ("q6", "q1", "part_agg")]
    assert {"queries", "refresh_stream"} <= set(config["reduced"])
    assert "streams" in config["assumed"] and config["guarantees"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(config["reduced"])
    e2e = {m["name"] for m in run_py.cell_metrics(bench, "end_to_end", CELL)}
    assert e2e == {"stmt_ms_geomean", "stmt_p95_x", "peak_hbm_gb", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) >= {
        "stmts_per_s", "dedup_share", "fused_task_share",
        "group_apart_share", "window_hold_ms", "span_self_ms.sched.queue",
        "device_busy_ms_per_stmt"}
    assert all(m["moves"] == "stmt_ms_geomean" for m in mine.values())
    wanted = {m["name"] for m in run_py.cell_metrics(bench, "per_layer", CELL)}
    # the entries with no list report here as they stand
    assert set(mine) <= wanted and {
        "wire_ms", "host_plan_ms", "sched_wait_ms", "launches_per_stmt",
        "window_compiles", "setup_compile_s", "transfer_wait_ms",
        "host_merge_ms", "device_idle_share", "setup_part_s.warmup"} <= wanted
    # device time a class is ambiguous with eight statements in flight
    assert not {m for m in wanted if m.startswith(("device_ms.", "idle_ms."))}
    for m in wanted:        # every reader is there, found by name
        run_py.load_module("layer_metrics", m.partition(".")[0])


# --------------------------------------------------------------------- #
# the counters' readers
# --------------------------------------------------------------------- #

ZERO = {"tasks_done": 0, "dedup_tasks": 0, "fused_tasks": 0,
        "fused_launches": 0, "batched_launches": 0, "fused_refused": 0,
        "batched_refused": 0, "groups_apart_unloaded": 0,
        "hold_ns_total": 0}


@pytest.mark.parametrize("reader,before,after,want", [
    # 14,000 tasks, 3,500 of them a second waiter of an execution
    ("dedup_share", ZERO, dict(ZERO, tasks_done=14000, dedup_tasks=3500),
     25.0),
    ("dedup_share", dict(ZERO, tasks_done=24), dict(ZERO, tasks_done=24),
     None),                                 # nothing served in the window
    ("dedup_share", {"tasks_done": 3}, {"tasks_done": 900}, None),  # parent
    ("fused_task_share", ZERO, dict(ZERO, tasks_done=1000, fused_tasks=120),
     12.0),
    ("fused_task_share", ZERO, dict(ZERO, tasks_done=1000), 0.0),
    ("fused_task_share", dict(ZERO, tasks_done=50, fused_tasks=10),
     dict(ZERO, tasks_done=1050, fused_tasks=10), 0.0),   # the warm-up's
    ("fused_task_share", {"launches": 3}, {"launches": 9}, None),
    # groups formed = launched in group form + refused + apart
    ("group_apart_share", ZERO,
     dict(ZERO, fused_launches=60, batched_launches=20, fused_refused=0,
          groups_apart_unloaded=20), 20.0),
    ("group_apart_share", ZERO, dict(ZERO, groups_apart_unloaded=7), 100.0),
    ("group_apart_share", ZERO, dict(ZERO, fused_launches=7), 0.0),
    ("group_apart_share", ZERO, ZERO, None),          # no group formed
    # a parent compiles the group's program where the clients wait: it
    # keeps no such counter
    ("group_apart_share", {"fused_launches": 0}, {"fused_launches": 16},
     None),
])
def test_counter_readers(reader, before, after, want):
    got = READERS[reader].read(_run(before, after))
    assert got == (want if want is None else pytest.approx(want))


def test_stmts_per_s_is_the_end_to_end_reader_under_a_per_layer_name():
    run = _run()
    run.t0, run.t_end = 100.0, 151.0
    run.records = [{"class": "q6", "stmt": 0, "due": t, "sent": t,
                    "done": t + 0.004, "ok": ok, "err": None}
                   for t, ok in ((100.5, True), (120.0, True),
                                 (150.999, True), (130.0, False))]
    # the last one ends after the window, the wrong one does not count
    assert READERS["stmts_per_s"].read(run) == pytest.approx(2 / 51.0)
    e2e = run_py.load_module("end_to_end", "stmts_per_s")
    assert e2e.read(run) == READERS["stmts_per_s"].read(run)
    assert READERS["stmts_per_s"].read(_run()) is None


# --------------------------------------------------------------------- #
# the spans' readers
# --------------------------------------------------------------------- #

def span(i, parent, name, start, dur):
    return {"id": i, "parent": parent, "name": name, "start_us": start,
            "duration_us": dur}


def tree(cls, queue_us, hold_us=None):
    spans = [span(1, None, "cop.dispatch", 0.0, 2000.0),
             span(2, 1, "sched.admit", 10.0, 300.0),
             span(3, 1, "sched.queue", 310.0, queue_us),
             span(5, 1, "sched.launch", 320.0 + queue_us, 500.0)]
    if hold_us is not None:         # the hold is the end of the queue span
        spans.append(span(4, 3, "sched.hold",
                          310.0 + queue_us - hold_us, hold_us))
    return {"class": cls, "spans": spans}


def test_the_hold_is_read_from_its_span_and_leaves_the_queues_self_time():
    held = dict(ZERO, hold_ns_total=1)
    trees = [tree("q6", 400.0, 100.0), tree("q6", 500.0, 300.0),
             tree("q6", 200.0),                      # not held: left out
             tree("q1", 900.0, 800.0), tree("part_agg", 150.0)]
    run = _run(ZERO, held, trees)
    # medians 0.2 (q6) and 0.8 (q1); part_agg was never held
    assert READERS["window_hold_ms"].read(run) \
        == pytest.approx((0.2 * 0.8) ** 0.5)
    # sched.queue's self-time is what the hold leaves of it: q6 0.3, 0.2,
    # 0.2 -> 0.2; q1 0.1; part_agg 0.15
    assert READERS["span_self_ms"].read(run, "sched.queue") \
        == pytest.approx((0.2 * 0.1 * 0.15) ** (1 / 3))
    # no statement sampled was held: 0, which is a reading
    assert READERS["window_hold_ms"].read(
        _run(ZERO, held, [tree("q6", 400.0)])) == 0.0
    # no tree (an untraced run), or a program without the span: nothing
    assert READERS["window_hold_ms"].read(_run(ZERO, held)) is None
    assert READERS["window_hold_ms"].read(
        _run({"launches": 1}, {"launches": 9}, [tree("q6", 400.0)])) is None


def test_device_busy_ms_per_stmt_counts_the_statements_wholly_inside():
    run = _run()
    assert READERS["device_busy_ms_per_stmt"].read(run) is None    # no trace
    # one device, busy 0..3 ms and 5..6 ms of a 10 ms slice
    run.trace = {"devices": {0: {"ops": [(0, 3_000_000, "%fusion"),
                                         (5_000_000, 6_000_000, "%copy")],
                                 "modules": []}}, "sync": []}
    run.trace_lo_ns, run.trace_hi_ns, run.clock_offset_ns = 0, 10_000_000, 0
    run.records = [{"class": "q6", "stmt": 0, "due": s, "sent": s,
                    "done": e, "ok": True, "err": None}
                   for s, e in ((0.001, 0.004), (0.0015, 0.0045),
                                (0.005, 0.007), (0.009, 0.012))]
    # three statements lie inside the slice, one outlasts it: 4 ms of
    # device time over 3 statements, though two of them shared a scan
    assert READERS["device_busy_ms_per_stmt"].read(run) \
        == pytest.approx(4.0 / 3)


# --------------------------------------------------------------------- #
# the cell, by its real entry, on the CPU
# --------------------------------------------------------------------- #

def test_throughput_cell_rehearsed_on_the_cpu():
    """Eight clients for three seconds at SF0.01, by the cell's real
    entry: every answer right, all three classes answered, the server
    counted every statement.  On the CPU mesh the native host engine
    answers scan-aggregate chains and the admission scheduler never
    starts, so its counters' readers find nothing there, and do not
    raise; what they read on the chip's ``/sched`` is above."""
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, CELL)
    run = run_py.run_cell(cell, config, mix, seed=2147483659, seconds=3.0,
                          trace=False, scale=0.01)
    assert run.records and all(r["ok"] for r in run.records)
    assert set(run.ms_by_class()) == {"q6", "q1", "part_agg"}
    assert len({r["stmt"] for r in run.records}) == 12      # 3 x 4 texts
    served = sum(n for n, _ in run.summary_after.values()) \
        - sum(n for n, _ in run.summary_before.values())
    assert served == len(run.records)
    # (the accepted counter readers want a started scheduler)
    listed = [m for m in run_py.cell_metrics(bench, "per_layer", CELL)
              if m["source"] in ("program_counter", "host_clock")
              and (m.get("workloads") == [CELL]
                   or m["name"].startswith("setup_part_s."))]
    got = run_py.read_metrics(run, "layer_metrics", listed)
    assert got["stmts_per_s"]["value"] == pytest.approx(
        sum(r["done"] <= run.t_end for r in run.records) / 3.0)
    assert {m["name"] for m in listed
            if m["name"].startswith("setup_part_s.")} <= set(got)
    if not run.sched_after.get("started"):
        assert not {"dedup_share", "fused_task_share",
                    "group_apart_share"} & set(got)
    e2e = run_py.read_metrics(
        run, "end_to_end",
        [m for m in run_py.cell_metrics(bench, "end_to_end", CELL)
         if m["name"] not in ("stmt_p95_x", "peak_hbm_gb")])
    assert e2e["stmt_ms_geomean"]["value"] > 0 and e2e["setup_s"]["value"] > 0
