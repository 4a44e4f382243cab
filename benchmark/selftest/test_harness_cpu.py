"""The harness end to end at SF0.01 on the CPU mesh, up to but not
including the result line: server over TCP, load generator in its own
process, every answer compared.  Counts and answers only: on a CPU mesh
the program's native host engine answers scan chains, so nothing here is
a device number, and ``main`` still refuses to run without a chip."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

SECONDS = 6.0


@pytest.fixture(scope="module")
def bench(run_py):
    return run_py.load_json(ROOT, "BENCHMARK.json")


def throughput8(run_py, bench):
    """The eight-client mix, which is a data file and not yet a cell
    (PERF.md section 7): run under the one-chip configuration."""
    cell, config, _ = run_py.find_cell(bench, "tpch10x1.power")
    mix = run_py.load_json(BENCH, "traffic", "throughput8.json")
    return dict(cell, name="tpch10x1.throughput", traffic="throughput8"), \
        config, mix


@pytest.mark.parametrize("workload", ["tpch10x1.power", "tpch10x1.small",
                                      "tpch10x1.throughput"])
def test_cell_runs_and_every_answer_is_right(run_py, bench, workload):
    cell, config, mix = throughput8(run_py, bench) \
        if workload == "tpch10x1.throughput" \
        else run_py.find_cell(bench, workload)
    run = run_py.run_cell(cell, config, mix, seed=3, seconds=SECONDS,
                          trace=False, scale=0.01)
    assert run.records and all(r["ok"] for r in run.records)
    assert set(run.ms_by_class()) == set(mix["mix"])
    assert run.setup_s > 0 and run.t_end - run.t0 == pytest.approx(SECONDS)
    # the window is as long as asked, whatever the statements take
    assert max(r["sent"] for r in run.records) < run.t_end
    # the ramp before it is set-up, and none of its readings is kept
    assert run.setup_parts["loadgen_ramp_s"] >= run_py.RAMP_S
    assert min(r["sent"] for r in run.records) >= run.t0
    # set-up has no unnamed part: the laps add up to it, and every
    # `setup_part_s.<part>` the benchmark lists finds its laps
    assert sum(run.setup_parts.values()) == pytest.approx(run.setup_s,
                                                          abs=2.0)
    listed = [m for m in bench["per_layer"]
              if m["name"].startswith("setup_part_s.")]
    parts = run_py.read_metrics(run, "layer_metrics", listed)
    assert listed and set(parts) == {m["name"] for m in listed}
    assert parts["setup_part_s.ramp"]["value"] >= run_py.RAMP_S
    served = sum(n for n, _ in run.summary_after.values()) \
        - sum(n for n, _ in run.summary_before.values())
    assert served == len(run.records)       # the server's own count
    e2e = run_py.read_metrics(
        run, "end_to_end",
        [m for m in run_py.cell_metrics(bench, "end_to_end", workload)
         if m["name"] not in ("stmt_p95_x", "peak_hbm_gb")])
    assert e2e["stmt_ms_geomean"]["value"] > 0
    per_s = run_py.load_module("end_to_end", "stmts_per_s").read(run)
    assert per_s == pytest.approx(
        sum(r["done"] <= run.t_end for r in run.records) / SECONDS)


def test_same_seed_same_statements(run_py, bench):
    from harness import traffic
    cell, config, mix = throughput8(run_py, bench)
    classes = {c: run_py.load_module("classes", c) for c in mix["mix"]}
    # the literals are the mix's, whatever the run's seed ...
    a, b, c = (traffic.pools(classes, dict(mix, pool_seed=s))
               for s in (mix["pool_seed"], mix["pool_seed"], 8))
    assert a == b and a != c
    assert all(len(p) == classes[k].POOL for k, p in a.items())
    # ... and the run's seed makes each client's order
    sizes = {k: len(v) for k, v in a.items()}
    s1, s2 = traffic.streams(mix, sizes, 7), traffic.streams(mix, sizes, 7)
    assert s1 == s2 and len(s1) == mix["clients"]
    assert s1 != traffic.streams(mix, sizes, 8)
    assert s1[0] != s1[1]                       # each stream its own order
    per_cycle = sum(mix["mix"].values())
    first = [c for c, _ in s1[0][:per_cycle]]
    assert sorted(first) == sorted(
        c for c, n in mix["mix"].items() for _ in range(n))


def test_open_loop_is_timed_from_when_due(run_py, bench, tmp_path):
    """The general generator's other loop, which no cell uses yet."""
    cell, config, _ = run_py.find_cell(bench, "tpch10x1.small")
    mix = {"loop": "open", "clients": 2, "rate_per_s": 8, "pool_seed": 1,
           "arrivals": "uniform", "mix": {"kv_agg": 1}, "order": "fixed",
           "cycles": 1}
    run = run_py.run_cell(cell, config, mix, seed=3, seconds=3.0,
                          trace=False, scale=0.01)
    assert all(r["ok"] for r in run.records)
    assert len(run.records) == 23               # due at k/8 s, k/8 < 3
    assert all(r["sent"] >= r["due"] for r in run.records)
    gaps = [b["due"] - a["due"] for a, b in
            zip(sorted(run.records, key=lambda r: r["due"])[:-1],
                sorted(run.records, key=lambda r: r["due"])[1:])]
    assert gaps == pytest.approx([0.125] * 22)


def test_the_command_fails_without_a_chip():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tpch10x1.small", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 2
    assert "needs 1 TPU chip" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_result_line_has_the_contract_keys(run_py, bench):
    """``result`` on made-up records: the keys the driver reads."""
    from harness.context import Run
    cell, config, mix = run_py.find_cell(bench, "tpch10x1.small")
    run = Run(cell=cell, config=config, mix=mix, classes={})
    run.t0, run.t_end, run.setup_s = 100.0, 110.0, 12.5
    run.memory_peak_bytes = 123_000_000
    run.records = [{"class": c, "stmt": c, "due": 100 + i * 0.01,
                    "sent": 100 + i * 0.01,
                    "done": 100 + i * 0.01 + 0.004, "ok": True, "err": None}
                   for i in range(150) for c in ("kv_agg", "part_agg")]
    run.records[0]["ok"], run.records[0]["err"] = None, "(9003) busy"
    sched = {"started": True, "client": {"degraded": 0, "oom_recovered": 0},
             "breaker": {}, "compile_cache": {"uncacheable": 0, "misses": 4},
             "tasks_done": 0, **{k: 0 for k in (
                 "launches", "coalesced_tasks", "batched_launches",
                 "fused_launches", "fused_tasks", "window_waits",
                 "busy_rejects", "quarantined", "bisected_launches",
                 "retried_launches", "warm_failures", "budget_rejects",
                 "fused_refused", "batched_refused", "oom_faults")}}
    run.sched_before = sched
    run.sched_after = dict(sched, tasks_done=299, launches=299)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    out = run_py.result(run, bench, False, device)
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert (out["correct"], out["attempted"], out["failed"]) == (True, 300, 1)
    assert set(out["metrics"]) == {"stmt_ms_geomean", "stmt_p95_x",
                                   "peak_hbm_gb", "setup_s"}
    assert out["metrics"]["stmt_ms_geomean"] == {
        "value": pytest.approx(4.0), "unit": "ms"}
    assert out["device"]["memory_peak_bytes"] == 123_000_000
    json.dumps(out)
    # a wrong answer, or a counter that says the device did not do the
    # work, makes the run incorrect
    run.sched_after = dict(run.sched_after, quarantined=1)
    assert run_py.result(run, bench, False, device)["correct"] is False
