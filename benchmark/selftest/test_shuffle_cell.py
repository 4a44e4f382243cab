"""The cell ``tpch10x4.shuffle``: its thin classes give the accepted
classes' text and answers, its new readers on made-up ``/sched`` documents
and traces, what the cell lists, and the cell rehearsed on the CPU at a
small scale on four virtual devices with the planner's broadcast cap
lowered as far as SF10 puts ``orders`` past it."""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4").strip() \
    if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", "") else os.environ["XLA_FLAGS"]

import numpy as np      # noqa: E402
import pytest           # noqa: E402

from conftest import load_run_py    # noqa: E402
from harness.context import Run     # noqa: E402

run_py = load_run_py()
CELL = "tpch10x4.shuffle"


def _reader(name):
    return run_py.load_module("layer_metrics", name)


def _run(before, after, q3=20, q12=20, chips=4):
    classes = {c: run_py.load_module("classes", c)
               for c in ("q3_x4", "q12_x4")}
    run = Run(cell={"name": CELL, "chips": chips}, config={},
              mix={"clients": 1}, classes=classes)
    run.records = [{"class": c, "stmt": 0, "due": 0.0, "sent": 0.0,
                    "done": 0.01, "ok": True, "err": None}
                   for c, n in (("q3_x4", q3), ("q12_x4", q12))
                   for _ in range(n)]
    run.sched_before, run.sched_after = before, after
    return run


# --------------------------------------------------------------------- #
# the thin classes
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("name", ["q3", "q12"])
def test_the_thin_class_is_the_accepted_class_s_text_and_oracle(name):
    base = run_py.load_module("classes", name)
    thin = run_py.load_module("classes", f"{name}_x4")
    assert thin.NAME == f"{name}_x4" and thin.READS == base.READS
    assert (thin.POOL, thin.ORDERED) == (base.POOL, base.ORDERED)
    assert thin.JOIN_LAUNCHES == base.JOIN_LAUNCHES
    for seed in (1, 35):
        p = base.draw(np.random.default_rng(seed))
        assert thin.draw(np.random.default_rng(seed)) == p
        assert thin.sql(p) == base.sql(p)
    data = {t: run_py.load_module("tables", t).generate(
        0.01, 2147483659, list(run_py.load_module("tables", t).TYPES))
        for t in thin.READS}
    p = base.draw(np.random.default_rng(35))
    assert thin.answer(thin.prepare(data), p) \
        == base.answer(base.prepare(data), p)
    rows = {t: 10 for t in thin.READS}
    width = {t: {c: 4 for c in cols} for t, cols in thin.READS.items()}
    assert thin.bytes_read(rows, width) == base.bytes_read(rows, width)


def test_a_program_without_the_counter_is_refused(monkeypatch):
    from tidb_tpu.copr import facts
    names = tuple(n for n in facts.counter_names()
                  if n != "join_exchange_launches")
    monkeypatch.setattr(facts, "counter_names", lambda: names)
    for name in ("q3_x4", "q12_x4"):
        with pytest.raises(SystemExit, match="tpch_sf10_orders_x4"):
            run_py.load_module("classes", name)


# --------------------------------------------------------------------- #
# the readers
# --------------------------------------------------------------------- #

ZERO = {k: 0 for k in (
    "join_launches", "join_exchange_launches", "join_host_fallbacks",
    "join_regrows", "exchange_overflows", "join_compact_overflows",
    "join_window_overflows", "hndv_agg_regrows", "rows_regrows")}
ZERO["client"] = {"degraded": 0, "oom_recovered": 0}


@pytest.mark.parametrize("before,after,want", [
    (ZERO, dict(ZERO, join_launches=60, join_exchange_launches=40),
     pytest.approx(66.67, abs=0.01)),
    (dict(ZERO, join_launches=9, join_exchange_launches=9),
     dict(ZERO, join_launches=69, join_exchange_launches=49),
     pytest.approx(66.67, abs=0.01)),
    (ZERO, dict(ZERO, join_launches=60), 0.0),
    (ZERO, ZERO, None),                         # no join launched
    ({"join_launches": 0}, {"join_launches": 60}, None),    # the parent
])
def test_join_exchange_share(before, after, want):
    assert _reader("join_exchange_share").read(_run(before, after)) == want


@pytest.mark.parametrize("after,want", [
    (dict(ZERO, join_launches=60), 100.0),      # 20 x 2 + 20 x 1
    (dict(ZERO, join_launches=66), 100.0 * 60 / 66),
    (dict(ZERO, join_launches=45), 75.0),
    (dict(ZERO, join_launches=60, exchange_overflows=1), 0.0),
    (dict(ZERO, join_launches=60, join_window_overflows=1), 0.0),
    (dict(ZERO, join_launches=60, join_compact_overflows=2), 0.0),
    (dict(ZERO, join_launches=60, rows_regrows=1), 0.0),
    (dict(ZERO, join_launches=60, hndv_agg_regrows=1), 0.0),
    (dict(ZERO, join_launches=60, join_regrows=1), 0.0),
    (dict(ZERO, join_launches=60, join_host_fallbacks=1), 0.0),
    (dict(ZERO, join_launches=60,
          client={"degraded": 0, "oom_recovered": 1}), 0.0),
])
def test_shuffle_device_share(after, want):
    got = _reader("shuffle_device_share").read(_run(ZERO, after))
    assert got == pytest.approx(want)


def test_shuffle_device_share_finds_nothing_on_the_parent():
    parent = {k: 0 for k in ZERO if k != "exchange_overflows"}
    reader = _reader("shuffle_device_share")
    assert reader.read(_run(parent, dict(parent, join_launches=60))) is None
    assert reader.read(_run(ZERO, dict(ZERO, join_launches=60), 0, 0)) is None


def _traced(ops_by_device, statements):
    run = _run(ZERO, ZERO, 0, 0)
    run.records = [{"class": c, "stmt": 0, "due": a / 1e9, "sent": a / 1e9,
                    "done": b / 1e9, "ok": True, "err": None}
                   for c, a, b in statements]
    run.trace = {"devices": {n: {"ops": ops, "modules": []}
                             for n, ops in ops_by_device.items()},
                 "sync": []}
    run.trace_lo_ns, run.trace_hi_ns, run.clock_offset_ns = 0, 10_000_000, 0
    return run


def test_exchange_ms_counts_the_operations_that_move_rows():
    """all-to-all and collective-permute, not the all-reduce of a merge;
    on the chip that spent longest there; exposed: with nothing else
    running on that chip."""
    # the name a v5e gave the operation (my chip run, PR 35): after
    # `lax.all_to_all`, underscores and all
    a2a = "%all_to_all.8 = u32[4,196608,3] all-to-all(%x)"
    perm = "%collective-permute.1 = u32[8] collective-permute(%y)"
    red = "%all-reduce.3 = s32[8] all-reduce(%z)"
    fus = "%fusion.9 = s32[8] fusion(%w)"
    ops = {0: [(1_000_000, 1_400_000, a2a), (1_200_000, 1_600_000, fus),
               (2_000_000, 2_100_000, red)],
           1: [(1_000_000, 1_300_000, a2a), (1_500_000, 1_550_000, perm)]}
    run = _traced(ops, [("q12_x4", 500_000, 3_000_000)])
    assert _reader("exchange_ms").read(run) == pytest.approx(0.4)
    # chip 0: 0.2 ms of its all-to-all ran beside the fusion; chip 1's
    # 0.35 ms ran alone
    assert _reader("exchange_exposed_ms").read(run) == pytest.approx(0.35)
    one = _traced(ops, [("q12_x4", 500_000, 3_000_000)])
    one.cell["chips"] = 1
    assert _reader("exchange_ms").read(one) is None
    assert _reader("exchange_exposed_ms").read(one) is None
    none = _run(ZERO, ZERO)
    assert _reader("exchange_ms").read(none) is None
    assert _reader("exchange_exposed_ms").read(none) is None


def test_the_rooflines_read_nothing_without_a_trace():
    run = _run(ZERO, ZERO)
    for name in ("q3_x4_join_roofline", "q12_x4_join_roofline"):
        assert _reader(name).read(run) is None


# --------------------------------------------------------------------- #
# the cell
# --------------------------------------------------------------------- #

def test_the_cell_lists_what_it_reports():
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, CELL)
    assert (cell["chips"], config["chips"], config["scale"],
            mix["clients"]) == (4, 4, 10, 1)
    assert mix["mix"] == {"q3_x4": 1, "q12_x4": 1} and mix["pool_seed"] == 35
    assert (mix["loop"], mix["order"], mix["cycles"]) \
        == ("closed", "shuffled", 16)
    assert config["architecture"] is None
    base = run_py.load_json(run_py.HERE, "configs", "tpch_sf1_orders_x1.json")
    assert config["tables"] == base["tables"]
    assert config["server"] == base["server"]
    assert config["guarantees"][:-1] == base["guarantees"]
    assert "every chip's shard is read" in config["guarantees"][-1]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(config["reduced"])
    e2e = {m["name"] for m in run_py.cell_metrics(bench, "end_to_end", CELL)}
    assert e2e == {"stmt_ms_geomean", "stmt_p95_x", "peak_hbm_gb", "setup_s"}
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine >= {
        "device_ms.q3_x4", "device_ms.q12_x4", "program_ms.table_rows",
        "q3_x4_join_roofline", "q12_x4_join_roofline", "exchange_ms",
        "exchange_exposed_ms", "join_exchange_share", "shuffle_device_share"}
    wanted = {m["name"] for m in
              run_py.cell_metrics(bench, "per_layer", CELL)}
    assert mine <= wanted and {
        "program_ms.solo_join_rows", "program_ms.solo_join_agg_sort",
        "program_ms.solo_join_agg_dense", "device_idle_share"} <= wanted
    assert not {"device_ms.q3", "collective_ms",
                "orderjoin_device_share"} & wanted
    # the cell's own membership: what else the benchmark holds is not
    # this file's to pin, or no later PR could add a cell
    assert CELL in [w["name"] for w in bench["workloads"] if w["chips"] == 4]


def test_cell_rehearsed_on_the_cpu_on_four_devices(monkeypatch):
    """The whole cell at SF0.01 for three seconds on four virtual
    devices, the cap lowered so that ORDERS (15,000 rows) is past it and
    CUSTOMER (1,500) under it: every warm-up and window answer equals the
    oracle's; every statement took the join launches its class file says,
    two in three of them exchanged, nothing fell back or was rerun in the
    window."""
    import jax
    if jax.device_count() < 4:
        pytest.skip("needs four (virtual) devices: run this file alone")
    from tidb_tpu.executor import plan
    monkeypatch.setattr(plan, "BROADCAST_BUILD_MAX_ROWS", 4096)
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, CELL)
    run = run_py.run_cell(cell, config, mix, seed=2147483659, seconds=3.0,
                          trace=False, scale=0.01)
    assert run.records and all(r["ok"] for r in run.records)
    assert set(run.ms_by_class()) == {"q3_x4", "q12_x4"}
    assert run.rows["CUSTOMER"] == 1_500 and run.rows["ORDERS"] == 15_000
    got = run_py.read_metrics(run, "layer_metrics", [
        m for m in bench["per_layer"]
        if m["name"] in ("join_exchange_share", "shuffle_device_share",
                         "join_direct_share")])
    assert got["shuffle_device_share"]["value"] == 100.0
    assert got["join_exchange_share"]["value"] == pytest.approx(66.67,
                                                                abs=0.5)
    for k in ("join_host_fallbacks", "join_shuffle_launches",
              "exchange_overflows", "rows_regrows", "hndv_agg_regrows"):
        assert run.sched_delta(k) == 0
