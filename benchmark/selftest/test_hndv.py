"""The high-NDV GROUP BY cell ``tpch1x1.hndv``: its two oracles against a
second computation in plain Python integers and ``Decimal``, its readers
on made-up ``/sched`` documents, and the cell rehearsed on the CPU."""

import datetime
from decimal import Decimal

import numpy as np
import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
EPOCH = datetime.date(1970, 1, 1)


@pytest.fixture(scope="module")
def small():
    """3,000 LINEITEM rows over 40 parts, a few parts left with no row,
    and ties in both rankings (parts 1 and 2 get the same rows)."""
    table = run_py.load_module("tables", "LINEITEM")
    li = table.generate(3000 / 6_000_000, 7, [
        "l_partkey", "l_quantity", "l_extendedprice", "l_discount",
        "l_shipdate"])
    li["l_partkey"] = np.random.default_rng(3).integers(3, 41, 3000)
    li["l_partkey"][li["l_partkey"] % 9 == 0] = 4
    for c in li:
        li[c] = np.concatenate([li[c], li[c][:50], li[c][:50]])
    li["l_partkey"][-100:-50], li["l_partkey"][-50:] = 1, 2
    return {"LINEITEM": li}


def _lines(li) -> list[dict]:
    return [dict(zip(li, row)) for row in zip(*(v.tolist()
                                                for v in li.values()))]


def test_hndv_qty_oracle_against_a_dictionary(small):
    cls = run_py.load_module("classes", "hndv_qty")
    assert cls.sql({}) == (
        "select l_partkey, sum(l_quantity) from lineitem "
        "group by l_partkey order by 2 desc, 1 limit 10")
    import chip_smoke
    assert cls.sql({}) == chip_smoke.HNDV_SQL
    total: dict = {}
    for l in _lines(small["LINEITEM"]):
        total[l["l_partkey"]] = total.get(l["l_partkey"], 0) \
            + Decimal(l["l_quantity"]).scaleb(-2)
    want = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:10]
    assert total[1] == total[2] and 9 not in total
    got = cls.answer(cls.prepare(small), {})
    assert got == [(str(k), f"{v:.2f}") for k, v in want]
    assert (cls.POOL, cls.ORDERED, cls.draw(None)) == (1, True, {})


def test_hndv_rev_oracle_against_a_dictionary(small):
    cls = run_py.load_module("classes", "hndv_rev")
    state = cls.prepare(small)
    seen = set()
    for year in range(1993, 1998):
        lo = (datetime.date(year, 1, 1) - EPOCH).days
        hi = (datetime.date(year + 1, 1, 1) - EPOCH).days
        rev: dict = {}
        for l in _lines(small["LINEITEM"]):
            if lo <= l["l_shipdate"] < hi:
                r, n = rev.get(l["l_partkey"], (0, 0))
                rev[l["l_partkey"]] = (
                    r + Decimal(l["l_extendedprice"]).scaleb(-2)
                    * (1 - Decimal(l["l_discount"]).scaleb(-2)), n + 1)
        want = sorted(rev.items(), key=lambda kv: (-kv[1][0], kv[0]))[:10]
        got = cls.answer(state, {"year": year})
        assert got == [(str(k), f"{r:.4f}", str(n)) for k, (r, n) in want]
        seen.add(tuple(got))
        assert f"date '{year}-01-01' + interval '1' year" \
            in cls.sql({"year": year})
    assert len(seen) == 5
    rng = np.random.default_rng(5)
    assert {cls.draw(rng)["year"] for _ in range(200)} == set(range(1993, 1998))
    assert cls.POOL == 4 and cls.ORDERED


@pytest.mark.parametrize("name,cols", [("hndv_qty", 2), ("hndv_rev", 4)])
def test_bytes_read_counts_each_column_once(name, cols):
    cls = run_py.load_module("classes", name)
    assert len(cls.READS["LINEITEM"]) == cols
    width = {"LINEITEM": {c: 4 for c in cls.READS["LINEITEM"]}}
    assert cls.bytes_read({"LINEITEM": 1000}, width) == 1000 * 4 * cols


share = run_py.load_module("layer_metrics", "hndv_device_share")
ZERO = {"launches": 0, "hndv_agg_launches": 0, "hndv_agg_regrows": 0,
        "hndv_host_topn_launches": 0,
        "client": {"degraded": 0, "oom_recovered": 0}}


def _run(before, after, answered):
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1}, classes={},
               sched_before=before, sched_after=after,
               records=[{"ok": True}] * answered)


@pytest.mark.parametrize("before,after,answered,want", [
    (ZERO, dict(ZERO, launches=40, hndv_agg_launches=40), 40, 100.0),
    (dict(ZERO, launches=9, hndv_agg_launches=9),
     dict(ZERO, launches=49, hndv_agg_launches=49), 40, 100.0),
    # ranked by the host: beside it, not folded in
    (ZERO, dict(ZERO, launches=40, hndv_agg_launches=40,
                hndv_host_topn_launches=40), 40, 100.0),
    # one statement in four answered by another kind of program
    (ZERO, dict(ZERO, launches=40, hndv_agg_launches=30), 40, 75.0),
    # a regrow in the window; two launches a statement; the host engine
    (ZERO, dict(ZERO, launches=41, hndv_agg_launches=41,
                hndv_agg_regrows=1), 40, 0.0),
    (ZERO, dict(ZERO, launches=80, hndv_agg_launches=40), 40, 0.0),
    (ZERO, dict(ZERO, launches=40, hndv_agg_launches=40,
                client={"degraded": 1, "oom_recovered": 0}), 40, 0.0),
    # nothing answered; a program without the counters (the parent)
    (ZERO, ZERO, 0, None),
    ({"launches": 3}, {"launches": 90}, 40, None),
])
def test_hndv_device_share(before, after, answered, want):
    assert share.read(_run(before, after, answered)) == want


def test_the_roofline_readers_find_nothing_without_a_trace():
    run = _run(ZERO, ZERO, 4)
    for name in ("hndv_qty_group_roofline", "hndv_rev_group_roofline"):
        assert run_py.load_module("layer_metrics", name).read(run) is None


def test_cell_rehearsed_on_the_cpu():
    """The whole cell at SF0.01 for three seconds: every warm-up and
    window answer equals the oracle's, every statement was one launch,
    and the cell's metrics are found.  (On the CPU mesh the host engine
    answers a high-NDV GROUP BY: no device program, so the share of
    statements a device GROUP BY answered reads 0 or nothing here; 100
    is the chip's.)"""
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(bench, "tpch1x1.hndv")
    assert (cell["chips"], config["scale"], mix["clients"]) == (1, 1, 1)
    assert mix["mix"] == {"hndv_qty": 1, "hndv_rev": 1}
    assert (mix["pool_seed"], mix["cycles"], mix["order"], mix["loop"]) \
        == (29, 16, "shuffled", "closed")
    assert config["server"]["set_global"] \
        == {"tidb_tpu_result_cache_entries": 0}
    run = run_py.run_cell(cell, config, mix, seed=2147483677, seconds=3.0,
                          trace=False, scale=0.01)
    assert run.records and all(r["ok"] for r in run.records)
    assert set(run.ms_by_class()) == {"hndv_qty", "hndv_rev"}
    assert run.rows == {"LINEITEM": 60_000}
    assert len({r["stmt"] for r in run.records}) == 5   # 1 + a pool of 4
    wanted = {m["name"] for m in
              run_py.cell_metrics(bench, "per_layer", "tpch1x1.hndv")}
    assert {"device_ms.hndv_qty", "device_ms.hndv_rev", "hndv_device_share",
            "program_ms.solo_agg_sort", "hndv_qty_group_roofline",
            "hndv_rev_group_roofline", "launches_per_stmt", "host_merge_ms",
            "host_plan_ms", "wire_ms", "device_idle_share"} <= wanted
    assert not {"device_ms.q14", "join_device_share"} & wanted
    e2e = {m["name"] for m in
           run_py.cell_metrics(bench, "end_to_end", "tpch1x1.hndv")}
    assert e2e == {"stmt_ms_geomean", "stmt_p95_x", "peak_hbm_gb", "setup_s"}
    got = run_py.read_metrics(run, "layer_metrics", [
        m for m in bench["per_layer"] if m["name"] == "hndv_device_share"])
    assert got.get("hndv_device_share", {"value": 0.0})["value"] == 0.0
