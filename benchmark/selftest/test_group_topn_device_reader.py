"""``group_topn_device_share`` on made-up ``/sched`` documents: it reads
what it says, and finds nothing (without raising) in a program that keeps
no such counter, as the parent of the PR that added it does not."""

import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
share = run_py.load_module("layer_metrics", "group_topn_device_share")


def _run(before, after):
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1}, classes={},
               sched_before=before, sched_after=after)


ZERO = {"hndv_agg_launches": 0, "group_topn_device_launches": 0,
        "hndv_host_topn_launches": 0}


@pytest.mark.parametrize("before,after,want", [
    (ZERO, dict(ZERO, hndv_agg_launches=365, group_topn_device_launches=365),
     100.0),
    # the warm-up's launches are not the window's
    (dict(ZERO, hndv_agg_launches=8, group_topn_device_launches=8),
     dict(ZERO, hndv_agg_launches=373, group_topn_device_launches=373),
     100.0),
    # one launch in four sent its table whole (a record rerun wide)
    (ZERO, dict(ZERO, hndv_agg_launches=40, group_topn_device_launches=30,
                hndv_host_topn_launches=10), 75.0),
    # a GROUP BY with no TopN above it, or one the host ranked
    (ZERO, dict(ZERO, hndv_agg_launches=12), 0.0),
    (ZERO, dict(ZERO, hndv_agg_launches=12, hndv_host_topn_launches=12),
     0.0),
    # counters that started with the window
    ({}, dict(ZERO, hndv_agg_launches=8, group_topn_device_launches=8),
     100.0),
    # no such program launched in the window (the power cells)
    (dict(ZERO, hndv_agg_launches=3, group_topn_device_launches=3),
     dict(ZERO, hndv_agg_launches=3, group_topn_device_launches=3), None),
    # a program without the counter (the parent): nothing, no KeyError
    ({"hndv_agg_launches": 3, "hndv_host_topn_launches": 0},
     {"hndv_agg_launches": 90, "hndv_host_topn_launches": 0}, None),
    ({"launches": 3}, {"launches": 90}, None),
])
def test_group_topn_device_share(before, after, want):
    assert share.read(_run(before, after)) == want


def test_the_cell_lists_it():
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "group_topn_device_share"]
    assert entry == {"name": "group_topn_device_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "device programs", "moves": "stmt_ms_geomean",
                     "workloads": ["tpch1x1.orderjoin"]}
