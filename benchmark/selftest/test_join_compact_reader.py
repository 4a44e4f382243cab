"""``join_compact_share`` on made-up ``/sched`` documents: it reads what it
says, and finds nothing (without raising) in a program that keeps no such
counter, as the parent of the PR that added it does not."""

import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
share = run_py.load_module("layer_metrics", "join_compact_share")


def _run(before, after):
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1}, classes={},
               sched_before=before, sched_after=after)


ZERO = {"join_launches": 0, "join_compact_launches": 0,
        "join_compact_overflows": 0}


@pytest.mark.parametrize("before,after,want", [
    (ZERO, dict(ZERO, join_launches=40, join_compact_launches=40), 100.0),
    (dict(ZERO, join_launches=16, join_compact_launches=16),
     dict(ZERO, join_launches=56, join_compact_launches=56), 100.0),
    # a digest that overflowed once and runs the exact program since
    (ZERO, dict(ZERO, join_launches=40, join_compact_launches=30,
                join_compact_overflows=1), 75.0),
    # an unfiltered probe side: joins, none compacted
    (ZERO, dict(ZERO, join_launches=12), 0.0),
    # counters that started with the window
    ({}, dict(ZERO, join_launches=8, join_compact_launches=8), 100.0),
    # no join program launched in the window (the power cells)
    (dict(ZERO, join_launches=3, join_compact_launches=3),
     dict(ZERO, join_launches=3, join_compact_launches=3), None),
    # a program without the counter (the parent): nothing, no KeyError
    ({"join_launches": 3}, {"join_launches": 90}, None),
    ({"launches": 3}, {"launches": 90}, None),
])
def test_join_compact_share(before, after, want):
    assert share.read(_run(before, after)) == want


def test_the_cell_lists_it():
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "join_compact_share"]
    assert entry == {"name": "join_compact_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "device programs", "moves": "stmt_ms_geomean",
                     "workloads": ["tpch1x1.partjoin"]}
