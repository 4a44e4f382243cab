"""``join_window_share`` on made-up ``/sched`` documents: it reads what
it says, and finds nothing (without raising) in a program that keeps no
such counter, as the parent of the PR that added it does not."""

import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
share = run_py.load_module("layer_metrics", "join_window_share")


def _run(before, after):
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1}, classes={},
               sched_before=before, sched_after=after)


ZERO = {"join_launches": 0, "join_direct_launches": 0,
        "join_window_launches": 0, "join_window_overflows": 0}


@pytest.mark.parametrize("before,after,want", [
    # a `q3` and a `q12` statement: three join launches, one by windows
    (ZERO, dict(ZERO, join_launches=3, join_direct_launches=3,
                join_window_launches=1), pytest.approx(100 / 3)),
    (ZERO, dict(ZERO, join_launches=1233, join_direct_launches=1233,
                join_window_launches=411), pytest.approx(100 / 3)),
    # the warm-up's launches are not the window's
    (dict(ZERO, join_launches=24, join_window_launches=8),
     dict(ZERO, join_launches=1257, join_window_launches=419),
     pytest.approx(100 / 3)),
    # a window that missed is rerun by the gather: one launch more, and
    # the digest gathers from then on
    (ZERO, dict(ZERO, join_launches=31, join_window_launches=1,
                join_window_overflows=1), pytest.approx(100 / 31)),
    # every join launch in key order; none (`tpch1x1.partjoin`)
    (ZERO, dict(ZERO, join_launches=40, join_window_launches=40), 100.0),
    (ZERO, dict(ZERO, join_launches=40), 0.0),
    # counters that started with the window
    ({}, dict(ZERO, join_launches=6, join_window_launches=2),
     pytest.approx(100 / 3)),
    # no join program launched in the window (the power cells)
    (dict(ZERO, join_launches=3, join_window_launches=1),
     dict(ZERO, join_launches=3, join_window_launches=1), None),
    # a program without the counter (the parent): nothing, no KeyError
    ({"join_launches": 3, "join_direct_launches": 3},
     {"join_launches": 90, "join_direct_launches": 90}, None),
    ({"launches": 3}, {"launches": 90}, None),
])
def test_join_window_share(before, after, want):
    assert share.read(_run(before, after)) == want


def test_the_cell_lists_it():
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "join_window_share"]
    assert entry == {"name": "join_window_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "device programs", "moves": "stmt_ms_geomean",
                     "workloads": ["tpch1x1.orderjoin"]}
