"""The two dense-aggregation readers on made-up counters and device times:
they read what they say, and find nothing (without raising) in a program
that keeps no such counters, as the parent of the PR that added them does
not."""

import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
share = run_py.load_module("layer_metrics", "dense_agg_limb_share")
roofline = run_py.load_module("layer_metrics", "q1_agg_roofline")


def _run(before=None, after=None, **kw):
    kw.setdefault("classes", {})
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1},
               sched_before=before or {}, sched_after=after or {}, **kw)


@pytest.mark.parametrize("before,after,want", [
    ({"dense_agg_launches": 8, "dense_agg_limb_launches": 8},
     {"dense_agg_launches": 408, "dense_agg_limb_launches": 408}, 100.0),
    # a program that fell back to the broadcast for some launches
    ({"dense_agg_launches": 8, "dense_agg_limb_launches": 0},
     {"dense_agg_launches": 16, "dense_agg_limb_launches": 2}, 25.0),
    # counters that started with the window
    ({}, {"dense_agg_launches": 5, "dense_agg_limb_launches": 5}, 100.0),
    # no DENSE aggregation launched in the window (tpch1x1.partjoin)
    ({"dense_agg_launches": 3, "dense_agg_limb_launches": 3},
     {"dense_agg_launches": 3, "dense_agg_limb_launches": 3}, None),
    # a program without the counters (the parent): nothing, no KeyError
    ({"launches": 3}, {"launches": 90}, None),
])
def test_limb_share(before, after, want):
    assert share.read(_run(before, after)) == want


class _Q1:
    @staticmethod
    def bytes_read(rows, widths):
        return rows["lineitem"] * 12


def test_q1_roofline_is_least_time_over_device_time():
    run = _run(classes={"q1": _Q1}, rows={"lineitem": 60_000_000},
               peaks={"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
               device_kind="TPU v5 lite", trace={})
    run._device_ms[None] = {"q1": [3.9, 4.0, 4.1], "q6": [4.6]}
    least_ms = 60_000_000 * 12 / 819e9 * 1e3
    assert roofline.read(run) == pytest.approx(100 * least_ms / 4.0)
    run.cell["chips"] = 4               # the bytes spread over the chips
    assert roofline.read(run) == pytest.approx(100 * least_ms / 4 / 4.0)
    run._device_ms[None] = {"q6": [4.6]}        # no Q1 in the slice
    assert roofline.read(run) is None
    assert roofline.read(_run(classes={"q1": _Q1})) is None     # no trace
