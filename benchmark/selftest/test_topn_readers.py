"""The two TopN readers on made-up counters and device times: they read
what they say, and find nothing (without raising) in a program that keeps
no such counters, as the parent of the PR that added them does not."""

import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
pruned = run_py.load_module("layer_metrics", "topn_pruned_share")
roofline = run_py.load_module("layer_metrics", "topn_scan_roofline")


def _run(before=None, after=None, **kw):
    kw.setdefault("classes", {})
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1},
               sched_before=before or {}, sched_after=after or {}, **kw)


@pytest.mark.parametrize("before,after,want", [
    ({"topn_launches": 4, "topn_pruned_launches": 4},
     {"topn_launches": 14, "topn_pruned_launches": 14}, 100.0),
    ({"topn_launches": 4, "topn_pruned_launches": 0},
     {"topn_launches": 12, "topn_pruned_launches": 2}, 25.0),
    # counters that started with the window
    ({}, {"topn_launches": 5, "topn_pruned_launches": 5}, 100.0),
    # no TopN launched in the window (tpch10x1.small): nothing to read
    ({"topn_launches": 0, "topn_pruned_launches": 0},
     {"topn_launches": 0, "topn_pruned_launches": 0}, None),
    # a program without the counters (the parent): nothing, no KeyError
    ({"launches": 3}, {"launches": 90}, None),
])
def test_pruned_share(before, after, want):
    assert pruned.read(_run(before, after)) == want


class _Topn:
    @staticmethod
    def bytes_read(rows, widths):
        return rows["lineitem"] * 8


def test_scan_roofline_is_least_time_over_device_time():
    run = _run(classes={"topn": _Topn}, rows={"lineitem": 60_000_000},
               peaks={"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
               device_kind="TPU v5 lite", trace={})
    run._device_ms[None] = {"topn": [11.0, 12.0, 13.0]}
    least_ms = 60_000_000 * 8 / 819e9 * 1e3
    assert roofline.read(run) == pytest.approx(100 * least_ms / 12.0)
    run.cell["chips"] = 4               # the bytes spread over the chips
    assert roofline.read(run) == pytest.approx(100 * least_ms / 4 / 12.0)
    run._device_ms[None] = {"q6": [4.6]}        # no TopN in the slice
    assert roofline.read(run) is None
    assert roofline.read(_run(classes={"topn": _Topn})) is None  # no trace
