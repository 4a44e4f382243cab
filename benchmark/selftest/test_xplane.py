"""The trace reduction pinned on a trace recorded on the chip, so that
every later PR computes busy time, idle share and per-class device time
the same way.  The trace is the traced slice of one ``tpch10x1.power`` run
on one TPU v5 lite (PR 22, chip call 2); ``power_slice.json`` holds the
harness's sync marks and the load generator's statement intervals."""

import json
import os
from statistics import median

import pytest

from harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "power_slice.json")) as f:
        meta = json.load(f)
    trace = xplane.read(os.path.join(DATA, "power_slice.xplane.pb.gz"))
    off = xplane.clock_offset_ns(trace["sync"], meta["marks"])
    statements = [(c, s * 1e9 + off, e * 1e9 + off)
                  for c, s, e in meta["statements"]]
    return trace, statements, meta["began"] + off, meta["ended"] + off


def test_planes_and_lines(recorded):
    trace = recorded[0]
    assert list(trace["devices"]) == [0]            # one chip, one plane
    dev = trace["devices"][0]
    assert (len(dev["ops"]), len(dev["modules"])) == (2492, 71)
    assert len(trace["sync"]) == 16
    assert {m[2].split("(")[0] for m in dev["modules"]} == {"jit__device_fn"}
    assert any(xplane.own_name(o) == "%sort" for o in dev["ops"])


def test_busy_union_and_idle_share(recorded):
    trace, _, lo, hi = recorded
    busy = xplane.busy(trace, lo, hi)
    assert busy["window_s"] == pytest.approx(4.03780366, abs=1e-6)
    assert busy["busy_s"] == pytest.approx(3.506076269, abs=1e-6)
    assert busy["idle_share"] == pytest.approx(0.131687282, abs=1e-6)
    # the operations' union is the programs' union, within 0.01 %
    dev = trace["devices"][0]
    ops = xplane.covered(xplane.union(dev["ops"]), lo, hi)
    modules = xplane.covered(xplane.union(dev["modules"]), lo, hi)
    assert ops == pytest.approx(modules, rel=1e-4)


def test_device_time_per_class(recorded):
    trace, statements, lo, hi = recorded
    per = xplane.per_statement(trace, statements, lo, hi)
    assert {c: len(v) for c, v in per.items()} == {"q6": 45, "q1": 20,
                                                   "topn": 5}
    assert median(per["q6"]) == pytest.approx(6.298535, abs=1e-5)
    assert median(per["q1"]) == pytest.approx(50.16796, abs=1e-5)
    assert median(per["topn"]) == pytest.approx(372.900459, abs=1e-5)
    # one chip: no collective ran
    coll = xplane.per_statement(trace, statements, lo, hi,
                                only=xplane.COLLECTIVE)
    assert all(max(v) == 0 for v in coll.values())
    assert all(max(v) == 0 for v in xplane.exposed_collective_ms(
        trace, statements, lo, hi).values())


def test_breakdown(recorded):
    trace, statements, lo, hi = recorded
    bd = xplane.breakdown(trace, statements, lo, hi)
    name, seconds = bd["device_ops"][0]
    assert name.startswith("%sort = (s32[67108864]")
    assert len(name) <= xplane.OP_NAME_CHARS
    assert seconds == pytest.approx(2.194211672, abs=1e-6)
    assert len(bd["device_ops"]) == 10
    gaps = dict(bd["idle_gaps"])
    assert set(gaps) == {"q1 in flight", "q6 in flight", "topn in flight"}
    busy = xplane.busy(trace, lo, hi)
    assert sum(gaps.values()) == pytest.approx(
        busy["window_s"] - busy["busy_s"], abs=1e-6)


def test_union_and_covered():
    u = xplane.union([(5, 7, "b"), (0, 2, "a"), (1, 3, "c"), (7, 8, "d")])
    assert u == [(0, 3), (5, 8)]
    assert xplane.union([(0, 10, "a")], lo=2, hi=4) == [(2, 4)]
    assert xplane.covered(u, 2, 6) == 2
    with pytest.raises(ValueError):
        xplane.clock_offset_ns([1, 2], [1])


def test_exposed_collective_counts_only_time_alone():
    trace = {"devices": {0: {"ops": [
        (0, 10, "%all-reduce.1 = f32[] all-reduce(%x)"),
        (4, 8, "%fusion.2 = f32[] fusion(%all-reduce.1)"),   # names it only
    ], "modules": []}}}
    statements = [("q", 0, 20)]
    ms = xplane.per_statement(trace, statements, 0, 20, xplane.COLLECTIVE)
    assert ms == {"q": [10 / 1e6]}
    assert xplane.exposed_collective_ms(trace, statements, 0, 20) == {
        "q": [6 / 1e6]}
