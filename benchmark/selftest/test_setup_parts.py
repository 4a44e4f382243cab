"""``setup_part_s.<part>`` on made-up laps: each part read once, their sum
``setup_s``, nothing (without raising) from a run that kept no such lap;
``run.py``'s laps leave nothing between them; and what ``BENCHMARK.json``
lists of them."""

import types

import pytest

from conftest import load_run_py
from harness.context import Run

run_py = load_run_py()
reader = run_py.load_module("layer_metrics", "setup_part_s")

LAPS = {"import_s": 9.5, "server_s": 1.25, "generate_s": 70.0,
        "register_s": 0.5, "analyze_s": 12.0, "h2d_s": 8.0,
        "oracle_wait_s": 2.0, "pools_s": 7.0, "answers_s": 0.001,
        "warmup_s": 14.0, "loadgen_ramp_s": 3.4, "counters_s": 0.02}
WANT = {"import": 9.5, "server": 1.25, "generate": 70.0, "register": 0.5,
        "analyze": 12.0, "h2d": 8.0, "oracle": 9.001, "warmup": 14.0,
        "ramp": 3.42}


def _run(parts):
    return Run(cell={"chips": 1}, config={}, mix={"clients": 1}, classes={},
               setup_parts=parts, setup_s=sum(parts.values()))


@pytest.mark.parametrize("part", sorted(WANT))
def test_a_part_is_its_laps(part):
    assert reader.read(_run(LAPS), part) == pytest.approx(WANT[part])


def test_the_parts_add_up_to_setup_s():
    run = _run(LAPS)
    assert sum(reader.read(run, p) for p in WANT) \
        == pytest.approx(run.setup_s)
    # every lap belongs to exactly one part
    named = [n for p in WANT for n in reader.PARTS.get(p, (f"{p}_s",))]
    assert sorted(named) == sorted(LAPS)


@pytest.mark.parametrize("parts,part", [
    ({}, "oracle"), ({}, "import"), ({"oracle_wait_s": 2.0}, "oracle"),
    (LAPS, "nothing_by_this_name")])
def test_nothing_to_read_is_none(parts, part):
    assert reader.read(_run(parts), part) is None


def test_laps_leave_nothing_between_them(monkeypatch):
    now = iter([10.0, 12.5, 12.5])
    monkeypatch.setattr(run_py, "time", types.SimpleNamespace(
        monotonic=lambda: next(now)))
    laps = run_py._Laps(4.0)
    assert laps.lap("a_s") == 10.0 and laps.lap("b_s") == 12.5
    laps.lap("c_s")
    assert laps.parts == {"a_s": 6.0, "b_s": 2.5, "c_s": 0.0}
    assert sum(laps.parts.values()) == 12.5 - 4.0


def test_the_benchmark_lists_them_for_every_cell():
    bench = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if m["name"].startswith("setup_part_s.")]
    assert {m["name"].partition(".")[2] for m in mine} == set(WANT)
    for m in mine:
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "host_clock", "layer": "set-up",
                     "moves": "setup_s"}
    for w in bench["workloads"]:
        wanted = {m["name"] for m in
                  run_py.cell_metrics(bench, "per_layer", w["name"])}
        assert {m["name"] for m in mine} <= wanted
