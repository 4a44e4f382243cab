"""BENCHMARK.json against the limits of the benchmark's contract, so that
a file the driver would refuse before its first run is caught here."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    # a full check: 2 + 14 x cells runs, each run_seconds + 60, 2 x 90 per
    # cell to compile, 1200 spare, inside 43200; and with the full 24 cells
    for n in (cells, 24):
        assert (2 + 14 * n) * (bench["run_seconds"] + 60) + 180 * n + 1200 \
            <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmark/")
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        # the file says what each cut is, and states the guarantees
        assert set(c["reduced"]) == set(held["reduced"])
        assert held["source"] == c["source"] and held["guarantees"]
        assert held["server"]["set_global"][
            "tidb_tpu_result_cache_entries"] == 0


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(len(cells) // 2, 1)
    configs = {c["name"]: c for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            assert json.load(f)["chips"] == w["chips"]
        mix_file = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        with open(mix_file) as f:
            mix = json.load(f)
        for cls in mix["mix"]:
            assert os.path.isfile(os.path.join(BENCH, "classes", cls + ".py"))


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e, layer = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in e2e}
        assert one_line(m["layer"])
        reader = m["name"].partition(".")[0]
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           reader + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_enough(bench, run_py):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in
               run_py.cell_metrics(bench, "end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run_py.cell_metrics(bench, "per_layer", w["name"])


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for folder, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel
