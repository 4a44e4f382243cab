"""A later PR adds a class, a table, a mix, a configuration and a metric
as new files plus entries in BENCHMARK.json, and edits no file that is
there.  Shown on a temporary copy of the benchmark."""

import hashlib
import json
import os
import shutil

from conftest import BENCH, ROOT, load_run_py

NEW_CLASS = '''
"""Throw-away class: parts at or under a size."""
NAME = "tmp_small_parts"
POOL = 2
ORDERED = True
READS = {"tmp_part": ["p_size"]}


def draw(rng):
    return {"size": int(rng.integers(5, 45))}


def sql(p):
    return f"select count(*) from tmp_part where p_size <= {p['size']}"


def prepare(data):
    return data["tmp_part"]["p_size"]


def answer(size, p):
    return [(str(int((size <= p["size"]).sum())),)]


def bytes_read(rows, width):
    return rows["tmp_part"] * width["tmp_part"]["p_size"]
'''

NEW_TABLE = '''
"""Throw-away table: sizes only."""
import numpy as np
NAME = "tmp_part"
LOAD = "bulk"
TYPES = {"p_size": "bigint"}


def generate(scale, seed, columns):
    rng = np.random.default_rng([seed, 77])
    return {"p_size": rng.integers(1, 51, int(200_000 * scale))}
'''

NEW_METRIC = '''
"""Throw-away per-layer metric: statements answered in the window."""


def read(run, arg=None):
    return len(run.answered())
'''


def _digests(top: str) -> dict:
    out = {}
    for folder, _, files in os.walk(top):
        if ".benchrun" in folder or "__pycache__" in folder:
            continue
        for f in files:
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                out[path] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_is_files_and_entries_only(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "selftest"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = _digests(str(copy))

    # the later PR's files ...
    (copy / "benchmark/classes/tmp_small_parts.py").write_text(NEW_CLASS)
    (copy / "benchmark/tables/tmp_part.py").write_text(NEW_TABLE)
    (copy / "benchmark/layer_metrics/tmp_answered.py").write_text(NEW_METRIC)
    (copy / "benchmark/traffic/tmp_mix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2, "order": "shuffled", "cycles": 4,
         "pool_seed": 5,
         "mix": {"tmp_small_parts": 3, "kv_agg": 1}}))
    config = json.load(open(os.path.join(BENCH, "configs/tpch_sf10_x1.json")))
    config["name"] = "tmp_config"
    config["tables"]["tmp_part"] = {"columns": ["p_size"]}
    (copy / "benchmark/configs/tmp_config.json").write_text(json.dumps(config))
    # ... and its entries
    bench["configs"].append({
        "name": "tmp_config", "source": "selftest",
        "file": "benchmark/configs/tmp_config.json", "reduced": [],
        "why": "selftest"})
    bench["workloads"].append({
        "name": "tmp.cell", "config": "tmp_config", "traffic": "tmp_mix",
        "chips": 1, "why": "selftest"})
    bench["per_layer"].append({
        "name": "tmp_answered", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "wire", "moves": "stmt_ms_geomean",
        "workloads": ["tmp.cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    run_py = load_run_py(str(copy / "benchmark"))
    loaded = run_py.load_json(run_py.ROOT, "BENCHMARK.json")
    cell, config, mix = run_py.find_cell(loaded, "tmp.cell")
    run = run_py.run_cell(cell, config, mix, seed=9, seconds=3.0,
                          trace=False, scale=0.01)
    assert run.records and all(r["ok"] for r in run.records)
    assert set(run.ms_by_class()) == {"tmp_small_parts", "kv_agg"}
    wanted = run_py.cell_metrics(loaded, "per_layer", "tmp.cell")
    assert "tmp_answered" in {m["name"] for m in wanted}
    # set-up's parts list no cell: a cell that comes later reports them
    assert "setup_part_s.oracle" in {m["name"] for m in wanted}
    # the one-chip power cells' metrics do not leak into the new cell
    assert "device_ms.q6" not in {m["name"] for m in wanted}
    got = run_py.read_metrics(
        run, "layer_metrics",
        [m for m in wanted if m["name"] == "tmp_answered"])
    assert got["tmp_answered"]["value"] == len(run.records)

    after = _digests(str(copy))
    assert {p: h for p, h in after.items() if p in before} == before
