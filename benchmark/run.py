#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one new process on a machine with the cell's chips.  This
process starts the server as ``python -m tidb_tpu serve`` does, makes the
cell's tables from the seed, registers them, runs ANALYZE, makes the
columns resident, computes the oracle's answers, issues every statement of
the cell twice (warm-up: its compiles are set-up), and then lets a child
process that never imports JAX offer the cell's traffic over TCP: a ramp of
a few seconds that is thrown away, then ``--seconds`` that are measured.
Every answer in the window is compared, text for text, with the oracle's.
The last line of standard output is the result.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<mix>.json``,
``classes/<class>.py``, ``tables/<table>.py``, ``end_to_end/<metric>.py``
and ``layer_metrics/<metric>.py``.  A metric named ``<reader>.<arg>`` is
read by ``<reader>.py`` with that argument.  There is no registry to edit.

With no TPU, or another number of chips than the cell names, it exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up counts from here

import argparse                     # noqa: E402
import glob                         # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import subprocess                   # noqa: E402
import sys                          # noqa: E402
import threading                    # noqa: E402
from statistics import median       # noqa: E402
import urllib.request               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)            # harness.*
sys.path.insert(1, ROOT)            # tidb_tpu

from harness import devicepath, traffic, wire, xplane   # noqa: E402
from harness.context import Run                          # noqa: E402

DB = "test"
TRACE_SLICE_S = 4.0                 # of the window, at its end
SYNC_EVERY_S = 0.25
QUIET_S, QUIET_CAP_S = 1.5, 120.0   # see _quiesce
RAMP_S = 3.0                        # see loadgen
SUMMARY_SQL = ("select exec_count, avg_latency_ms, query_sample_text "
               "from information_schema.statements_summary")


def log(*a) -> None:
    print("[bench]", *a, flush=True)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{name!r} is named but {path} is not there")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """The cell, its configuration and its traffic mix."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    mix = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    return cell, config, mix


def cell_metrics(bench: dict, section: str, workload: str) -> list[dict]:
    """The section's metrics this cell reports; a per-layer metric only
    where the end-to-end metric it moves is reported."""
    def here(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = {m["name"] for m in bench["end_to_end"] if here(m)}
    return [m for m in bench[section] if here(m)
            and (section == "end_to_end" or m["moves"] in e2e)]


def read_metrics(run: Run, section: str, metrics: list[dict]) -> dict:
    out = {}
    for m in metrics:
        reader, _, arg = m["name"].partition(".")
        value = load_module(section, reader).read(run, arg or None)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------- #
# set-up: server, tables, oracle, warm-up
# --------------------------------------------------------------------- #

def _column(kind: str, values, validity):
    """A generated column wrapped in the program's public ``Column``."""
    from tidb_tpu.chunk.column import Column, StringDict
    from tidb_tpu.types import dtypes as dt
    if kind == "dict":
        codes, dictionary = values
        return Column(dt.varchar(False), codes, validity,
                      StringDict(list(dictionary)))
    if kind == "bigint":
        return Column.from_numpy(dt.bigint(False), values, validity)
    if kind == "date":
        return Column.from_numpy(dt.date(False), values, validity)
    if kind.startswith("decimal(") and kind.endswith(")"):
        prec, scale = (int(x) for x in kind[8:-1].split(","))
        return Column.from_numpy(dt.decimal(prec, scale), values, validity)
    raise ValueError(f"no column type {kind!r}")


def _load_table(dom, admin, table, data: dict) -> None:
    if table.LOAD == "wire":
        admin.query(table.DDL)
        for stmt in table.inserts(data):    # raises unless acknowledged
            admin.query(stmt)
        return
    if table.LOAD != "bulk":
        raise ValueError(f"table {table.NAME}: LOAD is {table.LOAD!r}")
    import numpy as np
    from tidb_tpu.session.catalog import TableInfo
    valid = np.ones(len(next(_arrays(data))), bool)   # shared: no NULLs
    cols = [_column(table.TYPES[c], v, valid) for c, v in data.items()]
    info = TableInfo(table.NAME, list(data), [c.dtype for c in cols])
    info.register_columns(cols)
    dom.catalog.create_table(DB, info)


def _arrays(data: dict):
    """The generated columns' arrays (a dictionary column's are its codes)."""
    for v in data.values():
        yield v[0] if isinstance(v, tuple) else v


def _widths(data: dict) -> dict:
    """Bytes of the narrowest signed integer that holds each column: the
    width a scan has to read, worked out here and not asked of the
    program."""
    out = {}
    for c, v in zip(data, _arrays(data)):
        lo, hi = (int(v.min()), int(v.max())) if len(v) else (0, 0)
        out[c] = next(b for b in (1, 2, 4, 8)
                      if -(1 << (8 * b - 1)) <= lo and hi < 1 << (8 * b - 1))
    return out


class _Laps:
    """Set-up cut into named parts with nothing between them: a part ends
    where the next begins, so the parts add up to ``setup_s``."""

    def __init__(self, start: float):
        self.parts: dict = {}
        self._last = start

    def lap(self, name: str) -> float:
        """End the part ``name`` now; the time since the last lap is its."""
        now = time.monotonic()
        self.parts[name] = now - self._last
        self._last = now
        return now


def _get(port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60) as r:
        return json.load(r)


def _quiesce(port: int) -> None:
    """Wait until the scheduler's background compiles have stopped: no
    new program for ``QUIET_S``, or ``QUIET_CAP_S`` at the most."""
    def seen():
        s = _get(port, "/sched")
        return (s["compile_cache"]["misses"], s.get("warm_predicted"),
                s.get("warm_failures"))
    start = quiet = time.monotonic()
    last = seen()
    while time.monotonic() - quiet < QUIET_S \
            and time.monotonic() - start < QUIET_CAP_S:
        time.sleep(0.25)
        now = seen()
        if now != last:
            last, quiet = now, time.monotonic()


def _summary(admin, sql_class: dict) -> dict:
    """``{class: (exec_count, sum of latency ms)}`` from the server's own
    clock, over the digests whose sample is one of the cell's statements."""
    out: dict = {}
    for n, avg, sample in admin.query(SUMMARY_SQL):
        cls = sql_class.get(" ".join(sample.split()))
        if cls is not None:
            c, s = out.get(cls, (0, 0.0))
            out[cls] = (c + int(n), s + int(n) * float(avg))
    return out


def _trace_slice(run_dir: str, t0: float, seconds: float) -> dict:
    """Profile the last seconds of the window from this process, which
    holds the chip, writing a sync mark every quarter second."""
    import jax
    trace_dir = os.path.join(run_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    length = min(TRACE_SLICE_S, seconds / 2)
    lo, hi = t0 + seconds - length - 0.5, t0 + seconds - 0.5
    time.sleep(max(lo - time.monotonic(), 0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host stacks make the trace huge
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    marks = []
    began = time.monotonic_ns()
    while time.monotonic() < hi:
        with jax.profiler.TraceAnnotation(xplane.SYNC_NAME):
            marks.append(time.monotonic_ns())
        time.sleep(SYNC_EVERY_S)
    ended = time.monotonic_ns()
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb, found {files}")
    meta = {"file": files[0], "marks": marks, "began": began, "ended": ended}
    with open(os.path.join(run_dir, "trace_meta.json"), "w") as f:
        json.dump(meta, f)          # with records.json: the selftest's data
    return meta


def run_cell(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, scale: float | None = None) -> Run:
    """Set up, offer the traffic, and return what was measured.  ``scale``
    overrides the configuration's for the CPU rehearsal."""
    import jax
    from tidb_tpu.__main__ import start_server
    from tidb_tpu.config import load_config
    from tidb_tpu.jaxcache import place_jax_compile_cache

    scale = config["scale"] if scale is None else scale
    laps = _Laps(T_START)
    log("jax compile cache at", place_jax_compile_cache())
    classes = {c: load_module("classes", c) for c in mix["mix"]}
    tables = {t: load_module("tables", t)
              for c in classes.values() for t in c.READS}
    run = Run(cell=cell, config=config, mix=mix, classes=classes)
    run.peaks = load_json(HERE, "harness", "peaks.json")
    run.device_kind = jax.devices()[0].device_kind
    run_dir = os.path.join(ROOT, ".benchrun", cell["name"])
    os.makedirs(run_dir, exist_ok=True)

    cfg = load_config(None)
    cfg.port = cfg.status_port = 0          # ephemeral
    # the interpreter, JAX and the program imported, the chips found, the
    # cell's class and table files loaded
    laps.lap("import_s")
    dom, srv, st = start_server(cfg)
    admin = child = None
    try:
        admin = wire.Connection("127.0.0.1", srv.port, db=DB)
        for var, value in config["server"]["set_global"].items():
            admin.query(f"set global {var} = {value}")
        laps.lap("server_s")

        data = {name: tbl.generate(scale, seed,
                                   config["tables"][name]["columns"])
                for name, tbl in tables.items()}
        run.rows = {name: len(next(_arrays(d))) for name, d in data.items()}
        laps.lap("generate_s")

        # the oracle runs beside ANALYZE, H2D and nothing else: it is
        # numpy, which lets the interpreter go for most of its time
        state: dict = {}
        oracle_s: dict = {}

        def prepare(name, cls):
            t = time.monotonic()
            state[name] = cls.prepare(data)
            oracle_s[name] = time.monotonic() - t
        oracle = [threading.Thread(target=prepare, args=item)
                  for item in classes.items()]
        for th in oracle:
            th.start()

        for name, tbl in tables.items():
            _load_table(dom, admin, tbl, data[name])
        laps.lap("register_s")
        for name in tables:
            if config["tables"][name].get("analyze"):
                admin.query(f"analyze table {name}")
        laps.lap("analyze_s")
        mesh = dom.client.mesh
        jax.block_until_ready([
            dom.catalog.get_table(DB, name).snapshot().device_cols(mesh)
            for name, tbl in tables.items() if tbl.LOAD == "bulk"])
        laps.lap("h2d_s")
        for th in oracle:
            th.join()
        if set(state) != set(classes):
            raise RuntimeError("an oracle failed: see the traceback above")
        laps.lap("oracle_wait_s")
        log("the oracles' prepare, beside ANALYZE and H2D: " + "  ".join(
            f"{c}={v:.2f}s" for c, v in oracle_s.items()))

        # drawing the pools is the oracle's work too where a class draws
        # again on a tie (`q3`: a pass over `lineitem` for every set it
        # draws); `answer` then finds what `draw` worked out
        pools = traffic.pools(classes, mix)
        laps.lap("pools_s")
        statements, number = [], {}
        for name, pool in pools.items():
            for k, params in enumerate(pool):
                number[name, k] = len(statements)
                statements.append({
                    "class": name, "sql": classes[name].sql(params),
                    "ordered": classes[name].ORDERED,
                    "rows": classes[name].answer(state[name], params)})
        streams = [[number[c, k] for c, k in seq] for seq in traffic.streams(
            mix, {c: len(p) for c, p in pools.items()}, seed)]
        laps.lap("answers_s")

        # warm-up: every statement the window will send, checked, twice
        # over: the scheduler compiles fused programs it predicts from the
        # statements it has seen, in the background, and the last of them
        # start only on the second pass.  Then wait until it has gone quiet
        first: dict = {}
        for k, s in enumerate(statements + statements):
            t1 = time.monotonic()
            got, want = admin.query(s["sql"]), s["rows"]
            if k < len(statements):
                first[s["class"]] = first.get(s["class"], 0.0) \
                    + time.monotonic() - t1
            if not s["ordered"]:
                got, want = sorted(got), sorted(want)
            if got != want:
                raise RuntimeError(
                    f"warm-up: wrong answer for {s['sql']}\n got      "
                    f"{got[:4]}\n expected {want[:4]}")
        _quiesce(st.port)
        laps.lap("warmup_s")
        log("warm-up, first pass by class: " + "  ".join(
            f"{c}={v:.2f}s" for c, v in first.items()))

        plan = {"host": "127.0.0.1", "port": srv.port, "db": DB,
                "seconds": seconds, "seed": seed, "loop": mix["loop"],
                "ramp_s": RAMP_S,
                "clients": int(mix["clients"]),
                "rate_per_s": mix.get("rate_per_s"),
                "arrivals": mix.get("arrivals", "poisson"),
                "statements": statements, "streams": streams,
                "out": os.path.join(run_dir, "records.json")}
        with open(os.path.join(run_dir, "plan.json"), "w") as f:
            json.dump(plan, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
             os.path.join(run_dir, "plan.json")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        if child.stdout.readline().strip() != "READY":   # after its ramp
            raise RuntimeError("the load generator did not get ready")
        laps.lap("loadgen_ramp_s")

        sql_class = {" ".join(s["sql"].split()): s["class"]
                     for s in statements}
        run.summary_before = _summary(admin, sql_class)
        run.sched_before = _get(st.port, "/sched")
        wall0 = time.time()
        # the program's counters read: the last of set-up
        run.setup_s = laps.lap("counters_s") - T_START
        run.setup_parts = laps.parts
        child.stdin.write("GO\n")
        child.stdin.flush()
        t_go = time.monotonic()
        traced = _trace_slice(run_dir, t_go, seconds) if trace else None
        # no ``wait(timeout=...)``: it polls, and every wake-up of this
        # thread takes the interpreter from the server's threads
        watchdog = threading.Timer(seconds + 240, child.kill)
        watchdog.start()
        try:
            if child.wait() != 0:
                raise RuntimeError(
                    f"the load generator exited {child.returncode}")
        finally:
            watchdog.cancel()
        run.sched_after = _get(st.port, "/sched")
        run.summary_after = _summary(admin, sql_class)
        wall1 = time.time()

        got = load_json(plan["out"])
        run.t0, run.t_end = got["t0"], got["t_end"]
        run.records = [
            {"class": statements[i]["class"], "stmt": i, "due": due,
             "sent": sent,
             "done": done, "ok": ok, "err": err}
            for i, due, sent, done, ok, err in got["records"]]
        run.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices())
        if trace:
            for t in _get(st.port, "/trace")["traces"]:
                if wall0 <= t["start_ts"] <= wall1:
                    tree = _get(st.port, f"/trace/{t['trace_id']}")
                    tree["class"] = sql_class.get(" ".join(tree["sql"].split()))
                    if tree["class"] is not None:
                        run.trees.append(tree)
            run.trace = xplane.read(traced["file"])
            run.clock_offset_ns = xplane.clock_offset_ns(
                run.trace["sync"], traced["marks"])
            run.trace_lo_ns = traced["began"] + run.clock_offset_ns
            run.trace_hi_ns = traced["ended"] + run.clock_offset_ns
            run.widths = {name: _widths(d) for name, d in data.items()}
        return run
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if admin is not None:
            admin.close()
        srv.close()
        st.close()
        dom.close()


# --------------------------------------------------------------------- #
# the result line
# --------------------------------------------------------------------- #

def result(run: Run, bench: dict, trace: bool, device: dict) -> dict:
    """The one JSON object the driver reads."""
    ms = run.ms_by_class()
    for cls in sorted(ms):
        log(f"class {cls}: n={len(ms[cls])} median_ms={median(ms[cls]):.4f} "
            f"max_ms={max(ms[cls]):.4f}")
    log("set-up parts: " + "  ".join(
        f"{k}={v:.2f}" for k, v in run.setup_parts.items())
        + f"  (their sum {sum(run.setup_parts.values()):.2f}"
        f" = setup_s {run.setup_s:.2f})")
    wrong = [r for r in run.records if r["ok"] is False]
    failed = [r for r in run.records if r["ok"] is None]
    for r in (wrong + failed)[:5]:
        log(f"{r['class']}: {r['err']}")
    faults = devicepath.faults(device["platform"], run.sched_before,
                               run.sched_after, len(run.answered()))
    for f in faults:
        log("device path not proven:", f)
    log("scheduler in the window: " + "  ".join(
        f"{k}={run.sched_delta(k)}" for k in (
            "launches", "tasks_done", "coalesced_tasks", "batched_launches",
            "fused_launches", "fused_tasks", "window_waits", "busy_rejects")))
    compiles = run.sched_delta("compile_cache", "misses")
    if compiles:
        log(f"{compiles} programs compiled inside the window")
    section = "per_layer" if trace else "end_to_end"
    folder = "layer_metrics" if trace else "end_to_end"
    metrics = read_metrics(run, folder,
                           cell_metrics(bench, section, run.cell["name"]))
    device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": not wrong and not faults and bool(run.records),
           "attempted": len(run.records), "failed": len(failed),
           "metrics": metrics, "device": device}
    if trace:
        busy = run.busy()
        device["busy_s"] = busy["busy_s"]
        device["window_s"] = busy["window_s"]
        out["breakdown"] = xplane.breakdown(
            run.trace, run.traced_statements(), run.trace_lo_ns,
            run.trace_hi_ns)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, mix = find_cell(bench, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "tidb_tpu")):
        print("benchmark: no tidb_tpu/ beside benchmark/: nothing to measure",
              file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} x {devs[0].platform}", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["kind"] not in load_json(HERE, "harness", "peaks.json"):
        print(f"benchmark: no peaks for device kind {device['kind']!r}",
              file=sys.stderr)
        return 2
    log(f"{args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={device} jax={jax.__version__}")
    run = run_cell(cell, config, mix, args.seed, args.seconds,
                   bool(args.trace))
    print(json.dumps(result(run, bench, bool(args.trace), device)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
