"""Benchmark: TPC-H Q1 + Q6 + Q19 + ROLLUP through the coprocessor.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...},
or exits non-zero when the chip child recorded no rung.

- value: TPC-H Q1 rows/sec/chip at the LARGEST scale factor that
  completed, through the full CopClient -> shard_map -> fused-kernel ->
  psum path, warm, median of BENCH_ITERS runs.  The record names the
  platform it ran on; only a caller who sets JAX_PLATFORMS=cpu gets a
  CPU record, and that is a correctness run, not a speed.
- vs_baseline: ratio to a single-core vectorized numpy implementation of
  the same query on the same host (see BASELINE.md "reference CPU
  baseline").
- per-rung fields: q6/q19/rollup times + ratios and achieved physical
  GB/s for Q1+Q6.  (The high-NDV GROUP BY is measured by the cell
  `tpch1x1.hndv` of benchmark/run.py.)

One process per chip.  This parent imports only numpy and never touches
JAX.  It runs, one after the other:
  1. a CPU child that pre-generates the data (cached under
     BENCH_DATA_DIR);
  2. a CPU child with the scheduler scenarios (open-loop concurrent
     sessions: counts and invariants on the 8-virtual-device mesh);
  3. exactly one child that touches the chip, after the CPU children
     have exited: the SF ladder (0.1 -> 1 -> 10), each completed rung
     appended to the results file so a timeout mid-ladder still reports
     the largest completed rung.
JAX's persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
says, else <checkout>/.jax_cache (tidb_tpu/jaxcache.py).  Every stage
logs elapsed-time-stamped lines to stderr.

The rungs are the pre-round ones (hand-built DAGs, BENCH_ITERS=5); the
cell matrix that replaces them is ROADMAP queue 1 item 0.
"""

import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np

T0 = time.time()
DATA_DIR = os.environ.get("BENCH_DATA_DIR", "/tmp/tidb_tpu_bench")
RESULTS_PATH = os.path.join(DATA_DIR, "results.jsonl")
SCHED_PATH = os.path.join(DATA_DIR, "sched_concurrent.json")
COLS_NEEDED = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
               "l_returnflag", "l_linestatus", "l_shipdate", "l_partkey",
               "l_shipmode", "l_shipinstruct"]
SF100_COLS = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]


def log(*a):
    print(f"[bench {time.time()-T0:7.1f}s]", *a, file=sys.stderr, flush=True)


def _data_path(sf):
    return os.path.join(DATA_DIR, f"lineitem_sf{sf:g}.pkl")


# --------------------------------------------------------------------- #
# child process management (a child that overruns its time is killed
# with its whole process group)
# --------------------------------------------------------------------- #

def _run_child(env_extra, timeout_s, tag):
    env = dict(os.environ, **env_extra)
    log(f"starting child {tag} (timeout {timeout_s:.0f}s)")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stdout=subprocess.PIPE, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        log(f"child {tag} exited rc={proc.returncode}")
        return proc.returncode, out
    except subprocess.TimeoutExpired:
        log(f"child {tag} timed out after {timeout_s:.0f}s; killing group")
        try:
            os.killpg(proc.pid, 9)
        except Exception:
            pass
        try:
            out, _ = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            out = b""
        return None, out or b""


def orchestrate():
    deadline = T0 + float(os.environ.get("BENCH_DEADLINE", "3300"))
    os.makedirs(DATA_DIR, exist_ok=True)
    try:
        os.remove(RESULTS_PATH)
    except OSError:
        pass

    ladder = [float(x) for x in
              os.environ.get("BENCH_SF_LADDER", "0.1,1,10").split(",")]
    cpu_only = os.environ.get("JAX_PLATFORMS") == "cpu"

    # 1. pre-generate data (CPU child, no TPU backend)
    rc, _ = _run_child({"BENCH_MODE": "gen", "JAX_PLATFORMS": "cpu",
                        "BENCH_SF_LIST": ",".join(str(s) for s in ladder)},
                       900, "datagen")
    if rc != 0:
        log("datagen child failed; children will generate inline")

    # 1b. scheduler scenario (CPU child): open-loop concurrent sessions
    # through the admission scheduler — coalesce/fusion rates + p50/p99
    # schedWait, the tracked perf numbers for cross-query fusion
    try:
        os.remove(SCHED_PATH)
    except OSError:
        pass
    rc, _ = _run_child({"BENCH_MODE": "sched", "JAX_PLATFORMS": "cpu"},
                       900, "sched-concurrent")
    if rc != 0:
        log("sched-concurrent child failed; omitting scenario")

    # 2. the one child that touches the chip, after the CPU children
    # have exited.  It inherits the caller's platform choice: only a
    # caller who asked for JAX_PLATFORMS=cpu gets a CPU record.
    bench_t = max(deadline - time.time() - 30, 300)
    child_env = {"BENCH_MODE": "bench",
                 "BENCH_SF_LADDER": ",".join(str(s) for s in ladder),
                 "BENCH_CHILD_DEADLINE": str(time.time() + bench_t - 30)}
    rc, _ = _run_child(child_env, bench_t,
                       "cpu-bench" if cpu_only else "chip-bench")
    best = _best_result(platform_only="cpu") if cpu_only \
        else _best_result(platform_not="cpu")
    if best is None:
        log("bench child recorded no rung"
            + ("" if cpu_only else " on an accelerator")
            + f" (rc={rc}); no result")
        return rc if rc else 1
    sf100 = _sf100_result()
    if sf100 is not None:
        best["sf100_q6"] = sf100
    try:
        with open(SCHED_PATH) as f:
            best["sched_concurrent"] = json.load(f)
    except (OSError, ValueError):
        pass
    print(json.dumps(best))
    return 0


def _best_result(platform_not=None, platform_only=None):
    """Largest-SF result line recorded by a bench child."""
    try:
        lines = [json.loads(ln) for ln in open(RESULTS_PATH)
                 if ln.strip()]
    except OSError:
        return None
    lines = [r for r in lines if not r.get("sf100_only")]
    if platform_not is not None:
        lines = [r for r in lines if r.get("platform") != platform_not]
    if platform_only is not None:
        lines = [r for r in lines if r.get("platform") == platform_only]
    if not lines:
        return None
    r = dict(max(lines, key=lambda r: r.get("sf", 0)))
    return r


def _sf100_result():
    try:
        lines = [json.loads(ln) for ln in open(RESULTS_PATH)
                 if ln.strip()]
    except OSError:
        return None
    for r in reversed(lines):
        if r.get("sf100_only"):
            r.pop("sf100_only", None)
            return r
    return None


# --------------------------------------------------------------------- #
# modes that run inside children
# --------------------------------------------------------------------- #

def _cache_ok(path) -> bool:
    """A cached pickle from an older bench revision may miss columns the
    current rungs need — validate before trusting it."""
    try:
        with open(path, "rb") as f:
            names, _cols = pickle.load(f)
        return set(COLS_NEEDED) <= set(names)
    except Exception:
        return False


def mode_gen():
    """Generate + cache bench data without touching any TPU backend."""
    from tidb_tpu.testing.tpch import gen_lineitem
    for sf in [float(x) for x in os.environ["BENCH_SF_LIST"].split(",")]:
        path = _data_path(sf)
        if os.path.exists(path) and _cache_ok(path):
            log(f"sf={sf:g} cache hit")
            continue
        t = time.time()
        names, cols = gen_lineitem(sf=sf, columns=COLS_NEEDED)
        with open(path + ".tmp", "wb") as f:
            pickle.dump((names, cols), f, protocol=4)
        os.replace(path + ".tmp", path)
        log(f"generated sf={sf:g}: {len(cols[0])} rows in {time.time()-t:.1f}s")


def _load_data(sf):
    path = _data_path(sf)
    if os.path.exists(path) and _cache_ok(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    from tidb_tpu.testing.tpch import gen_lineitem
    t = time.time()
    names, cols = gen_lineitem(sf=sf, columns=COLS_NEEDED)
    log(f"generated sf={sf:g} inline: {len(cols[0])} rows "
        f"in {time.time()-t:.1f}s")
    return names, cols


def _record(res):
    with open(RESULTS_PATH, "a") as f:
        f.write(json.dumps(res) + "\n")


RATIOS_PATH = os.path.join(DATA_DIR, "ratios.json")


def _load_ratio(platform, sf):
    try:
        with open(RATIOS_PATH) as f:
            return json.load(f).get(f"{platform}_sf{sf:g}")
    except (OSError, ValueError):
        return None


def _store_ratio(platform, sf, ratio):
    try:
        with open(RATIOS_PATH) as f:
            d = json.load(f)
    except (OSError, ValueError):
        d = {}
    d[f"{platform}_sf{sf:g}"] = round(float(ratio), 3)
    with open(RATIOS_PATH, "w") as f:
        json.dump(d, f)


def _host_copy_bw_gbps():
    """Measured host memcpy bandwidth — the roofline denominator for the
    CPU path (a copy touches 2 bytes of traffic per byte of payload)."""
    buf = np.empty(1 << 28, np.uint8)   # 256 MB
    buf[:] = 1
    t = time.time()
    for _ in range(3):
        out = buf.copy()
    dt_ = (time.time() - t) / 3
    del out
    return 2 * buf.nbytes / dt_ / 1e9


def mode_bench():
    import logging

    import jax

    from tidb_tpu.jaxcache import place_jax_compile_cache
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="[bench] %(name)s %(message)s")
    log("compile cache at", place_jax_compile_cache())
    devs = jax.devices()
    platform, n_chips = devs[0].platform, len(devs)
    log(f"platform={platform} device_kind={devs[0].device_kind} "
        f"devices={n_chips}")
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        # JAX found no accelerator and fell back by itself: refuse, a
        # CPU ladder is only ever run for a caller who asked for one
        log("no accelerator and JAX_PLATFORMS=cpu was not asked for; "
            "no rung")
        sys.exit(3)
    iters = int(os.environ.get("BENCH_ITERS", "5"))
    ladder = [float(x) for x in os.environ["BENCH_SF_LADDER"].split(",")]
    mem_bw = _host_copy_bw_gbps() if platform == "cpu" else None
    if mem_bw:
        log(f"host copy bandwidth: {mem_bw:.1f} GB/s")
    for sf in ladder:
        log(f"=== SF {sf:g} ===")
        _bench_one_sf(sf, platform, n_chips, iters, mem_bw)
    deadline = float(os.environ.get("BENCH_CHILD_DEADLINE", "0") or 0)
    if platform == "cpu" and os.environ.get("BENCH_SF100", "1") != "0":
        budget = (deadline - time.time()) if deadline else 1e9
        # inline 600M-row generation alone measured ~900s on the 1-core
        # host; only start the rung when it can actually finish
        if budget > 1300:
            _bench_sf100(platform, mem_bw)
        else:
            log(f"skipping SF=100 rung ({budget:.0f}s left < 1300s)")


def mode_sched():
    """Open-loop concurrent-sessions scenario: N statement arrivals at a
    fixed rate (arrivals don't wait for completions — the "millions of
    users" shape) over ONE shared table, mixing identical and different
    aggregates, all through the device admission scheduler.  Reports
    coalesce rate, cross-query fusion rate, and p50/p99 schedWait."""
    import threading

    # the scenario models the 8-vdev mesh: request the virtual devices
    # BEFORE the first jax/backend import (a 1-device CPU env would
    # otherwise run the whole scenario — and its per-link transfer
    # attribution, which needs chip peers to exist — on one chip)
    if "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()

    from tidb_tpu.jaxcache import place_jax_compile_cache
    from tidb_tpu.session import Domain, Session

    place_jax_compile_cache()
    n_stmts = int(os.environ.get("BENCH_SCHED_STMTS", "240"))
    rate = float(os.environ.get("BENCH_SCHED_RATE", "400"))  # stmts/s
    rng = np.random.default_rng(7)
    n = 200_000
    dom = Domain()
    s = Session(dom)
    s.execute("create table lineitem (l_quantity bigint, l_discount "
              "bigint, l_extendedprice bigint, l_shipdays bigint)")
    q = rng.integers(1, 50, n)
    d = rng.integers(0, 10, n)
    p = rng.integers(100, 10_000, n)
    sd = rng.integers(0, 2000, n)
    step = 20_000
    for lo in range(0, n, step):
        s.execute("insert into lineitem values " + ",".join(
            f"({a},{b},{c},{e})" for a, b, c, e in
            zip(q[lo:lo + step], d[lo:lo + step], p[lo:lo + step],
                sd[lo:lo + step])))
    # no result-cache short circuit, device launch path pinned open
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    dom.client._platform = lambda: "tpu"
    queries = [
        "select sum(l_extendedprice * l_discount) from lineitem "
        "where l_shipdays >= 730 and l_shipdays < 1095",
        "select count(*) from lineitem where l_discount >= 5",
        "select min(l_extendedprice) from lineitem where l_quantity > 10",
        "select max(l_extendedprice) from lineitem where l_discount < 8",
    ]
    for qq in queries:              # warm: compile once per program
        s.must_query(qq)
    sched = dom.client._sched_obj
    if sched is None:
        log("scheduler did not engage; aborting scenario")
        return
    base = {k: sched.stats()[k] for k in
            ("launches", "coalesced_tasks", "fused_tasks", "tasks_done")}
    # open loop: arrival times are exponential(rate), pre-drawn; each
    # arrival runs on its own session thread regardless of prior
    # completions
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_stmts))
    picks = rng.integers(0, len(queries), n_stmts)
    errors: list = []
    t0 = time.monotonic()

    def run(i):
        delay = t0 + arrivals[i] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            Session(dom).must_query(queries[picks[i]])
        except Exception as e:
            errors.append(repr(e))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_stmts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    elapsed = time.monotonic() - t0
    st = sched.stats()
    tasks = st["tasks_done"] - base["tasks_done"]
    launches = st["launches"] - base["launches"]
    # copscope: p50/p99 now come from the prometheus-text latency
    # histograms (tidb_tpu_sched_wait_ms / _launch_ms) instead of the
    # scheduler's ad-hoc wait ring — same numbers every scrape sees
    from tidb_tpu.utils.metrics import global_registry
    wait_h = global_registry().histogram("tidb_tpu_sched_wait_ms")
    launch_h = global_registry().histogram("tidb_tpu_sched_launch_ms")
    out = {
        "stmts": n_stmts,
        "arrival_rate_per_s": rate,
        "elapsed_s": round(elapsed, 3),
        "errors": len(errors),
        "tasks": tasks,
        "launches": launches,
        "coalesce_rate": round(
            (st["coalesced_tasks"] - base["coalesced_tasks"])
            / max(tasks, 1), 4),
        "fusion_rate": round(
            (st["fused_tasks"] - base["fused_tasks"]) / max(tasks, 1), 4),
        "launch_reduction": round(1.0 - launches / max(tasks, 1), 4),
        "sched_wait_p50_ms": round(wait_h.quantile(0.50), 3),
        "sched_wait_p99_ms": round(wait_h.quantile(0.99), 3),
        "launch_p50_ms": round(launch_h.quantile(0.50), 3),
        "launch_p99_ms": round(launch_h.quantile(0.99), 3),
        "window_waits": st["window_waits"],
        # window feedback + HBM-budget admission (analysis/copcost):
        # hold hit-rate and the static footprint of the last launch,
        # for cross-run comparison against --cost-report predictions
        "window_hits": st.get("window_hits", 0),
        "budget_deferrals": st.get("budget_deferrals", 0),
        "last_launch_bytes": st.get("last_launch_bytes", 0),
        # buffer donation (analysis/lifetime): batched-stack and
        # streamed-batch launches that aliased inputs into outputs
        "donated_launches": st.get("donated_launches", 0),
        "donated_bytes": st.get("donated_bytes", 0),
        # per-link transfer attribution (shardflow, parallel/topology):
        # statically-classified collective bytes of every served task —
        # the ROADMAP multi-host success metric's static half (under the
        # declared tidb_tpu_topology_hosts view; single-host => dci 0)
        "transfer_breakdown": {
            "ici": st.get("transfer_ici_bytes", 0),
            "dci": st.get("transfer_dci_bytes", 0),
        },
    }
    out["trace_overhead"] = _sched_trace_overhead_scenario(dom, s, queries)
    out["trace_overhead_pct"] = \
        out["trace_overhead"]["trace_overhead_pct"]
    out["memwatch"] = _sched_memwatch_scenario(dom, s, sched, queries)
    out["rc"] = _sched_rc_scenario(dom, s, sched, queries[0])
    out["chaos"] = _sched_chaos_scenario(dom, s, sched, queries)
    out["stress"] = _sched_stress_scenario()
    out["podshare"] = _sched_podshare_scenario(sched)
    log("sched-concurrent:", json.dumps(out))
    os.makedirs(DATA_DIR, exist_ok=True)
    with open(SCHED_PATH, "w") as f:
        json.dump(out, f)


def _sched_trace_overhead_scenario(dom, s, queries, n=60, rounds=3):
    """copscope overhead guard: the same sequential statement loop with
    tracing OFF vs ON (tidb_tpu_trace), best-of-rounds to shed noise.
    The acceptance bound on this scenario is trace_overhead_pct <= 5 —
    span recording is a tuple append under a leaf lock, so anything
    above noise means a regression on the hot path."""
    def run_loop():
        t0 = time.monotonic()
        for i in range(n):
            s.must_query(queries[i % len(queries)])
        return time.monotonic() - t0

    s.execute("set global tidb_tpu_trace = 0")
    run_loop()                              # warm both code paths
    off = min(run_loop() for _ in range(rounds))
    s.execute("set global tidb_tpu_trace = 1")
    run_loop()
    on = min(run_loop() for _ in range(rounds))
    pct = (on - off) / max(off, 1e-9) * 100.0
    return {
        "stmts_per_round": n,
        "off_s": round(off, 4),
        "on_s": round(on, 4),
        "trace_overhead_pct": round(pct, 2),
        # flight-recorder retention state after the traced rounds
        "recorder": dom.flight_recorder.stats(),
    }


def _sched_memwatch_scenario(dom, s, sched, queries, n=32, rounds=2):
    """memwatch rung (copgauge, ISSUE 14): the device-memory plane
    under the mixed query loop — ledger watermark vs the admission
    budget, per-digest HBM prediction error p50/p99 (the mem_factor
    calibration state), and the ledger-overhead guard: the same loop with the ledger off vs
    on, acceptance <= 5% (ledger accounting is weakref bookkeeping +
    one memoized memory-analysis lookup per launch)."""
    def run_loop():
        t0 = time.monotonic()
        for i in range(n):
            s.must_query(queries[i % len(queries)])
        return time.monotonic() - t0

    # interleaved off/on pairs (best-of each): back-to-back rounds
    # cancel the machine drift a sequential off-then-on order picks up
    for flag in ("0", "1"):
        s.execute(f"set global tidb_tpu_hbm_ledger = {flag}")
        run_loop()                          # warm both code paths
    offs, ons = [], []
    for _ in range(rounds):
        s.execute("set global tidb_tpu_hbm_ledger = 0")
        offs.append(run_loop())
        s.execute("set global tidb_tpu_hbm_ledger = 1")
        ons.append(run_loop())
    off, on = min(offs), min(ons)
    pct = (on - off) / max(off, 1e-9) * 100.0
    st = sched.stats()
    hbm = st.get("hbm") or {}
    # per-digest HBM prediction error distribution (copmeter mem loop)
    from tidb_tpu.analysis.calibrate import correction_store
    errs = sorted(
        100.0 * p.get("mem_err", 0.0)
        for p in correction_store().entries_payload().values()
        if p.get("mem_samples", 0) > 0)
    def _pct_of(v, q):
        return round(v[min(int(q * len(v)), len(v) - 1)], 2) if v else None
    return {
        "stmts_per_round": n,
        "ledger_off_s": round(off, 4),
        "ledger_on_s": round(on, 4),
        "ledger_overhead_pct": round(pct, 2),
        "watermark_bytes": hbm.get("watermark_bytes", 0),
        "resident_bytes": hbm.get("resident_bytes", 0),
        "budget_bytes": st.get("hbm_budget", 0),
        "watermark_vs_budget": round(
            hbm.get("watermark_bytes", 0)
            / max(st.get("hbm_budget", 0), 1), 6),
        "measured_launches": hbm.get("measured_launches", 0),
        "negative_events": hbm.get("negative_events", 0),
        "mem_err_digests": len(errs),
        "mem_err_p50_pct": _pct_of(errs, 0.50),
        "mem_err_p99_pct": _pct_of(errs, 0.99),
    }


def _sched_rc_scenario(dom, s, sched, query):
    """Resource-control isolation scenario (rc/): one RU-exhausted
    group and one unlimited group submit the same query concurrently;
    admission-time enforcement must let the unlimited group's launches
    proceed while the starved group's tasks hold at the drain.  Reports
    per-group launch counts and the isolation ratio."""
    import threading

    from tidb_tpu.session import Session

    n_each = int(os.environ.get("BENCH_RC_STMTS", "16"))
    s.execute("create resource group bench_starved RU_PER_SEC = 1")
    s.execute("create resource group bench_free RU_PER_SEC = 0")
    starved = dom.resource_groups.get("bench_starved")
    starved.bucket.force_debit(1e9)     # exhausted for the whole run
    saved_deadline = sched.rc_max_queue_s
    sched.rc_max_queue_s = 3.0          # fail starved waiters quickly
    base = {g: dict(st) for g, st in sched.stats()["groups"].items()}
    results = {"bench_starved": [], "bench_free": []}

    def run(group):
        sess = Session(dom)
        sess.execute(f"set resource group {group}")
        try:
            sess.must_query(query)
            results[group].append("ok")
        except Exception as e:
            results[group].append(type(e).__name__)

    threads = [threading.Thread(target=run, args=(g,))
               for g in ("bench_starved", "bench_free")
               for _ in range(n_each)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    sched.rc_max_queue_s = saved_deadline
    groups = sched.stats()["groups"]

    def served(name):
        b = base.get(name, {}).get("tasks", 0)
        return groups.get(name, {}).get("tasks", 0) - b

    starved_n, free_n = served("bench_starved"), served("bench_free")
    return {
        "stmts_per_group": n_each,
        "starved_launches": starved_n,
        "free_launches": free_n,
        "isolation_ratio": round(free_n / max(starved_n, 1), 2),
        "starved_outcomes": {o: results["bench_starved"].count(o)
                             for o in set(results["bench_starved"])},
        "free_ok": results["bench_free"].count("ok"),
        "throttled": groups.get("bench_starved", {}).get("throttled", 0),
        "rc_exhausted": sched.stats().get("rc_exhausted", 0),
    }


def _sched_chaos_scenario(dom, s, sched, queries):
    """Chaos rung (faultline): sweep injected transient launch-fault
    rates through the supervised drain and record completion rate, p99
    sched wait, recovery counters, and correctness (ZERO wrong results
    is the invariant) per rung — then one targeted poison rung proving
    the breaker quarantine + host-oracle degradation end to end."""
    import threading

    from tidb_tpu import faults
    from tidb_tpu.faults import FaultPlan, FaultRule
    from tidb_tpu.session import Session

    n_stmts = int(os.environ.get("BENCH_CHAOS_STMTS", "36"))
    rates = [float(r) for r in os.environ.get(
        "BENCH_CHAOS_RATES", "0.05,0.2").split(",")]
    expected = {q: sorted(map(repr, s.must_query(q))) for q in queries}
    mu = threading.Lock()

    def run_round(n):
        counts = {"ok": 0, "wrong": 0, "failed": 0}

        def run(i):
            q = queries[i % len(queries)]
            try:
                got = sorted(map(repr, Session(dom).must_query(q)))
            except Exception:   # noqa: BLE001 counted, not raised
                with mu:
                    counts["failed"] += 1
                return
            with mu:
                counts["ok" if got == expected[q] else "wrong"] += 1

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        return counts

    rungs = []
    try:
        for rate in rates:
            faults.install(FaultPlan.parse(
                f"seed=7,launch:transient:{rate}"))
            base = sched.stats()
            t0 = time.monotonic()
            counts = run_round(n_stmts)
            st = sched.stats()
            rungs.append({
                "fault_rate": rate,
                "stmts": n_stmts,
                "elapsed_s": round(time.monotonic() - t0, 3),
                "completion_rate": round(counts["ok"] / n_stmts, 4),
                "wrong_results": counts["wrong"],
                "failed": counts["failed"],
                "injected": (st["faults"] or {}).get("total_injected", 0),
                "retried_launches": st["retried_launches"]
                - base["retried_launches"],
                "sched_wait_p99_ms": st["wait_p99_ms"],
            })
            faults.clear()

        # targeted poison rung: one query's digest fails forever; the
        # breaker must open and the host oracle must keep serving it
        sched._digest_ns.clear()
        Session(dom).must_query(queries[0])
        dig = next(iter(sched._digest_ns), None)
        poison = {"skipped": "no digest observed"}
        if dig is not None:
            faults.install(FaultPlan(
                [FaultRule("launch", "poison", match=dig)], seed=7))
            base = sched.stats()
            d0 = dom.client.degraded
            # sequential: each statement observes the breaker state the
            # previous one left — N failures trip it OPEN, then every
            # subsequent identical statement degrades to the host oracle
            counts = {"ok": 0, "wrong": 0, "failed": 0}
            for _ in range(12):
                try:
                    got = sorted(map(repr,
                                     Session(dom).must_query(queries[0])))
                except Exception:   # noqa: BLE001 counted, not raised
                    counts["failed"] += 1
                    continue
                counts["ok" if got == expected[queries[0]]
                       else "wrong"] += 1
            st = sched.stats()
            poison = {
                "stmts": 12,
                "ok": counts["ok"],
                "wrong_results": counts["wrong"],
                "failed": counts["failed"],
                "quarantined": st["quarantined"] - base["quarantined"],
                "bisected": st["bisected_launches"]
                - base["bisected_launches"],
                "degraded": dom.client.degraded - d0,
                "breaker": (st["breaker"] or {}).get(dig, {}),
            }
            # copforge: a poisoned digest's breaker state must NOT be
            # persisted into the warm manifest — quarantine laundering
            # through a restart would re-crash a healthy process.
            # laundered == 0 is the invariant.
            from tidb_tpu.compilecache import compile_cache
            poison["quarantine"] = compile_cache().quarantine_report()
        return {"rates": rungs, "poison": poison}
    finally:
        faults.clear()
        sched.breaker.reset()


def _sched_stress_scenario():
    """stress rung (copmeter, ISSUE 10): ~1k open-loop concurrent
    sessions over a mixed corpus (dense/SORT/SEGMENT/rows/shuffle)
    across 4 resource groups with the PR 8 chaos plane armed — p50/p99
    sched wait, fusion rate, RU fairness (max/min per-group completion
    ratio), completion rate, and calibrated-pricing error land as
    first-class BENCH JSON metrics.  Own Domain/tables; the process-
    wide per-mesh scheduler is shared with the rungs above, so deltas
    are taken inside the harness."""
    from tidb_tpu.testing.stress import (build_stress_domain,
                                         run_stress_harness)
    n = int(os.environ.get("BENCH_STRESS_SESSIONS", "1000"))
    rate = float(os.environ.get("BENCH_STRESS_RATE", "400"))
    dom, _s = build_stress_domain(n_rows=60_000)
    out = run_stress_harness(dom, n_sessions=n, rate_per_s=rate)
    # locksan sub-rung is deadline-aware: on a degraded/short run
    # (small BENCH_DEADLINE) skip it rather than blow the budget —
    # the tier-1 sanitizer smoke covers correctness either way
    remaining = T0 + float(os.environ.get("BENCH_DEADLINE", "3300")) \
        - time.time()
    if remaining > 90:
        out.update(_locksan_overhead_scenario())
    else:
        out["locksan_skipped"] = round(remaining, 1)
    log("stress:", json.dumps(out))
    return out


def _locksan_overhead_scenario(n_sessions=64, rounds=3):
    """copsan overhead guard (ISSUE 17): the same small open-loop
    harness over a sanitizer-off vs sanitizer-armed domain, best of
    interleaved rounds to cancel machine drift.  The sanitizer only
    wraps locks allocated while armed, so one domain of each flavor is
    built up front and the timed region is the harness alone (the
    steady-state cost, which is what the ≤5% acceptance bounds; the
    process-wide per-mesh scheduler predates both builds, so this
    measures domain-lock instrumentation + the factory patch — the
    fresh-process smoke in tests/test_concurrency.py covers scheduler
    locks).  Acceptance: locksan_overhead_pct <= 5 and ZERO novel
    edges (the static graph stays a superset of the harness's runtime
    behavior)."""
    from tidb_tpu.testing.stress import (build_stress_domain,
                                         run_stress_harness)
    from tidb_tpu.utils import locksan

    def run_once(dom):
        t0 = time.monotonic()
        run_stress_harness(dom, n_sessions=n_sessions, rate_per_s=400.0)
        return time.monotonic() - t0

    locksan.disarm()
    dom_off, _s = build_stress_domain(n_rows=20_000)
    san = locksan.arm()
    dom_on, _s = build_stress_domain(n_rows=20_000)
    # the shared scheduler's busy-retry sleep is the dominant (and
    # nondeterministic) term at 32 sessions — null it so the timed
    # region is CPU-bound and the off/on delta is the lock cost, not
    # backoff jitter (same discipline as the tier-1 stress tests)
    sched = dom_off.client._scheduler()
    saved_sleep = sched._retry_sleep
    sched._retry_sleep = lambda sec: None
    try:
        # both sides run with the factories patched, so stray runtime
        # allocations weigh on off and on equally; calibration keeps
        # learning across runs (each run is faster than the last for
        # the first few), so warm BOTH sides twice and alternate the
        # order each round — best-of then lands both at steady state
        for _ in range(2):
            run_once(dom_off)
            run_once(dom_on)
        offs, ons = [], []
        for i in range(rounds):
            pair = ((dom_off, offs), (dom_on, ons))
            for dom, acc in (pair if i % 2 == 0 else pair[::-1]):
                acc.append(run_once(dom))
    finally:
        sched._retry_sleep = saved_sleep
        locksan.disarm()
    off, on = min(offs), min(ons)
    # per-round paired deltas (adjacent runs share drift state), median
    # across rounds: the true lock cost here is ~100 wrapped acquires
    # (≈0), so the guard is sized to catch a REAL instrumentation
    # regression, not the harness's run-to-run jitter
    pcts = sorted((b - a) / max(a, 1e-9) * 100.0
                  for a, b in zip(offs, ons))
    pct = pcts[len(pcts) // 2]
    st = san.stats()
    return {
        "locksan_off_s": round(off, 4),
        "locksan_on_s": round(on, 4),
        "locksan_overhead_pct": round(pct, 2),
        "locksan_acquisitions": st.get("acquisitions", 0),
        "locksan_edges_observed": st.get("edges_observed", 0),
        "locksan_novel_edges": len(locksan.reports()),
        "locksan_ok": bool(pct <= 5.0 and not locksan.reports()),
    }


def _sched_podshare_scenario(sched):
    """podshare rung (coplace, ISSUE 16): two in-process Domains — the
    tier-1 model of two server processes — join one coordination store
    and share ONE RU_PER_SEC.  Reports the combined admitted RU rate of
    the limited group against the declared budget (the acceptance bound
    is 1.25x), the cross-process compile picture (claims won/denied,
    peer warm-pool adoptions), calibrated-pricing error after the
    traffic, and a mid-run store-kill sub-check: every in-flight
    statement completes, zero failures, both members degrade to local
    slices and rejoin."""
    import threading

    from tidb_tpu.pd import reset_pd
    from tidb_tpu.session import Domain, Session

    budget = float(os.environ.get("BENCH_POD_RU_PER_S", "600"))
    t_run = float(os.environ.get("BENCH_POD_SECONDS", "4"))
    n_rows = 50_000
    rng = np.random.default_rng(16)
    reset_pd()                       # fresh plane for the rung

    def make_domain():
        dom = Domain()
        s = Session(dom)
        s.execute("create table pod_t (a bigint, b bigint)")
        a = rng.integers(1, 50, n_rows)
        b = rng.integers(0, 10, n_rows)
        step = 10_000
        for lo in range(0, n_rows, step):
            s.execute("insert into pod_t values " + ",".join(
                f"({x},{y})" for x, y in
                zip(a[lo:lo + step], b[lo:lo + step])))
        s.execute(f"create resource group bench_pod "
                  f"RU_PER_SEC = {int(budget)}")
        s.execute("set resource group bench_pod")
        s.execute("set global tidb_tpu_result_cache_entries = 0")
        s.execute("set global tidb_tpu_pd = 1")
        dom.client._platform = lambda: "tpu"
        return dom, s

    dom_a, s_a = make_domain()
    dom_b, s_b = make_domain()
    q = "select sum(a*b), count(*) from pod_t where b < 7"
    s_a.must_query(q)                # warm both programs + attach pd
    s_b.must_query(q)
    ca, cb = dom_a.pd, dom_b.pd
    for c in (ca, cb):
        c.tick(force=True)
    ca.tick(force=True)              # a folds b's quota report back in
    # drain the initial burst allowance so the measured window is
    # steady-state refill, not stored tokens
    for dom in (dom_a, dom_b):
        bkt = dom.resource_groups.get("bench_pod").bucket
        bal = bkt.balance
        if bal > 0:
            bkt.force_debit(bal)
    base_rus = sched.stats()["groups"].get("bench_pod", {}).get("rus", 0.0)
    counts = {"a": 0, "b": 0}
    errors: list = []
    stop = time.monotonic() + t_run

    def run(name, dom):
        sess = Session(dom)
        sess.execute("set resource group bench_pod")
        while time.monotonic() < stop:
            try:
                sess.must_query(q)
                counts[name] += 1
            except Exception as e:
                errors.append(repr(e))

    t0 = time.monotonic()
    threads = [threading.Thread(target=run, args=("a", dom_a)),
               threading.Thread(target=run, args=("b", dom_b))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    elapsed = time.monotonic() - t0
    rus = sched.stats()["groups"].get("bench_pod", {}).get("rus", 0.0) \
        - base_rus
    combined = rus / max(elapsed, 1e-9)
    # calibrated-pricing error of the rung's digests (copmeter feedback
    # accumulated during the traffic above)
    from tidb_tpu.analysis.calibrate import correction_store
    calib_err = correction_store().stats()["mean_err_pct"]
    # ---- store-kill sub-check (acceptance d) --------------------- #
    degraded_before = ca.member.degraded_total + cb.member.degraded_total
    ca.store.backend.down = True
    kill_failures = 0
    kill_stmts = 0
    for sess in (s_a, s_b):
        for _ in range(3):
            kill_stmts += 1
            try:
                sess.must_query(q)
            except Exception:
                kill_failures += 1
    for c in (ca, cb):
        c.tick(force=True)
    degraded = (ca.member.degraded, cb.member.degraded)
    ca.store.backend.down = False
    for c in (ca, cb):
        c.tick(force=True)
    rejoined = ca.member.rejoins + cb.member.rejoins
    out = {
        "budget_ru_per_s": budget,
        "combined_ru_per_s": round(combined, 1),
        "budget_ratio": round(combined / max(budget, 1e-9), 3),
        "within_1_25x": combined <= 1.25 * budget,
        "stmts": dict(counts),
        "errors": len(errors),
        "quota_shares": {"a": ca.quota.shares.get("bench_pod", 0.0),
                         "b": cb.quota.shares.get("bench_pod", 0.0)},
        "claims": ca.registry.claims + cb.registry.claims,
        "claim_denials": ca.registry.claim_denials
        + cb.registry.claim_denials,
        "peer_warm": ca.registry.peer_warm + cb.registry.peer_warm,
        "calib_err_pct": calib_err,
        "storekill": {
            "stmts": kill_stmts,
            "failures": kill_failures,
            "degraded": list(degraded),
            "degraded_total_delta":
                ca.member.degraded_total + cb.member.degraded_total
                - degraded_before,
            "rejoins": rejoined,
        },
    }
    # detach the rung's members so later rungs see a quiet plane
    for s in (s_a, s_b):
        s.execute("set global tidb_tpu_pd = 0")
        s.must_query(q)
    reset_pd()
    log("podshare:", json.dumps(out))
    return out


def _median_times(fn, iters):
    ts = []
    for _ in range(iters):
        t = time.time()
        fn()
        ts.append(time.time() - t)
    return float(np.median(ts))


def _q6_dag(q1_cols, ix1):
    from tidb_tpu import copr
    from tidb_tpu.copr import dag as D
    from tidb_tpu.expr import ColumnRef
    from tidb_tpu.expr import builders as B
    from tidb_tpu.types import dtypes as dt
    r = lambda n: ColumnRef(q1_cols[ix1[n]].dtype, ix1[n], n)
    scan = D.TableScan(tuple(range(len(q1_cols))),
                       tuple(c.dtype for c in q1_cols))
    sel = D.Selection(scan, (
        B.compare("ge", r("l_shipdate"), B.lit("1994-01-01", dt.date())),
        B.compare("lt", r("l_shipdate"), B.lit("1995-01-01", dt.date())),
        B.between(r("l_discount"), B.decimal_lit("0.05"),
                  B.decimal_lit("0.07")),
        B.compare("lt", r("l_quantity"), B.decimal_lit("24"))))
    rev = B.arith("mul", r("l_extendedprice"), r("l_discount"))
    return D.Aggregation(sel, (),
                         (copr.AggDesc(copr.AggFunc.SUM, rev,
                                       copr.sum_out_dtype(rev.dtype)),
                          copr.AggDesc(copr.AggFunc.COUNT, None,
                                       dt.bigint(False))),
                         D.GroupStrategy.SCALAR)


# Q19-like predicate-heavy rung (BASELINE config 3): three OR'd
# conjunctive clauses over quantity ranges x shipmode sets x shipinstruct
def _q19_clauses(cols, ix):
    md = cols[ix["l_shipmode"]].dictionary
    sd = cols[ix["l_shipinstruct"]].dictionary
    air, regair = md.code_of("AIR"), md.code_of("REG AIR")
    fob, mail = md.code_of("FOB"), md.code_of("MAIL")
    ship_, truck = md.code_of("SHIP"), md.code_of("TRUCK")
    dip = sd.code_of("DELIVER IN PERSON")
    return (air, regair, fob, mail, ship_, truck, dip)


def _q19_dag(cols, ix):
    from tidb_tpu import copr
    from tidb_tpu.copr import dag as D
    from tidb_tpu.expr import ColumnRef, Const
    from tidb_tpu.expr import builders as B
    from tidb_tpu.types import dtypes as dt
    air, regair, fob, mail, ship_, truck, dip = _q19_clauses(cols, ix)
    r = lambda n: ColumnRef(cols[ix[n]].dtype, ix[n], n)
    sc = lambda c: Const(cols[ix["l_shipmode"]].dtype, c)
    qty = r("l_quantity")
    clause = lambda qlo, qhi, modes: B.logic(
        "and", B.logic("and",
                       B.between(qty, B.decimal_lit(str(qlo)),
                                 B.decimal_lit(str(qhi))),
                       B.in_list(r("l_shipmode"), [sc(m) for m in modes])),
        B.compare("eq", r("l_shipinstruct"),
                  Const(cols[ix["l_shipinstruct"]].dtype, dip)))
    pred = B.logic("or", B.logic("or",
                                 clause(1, 11, (air, regair)),
                                 clause(10, 20, (fob, mail))),
                   clause(20, 30, (ship_, truck)))
    scan = D.TableScan(tuple(range(len(cols))),
                       tuple(c.dtype for c in cols))
    sel = D.Selection(scan, (pred,))
    rev = B.arith("mul", r("l_extendedprice"),
                  B.arith("sub", B.decimal_lit("1"), r("l_discount")))
    return D.Aggregation(sel, (),
                         (copr.AggDesc(copr.AggFunc.SUM, rev,
                                       copr.sum_out_dtype(rev.dtype)),
                          copr.AggDesc(copr.AggFunc.COUNT, None,
                                       dt.bigint(False))),
                         D.GroupStrategy.SCALAR)


def np_q19(cols, ix):
    air, regair, fob, mail, ship_, truck, dip = _q19_clauses(cols, ix)
    qty = cols[ix["l_quantity"]].data
    mode = cols[ix["l_shipmode"]].data
    inst = cols[ix["l_shipinstruct"]].data
    price = cols[ix["l_extendedprice"]].data
    disc = cols[ix["l_discount"]].data
    c1 = (qty >= 100) & (qty <= 1100) & ((mode == air) | (mode == regair))
    c2 = (qty >= 1000) & (qty <= 2000) & ((mode == fob) | (mode == mail))
    c3 = (qty >= 2000) & (qty <= 3000) & ((mode == ship_) | (mode == truck))
    m = (c1 | c2 | c3) & (inst == dip)
    return int((price[m].astype(np.int64) * (100 - disc[m])).sum()), int(m.sum())


def _rollup_dag(cols, ix, dense=False):
    from tidb_tpu import copr
    from tidb_tpu.copr import dag as D
    from tidb_tpu.expr import ColumnRef
    from tidb_tpu.types import dtypes as dt
    rf = ColumnRef(cols[ix["l_returnflag"]].dtype, ix["l_returnflag"], "rf")
    ls = ColumnRef(cols[ix["l_linestatus"]].dtype, ix["l_linestatus"], "ls")
    qty = ColumnRef(cols[ix["l_quantity"]].dtype, ix["l_quantity"], "qty")
    scan = D.TableScan(tuple(range(len(cols))),
                       tuple(c.dtype for c in cols))
    n_base = len(cols)
    ex = D.Expand(scan, (rf, ls), 3)
    krf = ColumnRef(rf.dtype.with_nullable(True), n_base, "rf")
    kls = ColumnRef(ls.dtype.with_nullable(True), n_base + 1, "ls")
    gid = ColumnRef(dt.bigint(False), n_base + 2, "gid")
    aggs = (copr.AggDesc(copr.AggFunc.SUM, qty,
                         copr.sum_out_dtype(qty.dtype)),
            copr.AggDesc(copr.AggFunc.COUNT, None, dt.bigint(False)))
    from tidb_tpu.copr.aggregate import GroupKeyMeta
    if dense:
        # DENSE + bounded gid: the shape the TPU per-level Expand
        # execution keys on (copr/exec.py agg_states) — never
        # materializes levels×n, which OOM-crashed the v5e at SF=10
        drf = cols[ix["l_returnflag"]].dictionary
        dls = cols[ix["l_linestatus"]].dictionary
        sizes = (len(drf) + 1, len(dls) + 1, 3)
        agg = D.Aggregation(ex, (krf, kls, gid), aggs,
                            D.GroupStrategy.DENSE, domain_sizes=sizes)
        meta = [GroupKeyMeta(krf.dtype, sizes[0], drf),
                GroupKeyMeta(kls.dtype, sizes[1], dls),
                GroupKeyMeta(gid.dtype, sizes[2])]
        return agg, meta
    # SORT measures faster on the virtual CPU mesh (host-side merge
    # avoids the 8-device psum dispatch overhead, a harness artifact)
    agg = D.Aggregation(ex, (krf, kls, gid), aggs,
                        D.GroupStrategy.SORT, group_capacity=64)
    meta = [GroupKeyMeta(krf.dtype, 0, cols[ix["l_returnflag"]].dictionary),
            GroupKeyMeta(kls.dtype, 0, cols[ix["l_linestatus"]].dictionary),
            GroupKeyMeta(gid.dtype, 0)]
    return agg, meta


def np_rollup(cols, ix):
    """Oracle: grouping-sets counts/sums over (returnflag, linestatus)."""
    rf = cols[ix["l_returnflag"]].data.astype(np.int64)
    ls = cols[ix["l_linestatus"]].data.astype(np.int64)
    qty = cols[ix["l_quantity"]].data
    gid2 = rf * 2 + ls
    out = {}
    c2 = np.bincount(gid2, minlength=6)
    s2 = np.bincount(gid2, weights=qty.astype(np.float64), minlength=6)
    for g in range(6):
        if c2[g]:
            out[(g // 2, g % 2, 0)] = (int(s2[g]), int(c2[g]))
    c1 = np.bincount(rf, minlength=3)
    s1 = np.bincount(rf, weights=qty.astype(np.float64), minlength=3)
    for g in range(3):
        if c1[g]:
            out[(g, None, 1)] = (int(s1[g]), int(c1[g]))
    out[(None, None, 2)] = (int(qty.sum()), len(qty))
    return out


def _bench_one_sf(sf, platform, n_chips, iters, mem_bw):
    import jax

    from __graft_entry__ import _q1_dag
    from tidb_tpu import copr
    from tidb_tpu.copr import dag as D
    from tidb_tpu.copr.aggregate import GroupKeyMeta
    from tidb_tpu.expr import ColumnRef
    from tidb_tpu.parallel.mesh import get_mesh
    from tidb_tpu.store import CopClient, snapshot_from_columns
    from tidb_tpu.types import dtypes as dt

    names, cols = _load_data(sf)
    ix = {n: i for i, n in enumerate(names)}
    n_rows = len(cols[0])
    n_shards = int(os.environ.get("BENCH_SHARDS",
                                  str(max(8, len(jax.devices())))))
    log(f"rows={n_rows} shards={n_shards}")

    mesh = get_mesh()
    q1_names = [n for n in names if n not in
                ("l_partkey", "l_shipmode", "l_shipinstruct")]
    q1_cols = [cols[ix[n]] for n in q1_names]
    ix1 = {n: i for i, n in enumerate(q1_names)}
    snap = snapshot_from_columns(q1_names, q1_cols, n_shards=n_shards)
    client = CopClient(mesh)
    # the bench measures ENGINE throughput: identical repeated dispatches
    # must not short-circuit through the coprocessor result cache
    client._result_cache_cap = 0
    cap = int(os.environ.get("BENCH_DEVICE_MEM_CAP", "0") or 0)
    # CPU fallback caps at 2 GiB so the SF=10 rung exercises the HBM
    # streaming path when the host engine choice does not intercept
    client.device_mem_cap = cap or (12 << 30 if platform != "cpu"
                                    else 2 << 30)
    if snap.row_batches(client.device_mem_cap):
        log(f"table {snap.device_bytes()/2**30:.1f} GiB > cap: streaming")
    agg, meta = _q1_dag(q1_cols, q1_names)

    t = time.time()
    res = client.execute_agg(agg, snap, meta)   # warmup: compile + H2D
    log(f"Q1 warmup (compile+transfer) {time.time()-t:.1f}s")

    def _measure_q1():
        """Interleave engine and numpy-baseline runs so transient host
        contention hits both equally; the ratio of medians is
        contention-fair."""
        et, bt = [], []
        for _ in range(iters):
            t = time.time()
            client.execute_agg(agg, snap, meta)
            et.append(time.time() - t)
            t = time.time()
            np_q1(q1_cols, ix1)
            bt.append(time.time() - t)
        return et, bt

    et, bt = _measure_q1()
    if len(et) >= 3 and float(np.std(et)) > 0.5 * float(np.median(et)):
        log(f"Q1 timing CV high ({np.std(et)/np.median(et):.2f}); re-measuring")
        et, bt = _measure_q1()
    q1_t = float(np.median(et))
    b1 = float(np.median(bt))
    prior = _load_ratio(platform, sf)
    if prior is not None and not (0.5 <= (b1 / q1_t) / prior <= 2.0):
        log(f"Q1 ratio {b1/q1_t:.2f}x shifted >2x from prior {prior:.2f}x; "
            "re-measuring")
        et, bt = _measure_q1()
        q1_t = float(np.median(et))
        b1 = float(np.median(bt))
    _store_ratio(platform, sf, b1 / q1_t)
    q1_rps = n_rows / q1_t / n_chips
    # physical bytes: Q1 touches every q1 column at narrow width
    q1_bytes = sum(c.narrowed().dtype.itemsize for c in q1_cols) * n_rows
    log(f"Q1: {q1_t*1e3:.1f} ms  {q1_rps/1e6:.1f} M rows/s/chip "
        f"({n_chips} chips)  numpy {b1*1e3:.1f} ms  ratio {b1/q1_t:.2f}x  "
        f"{q1_bytes/q1_t/1e9:.1f} GB/s")

    # correctness spot-check vs numpy
    exp = np_q1(q1_cols, ix1)
    res = client.execute_agg(agg, snap, meta)
    got_counts = sorted(int(c) for c in res.columns[-1].data)
    assert got_counts == sorted(v[4] for v in exp.values()), "Q1 mismatch"

    rec = {
        "metric": f"tpch_q1_sf{sf:g}_rows_per_sec_per_chip",
        "value": round(q1_rps, 1),
        "unit": "rows/s",
        "vs_baseline": round(b1 / q1_t, 2),
        "platform": platform,
        "sf": sf,
        "q1_ms": round(q1_t * 1e3, 1),
        "q1_gbps_phys": round(q1_bytes / q1_t / 1e9, 2),
    }
    if mem_bw:
        rec["mem_bw_gbps"] = round(mem_bw, 1)
        rec["q1_roofline_frac"] = round(q1_bytes / q1_t / 1e9 / mem_bw, 3)
    # side rungs are fault-isolated: a failure degrades the record, it
    # must never lose the Q1 rung
    for tag, fn in (("q6", lambda: _rung_q6(client, snap, cols, ix,
                                            q1_cols, ix1, n_rows, iters,
                                            mem_bw)),
                    ("q19", lambda: _rung_q19(client, cols, ix, n_shards,
                                              iters)),
                    ("rollup", lambda: _rung_rollup(
                        client, cols, ix, n_shards, iters,
                        dense=(platform == "tpu"))),
                    ("narrowagg", lambda: _rung_narrowagg(
                        client, cols, ix, n_shards, iters))):
        try:
            rec.update(fn())
        except Exception as e:      # noqa: BLE001 - rung isolation
            log(f"{tag} rung FAILED: {type(e).__name__}: {e}")
            rec[f"{tag}_error"] = f"{type(e).__name__}: {e}"[:200]
    _record(rec)
    log(f"SF {sf:g} result recorded")


def _rung_q6(client, snap, cols, ix, q1_cols, ix1, n_rows, iters, mem_bw):
    q6 = _q6_dag(q1_cols, ix1)
    res6 = client.execute_agg(q6, snap, [])
    exp_rev, exp_cnt = np_q6(cols, ix)
    assert int(res6.columns[0].data[0]) == exp_rev, "Q6 sum mismatch"
    assert int(res6.columns[1].data[0]) == exp_cnt, "Q6 count mismatch"
    q6_t = _median_times(lambda: client.execute_agg(q6, snap, []), iters)
    b6 = _median_times(lambda: np_q6(cols, ix), max(iters // 2, 2))
    q6_cols = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")
    q6_bytes = sum(cols[ix[n]].narrowed().dtype.itemsize
                   for n in q6_cols) * n_rows
    log(f"Q6: {q6_t*1e3:.1f} ms ({n_rows/q6_t/1e6:.0f} M rows/s)  numpy "
        f"{b6*1e3:.1f} ms  ratio {b6/q6_t:.2f}x  {q6_bytes/q6_t/1e9:.1f} GB/s")
    out = {"q6_ms": round(q6_t * 1e3, 1),
           "q6_vs_numpy": round(b6 / q6_t, 2),
           "q6_gbps_phys": round(q6_bytes / q6_t / 1e9, 2)}
    if mem_bw:
        out["q6_roofline_frac"] = round(q6_bytes / q6_t / 1e9 / mem_bw, 3)
    return out


def _rung_q19(client, cols, ix, n_shards, iters):
    from tidb_tpu.store import snapshot_from_columns
    q19_names = ["l_quantity", "l_extendedprice", "l_discount",
                 "l_shipmode", "l_shipinstruct"]
    q19_cols = [cols[ix[n]] for n in q19_names]
    ix19 = {n: i for i, n in enumerate(q19_names)}
    snap19 = snapshot_from_columns(q19_names, q19_cols, n_shards=n_shards)
    q19 = _q19_dag(q19_cols, ix19)
    res19 = client.execute_agg(q19, snap19, [])
    e_rev, e_cnt = np_q19(q19_cols, ix19)
    assert int(res19.columns[0].data[0]) == e_rev, "Q19 sum mismatch"
    assert int(res19.columns[1].data[0]) == e_cnt, "Q19 count mismatch"
    q19_t = _median_times(lambda: client.execute_agg(q19, snap19, []), iters)
    b19 = _median_times(lambda: np_q19(q19_cols, ix19), max(iters // 2, 2))
    log(f"Q19: {q19_t*1e3:.1f} ms  numpy {b19*1e3:.1f} ms  "
        f"ratio {b19/q19_t:.2f}x")
    return {"q19_ms": round(q19_t * 1e3, 1),
            "q19_vs_numpy": round(b19 / q19_t, 2)}


def _rung_rollup(client, cols, ix, n_shards, iters, dense=False):
    from tidb_tpu.store import snapshot_from_columns
    ru_names = ["l_returnflag", "l_linestatus", "l_quantity"]
    ru_cols = [cols[ix[n]] for n in ru_names]
    ixr = {n: i for i, n in enumerate(ru_names)}
    snapr = snapshot_from_columns(ru_names, ru_cols, n_shards=n_shards)
    ragg, rmeta = _rollup_dag(ru_cols, ixr, dense=dense)
    resr = client.execute_agg(ragg, snapr, rmeta)
    expr_ = np_rollup(ru_cols, ixr)
    got = {}
    kc = resr.key_columns
    for i in range(len(kc[0])):
        key = (int(kc[0].data[i]) if kc[0].validity[i] else None,
               int(kc[1].data[i]) if kc[1].validity[i] else None,
               int(kc[2].data[i]))
        got[key] = (int(resr.columns[0].data[i]),
                    int(resr.columns[1].data[i]))
    assert got == expr_, "ROLLUP mismatch"
    ru_t = _median_times(lambda: client.execute_agg(ragg, snapr, rmeta),
                         max(iters // 2, 2))
    bru = _median_times(lambda: np_rollup(ru_cols, ixr),
                        max(iters // 2, 2))
    log(f"ROLLUP: {ru_t*1e3:.1f} ms  numpy {bru*1e3:.1f} ms  "
        f"ratio {bru/ru_t:.2f}x")
    return {"rollup_ms": round(ru_t * 1e3, 1),
            "rollup_vs_numpy": round(bru / ru_t, 2)}


def _rung_narrowagg(client, cols, ix, n_shards, iters):
    """Proven-narrow SUM rung (ISSUE 19): the same scalar decimal SUM
    executed with the single-word int64 state vs the (hi, lo) limb
    pair.  Results must be bit-identical (two's complement exactness);
    the record carries both wall times and the per-state widths copcost
    prices the fusion classes with."""
    import dataclasses

    from tidb_tpu import copr
    from tidb_tpu.analysis.copcost import _agg_state_width
    from tidb_tpu.copr import dag as D
    from tidb_tpu.expr import ColumnRef
    from tidb_tpu.store import snapshot_from_columns
    from tidb_tpu.types import dtypes as dt

    qcol = cols[ix["l_quantity"]]
    snapq = snapshot_from_columns(["l_quantity"], [qcol],
                                  n_shards=n_shards)
    ref = ColumnRef(qcol.dtype, 0, "l_quantity")
    limb = D.Aggregation(
        D.TableScan((0,), (qcol.dtype,)), (),
        (D.AggDesc(D.AggFunc.SUM, ref, copr.sum_out_dtype(qcol.dtype)),
         D.AggDesc(D.AggFunc.COUNT, None, dt.bigint(False))),
        D.GroupStrategy.SCALAR)
    narrow = dataclasses.replace(limb, narrow_sums=(0,))

    res_l = client.execute_agg(limb, snapq, [])
    res_n = client.execute_agg(narrow, snapq, [])
    sums = (res_l.columns[0].to_python()[0], res_n.columns[0].to_python()[0])
    assert sums[0] == sums[1], f"narrow SUM diverged: {sums}"
    assert int(res_l.columns[1].data[0]) == int(res_n.columns[1].data[0])

    it = max(iters // 2, 2)
    t_l = _median_times(lambda: client.execute_agg(limb, snapq, []), it)
    t_n = _median_times(lambda: client.execute_agg(narrow, snapq, []), it)
    wl = _agg_state_width(limb.aggs[0], narrow=False)
    wn = _agg_state_width(limb.aggs[0], narrow=True)
    log(f"NARROWAGG: narrow {t_n*1e3:.1f} ms ({wn} B/state)  limb "
        f"{t_l*1e3:.1f} ms ({wl} B/state)  bit-identical sum={sums[0]}")
    return {"narrowagg_narrow_ms": round(t_n * 1e3, 3),
            "narrowagg_limb_ms": round(t_l * 1e3, 3),
            "narrowagg_state_bytes": {"narrow": wn, "limb": wl},
            "narrowagg_identical": True}


def _bench_sf100(platform, mem_bw):
    """SF=100 Q6-only rung (BASELINE config 4 scale): 600M rows generated
    inline (4 columns, never pickled), aggregated through the engine."""
    from tidb_tpu.parallel.mesh import get_mesh
    from tidb_tpu.store import CopClient, snapshot_from_columns
    from tidb_tpu.testing.tpch import gen_lineitem
    log("=== SF 100 (Q6 only) ===")
    t = time.time()
    names, cols = gen_lineitem(sf=100, columns=SF100_COLS)
    n_rows = len(cols[0])
    log(f"generated inline: {n_rows} rows in {time.time()-t:.1f}s")
    ix = {n: i for i, n in enumerate(names)}
    snap = snapshot_from_columns(names, cols, n_shards=64)
    client = CopClient(get_mesh())
    client._result_cache_cap = 0
    q6 = _q6_dag(cols, ix)
    t = time.time()
    res = client.execute_agg(q6, snap, [])
    log(f"Q6 warmup {time.time()-t:.1f}s")
    exp_rev, exp_cnt = np_q6(cols, ix)
    assert int(res.columns[0].data[0]) == exp_rev, "SF100 Q6 sum mismatch"
    assert int(res.columns[1].data[0]) == exp_cnt, "SF100 Q6 count mismatch"
    q6_t = _median_times(lambda: client.execute_agg(q6, snap, []), 3)
    b6 = _median_times(lambda: np_q6(cols, ix), 2)
    rec = {
        "sf100_only": True,
        "platform": platform,
        "rows": n_rows,
        "q6_ms": round(q6_t * 1e3, 1),
        "q6_rows_per_sec": round(n_rows / q6_t, 1),
        "q6_vs_numpy": round(b6 / q6_t, 2),
    }
    q6_bytes = sum(c.narrowed().dtype.itemsize for c in cols) * n_rows
    rec["q6_gbps_phys"] = round(q6_bytes / q6_t / 1e9, 2)
    if mem_bw:
        rec["q6_roofline_frac"] = round(q6_bytes / q6_t / 1e9 / mem_bw, 3)
    log(f"SF100 Q6: {q6_t*1e3:.0f} ms  numpy {b6*1e3:.0f} ms  "
        f"ratio {b6/q6_t:.2f}x")
    _record(rec)


def np_q1(cols, ix):
    """Single-core numpy oracle/baseline for Q1 (int64 exact path)."""
    ship = cols[ix["l_shipdate"]].data
    mask = ship <= 10471  # 1998-09-02
    f = cols[ix["l_returnflag"]].data
    s = cols[ix["l_linestatus"]].data
    qty = cols[ix["l_quantity"]].data
    price = cols[ix["l_extendedprice"]].data
    disc = cols[ix["l_discount"]].data
    tax = cols[ix["l_tax"]].data
    gid = f.astype(np.int64) * 2 + s
    out = {}
    for g in np.unique(gid[mask]):
        m = mask & (gid == g)
        dp = price[m] * (100 - disc[m])
        ch = dp * (100 + tax[m])
        out[int(g)] = (int(qty[m].sum()), int(price[m].sum()),
                       int(dp.sum()), int(ch.sum()), int(m.sum()))
    return out


def np_q6(cols, ix):
    ship = cols[ix["l_shipdate"]].data
    disc = cols[ix["l_discount"]].data
    qty = cols[ix["l_quantity"]].data
    price = cols[ix["l_extendedprice"]].data
    m = ((ship >= 8766) & (ship < 9131) & (disc >= 5) & (disc <= 7)
         & (qty < 2400))
    return int((price[m].astype(np.int64) * disc[m]).sum()), int(m.sum())


if __name__ == "__main__":
    mode = os.environ.get("BENCH_MODE")
    if mode == "gen":
        mode_gen()
    elif mode == "bench":
        mode_bench()
    elif mode == "sched":
        mode_sched()
    else:
        sys.exit(orchestrate())
