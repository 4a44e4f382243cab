#!/usr/bin/env python3
"""chip_smoke.py: does the SQL -> coprocessor path still start on the chip?

Starts the server the way ``python -m tidb_tpu serve`` does, bulk-loads
TPC-H SF10 ``lineitem`` and ``part`` (generated from ``--seed``), makes one
small table over the wire, and issues a fixed set of statements as SQL text
over TCP, each twice (cold, then warm).  Every answer is compared, exactly,
with a plain numpy/``Decimal`` oracle over the same generated columns.  Then
it reads ``/sched`` and fails unless every statement really launched on the
device: no host degradation, no quarantine, no refused fusion, no
uncacheable program.

Needs a TPU: with none it exits non-zero in the first second and prints no
result.  One process owns the chip; nothing it spawns needs JAX.  The last
line of standard output is one JSON object, ``{"ok": true, "device": ...}``.

The printed milliseconds are a record, not a benchmark: one cold and one
warm reading per statement.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys
import time
import urllib.request
from decimal import ROUND_HALF_UP, Decimal

import numpy as np

SMOKE_SF = 10.0
# the lineitem columns the statements below read (all of them go to HBM)
LINEITEM_COLUMNS = ["l_orderkey", "l_partkey", "l_quantity",
                    "l_extendedprice", "l_discount", "l_returnflag",
                    "l_linestatus", "l_shipdate"]
SMALL_ROWS = 2000
# a broadcast lookup join with a group-by: lineitem probes, part (unique
# key) is built, 25 brand groups.  Not TPC-H Q19, though the records
# before PR 25 called it "Q19-shape": Q14 and Q19 themselves run in the
# benchmark's cell tpch1x1.partjoin
BCAST_JOIN_TAG = "bcast_join_groupby"
HNDV_SQL = ("select l_partkey, sum(l_quantity) from lineitem "
            "group by l_partkey order by 2 desc, 1 limit 10")
SMALL_SQL = "select count(*), sum(v) from smoke_kv where grp < 5"
CLUSTER_SQL = "select * from information_schema.cluster_info"
# /sched counters that must read zero after the run: each one is a way
# for a statement to be answered without the device doing the work
ZERO_COUNTERS = ("quarantined", "bisected_launches", "retried_launches",
                 "warm_failures", "budget_rejects", "fused_refused",
                 "batched_refused", "oom_faults", "join_host_fallbacks")


class SmokeFailure(AssertionError):
    pass


def log(*a) -> None:
    print("[chip_smoke]", *a, flush=True)


def _days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def _dec(raw: int, scale: int) -> Decimal:
    return Decimal(int(raw)).scaleb(-scale)


def _group_sums(keys: np.ndarray, values: np.ndarray, n: int) -> np.ndarray:
    """Exact int64 SUM(values) per key in [0, n): bincount accumulates
    in float64, so the values go through in 24-bit halves, each of whose
    group sums stays far below 2^53."""
    lo = np.bincount(keys, weights=(values & 0xFFFFFF), minlength=n)
    hi = np.bincount(keys, weights=(values >> 24), minlength=n)
    return (hi.astype(np.int64) << 24) + lo.astype(np.int64)


# --------------------------------------------------------------------- #
# the oracle: plain numpy / Decimal over the generated columns, no JAX
# --------------------------------------------------------------------- #

def oracle(li: dict, part: dict, small: np.ndarray) -> dict:
    """Expected rows per statement, in the text form the wire returns."""
    ship, qty = li["l_shipdate"].data, li["l_quantity"].data
    price, disc = li["l_extendedprice"].data, li["l_discount"].data
    pk, ok = li["l_partkey"].data, li["l_orderkey"].data
    out = {}

    m = ((ship >= _days(1994, 1, 1)) & (ship < _days(1995, 1, 1))
         & (disc >= 5) & (disc <= 7) & (qty < 2400))
    out["q6"] = [(_dec((price[m] * disc[m]).sum(), 4),)]

    rf, ls = li["l_returnflag"], li["l_linestatus"]
    m = ship <= _days(1998, 9, 2)
    gid = (rf.data.astype(np.int64) * len(ls.dictionary) + ls.data)[m]
    ng = len(rf.dictionary) * len(ls.dictionary)
    cnt = np.bincount(gid, minlength=ng)
    sums = [_group_sums(gid, li[c].data[m], ng)
            for c in ("l_quantity", "l_extendedprice", "l_discount")]
    q1 = []
    for g in np.nonzero(cnt)[0]:
        avg = (_dec(sums[2][g], 2) / int(cnt[g])).quantize(
            Decimal("0.000001"), ROUND_HALF_UP)
        q1.append((rf.dictionary.values[g // len(ls.dictionary)],
                   ls.dictionary.values[g % len(ls.dictionary)],
                   _dec(sums[0][g], 2), _dec(sums[1][g], 2), avg,
                   int(cnt[g])))
    out["q1"] = sorted(q1)

    brand = part["p_brand"]
    assert (part["p_partkey"].data
            == np.arange(1, len(brand.data) + 1)).all()
    m = qty < 1000
    bsum = _group_sums(brand.data[pk[m] - 1].astype(np.int64), price[m],
                       len(brand.dictionary))
    out[BCAST_JOIN_TAG] = sorted((brand.dictionary.values[b], _dec(s, 2))
                         for b, s in enumerate(bsum) if s)

    psum = _group_sums(pk, qty, int(pk.max()) + 1)
    top = np.lexsort((np.arange(len(psum)), -psum))[:10]
    out["hndv"] = [(int(k), _dec(psum[k], 2)) for k in top]

    kth = np.partition(price, len(price) - 10)[len(price) - 10]
    cand = np.nonzero(price >= kth)[0]
    order = np.lexsort((ok[cand], -price[cand]))[:10]
    out["topn"] = [(int(ok[i]), _dec(price[i], 2)) for i in cand[order]]

    m = small[:, 1] < 5
    out["small"] = [(int(m.sum()), int(small[m, 2].sum()))]
    return out


def _typed(rows, like) -> list[tuple]:
    """Wire rows are text; coerce each field to the oracle's type."""
    out = []
    for r in rows:
        if len(r) != len(like):
            raise SmokeFailure(f"row width {len(r)} != {len(like)}: {r}")
        out.append(tuple(None if v is None else type(t)(v)
                         for v, t in zip(r, like)))
    return out


# --------------------------------------------------------------------- #
# drive: server, load, statements, answers
# --------------------------------------------------------------------- #

def _sched(status_port: int) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{status_port}/sched", timeout=60) as r:
        return json.load(r)


def _server_ms(c, sql: str):
    """(slower, faster) execution ms of a statement run twice, as the
    server timed them (information_schema.statements_summary): what the
    client-side reading holds beyond these is the wire."""
    want = " ".join(sql.split())
    for n, avg, mx, text in c.query(
            "select exec_count, avg_latency_ms, max_latency_ms, "
            "query_sample_text from information_schema.statements_summary"):
        if " ".join(text.split()) == want and int(n) == 2:
            return float(mx), 2 * float(avg) - float(mx)
    raise SmokeFailure(f"statements_summary has no row run twice for: {want}")


def _peak_hbm() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return max(peaks, default=0)


def _run_twice(c, status_port: int, tag: str, sql: str, want: list) -> dict:
    """One statement over the wire, cold then warm, each answer checked;
    what /sched and the server's own clock say happened meanwhile."""
    strategy = next((r[0] for r in c.query("explain " + sql)
                     if r[0].startswith("agg strategy")), None)
    s0 = _sched(status_port)
    times, regrows = [], []
    for _ in range(2):
        t = time.monotonic()
        rows = c.query(sql)
        times.append((time.monotonic() - t) * 1e3)
        regrows.append(_sched(status_port).get("hndv_agg_regrows", 0))
        got = _typed(rows, want[0])
        if tag == BCAST_JOIN_TAG:
            got = sorted(got)               # no ORDER BY
        if got != want:
            raise SmokeFailure(f"{tag}: wrong answer\n got      {got[:12]}\n "
                               f"expected {want[:12]}")
    s1 = _sched(status_port)
    d0 = s0.get("digest_dispatch_ms", {})
    srv_cold, srv_warm = _server_ms(c, sql)
    r = {"strategy": strategy, "cold_ms": times[0], "warm_ms": times[1],
         "server_cold_ms": srv_cold, "server_warm_ms": srv_warm,
         "rows": len(rows),
         # reruns of the warm statement at a larger group table or a
         # wider sort record: what the first one learned has to hold
         "warm_regrows": regrows[1] - regrows[0],
         "launches": s1.get("launches", 0) - s0.get("launches", 0),
         # programs launched meanwhile (their dispatch time grew)
         "digests": sorted(k for k, v
                           in s1.get("digest_dispatch_ms", {}).items()
                           if v > d0.get(k, 0)),
         "compile_ms": s1["compile_cache"]["compile_ms"]
         - s0["compile_cache"]["compile_ms"],
         "peak_hbm_bytes": _peak_hbm()}
    log(f"{tag:18s} ok  {strategy or 'no agg strategy'}  "
        f"cold={times[0]:.1f}ms warm={times[1]:.1f}ms "
        f"(server cold={srv_cold:.1f}ms warm={srv_warm:.1f}ms)  "
        f"launches={r['launches']} compile={r['compile_ms']:.1f}ms "
        f"peak_hbm={r['peak_hbm_bytes']} digests={r['digests']}")
    return r


def drive(sf: float, seed: int = 0) -> dict:
    """Run the whole smoke at scale factor ``sf`` on whatever mesh JAX
    resolves and return the report.  Raises on any wrong answer.  The
    device-path assertions are ``check_device_path``'s, so a test can run
    this on the CPU mesh for the answers alone."""
    import jax

    from tidb_tpu.__main__ import start_server
    from tidb_tpu.config import load_config
    from tidb_tpu.server.client import Client
    from tidb_tpu.session.catalog import TableInfo
    from tidb_tpu.testing.tpch import (TPCH_PLAN_QUERIES, gen_lineitem,
                                       gen_part)

    statements = [("q6", TPCH_PLAN_QUERIES[0]), ("q1", TPCH_PLAN_QUERIES[1]),
                  (BCAST_JOIN_TAG, TPCH_PLAN_QUERIES[9]),
                  ("hndv", HNDV_SQL),
                  ("topn", TPCH_PLAN_QUERIES[6]), ("small", SMALL_SQL)]
    setup = {}
    cfg = load_config(None)
    cfg.port = cfg.status_port = 0          # ephemeral
    dom, srv, st = start_server(cfg)
    c = None
    try:
        t = time.monotonic()
        li_names, li_cols = gen_lineitem(sf=sf, seed=seed,
                                         columns=LINEITEM_COLUMNS)
        p_names, p_cols = gen_part(sf=sf, seed=seed + 1)
        setup["generate_s"] = time.monotonic() - t
        for name, names, cols in (("lineitem", li_names, li_cols),
                                  ("part", p_names, p_cols)):
            tbl = TableInfo(name, list(names), [x.dtype for x in cols])
            tbl.register_columns(list(cols))
            dom.catalog.create_table("test", tbl)
        log(f"loaded lineitem rows={len(li_cols[0])} "
            f"part rows={len(p_cols[0])} in {setup['generate_s']:.1f}s")

        c = Client("127.0.0.1", srv.port, db="test")
        c.sock.settimeout(900)      # a cold SF10 statement compiles + loads
        c.query("set global tidb_tpu_result_cache_entries = 0")
        t = time.monotonic()
        c.query("analyze table lineitem")
        setup["analyze_s"] = time.monotonic() - t

        # the small table goes the long way: wire -> KV -> lazy columnarize
        rng = np.random.default_rng([seed, 99])
        small = np.stack([np.arange(SMALL_ROWS), rng.integers(0, 10, SMALL_ROWS),
                          rng.integers(-1000, 1000, SMALL_ROWS)], axis=1)
        c.query("create table smoke_kv (id bigint primary key, grp bigint, "
                "v bigint)")
        for lo in range(0, SMALL_ROWS, 500):
            c.query("insert into smoke_kv values " + ",".join(
                f"({a},{b},{v})" for a, b, v in small[lo:lo + 500]))

        t = time.monotonic()
        mesh = dom.client.mesh
        resident = {name: dom.catalog.get_table("test", name)
                    .snapshot().device_cols(mesh)
                    for name in ("lineitem", "part")}
        jax.block_until_ready(resident)
        setup["h2d_s"] = time.monotonic() - t
        log("set-up seconds: " + "  ".join(
            f"{k[:-2]}={v:.1f}" for k, v in setup.items()))

        t = time.monotonic()
        expected = oracle(dict(zip(li_names, li_cols)),
                          dict(zip(p_names, p_cols)), small)
        setup["oracle_s"] = time.monotonic() - t

        results = {tag: _run_twice(c, st.port, tag, sql, expected[tag])
                   for tag, sql in statements}

        cluster = c.query(CLUSTER_SQL)
        devs = jax.devices()
        if [(r[3], int(r[4])) for r in cluster] \
                != [(devs[0].platform, len(devs))]:
            raise SmokeFailure(f"cluster_info {cluster} != jax {devs}")

        from tidb_tpu.copr import nativeops
        return {"sf": sf, "seed": seed, "setup": setup, "results": results,
                "statements": len(statements), "cluster_info": cluster,
                "sched": _sched(st.port),
                "native_hostops": nativeops.available(),
                "mesh_devices": [str(d) for d in mesh.devices.reshape(-1)],
                "lineitem_devices": sorted(
                    str(d) for d in
                    resident["lineitem"][0][0][0].sharding.device_set),
                "bytes_in_use": {
                    str(d): (d.memory_stats() or {}).get("bytes_in_use", 0)
                    for d in mesh.devices.reshape(-1)}}
    finally:
        if c is not None:
            c.close()
        srv.close()
        st.close()
        dom.close()


def check_device_path(rep: dict) -> None:
    """Fail unless the chip, and nothing in its place, answered."""
    s = rep["sched"]
    bad = []
    if rep["cluster_info"][0][3] != "tpu":
        bad.append(f"cluster_info platform {rep['cluster_info'][0][3]!r}")
    need = 2 * rep["statements"]
    if s.get("launches", 0) < need:
        bad.append(f"launches {s.get('launches')} < {need} device "
                   "statements issued")
    for tag, r in rep["results"].items():
        if r["launches"] < 2:
            bad.append(f"{tag}: {r['launches']} launches for a cold and a "
                       "warm run")
    if not rep["results"]["hndv"]["digests"]:
        bad.append("hndv: no program digest gained device time")
    if not s.get("hndv_agg_launches"):
        bad.append("hndv_agg_launches = 0: no launch had a high-NDV "
                   "GROUP BY at its root")
    for tag, r in rep["results"].items():
        if r["warm_regrows"]:
            bad.append(f"{tag}: hndv_agg_regrows moved by "
                       f"{r['warm_regrows']} in the warm run")
    for k in ("degraded", "oom_recovered"):
        if s["client"][k]:
            bad.append(f"client.{k} = {s['client'][k]}")
    for k in ZERO_COUNTERS:
        if s[k]:
            bad.append(f"{k} = {s[k]}")
    if s["breaker"]:
        bad.append(f"breaker not empty: {s['breaker']}")
    if s["compile_cache"]["uncacheable"]:
        bad.append(f"compile_cache.uncacheable = "
                   f"{s['compile_cache']['uncacheable']}")
    if not rep["native_hostops"]:
        bad.append("native hostops library did not build")
    if rep["lineitem_devices"] != sorted(rep["mesh_devices"]):
        bad.append(f"lineitem shards on {rep['lineitem_devices']}, mesh is "
                   f"{rep['mesh_devices']}")
    idle = [d for d, n in rep["bytes_in_use"].items() if n <= 0]
    if idle:
        bad.append(f"devices holding no bytes: {idle}")
    if bad:
        raise SmokeFailure("device path not proven:\n  " + "\n  ".join(bad))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.monotonic()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{devs[0].platform!r}; this script only runs on the chip",
              file=sys.stderr)
        return 2
    import logging

    import jaxlib

    from tidb_tpu.jaxcache import place_jax_compile_cache
    logging.basicConfig(level=logging.INFO, stream=sys.stdout,
                        format="[chip_smoke] %(name)s: %(message)s")
    log("jax compile cache at", place_jax_compile_cache())
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:   # noqa: BLE001 - version string is for the record
        libtpu = "unknown"
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"numpy={np.__version__} python={sys.version.split()[0]}")

    rep = drive(SMOKE_SF, args.seed)
    check_device_path(rep)
    s = rep["sched"]
    log("counters: launches=%d degraded=%d oom_recovered=%d %s "
        "uncacheable=%d breaker=%s" % (
            s["launches"], s["client"]["degraded"],
            s["client"]["oom_recovered"],
            " ".join(f"{k}={s[k]}" for k in ZERO_COUNTERS + (
                "hndv_agg_launches", "hndv_agg_regrows",
                "hndv_host_topn_launches")),
            s["compile_cache"]["uncacheable"], s["breaker"]))
    log(f"compile_ms_total={s['compile_cache']['compile_ms']:.1f} "
        f"(programs compiled {s['compile_cache']['misses']}, "
        f"set-up analyze {rep['setup']['analyze_s']:.1f}s)")
    log(f"digest_dispatch_ms={s['digest_dispatch_ms']}")
    log(f"bytes_in_use={rep['bytes_in_use']}")
    log(f"total {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
