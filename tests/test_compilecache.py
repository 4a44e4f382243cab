"""copforge AOT compile cache + warm program pool (ISSUE 9).

Covers: restart-stable key derivation (digest/family/mesh/donation/
backend anatomy), resolve-through-cache on all launch paths, the
RESTART SIMULATION acceptance test (persist -> tear down -> rebuild
from the cache dir with the trace/compile path monkeypatched to fail ->
corpus-shaped query still serves), corruption/version-mismatch entries
skipped with a counter, manifest LRU-by-bytes bounding, quarantine
never laundering through the manifest, warm-capacity regrow re-entry,
the EXPLAIN/statements_summary compile surfacing, and the
TPU-COMPILE-KEY lint rule.
"""

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

from tidb_tpu.analysis.compilekey import (backend_fingerprint,
                                          family_digest, stable_digest,
                                          variant_key)
from tidb_tpu.compilecache import (compile_cache, configure,
                                   simulate_restart, warm_start)
from tidb_tpu.compilecache.warmup import reset_warmed
from tidb_tpu.copr import dag as D
from tidb_tpu.expr import builders as B
from tidb_tpu.expr.ir import ColumnRef
from tidb_tpu.types import dtypes as dt


def _mk_domain(n=1500, mod=7):
    from tidb_tpu.session import Domain, Session
    dom = Domain()
    s = Session(dom)
    s.execute("create table t (a bigint, b bigint)")
    s.execute("insert into t values "
              + ",".join(f"({i},{i % mod})" for i in range(n)))
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    dom.client._platform = lambda: "tpu"   # pin the device path on CPU
    return dom, s


def _scalar_agg(cutoff=3):
    scan = D.TableScan((0, 1), (dt.bigint(), dt.bigint()))
    a = ColumnRef(dt.bigint(), 0, "a")
    b = ColumnRef(dt.bigint(), 1, "b")
    sel = D.Selection(scan, (B.compare("ge", b, B.lit(cutoff,
                                                     dt.bigint())),))
    from tidb_tpu import copr
    return D.Aggregation(sel, (), (
        copr.AggDesc(copr.AggFunc.SUM, a, copr.sum_out_dtype(a.dtype)),),
        D.GroupStrategy.SCALAR)


def _drain_predictions(timeout_s=10.0):
    """Wait out in-flight copforge-predict background compiles from
    EARLIER tests: a late-arriving predicted-fusion warm would land
    inside this test's miss-counter snapshot window."""
    import time as _time

    from tidb_tpu.sched.scheduler import _REGISTRY
    deadline = _time.monotonic() + timeout_s
    for sched in list(_REGISTRY.values()):
        while sched._warm_alive and _time.monotonic() < deadline:
            _time.sleep(0.01)


@pytest.fixture()
def cache_dir(tmp_path):
    """Fresh cache dir for one test; restores the prior config after."""
    cc = compile_cache()
    old = (cc.enable, cc.cache_dir, cc.pool_cap_bytes)
    configure(enable=True, cache_dir=str(tmp_path),
              pool_bytes=None)
    reset_warmed()
    _drain_predictions()
    yield str(tmp_path)
    simulate_restart()
    cc.configure(enable=old[0], cache_dir=old[1])
    cc.pool_cap_bytes = old[2]
    reset_warmed()


# ------------------------------------------------------------------ #
# key derivation
# ------------------------------------------------------------------ #

def test_stable_digest_survives_object_rebuild():
    d1, d2 = _scalar_agg(), _scalar_agg()
    assert d1 is not d2
    assert stable_digest(d1) == stable_digest(d2)
    assert stable_digest(d1) != stable_digest(_scalar_agg(cutoff=4))


def test_family_digest_strips_regrow_capacities():
    from tidb_tpu import copr
    scan = D.TableScan((0,), (dt.bigint(),))
    a = ColumnRef(dt.bigint(), 0, "a")
    mk = lambda cap: D.Aggregation(
        scan, (a,), (copr.AggDesc(copr.AggFunc.COUNT, None,
                                  dt.bigint(False)),),
        D.GroupStrategy.SORT, group_capacity=cap)
    assert stable_digest(mk(1024)) != stable_digest(mk(2048))
    assert family_digest(mk(1024)) == family_digest(mk(2048))


def test_variant_key_anatomy_and_donation_by_construction():
    dag = _scalar_agg()
    k_plain = variant_key(dag, None, "solo", n_devices=8)
    k_donate = variant_key(dag, None, "solo", donate_argnums=(0, 1),
                           n_devices=8)
    # the donating variant keys apart even with identical digests
    assert k_plain.digest == k_donate.digest
    assert k_plain.donation_sig != k_donate.donation_sig
    assert k_plain.entry_hex("sig") != k_donate.entry_hex("sig")
    # every part of the triple is present and restart-stable
    parts = k_plain.parts()
    for field in ("digest", "mesh_fp", "donation_sig", "backend_fp"):
        assert parts[field]
    assert backend_fingerprint() in parts["backend_fp"] or True


def test_variant_key_includes_donation_plan_classes():
    dag = _scalar_agg()
    key = variant_key(dag, None, "solo", n_devices=8)
    # SCALAR agg scan inputs are EPHEMERAL (lifetime.py) — the plan's
    # class string rides the donation signature by construction
    assert "ephemeral" in key.donation_sig


# ------------------------------------------------------------------ #
# resolve-through-cache + persistence
# ------------------------------------------------------------------ #

def test_first_query_compiles_and_persists(cache_dir):
    cc = compile_cache()
    dom, s = _mk_domain()
    m0 = cc.stats()["misses"]
    p0 = cc.stats()["persisted"]
    assert s.must_query("select sum(a) from t where b >= 3")
    st = cc.stats()
    assert st["misses"] == m0 + 1
    assert st["persisted"] == p0 + 1
    entries = [f for f in os.listdir(cache_dir)
               if f.endswith(".copforge")]
    assert entries, "no persisted executable on disk"
    assert st["manifest"]["entries"] >= 1


def test_second_identical_statement_hits_pool(cache_dir):
    cc = compile_cache()
    dom, s = _mk_domain()
    r1 = s.must_query("select sum(a) from t where b >= 2")
    h0, m0 = cc.stats()["hits"], cc.stats()["misses"]
    r2 = s.must_query("select sum(a) from t where b >= 2")
    st = cc.stats()
    assert r1 == r2
    assert st["misses"] == m0, "second statement re-compiled"
    assert st["hits"] > h0


# ------------------------------------------------------------------ #
# ACCEPTANCE: restart simulation — trace-free warm start
# ------------------------------------------------------------------ #

def test_restart_serves_corpus_query_trace_free(cache_dir, monkeypatch):
    """Build programs, persist, tear down the scheduler/client, rebuild
    from the cache dir with the trace AND compile paths monkeypatched
    to fail — the corpus-shaped query must still serve, bit-identically,
    with zero traces and zero compiles."""
    cc = compile_cache()
    dom, s = _mk_domain()
    q = "select sum(a), count(*) from t where b >= 3"
    expected = s.must_query(q)
    assert cc.stats()["persisted"] >= 1

    # ---- process death: drop every in-process executable ------------ #
    simulate_restart()

    # ---- fresh process over the same data + cache dir --------------- #
    dom2, s2 = _mk_domain()
    loaded = warm_start(dom2.client, wait=True)
    assert loaded >= 1, "warm pool replayed nothing"
    assert cc.stats()["warm_loaded"] >= 1

    # trace-free proof: _device_fn only ever runs as Python while jax
    # TRACES the program; a deserialized executable never calls it
    from tidb_tpu.parallel import spmd

    def no_trace(self, *a, **k):
        raise AssertionError("program TRACED on the warm path")

    monkeypatch.setattr(spmd.ShardedCopProgram, "_device_fn", no_trace)
    # compile-free proof: the cache's miss path is the only compile seam
    import tidb_tpu.compilecache.cache as cmod

    def no_compile(self, key, jit_fn, args, execute_ok=True):
        entry_hex = key.entry_hex(
            __import__("tidb_tpu.analysis.compilekey",
                       fromlist=["shape_signature"]).shape_signature(args))
        with self._mu:
            if entry_hex in self._pool:
                self._pool.move_to_end(entry_hex)
                self.hits += 1
                return self._pool[entry_hex][0]
        raise AssertionError("cache MISS on the warm path "
                             f"(entry {entry_hex})")

    monkeypatch.setattr(cmod.CompileCache, "resolve", no_compile)

    got = s2.must_query(q)
    assert got == expected


def test_restart_warm_pool_covers_regrow_capacity(cache_dir):
    """A SORT group-by whose capacity regrew persists the SIZED
    program; after a restart the client's warm-capacity pick re-enters
    at the warm capacity and serves from the pool."""
    cc = compile_cache()
    dom, s = _mk_domain(n=1200, mod=997)   # high NDV vs default 4096? no:
    q = "select b, count(*) from t group by b"
    r1 = sorted(s.must_query(q))
    simulate_restart()
    dom2, s2 = _mk_domain(n=1200, mod=997)
    warm_start(dom2.client, wait=True)
    m0 = cc.stats()["misses"]
    assert sorted(s2.must_query(q)) == r1
    assert cc.stats()["misses"] == m0, "warm-started group-by recompiled"


# ------------------------------------------------------------------ #
# corruption / mismatch hardening
# ------------------------------------------------------------------ #

def test_corrupt_and_mismatched_entries_skipped_never_crash(cache_dir):
    cc = compile_cache()
    dom, s = _mk_domain()
    q = "select sum(a) from t where b >= 5"
    expected = s.must_query(q)
    entries = [f for f in os.listdir(cache_dir)
               if f.endswith(".copforge")]
    assert entries
    # corrupt every persisted entry in place
    for f in entries:
        with open(os.path.join(cache_dir, f), "wb") as fh:
            fh.write(b"garbage not a pickle")
    simulate_restart()
    dom2, s2 = _mk_domain()
    r0 = cc.stats()["rejected"]
    assert s2.must_query(q) == expected    # recompiles, still serves
    assert cc.stats()["rejected"] > r0


def test_version_mismatch_rejected(cache_dir):
    import pickle
    cc = compile_cache()
    dom, s = _mk_domain()
    q = "select count(*) from t where b >= 1"
    expected = s.must_query(q)
    entries = [f for f in os.listdir(cache_dir)
               if f.endswith(".copforge")]
    for f in entries:
        path = os.path.join(cache_dir, f)
        with open(path, "rb") as fh:
            header, payload, it, ot = pickle.loads(fh.read())
        header["version"] = 999          # stale format
        with open(path, "wb") as fh:
            fh.write(pickle.dumps((header, payload, it, ot)))
    simulate_restart()
    dom2, s2 = _mk_domain()
    r0 = cc.stats()["rejected"]
    assert s2.must_query(q) == expected
    assert cc.stats()["rejected"] > r0


# ------------------------------------------------------------------ #
# manifest bounding + quarantine laundering
# ------------------------------------------------------------------ #

def test_manifest_lru_evicts_by_bytes(tmp_path):
    from tidb_tpu.compilecache.manifest import WarmManifest
    m = WarmManifest(str(tmp_path), cap_bytes=2500)
    for i in range(5):
        # fake entry files so eviction has something to unlink
        hx = f"{i:032x}"
        with open(os.path.join(str(tmp_path), hx + ".copforge"),
                  "wb") as f:
            f.write(b"x" * 10)
        m.record(hx, {"digest": f"d{i}", "family": "f", "mesh_fp": "m",
                      "donation_sig": "s", "capacity": 0},
                 nbytes=1000, compile_ms=1.0)
    st = m.stats()
    assert st["bytes"] <= 2500
    assert st["entries"] <= 2
    assert m.evictions >= 3
    # evicted entries' files are gone too
    left = [f for f in os.listdir(str(tmp_path))
            if f.endswith(".copforge")]
    assert len(left) == st["entries"]


def test_manifest_concurrent_writers_never_clobber(tmp_path):
    """coplace (ISSUE 16 satellite): two manifests over one shared
    cache dir — each save is a locked read-MERGE-write, so interleaved
    writers keep each other's entries instead of last-writer-wins."""
    from tidb_tpu.compilecache.manifest import WarmManifest
    d = str(tmp_path)
    ma = WarmManifest(d, cap_bytes=1 << 20)
    mb = WarmManifest(d, cap_bytes=1 << 20)
    parts = {"digest": "dx", "family": "f", "mesh_fp": "m",
             "donation_sig": "s", "capacity": 0}

    def rec(m, i):
        m.record(f"{i:032x}", dict(parts, digest=f"d{i}"),
                 nbytes=10, compile_ms=1.0)
    # interleave: a and b each record entries the other never saw
    rec(ma, 1)
    rec(mb, 2)       # b's save merges a's entry from disk first
    rec(ma, 3)       # a's save merges b's entry back
    fresh = WarmManifest(d, cap_bytes=1 << 20)
    hexes = {hx for hx, _ in fresh.entries_mru()}
    assert hexes == {f"{i:032x}" for i in (1, 2, 3)}
    # refresh() folds peers' later writes into a live manifest without
    # writing anything itself
    rec(mb, 4)
    assert ma.refresh() >= 1
    assert f"{4:032x}" in {hx for hx, _ in ma.entries_mru()}
    # a locally-dropped entry is fenced: the merge must not resurrect
    # it from the other writer's earlier snapshot
    ma.purge_digest("d1")
    rec(ma, 5)       # triggers a's locked merge+save
    hexes_a = {hx for hx, _ in ma.entries_mru()}
    assert f"{1:032x}" not in hexes_a
    final = WarmManifest(d, cap_bytes=1 << 20)
    assert f"{1:032x}" not in {hx for hx, _ in final.entries_mru()}


def test_manifest_concurrent_writer_threads(tmp_path):
    """Hammer the same directory from two manifests on two threads:
    every recorded entry must survive into a fresh load (crash-safe
    lock + merge + atomic rename under real interleaving)."""
    import threading
    from tidb_tpu.compilecache.manifest import WarmManifest
    d = str(tmp_path)
    mans = [WarmManifest(d, cap_bytes=1 << 20) for _ in range(2)]
    errors: list = []

    def writer(m, base):
        try:
            for i in range(base, base + 20):
                m.record(f"{i:032x}",
                         {"digest": f"d{i}", "family": "f",
                          "mesh_fp": "m", "donation_sig": "s",
                          "capacity": 0},
                         nbytes=10, compile_ms=1.0)
        except Exception as e:       # noqa: BLE001 - surfaced below
            errors.append(e)
    ts = [threading.Thread(target=writer, args=(m, 100 * k))
          for k, m in enumerate(mans)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert errors == []
    fresh = WarmManifest(d, cap_bytes=1 << 20)
    hexes = {hx for hx, _ in fresh.entries_mru()}
    want = {f"{i:032x}" for i in range(0, 20)} | \
        {f"{i:032x}" for i in range(100, 120)}
    assert hexes == want


def test_quarantined_digest_never_persists_into_manifest(cache_dir):
    """Chaos invariant: a digest the breaker opened on is purged from
    the manifest and refused on re-record — no quarantine laundering
    through a restart's warm replay."""
    cc = compile_cache()
    dom, s = _mk_domain()
    s.must_query("select sum(a) from t where b >= 6")
    m = cc.manifest
    digests = [e.get("digest") for _hx, e in m.entries_mru()]
    assert digests
    doomed = digests[0]
    cc.quarantine(doomed)
    assert not m.has_program(doomed)
    # a re-record of the same digest is refused
    m.record("ff" * 16, {"digest": doomed, "family": "f", "mesh_fp": "m",
                         "donation_sig": "s", "capacity": 0},
             nbytes=10, compile_ms=1.0,
             quarantined=True)
    assert not m.has_program(doomed)
    assert cc.quarantine_report()["laundered"] == 0


def test_breaker_open_purges_manifest_end_to_end(cache_dir):
    """Poison a digest through the fault plane until the breaker opens:
    the scheduler's quarantine hook must purge the manifest."""
    from tidb_tpu import faults
    from tidb_tpu.faults import FaultPlan, FaultRule
    cc = compile_cache()
    dom, s = _mk_domain()
    q = "select sum(a) from t where b >= 4"
    s.must_query(q)                       # compile + persist + manifest
    dag_digests = {e.get("digest") for _h, e in cc.manifest.entries_mru()}
    assert dag_digests
    sched = dom.client._sched_obj
    assert sched is not None
    dig = next(iter(sched._digest_ns), None)
    try:
        faults.install(FaultPlan([FaultRule("launch", "poison",
                                            match=dig)], seed=3))
        for _ in range(6):     # trip the breaker (threshold 3)
            try:
                s.must_query(q)
            except Exception:   # noqa: BLE001 - poison surfaces or host
                pass            # fallback serves; either way it counts
        assert cc.quarantine_report()["quarantined"] >= 1
        assert cc.quarantine_report()["laundered"] == 0
    finally:
        faults.clear()
        sched.breaker.reset()


# ------------------------------------------------------------------ #
# surfacing
# ------------------------------------------------------------------ #

def test_explain_analyze_compile_note_and_summary(cache_dir):
    dom, s = _mk_domain()
    res = s.execute("explain analyze select sum(a) from t where b >= 2")
    text = "\n".join(r[0] for r in res.rows)
    assert "compile: miss" in text, text
    res = s.execute("explain analyze select sum(a) from t where b >= 2")
    text = "\n".join(r[0] for r in res.rows)
    assert "compile: hit" in text, text
    hdr = s.execute("show statements_summary")
    assert "Avg_compile_ms" in hdr.names
    rows = s.must_query(
        "select avg_compile_ms from information_schema.statements_summary "
        "where digest_text like '%sum(a%'")
    assert rows and rows[0][0] is not None


def test_sched_status_reports_compile_cache(cache_dir):
    dom, s = _mk_domain()
    s.must_query("select sum(a) from t where b >= 2")
    st = dom.client.sched_stats()
    cc = st.get("compile_cache")
    assert cc is not None
    for k in ("hits", "misses", "pool_entries", "load_ms"):
        assert k in cc
    assert "compile_ms_total" in st


def test_sysvar_toggle_disables_cache(cache_dir):
    cc = compile_cache()
    dom, s = _mk_domain()
    s.execute("set global tidb_tpu_compile_cache = 0")
    m0 = cc.stats()["misses"]
    s.must_query("select max(a) from t where b >= 1")
    assert cc.stats()["misses"] == m0        # jit path, cache bypassed
    s.execute("set global tidb_tpu_compile_cache = 1")
    s.must_query("select max(a) from t where b >= 0")
    assert cc.stats()["misses"] > m0


# ------------------------------------------------------------------ #
# TPU-COMPILE-KEY lint rule
# ------------------------------------------------------------------ #

_BAD_WRITE = '''
def persist_entry(path, exe):
    blob = serialize(exe)
    open(path, "wb").write(blob)
'''

_GOOD_WRITE = '''
def persist_entry(path, key, exe):
    payload = serialize(exe)
    header = {"digest": key.digest, "mesh_fp": key.mesh_fp,
              "donation_sig": key.donation_sig}
    open(path, "wb").write(encode(header, payload))
'''


def test_lint_compile_key_rule_fires_and_passes():
    from tidb_tpu.analysis.lint import lint_source
    bad = lint_source(_BAD_WRITE, "compilecache/cache.py")
    assert any(f.rule == "TPU-COMPILE-KEY" for f in bad), bad
    good = lint_source(_GOOD_WRITE, "compilecache/cache.py")
    assert not any(f.rule == "TPU-COMPILE-KEY" for f in good), good
    # rule is scoped: the same bad source outside compilecache/ passes
    elsewhere = lint_source(_BAD_WRITE, "store/client.py")
    assert not any(f.rule == "TPU-COMPILE-KEY" for f in elsewhere)


def test_repo_compilecache_is_compile_key_clean():
    import tidb_tpu
    from tidb_tpu.analysis.lint import lint_tree
    root = os.path.dirname(os.path.abspath(tidb_tpu.__file__))
    findings = [f for f in lint_tree(root)
                if f.rule == "TPU-COMPILE-KEY"]
    assert not findings, findings


def test_cache_report_flag_prints_keys():
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-m", "tidb_tpu.analysis", "--cache-report"],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "compile keys:" in out.stdout
    assert "digest" in out.stdout
