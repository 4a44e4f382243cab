"""copforge: AOT compile cache + warm program pool.

Reference analog: compilation is the tail-latency cliff of every
compiled query engine — BENCH_r05 measured 153 s of warmup on SF100 Q6
and 3 s on SF10 Q1, and at production traffic every cold program digest
is a p99 disaster.  Flare's answer (PAPERS.md) is to keep compilation
off the hot path entirely; the compiler-first O(1)-caching inference
stack persists digest-keyed executables across process restarts.  This
module is that pattern for the spmd cop programs:

- every cacheable builder resolves its executable THROUGH this cache
  (``CachedProgram``): warm-pool hit -> call the held ``Compiled``
  object (zero trace, zero compile); disk hit -> ``deserialize_and_load``
  the persisted executable (zero trace); miss -> explicit AOT staging
  ``jit.lower(*args).compile()`` (SNIPPETS.md [1], the pjit ``Lowered``
  seam), then serialize + persist for the next process.
- entries are keyed by the restart-stable variant key
  (analysis/compilekey: dag digest + mesh fingerprint + capacity +
  DonationPlan signature + backend fingerprint) plus the concrete call
  signature; EVERY part is re-verified at load — a stale, corrupt, or
  backend-mismatched entry is skipped with a counter, never silently
  deserialized and never a crash.
- backends whose runtime cannot serialize executables keep the full
  warm-pool semantics in-process (the ``Lowered`` pool): persistence is
  probed once and skipped, nothing else changes — tier-1 exercises the
  whole code path on the CPU mesh either way.
- the warm pool is LRU-bounded by bytes (``tidb_tpu_compile_warm_pool``)
  and its persisted twin (compilecache/manifest.py) is replayed at boot
  through the admission queue at LOW priority (compilecache/warmup.py).
"""

from __future__ import annotations

import logging
import os
import pickle
import threading
import time
from collections import OrderedDict
from typing import Optional

from ..analysis.compilekey import (CompileKey, backend_fingerprint,
                                   shape_signature)
from ..obs import trace as _obs
from .manifest import DEFAULT_CAP_BYTES, WarmManifest

ENTRY_SUFFIX = ".copforge"
FORMAT_VERSION = 1
MAGIC = "copforge"

# builders whose program serves a GROUP of tasks (several member programs
# over one scan, or one program over stacked inputs): the admission
# scheduler never compiles these where a client waits, and at most
# GROUP_PROGRAMS_MAX of them may exist (warm pool and manifest together)
GROUP_PROGRAMS = ("fused", "fused-rows", "batched", "batched-rows")
GROUP_PROGRAMS_MAX = 32


def is_group_key(key: CompileKey) -> bool:
    return key.capacity_sig.split("/", 1)[0] in GROUP_PROGRAMS


# nominal pool accounting for executables the backend cannot serialize
# (no payload to size): small enough that a CPU-mesh pool holds the
# whole corpus, large enough that eviction still means something
NOMINAL_EXE_BYTES = 64 << 10

_log = logging.getLogger(__name__)


class _Counters(threading.local):
    """Per-thread mirror of the compile/load totals: the drain thread
    reads ITS OWN deltas around a launch, so concurrent sessions on
    other threads cannot pollute one launch's compile attribution."""

    def __init__(self):
        self.compiled_ns = 0
        self.loaded_ns = 0
        self.misses = 0
        self.hits = 0
        # copgauge: largest per-device (argument+output+temp) bytes of
        # an executable resolved on THIS thread since the drain's mark
        # — the measured-watermark source where the backend reports no
        # live memory_stats (the CPU mesh, so tier-1 exercises it)
        self.mem_peak = 0


class CompileCache:
    """Process-wide program cache (one per process, like the metric
    registry): the pool is keyed by entry hex so every builder object
    over the same program shares one executable."""

    def __init__(self):
        self.enable = os.environ.get(
            "TIDB_TPU_COMPILE_CACHE", "1") != "0"
        self.cache_dir = os.environ.get("TIDB_TPU_COMPILE_CACHE_DIR", "")
        self.pool_cap_bytes = DEFAULT_CAP_BYTES
        self._mu = threading.Lock()
        self._pool: OrderedDict[str, tuple] = OrderedDict()  # hex -> (exe, nbytes)
        self._pool_bytes = 0
        # copgauge: entry hex -> per-device executable memory bytes
        # (argument+output+temp from Compiled.memory_analysis; 0 =
        # backend reports none) — memoized next to the pool
        self._mem_info: dict[str, int] = {}
        self._bad_entries: set = set()     # rejected on disk; don't re-read
        self._caps: dict[str, set] = {}    # family -> warm capacities
        # group programs (GROUP_PROGRAMS) in the pool, slots a compile
        # under way has reserved against GROUP_PROGRAMS_MAX, and the
        # names of the threads that compiled one (never "sched-drain")
        self._group_entries: set = set()
        self._group_reserved = 0
        self._group_compile_threads: set = set()
        self._quarantined: set = set()     # stable digests the breaker opened
        self._manifest: Optional[WarmManifest] = None
        # persistence support is probed on first serialize attempt:
        # None = unknown, False = backend can't (in-process pool only)
        self._persist_ok: Optional[bool] = None
        self._tl = _Counters()
        # lifetime counters (mirrored to /sched + prometheus)
        self.hits = 0              # warm-pool hits (no trace, no load)
        self.disk_hits = 0         # persisted entries deserialized
        self.misses = 0            # AOT lower+compile runs
        self.uncacheable = 0       # programs the AOT path refused
        self._uncacheable_logged: set = set()   # digests logged once
        self.rejected = 0          # corrupt/stale/mismatched disk entries
        self.persisted = 0         # entries written to the cache dir
        self.evictions = 0         # pool LRU evictions
        self.fallback_calls = 0    # pooled executable refused the args
        self.warm_loaded = 0       # entries loaded by the boot warm pool
        self.compile_ms_total = 0.0
        self.load_ms_total = 0.0
        from ..utils.metrics import global_registry
        reg = global_registry()
        self._m_hits = reg.counter("tidb_tpu_compile_cache_hits",
                                   "compile cache hits (pool + disk)")
        self._m_miss = reg.counter("tidb_tpu_compile_cache_misses",
                                   "compile cache misses (AOT compiles)")
        self._m_load = reg.counter("tidb_tpu_compile_cache_load_ms",
                                   "milliseconds spent deserializing "
                                   "persisted executables")
        self._m_bytes = reg.gauge("tidb_tpu_compile_cache_bytes",
                                  "warm program pool resident bytes")
        # copscope (obs/): resolve latency histogram by outcome — every
        # perf_counter_ns measurement in this module records through
        # the obs histogram API (TPU-SPAN-LEAK contract)
        from ..utils.metrics import Histogram
        self._m_resolve_ms = reg.histogram(
            "tidb_tpu_compile_resolve_ms",
            "program resolve latency by outcome (load/compile/warm)",
            buckets=Histogram.MS_BUCKETS, labels=("outcome",))

    # ---- knobs (sysvars ride through session._exec_ctx) -------------- #

    def configure(self, enable: Optional[bool] = None,
                  cache_dir: Optional[str] = None,
                  pool_bytes: Optional[int] = None) -> None:
        if enable is not None:
            self.enable = bool(enable)
        if cache_dir is not None and cache_dir != self.cache_dir:
            with self._mu:
                self.cache_dir = cache_dir
                self._manifest = None
                self._bad_entries.clear()
        if pool_bytes is not None and pool_bytes >= 0:
            self.pool_cap_bytes = (pool_bytes if pool_bytes > 0
                                   else 0)        # 0 = unbounded
            if self._manifest is not None:
                self._manifest.cap_bytes = self.pool_cap_bytes

    @property
    def manifest(self) -> Optional[WarmManifest]:
        if not self.cache_dir:
            return None
        with self._mu:
            if self._manifest is None:
                self._manifest = WarmManifest(self.cache_dir,
                                              self.pool_cap_bytes)
            return self._manifest

    # ---- attribution seam (sched drain reads per-thread deltas) ------ #

    def thread_snapshot(self) -> tuple:
        t = self._tl
        return (t.compiled_ns + t.loaded_ns, t.misses, t.hits)

    # ---- measured-watermark seam (copgauge, obs/hbm) ----------------- #

    def thread_mem_mark(self) -> None:
        """Reset this thread's per-launch executable-memory high-water;
        the drain marks before a serve and takes after it."""
        self._tl.mem_peak = 0

    def thread_mem_take(self) -> int:
        """Largest per-device (argument + output + temp) bytes among
        the executables resolved on this thread since the mark — the
        compiled ``memory_analysis`` of the ACTUALLY-SERVED program, so
        the measured watermark reflects the executable that ran, not a
        re-lowered twin."""
        return self._tl.mem_peak

    def _entry_mem_bytes(self, entry_hex: str, exe) -> int:
        """Per-device (argument + output + temp) bytes of one pooled
        executable, from ``Compiled.memory_analysis`` — computed once
        per entry and memoized (the analysis walks the whole HLO
        module; doing it per launch would tax the drain)."""
        with self._mu:
            n = self._mem_info.get(entry_hex)
        if n is not None:
            return n
        n = 0
        try:
            ma = exe.memory_analysis()
            if ma is not None:
                n = (int(ma.argument_size_in_bytes)
                     + int(ma.output_size_in_bytes)
                     + int(ma.temp_size_in_bytes))
        except Exception:   # noqa: BLE001 - backend capability probe:
            # deserialized or exotic executables may expose no memory
            # analysis; the ledger then runs on its own accounting
            n = 0
        n = max(n, 0)
        with self._mu:
            self._mem_info[entry_hex] = n
        return n

    def _note_mem(self, entry_hex: str, exe) -> None:
        n = self._entry_mem_bytes(entry_hex, exe)
        if n > self._tl.mem_peak:
            self._tl.mem_peak = n

    # ---- pool ------------------------------------------------------- #

    def _pool_put_locked(self, entry_hex: str, exe, nbytes: int,
                         group: bool = False) -> None:
        old = self._pool.pop(entry_hex, None)
        if old is not None:
            self._pool_bytes -= old[1]
        self._pool[entry_hex] = (exe, nbytes)
        self._pool_bytes += nbytes
        if group:
            self._group_entries.add(entry_hex)
        while self.pool_cap_bytes > 0 and \
                self._pool_bytes > self.pool_cap_bytes and \
                len(self._pool) > 1:
            hx, (_exe, nb) = self._pool.popitem(last=False)
            self._pool_bytes -= nb
            self._group_entries.discard(hx)
            self.evictions += 1
        self._m_bytes.set(self._pool_bytes)

    def _note_caps(self, key: CompileKey) -> None:
        if key.capacity:
            with self._mu:
                self._caps.setdefault(key.family, set()).add(key.capacity)

    def warm_capacity(self, family: str, needed: int,
                      limit_factor: int = 4) -> Optional[int]:
        """Smallest warm capacity >= needed for this plan family, from
        the in-process pool and the persisted manifest — the regrow /
        paging loops round UP to a capacity that is already compiled
        instead of re-tracing at the minimal pow2 step.  Bounded: a warm
        buffer more than ``limit_factor``x the need wastes more HBM than
        the compile costs."""
        if not self.enable or needed <= 0:
            return None
        with self._mu:
            caps = set(self._caps.get(family, ()))
        m = self.manifest
        if m is not None:
            caps.update(m.capacities_for(family))
        good = [c for c in sorted(caps)
                if needed <= c <= needed * limit_factor]
        return good[0] if good else None

    # ---- quarantine (breaker -> manifest exclusion) ------------------ #

    def quarantine(self, digest: str) -> None:
        """The circuit breaker opened on this (stable) dag digest: purge
        its manifest entries and refuse new records, so a poisoned
        program cannot launder its quarantine through a restart's warm
        replay.  Its live cost corrections (analysis/calibrate) drop
        too — the manifest purge removes the persisted twin."""
        with self._mu:
            self._quarantined.add(digest)
        m = self.manifest
        if m is not None:
            m.purge_digest(digest)
        from ..analysis.calibrate import correction_store
        correction_store().purge(digest)

    def quarantine_report(self) -> dict:
        """Chaos-rung assertion surface: quarantined digests must have
        ZERO manifest presence (laundered == 0, always)."""
        with self._mu:
            quarantined = sorted(self._quarantined)
        m = self.manifest
        laundered = [d for d in quarantined
                     if m is not None and m.has_program(d)]
        return {"quarantined": len(quarantined),
                "laundered": len(laundered)}

    # ---- disk entries ------------------------------------------------ #

    def _entry_path(self, entry_hex: str) -> str:
        return os.path.join(self.cache_dir, entry_hex + ENTRY_SUFFIX)

    def _persist(self, entry_hex: str, key: CompileKey, exe) -> int:
        """Serialize one executable next to its FULL key anatomy: the
        header carries the digest + mesh-fingerprint + donation triple
        (and the rest of key.parts()) that the loader re-verifies, so a
        renamed or collided file can never deserialize silently."""
        if not self.cache_dir or self._persist_ok is False:
            return 0
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(exe)
            # the TPU-COMPILE-KEY triple is spelled AT the write seam
            # (not just inside key.parts()) so the gate can see every
            # serialized entry carries digest + mesh_fp + donation_sig
            header = {"magic": MAGIC, "version": FORMAT_VERSION,
                      "key": key.parts(), "entry": entry_hex,
                      "digest": key.digest, "mesh_fp": key.mesh_fp,
                      "donation_sig": key.donation_sig}
            blob = pickle.dumps((header, payload, in_tree, out_tree),
                                protocol=pickle.HIGHEST_PROTOCOL)
            self._persist_ok = True
        except Exception as e:   # noqa: BLE001 - backend capability probe:
            # runtimes without executable serialization keep the
            # in-process pool (full warm semantics, no persistence);
            # shown as persist_supported=false on /sched
            self._persist_ok = False
            _log.warning("executable serialization unavailable, compile "
                         "cache stays in-process: %s: %s",
                         type(e).__name__, str(e)[:200])
            return 0
        try:
            os.makedirs(self.cache_dir, exist_ok=True)
            path = self._entry_path(entry_hex)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except OSError:
            return 0
        with self._mu:
            self.persisted += 1
        return len(blob)

    def _load_entry(self, entry_hex: str, key_parts: Optional[dict]):
        """Deserialize one persisted executable, re-verifying the header
        against the expected key anatomy.  Returns (exe, nbytes) or
        None; every rejection is counted, none raises."""
        path = self._entry_path(entry_hex)
        try:
            with open(path, "rb") as f:
                blob = f.read()
            header, payload, in_tree, out_tree = pickle.loads(blob)
            if (header.get("magic") != MAGIC
                    or header.get("version") != FORMAT_VERSION
                    or header.get("entry") != entry_hex):
                raise ValueError("header mismatch")
            stored = header.get("key", {})
            if stored.get("backend_fp") != backend_fingerprint():
                raise ValueError("backend fingerprint mismatch")
            if key_parts is not None:
                for field in ("digest", "mesh_fp", "donation_sig"):
                    if stored.get(field) != key_parts.get(field):
                        raise ValueError(f"key {field} mismatch")
            from jax.experimental import serialize_executable as se
            exe = se.deserialize_and_load(payload, in_tree, out_tree)
            return exe, len(blob)
        except FileNotFoundError:
            return None
        except Exception:   # noqa: BLE001 - corrupt/stale entries are
            # skipped with a counter, never a crash (and never re-read)
            with self._mu:
                self.rejected += 1
                self._bad_entries.add(entry_hex)
            return None

    # ---- is it loaded? (asked before a group form is chosen) --------- #

    def loaded(self, key: CompileKey, args) -> bool:
        """Is the executable for (key, shape-of-args) in the warm pool?
        Neither lowers nor compiles nor reads the disk; False while the
        cache is off (nothing is known to be loaded then)."""
        return self.enable and self._in_pool(
            key.entry_hex(shape_signature(args)))

    def on_disk(self, key: CompileKey, args) -> bool:
        """Is it persisted in the cache directory (one ``stat``)?"""
        return self.enable and self._entry_on_disk(
            key.entry_hex(shape_signature(args)))

    def _in_pool(self, entry_hex: str) -> bool:
        with self._mu:
            return entry_hex in self._pool

    def _entry_on_disk(self, entry_hex: str) -> bool:
        if not self.cache_dir:
            return False
        with self._mu:
            if entry_hex in self._bad_entries:
                return False
        return os.path.isfile(self._entry_path(entry_hex))

    def _recorded_groups(self) -> set:
        m = self.manifest
        return m.group_entries() if m is not None else set()

    def group_programs(self) -> int:
        """How many group programs exist: in the warm pool and in the
        manifest together (an entry in both counts once)."""
        recorded = self._recorded_groups()
        with self._mu:
            return len(self._group_entries | recorded)

    def warm_group(self, key: CompileKey, jit_fn, args,
                   limit: int = GROUP_PROGRAMS_MAX) -> str:
        """Load or compile ONE group program off the serving path (the
        scheduler's ``warm_groups`` threads): "present" (already in the
        pool), "loaded" (from the cache directory), "compiled", "full"
        (``limit`` group programs exist and this is not one of them:
        nothing is compiled) or "failed"."""
        if not self.enable:
            return "failed"
        entry_hex = key.entry_hex(shape_signature(args))
        if self._in_pool(entry_hex):
            return "present"
        compiles = not self._entry_on_disk(entry_hex)
        if compiles:
            recorded = self._recorded_groups()
            with self._mu:      # counted and reserved in one step
                if len(self._group_entries | recorded) \
                        + self._group_reserved >= limit:
                    return "full"
                self._group_reserved += 1
        try:
            misses0 = self._tl.misses
            if self.resolve(key, jit_fn, args) is None:
                return "failed"
            return "compiled" if self._tl.misses > misses0 else "loaded"
        finally:
            if compiles:
                with self._mu:
                    self._group_reserved -= 1

    # ---- the resolve seam ------------------------------------------- #

    def resolve(self, key: CompileKey, jit_fn, args, execute_ok=True):
        """The executable for (key, shape-of-args): pool -> disk ->
        AOT compile.  Returns a callable, or None when the program is
        uncacheable (caller falls back to the plain jit path)."""
        entry_hex = key.entry_hex(shape_signature(args))
        with self._mu:
            hit = self._pool.get(entry_hex)
            if hit is not None:
                self._pool.move_to_end(entry_hex)
                self.hits += 1
                self._tl.hits += 1
            bad = entry_hex in self._bad_entries
        if hit is not None:
            self._m_hits.inc()
            self._note_mem(entry_hex, hit[0])
            return hit[0]
        if self.cache_dir and not bad:
            t0 = time.perf_counter_ns()
            with _obs.live_child("sched.compile", result="load"):
                loaded = self._load_entry(entry_hex, key.parts())
            if loaded is not None:
                exe, nbytes = loaded
                dt_ns = time.perf_counter_ns() - t0
                with self._mu:
                    self._pool_put_locked(entry_hex, exe, nbytes,
                                          is_group_key(key))
                    self.disk_hits += 1
                    self.hits += 1
                    self.load_ms_total += dt_ns / 1e6
                    self._tl.hits += 1
                    self._tl.loaded_ns += dt_ns
                self._note_caps(key)
                self._note_mem(entry_hex, exe)
                self._m_hits.inc()
                self._m_load.inc(dt_ns / 1e6)
                self._m_resolve_ms.observe(dt_ns / 1e6, outcome="load")
                m = self.manifest
                if m is not None:
                    m.touch(entry_hex, dt_ns / 1e6)
                return exe
        # coplace (pd/registry ISSUE 16): cross-process in-flight
        # compile claims.  Before the expensive AOT compile, claim the
        # entry on the coordination store; when a LIVE peer already
        # holds the claim, poll the shared cache dir briefly for its
        # persisted result instead of compiling the same program
        # twice.  pd off/degraded => claim is None and nothing here
        # changes; a timed-out poll falls through and compiles anyway
        # (compile-once is an optimization, never a correctness gate).
        claim = None
        if self.cache_dir and self._persist_ok is not False:
            from ..pd import try_compile_claim
            claim = try_compile_claim(entry_hex)
            if claim is False:
                exe = self._wait_peer_entry(entry_hex, key)
                if exe is not None:
                    return exe
        # miss: explicit AOT staging so we HOLD the Compiled object —
        # calling the jit wrapper would compile the same program into a
        # cache we cannot serialize from
        t0 = time.perf_counter_ns()
        try:
            with _obs.live_child("sched.compile", result="miss"):
                exe = jit_fn.lower(*args).compile()
        except Exception as e:   # noqa: BLE001 - AOT capability probe:
            # the plain jit path serves programs the staging API refuses
            # (and raises for real if the program cannot compile at all)
            with self._mu:
                self.uncacheable += 1
                first = key.digest not in self._uncacheable_logged
                if len(self._uncacheable_logged) > 256:
                    self._uncacheable_logged.clear()
                self._uncacheable_logged.add(key.digest)
            if first:
                _log.warning("AOT compile refused, serving through jit "
                             "(digest %s): %s: %s", key.digest[:16],
                             type(e).__name__, str(e)[:200])
            if claim is True:
                from ..pd import release_compile_claim
                release_compile_claim(entry_hex)
            return None
        dt_ns = time.perf_counter_ns() - t0
        group = is_group_key(key)
        with self._mu:
            self.misses += 1
            self.compile_ms_total += dt_ns / 1e6
            self._tl.misses += 1
            self._tl.compiled_ns += dt_ns
            if group:
                self._group_compile_threads.add(
                    threading.current_thread().name)
        self._m_miss.inc()
        self._m_resolve_ms.observe(dt_ns / 1e6, outcome="compile")
        nbytes = self._persist(entry_hex, key, exe) or NOMINAL_EXE_BYTES
        with self._mu:
            self._pool_put_locked(entry_hex, exe, nbytes, group)
        self._note_caps(key)
        self._note_mem(entry_hex, exe)
        m = self.manifest
        if m is not None:
            with self._mu:
                quarantined = key.digest in self._quarantined
            # the manifest record spells the key triple explicitly —
            # digest + mesh fingerprint + donation plan — so the warm
            # replay can never resurrect a wrong-variant executable
            m.record(entry_hex,
                     {"digest": key.digest, "family": key.family,
                      "mesh_fp": key.mesh_fp,
                      "donation_sig": key.donation_sig,
                      "capacity": key.capacity, "group": group},
                     nbytes, dt_ns / 1e6, quarantined=quarantined)
        if claim is True:
            # persisted (or at least pooled): peers polling on our
            # claim can stop early
            from ..pd import release_compile_claim
            release_compile_claim(entry_hex)
        return exe

    def _wait_peer_entry(self, entry_hex: str, key: CompileKey,
                         timeout_s: float = 1.5, poll_s: float = 0.05):
        """Bounded poll for the claim winner's persisted entry in the
        shared cache dir (coplace compile-once).  Returns the loaded
        executable or None (give up and compile locally) — never
        raises, never waits past ``timeout_s``."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            t0 = time.perf_counter_ns()
            loaded = self._load_entry(entry_hex, key.parts())
            if loaded is not None:
                exe, nbytes = loaded
                dt_ns = time.perf_counter_ns() - t0
                with self._mu:
                    self._pool_put_locked(entry_hex, exe, nbytes,
                                          is_group_key(key))
                    self.disk_hits += 1
                    self.hits += 1
                    self.load_ms_total += dt_ns / 1e6
                    self._tl.hits += 1
                    self._tl.loaded_ns += dt_ns
                self._note_caps(key)
                self._note_mem(entry_hex, exe)
                self._m_hits.inc()
                self._m_load.inc(dt_ns / 1e6)
                self._m_resolve_ms.observe(dt_ns / 1e6, outcome="load")
                m = self.manifest
                if m is not None:
                    m.refresh()      # adopt the winner's record too
                    m.touch(entry_hex, dt_ns / 1e6)
                return exe
            with self._mu:
                if entry_hex in self._bad_entries:
                    return None      # winner's entry is unreadable here
            time.sleep(poll_s)
        return None

    def load_warm(self, entry_hex: str) -> bool:
        """Boot warm pool: deserialize ONE manifest entry into the pool
        (no compile, no trace); False when missing/stale/corrupt."""
        with self._mu:
            if entry_hex in self._pool or entry_hex in self._bad_entries:
                return entry_hex in self._pool
        t0 = time.perf_counter_ns()
        loaded = self._load_entry(entry_hex, None)
        if loaded is None:
            return False
        exe, nbytes = loaded
        dt_ns = time.perf_counter_ns() - t0
        m = self.manifest
        group = m is not None and entry_hex in m.group_entries()
        with self._mu:
            self._pool_put_locked(entry_hex, exe, nbytes, group)
            self.warm_loaded += 1
            self.load_ms_total += dt_ns / 1e6
        self._m_load.inc(dt_ns / 1e6)
        self._m_resolve_ms.observe(dt_ns / 1e6, outcome="warm")
        if m is not None:
            m.touch(entry_hex, dt_ns / 1e6)
        return True

    def clear_pool(self) -> None:
        """Drop every in-process executable (restart simulation seam:
        tests and the bench coldwarm rung model a process death by
        clearing this plus the spmd builder caches; disk survives)."""
        with self._mu:
            self._pool.clear()
            self._pool_bytes = 0
            self._group_entries.clear()
            self._caps.clear()
            self._mem_info.clear()
            self._m_bytes.set(0)

    def stats(self) -> dict:
        with self._mu:
            out = {"enable": self.enable,
                   "cache_dir": self.cache_dir,
                   "pool_entries": len(self._pool),
                   "pool_bytes": self._pool_bytes,
                   "pool_cap_bytes": self.pool_cap_bytes,
                   "hits": self.hits, "misses": self.misses,
                   "disk_hits": self.disk_hits,
                   "warm_loaded": self.warm_loaded,
                   "uncacheable": self.uncacheable,
                   "rejected": self.rejected,
                   "persisted": self.persisted,
                   "evictions": self.evictions,
                   "fallback_calls": self.fallback_calls,
                   "persist_supported": self._persist_ok,
                   "group_programs_loaded": len(self._group_entries),
                   "group_compile_threads": sorted(
                       self._group_compile_threads),
                   "compile_ms": round(self.compile_ms_total, 3),
                   "load_ms": round(self.load_ms_total, 3)}
        m = self.manifest
        if m is not None:
            out["manifest"] = m.stats()
        return out


class CachedProgram:
    """The per-builder resolve-through-cache call seam: one of these
    replaces every direct ``self._fn(...)`` invocation in the spmd
    builders.  The underlying jit object stays exposed (``prog._fn``)
    for AOT introspection; this wrapper only decides WHERE the
    executable comes from."""

    __slots__ = ("_jit", "key")

    def __init__(self, jit_fn, key: CompileKey):
        self._jit = jit_fn
        self.key = key

    def __call__(self, *args):
        cache = compile_cache()
        if not cache.enable:
            return self._jit(*args)
        exe = cache.resolve(self.key, self._jit, args)
        if exe is None:
            return self._jit(*args)
        try:
            return exe(*args)
        except (TypeError, ValueError):
            # a pooled executable may refuse args whose placement drifted
            # from the lowering (cross-sharding call on a strict backend):
            # serve through jit — correctness beats the cache win
            with cache._mu:
                cache.fallback_calls += 1
            return self._jit(*args)

    def entry_hex(self, args) -> str:
        """The identity of the one executable these arguments call."""
        return self.key.entry_hex(shape_signature(args))

    def loaded(self, args) -> bool:
        """``CompileCache.loaded`` for this program's key."""
        return compile_cache().loaded(self.key, args)

    def warm_group(self, args, limit: int = GROUP_PROGRAMS_MAX) -> str:
        """``CompileCache.warm_group`` for this program's key: load or
        compile WITHOUT executing; ``args`` are ``jax.ShapeDtypeStruct``
        trees, so no array is held by a warm."""
        return compile_cache().warm_group(self.key, self._jit, args, limit)


_CACHE: Optional[CompileCache] = None
_CACHE_MU = threading.Lock()


def compile_cache() -> CompileCache:
    global _CACHE
    with _CACHE_MU:
        if _CACHE is None:
            _CACHE = CompileCache()
        return _CACHE


def configure(enable=None, cache_dir=None, pool_bytes=None) -> None:
    compile_cache().configure(enable, cache_dir, pool_bytes)


def cached_call(jit_fn, dag, mesh, program: str, row_capacity: int = 0,
                n_slots: int = 0, donate_argnums=(),
                extra=()) -> CachedProgram:
    """Builder facade: derive the variant key (DonationPlan included by
    construction — analysis/compilekey) and wrap the jit object."""
    from ..analysis.compilekey import variant_key
    key = variant_key(dag, mesh, program, row_capacity=row_capacity,
                      n_slots=n_slots,
                      donate_argnums=tuple(donate_argnums),
                      extra=tuple(extra))
    return CachedProgram(jit_fn, key)


__all__ = ["CompileCache", "CachedProgram", "compile_cache", "configure",
           "cached_call", "is_group_key", "GROUP_PROGRAMS",
           "GROUP_PROGRAMS_MAX", "ENTRY_SUFFIX", "FORMAT_VERSION", "MAGIC"]
