"""Per-mesh device admission scheduler: continuous micro-batching of
concurrent cop tasks.

Reference analog: tikv's unified read pool (resource-group-aware
priority queue in front of the storage threads) combined with the
continuous-batching admission loop of inference servers.  One scheduler
owns all launches onto one jax mesh:

- CopClient dispatch no longer calls the device directly; it submits
  `CopTask`s to a BOUNDED admission queue tagged by (program digest,
  capacity shape, resource group).  Overflow raises the MySQL-compatible
  "server is busy" error instead of growing memory without bound.
- A drain loop serves queues in weighted-fair order (stride scheduling
  over per-resource-group virtual time, weights from the group's
  PRIORITY — utils/resourcegroup.py).
- Compatible tasks COALESCE into one launch: identical inputs (same
  snapshot epoch residents) share a single program execution; distinct
  inputs of the same program stack along a batch-slot dim and run as ONE
  vmapped program (spmd.get_batched_program for dense aggs,
  spmd.get_batched_rows_program for compacted row outputs), with
  states/rows split back per task.
- Compatible-but-NON-identical tasks FUSE into one program: queued
  tasks sharing a contract-aware fusion key (one snapshot scan, one
  mesh, one capacity signature — analysis.contracts.fusion_signature,
  no tracing) but differing in filters/aggregates run as ONE
  FusedCopProgram computing every member's payload from a single scan
  pass; results demux back to each waiter (cross-query kernel fusion,
  the Flare shared-scan argument).
- An adaptive micro-batch WINDOW holds the drain briefly for
  stragglers: per fusion key, an EWMA of arrival gaps predicts whether
  a matching task is about to arrive; under bursty open-loop load the
  sub-millisecond wait raises coalesce/fusion rates sharply.
- The drain ENFORCES resource-group RU budgets (rc/): every task is
  priced from its static LaunchCost at submit, and a group whose token
  bucket (plus bounded overdraft) cannot cover its head task's RUs is
  SKIPPED — the exhausted group queues while other groups keep
  launching (no head-of-line blocking across groups), riders from an
  exhausted group may not hitch onto another group's launch, debits
  happen pre-launch at batch admission (fused groups pay the shared
  scan once, riders their marginal bytes), and a throttled task that
  overstays the max-queue deadline fails its waiter with the
  MySQL-compatible ResourceExhaustedError (8252).
- Launches are SUPERVISED (faultline): a transient launch failure
  retries through the store Backoffer's DEVICE_FAILED budget instead of
  failing the waiter; a failing fused/batched launch is DEMUXED and its
  members retried solo so one poisoned plan cannot take down innocent
  riders (fusion never widens a failure domain); a per-program-digest
  circuit breaker (CLOSED -> OPEN -> HALF_OPEN probe) makes repeat
  offenders fail fast at submit with LaunchQuarantinedError — which the
  CopClient degrades to the host oracle path where the plan shape
  allows.  The seeded FaultPlan (faults/plan.py) injects deterministic
  transient/poison faults at the build/launch/drain seams so every one
  of these paths is exercisable on a CPU mesh.
- Every task carries its statement's copscope TraceCtx (obs/): the
  drain records REAL spans from its own thread — queue wait (rc debit
  riding as an attr), copforge compile (hit/miss), launch (predicted
  vs measured ms, per-link transfer bytes), fusion assembly with
  per-member attributed share, transient-retry backoff, OOM/bisect/
  quarantine markers — into the statement's lock-protected span tree
  BEFORE the waiting task finishes, so TRACE and the flight recorder
  always see the scheduler-side story.  Untraced tasks skip it all.
- Queue-wait / launch / coalesce / fusion stats feed utils/metrics
  (scraped at /metrics), the /sched status route, per-statement
  execdetails (`schedWait`/`fused`/`ru` in EXPLAIN ANALYZE), priced
  per-group RU accounting, and measured launch wall time attributed
  per member (shared scan split by marginal bytes) and per program
  digest.

The drain thread starts lazily on first submit and exits after an idle
period, so embedders that never touch the device pay nothing.
"""

from __future__ import annotations

import contextlib
import logging
import os
import random
import threading
import time
from collections import Counter, deque
from typing import Optional

from ..faults import plan as _faults
from ..faults.breaker import CircuitBreaker, LaunchQuarantinedError
from ..obs import trace as _obs
from ..rc.controller import (DEFAULT_MAX_QUEUE_S, DEFAULT_OVERDRAFT_RU,
                             ResourceExhaustedError)
from ..rc.pricing import split_device_time, task_rus
from .task import CopTask, ServerBusyError, TaskCancelledError

DEFAULT_QUEUE_DEPTH = 256
DEFAULT_MAX_COALESCE = 8
IDLE_EXIT_S = 5.0
# adaptive micro-batch window: never hold a launch longer than this, and
# only hold at all when the key's EWMA arrival gap predicts a straggler
# inside the cap (2 * gap <= cap)
WINDOW_CAP_US = 1000
# arrival gaps beyond this clamp before feeding the EWMA so one long lull
# cannot poison the estimate forever (it recovers in a few arrivals)
WINDOW_GAP_CLAMP_NS = 50_000_000
WAIT_SAMPLES = 2048              # ring of recent task waits (p50/p99)
# window FEEDBACK (ROADMAP item): per-key EWMA of whether a hold actually
# yielded riders.  A key whose holds rarely pay decays its window toward
# zero (scale = min(1, hit/0.5)); below the floor the hold is skipped
# outright until a hit recovers the estimate.
WINDOW_HIT_INIT = 0.5            # optimistic prior: full window at start
WINDOW_HIT_ALPHA = 0.25          # EWMA step per observed hold outcome
WINDOW_HIT_FLOOR = 0.05          # scale cutoff: ~10 straight misses
# while every queued group is RU-throttled the drain sleeps this long
# between cover re-checks (bucket refill is time-driven; submits still
# notify the condition immediately)
RC_RETRY_S = 0.01
# per-program-digest device-time attribution map: bounded + LRU-evicted
# (analysis/calibrate.BoundedLRU — the same eviction policy the
# calibration correction store uses; the map previously grew per digest
# for the life of the process)
RC_DIGEST_CAP = 64
# copmeter deadline-aware early shedding: a submit whose CORRECTED-cost
# backlog (sum of the queue's measured expected service times) already
# exceeds this is rejected 9003 at the queue head — and an rc-limited
# waiter whose backlog exceeds its own max-queue deadline is rejected
# 8252 — instead of timing out deep in queue.  Only measured digests
# contribute to the backlog, so an uncalibrated process never sheds.
SHED_MAX_BACKLOG_S = 30.0
# supervised-launch transient retry: total Backoffer sleep budget the
# drain will spend re-launching one batch before classifying the
# failure as persistent (DEVICE_FAILED curve, store/backoff.py)
DEFAULT_LAUNCH_RETRY_MS = 2000.0
# seeded jitter for the drain's Backoffer when no FaultPlan is armed:
# retry histories stay reproducible either way
RETRY_JITTER_SEED = 0x5EED

_log = logging.getLogger(__name__)


def _verify_enabled() -> bool:
    """Admission-time plan-contract verification (analysis/contracts):
    on by default, TIDB_TPU_VERIFY_PLAN=0 disables (bisecting aid)."""
    return os.environ.get("TIDB_TPU_VERIFY_PLAN", "") != "0"


class _GroupQ:
    """One resource group's FIFO + stride-scheduler state."""

    __slots__ = ("name", "weight", "vtime", "seq", "queue",
                 "tasks", "wait_ns", "rus", "throttled", "device_ns")

    def __init__(self, name: str, weight: float, seq: int,
                 vtime: float = 0.0):
        self.name = name
        self.weight = max(weight, 1e-6)
        self.vtime = vtime        # accumulated service / weight
        self.seq = seq            # tie-break: registration order
        self.queue: deque = deque()
        self.tasks = 0            # served (lifetime)
        self.wait_ns = 0
        self.rus = 0.0            # priced RUs launched (rc/pricing)
        self.throttled = 0        # drain passes that skipped this group
        self.device_ns = 0        # attributed launch wall time


class DeviceScheduler:
    """Admission queue + weighted-fair drain loop for one device mesh."""

    def __init__(self, max_depth: int = DEFAULT_QUEUE_DEPTH,
                 max_coalesce: int = DEFAULT_MAX_COALESCE):
        self.max_depth = max_depth
        self.max_coalesce = max_coalesce
        self.fusion_enable = True         # tidb_tpu_sched_fusion
        self.window_us = -1               # tidb_tpu_sched_window_us
                                          # (-1 adaptive, 0 off, >0 fixed)
        # per-mesh HBM admission budget (tidb_tpu_sched_hbm_budget):
        # -1 = derive from device memory stats on first structured
        # submit (CPU fallback constant), 0 = unlimited, >0 = bytes
        self.hbm_budget = -1
        self._auto_budget: Optional[int] = None
        # resource control (rc/): RU-bucket enforcement at the drain
        # (tidb_tpu_rc_enable / tidb_tpu_rc_overdraft_ru sysvars); the
        # max-queue deadline bounds how long a throttled waiter queues
        self.rc_enable = True
        self.rc_overdraft_ru = DEFAULT_OVERDRAFT_RU
        self.rc_max_queue_s = DEFAULT_MAX_QUEUE_S
        # copmeter closed-loop calibration (analysis/calibrate;
        # tidb_tpu_cost_calibration sysvar): corrected LaunchCost feeds
        # RU pricing, budget admission, fusion caps, the micro-batch
        # window, and deadline-aware shedding.  Off = the static model
        # untouched, no feedback recorded.
        self.calibration_enable = True
        # copgauge (obs/hbm, tidb_tpu_hbm_ledger sysvar): live HBM
        # ledger accounting at launch begin/finish, measured launch
        # watermarks feeding mem_factor calibration.  Off = the static
        # model byte-identical
        # to the pre-copgauge behavior (mem_factor moves only on OOM).
        self.hbm_enable = True
        self._ledger_obj = None
        # coplace (pd/, tidb_tpu_pd sysvar): cross-process coordination
        # plane.  Off (default) = every path byte-identical to the
        # pre-pd behavior; on = breaker quarantines broadcast to peers
        # and /sched grows a "pd" section.  The coordinator itself is
        # per-Domain (session plumbs it); this flag only gates the
        # scheduler-side hooks.
        self.pd_enable = False
        # launch supervision (faultline): per-digest circuit breaker
        # consulted at submit, transient-retry budget spent at the
        # drain; _retry_sleep is the Backoffer sleep seam (tests)
        self.breaker = CircuitBreaker()
        self.launch_retry_ms = DEFAULT_LAUNCH_RETRY_MS
        self._retry_sleep = time.sleep
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._groups: dict[str, _GroupQ] = {}
        self._depth = 0
        self._gvt = 0.0           # global virtual time (newcomer floor)
        self._thread: Optional[threading.Thread] = None
        self._paused = False
        # micro-batch window bookkeeping: fusion key -> last arrival ns /
        # EWMA arrival gap ns / EWMA hold hit-rate (tiny dicts, cleared
        # when they grow)
        self._fk_last: dict = {}
        self._fk_gap: dict = {}
        self._fk_hit: dict = {}
        # recent task waits, for p50/p99 on /sched and in bench
        self._wait_ring: deque = deque(maxlen=WAIT_SAMPLES)
        # lifetime counters (read by /sched, tests, metrics mirror them)
        self.launches = 0
        self.coalesced_launches = 0       # launches serving >= 2 tasks
        self.coalesced_tasks = 0          # tasks that rode a shared launch
        self.batched_launches = 0         # stacked-slot vmap launches
        self.batched_rows_launches = 0    # rows-kind stacked launches
        self.fused_launches = 0           # cross-query fused launches
        # launches by what their programs say of themselves, and the
        # kernels' events (`count`): names and rules are copr/facts.py's
        from ..copr.facts import counter_names
        self.kernel_counts = Counter(dict.fromkeys(counter_names(), 0))
        self.fused_tasks = 0              # tasks served by a fused launch
        # group launches that raised and were served apart instead: the
        # results are the same, so only these counters (and one log line
        # per digest) say the fused / vmapped program never ran
        self.fused_refused = 0
        self.batched_refused = 0
        self._refusals_logged: set = set()
        # groups of two or more programs (or slots) whose group program
        # was not loaded: served apart, the program remembered for
        # `warm_groups` (NOT a refusal: nothing raised)
        self.groups_apart_unloaded = 0
        self.group_compiles_bg = 0        # group programs compiled, and
        self.group_loads_bg = 0           # loaded from the cache dir,
                                          # by `warm_groups`' threads
        self.dedup_tasks = 0              # waiters that shared another
                                          # task's execution (same key,
                                          # same input token)
        self.window_waits = 0             # drains that held for stragglers
        self.window_hits = 0              # holds that actually gained riders
        self.hold_ns_total = 0            # time those holds took
        self.busy_rejects = 0
        # HBM-budget admission accounting (analysis/copcost LaunchCost)
        self.budget_admitted = 0          # structured tasks costed + admitted
        self.budget_rejects = 0           # solo programs over budget (CostError)
        self.budget_deferrals = 0         # riders left queued by footprint cap
        self.last_launch_bytes = 0        # footprint of the last served batch
        # per-link transfer attribution (shardflow, parallel/topology):
        # statically-priced collective bytes of served tasks, split by
        # link class under the declared host view — the ROADMAP
        # multi-host success metric's static half
        self.transfer_ici_bytes = 0
        self.transfer_dci_bytes = 0
        # buffer-donation accounting (analysis/lifetime DonationPlan)
        self.donated_launches = 0         # launches with donated inputs
        self.donated_tasks = 0            # tasks that requested donation
        self.donated_bytes = 0            # priced input bytes aliased out
        # copforge compile-cache accounting (compilecache/): program
        # resolve/compile time the drain paid, split out of schedWait
        self.compile_ns_total = 0         # summed per-launch resolve time
        self.warm_failures = 0            # background group compiles
                                          # that failed (never surfaced)
        self._warm_alive = 0              # background compile threads
        # group programs waiting for `warm_groups`: cache entry ->
        # (CachedProgram, abstract args); those a thread is working on;
        # and those none will try again (failed, or the bound on group
        # programs was reached without them)
        self._groups_pending: dict = {}
        self._groups_inflight: set = set()
        self._groups_settled: set = set()
        # supervised-launch accounting (faultline)
        self.retried_launches = 0         # serve attempts re-run after a
                                          # transient launch failure
        self.retried_tasks = 0            # member tasks those retries span
        self.bisected_launches = 0        # failed group launches demuxed
                                          # for blast-radius isolation
        self.quarantined = 0              # submits failed fast by an OPEN
                                          # breaker (LaunchQuarantinedError)
        self.value_drifts = 0             # admitted tasks whose observed
                                          # column watermarks escaped the
                                          # plan's declared value interval
                                          # (valueflow stats drift)
        # rc enforcement accounting (rc/controller)
        self.rc_throttled = 0             # drain passes that skipped a group
        self.rc_exhausted = 0             # waiters failed at the deadline
        self.rc_debited_ru = 0.0          # priced RUs debited pre-launch
        # program digest -> device ns, bounded + LRU (shared eviction
        # policy with the calibration correction store)
        from ..analysis.calibrate import BoundedLRU
        self._digest_ns = BoundedLRU(RC_DIGEST_CAP)
        # copmeter accounting (analysis/calibrate)
        self.oom_faults = 0               # OOM-classified launch failures
        self.oom_demuxed = 0              # OOM group launches retried at
                                          # reduced fusion width
        self.shed_rejects = 0             # submits shed at the queue head
                                          # (corrected-cost backlog over
                                          # the waiter's deadline)
        self._backlog_ns = 0              # expected service ns queued
        self.tasks_done = 0
        from ..utils.metrics import global_registry
        reg = global_registry()
        self._m_depth = reg.gauge("tidb_tpu_sched_queue_depth",
                                  "device admission queue depth")
        self._m_tasks = reg.counter("tidb_tpu_sched_tasks_total",
                                    "cop tasks admitted", labels=("group",))
        self._m_busy = reg.counter("tidb_tpu_sched_busy_total",
                                   "admission rejections (queue full)")
        self._m_launch = reg.counter("tidb_tpu_sched_launch_total",
                                     "device launches", labels=("mode",))
        self._m_coal = reg.counter("tidb_tpu_sched_coalesced_tasks_total",
                                   "tasks served by a shared launch")
        self._m_fused = reg.counter("tidb_tpu_sched_fused_tasks_total",
                                    "tasks served by a cross-query "
                                    "fused launch")
        self._m_apart = reg.counter(
            "tidb_tpu_sched_groups_apart_unloaded_total",
            "groups served apart because their group program was not "
            "loaded")
        self._m_group_bg = reg.counter(
            "tidb_tpu_sched_group_programs_bg_total",
            "group programs the background thread compiled or loaded",
            labels=("outcome",))
        self._m_dedup = reg.counter(
            "tidb_tpu_sched_dedup_tasks_total",
            "tasks that shared another task's execution")
        self._m_wait = reg.histogram("tidb_tpu_sched_wait_seconds",
                                     "admission queue wait")
        self._m_ru = reg.counter("tidb_tpu_sched_ru_total",
                                 "request units launched", labels=("group",))
        self._m_budget = reg.gauge("tidb_tpu_sched_hbm_budget_bytes",
                                   "per-mesh HBM admission budget")
        self._m_launch_bytes = reg.gauge(
            "tidb_tpu_sched_launch_bytes",
            "estimated device bytes of the last served launch")
        self._m_badmit = reg.counter(
            "tidb_tpu_sched_budget_admitted_total",
            "structured tasks admitted under the HBM budget")
        self._m_brej = reg.counter(
            "tidb_tpu_sched_budget_rejects_total",
            "tasks rejected pre-trace: footprint over the HBM budget")
        self._m_bdefer = reg.counter(
            "tidb_tpu_sched_budget_deferrals_total",
            "riders deferred from a launch by the summed-footprint cap")
        self._m_ici = reg.counter(
            "tidb_tpu_sched_transfer_ici_bytes_total",
            "statically-priced same-host inter-chip collective bytes "
            "of served tasks (shardflow link attribution)")
        self._m_dci = reg.counter(
            "tidb_tpu_sched_transfer_dci_bytes_total",
            "statically-priced cross-host collective bytes of served "
            "tasks under the declared host view")
        self._m_donated = reg.counter(
            "tidb_tpu_sched_donated_bytes_total",
            "input bytes aliased into outputs by buffer donation")
        self._m_retried = reg.counter(
            "tidb_tpu_sched_retried_total",
            "tasks re-launched after a transient device failure")
        self._m_quar = reg.counter(
            "tidb_tpu_sched_quarantined_total",
            "submits failed fast by an OPEN program circuit breaker")
        self._m_bisect = reg.counter(
            "tidb_tpu_sched_bisected_total",
            "failed group launches demuxed for blast-radius isolation")
        # resource control plane (rc/): admission-side RU enforcement
        self._m_rc_throttle = reg.counter(
            "tidb_tpu_rc_throttled_total",
            "drain passes that skipped an RU-exhausted group",
            labels=("group",))
        self._m_rc_exhaust = reg.counter(
            "tidb_tpu_rc_exhausted_total",
            "waiters failed at the rc max-queue deadline",
            labels=("group",))
        self._m_rc_debit = reg.counter(
            "tidb_tpu_rc_ru_debited_total",
            "priced RUs debited pre-launch", labels=("group",))
        self._m_rc_overdraft = reg.gauge(
            "tidb_tpu_rc_overdraft_ru",
            "bounded RU overdraft the drain tolerates per group")
        self._m_rc_overdraft.set(self.rc_overdraft_ru)
        # copmeter (analysis/calibrate): OOM recovery + early shedding
        self._m_oom = reg.counter(
            "tidb_tpu_sched_oom_total",
            "OOM-classified launch failures recovered without charging "
            "the circuit breaker")
        self._m_shed = reg.counter(
            "tidb_tpu_sched_shed_total",
            "submits shed at the queue head: corrected-cost backlog "
            "already exceeded the waiter's deadline")
        # copscope (obs/): millisecond latency histograms — the
        # prometheus-scrapeable successors of the ad-hoc p50/p99 wait
        # ring (which /sched keeps for back-compat); bench pulls its
        # percentiles from these
        from ..utils.metrics import Histogram
        ms = Histogram.MS_BUCKETS
        self._m_wait_ms = reg.histogram(
            "tidb_tpu_sched_wait_ms",
            "admission queue wait per task (ms)", buckets=ms)
        self._m_launch_ms = reg.histogram(
            "tidb_tpu_sched_launch_ms",
            "device launch wall time per launch (ms)", buckets=ms)
        self._m_hold_ms = reg.histogram(
            "tidb_tpu_sched_hold_ms",
            "micro-batch window hold on a lead (ms)", buckets=ms)
        self._m_compile_ms = reg.histogram(
            "tidb_tpu_sched_compile_ms",
            "program resolve/compile time per launch (ms)", buckets=ms)
        self._m_agg_ms = reg.histogram(
            "tidb_tpu_agg_launch_ms",
            "agg launch wall time by group strategy (ms)", buckets=ms,
            labels=("strategy",))
        # copgauge (obs/hbm): the admission budget mirrored into the
        # tidb_tpu_hbm_* gauge family next to the ledger's
        # resident/watermark gauges
        self._m_hbm_budget = reg.gauge(
            "tidb_tpu_hbm_budget_bytes",
            "per-mesh HBM admission budget (copgauge gauge family "
            "twin of tidb_tpu_sched_hbm_budget_bytes)")

    # ------------------------------------------------------------- #
    # admission
    # ------------------------------------------------------------- #

    def configure(self, max_depth: Optional[int] = None,
                  max_coalesce: Optional[int] = None,
                  fusion: Optional[bool] = None,
                  window_us: Optional[int] = None,
                  hbm_budget: Optional[int] = None,
                  rc_enable: Optional[bool] = None,
                  rc_overdraft: Optional[float] = None,
                  calibration: Optional[bool] = None,
                  hbm_ledger: Optional[bool] = None,
                  pd_enable: Optional[bool] = None) -> None:
        """Apply sysvar knobs; negative/None = keep current (window_us
        and hbm_budget are the exceptions: -1 means adaptive/auto,
        0 disables the hold / the budget)."""
        if max_depth is not None and max_depth > 0:
            self.max_depth = max_depth
        if max_coalesce is not None and max_coalesce > 0:
            self.max_coalesce = max_coalesce
        if fusion is not None:
            self.fusion_enable = bool(fusion)
        if window_us is not None and window_us >= -1:
            self.window_us = int(window_us)
        if hbm_budget is not None and hbm_budget >= -1:
            self.hbm_budget = int(hbm_budget)
        if rc_enable is not None:
            self.rc_enable = bool(rc_enable)
        if rc_overdraft is not None and rc_overdraft >= 0:
            self.rc_overdraft_ru = float(rc_overdraft)
            self._m_rc_overdraft.set(self.rc_overdraft_ru)
        if calibration is not None:
            self.calibration_enable = bool(calibration)
        if hbm_ledger is not None:
            self.hbm_enable = bool(hbm_ledger)
        if pd_enable is not None:
            self.pd_enable = bool(pd_enable)

    # ---- HBM-budget admission (analysis/copcost) -------------------- #

    def effective_budget(self, mesh=None) -> int:
        """Resolved per-mesh budget in bytes; 0 = unlimited.  -1 (auto)
        derives from the mesh's device memory stats once, with a host
        fallback on backends that report none (CPU meshes)."""
        b = self.hbm_budget
        if b >= 0:
            return b
        if self._auto_budget is None:
            if mesh is None:
                return 0          # nothing to derive from yet
            from ..analysis.copcost import mesh_hbm_budget
            self._auto_budget = mesh_hbm_budget(mesh)
            self._m_budget.set(self._auto_budget)
        return self._auto_budget

    # ---- copmeter (analysis/calibrate): measured-cost correction ----- #

    @staticmethod
    def _stable_digest(task) -> Optional[str]:
        """Restart-stable digest of a structured task's program — the
        key the correction store, the copforge manifest, and the
        quarantine purge all share.  None for opaque tasks."""
        if task.dag is None:
            return None
        from ..analysis.compilekey import stable_digest
        return stable_digest(task.dag)

    def _calibrated_cost(self, task, cost):
        """Corrected LaunchCost for admission/pricing (clamped EWMA
        factors from the correction store); the static cost stays on
        ``task.cost_static`` so feedback never compounds on itself."""
        digest = self._stable_digest(task)
        if digest is None:
            return cost
        from ..analysis.calibrate import correction_store
        return correction_store().corrected_cost(digest, cost)

    def _expected_ns(self, task) -> int:
        """Measured expected service time of this task's program (EWMA,
        ns; 0 = never measured) — the shedding backlog unit."""
        if not self.calibration_enable:
            return 0
        digest = self._stable_digest(task)
        if digest is None:
            return 0
        from ..analysis.calibrate import correction_store
        return correction_store().expected_ns(digest)

    def _admit_cost(self, task: CopTask) -> None:
        """Static-footprint gate, run in the submitting thread BEFORE
        the drain loop could trace/compile anything: the task's
        LaunchCost (abstract shape/bytes walk, array metadata only) must
        fit the per-mesh budget, and every device node must have a
        statically derivable bound.  With calibration on, the budget
        comparison (and everything downstream: pricing, fusion caps,
        attribution weights) uses the CORRECTED cost."""
        from ..analysis.copcost import CostError, format_bytes, task_cost
        cost = task.cost_static = task.cost = task_cost(task)
        if cost is None:
            return
        if self.calibration_enable:
            cost = task.cost = self._calibrated_cost(task, cost)
        p = ("sched", type(task.dag).__name__)
        if cost.unbounded:
            raise CostError(
                "cost-unbounded", p,
                "no static device-footprint bound derivable for "
                f"{', '.join(cost.unbounded)}")
        if cost.dense_blowups:
            # degenerate DENSE at large NDV: the plan that 1000x-cliffed
            # (and at sf>=10 crashed) the real-TPU hndv rung — reject
            # pre-trace: such keys group by SORT
            path, groups, rows = cost.dense_blowups[0]
            with self._mu:
                self.budget_rejects += 1
            self._m_brej.inc()
            raise CostError(
                "dense-blowup", p,
                f"DENSE aggregation at {path} holds {groups} group "
                f"states for {rows} per-device rows — degenerate "
                "large-NDV dense domain; group by GroupStrategy.SORT")
        budget = self.effective_budget(task.mesh)
        # copgauge: the prediction the budget gate enforces — surfaced
        # on the launch span (hbm_predicted) and in EXPLAIN ANALYZE
        # next to the measured peak
        task.hbm_predicted = cost.peak_hbm_bytes
        self._m_hbm_budget.set(budget)
        if budget > 0 and cost.peak_hbm_bytes > budget:
            with self._mu:
                self.budget_rejects += 1
            self._m_brej.inc()
            raise CostError(
                "hbm-budget", p,
                f"estimated peak device bytes "
                f"{format_bytes(cost.peak_hbm_bytes)} exceed the mesh "
                f"admission budget {format_bytes(budget)} "
                "(tidb_tpu_sched_hbm_budget)")
        with self._mu:
            self.budget_admitted += 1
        self._m_badmit.inc()

    def _shed_locked(self, task: CopTask) -> None:
        """Deadline-aware early shedding (copmeter), called with _cv
        held BEFORE the task queues: when the corrected-cost backlog —
        the sum of measured expected service times already queued —
        provably exceeds what this waiter can tolerate, fail it at the
        queue HEAD (rc waiters with the MySQL-compatible 8252, others
        with the 9003 busy error) instead of letting it time out deep
        in queue.  Conservative by construction: only MEASURED digests
        contribute to the backlog, so a cold process never sheds."""
        if not self.calibration_enable or self._backlog_ns <= 0:
            return
        deadline_ns = None
        if task.deadline_ns:
            deadline_ns = int(self.rc_max_queue_s * 1e9)
        elif self._backlog_ns > int(SHED_MAX_BACKLOG_S * 1e9):
            deadline_ns = int(SHED_MAX_BACKLOG_S * 1e9)
        if deadline_ns is None or self._backlog_ns <= deadline_ns:
            return
        self.shed_rejects += 1
        self._m_shed.inc()
        if task.key is not None:
            # same slot hygiene as the busy path: a shed HALF_OPEN
            # probe must release its probe slot
            self.breaker.abort_probe(task.key[0])
        if task.deadline_ns:
            raise ResourceExhaustedError(
                task.group, self._backlog_ns / 1e9, task.rus)
        raise ServerBusyError(self.max_depth)

    def _backlog_sub_locked(self, task: CopTask) -> None:
        """A queued task left the queue (served, expired, cancelled):
        release its expected-service contribution (called with _cv
        held; clamped so bookkeeping drift can never wedge admission)."""
        self._backlog_ns = max(self._backlog_ns - task.svc_ns, 0)

    @staticmethod
    def _marginal_bytes(t: CopTask, lead: CopTask) -> int:
        """Bytes a rider ADDS to lead's launch: its payload only when it
        shares lead's resident scan (fusion / in-flight dedup), its full
        footprint when it brings distinct inputs (batch-slot stacking)."""
        if t.cost is None:
            return 0
        if t.input_token == lead.input_token:
            return t.cost.peak_hbm_bytes - t.cost.input_bytes
        return t.cost.peak_hbm_bytes

    def submit(self, task: CopTask) -> CopTask:
        """Enqueue; raises ServerBusyError when the bounded queue is
        full (backpressure instead of unbounded buffering).  Structured
        tasks are contract-verified AND cost-gated on admission — a
        malformed task (capacity-shape drift, stale mesh key, invalid
        DAG) or an over-budget program is rejected with a structured
        PlanContractError/CostError HERE, in the submitting thread,
        before the drain loop would trace/compile anything.  All of it,
        down to the enqueue, is the statement thread's ``sched.admit``
        span (a child of its ``cop.dispatch``)."""
        with _obs.span("sched.admit"):
            if task.key is not None and _verify_enabled():
                from ..analysis.contracts import verify_task
                verify_task(task)
                self._admit_cost(task)
                if task.value_drift:
                    # valueflow watermark drift: the plan's declared value
                    # interval no longer contains the observed ANALYZE
                    # watermark — never wrong (proofs carry append
                    # headroom), but the operator should re-ANALYZE
                    with self._mu:
                        self.value_drifts += task.value_drift
            if task.key is not None:
                # circuit breaker: a digest whose launches keep failing is
                # quarantined HERE, in the submitting thread — fail fast
                # with the structured error the client's host fallback
                # understands, instead of re-crashing the device
                try:
                    self.breaker.admit(task.key[0])
                except LaunchQuarantinedError:
                    with self._mu:
                        self.quarantined += 1
                    self._m_quar.inc()
                    self._trace_mark(task, "sched.quarantine",
                                     digest=self._digest_hex(task.key[0]))
                    if task.trace is not None:
                        task.trace.tree.flag("quarantined")
                    raise
            # rc pricing happens HERE, in the submitting thread: structured
            # tasks price from the LaunchCost the admission gate just
            # computed, opaque tasks from their row estimate — the drain
            # only compares/debits, never prices
            task.rus = task_rus(task)
            if self.rc_enable and task.rc_group is not None \
                    and task.rc_group.limited:
                task.deadline_ns = task.submit_ns + \
                    int(self.rc_max_queue_s * 1e9)
            # copmeter: the task's measured expected service time (0 when
            # the digest was never measured) — computed OUTSIDE the lock
            task.svc_ns = self._expected_ns(task)
            with self._cv:
                if self._depth >= self.max_depth:
                    self.busy_rejects += 1
                    self._m_busy.inc()
                    if task.key is not None:
                        # an admitted HALF_OPEN probe that never queues must
                        # release its slot or no probe could ever run
                        self.breaker.abort_probe(task.key[0])
                    raise ServerBusyError(self.max_depth)
                self._shed_locked(task)
                g = self._groups.get(task.group)
                if g is None:
                    g = self._groups[task.group] = _GroupQ(
                        task.group, task.weight, len(self._groups),
                        vtime=self._gvt)
                else:
                    g.weight = max(task.weight, 1e-6)
                    if not g.queue:
                        # re-activating group: forfeit banked idle time so it
                        # cannot starve others (stride newcomer rule)
                        g.vtime = max(g.vtime, self._gvt)
                g.queue.append(task)
                self._depth += 1
                self._backlog_ns += task.svc_ns
                self._note_arrival(task)
                self._m_depth.set(self._depth)
                self._m_tasks.inc(group=task.group)
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._loop, name="sched-drain", daemon=True)
                    self._thread.start()
                self._cv.notify_all()
        # the tree's sched.queue span runs from the end of sched.admit,
        # so that the two share no time (the drain cannot have the
        # interpreter before this thread blocks; where it did, the span
        # starts at its pick-up); wait_ns (/sched wait_p50_ms) keeps
        # counting from submit_ns
        task.enqueue_ns = time.perf_counter_ns()
        return task

    def pause(self) -> None:
        """Hold the drain loop (tests / maintenance); submits still queue."""
        with self._cv:
            self._paused = True

    def resume(self) -> None:
        with self._cv:
            self._paused = False
            self._cv.notify_all()

    # ------------------------------------------------------------- #
    # drain loop
    # ------------------------------------------------------------- #

    def _pick(self) -> Optional[_GroupQ]:
        best = None
        for g in self._groups.values():
            if not g.queue:
                continue
            if self._rc_blocked(g):
                continue
            if best is None or (g.vtime, g.seq) < (best.vtime, best.seq):
                best = g
        return best

    # ---- resource-control enforcement (rc/: priced RU admission) ---- #

    def _task_bucket(self, t):
        """The RU bucket governing task ``t``; None = not enforced
        (rc disabled, no group attached, or the group is unlimited)."""
        if not self.rc_enable or t.rc_group is None:
            return None
        return t.rc_group.bucket if t.rc_group.limited else None

    def _rc_blocked(self, g: _GroupQ) -> bool:
        """May this group's HEAD task launch under its RU budget?  A
        blocked group is skipped by the fair pick — it queues while
        sibling groups keep launching (tikv unified-read-pool deadline
        behavior); cancelled heads always pass so the drain can fail
        them out of the queue."""
        head = g.queue[0]
        if head.cancelled:
            return False
        b = self._task_bucket(head)
        if b is None or b.can_cover(head.rus, self.rc_overdraft_ru):
            return False
        g.throttled += 1
        self.rc_throttled += 1
        self._m_rc_throttle.inc(group=g.name)
        return True

    def _rc_covers(self, t, lead) -> bool:
        """May ``t`` ride lead's launch under t's OWN group budget?  A
        rider from an exhausted group must stay queued even when the
        launch itself is free capacity — otherwise fusion would be an
        RU-bypass."""
        b = self._task_bucket(t)
        return b is None or b.can_cover(task_rus(t, lead),
                                        self.rc_overdraft_ru)

    def _rc_debit(self, t, lead=None) -> None:
        """Pre-launch debit at batch admission: the task's priced RUs
        (marginal when it shares lead's resident scan) leave its
        group's bucket BEFORE anything traces or launches.  The check
        ran in _rc_blocked/_rc_covers on this same drain thread, so
        check-then-debit cannot interleave with itself."""
        rus = task_rus(t, lead)
        t.rus_charged = rus
        b = self._task_bucket(t)
        if b is not None:
            b.debit(rus)
            self.rc_debited_ru += rus
            self._m_rc_debit.inc(rus, group=t.group)

    def _rc_expire_locked(self) -> None:
        """Fail throttled waiters that overstayed the max-queue
        deadline with the MySQL-compatible resource-exhausted error
        (called with _cv held).  Only tasks whose bucket STILL cannot
        cover them expire — a covered task merely queued behind load
        keeps waiting for the fair drain."""
        now = time.perf_counter_ns()
        expired = False
        for g in self._groups.values():
            if not g.queue:
                continue
            for t in list(g.queue):
                if not t.deadline_ns or now <= t.deadline_ns:
                    continue
                b = self._task_bucket(t)
                if b is not None and not b.can_cover(
                        t.rus, self.rc_overdraft_ru):
                    g.queue.remove(t)
                    self._depth -= 1
                    self._backlog_sub_locked(t)
                    self.rc_exhausted += 1
                    self._m_rc_exhaust.inc(group=g.name)
                    if t.trace is not None:
                        # the waiter never launched: its whole life was
                        # queue wait — record it with the expiry marked
                        t.trace.add("sched.queue",
                                    min(t.enqueue_ns, now), now,
                                    group=g.name, expired=True)
                    t.fail(ResourceExhaustedError(
                        t.group, (now - t.submit_ns) / 1e9, t.rus))
                    expired = True
        if expired:
            self._m_depth.set(self._depth)

    # ---- adaptive micro-batch window (EWMA of arrival gaps) --------- #

    def _note_arrival(self, task) -> None:
        """Track per-fusion-key arrival gaps (called with _cv held).
        Plain coalescing benefits from the window too, so keyed tasks
        without a fusion key track under their task key."""
        fk = task.fusion_key if task.fusion_key is not None else task.key
        if fk is None:
            return
        if len(self._fk_last) > 256:      # hot keys are few; stay tiny
            self._fk_last.clear()
            self._fk_gap.clear()
            self._fk_hit.clear()
        last = self._fk_last.get(fk)
        self._fk_last[fk] = task.submit_ns
        if last is None:
            return
        gap = min(task.submit_ns - last, WINDOW_GAP_CLAMP_NS)
        prev = self._fk_gap.get(fk)
        self._fk_gap[fk] = gap if prev is None else \
            0.7 * prev + 0.3 * gap

    def _window_ns(self, lead) -> int:
        """How long the drain may hold `lead` waiting for stragglers.
        Fixed when the sysvar pins it; adaptive (-1) holds 2x the key's
        EWMA arrival gap SCALED by the key's observed hold hit-rate
        (window feedback: a key whose holds rarely yield riders decays
        its window toward zero and stops paying the hold at all), and
        only when the base window fits the cap — a key whose matches
        arrive slowly never delays its own launch."""
        if lead.key is None:
            return 0
        if self.window_us == 0:
            return 0
        if self.window_us > 0:
            return self.window_us * 1000
        fk = lead.fusion_key if lead.fusion_key is not None else lead.key
        gap = self._fk_gap.get(fk)
        if gap is None:
            return 0
        w = int(2 * gap)
        if w > WINDOW_CAP_US * 1000:
            return 0
        scale = min(1.0, self._fk_hit.get(fk, WINDOW_HIT_INIT)
                    / WINDOW_HIT_INIT)
        if scale < WINDOW_HIT_FLOOR:
            return 0
        w = int(w * scale)
        if self.calibration_enable:
            # copmeter window feed: a hold only pays when it is small
            # next to the launch it delays — cap the hold at a quarter
            # of the digest's MEASURED launch time, so a program the
            # calibration knows to be fast never waits longer than it
            # would run
            exp = self._expected_ns(lead)
            if exp:
                w = min(w, exp // 4)
        return w

    def _note_window_outcome(self, lead, hit: bool) -> None:
        """Feed one hold's outcome back into the key's hit-rate EWMA
        (called with _cv held, right after the hold resolves)."""
        fk = lead.fusion_key if lead.fusion_key is not None else lead.key
        if fk is None:
            return
        prev = self._fk_hit.get(fk, WINDOW_HIT_INIT)
        self._fk_hit[fk] = ((1.0 - WINDOW_HIT_ALPHA) * prev
                            + WINDOW_HIT_ALPHA * (1.0 if hit else 0.0))
        if hit:
            self.window_hits += 1

    # ---- batch assembly --------------------------------------------- #

    def _rides(self, t, lead) -> bool:
        """May `t` share lead's launch?  Same program (in-flight dedup /
        batch-slot stacking) or same fusion key with a different digest
        (cross-query fusion: one scan, many payloads)."""
        if t.cancelled:
            return False
        if (t.key == lead.key and t.mesh is lead.mesh
                and (t.dag is lead.dag or t.dag == lead.dag)):
            return True
        return (self.fusion_enable
                and lead.fusion_key is not None
                and t.fusion_key == lead.fusion_key
                and t.mesh is lead.mesh)

    def _collect_riders(self, lead, batch: list) -> None:
        """Pop every queued rider across ALL groups — coalescing and
        fusion are cross-session by design.  Each rider charges its own
        group's virtual time.  Group size is capped by SUMMED static
        footprint (analysis/copcost LaunchCost) against the mesh budget
        — the scan is paid once, but every distinct payload/input adds
        HBM, so a fused group must fit as a whole — with the member
        count cap (tidb_tpu_sched_max_coalesce) still the outer bound."""
        budget = self.effective_budget(lead.mesh)
        footprint = lead.cost.peak_hbm_bytes if lead.cost is not None else 0
        for og in self._groups.values():
            if len(batch) >= self.max_coalesce:
                break
            kept: deque = deque()
            while og.queue:
                t = og.queue.popleft()
                if len(batch) < self.max_coalesce \
                        and self._rides(t, lead) \
                        and self._rc_covers(t, lead):
                    add = self._marginal_bytes(t, lead)
                    if budget > 0 and footprint and \
                            footprint + add > budget:
                        # over the summed-footprint cap: defer — the
                        # rider stays queued and leads a later launch
                        self.budget_deferrals += 1
                        self._m_bdefer.inc()
                        kept.append(t)
                        continue
                    footprint += add
                    self._rc_debit(t, lead)
                    batch.append(t)
                    self._depth -= 1
                    self._backlog_sub_locked(t)
                    og.vtime += 1.0 / og.weight
                    og.tasks += 1
                else:
                    kept.append(t)
            og.queue = kept

    def _take_batch(self) -> list:
        """Pop the fair-ordered head task plus every compatible queued
        rider; optionally hold inside the micro-batch window so
        stragglers that are statistically about to arrive (EWMA of the
        key's arrival gaps) coalesce/fuse instead of launching apart."""
        self._rc_expire_locked()
        g = self._pick()
        if g is None:
            return []
        lead = g.queue.popleft()
        self._depth -= 1
        self._backlog_sub_locked(lead)
        g.vtime += 1.0 / g.weight
        self._gvt = g.vtime
        g.tasks += 1
        if lead.cancelled:
            self._m_depth.set(self._depth)
            lead.fail(TaskCancelledError())
            return [None]          # sentinel: retry pick
        self._rc_debit(lead)
        batch = [lead]
        if lead.key is not None:
            self._collect_riders(lead, batch)
            w_ns = self._window_ns(lead)
            if w_ns > 0 and len(batch) < self.max_coalesce:
                # wait-for-stragglers: _cv.wait releases the lock, so
                # submits land and notify; re-collect after each wake
                held_ns = time.perf_counter_ns()
                deadline = held_ns + w_ns
                self.window_waits += 1
                held_at = len(batch)
                while len(batch) < self.max_coalesce:
                    rem_ns = deadline - time.perf_counter_ns()
                    if rem_ns <= 0:
                        break
                    self._cv.wait(rem_ns / 1e9)
                    self._collect_riders(lead, batch)
                # window feedback: did the hold actually gain riders?
                gained = len(batch) - held_at
                self._note_window_outcome(lead, gained > 0)
                # the tree's sched.hold, on every task of the batch
                hold = (held_ns, time.perf_counter_ns(), gained)
                self.hold_ns_total += hold[1] - held_ns
                self._m_hold_ms.observe((hold[1] - held_ns) / 1e6)
                for t in batch:
                    t.hold = hold
        self._m_depth.set(self._depth)
        return batch

    def _loop(self) -> None:
        idle_since = time.monotonic()
        while True:
            with self._cv:
                while self._paused or self._depth == 0:
                    if self._depth == 0 and not self._paused and \
                            time.monotonic() - idle_since > IDLE_EXIT_S:
                        self._thread = None
                        return
                    self._cv.wait(timeout=0.5)
                    if not self._paused and self._depth == 0:
                        continue
                batch = self._take_batch()
                if not batch and self._depth > 0:
                    # every queued group is RU-throttled: their waiters
                    # stay queued until a bucket refill covers a head
                    # task or the max-queue deadline expires them
                    # (_rc_expire_locked ran inside _take_batch); sleep
                    # briefly — submits still notify the condition
                    self._cv.wait(timeout=RC_RETRY_S)
            idle_since = time.monotonic()
            if not batch or batch == [None]:
                continue
            now = time.perf_counter_ns()
            for t in batch:
                t.start_ns = now
                t.wait_ns = now - t.submit_ns
            self._note_launch_bytes(batch)
            # copgauge: launch-scoped bytes enter the ledger at
            # admission and leave at finish; the measured watermark
            # (stamped by _mem_note inside the serve) feeds it after
            led = self._ledger(batch[0].mesh)
            eph = self._launch_ephemeral_bytes(batch) \
                if led is not None else 0
            if led is not None:
                led.launch_begin(eph)
                self._mem_mark()
            try:
                self._serve_supervised(batch)
            except BaseException as e:  # noqa: BLE001 supervisor safety
                for t in batch:         # net: the drain must never die
                    t.fail(e)
            finally:
                if led is not None:
                    led.launch_end(eph)
                    measured = max(
                        (t.hbm_measured for t in batch), default=0)
                    if measured > 0:
                        led.note_measured(measured)
            self._attribute_launch(batch,
                                   time.perf_counter_ns() - now)
            self._account(batch)

    # ------------------------------------------------------------- #
    # copgauge (obs/hbm): live ledger + measured launch watermarks
    # ------------------------------------------------------------- #

    def _ledger(self, mesh):
        """This mesh's live HBM ledger; None when copgauge is off."""
        if not self.hbm_enable or mesh is None:
            return None
        led = self._ledger_obj
        if led is None:
            from ..obs.hbm import ledger_for
            from .task import mesh_fingerprint
            led = self._ledger_obj = ledger_for(mesh_fingerprint(mesh))
        return led

    def _launch_ephemeral_bytes(self, batch: list) -> int:
        """EPHEMERAL/LOOP-CARRIED bytes this launch adds ON TOP of the
        persistent residents: the lead's peak minus its resident scan
        (live snapshot-cache inputs are already on the ledger's
        persistent side), plus each rider's marginal bytes.  Donated
        bytes are credited at dispatch by construction —
        ``peak_hbm_bytes`` already subtracts ``donated_bytes``."""
        lead = batch[0]
        if lead.cost is None:
            return 0
        n = lead.cost.peak_hbm_bytes
        from ..analysis.lifetime import is_resident
        if is_resident(lead.counts):
            n -= lead.cost.input_bytes
        n += sum(self._marginal_bytes(t, lead) for t in batch[1:])
        return max(n, 0)

    @staticmethod
    def _mem_mark() -> None:
        """Reset the drain thread's executable-memory high-water before
        a serve (the copforge measured-watermark seam)."""
        from ..compilecache import compile_cache
        compile_cache().thread_mem_mark()

    def _mem_note(self, tasks: list, mesh) -> int:
        """Measured peak of the launch that just ran on this thread:
        the compiled memory analysis of the ACTUALLY-SERVED executable
        (per-device, scaled by mesh size), stamped onto every task
        BEFORE finish so waiters/EXPLAIN observe it.  Live memory_stats
        never rides here — the ledger's bounded ``reconcile`` owns that
        poll, off the launch path.  0 = backend reports nothing."""
        if not self.hbm_enable:
            return 0
        from ..compilecache import compile_cache
        per_dev = compile_cache().thread_mem_take()
        if per_dev <= 0:
            return 0
        n_dev = int(mesh.devices.size) if mesh is not None else 1
        measured = per_dev * n_dev
        for t in tasks:
            t.hbm_measured = measured
        return measured

    # ------------------------------------------------------------- #
    # copforge (compilecache/): compile attribution + fusion warmup
    # ------------------------------------------------------------- #

    @staticmethod
    def _cc_mark() -> tuple:
        """Drain-thread snapshot of the compile cache's per-thread
        resolve totals (ns, misses, hits) — deltas around a launch are
        THIS launch's compile bill, uncontaminated by other threads."""
        from ..compilecache import compile_cache
        return compile_cache().thread_snapshot()

    def _cc_note(self, tasks: list, mark: tuple) -> None:
        """Attribute the resolve/compile time since ``mark`` to every
        task of the launch BEFORE it finishes, so waiters always observe
        it: this is the ``compile_wait_ms`` split out of schedWait — a
        deduped rider that queued while the lead traced sees WHERE its
        wait went (satellite: Avg_compile_ms in statements_summary)."""
        from ..compilecache import compile_cache
        ns, misses, _hits = compile_cache().thread_snapshot()
        dns, dmiss = ns - mark[0], misses - mark[1]
        if dns <= 0 and dmiss <= 0:
            return
        self.compile_ns_total += dns
        if dns > 0:
            # copscope: resolve/compile latency histogram (the span
            # twin is recorded per launch in _trace_launch)
            self._m_compile_ms.observe(dns / 1e6)
        for t in tasks:
            t.compile_ns += dns
            if dmiss:
                t.compile_miss = True

    def _group_unloaded(self, tasks: list, prog, args) -> None:
        """A group of two or more programs (or slots) formed and its
        group program ``prog`` is not loaded.  The drain never compiles
        one where clients wait, and nothing else compiles one by itself
        either: the caller serves the members apart in this turn, by
        their solo programs, and the missing program is remembered (a
        bounded list) for an explicit ``warm_groups``.  ``args``: the
        program's call as shapes (``abstract_args``), which hold no
        array."""
        from ..compilecache import GROUP_PROGRAMS_MAX
        for t in tasks:
            t.apart = True
        entry = prog._cached.entry_hex(args)
        with self._mu:
            self.groups_apart_unloaded += 1
            if entry not in self._groups_settled \
                    and entry not in self._groups_inflight \
                    and len(self._groups_pending) < GROUP_PROGRAMS_MAX:
                self._groups_pending.setdefault(entry,
                                                (prog._cached, args))
        self._m_apart.inc()

    def warm_groups(self) -> int:
        """The explicit warm: load or compile, on background threads
        (two alive at the most), the group programs of the member sets
        that co-occurred and were served apart.  With a cache directory
        they persist, and boot replay (compilecache/warmup) brings them
        back with the next process.  -> how many were waiting."""
        with self._mu:
            n = len(self._groups_pending)
            start = max(min(n, 2 - self._warm_alive), 0)
            self._warm_alive += start
        for _ in range(start):
            # not a daemon: a process that exits inside an XLA compile
            # aborts
            threading.Thread(target=self._group_worker,
                             name="copforge-group", daemon=False).start()
        return n

    def _group_worker(self) -> None:
        """``warm_groups``' thread: load or compile the pending group
        programs, oldest first, through ``CompileCache.warm_group``,
        which holds them to ``GROUP_PROGRAMS_MAX``: what exists beyond
        this process counts, so once a cache directory holds that many
        no process over it compiles another, and a set that is not
        among them is served apart for good.  Never surfaced on
        failure: the members were served apart already."""
        while True:
            with self._mu:
                if not self._groups_pending:
                    self._warm_alive -= 1
                    return
                entry = next(iter(self._groups_pending))
                cached, args = self._groups_pending.pop(entry)
                self._groups_inflight.add(entry)
            try:
                outcome = cached.warm_group(args)
            except Exception as e:   # noqa: BLE001 - a pure optimization:
                # an unfusable set or a backend refusal means the set
                # stays apart (counted as warm_failures below)
                outcome = "failed"
                _log.warning("background group compile failed: %s",
                             self._err_label(e))
            with self._mu:
                self._groups_inflight.discard(entry)
                if outcome == "compiled":
                    self.group_compiles_bg += 1
                elif outcome == "loaded":
                    self.group_loads_bg += 1
                elif outcome in ("failed", "full"):
                    if len(self._groups_settled) > 1024:
                        self._groups_settled.clear()
                    self._groups_settled.add(entry)
                    if outcome == "failed":
                        self.warm_failures += 1
            if outcome in ("compiled", "loaded"):
                self._m_group_bg.inc(outcome=outcome)

    # ------------------------------------------------------------- #
    # copscope span recording (obs/): the drain's side of the trace
    # ------------------------------------------------------------- #

    @staticmethod
    def _digest_hex(dag_digest: int) -> str:
        """A task key's (process-local) dag digest as /sched prints it."""
        return f"{dag_digest & ((1 << 64) - 1):016x}"

    @staticmethod
    def _err_label(e: BaseException) -> str:
        return f"{type(e).__name__}: {str(e)[:80]}"

    @staticmethod
    def _strategy_of(dag) -> Optional[str]:
        s = getattr(dag, "strategy", None)
        return getattr(s, "value", None) if s is not None else None

    @staticmethod
    def _live_launch(tasks: list, mode: str, program: str = ""):
        """The profiler annotation that brackets one launch's resolve +
        dispatch on the drain thread, under the first traced task's
        trace id (``_trace_launch`` records the tree spans afterwards)."""
        ctx = next((t.trace for t in tasks if t.trace is not None), None)
        return _obs.live("sched.launch", ctx, mode=mode, program=program)

    @contextlib.contextmanager
    def _epilogue(self, tasks: list):
        """``sched.epilogue``: what the drain does between a launch
        span's end and ``finish()`` (the launch's spans and histograms,
        its facts' counters).  Entered where ``sched.launch`` ends:
        ``_trace_launch`` opens the tree's span there, for each traced
        task, and this stamps its end, and the finish the waiter's
        ``sched.wake`` starts from, just before the caller finishes
        the tasks."""
        ctx = next((t.trace for t in tasks if t.trace is not None), None)
        with _obs.live("sched.epilogue", ctx):
            yield
            if ctx is not None:
                now = time.perf_counter_ns()
                for t in tasks:
                    if t.epilogue is not None:
                        t.epilogue.end_ns = t.finish_ns = now

    def count(self, name: str) -> None:
        """Bump one of `kernel_counts` for an event no launch carries
        (copr/facts.EVENTS), from whichever thread sees it happen."""
        with self._mu:
            self.kernel_counts[name] += 1

    @staticmethod
    def _trace_mark(t, name: str, **attrs) -> None:
        """Zero-duration marker span on one task's trace (oom / bisect
        / quarantine / fail seams); no-op when untraced."""
        if t.trace is not None:
            now = time.perf_counter_ns()
            t.trace.add(name, now, now, **attrs)

    def _trace_launch(self, tasks: list, start_ns: int, end_ns: int,
                      mode: str, fused: int = 0, program: str = "",
                      said: Optional[dict] = None) -> None:
        """Record one physical launch's scheduler-side span tree +
        latency histograms, on the DRAIN thread BEFORE the tasks
        finish — a waiter rendering its trace right after wait()
        always sees these spans (no post-finish race).

        Per traced task: a ``sched.queue`` span (enqueue -> drain
        pickup; rc debit rides it as the ``ru`` attr) and a
        ``sched.launch`` span (resolve + DISPATCH: the call returns
        once the program is enqueued, before the device has run it)
        carrying the program's name, ``said`` (what the program says
        of itself, as far as copr/facts.py puts it on the span),
        predicted_ms (calibrated
        LaunchCost via copmeter's predict_ms) next to dispatch_ms (the
        span's own wall time), the shardflow per-link transfer breakdown,
        and — as children — the copforge ``sched.compile`` span
        (hit/miss) and the ``sched.fusion`` assembly span with the
        member count and this member's attributed share."""
        wall_ms = (end_ns - start_ns) / 1e6
        self._m_launch_ms.observe(wall_ms)
        for strat in {self._strategy_of(t.dag) for t in tasks} - {None}:
            self._m_agg_ms.observe(wall_ms, strategy=strat)
        if all(t.trace is None for t in tasks):
            return
        lead = tasks[0]
        shares = None
        if fused > 1 or len(tasks) > 1:
            weights = [lead.cost.peak_hbm_bytes
                       if lead.cost is not None else 0]
            weights += [self._marginal_bytes(t, lead) for t in tasks[1:]]
            shares = split_device_time(weights, end_ns - start_ns)
        from ..analysis.calibrate import predict_ms
        for i, t in enumerate(tasks):
            ctx = t.trace
            if ctx is None:
                continue
            attrs = {"mode": mode, "dispatch_ms": round(wall_ms, 3)}
            if program:
                attrs["program"] = program
            attrs.update(said or {})
            if t.cost is not None:
                attrs["predicted_ms"] = round(predict_ms(t.cost), 3)
                bd = t.cost.transfer_breakdown or (0, 0, 0)
                if bd[1] or bd[2]:
                    attrs["ici_bytes"], attrs["dci_bytes"] = bd[1], bd[2]
            # copgauge: the memory axis of the launch span — the
            # admission prediction next to the measured executable peak
            if t.hbm_predicted:
                attrs["hbm_predicted"] = t.hbm_predicted
            if t.hbm_measured:
                attrs["hbm_measured"] = t.hbm_measured
            if t.value_drift:
                # valueflow: declared interval no longer contains the
                # observed watermark (stats drift, not a wrong result)
                attrs["value_drift"] = t.value_drift
            strat = self._strategy_of(t.dag)
            if strat is not None:
                attrs["strategy"] = strat
            if t.retries:
                attrs["retries"] = t.retries
            queued_ns = min(t.enqueue_ns, t.start_ns)
            items = [
                ("sched.queue", queued_ns,
                 t.start_ns, ctx.span_id,
                 {"group": t.group, "ru": round(t.rus_charged, 2)}),
                ("sched.launch", start_ns, end_ns, ctx.span_id, attrs),
            ]
            if t.hold is not None and t.hold[1] > max(t.hold[0], queued_ns):
                # the micro-batch window's hold, as far as this task sat
                # through it: a child of its sched.queue
                items.append(("sched.hold", max(t.hold[0], queued_ns),
                              t.hold[1], ("rel", 0),
                              {"riders_gained": t.hold[2]}))
            if fused <= 1 and start_ns > t.start_ns:
                # the drain between picking the batch up and the launch
                # span: the ledger, the supervisor, the grouping by key
                # (tree only: the statement's cop.dispatch annotation
                # is the innermost in flight; a fused launch's assembly
                # is sched.fusion)
                items.append(("sched.pickup", t.start_ns, start_ns,
                              ctx.span_id, {}))
            if t.compile_ns:
                items.append((
                    "sched.compile", start_ns, start_ns + t.compile_ns,
                    ("rel", 1),
                    {"result": "miss" if t.compile_miss else "hit"}))
            if fused > 1:
                fat = {"members": fused}
                if shares is not None:
                    fat["share_ms"] = round(shares[i] / 1e6, 3)
                items.append(("sched.fusion", t.start_ns, start_ns,
                              ("rel", 1), fat))
            ctx.tree.add_batch(items)
            # open until _epilogue stamps its end, before finish()
            t.epilogue = ctx.tree.open("sched.epilogue", ctx.span_id, {},
                                       start_ns=end_ns)

    def _trace_retry(self, tasks: list, err: BaseException,
                     start_ns: int, end_ns: int) -> None:
        """One transient-failure backoff cycle: a real span covering
        the retry sleep, per affected waiter."""
        label = self._err_label(err)
        for t in tasks:
            if t.trace is not None:
                t.trace.add("sched.retry", start_ns, end_ns,
                            attempt=t.retries, error=label)

    # ------------------------------------------------------------- #
    # launch supervision (faultline)
    # ------------------------------------------------------------- #

    @staticmethod
    def _digests(tasks: list) -> set:
        return {t.key[0] for t in tasks if t.key is not None}

    @staticmethod
    def _is_transient(e: BaseException) -> bool:
        """Retry-worthy launch failures: injected transient faults and
        typed retryable dispatch errors.  Everything else — compile
        errors, device crashes, contract violations — is treated as
        persistent: retrying an identical program would re-crash the
        device, so it fails (and charges the breaker) instead."""
        from ..store.backoff import RegionError
        return isinstance(e, (_faults.TransientFault, RegionError))

    @staticmethod
    def _is_fatal(e: BaseException) -> bool:
        """Never retried, never breaker-charged: cancellation and
        interpreter teardown."""
        from ..copr.coordinator import QueryInterrupted
        return isinstance(e, (TaskCancelledError, QueryInterrupted,
                              KeyboardInterrupt, SystemExit))

    def _launch_backoffer(self):
        from ..store.backoff import Backoffer
        fp = _faults.active()
        rng = fp.backoff_rng() if fp is not None \
            else random.Random(RETRY_JITTER_SEED)
        return Backoffer(max_sleep_ms=self.launch_retry_ms,
                         sleep_fn=self._retry_sleep, rng=rng)

    def _serve_supervised(self, batch: list) -> None:
        """Serve a batch under the retry/breaker contract: transient
        failures re-launch through the DEVICE_FAILED backoff budget
        (already-finished members are never re-run — finish() is
        idempotent and the live filter drops them), persistent failures
        go to blast-radius isolation, cancelled waiters fail typed and
        are never retried.  Successful launches clear their digests'
        breaker state."""
        from ..store.backoff import DEVICE_FAILED, RetryBudgetExceeded
        bo = None
        while True:
            live = []
            for t in batch:
                if t.done:
                    continue
                if t.cancelled:
                    t.fail(TaskCancelledError())
                    continue
                live.append(t)
            if not live:
                return
            try:
                _faults.check("drain")
                self._serve(live)
            except BaseException as e:  # noqa: BLE001 classified below
                if self._is_fatal(e):
                    for t in live:
                        t.fail(e)
                    return
                if _faults.is_oom_error(e):
                    # memory exhaustion is its own class (copmeter): a
                    # healthy program outgrew the budget — recover by
                    # shrinking the launch, never by charging the
                    # poison breaker
                    self._handle_oom([t for t in batch if not t.done], e)
                    return
                if self._is_transient(e):
                    if bo is None:
                        bo = self._launch_backoffer()
                    retry_t0 = time.perf_counter_ns()
                    try:
                        bo.backoff(DEVICE_FAILED, e)
                    except RetryBudgetExceeded as budget:
                        self._isolate(
                            [t for t in batch if not t.done], budget)
                        return
                    self.retried_launches += 1
                    self.retried_tasks += len(live)
                    self._m_retried.inc(len(live))
                    for t in live:
                        t.retries += 1
                    self._trace_retry(live, e, retry_t0,
                                      time.perf_counter_ns())
                    continue
                self._isolate([t for t in batch if not t.done], e)
                return
            else:
                for d in self._digests(live):
                    self.breaker.record_success(d)
                return

    def _handle_oom(self, live: list, err: BaseException) -> None:
        """OOM-classified launch failure (copmeter): RESOURCE_EXHAUSTED
        / XLA-OOM — a healthy program whose modeled footprint was too
        small, NOT a poisoned kernel.  Bump every member digest's
        memory correction (so future admission sees the bigger
        footprint: budget rejection into streaming, smaller fusion
        groups), retry group launches at reduced fusion width (the
        members relaunch solo), and fail a solo launch to its waiter —
        whose CopClient recovers via streamed batching or the host
        oracle.  The poison circuit breaker is NEVER charged: an OOM
        must not quarantine a program that would fit when resized."""
        self.oom_faults += 1
        self._m_oom.inc()
        for t in live:
            self._trace_mark(t, "sched.oom", error=self._err_label(err))
            if t.trace is not None:
                t.trace.tree.flag("oom")
        if self.calibration_enable:
            from ..analysis.calibrate import correction_store
            store = correction_store()
            for digest in sorted({d for d in map(self._stable_digest,
                                                 live) if d is not None}):
                store.observe_oom(digest)
            store.sync_manifest()
        subs: list = []
        by_member: dict = {}
        for t in live:
            k = (t.key, t.input_token)
            g = by_member.get(k)
            if g is None:
                g = by_member[k] = []
                subs.append(g)
            g.append(t)
        if len(subs) <= 1:
            for t in live:
                t.fail(err)
            return
        self.oom_demuxed += 1
        for sub in subs:
            # reduced fusion width: each member relaunches alone; a
            # member that STILL OOMs solo lands in the fail branch
            # above and its waiter's client degrades (stream / host)
            self._serve_supervised(sub)

    def _isolate(self, live: list, err: BaseException) -> None:
        """Blast-radius isolation: a failed GROUP launch (fused members
        and/or batched slots) is demuxed into its (program, input)
        members and each retried SOLO — innocent riders complete, only
        the poisoned member fails its waiter and charges its digest's
        breaker.  Fusion must never widen a failure domain.  A launch
        that was already solo is the bisection base case: fail + charge."""
        subs: list = []
        by_member: dict = {}
        for t in live:
            k = (t.key, t.input_token)
            g = by_member.get(k)
            if g is None:
                g = by_member[k] = []
                subs.append(g)
            g.append(t)
        if len(subs) <= 1:
            for d in self._digests(live):
                self.breaker.record_failure(d)
                if self.breaker.state(d) == "OPEN":
                    # copforge: an OPEN breaker must not warm-replay
                    # after a restart — purge the digest's manifest
                    # entries (no quarantine laundering)
                    self._cc_quarantine(d, live)
            for t in live:
                self._trace_mark(t, "sched.fail",
                                 error=self._err_label(err))
                t.fail(err)
            return
        self.bisected_launches += 1
        self._m_bisect.inc()
        for t in live:
            self._trace_mark(t, "sched.bisect", members=len(subs))
        for sub in subs:
            # recursion bottoms out: a solo member that fails again
            # lands in the len(subs) <= 1 branch above
            self._serve_supervised(sub)

    def _cc_quarantine(self, digest: int, live: list) -> None:
        """Map the breaker's process-local digest to the restart-stable
        one and purge it from the compile cache's warm manifest."""
        from ..analysis.compilekey import stable_digest
        from ..compilecache import compile_cache
        for t in live:
            if t.key is not None and t.key[0] == digest \
                    and t.dag is not None:
                sd = stable_digest(t.dag)
                compile_cache().quarantine(sd)
                if self.pd_enable:
                    # coplace: tombstone the digest for every peer so
                    # a breaker-opened program is not laundered back
                    # through a peer's warm pool (pd/registry)
                    from ..pd import broadcast_quarantine
                    broadcast_quarantine(sd)
                return

    # ------------------------------------------------------------- #
    # launch
    # ------------------------------------------------------------- #

    def _note_launch_bytes(self, batch: list) -> None:
        """Static footprint of the batch about to launch (scan counted
        once per distinct input, payloads summed) — the bytes gauge the
        budget admission reasons in."""
        lead = batch[0]
        if lead.cost is None:
            return
        est = lead.cost.peak_hbm_bytes + sum(
            self._marginal_bytes(t, lead) for t in batch[1:])
        self.last_launch_bytes = est
        self._m_launch_bytes.set(est)

    def _serve(self, batch: list) -> None:
        lead = batch[0]
        if lead.fn is not None:                     # opaque launch
            # failures PROPAGATE so the supervisor classifies them
            # (transient retry vs fail) instead of failing the waiter
            # on the first error
            _faults.check("launch")
            t_l0 = time.perf_counter_ns()
            with self._live_launch([lead], "opaque", lead.program):
                val = lead.fn()
            self._mem_note([lead], lead.mesh)
            t_l1 = time.perf_counter_ns()
            with self._epilogue([lead]):
                self._trace_launch([lead], t_l0, t_l1, "opaque",
                                   program=lead.program)
            lead.finish(val)
            self.launches += 1
            self._m_launch.inc(mode="single")
            return
        # partition by task key: a fusion batch carries several distinct
        # programs over one shared scan
        programs: list[list] = []
        by_key: dict = {}
        for t in batch:
            grp = by_key.get(t.key)
            if grp is None:
                grp = by_key[t.key] = []
                programs.append(grp)
            grp.append(t)
        if len(programs) > 1 and self._serve_fused(programs):
            return
        for grp in programs:
            self._serve_program(grp)
            self._note_coalesce(grp)

    def _serve_fused(self, programs: list) -> bool:
        """ONE launch computing every member program's payload from the
        shared scan; False = the caller serves the programs apart:
        refused (contract violation / backend can't; counted), or the
        fused program of this member set is not loaded
        (``_group_unloaded``: nothing compiles here).  Agg member
        groups run as a FusedCopProgram; rows-kind groups (fusion-breadth
        follow-on) run as a FusedRowsProgram with per-member output
        capacities."""
        from ..analysis.compilekey import stable_digest
        from ..copr import dag as D
        from ..parallel.spmd import (get_fused_program,
                                     get_fused_rows_program,
                                     get_sharded_program)
        # a member SET is one program, in whatever order its members
        # arrived
        programs = sorted(programs,
                          key=lambda grp: stable_digest(grp[0].dag))
        members = [grp[0] for grp in programs]
        all_tasks = [t for grp in programs for t in grp]
        lead = members[0]
        cc0 = self._cc_mark()
        t_l0 = time.perf_counter_ns()     # launch span covers resolve
        try:
            # the launch seam is consulted once PER MEMBER digest: a
            # poisoned member refuses the fused launch (caught below),
            # demuxing to per-program launches where the guilty member
            # fails ALONE — injected faults exercise exactly the
            # blast-radius contract real failures follow
            for m in members:
                _faults.check("launch", m.key[0])
            from ..analysis.contracts import verify_fusion_group
            # EVERY task (riders too): a same-key rider carrying a
            # different input token must refuse the fused scan — its
            # result would come from the wrong snapshot residents
            verify_fusion_group(all_tasks)
            fused = D.FusedDag(tuple(t.dag for t in members))
            if isinstance(lead.dag, D.Aggregation):
                fprog = get_fused_program(fused, lead.mesh,
                                          donate=lead.donate)
            else:
                fprog = get_fused_rows_program(
                    fused, lead.mesh,
                    tuple(t.row_capacity for t in members))
            args = fprog.abstract_args(lead.cols, lead.counts)
            if not fprog._cached.loaded(args):
                self._group_unloaded(all_tasks, fprog, args)
                return False
            with self._live_launch(all_tasks, "fused", fprog.name):
                outs = fprog(lead.cols, lead.counts)
        except Exception as e:   # noqa: BLE001 - fusion capability probe:
            # refused groups launch apart below (same results, no
            # fusion win) — counted and logged, never silent
            self._note_refusal("fused", lead, e)
            return False
        served = []
        for grp, out in zip(programs, outs):
            sprog = get_sharded_program(grp[0].dag, grp[0].mesh,
                                        grp[0].row_capacity)
            served += [(t, (sprog, out)) for t in grp]
        self._launched(served, fprog, "fused",
                       fprog.facts(lead.cols, lead.counts), t_l0, cc0,
                       coalesced=len(all_tasks), fused=len(programs))
        return True

    # a launch's form, as its span's `group` says it
    GROUP_OF_MODE = {"single": "solo", "coalesced": "dedup"}

    def _launched(self, served: list, program, mode: str, facts: dict,
                  t0: int, cc0: tuple, coalesced: int,
                  fused: int = 0, slots: int = 1) -> None:
        """The epilogue of every structured launch: `served` is its
        (task, the task's result) pairs, `facts` the program's
        ``facts()`` for these inputs, counted and put on the span as
        copr/facts.py says.  What a waiter reads of its task is set
        BEFORE finish(): its _note_sched reads task.fused right after
        wait() returns, so setting it after finish raced the waiter and
        undercounted `fused`/`coalesced` in EXPLAIN ANALYZE and
        statements_summary (copscope satellite: the note_sched seam).
        ``slots``: a batched launch's distinct inputs; with ``fused``
        (its member programs) what the launch executed, so the tasks
        beyond that many shared another task's execution."""
        from ..copr import facts as F
        tasks = [t for t, _val in served]
        self._cc_note(tasks, cc0)
        for t in tasks:
            t.fused, t.coalesced = fused, coalesced
        self._mem_note(tasks, tasks[0].mesh)
        t1 = time.perf_counter_ns()
        form = {"members": fused or 1, "waiters": len(tasks),
                "group": "apart_unloaded" if tasks[0].apart
                else self.GROUP_OF_MODE.get(mode, mode)}
        dedup = len(tasks) - max(fused, slots)
        if dedup:
            self.dedup_tasks += dedup
            self._m_dedup.inc(dedup)
        with self._epilogue(tasks):
            self._trace_launch(tasks, t0, t1, mode, fused=fused,
                               program=program.name,
                               said={**form, **F.span_attrs(facts)})
            for name in F.counters(facts):
                self.kernel_counts[name] += 1
        for t, val in served:
            t.finish(val)
        self.launches += 1
        if program._donate_argnums:
            # (a batched program's: its per-launch stacked copies,
            # whatever the member arrays' own lifetime)
            self.donated_launches += 1
        if mode == "fused":
            self.fused_launches += 1
            self.fused_tasks += len(tasks)
            self._m_fused.inc(len(tasks))
        elif mode == "batched":
            self.batched_launches += 1
            if program.base.kind == "rows":
                self.batched_rows_launches += 1
        self._m_launch.inc(mode=mode)

    def _serve_program(self, batch: list) -> None:
        """Launch ONE program's tasks: in-flight dedup by input token,
        batch-slot vmap stacking for distinct inputs (dense aggs AND
        compacted row outputs), per-slot launches otherwise."""
        lead = batch[0]
        from ..parallel.spmd import (get_batched_program,
                                     get_batched_rows_program,
                                     get_sharded_program)
        digest = lead.key[0] if lead.key is not None else None
        cc0 = self._cc_mark()
        t_l0 = time.perf_counter_ns()     # launch span covers resolve
        _faults.check("build", digest)
        prog = get_sharded_program(lead.dag, lead.mesh, lead.row_capacity,
                                   donate=lead.donate)
        _faults.check("launch", digest)
        # group riders by input identity: same-token tasks share ONE
        # program execution (in-flight dedup)
        slots: list[list] = []
        by_token: dict = {}
        for t in batch:
            s = by_token.get(t.input_token)
            if s is None:
                s = by_token[t.input_token] = []
                slots.append(s)
            s.append(t)
        if len(slots) > 1 and not prog.host_merge and not prog.has_extras \
                and all(s[0].aux == () for s in slots):
            # distinct inputs, one program: stack along the batch-slot
            # dim, ONE vmapped launch, split states/rows per task —
            # where that program is loaded; else the slots launch apart
            # below
            try:
                if prog.kind == "agg":
                    bprog = get_batched_program(lead.dag, lead.mesh,
                                                len(slots))
                else:
                    bprog = get_batched_rows_program(
                        lead.dag, lead.mesh, lead.row_capacity, len(slots))
                cols_list = [s[0].cols for s in slots]
                counts_list = [s[0].counts for s in slots]
                args = bprog.abstract_args(cols_list, counts_list)
                if bprog._cached.loaded(args):
                    with self._live_launch(batch, "batched", bprog.name):
                        outs = bprog(cols_list, counts_list)
                    # a slot's facts are the solo program's
                    self._launched(
                        [(t, (prog, out)) for s, out in zip(slots, outs)
                         for t in s], bprog, "batched",
                        prog.facts(lead.cols, lead.counts), t_l0, cc0,
                        coalesced=len(batch), slots=len(slots))
                    return
                self._group_unloaded(batch, bprog, args)
            except Exception as e:   # planlint: ok - vmap capability probe;
                # op not vmappable on this backend: launch apart below
                # (same results, no batching win) — counted and logged
                self._note_refusal("batched", lead, e)
        first = True
        for s in slots:
            t_s0 = t_l0 if first else time.perf_counter_ns()
            first = False
            mode = "coalesced" if len(s) > 1 else "single"
            with self._live_launch(s, mode, prog.name):
                out = prog(s[0].cols, s[0].counts, s[0].aux)
            # cc0 is the group's entry: a later slot DID wait on the
            # earlier slots' (and the lead's) resolve/compile
            self._launched(
                [(t, (prog, out)) for t in s], prog, mode,
                prog.facts(s[0].cols, s[0].counts, s[0].aux), t_s0, cc0,
                coalesced=len(batch))

    def _note_refusal(self, kind: str, lead, err: BaseException) -> None:
        """A fused / vmap-batched group launch raised and its members
        are being served apart: bump ``<kind>_refused`` and log the
        exception once per lead digest."""
        with self._mu:
            if kind == "fused":
                self.fused_refused += 1
            else:
                self.batched_refused += 1
            digest = lead.key[0] if lead.key is not None else None
            first = (kind, digest) not in self._refusals_logged
            if first:
                if len(self._refusals_logged) > 256:
                    self._refusals_logged.clear()
                self._refusals_logged.add((kind, digest))
        if first:
            _log.warning("%s launch refused, members served apart "
                         "(digest %s): %s", kind,
                         "-" if digest is None
                         else self._digest_hex(digest),
                         self._err_label(err))

    def _note_coalesce(self, batch: list) -> None:
        if len(batch) > 1:
            self.coalesced_launches += 1
            self.coalesced_tasks += len(batch)
            self._m_coal.inc(len(batch))
            for t in batch:
                t.coalesced = len(batch)

    def _attribute_launch(self, batch: list, wall_ns: int) -> None:
        """Split one launch's measured wall time across its members by
        marginal bytes — the shared scan belongs to the lead, each
        rider weighs what it ADDED — so per-group and per-digest device
        time stays honest under fusion/coalescing instead of landing
        wholesale on whichever member's group drained the batch."""
        lead = batch[0]
        weights = [lead.cost.peak_hbm_bytes if lead.cost is not None
                   else 0]
        weights += [self._marginal_bytes(t, lead) for t in batch[1:]]
        for t, ns in zip(batch, split_device_time(weights, wall_ns)):
            t.device_ns = ns
        if self.calibration_enable:
            self._observe_launch(batch)

    def _observe_launch(self, batch: list) -> None:
        """copmeter feedback: each SERVED member's attributed wall time
        EWMAs into its digest's correction against the STATIC cost
        (cost_static, never the already-corrected one — feedback must
        not compound on itself), then throttle-persists through the
        copforge manifest so calibration survives restarts."""
        from ..analysis.calibrate import correction_store
        store = correction_store()
        fed = False
        for t in batch:
            if t.failed or t.device_ns <= 0 or t.cost_static is None \
                    or t.compile_miss:
                # cold launches measure the COMPILER, not the program
                # (compile_wait is already split out for EXPLAIN; the
                # wall split here still contains the trace) — only
                # warm launches feed the loop
                continue
            digest = self._stable_digest(t)
            if digest is None:
                continue
            store.observe(digest, t.cost_static, t.device_ns)
            fed = True
        # copgauge: the measured launch watermark EWMAs the digest's
        # mem_factor (clamped, exactly like time_factor) — only for
        # single-program launches, where the measured executable IS the
        # digest's program (a fused measure would mis-attribute every
        # member); riders share the lead's key, so one feed per launch
        lead = batch[0]
        if self.hbm_enable and lead.hbm_measured \
                and not lead.failed and lead.cost_static is not None \
                and all(t.key == lead.key for t in batch):
            digest = self._stable_digest(lead)
            if digest is not None:
                store.observe_mem(digest, lead.cost_static,
                                  lead.hbm_measured)
                fed = True
        if fed:
            store.sync_manifest()

    def _account(self, batch: list) -> None:
        """Post-launch bookkeeping.  RUs were PRICED at submit and
        DEBITED at batch admission (t.rus_charged — rc/pricing from the
        static LaunchCost; the old est_rows/100+1 post-hoc charge is
        retired); this only mirrors them into the per-group stat and
        the tidb_tpu_sched_ru_total counter /sched consumers read."""
        with self._mu:
            for t in batch:
                self.tasks_done += 1
                if t.cost is not None:
                    # per-link attribution: each task's own collective
                    # payload (merge psums, exchanges) — riders pay
                    # theirs, the shared scan's H2D stays intra
                    ici, dci = t.cost.ici_bytes, t.cost.dci_bytes
                    self.transfer_ici_bytes += ici
                    self.transfer_dci_bytes += dci
                    if ici:
                        self._m_ici.inc(ici)
                    if dci:
                        self._m_dci.inc(dci)
                if t.donate:
                    self.donated_tasks += 1
                    saved = t.cost.donated_bytes if t.cost is not None \
                        else 0
                    self.donated_bytes += saved
                    if saved:
                        self._m_donated.inc(saved)
                g = self._groups.get(t.group)
                if g is not None:
                    g.wait_ns += t.wait_ns
                    g.rus += t.rus_charged
                    g.device_ns += t.device_ns
                if t.key is not None and t.device_ns:
                    # bounded + LRU (BoundedLRU, the calibration
                    # store's eviction policy) — no more unbounded
                    # per-digest growth, no more wholesale clear()
                    self._digest_ns.bump(self._digest_hex(t.key[0]),
                                         t.device_ns)
                self._wait_ring.append(t.wait_ns)
                self._m_wait.observe(t.wait_ns / 1e9)
                self._m_wait_ms.observe(t.wait_ns / 1e6)
                self._m_ru.inc(t.rus_charged, group=t.group)

    # ------------------------------------------------------------- #
    # introspection
    # ------------------------------------------------------------- #

    @property
    def depth(self) -> int:
        return self._depth

    def _calibration_stats(self) -> dict:
        from ..analysis.calibrate import correction_store
        return {"enabled": self.calibration_enable,
                **correction_store().stats()}

    def _hbm_stats(self) -> dict:
        out = {"enabled": self.hbm_enable}
        led = self._ledger_obj
        if led is not None:
            out.update(led.stats())
        return out

    def _pd_stats(self) -> dict:
        """coplace: the /sched ``pd`` section — membership + quota
        shares per attached coordinator (the full store dump lives on
        /pd).  Pure local state, no store I/O from the stats path."""
        if not self.pd_enable:
            return {"enabled": False}
        from ..pd import coordinators
        out = {"enabled": True, "members": []}
        for c in coordinators():
            out["members"].append({
                "member_id": c.member.member_id,
                "epoch": c.member.epoch,
                "degraded": c.member.degraded,
                "degraded_total": c.member.degraded_total,
                "sync_total": c.sync_total,
                "quota_shares": dict(sorted(c.quota.shares.items())),
                "peer_warm": c.registry.peer_warm,
                "claim_denials": c.registry.claim_denials,
            })
        return out

    @staticmethod
    def _pct(samples: list, q: float) -> float:
        if not samples:
            return 0.0
        i = min(int(q * len(samples)), len(samples) - 1)
        return samples[i]

    def stats(self) -> dict:
        with self._mu:
            waits = sorted(self._wait_ring)
            return {
                "queue_depth": self._depth,
                "max_depth": self.max_depth,
                "max_coalesce": self.max_coalesce,
                "fusion": self.fusion_enable,
                "window_us": self.window_us,
                "launches": self.launches,
                "coalesced_launches": self.coalesced_launches,
                "coalesced_tasks": self.coalesced_tasks,
                "batched_launches": self.batched_launches,
                "batched_rows_launches": self.batched_rows_launches,
                "fused_launches": self.fused_launches,
                **self.kernel_counts,
                "fused_tasks": self.fused_tasks,
                "fused_refused": self.fused_refused,
                "batched_refused": self.batched_refused,
                "groups_apart_unloaded": self.groups_apart_unloaded,
                "group_compiles_bg": self.group_compiles_bg,
                "group_loads_bg": self.group_loads_bg,
                "dedup_tasks": self.dedup_tasks,
                "window_waits": self.window_waits,
                "window_hits": self.window_hits,
                "hold_ns_total": self.hold_ns_total,
                "busy_rejects": self.busy_rejects,
                "hbm_budget": self.effective_budget(),
                "budget_admitted": self.budget_admitted,
                "budget_rejects": self.budget_rejects,
                "budget_deferrals": self.budget_deferrals,
                "last_launch_bytes": self.last_launch_bytes,
                "transfer_ici_bytes": self.transfer_ici_bytes,
                "transfer_dci_bytes": self.transfer_dci_bytes,
                "donated_launches": self.donated_launches,
                "donated_tasks": self.donated_tasks,
                "donated_bytes": self.donated_bytes,
                # copforge (compilecache/): drain-paid resolve time +
                # background group compiles that failed
                "compile_ms_total": round(self.compile_ns_total / 1e6, 3),
                "warm_failures": self.warm_failures,
                # launch supervision (faultline): retry/bisect/breaker
                "retried_launches": self.retried_launches,
                "retried_tasks": self.retried_tasks,
                "bisected_launches": self.bisected_launches,
                "quarantined": self.quarantined,
                "value_drifts": self.value_drifts,
                "breaker": self.breaker.snapshot(),
                "faults": _faults.stats(),   # None when unarmed
                "rc_enable": self.rc_enable,
                "rc_overdraft_ru": self.rc_overdraft_ru,
                "rc_throttled": self.rc_throttled,
                "rc_exhausted": self.rc_exhausted,
                "rc_debited_ru": round(self.rc_debited_ru, 2),
                # copmeter (analysis/calibrate): closed-loop state
                "calibration": self._calibration_stats(),
                # copgauge (obs/hbm): the live device-memory ledger
                "hbm": self._hbm_stats(),
                # coplace (pd/): coordination-plane membership
                "pd": self._pd_stats(),
                "oom_faults": self.oom_faults,
                "oom_demuxed": self.oom_demuxed,
                "shed_rejects": self.shed_rejects,
                "backlog_ms": round(self._backlog_ns / 1e6, 3),
                # per digest, the wall time of its launches' resolve +
                # DISPATCH (enqueue), not the device's execution time
                "digest_dispatch_ms": {
                    dk: round(ns / 1e6, 3) for dk, ns in sorted(
                        self._digest_ns.items(),
                        key=lambda kv: -kv[1])[:8]},
                "tasks_done": self.tasks_done,
                "wait_p50_ms": round(self._pct(waits, 0.50) / 1e6, 3),
                "wait_p99_ms": round(self._pct(waits, 0.99) / 1e6, 3),
                "groups": {
                    g.name: {"weight": g.weight, "tasks": g.tasks,
                             "queued": len(g.queue),
                             "wait_ms": round(g.wait_ns / 1e6, 3),
                             "rus": round(g.rus, 2),
                             "throttled": g.throttled,
                             "device_ms": round(g.device_ns / 1e6, 3)}
                    for g in self._groups.values()},
            }


# --------------------------------------------------------------------- #
# per-mesh registry: the scheduler is the mesh's single device executor
# --------------------------------------------------------------------- #

_REGISTRY: dict = {}
_REG_MU = threading.Lock()


def scheduler_for(mesh) -> DeviceScheduler:
    """The (process-wide) scheduler owning launches onto `mesh`.  Keyed
    by the mesh FINGERPRINT (axis names + shape + device ids), not
    id(mesh): device capacity belongs to the chips, so every Domain —
    and every rebuilt Mesh object over the same chips — must share one
    admission queue, and an id() key could false-hit when the allocator
    reuses a dead mesh's address (the columnar device-cache bug)."""
    from .task import mesh_fingerprint
    fp = mesh_fingerprint(mesh)
    with _REG_MU:
        s = _REGISTRY.get(fp)
    if s is not None:
        return s
    with _REG_MU:
        return _REGISTRY.setdefault(fp, DeviceScheduler())


def breaker_snapshot_all() -> dict:
    """Merged breaker view across every registered scheduler (the
    retry-daemon's last-probe summary and /sched aggregation seam)."""
    with _REG_MU:
        scheds = list(_REGISTRY.values())
    out: dict = {}
    for s in scheds:
        out.update(s.breaker.snapshot())
    return out


__all__ = ["DeviceScheduler", "scheduler_for", "breaker_snapshot_all",
           "DEFAULT_QUEUE_DEPTH", "DEFAULT_MAX_COALESCE",
           "WINDOW_CAP_US", "DEFAULT_LAUNCH_RETRY_MS"]
