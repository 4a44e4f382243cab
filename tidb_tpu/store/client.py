"""CopClient: dispatch coprocessor DAGs over the shard store.

Reference analog: pkg/store/copr CopClient.Send → buildCopTasks →
copIterator worker pool → per-region RPCs, with backoff/paging/retry
(coprocessor.go:83-1353).  Here the fan-out is one SPMD program
(parallel/spmd.py); what remains of the client is:

- program-cache lookup per (dag digest, shard layout) — the cop cache seam,
- the paging loop for row-returning plans: run with a capacity guess,
  check reported true counts, double and re-run on overflow
  (kv.Request.Paging grow-from-min analog, SURVEY.md §5.7),
- epoch validation: snapshots carry an epoch; a concurrent write bumps it
  and the device cache invalidates (region epoch-not-match analog).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from ..chunk.column import Column
from ..copr import dag as D
from ..copr.aggregate import (GroupKeyMeta, finalize, finalize_sorted,
                              merge_sorted_states, merge_states)
from ..faults import plan as _faults
from ..faults.breaker import LaunchQuarantinedError
from ..obs.trace import annotate as _obs_annotate
from ..obs.trace import flag as _obs_flag
from ..obs.trace import leaf as _obs_leaf
from ..obs.trace import span as _obs_span
from ..obs.trace import until_next as _obs_until_next
from .columnar import ColumnarSnapshot, _pow2_at_least

# initial fraction of table rows assumed to survive a row-returning plan
INITIAL_SELECTIVITY = 4  # capacity = max(rows/shards/4, 1024)

# SORT-agg group-table sizing: first guess when the planner supplies no
# NDV estimate, and the regrow ceiling
DEFAULT_GROUP_CAPACITY = 4096
# what a join program says of a launch beside its outputs (the extras
# of copr/exec `_compact_probe` and `_exec_lookup_join`), fetched with
# the outputs (`_fetch_states`)
_JOIN_REPORTS = ("join_live", "join_need", "join_window_miss",
                 "exchange_need", "exchange_sent")


@dataclass
class CopResult:
    """Decoded result of one pushdown: either agg groups or row columns."""
    columns: list[Column]
    key_columns: list[Column]


class CopClient:
    def __init__(self, mesh):
        # ``mesh`` may be a jax.sharding.Mesh or a zero-arg callable
        # returning one.  The callable form defers jax backend
        # initialization until a query actually needs device execution:
        # constructing a Session and running host-only statements must
        # not acquire the device (library-safe init).
        from jax.sharding import Mesh as _Mesh
        import threading as _threading
        is_factory = callable(mesh) and not isinstance(mesh, _Mesh)
        self._mesh = None if is_factory else mesh
        self._mesh_fn = mesh if is_factory else None
        self._mesh_mu = _threading.Lock()
        # paging feedback: dag digest -> EWMA of observed per-shard live
        # fraction; replaces the constant first guess with the reference's
        # adaptive min->max paging discipline (pkg/util/paging) fed by
        # actual run results instead of a fixed growth schedule.  LRU-capped:
        # digests embed predicate constants, so point-query workloads would
        # otherwise grow it without bound.
        from collections import OrderedDict
        self._page_feedback: OrderedDict[int, float] = OrderedDict()
        self._page_feedback_cap = 512
        self.last_page_iters = 0       # observability: regrow passes
        # counters above double as status-route payload (sched_stats
        # "client" section): assignments happen under _stat_mu so
        # concurrent connection threads never lose updates
        self._stat_mu = _threading.Lock()
        # failure detection/recovery (copIterator backoff-and-retry):
        # transient dispatch errors retry under a typed backoff budget
        self.retry_budget_ms = 5000.0
        # streaming threshold: tables whose stacked device footprint
        # exceeds this stream through HBM in double-buffered batches
        # (SURVEY.md §5.7; 0 = never stream).  Overridable per-client and
        # via TIDB_TPU_DEVICE_MEM_CAP.
        import os
        self.device_mem_cap = int(
            os.environ.get("TIDB_TPU_DEVICE_MEM_CAP", "0") or 0)
        # last_retries is best-effort observability (per-dispatch); the
        # failpoint queue is lock-guarded since the client is shared by
        # every connection thread
        self.last_retries = 0
        self.last_heals = 0    # topology mutations by the retry loop
        import threading
        self._fp_mu = threading.Lock()
        self._failpoints: list = []    # injected RegionErrors (tests/chaos)
        # _page_feedback is shared across connection threads: guard its
        # get/assign/move_to_end/popitem sequence (ADVICE r2: a concurrent
        # eviction between get and move_to_end raised KeyError)
        self._pf_mu = threading.Lock()
        # digests of join programs whose shortcut did not hold: a
        # compacting join that found more live probe rows than its
        # capacity (`_uncompacted`), a lookup read by windows that found
        # a live row outside its window (`_unwindowed`) -> what makes
        # the DAG exact again (`dag.uncompacted`, `dag.unwindowed`): the
        # next statement with that digest launches that form at once
        # (`_join_form`).  Guarded by _pf_mu, LRU-capped like the
        # paging feedback.
        self._exact_forms: OrderedDict[int, object] = OrderedDict()
        # SORT aggregations whose exact record took more words than the
        # planner guessed (`_wider_record`): digest -> the words the next
        # statement starts with.  Guarded by _pf_mu, capped alike.
        self._record_words: OrderedDict[int, int] = OrderedDict()
        # a host-merged aggregation whose group table was regrown:
        # digest -> the capacity the next statement of it starts with
        # (`_execute_sort_agg`; a GROUP BY above a join has no column
        # statistics to size its table from).  Guarded and capped alike.
        self._group_caps: OrderedDict[int, int] = OrderedDict()
        # a GROUP BY above lookup joins whose builds a run found unique:
        # the plan's digest -> (the group keys that rode as dependents,
        # the record's words), for EXPLAIN (`dependent_keys_found`).
        # Guarded and capped alike.
        self._dependents: OrderedDict[int, tuple] = OrderedDict()
        # coprocessor RESULT cache (copr/coprocessor_cache.go analog):
        # key = (dag digest, snapshot epoch, placement epoch, shard
        # layout); a table write creates a new snapshot + epoch, so stale
        # entries never hit and the LRU ages them out.  Entries hold a
        # weakref to their snapshot: a hit must come from the SAME
        # snapshot object (guards id()/epoch reuse).
        self._result_cache: OrderedDict = OrderedDict()
        self._result_cache_cap = 64
        self._rc_max_bytes = 4 << 20   # only small responses, like the ref
        self._rc_mu = threading.Lock()
        self.result_cache_hits = 0
        self.result_cache_misses = 0
        # device admission scheduler (sched/): every launch onto the mesh
        # goes through a bounded weighted-fair queue that coalesces
        # concurrent compatible tasks.  -1 = scheduler defaults.
        self.sched_queue_depth = -1
        self.sched_max_coalesce = -1
        # cross-query fusion + adaptive micro-batch window knobs
        # (tidb_tpu_sched_fusion / tidb_tpu_sched_window_us); None =
        # scheduler defaults (fusion on, adaptive window)
        self.sched_fusion = None
        self.sched_window_us = None
        # per-mesh HBM admission budget (tidb_tpu_sched_hbm_budget):
        # None = keep scheduler state, -1 = auto from device memory
        # stats, 0 = unlimited, >0 = bytes (analysis/copcost gate)
        self.sched_hbm_budget = None
        # resource control plane (rc/): RU-bucket enforcement at the
        # drain (tidb_tpu_rc_enable) and the bounded overdraft
        # (tidb_tpu_rc_overdraft_ru); None = keep scheduler state
        self.rc_enable = None
        self.rc_overdraft = None
        # copmeter closed-loop cost calibration
        # (tidb_tpu_cost_calibration): None = keep scheduler state
        self.calibration = None
        # copgauge live HBM ledger + measured watermarks
        # (tidb_tpu_hbm_ledger): None = keep scheduler state
        self.hbm_ledger = None
        # coplace coordination plane (tidb_tpu_pd): None = keep
        # scheduler state (the per-Domain coordinator rides session/;
        # this knob only arms the scheduler-side pd hooks)
        self.pd_enable = None
        self._sched_obj = None
        # graceful degradation (faultline; tidb_tpu_sched_host_fallback):
        # a digest quarantined by the launch circuit breaker falls back
        # to the host oracle path when the plan has a host-executable
        # shape — slow-but-correct instead of unavailable (the Flare
        # unsupported-path degradation pattern)
        self.host_fallback = True
        self.degraded = 0      # statements served by that fallback
        # copmeter OOM recovery (faults.is_oom_error): a launch that
        # exhausted device memory retries through the recovery ladder —
        # streamed half-size batches, then the host oracle — instead of
        # failing the statement or charging the poison breaker
        self.oom_recovered = 0
        # copscope (obs/): the last launch's per-link transfer
        # breakdown, stashed per STATEMENT THREAD by _note_sched so the
        # device->host transfer span that follows the launch can carry
        # the shardflow {intra, ici, dci} attribution without re-costing
        self._obs_tl = threading.local()

    @property
    def mesh(self):
        if self._mesh is None:
            with self._mesh_mu:     # concurrent first dispatches resolve once
                if self._mesh is None:
                    self._mesh = self._mesh_fn()
        return self._mesh

    @mesh.setter
    def mesh(self, value):
        self._mesh = value

    # -- dispatch retry seam (pkg/store/copr backoff loop analog) ------ #

    def inject_failures(self, kind, n: int = 1, shard=None,
                        store=None) -> None:
        """Failpoint: the next n dispatches raise a RegionError of `kind`
        before touching the device (chaos/testing seam, the reference's
        failpoint.Inject on rpc errors).  `shard`/`store` name the failing
        topology element so the retry can heal it (re-split / exclude)."""
        from .backoff import RegionError
        with self._fp_mu:
            for _ in range(n):
                e = RegionError(kind)
                e.shard = shard
                e.store = store
                self._failpoints.append(e)

    def _next_failpoint(self):
        with self._fp_mu:
            return self._failpoints.pop(0) if self._failpoints else None

    def _retry(self, fn, snap: "ColumnarSnapshot" = None):
        """Backoff loop that HEALS the topology before retrying: a
        RegionError naming a shard/store mutates the snapshot's placement
        (split the shard / exclude the store, placement.heal), bumping its
        epoch so the retry dispatches a DIFFERENT fan-out — the
        copr handleTask re-split discipline (coprocessor.go:337,:1308),
        not an identical re-run."""
        from ..copr.coordinator import check_killed
        from .backoff import DEVICE_FAILED, Backoffer, RegionError
        bo = Backoffer(max_sleep_ms=self.retry_budget_ms)
        retries = 0
        while True:
            check_killed()    # KILL QUERY cancels in-flight dispatch loops
            try:
                fp = self._next_failpoint()
                if fp is not None:
                    raise fp
                _faults.check("dispatch")   # faultline store-dispatch seam
                with self._stat_mu:
                    self.last_retries = retries
                return fn()
            except RegionError as e:
                bo.backoff(e.kind, e)
                if snap is not None and snap.placement is not None:
                    healed = snap.placement.heal(e)
                    if healed:
                        with self._stat_mu:
                            self.last_heals += 1
                retries += 1
            except _faults.TransientFault as e:
                # injected retryable dispatch/transfer fault: same typed
                # budget, DEVICE_FAILED curve; poison faults propagate
                bo.backoff(DEVICE_FAILED, e)
                retries += 1

    # ------------------------------------------------------------- #
    # device launch seam: admission scheduler (sched/)
    # ------------------------------------------------------------- #

    def _scheduler(self):
        """This mesh's admission scheduler, with the client's knobs."""
        s = self._sched_obj
        if s is None:
            from ..sched import scheduler_for
            s = self._sched_obj = scheduler_for(self.mesh)
        s.configure(
            self.sched_queue_depth if self.sched_queue_depth > 0 else None,
            self.sched_max_coalesce if self.sched_max_coalesce > 0
            else None,
            fusion=self.sched_fusion,
            window_us=self.sched_window_us,
            hbm_budget=self.sched_hbm_budget,
            rc_enable=self.rc_enable,
            rc_overdraft=self.rc_overdraft,
            calibration=self.calibration,
            hbm_ledger=self.hbm_ledger,
            pd_enable=self.pd_enable)
        return s

    def _client_stats(self) -> dict:
        with self._stat_mu:
            return {"last_page_iters": self.last_page_iters,
                    "last_retries": self.last_retries,
                    "last_heals": self.last_heals,
                    "degraded": self.degraded,
                    "oom_recovered": self.oom_recovered,
                    "host_fallback": self.host_fallback}

    def sched_stats(self) -> dict:
        """Status-API introspection; never resolves a pending mesh."""
        with self._rc_mu:
            rc = {"result_cache_hits": self.result_cache_hits,
                  "result_cache_misses": self.result_cache_misses}
        client = {**self._client_stats(), **rc}
        from ..compilecache import compile_cache
        cc = {"compile_cache": compile_cache().stats()}
        if self._sched_obj is None:
            return {"enabled": True, "started": False,
                    "client": client, **cc}
        return {"enabled": True, "started": True,
                "client": client, **cc, **self._sched_obj.stats()}

    def _transfer_attrs(self) -> dict:
        """Per-link attrs for the NEXT transfer span on this statement
        thread (stashed by _note_sched from the served task's
        calibrated LaunchCost — shardflow's typed-link split)."""
        bd = getattr(self._obs_tl, "breakdown", None)
        self._obs_tl.breakdown = None
        if not bd or not (bd[0] or bd[1] or bd[2]):
            return {}
        return {"intra_bytes": bd[0], "ici_bytes": bd[1],
                "dci_bytes": bd[2]}

    def _fetch(self, out, probe=None, **attrs):
        """Host copy of a launch's outputs: the ONE place this client
        waits for the device.  ``cop.transfer`` keeps the extent it
        always had (blocked until the values are on the host); its
        children split it into the wait for the program to finish
        (``cop.device_wait``) and what is left of the copy after that
        (``cop.d2h``), after the requests for the copies
        (``cop.d2h_issue``).  The copies are requested first, as
        ``jax.device_get`` alone would, so they still follow the program
        on the device without a round trip through the host.

        ``probe``: what a join program reports beside its outputs, a
        dict of per-device arrays (copr/exec: ``join_live``,
        ``join_need``, ``join_window_miss``), fetched with the outputs,
        in the same round trip; -> (outputs, the dict on the host), and
        ``probe_live`` (the live rows of all devices) on the span.  The
        states of a host-merged aggregation put ``ngroups`` there."""
        with _obs_span("cop.transfer", **attrs) as xfer:
            t_issue = time.perf_counter_ns()
            for leaf in jax.tree_util.tree_leaves((out, probe)):
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
            if xfer is not None:
                # tree only: under cop.transfer's annotation the
                # stretch stays the profile's ``transfer`` phase
                xfer.add("cop.d2h_issue", t_issue, time.perf_counter_ns())
            with _obs_span("cop.device_wait"):
                jax.block_until_ready((out, probe))
            with _obs_span("cop.d2h"):
                out, probe = jax.device_get((out, probe))
            if isinstance(out, dict) and "__ngroups__" in out:
                # a host-merged aggregation: the groups the devices found
                _obs_annotate(ngroups=int(np.sum(out["__ngroups__"])))
            if probe is None:
                return out
            if "join_live" in probe:
                _obs_annotate(probe_live=int(np.sum(probe["join_live"])))
            if "exchange_sent" in probe:
                # the most any device sent to the devices that own its
                # probe rows' keys (copr/exec `_sharded_lookup`)
                _obs_annotate(exchange_rows_sent=int(
                    np.max(probe["exchange_sent"])))
            return out, probe

    def _fetch_states(self, dag, out, extras: dict):
        """(a launch's aggregate states on the host; the DAG to rerun the
        statement with, or None).  Where the program's join compacted
        its rows or read its table by windows, what it reports beside
        its outputs (copr/exec `_compact_probe`, `_exec_lookup_join`)
        comes in the same fetch; rows that did not fit, or fell outside
        their window, mean a rerun (`_exact_form`)."""
        said = {k: v for k, v in extras.items() if k in _JOIN_REPORTS}
        if not said:
            states = self._fetch(out, **self._transfer_attrs())
        else:
            states, said = self._fetch(out, said, **self._transfer_attrs())
        # session.settle: from the fetch to the merge (or the rerun):
        # did the compaction fit, the fault seam keyed by the DAG's
        # digest.  Not a cop.* name: host_plan_ms subtracts those
        _obs_until_next("session.settle", root_only=True)
        return states, self._exact_form(dag, said)

    def _note_sched(self, task) -> None:
        if task.cost is not None:
            self._obs_tl.breakdown = task.cost.transfer_breakdown
        from ..copr.coordinator import QUERY_HANDLE
        h = QUERY_HANDLE.get()
        if h is not None:
            # rus_charged is set at batch admission (before finish) and
            # compile_ns/compile_miss before finish too, so the waiter
            # always observes them; device_ns is attributed post-serve
            # and stays a scheduler-side stat
            h.note_sched(task.wait_ns, task.coalesced, task.fused,
                         rus=task.rus_charged, retried=task.retries,
                         compile_ns=task.compile_ns,
                         compile_miss=task.compile_miss,
                         hbm_predicted=task.hbm_predicted,
                         hbm_measured=task.hbm_measured)

    def _launch(self, dag, cols, counts, aux, row_capacity: int = 0,
                donate: bool = False):
        """One device launch of a sharded cop program, routed through the
        admission queue: the scheduler resolves the compiled program (so
        concurrent identical tasks share ONE compile + launch) and may
        coalesce this task with compatible ones from other sessions.
        ``donate=True`` marks the inputs launch-unique (streamed HBM
        batches): the DonationPlan-derived program variant aliases them
        into outputs (analysis/lifetime) — never set it for snapshot
        residents or regrow-loop inputs.  Returns (program, out)."""
        from ..sched import CopTask
        est = 0
        if cols:
            s, c = cols[0][0].shape[:2]
            est = s * c
        # copscope: the dispatch span is the parent every scheduler-
        # thread span (queue/compile/launch/retry) stitches under — the
        # CopTask captures the child TraceCtx at construction
        with _obs_span("cop.dispatch"):
            # sched.task: the task's key (the DAG's digest, the inputs'
            # shape signature, the fusion signature)
            with _obs_leaf("sched.task"):
                task = CopTask.structured(
                    dag, self.mesh, row_capacity, cols, counts, tuple(aux),
                    est_rows=est, donate=donate)
            t = self._scheduler().submit(task)
            try:
                return t.wait()
            finally:
                self._note_sched(t)

    def _launch_opaque(self, fn, est_rows: int = 0, program: str = ""):
        """Admission-controlled launch of a program with a non-standard
        signature (shuffle/window): fair-ordered, never coalesced.
        ``program`` names it on the launch span."""
        from ..sched import CopTask
        with _obs_span("cop.dispatch", opaque=True):
            t = self._scheduler().submit(CopTask.opaque(
                fn, est_rows=est_rows, program=program))
            try:
                return t.wait()
            finally:
                self._note_sched(t)

    # ------------------------------------------------------------- #

    def execute_agg(self, agg: D.Aggregation, snap: ColumnarSnapshot,
                    key_meta: list[GroupKeyMeta], aux_cols=()) -> CopResult:
        key = None
        if not aux_cols:      # aux (join builds) = host inputs, not cacheable
            key = self._rc_key(agg, snap)
            hit = self._rc_get(key, snap)
            if hit is not None:
                return hit
        try:
            res = self._retry(lambda: self._execute_agg_once(
                agg, snap, key_meta, aux_cols), snap=snap)
        except LaunchQuarantinedError as err:
            # OPEN breaker: the device program keeps failing — degrade
            # to the host oracle where the plan shape allows it
            _obs_flag("quarantined")
            res = self._degraded_agg(agg, snap, key_meta, aux_cols, err)
        except Exception as err:
            # copmeter OOM recovery: a launch that exhausted device
            # memory (injected MemoryFault or a real RESOURCE_EXHAUSTED)
            # walks the recovery ladder; everything else re-raises
            if not _faults.is_oom_error(err):
                raise
            res = self._oom_degraded_agg(agg, snap, key_meta, aux_cols,
                                         err)
        if key is not None:
            self._rc_put(key, snap, res)
        return res

    def _oom_degraded_agg(self, agg: D.Aggregation, snap: ColumnarSnapshot,
                          key_meta, aux_cols, err) -> CopResult:
        """OOM recovery ladder (copmeter): the scheduler already bumped
        the digest's memory correction and demuxed any fused launch;
        a SOLO launch that still did not fit lands here.  Try streamed
        half-size batches first (the launch that OOM'd resident runs
        as >= 2 HBM-streamed batches), then the host oracle — results
        stay bit-identical to the uncontended run on every rung.  Plans
        with neither shape re-raise the original error."""
        _obs_flag("oom")
        if not aux_cols:
            half = max(snap.device_bytes() // 2, 1)
            batches = snap.row_batches(half)
            if batches and len(batches) >= 2:
                try:
                    if agg.host_merged:
                        res = self._stream_sort_agg(agg, batches, key_meta)
                    else:
                        res = self._stream_dense_agg(agg, batches, key_meta)
                    with self._stat_mu:
                        self.oom_recovered += 1
                    return res
                except Exception as e:  # noqa: BLE001 - recovery ladder:
                    # a half-size stream may STILL exhaust memory (or
                    # trip the same injected fault) — fall through to
                    # the host oracle; anything non-OOM re-raises
                    if not _faults.is_oom_error(e):
                        raise
        res = self._degraded_agg(agg, snap, key_meta, aux_cols, err)
        with self._stat_mu:
            self.oom_recovered += 1
        return res

    def _degraded_agg(self, agg: D.Aggregation, snap: ColumnarSnapshot,
                      key_meta, aux_cols, err) -> CopResult:
        """Graceful degradation for a quarantined program digest
        (faultline): serve the aggregation from the host oracle path
        (copr/hostagg) — slow-but-correct instead of unavailable, the
        Flare compiled-path-falls-back-to-interpreter pattern.  Plans
        without a host-executable shape re-raise the quarantine error
        so the client sees the structured failure."""
        res = None
        if self.host_fallback and not aux_cols:
            if agg.host_merged:
                res = self._host_sort_agg(agg, snap, key_meta)
            else:
                from ..copr.hostagg import host_dense_agg
                states = host_dense_agg(agg, snap)
                if states is not None:
                    merged = merge_states([states])
                    key_cols, agg_cols = finalize(agg, merged, key_meta)
                    res = CopResult(agg_cols, key_cols)
        if res is None:
            raise err
        _obs_flag("degraded")
        with self._stat_mu:
            self.degraded += 1
        from ..utils.metrics import global_registry
        global_registry().counter(
            "tidb_tpu_sched_degraded_total",
            "statements served by the host oracle after a launch "
            "quarantine").inc()
        from ..copr.coordinator import QUERY_HANDLE
        h = QUERY_HANDLE.get()
        if h is not None:
            h.note_degraded()
        return res

    def _rc_key(self, dag, snap: ColumnarSnapshot):
        p_epoch = snap.placement.epoch if snap.placement is not None else -1
        return (D.dag_digest(dag), snap.epoch, p_epoch, snap.num_rows,
                snap.n_shards)

    def _rc_get(self, key, snap) -> Optional[CopResult]:
        with self._rc_mu:
            ent = self._result_cache.get(key)
            if ent is not None and ent[0]() is snap:
                self._result_cache.move_to_end(key)
                self.result_cache_hits += 1
                return ent[1]
            # miss counter bumps under the same lock: the client is
            # shared by every connection thread and an unguarded
            # read-modify-write here loses updates under load
            self.result_cache_misses += 1
        return None

    def _rc_put(self, key, snap, res: CopResult) -> None:
        import weakref
        nbytes = sum(c.data.nbytes for c in res.columns + res.key_columns
                     if hasattr(c.data, "nbytes"))
        if nbytes > self._rc_max_bytes:
            return
        with self._rc_mu:
            self._result_cache[key] = (weakref.ref(snap), res)
            self._result_cache.move_to_end(key)
            while len(self._result_cache) > self._result_cache_cap:
                self._result_cache.popitem(last=False)

    def _execute_agg_once(self, agg: D.Aggregation, snap: ColumnarSnapshot,
                          key_meta: list[GroupKeyMeta],
                          aux_cols=()) -> CopResult:
        if agg.host_merged:
            # per-device group tables, host final merge, capacity regrow
            if not aux_cols and self._platform() == "cpu":
                res = self._host_sort_agg(agg, snap, key_meta)
                if res is not None:
                    return res
            batches = self._stream_batches(agg, snap)
            if batches is not None:
                return self._stream_sort_agg(agg, batches, key_meta)
            cols, counts = snap.device_cols(self.mesh)
            return self._execute_sort_agg(agg, cols, counts, key_meta,
                                          aux_cols)
        if not aux_cols and self._platform() == "cpu":
            # CPU engine choice for DENSE/SCALAR too: scatter-add limbs
            # beat the XLA-CPU program ~3x (hostagg.host_dense_agg)
            from ..copr.hostagg import host_dense_agg
            states = host_dense_agg(agg, snap)
            if states is not None:
                merged = merge_states([states])
                key_cols, agg_cols = finalize(agg, merged, key_meta)
                return CopResult(agg_cols, key_cols)
        batches = self._stream_batches(agg, snap)
        if batches is not None:
            return self._stream_dense_agg(agg, batches, key_meta)
        cols, counts = snap.device_cols(self.mesh)
        if aux_cols:
            agg = self._join_form(agg)
        for _ in range(8):
            prog, out = self._launch(agg, cols, counts, tuple(aux_cols))
            extras = {}
            if prog.has_extras:
                out, extras = out
                grown = self._grown_join_dag(agg, extras)
                if grown is not None:
                    agg = grown
                    continue
            states, exact = self._fetch_states(agg, out, extras)
            if exact is not None:
                agg = exact
                continue
            # faultline transfer/host-merge seam, keyed by the digest
            _faults.check("transfer", D.dag_digest(agg))
            break
        else:
            raise RuntimeError("join-capacity regrow did not converge")
        with _obs_span("cop.host_merge",
                       kind="per-device" if prog.host_merge else "root"):
            if prog.host_merge:
                # min/max partials come back per-device (leading axis);
                # the final merge is the host's root-worker role
                per_dev = self._split_devices(states)
                merged = merge_states(per_dev)
            else:
                merged = merge_states([states])
            key_cols, agg_cols = finalize(agg, merged, key_meta)
        return CopResult(agg_cols, key_cols)

    def _platform(self) -> str:
        return self.mesh.devices.reshape(-1)[0].platform

    # ------------------------------------------------------------- #
    # streaming: tables bigger than device memory (SURVEY.md §5.7)
    # ------------------------------------------------------------- #

    def _stream_batches(self, dag, snap: ColumnarSnapshot, aux_cols=()):
        """Row-range batch views when the snapshot exceeds the device
        memory cap; None = run resident.  Plans with expanding joins keep
        the resident path (their capacity-regrow loop re-runs programs)."""
        if not self.device_mem_cap or aux_cols \
                or D.find_expand_join(dag) is not None:
            return None
        return snap.row_batches(self.device_mem_cap)

    def _stream_states(self, agg, batches):
        """Double-buffered dispatch: batch k+1's H2D transfer overlaps
        batch k's compute (jax dispatch is async; nothing blocks until the
        final device_get).  The paging/double-buffer analog of
        kv.Request.Paging (SURVEY.md §5.7)."""
        from ..copr.coordinator import check_killed
        outs = []
        nxt = batches[0].device_put_uncached(self.mesh)
        for i in range(len(batches)):
            check_killed()   # cancellation between streamed HBM batches
            cols, counts = nxt
            # uncached batch, launched exactly once: EPHEMERAL in the
            # lifetime taxonomy — the donating program variant lets XLA
            # alias the batch into its outputs, so the steady-state
            # paging loop stops holding input + output + temp at once
            _prog, out = self._launch(agg, cols, counts, (), donate=True)
            outs.append(out)
            if i + 1 < len(batches):
                nxt = batches[i + 1].device_put_uncached(self.mesh)
            del cols, counts     # free the batch once its program consumed it
        return self._fetch(outs, batches=len(outs))

    def _stream_dense_agg(self, agg, batches, key_meta) -> CopResult:
        states_list = self._stream_states(agg, batches)
        merged = merge_states(states_list)
        key_cols, agg_cols = finalize(agg, merged, key_meta)
        return CopResult(agg_cols, key_cols)

    @staticmethod
    def _warm_cap(dag, needed: int) -> int:
        """copforge regrow/paging re-entry seam: prefer a capacity the
        warm program pool (or the persisted manifest) already compiled
        for this plan FAMILY over the minimal pow2 step — re-entering
        at a warm capacity serves from the pool instead of re-tracing.
        Bounded (<= 4x need) so a warm-but-huge buffer never wins."""
        from ..analysis.compilekey import family_digest
        from ..compilecache import compile_cache
        warm = compile_cache().warm_capacity(family_digest(dag), needed)
        return warm if warm is not None else needed

    @staticmethod
    def _with_capacity(agg: D.Aggregation, cap: int) -> D.Aggregation:
        """Rebuild a host-merged aggregation with a new per-device group
        table capacity (pow2, so the capacity lands in a shared fusion
        shape class): the regrow knob."""
        import dataclasses
        return dataclasses.replace(agg,
                                   group_capacity=_pow2_at_least(cap))

    def _stream_sort_agg(self, agg, batches, key_meta) -> CopResult:
        agg = D.wide_groups(agg)    # a group's rows are in many batches
        cap = self._warm_cap(agg, agg.group_capacity
                             or DEFAULT_GROUP_CAPACITY)
        per_dev_all = []
        for b in batches:
            cols, counts = b.device_put_uncached(self.mesh)
            for _ in range(10):
                sized = self._with_capacity(agg, cap)
                _prog, out = self._launch(sized, cols, counts, ())
                states = self._fetch(out)
                true_ng = int(np.max(np.asarray(states["__ngroups__"])))
                if true_ng <= cap:
                    break
                cap = self._warm_cap(agg, _pow2_at_least(true_ng))
            else:
                raise RuntimeError("group-capacity regrow did not converge")
            per_dev_all.extend(self._split_devices(states))
            del cols, counts
        sized = self._with_capacity(agg, cap)
        merged = merge_sorted_states(sized, per_dev_all)
        key_cols, agg_cols = finalize_sorted(sized, merged, key_meta)
        return CopResult(agg_cols, key_cols)

    def _host_sort_agg(self, agg: D.Aggregation, snap: ColumnarSnapshot,
                       key_meta) -> Optional[CopResult]:
        """CPU engine choice for high-NDV group-by.

        The reference's CPU answer is a hash table (parallel HashAgg,
        pkg/executor/aggregate/agg_hash_executor.go:94); XLA's TPU-shaped
        sort+scatter SORT program measured 56x SLOWER than a host
        np.unique on CPU (VERDICT r2 #2).  On a CPU mesh the coprocessor
        therefore runs unbounded-NDV group-by as a host unique + segment
        reduction over the snapshot columns — the per-platform strategy
        split precedented by the dense-reduce path (copr/exec._reduce).
        Returns None when the DAG shape isn't the scan/filter/project
        chain this path handles (falls back to the device program).
        """
        from ..copr.hostagg import host_sort_agg
        states = host_sort_agg(agg, snap)
        if states is None:
            return None
        # single host table: groups are already unique — the cross-device
        # re-group of merge_sorted_states would be a no-op
        merged = {k: v for k, v in states.items() if k != "__ngroups__"}
        key_cols, agg_cols = finalize_sorted(agg, merged, key_meta)
        return CopResult(agg_cols, key_cols)

    @staticmethod
    def _record_key(agg: D.Aggregation) -> int:
        import dataclasses
        return D.dag_digest(dataclasses.replace(
            agg, pack_words=0, group_capacity=0))

    def _group_form(self, agg: D.Aggregation) -> D.Aggregation:
        """`agg` as this mesh launches it: its groups ranked by the host
        where a group's rows lie on several devices (they do not where a
        join beneath sends every row to the device that owns its group
        key: `dag.groups_whole`), and its exact
        record (copr/runagg) as wide as a statement of this digest has
        needed before."""
        import dataclasses
        if agg.topn is not None and agg.topn.on_device \
                and self.mesh.devices.size > 1 and not D.groups_whole(agg):
            agg = dataclasses.replace(agg, topn=dataclasses.replace(
                agg.topn, on_device=False))
        if agg.pack_words and self._record_words:
            with self._pf_mu:
                words = self._record_words.get(self._record_key(agg))
            if words is not None:
                agg = dataclasses.replace(agg, pack_words=words)
        return agg

    def _wider_record(self, agg: D.Aggregation,
                      states) -> Optional[D.Aggregation]:
        """If a device's exact record took more bits than `agg` has
        words for (the states are then not the groups'), `agg` with as
        many as it takes, or the wide form, which the statement is rerun
        with; the digest is remembered (`_group_form`).  None when it
        fit."""
        import dataclasses
        if "__bits__" not in states:
            return None
        bits = int(np.max(np.asarray(states["__bits__"])))
        if bits <= 32 * agg.pack_words:
            return None
        self._scheduler().count("hndv_agg_regrows")
        words = 2 if bits <= 64 else 0
        with self._pf_mu:
            self._record_words[self._record_key(agg)] = words
            while len(self._record_words) > self._page_feedback_cap:
                self._record_words.popitem(last=False)
        return dataclasses.replace(agg, pack_words=words)

    def found_dependent_keys(self, planned, marked: D.Aggregation) -> None:
        """A run of the plan's DAG `planned` found its builds unique and
        launched `marked` (executor/physical `_grouped`)."""
        with self._pf_mu:
            self._dependents[D.dag_digest(planned)] = (
                marked.dependent, marked.pack_words)
            while len(self._dependents) > self._page_feedback_cap:
                self._dependents.popitem(last=False)

    def dependent_keys_found(self, planned) -> Optional[tuple]:
        """(`dependent`, `pack_words`) of what the last run of the
        plan's DAG launched, or None: no statement of it has run, or
        none found dependent keys."""
        with self._pf_mu:
            return self._dependents.get(D.dag_digest(planned))

    def _join_form(self, dag):
        """`dag` (of a program that joins), or in the exact form a
        program of this digest had to be rerun in before: its lookups a
        gather where a window missed a row, its compacting join looking
        up every slot where the live rows did not fit."""
        while self._exact_forms:
            with self._pf_mu:
                exact = self._exact_forms.get(D.dag_digest(dag))
            if exact is None:
                break
            dag = exact(dag)
        return dag

    def _exact_form(self, dag, said: dict) -> Optional[D.CopNode]:
        """The DAG to rerun a statement with after what its launch
        `said` of its joins (`_fetch_states`), or None: nothing was
        lost.  A window's miss first: what a compaction counted above a
        wrong lookup is wrong too."""
        if "join_window_miss" in said \
                and int(np.sum(said["join_window_miss"])):
            return self._unwindowed(dag)
        if "exchange_need" in said:
            grown = self._exchange_regrown(
                dag, int(np.max(said["exchange_need"])))
            if grown is not None:
                return grown
        if "join_need" in said:
            return self._uncompacted(dag, int(np.max(said["join_need"])))
        return None

    def _remembered(self, dag, event: str, exact) -> D.CopNode:
        """`exact(dag)`, which the statement is rerun with, `event`
        counted and the digest remembered (`_join_form`)."""
        self._scheduler().count(event)
        with self._pf_mu:
            self._exact_forms[D.dag_digest(dag)] = exact
            while len(self._exact_forms) > self._page_feedback_cap:
                self._exact_forms.popitem(last=False)
        return exact(dag)

    def _uncompacted(self, dag, need: int) -> Optional[D.CopNode]:
        """If a device's live probe rows take more than the compacting
        join's capacity (some are missing from this launch's result),
        the DAG with every slot looked up; None when they fit."""
        if need <= D.compact_capacity(D.compacting_join(dag)):
            return None
        return self._remembered(dag, "join_compact_overflows", D.uncompacted)

    def _exchange_regrown(self, dag, need: int) -> Optional[D.CopNode]:
        """If a device had more rows for one destination than a bucket
        of the join's exchange holds (some are missing from this
        launch's result), the DAG with buckets of what the devices
        found and a quarter more; None when they fit.  The digest
        remembers its last finding (`_join_form`), as a group table's
        capacity is remembered (`_group_caps`)."""
        join = D.exchanging_join(dag)
        if join is None or need <= join.exchange:
            return None
        cap = D.exchange_capacity_round(need + need // 4)
        return self._remembered(
            dag, "exchange_overflows", lambda d: D.rewrite_lookup(
                d, pred=lambda j: 0 < j.exchange < cap, exchange=cap))

    def _unwindowed(self, dag) -> D.CopNode:
        """A lookup read by windows found a live row outside its window
        (the probe key was not in the order ANALYZE saw, or its blocks
        span more than the window): the DAG with every lookup a
        gather."""
        return self._remembered(dag, "join_window_overflows", D.unwindowed)

    def _grown_join_dag(self, dag, extras) -> Optional[D.CopNode]:
        """If the expanding join overflowed its capacity, return the DAG
        rebuilt with a big-enough capacity; None when it fits (the join
        half of the paging grow-from-min discipline)."""
        if "join_total" not in extras:
            return None
        need = int(np.max(np.asarray(self._fetch(extras["join_total"]))))
        node = D.find_expand_join(dag)
        if node is not None and need > node.out_capacity:
            self._scheduler().count("join_regrows")
            return D.rewrite_expand_capacity(dag, _pow2_at_least(need))
        return None

    def _split_devices(self, states):
        n_dev = len(self.mesh.devices.reshape(-1))
        return [jax.tree_util.tree_map(lambda a: np.asarray(a)[d], states)
                for d in range(n_dev)]

    def _execute_sort_agg(self, agg, cols, counts, key_meta,
                          aux_cols) -> CopResult:
        """High-NDV group-by (SORT): per-device group tables of the
        sort's runs, regrown when a device sees more distinct groups
        than capacity (the paging grow-from-min analog), then host final
        merge."""
        agg = self._group_form(agg)
        with self._pf_mu:
            regrown = self._group_caps.get(self._record_key(agg), 0)
        cap = self._warm_cap(agg, max(agg.group_capacity
                                      or DEFAULT_GROUP_CAPACITY, regrown))
        if aux_cols:
            agg = self._join_form(agg)
        for _ in range(10):
            sized = self._with_capacity(agg, cap)
            prog, out = self._launch(sized, cols, counts, tuple(aux_cols))
            extras = {}
            if prog.has_extras:
                out, extras = out
                grown = self._grown_join_dag(sized, extras)
                if grown is not None:
                    agg = grown
                    continue
            states, exact = self._fetch_states(agg, out, extras)
            if exact is not None:
                agg = exact
                continue
            wider = self._wider_record(agg, states)
            if wider is not None:
                agg = wider
                continue
            true_ng = int(np.max(np.asarray(states["__ngroups__"])))
            if true_ng <= cap:
                sized = self._with_capacity(agg, cap)
                break
            self._scheduler().count("hndv_agg_regrows")
            cap = self._warm_cap(agg, _pow2_at_least(true_ng))
            with self._pf_mu:
                self._group_caps[self._record_key(agg)] = cap
                while len(self._group_caps) > self._page_feedback_cap:
                    self._group_caps.popitem(last=False)
        else:
            raise RuntimeError("group-capacity regrow did not converge")
        with _obs_span("cop.host_merge", kind="sorted"):
            per_dev = self._split_devices(states)
            merged = merge_sorted_states(sized, per_dev)
            key_cols, agg_cols = finalize_sorted(sized, merged, key_meta)
        return CopResult(agg_cols, key_cols)

    # ------------------------------------------------------------- #
    # repartition (shuffle) join — parallel/shuffle.py
    # ------------------------------------------------------------- #

    def _shuffle_initial_caps(self, lsnap, rsnap, row_cap: int):
        from ..parallel.shuffle import ShuffleCaps
        n_dev = len(self.mesh.devices.reshape(-1))
        # expected send-bucket rows under a uniform hash: local/n_dev;
        # 2x headroom, grown from the reported true maxima on overflow
        lcap = _pow2_at_least(
            max(2 * lsnap.num_rows // max(n_dev * n_dev, 1) + 1, 1024))
        rcap = _pow2_at_least(
            max(2 * rsnap.num_rows // max(n_dev * n_dev, 1) + 1, 1024))
        ocap = _pow2_at_least(max(2 * lsnap.num_rows // n_dev + 1, 1024))
        return ShuffleCaps(lcap, rcap, ocap, row_cap)

    def _run_shuffle(self, spec: D.ShuffleJoinSpec, lsnap, rsnap, aux_cols,
                     row_cap: int = 0):
        """Run the shuffle program, regrowing whichever static capacity
        (exchange buckets / join output / group table / row output) the
        extras report as overflowed — the paging discipline."""
        import dataclasses

        from ..parallel.shuffle import ShuffleCaps, get_shuffle_program
        lcols, lcounts = lsnap.device_cols(self.mesh)
        rcols, rcounts = rsnap.device_cols(self.mesh)
        caps = self._shuffle_initial_caps(lsnap, rsnap, row_cap)
        agg = spec.top if isinstance(spec.top, D.Aggregation) else None
        if agg is not None and agg.host_merged:
            # the exchange's output is no resident launch's: the wide
            # record, which always fits, and the host ranks the groups
            agg = D.wide_groups(agg)
            spec = dataclasses.replace(spec, top=agg if agg.group_capacity
                                       else self._with_capacity(
                                           agg, DEFAULT_GROUP_CAPACITY))
        for _ in range(12):
            prog = get_shuffle_program(spec, self.mesh, caps)
            self._scheduler().count("join_shuffle_launches")
            out, extras = self._launch_opaque(
                lambda p=prog: p(lcols, lcounts, rcols, rcounts, aux_cols),
                est_rows=lsnap.num_rows + rsnap.num_rows,
                program=prog.name)
            extras = {k: np.asarray(v)
                      for k, v in self._fetch(extras).items()}
            grew = False
            need_l = int(extras["lmax"].max())
            if need_l > caps.left:
                caps = dataclasses.replace(caps,
                                           left=_pow2_at_least(need_l))
                grew = True
            need_r = int(extras["rmax"].max())
            if need_r > caps.right:
                caps = dataclasses.replace(caps,
                                           right=_pow2_at_least(need_r))
                grew = True
            need_j = int(extras["join_total"].max())
            if spec.kind in ("inner", "left") and need_j > caps.out:
                caps = dataclasses.replace(caps, out=_pow2_at_least(need_j))
                grew = True
            if grew:
                continue
            agg = spec.top if isinstance(spec.top, D.Aggregation) else None
            if agg is not None and agg.host_merged:
                true_ng = int(np.max(np.asarray(
                    self._fetch(out["__ngroups__"]))))
                if true_ng > agg.group_capacity:
                    spec = dataclasses.replace(spec, top=self._with_capacity(
                        agg, _pow2_at_least(true_ng)))
                    continue
            if agg is None:
                _cols, counts = out
                counts = np.asarray(self._fetch(counts))
                if (counts > caps.rows).any():
                    caps = dataclasses.replace(
                        caps, rows=_pow2_at_least(int(counts.max())))
                    continue
            return prog, out
        raise RuntimeError("shuffle capacity regrow did not converge")

    def execute_window(self, spec: D.WindowShuffleSpec,
                       snap: ColumnarSnapshot, out_dtypes,
                       dictionaries=None, aux_cols=()) -> list[Column]:
        return self._retry(lambda: self._execute_window_once(
            spec, snap, out_dtypes, dictionaries, aux_cols))

    def _execute_window_once(self, spec, snap, out_dtypes,
                             dictionaries=None, aux_cols=()) -> list[Column]:
        """Hash-repartitioned window program (TiFlash MPP window analog):
        bucket capacity regrows from the reported true maximum, the
        paging discipline."""
        from ..parallel.window import get_window_program
        cols, counts = snap.device_cols(self.mesh)
        n_dev = len(self.mesh.devices.reshape(-1))
        # expected bucket rows under uniform hashing, 2x headroom
        cap = _pow2_at_least(
            max(2 * snap.num_rows // max(n_dev * n_dev, 1) + 1, 1024))
        for _ in range(10):
            prog = get_window_program(spec, self.mesh, cap)
            (out_cols, out_counts), extras = self._launch_opaque(
                lambda p=prog: p(cols, counts, aux_cols),
                est_rows=snap.num_rows, program=prog.name)
            need = int(np.max(np.asarray(self._fetch(extras["wmax"]))))
            if need <= cap:
                break
            cap = _pow2_at_least(need)
        else:
            raise RuntimeError("window bucket regrow did not converge")
        return self._assemble_rows(out_cols, out_counts,
                                   n_dev * cap, out_dtypes, dictionaries)

    def execute_shuffle_agg(self, spec: D.ShuffleJoinSpec, lsnap, rsnap,
                            key_meta: list[GroupKeyMeta],
                            aux_cols=()) -> CopResult:
        return self._retry(lambda: self._execute_shuffle_agg_once(
            spec, lsnap, rsnap, key_meta, aux_cols))

    def _execute_shuffle_agg_once(self, spec, lsnap, rsnap, key_meta,
                                  aux_cols=()) -> CopResult:
        prog, out = self._run_shuffle(spec, lsnap, rsnap, aux_cols)
        agg = prog.spec.top
        states = self._fetch(out)
        if prog.host_merge:
            per_dev = self._split_devices(states)
            if agg.host_merged:
                merged = merge_sorted_states(agg, per_dev)
                key_cols, agg_cols = finalize_sorted(agg, merged, key_meta)
                return CopResult(agg_cols, key_cols)
            merged = merge_states(per_dev)
        else:
            merged = merge_states([states])
        key_cols, agg_cols = finalize(agg, merged, key_meta)
        return CopResult(agg_cols, key_cols)

    def execute_shuffle_rows(self, spec: D.ShuffleJoinSpec, lsnap, rsnap,
                             out_dtypes, dictionaries=None,
                             aux_cols=()) -> list[Column]:
        return self._retry(lambda: self._execute_shuffle_rows_once(
            spec, lsnap, rsnap, out_dtypes, dictionaries, aux_cols))

    def _execute_shuffle_rows_once(self, spec, lsnap, rsnap, out_dtypes,
                                   dictionaries=None,
                                   aux_cols=()) -> list[Column]:
        n_dev = len(self.mesh.devices.reshape(-1))
        if isinstance(spec.top, (D.TopN, D.Limit)):
            row_cap = max(spec.top.limit, 16)
        else:
            row_cap = _pow2_at_least(
                max(2 * lsnap.num_rows // max(n_dev, 1) + 1, 1024))
        prog, out = self._run_shuffle(spec, lsnap, rsnap, aux_cols, row_cap)
        out_cols, out_counts = out
        return self._assemble_rows(out_cols, out_counts, prog.caps.rows,
                                   out_dtypes, dictionaries)

    def _assemble_rows(self, out_cols, out_counts, cap, out_dtypes,
                       dictionaries) -> list[Column]:
        """Concatenate per-device compacted outputs into host Columns.
        A device's rows are its first `count` slots, or, where the
        program put out its slots' live mask as one more column than the
        schema has (copr/exec `compact_root`), the slots that says."""
        _faults.check("transfer")   # faultline device->host seam
        n_dev = len(self.mesh.devices.reshape(-1))
        out_cols, out_counts = self._fetch((out_cols, out_counts))
        out_counts = np.asarray(out_counts)
        per_dev_take = np.minimum(out_counts, cap)
        if len(out_cols) > len(out_dtypes):
            live = np.asarray(out_cols[len(out_dtypes)][0])
            take = [np.nonzero(live[d])[0] for d in range(n_dev)]
        else:
            take = [slice(0, per_dev_take[d]) for d in range(n_dev)]

        def rows_of(a):
            a = np.asarray(a)
            if n_dev == 1:
                return a[0][take[0]]
            return np.concatenate([a[d][take[d]] for d in range(n_dev)])
        result = []
        for j, t in enumerate(out_dtypes):
            data, valid = rows_of(out_cols[j][0]), rows_of(out_cols[j][1])
            dic = dictionaries.get(j) if dictionaries else None
            result.append(Column(t, data.astype(t.np_dtype(), copy=False),
                                 valid, dic))
        return result

    # ------------------------------------------------------------- #

    def execute_rows(self, root: D.CopNode, snap: ColumnarSnapshot,
                     out_dtypes, dictionaries=None, aux_cols=()) -> list[Column]:
        return self._retry(lambda: self._execute_rows_once(
            root, snap, out_dtypes, dictionaries, aux_cols), snap=snap)

    def execute_rows_resident(self, root: D.CopNode, snap: ColumnarSnapshot,
                              aux_cols=()):
        """A rows-returning plan whose rows stay on their devices: the
        program's output columns as it put them out (a leading device
        axis, `capacity` slots a device, the slots' live mask last:
        copr/exec `compact_root`), unfetched; None where the table is
        streamed in batches.  The paging loop fetches the live counts
        alone.  A lookup join's sharded build side is made from them
        (`sharded_tables`)."""
        return self._retry(lambda: self._execute_rows_once(
            root, snap, None, None, aux_cols, resident=True), snap=snap)

    def sharded_tables(self, out_cols, spec, meta, part):
        """The direct-addressed word tables of a lookup join's sharded
        build side, made on the devices from the rows
        `execute_rows_resident` left there (parallel/shuffle
        `ShardedTableProgram`): (tables with a leading device axis, per
        device [rows written, slots that hold one, live rows outside the
        device's key range])."""
        from ..parallel.shuffle import get_table_program
        prog = get_table_program(spec, self.mesh)
        tables, said = self._launch_opaque(
            lambda: prog(out_cols, meta, part), program=prog.name)
        return tables, np.asarray(self._fetch(said))

    def _execute_rows_once(self, root: D.CopNode, snap: ColumnarSnapshot,
                           out_dtypes, dictionaries=None,
                           aux_cols=(), resident: bool = False):
        """Row-returning plan with the paging loop."""
        batches = self._stream_batches(root, snap, aux_cols)
        if batches is not None and resident:
            return None
        if batches is not None:
            # per-batch results concatenate; TopN/Limit callers already
            # re-trim the multi-device candidate union, batches just widen
            # that union
            parts = [self._execute_rows_once(root, b, out_dtypes,
                                             dictionaries, aux_cols)
                     for b in batches]
            return [Column.concat([p[j] for p in parts])
                    for j in range(len(out_dtypes))]
        n_dev = len(self.mesh.devices.reshape(-1))
        is_topn = isinstance(root, D.TopN)
        is_limit = isinstance(root, D.Limit)
        fb_key = D.dag_digest(root)
        per_shard = -(-snap.num_rows // max(snap.n_shards, 1)) \
            if snap.num_rows else 1
        # a capacity is a device's, and a device holds several shards
        per_dev = per_shard * -(-max(snap.n_shards, 1) // n_dev)
        if is_topn or is_limit:
            cap = max(root.limit, 16)
        else:
            with self._pf_mu:
                fb = self._page_feedback.get(fb_key)
            if fb is not None:
                # prior observation + 50% headroom, clamped to the
                # device's rows
                cap = _pow2_at_least(
                    max(int(per_dev * min(fb * 1.5, 1.0)) + 1, 256))
            else:
                cap = max(_pow2_at_least(
                    max(per_shard // INITIAL_SELECTIVITY, 1)), 1024)
            # copforge: a capacity the warm pool already compiled beats
            # the feedback guess — the paging loop's first launch hits
            # the pool instead of tracing a nearby-but-cold capacity
            cap = self._warm_cap(root, cap)

        cols, counts = snap.device_cols(self.mesh)
        if aux_cols:
            root = self._join_form(root)
        page_iters = 0       # published once, under _stat_mu, at the end
        for _ in range(10):  # paging: grow until fits
            page_iters += 1
            prog, out = self._launch(root, cols, counts, tuple(aux_cols),
                                     row_capacity=cap)
            missed = None
            if prog.has_extras:
                out, extras = out
                grown = self._grown_join_dag(root, extras)
                if grown is not None:
                    root = grown
                    continue
                missed = extras.get("join_window_miss")
            out_cols, out_counts = out
            if missed is not None \
                    and int(np.sum(np.asarray(self._fetch(missed)))):
                # a lookup read by windows lost a row: the gather form
                root = self._unwindowed(root)
                continue
            if prog.has_extras and "exchange_need" in extras:
                # a bucket of the join's exchange did not fit: larger
                _n, said = self._fetch(None, {
                    k: extras[k] for k in ("exchange_need",
                                           "exchange_sent")})
                grown = self._exchange_regrown(
                    root, int(np.max(said["exchange_need"])))
                if grown is not None:
                    root = grown
                    continue
            if is_topn or is_limit:
                break       # the capacity is the limit: nothing to regrow
            out_counts = np.asarray(self._fetch(out_counts))
            if (out_counts <= cap).all():
                break
            self._scheduler().count("rows_regrows")
            cap = self._warm_cap(root, _pow2_at_least(int(out_counts.max())))
        else:
            raise RuntimeError("paging loop did not converge")
        with self._stat_mu:
            self.last_page_iters = page_iters

        if not (is_topn or is_limit) and per_dev > 0:
            frac = float(out_counts.max()) / per_dev
            with self._pf_mu:
                old = self._page_feedback.get(fb_key, frac)
                self._page_feedback[fb_key] = 0.5 * old + 0.5 * frac
                self._page_feedback.move_to_end(fb_key)
                while len(self._page_feedback) > self._page_feedback_cap:
                    self._page_feedback.popitem(last=False)
        if resident:
            return out_cols, cap
        return self._assemble_rows(out_cols, out_counts, cap, out_dtypes,
                                   dictionaries)


__all__ = ["CopClient", "CopResult"]
