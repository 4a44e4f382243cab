"""CopTask: one admission unit of device work.

Reference analog: the request level of tikv's unified read pool +
tidb's copr task queue — every coprocessor launch becomes a queued,
taggable unit instead of an ad-hoc device call.  A task is either

- *structured*: carries (dag, mesh, row_capacity, device inputs) so the
  scheduler itself resolves the compiled program (parallel/spmd cache)
  and may COALESCE it with compatible tasks from other sessions — the
  continuous-batching admission unit, or
- *opaque*: a zero-arg launch closure (shuffle/window programs whose
  signatures differ); still admission-controlled and fair-ordered, never
  coalesced.

The task key tags (program digest, capacity shape, mesh) — the same key
`spmd.get_sharded_program` caches compiled programs on — so the
scheduler can recognize "same program in flight" across sessions.
"""

from __future__ import annotations

import contextvars
import threading
import time
from typing import Any, Callable, Optional

from ..obs.trace import TRACE_CTX as _TRACE_CTX

# the submitting statement's (resource group name, fair-share weight,
# rc ResourceGroup-or-None) — bound by Session.execute around each
# statement; travels into worker threads via contextvars.copy_context
# like KILL_EVENT does.  The third element is the live group object so
# the drain can consult the group's RU bucket (rc/controller) without a
# registry lookup; pre-rc 2-tuples are still accepted.
SCHED_GROUP: contextvars.ContextVar = contextvars.ContextVar(
    "sched_group", default=None)

DEFAULT_GROUP = "default"
DEFAULT_WEIGHT = 8.0


class ServerBusyError(RuntimeError):
    """Admission queue overflow: the MySQL-compatible "server is busy"
    backpressure error (TiDB error space 9003, ErrTiKVServerBusy) — the
    client should back off and retry instead of piling work onto an
    already-saturated device."""

    errno = 9003

    def __init__(self, depth: int):
        super().__init__(
            f"TiKV server is busy (device admission queue full, "
            f"depth={depth}); retry later")


class TaskCancelledError(RuntimeError):
    """The waiter was killed (KILL QUERY / connection teardown) while
    its task queued: the drain fails the lead with THIS typed error so
    the supervised retry layer — and clients — can tell cancellation
    from device failure.  Cancellation is never retried and never
    charges the program's circuit breaker."""

    def __init__(self):
        super().__init__("cop task cancelled before launch")


def current_group() -> tuple:
    """(group name, weight, rc group-or-None) of the calling statement
    context; 2-tuple bindings (pre-rc embedders) gain a None."""
    g = SCHED_GROUP.get()
    if not g:
        return DEFAULT_GROUP, DEFAULT_WEIGHT, None
    if len(g) == 2:
        return g[0], g[1], None
    return g


def _shape_sig(cols, counts) -> tuple:
    """Capacity-shape signature of the stacked device inputs: coalescing
    requires byte-identical program input shapes (the capacity half of
    the compile-cache key)."""
    sig = []
    for v, m in cols:
        sig.append((tuple(v.shape), str(v.dtype), m is None))
    return tuple(sig) + ((tuple(counts.shape),) if counts is not None
                         else ())


# id(mesh) -> fingerprint memo: id() here is only a transient cache slot
# for a live object we hold a reference to, never part of the key itself
_FP_CACHE: dict = {}


def mesh_fingerprint(mesh) -> tuple:
    """Stable identity of a device mesh: axis names, axis shape, and the
    global device ids.  Two Mesh objects over the same chips fingerprint
    identically, so task dedup/coalescing keys survive mesh rebuilds
    (a Domain re-creating its mesh after reconfig) — id(mesh) does not."""
    fp = _FP_CACHE.get(id(mesh))          # planlint: ok - memo slot only
    if fp is None:
        fp = (tuple(mesh.axis_names), tuple(mesh.devices.shape),
              tuple(int(d.id) for d in mesh.devices.reshape(-1)))
        if len(_FP_CACHE) > 16:           # meshes are few; stay tiny
            _FP_CACHE.clear()
        _FP_CACHE[id(mesh)] = fp          # planlint: ok - memo slot only
    return fp


class CopTask:
    """One queued device launch; resolved to (program, out) on wait()."""

    __slots__ = ("key", "dag", "mesh", "row_capacity", "cols", "counts",
                 "aux", "input_token", "fn", "group", "weight",
                 "submit_ns", "start_ns", "wait_ns", "coalesced", "fused",
                 "fusion_key", "cancelled", "_done", "_value", "_exc",
                 "est_rows", "cost", "cost_static", "rc_group", "rus",
                 "rus_charged", "device_ns", "deadline_ns", "svc_ns",
                 "donate", "retries", "compile_ns", "compile_miss",
                 "hbm_predicted", "hbm_measured", "value_drift", "trace",
                 "program", "epilogue", "finish_ns",
                 "enqueue_ns", "hold", "apart")

    def __init__(self, *, key=None, dag=None, mesh=None, row_capacity=0,
                 cols=None, counts=None, aux=(), input_token=None,
                 fusion_key=None, fn: Optional[Callable[[], Any]] = None,
                 group: Optional[str] = None,
                 weight: Optional[float] = None, est_rows: int = 0,
                 rc_group=None, donate: bool = False, program: str = ""):
        if group is None:
            group, gw, rcg = current_group()
            if weight is None:
                weight = gw
            if rc_group is None:
                rc_group = rcg
        self.key = key
        self.dag = dag
        self.mesh = mesh
        self.row_capacity = row_capacity
        self.cols = cols
        self.counts = counts
        self.aux = aux
        self.input_token = input_token
        self.fusion_key = fusion_key
        self.fn = fn
        self.program = program    # an opaque launch's program name (a
                                  # structured task's is its builder's)
        self.group = group
        self.weight = float(weight or DEFAULT_WEIGHT)
        self.est_rows = est_rows
        self.submit_ns = time.perf_counter_ns()
        self.enqueue_ns = self.submit_ns    # restamped after admission
        self.start_ns = 0
        self.wait_ns = 0
        self.coalesced = 1        # tasks served by this task's launch
        self.fused = 0            # member programs in this task's launch
        self.cost = None          # LaunchCost set at admission (copcost;
                                  # calibration-corrected when enabled)
        self.cost_static = None   # the uncorrected LaunchCost — the
                                  # calibration feedback baseline
                                  # (copmeter; never fed back on itself)
        self.rc_group = rc_group  # live rc ResourceGroup (bucket owner)
        self.rus = 1.0            # priced RUs, set at submit (rc/pricing)
        self.rus_charged = 0.0    # RUs actually debited at the drain
        self.device_ns = 0        # attributed share of launch wall time
        self.deadline_ns = 0      # rc max-queue deadline (0 = none)
        self.svc_ns = 0           # measured expected service time the
                                  # shedding backlog accounts (copmeter)
        self.donate = bool(donate)  # launch-unique inputs: donate them
        self.retries = 0          # transient-failure re-launches (drain)
        self.compile_ns = 0       # program resolve/compile time this
                                  # task's launch paid (copforge; 0 = warm)
        self.compile_miss = False  # launch compiled (vs warm-pool hit)
        self.hbm_predicted = 0    # admission HBM prediction (copgauge:
                                  # the calibrated peak_hbm_bytes the
                                  # budget gate enforced)
        self.hbm_measured = 0     # measured launch peak bytes, set by
                                  # the drain BEFORE finish (memory
                                  # stats delta / compiled analysis of
                                  # the served executable; 0 = none)
        self.value_drift = 0      # columns whose observed ANALYZE
                                  # watermark escaped the plan's
                                  # declared value interval (valueflow
                                  # stats drift — surfaced, never fatal)
        # copscope trace propagation (obs/): the submitting statement's
        # TraceCtx rides the task like SCHED_GROUP does, so the drain
        # thread records queue/compile/launch/retry spans under the
        # statement's dispatch span — None = untraced, zero overhead
        self.trace = _TRACE_CTX.get()
        # set by the drain for a traced task: its open sched.epilogue
        # span, and the stamp it took just before finish(), from which
        # the waiter's sched.wake runs
        self.epilogue = None
        self.finish_ns = 0
        # the micro-batch window's hold this task's batch sat through:
        # (start ns, end ns, riders gained), set by the drain; and
        # whether its group was served apart because the group's
        # program was not loaded
        self.hold = None
        self.apart = False
        self.cancelled = False
        self._done = threading.Event()
        self._value = None
        self._exc = None

    # -------- factory helpers -------- #

    @classmethod
    def structured(cls, dag, mesh, row_capacity, cols, counts, aux,
                   est_rows: int = 0, donate: bool = False) -> "CopTask":
        from ..copr.dag import dag_digest
        fp = mesh_fingerprint(mesh)
        sig = _shape_sig(cols, counts)
        # donation is baked into the compiled executable's input
        # aliasing, so the donating variant keys (and fuses) apart —
        # a donating and a non-donating task must never dedup together
        key = (dag_digest(dag), fp, int(row_capacity), sig, bool(donate))
        # input identity for in-flight dedup: the snapshot's resident
        # device cache returns the SAME array objects per epoch, so two
        # sessions over one snapshot share ids; the task pins the refs.
        # Identity is the POINT here (same buffers = one launch serves
        # both), so id() is correct, unlike in the persistent key above.
        token = (id(cols), id(counts), id(aux))    # planlint: ok - see above
        # cross-query fusion key (contract-aware, NO tracing): tasks
        # sharing one snapshot scan (same resident arrays = same epoch),
        # one mesh, and one capacity signature, whose chains are in the
        # fusable contract class, may compute their payloads in ONE
        # program even when their digests differ.
        fusion_key = None
        if aux == ():
            from ..analysis.contracts import fusion_signature
            fsig = fusion_signature(dag)
            if fsig is not None:
                fusion_key = (token, fp, sig, fsig, bool(donate))
        return cls(key=key, dag=dag, mesh=mesh, row_capacity=row_capacity,
                   cols=cols, counts=counts, aux=aux, input_token=token,
                   fusion_key=fusion_key, est_rows=est_rows,
                   donate=donate)

    @classmethod
    def opaque(cls, fn: Callable[[], Any], est_rows: int = 0,
               program: str = "") -> "CopTask":
        return cls(fn=fn, est_rows=est_rows, program=program)

    # -------- completion -------- #

    @property
    def done(self) -> bool:
        """Resolved (served or failed) — the supervised drain filters
        already-finished members out of a retried batch."""
        return self._done.is_set()

    @property
    def failed(self) -> bool:
        """Resolved WITH an error — failed launches must not feed the
        calibration loop (their wall time measures the failure path)."""
        return self._done.is_set() and self._exc is not None

    def finish(self, value) -> None:
        if self._done.is_set():
            return
        self._value = value
        self._done.set()

    def fail(self, exc: BaseException) -> None:
        if self._done.is_set():      # a served task keeps its result
            return
        self._exc = exc
        self._done.set()

    def wait(self):
        """Block until the scheduler serves this task.  Cooperative with
        KILL QUERY: polls the caller's kill event between waits; a killed
        waiter marks itself cancelled so the drain loop skips it."""
        from ..copr.coordinator import QueryInterrupted, check_killed
        while not self._done.wait(0.05):
            try:
                check_killed()
            except QueryInterrupted:
                self.cancelled = True
                raise
        if self.finish_ns:
            # sched.wake (tree only: a wait): the drain's finish stamp
            # -> this thread running again
            self.trace.add("sched.wake", self.finish_ns,
                           time.perf_counter_ns())
            self.finish_ns = 0      # once, whoever waits again
        if self._exc is not None:
            raise self._exc
        return self._value


__all__ = ["CopTask", "ServerBusyError", "TaskCancelledError",
           "SCHED_GROUP", "current_group", "DEFAULT_GROUP",
           "DEFAULT_WEIGHT", "mesh_fingerprint"]
