"""copscope trace core: cross-thread trace propagation + per-statement
span trees.

Reference analog: pkg/util/tracing's StartRegionEx regions rendered by
the TRACE statement (executor/trace.go), grown to the Canopy/Dapper
shape the async stack needs — the statement path crosses seven thread
seams (admission queue, rc throttle, fusion window, copforge compile,
supervised launch, transfer, host merge) so a depth counter cannot
attribute them.  Here every span carries an
EXPLICIT parent id and the per-statement tree is lock-protected, so the
scheduler drain, copforge resolve, and client transfer seams record
real spans from their own threads and the session renderer stitches one
tree.

Propagation is contextvar + task-stamp:

- ``TRACE_CTX`` holds the session-side ``TraceCtx`` (tree + current
  span id); ``span(name)`` nests under it within one thread.
- ``CopTask`` captures ``current()`` at construction (same discipline
  as ``SCHED_GROUP``/``KILL_EVENT``), so the drain thread can record
  spans under the submitting statement's dispatch span via
  ``ctx.add(...)`` — no contextvar crosses the thread boundary.

Recording is deliberately cheap (one tuple append under the tree lock;
``add`` is the only hot-path entry) so tracing can stay on in
production.  ``tests/test_obs.py::test_tracing_overhead_guard`` bounds
the cost per span; what it costs a served statement on the chip
(copscope on against ``tidb_tpu_trace = 0``) is measured per PR and
written in PERF.md section 6.

One clock with the device: every live span also enters a
``jax.profiler.TraceAnnotation`` of the same name for its extent
(``trace_id`` and the span's attrs as its stats), so whoever profiles
the process — the benchmark's harness, or an operator through
``/profile?ms=N`` — finds the spans on ``/host:CPU``, on the line of the
thread that made them and on the clock of the device's ``XLA Ops``.
While no profiler session records, an annotation is a flag test in C++
(50 ns) and a shared null context.  The drain thread records its spans post hoc
(``add_batch``), so it brackets the work itself with ``live()``; waits
(``sched.queue``, ``sched.wake``) and containers that are all children
(``wire.stmt``) stay tree-only.

The tree is closed: a statement served over the wire is rooted at
``wire.stmt`` (the command's payload read -> the last ``sendall`` of its
result), and every stretch of the statement's own threads has a name
(``until_next`` brackets the ones that end where a callee's first span
begins).  What a container's children do not cover is its self-time;
the benchmark's ``server_unnamed_ms`` sums it.

``gc_ms``: the collector's runs of generation 1 and 2 are kept in a ring
of 64 (``gc.callbacks``); ``note_gc`` puts the milliseconds of those
that overlap a statement on its root span, and is called only for a
tree the recorder keeps as ``slow`` or ``outlier``.
"""

from __future__ import annotations

import contextvars
import gc
import itertools
import threading
import time
from collections import deque
from contextlib import ContextDecorator, contextmanager, nullcontext
from typing import Optional

from jax.profiler import TraceAnnotation as Annotation

# the active statement's TraceCtx; None = tracing off / no statement
TRACE_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "trace_ctx", default=None)

_TRACE_SEQ = itertools.count(1)


def new_trace_id(conn_id: int = 0) -> str:
    """Process-unique trace id: conn + monotonic sequence (readable in
    logs, stable enough for the flight-recorder index)."""
    return f"{conn_id:x}-{next(_TRACE_SEQ):06x}"


class Span:
    """One completed (or open) region.  ``parent_id`` is explicit —
    depth is DERIVED at render time, never tracked by a counter, so
    spans recorded out of order from other threads still nest right."""

    __slots__ = ("span_id", "parent_id", "name", "start_ns", "end_ns",
                 "thread", "attrs")

    def __init__(self, span_id: int, parent_id: Optional[int], name: str,
                 start_ns: int, end_ns: int = 0,
                 thread: str = "", attrs: Optional[dict] = None):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.thread = thread
        self.attrs = attrs or {}

    @property
    def duration_us(self) -> float:
        return (self.end_ns - self.start_ns) / 1e3


class SpanTree:
    """Lock-protected per-statement span collector.

    Every mutation takes ``_mu``; renders snapshot under it.  Span ids
    are tree-local ints; parent links make the tree — the session's
    root span is the statement, client dispatch spans hang under it,
    and scheduler-thread spans hang under the dispatch span whose
    ``TraceCtx`` rode the CopTask."""

    def __init__(self, trace_id: str = "", sql: str = "", conn_id: int = 0):
        self.trace_id = trace_id or new_trace_id(conn_id)
        self.sql = sql
        self.conn_id = conn_id
        self.t0 = time.perf_counter_ns()
        self.wall_start = time.time()
        self.latency_ms = 0.0
        self.flags: set = set()       # failed/degraded/quarantined/
                                      # retried/slow/outlier — recorder
                                      # retention
        self.nth = 0                  # the statement's place in its
                                      # digest's count, where that is
                                      # what admitted it to the recorder
        self.spans: list[Span] = []
        # the session.ExecuteStmt span's id (``until_next(root_only=)``)
        self.exec_root: Optional[int] = None
        # an ``until_next`` span still open: (span, annotation, thread id)
        self.pending: Optional[tuple] = None
        self._mu = threading.Lock()
        self._next = 0

    # ---- recording (any thread) ---------------------------------- #

    def add(self, name: str, start_ns: int, end_ns: int,
            parent_id: Optional[int] = None, **attrs) -> int:
        """Record one COMPLETED span — the cross-thread hot path (the
        drain records post-measurement, pre-``finish``, so a waiter
        rendering the tree always sees its scheduler spans)."""
        with self._mu:
            sid = self._next = self._next + 1
            self.spans.append(Span(
                sid, parent_id, name, start_ns, end_ns,
                thread=threading.current_thread().name, attrs=attrs))
            return sid

    def open(self, name: str, parent_id: Optional[int],
             attrs: dict, start_ns: int = 0) -> Span:
        """Record an OPEN span starting now (or at ``start_ns``) and
        hand it back: its owner sets ``end_ns`` on it (``span()``'s
        path: no search on exit)."""
        sp = Span(0, parent_id, name,
                  start_ns or time.perf_counter_ns(), 0,
                  thread=threading.current_thread().name, attrs=attrs)
        with self._mu:
            sp.span_id = self._next = self._next + 1
            self.spans.append(sp)
        return sp

    def add_batch(self, items: list) -> list[int]:
        """Record several completed spans in ONE lock acquisition —
        the drain's per-launch recording path (queue + launch +
        compile + fusion per task would otherwise take the lock four
        times at the scheduler's serialization point).

        ``items``: ``(name, start_ns, end_ns, parent, attrs)`` tuples;
        ``parent`` is a span id, None, or ``("rel", i)`` referring to
        the i-th span OF THIS BATCH (the launch->compile nesting)."""
        thread = threading.current_thread().name
        out: list[int] = []
        with self._mu:
            for name, start_ns, end_ns, parent, attrs in items:
                if isinstance(parent, tuple):
                    parent = out[parent[1]]
                sid = self._next = self._next + 1
                self.spans.append(Span(sid, parent, name, start_ns,
                                       end_ns, thread=thread,
                                       attrs=attrs))
                out.append(sid)
        return out

    def begin(self, name: str, parent_id: Optional[int] = None,
              **attrs) -> int:
        return self.add(name, time.perf_counter_ns(), 0,
                        parent_id, **attrs)

    def end(self, span_id: int, **attrs) -> None:
        now = time.perf_counter_ns()
        with self._mu:
            for sp in reversed(self.spans):
                if sp.span_id == span_id:
                    sp.end_ns = now
                    if attrs:
                        sp.attrs.update(attrs)
                    return

    def flag(self, *names: str) -> None:
        with self._mu:
            self.flags.update(names)

    def annotate(self, span_id: int, **attrs) -> None:
        with self._mu:
            for sp in reversed(self.spans):
                if sp.span_id == span_id:
                    sp.attrs.update(attrs)
                    return

    # ---- rendering ------------------------------------------------ #

    def _snapshot(self) -> list[Span]:
        with self._mu:
            return list(self.spans)

    def ordered(self) -> list[tuple[Span, int]]:
        """(span, depth) depth-first, children ordered by start time —
        the TRACE result-set order.  Orphan parents (span recorded
        before its parent — impossible today, defensive) render at
        root depth rather than vanish."""
        spans = self._snapshot()
        ids = {sp.span_id for sp in spans}
        kids: dict = {}
        roots: list = []
        for sp in spans:
            if sp.parent_id is not None and sp.parent_id in ids:
                kids.setdefault(sp.parent_id, []).append(sp)
            else:
                roots.append(sp)
        out: list = []

        def walk(sp: Span, depth: int) -> None:
            out.append((sp, depth))
            for ch in sorted(kids.get(sp.span_id, ()),
                             key=lambda s: (s.start_ns, s.span_id)):
                walk(ch, depth + 1)

        for sp in sorted(roots, key=lambda s: (s.start_ns, s.span_id)):
            walk(sp, 0)
        return out

    def rows(self) -> list[tuple]:
        """TRACE renderer rows: (indented name [attrs], start_us_rel,
        duration_us)."""
        out = []
        for sp, depth in self.ordered():
            end = sp.end_ns or sp.start_ns
            label = "  " * depth + sp.name
            if sp.attrs:
                kv = ", ".join(f"{k}={_fmt(v)}"
                               for k, v in sorted(sp.attrs.items()))
                label += f" {{{kv}}}"
            out.append((label,
                        round((sp.start_ns - self.t0) / 1e3, 1),
                        round((end - sp.start_ns) / 1e3, 1)))
        return out

    def to_dict(self) -> dict:
        """Flight-recorder / ``/trace/<id>`` JSON shape."""
        return {
            "trace_id": self.trace_id,
            "conn_id": self.conn_id,
            "sql": self.sql,
            "start_ts": self.wall_start,
            "latency_ms": round(self.latency_ms, 3),
            "flags": sorted(self.flags),
            "nth": self.nth,
            "spans": [{
                "id": sp.span_id, "parent": sp.parent_id,
                "name": sp.name, "thread": sp.thread,
                "start_us": round((sp.start_ns - self.t0) / 1e3, 1),
                "duration_us": round(
                    ((sp.end_ns or sp.start_ns) - sp.start_ns) / 1e3, 1),
                "attrs": {k: _json_safe(v)
                          for k, v in sorted(sp.attrs.items())},
            } for sp, _d in self.ordered()],
        }

    def chrome_trace(self) -> dict:
        """Chrome trace-event / Perfetto JSON (``?fmt=chrome``): one
        complete ("ph": "X") event per span, tids = recording threads
        so the cross-thread seams are visible as separate tracks."""
        tids: dict = {}
        events = []
        for sp, _d in self.ordered():
            tid = tids.setdefault(sp.thread, len(tids) + 1)
            end = sp.end_ns or sp.start_ns
            events.append({
                "name": sp.name, "ph": "X", "pid": 1, "tid": tid,
                "ts": round((sp.start_ns - self.t0) / 1e3, 3),
                "dur": round((end - sp.start_ns) / 1e3, 3),
                "cat": sp.name.split(".", 1)[0],
                "args": {k: _json_safe(v)
                         for k, v in sorted(sp.attrs.items())},
            })
        meta = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                 "args": {"name": thread}}
                for thread, tid in sorted(tids.items(), key=lambda kv: kv[1])]
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms",
                "otherData": {"trace_id": self.trace_id, "sql": self.sql}}


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.3f}"
    return str(v)


def _json_safe(v):
    if isinstance(v, (int, float, str, bool)) or v is None:
        return v
    return str(v)


class TraceCtx:
    """Propagation unit: (tree, parent span id).  Stamped onto CopTask
    at submit; the drain records under ``span_id`` from its own thread.
    The trace id lives on the tree — one per statement."""

    __slots__ = ("tree", "span_id")

    def __init__(self, tree: SpanTree, span_id: Optional[int] = None):
        self.tree = tree
        self.span_id = span_id

    @property
    def trace_id(self) -> str:
        return self.tree.trace_id

    def add(self, name: str, start_ns: int, end_ns: int, **attrs) -> int:
        """Record a completed child span from ANY thread."""
        return self.tree.add(name, start_ns, end_ns,
                             parent_id=self.span_id, **attrs)

    def child(self, span_id: int) -> "TraceCtx":
        return TraceCtx(self.tree, span_id)


def current() -> Optional[TraceCtx]:
    """The calling thread's active trace context (None = untraced)."""
    return TRACE_CTX.get()


class span(ContextDecorator):
    """Session-side nested region: opens a child span under the active
    context and re-points ``TRACE_CTX`` at it for the dynamic extent,
    so tasks submitted inside hang under THIS span; while a profiler
    session records, the annotation of the same name covers the same
    extent.  A no-op (yields None) when tracing is off — callers never
    branch.  Also a decorator: ``@span("session.plan")``.

    A class and not a generator, and it sets the end on the ``Span`` it
    holds: a served statement opens a dozen of these, and this form
    costs half of what ``contextmanager`` + ``tree.end`` did."""

    __slots__ = ("name", "attrs", "_sp", "_tok", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self._sp = None

    def _recreate_cm(self):         # one instance per decorated call
        return span(self.name, **self.attrs)

    def __enter__(self) -> Optional[TraceCtx]:
        ctx = TRACE_CTX.get()
        if ctx is None:
            return None
        tree = ctx.tree
        if tree.pending is not None:
            close_pending(tree)
        sp = self._sp = tree.open(self.name, ctx.span_id, self.attrs)
        sub = TraceCtx(tree, sp.span_id)
        self._tok = TRACE_CTX.set(sub)
        self._ann = annotation(self.name, tree.trace_id, **self.attrs)
        self._ann.__enter__()
        return sub

    def __exit__(self, *exc) -> bool:
        sp = self._sp
        if sp is not None:
            self._ann.__exit__(*exc)
            TRACE_CTX.reset(self._tok)
            sp.end_ns = time.perf_counter_ns()
        return False


_NO_ANNOTATION = nullcontext()


def annotation(name: str, trace_id: str = "", **attrs):
    """The profiler annotation of a span, to be entered for its extent;
    a shared null context while no profiler session records (that test
    costs 50 ns; an annotation nobody records, half a microsecond)."""
    if not Annotation.is_enabled():
        return _NO_ANNOTATION
    return Annotation(name, trace_id=trace_id, **attrs)


def until_next(name: str, root_only: bool = False) -> None:
    """Open a live span under the active context that ends where the
    next ``span()`` of this thread begins (or at ``close_pending``): a
    stretch of the caller's own work whose end is a callee's first span
    (``session.inputs``: plan built -> the first ``cop.*``;
    ``session.outputs``: a cop task's columns returned -> the next
    span).  It does not become the parent of what follows.
    ``root_only``: only directly under ``session.ExecuteStmt`` (a cop
    task run inside ``cop.join_build`` leaves that span's self-time
    alone).  No-op when untraced."""
    ctx = TRACE_CTX.get()
    if ctx is None:
        return
    tree = ctx.tree
    if root_only and ctx.span_id != tree.exec_root:
        return
    if tree.pending is not None:
        close_pending(tree)
        if tree.pending is not None:    # another thread's: leave both
            return
    ann = annotation(name, tree.trace_id)
    sp = tree.open(name, ctx.span_id, {})
    ann.__enter__()
    tree.pending = (sp, ann, threading.get_ident())


def end_pending() -> None:
    """End the active tree's ``until_next`` span here, where no span
    begins (``session.enter`` ends at the statement's dispatch)."""
    ctx = TRACE_CTX.get()
    if ctx is not None and ctx.tree.pending is not None:
        close_pending(ctx.tree)


def close_pending(tree: SpanTree, force: bool = False) -> None:
    """End the tree's ``until_next`` span, on the thread that opened it
    (another thread's spans leave it open: an annotation is left where
    it was entered).  ``force``: the statement is over, end it whoever
    opened it (a pool worker's is left to its annotation's destructor)."""
    p = tree.pending
    if p is None:
        return
    mine = p[2] == threading.get_ident()
    if mine or force:
        tree.pending = None
        if mine:
            p[1].__exit__(None, None, None)
        p[0].end_ns = time.perf_counter_ns()


@contextmanager
def late_span(tree: Optional[SpanTree], name: str,
              parent_id: Optional[int] = None):
    """A span on a statement's tree after ``Session.execute`` returned
    and the recorder took the tree (it holds a reference; ``add`` is
    lock-protected): the connection's ``wire.write``, under the
    statement's ``wire.stmt``.  Yields the span's attrs, for what is
    known only once the body ran.  ``tree`` None = untraced = no-op."""
    attrs: dict = {}
    if tree is None:
        yield attrs
        return
    t0 = time.perf_counter_ns()
    try:
        with annotation(name, tree.trace_id):
            yield attrs
    finally:
        tree.add(name, t0, time.perf_counter_ns(), parent_id=parent_id,
                 **attrs)


def leaf(name: str):
    """A live span under the active context that does NOT re-point
    ``TRACE_CTX``: nothing nests under it, and a ``CopTask`` built
    inside still captures the enclosing span (``sched.task``)."""
    ctx = TRACE_CTX.get()
    if ctx is None:
        return _NO_ANNOTATION
    return late_span(ctx.tree, name, ctx.span_id)


# the TraceCtx whose live() annotation is open on this thread
_LIVE = threading.local()


@contextmanager
def live(name: str, ctx: Optional[TraceCtx], **attrs):
    """Profiler annotation alone, for a thread that records its tree
    spans post hoc (the drain's ``sched.launch``): brackets the work the
    span will describe.  ``ctx`` None = untraced = no-op."""
    if ctx is None:
        yield
        return
    prev = getattr(_LIVE, "ctx", None)
    _LIVE.ctx = ctx
    try:
        with annotation(name, ctx.trace_id, **attrs):
            yield
    finally:
        _LIVE.ctx = prev


def live_child(name: str, **attrs):
    """Annotation nested in this thread's open ``live()`` (the compile
    or cache load inside a launch); a null context outside one."""
    ctx = getattr(_LIVE, "ctx", None)
    if ctx is None:
        return _NO_ANNOTATION
    return annotation(name, ctx.trace_id, **attrs)


def flag(*names: str) -> None:
    """Mark the active trace (quarantined/degraded/...); no-op when
    untraced."""
    ctx = TRACE_CTX.get()
    if ctx is not None:
        ctx.tree.flag(*names)


def annotate(**attrs) -> None:
    """Attach attrs to the active span; no-op when untraced."""
    ctx = TRACE_CTX.get()
    if ctx is not None and ctx.span_id is not None:
        ctx.tree.annotate(ctx.span_id, **attrs)


# ---- the collector's runs, for the trees that are kept as slow ---- #

GC_RING = 64
# a tree kept for being slow carries the collector's share of its time
GC_FLAGS = frozenset({"slow", "outlier"})
# (start_ns, end_ns, generation) of the last runs of generation 1 or 2
_GC_RUNS: deque = deque(maxlen=GC_RING)
_gc_t0 = [0]


def _gc_callback(phase: str, info: dict) -> None:
    if info["generation"] == 0:     # a young run: microseconds, and
        return                      # several a statement
    if phase == "start":
        _gc_t0[0] = time.perf_counter_ns()
    elif _gc_t0[0]:
        _GC_RUNS.append((_gc_t0[0], time.perf_counter_ns(),
                         info["generation"]))
        _gc_t0[0] = 0


gc.callbacks.append(_gc_callback)


def gc_overlap_ms(start_ns: int, end_ns: int) -> float:
    """Milliseconds of the remembered collector runs inside
    [start_ns, end_ns]."""
    return sum(max(min(b, end_ns) - max(a, start_ns), 0)
               for a, b, _gen in list(_GC_RUNS)) / 1e6


def note_gc(tree: SpanTree) -> None:
    """``gc_ms`` on the root span of a tree flagged slow or outlier (any
    other tree is left alone: nothing reads the ring for it): the
    collector's runs from the root's start until now.  The recorder
    calls it when it keeps the tree, the connection again when the
    result is written."""
    with tree._mu:
        root = tree.spans[0] if tree.spans and tree.flags & GC_FLAGS \
            else None
    if root is not None:
        root.attrs["gc_ms"] = round(
            gc_overlap_ms(root.start_ns, time.perf_counter_ns()), 3)


__all__ = ["Span", "SpanTree", "TraceCtx", "TRACE_CTX", "current",
           "span", "late_span", "leaf", "until_next", "end_pending",
           "close_pending", "live",
           "live_child", "annotation", "flag", "annotate",
           "new_trace_id", "note_gc", "gc_overlap_ms", "GC_FLAGS"]
