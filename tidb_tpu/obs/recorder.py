"""copscope flight recorder: bounded ring of completed query traces.

Reference analog: TiDB's continuous-profiling/Top-SQL direction — keep
enough recent per-query evidence in memory that the question "what did
that slow/failed statement actually spend its time on?" is answerable
AFTER the fact, without re-running anything.

Retention contract (tested):

- Interesting traces are ALWAYS admitted: any trace flagged ``failed``,
  ``degraded``, ``quarantined``, ``retried``, ``slow`` (slower than
  ``tidb_tpu_slow_threshold_ms``) or ``outlier`` (slower than its own
  digest: over ``OUTLIER_X`` times the digest's running mean and at
  least ``OUTLIER_MIN_MS`` over it; the mean is ``StmtSummary``'s, of
  the digest's last complete block of 32 executions, so a digest with
  fewer is exempt; the recorder sets the flag).
- Ordinary traces are SAMPLED 1-in-``sample_every`` PER DIGEST: the one
  whose place in its digest's count (``StmtSummary``'s ``exec_count``,
  handed in as ``nth``) is 1 mod ``sample_every`` is kept, so a rare
  digest is not starved behind a common one and a mix whose period
  divides the cadence still leaves every class in the ring.  A trace
  offered without a place (``nth`` 0: no digest was counted) falls
  back to its place in the recorder's own count.
- A ``slow`` or ``outlier`` tree carries ``gc_ms`` on its root (the
  collector's runs that overlap the statement, ``trace.note_gc``).
- The ring is provably bounded: one deque(maxlen=capacity) holds
  everything — admission decides what enters, the ring bounds what
  stays.  No unbounded always-keep side list.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Optional

from .trace import GC_FLAGS, SpanTree, note_gc

DEFAULT_CAPACITY = 512     # a 51 s window of 12 ms pairs samples 260
DEFAULT_SAMPLE_EVERY = 16

# flags that force admission regardless of the sampling cadence
KEEP_FLAGS = frozenset(
    {"failed", "degraded", "quarantined", "retried", "slow", "outlier"})
# an outlier of its digest: 1.5 and not more because the stalls to be
# caught add 75-120 ms to statements of 10-110 ms (PERF.md section 7)
OUTLIER_X = 1.5
OUTLIER_MIN_MS = 1.0


class FlightRecorder:
    """Bounded ring of completed statement traces (``SpanTree``)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 sample_every: int = DEFAULT_SAMPLE_EVERY):
        self.capacity = max(int(capacity), 1)
        self.sample_every = max(int(sample_every), 1)
        self._ring: deque = deque(maxlen=self.capacity)
        self._mu = threading.Lock()
        self._seen = 0           # completed traces offered (lifetime)
        self.recorded = 0        # admitted to the ring (lifetime)
        self.sampled_out = 0     # ordinary traces the cadence skipped
        self.outliers = 0        # traces flagged ``outlier`` (lifetime)

    def record(self, tree: SpanTree, nth: int = 0,
               mean_ms: float = 0.0) -> bool:
        """Offer one completed trace; True = admitted to the ring.
        ``nth``: the statement's place in its digest's count, from 1;
        ``mean_ms``: the digest's running mean before it (0: it has
        none yet)."""
        if mean_ms > 0 and tree.latency_ms > max(
                OUTLIER_X * mean_ms, mean_ms + OUTLIER_MIN_MS):
            tree.flag("outlier")
        flagged = tree.flags & KEEP_FLAGS
        with self._mu:
            self._seen += 1
            if "outlier" in flagged:
                self.outliers += 1
            keep = bool(flagged) or self.sample_every == 1 \
                or ((nth or self._seen) % self.sample_every) == 1
            if not keep:
                self.sampled_out += 1
                return False
            self.recorded += 1
            self._ring.append(tree)
        if not flagged:
            tree.nth = nth
        note_gc(tree)
        return True

    def get(self, trace_id: str) -> Optional[SpanTree]:
        with self._mu:
            for tree in reversed(self._ring):
                if tree.trace_id == trace_id:
                    return tree
        return None

    def index(self) -> list[dict]:
        """Newest-first trace summaries — the ``/trace`` listing."""
        with self._mu:
            trees = list(self._ring)
        return [{
            "trace_id": t.trace_id,
            "conn_id": t.conn_id,
            "sql": t.sql[:200],
            "start_ts": t.wall_start,
            "latency_ms": round(t.latency_ms, 3),
            "flags": sorted(t.flags),
            "nth": t.nth,
            "spans": len(t.spans),
        } for t in reversed(trees)]

    def stats(self) -> dict:
        with self._mu:
            return {"capacity": self.capacity,
                    "sample_every": self.sample_every,
                    "size": len(self._ring),
                    "seen": self._seen,
                    "recorded": self.recorded,
                    "sampled_out": self.sampled_out,
                    "outliers": self.outliers}

    def clear(self) -> None:
        with self._mu:
            self._ring.clear()

    def __len__(self) -> int:
        with self._mu:
            return len(self._ring)


__all__ = ["FlightRecorder", "KEEP_FLAGS", "GC_FLAGS", "DEFAULT_CAPACITY",
           "DEFAULT_SAMPLE_EVERY", "OUTLIER_X", "OUTLIER_MIN_MS"]
