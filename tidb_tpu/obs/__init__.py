"""copscope (ISSUE 13): end-to-end observability for the async serving
stack.

- ``trace``: cross-thread trace propagation (``TraceCtx`` stamped onto
  CopTask at submit) + lock-protected per-statement span trees with
  explicit parent ids — the scheduler drain, copforge resolve, and
  client transfer/merge seams record real spans from their own threads.
- ``recorder``: bounded flight-recorder ring of completed query traces
  (failed/degraded/quarantined/retried/slow and a digest's outliers
  always kept, the rest sampled per digest), served at ``/trace`` + ``/trace/<id>`` with Chrome
  trace-event export (``?fmt=chrome``).

Latency histograms ride ``utils/metrics`` (label-aware prometheus-text
histograms) — ``tidb_tpu_sched_{wait,launch,compile}_ms`` and the
per-strategy agg launch histogram are wired at the scheduler drain.

copgauge (ISSUE 14) adds the memory/throughput axis:

- ``hbm``: the live per-mesh HBM ledger (persistent residents through
  the PR 7 weakref registry, launch-scoped bytes at admission/finish),
  measured launch watermarks, bounded device ``memory_stats``
  reconciliation, and the on-demand ``/profile`` capture gate.
  (A kernel's share of its roofline is the benchmark's to read, from
  the device's trace: ``benchmark/layer_metrics/*_roofline.py``.)
"""

from .hbm import (HbmLedger, all_ledgers, device_memory_stats,
                  hbm_status, ledger_for, profiler_gate)
from .recorder import FlightRecorder
from .trace import (TRACE_CTX, Span, SpanTree, TraceCtx, annotate,
                    current, flag, late_span, live, live_child,
                    new_trace_id, span)

__all__ = ["Span", "SpanTree", "TraceCtx", "TRACE_CTX", "current",
           "span", "late_span", "live", "live_child", "flag", "annotate",
           "new_trace_id", "FlightRecorder",
           "HbmLedger", "ledger_for", "all_ledgers", "hbm_status",
           "device_memory_stats", "profiler_gate"]
